"""The `brumby` family in the benchmark: its tiny cell through
rehearse_brumby.py (the serving cell's whole control flow on the CPU backend;
the family's own manifest rehearsal_brumby.json, since rehearsal.json is the
benchmark's and not a model PR's to edit), the metrics this family brought,
read from a hand-made ring and a hand-made reduced trace (the state's bytes
a step and the state commit under the cell's own names, the two shares of a
peak through readers/span_need.py, the two scopes' shares of their rooflines
through readers/scope_roofline.py over the prefill and over the decode
program), the parameter count, and how tight the comparisons are: an fp8
engine through the cell's served-token rule (control.py) and the logits check
with its four wrong references (logits_check_brumby.py), both at the tiny
size. Nothing here times anything. New entries of the manifest are found by
membership and ordered by index: nothing here asserts that an entry is the
last, or how many there are."""

import json
import subprocess
import sys
import types

import pytest

from test_granitemoehybrid_rehearsal import (BENCH, CPU_ENV, MS, ROOT, SKEW,
                                             Ring, Run, read)

from harness import flops_brumby as flops  # noqa: E402
from harness import manifest as mf  # noqa: E402
from harness import peaks  # noqa: E402
from harness.trace_reduce import Op, Trace  # noqa: E402

CONFIG = "Brumby-14B-Base"
CELL = CONFIG + ".serve-longanswer"
TINY = "brumby-tiny.serve"
NEW = ["prefill_mfu.brumby", "decode_step_hbm_roofline.brumby",
       "retention_scan_roofline.brumby", "retention_step_roofline.brumby",
       "wave_linear_attention_device_ms.brumby",
       "decode_linear_attention_device_ms_per_step.brumby",
       "linear_state_mb_per_step.decode.brumby", "state_commit_ms.brumby"]
REHEARSAL = "rehearsal_brumby.json"
FIVE = ["gpt2-medium.serve-chat", "granite-4.0-h-small.serve-chat",
        "GigaChat3.1-702B-A36B.serve-chat",
        "NVIDIA-Nemotron-3-Super-120B-A12B-BF16.serve-chat",
        "Ling-3.0-flash.serve-chat"]
STATE = 6 * 8 * 8256 * 129 * 4          # a slot's state, all layers


def test_every_cell_config_and_reader_of_the_familys_manifest_is_found_by_name():
    from test_benchmark_harness import (
        test_every_cell_config_and_reader_is_found_by_name as found_by_name)

    found_by_name(BENCH / REHEARSAL)
    manifest = mf.load_manifest(BENCH / REHEARSAL)
    real = {w["name"] for w in mf.load_manifest()["workloads"]}
    assert [w["stands_for"] for w in manifest["workloads"]] == [CELL]
    assert CELL in real
    # the benchmark's own rehearsal manifest is as it was
    assert "brumby" not in (BENCH / "rehearsal.json").read_text()


def test_rehearsal_runs_the_cells_control_flow_and_reports_no_metric():
    done = subprocess.run(
        [sys.executable, str(BENCH / "rehearse_brumby.py"), "--workload",
         TINY, "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=CPU_ENV, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
    # every metric the cell lists is reported
    cell = mf.load_cell(mf.load_manifest(), CELL)
    assert set(NEW) <= set(last["would_report"])
    assert set(last["would_report"]) == {m["name"] for m in cell.per_layer}
    # no layer of this model pages, mixes or routes
    assert not {"wave_attention_device_ms", "decode_attention_device_ms_per_step",
                "wave_mixer_device_ms", "wave_experts_device_ms",
                "moe_rows_computed_share.prefill", "state_commit_ms",
                "linear_state_mb_per_step.decode.ling"} \
        & set(last["would_report"])


def _tool(script, *args, rehearsal=("--rehearsal",)):
    done = subprocess.run(
        [sys.executable, str(BENCH / script), *rehearsal, "--workload", TINY,
         *args],
        cwd=ROOT, env=CPU_ENV, capture_output=True, text=True, timeout=900)
    lines = [json.loads(l) for l in done.stdout.strip().splitlines()]
    return done, lines


def test_an_fp8_engine_goes_through_the_cells_rule():
    """control.py's flow (family-neutral, as it is): per seed a sound and a
    lowered window, each judged by cells/serve.py's parity against the
    weights as initialised. The tiny cell computes in float32 (its workload
    file says why), so the sound engine reads 0; the chip run at the
    published widths must come out `tight` (PERF.md has its readings)."""
    done, lines = _tool("control.py", "--seeds", f"7,{2 ** 31 + 11}",
                        "--seconds", "2", rehearsal=("--rehearsal", REHEARSAL))
    windows = [l for l in lines if l.get("fact") == "control_window"]
    assert [(w["seed"], w["engine"]) for w in windows] == [
        (7, "sound"), (7, "low"), (2 ** 31 + 11, "sound"), (2 ** 31 + 11, "low")]
    assert all(w["ok"] for w in windows if w["engine"] == "sound")
    last = lines[-1]
    assert done.returncode == 0 and last["tight"] is True
    assert last["sound_worst_gap_bf16_ulps"] < 1 < 8 < last["low_worst_gap_bf16_ulps"]


def test_the_logits_check_parts_the_program_from_four_wrong_references():
    """Prefill through the program the scheduler runs, then decode through
    the per-slot state, against the reference's full forward (the pair
    form), on logits: the float32 tiny program within 1e-4 of the scale (the
    order of its sums); the reference with fp8 weights, with a bfloat16
    state, without the normaliser and with every gate a tenth off far
    outside it."""
    done, lines = _tool("logits_check_brumby.py", "--seeds",
                        f"5,{2 ** 31 + 11}", "--steps", "12",
                        "--tolerance", "1e-4")
    assert done.returncode == 0, done.stderr[-2000:]
    last = lines[-1]
    assert last["holds"] is True and last["report_only"] == []
    wrong = last["wrong_mean_diff_over_scale"]
    assert set(wrong) == {"fp8_reference", "bf16_state_reference",
                          "no_normaliser_reference", "gate_reference"}
    assert last["program_mean_diff_over_scale"] < 1e-4 < 5e-4 \
        < min(wrong.values())
    assert all(m > 5 for m in last["margin_over_program"].values())
    assert last["program_served_gap_ulps"] == 0 < last["served_gap_limit_ulps"] \
        < last["fp8_served_gap_ulps"]
    served = [l["served"] for l in lines if l.get("fact") == "logits"]
    assert all(s["over_8_ulps"] == 0 and s["tokens"] == 4 * 13 for s in served)
    assert lines[0]["state_in_place"] is False     # the tiny state: committed


def test_the_cell_lists_every_metric_it_reports():
    manifest = mf.load_manifest()
    cell = mf.load_cell(manifest, CELL)
    assert cell.chips == 1 and cell.traffic_name == "serve-longanswer"
    assert cell.system["max_batch_slots"] == 16
    assert cell.system["max_decode_len"] == 512 and cell.system["kv_page_size"] == 16
    assert cell.system["ffconfig"] == {"compute_dtype": "bfloat16",
                                       "mesh_shape": {"data": 1}}
    # the cell's own rate over the traffic file's placeholder
    assert cell.traffic["rate_rps"] == cell.system["traffic"]["rate_rps"] != 1.0
    assert cell.traffic["shape_seed"] == 24
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names
    assert {"decode_step_device_ms", "prefill_device_ms", "device_idle.serve",
            "op_scope_unattributed.serve", "trace_lower_s",
            "backend_compile_s", "search_s", "compile_s"} <= names
    assert not {n for n in names if n.startswith(("moe_", "wave_attention",
                                                  "decode_attention",
                                                  "wave_mixer", "wave_experts",
                                                  "decode_experts",
                                                  "latent_cache"))}
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    assert all(per_layer[name]["workloads"] == [CELL] for name in NEW)
    assert all(per_layer[n]["unit"] == "%" for n in NEW if "roofline" in n
               or "mfu" in n)
    # membership, and order by index: the new entries come after what was
    # there, in the order ISSUE 43 lists them
    order = [m["name"] for m in manifest["per_layer"]]
    assert [order.index(n) for n in NEW] == sorted(order.index(n) for n in NEW)
    assert order.index(NEW[0]) > order.index("state_commit_ms.ling")
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) > max(cells.index(c) for c in FIVE)
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    # every serving metric the five other serving cells report, this one
    # too, appended behind them: but for the attention layers' two
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        listed = m.get("workloads", [])
        if set(FIVE) <= set(listed) and "attention" not in m["name"]:
            assert CELL in listed and listed.index(CELL) > max(
                listed.index(c) for c in FIVE), m["name"]
    configs = [c["name"] for c in manifest["configs"]]
    entry = manifest["configs"][configs.index(CONFIG)]
    cfg = cell.config
    assert entry["source"] == cfg["source"] and cfg["reduced"] == entry["reduced"] \
        == ["num_hidden_layers"]
    assert len(manifest["workloads"][cells.index(CELL)]["why"]) <= 200 \
        and len(entry["why"]) <= 200
    # the parameter count, to the parameter, and at the published depth
    assert flops.param_count(cfg) == 3537947136
    assert round(flops.param_count(cfg, layers=40) / 1e9, 2) == 14.77
    # what the device holds: over the floor of a quarter of the chip
    held = 2 * flops.param_count(cfg) + 16 * flops.state_bytes_per_slot(cfg)
    assert flops.state_bytes_per_slot(cfg) == STATE
    assert 0.64 < held / peaks.peaks_for("TPU v5 lite")["hbm_bytes"] < 0.66


def cell():
    return mf.load_cell(mf.load_manifest(), CELL)


def traced_serving(monkeypatch, with_counters=True):
    """The window's run (profiler off), then a traced run of 1 s from
    20 000 ms whose clock in the trace is SKEW ahead: one wave, one decode
    window of two steps with 8 live slots."""
    def moved(steps, live):
        return {"linear_state_bytes": steps * 2.0 * live * STATE} \
            if with_counters else {}

    r = Ring()
    win = r.add("serve/run", 1000, 5000, requests=9)
    r.add("serve/prefill/commit_state", 1500, 1500.002, parent=win.id, bytes=0)
    r.add("serve/prefill/commit_state", 2500, 2500.004, parent=win.id, bytes=0)
    r.add("serve/decode/window_sync", 3000, 3010, parent=win.id, window=1,
          steps=4, **moved(4, 16))
    r.add("serve/decode/window_sync", 3100, 3110, parent=win.id, window=2,
          steps=2, **moved(2, 8))
    t0 = 20_000
    root = r.add("serve/run", t0, t0 + 2000, requests=2)
    a = r.add("serve/admit", t0 + 100, t0 + 1100, parent=root.id, wave=1,
              requests=4, prompt_tokens=640, padded_tokens=16384)
    wave = {"retention_layers": 6, "retention_rows": 6 * 4,
            "state_written_bytes": 4.0 * STATE} if with_counters else {}
    r.add("serve/prefill/device_wait", t0 + 150, t0 + 1050, parent=a.id, **wave)
    for k in range(2):
        r.add("serve/decode/dispatch", t0 + 1200 + 20 * k, t0 + 1202 + 20 * k,
              parent=root.id, window=1)
    r.add("serve/decode/window_sync", t0 + 1240, t0 + 1250, parent=root.id,
          window=1, steps=2, **moved(2, 8))
    r.install(monkeypatch)

    def at(ms):
        return int((t0 + ms) * MS) + SKEW
    # the wave: 800 ms of device time, 150 of them the sequence form's two
    # fusions; a step: 14 ms, 6 of them the recurrence's one
    ops = [Op("fusion.1", at(160), at(810)), Op("fusion.7", at(810), at(900)),
           Op("fusion.8", at(900), at(960)),
           Op("fusion.3", at(1201), at(1209)), Op("fusion.5", at(1209), at(1215)),
           Op("fusion.4", at(1221), at(1229)), Op("fusion.6", at(1229), at(1235))]
    host = [Op("bench/traced_run", at(-5), at(2000))]
    return Run(cell(), trace=Trace({0: ops}, host), window=(at(0), at(2000)))


def test_commit_and_state_bytes_from_the_windows_ring(monkeypatch):
    run = traced_serving(monkeypatch)
    assert read(run, "state_commit_ms.brumby") == pytest.approx(0.003)
    # 16 live slots in four steps, 8 in two: 409 MB a live slot a step
    got = read(run, "linear_state_mb_per_step.decode.brumby")
    assert got == pytest.approx((4 * 16 + 2 * 8) / 6 * 2 * STATE / 1e6)
    assert 2 * STATE / 1e6 == pytest.approx(408.97, abs=0.01)


def test_shares_of_the_peaks_from_the_traced_runs_spans(monkeypatch):
    run = traced_serving(monkeypatch)
    c = run.cell
    # one decode window of two steps, 14 ms of device time each
    got = read(run, "decode_step_hbm_roofline.brumby")
    need = flops.decode_step_need(c.config, c.system, c.traffic,
                                  {"linear_state_bytes": 2.0 * 8 * STATE})
    assert got == pytest.approx(100 * need["bytes"] / 819e9 / 14e-3)
    assert 75 < got < 80
    note = run.notes[-1]
    assert note["bound"] == "memory" and note["per"] == 2
    assert note["measured_ms"] == pytest.approx(14.0)
    # one wave, 800 ms of device time
    got = read(run, "prefill_mfu.brumby")
    need = flops.prefill_wave_need(c.config, c.system, c.traffic,
                                   {"retention_rows": 24})
    assert got == pytest.approx(100 * need["flops"] / 197e12 / 0.8)
    assert 40 < got < 45 and run.notes[-1]["bound"] == "compute"


def test_the_two_scopes_shares_of_their_rooflines(monkeypatch):
    """readers/scope_roofline.py over the prefill program (the sequence
    form under `ff_power_retention_scan`, a wave) and over the decode
    program (the step under `ff_power_retention_step`, per decode step, the
    window's counters over its `steps`)."""
    from flexflow_tpu import attribution

    run = traced_serving(monkeypatch)
    asked = []

    def under(name, scope):
        asked.append((name, scope))
        return {"serve/prefill": [{"fusion.7", "fusion.8", "fusion.99"}],
                "serve/decode": [{"fusion.5", "fusion.6"}]}.get(name, [])

    monkeypatch.setattr(attribution, "instructions_under", under)
    c = run.cell
    got = read(run, "retention_scan_roofline.brumby")
    need = flops.retention_scan_need(c.config, c.system, c.traffic,
                                     {"retention_rows": 24})
    # 4 rows of 6 layers: 0.68 TFLOP at 197 TFLOP/s, 1.4 GB at 819 GB/s,
    # against 150 ms
    assert got == pytest.approx(100 * need["flops"] / 197e12 / 0.15)
    assert 2 < got < 3
    note = run.notes[-1]
    assert note["bound"] == "compute" and note["events"] == 2 \
        and note["units"] == 1 and note["scope"] == "ff_power_retention_scan"
    got = read(run, "retention_step_roofline.brumby")
    # 8 live slots' state read and written once at 819 GB/s against 6 ms
    assert got == pytest.approx(100 * 2.0 * 8 * STATE / 819e9 / 6e-3)
    assert 65 < got < 68
    note = run.notes[-1]
    assert note["bound"] == "memory" and note["events"] == 2 \
        and note["units"] == 2 and note["scope"] == "ff_power_retention_step"
    assert {("serve/prefill", "ff_power_retention_scan"),
            ("serve/decode", "ff_power_retention_step")} <= set(asked)
    # a program without the scopes in it, and a program from before the
    # function (the parent commit): nothing to read
    monkeypatch.setattr(attribution, "instructions_under", lambda n, s: [set()])
    assert read(run, "retention_scan_roofline.brumby") is None
    monkeypatch.delattr(attribution, "instructions_under")
    assert read(run, "retention_step_roofline.brumby") is None


def test_nothing_to_read_is_none(monkeypatch):
    # a program whose spans carry no counters (a parent from before them)
    run = traced_serving(monkeypatch, with_counters=False)
    for name in ("prefill_mfu.brumby", "decode_step_hbm_roofline.brumby",
                 "retention_scan_roofline.brumby",
                 "retention_step_roofline.brumby"):
        assert read(run, name) is None, name
    assert read(run, "linear_state_mb_per_step.decode.brumby") == 0.0
    from flexflow_tpu import attribution
    monkeypatch.setattr(attribution, "op_scopes", lambda name: [])
    assert read(run, "wave_linear_attention_device_ms.brumby") is None
    assert read(run, "decode_linear_attention_device_ms_per_step.brumby") is None
    # a family without a flops module of its own
    other = types.SimpleNamespace(config={"family": "no_such_family"},
                                  system={}, traffic={"kind": "serve"}, chips=1)
    assert read(Run(other, trace=run.trace, window=run.window),
                "prefill_mfu.brumby") is None
    # a program from before the ring
    from flexflow_tpu import telemetry as tel
    monkeypatch.delattr(tel, "ring_spans")
    assert [read(run, n) for n in NEW] == [None] * len(NEW)
