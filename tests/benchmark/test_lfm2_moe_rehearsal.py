"""The `lfm2_moe` family in the benchmark: its tiny cell through
rehearse_lfm2_moe.py (the serving cell's whole control flow on the CPU
backend; the family's own manifest rehearsal_lfm2_moe.json, since
rehearsal.json is the benchmark's and not a model PR's to edit), the metrics
this family brought, read from a hand-made ring and a hand-made reduced
trace (the share of the held experts a step hits, the two shares of a peak
through readers/span_need.py, the grouped products' share of their roofline
through readers/scope_roofline.py over the decode program), the parameter
count, and how tight the comparisons are: an fp8 engine through the cell's
served-token rule (control.py) and the logits check with its four wrong
references (logits_check_lfm2_moe.py), both at the tiny size. Nothing here
times anything. New entries of the manifest are found by membership and
ordered by index: nothing here asserts that an entry is the last, or how many
there are."""

import json
import subprocess
import sys
import types

import pytest

from test_granitemoehybrid_rehearsal import (BENCH, CPU_ENV, MS, ROOT, SKEW,
                                             Ring, Run, read)

from harness import flops_lfm2_moe as flops  # noqa: E402
from harness import manifest as mf  # noqa: E402
from harness import peaks  # noqa: E402
from harness.trace_reduce import Op, Trace  # noqa: E402

CONFIG = "LFM2-24B-A2B"
CELL = CONFIG + ".serve-longanswer"
TINY = "lfm2-moe-tiny.serve"
NEW = ["prefill_mfu.lfm2", "decode_step_hbm_roofline.lfm2",
       "moe_experts_roofline.decode.lfm2", "moe_experts_hit_share.decode.lfm2",
       "moe_held_pair_share.decode.lfm2",
       "moe_expert_load_max_over_mean.decode.lfm2",
       "wave_short_conv_device_ms.lfm2",
       "decode_short_conv_device_ms_per_step.lfm2", "state_commit_ms.lfm2"]
APPENDED = ["moe_rows_computed_share.prefill", "wave_experts_device_ms",
            "decode_experts_device_ms_per_step", "wave_attention_device_ms",
            "decode_attention_device_ms_per_step"]
REHEARSAL = "rehearsal_lfm2_moe.json"
SIX = ["gpt2-medium.serve-chat", "granite-4.0-h-small.serve-chat",
       "GigaChat3.1-702B-A36B.serve-chat",
       "NVIDIA-Nemotron-3-Super-120B-A12B-BF16.serve-chat",
       "Ling-3.0-flash.serve-chat", "Brumby-14B-Base.serve-longanswer"]
EXPERT = 18874368               # one expert's three matrices, bf16 bytes
STATE = 7 * 2 * 2048 * 2        # a slot's convolution state, all layers


def test_every_cell_config_and_reader_of_the_familys_manifest_is_found_by_name():
    from test_benchmark_harness import (
        test_every_cell_config_and_reader_is_found_by_name as found_by_name)

    found_by_name(BENCH / REHEARSAL)
    manifest = mf.load_manifest(BENCH / REHEARSAL)
    real = {w["name"] for w in mf.load_manifest()["workloads"]}
    assert [w["stands_for"] for w in manifest["workloads"]] == [CELL]
    assert CELL in real
    # the benchmark's own rehearsal manifest is as it was
    assert "lfm2" not in (BENCH / "rehearsal.json").read_text()


def test_rehearsal_runs_the_cells_control_flow_and_reports_no_metric():
    done = subprocess.run(
        [sys.executable, str(BENCH / "rehearse_lfm2_moe.py"), "--workload",
         TINY, "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=CPU_ENV, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
    # every metric the cell lists is reported
    cell = mf.load_cell(mf.load_manifest(), CELL)
    assert set(NEW) | set(APPENDED) <= set(last["would_report"])
    assert set(last["would_report"]) == {m["name"] for m in cell.per_layer}
    # no state-space mixer, no latent, no linear attention in this model
    assert not {"wave_mixer_device_ms", "state_commit_ms",
                "latent_cache_read_mb_per_step.decode",
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
    file says why), so the sound engine reads 0 and the lowered one well
    over it. Whether the lowered one passes the limit is asked at the
    published widths alone: the family's unit is set from the chip's readings
    there (0.5625 of a row's scale over 16 tokens: families/lfm2_moe.py),
    where eight whole-held expert layers cascade; this tiny model's fp8 engine
    reads 3-6 of the 8, so `tight` is not asked of it (PERF.md has the chip's
    readings)."""
    done, lines = _tool("control.py", "--seeds", f"7,{2 ** 31 + 11}",
                        "--seconds", "2", rehearsal=("--rehearsal", REHEARSAL))
    windows = [l for l in lines if l.get("fact") == "control_window"]
    assert [(w["seed"], w["engine"]) for w in windows] == [
        (7, "sound"), (7, "low"), (2 ** 31 + 11, "sound"), (2 ** 31 + 11, "low")]
    assert all(w["ok"] for w in windows if w["engine"] == "sound")
    last = lines[-1]
    assert done.returncode in (0, 1) and "tight" in last
    assert last["sound_worst_gap_bf16_ulps"] < 0.1 < 1 \
        < last["low_worst_gap_bf16_ulps"]


@pytest.mark.parametrize("routed", ("as_published", "witness"))
def test_the_logits_check_parts_the_program_from_four_wrong_references(routed):
    """Prefill through the program the scheduler runs, then decode through
    the pools and the convolutions' state, against the reference's full
    forward, on logits: the float32 tiny program within 1e-4 of the scale
    (the order of its sums); the reference with fp8 weights, with a router
    that leaves its selection bias out, with q and k without their norms,
    and with the convolution's state taken where no token is, far outside
    it. The witness (the routed sum scaled by 0 on both sides) holds what is
    left with no router in between: the bias then decides nothing."""
    extra = ("--routed-scale", "0") if routed == "witness" else ()
    done, lines = _tool("logits_check_lfm2_moe.py", "--seeds",
                        f"5,{2 ** 31 + 11}", "--steps", "32",
                        "--tolerance", "1e-4", *extra)
    assert done.returncode == 0, done.stderr[-2000:]
    last = lines[-1]
    assert last["holds"] is True
    wrong = dict(last["wrong_mean_diff_over_scale"])
    assert set(wrong) == {"fp8_reference", "no_selection_bias_reference",
                          "no_qk_norm_reference", "padded_end_state_reference"}
    if routed == "witness":
        assert last["report_only"] == ["no_selection_bias_reference"]
        assert wrong.pop("no_selection_bias_reference") == 0.0
    else:
        assert last["report_only"] == []
    assert last["program_mean_diff_over_scale"] < 1e-4 < 5e-2 \
        < min(wrong.values())
    assert last["program_served_gap_ulps"] == 0 < last["fp8_served_gap_ulps"]
    assert last["served_gap_limit_ulps"] == 8 * 18.0
    served = [l["served"] for l in lines if l.get("fact") == "logits"]
    assert all(s["over_8_ulps"] == 0 and s["tokens"] == 4 * 33 for s in served)
    judged = [l["steps_judged"] for l in lines
              if l.get("fact") == "padded_end_state_reference"]
    assert judged == [2, 2]
    assert lines[0]["state_kinds"] == "paged_kv+recurrent"


def test_the_cell_lists_every_metric_it_reports():
    manifest = mf.load_manifest()
    cell = mf.load_cell(manifest, CELL)
    assert cell.chips == 1 and cell.traffic_name == "serve-longanswer"
    assert cell.system["max_batch_slots"] == 16
    assert cell.system["max_decode_len"] == 512 and cell.system["kv_page_size"] == 16
    assert cell.system["ffconfig"] == {"compute_dtype": "bfloat16",
                                       "mesh_shape": {"data": 1}}
    # the cell's own rate over the traffic file's placeholder, and nothing
    # else of the traffic overridden
    assert list(cell.system["traffic"]) == ["rate_rps"]
    assert cell.traffic["rate_rps"] == cell.system["traffic"]["rate_rps"] != 1.0
    assert cell.traffic["shape_seed"] == 24
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | set(APPENDED) <= names
    assert {"decode_step_device_ms", "prefill_device_ms", "device_idle.serve",
            "op_scope_unattributed.serve", "trace_lower_s",
            "backend_compile_s", "search_s", "compile_s"} <= names
    assert not {n for n in names if n.startswith(("wave_mixer", "latent_cache",
                                                  "linear_state",
                                                  "wave_linear"))}
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    assert all(per_layer[name]["workloads"] == [CELL] for name in NEW)
    assert all(per_layer[n]["unit"] == "%" for n in NEW if "roofline" in n
               or "mfu" in n)
    assert per_layer["moe_experts_hit_share.decode.lfm2"]["better"] == "lower"
    # membership, and order by index: the new entries come after what was
    # there, in the order ISSUE 47 lists them
    order = [m["name"] for m in manifest["per_layer"]]
    assert [order.index(n) for n in NEW] == sorted(order.index(n) for n in NEW)
    assert order.index(NEW[0]) > order.index("state_commit_ms.brumby")
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) > max(cells.index(c) for c in SIX)
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    # every serving metric the six other serving cells report, this one
    # too, appended behind them; and the five the issue names besides
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        listed = m.get("workloads", [])
        if set(SIX) <= set(listed) or m["name"] in APPENDED:
            assert listed[-1] == CELL or listed.index(CELL) > max(
                listed.index(c) for c in SIX if c in listed), m["name"]
    configs = [c["name"] for c in manifest["configs"]]
    entry = manifest["configs"][configs.index(CONFIG)]
    cfg = cell.config
    assert entry["source"] == cfg["source"] and cfg["reduced"] == entry["reduced"] \
        == ["num_hidden_layers", "num_dense_layers", "layer_types"]
    assert len(manifest["workloads"][cells.index(CELL)]["why"]) <= 200 \
        and len(entry["why"]) <= 200
    # the parameter count, to the parameter, and at the published depth
    assert flops.param_count(cfg) == 5312168704
    full = dict(cfg, num_hidden_layers=40, num_dense_layers=2,
                layer_types=cfg["published"]["layer_types"])
    assert round(flops.param_count(full, tied_head=True) / 1e9, 2) == 23.84
    # what the device holds: two thirds of the chip before a wave's
    # temporaries, over the floor of a quarter
    held = 2 * flops.param_count(cfg) + 16 * flops.state_bytes_per_slot(cfg) \
        + 16 * 1536 * flops.kv_bytes_per_token(cfg)
    assert flops.state_bytes_per_slot(cfg) == STATE
    assert 0.66 < held / peaks.peaks_for("TPU v5 lite")["hbm_bytes"] < 0.68


def cell():
    return mf.load_cell(mf.load_manifest(), CELL)


def counters(steps, live, hit, with_counters=True):
    """What `steps` decode steps of 8 expert layers report with `live` slots
    and `hit` experts with a row a layer."""
    if not with_counters:
        return {}
    return {"moe_routed_pairs": steps * 8 * live * 4,
            "moe_held_pairs": steps * 8 * live * 4,
            "moe_load_max": steps * 8 * 3, "moe_load_mean": steps * 8 * live * 4 / 64,
            "moe_experts_hit": steps * 8 * hit,
            "moe_experts_held": steps * 8 * 64,
            "ssm_state_bytes": steps * 2.0 * live * STATE}


def traced_serving(monkeypatch, with_counters=True):
    """The window's run (profiler off), then a traced run of 1 s from
    20 000 ms whose clock in the trace is SKEW ahead: one wave, one decode
    window of two steps with 6 live slots that hit 21 experts a layer."""
    r = Ring()
    win = r.add("serve/run", 1000, 5000, requests=9)
    r.add("serve/prefill/commit_state", 1500, 1500.2, parent=win.id, bytes=8 * STATE)
    r.add("serve/prefill/commit_state", 2500, 2500.4, parent=win.id, bytes=8 * STATE)
    r.add("serve/decode/window_sync", 3000, 3010, parent=win.id, window=1,
          steps=4, **counters(4, 8, 26, with_counters))
    r.add("serve/decode/window_sync", 3100, 3110, parent=win.id, window=2,
          steps=2, **counters(2, 3, 11, with_counters))
    t0 = 20_000
    root = r.add("serve/run", t0, t0 + 2000, requests=2)
    a = r.add("serve/admit", t0 + 100, t0 + 400, parent=root.id, wave=1,
              requests=2, prompt_tokens=300, padded_tokens=16384)
    wave = {"moe_held_pairs": 8 * 4 * 300, "moe_rows_computed": 8 * 1024,
            "moe_rows_static": 8 * 65536} if with_counters else {}
    r.add("serve/prefill/device_wait", t0 + 150, t0 + 350, parent=a.id, **wave)
    for k in range(2):
        r.add("serve/decode/dispatch", t0 + 1200 + 20 * k, t0 + 1202 + 20 * k,
              parent=root.id, window=1)
    r.add("serve/decode/window_sync", t0 + 1240, t0 + 1250, parent=root.id,
          window=1, steps=2, **counters(2, 6, 21, with_counters))
    r.install(monkeypatch)

    def at(ms):
        return int((t0 + ms) * MS) + SKEW
    # the wave: 110 ms of device time; a step: 7 ms, 5 of them the experts'
    # grouped products (two calls a step here)
    ops = [Op("fusion.1", at(160), at(270)),
           Op("fusion.3", at(1201), at(1203)),
           Op("ragged-dot-none.5", at(1203), at(1208)),
           Op("fusion.4", at(1221), at(1223)),
           Op("ragged-dot-none.5", at(1223), at(1228))]
    host = [Op("bench/traced_run", at(-5), at(2000))]
    return Run(cell(), trace=Trace({0: ops}, host), window=(at(0), at(2000)))


def test_expert_shares_and_commit_from_the_windows_ring(monkeypatch):
    run = traced_serving(monkeypatch)
    assert read(run, "state_commit_ms.lfm2") == pytest.approx(0.3)
    # 26 of 64 in four steps, 11 of 64 in two
    got = read(run, "moe_experts_hit_share.decode.lfm2")
    assert got == pytest.approx(100 * (4 * 26 + 2 * 11) / (6 * 64))
    assert read(run, "moe_held_pair_share.decode.lfm2") == 100.0
    assert read(run, "moe_expert_load_max_over_mean.decode.lfm2") \
        == pytest.approx(6 * 8 * 3 / ((4 * 8 + 2 * 3) * 8 * 4 / 64))
    # the curve the cell's `why` speaks of
    assert [round(64 * (1 - (15 / 16) ** n)) for n in (3, 6, 8, 16)] \
        == [11, 21, 26, 41]


def test_shares_of_the_peaks_from_the_traced_runs_spans(monkeypatch):
    run = traced_serving(monkeypatch)
    c = run.cell
    # one decode window of two steps, 7 ms of device time each
    got = read(run, "decode_step_hbm_roofline.lfm2")
    need = flops.decode_step_need(c.config, c.system, c.traffic, {
        k: v / 2 for k, v in counters(2, 6, 21).items()})
    assert got == pytest.approx(100 * need["bytes"] / 819e9 / 7e-3)
    assert 60 < got < 75
    experts = 8 * 21 * EXPERT
    assert 0.70 < experts / need["bytes"] < 0.85
    note = run.notes[-1]
    assert note["bound"] == "memory" and note["per"] == 2
    assert note["measured_ms"] == pytest.approx(7.0)
    # one wave, 110 ms of device time
    got = read(run, "prefill_mfu.lfm2")
    need = flops.prefill_wave_need(c.config, c.system, c.traffic,
                                   {"moe_held_pairs": 8 * 4 * 300})
    assert got == pytest.approx(100 * need["flops"] / 197e12 / 0.11)
    assert 30 < got < 36 and run.notes[-1]["bound"] == "compute"


def test_the_grouped_products_share_of_their_roofline(monkeypatch):
    """readers/scope_roofline.py over the decode program: the operations
    under `ff_moe_experts` (what the chip's compiler makes of the ragged
    products among them), per decode step, the window's counters over its
    `steps`."""
    from flexflow_tpu import attribution

    run = traced_serving(monkeypatch)
    asked = []

    def under(name, scope):
        asked.append((name, scope))
        return {"serve/decode": [{"ragged-dot-none.5", "fusion.77"}]
                }.get(name, [])

    monkeypatch.setattr(attribution, "instructions_under", under)
    got = read(run, "moe_experts_roofline.decode.lfm2")
    # 8 x 21 experts read once at 819 GB/s against 5 ms
    assert got == pytest.approx(100 * 8 * 21 * EXPERT / 819e9 / 5e-3)
    assert 75 < got < 80
    note = run.notes[-1]
    assert note["bound"] == "memory" and note["events"] == 2 \
        and note["units"] == 2 and note["scope"] == "ff_moe_experts"
    assert ("serve/decode", "ff_moe_experts") in asked
    # a program without the scope in it, and a program from before the
    # function (the parent commit): nothing to read
    monkeypatch.setattr(attribution, "instructions_under", lambda n, s: [set()])
    assert read(run, "moe_experts_roofline.decode.lfm2") is None
    monkeypatch.delattr(attribution, "instructions_under")
    assert read(run, "moe_experts_roofline.decode.lfm2") is None


def test_the_scope_holds_what_the_chips_compiler_makes_of_a_ragged_dot():
    """`attribution.instructions_in_scope`: a ragged-dot under the scope
    comes out of the chip's compiler as a Mosaic call named
    `ragged-dot-none.N` with no name stack; it is under the scope where an
    instruction it reads, or one that reads it, is. Other nameless
    instructions stay out."""
    from flexflow_tpu import attribution

    hlo = """HloModule jit__decode

ENTRY %main (p0: bf16[64,2048], p1: bf16[64,2048,3072], p2: bf16[64,1536,2048]) -> bf16[64,2048] {
  %p0 = bf16[64,2048]{1,0} parameter(0)
  %p1 = bf16[64,2048,3072]{2,1,0} parameter(1)
  %p2 = bf16[64,1536,2048]{2,1,0} parameter(2)
  %fusion.1 = bf16[64,2048]{1,0} fusion(%p0), kind=kLoop, calls=%fc.1, metadata={op_name="jit(_decode)/l1_moe/gather"}
  %ragged-dot-none.2 = bf16[64,3072]{1,0} custom-call(%fusion.1, %p1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.3 = bf16[64,1536]{1,0} fusion(%ragged-dot-none.2), kind=kLoop, calls=%fc.3, metadata={op_name="jit(_decode)/l1_moe/ff_moe_experts/mul"}
  %ragged-dot-none.1 = bf16[64,2048]{1,0} custom-call(%fusion.3, %p2), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %copy.9 = bf16[64,2048]{1,0} copy(%ragged-dot-none.1)
  ROOT %fusion.5 = bf16[64,2048]{1,0} fusion(%copy.9), kind=kLoop, calls=%fc.5, metadata={op_name="jit(_decode)/l1_moe/scatter-add"}
}
"""
    assert attribution.instructions_in_scope(hlo, "ff_moe_experts") == {
        "ragged-dot-none.2", "fusion.3", "ragged-dot-none.1"}
    assert attribution.instructions_in_scope(hlo, "l1_moe") == {
        "fusion.1", "ragged-dot-none.2", "fusion.3", "ragged-dot-none.1",
        "fusion.5"}


def test_nothing_to_read_is_none(monkeypatch):
    # a program whose spans carry no counters (a parent from before them)
    run = traced_serving(monkeypatch, with_counters=False)
    for name in ("prefill_mfu.lfm2", "decode_step_hbm_roofline.lfm2",
                 "moe_experts_roofline.decode.lfm2",
                 "moe_experts_hit_share.decode.lfm2",
                 "moe_held_pair_share.decode.lfm2",
                 "moe_expert_load_max_over_mean.decode.lfm2"):
        assert read(run, name) is None, name
    from flexflow_tpu import attribution
    monkeypatch.setattr(attribution, "op_scopes", lambda name: [])
    assert read(run, "wave_short_conv_device_ms.lfm2") is None
    assert read(run, "decode_short_conv_device_ms_per_step.lfm2") is None
    # a family without a flops module of its own
    other = types.SimpleNamespace(config={"family": "no_such_family"},
                                  system={}, traffic={"kind": "serve"}, chips=1)
    assert read(Run(other, trace=run.trace, window=run.window),
                "prefill_mfu.lfm2") is None
    # a program from before the ring
    from flexflow_tpu import telemetry as tel
    monkeypatch.delattr(tel, "ring_spans")
    assert [read(run, n) for n in NEW] == [None] * len(NEW)
