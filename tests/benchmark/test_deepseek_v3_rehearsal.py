"""The `deepseek_v3` family in the benchmark: its tiny cell through
rehearse_deepseek_v3.py (the serving cell's whole control flow on the CPU
backend; the family's own manifest rehearsal_deepseek_v3.json, since
rehearsal.json is the benchmark's and not a model PR's to edit), the metrics
this family brought, read from a hand-made ring and a hand-made reduced trace
(the latent cache's counters that ride on the program's spans, the routing
counters under the cell's own names, and the two shares of a peak through
readers/span_need.py), and how tight the cell's `correct` is: the
served-token rule at the logits' own scale, with an fp8 engine put through it
(control.py) and the logits check (logits_check_deepseek_v3.py), both at the
tiny size. Nothing here times anything."""

import json
import subprocess
import sys
import types

import pytest

from test_granitemoehybrid_rehearsal import (BENCH, CPU_ENV, MS, ROOT, SKEW,
                                             Ring, Run, read)

from harness import flops_deepseek_v3 as flops  # noqa: E402
from harness import manifest as mf  # noqa: E402
from harness import peaks  # noqa: E402
from harness.trace_reduce import Op, Trace  # noqa: E402

CELL = "GigaChat3.1-702B-A36B.serve-chat"
TINY = "deepseek-v3-tiny.serve"
NEW = ["prefill_mfu.gigachat", "decode_step_hbm_roofline.gigachat",
       "latent_cache_read_mb_per_step.decode",
       "moe_held_pair_share.decode.gigachat",
       "moe_expert_load_max_over_mean.decode.gigachat"]
REHEARSAL = "rehearsal_deepseek_v3.json"


def test_every_cell_config_and_reader_of_the_familys_manifest_is_found_by_name():
    from test_benchmark_harness import (
        test_every_cell_config_and_reader_is_found_by_name as found_by_name)

    found_by_name(BENCH / REHEARSAL)
    manifest = mf.load_manifest(BENCH / REHEARSAL)
    real = {w["name"] for w in mf.load_manifest()["workloads"]}
    assert [w["stands_for"] for w in manifest["workloads"]] == [CELL]
    assert CELL in real
    # the benchmark's own rehearsal manifest is as it was
    assert "deepseek" not in (BENCH / "rehearsal.json").read_text()


def test_rehearsal_runs_the_cells_control_flow_and_reports_no_metric():
    done = subprocess.run(
        [sys.executable, str(BENCH / "rehearse_deepseek_v3.py"), "--workload",
         TINY, "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=CPU_ENV, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
    assert set(NEW) <= set(last["would_report"])
    assert "moe_held_pair_share.decode" not in last["would_report"]


def _tool(script, *args, rehearsal=("--rehearsal",)):
    done = subprocess.run(
        [sys.executable, str(BENCH / script), *rehearsal, "--workload", TINY,
         *args],
        cwd=ROOT, env=CPU_ENV, capture_output=True, text=True, timeout=600)
    lines = [json.loads(l) for l in done.stdout.strip().splitlines()]
    return done, lines


def test_the_served_token_rule_counts_at_the_logits_own_scale(monkeypatch):
    """cells/serve.py floors the scale at 1; the family hands the gaps out in
    units of GAP_UNIT_ROW_SCALES of the row's own scale (and the scale as 1),
    so the rule's 8 are 16 bf16 ulps of what the logits are, whatever their
    scale; and a token whose routing the reference does not decide goes out
    with gap 0: not judged."""
    import numpy as np

    from families import deepseek_v3 as family

    cfg = mf.load_cell(mf.load_manifest(BENCH / REHEARSAL), TINY).config
    gap = np.array([[0.0, 0.04, 0.08, 3.0]])
    scale = np.array([[5.0, 5.0, 10.0, 5.0]])
    monkeypatch.setattr(family, "reference_params", lambda params, cfg: params)
    monkeypatch.setattr(
        family.reference, "token_gaps",
        lambda params, ids, pos, hp, scores: (gap, scale, ["selected"]))
    monkeypatch.setattr(
        family, "routing_decided",
        lambda selected, hp: np.array([[True, True, True, False, True]]))
    got, unit = family.reference_token_gaps(cfg, None, None, None)
    assert (np.asarray(unit) == 1).all()
    ulps = np.asarray(got) / (np.maximum(1.0, unit) * 2.0 ** -8)    # as cells/serve.py counts
    assert np.allclose(ulps, [[0.0, 1.024, 1.024, 0.0]])
    assert 8.0 * family.GAP_UNIT_ROW_SCALES == 16.0


def test_a_token_is_judged_only_where_its_routing_is_decided():
    """Selection scores of 16 experts in 4 groups, top 3 within 2 groups,
    experts 0-7 held (the tiny configuration): decided where the held
    experts among the chosen have margins well over the noise; not decided
    where a held expert lies on the edge of the top k, or where a held
    group lies on the edge of the groups that stay; a photo finish between
    two experts that are NOT held decides nothing here and is no reason
    not to judge."""
    import numpy as np

    from families import deepseek_v3 as family

    hp = {"held": (0, 8), "n_group": 4, "topk_group": 2, "top_k": 3}
    base = np.full(16, 0.1, np.float32)

    def scores(**at):
        c = base.copy()
        for e, v in at.items():
            c[int(e[1:])] = v
        return c

    clear = scores(e0=0.9, e1=0.8, e4=0.7, e5=0.6)          # groups 0, 1; 0 1 4
    edge = scores(e0=0.9, e1=0.8, e4=0.7001, e5=0.7)        # 4 | 5 on the edge
    group_edge = scores(e0=0.95, e1=0.9, e8=0.85, e9=0.8,   # group 0 is in,
                        e12=0.85, e13=0.7999)               # 2 | 3 on the edge
    held_group_edge = scores(e0=0.85, e1=0.8499, e8=0.9, e9=0.88,
                             e12=0.85, e13=0.85)            # 0 | 3 on the edge
    unheld = scores(e0=0.9, e1=0.8, e8=0.7001, e9=0.7, e12=0.2)   # 8 | 9
    c = np.stack([clear, edge, group_edge, held_group_edge, unheld])[None]
    got = np.asarray(family.routing_decided([c], hp, noise=0.004, draws=16))
    assert got.tolist() == [[True, False, True, False, True]]
    # every layer has to be decided
    both = np.asarray(family.routing_decided([c, c[:, ::-1]], hp, noise=0.004))
    assert both.tolist() == [[True, False, True, False, True]]   # a palindrome
    mixed = np.asarray(family.routing_decided([c, np.roll(c, 1, axis=1)], hp,
                                              noise=0.004))
    assert mixed.tolist() == [[True, False, False, False, False]]
    # without noise everything is decided
    assert np.asarray(family.routing_decided([c], hp, noise=0.0)).all()


def test_an_fp8_engine_goes_through_the_cells_rule():
    """control.py's flow (family-neutral, as it is): per seed a sound and a
    lowered window, each judged by cells/serve.py's parity against the
    weights as initialised. The tiny cell computes in float32 (its workload
    file says why), so the sound engine reads 0; the chip run at the
    published widths must come out `tight` (PERF.md has its readings)."""
    done, lines = _tool("control.py", "--seeds", f"5,{2 ** 31 + 11}",
                        "--seconds", "2", rehearsal=("--rehearsal", REHEARSAL))
    windows = [l for l in lines if l.get("fact") == "control_window"]
    assert [(w["seed"], w["engine"]) for w in windows] == [
        (5, "sound"), (5, "low"), (2 ** 31 + 11, "sound"), (2 ** 31 + 11, "low")]
    assert all(w["ok"] for w in windows if w["engine"] == "sound")
    last = lines[-1]
    assert done.returncode == 0 and last["tight"] is True
    assert last["sound_worst_gap_bf16_ulps"] < 1 < 8 < last["low_worst_gap_bf16_ulps"]


def test_the_logits_check_parts_the_program_from_fp8():
    """Prefill, then decode through the latent cache in the absorbed form,
    against the reference's un-absorbed full forward, on logits: the float32
    tiny program within 1e-4 of the scale (the order of its sums), the
    reference with fp8 weights far outside it (0.10 at the published widths
    in bf16, on the chip)."""
    done, lines = _tool("logits_check_deepseek_v3.py", "--seeds",
                        f"5,{2 ** 31 + 11}", "--tolerance", "1e-4")
    assert done.returncode == 0, done.stderr[-2000:]
    last = lines[-1]
    assert last["holds"] is True
    assert last["program_max_diff_over_scale"] < 1e-4 < 0.1 \
        < last["fp8_max_diff_over_scale"]
    served = [l["served"] for l in lines if l.get("fact") == "logits"]
    assert all(s["over_8_ulps"] == 0 and s["tokens"] == 4 * 33 for s in served)
    flips = [l for l in lines if l.get("fact") == "router_flips"]
    assert len(flips) == 2 and all(len(f["share_of_tokens_by_layer"]) == 2
                                   for f in flips)


def test_the_cell_lists_every_metric_it_reports():
    manifest = mf.load_manifest()
    cell = mf.load_cell(manifest, CELL)
    assert cell.chips == 1 and cell.traffic_name == "serve-chat"
    assert cell.system["max_batch_slots"] == 16
    assert cell.system["max_decode_len"] == 256 and cell.system["kv_page_size"] == 16
    # the cell's own rate on the untouched traffic file
    assert cell.traffic["rate_rps"] == cell.system["traffic"]["rate_rps"]
    assert cell.traffic["shape_seed"] == 24
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names and "decode_step_device_ms" in names
    assert not {"prefill_mfu.granite", "state_commit_ms",
                "moe_held_pair_share.decode"} & names
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    # (no position in a list is asked: a later PR appends after these)
    assert all(CELL in per_layer[name]["workloads"] for name in NEW)
    # every serving metric both other serving cells report, this one too
    both = {"gpt2-medium.serve-chat", "granite-4.0-h-small.serve-chat"}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if both <= set(m.get("workloads", [])):
            assert CELL in m["workloads"], m["name"]
    entry = [c for c in manifest["configs"]
             if c["name"] == "GigaChat3.1-702B-A36B"][0]
    cfg = cell.config
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 64,
                                "first_k_dense_replace": 3,
                                "n_routed_experts": 256, "vocab_size": 128256}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (6, 1, 16, 16032)
    # no width changed: the catalog row's numbers
    widths = {"hidden_size": 7168, "intermediate_size": 18432,
              "moe_intermediate_size": 2048, "num_attention_heads": 64,
              "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
              "qk_rope_head_dim": 64, "v_head_dim": 192,
              "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
              "n_shared_experts": 1, "routed_scaling_factor": 2.5,
              "num_nextn_predict_layers": 1, "max_position_embeddings": 262144,
              "rope_theta": 100000, "rms_norm_eps": 1e-06}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["rope_scaling"]["factor"] == 64 \
        and cfg["rope_scaling"]["original_max_position_embeddings"] == 4096
    assert set(cfg["departures"]) >= {"num_nextn_predict_layers",
                                      "max_position_embeddings", "weights"}
    # what the device holds: over the floor of a quarter of the chip
    held = 2 * flops.param_count(cfg)
    assert 0.64 < held / peaks.peaks_for("TPU v5 lite")["hbm_bytes"] < 0.66


def cell():
    return mf.load_cell(mf.load_manifest(), CELL)


def counters(steps, live=16, hit=6, context=300):
    """What `steps` decode steps of 5 expert and 6 latent layers report:
    `live` slots' pairs, a sixteenth of them held, `hit` held experts with a
    row a layer, `context` cached positions a live slot."""
    routed = steps * 5 * live * 8
    tokens = steps * 6 * live * context
    return {"moe_routed_pairs": routed, "moe_held_pairs": routed // 16,
            "moe_load_max": steps * 5 * 3, "moe_load_mean": routed / 16 / 16,
            "moe_experts_hit": steps * 5 * hit,
            "latent_cache_tokens": tokens, "latent_cache_bytes": tokens * 1280.0}


def traced_serving(monkeypatch, with_counters=True):
    """The window's run (profiler off), then a traced run of 1 s from
    20 000 ms whose clock in the trace is SKEW ahead: one wave, one decode
    window of two steps."""
    r = Ring()
    win = r.add("serve/run", 1000, 5000, requests=9)
    r.add("serve/decode/window_sync", 3000, 3010, parent=win.id, window=1,
          steps=4, **(counters(4) if with_counters else {}))
    r.add("serve/decode/window_sync", 3100, 3110, parent=win.id, window=2,
          steps=2, **(counters(2, live=8, context=500) if with_counters else {}))
    t0 = 20_000
    root = r.add("serve/run", t0, t0 + 2000, requests=2)
    a = r.add("serve/admit", t0 + 100, t0 + 1100, parent=root.id, wave=1,
              requests=4, prompt_tokens=640, padded_tokens=16384)
    wave = {"moe_held_pairs": 5 * 640 // 2, "latent_tokens_committed": 6 * 640} \
        if with_counters else {}
    r.add("serve/prefill/device_wait", t0 + 150, t0 + 1050, parent=a.id, **wave)
    for k in range(2):
        r.add("serve/decode/dispatch", t0 + 1200 + 20 * k, t0 + 1202 + 20 * k,
              parent=root.id, window=1)
    r.add("serve/decode/window_sync", t0 + 1240, t0 + 1250, parent=root.id,
          window=1, steps=2, **(counters(2) if with_counters else {}))
    r.install(monkeypatch)

    def at(ms):
        return int((t0 + ms) * MS) + SKEW
    ops = [Op("fusion.1", at(160), at(1010)),               # the wave: 850 ms
           Op("fusion.3", at(1201), at(1213)), Op("fusion.4", at(1221), at(1233))]
    host = [Op("bench/traced_run", at(-5), at(2000))]
    return Run(cell(), trace=Trace({0: ops}, host), window=(at(0), at(2000)))


def test_cache_and_routing_counters_from_the_windows_ring(monkeypatch):
    run = traced_serving(monkeypatch)
    # the window's spans (the run before the traced one), not the traced run's
    tokens = counters(4)["latent_cache_tokens"] \
        + counters(2, live=8, context=500)["latent_cache_tokens"]
    assert read(run, "latent_cache_read_mb_per_step.decode") == pytest.approx(
        tokens * 1280 / 6 / 1e6)
    # 16 slots x 300 positions x 6 layers x 1280 B = 36.9 MB a step; K and V
    # decompressed (64 heads x 320 values x 2 B a token a layer) would be 32 x
    assert 25 < read(run, "latent_cache_read_mb_per_step.decode") < 40
    assert read(run, "moe_held_pair_share.decode.gigachat") == pytest.approx(6.25)
    routed = counters(4)["moe_routed_pairs"] + counters(2, live=8)["moe_routed_pairs"]
    assert read(run, "moe_expert_load_max_over_mean.decode.gigachat") == \
        pytest.approx(6 * 5 * 3 / (routed / 16 / 16))


def test_shares_of_the_peaks_from_the_traced_runs_spans(monkeypatch):
    run = traced_serving(monkeypatch)
    c = run.cell
    # one decode window of two steps, 12 ms of device time each
    got = read(run, "decode_step_hbm_roofline.gigachat")
    per_step = {k: v / 2 for k, v in counters(2).items()}
    need = flops.decode_step_need(c.config, c.system, c.traffic, per_step)
    assert got == pytest.approx(100 * need["bytes"] / 819e9 / 12e-3)
    # 3.07 GB outside the routed experts, 30 experts of 88 MB, 37 MB of
    # cache: 5.75 GB a step at 819 GB/s is 7.0 ms
    assert 55 < got < 62
    note = run.notes[-1]
    assert note["bound"] == "memory" and note["per"] == 2
    assert note["measured_ms"] == pytest.approx(12.0)
    # one wave, 850 ms of device time
    got = read(run, "prefill_mfu.gigachat")
    need = flops.prefill_wave_need(c.config, c.system, c.traffic,
                                   {"moe_held_pairs": 5 * 640 // 2})
    assert got == pytest.approx(100 * need["flops"] / 197e12 / 0.85)
    assert 25 < got < 35 and run.notes[-1]["bound"] == "compute"
    # granite's shares list granite's cell alone; read here they would count
    # this family's need, and this cell does not list them
    assert "prefill_mfu.granite" not in {m["name"] for m in c.per_layer}


def test_nothing_to_read_is_none(monkeypatch):
    # a program whose spans carry no counters (a parent from before them):
    # the shares and the routing ratios read nothing
    run = traced_serving(monkeypatch, with_counters=False)
    quiet = [n for n in NEW if n != "latent_cache_read_mb_per_step.decode"]
    assert [read(run, n) for n in quiet] == [None] * 4
    # a family without a flops module of its own
    other = types.SimpleNamespace(config={"family": "no_such_family"},
                                  system={}, traffic={"kind": "serve"}, chips=1)
    assert read(Run(other, trace=run.trace, window=run.window),
                "prefill_mfu.gigachat") is None
    # a program from before the ring
    from flexflow_tpu import telemetry as tel
    monkeypatch.delattr(tel, "ring_spans")
    assert [read(run, n) for n in NEW] == [None] * 5
