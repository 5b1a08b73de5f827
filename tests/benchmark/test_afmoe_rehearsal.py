"""The `afmoe` family in the benchmark: its tiny cell through
rehearse_afmoe.py (the TRAINING cell's whole control flow on the CPU backend:
build, compile with remat_blocks, warm-up, fit calls, the traced call, the
reference's loss on the first batch; the family's own manifest
rehearsal_afmoe.json, since rehearsal.json is the benchmark's and not a model
PR's to edit), what the cell lists, the counter metrics read from a hand-made
ring of fit calls, and the need functions' arithmetic. Nothing here times
anything. New entries of the manifest are found by membership: nothing here
asserts that an entry is the last, or how many there are."""

import json
import subprocess
import sys

import pytest

from test_granitemoehybrid_rehearsal import (BENCH, CPU_ENV, ROOT, Ring, Run,
                                             read)

from harness import flops_afmoe as flops  # noqa: E402
from harness import manifest as mf  # noqa: E402

CONFIG = "Trinity-Mini"
CELL = CONFIG + ".train-8k"
TINY = "afmoe-tiny.train"
REHEARSAL = "rehearsal_afmoe.json"
NEW = {"train_mfu.trinity": "span_need",
       "flash_window_roofline.train.trinity": "scope_roofline",
       "flash_full_roofline.train.trinity": "scope_roofline",
       "moe_experts_mfu.train.trinity": "scope_roofline",
       "step_experts_device_ms.train.trinity": "named_scope",
       "step_window_attend_device_ms.train.trinity": "named_scope",
       "step_full_attend_device_ms.train.trinity": "named_scope",
       "moe_held_pair_share.train.trinity": "ring_stat",
       "moe_expert_load_max_over_mean.train.trinity": "ring_stat",
       "window_kv_pair_share.train.trinity": "ring_stat"}
APPENDED = ["cost_pred_over_meas.train", "fit_host_syncs_per_100steps",
            "device_idle.train", "fit_call_ends_ms.train", "fit_stall_ms.train",
            "fit_materializations_per_100steps", "step_forward_device_ms.train",
            "step_backward_device_ms.train", "step_update_device_ms.train",
            "step_loss_device_ms.train", "step_attention_device_ms.train",
            "step_norm_device_ms.train", "op_scope_unattributed.train",
            "setup_trace_lower_s.step", "setup_search_s", "setup_init_s"]
NOT_LISTED = ["fused_optim_ms.train", "flash_attention_roofline.train"]


def cell():
    return mf.load_cell(mf.load_manifest(), CELL)


def test_every_cell_config_and_reader_of_the_familys_manifest_is_found_by_name():
    from test_benchmark_harness import (
        test_every_cell_config_and_reader_is_found_by_name as found_by_name)

    found_by_name(BENCH / REHEARSAL)
    manifest = mf.load_manifest(BENCH / REHEARSAL)
    real = {w["name"] for w in mf.load_manifest()["workloads"]}
    assert [w["stands_for"] for w in manifest["workloads"]] == [CELL]
    assert CELL in real
    assert "afmoe" not in (BENCH / "rehearsal.json").read_text()


def test_rehearsal_runs_the_cells_control_flow_and_reports_no_metric():
    done = subprocess.run(
        [sys.executable, str(BENCH / "rehearse_afmoe.py"), "--workload", TINY,
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=CPU_ENV, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0 \
        and last["attempted"] > 0
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
    assert set(NEW) | set(APPENDED) <= set(last["would_report"])
    assert set(last["would_report"]) == {m["name"] for m in cell().per_layer}
    assert not set(NOT_LISTED) & set(last["would_report"])
    checks = next(l for l in lines if l.get("fact") == "correctness")
    assert all(checks["checks"].values())
    # float32 at the tiny size: the order of the sums alone
    assert checks["abs_diff"] < 1e-4
    window = next(l for l in lines if l.get("fact") == "train_window")
    assert window["flops_per_token"] == window["program_flops_per_token"]
    assert window["step_stats"]["fit_host_syncs"] == 0


def test_the_cell_lists_every_metric_it_reports():
    manifest = mf.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]]
    listed = {m["name"] for m in cell().per_layer}
    for name, reader in NEW.items():
        entry = manifest["per_layer"][names.index(name)]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_tokens_per_s"
        assert mf.read_named("metrics", name)["reader"] == reader
        if "roofline" in name or "mfu" in name:
            assert entry["unit"] == "%" and entry["source"] == "device_trace"
    assert set(NEW) | set(APPENDED) <= listed
    for name in NOT_LISTED:
        assert CELL not in manifest["per_layer"][names.index(name)]["workloads"]
    for m in manifest["per_layer"]:
        if "serve" in m["name"] or "decode" in m["name"] \
                or "prefill" in m["name"]:
            assert CELL not in m.get("workloads", [])
    assert {m["name"] for m in cell().end_to_end} == {"train_tokens_per_s",
                                                      "setup_s"}
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "layer_types", "num_experts", "vocab_size"]
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "train-8k"
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200


def step_counters(cfg, steps=10):
    """What a fit call's `fit/step_stats` span holds at the cell's sizes:
    means a step (two sequences of 8192; 4 expert layers, 4 sliding layers
    and 1 full one, 32 heads)."""
    s, w = 8192, cfg["sliding_window"]
    tri = s * (s + 1) // 2
    band = tri - (s - w) * (s - w + 1) // 2
    routed = 4 * 16384 * 8
    return {"steps": steps, "moe_routed_pairs": routed,
            "moe_held_pairs": routed / 8 + 300, "moe_router_load_max": 4 * 1400,
            "moe_router_load_mean": 4 * 1024, "window_keys_seen": 4 * 64 * band,
            "window_keys_causal": 4 * 64 * tri, "full_keys_seen": 64 * tri}


def fit_calls(monkeypatch, calls=3, with_counters=True):
    ring, run = Ring(), Run(cell())
    for i in range(calls):
        root = ring.add("fit/call", 1000 * i, 1000 * i + 900, steps=10)
        if with_counters:
            ring.add("fit/step_stats", 1000 * i + 890, 1000 * i + 890,
                     parent=root.id, epoch=0,
                     **step_counters(run.cell.config))
    run.facts["fit_seconds"] = [0.9] * calls
    ring.install(monkeypatch)
    return run


def test_the_counters_shares_from_the_fit_calls_spans(monkeypatch):
    run = fit_calls(monkeypatch)
    assert read(run, "window_kv_pair_share.train.trinity") \
        == pytest.approx(43.75, abs=0.01)
    assert read(run, "moe_held_pair_share.train.trinity") \
        == pytest.approx(100 * (1 / 8 + 300 / (4 * 16384 * 8)))
    assert read(run, "moe_expert_load_max_over_mean.train.trinity") \
        == pytest.approx(1400 / 1024)


def test_nothing_to_read_is_none(monkeypatch):
    """A parent's program has no fit/step_stats span and no scope: every new
    reader gives None, none raises."""
    run = fit_calls(monkeypatch, with_counters=False)
    for name in NEW:
        assert read(run, name) is None, name


def test_the_need_functions_count_what_the_counters_say():
    c = cell()
    cfg, counters = c.config, step_counters(c.config, steps=1)
    whole = flops.train_step_need(cfg, c.system, c.traffic, counters)["flops"]
    # ISSUE 58's reckoning: 2.21 GFLOP a token trained, 36.2 TFLOP a step
    assert whole == pytest.approx(36.2e12, rel=0.01)
    assert flops.train_step_need(cfg, c.system, c.traffic,
                                 dict(counters, steps=4))["flops"] == 4 * whole
    band = flops.window_attend_train_need(cfg, c.system, c.traffic, counters)
    full = flops.full_attend_train_need(cfg, c.system, c.traffic, counters)
    experts = flops.moe_experts_train_need(cfg, c.system, c.traffic, counters)
    # 7 products where the step's share counts 2 forward x 3
    assert (band["flops"] + full["flops"]) * 6 / 7 + experts["flops"] < whole
    assert band["flops"] / full["flops"] == pytest.approx(4 * 0.4375, rel=1e-3)
    assert experts["flops"] == 18 * counters["moe_held_pairs"] * 2048 * 1024
    assert band["bytes"] > full["bytes"] > 0 and experts["bytes"] > 0
    assert flops.keys_seen(cfg, "sliding_attention", 8192) == 14_681_088
    assert flops.keys_seen(cfg, "full_attention", 8192) == 33_558_528
