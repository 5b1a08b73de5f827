"""The benchmark's harness (benchmarks/), on the CPU, in seconds: the
manifest's form, files found by name, the traffic generator, the metric
arithmetic, the trace reduction on a small recorded trace, the FLOP functions
and the plain reference against the program. Nothing here times anything or
describes a TPU topology (that stays in tests/test_chip_compile.py)."""

import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"
sys.path.insert(0, str(BENCH))

import run as bench_run  # noqa: E402
from harness import (flops, manifest as mf, peaks, reference_gpt2, stats,  # noqa: E402
                     trace_reduce, traffic)

RECORDED_TRACE = Path(__file__).with_name("recorded_tpu_trace.xplane.pb")
CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="0")


@pytest.fixture(scope="module")
def manifest():
    return mf.load_manifest()


def test_manifest_names_units_and_moves(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    cells = {w["name"]: w for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    names = (list(cells) + list(e2e) + [m["name"] for m in manifest["per_layer"]]
             + [c["name"] for c in manifest["configs"]]
             + [w["traffic"] for w in cells.values()])
    assert all(mf.NAME_RE.match(n) for n in names), names
    assert len(set(list(e2e) + [m["name"] for m in manifest["per_layer"]])) == \
        len(e2e) + len(manifest["per_layer"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert mf.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells), m
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert all(0.01 <= m["bound"] <= 0.1 for m in e2e.values())
    for m in manifest["per_layer"]:
        moved = e2e[m["moves"]]     # KeyError: moves names no end-to-end metric
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells)), \
            f"{m['name']} moves {m['moves']}, which not all of its cells report"
    for name, cell in cells.items():
        assert cell["chips"] in (1, 4)
        assert len(mf.metrics_for(manifest, "end_to_end", name)) >= 2
        assert mf.metrics_for(manifest, "per_layer", name)
    assert {c["name"] for c in manifest["configs"]} == \
        {w["config"] for w in cells.values()}
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 4)


@pytest.mark.parametrize("path", [mf.MANIFEST, BENCH / "rehearsal.json"],
                         ids=["BENCHMARK.json", "rehearsal.json"])
def test_every_cell_config_and_reader_is_found_by_name(path):
    manifest = mf.load_manifest(path)
    for c in manifest["configs"]:
        assert (ROOT / c["file"]).is_file(), c
    for w in manifest["workloads"]:
        cell = mf.load_cell(manifest, w["name"])
        assert cell.config["n_embd"] % cell.config["n_head"] == 0
        assert (BENCH / "families" / f"{cell.config['family']}.py").is_file()
        assert (BENCH / "cells" / f"{cell.traffic['kind']}.py").is_file()
        assert cell.end_to_end and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            spec = mf.read_named("metrics", m["name"], required=False)
            if spec is not None:
                assert (BENCH / "readers" / f"{spec['reader']}.py").is_file()


def test_files_dropped_beside_the_others_are_picked_up_without_an_edit(
        manifest, tmp_path):
    """A later PR adds a configuration, a cell and a per-layer metric as new
    files and new entries alone."""
    for kind in ("configs", "traffic", "workloads", "metrics"):
        shutil.copytree(BENCH / kind, tmp_path / kind)
    cfg = json.loads((BENCH / "configs" / "gpt2-tiny.json").read_text())
    (tmp_path / "configs" / "new-model.json").write_text(
        json.dumps(dict(cfg, n_layer=3)))
    (tmp_path / "traffic" / "train-b2.json").write_text(
        json.dumps({"kind": "train", "global_batch": 2, "steps_per_fit": 5}))
    (tmp_path / "workloads" / "new-model.train-b2.json").write_text(
        json.dumps({"ffconfig": {}, "adam_lr": 1e-3, "loss_tolerance": 0.1,
                    "traffic": {"steps_per_fit": 7}}))
    (tmp_path / "metrics" / "steps_per_sync.json").write_text(
        json.dumps({"reader": "ratio",
                    "args": {"num": "steps", "den": "fit_host_syncs"}}))
    grown = json.loads(json.dumps(manifest))
    grown["configs"].append({"name": "new-model"})
    grown["workloads"].append({"name": "new-model.train-b2", "chips": 1,
                               "config": "new-model", "traffic": "train-b2"})
    grown["per_layer"].append({"name": "steps_per_sync", "unit": "count",
                               "workloads": ["new-model.train-b2"]})
    cell = mf.load_cell(grown, "new-model.train-b2", bench_dir=tmp_path)
    assert cell.config["n_layer"] == 3
    assert cell.traffic["global_batch"] == 2
    assert cell.traffic["steps_per_fit"] == 7      # the cell's own override
    assert "train_tokens_per_s" not in [m["name"] for m in cell.end_to_end]
    view = bench_run.RunView(facts={"steps": 40, "fit_host_syncs": 2},
                             cell=cell, peaks=None)
    got = bench_run.read_metrics(cell.per_layer, view, bench_dir=tmp_path)
    assert got["steps_per_sync"] == {"value": 20.0, "unit": "count"}
    assert "search_s" not in got        # nothing to read: left out
    with pytest.raises(KeyError):
        mf.load_cell(grown, "no-such-cell", bench_dir=tmp_path)


def test_traffic_is_the_same_for_a_seed_and_the_same_schedule_for_every_seed(manifest):
    tr = mf.load_cell(manifest, "gpt2-medium.serve-chat").traffic
    big = 2 ** 31 + 12345
    window = float(manifest["run_seconds"])
    a = traffic.serve_requests(tr, window, big, 50257)
    assert a == traffic.serve_requests(tr, window, big, 50257)
    b = traffic.serve_requests(tr, window, 7, 50257)
    assert a != b
    assert len(a) == round(tr["rate_rps"] * window)
    for reqs in (a, b):
        arrivals = [r["arrival_s"] for r in reqs]
        assert arrivals == sorted(arrivals) and 0 <= arrivals[0] and arrivals[-1] < window
        for r in reqs:
            assert tr["prompt_len"]["min"] <= len(r["prompt"]) <= tr["prompt_len"]["max"]
            assert tr["output_len"]["min"] <= r["max_new_tokens"] <= tr["output_len"]["max"]
            assert len(r["prompt"]) + r["max_new_tokens"] <= 1024
            assert min(r["prompt"]) >= 1 and max(r["prompt"]) < 50257
    schedule = lambda reqs: [(r["arrival_s"], len(r["prompt"]), r["max_new_tokens"]) for r in reqs]  # noqa: E731
    assert schedule(a) == schedule(b)             # the seed draws token ids only
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    with pytest.raises(ValueError, match="unknown arrival process"):
        traffic.serve_requests(dict(tr, arrivals="no-such-law"), window, 7, 50257)
    (ids, pos), labels = traffic.stride_dataset(50257, 1024, 4, big)
    assert ids.shape == pos.shape == labels.shape == (4, 1024)
    assert (ids[:, 1:] == labels[:, :-1]).all()


def test_percentiles_and_rates_on_a_hand_made_request_list():
    def req(arrival, ttft, tokens, finish, outcome="done", admit=None):
        return {"arrival_s": arrival, "ttft_s": ttft, "n_tokens": tokens,
                "admit_s": arrival if admit is None else admit,
                "finish_s": finish, "outcome": outcome}

    reqs = [req(0.0, 0.1 * (i + 1), 11, 0.1 * (i + 1) + 1.0) for i in range(19)]
    reqs.append(req(1.0, None, 0, 1.5, outcome="shed"))
    s = stats.serve_summary(reqs, window_s=10.0, drain_limit_s=5.0)
    assert (s["attempted"], s["failed"], s["completed"]) == (20, 1, 19)
    assert s["serve_tokens_per_s"] == pytest.approx(19 * 11 / 10.0)
    # 20 samples, the shed one is +inf: p95 interpolates between the 19th
    # (1900 ms) and +inf, so the failure is felt and not dropped
    assert math.isinf(s["ttft_p95_ms"])
    assert s["ttft_p50_ms"] == pytest.approx(1050.0)
    assert s["tpot_p50_ms"] == pytest.approx(100.0)      # 1.0 s / 10 gaps
    ok = stats.serve_summary(reqs[:19], 10.0, 5.0)
    assert ok["ttft_p95_ms"] == pytest.approx(1810.0)
    assert ok["queue_wait_p95_ms"] == 0.0
    assert ok["prefill_wave_ms"] == pytest.approx(1000.0)
    late = stats.serve_summary([req(9.0, 0.5, 5, 16.0)], 10.0, 5.0)
    assert late["failed"] == 1          # finished after the drain limit
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.spread([10, 10, 11, 9, 10, 10]) == pytest.approx(0.05)
    with pytest.raises(ValueError):
        stats.tokens_per_s(10, 0.0)


def test_trace_reduction_on_the_recorded_tpu_trace():
    """recorded_tpu_trace.xplane.pb: the traced fit call of the tiny
    rehearsal cell, recorded on a TPU v5e (PR 24)."""
    tr = trace_reduce.load(RECORDED_TRACE)
    assert sorted(tr.devices) == [0]
    window = trace_reduce.window_of(tr, "bench/traced_fit")
    window_s = (window[1] - window[0]) / 1e9
    busy = trace_reduce.busy_seconds(tr, window)[0]
    assert 0 < busy < window_s
    ops = tr.devices[0]
    assert busy <= sum(o.end - o.start for o in ops) / 1e9   # a union
    flash = trace_reduce.matching_seconds(tr, window, "ff_flash_attention")[0]
    optim = trace_reduce.matching_seconds(tr, window, "ff_fused_optim")[0]
    assert flash["events"] > 0 and 0 < flash["seconds"] < busy
    assert optim["events"] > 0 and 0 < optim["seconds"] < busy
    assert trace_reduce.matching_seconds(tr, window, "no_such_kernel")[0] == \
        {"seconds": 0.0, "events": 0}
    half = (window[0], (window[0] + window[1]) // 2)
    assert trace_reduce.busy_seconds(tr, half)[0] < busy
    top = trace_reduce.top_ops(tr, window)
    assert 0 < len(top) <= 10 and top[0][1] >= top[-1][1] > 0
    gaps = trace_reduce.idle_gaps(tr, window)
    assert sum(s for _n, s in gaps) == pytest.approx(window_s - busy, rel=1e-6)
    assert gaps[0][0].startswith("bench/")
    with pytest.raises(ValueError):
        trace_reduce.window_of(tr, "bench/no_such_span")


def test_busy_is_a_union_of_nested_and_overlapping_operations():
    ops = [trace_reduce.Op("while", 0, 100), trace_reduce.Op("fusion.1", 10, 40),
           trace_reduce.Op("all-reduce-start.1", 90, 130),
           trace_reduce.Op("all-reduce-done.1", 120, 150),
           trace_reduce.Op("fusion.2", 200, 260)]
    tr = trace_reduce.Trace({0: ops}, [trace_reduce.Op("bench/w", 0, 300),
                                       trace_reduce.Op("bench/w/inner", 150, 210)])
    w = trace_reduce.window_of(tr, "bench/w")
    assert trace_reduce.busy_seconds(tr, w)[0] == pytest.approx(210e-9)
    coll = trace_reduce.matching_seconds(tr, w, trace_reduce.COLLECTIVE)[0]
    assert coll == {"seconds": pytest.approx(60e-9), "events": 2}
    assert trace_reduce.idle_gaps(tr, w) == [["bench/w/inner", pytest.approx(50e-9)],
                                             ["bench/w", pytest.approx(40e-9)]]
    assert trace_reduce.busy_seconds(tr, trace_reduce.window_of(
        tr, "bench/w", from_s=100e-9, to_s=250e-9))[0] == pytest.approx(100e-9)


@pytest.mark.parametrize("name", ["gpt2-medium", "gpt2-large", "gpt2-tiny"])
def test_flop_and_byte_functions_against_the_program(name):
    from families import family_of

    cfg = mf.read_named("configs", name)
    gcfg = family_of(cfg).program_config(cfg)
    assert flops.train_flops_per_token(cfg, gcfg.seq) == gcfg.flops_per_token()
    assert flops.param_count(cfg) == gcfg.param_count()
    need = flops.flash_attention_train_need(cfg, batch=8, seq=gcfg.seq)
    full_square = 3 * cfg["n_layer"] * 4 * gcfg.seq * cfg["n_embd"] * 8 * gcfg.seq
    assert 0.5 < need["flops"] / full_square < 0.6     # causal half, 7 of 6 matmuls
    table = peaks.peaks_for("TPU v5 lite")
    least = flops.roofline_seconds(need, table)
    assert least["bound"] in ("compute", "memory") and least["seconds"] > 0
    assert flops.mfu(1.0, table["bf16_flops_per_s"], table, chips=1) == 1.0


def test_an_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in peaks.json"):
        peaks.peaks_for("TPU v9 imaginary")


def test_reference_forward_against_cm_forward_at_tiny():
    """The plain reference and the program's forward on the same seeded
    weights, GPT2Config.tiny(): f32 reference against the program's f32
    compute, so the tolerance is rounding of a 2-layer f32 forward (1e-3 on
    logits of scale ~1); a wrong head split, mask, gelu or layer norm is
    off by 1e-1 and more."""
    from families import family_of
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel

    cfg = mf.read_named("configs", "gpt2-tiny")
    family = family_of(cfg)
    ff = FFConfig(batch_size=2, seed=3, strategy_cache=False,
                  log_level="warning", mesh_shape={"data": 1})
    model = FFModel(ff)
    gcfg = family.build(model, cfg, batch=2)
    cm = model.compile(AdamOptimizer(alpha=1e-3),
                       loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=3)
    (ids, pos), labels = traffic.stride_dataset(gcfg.vocab, gcfg.seq, 2, 3)
    got = np.asarray(cm.forward(ids, pos), np.float32)
    params = family.reference_params(cm.params, cfg)
    want = np.asarray(reference_gpt2.forward(params, ids, pos, gcfg.heads))
    assert got.shape == want.shape == (2, gcfg.seq, gcfg.vocab)
    assert np.abs(got - want).max() <= 1e-3 * max(1.0, np.abs(want).max())
    loss = float(reference_gpt2.next_token_loss(params, ids, pos, labels, gcfg.heads))
    assert abs(loss - math.log(gcfg.vocab)) < 1.0
    gap, _scale = family.reference_token_gaps(cfg, cm.params, ids, pos)
    assert gap.shape == (2, gcfg.seq - 1) and float(gap.min()) >= 0.0


def test_run_py_prints_no_result_without_a_tpu():
    done = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "gpt2-medium.train-b8", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=CPU_ENV, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "needs a TPU" in done.stderr
    assert '"metrics"' not in done.stdout and '"correct"' not in done.stdout


@pytest.mark.parametrize("cell", ["gpt2-tiny.train", "gpt2-tiny.serve"])
def test_rehearsal_runs_the_cells_control_flow_and_reports_no_metric(cell):
    """The whole of run.py on the CPU backend at a tiny size: the program's
    outputs are checked against the reference, and no number is printed
    under a metric's name."""
    done = subprocess.run(
        [sys.executable, str(BENCH / "rehearse.py"), "--workload", cell,
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=CPU_ENV, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
    assert "setup_s" in last["would_report"]
