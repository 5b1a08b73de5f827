"""Per-op performance attribution (ISSUE 7): op-level measured vs predicted
vs roofline joins, the per-op drift top-K, the telemetry→dataset pipeline,
and the CI wiring of the new tools' --check smokes.

Acceptance anchors: per-op attributed times sum to the measured step time
within attribution.SUM_TOLERANCE on the gpt2 CPU twin (single-device data
mesh, sharded mesh, and pipelined S=2), dataset rows round-trip through
span_dataset with stable feature keys, and the drift top-K is populated
after a fit with telemetry on.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import profile_attribution
import span_dataset
import trace_report

from flexflow_tpu import (FFConfig, FFModel, LossType, SGDOptimizer,
                          attribution, telemetry as tel)
from flexflow_tpu.models import GPT2Config, build_gpt2


def _gpt2_twin_fit(tmp_path, tag, epochs=2, profile_ops=False, **cfg_kw):
    """Tiny gpt2 CPU twin fit with telemetry on; returns (cm, tdir)."""
    tdir = str(tmp_path / f"tele_{tag}")
    cfg = FFConfig(batch_size=8, only_data_parallel=True,
                   telemetry_dir=tdir, profile_ops=profile_ops,
                   log_level="warning", **cfg_kw)
    m = FFModel(cfg)
    gcfg = GPT2Config(vocab=128, seq=8, d_model=32, heads=2, layers=1,
                      dropout=0.0)
    build_gpt2(m, gcfg, batch=8)
    cm = m.compile(SGDOptimizer(lr=0.01),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, size=(32, 8)).astype(np.int32)
    pos = np.broadcast_to(np.arange(8, dtype=np.int32), (32, 8)).copy()
    y = rng.integers(0, 128, size=(32, 8)).astype(np.int32)
    cm.fit([ids, pos], y, epochs=epochs, verbose=False)
    return cm, tdir


def _assert_report_shape(report):
    """Every row carries predicted cost, measured time, roofline bound and
    MFU; attributed times sum to the measured step within tolerance."""
    rows = report["rows"]
    assert rows
    for r in rows:
        for k in ("predicted_s", "measured_s", "attributed_s",
                  "roofline_s", "mfu", "mfu_ceiling"):
            assert isinstance(r[k], float), (k, r)
        assert r["bound"] in ("compute", "bandwidth"), r
        assert r["roofline_s"] >= 0.0
        assert r["key"] == attribution.feature_key(r["features"])
    step = report["step_time_s"]
    assert step and step > 0
    att = report["attributed_total_s"]
    assert abs(att - step) / step <= attribution.SUM_TOLERANCE, (att, step)


# ------------------------------------------------------- single-device path
def test_attribution_gpt2_twin(devices, tmp_path):
    cm, tdir = _gpt2_twin_fit(tmp_path, "single")
    report = cm.op_attribution(print_table=False)
    _assert_report_shape(report)
    # the drift top-K names the worst-mispriced op
    td = report["top_drift"]
    assert td["rows"] and td["rows"][0]["layer"]
    assert 0.0 < td["explained"] <= 1.0 + 1e-9
    # attribution emitted the op/attr corpus events
    tel.flush()
    evs = tel.read_events(tdir)
    assert any(e.get("name") == attribution.OP_EVENT for e in evs)
    assert any(e.get("name") == attribution.DRIFT_EVENT for e in evs)
    tel.shutdown()


def test_attribution_without_fit_uses_isolated_times(devices, tmp_path):
    """No fit yet -> no measured step time: attributed == isolated
    measured (scale 1), still a complete per-op roofline/MFU join."""
    cfg = FFConfig(batch_size=8, only_data_parallel=True,
                   log_level="warning")
    m = FFModel(cfg)
    x = m.create_tensor([8, 16], name="x")
    m.dense(m.dense(x, 32, activation="relu", name="fc1"), 4, name="fc2")
    cm = m.compile(SGDOptimizer(),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    report = cm.op_attribution(print_table=False)
    assert report["step_time_s"] is None and report["scale"] == 1.0
    for r in report["rows"]:
        assert r["attributed_s"] == r["measured_s"]
        assert r["bound"] in ("compute", "bandwidth")


# ------------------------------------------------------------- sharded path
def test_attribution_sharded_with_search_stamps(devices, tmp_path):
    """Searched compile on a data x model mesh: the strategy carries the
    DP's per-op predicted costs, attribution joins against them, and the
    warm (cached) compile restores the stamp."""
    def compile_once(tag):
        cfg = FFConfig(batch_size=8, mesh_shape={"data": 4, "model": 2},
                       search_budget=16, telemetry_dir="",
                       log_level="warning",
                       strategy_cache_dir=str(tmp_path / "cache"))
        m = FFModel(cfg)
        x = m.create_tensor([8, 16], name="x")
        h = m.dense(x, 64, activation="relu", name="up")
        m.dense(h, 16, name="down")
        return m.compile(SGDOptimizer(),
                         LossType.SPARSE_CATEGORICAL_CROSSENTROPY)

    cm = compile_once("cold")
    stamped = getattr(cm.strategy, "_predicted_op_costs", None)
    assert stamped, "search did not stamp per-op predicted costs"
    assert all(v > 0 for v in stamped.values())
    report = cm.op_attribution(print_table=False)
    by_layer = {r["layer"]: r for r in report["rows"]}
    for lname, cost in stamped.items():
        if lname in by_layer:
            assert by_layer[lname]["predicted_s"] == pytest.approx(cost)
    # warm compile: the cache restores the per-op stamp with the strategy
    cm2 = compile_once("warm")
    info = cm2.search_cache_info
    assert info and info.get("event") == "hit"
    assert getattr(cm2.strategy, "_predicted_op_costs", None) == stamped


# ----------------------------------------------------------- pipelined path
def test_attribution_pipelined_s2(devices, tmp_path):
    tdir = str(tmp_path / "tele_pipe")
    cfg = FFConfig(batch_size=8, only_data_parallel=True, seed=3,
                   pipeline_stages=2, pipeline_schedule="1f1b",
                   accum_steps=4, telemetry_dir=tdir, log_level="warning")
    m = FFModel(cfg)
    t = m.create_tensor([8, 64], name="x")
    h = m.dense(t, 256, activation="gelu", name="up")
    h = m.dense(h, 64, name="down")
    h = m.dense(h, 128, activation="relu", name="mid")
    m.dense(h, 8, name="head")
    cm = m.compile(SGDOptimizer(lr=0.05),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 64)).astype(np.float32)
    y = rng.integers(0, 8, size=(32,)).astype(np.int32)
    cm.fit([x], y, epochs=2, verbose=False)
    report = cm.op_attribution(print_table=False)
    _assert_report_shape(report)
    assert {r["stage"] for r in report["rows"]} == {0, 1}
    assert report["top_drift"]["rows"]
    tel.shutdown()


# ------------------------------------------------- telemetry -> dataset
def test_span_dataset_roundtrip_from_profiled_fit(devices, tmp_path):
    cm, tdir = _gpt2_twin_fit(tmp_path, "corpus", profile_ops=True)
    tel.flush()
    out = str(tmp_path / "corpus.jsonl")
    rows = span_dataset.build(tdir, out_path=out, quiet=True)
    assert rows, "profiled fit (--profile-ops) grew no corpus"
    back = span_dataset.read_jsonl(out)
    assert len(back) == len(rows)
    for r in back:
        # stable feature keys: recomputing from the round-tripped features
        # reproduces the dedup key
        assert attribution.feature_key(r["features"]) == r["key"]
        assert r["n"] >= 1 and r["measured_s"]["mean"] is not None
        assert r["predicted_s"] is not None
        assert r["roofline_s"] is not None
    # identical ops across the model (none in the 1-block twin's blocks,
    # but keys must at least be unique per row)
    assert len({r["key"] for r in back}) == len(back)
    # trace_report surfaces the same events in its [ops] section
    rep = trace_report.render(tdir, out_path=None, quiet=True)
    assert rep["ops"], "trace_report found no op/attr rows"
    assert rep["op_drift"], "trace_report found no op/drift_topk event"
    tel.shutdown()


def test_feature_key_dedups_structural_twins(devices):
    """Two identically-shaped layers (different names) produce the SAME
    feature key — the corpus dedups structural twins — while a different
    shape changes the key."""
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.search.candidates import layer_candidates

    cfg = FFConfig(batch_size=8, only_data_parallel=True,
                   log_level="warning")
    m = FFModel(cfg)
    x = m.create_tensor([8, 16], name="x")
    h = m.dense(x, 16, name="twin_a")
    h = m.dense(h, 16, name="twin_b")
    m.dense(h, 4, name="odd_one")
    machine = MachineSpec.detect()
    keys = {}
    for lname in ("twin_a", "twin_b", "odd_one"):
        layer = m.get_layer_by_name(lname)
        cand = layer_candidates(layer, machine, {8})[0]
        keys[lname] = attribution.feature_key(
            attribution.op_features(layer, cand, machine))
    assert keys["twin_a"] == keys["twin_b"]
    assert keys["odd_one"] != keys["twin_a"]


# --------------------------------------------------------- trace primary path
def test_measured_from_trace_boundary_and_normalization(devices, tmp_path):
    """The --profiling trace path: events map to layers only on exact
    "<name>/" path segments (no prefix/substring bleed — "up" must not
    absorb "update"), and build_report normalizes the WHOLE-RUN trace
    totals onto the measured per-update step time."""
    pdir = tmp_path / "prof" / "plugins" / "profile" / "run1"
    pdir.mkdir(parents=True)
    events = [
        # 3 steps of the same two ops (whole-run totals 300us and 600us)
        *[{"ph": "X", "ts": i * 1000.0, "dur": 100.0,
           "name": f"jit(train_step)/up/dot_general.{i}"}
          for i in range(3)],
        *[{"ph": "X", "ts": i * 1000.0 + 500, "dur": 200.0,
           "name": f"jit(train_step)/down/dot_general.{i}"}
          for i in range(3)],
        # must NOT be credited to layer "up": not a "<name>/" segment
        {"ph": "X", "ts": 9000.0, "dur": 5000.0, "name": "update/adam"},
        {"ph": "X", "ts": 9500.0, "dur": 5000.0, "name": "warmup/copy"},
        {"ph": "i", "ts": 0.0, "name": "up/instant_without_dur"},
    ]
    with open(pdir / "host.trace.json", "w") as f:
        json.dump({"traceEvents": events}, f)

    totals = attribution.measured_from_trace(
        str(tmp_path / "prof"), ["up", "down"])
    assert totals == {"up": 300.0, "down": 600.0}

    cfg = FFConfig(batch_size=8, only_data_parallel=True,
                   log_level="warning")
    m = FFModel(cfg)
    x = m.create_tensor([8, 16], name="x")
    m.dense(m.dense(x, 32, activation="relu", name="up"), 4, name="down")
    cm = m.compile(SGDOptimizer(),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    items = [{"layer": m.get_layer_by_name(n),
              "cand": cm._candidate_for(m.get_layer_by_name(n)),
              "machine": cm.machine, "predicted_s": None, "stage": None}
             for n in ("up", "down")]
    report = attribution.build_report(
        items, step_time_s=0.009, profile_dir=str(tmp_path / "prof"),
        source="trace", emit=False)
    assert report["source"] == "trace"
    by = {r["layer"]: r for r in report["rows"]}
    # per-update measured = stream share x step time (1/3 and 2/3 of 9ms)
    assert by["up"]["measured_s"] == pytest.approx(0.003)
    assert by["down"]["measured_s"] == pytest.approx(0.006)
    assert report["attributed_total_s"] == pytest.approx(0.009)
    # trace source without a measured step time is an explicit error;
    # "auto" silently falls back to the re-execution path
    with pytest.raises(ValueError, match="step"):
        attribution.build_report(items, step_time_s=None,
                                 profile_dir=str(tmp_path / "prof"),
                                 source="trace", emit=False)
    rep2 = attribution.build_report(items, step_time_s=None,
                                    profile_dir=str(tmp_path / "prof"),
                                    source="auto", emit=False)
    assert rep2["source"] == "measure"


# ------------------------------------------------------ probe -> telemetry
def test_perf_probe_emits_into_sink(tmp_path):
    """tools/perf_probe.py lands its measurements in the span stream when
    a sink is active (stdout-only otherwise) — unit-level: the emit helper
    with a fake measurement dict."""
    import perf_probe

    out = {"adam_step_ms": 12.5, "sgd_step_ms": 10.0, "fwd_only_ms": 4.0,
           "identity_loss_step_ms": 11.0, "optimizer_delta_ms": 2.5,
           "ce_delta_ms": 1.5, "bwd_update_ms": 8.5}
    # no sink: a no-op
    tel.shutdown()
    perf_probe._emit_telemetry(dict(out), iters=2, windows=1)
    tdir = str(tmp_path / "tele_probe")
    tel.configure(tdir)
    perf_probe._emit_telemetry(dict(out), iters=2, windows=1)
    tel.flush()
    evs = tel.read_events(tdir)
    spans = [e for e in evs if e.get("ph") == "X"
             and str(e.get("name", "")).startswith("probe/")]
    names = {e["name"] for e in spans}
    assert names == {"probe/adam_step", "probe/sgd_step", "probe/fwd_only",
                     "probe/identity_loss_step"}, names
    for e in spans:
        assert e["dur"] == pytest.approx(e["args"]["step_ms"] * 1e3,
                                         rel=1e-6)
    assert any(e.get("name") == "probe/summary" for e in evs)
    tel.shutdown()


# ------------------------------------------------------------- CI wiring
def test_span_dataset_check_smoke():
    """tools/span_dataset.py --check wired into tier-1 (the --check
    convention of the operator tools)."""
    assert span_dataset.main(["--check"]) == 0
    assert not tel.enabled()


def test_profile_attribution_check_smoke():
    """tools/profile_attribution.py --check: the ISSUE 7 acceptance chain
    (attributed sums to step within 15%, full rows, drift top-K named,
    non-empty corpus) on the gpt2 CPU twin."""
    assert profile_attribution.main(["--check"]) == 0
    assert not tel.enabled()
