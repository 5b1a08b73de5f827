"""Learned cost model (ISSUE 14): train/predict round-trip with a stable
content-hash fingerprint, per-op OOD fallback to the analytic price
(coverage < 1), winner-safe candidate pruning, strategy-cache invalidation
when a refit changes the model fingerprint, the telemetry->refit loop, and
the new config knobs.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import refit_cost_model
import span_dataset

from flexflow_tpu import FFConfig, FFModel, telemetry as tel
from flexflow_tpu.attribution import OP_EVENT, feature_key
from flexflow_tpu.parallel.machine import MachineSpec
from flexflow_tpu.search import learned_cost as lc
from flexflow_tpu.search import memo
from flexflow_tpu.search import strategy_cache as sc
from flexflow_tpu.search.candidates import layer_candidates
from flexflow_tpu.search.dp import SEARCH_STATS, reset_search_stats
from flexflow_tpu.search.optimize import graph_optimize

V5P8 = MachineSpec(mesh_axes={"data": 4, "model": 2}, chip="v5p")


@pytest.fixture(autouse=True)
def _fresh_fastpath():
    memo.clear()
    reset_search_stats()
    yield
    memo.clear()


# ------------------------------------------------------- synthetic corpus
def _features(i, kind="linear", n=64):
    """A 2008.01040-style feature dict whose sizes scale with n (so the
    log-space ridge has real signal to fit)."""
    return {"op": kind, "dtype": "float32",
            "in_shapes": [[8, n]], "out_shapes": [[8, 2 * n]],
            "weight_shapes": {"kernel": [n, 2 * n]},
            "sharding": {"out": [["data"], []],
                         "weights": {"kernel": [[], []]}},
            "machine": "m0", "name": f"op{i}"}


def _row(i, kind="linear", n=64, measured=None):
    feats = _features(i, kind, n)
    m = measured if measured is not None else 2e-9 * n * n
    return {"schema_version": span_dataset.SCHEMA_VERSION,
            "key": feature_key(feats), "features": feats, "machine": "m0",
            "n": 3, "measured_s": {"mean": m},
            "predicted_s": m * 0.5, "roofline_s": m * 0.25}


def _corpus(k=8):
    return [_row(i, n=32 * (i + 1)) for i in range(k)]


# ------------------------------------------------------- train / predict
def test_train_predict_roundtrip(tmp_path):
    rows = _corpus()
    model = lc.train(rows)
    assert "linear" in model.kinds
    assert model.meta["rows"] == len(rows)
    # a corpus row's key is a measurement: the exact table returns its mean
    assert model.predict_row(rows[0]) == rows[0]["measured_s"]["mean"]
    # an unseen key of a FITTED kind goes through the ridge; with the
    # analytic times riding along as features the residual fit lands close
    q = _row(99, n=48)
    q["key"] = "unseen-key"
    pred = model.predict_row(q)
    truth = q["measured_s"]["mean"]
    assert pred is not None and abs(pred - truth) / truth < 0.5
    # an unseen KIND is OOD: the model says None, the caller falls back
    assert model.predict_features(_features(0, kind="conv2d")) is None
    # save/load round-trips the fingerprint and the predictions
    mp = str(tmp_path / "cm.json")
    fp = model.save(mp)
    loaded = lc.LearnedCostModel.load(mp)
    assert loaded.fingerprint == fp == model.fingerprint
    assert loaded.predict_row(q) == pytest.approx(pred)
    # content-hash fingerprint: same data -> same hash, new data -> new hash
    assert lc.train(rows).fingerprint == fp
    assert lc.train(_corpus(9)).fingerprint != fp
    # schema mismatches fail loud, not with a silently wrong model
    payload = loaded.to_json()
    payload["schema_version"] = 99
    with pytest.raises(ValueError, match="schema"):
        lc.LearnedCostModel.from_json(payload)


def test_train_skips_unusable_and_small_kinds():
    rows = _corpus(6)
    rows.append(_row(50, kind="layer_norm", n=64))  # 1 row < MIN_ROWS_PER_KIND
    rows.append({"key": "broken", "features": None,
                 "measured_s": {"mean": None}})
    model = lc.train(rows)
    assert model.meta["kinds_fitted"] == ["linear"]
    # the lone layer_norm row still serves via the exact table...
    assert model.predict_row(rows[6]) == rows[6]["measured_s"]["mean"]
    # ...but an unseen layer_norm placement is OOD
    assert model.predict_features(_features(51, kind="layer_norm",
                                            n=128)) is None


# ------------------------------------------- OOD fallback on a real graph
def _probe_model(batch=16):
    m = FFModel(FFConfig(batch_size=batch))
    x = m.create_tensor([batch, 64], name="x")
    h = m.dense(x, 128, activation="gelu", name="fc1")
    h = m.layer_norm(h, name="ln")
    m.dense(h, 32, name="fc2")
    return m


def test_learned_cost_ood_falls_back_to_analytic():
    """ISSUE 14 satellite: an op kind the model never saw (layer_norm here
    — the corpus is all linear) is priced by the analytic roofline
    per-op, coverage() reports the learned fraction < 1, and every
    returned time stays positive and finite."""
    model = lc.train(_corpus())
    lcost = lc.LearnedCost(model, V5P8)
    m = _probe_model()
    kinds_priced = set()
    for layer in m.layers:
        for cand in layer_candidates(layer, V5P8, {16}):
            if cand.passthrough:
                continue
            t = lcost.op_time(layer, cand)
            assert 0.0 <= t < 1e6
            kinds_priced.add(layer.op_type.name)
    assert lcost.hits > 0, "dense ops must be learned-priced"
    assert lcost.misses > 0, "layer_norm must fall back to analytic"
    assert 0.0 < lcost.coverage() < 1.0
    assert "LAYERNORM" in kinds_priced


def test_prune_candidates_keeps_escape_hatches():
    model = lc.train(_corpus())
    lcost = lc.LearnedCost(model, V5P8)
    m = _probe_model()
    fc1 = next(l for l in m.layers if l.name == "fc1")
    cands = layer_candidates(fc1, V5P8, {16})
    kept, dropped = lcost.prune_candidates(fc1, cands)
    assert len(kept) + dropped == len(cands)
    # passthroughs always survive, and so does the learned-best candidate
    assert all(c in kept for c in cands if c.passthrough)
    timed = [(lcost._predict(fc1, c)[0], c) for c in cands
             if not c.passthrough]
    assert min(timed, key=lambda tc: tc[0])[1] in kept
    # the ratio knob is the off switch
    lcost.prune_ratio = None
    assert lcost.prune_candidates(fc1, cands) == (cands, 0)


# ------------------------------------ strategy cache: refit invalidation
def _mlp(cache_dir, model_path, mode="learned", batch=32):
    m = FFModel(FFConfig(batch_size=batch, search_budget=8,
                         strategy_cache_dir=str(cache_dir),
                         simulator_mode=mode, cost_model_path=model_path,
                         log_level="warning"))
    x = m.create_tensor([batch, 512], name="x")
    h = m.dense(x, 1024, activation="gelu", name="up")
    h = m.dense(h, 512, name="down")
    m.dense(h, 16, name="head")
    return m


def test_refit_invalidates_strategy_cache(tmp_path):
    """ISSUE 14 satellite: the cache key carries the learned model's
    content fingerprint — warm hit before a refit, miss + re-search after
    the model file changes (a stale model must never serve its old
    strategies)."""
    mp = str(tmp_path / "cm.json")
    lc.train(_corpus()).save(mp)
    cache = tmp_path / "sc"
    st1 = graph_optimize(_mlp(cache, mp), V5P8)
    assert st1._cache_info["event"] == "store"
    assert SEARCH_STATS["expansions"] > 0 and st1.op_shardings
    fp_before = sc.learned_fingerprint(mp)
    # warm: same model file -> hit, zero DP work
    memo.clear()
    reset_search_stats()
    st2 = graph_optimize(_mlp(cache, mp), V5P8)
    assert st2._cache_info["event"] == "hit"
    assert SEARCH_STATS["calls"] == 0
    assert json.loads(json.dumps(st1.to_json())) == \
        json.loads(json.dumps(st2.to_json()))
    # refit: new corpus -> new coefficients -> new file hash -> miss
    lc.train(_corpus(10)).save(mp)
    assert sc.learned_fingerprint(mp) != fp_before
    memo.clear()
    reset_search_stats()
    st3 = graph_optimize(_mlp(cache, mp), V5P8)
    assert st3._cache_info["event"] == "store"
    assert SEARCH_STATS["calls"] > 0


def test_learned_fingerprint_states(tmp_path):
    assert sc.learned_fingerprint(None) == ""
    assert sc.learned_fingerprint("") == ""
    assert sc.learned_fingerprint(str(tmp_path / "nope.json")) == \
        "learned:absent"
    mp = str(tmp_path / "cm.json")
    lc.train(_corpus()).save(mp)
    fp = sc.learned_fingerprint(mp)
    assert fp.startswith("learned:") and fp != "learned:absent"
    # the no-model cache key is bitwise-identical to the pre-ISSUE-14 key:
    # learned_fp only ever APPENDS to the parts tuple
    m = _mlp(tmp_path / "sc", "", mode="additive")
    base = sc.cache_key(m, V5P8, m.config, "", "")
    assert sc.cache_key(m, V5P8, m.config, "", "", learned_fp="") == base
    assert sc.cache_key(m, V5P8, m.config, "", "", learned_fp=fp) != base


def test_load_for_config_gate(tmp_path):
    """Every learned path is double-gated: --simulator-mode learned AND a
    readable model file. Missing either -> None -> bitwise-stock search."""
    mp = str(tmp_path / "cm.json")
    lc.train(_corpus()).save(mp)
    ok = lc.load_for_config(
        FFConfig(simulator_mode="learned", cost_model_path=mp), V5P8)
    assert ok is not None and ok.path == mp
    assert lc.load_for_config(
        FFConfig(simulator_mode="additive", cost_model_path=mp), V5P8) is None
    assert lc.load_for_config(
        FFConfig(simulator_mode="learned",
                 cost_model_path=str(tmp_path / "nope.json")), V5P8) is None
    # a corrupt model file degrades to stock, never crashes the search
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        f.write("{not json")
    assert lc.load_for_config(
        FFConfig(simulator_mode="learned", cost_model_path=bad), V5P8) is None


# ------------------------------------------------- telemetry -> refit loop
def _emit_synthetic_ops(tdir, k=5, scale=1.0):
    tel.configure(tdir)
    for i in range(k):
        feats = _features(i, n=32 * (i + 1))
        m = 2e-9 * (32 * (i + 1)) ** 2 * scale
        tel.event(OP_EVENT, cat="profile", key=feature_key(feats),
                  features=feats, measured_s=m, predicted_s=m * 0.5,
                  roofline_s=m * 0.25, source="measure")
    tel.flush()


def test_refit_roundtrip_and_auto_refit(tmp_path):
    """tools/refit_cost_model.refit folds a telemetry dir through
    span_dataset into a saved model; auto_refit() is the same loop behind
    the --auto-refit + --telemetry-dir gate (the drift warning's
    self-calibration path)."""
    tdir = str(tmp_path / "tele")
    mp = str(tmp_path / "cm.json")
    cp = str(tmp_path / "corpus.jsonl")
    try:
        _emit_synthetic_ops(tdir)
        info = refit_cost_model.refit(tdir, model_path=mp, corpus_path=cp)
        assert info is not None and info["rows"] == 5
        assert "linear" in info["kinds"]
        assert os.path.exists(mp) and os.path.exists(cp)
        model = lc.LearnedCostModel.load(mp)
        assert model.fingerprint == info["fingerprint"]
        assert model.predict_row(_row(0, n=32)) is not None
        # re-running over the same telemetry is idempotent (merge pools
        # identical measurements -> identical model)
        info2 = refit_cost_model.refit(tdir, model_path=mp, corpus_path=cp)
        assert info2["fingerprint"] == info["fingerprint"]
        # auto_refit: gated on BOTH --telemetry-dir and --auto-refit
        assert lc.auto_refit(FFConfig(auto_refit=True)) is None
        assert lc.auto_refit(FFConfig(telemetry_dir=tdir)) is None
        mp2 = str(tmp_path / "cm2.json")
        info3 = lc.auto_refit(FFConfig(telemetry_dir=tdir, auto_refit=True,
                                       cost_model_path=mp2))
        assert info3 is not None and os.path.exists(mp2)
    finally:
        tel.shutdown()


def test_auto_refit_fires_after_op_attribution(devices, tmp_path):
    """--auto-refit runs AFTER the fit's op/attr emission — the refit must
    fold THIS run's rows, not last run's (ordering bug caught by the
    verify drive: hooked at _fit_end_report it saw an empty stream and
    refused to write). One profiled fit with the flag leaves a trained
    model on disk whose exact table carries the fit's own measurements."""
    import numpy as np

    from flexflow_tpu import SGDOptimizer

    mp = str(tmp_path / "cm.json")
    try:
        cfg = FFConfig(batch_size=16, only_data_parallel=True,
                       telemetry_dir=str(tmp_path / "tele"),
                       profile_ops=True, auto_refit=True,
                       cost_model_path=mp, epochs=1, log_level="warning")
        m = FFModel(cfg)
        x = m.create_tensor([16, 32], name="x")
        m.dense(m.dense(x, 64, activation="relu", name="up"), 4, name="head")
        m.compile(SGDOptimizer(lr=0.05),
                  loss_type="sparse_categorical_crossentropy", metrics=[])
        m.fit(np.zeros((32, 32), np.float32), np.zeros((32,), np.int32))
    finally:
        tel.shutdown()
    assert os.path.exists(mp), "--auto-refit left no model after a " \
        "profiled fit"
    model = lc.LearnedCostModel.load(mp)
    assert model.exact and model.meta["rows"] > 0


def test_refit_empty_telemetry_never_clobbers_model(tmp_path):
    tdir = str(tmp_path / "tele")
    os.makedirs(tdir)
    mp = str(tmp_path / "cm.json")
    fp = lc.train(_corpus()).save(mp)
    assert refit_cost_model.refit(tdir, model_path=mp,
                                  corpus_path=str(tmp_path / "c.jsonl")) \
        is None
    assert lc.LearnedCostModel.load(mp).fingerprint == fp


# ------------------------------------------------------------ config wiring
def test_learned_flags_wired():
    """The ISSUE-14 knobs flow parse_args -> FFConfig via build_parser only
    (the launcher's value-flag set derives automatically): the learned
    simulator tier, the model path override, and the auto-refit gate."""
    cfg = FFConfig.parse_args(["--simulator-mode", "learned",
                               "--cost-model-path", "/tmp/cm.json",
                               "--auto-refit"])
    assert cfg.simulator_mode == "learned"
    assert cfg.cost_model_path == "/tmp/cm.json"
    assert cfg.auto_refit is True
    d = FFConfig()
    assert d.simulator_mode == "additive"  # learned is an explicit opt-in
    assert d.cost_model_path == ""         # "" -> env var -> checkout default
    assert d.auto_refit is False
    with pytest.raises(SystemExit):
        FFConfig.parse_args(["--simulator-mode", "psychic"])
    vf = FFConfig.launcher_value_flags()
    assert "--cost-model-path" in vf
    assert "--simulator-mode" in vf
    assert "--auto-refit" not in vf        # the gate takes no value token
    # the path resolution order: flag > env > default
    assert lc.resolve_model_path(cfg) == "/tmp/cm.json"
    old = os.environ.pop("FF_COST_MODEL_PATH", None)
    try:
        os.environ["FF_COST_MODEL_PATH"] = "/tmp/env.json"
        assert lc.resolve_model_path(d) == "/tmp/env.json"
        del os.environ["FF_COST_MODEL_PATH"]
        assert lc.resolve_model_path(d).endswith(
            os.path.join(".ff_cache", "cost_model.json"))
    finally:
        if old is not None:
            os.environ["FF_COST_MODEL_PATH"] = old


# --------------------------------------------------------------- CI smokes
def test_refit_cost_model_check_smoke():
    """tools/refit_cost_model.py --check: profiled fit -> corpus -> model
    -> reload -> predict, twice (the --check convention of span_dataset)."""
    assert refit_cost_model.main(["--check"]) == 0
    assert not tel.enabled()
