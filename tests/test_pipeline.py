"""Pipeline-parallel execution (parallel/pipeline.py, compiler pipeline
path, bubble-aware search): schedule numerics vs the sequential accum loop
(SGD + Adam, dropout rng parity, steps_per_dispatch fusion parity),
stage-sharded memory, cross-mesh checkpoint restore, the memory-capped DP
selection (MULTICHIP-style assertion), and schedule-grid invariants."""

import os

import numpy as np
import pytest

from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.losses import LossType


def _mlp(cfg, batch):
    m = FFModel(cfg)
    t = m.create_tensor([batch, 64], name="x")
    h = m.dense(t, 256, activation="gelu", name="up")
    h = m.dense(h, 64, name="down")
    h = m.dense(h, 128, activation="relu", name="mid")
    m.dense(h, 8, name="head")
    return m


def _gpt2(cfg, batch, dropout=0.0):
    from flexflow_tpu.models import GPT2Config, build_gpt2

    m = FFModel(cfg)
    build_gpt2(m, GPT2Config(vocab=512, seq=16, d_model=64, heads=2,
                             layers=2, dropout=dropout), batch=batch)
    return m


def _data(kind, n, rng):
    if kind == "gpt2":
        ids = rng.integers(0, 512, size=(n, 16)).astype(np.int32)
        pos = np.broadcast_to(np.arange(16, dtype=np.int32), (n, 16)).copy()
        y = rng.integers(0, 512, size=(n, 16)).astype(np.int32)
        return [ids, pos], y
    x = rng.normal(size=(n, 64)).astype(np.float32)
    return [x], rng.integers(0, 8, size=(n,)).astype(np.int32)


def _train(kind, stages, accum=4, sched="1f1b", opt=None, zero="off",
           epochs=2, n=64, mesh=None, dropout=0.0,
           steps_per_dispatch=1):
    cfg = FFConfig(batch_size=8, only_data_parallel=True, seed=3,
                   pipeline_stages=stages, pipeline_schedule=sched,
                   accum_steps=accum, zero_sharding=zero,
                   steps_per_dispatch=steps_per_dispatch,
                   mesh_shape=mesh or {}, log_level="warning")
    m = _gpt2(cfg, 8, dropout) if kind == "gpt2" else _mlp(cfg, 8)
    cm = m.compile(opt or AdamOptimizer(alpha=0.01),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    x, y = _data(kind, n, np.random.default_rng(0))
    hist = cm.fit(x, y, epochs=epochs, verbose=False)
    return cm, hist


# ------------------------------------------------------ schedule numerics
@pytest.mark.parametrize("kind,opt_fn", [
    ("mlp", lambda: SGDOptimizer(lr=0.05)),
    ("mlp", lambda: AdamOptimizer(alpha=0.01)),
    ("gpt2", lambda: AdamOptimizer(alpha=0.01)),
])
def test_schedules_match_sequential_accum(devices, kind, opt_fn):
    """GPipe and 1F1B must train to the sequential accum loop's loss up to
    float reassociation (same data, seeds, per-microbatch rng streams,
    mean-of-M gradient, one update per group) — and the two schedules must
    match EACH OTHER bitwise (same ops, same order per stage pair)."""
    _, h_seq = _train(kind, 1, opt=opt_fn())
    _, h_g = _train(kind, 2, sched="gpipe", opt=opt_fn())
    _, h_f = _train(kind, 2, sched="1f1b", opt=opt_fn())
    assert h_g[-1]["loss"] == pytest.approx(h_seq[-1]["loss"], rel=1e-5)
    assert h_f[-1]["loss"] == h_g[-1]["loss"]


def test_dropout_rng_stream_parity(devices):
    """Dropout streams fold by layer guid and microbatch index, both of
    which stage partitioning preserves — the SAME model instance (guids
    fixed) compiled sequentially and pipelined must reproduce the same
    loss trajectory under dropout."""
    cfg = FFConfig(batch_size=8, only_data_parallel=True, seed=3,
                   accum_steps=4, log_level="warning")
    m = _gpt2(cfg, 8, dropout=0.1)
    x, y = _data("gpt2", 64, np.random.default_rng(0))

    def run():
        cm = m.compile(AdamOptimizer(alpha=0.01),
                       LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                       metrics=[])
        cm.init(seed=0)
        return cm.fit(x, y, epochs=2, verbose=False)

    h_seq = run()
    m.config.pipeline_stages = 2  # recompile the SAME graph pipelined
    h_p = run()
    assert h_p[-1]["loss"] == pytest.approx(h_seq[-1]["loss"], rel=1e-5)


def test_parity_with_fused_dispatch_baseline(devices):
    """rng parity under steps_per_dispatch fusion: the sequential baseline
    run through make_multi_step (K=2 fused updates per dispatch) and the
    pipeline consume the SAME per-iteration rng stream, so losses agree."""
    cm_seq, h_seq = _train("mlp", 1, steps_per_dispatch=2)
    assert cm_seq.step_stats["fused_steps"] > 0  # fusion engaged
    _, h_p = _train("mlp", 2)
    assert h_p[-1]["loss"] == pytest.approx(h_seq[-1]["loss"], rel=1e-5)


@pytest.mark.parametrize("kind,S", [("mlp", 4), ("gpt2", 2)])
def test_stage_weight_residency(devices, kind, S):
    """Per-stage weights live ONLY on the owning group — summing one
    representative device per stage reconstructs the model, and the max
    per-device share of parameters + optimizer state (live buffers)
    shrinks by at least S/2 vs the replicated S=1 twin."""
    cm1, h1 = _train(kind, 1, accum=8)
    cmS, hS = _train(kind, S, accum=8)
    assert hS[-1]["loss"] == pytest.approx(h1[-1]["loss"], rel=1e-5)
    m1, mS = cm1.memory_stats(), cmS.memory_stats()
    full = m1["actual_param_bytes_per_device"]
    # stage shares reassemble the model (tiny drift allowed: a divisible
    # bias may shard over data=8 at S=1 but not over a stage's data=2)
    assert sum(mS["per_stage_param_bytes"]) == pytest.approx(full,
                                                             rel=0.01)
    assert mS["actual_param_bytes_per_device"] <= full / (S / 2)
    assert (full + m1["actual_opt_state_bytes_per_device"]) >= (S / 2) * (
        mS["actual_param_bytes_per_device"]
        + mS["actual_opt_state_bytes_per_device"])
    # disjoint groups: every layer's weights on exactly one stage
    names = [set(p) for p in cmS.stage_params]
    for i in range(S):
        for j in range(i + 1, S):
            assert not (names[i] & names[j])


def test_zero_sharding_composes_with_stages(devices):
    """--zero-sharding inside a stage: moments shard over the STAGE's data
    axis on top of the stage split — opt bytes divide by stages x degree,
    and the loss stays on the replicated trajectory."""
    _, h_off = _train("mlp", 2)
    cm_z, h_z = _train("mlp", 2, zero="zero1")
    assert h_z[-1]["loss"] == pytest.approx(h_off[-1]["loss"], abs=1e-6)
    mz = cm_z.memory_stats()
    assert mz["zero_sharding"] == "zero1"
    # stage data degree is 4: sharded moments well under the params' bytes
    assert mz["actual_opt_state_bytes_per_device"] < \
        mz["actual_param_bytes_per_device"]


# ------------------------------------------------------------- checkpoint
def test_cross_mesh_checkpoint_restore_of_stage_sharded_state(devices,
                                                              tmp_path):
    """Save under stage mesh {data: 4}, restore under {pipe: 2, data: 2}:
    params + per-stage optimizer state re-shard onto the smaller stage
    meshes and training resumes on the identical trajectory."""
    import jax

    cm1, _ = _train("mlp", 2, zero="zero1", epochs=1)
    ck = str(tmp_path / "ck")
    cm1.save_checkpoint(ck, block=True)
    mu_saved = [np.asarray(cm1.stage_opt[s][0].mu[
        next(iter(cm1.stage_params[s]))]["kernel"]) for s in range(2)]
    x, y = _data("mlp", 64, np.random.default_rng(0))
    h_ref = cm1.fit(x, y, epochs=1, verbose=False)

    cfg = FFConfig(batch_size=8, only_data_parallel=True, seed=3,
                   pipeline_stages=2, accum_steps=4, zero_sharding="zero1",
                   mesh_shape={"pipe": 2, "data": 2}, log_level="warning")
    m = _mlp(cfg, 8)
    cm2 = m.compile(AdamOptimizer(alpha=0.01),
                    LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm2.init(seed=99)  # different init — must be overwritten
    cm2.load_checkpoint(ck)
    assert cm2._iteration == cm1._iteration - 2  # pre-second-fit counter
    # state landed in the NEW stage mesh's sharding
    w = cm2.stage_params[0][next(iter(cm2.stage_params[0]))]["kernel"]
    assert len(w.sharding.mesh.devices.flatten()) == 2
    # moments bitwise-identical to the SAVED ones after the re-shard
    for s in range(2):
        np.testing.assert_array_equal(
            mu_saved[s],
            np.asarray(cm2.stage_opt[s][0].mu[
                next(iter(cm2.stage_params[s]))]["kernel"]))
    h_res = cm2.fit(x, y, epochs=1, verbose=False)
    assert h_res[0]["loss"] == pytest.approx(h_ref[0]["loss"], rel=1e-6)


def test_stage_count_elastic_restore_legacy_rejected(devices, tmp_path):
    """Elastic resume (ISSUE 6) made stage count a placement detail: the
    per-layer optimizer schema restores a S=2 snapshot onto S=4
    (trajectory parity covered in tests/test_resilience.py). Only LEGACY
    stage-keyed checkpoints — no opt_schema marker — are still rejected,
    cleanly, with a re-save hint."""
    import json

    from flexflow_tpu.runtime.checkpoint import CheckpointMismatchError

    cm1, _ = _train("mlp", 2, epochs=1, n=32)
    ck = str(tmp_path / "ck")
    cm1.save_checkpoint(ck, block=True)
    cm4, _ = _train("mlp", 4, accum=8, epochs=1, n=32)
    cm4.load_checkpoint(ck)  # different stage count: elastic re-key
    assert cm4._iteration == cm1._iteration
    meta_path = os.path.join(ck, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    del meta["opt_schema"]  # forge a pre-elastic checkpoint
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with pytest.raises(CheckpointMismatchError, match="legacy"):
        cm4.load_checkpoint(ck)


# ---------------------------------------------------------------- search
def test_memory_capped_search_selects_pipelining(devices):
    """The MULTICHIP-style assertion: under a memory cap pure data
    parallelism cannot satisfy, the DP picks a pipelined strategy whose
    score (cost x over-HBM penalty) beats the best feasible non-pipelined
    candidate; uncapped, the same units still make the comparison fair."""
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.search.dp import (choose_pipeline, search_graph,
                                        search_pipelined, _score)

    cfg = FFConfig(batch_size=8, log_level="warning")
    model = _gpt2(cfg, 8)
    mach = MachineSpec(mesh_axes={"data": 8}, chip="v5e")
    r0 = search_graph(model, mach)
    cap = r0.mem_bytes * 0.6  # dp CANNOT fit: replicated weights too big
    best = choose_pipeline(model, mach, 8, stages_options=(1, 2, 4),
                           mem_budget=cap)
    assert best.stages > 1
    assert best.mem_bytes < r0.mem_bytes
    score_dp = _score(8 * r0.cost, r0.mem_bytes, cap)
    assert best.score < score_dp
    # the winning schedule was validated by the event replay: bubble set
    r2 = search_pipelined(model, mach, 2, 8, mem_budget=cap)
    assert 0.0 < r2.bubble < 1.0
    assert len(r2.cuts) == 1 and len(r2.stage_costs) == 2


def test_schedule_grid_invariants(devices):
    """Every (stage, phase, microbatch) op appears exactly once, the
    event replay validates all dependencies, balanced stages reproduce the
    (S-1)/(M+S-1) closed form, and 1f1b's in-flight stash is min(S, M)
    vs gpipe's M."""
    from flexflow_tpu.search import cost_model as cm
    from flexflow_tpu.search.simulator import simulate_pipeline

    for sched in ("gpipe", "1f1b"):
        for S, M in ((2, 4), (4, 8), (3, 2)):
            ticks = cm.pipeline_schedule(sched, S, M)
            ops = [op for row in ticks for op in row]
            assert len(ops) == len(set(ops)) == 2 * S * M
            rep = simulate_pipeline([1.0] * S, [2.0] * S, sched, M)
            assert rep["bubble"] == pytest.approx(
                cm.pipeline_bubble_fraction(sched, S, M), abs=1e-9)
    assert cm.pipeline_inflight_acts("gpipe", 4, 16) == 16
    assert cm.pipeline_inflight_acts("1f1b", 4, 16) == 4


def test_stage_cut_candidates_are_single_tensor_cuts(devices):
    from flexflow_tpu.core.graph import topo_order
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.search.candidates import stage_cut_candidates
    from flexflow_tpu.search.unity import sequence_cut_indices

    cfg = FFConfig(batch_size=8, log_level="warning")
    model = _gpt2(cfg, 8)
    mach = MachineSpec(mesh_axes={"data": 4}, chip="v5e")
    combos = stage_cut_candidates(model, mach, 2, max_candidates=6)
    assert combos
    ok = set(sequence_cut_indices(topo_order(model.layers),
                                  model.input_tensors))
    for combo in combos:
        assert len(combo) == 1 and combo[0] in ok


def test_strategy_cache_keys_on_pipeline_knobs(devices):
    """A strategy searched for one (stages, schedule, M) must never hit
    another's cache entry; plain compiles keep their hits across accum
    changes."""
    from flexflow_tpu.search.strategy_cache import knob_fingerprint

    base = FFConfig(batch_size=8)
    assert knob_fingerprint(base) == knob_fingerprint(
        FFConfig(batch_size=8, accum_steps=4))  # non-pipelined: accum free
    for other in (FFConfig(batch_size=8, pipeline_stages=2),
                  FFConfig(batch_size=8, pipeline_stages=2,
                           pipeline_schedule="gpipe"),
                  FFConfig(batch_size=8, pipeline_stages=2, accum_steps=4)):
        assert knob_fingerprint(other) != knob_fingerprint(base)
    assert knob_fingerprint(
        FFConfig(batch_size=8, pipeline_stages=2)) != knob_fingerprint(
        FFConfig(batch_size=8, pipeline_stages=2, accum_steps=4))


def test_strategy_pipeline_block_roundtrips(devices, tmp_path):
    from flexflow_tpu.parallel.sharding import Strategy

    st = Strategy(mesh_axes={"data": 4}, name="t",
                  pipeline={"stages": 2, "cuts": [3], "schedule": "gpipe"})
    path = str(tmp_path / "s.json")
    st.save(path)
    st2 = Strategy.load(path)
    assert st2.pipeline == {"stages": 2, "cuts": [3], "schedule": "gpipe"}


# ------------------------------------------------------ launcher satellite
def test_launcher_value_flags_derived_from_parser():
    """Satellite: the launcher's value-flag set is DERIVED from the
    FFConfig parser — every value-taking option of a freshly built parser
    must be covered (so adding a flag cannot silently break `python -m
    flexflow_tpu --new-flag VALUE train.py`), flag-only options must NOT
    consume a token, and the split logic must route each case."""
    from flexflow_tpu.__main__ import split_argv

    parser = FFConfig.build_parser()
    derived = FFConfig.launcher_value_flags()
    for action in parser._actions:
        for opt in action.option_strings:
            if action.nargs == 0:
                assert opt not in derived, opt
                assert split_argv([opt, "s.py"])[0] == "s.py"
            else:
                assert opt in derived, opt
                script, largs, sargs = split_argv([opt, "VAL", "s.py",
                                                   "tail"])
                assert script == "s.py", opt
                assert largs == [opt, "VAL"] and sargs == ["tail"]
    # the new pipeline knobs ride along automatically
    assert "--pipeline-stages" in derived
    assert "--pipeline-schedule" in derived


# ------------------------------------------------- review-hardening cases
def test_batchnorm_state_chains_under_both_schedules(devices):
    """Review class: the last stage's backward runs from the LIVE state —
    under gpipe a stashed pre-step state would replay every microbatch's
    BN running-stats update from the same base, losing M-1 of M. BN in
    the final stage must end with the sequential loop's chained stats."""
    def build(stages):
        cfg = FFConfig(batch_size=8, only_data_parallel=True, seed=3,
                       pipeline_stages=stages, accum_steps=4,
                       log_level="warning")
        m = FFModel(cfg)
        t = m.create_tensor([8, 64], name="x")
        h = m.dense(t, 256, activation="gelu", name="up")  # heavy stage 0
        h = m.batch_norm(h, relu=True, name="bn")
        m.dense(h, 8, name="head")
        cm = m.compile(SGDOptimizer(lr=0.05),
                       LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                       metrics=[])
        cm.init(seed=0)
        return cm

    x, y = _data("mlp", 64, np.random.default_rng(0))
    states = {}
    for mode, stages in (("seq", 1), ("gpipe", 2), ("1f1b", 2)):
        cm = build(stages)
        if stages > 1:
            cm.schedule = mode
            # the balance heuristic must have put BN in the LAST stage or
            # this test exercises nothing
            assert any(l.name == "bn" for l in cm.stage_layers[-1])
        cm.fit(x, y, epochs=1, verbose=False)
        st = cm.state if stages == 1 else \
            {k: v for d in cm.stage_state for k, v in d.items()}
        states[mode] = {k: np.asarray(v) for k, v in st.items()}
    assert states["seq"], "BN produced no running state?"
    for mode in ("gpipe", "1f1b"):
        for k, v in states["seq"].items():
            np.testing.assert_allclose(states[mode][k], v, rtol=1e-6,
                                       err_msg=f"{mode}:{k}")


def test_regularizer_loss_reported_from_every_stage(devices):
    """Review class: an l2 penalty on a stage-0 weight must show up in the
    pipelined history loss exactly as it does sequentially (the gradients
    carried it either way; the REPORTED loss must too)."""
    def run(stages):
        cfg = FFConfig(batch_size=8, only_data_parallel=True, seed=3,
                       pipeline_stages=stages, accum_steps=4,
                       log_level="warning")
        m = _mlp(cfg, 8)
        m.add_weight_regularizer("up", "kernel", "l2", 0.01)
        cm = m.compile(SGDOptimizer(lr=0.05),
                       LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                       metrics=[])
        cm.init(seed=0)
        x, y = _data("mlp", 64, np.random.default_rng(0))
        return cm.fit(x, y, epochs=2, verbose=False)

    h_seq = run(1)
    h_p = run(2)
    assert h_p[-1]["loss"] == pytest.approx(h_seq[-1]["loss"], rel=1e-5)
    # the penalty is material in this setup — parity is not vacuous
    assert h_seq[-1]["loss"] > 1.0


def test_unsorted_imported_cuts_are_normalized(devices):
    """Review class: a hand-edited strategy JSON may list cuts out of
    order; stage/boundary pairing must not silently cross wires."""
    cm, _ = _train("mlp", 2, epochs=1, n=32)
    st = cm.strategy
    # 3-stage partition with cuts listed REVERSED
    from flexflow_tpu.search.unity import sequence_cut_indices
    from flexflow_tpu.core.graph import topo_order

    ok = sorted(sequence_cut_indices(topo_order(cm.model.layers),
                                     cm.model.input_tensors))
    assert len(ok) >= 2
    cfg = FFConfig(batch_size=8, only_data_parallel=True, seed=3,
                   pipeline_stages=2, accum_steps=2, log_level="warning")
    m = _mlp(cfg, 8)
    st2 = type(st)(mesh_axes=dict(st.mesh_axes), name="t",
                   pipeline={"stages": 3, "cuts": [ok[1], ok[0]],
                             "schedule": "1f1b"})
    cfg.pipeline_stages = 3
    from flexflow_tpu.parallel.pipeline import PipelinedModel
    from flexflow_tpu.parallel.machine import MachineSpec

    mach = MachineSpec.detect({"data": 8})
    stage_mach = MachineSpec(mesh_axes={"data": 2}, chip=mach.chip)
    pm = PipelinedModel(m, mach, stage_mach, st2, SGDOptimizer(lr=0.05),
                        LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [],
                        m.layers[-1].outputs[:1])
    assert pm.cuts == sorted(pm.cuts)
    # boundaries pair with ascending cuts: stage s's declared output IS a
    # tensor stage s produces
    for s in range(2):
        assert pm.boundaries[s].owner in pm.stage_layers[s]


def test_warm_cache_skips_pipelined_search(devices, tmp_path):
    """Review class: the cut search's result is re-stored into the
    strategy-cache entry, so a warm pipelined compile runs ZERO DP
    expansions (the cache's headline contract)."""
    from flexflow_tpu.search.dp import SEARCH_STATS, reset_search_stats

    def compile_once():
        cfg = FFConfig(batch_size=8, only_data_parallel=False,
                       search_budget=8, pipeline_stages=2, accum_steps=4,
                       strategy_cache_dir=str(tmp_path),
                       log_level="warning")
        m = _mlp(cfg, 8)
        return m.compile(SGDOptimizer(lr=0.05),
                         LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                         metrics=[])

    cm1 = compile_once()
    assert cm1.strategy.pipeline
    reset_search_stats()
    cm2 = compile_once()
    assert SEARCH_STATS["calls"] == 0, SEARCH_STATS
    assert cm2.strategy.pipeline == cm1.strategy.pipeline
    assert cm2.strategy._cache_info["event"] == "hit"


def test_cut_boundary_is_live_output_not_first(devices):
    """Review class: a multi-output layer whose FIRST output dies early is
    a valid single-tensor cut point whose boundary is a LATER output —
    stage wiring must ship the live tensor, and training must match the
    sequential run (pre-fix: the dead half crossed the boundary)."""
    def build(stages):
        cfg = FFConfig(batch_size=8, only_data_parallel=True, seed=3,
                       pipeline_stages=stages, accum_steps=4,
                       log_level="warning")
        m = FFModel(cfg)
        t = m.create_tensor([8, 64], name="x")
        h = m.dense(t, 128, activation="gelu", name="up")
        dead, live = m.split(h, [48, 80], axis=1, name="sp")
        h = m.dense(live, 64, activation="relu", name="mid")
        m.dense(h, 8, name="head")
        cm = m.compile(SGDOptimizer(lr=0.05),
                       LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                       metrics=[])
        cm.init(seed=0)
        return cm

    from flexflow_tpu.core.graph import topo_order
    from flexflow_tpu.search.candidates import cut_boundary_tensor
    from flexflow_tpu.search.unity import sequence_cut_indices

    cm_p = build(2)
    order = topo_order(cm_p.model.layers)
    cuts = cm_p.cuts
    # if the chosen cut is the split layer, the boundary must be the LIVE
    # (second, 80-wide) output; either way the helper must agree with the
    # wired boundary
    for i, c in enumerate(cuts):
        assert cm_p.boundaries[i] is cut_boundary_tensor(order, c)
    sp_idx = next(i for i, l in enumerate(order) if l.name == "sp")
    if sp_idx in set(sequence_cut_indices(order, cm_p.model.input_tensors)):
        bt = cut_boundary_tensor(order, sp_idx)
        assert bt.shape[-1] == 80  # the live output, not outputs[0]

    x, y = _data("mlp", 64, np.random.default_rng(0))
    h_p = cm_p.fit([x[0]], y, epochs=2, verbose=False)
    cm_s = build(1)
    h_s = cm_s.fit([x[0]], y, epochs=2, verbose=False)
    assert h_p[-1]["loss"] == pytest.approx(h_s[-1]["loss"], rel=1e-5)
