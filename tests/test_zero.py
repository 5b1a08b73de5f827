"""ZeRO-sharded optimizer state + gradient accumulation
(compiler/compile.py, search/cost_model.py OptMemSpec,
runtime/checkpoint.py re-shard): loss parity with the replicated regime,
the ~data-degree opt-state memory reduction (predicted AND live-buffer),
the DP search's sharded-moment accounting, and cross-mesh checkpoint
round-trips."""

import jax
import numpy as np
import pytest

from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.losses import LossType


def _mlp(cfg, batch):
    m = FFModel(cfg)
    t = m.create_tensor([batch, 64], name="x")
    h = m.dense(t, 256, activation="gelu", name="up")
    h = m.dense(h, 64, name="down")
    m.dense(h, 8, name="head")
    return m


def _gpt2(cfg, batch):
    from flexflow_tpu.models import GPT2Config, build_gpt2

    m = FFModel(cfg)
    build_gpt2(m, GPT2Config(vocab=512, seq=16, d_model=64, heads=2,
                             layers=1, dropout=0.0), batch=batch)
    return m


def _data(kind, n, rng):
    if kind == "gpt2":
        ids = rng.integers(0, 512, size=(n, 16)).astype(np.int32)
        pos = np.broadcast_to(np.arange(16, dtype=np.int32), (n, 16)).copy()
        y = rng.integers(0, 512, size=(n, 16)).astype(np.int32)
        return [ids, pos], y
    x = rng.normal(size=(n, 64)).astype(np.float32)
    return [x], rng.integers(0, 8, size=(n,)).astype(np.int32)


def _train(kind, zero, batch=8, accum=1, epochs=2, opt=None, n=128,
           mesh=None, steps_per_dispatch=1):
    cfg = FFConfig(batch_size=batch, only_data_parallel=True, seed=3,
                   zero_sharding=zero, accum_steps=accum,
                   steps_per_dispatch=steps_per_dispatch,
                   mesh_shape=mesh or {}, log_level="warning")
    m = _gpt2(cfg, batch) if kind == "gpt2" else _mlp(cfg, batch)
    cm = m.compile(opt or AdamOptimizer(alpha=0.01),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    x, y = _data(kind, n, np.random.default_rng(0))
    hist = cm.fit(x, y, epochs=epochs, verbose=False)
    return cm, hist


# ----------------------------------------------------------- loss parity
@pytest.mark.parametrize("kind", ["mlp", "gpt2"])
def test_zero1_loss_parity_and_memory_reduction(devices, kind):
    """zero1 must train IDENTICALLY to the replicated baseline (the update
    arithmetic is elementwise — only the layout moves) while the
    per-device optimizer state shrinks by ~the data-axis degree, in both
    the cost model's prediction and the live buffers."""
    cm_off, h_off = _train(kind, "off")
    cm_z, h_z = _train(kind, "zero1")
    assert h_z[-1]["loss"] == pytest.approx(h_off[-1]["loss"], abs=1e-6)

    m_off, m_z = cm_off.memory_stats(), cm_z.memory_stats()
    deg = m_z["data_axis_degree"]
    assert deg == 8
    for key in ("predicted_opt_state_bytes",
                "actual_opt_state_bytes_per_device"):
        assert m_off[key] >= (deg / 2) * m_z[key], (key, m_off[key], m_z[key])
    # params themselves stay replicated (zero1 shards STATE, not weights)
    assert m_z["actual_param_bytes_per_device"] == \
        m_off["actual_param_bytes_per_device"]


def test_zero2_and_fused_dispatch_parity(devices):
    """zero2 (scattered accumulators) composed with accumulation and the
    K-fused dispatch loop stays within float32 reassociation of the plain
    accumulation run — and the PER-MICROBATCH scatter constraint zero2
    exists for is really in the traced step (loss parity alone would pass
    under zero1 too, since losses are layout-invariant)."""
    _, h_ref = _train("mlp", "off", accum=2)
    cm, h = _train("mlp", "zero2", accum=2, steps_per_dispatch=2)
    assert cm.step_stats["fused_steps"] > 0  # fusion actually engaged
    assert h[-1]["loss"] == pytest.approx(h_ref[-1]["loss"], abs=1e-6)

    def n_constraints(c):
        import jax

        args = (c.params, c.opt_state, c.state,
                [jax.ShapeDtypeStruct((2, 8, 64), "float32")],
                jax.ShapeDtypeStruct((2, 8), "int32"), jax.random.PRNGKey(0))
        jaxpr = jax.make_jaxpr(c._train_step_fn)(*args)
        # str() count reaches INSIDE the fori_loop body sub-jaxpr, where
        # microbatches 1..N-1 apply their constraints
        return str(jaxpr).count("sharding_constraint")

    cm1, _ = _train("mlp", "zero1", accum=2, epochs=1, n=32)
    # zero2 constrains each microbatch's gradient tree (6 param leaves x 2
    # microbatches) ON TOP of zero1's shared update-path constraints
    assert n_constraints(cm) >= n_constraints(cm1) + 2 * 6


def test_opt_state_sharded_from_init(devices):
    """Satellite: the jitted tx.init with explicit out_shardings must land
    the moments sharded at birth — each device's opt-state shard is
    ~1/degree of the replicated layout's, before any step runs."""
    cfg = FFConfig(batch_size=16, only_data_parallel=True,
                   zero_sharding="zero1", log_level="warning")
    m = _mlp(cfg, 16)
    cm = m.compile(AdamOptimizer(alpha=0.01),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    mu = cm.opt_state[0].mu["up"]["kernel"]
    shard = next(iter(mu.addressable_shards)).data.shape
    assert shard[0] == mu.shape[0] // 8, (shard, mu.shape)
    stats = cm.memory_stats()
    assert stats["actual_opt_state_bytes_per_device"] * 4 <= \
        stats["actual_param_bytes_per_device"] * 2


# ------------------------------------------------- gradient accumulation
@pytest.mark.parametrize("kind,opt_fn,tol", [
    ("mlp", lambda: SGDOptimizer(lr=0.05), 1e-6),
    ("mlp", lambda: AdamOptimizer(alpha=0.01), 1e-6),
    ("gpt2", lambda: AdamOptimizer(alpha=0.01), 1e-5),
], ids=["mlp-sgd", "mlp-adam", "gpt2-adam"])
def test_accum_equivalence_sgd_and_adam(devices, kind, opt_fn, tol):
    """accum_steps=4 at batch B == one update at batch 4B on the same
    data: exact-ish under SGD (reduction-order noise only), <= 1e-6 rel
    under Adam; 1e-5 through GPT-2's vocabulary-wide loss."""
    _, h_acc = _train(kind, "off", batch=8, accum=4, opt=opt_fn(), n=256)
    _, h_big = _train(kind, "off", batch=32, accum=1, opt=opt_fn(), n=256)
    assert h_acc[-1]["loss"] == pytest.approx(h_big[-1]["loss"], rel=tol)


def test_accum_override_not_sticky(devices):
    """fit(accum_steps=N) is a PER-CALL override (the sync_every/
    steps_per_dispatch contract): the next fit() without it reverts to the
    config's width."""
    cfg = FFConfig(batch_size=8, only_data_parallel=True, seed=3,
                   log_level="warning")
    m = _mlp(cfg, 8)
    cm = m.compile(SGDOptimizer(lr=0.01),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    x, y = _data("mlp", 64, np.random.default_rng(0))
    h = cm.fit(x, y, epochs=1, verbose=False, accum_steps=4)
    assert h[0]["dispatches"] == 2.0  # 8 microbatches / 4
    h = cm.fit(x, y, epochs=1, verbose=False)  # None -> cfg's accum_steps=1
    assert h[0]["dispatches"] == 8.0


def test_group_microbatches_drops_ragged_tail(devices):
    """A short remainder batch (drop_remainder=False loaders) must not
    crash np.stack — the broken group is dropped, uniform groups after it
    still form."""
    from flexflow_tpu.runtime.dataloader import group_microbatches

    sizes = [4, 4, 3, 4, 4]

    def gen():
        for n in sizes:
            yield [np.zeros((n, 2), np.float32)], np.zeros((n,), np.int32)

    out = [np.asarray(y).shape for _, y in group_microbatches(gen(), 2)]
    assert out == [(2, 4), (2, 4)]  # [4,4] grouped; 3 breaks; [4,4] grouped


def test_accum_counts_updates_not_microbatches(devices):
    """One accumulation group = one optimizer update = one iteration; the
    epoch history reports update-level dispatch counts and full-epoch
    sample throughput."""
    cm, hist = _train("mlp", "off", batch=8, accum=4, epochs=1, n=128)
    assert cm._iteration == 128 // (8 * 4)
    assert hist[0]["dispatches"] == 4.0
    assert hist[0]["samples"] == 128.0


# ------------------------------------------------------- search accounting
def test_dp_search_prices_sharded_moments(devices):
    """--memory-search accounting: the same graph costed with the ZeRO
    OptMemSpec must predict ~(2 + 2/deg)/4 of the replicated weight-state
    memory (params+grads full, moments /deg), and bf16 moments halve the
    moment term (satellite: state_dtype sizing)."""
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.search import cost_model as cm
    from flexflow_tpu.search.dp import search_graph

    cfg = FFConfig(batch_size=32, log_level="warning")
    model = _mlp(cfg, 32)
    mach = MachineSpec(mesh_axes={"data": 8}, chip="v5e")

    adam = AdamOptimizer(alpha=0.01)
    r_legacy = search_graph(model, mach)
    om_off = cm.opt_mem_spec(adam, cfg, mach)
    r_repl = search_graph(model, mach, opt_mem=om_off)
    cfg_z = FFConfig(batch_size=32, zero_sharding="zero1",
                     log_level="warning")
    om_zero = cm.opt_mem_spec(adam, cfg_z, mach)
    assert om_zero.zero_axes == ("data",)
    r_zero = search_graph(model, mach, opt_mem=om_zero)

    # f32 Adam without zero == the legacy params-x4 accounting
    assert r_repl.mem_bytes == r_legacy.mem_bytes
    assert r_zero.mem_bytes < r_repl.mem_bytes
    # all-dp strategy on this mlp: every weight dim divides 8, so moments
    # shrink exactly 8x; act memory is identical across the two runs
    w = sum(s.size_bytes for l in model.layers
            for s in l.weight_specs.values())
    assert r_repl.mem_bytes - r_zero.mem_bytes == 2 * w - 2 * w // 8

    bf16 = AdamOptimizer(alpha=0.01, state_dtype="bfloat16")
    r_bf16 = search_graph(model, mach,
                          opt_mem=cm.opt_mem_spec(bf16, cfg, mach))
    assert r_repl.mem_bytes - r_bf16.mem_bytes == w  # 2 f32 -> 2 bf16 moments

    # sgd (no momentum) carries NO moments
    om_sgd = cm.opt_mem_spec(SGDOptimizer(lr=0.1), cfg, mach)
    assert om_sgd.moments == 0
    r_sgd = search_graph(model, mach, opt_mem=om_sgd)
    assert r_repl.mem_bytes - r_sgd.mem_bytes == 2 * w


def test_zero_divisor_mirrors_runtime_rule(devices):
    """cost_model.zero_divisor must agree with the compile-side
    _zero_moment_pspec placement on divisible, non-divisible and
    already-data-sharded weights."""
    from flexflow_tpu.core.tensor import TensorSpec
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.search.cost_model import zero_divisor

    mach = MachineSpec(mesh_axes={"data": 8, "model": 2}, chip="v5e")
    za = ("data",)
    assert zero_divisor(TensorSpec((64, 32)), [None, None], mach, za) == 8
    # first dim model-sharded, second divides: still 8
    assert zero_divisor(TensorSpec((64, 32)), ["model", None], mach, za) == 8
    # no dim divisible by 8 -> moments stay replicated
    assert zero_divisor(TensorSpec((3, 5)), [None, None], mach, za) == 1
    # already sharded over data -> nothing left to remove
    assert zero_divisor(TensorSpec((64, 32)), ["data", None], mach, za) == 1
    assert zero_divisor(TensorSpec((64, 32)), [None, None], mach, ()) == 1


# ------------------------------------------------------------- checkpoint
def test_zero_checkpoint_roundtrip_across_meshes(devices, tmp_path):
    """Save ZeRO-sharded opt state under mesh {data:4, model:2}, restore
    under {data:2, model:4}: moments must bitwise-match after the
    re-shard, and training must resume on the identical trajectory."""
    def build(mesh):
        cfg = FFConfig(batch_size=16, mesh_shape=mesh,
                       only_data_parallel=True, seed=5,
                       zero_sharding="zero1", log_level="warning")
        m = _mlp(cfg, 16)
        return m.compile(AdamOptimizer(alpha=0.01),
                         LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                         metrics=[])

    rng = np.random.default_rng(0)
    x, y = _data("mlp", 64, rng)
    cm1 = build({"data": 4, "model": 2})
    cm1.init(seed=0)
    cm1.fit(x, y, epochs=1, verbose=False)
    ck = str(tmp_path / "ck")
    cm1.save_checkpoint(ck, block=True)
    mu_saved = jax.tree_util.tree_map(np.asarray, cm1.opt_state[0].mu)
    h_ref = cm1.fit(x, y, epochs=1, verbose=False)

    cm2 = build({"data": 2, "model": 4})
    cm2.init(seed=123)  # different init — must be overwritten
    cm2.load_checkpoint(ck)
    assert cm2._iteration == 4
    # moments bitwise-identical after the cross-mesh re-shard...
    jax.tree_util.tree_map(
        np.testing.assert_array_equal, mu_saved,
        jax.tree_util.tree_map(np.asarray, cm2.opt_state[0].mu))
    # ...and landed in the NEW mesh's zero layout (data degree 2)
    mu = cm2.opt_state[0].mu["up"]["kernel"]
    assert next(iter(mu.addressable_shards)).data.shape[0] == \
        mu.shape[0] // 2
    h_res = cm2.fit(x, y, epochs=1, verbose=False)
    assert h_res[0]["loss"] == pytest.approx(h_ref[0]["loss"], rel=1e-6)


def test_launcher_value_flags_cover_new_knobs():
    """PR-2 review class: every new value-taking FFConfig flag must be in
    the launcher's value_flags set, or `python -m flexflow_tpu
    --zero-sharding zero1 train.py` would treat the VALUE as the script.
    The set is now DERIVED from the parser (FFConfig.launcher_value_flags);
    tests/test_pipeline.py checks the derivation exhaustively — this keeps
    the zero-knob spot check alive."""
    from flexflow_tpu import FFConfig
    from flexflow_tpu.__main__ import split_argv

    flags = FFConfig.launcher_value_flags()
    for flag in ("--zero-sharding", "--accum-steps"):
        assert flag in flags, flag
        assert split_argv([flag, "v", "train.py"])[0] == "train.py"
