"""Async training-loop pipeline (compiler/compile.py _fit_epochs +
runtime/dataloader.py): device-resident metrics (zero mid-epoch host syncs
in the default config), K-step fused dispatch, prefetcher exception
forwarding, and the make_multi_step donation contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType, MetricsType, SGDOptimizer
from flexflow_tpu.runtime.dataloader import prefetch_multi, prefetch_to_device


# ---------------------------------------------------------------- prefetcher
def test_prefetch_exception_forwarding(devices):
    """A worker raise mid-epoch must surface at the consumer AFTER the
    already-transferred batches drain — no hang, no swallowed error."""
    def gen():
        for i in range(3):
            yield [np.full((4, 2), i, np.float32)], np.zeros((4,), np.int32)
        raise RuntimeError("boom mid-epoch")

    got = []
    with pytest.raises(RuntimeError, match="boom mid-epoch"):
        for dx, dy in prefetch_to_device(gen(), [None], None):
            got.append(float(np.asarray(dx[0])[0, 0]))
    assert got == [0.0, 1.0, 2.0]  # queue drained before the raise surfaced


def test_prefetch_multi_groups_and_tail(devices):
    """prefetch_multi stacks k batches into one (k, ...) transfer and
    flushes the short tail as singles, preserving order and content."""
    def gen():
        for i in range(7):
            yield [np.full((4, 2), i, np.float32)], np.full((4,), i, np.int32)

    kinds, firsts = [], []
    for kind, dx, dy in prefetch_multi(gen(), 3, [None], None):
        kinds.append(kind)
        a = np.asarray(dx[0])
        if kind == "k":
            assert a.shape == (3, 4, 2) and np.asarray(dy).shape == (3, 4)
            firsts.extend(a[:, 0, 0].tolist())
        else:
            assert a.shape == (4, 2)
            firsts.append(float(a[0, 0]))
    assert kinds == ["k", "k", "1"]
    assert firsts == [0.0, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0]


def test_prefetch_multi_ragged_batch_flushes_singly(devices):
    """A batch whose shapes differ from its group's flushes the partial
    group as singles instead of crashing np.stack."""
    sizes = [4, 3, 4, 4]

    def gen():
        for n in sizes:
            yield [np.zeros((n, 2), np.float32)], np.zeros((n,), np.int32)

    out = [(kind, np.asarray(dy).shape)
           for kind, dx, dy in prefetch_multi(gen(), 2, [None], None)]
    assert out == [("1", (4,)), ("1", (3,)), ("k", (2, 4))]


def test_prefetch_multi_forwards_worker_exception(devices):
    def gen():
        yield [np.zeros((4, 2), np.float32)], np.zeros((4,), np.int32)
        raise ValueError("loader died")

    with pytest.raises(ValueError, match="loader died"):
        list(prefetch_multi(gen(), 3, [None], None))


# ---------------------------------------------------------- fused dispatch
def _donation_supported() -> bool:
    f = jax.jit(lambda a: a + 1, donate_argnums=(0,))
    x = jnp.ones((8,))
    f(x)
    return x.is_deleted()


def _compile_tiny(donate_state: bool):
    m = FFModel(FFConfig(batch_size=8, only_data_parallel=True,
                         donate_state=donate_state))
    t = m.create_tensor([8, 16], name="x")
    m.dense(t, 4, name="fc")
    cm = m.compile(SGDOptimizer(lr=0.01),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    return cm


def test_make_multi_step_donation_contract(devices):
    """donate=True consumes the INPUT params/opt_state/state buffers (the
    caller must write the returned trees back); donate=False keeps them
    alive and readable."""
    if not _donation_supported():
        pytest.skip("backend does not implement buffer donation")
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(2, 8, 16)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 4, size=(2, 8)).astype(np.int32))

    cm = _compile_tiny(donate_state=True)
    old = jax.tree_util.tree_leaves((cm.params, cm.opt_state))
    p, o, s, loss, _ = cm.make_multi_step(2, donate=True)(
        cm.params, cm.opt_state, cm.state, [xs], ys, jax.random.PRNGKey(0))
    assert all(l.is_deleted() for l in old), "donated buffers must be freed"
    cm.params, cm.opt_state, cm.state = p, o, s  # the documented write-back
    assert np.isfinite(float(loss))

    cm2 = _compile_tiny(donate_state=False)
    old2 = jax.tree_util.tree_leaves((cm2.params, cm2.opt_state))
    cm2.make_multi_step(2, donate=False)(
        cm2.params, cm2.opt_state, cm2.state, [xs], ys, jax.random.PRNGKey(0))
    assert not any(l.is_deleted() for l in old2)
    for l in old2:  # still materializable
        assert np.isfinite(np.asarray(l)).all()


# ----------------------------------------------------------- async fit loop
def _fit_run(sync_every, steps_per_dispatch, callbacks=None, epochs=2,
             model="mlp"):
    """8 batches an epoch of a two-layer MLP, or of a one-block GPT-2
    (embedding, attention and a vocabulary-wide loss under the same loop)."""
    rng = np.random.default_rng(0)
    cfg = FFConfig(batch_size=32, only_data_parallel=True,
                   sync_every=sync_every,
                   steps_per_dispatch=steps_per_dispatch)
    m = FFModel(cfg)
    if model == "gpt2":
        from flexflow_tpu.models import GPT2Config, build_gpt2

        build_gpt2(m, GPT2Config(vocab=512, seq=16, d_model=64, heads=2,
                                 layers=1, dropout=0.0), batch=32)
        x = [rng.integers(0, 512, size=(256, 16)).astype(np.int32),
             np.broadcast_to(np.arange(16, dtype=np.int32), (256, 16)).copy()]
        y = rng.integers(0, 512, size=(256, 16)).astype(np.int32)
    else:
        x = rng.normal(size=(256, 16)).astype(np.float32)
        y = rng.integers(0, 4, size=(256,)).astype(np.int32)
        t = m.create_tensor([32, 16], name="x")
        h = m.dense(t, 32, activation="relu")
        m.dense(h, 4)
    cm = m.compile(SGDOptimizer(lr=0.05),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   [MetricsType.ACCURACY])
    cm.init(seed=0)
    hist = cm.fit(x, y, epochs=epochs, verbose=False, callbacks=callbacks)
    return cm, hist


@pytest.mark.parametrize("model", ["mlp", "gpt2"])
def test_async_fit_zero_host_syncs_and_loss_parity(devices, model):
    """Default config (sync_every=0): zero mid-epoch host syncs, and the
    deferred float64 loss/metric accumulation is BIT-identical to the
    synchronous loop (same values, same summation order)."""
    _, h_sync = _fit_run(sync_every=1, steps_per_dispatch=1, model=model)
    cm, h_async = _fit_run(sync_every=0, steps_per_dispatch=1, model=model)
    assert cm.step_stats["host_syncs"] == 0
    assert cm.step_stats["dispatches"] == 16  # 8 batches x 2 epochs
    for es, ea in zip(h_sync, h_async):
        assert ea["loss"] == es["loss"]
        assert ea["accuracy"] == es["accuracy"]
        assert ea["host_syncs"] == 0.0 and es["host_syncs"] > 0


@pytest.mark.parametrize("model", ["mlp", "gpt2"])
def test_fused_fit_amortizes_dispatches(devices, model):
    """K=4 over 8 batches/epoch: 2 dispatches per epoch, all steps fused,
    loss within float32 reassociation of the synchronous loop."""
    _, h_sync = _fit_run(sync_every=1, steps_per_dispatch=1, model=model)
    cm, h_fused = _fit_run(sync_every=0, steps_per_dispatch=4, model=model)
    assert cm.step_stats == {"dispatches": 4, "host_syncs": 0,
                             "barriers": 0, "fused_steps": 16,
                             "epoch_end_syncs": 2}
    assert h_fused[-1]["dispatches"] == 2.0
    assert h_fused[-1]["loss"] == pytest.approx(h_sync[-1]["loss"], abs=1e-6)
    assert h_fused[-1]["accuracy"] == pytest.approx(
        h_sync[-1]["accuracy"], abs=1e-6)


def test_sync_every_periodic_materialization(devices):
    """sync_every=4 with 8 batches/epoch: two mid-epoch host syncs per
    epoch, same loss as the fully synchronous loop."""
    cm, hist = _fit_run(sync_every=4, steps_per_dispatch=1)
    assert hist[-1]["host_syncs"] == 2.0
    _, h_sync = _fit_run(sync_every=1, steps_per_dispatch=1)
    assert hist[-1]["loss"] == h_sync[-1]["loss"]


def test_per_batch_callback_forces_synchronous_fallback(devices):
    """A callback with on_batch_end needs per-step host control: the loop
    must fall back to 1-step dispatch + per-step materialization and feed
    the callback every step's loss."""
    class BatchCB:
        def __init__(self):
            self.losses = []

        def on_batch_end(self, iteration, logs):
            self.losses.append(logs["loss"])

    cb = BatchCB()
    cm, hist = _fit_run(sync_every=0, steps_per_dispatch=4, callbacks=[cb])
    assert cm.step_stats["fused_steps"] == 0  # fell back to 1-step
    assert len(cb.losses) == 16 and all(np.isfinite(l) for l in cb.losses)
    assert hist[-1]["host_syncs"] == 8.0


def test_recompile_registered_mid_fit_drops_fusion(devices):
    """A recompile trigger registered by on_epoch_end must force the NEXT
    epoch down to 1-step dispatch (the fused fn compiled before the
    recompile would otherwise keep training the stale graph)."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 16)).astype(np.float32)
    y = rng.integers(0, 4, size=(256,)).astype(np.int32)
    m = FFModel(FFConfig(batch_size=32, only_data_parallel=True))
    t = m.create_tensor([32, 16], name="x")
    m.dense(t, 4)
    cm = m.compile(SGDOptimizer(lr=0.05),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)

    class EpochCB:
        def on_epoch_end(self, epoch, metrics):
            if cm.recompile_state is None:
                cm.recompile_on_condition(lambda c: False, lambda c: None)

    hist = cm.fit(x, y, epochs=2, verbose=False, steps_per_dispatch=4,
                  callbacks=[EpochCB()])
    assert hist[0]["dispatches"] == 2.0  # epoch 0: fused, 8 batches / K=4
    assert hist[1]["dispatches"] == 8.0  # epoch 1: fell back to 1-step


def test_perf_metrics_deferred_fold_parity(devices):
    """Deferred accumulation past fold_after (device chunk folding) stays
    within float32-reassociation of the eager host path, and is
    bit-identical below the fold threshold."""
    from flexflow_tpu.metrics import PerfMetrics

    rng = np.random.default_rng(0)
    vals = rng.uniform(0.2, 2.0, size=600).astype(np.float32)
    eager, deferred = PerfMetrics(), PerfMetrics()
    for v in vals:
        eager.update(4, {"m": float(jnp.float32(v))})
        deferred.update_deferred(4, {"m": jnp.float32(v)})
    assert deferred.pending_updates < deferred.fold_after  # folding engaged
    s_e, s_d = eager.summary(), deferred.summary()
    assert s_d["samples"] == s_e["samples"] == 2400.0
    assert s_d["m"] == pytest.approx(s_e["m"], rel=1e-6)

    small_e, small_d = PerfMetrics(), PerfMetrics()
    for v in vals[:100]:  # below fold_after: bit-identical
        small_e.update(4, {"m": float(jnp.float32(v))})
        small_d.update_deferred(4, {"m": jnp.float32(v)})
    assert small_d.summary()["m"] == small_e.summary()["m"]
