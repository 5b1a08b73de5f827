"""Fused cross-entropy kernel (ISSUE 12 tentpole b): blockwise online
log-sum-exp loss vs the optax reference — forward and gradient parity at
f32/bf16, the no-f32-[N,vocab]-materialization claim checked on the
jaxpr, the auto/on/off mode gate, and end-to-end loss parity on the
sharded compile path."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.core.layer import Layer
from flexflow_tpu.core.tensor import Tensor
from flexflow_tpu.kernels.fused_ce import (fused_ce_supported,
                                           fused_cross_entropy,
                                           use_fused_ce)
from flexflow_tpu.losses import LossType


def _ref(logits, labels):
    return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels))


def _data(n=64, v=640, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=(n, v)) * 3.0, dtype)
    labels = jnp.asarray(rng.integers(0, v, size=(n,)), jnp.int32)
    return logits, labels


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_forward_matches_optax(dtype):
    logits, labels = _data(dtype=dtype)
    out = fused_cross_entropy(logits, labels)
    ref = _ref(logits, labels)
    # both paths do the log-sum-exp in f32 from the same inputs
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gradient_matches_optax(dtype):
    logits, labels = _data(dtype=dtype)
    g_fused = jax.grad(lambda x: fused_cross_entropy(x, labels))(logits)
    g_ref = jax.grad(lambda x: _ref(x, labels))(logits)
    atol = 1e-6 if dtype == jnp.float32 else 2e-4  # bf16 output rounding
    np.testing.assert_allclose(np.asarray(g_fused, jnp.float32),
                               np.asarray(g_ref, jnp.float32),
                               atol=atol, rtol=1e-4)


def test_3d_logits_mean_over_all_leading_dims():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(4, 16, 256)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 256, size=(4, 16)), jnp.int32)
    out = fused_cross_entropy(logits, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(
        logits, labels)), atol=1e-5, rtol=1e-5)


def test_never_materializes_f32_logits():
    """The headline memory claim: with bf16 logits no f32 [N, vocab]
    intermediate exists anywhere in the traced forward+backward — the
    optax path creates two (the cast + the log-softmax)."""
    logits, labels = _data(dtype=jnp.bfloat16)
    n, v = logits.shape

    def has_f32_nv(fn):
        jaxpr = jax.make_jaxpr(fn)(logits)
        found = []

        def walk(jp):
            for eqn in jp.eqns:
                for var in eqn.outvars:
                    aval = getattr(var, "aval", None)
                    if aval is not None and tuple(aval.shape) == (n, v) \
                            and aval.dtype == jnp.float32:
                        found.append(eqn.primitive.name)
                for val in eqn.params.values():
                    inner = getattr(val, "jaxpr", None)
                    if inner is not None:
                        walk(inner)
        walk(jaxpr.jaxpr)
        return found

    assert not has_f32_nv(
        lambda x: jax.grad(lambda y: fused_cross_entropy(y, labels))(x))
    # the reference path DOES: the assertion above is meaningful
    assert has_f32_nv(lambda x: jax.grad(lambda y: _ref(y, labels))(x))


def test_supported_precheck():
    f32 = jnp.float32
    assert fused_ce_supported((64, 640), f32)
    assert fused_ce_supported((4, 16, 256), jnp.bfloat16)
    assert not fused_ce_supported((64, 130), f32)   # vocab % 128 != 0
    assert not fused_ce_supported((13, 256), f32)   # rows match no block
    assert not fused_ce_supported((64, 640), jnp.int32)
    assert not fused_ce_supported((640,), f32)      # needs >= 2 dims
    with pytest.raises(ValueError):
        fused_cross_entropy(jnp.zeros((64, 130), f32),
                            jnp.zeros((64,), jnp.int32))


def test_use_fused_ce_gate():
    sce = LossType.SPARSE_CATEGORICAL_CROSSENTROPY
    good = jnp.zeros((64, 640), jnp.float32)
    bad = jnp.zeros((64, 130), jnp.float32)
    assert use_fused_ce(sce, good, "auto", enable_fusion=True)
    assert not use_fused_ce(sce, good, "off", enable_fusion=True)
    assert not use_fused_ce(sce, good, "auto", enable_fusion=False)
    assert not use_fused_ce(sce, bad, "auto", enable_fusion=True)
    assert use_fused_ce(sce, good, "on", enable_fusion=False)  # forced
    with pytest.raises(ValueError):
        use_fused_ce(sce, bad, "on")
    with pytest.raises(ValueError):
        use_fused_ce(LossType.MEAN_SQUARED_ERROR, good, "on")
    assert not use_fused_ce(LossType.MEAN_SQUARED_ERROR, good, "auto")


def _fit(devices, fused_loss: str):
    # consecutive builds shift the guid-derived dropout streams: pin them
    Layer._next_guid[0] = 100
    Tensor._next_guid[0] = 1000
    # batch 32 over data=4: 8 rows per device — the kernel runs PER SHARD
    # on a multi-device mesh, and its row block is 8
    cfg = FFConfig(batch_size=32, mesh_shape={"data": 4, "model": 2},
                   only_data_parallel=False, search_budget=0,
                   fused_loss=fused_loss, seed=3)
    m = FFModel(cfg)
    x = m.create_tensor([32, 32], name="x")
    h = m.dense(x, 64, activation="gelu", name="up")
    m.dense(h, 256, name="head")  # vocab-like: 256 % 128 == 0
    cmod = m.compile(SGDOptimizer(lr=0.05),
                     LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cmod.init(seed=0)
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(64, 32)).astype(np.float32)
    ys = rng.integers(0, 256, size=(64,)).astype(np.int32)
    return [h["loss"] for h in cmod.fit([xs], ys, epochs=2, verbose=False)]


def test_e2e_loss_parity_on_sharded_mesh(devices):
    """Acceptance: fused vs reference loss within 1e-5 on the real
    compile path over a 4x2 mesh (the kernel runs per shard of the
    batch-sharded logits under shard_map, interpret mode on CPU)."""
    base = _fit(devices, "off")
    fused = _fit(devices, "on")
    assert np.allclose(base, fused, atol=1e-5, rtol=1e-5)


def test_fused_loss_trains_gpt2_to_the_optax_loss(devices):
    """Through `fit`, not the kernel alone: a one-block GPT-2 under Adam
    with the fused cross-entropy forced on ("on" raises where the kernel is
    not taken) ends two epochs on the loss of the optax loss, within
    1e-5."""
    from flexflow_tpu import AdamOptimizer
    from flexflow_tpu.models import GPT2Config, build_gpt2

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 512, size=(128, 16)).astype(np.int32)
    pos = np.broadcast_to(np.arange(16, dtype=np.int32), (128, 16)).copy()
    y = rng.integers(0, 512, size=(128, 16)).astype(np.int32)

    def final_loss(mode):
        m = FFModel(FFConfig(batch_size=8, only_data_parallel=True, seed=3,
                             fused_loss=mode, log_level="warning"))
        build_gpt2(m, GPT2Config(vocab=512, seq=16, d_model=64, heads=2,
                                 layers=1, dropout=0.0), batch=8)
        cm = m.compile(AdamOptimizer(alpha=1e-3),
                       LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
        cm.init(seed=0)
        return cm.fit([ids, pos], y, epochs=2, verbose=False)[-1]["loss"]

    assert final_loss("on") == pytest.approx(final_loss("off"), abs=1e-5)
