"""Fused optimizer update kernel (ISSUE 12 tentpole b): the single-pass
Adam/SGD moment kernel vs the optax chain it replaces — update and state
parity across every recognized plan, exact state-tree structure (the
checkpoint/ZeRO contract), multi-step continuation, and the plan gate."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from flexflow_tpu import AdamOptimizer, SGDOptimizer
from flexflow_tpu.kernels.fused_optim import fused_update, plan_for


def _params(seed=0):
    rng = np.random.default_rng(seed)
    # odd sizes on purpose: exercises the pad-to-(rows,128) path
    return {
        "fc": {"kernel": jnp.asarray(rng.normal(size=(33, 65)), jnp.float32),
               "bias": jnp.asarray(rng.normal(size=(65,)), jnp.float32)},
        "head": {"kernel": jnp.asarray(rng.normal(size=(7,)), jnp.float32)},
    }


def _grads(seed):
    return jax.tree_util.tree_map(
        lambda p: jnp.asarray(
            np.random.default_rng(seed + p.size).normal(size=p.shape),
            jnp.float32), _params())


OPTS = [
    pytest.param(AdamOptimizer(alpha=1e-3), id="adam"),
    pytest.param(AdamOptimizer(alpha=1e-3, weight_decay=0.01), id="adamw"),
    pytest.param(AdamOptimizer(alpha=1e-3, state_dtype="bfloat16"),
                 id="adam-bf16"),
    pytest.param(SGDOptimizer(lr=0.05), id="sgd"),
    pytest.param(SGDOptimizer(lr=0.05, momentum=0.9, nesterov=True),
                 id="sgd-nesterov"),
]


@pytest.mark.parametrize("opt", OPTS)
def test_fused_matches_optax_update_and_state(opt):
    tx = opt.to_optax()
    params = _params()
    state = tx.init(params)
    plan = plan_for(opt)
    assert plan is not None

    ref_state, fused_state = state, state
    for step in range(3):  # multi-step: the count/bias-correction advances
        grads = _grads(step)
        ref_upd, ref_state = tx.update(grads, ref_state, params)
        done = fused_update(plan, grads, fused_state, params)
        assert done is not None
        upd, fused_state = done
        # exact optax tree structure: checkpoints and ZeRO sharding
        # constraints address the state by this layout
        assert jax.tree_util.tree_structure(fused_state) == \
            jax.tree_util.tree_structure(ref_state)
        for a, b in zip(jax.tree_util.tree_leaves(upd),
                        jax.tree_util.tree_leaves(ref_upd)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=1e-6, rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(fused_state),
                        jax.tree_util.tree_leaves(ref_state)):
            assert jnp.asarray(a).dtype == jnp.asarray(b).dtype
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=1e-6, rtol=1e-5)


def test_plan_for_rejects_unknown_optimizers():
    class CustomAdam(AdamOptimizer):
        """A subclass may override to_optax: the exact-type check must
        refuse to guess its math."""

    assert plan_for(CustomAdam(alpha=1e-3)) is None
    assert plan_for(object()) is None
    assert plan_for(AdamOptimizer(alpha=1e-3, state_dtype="float16")) is None


def test_fused_update_none_on_foreign_state():
    """A state tree without the expected moment node falls back (None)
    instead of corrupting anything."""
    opt = AdamOptimizer(alpha=1e-3)
    plan = plan_for(opt)
    params = _params()
    foreign = optax.sgd(0.1).init(params)
    assert fused_update(plan, _grads(0), foreign, params) is None


def test_fused_suite_trains_gpt2_to_the_optax_loss(devices):
    """Through `fit`, not the kernel alone: a one-block GPT-2 under Adam
    with the fused optimizer update and the fused cross-entropy forced on
    ("on" raises where a kernel is not taken) ends two epochs on the loss
    of the optax update and the optax loss, within 1e-5."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.losses import LossType
    from flexflow_tpu.models import GPT2Config, build_gpt2

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 512, size=(128, 16)).astype(np.int32)
    pos = np.broadcast_to(np.arange(16, dtype=np.int32), (128, 16)).copy()
    y = rng.integers(0, 512, size=(128, 16)).astype(np.int32)

    def final_loss(mode):
        m = FFModel(FFConfig(batch_size=8, only_data_parallel=True, seed=3,
                             fused_loss=mode, fused_optimizer=mode,
                             log_level="warning"))
        build_gpt2(m, GPT2Config(vocab=512, seq=16, d_model=64, heads=2,
                                 layers=1, dropout=0.0), batch=8)
        cm = m.compile(AdamOptimizer(alpha=1e-3),
                       LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
        cm.init(seed=0)
        return cm.fit([ids, pos], y, epochs=2, verbose=False)[-1]["loss"]

    assert final_loss("on") == pytest.approx(final_loss("off"), abs=1e-5)
