"""An expert layer's routing decision is made once a training step (PR 59):
what `_route_tokens` decides (the chosen experts and their scores as gathered,
the pairs' order by expert and its inverse, both counts) is tagged
`moe_ops.ROUTING_KEPT` in training, and every `jax.checkpoint` around the
layer keeps it: the layer's own around a token block, and a `remat_blocks`
unit's (`compiler/lowering.run_block`, through the op's `kept_names`).

Here: a tiny afmoe decoder (four expert layers, a part holder with a stateful
selection bias) whose step holds two token blocks (`MOE_TOKEN_BLOCK` set to
32), on the CPU. "Patched out" means `moe_ops._kept` returns its argument:
no tag, so both policies keep nothing and the step is the parent's.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))
sys.path.insert(0, str(ROOT / "tests"))

from flexflow_tpu import AdamOptimizer, FFModel, attribution  # noqa: E402
from flexflow_tpu.compiler import lowering  # noqa: E402
from flexflow_tpu.models import (GPT2Config, build_afmoe,  # noqa: E402
                                 build_gpt2)
from flexflow_tpu.ops import get_op_def, moe_ops  # noqa: E402
from flexflow_tpu.ops.op_type import OperatorType  # noqa: E402
from flexflow_tpu.ops.registry import LoweringCtx  # noqa: E402
from test_afmoe import batch_of, compiled, ffconfig  # noqa: E402
from test_afmoe import held_tiny as held_tiny_and_file  # noqa: E402

EXPERT_LAYERS = 4


@pytest.fixture
def two_blocks(monkeypatch):
    """A step of 2 x 32 tokens goes through each expert layer in two
    blocks; with `rung_rows` a block of 64 pairs gets the ladder of row
    rungs (and its `lax.switch`) too."""
    def set_sizes(rung_rows=None):
        monkeypatch.setattr(moe_ops, "MOE_TOKEN_BLOCK", 32)
        if rung_rows:
            monkeypatch.setattr(moe_ops, "MOE_MIN_RUNG_ROWS", rung_rows)
    return set_sizes


def patch_out(monkeypatch):
    monkeypatch.setattr(moe_ops, "_kept", lambda x, training: x)


def held_tiny():
    """test_afmoe's part holder: 4 of 8 experts, 32 positions."""
    return held_tiny_and_file()[0]


def step_args(cm, g):
    ids, pos, labels = batch_of(g, 2)
    return (cm.params, cm.opt_state, cm.state,
            [jnp.asarray(ids), jnp.asarray(pos)], jnp.asarray(labels),
            jax.random.PRNGKey(0))


def primitives(jaxpr, counts=None):
    """{primitive name: equations}, through every sub-jaxpr."""
    counts = {} if counts is None else counts
    for e in jaxpr.eqns:
        counts[e.primitive.name] = counts.get(e.primitive.name, 0) + 1
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    primitives(sub, counts)
    return counts


# (remat_blocks, tagging patched out) -> how often the step decides: the
# forward pass, the block's recomputation, the unit's
PASSES = {(False, False): 1, (True, False): 1,
          (False, True): 2, (True, True): 3}


@pytest.mark.parametrize("ladder", (False, True))
@pytest.mark.parametrize("remat_blocks, patched", sorted(PASSES))
def test_a_training_step_decides_once_a_block_a_layer(
        two_blocks, monkeypatch, remat_blocks, patched, ladder):
    """The whole step (`value_and_grad` and the update) holds one `top_k`
    and one pair of sorts (the order by expert, and its inverse where the
    whole block's buffer is combined) a token block a layer: `lax.map`
    traces a block once, so one of each a layer. Without the tags: twice
    (the block's checkpoint), three times under `remat_blocks`."""
    two_blocks(rung_rows=4 if ladder else None)
    if patched:
        patch_out(monkeypatch)
    g = held_tiny()
    cm = compiled(g, remat_blocks=remat_blocks)
    args = step_args(cm, g)
    counts = primitives(jax.make_jaxpr(cm.train_step)(*args).jaxpr)
    passes = PASSES[remat_blocks, patched]
    assert counts["top_k"] == EXPERT_LAYERS * passes
    assert counts["sort"] == 2 * EXPERT_LAYERS * passes
    assert counts["scan"] >= EXPERT_LAYERS         # the blocks are a loop
    assert ("cond" in counts) == ladder
    assert ("name" in counts) == (not patched)
    # the engagement counter, from the compiled program's own text
    text = cm.train_step.lower(*args).compile().as_text()
    assert attribution.routing_passes(
        text, {l.name: l.op_type.value for l in cm.model.layers}) == passes


@pytest.mark.parametrize("remat_blocks", (False, True))
def test_losses_and_every_gradient_are_the_untagged_steps(
        two_blocks, monkeypatch, remat_blocks):
    """The same program but for what is rerun: the same integers, so the
    same rows in the same order through the same rung. Losses, Adam's
    moments (the gradients), the parameters and the biases after three
    steps, bit for bit."""
    two_blocks(rung_rows=4)
    g = held_tiny()
    ids, pos, labels = batch_of(g, 6)
    seen = []
    for patched in (False, True):
        if patched:
            patch_out(monkeypatch)
        cm = compiled(g, remat_blocks=remat_blocks)
        hist = cm.fit([ids, pos], labels, epochs=1, verbose=False)
        seen.append(([h["loss"] for h in hist], cm.params,
                     cm.opt_state[0].mu, cm.opt_state[0].nu, cm.state))
    kept, plain = (jax.tree_util.tree_leaves(s) for s in seen)
    assert len(kept) == len(plain) > 50
    for a, b in zip(kept, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def moe_layer_jaxpr(training: bool):
    g = held_tiny()
    m = FFModel(ffconfig(2))
    build_afmoe(m, g, batch=2)
    layer = next(l for l in m.layers if l.op_type.value == "moe_layer")
    w = {k: jax.ShapeDtypeStruct(s.shape, s.dtype.jnp_dtype)
         for k, s in layer.weight_specs.items()}
    ins = [jax.ShapeDtypeStruct(t.spec.shape, t.spec.dtype.jnp_dtype)
           for t in layer.inputs]
    state = {f"{layer.name}/score_bias":
             jax.ShapeDtypeStruct((g.num_experts,), jnp.float32)}

    def f(ins, w, state):
        ctx = LoweringCtx(state=state, training=training)
        return get_op_def(layer.op_type).lower(layer, ins, w, ctx)

    return jax.make_jaxpr(f)(ins, w, state)


@pytest.mark.parametrize("ladder", (False, True))
def test_outside_training_nothing_is_tagged(two_blocks, monkeypatch, ladder):
    """A layer lowered with `ctx.training` false (every serving program, an
    evaluation) has no `name` equation and no checkpoint, and is equation
    for equation the layer with the tagging patched out; lowered for
    training it names the six parts of the decision."""
    two_blocks(rung_rows=4 if ladder else None)
    served = moe_layer_jaxpr(training=False)
    counts = primitives(served.jaxpr)
    assert "name" not in counts and "remat2" not in counts
    trained = primitives(moe_layer_jaxpr(training=True).jaxpr)
    # experts, their scores, routed, order, sizes, and the order's inverse
    # in the branch that combines the whole block
    assert trained["name"] == 6 and trained["remat2"] == 1
    patch_out(monkeypatch)
    assert str(moe_layer_jaxpr(training=False)) == str(served)
    assert "name" not in primitives(moe_layer_jaxpr(training=True).jaxpr)


def checkpoint_policies(monkeypatch, build):
    """The `policy=` of every `jax.checkpoint` that `run_block` makes while
    a model's training step is traced."""
    seen = []
    real = jax.checkpoint

    def checkpoint(fun, **options):
        if fun.__name__ == "_unit":
            seen.append(options.get("policy"))
        return real(fun, **options)

    monkeypatch.setattr(lowering.jax, "checkpoint", checkpoint)
    m = FFModel(ffconfig(2, remat_blocks=True))
    g = build(m)
    cm = m.compile(AdamOptimizer(alpha=1e-3),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=3)
    ids, pos, labels = batch_of(g, 2)
    ins = [jnp.asarray(ids), jnp.asarray(pos)][:len(m.input_tensors)]
    jax.make_jaxpr(cm.train_step)(cm.params, cm.opt_state, cm.state, ins,
                                  jnp.asarray(labels), jax.random.PRNGKey(0))
    return seen


def test_a_unit_whose_ops_name_nothing_has_no_policy(monkeypatch):
    """`remat_blocks` over a dense model: every unit is checkpointed with
    `policy=None`, as before PR 59 (the program's text is the parent's).
    Over the expert model: the units that hold an expert layer, and only
    those, keep `ROUTING_KEPT`."""
    def gpt2(m):
        g = GPT2Config.tiny()
        build_gpt2(m, g, batch=2)
        return g

    dense = checkpoint_policies(monkeypatch, gpt2)
    assert len(dense) > 3 and all(p is None for p in dense)

    def afmoe(m):
        g = held_tiny()
        build_afmoe(m, g, batch=2)
        return g

    expert = checkpoint_policies(monkeypatch, afmoe)
    assert sum(p is not None for p in expert) == EXPERT_LAYERS
    assert sum(p is None for p in expert) > EXPERT_LAYERS
    assert get_op_def(OperatorType.MOE_LAYER).kept_names \
        == (moe_ops.ROUTING_KEPT,)
    assert get_op_def(OperatorType.LINEAR).kept_names == ()
