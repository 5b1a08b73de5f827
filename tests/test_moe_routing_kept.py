"""An expert layer's routing decision is made once a training step (PR 59):
what `_route_tokens` decides (the chosen experts and their scores as gathered,
the pairs' order by expert and its inverse, both counts) is tagged
`moe_ops.ROUTING_KEPT` in training, and every `jax.checkpoint` around the
layer keeps it: the layer's own around a token block, and a `remat_blocks`
unit's (`compiler/lowering.run_block`, through the op's `kept_names`).

Here: a tiny afmoe decoder (four expert layers, a part holder with a stateful
selection bias) whose step holds two token blocks (`MOE_TOKEN_BLOCK` set to
32), on the CPU. "Patched out" means `moe_ops.checkpoint_name` returns its
argument: no tag, so both policies keep nothing and the step is the parent's.

The same hook's second user (PR 61): the flash forward kernel's `o` and `lse`,
named `flash_attention.FLASH_KEPT` in the call's forward rule and kept by a
`remat_blocks` unit through `multihead_attention`'s `kept_names`.

The layer says what else outlives its forward pass (PR 64): its result is
named `moe_ops.LAYER_KEPT`, which a unit keeps too, so the unit's
recomputation runs none of the layer's own work; and a training block's rows
are one `jax.custom_vjp` (`moe_ops._switched_rows`) whose backward is a
`lax.switch` of the branches' own vjps, so no branch hands on another's
residuals. "Patched out" takes this name away too; "the plain switch" puts
`lax.switch` back where the rule stands, which JAX then differentiates as it
did.

Every case of a (remat_blocks, patched, ladder) reads ONE compiled step
(`steps`, a module's worth): its jaxpr, its text and three runs of it.
"""

import collections
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))
sys.path.insert(0, str(ROOT / "tests"))

from flexflow_tpu import AdamOptimizer, FFModel, attribution  # noqa: E402
from flexflow_tpu.compiler import lowering  # noqa: E402
from flexflow_tpu.compiler.compile import build_state_init_fn  # noqa: E402
from flexflow_tpu.core.graph import topo_order  # noqa: E402
from flexflow_tpu.kernels.flash_attention import FLASH_KEPT  # noqa: E402
from flexflow_tpu.models import (GPT2Config, build_afmoe,  # noqa: E402
                                 build_gpt2)
from flexflow_tpu.ops import get_op_def, moe_ops  # noqa: E402
from flexflow_tpu.ops.op_type import OperatorType  # noqa: E402
from flexflow_tpu.ops.registry import LoweringCtx  # noqa: E402
import test_afmoe  # noqa: E402
from test_afmoe import batch_of, compiled, ffconfig  # noqa: E402
from test_afmoe import held_tiny as held_tiny_and_file  # noqa: E402
from test_flash_attention import equations, kernel_calls  # noqa: E402

EXPERT_LAYERS, ATTENTION_LAYERS = 4, 5


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


# no tag: neither the routing decision's nor the layer's result's
UNTAGGED = [(moe_ops, "checkpoint_name", lambda x, name: x)]


def patch_out(monkeypatch):
    for target, name, value in UNTAGGED:
        monkeypatch.setattr(target, name, value)


def plain_switch(branches):
    """In `moe_ops._switched_rows`' place: the conditional as JAX
    differentiates it (the parent's)."""
    return lambda rung, *operands: jax.lax.switch(rung, branches, *operands)


def held_tiny():
    """test_afmoe's part holder: 4 of 8 experts, 32 positions."""
    return held_tiny_and_file()[0]


def primitives(jaxpr):
    """{primitive name: equations}, through every sub-jaxpr."""
    return collections.Counter(e.primitive.name for e in equations(jaxpr))


def leaves_after(cm):
    """Everything a step leaves behind: the parameters, Adam's moments (the
    gradients), the biases."""
    return jax.tree_util.tree_leaves(
        (cm.params, cm.opt_state[0].mu, cm.opt_state[0].nu, cm.state))


def three_steps(g, patches, **config):
    """The tiny model's training step traced ONCE under `patches`
    ([(module, attribute, value)]), compiled once, and that executable run
    over three batches: (cm, the step's jaxpr, its compiled text, the three
    losses, `leaves_after`)."""
    ids, pos, labels = (jnp.asarray(a) for a in batch_of(g, 6))
    cm = compiled(g, **config)
    with pytest.MonkeyPatch.context() as mp:
        for target, name, value in patches:
            mp.setattr(target, name, value)
        traced = cm.train_step.trace(
            cm.params, cm.opt_state, cm.state, [ids[:2], pos[:2]], labels[:2],
            jax.random.PRNGKey(0))
    step, losses = traced.lower().compile(), []
    for at in range(0, 6, 2):       # the step donates what it updates
        cm.params, cm.opt_state, cm.state, loss, _ = step(
            cm.params, cm.opt_state, cm.state,
            [ids[at:at + 2], pos[at:at + 2]], labels[at:at + 2],
            jax.random.PRNGKey(0))
        losses.append(float(loss))
    return cm, traced.jaxpr.jaxpr, step.as_text(), losses, leaves_after(cm)


def assert_the_same_steps(ran, other):
    """Two `three_steps`: the losses and everything left behind, bit for
    bit."""
    assert ran[3] == other[3]
    assert len(ran[4]) == len(other[4]) > 50
    for a, b in zip(ran[4], other[4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(scope="module")
def steps():
    """(remat_blocks, patched, ladder) -> `three_steps` of the part holder
    whose step holds two token blocks, built once a module: the counts and
    the bit-for-bit comparison read the same compiled program."""
    built = {}

    def step(remat_blocks, patched, ladder):
        key = (remat_blocks, patched, ladder)
        if key not in built:
            patches = [(moe_ops, "MOE_TOKEN_BLOCK", 32)]
            if ladder:
                patches.append((moe_ops, "MOE_MIN_RUNG_ROWS", 4))
            if patched:
                patches.extend(UNTAGGED)
            built[key] = three_steps(held_tiny(), patches,
                                     remat_blocks=remat_blocks)
        return built[key]

    return step


# (remat_blocks, tagging patched out) -> how often the step decides: the
# forward pass, the block's recomputation, the unit's
PASSES = {(False, False): 1, (True, False): 1,
          (False, True): 2, (True, True): 3}
# and how often it multiplies a block's rows for their result: the forward
# pass and the block's backward (the gates' gradient reads `out`), and the
# unit's recomputation where the unit does not keep the layer's result
ROWS_PASSES = {(False, False): 2, (True, False): 2,
               (False, True): 2, (True, True): 3}


@pytest.mark.parametrize("ladder", (False, True))
@pytest.mark.parametrize("remat_blocks, patched", sorted(PASSES))
def test_a_training_step_decides_once_a_block_a_layer(
        steps, remat_blocks, patched, ladder):
    """The whole step (`value_and_grad` and the update) holds one `top_k`
    and one pair of sorts (the order by expert, and its inverse where the
    whole block's buffer is combined) a token block a layer: `lax.map`
    traces a block once, so one of each a layer. Without the tags: twice
    (the block's checkpoint), three times under `remat_blocks`."""
    cm, jaxpr, text, _, _ = steps(remat_blocks, patched, ladder)
    counts = primitives(jaxpr)
    passes = PASSES[remat_blocks, patched]
    assert counts["top_k"] == EXPERT_LAYERS * passes
    assert counts["sort"] == 2 * EXPERT_LAYERS * passes
    assert counts["scan"] >= EXPERT_LAYERS         # the blocks are a loop
    assert ("cond" in counts) == ladder
    assert ("name" in counts) == (not patched)
    # the engagement counter, from the compiled program's own text
    assert attribution.routing_passes(
        text, {l.name: l.op_type.value for l in cm.model.layers}) == passes


@pytest.mark.parametrize("remat_blocks", (False, True))
def test_losses_and_every_gradient_are_the_untagged_steps(steps,
                                                          remat_blocks):
    """The same program but for what is rerun: the same integers, so the
    same rows in the same order through the same rung. Losses, Adam's
    moments (the gradients), the parameters and the biases after three
    steps, bit for bit."""
    assert_the_same_steps(steps(remat_blocks, False, True),
                          steps(remat_blocks, True, True))


def rows_forwards(jaxpr, g):
    """The `ragged_dot` equations that are the experts' FIRST product in a
    forward evaluation: rows `[.., d]` by `w_in` `[held, d, 2 * width]`
    contracting `d`, the rows ragged (a transpose contracts the width, or
    the ragged rows themselves)."""
    found = 0
    for e in equations(jaxpr):
        if e.primitive.name != "ragged_dot_general":
            continue
        dims = e.params["ragged_dot_dimension_numbers"]
        (lhs_c, rhs_c), _ = dims.dot_dimension_numbers
        found += (list(lhs_c), list(rhs_c)) == ([1], [1]) \
            and list(dims.lhs_ragged_dimensions) == [0] \
            and e.invars[1].aval.shape[-1] == 2 * g.expert_width
    return found


@pytest.mark.parametrize("ladder", (False, True))
@pytest.mark.parametrize("remat_blocks, patched", sorted(ROWS_PASSES))
def test_a_unit_keeps_the_layers_result(steps, remat_blocks, patched, ladder):
    """The step multiplies a block's rows for their result twice a rung a
    layer: in the forward pass, and in the block's backward, whose gates'
    gradient reads the products' result. The `remat_blocks` unit keeps the
    layer's `y` (`LAYER_KEPT`), so its recomputation runs no product;
    without the name it runs them a third time. The engagement counter
    says the same from the compiled program's text."""
    cm, jaxpr, text, _, _ = steps(remat_blocks, patched, ladder)
    passes = ROWS_PASSES[remat_blocks, patched]
    rungs = 3 if ladder else 1          # of 64 pairs: 4, 16 and all
    assert rows_forwards(jaxpr, held_tiny()) \
        == EXPERT_LAYERS * rungs * passes
    kept = [e.outvars[0].aval.shape for e in equations(jaxpr)
            if e.primitive.name == "name"
            and e.params["name"] == moe_ops.LAYER_KEPT]
    assert kept == ([] if patched else [(2, 32, 64)] * EXPERT_LAYERS)
    assert attribution.step_passes(
        text, {l.name: l.op_type.value for l in cm.model.layers})[
        "moe_rows_passes"] == passes


@pytest.mark.parametrize("remat_blocks", (False, True))
def test_the_backwards_conditional_returns_the_cotangents_alone(
        steps, remat_blocks):
    """A layer's three conditionals a block: the order's inverse (made only
    where the whole block is combined), the forward's switch, which returns
    `y` alone, and the backward's, which returns the cotangents of the
    tokens, the gates and the two weights and nothing else: no residual
    leaves a branch, so none is written as zeros by the others. The only
    zeros are `_no_rows`' own cotangents, written where it runs."""
    _, jaxpr, _, _, _ = steps(remat_blocks, False, True)
    conds = [e for e in equations(jaxpr) if e.primitive.name == "cond"]
    assert sorted(len(e.outvars) for e in conds) \
        == [1] * 2 * EXPERT_LAYERS + [4] * EXPERT_LAYERS

    def zeros_returned(branch):
        made = {e.outvars[0]: e for e in branch.jaxpr.eqns
                if e.primitive.name == "broadcast_in_dim"}
        return [v.aval.shape for v in branch.jaxpr.outvars
                if v in made
                and isinstance(made[v].invars[0], Literal)]

    for e in conds:
        if len(e.outvars) == 4:
            nothing, *rungs = e.params["branches"]
            assert zeros_returned(nothing) \
                == [(32, 64), (32, 2), (4, 64, 96), (4, 48, 64)]
            assert [zeros_returned(b) for b in rungs] == [[], [], []]


def flash_tiny():
    """The part holder cut to two layers at 128 positions (a dense one
    under the window of 8, an expert one that sees every key; grouped K/V
    heads in both), whose attention goes through the flash kernels
    (interpreted here) as `test_afmoe.py::
    test_the_flash_path_is_taken_under_a_window_and_says_so` forces one."""
    g = held_tiny()
    g.seq, g.layer_types = 128, ("sliding_attention", "full_attention")
    return g


def forced_flash(build):
    """`build_afmoe` with `impl="flash"` on every attention layer."""
    def built(model, g, **kw):
        out = build(model, g, **kw)
        for layer in model.layers:
            if layer.op_type == OperatorType.MULTIHEAD_ATTENTION:
                layer.params["impl"] = "flash"
        return out
    return built


def test_a_unit_keeps_what_the_flash_forward_kernel_wrote(monkeypatch):
    """`remat_blocks` over attention layers that run the flash kernels: the
    step holds `ff_flash_attention_fwd` once a layer, beside one `_dq` and
    one `_dkv` (twice with `multihead_attention`'s `kept_names` emptied:
    the unit's recomputation runs it again), and losses, moments,
    parameters and biases after three steps are that step's, bit for bit."""
    monkeypatch.setattr(test_afmoe, "build_afmoe", forced_flash(build_afmoe))
    mha = get_op_def(OperatorType.MULTIHEAD_ATTENTION)
    assert mha.kept_names == (FLASH_KEPT,)
    layers, ran = len(flash_tiny().layer_types), {}
    for passes, patches in ((1, []), (2, [(mha, "kept_names", ())])):
        ran[passes] = three_steps(flash_tiny(), patches, remat_blocks=True)
        assert kernel_calls(ran[passes][1]) == {
            "ff_flash_attention_fwd": layers * passes,
            "ff_flash_attention_dq": layers, "ff_flash_attention_dkv": layers}
    assert_the_same_steps(ran[1], ran[2])


def moe_layer(training: bool, latent: bool = False, mesh=None):
    """One expert layer of the part holder alone: (its lowering as a
    function of (inputs, weights, state), the three as shapes). `latent`:
    its experts work in a latent of 32."""
    g = held_tiny()
    m = FFModel(ffconfig(2))
    build_afmoe(m, g, batch=2)
    layer = next(l for l in m.layers if l.op_type.value == "moe_layer")
    if latent:
        layer.params["latent_size"] = 32
        moe_ops._moe_layer_infer(layer)
    w = {k: jax.ShapeDtypeStruct(s.shape, s.dtype.jnp_dtype)
         for k, s in layer.weight_specs.items()}
    ins = [jax.ShapeDtypeStruct(t.spec.shape, t.spec.dtype.jnp_dtype)
           for t in layer.inputs]
    state = {f"{layer.name}/score_bias":
             jax.ShapeDtypeStruct((g.num_experts,), jnp.float32)}

    def f(ins, w, state):
        ctx = LoweringCtx(state=state, training=training, mesh=mesh)
        return get_op_def(layer.op_type).lower(layer, ins, w, ctx)

    return f, ins, w, state


def moe_layer_jaxpr(training: bool):
    f, *shapes = moe_layer(training)
    return jax.make_jaxpr(f)(*shapes)


def drawn(shapes, seed):
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [jax.random.normal(k, s.shape, s.dtype) * 0.5
               for k, s in zip(keys, leaves)])


@pytest.mark.parametrize("ladder, latent, devices", [
    (True, False, 1), (False, False, 1), (True, True, 1), (True, True, 8)])
def test_the_rule_is_the_vjp_jax_took(two_blocks, ladder, latent, devices):
    """One layer alone, two token blocks under the block's checkpoint, the
    selection bias its state: `y` and the cotangents of the tokens and of
    every weight (the router's too, through the gates) under
    `_switched_rows` are, bit for bit, those of the plain `lax.switch` that
    JAX differentiates itself: with and without the ladder, with the
    experts in a latent, on one device and with the tokens over eight."""
    two_blocks(rung_rows=4 if ladder else None)
    mesh = Mesh(np.array(jax.devices()[:devices]), ("data",)) \
        if devices > 1 else None
    ran = []
    for rule in (moe_ops._switched_rows, plain_switch):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(moe_ops, "_switched_rows", rule)
            f, ins, w, state = moe_layer(True, latent, mesh)
            ins, w = drawn(ins, 1), drawn(w, 2)
            state = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), state)
            if mesh is not None:
                ins = jax.device_put(ins, NamedSharding(mesh,
                                                        P(None, "data")))

            def pulled(ins, w, ct):
                (y,), pull = jax.vjp(lambda ins, w: f(ins, w, state), ins, w)
                return y, pull([ct])

            ran.append(jax.jit(pulled)(ins, w, drawn(ins[0], 3)))
    assert len(jax.tree_util.tree_leaves(ran[0])) == (7 if latent else 5)
    for a, b in zip(*map(jax.tree_util.tree_leaves, ran)):
        assert np.any(np.asarray(a) != 0)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("ladder", (False, True))
def test_outside_training_nothing_is_tagged(two_blocks, monkeypatch, ladder):
    """A layer lowered with `ctx.training` false (every serving program, an
    evaluation) has no `name` equation, no checkpoint and no rule of its
    own, and is equation for equation the layer with the tagging and the
    rule patched out; lowered for training it names the six parts of the
    decision and its result, and a block's rows are one `custom_vjp`."""
    two_blocks(rung_rows=4 if ladder else None)
    served = moe_layer_jaxpr(training=False)
    counts = primitives(served.jaxpr)
    assert not {"name", "remat2", "custom_vjp_call"} & set(counts)
    assert counts["cond"] == (1 if ladder else 0)
    trained = primitives(moe_layer_jaxpr(training=True).jaxpr)
    # experts, their scores, routed, order, sizes, the order's inverse (in
    # a conditional of its own under the ladder), and `y`
    assert trained["name"] == 7 and trained["remat2"] == 1
    assert trained["custom_vjp_call"] == 1
    assert trained["cond"] == (2 if ladder else 0)
    patch_out(monkeypatch)
    monkeypatch.setattr(moe_ops, "_switched_rows", plain_switch)
    assert str(moe_layer_jaxpr(training=False)) == str(served)
    untagged = primitives(moe_layer_jaxpr(training=True).jaxpr)
    assert not {"name", "custom_vjp_call"} & set(untagged)


def checkpoint_policies(monkeypatch, build):
    """What every `jax.checkpoint` that `run_block` makes keeps, while a
    model's training step is traced: the names its policy was made from,
    None for no policy."""
    seen = []
    real, real_names = jax.checkpoint, \
        jax.checkpoint_policies.save_only_these_names

    def save_only_these_names(*names):
        policy = real_names(*names)
        policy.names = names
        return policy

    def checkpoint(fun, **options):
        if fun.__name__ == "_unit":
            policy = options.get("policy")
            seen.append(policy if policy is None else policy.names)
        return real(fun, **options)

    monkeypatch.setattr(lowering.jax, "checkpoint", checkpoint)
    monkeypatch.setattr(lowering.jax.checkpoint_policies,
                        "save_only_these_names", save_only_these_names)
    m = FFModel(ffconfig(2, remat_blocks=True))
    g = build(m)
    cm = m.compile(AdamOptimizer(alpha=1e-3),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
    # traced from shapes: nothing is drawn, no init program compiled
    params, _ = cm._param_templates()
    state = jax.eval_shape(
        build_state_init_fn(topo_order(m.layers), m._initializer_overrides),
        jax.random.PRNGKey(0))
    ids, pos, labels = batch_of(g, 2)
    ins = [jnp.asarray(ids), jnp.asarray(pos)][:len(m.input_tensors)]
    jax.make_jaxpr(cm.train_step)(
        params, jax.eval_shape(cm.tx.init, params), state, ins,
        jnp.asarray(labels), jax.random.PRNGKey(0))
    return seen


def test_a_unit_whose_ops_name_nothing_has_no_policy(monkeypatch):
    """`remat_blocks` over a dense model: a unit is checkpointed with
    `policy=None`, as before PR 59, unless it holds an attention layer,
    whose flash call's residuals it keeps (PR 61; where the layer takes the
    einsum form nothing bears the name and nothing is kept). Over the
    expert model: the units that hold an expert layer, and only those,
    keep `ROUTING_KEPT` and `LAYER_KEPT` (PR 64: the layer's result)."""
    def gpt2(m):
        g = GPT2Config.tiny()
        build_gpt2(m, g, batch=2)
        return g

    dense = checkpoint_policies(monkeypatch, gpt2)
    assert dense.count((FLASH_KEPT,)) == GPT2Config.tiny().layers
    assert dense.count(None) >= 3
    assert set(dense) == {None, (FLASH_KEPT,)}

    def afmoe(m):
        g = held_tiny()
        build_afmoe(m, g, batch=2)
        return g

    expert = checkpoint_policies(monkeypatch, afmoe)
    assert expert.count((moe_ops.ROUTING_KEPT, moe_ops.LAYER_KEPT)) \
        == EXPERT_LAYERS
    assert expert.count((FLASH_KEPT,)) == ATTENTION_LAYERS
    assert expert.count(None) > EXPERT_LAYERS
    assert get_op_def(OperatorType.MOE_LAYER).kept_names \
        == (moe_ops.ROUTING_KEPT, moe_ops.LAYER_KEPT)
    assert get_op_def(OperatorType.LINEAR).kept_names == ()
