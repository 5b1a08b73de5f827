"""The routed experts of a block of many rows are one kernel
(kernels/moe_rows.py behind ops/moe_ops.py: `_experts(.., tile)`): against
the grouped-product form the parent took (`_experts` with no tile: two
`jax.lax.ragged_dot`) and against a float64 loop over the experts, on the
same rows, at lane-aligned toy widths, the kernel interpreted.

Tolerances. In float32 both forms multiply the same rows by the same
weights with the same roundings; they differ by the order of a
contraction's sum: RTOL 1e-5 of the output's scale (a row given to another
expert is off by the size of a row). In bfloat16 both round `ab`, `mid` and
the result to bf16 at the same places: a few bf16 ulps, RTOL 2e-2.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.core.layer import Layer
from flexflow_tpu.core.tensor import Tensor, TensorSpec
from flexflow_tpu.dtype import DataType
from flexflow_tpu.kernels import moe_rows, moe_step
from flexflow_tpu.ops import get_op_def, moe_ops
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.ops.registry import LoweringCtx

TM = 128
# name: (rows on each expert, rows of the buffer, K, width, relu2, tile of
# the width, compute type)
CASES = {
    "gated": ([100, 0, 60, 96], 384, 128, 128, False, 128, "float32"),
    "relu2": ([100, 0, 60, 96], 384, 128, 128, True, 128, "float32"),
    "an_empty_group": ([120, 0, 0, 136], 384, 128, 128, False, 128,
                       "float32"),
    "a_group_that_straddles_a_tile": ([100, 100, 56, 128], 384, 128, 128,
                                      False, 128, "float32"),
    "groups_that_end_on_tile_boundaries": ([128, 256, 0, 0], 384, 128, 128,
                                           False, 128, "float32"),
    "a_group_larger_than_a_tile": ([10, 300, 0, 74], 384, 128, 128, False,
                                   128, "float32"),
    "many_groups_in_one_tile": ([3, 1, 0, 7], 384, 128, 128, True, 128,
                                "float32"),
    "all_rows_on_one_expert": ([0, 384, 0, 0], 384, 128, 128, False, 128,
                               "float32"),
    "rows_past_the_last_group": ([40, 0, 50, 0], 384, 128, 128, False, 128,
                                 "float32"),
    "no_row_at_all": ([0, 0, 0, 0], 384, 128, 128, False, 128, "float32"),
    "a_latent_wide_k": ([90, 70, 96], 256, 256, 128, True, 128, "float32"),
    "two_tiles_of_the_width": ([100, 60, 96], 256, 128, 256, False, 128,
                               "float32"),
    "two_tiles_of_the_width_relu2": ([100, 60, 96], 256, 128, 256, True, 128,
                                     "float32"),
    "bfloat16_gated": ([100, 0, 60, 96], 384, 128, 128, False, 128,
                       "bfloat16"),
    "bfloat16_relu2_two_tiles": ([30, 0, 200], 256, 128, 256, True, 128,
                                 "bfloat16"),
}


def operands(case, seed=0):
    sizes, rows, k_dim, width, relu2, _tn, dtype = CASES[case]
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, k_dim))
    w_in = rng.normal(size=(len(sizes), k_dim, width * (1 if relu2 else 2))) \
        / np.sqrt(k_dim)
    w_out = rng.normal(size=(len(sizes), width, k_dim)) / np.sqrt(width)
    return (jnp.asarray(x, dtype), jnp.asarray(sizes, jnp.int32),
            jnp.asarray(w_in, dtype), jnp.asarray(w_out, dtype))


def in_float64(x, sizes, w_in, w_out, relu2):
    x, w_in, w_out = (np.asarray(v.astype(jnp.float32), np.float64)
                      for v in (x, w_in, w_out))
    width = w_out.shape[1]
    y, start = np.zeros_like(x), 0
    for e, n in enumerate(np.asarray(sizes)):
        ab = x[start:start + n] @ w_in[e]
        mid = np.square(np.maximum(ab, 0.0)) if relu2 \
            else ab[:, :width] / (1.0 + np.exp(-ab[:, :width])) * ab[:, width:]
        y[start:start + n] = mid @ w_out[e]
        start += n
    return y


@functools.cache
def _grouped(width, relu2):
    """`_experts`' two `ragged_dot`, one compile a shape."""
    p = {"expert_width": width,
         "expert_activation": "relu2" if relu2 else None}
    return jax.jit(lambda x, sizes, w_in, w_out: moe_ops._experts(
        x, sizes, {"w_in": w_in, "w_out": w_out}, p))


@pytest.mark.parametrize("case", list(CASES))
def test_the_rows_kernel_against_the_grouped_product(case):
    sizes, rows, _k, width, relu2, tn, dtype = CASES[case]
    x, sizes_, w_in, w_out = operands(case)
    got = np.asarray(moe_rows.moe_rows(x, sizes_, w_in, w_out, relu2, TM, tn)
                     .astype(jnp.float32))
    want = np.asarray(_grouped(width, relu2)(x, sizes_, w_in, w_out)
                      .astype(jnp.float32))
    exact = in_float64(x, sizes_, w_in, w_out, relu2)
    live = sum(sizes)
    rtol = 2e-2 if dtype == "bfloat16" else 1e-5
    scale = max(np.abs(exact).max(), 1e-30)
    assert np.abs(got[:live] - want[:live]).max(initial=0.0) <= rtol * scale
    assert np.abs(got[:live] - exact[:live]).max(initial=0.0) \
        <= (3e-2 if dtype == "bfloat16" else 1e-4) * scale
    if live:
        assert np.abs(got[:live]).max() > 0.1


def test_a_row_past_the_last_group_takes_no_cotangent(monkeypatch):
    """PR 64, found on the chip: the grouped product writes nothing past
    the last group there, and neither does its transpose, so the rows'
    cotangent behind it was what the buffer held, and the layer's gather
    added it to the tokens' gradient. Here `ragged_dot`'s transpose is
    made to leave 1e4 in those rows, as the chip leaves what lay there;
    the kernel's backward rule hands on zeros for them, and the rows of a
    group what the grouped product gives."""
    real = jax.lax.ragged_dot

    def product(lhs, rhs, sizes):
        return real(lhs, rhs, sizes)

    def transposed(kept, ct):
        lhs, rhs, sizes = kept
        d_lhs, d_rhs = jax.vjp(lambda l, r: real(l, r, sizes), lhs, rhs)[1](ct)
        stale = jnp.arange(lhs.shape[0])[:, None] >= jnp.sum(sizes)
        return jnp.where(stale, 1e4, d_lhs), d_rhs, None

    as_on_the_chip = jax.custom_vjp(product)
    as_on_the_chip.defvjp(lambda *kept: (product(*kept), kept), transposed)
    sizes, rows, _k, width, relu2, tn, _dtype = CASES[
        "rows_past_the_last_group"]
    x, sizes_, w_in, w_out = operands("rows_past_the_last_group")
    ct = jnp.asarray(np.random.default_rng(1).normal(size=x.shape), x.dtype)

    def d_rows(fn):
        return np.asarray(jax.grad(lambda x: jnp.sum(fn(x) * ct))(x))

    want = d_rows(lambda x: _grouped(width, relu2)(x, sizes_, w_in, w_out))
    monkeypatch.setattr(jax.lax, "ragged_dot", as_on_the_chip)
    got = d_rows(lambda x: moe_ops._rows_kernel(x, sizes_, w_in, w_out, relu2,
                                                (TM, tn)))
    live = sum(sizes)
    assert not got[live:].any() and np.abs(got[:live]).max() > 0.1
    assert np.abs(got[:live] - want[:live]).max() \
        <= 1e-5 * np.abs(want[:live]).max()


@pytest.mark.parametrize("sizes,rows", [
    ([0, 0, 0], 256), ([256, 0, 0], 256), ([0, 0, 256], 256),
    ([100, 100, 56], 256), ([1, 1, 1], 128), ([128, 128, 128], 384),
    ([127, 2, 127], 256), ([10, 300, 74], 384), ([40, 50], 384),
    ([0, 129, 0, 1], 256),
], ids=str)
def test_the_walk_visits_each_groups_tiles_in_order(sizes, rows):
    expert, tile, lo, hi, count = (np.asarray(v) for v in jax.jit(
        lambda s: moe_rows.group_visits(s, rows, TM))(
            jnp.asarray(sizes, jnp.int32)))
    want, start = [], 0
    for e, n in enumerate(sizes):
        if n:
            want += [(e, t, start, start + n)
                     for t in range(start // TM, (start + n - 1) // TM + 1)]
        start += n
    assert expert.shape == (rows // TM + len(sizes) - 1,)
    assert int(count) == len(want)
    assert list(zip(expert, tile, lo, hi))[:len(want)] == want
    # past the count: blocks that exist, and no row
    assert (expert < len(sizes)).all() and (tile < rows // TM).all() \
        and (tile >= 0).all() and not (hi - lo)[len(want):].any()


def test_the_walk_sorts_and_scatters_nothing():
    jaxpr = str(jax.make_jaxpr(lambda s: moe_rows.group_visits(s, 384, TM))(
        jnp.zeros((4,), jnp.int32)))
    assert " sort[" not in jaxpr and "scatter" not in jaxpr


@pytest.mark.parametrize("shape", [
    # (rows, K, width, matrices in w_in, bytes a value) -> the tiles
    ("mellum_chunk", (16384, 2304, 896, 2, 2), 896),
    ("keye_chunk", (16384, 2048, 768, 2, 2), 768),
    ("nemotron_rung", (5632, 1024, 2688, 1, 2), 2688),
    ("lfm2_rung", (1024, 2048, 1536, 2, 2), 1536),
    ("gigachat_rung", (2048, 7168, 2048, 2, 2), 512),
    ("a_verifier_block", (640, 1024, 2688, 1, 2), None),
    ("fewer_rows_than_a_tile", (128, 1024, 2688, 1, 2), None),
    ("tiny_width_64", (4096, 64, 32, 2, 4), None),
    ("width_not_in_slabs", (4096, 128, 192, 2, 4), None),
], ids=lambda s: s[0] if isinstance(s[0], str) else None)
def test_the_tiles_are_chosen_from_the_shapes(shape):
    _name, args, want = shape
    tiles = moe_rows.row_tiles(*args)
    assert tiles == (want and (moe_rows.ROW_TILE, want))
    assert (tiles and tiles[1]) == moe_step.width_tile(*args[1:]) \
        or tiles is None


# ------------------------------------------------------------ in the layer
D, EXPERTS, TOP_K, WIDTH = 128, 8, 2, 256
COUNTERS = ("moe_routed_pairs", "moe_held_pairs", "moe_load_max",
            "moe_load_mean", "moe_experts_hit", "moe_experts_held",
            "moe_rows_static", "moe_rows_computed", "moe_step_kernel_experts")
# name: (layer params beside the sizes, experts held, tokens, live tokens
# (None: no `valid` input), compute type)
LAYERS = {
    "whole_holder_no_ladder": ({}, (0, 8), 256, None, "float32"),
    "whole_holder_told_all_live": ({}, (0, 8), 1024, 1024, "float32"),
    "partial_holder_middle_rung": ({}, (0, 4), 1024, 400, "float32"),
    "relu2_latent_partial": ({"expert_activation": "relu2",
                              "latent_size": 256}, (2, 6), 1024, 1024,
                             "float32"),
    "no_token_live": ({}, (0, 8), 1024, 0, "float32"),
    "bfloat16_gated": ({}, (0, 8), 512, 300, "bfloat16"),
}
# traced, never run: a block all of whose rungs are whole row tiles
ALL_RUNGS = ({}, (0, 4), 2048, 700, "float32")


def make(case, seed=0, d=D, width=WIDTH):
    params, held, tokens, live, dtype = LAYERS.get(case, ALL_RUNGS)
    ins = [Tensor(TensorSpec((1, 1, d), DataType.from_any(dtype)), name="x"),
           Tensor(TensorSpec((1, 1), DataType.INT32), name="valid")]
    layer = Layer(OperatorType.MOE_LAYER,
                  {"num_experts": EXPERTS, "top_k": TOP_K,
                   "expert_width": width, "experts_held": held, **params},
                  ins, name="moe")
    get_op_def(OperatorType.MOE_LAYER).infer(layer)
    rng = np.random.default_rng(seed)
    weights = {name: jnp.asarray(rng.normal(size=spec.shape)
                                 / np.sqrt(spec.shape[-2]), dtype)
               for name, spec in layer.weight_specs.items()}
    inputs = [jnp.asarray(rng.normal(size=(1, tokens, d)), dtype)]
    if live is not None:
        inputs.append(jnp.asarray(
            (np.arange(tokens) < live).astype(np.int32).reshape(1, tokens)))
    return layer, inputs, weights


def run(layer, inputs, weights, mesh=None):
    def lower(inputs, weights):
        ctx = LoweringCtx(stats={}, mesh=mesh)
        y = get_op_def(OperatorType.MOE_LAYER).lower(layer, inputs, weights,
                                                      ctx)[0]
        return y, ctx.stats

    y, stats = jax.jit(lower)(inputs, weights)
    return (np.asarray(y.astype(jnp.float32)),
            {k: np.asarray(v) for k, v in stats.items()})


@pytest.fixture
def grouped(monkeypatch):
    """Switches the layer to the parent's path: no buffer gets a tile."""
    def switch():
        monkeypatch.setattr(moe_ops, "_rows_tile", lambda *a: None)
    return switch


@pytest.mark.parametrize("case", list(LAYERS))
def test_a_layers_blocks_take_the_kernel_and_count_its_rows(case, grouped):
    layer, inputs, weights = make(case)
    got, stats = run(layer, inputs, weights)
    grouped()
    want, parents = run(layer, inputs, weights)
    for name in COUNTERS:
        assert stats[name] == parents[name], name
    assert parents["moe_rows_kernel"] == 0
    assert stats["moe_rows_kernel"] == stats["moe_rows_computed"]
    assert stats["moe_step_kernel_experts"] == 0
    rtol = 2e-2 if LAYERS[case][4] == "bfloat16" else 1e-5
    assert np.abs(got - want).max() \
        <= rtol * max(np.abs(want).max(), 1e-30)
    if case == "no_token_live":
        assert stats["moe_rows_computed"] == 0 and not got.any()
    else:
        assert stats["moe_rows_computed"] >= moe_rows.ROW_TILE
        assert np.abs(got).max() > 0.1
    live = LAYERS[case][3]
    if live is not None:                            # an absent token's row
        assert not got[0, live:].any()


@pytest.mark.parametrize("case", ["whole_holder_no_ladder",
                                  "relu2_latent_partial"])
def test_the_gradient_of_a_kernel_block_is_the_grouped_products(case,
                                                                grouped):
    """`custom_vjp`: the cotangent goes through `_experts`' grouped form
    over the same rows, so with the same cotangent both paths give the
    same gradients (to float32's rounding of the forward they are taken
    at)."""
    layer, inputs, weights = make(case)
    ct = jnp.asarray(np.random.default_rng(3).normal(
        size=inputs[0].shape).astype(np.float32))

    def loss(x, weights):
        y = get_op_def(OperatorType.MOE_LAYER).lower(
            layer, [x] + inputs[1:], weights, LoweringCtx(stats={}))[0]
        return jnp.sum(y * ct)

    if case == "whole_holder_no_ladder":
        jaxpr = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(inputs[0],
                                                           weights))
        assert "ff_moe_rows" in jaxpr and "ragged_dot" in jaxpr
    got = jax.jit(jax.grad(loss, (0, 1)))(inputs[0], weights)
    grouped()
    want = jax.jit(jax.grad(loss, (0, 1)))(inputs[0], weights)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.abs(np.asarray(g) - np.asarray(w)).max() \
            <= 1e-5 * max(np.abs(np.asarray(w)).max(), 1e-30)
    assert np.abs(np.asarray(got[1]["router"])).max() > 0


@pytest.mark.parametrize("block", ["on_two_devices", "a_width_of_no_slab",
                                   "a_k_of_no_slab", "fewer_rows_than_a_tile"])
def test_every_other_block_keeps_the_grouped_product(block):
    d, width, mesh = D, WIDTH, None
    if block == "on_two_devices":
        # GSPMD cannot partition a Mosaic call (kernels/partition.py)
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("data",))
    elif block == "a_width_of_no_slab":
        width = 192
    elif block == "a_k_of_no_slab":
        d = 64
    layer, inputs, weights = make("whole_holder_told_all_live", d=d,
                                  width=width)
    if block == "fewer_rows_than_a_tile":
        inputs = [v[:, :64] for v in inputs]
    jaxpr = str(jax.make_jaxpr(
        lambda x, w: get_op_def(OperatorType.MOE_LAYER).lower(
            layer, [x] + inputs[1:], w,
            LoweringCtx(stats={}, mesh=mesh))[0])(inputs[0], weights))
    assert "ragged_dot" in jaxpr and "pallas_call" not in jaxpr
    _y, stats = run(layer, inputs, weights, mesh)
    assert stats["moe_rows_kernel"] == 0 < stats["moe_rows_computed"]


def test_a_rung_under_one_tile_keeps_the_grouped_product():
    """The kernel is chosen a rung: 1024 tokens' ladder is 128, 512 and 2048
    rows, and 50 live tokens' 100 pairs take the first, which is no whole
    row tile."""
    layer, inputs, weights = make("whole_holder_told_all_live")
    inputs[1] = (jnp.arange(1024) < 50).astype(jnp.int32)[None]
    _y, stats = run(layer, inputs, weights)
    assert stats["moe_rows_computed"] == 128 < moe_rows.ROW_TILE
    assert stats["moe_rows_kernel"] == 0


def test_a_kernel_block_holds_no_grouped_product():
    layer, inputs, weights = make("all_rungs")
    jaxpr = str(jax.make_jaxpr(
        lambda x, w: get_op_def(OperatorType.MOE_LAYER).lower(
            layer, [x] + inputs[1:], w, LoweringCtx(stats={}))[0])(
                inputs[0], weights))
    assert "ragged_dot" not in jaxpr
    # the rungs 256 and 1024 and the whole block's 4096 rows
    assert jaxpr.count("ff_moe_rows") == 3
