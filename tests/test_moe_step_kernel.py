"""A decode step's expert layer is one kernel (kernels/moe_step.py behind
ops/moe_ops.py: `_route_step`): against the grouped-product path the parent
took (`_route_tokens`; here: the same layer with `_step_tile` answering
None) on the same inputs, at lane-aligned toy widths, the kernel
interpreted.

Tolerances. In float32 both paths multiply the same rows by the same
weights; the kernel adds a token's gated experts in expert order where the
grouped path adds them choice by choice: a few ulps, RTOL 1e-5 of the
output's scale (a row gated twice, dropped or given to another token is off
by the size of a row). In bfloat16 the grouped product rounds an expert's
output to bf16 before the gate and the kernel does not: 2^-8 of a row, RTOL
2e-2 of the scale.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.core.layer import Layer
from flexflow_tpu.core.tensor import Tensor, TensorSpec
from flexflow_tpu.dtype import DataType
from flexflow_tpu.kernels import moe_step
from flexflow_tpu.ops import get_op_def, moe_ops
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.ops.registry import LoweringCtx

D, EXPERTS, TOP_K, WIDTH = 128, 8, 3, 256
SIGMOID = {"scoring": "sigmoid", "norm_topk_prob": True, "score_bias": True}
COUNTERS = ("moe_routed_pairs", "moe_held_pairs", "moe_load_max",
            "moe_load_mean", "moe_experts_hit", "moe_experts_held",
            "moe_rows_static", "moe_rows_computed")
# name: (layer params beside the sizes, experts held, tokens, live tokens
# (None: no `valid` input), how the router is rigged, compute type)
CASES = {
    "gated_whole_holder": ({}, (0, 8), 16, None, None, "float32"),
    "gated_partial_holder": ({}, (2, 6), 16, 16, None, "float32"),
    "relu2_whole_holder": ({"expert_activation": "relu2"}, (0, 8), 16, 16,
                           None, "float32"),
    "relu2_latent_partial": ({"expert_activation": "relu2",
                              "latent_size": 256, **SIGMOID}, (2, 6), 16, 16,
                             None, "float32"),
    "gated_latent_whole": ({"latent_size": 128}, (0, 8), 16, 16, None,
                           "float32"),
    "sigmoid_groups_scaled": ({**SIGMOID, "n_group": 4, "topk_group": 2,
                               "routed_scaling_factor": 2.5}, (0, 4), 16, 16,
                              None, "float32"),
    "dead_slots": ({}, (0, 8), 16, 5, None, "float32"),
    "one_live_slot": ({**SIGMOID}, (2, 6), 16, 1, None, "float32"),
    "fewer_tokens_than_a_tile": ({}, (0, 8), 6, 4, None, "float32"),
    "all_tokens_on_one_expert": ({}, (3, 4), 16, 16, "held_win", "float32"),
    "no_pair_held": ({}, (2, 6), 16, 16, "held_lose", "float32"),
    "no_token_live": ({}, (0, 8), 16, 0, None, "float32"),
    "routed_scale_0": ({**SIGMOID, "routed_scaling_factor": 0.0}, (0, 8), 16,
                       16, None, "float32"),
    "two_tiles_of_the_width": ({}, (0, 8), 16, 16, None, "float32"),
    "bfloat16_gated": ({}, (0, 8), 16, 9, None, "bfloat16"),
    "bfloat16_relu2_latent": ({"expert_activation": "relu2",
                               "latent_size": 256}, (1, 7), 16, 9, None,
                              "bfloat16"),
}


def layer_of(params, held, dtype):
    ins = [Tensor(TensorSpec((1, 1, D), DataType.from_any(dtype)), name="x"),
           Tensor(TensorSpec((1, 1), DataType.INT32), name="valid")]
    layer = Layer(OperatorType.MOE_LAYER,
                  {"num_experts": EXPERTS, "top_k": TOP_K,
                   "expert_width": WIDTH, "experts_held": held, **params},
                  ins, name="moe")
    get_op_def(OperatorType.MOE_LAYER).infer(layer)
    return layer


def make(case, seed=0):
    params, held, tokens, live, rigged, dtype = CASES[case]
    layer = layer_of(params, held, dtype)
    rng = np.random.default_rng(seed)
    weights = {}
    for name, spec in layer.weight_specs.items():
        if name == "score_bias":
            weights[name] = rng.uniform(-0.02, 0.02, spec.shape).astype(
                np.float32)
        else:
            weights[name] = (rng.normal(size=spec.shape)
                             / np.sqrt(spec.shape[-2])).astype(np.float32)
    x = rng.normal(size=(tokens, 1, D)).astype(np.float32)
    if rigged:      # feature 0 of every token is 1: the held experts win/lose
        x[..., 0] = 1.0
        sign = 6.0 if rigged == "held_win" else -6.0
        weights["router"][0] = -sign
        weights["router"][0, held[0]:held[1]] = sign
    as_type = lambda n, v: jnp.asarray(                         # noqa: E731
        v, jnp.float32 if n == "score_bias" else dtype)
    inputs = [jnp.asarray(x, dtype)]
    if live is not None:
        inputs.append(jnp.asarray(
            (np.arange(tokens) < live).astype(np.int32).reshape(tokens, 1)))
    return layer, inputs, {n: as_type(n, v) for n, v in weights.items()}


def run(layer, inputs, weights):
    ctx = LoweringCtx(stats={})
    y = get_op_def(OperatorType.MOE_LAYER).lower(layer, inputs, weights,
                                                  ctx)[0]
    return (np.asarray(y.astype(jnp.float32)),
            {k: np.asarray(v) for k, v in ctx.stats.items()})


@pytest.fixture
def grouped(monkeypatch):
    """Switches the layer to the parent's path: no block gets a tile."""
    def switch():
        monkeypatch.setattr(moe_ops, "_step_tile", lambda *a: None)
    return switch


@pytest.mark.parametrize("case", list(CASES))
def test_the_step_kernel_against_the_grouped_product(case, grouped,
                                                     monkeypatch):
    if case == "two_tiles_of_the_width":
        # the toy expert is 3 * 128 * 256 * 4 bytes: room for half of one
        monkeypatch.setattr(moe_step, "_TILE_BYTES", 3 * D * WIDTH * 4)
        assert moe_ops._step_tile(16, D, 4, layer_of({}, (0, 8),
                                                     "float32").params) == 128
    layer, inputs, weights = make(case)
    got, stats = run(layer, inputs, weights)
    grouped()
    want, parents = run(layer, inputs, weights)
    for name in COUNTERS:
        assert stats[name] == parents[name], name
    assert parents["moe_step_kernel_experts"] == 0
    assert stats["moe_step_kernel_experts"] == stats["moe_experts_hit"]
    rtol = 2e-2 if CASES[case][5] == "bfloat16" else 1e-5
    scale = max(np.abs(want).max(), 1e-30)
    assert np.abs(got - want).max() <= rtol * scale
    if case in ("no_pair_held", "no_token_live"):
        # no grid step ran: nothing computed, the output is zeros
        assert stats["moe_step_kernel_experts"] == 0
        assert not got.any() and not want.any()
    elif case == "routed_scale_0":
        assert stats["moe_step_kernel_experts"] > 0 and not got.any()
    elif case == "all_tokens_on_one_expert":
        assert stats["moe_load_max"] == 16 and stats["moe_experts_hit"] == 1
        assert np.abs(got).max() > 0.1
    else:
        assert np.abs(got).max() > 0.1
    if CASES[case][3] not in (None, CASES[case][2]):    # a dead slot's row
        assert not got[CASES[case][3]:].any()


@pytest.mark.parametrize("case", ["gated_partial_holder",
                                  "relu2_latent_partial", "dead_slots"])
def test_the_gradient_of_a_step_block_is_the_grouped_products(case, grouped):
    """`custom_vjp`: the cotangent goes through `_all_rows` over the same
    pairs, so with the same cotangent both paths give the same gradients
    (to float32's rounding of the forward they are taken at)."""
    layer, inputs, weights = make(case)
    ct = jnp.asarray(np.random.default_rng(3).normal(
        size=inputs[0].shape).astype(np.float32))

    def loss(x, weights):
        y = get_op_def(OperatorType.MOE_LAYER).lower(
            layer, [x] + inputs[1:], weights, LoweringCtx(stats={}))[0]
        return jnp.sum(y * ct)

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(inputs[0], weights))
    assert "pallas_call" in jaxpr and "ragged_dot" in jaxpr
    got = jax.grad(loss, (0, 1))(inputs[0], weights)
    grouped()
    want = jax.grad(loss, (0, 1))(inputs[0], weights)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.abs(np.asarray(g) - np.asarray(w)).max() \
            <= 1e-5 * max(np.abs(np.asarray(w)).max(), 1e-30)
    assert np.abs(np.asarray(got[1]["router"])).max() > 0


@pytest.mark.parametrize("shape", [
    # (tokens, K, width, matrices in w_in, bytes a value) -> the tile
    ("nemotron", (16, 1024, 2688, 1, 2), 2688),
    ("ling", (16, 2560, 768, 2, 2), 768),
    ("granite", (16, 4096, 768, 2, 2), 768),
    ("lfm2", (16, 2048, 1536, 2, 2), 1536),
    ("gigachat", (16, 7168, 2048, 2, 2), 512),
    ("one_token", (1, 1024, 2688, 1, 2), 2688),
    ("tiny_width_64", (16, 64, 32, 2, 4), None),
    ("width_not_in_slabs", (16, 128, 192, 2, 4), None),
    ("a_verifier_block", (80, 1024, 2688, 1, 2), None),
    ("a_row_over_a_tile", (17, 1024, 2688, 1, 2), None),
], ids=lambda s: s[0] if isinstance(s[0], str) else None)
def test_the_tile_is_chosen_from_the_shapes(shape):
    _name, args, want = shape
    assert moe_step.tile_width(*args) == want
    if want:
        _tokens, k_dim, width, parts, itemsize = args
        assert width % want == 0 and want % moe_step.LANES == 0
        assert 2 * (parts + 1) * k_dim * want * itemsize \
            <= moe_step._TILE_BYTES < moe_step._VMEM_LIMIT_BYTES


@pytest.mark.parametrize("sizes", [[0, 0, 0, 0], [3, 0, 0, 1], [0, 2, 2, 0],
                                   [1, 1, 1, 1], [0, 0, 0, 5]])
def test_the_hit_experts_are_compacted_in_order(sizes):
    ids, count = moe_step.hit_experts(jnp.asarray(sizes, jnp.int32), 3)
    want = [e for e, n in enumerate(sizes) if n][:3]
    assert int(count) == sum(n > 0 for n in sizes)
    assert ids.shape == (3,)
    assert list(np.asarray(ids)[:len(want)]) == want
    jaxpr = str(jax.make_jaxpr(lambda s: moe_step.hit_experts(s, 3))(
        jnp.asarray(sizes, jnp.int32)))
    assert "sort" not in jaxpr and "scatter" not in jaxpr


def _layer_jaxpr(layer, inputs, weights, mesh=None):
    return str(jax.make_jaxpr(
        lambda x, w: get_op_def(OperatorType.MOE_LAYER).lower(
            layer, [x] + inputs[1:], w,
            LoweringCtx(stats={}, mesh=mesh))[0])(inputs[0], weights))


def test_a_step_block_holds_no_sort_gather_or_grouped_product():
    """What went from the step's program: both argsorts (the router's
    `top_k` stays), the row gather and the combine's k gathers, the
    `bincount` scatter and both grouped products."""
    layer, inputs, weights = make("relu2_latent_partial")
    jaxpr = _layer_jaxpr(layer, inputs, weights)
    assert "pallas_call" in jaxpr and "ff_moe_step" in jaxpr
    for gone in ("ragged_dot", "argsort", " sort[", "scatter"):
        assert gone not in jaxpr, gone
    # the one gather left is `_choose`'s take of the chosen scores
    assert jaxpr.count(" gather[") == 1


@pytest.mark.parametrize("block", ["a_verifier_block", "a_wave_block",
                                   "a_tiny_width", "a_step_on_two_devices"])
def test_every_other_block_keeps_off_the_step_kernel(block):
    mesh = None
    if block == "a_tiny_width":
        ins = [Tensor(TensorSpec((1, 1, 64), DataType.FLOAT), name="x")]
        layer = Layer(OperatorType.MOE_LAYER,
                      {"num_experts": 8, "top_k": 2, "expert_width": 32,
                       "experts_held": (0, 8)}, ins, name="moe")
        get_op_def(OperatorType.MOE_LAYER).infer(layer)
        x = jnp.zeros((16, 1, 64), jnp.float32)
    else:
        layer = layer_of({}, (0, 8), "float32")
        x = jnp.zeros({"a_verifier_block": (16, 5, D),
                       "a_wave_block": (2, 512, D),
                       "a_step_on_two_devices": (16, 1, D)}[block],
                      jnp.float32)
    if block == "a_step_on_two_devices":
        # GSPMD cannot partition a Mosaic call (kernels/partition.py)
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("data",))
    weights = {n: jnp.zeros(s.shape, jnp.float32)
               for n, s in layer.weight_specs.items()}
    jaxpr = _layer_jaxpr(layer, [x], weights, mesh)
    assert "ff_moe_step" not in jaxpr
    if block == "a_wave_block":
        # 3072 rows at widths in whole slabs: since PR 57 the rows kernel
        # (tests/test_moe_rows_kernel.py), and no step kernel
        assert "ff_moe_rows" in jaxpr and "ragged_dot" not in jaxpr
    else:
        assert "ragged_dot" in jaxpr and "pallas_call" not in jaxpr


def test_layers_of_one_shape_trace_the_kernel_once(monkeypatch):
    """`_call` is jitted with the shapes as its key: the five to ten expert
    layers of a decode program trace and lower the body once."""
    calls = []
    kernel = moe_step._kernel
    monkeypatch.setattr(moe_step, "_kernel",
                        lambda *a, **k: calls.append(1) or kernel(*a, **k))
    moe_step._call.clear_cache()
    layer, inputs, weights = make("gated_partial_holder")

    def three(x, w):
        for _ in range(3):
            x = x + get_op_def(OperatorType.MOE_LAYER).lower(
                layer, [x] + inputs[1:], w, LoweringCtx(stats={}))[0]
        return x

    jax.jit(three).lower(inputs[0], weights)
    assert len(calls) == 1
    moe_step._call.clear_cache()
