"""The expert layer's row buffers are sized at run time (ops/moe_ops.py:
`_route_tokens` picks, per block, the smallest rung of a static ladder of
capacities that holds the pairs held here; the last rung is the whole
`tokens * k` block). Whatever rung is taken, the layer computes what the
whole-block path computes and what a plain loop over the experts computes:
no capacity factor, no dropped pair.

Tolerances. Every path multiplies the same rows by the same weights in
float32; a rung adds a token's (at most k) gated rows into the output in
the order the scatter takes them where the whole-block path adds them
choice by choice, and the grouped product's rows come in another buffer: a
few float32 ulps, about 1e-6 of the output's scale. RTOL_PATHS 1e-5 leaves
one order for that and none for a fault (a row gated twice, dropped or given
to another token is off by the size of a row, 1e-1 and more). The dense
reference is numpy float64 over a float32 program, so it differs by
float32's own rounding of three products: RTOL_DENSE 1e-4, the family tests'
tolerance.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.core.layer import Layer
from flexflow_tpu.core.tensor import Tensor, TensorSpec
from flexflow_tpu.dtype import DataType
from flexflow_tpu.models import (DeepseekV3Config, GraniteHybridConfig,
                                 build_deepseek_v3, build_granite_hybrid)
from flexflow_tpu.ops import get_op_def, moe_ops
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.ops.registry import LoweringCtx
from flexflow_tpu.serving import compile_serving

RTOL_PATHS = 1e-5
RTOL_DENSE = 1e-4
D, EXPERTS, TOP_K, WIDTH, HELD = 64, 16, 3, 32, (0, 4)
ROWS, SEQ = 4, 64                   # rows (slots) and positions of a block
BLOCK = ROWS * SEQ                  # 256 tokens: 768 pairs, rungs 0 | 48 | 192 | 768
MIN_RUNG = 32
ROUTINGS = {
    "softmax_top_k": {},
    "sigmoid_bias_groups_scaled": {
        "scoring": "sigmoid", "n_group": 4, "topk_group": 2,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        "score_bias": True},
}
COUNTERS = ("moe_routed_pairs", "moe_held_pairs", "moe_load_max",
            "moe_load_mean", "moe_experts_hit")


def close(got, want, rtol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() <= rtol * max(np.abs(want).max(), 1e-30)


def make_weights(routing, rigged, seed=5):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return (rng.normal(size=shape) / np.sqrt(shape[-2])).astype(np.float32)

    weights = {"router": w(D, EXPERTS), "w_in": w(EXPERTS, D, 2 * WIDTH),
               "w_out": w(EXPERTS, WIDTH, D)}
    if routing.get("score_bias"):
        weights["score_bias"] = rng.uniform(-0.02, 0.02, EXPERTS).astype(
            np.float32)
    if rigged:      # feature 0 of every token is 1: the held experts win
        lo, hi = HELD
        weights["router"][0] = -6.0
        weights["router"][0, lo:hi] = 6.0
    return weights


def make_inputs(blocks, validity, seed=1):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(blocks * ROWS, SEQ, D)).astype(np.float32)
    x[..., 0] = 1.0
    valid = np.ones((blocks * ROWS, SEQ), np.int32)
    if validity == "served_wave":       # a few short prompts in padded rows
        valid[:] = 0
        for row, length in ((0, 8), (2, 30), (5, 3), (9, 17)):
            if row < valid.shape[0]:
                valid[row, :length] = 1
    elif validity == "half":
        valid[1::2] = 0
    return x, valid


def layer_of(routing, held=HELD):
    ins = [Tensor(TensorSpec((1, 1, D), DataType.FLOAT), name="x"),
           Tensor(TensorSpec((1, 1), DataType.INT32), name="valid")]
    layer = Layer(OperatorType.MOE_LAYER,
                  {"num_experts": EXPERTS, "top_k": TOP_K,
                   "expert_width": WIDTH, "experts_held": held, **routing},
                  ins, name="moe")
    get_op_def(OperatorType.MOE_LAYER).infer(layer)
    return layer


def held_weights(weights, held=HELD):
    lo, hi = held
    return {k: jnp.asarray(v[lo:hi] if k in ("w_in", "w_out") else v)
            for k, v in weights.items()}


def run_layer(routing, weights, x, valid):
    ctx = LoweringCtx(stats={})
    y = get_op_def(OperatorType.MOE_LAYER).lower(
        layer_of(routing), [jnp.asarray(x), jnp.asarray(valid)],
        held_weights(weights), ctx)[0]
    return np.asarray(y), {k: np.asarray(v) for k, v in ctx.stats.items()}


def route_blocks(routing, weights, x, valid, blocks):
    """[(rows on each held expert, rows computed)] of `_route_tokens` over
    each block of `x` alone."""
    w, p = held_weights(weights), layer_of(routing).params
    out = []
    for b in range(blocks):
        xt = jnp.asarray(x[b * ROWS:(b + 1) * ROWS].reshape(BLOCK, D))
        ex = jnp.asarray(valid[b * ROWS:(b + 1) * ROWS].reshape(BLOCK, 1) > 0)
        _y, sizes, rows = moe_ops._route_tokens(xt, ex, w, p, True)
        out.append((np.asarray(sizes), int(rows)))
    return out


def dense_reference(x, valid, weights, routing, held=HELD):
    """Token by token and expert by expert in numpy float64: scores, the
    choice as the routing's keys say, the gates, then every held expert
    that was chosen applied to the token. Returns (output, pairs held)."""
    f = np.float64
    tokens = x.reshape(-1, D).astype(f)
    raw = tokens @ weights["router"].astype(f)
    own = 1.0 / (1.0 + np.exp(-raw)) if routing.get("scoring") == "sigmoid" \
        else raw
    choice = own + weights["score_bias"] if "score_bias" in weights else own
    y = np.zeros_like(tokens)
    pairs = 0
    for t in np.flatnonzero(valid.reshape(-1)):
        allowed = np.arange(EXPERTS)
        if routing.get("n_group"):
            per = EXPERTS // routing["n_group"]
            score = [np.sort(choice[t, g * per:(g + 1) * per])[-2:].sum()
                     for g in range(routing["n_group"])]
            best = np.argsort(score)[-routing["topk_group"]:]
            allowed = np.concatenate([np.arange(g * per, (g + 1) * per)
                                      for g in best])
        top = allowed[np.argsort(-choice[t, allowed], kind="stable")[:TOP_K]]
        gate = own[t, top]
        if routing.get("scoring") != "sigmoid":
            gate = np.exp(gate - gate.max())
            gate = gate / gate.sum()
        if routing.get("norm_topk_prob"):
            gate = gate / (gate.sum() + 1e-20)
        gate = gate * routing.get("routed_scaling_factor", 1.0)
        for g, e in zip(gate, top):
            if held[0] <= e < held[1]:
                ab = tokens[t] @ weights["w_in"][e].astype(f)
                a, b = ab[:WIDTH], ab[WIDTH:]
                y[t] += g * ((a / (1.0 + np.exp(-a)) * b)
                             @ weights["w_out"][e].astype(f))
                pairs += 1
    return y.reshape(x.shape), pairs


def smallest_rung(held_pairs, ladder):
    return next(c for c in ladder if c >= held_pairs)


@pytest.fixture
def small_ladder(monkeypatch):
    """The ladder of a 256-token block: rungs of 0, 48, 192 and 768 rows."""
    monkeypatch.setattr(moe_ops, "MOE_MIN_RUNG_ROWS", MIN_RUNG)
    monkeypatch.setattr(moe_ops, "MOE_TOKEN_BLOCK", BLOCK)
    assert moe_ops._row_capacities(BLOCK * TOP_K) == [0, 48, 192, 768]


@pytest.mark.parametrize("blocks", (1, 4), ids=("one_block", "four_blocks"))
@pytest.mark.parametrize("validity", ("all_exist", "half", "served_wave",
                                      "rigged_all_held"))
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_a_rung_computes_what_the_whole_block_computes(
        routing, validity, blocks, small_ladder, monkeypatch):
    """Output, rows on each expert and the five counters are the
    whole-block path's; the rows computed are the smallest rung that holds
    each block's pairs; and all of it is the dense loop over experts: with
    every choice rigged onto a held expert the last rung is taken and
    every one of the `tokens * k` pairs is in the result."""
    r = ROUTINGS[routing]
    weights = make_weights(r, rigged=validity == "rigged_all_held")
    x, valid = make_inputs(blocks, validity)
    got, stats = run_layer(r, weights, x, valid)
    want_dense, pairs_dense = dense_reference(x, valid, weights, r)
    # rows on each held expert, block by block, with and without the ladder
    ladder = moe_ops._row_capacities(BLOCK * TOP_K)
    with_ladder = route_blocks(r, weights, x, valid, blocks)
    per_block = [rows for _sizes, rows in with_ladder]
    monkeypatch.setattr(moe_ops, "MOE_ROW_RUNGS", ())       # the whole block
    whole, whole_stats = run_layer(r, weights, x, valid)
    for (sizes, rows), (want_sizes, want_rows) in zip(
            with_ladder, route_blocks(r, weights, x, valid, blocks)):
        assert want_rows == BLOCK * TOP_K
        assert (sizes == want_sizes).all()
        assert rows == smallest_rung(int(sizes.sum()), ladder)
    assert close(got, whole, RTOL_PATHS)
    assert not got[valid == 0].any()
    for name in COUNTERS:
        assert stats[name] == whole_stats[name], name
    assert int(stats["moe_held_pairs"]) == pairs_dense
    assert int(stats["moe_rows_static"]) == blocks * BLOCK * TOP_K \
        == int(whole_stats["moe_rows_computed"])
    assert int(stats["moe_rows_computed"]) == sum(per_block)
    assert close(got, want_dense, RTOL_DENSE)
    if validity == "rigged_all_held":
        assert pairs_dense == blocks * BLOCK * TOP_K          # nothing dropped
        assert set(per_block) == {BLOCK * TOP_K}
    elif validity == "served_wave":     # rows 0, 2, 5, 9 hold a prompt
        assert per_block == [48, 48, 48, 0][:blocks]
    elif validity == "half":
        assert max(per_block) < BLOCK * TOP_K


def test_every_rung_is_taken(small_ladder):
    """The parametrised cases between them take every rung, the empty one
    too (a ladder one of whose rungs no case reaches would be tested in
    name only)."""
    taken = set()
    r = ROUTINGS["softmax_top_k"]
    for validity in ("all_exist", "half", "served_wave", "rigged_all_held"):
        weights = make_weights(r, rigged=validity == "rigged_all_held")
        x, valid = make_inputs(1, validity)
        taken.add(int(run_layer(r, weights, x, valid)[1]["moe_rows_computed"]))
    x, valid = make_inputs(1, "all_exist")
    got, stats = run_layer(r, make_weights(r, False), x, 0 * valid)
    assert not got.any() and int(stats["moe_held_pairs"]) == 0
    taken.add(int(stats["moe_rows_computed"]))
    assert taken == {0, 48, 192, 768}


def test_trace_report_prints_the_rows_computed_beside_the_held_share():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import trace_report

    wave = {"moe_routed_pairs": 1600, "moe_held_pairs": 100,
            "moe_rows_static": 655360, "moe_rows_computed": 40960}
    step = {"moe_routed_pairs": 640, "moe_held_pairs": 40, "steps": 1,
            "moe_rows_static": 640, "moe_rows_computed": 640}
    events = [{"ph": "X", "name": "serve/prefill/device_wait", "args": wave},
              {"ph": "X", "name": "serve/prefill/device_wait", "args": wave},
              {"ph": "X", "name": "serve/decode/window_sync", "args": step},
              {"ph": "X", "name": "serve/decode/window_sync",
               "args": {"moe_routed_pairs": 8, "moe_held_pairs": 1}},
              {"ph": "X", "name": "serve/admit", "args": {"wave": 1}}]
    assert trace_report.expert_layer_lines(events) == [
        "[serve] expert layers in serve/decode/window_sync: held 6.33% of "
        "648 routed pairs, rows computed 100.00% of 640 static",
        "[serve] expert layers in serve/prefill/device_wait: held 6.25% of "
        "3200 routed pairs, rows computed 6.25% of 1310720 static"]
    # a program from before the counters: the held share alone
    old = [{"ph": "X", "name": "serve/prefill/device_wait",
            "args": {"moe_routed_pairs": 10, "moe_held_pairs": 5}}]
    assert trace_report.expert_layer_lines(old) == [
        "[serve] expert layers in serve/prefill/device_wait: held 50.00% of "
        "10 routed pairs"]


# ------------------------------------------------------------------ lowering
def lowered(routing, held, tokens_shape, told=True):
    """StableHLO of the layer alone over `[batch, seq, D]`, as a training
    graph lowers it (no counters); `told`: with its `valid` input."""
    layer = layer_of(routing, held)
    weights = held_weights(make_weights(routing, rigged=False), held)

    def f(x, valid, w):
        return get_op_def(OperatorType.MOE_LAYER).lower(
            layer, [x, valid] if told else [x], w, LoweringCtx())[0]

    b, s = tokens_shape
    return jax.jit(f).lower(jnp.zeros((b, s, D), jnp.float32),
                            jnp.ones((b, s), jnp.int32), weights).as_text()


def conditionals(text):
    return len(re.findall(r"stablehlo\.(case|if)\b", text))


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
@pytest.mark.parametrize("case", ("every_expert_held", "a_decode_step"))
def test_where_the_ladder_cannot_help_the_program_is_the_one_without_it(
        routing, case, monkeypatch):
    """A holder of every expert that is told of no absent token (no `valid`
    input: `build_moe_mlp`'s graphs) and a block of a few rows (the decode
    step: 16 tokens) lower with no conditional, to the very text the layer
    lowers to with no ladder. Since PR 47 a holder of every expert WITH
    `valid` gets the ladder: its rows follow the tokens that exist."""
    r = ROUTINGS[routing]
    whole = case == "every_expert_held"
    held, shape = ((0, EXPERTS), (4, 512)) if whole else (HELD, (16, 1))
    text = lowered(r, held, shape, told=not whole)
    assert conditionals(text) == 0
    if whole:
        assert conditionals(lowered(r, held, shape)) == 1
    monkeypatch.setattr(moe_ops, "MOE_ROW_RUNGS", ())
    assert text == lowered(r, held, shape, told=not whole)


def test_a_partial_holder_of_a_long_input_gets_one_conditional():
    r = ROUTINGS["sigmoid_bias_groups_scaled"]
    assert moe_ops._row_capacities(4 * 512 * TOP_K) == [0, 384, 1536, 6144]
    assert conditionals(lowered(r, HELD, (4, 512))) == 1
    # and the decode step's size is under every rung, at the cells' widths
    assert moe_ops._row_capacities(16 * 8) == [16 * 8]
    assert moe_ops._row_capacities(16 * 10) == [16 * 10]
    assert moe_ops._row_capacities(4096 * 8) == [0, 2048, 8192, 32768]
    assert moe_ops._row_capacities(4096 * 10) == [0, 2560, 10240, 40960]


def _tiny_prefill(family):
    if family == "granite":
        g, build = GraniteHybridConfig.tiny(), build_granite_hybrid
        expert_layers, inputs = len(g.layer_types), 2
    else:
        g, build = DeepseekV3Config.tiny(), build_deepseek_v3
        expert_layers, inputs = g.layers - g.first_k_dense, 3
    slots = 4
    model = FFModel(FFConfig(batch_size=slots, seed=3, strategy_cache=False,
                             log_level="warning", mesh_shape={"data": 1}))
    build(model, g, batch=slots)
    eng = compile_serving(model, max_batch_slots=slots, max_decode_len=16,
                          kv_page_size=8)
    eng.init(seed=3)
    wave = [jnp.zeros((slots, g.seq), jnp.int32)] * inputs
    low = eng._prefill_first_tokens_jit.lower(
        eng.params, wave, jnp.zeros((slots,), jnp.int32))
    step = [jnp.zeros((slots, 1), jnp.int32)] * inputs
    decode = eng._decode_jit.lower(eng.params, eng.kv.state, step)
    return low, decode, expert_layers


@pytest.mark.parametrize("blocked", (False, True), ids=("one_piece", "lax_map"))
@pytest.mark.parametrize("family", ("granite", "gigachat"))
def test_tiny_prefill_programs_have_one_conditional_an_expert_layer(
        family, blocked, monkeypatch):
    """4 slots x 48 positions x top-3 = 576 pairs: rungs of 0, 144 and 576
    rows. In blocks under `lax.map` (a scan) the switch stays a real
    conditional in the compiled program: one branch runs, not a select over
    all of them. The decode program has none."""
    if blocked:     # 2 blocks of 96 tokens: rungs of 0, 72 and 288 rows
        monkeypatch.setattr(moe_ops, "MOE_TOKEN_BLOCK", 96)
        monkeypatch.setattr(moe_ops, "MOE_MIN_RUNG_ROWS", 64)
    low, decode, expert_layers = _tiny_prefill(family)
    text = low.as_text()
    assert conditionals(text) == expert_layers
    assert ("stablehlo.while" in text) == (blocked or family == "granite")
    compiled = low.compile().as_text()
    assert len(re.findall(r" conditional\(", compiled)) >= expert_layers
    assert conditionals(decode.as_text()) == 0


@pytest.mark.parametrize("blocks", (1, 2), ids=("one_block", "lax_map"))
def test_the_gradient_through_a_rung_is_the_whole_blocks(blocks, small_ladder,
                                                         monkeypatch):
    """A training graph with a partial `experts_held` differentiates
    through the switch: d loss / d (input, expert weights, router) is the
    whole-block path's."""
    r = ROUTINGS["sigmoid_bias_groups_scaled"]
    weights = make_weights(r, rigged=False)
    x, valid = make_inputs(blocks, "half")
    target = np.random.default_rng(7).normal(size=x.shape).astype(np.float32)
    layer = layer_of(r)

    def loss(x, w):
        y = get_op_def(OperatorType.MOE_LAYER).lower(
            layer, [x, jnp.asarray(valid)], w, LoweringCtx())[0]
        return jnp.sum((y - target) ** 2)

    w = held_weights(weights)
    got = jax.grad(loss, argnums=(0, 1))(jnp.asarray(x), w)
    assert int(run_layer(r, weights, x, valid)[1]["moe_rows_computed"]) \
        < blocks * BLOCK * TOP_K                    # a lower rung was taken
    monkeypatch.setattr(moe_ops, "MOE_ROW_RUNGS", ())
    want = jax.grad(loss, argnums=(0, 1))(jnp.asarray(x), w)
    flat_got, tree = jax.tree_util.tree_flatten(got)
    flat_want, tree_want = jax.tree_util.tree_flatten(want)
    assert tree == tree_want
    for g_, w_ in zip(flat_got, flat_want):
        assert close(g_, w_, RTOL_PATHS)
    for name in ("router", "w_in", "w_out"):        # the selection bias: 0
        assert np.abs(np.asarray(want[1][name])).max() > 0
    assert np.abs(np.asarray(want[0])).max() > 0
