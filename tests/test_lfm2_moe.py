"""The LFM2-MoE decoder (flexflow_tpu/models/lfm2_moe.py: gated short
convolutions in ops/short_conv_ops.py, grouped-query attention with a q/k
norm a head and rotary positions in ops/attention_ops.py, a holder of every
expert whose row buffers follow the tokens that exist in ops/moe_ops.py)
against its plain reference (benchmarks/harness/reference_lfm2_moe.py), at a
small size on the CPU with seeded random weights.

Tolerance: program and reference both compute in float32, so they differ by
the order of their sums alone (the taps on a state against three shifted
products, the grouped product against a loop over experts, the cache against
one full pass): about 1e-6 of the result's scale. RTOL 1e-4 leaves two
orders for that and none for a fault: a convolution state rounded to
bfloat16, a q/k norm left out, a selection without its bias or a state taken
at the wave's padded end is off by 1e-3 and more (each has its test).
"""

import hashlib
import io
import contextlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))
sys.path.insert(0, str(ROOT / "tools"))

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer  # noqa: E402
from flexflow_tpu import telemetry as tel  # noqa: E402
from flexflow_tpu.core.layer import Layer  # noqa: E402
from flexflow_tpu.core.tensor import Tensor, TensorSpec  # noqa: E402
from flexflow_tpu.dtype import DataType  # noqa: E402
from flexflow_tpu.models import (BailingHybridConfig, DeepseekV3Config,  # noqa: E402
                                 GPT2Config, GraniteHybridConfig,
                                 Lfm2MoeConfig, NemotronHConfig,
                                 build_bailing_hybrid, build_deepseek_v3,
                                 build_gpt2, build_granite_hybrid,
                                 build_lfm2_moe, build_nemotron_h)
from flexflow_tpu.ops import get_op_def, moe_ops, short_conv_ops  # noqa: E402
from flexflow_tpu.ops.op_type import OperatorType  # noqa: E402
from flexflow_tpu.ops.registry import STATS_KEY, LoweringCtx  # noqa: E402
from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,  # noqa: E402
                                  compile_serving,
                                  positions_valid_prompt_inputs,
                                  positions_valid_step_inputs)
from flexflow_tpu.serving.program import (clone_for_serving,  # noqa: E402
                                          page_geometry, recurrent_layers)
from families import lfm2_moe as family  # noqa: E402
from harness import flops_lfm2_moe as flops  # noqa: E402
from harness import manifest as mf  # noqa: E402
from harness import reference_lfm2_moe as reference  # noqa: E402
from served import Served, off_by  # noqa: E402

RTOL = 1e-4
SLOTS = 4
PUBLISHED = "LFM2-24B-A2B"
F32 = DataType.FLOAT


def ffconfig(batch):
    return FFConfig(batch_size=batch, seed=3, strategy_cache=False,
                    log_level="warning", mesh_shape={"data": 1})


def tiny_file() -> dict:
    return mf.read_named("configs", "lfm2-moe-tiny")


def lower(layer, inputs, weights, state=None, stats=False):
    ctx = LoweringCtx(state=state or {}, stats={} if stats else None)
    out = get_op_def(layer.op_type).lower(layer, inputs, weights, ctx)
    return out[0], ctx


def random_weights(layer, seed, scale=None):
    rng = np.random.default_rng(seed)
    out = {}
    for name, spec in layer.weight_specs.items():
        w = rng.standard_normal(spec.shape).astype(np.float32)
        fan = spec.shape[-2] if len(spec.shape) > 1 else 1
        out[name] = jnp.asarray(w / np.sqrt(fan) if scale is None else w * scale)
    return out


def tensor(shape, dtype=F32):
    return Tensor(TensorSpec(tuple(shape), dtype))


# ------------------------------------------------------- the short convolution
def conv_layer(b, s, d, kernel, mode=None, valid=True):
    ins = [tensor((b, s, d))] + ([tensor((b, s), DataType.INT32)] if valid else [])
    params = {"kernel": kernel}
    if mode:
        params["mode"] = mode
    layer = Layer(OperatorType.SHORT_CONV, params, ins, name="conv")
    get_op_def(layer.op_type).infer(layer)
    return layer


def literal_conv(x, w, kernel):
    """The recurrence, one token at a time, from a zero state: (y [s, d],
    the state after every step [s, kernel - 1, d])."""
    d = x.shape[-1]
    state = np.zeros((kernel - 1, d), np.float64)
    ys, states = [], []
    for t in range(x.shape[0]):
        bcx = x[t].astype(np.float64) @ np.asarray(w["in_proj"], np.float64)
        z = bcx[:d] * bcx[2 * d:]
        window = np.concatenate([state, z[None]], axis=0)
        c = (window * np.asarray(w["conv_w"], np.float64)).sum(axis=0)
        ys.append((bcx[d:2 * d] * c) @ np.asarray(w["out_proj"], np.float64))
        state = window[1:]
        states.append(state)
    return np.stack(ys), np.stack(states)


@pytest.mark.parametrize("kernel", (3, 4))
def test_short_conv_three_forms_against_the_literal_recurrence(kernel):
    """The whole sequence, the prefill form across a right-padded wave (each
    row's state at its last REAL token; a one-token row and an empty row keep
    zeros ahead of what they have) and decode steps from that state, against
    the recurrence one token at a time in float64 and against the
    reference's shifted products."""
    b, s, d = 4, 12, 16
    rng = np.random.default_rng(kernel)
    x = rng.standard_normal((b, s, d)).astype(np.float32)
    lengths = np.asarray([12, 1, 7, 0])
    valid = (np.arange(s)[None] < lengths[:, None]).astype(np.int32)
    w = random_weights(conv_layer(b, s, d, kernel), 1)
    want = [literal_conv(x[r], w, kernel) for r in range(b)]
    whole, _ = lower(conv_layer(b, s, d, kernel), [jnp.asarray(x),
                                                   jnp.asarray(valid)], w)
    with jax.default_matmul_precision("highest"):
        ref = jnp.stack([reference.short_conv(jnp.asarray(x[r]), w)
                         for r in range(b)])
    assert off_by(whole, np.stack([y for y, _ in want])) < RTOL
    assert off_by(whole, ref) < RTOL
    out, ctx = lower(conv_layer(b, s, d, kernel, "state_out"),
                     [jnp.asarray(x), jnp.asarray(valid)], w)
    assert off_by(out, whole) < 1e-6
    state = ctx.new_state["conv"]["conv"]
    assert state.shape == (b, kernel - 1, d)
    for r, n in enumerate(lengths):
        expect = want[r][1][n - 1] if n else np.zeros((kernel - 1, d))
        assert np.abs(np.asarray(state[r]) - expect).max() < 1e-5, r
    # decode: rows 0 and 2 go on with a fresh token each, 1 and 3 sit out
    step = conv_layer(b, 1, d, kernel, "decode")
    nxt = rng.standard_normal((b, 1, d)).astype(np.float32)
    live = np.asarray([[1], [0], [1], [0]], np.int32)
    y, ctx2 = lower(step, [jnp.asarray(nxt), jnp.asarray(live)], w,
                    state={"conv": {"conv": state}}, stats=True)
    for r in (0, 2):
        n = lengths[r]
        seq = np.concatenate([x[r, :n], nxt[r]], axis=0)
        y_lit, st_lit = literal_conv(seq, w, kernel)
        assert off_by(y[r, 0], y_lit[-1]) < RTOL
        assert np.abs(np.asarray(ctx2.new_state["conv"]["conv"][r])
                      - st_lit[-1]).max() < 1e-5
    for r in (1, 3):    # a slot that is not live keeps its state
        assert np.array_equal(ctx2.new_state["conv"]["conv"][r], state[r])
    # the live slots' state, read and written: the state-space op's counter
    assert float(ctx2.stats["ssm_state_bytes"]) == 2 * 2 * (kernel - 1) * d * 4


def test_a_bf16_convolution_state_fails_the_tolerance():
    """The state in the compute type is part of the result: the same step
    from a state rounded to bfloat16 is off by more than RTOL."""
    b, d, kernel = 2, 16, 3
    rng = np.random.default_rng(5)
    w = random_weights(conv_layer(b, 1, d, kernel), 2)
    state = jnp.asarray(rng.standard_normal((b, kernel - 1, d)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, 1, d)), jnp.float32)
    step = conv_layer(b, 1, d, kernel, "decode", valid=False)
    y, _ = lower(step, [x], w, state={"conv": {"conv": state}})
    rounded = state.astype(jnp.bfloat16).astype(jnp.float32)
    y16, _ = lower(step, [x], w, state={"conv": {"conv": rounded}})
    assert off_by(y16, y) > 3 * RTOL


def test_short_conv_declares_its_state_and_its_cost():
    layer = conv_layer(2, 8, 32, 3)
    d = get_op_def(OperatorType.SHORT_CONV)
    assert d.state_kind == "recurrent"
    assert d.slot_state(layer) == {"conv": ((2, 32), jnp.float32)}
    assert d.span_facts(layer) == {"conv_kernel": 3}
    assert d.serving_params({"kernel": 3}, "decode")["mode"] == "decode"
    assert d.serving_params({"kernel": 3}, "prefill")["mode"] == "state_out"
    assert {k: s.shape for k, s in layer.weight_specs.items()} == {
        "in_proj": (32, 96), "conv_w": (3, 32), "out_proj": (32, 32)}
    assert d.flops(layer) == 2 * 16 * 4 * 32 * 32 + 2 * 16 * 32 * 4
    with pytest.raises(ValueError):
        conv_layer(2, 8, 32, 1)
    assert short_conv_ops.conv_tail is \
        sys.modules["flexflow_tpu.ops.ssm_ops"].conv_tail


# ------------------------------------------------------------------- attention
def attn_layer(b, s, d, heads, kv, **params):
    ins = [tensor((b, s, d))] * 3 + [tensor((b, s), DataType.INT32)]
    p = {"embed_dim": d, "num_heads": heads, "bias": False, "causal": True,
         "impl": "xla", "rope_theta": 1e6, "qk_norm": True,
         "qk_norm_eps": 1e-5}
    if kv != heads:
        p["num_kv_heads"] = kv
    p.update(params)
    layer = Layer(OperatorType.MULTIHEAD_ATTENTION, p, ins, name="attn")
    get_op_def(layer.op_type).infer(layer)
    return layer


HP = {"heads": 8, "kv_heads": 2, "rope_theta": 1e6, "eps": 1e-5}


def attention_case(seed=0, b=2, s=10, d=64):
    rng = np.random.default_rng(seed)
    layer = attn_layer(b, s, d, 8, 2)
    w = random_weights(layer, seed + 1)
    w["q_norm"] = jnp.asarray(rng.uniform(0.5, 1.5, 8), jnp.float32)
    w["k_norm"] = jnp.asarray(rng.uniform(0.5, 1.5, 8), jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 500, (b, 1)) + np.arange(s)[None],
                      jnp.int32)
    return layer, w, x, pos


def reference_attention(x, pos, w, hp=HP):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([reference.attention(x[r], pos[r], w, hp)
                          for r in range(x.shape[0])])


def test_attention_with_norm_and_rotation_whole_sequence_and_prefill():
    """The whole sequence and the prefill twin against the reference; the
    prefill twin hands out k AFTER its norm and rotation, v as projected."""
    layer, w, x, pos = attention_case()
    want = reference_attention(x, pos, w)
    got, _ = lower(layer, [x, x, x, pos], w)
    assert off_by(got, want) < RTOL
    assert list(layer.weight_specs) == ["wq", "wk", "wv", "wo", "q_norm",
                                        "k_norm"]
    pre = attn_layer(2, 10, 64, 8, 2, kv_out=True)
    out, ctx = lower(pre, [x, x, x, pos], w)
    assert off_by(out, want) < RTOL
    with jax.default_matmul_precision("highest"):
        k = reference.rotate_half(reference.rms(
            (x[0] @ w["wk"]).reshape(10, 2, 8), w["k_norm"], 1e-5), pos[0], 1e6)
    assert off_by(ctx.new_state["attn"]["k"][0], k) < RTOL
    assert off_by(ctx.new_state["attn"]["v"][0],
                  (x[0] @ w["wv"]).reshape(10, 2, 8)) < RTOL


@pytest.mark.parametrize("wrong", ("no_norm", "no_rotation", "norm_after"))
def test_attention_parts_from_a_wrong_one(wrong):
    """What the tolerance refuses: q and k without their norms, without
    their rotation, or normed after it."""
    layer, w, x, pos = attention_case(3)
    got, _ = lower(layer, [x, x, x, pos], w)
    if wrong == "no_norm":
        other = reference_attention(x, pos, w, dict(HP, qk_norm=False))
    elif wrong == "no_rotation":
        other = reference_attention(x, jnp.zeros_like(pos), w)
    else:
        plain = attn_layer(2, 10, 64, 8, 2, qk_norm=False)
        plain.weight_specs.pop("q_norm", None)
        other, _ = lower(plain, [x, x, x, pos],
                         {k: v for k, v in w.items() if "norm" not in k})
        assert off_by(other, reference_attention(
            x, pos, w, dict(HP, qk_norm=False))) < RTOL
    assert off_by(got, other) > 100 * RTOL


def test_attention_decode_step_norms_and_rotates_before_the_append():
    """The paged decode twin: the step's q and k are normed and rotated at
    the slot's position, the pool takes that k, and the step's output is
    the reference's last row over prompt + token."""
    layer, w, x, pos = attention_case(5, b=2, s=9)
    pos = jnp.tile(jnp.arange(9, dtype=jnp.int32)[None], (2, 1))
    pre = attn_layer(2, 8, 64, 8, 2, kv_out=True)
    _, ctx = lower(pre, [x[:, :8]] * 3 + [pos[:, :8]], w)
    page, kvw = 4, 16
    pool_k = jnp.zeros((7, page, kvw)).at[1:5].set(
        ctx.new_state["attn"]["k"].reshape(2 * 2, page, kvw))
    pool_v = jnp.zeros((7, page, kvw)).at[1:5].set(
        ctx.new_state["attn"]["v"].reshape(2 * 2, page, kvw))
    state = {"attn": {"k": pool_k, "v": pool_v},
             "serve/page_table": jnp.asarray([[1, 2, 5], [3, 4, 6]], jnp.int32),
             "serve/pos": jnp.asarray([8, 8], jnp.int32)}
    step = attn_layer(2, 1, 64, 8, 2, decode=True)
    y, ctx2 = lower(step, [x[:, 8:]] * 3 + [pos[:, 8:]], w, state=state)
    want = reference_attention(x, pos, w)
    assert off_by(y[:, 0], want[:, 8]) < RTOL
    with jax.default_matmul_precision("highest"):
        k8 = reference.rotate_half(reference.rms(
            (x[0, 8:] @ w["wk"]).reshape(1, 2, 8), w["k_norm"], 1e-5),
            pos[0, 8:], 1e6)
    assert off_by(ctx2.new_state["attn"]["k"][5, 0], k8.reshape(-1)) < RTOL


def test_a_groups_query_heads_equal_separate_heads_with_repeated_kv():
    """Query head h reads K/V head h // 4: the grouped layer equals a layer
    of 8 K/V heads whose K/V weights repeat each group's four times."""
    layer, w, x, pos = attention_case(7)
    got, _ = lower(layer, [x, x, x, pos], w)
    full = attn_layer(2, 10, 64, 8, 8)
    rep = dict(w)
    for name in ("wk", "wv"):
        rep[name] = jnp.repeat(w[name].reshape(64, 2, 8), 4, axis=1
                               ).reshape(64, 64)
    separate, _ = lower(full, [x, x, x, pos], rep)
    assert off_by(got, separate) < 1e-6


def test_positions_and_norm_enter_a_layer_only_where_set():
    m = FFModel(ffconfig(2))
    x = m.create_tensor([2, 8, 32], name="x")
    pos = m.create_tensor([2, 8], DataType.INT32, name="pos")
    m.multihead_attention(x, x, x, 32, 4, name="plain")
    m.multihead_attention(x, x, x, 32, 4, positions=pos, rope_theta=1e6,
                          qk_norm=1e-5, name="turned")
    plain = m.get_layer_by_name("plain")
    turned = m.get_layer_by_name("turned")
    assert not {"rope_theta", "qk_norm", "qk_norm_eps"} & set(plain.params)
    assert len(plain.inputs) == 3 and "q_norm" not in plain.weight_specs
    assert turned.params["rope_theta"] == 1e6 and turned.params["qk_norm"]
    assert turned.params["qk_norm_eps"] == 1e-5 and len(turned.inputs) == 4
    facts = get_op_def(OperatorType.MULTIHEAD_ATTENTION).span_facts
    assert facts(plain) == {}
    assert facts(turned) == {"rope_theta": 1e6, "qk_norm": True}
    with pytest.raises(NotImplementedError):
        m.multihead_attention(x, x, x, 32, 4, positions=pos, add_zero_attn=True)


# ------------------------------------------------------------------ the router
def moe_layer(tokens, d, experts, k, width, held=None, valid=True, b=1,
              **params):
    ins = [tensor((b, tokens // b, d))] \
        + ([tensor((b, tokens // b), DataType.INT32)] if valid else [])
    p = {"num_experts": experts, "top_k": k, "expert_width": width,
         "experts_held": held or (0, experts), "scoring": "sigmoid",
         "norm_topk_prob": True, "routed_scaling_factor": 1.0,
         "score_bias": True, "gate_norm_eps": 1e-6}
    p.update(params)
    layer = Layer(OperatorType.MOE_LAYER, p, ins, name="moe")
    get_op_def(layer.op_type).infer(layer)
    return layer


def moe_weights(layer, seed):
    w = random_weights(layer, seed)
    rng = np.random.default_rng(seed + 100)
    w["score_bias"] = jnp.asarray(rng.uniform(-0.2, 0.2,
                                              layer.params["num_experts"]),
                                  jnp.float32)
    return w


def moe_hp(layer):
    p = layer.params
    return {"top_k": p["top_k"], "held": p["experts_held"],
            "routed_scaling_factor": 1.0, "gate_norm_eps": 1e-6}


def test_choose_without_groups_at_the_gate_norm_eps_equals_the_reference():
    """Sigmoid scores, the bias for the selection only, gates over their sum
    + 1e-6: `_choose` against the reference's router; and a router that
    leaves the bias out, or divides by the sum + 1e-20 where the gates are
    small, chooses or gates otherwise."""
    layer = moe_layer(64, 16, 8, 2, 8)
    w = moe_weights(layer, 4)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((64, 16)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        scores = x @ w["router"]
        gate, experts = moe_ops._choose(scores, w, layer.params)
        g_ref, e_ref = reference.route(x, w, moe_hp(layer))
        _, e_nobias = reference.route(x, w, dict(moe_hp(layer),
                                                 use_expert_bias=False))
    assert np.array_equal(experts, e_ref)
    assert off_by(gate, g_ref) < 1e-6
    assert not np.array_equal(e_ref, e_nobias)
    # the epsilon is a param present only where set: tiny scores part them
    small = jnp.full_like(scores, -14.0)         # sigmoid = 8e-7
    g6, _ = moe_ops._choose(small, w, layer.params)
    g20, _ = moe_ops._choose(small, w, {k: v for k, v in layer.params.items()
                                        if k != "gate_norm_eps"})
    assert float(g6.sum(-1)[0]) < 0.7 and abs(float(g20.sum(-1)[0]) - 1) < 1e-5
    m = FFModel(ffconfig(2))
    t = m.create_tensor([2, 8, 16], name="x")
    m.moe_layer(t, 8, 2, 8, name="plain")
    m.moe_layer(t, 8, 2, 8, gate_norm_eps=1e-6, name="eps")
    assert "gate_norm_eps" not in m.get_layer_by_name("plain").params
    assert m.get_layer_by_name("eps").params["gate_norm_eps"] == 1e-6


# ------------------------------------------------- the whole-holder's ladder
LADDER_TOKENS = 2048        # x top-2 = 4096 pairs: rungs [0, 256, 1024, 4096]


@pytest.mark.parametrize("existing, rung", [(0, 0), (100, 256), (128, 256),
                                            (129, 1024), (512, 1024),
                                            (513, 4096), (2048, 4096)])
def test_a_whole_holders_rung_follows_the_tokens_that_exist(existing, rung,
                                                            monkeypatch):
    """A holder of EVERY expert with a `valid` input gets the ladder: the
    rung is the smallest that holds the pairs of the tokens that exist,
    `moe_rows_computed` says so, and the output equals the no-ladder
    lowering's (every pair a row) to float32 rounding at every rung."""
    layer = moe_layer(LADDER_TOKENS, 16, 8, 2, 8)
    assert layer.params["experts_held"] == (0, 8)
    w = moe_weights(layer, 9)
    rng = np.random.default_rng(existing)
    x = jnp.asarray(rng.standard_normal((1, LADDER_TOKENS, 16)), jnp.float32)
    valid = jnp.asarray((np.arange(LADDER_TOKENS) < existing)[None], jnp.int32)
    assert moe_ops._row_capacities(LADDER_TOKENS * 2) == [0, 256, 1024, 4096]
    run = jax.jit(lambda x, valid, w: (
        lambda out, ctx: (out, ctx.stats))(*lower(layer, [x, valid], w,
                                                  stats=True)))
    got, stats = run(x, valid, w)
    assert int(stats["moe_rows_computed"]) == rung
    assert int(stats["moe_rows_static"]) == 4096
    assert int(stats["moe_held_pairs"]) == 2 * existing \
        == int(stats["moe_routed_pairs"])
    assert int(stats["moe_experts_held"]) == 8
    assert int(stats["moe_experts_hit"]) == (8 if existing >= 100 else 0)
    text = jax.jit(lambda x, valid, w: lower(layer, [x, valid], w)[0]
                   ).lower(x, valid, w).as_text()
    assert "stablehlo.case" in text
    monkeypatch.setattr(moe_ops, "_row_capacities", lambda pairs: [pairs])
    whole, stats_w = jax.jit(lambda x, valid, w: (
        lambda out, ctx: (out, ctx.stats))(*lower(layer, [x, valid], w,
                                                  stats=True)))(x, valid, w)
    assert int(stats_w["moe_rows_computed"]) == 4096
    # the same rows through the same products; a token's k gated rows are
    # added in expert order here and in choice order there, so the f32 sums
    # may part in their last bit and nowhere else
    if existing:
        assert off_by(got, whole) < 1e-6
    else:
        assert not np.any(np.asarray(whole))
    assert not np.any(np.asarray(got)[0, existing:])


def test_a_whole_holder_without_valid_and_a_decode_step_have_no_ladder():
    """Without `valid` every pair of a whole-holder has a row: it lowers as
    before (no conditional); a decode step's few pairs get no rung either."""
    for layer, ins in (
            (moe_layer(LADDER_TOKENS, 16, 8, 2, 8, valid=False), 1),
            (moe_layer(16, 16, 8, 2, 8, b=16), 2)):
        w = moe_weights(layer, 2)
        shapes = [jnp.zeros(t.spec.shape, t.spec.dtype.jnp_dtype)
                  for t in layer.inputs]
        assert len(shapes) == ins
        text = jax.jit(lambda ins, w: lower(layer, ins, w)[0]
                       ).lower(shapes, w).as_text()
        assert "stablehlo.case" not in text


def test_the_holders_parts_add_up_to_the_whole_layer():
    """The guide's tie of share and model, for the layer that now also runs
    whole: the holders (0, 4) and (4, 8) add up to the holder of all 8, and
    that to the reference's whole layer."""
    tokens, d = 96, 16
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((1, tokens, d)), jnp.float32)
    valid = jnp.asarray((np.arange(tokens) < 80)[None], jnp.int32)
    whole = moe_layer(tokens, d, 8, 2, 8)
    w = moe_weights(whole, 6)
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        part = moe_layer(tokens, d, 8, 2, 8, held=(lo, hi))
        wp = dict(w, w_in=w["w_in"][lo:hi], w_out=w["w_out"][lo:hi])
        parts.append(lower(part, [x, valid], wp)[0])
        with jax.default_matmul_precision("highest"):
            ref = reference.moe(x[0], wp, dict(moe_hp(part), held=(lo, hi)))
        assert off_by(parts[-1][0, :80], ref[:80]) < RTOL
    got, _ = lower(whole, [x, valid], w)
    assert off_by(parts[0] + parts[1], got) < RTOL
    with jax.default_matmul_precision("highest"):
        ref = reference.moe(x[0], w, moe_hp(whole))
    assert off_by(got[0, :80], ref[:80]) < RTOL
    assert not np.any(np.asarray(got[0, 80:]))


# ------------------------------------------------------------------ the model
def reference_logits(params, g, cfg, ids):
    ids = jnp.asarray(ids)
    pos = jnp.tile(jnp.arange(ids.shape[1], dtype=jnp.int32)[None],
                   (ids.shape[0], 1))
    return reference.forward(family.reference_params(params, cfg), ids, pos,
                             family.hyper(cfg))


def engine_for(g, seed=3, **compile_kw):
    model = FFModel(ffconfig(SLOTS))
    build_lfm2_moe(model, g, batch=SLOTS)
    eng = compile_serving(model, max_batch_slots=SLOTS, max_decode_len=16,
                          kv_page_size=8, **compile_kw)
    eng.init(seed=seed)
    return eng


def test_the_tiny_file_is_the_programs_tiny_config():
    g, cfg = Lfm2MoeConfig.tiny(seq=128), tiny_file()
    assert family.program_config(cfg) == g
    assert g.layer_types == ("conv", "conv", "full_attention", "conv") * 2
    assert (g.num_dense_layers, g.num_experts, g.experts_per_tok) == (1, 8, 2)


def test_forward_logits_against_the_reference():
    g = Lfm2MoeConfig.tiny(seq=40)
    m = FFModel(ffconfig(4))
    build_lfm2_moe(m, g, batch=4)
    cm = m.compile(SGDOptimizer(lr=1.0),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=3)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, g.vocab, (4, 40)).astype(np.int32)
    pos = np.tile(np.arange(40, dtype=np.int32), (4, 1))
    got = cm.forward(ids, pos, np.ones_like(ids))
    assert got.shape == (4, g.seq, g.vocab)
    want = reference_logits(cm.params, g, tiny_file(), ids)
    assert off_by(got, want) < RTOL
    # in bfloat16 the same program fails the tolerance
    m16 = FFModel(FFConfig(batch_size=4, seed=3, strategy_cache=False,
                           log_level="warning", mesh_shape={"data": 1},
                           compute_dtype="bfloat16"))
    build_lfm2_moe(m16, g, batch=4)
    cm16 = m16.compile(SGDOptimizer(lr=1.0),
                       loss_type="sparse_categorical_crossentropy", metrics=[])
    cm16.init(seed=3)
    got16 = cm16.forward(ids, pos, np.ones_like(ids))
    assert off_by(got16, reference_logits(cm16.params, g, tiny_file(), ids)) \
        > 10 * RTOL


def test_prefill_then_decode_through_cache_and_state_equals_the_full_forward():
    """Logits, not tokens. Prompts of different lengths in one padded wave
    (one of a single token, one past four pages): K/V pages (k after norm
    and rotation) and the convolutions' state are committed at each row's
    last real token; a slot that sits out the second wave keeps its state
    and decodes correctly; a second wave into a freed slot and into one
    never used. A wave's expert layers take the rung of the tokens that
    exist and every step reports what it held and moved."""
    g = Lfm2MoeConfig.tiny(seq=48)
    cfg = tiny_file()
    eng = engine_for(g)
    assert len(eng.attn_layers) == 2 and len(eng.kv.recurrent) == 6
    assert eng.kv.state_kinds == "paged_kv+recurrent"
    rng = np.random.default_rng(7)

    def wave_stats(s, stats, prompts):
        existing = sum(len(p) for p in prompts.values())
        assert int(stats["moe_held_pairs"]) == 7 * 2 * existing
        assert int(stats["moe_rows_static"]) == 7 * 2 * SLOTS * 48
        assert int(stats["moe_experts_held"]) == 7 * 8

    def step_stats(s, stats):
        assert float(stats["ssm_state_bytes"]) \
            == 2 * len(s.seqs) * eng.kv_spec.state_bytes_per_slot
        assert int(stats["moe_experts_held"]) == 7 * 8
        assert int(stats["moe_held_pairs"]) == 7 * 2 * len(s.seqs) \
            == int(stats["moe_routed_pairs"])

    s = Served(eng, lambda ids: reference_logits(eng.params, g, cfg, ids),
               positions_valid_prompt_inputs, positions_valid_step_inputs,
               RTOL, wave_stats=wave_stats, step_stats=step_stats)

    def prompt(n):
        return [int(t) for t in rng.integers(0, g.vocab, n)]

    s.wave({0: prompt(1), 1: prompt(19), 2: prompt(33)})
    s.decode(3)
    s.evict(1)
    s.wave({1: prompt(9), 3: prompt(17)})       # 0 and 2 sit it out
    s.decode(3)
    assert s.checked == 3 + 9 + 2 + 12


@pytest.mark.parametrize("wrong", ("no_qk_norm", "no_selection_bias",
                                   "state_at_the_padded_end"))
def test_the_tolerance_refuses_a_wrong_model(wrong, monkeypatch):
    """What RTOL is for: the same harness against a reference without the
    q/k norms or without the selection bias, or on an engine whose
    convolution state is taken at the wave's padded end, fails."""
    g = Lfm2MoeConfig.tiny(seq=48)
    cfg = tiny_file()
    hp = dict(family.hyper(cfg))
    if wrong == "no_qk_norm":
        hp["qk_norm"] = False
    elif wrong == "no_selection_bias":
        hp["use_expert_bias"] = False
    else:
        monkeypatch.setattr(
            short_conv_ops, "conv_tail",
            lambda x, valid, k: x[:, x.shape[1] - (k - 1):])
    eng = engine_for(g)

    def ref(ids):
        ids = jnp.asarray(ids)
        pos = jnp.tile(jnp.arange(ids.shape[1], dtype=jnp.int32)[None],
                       (ids.shape[0], 1))
        return reference.forward(family.reference_params(eng.params, cfg),
                                 ids, pos, hp)

    s = Served(eng, ref, positions_valid_prompt_inputs,
               positions_valid_step_inputs, RTOL)
    rng = np.random.default_rng(2)
    with pytest.raises(AssertionError):
        s.wave({0: [int(t) for t in rng.integers(0, g.vocab, 21)],
                1: [int(t) for t in rng.integers(0, g.vocab, 30)]})
        s.decode(2)


def test_the_scheduler_serves_it_and_its_spans_carry_the_new_facts():
    """Through ContinuousBatchingScheduler (the benchmark's path): every
    request completes; the compile span says what the layers are (two paged
    and six per-slot layers here, the convolution's kernel, the attention
    layers' theta and norm, every expert held), the wave's and the steps'
    spans carry `moe_experts_held` beside `moe_experts_hit`, and
    tools/trace_report.py prints the line."""
    import trace_report

    g = Lfm2MoeConfig.tiny(seq=48)
    tel.ring_clear()
    eng = engine_for(g)
    span = tel.ring_spans("serve/compile_serving")[-1].args
    assert (span["kv_layers"], span["state_layers"]) == (2, 6)
    assert span["conv_kernel"] == 3 and span["qk_norm"] is True
    assert span["rope_theta"] == 1e6
    assert (span["experts_held"], span["experts_routed_over"],
            span["expert_layers"]) == (8, 8, 7)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=[int(t) for t in rng.integers(0, g.vocab, n)],
                    max_new_tokens=6, arrival_s=0.0)
            for i, n in enumerate((5, 17, 30))]
    sched = ContinuousBatchingScheduler(
        eng, eng.params, positions_valid_prompt_inputs,
        positions_valid_step_inputs, eos_id=None)
    sched.run(reqs)
    assert all(r.outcome == "done" and len(r.tokens) == 6 for r in reqs)
    wave = tel.ring_spans("serve/prefill/device_wait")[-1].args
    assert wave["moe_experts_held"] == 7 * 8 >= wave["moe_experts_hit"] > 0
    assert wave["moe_rows_computed"] <= wave["moe_rows_static"]
    steps = [s.args for s in tel.ring_spans("serve/decode/window_sync")
             if s.args and "moe_experts_hit" in s.args]
    assert steps and all(a["moe_experts_held"] == 7 * 8 * a["steps"]
                         >= a["moe_experts_hit"] > 0 for a in steps)
    assert all("ssm_state_bytes" in a for a in steps)
    events = [{"ph": "X", "name": s.name, "args": dict(s.args or {})}
              for s in tel.ring_spans()]
    lines = trace_report.expert_layer_lines(events)
    assert any("of the held experts hit of" in line for line in lines), lines


# ------------------------------------- the other models lower as they lowered
def layer_text(layer, state=None):
    d = get_op_def(layer.op_type)
    w = {k: jax.ShapeDtypeStruct(s.shape, s.dtype.jnp_dtype)
         for k, s in layer.weight_specs.items()}
    ins = [jax.ShapeDtypeStruct(t.spec.shape, t.spec.dtype.jnp_dtype)
           for t in layer.inputs]

    def f(ins, w, state):
        ctx = LoweringCtx(state=state or {})
        return d.lower(layer, ins, w, ctx), ctx.new_state

    return jax.jit(f).lower(ins, w, state).as_text()


def decode_state(layer, slots=4, pages=9, page=8):
    p = layer.params
    kv = int(p.get("num_kv_heads") or p["num_heads"])
    pool = jax.ShapeDtypeStruct(
        (pages, page, kv * (p["embed_dim"] // p["num_heads"])), jnp.float32)
    return {layer.name: {"k": pool, "v": pool},
            "serve/page_table": jax.ShapeDtypeStruct((slots, 2), jnp.int32),
            "serve/pos": jax.ShapeDtypeStruct((slots,), jnp.int32)}


OTHERS = {
    "gpt2": lambda m: build_gpt2(m, GPT2Config.tiny(), batch=4),
    "granite": lambda m: build_granite_hybrid(m, GraniteHybridConfig.tiny(),
                                              batch=4),
    "gigachat": lambda m: build_deepseek_v3(m, DeepseekV3Config.tiny(),
                                            batch=4),
    "nemotron": lambda m: build_nemotron_h(m, NemotronHConfig.tiny(), batch=4),
    "ling": lambda m: build_bailing_hybrid(m, BailingHybridConfig.tiny(),
                                           batch=4)}
# sha256[:24] of the StableHLO each layer's own lowering gave at commit
# fd7a46f (PR 46), by (model, graph, op type): the training graph and both
# serving clones. GPT-2's training and prefill layers were re-pinned by PR 63
# (from ed154ffb.. / dea5b01a..): their causal attention is the interpreted
# flash call, whose program at the kernels' boundary that PR changed on
# purpose (four heads of 64: the `two_heads` entry, so the layer hands the
# kernels its projections `[4, 128, 256]` as they lie and no reshape or
# transposition stands between `x @ wq` and `@ wo`; blocks with their
# leading dimensions squeezed, `lse` `(b, h, 1, s)` and none at all in the
# prefill clone, `delta` made in the dq kernel). The
# decode clone, which calls no flash kernel, kept its text, as did granite's
# and Nemotron's layers (the XLA form at these widths)
PARENT_TEXT = {
    ("gpt2", "train", "multihead_attention"): "3577388bc88f67e03343c564",
    ("gpt2", "prefill", "multihead_attention"): "8975e3df859cb3094f427462",
    ("gpt2", "decode", "multihead_attention"): "b8dad677ccb735ebc850a721",
    ("granite", "train", "multihead_attention"): "cd7db45e046bb7ecd5e11fee",
    ("granite", "prefill", "multihead_attention"): "834730d09cb1303498000a9d",
    ("granite", "decode", "multihead_attention"): "4d2a7c30bc8947bacd36b1ae",
    ("nemotron", "train", "multihead_attention"): "96eb65d2e5274a1729a46cee",
    ("nemotron", "prefill", "multihead_attention"): "4ffad81fe1e120310d7d6e34",
    ("nemotron", "decode", "multihead_attention"): "b1a505d497a0c2135705e62d",
    ("granite", "train", "moe_layer"): "cb290e74601682adbc5f2f31",
    ("granite", "prefill", "moe_layer"): "cb290e74601682adbc5f2f31",
    ("granite", "decode", "moe_layer"): "b0c56778b6071abf87534d04",
    ("gigachat", "train", "moe_layer"): "ef9504ceb6aa8b8d80a52ba6",
    ("gigachat", "prefill", "moe_layer"): "ef9504ceb6aa8b8d80a52ba6",
    ("gigachat", "decode", "moe_layer"): "75d9cca8219520482f5e65f8",
    ("nemotron", "train", "moe_layer"): "fb5185cce147d5f928f9c4a3",
    ("nemotron", "prefill", "moe_layer"): "fb5185cce147d5f928f9c4a3",
    ("nemotron", "decode", "moe_layer"): "b580aa9e0cfb188b1c9df721",
    ("ling", "train", "moe_layer"): "ef9504ceb6aa8b8d80a52ba6",
    ("ling", "prefill", "moe_layer"): "ef9504ceb6aa8b8d80a52ba6",
    ("ling", "decode", "moe_layer"): "75d9cca8219520482f5e65f8"}


@pytest.fixture(scope="module")
def other_graphs():
    graphs = {}
    for name, build in OTHERS.items():
        m = FFModel(FFConfig(batch_size=4, only_data_parallel=True))
        build(m)
        graphs[name] = {"train": m,
                        "prefill": clone_for_serving(m, "prefill", 4)[0],
                        "decode": clone_for_serving(m, "decode", 4)[0]}
    return graphs


@pytest.mark.parametrize("model, graph, op", sorted(PARENT_TEXT))
def test_the_other_models_layers_lower_to_the_parents_text(other_graphs, model,
                                                           graph, op):
    """GPT-2's, granite's and Nemotron's attention layers (no positions, no
    q/k norm) and the four expert configurations' `moe_layer`s (part holders
    with `valid`; no `gate_norm_eps`) lower to the StableHLO the parent
    commit lowered them to: what this PR adds enters a layer only where a
    model sets it, and the ladder's rule gives a part holder what it had."""
    layer = next(l for l in other_graphs[model][graph].layers
                 if l.op_type.value == op)
    state = decode_state(layer) \
        if (graph, op) == ("decode", "multihead_attention") else None
    assert hashlib.sha256(layer_text(layer, state).encode()).hexdigest()[:24] \
        == PARENT_TEXT[model, graph, op]


# ------------------------------------------------------- counts and the file
def test_the_configuration_file_against_the_catalog_and_the_issue():
    cfg = mf.read_named("configs", PUBLISHED)
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types"]
    assert cfg["published"]["num_hidden_layers"] == 40
    assert cfg["published"]["num_dense_layers"] == 2
    full = cfg["published"]["layer_types"]
    assert full == ["conv", "conv", "full_attention", "conv"] * 10
    cut = cfg["layer_types"]
    assert cut == ["conv"] + full[2:10] and len(cut) == 9 \
        == cfg["num_hidden_layers"]
    assert (cut.count("conv"), cut.count("full_attention")) == (7, 2)
    assert cfg["num_dense_layers"] == 1
    widths = {"hidden_size": 2048, "intermediate_size": 11776,
              "moe_intermediate_size": 1536, "num_experts": 64,
              "num_experts_per_tok": 4, "num_attention_heads": 32,
              "num_key_value_heads": 8, "vocab_size": 65536,
              "conv_L_cache": 3}
    assert {k: cfg[k] for k in widths} == widths
    for key in ("source", "published", "deployment", "departures", "assumed"):
        assert cfg[key]
    assert cfg["assumed"]["head_dim"] == 64 == flops.head_dim(cfg)
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(r for r in map(json.loads, catalog.read_text().splitlines())
                   if r["name"] == PUBLISHED)
        assert cfg["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == set(cfg["reduced"])


@pytest.mark.parametrize("name", (PUBLISHED, "lfm2-moe-tiny"))
def test_flop_and_byte_functions_against_the_program(name):
    cfg = mf.read_named("configs", name)
    g = family.program_config(cfg)
    assert flops.train_flops_per_token(cfg, g.seq) == g.flops_per_token()
    assert flops.param_count(cfg) == g.param_count()
    assert flops.state_bytes_per_slot(cfg) == g.state_bytes_per_slot()
    m = FFModel(ffconfig(2))
    build_lfm2_moe(m, g, batch=2)
    assert sum(spec.num_elements for l in m.layers
               for spec in l.weight_specs.values()) == g.param_count()
    assert page_geometry(m) == {"heads": g.kv_heads, "head_dim": g.head_dim}
    assert len(recurrent_layers(m)) == g.layer_types.count("conv")


def test_the_issues_arithmetic():
    cfg = mf.read_named("configs", PUBLISHED)
    assert flops.param_count(cfg) == 5312168704
    assert flops.operator_matmul_params(cfg, "conv") \
        + flops.operator_small_params(cfg, "conv") == 16783360
    assert flops.operator_matmul_params(cfg, "full_attention") \
        + flops.operator_small_params(cfg, "full_attention") == 10485888
    assert flops.feed_forward_matmul_params(cfg, 0) == 72351744
    assert 64 * flops.expert_params(cfg) + 2048 * 64 + 64 == 604110912
    assert flops.expert_params(cfg) * 2 == 18874368
    assert flops.kv_bytes_per_token(cfg) == 4096
    assert flops.state_bytes_per_slot(cfg) == 7 * 8192
    # the published 40 layers: 23.84 B with a tied head, 23.98 B untied
    full = dict(cfg, num_hidden_layers=40, num_dense_layers=2,
                layer_types=cfg["published"]["layer_types"])
    assert round(flops.param_count(full, tied_head=True) / 1e9, 2) == 23.84
    assert round(flops.param_count(full) / 1e9, 2) == 23.98
    assert flops.param_count(full) == Lfm2MoeConfig().param_count()
    # a decode step's need: the experts hit set its bytes
    system = mf.read_named("workloads", "LFM2-24B-A2B.serve-longanswer")
    traffic = mf.read_named("traffic", "serve-longanswer")
    need = flops.decode_step_need(
        cfg, system, traffic, {"moe_routed_pairs": 6 * 4 * 8,
                               "moe_experts_hit": 8 * 21,
                               "ssm_state_bytes": 6 * 2 * 7 * 8192})
    experts = 8 * 21 * 18874368
    assert 0.70 < experts / need["bytes"] < 0.85
    assert flops.moe_decode_need(cfg, system, traffic, {
        "moe_experts_hit": 2 * 8 * 21, "steps": 2}) \
        == {"flops": 0.0, "bytes": float(experts)}
    wave = flops.prefill_wave_need(cfg, system, traffic,
                                   {"moe_held_pairs": 8 * 4 * 300})
    assert 6.8e12 < wave["flops"] < 7.6e12


def test_the_search_takes_an_attention_layer_with_positions():
    """tp_heads still shards it (the head norms replicated); the ring
    candidate, which lays three inputs out, is not offered."""
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.search.candidates import layer_candidates

    m = FFModel(FFConfig(batch_size=4, mesh_shape={"data": 2, "model": 2}))
    x = m.create_tensor([4, 256, 64], name="x")
    pos = m.create_tensor([4, 256], DataType.INT32, name="pos")
    m.multihead_attention(x, x, x, 64, 8, bias=False, causal=True,
                          num_kv_heads=2, positions=pos, qk_norm=1e-5,
                          name="attn")
    machine = MachineSpec(mesh_axes={"data": 2, "model": 2}, chip="v5e")
    cands = layer_candidates(m.get_layer_by_name("attn"), machine, {4})
    names = [c.name for c in cands]
    assert any(n.startswith("tp_heads") for n in names)
    assert not any(n.startswith("sp_ring") for n in names)
    tp = next(c for c in cands if c.name.startswith("tp_heads"))
    assert tp.weight_dims["q_norm"] == [None] == tp.weight_dims["k_norm"]
