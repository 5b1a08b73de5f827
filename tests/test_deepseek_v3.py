"""The DeepSeek-V3 decoder (flexflow_tpu/models/deepseek_v3.py: multi-head
latent attention with YaRN rotary positions in ops/latent_attention_ops.py,
sigmoid / group-limited / biased routing in ops/moe_ops.py's moe_layer, the
paged latent pool in serving/) against its plain reference
(benchmarks/harness/reference_deepseek_v3.py), at a small size on the CPU
with seeded random weights.

Tolerance: program and reference both compute in float32, so they differ by
the order of their sums alone (the absorbed product against the
decompressed one, the grouped product against a loop over experts, the
cache against one full pass, the rotation by a signed permutation against
the literal pairs): about 1e-6 of the result's scale. RTOL 1e-4 leaves two
orders for that and none for a fault: a wrong mask, scale, pairing, group
or gate is off by 1e-2 and more, and the same program computing in bfloat16
is off by about 1e-2 (test_bf16_program_fails_the_f32_tolerance).
"""

import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer  # noqa: E402
from flexflow_tpu import telemetry as tel  # noqa: E402
from flexflow_tpu.core.layer import Layer  # noqa: E402
from flexflow_tpu.core.tensor import Tensor, TensorSpec  # noqa: E402
from flexflow_tpu.dtype import DataType  # noqa: E402
from flexflow_tpu.models import (DeepseekV3Config, GPT2Config,  # noqa: E402
                                 GraniteHybridConfig, build_deepseek_v3,
                                 build_gpt2, build_granite_hybrid)
from flexflow_tpu.ops import get_op_def  # noqa: E402
from flexflow_tpu.ops import latent_attention_ops as mla  # noqa: E402
from flexflow_tpu.ops.op_type import OperatorType  # noqa: E402
from flexflow_tpu.ops.registry import LoweringCtx  # noqa: E402
from flexflow_tpu.search.cost_model import KVCacheSpec  # noqa: E402
from flexflow_tpu.search.strategy_cache import graph_fingerprint  # noqa: E402
from flexflow_tpu.serving import (ContinuousBatchingScheduler, PagedKVCache,  # noqa: E402
                                  Request, compile_serving,
                                  positions_valid_prompt_inputs,
                                  positions_valid_step_inputs)
from flexflow_tpu.serving.kv_cache import PAGE_TABLE_KEY, POS_KEY  # noqa: E402
from flexflow_tpu.serving.program import clone_for_serving  # noqa: E402
from families import deepseek_v3 as family  # noqa: E402
from harness import reference_deepseek_v3 as reference  # noqa: E402
from served import Served  # noqa: E402

RTOL = 1e-4
SLOTS = 4


def file_config(g: DeepseekV3Config) -> dict:
    """`g` in the keys of a configuration file, as the family reads them."""
    lo, hi = g.experts_held
    assert lo == 0
    return {"hidden_size": g.d_model, "num_hidden_layers": g.layers,
            "first_k_dense_replace": g.first_k_dense,
            "num_attention_heads": g.heads, "q_lora_rank": g.q_lora_rank,
            "kv_lora_rank": g.kv_lora_rank,
            "qk_nope_head_dim": g.qk_nope_head_dim,
            "qk_rope_head_dim": g.qk_rope_head_dim, "v_head_dim": g.v_head_dim,
            "intermediate_size": g.dense_width,
            "moe_intermediate_size": g.expert_width,
            "n_shared_experts": g.shared_experts, "n_routed_experts": hi,
            "published": {"n_routed_experts": g.num_experts},
            "num_experts_per_tok": g.experts_per_tok, "n_group": g.n_group,
            "topk_group": g.topk_group, "norm_topk_prob": g.norm_topk_prob,
            "routed_scaling_factor": g.routed_scaling_factor,
            "rope_theta": g.rope_theta, "rope_scaling": dict(g.rope_scaling),
            "rms_norm_eps": g.eps, "vocab_size": g.vocab,
            "assumed": {"serve_positions": g.seq, "weights_dtype": g.dtype,
                        "e_score_correction_bias_range": g.score_bias_range}}


def ffconfig(batch, **kw):
    return FFConfig(batch_size=batch, seed=3, strategy_cache=False,
                    log_level="warning", mesh_shape={"data": 1}, **kw)


def compiled(g, batch=2, lr=1.0, **kw):
    model = FFModel(ffconfig(batch, **kw))
    build_deepseek_v3(model, g, batch=batch)
    cm = model.compile(SGDOptimizer(lr=lr),
                       loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=3)
    return cm


def close(got, want, rtol=RTOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    return float(np.abs(got - want).max()) <= rtol * float(np.abs(want).max())


def tokens(g, batch, seed=0):
    return np.random.default_rng(seed).integers(
        0, g.vocab, (batch, g.seq)).astype(np.int32)


def positions_of(ids):
    return np.broadcast_to(np.arange(ids.shape[1], dtype=np.int32), ids.shape)


def reference_logits(params, g, ids):
    cfg = file_config(g)
    return reference.forward(family.reference_params(params, cfg), ids,
                             positions_of(ids), family.hyper(cfg))


# ------------------------------------------------------------------ forward
def test_forward_logits_against_the_reference():
    g = DeepseekV3Config.tiny(seq=40)
    cm = compiled(g)
    ids = tokens(g, 2)
    got = cm.forward(ids, positions_of(ids), np.ones_like(ids))
    assert got.shape == (2, g.seq, g.vocab)
    assert close(got, reference_logits(cm.params, g, ids))


def test_bf16_program_fails_the_f32_tolerance():
    """The comparison is tight enough to catch a lower precision."""
    g = DeepseekV3Config.tiny(seq=40)
    cm = compiled(g, compute_dtype="bfloat16")
    ids = tokens(g, 2)
    got = cm.forward(ids, positions_of(ids), np.ones_like(ids))
    want = reference_logits(cm.params, g, ids)
    assert not close(got, want)
    assert close(got, want, rtol=0.2)       # lower precision, not another model


def test_fit_first_loss_and_gradients_against_the_reference():
    """Through compile / fit: the first step's loss and, through plain SGD
    (p1 = p0 - lr * grad), its gradients for a weight of each new kind
    against jax.grad of the reference's next_token_loss; then the loss
    falls."""
    g = DeepseekV3Config.tiny(seq=24)
    lr = 1.0
    cm = compiled(g, lr=lr)
    cfg, hp = file_config(g), family.hyper(file_config(g))
    ids = tokens(g, 2)
    labels = np.roll(ids, -1, axis=1)
    x = [ids, positions_of(ids), np.ones_like(ids)]
    before = jax.tree_util.tree_map(np.asarray, cm.params)
    want_loss, want_grad = jax.value_and_grad(reference.next_token_loss)(
        family.reference_params(before, cfg), ids, positions_of(ids), labels, hp)
    first = cm.fit(x, labels, epochs=1, verbose=False)[-1]["loss"]
    after = jax.tree_util.tree_map(np.asarray, cm.params)
    assert abs(first - float(want_loss)) <= RTOL * float(want_loss)
    layer = want_grad["layers"]
    for name, w, want in [("l0_attn", "wq_b", layer[0]["wq_b"]),
                          ("l0_attn", "wkv_a", layer[0]["wkv_a"]),
                          ("l1_attn", "wkv_b", layer[1]["wkv_b"]),
                          ("l1_attn", "kv_norm", layer[1]["kv_norm"]),
                          ("l0_mlp_in", "kernel", layer[0]["mlp_in"]),
                          ("l1_moe", "w_in", layer[1]["w_in"]),
                          ("l2_moe", "router", layer[2]["router"])]:
        got = (before[name][w] - after[name][w]) / lr
        # a step of lr 1 is read back from f32 weights: their rounding, at
        # the weights' scale, is the floor of this comparison
        floor = 4e-7 * float(np.abs(before[name][w]).max())
        assert float(np.abs(got - np.asarray(want)).max()) <= \
            RTOL * float(np.abs(want).max()) + floor, (name, w)
    cm2 = compiled(g, lr=0.05)
    losses = [cm2.fit(x, labels, epochs=1, verbose=False)[-1]["loss"]
              for _ in range(3)]
    assert losses[2] < losses[0]


# ------------------------------------------------------------------- rotary
PUBLISHED_ROPE = dict(dim=64, base=100000.0, factor=64.0, original_len=4096,
                      beta_fast=32, beta_slow=1)


def test_yarn_frequencies_and_scale_against_the_closed_form():
    """At the published keys: cd(32) = 8.38, cd(1) = 18.01, so pairs 0-8
    keep their frequency, pairs 19-31 turn 64 times slower, and those
    between are blended; scale = 192^-1/2 (0.1 ln 64 + 1)^2 = 0.14468."""
    f = 100000.0 ** (-2.0 * np.arange(32) / 64)

    def cd(n):
        return 64 * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(100000.0))

    low, high = math.floor(cd(32)), math.ceil(cd(1))
    assert (low, high) == (8, 19)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = f * (1 - ramp) + f / 64 * ramp
    got = mla.yarn_inv_freq(**PUBLISHED_ROPE)
    assert np.allclose(got, want, rtol=1e-12)
    assert np.allclose(got[:9], f[:9]) and np.allclose(got[19:], f[19:] / 64)
    assert np.all(np.diff(got) < 0)
    hp = {"dr": 64, "rope_theta": 100000.0, "rope_factor": 64.0,
          "rope_original_len": 4096, "beta_fast": 32, "beta_slow": 1}
    assert np.allclose(reference.inv_freq(hp), want, rtol=1e-12)
    # no YaRN: the plain frequencies
    assert np.allclose(mla.yarn_inv_freq(64, 100000.0), f)
    p = {"qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rope_factor": 64.0,
         "rope_mscale_all_dim": 1.0, "rope_mscale": 1.0}
    assert mla.softmax_scale(p) == pytest.approx(0.14468, abs=5e-6)
    assert mla.softmax_scale(p) == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)
    assert mla.softmax_scale({"qk_nope_head_dim": 128, "qk_rope_head_dim": 64}) \
        == pytest.approx(192 ** -0.5)
    # cos and sin are scaled by mscale / mscale_all_dim = 1
    cos, sin = mla.rope_tables(jnp.zeros((1, 1), jnp.int32), dict(p, rope_theta=1e5))
    assert np.allclose(cos, 1.0) and np.allclose(sin, 0.0)


def test_rotation_turns_the_pairs_where_they_lie():
    """Pairs are (2i, 2i+1): against complex multiplication, and against
    the reference's literal pairs; rotating halves (0..31 with 32..63)
    is another function."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 5, 3, 8)).astype(np.float32)
    pos = np.array([[0, 1, 2, 7, 40], [3, 3, 9, 100, 1279]], np.int32)
    p = {"qk_rope_head_dim": 8, "rope_theta": 100.0}
    cos, sin = mla.rope_tables(jnp.asarray(pos), p)
    got = np.asarray(mla.apply_rope(jnp.asarray(x), cos[:, :, None],
                                    sin[:, :, None]))
    inv = mla.yarn_inv_freq(8, 100.0)
    z = (x[..., 0::2] + 1j * x[..., 1::2]) \
        * np.exp(1j * pos[:, :, None, None] * inv)
    want = np.stack([z.real, z.imag], axis=-1).reshape(x.shape)
    # f32 angles of up to 1279 rad are good to about 1e-4
    assert np.allclose(got, want, atol=5e-4)
    hp = {"dr": 8, "rope_theta": 100.0, "rope_factor": 1.0, "mscale": 1.0,
          "mscale_all_dim": 1.0}
    ref = reference.rope(jnp.asarray(x), jnp.asarray(pos, jnp.float32)[:, :, None], hp)
    assert np.allclose(got, ref, atol=5e-4)
    halves = np.concatenate([x[..., :4] * np.cos(pos[:, :, None, None] * inv)
                             - x[..., 4:] * np.sin(pos[:, :, None, None] * inv),
                             x[..., 4:] * np.cos(pos[:, :, None, None] * inv)
                             + x[..., :4] * np.sin(pos[:, :, None, None] * inv)], -1)
    assert not np.allclose(got, halves, atol=1e-2)
    # position 0 turns nothing; a rotation keeps each pair's length
    assert np.allclose(got[0, 0], x[0, 0], atol=1e-6)
    assert np.allclose(got[..., 0::2] ** 2 + got[..., 1::2] ** 2,
                       x[..., 0::2] ** 2 + x[..., 1::2] ** 2, rtol=1e-4)


# ------------------------------------------------------------- expert layer
ROUTING = {"scoring": "sigmoid", "n_group": 4, "topk_group": 2,
           "norm_topk_prob": True, "routed_scaling_factor": 2.5,
           "score_bias": True}
HP = {"top_k": 3, "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
      "routed_scaling_factor": 2.5}


def moe_layer_output(x, weights, num_experts, top_k, width, held, **routing):
    ins = [Tensor(TensorSpec(x.shape, DataType.FLOAT), name="x")]
    layer = Layer(OperatorType.MOE_LAYER,
                  {"num_experts": num_experts, "top_k": top_k,
                   "expert_width": width, "experts_held": held, **routing},
                  ins, name="moe")
    op = get_op_def(OperatorType.MOE_LAYER)
    op.infer(layer)
    lo, hi = held
    w = {"router": weights["router"], "w_in": weights["w_in"][lo:hi],
         "w_out": weights["w_out"][lo:hi]}
    if routing.get("score_bias"):
        assert layer.weight_specs["score_bias"].shape == (num_experts,)
        w["score_bias"] = weights["score_bias"]
    ctx = LoweringCtx(stats={})
    return np.asarray(op.lower(layer, [jnp.asarray(x)], w, ctx)[0]), ctx.stats


def moe_weights(d=64, experts=16, width=32, shared=32, seed=5, bias=0.02):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return (rng.normal(size=shape) / np.sqrt(shape[-2])).astype(np.float32)

    return {"router": w(d, experts), "w_in": w(experts, d, 2 * width),
            "w_out": w(experts, width, d), "shared_in": w(d, 2 * shared),
            "shared_out": w(shared, d),
            "score_bias": rng.uniform(-bias, bias, experts).astype(np.float32)}


def literal_route(x, w, k=3, groups=4, keep=2, scale=2.5):
    """The issue's sentences, token by token in numpy float64."""
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ w["router"].astype(np.float64))))
    c = s + w["score_bias"]
    per = c.shape[-1] // groups
    gates, chosen = [], []
    for s_t, c_t in zip(s.reshape(-1, s.shape[-1]), c.reshape(-1, c.shape[-1])):
        score = [np.sort(c_t[g * per:(g + 1) * per])[-2:].sum()
                 for g in range(groups)]
        best = np.argsort(score)[-keep:]
        allowed = [e for g in best for e in range(g * per, (g + 1) * per)]
        top = sorted(allowed, key=lambda e: -c_t[e])[:k]
        chosen.append(sorted(top))
        gates.append({e: scale * s_t[e] / (sum(s_t[j] for j in top) + 1e-20)
                      for e in top})
    return gates, chosen


def test_routing_against_the_reference_and_the_issues_sentences():
    """Sigmoid scores; the bias decides who is chosen and never the gate;
    2 of 4 groups (by the sum of their two best), then the top 3 among
    their experts; gates normalised over the 3 and times 2.5."""
    weights = moe_weights()
    x = np.random.default_rng(1).normal(size=(2, 40, 64)).astype(np.float32)
    w = {k: jnp.asarray(v) for k, v in weights.items()}
    with jax.default_matmul_precision("highest"):
        gates, experts = reference.route(jnp.asarray(x), w, HP)
    gates, experts = np.asarray(gates), np.asarray(experts)
    want_gates, want_chosen = literal_route(x, weights)
    flat_e, flat_g = experts.reshape(-1, 3), gates.reshape(-1, 3)
    for t in range(flat_e.shape[0]):
        assert sorted(flat_e[t]) == want_chosen[t]
        for e, g in zip(flat_e[t], flat_g[t]):
            assert g == pytest.approx(want_gates[t][e], rel=1e-5)
        groups = {e // 4 for e in flat_e[t]}
        assert len(groups) <= 2
    assert np.allclose(flat_g.sum(-1), 2.5, rtol=1e-5)
    # the program's layer against the reference's, all experts held
    got, stats = moe_layer_output(x, weights, 16, 3, 32, (0, 16), **ROUTING)
    with jax.default_matmul_precision("highest"):
        want = reference.moe(jnp.asarray(x), w, dict(HP, held=(0, 16)))
    assert close(got, want)
    assert int(stats["moe_routed_pairs"]) == int(stats["moe_held_pairs"]) == 240
    # each key alone changes the result: none of them is decoration
    for drop in ROUTING:
        less = {k: v for k, v in ROUTING.items() if k != drop}
        if drop == "n_group":
            less.pop("topk_group")
        if drop == "topk_group":
            less.update(topk_group=4)
        other, _ = moe_layer_output(x, weights, 16, 3, 32, (0, 16), **less)
        assert not close(other, want, rtol=1e-3), drop


def test_the_bias_changes_who_is_chosen_and_not_the_gate():
    """A bias large beside the scores' gaps: other experts are chosen than
    without it, and the gates are still the chosen experts' own sigmoid
    scores, normalised: the bias is in no gate."""
    weights = moe_weights(bias=0.3)
    x = np.random.default_rng(2).normal(size=(1, 24, 64)).astype(np.float32)
    w = {k: jnp.asarray(v) for k, v in weights.items()}
    with jax.default_matmul_precision("highest"):
        gates, experts = reference.route(jnp.asarray(x), w, HP)
        _g0, without = reference.route(
            jnp.asarray(x), dict(w, score_bias=jnp.zeros(16)), HP)
        s = np.asarray(jax.nn.sigmoid(jnp.asarray(x) @ w["router"]))
    experts, without = np.sort(np.asarray(experts)), np.sort(np.asarray(without))
    assert (experts != without).any(axis=-1).sum() >= 6
    picked = np.take_along_axis(s, np.asarray(
        reference.route(jnp.asarray(x), w, HP)[1]), axis=-1)
    assert np.allclose(np.asarray(gates),
                       2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    got, _ = moe_layer_output(x, weights, 16, 3, 32, (0, 16), **ROUTING)
    with jax.default_matmul_precision("highest"):
        want = reference.moe(jnp.asarray(x), w, dict(HP, held=(0, 16)))
        unbiased = reference.moe(jnp.asarray(x),
                                 dict(w, score_bias=jnp.zeros(16)),
                                 dict(HP, held=(0, 16)))
    assert close(got, want) and not close(got, unbiased, rtol=1e-2)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four holders of 4 experts each (one group a holder), the shared
    expert counted once, equal the uncut reference's layer: what one chip of
    a four-chip deployment computes is its part of the whole, no more and
    no less; a token that chooses none of a holder's experts gets nothing
    from it."""
    weights = moe_weights()
    x = np.random.default_rng(1).normal(size=(2, 12, 64)).astype(np.float32)
    parts, held_pairs = [], 0
    for lo in range(0, 16, 4):
        part, stats = moe_layer_output(x, weights, 16, 3, 32, (lo, lo + 4),
                                       **ROUTING)
        parts.append(part)
        held_pairs += int(stats["moe_held_pairs"])
        assert int(stats["moe_routed_pairs"]) == 2 * 12 * 3
    w = {k: jnp.asarray(v) for k, v in weights.items()}
    with jax.default_matmul_precision("highest"):
        shared = reference.gated_mlp(jnp.asarray(x), w["shared_in"],
                                     w["shared_out"])
        want = reference.moe(jnp.asarray(x), w, dict(HP, held=(0, 16))) + shared
        first = reference.moe(
            jnp.asarray(x), dict(w, w_in=w["w_in"][:4], w_out=w["w_out"][:4]),
            dict(HP, held=(0, 4)))
        _g, experts = reference.route(jnp.asarray(x), w, HP)
    assert close(sum(parts) + np.asarray(shared), want)
    assert close(parts[0], first)       # the reference given the same share
    assert not close(sum(parts[:3]) + np.asarray(shared), want, rtol=1e-2)
    assert held_pairs == 2 * 12 * 3     # no capacity, no drops
    # 2 of 4 groups: at least two holders add nothing to a token
    nothing = sum((np.abs(p).max(axis=-1) == 0) for p in parts)
    assert (nothing >= 2).all()
    outside = ~(np.asarray(experts) < 4).any(axis=-1)
    assert outside.any() and not parts[0][outside].any()


# --------------------------------------------------------- latent attention
def latent_layer(g, mode=None, batch=2, seq=12):
    ins = [Tensor(TensorSpec((batch, seq, g.d_model), DataType.FLOAT), name="x"),
           Tensor(TensorSpec((batch, seq), DataType.INT32), name="positions")]
    rs = g.rope_scaling
    params = {"heads": g.heads, "q_lora_rank": g.q_lora_rank,
              "kv_lora_rank": g.kv_lora_rank,
              "qk_nope_head_dim": g.qk_nope_head_dim,
              "qk_rope_head_dim": g.qk_rope_head_dim,
              "v_head_dim": g.v_head_dim, "eps": g.eps,
              "rope_theta": g.rope_theta, "rope_factor": float(rs["factor"]),
              "rope_original_len": rs["original_max_position_embeddings"],
              "rope_beta_fast": 32.0, "rope_beta_slow": 1.0,
              "rope_mscale": 1.0, "rope_mscale_all_dim": 1.0, "impl": "auto"}
    if mode:
        params["mode"] = mode
    layer = Layer(OperatorType.LATENT_ATTENTION, params, ins, name="attn")
    get_op_def(OperatorType.LATENT_ATTENTION).infer(layer)
    return layer


def latent_weights(layer, seed=0):
    rng = np.random.default_rng(seed)
    out = {}
    for name, spec in layer.weight_specs.items():
        if len(spec.shape) == 1:
            out[name] = jnp.asarray(rng.uniform(0.5, 1.5, spec.shape), jnp.float32)
        else:
            out[name] = jnp.asarray(rng.normal(size=spec.shape)
                                    / np.sqrt(spec.shape[0]), jnp.float32)
    return out


def test_absorbed_decode_equals_the_decompressed_form():
    """One op, two forms: the whole sequence with K and V decompressed, and
    the last token alone against a pool that holds the latent of the others
    (a page table that is no identity, a row padded to whole lanes), in the
    absorbed form. Equal at the last position; also against the reference's
    attention."""
    g = DeepseekV3Config.tiny()
    b, s, page = 2, 12, 4
    whole = latent_layer(g, None, b, s)
    w = latent_weights(whole)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(b, s, g.d_model)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    op = get_op_def(OperatorType.LATENT_ATTENTION)
    ctx = LoweringCtx(stats={})
    want = op.lower(latent_layer(g, "latent_out", b, s), [x, pos], w, ctx)[0]
    assert close(op.lower(whole, [x, pos], w, LoweringCtx())[0], want)
    latent = ctx.new_state["attn"]["latent"]
    assert latent.shape == (b, s, g.latent_dim)
    assert int(ctx.stats["latent_tokens_committed"]) == b * s
    hp = family.hyper(file_config(g))
    with jax.default_matmul_precision("highest"):
        ref = jnp.stack([reference.attention(x[r], pos[r], w, hp)
                         for r in range(b)])
    assert close(want, ref)
    # the cache: the first s - 1 rows of each sequence, pages out of order
    width = KVCacheSpec(1, 0, 0, b, 3, page, latent_dim=g.latent_dim) \
        .row_widths()["latent"]
    assert width == 128 and g.latent_dim == 40
    table = np.array([[5, 2, 6], [1, 4, 3]], np.int32)
    pool = np.zeros((7, page, width), np.float32)
    for r in range(b):
        for t in range(s - 1):
            pool[table[r, t // page], t % page, :g.latent_dim] = latent[r, t]
    state = {"attn": {"latent": jnp.asarray(pool)},
             PAGE_TABLE_KEY: jnp.asarray(table),
             POS_KEY: jnp.full((b,), s - 1, jnp.int32),
             "serve/active": jnp.ones((b,), jnp.int32)}
    dctx = LoweringCtx(state=state, stats={})
    got = op.lower(latent_layer(g, "decode", b, 1),
                   [x[:, -1:], pos[:, -1:]], w, dctx)[0]
    assert close(got[:, 0], want[:, -1])
    # the step appended its own row where the table says, zeros after it
    new_pool = np.asarray(dctx.new_state["attn"]["latent"])
    for r in range(b):
        row = new_pool[table[r, (s - 1) // page], (s - 1) % page]
        assert close(row[:g.latent_dim], latent[r, -1]) and not row[g.latent_dim:].any()
    assert int(dctx.stats["latent_cache_tokens"]) == b * s
    assert float(dctx.stats["latent_cache_bytes"]) == b * s * width * 4


def decode_flops(seq):
    g = DeepseekV3Config.tiny(seq=seq)
    eng = engine_for(g)
    tok = jnp.zeros((SLOTS, 1), jnp.int32)
    cost = eng._decode_jit.lower(eng.params, eng.kv.state,
                                 [tok, tok, tok]).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float(cost["flops"]), eng.kv_spec.padded_len, g


def test_a_decode_steps_cost_does_not_grow_as_a_decompression_would():
    """The compiled decode step's FLOPs at two context lengths: a cached
    position costs about 2 (2 r + dr) a head (scores over r + dr, values
    over r; whole lanes here), not the 2 r (dn + dv) of decompressing it."""
    short, l0, g = decode_flops(48)
    long_, l1, _ = decode_flops(304)
    assert (l0, l1) == (64, 320)
    per = (long_ - short) / ((l1 - l0) * SLOTS * g.layers * g.heads)
    absorbed = 2 * (128 + g.kv_lora_rank)       # rows lie 128 wide at rest
    decompress = 2 * g.kv_lora_rank * (g.qk_nope_head_dim + g.v_head_dim)
    assert 0.5 * absorbed < per < 2 * absorbed < 0.5 * decompress, \
        (per, absorbed, decompress)


# ------------------------------------------------------------------ serving
def engine_for(g, **compile_kw):
    model = FFModel(ffconfig(SLOTS))
    build_deepseek_v3(model, g, batch=SLOTS)
    eng = compile_serving(model, max_batch_slots=SLOTS, max_decode_len=16,
                          kv_page_size=8, **compile_kw)
    eng.init(seed=3)
    return eng


def served(g):
    """The shared harness on this family's engine, input builders and
    reference."""
    eng = engine_for(g)

    def wave_stats(s, stats, prompts):
        assert int(stats["latent_tokens_committed"]) == \
            g.layers * sum(len(p) for p in prompts.values())

    def step_stats(s, stats):
        # every live slot attended over what it holds, this token too
        assert int(stats["latent_cache_tokens"]) == g.layers * sum(
            len(seq) for seq in s.seqs.values())

    return Served(eng, lambda ids: reference_logits(eng.params, g, ids),
                  positions_valid_prompt_inputs, positions_valid_step_inputs,
                  RTOL, wave_stats=wave_stats, step_stats=step_stats)


def test_prefill_then_decode_through_the_latent_cache_equals_the_full_forward():
    """Logits, not tokens. Prompts of different lengths in one padded wave
    (one of 2 tokens, one past four pages); a slot that sits out the second
    wave and keeps decoding correctly; a second wave into a freed slot (its
    pages reused) and into one never used. Positions come from the cache's
    own counters in the decode steps."""
    g = DeepseekV3Config.tiny(seq=48)
    rng = np.random.default_rng(7)
    s = served(g)

    def prompt(n):
        return [int(t) for t in rng.integers(0, g.vocab, n)]

    s.wave({0: prompt(2), 1: prompt(19), 2: prompt(33)})
    s.decode(3)
    freed = set(s.eng.kv._slot_pages[1])
    s.evict(1)
    s.wave({1: prompt(9), 3: prompt(17)})       # 0 and 2 sit it out
    assert freed & (set(s.eng.kv._slot_pages[1]) | set(s.eng.kv._slot_pages[3]))
    s.decode(3)
    assert s.checked == 3 + 3 * 3 + 2 + 4 * 3
    assert len(s.seqs[0]) == 2 + 1 + 6 and len(s.seqs[1]) == 9 + 1 + 3


def test_the_latent_pool_its_geometry_bytes_and_pages():
    """One pool a layer, no heads axis, no V pool; a row is r + dr values in
    whole lanes; KVCacheSpec counts what is stored; pages are admitted,
    exhausted, evicted and reused; a padded row is committed up to its last
    real token and the rest of the wave lands in the scratch page."""
    published = KVCacheSpec(layers=6, heads=0, head_dim=0, slots=16,
                            pages_per_slot=80, page_size=16, itemsize=2,
                            latent_dim=576)
    assert published.row_widths() == {"latent": 640}
    assert published.page_bytes() == 16 * 640 * 2
    assert published.layer_bytes() == (16 * 80 + 1) * 16 * 640 * 2
    assert published.total_bytes() == 6 * published.layer_bytes()
    # 1152 B of latent a token a layer, 1280 B as stored: a 37th of K and V
    # decompressed (64 heads x 320 values x 2 B)
    assert 576 * 2 == 1152 and published.page_bytes() // 16 == 1280
    assert 64 * 320 * 2 / 1152 > 35
    kv_spec = KVCacheSpec(layers=6, heads=64, head_dim=192, slots=16,
                          pages_per_slot=80, page_size=16, itemsize=2)
    assert kv_spec.fingerprint() != published.fingerprint()
    assert "latent" in published.fingerprint() \
        and "latent" not in kv_spec.fingerprint()

    spec = KVCacheSpec(layers=2, heads=0, head_dim=0, slots=3,
                       pages_per_slot=3, page_size=4, latent_dim=40)
    kv = PagedKVCache(spec, ["a", "b"])
    assert kv.state_kinds == "paged_latent"
    assert set(kv.state["a"]) == {"latent"}
    assert kv.state["a"]["latent"].shape == (10, 4, 128)
    assert kv.device_bytes() == spec.total_bytes() == 2 * 10 * 4 * 128 * 4
    kv.admit(0, 5, 9)                   # 3 pages
    kv.admit(1, 2, 12)                  # 3 pages
    kv.admit(2, 1, 7)                   # 2 pages: 1 of 9 is left
    assert kv.can_admit(4) and not kv.can_admit(5)
    kv.evict(2)
    assert len(kv.free_pages) == 3
    kv.push()
    rng = np.random.default_rng(0)
    fresh = {n: {"latent": jnp.asarray(rng.normal(size=(3, 8, 40)), jnp.float32)}
             for n in ("a", "b")}
    kept = jax.tree_util.tree_map(np.asarray, fresh)
    kv.commit_prefill(fresh, np.arange(3, dtype=np.int32),
                      np.array([5, 2, 0], np.int32))
    table = kv._table
    for name in ("a", "b"):
        pool = np.asarray(kv.state[name]["latent"])
        for slot, n in ((0, 5), (1, 2)):
            for t in range(8):
                row = pool[table[slot, t // 4], t % 4]
                if t < n:
                    assert np.array_equal(row[:40], kept[name]["latent"][slot, t])
                    assert not row[40:].any()
        # past a row's last real token nothing of it is in its pages
        assert not pool[table[0, 1], 1:].any() and not pool[table[1, 0], 2:].any()
    pages = set(kv._slot_pages[0])
    kv.evict(0)
    kv.admit(2, 3, 12)
    assert set(kv._slot_pages[2]) == pages
    for path, call in (("spill", lambda: kv.spill(1, 0)),
                       ("export_parked", lambda: kv.export_parked(1)),
                       ("import_parked", lambda: kv.import_parked(
                           0, {"pages": 1, "pos": 1, "layers": {}}))):
        with pytest.raises(NotImplementedError, match=f"{path}.*paged_latent"):
            call()
    with pytest.raises(NotImplementedError, match="quantized.*paged_latent"):
        PagedKVCache(spec, ["a"], quantized=True)


def test_scheduler_serves_it_and_reports_its_spans_and_counters():
    """Through ContinuousBatchingScheduler, with nothing model-specific in
    it: every served token is the reference's argmax over the request's own
    tokens, and the spans and counters the benchmark reads are there."""
    g = DeepseekV3Config.tiny(seq=48)
    tel.ring_clear()
    eng = engine_for(g)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=[int(t) for t in rng.integers(0, g.vocab, n)],
                    max_new_tokens=new, arrival_s=0.0)
            for i, (n, new) in enumerate([(5, 10), (17, 6), (30, 12), (9, 8),
                                          (12, 7), (20, 9), (3, 5)])]
    sched = ContinuousBatchingScheduler(
        eng, eng.params, positions_valid_prompt_inputs,
        positions_valid_step_inputs, eos_id=None)
    sched.run(reqs)
    assert len(sched.completed) == len(reqs) and sched.prefills >= 2
    for r in reqs:
        logits = np.asarray(reference_logits(
            eng.params, g, np.asarray([r.prompt + r.tokens], np.int32)))[0]
        rows = logits[len(r.prompt) - 1:len(r.prompt) - 1 + len(r.tokens)]
        assert len(r.tokens) == r.max_new_tokens
        assert (rows.argmax(-1) == np.asarray(r.tokens)).all(), r.rid
    spans = {}
    for sp in tel.ring_spans():
        spans.setdefault(sp.name, []).append(sp.args or {})
    made = spans["serve/compile_serving"][-1]
    assert (made["kv_layers"], made["state_layers"]) == (3, 0)
    assert made["paged_state"] == "paged_latent"
    assert made["kv_pool_shape"] == [SLOTS * 8 + 1, 8, 128]
    assert (made["experts_held"], made["experts_routed_over"]) == (8, 16)
    assert eng.kv_spec.latent_dim == g.latent_dim == 40
    mem = eng.memory_stats()
    assert mem["predicted_kv_cache_bytes"] == mem["actual_kv_cache_bytes_per_device"] \
        == 3 * (SLOTS * 8 + 1) * 8 * 128 * 4
    assert len(spans["serve/prefill/commit_kv"]) == sched.prefills
    assert all(a["state"] == "paged_latent"
               for name in ("serve/prefill/commit", "serve/prefill/commit_kv",
                            "serve/admit/place") for a in spans[name])
    assert all(a["bytes"] == 3 * SLOTS * g.seq * 40 * 4
               for a in spans["serve/prefill/commit_kv"])
    steps = 0
    row_bytes = 128 * 4
    for a in spans["serve/decode/window_sync"]:
        steps += a["steps"]
        # 2 expert layers, top 3: at most slots * 3 pairs a layer and step
        assert 0 <= a["moe_held_pairs"] <= a["moe_routed_pairs"] \
            <= a["steps"] * 2 * SLOTS * g.experts_per_tok
        assert a["moe_experts_hit"] <= a["steps"] * 2 * 8
        # 3 latent layers; a live slot attends over at least its prompt
        assert 0 < a["latent_cache_tokens"] <= a["steps"] * 3 * SLOTS * (g.seq + 16)
        assert a["latent_cache_bytes"] == a["latent_cache_tokens"] * row_bytes
    assert steps == sched.decode_steps
    first = reqs[:SLOTS]
    wave = spans["serve/prefill/device_wait"][0]
    assert wave["latent_tokens_committed"] == 3 * sum(len(r.prompt) for r in first)
    assert wave["moe_routed_pairs"] == 2 * g.experts_per_tok * sum(
        len(r.prompt) for r in first)
    assert 0 < wave["moe_held_pairs"] < wave["moe_routed_pairs"]
    # 576 pairs a layer and wave, 183 of them routed and about half of
    # those held: the rung of 144 rows; a decode step's 12 pairs are under
    # every rung
    assert wave["moe_rows_static"] == 2 * SLOTS * g.seq * g.experts_per_tok
    assert wave["moe_held_pairs"] <= wave["moe_rows_computed"] == 2 * 144
    assert all(a["moe_rows_computed"] == a["moe_rows_static"]
               == a["steps"] * 2 * SLOTS * g.experts_per_tok
               for a in spans["serve/decode/window_sync"])


def test_what_a_latent_cache_does_not_support_fails_loudly():
    g = DeepseekV3Config.tiny(seq=48)

    def model(**kw):
        m = FFModel(ffconfig(SLOTS, **kw))
        build_deepseek_v3(m, g, batch=SLOTS)
        return m

    def serve(m, **kw):
        return compile_serving(m, max_batch_slots=SLOTS, max_decode_len=16,
                               kv_page_size=8, **kw)

    with pytest.raises(NotImplementedError, match="paged_latent.*host KV tier"):
        serve(model(kv_host_pages=8))
    with pytest.raises(NotImplementedError, match="paged_latent.*speculative"):
        serve(model(), draft=model(), spec_tokens=2)
    with pytest.raises(NotImplementedError, match="paged_latent.*quantized"):
        serve(model(), kv_cache_dtype="int8")
    eng = serve(model())
    eng.init(seed=3)
    with pytest.raises(NotImplementedError, match="handoff.*paged_latent"):
        ContinuousBatchingScheduler(
            eng, eng.params, positions_valid_prompt_inputs,
            positions_valid_step_inputs, handoff=lambda req, payload: None)


# ------------------------------------------------- the models that were there
def serving_fingerprints(build):
    m = FFModel(FFConfig(batch_size=4, only_data_parallel=True))
    build(m)
    return [graph_fingerprint(m)] + [
        graph_fingerprint(clone_for_serving(m, kind, 4)[0])
        for kind in ("prefill", "decode")]


@pytest.mark.parametrize("name, build, want", [
    ("granite", lambda m: build_granite_hybrid(m, GraniteHybridConfig.tiny(),
                                               batch=4),
     ["8b7a580f398078156384a364", "a7f4b2a09b01abb4d69b112c",
      "f384227b8b52f51c125a423d"]),
    ("gpt2", lambda m: build_gpt2(m, GPT2Config.tiny(), batch=4),
     ["ac4194f91a1d6595b2d39edd", "7707646a5c42ff7f8d94f5a3",
      "007d0b7bd9f8c75f1880699d"])], ids=["granite", "gpt2"])
def test_the_other_models_graphs_keep_their_fingerprints(name, build, want):
    """The training graph and both serving clones, as PR 31's tree hashed
    them (strategy_cache.graph_fingerprint: names, op types, params, weight
    specs, wiring): the expert layer's new params and the attention op's
    state declaration enter a graph only where a model sets them, so cached
    strategies and compiled programs of the models that were there stay
    valid."""
    assert serving_fingerprints(build) == want
    g = DeepseekV3Config.tiny()
    assert serving_fingerprints(
        lambda m: build_deepseek_v3(m, g, batch=4))[0] not in want


def test_only_what_is_set_enters_an_expert_layers_params():
    m = FFModel(ffconfig(2))
    x = m.create_tensor([2, 4, 16], name="x")
    m.moe_layer(x, 8, 2, 8, name="plain")
    m.moe_layer(x, 8, 2, 8, scoring="sigmoid", n_group=4, topk_group=2,
                norm_topk_prob=True, routed_scaling_factor=2.5,
                score_bias=True, name="v3")
    plain, v3 = m.layers[-2], m.layers[-1]
    assert set(plain.params) == {"num_experts", "top_k", "expert_width",
                                 "experts_held"}
    assert set(plain.weight_specs) == {"router", "w_in", "w_out"}
    assert set(v3.params) - set(plain.params) == {
        "scoring", "n_group", "topk_group", "norm_topk_prob",
        "routed_scaling_factor", "score_bias"}
    assert v3.weight_specs["score_bias"].dtype == DataType.FLOAT
    with pytest.raises(ValueError, match="groups"):
        m.moe_layer(x, 8, 2, 8, n_group=3, topk_group=1)


def test_flop_and_byte_functions_against_the_program():
    """benchmarks/harness/flops_deepseek_v3.py counts what the program's own
    configuration counts, and its parameters are the ones the program
    initialises; the issue's arithmetic at the published widths."""
    from harness import flops_deepseek_v3 as flops
    from harness import manifest as mf

    for name in ("GigaChat3.1-702B-A36B", "deepseek-v3-tiny"):
        cfg = mf.read_named("configs", name)
        g = family.program_config(cfg)
        assert flops.train_flops_per_token(cfg, g.seq) == g.flops_per_token()
        assert flops.param_count(cfg) == g.param_count()
    giga = mf.read_named("configs", "GigaChat3.1-702B-A36B")
    assert round(flops.attention_params(giga) / 1e6, 2) == 132.58
    assert round(flops.expert_params(giga) / 1e6, 2) == 44.04
    assert round(flops.param_count(giga) / 1e6) == 5174
    assert round(2 * flops.param_count(giga) / 1e9, 2) == 10.35
    assert flops.cache_bytes_per_token(giga) == 6 * 1152
    tiny = DeepseekV3Config.tiny()
    cm = compiled(tiny)
    held = sum(int(np.prod(w.shape)) for lw in cm.params.values()
               for w in lw.values())
    assert held == tiny.param_count() == flops.param_count(file_config(tiny))
    bias = np.asarray(cm.params["l1_moe"]["score_bias"])
    assert bias.dtype == np.float32 and 0 < np.abs(bias).max() <= 0.02
    chat = mf.read_named("traffic", "serve-chat")
    counters = {"moe_routed_pairs": 16 * 8 * 5, "moe_experts_hit": 5 * 6,
                "latent_cache_bytes": 16 * 300 * 6 * 1280.0}
    step = flops.decode_step_need(giga, {"max_batch_slots": 16}, chat, counters)
    # 1.42 G parameters outside the routed experts, the head's 115 M, 30
    # experts of 44 M, 37 MB of cache
    assert 5.6e9 < step["bytes"] < 6.0e9 and step["flops"] == 0
    wave = flops.prefill_wave_need(giga, {"max_batch_slots": 16}, chat,
                                   {"moe_held_pairs": 5 * 16 * 128 * 0.5})
    # 16384 positions x 1.42 G multiplied parameters x 2, 2.6 TFLOP of
    # attention under the diagonal: the issue's "about 49 TFLOP"
    assert 4.8e13 < wave["flops"] < 5.1e13 and wave["bytes"] == 0
