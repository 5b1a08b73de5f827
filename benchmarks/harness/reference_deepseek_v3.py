"""DeepSeek-V3 decoders (Hugging Face `DeepseekV3ForCausalLM`, model_type
`deepseek_v3`; GigaChat3.1-702B-A36B is one) as their config.json describes
them: the plain reference the system is held to.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"): no kernel, no cache, no absorbed
product, attention over the whole sequence with K and V decompressed from
the latent. Written from the description (ISSUE 32, "The layer equations"),
not from flexflow_tpu/models/deepseek_v3.py or flexflow_tpu/ops/. With
RMS(x; w) = x / sqrt(mean(x^2) + eps) * w, H heads, r the K/V rank, dn / dr
the widths of a head's position-free and rotary parts, dv its value width:

    h_0 = E[ids]
    for l in layers:
        h = h + Attn(RMS(h; w_in), positions)
        x = RMS(h; w_post)
        h = h + (MLP(x) if the layer is dense else MoE(x) + Shared(x))
    logits = RMS(h_L; w_f) W_head

    Attn(x):   c_q = RMS(x W_qa; w_q);  [q_n | q_r] = c_q W_qb   per head dn | dr
               [c | k_r] = x W_kva;  c_kv = RMS(c; w_kv)         k_r: ONE vector a token
               q_r, k_r <- RoPE(., position)
               [k_n | v] = c_kv W_kvb                            per head dn | dv
               s = softmax(scale (q_n k_n^T + q_r k_r^T) + causal mask)
               out = concat_heads(s v) W_o
               scale = (dn + dr)^-1/2 m^2, m = 0.1 mscale_all_dim ln(factor) + 1
    RoPE:      pairs (2i, 2i+1) of the dr-wide slice turn by position x inv_freq_i:
               f_i = base^(-2i/dr); cd(n) = dr ln(L0 / (2 pi n)) / (2 ln base);
               low = max(floor(cd(beta_fast)), 0); high = min(ceil(cd(beta_slow)), dr - 1)
               ramp_i = clip((i - low) / (high - low), 0, 1)
               inv_freq_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i
               cos, sin times yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    MLP(x):    [a | b] = x W_in; (silu(a) * b) W_out              also Shared(x)
    MoE(x):    s = sigmoid(x W_r);  c = s + bias                  bias: selection only
               a group (E / n_group consecutive experts) scores the sum of its
               two largest c; the topk_group best groups stay; the k largest c
               among their experts are chosen;
               g_i = routed_scaling_factor s_i / (sum over the k of s + 1e-20)
               MoE = sum_i g_i Expert_i(x) over the chosen experts that are HELD;
               the others add nothing

Departures from the published model, the system's and so mirrored here:
- `held` and the vocabulary are arguments (through the weights' shapes and
  `held`): the reference is given the same share of a stated deployment as
  the program, and returns that holder's part of the result;
- the multi-token-prediction module is not built (the logits do not depend
  on it);
- the published code de-interleaves the rotary slice and rotates halves;
  here the pairs (2i, 2i+1) turn where they lie. q_r . k_r is the same.

It is applied ONE LAYER AT A TIME (`layer_step`: two jitted functions a
layer, looped in Python by `forward`), attention one row of the batch at a
time and the experts one at a time, each weight cast to float32 as it is
reached, so that a model whose weights fill most of a chip in bf16 can
still be checked on that chip.

Parameters: {"embed" [vocab, d], "norm_f" [d], "head" [d, vocab], "layers":
[{"norm_in", "norm_post", "wq_a" [d, r_q], "q_norm", "wq_b" [r_q, H (dn +
dr)], "wkv_a" [d, r + dr], "kv_norm", "wkv_b" [r, H (dn + dv)], "wo" [H dv,
d], and for a dense layer "mlp_in" [d, 2w], "mlp_out" [w, d]; for an expert
layer "router" [d, E], "score_bias" [E], "w_in" [held, d, 2w], "w_out"
[held, w, d], "shared_in", "shared_out"}]}; a layer's kind is read from
which of these it holds; matrices are [in, out]. `hp` (hyper-parameters):
{"heads", "dn", "dr", "dv", "rank", "top_k", "n_group", "topk_group",
"norm_topk_prob", "routed_scaling_factor", "held": (lo, hi), "eps",
"rope_theta", "rope_factor", "rope_original_len", "beta_fast", "beta_slow",
"mscale", "mscale_all_dim"}.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(hp) -> np.ndarray:
    """[dr / 2] float64, by the closed form above."""
    dr, base, factor = hp["dr"], hp["rope_theta"], hp["rope_factor"]
    i = np.arange(dr // 2, dtype=np.float64)
    f = base ** (-2.0 * i / dr)
    if factor <= 1:
        return f

    def cd(n):
        return dr * math.log(hp["rope_original_len"] / (2 * math.pi * n)) \
            / (2 * math.log(base))

    low = max(math.floor(cd(hp["beta_fast"])), 0)
    high = min(math.ceil(cd(hp["beta_slow"])), dr - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return f * (1.0 - ramp) + f / factor * ramp


def attention_scale(hp) -> float:
    m = yarn_mscale(hp["rope_factor"], hp["mscale_all_dim"])
    return (hp["dn"] + hp["dr"]) ** -0.5 * m * m


def rope(x, positions, hp):
    """x [.., seq, .., dr] with `positions` broadcastable to x's shape less
    the last axis: the pairs (2i, 2i+1) turned by position x inv_freq_i."""
    angles = positions[..., None] * jnp.asarray(inv_freq(hp), jnp.float32)
    m = yarn_mscale(hp["rope_factor"], hp["mscale"]) \
        / yarn_mscale(hp["rope_factor"], hp["mscale_all_dim"])
    cos, sin = jnp.cos(angles) * m, jnp.sin(angles) * m
    pairs = x.reshape(x.shape[:-1] + (hp["dr"] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def attention(x, positions, w, hp):
    """One sequence: x [s, d], positions [s]."""
    s, _d = x.shape
    heads, dn, dr, dv, r = hp["heads"], hp["dn"], hp["dr"], hp["dv"], hp["rank"]
    c_q = rms(x @ _f32(w["wq_a"]), _f32(w["q_norm"]), hp["eps"])
    q = (c_q @ _f32(w["wq_b"])).reshape(s, heads, dn + dr)
    ckr = x @ _f32(w["wkv_a"])
    c_kv = rms(ckr[:, :r], _f32(w["kv_norm"]), hp["eps"])
    pos = positions.astype(jnp.float32)
    k_r = rope(ckr[:, r:], pos, hp)                          # [s, dr]
    q_r = rope(q[..., dn:], pos[:, None], hp)                # [s, H, dr]
    kv = (c_kv @ _f32(w["wkv_b"])).reshape(s, heads, dn + dv)
    scores = jnp.einsum("qhd,khd->hqk", q[..., :dn], kv[..., :dn]) \
        + jnp.einsum("qhd,kd->hqk", q_r, k_r)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)),
                       scores * attention_scale(hp), -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                     kv[..., dn:])
    return out.reshape(s, heads * dv) @ _f32(w["wo"])


def selection_scores(x, w):
    """(s, c) [.., E]: an expert's score sigmoid(x W_r), and the score it is
    SELECTED by, s + bias."""
    s = jax.nn.sigmoid(x @ _f32(w["router"]))
    return s, s + _f32(w["score_bias"])


def chosen(c, hp):
    """The experts [.., k] that selection scores c [.., E] choose: a group
    scores the sum of its two largest c, the topk_group best groups stay,
    the k largest c among their experts are chosen."""
    n, groups = c.shape[-1], hp["n_group"]
    grouped = c.reshape(c.shape[:-1] + (groups, n // groups))
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    kept = jax.lax.top_k(group_score, hp["topk_group"])[1]   # [.., topk_group]
    stays = jnp.any(kept[..., None] == jnp.arange(groups), axis=-2)
    c = jnp.where(jnp.repeat(stays, n // groups, axis=-1), c, -jnp.inf)
    return jax.lax.top_k(c, hp["top_k"])[1]


def route(x, w, hp):
    """(gates [.., k] of the chosen, experts [.., k]) over ALL experts."""
    s, c = selection_scores(x, w)
    experts = chosen(c, hp)
    g = jnp.take_along_axis(s, experts, axis=-1)
    if hp["norm_topk_prob"]:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return g * hp["routed_scaling_factor"], experts


def gated_mlp(x, w_in, w_out):
    ab = x @ w_in
    half = ab.shape[-1] // 2
    return (silu(ab[..., :half]) * ab[..., half:]) @ w_out


def moe(x, w, hp):
    """This holder's part of the routed layer: a loop over the held experts,
    each applied to every token and masked by its gate."""
    lo, hi = hp["held"]
    gates, experts = route(x, w, hp)

    def one(e, acc):
        gate = jnp.sum(jnp.where(experts == lo + e, gates, 0.0), axis=-1)
        return acc + gate[..., None] * gated_mlp(x, _f32(w["w_in"][e]),
                                                 _f32(w["w_out"][e]))

    return jax.lax.fori_loop(0, hi - lo, one, jnp.zeros_like(x))


def _hp_key(hp):
    return tuple(sorted(hp.items()))


@functools.partial(jax.jit, static_argnums=(3,))
def _attention_step(h, positions, w, hp_key):
    hp = dict(hp_key)
    with jax.default_matmul_precision("highest"):
        x = rms(h, _f32(w["norm_in"]), hp["eps"])
        return h + jax.lax.map(lambda row: attention(row[0], row[1], w, hp),
                               (x, positions))


@functools.partial(jax.jit, static_argnums=(2,))
def _feed_forward_step(h, w, hp_key):
    hp = dict(hp_key)
    with jax.default_matmul_precision("highest"):
        x = rms(h, _f32(w["norm_post"]), hp["eps"])
        if "mlp_in" in w:
            return (h + gated_mlp(x, _f32(w["mlp_in"]), _f32(w["mlp_out"])),
                    None, None)
        shared = gated_mlp(x, _f32(w["shared_in"]), _f32(w["shared_out"]))
        return (h + moe(x, w, hp) + shared, route(x, w, hp)[1],
                selection_scores(x, w)[1])


def layer_step(h, positions, layer, hp, choices: bool = False,
               scores: bool = False):
    """One decoder layer on h [batch, seq, d] float32; `layer` holds that
    layer's weights in whatever type they are kept. `choices`: also the
    experts each token was routed to, [batch, seq, k] (None for a dense
    layer), for a measurement of how often a lower precision routes
    otherwise; `scores`: and the selection scores they were chosen by,
    [batch, seq, E]."""
    key = _hp_key(hp)
    attn = {k: layer[k] for k in ("norm_in", "wq_a", "q_norm", "wq_b",
                                  "wkv_a", "kv_norm", "wkv_b", "wo")}
    ff = {k: v for k, v in layer.items() if k not in attn}
    h = _attention_step(h, positions, attn, key)
    h, experts, c = _feed_forward_step(h, ff, key)
    if scores:
        return h, experts, c
    return (h, experts) if choices else h


@jax.jit
def _embed(embed, ids):
    return _f32(embed)[ids]


@functools.partial(jax.jit, static_argnums=(3,))
def _head(h, norm_f, head, eps):
    with jax.default_matmul_precision("highest"):
        return rms(h, _f32(norm_f), eps) @ _f32(head)


def hidden(params, ids, positions, hp):
    """The hidden state after every layer, before the final norm."""
    h = _embed(params["embed"], ids)
    for layer in params["layers"]:
        h = layer_step(h, positions, layer, hp)
    return h


def forward(params, ids, positions, hp):
    """Logits [batch, seq, vocab] in float32."""
    return _head(hidden(params, ids, positions, hp), params["norm_f"],
                 params["head"], hp["eps"])


def next_token_loss(params, ids, positions, labels, hp):
    """Mean cross-entropy of labels[b, t] under logits[b, t]."""
    logp = jax.nn.log_softmax(forward(params, ids, positions, hp), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def token_gaps(params, ids, positions, hp, scores: bool = False):
    """For every position t < seq - 1: how far the logit of the token that
    FOLLOWS in `ids` lies under the largest logit, and the row's scale.
    Returns (gap [b, seq-1], scale [b, seq-1]); gap 0 means the following
    token is the reference argmax. `scores`: also each expert layer's
    selection scores [b, seq, E], a list."""
    h = _embed(params["embed"], ids)
    selected = []
    for layer in params["layers"]:
        h, _experts, c = layer_step(h, positions, layer, hp, scores=True)
        if c is not None:
            selected.append(c)
    logits = _head(h, params["norm_f"], params["head"], hp["eps"])[:, :-1]
    got = jnp.take_along_axis(logits, ids[:, 1:, None], axis=-1)[..., 0]
    out = (logits.max(axis=-1) - got, jnp.abs(logits).max(axis=-1))
    return out + (selected,) if scores else out
