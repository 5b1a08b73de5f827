"""Bailing hybrid decoders (Hugging Face model_type `bailing_hybrid`;
Ling-3.0-flash is one) as their config.json describes them: the plain
reference the system is held to.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"): no kernel, no cache, no chunk, no
absorbed product. The linear-attention layers run as the token-by-token
RECURRENCE (a `lax.scan` over time), the latent-attention layers over the
whole sequence with K and V decompressed from the latent. Written from the
description (ISSUE 41, "The layers"), not from flexflow_tpu/models/ or
flexflow_tpu/ops/; the latent attention, the router and the experts are
this configuration's own copy of harness/reference_deepseek_v3.py's. With
RMS(x; w) = x / sqrt(mean(x^2) + eps) * w, H heads, D the linear-attention
heads' width, r the K/V rank, dn / dr the widths of a latent-attention
head's position-free and rotary parts, dv its value width:

    h_0 = E[ids]
    for l in layers:
        h = h + Mixer(RMS(h; w_in))             KDA, or Attn on every
        x = RMS(h; w_post)                      layer_group_size-th layer
        h = h + (MLP(x) if the layer is dense else MoE(x) + Shared(x))
    logits = RMS(h_L; w_f) W_head

    KDA(x):    [q' | k' | v' | f | z | b] = x W_in     3 x H D | H D | H D | H
               [q' | k' | v'] = silu(conv(.)): causal, depth-wise, width d_conv,
               out[t] = sum_j w[j] in[t - d_conv + 1 + j], zeros before the start
               q = q' / sqrt(|q'|^2 + 1e-6) / sqrt(D);  k = k' / sqrt(|k'|^2 + 1e-6)   a head
               g = lower_bound sigmoid(exp(A_log_h) (f + dt_bias))    a channel
               beta = sigmoid(b)                                      a head
               S' = diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
               o_t = S_t^T q_t                                        S_0 = 0, [D, D] a head
               out = (RMS(o_t; w_norm) sigmoid(z)) W_out              the norm over a head's D
    Attn(x):   [q_n | q_r] = x W_q                       per head dn | dr; no query latent
               [c | k_r] = x W_kva;  c_kv = RMS(c; w_kv)  k_r: ONE vector a token
               q_r, k_r <- RoPE(., position)              pairs (2i, 2i+1), f_i = base^(-2i/dr)
               [k_n | v] = c_kv W_kvb                     per head dn | dv
               s = softmax((dn + dr)^-1/2 (q_n k_n^T + q_r k_r^T) + causal mask)
               out = concat_heads((s v)_j sigmoid(x w_gate)_j) W_o
    MLP(x):    [a | b] = x W_in; (silu(a) * b) W_out      also Shared(x)
    MoE(x):    s = sigmoid(x W_r);  c = s + bias          bias: selection only
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
- the multi-token-prediction module is not built, and no SwiGLU clamp (the
  published lists read 0 on every layer a configuration here keeps);
- the rotary pairs (2i, 2i+1) turn where they lie (`rope_interleave`).

It is applied ONE LAYER AT A TIME (`layer_step`: two jitted functions a
layer, looped in Python by `forward`), the mixers one row of the batch at a
time and the experts one at a time, each weight cast to float32 as it is
reached, so that a model whose weights fill most of a chip in bf16 can
still be checked on that chip.

Parameters: {"embed" [vocab, d], "norm_f" [d], "head" [d, vocab], "layers":
[{"norm_in", "norm_post"; a KDA layer "in_proj" [d, 5 H D + H], "conv_w"
[d_conv, 3 H D], "A_log" [H], "dt_bias" [H D], "gate_norm" [D], "out_proj"
[H D, d]; a latent layer "wq" [d, H (dn + dr)], "wkv_a" [d, r + dr],
"kv_norm", "wkv_b" [r, H (dn + dv)], "wo" [H dv, d], "w_gate" [d, H]; a
dense layer "mlp_in" [d, 2w], "mlp_out" [w, d]; an expert layer "router"
[d, E], "score_bias" [E], "w_in" [held, d, 2w], "w_out" [held, w, d],
"shared_in", "shared_out"}]}; a layer's kinds are read from which of these
it holds; matrices are [in, out]. `hp` (hyper-parameters): {"heads",
"head_dim", "d_conv", "lower_bound", "dn", "dr", "dv", "rank", "top_k",
"n_group", "topk_group", "norm_topk_prob", "routed_scaling_factor", "held":
(lo, hi), "eps", "rope_theta"}; a control may add "state_dtype" (`delta_rule`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def inv_freq(hp) -> np.ndarray:
    """[dr / 2] float64: f_i = base^(-2i/dr), no scaling."""
    dr = hp["dr"]
    return hp["rope_theta"] ** (-2.0 * np.arange(dr // 2, dtype=np.float64) / dr)


def attention_scale(hp) -> float:
    return (hp["dn"] + hp["dr"]) ** -0.5


def rope(x, positions, hp):
    """x [.., seq, .., dr] with `positions` broadcastable to x's shape less
    the last axis: the pairs (2i, 2i+1) turned by position x inv_freq_i."""
    angles = positions[..., None] * jnp.asarray(inv_freq(hp), jnp.float32)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x.reshape(x.shape[:-1] + (hp["dr"] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def attention(x, positions, w, hp):
    """One sequence: x [s, d], positions [s]."""
    s, _d = x.shape
    heads, dn, dr, dv, r = hp["heads"], hp["dn"], hp["dr"], hp["dv"], hp["rank"]
    q = (x @ _f32(w["wq"])).reshape(s, heads, dn + dr)
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
    gate = jax.nn.sigmoid(x @ _f32(w["w_gate"]))             # [s, H]
    return (out * gate[..., None]).reshape(s, heads * dv) @ _f32(w["wo"])


def kda_inputs(x, w, hp):
    """One sequence x [s, d] -> (q, k, v, g [s, H, D], beta [s, H], z [s, H
    D]): everything the recurrence and the output gate take."""
    s, _d = x.shape
    heads, hd, width = hp["heads"], hp["head_dim"], hp["d_conv"]
    inner = heads * hd
    proj = x @ _f32(w["in_proj"])
    conv_w = _f32(w["conv_w"])
    padded = jnp.concatenate([jnp.zeros((width - 1, 3 * inner)),
                              proj[:, :3 * inner]], axis=0)
    mixed = silu(sum(padded[j:j + s] * conv_w[j] for j in range(width)))
    q, k, v = (mixed[:, i * inner:(i + 1) * inner].reshape(s, heads, hd)
               for i in range(3))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) / hd ** 0.5
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    rate = jnp.exp(_f32(w["A_log"]))[:, None]                # [H, 1]
    g = hp["lower_bound"] * jax.nn.sigmoid(
        rate * (proj[:, 3 * inner:4 * inner] + _f32(w["dt_bias"])
                ).reshape(s, heads, hd))
    beta = jax.nn.sigmoid(proj[:, 5 * inner:])
    return q, k, v, g, beta, proj[:, 4 * inner:5 * inner]


def delta_rule(q, k, v, g, beta, state=None, state_dtype=None):
    """The recurrence, a token at a time: (o [s, H, D], the last state [H,
    D, D]). S' = diag(e^{g_t}) S; S = S' + beta_t k_t (v_t - S'^T k_t)^T;
    o_t = S^T q_t. `state_dtype` (a control, never the reference itself):
    "bfloat16" rounds the state to 8 exponent and 7 mantissa bits after
    every token (reduce_precision: a cast there and back is a pair XLA may
    drop, and on the chip it did)."""
    _s, heads, hd = q.shape

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[:, :, None]
        rest = v_t - jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + (b_t[:, None] * k_t)[:, :, None] * rest[:, None, :]
        if state_dtype == "bfloat16":
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    if state is None:
        state = jnp.zeros((heads, hd, hd), jnp.float32)
    state, out = jax.lax.scan(step, state, (q, k, v, g, beta))
    return out, state


def kda(x, w, hp):
    """One sequence: x [s, d]."""
    s, _d = x.shape
    q, k, v, g, beta, z = kda_inputs(x, w, hp)
    out, _state = delta_rule(q, k, v, g, beta,
                             state_dtype=hp.get("state_dtype"))
    out = rms(out, _f32(w["gate_norm"]), hp["eps"]) \
        * jax.nn.sigmoid(z).reshape(out.shape)
    return out.reshape(s, -1) @ _f32(w["out_proj"])


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
def _mixer_step(h, positions, w, hp_key):
    hp = dict(hp_key)
    with jax.default_matmul_precision("highest"):
        x = rms(h, _f32(w["norm_in"]), hp["eps"])
        if "in_proj" in w:
            return h + jax.lax.map(lambda row: kda(row, w, hp), x)
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


MIXER_KEYS = ("norm_in", "in_proj", "conv_w", "A_log", "dt_bias", "gate_norm",
           "out_proj", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "w_gate")


def layer_step(h, positions, layer, hp, choices: bool = False,
               scores: bool = False):
    """One decoder layer on h [batch, seq, d] float32; `layer` holds that
    layer's weights in whatever type they are kept. `choices`: also the
    experts each token was routed to, [batch, seq, k] (None for a dense
    layer), for a measurement of how often a lower precision routes
    otherwise; `scores`: and the selection scores they were chosen by,
    [batch, seq, E]."""
    key = _hp_key(hp)
    mixer = {k: layer[k] for k in MIXER_KEYS if k in layer}
    ff = {k: v for k, v in layer.items() if k not in mixer}
    h = _mixer_step(h, positions, mixer, key)
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

