"""Mellum decoders (JetBrains/Mellum2-12B-A2.5B-Instruct's config.json,
model_type `mellum`: a Qwen3-MoE text stack whose attention layers are
windowed or full as `layer_types` says, the full ones with YaRN) as the
config's keys and Hugging Face's rules for them describe it: the plain
reference the system is held to.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"): no kernel, no cache, no chunks, no
ring, no batching. Written from the description, not from
flexflow_tpu/models/mellum.py or flexflow_tpu/ops/ (it imports nothing of
flexflow_tpu). With RMS(x; w) = x / sqrt(mean(x^2) + eps) * w, h_t the hidden
state after a layer's first norm and p_t its position:

    1. q = W_q h [heads, hd], k = W_k h, v = W_v h [kv_heads, hd], no bias;
       q, k = RMS over each head's hd values, one weight each.
       ASSUMED: the config has no key for the head norms; they are the
       family's convention (Qwen3), whose keys the config carries.
    2. rotary, rotate-half (pairs (i, i + hd / 2)), angle p * f_i, f_i =
       theta^(-2i/hd) on a `sliding_attention` layer. On a `full_attention`
       layer YaRN, by Hugging Face's rule from `rope_parameters.
       full_attention`: with c(n) = hd ln(L0 / (2 pi n)) / (2 ln theta) the
       real pair index that turns n times over the original L0 positions,
       low = max(floor(c(beta_fast)), 0), high = min(ceil(c(beta_slow)), hd -
       1), ramp_i = clip((i - low) / (high - low), 0, 1): f_i <- f_i (1 -
       ramp_i) + (f_i / factor) ramp_i; and cos and sin are multiplied with
       `attention_factor` (0.1 ln(factor) + 1 where the config gives none),
       so the layer's scores carry its square.
    3. o[t, j] = sum_s softmax_s(q[t, j] . k[s, g(j)] / sqrt(hd)) v[s, g(j)]
       over the keys s the layer's kind lets query t see, g(j) = j // (heads
       / kv_heads): `sliding_attention`: t - window < s <= t (`window` keys,
       itself among them); `full_attention`: every s <= t. x += W_o o.
    4. second RMS; softmax(W_r h) over ALL experts, the top k renormalised
       to sum 1; experts W_2 (silu(W_1 h) * W_3 h); x += their weighted sum.
    After the last layer an RMS and an untied head.

Departures from the published model, the system's and so mirrored here:
- `held` is an argument (with the weights' shapes): the reference returns
  that holder's part of the expert layers; the benchmark's configuration
  holds every expert, so there it is the whole layer;
- the expert layer is a LOOP over the held experts, each applied to every
  token and masked by that token's gate for it (0 where it was not chosen):
  dropless;
- the multi-token-prediction head the model card names is not here: the
  config has no key of it and the logits do not depend on it.

Switches for the logits check's WRONG references (`hp`, all absent in the
sound one): "window" (another window than the configuration's), "yarn_on" False
(plain frequencies and no attention factor on the full layers too).

It is applied ONE LAYER AT A TIME (`layer_step`, jitted functions a layer
part, looped in Python by `hidden`), a row of the batch at a time, attention
a block of QUERY_BLOCK queries at a time (a block of a windowed layer slices
the keys it cannot see away: the mask is the same): a layer's weights are
cast to float32 as the layer is reached, a routed expert's as the loop
reaches it, and the head a block of the vocabulary at a time, so that a model
whose weights fill most of a chip in bf16 can still be checked on that chip
at sequences of thousands of tokens.

Parameters: {"embed" [vocab, d], "norm_f" [d], "head" [d, vocab], "layers":
[{"norm_op" [d], "norm_ffn" [d], "wq" [d, heads hd], "wk", "wv" [d, kv hd],
"wo" [heads hd, d], "q_norm", "k_norm" [hd], "router" [d, E], "w_in" [held,
d, 2 w], "w_out" [held, w, d]}]}; matrices are [in, out]. `hp`: {"heads",
"kv_heads", "head_dim", "rope_theta", "layer_types", "window", "yarn":
(factor, original positions, beta_fast, beta_slow, attention_factor or
None), "top_k", "held": (lo, hi), "eps"}. Positions are [batch, seq].
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# the head is applied to this many columns of the vocabulary at a time
VOCAB_BLOCK = 16384
# queries whose scores of every key exist at once (at 16 896 keys and 32
# heads a block's float32 scores are 277 MB, and a layer step's temporaries
# under 1 GB: what is left beside an engine that fills most of 16 GB)
QUERY_BLOCK = 128


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), tree)


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x / (1.0 + jnp.exp(-x))


# --------------------------------------------------------------------- rotary
def correction_indices(hd: int, theta: float, original: int,
                       beta_fast: float, beta_slow: float):
    """(low, high) of step 2: the pairs between which YaRN's ramp runs."""
    def c(n):
        return hd * math.log(original / (2 * math.pi * n)) \
            / (2 * math.log(theta))

    return max(math.floor(c(beta_fast)), 0), min(math.ceil(c(beta_slow)),
                                                 hd - 1)


def frequencies(hd: int, theta: float, yarn=None) -> np.ndarray:
    """f_i of the hd / 2 pairs, float64: plain, or YaRN's (step 2)."""
    i = np.arange(hd // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / hd)
    if yarn is None:
        return f
    factor, original, beta_fast, beta_slow = yarn[:4]
    low, high = correction_indices(hd, theta, original, beta_fast, beta_slow)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + f / factor * ramp


def attention_factor(yarn) -> float:
    if yarn is None:
        return 1.0
    return float(yarn[4]) if yarn[4] is not None \
        else 0.1 * math.log(yarn[0]) + 1.0


def rotate(x, positions, theta, yarn=None):
    """x [s, heads, hd], positions [s]: the pairs (i, i + hd / 2) turned by
    position * f_i; under YaRN cos and sin times the attention factor."""
    hd = x.shape[-1]
    f = jnp.asarray(frequencies(hd, theta, yarn), jnp.float32)
    angle = (positions.astype(jnp.float32)[:, None] * f)[:, None, :]
    m = attention_factor(yarn)
    cos, sin = jnp.cos(angle) * m, jnp.sin(angle) * m
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# ------------------------------------------------------------------ attention
def attention(x, positions, w, hp, kind: str):
    """x [s, d], positions [s] -> [s, d], one sequence (steps 1-3)."""
    s = x.shape[0]
    heads, kv, hd = hp["heads"], hp["kv_heads"], hp["head_dim"]
    window = hp["window"] if kind == "sliding_attention" else 0
    yarn = hp.get("yarn") if kind == "full_attention" \
        and hp.get("yarn_on", True) else None
    q = rms((x @ w["wq"]).reshape(s, heads, hd), w["q_norm"], hp["eps"])
    k = rms((x @ w["wk"]).reshape(s, kv, hd), w["k_norm"], hp["eps"])
    v = (x @ w["wv"]).reshape(s, kv, hd)
    q = rotate(q, positions, hp["rope_theta"], yarn)
    k = rotate(k, positions, hp["rope_theta"], yarn)
    # query head j reads K/V head g(j) = j // (heads / kv): q as [s, kv
    # groups, heads a group, hd] against its group's k and v
    q = q.reshape(s, kv, heads // kv, hd)

    def block(qb, tq, kb, vb, sk):
        """Queries at places tq [q] of the sequence against keys at places
        sk [n] (negative: a filler row, seen by nobody)."""
        see = (sk[None, :] <= tq[:, None]) & (sk[None, :] >= 0)
        if window:
            see &= sk[None, :] > tq[:, None] - window
        scores = jnp.einsum("qgrd,kgd->grqk", qb, kb) / jnp.sqrt(float(hd))
        scores = jnp.where(see[None, None], scores, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, axis=-1),
                          vb)

    at = jnp.arange(s)
    if s <= QUERY_BLOCK:
        out = block(q, at, k, v, at)
    else:
        n = -(-s // QUERY_BLOCK)
        fill = n * QUERY_BLOCK - s
        qs = jnp.pad(q, [(0, fill), (0, 0), (0, 0), (0, 0)]).reshape(
            n, QUERY_BLOCK, kv, heads // kv, hd)
        ats = jnp.pad(at, [(0, fill)], constant_values=s - 1).reshape(
            n, QUERY_BLOCK)
        if window and s > window + QUERY_BLOCK:
            # a block sees no key before its first query's window: slice
            span = window + QUERY_BLOCK
            kp = jnp.pad(k, [(window, fill), (0, 0), (0, 0)])
            vp = jnp.pad(v, [(window, fill), (0, 0), (0, 0)])

            def one(args):
                i, qb, tq = args
                lo = i * QUERY_BLOCK
                kb = jax.lax.dynamic_slice_in_dim(kp, lo, span)
                vb = jax.lax.dynamic_slice_in_dim(vp, lo, span)
                return block(qb, tq, kb, vb, lo - window + jnp.arange(span))

            out = jax.lax.map(one, (jnp.arange(n), qs, ats))
        else:
            out = jax.lax.map(lambda a: block(a[0], a[1], k, v, at),
                              (qs, ats))
        out = out.reshape(n * QUERY_BLOCK, heads * hd)[:s]
    return out.reshape(s, heads * hd) @ w["wo"]


# ---------------------------------------------------------------- feed-forward
def gated_mlp(x, w_in, w_out):
    a, b = jnp.split(x @ w_in, 2, axis=-1)
    return (silu(a) * b) @ w_out


def route(x, w, hp):
    """(gates [.., k] of the chosen, experts [.., k]) over ALL experts: the
    softmax over all of them, the top k, renormalised to sum 1."""
    p = jax.nn.softmax(x @ _f32(w["router"]), axis=-1)
    g, experts = jax.lax.top_k(p, hp["top_k"])
    return g / jnp.sum(g, axis=-1, keepdims=True), experts


def moe(x, w, hp):
    """This holder's part of the routed layer: a loop over the held experts,
    each applied to every token and masked by the token's gate for it."""
    lo, hi = hp["held"]
    gates, experts = route(x, w, hp)

    def one(e, acc):
        gate = jnp.sum(jnp.where(experts == lo + e, gates, 0.0), axis=-1)
        return acc + gate[..., None] * gated_mlp(x, _f32(w["w_in"][e]),
                                                 _f32(w["w_out"][e]))

    return jax.lax.fori_loop(0, hi - lo, one, jnp.zeros_like(x))


# --------------------------------------------------------------------- layers
def _hp_key(hp):
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in hp.items()))


FEED_FORWARD_KEYS = ("norm_ffn", "router", "w_in", "w_out")


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _attention_step(h, positions, w, hp_key, kind, residual=True):
    hp = dict(hp_key)
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        x = rms(h, w["norm_op"], hp["eps"])
        y = jax.lax.map(lambda row: attention(row[0], row[1], w, hp, kind),
                        (x, positions))
    return h + y if residual else y


@functools.partial(jax.jit, static_argnums=(2,))
def _feed_forward_step(h, w, hp_key):
    hp = dict(hp_key)
    with jax.default_matmul_precision("highest"):
        x = rms(h, _f32(w["norm_ffn"]), hp["eps"])
        # a row at a time; its experts are cast as the loop reaches them
        return h + jax.lax.map(lambda row: moe(row, w, hp), x)


def layer_step(h, positions, layer, hp, kind: str):
    """One decoder layer of `kind` on h [batch, seq, d] float32; `layer`
    holds that layer's weights in whatever type they are kept."""
    key = _hp_key(hp)
    ff = {k: v for k, v in layer.items() if k in FEED_FORWARD_KEYS}
    op = {k: v for k, v in layer.items() if k not in ff}
    return _feed_forward_step(_attention_step(h, positions, op, key, kind),
                              ff, key)


def attention_output(params, ids, positions, hp, layer: int = 0):
    """[batch, seq, d]: what layer `layer`'s attention adds to the residual
    stream (W_o o), before it is added: what the logits check compares at
    its own scale."""
    kinds = hp["layer_types"]
    h = _embed(params["embed"], ids)
    for l, kind in zip(params["layers"][:layer], kinds):
        h = layer_step(h, positions, l, hp, kind)
    op = {k: v for k, v in params["layers"][layer].items()
          if k not in FEED_FORWARD_KEYS}
    return _attention_step(h, positions, op, _hp_key(hp), kinds[layer], False)


@jax.jit
def _embed(embed, ids):
    return _f32(embed[ids])


def hidden(params, ids, positions, hp):
    """The hidden state after every layer, before the final norm."""
    h = _embed(params["embed"], ids)
    for layer, kind in zip(params["layers"], hp["layer_types"]):
        h = layer_step(h, positions, layer, hp, kind)
    return h


@functools.partial(jax.jit, static_argnums=(3,))
def _head(h, norm_f, head, eps):
    with jax.default_matmul_precision("highest"):
        return rms(h, _f32(norm_f), eps) @ _f32(head)


def forward(params, ids, positions, hp):
    """Logits [batch, seq, vocab] in float32 (whole: for sizes that fit)."""
    return _head(hidden(params, ids, positions, hp), params["norm_f"],
                 params["head"], hp["eps"])


def next_token_loss(params, ids, positions, labels, hp):
    """Mean cross-entropy of labels[b, t] under logits[b, t]."""
    logp = jax.nn.log_softmax(forward(params, ids, positions, hp), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


@functools.partial(jax.jit, static_argnums=(4,))
def _head_gaps(h, norm_f, head, follows, eps):
    """(largest logit - the logit of `follows`, largest |logit|) of every
    row of h [rows, seq, d], the head a block of the vocabulary at a time:
    `[seq, VOCAB_BLOCK]` logits exist at once, never `[rows, seq, vocab]`."""
    vocab = head.shape[1]
    edges = list(range(0, vocab, VOCAB_BLOCK)) + [vocab]

    def one(row):
        x, nxt = row
        with jax.default_matmul_precision("highest"):
            x = rms(x, _f32(norm_f), eps)
            top = jnp.full(x.shape[:1], -jnp.inf)
            scale = jnp.zeros(x.shape[:1])
            got = jnp.zeros(x.shape[:1])
            for lo, hi in zip(edges[:-1], edges[1:]):
                block = x @ _f32(head[:, lo:hi])
                top = jnp.maximum(top, block.max(axis=-1))
                scale = jnp.maximum(scale, jnp.abs(block).max(axis=-1))
                inside = (nxt >= lo) & (nxt < hi)
                here = jnp.take_along_axis(
                    block, jnp.clip(nxt - lo, 0, hi - lo - 1)[:, None],
                    axis=-1)[:, 0]
                got = jnp.where(inside, here, got)
        return top - got, scale

    return jax.lax.map(one, (h, follows))


def token_gaps(params, ids, positions, hp):
    """For every position t < seq - 1: how far the logit of the token that
    FOLLOWS in `ids` lies under the largest logit, and the row's scale.
    Returns (gap [b, seq-1], scale [b, seq-1]); gap 0 means the following
    token is the reference argmax."""
    h = hidden(params, ids, positions, hp)
    return _head_gaps(h[:, :-1], params["norm_f"], params["head"],
                      jnp.asarray(ids)[:, 1:], hp["eps"])
