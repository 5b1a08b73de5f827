"""LFM2-MoE (Hugging Face `Lfm2MoeForCausalLM`, model_type `lfm2_moe`;
LFM2-24B-A2B is one) as its config.json and the family's published block
describe it: the plain reference the system is held to.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"): no kernel, no cache, no state, no
batching, no sort, no ladder. Written from the description, not from
flexflow_tpu/models/lfm2_moe.py or flexflow_tpu/ops/ (it imports nothing of
flexflow_tpu). With d the hidden size and RMS(x; w) = x / sqrt(mean(x^2) +
eps) * w:

    h_0 = E[ids]
    for l in layers:
        h = h + Op_l(RMS(h; operator_norm_l), positions)
        x = RMS(h; ffn_norm_l)
        h = h + (MLP_l(x) if the layer is dense else MoE_l(x))
    logits = RMS(h_L; embedding_norm) W_head

    ShortConv(u): [B | C | X] = u W_in           three d-wide parts
                  z_t = B_t * X_t
                  c_t = sum_{j<k} w_j * z_{t-k+1+j}    the whole sequence at
                                     once, as k shifted products; zeros
                                     before the start; no bias, no activation
                  out = (C * c) W_out
    Attn(u, pos): q = RoPE(RMS_hd(u W_q; g_q), pos)   [heads, hd]
                  k = RoPE(RMS_hd(u W_k; g_k), pos)   [kv_heads, hd]
                  v = u W_v; the norm over each head's hd values; rotary
                  over the whole head, rotate-half (pairs (i, i + hd / 2)),
                  angles pos * theta^(-2i / hd); query head j reads K/V head
                  j // (heads / kv_heads); p = softmax(q k^T / sqrt(hd) +
                  causal mask); out = (p v) W_o. Full attention, no cache.
    MLP(x):       (silu(a) * b) W_2 with [a | b] = x [W_1 | W_3]
    MoE(x):       s = sigmoid(x W_r) over ALL experts; the top k of s + bias
                  are chosen (the bias for the SELECTION only); g =
                  s[chosen] / (sum + gate_norm_eps) * routed_scaling_factor;
                  MoE = sum over the choices whose expert is HELD of
                  g_i (silu(a_i) * b_i) W_2i, [a_i | b_i] = x [W_1i | W_3i]

Departures from the published model, the system's and so mirrored here:
- the head is a weight of its own (the family ties it to the embedding);
- `held` is an argument (with the weights' shapes): the reference returns
  that holder's part of the expert layers; the benchmark's configuration
  holds every expert, so there it is the whole layer;
- the expert layer is a LOOP over the held experts, each applied to every
  token and masked by that token's gate for it (0 where it was not chosen),
  not a gather of each token's k experts' matrices: at the published widths
  a gathered `[tokens, k, d, 2 w]` does not exist beside the weights. Every
  token still goes through its k experts and no other contributes.

Switches for the logits check's WRONG references (`hp`, all absent in the
sound one): "use_expert_bias" False (the selection without its bias),
"qk_norm" False (q and k without their norms), and `state_from` (an argument
of `layer_step`: the decode positions' taps that reach back before a row's
prompt end read the gated inputs of two other columns instead, as an engine
would that took the convolution's state at the wave's padded end).

It is applied ONE LAYER AT A TIME (`layer_step`, jitted functions a layer
part, looped in Python by `hidden`): a layer's weights are cast to float32
as the layer is reached, a routed expert's as the loop reaches it, and the
head a block of the vocabulary at a time, so that a model whose weights fill
most of a chip in bf16 can still be checked on that chip.

Parameters: {"embed" [vocab, d], "norm_f" [d], "head" [d, vocab], "layers":
[{"norm_op" [d], "norm_ffn" [d]; a convolution layer: "in_proj" [d, 3 d],
"conv_w" [k, d], "out_proj" [d, d]; an attention layer: "wq", "wk", "wv",
"wo", "q_norm" [hd], "k_norm" [hd]; a dense layer: "mlp_in" [d, 2 w],
"mlp_out" [w, d]; an expert layer: "router" [d, E], "score_bias" [E], "w_in"
[held, d, 2 w], "w_out" [held, w, d]}]}; a layer's kinds are read from which
of these it holds; matrices are [in, out]. `hp`: {"heads", "kv_heads",
"rope_theta", "top_k", "held": (lo, hi), "routed_scaling_factor",
"gate_norm_eps", "eps"}.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# the head is applied to this many columns of the vocabulary at a time
VOCAB_BLOCK = 16384


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), tree)


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x / (1.0 + jnp.exp(-x))


# ------------------------------------------------------------------ operators
def short_conv(x, w, state_from=None, lengths=None):
    """x [s, d] -> [s, d], one sequence. `state_from`, `lengths` (scalars):
    the wrong reference's switch, see the module's text."""
    s, d = x.shape
    bcx = x @ w["in_proj"]
    b_gate, c_gate, xs = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = b_gate * xs
    k = w["conv_w"].shape[0]
    t = jnp.arange(s)
    conv = jnp.zeros_like(z)
    for j in range(k):
        back = k - 1 - j                           # this tap reads z[t - back]
        src = t - back
        if state_from is not None:
            # a decode position (t >= length) reaching before the prompt's
            # end reads the column as far before `state_from` instead
            wrong = (t >= lengths) & (src < lengths)
            src = jnp.where(wrong, state_from - (lengths - src), src)
        tap = jnp.where((src >= 0)[:, None], z[jnp.clip(src, 0, s - 1)], 0.0)
        conv = conv + tap * w["conv_w"][j]
    return (c_gate * conv) @ w["out_proj"]


def rotate_half(x, positions, theta):
    """x [s, heads, hd]: the pairs (i, i + hd / 2) turned by positions *
    theta^(-2 i / hd)."""
    hd = x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(hd // 2, dtype=jnp.float32) / hd)
    angle = positions.astype(jnp.float32)[:, None, None] * inv      # [s, 1, hd/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(x, positions, w, hp):
    """x [s, d], positions [s] -> [s, d], one sequence."""
    s, d = x.shape
    heads, kv = hp["heads"], hp["kv_heads"]
    hd = d // heads
    q = (x @ w["wq"]).reshape(s, heads, hd)
    k = (x @ w["wk"]).reshape(s, kv, hd)
    v = (x @ w["wv"]).reshape(s, kv, hd)
    if hp.get("qk_norm", True):
        q, k = rms(q, w["q_norm"], hp["eps"]), rms(k, w["k_norm"], hp["eps"])
    q = rotate_half(q, positions, hp["rope_theta"])
    k = rotate_half(k, positions, hp["rope_theta"])
    of_head = jnp.arange(heads) // (heads // kv)    # head j reads K/V head j // group
    k, v = k[:, of_head], v[:, of_head]
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(hd))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(s, d) @ w["wo"]


# ---------------------------------------------------------------- feed-forward
def gated_mlp(x, w_in, w_out):
    a, b = jnp.split(x @ w_in, 2, axis=-1)
    return (silu(a) * b) @ w_out


def selection_scores(x, w, hp):
    """(s, c) [.., E]: an expert's score sigmoid(x W_r), and the score it is
    SELECTED by, s + bias."""
    s = jax.nn.sigmoid(x @ _f32(w["router"]))
    return s, (s + _f32(w["score_bias"]) if hp.get("use_expert_bias", True)
               else s)


def route(x, w, hp):
    """(gates [.., k] of the chosen, experts [.., k]) over ALL experts."""
    s, c = selection_scores(x, w, hp)
    experts = jax.lax.top_k(c, hp["top_k"])[1]
    g = jnp.take_along_axis(s, experts, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + hp["gate_norm_eps"])
    return g * hp["routed_scaling_factor"], experts


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
    return tuple(sorted(hp.items()))


OPERATOR_KEYS = ("norm_op", "in_proj", "conv_w", "out_proj", "wq", "wk", "wv",
                 "wo", "q_norm", "k_norm")


@functools.partial(jax.jit, static_argnums=(3,))
def _operator_step(h, positions, w, hp_key, state_from=None, lengths=None):
    hp = dict(hp_key)
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        x = rms(h, w["norm_op"], hp["eps"])
        if "in_proj" in w:
            if state_from is None:
                return h + jax.lax.map(lambda row: short_conv(row, w), x)
            return h + jax.lax.map(
                lambda row: short_conv(row[0], w, row[1], row[2]),
                (x, state_from, lengths))
        return h + jax.lax.map(
            lambda row: attention(row[0], row[1], w, hp), (x, positions))


@functools.partial(jax.jit, static_argnums=(2,))
def _feed_forward_step(h, w, hp_key):
    hp = dict(hp_key)
    with jax.default_matmul_precision("highest"):
        x = rms(h, _f32(w["norm_ffn"]), hp["eps"])
        if "mlp_in" in w:
            return h + jax.lax.map(
                lambda row: gated_mlp(row, _f32(w["mlp_in"]),
                                      _f32(w["mlp_out"])), x), None
        # its experts are cast as the loop reaches them
        return h + moe(x, w, hp), route(x, w, hp)[1]


def layer_step(h, positions, layer, hp, choices: bool = False,
               state_from=None, lengths=None):
    """One decoder layer on h [batch, seq, d] float32; `layer` holds that
    layer's weights in whatever type they are kept. `choices`: also the
    experts each token was routed to, [batch, seq, k] (None for a dense
    layer). `state_from`, `lengths` [batch]: the wrong reference's switch
    (the module's text); None in the sound one."""
    key = _hp_key(hp)
    operator = {k: v for k, v in layer.items() if k in OPERATOR_KEYS}
    ff = {k: v for k, v in layer.items() if k not in operator}
    if "in_proj" not in operator:
        state_from = lengths = None
    h, experts = _feed_forward_step(
        _operator_step(h, positions, operator, key, state_from, lengths),
        ff, key)
    return (h, experts) if choices else h


@jax.jit
def _embed(embed, ids):
    return _f32(embed)[ids]


def hidden(params, ids, positions, hp):
    """The hidden state after every layer, before the final norm."""
    h = _embed(params["embed"], ids)
    for layer in params["layers"]:
        h = layer_step(h, positions, layer, hp)
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
