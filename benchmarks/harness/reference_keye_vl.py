"""The language model of Keye-VL-2.0 (Kwai-Keye/Keye-VL-2.0-30B-A3B's
config.json: a Qwen3-MoE text stack with `sa_config`, a DeepSeek-sparse-
attention indexer) as the config and the published equations of the indexer
(DeepSeek-V3.2-Exp) describe it: the plain reference the system is held to.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"): no kernel, no cache, no chunks, no
batching, no threshold search. Written from the description, not from
flexflow_tpu/models/keye_vl.py or flexflow_tpu/ops/ (it imports nothing of
flexflow_tpu). With RMS(x; w) = x / sqrt(mean(x^2) + eps) * w, h_t the hidden
state after a layer's first norm and p_t = (p^T, p^H, p^W) its position:

    1. q = W_q h [heads, hd], k = W_k h, v = W_v h [kv_heads, hd], no bias;
       q, k = RMS over each head's hd values, one weight each.
       ASSUMED: the config has no key for the head norms; they are the
       family's convention (Qwen3).
    2. rotary, rotate-half (pairs (i, i + hd / 2)), angle p * theta^(-2i/hd):
       pair i turns by the position axis whose `mrope_section` holds i
       (sections laid in a row: [16, 24, 24] gives pairs 0-15 p^T, 16-39
       p^H, 40-63 p^W).
    3. the indexer: qI = W_qI h [iheads, ihd], kI = LayerNorm(W_kI h) [ihd]
       (with a bias), w = W_w h [iheads]; the same rotary on qI and kI over
       their ihd / 2 pairs with `indexer_mrope_section`;
       I[t, s] = (iheads * ihd)^-1/2 sum_j w[t, j] relu(qI[t, j] . kI[s]).
       ASSUMED: fed by h (this model has no query latent); the LayerNorm as
       in the published indexer; rotary over the WHOLE indexer head with
       sections [8, 12, 12] (the published indexer turns a slice).
    4. S_t = the `topk` positions s <= t of largest I[t, s], ties to the
       lower s; every s <= t while t < topk. One set a token for all heads.
       ASSUMED: `q_chunk_size` / `kv_chunk_size` are the tiles in which the
       published code computes I and change no result.
    5. o[t, j] = sum_{s in S_t} softmax_s(q[t, j] . k[s, g(j)] / sqrt(hd))
       v[s, g(j)], g(j) = j // (heads / kv_heads); x += W_o o.
    6. second RMS; softmax(W_r h) over ALL experts, the top k renormalised
       to sum 1; experts W_2 (silu(W_1 h) * W_3 h); x += their weighted sum.
    After the last layer an RMS and an untied head.

Here S_t is found by sorting: `lax.top_k` gives the topk-th largest score of
a row, everything above it is kept, and of the scores equal to it the first
ones in the row, as many as there is room for. Attention is dense under that
membership mask, which is the sum over S_t term for term.

Departures from the published model, the system's and so mirrored here:
- `held` is an argument (with the weights' shapes): the reference returns
  that holder's part of the expert layers; the benchmark's configuration
  holds every expert, so there it is the whole layer;
- the expert layer is a LOOP over the held experts, each applied to every
  token and masked by that token's gate for it (0 where it was not chosen).

Switches for the logits check's WRONG references (`hp`, all absent in the
sound one): "indexer" False (every s <= t kept: plain attention), "topk"
(another number of kept keys than the configuration's).

It is applied ONE LAYER AT A TIME (`layer_step`, jitted functions a layer
part, looped in Python by `hidden`), a row of the batch at a time, attention
and the indexer a block of QUERY_BLOCK queries at a time: a layer's weights
are cast to float32 as the layer is reached, a routed expert's as the loop
reaches it, and the head a block of the vocabulary at a time, so that a model
whose weights fill most of a chip in bf16 can still be checked on that chip
at sequences of thousands of tokens.

Parameters: {"embed" [vocab, d], "norm_f" [d], "head" [d, vocab], "layers":
[{"norm_op" [d], "norm_ffn" [d], "wq" [d, heads hd], "wk", "wv" [d, kv hd],
"wo" [heads hd, d], "q_norm", "k_norm" [hd], "index": {"wq" [d, iheads ihd],
"wk" [d, ihd], "k_norm", "k_norm_bias" [ihd], "ww" [d, iheads]}, "router"
[d, E], "w_in" [held, d, 2 w], "w_out" [held, w, d]}]}; matrices are [in,
out]. `hp`: {"heads", "kv_heads", "head_dim", "rope_theta", "mrope_section",
"indexer_heads", "indexer_head_dim", "indexer_mrope_section", "topk",
"top_k", "held": (lo, hi), "eps"}. Positions are [batch, seq, 3].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# the head is applied to this many columns of the vocabulary at a time
VOCAB_BLOCK = 16384
# queries whose scores of every key exist at once (at 16 896 keys and 32
# heads a block's float32 scores are 277 MB, and a layer step's temporaries
# under 1 GB: what is left beside an engine that fills 12.5 of 16 GB)
QUERY_BLOCK = 128


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), tree)


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def silu(x):
    return x / (1.0 + jnp.exp(-x))


# ------------------------------------------------------------------ operators
def rotate_sections(x, positions, theta, sections):
    """x [s, heads, hd], positions [s, axes]: the pairs (i, i + hd / 2)
    turned by (the position of the axis whose section holds i) *
    theta^(-2 i / hd)."""
    hd = x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(hd // 2, dtype=jnp.float32) / hd)
    axis_of_pair = np.repeat(np.arange(len(sections)), sections)
    p = positions.astype(jnp.float32)[:, axis_of_pair]           # [s, hd/2]
    angle = (p * inv)[:, None, :]                                # [s, 1, hd/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def in_blocks(fn, s, *arrays):
    """`fn` over QUERY_BLOCK rows of `arrays` (each [s, ...]) at a time; a
    last block that is not whole is filled with zeros and cut off again."""
    if s <= QUERY_BLOCK:
        return fn(*arrays)
    n = -(-s // QUERY_BLOCK)
    arrays = [jnp.pad(a, [(0, n * QUERY_BLOCK - s)] + [(0, 0)] * (a.ndim - 1))
              for a in arrays]
    split = [a.reshape((n, QUERY_BLOCK) + a.shape[1:]) for a in arrays]
    out = jax.lax.map(lambda xs: fn(*xs), tuple(split))
    return out.reshape((n * QUERY_BLOCK,) + out.shape[2:])[:s]


def kept_keys(scores, at, topk):
    """[q, s] bool: S_t of step 4 for the queries at positions `at` [q],
    from their scores of every position [q, s]."""
    s = scores.shape[-1]
    causal = jnp.arange(s)[None, :] <= at[:, None]
    if topk >= s:
        return causal
    masked = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(masked, topk)[0][:, -1:]      # the topk-th largest
    above = masked > kth
    tied = (masked == kth) & causal
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    first = jnp.cumsum(tied, axis=-1) <= room
    return (above | (tied & first)) & causal


def indexer_keys(x, positions, w, hp):
    """The kept set [s, s] bool of one sequence x [s, d] (step 3 and 4)."""
    s = x.shape[0]
    if not hp.get("indexer", True):
        return jnp.tril(jnp.ones((s, s), bool))
    ih, ihd = hp["indexer_heads"], hp["indexer_head_dim"]
    qi = (x @ w["wq"]).reshape(s, ih, ihd)
    ki = layer_norm(x @ w["wk"], w["k_norm"], w["k_norm_bias"], hp["eps"])
    wt = x @ w["ww"]                                             # [s, ih]
    sections = hp["indexer_mrope_section"]
    qi = rotate_sections(qi, positions, hp["rope_theta"], sections)
    ki = rotate_sections(ki[:, None], positions, hp["rope_theta"],
                         sections)[:, 0]

    def block(q, wq, at):
        dots = jnp.maximum(jnp.einsum("qjd,kd->jqk", q, ki), 0.0)
        scores = jnp.einsum("jqk,qj->qk", dots, wq) / jnp.sqrt(float(ih * ihd))
        return kept_keys(scores, at, hp["topk"])

    return in_blocks(block, s, qi, wt, jnp.arange(s))


def attention(x, positions, w, hp):
    """x [s, d], positions [s, 3] -> [s, d], one sequence (steps 1-5)."""
    s = x.shape[0]
    heads, kv, hd = hp["heads"], hp["kv_heads"], hp["head_dim"]
    q = rms((x @ w["wq"]).reshape(s, heads, hd), w["q_norm"], hp["eps"])
    k = rms((x @ w["wk"]).reshape(s, kv, hd), w["k_norm"], hp["eps"])
    v = (x @ w["wv"]).reshape(s, kv, hd)
    q = rotate_sections(q, positions, hp["rope_theta"], hp["mrope_section"])
    k = rotate_sections(k, positions, hp["rope_theta"], hp["mrope_section"])
    keep = indexer_keys(x, positions, w["index"], hp)            # [s, s]
    of_head = jnp.arange(heads) // (heads // kv)
    k, v = k[:, of_head], v[:, of_head]

    def block(qb, keep_b):
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(float(hd))
        scores = jnp.where(keep_b[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    return in_blocks(block, s, q, keep).reshape(s, heads * hd) @ w["wo"]


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


@functools.partial(jax.jit, static_argnums=(3, 4))
def _attention_step(h, positions, w, hp_key, residual=True):
    hp = dict(hp_key)
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        x = rms(h, w["norm_op"], hp["eps"])
        y = jax.lax.map(lambda row: attention(row[0], row[1], w, hp),
                        (x, positions))
    return h + y if residual else y


@functools.partial(jax.jit, static_argnums=(2,))
def _feed_forward_step(h, w, hp_key):
    hp = dict(hp_key)
    with jax.default_matmul_precision("highest"):
        x = rms(h, _f32(w["norm_ffn"]), hp["eps"])
        # a row at a time; its experts are cast as the loop reaches them
        return h + jax.lax.map(lambda row: moe(row, w, hp), x)


def layer_step(h, positions, layer, hp):
    """One decoder layer on h [batch, seq, d] float32; `layer` holds that
    layer's weights in whatever type they are kept."""
    key = _hp_key(hp)
    ff = {k: v for k, v in layer.items() if k in FEED_FORWARD_KEYS}
    op = {k: v for k, v in layer.items() if k not in ff}
    return _feed_forward_step(_attention_step(h, positions, op, key), ff, key)


def attention_output(params, ids, positions, hp, layer: int = 0):
    """[batch, seq, d]: what layer `layer`'s attention adds to the residual
    stream (W_o o), before it is added: what the logits check compares at
    its own scale."""
    h = _embed(params["embed"], ids)
    for l in params["layers"][:layer]:
        h = layer_step(h, positions, l, hp)
    op = {k: v for k, v in params["layers"][layer].items()
          if k not in FEED_FORWARD_KEYS}
    return _attention_step(h, positions, op, _hp_key(hp), False)


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
