"""Jamba decoders (Hugging Face `JambaForCausalLM`, model_type `jamba`;
AI21-Jamba2-3B is one) as the config.json describes them: the plain reference
the system is held to.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"): no kernel, no cache, no batching;
the state-space recurrence is a `lax.scan` over SINGLE positions, no chunks.
Written from the description, not from flexflow_tpu/models/jamba.py or
flexflow_tpu/ops/. With d the hidden size, C = mamba_expand x d, N =
mamba_d_state, R = mamba_dt_rank and RMS(x; w) = x / sqrt(mean(x^2) + eps) * w:

    h_0 = E[ids]
    for l in layers:
        h = h + Mixer_l(RMS(h; w_in))     Attn if l % attn_layer_period ==
                                          attn_layer_offset else Mamba
        x = RMS(h; w_ff)
        h = h + (silu(x W_gate) * (x W_up)) W_down          (num_experts 1)
    logits = RMS(h_L; w_f) W_head

    Mamba(x):  [u | z] = x W_in                              d -> C | C
               u = silu(causal depthwise conv1d(u, width d_conv) + b_conv)
               [dt_r | B | C] = u W_x                        C -> R | N | N
               dt_r = RMS(dt_r; w_dt); B = RMS(B; w_B); C = RMS(C; w_C)
               dt = softplus(dt_r W_dt + b_dt)               R -> C
               A = -exp(A_log)                               [C, N]
               S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n]
                           + dt_t[c] B_t[n] u_t[c]           (the literal
               y_t[c] = sum_n S_t[c, n] C_t[n] + D[c] u_t[c]  recurrence over t)
               out = (y * silu(z)) W_out
    Attn(x):   q = x Wq [heads x hd], k = x Wk, v = x Wv [kv_heads x hd];
               query head j reads K/V head j // (heads / kv_heads) (all of
               them the one head where kv_heads is 1); p = softmax(q k^T /
               sqrt(hd) + causal mask); out = (p v) Wo. No positions, no bias.

Departures from the published model, the system's and so mirrored here:
- the head is a weight of its own, not tied to E (`tie_word_embeddings` true
  as published; the system's graph lets a layer read its own weights only).

For sizes that fill a chip it is applied ONE LAYER AT A TIME (`layer_step`, a
jitted function per layer kind, looped in Python by `hidden`): each layer's
weights are cast to float32 as the layer is reached. Attention's scores
exist for QUERY_BLOCK queries at a time and the head's logits for
VOCAB_BLOCK columns at a time (at 16 896 positions the whole of either is
tens of GB); both are the same sums in the same order as the whole.

Parameters: {"embed" [vocab, d], "norm_f" [d], "head" [d, vocab], "layers":
[{"norm_in", "norm_ff" [d], "w_gate", "w_up" [d, w], "w_down" [w, d], and
for a Mamba layer: "in_proj" [d, 2 C], "conv_w" [d_conv, C], "conv_b" [C],
"x_proj" [C, R + 2 N], "dt_norm" [R], "b_norm", "c_norm" [N], "dt_proj" [R,
C], "dt_bias" [C], "A_log" [C, N], "D" [C], "out_proj" [C, d]; for an
attention layer: "wq", "wk", "wv", "wo"}]}; a layer's kind is read from which
of these it holds; matrices are [in, out]. `hp`: {"heads", "kv_heads",
"eps"}, and for the logits check's wrong references "bf16_state" /
"bf16_decay": the recurrence's state, or its decay exp(dt A), rounded to
bfloat16 at every position.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# the head is applied to this many columns of the vocabulary at a time
VOCAB_BLOCK = 16384
# queries whose scores of every key exist at once (at 16 896 keys and 20
# heads a block's float32 scores are 173 MB)
QUERY_BLOCK = 128


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), tree)


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def mamba(x, w, hp, state=None):
    """x [b, s, d] -> [b, s, d]; with `state` = (S [b, C, N], the last d_conv
    - 1 rows of u before the conv [b, d_conv - 1, C]) the sequence continues
    from it, and the state after the last position is handed back too."""
    b, s, _d = x.shape
    c, n = w["A_log"].shape
    r = w["dt_proj"].shape[0]
    eps = hp["eps"]
    uz = x @ w["in_proj"]
    u, z = uz[..., :c], uz[..., c:]
    width = w["conv_w"].shape[0]
    before = jnp.zeros((b, width - 1, c), u.dtype) if state is None \
        else state[1]
    padded = jnp.concatenate([before, u], axis=1)
    conv = sum(padded[:, j:j + s] * w["conv_w"][j] for j in range(width))
    u = silu(conv + w["conv_b"])
    proj = u @ w["x_proj"]
    dt_r = rms(proj[..., :r], w["dt_norm"], eps)
    b_in = rms(proj[..., r:r + n], w["b_norm"], eps)
    c_out = rms(proj[..., r + n:], w["c_norm"], eps)
    dt = jax.nn.softplus(dt_r @ w["dt_proj"] + w["dt_bias"])    # [b, s, C]
    a = -jnp.exp(w["A_log"])                                    # [C, N]

    def low(x, switch):
        """x rounded to bfloat16 where `hp[switch]` says so: what a program
        that kept that value in the compute type would compute (the logits
        check's wrong references; reduce_precision, not a cast there and
        back, which XLA may drop)."""
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7) \
            if hp.get(switch) else x

    def step(ssm, t):
        u_t, b_t, c_t, dt_t = t                                 # one position
        ssm = low(low(jnp.exp(dt_t[..., None] * a), "bf16_decay") * ssm
                  + (dt_t * u_t)[..., None] * b_t[:, None, :], "bf16_state")
        return ssm, jnp.sum(ssm * c_t[:, None, :], axis=-1) + w["D"] * u_t

    first = jnp.zeros((b, c, n), jnp.float32) if state is None else state[0]
    last, y = jax.lax.scan(step, first, tuple(
        jnp.moveaxis(t, 1, 0) for t in (u, b_in, c_out, dt)))
    out = (jnp.moveaxis(y, 0, 1) * silu(z)) @ w["out_proj"]
    if state is None:
        return out
    return out, (last, padded[:, s:])


def attention(x, w, hp):
    """x [s, d] -> [s, d], one sequence."""
    s, d = x.shape
    heads, kv = hp["heads"], hp["kv_heads"]
    hd = d // heads
    # query head j reads K/V head j // (heads / kv): q as [s, kv groups,
    # heads a group, hd] against its group's k and v
    q = (x @ w["wq"]).reshape(s, kv, heads // kv, hd)
    k = (x @ w["wk"]).reshape(s, kv, hd)
    v = (x @ w["wv"]).reshape(s, kv, hd)

    def block(qb, tq):
        """Queries at places tq [q] of the sequence against every key."""
        scores = jnp.einsum("qgrd,kgd->grqk", qb, k) / jnp.sqrt(float(hd))
        scores = jnp.where(jnp.arange(s)[None, :] <= tq[:, None], scores,
                           -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, axis=-1), v)

    at = jnp.arange(s)
    if s <= QUERY_BLOCK:
        out = block(q, at)
    else:
        n = -(-s // QUERY_BLOCK)
        fill = n * QUERY_BLOCK - s
        qs = jnp.pad(q, [(0, fill), (0, 0), (0, 0), (0, 0)]).reshape(
            n, QUERY_BLOCK, kv, heads // kv, hd)
        ats = jnp.pad(at, [(0, fill)], constant_values=s - 1).reshape(
            n, QUERY_BLOCK)
        out = jax.lax.map(lambda a: block(a[0], a[1]), (qs, ats))
        out = out.reshape(n * QUERY_BLOCK, d)[:s]
    return out.reshape(s, d) @ w["wo"]


def mlp(x, w):
    return (silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def _hp_key(hp):
    return tuple(sorted(hp.items()))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_step(h, w, kind, hp_key):
    hp = dict(hp_key)
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        x = rms(h, w["norm_in"], hp["eps"])
        if kind == "mamba":
            h = h + mamba(x, w, hp)
        else:
            h = h + jax.lax.map(lambda row: attention(row, w, hp), x)
        return h + mlp(rms(h, w["norm_ff"], hp["eps"]), w)


def layer_step(h, layer, hp):
    """One decoder layer on h [batch, seq, d] float32; `layer` holds that
    layer's weights in whatever type they are kept."""
    kind = "mamba" if "in_proj" in layer else "attention"
    return _layer_step(h, layer, kind, _hp_key(hp))


@jax.jit
def _embed(embed, ids):
    return _f32(embed[ids])


def hidden(params, ids, hp):
    """The hidden state after every layer, before the final norm."""
    h = _embed(params["embed"], ids)
    for layer in params["layers"]:
        h = layer_step(h, layer, hp)
    return h


@functools.partial(jax.jit, static_argnums=(3,))
def _head(h, norm_f, head, eps):
    with jax.default_matmul_precision("highest"):
        return rms(h, _f32(norm_f), eps) @ _f32(head)


def forward(params, ids, hp):
    """Logits [batch, seq, vocab] in float32 (whole: for sizes that fit)."""
    return _head(hidden(params, ids, hp), params["norm_f"], params["head"],
                 hp["eps"])


def next_token_loss(params, ids, labels, hp):
    """Mean cross-entropy of labels[b, t] under logits[b, t]."""
    logp = jax.nn.log_softmax(forward(params, ids, hp), axis=-1)
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


def token_gaps(params, ids, hp):
    """For every position t < seq - 1: how far the logit of the token that
    FOLLOWS in `ids` lies under the largest logit, and the row's scale.
    Returns (gap [b, seq-1], scale [b, seq-1]); gap 0 means the following
    token is the reference argmax."""
    h = hidden(params, ids, hp)
    return _head_gaps(h[:, :-1], params["norm_f"], params["head"],
                      jnp.asarray(ids)[:, 1:], hp["eps"])
