"""Brumby decoders (Hugging Face model_type `brumby`; Brumby-14B-Base is one)
as their config.json and the power-retention paper describe them: the plain
reference the system is held to.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"): no kernel, no cache, no chunk, no
state. The retention layers run in the PAIR form over the whole sequence (a
masked `[T, T]` matrix a head, as attention would). Written from the
description (ISSUE 43, "The power-retention layer"; Manifest AI, "Scaling
Context Requires Rethinking Attention", arXiv:2507.04239), not from
flexflow_tpu/models/ or flexflow_tpu/ops/. With RMS(x; w) = x / sqrt(mean(x^2)
+ eps) * w, H query heads and J K/V heads of width D, query head h reading
group j = h // (H / J):

    h_0 = E[ids]
    for l in layers:
        h = h + Ret(RMS(h; w_in))
        u = RMS(h; w_post)
        h = h + (silu(u W_gate) * (u W_up)) W_down      [W_gate | W_up] = W_in
    logits = RMS(h_L; w_f) W_head

    Ret(u):  q = RoPE(RMS(u W_q; w_q))  a head [H, D];  k = RoPE(RMS(u W_k; w_k))  [J, D]
             v = u W_v  [J, D];  log g_t = logsigmoid(u_t W_g)  [J];  G_t = sum_{r<=t} log g_r
             RoPE: rotate-half, x cos + [-x_2 | x_1] sin over the whole head,
             angle of pair i = position * theta^(-2i/D)
             a_{t,s} = exp(G_{j,t} - G_{j,s}) (q_{h,t} . k_{j,s})^2   for s <= t
             y_{h,t} = sum_s a_{t,s} v_{j,s} / (sum_s a_{t,s} + eps)
             Ret = concat_h(y_h) W_o

`retention_recurrence` is the same numbers as the literal recurrence over
the plain `k (x) k` (S_t = g_t S_{t-1} + (k_t (x) k_t) v_t^T in `[D, D, D]`,
z_t = g_t z_{t-1} + k_t (x) k_t, y = (q (x) q) : S / ((q (x) q) : z + eps)):
what the tests hold the pair form and the program's state to, and where a
state in a lower precision can be tried (`state_dtype`).

Departures from the published model, the system's and so mirrored here:
what ISSUE 43 lists as `assumed` (degree 2; the gate's form and that it is a
K/V head's; q/k norms and rotary positions as in the Qwen3 block; eps; no
scale inside the power) is assumed here alike. `hp` may carry the switches
of benchmarks/logits_check_brumby.py's wrong variants: `normaliser` False
(y = the numerator), `gate_factor` (g times it), `state_dtype` (the layers
run as the recurrence with the state rounded to it after every step).

It is applied ONE LAYER AT A TIME (two jitted functions a layer, looped in
Python by `forward`), the mixer one row of the batch at a time, each weight
cast to float32 as it is reached and the head a block of the vocabulary at
a time, so that a model whose weights fill most of a chip in bf16 can still
be checked on that chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# rows of the vocabulary the head takes at a time (`token_gaps`)
VOCAB_BLOCK = 16384


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, hp):
    """x [T, heads, D] rotated at `positions` [T], the rotate-half layout."""
    d = x.shape[-1]
    inv = hp["rope_theta"] ** (-2.0 * np.arange(d // 2) / d)
    angle = positions.astype(jnp.float32)[:, None, None] \
        * jnp.asarray(np.concatenate([inv, inv]), jnp.float32)
    partner = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(angle) + partner * jnp.sin(angle)


def retention_inputs(u, positions, w, hp):
    """(q [T, H, D], k [T, J, D], v [T, J, D], log g [T, J]) of one row u
    [T, d]."""
    t = u.shape[0]
    heads, kv, d = hp["heads"], hp["kv_heads"], hp["head_dim"]
    q = rms((u @ _f32(w["wq"])).reshape(t, heads, d), _f32(w["q_norm"]),
            hp["eps"])
    k = rms((u @ _f32(w["wk"])).reshape(t, kv, d), _f32(w["k_norm"]),
            hp["eps"])
    v = (u @ _f32(w["wv"])).reshape(t, kv, d)
    log_g = jax.nn.log_sigmoid(u @ _f32(w["wg"])) \
        + jnp.log(hp.get("gate_factor", 1.0))
    return rope(q, positions, hp), rope(k, positions, hp), v, log_g


def retention_pairs(q, k, v, log_g, hp):
    """The pair form: y [T, H, D]."""
    t, heads, d = q.shape
    kv = k.shape[1]
    run = jnp.cumsum(log_g, axis=0)                          # G, [T, J]
    qg = q.reshape(t, kv, heads // kv, d)
    scores = jnp.einsum("tjgd,sjd->jgts", qg, k)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    fall = jnp.where(causal, run.T[:, :, None] - run.T[:, None, :], -jnp.inf)
    a = jnp.square(scores) * jnp.exp(fall)[:, None]          # [J, G, T, T]
    num = jnp.einsum("jgts,sjd->tjgd", a, v)
    if not hp.get("normaliser", True):
        return num.reshape(t, heads, d)
    den = jnp.moveaxis(jnp.sum(a, axis=-1), -1, 0)           # [T, J, G]
    return (num / (den[..., None] + hp["eps"])).reshape(t, heads, d)


def retention_recurrence(q, k, v, log_g, hp, state=None, state_dtype=None):
    """The literal recurrence over the plain k (x) k: (y [T, H, D], (S [J, D,
    D, D], z [J, D, D]) after the last step). `state`: where to start from;
    `state_dtype`: the state is rounded to it after every step."""
    t, heads, d = q.shape
    kv = k.shape[1]
    keep = (lambda s: s) if state_dtype is None else \
        (lambda s: s.astype(state_dtype).astype(jnp.float32))
    if state is None:
        state = (jnp.zeros((kv, d, d, d), jnp.float32),
                 jnp.zeros((kv, d, d), jnp.float32))

    def step(held, xs):
        s, z = held
        q_t, k_t, v_t, lg = xs
        g = jnp.exp(lg)
        kk = k_t[:, :, None] * k_t[:, None, :]               # [J, D, D]
        s = keep(s * g[:, None, None, None] + kk[..., None]
                 * v_t[:, None, None, :])
        z = keep(z * g[:, None, None] + kk)
        qg = q_t.reshape(kv, heads // kv, d)
        qq = qg[..., :, None] * qg[..., None, :]             # [J, G, D, D]
        num = jnp.einsum("jgab,jabd->jgd", qq, s)
        den = jnp.einsum("jgab,jab->jg", qq, z)
        y = num / (den[..., None] + hp["eps"]) \
            if hp.get("normaliser", True) else num
        return (s, z), y.reshape(heads, d)

    held, y = jax.lax.scan(step, state, (q, k, v, log_g))
    return y, held


def retention(u, positions, w, hp):
    """One row: u [T, d] -> [T, d]."""
    q, k, v, log_g = retention_inputs(u, positions, w, hp)
    if hp.get("state_dtype"):   # a wrong variant: the state in that type
        y, _ = retention_recurrence(q, k, v, log_g, hp,
                                    state_dtype=jnp.dtype(hp["state_dtype"]))
    else:
        y = retention_pairs(q, k, v, log_g, hp)
    return y.reshape(u.shape[0], -1) @ _f32(w["wo"])


def gated_mlp(x, w_in, w_out):
    ab = x @ w_in
    a, b = jnp.split(ab, 2, axis=-1)
    return (jax.nn.silu(a) * b) @ w_out


def _hp_key(hp):
    return tuple(sorted(hp.items()))


@functools.partial(jax.jit, static_argnums=(3,))
def _mixer_step(h, positions, w, hp_key):
    hp = dict(hp_key)
    with jax.default_matmul_precision("highest"):
        x = rms(h, _f32(w["norm_in"]), hp["eps"])
        return h + jax.lax.map(lambda row: retention(row[0], row[1], w, hp),
                               (x, positions))


@functools.partial(jax.jit, static_argnums=(2,))
def _feed_forward_step(h, w, hp_key):
    hp = dict(hp_key)
    with jax.default_matmul_precision("highest"):
        x = rms(h, _f32(w["norm_post"]), hp["eps"])
        return h + jax.lax.map(
            lambda row: gated_mlp(row, _f32(w["mlp_in"]), _f32(w["mlp_out"])),
            x)


FF_KEYS = ("norm_post", "mlp_in", "mlp_out")


def layer_step(h, positions, layer, hp):
    """One decoder layer on h [batch, seq, d] float32; `layer` holds that
    layer's weights in whatever type they are kept."""
    key = _hp_key(hp)
    ff = {k: layer[k] for k in FF_KEYS}
    mixer = {k: v for k, v in layer.items() if k not in ff}
    return _feed_forward_step(_mixer_step(h, positions, mixer, key), ff, key)


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
