"""Granite 4.0-H (Hugging Face `GraniteMoeHybridForCausalLM`, model_type
`granitemoehybrid`) as its config.json describes it: the plain reference the
system is held to.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"): no kernel, no cache, no batching,
no chunking. Written from the description, not from
flexflow_tpu/models/granite_hybrid.py or flexflow_tpu/ops/. With d the
hidden size and RMS(x; w) = x / sqrt(mean(x^2) + eps) * w:

    h_0 = E[ids] * embedding_multiplier
    for l in layers:
        y = Mixer_l(RMS(h; w_in))       Mamba2 if layer_types[l] == "mamba" else Attn
        h = h + residual_multiplier * y
        x = RMS(h; w_post)
        h = h + residual_multiplier * (MoE(x) + Shared(x))
    logits = RMS(h_L; w_f) W_head / logits_scaling

    Attn(x):   q = x Wq [heads x hd], k = x Wk, v = x Wv [kv_heads x hd];
               query head j reads K/V head j // (heads / kv_heads);
               p = softmax(q k^T * attention_multiplier + causal mask);
               out = (p v) Wo.   No positions, no bias.
    Mamba2(x): [z | xBC | dt] = x W_in
               xBC = silu(causal depthwise conv1d(xBC, width d_conv) + b_conv)
               [u | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
               S_t = exp(dt_t A) S_{t-1} + dt_t u_t (x) B_t     (the literal
               y_t = S_t C_t + D u_t                             recurrence over t)
               out = RMS(y * silu(z); w_norm) W_out
    MoE(x):    r = x W_r [all experts]; (v, e) = top-k(r); g = softmax(v)
               MoE = sum_i g_i (silu(a_i) * b_i) W_out[e_i], [a_i | b_i] = x W_in[e_i]
               over the choices whose expert is HELD; the others add nothing
    Shared(x): [a | b] = x W_s_in; (silu(a) * b) W_s_out

Departures from the published model, the system's and so mirrored here:
- the head is a weight of its own, not tied to E (the system's graph lets a
  layer read its own weights only);
- `experts_held` and the vocabulary are arguments (through the weights'
  shapes and `held`): the reference is given the same share of a stated
  deployment as the program, and returns that holder's part of the result;
- n_groups is 1 (as published), so B and C are shared by all heads.

It is applied ONE LAYER AT A TIME (`layer_step`, a jitted function per layer
kind, looped in Python by `forward`): each layer's weights are cast to
float32 as the layer is reached, so that a model whose weights fill most of
a chip in bf16 can still be checked on that chip.

Parameters: {"embed" [vocab, d], "norm_f" [d], "head" [d, vocab], "layers":
[{"norm_in", "norm_post", "router" [d, E], "w_in" [held, d, 2w], "w_out"
[held, w, d], "shared_in" [d, 2ws], "shared_out" [ws, d], and for a Mamba
layer: "in_proj", "conv_w" [d_conv, conv_dim], "conv_b", "A_log", "D",
"dt_bias", "norm", "out_proj"; for an attention layer: "wq", "wk", "wv",
"wo"}]}; a layer's kind is read from which of these it holds; matrices are
[in, out]. `hp` (hyper-parameters): {"heads", "kv_heads",
"mamba_heads", "mamba_head_dim", "d_state", "top_k", "held": (lo, hi),
"embedding_multiplier", "residual_multiplier", "attention_multiplier",
"logits_scaling", "eps"}.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), tree)


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def attention(x, w, hp):
    b, s, d = x.shape
    heads, kv = hp["heads"], hp["kv_heads"]
    hd = d // heads
    q = (x @ w["wq"]).reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
    k = (x @ w["wk"]).reshape(b, s, kv, hd).transpose(0, 2, 1, 3)
    v = (x @ w["wv"]).reshape(b, s, kv, hd).transpose(0, 2, 1, 3)
    group = heads // kv
    k = k[:, jnp.arange(heads) // group]        # head j reads K/V head j // group
    v = v[:, jnp.arange(heads) // group]
    scores = q @ k.transpose(0, 1, 3, 2) * hp["attention_multiplier"]
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ v
    return out.transpose(0, 2, 1, 3).reshape(b, s, d) @ w["wo"]


def mamba2(x, w, hp):
    b, s, _d = x.shape
    heads, p, n = hp["mamba_heads"], hp["mamba_head_dim"], hp["d_state"]
    d_inner = heads * p
    zxbcdt = x @ w["in_proj"]
    z, xbc, dt = (zxbcdt[..., :d_inner], zxbcdt[..., d_inner:2 * d_inner + 2 * n],
                  zxbcdt[..., 2 * d_inner + 2 * n:])
    width = w["conv_w"].shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((b, width - 1, xbc.shape[-1]), xbc.dtype), xbc], axis=1)
    conv = sum(padded[:, j:j + s] * w["conv_w"][j] for j in range(width))
    xbc = silu(conv + w["conv_b"])
    u = xbc[..., :d_inner].reshape(b, s, heads, p)
    b_in, c_out = xbc[..., d_inner:d_inner + n], xbc[..., d_inner + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])                 # [b, s, heads]
    a = -jnp.exp(w["A_log"])

    def step(state, t):
        u_t, b_t, c_t, dt_t = t                             # one position
        state = state * jnp.exp(dt_t * a)[:, :, None, None] \
            + (dt_t[..., None] * u_t)[..., None] * b_t[:, None, None, :]
        y_t = jnp.sum(state * c_t[:, None, None, :], axis=-1) \
            + w["D"][None, :, None] * u_t
        return state, y_t

    time_first = [jnp.moveaxis(t, 1, 0) for t in (u, b_in, c_out, dt)]
    _, y = jax.lax.scan(step, jnp.zeros((b, heads, p, n), jnp.float32),
                        tuple(time_first))
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, d_inner)
    return rms(y * silu(z), w["norm"], hp["eps"]) @ w["out_proj"]


def route(x, w, hp):
    """(gates [.., k] over the chosen, experts [.., k]): the top k of the
    scores of ALL experts, softmax over those k."""
    top, experts = jax.lax.top_k(x @ w["router"], hp["top_k"])
    return jax.nn.softmax(top, axis=-1), experts


def moe(x, w, hp):
    """This holder's part of the routed layer: a loop over the held experts,
    each applied to every token and masked by its gate."""
    lo, hi = hp["held"]
    gates, experts = route(x, w, hp)
    width = w["w_out"].shape[1]

    def one(e, acc):
        gate = jnp.sum(jnp.where(experts == lo + e, gates, 0.0), axis=-1)
        ab = x @ w["w_in"][e]
        return acc + gate[..., None] * (
            (silu(ab[..., :width]) * ab[..., width:]) @ w["w_out"][e])

    return jax.lax.fori_loop(0, hi - lo, one, jnp.zeros_like(x))


def shared(x, w):
    ab = x @ w["shared_in"]
    half = ab.shape[-1] // 2
    return (silu(ab[..., :half]) * ab[..., half:]) @ w["shared_out"]


def _hp_key(hp):
    return tuple(sorted(hp.items()))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_step(h, w, kind, hp_key):
    hp = dict(hp_key)
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        mix = mamba2 if kind == "mamba" else attention
        h = h + hp["residual_multiplier"] * mix(
            rms(h, w["norm_in"], hp["eps"]), w, hp)
        x = rms(h, w["norm_post"], hp["eps"])
        return (h + hp["residual_multiplier"] * (moe(x, w, hp) + shared(x, w)),
                route(x, w, hp)[1])


def layer_step(h, layer, hp, choices: bool = False):
    """One decoder layer on h [batch, seq, d] float32; `layer` holds that
    layer's weights in whatever type they are kept. `choices`: also the
    experts each token was routed to, [batch, seq, k] (for a measurement of
    how often a lower precision routes otherwise)."""
    kind = "mamba" if "in_proj" in layer else "attention"
    h, experts = _layer_step(h, layer, kind, _hp_key(hp))
    return (h, experts) if choices else h


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(embed, ids, multiplier):
    return jnp.asarray(embed, jnp.float32)[ids] * multiplier


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(h, norm_f, head, eps, scaling):
    with jax.default_matmul_precision("highest"):
        return rms(h, jnp.asarray(norm_f, jnp.float32), eps) \
            @ jnp.asarray(head, jnp.float32) / scaling


def hidden(params, ids, hp):
    """The hidden state after every layer, before the final norm."""
    h = _embed(params["embed"], ids, hp["embedding_multiplier"])
    for layer in params["layers"]:
        h = layer_step(h, layer, hp)
    return h


def forward(params, ids, hp):
    """Logits [batch, seq, vocab] in float32."""
    return _head(hidden(params, ids, hp), params["norm_f"], params["head"],
                 hp["eps"], hp["logits_scaling"])


def next_token_loss(params, ids, labels, hp):
    """Mean cross-entropy of labels[b, t] under logits[b, t]."""
    logp = jax.nn.log_softmax(forward(params, ids, hp), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def token_gaps(params, ids, hp):
    """For every position t < seq - 1: how far the logit of the token that
    FOLLOWS in `ids` lies under the largest logit, and the row's scale.
    Returns (gap [b, seq-1], scale [b, seq-1]); gap 0 means the following
    token is the reference argmax."""
    logits = forward(params, ids, hp)[:, :-1]
    got = jnp.take_along_axis(logits, ids[:, 1:, None], axis=-1)[..., 0]
    return logits.max(axis=-1) - got, jnp.abs(logits).max(axis=-1)
