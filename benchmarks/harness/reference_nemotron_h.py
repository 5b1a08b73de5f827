"""Nemotron-H (Hugging Face `NemotronHForCausalLM`, model_type `nemotron_h`;
NVIDIA-Nemotron-3-Super-120B-A12B is one) as its config.json describes it:
the plain reference the system is held to.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"): no kernel, no cache, no batching,
no chunking. Written from the description, not from
flexflow_tpu/models/nemotron_h.py or flexflow_tpu/ops/. With d the hidden
size and RMS(x; w) = x / sqrt(mean(x^2) + eps) * w:

    h_0 = E[ids]
    for l in layers:                one mixer a layer, by hybrid_override_pattern
        h = h + f_l(RMS(h; w_l))    f_l: Mamba2 ("M") | Attn ("*") | Experts ("E")
    logits = RMS(h_L; w_f) W_head

    Mamba2(x): [z | xBC | dt] = x W_in      widths d_inner | d_inner + 2 G N | H
               xBC = silu(causal depthwise conv1d(xBC, width d_conv) + b_conv)
               [u | B | C] = xBC;  u [H heads, P], B and C [G groups, N]
               dt = softplus(dt + dt_bias);  A = -exp(A_log)
               S_t = exp(dt_t A) S_{t-1} + dt_t u_t (x) B_t     (the literal
               y_t = S_t C_t + D u_t                             recurrence over t;
                                     head h reads B, C of group h // (H / G))
               g = y * silu(z);  each group's d_inner / G values normalised
               apart: g / sqrt(mean_group(g^2) + eps) * w_norm;  out = g W_out
    Attn(x):   q = x Wq [heads x hd], k = x Wk, v = x Wv [kv_heads x hd];
               query head j reads K/V head j // (heads / kv_heads);
               p = softmax(q k^T / sqrt(hd) + causal mask); out = (p v) Wo.
               No positions, no bias.
    Experts(x): s = sigmoid(x W_r) over ALL experts; the top k of s + b are
               chosen (b the selection bias); g = s[chosen] / (sum + 1e-20)
               * routed_scaling_factor
               l = x W_down                             d -> latent
               routed = (sum_i g_i relu(l U_i)^2 V_i) W_up   over the choices
               whose expert is HELD; the others add nothing
               Experts = routed + relu(x U_s)^2 V_s     the shared expert, on x

Departures from the published model, the system's and so mirrored here:
- `held` and the vocabulary are arguments (through the weights' shapes and
  `held`): the reference is given the same share of a stated deployment as
  the program, and returns that holder's part of the result;
- the multi-token-prediction module (`num_nextn_predict_layers` 1) is not
  built: the next-token logits do not depend on it;
- attention carries no rotary positions (the family's NoPE; the config's
  `rope_theta` is not read), and the router reads the d-wide row while the
  experts read the latent (both `assumed` in the configuration file).

It is applied ONE LAYER AT A TIME (`layer_step`, a jitted function per layer
kind, looped in Python by `forward`): a layer's weights are cast to float32
as the layer is reached, a routed expert's as the loop reaches it, so that
a model whose weights fill most of a chip in bf16 can still be checked on
that chip.

Parameters: {"embed" [vocab, d], "norm_f" [d], "head" [d, vocab], "layers":
[{"norm" [d], and for a Mamba layer: "in_proj", "conv_w" [d_conv, conv_dim],
"conv_b", "A_log", "D", "dt_bias", "gate_norm" [d_inner], "out_proj"; for
an attention layer: "wq", "wk", "wv", "wo"; for an expert layer: "router"
[d, E], "score_bias" [E], "latent_in" [d, latent], "latent_out" [latent, d],
"w_in" [held, latent, w], "w_out" [held, w, latent], "shared_in" [d, ws],
"shared_out" [ws, d]}]}; a layer's kind is read from which of these it
holds; matrices are [in, out]. `hp` (hyper-parameters): {"heads",
"kv_heads", "mamba_heads", "mamba_head_dim", "d_state", "n_groups",
"top_k", "held": (lo, hi), "routed_scaling_factor", "eps"}.
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


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


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
    scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(float(hd))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ v
    return out.transpose(0, 2, 1, 3).reshape(b, s, d) @ w["wo"]


def mamba2(x, w, hp):
    b, s, _d = x.shape
    heads, p, n = hp["mamba_heads"], hp["mamba_head_dim"], hp["d_state"]
    groups = hp["n_groups"]
    d_inner = heads * p
    conv_dim = d_inner + 2 * groups * n
    zxbcdt = x @ w["in_proj"]
    z, xbc, dt = (zxbcdt[..., :d_inner], zxbcdt[..., d_inner:d_inner + conv_dim],
                  zxbcdt[..., d_inner + conv_dim:])
    width = w["conv_w"].shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((b, width - 1, conv_dim), xbc.dtype), xbc], axis=1)
    conv = sum(padded[:, j:j + s] * w["conv_w"][j] for j in range(width))
    xbc = silu(conv + w["conv_b"])
    u = xbc[..., :d_inner].reshape(b, s, heads, p)
    of_head = jnp.arange(heads) // (heads // groups)    # a head's group
    b_in = xbc[..., d_inner:d_inner + groups * n].reshape(b, s, groups, n)
    c_out = xbc[..., d_inner + groups * n:].reshape(b, s, groups, n)
    dt = jax.nn.softplus(dt + w["dt_bias"])                     # [b, s, H]
    a = -jnp.exp(w["A_log"])

    def step(state, t):
        u_t, b_t, c_t, dt_t = t                                 # one position
        b_t, c_t = b_t[:, of_head], c_t[:, of_head]             # [b, H, N]
        state = state * jnp.exp(dt_t * a)[:, :, None, None] \
            + (dt_t[..., None] * u_t)[..., None] * b_t[:, :, None, :]
        y_t = jnp.sum(state * c_t[:, :, None, :], axis=-1) \
            + w["D"][None, :, None] * u_t
        return state, y_t

    time_first = [jnp.moveaxis(t, 1, 0) for t in (u, b_in, c_out, dt)]
    _, y = jax.lax.scan(step, jnp.zeros((b, heads, p, n), jnp.float32),
                        tuple(time_first))
    g = jnp.moveaxis(y, 0, 1).reshape(b, s, d_inner) * silu(z)
    g = g.reshape(b, s, groups, d_inner // groups)
    g = g / jnp.sqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + hp["eps"])
    return (g.reshape(b, s, d_inner) * w["gate_norm"]) @ w["out_proj"]


def selection_scores(x, w):
    """(s, c) [.., E]: an expert's score sigmoid(x W_r), and the score it is
    SELECTED by, s + bias."""
    s = jax.nn.sigmoid(x @ _f32(w["router"]))
    return s, s + _f32(w["score_bias"])


def chosen(c, hp):
    """The experts [.., k] that selection scores c [.., E] choose."""
    return jax.lax.top_k(c, hp["top_k"])[1]


def route(x, w, hp):
    """(gates [.., k] of the chosen, experts [.., k]) over ALL experts."""
    s, c = selection_scores(x, w)
    experts = chosen(c, hp)
    g = jnp.take_along_axis(s, experts, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return g * hp["routed_scaling_factor"], experts


def moe(x, w, hp):
    """This holder's part of the routed layer: a loop over the held experts,
    each applied to every token's latent and masked by its gate; the sum is
    projected back."""
    lo, hi = hp["held"]
    gates, experts = route(x, w, hp)
    latent = x @ _f32(w["latent_in"])

    def one(e, acc):
        gate = jnp.sum(jnp.where(experts == lo + e, gates, 0.0), axis=-1)
        return acc + gate[..., None] * (
            relu2(latent @ _f32(w["w_in"][e])) @ _f32(w["w_out"][e]))

    return jax.lax.fori_loop(0, hi - lo, one, jnp.zeros_like(latent)) \
        @ _f32(w["latent_out"])


def shared(x, w):
    return relu2(x @ _f32(w["shared_in"])) @ _f32(w["shared_out"])


def _hp_key(hp):
    return tuple(sorted(hp.items()))


def kind_of(layer) -> str:
    return ("mamba" if "in_proj" in layer
            else "attention" if "wq" in layer else "experts")


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_step(h, w, kind, hp_key):
    hp = dict(hp_key)
    with jax.default_matmul_precision("highest"):
        x = rms(h, _f32(w["norm"]), hp["eps"])
        if kind == "experts":   # its experts are cast as the loop reaches them
            return (h + moe(x, w, hp) + shared(x, w), route(x, w, hp)[1],
                    selection_scores(x, w)[1])
        mix = mamba2 if kind == "mamba" else attention
        return h + mix(x, _f32(w), hp), None, None


def layer_step(h, layer, hp, choices: bool = False, scores: bool = False):
    """One decoder layer on h [batch, seq, d] float32; `layer` holds that
    layer's weights in whatever type they are kept. `choices`: also the
    experts each token was routed to, [batch, seq, k] (None for a layer
    without experts), for a measurement of how often a lower precision
    routes otherwise; `scores`: and the selection scores they were chosen
    by, [batch, seq, E]."""
    h, experts, c = _layer_step(h, layer, kind_of(layer), _hp_key(hp))
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


def hidden(params, ids, hp):
    """The hidden state after every layer, before the final norm."""
    h = _embed(params["embed"], ids)
    for layer in params["layers"]:
        h = layer_step(h, layer, hp)
    return h


def forward(params, ids, hp):
    """Logits [batch, seq, vocab] in float32."""
    return _head(hidden(params, ids, hp), params["norm_f"], params["head"],
                 hp["eps"])


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
