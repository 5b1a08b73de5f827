"""GPT-2 as published (Radford et al. 2019; the Hugging Face GPT2LMHeadModel
layout of config.json): the plain reference the system is held to.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"): no kernel, no cache, no batching
tricks, one full causal forward. Written from the description, not from
flexflow_tpu/models/gpt2.py.

    h_0   = wte[ids] + wpe[pos]
    a     = LN1(h);  q, k, v = a Wq + bq, a Wk + bk, a Wv + bv    (heads split
            along the feature axis, head_dim = d / n_head)
    att   = softmax(q k^T / sqrt(head_dim) + causal mask) v
    h     = h + att Wo + bo
    h     = h + gelu_new(LN2(h) Wup + bup) Wdown + bdown
    logit = LN_f(h_L) Whead

Departures from the published model, both the system's and so mirrored here:
the head is a weight of its own (not tied to wte), and q/k/v are three
matrices where the checkpoint has one fused c_attn (the same mathematics).

Parameters: {"wte", "wpe", "lnf_g", "lnf_b", "head", "blocks": [{"ln1_g",
"ln1_b", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "bo", "ln2_g", "ln2_b",
"w_up", "b_up", "w_down", "b_down"}]}; matrices are [in, out].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def attention(a, blk, n_head):
    b, s, d = a.shape
    hd = d // n_head

    def heads(w, bias):
        return (a @ w + bias).reshape(b, s, n_head, hd).transpose(0, 2, 1, 3)

    q, k, v = (heads(blk["wq"], blk["bq"]), heads(blk["wk"], blk["bk"]),
               heads(blk["wv"], blk["bv"]))
    scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ v
    return out.transpose(0, 2, 1, 3).reshape(b, s, d) @ blk["wo"] + blk["bo"]


def forward(params, ids, pos, n_head: int, eps: float = 1e-5):
    """Logits [batch, seq, vocab] in float32."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
        h = p["wte"][ids] + p["wpe"][pos]
        for blk in p["blocks"]:
            h = h + attention(layer_norm(h, blk["ln1_g"], blk["ln1_b"], eps),
                              blk, n_head)
            m = layer_norm(h, blk["ln2_g"], blk["ln2_b"], eps)
            h = h + gelu_new(m @ blk["w_up"] + blk["b_up"]) @ blk["w_down"] \
                + blk["b_down"]
        return layer_norm(h, p["lnf_g"], p["lnf_b"], eps) @ p["head"]


def next_token_loss(params, ids, pos, labels, n_head: int, eps: float = 1e-5):
    """Mean cross-entropy of labels[b, t] under logits[b, t]."""
    logits = forward(params, ids, pos, n_head, eps)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def token_gaps(params, ids, pos, n_head: int, eps: float = 1e-5):
    """For every position t < seq - 1: how far the logit of the token that
    FOLLOWS in `ids` lies under the largest logit, and the row's scale.
    Returns (gap [b, seq-1], scale [b, seq-1]); gap 0 means the following
    token is the reference argmax."""
    logits = forward(params, ids, pos, n_head, eps)[:, :-1]
    got = jnp.take_along_axis(logits, ids[:, 1:, None], axis=-1)[..., 0]
    return logits.max(axis=-1) - got, jnp.abs(logits).max(axis=-1)
