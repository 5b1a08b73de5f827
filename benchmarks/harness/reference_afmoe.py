"""AFMoE decoders (arcee-ai/Trinity-Mini's config.json, model_type `afmoe`)
as the config's keys and the family's published modelling code
(`modeling_afmoe.py` in Hugging Face transformers) describe them: the plain
reference the system is held to, forward, next-token loss and, through
`jax.grad`, every gradient.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"): no kernel, no cache, no batching.
Written from the description, not from flexflow_tpu/models/afmoe.py or
flexflow_tpu/ops/ (it imports nothing of flexflow_tpu). With RMS(x; w) = x /
sqrt(mean(x^2) + eps) * w:

    h_0 = E[ids] * sqrt(hidden_size)                       (`mup_enabled`)
    a layer:  h <- h + N2(Attn(N1(h)));  h <- h + N4(F(N3(h)))
    Attn(x):  q = W_q x [heads, hd], k = W_k x, v = W_v x [kv_heads, hd], no
              bias; q, k = RMS over each head's hd values, one weight each.
              A `sliding_attention` layer turns q and k (rotate-half, pairs
              (i, i + hd / 2), angle p * theta^(-2i/hd)) and lets query t see
              t - window < s <= t; a `full_attention` layer turns NOTHING and
              sees every s <= t. a[t, j] = sum_s softmax_s(q[t, j] . k[s,
              g(j)] / sqrt(hd)) v[s, g(j)], g(j) = j // (heads / kv_heads).
              Attn = W_o (a * sigmoid(W_g x)).
    F(x):     layers < num_dense_layers: W_2 (silu(W_1 x) * W_3 x), 6144 wide.
              Else Shared(x) + sum_i g_i Expert_{e_i}(x): scores s =
              sigmoid(W_r x) over ALL experts; e = the top k of s + b; g_i =
              s[e_i] / (sum_j s[e_j] + 1e-20) * route_scale.
    logits = RMS(h_L) W_head;  loss = mean next-token cross-entropy.
    After a training step, a layer at a time, with c[e] the tokens the step
    routed to expert e:  d = load_balance_coeff * sign(mean(c) - c);
    b <- b + d - mean(d).  (`bias_update`)

ASSUMED, because the config has no key for it and the modelling code says so
(each is listed in the configuration file's `assumed`; a reader of
modeling_afmoe.py should check them in this order): (1) no rotation at all
on the full layers; (2) the output gate reads the layer's normed input and
multiplies the heads' output BEFORE W_o; (3) four norms a layer, the second
and fourth on the sub-layer's OUTPUT before the residual add; (4) the head
norms on q and k before the rotation; (5) the gates are the scores WITHOUT
the bias, normalised over their sum + 1e-20, then scaled; (6) the bias rule
is torchtitan's (the config's `load_balance_coeff`, `score_func`,
`route_norm`, `route_scale`, `use_grouped_mm` are its `MoEArgs`); (7) the
embedding's output times sqrt(hidden_size) under `mup_enabled`.

Departures from the published model, the system's and so mirrored here:
- `held = (lo, hi)` is an argument (with the weights' shapes): the reference
  returns that holder's part of the expert layers, the routed experts lo ..
  hi - 1 beside the whole shared expert; the router, the bias and the top k
  stay `router_width` wide. What the absent experts would add is left out and
  that partial result goes on to the next layer;
- the expert layer is a LOOP over the held experts, each applied to every
  token and masked by that token's gate for it (0 where it was not chosen):
  dropless;
- a row of the batch at a time, attention a block of QUERY_BLOCK queries at
  a time, each layer and each block under `jax.checkpoint` (which changes no
  value: it lets the gradient of 8192 positions fit a chip); both loops are
  ROLLED (`lax.map` over the blocks, `lax.scan` over the held experts), so
  that a row's program is small: unrolled, the reference took over a minute
  to compile at the timed sizes, inside a run that may take six.

Parameters: {"embed" [vocab, d], "norm_f" [d], "head" [d, vocab], "layers":
[{"norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp" [d], "wq",
"wg" [d, heads hd], "wk", "wv" [d, kv hd], "wo" [heads hd, d], "q_norm",
"k_norm" [hd], and a dense layer's "w_in" [d, 2 w], "w_out" [w, d] or an
expert layer's "router" [d, E], "bias" [E], "experts_in" [held, d, 2 w],
"experts_out" [held, w, d], "shared_in" [d, 2 w], "shared_out" [w, d]}]}.
`w_in` holds [W_1 | W_3] side by side.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
TOKEN_BLOCK = 512
F32 = jnp.float32


def shape(cfg: dict) -> dict:
    """The configuration file's keys as the numbers used here."""
    return {"heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "window": cfg["sliding_window"], "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]), "k": cfg["num_experts_per_tok"],
            "scale": float(cfg["route_scale"]),
            "norm": bool(cfg["route_norm"]),
            "kinds": tuple(cfg["layer_types"]),
            "embed_scale": math.sqrt(cfg["hidden_size"])
            if cfg.get("mup_enabled") else 1.0}


def rms(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def tokenwise(f, *xs):
    """`f(*xs)` for a function that treats every token alone (each argument
    and each result `[s, ...]`), TOKEN_BLOCK tokens at a time in a rolled
    loop: the same values from products of 512 rows, which the chip's
    compiler takes a sixth of the time to compile at highest precision (a
    run of the cell compiles the reference inside its six minutes)."""
    s = xs[0].shape[0]
    tb = min(TOKEN_BLOCK, s)
    if s % tb or s == tb:
        return f(*xs)
    out = jax.lax.map(lambda blk: f(*blk), tuple(
        x.reshape(s // tb, tb, *x.shape[1:]) for x in xs))
    return jax.tree_util.tree_map(lambda o: o.reshape(s, *o.shape[2:]), out)


def rotate(x, pos, theta):
    """Rotate-half over the whole head: x [s, h, hd], pos [s]."""
    hd = x.shape[-1]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * freq[None]                 # [s, hd/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None]
    half = hd // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def attention(p, x, pos, kind, sh):
    """One sequence: x [s, d] (after N1), pos [s] -> [s, d]."""
    s = x.shape[0]
    heads, kv, hd = sh["heads"], sh["kv_heads"], sh["hd"]
    sliding = kind == "sliding_attention"

    def project(x, pos):
        n = x.shape[0]
        x = x.astype(F32)
        q = (x @ p["wq"].astype(F32)).reshape(n, heads, hd)
        k = (x @ p["wk"].astype(F32)).reshape(n, kv, hd)
        v = (x @ p["wv"].astype(F32)).reshape(n, kv, hd)
        q, k = rms(q, p["q_norm"], sh["eps"]), rms(k, p["k_norm"], sh["eps"])
        if sliding:
            q, k = rotate(q, pos, sh["theta"]), rotate(k, pos, sh["theta"])
        return q, k, v, jax.nn.sigmoid(x @ p["wg"].astype(F32))

    q, k, v, gate = tokenwise(project, x, pos)
    k, v = (jnp.repeat(t, heads // kv, axis=1) for t in (k, v))
    at = jnp.arange(s)
    block = jax.checkpoint(functools.partial(
        attend_block, window=sh["window"] if sliding else 0))
    qb = min(QUERY_BLOCK, s)
    if s % qb:
        raise ValueError(f"{s} positions are no whole blocks of {qb} queries")
    # a loop over the blocks of queries (rolled: one block's program)
    a = jax.lax.map(lambda blk: block(blk[0], blk[1], k, v),
                    (q.reshape(s // qb, qb, heads, hd),
                     at.reshape(s // qb, qb))).reshape(s, heads * hd)
    return tokenwise(lambda a, g: (a * g) @ p["wo"].astype(F32), a, gate)


def attend_block(q_blk, t_blk, k, v, window):
    """Queries q_blk [qb, h, hd] at positions t_blk [qb] over all the keys
    k, v [s, h, hd]: every s <= t, under `window` (0: none) t - window < s."""
    at = jnp.arange(k.shape[0])
    scores = jnp.einsum("qhd,khd->hqk", q_blk, k) / math.sqrt(q_blk.shape[-1])
    seen = at[None, :] <= t_blk[:, None]
    if window:
        seen &= at[None, :] > t_blk[:, None] - window
    scores = jnp.where(seen[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)


def gated_mlp(x, w_in, w_out):
    ab = x @ w_in.astype(F32)
    half = ab.shape[-1] // 2
    return (jax.nn.silu(ab[:, :half]) * ab[:, half:]) @ w_out.astype(F32)


# a token whose k-th and (k + 1)-th selection scores lie closer than this is
# UNDECIDED: a system that keeps its hidden state in bfloat16 (2^-8 of a
# value) may send it to the other expert; sigmoid scores lie in (0, 1)
UNDECIDED_MARGIN = 2.0 ** -8


def route(p, x, sh):
    """(gates [s, k], experts [s, k], how many tokens are undecided) of the
    tokens x [s, d]."""
    scores = jax.nn.sigmoid(x @ p["router"].astype(F32))        # [s, E]
    top, experts = jax.lax.top_k(scores + p["bias"].astype(F32), sh["k"] + 1)
    undecided = jnp.sum(top[:, -2] - top[:, -1] < UNDECIDED_MARGIN)
    experts = experts[:, :-1]
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if sh["norm"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return gates * sh["scale"], experts, undecided


def expert_layer(p, x, held, sh):
    """(the shared expert and this holder's routed part [s, d], the tokens
    routed to each of ALL the experts and, last, the undecided tokens
    [E + 1])."""
    lo, hi = held
    width = p["router"].shape[-1]

    def tokens(x):
        gates, experts, undecided = route(p, x, sh)

        def add_expert(y, held_expert):  # every token, masked by its gate
            e, w_in, w_out = held_expert
            g = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)  # [n]
            return y + g[:, None] * gated_mlp(x, w_in, w_out), None

        y, _ = jax.lax.scan(add_expert,
                            gated_mlp(x, p["shared_in"], p["shared_out"]),
                            (jnp.arange(lo, hi), p["experts_in"],
                             p["experts_out"]))
        counts = jnp.sum(experts[..., None] == jnp.arange(width), axis=(0, 1))
        # a row a token, so that `tokenwise` can hand the counts through
        return y, jnp.broadcast_to(jnp.append(counts, undecided) / x.shape[0],
                                   (x.shape[0], width + 1))

    y, shares = tokenwise(tokens, x)
    return y, jnp.rint(jnp.sum(shares, axis=0)).astype(jnp.int32)


def layer(p, h, pos, kind, held, sh):
    """One layer over one sequence: (h [s, d], routed counts [E] or None)."""
    eps = sh["eps"]
    a = attention(p, rms(h, p["norm_in"], eps), pos, kind, sh)
    h = h + rms(a, p["norm_post_attn"], eps)
    x = rms(h, p["norm_pre_mlp"], eps)
    if "router" in p:
        f, counts = expert_layer(p, x, held, sh)
    else:
        f, counts = tokenwise(
            lambda x: gated_mlp(x, p["w_in"], p["w_out"]), x), None
    return h + rms(f, p["norm_post_mlp"], eps), counts


def hidden(params, ids, pos, cfg: dict, held):
    """One sequence ids, pos [s] -> (h_L [s, d] before the final norm, the
    expert layers' routed counts [layers with experts, E])."""
    sh = shape(cfg)
    h = params["embed"].astype(F32)[ids] * sh["embed_scale"]
    counts = []
    for p, kind in zip(params["layers"], sh["kinds"]):
        h, c = jax.checkpoint(functools.partial(
            layer, kind=kind, held=held, sh=sh))(p, h, pos)
        if c is not None:
            counts.append(c)
    return h, counts


def logits(params, ids, pos, cfg: dict, held):
    """ids, pos [b, s] -> logits [b, s, vocab] f32."""
    with jax.default_matmul_precision("highest"):
        rows = []
        for i in range(ids.shape[0]):
            h, _ = hidden(params, ids[i], pos[i], cfg, held)
            rows.append(rms(h, params["norm_f"], float(cfg["rms_norm_eps"]))
                        @ params["head"].astype(F32))
        return jnp.stack(rows)


def next_token_loss(params, ids, pos, labels, cfg: dict, held):
    """The mean over every position of -log softmax(logits)[label]."""
    return loss_and_counts(params, ids, pos, labels, cfg, held)[0]


def loss_and_counts(params, ids, pos, labels, cfg: dict, held):
    """(the loss, the tokens the batch routed to each expert [expert
    layers, E], the batch's undecided tokens a layer [expert layers])."""
    with jax.default_matmul_precision("highest"):
        total, counts = 0.0, 0
        for i in range(ids.shape[0]):
            h, c = hidden(params, ids[i], pos[i], cfg, held)
            def token_loss(h, label):
                out = rms(h, params["norm_f"], float(cfg["rms_norm_eps"])) \
                    @ params["head"].astype(F32)
                return -jnp.take_along_axis(jax.nn.log_softmax(out, axis=-1),
                                            label[:, None], axis=-1)[:, 0]

            total = total + jnp.sum(tokenwise(
                token_loss, h, labels[i].astype(jnp.int32)))
            counts = counts + jnp.stack(c)
        return total / labels.size, counts[:, :-1], counts[:, -1]


def gradients(params, ids, pos, labels, cfg: dict, held):
    """d loss / d params, the tree of `params` (the bias's is zero: the
    selection is piecewise constant in it and the gates do not read it)."""
    return jax.grad(next_token_loss)(params, ids, pos, labels, cfg, held)


def bias_update(bias, counts, rate: float):
    """The selection bias after a step that routed counts[e] tokens to
    expert e (torchtitan's rule)."""
    c = counts.astype(F32)
    d = rate * jnp.sign(jnp.mean(c) - c)
    return bias + d - jnp.mean(d)
