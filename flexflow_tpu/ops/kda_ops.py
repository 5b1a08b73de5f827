"""Linear attention over a matrix state: Kimi Delta Attention (Kimi Linear,
2025), the gated delta rule with a decay a CHANNEL, in the layout of the
`bailing_hybrid` decoders' linear-attention layers.

With x `[T, d]`, H heads of width D (keys, queries and values alike), and
conv() a causal depth-wise convolution of width `d_conv` along time:

    [q' | k' | v' | f | z | b] = x W_in        sizes 3 x H D | H D | H D | H
    [q' | k' | v'] = silu(conv([q' | k' | v']))
    q = q' / |q'| / sqrt(D);  k = k' / |k'|    a head; |.|^2 + 1e-6 under the root
    g = lower_bound * sigmoid(exp(A_log_h) * (f + dt_bias))    in (lower_bound, 0)
    beta = sigmoid(b)                          one value a head
    S' = diag(exp(g_t)) S_{t-1}                S: [D, D] a head, float32
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t
    out = (RMS(o_t; w_norm) * sigmoid(z)) W_out     the norm over a head's D

`|k| = 1` is what makes `I - beta k k^T` a contraction; `lower_bound` (the
published -5) is what sizes the chunked form's chunk (16 steps at -5), so
that its exponents stay in float32's range.

Three forms of one op, chosen by `params["mode"]`:

- None (training, evaluation): the whole sequence by chunks
  (`kda_mixer_scan`: `kda_chunk_scan` with the unit vectors before it and
  the gated head norm after). Equal to the recurrence at any length; a
  length that is no multiple of the chunk (or of the kernel's tile) is
  padded with steps that change nothing.
- "state_out" (serving prefill): the same, and the state is handed out in
  `ctx.new_state[layer.name] = {"state": [b, H, D, D] f32, "conv": [b,
  d_conv - 1, 3 H D]}`. Reports (ctx.add_stat) `kda_layers`, 1 a layer.
- "decode" (serving decode): one step of the recurrence on
  `ctx.state[layer.name]`, written back to `ctx.new_state`. Reports
  `linear_state_bytes`: the state the step's live slots read and wrote
  (both leaves, twice).

The second input, `valid` `[b, s]` (int, 1 = a real token), says which
positions exist: at the others g = 0 and beta = 0 (the state neither decays
nor takes anything in) and 0 enters the convolution. A right-padded prompt
wave therefore hands out each row's state after its LAST REAL token, and a
decode step advances only the slots that `valid` names.

The chunked form. In a chunk of C steps with G_t the running sum of g
(`G_0` before the first step = 0) and S the state before the chunk:

    A = strict_lower(beta_t (k_t * e^{G_t - G_s}) . k_s)     [C, C]
    T = (I + A)^-1
    W = T (beta K e^{G});  U = T (beta V)
    V' = U - W S
    O = (Q e^{G}) S + lower((Q e^{G_t - G_s}) K^T) V'
    S <- diag(e^{G_C}) S + (K e^{G_C - G})^T V'

G falls by up to |lower_bound| a step, so e^{-G} leaves float32 after 17
steps at -5: no exponent is ever formed but as a DIFFERENCE that the chunk
bounds. The chunk is the algorithm's own constant, `chunk_steps(lower_bound)`
(16 at -5: `C * |lower_bound| <= 80`; no caller chooses it): with m = G at the
chunk's middle row, the pair (t, s) is taken as (k_t e^{G_t - m}) . (k_s e^{m -
G_s}), both factors in (e^-40, e^40), so a product of the two, summed over a
head's channels, stays finite also for the s > t that the mask drops (it drops
a number and not a NaN; and e^-40 times a unit vector's entry is far from
float32's smallest normal, which e^-80 times a small entry, with the chunk's
first row as reference, is not). T comes from block forward substitution by
doubling (exact, log2 C products, no series).

Two forms of the chunked scan under the named scope `ff_kda_chunk_scan`,
chosen from the shapes (`scan_path`; a lowered layer reports the choice in a
`kda/scan_path` span): the Pallas kernel `kernels/kda_scan.py` where a head is
whole 128-lane slabs and a chunk whole sublane tiles (everything above on a
tile in VMEM, the operands read where they lie as `[b, L, H D]`; the layer's
entry `kda_mixer_scan` also makes q and k unit vectors and applies the gated
head norm there, so that no `[b, L, H, D]` value exists around it), plain XLA
(jax.numpy, `_chunk_scan`) elsewhere. In both, products take their operands in
the compute type and accumulate in float32; decays and the state are float32;
T is solved in float32 (in the kernel under bfloat16: products of three bf16
passes, 2^-16) and enters the products for W and U in the compute type, like
their other operand. Gradients: JAX's through the XLA form, which is also the
kernel's backward (a `custom_vjp` that recomputes it).
"""

from __future__ import annotations

import math
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

if TYPE_CHECKING:
    from flexflow_tpu.core.layer import Layer
from flexflow_tpu import telemetry as tel
from flexflow_tpu.core.tensor import TensorSpec
from flexflow_tpu.dtype import DataType
from flexflow_tpu.kernels import kda_scan
from flexflow_tpu.ops.norm_ops import rms_norm
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.ops.registry import LoweringCtx, register_op
from flexflow_tpu.ops.ssm_ops import conv_tail

SCAN_SCOPE = "ff_kda_chunk_scan"
# the most G may fall over a chunk: its two halves are taken from the middle
# row, so a factor carries at most e^+-40 and a product of two, summed over a
# head's channels, stays in float32
MAX_EXPONENT = 80.0
# and the most steps a chunk has however slow the decay: its `[C, C]` pairs
MAX_CHUNK = 64
L2_EPS = 1e-6
# the XLA form takes a long input through the scan in blocks of about this
# many tokens (lax.map over groups of rows), so that the per-chunk operands it
# streams through HBM (the decayed copies of q and k, the `[chunk, chunk]`
# pair products, W and U) stay a fraction of a prefill wave's. The kernel
# holds them on the chip and takes the wave whole.
KDA_TOKEN_BLOCK = 2048


def _sizes(p):
    heads, hd = p["heads"], p["head_dim"]
    return heads, hd, heads * hd


def chunk_steps(lower_bound: float) -> int:
    """The steps of a chunk: the largest power of two whose fall of G,
    `steps * |lower_bound|`, stays under MAX_EXPONENT (16 at -5), and no
    more than MAX_CHUNK."""
    steps = MAX_EXPONENT / abs(float(lower_bound))
    return max(1, min(MAX_CHUNK, 2 ** int(math.floor(math.log2(steps)))))


def _kda_infer(layer: Layer):
    x = layer.inputs[0].spec
    p = layer.params
    heads, hd, inner = _sizes(p)
    if p["lower_bound"] >= 0:
        raise ValueError(f"kda: lower_bound {p['lower_bound']} must be < 0")
    d = x.shape[-1]
    f32 = DataType.FLOAT
    layer.weight_specs = {
        "in_proj": TensorSpec((d, 5 * inner + heads), x.dtype),
        "conv_w": TensorSpec((p["d_conv"], 3 * inner), x.dtype),
        "A_log": TensorSpec((heads,), f32),
        "dt_bias": TensorSpec((inner,), f32),
        "norm": TensorSpec((hd,), x.dtype),
        "out_proj": TensorSpec((inner, d), x.dtype),
    }
    return [x]


def _unit_lower_inverse(a):
    """(I + a)^-1 for a strictly lower triangular `[.., C, C]` float32, C a
    power of two: block forward substitution by doubling. The inverses of
    the diagonal blocks of width m give those of width 2m,
    [[T1, 0], [B, T2]]^-1 = [[T1^-1, 0], [-T2^-1 B T1^-1, T2^-1]]."""
    c = a.shape[-1]
    lead = a.shape[:-2]
    inv = jnp.ones(lead + (c, 1, 1), jnp.float32)
    m = 1
    hi = jax.lax.Precision.HIGHEST
    while m < c:
        n = c // (2 * m)
        blocks = a.reshape(lead + (n, 2, m, n, 2, m))
        below = jnp.moveaxis(jnp.diagonal(
            blocks[..., :, 1, :, :, 0, :], axis1=-4, axis2=-2), -1, -3)
        pairs = inv.reshape(lead + (n, 2, m, m))
        t1, t2 = pairs[..., 0, :, :], pairs[..., 1, :, :]
        off = -jnp.einsum("...ab,...bc,...cd->...ad", t2, below, t1,
                          precision=hi)
        top = jnp.concatenate([t1, jnp.zeros_like(t1)], axis=-1)
        inv = jnp.concatenate(
            [top, jnp.concatenate([off, t2], axis=-1)], axis=-2)
        m *= 2
    return inv.reshape(lead + (c, c))


def _head_block(q, chunk: int):
    _b, _length, heads, hd = q.shape
    return kda_scan.heads_a_step(heads, hd, q.dtype.itemsize, chunk)


def scan_path(q, lower_bound: float) -> dict:
    """Which form the scan of q `[b, L, H, D]` (an array or its shape and
    type) takes: `{"path": "kernel", "tile": positions a grid step,
    "head_block": heads a grid step}` or `{"path": "xla", "tile": chunk}`."""
    chunk = chunk_steps(lower_bound)
    hs = _head_block(q, chunk)
    if hs is None:
        return {"path": "xla", "tile": chunk}
    return {"path": "kernel", "tile": kda_scan.TILE, "head_block": hs}


# The kernel forward, the XLA form's gradient (recomputed: no cell trains a
# KDA layer, so the backward's speed is nobody's). Static: the chunk, the
# heads of a grid step and, gated, the layer's heads and eps.
def _scan_forward(q, k, v, g, beta, chunk, hs):
    return kda_scan.kda_chunk_scan(q, k, v, g, beta, chunk, hs)


def _scan_backward(chunk, hs, operands, ct):
    return jax.vjp(lambda *t: _chunk_scan(*t, chunk), *operands)[1](ct)


_scan_kernel = jax.custom_vjp(_scan_forward, nondiff_argnums=(5, 6))
_scan_kernel.defvjp(lambda *args: (_scan_forward(*args), args[:5]),
                    _scan_backward)


def _mixer_xla(act, z, g, beta, norm, heads, chunk, eps):
    """`kda_mixer_scan` in plain XLA: the unit vectors, the scan and the
    gated head norm."""
    b, s, inner = g.shape
    hd = inner // heads
    dt = act.dtype
    q, k, v = _heads_of(act.astype(jnp.float32), (b, s), heads, hd)
    o, state = _chunk_scan(q.astype(dt), k.astype(dt), v.astype(dt),
                           g.reshape(b, s, heads, hd), beta, chunk)
    return _gated(o, z, norm, eps, dt), state


def _mixer_forward(act, z, g, beta, norm, heads, chunk, eps, hs):
    return kda_scan.kda_chunk_scan_gated(act, g, beta, z, norm, heads, chunk,
                                         hs, l2_eps=L2_EPS, eps=eps)


def _mixer_backward(heads, chunk, eps, hs, operands, ct):
    return jax.vjp(lambda *t: _mixer_xla(*t, heads, chunk, eps),
                   *operands)[1](ct)


_mixer_kernel = jax.custom_vjp(_mixer_forward, nondiff_argnums=(5, 6, 7, 8))
_mixer_kernel.defvjp(lambda *args: (_mixer_forward(*args), args[:5]),
                     _mixer_backward)


def kda_chunk_scan(q, k, v, g, beta, lower_bound: float):
    """The recurrence S' = diag(e^{g_t}) S_{t-1}, S_t = S' + beta_t k_t (v_t
    - S'^T k_t)^T, o_t = S_t^T q_t from S_0 = 0, by chunks of
    `chunk_steps(lower_bound)`. q, k, v `[b, L, H, D]`; g `[b, L, H, D]` f32
    in `[lower_bound, 0]`; beta `[b, L, H]` f32. Returns (o `[b, L, H, D]`
    f32, the state after step L `[b, H, D, D]` f32).

    One algorithm for every caller, the form chosen from the shapes
    (`scan_path`): the kernel where `kda_scan.heads_a_step` takes them
    (heads of whole 128-lane slabs), the XLA form elsewhere and for the
    kernel's backward."""
    chunk = chunk_steps(lower_bound)
    hs = _head_block(q, chunk)
    with jax.named_scope(SCAN_SCOPE):
        if hs is None:
            return _chunk_scan(q, k, v, g, beta, chunk)
        return _scan_kernel(q, k, v, g.astype(jnp.float32),
                            beta.astype(jnp.float32), chunk, hs)


def kda_mixer_scan(act, z, g, beta, norm, heads: int, lower_bound: float,
                   eps: float):
    """`kda_chunk_scan` with its neighbours: `act` `[b, L, 3 H D]` is `[q' |
    k' | v']` as they leave the convolution, q = q' / |q'| / sqrt(D) and k =
    k' / |k'| a head; z and g `[b, L, H D]`, g f32; beta `[b, L, H]` f32.
    Returns (`RMS(o; norm) * sigmoid(z)` a head, `[b, L, H D]` in act's
    type, the state after step L). The same choice of form as
    `kda_chunk_scan`; the kernel does all of it on the tile it holds, so no
    `[b, L, H, D]` value exists, in f32 or relaid."""
    chunk = chunk_steps(lower_bound)
    hs = kda_scan.heads_a_step(heads, g.shape[-1] // heads,
                               act.dtype.itemsize, chunk)
    with jax.named_scope(SCAN_SCOPE):
        if hs is None:
            return _mixer_xla(act, z, g, beta, norm, heads, chunk, eps)
        return _mixer_kernel(act, z, g.astype(jnp.float32),
                             beta.astype(jnp.float32), norm, heads, chunk,
                             float(eps), hs)


def _chunk_scan(q, k, v, g, beta, chunk):
    b, length, heads, hd = q.shape
    rows = max(1, KDA_TOKEN_BLOCK // length)
    if b > rows and b % rows == 0:
        out, state = jax.lax.map(
            lambda xs: _chunk_scan(*xs, chunk),
            tuple(t.reshape((b // rows, rows) + t.shape[1:])
                  for t in (q, k, v, g, beta)))
        return (out.reshape((b,) + out.shape[2:]),
                state.reshape((b,) + state.shape[2:]))
    dot = q.dtype
    c = min(chunk, 1 << max(0, (length - 1).bit_length()))
    pad = -length % c
    if pad:     # steps with g = 0, beta = 0 and k = 0: the state stays
        q, k, v, g, beta = (
            jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    nc = (length + pad) // c
    f32 = jnp.float32
    # head-major chunks: [b, nc, H, C, D]
    q, k, v, g = (jnp.transpose(t.reshape(b, nc, c, heads, hd), (0, 1, 3, 2, 4))
                  for t in (q, k, v, g))
    beta = jnp.transpose(beta.reshape(b, nc, c, heads), (0, 1, 3, 2))[..., None]
    kf, qf = k.astype(f32), q.astype(f32)
    run = jnp.cumsum(g.astype(f32), axis=-2)                # G, [.., C, D]
    fall = run - run[..., (c - 1) // 2, None, :]            # G_t - m
    k_t = (kf * jnp.exp(fall)).astype(dot)
    q_t = (qf * jnp.exp(fall)).astype(dot)
    k_s = (kf * jnp.exp(-fall)).astype(dot)                 # k_s e^{m - G_s}

    def pairs(left):    # [.., C, D] x [.., C, D] -> [.., C, C]
        return jnp.einsum("...td,...sd->...ts", left, k_s,
                          preferred_element_type=f32)

    row, col = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    a = jnp.where(row > col, pairs(k_t) * beta, 0.0)
    p = jnp.where(row >= col, pairs(q_t), 0.0).astype(dot)
    t = _unit_lower_inverse(a).astype(dot)
    decayed = jnp.exp(run)
    w = jnp.einsum("...ts,...sd->...td", t, (kf * decayed * beta).astype(dot),
                   preferred_element_type=f32).astype(dot)
    u = jnp.einsum("...ts,...sd->...td", t, (v.astype(f32) * beta).astype(dot),
                   preferred_element_type=f32)
    q_g = (qf * decayed).astype(dot)
    last = run[..., -1:, :]                                 # G_C, [.., 1, D]
    k_end = (kf * jnp.exp(last - run)).astype(dot)
    chunk_decay = jnp.exp(last[..., 0, :])                  # [b, nc, H, D]

    def carry(state, xs):
        w_c, u_c, q_c, p_c, k_c, decay_c = xs
        s_dot = state.astype(dot)
        v_new = u_c - jnp.einsum("bhtk,bhkv->bhtv", w_c, s_dot,
                                 preferred_element_type=f32)
        o_c = jnp.einsum("bhtk,bhkv->bhtv", q_c, s_dot,
                         preferred_element_type=f32) \
            + jnp.einsum("bhts,bhsv->bhtv", p_c, v_new.astype(dot),
                         preferred_element_type=f32)
        state = state * decay_c[..., None] \
            + jnp.einsum("bhtk,bhtv->bhkv", k_c, v_new.astype(dot),
                         preferred_element_type=f32)
        return state, o_c

    state, out = jax.lax.scan(
        carry, jnp.zeros((b, heads, hd, hd), f32),
        tuple(jnp.moveaxis(x, 1, 0)
              for x in (w, u, q_g, p, k_end, chunk_decay)))
    # [nc, b, H, C, D] -> [b, L, H, D]
    out = jnp.transpose(out, (1, 0, 3, 2, 4)).reshape(b, nc * c, heads, hd)
    return out[:, :length], state


def kda_step(state, q, k, v, g, beta):
    """One step of the recurrence: state `[b, H, D, D]` f32, q, k, v, g `[b,
    H, D]` f32, beta `[b, H]` f32 -> (o `[b, H, D]` f32, the new state)."""
    decayed = state * jnp.exp(g)[..., None]
    delta = v - jnp.einsum("bhkv,bhk->bhv", decayed, k,
                           precision=jax.lax.Precision.HIGHEST)
    state = decayed + (beta[..., None] * k)[..., None] * delta[:, :, None, :]
    return jnp.einsum("bhkv,bhk->bhv", state, q,
                      precision=jax.lax.Precision.HIGHEST), state


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + L2_EPS)


def _heads_of(act, lead, heads, hd):
    """q, k (unit vectors a head, q over sqrt(D)) and v out of the activated
    `[*lead, 3 H D]`, float32."""
    q, k, v = (act[..., i * heads * hd:(i + 1) * heads * hd]
               .reshape(lead + (heads, hd)) for i in range(3))
    return _unit(q) * hd ** -0.5, _unit(k), v


def _gated(o, z, norm, eps, dt):
    """RMS(o; norm) * sigmoid(z) with the norm over each head: o `[*lead, H,
    D]` f32, z `[*lead, H D]` -> `[*lead, H D]` in `dt`."""
    y = rms_norm(o, norm, eps) \
        * jax.nn.sigmoid(z.astype(jnp.float32)).reshape(o.shape)
    return y.reshape(z.shape).astype(dt)


def _kda_lower(layer: Layer, inputs, weights, ctx: LoweringCtx):
    x = inputs[0]
    p = layer.params
    heads, hd, inner = _sizes(p)
    kw = p["d_conv"]
    dt = x.dtype
    b, s, _d = x.shape
    valid = (inputs[1] > 0) if len(inputs) > 1 else jnp.ones((b, s), bool)
    f32 = jnp.float32
    conv_w = weights["conv_w"].astype(f32)

    decode = p.get("mode") == "decode"
    w_in = weights["in_proj"].astype(dt)
    # [q' | k' | v'], f, z, b
    parts = ((0, 3 * inner), (3 * inner, 4 * inner), (4 * inner, 5 * inner),
             (5 * inner, 5 * inner + heads))
    if decode:      # one product: the step reads the weights once
        proj = x @ w_in
        pq, pf, z, pb = (proj[..., a:b] for a, b in parts)
    else:   # one a consumer, each born in the layout its reader takes: the
        # scan kernel reads `[b, s, H D]` row-major, and a whole
        # in-projection that something reads so is relaid, all 5 H D + H of it
        pq, pf, z, pb = (x @ w_in[:, a:b] for a, b in parts)
    qkv = jnp.where(valid[..., None], pq, 0)
    rate = jnp.repeat(jnp.exp(weights["A_log"].astype(f32)), hd)
    g = p["lower_bound"] * jax.nn.sigmoid(
        rate * (pf.astype(f32) + weights["dt_bias"].astype(f32)))
    g = jnp.where(valid[..., None], g, 0.0)                 # [b, s, H D]
    beta = jnp.where(valid[..., None], jax.nn.sigmoid(pb.astype(f32)), 0.0)

    if decode:
        if s != 1:
            raise NotImplementedError(
                "kda decode takes one token a step (a verify pass over "
                "several would have to roll the state back)")
        st = ctx.state[layer.name]
        window = jnp.concatenate([st["conv"], qkv.astype(st["conv"].dtype)],
                                 axis=1)                    # [b, k, 3 H D]
        act = jax.nn.silu(jnp.einsum("bkc,kc->bc", window.astype(f32), conv_w))
        q, k, v = _heads_of(act, (b,), heads, hd)
        o, state = kda_step(st["state"], q, k, v,
                            g[:, 0].reshape(b, heads, hd), beta[:, 0])
        ctx.new_state[layer.name] = {
            "state": state,
            "conv": jnp.where(valid[:, :1, None], window[:, 1:], st["conv"])}
        ctx.add_stat("linear_state_bytes", jnp.sum(valid).astype(f32)
                     * (2.0 * sum(leaf[0].nbytes for leaf in st.values())))
        return [_gated(o[:, None], z, weights["norm"], p.get("eps", 1e-6), dt)
                @ weights["out_proj"].astype(dt)]

    # causal depthwise conv: out[t] = sum_j w[j] x[t - k + 1 + j]
    xp = jnp.pad(qkv, [(0, 0), (kw - 1, 0), (0, 0)])
    act = jax.nn.silu(sum(xp[:, j:j + s].astype(f32) * conv_w[j]
                          for j in range(kw))).astype(dt)
    # one span a lowered layer (trace time): the form its scan took
    with tel.span("kda/scan_path", cat="compile", layer=layer.name,
                  **scan_path(jax.ShapeDtypeStruct((b, s, heads, hd), dt),
                              p["lower_bound"])):
        y, state = kda_mixer_scan(act, z, g, beta, weights["norm"], heads,
                                  p["lower_bound"], p.get("eps", 1e-6))
    out = y @ weights["out_proj"].astype(dt)
    if p.get("mode") == "state_out":
        # the conv tail of [q' | k' | v'], taken now, with the layer's
        # output, so that no layer's projection stays live to the program's
        # end (ssm_ops.py)
        out, tail = jax.lax.optimization_barrier(
            (out, conv_tail(qkv, valid, kw)))
        ctx.hand_out_slot_state(layer.name, {"state": state, "conv": tail},
                                valid)
        ctx.add_stat("kda_layers", jnp.asarray(1, jnp.int32))
    return [out]


def recurrence_flops_per_token(heads: int, hd: int) -> int:
    """The recurrence's own products a token: the decay (one product a
    state entry) and S'^T k, the rank-one update and the read-out (two
    each), D x D a head."""
    return 7 * heads * hd * hd


def _kda_flops(layer: Layer):
    """Forward: the projections and the recurrence's own products. What
    the chunked form multiplies besides (the pairs inside a chunk, T, W and
    U) is the algorithm's price, not the layer's need, as `_mamba_flops`
    has it."""
    x = layer.inputs[0].spec
    heads, hd, inner = _sizes(layer.params)
    tokens = x.num_elements // x.shape[-1]
    proj = x.shape[-1] * (5 * inner + heads) + inner * x.shape[-1]
    return 2.0 * tokens * proj \
        + float(tokens) * recurrence_flops_per_token(heads, hd)


def _kda_serving_params(params: dict, kind: str) -> dict:
    return dict(params, mode="decode" if kind == "decode" else "state_out")


def _kda_slot_state(layer: Layer) -> dict:
    heads, hd, inner = _sizes(layer.params)
    return {"state": ((heads, hd, hd), jnp.float32),
            "conv": ((layer.params["d_conv"] - 1, 3 * inner),
                     layer.inputs[0].spec.dtype.jnp_dtype)}


register_op(OperatorType.KDA, _kda_infer, _kda_lower, _kda_flops,
            serving_params=_kda_serving_params, state_kind="recurrent",
            slot_state=_kda_slot_state)
