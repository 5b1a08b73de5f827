"""The chunked delta-rule scan of Kimi Delta Attention as one pallas TPU
forward kernel: `ops/kda_ops.kda_chunk_scan`'s recurrence

    S' = diag(exp(g_t)) S_{t-1},  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T,
    o_t = S_t^T q_t,   S_0 = 0

by tiles of T = 128 positions, each `T / C` chunks of the algorithm's own C
steps (`kda_ops.chunk_steps`, 16 at the published decay bound), and on the
same tile the layer's neighbours of the scan: q and k made unit vectors a
head before it, `RMS(o; w_norm) * sigmoid(z)` a head after it. What the XLA
form streams through HBM (the running sums G, three decayed copies of q and
k, the `[C, C]` pair products A and P, the triangular inverse T, W, U,
`k_end`, the per-chunk outputs; head-major copies of all four operands and
of the result; o in f32 through the norm's two passes) here lives on a tile
in VMEM and is never written: the kernel reads q, k, v, g, beta and z and
writes the gated result in the compute type and the last state.

Layout. q, k, v, z (compute type) and g (f32) are read where they lie, `[b,
L, H * D]` (a head of D = 128 is one 128-lane slab; q, k and v may be three
column ranges of one `[q' | k' | v']`), beta as `[b, H / hs, L, hs]` f32 so
that a head's column lies on the tile's rows. The grid is (row, block of
`hs` heads, tile along L), the last sequential; the block's `[hs, D, D]` f32
state is the output block that stays resident over a row's tiles. Inside a
grid step every value is `[hs, ...]`, the block's heads a leading axis, and
every product a batch of `hs` products: each product of one head waits for
the one before it (the solve's six in a row, then the chain state -> `v_new`
-> state of `T / C` chunks), and the scheduler fills the wait with the next
head's only where they stand side by side. (As straight-line code head after
head the same arithmetic took 9.4 ms a layer at the served shape; stage by
stage over the heads 5.9, at 7170 equations of jaxpr and 4 s more of every
start; with the heads as a batch axis 6.1 at 1508.)

A tile of one head:

- the chunk-wise sums of g as ONE product with a constant `[3 T, T]` matrix
  of 0 / +-1 (`_sum_matrix`): `fall` = G_t - m, m = G at the chunk's middle
  row, broadcast over the chunk, and d = G_C - m likewise. Every decay is a
  product of exp(+-fall), exp(m) and exp(d), each in (e^-40, e^40).
- the pair products of the whole tile as one `[2 T, D] x [D, T]` product
  masked to its C-wide diagonal blocks: with each factor referred to its OWN
  chunk's middle row both lie in (e^-40, e^40), so an entry outside the
  blocks is a finite number that the mask drops.
- (I + A)^-1 of all `T / C` blocks at once by block forward substitution by
  doubling, `X <- X - X (B_m X)` with B_m the entries of A that join two
  solved blocks of width m: exact, log2 C steps, no series.
- W and U as one block-diagonal `[T, T] x [T, 2 D]` product.
- the chain, a chunk at a time: `[w_c; q_c] S`, `v_new = u_c - w_c S`, `S <-
  diag(e^{G_C}) S + k_end_c^T v_new`; the chunk's decay lies on the state's
  rows, so it comes from the transposed tile of decays.
- the outputs' second term, `lower(P) v_new`, as one block-diagonal product
  when the tile's `v_new` are all there.

Products take their operands in the compute type and accumulate in f32, as
in the XLA form; G, the decays, the solve and the state are f32. Where the
compute type is bfloat16 a product of two f32 operands (the sums of g, the
solve) is made of bf16 passes on the matrix unit: the constant matrix times
g split in three (exact), the solve's products as hi x hi + hi x lo + lo x
hi (2^-16 of the result; the inverse enters W and U rounded to 2^-9).
Forward only: `ops/kda_ops` gives both entry points a `custom_vjp` whose
backward differentiates the XLA form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from flexflow_tpu.kernels.flash_attention import _interpret

LANES = 128
# the positions of a grid step: the width of the pair products and of the
# solve (PERF.md section 3, the KDA scan's tile rule; Findings PR 42 has the
# sweep). A length it does not divide is padded with steps of g = 0, beta =
# 0, k = 0.
TILE = 128
# heads of straight-line code a grid step: the most that divides the heads
_HEADS_A_STEP = (8, 4, 2, 1)
_VMEM_LIMIT_BYTES = 96 * 1024 * 1024


def _vmem_bytes(hs: int, d: int, itemsize: int) -> int:
    """What a grid step holds: q, k, v, z, g and the result tile and the
    state (blocks: twice, the pipeline's two buffers), and some forty
    `[TILE, d]` f32 values a head in flight."""
    blocks = TILE * hs * d * (5 * itemsize + 4) + hs * d * d * 4
    return 2 * blocks + 40 * hs * TILE * d * 4


def heads_a_step(heads: int, head_dim: int, itemsize: int, chunk: int):
    """The heads of a grid step, or None where the kernel does not take the
    shape (the XLA form does): it wants heads of whole 128-lane slabs,
    chunks of whole sublane tiles of the compute type (16 steps or more)
    that divide the tile, and a step that fits VMEM."""
    if head_dim % LANES or chunk % 16 or TILE % chunk:
        return None
    return next((hs for hs in _HEADS_A_STEP if heads % hs == 0
                 and 2 * _vmem_bytes(hs, head_dim, itemsize)
                 <= _VMEM_LIMIT_BYTES), None)


def _sum_matrix(chunk: int) -> np.ndarray:
    """`[3 TILE, TILE]` of 0 / +-1: times g `[TILE, D]` it gives, stacked, for
    row t of a chunk with middle row `mid`: G_t - G_mid, G_mid and G_C -
    G_mid (the last two the same on all rows of a chunk)."""
    t = np.arange(TILE)[:, None]
    i = np.arange(TILE)[None, :]
    same = t // chunk == i // chunk
    mid = (t // chunk) * chunk + (chunk - 1) // 2
    fall = (same & (i > mid) & (i <= t)) * 1.0 - (same & (i > t) & (i <= mid))
    return np.concatenate([fall, same & (i <= mid), same & (i > mid)],
                          axis=0).astype(np.float32)


def _dot(a, b, contract, batch=((), ())):
    """f32 accumulation; f32 operands as f32 (the chip's default would
    round them to bfloat16)."""
    return jax.lax.dot_general(
        a, b, (contract, batch), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None)


_HEADS = ((0,), (0,))   # the leading axis of both operands: a product a head


def _nn(a, b):
    """`[h, m, n] x [h, n, d] -> [h, m, d]` f32, a product a head."""
    return _dot(a, b, ((2,), (1,)), _HEADS)


def _nt(a, b):
    """`[h, m, d] x [h, n, d] -> [h, m, n]`."""
    return _dot(a, b, ((2,), (2,)), _HEADS)


def _tn(a, b):
    """`[h, n, m] x [h, n, d] -> [h, m, d]`."""
    return _dot(a, b, ((1,), (1,)), _HEADS)


def _split(x, parts: int):
    """f32 x as a sum of `parts` bfloat16 terms, largest first."""
    out = []
    for _ in range(parts - 1):
        hi = x.astype(jnp.bfloat16)
        out.append(hi)
        x = x - hi.astype(jnp.float32)
    return out + [x.astype(jnp.bfloat16)]


def _kernel(*refs, d: int, chunk: int, l2_eps, eps):
    """One (row, block of heads, tile) of the scan. With `l2_eps` q and k
    arrive as they leave the convolution and are made unit vectors a head
    here (q over sqrt(d)); with `eps` `refs` also hold z and the norm's
    weight, and the result leaves as `RMS(o; w) * sigmoid(z)` in the
    compute type. Every value is `[hs, ...]`, the block's heads leading."""
    sums_ref, q_ref, k_ref, v_ref, g_ref, beta_ref = refs[:6]
    if eps is None:
        out_ref, s_ref = refs[6:]
    else:
        z_ref, norm_ref, out_ref, s_ref = refs[6:]
    f32 = jnp.float32
    dot = q_ref.dtype
    hs = s_ref.shape[1]
    head_lanes = [slice(i * d, (i + 1) * d) for i in range(hs)]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, f32)

    row = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1)
    differ = row ^ col          # < chunk: the same chunk
    strict = (differ < chunk) & (row > col)
    lower = (differ < chunk) & (row >= col)
    eye = (row == col).astype(f32)

    def level(m):   # the pairs that join two solved blocks of width m
        return strict & (differ >= m) & (differ < 2 * m)

    def heads_of(wide):     # [TILE, hs d] -> [hs, TILE, d]: a head a slab
        return jnp.stack([wide[:, lanes] for lanes in head_lanes])

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=2, keepdims=True) + l2_eps)

    def product32(a, b):
        """a @ b a head for two f32 operands, to f32's grade: as they stand
        where the compute type is f32, else hi x hi + hi x lo + lo x hi of
        their bf16 halves."""
        if dot == f32:
            return _nn(a, b)
        (ah, al), (bh, bl) = a, b
        return _nn(ah, bh) + _nn(ah, bl) + _nn(al, bh)

    def halves(x):
        return x if dot == f32 else _split(x, 2)

    kf = heads_of(k_ref[0]).astype(f32)
    qf = heads_of(q_ref[0]).astype(f32)
    if l2_eps is not None:
        kf, qf = unit(kf), unit(qf) * d ** -0.5
    beta = jnp.stack([beta_ref[0, 0, :, i:i + 1] for i in range(hs)])  # [hs, TILE, 1]
    # the sums of every head's g at once: [3 TILE, TILE] x [TILE, hs d]
    g = g_ref[0]
    run = heads_of(jax.lax.dot(sums_ref[...], g, precision="highest")
                   if dot == f32 else
                   sum(jax.lax.dot(sums_ref[...], part,
                                   preferred_element_type=f32)
                       for part in _split(g, 3)))               # [hs, 3 TILE, d]
    fall = run[:, :TILE]
    up, down = jnp.exp(fall), jnp.exp(-fall)
    at_mid, to_end = jnp.exp(run[:, TILE:2 * TILE]), jnp.exp(run[:, 2 * TILE:])
    k_t, q_t, k_s = kf * up, qf * up, kf * down
    pairs = _nt(jnp.concatenate([k_t, q_t], axis=1).astype(dot),
                k_s.astype(dot))                                # [hs, 2 TILE, TILE]
    a = jnp.where(strict, pairs[:, :TILE] * beta, 0.0)
    inv = eye - jnp.where(level(1), a, 0.0)
    m = 2
    while m < chunk:
        terms = halves(inv)
        joined = product32(halves(jnp.where(level(m), a, 0.0)), terms)
        inv = inv - product32(terms, halves(joined))
        m *= 2
    wu = _nn(inv.astype(dot), jnp.concatenate(
        [(k_t * at_mid * beta).astype(dot),
         (heads_of(v_ref[0]).astype(f32) * beta).astype(dot)], axis=2))  # [hs, TILE, 2 d]
    w, u = wu[:, :, :d].astype(dot), wu[:, :, d:]
    q_g = (q_t * at_mid).astype(dot)
    k_end = (k_s * to_end).astype(dot)
    # a chunk's whole decay, on the state's rows: [hs, d, TILE]
    decay = jnp.swapaxes(at_mid * to_end, 1, 2)
    state = s_ref[0]
    v_new, through = [], []
    for c in range(0, TILE, chunk):
        steps = slice(c, c + chunk)
        both = _nn(jnp.concatenate([w[:, steps], q_g[:, steps]], axis=1),
                   state.astype(dot))                           # [hs, 2 C, d]
        v_new.append((u[:, steps] - both[:, :chunk]).astype(dot))
        through.append(both[:, chunk:])
        state = state * decay[:, :, c:c + 1] + _tn(k_end[:, steps], v_new[-1])
    s_ref[0] = state
    o = jnp.concatenate(through, axis=1) \
        + _nn(jnp.where(lower, pairs[:, TILE:], 0.0).astype(dot),
              jnp.concatenate(v_new, axis=1))                   # [hs, TILE, d]
    if eps is not None:
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=2, keepdims=True) + eps) \
            * norm_ref[...] * jax.nn.sigmoid(heads_of(z_ref[0]).astype(f32))
    for i, lanes in enumerate(head_lanes):
        out_ref[0, :, lanes] = o[i].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=tuple(range(6, 13)))
def _call(q, k, v, g, beta, gate, heads, chunk, hs, interpret, columns,
          l2_eps, eps):
    """q, k, v: `[b, L, >= column + H D]` each, read from its column of
    `columns` = (q, k, v) on (k None: three views of q's one `[q' | k' |
    v']`); g `[b, L, H D]` f32; beta `[b, L, H]` f32; `gate` None, or with
    `eps` (z `[b, L, H D]`, the norm's weight `[D]`) -> (o `[b, L, H D]`, f32
    or gated and normed in q's type; state `[b, H, D, D]` f32). Jitted, all
    but the arrays static: a model's layers call it at one shape, and a
    trace of the program traces and lowers the body once and not once a
    layer."""
    from jax.experimental.pallas import tpu as pltpu

    b, length, width = g.shape
    d = width // heads
    pad = -length % TILE

    def padded(t):  # steps with g = 0, beta = 0 and k = 0: the state stays
        return jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2)) \
            if pad else t

    full = length + pad
    blocks = heads // hs
    step = hs * d       # the lanes of a grid step: column blocks of `step`

    def wide(column=0):
        first = column // step
        return pl.BlockSpec((1, TILE, step), lambda i, j, c: (i, c, first + j))

    q, k, v = (padded(t) for t in ((q,) * 3 if k is None else (q, k, v)))
    operands = [jnp.asarray(_sum_matrix(chunk), q.dtype), q, k, v, padded(g),
                jnp.swapaxes(padded(beta).reshape(b, full, blocks, hs), 1, 2)]
    in_specs = [pl.BlockSpec((3 * TILE, TILE), lambda i, j, c: (0, 0)),
                wide(columns[0]), wide(columns[1]), wide(columns[2]), wide(),
                pl.BlockSpec((1, 1, TILE, hs), lambda i, j, c: (i, j, c, 0))]
    if eps is not None:
        z, norm = gate
        operands += [padded(z), norm.astype(jnp.float32)[None]]
        in_specs += [wide(), pl.BlockSpec((1, d), lambda i, j, c: (0, 0))]
    out, state = pl.pallas_call(
        functools.partial(_kernel, d=d, chunk=chunk, l2_eps=l2_eps, eps=eps),
        grid=(b, blocks, full // TILE),
        in_specs=in_specs,
        out_specs=[
            wide(),
            # the block's state: resident over the tiles of a row
            pl.BlockSpec((1, hs, d, d), lambda i, j, c: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, full, width),
                                 jnp.float32 if eps is None else q.dtype),
            jax.ShapeDtypeStruct((b, heads, d, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ff_kda_chunk_scan",
    )(*operands)
    return out[:, :length], state


def kda_chunk_scan(q, k, v, g, beta, chunk: int, hs: int):
    """The scan with `hs` heads a grid step (`heads_a_step`); q, k, v, g `[b,
    L, H, D]`: (o `[b, L, H, D]` f32, the last state). Interpreted on the
    CPU."""
    b, length, heads, d = q.shape
    out, state = _call(*(t.reshape(b, length, heads * d) for t in (q, k, v, g)),
                       beta, None, heads, chunk, hs, _interpret(), (0, 0, 0),
                       None, None)
    return out.reshape(q.shape), state


def kda_chunk_scan_gated(act, g, beta, z, norm, heads: int, chunk: int,
                         hs: int, l2_eps: float, eps: float):
    """The scan with its neighbours on the tile: `act` `[b, L, 3 H D]` holds
    `[q' | k' | v']` as they leave the convolution (q and k are made unit
    vectors a head here, q over sqrt(D)), and the result is `RMS(o; norm) *
    sigmoid(z)` a head (z `[b, L, H D]`), `[b, L, H D]` in act's type,
    beside the last state."""
    inner = g.shape[-1]
    return _call(act, None, None, g, beta, (z, norm), heads, chunk, hs,
                 _interpret(), (0, inner, 2 * inner), float(l2_eps),
                 float(eps))
