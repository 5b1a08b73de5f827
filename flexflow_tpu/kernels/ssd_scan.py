"""The Mamba-2 chunked scan (SSD, Dao & Gu 2024) as one pallas TPU forward
kernel: `ops/ssm_ops.ssd_scan`'s recurrence

    S_t = exp(dt_t a) S_{t-1} + dt_t u_t (x) B_t,   y_t = S_t C_t,   S_0 = 0

by tiles of `q` positions, and on the same tile the rest of the mixer up to
its out-projection, `RMS_G((y + D u) * silu(z); w_norm)`. Inside a tile the
recurrence is one masked matrix product a head, `(C B^T * decay * dt) @ u`;
between tiles the state is carried. What the XLA form streams through HBM
(the f32 `[rows, heads, q, q]` decay mask, `C B^T`, their product with `dt`,
relaid for the product with `u`; then `y` in f32 through the skip, the gate
and the norm's two passes) here lives on a tile in VMEM and is never
written: the kernel reads `u`, `z`, `B`, `C` and the per-head cumulative
sums, and writes the normed result in the compute type and the last state.

Layout. The grid is (row, group, tile, sub-block), the last two sequential. A
group is the heads that share one B and one C, which is also one group of
the gated norm; a grid step computes `hs` of its heads (all, or a sub-block
of 1024 lanes where the group is wider: granite's one group is 8192) as `hs
* P` lanes of the lane-dense `[b, L, H * P]` operands (nothing is relaid to
a per-head `[.., P]` minor axis, for one group or for eight). The group's
state lies TRANSPOSED, `[sub-blocks, N, hs * P]` f32, in the output block
that stays resident over a row's tiles, and the group's tile of the result
stays resident over its sub-blocks, to be normed and written at the last. So
the two products that touch the state are whole-width, `C [q, N] @ S^T` and
`B^T [N, q] @ (w * u)`, and `C B^T` is computed once a grid step, not once a
head. Only the masked product is per head: its `[q, q]` operand is built
from the head's cumulative sum in both orientations (`[q, 1]` down the rows
and `[1, q]` along the lanes, both handed in, so the kernel transposes
nothing), and multiplies the 128-lane slab of `u` that holds the head; where
a slab holds several heads (P = 64: two) each takes its own lanes of its
product, which costs the MXU nothing (a 64-wide result occupies the array as
a 128-wide one does). Every step of the schedule inside a grid step is
static, 16 heads of straight-line code at the served widths.

Operands of the products are in `u`'s dtype with f32 accumulation; decays,
`dt`, the cumulative sums, the state, the gate and the norm's statistics are
f32, as in the XLA form. Forward only: `ops/ssm_ops` gives both entry points
a `custom_vjp` whose backward differentiates the XLA form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from flexflow_tpu.kernels.flash_attention import _interpret, _nn, _nt, _tn

LANES = 128
# the positions of a tile: 128 where the published chunk is 128 or less, 256
# above (PERF.md section 3, the scan's tile rule; Findings PR 39 has the
# sweep). A length that no tile divides is padded with steps of dt = 0.
_TILES = (256, 128)
# lanes (heads x head_dim) of a group that a grid step computes: a group
# wider than this (granite's one group: 8192) goes through its steps in
# sub-blocks, so that the straight-line code of a step is 16 heads' and not
# 128 (which took 10 s to trace and lower and 40 s to compile)
_LANES_A_STEP = 1024
# what a group may hold in VMEM (a v5e core has 128 MiB; Mosaic's own default
# of 16 MiB is under one group of granite's: 8192 lanes of state in f32)
_VMEM_LIMIT_BYTES = 96 * 1024 * 1024


def _vmem_bytes(q: int, lanes: int, step: int, n: int, itemsize: int) -> int:
    """What a group of `lanes` holds over a tile: the state and the result
    (blocks: twice, the pipeline's two buffers), the gated values, and a
    step's u and z."""
    return 2 * (n * lanes * 4 + q * lanes * itemsize) + q * lanes * 4 \
        + 4 * q * step * itemsize


def scan_tiles(seq: int, heads: int, head_dim: int, d_state: int, groups: int,
               itemsize: int, chunk: int):
    """(q, hs): the tile along the sequence and the heads a grid step
    computes (a B/C group's, which is also a group of the gated norm, or a
    sub-block of one), or None where the kernel does not take the shape (the
    XLA form does): it wants whole lanes of state and of heads, and a group
    that fits VMEM. `chunk`, the configuration's published chunk size,
    bounds the tile from above down to 128; it is a tile bound and not
    mathematics."""
    del seq     # any length: one that no tile divides is padded
    hb = heads // groups
    slab_heads = max(1, LANES // head_dim)
    if d_state % LANES or (hb * head_dim) % LANES \
            or not (LANES % head_dim == 0 or head_dim % LANES == 0):
        return None
    hs = next(h for h in range(hb, 0, -1)
              if hb % h == 0 and h % slab_heads == 0
              and (h * head_dim <= _LANES_A_STEP or h == slab_heads))
    q = next((t for t in _TILES if t <= max(int(chunk), _TILES[-1])
              and 2 * _vmem_bytes(t, hb * head_dim, hs * head_dim, d_state,
                                  itemsize) <= _VMEM_LIMIT_BYTES), None)
    return None if q is None else (q, hs)


def _kernel(*refs, p: int, subs: int, eps):
    """One (row, group, tile, sub-block of the group's heads) of the scan;
    with `eps` also the skip, the gate and, at the group's last sub-block,
    its RMS norm (`refs` then hold z, D, the norm's weight, and as scratch
    the gated values of the group's tile `[subs, q, lanes]` and the sums of
    their squares `[q, 1]`, both f32)."""
    (u_ref, b_ref, c_ref, cs_row_ref, dt_row_ref, cs_col_ref, w_col_ref,
     dec_ref) = refs[:8]
    if eps is None:
        out_ref, s_ref = refs[8:]
    else:
        z_ref, d_ref, norm_ref, out_ref, s_ref, g_ref, squares_ref = refs[8:]
    q, width = u_ref.shape[1:]
    dot = u_ref.dtype
    sub = pl.program_id(3)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[0, sub] = jnp.zeros(s_ref.shape[2:], jnp.float32)

    bm, cm = b_ref[0], c_ref[0]                                 # [q, N]
    cb = _nt(cm, bm)                                            # [l, s] f32
    under = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    slab = max(p, LANES)
    per = slab // p             # heads a slab
    if per > 1:
        head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (q, slab), 1) // p
    slabs = [slice(k, k + slab) for k in range(0, width, slab)]
    squares = jnp.zeros((q, 1), jnp.float32)
    for k, lanes in enumerate(slabs):
        u_k = u_ref[0, :, lanes]                                # [q, slab]
        state = s_ref[0, sub, :, lanes]                         # [N, slab] f32
        y = e = w = None
        for i in range(per):
            h = k * per + i
            cs_c = cs_col_ref[0, 0, :, h:h + 1]                 # [q, 1]
            seg = cs_c - cs_row_ref[0, 0, 0, h:h + 1, :]        # [l, s]
            m = cb * jnp.exp(jnp.where(under, seg, -jnp.inf)) \
                * dt_row_ref[0, 0, 0, h:h + 1, :]
            y_h = _nn(m.astype(dot), u_k)                       # [q, slab] f32
            e_h, w_h = jnp.exp(cs_c), w_col_ref[0, 0, :, h:h + 1]
            if i == 0:
                y, e, w = y_h, e_h, w_h
            else:       # each head its own lanes of the slab
                mine = head_of_lane == i
                y, e, w = (jnp.where(mine, new, old) for new, old in
                           ((y_h, y), (e_h, e), (w_h, w)))
        y = y + _nn(cm, state.astype(dot)) * e
        s_ref[0, sub, :, lanes] = state * dec_ref[0, 0, :, lanes] + _tn(
            bm, (u_k.astype(jnp.float32) * w).astype(dot))
        if eps is None:
            out_ref[0, :, lanes] = y
            continue
        y = y + d_ref[:, lanes] * u_k.astype(jnp.float32)
        g = y * jax.nn.silu(z_ref[0, :, lanes].astype(jnp.float32))
        g_ref[sub, :, lanes] = g
        squares = squares + jnp.sum(g * g, axis=1, keepdims=True)
    if eps is None:
        return

    @pl.when(sub == 0)
    def _():
        squares_ref[...] = jnp.zeros_like(squares_ref)

    squares_ref[...] += squares

    @pl.when(sub == subs - 1)
    def _():    # the group's tile is whole: its norm, and out it goes
        scale = jax.lax.rsqrt(squares_ref[...] / (subs * width) + eps)
        for t in range(subs):
            for lanes in slabs:
                at = slice(t * width + lanes.start, t * width + lanes.stop)
                out_ref[0, :, at] = (g_ref[t, :, lanes] * scale
                                     * norm_ref[:, at]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _call(u, dt, a, bm, cm, q, hs, interpret, eps, z_column, gate):
    """u [b, L, H, P]; dt [b, L, H] f32; a [H] f32; bm, cm [b, L, G, N] ->
    (y [b, L, H, P] f32, state [b, H, P, N] f32); with `eps`, `gate` = (z
    `[b, L, >= z_column + H P]` read from column `z_column` on, D [H], the
    norm's weight [H P]) and the first result is `[b, L, H P]` in u's type,
    gated and normed. Jitted, all but the arrays static: a model's mixers
    call it at one shape, and a trace of the step traces and lowers the body
    once and not once a layer."""
    from jax.experimental.pallas import tpu as pltpu

    b, length, heads, p = u.shape
    groups, n = bm.shape[2:]
    pad = -length % q
    if pad:     # steps with dt = 0 and u = 0: the state stays, y is unused
        def padded(t):
            return jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))

        u, dt, bm, cm = (padded(t) for t in (u, dt, bm, cm))
    full = length + pad
    nc = full // q
    blocks = heads // hs            # sub-blocks of heads, all groups'
    subs = blocks // groups         # and of one group
    # per head and position, f32, 4 bytes x b x L x H each: the cumulative
    # sum of dt a inside a tile, dt, the weight of a position in its tile's
    # last state, and a tile's whole decay; by rows for the lanes of the
    # [q, q] tile and by columns for its rows
    dt = dt.reshape(b, nc, q, heads)
    cs = jnp.cumsum(dt * a, axis=2)
    w = jnp.exp(cs[:, :, -1:] - cs) * dt
    dec = jnp.repeat(jnp.exp(cs[:, :, -1]), p, axis=-1)        # [b, nc, H P]

    def by_rows(t):     # -> [b, nc, blocks, hs, q]
        return jnp.swapaxes(t, 2, 3).reshape(b, nc, blocks, hs, q)

    def by_columns(t):  # -> [b, blocks, L, hs]
        return jnp.swapaxes(t.reshape(b, full, blocks, hs), 1, 2)

    width = hs * p
    step = pl.BlockSpec((1, q, width), lambda i, j, c, s: (i, c, j * subs + s))
    group = pl.BlockSpec((1, q, n), lambda i, j, c, s: (i, c, j))
    rows = pl.BlockSpec((1, 1, 1, hs, q),
                        lambda i, j, c, s: (i, c, j * subs + s, 0, 0))
    columns = pl.BlockSpec((1, 1, q, hs),
                           lambda i, j, c, s: (i, j * subs + s, c, 0))
    operands = [u.reshape(b, full, heads * p), bm.reshape(b, full, groups * n),
                cm.reshape(b, full, groups * n), by_rows(cs), by_rows(dt),
                by_columns(cs), by_columns(w), dec.reshape(b, nc, 1, heads * p)]
    in_specs = [step, group, group, rows, rows, columns, columns,
                pl.BlockSpec((1, 1, 1, width),
                             lambda i, j, c, s: (i, c, 0, j * subs + s))]
    out_spec, scratch = step, []
    if eps is not None:
        z, d_skip, norm = gate
        if pad:
            z = padded(z)
        first = z_column // width       # z's first block of `width` columns
        operands += [z, jnp.repeat(d_skip, p)[None], norm.astype(jnp.float32)[None]]
        in_specs += [
            pl.BlockSpec((1, q, width),
                         lambda i, j, c, s: (i, c, first + j * subs + s)),
            pl.BlockSpec((1, width), lambda i, j, c, s: (0, j * subs + s)),
            pl.BlockSpec((1, subs * width), lambda i, j, c, s: (0, j))]
        # the group's tile: resident over its sub-blocks, written at the last
        out_spec = pl.BlockSpec((1, q, subs * width),
                                lambda i, j, c, s: (i, c, j))
        scratch = [pltpu.VMEM((subs, q, width), jnp.float32),
                   pltpu.VMEM((q, 1), jnp.float32)]
    out, state = pl.pallas_call(
        functools.partial(_kernel, p=p, subs=subs, eps=eps),
        grid=(b, groups, nc, subs),
        in_specs=in_specs,
        out_specs=[
            out_spec,
            # the group's state, transposed, a sub-block a leading index:
            # resident over the tiles of a row
            pl.BlockSpec((1, subs, n, width), lambda i, j, c, s: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, full, heads * p),
                                 jnp.float32 if eps is None else u.dtype),
            jax.ShapeDtypeStruct((b, blocks, n, width), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ff_ssd_chunk_scan",
    )(*operands)
    out = out[:, :length]
    # [b, blocks, N, hs, P] -> [b, H, P, N]
    state = jnp.transpose(state.reshape(b, blocks, n, hs, p), (0, 1, 3, 4, 2))
    return (out.reshape(b, length, heads, p) if eps is None else out,
            state.reshape(b, heads, p, n))


def ssd_chunk_scan(u, dt, a, bm, cm, q: int, hs: int):
    """The scan at tile `(q, hs)` of `scan_tiles`: (y f32, the last
    state). Interpreted on the CPU."""
    return _call(u, dt, a, bm, cm, q, hs, _interpret(), None, 0, None)


def ssd_chunk_scan_gated(u, dt, a, bm, cm, z, d_skip, norm, q: int, hs: int,
                         eps: float, z_column: int = 0):
    """The scan, and on its tile the skip `y + D u`, the gate `* silu(z)`
    and each group's RMS norm: (`[b, L, H P]` in u's type, the last state).
    `z` is read where it lies, from column `z_column` of the array handed
    in (a multiple of the lanes a grid step computes)."""
    return _call(u, dt, a, bm, cm, q, hs, _interpret(), float(eps),
                 int(z_column), (z, d_skip, norm))
