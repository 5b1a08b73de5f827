"""The Mamba-1 selective scan (Gu & Dao 2023) of one `[tokens, channels]`
block from a state, as one pallas TPU forward kernel: `ops/mamba_ops`'s
recurrence

    dt_t = softplus(dt_raw_t + b_dt)                              [C] f32
    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] u_t[c] B_t[n]
    y_t[c] = (sum_n S_t[n, c] C_t[n] + D[c] u_t[c]) * silu(z_t[c])

from `S_0` handed in, over the positions that exist (a row's first `length`:
behind them `dt` is 0, so the state neither decays nor takes anything in).
The decay differs for every channel and state index, so there is no matrix
form of a chunk (the Mamba-2 kernel's, kernels/ssd_scan.py): the recurrence
is stepped a position at a time on the VPU. What the XLA form streams through
HBM (the f32 `[tokens, N, C]` decays, inputs and states: 671 MB a tensor for
2048 tokens at N 16 and C 5120) here lives in vector registers; the kernel
reads `u`, `dt_raw` and `z` in the compute type and B and C, and writes `y`
in the compute type and the last state.

Layout. The state lies `[N, C]`: the channels on lanes, the N = 16 state
indices on two f32 sublane tiles, so `dt_t`, `u_t` (a row of the block: one
value a lane) are broadcast over sublanes, which is free, and `B_t`, `C_t`
(one value a sublane) over lanes. That second broadcast is not one the kernel
can make cheaply from a `[tokens, N]` block, so B and C are handed in already
laid over 128 lanes, `[tokens, N, 128]` f32 (64 KB a position, 16 MB a chunk
of 2048 for each), and the grid is (row, time block, channel tile), all
sequential, so that a time block's B and C are fetched once for all its
channel tiles (the block index does not change between them). The state of
every channel tile stays in one VMEM scratch `[tiles, N, L]` (327 KB at the
served widths) over a row's time blocks and is written out at each. A time
block wholly behind a row's length costs its grid steps and nothing else (its
`y` is zeros).

`exp(dt A)`, `softplus` and the state are float32 whatever the compute type.
Forward only: `ops/mamba_ops` gives the entry point a `custom_vjp` whose
backward differentiates the XLA form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from flexflow_tpu.kernels.flash_attention import _interpret

LANES = 128
# positions a time block and channels a tile (PERF.md, Findings PR 62, has
# the sweep on the chip)
_TIME_BLOCK = 256
_LANE_TILE = 512
# positions of the stepped loop that are unrolled into one body: the chain
# from state to state is one multiply-add a position, everything else of a
# position depends on no other and overlaps
_UNROLL = 8


def scan_tiles(channels: int, d_state: int, itemsize: int):
    """(positions a time block, channels a tile), or None where the kernel
    does not take the shape (the XLA form does): it wants whole channel
    tiles of `_LANE_TILE` lanes (every tiny model has fewer) and the state
    indices in whole f32 sublane tiles."""
    if channels % _LANE_TILE or d_state % 8 or itemsize not in (2, 4):
        return None
    return _TIME_BLOCK, _LANE_TILE


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _kernel(len_ref, u_ref, dtr_ref, z_ref, b_ref, c_ref, a_ref, d_ref,
            bias_ref, s0_ref, y_ref, s_ref, state_s, dt_s, dtu_s, y_s, *,
            unroll: int):
    """One (row, time block, channel tile). len_ref `[b]` in SMEM; u_ref,
    dtr_ref, z_ref, y_ref `[1, tq, L]`; b_ref, c_ref `[1, tq, N, 128]` f32;
    a_ref `[N, L]`, d_ref, bias_ref `[1, L]` f32; s0_ref, s_ref `[1, N, L]`
    f32; state_s `[tiles, N, L]`, dt_s, dtu_s, y_s `[tq, L]` f32."""
    row, j, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    tq, width = dt_s.shape
    length = len_ref[row]
    start = j * tq

    @pl.when(j == 0)
    def _():
        state_s[i] = s0_ref[0]

    @pl.when(start < length)
    def _():
        u = u_ref[0].astype(jnp.float32)
        dt = _softplus(dtr_ref[0].astype(jnp.float32) + bias_ref[...])
        at = start + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
        dt = jnp.where(at < length, dt, 0.0)
        dt_s[...] = dt
        dtu_s[...] = dt * u
        slabs = [slice(k, k + LANES) for k in range(0, width, LANES)]
        a = [a_ref[:, lanes] for lanes in slabs]

        sublane = jax.lax.broadcasted_iota(jnp.int32, (unroll, LANES), 0)

        def steps(g, state):
            """`unroll` positions from `g * unroll` on, straight-line."""
            base = pl.multiple_of(g * unroll, unroll)
            state = list(state)
            for k, lanes in enumerate(slabs):
                dt_g = dt_s[pl.ds(base, unroll), lanes]         # [unroll, 128]
                dtu_g = dtu_s[pl.ds(base, unroll), lanes]
                y_g = jnp.zeros((unroll, LANES), jnp.float32)
                for r in range(unroll):
                    s = jnp.exp(dt_g[r:r + 1] * a[k]) * state[k] \
                        + dtu_g[r:r + 1] * b_ref[0, base + r]
                    y_g = jnp.where(
                        sublane == r,
                        jnp.sum(s * c_ref[0, base + r], axis=0, keepdims=True),
                        y_g)
                    state[k] = s
                y_s[pl.ds(base, unroll), lanes] = y_g
            return tuple(state)

        state = jax.lax.fori_loop(
            0, tq // unroll, steps,
            tuple(state_s[i, :, lanes] for lanes in slabs))
        for k, lanes in enumerate(slabs):
            state_s[i, :, lanes] = state[k]
        y = (y_s[...] + d_ref[...] * u) \
            * jax.nn.silu(z_ref[0].astype(jnp.float32))
        y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(start >= length)
    def _():
        y_ref[0] = jnp.zeros(y_ref.shape[1:], y_ref.dtype)

    s_ref[0] = state_s[i]


@functools.partial(jax.jit, static_argnums=(10, 11, 12, 13))
def _call(u, dt_raw, z, bm, cm, a, d_skip, dt_bias, s0, lengths, tq, width,
          unroll, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, length, channels = u.shape
    n = a.shape[0]
    pad = -length % tq
    if pad:     # behind every row's length: nothing is stepped there
        u, dt_raw, z, bm, cm = (
            jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
            for t in (u, dt_raw, z, bm, cm))
    full = length + pad
    tiles = channels // width
    f32 = jnp.float32

    def over_lanes(t):      # [b, L, N] -> [b, L, N, 128]: a value a sublane
        return jnp.broadcast_to(t.astype(f32)[..., None], t.shape + (LANES,))

    block = pl.BlockSpec((1, tq, width), lambda r, j, i, lens: (r, j, i))
    coeff = pl.BlockSpec((1, tq, n, LANES), lambda r, j, i, lens: (r, j, 0, 0))
    lane_row = pl.BlockSpec((1, width), lambda r, j, i, lens: (0, i))
    state = pl.BlockSpec((1, n, width), lambda r, j, i, lens: (r, 0, i))
    y, last = pl.pallas_call(
        functools.partial(_kernel, unroll=unroll),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, full // tq, tiles),
            in_specs=[block, block, block, coeff, coeff,
                      pl.BlockSpec((n, width), lambda r, j, i, lens: (0, i)),
                      lane_row, lane_row, state],
            out_specs=[block, state],
            scratch_shapes=[pltpu.VMEM((tiles, n, width), f32),
                            pltpu.VMEM((tq, width), f32),
                            pltpu.VMEM((tq, width), f32),
                            pltpu.VMEM((tq, width), f32)]),
        out_shape=[jax.ShapeDtypeStruct((b, full, channels), u.dtype),
                   jax.ShapeDtypeStruct((b, n, channels), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="ff_selective_scan",
    )(lengths.astype(jnp.int32), u, dt_raw, z, over_lanes(bm), over_lanes(cm),
      a.astype(f32), d_skip.astype(f32)[None], dt_bias.astype(f32)[None],
      s0.astype(f32))
    return y[:, :length], last


def selective_scan(u, dt_raw, z, bm, cm, a, d_skip, dt_bias, s0, lengths,
                   tq: int, width: int):
    """u, dt_raw, z `[b, L, C]` in the compute type (the conv's activated
    output, `dt`'s projection before its bias, the gate), bm, cm `[b, L, N]`,
    a `[N, C]` f32 (< 0), d_skip, dt_bias `[C]`, s0 `[b, N, C]` f32, lengths
    `[b]` int (a row's positions that exist: its first `lengths[r]`), at the
    tile `(tq, width)` of `scan_tiles` -> (y `[b, L, C]` in u's type: the
    read-out, the skip and the gate; the state after a row's last real
    position `[b, N, C]` f32). Interpreted on the CPU; the layers of a
    program that call it at one shape trace its body once."""
    return _call(u, dt_raw, z, bm, cm, a, d_skip, dt_bias, s0, lengths,
                 int(tq), int(width), _UNROLL, _interpret())
