"""One decode step of the Mamba-2 recurrence (`ops/ssm_ops.py`, `mode ==
"decode"`) for the live slots as one pallas TPU kernel: per live slot and
head

    S <- exp(dt a) S + (dt u) (x) B,    y = S C         S [P, N] float32

with the state read from HBM once and written once, in place in the donated
slot array, and the read-out of the NEW state made from the tile while it is
in VMEM. The XLA form passes over all of the slots' state, a slot that is not
live multiplied by 1 and written back; here such a slot costs no grid step
and its bytes are never touched. The skip `D u`, the gate and the norm stay
with the op (a few KB a slot).

The grid is (live slot, block of heads), as `kernels/retention_step.py`
builds its own: the slot of a grid step is `order[i]`, a scalar-prefetched
compaction of the live slots (`partition.live_order`), and the first grid
bound is their count, known at run time. A grid step holds `[hs, P, N]` of a
slot's state as its block, input and output one buffer; Pallas's own double
buffering brings the next block in and writes the last one back while this
one is computed. On the tile, eight heads `[8, P, N]` (64 vregs at P 64, N
128) a turn of one short loop:

- a head's decay `exp(dt a)` is a scalar from SMEM (scalar-prefetched beside
  `order`); B and C of the turn's group are rows over the lanes (eight heads
  lie inside one group: `head_block` asks for groups of whole sublane
  tiles);
- `dt u` comes as it is made, `[8, P]` with P on the lanes, and is turned
  onto the sublanes for `(dt u) (x) B` (`[:, :, None]`: Mosaic's own
  relayout); `new = decay * S + (dt u) (x) B`, written back;
- `sum_n new * C` a row, `[8, P]` with P back on the lanes.
- float32 on the vector unit throughout, the operations of the XLA form in
  its order: nothing is rounded to bfloat16 and nothing goes through the
  matrix unit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from flexflow_tpu.kernels.flash_attention import _interpret
from flexflow_tpu.kernels.partition import live_order

LANES = 128
SUBLANES = 8
# a grid step holds a block of a slot's state as its input and its output,
# twice each (the pipeline's two buffers), and a few KB of small operands
_VMEM_LIMIT_BYTES = 16 * 1024 * 1024
# the state a grid step takes at most: half a slot at the served widths (64
# of 128 heads of [64, 128] f32). Measured on the chip (PERF.md, PR 50): the
# stream reads the same 630 GB/s at 16 live slots from 1 to 4 MiB a block;
# what a block costs is its first load and last store, which nothing
# overlaps: a whole slot a block is 6 us a layer slower at one live slot
_BLOCK_BYTES = 2 * 1024 * 1024
# the heads a turn of the kernel's loop takes: a tile's sublanes
_TURN = SUBLANES


def head_block(heads: int, head_dim: int, d_state: int, groups: int):
    """The heads `hs` a grid step takes, or None where the kernel does not
    take the state (the XLA form does): it wants a head's `[P, N]` in whole
    f32 tiles (N whole 128-lane slabs, P whole sublane tiles) and a B/C
    group's heads in whole sublane tiles (a turn's eight heads share one B
    and one C), and takes the largest part of the heads in whole turns whose
    block fits `_BLOCK_BYTES`."""
    if d_state % LANES or head_dim % SUBLANES or heads % groups \
            or (heads // groups) % _TURN:
        return None
    for parts in range(1, heads // _TURN + 1):
        hs = heads // parts
        if heads % parts == 0 and hs % _TURN == 0 \
                and hs * head_dim * d_state * 4 <= _BLOCK_BYTES:
            return hs
    return None


def _kernel(order_ref, decay_ref, du_ref, b_ref, c_ref, s_ref, y_ref, s_out,
            *, hs: int, per: int):
    """One (live slot, block of hs heads). decay_ref `[b, H]` f32 in SMEM;
    du_ref / y_ref `[1, hs, P]`: `dt u` and the read-out; b_ref, c_ref `[1,
    G, N]`; s_ref / s_out `[1, hs, P, N]`: the state, in and out one buffer.
    `per`: the heads of a B/C group."""
    slot = order_ref[pl.program_id(0)]
    first = pl.program_id(1) * hs

    def turn(i, carry):
        h0 = pl.multiple_of(i * _TURN, _TURN)
        rows = pl.ds(h0, _TURN)
        group = pl.ds((first + h0) // per, 1)
        decayed = jnp.stack([s_ref[0, h0 + k] * decay_ref[slot, first + h0 + k]
                             for k in range(_TURN)])
        new = decayed \
            + du_ref[0, rows, :][:, :, None] * b_ref[0, group, :][None]
        s_out[0, rows] = new
        y_ref[0, rows, :] = jnp.sum(new * c_ref[0, group, :][None], axis=-1)
        return carry

    jax.lax.fori_loop(0, hs // _TURN, turn, 0)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _call(state, decay, du, bm, cm, live, hs, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, heads, hd, n = state.shape
    groups = bm.shape[1]
    f32 = jnp.float32
    order, count = live_order(live)

    def of_slot(*block):        # a block of heads of the grid step's slot
        zeros = (0,) * (len(block) - 1)
        return pl.BlockSpec((1,) + block,
                            lambda i, j, order, decay: (order[i], j) + zeros)

    groups_rows = pl.BlockSpec(
        (1, groups, n), lambda i, j, order, decay: (order[i], 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_kernel, hs=hs, per=heads // groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(count, heads // hs),
            in_specs=[of_slot(hs, hd), groups_rows, groups_rows,
                      of_slot(hs, hd, n)],
            out_specs=[of_slot(hs, hd), of_slot(hs, hd, n)]),
        out_shape=[jax.ShapeDtypeStruct((b, heads, hd), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operands: order, decay, du, bm, cm, state
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ff_mamba2_step",
    )(order, decay, du, bm, cm, state)
    # a slot that is not live: no grid step wrote its y
    return jnp.where(live[:, None, None], y, 0.0), state


def mamba2_step(state, decay, du, bm, cm, live, hs: int):
    """All float32: state `[b, H, P, N]` (donated: updated in place), decay
    `[b, H]` (the step's `exp(dt a)`), du `[b, H, P]` (`dt u`), bm and cm `[b,
    G, N]`; live `[b]` bool, `hs` as `head_block` says -> (y `[b, H, P]`,
    the read-out `S C` of the new state, 0 for a slot that is not live; the
    new state, a slot that is not live keeping its bytes). Interpreted on the
    CPU; the layers of a program that call it at one shape trace its body
    once."""
    return _call(state, decay, du, bm, cm, live, hs, _interpret())
