"""One decode step of power retention (`ops/power_retention_ops.py`) for the
live slots as one pallas TPU kernel: per live slot and K/V head

    S <- g S + phi(k) v^T,   z <- g z + phi(k),
    y_h = S^T phi(q_h) / (z . phi(q_h) + eps)     for the group's query heads

with the state read from HBM once and written once, in place in the donated
slot arrays, and the read-out of the NEW state made from the tile while it is
in VMEM. What the XLA loop moves five times (a copy of the slot out of the
array, the update, the read-out's sums from the copy) moves twice here.

Layout (`row_block`, `block_rows`; `ops/power_retention_ops._row_pairs` lays
the rows so). S `[J, R, D]` f32 holds row (a, b) = sum of decayed k_a k_b v:
the values a of the key in blocks of 8 (an f32 tile's sublanes), and for
every a of block A the run b in [8 A, D), so that every run starts on a tile
and is whole tiles long: R = 8704 rows at D = 128 where the symmetric half
has 8256. The rows with b < a inside a diagonal block hold the mirror
products k_a k_b, like every other row, and the query side gives them weight
0 (weight 1 on the diagonal, 2 above it: the square of the symmetric
embedding's sqrt 2). The normaliser z lies as the whole `[J, D, D]` square of
decayed k_a k_b, a on the sublanes and b on the lanes, read with the same
weights.

The grid is (live slot, K/V head): the slot of a grid step is `order[i]`, a
scalar-prefetched compaction of the live slots (`partition.live_order`), and
the first grid bound is their count, known at run time, so a slot that is
not live costs nothing and its bytes are never touched. A grid step holds one
head's whole `[R, D]` state (4.46 MB) as its block; Pallas's own double
buffering brings the next head in and writes the last one back while this one
is computed. On the tile:

- `k (x) v` once a head as a `[D, D]` slab (k_b on the sublanes: the
  transpose of k broadcast over a slab), and q_h likewise a query head;
- a run a at a time, last to first: `new = g S + k_a (k (x) v)[b]`, k_a a
  row of the broadcast slab; written back; then for every query head the
  run's rows weighted by q_{h,b} (the diagonal block's rows by 0, 1/2, 1)
  and summed tile on tile into one `[8, D]` accumulator, which enters the
  head's running `[8, D]` sum times 2 q_{h,a}. One loop serves `_SPAN` = 32
  values of a (four blocks, whose runs differ in length): a run enters as
  the window of rows that ends where it ends and is as long as the span's
  longest run. What a window holds before its own run belongs to runs that
  come later; their weight is 0 and what is written over them is written
  again in their turn. Four loop bodies instead of sixteen: the kernel is
  bound by its DMA (the arithmetic alone is 54 of 112 us a live slot and
  layer: PERF.md, PR 44), and what a long jaxpr costs is every start's
  tracing.
- float32 on the vector unit throughout: a read-out of this state cancels
  to a fortieth of its terms' size (PERF.md, PR 43), so nothing here is
  rounded to bfloat16 and nothing goes through the matrix unit.
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
# a grid step holds a head's state as its input and its output block, twice
# each (the pipeline's two buffers), and the slabs of k, k (x) v and q
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024
# the values a of the key that one loop of the kernel serves (four blocks)
_SPAN = 32


def row_block(head_dim: int) -> int:
    """How many values a of the key share their run of b: 8 (a tile's
    sublanes) where a head is whole 128-lane slabs, 1 elsewhere (the
    symmetric half exactly, `np.triu_indices` order)."""
    return 1 if head_dim % LANES else SUBLANES


def block_rows(head_dim: int) -> list[int]:
    """The rows of each block of `row_block` values of a: block A holds, for
    each of its a, the run b in [A * row_block, D)."""
    blk = row_block(head_dim)       # 1, or 8 dividing D
    return [blk * (head_dim - lo) for lo in range(0, head_dim, blk)]


def step_supported(head_dim: int, group: int) -> bool:
    """Whether the kernel takes a state of this width: heads of whole
    128-lane slabs whose state fits a grid step's blocks."""
    if head_dim % LANES:
        return False
    block = sum(block_rows(head_dim)) * head_dim * 4
    slabs = (3 + group) * head_dim * head_dim * 4
    return 4 * block + 6 * slabs <= _VMEM_LIMIT_BYTES


def _kernel(order_ref, q_ref, kvg_ref, s_ref, z_ref, y_ref, s_out, z_out,
            kb_ref, kv_ref, w_ref, *, d: int, group: int, eps: float):
    """One (live slot, K/V head). q_ref `[1, 1, Gp, d]`: the group's query
    heads; kvg_ref `[1, 1, 8, d]`: rows k, v and the gate over the lanes;
    s_ref / s_out `[1, 1, R, d]`, z_ref / z_out `[1, 1, d, d]`: the state,
    in and out one buffer; y_ref `[1, 1, Gp, d]`. Scratch: kb `[d, d]` (k_b
    over the lanes), kv `[d, d]` (k (x) v), w `[group, d, d]` (q_{h,b} over
    the lanes)."""
    del order_ref       # the index maps read it
    f32 = jnp.float32
    kvg = kvg_ref[0, 0]
    k_row, v_row, gate = kvg[0:1], kvg[1:2], kvg[2:3]
    q = q_ref[0, 0]

    def over_lanes(row):    # [1, d] -> [d, d]: entry b of `row` on sublane b
        return jnp.broadcast_to(row, (d, d)).T

    kb = over_lanes(k_row)
    kb_ref[...] = kb
    kv_ref[...] = kb * v_row
    row = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
    weight = jnp.where(row < col, 2.0, jnp.where(row == col, 1.0, 0.0))
    z_new = z_ref[0, 0] * gate + kb * k_row
    z_out[0, 0] = z_new
    weighted = z_new * weight
    den = []
    for h in range(group):
        w = over_lanes(q[h:h + 1])
        w_ref[h] = w
        den.append(jnp.sum(weighted * w * q[h:h + 1], keepdims=True))

    # The runs, last to first, `_SPAN` values of a to one loop: a run enters
    # as the window of m rows that ENDS where it ends, m the rows of the
    # span's longest run (its first block's), so that one loop body serves
    # four blocks. The rows a window holds before its own run are those of
    # runs yet to come (smaller a): what is written over them is written
    # again, from `s_ref`, when their turn comes, and the weights below
    # give them nothing (b < a).
    ys = jnp.zeros((group, SUBLANES, d), f32)
    for first_a in reversed(range(0, d, _SPAN)):
        m = d - first_a
        b_of = jax.lax.broadcasted_iota(jnp.int32, (m, d), 0) + first_a

        def run(t, ys, first_a=first_a, m=m, b_of=b_of):
            a = first_a + _SPAN - 1 - t
            block = a // SUBLANES
            n = d - SUBLANES * block        # the rows of a's own run
            # where it ends: the blocks before its own (8 (d - 8 A') rows
            # each, summed over A' < block), then its block's runs up to it
            end = SUBLANES * block * (d - SUBLANES // 2 * (block - 1)) \
                + (a % SUBLANES + 1) * n
            rows = pl.ds(pl.multiple_of(end - m, SUBLANES), m)
            new = s_ref[0, 0, rows, :] * gate \
                + kb_ref[pl.ds(a, 1), :] * kv_ref[first_a:, :]
            s_out[0, 0, rows, :] = new
            # every query head at once: the rows weighted by q_{h,b}, b < a
            # nothing, b = a once, b > a twice (the 2 is on q_{h,a} below),
            # summed tile on tile
            counted = new * jnp.where(b_of < a, 0.0,
                                      jnp.where(b_of == a, 0.5, 1.0))
            acc = jnp.sum((counted * w_ref[:, first_a:, :]).reshape(
                group, m // SUBLANES, SUBLANES, d), axis=1)
            return ys + 2.0 * w_ref[:, pl.ds(a, 1), :] * acc

        ys = jax.lax.fori_loop(0, _SPAN, run, ys)
    for h in range(group):
        y_ref[0, 0, h:h + 1, :] = jnp.sum(ys[h], axis=0, keepdims=True) \
            / (den[h] + eps)


@functools.partial(jax.jit, static_argnums=(7, 8))
def _call(state, total, q, k, v, gate, live, eps, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, kv, rows, d = state.shape
    group = q.shape[1] // kv
    padded = -(-group // SUBLANES) * SUBLANES
    f32 = jnp.float32
    order, count = live_order(live)
    q8 = jnp.pad(q.astype(f32).reshape(b, kv, group, d),
                 [(0, 0), (0, 0), (0, padded - group), (0, 0)])
    kvg = jnp.pad(jnp.stack(
        [k.astype(f32), v.astype(f32),
         jnp.broadcast_to(gate.astype(f32)[..., None], (b, kv, d))], axis=2),
        [(0, 0), (0, 0), (0, SUBLANES - 3), (0, 0)])

    def of_slot(*block):
        return pl.BlockSpec((1, 1) + block,
                            lambda i, j, order: (order[i], j, 0, 0))

    y, state, total = pl.pallas_call(
        functools.partial(_kernel, d=d, group=group, eps=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(count, kv),
            in_specs=[of_slot(padded, d), of_slot(SUBLANES, d),
                      of_slot(rows, d), of_slot(d, d)],
            out_specs=[of_slot(padded, d), of_slot(rows, d), of_slot(d, d)],
            scratch_shapes=[pltpu.VMEM((d, d), f32), pltpu.VMEM((d, d), f32),
                            pltpu.VMEM((group, d, d), f32)]),
        out_shape=[jax.ShapeDtypeStruct((b, kv, padded, d), f32),
                   jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct(total.shape, f32)],
        # operands: order, q8, kvg, state, total
        input_output_aliases={3: 1, 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ff_power_retention_step",
    )(order, q8, kvg, state, total)
    # a slot that is not live: no grid step wrote its y
    y = jnp.where(live[:, None, None, None], y[:, :, :group], 0.0)
    return y.reshape(b, kv * group, d), state, total


def retention_step(state, total, q, k, v, gate, live, eps: float):
    """state `[b, J, R, D]` and total `[b, J, D, D]` f32 as `row_block` lays
    them (donated: updated in place), q `[b, H, D]`, k and v `[b, J, D]`,
    gate `[b, J]` f32 (the step's decay g), live `[b]` bool -> (y `[b, H,
    D]` f32, 0 for a slot that is not live; the new state and total).
    Interpreted on the CPU."""
    return _call(state, total, q, k, v, gate, live, float(eps), _interpret())
