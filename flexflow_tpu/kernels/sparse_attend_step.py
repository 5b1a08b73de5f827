"""One decode step's attention over the keys an indexer kept
(`ops/attention_ops.py: _selected_cache_attention`, `s == 1`) for the live
slots as one pallas TPU kernel: per live slot, online softmax over the slot's
own context, fetched BY PAGE from the K and V pools where they lie, under the
indexer's membership mask.

The XLA form gathers `topk` rows of K and of V a slot, every slot, one row a
fetch, writes them out and reads them again. Here a slot that is not live
costs no grid step and none of its pages is touched, and a live slot's
context comes in by whole pages, each a copy of whole tiles (one row of a
tile is not a unit a copy can take: Mosaic refuses a slice of the pools'
tiled second-minor dimension under 8 rows). Attention over a slot's pages
with the kept set as a MASK is the softmax over the same keys. Measured on
the chip at the served shapes (PERF.md, PR 53): by page is under the XLA
form's time a layer at every live count, all 16 slots live included.

The grid is (live slot, key block), as `kernels/mamba2_step.py` builds its
own: the slot of a grid step is `order[i]`, the scalar-prefetched compaction
of the live slots (`partition.live_order`), the first grid bound is their
count and the second the blocks the longest live context reaches, both known
at run time. A key block is `block_pages` pages. The pools stay in HBM
(`memory_space` ANY): for the block a grid step will need NEXT (the slot's
next block, or the next live slot's first) it starts one async copy a page
and pool, `[page, heads * head_dim]` from `pool[page_table[slot, ..]]` (the
table scalar-prefetched), into the other half of a double buffer, only for
pages under the slot's `t + 1`; then it waits for its own block's copies (a
whole block's starts are a loop of known length, `_UNROLL` pages a turn, and
ONE wait a pool, a DMA semaphore counting bytes; a context's last block loops
over its pages). Blocks behind a
slot's context start nothing and compute nothing.

On the block: a K/V head's own span of the merged K/V axis (`head_dim`
lanes, whole 128-lane slabs: no relayout of K or V) against the query rows
that read it, `[rows, width] x [block, width]^T` with float32 scores, the
mask (the indexer's membership AND `position <= t`: the buffer's rows behind
`t` are not the slot's), float32 running maximum, sum and accumulator in
VMEM scratch, `probs` in the pools' type against V with a float32 sum. V's
pages that were not fetched are zeroed (0 x stale bits is not 0).

The kept keys may also be stated BY POSITION (`first <= s <= t`, no mask
operand: `ops/attention_ops.py: _bounded_cache_attention`, a layer of
windowed attention or of full attention beside one): a slot's page walk
then starts at the page that holds `first` and its blocks are counted from
there, so a window of `w` keys fetches `w / page` pages and a page at each
end whatever the context; with `ring` the slot's table is a ring whose entry
`n % entries` holds page `n` of the context (one remainder a block, none a
page). Under a mask `first` is 0 and the walk is the one above, instruction
for instruction.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from flexflow_tpu.kernels.flash_attention import _interpret
from flexflow_tpu.kernels.partition import live_order

LANES = 128
# the double buffer (two pools x two blocks of [block, row]: 4 MiB at 1024
# tokens of 512 bfloat16) and the pipeline's small operands
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024
# tokens a key block. Measured on the chip (PERF.md, PR 53: one layer alone
# at the served shapes, 2 live slots of 12 k context): 110 us a layer at
# 512, 89 at 1024, 87 at 2048; 24 / 21 / 24 us at three contexts under 2 k
_BLOCK_TOKENS = 1024
# the pages a turn of the loop that starts a whole block's copies (the same
# measurement at 1024: 102 us a layer at 1, 94 at 4, 91 at 8, 90 at 16, 89
# as straight-line code, which cost the cell 4 s of set-up: the body traced
# and lowered 128 times)
_UNROLL = 8
_NEG = float(jnp.finfo(jnp.float32).min)


def block_pages(page: int, head_dim: int, itemsize: int, pages_per_slot: int):
    """The pages a key block takes, or None where the kernel does not take
    the cache (the XLA form does): it wants a K/V head in whole 128-lane
    slabs (its span of the merged row is then whole tiles of a page) and a
    page in whole tiles of the pools' type (16 rows of bfloat16, 8 of
    float32: a page's copy then lands on whole tiles of the buffer), and a
    block's tokens in whole lanes (the mask's block) unless one block is the
    whole context."""
    if head_dim % LANES or itemsize not in (2, 4) or page % (32 // itemsize):
        return None
    pages = min(max(1, _BLOCK_TOKENS // page), pages_per_slot)
    if pages < pages_per_slot and pages * page % LANES:
        return None
    return pages


def softmax_block(q, k, v, keep, scale: float, m_s, l_s, acc_s, h):
    """One key block into head `h`'s running softmax (what this kernel and
    `kernels/sparse_attend_chunk.py` share): `q` `[rows, width]` against `k`,
    `v` `[block, width]` under `keep`, bool, `[1, block]` (one set for every
    row) or `[rows / r, block]` (the rows are `r` heads of the same queries,
    head-major: one set a query for all of them). Float32 scores of the
    operands' products, float32 running maximum `m_s[h]`, sum `l_s[h]`
    (`[rows, LANES]`, a value a row) and accumulator `acc_s[h]` `[rows,
    width]`, `probs` in `v`'s type against `v` with a float32 sum."""
    def under(x, other):
        if keep.shape[0] == 1:
            return jnp.where(keep, x, other)
        heads = (x.shape[0] // keep.shape[0],) + keep.shape
        return jnp.where(keep[None], x.reshape(heads), other).reshape(x.shape)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = under(s, _NEG)                                      # [rows, block]
    m_prev = m_s[h]                                         # [rows, LANES]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = under(jnp.exp(s - m_new[:, :1]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_s[h] = alpha * l_s[h] + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[h] = alpha[:, :1] * acc_s[h] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_s[h] = m_new


def _kernel(order_ref, count_ref, ctx_ref, lo_ref, table_ref, q_ref, *refs,
            scale: float, pages: int, page: int, per_slot: int, masked: bool,
            ring: bool):
    """One (live slot, key block). order_ref, ctx_ref, lo_ref `[b]` (the live
    slots first; a slot's `t + 1`; the first position its query may see: 0
    under a mask), count_ref `[1]` (the first grid bound: the
    interpreter has no `num_programs` of a bound known at run time) and
    table_ref `[b * per_slot]` in SMEM; q_ref `[1, spans, rows, width]`;
    with `masked` keep_ref `[1, 1, block]` int32; k_hbm, v_hbm `[pool pages,
    page, spans * width]` in HBM; o_ref as q_ref, float32; kbuf, vbuf `[2,
    block, spans * width]`; sems `[2, 2]` (buffer, pool); buf_ref `[1]` SMEM:
    the buffer the next grid step reads. A slot's blocks start at the page
    that holds `lo`; with `ring` the table is a ring and page `n` of the
    context lies at entry `n % per_slot`."""
    from jax.experimental.pallas import tpu as pltpu

    keep_ref = refs[0] if masked else None
    (k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, buf_ref, m_s, l_s,
     acc_s) = refs[1:] if masked else refs
    i, j = pl.program_id(0), pl.program_id(1)
    slot = order_ref[i]
    ctx = ctx_ref[slot]
    block = pages * page

    def first_page(slot):
        """The page of the context that holds the slot's first position."""
        return 0 if masked else lo_ref[slot] // page

    def reach(slot):
        """The pages from the slot's first to the last under its context."""
        return (ctx_ref[slot] + page - 1) // page - first_page(slot)

    blocks = (reach(slot) + pages - 1) // pages
    spans, rows, width = q_ref.shape[1:]

    def under(slot, blk):
        """The pages of (slot, blk) under the slot's context."""
        return jnp.clip(reach(slot) - blk * pages, 0, pages)

    def entry(slot, blk):
        """The table entry of (slot, blk)'s first page (one remainder a
        block on a ring, none a page)."""
        at = first_page(slot) + blk * pages
        return at % per_slot if ring else at

    def page_copy(slot, blk, buf, p, act):
        """`act` on the K and the V copy of page `p` of (slot, blk) into
        buffer `buf`."""
        at = entry(slot, blk) + p
        if ring:
            at = jnp.where(at >= per_slot, at - per_slot, at)
        at = table_ref[slot * per_slot + at]
        to = pl.ds(pl.multiple_of(p * page, page), page)
        act(pltpu.make_async_copy(k_hbm.at[at], kbuf.at[buf, to],
                                  sems.at[buf, 0]))
        act(pltpu.make_async_copy(v_hbm.at[at], vbuf.at[buf, to],
                                  sems.at[buf, 1]))

    def start(slot, blk, buf):
        """Starts the copies of (slot, blk)'s pages under the slot's
        context: a whole block's `_UNROLL` pages a turn of a loop of known
        length, a last block's in a loop over what it has."""
        n = under(slot, blk)
        turn = math.gcd(pages, _UNROLL)

        def some(at, carry):
            for p in range(turn):
                page_copy(slot, blk, buf, at * turn + p, lambda c: c.start())
            return carry

        def one(p, carry):
            page_copy(slot, blk, buf, p, lambda c: c.start())
            return carry

        @pl.when(n == pages)
        def _():
            jax.lax.fori_loop(0, pages // turn, some, 0)

        @pl.when(n < pages)
        def _():
            jax.lax.fori_loop(0, n, one, 0)

    def wait(slot, blk, buf):
        """Waits for what `start` started. A DMA semaphore counts bytes: a
        whole block's copies are one wait a pool, for the buffer's size."""
        n = under(slot, blk)

        @pl.when(n == pages)
        def _():
            for pool, sem in ((kbuf, 0), (vbuf, 1)):
                pltpu.make_async_copy(pool.at[buf], pool.at[buf],
                                      sems.at[buf, sem]).wait()

        @pl.when(n < pages)
        def _():
            def one(p, carry):
                page_copy(slot, blk, buf, p, lambda c: c.wait())
                return carry

            jax.lax.fori_loop(0, n, one, 0)

            # V's pages that were not fetched: 0 x stale bits is not 0
            def zero(p, carry):
                vbuf[buf, pl.ds(pl.multiple_of(p * page, page), page), :] = \
                    jnp.zeros((page, vbuf.shape[-1]), vbuf.dtype)
                return carry

            jax.lax.fori_loop(n, pages, zero, 0)

    @pl.when(j < blocks)
    def _():
        first = jnp.logical_and(i == 0, j == 0)
        buf = jnp.where(first, 0, buf_ref[0])

        @pl.when(first)
        def _():
            start(slot, j, buf)

        # the block the next grid step with work takes: this slot's next,
        # or the next live slot's first (a live slot has one at least)
        last = j + 1 >= blocks
        after = order_ref[jnp.minimum(i + 1, order_ref.shape[0] - 1)]

        @pl.when(jnp.logical_or(jnp.logical_not(last),
                                i + 1 < count_ref[0]))
        def _():
            start(jnp.where(last, after, slot), jnp.where(last, 0, j + 1),
                  1 - buf)

        buf_ref[0] = 1 - buf

        @pl.when(j == 0)
        def _():
            m_s[...] = jnp.full_like(m_s, _NEG)
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)

        wait(slot, j, buf)

        at = first_page(slot) * page + j * block \
            + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        keep = jnp.logical_and(keep_ref[0] != 0 if masked
                               else at >= lo_ref[slot], at < ctx)  # [1, block]
        for h in range(spans):
            lanes = slice(h * width, (h + 1) * width)
            softmax_block(q_ref[0, h], kbuf[buf, :, lanes],
                          vbuf[buf, :, lanes], keep, scale, m_s, l_s, acc_s, h)

        @pl.when(last)
        def _():
            for h in range(spans):
                o_ref[0, h] = acc_s[h] / l_s[h][:, :1]


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11))
def _call(q, keep, k_pool, v_pool, table, t, live, lo, scale, pages, ring,
          interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, spans, rows, width = q.shape
    page = k_pool.shape[1]
    per_slot = table.shape[1]
    block = pages * page
    masked = keep is not None
    order, count = live_order(live)
    # a ring holds any position; a table none past its last page
    ctx = (t + 1 if ring else jnp.minimum(t + 1, per_slot * page)
           ).astype(jnp.int32)
    lo = jnp.zeros_like(ctx) if masked else jnp.clip(lo, 0, ctx - 1
                                                      ).astype(jnp.int32)
    blocks = ((ctx + page - 1) // page - lo // page + pages - 1) // pages
    reach = jnp.max(jnp.where(live, blocks, 0))

    def of_slot(i, j, order, count, ctx, lo, table):
        return (order[i], 0, 0, 0)

    def keep_block(i, j, order, count, ctx, lo, table):
        # a block behind the context: the last one again (nothing is moved)
        slot = order[i]
        last = jnp.maximum((ctx[slot] + block - 1) // block - 1, 0)
        return (slot, 0, jnp.minimum(j, last))

    f32 = jnp.float32
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, pages=pages, page=page,
                          per_slot=per_slot, masked=masked, ring=ring),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(count, reach),
            in_specs=[pl.BlockSpec((1, spans, rows, width), of_slot)]
            + ([pl.BlockSpec((1, 1, block), keep_block)] if masked else [])
            + [pl.BlockSpec(memory_space=pl.ANY),
               pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, spans, rows, width), of_slot),
            scratch_shapes=[
                pltpu.VMEM((2, block, spans * width), k_pool.dtype),
                pltpu.VMEM((2, block, spans * width), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((spans, rows, LANES), f32),
                pltpu.VMEM((spans, rows, LANES), f32),
                pltpu.VMEM((spans, rows, width), f32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ff_sparse_attend_step",
    )(order, count.reshape(1), ctx, lo, table.reshape(-1).astype(jnp.int32),
      q, *([keep.astype(jnp.int32)] if masked else []), k_pool, v_pool)
    # a slot that is not live: no grid step wrote its rows
    return jnp.where(live[:, None, None, None], out, 0.0)


def sparse_attend_step(qg, keep, k_pool, v_pool, table, t, live, scale: float,
                       pages: int, first=None, ring: bool = False):
    """qg `[b, g, r, d]` (g K/V heads of d, whole 128-lane slabs; r query
    heads a group), keep `[b, 1, L]` bool (the indexer's kept keys over the
    slot's padded context) or None (the kept keys are those at positions
    `first <= s <= t`, `first` `[b]` int32: no mask operand, and the page walk
    starts at the page that holds `first`; with `ring` the table is a ring
    whose entry `n % pages_per_slot` holds page `n` of the context), k_pool
    and v_pool `[pool pages, page, g * d]` (read where they lie), table `[b,
    pages_per_slot]` int32, t `[b]` int32 (the step's position), live `[b]`
    bool, `pages` as `block_pages` says -> `[b, g, r, d]` float32: softmax(q
    k^T scale) v over the kept keys at positions <= t, 0 for a slot that is
    not live. Interpreted on the CPU; the layers of a program that call it at
    one shape trace its body once."""
    if (keep is None) == (first is None):
        raise ValueError("sparse_attend_step takes a mask or a first position")
    return _call(qg.astype(k_pool.dtype), keep, k_pool, v_pool, table, t,
                 live, first, float(scale), pages, bool(ring), _interpret())
