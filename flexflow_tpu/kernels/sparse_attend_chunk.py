"""A block of `s > 1` tokens' attention over the keys an indexer kept
(`ops/attention_ops.py: _selected_cache_attention`, a prefill chunk) as one
pallas TPU kernel: dense under the indexer's membership mask, as the XLA form
is, with the scores in VMEM.

The XLA form writes a query block's float32 scores `[heads, 256, keys]` to
HBM, reads them for the maximum and for the sum, writes `probs` and reads it
again for the second product: at 8448 keys 1.3 GB a block, and that traffic,
not the arithmetic, is its time. Each query keeps its own `topk` of the
context and under random weights the kept keys do not cluster, so a block of
queries touches every key block: dense under the mask is the right form, and
what can be skipped is known from positions alone, the keys behind a query
block's last position.

The grid is (row, K/V heads a step, query block, key block), the key axis
sequential and its bound known at run time: the blocks the chunk's last
position reaches. The end of each query block (its last position + 1) is
scalar-prefetched; a key block wholly behind it is neither fetched (its
index is the last needed one's again: nothing is moved) nor multiplied. K and
V come gathered by page as the pools hold them, `[b, L, heads * head_dim]`:
a K/V head is one `head_dim`-lane column block of the merged row (whole
128-lane slabs: no transpose, no relayout). A grid step holds the `r` query
heads of a K/V head for one block of queries as `[r * qb, head_dim]` rows
against one key block: the mask tile `[qb, kb]`, read once as the int8 it is,
is applied to all `r` heads, and the online softmax is
`sparse_attend_step.softmax_block`, the decode step's own. V's rows behind the
query block's end are zeroed (0 x what lies behind the context is not 0 where
that is not a number).

The kept keys may also be stated BY POSITION (no mask operand:
`ops/attention_ops.py: _bounded_cache_attention`): the query at `t` keeps `t
- window < s <= t` (every `s <= t` at `window` 0), its block's queries one
position after the other, the gathered keys' row 0 at position `base`. A
query block then has a FIRST key block as well as a last (the one that holds
its first query's first key), the grid's key axis counts from it, and its
bound is the most blocks any query block spans: a window of 1024 behind 2048
queries visits two or three key blocks of 1024 a query block, not the
context's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from flexflow_tpu.kernels.flash_attention import _interpret
from flexflow_tpu.kernels.sparse_attend_step import LANES, _NEG, softmax_block

# a grid step's float32 scores [r * qb, kb] (4 MiB at 8 x 128 x 1024) and
# what the softmax makes of them, the pipeline's tiles twice
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# queries and keys a tile, and the K/V heads a grid step takes. Measured on
# the chip (PERF.md, PR 55: one layer alone at the served shapes, a chunk of
# 2048 queries behind 8192 tokens, ms a layer): a step's cost is its rows'
# running maximum, sum and rescaling whatever the keys, so the key block
# sets the time: 9.25 at 128 x 256, 4.88 at 128 x 512, 2.88 at 128 x 1024,
# 3.00 at 128 x 1536 and at 128 x 2048 (two thirds of the MXU's peak from
# 1024 on); 256 queries 2.78 at 1024; two K/V heads a step (the mask tile
# read and unpacked once for both) 2.71, four 2.62 for 5 s more of Mosaic's
# compile, which the cell's set-up has no room for
_QUERY_BLOCK = 128
_KEY_BLOCK = 1024
_HEADS_A_STEP = 2


def chunk_tiles(head_dim: int, page: int, pages_per_slot: int, chunk: int,
                itemsize: int):
    """(queries, keys) a tile, or None where the kernel does not take the
    block (the XLA form does): it wants a K/V head in whole 128-lane slabs
    (its column block of the merged row), a page in whole tiles of the pools'
    type (the gathered pages are then the context's rows as they lie), the
    chunk a whole number of query blocks of whole tiles (a group's heads of a
    query block are then rows one after the other), and the mask's tile whole
    tiles of int8 (32 x 128) unless it is all of the chunk or of the context
    (whose last key block need not be whole: the kernel reads nothing behind
    a query block's end)."""
    if itemsize not in (2, 4):
        return None
    tile = 32 // itemsize
    context = pages_per_slot * page
    qb, kb = min(_QUERY_BLOCK, chunk), min(_KEY_BLOCK, context)
    if head_dim % LANES or page % tile or chunk % qb or qb % tile \
            or (qb < chunk and qb % 32) or (kb < context and kb % LANES):
        return None
    return qb, kb


def _kernel(ends_ref, lasts_ref, firsts_ref, starts_ref, q_ref, *refs,
            scale: float, kb: int, blocks: int, masked: bool, window: int):
    """One (row, K/V heads, query block, key block). ends_ref, lasts_ref,
    firsts_ref, starts_ref `[b * blocks]` in SMEM (`blocks` query blocks a
    row): a query block's last position + 1, the last and the first key
    block that hold a position one of its queries may see (the grid's key
    axis counts from the first), and the position of its first query, all
    positions counted from the first key row's; q_ref `[1, spans, r, qb,
    width]`; with `masked` keep_ref `[1, qb, kb]` int8, without it the kept
    keys of the query at `t` are the positions `t - window < s <= t` (`s <=
    t` at `window` 0), its block's queries one position after the other;
    k_ref, v_ref `[1, kb, spans * width]`; o_ref as q_ref; m_s, l_s `[spans,
    r * qb, LANES]`, acc_s `[spans, r * qb, width]` float32."""
    keep_ref = refs[0] if masked else None
    k_ref, v_ref, o_ref, m_s, l_s, acc_s = refs[1:] if masked else refs
    row, i = pl.program_id(0), pl.program_id(2)
    spans, r, qb, width = q_ref.shape[1:]
    end = ends_ref[row * blocks + i]
    last = lasts_ref[row * blocks + i]
    j = pl.program_id(3) + (0 if masked else firsts_ref[row * blocks + i])

    @pl.when(pl.program_id(3) == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(j <= last)
    def _():
        # a last key block may reach past the context: what lies behind the
        # query block's end is not the mask's to say, nor V's to weigh
        at = j * kb + jax.lax.broadcasted_iota(jnp.int32, (1, kb), 1)
        if masked:
            keep = jnp.logical_and(keep_ref[0].astype(jnp.int32) != 0,
                                   at < end)                    # [qb, kb]
        else:
            t = starts_ref[row * blocks + i] \
                + jax.lax.broadcasted_iota(jnp.int32, (qb, 1), 0)
            keep = at <= t
            if window:
                keep = jnp.logical_and(keep, at > t - window)
        rows = j * kb + jax.lax.broadcasted_iota(jnp.int32, (kb, 1), 0)
        for h in range(spans):
            lanes = slice(h * width, (h + 1) * width)
            v = v_ref[0, :, lanes]
            softmax_block(q_ref[0, h].reshape(r * qb, width),
                          k_ref[0, :, lanes],
                          jnp.where(rows < end, v, jnp.zeros_like(v)), keep,
                          scale, m_s, l_s, acc_s, h)

    @pl.when(j == last)
    def _():
        for h in range(spans):
            # a query that kept nothing reads zeros
            out = acc_s[h] / jnp.maximum(l_s[h][:, :1], 1e-30)
            o_ref[0, h] = out.reshape(r, qb, width).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11))
def _call(q, keep, k, v, t, base, scale, qb, kb, spans, window, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, g, r, s, width = q.shape
    context = k.shape[1]
    blocks = s // qb
    masked = keep is not None
    # positions from the first key row's on
    t = (t - base[:, None]).reshape(b, blocks, qb)
    # a query block's last position + 1, within the keys there are
    ends = jnp.clip(jnp.max(t, axis=-1) + 1, 1, context).astype(jnp.int32)
    lasts = ((ends + kb - 1) // kb - 1).reshape(-1)
    starts = t[:, :, 0].reshape(-1).astype(jnp.int32)
    # the first key block a query of the block may see
    firsts = jnp.clip(starts - window + 1, 0, context - 1) // kb \
        if window else jnp.zeros_like(lasts)

    def queries(row, h, i, j, ends, lasts, firsts, starts):
        return (row, h, 0, i, 0)

    def keys(row, h, i, j, ends, lasts, firsts, starts):
        # a block behind the query block's end: the last one again
        at = row * blocks + i
        return (row, jnp.minimum(firsts[at] + j, lasts[at]), h)

    def mask(row, h, i, j, ends, lasts, firsts, starts):
        return (row, i, jnp.minimum(j, lasts[row * blocks + i]))

    f32 = jnp.float32
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, kb=kb, blocks=blocks,
                          masked=masked, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, g // spans, blocks, jnp.max(lasts - firsts) + 1),
            in_specs=[pl.BlockSpec((1, spans, r, qb, width), queries)]
            + ([pl.BlockSpec((1, qb, kb), mask)] if masked else [])
            + [pl.BlockSpec((1, kb, spans * width), keys),
               pl.BlockSpec((1, kb, spans * width), keys)],
            out_specs=pl.BlockSpec((1, spans, r, qb, width), queries),
            scratch_shapes=[pltpu.VMEM((spans, r * qb, LANES), f32),
                            pltpu.VMEM((spans, r * qb, LANES), f32),
                            pltpu.VMEM((spans, r * qb, width), f32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ff_sparse_attend_chunk",
    )(ends.reshape(-1), lasts, firsts, starts, q,
      *([keep.astype(jnp.int8)] if masked else []), k, v)
    return out, jnp.sum(lasts - firsts + 1)


def sparse_attend_chunk(qg, keep, k, v, t, scale: float, qb: int, kb: int,
                        base=None, window: int = 0):
    """qg `[b, s, g, r, d]` (g K/V heads of d, whole 128-lane slabs; r query
    heads a group), keep `[b, s, L]` bool (the indexer's kept keys over the
    slot's padded context: False behind a query's position) or None (the
    query at `t` keeps the positions `t - window < s <= t`, every `s <= t` at
    `window` 0: no mask operand, a first key block as well as a last; the
    queries of a row then lie one position after the other), k and v `[b, L,
    g * d]` (the slot's pages gathered, as the pools hold them, row 0 at
    position `base` `[b]` int32, 0 where None), t `[b, s]`
    int32 (the queries' positions, rising along a row), `qb` and `kb` as
    `chunk_tiles` says -> (`[b, s, g, r, d]` in k's type: softmax(q k^T
    scale) v over the kept keys, zeros for a query that kept none; the
    (query block, key block) tiles visited, int32). Interpreted on the CPU;
    the layers of a program that call it at one shape trace its body once."""
    g = qg.shape[2]
    spans = _HEADS_A_STEP if g % _HEADS_A_STEP == 0 else 1
    if keep is not None and (window or base is not None):
        raise ValueError("sparse_attend_chunk takes a mask or position bounds")
    if base is None:
        base = jnp.zeros(t.shape[:1], t.dtype)
    out, tiles = _call(qg.transpose(0, 2, 3, 1, 4).astype(k.dtype), keep, k,
                       v, t, base, float(scale), qb, kb, spans, int(window),
                       _interpret())
    return out.transpose(0, 3, 1, 2, 4), tiles
