"""Block-wise (flash) attention as a pallas TPU kernel.

Capability replaced: the reference's fused cuDNN multi-head attention
(src/ops/attention.cu:35, cudnnMultiHeadAttnForward) — a single kernel that
never materializes the (b, h, sq, sk) logits tensor. The TPU-native
formulation is the standard online-softmax blocked algorithm: k/v live in
VMEM per (b, h) grid step (bounded by _VMEM_SEQ_BYTES) and stream through
the MXU in blocks, with running max/sum statistics kept in f32, so HBM
traffic is O(s*d) instead of O(s^2).

Forward saves the per-row logsumexp; the backward pass is two more pallas
kernels (dq gridded over q blocks; dk/dv gridded over k blocks) recomputing
the probabilities from the saved lse — the flash-attention v2 recipe.

Layout (PR 63). How operands and residuals lie in HBM is decided by the
shape (`entry_of`), never by a flag:

- `merged`, head widths of whole 128-lane slabs (Trinity-Mini's, granite's,
  Nemotron's 128): q, o, dq are `[b, s, h * d]` and k, v, dk, dv `[b, s,
  K/V heads * d]`, exactly what `x @ wq` writes and `@ wo` reads; a block is
  `(rows, d)` and the head is its LANE block index (K/V head `h // group`).
  Nothing is split, swapped or relaid around a call: on one device
  `ops/attention_ops._mha_lower` hands `flash_attention_merged` its
  projections as they lie, and where the layer norms or rotates its heads,
  kernels/head_turn.py does that on the merged axis too.
- `two_heads`, width 64 with an even number of heads and one K/V head a
  query head (GPT-2): the same arrays, two heads a 128-lane block, the grid
  over `h / 2`, each head's 64 lanes a static slice of the tile.
- `swapped`, every other width (the latent layers' 192; grouped heads of
  64): `[b, h, s, d]`, as before; `flash_attention_qkv` swaps the axes
  around the call.

The two row statistics, `lse` (forward -> both backward kernels) and `delta
= rowsum(do * o)` (dq kernel -> dk/dv kernel), are `(b, h, 1, s)` float32
whatever the entry: the sequence on the lanes, 4 bytes a row (as `[.., s,
1]` the chip pads a number to a 128-lane tile: 268 MB for 2 MB at `[2, 32,
8192]`). The forward lays a tile's column along the lanes once a q tile;
the dq kernel makes `delta` from `o`'s rows (a column, as it reads it),
turns its block's `lse` row into a column once a grid step, and hands
`delta` on as a row; the dk/dv kernel works on the TRANSPOSED score tile `k
q^T`, where both statistics are rows as they lie and dk = ds^T q, dv = p^T
do need no transposed operand. A call nobody differentiates writes no `lse`
at all. The kernels see one shape whatever the entry: an operand `[rows,
heads_a_block * d]`, a statistic `[heads_a_block, 1, rows]`.

A VMEM budget bounds a block from above (`_blocks_for`). A non-causal call
takes that bound as its tile and loops over the key blocks with the online
softmax. A causal call holds a block of that size per grid step and tiles
the score matrix under it (`_tiles`: never the whole causal square where a
smaller tile divides the sequence): the key blocks before the step's own
lie wholly under the diagonal and are met whole in a loop; inside the
step's own block, which the diagonal crosses, every tile's place is static,
so each row of tiles meets its tiles wholly under the diagonal in one
unmasked product and the tiles the diagonal crosses in one masked product,
and the tiles above the diagonal are not visited (`_k_tile_bounds`,
`_q_tile_bounds`: exact for unequal q and k tiles). Where the block is the
whole sequence (GPT-2's 1024 at head_dim 64) no statistic is carried from
tile to tile at all. Measured on a v5e (PERF.md, Findings PR 36): a loop
over small tiles with a trip count known only at run time costs more than
the masked half it skips; the static schedule does not.

A causal call may state a `window`: query `t` sees the keys `t - window < s
<= t`. The band is translation invariant, so which of the key blocks before
the step's own lie wholly inside it, which its lower edge crosses and which
lie wholly before it is known per OFFSET from the step's block
(`_band_offsets`): the blocks inside are met whole in the loop, the crossed
ones in a loop of their own under the band's mask, and the ones before the
band are not visited at all. Inside the step's own block the band matters
only where `window` is under the block (`_band_k_tile`, `_band_q_tile`).
A call without a window traces to the program it traced to before windows
existed. K/V heads may be fewer than the query heads (`k.shape[1]` divides
`q.shape[1]`): query head j reads K/V head j // group through the block
index, no repeated copy; dk/dv come out a query head in float32 and are
summed over each group (on the merged axis by lane slabs: `_group_sum`).

All matmuls accumulate in float32 (preferred_element_type) regardless of the
input dtype; bf16 inputs hit the MXU at full rate.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from flexflow_tpu import telemetry as tel

_BLOCK_CANDIDATES = (1024, 512, 256, 128)
# the `checkpoint_name` of what a differentiated call's forward kernel writes
# (`o`, and the rows' logsumexp): the residuals the two backward kernels read
# as they are. A `jax.checkpoint` whose policy keeps the name (a
# `remat_blocks` unit's, through `multihead_attention`'s `kept_names`) does
# not run `ff_flash_attention_fwd` again in its recomputation; under any
# other checkpoint, and under none, the name lowers to nothing
FLASH_KEPT = "ff_flash_residuals"
_NEG_INF = float("-inf")
# what a pair outside the band scores in a block BEFORE the step's own: a
# row may meet such a block with no pair inside the band, and a maximum of
# -inf would make its exp(s - m) a NaN; at this its carry is wiped
# (alpha = 0) when the row meets a pair inside (its own position at last)
_OUT_OF_BAND = -1e30
# k/v (fwd/dq) and q/do (dk/dv) are held fully in VMEM per (b, h) grid step;
# cap their footprint well under the ~16MB VMEM budget so Mosaic never OOMs
# on shapes that pass the divisibility checks. Longer sequences belong to the
# ring-attention path (kernels/ring_attention.py).
_VMEM_SEQ_BYTES = 6 * 1024 * 1024
# per-BLOCK VMEM budget, the bound on a block from above: a grid step's
# operands (q, do, dq accumulators) each carry rows x depth x itemsize, so
# the bound is parametrized by (depth, itemsize): head_dim 128 f32 takes
# 256 rows, bf16 512; head_dim 192 bf16 256.
_VMEM_BLOCK_BYTES = 160 * 1024
# narrow heads (d <= 64, GPT-2's case) may hold more rows a grid step: a
# 1024 x 64 f32 block is 256KB and three such operands are still < 1MB of
# VMEM. It is a bound and no longer the causal tile: a non-causal call takes
# it, a causal call holds a block of it and tiles under it (`_tiles`).
_VMEM_BLOCK_BYTES_NARROW = 256 * 1024
# the causal tile of narrow heads, from a sweep of each kernel on a v5e at
# [8, 16, 1024, 64] bf16 (PERF.md, Findings PR 36): 256 is within 4 % of
# each kernel's best and 11-13 % ahead of 512 in the two backward kernels.
# Wider heads keep their bound (512 at head_dim 128 bf16 measured equal to
# 256 there).
_CAUSAL_TILE_NARROW = 256


def _blocks_for(depth: int, itemsize: int):
    """The candidate blocks the VMEM budget admits, largest first."""
    budget = _VMEM_BLOCK_BYTES_NARROW if depth <= 64 else _VMEM_BLOCK_BYTES
    ok = tuple(b for b in _BLOCK_CANDIDATES
               if b * max(1, depth) * itemsize <= budget)
    # always leave the smallest block available: a 128-row block at any
    # plausible head_dim fits VMEM; the budget only orders preferences
    return ok or _BLOCK_CANDIDATES[-1:]


def flash_supported(seq: int, depth: int, itemsize: int = 4) -> bool:
    """Whether the fused kernel covers this shape (depth-aware block
    divisibility + the VMEM-resident k/v budget). Beyond it, attention
    either falls back to materializing full logits or goes
    sequence-parallel via the ring path — the search uses this to price
    that choice."""
    if any(seq % b == 0 for b in _blocks_for(depth, itemsize)):
        return 2 * seq * depth * itemsize <= _VMEM_SEQ_BYTES
    return False


def _forced_block(env: str, s: int, cands) -> int:
    """The tuning override's block, 0 where unset or unusable: only
    known-safe sizes count (the per-block VMEM budget was sized for
    _blocks_for's output; arbitrary values could OOM Mosaic)."""
    import os

    try:
        forced = int(os.environ.get(env, "0") or "0")
    except ValueError:
        return 0
    return forced if forced in cands and s % forced == 0 else 0


def _bound(s: int, depth: int, itemsize: int) -> int:
    """The largest tile the VMEM budget admits that divides `s`."""
    cands = _blocks_for(depth, itemsize)
    for b in cands:
        if s % b == 0:
            return b
    raise ValueError(f"sequence length {s} not divisible by any of {cands} "
                     f"(head_dim {depth}, itemsize {itemsize})")


def _pick_block(s: int, depth: int = 64, itemsize: int = 4,
                env: str = "FLEXFLOW_FLASH_BLOCK") -> int:
    """`_bound`, or what the tuning override forces: what a non-causal
    call takes, and what a causal call picks under (`_tiles`)."""
    forced = _forced_block(env, s, _blocks_for(depth, itemsize))
    if forced:
        return forced
    if env != "FLEXFLOW_FLASH_BLOCK":
        # bwd knob unset OR invalid: inherit the main block choice (so a
        # typo'd bwd value degrades to the fwd configuration, not to a
        # third configuration nobody asked for)
        return _pick_block(s, depth, itemsize)
    return _bound(s, depth, itemsize)


KERNELS = ("fwd", "dq", "dkv")


def _tiles(kernel: str, seq_q: int, seq_k: int, depth: int, itemsize: int,
           causal: bool):
    """(bq, bk) of one of the three kernels, from what it can see. A
    non-causal call, and one a tuning override speaks for, takes
    `_pick_block`. A causal call (seq_q == seq_k) picks under that bound:
    narrow heads at most `_CAUSAL_TILE_NARROW`, and no call the whole
    causal square where a smaller tile divides the sequence, so that the
    tiles above the diagonal can be skipped."""
    env = ("FLEXFLOW_FLASH_BLOCK" if kernel == "fwd"
           else "FLEXFLOW_FLASH_BLOCK_BWD")
    bq = _pick_block(seq_q, depth, itemsize, env)
    bk = _pick_block(seq_k, depth, itemsize, env)
    cands = _blocks_for(depth, itemsize)
    if not causal or _forced_block(env, seq_q, cands) \
            or _forced_block("FLEXFLOW_FLASH_BLOCK", seq_q, cands):
        return bq, bk
    tile = min(bq, _CAUSAL_TILE_NARROW) if depth <= 64 else bq
    if tile == seq_q:
        tile = next((b for b in _BLOCK_CANDIDATES
                     if b < seq_q and seq_q % b == 0), tile)
    return tile, tile


# ------------------------------------------------------------ causal schedule
def _k_tile_bounds(q_start, bq: int, bk: int):
    """Of the k tiles of width `bk`, seen from the q rows [q_start, q_start
    + bq) of a causal call: tiles [0, full) lie wholly at or under the
    diagonal (every pair unmasked), [full, visit) are crossed by it, and
    from `visit` on no pair is unmasked. Exact for any bq, bk; `q_start`
    may be traced."""
    return (q_start + 1) // bk, (q_start + bq - 1) // bk + 1


def _q_tile_bounds(k_start, bq: int, bk: int):
    """The same seen from the k columns [k_start, k_start + bk): q tiles
    before `visit` hold no unmasked pair, [visit, full) are crossed by the
    diagonal, from `full` on every pair is unmasked."""
    return k_start // bq, (k_start + bk + bq - 2) // bq


def _band_offsets(window: int, block: int):
    """(inside, out) for blocks of `block` rows and keys `j >= 1` blocks
    apart (the keys before the queries): at j <= inside every pair lies in
    the band `t - s < window`, at j >= out none does, between them the
    band's lower edge crosses the block."""
    return max(0, window // block - 1), -(-(window - 1) // block) + 1


def _band_k_tile(q_start: int, bk: int, window: int) -> int:
    """The first k tile of the step's own block that holds a key some row
    from `q_start` on may see."""
    return max(0, q_start - window + 1) // bk


def _band_q_tile(k_start: int, bq: int, bk: int, window: int, rows: int):
    """One past the last q tile of the step's own block (of `rows` rows)
    that holds a query which may see a key of [k_start, k_start + bk)."""
    return -(-min(rows, k_start + bk - 1 + window) // bq)


def _ahead(bq: int, bk: int, keys_first: bool = False):
    """How far each pair's query lies after its key, counted from the
    tile's first of each: (bq, bk) int32, or (`keys_first`) the transposed
    tile's (bk, bq)."""
    shape = (bk, bq) if keys_first else (bq, bk)
    return jax.lax.broadcasted_iota(jnp.int32, shape, int(keys_first)) \
        - jax.lax.broadcasted_iota(jnp.int32, shape, int(not keys_first))


def _in_band(offset, bq: int, bk: int, window: int, keys_first=False):
    """(bq, bk) bool: the pairs whose query lies under `window` after the
    key, the tile's first query `offset` after its first key."""
    return _ahead(bq, bk, keys_first) < window - offset


def _schedule(kernel: str, seq_q: int, seq_k: int, bq: int, bk: int,
              causal: bool, window: int = 0, block: int = 0):
    """(visited, masked, total) tiles of one (batch, head)'s score matrix
    for a kernel at tiles (bq, bk), counted with the kernel's own bounds:
    `visited` are computed, `masked` of them take the causal mask or the
    band's. Under a `window` the count needs the grid step's `block`."""
    nq, nk = seq_q // bq, seq_k // bk
    if not causal:
        return nq * nk, 0, nq * nk
    if window:
        return _band_schedule(kernel, seq_q, bq, bk, window, block) \
            + (nq * nk,)
    if kernel == "dkv":         # a k tile a grid step, looping over q tiles
        spans = [_q_tile_bounds(j * bk, bq, bk) for j in range(nk)]
        return (sum(nq - first for first, _ in spans),
                sum(full - first for first, full in spans), nq * nk)
    spans = [_k_tile_bounds(i * bq, bq, bk) for i in range(nq)]
    return (sum(visit for _, visit in spans),
            sum(visit - full for full, visit in spans), nq * nk)


def _band_schedule(kernel: str, seq: int, bq: int, bk: int, window: int,
                   block: int):
    """(visited, masked) tiles of a windowed causal call whose grid steps
    hold `block` rows, as the three kernels walk it."""
    inside, out = _band_offsets(window, block)
    steps, per_block = seq // block, (block // bq) * (block // bk)
    # the blocks before (fwd, dq) or after (dkv) the step's own, by offset
    whole = sum(min(i, inside) for i in range(steps))
    crossed = sum(min(i, out - 1) - min(i, inside) for i in range(steps))
    visited, masked = (whole + crossed) * per_block, crossed * per_block
    banded = window < block
    if kernel == "dkv":
        for k_at in range(0, block, bk):
            first, full = _q_tile_bounds(k_at, bq, bk)
            last = _band_q_tile(k_at, bq, bk, window, block) if banded \
                else block // bq
            visited += steps * (max(last, full) - first)
            masked += steps * ((full - first)
                               + (max(last, full) - full if banded else 0))
        return visited, masked
    for q_at in range(0, block, bq):
        full, visit = _k_tile_bounds(q_at, bq, bk)
        lo = min(_band_k_tile(q_at, bk, window), full) if banded else 0
        visited += steps * (visit - lo)
        masked += steps * ((visit - full) + (full - lo if banded else 0))
    return visited, masked


def tile_plan(seq_q: int, seq_k: int, depth: int, itemsize: int,
              causal: bool, window: int = 0) -> dict:
    """What each of the three kernels does at this shape, as the lowering
    span reports it: its tile and the `_schedule` counts."""
    plan = {}
    for kernel in KERNELS:
        bq, bk = _tiles(kernel, seq_q, seq_k, depth, itemsize, causal)
        block = _grid_block(seq_q, depth, itemsize, causal, bq, bk)
        visited, masked, total = _schedule(kernel, seq_q, seq_k, bq, bk,
                                           causal, window, block)
        plan[kernel] = {"flash_tile_q": bq, "flash_tile_k": bk,
                        "flash_tiles_visited": visited,
                        "flash_tiles_masked": masked,
                        "flash_tiles_total": total}
    return plan


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


# what Mosaic gives a kernel's VMEM scope unasked, and the resident bytes
# from which a call asks for its own (`_params`): GPT-2's 1024 x 64 stay far
# under it and keep the default
_VMEM_DEFAULT_SCOPE = 16 * 1024 * 1024
_VMEM_ASK_FROM = 10 * 1024 * 1024


def _params(seq: int = 0, width: int = 0, itemsize: int = 0,
            vectors: int = 0):
    """The kernels' compiler parameters. A grid step holds two whole-
    sequence operands of `width` lanes (k and v, or q and do) and `vectors`
    per-row f32 rows (lse, delta: a `[1, seq]` block lies in VMEM eight
    sublanes a 128-lane tile), each double-buffered: where that passes
    `_VMEM_ASK_FROM` (8192 x 128 does in the backward) the call states its
    own scope, the resident bytes and the default beside them."""
    from jax.experimental.pallas import tpu as pltpu

    # batch/head/q-block grid dims are independent; lets Mosaic pipeline them
    semantics = ("parallel", "parallel", "arbitrary")
    resident = 2 * seq * (2 * width * itemsize + vectors * 8 * 4)
    if resident <= _VMEM_ASK_FROM:
        return pltpu.CompilerParams(dimension_semantics=semantics)
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=resident + _VMEM_DEFAULT_SCOPE)


def _traced_once(arrays: int, **static):
    """`call(*arrays, causal, scale, bq, bk, *static, interpret)` under
    `jax.jit`, all but the arrays static (`static`: the keywords a caller
    may give, with their defaults): a model's layers call a kernel at one
    shape, and a trace of the step then traces and lowers the kernel's body
    once and not once a layer (the static schedule under the diagonal is
    straight-line code, twice the parent's loop to trace). Interpret mode
    is part of the key: tests switch it within a process."""
    def wrap(call):
        jitted = jax.jit(call, static_argnums=tuple(
            range(arrays, arrays + 5 + len(static))))

        def traced(*args, **given):
            return jitted(*args, *({**static, **given}.values()), _interpret())

        return traced
    return wrap


def _under_diagonal(q_start, k_start, bq: int, bk: int, keys_first=False):
    """(bq, bk) bool: the pairs of a tile the diagonal crosses whose query
    is at or after the key; `keys_first`: the transposed tile, (bk, bq)."""
    return _ahead(bq, bk, keys_first) >= k_start - q_start


def _nt(a, b):
    """a (m, d) . b (n, d)^T -> (m, n) f32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    """a (m, n) . b (n, d) -> (m, d) f32."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    """a (n, m)^T . b (n, d) -> (m, d) f32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _online(carry, parts):
    """One online-softmax step of some q rows over `parts`, a list of
    (scores f32 (r, n), values (n, d)) that are met together: one max and
    one sum over all of them, so only `carry` (None, or the rows' (m, l,
    acc) so far) is rescaled."""
    m = functools.reduce(
        jnp.maximum, [jnp.max(s, axis=-1, keepdims=True) for s, _ in parts])
    if carry is not None:
        m = jnp.maximum(carry[0], m)
    l = acc = 0.0
    for s, v in parts:
        p = jnp.exp(s - m)
        l = l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc + _nn(p.astype(v.dtype), v)
    if carry is not None:
        alpha = jnp.exp(carry[0] - m)
        l, acc = l + carry[1] * alpha, acc + carry[2] * alpha
    return m, l, acc


def _at(base, offset: int, size: int, align: int):
    """Rows [base + offset, base + offset + size) of a resident operand;
    `base` is 0 or a traced multiple of `align`, as is `offset`."""
    if isinstance(base, int):
        return pl.ds(base + offset, size)
    return pl.ds(pl.multiple_of(base + offset, align), size)


# ------------------------------------------------------- layout at the boundary
# How operands and residuals lie in HBM (module docstring, "Layout"). A kernel
# sees one shape whatever the entry: an operand `[rows, heads_a_block * d]`
# (its block's other dimensions squeezed away), a row statistic
# `[heads_a_block, 1, rows]`, and walks the block's heads by their lanes.
# how the rows' statistics lie, as the lowering span says it: the sequence on
# the lanes
RESIDUAL = "lanes"


def entry_of(depth: int, heads: int, kv_heads: int) -> str:
    """Which entry a shape takes: head widths of whole 128-lane slabs are
    read from `[b, s, h * d]` as the projections write it (`merged`), width
    64 two heads a 128-lane block (`two_heads`: an even number of heads, one
    K/V head a query head); every other width through `[b, h, s, d]`
    (`swapped`: a lane block narrower or wider than its head cannot be
    indexed by the head)."""
    if depth % 128 == 0:
        return "merged"
    if depth == 64 and heads % 2 == 0 and kv_heads == heads:
        return "two_heads"
    return "swapped"


def _dims(q, k, heads: int):
    """(b, h, K/V heads, sq, sk, d) of a call's operands: `heads` 0, q `(b,
    h, sq, d)`; else q `[b, sq, heads * d]` and k `[b, sk, K/V heads * d]`."""
    if not heads:
        b, h, sq, d = q.shape
        return b, h, k.shape[1], sq, k.shape[2], d
    b, sq, e = q.shape
    return b, heads, k.shape[2] * heads // e, sq, k.shape[1], e // heads


def _heads_a_block(heads: int, depth: int) -> int:
    """Query heads a grid step holds: the heads of one 128-lane block."""
    return max(1, 128 // depth) if heads else 1


def _operand(heads: int, depth: int, rows: int, whole: bool, group: int = 1):
    """The BlockSpec of `rows` rows of one (batch, head block): the grid
    step's block of them, or (`whole`) the sequence; `group` query heads
    read one K/V head (query head j K/V head j // group)."""
    at = (lambda i: 0) if whole else (lambda i: i)
    head = (lambda h_: h_) if group == 1 else (lambda h_: h_ // group)
    if heads:       # the head (or pair) is the LANE block
        return pl.BlockSpec(
            (None, rows, _heads_a_block(heads, depth) * depth),
            lambda b_, h_, i: (b_, at(i), head(h_)))
    return pl.BlockSpec((None, None, rows, depth),
                        lambda b_, h_, i: (b_, head(h_), at(i), 0))


def _statistic(heads: int, depth: int, rows: int, whole: bool):
    """The BlockSpec of a row statistic `(b, h, 1, s)` float32: the
    sequence on the LANES (as `[.., s, 1]` the chip pads every number to a
    128-lane tile, in HBM and in VMEM)."""
    at = (lambda i: 0) if whole else (lambda i: i)
    return pl.BlockSpec((None, _heads_a_block(heads, depth), 1, rows),
                        lambda b_, h_, i: (b_, h_, 0, at(i)))


def _shape_of(heads: int, b: int, h: int, s: int, d: int, dtype):
    return jax.ShapeDtypeStruct((b, s, h * d) if heads else (b, h, s, d),
                                dtype)


def _turned(vector):
    """A tile's column `(n, 1)` laid along the lanes `(1, n)`, or a
    statistic's row as the column a score tile with the queries first
    subtracts: one relayout of n numbers on the chip."""
    return vector.T


def _lanes_of(ref, depth: int):
    """[(j, lanes)] of the heads of a kernel's block."""
    return [(j, pl.ds(j * depth, depth))
            for j in range(ref.shape[-1] // depth)]


# --------------------------------------------------------------------- forward
# Each kernel: the whole key (or q) blocks in a loop first, `carry` None
# where there are none; then, causal, the step's own block by static tiles.
# Under a window the loop starts where the band does, and the blocks its
# lower edge crosses go first in a loop of their own, under its mask.
def _block_loops(step, steps: int, window: int, block: int, after: bool):
    """[(lo, hi, banded)] of the loops over the whole blocks beside the
    step's own of a causal call: before it (`after` False: fwd, dq, over
    key blocks) or after it (dkv, over q blocks, of which there are
    `steps`); a windowed call's crossed blocks first."""
    if not window:
        return [(step + 1, steps, False) if after else (0, step, False)]
    inside, out = _band_offsets(window, block)
    if after:
        edge = jnp.minimum(steps, step + inside + 1)
        return [(edge, jnp.minimum(steps, step + out), True),
                (step + 1, edge, False)]
    edge = jnp.maximum(0, step - inside)
    return [(jnp.maximum(0, step - out + 1), edge, True), (edge, step, False)]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref=None, *, scale, causal,
                bq, bk, depth, window=0):
    for j, lanes in _lanes_of(q_ref, depth):
        _fwd_head(q_ref, k_ref, v_ref, o_ref, lse_ref, j, lanes, scale,
                  causal, bq, bk, window)


def _fwd_head(q_ref, k_ref, v_ref, o_ref, lse_ref, j, lanes, scale, causal,
              bq, bk, window):
    gq, sk, d = q_ref.shape[0], k_ref.shape[0], lanes.size
    qi = pl.program_id(2)
    block_k = gq if causal else bk

    def finish(carry, rows):
        m, l, acc = carry
        o_ref[rows, lanes] = (acc / l).astype(o_ref.dtype)
        if lse_ref is not None:     # the rows' column, laid along the lanes
            lse_ref[j, :, rows] = _turned(m + jnp.log(l))

    def whole_block(ki, carry, banded=False):
        keys = _at(ki * block_k, 0, block_k, block_k)
        s = _nt(q_ref[:, lanes], k_ref[keys, lanes]) * scale
        if banded:
            s = jnp.where(_in_band((qi - ki) * gq, gq, block_k, window), s,
                          _OUT_OF_BAND)
        return _online(carry, [(s, v_ref[keys, lanes])])

    carry = None
    if not causal or gq != sk:
        carry = (jnp.full((gq, 1), _NEG_INF, jnp.float32),
                 jnp.zeros((gq, 1), jnp.float32), jnp.zeros((gq, d), jnp.float32))
        loops = _block_loops(qi, 0, window, gq, False) if causal \
            else [(0, sk // block_k, False)]
        for lo, hi, banded in loops:
            carry = jax.lax.fori_loop(
                lo, hi, functools.partial(whole_block, banded=banded)
                if banded else whole_block, carry)
    if not causal:
        finish(carry, slice(None))
        return
    base = 0 if gq == sk else qi * gq
    banded = 0 < window < gq
    for q_at in range(0, gq, bq):
        rows = slice(q_at, q_at + bq)
        q = q_ref[rows, lanes]
        full, visit = _k_tile_bounds(q_at, bq, bk)
        lo = min(_band_k_tile(q_at, bk, window), full) if banded else 0
        parts = []
        if full > lo:
            keys = _at(base, lo * bk, (full - lo) * bk, bk)
            s = _nt(q, k_ref[keys, lanes]) * scale
            if banded:
                s = jnp.where(_in_band(q_at - lo * bk, *s.shape, window), s,
                              _NEG_INF)
            parts.append((s, v_ref[keys, lanes]))
        keys = _at(base, full * bk, (visit - full) * bk, bk)
        s = _nt(q, k_ref[keys, lanes]) * scale
        # every row meets its own position here: its max is finite
        keep = _under_diagonal(q_at, full * bk, *s.shape)
        if banded:
            keep &= _in_band(q_at - full * bk, *s.shape, window)
        s = jnp.where(keep, s, _NEG_INF)
        parts.append((s, v_ref[keys, lanes]))
        before = None if carry is None else tuple(x[rows] for x in carry)
        finish(_online(before, parts), rows)


def _grid_block(seq: int, depth: int, itemsize: int, causal: bool,
                tile: int, other: int) -> int:
    """Rows of q (fwd, dq) or k (dkv) a grid step holds: a non-causal
    step its tile; a causal one the budget's bound, tiled inside."""
    return max(_bound(seq, depth, itemsize), tile, other) if causal else tile


def _band_args(window: int):
    """A kernel's keyword for the window, none without one (the partial of
    a call without a window is the one it was)."""
    return {"window": window} if window else {}


@_traced_once(3, window=0, heads=0, stats=True)
def _fwd_call(q, k, v, causal, scale, bq, bk, window, heads, stats, interpret):
    """(o, lse) of either form of operands (`_dims`); `stats` False: `o`
    alone, and no `lse` leaves the kernel (a call nobody differentiates)."""
    b, h, kvh, sq, sk, d = _dims(q, k, heads)
    gq = _grid_block(sq, d, q.dtype.itemsize, causal, bq, bk)
    hb = _heads_a_block(heads, d)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, depth=d, **_band_args(window))
    q_spec = _operand(heads, d, gq, False)
    k_full = _operand(heads, d, sk, True, h // kvh)
    out = pl.pallas_call(
        kernel,
        grid=(b, h // hb, sq // gq),
        in_specs=[q_spec, k_full, k_full],
        out_specs=[q_spec] + [_statistic(heads, d, gq, False)] * stats,
        out_shape=[_shape_of(heads, b, h, sq, d, q.dtype)]
        + [jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32)] * stats,
        compiler_params=_params(sk, hb * d, k.dtype.itemsize),
        interpret=interpret,
        name="ff_flash_attention_fwd",
    )(q, k, v)
    return tuple(out) if stats else out[0]


def _fwd(q, k, v, causal, scale, window=0, heads=0, stats=True):
    """q: (b, h, sq, d); k/v: (b, h or its K/V heads, sk, d), or with
    `heads` all three `[b, s, heads * d]` -> (o, lse `(b, h, 1, sq)`)."""
    _, _, _, sq, sk, d = _dims(q, k, heads)
    bq, bk = _tiles("fwd", sq, sk, d, q.dtype.itemsize, causal)
    return _fwd_call(q, k, v, causal, scale, bq, bk, window=window,
                     heads=heads, stats=stats)


# -------------------------------------------------------------------- backward
# `lse` and `delta` come in as ROWS (the sequence on the lanes). The dq
# kernel holds a block of q rows against every key: it turns its block's two
# rows into columns once a grid step and works on the score tile as the
# forward does. The dk/dv kernel holds a block of keys against every q row
# and would turn a vector a tile: it works on the TRANSPOSED tile `k q^T`
# instead, where the two statistics are rows as they lie and dk = ds^T q,
# dv = p^T do need no transposed operand either.
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, delta_ref,
               *, scale, causal, bq, bk, depth, window=0):
    for j, lanes in _lanes_of(q_ref, depth):
        # delta = rowsum(do * o), made here from the block's own rows (a
        # column, as this kernel reads it) and handed on as a row
        delta = jnp.sum(do_ref[:, lanes].astype(jnp.float32)
                        * o_ref[:, lanes].astype(jnp.float32),
                        axis=-1, keepdims=True)
        delta_ref[j] = _turned(delta)
        _dq_head(q_ref, k_ref, v_ref, do_ref, _turned(lse_ref[j]), delta, dq_ref,
                 lanes, scale, causal, bq, bk, window)


def _dq_head(q_ref, k_ref, v_ref, do_ref, lse, delta, dq_ref, lanes, scale,
             causal, bq, bk, window):
    """`lse`, `delta`: the block's columns `(gq, 1)`."""
    gq, sk, d = q_ref.shape[0], k_ref.shape[0], lanes.size
    qi = pl.program_id(2)
    block_k = gq if causal else bk

    def dq_of(rows, keys, mask=None):
        k = k_ref[keys, lanes]
        # the probabilities recomputed from the saved lse; `mask` where the
        # diagonal (or a band's edge) crosses
        p = jnp.exp(_nt(q_ref[rows, lanes], k) * scale - lse[rows])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        ds = p * (_nt(do_ref[rows, lanes], v_ref[keys, lanes])
                  - delta[rows]) * scale
        return _nn(ds.astype(k.dtype), k)

    def whole_block(ki, dq, banded=False):
        mask = _in_band((qi - ki) * gq, gq, block_k, window) if banded \
            else None
        return dq + dq_of(slice(None),
                          _at(ki * block_k, 0, block_k, block_k), mask)

    dq = None
    if not causal or gq != sk:
        dq = jnp.zeros((gq, d), jnp.float32)
        loops = _block_loops(qi, 0, window, gq, False) if causal \
            else [(0, sk // block_k, False)]
        for lo, hi, banded in loops:
            dq = jax.lax.fori_loop(
                lo, hi, functools.partial(whole_block, banded=banded)
                if banded else whole_block, dq)
    if not causal:
        dq_ref[:, lanes] = dq.astype(dq_ref.dtype)
        return
    base = 0 if gq == sk else qi * gq
    banded = 0 < window < gq
    for q_at in range(0, gq, bq):
        rows = slice(q_at, q_at + bq)
        full, visit = _k_tile_bounds(q_at, bq, bk)
        lo = min(_band_k_tile(q_at, bk, window), full) if banded else 0
        crossed = (visit - full) * bk
        keep = _under_diagonal(q_at, full * bk, bq, crossed)
        if banded:
            keep &= _in_band(q_at - full * bk, bq, crossed, window)
        acc = dq_of(rows, _at(base, full * bk, crossed, bk), keep)
        if full > lo:
            acc = acc + dq_of(
                rows, _at(base, lo * bk, (full - lo) * bk, bk),
                _in_band(q_at - lo * bk, bq, (full - lo) * bk, window)
                if banded else None)
        if dq is not None:
            acc = acc + dq[rows]
        dq_ref[rows, lanes] = acc.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                *, scale, causal, bq, bk, depth, window=0):
    for j, lanes in _lanes_of(k_ref, depth):
        _dkv_head(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                  dv_ref, j, lanes, scale, causal, bq, bk, window)


def _dkv_head(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
              j, lanes, scale, causal, bq, bk, window):
    gk, sq, d = k_ref.shape[0], q_ref.shape[0], lanes.size
    kj = pl.program_id(2)
    block_q = gk if causal else bq

    def dkv_of(rows, keys, mask=None):
        """(dk, dv) of some keys from some q rows, on the tile (keys, rows):
        `mask` (keys first) where the diagonal or a band's edge crosses."""
        q, do = q_ref[rows, lanes], do_ref[rows, lanes]
        p = jnp.exp(_nt(k_ref[keys, lanes], q) * scale - lse_ref[j, :, rows])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        ds = p * (_nt(v_ref[keys, lanes], do) - delta_ref[j, :, rows]) * scale
        return _nn(ds.astype(q.dtype), q), _nn(p.astype(do.dtype), do)

    def whole_block(qi, carry, banded=False):
        mask = _in_band((qi - kj) * gk, block_q, gk, window, True) \
            if banded else None
        dk, dv = dkv_of(_at(qi * block_q, 0, block_q, block_q), slice(None),
                        mask)
        return carry[0] + dk, carry[1] + dv

    carry = None
    if not causal or gk != sq:
        z = jnp.zeros((gk, d), jnp.float32)
        carry = (z, z)
        loops = _block_loops(kj, sq // block_q, window, gk, True) if causal \
            else [(0, sq // block_q, False)]
        for lo, hi, banded in loops:
            carry = jax.lax.fori_loop(
                lo, hi, functools.partial(whole_block, banded=banded)
                if banded else whole_block, carry)
    if not causal:
        dk_ref[:, lanes] = carry[0].astype(dk_ref.dtype)
        dv_ref[:, lanes] = carry[1].astype(dv_ref.dtype)
        return
    base = 0 if gk == sq else kj * gk
    banded = 0 < window < gk
    for k_at in range(0, gk, bk):
        keys = slice(k_at, k_at + bk)
        first, full = _q_tile_bounds(k_at, bq, bk)
        crossed = (full - first) * bq
        keep = _under_diagonal(first * bq, k_at, crossed, bk, True)
        if banded:
            keep &= _in_band(first * bq - k_at, crossed, bk, window, True)
        dk, dv = dkv_of(_at(base, first * bq, crossed, bq), keys, keep)
        last = max(full, _band_q_tile(k_at, bq, bk, window, gk)) if banded \
            else gk // bq
        if full < last:
            more = dkv_of(
                _at(base, full * bq, (last - full) * bq, bq), keys,
                _in_band(full * bq - k_at, (last - full) * bq, bk, window,
                         True) if banded else None)
            dk, dv = dk + more[0], dv + more[1]
        if carry is not None:
            dk, dv = dk + carry[0][keys], dv + carry[1][keys]
        dk_ref[keys, lanes] = dk.astype(dk_ref.dtype)
        dv_ref[keys, lanes] = dv.astype(dv_ref.dtype)


@_traced_once(6, window=0, heads=0)
def _dq_call(q, k, v, g, o, lse, causal, scale, bq, bk, window, heads,
             interpret):
    """(dq, delta): `delta` = rowsum(do * o) `(b, h, 1, sq)` float32, the
    second statistic of the dk/dv kernel, which runs after this one."""
    b, h, kvh, sq, sk, d = _dims(q, k, heads)
    gq = _grid_block(sq, d, q.dtype.itemsize, causal, bq, bk)
    hb = _heads_a_block(heads, d)
    q_spec = _operand(heads, d, gq, False)
    k_full = _operand(heads, d, sk, True, h // kvh)
    vec_q = _statistic(heads, d, gq, False)
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                          depth=d, **_band_args(window)),
        grid=(b, h // hb, sq // gq),
        in_specs=[q_spec, k_full, k_full, q_spec, q_spec, vec_q],
        out_specs=[q_spec, vec_q],
        out_shape=[_shape_of(heads, b, h, sq, d, q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32)],
        compiler_params=_params(sk, hb * d, k.dtype.itemsize),
        interpret=interpret,
        name="ff_flash_attention_dq",
    )(q, k, v, g, o, lse)


@_traced_once(6, window=0, heads=0)
def _dkv_call(q, k, v, g, lse, delta, causal, scale, bq, bk, window, heads,
              interpret):
    b, h, kvh, sq, sk, d = _dims(q, k, heads)
    group = h // kvh
    gk = _grid_block(sk, d, k.dtype.itemsize, causal, bk, bq)
    hb = _heads_a_block(heads, d)
    q_full = _operand(heads, d, sq, True)
    k_in = _operand(heads, d, gk, False, group)
    # a query head's own dk, dv: in f32 where a group's are summed after
    k_out = _operand(heads, d, gk, False)
    vec_full = _statistic(heads, d, sq, True)
    out_dt = (k.dtype, v.dtype) if group == 1 else (jnp.float32, jnp.float32)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                          depth=d, **_band_args(window)),
        grid=(b, h // hb, sk // gk),
        in_specs=[q_full, k_in, k_in, q_full, vec_full, vec_full],
        out_specs=[k_out, k_out],
        out_shape=[_shape_of(heads, b, h, sk, d, out_dt[0]),
                   _shape_of(heads, b, h, sk, d, out_dt[1])],
        compiler_params=_params(sq, hb * d, q.dtype.itemsize, vectors=2),
        interpret=interpret,
        name="ff_flash_attention_dkv",
    )(q, k, v, g, lse, delta)


def _group_sum(x, group: int, depth: int, heads: int, dtype):
    """A K/V head's dk or dv: its group's query heads' (float32), summed.
    On the merged axis by lane slabs: splitting `[b, s, h * d]` into heads
    would relay all of it (a head's rows lie eight positions a tile)."""
    if not heads:
        b, h, sk, d = x.shape
        return x.reshape(b, h // group, group, sk, d).sum(axis=2).astype(dtype)
    slabs = [x[:, :, j * depth:(j + 1) * depth] for j in range(heads)]
    return jnp.concatenate(
        [functools.reduce(jnp.add, slabs[j:j + group])
         for j in range(0, heads, group)], axis=-1).astype(dtype)


def _bwd(causal, scale, window, heads, res, g):
    q, k, v, o, lse = res
    # FLEXFLOW_FLASH_BLOCK_BWD tunes the backward independently (the dq /
    # dkv kernels have different VMEM/recompute balance than the forward);
    # unset = inherit FLEXFLOW_FLASH_BLOCK's choice
    _, h, kvh, sq, sk, d = _dims(q, k, heads)
    shape = (sq, sk, d, q.dtype.itemsize, causal)
    dq, delta = _dq_call(q, k, v, g, o, lse, causal, scale,
                         *_tiles("dq", *shape), window=window, heads=heads)
    dk, dv = _dkv_call(q, k, v, g, lse, delta, causal, scale,
                       *_tiles("dkv", *shape), window=window, heads=heads)
    if kvh != h:
        dk, dv = (_group_sum(x, h // kvh, d, heads, k.dtype) for x in (dk, dv))
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, window, heads):
    return _fwd(q, k, v, causal, scale, window, heads, stats=False)


def _flash_fwd(q, k, v, causal, scale, window, heads):
    """The forward rule: both results of the kernel named `FLASH_KEPT` (a
    recomputation drops the call only where every result is kept), `lse` as
    it lies: `(b, h, 1, s)` float32, the sequence on the lanes."""
    o, lse = _fwd(q, k, v, causal, scale, window, heads)
    o, lse = checkpoint_name(o, FLASH_KEPT), checkpoint_name(lse, FLASH_KEPT)
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


# ------------------------------------------------------------------ public API
def _checked(q, k, v, causal, scale, window, heads):
    """Either form's call (`_dims`): raises ValueError when shapes don't
    qualify (sequence not divisible by a block size, causal with sq != sk);
    callers precheck with flash_supported, and a kernel that was chosen and
    then fails must fail."""
    b, h, kvh, sq, sk, d = _dims(q, k, heads)
    if causal and sq != sk:
        raise ValueError("causal flash attention requires sq == sk "
                         f"(got {sq} vs {sk})")
    if k.shape != v.shape:
        raise ValueError(f"k/v mismatch {k.shape} vs {v.shape}")
    if h % kvh or q.shape[-1] != (h * d if heads else d):
        raise ValueError(f"{kvh} K/V heads under {h} query heads "
                         f"({q.shape} / {k.shape})")
    if window < 0 or (window and not causal):
        raise ValueError(f"window {window} on a call that is not causal")
    window = 0 if window >= sk else int(window)
    _pick_block(sq, d, q.dtype.itemsize)
    _pick_block(sk, d, k.dtype.itemsize)
    for s_, it in ((sq, q.dtype.itemsize), (sk, k.dtype.itemsize)):
        if 2 * s_ * d * it > _VMEM_SEQ_BYTES:
            # the Mosaic-reject precheck (same bound as flash_supported):
            # shapes whose VMEM-resident operands can't fit raise HERE, at
            # trace time, with a message that names the shape
            raise ValueError(
                f"sequence {s_} x depth {d} exceeds the VMEM-resident budget "
                f"({_VMEM_SEQ_BYTES} bytes); use the einsum or ring path")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # one span a lowered call (trace time): the schedule each of the three
    # kernels takes at this shape and how its operands lie, for
    # tools/trace_report.py
    facts = {"window": window} if window else {}
    with tel.span("lower/flash_attention", cat="compile",
                  batch_heads=b * h, seq_q=sq, seq_k=sk, depth=d,
                  causal=bool(causal),
                  entry=entry_of(d, h, kvh) if heads else "swapped",
                  residual=RESIDUAL,
                  kernels=tile_plan(sq, sk, d, q.dtype.itemsize, causal,
                                    window),
                  **facts):
        return _flash(q, k, v, causal, float(scale), window, heads)


def flash_attention(q, k, v, causal: bool = False, scale: float | None = None,
                    window: int = 0):
    """The `swapped` entry. q: (b, h, sq, d), k/v: (b, h or K/V heads that
    divide h, sk, d) -> (b, h, sq, d). `window` (causal only): query t sees
    the keys t - window < s <= t; 0, or one that holds the sequence: every
    s <= t. Unsupported shapes raise ValueError (`_checked`)."""
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError(f"expected rank-4 q/k/v, got {q.shape}/{k.shape}/{v.shape}")
    return _checked(q, k, v, causal, scale, window, 0)


def flash_attention_merged(q, k, v, heads: int, causal: bool = False,
                           scale: float | None = None, window: int = 0):
    """The `merged` and `two_heads` entries: q `[b, sq, heads * d]`, k/v
    `[b, sk, K/V heads * d]`, as `x @ wq` writes them and `@ wo` reads the
    result `[b, sq, heads * d]`; no operand is relaid for the kernels, whose
    blocks index the head (or pair of heads) along the lanes."""
    if q.ndim != 3 or k.ndim != 3 or v.ndim != 3 or q.shape[2] % heads:
        raise ValueError(f"expected [b, s, {heads} * d] q/k/v, got "
                         f"{q.shape}/{k.shape}/{v.shape}")
    d = q.shape[2] // heads
    if k.shape[2] % d or entry_of(d, heads, k.shape[2] // d) == "swapped":
        raise ValueError(f"heads of {d} under {q.shape}/{k.shape} are not "
                         "whole 128-lane blocks: the swapped entry's")
    return _checked(q, k, v, causal, scale, window, heads)


def flash_attention_qkv(q, k, v, causal: bool = False, scale: float | None = None,
                        window: int = 0):
    """For a caller that holds split heads (a mesh's shard of
    ops/attention_ops, the latent layers): q/k/v (b, s, h, d), returns (b,
    sq, h, d). The shape decides the entry (`entry_of`): where a head is
    whole lanes the split is undone and the kernels read the projections'
    own layout; else the heads are swapped before the sequence and back.
    On one device `_mha_lower` asks `entry_of` itself and hands
    `flash_attention_merged` its projections unsplit. Unsupported shapes
    raise ValueError."""
    (b, sq, h, d), kvh = q.shape, k.shape[2]
    if entry_of(d, h, kvh) != "swapped":
        out = flash_attention_merged(
            q.reshape(b, sq, h * d), k.reshape(b, -1, kvh * d),
            v.reshape(b, -1, kvh * d), h, causal=causal, scale=scale,
            window=window)
        return out.reshape(b, sq, h, d)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention(qt, kt, vt, causal=causal, scale=scale,
                          window=window)
    return jnp.swapaxes(out, 1, 2)
