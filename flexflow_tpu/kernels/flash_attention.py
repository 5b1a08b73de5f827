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
summed over each group.

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


def _in_band(offset, bq: int, bk: int, window: int):
    """(bq, bk) bool: the pairs whose query lies under `window` after the
    key, the tile's first query `offset` after its first key."""
    ahead = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        - jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return ahead < window - offset


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


def _params(seq: int = 0, depth: int = 0, itemsize: int = 0,
            vectors: int = 0):
    """The kernels' compiler parameters. A grid step holds two whole-
    sequence operands of `depth` (k and v, or q and do) and `vectors`
    per-row f32 columns (lse, delta: a `[seq, 1]` block lies in VMEM a
    128-lane tile a row), each double-buffered: where that passes
    `_VMEM_ASK_FROM` (8192 x 128 does in the backward) the call states its
    own scope, the resident bytes and the default beside them."""
    from jax.experimental.pallas import tpu as pltpu

    # batch/head/q-block grid dims are independent; lets Mosaic pipeline them
    semantics = ("parallel", "parallel", "arbitrary")
    resident = 2 * seq * (2 * depth * itemsize + vectors * 128 * 4)
    if resident <= _VMEM_ASK_FROM:
        return pltpu.CompilerParams(dimension_semantics=semantics)
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=resident + _VMEM_DEFAULT_SCOPE)


def _traced_once(arrays: int):
    """`call(*arrays, causal, scale, bq, bk, window, interpret)` under `jax.jit`,
    all but the arrays static: a model's layers call a kernel at one shape,
    and a trace of the step then traces and lowers the kernel's body once
    and not once a layer (the static schedule under the diagonal is
    straight-line code, twice the parent's loop to trace). Interpret mode
    is part of the key: tests switch it within a process."""
    def wrap(call):
        jitted = jax.jit(call, static_argnums=tuple(range(arrays, arrays + 6)))
        return lambda *args, window=0: jitted(*args, window, _interpret())
    return wrap


def _under_diagonal(q_start, k_start, bq: int, bk: int):
    """(bq, bk) bool: the pairs of a tile the diagonal crosses whose query
    is at or after the key."""
    ahead = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        - jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return ahead >= k_start - q_start


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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, bq, bk,
                window=0):
    gq, d = q_ref.shape[2:]
    sk = k_ref.shape[2]
    qi = pl.program_id(2)
    block_k = gq if causal else bk

    def finish(carry, rows):
        m, l, acc = carry
        o_ref[0, 0, rows, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, 0, rows, :] = m + jnp.log(l)         # (rows, 1)

    def whole_block(ki, carry, banded=False):
        keys = _at(ki * block_k, 0, block_k, block_k)
        s = _nt(q_ref[0, 0], k_ref[0, 0, keys, :]) * scale
        if banded:
            s = jnp.where(_in_band((qi - ki) * gq, gq, block_k, window), s,
                          _OUT_OF_BAND)
        return _online(carry, [(s, v_ref[0, 0, keys, :])])

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
        q = q_ref[0, 0, rows, :]
        full, visit = _k_tile_bounds(q_at, bq, bk)
        lo = min(_band_k_tile(q_at, bk, window), full) if banded else 0
        parts = []
        if full > lo:
            keys = _at(base, lo * bk, (full - lo) * bk, bk)
            s = _nt(q, k_ref[0, 0, keys, :]) * scale
            if banded:
                s = jnp.where(_in_band(q_at - lo * bk, *s.shape, window), s,
                              _NEG_INF)
            parts.append((s, v_ref[0, 0, keys, :]))
        keys = _at(base, full * bk, (visit - full) * bk, bk)
        s = _nt(q, k_ref[0, 0, keys, :]) * scale
        # every row meets its own position here: its max is finite
        keep = _under_diagonal(q_at, full * bk, *s.shape)
        if banded:
            keep &= _in_band(q_at - full * bk, *s.shape, window)
        s = jnp.where(keep, s, _NEG_INF)
        parts.append((s, v_ref[0, 0, keys, :]))
        before = None if carry is None else tuple(x[rows] for x in carry)
        finish(_online(before, parts), rows)


def _grid_block(seq: int, depth: int, itemsize: int, causal: bool,
                tile: int, other: int) -> int:
    """Rows of q (fwd, dq) or k (dkv) a grid step holds: a non-causal
    step its tile; a causal one the budget's bound, tiled inside."""
    return max(_bound(seq, depth, itemsize), tile, other) if causal else tile


def _kv_index(group: int, whole: bool):
    """The block index of a K/V operand for query head `h_`: its group's
    K/V head (`group` query heads read one), the whole sequence or the
    grid step's block."""
    if group == 1:
        return (lambda b_, h_, i: (b_, h_, 0, 0)) if whole \
            else (lambda b_, h_, i: (b_, h_, i, 0))
    return (lambda b_, h_, i: (b_, h_ // group, 0, 0)) if whole \
        else (lambda b_, h_, i: (b_, h_ // group, i, 0))


def _band_args(window: int):
    """A kernel's keyword for the window, none without one (the partial of
    a call without a window is the one it was)."""
    return {"window": window} if window else {}


@_traced_once(3)
def _fwd_call(q, k, v, causal, scale, bq, bk, window, interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    gq = _grid_block(sq, d, q.dtype.itemsize, causal, bq, bk)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, **_band_args(window))
    k_full = pl.BlockSpec((1, 1, sk, d), _kv_index(h // k.shape[1], True))
    return pl.pallas_call(
        kernel,
        grid=(b, h, sq // gq),
        in_specs=[
            pl.BlockSpec((1, 1, gq, d), lambda b_, h_, i: (b_, h_, i, 0)),
            k_full,
            k_full,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, gq, d), lambda b_, h_, i: (b_, h_, i, 0)),
            # lse is (b, h, sq, 1): the trailing singleton keeps the block's
            # last-two dims TPU-tileable ((gq, 1) with 1 == full array dim)
            pl.BlockSpec((1, 1, gq, 1), lambda b_, h_, i: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        compiler_params=_params(sk, d, k.dtype.itemsize),
        interpret=interpret,
        name="ff_flash_attention_fwd",
    )(q, k, v)


def _fwd(q, k, v, causal, scale, window=0):
    """q: (b, h, sq, d); k/v: (b, h or its K/V heads, sk, d) -> (o, lse)."""
    bq, bk = _tiles("fwd", q.shape[2], k.shape[2], q.shape[3],
                    q.dtype.itemsize, causal)
    return _fwd_call(q, k, v, causal, scale, bq, bk, window=window)


# -------------------------------------------------------------------- backward
def _ds(q, k, v, do, lse, delta, scale, mask):
    """(p, ds) f32 (rows, keys) of some q rows against some keys, the
    probabilities recomputed from the saved lse; `mask` where the diagonal
    (or a band's edge) crosses."""
    p = jnp.exp(_nt(q, k) * scale - lse)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    return p, p * (_nt(do, v) - delta) * scale


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *, scale, causal, bq, bk, window=0):
    gq, d = q_ref.shape[2:]
    sk = k_ref.shape[2]
    qi = pl.program_id(2)
    block_k = gq if causal else bk

    def dq_of(rows, keys, mask=None):
        k = k_ref[0, 0, keys, :]
        _, ds = _ds(q_ref[0, 0, rows, :], k, v_ref[0, 0, keys, :],
                    do_ref[0, 0, rows, :], lse_ref[0, 0, rows, :],
                    delta_ref[0, 0, rows, :], scale, mask)
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
        dq_ref[0, 0] = dq.astype(dq_ref.dtype)
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
        dq_ref[0, 0, rows, :] = acc.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                *, scale, causal, bq, bk, window=0):
    gk, d = k_ref.shape[2:]
    sq = q_ref.shape[2]
    kj = pl.program_id(2)
    block_q = gk if causal else bq

    def dkv_of(rows, keys, mask=None):
        q, do = q_ref[0, 0, rows, :], do_ref[0, 0, rows, :]
        p, ds = _ds(q, k_ref[0, 0, keys, :], v_ref[0, 0, keys, :], do,
                    lse_ref[0, 0, rows, :], delta_ref[0, 0, rows, :], scale,
                    mask)
        return _tn(ds.astype(q.dtype), q), _tn(p.astype(do.dtype), do)

    def whole_block(qi, carry, banded=False):
        mask = _in_band((qi - kj) * gk, block_q, gk, window) if banded \
            else None
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
        dk_ref[0, 0] = carry[0].astype(dk_ref.dtype)
        dv_ref[0, 0] = carry[1].astype(dv_ref.dtype)
        return
    base = 0 if gk == sq else kj * gk
    banded = 0 < window < gk
    for k_at in range(0, gk, bk):
        keys = slice(k_at, k_at + bk)
        first, full = _q_tile_bounds(k_at, bq, bk)
        crossed = (full - first) * bq
        keep = _under_diagonal(first * bq, k_at, crossed, bk)
        if banded:
            keep &= _in_band(first * bq - k_at, crossed, bk, window)
        dk, dv = dkv_of(_at(base, first * bq, crossed, bq), keys, keep)
        last = max(full, _band_q_tile(k_at, bq, bk, window, gk)) if banded \
            else gk // bq
        if full < last:
            more = dkv_of(
                _at(base, full * bq, (last - full) * bq, bq), keys,
                _in_band(full * bq - k_at, (last - full) * bq, bk, window)
                if banded else None)
            dk, dv = dk + more[0], dv + more[1]
        if carry is not None:
            dk, dv = dk + carry[0][keys], dv + carry[1][keys]
        dk_ref[0, 0, keys, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0, keys, :] = dv.astype(dv_ref.dtype)


@_traced_once(6)
def _dq_call(q, k, v, g, lse, delta, causal, scale, bq, bk, window, interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    gq = _grid_block(sq, d, q.dtype.itemsize, causal, bq, bk)
    q_spec = pl.BlockSpec((1, 1, gq, d), lambda b_, h_, i: (b_, h_, i, 0))
    k_full = pl.BlockSpec((1, 1, sk, d), _kv_index(h // k.shape[1], True))
    vec_q = pl.BlockSpec((1, 1, gq, 1), lambda b_, h_, i: (b_, h_, i, 0))
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                          **_band_args(window)),
        grid=(b, h, sq // gq),
        in_specs=[q_spec, k_full, k_full, q_spec, vec_q, vec_q],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        compiler_params=_params(sk, d, k.dtype.itemsize),
        interpret=interpret,
        name="ff_flash_attention_dq",
    )(q, k, v, g, lse, delta)


@_traced_once(6)
def _dkv_call(q, k, v, g, lse, delta, causal, scale, bq, bk, window, interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    group = h // k.shape[1]
    gk = _grid_block(sk, d, k.dtype.itemsize, causal, bk, bq)
    q_full = pl.BlockSpec((1, 1, sq, d), lambda b_, h_, i: (b_, h_, 0, 0))
    k_in = pl.BlockSpec((1, 1, gk, d), _kv_index(group, False))
    # a query head's own dk, dv: in f32 where a group's are summed after
    k_out = pl.BlockSpec((1, 1, gk, d), lambda b_, h_, i: (b_, h_, i, 0))
    vec_full = pl.BlockSpec((1, 1, sq, 1), lambda b_, h_, i: (b_, h_, 0, 0))
    out_dt = (k.dtype, v.dtype) if group == 1 else (jnp.float32, jnp.float32)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                          **_band_args(window)),
        grid=(b, h, sk // gk),
        in_specs=[q_full, k_in, k_in, q_full, vec_full, vec_full],
        out_specs=[k_out, k_out],
        out_shape=[jax.ShapeDtypeStruct((b, h, sk, d), out_dt[0]),
                   jax.ShapeDtypeStruct((b, h, sk, d), out_dt[1])],
        compiler_params=_params(sq, d, q.dtype.itemsize, vectors=2),
        interpret=interpret,
        name="ff_flash_attention_dkv",
    )(q, k, v, g, lse, delta)


def _bwd(causal, scale, window, res, g):
    q, k, v, o, lse = res
    # FLEXFLOW_FLASH_BLOCK_BWD tunes the backward independently (the dq /
    # dkv kernels have different VMEM/recompute balance than the forward);
    # unset = inherit FLEXFLOW_FLASH_BLOCK's choice
    shape = (q.shape[2], k.shape[2], q.shape[3], q.dtype.itemsize, causal)
    do = g.astype(jnp.float32)
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1, keepdims=True)  # (b, h, sq, 1)
    dq = _dq_call(q, k, v, g, lse, delta, causal, scale, *_tiles("dq", *shape),
                  window=window)
    dk, dv = _dkv_call(q, k, v, g, lse, delta, causal, scale,
                       *_tiles("dkv", *shape), window=window)
    if k.shape[1] != q.shape[1]:    # a K/V head's: its group's, summed
        b, kvh, sk, d = k.shape
        dk, dv = (x.reshape(b, kvh, -1, sk, d).sum(axis=2).astype(k.dtype)
                  for x in (dk, dv))
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, scale, window):
    return _fwd(q, k, v, causal, scale, window)[0]


def _flash_fwd(q, k, v, causal, scale, window):
    """The forward rule: both results of the kernel named `FLASH_KEPT` (a
    recomputation drops the call only where every result is kept). `lse` is
    named FLAT: as `(b, h, s, 1)` float32 the chip pads its last dimension
    to 128 lanes, 128 times its size where it survives a recomputation."""
    o, lse = _fwd(q, k, v, causal, scale, window)
    o = checkpoint_name(o, FLASH_KEPT)
    lse = checkpoint_name(lse.reshape(-1), FLASH_KEPT).reshape(lse.shape)
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


# ------------------------------------------------------------------ public API
def flash_attention(q, k, v, causal: bool = False, scale: float | None = None,
                    window: int = 0):
    """q: (b, h, sq, d), k/v: (b, h or K/V heads that divide h, sk, d) ->
    (b, h, sq, d). `window` (causal only): query t sees the keys
    t - window < s <= t; 0, or one that holds the sequence: every s <= t.

    Raises ValueError when shapes don't qualify (sequence not divisible by a
    block size, causal with sq != sk) — callers precheck with
    flash_supported; a kernel that was chosen and then fails must fail.
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError(f"expected rank-4 q/k/v, got {q.shape}/{k.shape}/{v.shape}")
    if causal and q.shape[2] != k.shape[2]:
        raise ValueError("causal flash attention requires sq == sk "
                         f"(got {q.shape[2]} vs {k.shape[2]})")
    if k.shape[2] != v.shape[2]:
        raise ValueError(f"k/v length mismatch {k.shape} vs {v.shape}")
    if k.shape[1] != v.shape[1] or q.shape[1] % k.shape[1]:
        raise ValueError(f"{k.shape[1]}/{v.shape[1]} K/V heads under "
                         f"{q.shape[1]} query heads")
    if window < 0 or (window and not causal):
        raise ValueError(f"window {window} on a call that is not causal")
    window = 0 if window >= k.shape[2] else int(window)
    _pick_block(q.shape[2], q.shape[3], q.dtype.itemsize)
    _pick_block(k.shape[2], k.shape[3], k.dtype.itemsize)
    for s_, d_, it in ((q.shape[2], q.shape[3], q.dtype.itemsize),
                      (k.shape[2], k.shape[3], k.dtype.itemsize)):
        if 2 * s_ * d_ * it > _VMEM_SEQ_BYTES:
            # the Mosaic-reject precheck (same bound as flash_supported):
            # shapes whose VMEM-resident operands can't fit raise HERE, at
            # trace time, with a message that names the shape
            raise ValueError(
                f"sequence {s_} x depth {d_} exceeds the VMEM-resident budget "
                f"({_VMEM_SEQ_BYTES} bytes); use the einsum or ring path")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    # one span a lowered call (trace time): the schedule each of the three
    # kernels takes at this shape, for tools/trace_report.py
    facts = {"window": window} if window else {}
    with tel.span("lower/flash_attention", cat="compile",
                  batch_heads=q.shape[0] * q.shape[1], seq_q=q.shape[2],
                  seq_k=k.shape[2], depth=q.shape[3], causal=bool(causal),
                  kernels=tile_plan(q.shape[2], k.shape[2], q.shape[3],
                                    q.dtype.itemsize, causal, window),
                  **facts):
        return _flash(q, k, v, causal, float(scale), window)


def flash_attention_qkv(q, k, v, causal: bool = False, scale: float | None = None,
                        window: int = 0):
    """Head-minor layout entry used by ops/attention_ops: q/k/v (b, s, h, d),
    returns (b, sq, h, d). Unsupported shapes raise ValueError."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention(qt, kt, vt, causal=causal, scale=scale,
                          window=window)
    return jnp.swapaxes(out, 1, 2)
