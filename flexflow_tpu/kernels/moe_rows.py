"""The routed experts of a block of many rows (`ops/moe_ops.py`, `_experts`:
a prefill chunk's 16 384 (token, choice) rows, a wave's rungs) as one pallas
TPU kernel: over rows sorted by held expert, `sizes[e]` of them on expert e,

    y[group e] = act(x[group e] W_in[e]) W_out[e]

where the grouped-product form makes two `jax.lax.ragged_dot` calls with
the activation between them and writes `[rows, width (* 2)]` and `[rows,
width]` to HBM on the way. Here neither leaves the chip.

The grid is (visit, tile of the middle width). A VISIT is one row tile of
`tm` rows under one expert: rows sorted by expert do not start on tile
boundaries, so a tile that holds the end of one group and the start of the
next is visited once for each (`group_visits`: scalar-prefetched expert,
row tile and the group's row range a visit, their count the first grid
bound, known at run time; an expert with no row has no visit and its bytes
are never touched). The rows are NOT laid out again with each group on a
tile boundary: the gather and the combine around the kernel stay the
grouped form's. A grid step holds the row tile `[tm, K]`, tile j of the
expert's `w_in` (`[K, tn]`; for the gated expert the matching columns of
both halves of `[a | b]`, the same array under two block specs) and rows j
of its `w_out` (`[tn, K]`): x times the first, the activation in f32 on the
tile (`silu(a) * b` or `relu(a)^2`), times the second, summed over the
width tiles in f32 in VMEM, and stored UNDER THE GROUP'S MASK into the
visit's `[tm, K]` block of the result, which stays in VMEM while
consecutive visits name the same row tile. Consecutive visits of one
expert name the same weight blocks, which the pipeline then does not fetch
again: where the whole width is one tile an expert's matrices cross the
HBM once, whatever its group's size. Rows past the last group are never
written (the callers select them away) and no expert is read for them.

The roundings are `moe_ops._experts`': `ab` to the compute type before the
activation, `mid` to the compute type before the second product, the
result to the compute type once the width tiles are summed.

Tiles from the shapes (`row_tiles`): whole K, `tn` by `moe_step`'s rule
(the largest part of the width in whole 128-lane slabs whose weight tiles
fit the budget twice), `tm` = `ROW_TILE`. Forward only: `ops/moe_ops`
gives it a `custom_vjp` whose backward differentiates the grouped-product
form over the same rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from flexflow_tpu.kernels import moe_step
from flexflow_tpu.kernels.flash_attention import _interpret

# the rows of a visit. Swept on the chip over 128, 256 and 512 (PERF.md, PR
# 57: 128 and 256 read the same where the whole width is one tile, 256 is
# ahead where an expert streams again each visit, 512 behind everywhere)
ROW_TILE = 256
_VMEM_LIMIT_BYTES = 96 * 1024 * 1024


def row_tiles(rows: int, k_dim: int, width: int, parts: int, itemsize: int):
    """(tm, tn): the row tile and the tile of the middle width a grid step
    takes, or None where the kernel does not take the block (the grouped
    product does): it wants whole row tiles, and K, the width and its tile
    in whole 128-lane slabs. `parts`: the matrices `w_in` holds side by
    side (2 gated, 1 not)."""
    tn = moe_step.width_tile(k_dim, width, parts, itemsize)
    if tn is None or rows < ROW_TILE or rows % ROW_TILE:
        return None
    return ROW_TILE, tn


def group_visits(sizes, rows: int, tm: int):
    """The kernel's walk over `rows` rows sorted by expert, `sizes[e]` of
    them on held expert e -> (expert, row tile, first row, row past the
    last, each `[rows / tm + held - 1]` int32, a visit; how many visits
    there are). Visit v is one row tile under one expert, experts in order
    and an expert's tiles in order; places past the count repeat in-range
    blocks and name no row. By rank and compare: no sort, no scatter."""
    held_n = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    visits = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(visits)
    place = jnp.arange(rows // tm + held_n - 1, dtype=jnp.int32)
    expert = jnp.minimum(
        jnp.sum(visit_ends[None] <= place[:, None], axis=1, dtype=jnp.int32),
        held_n - 1)
    tile = first[expert] + place - (visit_ends - visits)[expert]
    live = place < visit_ends[-1]
    return (expert, jnp.clip(tile, 0, rows // tm - 1),
            jnp.where(live, starts[expert], 0),
            jnp.where(live, ends[expert], 0), visit_ends[-1])


def _kernel(ids_ref, tile_ref, lo_ref, hi_ref, x_ref, *refs, relu2: bool,
            tiles: int):
    """One (visit, tile of the width). x_ref `[tm, K]`; then the tile of
    `w_in` `[1, K, tn]` (two of them for the gated expert), of `w_out`
    `[1, tn, K]`, y_ref `[tm, K]`, and where the width has several tiles
    the f32 sum over them `[tm, K]`."""
    del ids_ref         # the index maps read it
    a_ref, *b_ref, out_ref, y_ref = refs[:3 if relu2 else 4]
    f32 = jnp.float32
    v, j = pl.program_id(0), pl.program_id(1)
    x = x_ref[...]
    dt = x.dtype

    def into(w_ref):
        return jnp.dot(x, w_ref[0], preferred_element_type=f32) \
            .astype(dt).astype(f32)

    a = into(a_ref)
    mid = jnp.square(jnp.maximum(a, 0.0)) if relu2 \
        else a * jax.nn.sigmoid(a) * into(b_ref[0])
    out = jnp.dot(mid.astype(dt), out_ref[0], preferred_element_type=f32)

    def store(out):
        # the rows of this visit's group alone: the tile's other rows are
        # another visit's, or nobody's
        row = tile_ref[v] * x.shape[0] \
            + jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
        mine = (row >= lo_ref[v]) & (row < hi_ref[v])
        y_ref[...] = jnp.where(mine, out.astype(dt), y_ref[...])

    if tiles == 1:
        store(out)
        return
    acc_ref = refs[-1]

    @pl.when(j == 0)
    def _():
        acc_ref[...] = out

    @pl.when(j > 0)
    def _():
        acc_ref[...] += out

    @pl.when(j == tiles - 1)
    def _():
        store(acc_ref[...])


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _call(x, sizes, w_in, w_out, relu2, tm, tn, interpret):
    from jax.experimental.pallas import tpu as pltpu

    rows, k_dim = x.shape
    tiles = w_out.shape[1] // tn
    halves = 1 if relu2 else 2      # of `w_in`: `[a]`, or `[a | b]`
    *walk, count = group_visits(sizes, rows, tm)

    def w_in_tile(half):
        return pl.BlockSpec((1, k_dim, tn), lambda v, j, ids, *_:
                            (ids[v], 0, half * tiles + j))

    def row_tile(v, j, ids, tile, *_):
        return tile[v], 0

    return pl.pallas_call(
        functools.partial(_kernel, relu2=relu2, tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(count, tiles),
            in_specs=[pl.BlockSpec((tm, k_dim), row_tile)]
            + [w_in_tile(half) for half in range(halves)]
            + [pl.BlockSpec((1, tn, k_dim),
                            lambda v, j, ids, *_: (ids[v], j, 0))],
            out_specs=pl.BlockSpec((tm, k_dim), row_tile),
            scratch_shapes=[pltpu.VMEM((tm, k_dim), jnp.float32)]
            if tiles > 1 else []),
        out_shape=jax.ShapeDtypeStruct((rows, k_dim), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ff_moe_rows",
    )(*walk, x, *([w_in] * halves), w_out)


def moe_rows(x, sizes, w_in, w_out, relu2: bool, tm: int, tn: int):
    """x `[rows, K]` sorted by held expert, `sizes` `[held]` int32 rows on
    each; w_in `[held, K, width (* 2 gated)]`, w_out `[held, width, K]` in
    x's type; `tm`, `tn` as `row_tiles` says -> `[rows, K]` in x's type,
    the rows past the last group not written. Interpreted on the CPU; the
    layers of a program that call it at one shape trace its body once."""
    return _call(x, sizes, w_in, w_out, relu2, tm, tn, _interpret())
