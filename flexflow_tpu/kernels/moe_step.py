"""One decode step's routed experts (`ops/moe_ops.py`, `moe_layer`) as one
pallas TPU kernel: for every held expert e that a live slot chose

    y += gate_e * (act(x W_in[e]) W_out[e])

over ALL of the step's (at most 16) rows at once, `gate_e[t]` the gate of
token t for expert e, or 0 where t did not choose it. Top-k picks distinct
experts, so an expert's group in a decode step holds at most as many rows
as the step has tokens: one bf16 sublane tile. Nothing has to be sorted or
gathered to multiply 16 rows, and a row that did not choose the expert is
multiplied all the same (the matrix is on the chip for the rows that did;
its weight is 0).

The grid is (hit expert, tile of the middle width): the expert of a grid
step is `ids[i]`, a scalar-prefetched compaction of the held experts with a
row, and the first grid bound is their count, known at run time (as
`kernels/retention_step.py` bounds its own by the live slots), so an expert
that no live slot chose costs nothing and its bytes are never touched. A
grid step (i, j) holds tile j of the expert's `w_in` (`[K, tn]`; for the
gated expert the matching columns of both halves of `[a | b]`, the same
array under two block specs) and rows j of its `w_out` (`[tn, K]`): x
`[16, K]` times the first, the activation in f32 on the tile (`silu(a) * b`
or `relu(a)^2`, with the roundings to the compute type that
`moe_ops._experts` makes), times the second, and `gate * out` added into
the one `[16, K]` f32 result, which stays in VMEM across the whole grid.
The second product is NOT rounded to the compute type before the gate (the
grouped product's result is): a tile holds a part of its contraction.

Tiles are sized for the stream, not for rows: whole K, and tn the largest
part of the width in whole 128-lane slabs whose tiles fit `_TILE_BYTES`
twice (the pipeline's two buffers): a whole expert of 11-19 MB a grid step
where it fits, 22 MB of an 88 MB one. Pallas's own double buffering brings
the next expert's first tile in while this one's last is multiplied.
Forward only: `ops/moe_ops` gives it a `custom_vjp` whose backward
differentiates the grouped-product form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from flexflow_tpu.kernels.flash_attention import _interpret

LANES = 128
# the rows of a grid step's left operand: a bf16 tile's sublanes
ROWS = 16
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# of which the weight tiles of a grid step, twice (the pipeline's two
# buffers); the rest holds x, the result and the tile's f32 intermediates
_TILE_BYTES = 48 * 1024 * 1024


def width_tile(k_dim: int, width: int, parts: int, itemsize: int):
    """The largest part `tn` of the middle width, in whole 128-lane slabs,
    whose weight tiles (`[K, tn]` of each of `w_in`'s `parts` matrices and
    `[tn, K]` of `w_out`) fit `_TILE_BYTES` twice; None where K or the
    width is not whole slabs. `kernels/moe_rows.py` sizes its own by it."""
    if k_dim % LANES or width % LANES:
        return None
    slabs = width // LANES
    for tiles in range(1, slabs + 1):
        tn = width // tiles
        if slabs % tiles == 0 \
                and 2 * (parts + 1) * k_dim * tn * itemsize <= _TILE_BYTES:
            return tn
    return None


def tile_width(tokens: int, k_dim: int, width: int, parts: int,
               itemsize: int):
    """The tile `tn` of the middle width a grid step takes, or None where
    the kernel does not take the block (the grouped product does): it wants
    at most `ROWS` tokens, and K, the width and the tile in whole 128-lane
    slabs. `parts`: the matrices `w_in` holds side by side (2 gated, 1
    not)."""
    if tokens > ROWS:
        return None
    return width_tile(k_dim, width, parts, itemsize)


def hit_experts(sizes, pairs: int):
    """(ids `[min(held, pairs)]` int32, count): the held experts with a row
    (`sizes[e] > 0`), in order, and how many they are; ids past the count
    are 0. By rank and compare: no sort, no scatter."""
    held_n = sizes.shape[0]
    hit = sizes > 0
    rank = jnp.cumsum(hit.astype(jnp.int32)) - 1
    place = jnp.arange(min(held_n, pairs), dtype=jnp.int32)[:, None]
    ids = jnp.sum(jnp.where(hit[None] & (rank[None] == place),
                            jnp.arange(held_n, dtype=jnp.int32)[None], 0),
                  axis=1)
    return ids, jnp.sum(hit.astype(jnp.int32))


def _kernel(ids_ref, x_ref, gate_ref, *refs, relu2: bool):
    """One (hit expert, tile). x_ref `[16, K]`; gate_ref `[1, 16, 1]` f32;
    then the tile of `w_in` `[1, K, tn]` (two of them for the gated
    expert), of `w_out` `[1, tn, K]`, and y_ref `[16, K]` f32, one block
    the whole grid long."""
    del ids_ref         # the index maps read it
    if relu2:
        a_ref, out_ref, y_ref = refs
    else:
        a_ref, b_ref, out_ref, y_ref = refs
    f32 = jnp.float32

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    x = x_ref[...]
    dt = x.dtype

    def into(w_ref):
        return jnp.dot(x, w_ref[0], preferred_element_type=f32) \
            .astype(dt).astype(f32)

    a = into(a_ref)
    mid = jnp.square(jnp.maximum(a, 0.0)) if relu2 \
        else a * jax.nn.sigmoid(a) * into(b_ref)
    out = jnp.dot(mid.astype(dt), out_ref[0], preferred_element_type=f32)
    y_ref[...] += gate_ref[0] * out


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _call(ids, count, x, gates, w_in, w_out, relu2, tn, interpret):
    from jax.experimental.pallas import tpu as pltpu

    tokens, k_dim = x.shape
    tiles = w_out.shape[1] // tn
    halves = 1 if relu2 else 2      # of `w_in`: `[a]`, or `[a | b]`
    x = jnp.pad(x, [(0, ROWS - tokens), (0, 0)])
    gates = jnp.pad(gates, [(0, 0), (0, ROWS - tokens)])[..., None]

    def w_in_tile(half):
        return pl.BlockSpec((1, k_dim, tn), lambda i, j, ids:
                            (ids[i], 0, half * tiles + j))

    y = pl.pallas_call(
        functools.partial(_kernel, relu2=relu2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(count, tiles),
            in_specs=[pl.BlockSpec((ROWS, k_dim), lambda i, j, ids: (0, 0)),
                      pl.BlockSpec((1, ROWS, 1),
                                   lambda i, j, ids: (ids[i], 0, 0))]
            + [w_in_tile(half) for half in range(halves)]
            + [pl.BlockSpec((1, tn, k_dim),
                            lambda i, j, ids: (ids[i], j, 0))],
            out_specs=pl.BlockSpec((ROWS, k_dim), lambda i, j, ids: (0, 0))),
        out_shape=jax.ShapeDtypeStruct((ROWS, k_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ff_moe_step",
    )(ids, x, gates, *([w_in] * halves), w_out)
    # no expert hit: no grid step ran, nothing wrote y
    return jnp.where(count > 0, y[:tokens], 0.0)


def moe_step(ids, count, x, gates, w_in, w_out, relu2: bool, tn: int):
    """x `[tokens <= 16, K]`; gates `[held, tokens]` f32, expert e's gate of
    each token (0 where the token did not choose it); ids, count as
    `hit_experts` gives them; w_in `[held, K, width (* 2 gated)]`, w_out
    `[held, width, K]` in x's type; `tn` as `tile_width` says -> the gated
    sum over the hit experts `[tokens, K]` f32. Interpreted on the CPU; the
    layers of a program that call it at one shape trace its body once."""
    return _call(ids, count, x, gates, w_in, w_out, relu2, tn, _interpret())
