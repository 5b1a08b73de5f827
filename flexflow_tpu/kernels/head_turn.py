"""A head's RMS norm and rotate-half rotary turn on the MERGED axis, as a
pallas TPU kernel: what `multihead_attention` does to q and k between the
projection and the scores, where a head is whole 128-lane slabs.

`x @ wq` writes `[b, s, h * d]` and the flash kernels' merged entry
(kernels/flash_attention.py) reads exactly that. The norm and the rotation
are per head, and XLA works them on `[b, s, h, d]`: on the chip a head's
rows then lie eight heads a tile where the merged axis holds eight
POSITIONS a tile, so the split is a relayout of the whole tensor, and the
norm's factor and the rotation's halves come back as float32 broadcasts and
reshapes of the same size (PERF.md, Findings PR 63: five float32 passes a
tensor in the compiled step). Here a grid step holds some rows of a few
heads `[rows, heads_a_step * d]`: a head is a static lane slab, its sum of
squares one lane reduction, its rotation by half a head one lane roll by
`d / 2` (the pairs (i, i + d / 2) of ops/rotary.apply_rope_half; the sign
of the first half is folded into the sine's table by the caller); one pass
over the operand in its own dtype.

The arithmetic is ops/norm_ops.rms_norm's then apply_rope_half's, step for
step, statistics in float32, as the CHIP's compiler runs the two one after
the other: `rms_norm` states a rounding of the normed head to the operand's
dtype before `apply_rope_half` widens it again, and XLA on the TPU drops
that pair of conversions, forward and in the gradient (measured, PERF.md
Findings PR 63, review round: with the rounding kept, a third of a bf16
tensor's values sat one unit off the XLA form's; `rsqrt` and the sum of
squares are the same to the bit in Mosaic and in XLA). So the head stays
float32 between the norm and the turn here too, and is rounded once, where
it is stored. The backward is the transposition of the same steps, made
here and not by autodiff: d gamma leaves as partial sums a row block, which
XLA adds up.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from flexflow_tpu.kernels.flash_attention import _interpret

_ROWS = (512, 256, 128, 64, 32, 16)
# lanes a grid step holds: eight heads of 128
_LANES = 1024


def turn_supported(seq: int, depth: int) -> bool:
    """Whether the kernel covers a sequence of heads of `depth`: whole
    128-lane slabs, and rows in whole tiles of either dtype."""
    return depth % 128 == 0 and seq % _ROWS[-1] == 0


def _tiles(seq: int, heads: int, depth: int):
    """(rows, heads) a grid step holds."""
    rows = next(r for r in _ROWS if seq % r == 0)
    step = max(1, _LANES // depth)
    while heads % step:
        step -= 1
    return rows, step


def _roll(x, shift: int):
    """x rolled along its lanes (the last axis)."""
    from jax.experimental.pallas import tpu as pltpu

    return jnp.roll(x, shift, axis=-1) if _interpret() \
        else pltpu.roll(x, shift, x.ndim - 1)


def _unit(xf, eps):
    """(x r, r) float32 of one head's rows, r = 1 / sqrt(mean(x^2) + eps):
    the norm is x r gamma."""
    r = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return xf * r, r


def _fwd_kernel(*refs, depth, eps, norm, turn):
    x_ref, o_ref = refs[0], refs[-1]
    gamma = refs[1][...].astype(jnp.float32) if norm else None
    cos, sin = (r[...] for r in refs[1 + norm:3 + norm]) if turn \
        else (None, None)
    for j in range(x_ref.shape[-1] // depth):
        lanes = pl.ds(j * depth, depth)
        # the normed head goes into the turn in float32, as the chip's
        # compiler hands it from `rms_norm` to `apply_rope_half`
        y = x_ref[:, lanes].astype(jnp.float32)
        if norm:
            y = _unit(y, eps)[0] * gamma
        if turn:
            y = y * cos + _roll(y, depth // 2) * sin
        o_ref[:, lanes] = y.astype(o_ref.dtype)


def _bwd_kernel(*refs, depth, eps, norm, turn):
    """(dx, d gamma's partial sum over this step's rows and heads) from x
    and d out: the forward's steps transposed, last first."""
    x_ref, g_ref = refs[0], refs[1]
    dx_ref = refs[2 + norm + 2 * turn]
    gamma = refs[2][...].astype(jnp.float32) if norm else None
    cos, sin = (r[...] for r in refs[2 + norm:4 + norm]) if turn \
        else (None, None)
    dgamma = jnp.zeros((1, depth), jnp.float32)
    for j in range(x_ref.shape[-1] // depth):
        lanes = pl.ds(j * depth, depth)
        dy = g_ref[:, lanes].astype(jnp.float32)
        if turn:    # out_i = y_i cos_i + y_(i - d/2) sin_i
            dy = dy * cos + _roll(dy * sin, depth // 2)
        if norm:
            xr, r = _unit(x_ref[:, lanes].astype(jnp.float32), eps)
            t = dy * gamma
            dgamma = dgamma + jnp.sum(dy * xr, axis=0, keepdims=True)
            dy = r * (t - xr * jnp.mean(t * xr, axis=-1, keepdims=True))
        dx_ref[:, lanes] = dy.astype(dx_ref.dtype)
    if norm:
        dg_ref = refs[-1]

        @pl.when(pl.program_id(2) == 0)
        def _():
            dg_ref[...] = jnp.zeros_like(dg_ref)

        dg_ref[...] += dgamma


def _specs(seq, heads, depth, norm, turn):
    rows, step = _tiles(seq, heads, depth)
    x_spec = pl.BlockSpec((None, rows, step * depth),
                          lambda b_, i, h_: (b_, i, h_))
    table = pl.BlockSpec((None, rows, depth), lambda b_, i, h_: (b_, i, 0))
    gamma = pl.BlockSpec((1, depth), lambda b_, i, h_: (0, 0))
    return rows, step, x_spec, [gamma] * norm + [table, table] * turn


def _params(reduces: bool):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "arbitrary" if reduces else "parallel"))


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _fwd_call(x, gamma, cos, sin, heads, eps, interpret):
    b, s, e = x.shape
    d = e // heads
    norm, turn = gamma is not None, cos is not None
    rows, step, x_spec, others = _specs(s, heads, d, norm, turn)
    operands = [x] + ([gamma.reshape(1, d)] if norm else []) + [cos, sin] * turn
    return pl.pallas_call(
        functools.partial(_fwd_kernel, depth=d, eps=eps, norm=norm, turn=turn),
        grid=(b, s // rows, heads // step),
        in_specs=[x_spec] + others, out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_params(False), interpret=interpret,
        name="ff_head_turn_fwd")(*operands)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _bwd_call(x, g, gamma, cos, sin, heads, eps, interpret):
    b, s, e = x.shape
    d = e // heads
    norm, turn = gamma is not None, cos is not None
    rows, step, x_spec, others = _specs(s, heads, d, norm, turn)
    operands = [x, g] + ([gamma.reshape(1, d)] if norm else []) \
        + [cos, sin] * turn
    # d gamma: one row a (batch, row block), summed over the heads inside
    part = pl.BlockSpec((None, None, 1, d), lambda b_, i, h_: (b_, i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, depth=d, eps=eps, norm=norm, turn=turn),
        grid=(b, s // rows, heads // step),
        in_specs=[x_spec, x_spec] + others,
        out_specs=[x_spec] + [part] * norm,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)]
        + [jax.ShapeDtypeStruct((b, s // rows, 1, d), jnp.float32)] * norm,
        compiler_params=_params(norm), interpret=interpret,
        name="ff_head_turn_bwd")(*operands)
    return out[0], (jnp.sum(out[1], axis=(0, 1, 2)) if norm else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def head_turn(x, gamma, cos, sin, heads: int, eps: float):
    """x `[b, s, heads * d]` -> the same: each head's RMS norm with weight
    `gamma` `[d]` (None: no norm), then its rotate-half turn by the tables
    `cos`, `sin` `[b, s, d]` float32 (None: no turn), `sin` already signed
    (minus over the first half of a head). Differentiable in x and gamma.
    Tables of any other shape raise ValueError (their block follows the
    batch and the rows: one broadcast over the batch would be read past
    its end)."""
    table = (x.shape[0], x.shape[1], x.shape[2] // heads)
    for t in (cos, sin):
        if t is not None and (t.shape != table or t.dtype != jnp.float32):
            raise ValueError(f"head_turn: a table {t.dtype}{list(t.shape)} "
                             f"where float32{list(table)} is read")
    return _fwd_call(x, gamma, cos, sin, heads, eps, _interpret())


def _turn_fwd(x, gamma, cos, sin, heads, eps):
    return head_turn(x, gamma, cos, sin, heads, eps), (x, gamma, cos, sin)


def _turn_bwd(heads, eps, res, g):
    x, gamma, cos, sin = res
    dx, dgamma = _bwd_call(x, g, gamma, cos, sin, heads, eps, _interpret())
    zero = lambda t: None if t is None else jnp.zeros_like(t)   # noqa: E731
    return (dx, None if gamma is None else dgamma.astype(gamma.dtype),
            zero(cos), zero(sin))


head_turn.defvjp(_turn_fwd, _turn_bwd)
