"""Fused sparse cross-entropy as a pallas TPU kernel.

Capability replaced: the `optax.softmax_cross_entropy_with_integer_labels`
path in losses.py, which needs an f32 copy of the logits plus a same-shape
log-softmax intermediate — for a language model the [B, S, vocab] logits are
the single largest activation, and the reference path holds three copies of
it live at the loss. Here the loss is computed blockwise with an online
log-sum-exp over the vocab axis (the 1-D analog of flash attention's online
softmax): each (row-block, vocab-block) grid step streams one logits tile
through VMEM, carrying running max / sum / picked-logit statistics in f32
scratch, so the forward pass keeps the logits in their native dtype and
never materializes an f32 [N, vocab] array.

The custom VJP computes d_logits = g/N * (softmax - onehot) tile-by-tile
from the saved per-row logsumexp — one output-dtype [N, vocab] array (the
gradient the lm_head matmul needs anyway), again with no f32 blow-up.

Mode gate (mirrors flash attention's auto precheck): "auto" uses the kernel
whenever the shape/dtype qualify (falling back to the optax path otherwise),
"on" forces it and raises on unsupported shapes, "off" never fuses. On CPU
the kernel runs in pallas interpret mode, so parity tests cover the same
code path the TPU executes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec

from flexflow_tpu.kernels.partition import (local_shape, multi_device,
                                            per_shard)
from flexflow_tpu.parallel.sharding import used_axes

_NEG_INF = float("-inf")
_ROW_BLOCKS = (256, 128, 64, 32, 16, 8)
_VOCAB_BLOCKS = (2048, 1024, 512, 256, 128)
# one logits tile per grid step; three tiles of headroom (x, exp, dx) keeps
# the kernel far under the ~16MB VMEM budget at any candidate pairing
_VMEM_TILE_BYTES = 512 * 1024


def _pick_blocks(n: int, v: int, itemsize: int):
    """Largest (row, vocab) blocks dividing (n, v) under the tile budget,
    or None when no pairing qualifies (caller falls back to optax)."""
    bn = next((b for b in _ROW_BLOCKS if n % b == 0), None)
    if bn is None:
        return None
    bv = next((b for b in _VOCAB_BLOCKS
               if v % b == 0 and bn * b * itemsize <= _VMEM_TILE_BYTES), None)
    if bv is None:
        return None
    return bn, bv


def fused_ce_supported(shape, dtype) -> bool:
    """Whether the fused kernel covers logits of this shape/dtype."""
    try:
        dt = jnp.dtype(dtype)
    except TypeError:
        return False
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    if len(shape) < 2:
        return False
    v = int(shape[-1])
    n = 1
    for s in shape[:-1]:
        n *= int(s)
    return n > 0 and v > 0 and _pick_blocks(n, v, dt.itemsize) is not None


def _dims(ndim: int, pspec):
    """`pspec` as exactly `ndim` per-dim shardings."""
    return (tuple(pspec or ()) + (None,) * ndim)[:ndim]


def _shard_shape(shape, mesh, pspec):
    """The logits shape ONE device sees — `shape` itself on one device.
    None when the kernel cannot be split per shard: the strategy shards the
    vocab dim (the log-sum-exp would need a cross-device reduction) or a
    sharded dim does not divide."""
    if not multi_device(mesh):
        return tuple(shape)
    dims = _dims(len(shape), pspec)
    if dims[-1] is not None:
        return None
    return local_shape(shape, dims, mesh)


def use_fused_ce(loss_type, logits, mode: str,
                 enable_fusion: bool = True, mesh=None, pspec=None) -> bool:
    """The compile-time gate: cfg.fused_loss x loss type x shape precheck
    (on the per-shard shape when `mesh` has more than one device and the
    logits are laid out as `pspec`)."""
    from flexflow_tpu.losses import LossType

    if mode == "off":
        return False
    if LossType.from_any(loss_type) is not \
            LossType.SPARSE_CATEGORICAL_CROSSENTROPY:
        if mode == "on":
            raise ValueError(
                f"--fused-loss=on requires sparse_categorical_crossentropy "
                f"(got {loss_type})")
        return False
    local = _shard_shape(logits.shape, mesh, pspec)
    ok = local is not None and fused_ce_supported(local, logits.dtype)
    if mode == "on":
        if not ok:
            raise ValueError(
                f"--fused-loss=on but logits {logits.shape} {logits.dtype} "
                f"(per-device {local}) don't qualify (need rows % 8 == 0, "
                f"vocab % 128 == 0 and unsharded, f32/bf16)")
        return True
    return ok and enable_fusion


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _params(semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics)


# --------------------------------------------------------------------- forward
def _fwd_kernel(x_ref, y_ref, loss_ref, lse_ref, m_s, l_s, c_s,
                *, block_v, n_vblocks):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, _NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        c_s[...] = jnp.zeros(c_s.shape, jnp.float32)

    x = x_ref[...].astype(jnp.float32)              # (bn, bv) tile
    y = y_ref[...]                                  # (bn, 1) int32
    bn, bv = x.shape
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(x, axis=-1, keepdims=True))
    l_s[...] = (l_s[...] * jnp.exp(m_prev - m_new)
                + jnp.sum(jnp.exp(x - m_new), axis=-1, keepdims=True))
    m_s[...] = m_new
    # the label's logit: exactly one vocab block contains it per row
    c_s[...] += jnp.sum(jnp.where(col == y, x, 0.0), axis=-1, keepdims=True)

    @pl.when(j == n_vblocks - 1)
    def _fin():
        lse = m_s[...] + jnp.log(l_s[...])
        lse_ref[...] = lse
        loss_ref[...] = lse - c_s[...]


def _forward(x2, y2):
    """x2: (n, v) logits; y2: (n, 1) int32 -> (per-row loss (n,1) f32,
    lse (n,1) f32)."""
    from jax.experimental.pallas import tpu as pltpu

    n, v = x2.shape
    bn, bv = _pick_blocks(n, v, x2.dtype.itemsize)
    kernel = functools.partial(_fwd_kernel, block_v=bv, n_vblocks=v // bv)
    loss, lse = pl.pallas_call(
        kernel,
        grid=(n // bn, v // bv),
        in_specs=[
            pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)] * 3,
        # vocab is the accumulation dim: must run in order per row block
        compiler_params=_params(("parallel", "arbitrary")),
        interpret=_interpret(),
        name="ff_fused_ce_fwd",
    )(x2, y2)
    return loss, lse


# -------------------------------------------------------------------- backward
def _bwd_kernel(x_ref, y_ref, lse_ref, g_ref, dx_ref, *, block_v):
    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...]
    lse = lse_ref[...]
    g = g_ref[0, 0]                                 # cotangent / n
    bn, bv = x.shape
    j = pl.program_id(1)
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    p = jnp.exp(x - lse)                            # softmax tile
    dx_ref[...] = (g * (p - jnp.where(col == y, 1.0, 0.0))).astype(
        dx_ref.dtype)


def _backward(x2, y2, lse, gscale):
    n, v = x2.shape
    bn, bv = _pick_blocks(n, v, x2.dtype.itemsize)
    g = gscale.astype(jnp.float32).reshape(1, 1)
    dx = pl.pallas_call(
        functools.partial(_bwd_kernel, block_v=bv),
        grid=(n // bn, v // bv),
        in_specs=[
            pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, v), x2.dtype),
        compiler_params=_params(("parallel", "parallel")),
        interpret=_interpret(),
        name="ff_fused_ce_bwd",
    )(x2, y2, lse, g)
    return dx


@jax.custom_vjp
def _fce(x2, y2):
    loss, _ = _forward(x2, y2)
    return jnp.mean(loss)


def _fce_fwd(x2, y2):
    loss, lse = _forward(x2, y2)
    return jnp.mean(loss), (x2, y2, lse)


def _fce_bwd(res, g):
    x2, y2, lse = res
    dx = _backward(x2, y2, lse, g / x2.shape[0])
    # integer labels take a float0 cotangent
    return dx, np.zeros(y2.shape, jax.dtypes.float0)


_fce.defvjp(_fce_fwd, _fce_bwd)


# ------------------------------------------------------------------ public API
def fused_cross_entropy(logits, labels, mesh=None, pspec=None) -> jax.Array:
    """Mean sparse cross-entropy over all leading dims. On a multi-device
    `mesh` with the logits laid out as `pspec` (vocab unsharded), every
    device runs the kernel on its own rows and returns its mean; the global
    mean is taken outside the manual region (equal shards, no collective
    inside — kernels/partition.py).

    logits: [..., vocab] (f32 or bf16, kept in native dtype); labels:
    integer ids broadcastable to logits.shape[:-1]. Numerically equivalent
    to jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
    logits.astype(f32), labels)). Raises ValueError on unsupported shapes —
    callers precheck with fused_ce_supported / use_fused_ce.
    """
    local = _shard_shape(logits.shape, mesh, pspec)
    if local is None or not fused_ce_supported(local, logits.dtype):
        raise ValueError(f"fused_cross_entropy: unsupported logits "
                         f"{logits.shape} {logits.dtype} (per-device {local})")
    v = logits.shape[-1]

    def local_mean(x, y):
        n = x.size // v
        return _fce(x.reshape(n, v), y.reshape(n, 1).astype(jnp.int32))[None]

    labels = labels.reshape(logits.shape[:-1])
    if not multi_device(mesh):
        return local_mean(logits, labels)[0]
    lead = _dims(logits.ndim, pspec)[:-1]
    means = per_shard(local_mean, mesh,
                      (PartitionSpec(*lead, None), PartitionSpec(*lead)),
                      PartitionSpec(tuple(used_axes(lead)) or None)
                      )(logits, labels)
    return jnp.mean(means)
