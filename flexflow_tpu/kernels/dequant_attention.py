"""Fused dequantize + decode attention for the int8 paged-KV path.

The quantized KV cache (serving/kv_cache.py, --kv-cache-dtype int8) stores
pools as int8 values with per-(page entry, head) f32 scales. The reference
decode path dequantizes the GATHERED context into a full f32 [b, L, h, d]
K/V copy before the attention einsums — exactly the materialization the
quantization was meant to shrink. This kernel fuses the dequant into the
attention instead: per (batch, head) grid step the int8 context and its
scale column stream into VMEM, are widened in-register, and run through a
stable softmax, so the f32 copy of the context never touches HBM.

Decode contexts are short (pages_per_slot * page_size positions) and the
query is 1..K+1 tokens (speculative verify), so the kernel keeps the whole
context per grid step instead of blocking it — `dequant_supported` says
where that stops being true, and the caller (ops/attention_ops.py) asks it
before choosing between this kernel and the einsum path.

CPU runs use pallas interpret mode (tests/benches); all accumulation is
f32 regardless of the query dtype.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
# int8 k + v context, their f32 scales, and one f32 widened operand per
# dot must fit VMEM per (b, h) grid step
_VMEM_CTX_BYTES = 4 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


def dequant_supported(ctx_len: int, depth: int) -> bool:
    """Whether the fused kernel covers this gathered-context geometry (the
    whole int8 context + scales + one widened operand stay in VMEM per
    grid step). The auto path asks BEFORE tracing; beyond it decode takes
    the einsum dequant path."""
    return 2 * ctx_len * depth * (1 + 4) + 8 * ctx_len <= _VMEM_CTX_BYTES


def _kernel(pos_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref, o_ref, *, scale):
    q = q_ref[0, 0].astype(jnp.float32)            # (s, d)
    k = kq_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]   # (L, d) dequant
    v = vq_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
    s_mat = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
    sq, L = s_mat.shape
    # causal-by-construction over the cached extent: query token i sits at
    # position pos + i, so it attends cached positions 0..pos+i inclusive
    pos = pos_ref[pl.program_id(0)]
    row = jax.lax.broadcasted_iota(jnp.int32, (sq, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (sq, L), 1)
    s_mat = jnp.where(col <= pos + row, s_mat, _NEG_INF)
    m = jnp.max(s_mat, axis=-1, keepdims=True)
    p = jnp.exp(s_mat - m)
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0, 0] = (o / jnp.sum(p, axis=-1, keepdims=True)).astype(o_ref.dtype)


def dequant_decode_attention(qh, kq, ks, vq, vs, pos,
                             scale: float | None = None):
    """qh (b, s, h, d) queries; kq/vq (b, L, h, d) int8 gathered context;
    ks/vs (b, L, h) f32 scales; pos (b,) int32 cached-extent per slot.
    Returns (b, s, h, d) in qh's dtype. Raises ValueError on unsupported
    shapes/dtypes — callers precheck with dequant_supported."""
    if qh.ndim != 4 or kq.ndim != 4 or ks.ndim != 3:
        raise ValueError(f"bad ranks q={qh.shape} kq={kq.shape} ks={ks.shape}")
    if kq.dtype != jnp.int8 or vq.dtype != jnp.int8:
        raise ValueError(f"context must be int8, got {kq.dtype}/{vq.dtype}")
    b, s, h, d = qh.shape
    L = kq.shape[1]
    if not dequant_supported(L, d):
        raise ValueError(f"context {L} x depth {d} exceeds the VMEM budget; "
                         "use the einsum dequant path")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qt = jnp.swapaxes(qh, 1, 2)                    # (b, h, s, d)
    kqt = jnp.swapaxes(kq, 1, 2)
    vqt = jnp.swapaxes(vq, 1, 2)
    # trailing singleton keeps the scale blocks' last-two dims tileable
    kst = jnp.swapaxes(ks, 1, 2)[..., None]        # (b, h, L, 1)
    vst = jnp.swapaxes(vs, 1, 2)[..., None]
    # pos is a per-slot scalar: scalar-prefetched into SMEM (a (1, 1) VMEM
    # block of a (b, 1) array is not a legal TPU tile)
    def blk(rows, cols):   # one (batch, head) slab per grid step
        return pl.BlockSpec((1, 1, rows, cols),
                            lambda b_, h_, pos_: (b_, h_, 0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, scale=float(scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h),
            in_specs=[blk(s, d), blk(L, d), blk(L, 1), blk(L, d), blk(L, 1)],
            out_specs=blk(s, d),
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), qh.dtype),
        compiler_params=_params(),
        interpret=_interpret(),
        name="ff_dequant_attention",
    )(pos.astype(jnp.int32), qt, kqt, kst, vqt, vst)
    return jnp.swapaxes(out, 1, 2)
