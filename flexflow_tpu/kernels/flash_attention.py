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

All matmuls accumulate in float32 (preferred_element_type) regardless of the
input dtype; bf16 inputs hit the MXU at full rate.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BLOCK_CANDIDATES = (1024, 512, 256, 128)
_NEG_INF = float("-inf")
# k/v (fwd/dq) and q/do (dk/dv) are held fully in VMEM per (b, h) grid step;
# cap their footprint well under the ~16MB VMEM budget so Mosaic never OOMs
# on shapes that pass the divisibility checks. Longer sequences belong to the
# ring-attention path (kernels/ring_attention.py).
_VMEM_SEQ_BYTES = 6 * 1024 * 1024
# per-BLOCK VMEM budget: the block-shape ceiling was implicitly sized for
# head_dim 64 (a 512 x 64 f32 block = 128KB). Wider heads scale the block
# footprint linearly, so the block choice is parametrized by (depth,
# itemsize): head_dim 128 f32 drops 512 -> 256 instead of handing Mosaic a
# 256KB block per operand (q, do, dq accumulators all carry it); bf16 keeps
# the full 512. 160KB leaves the d=64 behavior exactly as before.
_VMEM_BLOCK_BYTES = 160 * 1024
# narrow heads (d <= 64, GPT-2's case) get a larger
# per-block budget: a 1024 x 64 f32 block is 256KB and three such operands
# are still < 1MB of VMEM, while the doubled rows-per-grid-step halve the
# k/v streaming overhead that starves the MXU at short blocks. Wider heads
# keep the 160KB budget (d=128 behavior unchanged: f32 -> 256, bf16 -> 512).
_VMEM_BLOCK_BYTES_NARROW = 256 * 1024


def _blocks_for(depth: int, itemsize: int):
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


def _pick_block(s: int, depth: int = 64, itemsize: int = 4,
                env: str = "FLEXFLOW_FLASH_BLOCK") -> int:
    import os

    cands = _blocks_for(depth, itemsize)
    try:
        forced = int(os.environ.get(env, "0") or "0")
    except ValueError:
        forced = 0
    # tuning override: only known-safe block sizes (the per-block VMEM
    # budget was sized for _blocks_for's output; arbitrary values could
    # OOM Mosaic)
    if forced in cands and s % forced == 0:
        return forced
    if env != "FLEXFLOW_FLASH_BLOCK":
        # bwd knob unset OR invalid: inherit the main block choice (so a
        # typo'd bwd value degrades to the fwd configuration, not to a
        # third configuration nobody asked for)
        return _pick_block(s, depth, itemsize)
    for b in cands:
        if s % b == 0:
            return b
    raise ValueError(f"sequence length {s} not divisible by any of {cands} "
                     f"(head_dim {depth}, itemsize {itemsize})")


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _params():
    from jax.experimental.pallas import tpu as pltpu

    # batch/head/q-block grid dims are independent; lets Mosaic pipeline them
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


# --------------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, block_k):
    q = q_ref[0, 0]                                # (bq, d), input dtype (MXU bf16)
    bq, d = q.shape
    sk = k_ref.shape[2]
    qi = pl.program_id(2)
    q_start = qi * bq

    if causal:
        nk_loop = (q_start + bq) // block_k        # blocks at/under the diagonal
    else:
        nk_loop = sk // block_k

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(ki * block_k, block_k), :]
        v = v_ref[0, 0, pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            row = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(row >= col, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk_loop, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)                 # (bq, 1)


def _fwd(q, k, v, causal, scale):
    """q: (b, h, sq, d); k/v: (b, h, sk, d) -> (o, lse)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = _pick_block(sq, d, q.dtype.itemsize)
    bk = _pick_block(sk, d, k.dtype.itemsize)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal, block_k=bk)
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, h, sq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda b_, h_, i: (b_, h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
            # lse is (b, h, sq, 1): the trailing singleton keeps the block's
            # last-two dims TPU-tileable ((bq, 1) with 1 == full array dim)
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, i: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=_interpret(),
        name="ff_flash_attention_fwd",
    )(q, k, v)
    return o, lse


# -------------------------------------------------------------------- backward
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *, scale, causal, block_k):
    q = q_ref[0, 0]                                # input dtype: MXU-rate dots
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]                            # (bq, 1) f32
    delta = delta_ref[0, 0]
    bq, d = q.shape
    sk = k_ref.shape[2]
    qi = pl.program_id(2)
    q_start = qi * bq
    nk_loop = (q_start + bq) // block_k if causal else sk // block_k

    def body(ki, dq_acc):
        k = k_ref[0, 0, pl.ds(ki * block_k, block_k), :]
        v = v_ref[0, 0, pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)
        if causal:
            row = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            p = jnp.where(row >= col, p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        return dq_acc + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, nk_loop, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                *, scale, causal, block_q):
    k = k_ref[0, 0]                                # (bk, d), input dtype
    v = v_ref[0, 0]
    bk, d = k.shape
    sq = q_ref.shape[2]
    ki = pl.program_id(2)
    k_start = ki * bk
    nq = sq // block_q
    qi_start = k_start // block_q if causal else 0

    def body(qi, carry):
        dk_acc, dv_acc = carry
        q = q_ref[0, 0, pl.ds(qi * block_q, block_q), :]
        do = do_ref[0, 0, pl.ds(qi * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q), :]
        delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)                        # (bq, bk) f32
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
            col = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
            p = jnp.where(row >= col, p, 0.0)
        pc = p.astype(do.dtype)
        dv_acc = dv_acc + jax.lax.dot_general(pc, do, (((0,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_acc = dk_acc + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    z = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(qi_start, nq, body, (z, z))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _bwd(causal, scale, res, g):
    q, k, v, o, lse = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    # FLEXFLOW_FLASH_BLOCK_BWD tunes the backward independently (the dq /
    # dkv kernels have different VMEM/recompute balance than the forward);
    # unset = inherit FLEXFLOW_FLASH_BLOCK's choice
    bq = _pick_block(sq, d, q.dtype.itemsize, env="FLEXFLOW_FLASH_BLOCK_BWD")
    bk = _pick_block(sk, d, k.dtype.itemsize, env="FLEXFLOW_FLASH_BLOCK_BWD")
    do = g.astype(jnp.float32)
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1, keepdims=True)  # (b, h, sq, 1)

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0))
    k_full = pl.BlockSpec((1, 1, sk, d), lambda b_, h_, i: (b_, h_, 0, 0))
    q_full = pl.BlockSpec((1, 1, sq, d), lambda b_, h_, i: (b_, h_, 0, 0))
    k_spec = pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i: (b_, h_, i, 0))
    vec_q = pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, i: (b_, h_, i, 0))
    vec_full = pl.BlockSpec((1, 1, sq, 1), lambda b_, h_, i: (b_, h_, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, block_k=bk),
        grid=(b, h, sq // bq),
        in_specs=[q_spec, k_full, k_full, q_spec, vec_q, vec_q],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        compiler_params=_params(),
        interpret=_interpret(),
        name="ff_flash_attention_dq",
    )(q, k, v, g, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, block_q=bq),
        grid=(b, h, sk // bk),
        in_specs=[q_full, k_spec, k_spec, q_full, vec_full, vec_full],
        out_specs=[k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, sk, d), v.dtype)],
        compiler_params=_params(),
        interpret=_interpret(),
        name="ff_flash_attention_dkv",
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, scale):
    return _fwd(q, k, v, causal, scale)[0]


def _flash_fwd(q, k, v, causal, scale):
    o, lse = _fwd(q, k, v, causal, scale)
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


# ------------------------------------------------------------------ public API
def flash_attention(q, k, v, causal: bool = False, scale: float | None = None):
    """q: (b, h, sq, d), k/v: (b, h, sk, d) -> (b, h, sq, d).

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
    return _flash(q, k, v, causal, float(scale))


def flash_attention_qkv(q, k, v, causal: bool = False, scale: float | None = None):
    """Head-minor layout entry used by ops/attention_ops: q/k/v (b, s, h, d),
    returns (b, sq, h, d). Unsupported shapes raise ValueError."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention(qt, kt, vt, causal=causal, scale=scale)
    return jnp.swapaxes(out, 1, 2)
