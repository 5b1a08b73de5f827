"""Ring attention — sequence-parallel attention over a mesh axis.

Capability: long-context attention beyond one chip's memory. The flash
kernel (kernels/flash_attention.py) keeps k/v VMEM-resident per (b, h) and
is capped by the VMEM budget; past that, round-3 fell back to materializing
the full (s, s) logits. Ring attention removes both limits — for training,
not just inference: q, k, v are sharded over the sequence dim on a mesh
axis, each device computes blockwise attention of its q shard against the
k/v shard it currently holds, and k/v shards rotate around the ring with
`ppermute` — after P steps every q block has seen every k/v block. Per-device
*live* memory is O(s_local·d): the (s_local, s_local) chunk logits are
transient within one ring step and XLA reuses the buffer across steps.

Backward is a hand-written VJP in the flash-attention style (same structure
as kernels/flash_attention.py's `_flash_bwd`): the forward saves only
(q, k, v, out, lse) — lse is the per-row logsumexp, O(s_local) — and the
backward re-runs the ring, RECOMPUTING each chunk's probabilities from the
saved lse instead of storing the P probability blocks autodiff would save.
dk/dv accumulators travel around the ring together with their k/v chunks
(P rotations total returns every chunk, and its gradient, to its home
device). Without this, training memory is O(s²/P) per device and 32k+
sequences — the whole point of the ring path — exceed HBM.

The merge across steps is the standard online-softmax accumulation
(running max m, normalizer l, weighted accumulator acc) in float32.
Causal masking uses the blocks' GLOBAL offsets (device index × s_local), so
future blocks contribute exp(-inf)=0 — they still traverse the ring (the
rotation is the synchronization), but their FLOPs are masked.

No reference analog: the reference has no sequence/context parallelism at
all (SURVEY P10); this is the declared TPU extension (SURVEY §5, stage 8).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from jax import shard_map

_NEG_INF = float("-inf")


def _chunk_attn(q, k, v, row0, col0, scale, causal):
    """Blockwise attention of local q vs one k/v chunk with global offsets.
    q: (b, h, sq, d); k/v: (b, h, sk, d). Returns (acc_update terms)
    (s_max, p_sum, pv) with f32 statistics."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        row = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        col = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(row >= col, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # (b,h,sq,1)
    # fully-masked rows (future blocks): keep exp finite
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe)
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    return m, m_safe, l, pv


def _masked_probs(q, k, lse, row0, col0, scale, causal):
    """Recompute one chunk's probability block p = exp(q·kᵀ·scale − lse)
    from the saved logsumexp (backward-pass analog of _chunk_attn)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        row = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        col = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(row >= col, s, _NEG_INF)
    p = jnp.exp(s - lse)
    return jnp.where(jnp.isfinite(s), p, 0.0)


def _ring_fwd_local(q_l, k_l, v_l, *, axis, P, s_loc, d, scale, causal, perm):
    """Shard-local forward: online-softmax over P rotating k/v chunks.
    Returns (out, lse) — lse (b,h,sq,1) f32 is the backward residual."""
    idx = jax.lax.axis_index(axis)
    row0 = idx * s_loc
    m = jnp.full(q_l.shape[:3] + (1,), _NEG_INF, jnp.float32)
    l = jnp.zeros_like(m)
    acc = jnp.zeros(q_l.shape[:3] + (d,), jnp.float32)
    k_cur, v_cur = k_l, v_l
    for j in range(P):
        kv_idx = (idx - j) % P
        cm, cm_safe, cl, cpv = _chunk_attn(
            q_l, k_cur, v_cur, row0, kv_idx * s_loc, scale, causal)
        m_new = jnp.maximum(m, cm)
        m_new_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new_safe), 0.0)
        beta = jnp.where(jnp.isfinite(cm), jnp.exp(cm_safe - m_new_safe), 0.0)
        l = l * alpha + cl * beta
        acc = acc * alpha + cpv * beta
        m = m_new
        if j < P - 1:
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)
    # every causal row has at least its own diagonal; non-causal always
    out = acc / jnp.maximum(l, 1e-30)
    m_fin = jnp.where(jnp.isfinite(m), m, 0.0)
    lse = m_fin + jnp.log(jnp.maximum(l, 1e-30))
    return out.astype(q_l.dtype), lse


def _ring_bwd_local(q_l, k_l, v_l, out, lse, do, *,
                    axis, P, s_loc, scale, causal, perm):
    """Shard-local backward: second ring pass recomputing chunk probs from
    lse (no stored probability blocks). dk/dv accumulators rotate WITH their
    k/v chunks; after P rotations every chunk's gradient is home."""
    idx = jax.lax.axis_index(axis)
    row0 = idx * s_loc
    do32 = do.astype(jnp.float32)
    # delta_i = Σ_d do_i · out_i  (flash-attention bwd identity)
    delta = jnp.sum(do32 * out.astype(jnp.float32), axis=-1, keepdims=True)
    dq = jnp.zeros(q_l.shape, jnp.float32)
    dk = jnp.zeros(k_l.shape, jnp.float32)
    dv = jnp.zeros(v_l.shape, jnp.float32)
    k_cur, v_cur = k_l, v_l
    for j in range(P):
        kv_idx = (idx - j) % P
        p = _masked_probs(q_l, k_cur, lse, row0, kv_idx * s_loc, scale, causal)
        pc = p.astype(do.dtype)
        dv = dv + jnp.einsum("bhqk,bhqd->bhkd", pc, do,
                             preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do, v_cur,
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q_l.dtype)
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, k_cur,
                             preferred_element_type=jnp.float32)
        dk = dk + jnp.einsum("bhqk,bhqd->bhkd", ds, q_l,
                             preferred_element_type=jnp.float32)
        # rotate every iteration (P total): chunks + grads return home
        k_cur = jax.lax.ppermute(k_cur, axis, perm)
        v_cur = jax.lax.ppermute(v_cur, axis, perm)
        dk = jax.lax.ppermute(dk, axis, perm)
        dv = jax.lax.ppermute(dv, axis, perm)
    return dq.astype(q_l.dtype), dk.astype(k_l.dtype), dv.astype(v_l.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str,
    causal: bool = False,
    scale: Optional[float] = None,
    batch_axes: Sequence[str] = ("data",),
) -> jax.Array:
    """q/k/v: (b, h, s, d) GLOBAL arrays; s must divide by the axis size.
    Returns (b, h, s, d), sequence-sharded like the inputs. Differentiable
    via the hand-written two-pass VJP above (custom_vjp OUTSIDE the
    shard_map, the same composition parallel/interop.py uses — backward is
    its own primal-mode shard_map)."""
    b, h, s, d = q.shape
    P = mesh.shape[axis]
    if s % P:
        raise ValueError(f"seq {s} not divisible by ring axis {axis}={P}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    db = [a for a in batch_axes if a in mesh.shape and a != axis
          and b % mesh.shape[a] == 0]
    bspec = tuple(db) if len(db) > 1 else (db[0] if db else None)
    spec = PartitionSpec(bspec, None, axis, None)
    lspec = PartitionSpec(bspec, None, axis, None)  # lse (b,h,s,1): seq-sharded
    s_loc = s // P
    perm = [(i, (i + 1) % P) for i in range(P)]

    fwd_local = partial(_ring_fwd_local, axis=axis, P=P, s_loc=s_loc, d=d,
                        scale=scale, causal=causal, perm=perm)
    bwd_local = partial(_ring_bwd_local, axis=axis, P=P, s_loc=s_loc,
                        scale=scale, causal=causal, perm=perm)

    run_fwd = shard_map(fwd_local, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=(spec, lspec))
    run_bwd = shard_map(bwd_local, mesh=mesh,
                        in_specs=(spec, spec, spec, spec, lspec, spec),
                        out_specs=(spec, spec, spec))

    @jax.custom_vjp
    def attn(q, k, v):
        out, _ = run_fwd(q, k, v)
        return out

    def attn_fwd(q, k, v):
        out, lse = run_fwd(q, k, v)
        # residuals: O(s·d) arrays + O(s) lse — NO probability blocks
        return out, (q, k, v, out, lse)

    def attn_bwd(res, do):
        q, k, v, out, lse = res
        return run_bwd(q, k, v, out, lse, do)

    attn.defvjp(attn_fwd, attn_bwd)
    return attn(q, k, v)


def ring_attention_qkv(q, k, v, mesh, axis, causal=False, scale=None,
                       batch_axes=("data",)):
    """Head-minor layout entry (b, s, h, d) used by ops/attention_ops."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = ring_attention(qt, kt, vt, mesh, axis, causal=causal, scale=scale,
                         batch_axes=batch_axes)
    return jnp.swapaxes(out, 1, 2)
