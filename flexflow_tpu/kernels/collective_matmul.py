"""Collective matmul — all-gather/matmul overlap on the model axis.

Capability: when the searched sharding puts a dense layer's weight columns
on the model axis while its activation rows ride another axis, GSPMD lowers
the layout change as a blocking all-gather followed by the full matmul —
the ICI transfer and the MXU serialize. The collective matmul (the TPU
"Overlap Communication with Computation" decomposition, PAPERS.md) instead
keeps the activation SHARDED and walks it around the ring: at every step
each device multiplies the activation chunk it currently holds against its
resident weight shard while `ppermute` moves the next chunk — P-1 hops of
size 1/P overlap P local matmuls, hiding the gather behind the compute.

Formulation (shard_map over the ring axis, same idiom as
kernels/ring_attention.py):

    x: (m, k) sharded P(axis, ...)   — activation, rows on the ring
    w: (k, n) sharded P(..., axis)   — weight, columns resident per device
    y: (m, n) sharded P(..., axis)   — every device ends with ALL rows of
                                       its n-shard: the all-gather happened
                                       implicitly, chunk by chunk

Autodiff flows through `ppermute` / `dynamic_update_slice` natively (the
transpose of a rotation is the inverse rotation), so no custom VJP is
needed — the backward pass is itself a ring of chunked matmuls.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from jax import shard_map


def collective_matmul_supported(mesh, axis: str, m: int, n: int) -> bool:
    """Shape/mesh precheck (the auto-mode gate, flash-attention style)."""
    if mesh is None or axis not in getattr(mesh, "shape", {}):
        return False
    p = mesh.shape[axis]
    return p > 1 and m % p == 0 and n % p == 0


def _ring_matmul(x_loc, w_loc, axis: str, p: int):
    """Per-device body: x_loc (m/p, k) — this device's activation chunk;
    w_loc (k, n/p) — its resident weight columns. Returns (m, n/p)."""
    idx = jax.lax.axis_index(axis)
    mp = x_loc.shape[0]
    perm = [(i, (i + 1) % p) for i in range(p)]
    y = jnp.zeros((mp * p, w_loc.shape[1]),
                  jnp.promote_types(x_loc.dtype, w_loc.dtype))
    x_cur = x_loc

    def body(i, carry):
        y, x_cur = carry
        # kick off the next hop FIRST: XLA overlaps the async ppermute
        # with the chunk matmul below (the whole point of the kernel)
        x_nxt = jax.lax.ppermute(x_cur, axis, perm)
        src = (idx - i) % p                      # whose rows we hold now
        chunk = jnp.dot(x_cur, w_loc,
                        preferred_element_type=jnp.float32)
        y = jax.lax.dynamic_update_slice(
            y, chunk.astype(y.dtype), (src * mp, 0))
        return y, x_nxt

    # the last step needs no hop; keeping it in the loop costs one extra
    # permute but lets XLA pipeline a static-trip-count loop
    y, _ = jax.lax.fori_loop(0, p, body, (y, x_cur))
    return y


def collective_matmul(x, w, mesh: Mesh, axis: str,
                      x_spec: PartitionSpec | None = None,
                      w_spec: PartitionSpec | None = None):
    """y = x @ w with the all-gather of x overlapped into the ring.

    x: (m, k) with rows sharded on `axis`; w: (k, n) with columns sharded
    on `axis`; returns y: (m, n) with columns sharded on `axis` — exactly
    what `x @ w` under GSPMD produces for these layouts, minus the blocking
    gather. Raises ValueError on unsupported shapes/meshes (callers
    precheck with collective_matmul_supported).
    """
    if x.ndim != 2 or w.ndim != 2 or x.shape[1] != w.shape[0]:
        raise ValueError(f"collective_matmul: bad shapes {x.shape} @ "
                         f"{w.shape}")
    if not collective_matmul_supported(mesh, axis, x.shape[0], w.shape[1]):
        raise ValueError(
            f"collective_matmul: mesh axis {axis!r} (mesh "
            f"{dict(getattr(mesh, 'shape', {}))}) can't ring "
            f"{x.shape} @ {w.shape}")
    p = mesh.shape[axis]
    x_spec = x_spec if x_spec is not None else PartitionSpec(axis, None)
    w_spec = w_spec if w_spec is not None else PartitionSpec(None, axis)
    out_spec = PartitionSpec(None, w_spec[1])
    fn = shard_map(partial(_ring_matmul, axis=axis, p=p), mesh=mesh,
                   in_specs=(x_spec, w_spec), out_specs=out_spec,
                   check_vma=False)
    return fn(x, w)
