"""Pallas (Mosaic) kernels on a mesh of more than one device.

GSPMD cannot partition a Mosaic custom call — the chip's compiler refuses
the program with "Mosaic kernels cannot be automatically partitioned.
Please wrap the call in a shard_map." (interpret mode on the CPU mesh never
showed it: an interpreted kernel is plain jax ops). So every kernel of this
package that runs under a multi-device mesh runs PER SHARD under shard_map,
with specs its caller derives from the searched strategy — the op's own
batch / heads / weight layout — and no collective inside the manual region.
On one device the kernel is called directly.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import shard_map

from flexflow_tpu.parallel.sharding import used_axes


def live_order(live):
    """`live` `[b]` bool -> (`order` `[b]` int32: the live slots in rising
    order, then the others; `count` int32: how many are live). What the grid
    of a decode step's per-slot kernel walks on its one device
    (`retention_step`, `mamba2_step`, `sparse_attend_step`): the slot of grid
    step `i` is `order[i]`, scalar-prefetched, and the first grid bound is
    `count`, known at run time, so a slot that is not live costs no grid
    step and none of its bytes is touched."""
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    return order, jnp.sum(live.astype(jnp.int32))


def multi_device(mesh) -> bool:
    return mesh is not None and mesh.size > 1


def per_shard(fn, mesh, in_specs, out_specs):
    """`fn` itself on one device; shard_map(fn) over `mesh` otherwise."""
    if not multi_device(mesh):
        return fn
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def _degree(dim, mesh) -> int:
    """Devices one DimSharding splits its dim over (0: an axis the mesh
    does not have)."""
    degree = 1
    for a in used_axes([dim]):
        degree *= mesh.shape.get(a, 0)
    return degree


def dividing(dim, size: int, mesh, taken=()):
    """The DimSharding `dim` when its mesh-axis degree divides `size` and
    none of its axes is in `taken`; else None (that dim stays replicated —
    shard_map then gathers it, which is correct, only slower)."""
    degree = _degree(dim, mesh)
    if degree < 2 or size % degree or set(used_axes([dim])) & set(taken):
        return None
    return dim
