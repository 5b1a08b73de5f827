"""Inter-operator (branch) placement on disjoint device subsets.

Reference analog: Unity's nonsequence splits — VERTICAL (split nodes) /
HORIZONTAL (split workers) in `find_optimal_nonsequence_graph_time`
(/root/reference/src/runtime/graph.cc:187-321): parallel branches of the PCG
are placed on disjoint subsets of the machine and run concurrently.

TPU-native formulation. GSPMD alone cannot express "op A on chips 0..3, op B
on chips 4..7": an op whose operands are replicated is computed redundantly
on EVERY device of the mesh, so branch placement buys nothing. The disjoint
placement needs runtime control flow over the device id, which is exactly
`shard_map` + `lax.switch(lax.axis_index(axis), ...)`:

  - the mesh axis chosen for inter-op placement has one index per branch;
  - inside the shard_map body each device group executes ONLY its branch
    (switch executes a single arm at runtime — the other branches are
    compiled but not run);
  - the body emits the branch output under a stacked leading dim sharded
    over the axis; the join (sum / feature concat) happens OUTSIDE the
    shard_map, where XLA GSPMD emits the collective;
  - other mesh axes (data) keep sharding the batch dim as usual, so inter-op
    placement composes with data parallelism.

Weight residency — two regimes:

  - CONGRUENT branches (identical sub-layer names + weight shapes, the case
    the search targets): weights are stored STACKED, one (k, ...) array per
    sub-weight, sharded over the placement axis (`place_branches_stacked`).
    Each device holds ONLY its branch's weights — memory, weight streaming
    and gradient all-reduce all divide by k. This is the owned-device
    residency of the reference's resource division (graph.cc:267-321).
  - heterogeneous branches: weights are passed replicated (every chip holds
    every branch's weights — the memory price of switch-based placement;
    the search's memory accounting charges the full union).

Autodiff: jax (≤0.9) mis-transposes a switch-on-axis_index inside shard_map
(the backward collapses onto arm 0), so the VJP is written explicitly: the
backward pass is another primal-mode shard_map whose switch dispatches each
device group to ITS branch's vjp (recompute, flash-attention style), then
psums dx over the placement axis and dweights over the whole mesh. Each
branch weight's gradient is therefore the sum over exactly the devices that
executed that branch — the same all-reduce semantics as data parallelism.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from jax import shard_map


def _pvary(x, axes):
    """Mark x as varying over `axes` in shard_map's vma type system."""
    return jax.lax.pcast(x, tuple(axes), to="varying")


def _batch_pspec(mesh: Mesh, axis: str, batch_len: int,
                 batch_axes=None):
    """Batch-dim sharding for a placement body — MIRRORS the search's
    _dp_dims convention (search/candidates.py) so the divisibility the
    candidate assumed holds at lowering: node+data jointly when their
    product divides the batch, else the first axis that divides, else
    replicated. Explicit `batch_axes` (tests / manual callers) filters by
    per-axis divisibility as before."""
    if batch_axes is not None:
        db = [a for a in batch_axes if a in mesh.shape and a != axis
              and batch_len % mesh.shape[a] == 0]
    else:
        cand = [a for a in ("node", "data") if a in mesh.shape and a != axis]
        deg = 1
        for a in cand:
            deg *= mesh.shape[a]
        if len(cand) > 1 and batch_len % deg == 0:
            db = cand
        else:
            db = next(([a] for a in cand if batch_len % mesh.shape[a] == 0),
                      [])
    bspec = tuple(db) if len(db) > 1 else (db[0] if db else None)
    b_local = batch_len
    for a in db:
        b_local //= mesh.shape[a]
    return db, bspec, b_local


def place_branches(
    mesh: Mesh,
    axis: str,
    branch_fns: List[Callable],
    x: jax.Array,
    branch_weights: Sequence,
    join: str,
    batch_axes: Optional[Sequence[str]] = None,
):
    """Run branch i of `branch_fns` on mesh-axis index i only.

    branch_fns[i](x_local, branch_weights[i]) -> y_local; all branches must
    produce equal shapes. join == "add" sums branch outputs; join ==
    "concat" concatenates them along the last dim.
    """
    k = len(branch_fns)
    if axis not in mesh.shape:
        raise ValueError(f"mesh has no axis {axis!r} (axes: {dict(mesh.shape)})")
    if mesh.shape[axis] != k:
        raise ValueError(
            f"inter-op placement needs axis size == n_branches "
            f"({axis}={mesh.shape[axis]} vs {k} branches)")
    if join not in ("add", "concat"):
        raise ValueError(f"unsupported join {join!r}")

    # batch dim rides the data axes; everything else is replicated
    _db, bspec, _bl = _batch_pspec(mesh, axis, x.shape[0], batch_axes)
    x_spec = PartitionSpec(bspec, *([None] * (x.ndim - 1)))
    w_specs = jax.tree_util.tree_map(lambda _: PartitionSpec(),
                                     tuple(branch_weights))
    stk_spec = PartitionSpec(axis, *x_spec)  # (k, batch, ..., d)
    all_axes = tuple(mesh.shape.keys())

    def _branch_arm(i):
        def arm(x_l, ws_l):
            return branch_fns[i](x_l, ws_l[i])[None]
        return arm

    def _fwd_body(x_l, *ws_l):
        bi = jax.lax.axis_index(axis)
        return jax.lax.switch(bi, [_branch_arm(i) for i in range(k)], x_l, ws_l)

    fwd_sm = shard_map(_fwd_body, mesh=mesh,
                       in_specs=(x_spec,) + w_specs, out_specs=stk_spec)

    def _bwd_arm(i):
        def arm(x_l, ws_l, g_l):
            _, pull = jax.vjp(lambda xv, wv: branch_fns[i](xv, wv), x_l, ws_l[i])
            dx, dw_i = pull(g_l[0])
            dws = tuple(dw_i if j == i
                        else jax.tree_util.tree_map(jnp.zeros_like, ws_l[j])
                        for j in range(k))
            return dx, dws
        return arm

    def _bwd_body(x_l, g_l, *ws_l):
        bi = jax.lax.axis_index(axis)
        # promote the replicated primals to device-varying (vma) so the
        # inner vjp's cotangent types line up with g (which varies over the
        # placement axis by construction)
        x_l = _pvary(x_l, (axis,))
        ws_l = _pvary(ws_l, all_axes)
        dx, dws = jax.lax.switch(bi, [_bwd_arm(i) for i in range(k)],
                                 x_l, ws_l, g_l)
        # x is replicated over the placement axis -> its grads sum over it;
        # weights are replicated over the WHOLE mesh -> grads sum everywhere
        dx = jax.lax.psum(dx, axis)
        dws = jax.lax.psum(dws, all_axes)
        return dx, dws

    bwd_sm = shard_map(_bwd_body, mesh=mesh,
                       in_specs=(x_spec, stk_spec) + w_specs,
                       out_specs=(x_spec, w_specs))

    @jax.custom_vjp
    def run(x_, ws_):
        return fwd_sm(x_, *ws_)

    def run_fwd(x_, ws_):
        return fwd_sm(x_, *ws_), (x_, ws_)

    def run_bwd(res, g):
        x_, ws_ = res
        dx, dws = bwd_sm(x_, g, *ws_)
        return dx, dws

    run.defvjp(run_fwd, run_bwd)

    stacked = run(x, tuple(branch_weights))  # (k, batch, ..., d)
    if join == "add":
        return stacked.sum(axis=0)
    return jnp.concatenate(list(stacked), axis=-1)


def divide_workers(costs: Sequence[float], n: int) -> List[int]:
    """Optimal division of n workers among branches for the makespan metric
    max_b(costs[b] / g[b]) — the reference enumerates these divisions
    (graph.cc:267-321, "first i of n workers vs the rest"); for the max
    metric the greedy waterfill is exact: give every branch one worker, then
    repeatedly give the next worker to the branch with the largest per-worker
    cost.

    Manual-placement helper for `place_branches_grouped` callers. The SEARCH
    uses the divisor-constrained variant instead
    (search/candidates._best_groups): the kernel row-slices the per-device
    batch, so each g_b must divide it — a constraint under which plain
    waterfill can emit invalid divisions."""
    k = len(costs)
    if n < k:
        raise ValueError(f"need at least one worker per branch ({n} < {k})")
    g = [1] * k
    for _ in range(n - k):
        b = max(range(k), key=lambda i: costs[i] / g[i])
        g[b] += 1
    return g


def place_branches_grouped(
    mesh: Mesh,
    axis: str,
    branch_fns: List[Callable],
    x: jax.Array,
    branch_weights: Sequence,
    join: str,
    group_sizes: Sequence[int],
    out_dims: Sequence[int],
    out_ndim: int,
    batch_axes: Optional[Sequence[str]] = None,
):
    """UNEQUAL resource division: branch b owns a contiguous group of
    `group_sizes[b]` indices of the placement axis (sum == axis size), the
    reference's machine-resource enumeration between branches
    (graph.cc:267-321) rather than one-index-per-branch. Devices inside a
    group split their branch's BATCH g_b ways, so a fat branch with more
    chips runs proportionally faster.

    Mechanism: each device computes only its (branch, batch-slice) share,
    writes it into a zero-padded buffer of the full JOINED output (feature
    offset static per branch, batch offset dynamic in the group index), and
    one psum over the placement axis assembles batch slices AND performs the
    join in the same collective ("add" sums overlapping feature blocks;
    "concat" blocks are disjoint). Weights are passed replicated (the
    stacked owned-device storage needs one axis index per branch; unequal
    groups trade that memory saving for balance — priced by the search).

    `out_dims[b]` = branch b's last-dim width (join=="add": all equal)."""
    k = len(branch_fns)
    n = sum(group_sizes)
    if axis not in mesh.shape:
        raise ValueError(f"mesh has no axis {axis!r} (axes: {dict(mesh.shape)})")
    if mesh.shape[axis] != n:
        raise ValueError(f"group sizes {list(group_sizes)} sum to {n} but "
                         f"axis {axis} has size {mesh.shape[axis]}")
    if join not in ("add", "concat"):
        raise ValueError(f"unsupported join {join!r}")
    starts = [sum(group_sizes[:b]) for b in range(k)]
    d_join = out_dims[0] if join == "add" else sum(out_dims)
    feat_off = [0] * k if join == "add" else \
        [sum(out_dims[:b]) for b in range(k)]

    _db, bspec, b_local = _batch_pspec(mesh, axis, x.shape[0], batch_axes)
    for g in group_sizes:
        if b_local % g:
            raise ValueError(
                f"per-device batch {b_local} not divisible by group size {g} "
                f"(groups {list(group_sizes)})")
    x_spec = PartitionSpec(bspec, *([None] * (x.ndim - 1)))
    o_spec = PartitionSpec(bspec, *([None] * (out_ndim - 1)))
    w_specs = jax.tree_util.tree_map(lambda _: PartitionSpec(),
                                     tuple(branch_weights))
    all_axes = tuple(mesh.shape.keys())

    def _row0(ndim, row):
        return (row,) + (0,) * (ndim - 1)

    def _fwd_arm(b):
        def arm(x_l, ws_l, row):
            m = x_l.shape[0] // group_sizes[b]
            xs = jax.lax.dynamic_slice_in_dim(x_l, row * m, m, axis=0)
            y = branch_fns[b](xs, ws_l[b])
            pad = jnp.zeros(y.shape[:-1] + (d_join,), y.dtype)
            pad = jax.lax.dynamic_update_slice(
                pad, y, (0,) * (y.ndim - 1) + (feat_off[b],))
            buf = jnp.zeros((x_l.shape[0],) + pad.shape[1:], y.dtype)
            return jax.lax.dynamic_update_slice(
                buf, pad, _row0(buf.ndim, row * m))
        return arm

    def _branch_of(bi):
        # static decision tree over the traced axis index
        b = jnp.zeros((), jnp.int32)
        for j in range(1, k):
            b = jnp.where(bi >= starts[j], j, b)
        row = bi - jnp.take(jnp.asarray(starts), b)
        return b, row

    def _fwd_body(x_l, *ws_l):
        b, row = _branch_of(jax.lax.axis_index(axis))
        part = jax.lax.switch(b, [_fwd_arm(i) for i in range(k)],
                              x_l, ws_l, row)
        return jax.lax.psum(part, axis)

    fwd_sm = shard_map(_fwd_body, mesh=mesh,
                       in_specs=(x_spec,) + w_specs, out_specs=o_spec)

    def _bwd_arm(b):
        def arm(x_l, ws_l, g_l, row):
            g = group_sizes[b]
            m = x_l.shape[0] // g
            xs = jax.lax.dynamic_slice_in_dim(x_l, row * m, m, axis=0)
            gs = jax.lax.dynamic_slice_in_dim(g_l, row * m, m, axis=0)
            gb = jax.lax.dynamic_slice(
                gs, (0,) * (gs.ndim - 1) + (feat_off[b],),
                gs.shape[:-1] + (out_dims[b],))
            _, pull = jax.vjp(lambda xv, wv: branch_fns[b](xv, wv),
                              xs, ws_l[b])
            dxs, dw_b = pull(gb)
            dx = jnp.zeros(x_l.shape, dxs.dtype)
            dx = jax.lax.dynamic_update_slice(dx, dxs, _row0(dx.ndim, row * m))
            dws = tuple(dw_b if j == b
                        else jax.tree_util.tree_map(jnp.zeros_like, ws_l[j])
                        for j in range(k))
            return dx, dws
        return arm

    def _bwd_body(x_l, g_l, *ws_l):
        b, row = _branch_of(jax.lax.axis_index(axis))
        x_l = _pvary(x_l, (axis,))
        g_l = _pvary(g_l, (axis,))
        ws_l = _pvary(ws_l, all_axes)
        dx, dws = jax.lax.switch(b, [_bwd_arm(i) for i in range(k)],
                                 x_l, ws_l, g_l, row)
        # every contribution is zero-padded to full shape: one psum over the
        # placement axis assembles dx; weight grads sum over the whole mesh
        # (each branch's arm zeroes the other branches' slots)
        dx = jax.lax.psum(dx, axis)
        dws = jax.lax.psum(dws, all_axes)
        return dx, dws

    bwd_sm = shard_map(_bwd_body, mesh=mesh,
                       in_specs=(x_spec, o_spec) + w_specs,
                       out_specs=(x_spec, w_specs))

    @jax.custom_vjp
    def run(x_, ws_):
        return fwd_sm(x_, *ws_)

    def run_fwd(x_, ws_):
        return fwd_sm(x_, *ws_), (x_, ws_)

    def run_bwd(res, g):
        x_, ws_ = res
        dx, dws = bwd_sm(x_, g, *ws_)
        return dx, dws

    run.defvjp(run_fwd, run_bwd)
    return run(x, tuple(branch_weights))


def place_branches_stacked(
    mesh: Mesh,
    axis: str,
    branch_fns: List[Callable],
    x: jax.Array,
    stacked_weights,
    join: str,
    batch_axes: Optional[Sequence[str]] = None,
):
    """Owned-device variant: `stacked_weights` is one pytree whose leaves are
    (k, ...) arrays — leaf [i] is branch i's weight — sharded over the
    placement axis, so each device group STORES only its branch's slice.
    branch_fns[i](x_local, weights_tree) with weights_tree = the unstacked
    local slice. Gradients for the stacked leaves stay sharded over the
    placement axis (no cross-branch all-reduce at all); they sum only over
    the axes the weights are replicated on (data)."""
    k = len(branch_fns)
    if axis not in mesh.shape:
        raise ValueError(f"mesh has no axis {axis!r} (axes: {dict(mesh.shape)})")
    if mesh.shape[axis] != k:
        raise ValueError(
            f"inter-op placement needs axis size == n_branches "
            f"({axis}={mesh.shape[axis]} vs {k} branches)")
    if join not in ("add", "concat"):
        raise ValueError(f"unsupported join {join!r}")

    _db, bspec, _bl = _batch_pspec(mesh, axis, x.shape[0], batch_axes)
    x_spec = PartitionSpec(bspec, *([None] * (x.ndim - 1)))
    w_spec = jax.tree_util.tree_map(lambda _: PartitionSpec(axis),
                                    stacked_weights)
    stk_spec = PartitionSpec(axis, *x_spec)
    other_axes = tuple(a for a in mesh.shape.keys() if a != axis)

    def _local(ws_l):
        # shard_map hands each device its (1, ...) slice of the stack
        return jax.tree_util.tree_map(lambda a: a[0], ws_l)

    def _arm(i):
        def arm(x_l, ws_l):
            return branch_fns[i](x_l, _local(ws_l))[None]
        return arm

    def _fwd_body(x_l, ws_l):
        bi = jax.lax.axis_index(axis)
        return jax.lax.switch(bi, [_arm(i) for i in range(k)], x_l, ws_l)

    fwd_sm = shard_map(_fwd_body, mesh=mesh, in_specs=(x_spec, w_spec),
                       out_specs=stk_spec)

    def _bwd_arm(i):
        def arm(x_l, ws_l, g_l):
            _, pull = jax.vjp(lambda xv, wv: branch_fns[i](xv, wv),
                              x_l, _local(ws_l))
            dx, dw = pull(g_l[0])
            # re-stack the local slice's gradient: (1, ...) per leaf
            return dx, jax.tree_util.tree_map(lambda a: a[None], dw)
        return arm

    def _bwd_body(x_l, g_l, ws_l):
        bi = jax.lax.axis_index(axis)
        x_l = _pvary(x_l, (axis,))
        if other_axes:
            ws_l = _pvary(ws_l, other_axes)
        dx, dws = jax.lax.switch(bi, [_bwd_arm(i) for i in range(k)],
                                 x_l, ws_l, g_l)
        # x replicated over the placement axis -> psum its grad over it;
        # weights SHARDED over the placement axis -> no psum over it, only
        # over the axes they are replicated on (data)
        dx = jax.lax.psum(dx, axis)
        if other_axes:
            dws = jax.lax.psum(dws, other_axes)
        return dx, dws

    bwd_sm = shard_map(_bwd_body, mesh=mesh,
                       in_specs=(x_spec, stk_spec, w_spec),
                       out_specs=(x_spec, w_spec))

    @jax.custom_vjp
    def run(x_, ws_):
        return fwd_sm(x_, ws_)

    def run_fwd(x_, ws_):
        return fwd_sm(x_, ws_), (x_, ws_)

    def run_bwd(res, g):
        x_, ws_ = res
        dx, dws = bwd_sm(x_, g, ws_)
        return dx, dws

    run.defvjp(run_fwd, run_bwd)

    stacked = run(x, stacked_weights)  # (k, batch, ..., d)
    if join == "add":
        return stacked.sum(axis=0)
    return jnp.concatenate(list(stacked), axis=-1)
