"""Fused optimizer moment update as a pallas TPU kernel.

Capability replaced: the optax update chain in the train step
(compile.py apply_update -> tx.update). optax expresses Adam as a series of
tree_maps — XLA usually fuses them, but the moment update is memory-bound
either way and perf_probe prices it at ~12 ms of a GPT-2-medium step; one
kernel per param block reads (g, mu, nu, p) and writes (update, mu', nu')
in a single pass over HBM, with all arithmetic in f32 and the moments
stored back in the optimizer's state dtype (f32 or bf16, mirroring
optimizers._scale_by_adam_lowp).

The fused path REPLACES only the arithmetic, never the state structure:
`plan_for(optimizer)` recognizes the repo's Adam/SGD configurations (an
unrecognized optimizer silently falls back to tx.update — the "auto" mode
contract), and `fused_update` locates the ScaleByAdamState / TraceState
node inside the existing optax chain state and rebuilds it in place, so
checkpoints, ZeRO's scattered-moment sharding constraints, and state
inspection all see the exact optax layout. Sharding composes the same way
tx.update does: the caller constrains grads to the moment layout before and
the opt state after (compile.py), and the kernel is purely elementwise, so
under ZeRO each device updates only its moment shard.

Numerics mirror optax exactly: same moment recurrences, same
`1 - beta**count` bias-correction expressions, decoupled weight decay
applied after the Adam term, `scale(-lr)` last.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.sharding import NamedSharding, PartitionSpec

from flexflow_tpu.kernels.partition import per_shard

_LANES = 128
_BLOCK_ROWS = 256


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=("parallel",))


# ----------------------------------------------------------------- planning
def plan_for(optimizer) -> Optional[Dict[str, Any]]:
    """Recognize the optimizer's update math, or None (caller falls back to
    tx.update). Import is local to avoid a kernels <-> optimizers cycle."""
    from flexflow_tpu.optimizers import AdamOptimizer, SGDOptimizer

    if type(optimizer) is AdamOptimizer:
        sd = optimizer.state_dtype or "float32"
        if sd not in ("float32", "bfloat16"):
            return None
        return {"kind": "adam", "lr": float(optimizer.alpha),
                "b1": float(optimizer.beta1), "b2": float(optimizer.beta2),
                "eps": float(optimizer.epsilon),
                "wd": float(optimizer.weight_decay),
                "state_dtype": jnp.dtype(sd)}
    if type(optimizer) is SGDOptimizer:
        return {"kind": "sgd", "lr": float(optimizer.lr),
                "momentum": float(optimizer.momentum),
                "nesterov": bool(optimizer.nesterov),
                "wd": float(optimizer.weight_decay)}
    return None


# ----------------------------------------------------- leaf padding helpers
def _pad2d(a):
    """Flatten a leaf to (rows, 128) with rows a multiple of the block."""
    size = a.size
    rows = -(-size // _LANES)
    br = rows if rows <= _BLOCK_ROWS else _BLOCK_ROWS
    rows_p = -(-rows // br) * br
    flat = a.reshape(-1)
    pad = rows_p * _LANES - size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows_p, _LANES), br


def _unpad(a2, shape, size, dtype=None):
    out = a2.reshape(-1)[:size].reshape(shape)
    return out.astype(dtype) if dtype is not None else out


# ------------------------------------------------------------------ kernels
def _adam_kernel(g_ref, mu_ref, nu_ref, p_ref, sc_ref,
                 upd_ref, mu_o_ref, nu_o_ref, *, b1, b2, eps, lr, wd):
    g = g_ref[...].astype(jnp.float32)
    mu = mu_ref[...].astype(jnp.float32)
    nu = nu_ref[...].astype(jnp.float32)
    bc1 = sc_ref[0, 0]                 # 1 - b1**count (f32, optax's exact
    bc2 = sc_ref[0, 1]                 # bias-correction denominators)
    mu_n = b1 * mu + (1.0 - b1) * g
    nu_n = b2 * nu + (1.0 - b2) * g * g
    u = (mu_n / bc1) / (jnp.sqrt(nu_n / bc2) + eps)
    if wd:
        u = u + wd * p_ref[...].astype(jnp.float32)
    upd_ref[...] = (-lr * u).astype(upd_ref.dtype)
    mu_o_ref[...] = mu_n.astype(mu_o_ref.dtype)
    nu_o_ref[...] = nu_n.astype(nu_o_ref.dtype)


def _sgd_kernel(g_ref, t_ref, p_ref, upd_ref, t_o_ref,
                *, momentum, nesterov, lr, wd):
    g = g_ref[...].astype(jnp.float32)
    if wd:
        g = g + wd * p_ref[...].astype(jnp.float32)
    t_n = g + momentum * t_ref[...].astype(jnp.float32)
    u = g + momentum * t_n if nesterov else t_n
    upd_ref[...] = (-lr * u).astype(upd_ref.dtype)
    t_o_ref[...] = t_n.astype(t_o_ref.dtype)


def _sgd_plain_kernel(g_ref, p_ref, upd_ref, *, lr, wd):
    g = g_ref[...].astype(jnp.float32)
    if wd:
        g = g + wd * p_ref[...].astype(jnp.float32)
    upd_ref[...] = (-lr * g).astype(upd_ref.dtype)


def _row_spec(br):
    return pl.BlockSpec((br, _LANES), lambda i: (i, 0))


def _scalar_spec():
    return pl.BlockSpec((1, _LANES), lambda i: (0, 0))


def _adam_leaf(g, mu, nu, p, sc, plan):
    g2, br = _pad2d(g)
    mu2, _ = _pad2d(mu)
    nu2, _ = _pad2d(nu)
    p2, _ = _pad2d(p)
    sd = plan["state_dtype"]
    kernel = functools.partial(_adam_kernel, b1=plan["b1"], b2=plan["b2"],
                               eps=plan["eps"], lr=plan["lr"], wd=plan["wd"])
    upd2, mu_o2, nu_o2 = pl.pallas_call(
        kernel,
        grid=(g2.shape[0] // br,),
        in_specs=[_row_spec(br)] * 4 + [_scalar_spec()],
        out_specs=[_row_spec(br)] * 3,
        out_shape=[jax.ShapeDtypeStruct(g2.shape, g.dtype),
                   jax.ShapeDtypeStruct(g2.shape, sd),
                   jax.ShapeDtypeStruct(g2.shape, sd)],
        compiler_params=_params(),
        interpret=_interpret(),
        name="ff_fused_optim_adam",
    )(g2, mu2, nu2, p2, sc)
    return (_unpad(upd2, g.shape, g.size),
            _unpad(mu_o2, g.shape, g.size),
            _unpad(nu_o2, g.shape, g.size))


def _sgd_leaf(g, t, p, plan):
    g2, br = _pad2d(g)
    p2, _ = _pad2d(p)
    common = dict(compiler_params=_params(), interpret=_interpret(),
                  name="ff_fused_optim_sgd")
    if t is None:
        upd2 = pl.pallas_call(
            functools.partial(_sgd_plain_kernel, lr=plan["lr"],
                              wd=plan["wd"]),
            grid=(g2.shape[0] // br,),
            in_specs=[_row_spec(br)] * 2,
            out_specs=_row_spec(br),
            out_shape=jax.ShapeDtypeStruct(g2.shape, g.dtype),
            **common,
        )(g2, p2)
        return _unpad(upd2, g.shape, g.size), None
    t2, _ = _pad2d(t)
    upd2, t_o2 = pl.pallas_call(
        functools.partial(_sgd_kernel, momentum=plan["momentum"],
                          nesterov=plan["nesterov"], lr=plan["lr"],
                          wd=plan["wd"]),
        grid=(g2.shape[0] // br,),
        in_specs=[_row_spec(br)] * 3,
        out_specs=[_row_spec(br)] * 2,
        out_shape=[jax.ShapeDtypeStruct(g2.shape, g.dtype),
                   jax.ShapeDtypeStruct(g2.shape, t.dtype)],
        **common,
    )(g2, t2, p2)
    return _unpad(upd2, g.shape, g.size), _unpad(t_o2, g.shape, g.size)


# ----------------------------------------------- state-structure surgery
def _find_node(state, cls):
    """Depth-first search for the unique `cls` node in an optax chain state.
    Returns the node or None."""
    if isinstance(state, cls):
        return state
    if isinstance(state, (tuple, list)) and not hasattr(state, "_fields"):
        for s in state:
            found = _find_node(s, cls)
            if found is not None:
                return found
    return None


def _replace_node(state, cls, new):
    if isinstance(state, cls):
        return new
    if isinstance(state, (tuple, list)) and not hasattr(state, "_fields"):
        return type(state)(_replace_node(s, cls, new) for s in state)
    return state


def _tree3(out_tree, grads):
    """Transpose a tree-of-3-tuples into 3 trees."""
    outer = jax.tree_util.tree_structure(grads)
    inner = jax.tree_util.tree_structure((0, 0, 0))
    return jax.tree_util.tree_transpose(outer, inner, out_tree)


# ------------------------------------------------------------------ update
def fused_update(plan: Dict[str, Any], grads, opt_state, params,
                 mesh=None, shardings=None) -> Optional[Tuple[Any, Any]]:
    """tx.update replacement: (updates, new_opt_state), or None when the
    live state doesn't match the plan (caller falls back to tx.update).

    On a multi-device `mesh`, `shardings` (a NamedSharding per param — the
    moment layout) splits every leaf's kernel per shard: the update is
    elementwise, so each device runs it on its own slice of (g, mu, nu, p)
    with no collective (kernels/partition.py)."""
    if shardings is None:
        shardings = jax.tree_util.tree_map(lambda _: None, grads)

    def tm(leaf_fn, n_out, *trees, consts=()):
        # `consts` ride along replicated (a traced value must enter a
        # shard_map as an argument, not a closure capture)
        def one(sh, *leaves):
            spec = sh.spec if sh is not None else PartitionSpec()
            return per_shard(
                leaf_fn, mesh,
                (spec,) * len(leaves) + (PartitionSpec(),) * len(consts),
                (spec,) * n_out if n_out > 1 else spec)(*leaves, *consts)

        return jax.tree_util.tree_map(
            one, shardings, *trees,
            is_leaf=lambda x: x is None or isinstance(x, NamedSharding))
    if plan["kind"] == "adam":
        s = _find_node(opt_state, optax.ScaleByAdamState)
        if s is None:
            return None
        count = s.count + 1
        c32 = count.astype(jnp.float32)
        bc1 = 1.0 - plan["b1"] ** c32
        bc2 = 1.0 - plan["b2"] ** c32
        sc = jnp.zeros((1, _LANES), jnp.float32)
        sc = sc.at[0, 0].set(bc1).at[0, 1].set(bc2)
        out = tm(lambda g, m, n, p, sc: _adam_leaf(g, m, n, p, sc, plan), 3,
                 grads, s.mu, s.nu, params, consts=(sc,))
        upd, mu, nu = _tree3(out, grads)
        new_s = optax.ScaleByAdamState(count=count, mu=mu, nu=nu)
        return upd, _replace_node(opt_state, optax.ScaleByAdamState, new_s)
    if plan["kind"] == "sgd":
        if plan["momentum"]:
            s = _find_node(opt_state, optax.TraceState)
            if s is None:
                return None
            out = tm(lambda g, t, p: _sgd_leaf(g, t, p, plan), 2,
                     grads, s.trace, params)
            outer = jax.tree_util.tree_structure(grads)
            inner = jax.tree_util.tree_structure((0, 0))
            upd, trace = jax.tree_util.tree_transpose(outer, inner, out)
            new_s = optax.TraceState(trace=trace)
            return upd, _replace_node(opt_state, optax.TraceState, new_s)
        upd = tm(lambda g, p: _sgd_leaf(g, None, p, plan)[0], 1,
                 grads, params)
        return upd, opt_state
    return None
