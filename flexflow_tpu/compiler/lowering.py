"""Layer-graph → JAX forward function.

Reference analog: the execution half of FFModel::compile + FFModel::forward
(src/runtime/model.cc:2415) — but where the reference launches one Legion
IndexLauncher per op per iteration, here the whole graph is interpreted ONCE
at trace time into a single XLA computation; sharding constraints (the
searched strategy) are attached per op output, and XLA GSPMD inserts the
collectives the reference got from Legion region movement + NCCL.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from flexflow_tpu.core.graph import topo_order
from flexflow_tpu.core.layer import Layer
from flexflow_tpu.core.tensor import Tensor
from flexflow_tpu.ops import get_op_def
from flexflow_tpu.ops.registry import STATS_KEY, LoweringCtx
from flexflow_tpu.parallel.sharding import Strategy, used_axes


def constrainable(pspec: PartitionSpec, shape, mesh: Mesh) -> bool:
    """A constraint is legal only if every sharded dim divides evenly."""
    for i, ax in enumerate(pspec):
        if ax is None:
            continue
        axes = [ax] if isinstance(ax, str) else list(ax)
        degree = 1
        for a in axes:
            if a not in mesh.shape:
                return False
            degree *= mesh.shape[a]
        if i >= len(shape) or shape[i] % degree != 0:
            return False
    return True


def maybe_constrain(x, pspec: PartitionSpec, mesh: Mesh):
    # Leave unconstrained when the spec pins nothing: constraining to
    # fully-replicated would force an all-gather GSPMD might not need.
    if len(pspec) == 0 or all(a is None for a in pspec):
        return x
    if not constrainable(pspec, x.shape, mesh):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, pspec))


def checkpoint_units(order: Sequence[Layer], outputs: Sequence[Tensor],
                     remat_map: Dict[str, str]) -> List[List[Layer]]:
    """`order` cut into the units a forward pass runs: a layer alone, or a
    run of consecutive layers whose policy is "block", which `jax.checkpoint`
    recomputes as ONE function in the backward pass. Such a unit ends after
    a layer one of whose outputs has more than one consumer or is the
    graph's (the residual stream after each add, a norm's output that two
    branches read): those tensors, the units' inputs, are all the forward
    pass keeps of it."""
    consumers: Dict[int, int] = {}      # the layers that read a tensor
    for layer in order:
        for guid in {t.guid for t in layer.inputs}:
            consumers[guid] = consumers.get(guid, 0) + 1
    for t in outputs:
        consumers[t.guid] = consumers.get(t.guid, 0) + 2
    units: List[List[Layer]] = []
    open_unit = False
    for layer in order:
        block = remat_map.get(layer.name) == "block"
        if block and open_unit:
            units[-1].append(layer)
        else:
            units.append([layer])
        open_unit = block and all(consumers.get(t.guid, 0) <= 1
                                  for t in layer.outputs)
    return units


def build_forward(
    layers: Sequence[Layer],
    graph_inputs: Sequence[Tensor],
    outputs: Sequence[Tensor],
    mesh: Optional[Mesh],
    strategy: Strategy,
    seq_length: Optional[int] = None,
    compute_dtype: Optional[str] = None,
    enable_fusion: bool = True,
    collect_stats: bool = False,
) -> Callable:
    """Returns forward(params, state, input_arrays, training, rng)
    -> (output_arrays, new_state). `collect_stats`: new_state[STATS_KEY]
    holds the counters that ops reported through ctx.add_stat, where any
    did (the serving programs; a training step, which hands them out with
    its metrics)."""
    import jax.numpy as jnp

    order = topo_order(layers)
    cast_to = None
    if compute_dtype and compute_dtype not in ("float32", "f32", None):
        cast_to = jnp.dtype(compute_dtype)

    op_attrs = {name: dict(sh.attrs)
                for name, sh in strategy.op_shardings.items() if sh.attrs}

    # per-layer rematerialization (searched by the memory-aware DP, or the
    # uniform --remat compat alias): "full" saves only the layer's inputs
    # and recomputes everything in the backward pass; "dots" keeps matmul
    # results (jax.checkpoint_policies.checkpoint_dots) and recomputes the
    # cheap elementwise tail. Recompute reuses the SAME rng (fold_in of the
    # layer guid is deterministic), so remat never changes numerics.
    remat_map: Dict[str, str] = dict(getattr(strategy, "remat", None) or {})
    _ckpt_policies = {
        "full": None,
        "dots": jax.checkpoint_policies.checkpoint_dots,
    }

    from flexflow_tpu.ops.op_type import OperatorType as _OT

    _norm_types = (_OT.LAYERNORM, _OT.BATCHNORM, _OT.RMSNORM)
    # per-layer weight names exempt from the compute-dtype cast: norm params
    # (gamma/beta) — including norms nested inside fork_join branches, whose
    # weights surface as "b{i}.{sublayer}.{w}" on the composite layer
    cast_exempt: Dict[str, set] = {}
    for _l in layers:
        if _l.op_type in _norm_types:
            cast_exempt[_l.name] = set(_l.weight_specs)
        elif get_op_def(_l.op_type).uncast_weights:
            cast_exempt[_l.name] = set(get_op_def(_l.op_type).uncast_weights)
        elif _l.op_type is _OT.FORK_JOIN:
            ex = set()
            for bi, (bls, _bx, _bo) in enumerate(_l.branches):
                for bl in bls:
                    if bl.op_type in _norm_types:
                        ex.update(f"b{bi}.{bl.name}.{w}" for w in bl.weight_specs)
                        ex.update(f"stk.{bl.name}.{w}" for w in bl.weight_specs)
            if ex:
                cast_exempt[_l.name] = ex

    units = checkpoint_units(order, outputs, remat_map)
    # what each unit hands on: its tensors that a layer of another unit,
    # or the caller, reads
    unit_of = {layer.name: i for i, unit in enumerate(units)
               for layer in unit}
    handed_on: List[List[int]] = [[] for _ in units]
    reads = [(unit_of[layer.name], t) for layer in order
             for t in layer.inputs] + [(None, t) for t in outputs]
    for reader, t in reads:
        # (a tensor of a layer outside `layers` is an input here)
        made_in = unit_of.get(t.owner.name) if t.owner is not None else None
        if made_in is not None and made_in != reader \
                and t.guid not in handed_on[made_in]:
            handed_on[made_in].append(t.guid)
    ctx_kw = dict(seq_length=seq_length,
                  compute_dtype=str(cast_to) if cast_to else None, mesh=mesh,
                  op_attrs=op_attrs, op_shardings=strategy.op_shardings,
                  enable_fusion=enable_fusion)

    def sub_ctx(state, rng, training):
        """The context of a checkpointed function: its own new_state and
        stats, which come back as explicit outputs."""
        return LoweringCtx(training=training, rng=rng, state=state,
                           stats={} if collect_stats else None, **ctx_kw)

    def cast_weights(layer, w):
        # uniform mixed-precision policy: master weights stay f32 in
        # params/optimizer, every op computes in compute_dtype; grads
        # flow back through the cast and accumulate in f32. Norm
        # params (gamma/beta) are exempt — their lowerings compute the
        # affine in f32 (standard AMP keeps norm params full
        # precision) — including norms inside fork_join branches.
        ex = cast_exempt.get(layer.name, ())
        return {k: (v.astype(cast_to)
                    if k not in ex and jnp.issubdtype(v.dtype, jnp.floating)
                    else v)
                for k, v in w.items()}

    def lower_one(layer, ins, w, ctx):
        outs = get_op_def(layer.op_type).lower(layer, ins, w, ctx)
        if mesh is not None:
            sh = strategy.sharding_for(layer.name)
            outs = [maybe_constrain(o, sh.output_pspec(i), mesh)
                    for i, o in enumerate(outs)]
        return outs

    def run_block(unit, env, params, ctx):
        """A unit of "block" layers as ONE checkpointed function of the
        tensors it reads from outside, its layers' weights as they lie (the
        compute-dtype cast is inside: recomputed, not kept), the state and
        the rng; its layers keep their own name scopes. Kept besides: what
        the unit's ops tag under their `kept_names` (an expert layer's
        routing decision: two megabytes a layer, where the recomputation
        would run the `top_k`, the gather, the sort and the counts again,
        and a third time under the layer's own checkpoint of a token
        block; the expert layer's result, which is all that the unit's
        later layers read of it, so the recomputation runs none of the
        layer's work; an attention layer's `o` and flat `lse` where its
        sequence went through the flash kernels, whose forward one the
        recomputation then leaves out). A unit whose ops name nothing is
        checkpointed with no policy, as it was."""
        made = {t.guid for layer in unit for t in layer.outputs}
        reads = list(dict.fromkeys(t.guid for layer in unit
                                   for t in layer.inputs
                                   if t.guid not in made))
        hands_on = handed_on[unit_of[unit[0].name]]
        training = ctx.training
        kept = sorted({name for layer in unit
                       for name in get_op_def(layer.op_type).kept_names})

        def _unit(u_ins, u_w, u_state, u_rng):
            sub = sub_ctx(u_state, u_rng, training)
            local = dict(zip(reads, u_ins))
            for layer in unit:
                with jax.named_scope(layer.name):
                    w = u_w.get(layer.name, {})
                    if cast_to is not None:
                        w = cast_weights(layer, w)
                    outs = lower_one(layer, [local[t.guid]
                                             for t in layer.inputs], w, sub)
                for t, o in zip(layer.outputs, outs):
                    local[t.guid] = o
            return [local[g] for g in hands_on], sub.new_state, \
                sub.stats or {}

        policy = jax.checkpoint_policies.save_only_these_names(*kept) \
            if kept else None
        outs, delta, counted = jax.checkpoint(_unit, policy=policy)(
            [env[g] for g in reads],
            {layer.name: params[layer.name] for layer in unit
             if layer.name in params}, dict(ctx.state), ctx.rng)
        env.update(zip(hands_on, outs))
        ctx.new_state.update(delta)
        for stat, value in counted.items():
            ctx.add_stat(stat, value)

    def forward(params, state, input_arrays, training, rng):
        ctx = LoweringCtx(training=training, rng=rng, seq_length=seq_length,
                          state=dict(state),
                          compute_dtype=str(cast_to) if cast_to else None,
                          mesh=mesh, op_attrs=op_attrs,
                          op_shardings=strategy.op_shardings,
                          enable_fusion=enable_fusion,
                          stats={} if collect_stats else None)
        env: Dict[int, jax.Array] = {}
        for t, arr in zip(graph_inputs, input_arrays):
            if cast_to is not None and jnp.issubdtype(arr.dtype, jnp.floating):
                arr = arr.astype(cast_to)
            if mesh is not None:
                arr = maybe_constrain(arr, strategy.input_pspec(t.name), mesh)
            env[t.guid] = arr
        for unit in units:
            if remat_map.get(unit[0].name) == "block":
                run_block(unit, env, params, ctx)
                continue
            layer, = unit
            ins = [env[t.guid] for t in layer.inputs]
            w = params.get(layer.name, {})
            # stamp the graph-layer name into the name stack: the compiled
            # program's optimized HLO then says, per instruction,
            # metadata.op_name "jit(train_step)/transpose(jvp(<layer.name>))
            # /dot_general". The profile itself carries no such names (a
            # device event is named by its HLO instruction), so
            # attribution.op_scope_map reads them from the executable's
            # text and joins by instruction name.
            scope = jax.named_scope(layer.name)
            if cast_to is not None:
                with jax.named_scope(layer.name):   # the cast is its work too
                    w = cast_weights(layer, w)
            pol = remat_map.get(layer.name)
            if pol in _ckpt_policies:
                # run the layer inside jax.checkpoint as a pure function of
                # (ins, w, state, rng): the sub-ctx isolates new_state so
                # stateful updates come back as an explicit output instead
                # of leaking tracers through the closed-over ctx
                def _one(l_ins, l_w, l_state, l_rng, _l=layer):
                    sub = sub_ctx(l_state, l_rng, training)
                    return lower_one(_l, l_ins, l_w, sub), sub.new_state, \
                        sub.stats or {}
                ckpt = jax.checkpoint(_one, policy=_ckpt_policies[pol])
                with scope:
                    outs, delta, counted = ckpt(ins, w, dict(ctx.state),
                                                ctx.rng)
                ctx.new_state.update(delta)
                for stat, value in counted.items():
                    ctx.add_stat(stat, value)
            else:
                with scope:
                    outs = lower_one(layer, ins, w, ctx)
            for t, o in zip(layer.outputs, outs):
                env[t.guid] = o
        result = [env[t.guid] for t in outputs]
        new_state = dict(state)
        new_state.update(ctx.new_state)
        if ctx.stats:
            new_state[STATS_KEY] = ctx.stats
        return result, new_state

    return forward
