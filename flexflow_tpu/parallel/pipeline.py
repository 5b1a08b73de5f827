"""Pipeline-parallel execution: sequential stages on disjoint device groups.

Reference analog: the sequential inter-op splits of the PCG search ("Beyond
Data and Model Parallelism for DNNs" — pipeline as a first-class dimension
of the hybrid space) executed MPMD-style as in JaxPP ("Scaling Deep Learning
Training with MPMD Pipeline Parallelism"): each stage is its OWN jitted
computation placed on its own sub-mesh, and the host drives the microbatch
schedule by dispatching stage programs asynchronously — device groups on
different stages run concurrently because their dispatches are independent,
exactly the Legion async-launch property the training loop already exploits
(compiler/compile.py _fit_epochs).

Why not one big shard_map over a `pipe` mesh axis (the interop.py pattern)?
Stage boundaries carry DIFFERENT tensor shapes (token ids in, hiddens
between, logits out) and the 1F1B schedule needs per-(stage, microbatch)
control flow with buffer retirement — a single SPMD program would have to
lockstep all of it through lax.switch with padded uniform buffers. Per-stage
programs keep each stage's XLA computation clean and make the schedule a
host-side data structure (cost_model.pipeline_order — the SAME definition
the search prices and the simulator validates).

Residency: stage s's weights and optimizer state live ONLY on its device
group (sharded/replicated over the stage sub-mesh by the searched intra-
stage strategy) — per-device persistent memory divides by the stage count,
composing with --zero-sharding (moments further divide by the stage's data
degree) and with tensor parallelism inside a stage.

Backward: recompute-based (the flash-attention/interop.py convention): each
backward op re-runs its stage's forward under jax.vjp from the stashed
stage INPUT — so a stage stashes one input activation per in-flight
microbatch (M under gpipe, <= S under 1f1b), never the interior
activations.

Numerics: identical to the sequential accum_steps loop up to float
reassociation — same per-microbatch rng streams (fold_in(iter_rng, m), and
dropout folds by layer guid, which partitioning preserves), same mean-of-M
gradient, one optimizer update per group. Weight init folds by GLOBAL topo
position (compiler.compile.build_init_fn), so a pipelined model starts from
bitwise the same weights as its sequential twin.
"""

from __future__ import annotations

import itertools
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from flexflow_tpu import health
from flexflow_tpu import telemetry as tel
from flexflow_tpu.core.graph import topo_order
from flexflow_tpu.losses import LossType, compute_loss
from flexflow_tpu.metrics import compute_metrics
from flexflow_tpu.parallel.machine import MachineSpec
from flexflow_tpu.parallel.sharding import Strategy, dims_to_pspec
from flexflow_tpu.runtime import faults as _faults
from flexflow_tpu.runtime.dataloader import SingleDataLoader, group_microbatches
from flexflow_tpu.runtime.resilience import (RetryPolicy, progress_dict,
                                             run_resilient, start_state)
from flexflow_tpu.search import cost_model as cm


# process-wide fit sequence: telemetry pipe events carry fit=<id> so the
# bubble grouping (telemetry.pipeline_bubble_from_events) never merges two
# fits whose update counters both restarted at 0 (init() resets iteration)
_FIT_SEQ = itertools.count()


def stage_device_groups(num_stages: int, per_stage: int) -> List[List]:
    """Contiguous disjoint device groups, stage-major: stage s owns devices
    [s*per_stage, (s+1)*per_stage). Contiguity keeps a stage's collectives
    on neighboring chips and the boundary hop between neighbors."""
    devs = jax.devices()
    need = num_stages * per_stage
    if need > len(devs):
        raise ValueError(f"{num_stages} stages x {per_stage} devices "
                         f"need {need} devices, have {len(devs)}")
    return [devs[s * per_stage:(s + 1) * per_stage]
            for s in range(num_stages)]


def partition_layers(model, cuts: Sequence[int]):
    """Split the model's topo order at `cuts` (cut AFTER topo index c) into
    stage layer lists + the boundary tensor each cut transfers. Cuts must
    be single-tensor cut points (candidates.stage_cut_candidates enforces
    this for searched cuts; explicit cuts are validated here)."""
    from flexflow_tpu.search.candidates import cut_boundary_tensor
    from flexflow_tpu.search.unity import sequence_cut_indices

    order = topo_order(model.layers)
    cuts = sorted(cuts)  # stages AND boundaries index off the same order
    bounds = [-1] + cuts + [len(order) - 1]
    stages, boundaries = [], []
    for si in range(len(bounds) - 1):
        stages.append(order[bounds[si] + 1:bounds[si + 1] + 1])
    ok = set(sequence_cut_indices(order, model.input_tensors))
    for c in cuts:
        if c not in ok:
            raise ValueError(
                f"cut after layer {order[c].name} (topo {c}) is not a "
                f"single-tensor cut point; valid cuts: {sorted(ok)}")
        # the LIVE output of the cut layer (not necessarily outputs[0])
        boundaries.append(cut_boundary_tensor(order, c))
    # every model input must be consumed inside stage 0 (guaranteed by the
    # single-live-tensor rule: a later consumer would keep the input live
    # across the cut)
    s0 = {id(l) for l in stages[0]}
    for t in model.input_tensors:
        for l in order:
            if any(x.guid == t.guid for x in l.inputs) and id(l) not in s0:
                raise ValueError(f"model input {t.name} consumed outside "
                                 f"stage 0 (layer {l.name})")
    return stages, boundaries


def balanced_cuts(model, stage_machine: MachineSpec, num_stages: int):
    """Default stage partition when the search is off: the best-balance
    candidate from the same enumerator the search uses."""
    from flexflow_tpu.search.candidates import stage_cut_candidates

    combos = stage_cut_candidates(model, stage_machine, num_stages,
                                  max_candidates=1)
    if not combos:
        raise ValueError(
            f"model has too few single-tensor cut points for "
            f"{num_stages} pipeline stages")
    return list(combos[0])


class PipelinedModel:
    """The pipeline-parallel counterpart of CompiledModel: same fit /
    evaluate / init / memory_stats / checkpoint surface, executed as S
    per-stage programs under a GPipe or 1F1B microbatch schedule.

    One "step" = one optimizer update = cfg.accum_steps microbatches
    through the pipeline (the existing microbatch plumbing: the fit loop
    groups the loader with runtime/dataloader.group_microbatches, exactly
    as the sequential accum path does)."""

    def __init__(self, model, machine: MachineSpec,
                 stage_machine: MachineSpec, strategy: Strategy,
                 optimizer, loss_type: LossType, metrics, outputs):
        if not strategy.pipeline:
            raise ValueError("strategy carries no pipeline block")
        self.model = model
        self.machine = machine          # the FULL machine (all groups)
        self.stage_machine = stage_machine
        self.strategy = strategy
        self.optimizer = optimizer
        self.tx = optimizer.to_optax()
        self.loss_type = loss_type
        self.metrics = list(metrics)
        self.outputs = list(outputs)
        self.cfg = model.config
        self.num_stages = int(strategy.pipeline["stages"])
        if self.num_stages < 2:
            raise ValueError("PipelinedModel needs >= 2 stages; use the "
                             "plain CompiledModel path for 1")
        self.schedule = strategy.pipeline.get("schedule",
                                              self.cfg.pipeline_schedule)
        # sorted defensively: an imported/hand-edited strategy JSON may
        # carry cuts out of order, and stage/boundary pairing assumes
        # ascending topo positions
        self.cuts = sorted(int(c) for c in strategy.pipeline["cuts"])
        self._retry_policy = RetryPolicy.from_config(self.cfg)
        self._iteration = 0
        self.step_stats: Dict[str, int] = {}
        # drift-monitor windows [(updates, wall_seconds)] per epoch of the
        # last fit, and the telemetry-measured bubble accumulator (mean of
        # per-update bubbles from the executed op timeline)
        self._drift_windows: List[tuple] = []
        self._bubble_sum = 0.0
        self._bubble_n = 0
        # run health (ISSUE 9): goodput buckets, HBM watermarks, and the
        # numerics sentinel state of the last fit (flexflow_tpu/health.py)
        self._goodput: Optional[health.GoodputMeter] = None
        self._watermarks = health.WatermarkTracker()
        self._sentinel_state: Optional[health.SentinelState] = None
        self._gn_acc: List[Any] = []
        if jax.process_count() != 1:
            raise NotImplementedError(
                "pipeline parallelism is single-process for now (stage "
                "groups are subsets of the local devices)")

        self.stage_layers, self.boundaries = partition_layers(model,
                                                              self.cuts)
        groups = stage_device_groups(self.num_stages,
                                     stage_machine.num_devices)
        shape = tuple(stage_machine.mesh_axes.values())
        names = tuple(stage_machine.mesh_axes.keys())
        self.stage_meshes = [Mesh(np.array(g).reshape(shape), names)
                             for g in groups]

        self._build_stage_graphs()
        self._build_stage_fns()
        self.stage_params: List[Any] = [None] * self.num_stages
        self.stage_opt: List[Any] = [None] * self.num_stages
        self.stage_state: List[Dict[str, Any]] = [{} for _ in
                                                  range(self.num_stages)]

    # ------------------------------------------------------------ builders
    def _batch_sizes(self):
        return {t.shape[0] for t in self.model.input_tensors if t.ndim > 0}

    def _dp_pspec(self, shape) -> PartitionSpec:
        from flexflow_tpu.search.candidates import _dp_dims

        return dims_to_pspec(_dp_dims(shape, self.stage_machine,
                                      self._batch_sizes()))

    def _build_stage_graphs(self):
        from flexflow_tpu.compiler.lowering import build_forward

        S = self.num_stages
        self.stage_inputs: List[List] = []
        self.stage_outputs: List[List] = []
        self._forwards = []
        for s in range(S):
            seg = self.stage_layers[s]
            internal = {o.guid for l in seg for o in l.outputs}
            ext, seen = [], set()
            for l in seg:
                for t in l.inputs:
                    if t.guid not in internal and t.guid not in seen:
                        seen.add(t.guid)
                        ext.append(t)
            outs = [self.boundaries[s]] if s < S - 1 else self.outputs
            self.stage_inputs.append(ext)
            self.stage_outputs.append(outs)
            self._forwards.append(build_forward(
                seg, ext, outs, self.stage_meshes[s], self.strategy,
                seq_length=self.cfg.seq_length or None,
                compute_dtype=self.cfg.compute_dtype,
                enable_fusion=self.cfg.enable_fusion))
        # boundary b sits between stages b and b+1: the SAME dp pspec on
        # the producer's mesh (outbound) and the consumer's mesh (inbound)
        # — the stage-boundary transfer is a resharding between the two
        # sub-meshes, expressed as a device_put onto the target
        # NamedSharding (GSPMD-level constraint, host never touches data)
        self._bound_out_sh = []
        self._bound_in_sh = []
        for b, t in enumerate(self.boundaries):
            ps = self._dp_pspec(t.shape)
            self._bound_out_sh.append(
                NamedSharding(self.stage_meshes[b], ps))
            self._bound_in_sh.append(
                NamedSharding(self.stage_meshes[b + 1], ps))
        self._in_sh0 = [
            NamedSharding(self.stage_meshes[0], self._dp_pspec(t.shape))
            for t in self.model.input_tensors]

    def _stage_weight_shardings(self, s: int):
        from flexflow_tpu.compiler.lowering import constrainable

        mesh = self.stage_meshes[s]
        shardings = {}
        for layer in self.stage_layers[s]:
            if not layer.weight_specs:
                continue
            d = {}
            for w, spec in layer.weight_specs.items():
                ps = self.strategy.sharding_for(layer.name).weight_pspec(w)
                if not constrainable(ps, spec.shape, mesh):
                    ps = PartitionSpec()
                d[w] = NamedSharding(mesh, ps)
            shardings[layer.name] = d
        return shardings

    def _zero_mode(self) -> str:
        from flexflow_tpu.compiler.compile import _zero_axes_of

        mode = (self.cfg.zero_sharding or "off").lower()
        if mode not in ("off", "zero1", "zero2"):
            raise ValueError(f"zero_sharding={self.cfg.zero_sharding!r}")
        if mode != "off" and not _zero_axes_of(self.stage_meshes[0]):
            return "off"
        return mode

    def _stage_opt_shardings(self, s: int, pshapes, pshards):
        """Optimizer-state sharding tree for one stage: the param's layout,
        plus the ZeRO data-axis spread on the STAGE sub-mesh — pipeline and
        ZeRO compose (per-device moments divide by stages x data degree)."""
        from flexflow_tpu.compiler.compile import (_zero_axes_of,
                                                   _zero_moment_pspec)

        mesh = self.stage_meshes[s]
        repl = NamedSharding(mesh, PartitionSpec())
        if self._zero_mode() == "off":
            moment_sh = pshards
        else:
            za = _zero_axes_of(mesh)
            moment_sh = jax.tree_util.tree_map(
                lambda sds, sh: NamedSharding(mesh, _zero_moment_pspec(
                    sh.spec, sds.shape, mesh, za)), pshapes, pshards)
        shapes = jax.eval_shape(self.tx.init, pshapes)
        pstruct = jax.tree_util.tree_structure(pshapes)
        if pstruct.num_leaves == 0:
            return (jax.tree_util.tree_map(lambda _: repl, shapes),
                    moment_sh)

        def is_params_subtree(x):
            return jax.tree_util.tree_structure(x) == pstruct

        return (jax.tree_util.tree_map(
            lambda sub: moment_sh if is_params_subtree(sub) else repl,
            shapes, is_leaf=is_params_subtree), moment_sh)

    def _build_stage_fns(self):
        S = self.num_stages
        loss_type, metric_types = self.loss_type, self.metrics
        remat = self.cfg.remat
        precision = None if self.cfg.allow_tensor_op_math_conversion \
            else "highest"
        all_regs = dict(self.model._weight_regularizers)

        def _wrap(fn):
            if precision is None:
                return fn

            def wrapped(*a):
                with jax.default_matmul_precision(precision):
                    return fn(*a)

            return wrapped

        self._f_fns, self._b_fns = [], []
        self._upd_fns, self._acc_fns = [], []
        self._ef_fns = []
        self._stage_has_regs = []
        zero = self._zero_mode()
        self._param_sh, self._opt_sh = [], []
        self._moment_sh = []
        for s in range(S):
            fwd = self._forwards[s]
            if remat:
                fwd = jax.checkpoint(fwd, static_argnums=(3,))
            names = {l.name for l in self.stage_layers[s]}
            regs = {k: v for k, v in all_regs.items() if k[0] in names}
            self._stage_has_regs.append(bool(regs))
            last = s == S - 1

            def reg_loss(p, _regs=regs):
                r = 0.0
                for (ln, wn), terms in _regs.items():
                    w = p[ln][wn].astype(jnp.float32)
                    for mode, lam in terms:
                        r = r + lam * (jnp.sum(jnp.abs(w)) if mode == "l1"
                                       else jnp.sum(w * w))
                return r

            def f_fn(params, state, xs, rng, _fwd=fwd):
                outs, new_state = _fwd(params, state, xs, True, rng)
                return outs[0], new_state

            def ef_fn(params, state, xs, _fwd=fwd, _all=last):
                outs, _ = _fwd(params, state, xs, False,
                               jax.random.PRNGKey(0))
                # interior stages ship the single boundary tensor; the
                # LAST stage returns every model output (forward() parity
                # with CompiledModel on multi-output models)
                return outs if _all else outs[0]

            if last:
                def b_fn(params, state, xs, label, rng, _fwd=fwd,
                         _regs=regs, _first=(s == 0)):
                    def loss_fn(p, x):
                        outs, new_state = _fwd(p, state, x, True, rng)
                        logits = outs[0]
                        loss = compute_loss(loss_type,
                                            logits.astype(jnp.float32),
                                            label)
                        loss = loss + reg_loss(p, _regs)
                        return loss, (logits, new_state)

                    if _first:  # S==1 is rejected upstream; stage0==last
                        raise AssertionError("unreachable")
                    (loss, (logits, new_state)), (gp, gx) = \
                        jax.value_and_grad(loss_fn, argnums=(0, 1),
                                           has_aux=True)(params, xs)
                    mvals = compute_metrics(metric_types,
                                            logits.astype(jnp.float32),
                                            label)
                    return loss, gp, gx[0], new_state, mvals

                def e_fn(params, state, xs, label, _fwd=fwd):
                    outs, _ = _fwd(params, state, xs, False,
                                   jax.random.PRNGKey(0))
                    logits = outs[0].astype(jnp.float32)
                    return (compute_loss(loss_type, logits, label),
                            compute_metrics(metric_types, logits, label))

                self._e_last = jax.jit(_wrap(e_fn))
            elif s == 0:
                # first stage: inputs may be integer (token ids) — no
                # input cotangent exists or is needed. Returns the stage's
                # regularizer penalty too: the reported loss must include
                # EVERY stage's reg terms, like the sequential loop's.
                def b_fn(params, state, xs, gy, rng, _fwd=fwd, _regs=regs):
                    def run(p):
                        return _fwd(p, state, xs, True, rng)[0][0]

                    _, pull = jax.vjp(run, params)
                    (gp,) = pull(gy)
                    rv = jnp.float32(0.0)
                    if _regs:
                        rv, gr = jax.value_and_grad(
                            lambda p: reg_loss(p, _regs))(params)
                        gp = jax.tree_util.tree_map(jnp.add, gp, gr)
                    return gp, None, rv
            else:
                def b_fn(params, state, xs, gy, rng, _fwd=fwd, _regs=regs):
                    def run(p, x):
                        return _fwd(p, state, x, True, rng)[0][0]

                    _, pull = jax.vjp(run, params, xs)
                    gp, gx = pull(gy)
                    rv = jnp.float32(0.0)
                    if _regs:
                        rv, gr = jax.value_and_grad(
                            lambda p: reg_loss(p, _regs))(params)
                        gp = jax.tree_util.tree_map(jnp.add, gp, gr)
                    return gp, gx[0], rv

            # optimizer update: mean the accumulated gradient sum, then the
            # (possibly ZeRO-rewritten) update — reduce-scatter(grads) ->
            # sharded moment update -> all-gather(updates), exactly the
            # compile.py apply_update contract, on the stage sub-mesh
            pshapes = {
                l.name: {w: jax.ShapeDtypeStruct(sp.shape,
                                                 sp.dtype.jnp_dtype)
                         for w, sp in l.weight_specs.items()}
                for l in self.stage_layers[s] if l.weight_specs}
            pshards = self._stage_weight_shardings(s)
            opt_sh, moment_sh = self._stage_opt_shardings(s, pshapes,
                                                          pshards)
            self._param_sh.append(pshards)
            self._opt_sh.append(opt_sh)
            self._moment_sh.append(moment_sh)
            wsc = jax.lax.with_sharding_constraint
            tx = self.tx
            sent_on = bool(getattr(self.cfg, "health_sentinels", False))
            self._sentinels_on = sent_on

            def upd_fn(params, opt_state, gsum, inv, _moment_sh=moment_sh,
                       _pshards=pshards, _opt_sh=opt_sh):
                g = jax.tree_util.tree_map(lambda t: t * inv, gsum)
                # numerics sentinel (health.py): this stage's squared grad
                # global-norm rides out as a third output — a device
                # scalar on the STAGE mesh, accumulated there and
                # materialized only at epoch end (cross-stage norms sum as
                # squares; NaN/Inf propagates through the sum)
                gn_sq = optax.global_norm(g) ** 2 if sent_on \
                    else jnp.float32(0.0)
                if zero != "off":
                    g = wsc(g, _moment_sh)
                updates, opt_state = tx.update(g, opt_state, params)
                if zero != "off":
                    updates = wsc(updates, _pshards)
                    opt_state = wsc(opt_state, _opt_sh)
                return optax.apply_updates(params, updates), opt_state, \
                    gn_sq

            donate = (0, 1, 2) if self.cfg.donate_state else ()
            self._f_fns.append(jax.jit(_wrap(f_fn)))
            self._ef_fns.append(jax.jit(_wrap(ef_fn)))
            self._b_fns.append(jax.jit(_wrap(b_fn)))
            self._upd_fns.append(jax.jit(_wrap(upd_fn),
                                         donate_argnums=donate))
            self._acc_fns.append(jax.jit(
                lambda a, g: jax.tree_util.tree_map(jnp.add, a, g),
                donate_argnums=(0,)))

    # ---------------------------------------------------------------- init
    def init(self, seed: Optional[int] = None):
        from flexflow_tpu.compiler.compile import build_init_fn

        seed = self.cfg.seed if seed is None else seed
        full_order = topo_order(self.model.layers)
        topo_idx = {id(l): i for i, l in enumerate(full_order)}
        overrides = self.model._initializer_overrides
        for s in range(self.num_stages):
            init_fn = build_init_fn(self.stage_layers[s], overrides,
                                    topo_idx)
            self.stage_params[s] = jax.jit(
                init_fn, out_shardings=self._param_sh[s])(
                    jax.random.PRNGKey(seed))
            self.stage_opt[s] = jax.jit(
                self.tx.init, out_shardings=self._opt_sh[s])(
                    self.stage_params[s])
            self.stage_state[s] = {}
        self._iteration = 0
        # HBM watermark at the compile/init boundary (health.py): the
        # persistent per-stage footprint right after state materialization
        self._watermarks.sample(
            "init", tuple(self.stage_params) + tuple(self.stage_opt))
        return self.stage_params

    # ------------------------------------------------------------ the step
    def _put(self, arr, sharding):
        return jax.device_put(arr, sharding)

    def _xfer_in(self, arr, sharding):
        """Host->device microbatch input transfer (stage 0) — the
        `dataloader/transfer` retry + fault-injection site on the
        pipelined path (the flat path's prefetch worker wraps the same
        site), so a fault plan naming it is never silently inert here."""
        return run_resilient("dataloader/transfer",
                             lambda: self._put(arr, sharding),
                             self._retry_policy)

    def _hop(self, arr, sharding):
        """Stage-boundary transfer (activation/cotangent resharding hop
        between sub-meshes) — the `pipe/boundary_hop` retry + fault-
        injection site, always armed (a transient device_put failure in a
        real run must get the same backoff the tests exercise). The hop's
        input is a live (non-donated) array, so a retried device_put
        re-runs identical work."""
        return run_resilient("pipe/boundary_hop",
                             lambda: self._put(arr, sharding),
                             self._retry_policy)

    def _label_sharding(self, label_shape):
        mesh = self.stage_meshes[-1]
        ax = "data" if "data" in mesh.shape else list(mesh.shape)[0]
        if label_shape and label_shape[0] % mesh.shape[ax] == 0:
            return NamedSharding(mesh, PartitionSpec(ax))
        return NamedSharding(mesh, PartitionSpec())

    def _pipeline_step(self, micro_xs, micro_y, lab_sh, rng_iter, ticks,
                      num_micro):
        """One optimizer update: drive the tick grid, dispatching each
        stage's (phase, microbatch) op and the boundary transfers. The
        host never blocks — ticks are a dependency-consistent dispatch
        order; actual overlap happens on the device groups' async queues
        (GPipe's flush and 1F1B's steady state differ only in per-stage op
        ORDER and stash lifetime, both encoded in the grid)."""
        S = self.num_stages
        if self._sentinels_on and len(self._gn_acc) != S:
            self._gn_acc = [None] * S
        stash_x: List[Dict[int, Any]] = [dict() for _ in range(S)]
        stash_st: List[Dict[int, Any]] = [dict() for _ in range(S)]
        ybuf: Dict = {}
        gybuf: Dict = {}
        acc: List[Any] = [None] * S
        state = list(self.stage_state)
        loss_sum = None
        msum = None
        rngs = [jax.random.fold_in(rng_iter, m) for m in range(num_micro)]
        # telemetry mode: each stage op is timed to COMPLETION
        # (block_until_ready after dispatch) and emitted as a pipe/F|B
        # event, so the measured bubble fraction comes from the real
        # executed timeline. The blocking serializes the host against each
        # op — it perturbs overlap, which is why it only happens with
        # telemetry on; the default path dispatches fully asynchronously.
        rec = tel.enabled()
        ops: List[tuple] = []
        upd = self._iteration
        fid = getattr(self, "_fit_id", 0)
        for row in ticks:
            for (s, ph, m) in row:
                if ph == "F":
                    if s == 0:
                        x = [self._xfer_in(a[m], sh)
                             for a, sh in zip(micro_xs, self._in_sh0)]
                    else:
                        # stage graphs take a LIST of inputs; interior
                        # stages have exactly one (the boundary tensor)
                        x = [self._hop(ybuf.pop((s - 1, m)),
                                       self._bound_in_sh[s - 1])]
                    stash_x[s][m] = x
                    stash_st[s][m] = state[s]
                    if s < S - 1:
                        t0 = tel.now_us() if rec else 0.0
                        y, state[s] = self._f_fns[s](self.stage_params[s],
                                                     state[s], x, rngs[m])
                        if rec:
                            jax.block_until_ready(y)
                            t1 = tel.now_us()
                            ops.append((s, t0, t1))
                            tel.record("pipe/F", t0, t1, cat="pipeline",
                                       stage=s, micro=m, update=upd,
                                       fit=fid)
                        ybuf[(s, m)] = y
                    # last stage: forward is fused into the backward slot
                    # (value_and_grad recomputes it) — F only stashes
                else:
                    t0 = tel.now_us() if rec else 0.0
                    if s == S - 1:
                        # the last stage's backward IS its forward
                        # (value_and_grad) — run it from the LIVE state so
                        # non-trainable state (BN running stats) chains
                        # through microbatches exactly like the sequential
                        # loop under BOTH schedules (the stashed pre-step
                        # state would replay microbatch updates from the
                        # same base under gpipe, losing M-1 of them)
                        lab = self._put(micro_y[m], lab_sh)
                        loss, gp, gx, state[s], mv = self._b_fns[s](
                            self.stage_params[s], state[s],
                            stash_x[s][m], lab, rngs[m])
                        loss_sum = loss if loss_sum is None \
                            else loss_sum + loss
                        msum = mv if msum is None else \
                            jax.tree_util.tree_map(jnp.add, msum, mv)
                    else:
                        gy = gybuf.pop((s, m))
                        gp, gx, rv = self._b_fns[s](self.stage_params[s],
                                                    stash_st[s][m],
                                                    stash_x[s][m], gy,
                                                    rngs[m])
                        if self._stage_has_regs[s]:
                            # earlier stages' regularizer penalties ride
                            # into the REPORTED loss (grads carry them
                            # either way; sequential fit reports them).
                            # The scalar lives on stage s's group — hop it
                            # to the last stage's, where loss_sum lives.
                            rv = self._put(
                                rv, NamedSharding(self.stage_meshes[-1],
                                                  PartitionSpec()))
                            loss_sum = rv if loss_sum is None \
                                else loss_sum + rv
                    if rec:
                        jax.block_until_ready(gp)
                        t1 = tel.now_us()
                        ops.append((s, t0, t1))
                        tel.record("pipe/B", t0, t1, cat="pipeline",
                                   stage=s, micro=m, update=upd, fit=fid)
                    del stash_x[s][m], stash_st[s][m]
                    if s > 0:
                        # activation-gradient hop back to the upstream group
                        gybuf[(s - 1, m)] = self._hop(
                            gx, self._bound_out_sh[s - 1])
                    acc[s] = gp if acc[s] is None \
                        else self._acc_fns[s](acc[s], gp)
        inv = 1.0 / num_micro
        for s in range(S):
            t0 = tel.now_us() if rec else 0.0
            self.stage_params[s], self.stage_opt[s], gn_sq = \
                self._upd_fns[s](self.stage_params[s], self.stage_opt[s],
                                 acc[s], jnp.float32(inv))
            if self._sentinels_on:
                # per-stage device-scalar accumulator (same stage mesh —
                # cross-mesh adds are illegal); materialized at epoch end
                a = self._gn_acc[s] if s < len(self._gn_acc) else None
                self._gn_acc[s] = gn_sq if a is None else a + gn_sq
            if rec:
                jax.block_until_ready(self.stage_opt[s])
                tel.record("pipe/update", t0, cat="pipeline-update",
                           stage=s, update=upd)
        if rec and ops:
            # executed-timeline bubble of THIS update — the same
            # accounting trace_report recomputes from the pipe/F|B events
            # (telemetry.bubble_from_ops is the one shared definition)
            b = tel.bubble_from_ops(S, ops)
            if b is not None:
                self._bubble_sum += b
                self._bubble_n += 1
        self.stage_state = state
        mvals = jax.tree_util.tree_map(lambda v: v * inv, msum) \
            if msum is not None else {}
        return loss_sum * inv, mvals

    # ------------------------------------------------------------ training
    def fit(self, x, y, batch_size: Optional[int] = None,
            epochs: Optional[int] = None, callbacks=None,
            verbose: bool = True, accum_steps: Optional[int] = None,
            steps_per_dispatch: Optional[int] = None,
            resume: Optional[str] = None,
            checkpoint_dir: Optional[str] = None,
            checkpoint_every_steps: Optional[int] = None,
            checkpoint_every_secs: Optional[float] = None, **_ignored):
        """Same contract as CompiledModel.fit; `accum_steps` is the
        microbatch count M the schedule pipelines over (config default).
        steps_per_dispatch is accepted for interface parity — the pipeline
        loop is already fully asynchronous (the host never reads a device
        value mid-epoch), so there is nothing left to fuse; K is recorded
        in step_stats for observability. The resilience knobs (durable
        periodic checkpoints, SIGTERM/SIGINT drain, resume="auto" — see
        runtime/resilience.py) work exactly as on the flat path; elastic
        resume composes with the per-layer pipeline checkpoint schema, so
        a relaunch may use a different stage count or stage mesh."""
        from flexflow_tpu.metrics import PerfMetrics
        from flexflow_tpu.runtime.resilience import FitResilience

        xs = x if isinstance(x, (list, tuple)) else [x]
        if self.stage_params[0] is None:
            self.init()
        gb = self.model.input_tensors[0].shape[0]
        if batch_size is not None and batch_size != gb:
            import warnings

            warnings.warn(f"batch_size={batch_size} coerced to graph "
                          f"batch {gb}")
        batch_size = gb
        epochs = epochs or self.cfg.epochs
        M = int(accum_steps or self.cfg.accum_steps)
        if M < 1:
            M = 1
        ticks = cm.pipeline_schedule(self.schedule, self.num_stages, M)
        res = FitResilience.build(self, resume, checkpoint_dir,
                                  checkpoint_every_steps,
                                  checkpoint_every_secs)
        if res is not None:
            # effective (per-call) knobs define the manifest's progress
            # units — for save AND the resume-compatibility check
            res.set_effective(batch_size, M)
            # ONE policy per fit: the hop/transfer sites (_hop/_xfer_in)
            # share res's instead of the model-lifetime default, so a
            # future per-fit retry override reaches every site
            self._retry_policy = res.policy
        # goodput accounting for this fit (health.GoodputMeter): resume /
        # restore time is charged out-of-band, everything inside the epoch
        # loop through the contiguous lap cursor
        gm = self._goodput = health.GoodputMeter()
        t_res = time.perf_counter()
        progress = res.resume_now(verbose) if res is not None else None
        gm.add("resume", time.perf_counter() - t_res)
        loader = SingleDataLoader(xs, y, batch_size, shuffle=True,
                                  seed=self.cfg.seed)
        lab_sh = self._label_sharding(
            (batch_size,) + tuple(np.asarray(y).shape[1:]))
        base_rng = jax.random.PRNGKey(self.cfg.seed + 17)
        stats = self.step_stats = {
            "updates": 0, "microbatches": 0,
            "stages": self.num_stages, "schedule": self.schedule,
            "steps_per_dispatch": int(steps_per_dispatch
                                      or self.cfg.steps_per_dispatch)}
        ahead = max(1, int(self.cfg.dispatch_ahead))
        self._drift_windows = []
        self._bubble_sum, self._bubble_n = 0.0, 0
        self._fit_id = next(_FIT_SEQ)
        # numerics sentinels (health.py): per-stage grad-norm-sq device
        # accumulators are checked at the loop's EXISTING epoch-end
        # materialization — zero extra host syncs on the healthy path
        sstate = self._sentinel_state = health.SentinelState() \
            if self._sentinels_on else None
        halt_on = bool(getattr(self.cfg, "halt_on_nonfinite", False))
        self._gn_acc = [None] * self.num_stages
        start_epoch, skip_steps, history = start_state(progress)
        if progress:
            loader.advance_epochs(start_epoch)
        faults_on = _faults.active()
        if res is not None:
            res.install_guard()
        try:
            for epoch in range(start_epoch, epochs):
              # per-update losses fold into ONE device scalar (bounded
              # memory on long epochs — each add consumes its predecessor),
              # materialized at epoch end only (the async-loop contract)
              loss_sum = None
              pm = PerfMetrics()
              t0 = time.perf_counter()
              gm.tick()
              nb = 0
              seed_steps = 0  # see the flat loop: resumed steps are not
              resuming = epoch == start_epoch and progress  # this session's work
              # resume mid-epoch: the loader fast-forwards past the
              # consumed accumulation groups' microbatches without
              # gathering them; accumulators re-seed (see the flat loop)
              grouped = group_microbatches(
                  loader.epoch(skip_batches=skip_steps * M
                               if resuming else 0), M)
              if resuming:
                  nb = seed_steps = skip_steps
                  if progress.get("loss_sum") is not None and nb:
                      # a host float: `float + device scalar` promotes onto
                      # the last stage's devices (a seeded jnp array would
                      # live on the default device — a cross-mesh add)
                      loss_sum = float(progress["loss_sum"])
                  pm.sums = {mk: float(mv) for mk, mv in
                             (progress.get("metric_sums") or {}).items()}
                  pm.train_all = int(progress.get("samples", 0))

              def make_progress(_pm=pm, _epoch=epoch):
                  # durable progress counters for res.maybe_checkpoint
                  # (reads nb/loss_sum/history at call time)
                  _pm.materialize()
                  return progress_dict(_epoch, nb,
                                       float(np.asarray(loss_sum))
                                       if loss_sum is not None else 0.0,
                                       _pm.sums, _pm.train_all, history)

              for gxs, gy in grouped:
                  # the generator's host-side gather/slicing is the input
                  # pipeline on this path — charge it as a data stall
                  gm.lap("prefetch_wait")
                  if M == 1:
                      gxs = [a[None] for a in gxs]
                      gy = gy[None]
                  if faults_on:
                      # fit/dispatch admission BEFORE the update (nothing
                      # consumed yet, retry-safe); one pipelined update =
                      # one global step, so index = 1-based step, same
                      # contract as the flat loop
                      run_resilient("fit/dispatch", lambda: None,
                                    self._retry_policy,
                                    index=self._iteration + 1)
                      if _faults.poison("health/nonfinite",
                                        index=self._iteration + 1):
                          # silent numerics blow-up: NaN-poison one stage-0
                          # weight; no exception — the sentinel must catch
                          leaves, tdef = jax.tree_util.tree_flatten(
                              self.stage_params[0])
                          if leaves:
                              leaves[0] = leaves[0] * jnp.float32(np.nan)
                              self.stage_params[0] = \
                                  jax.tree_util.tree_unflatten(tdef, leaves)
                  rng_iter = jax.random.fold_in(base_rng, self._iteration)
                  loss, mvals = self._pipeline_step(gxs, gy, lab_sh,
                                                    rng_iter, ticks, M)
                  gm.lap("dispatch")
                  loss_sum = loss if loss_sum is None else loss_sum + loss
                  pm.update_deferred(batch_size * M, mvals)
                  self._iteration += 1
                  nb += 1
                  stats["updates"] += 1
                  stats["microbatches"] += M
                  gm.lap("loop")
                  if nb % ahead == 0:
                      # bounded dispatch-ahead (the PR-2 fit-loop contract):
                      # don't let the host enqueue unboundedly many stage
                      # dispatches past the devices
                      jax.block_until_ready(loss)
                      stats["barriers"] = stats.get("barriers", 0) + 1
                      gm.lap("barrier")
                  if res is not None:
                      res.maybe_checkpoint(loss, make_progress)
                      gm.lap("checkpoint")
              dt = time.perf_counter() - t0
              self._drift_windows.append((nb - seed_steps, dt))
              if self._bubble_n:
                  # mean of per-update executed-timeline bubbles so far
                  # (telemetry mode only — the async path has no honest
                  # per-op completion times to derive one from)
                  stats["measured_bubble"] = self._bubble_sum / self._bubble_n
              if tel.enabled():
                  tel.record("fit/epoch", tel.now_us() - dt * 1e6, cat="fit",
                             epoch=epoch, steps=nb)
              summ = pm.summary()
              loss_mean = float(np.asarray(loss_sum)) / nb if nb else 0.0
              summ["loss"] = loss_mean
              if sstate is not None and nb > seed_steps:
                  # sentinel check at the EXISTING epoch-end sync: drain
                  # the per-stage grad-norm-sq accumulators (squares sum
                  # across stages — disjoint param partitions), RMS over
                  # the window's updates, host-side finite check
                  win = nb - seed_steps
                  gn_sq_tot = 0.0
                  for s in range(self.num_stages):
                      if self._gn_acc[s] is not None:
                          gn_sq_tot += float(np.asarray(self._gn_acc[s]))
                  self._gn_acc = [None] * self.num_stages
                  grad_norm = float(np.sqrt(gn_sq_tot / win)) \
                      if gn_sq_tot == gn_sq_tot else float("nan")
                  nonfinite = 0.0 if (np.isfinite(loss_mean)
                                      and np.isfinite(grad_norm)) else 1.0
                  verdict = sstate.observe(self._iteration,
                                           loss_mean=loss_mean,
                                           grad_norm=grad_norm,
                                           nonfinite=nonfinite)
                  if verdict == "nonfinite" and halt_on:
                      # PR-6 drain: join pending writes, raise carrying
                      # the last DURABLE checkpoint (the recovery point)
                      health.halt_nonfinite(
                          self._iteration,
                          res.root if res is not None else None,
                          detail="pipeline epoch-end window")
              summ["epoch_time_s"] = dt
              summ["samples_per_sec"] = ((nb - seed_steps) * M * batch_size) \
                  / dt if dt > 0 else 0.0
              summ["dispatches"] = float(nb)
              grec = gm.epoch_end(
                  dt, epoch,
                  bubble_frac=(self._bubble_sum / self._bubble_n)
                  if self._bubble_n else None)
              summ["goodput"] = grec["goodput"]
              self._watermarks.sample(
                  f"epoch{epoch}",
                  tuple(self.stage_params) + tuple(self.stage_opt))
              history.append(summ)
              if verbose:
                  ms = " ".join(f"{k}={v:.4f}" for k, v in summ.items()
                                if k != "samples")
                  print(f"[epoch {epoch}] {ms}")
              for cb in callbacks or []:
                  if hasattr(cb, "on_epoch_end"):
                      cb.on_epoch_end(epoch, summ)
              if res is not None:
                  res.epoch_end(epoch, history)
            if res is not None:
                res.final_save(epochs, history)
        finally:
            if res is not None:
                res.guard.uninstall()
        self._fit_end_report(verbose)
        if self.cfg.profile_ops and (verbose or tel.enabled()):
            # --profile-ops, pipeline edition: per-stage per-op attribution
            # of the measured update time (flexflow_tpu/attribution.py);
            # skipped when neither the printed table nor the telemetry
            # corpus would consume the measurement work
            self.op_attribution(print_table=verbose)
        return history

    def _fit_end_report(self, verbose: bool) -> None:
        """Fit-end hooks, pipeline edition: drift event (predicted vs
        measured UPDATE time, plus the measured bubble when telemetry
        timed the ops), drift warning, failed-async-checkpoint warning."""
        from flexflow_tpu.runtime.checkpoint import warn_failed_writes

        tel.emit_fit_end(
            self.drift_stats(), verbose,
            measured_bubble=self.step_stats.get("measured_bubble"))
        warn_failed_writes(verbose)

    def evaluate(self, x, y, batch_size: Optional[int] = None):
        from flexflow_tpu.metrics import PerfMetrics

        xs = x if isinstance(x, (list, tuple)) else [x]
        gb = self.model.input_tensors[0].shape[0]
        if batch_size is not None and batch_size != gb:
            import warnings

            warnings.warn(f"batch_size={batch_size} coerced to graph "
                          f"batch {gb} (XLA static shapes)")
        loader = SingleDataLoader(xs, y, gb, shuffle=False)
        lab_sh = self._label_sharding((gb,) + tuple(np.asarray(y).shape[1:]))
        pm = PerfMetrics()
        loss_sum = None
        ahead = max(1, int(self.cfg.dispatch_ahead))
        nb = 0
        for bxs, by in loader.epoch():
            h = [self._put(a, sh) for a, sh in zip(bxs, self._in_sh0)]
            for s in range(self.num_stages - 1):
                y = self._ef_fns[s](self.stage_params[s],
                                    self.stage_state[s], h)
                h = [self._put(y, self._bound_in_sh[s])]
            loss, mvals = self._e_last(self.stage_params[-1],
                                       self.stage_state[-1], h,
                                       self._put(by, lab_sh))
            loss_sum = loss if loss_sum is None else loss_sum + loss
            pm.update_deferred(gb, mvals)
            nb += 1
            if nb % ahead == 0:  # bounded dispatch-ahead, as in fit
                jax.block_until_ready(loss)
        out = pm.summary()
        out["loss"] = float(np.asarray(loss_sum)) / nb if nb else 0.0
        return out

    def forward(self, *inputs):
        if self.stage_params[0] is None:
            self.init()
        h = [self._put(np.asarray(a), sh)
             for a, sh in zip(inputs, self._in_sh0)]
        for s in range(self.num_stages - 1):
            y = self._ef_fns[s](self.stage_params[s], self.stage_state[s],
                                h)
            h = [self._put(y, self._bound_in_sh[s])]
        outs = self._ef_fns[-1](self.stage_params[-1],
                                self.stage_state[-1], h)
        return outs[0] if len(outs) == 1 else outs

    # --------------------------------------------------------------- state
    def merged_params(self) -> Dict[str, Any]:
        """One logical params tree keyed by layer name (stage trees are
        disjoint by construction) — the checkpoint schema, and the
        cross-mesh restore target."""
        merged: Dict[str, Any] = {}
        for p in self.stage_params:
            merged.update(p)
        return merged

    def get_weight(self, layer_name: str, wname: str = "kernel"):
        for p in self.stage_params:
            if layer_name in p:
                return np.asarray(p[layer_name][wname])
        raise KeyError(layer_name)

    def set_weight(self, layer_name: str, wname: str, value):
        value = np.asarray(value)
        for s, p in enumerate(self.stage_params):
            if layer_name in p:
                target = p[layer_name][wname]
                assert value.shape == tuple(target.shape)
                p[layer_name][wname] = self._put(value, target.sharding)
                return
        raise KeyError(layer_name)

    def memory_stats(self) -> dict:
        """Per-device persistent-memory report, pipeline edition: one
        representative device PER STAGE (live addressable-shard bytes of
        that stage's params/opt state), next to the non-pipelined
        prediction — tests/test_pipeline.py asserts the reduction
        against the S=1 twin's live buffers."""
        def dev_bytes(tree, dev):
            total = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                shards = getattr(leaf, "addressable_shards", None)
                if shards is None:
                    continue
                total += sum(sh.data.nbytes for sh in shards
                             if sh.device == dev)
            return total

        per_stage_p, per_stage_o = [], []
        for s in range(self.num_stages):
            dev = self.stage_meshes[s].devices.flat[0]
            per_stage_p.append(dev_bytes(self.stage_params[s], dev))
            per_stage_o.append(dev_bytes(self.stage_opt[s], dev))
        return {
            "pipeline_stages": self.num_stages,
            "schedule": self.schedule,
            "cuts": list(self.cuts),
            "zero_sharding": self._zero_mode(),
            "per_stage_param_bytes": per_stage_p,
            "per_stage_opt_bytes": per_stage_o,
            "actual_param_bytes_per_device": max(per_stage_p),
            "actual_opt_state_bytes_per_device": max(per_stage_o),
            "inflight_activations": cm.pipeline_inflight_acts(
                self.schedule, self.num_stages,
                max(1, int(self.cfg.accum_steps))),
        }

    def predicted_schedule(self, num_micro: Optional[int] = None) -> dict:
        """The cost model's view of this compile's schedule (per-stage
        analytic times -> event-replay makespan + bubble): what the bench
        compares its measured numbers against."""
        from flexflow_tpu.search.candidates import layer_candidates

        M = int(num_micro or self.cfg.accum_steps) or 1
        bs = self._batch_sizes()
        stage_costs = []
        for seg in self.stage_layers:
            t = 0.0
            for layer in seg:
                cands = layer_candidates(layer, self.stage_machine, bs)
                if not cands[0].passthrough:
                    t += cands[0].op_time(layer, self.stage_machine)
            stage_costs.append(t)
        fwd, bwd = cm.pipeline_phase_times(stage_costs)
        from flexflow_tpu.search.simulator import simulate_pipeline

        rep = simulate_pipeline(fwd, bwd, self.schedule, M)
        return {
            "stage_costs_s": stage_costs,
            "makespan_s": rep["makespan"],
            "bubble": rep["bubble"],
            "bubble_closed_form": cm.pipeline_bubble_fraction(
                self.schedule, self.num_stages, M),
        }

    # ------------------------------------------------------------ profiling
    def predicted_step_time(self) -> Optional[float]:
        """The cost model's per-UPDATE prediction: the event-replay
        makespan of this compile's schedule over M microbatches (the same
        number the cut search ranked by) — comparable to drift_stats'
        measured per-update windows."""
        try:
            t = float(self.predicted_schedule()["makespan_s"])
            return t if t > 0 else None
        except Exception:
            return None

    def drift_stats(self) -> dict:
        return tel.drift_stats(self.predicted_step_time(),
                               list(self._drift_windows))

    def goodput_report(self) -> dict:
        """The last fit's wall-clock bucket accounting (see
        health.GoodputMeter.report), pipeline edition — the bubble
        carve-out uses the telemetry-measured bubble fraction when one
        was recorded. Empty dict before any fit."""
        return self._goodput.report() if self._goodput is not None else {}

    def health_report(self) -> dict:
        """Run-health summary, pipeline edition: sentinel status plus the
        HBM watermark vs the heaviest stage's persistent footprint (the
        pipeline memory report has no single-machine prediction — the
        per-device expectation IS the max stage params+opt bytes)."""
        sent = self._sentinel_state.status() \
            if self._sentinel_state is not None else None
        wm = None
        if self._watermarks.samples:
            mem = self.memory_stats()
            pred = (mem["actual_param_bytes_per_device"]
                    + mem["actual_opt_state_bytes_per_device"])
            wm = self._watermarks.report(pred)
        return {"sentinels": sent, "watermarks": wm}

    def op_attribution(self, step_time_s: Optional[float] = None,
                       source: str = "auto", top: int = 0,
                       print_table: bool = True) -> dict:
        """Per-op attribution, pipeline edition (see CompiledModel.
        op_attribution / flexflow_tpu/attribution.py): every stage's ops on
        the STAGE machine, each row tagged with its stage, measured/
        predicted/roofline all per UPDATE (x M microbatches). The update's
        measured wall time (drift monitor) is the makespan of CONCURRENT
        stages, so attributed times — rescaled to sum to it — express each
        op's share of the wall clock, not of the summed stage-local work
        (`coverage` reports that ratio)."""
        from flexflow_tpu import attribution
        from flexflow_tpu.search.candidates import compiled_candidate

        if step_time_s is None:
            step_time_s = self.drift_stats().get("measured_step_time_s")
        pred = getattr(self.strategy, "_predicted_op_costs", None) or {}
        bs = self._batch_sizes()
        items = []
        for s, seg in enumerate(self.stage_layers):
            for layer in seg:
                # the COMPILED intra-stage placement, not the dp default —
                # corpus rows must describe what actually ran
                cand = compiled_candidate(layer, self.strategy,
                                          self.stage_machine, bs)
                if cand.passthrough:
                    continue
                items.append({"layer": layer, "cand": cand,
                              "machine": self.stage_machine,
                              "predicted_s": pred.get(layer.name),
                              "stage": s})
        profile_dir = (self.cfg.profile_dir or "./ff_profile") \
            if self.cfg.profiling else None
        report = attribution.build_report(
            items, step_time_s=step_time_s,
            mult=max(1, int(self.cfg.accum_steps)),
            profile_dir=profile_dir, source=source)
        if print_table:
            for line in attribution.format_report(report, top=top):
                print(line)
        return report

    def profile_report(self, top: int = 0, print_table: bool = True):
        """Per-op timing table, pipeline edition: each stage's layers under
        the dp candidate on the STAGE machine (analytic + isolated
        measured), plus [pipeline] (schedule + predicted vs measured
        bubble), [drift], [memory] per stage, and any failed async
        checkpoint writes. Returns the rows (each tagged with its stage)."""
        from flexflow_tpu.search.candidates import layer_candidates
        from flexflow_tpu.search.measure import MeasuredCost

        mc = MeasuredCost(self.stage_machine, repeats=3, warmup=1,
                          cache_dir="")
        bs = self._batch_sizes()
        rows = []
        for s, seg in enumerate(self.stage_layers):
            for layer in seg:
                cand = layer_candidates(layer, self.stage_machine, bs)[0]
                if cand.passthrough:
                    continue
                rows.append({
                    "stage": s,
                    "layer": layer.name,
                    "op": layer.op_type.value,
                    "candidate": cand.name,
                    "analytic_us": cand.op_time(layer,
                                                self.stage_machine) * 1e6,
                    "measured_us": mc.op_time(layer, cand) * 1e6,
                })
        rows.sort(key=lambda x: (x["stage"], -x["measured_us"]))
        if top:
            rows = rows[:top]
        if print_table:
            print(f"{'st':>2} {'layer':26} {'op':16} {'analytic':>10} "
                  f"{'measured':>10}")
            for x in rows:
                print(f"{x['stage']:2d} {x['layer'][:26]:26} "
                      f"{x['op'][:16]:16} {x['analytic_us']:9.1f}u "
                      f"{x['measured_us']:9.1f}u")
            pred = self.predicted_schedule()
            mb = self.step_stats.get("measured_bubble")
            print(f"[pipeline] stages={self.num_stages} "
                  f"schedule={self.schedule} cuts={list(self.cuts)} "
                  f"predicted_bubble={pred['bubble']:.3f} "
                  + (f"measured_bubble={mb:.3f}" if mb is not None
                     else "measured_bubble=n/a (enable --telemetry-dir)"))
            for line in tel.format_drift(self.drift_stats()):
                print(line)
            if self._goodput is not None and self._goodput.epochs:
                for line in health.format_goodput(self._goodput.report()):
                    print(line)
            hrep = self.health_report()
            for line in health.format_health(hrep["sentinels"],
                                             hrep["watermarks"]):
                print(line)
            if self.cfg.profile_ops:
                self.op_attribution(print_table=True, top=top)
            else:
                print("[drift] per-op attribution: --profile-ops / "
                      "op_attribution() / tools/profile_attribution.py")
            mem = self.memory_stats()
            mbyte = 1024 * 1024
            for s in range(self.num_stages):
                print(f"[memory] stage {s}: params "
                      f"{mem['per_stage_param_bytes'][s] / mbyte:.2f}MB, "
                      f"opt state "
                      f"{mem['per_stage_opt_bytes'][s] / mbyte:.2f}MB "
                      "per device")
            from flexflow_tpu.runtime.checkpoint import \
                report_failed_writes

            for line in report_failed_writes():
                print(line)
        return rows

    # ----------------------------------------------------------- checkpoint
    def save_checkpoint(self, path: str, block: Optional[bool] = None) -> str:
        from flexflow_tpu.runtime.checkpoint import save_pipeline_checkpoint

        if block is None:
            block = not self.cfg.async_checkpoint
        return save_pipeline_checkpoint(self, path, block=block)

    def load_checkpoint(self, path: str) -> None:
        from flexflow_tpu.runtime.checkpoint import \
            restore_pipeline_checkpoint

        restore_pipeline_checkpoint(self, path)

    def wait_checkpoints(self) -> None:
        from flexflow_tpu.runtime.checkpoint import wait_pending

        wait_pending()
