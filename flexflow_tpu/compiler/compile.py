"""compile_model — the pivot of the framework.

Reference analog: FFModel::compile (src/runtime/model.cc:2803): lower layers
→ operators, run the strategy search, materialize tensors onto the machine,
create the label tensor, init optimizer + NCCL. The TPU-native pipeline:

  1. build/machine-detect the logical Mesh            (mapper analog)
  2. pick a Strategy: imported file > search > data-parallel
     (graph_optimize_task analog)
  3. trace the layer graph into one SPMD train step jitted over the mesh
     (IndexLauncher-per-op → one XLA computation; collectives via GSPMD)
  4. init weights directly into their target shardings
     (region materialization analog)
"""

from __future__ import annotations

import logging
import time
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax._src import xla_bridge
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from flexflow_tpu import attribution, health
from flexflow_tpu import telemetry as tel
from flexflow_tpu.config import ensure_compile_cache
from flexflow_tpu.core.graph import topo_order
from flexflow_tpu.core.tensor import Tensor
from flexflow_tpu.compiler.lowering import build_forward, constrainable
from flexflow_tpu.ops.registry import STATS_KEY
from flexflow_tpu.dtype import DataType
from flexflow_tpu.initializers import ZeroInitializer, default_initializer
from flexflow_tpu.losses import LossType, compute_loss
from flexflow_tpu.metrics import MetricsType, PerfMetrics, compute_metrics
from flexflow_tpu.optimizers import Optimizer, SGDOptimizer
from flexflow_tpu.parallel.default_strategy import data_parallel_strategy
from flexflow_tpu.parallel.machine import MachineSpec, build_mesh
from flexflow_tpu.parallel.sharding import Strategy
from flexflow_tpu.runtime.dataloader import (SingleDataLoader,
                                             group_microbatches,
                                             prefetch_multi,
                                             prefetch_to_device)


def _search_machine(cfg, machine: MachineSpec) -> MachineSpec:
    """--search-num-nodes/--search-num-workers (reference config.h:154-155):
    search strategies for a machine LARGER than the real one (typically with
    --export, so big-machine strategies can be found on a small host). Nodes
    map to a DCN-crossing data axis, workers to the intra-node model axis."""
    if not cfg.search_num_nodes and not cfg.search_num_workers:
        return machine
    nodes = max(1, cfg.search_num_nodes)
    workers = max(1, cfg.search_num_workers)
    axes = {"data": nodes, "model": workers}
    return MachineSpec(mesh_axes=axes, chip=machine.chip,
                       dcn_axes=("data",) if nodes > 1 else ())


def _pick_strategy(model, machine: MachineSpec, optimizer=None) -> Strategy:
    cfg = model.config
    if cfg.import_strategy_file:
        return Strategy.load(cfg.import_strategy_file)
    sm = _search_machine(cfg, machine)
    if sm is not machine and sm.mesh_axes != machine.mesh_axes \
            and not cfg.export_strategy_file:
        import warnings

        warnings.warn(
            f"searching for machine {sm.mesh_axes} but executing on "
            f"{machine.mesh_axes}: shardings that don't fit the real mesh "
            "degrade to replicated — --search-num-nodes/--search-num-workers "
            "are meant to be paired with --export")
    if cfg.search_budget > 0 and not cfg.only_data_parallel and sm.num_devices > 1:
        from flexflow_tpu.search.optimize import graph_optimize

        # the optimizer rides along so the search's memory model can
        # price its moments (count/state_dtype/ZeRO divisor) honestly
        with tel.span("compile/graph_optimize", cat="compile",
                      mesh=str(dict(sm.mesh_axes))):
            return graph_optimize(model, sm, optimizer=optimizer)
    return data_parallel_strategy(model, machine)


def _overlay_parallel_ops(model, strategy: Strategy):
    """Explicit parallel-op layers override the strategy's layout for their
    outputs (reference: parallel ops ARE PCG nodes; here they are resharding
    requests, see flexflow_tpu/ops/parallel_ops.py)."""
    from flexflow_tpu.ops.op_type import PARALLEL_OPS
    from flexflow_tpu.ops.parallel_ops import requested_dims
    from flexflow_tpu.parallel.sharding import OpSharding

    for layer in model.layers:
        if layer.op_type not in PARALLEL_OPS:
            continue
        src = layer.inputs[0]
        incoming = None
        if src.owner is not None:
            sh = strategy.op_shardings.get(src.owner.name)
            if sh and src.owner_idx < len(sh.outputs):
                incoming = sh.outputs[src.owner_idx]
        elif src.name in strategy.input_shardings:
            incoming = strategy.input_shardings[src.name]
        dims = requested_dims(layer, incoming)
        strategy.op_shardings[layer.name] = OpSharding(outputs=[dims])


def compile_model(model, optimizer, loss_type: LossType, metrics: Sequence[MetricsType],
                  outputs: Optional[Sequence[Tensor]] = None) -> "CompiledModel":
    cfg = model.config
    ensure_compile_cache()
    # --telemetry-dir enables the process-global span stream; "" leaves
    # the current state untouched (disabling is an explicit
    # telemetry.shutdown(), never a side effect of a later compile)
    if getattr(cfg, "telemetry_dir", ""):
        tel.configure(cfg.telemetry_dir,
                      max_mb=getattr(cfg, "telemetry_max_mb", None))
    # --fault-plan arms the deterministic fault injector (FF_FAULT_PLAN is
    # read at faults import; an explicit config plan overrides it)
    if getattr(cfg, "fault_plan", ""):
        from flexflow_tpu.runtime import faults

        faults.configure(cfg.fault_plan)
    with tel.span("compile/compile_model", cat="compile",
                  pipeline_stages=int(cfg.pipeline_stages)):
        return _compile_model(model, optimizer, loss_type, metrics, outputs)


def compile_serving(model, **kwargs):
    """Serving twin of `compile_model` (flexflow_tpu/serving/engine.py):
    lowers the graph twice — compute-priced prefill and bandwidth-priced
    single-token decode — searches a strategy per program, and returns a
    ServingCompiled over a paged KV cache. Lazy import: serving builds on
    this module (build_init_fn / resolve_machine / _overlay_parallel_ops)."""
    from flexflow_tpu.serving.engine import compile_serving as _compile_serving

    return _compile_serving(model, **kwargs)


def resolve_machine(cfg) -> MachineSpec:
    """The machine description every compile entry point shares (training
    `compile_model` and the serving `compile_serving`): an explicit machine
    file wins, then the --nodes DCN description, then mesh-shape detection.
    A compile's first question to the backend is asked here, under
    `start/backend`: it starts the backend (on a chip the TPU runtime,
    seconds) unless the caller or an earlier compile has (`already_up`;
    then microseconds)."""
    with tel.span("start/backend", cat="start",
                  already_up=bool(xla_bridge.backends_are_initialized())):
        jax.devices()
    if cfg.machine_model_file:
        return MachineSpec.from_file(cfg.machine_model_file)
    if not cfg.mesh_shape and cfg.num_nodes > 1:
        # --nodes/-ll:tpu (reference machine description): nodes form a
        # DCN-crossing axis, per-node workers the intra-node data axis
        workers = cfg.workers_per_node or max(
            1, len(jax.devices()) // cfg.num_nodes)
        return MachineSpec.detect({"node": cfg.num_nodes, "data": workers},
                                  dcn_axes=("node",))
    return MachineSpec.detect(cfg.mesh_shape)


def _compile_model(model, optimizer, loss_type, metrics, outputs):
    cfg = model.config
    machine = resolve_machine(cfg)
    level = getattr(logging, cfg.log_level.upper(), None)
    if level is None:
        raise ValueError(f"unknown log_level {cfg.log_level!r}")
    lg = logging.getLogger("flexflow_tpu")
    if lg.level == logging.NOTSET:  # never clobber application logging config
        lg.setLevel(level)
    optimizer = optimizer or SGDOptimizer(lr=cfg.learning_rate)
    if cfg.pipeline_stages > 1:
        return _compile_pipelined(model, machine, optimizer, loss_type,
                                  metrics, outputs)
    mesh = build_mesh(machine)
    strategy = _pick_strategy(model, machine, optimizer)
    logging.getLogger("flexflow_tpu").info(
        "compile: mesh=%s strategy=%s", dict(machine.mesh_axes), strategy.name)
    _overlay_parallel_ops(model, strategy)
    if cfg.export_strategy_file:
        strategy.save(cfg.export_strategy_file)
    if outputs is None:
        outputs = model.layers[-1].outputs[:1] if model.layers else []
    return CompiledModel(model, machine, mesh, strategy, optimizer,
                         loss_type, list(metrics), list(outputs))


def _compile_pipelined(model, machine: MachineSpec, optimizer,
                       loss_type: LossType, metrics, outputs):
    """--pipeline-stages N: partition the graph into N sequential stages on
    disjoint device groups. The machine description covers the FULL
    cluster; the pipe dimension is carved out of it (an explicit pipe mesh
    axis, else the batch axis degree divides by N — dp.stage_machine_for),
    intra-stage layouts are searched on the STAGE machine (tensor/data
    parallelism inside a stage composes with the pipeline split), and the
    cut points come from the bubble-aware cut search when a search budget
    is set, else from the balance heuristic. The schedule runs M =
    cfg.accum_steps microbatches per optimizer update
    (parallel/pipeline.py).

    Known approximation: the cut search prices stage times under plain
    per-stage frontier-DP layouts, while execution uses the (possibly
    richer, substitution-searched) strategy from _pick_strategy — the
    cuts are optimal for a close under-approximation of the executed
    layouts, not for them exactly. Both searches are cold-compile-only:
    the warm path (cached strategy with its pipeline block) skips both."""
    from flexflow_tpu.parallel.pipeline import PipelinedModel, balanced_cuts
    from flexflow_tpu.search.dp import search_pipelined, stage_machine_for

    cfg = model.config
    S = int(cfg.pipeline_stages)
    stage_machine = stage_machine_for(machine, S)
    strategy = _pick_strategy(model, stage_machine, optimizer)
    if strategy.pipeline and int(strategy.pipeline.get("stages", S)) != S:
        raise ValueError(f"imported strategy pipelines "
                         f"{strategy.pipeline.get('stages')} stages but "
                         f"--pipeline-stages is {S}")
    if not strategy.pipeline:
        # First compile at these knobs: graph_optimize stored the strategy
        # (intra-stage layouts) BEFORE the pipeline block exists, so the
        # cuts are searched here and the entry is re-stored WITH the block
        # below — the warm path then finds strategy.pipeline set and skips
        # the cut search entirely (zero DP expansions, the cache's
        # headline contract; the knob fingerprint already keys on
        # stages/schedule/M).
        micro = max(1, int(cfg.accum_steps))
        cuts = None
        if cfg.search_budget > 0 and not cfg.only_data_parallel:
            from flexflow_tpu.search import cost_model as cmod

            with tel.span("compile/pipeline_cut_search", cat="compile",
                          stages=S, micro=micro):
                r = search_pipelined(
                    model, machine, S, micro,
                    schedule=cfg.pipeline_schedule,
                    mem_budget=machine.hbm_bytes if cfg.memory_search
                    else None,
                    opt_mem=cmod.opt_mem_spec(optimizer, cfg,
                                              stage_machine))
            if r is not None:
                cuts = list(r.cuts)
                logging.getLogger("flexflow_tpu").info(
                    "pipeline cut search: cuts=%s predicted bubble=%.3f "
                    "stage costs=%s", cuts, r.bubble,
                    ["%.3g" % c for c in r.stage_costs])
        if cuts is None:
            cuts = balanced_cuts(model, stage_machine, S)
        strategy.pipeline = {"stages": S, "cuts": cuts,
                             "schedule": cfg.pipeline_schedule}
        info = getattr(strategy, "_cache_info", None)
        if info and info.get("dir") and info.get("key"):
            # write the completed artifact (layouts + cuts) back into the
            # cache entry graph_optimize created / hit
            from flexflow_tpu.search import strategy_cache as sc

            sc.store(info["dir"], info["key"], strategy,
                     meta=dict(info.get("meta", {})))
    _overlay_parallel_ops(model, strategy)
    if cfg.export_strategy_file:
        strategy.save(cfg.export_strategy_file)
    if outputs is None:
        outputs = model.layers[-1].outputs[:1] if model.layers else []
    logging.getLogger("flexflow_tpu").info(
        "compile: pipeline stages=%d schedule=%s stage_mesh=%s cuts=%s",
        S, strategy.pipeline.get("schedule"),
        dict(stage_machine.mesh_axes), strategy.pipeline.get("cuts"))
    return PipelinedModel(model, machine, stage_machine, strategy,
                          optimizer, loss_type, list(metrics),
                          list(outputs))


def _zero_axes_of(mesh: Mesh) -> List[str]:
    """Mesh axes ZeRO shards optimizer moments over: the batch axes
    (candidates._batch_axes convention — "node"/"data", else the first
    axis) with degree > 1. Sharding over the batch axes is what removes
    REDUNDANT state: every other axis already partitions the params."""
    axes = [a for a in ("node", "data") if a in mesh.shape]
    if not axes and mesh.shape:
        axes = [next(iter(mesh.shape))]
    return [a for a in axes if mesh.shape[a] > 1]


def _zero_moment_pspec(pspec: PartitionSpec, shape, mesh: Mesh,
                       zero_axes: Sequence[str]) -> PartitionSpec:
    """Moment layout for one param under ZeRO: the param's own spec plus
    the FULL data-axis degree on the first unsharded dim it divides. A
    param with no such dim keeps its (possibly model-sharded) layout —
    its moments stay replicated over data, exactly what the search's
    cost_model.zero_divisor mirror predicts. Keep the two rules in
    lockstep or --memory-search prices memory the runtime doesn't save."""
    spec = list(pspec) + [None] * (len(shape) - len(pspec))
    used = {a for d in spec if d is not None
            for a in ((d,) if isinstance(d, str) else tuple(d))}
    if used & set(zero_axes):
        return PartitionSpec(*spec)
    deg = 1
    for a in zero_axes:
        deg *= mesh.shape[a]
    if deg <= 1:
        return PartitionSpec(*spec)
    for i, d in enumerate(spec):
        if d is None and shape[i] % deg == 0:
            spec[i] = zero_axes[0] if len(zero_axes) == 1 \
                else tuple(zero_axes)
            break
    return PartitionSpec(*spec)


# a layer's state is keyed past any weight of it
STATE_KEY_OFFSET = 1 << 16
# the prefix under which a step's counters (`ctx.add_stat`) ride its metrics
STEP_STATS_PREFIX = "stats/"


def build_state_init_fn(layers, overrides):
    """The same for what layers declare as non-trainable state
    (`Layer.state_specs`: a selection bias that a step moves without a
    gradient): `{"<layer>/<name>": array}`, flat like batch norm's running
    statistics (runtime/checkpoint.py saves such a dict as it is), zeros
    where the layer names no initializer for it."""
    def init_fn(key):
        state = {}
        for li, layer in enumerate(layers):
            for i, (sname, spec) in enumerate(
                    sorted(layer.state_specs.items())):
                init = overrides.get((layer.name, sname)) or ZeroInitializer()
                state[f"{layer.name}/{sname}"] = init(
                    jax.random.fold_in(jax.random.fold_in(key, li),
                                       STATE_KEY_OFFSET + i), spec)
        return state

    return init_fn


def build_init_fn(layers, overrides, topo_idx=None):
    """Weight-init closure shared by CompiledModel.init and the pipeline
    runtime (parallel/pipeline.py): params for `layers`, each weight keyed
    by fold_in(fold_in(key, topo_idx[layer]), weight_idx). `topo_idx` maps
    a layer to its position in the FULL model's topo order (default: its
    position in `layers`) — pipeline stages pass GLOBAL indices so a
    stage-partitioned model initializes bitwise-identically to the
    sequential compile of the same graph."""
    from flexflow_tpu.core.tensor import TensorSpec

    if topo_idx is None:
        topo_idx = {id(l): i for i, l in enumerate(layers)}

    def init_fn(key):
        params = {}
        for layer in layers:
            if not layer.weight_specs:
                continue
            li = topo_idx[id(layer)]
            d = {}
            for i, (wname, spec) in enumerate(sorted(layer.weight_specs.items())):
                # fork_join weights are "b{i}.{sublayer}.{wname}" (or
                # "stk.{sublayer}.{wname}" stacked): the default
                # initializer keys off the terminal wname
                # fold by topo position (not guid) so identically-built
                # models init identically across FFModel instances
                k = jax.random.fold_in(jax.random.fold_in(key, li), i)
                if wname.startswith("stk."):
                    # stacked fork_join storage: init each branch slice
                    # independently (fan-in/out from the SLICE shape, and
                    # per-branch initializer overrides still apply)
                    sspec = TensorSpec(spec.shape[1:], spec.dtype)
                    default = default_initializer(wname.rsplit(".", 1)[-1])
                    slices = []
                    for b in range(spec.shape[0]):
                        init = overrides.get(
                            (layer.name, f"b{b}.{wname[4:]}")) or default
                        slices.append(init(jax.random.fold_in(k, b), sspec))
                    d[wname] = jnp.stack(slices)
                else:
                    init = overrides.get((layer.name, wname)) or \
                        default_initializer(wname.rsplit(".", 1)[-1])
                    d[wname] = init(k, spec)
            params[layer.name] = d
        return params

    return init_fn


def weights_facts(params) -> Dict[str, int]:
    """What an init span says of the weights it made (array metadata: no
    wait for the device)."""
    leaves = jax.tree_util.tree_leaves(params)
    return {"parameters": sum(int(l.size) for l in leaves),
            "bytes": sum(int(l.nbytes) for l in leaves),
            "leaves": len(leaves)}


@partial(jax.jit, donate_argnums=(0,))
def _stacked_slice_set(stack, value, b):
    """Update slice b of a stacked (k, ...) weight in place, preserving its
    sharding (used by set_weight's per-branch alias on owned fork-join
    weights)."""
    return jax.lax.dynamic_update_index_in_dim(stack, value, b, 0)


class CompiledModel:
    def __init__(self, model, machine: MachineSpec, mesh: Mesh, strategy: Strategy,
                 optimizer: Optimizer, loss_type: LossType,
                 metrics: List[MetricsType], outputs: List[Tensor]):
        self.model = model
        self.machine = machine
        self.mesh = mesh
        self.strategy = strategy
        self.optimizer = optimizer
        self.tx = optimizer.to_optax()
        self.loss_type = loss_type
        self.metrics = metrics
        self.outputs = outputs
        self.cfg = model.config
        self._iteration = 0
        self.recompile_state = None  # set via recompile_on_condition
        # strategy-cache event for THIS compile (hit/store), stamped by
        # search/strategy_cache.py on the returned Strategy; None when the
        # search didn't run (imported / data-parallel) or caching is off
        self.search_cache_info = getattr(strategy, "_cache_info", None)
        # async-pipeline observability, rewritten by each fit (_fit_epochs):
        # dispatches / host_syncs / barriers / fused_steps
        self.step_stats: Dict[str, int] = {}
        # drift-monitor windows from the LAST fit: [(steps, wall_seconds)]
        # per epoch — drift_stats() medians these against the strategy's
        # predicted step time
        self._drift_windows: List[tuple] = []
        # run-health layer (flexflow_tpu/health.py, ISSUE 9): goodput
        # meter is per-fit (rebuilt by _fit), the HBM watermark tracker
        # spans the compile's lifetime (init + every epoch boundary), and
        # the sentinel monitor follows cfg.health_sentinels
        self._goodput: Optional[health.GoodputMeter] = None
        self._watermarks = health.WatermarkTracker()
        self._sentinels: Optional[health.SentinelMonitor] = None

        # --remat compat alias (deprecated): uniform "full" per-layer policy.
        # The searched path (--remat-search) arrives here with the DP's
        # per-layer choices already on strategy.remat.
        if self.cfg.remat and not getattr(strategy, "remat", None):
            strategy.remat = {l.name: "full" for l in model.layers}
        if self.cfg.remat_blocks:
            strategy.remat = {l.name: "block" for l in model.layers}

        self.forward_fn = build_forward(model.layers, model.input_tensors, outputs,
                                        mesh, strategy,
                                        seq_length=self.cfg.seq_length or None,
                                        compute_dtype=self.cfg.compute_dtype,
                                        enable_fusion=self.cfg.enable_fusion,
                                        collect_stats=True)
        # gradient-accumulation width the step functions are built for
        # (cfg default; fit(accum_steps=...) rebuilds on a different value)
        self._accum_steps = max(1, int(self.cfg.accum_steps))
        self._build_steps()
        self.params = None
        self.state: Dict[str, Any] = {}
        self.opt_state = None

    # ------------------------------------------------------------- sharding
    def _weight_sharding(self, layer_name: str, wname: str, shape) -> NamedSharding:
        pspec = self.strategy.sharding_for(layer_name).weight_pspec(wname)
        if not constrainable(pspec, shape, self.mesh):
            pspec = PartitionSpec()
        return NamedSharding(self.mesh, pspec)

    def input_sharding(self, tensor: Tensor) -> NamedSharding:
        pspec = self.strategy.input_pspec(tensor.name)
        if not constrainable(pspec, tensor.shape, self.mesh):
            pspec = PartitionSpec()
        return NamedSharding(self.mesh, pspec)

    def label_sharding(self, label_shape) -> NamedSharding:
        ax = "data" if "data" in self.mesh.shape else list(self.mesh.shape)[0]
        if label_shape and label_shape[0] % self.mesh.shape[ax] == 0:
            return NamedSharding(self.mesh, PartitionSpec(ax))
        return NamedSharding(self.mesh, PartitionSpec())

    def _put(self, arr, sharding):
        """Host→device transfer for EVERY data path (fit/evaluate/forward/
        set_weight). Single-process: plain device_put. Multi-process
        (control-replication analog): every process holds the full host
        array and contributes the rows its addressable shards own."""
        if jax.process_count() == 1:
            return jax.device_put(arr, sharding)
        from flexflow_tpu.runtime.distributed import global_batch_from_full

        return global_batch_from_full(np.asarray(arr), self.mesh, sharding.spec)

    # ------------------------------------------------- zero-redundancy state
    def _zero_mode(self) -> str:
        """Resolved ZeRO regime: cfg.zero_sharding, degraded to "off" when
        the mesh has no batch axis to shard over (1-device runs)."""
        mode = (self.cfg.zero_sharding or "off").lower()
        if mode not in ("off", "zero1", "zero2"):
            raise ValueError(f"zero_sharding={self.cfg.zero_sharding!r} "
                             "(choose from off/zero1/zero2)")
        if mode != "off" and not _zero_axes_of(self.mesh):
            return "off"
        return mode

    def _param_templates(self):
        """params-shaped trees of avals + compiled shardings, WITHOUT
        materializing arrays — mirrors init()'s params structure (one dict
        per weighted layer), so tx.init's state shape can be derived before
        any weight exists."""
        shapes: Dict[str, Dict[str, jax.ShapeDtypeStruct]] = {}
        shards: Dict[str, Dict[str, NamedSharding]] = {}
        for layer in topo_order(self.model.layers):
            if not layer.weight_specs:
                continue
            shapes[layer.name] = {
                w: jax.ShapeDtypeStruct(s.shape, s.dtype.jnp_dtype)
                for w, s in layer.weight_specs.items()}
            shards[layer.name] = {
                w: self._weight_sharding(layer.name, w, s.shape)
                for w, s in layer.weight_specs.items()}
        return shapes, shards

    def _moment_shardings(self, pshapes, pshards):
        """Per-param layout of the optimizer moments: the param's own
        sharding (the replicated regime / zero off), or that plus the
        data-axis degree on the first divisible free dim (ZeRO)."""
        if self._zero_mode() == "off":
            return pshards
        za = _zero_axes_of(self.mesh)
        return jax.tree_util.tree_map(
            lambda sds, sh: NamedSharding(self.mesh, _zero_moment_pspec(
                sh.spec, sds.shape, self.mesh, za)), pshapes, pshards)

    def _opt_state_shardings(self, pshapes, moment_sh):
        """Sharding tree matching tx.init's FULL state structure (for the
        jitted init's out_shardings and the in-step constraints): optax
        states embed params-shaped subtrees for the moments — those get
        `moment_sh` — while everything else (step counts, EmptyState)
        replicates."""
        repl = NamedSharding(self.mesh, PartitionSpec())
        shapes = jax.eval_shape(self.tx.init, pshapes)
        pstruct = jax.tree_util.tree_structure(pshapes)
        if pstruct.num_leaves == 0:
            return jax.tree_util.tree_map(lambda _: repl, shapes)

        def is_params_subtree(x):
            return jax.tree_util.tree_structure(x) == pstruct

        return jax.tree_util.tree_map(
            lambda sub: moment_sh if is_params_subtree(sub) else repl,
            shapes, is_leaf=is_params_subtree)

    # ---------------------------------------------------------------- init
    def init(self, seed: Optional[int] = None):
        """Initialize weights sharded-at-birth (no host round trip). The
        span `compile/init` is the HOST's part of it: tracing, lowering,
        compile (or cache read) and dispatch of the two init programs; both
        are asynchronous, and the device's time to fill the weights is
        waited for by whoever first needs them (warm-up's first step)."""
        seed = self.cfg.seed if seed is None else seed
        layers = topo_order(self.model.layers)
        overrides = self.model._initializer_overrides
        shardings = {}
        for layer in layers:
            if not layer.weight_specs:
                continue
            shardings[layer.name] = {
                w: self._weight_sharding(layer.name, w, s.shape)
                for w, s in layer.weight_specs.items()
            }

        init_fn = build_init_fn(layers, overrides)
        with tel.span("compile/init", cat="compile") as sp:
            self.params = jax.jit(init_fn, out_shardings=shardings)(
                jax.random.PRNGKey(seed))
            # what layers declare as state (`Layer.state_specs`), drawn
            # like the weights; nothing for a model without
            self.state = jax.jit(build_state_init_fn(layers, overrides))(
                jax.random.PRNGKey(seed)) \
                if any(l.state_specs for l in layers) else {}
            # jitted with EXPLICIT out_shardings (vs the old eager tx.init):
            # moments land directly in their target layout — sharded from
            # the first byte under ZeRO, and never paying the transient
            # fully-replicated allocation implicit propagation produced
            self.opt_state = jax.jit(self.tx.init,
                                     out_shardings=self._opt_sh)(self.params)
            sp.set(**weights_facts(self.params))
        self._iteration = 0
        # first HBM watermark: the persistent footprint right after init
        self._watermarks.sample("init", (self.params, self.opt_state))
        return self.params

    # ---------------------------------------------------------------- steps
    def _build_steps(self):
        forward_fn = self.forward_fn
        loss_type, metric_types = self.loss_type, self.metrics
        tx = self.tx
        # --allow-tensor-op-math-conversion (reference config.h / cuBLAS
        # tensor-op gate ≙ the MXU's reduced-precision passes): when off,
        # every dot runs at HIGHEST precision (f32 accumulation passes)
        precision = None if self.cfg.allow_tensor_op_math_conversion else "highest"

        regularizers = dict(self.model._weight_regularizers)
        # numerics sentinels (flexflow_tpu/health.py): fold the grad
        # global-norm + non-finite flag into the step's metric outputs —
        # they ride the deferred-metrics machinery (sums/means across
        # fused and accumulated steps), so the healthy path pays zero
        # extra host syncs; the fit loop pops the reserved keys off
        # before user-facing metric accounting
        sentinels = bool(getattr(self.cfg, "health_sentinels", False))

        # ZeRO machinery: the moment/opt-state sharding trees are fixed by
        # (strategy, mesh, optimizer), so build them once per compile and
        # share between the jitted tx.init (see init()) and the in-step
        # constraints below
        zero = self._zero_mode()
        accum = max(1, int(self._accum_steps))
        pshapes, pshards = self._param_templates()
        moment_sh = self._moment_sh = self._moment_shardings(pshapes, pshards)
        self._param_sh = pshards
        opt_sh = self._opt_sh = self._opt_state_shardings(pshapes, moment_sh)
        wsc = jax.lax.with_sharding_constraint

        def value_and_grads(params, state, inputs, label, rng):
            def loss_fn(p):
                # rematerialization is per-layer now (strategy.remat applied
                # inside build_forward); --remat aliases to all-layers "full"
                outs, new_state = forward_fn(p, state, inputs, True, rng)
                logits = outs[0]
                # the name stack is the phase key of attribution.op_scope_map
                # (layers carry their own name from build_forward): what is
                # computed here, and its backward, is the step's `loss`
                with jax.named_scope(attribution.LOSS_SCOPE):
                    loss = compute_loss(loss_type,
                                        logits.astype(jnp.float32), label)
                    for (ln, wn), terms in regularizers.items():
                        w = p[ln][wn].astype(jnp.float32)
                        for mode, lam in terms:
                            loss = loss + lam * (
                                jnp.sum(jnp.abs(w)) if mode == "l1"
                                else jnp.sum(w * w))
                return loss, (logits, new_state)

            return jax.value_and_grad(loss_fn, has_aux=True)(params)

        def apply_update(params, opt_state, grads):
            """One optimizer update. Under ZeRO this is the rewritten sync:
            constraining the (all-reduced) grads to the moment layout lets
            GSPMD lower the sync as reduce-scatter, each device updates
            only ITS moment shard, and the param-dtype updates all-gather
            back — same ring volume as the fused all-reduce
            (cost_model.grad_sync_time zero=True), 1/degree the moment
            memory and update flops."""
            with jax.named_scope(attribution.UPDATE_SCOPE):
                if zero != "off":
                    grads = wsc(grads, moment_sh)
                updates, opt_state = tx.update(grads, opt_state, params)
                if zero != "off":
                    updates = wsc(updates, pshards)      # all-gather
                    opt_state = wsc(opt_state, opt_sh)   # moments stay sharded
                return optax.apply_updates(params, updates), opt_state

        def grad_sentinels(mvals, loss, grads):
            """The health sentinel's sums over the gradients: the step's
            `update` phase, beside the update they watch."""
            if not sentinels:
                return mvals
            with jax.named_scope(attribution.UPDATE_SCOPE):
                return dict(mvals, **health.sentinel_metrics(
                    loss, optax.global_norm(grads)))

        def step_stats(mvals, new_state):
            """The counters the step's ops reported (`ctx.add_stat`) leave
            the state they came out in and ride the step's metrics under
            `STEP_STATS_PREFIX`: the fit loop takes them off again, and a
            model whose ops report none has none."""
            counted = new_state.pop(STATS_KEY, None)
            if not counted:
                return mvals
            return dict(mvals, **{STEP_STATS_PREFIX + k: jnp.float32(v)
                                  for k, v in counted.items()})

        def train_step(params, opt_state, state, inputs, label, rng):
            (loss, (logits, new_state)), grads = value_and_grads(
                params, state, inputs, label, rng)
            params, opt_state = apply_update(params, opt_state, grads)
            with jax.named_scope(attribution.LOSS_SCOPE):
                mvals = compute_metrics(metric_types,
                                        logits.astype(jnp.float32), label)
            return (params, opt_state, new_state, loss,
                    grad_sentinels(step_stats(mvals, new_state), loss, grads))

        def accum_step(params, opt_state, state, inputs, label, rng):
            """accum_steps=N microbatching: inputs/label carry a leading
            (N, ...) microbatch dim (runtime/dataloader.group_microbatches);
            N fwd/bwd passes accumulate a device-resident mean gradient and
            ONE optimizer update applies it — effective batch N x batch.
            Same signature as train_step, so make_multi_step fuses K
            UPDATES per dispatch unchanged. Under zero2 each microbatch's
            gradient is reduce-scattered before accumulation, so the
            accumulator is stored sharded like the moments (zero1 keeps
            full-size accumulators). Loss/metrics are means over the N
            microbatches. Microbatch j uses fold_in(rng, j) — dropout
            streams differ from an equivalent big-batch step by design."""
            def micro(j, state):
                ins = [jax.lax.dynamic_index_in_dim(a, j, keepdims=False)
                       for a in inputs]
                lab = jax.lax.dynamic_index_in_dim(label, j, keepdims=False)
                (loss, (logits, new_state)), grads = value_and_grads(
                    params, state, ins, lab, jax.random.fold_in(rng, j))
                if zero == "zero2":
                    grads = wsc(grads, moment_sh)
                with jax.named_scope(attribution.LOSS_SCOPE):
                    mvals = compute_metrics(metric_types,
                                            logits.astype(jnp.float32), lab)
                return new_state, grads, loss, step_stats(mvals, new_state)

            def body(j, carry):
                s, g, lsum, msum = carry
                s, g2, l2, mv2 = micro(j, s)
                tm = jax.tree_util.tree_map
                return (s, tm(jnp.add, g, g2), lsum + l2,
                        tm(jnp.add, msum, mv2))

            # microbatch 0 outside the loop fixes the carry's shapes (the
            # make_multi_step convention)
            s, g, lsum, msum = micro(0, state)
            s, g, lsum, msum = jax.lax.fori_loop(1, accum, body,
                                                 (s, g, lsum, msum))
            inv = 1.0 / accum
            with jax.named_scope(attribution.UPDATE_SCOPE):
                g = jax.tree_util.tree_map(lambda t: t * inv, g)
            params, opt_state = apply_update(params, opt_state, g)
            loss = lsum * inv
            mvals = jax.tree_util.tree_map(lambda x: x * inv, msum)
            return params, opt_state, s, loss, grad_sentinels(mvals, loss, g)

        step_fn = accum_step if accum > 1 else train_step

        def eval_step(params, state, inputs, label):
            outs, _ = forward_fn(params, state, inputs, False, jax.random.PRNGKey(0))
            logits = outs[0].astype(jnp.float32)
            loss = compute_loss(loss_type, logits, label)
            return loss, compute_metrics(metric_types, logits, label)

        def infer(params, state, inputs):
            outs, _ = forward_fn(params, state, inputs, False, jax.random.PRNGKey(0))
            return outs

        def _wrap(fn):
            if precision is None:
                return fn

            def wrapped(*a):
                with jax.default_matmul_precision(precision):
                    return fn(*a)

            return wrapped

        # donate_state=False keeps the previous params/opt/state buffers
        # alive after each step (debugging / external references)
        donate = (0, 1, 2) if self.cfg.donate_state else ()
        self.train_step = jax.jit(_wrap(step_fn), donate_argnums=donate)
        # what the step's device time is made of, for whoever asks
        # (attribution.op_scopes): a weak reference now, the executable at
        # the first dispatch, its HLO text only on demand
        self._programs = {1: attribution.register_program(
            "train_step", self.train_step, self.model.layers)}
        self.eval_step = jax.jit(_wrap(eval_step))
        self.infer_step = jax.jit(_wrap(infer))
        self._train_step_fn = step_fn  # unjitted body for make_multi_step
        self._wrap_precision = _wrap
        self._multi_cache = {}  # steps_per_dispatch -> jitted multi-step

    def _get_multi(self, k: int):
        """Cached make_multi_step(k) — one jit per fused width per compile
        (cleared by _build_steps on recompile)."""
        fn = self._multi_cache.get(k)
        if fn is None:
            fn = self._multi_cache[k] = self.make_multi_step(k)
            # the fused loop runs the same step body: same scopes, a
            # program of its own (its steps lie inside one `while`)
            self._programs[k] = attribution.register_program(
                "train_step", fn, self.model.layers)
        return fn

    def make_multi_step(self, n: int, donate: "Optional[bool]" = None):
        """One-dispatch n-step training: fori_loop over n stacked batches
        inside a single jitted program. The reference's analog is the Legion
        trace replay its Python fit loop wraps around each iteration
        (flexflow_cffi.py begin_trace/end_trace) — amortizing per-step
        runtime overhead; here it amortizes per-step DISPATCH, which
        dominates sub-10ms steps.

        Returns jitted fn(params, opt_state, state, stacked_inputs,
        stacked_labels, rng, i0=0) -> (params, opt_state, state, mean_loss,
        mean_metrics); stacked arrays carry a leading n dim. `i0` is the
        global iteration of the first fused step: step i uses
        fold_in(rng, i0 + i), so with rng = fit's base key the fused loop
        consumes the SAME dropout/rng stream as n individually dispatched
        train_steps at iterations i0..i0+n-1 (pass i0 as a jnp scalar to
        avoid retracing per value).

        `donate=None` follows cfg.donate_state. CAUTION (same contract as
        train_step): under donation the INPUT params/opt_state/state
        buffers are consumed — if you pass cm.params etc., write the
        returned trees back (cm.params, cm.opt_state, cm.state = p, o, s)
        before touching any other CompiledModel method, or they will
        dereference deleted arrays."""
        import jax

        if donate is None:
            donate = self.cfg.donate_state
        step = self._train_step_fn

        def multi(params, opt_state, state, inputs, labels, rng, i0=0):
            def at(i, arrs):
                return [jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
                        for a in arrs]

            def body(i, carry):
                p, o, s, loss_sum, msum = carry
                p, o, s, loss, mv = step(
                    p, o, s, at(i, inputs),
                    jax.lax.dynamic_index_in_dim(labels, i, keepdims=False),
                    jax.random.fold_in(rng, i0 + i))
                return (p, o, s, loss_sum + loss,
                        jax.tree_util.tree_map(jnp.add, msum, mv))

            # step 0 outside the loop fixes the carry's loss/metric shapes
            p, o, s, l0, mv0 = step(params, opt_state, state,
                                    [a[0] for a in inputs], labels[0],
                                    jax.random.fold_in(rng, i0))
            p, o, s, lsum, msum = jax.lax.fori_loop(
                1, n, body, (p, o, s, l0, mv0))
            return p, o, s, lsum / n, \
                jax.tree_util.tree_map(lambda x: x / n, msum)

        return jax.jit(self._wrap_precision(multi),
                       donate_argnums=(0, 1, 2) if donate else ())

    def _coerce_batch(self, batch_size: Optional[int]) -> int:
        # batch must match the traced graph-input batch dim (XLA static shapes)
        gb = self.model.input_tensors[0].shape[0]
        if batch_size is not None and batch_size != gb:
            import warnings

            warnings.warn(f"batch_size={batch_size} coerced to graph batch {gb} "
                          "(XLA static shapes; rebuild the model to change it)")
        return gb

    # ------------------------------------------------------------- training
    def fit(self, x, y, batch_size: Optional[int] = None, epochs: Optional[int] = None,
            callbacks=None, verbose: bool = True,
            sync_every: Optional[int] = None,
            steps_per_dispatch: Optional[int] = None,
            accum_steps: Optional[int] = None,
            resume: Optional[str] = None,
            checkpoint_dir: Optional[str] = None,
            checkpoint_every_steps: Optional[int] = None,
            checkpoint_every_secs: Optional[float] = None):
        # per-call overrides of the async-pipeline knobs (see config.py);
        # None = the config's value, threaded through (cfg never mutated)
        if sync_every is None:
            sync_every = self.cfg.sync_every
        if steps_per_dispatch is None:
            steps_per_dispatch = self.cfg.steps_per_dispatch
        if accum_steps is None:
            accum_steps = self.cfg.accum_steps
        if max(1, int(accum_steps)) != self._accum_steps:
            # the accumulation width is baked into the jitted step
            # functions: a different per-call value (or reverting to the
            # config's after an override) rebuilds them (and clears the
            # fused multi-step cache)
            self._accum_steps = max(1, int(accum_steps))
            self._build_steps()
        return self._fit(x, y, batch_size, epochs, callbacks, verbose,
                         sync_every, steps_per_dispatch,
                         resume, checkpoint_dir, checkpoint_every_steps,
                         checkpoint_every_secs)

    def _fit(self, x, y, batch_size, epochs, callbacks, verbose,
             sync_every, steps_per_dispatch, resume=None,
             checkpoint_dir=None, checkpoint_every_steps=None,
             checkpoint_every_secs=None):
        from flexflow_tpu.runtime.resilience import FitResilience

        with tel.span("fit/call", cat="fit") as call:
            with tel.span("fit/setup", cat="fit"):
                xs = x if isinstance(x, (list, tuple)) else [x]
                batch_size = batch_size or self.cfg.batch_size
                epochs = epochs or self.cfg.epochs
                if self.params is None:
                    self.init()
                batch_size = self._coerce_batch(batch_size)
                # resilience (runtime/resilience.py): durable periodic
                # checkpoints, SIGTERM/SIGINT drain, resume="auto". None
                # when fully off — the loop below then runs exactly the
                # PR-2 async pipeline.
                res = FitResilience.build(self, resume, checkpoint_dir,
                                          checkpoint_every_steps,
                                          checkpoint_every_secs)
                if res is not None:
                    # effective (per-call) knobs, not cfg: they define
                    # what the manifest's progress counters mean, for save
                    # AND resume check
                    res.set_effective(batch_size, self._accum_steps)
                # goodput accounting (flexflow_tpu/health.py): one meter
                # per fit; restore-from-checkpoint time is the "resume"
                # bucket (it happens before any epoch wall-clock starts)
                gm = self._goodput = health.GoodputMeter()
                t_res = time.perf_counter()
                progress = res.resume_now(verbose) if res is not None \
                    else None
                gm.add("resume", time.perf_counter() - t_res)
                loader = SingleDataLoader(xs, y, batch_size, shuffle=True,
                                          seed=self.cfg.seed)
                in_sh = [self.input_sharding(t)
                         for t in self.model.input_tensors]
                lab_sh = self.label_sharding(
                    (batch_size,) + tuple(np.asarray(y).shape[1:]))
                base_rng = jax.random.PRNGKey(self.cfg.seed + 17)
                self._drift_windows = []  # this fit's drift-monitor windows
                # --profiling (reference config.h:126): capture an xplane
                # trace of the whole fit (the Legion-trace/profiler analog,
                # flexflow_c.cc:1747)
                prof_ctx = None
                if self.cfg.profiling:
                    import os

                    pdir = self.cfg.profile_dir or "./ff_profile"
                    os.makedirs(pdir, exist_ok=True)
                    prof_ctx = jax.profiler.trace(pdir)
                    prof_ctx.__enter__()
            it0 = self._iteration
            try:
                history = self._fit_epochs(epochs, loader, in_sh, lab_sh,
                                           base_rng, batch_size, callbacks,
                                           verbose, sync_every,
                                           steps_per_dispatch, res, progress,
                                           gm)
            finally:
                if prof_ctx is not None:
                    prof_ctx.__exit__(None, None, None)
                    if verbose:
                        print(f"[profiling] trace written to "
                              f"{self.cfg.profile_dir or './ff_profile'}")
            call.set(steps=self._iteration - it0)
            with tel.span("fit/finish", cat="fit"):
                self._fit_end_report(verbose)
                # per-op work only on the success path (it launches
                # measurement jits; on an error path it would mask the real
                # exception). --profile-ops: attribute the fit's REAL
                # measured step time to individual ops
                # (flexflow_tpu/attribution.py) — only when someone consumes
                # the result (printed table or the telemetry sink), and
                # not when profile_report below runs the same join anyway
                will_report = prof_ctx is not None and verbose
                if self.cfg.profile_ops and (verbose or tel.enabled()) \
                        and not will_report:
                    self.op_attribution(print_table=verbose)
                if will_report:
                    self.profile_report()
        return history

    def _fit_end_report(self, verbose: bool) -> None:
        """Fit-end summary hooks: emit the drift event into the telemetry
        stream, warn when the cost model has drifted past the threshold,
        and surface any FAILED async checkpoint writes (a dropped
        checkpoint must never go unnoticed — satellite of ISSUE 5)."""
        from flexflow_tpu.runtime.checkpoint import warn_failed_writes

        tel.emit_fit_end(self.drift_stats(), verbose)
        warn_failed_writes(verbose)

    def _fit_epochs(self, epochs, loader, in_sh, lab_sh, base_rng,
                    batch_size, callbacks, verbose, sync_every,
                    steps_per_dispatch, res=None, progress=None, gm=None):
        """Asynchronous training pipeline (the Legion async-launch analog):
        the host's only per-step work is folding the rng key and issuing
        the next dispatch — loss/metrics stay device-resident (deferred
        PerfMetrics + a pending-loss list) and are materialized every
        cfg.sync_every steps (0 = epoch end only), K=cfg.steps_per_dispatch
        consecutive steps fuse into one make_multi_step dispatch over
        stacked prefetched batches, and a block_until_ready barrier every
        cfg.dispatch_ahead dispatches bounds how far the host may queue
        ahead of the device. Per-batch callbacks (`on_batch_end`) or a
        recompile trigger need per-step host control: they force K=1 and
        per-step materialization (the synchronous loop).

        `self.step_stats` counts dispatches / host_syncs / barriers /
        fused_steps for the whole fit; each epoch's history entry carries
        its own dispatches/host_syncs (tests/test_step_pipeline.py asserts
        ceil(num_batches/K) dispatches and zero mid-epoch host syncs in
        the default config).

        `res` (runtime/resilience.FitResilience, None = off) adds durable
        periodic checkpoints + SIGTERM/SIGINT drain, and `progress` (from
        a restored snapshot's manifest) resumes MID-RUN on the identical
        trajectory: the loader's shuffle rng fast-forwards past the
        completed epochs, the interrupted epoch skips its already-consumed
        accumulation groups, and the epoch's loss/metric accumulators are
        re-seeded from the snapshot so its summary covers the full epoch."""
        from flexflow_tpu.runtime import faults as _faults
        from flexflow_tpu.runtime.resilience import (RetryPolicy,
                                                     progress_dict,
                                                     run_resilient,
                                                     start_state)

        policy = res.policy if res is not None \
            else RetryPolicy.from_config(self.cfg)
        # run-health layer: the goodput meter buckets the loop's
        # wall-clock via its lap cursor (always on — a handful of
        # perf_counter calls per DISPATCH, not per step), and the
        # sentinel monitor strips the step functions' health/* outputs
        # into its own deferred window, checked only at the loop's
        # existing materialization points
        if gm is None:
            gm = self._goodput = health.GoodputMeter()
        sent = None
        if getattr(self.cfg, "health_sentinels", False):
            sent = health.SentinelMonitor(
                halt=bool(getattr(self.cfg, "halt_on_nonfinite", False)),
                checkpoint_root=res.root if res is not None else None)
        self._sentinels = sent
        start_epoch, skip_steps, history = start_state(progress)
        if progress:
            # the dataloader cursor: epochs 0..start_epoch-1 consumed their
            # shuffles; the resumed epoch below re-draws the SAME one
            loader.advance_epochs(start_epoch)
        per_batch_cbs = [cb for cb in callbacks or []
                         if hasattr(cb, "on_batch_end")]
        ahead = max(1, int(self.cfg.dispatch_ahead))
        # accum_steps=N: the loop's unit becomes an (N, ...)-stacked
        # accumulation group (group_microbatches below) — one dispatch of
        # the accumulating step = one optimizer update over N microbatches.
        # The unit shardings gain a leading unsharded microbatch dim; the
        # K-fused stacking then rides on top ((K, N, ...) transfers).
        accum = max(1, int(self._accum_steps))
        if accum > 1:
            in_sh_u = [NamedSharding(self.mesh, PartitionSpec(None, *s.spec))
                       for s in in_sh]
            lab_sh_u = NamedSharding(self.mesh,
                                     PartitionSpec(None, *lab_sh.spec))
        else:
            in_sh_u, lab_sh_u = in_sh, lab_sh
        in_sh_k = [NamedSharding(self.mesh, PartitionSpec(None, *s.spec))
                   for s in in_sh_u]
        lab_sh_k = NamedSharding(self.mesh,
                                 PartitionSpec(None, *lab_sh_u.spec))
        stats = self.step_stats = {"dispatches": 0, "host_syncs": 0,
                                   "barriers": 0, "fused_steps": 0,
                                   "epoch_end_syncs": 0}
        # every tel.span below times and labels (ring, profiler step
        # annotation, file sink); none adds a dispatch or a host sync
        faults_on = _faults.active()
        if res is not None:
            res.install_guard()
        try:
            for epoch in range(start_epoch, epochs):
              # fallbacks re-evaluated per epoch: a recompile trigger
              # registered mid-fit (e.g. by on_epoch_end) must drop the loop
              # to 1-step dispatch — and _get_multi must be re-fetched after
              # any recompile rebuilt the step functions
              k = max(1, int(steps_per_dispatch))
              sync = max(0, int(sync_every))
              if per_batch_cbs or self.recompile_state is not None:
                  k, sync = 1, 1  # per-step host control required
              multi = self._get_multi(k) if k > 1 else None
              pm = PerfMetrics()
              t0 = time.perf_counter()
              gm.tick()  # arm the goodput lap cursor at the epoch wall
              # loss rides a second deferred PerfMetrics keyed by STEPS (not
              # samples): device chunk-folding bounds memory on long epochs.
              # Parity with the old `loss_sum += float(loss)` loop is
              # bit-exact below fold_after pending steps, ~1e-7 relative
              # beyond (see PerfMetrics docstring)
              pml = PerfMetrics()
              # and a third for the counters the step's ops reported
              # (`ctx.add_stat`), keyed by steps: taken off the metrics
              # below, read where the loss is, at the epoch's end
              pst = PerfMetrics()
              nb = 0
              # steps/samples re-seeded from a resumed snapshot: the epoch
              # SUMMARY covers the whole epoch, but wall-clock-derived
              # stats (drift windows, samples/sec) must only count work
              # executed in THIS session
              seed_steps = seed_samples = 0
              # resume mid-epoch: the first `skip_steps` accumulation
              # groups were consumed before the snapshot — the loader
              # fast-forwards past their batches WITHOUT gathering them
              # (snapshots land on dispatch boundaries, so the skipped
              # region is whole accum-groups), and the epoch accumulators
              # re-seed from the manifest so this epoch's summary still
              # covers the WHOLE epoch
              resuming = epoch == start_epoch and progress
              grouped = group_microbatches(
                  loader.epoch(skip_batches=skip_steps * accum
                               if resuming else 0), accum)
              if resuming:
                  nb = seed_steps = skip_steps
                  pml.sums["loss"] = float(progress.get("loss_sum", 0.0))
                  pml.train_all = nb
                  pm.sums = {mk: float(mv) for mk, mv in
                             (progress.get("metric_sums") or {}).items()}
                  pm.train_all = seed_samples = int(progress.get("samples", 0))
              if sent is not None:
                  # per-epoch loss-window baseline (re-seeded on resume so
                  # pre-snapshot loss mass can't look like a spike)
                  sent._loss_sum_prev = pml.sums.get("loss", 0.0)
                  sent._steps_prev = nb
              ep_disp = ep_sync = 0
              since_sync = 0
              gen = prefetch_multi(
                  grouped, k,
                  in_sh_u, lab_sh_u, in_sh_k, lab_sh_k,
                  put=self._put, retry_policy=policy)

              def make_progress(_pml=pml, _pm=pm, _epoch=epoch):
                  # durable progress counters for res.maybe_checkpoint
                  # (reads nb/history at call time)
                  _pml.materialize()
                  _pm.materialize()
                  return progress_dict(_epoch, nb,
                                       _pml.sums.get("loss", 0.0),
                                       _pm.sums, _pm.train_all, history)

              while True:
                  # the gap between "want next batch" and "prefetcher
                  # delivered" is the data-wait cost the async loop is
                  # supposed to hide
                  with tel.span("fit/prefetch_wait", cat="fit"):
                      item = next(gen, None)
                  gm.lap("prefetch_wait")
                  if item is None:
                      break
                  kind, dx, dy = item
                  if faults_on:
                      # the fit/dispatch fault site: admission check BEFORE
                      # the jitted call (nothing consumed yet, retry-safe
                      # even under donation). One check per 1-based global
                      # step COVERED by this dispatch — "fail step 3" is
                      # fit/dispatch@3 regardless of how steps batch into
                      # fused dispatches (the faults.py contract)
                      for s in range(self._iteration + 1,
                                     self._iteration + 1
                                     + (k if kind == "k" else 1)):
                          run_resilient("fit/dispatch", lambda: None,
                                        policy, index=s)
                          # health/nonfinite: SILENT corruption — NaN-
                          # poison the first param leaf instead of
                          # raising, so the numerics sentinel (not an
                          # exception) must catch the blow-up
                          if _faults.poison("health/nonfinite", index=s):
                              leaves, tdef = jax.tree_util.tree_flatten(
                                  self.params)
                              if leaves:
                                  leaves[0] = leaves[0] * jnp.float32(
                                      np.nan)
                                  self.params = \
                                      jax.tree_util.tree_unflatten(
                                          tdef, leaves)
                  with tel.span("fit/dispatch", cat="fit",
                                step_num=self._iteration, kind=kind) as sp:
                      if kind == "k":
                          i0 = jnp.int32(self._iteration)
                          if self._programs[k].compiled is None:
                              self._programs[k].first_run(
                                  self.params, self.opt_state, self.state,
                                  dx, dy, base_rng, i0)
                          (self.params, self.opt_state, self.state, loss,
                           mvals) = multi(self.params, self.opt_state,
                                          self.state, dx, dy, base_rng, i0)
                          steps = k
                          stats["fused_steps"] += k
                      else:  # single step (k==1, or the fused-epoch tail)
                          rng = jax.random.fold_in(base_rng, self._iteration)
                          if self._programs[1].compiled is None:
                              self._programs[1].first_run(
                                  self.params, self.opt_state, self.state,
                                  dx, dy, rng)
                          (self.params, self.opt_state, self.state, loss,
                           mvals) = self.train_step(self.params,
                                                    self.opt_state,
                                                    self.state, dx, dy, rng)
                          steps = 1
                      sp.set(steps=steps, iteration=self._iteration + steps)
                  gm.lap("dispatch")
                  self._iteration += steps
                  nb += steps
                  since_sync += steps
                  ep_disp += 1
                  stats["dispatches"] += 1
                  if sent is not None:
                      sent.push(steps, mvals)  # strips health/* keys
                  counted = [mk for mk in mvals
                             if mk.startswith(STEP_STATS_PREFIX)]
                  if counted:
                      pst.update_deferred(
                          steps, {mk[len(STEP_STATS_PREFIX):]: mvals.pop(mk)
                                  for mk in counted})
                  pml.update_deferred(steps, {"loss": loss})
                  pm.update_deferred(batch_size * accum * steps, mvals)
                  gm.lap("loop")
                  if sync and since_sync >= sync:
                      with tel.span("fit/host_sync", cat="fit",
                                    iteration=self._iteration):
                          pml.materialize()
                          pm.materialize()
                          if sent is not None:
                              # sentinel window check rides the EXISTING
                              # sync (no extra materialization point)
                              sent.check(self._iteration,
                                         loss_sum=pml.sums.get("loss", 0.0),
                                         steps_total=nb)
                      stats["host_syncs"] += 1
                      ep_sync += 1
                      since_sync = 0
                      gm.lap("host_sync")
                  elif ep_disp % ahead == 0:
                      # bounded dispatch-ahead: wait for the device to catch
                      # up (no host transfer, just a queue-depth barrier)
                      with tel.span("fit/barrier_sync", cat="fit",
                                    iteration=self._iteration):
                          jax.block_until_ready(loss)
                      stats["barriers"] += 1
                      gm.lap("barrier")
                  if res is not None:
                      res.maybe_checkpoint(loss, make_progress)
                      gm.lap("checkpoint")
                  for cb in per_batch_cbs:
                      cb.on_batch_end(self._iteration, {"loss": float(loss)})
                  if kind == "1":
                      self._maybe_recompile()
              # epoch end: the one unavoidable materialization (counted on
              # its own, not as a mid-epoch host sync)
              with tel.span("fit/epoch_end_sync", cat="fit"):
                  pml.materialize()
                  if pst.train_all:
                      # the epoch's counters, a mean a step: one record
                      step_means = pst.summary()
                      step_means.pop("samples")
                      tel.record("fit/step_stats", tel.now_us(), cat="fit",
                                 epoch=epoch, steps=pst.train_all,
                                 **step_means)
                  if sent is not None:
                      sent.check(self._iteration,
                                 loss_sum=pml.sums.get("loss", 0.0),
                                 steps_total=nb)
              stats["epoch_end_syncs"] += 1
              gm.lap("host_sync")
              dt = time.perf_counter() - t0
              # drift/throughput count only work executed THIS session: a
              # resumed epoch's re-seeded steps/samples ran before the
              # snapshot, against a wall clock that started at resume
              self._drift_windows.append((nb - seed_steps, dt))
              tel.record("fit/epoch", tel.now_us() - dt * 1e6, cat="fit",
                         epoch=epoch, steps=nb)
              grec = gm.epoch_end(dt, epoch)
              # HBM watermark at the epoch boundary (outside the epoch
              # wall; memory_stats() on real backends, live-buffer bytes
              # on the CPU twin)
              self._watermarks.sample(f"epoch{epoch}",
                                      (self.params, self.opt_state))
              summ = pm.summary()
              summ["loss"] = pml.sums.get("loss", 0.0) / max(1, nb)
              summ["epoch_time_s"] = dt
              summ["samples_per_sec"] = (pm.train_all - seed_samples) / dt \
                  if dt > 0 else 0.0
              summ["dispatches"] = float(ep_disp)
              summ["host_syncs"] = float(ep_sync)
              summ["goodput"] = grec["goodput"]
              history.append(summ)
              if verbose:
                  ms = " ".join(f"{k_}={v:.4f}" for k_, v in summ.items()
                                if k_ not in ("samples", "dispatches",
                                              "host_syncs"))
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
        return history

    def evaluate(self, x, y, batch_size: Optional[int] = None):
        # batch is pinned to the traced graph batch; tail samples beyond the
        # last full batch are excluded (drop_remainder, like the reference's
        # shard-sized batches)
        xs = x if isinstance(x, (list, tuple)) else [x]
        batch_size = self._coerce_batch(batch_size)
        loader = SingleDataLoader(xs, y, batch_size, shuffle=False)
        in_sh = [self.input_sharding(t) for t in self.model.input_tensors]
        lab_sh = self.label_sharding((batch_size,) + tuple(np.asarray(y).shape[1:]))
        pm = PerfMetrics()
        pml = PerfMetrics()  # deferred per-batch losses (chunk-folded)
        ahead = max(1, int(self.cfg.dispatch_ahead))
        nb = 0
        for dx, dy in prefetch_to_device(loader.epoch(), in_sh, lab_sh,
                                         put=self._put):
            loss, mvals = self.eval_step(self.params, self.state, dx, dy)
            pm.update_deferred(batch_size, mvals)
            pml.update_deferred(1, {"loss": loss})
            nb += 1
            if nb % ahead == 0:  # bounded dispatch-ahead, as in fit
                jax.block_until_ready(loss)
        pml.materialize()
        out = pm.summary()
        out["loss"] = pml.sums.get("loss", 0.0) / max(1, nb)
        return out

    def forward(self, *inputs):
        if self.params is None:
            self.init()
        arrs = [self._put(np.asarray(a), s)
                for a, s in zip(inputs, [self.input_sharding(t) for t in self.model.input_tensors])]
        outs = self.infer_step(self.params, self.state, arrs)
        return outs[0] if len(outs) == 1 else outs

    # ------------------------------------------------------------ profiling
    def _candidate_for(self, layer):
        """The sharding candidate matching the COMPILED strategy's weight
        layout for this layer (falls back to dp when nothing matches) —
        see candidates.compiled_candidate."""
        from flexflow_tpu.search.candidates import compiled_candidate

        batch_sizes = {t.shape[0] for t in self.model.input_tensors if t.ndim > 0}
        return compiled_candidate(layer, self.strategy, self.machine,
                                  batch_sizes)

    def memory_stats(self) -> dict:
        """Per-device persistent-memory report: what the search-side cost
        model PREDICTS for this compile's strategy + optimizer (params +
        grads + moments under the OptMemSpec accounting, ZeRO divisor
        included) next to what the live buffers ACTUALLY hold (summed
        addressable-shard bytes on device 0). tests/test_zero.py asserts
        the two agree on the ~data-degree optimizer-state reduction."""
        from flexflow_tpu.search import cost_model as cmod

        opt_mem = cmod.opt_mem_spec(self.optimizer, self.cfg, self.machine)
        pred_w = pred_opt = 0
        for layer in self.model.layers:
            if not layer.weight_specs:
                continue
            cand = self._candidate_for(layer)
            pred_w += cand.weight_mem_bytes(layer, self.machine, opt_mem)
            for w, spec in layer.weight_specs.items():
                dims = cand.weight_dims.get(w, [])
                elems = cmod.shard_bytes(spec, dims, self.machine) \
                    // max(1, spec.dtype.itemsize)
                pred_opt += (opt_mem.moments * elems * opt_mem.state_itemsize
                             // cmod.zero_divisor(spec, dims, self.machine,
                                                  opt_mem.zero_axes))

        def per_device_bytes(tree):
            if tree is None:
                return 0
            dev = self.mesh.devices.flat[0]
            total = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                shards = getattr(leaf, "addressable_shards", None)
                if shards is None:
                    total += int(getattr(leaf, "nbytes", 0))
                    continue
                total += sum(s.data.nbytes for s in shards
                             if s.device == dev)
            return total

        za = _zero_axes_of(self.mesh)
        deg = 1
        for a in za:
            deg *= self.mesh.shape[a]
        return {
            "zero_sharding": self._zero_mode(),
            "data_axis_degree": deg,
            "predicted_weight_state_bytes": int(pred_w),
            "predicted_opt_state_bytes": int(pred_opt),
            "actual_param_bytes_per_device": per_device_bytes(self.params),
            "actual_opt_state_bytes_per_device":
                per_device_bytes(self.opt_state),
        }

    def search_cache_stats(self) -> dict:
        """Search fast-path observability: this compile's strategy-cache
        event, the process-wide cache counters, the memoized-costing hit
        rates, and the DP work counters (cache-stats of profile_report)."""
        from flexflow_tpu.search import memo
        from flexflow_tpu.search import strategy_cache as sc
        from flexflow_tpu.search.dp import SEARCH_STATS

        return {
            "strategy_cache": dict(sc.STATS.as_dict(),
                                   this_compile=self.search_cache_info),
            "memo": memo.stats(),
            "dp": dict(SEARCH_STATS),
        }

    def predicted_step_time(self) -> Optional[float]:
        """The cost model's per-UPDATE time prediction for this compile:
        the search's own best_cost when the strategy came out of
        graph_optimize (stamped there, and restored from the cache entry's
        meta on warm hits), else the analytic additive sum over the
        compiled candidates (data-parallel / imported strategies). Scaled
        by accum_steps — one fit-loop step is one update over N
        microbatch passes — so it is directly comparable to the drift
        monitor's measured windows."""
        accum = max(1, int(self._accum_steps))
        pc = getattr(self.strategy, "_predicted_cost", None)
        if pc:
            return float(pc) * accum
        try:
            total = 0.0
            for layer in self.model.layers:
                cand = self._candidate_for(layer)
                if not cand.passthrough:
                    total += cand.op_time(layer, self.machine)
            return total * accum if total > 0 else None
        except Exception:
            return None

    def drift_stats(self) -> dict:
        """Cost-model drift monitor: predicted vs measured step time (see
        telemetry.drift_stats; windows are the last fit's per-epoch
        (steps, seconds) pairs)."""
        return tel.drift_stats(self.predicted_step_time(),
                               list(self._drift_windows))

    def goodput_report(self) -> dict:
        """The last fit's wall-clock bucket accounting (see
        health.GoodputMeter.report): per-bucket seconds, goodput%, the
        unattributed residual, and the accounted fraction. Empty dict
        before any fit."""
        return self._goodput.report() if self._goodput is not None else {}

    def health_report(self) -> dict:
        """Run-health summary: sentinel detector status (nonfinite /
        spike counts) and the HBM watermark vs the cost model's
        predicted per-device footprint (health.watermark_drift)."""
        sent = self._sentinels.state.status() \
            if self._sentinels is not None else None
        wm = None
        if self._watermarks.samples:
            pred = self.memory_stats()["predicted_weight_state_bytes"]
            wm = self._watermarks.report(pred)
        return {"sentinels": sent, "watermarks": wm}

    def op_attribution(self, step_time_s: Optional[float] = None,
                       source: str = "auto", top: int = 0,
                       print_table: bool = True) -> dict:
        """Per-op performance attribution (ISSUE 7 tentpole; see
        flexflow_tpu/attribution.py): joins each compiled op's measured
        time — the --profiling trace when one exists, else the partitioned
        re-execution — against the search's stamped per-op predicted cost
        and the roofline bound. step_time_s defaults to the drift
        monitor's measured per-update time from the LAST fit (attributed
        times are rescaled to sum to it); with no fit yet, attributed ==
        isolated measured. Emits op/attr + op/drift_topk telemetry events
        when the sink is on. Returns the report
        dict ({"rows", "top_drift", "coverage", ...})."""
        from flexflow_tpu import attribution

        if step_time_s is None:
            step_time_s = self.drift_stats().get("measured_step_time_s")
        pred = getattr(self.strategy, "_predicted_op_costs", None) or {}
        items = []
        for layer in topo_order(self.model.layers):
            cand = self._candidate_for(layer)
            if cand.passthrough:
                continue
            items.append({"layer": layer, "cand": cand,
                          "machine": self.machine,
                          "predicted_s": pred.get(layer.name),
                          "stage": None})
        profile_dir = (self.cfg.profile_dir or "./ff_profile") \
            if self.cfg.profiling else None
        report = attribution.build_report(
            items, step_time_s=step_time_s,
            mult=max(1, int(self._accum_steps)),
            profile_dir=profile_dir, source=source,
            programs=list(self._programs.values()))
        if print_table:
            for line in attribution.format_report(report, top=top):
                print(line)
        return report

    def profile_report(self, top: int = 0, print_table: bool = True):
        """Per-op timing table (reference: per-kernel ms prints behind
        --profiling, src/ops/kernels/linear_kernels.cu:98-117): each layer's
        analytic roofline prediction and isolated measured time under the
        candidate matching its COMPILED sharding, plus the search fast-path
        cache stats (strategy cache / memoized costing / DP counters).
        Returns the rows."""
        from flexflow_tpu.search.measure import MeasuredCost

        # deliberately NOT backed by the persistent measured-cost store
        # (cache_dir="" also overrides the FF_MEASURE_CACHE_DIR fallback):
        # these quick repeats=3/warmup=1 numbers are report-quality, and
        # persisting them would silently degrade the calibration data (and
        # fingerprint) the measured SEARCH path relies on
        mc = MeasuredCost(self.machine, repeats=3, warmup=1, cache_dir="")
        rows = []
        for layer in self.model.layers:
            cand = self._candidate_for(layer)
            if cand.passthrough:
                continue
            rows.append({
                "layer": layer.name,
                "op": layer.op_type.value,
                "candidate": cand.name,
                "analytic_us": cand.op_time(layer, self.machine) * 1e6,
                "measured_us": mc.op_time(layer, cand) * 1e6,
            })
        rows.sort(key=lambda x: -x["measured_us"])
        if top:
            rows = rows[:top]
        if print_table:
            total = sum(x["measured_us"] for x in rows) or 1.0
            print(f"{'layer':28} {'op':18} {'analytic':>10} {'measured':>10} {'%':>5}")
            for x in rows:
                print(f"{x['layer'][:28]:28} {x['op'][:18]:18} "
                      f"{x['analytic_us']:9.1f}u {x['measured_us']:9.1f}u "
                      f"{100 * x['measured_us'] / total:4.1f}%")
            from flexflow_tpu.search import memo

            stats = self.search_cache_stats()
            cs, dp = stats["strategy_cache"], stats["dp"]
            info = self.search_cache_info or {}
            print(f"[strategy-cache] this_compile="
                  f"{info.get('event', 'off/skipped')} "
                  f"hits={cs['hits']} misses={cs['misses']} "
                  f"stores={cs['stores']} invalidated={cs['invalidated']}")
            print(f"[search] dp_calls={dp.get('calls', 0)} "
                  f"expansions={dp.get('expansions', 0)} "
                  f"prefix_skipped_layers={dp.get('layers_skipped', 0)}; "
                  f"{memo.stats_line()}")
            mem = self.memory_stats()
            mb = 1024 * 1024
            print(f"[memory] zero={mem['zero_sharding']} "
                  f"data_degree={mem['data_axis_degree']} "
                  f"predicted/device: weight-state "
                  f"{mem['predicted_weight_state_bytes'] / mb:.2f}MB "
                  f"(opt {mem['predicted_opt_state_bytes'] / mb:.2f}MB)")
            print(f"[memory] actual/device:    params "
                  f"{mem['actual_param_bytes_per_device'] / mb:.2f}MB, "
                  f"opt state "
                  f"{mem['actual_opt_state_bytes_per_device'] / mb:.2f}MB")
            for line in tel.format_drift(self.drift_stats()):
                print(line)
            if self._goodput is not None and self._goodput.epochs:
                for line in health.format_goodput(self._goodput.report()):
                    print(line)
            wm = self._watermarks.report(
                mem["predicted_weight_state_bytes"]) \
                if self._watermarks.samples else None
            sent = self._sentinels.state.status() \
                if self._sentinels is not None else None
            for line in health.format_health(sent, wm):
                print(line)
            if self.cfg.profile_ops:
                # --profile-ops: the full attribution join (measured vs
                # predicted vs roofline, MFU, per-op drift top-K)
                self.op_attribution(print_table=True, top=top)
            else:
                print("[drift] per-op attribution: --profile-ops / "
                      "op_attribution() / tools/profile_attribution.py")
            from flexflow_tpu.runtime.checkpoint import \
                report_failed_writes

            for line in report_failed_writes():
                print(line)
        return rows

    def export_sim_trace(self, path: str):
        """Replay the COMPILED strategy through the event-driven simulator
        and write a chrome-trace timeline (load in chrome://tracing /
        perfetto) — the reference taskgraph simulator's export_file_name
        analog. Wired to --simulator-trace. Returns the SimReport."""
        from flexflow_tpu.search.simulator import simulate_strategy

        choices = {l.name: self._candidate_for(l) for l in self.model.layers}
        # same segmentation the search's re-rank used, so the exported
        # timeline matches the simulation that ranked the strategy
        report = simulate_strategy(self.model, choices, self.machine,
                                   segment_bytes=self.cfg.simulator_segment_size)
        report.export_trace(path)
        return report

    # ------------------------------------------------- recompile-on-condition
    def recompile_on_condition(self, trigger_fn, alter_fn):
        """Reference: RecompileState (include/flexflow/recompile.h:26-43),
        FFModel::recompile_on_condition (src/runtime/model.cc:2422)."""
        self.recompile_state = (trigger_fn, alter_fn)

    def _maybe_recompile(self):
        if self.recompile_state is None:
            return
        trigger, alter = self.recompile_state
        if trigger(self):
            alter(self)
            self.forward_fn = build_forward(self.model.layers, self.model.input_tensors,
                                            self.outputs, self.mesh, self.strategy,
                                            seq_length=self.cfg.seq_length or None,
                                            compute_dtype=self.cfg.compute_dtype,
                                            enable_fusion=self.cfg.enable_fusion)
            self._build_steps()
            if self._goodput is not None:
                # charge the rebuild to the recompile goodput bucket
                self._goodput.lap("recompile")

    # ----------------------------------------------------------- checkpoint
    def save_checkpoint(self, path: str, block: Optional[bool] = None) -> str:
        """Full training-state checkpoint (params + optimizer state + BN
        state + iteration) — orbax-backed; see runtime/checkpoint.py.

        With cfg.async_checkpoint (the default), the device→host snapshot
        happens here (donation-safe) and serialization + fsync run on a
        background writer thread, so periodic saves don't stall the step
        loop. `load_checkpoint`/`wait_checkpoints` join pending writes;
        pass block=True to force the old fully synchronous save."""
        from flexflow_tpu.runtime.checkpoint import save_checkpoint

        if block is None:
            block = not self.cfg.async_checkpoint
        return save_checkpoint(self, path, block=block)

    def wait_checkpoints(self) -> None:
        """Join any in-flight async checkpoint writes (surfacing their
        errors here rather than losing them with the writer thread)."""
        from flexflow_tpu.runtime.checkpoint import wait_pending

        wait_pending()

    def load_checkpoint(self, path: str) -> None:
        from flexflow_tpu.runtime.checkpoint import restore_checkpoint

        restore_checkpoint(self, path)

    # ------------------------------------------------------------- weights
    def parallel_view(self, layer_name: str, out_idx: int = 0):
        """The ParallelTensor view of a layer output under the compiled
        strategy: per-dim degrees, shard shape, replica axes (reference
        ParallelTensorBase, include/flexflow/parallel_tensor.h:134-198)."""
        from flexflow_tpu.parallel.ptensor import ParallelTensor

        layer = self.model.get_layer_by_name(layer_name)
        sh = self.strategy.op_shardings.get(layer_name)
        dims = sh.outputs[out_idx] if sh and out_idx < len(sh.outputs) else []
        return ParallelTensor.build(layer.outputs[out_idx].spec, list(dims),
                                    self.machine)

    @staticmethod
    def _stacked_alias(layer, wname):
        """Resolve a per-branch "b{i}.{sub}.{w}" name against stacked
        storage: returns (stacked_key, branch_index) or None. Keeps the
        per-branch weight API stable across the two residency regimes."""
        if wname in layer.weight_specs or not wname.startswith("b"):
            return None
        head, _, rest = wname.partition(".")
        if not rest or not head[1:].isdigit():
            return None
        stk = f"stk.{rest}"
        return (stk, int(head[1:])) if stk in layer.weight_specs else None

    def weight_view(self, layer_name: str, wname: str = "kernel"):
        """ParallelTensor view of a weight under the compiled strategy."""
        from flexflow_tpu.core.tensor import TensorSpec
        from flexflow_tpu.parallel.ptensor import ParallelTensor

        layer = self.model.get_layer_by_name(layer_name)
        sh = self.strategy.op_shardings.get(layer_name)
        alias = self._stacked_alias(layer, wname)
        if alias is not None:
            stk, _b = alias
            spec = layer.weight_specs[stk]
            dims = list(sh.weights.get(stk, []) if sh else [])
            # the branch slice drops the stacked dim (and its sharding)
            return ParallelTensor.build(
                TensorSpec(spec.shape[1:], spec.dtype), list(dims[1:]),
                self.machine)
        dims = (sh.weights.get(wname, []) if sh else [])
        return ParallelTensor.build(layer.weight_specs[wname], list(dims),
                                    self.machine)

    def get_weight(self, layer_name: str, wname: str = "kernel") -> np.ndarray:
        layer = self.model.get_layer_by_name(layer_name)
        alias = self._stacked_alias(layer, wname)
        if alias is not None:
            stk, b = alias
            return np.asarray(self.params[layer_name][stk])[b]
        return np.asarray(self.params[layer_name][wname])

    def set_weight(self, layer_name: str, wname: str, value):
        value = np.asarray(value)
        layer = self.model.get_layer_by_name(layer_name)
        alias = self._stacked_alias(layer, wname)
        if alias is not None:
            stk, b = alias
            target = self.params[layer_name][stk]
            assert value.shape == tuple(target.shape[1:]), \
                (value.shape, target.shape)
            # in-place sharded slice update: only the owning devices' shard
            # moves (gathering the whole stack to host would defeat the
            # owned-device residency); branch index is a traced argument so
            # repeated set_weight calls hit the jit cache
            self.params[layer_name][stk] = _stacked_slice_set(
                target, jnp.asarray(value, target.dtype), jnp.int32(b))
            return
        target = self.params[layer_name][wname]
        assert value.shape == tuple(target.shape), (value.shape, target.shape)
        self.params[layer_name][wname] = self._put(value, target.sharding)
