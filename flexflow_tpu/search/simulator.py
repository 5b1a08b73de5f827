"""Event-driven task-graph simulator — concurrent replay of a full strategy.

Reference analog: `LogicalTaskgraphBasedSimulator::simulate_runtime`
(include/flexflow/simulator.h:785-827, src/runtime/simulator.cc:1251-1480):
build fwd/bwd/allreduce tasks per op under a chosen ParallelConfig, wire
dependency edges with transfer tasks, then replay the graph on a machine
model with a ready-queue — per-device timelines advance concurrently, so
compute/communication overlap *emerges* from the schedule instead of being a
calibrated scalar (the closed-form `overlapped_step_cost` stand-in the
frontier DP uses per-layer, search/dp.py).

TPU formulation: under SPMD every chip executes the same program, so one
logical timeline per *hardware stream* replaces per-GPU queues — the MXU
compute stream plus one DMA stream per mesh axis (ICI links run concurrently
with compute and with other axes' links; that concurrency is exactly why
XLA's async collectives hide). Tasks:

  fwd[i]  (mxu)     candidate forward compute
  bwd[i]  (mxu)     candidate backward compute (reverse graph order)
  edge comm (link)  reshard of an input edge, fwd direction (the additive
                    model's convention: one priced transfer per edge)
  inherent comm     candidate extra_comm (tp all-reduce, ring hops, halos)
  grad sync (link)  per-layer gradient all-reduce over replica axes
  update[i] (mxu)   optimizer update, HBM-bound (reference
                    new_update_task_unrecorded)

Big transfers are split into `segment_bytes` chunks (reference
`--simulator-segment-size`, default 16 MB, model.cc:3493) so short
transfers interleave with long ones on a shared link.

The headline effect this captures that additive costing cannot: gradient
all-reduces of layer i ride the ICI links while the MXU runs the backward
of layers < i — large-weight data-parallel plans are systematically
over-priced by additive accumulation (see test_simulator.py's ranking flip).
"""

from __future__ import annotations

import dataclasses
import heapq
import json
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from flexflow_tpu.core.graph import topo_order
from flexflow_tpu.parallel.machine import MachineSpec
from flexflow_tpu.search import cost_model as cm
from flexflow_tpu.search.candidates import Candidate, _batch_axes, _dp_dims
from flexflow_tpu.search.dp import _drop_axis, _freeze_dims

DEFAULT_SEGMENT_BYTES = 16 * 1024 * 1024  # reference model.cc:3493


@dataclasses.dataclass
class SimTask:
    name: str
    kind: str          # "comp" | "comm"
    resource: str      # "mxu" | "link:<axis>"
    duration: float
    bytes: int = 0
    ready_time: float = 0.0
    counter: int = 0
    next_tasks: List["SimTask"] = dataclasses.field(default_factory=list)
    start: float = -1.0
    end: float = -1.0

    def add_next(self, t: "SimTask") -> None:
        self.next_tasks.append(t)
        t.counter += 1


@dataclasses.dataclass
class SimReport:
    makespan: float
    tasks: List[SimTask]
    resource_busy: Dict[str, float]

    @property
    def total_comm(self) -> float:
        return sum(t.duration for t in self.tasks if t.kind == "comm")

    @property
    def exposed_comm(self) -> float:
        """Wall-clock the MXU sat idle — the comm (and dependency stall) time
        the schedule failed to hide behind compute."""
        return max(0.0, self.makespan - self.resource_busy.get("mxu", 0.0))

    @property
    def hidden_frac(self) -> float:
        tc = self.total_comm
        if tc <= 0:
            return 1.0
        return max(0.0, min(1.0, 1.0 - self.exposed_comm / tc))

    def to_json(self) -> dict:
        return {
            "makespan_s": self.makespan,
            "total_comm_s": self.total_comm,
            "exposed_comm_s": self.exposed_comm,
            "hidden_frac": self.hidden_frac,
            "resource_busy_s": dict(self.resource_busy),
            "timeline": [
                {"name": t.name, "kind": t.kind, "resource": t.resource,
                 "start_us": t.start * 1e6, "end_us": t.end * 1e6}
                for t in self.tasks],
        }

    def export_trace(self, path: str) -> None:
        """Chrome trace-event format (load in chrome://tracing / perfetto) —
        the reference's taskgraph export analog (export_file_name)."""
        pids = {r: i for i, r in enumerate(sorted(self.resource_busy))}
        events = [
            {"name": t.name, "cat": t.kind, "ph": "X",
             "ts": t.start * 1e6, "dur": (t.end - t.start) * 1e6,
             "pid": 0, "tid": pids.get(t.resource, 99),
             "args": {"resource": t.resource}}
            for t in self.tasks]
        meta = [{"name": "thread_name", "ph": "M", "pid": 0, "tid": i,
                 "args": {"name": r}} for r, i in pids.items()]
        with open(path, "w") as f:
            json.dump({"traceEvents": meta + events}, f)


def _involved_axes(src, dst) -> Tuple[str, ...]:
    sa = {a for d in src for a in cm._axes_of(d)}
    da = {a for d in dst for a in cm._axes_of(d)}
    return tuple(sorted(sa.symmetric_difference(da))) or tuple(sorted(sa | da))


def _link_of(axes: Sequence[str], machine: MachineSpec) -> str:
    """Multi-axis collectives stage hierarchically (cost_model's
    _hier_gather_time) — the serial total occupies the slowest involved
    link's timeline (the stage that dominates)."""
    live = [a for a in axes if machine.mesh_axes.get(a, 1) > 1]
    if not live:
        return "link:_"
    return "link:" + min(live, key=lambda a: machine.axis_bw_eff(a))


def build_step_tasks(model, choices: Dict[str, Candidate], machine: MachineSpec,
                     cost_fn=None, include_update: bool = True,
                     segment_bytes: int = DEFAULT_SEGMENT_BYTES,
                     ) -> List[SimTask]:
    """Task graph for one training step under a full per-op assignment.

    `choices` maps layer name -> chosen Candidate (a SearchResult.choices or
    an MCMC assignment). `cost_fn(layer, cand)` overrides the analytic total
    op time; if it exposes `.op_times(layer, cand) -> (fwd, bwd)` (the
    MeasuredCost protocol) the independently measured split is used,
    otherwise pure compute splits fwd:bwd = 1:2 (cost_model.compute_time's
    3x convention)."""
    layers = topo_order(model.layers)
    batch_sizes = {t.shape[0] for t in model.input_tensors if t.ndim > 0}
    batch_axes = _batch_axes(machine)
    tasks: List[SimTask] = []

    def comm_task(name: str, dur: float, nbytes: int, link: str,
                  after: Sequence[SimTask], before: Sequence[SimTask]) -> None:
        """Emit a comm task, segmented into `segment_bytes` chunks chained on
        the link so other transfers can interleave (reference
        route_transfer_seg, simulator.cc: requeue-unfinished)."""
        if dur <= 0:
            for a in after:
                for b in before:
                    a.add_next(b)
            return
        nseg = max(1, math.ceil(nbytes / segment_bytes)) if nbytes else 1
        prev: Optional[SimTask] = None
        for s in range(nseg):
            t = SimTask(f"{name}[{s}/{nseg}]" if nseg > 1 else name,
                        "comm", link, dur / nseg, nbytes // nseg)
            tasks.append(t)
            for a in (after if s == 0 else [prev]):
                a.add_next(t)
            prev = t
        for b in before:
            prev.add_next(b)

    # frontier layouts, evolved as the DP evolves them
    lay: Dict[int, Tuple] = {
        t.guid: _freeze_dims(_dp_dims(t.shape, machine, batch_sizes))
        for t in model.input_tensors}
    specs = {t.guid: t.spec for t in model.input_tensors}
    fwd_of: Dict[str, SimTask] = {}
    bwd_of: Dict[str, SimTask] = {}
    producer: Dict[int, str] = {}  # tensor guid -> producing layer name

    for layer in layers:
        for o in layer.outputs:
            specs[o.guid] = o.spec
        cand = choices[layer.name]
        if cand.passthrough:
            src = lay.get(layer.inputs[0].guid) if layer.inputs else None
            if src is None:
                src = _freeze_dims([None] * layer.inputs[0].spec.ndim)
            od = tuple(_drop_axis(d, cand.drop_axis) for d in src)
            pname = producer.get(layer.inputs[0].guid) if layer.inputs else None
            if od != src:
                # implied all-gather: a real comm task between producer and
                # consumers; fwd/bwd anchors alias the producer's
                spec = layer.inputs[0].spec
                dur = cm.reshard_time(spec, list(src), list(od), machine)
                link = _link_of(_involved_axes(src, od), machine)
                anchor = SimTask(f"{layer.name}:gather-anchor", "comp", "mxu", 0.0)
                tasks.append(anchor)
                comm_task(f"{layer.name}:gather", dur,
                          cm.shard_bytes(spec, list(od), machine), link,
                          [fwd_of[pname]] if pname and pname in fwd_of else [],
                          [anchor])
                fwd_of[layer.name] = anchor
                bwd_of[layer.name] = bwd_of.get(pname) if pname else None
            elif pname and pname in fwd_of:
                fwd_of[layer.name] = fwd_of[pname]
                bwd_of[layer.name] = bwd_of.get(pname)
            for o in layer.outputs:
                lay[o.guid] = od
                producer[o.guid] = layer.name
            continue

        # --- split op time into fwd / bwd pure compute + inherent comm
        op_comm = cand.extra_comm + cm.grad_sync_time(
            layer.weight_specs, cand.weight_dims, machine, batch_axes)
        # the measured path passes the BOUND METHOD MeasuredCost.op_time as
        # cost_fn (optimize.py) — recover the measurer through __self__ so
        # the independently timed fwd/bwd split is actually used
        measurer = getattr(getattr(cost_fn, "__self__", None), "op_times",
                           None) or getattr(cost_fn, "op_times", None)
        if measurer is not None:
            fwd_t, bwd_t = measurer(layer, cand)
        else:
            total = cost_fn(layer, cand) if cost_fn else cand.op_time(layer, machine)
            comp = max(0.0, total - op_comm)
            fwd_t, bwd_t = comp / 3.0, 2.0 * comp / 3.0

        fwd = SimTask(f"{layer.name}:fwd", "comp", "mxu", fwd_t)
        bwd = SimTask(f"{layer.name}:bwd", "comp", "mxu", bwd_t)
        tasks += [fwd, bwd]
        fwd.add_next(bwd)  # bwd additionally waits on consumers' bwd, below
        fwd_of[layer.name], bwd_of[layer.name] = fwd, bwd

        # --- input edges: reshard comm in fwd; reverse dependency in bwd
        for ii, tin in enumerate(layer.inputs):
            cur = lay.get(tin.guid)
            if cur is None:
                cur = _freeze_dims([None] * tin.spec.ndim)
            want = _freeze_dims(cand.in_dims[ii] if ii < len(cand.in_dims)
                                else [None] * tin.spec.ndim)
            pname = producer.get(tin.guid)
            src_fwd = [fwd_of[pname]] if pname and pname in fwd_of else []
            dur = cm.reshard_time(tin.spec, list(cur), list(want), machine)
            comm_task(f"{layer.name}:in{ii}", dur,
                      cm.shard_bytes(tin.spec, list(want), machine),
                      _link_of(_involved_axes(cur, want), machine),
                      src_fwd, [fwd])
            if pname and bwd_of.get(pname) is not None:
                bwd.add_next(bwd_of[pname])

        # --- inherent collective (tp_row all-reduce, ring hops, halos):
        # between this op's fwd and its consumers — consumers attach to the
        # *fwd* task; approximating the collective as the last stage, we
        # chain it after fwd and splice consumers after it via an anchor.
        if cand.extra_comm > 0:
            # candidate names encode the axis as the SECOND token
            # ("tp_row:model", "inter:model:3-1" — groups come after)
            link = "link:_"
            parts = cand.name.split(":")
            if len(parts) > 1 and machine.mesh_axes.get(parts[1], 1) > 1:
                link = f"link:{parts[1]}"
            anchor = SimTask(f"{layer.name}:coll-anchor", "comp", "mxu", 0.0)
            tasks.append(anchor)
            out_bytes = sum(cm.shard_bytes(o.spec, list(
                cand.out_dims[oi] if oi < len(cand.out_dims) else []), machine)
                for oi, o in enumerate(layer.outputs))
            comm_task(f"{layer.name}:coll", cand.extra_comm, out_bytes, link,
                      [fwd], [anchor])
            fwd_of[layer.name] = anchor  # consumers wait for the collective
            # the backward consumes the collective's product too (the loss
            # needs the full all-reduced output when this is the last layer)
            anchor.add_next(bwd)

        # --- gradient all-reduce per weight + optimizer update
        for w, spec in layer.weight_specs.items():
            dims = cand.weight_dims.get(w, [None] * spec.ndim)
            used = {a for d in dims for a in cm._axes_of(d)}
            replica_axes = tuple(a for a in batch_axes if a not in used)
            wbytes = cm.shard_bytes(spec, dims, machine)
            followers: List[SimTask] = []
            if include_update:
                # SGD/Adam update: HBM-bound elementwise, ~6 passes over the
                # shard (read w,g,m,v; write w,m,v) fused by XLA into one
                upd = SimTask(f"{layer.name}:{w}:update", "comp", "mxu",
                              6.0 * wbytes / machine.hbm_bw)
                tasks.append(upd)
                followers.append(upd)
            if replica_axes:
                dur = cm.all_reduce_time(wbytes, replica_axes, machine)
                comm_task(f"{layer.name}:{w}:gradsync", dur, wbytes,
                          _link_of(replica_axes, machine), [bwd], followers)
            else:
                for f in followers:
                    bwd.add_next(f)

        for oi, o in enumerate(layer.outputs):
            lay[o.guid] = _freeze_dims(
                cand.out_dims[oi] if oi < len(cand.out_dims)
                else [None] * o.spec.ndim)
            producer[o.guid] = layer.name

    return tasks


def replay(tasks: List[SimTask]) -> SimReport:
    """Reference simulate_runtime step 4-5 (simulator.cc:1369-1447): pop the
    earliest-ready task, bind it to its resource's timeline, propagate
    completion to dependents."""
    heap: List[Tuple[float, int, SimTask]] = []
    seq = 0
    for t in tasks:
        if t.counter == 0:
            heap.append((t.ready_time, seq, t))
            seq += 1
    heapq.heapify(heap)
    free: Dict[str, float] = {}
    busy: Dict[str, float] = {}
    makespan = 0.0
    done = 0
    while heap:
        _, _, cur = heapq.heappop(heap)
        start = max(free.get(cur.resource, 0.0), cur.ready_time)
        end = start + cur.duration
        free[cur.resource] = end
        busy[cur.resource] = busy.get(cur.resource, 0.0) + cur.duration
        cur.start, cur.end = start, end
        makespan = max(makespan, end)
        done += 1
        for nxt in cur.next_tasks:
            nxt.ready_time = max(nxt.ready_time, end)
            nxt.counter -= 1
            if nxt.counter == 0:
                heapq.heappush(heap, (nxt.ready_time, seq, nxt))
                seq += 1
    if done != len(tasks):
        raise RuntimeError(
            f"task graph deadlock: {len(tasks) - done} tasks never ready")
    return SimReport(makespan=makespan, tasks=tasks, resource_busy=busy)


def simulate_strategy(model, choices: Dict[str, Candidate],
                      machine: MachineSpec, cost_fn=None,
                      include_update: bool = True,
                      segment_bytes: int = DEFAULT_SEGMENT_BYTES) -> SimReport:
    tasks = build_step_tasks(model, choices, machine, cost_fn=cost_fn,
                             include_update=include_update,
                             segment_bytes=segment_bytes)
    return replay(tasks)


def rerank(model, machine: MachineSpec, results: Sequence,
           cost_fn=None, segment_bytes: int = DEFAULT_SEGMENT_BYTES):
    """Re-rank DP finalists by simulated makespan (the refinement pass the
    compile pipeline runs when simulator_mode='taskgraph'): the frontier DP's
    additive+overlap_frac costing prunes the space cheaply; the event-driven
    replay decides among the survivors. Returns (best_result, reports) with
    reports parallel to `results`."""
    reports = [simulate_strategy(model, r.choices, machine, cost_fn=cost_fn,
                                 segment_bytes=segment_bytes)
               for r in results]
    best = min(range(len(results)), key=lambda i: reports[i].makespan)
    return results[best], reports


# ----------------------------------------------------- pipeline validation
def simulate_pipeline(fwd_times: Sequence[float], bwd_times: Sequence[float],
                      schedule: str, num_micro: int,
                      p2p: float = 0.0) -> dict:
    """Event-driven replay of a pipeline schedule (the per-STAGE analog of
    replay()'s per-stream timelines): each stage is one serial resource,
    ops start at max(stage free, producer finish + p2p). Validates the
    schedule the cut-point search chose — every dependency edge is checked
    against the replayed event times (a schedule bug would surface as a
    consumer starting before its producer finished) — and returns the
    makespan / bubble the bench compares measured numbers against.

    Returns {"makespan", "bubble", "events"} with events keyed
    (phase, stage, microbatch) -> (start, end)."""
    span, events = cm.pipeline_timeline(schedule, num_micro,
                                        list(fwd_times), list(bwd_times),
                                        p2p=p2p)
    S = len(fwd_times)
    for (ph, s, m), (start, _end) in events.items():
        deps = []
        if ph == "F" and s > 0:
            deps.append(("F", s - 1, m))
        if ph == "B":
            deps.append(("F", s, m))
            if s < S - 1:
                deps.append(("B", s + 1, m))
        for d in deps:
            if events[d][1] > start + 1e-12:
                raise RuntimeError(
                    f"invalid pipeline schedule: {ph}(s={s}, m={m}) starts "
                    f"at {start} before its producer {d} ends at "
                    f"{events[d][1]}")
    return {
        "makespan": span,
        "bubble": cm.pipeline_bubble(schedule, num_micro, list(fwd_times),
                                     list(bwd_times), p2p=p2p),
        "events": events,
    }
