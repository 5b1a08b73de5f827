"""Fleet control plane: router, replica pools, rolling hot-swap (ISSUE 18).

The single-engine serving stack (PR 10-16) is one `ServingCompiled` driven
by one `ContinuousBatchingScheduler`. This module scales it out to N
in-process replicas sharing ONE policy brain:

- `admission.AdmissionControl` — the data plane's shed-or-queue class. A
  standalone scheduler owns one instance; `ServingFleet` uses the same
  class for fleet-level admission, so request policy is decided once, not
  per replica.
- `FleetRouter` — least-loaded / estimated-TTFT placement over the live
  per-replica signals the replica loop exports without syncs (queue
  depth, active slots, outstanding assignments, EMA prefill service
  time), with `SLOTracker` burn rates steering work away from a replica
  that is burning its error budget.
- Prefill/decode disaggregation (`topology="disagg"`) — dedicated
  prefill replicas run the compute-bound program only; committed KV
  pages travel to the decode pool over the host tier (the PR 16
  spill/prefetch buffers). The decode side adopts the payload as
  a parked slot, so rejoining is bitwise the spill path — disaggregated
  greedy streams equal colocated ones.
- `RollingSwapController` — the train->serve loop: a fine-tuning sibling
  commits durable snapshots into a watched root and the fleet rolls the
  swap ONE replica at a time, each flip at that replica's between-windows
  safe point (zero dropped requests fleet-wide by construction), with
  rollback + rollout freeze when a swapped replica's SLO burn rate
  crosses the ceiling.

Observability aggregates exactly: `StreamingHistogram`s share fixed
bucket edges so cross-replica merges are bucket-for-bucket identical to
pooling the samples, and `merge_slo_trackers` rebuilds the scoreboard a
single tracker would hold had it seen the union of terminal records
(both pinned in tests/test_fleet.py).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from flexflow_tpu import telemetry as tel
from flexflow_tpu.health import (SLOTracker, merge_slo_trackers,  # noqa: F401
                                 parse_slo, scaling_signal)
from flexflow_tpu.serving.admission import AdmissionControl
from flexflow_tpu.serving.reqtrace import (HIST_METRICS, StreamingHistogram,
                                           terminal_record)
from flexflow_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                            Request)

__all__ = [
    "FleetRouter", "ReplicaHandle",
    "RollingSwapController", "ServingFleet", "merge_histograms",
    "merge_slo_trackers",
]


# ------------------------------------------------------------ aggregation
def merge_histograms(hists) -> StreamingHistogram:
    """Exact cross-replica histogram merge: fixed shared bucket edges make
    the merged counts bucket-for-bucket identical to one histogram fed the
    pooled samples (pinned in tests)."""
    out = StreamingHistogram()
    for h in hists:
        out.merge(h)
    return out


# merge_slo_trackers moved to health.py (next to SLOTracker — the
# windowed-state-preserving merge is an SLO concern, not a fleet one);
# re-exported here so `from serving.fleet import merge_slo_trackers`
# keeps working.


# ------------------------------------------------------------------ feed
class _Feed:
    """Thread-safe arrival feed the fleet pump pushes into and a replica
    scheduler drains at the top of its loop (the scheduler duck-types
    `.closed` / `.drain()` — no import edge back into this module)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._items: List[Any] = []
        self.closed = False

    def push(self, item: Any) -> None:
        with self._lock:
            self._items.append(item)

    def drain(self) -> List[Any]:
        if not self._items:
            return []
        with self._lock:
            out, self._items = self._items, []
        return out

    @property
    def exhausted(self) -> bool:
        """True once nothing more can ever arrive: closed AND drained.
        The scheduler loops on this, not on `closed` — a close racing a
        push must not strand the pushed item."""
        return self.closed and not self._items

    def close(self) -> None:
        self.closed = True


# -------------------------------------------------------- shared runtime
class _LockedKV:
    """Per-replica KV pool with its device-launching methods serialized
    under the fleet's shared-runtime lock (see _SharedRuntimeEngine);
    host-side bookkeeping (admit/evict/free_slots/...) stays lock-free —
    the pools themselves are replica-private."""

    _DEVICE_CALLS = frozenset((
        "push", "commit_prefill", "spill", "prefetch", "join",
        "sync_after", "export_parked", "import_parked"))

    def __init__(self, kv: Any, lock: threading.Lock):
        object.__setattr__(self, "_kv", kv)
        object.__setattr__(self, "_lock", lock)

    def __getattr__(self, name):
        val = getattr(self._kv, name)
        if name in self._DEVICE_CALLS and callable(val):
            lock = self._lock

            def locked(*a, __val=val, **kw):
                with lock:
                    out = __val(*a, **kw)
                    # run-to-completion: no async tail may escape the lock
                    jax.block_until_ready(self._kv.state)
                    return out
            return locked
        return val

    def __setattr__(self, name, value):
        setattr(self._kv, name, value)


class _SharedRuntimeEngine:
    """In-process replicas share ONE XLA runtime over the same (virtual)
    device set, and its cross-device collectives rendezvous by device: two
    replicas' programs interleaving their rendezvous deadlock the backend.
    This proxy serializes compiled-program execution under one fleet-wide
    lock, run-to-completion (`block_until_ready` inside the lock, so no
    async tail escapes it), and paces the optional simulated device-step
    floor on a PER-REPLICA virtual device timeline: every floored call
    reserves `step_floor_s` of device occupancy starting no earlier than
    the previous reservation's end, and the caller sleeps (outside the
    lock) until its reservation elapses. Host-side scheduler work between
    steps eats into the next sleep's slack instead of adding to the
    chain — exactly how a pipelined accelerator overlaps host dispatch
    with device execution — and the sleeps of different replicas overlap
    as dedicated per-replica devices would. Replicas on disjoint real
    slices (process-per-replica) don't need this and don't get it: the
    fleet only installs the proxy for in-process multi-replica serving."""

    _DEVICE_CALLS = frozenset((
        "prefill", "prefill_first_tokens", "decode_step", "spec_round_step",
        "verify_step", "poll_swap", "hot_swap", "rollback", "load_params"))
    _FLOORED = frozenset((
        "prefill", "prefill_first_tokens", "decode_step", "spec_round_step",
        "verify_step"))

    def __init__(self, eng: Any, lock: threading.Lock,
                 step_floor_s: float = 0.0):
        self._eng = eng
        self._lock = lock
        self._floor = float(step_floor_s or 0.0)
        self._device_free = 0.0   # this replica's virtual device timeline
        self._kv: Optional[_LockedKV] = None

    def __getattr__(self, name):
        if name == "kv":
            if self._kv is None:
                self._kv = _LockedKV(self._eng.kv, self._lock)
            return self._kv
        val = getattr(self._eng, name)
        if name not in self._DEVICE_CALLS or not callable(val):
            return val
        floor = self._floor if name in self._FLOORED else 0.0
        lock = self._lock

        def locked(*a, __val=val, __floor=floor, **kw):
            t0 = time.perf_counter()
            with lock:
                out = __val(*a, **kw)
                jax.block_until_ready(out)
            if __floor:
                # reserve a floor-length occupancy slot on this replica's
                # virtual device and surface the result when it elapses
                self._device_free = max(self._device_free, t0) + __floor
                pause = self._device_free - time.perf_counter()
                if pause > 0:
                    time.sleep(pause)
            return out
        return locked


# ---------------------------------------------------------------- replicas
@dataclasses.dataclass
class ReplicaHandle:
    """One replica: an engine, its scheduler (built per serve), its feed,
    and the live load signals the router reads (plain ints/list lengths —
    safe to read cross-thread without locks)."""

    index: int
    engine: Any
    role: str = "mixed"      # "mixed" | "prefill" | "decode"
    sched: Optional[ContinuousBatchingScheduler] = None
    feed: Optional[_Feed] = None
    thread: Optional[threading.Thread] = None
    assigned: int = 0

    @property
    def finished(self) -> int:
        s = self.sched
        if s is None:
            return 0
        return (len(s.completed) + len(s.shed) + len(s.failed)
                + s.handoffs)

    @property
    def outstanding(self) -> int:
        return max(0, self.assigned - self.finished)

    def worst_burn(self) -> float:
        slo = getattr(self.engine, "slo", None)
        if slo is None or not slo.objectives:
            return 0.0
        burn = slo.report().get("worst_burn_rate")
        return float(burn) if burn is not None else 0.0


class FleetRouter:
    """Placement over live replica signals. `least_loaded` minimizes
    (outstanding work, estimated TTFT); the estimated TTFT is queue depth
    x the replica's EMA prefill service time — the same estimator the
    TTFT-budget shed uses, so routing and shedding price a queue the same
    way. With a burn ceiling set, a replica whose SLO worst burn rate
    crossed it only receives work when every alternative crossed too."""

    def __init__(self, policy: str = "least_loaded",
                 burn_max: float = 0.0):
        if policy not in ("least_loaded", "round_robin"):
            raise ValueError(f"unknown router policy {policy!r}")
        self.policy = policy
        self.burn_max = float(burn_max or 0.0)
        self._rr = 0

    def estimated_ttft_s(self, h: ReplicaHandle) -> float:
        s = h.sched
        ema_s = ((getattr(s, "_ema_serve_ms", 0.0) or 50.0) / 1e3
                 if s is not None else 0.05)
        depth = getattr(s, "queue_depth", 0) if s is not None else 0
        return (1.0 + depth) * ema_s

    def pick(self, handles: List[ReplicaHandle]) -> ReplicaHandle:
        if not handles:
            raise ValueError("router: empty replica pool")
        if self.policy == "round_robin":
            h = handles[self._rr % len(handles)]
            self._rr += 1
            return h
        return min(handles, key=lambda h: (
            (h.worst_burn() > self.burn_max) if self.burn_max else False,
            h.outstanding, self.estimated_ttft_s(h), h.index))


# ------------------------------------------------------------ rolling swap
class _ReplicaControl:
    """Per-replica view of the rolling controller, installed as
    `scheduler.control` — the scheduler calls it at its between-windows
    safe point instead of polling the engine directly."""

    __slots__ = ("_ctl", "_idx")

    def __init__(self, ctl: "RollingSwapController", idx: int):
        self._ctl = ctl
        self._idx = idx

    def at_safe_point(self, sched) -> bool:
        return self._ctl.at_safe_point(self._idx, sched)


class RollingSwapController:
    """Rolls a new snapshot across the fleet ONE replica at a time:
    replica k may advance only after replicas 0..k-1 took it, and every
    flip happens at that replica's between-windows safe point (the engine
    hot-swap pointer flip) — zero dropped requests fleet-wide by
    construction. A swapped replica whose SLO worst burn rate exceeds
    `burn_max` is rolled back to its previous pinned version and the
    rollout FREEZES, so a bad model stops at one replica instead of
    deploying itself fleet-wide."""

    def __init__(self, engines: List[Any], burn_max: float = 0.0):
        self.engines = list(engines)
        self.burn_max = float(burn_max or 0.0)
        self._lock = threading.Lock()
        self._cursor = 0
        self.halted = False
        self.swaps: List[Tuple[int, Optional[int]]] = []
        self.rollbacks: List[Tuple[int, Optional[int]]] = []

    def control(self, idx: int) -> _ReplicaControl:
        return _ReplicaControl(self, idx)

    def _burned(self, eng) -> bool:
        slo = getattr(eng, "slo", None)
        if not self.burn_max or slo is None or not slo.objectives:
            return False
        burn = slo.report().get("worst_burn_rate")
        return burn is not None and burn > self.burn_max

    def at_safe_point(self, idx: int, sched=None) -> bool:
        """Called by replica `idx` between dispatch windows. Returns True
        iff the replica's live params changed (swap OR rollback) — the
        scheduler then refreshes its param handle."""
        with self._lock:
            eng = self.engines[idx]
            swapped = any(r == idx for r, _ in self.swaps)
            rolled = any(r == idx for r, _ in self.rollbacks)
            if swapped and not rolled and self._burned(eng):
                try:
                    eng.rollback()
                except Exception:  # noqa: BLE001 — nothing retained to re-pin
                    return False
                self.halted = True
                ver = getattr(eng, "active_version", None)
                self.rollbacks.append((idx, ver))
                tel.event("serve/fleet_rollout", cat="serve", replica=idx,
                          action="rollback", version=ver)
                return True
            if self.halted or idx != self._cursor % len(self.engines):
                return False
            if not getattr(eng, "watching", False):
                return False
            if not eng.poll_swap():
                return False
            self._cursor += 1
            ver = getattr(eng, "active_version", None)
            self.swaps.append((idx, ver))
            tel.event("serve/fleet_rollout", cat="serve", replica=idx,
                      action="swap", version=ver)
            return True


# ------------------------------------------------------------------ fleet
class ServingFleet:
    """N replica engines behind one admission brain, one router, and one
    rollout controller. `serve(requests)` runs the open-loop trace across
    the fleet and returns the completed requests; `self.shed`/`self.failed`
    /`self.stats` mirror the scheduler's fields fleet-wide.

    With ONE replica and colocated topology, `serve` degenerates to the
    plain pre-fleet scheduler — same code path, no feed, no pump threads —
    so single-replica serving is behaviorally identical to PR 16 (pinned
    in tests). Engines must be homogeneous (same compiled twin); disagg
    topology needs every replica built with `--kv-host-pages > 0` (the
    handoff travels through the host tier on both sides)."""

    def __init__(self, engines: List[Any], prompt_inputs_fn: Callable,
                 step_inputs_fn: Callable, eos_id: Optional[int] = None,
                 topology: Optional[str] = None,
                 prefill_replicas: Optional[int] = None,
                 router: Any = None,
                 rollout_burn_max: Optional[float] = None,
                 step_floor_s: float = 0.0,
                 **sched_kwargs: Any):
        if not engines:
            raise ValueError("ServingFleet needs at least one engine")
        self.engines = list(engines)
        cfg = self.engines[0].cfg
        self.prompt_inputs_fn = prompt_inputs_fn
        self.step_inputs_fn = step_inputs_fn
        self.eos_id = eos_id
        self.sched_kwargs = dict(sched_kwargs)
        self.topology = (topology if topology is not None else
                         getattr(cfg, "serve_fleet_topology", "colocated")
                         ) or "colocated"
        if self.topology not in ("colocated", "disagg"):
            raise ValueError(f"unknown fleet topology {self.topology!r}")
        if isinstance(router, FleetRouter):
            self.router = router
        else:
            policy = (router or getattr(cfg, "serve_router", "least_loaded")
                      or "least_loaded")
            self.router = FleetRouter(policy)
        self.rollout_burn_max = float(
            rollout_burn_max if rollout_burn_max is not None
            else getattr(cfg, "serve_rollout_burn_max", 0.0) or 0.0)
        # simulated per-replica device-step latency floor (multi-replica
        # only; see _SharedRuntimeEngine) — 0 = no pacing
        self.step_floor_s = float(step_floor_s or 0.0)
        n = len(self.engines)
        if self.topology == "disagg":
            if n < 2:
                raise ValueError("disagg topology needs >= 2 replicas "
                                 "(one prefill + one decode minimum)")
            n_pre = int(prefill_replicas if prefill_replicas is not None
                        else getattr(cfg, "serve_prefill_replicas", 1) or 1)
            n_pre = max(1, min(n_pre, n - 1))
            roles = ["prefill"] * n_pre + ["decode"] * (n - n_pre)
            for eng in self.engines:
                if not getattr(eng.kv, "host_pages", 0):
                    raise ValueError(
                        "disagg topology: every replica needs "
                        "--kv-host-pages > 0 (the KV handoff travels "
                        "through the host tier)")
        else:
            roles = ["mixed"] * n
        self.replicas = [ReplicaHandle(i, eng, roles[i])
                         for i, eng in enumerate(self.engines)]
        # fleet-level admission: permanent sheds are decided ONCE here,
        # before routing — the same policy class the replica loop uses
        eng0 = self.engines[0]
        seq = int(eng0.prefill_model.input_tensors[0].spec.shape[1])
        dispatch_ahead = max(1, int(self.sched_kwargs.get(
            "dispatch_ahead", 4)))
        spec_tokens = int(getattr(eng0, "spec_tokens", 0) or 0)
        self.admission = AdmissionControl(
            seq=seq,
            max_context=int(getattr(cfg, "serve_max_context", 0) or 0),
            overhead_tokens=dispatch_ahead + spec_tokens,
            pages_needed=eng0.kv.pages_needed,
            capacity_pages=eng0.kv.capacity_pages)
        self.slo = SLOTracker(parse_slo(getattr(cfg, "serve_slo", "")
                                        or ""))
        # --serve-trace-out (ISSUE 20): the fleet exports ONE pool-wide
        # replayable trace of the offered load; replica schedulers have
        # their per-replica export cleared in _build_sched.
        self.trace_out = str(getattr(cfg, "serve_trace_out", "") or "")
        self.rolling: Optional[RollingSwapController] = None
        self.completed: List[Request] = []
        self.shed: List[Request] = []
        self.failed: List[Request] = []
        self.stats: Dict[str, Any] = {}
        self._shed_fleet: List[Request] = []
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()

    # ----------------------------------------------------------- plumbing
    def _build_sched(self, h: ReplicaHandle,
                     handoff: Optional[Callable] = None,
                     engine: Any = None) -> ContinuousBatchingScheduler:
        eng = engine if engine is not None else h.engine
        sched = ContinuousBatchingScheduler(
            eng, eng.params, self.prompt_inputs_fn,
            self.step_inputs_fn, eos_id=self.eos_id, handoff=handoff,
            **self.sched_kwargs)
        if len(self.replicas) > 1:
            # one trace for the pool (serve() exports it), not N partials
            sched.trace_out = ""
        h.sched = sched
        return sched

    def _route_handoff(self, req: Request, payload: Dict) -> None:
        """Called from a prefill replica's thread: deliver the committed
        KV payload to the least-loaded decode replica's feed."""
        pool = [x for x in self.replicas if x.role == "decode"]
        with self._lock:
            h = self.router.pick(pool)
            h.assigned += 1
        h.feed.push((req, payload))

    def _fleet_shed(self, req: Request, reason: str, now_s: float) -> None:
        req.outcome = "shed"
        req.shed_reason = reason
        req.finish_s = now_s
        self._shed_fleet.append(req)
        rec = terminal_record(req, now_s, 0, reason)
        self.slo.observe(rec)
        tel.event("serve/request_shed", cat="serve", reason=reason,
                  fleet=True, **rec)

    # --------------------------------------------------------------- serve
    def serve(self, requests: List[Request],
              watch_root: Optional[str] = None,
              poll_interval_s: float = 0.05) -> List[Request]:
        """Serve the open-loop trace (arrival_s offsets) across the fleet;
        returns the completed requests fleet-wide. `watch_root` arms the
        rolling train->serve loop: every replica watches the durable-
        snapshot root and the RollingSwapController advances them one at
        a time."""
        self.completed, self.shed, self.failed = [], [], []
        self._shed_fleet = []
        for h in self.replicas:
            h.assigned = 0
        if watch_root is not None:
            for h in self.replicas:
                h.engine.watch(watch_root, poll_interval_s=poll_interval_s)
        self._t0 = time.perf_counter()
        if len(self.replicas) == 1 and self.topology == "colocated" \
                and not self.step_floor_s:
            # the single-replica path IS the pre-fleet scheduler: no feed,
            # no pump, no control — pinned behaviorally identical in tests.
            # (A step floor forces the threaded path even at one replica,
            # so paced scaling baselines pace the baseline too.)
            h = self.replicas[0]
            sched = self._build_sched(h)
            h.assigned = len(requests)
            sched.run(list(requests))
            self._collect()
            return list(self.completed)
        # in-process replicas share one XLA runtime: serialize program
        # execution under a fleet-wide lock (deadlock-free collectives),
        # pay the simulated device-step floor outside it
        run_lock = threading.RLock()
        proxies = [_SharedRuntimeEngine(h.engine, run_lock,
                                        self.step_floor_s)
                   for h in self.replicas]
        self.rolling = (RollingSwapController(
            proxies, burn_max=self.rollout_burn_max)
            if watch_root is not None else None)
        prefill_pool = [h for h in self.replicas if h.role != "decode"]
        decode_pool = [h for h in self.replicas if h.role != "prefill"]
        for h, proxy in zip(self.replicas, proxies):
            handoff = self._route_handoff if h.role == "prefill" else None
            sched = self._build_sched(h, handoff=handoff, engine=proxy)
            sched.exec_lock = run_lock
            sched._exec_serialized = True
            h.feed = _Feed()
            sched.feed = h.feed
            if self.rolling is not None:
                sched.control = self.rolling.control(h.index)
            h.thread = threading.Thread(
                target=sched.run, args=([],),
                name=f"fleet-replica-{h.index}", daemon=True)
        for h in self.replicas:
            h.thread.start()
        # the pump: fleet admission + routing at each request's arrival
        for req in sorted(requests, key=lambda r: (r.arrival_s, r.rid)):
            delay = self._t0 + req.arrival_s - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            now = time.perf_counter() - self._t0
            reason = self.admission.permanent_shed_reason(req)
            if reason is not None:
                self._fleet_shed(req, reason, now)
                continue
            with self._lock:
                h = self.router.pick(prefill_pool)
                h.assigned += 1
            h.feed.push(req)
        # drain prefill replicas first: their handoffs feed the decode pool
        if self.topology == "disagg":
            for h in prefill_pool:
                h.feed.close()
            for h in prefill_pool:
                h.thread.join()
            for h in decode_pool:
                h.feed.close()
            for h in decode_pool:
                h.thread.join()
        else:
            for h in self.replicas:
                h.feed.close()
            for h in self.replicas:
                h.thread.join()
        self._collect()
        if self.trace_out and requests:
            from flexflow_tpu.serving import tracefmt
            tracefmt.save_trace(
                self.trace_out,
                tracefmt.requests_to_records(
                    sorted(requests, key=lambda r: (r.arrival_s, r.rid))),
                meta={"source": "fleet", "replicas": len(self.replicas),
                      "topology": self.topology})
        return list(self.completed)

    # ------------------------------------------------------------- results
    def _collect(self) -> None:
        wall = max(1e-9, time.perf_counter() - self._t0)
        self.completed = []
        self.shed = list(self._shed_fleet)
        self.failed = []
        per: List[Dict[str, Any]] = []
        handoffs = swaps = 0
        for h in self.replicas:
            s = h.sched
            if s is None:
                continue
            self.completed.extend(s.completed)
            self.shed.extend(s.shed)
            self.failed.extend(s.failed)
            handoffs += s.handoffs
            swaps += s.stats.get("swaps", 0)
            toks = sum(len(r.tokens) for r in s.completed)
            row = {"replica": h.index, "role": h.role,
                   "assigned": h.assigned, "completed": len(s.completed),
                   "shed": len(s.shed), "failed": len(s.failed),
                   "handoffs": s.handoffs, "tokens_out": toks,
                   "tokens_per_s": toks / wall,
                   "queue_depth": s.queue_depth,
                   "active_slots": s.active_count,
                   "swaps": s.stats.get("swaps", 0),
                   "swap_version": getattr(h.engine, "active_version",
                                           None)}
            per.append(row)
            tel.event("serve/fleet_replica", cat="serve", **row)
        self.completed.sort(key=lambda r: r.rid)
        total_toks = sum(len(r.tokens) for r in self.completed)
        self.stats = {
            "replicas": len(self.replicas), "topology": self.topology,
            "completed": len(self.completed), "shed": len(self.shed),
            "failed": len(self.failed), "handoffs": handoffs,
            "swaps": swaps, "tokens_out": total_toks,
            "tokens_per_s": total_toks / wall, "wall_s": wall,
            "per_replica": per,
        }
        if self.rolling is not None:
            self.stats["rollout_swaps"] = len(self.rolling.swaps)
            self.stats["rollout_rollbacks"] = len(self.rolling.rollbacks)
            self.stats["rollout_halted"] = self.rolling.halted
        tel.event("serve/fleet", cat="serve",
                  **{k: v for k, v in self.stats.items()
                     if k != "per_replica"})

    def report(self) -> Dict[str, Any]:
        """Fleet-wide observability: exact cross-replica histogram merges
        (fixed edges) + the SLO scoreboard of a virtual single tracker fed
        the union of every replica's terminal records."""
        hists: Dict[str, Any] = {}
        for m in HIST_METRICS:
            hs = [h.sched.tracer.hists[m] for h in self.replicas
                  if h.sched is not None and h.sched.tracer is not None]
            hs = [h for h in hs if h.count]
            if hs:
                merged = merge_histograms(hs)
                hists[m] = {"count": merged.count,
                            "mean": merged.mean(),
                            "p50": merged.quantile(0.5),
                            "p99": merged.quantile(0.99)}
        trackers = [getattr(h.engine, "slo", None) for h in self.replicas]
        merged_slo = merge_slo_trackers(trackers + [self.slo])
        slo_report = merged_slo.report()
        return {"stats": dict(self.stats), "hists": hists,
                "slo": slo_report,
                # ROADMAP item 5: the burn-rate policy's recommendation
                # rides every fleet report (the router-driven autoscaler's
                # input signal)
                "scaling": scaling_signal(slo_report)}
