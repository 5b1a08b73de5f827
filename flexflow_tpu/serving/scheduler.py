"""Continuous-batching scheduler over the two serving programs.

Policy (the vLLM-style loop, on PR 2's async-dispatch discipline):

- ADMISSION: at every sync point, waiting requests are placed into free
  decode slots (page allocation permitting — a short free list is
  backpressure, the request stays queued). Admitted prompts are right-
  padded into the `[slots, S]` prefill batch at their slot's row, run
  through the prefill program once ("prefill-then-join"), their K/V
  committed into the paged cache, and their first token recorded as
  time-to-first-token. The program takes it itself (argmax at each slot's
  last real position, `engine.prefill_first_tokens`): `slots` int32 reach
  the host, never the `[slots, S, vocab]` logits.
- DECODE: in steady decode the chip always has a dispatched step queued.
  The loop keeps `OVERLAP_DEPTH` single-token steps in flight (two: one
  running, one queued; never more than `dispatch_ahead`) — each step's
  argmax feeds the next step as a device array, so the token chain never
  leaves the device — and once that many are out it materializes the
  OLDEST one (one `jax.device_get` of its tokens and its counters) while
  the newer run, dispatches the replacement at once, and only then
  commits the tokens it pulled on the host. The steps in
  flight are ALL materialized (a drain: the pipeline runs empty) only where
  the host mirrors are about to be published to the device or a decision
  needs every token: a finish, an admission that can be placed, tier
  rotation, a hand-off, a fault. The steps in flight count against the
  smallest remaining token budget across active slots, so the pipeline
  runs empty exactly at a max-len finish and never decodes past it; an
  EOS finish is seen one sync late, masked out of the committed KV
  advance (`sync_after(advances=...)`), counted as `overdecode_tokens`,
  and evicted after the drain that follows. A scheduler built with a
  feature that needs a safe point every window (hot swap or a fleet's
  swap control, a fleet's feed and serialized execution, the host tier,
  the prefill-only hand-off, the decode watchdog) drains whenever the
  window is full, as every scheduler did before. `sched.stats` counts
  `overlapped_syncs` (a step or more stayed in flight behind the sync),
  `drains` and `drains_by_reason`.
- EVICTION: at drains, slots whose sequence hit EOS or max-new are
  evicted (pages freed). The decode attention routes any out-of-range
  write to the scratch page, so over-decode can never corrupt a
  neighbour.

SLO-aware admission & graceful degradation (ISSUE 11):

- Requests carry a `priority` class (lower = more urgent; ties broken by
  arrival) and an optional `deadline_s` TTFT deadline. The waiting queue
  is served priority-first.
- SHED-OR-QUEUE at admit: with `--serve-queue-cap` set, an arrival into
  a full queue sheds the lowest-priority waiter (or the arrival itself
  if nothing waiting is less urgent). With `--serve-ttft-budget-ms` set,
  a waiter whose elapsed wait plus the EMA prefill service time can no
  longer make the budget is shed instead of serving a dead-on-arrival
  response. Deadline-expired waiters shed the same way. Prompts longer
  than the prefill window are shed as `prompt_too_long` (they can never
  be admitted), and `KVPoolExhausted` from a lost admission race keeps
  the request queued (backpressure, not an error).
- CHUNKED-PREFILL admission: `prefill_chunk_tokens` caps the summed
  prompt length of one admission wave, so a burst of long prompts
  spreads over several prefill batches instead of monopolizing the
  engine while decode slots starve.
- CHUNKED PREFILL (an engine compiled with `--serve-prefill-chunk`): a
  placed request's prompt goes into its slot's pages a chunk at a time
  (`engine.prefill_chunk`: one request a call, the oldest first, the
  chunk attending over what its slot has cached and over itself), one chunk a turn at a drained point, and a decode step of the
  live slots between two chunks. A slot is `prefilling` (it owns its pages,
  the device does not see them: kv_cache.py) until its last chunk yields the
  request's first token and `kv.activate` joins it to the decode steps. Each
  chunk is one `serve/admit` span (`requests`, `prompt_tokens` the chunk's
  real tokens, `padded_tokens` the program's, `requests_started` those
  of them at their first chunk, `chunk_index`, `chunks_of_request`,
  `context_before`) with `serve/prefill/dispatch`,
  `/commit` (nothing to move: the chunk wrote its pages) and `/device_wait`
  inside; `admit_s` is the first chunk's dispatch, `ttft_s` the last
  chunk's token. A prompt longer than `seq - max_decode_len` is shed as
  `prompt_too_long`.
- WATCHDOG: with `--serve-decode-timeout-ms` set, a dispatched window
  whose per-step materialization exceeds the budget evicts the longest-
  resident slot (outcome "timeout") instead of stalling the whole batch.

Fault wrapping (ISSUE 11): prefill dispatch, KV admission, and decode
dispatch run under `run_resilient` with the serving retry policy — a
transient `serve/prefill` / `serve/kv_admit` / `serve/decode_step` fault
costs a retry (telemetry `retry` events); a permanent one fails ONLY the
affected request(s): a kv_admit escalation sheds that request, a prefill
escalation fails the batch being admitted, a decode escalation evicts
the wedged slot — the engine keeps serving in every case.

Hot-swap integration: when the engine `watch()`es a checkpoint root, the
loop calls `engine.poll_swap()` only while the dispatched window is
empty — the swap's pointer flip happens BETWEEN decode steps, with no
in-flight dispatch referencing the retiring param tree.

Fleet integration (ISSUE 18): this class is the REPLICA-LOCAL decode
loop. The admission policy brain (shed-or-queue, queue-cap displacement,
staleness sweeps) lives in `admission.AdmissionControl` — one instance here
for standalone use, the same class at fleet level for cross-replica
admission — and three hooks let `fleet.ServingFleet` drive N loops:
`self.feed` (a thread-safe arrival feed replacing the static trace),
`self.control` (swap orchestration at the between-windows safe point,
replacing the local `poll_swap` call), and the `handoff` callback
(prefill-only mode: admitted slots are spilled, exported, and handed to
the decode pool right after their TTFT materialization). All three
default to off, and every fleet branch is guarded on them — a standalone
scheduler is bitwise the pre-fleet loop.

Model specifics stay out of the loop: `prompt_inputs_fn` and
`step_inputs_fn` adapt token ids + cache state to the model's input list
(gpt2 adapters below; the generic transformer feeds embeddings directly
and drives the engine without this scheduler).
"""

from __future__ import annotations

import dataclasses
import gc
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu import telemetry as tel
from flexflow_tpu.ops.registry import STATS_KEY
from flexflow_tpu.runtime.resilience import RetryPolicy, run_resilient
from flexflow_tpu.serving.admission import AdmissionControl, _urgency
from flexflow_tpu.serving.kv_cache import (ACTIVE_KEY, KVPoolExhausted,
                                           POS_KEY)
from flexflow_tpu.serving.reqtrace import RequestTracer, terminal_record


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    arrival_s: float = 0.0        # offset from scheduler start (open loop)
    priority: int = 1             # SLO class, lower = more urgent
    deadline_s: Optional[float] = None  # TTFT deadline (relative to arrival)
    # filled by the scheduler:
    tokens: List[int] = dataclasses.field(default_factory=list)
    ttft_s: Optional[float] = None
    admit_s: Optional[float] = None  # prefill-dispatch time (queue-wait end)
    finish_s: Optional[float] = None
    slot: Optional[int] = None
    outcome: str = ""             # "done" | "shed" | "failed" | "timeout"
    shed_reason: str = ""


def gpt2_prompt_inputs(ids: np.ndarray, lengths: np.ndarray) -> List[np.ndarray]:
    """gpt2 prefill inputs: token ids + positions 0..S-1."""
    pos = np.broadcast_to(np.arange(ids.shape[1], dtype=np.int32), ids.shape)
    return [ids.astype(np.int32), np.ascontiguousarray(pos)]


def gpt2_step_inputs(tokens, state) -> List[Any]:
    """gpt2 decode inputs: next token ids + the device-side positions (the
    index each slot's token is written at — no host sync to build them).
    Generalizes to multi-token steps (`tokens` shaped [slots, s] for the
    speculative-verify pass): token i of a slot sits at position pos+i."""
    pos = state[POS_KEY][:, None]
    s = int(tokens.shape[1])
    if s > 1:
        pos = pos + jnp.arange(s, dtype=state[POS_KEY].dtype)[None, :]
    return [tokens, pos]


def valid_prompt_inputs(ids: np.ndarray, lengths: np.ndarray,
                        offsets=None) -> List[np.ndarray]:
    """Prefill inputs of a model without positions (`input_ids`, `valid`):
    token ids, and which positions of the padded wave hold a prompt token.
    Layers whose state depends on where a row ends (a state-space mixer)
    read it from `valid`. `offsets` (where a prefill chunk starts in its
    sequence) is the chunk scheduler's argument; nothing here has a use for
    it: the slot's cache and state are where a chunk's past is."""
    valid = np.arange(ids.shape[1])[None, :] < np.asarray(lengths)[:, None]
    return [ids.astype(np.int32), valid.astype(np.int32)]


def valid_step_inputs(tokens, state) -> List[Any]:
    """Decode inputs of such a model: the next token of every slot, and
    which slots are live (device-side, from the cache state: no host sync),
    so that a step advances the recurrent state of live slots only."""
    live = state[ACTIVE_KEY].astype(jnp.int32)[:, None]
    return [tokens, jnp.broadcast_to(live, tokens.shape)]


def positions_valid_prompt_inputs(ids: np.ndarray, lengths: np.ndarray,
                                 offsets=None) -> List[np.ndarray]:
    """Prefill inputs of a model with rotary positions whose layers are also
    told which positions exist (`input_ids`, `positions`, `valid`).
    `offsets` `[rows]`: where each row's block starts in its sequence (a
    prefill chunk's context)."""
    ids, pos = gpt2_prompt_inputs(ids, lengths)
    if offsets is not None:
        pos = pos + np.asarray(offsets, np.int32)[:, None]
    return [ids, pos, valid_prompt_inputs(ids, lengths)[1]]


def positions_valid_step_inputs(tokens, state) -> List[Any]:
    """Decode inputs of such a model: the next tokens, the device-side
    position each is written at, and which slots are live."""
    tokens, pos = gpt2_step_inputs(tokens, state)
    return [tokens, pos, valid_step_inputs(tokens, state)[1]]


def positions3_valid_prompt_inputs(ids: np.ndarray, lengths: np.ndarray,
                                  offsets=None) -> List[np.ndarray]:
    """Prompt inputs of a model with three-axis rotary positions
    (`input_ids`, `positions` `[rows, s, 3]`, `valid`): text, whose time,
    height and width positions are one number. `offsets` `[rows]`: where
    each row's block starts in its sequence (a prefill chunk's context)."""
    ids, pos, valid = positions_valid_prompt_inputs(ids, lengths, offsets)
    return [ids, np.ascontiguousarray(np.repeat(pos[..., None], 3, axis=-1)),
            valid]


def positions3_valid_step_inputs(tokens, state) -> List[Any]:
    """Decode inputs of such a model: a generated token is text."""
    tokens, pos, valid = positions_valid_step_inputs(tokens, state)
    return [tokens, jnp.repeat(pos[..., None], 3, axis=-1), valid]


def _stat_totals(stats: List[Any]) -> Dict[str, float]:
    """The programs' own counters (one dict of scalars per decode step or
    prefill wave, as it came out under STATS_KEY and was brought to the
    host with the tokens it rode beside), summed per name."""
    totals: Dict[str, float] = {}
    for step in stats:
        for name, value in (step or {}).items():
            totals[name] = totals.get(name, 0) + value.item()
    return totals


@jax.jit
def _greedy_tokens(logits):
    """Each slot's next token `[slots, 1]` from a step's logits, as ONE
    launch. Written eagerly (`logits[:, -1, :]`, argmax, cast, new axis)
    this was four or five programs a step and 3.7 ms of the host's time
    on the chip's machine, where the whole decode launch is 1.0 (PERF.md,
    Findings PR 45): more than the device needs for some models' step, so
    no ordering of the loop could keep the chip fed."""
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]


# Steps the overlapped decode loop keeps in flight: one running and one
# queued behind it, which feeds the chip as long as the host turns a step
# round faster than the device runs one (2.1-2.4 ms against 4-12 in the
# benchmark's cells). Measured, not derived (PERF.md, Findings PR 45): with
# four in flight every cell idled as much as with two, `ttft_p95_ms` rose
# 3-12 % (an arriving request waits out every step in flight before its
# wave) and 3 of 20 runs paused 1.2-3 s for a cause not yet found.
OVERLAP_DEPTH = 2


class ContinuousBatchingScheduler:
    def __init__(self, engine, params, prompt_inputs_fn: Callable,
                 step_inputs_fn: Callable, eos_id: Optional[int] = None,
                 dispatch_ahead: int = 4,  # most steps in flight: `_depth`
                 ttft_budget_ms: Optional[float] = None,
                 queue_cap: Optional[int] = None,
                 decode_timeout_ms: Optional[float] = None,
                 prefill_chunk_tokens: int = 0,
                 retry_policy: Optional[RetryPolicy] = None,
                 reqtrace: Optional[bool] = None,
                 handoff: Optional[Callable] = None):
        self.engine = engine
        self.params = params
        self.prompt_inputs_fn = prompt_inputs_fn
        self.step_inputs_fn = step_inputs_fn
        self.eos_id = eos_id
        self.dispatch_ahead = max(1, int(dispatch_ahead))
        self.kv = engine.kv
        self.slots = engine.slots
        self.seq = int(engine.prefill_model.input_tensors[0].spec.shape[1])
        cfg = engine.cfg
        self.ttft_budget_ms = float(
            ttft_budget_ms if ttft_budget_ms is not None
            else getattr(cfg, "serve_ttft_budget_ms", 0.0))
        self.queue_cap = int(queue_cap if queue_cap is not None
                             else getattr(cfg, "serve_queue_cap", 0))
        self.decode_timeout_ms = float(
            decode_timeout_ms if decode_timeout_ms is not None
            else getattr(cfg, "serve_decode_timeout_ms", 0.0))
        self.prefill_chunk_tokens = max(0, int(prefill_chunk_tokens))
        self.retry_policy = (retry_policy if retry_policy is not None
                             else RetryPolicy.from_config(cfg))
        self.completed: List[Request] = []
        self.shed: List[Request] = []
        self.failed: List[Request] = []
        # speculative decoding: when the engine carries a draft, every
        # round drafts K tokens and verifies them in one target pass; the
        # draft's paged cache mirrors every admit/advance/evict
        self.spec_tokens = int(getattr(engine, "spec_tokens", 0) or 0)
        self.draft = getattr(engine, "draft", None)
        self._spec = self.spec_tokens > 0 and self.draft is not None
        if self._spec and self.draft.params is None:
            raise ValueError(
                "speculative scheduler: draft engine has no params (call "
                "engine.draft.init() or engine.draft.load_params first)")
        self._spec_fused = None
        if self._spec:
            try:
                # one dispatch per round (draft chain + verify fused);
                # requires a jax-traceable step_inputs_fn — probe with an
                # abstract trace so a host-side fn falls back cleanly here
                # instead of blowing up mid-serve
                fn = engine.build_spec_program(step_inputs_fn)
                jax.eval_shape(fn, params, self.draft.params, self.kv.state,
                               self.draft.kv.state,
                               jax.ShapeDtypeStruct((self.slots, 1),
                                                    jnp.int32))
                self._spec_fused = fn
            except Exception:  # noqa: BLE001 — untraceable inputs fn
                self._spec_fused = None
        self._accept_ema = 0.0
        # tiered KV cache (ISSUE 16): parked requests own their slot id
        # while their K/V sits in the host tier; rotation happens only at
        # sync points so a parked slot never has un-materialized window
        # tokens. prefetch_ahead is the hit/stall classifier AND the lead
        # the rotation aims for.
        self.tiered = bool(getattr(self.kv, "host_pages", 0))
        self.prefetch_ahead = max(1, int(
            getattr(cfg, "kv_prefetch_ahead", 2) or 2))
        self.max_context = int(getattr(cfg, "serve_max_context", 0) or 0)
        # chunked prefill: tokens a chunk (0: one padded wave);
        # slot -> [request, prompt tokens cached], oldest first
        self.chunk = int(getattr(engine, "chunk_tokens", 0) or 0)
        self._prefilling: Dict[int, List[Any]] = {}
        # the admission policy brain (a fleet shares the same class across
        # replicas); a standalone scheduler owns one instance
        self.admission = AdmissionControl(
            # chunked, `seq` is a slot's whole context: the longest prompt
            # leaves room for the longest answer
            seq=self.seq - (engine.max_decode_len if self.chunk else 0),
            max_context=self.max_context,
            queue_cap=self.queue_cap, ttft_budget_ms=self.ttft_budget_ms,
            overhead_tokens=self.dispatch_ahead + self.spec_tokens,
            pages_needed=self.kv.pages_needed,
            capacity_pages=self.kv.capacity_pages)
        # fleet hooks (all default-off; see module docstring)
        self.feed = None                    # fleet-injected arrival feed
        self.control = None                 # fleet swap orchestration
        self.handoff = handoff              # prefill-only: route to decode
        # device-execution serialization: standalone, a private (never
        # contended) lock — zero behavior change. Under an in-process
        # fleet this is the fleet-wide RLock and _exec_serialized=True
        # adds run-to-completion barriers, because concurrent collective
        # programs from sibling replicas deadlock the shared XLA runtime
        # (see fleet._SharedRuntimeEngine).
        self.exec_lock: Any = threading.RLock()
        self._exec_serialized = False
        if self.handoff is not None and self.kv.spec.latent_dim:
            raise NotImplementedError(
                "prefill-only handoff: the cache holds paged_latent state, "
                "which the hand-off does not move yet")
        if self.handoff is not None and getattr(self.kv, "recurrent", None):
            raise NotImplementedError(
                "prefill-only handoff: the model has layers with per-slot "
                f"recurrent state ({sorted(self.kv.recurrent)[0]}, ...), "
                "which the hand-off does not move")
        if self.handoff is not None and self._spec:
            raise ValueError("prefill-only handoff does not compose with "
                             "speculative decoding (no draft-cache handoff)")
        self.handoffs = 0
        self._pending_handoffs: List = []   # (Request, payload) to ingest
        self.queue_depth = 0                # live router signals (ints,
        self.active_count = 0               # safe to read cross-thread)
        self.parked: Dict[int, Request] = {}
        self.stats: Dict[str, Any] = {
            "shed_queue_full": 0, "shed_ttft_budget": 0, "shed_deadline": 0,
            "shed_prompt_too_long": 0, "shed_over_max_context": 0,
            "failed": 0, "evicted_wedged": 0,
            "decode_timeouts": 0, "overdecode_tokens": 0, "swaps": 0,
            "spec_rounds": 0, "spec_drafted_tokens": 0,
            "spec_accepted_tokens": 0,
            # how often the decode loop hid a sync behind device work: of
            # `materializations`, those with a step or more still in
            # flight, and those that emptied the pipeline, by what for
            "overlapped_syncs": 0, "drains": 0, "drains_by_reason": {}}
        self._ema_serve_ms = 0.0  # EMA of prefill wall (the shed estimator)
        # per-decode-step wall seconds at materialization granularity —
        # the per-token latency samples the bench quantiles
        self.step_times: List[float] = []
        self.decode_steps = 0
        self.prefills = 0
        self.materializations = 0  # host syncs of dispatched steps
        # dispatched, unmaterialized steps, oldest first: (tokens
        # `[slots, 1]` on the device, the step's counters or None)
        self._in_flight: deque = deque()
        # slots whose request an overlapped sync saw finish (EOS) with
        # steps still in flight: evicted after the drain that follows
        self._finishing: Dict[int, Request] = {}
        # request-level tracing (ISSUE 15): zero-sync by construction —
        # the tracer only ever sees timestamps the loop already took at
        # its sync points. With reqtrace off there is NO tracer and the
        # dispatch path is bitwise the PR-13 baseline.
        rt_on = (reqtrace if reqtrace is not None
                 else bool(getattr(cfg, "serve_reqtrace", True)))
        self.tracer: Optional[RequestTracer] = \
            RequestTracer() if rt_on else None
        # SLO classification rides the unified terminal records (cheap
        # host arithmetic, no syncs) so it stays on even without tracing
        self.slo = getattr(engine, "slo", None)
        # --serve-trace-out (ISSUE 20): export the offered load as a
        # replayable tracefmt trace at run() end — recorded traffic and
        # synthetic bench load become interchangeable twin inputs. A
        # fleet clears this per replica and exports ONE pool-wide trace.
        self.trace_out = str(getattr(cfg, "serve_trace_out", "") or "")
        self._trace_arrivals: List[Request] = []
        self._t0 = time.perf_counter()  # run() re-anchors

    # ----------------------------------------------------------- terminal
    def _terminal(self, req: Request, now_s: float, reason: str,
                  kv_pages: int = 0) -> Dict[str, Any]:
        """Every outcome funnels through here: build the UNIFIED terminal
        record (ISSUE 15 satellite — done/shed/failed/timeout all carry
        the same field schema), classify it against the SLO objectives,
        and close the request's trace."""
        rec = terminal_record(req, now_s, kv_pages, reason)
        if self.slo is not None:
            self.slo.observe(rec)
        if self.tracer is not None:
            self.tracer.on_terminal(req, now_s, rec)
        return rec

    # --------------------------------------------------------- degradation
    def _shed(self, req: Request, reason: str, now_s: float) -> None:
        req.outcome = "shed"
        req.shed_reason = reason
        req.finish_s = now_s
        self.shed.append(req)
        self.stats["shed_" + reason] += 1
        rec = self._terminal(req, now_s, reason)
        tel.event("serve/request_shed", cat="serve", reason=reason,
                  waited_s=max(0.0, now_s - req.arrival_s), **rec)

    def _fail(self, req: Request, outcome: str, now_s: float,
              err: Optional[BaseException] = None) -> None:
        req.outcome = outcome
        req.finish_s = now_s
        req.slot = None
        self.failed.append(req)
        self.stats["failed"] += 1
        rec = self._terminal(
            req, now_s,
            "decode_timeout" if outcome == "timeout" else "error")
        tel.event("serve/request_failed", cat="serve",
                  error=repr(err)[:200] if err else "", **rec)

    def _enqueue(self, req: Request, waiting: List[Request],
                 now_s: float) -> None:
        """The shed-or-queue decision for one arrival. The decisions
        themselves live in `admission.AdmissionControl` (the PR 11 machinery,
        lifted to where the fleet can share it); this wrapper keeps the
        side effects — tracing, shed telemetry, terminal records — on the
        replica that owns the request."""
        if self.tracer is not None:
            self.tracer.on_submit(req, now_s)
        # getattr: admission-probe test doubles duck-type the scheduler
        # without running __init__
        if getattr(self, "trace_out", ""):
            self._trace_arrivals.append(req)
        reason = self.admission.permanent_shed_reason(req)
        if reason is not None:
            self._shed(req, reason, now_s)
            return
        victim = self.admission.queue_or_displace(req, waiting)
        if victim is not None:
            self._shed(victim, "queue_full", now_s)

    def _shed_stale(self, waiting: List[Request], now_s: float) -> None:
        """Deadline/TTFT-budget sweep: shed waiters that can no longer be
        served in time (their elapsed wait plus the EMA prefill service
        time already blows the budget) — serving them would burn slots on
        dead-on-arrival responses."""
        for r, reason in self.admission.stale(waiting, now_s,
                                              self._ema_serve_ms):
            self._shed(r, reason, now_s)

    def _pick_wedged(self, active: Dict[int, Request]) -> int:
        """Deterministic eviction choice for a wedged/faulted decode
        batch: the longest-resident slot (most tokens; ties to the lowest
        slot id)."""
        return max(active.items(),
                   key=lambda it: (len(it[1].tokens), -it[0]))[0]

    def _evict_wedged(self, active: Dict[int, Request], outcome: str,
                      now_s: float, err: Optional[BaseException]) -> None:
        slot = self._pick_wedged(active)
        req = active.pop(slot)
        self.kv.evict(slot)
        self.kv.push()
        if self._spec:
            self.draft.kv.evict(slot)
            self.draft.kv.push()
        self.stats["evicted_wedged"] += 1
        tel.event("serve/slot_evicted", cat="serve", rid=req.rid, slot=slot,
                  outcome=outcome, tokens=len(req.tokens))
        self._fail(req, outcome, now_s, err)

    # ------------------------------------------------------------ admission
    def _admit(self, waiting: List[Request], active: Dict[int, Request],
               next_host: np.ndarray, now_s: float) -> bool:
        """Place as many waiting requests as slots/pages/chunk budget
        allow (priority-first), prefill them as one batch, commit K/V,
        record TTFT. Returns True if any were admitted. Host page tables
        are pushed BEFORE the commit so the scatter sees the new pages.
        One `serve/admit` span per wave, from the first placement to the
        last first token; none when no batch formed."""
        if self.chunk:
            return self._admit_chunk(waiting, active, next_host, now_s)
        with tel.span("serve/admit", cat="serve",
                      wave=self.prefills + 1) as wave:
            with tel.span("serve/admit/place", cat="serve",
                          state=self.kv.state_kinds):
                batch = self._place(waiting, active, now_s)
                if batch:
                    self.kv.push()
                    if self._spec:
                        self.draft.kv.push()
                    ids = np.zeros((self.slots, self.seq), np.int32)
                    lengths = np.zeros((self.slots,), np.int32)
                    for req in batch:
                        n = min(len(req.prompt), self.seq)
                        ids[req.slot, :n] = req.prompt[:n]
                        lengths[req.slot] = n
            if not batch:
                wave.cancel()
                return False
            wave.set(requests=len(batch), prompt_tokens=int(lengths.sum()),
                     padded_tokens=self.slots * self.seq)
            return self._prefill_wave(batch, ids, lengths, active, next_host)

    def _admit_chunk(self, waiting: List[Request],
                     active: Dict[int, Request], next_host: np.ndarray,
                     now_s: float) -> bool:
        """Chunked prefill's turn: place whoever fits (their slots start
        `prefilling`), then run ONE chunk of the oldest prefilling request.
        Returns True if it got its first token and joined `active`."""
        with tel.span("serve/admit", cat="serve",
                      wave=self.prefills + 1) as wave:
            with tel.span("serve/admit/place", cat="serve",
                          state=self.kv.state_kinds):
                placed = self._place(waiting, active, now_s)
                for req in placed:
                    self._prefilling[req.slot] = [req, 0]
                if placed:
                    # the pages may be an evicted slot's: the device must
                    # stop seeing them under that slot before a chunk, and
                    # the decode steps between chunks, write anything
                    self.kv.push()
                row = next(iter(self._prefilling.values()), None)
                if row is not None:
                    req, done = row
                    part = req.prompt[done:done + self.chunk]
                    ids = np.zeros((1, self.chunk), np.int32)
                    ids[0, :len(part)] = part
            if row is None:
                wave.cancel()
                return False
            wave.set(requests=1, requests_started=int(done == 0),
                     prompt_tokens=len(part), padded_tokens=self.chunk,
                     chunk_index=done // self.chunk,
                     chunks_of_request=-(-len(req.prompt) // self.chunk),
                     context_before=done)
            return self._prefill_chunk(row, ids, len(part), active, next_host)

    def _prefill_chunk(self, row: List[Any], ids: np.ndarray, length: int,
                       active: Dict[int, Request],
                       next_host: np.ndarray) -> bool:
        """Run one chunk of `row`'s request (`length` real tokens in `ids`)
        and take up what it brought: once the prompt is whole in its pages
        the request gets its first token and joins the decode steps."""
        req, done = row
        lengths = np.array([length], np.int32)
        context = np.array([done], np.int32)
        page_rows = self.kv.prefill_row(req.slot)[None]
        t_pre = time.perf_counter()
        try:
            with tel.span("serve/prefill/dispatch", cat="serve"):
                # the chunk DONATES the cache state, as a decode step does
                # (`_dispatch` says what a retry may assume)
                tokens, state = run_resilient(
                    "serve/prefill",
                    lambda s=self.kv.state: self.engine.prefill_chunk(
                        self.params, s,
                        self.prompt_inputs_fn(ids, lengths, context),
                        page_rows, context, lengths,
                        np.array([req.slot], np.int32)),
                    policy=self.retry_policy)
                stats = state.pop(STATS_KEY, None)
                self.kv.adopt(state)
        except Exception as e:  # noqa: BLE001 — permanent prefill fault:
            self._prefilling.pop(req.slot, None)    # fail ONLY this request
            self.kv.evict(req.slot)
            self._fail(req, "failed", self._now(), e)
            self.kv.push()
            return False
        with tel.span("serve/prefill/commit", cat="serve",
                      state=self.kv.state_kinds, bytes=0):
            pass    # the chunk appended to its slot's pages itself
        self.prefills += 1
        tok, t_first = self._first_tokens_to_host(tokens, stats, t_pre)
        t_pre_off, t_first_off = t_pre - self._t0, t_first - self._t0
        with tel.span("serve/prefill/first_tokens", cat="serve"):
            if req.admit_s is None:
                req.admit_s = t_pre_off     # its first chunk's dispatch
            row[1] += length
            if row[1] < len(req.prompt):
                return False
            del self._prefilling[req.slot]
            self.kv.activate(req.slot, len(req.prompt))
            first = int(tok[0])
            req.tokens.append(first)
            req.ttft_s = t_first_off - req.arrival_s
            next_host[req.slot, 0] = first
            active[req.slot] = req
            if self.tracer is not None:
                self.tracer.on_admit(req, req.admit_s, t_first_off,
                                     wave=self.prefills)
            tel.event("serve/request_admitted", cat="serve",
                      rid=req.rid, slot=req.slot,
                      prompt_len=len(req.prompt),
                      priority=req.priority, ttft_s=req.ttft_s,
                      queue_wait_s=max(0.0, req.admit_s - req.arrival_s))
        self.kv.push()
        return True

    def _first_tokens_to_host(self, first_tokens, stats, t_pre: float):
        """A wave's or a chunk's sync point, in two parts: waiting for the
        device (the program's counters ride on that span), then moving the
        bytes (TTFT is a real materialization). The second span keeps the
        name it had when whole logits crossed here: its `bytes` say which it
        was, 4 a row now. Returns (the tokens on the host, when they
        were)."""
        with tel.span("serve/prefill/device_wait", cat="serve") as sp:
            jax.block_until_ready(first_tokens)
            if stats is not None:
                sp.set(**_stat_totals(jax.device_get([stats])))
        with tel.span("serve/prefill/logits_to_host", cat="serve") as sp:
            tok = np.asarray(first_tokens)
            sp.set(bytes=int(tok.nbytes))
        t_first = time.perf_counter()
        serve_ms = 1e3 * (t_first - t_pre)
        self._ema_serve_ms = (serve_ms if not self._ema_serve_ms
                              else 0.5 * self._ema_serve_ms + 0.5 * serve_ms)
        return tok, t_first

    def _reserved_tokens(self, req: Request) -> int:
        """Positions a request's admission reserves. Speculation slack: a
        verify pass caches up to K entries past the committed extent, so
        the page reservation grows by K — rollback must never need pages
        the admit didn't grant."""
        return (len(req.prompt) + req.max_new_tokens
                + self.dispatch_ahead + self.spec_tokens)

    def _admissible(self, waiting: List[Request]) -> bool:
        """Whether `_place` would place anybody now: a slot is free and the
        most urgent waiter's pages are (or the host tier can make room).
        A waiter held back by a short free list is no reason to empty the
        pipeline: pages come back at a finish, which drains it anyway. A
        prompt part-way through its chunks always has its next chunk to
        run."""
        if self._prefilling:
            return True
        if not waiting or not self.kv.free_slots():
            return False
        return self.tiered or self.kv.can_admit(
            self._reserved_tokens(min(waiting, key=_urgency)))

    def _place(self, waiting: List[Request], active: Dict[int, Request],
               now_s: float) -> List[Request]:
        """The placement loop: requests that got a slot and their pages,
        in admission order (removed from `waiting`)."""
        free = self.kv.free_slots()
        batch: List[Request] = []
        chunk_used = 0
        waiting.sort(key=_urgency)
        i = 0
        while i < len(waiting) and free:
            req = waiting[i]
            if self.prefill_chunk_tokens and batch and \
                    chunk_used + len(req.prompt) > self.prefill_chunk_tokens:
                break  # chunked admission: the rest joins the next wave
            need = self._reserved_tokens(req)
            if not self.kv.can_admit(need):
                # tiered: spill an active slot's pages to the host tier to
                # make HBM room before conceding backpressure
                if not (self.tiered and self._make_room(need, active)):
                    break  # page backpressure: keep queued
            slot = free[0]
            try:
                run_resilient(
                    "serve/kv_admit",
                    lambda s=slot, r=req, n=need:
                        self.kv.admit(s, len(r.prompt), n, prefilling=True)
                        if self.chunk else self.kv.admit(s, len(r.prompt), n),
                    policy=self.retry_policy)
            except KVPoolExhausted:
                break  # lost a race below can_admit: keep queued
            except Exception as e:  # noqa: BLE001 — escalated injected/IO
                waiting.pop(i)
                self._fail(req, "failed", now_s, e)
                continue
            if self._spec:
                try:  # mirror the reservation in the draft's cache
                    self.draft.kv.admit(slot, len(req.prompt), need)
                except KVPoolExhausted:
                    self.kv.evict(slot)
                    break
            free.pop(0)
            req.slot = slot
            chunk_used += len(req.prompt)
            batch.append(waiting.pop(i))
        return batch

    def _prefill_wave(self, batch: List[Request], ids: np.ndarray,
                      lengths: np.ndarray, active: Dict[int, Request],
                      next_host: np.ndarray) -> bool:
        """Prefill the placed batch, commit its K/V and bring each
        request's first token (taken on the device) to the host."""
        t_pre = time.perf_counter()
        try:
            with tel.span("serve/prefill/dispatch", cat="serve"):
                first_tokens, kv_state = run_resilient(
                    "serve/prefill",
                    lambda: self.engine.prefill_first_tokens(
                        self.params, self.prompt_inputs_fn(ids, lengths),
                        lengths),
                    policy=self.retry_policy)
        except Exception as e:  # noqa: BLE001 — permanent prefill fault:
            for req in batch:   # fail ONLY the batch being admitted
                self.kv.evict(req.slot)
                if self._spec:
                    self.draft.kv.evict(req.slot)
                self._fail(req, "failed", self._now(), e)
            self.kv.push()
            if self._spec:
                self.draft.kv.push()
            return False
        with tel.span("serve/prefill/commit", cat="serve",
                      state=self.kv.state_kinds):
            self.kv.commit_prefill(
                kv_state, np.arange(self.slots, dtype=np.int32), lengths)
        if self._spec:
            # the draft prefills the SAME prompt batch into its own cache;
            # positions stay pairwise consistent with the target from here
            try:
                _dtok, dkv_state = run_resilient(
                    "serve/prefill",
                    lambda: self.draft.prefill_first_tokens(
                        self.draft.params,
                        self.prompt_inputs_fn(ids, lengths), lengths),
                    policy=self.retry_policy)
            except Exception as e:  # noqa: BLE001
                for req in batch:
                    self.kv.evict(req.slot)
                    self.draft.kv.evict(req.slot)
                    self._fail(req, "failed", self._now(), e)
                self.kv.push()
                self.draft.kv.push()
                return False
            self.draft.kv.commit_prefill(
                dkv_state, np.arange(self.slots, dtype=np.int32), lengths)
        self.prefills += 1
        tok, t_first = self._first_tokens_to_host(
            first_tokens, kv_state.get(STATS_KEY), t_pre)
        t_pre_off = t_pre - self._t0
        t_first_off = t_first - self._t0
        with tel.span("serve/prefill/first_tokens", cat="serve"):
            for req in batch:
                first = int(tok[req.slot])
                req.tokens.append(first)
                req.ttft_s = t_first_off - req.arrival_s
                req.admit_s = t_pre_off
                next_host[req.slot, 0] = first
                active[req.slot] = req
                if self.tracer is not None:
                    # closes the queue stage at prefill dispatch and spans
                    # the prefill wave to the TTFT sync — both timestamps
                    # already taken above, nothing extra is materialized
                    self.tracer.on_admit(req, t_pre_off, t_first_off,
                                         wave=self.prefills)
                tel.event("serve/request_admitted", cat="serve",
                          rid=req.rid, slot=req.slot,
                          prompt_len=int(lengths[req.slot]),
                          priority=req.priority, ttft_s=req.ttft_s,
                          queue_wait_s=max(0.0, t_pre_off - req.arrival_s))
        return True

    # ---------------------------------------------------------- tier rotation
    def _park(self, slot: int, active: Dict[int, Request]) -> None:
        """Spill one active slot to the host tier. Only called at sync
        points (the window was just materialized), so the request's token
        list and the KV position mirrors agree on the committed extent."""
        req = active.pop(slot)
        self.kv.spill(slot, self.decode_steps)
        if self._spec:
            self.draft.kv.spill(slot, self.decode_steps)
        self.parked[slot] = req
        tel.event("serve/slot_parked", cat="serve", rid=req.rid, slot=slot,
                  tokens=len(req.tokens))

    def _make_room(self, need: int, active: Dict[int, Request]) -> bool:
        """Spill active slots (largest remaining decode budget first — the
        fairness heuristic: the request farthest from finishing donates
        its HBM residency) until `need` pages fit. Spills publish their
        table/active updates immediately so a failed admission afterwards
        can never leave a parked slot looking active on device."""
        spilled = False
        while not self.kv.can_admit(need):
            cands = [s for s in active
                     if self.kv.can_spill(s)
                     and (not self._spec or self.draft.kv.can_spill(s))]
            if not cands:
                break
            slot = max(cands, key=lambda s: (
                active[s].max_new_tokens - len(active[s].tokens), -s))
            self._park(slot, active)
            spilled = True
        if spilled:
            self.kv.push()
            if self._spec:
                self.draft.kv.push()
        return self.kv.can_admit(need)

    def _rotate(self, active: Dict[int, Request], next_host: np.ndarray,
                now_s: float) -> bool:
        """One rotation round at a sync point: issue host→HBM prefetches
        for parked slots (FIFO by park order, as far as device pages
        allow), then rejoin slots whose prefetch has had `prefetch_ahead`
        decode steps to land — or immediately when nothing is active (the
        forced join counts as a stall, never a silent block). Returns True
        when device state changed (caller refreshes its local handles)."""
        changed = False
        for slot in list(self.parked):
            if slot in self.kv._inflight:
                continue
            if not self.kv.prefetch(slot, self.decode_steps):
                break  # device pages short: retry next sync point
            if self._spec:
                self.draft.kv.prefetch(slot, self.decode_steps)
            changed = True
        for slot in list(self.parked):
            issued = self.kv._inflight.get(slot)
            if issued is None:
                continue
            lead = self.decode_steps - issued
            if lead < self.prefetch_ahead and active:
                continue  # not ready and decode has other work
            stalled = self.kv.join(slot, self.decode_steps,
                                   self.prefetch_ahead)
            if self._spec:
                self.draft.kv.join(slot, self.decode_steps,
                                   self.prefetch_ahead)
            req = self.parked.pop(slot)
            # re-seed the decode feedback: the next step consumes the last
            # committed token at the preserved position — this is what
            # makes the spill path bitwise-identical to staying resident
            next_host[slot, 0] = req.tokens[-1]
            active[slot] = req
            changed = True
            if self.tracer is not None:
                # the parked interval tiles into the request's timeline as
                # its own stage, charged to the rejoin sync
                self.tracer.stage(req, "kv_prefetch", now_s,
                                  stalled=int(stalled),
                                  pages=len(self.kv._slot_pages.get(slot, ())))
            tel.event("serve/slot_rejoined", cat="serve", rid=req.rid,
                      slot=slot, stalled=int(stalled), lead_steps=int(lead))
        if changed:
            self.kv.push()
            if self._spec:
                self.draft.kv.push()
            self._emit_tier()
        return changed

    # -------------------------------------------------- disaggregated handoff
    def _handoff_all(self, active: Dict[int, Request]) -> None:
        """Prefill-only mode (ISSUE 18 `--serve-fleet-topology disagg`):
        right after the TTFT materialization, every admitted slot is
        spilled to the host tier, its committed K/V exported, and the
        request handed to the fleet's decode pool via the `handoff`
        callback. A slot that cannot spill (host pages short) simply stays
        and decodes locally — colocated fallback, never a drop."""
        moved = False
        for slot in list(active):
            if not self.kv.can_spill(slot):
                continue
            req = active.pop(slot)
            self.kv.spill(slot, self.decode_steps)
            payload = self.kv.export_parked(slot)
            self.kv.evict(slot)
            req.slot = None
            moved = True
            self.handoffs += 1
            self.handoff(req, payload)
        if moved:
            self.kv.push()

    def _ingest_handoffs(self, now_s: float) -> None:
        """Decode-side of the handoff: adopt each pending payload into the
        host tier as a PARKED slot (position preserved), so the ordinary
        rotation prefetches + rejoins it — bitwise the spill path. A short
        host free list keeps the payload pending (backpressure, retried at
        the next sync point)."""
        still: List = []
        for req, payload in self._pending_handoffs:
            free = self.kv.free_slots()
            if not free or not self.kv.can_import(payload):
                still.append((req, payload))
                continue
            slot = free[0]
            self.kv.import_parked(slot, payload)
            req.slot = slot
            self.parked[slot] = req
            if self.tracer is not None:
                self.tracer.on_submit(req, now_s)
        self._pending_handoffs = still

    def _emit_tier(self) -> None:
        ts = self.kv.tier_stats()
        tel.counter("serve/kv_tier_hot_pages", ts["kv_hot_pages"],
                    cat="serve")
        tel.counter("serve/kv_tier_cold_pages", ts["kv_cold_pages"],
                    cat="serve")
        tel.counter("serve/kv_prefetch_hits", ts["kv_prefetch_hits"],
                    cat="serve")
        tel.counter("serve/kv_prefetch_stalls", ts["kv_prefetch_stalls"],
                    cat="serve")
        tel.counter("serve/kv_spills", ts["kv_spills"], cat="serve")

    # ------------------------------------------------------------- finish
    def _finish(self, req: Request, now_s: float) -> None:
        req.outcome = "done"
        req.finish_s = now_s
        kv_pages = len(self.kv._slot_pages.get(req.slot, ()))
        self.kv.evict(req.slot)
        if self._spec:
            self.draft.kv.evict(req.slot)
        self.completed.append(req)
        reason = ("eos" if self.eos_id is not None
                  and self.eos_id in req.tokens else "max_new_tokens")
        rec = self._terminal(req, now_s, reason, kv_pages=kv_pages)
        tel.event("serve/request_done", cat="serve",
                  tokens=len(req.tokens), **rec)

    def _truncate(self, req: Request) -> bool:
        """Apply EOS/max-len to a request's token list; True = finished."""
        toks = req.tokens
        if self.eos_id is not None and self.eos_id in toks:
            del toks[toks.index(self.eos_id) + 1:]
            return True
        if len(toks) >= req.max_new_tokens:
            del toks[req.max_new_tokens:]
            return True
        return False

    def _budget(self, active: Dict[int, Request]) -> int:
        """Steps until the nearest max-len finish, counted from the tokens
        COMMITTED: the steps in flight count against it."""
        if not active:
            return self.dispatch_ahead
        return max(1, min(r.max_new_tokens - len(r.tokens)
                          for r in active.values()))

    def _window_cap(self, active: Dict[int, Request], pulled: int = 0) -> int:
        """The most steps that may be in flight now: `_depth`, and no more
        than the smallest remaining token budget across active slots, less
        the `pulled` steps an overlapped sync brought and the turn has not
        committed yet. Once as many are out, every token the nearest finish
        needs is on its way, the loop stops dispatching, and the pipeline
        runs empty exactly at a max-len finish (the `scheduler.py`
        over-decode waste fix of ISSUE 11)."""
        return min(self._depth, self._budget(active) - pulled)

    @property
    def _drains_every_window(self) -> bool:
        """Whether this scheduler was built with a feature that needs the
        pipeline empty between windows: a swap poll (the engine watches a
        checkpoint root, or a fleet's controller decides), a fleet's feed
        (hand-offs arrive through it) and its run-to-completion barriers,
        tier rotation, the prefill-only hand-off, the decode watchdog
        (which evicts on a window's own wall time); or with room for one
        step in flight, which is a window."""
        return bool(self.control is not None or self.feed is not None
                    or self._exec_serialized or self.engine.watching
                    or self.tiered or self.handoff is not None
                    or self.decode_timeout_ms or self.dispatch_ahead == 1)

    @property
    def _depth(self) -> int:
        """The most steps in flight, at which the loop materializes: a
        window of `dispatch_ahead` where every window is drained, as
        before; in the overlapped loop `OVERLAP_DEPTH` of them, and never
        more than `dispatch_ahead`."""
        if self._drains_every_window:
            return self.dispatch_ahead
        return min(self.dispatch_ahead, OVERLAP_DEPTH)

    def _drain_reason(self, waiting: List[Request],
                      active: Dict[int, Request]) -> Optional[str]:
        """Why every step in flight has to be materialized before the loop
        goes on, or None where the oldest will do (or nothing yet).
        `kv.push()` after an admission or an eviction writes the host's
        positions and tables over the device's, which is only right when no
        step is in flight; a finish is decided on the request's whole token
        list. The run's end is a finish too: the last slot's."""
        if self._finishing or len(self._in_flight) >= self._budget(active):
            # an EOS was seen, or a budget's last step is out: a sync of
            # the oldest alone could not be followed by a dispatch
            return "finish"
        if self._admissible(waiting):
            return "admit"
        if self._drains_every_window and (
                len(self._in_flight) >= self.dispatch_ahead
                # under the host tier a parked request or a hand-off is
                # taken up at the next turn, not at the window's end
                or self.parked or self._pending_handoffs):
            return "safe_point"
        return None

    def _sync(self, waiting: List[Request], active: Dict[int, Request],
              drain: Optional[str] = None):
        """A turn's materialization, under `serve/decode/window_sync`: decide
        what has to come to the host (every step in flight where
        `_drain_reason` names a reason, or the caller does; the oldest one
        where `_depth` are out; nothing while the pipeline is still
        filling) and bring it, tokens and counters in ONE transfer. The
        span's `in_flight` is what stays dispatched behind the sync (0 at a
        drain, whose reason it names): the device work that hides this sync
        and the commit after it. The decision and the release of the pulled
        device arrays lie inside the span, so the loop's spans account for
        its turn. Returns (the steps' tokens, or None where nothing was
        materialized; the drain's reason, or None)."""
        with tel.span("serve/decode/window_sync", cat="serve",
                      window=self.materializations + 1) as sp:
            drain = drain or self._drain_reason(waiting, active)
            if not drain and len(self._in_flight) < self._depth:
                sp.cancel()
                return None, None
            steps = len(self._in_flight) if drain else 1
            taken = [self._in_flight.popleft() for _ in range(steps)]
            left = len(self._in_flight)
            sp.set(steps=steps, in_flight=left)
            mats, stats = jax.device_get(([t for t, _ in taken],
                                          [c for _, c in taken]))
            sp.set(**_stat_totals(stats))
            del taken
            self.materializations += 1
            if left:
                self.stats["overlapped_syncs"] += 1
            else:
                sp.set(drain=drain)
                self.stats["drains"] += 1
                by = self.stats["drains_by_reason"]
                by[drain] = by.get(drain, 0) + 1
        return mats, drain

    def _commit_window(self, mats: List[np.ndarray],
                       active: Dict[int, Request], window_t0: float,
                       t_now: float) -> np.ndarray:
        """The host side of materialized steps, under `serve/decode/commit`:
        extend token lists, advance the KV mirrors (per-slot — an EOS
        finish is masked out of the committed advance), and, once nothing
        is in flight, finish and evict. The cache state needs no hand-over
        here: `self.kv.state` is the newest tree after every dispatch.
        `window_t0` is when the previous sync ended (or the wave that
        seeded these steps), so `step_times` is wall time between syncs
        over the steps materialized. Returns the last step's tokens (the
        next dispatch's seed once nothing is in flight)."""
        with tel.span("serve/decode/commit", cat="serve",
                      window=self.materializations) as sp:
            steps = len(mats)
            per_step = (t_now - window_t0) / steps
            self.step_times.extend([per_step] * steps)
            adv = np.zeros((self.slots,), np.int32)
            # a slot that finished at an earlier sync decoded these too
            self.stats["overdecode_tokens"] += steps * len(self._finishing)
            for slot, req in active.items():
                prev = len(req.tokens)
                req.tokens.extend(int(m[slot, 0]) for m in mats)
                if self._truncate(req):
                    kept = max(0, len(req.tokens) - prev)
                    adv[slot] = kept
                    self.stats["overdecode_tokens"] += steps - kept
                    self._finishing[slot] = req
                else:
                    adv[slot] = steps
            if self.tracer is not None:
                # attribute the materialized steps to every slot that
                # decoded in them, using the t_now this sync already took
                self.tracer.on_decode_window(
                    list(active.values()), t_now - self._t0, steps, per_step,
                    {slot: int(adv[slot]) for slot in active},
                    window=self.materializations)
            self.kv.sync_after(steps, advances=adv)
            for slot in self._finishing:
                active.pop(slot, None)
            if not self._in_flight:
                # drained: the evictions' mirrors may be published
                for req in self._finishing.values():
                    self._finish(req, self._now())
                self._finishing.clear()
            if self.decode_timeout_ms and active and \
                    per_step * 1e3 > self.decode_timeout_ms:
                # bounded-step watchdog: the window came back slower than
                # the per-step budget — evict the longest-resident slot
                # instead of letting one wedged sequence stall every
                # neighbour
                self.stats["decode_timeouts"] += 1
                self._evict_wedged(active, "timeout", self._now(), None)
            sp.set(tokens_committed=int(adv.sum()))
        return mats[-1].copy()

    # --------------------------------------------------------- speculation
    def _spec_round(self, active: Dict[int, Request],
                    next_host: np.ndarray) -> np.ndarray:
        """One speculative round: K chained greedy draft steps, ONE
        batched target verify pass over `[last, d1..dK]`, then the
        longest-accepted-prefix commit. Every committed token is the
        verify program's argmax (the mismatch slot commits the target's
        correction token), so greedy streams are bitwise identical to
        non-speculative decode. Full acceptance caps the commit at K —
        the draft never cached d_K's K/V, so committing the K+1'th
        (bonus) token would start the next round with a draft-cache hole.

        Device work and materializations all happen before any host
        mutation, so a retried round (transient decode fault) replays
        cleanly off the unchanged host mirrors. Every launch donates the
        cache state it is handed, so what it returns is adopted at once
        (counters popped: plain decode alone reads them); a round that
        fails after a launch rolls the device positions back to the
        committed extent, as acceptance does, before the fault surfaces —
        the replay then rewrites the entries past it."""
        K = self.spec_tokens
        t0 = time.perf_counter()

        def adopt(kv, state):
            state.pop(STATS_KEY, None)
            kv.adopt(state)

        with self.exec_lock:
            last = jnp.asarray(next_host)
            try:
                if self._spec_fused is not None:
                    # the whole round is ONE program launch (see
                    # engine.build_spec_program) — the draft chain's argmax
                    # feedback never leaves the device
                    t_pred_dev, ver_in, tstate, dstate = \
                        self.engine.spec_round_step(
                            self.params, self.draft.params, self.kv.state,
                            self.draft.kv.state, last, self.step_inputs_fn)
                    adopt(self.kv, tstate)
                    adopt(self.draft.kv, dstate)
                else:
                    # unfused fallback (untraceable step_inputs_fn): K+1
                    # launches
                    cur = last
                    drafts = []
                    for _ in range(K):
                        dstate = self.draft.kv.state
                        dlogits, dstate = self.draft.decode_step(
                            self.draft.params, dstate,
                            self.step_inputs_fn(cur, dstate))
                        adopt(self.draft.kv, dstate)
                        cur = jnp.argmax(dlogits[:, -1, :], axis=-1).astype(
                            jnp.int32)[:, None]
                        drafts.append(cur)
                    ver_in = jnp.concatenate([last] + drafts, axis=1)
                    tstate = self.kv.state
                    vlogits, tstate = self.engine.verify_step(
                        self.params, tstate,
                        self.step_inputs_fn(ver_in, tstate))
                    adopt(self.kv, tstate)
                    t_pred_dev = jnp.argmax(vlogits, axis=-1).astype(
                        jnp.int32)
                t_pred = np.asarray(t_pred_dev)
                drafted = np.asarray(ver_in)[:, 1:]          # [slots, K]
                if self._exec_serialized:
                    jax.block_until_ready((self.kv.state,
                                           self.draft.kv.state))
            except Exception:
                for kv in (self.kv, self.draft.kv):
                    kv.push()
                raise
        wall = time.perf_counter() - t0
        self.materializations += 1
        t_end_off = (t0 + wall) - self._t0
        # ---- host commit: nothing below touches the device programs ----
        match = drafted == t_pred[:, :-1]                    # [slots, K]
        adv = np.zeros((self.slots,), np.int32)
        out = next_host.copy()
        finished: List[int] = []
        round_accept = 0
        max_commit = 1
        for slot, req in active.items():
            m = match[slot]
            j = K if m.all() else int(m.argmin())  # accepted draft tokens
            ncommit = min(j + 1, K)
            committed = [int(t) for t in t_pred[slot, :ncommit]]
            prev = len(req.tokens)
            req.tokens.extend(committed)
            round_accept += j
            if self._truncate(req):
                kept = max(0, len(req.tokens) - prev)
                adv[slot] = kept
                self.stats["overdecode_tokens"] += ncommit - kept
                finished.append(slot)
            else:
                adv[slot] = ncommit
                out[slot, 0] = committed[-1]
            max_commit = max(max_commit, ncommit)
            if self.tracer is not None:
                # drafted-vs-committed-vs-rejected per slot, timed off the
                # round's already-taken wall timestamp
                self.tracer.on_spec_round(
                    req, t_end_off, drafted=K, committed=ncommit,
                    rejected=K - min(j, K))
        for kv in (self.kv, self.draft.kv):
            kv.sync_after(0, advances=adv)
            kv.push()  # re-publish the COMMITTED extent: the device-side
            #            speculative advance (K for draft, K+1 for the
            #            verify pass) rolls back to what acceptance kept
        for slot in finished:
            self._finish(active.pop(slot), self._now())
        round_drafted = K * max(1, len(finished) + len(active))
        self.stats["spec_rounds"] += 1
        self.stats["spec_drafted_tokens"] += round_drafted
        self.stats["spec_accepted_tokens"] += round_accept
        rate = round_accept / round_drafted
        self._accept_ema = (rate if self.stats["spec_rounds"] == 1
                            else 0.9 * self._accept_ema + 0.1 * rate)
        tel.counter("serve/spec_drafted_tokens",
                    self.stats["spec_drafted_tokens"], cat="serve")
        tel.counter("serve/spec_accepted_tokens",
                    self.stats["spec_accepted_tokens"], cat="serve")
        tel.counter("serve/spec_accept_rate", self._accept_ema, cat="serve")
        per_tok = wall / max_commit
        self.step_times.extend([per_tok] * max_commit)
        if self.tracer is not None:
            self.tracer.hists["decode_step"].add(per_tok, n=max_commit)
        self.decode_steps += K + 1
        if self.decode_timeout_ms and active and \
                1e3 * wall / (K + 1) > self.decode_timeout_ms:
            self.stats["decode_timeouts"] += 1
            self._evict_wedged(active, "timeout", self._now(), None)
        return out

    def _dispatch(self, next_dev):
        """Launch one decode step on the newest cache state and queue its
        tokens for materialization; returns them as the next step's input
        (a device array: the token chain stays on the device). Raises what
        a permanent fault of the launch raises."""
        with tel.span("serve/decode/dispatch", cat="serve",
                      window=self.materializations + 1):
            state = self.kv.state
            inputs = self.step_inputs_fn(next_dev, state)
            # the step DONATES `s` and a retry re-calls with the same `s`.
            # What a retry may assume: a dispatch that raised before it was
            # enqueued (every injected fault fires ahead of fn; a refused
            # launch) has not consumed its input, and replays identical
            # work. One that consumed `s` and then raised cannot be retried
            # with it: the replay fails on the deleted buffers, the budget
            # runs out and the fault is permanent.
            launch = (lambda s=state, ins=inputs:
                      self.engine.decode_step(self.params, s, ins))
            logits, state = run_resilient("serve/decode_step", launch,
                                          policy=self.retry_policy)
            stats = state.pop(STATS_KEY, None)
            # the tree that went in is dead (donated): the pool holds the
            # newest one after every dispatch, steps in flight or not, so
            # pushes, admissions, rotations and the fault path never touch
            # a consumed buffer
            self.kv.adopt(state)
            with self.exec_lock:
                # the argmax over model-sharded logits is its own
                # collective program; under a fleet it must not interleave
                # with a sibling replica's collectives (the engine call
                # above serializes inside the proxy — this is the one
                # launch the scheduler itself owns)
                next_dev = _greedy_tokens(logits)
                if self._exec_serialized:
                    jax.block_until_ready(next_dev)
            self._in_flight.append((next_dev, stats))
            self.decode_steps += 1
            del logits, inputs, state, launch
        return next_dev

    def _now(self) -> float:
        return time.perf_counter() - self._t0

    # --------------------------------------------------------------- loop
    def run(self, requests: List[Request]) -> List[Request]:
        """Serve `requests` (arrival_s offsets define the open-loop trace)
        to completion; returns the COMPLETED ones with tokens + latency
        fields filled. Shed and failed requests land in `self.shed` /
        `self.failed` with their outcome + reason stamped."""
        with tel.span("serve/run", cat="serve", requests=len(requests)):
            # what the process held before the run is out of the collector's
            # sight while requests are in flight: a full collection that
            # comes due inside the run walks what the run made, and not a
            # set-up's quarter of a million objects with every slot waiting
            # (70-85 ms, once in every window of Nemotron's cell: PERF.md
            # Findings, PR 54). Two list splices; no collection is forced
            gc.freeze()
            try:
                return self._run(requests)
            finally:
                gc.unfreeze()

    def _run(self, requests: List[Request]) -> List[Request]:
        self._t0 = time.perf_counter()
        if self.tracer is not None:
            self.tracer.begin(self._t0)
        queue = deque(sorted(requests, key=lambda r: (r.arrival_s, r.rid)))
        waiting: List[Request] = []
        active: Dict[int, Request] = {}
        next_host = np.zeros((self.slots, 1), np.int32)
        # the cache state is never held here: every program that writes the
        # pools donates the tree it is handed, and `self.kv.state` is the
        # newest one after every dispatch and every commit
        next_dev = jnp.asarray(next_host)
        # when the previous sync ended, or the wave that seeded the steps
        window_t0 = time.perf_counter()

        while (queue or waiting or active or self.parked
               or self._finishing or self._pending_handoffs
               or self._prefilling
               or (self.feed is not None and not self.feed.exhausted)):
            now = self._now()
            if self.feed is not None:
                # fleet feed: the router delivers arrivals (and handed-off
                # prefill payloads) while the loop runs
                for item in self.feed.drain():
                    if isinstance(item, tuple):
                        self._pending_handoffs.append(item)
                    else:
                        self._enqueue(item, waiting, now)
            while queue and queue[0].arrival_s <= now:
                self._enqueue(queue.popleft(), waiting, now)
            self.queue_depth = len(waiting)
            self.active_count = len(active)
            tel.counter("serve/queue_depth", len(waiting), cat="serve")
            tel.counter("serve/active_slots", len(active), cat="serve")
            # a turn with steps in flight materializes all of them where
            # something needs the pipeline empty, the oldest one where as
            # many are out as may be, none while it is still filling. What
            # an overlapped sync pulled is committed AFTER the turn's
            # dispatch, so the chip has the replacement queued while the
            # host walks the slots
            pulled, t_sync, drain = None, window_t0, None
            if self._in_flight:
                pulled, drain = self._sync(waiting, active)
                t_sync = time.perf_counter()
                if drain:
                    next_host = self._commit_window(
                        pulled, active, window_t0, t_sync)
                    pulled = None
                    window_t0 = time.perf_counter()
            drained = not self._in_flight and pulled is None
            if drained and (self.control is not None
                            or self.engine.watching):
                # safe swap point: nothing dispatched references params.
                # Under a fleet, the rolling controller decides whether
                # THIS replica may advance (or must roll back) here.
                swapped = (self.control.at_safe_point(self)
                           if self.control is not None
                           else self.engine.poll_swap())
                if swapped:
                    self.params = self.engine.params
                    self.stats["swaps"] += 1
                    if self.tracer is not None:
                        # the swap landed between windows: mark it inside
                        # every in-flight request's timeline
                        self.tracer.on_swap(
                            list(active.values()), self._now(),
                            getattr(self.engine, "active_version", None))
            if waiting:
                self._shed_stale(waiting, self._now())
            if self._pending_handoffs and drained:
                # disaggregated decode side: adopt handed-off prefills into
                # the host tier; the rotation below carries them to HBM
                self._ingest_handoffs(self._now())
            if self.parked and drained:
                # tier rotation at this sync point: prefetch-ahead issues +
                # ready/forced rejoins (forced = active drained, a counted
                # stall); runs before admission so rejoining slots claim
                # device pages ahead of new arrivals (they are older)
                self._rotate(active, next_host, self._now())
            if drained and (drain == "admit" or self._admissible(waiting)):
                if self._admit(waiting, active, next_host, self._now()):
                    next_dev = jnp.asarray(next_host)
                    window_t0 = time.perf_counter()
            if self.handoff is not None and active and drained:
                # prefill replica: everything admitted leaves for the
                # decode pool right after its TTFT materialization
                self._handoff_all(active)
            if self.tiered and drained:
                # rotation/spill change which slots decode outside _admit's
                # refresh; re-seed at drained points only — with steps in
                # flight `next_host` is BEHIND the device, and resetting to
                # it would re-dispatch the last materialized token
                # (untiered runs keep the exact pre-PR dispatch sequence)
                next_dev = jnp.asarray(next_host)
            if not active:
                if self._prefilling:
                    continue        # the next chunk, at once
                if queue and not waiting:
                    # open loop: idle until the next arrival (short naps
                    # when watching, so snapshot polls keep happening)
                    wait = max(0.0, queue[0].arrival_s - self._now())
                    with tel.span("serve/idle_wait", cat="serve"):
                        time.sleep(min(wait, 0.05)
                                   if (self.engine.watching
                                       or self.control is not None)
                                   else wait)
                elif self.feed is not None and not waiting \
                        and not self.parked and not self._pending_handoffs:
                    # fed loop with nothing in hand: nap instead of
                    # spinning on the (still open) feed
                    with tel.span("serve/idle_wait", cat="serve"):
                        time.sleep(0.002)
                continue
            if self._spec:
                # speculative rounds are self-contained (draft chain +
                # verify + host commit) — nothing stays in flight, every
                # round is a sync point, so poll_swap stays safe above
                try:
                    next_host = run_resilient(
                        "serve/decode_step",
                        lambda nh=next_host: self._spec_round(active, nh),
                        policy=self.retry_policy)
                except Exception as e:  # noqa: BLE001 — permanent fault
                    if active:
                        self._evict_wedged(active, "failed", self._now(), e)
                next_dev = jnp.asarray(next_host)
                continue
            if len(self._in_flight) < self._window_cap(
                    active, len(pulled or ())):
                try:
                    next_dev = self._dispatch(next_dev)
                except Exception as e:  # noqa: BLE001 — permanent fault
                    # materialize what WAS dispatched successfully, then
                    # evict the wedged slot; every other slot keeps
                    # serving. Both go through `self.kv.state`, the tree
                    # the last successful dispatch returned
                    mats = pulled or []
                    if self._in_flight:
                        mats += self._sync(waiting, active, "fault")[0]
                    if mats:
                        next_host = self._commit_window(
                            mats, active, window_t0, time.perf_counter())
                    if active:
                        self._evict_wedged(active, "failed", self._now(), e)
                    next_dev = jnp.asarray(next_host)
                    window_t0 = time.perf_counter()
                    continue
            if pulled is not None:
                self._commit_window(pulled, active, window_t0, t_sync)
                window_t0 = t_sync
        if self.tiered:
            # final tier ledger: counters into telemetry (monitor/prom) and
            # into stats (the bench + tests read them from here)
            self._emit_tier()
            self.stats.update(self.kv.tier_stats())
        if self.tracer is not None:
            # publish the live histograms + SLO scoreboard into the
            # telemetry stream (monitor/prom read them from here)
            self.tracer.emit_hists()
        if self.slo is not None and tel.enabled():
            tel.event("serve/slo", cat="serve", report=self.slo.report())
        if getattr(self.engine.cfg, "profile_ops", False) and tel.enabled():
            # --profile-ops: this run's prefill + decode placements as
            # op/attr rows (tools/trace_report.py's [ops] section), with
            # the run's REAL wall times as the step normalizers
            try:
                self.engine.op_attribution(
                    step_time_s=(float(np.median(self.step_times))
                                 if self.step_times else None),
                    prefill_step_time_s=(self._ema_serve_ms / 1e3
                                         if self._ema_serve_ms else None))
            except Exception:  # noqa: BLE001 — never fail a served batch
                pass
        if getattr(self, "trace_out", "") and self._trace_arrivals:
            from flexflow_tpu.serving import tracefmt
            tracefmt.save_trace(
                self.trace_out,
                tracefmt.requests_to_records(
                    sorted(self._trace_arrivals,
                           key=lambda r: (r.arrival_s, r.rid))),
                meta={"source": "scheduler", "slots": self.slots,
                      "seq": self.seq})
        return self.completed
