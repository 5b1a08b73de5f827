"""Unified telemetry: one span/counter event stream for every layer.

Motivation (ISSUE 5): observability was scattered — `profile_report`
cache/memory tables, `CompiledModel.step_stats`, pipeline bubble replay,
and the whole-fit `jax.profiler.trace` each lived in their own corner with
no shared event stream. This module is the shared stream: a lightweight,
thread-safe, process-global sink that the compiler (graph_optimize /
substitution rounds / DP / strategy-cache / simulator re-rank), the fit
loop (prefetch wait / dispatch / host sync / barrier), the serving
scheduler (admit / prefill / decode windows), the pipeline executor
(per-stage, per-microbatch phase ops), the dataloader prefetch threads
(queue occupancy) and the async checkpoint writer all emit into.

Design contract:
  * Spans (`span()`, `record()`, JAX's compile phases) ALWAYS land in a
    bounded in-memory ring (`RING_SIZE` records; `ring_spans()` reads it):
    a flight recorder that is on when the rare slow call happens, and the
    way a benchmark in the same process reads the program's spans. A
    `span()` also enters a `jax.profiler.TraceAnnotation("ff/<name>")`, so
    the same span lies in the profiler's host plane, on the profiler's
    clock, whenever a profiler session is open. "Off" means: no profiler
    session, no file sink; a span then costs two `perf_counter_ns` calls,
    an inactive annotation and a deque append (about 2 us), and never adds
    a dispatch or a host sync (tests/test_telemetry.py pins this against
    the PR-2 baseline counters).
  * The FILE sink is off by default and enabled via `configure(dir)` —
    `--telemetry-dir` through FFConfig / compile_model — writing JSON
    Lines to `<dir>/telemetry-<pid>.jsonl`. `enabled()` says whether it
    is on; `event()` and `counter()` are sink-only.
  * The ring's clock is `time.perf_counter_ns()`. The sink's timestamps
    are MICROSECONDS on the same clock since the first line of the
    package's `__init__` (`now_us()`; `start/import` begins at 0), so
    events map 1:1 onto the Chrome trace-event format
    `tools/trace_report.py` renders (ph "X" complete span / "i" instant /
    "C" counter, ts/dur in us).
  * A sink that opens is first given the ring's `cat="start"` records that
    no sink has had (`start/import`, made before any `configure()` can
    run): every compile entry point configures on its first line, so the
    file then tells of set-up what the ring tells.

Ring record: `Span(name, start_ns, end_ns, thread, parent, args, id, cat)`;
`parent` is the `id` of the span that was open on that thread (0: none).

Set-up's anatomy (ISSUE 54), all before a benchmark's window: `start/import`
and `start/import_serving` (the packages' own `__init__`), `start/backend`
(`compile.resolve_machine`, a compile's first question to the backend),
`compile/compile_model` / `serve/compile_serving` (the search),
`compile/init` / `serve/init` (the weights), and JAX's compile phases under
whichever span made the first call (`_on_jax_duration`).

Sink record schema (one JSON object per line):
  {"name": str, "ph": "X"|"i"|"C", "ts": us, "dur": us (X only),
   "pid": int, "tid": thread-name, "cat": str?, "args": dict?}
"""

from __future__ import annotations

import itertools
import json
import os
import statistics
import threading
from collections import deque
from time import perf_counter_ns
from typing import (Any, Dict, Iterable, List, NamedTuple, Optional,
                    Sequence, Tuple)

from jax import monitoring as _monitoring
from jax.profiler import StepTraceAnnotation, TraceAnnotation

# the process epoch all sink ts are relative to: the package's first line
# (this module is imported by it), so that `start/import` begins at 0
from flexflow_tpu import _T0_NS

_LOCK = threading.Lock()
_SINK: Optional["_Sink"] = None
_UNSUNK_SINCE_NS = 0     # what ended since then has gone to no sink

# the ring holds this many spans; a benchmark run must stay under it
# (`ring_peak()`), a long-lived process keeps the newest. A traced run of
# the busiest cell (LFM2's, 7 000 decode turns with their `serve/req/*`
# stages) read a peak of 63 185 (my chip run, PR 54): 65 536 was one seed
# from dropping the set-up's records, which are the oldest
RING_SIZE = 131072
ANNOTATION_PREFIX = "ff/"
# a JAX compile phase shorter than this gets no ring record of its own
JAX_SPAN_MIN_NS = 1_000_000

# cost-model drift guardrail: measured/predicted step-time ratios beyond
# this factor (either direction) flag the calibration as stale
DRIFT_WARN_RATIO = 3.0


class _Sink:
    """One open JSONL stream. All writes serialize under the module lock
    (spans are emitted from the fit loop, prefetch threads, and the async
    checkpoint writer concurrently).

    Long elastic runs (days of fit + resume cycles) would grow a single
    JSONL without bound, so the sink rotates by SIZE: once the current
    segment exceeds `max_bytes` the next emit rolls to
    `telemetry-<pid>.<seq>.jsonl`. Segments are never renamed or deleted
    (concurrent readers — tools/monitor.py tailing the dir — stay valid),
    and read_events() merges every `telemetry-*.jsonl` in the dir
    ts-sorted, so trace_report / monitor see one stream."""

    def __init__(self, dir_: str, max_bytes: Optional[int] = None):
        os.makedirs(dir_, exist_ok=True)
        self.dir = dir_
        self.max_bytes = max_bytes
        self._seq = 0
        self.path = os.path.join(dir_, f"telemetry-{os.getpid()}.jsonl")
        self._f = open(self.path, "a", buffering=1 << 16)
        # appending to an existing stream (re-configure to the same dir in
        # a new sink): count what's already there toward the size cap
        try:
            self._written = os.path.getsize(self.path)
        except OSError:
            self._written = 0

    def _rotate_locked(self) -> None:
        """Roll to the next segment (caller holds _LOCK)."""
        try:
            self._f.flush()
            self._f.close()
        except ValueError:
            pass
        self._seq += 1
        self.path = os.path.join(
            self.dir, f"telemetry-{os.getpid()}.{self._seq:03d}.jsonl")
        self._f = open(self.path, "a", buffering=1 << 16)
        self._written = 0

    def emit(self, obj: Dict[str, Any]) -> None:
        line = json.dumps(obj, separators=(",", ":"), default=str)
        with _LOCK:
            # a writer thread (async checkpoint, prefetcher) may hold a
            # sink reference shutdown() is concurrently closing: dropping
            # the event is correct, raising into the caller is not (it
            # would mark a SUCCESSFUL checkpoint write as failed)
            try:
                if self._f.closed:
                    return
                if (self.max_bytes is not None
                        and self._written >= self.max_bytes):
                    self._rotate_locked()
                self._f.write(line + "\n")
                self._written += len(line) + 1
            except ValueError:
                pass

    def flush(self) -> None:
        with _LOCK:
            self._f.flush()

    def close(self) -> None:
        with _LOCK:
            try:
                self._f.flush()
                self._f.close()
            except ValueError:  # already closed
                pass


_ATEXIT_HOOKED = False


def _register_atexit() -> None:
    global _ATEXIT_HOOKED
    if _ATEXIT_HOOKED:
        return
    _ATEXIT_HOOKED = True
    import atexit

    atexit.register(flush)


def configure(telemetry_dir: Optional[str],
              max_mb: Optional[float] = None) -> bool:
    """Enable (or re-point) the process-global sink. A falsy dir is a
    no-op — telemetry keeps its current state; turning it OFF is an
    explicit `shutdown()` (so one compile with --telemetry-dir doesn't get
    silently disabled by a later compile without it). `max_mb` caps each
    JSONL segment's size (`--telemetry-max-mb`; None/0 = unbounded) — the
    sink rotates to numbered segments past it. Returns enabled()."""
    global _SINK
    if not telemetry_dir:
        return _SINK is not None
    d = os.path.abspath(os.path.expanduser(telemetry_dir))
    max_bytes = int(max_mb * (1 << 20)) if max_mb else None
    old = _SINK
    if old is not None and old.dir == d:
        if max_mb is not None:
            with _LOCK:
                old.max_bytes = max_bytes
        return True
    new = _Sink(d, max_bytes=max_bytes)
    # set-up's first spans (cat "start": the imports, the backend) are made
    # before any configure() can run: a file begins with those that no
    # file has had, and then tells of set-up what the ring tells
    since = _UNSUNK_SINCE_NS if old is None else 0
    for rec in list(_RING):
        if rec.cat == "start" and rec.end_ns >= since:
            new.emit(_sink_obj(rec))
    _SINK = new
    if old is not None:
        old.close()
    _register_atexit()
    return True


def shutdown() -> None:
    """Disable telemetry and close the stream (flushes buffered lines)."""
    global _SINK, _UNSUNK_SINCE_NS
    s, _SINK = _SINK, None
    if s is not None:
        s.close()
        _UNSUNK_SINCE_NS = perf_counter_ns()


def flush() -> None:
    s = _SINK
    if s is not None:
        s.flush()


def enabled() -> bool:
    return _SINK is not None


def sink_path() -> Optional[str]:
    s = _SINK
    return s.path if s is not None else None


def now_us() -> float:
    """Microseconds on the process-monotonic clock (the ts domain of every
    sink event and of the Chrome trace export)."""
    return (perf_counter_ns() - _T0_NS) / 1e3


# ------------------------------------------------------------------- ring
class Span(NamedTuple):
    """One ring record. `start_ns`/`end_ns` are `perf_counter_ns()`."""
    name: str
    start_ns: int
    end_ns: int
    thread: str
    parent: int                     # id of the enclosing span, 0 = none
    args: Optional[Dict[str, Any]]
    id: int
    cat: Optional[str] = None


class _ThreadState(threading.local):
    def __init__(self):
        self.stack: List["_Span"] = []  # the spans open here, outermost first
        # (start ns, seconds) of this thread's compile phases that no
        # later phase has enclosed yet
        self.phases: "deque[Tuple[int, float]]" = deque(maxlen=4096)


_RING: "deque[Span]" = deque(maxlen=RING_SIZE)
_IDS = itertools.count(1)
_TLS = _ThreadState()
_PEAK = 0                           # most records held before a clear

# JAX's compile phases (jax.monitoring duration events) as spans
_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax/lower",
    "/jax/core/compile/backend_compile_duration": "jax/backend_compile",
}
# always on: name -> [count, seconds] since the process began (a phase's
# seconds are its own: less the phases nested in it)
totals: Dict[str, List[float]] = {n: [0, 0.0] for n in _JAX_EVENTS.values()}
# phases under JAX_SPAN_MIN_NS since the last flush, by what made them:
# (phase, thread, anchor span id, direct parent's name, fun_name)
#   -> [count, seconds, first start ns, last end ns]
_SMALL: Dict[Tuple[Any, ...], List[Any]] = {}


def _flush_small() -> None:
    """One ring record a key for the short compile phases gathered since
    the last flush: `parent` is the key's anchor, `args` hold the function
    that was traced or lowered (`fun`), how many phases (`count`), their
    summed own `seconds` and, where the anchor is not the direct parent,
    that parent's name (`under`). Flushed whenever a root span opens and
    before the ring is read, so every phase that ended before a root span
    lies before it in the ring. The keys are programs x function names:
    the largest set-up (Keye-VL's chunk cell) is 785 ring records in all
    before its window, spans and compile phases of their own included (my
    chip run, PR 54), against a ring of 131 072."""
    with _LOCK:
        gathered = list(_SMALL.items())
        _SMALL.clear()
    for (name, thread, anchor, under, fun), (count, secs, start, end) \
            in gathered:
        args = {"fun": fun, "count": count, "seconds": secs}
        if under is not None:
            args["under"] = under
        _emit(name, start, end, "compile", thread, anchor, args, next(_IDS))


def _small_key(name: str, fun: Optional[str]) -> Tuple[Any, ...]:
    """What a short phase is gathered under: the span open on this thread
    (its program) and the function. A trace-time span opens once a lowered
    CALL (`lower/flash_attention`, `ssm/step_path`, every `cat="compile"`
    span inside a first call), so a phase right under one is keyed by that
    span's NAME and anchored at the nearest span above it that is not
    `cat="compile"` (the dispatch, the admission), or at the outermost
    where all are (the search): the records stay as few as the programs
    times the functions, not as many as the calls."""
    thread = threading.current_thread().name
    stack = _TLS.stack
    if not stack:
        return (name, thread, 0, None, fun)
    top = stack[-1]
    anchor = top
    for sp in reversed(stack):
        anchor = sp
        if sp._cat != "compile":
            break
    return (name, thread, anchor.id,
            None if anchor is top else top.name, fun)


def _on_jax_duration(event: str, secs: float, **kw: Any) -> None:
    name = _JAX_EVENTS.get(event)
    if name is None:
        return
    end = perf_counter_ns()
    start = end - int(secs * 1e9)
    # phases nest (a jit traced while another is traced): what is counted
    # is a phase's OWN seconds, less the phases that ended inside it, so
    # the totals add up to time spent and not to more
    recent = _TLS.phases
    own = secs
    while recent and recent[-1][0] >= start:
        own -= recent.pop()[1]
    recent.append((start, secs))
    own = max(0.0, own)
    fun = kw.get("fun_name")
    short = end - start < JAX_SPAN_MIN_NS
    key = _small_key(name, fun) if short else None
    with _LOCK:
        tot = totals[name]
        tot[0] += 1
        tot[1] += own
        if short:
            small = _SMALL.setdefault(key, [0, 0.0, start, end])
            small[0] += 1
            small[1] += own
            small[3] = end
            return
    # parent = the span open on this thread: a compile inside fit/dispatch
    # or serve/decode/dispatch names the step that recompiled
    stack = _TLS.stack
    _emit(name, start, end, "compile", None, stack[-1].id if stack else 0,
          {"fun": fun, "seconds": own}, next(_IDS))


_monitoring.register_event_duration_secs_listener(_on_jax_duration)


def ring_spans(name: Optional[str] = None,
               since_ns: Optional[int] = None) -> List[Span]:
    """The ring's records, oldest first (a span is recorded when it ENDS,
    so a parent follows its children); optionally only those called
    `name` and/or started at or after `since_ns`."""
    _flush_small()
    return [s for s in list(_RING)
            if (name is None or s.name == name)
            and (since_ns is None or s.start_ns >= since_ns)]


def ring_clear() -> None:
    global _PEAK
    _flush_small()      # what is gathered for the ring goes with it
    _PEAK = max(_PEAK, len(_RING))
    _RING.clear()


def ring_peak() -> int:
    """The most records the ring has held; RING_SIZE means it wrapped (or
    was about to) and the oldest records are gone."""
    return max(_PEAK, len(_RING))


def _base(name: str, ph: str, ts: float, cat: Optional[str],
          args: Optional[Dict[str, Any]],
          tid: Optional[str] = None) -> Dict[str, Any]:
    obj: Dict[str, Any] = {"name": name, "ph": ph, "ts": ts,
                           "pid": os.getpid(),
                           "tid": tid if tid is not None
                           else threading.current_thread().name}
    if cat:
        obj["cat"] = cat
    if args:
        obj["args"] = args
    return obj


def _emit(name: str, start_ns: int, end_ns: int, cat: Optional[str],
          tid: Optional[str], parent: int, args: Optional[Dict[str, Any]],
          span_id: int) -> None:
    """One finished span: into the ring, and into the file sink if one is
    configured."""
    rec = Span(name, start_ns, end_ns,
               tid if tid is not None else threading.current_thread().name,
               parent, args, span_id, cat)
    _RING.append(rec)
    s = _SINK
    if s is not None:
        s.emit(_sink_obj(rec))


def _sink_obj(rec: Span) -> Dict[str, Any]:
    obj = _base(rec.name, "X", (rec.start_ns - _T0_NS) / 1e3, rec.cat,
                rec.args, tid=rec.thread)
    obj["dur"] = max(0.0, (rec.end_ns - rec.start_ns) / 1e3)
    return obj


def record(name: str, start_us: float, end_us: Optional[float] = None,
           cat: Optional[str] = None, tid: Optional[str] = None,
           **args: Any) -> None:
    """A complete span from explicit `now_us()` timestamps, for an
    interval known only afterwards (the serving request tracer's stages,
    whose boundaries are stamps the scheduler already took). It gets no
    profiler annotation. `tid` overrides the default thread-name track —
    the request tracer uses "slot<k>" so the Chrome export reads as one
    row per decode slot instead of one row per host thread."""
    end = now_us() if end_us is None else end_us
    start_ns = _T0_NS + round(start_us * 1e3)
    stack = _TLS.stack
    _emit(name, start_ns, start_ns + round((end - start_us) * 1e3), cat,
          tid, stack[-1].id if stack else 0, args or None, next(_IDS))


def event(name: str, cat: Optional[str] = None, **args: Any) -> None:
    """Instant event (Chrome ph "i"); file sink only."""
    s = _SINK
    if s is None:
        return
    obj = _base(name, "i", now_us(), cat, args or None)
    obj["s"] = "p"  # process-scoped instant
    s.emit(obj)


def error(name: str, **args: Any) -> None:
    """Instant event in the reserved "error" category — surfaced by
    trace_report's summary and by the fit-end / profile_report warnings
    (e.g. checkpoint/write_failed from runtime/checkpoint.py)."""
    event(name, cat="error", **args)


def retry(site: str, attempt: int, exc: BaseException, **args: Any) -> None:
    """Instant event in the reserved "retry" category — one per backoff
    retry of a transient fault (runtime/resilience.run_resilient).
    tests/test_resilience.py asserts these appear for every recovered
    injected fault; exhaustion lands in the "error" category instead."""
    event("retry", cat="retry", site=site, attempt=attempt,
          error=repr(exc), **args)


def counter(name: str, value: float, cat: Optional[str] = None) -> None:
    """Counter sample (Chrome ph "C") — e.g. dataloader queue occupancy;
    file sink only."""
    s = _SINK
    if s is None:
        return
    obj = _base(name, "C", now_us(), cat, {"value": float(value)})
    s.emit(obj)


class _Span:
    __slots__ = ("name", "args", "id", "parent", "_cat", "_ann", "_t0")

    def __init__(self, name: str, cat: Optional[str],
                 step_num: Optional[int], args: Dict[str, Any]):
        self.name = name
        self.args = args
        self._cat = cat
        label = ANNOTATION_PREFIX + name
        self._ann = (TraceAnnotation(label) if step_num is None
                     else StepTraceAnnotation(label, step_num=step_num))

    def set(self, **args: Any) -> None:
        """Add what is known only inside the span (bytes moved, steps)."""
        self.args.update(args)

    def cancel(self) -> None:
        """Record nothing: the span turned out to cover no work (an
        admission pass in which no batch formed)."""
        self.name = None

    def __enter__(self) -> "_Span":
        stack = _TLS.stack
        if stack:
            self.parent = stack[-1].id
        else:
            self.parent = 0
            if _SMALL:
                _flush_small()
        self.id = next(_IDS)
        stack.append(self)
        # the annotation outside the stamps: the ring's span lies inside
        # the profiler's, by the cost of one clock read at each end
        self._ann.__enter__()
        self._t0 = perf_counter_ns()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        t1 = perf_counter_ns()
        self._ann.__exit__(et, ev, tb)
        _TLS.stack.pop()
        if self.name is None:
            return False
        if et is not None:
            self.args["error"] = repr(ev)
        _emit(self.name, self._t0, t1, self._cat, None, self.parent,
              self.args or None, self.id)
        return False


def span(name: str, cat: Optional[str] = None,
         step_num: Optional[int] = None, **args: Any) -> _Span:
    """Context manager recording a complete span around its body: into
    the ring, as the profiler annotation "ff/<name>" (a
    `StepTraceAnnotation` when `step_num` is given), and into the file
    sink when one is configured. The hot-loop helper: see the module
    docstring for what it costs."""
    return _Span(name, cat, step_num, args)


# ------------------------------------------------------------------ readers
def read_events(path: str) -> List[Dict[str, Any]]:
    """Load a telemetry stream: `path` is one .jsonl file or a telemetry
    dir (all telemetry-*.jsonl merged). Events come back ts-sorted;
    malformed lines (a crashed writer's torn tail) are skipped."""
    files: List[str]
    if os.path.isdir(path):
        files = sorted(
            os.path.join(path, f) for f in os.listdir(path)
            if f.startswith("telemetry-") and f.endswith(".jsonl"))
    else:
        files = [path]
    out: List[Dict[str, Any]] = []
    for fp in files:
        with open(fp) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                if isinstance(ev, dict) and "name" in ev and "ts" in ev:
                    out.append(ev)
    out.sort(key=lambda e: e.get("ts", 0.0))
    return out


# ------------------------------------------------- shared derived metrics
def bubble_from_ops(num_stages: int,
                    ops: Iterable[Tuple[int, float, float]]
                    ) -> Optional[float]:
    """Bubble fraction of one executed pipeline update from its per-op
    timeline: ops are (stage, start_us, end_us) for every F/B op the
    executor dispatched. bubble = 1 - busy / (stages * span). This is THE
    accounting both the executor's step_stats["measured_bubble"] and
    tools/trace_report.py use — shared so the two can never disagree
    (tests assert they match on the same stream)."""
    ops = list(ops)
    if not ops or num_stages <= 0:
        return None
    start = min(o[1] for o in ops)
    end = max(o[2] for o in ops)
    span_us = end - start
    if span_us <= 0.0:
        return None
    busy = sum(e - s for _stage, s, e in ops)
    return max(0.0, 1.0 - busy / (num_stages * span_us))


def pipeline_bubble_from_events(events: Sequence[Dict[str, Any]]
                                ) -> Optional[float]:
    """Mean per-update bubble over a stream's pipeline phase events
    (cat "pipeline", names pipe/F + pipe/B, args stage/micro/update/fit):
    groups by (pid, fit id, update id) — update counters restart per
    process AND per fit (init() resets the iteration counter), and each
    process's ts lives on its own monotonic epoch, so a stream holding
    several runs must never merge their ops into one timeline — applies
    bubble_from_ops per update with that update's OWN stage count, in
    group order; the executor accumulates its reported bubble the same
    way (over one fit; on a multi-fit stream this is the mean over every
    fit's updates)."""
    per_update: Dict[Any, List[Tuple[int, float, float]]] = {}
    for ev in events:
        if ev.get("cat") != "pipeline" or ev.get("ph") != "X":
            continue
        if ev.get("name") not in ("pipe/F", "pipe/B"):
            continue
        args = ev.get("args") or {}
        s = int(args.get("stage", 0))
        key = (ev.get("pid"), args.get("fit"), args.get("update"))
        per_update.setdefault(key, []).append(
            (s, float(ev["ts"]), float(ev["ts"]) + float(ev.get("dur", 0.0))))
    if not per_update:
        return None
    total, n = 0.0, 0
    for key in sorted(per_update,
                      key=lambda k: tuple((x is None, x) for x in k)):
        ops = per_update[key]
        stages = max(o[0] for o in ops) + 1
        b = bubble_from_ops(stages, ops)
        if b is not None:
            total += b
            n += 1
    return total / n if n else None


def drift_stats(predicted_s: Optional[float],
                windows: Sequence[Tuple[int, float]]) -> Dict[str, Any]:
    """Cost-model drift: the search's predicted per-update step time vs
    the fit loop's measured windows [(steps, wall_seconds), one per
    epoch]. The FIRST window pays jit tracing + XLA compilation, so when
    more than one exists it is excluded and the rest reduce by MEDIAN;
    warn only trips (past DRIFT_WARN_RATIO in either direction) when at
    least one post-compilation window exists — a 1-epoch fit reports the
    ratio for the record but can't distinguish drift from compile cost."""
    ws = [(int(n), float(t)) for n, t in windows if n > 0 and t > 0.0]
    steady = ws[1:] if len(ws) >= 2 else ws
    measured = statistics.median(t / n for n, t in steady) if steady \
        else None
    out: Dict[str, Any] = {
        "predicted_step_time_s": float(predicted_s) if predicted_s else None,
        "measured_step_time_s": measured,
        "windows": len(ws),
        "ratio": None,
        "warn": False,
    }
    if out["predicted_step_time_s"] and measured:
        r = measured / out["predicted_step_time_s"]
        out["ratio"] = r
        out["warn"] = bool(len(ws) >= 2 and (r > DRIFT_WARN_RATIO
                                             or r < 1.0 / DRIFT_WARN_RATIO))
    return out


def emit_fit_end(drift: Dict[str, Any], verbose: bool,
                 **extra: Any) -> None:
    """Shared fit-end drift hook (CompiledModel and PipelinedModel both
    call it): emit the fit/drift event into the stream when telemetry is
    on, and print the [drift] warning lines when the monitor tripped."""
    if enabled():
        args = {k: v for k, v in drift.items() if v is not None}
        args.update({k: v for k, v in extra.items() if v is not None})
        event("fit/drift", cat="drift", **args)
    if verbose and drift.get("warn"):
        for line in format_drift(drift):
            print(line)


def format_drift(d: Dict[str, Any]) -> List[str]:
    """The `[drift]` report lines (profile_report + fit-end summary share
    this formatting)."""
    pred, meas = d.get("predicted_step_time_s"), d.get("measured_step_time_s")
    if pred is None and meas is None:
        return ["[drift] no prediction and no measured fit windows yet"]
    if meas is None:
        return [f"[drift] predicted_step={pred * 1e3:.3f}ms; no measured "
                "fit windows yet (run fit())"]
    if pred is None:
        return [f"[drift] measured_step={meas * 1e3:.3f}ms; strategy "
                "carries no predicted cost"]
    lines = [f"[drift] predicted_step={pred * 1e3:.3f}ms "
             f"measured_step={meas * 1e3:.3f}ms "
             f"ratio={d['ratio']:.2f}x "
             f"(median of {d['windows']} epoch windows)"]
    if d.get("warn"):
        lines.append(
            f"[drift] WARNING: measured/predicted ratio {d['ratio']:.2f}x "
            f"outside [1/{DRIFT_WARN_RATIO:g}, {DRIFT_WARN_RATIO:g}] — the "
            "cost model has drifted")
    return lines
