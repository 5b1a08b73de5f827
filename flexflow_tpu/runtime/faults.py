"""Deterministic fault injection — the test harness for every recovery path.

Reference gap (ISSUE 6): the reference leans on Legion's resilient task
runtime; our JAX rebuild has explicit recovery code (runtime/resilience.py
retry/backoff, durable checkpoints, preemption drain) and every one of
those paths must be EXERCISABLE on demand, deterministically, in tests and
in the kill-and-resume tests (tests/test_resilience.py). This module is
the switchboard: a `FaultPlan` arms named SITES to raise at chosen
indices, and each instrumented callsite asks `check(site)` before doing
the real work — so an armed fault fires BEFORE any state is mutated
(safe to retry, even under buffer donation).

Sites (the full set is `SITES`; `check` rejects unknown names so a typo'd
plan can't silently arm nothing):

  dataloader/transfer   host->device batch transfer (prefetch worker)
  checkpoint/write      checkpoint serialization (sync or writer thread)
  fit/dispatch          train-step dispatch admission (index = global step)
  distributed/init      jax.distributed initialization
  pipe/boundary_hop     pipeline stage-boundary activation transfer
  health/nonfinite      NaN-poison the parameters before a step (index =
                        1-based global step) — exercises the numerics
                        sentinels in flexflow_tpu/health.py. This site is
                        NON-RAISING: the fit loops query `poison()` and
                        corrupt the params themselves, modeling a silent
                        numerics blow-up rather than a thrown error.
  serve/prefill         serving prefill dispatch (one index per admission
                        batch) — a permanent fault fails the batch being
                        admitted, never the engine
  serve/decode_step     serving decode-step dispatch — a permanent fault
                        makes the scheduler evict the wedged slot and
                        keep serving the rest
  serve/kv_admit        KV-cache page allocation at admission (one index
                        per request) — a permanent fault sheds only that
                        request
  serve/param_swap      the hot-swap's durable-snapshot read — a
                        permanent fault aborts the swap; the engine keeps
                        serving the currently active version

Plan grammar (FF_FAULT_PLAN env var or --fault-plan, comma-separated):

  site@N        fail once at index N (1-based)
  site@N*T      fail T consecutive times starting at index N (transient:
                a retrying caller recovers once the T failures are spent)
  site@N!       fail EVERY time from index N on (permanent: retries burn
                their budget and the caller escalates)

The index is the site's own 1-based call count, except `fit/dispatch`
where the caller passes the 1-based global step — "fail step 3" is
`fit/dispatch@3` regardless of how steps batch into dispatches.
"""

from __future__ import annotations

import os
import re
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from flexflow_tpu import telemetry as tel

SITES = (
    "dataloader/transfer",
    "checkpoint/write",
    "fit/dispatch",
    "distributed/init",
    "pipe/boundary_hop",
    "health/nonfinite",
    "serve/prefill",
    "serve/decode_step",
    "serve/kv_admit",
    "serve/param_swap",
)


class InjectedFault(RuntimeError):
    """A deterministic injected failure (transient unless Permanent)."""


class PermanentInjectedFault(InjectedFault):
    """An injected failure armed to outlast any retry budget."""


@dataclass
class FaultSpec:
    site: str
    at: int = 1            # first 1-based index that fires
    times: int = 1         # consecutive failures (ignored when permanent)
    permanent: bool = False
    fired: int = field(default=0, compare=False)

    def should_fire(self, idx: int) -> bool:
        if idx < self.at:
            return False
        if self.permanent:
            return True
        return self.fired < self.times


_SPEC_RE = re.compile(r"^(?P<site>[\w/._-]+)@(?P<at>\d+)"
                      r"(?:\*(?P<times>\d+))?(?P<perm>!)?$")


def parse_plan(spec: str) -> List[FaultSpec]:
    """Parse the plan grammar; unknown sites and malformed entries raise
    (a fault plan that silently arms nothing would green-light a broken
    recovery path)."""
    out: List[FaultSpec] = []
    for entry in (spec or "").split(","):
        entry = entry.strip()
        if not entry:
            continue
        m = _SPEC_RE.match(entry)
        if m is None:
            raise ValueError(
                f"bad fault spec {entry!r}: expected site@N, site@N*T or "
                f"site@N! (sites: {', '.join(SITES)})")
        site = m.group("site")
        if site not in SITES:
            raise ValueError(f"unknown fault site {site!r} in {entry!r}; "
                             f"sites: {', '.join(SITES)}")
        out.append(FaultSpec(site=site, at=int(m.group("at")),
                             times=int(m.group("times") or 1),
                             permanent=bool(m.group("perm"))))
    return out


_LOCK = threading.Lock()
_SPECS: List[FaultSpec] = []
_COUNTS: Dict[str, int] = {}
_FIRED: Dict[str, int] = {}

# FF_FAULT_PLAN at import: a harness that starts the worker as a process of
# its own arms the plan via the environment before the worker imports
# anything
if os.environ.get("FF_FAULT_PLAN"):
    _SPECS = parse_plan(os.environ["FF_FAULT_PLAN"])


def configure(spec) -> None:
    """Arm a plan: a grammar string, a list of FaultSpec, or falsy (leave
    the current plan untouched, mirroring telemetry.configure)."""
    global _SPECS
    if not spec:
        return
    specs = parse_plan(spec) if isinstance(spec, str) else list(spec)
    with _LOCK:
        _SPECS = specs
        _COUNTS.clear()
        _FIRED.clear()


def clear() -> None:
    global _SPECS
    with _LOCK:
        _SPECS = []
        _COUNTS.clear()
        _FIRED.clear()


def active() -> bool:
    """One cheap read — hot loops guard their check() call on this."""
    return bool(_SPECS)


def counts() -> Dict[str, int]:
    """Per-site OPERATION counts (test observability) — retries of one
    operation re-check the same index, so they don't advance this."""
    with _LOCK:
        return dict(_COUNTS)


def next_index(site: str) -> int:
    """Allocate the next 1-based index for one REAL operation at `site`.
    run_resilient calls this once per invocation and re-checks the same
    index on every retry attempt — otherwise a retry would advance the
    counter and shift where a later spec on the same site fires (a plan
    author counts operations, not attempts)."""
    if site not in SITES:
        raise ValueError(f"unknown fault site {site!r}")
    with _LOCK:
        _COUNTS[site] = _COUNTS.get(site, 0) + 1
        return _COUNTS[site]


def fired() -> Dict[str, int]:
    """Per-site injected-failure counts (test observability)."""
    with _LOCK:
        return dict(_FIRED)


def check(site: str, index: Optional[int] = None) -> None:
    """Raise the armed fault for `site`, if any. Called BEFORE the real
    work at every instrumented site, so a fired fault never leaves partial
    state behind. `index` is the operation's index — run_resilient
    allocates it via next_index once per operation (or passes the 1-based
    global step for fit/dispatch) and re-checks the SAME index on
    retries; a bare check() allocates its own."""
    if site not in SITES:
        raise ValueError(f"unknown fault site {site!r}")
    if not _SPECS:
        return
    idx = next_index(site) if index is None else int(index)
    with _LOCK:
        for spec in _SPECS:
            if spec.site == site and spec.should_fire(idx):
                spec.fired += 1
                _FIRED[site] = _FIRED.get(site, 0) + 1
                permanent = spec.permanent
                break
        else:
            return
    tel.event("fault/injected", cat="fault", site=site, index=idx,
              permanent=permanent)
    cls = PermanentInjectedFault if permanent else InjectedFault
    raise cls(f"injected fault at {site} (index {idx}"
              + (", permanent)" if permanent else ")"))


def poison(site: str, index: Optional[int] = None) -> bool:
    """Non-raising variant of check(): True when the armed fault for
    `site` fires at `index`. Used by sites that model SILENT corruption
    (health/nonfinite — the fit loop NaN-poisons the params and keeps
    going so the numerics sentinel, not an exception, must catch it).
    Emits the same fault/injected telemetry event as check()."""
    if site not in SITES:
        raise ValueError(f"unknown fault site {site!r}")
    if not _SPECS:
        return False
    idx = next_index(site) if index is None else int(index)
    with _LOCK:
        for spec in _SPECS:
            if spec.site == site and spec.should_fire(idx):
                spec.fired += 1
                _FIRED[site] = _FIRED.get(site, 0) + 1
                permanent = spec.permanent
                break
        else:
            return False
    tel.event("fault/injected", cat="fault", site=site, index=idx,
              permanent=permanent, poison=True)
    return True
