"""The admission policy of the serving data plane: the order waiting
requests are served in and the shed-or-queue decisions (PR 11: permanent
sheds, queue-cap displacement, deadline/TTFT staleness sweeps). A replica's
scheduler owns one `AdmissionControl`; a fleet uses the same class for
cross-replica admission, so request policy is decided once."""

from __future__ import annotations

from typing import TYPE_CHECKING, Callable, List, Optional, Tuple

if TYPE_CHECKING:
    from flexflow_tpu.serving.scheduler import Request


def _urgency(r: Request):
    return (r.priority, r.arrival_s, r.rid)


class AdmissionControl:
    """The admission policy brain (PR 11 machinery, lifted out of the
    replica scheduler so one instance can guard a whole fleet). Decisions
    only, no side effects: the caller — a replica scheduler or the fleet
    control plane — owns shedding, telemetry, and terminal records, so
    the single-replica path emits bitwise the same events it always did.

    `pages_needed`/`capacity_pages` are probes into a representative
    KV cache (replicas are homogeneous); `overhead_tokens` is the
    dispatch-ahead + speculation slack every admission reserves."""

    def __init__(self, seq: int, max_context: int = 0, queue_cap: int = 0,
                 ttft_budget_ms: float = 0.0, overhead_tokens: int = 0,
                 pages_needed: Optional[Callable[[int], int]] = None,
                 capacity_pages: Optional[Callable[[], int]] = None):
        self.seq = int(seq)
        self.max_context = int(max_context or 0)
        self.queue_cap = int(queue_cap or 0)
        self.ttft_budget_ms = float(ttft_budget_ms or 0.0)
        self.overhead_tokens = int(overhead_tokens)
        self.pages_needed = pages_needed
        self.capacity_pages = capacity_pages

    def permanent_shed_reason(self, req: Request) -> Optional[str]:
        """A reason means the request can NEVER be served (fixed prefill
        window, operator context ceiling, or two-tier page capacity) —
        distinct from transient backpressure, which queues."""
        if len(req.prompt) > self.seq:
            # the prefill program's window is fixed at `seq`; silently
            # truncating would serve a different request than the one sent
            return "prompt_too_long"
        if self.max_context and \
                len(req.prompt) + req.max_new_tokens > self.max_context:
            return "over_max_context"
        need = len(req.prompt) + req.max_new_tokens + self.overhead_tokens
        if self.pages_needed is not None and \
                self.pages_needed(need) > self.capacity_pages():
            # permanent by CAPACITY, not occupancy: no sequence of
            # evictions/spills frees enough pages across BOTH tiers
            return "prompt_too_long"
        return None

    def queue_or_displace(self, req: Request,
                          waiting: List[Request]) -> Optional[Request]:
        """Queue-cap shed-or-queue: returns the displaced victim (the
        lowest-priority waiter, or the arrival itself when nothing waiting
        is less urgent) for the caller to shed as `queue_full`; None means
        the arrival simply queued. Mutates `waiting`."""
        if self.queue_cap and len(waiting) >= self.queue_cap:
            worst = max(waiting, key=_urgency)
            if _urgency(req) < _urgency(worst):
                waiting.remove(worst)
                waiting.append(req)
                return worst
            return req
        waiting.append(req)
        return None

    def stale(self, waiting: List[Request], now_s: float,
              ema_serve_ms: float) -> List[Tuple[Request, str]]:
        """Deadline/TTFT-budget sweep: removes and returns the waiters
        that can no longer be served in time (elapsed wait plus the EMA
        prefill service estimate blows the budget)."""
        out: List[Tuple[Request, str]] = []
        for r in list(waiting):
            waited_ms = 1e3 * (now_s - r.arrival_s)
            if r.deadline_s is not None and now_s > r.arrival_s + r.deadline_s:
                waiting.remove(r)
                out.append((r, "deadline"))
            elif self.ttft_budget_ms and \
                    waited_ms + ema_serve_ms > self.ttft_budget_ms:
                waiting.remove(r)
                out.append((r, "ttft_budget"))
        return out
