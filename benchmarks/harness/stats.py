"""Metric arithmetic on plain numbers and plain request records. A request
record is a dict {arrival_s, ttft_s, admit_s, finish_s, n_tokens, outcome};
times are seconds from the start of the run, ttft_s counts from arrival_s.
A request that did not complete (shed, failed, timed out, or finished after
the drain limit) is missing every latency: it counts in the percentile as
+inf, so failures push a tail up and never flatter it."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics; +inf entries sort last, and a percentile that lands on one
    is +inf. Empty input has no percentile."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no samples")
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if math.isinf(v[hi]):
        return math.inf
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def spread(values) -> float:
    """(Q3 - Q1) / median with statistics.quantiles(n=4): the contract's
    spread of a set of runs."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def completed(req: dict, deadline_s: float) -> bool:
    return (req["outcome"] == "done" and req["finish_s"] is not None
            and req["finish_s"] <= deadline_s)


def ttft_ms(req: dict, deadline_s: float) -> float:
    if not completed(req, deadline_s) or req["ttft_s"] is None:
        return math.inf
    return 1e3 * req["ttft_s"]


def tpot_ms(req: dict, deadline_s: float) -> float:
    """(finish - first token) / (tokens - 1): the gap a user sees between
    tokens, other requests' prefill stalls included. A one-token answer has
    no gap and is left out by the caller."""
    if not completed(req, deadline_s):
        return math.inf
    first = req["arrival_s"] + req["ttft_s"]
    return 1e3 * (req["finish_s"] - first) / (req["n_tokens"] - 1)


def queue_wait_ms(req: dict, deadline_s: float) -> float:
    if not completed(req, deadline_s) or req["admit_s"] is None:
        return math.inf
    return 1e3 * max(0.0, req["admit_s"] - req["arrival_s"])


def prefill_wave_ms(req: dict) -> float:
    """Prefill dispatch to first token on the host: ttft minus queue wait."""
    return 1e3 * (req["ttft_s"] - max(0.0, req["admit_s"] - req["arrival_s"]))


def serve_summary(reqs, window_s: float, drain_limit_s: float) -> dict:
    """Every serving number the cell reports, from request records."""
    deadline = window_s + drain_limit_s
    done = [r for r in reqs if completed(r, deadline)]
    multi = [r for r in reqs if not completed(r, deadline) or r["n_tokens"] > 1]
    out = {"attempted": len(reqs), "failed": len(reqs) - len(done),
           "completed": len(done),
           "output_tokens_completed": sum(r["n_tokens"] for r in done),
           "ttft_samples": len(reqs), "tpot_samples": len(multi)}
    out["serve_tokens_per_s"] = tokens_per_s(out["output_tokens_completed"],
                                             window_s)
    ttft = [ttft_ms(r, deadline) for r in reqs]
    tpot = [tpot_ms(r, deadline) for r in multi]
    for q in (50, 95):
        out[f"ttft_p{q}_ms"] = percentile(ttft, q)
        out[f"tpot_p{q}_ms"] = percentile(tpot, q)
    out["queue_wait_p95_ms"] = percentile(
        [queue_wait_ms(r, deadline) for r in reqs], 95)
    if done:
        out["prefill_wave_ms"] = statistics.median(
            prefill_wave_ms(r) for r in done)
        out["last_finish_s"] = max(r["finish_s"] for r in done)
    return out


def tokens_per_s(tokens: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError(f"a rate over {seconds} s")
    return tokens / seconds
