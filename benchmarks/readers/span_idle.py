"""The device's idle time in the steady window by what the program was in:
each idle gap of the first chip goes to the innermost program span (below
the aligned root, request-stage spans `serve/req/*` left out: they tile a
request's whole life and would cover everything) that covers the gap's
midpoint. The value is the share of idle time that no such span covers, in
%; the note lists idle seconds by span."""

import bisect
import itertools

from harness import trace_reduce
from readers import span_device

SKIP_PREFIX = "serve/req/"


def innermost(spans, starts, latest_end, t):
    """Name of the latest-started span that covers `t`, or None. `spans`
    sorted by start, `starts` their starts, `latest_end[i]` the latest end
    among spans[:i + 1] (so the search stops where nothing earlier can
    reach `t`)."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0 and latest_end[i] > t:
        if spans[i].end_ns > t:
            return spans[i].name
        i -= 1
    return None


def read(run, name):
    found = span_device.aligned_root(run, name)
    if found is None or not run.trace.devices:
        return None
    _root, kids, offset = found
    spans = sorted((s for s in kids if not s.name.startswith(SKIP_PREFIX)),
                   key=lambda s: s.start_ns)
    starts = [s.start_ns for s in spans]
    latest_end = list(itertools.accumulate((s.end_ns for s in spans), max))
    lo, hi = run.window
    ops = run.trace.devices[min(run.trace.devices)]
    edges = [lo] + [t for iv in trace_reduce.busy_intervals(ops, run.window)
                    for t in iv] + [hi]
    by_name, idle = {}, 0
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        inner = innermost(spans, starts, latest_end,
                          (g0 + g1) // 2 - offset)
        by_name[inner] = by_name.get(inner, 0) + (g1 - g0)
        idle += g1 - g0
    if not idle:
        return None
    ranked = sorted(((n, v) for n, v in by_name.items() if n is not None),
                    key=lambda kv: -kv[1])
    run.note(metric=name, idle_s=idle / 1e9,
             idle_by_span_s=[[n, v / 1e9] for n, v in ranked[:12]],
             unattributed_s=by_name.get(None, 0) / 1e9)
    return 100.0 * by_name.get(None, 0) / idle
