"""Device time inside the program's own spans: the ring's clock
(perf_counter_ns) laid over the device trace's (the profiler session's).

Both ends of one interval exist on both clocks: the benchmark's anchor span
(in run.trace.host) and the program's root span inside it (the last one in
the ring). Serving aligns end to end (between sched.run
returning and bench/traced_run closing, cells/serve.py only builds the
request records), training start to start (cells/train.py calls cm.fit
within microseconds of opening bench/traced_fit and blocks on parameters
after it). No alignment, and None with a note, where the aligned root does
not lie inside the anchor.

read(): device-busy milliseconds inside intervals of the traced root that
lie in the steady window, per `per_arg` of the interval's closing span (or
per interval). An interval runs from the first `start_span` to the end of
the `end_span` that shares its `group_arg` (a decode window: first
serve/decode/dispatch to the end of serve/decode/window_sync); without
`start_span` it is the `end_span` itself (a serve/admit wave)."""

from harness import trace_reduce
from readers import ring_stat

# by cells/<kind>.py: (the benchmark's anchor span, the program's root span
# inside it, the end at which the two coincide)
ANCHOR = {"serve": ("bench/traced_run", "serve/run", "end"),
          "train": ("bench/traced_fit", "fit/call", "start")}
# how far outside the anchor the aligned root may lie: the ends that are
# aligned coincide by construction, the other end has the benchmark's own
# lines between the two spans
SLACK_NS = 1_000_000


def aligned_root(run, name):
    """(root span, its descendants by time, ring ns -> trace ns offset), or
    None with a note."""
    spans = ring_stat.ring()
    if spans is None or run.trace is None:
        return None
    anchor, root, align = ANCHOR[run.cell.traffic["kind"]]
    anchors = [o for o in run.trace.host if o.name == anchor]
    roots = [s for s in spans if s.name == root]
    if not anchors or not roots:
        run.note(metric=name, not_aligned=f"no {anchor} in the trace" if
                 not anchors else f"no {root} in the ring")
        return None
    a, r = anchors[-1], roots[-1]
    offset = a.start - r.start_ns if align == "start" else a.end - r.end_ns
    if r.start_ns + offset < a.start - SLACK_NS or \
            r.end_ns + offset > a.end + SLACK_NS:
        run.note(metric=name, not_aligned=f"the last {root} "
                 f"({(r.end_ns - r.start_ns) / 1e9:.6f} s) does not lie inside "
                 f"{anchor} ({(a.end - a.start) / 1e9:.6f} s)")
        return None
    return r, ring_stat.inside(spans, r), offset


def intervals(kids, end_span, start_span=None, group_arg=None, per_arg=None):
    """[(start ns, end ns, weight)] on the ring's clock."""
    first = {}          # group -> start of its first `start_span`
    for s in kids:
        if s.name == start_span:
            key = (s.args or {}).get(group_arg)
            first[key] = min(first.get(key, s.start_ns), s.start_ns)
    out = []
    for e in (s for s in kids if s.name == end_span):
        args = e.args or {}
        start = e.start_ns if start_span is None else first.get(args.get(group_arg))
        if start is not None and start <= e.start_ns:
            out.append((start, e.end_ns,
                        1 if per_arg is None else args.get(per_arg, 0)))
    return out


def read(run, name, end_span, start_span=None, group_arg=None, per_arg=None):
    found = aligned_root(run, name)
    if found is None or not run.trace.devices:
        return None
    _root, kids, offset = found
    lo, hi = run.window
    busy_ns = wall_ns = weight = count = 0
    for start, end, w in intervals(kids, end_span, start_span, group_arg,
                                   per_arg):
        start, end = start + offset, end + offset
        if start < lo or end > hi:
            continue            # only intervals wholly in the steady part
        per_chip = [sum(e - s for s, e in
                        trace_reduce.busy_intervals(ops, (start, end)))
                    for ops in run.trace.devices.values()]
        busy_ns += sum(per_chip) / len(per_chip)
        wall_ns += end - start
        weight += w
        count += 1
    if not weight:
        return None
    # how much the number rests on: a steady window that is mostly prefill
    # waves holds few decode windows
    run.note(metric=name, intervals=count, per=weight,
             wall_ms=wall_ns / 1e6, device_busy_ms=busy_ns / 1e6)
    return busy_ns / 1e6 / weight
