"""A share of a peak, in %, for intervals of the program's own spans: the
least time the cell's chips could take for what one interval NEEDS over the
device-busy time measured inside it.

The intervals and their device time are span_device's (the ring's clock
laid over the device trace's; only intervals wholly in the steady part of
the traced run). What an interval needs comes from the family's own module
harness/flops_<family>.py: its function `need(config, system, traffic,
counters)` -> {"flops", "bytes"}, where `counters` are the arguments that
the program set on its `counter_span`s inside those intervals (routed rows,
experts that received one), each as a mean per `per_arg` of the interval's
closing span (a decode step) or per interval (a prefill wave). A family
without such a module, a program without the counters (a parent commit from
before them) or a trace without such intervals gives None: nothing to read."""

import importlib

from harness import flops, trace_reduce
from readers import span_device


def read(run, name, need, end_span, counter_span, counters, start_span=None,
         group_arg=None, per_arg=None):
    try:
        family = importlib.import_module(
            f"harness.flops_{run.cell.config['family']}")
    except ImportError:
        return None
    found = span_device.aligned_root(run, name)
    if found is None or not run.trace.devices:
        return None
    _root, kids, offset = found
    lo, hi = run.window
    busy_ns = weight = 0
    kept = []
    for start, end, w in span_device.intervals(kids, end_span, start_span,
                                               group_arg, per_arg):
        if start + offset < lo or end + offset > hi or not w:
            continue
        per_chip = [sum(e - s for s, e in trace_reduce.busy_intervals(
            ops, (start + offset, end + offset)))
            for ops in run.trace.devices.values()]
        busy_ns += sum(per_chip) / len(per_chip)
        weight += w
        kept.append((start, end))
    inside = [s for s in kids if s.name == counter_span and s.args
              and all(c in s.args for c in counters)
              and any(a <= s.start_ns and s.end_ns <= b for a, b in kept)]
    if not busy_ns or not inside:
        return None
    mean = {c: sum(s.args[c] for s in inside) / weight for c in counters}
    needed = getattr(family, need)(run.cell.config, run.cell.system,
                                   run.cell.traffic, mean)
    least = flops.roofline_seconds(needed, run.peaks, chips=run.cell.chips)
    measured = busy_ns / 1e9 / weight
    run.note(metric=name, bound=least["bound"], least_ms=1e3 * least["seconds"],
             measured_ms=1e3 * measured, intervals=len(kept), per=weight,
             counters=mean)
    return 100.0 * least["seconds"] / measured
