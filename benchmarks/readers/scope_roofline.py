"""A named scope's share of its roofline, in %: the least time the cell's
chips could take for what the scope's work NEEDS in one unit of a program (a
prefill wave) over the device time of the operations that the program's own
compiled HLO puts under that `jax.named_scope`.

The intervals and the join by instruction name are scope_device's (the
`serve/admit` waves laid over the device clock; only intervals wholly in
the steady window; chips averaged); which instructions lie under the scope
is asked of the program (`flexflow_tpu.attribution.instructions_under`), and
what a unit needs of the family's own module harness/flops_<family>.py: its
function `need(config, system, traffic, counters)` -> {"flops", "bytes"},
with `counters` the arguments the program set on its `counter_span`s, each
as a mean per span. A program without that function, without the scope or
without the counters (a parent commit from before them) gives None: nothing
to read, the metric is left out."""

import bisect
import importlib

from harness import flops
from readers import scope_device, span_device


def read(run, name, program, scope, need, counter_span, counters):
    attribution = scope_device._attribution()
    if attribution is None or not hasattr(attribution, "instructions_under") \
            or run.trace is None or not run.trace.devices:
        return None
    try:
        family = importlib.import_module(
            f"harness.flops_{run.cell.config['family']}")
    except ImportError:
        return None
    found = scope_device._intervals(run, name, program)
    if not found or not hasattr(family, need):
        return None
    registered, _per, _spans = scope_device.PROGRAMS[program]
    names = {n for r in registered
             for found_in in attribution.instructions_under(r, scope)
             for n in found_in}
    if not names:
        return None
    chips = len(run.trace.devices)
    scope_ns, events = 0.0, 0
    for ops in run.trace.devices.values():
        starts = [o.start for o in ops]
        for lo, hi, _w in found:
            for o in ops[bisect.bisect_left(starts, lo):
                         bisect.bisect_left(starts, hi)]:
                if o.name in names:
                    scope_ns += (min(o.end, hi) - o.start) / chips
                    events += 1
    units = sum(w for _lo, _hi, w in found)
    _root, kids, _offset = span_device.aligned_root(run, name)
    inside = [s for s in kids if s.name == counter_span and s.args
              and all(c in s.args for c in counters)]
    if not scope_ns or not units or not inside:
        return None
    mean = {c: sum(s.args[c] for s in inside) / len(inside) for c in counters}
    needed = getattr(family, need)(run.cell.config, run.cell.system,
                                   run.cell.traffic, mean)
    least = flops.roofline_seconds(needed, run.peaks, chips=run.cell.chips)
    measured = scope_ns / 1e9 / units
    run.note(metric=name, scope=scope, bound=least["bound"],
             least_ms=1e3 * least["seconds"], measured_ms=1e3 * measured,
             units=units, events=events, instructions=len(names),
             counters=mean)
    return 100.0 * least["seconds"] / measured
