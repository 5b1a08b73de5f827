"""What a NAMED part of the program says of itself, two ways.

`scope` (with `program`): device milliseconds a unit of the program (a
prefill wave, a decode step) of the operations that the program's own
compiled HLO puts under that `jax.named_scope`: scope_roofline's time
without a need beside it (the intervals and the join by instruction name
are scope_device's: only intervals wholly in the steady window; chips
averaged).

`span` (with `num`, `den`): `scale * sum(args[num]) / args[den]` of the
NEWEST span of that name anywhere in the program's ring (a compile span lies
outside the measured window's root, where ring_stat does not look); `num` a
name or several.

A program without the scope, the span or the arguments (a parent commit from
before them) gives None: nothing to read, the metric is left out."""

import bisect

from readers import ring_stat, scope_device


def _span_ratio(span, num, den, scale):
    found = [s for s in (ring_stat.ring() or ()) if s.name == span and s.args]
    if not found:
        return None
    args = found[-1].args
    nums = [num] if isinstance(num, str) else list(num)
    if any(n not in args for n in nums) or not args.get(den):
        return None
    return scale * sum(args[n] for n in nums) / args[den]


def _scope_ms(run, name, program, scope):
    attribution = scope_device._attribution()
    if attribution is None or not hasattr(attribution, "instructions_under") \
            or run.trace is None or not run.trace.devices:
        return None
    found = scope_device._intervals(run, name, program)
    if not found:
        return None
    registered, per, _spans = scope_device.PROGRAMS[program]
    names = {n for r in registered
             for found_in in attribution.instructions_under(r, scope)
             for n in found_in}
    units = sum(w for _lo, _hi, w in found)
    if not names or not units:
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
    if not events:
        return None
    run.note(metric=name, scope=scope, program=program, per=per, units=units,
             events=events, instructions=len(names))
    return scope_ns / 1e6 / units


def read(run, name, program=None, scope=None, span=None, num=None, den=None,
         scale=1.0):
    if span is not None:
        return _span_ratio(span, num, den, scale)
    return _scope_ms(run, name, program, scope)
