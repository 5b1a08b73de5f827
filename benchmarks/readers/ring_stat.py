"""Statistics over the program's own spans, read from its in-memory ring
(flexflow_tpu.telemetry.ring_spans) in the benchmark's process: the measured
window with the profiler off, beside prefill_wave_ms and decode_step_ms.

The window's root spans follow from the cell's kind and the order in which
cells/<kind>.py calls the program: warm-up, the window, then the traced
run. Serving: the serve/run before the traced one (the measured sched.run);
training: the len(facts["fit_seconds"]) fit/call spans before the traced
one (the window's cm.fit calls). `stat` is one of the functions below; each
takes the window's roots, each with the spans that lie inside it. A program
without the ring (a parent commit from before it) gives None: nothing to
read."""

import statistics

ROOT = {"serve": "serve/run", "train": "fit/call"}   # by cells/<kind>.py


def ring():
    """The ring's spans, oldest first, or None where the program has none."""
    try:
        from flexflow_tpu import telemetry
    except ImportError:
        return None
    spans = getattr(telemetry, "ring_spans", None)
    return None if spans is None else spans()


def inside(spans, root):
    return [s for s in spans if s.id != root.id
            and root.start_ns <= s.start_ns and s.end_ns <= root.end_ns]


def window_roots(run, spans):
    kind = run.cell.traffic["kind"]
    roots = [s for s in spans if s.name == ROOT[kind]]
    n = len(run.facts.get("fit_seconds") or ()) if kind == "train" else 1
    if run.trace is not None:       # the traced call came last
        roots = roots[:-1]
    return roots[-n:] if n and len(roots) >= n else []


def _ms(spans):
    return sum(s.end_ns - s.start_ns for s in spans) / 1e6


def _named(spans, names):
    return [s for s in spans if s.name in names]


def median_ms(run, name, calls, spans):
    """Median duration of the `spans` inside the window."""
    found = [s for _r, kids in calls for s in _named(kids, spans)]
    return statistics.median(_ms([s]) for s in found) if found else None


def mean_arg(run, name, calls, spans, arg, scale=1.0):
    found = [s for _r, kids in calls for s in _named(kids, spans)
             if s.args and arg in s.args]
    return scale * statistics.fmean(s.args[arg] for s in found) if found else None


def arg_ratio(run, name, calls, spans, num, den, scale=1.0):
    """sum(args[num]) / sum(args[den]) over the `spans`."""
    found = [s for _r, kids in calls for s in _named(kids, spans) if s.args]
    total = sum(s.args.get(den, 0) for s in found)
    return scale * sum(s.args.get(num, 0) for s in found) / total if total else None


def ms_per(run, name, calls, spans, per_span, per_arg):
    """Summed duration of the `spans` over the sum of `per_span`'s
    `per_arg` (host milliseconds per decode step)."""
    kids = [s for _r, ks in calls for s in ks]
    per = sum(s.args[per_arg] for s in _named(kids, [per_span]) if s.args)
    return _ms(_named(kids, spans)) / per if per else None


def median_call_ms(run, name, calls, spans):
    """Median over the window's roots of the `spans`' summed duration."""
    return statistics.median(_ms(_named(kids, spans)) for _r, kids in calls)


def count_per_100(run, name, calls, spans, per_arg):
    """How many `spans` the window holds per 100 of the roots' `per_arg`."""
    per = sum(r.args[per_arg] for r, _k in calls if r.args)
    n = sum(len(_named(kids, spans)) for _r, kids in calls)
    return 100.0 * n / per if per else None


def _children_ms(root, kids):
    """Summed milliseconds of the root's own children, by name; a child
    that spans other children (fit/epoch, recorded when it ends) is left
    out, its parts are counted."""
    own = [s for s in kids if s.parent == root.id]
    by_name = {}
    for s in own:
        if not any(o.id != s.id and s.start_ns <= o.start_ns
                   and o.end_ns <= s.end_ns for o in own):
            by_name[s.name] = by_name.get(s.name, 0.0) + _ms([s])
    return by_name


def stall_ms(run, name, calls):
    """The longest root's duration less the median root's; the note names
    the child spans that hold most of the excess (each against its median
    over the other roots) and what no child covers."""
    if len(calls) < 2:
        return None
    worst, kids = max(calls, key=lambda c: c[0].end_ns - c[0].start_ns)
    excess = _ms([worst]) - statistics.median(_ms([r]) for r, _k in calls)
    by_name = _children_ms(worst, kids)
    others = [_children_ms(r, ks) for r, ks in calls if r.id != worst.id]
    usual = {n: statistics.median(o.get(n, 0.0) for o in others)
             for n in by_name}
    over = sorted(((by_name[n] - usual[n], n) for n in by_name), reverse=True)
    run.note(metric=name, excess_ms=excess,
             excess_by_child_ms=[[n, ms] for ms, n in over[:4]],
             outside_children_ms=_ms([worst]) - sum(by_name.values()))
    return excess


def before_window_s(run, name, calls, spans):
    """Seconds of the `spans` (JAX's compile phases) that ended before the
    window's first root began. Each record carries its own `seconds`: a
    phase's duration less the phases nested in it, or the sum over the
    many short phases it gathers; so nothing is counted twice."""
    t0 = min(r.start_ns for r, _k in calls)
    return sum(s.args["seconds"] for s in _named(ring(), spans)
               if s.end_ns <= t0)


STATS = {f.__name__: f for f in (median_ms, mean_arg, arg_ratio, ms_per,
                                 median_call_ms, count_per_100, stall_ms,
                                 before_window_s)}


def read(run, name, stat, **args):
    spans = ring()
    if spans is None:
        return None
    roots = window_roots(run, spans)
    if not roots:
        run.note(metric=name, nothing_to_read="the ring holds no "
                 f"{ROOT[run.cell.traffic['kind']]} span of the window")
        return None
    return STATS[stat](run, name, [(r, inside(spans, r)) for r in roots],
                       **args)
