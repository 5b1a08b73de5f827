"""Device time by what the program says each instruction belongs to: graph
layer, op type and phase (forward / backward / update / loss / other).

The device trace names an event by its HLO instruction ("fusion.12") and
nothing else; the program's own compiled HLO says, per instruction, which
layer and phase it came from (`flexflow_tpu.attribution.op_scopes(<program>)`,
rendered on demand from the executables that ran; absent on a program from
before it: nothing to read, the metric is left out). The join is by the
instruction's name, inside the intervals in which a program ran, laid over
the device clock as `span_device` does:

    train_step     the traced `fit/call` (to the end of the window: the
                   device runs behind the host), per its `steps`
    serve/prefill  the `serve/admit` waves (the prefill program, then the
                   cache's commit programs: `serve/commit`), per wave
    serve/decode   a decode window's first `serve/decode/dispatch` to the
                   end of its `serve/decode/window_sync`, per its `steps`

Only intervals wholly in the steady window count; chips are averaged.
A name that two programs of an interval give different scopes is
`ambiguous`, a name in no map `unattributed`: both are reported, neither is
guessed. Containers (`while`, `conditional`, `call`) are left out and their
bodies counted, as `trace_reduce.top_ops` does; what of a container's time no
operation of its body covers is the note's `container_self_ms`.

read(): milliseconds per unit (`per`: the program's own, named in the
metric's file so that it reads alone) of the scopes that `select` picks
(`phase` and / or `op_types`, each a name or a list), or with
`share_of_busy` their share of the intervals' device-busy time in %, over
one program or several. The first metric of a run
that asks for a program emits ONE `metric_note` with the whole table of that
program: by op type and phase, by operation name inside each op type (the
instruction's name without its number, as `device_ops` prints it), the costliest
layers, the share in `mixed` fusions, and the costliest unattributed names."""

import bisect

from harness import trace_reduce
from readers import ring_stat, span_device

# program -> (the registered programs that may run inside its intervals,
#             the unit a value is per,
#             span_device.intervals arguments; None: the root span itself)
PROGRAMS = {
    "train_step": (("train_step",), "steps", None),
    "serve/prefill": (("serve/prefill", "serve/commit"), "waves",
                      {"end_span": "serve/admit"}),
    "serve/decode": (("serve/decode",), "steps",
                     {"end_span": "serve/decode/window_sync",
                      "start_span": "serve/decode/dispatch",
                      "group_arg": "window"}),
}
OUTSIDE = "(no layer)"


def _attribution():
    try:
        from flexflow_tpu import attribution
    except ImportError:
        return None
    return attribution if hasattr(attribution, "op_scopes") else None


def _overlap_ns(merged, lo, hi):
    """How much of [lo, hi) the sorted, disjoint intervals cover."""
    i = max(0, bisect.bisect_right(merged, [lo]) - 1)
    total = 0
    for s, e in merged[i:]:
        if s >= hi:
            break
        total += max(0, min(e, hi) - max(s, lo))
    return total


def _intervals(run, name, program):
    """[(start, end, weight)] on the trace's clock, wholly in the window;
    the weight is the interval's `steps`, or 1 a wave."""
    found = span_device.aligned_root(run, name)
    if found is None:
        return None
    root, kids, offset = found
    lo, hi = run.window
    _registered, per, spans = PROGRAMS[program]
    per_arg = "steps" if per == "steps" else None
    if spans is None:
        return [(max(lo, root.start_ns + offset), hi,
                 (root.args or {}).get(per_arg, 0))]
    return [(s + offset, e + offset, w)
            for s, e, w in span_device.intervals(kids, per_arg=per_arg, **spans)
            if s + offset >= lo and e + offset <= hi]


_tables = {}        # of the run being read: "run" and program -> table


def table(run, name, program):
    """{"by_scope": {OpScope: ns}, "busy_ns", "units", "intervals"} of one
    program in this run, or None; computed once a run, with its note."""
    if _tables.get("run") is not run:
        _tables.clear()
        _tables["run"] = run
    if program not in _tables:
        _tables[program] = _table(run, name, program)
    return _tables[program]


def _table(run, name, program):
    attribution = _attribution()
    if attribution is None or run.trace is None or not run.trace.devices:
        return None
    found = _intervals(run, name, program)
    if not found:
        return None
    registered, per, _spans = PROGRAMS[program]
    maps = [m for r in registered for m in attribution.op_scopes(r)]
    if not maps:
        run.note(metric=name, program=program, nothing_to_read="no program "
                 f"registered as {registered} has run")
        return None
    merged = attribution.merge_scopes(maps)
    chips = len(run.trace.devices)
    by_scope, by_name, busy_ns, leaf_ns = {}, {}, 0.0, 0.0
    for ops in run.trace.devices.values():
        # busy time as span_device reads it (the union of the operations'
        # intervals), once over the window; an interval takes its share
        busy = trace_reduce.busy_intervals(ops, run.window)
        leaves = trace_reduce.busy_intervals(
            [o for o in ops if attribution.fold_name(o.name)
             not in attribution.CONTAINERS], run.window)
        starts = [o.start for o in ops]
        for lo, hi, _w in found:
            busy_ns += _overlap_ns(busy, lo, hi) / chips
            leaf_ns += _overlap_ns(leaves, lo, hi) / chips
            # by name: the operations that begin inside the interval
            events = [(o.name, o.start, min(o.end, hi)) for o in
                      ops[bisect.bisect_left(starts, lo):
                          bisect.bisect_left(starts, hi)]]
            for (s, folded), ns in attribution.device_time_by_scope(
                    events, merged, by_name=True).items():
                by_scope[s] = by_scope.get(s, 0.0) + ns / chips
                if s.phase not in (attribution.UNATTRIBUTED,
                                   attribution.AMBIGUOUS):
                    k = (s.op_type or OUTSIDE, folded)
                    by_name[k] = by_name.get(k, 0.0) + ns / chips
    units = sum(w for _lo, _hi, w in found)
    if not units:
        return None
    out = {"by_scope": by_scope, "by_name": by_name, "busy_ns": busy_ns,
           "container_self_ns": busy_ns - leaf_ns, "units": units,
           "intervals": len(found)}
    run.note(metric=name, program=program, per=per, **_note(out, attribution))
    return out


def _ranked(d, n=None):
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def _note(t, attribution):
    """The table as a fact: device ms per unit."""
    per_ms = 1.0 / (1e6 * t["units"])
    phases, layers, unattributed = {}, {}, {}
    names = {}
    for (kind, folded), ns in t["by_name"].items():
        names.setdefault(kind, {})[folded] = ns * per_ms
    mixed, inferred = {}, {}
    ambiguous = 0.0
    for s, ns in t["by_scope"].items():
        ms = ns * per_ms
        if s.phase == attribution.UNATTRIBUTED:
            unattributed[s.opcode] = unattributed.get(s.opcode, 0.0) + ms
            continue
        if s.phase == attribution.AMBIGUOUS:
            ambiguous += ms
            continue
        kind = s.op_type or OUTSIDE
        by_phase = phases.setdefault(kind, {})
        by_phase[s.phase] = by_phase.get(s.phase, 0.0) + ms
        if s.layer:
            layers[s.layer] = layers.get(s.layer, 0.0) + ms
        if s.mixed:
            mixed[s.phase] = mixed.get(s.phase, 0.0) + ms
        if s.inferred:
            inferred[s.phase] = inferred.get(s.phase, 0.0) + ms
    total = sum(t["by_scope"].values()) * per_ms
    by_phase_total = {}
    for by_phase in phases.values():
        for ph, ms in by_phase.items():
            by_phase_total[ph] = by_phase_total.get(ph, 0.0) + ms
    rendered = [dict(s.args or {}, seconds=(s.end_ns - s.start_ns) / 1e9)
                for s in (ring_stat.ring() or ()) if s.name == attribution.SPAN]
    return {
        "units": t["units"], "intervals": t["intervals"],
        "device_busy_ms": t["busy_ns"] * per_ms, "table_sum_ms": total,
        # busy time under a `while` / `conditional` / `call` that none of
        # its body's operations covers (the gaps between them): in
        # device_busy_ms, in no row of the table
        "container_self_ms": t["container_self_ns"] * per_ms,
        "ms_by_phase": by_phase_total,
        "ms_by_op_type_and_phase": phases,
        # by the instruction's name without its number, as `device_ops`
        # prints it: a plain instruction's is its opcode, a fusion's what
        # XLA called it (`fusion`, `convert_reduce_fusion`), a kernel's
        # its own (`ff_flash_attention_fwd`, `ragged-dot-none`)
        "ms_by_op_type_and_op_name": {k: dict(_ranked(v, 8))
                                      for k, v in names.items()},
        "costliest_layers_ms": _ranked(layers, 10),
        # of a phase's time, what lies in fusions whose body spans more
        # than one (layer, phase): credited to one, shared by several
        "mixed_fusion_share": {ph: ms / by_phase_total[ph]
                               for ph, ms in mixed.items()},
        # of a phase's time, what the compiler made (relayout copies,
        # prefetch slices: no name stack of their own) and the map scoped
        # by the instruction that uses it
        "inferred_scope_share": {ph: ms / by_phase_total[ph]
                                 for ph, ms in inferred.items()},
        "ambiguous_ms": ambiguous,
        "unattributed_ms": sum(unattributed.values()),
        "costliest_unattributed_ms": _ranked(unattributed, 5),
        "op_scopes_rendered": rendered,
    }


def _names(value):
    return None if value is None else \
        {value} if isinstance(value, str) else set(value)


def read(run, name, program, select, per=None, share_of_busy=False):
    programs = [program] if isinstance(program, str) else list(program)
    if per is not None and any(PROGRAMS[p][1] != per for p in programs):
        raise ValueError(f"metric {name}: per {per!r} is not the unit of "
                         f"{programs} ({[PROGRAMS[p][1] for p in programs]})")
    phases, op_types = _names(select.get("phase")), _names(select.get("op_types"))
    picked = busy = 0.0
    per_unit = []
    for prog in programs:
        t = table(run, name, prog)
        if t is None:
            continue
        ns = sum(v for s, v in t["by_scope"].items()
                 if (phases is None or s.phase in phases)
                 and (op_types is None or s.op_type in op_types))
        picked += ns
        busy += t["busy_ns"]
        per_unit.append(ns / 1e6 / t["units"])
    if not per_unit:
        return None
    if share_of_busy:
        return 100.0 * picked / busy if busy else None
    return sum(per_unit)
