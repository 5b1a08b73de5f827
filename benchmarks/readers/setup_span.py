"""Set-up, in parts, from the program's own spans in its ring
(flexflow_tpu.telemetry, ISSUE 54): `start/import` and `start/import_*` (the
packages' imports), `serve/compile_serving` / `compile/compile_model` (the
search), `serve/init` / `compile/init` (the weights) and JAX's compile phases
(`jax/trace`, `jax/lower`, `jax/backend_compile`), each under the span that
made the first call. Everything read ENDED before the window's first root span
(ring_stat.window_roots), as ring_stat.before_window_s reads. A program
without `start/import` (a parent commit from before the spans) gives None.

`part` picks the metric:

    import       durations of `start/import` + `start/import_*` (its thread's)
    spans        whole duration of the `spans` (their jax/* children included)
    trace_lower  own seconds of jax/trace + jax/lower records with an
                 ancestor among `under`
    caller       from the end of `start/import` to the window's first root,
                 less the union of every program span on the importing thread
                 in between: what no program span covers (under run.py:
                 jax.devices(), the cells' imports, the family's graph
                 building, the traffic generator)

The first metric read in a run emits ONE `metric_note`, the closing sum: the
interval from the start of `start/import` to the window's first root in parts
that do not overlap,

    import_s + caller_s + covered_s          (= interval_s)
    covered_s = trace_lower_s by program (wave, step, init, every other
                parent by name) + backend_compile_s + search_self_s
                + init_self_s + program_other_s

where the search's and the init's SELF time is the span less the jax/* records
under it, and `program_other_s` is what is left of the covered time: warm-up's
execution and waits. Beside it the ten largest (phase, fun) records of the
wave, of the step and of the init, the compile phases under NO span
(`unparented`), and
`setup_s` as the cell stamped it with its difference from `interval_s`."""

from readers import ring_stat

PHASES = ("jax/trace", "jax/lower")
COMPILE = "jax/backend_compile"
IMPORT = "start/import"
SEARCH = ("serve/compile_serving", "compile/compile_model")
INIT = ("serve/init", "compile/init")
PROGRAMS = {"wave": ("serve/admit",),
            "step": ("serve/decode/dispatch", "fit/dispatch"),
            "init": INIT}


def _union(intervals):
    """Sorted, disjoint [lo, hi] covering the same points."""
    out = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def _length(merged):
    return sum(hi - lo for lo, hi in merged)


def _clip(spans, lo, hi):
    return [(max(s.start_ns, lo), min(s.end_ns, hi)) for s in spans]


def _seconds(records):
    return sum(s.args["seconds"] for s in records)


class Anatomy:
    """The ring's records that ended before `t0`, with who lies under whom.
    `first` is the `start/import` span (None in a ring without it); the later
    imports (`start/import_*`) are those on its thread."""

    def __init__(self, spans, t0):
        self.t0 = t0
        self.spans = [s for s in spans if s.end_ns <= t0]
        self.by_id = {s.id: s for s in spans}
        self.first = next((s for s in self.spans if s.name == IMPORT), None)
        self.imported = [s for s in self.spans if self.first is not None
                         and s.thread == self.first.thread
                         and (s.name == IMPORT
                              or s.name.startswith(IMPORT + "_"))]
        self.jax = [s for s in self.spans if s.name.startswith("jax/")
                    and s.args and "seconds" in s.args]
        self._chains = {}

    def ancestors(self, s):
        """Names from the direct parent outwards (`under`: the trace-time
        span a gathered record's phases lay right under)."""
        if s.id not in self._chains:
            names = [s.args["under"]] if s.args and s.args.get("under") else []
            seen, at = set(), s
            while at.parent in self.by_id and at.parent not in seen:
                seen.add(at.parent)
                at = self.by_id[at.parent]
                names.append(at.name)
            self._chains[s.id] = names
        return self._chains[s.id]

    def named(self, names):
        return [s for s in self.spans if s.name in names]

    def under(self, names, phases):
        """jax/* records of the `phases` with an ancestor among `names`."""
        return [s for s in self.jax if s.name in phases
                and any(a in names for a in self.ancestors(s))]

    def _union_ns(self, spans, lo=0):
        return _length(_union(_clip(spans, lo, self.t0)))

    def span_s(self, names):
        return self._union_ns(self.named(names)) / 1e9

    def import_s(self):
        return self._union_ns(self.imported) / 1e9

    def covered_ns(self):
        """What the program's spans on the importing thread cover between
        the end of `start/import` and t0 (the later imports among them)."""
        lo = self.first.end_ns
        return self._union_ns(
            [s for s in self.spans if s.thread == self.first.thread
             and not s.name.startswith("jax/") and s.end_ns > lo], lo)

    def caller_s(self):
        return (self.t0 - self.first.end_ns - self.covered_ns()) / 1e9

    def parts(self, setup_s=None):
        first = self.first
        # the later imports are spans too: counted as imports, not covered
        covered_s = self.covered_ns() / 1e9 - (
            self.import_s() - (first.end_ns - first.start_ns) / 1e9)
        parented = [s for s in self.jax if s.parent]
        lowered = [s for s in parented if s.name in PHASES]
        by_program, rest = {}, lowered
        for program, names in PROGRAMS.items():
            found = [s for s in rest
                     if any(a in names for a in self.ancestors(s))]
            if found:
                by_program[program] = found
            ids = {s.id for s in found}
            rest = [s for s in rest if s.id not in ids]
        other = {}
        for s in rest:
            parent = (self.ancestors(s) or ["(gone)"])[0]
            other[parent] = other.get(parent, 0.0) + s.args["seconds"]
        self_s = {key: self.span_s(names) - _seconds(
            self.under(names, PHASES + (COMPILE,)))
            for key, names in (("search_self_s", SEARCH),
                               ("init_self_s", INIT))}
        compiled = _seconds(s for s in parented if s.name == COMPILE)
        lone = [s for s in self.jax if not s.parent]
        out = {
            "interval_s": (self.t0 - first.start_ns) / 1e9,
            "import_s": self.import_s(),
            "imports": {s.name: dict(s.args or {},
                                     seconds=(s.end_ns - s.start_ns) / 1e9)
                        for s in self.imported},
            "caller_s": self.caller_s(),
            "covered_s": covered_s,
            "trace_lower_s": {
                **{p: _seconds(found) for p, found in by_program.items()},
                **dict(sorted(other.items(), key=lambda kv: -kv[1]))},
            "backend_compile_s": compiled,
            **self_s,
            "program_other_s": covered_s - _seconds(lowered) - compiled
            - sum(self_s.values()),
            "largest": {p: _largest(found)
                        for p, found in by_program.items()},
            "unparented": {"seconds": _seconds(lone),
                           "largest": _largest(lone, 3)},
            "records": len(self.spans),
            "gathered": sum(1 for s in self.jax if "count" in s.args),
        }
        backend = self.named(("start/backend",))
        if backend:
            out["backend_start"] = {
                "seconds": sum(s.end_ns - s.start_ns for s in backend) / 1e9,
                "already_up": [s.args.get("already_up") for s in backend]}
        if setup_s is not None:
            out["setup_s"] = setup_s
            out["setup_s_minus_interval_s"] = setup_s - out["interval_s"]
        return out


def _largest(records, n=10):
    """[phase, fun, count, seconds] of the longest, by own seconds."""
    top = sorted(records, key=lambda s: -s.args["seconds"])[:n]
    return [[s.name, s.args.get("fun"), s.args.get("count", 1),
             s.args["seconds"]] for s in top]


_read = {}      # of the run being read: the anatomy, made (and noted) once


def anatomy(run, name):
    if _read.get("run") is not run:
        _read.clear()
        _read["run"] = run
        _read["anatomy"] = _anatomy(run, name)
    return _read["anatomy"]


def _anatomy(run, name):
    spans = ring_stat.ring()
    if spans is None or not any(s.name == IMPORT for s in spans):
        return None         # a program from before the spans
    roots = ring_stat.window_roots(run, spans)
    if not roots:
        run.note(metric=name, nothing_to_read="the ring holds no "
                 f"{ring_stat.ROOT[run.cell.traffic['kind']]} span of the "
                 "window")
        return None
    found = Anatomy(spans, min(r.start_ns for r in roots))
    if found.first is None:
        return None
    from flexflow_tpu import telemetry

    run.note(metric="setup_s", by="readers/setup_span",
             ring_peak=telemetry.ring_peak(),
             **found.parts(run.facts.get("setup_s")))
    return found


def read(run, name, part, spans=(), under=()):
    found = anatomy(run, name)
    if found is None:
        return None
    if part == "import":
        return found.import_s()
    if part == "spans":
        return found.span_s(tuple(spans)) if found.named(spans) else None
    if part == "trace_lower":
        records = found.under(tuple(under), PHASES)
        return _seconds(records) if records else None
    if part == "caller":
        return found.caller_s()
    raise ValueError(f"metric {name}: setup_span has no part {part!r}")
