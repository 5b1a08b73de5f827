"""From the profiler's .xplane.pb to numbers: device busy and idle time, a
named kernel's device time, collective time, the operations that took most
time and the longest idle gaps by what the benchmark's host code was in.

Read with jax.profiler.ProfileData and nothing else. What the reduction
relies on, as seen on a TPU v5e trace (PERF.md, Findings, PR 24):
  - one plane per chip, named "/device:TPU:<n>"; its line "XLA Ops" holds one
    event per executed HLO operation, start and duration in nanoseconds,
    one after the other (asynchronous copies and slices that overlap them
    are on a line of their own, "Async XLA Ops", and are not read);
  - an event's name is the whole HLO instruction, "%fusion.12 = bf16[...]
    fusion(%operand, ...)": only the part before " = " is the operation's
    own name, the rest names its operands;
  - a Pallas kernel is a custom call named by the kernel's stable `name=`:
    "%ff_flash_attention_dkv.30"; a collective by its opcode: "%all-reduce.5";
  - a while loop is one event that spans the events of its body;
  - the benchmark's own jax.profiler.TraceAnnotation spans ("bench/...") are
    events on the host plane's thread lines, on the same clock.
`python benchmarks/harness/trace_reduce.py <dir-or-file>` prints what a
trace holds, for looking at one by hand.
"""

from __future__ import annotations

import dataclasses
import re
import sys
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench/"
COLLECTIVE = r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
CONTAINERS = ("while", "conditional", "call")   # span the operations inside them


@dataclasses.dataclass
class Op:
    name: str       # the operation's own name: "fusion.12", "ff_fused_optim_adam.3"
    start: int      # ns
    end: int


@dataclasses.dataclass
class Trace:
    devices: dict   # chip number -> [Op], sorted by start
    host: list      # [Op] of the benchmark's own annotations


def find_xplane(path) -> Path:
    path = Path(path)
    if path.is_file():
        return path
    found = sorted(path.rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def op_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(path)))
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = [Op(op_name(e.name), int(e.start_ns),
                      int(e.start_ns + e.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices[int(m.group(1))] = sorted(ops, key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            host.extend(Op(e.name, int(e.start_ns),
                           int(e.start_ns + e.duration_ns))
                        for line in plane.lines for e in line.events
                        if e.name.startswith(HOST_PREFIX))
    return Trace(devices, sorted(host, key=lambda o: o.start))


def window_of(trace: Trace, anchor: str, from_s: float = 0.0,
              to_s: float | None = None) -> tuple:
    """(start, end) in ns: the host span `anchor`, or the part of it from
    `from_s` to `to_s` seconds after its start."""
    spans = [o for o in trace.host if o.name == anchor]
    if not spans:
        raise ValueError(f"the trace has no host span {anchor!r} "
                         f"(has {sorted({o.name for o in trace.host})})")
    span = spans[-1]
    start = span.start + int(from_s * 1e9)
    end = span.end if to_s is None else min(span.end,
                                            span.start + int(to_s * 1e9))
    if end <= start:
        raise ValueError(f"empty window in span {anchor!r}")
    return start, end


def _clip(ops, window):
    lo, hi = window
    for o in ops:
        if o.end > lo and o.start < hi:
            yield max(o.start, lo), min(o.end, hi), o


def busy_intervals(ops, window) -> list:
    """Union of the operations' intervals inside the window (operations
    nest and overlap: a while loop spans its body)."""
    merged = []
    for s, e, _o in _clip(ops, window):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_seconds(trace: Trace, window) -> dict:
    """Per chip: seconds in which some operation ran."""
    return {chip: sum(e - s for s, e in busy_intervals(ops, window)) / 1e9
            for chip, ops in trace.devices.items()}


def matching_seconds(trace: Trace, window, pattern: str) -> dict:
    """Per chip: the union of the intervals of operations whose name
    matches `pattern` (a union, so a collective's start/done pair or nested
    events are not counted twice), and how many matched."""
    rx = re.compile(pattern)
    out = {}
    for chip, ops in trace.devices.items():
        hit = [o for o in ops if rx.search(o.name)]
        out[chip] = {
            "seconds": sum(e - s for s, e in busy_intervals(hit, window)) / 1e9,
            "events": sum(1 for _ in _clip(hit, window))}
    return out


def top_ops(trace: Trace, window, n: int = 10) -> list:
    """[[name, seconds], ...]: operations by total device time, averaged over
    the chips; an event is named by its HLO instruction ("%fusion.12 = ..."),
    numbers folded so that fusion.12 and fusion.13 add up, a Pallas kernel
    by its ff_ name; containers (while) left out, their bodies counted."""
    total = {}
    for ops in trace.devices.values():
        for s, e, o in _clip(ops, window):
            key = re.sub(r"[.\-_]?\d+$", "", o.name)
            if key in CONTAINERS:
                continue
            total[key] = total.get(key, 0) + (e - s)
    chips = max(1, len(trace.devices))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / chips] for k, v in ranked]


def idle_gaps(trace: Trace, window, n: int = 10) -> list:
    """[[name, seconds], ...]: the idle time of the first chip inside the
    window, summed by the innermost benchmark host span that covers the
    middle of each gap ("(no span)" where none does)."""
    if not trace.devices:
        return []
    ops = trace.devices[min(trace.devices)]
    lo, hi = window
    edges = [lo] + [t for iv in busy_intervals(ops, window) for t in iv] + [hi]
    by_name = {}
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        mid = (g0 + g1) // 2
        cover = [o for o in trace.host if o.start <= mid < o.end]
        name = max(cover, key=lambda o: o.start).name if cover else "(no span)"
        by_name[name] = by_name.get(name, 0) + (g1 - g0)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def dump(path, limit: int = 12) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(path)))
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            t0 = min(e.start_ns for e in events)
            t1 = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{t0 / 1e9:.6f}..{t1 / 1e9:.6f} s")
            shown = events[:limit] if plane.name.startswith("/device") else [
                e for e in events if e.name.startswith(HOST_PREFIX)][:limit]
            for e in shown:
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in e.stats}
                print(f"    {e.name[:120]!r} start={e.start_ns} "
                      f"dur={e.duration_ns} stats={stats}")
            if plane.name.startswith("/device"):
                total = {}
                for e in events:
                    n, d = total.get(e.name, (0, 0))
                    total[e.name] = (n + 1, d + e.duration_ns)
                for name, (n, d) in sorted(total.items(),
                                           key=lambda kv: -kv[1][1])[:limit]:
                    print(f"    TOTAL {name[:100]!r}: {n} events, {d / 1e6:.3f} ms")


if __name__ == "__main__":
    dump(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 12)
