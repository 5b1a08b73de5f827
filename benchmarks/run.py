"""The benchmark's one command:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time, from the root of a checkout. It reads the cell from
BENCHMARK.json and the files it names, and only then touches JAX. It fails
(no result line, non-zero exit) without a TPU, with fewer chips than the cell
asks for, with a device_kind that harness/peaks.json does not hold, or where
flexflow_tpu cannot be imported. Earlier stdout lines are JSON facts; the
LAST line is the result: correct, attempted, failed, metrics, device.
--trace 0 reports the cell's end-to-end metrics; --trace 1 also wraps a few
steady seconds in jax.profiler.trace and reports the per-layer metrics.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import math
import shutil
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
for p in (str(BENCH_DIR), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import manifest as mf  # noqa: E402
from harness.facts import CompileCounter, emit  # noqa: E402
from harness.peaks import peaks_for  # noqa: E402


@dataclasses.dataclass
class RunView:
    """What a metric reader sees."""
    facts: dict
    cell: object
    peaks: dict
    trace: object = None
    window: tuple = None

    def note(self, **kw) -> None:
        emit(fact="metric_note", **kw)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="default: the manifest's run_seconds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_device(chips: int, rehearsal: bool):
    """(device dict, peaks). The one place that decides whether this machine
    may report a number."""
    import jax

    devs = jax.devices()
    if rehearsal:
        return ({"platform": devs[0].platform, "kind": devs[0].device_kind,
                 "count": chips}, None)
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmarks/run.py needs a TPU: platform is "
                         f"{devs[0].platform!r}; nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX reports {len(devs)}")
    peaks = peaks_for(devs[0].device_kind)   # KeyError: not in the table
    return {"platform": "tpu", "kind": devs[0].device_kind, "count": chips}, peaks


def read_metrics(entries, view: RunView, bench_dir=BENCH_DIR) -> dict:
    out = {}
    for m in entries:
        spec = mf.read_named("metrics", m["name"], bench_dir, required=False) \
            or {"reader": "fact"}
        reader = importlib.import_module(f"readers.{spec['reader']}")
        value = reader.read(view, m["name"], **spec.get("args", {}))
        if value is None:
            continue        # nothing to read: left out of the line
        if not math.isfinite(value):
            raise SystemExit(f"metric {m['name']} is {value}: too many "
                             "requests failed for it to exist; no result")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, manifest_path=mf.MANIFEST, rehearsal: bool = False) -> int:
    """`manifest_path` and `rehearsal` are rehearse.py's: the tiny cells of
    rehearsal.json on whatever backend there is, and no metric printed."""
    args = parse_args(argv)
    manifest = mf.load_manifest(manifest_path)
    cell = mf.load_cell(manifest, args.workload)
    seconds = float(args.seconds if args.seconds is not None
                    else manifest["run_seconds"])
    try:
        import flexflow_tpu  # noqa: F401
    except ImportError as e:
        raise SystemExit(f"benchmarks/run.py runs from the root of a checkout "
                         f"of the repository: {e}")
    device, peaks = require_device(cell.chips, rehearsal)

    from cells.common import Ctx
    from flexflow_tpu.config import ensure_compile_cache
    from harness import trace_reduce

    trace_dir = ROOT / "chiprun_out" / "bench_trace" / cell.name
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    ctx = Ctx(cell=cell, seed=args.seed, seconds=seconds,
              trace=bool(args.trace), trace_dir=str(trace_dir),
              counter=CompileCounter(), peaks=peaks)
    emit(fact="run", workload=cell.name, config=cell.config_name,
         traffic=cell.traffic_name, seed=args.seed, seconds=seconds,
         trace=args.trace, device=device, rehearsal=rehearsal,
         compile_cache_dir=ensure_compile_cache())
    facts = importlib.import_module(f"cells.{cell.traffic['kind']}").run(ctx)

    device["memory_peak_bytes"] = max(facts["memory_peak_bytes"])
    view = RunView(facts=facts, cell=cell, peaks=peaks)
    result = {"correct": bool(facts["correct"]),
              "attempted": int(facts["attempted"]),
              "failed": int(facts["failed"])}
    if args.trace:
        view.trace = trace_reduce.load(trace_dir)
        view.window = trace_reduce.window_of(view.trace, **ctx.trace_window)
        busy = trace_reduce.busy_seconds(view.trace, view.window)
        if busy:
            device["busy_s"] = sum(busy.values()) / len(busy)
            device["window_s"] = (view.window[1] - view.window[0]) / 1e9
            result["breakdown"] = {
                "device_ops": trace_reduce.top_ops(view.trace, view.window),
                "idle_gaps": trace_reduce.idle_gaps(view.trace, view.window)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    entries = cell.per_layer if args.trace else cell.end_to_end
    emit(fact="summary", wall_s=ctx.since_start(), **ctx.counter.facts())
    if rehearsal:
        # a CPU run shows control flow, results and counts: never a number
        # under the name of a device metric
        result["would_report"] = [m["name"] for m in entries]
    else:
        result["metrics"] = read_metrics(entries, view)
    result["device"] = device
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
