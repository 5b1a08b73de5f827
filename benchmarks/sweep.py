"""Find a serving cell's knee once: one process, one engine, the cell's
traffic at several multiples of its `rate_rps`, sharing the compiled programs.

    python benchmarks/sweep.py --workload gpt2-medium.serve-chat --rates 0.4,0.5,0.6,0.8

Every rate runs the cell's own schedule law and window (the manifest's
run_seconds unless --seconds says otherwise). Prints one JSON line per rate
and writes what it has to chiprun_out/sweep_<cell>.json after each.
The knee is the highest rate at which the backlog does not grow over the
window: the queue wait of the second half of the arrivals is not above that of
the first half by more than a prefill wave, and the run drains soon after
the last arrival. The cell's file then takes 0.8 of it. Needs the cell's chips
like run.py; not part of a check.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402  (puts the checkout on sys.path)
from harness import manifest as mf  # noqa: E402
from harness.facts import CompileCounter, emit  # noqa: E402


def half_waits(reqs, seconds):
    """Median queue wait (ms) of the requests due in each half of the window."""
    halves = ([], [])
    for r in reqs:
        if r.admit_s is not None:
            halves[r.arrival_s >= seconds / 2].append(
                1e3 * max(0.0, r.admit_s - r.arrival_s))
    return [statistics.median(h) if h else None for h in halves]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    manifest = mf.load_manifest()
    cell = mf.load_cell(manifest, args.workload)
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    device, _peaks = run.require_device(cell.chips, rehearsal=False)

    from cells import serve
    from cells.common import Ctx

    ctx = Ctx(cell=cell, seed=args.seed, seconds=args.seconds, trace=False,
              trace_dir="", counter=CompileCounter())
    facts = {}
    served = serve.build_engine(ctx, facts)
    emit(fact="sweep", workload=cell.name, device=device,
         seconds=args.seconds, **facts)
    rows = []
    out = run.ROOT / "chiprun_out" / f"sweep_{cell.name}.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        sched, reqs, records, wall = serve.serve_window(
            served, cell.traffic, args.seconds, args.seed + i,
            rate_scale=rate / cell.traffic["rate_rps"])
        row = serve.window_facts(sched, reqs, records, args.seconds, 1e9)
        first, second = half_waits(reqs, args.seconds)
        row.update(rate_rps=rate, run_wall_s=wall,
                   drain_after_window_s=row["last_finish_s"] - args.seconds,
                   queue_wait_median_ms_first_half=first,
                   queue_wait_median_ms_second_half=second,
                   queued_at_window_end=sum(
                       r.admit_s is None or r.admit_s > args.seconds
                       for r in reqs),
                   tokens_per_s_over_makespan=row["output_tokens_completed"]
                   / row["last_finish_s"])
        rows.append(row)
        emit(**row)
        out.write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
