#!/usr/bin/env python
"""Aggregate the repo's BENCH_*.json files into one perf-trajectory table.

Every PR that claims a performance win ships a BENCH_*.json evidence file
(bench_search / bench_step / bench_zero / bench_pipeline / bench_resilience
/ profile_attribution / ...), but the trajectory across them was invisible.
This tool knows each family's headline metric and renders one (metric,
source, value, delta-vs-previous) table. These are CPU-mesh counts and
parity facts; on-chip numbers live in the driver's PERF_LEDGER.jsonl.

Usage:
    python tools/bench_history.py [--repo DIR] [--json]
    python tools/bench_history.py --check   # CI: every BENCH file parses
                                            # and carries its headline
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Callable, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# family -> (filename regex, extractor returning [(metric, value), ...]);
# an extractor returning an EMPTY list means "headline missing" (--check
# fails on it — an evidence file without its claim is a broken artifact)
FAMILIES: Dict[str, Tuple[str, Callable[[Dict[str, Any]],
                                        List[Tuple[str, float]]]]] = {
    "search_fastpath": (
        r"^BENCH_search_fastpath\.json$",
        lambda d: [(k, float(d[k])) for k in
                   ("warm_speedup_vs_cold", "cold_speedup_vs_baseline")
                   if d.get(k) is not None]),
    "step_pipeline": (
        r"^BENCH_step_pipeline\.json$",
        lambda d: [(k, float(d[k])) for k in
                   ("fused_vs_sync_speedup", "async_vs_sync_speedup")
                   if d.get(k) is not None]),
    "zero": (
        r"^BENCH_zero\.json$",
        lambda d: [(k, float(d[k])) for k in
                   ("opt_state_reduction_actual", "zero_vs_replicated_speed")
                   if d.get(k) is not None]),
    "pipeline": (
        r"^BENCH_pipeline\.json$",
        lambda d: ([("one_f1b_vs_gpipe_speed",
                     float(d["one_f1b_vs_gpipe_speed"]))]
                   if d.get("one_f1b_vs_gpipe_speed") is not None else [])
        + [(f"mem_reduction_vs_dp[{k}]", float(v))
           for k, v in sorted((d.get("mem_reduction_vs_dp") or {}).items())
           if isinstance(v, (int, float))]),
    "resilience": (
        r"^BENCH_resilience\.json$",
        lambda d: [(k, float(d[k])) for k in
                   ("checkpoint_overhead_pct", "legs_passed")
                   if d.get(k) is not None]),
    "attribution": (
        r"^BENCH_attribution\.json$",
        lambda d: [(k, float(d[k])) for k in
                   ("attributed_over_step", "coverage", "rows")
                   if d.get(k) is not None]),
    "goodput": (
        r"^BENCH_goodput\.json$",
        lambda d: [(k, float(d[k])) for k in
                   ("goodput_baseline", "goodput_ckpt_heavy",
                    "accounted_frac_min")
                   if d.get(k) is not None]),
    "serve": (
        r"^BENCH_serve\.json$",
        lambda d: [(k, float(d[k])) for k in
                   ("tokens_per_s_per_cpu_device", "ttft_p99_s",
                    "per_token_p99_s", "spec_accept_rate",
                    "kv_itemsize")
                   if d.get(k) is not None]),
    "spec": (
        r"^BENCH_spec\.json$",
        lambda d: [(k, float(d[k])) for k in
                   ("spec_speedup_best", "spec_accept_rate_best",
                    "spec_tokens_best", "int8_tokens_per_s_per_cpu_device",
                    "int8_kv_shard_degree", "bf16_kv_shard_degree",
                    "legs_passed")
                   if d.get(k) is not None]),
    "mfu": (
        r"^BENCH_mfu\.json$",
        lambda d: [(k, float(d[k])) for k in
                   ("remat_pred_mem_reduction", "remat_live_temp_reduction",
                    "fused_ce_max_diff", "step_ms_fused",
                    "mfu_weighted_fused", "hbm_peak_bytes", "legs_passed")
                   if d.get(k) is not None]),
    "learned": (
        r"^BENCH_learned\.json$",
        lambda d: [(k, float(d[k])) for k in
                   ("mape_learned", "mape_additive", "cold_compile_s",
                    "dp_expansions", "expansions_saved_frac",
                    "prune_speedup", "coverage", "legs_passed")
                   if d.get(k) is not None]),
    "swap": (
        r"^BENCH_swap\.json$",
        lambda d: [(k, float(d[k])) for k in
                   ("swaps_completed", "swap_p99_s", "dropped_inflight",
                    "overload_shed", "served_ttft_p99_s", "legs_passed")
                   if d.get(k) is not None]),
    "longctx": (
        r"^BENCH_longctx\.json$",
        lambda d: [(k, float(d[k])) for k in
                   ("context_gain_vs_hbm_only", "prefetch_hit_rate",
                    "spill_parity", "ring_crossover", "legs_passed")
                   if d.get(k) is not None]),
    "fleet": (
        r"^BENCH_fleet\.json$",
        lambda d: [(k, float(d[k])) for k in
                   ("scale2_x", "scale4_x", "fleet_tokens_per_s",
                    "mixed_ttft_p99_s", "rolling_swaps",
                    "rolling_dropped_inflight", "disagg_goodput_ratio",
                    "legs_passed")
                   if d.get(k) is not None]),
    "slo": (
        r"^BENCH_reqtrace\.json$",
        lambda d: [(k, float(d[k])) for k in
                   ("overhead_pct", "accounting_frac_min",
                    "ttft_budget_remaining", "burn_rate_1m", "shed_rate",
                    "legs_passed")
                   if d.get(k) is not None]),
    "twin": (
        r"^BENCH_twin\.json$",
        lambda d: [(k, float(d[k])) for k in
                   ("twin_vs_live_err", "capacity_rps_1",
                    "capacity_scale2_x", "capacity_scale4_x",
                    "autoscale_budget_at_signal",
                    "autoscale_recommended_replicas", "legs_passed")
                   if d.get(k) is not None]),
}


def scan(repo: str = REPO) -> List[Dict[str, Any]]:
    """Parse every BENCH_*.json under `repo` into records:
    {"file", "family", "order", "metrics": [(name, value), ...]} — or
    {"file", "error"} for an unparseable/unrecognized one."""
    recs = []
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_*.json"))):
        fname = os.path.basename(path)
        try:
            with open(path) as f:
                d = json.load(f)
        except (OSError, ValueError) as e:
            recs.append({"file": fname, "error": f"unparseable: {e}"})
            continue
        for family, (pat, extract) in FAMILIES.items():
            mobj = re.match(pat, fname)
            if not mobj:
                continue
            try:
                metrics = extract(d)
            except (KeyError, TypeError, ValueError) as e:
                metrics, err = [], repr(e)
            else:
                err = None
            if not metrics:
                recs.append({"file": fname, "family": family,
                             "error": err or "headline metric missing"})
            else:
                order = int(mobj.group(1)) if mobj.groups() else 0
                recs.append({"file": fname, "family": family,
                             "order": order, "metrics": metrics})
            break
        else:
            recs.append({"file": fname, "error": "unknown BENCH family "
                         "(add it to bench_history.FAMILIES)"})
    return recs


def trajectory(recs: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Flatten records into the table: one row per (metric, source), with
    delta vs the previous occurrence of the SAME metric (one-off family
    files have no predecessor)."""
    rows: List[Dict[str, Any]] = []
    last: Dict[str, float] = {}
    ordered = sorted((r for r in recs if "metrics" in r),
                     key=lambda r: (r.get("order", 0), r["file"]))
    for rec in ordered:
        for name, value in rec["metrics"]:
            prev = last.get(name)
            rows.append({
                "metric": name,
                "source": rec["file"],
                "value": value,
                "delta": (value - prev) if prev is not None else None,
                "delta_pct": (100.0 * (value - prev) / prev
                              if prev not in (None, 0.0) else None),
            })
            last[name] = value
    return rows


def print_table(rows: List[Dict[str, Any]]) -> None:
    print(f"{'metric':44} {'source':28} {'value':>12} {'delta':>10}")
    for r in rows:
        d = (f"{r['delta_pct']:+9.1f}%" if r["delta_pct"] is not None
             else "         -")
        print(f"{r['metric'][:44]:44} {r['source'][:28]:28} "
              f"{r['value']:12.4g} {d}")


# --------------------------------------------------------------- check mode
def _check(repo: str) -> int:
    """CI: every BENCH file parses and carries its family's headline
    metric — a bench artifact that lost its claim fails loudly here
    instead of silently dropping out of the trajectory."""
    recs = scan(repo)
    assert recs, f"no BENCH_*.json under {repo}"
    bad = [r for r in recs if "error" in r]
    assert not bad, "broken bench artifacts: " + "; ".join(
        f"{r['file']}: {r['error']}" for r in bad)
    rows = trajectory(recs)
    assert rows, "no headline metrics extracted"
    print(f"bench_history --check OK ({len(recs)} files, "
          f"{len(rows)} metric rows)")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        "bench_history", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--repo", default=REPO,
                    help="repo root holding the BENCH_*.json files")
    ap.add_argument("--json", action="store_true",
                    help="emit the table as JSON instead of text")
    ap.add_argument("--check", action="store_true",
                    help="CI: every bench file parses + carries its "
                         "headline metric")
    args = ap.parse_args(argv)
    if args.check:
        return _check(args.repo)
    rows = trajectory(scan(args.repo))
    if args.json:
        print(json.dumps(rows, indent=1))
    else:
        print_table(rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
