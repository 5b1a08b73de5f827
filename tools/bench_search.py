"""Search fast-path benchmark: cold vs warm strategy-search wall-clock.

Times the three tiers of the search fast path on a fixed workload and mesh:

  baseline  — memoization + incremental DP + strategy cache all OFF
              (the pre-fast-path search; skip with --no-baseline)
  cold      — fast path ON, empty strategy cache (tier 2+3: memoized
              costing + DP prefix resume inside one search)
  warm      — same graph again (tier 1: persistent strategy-cache hit;
              must do ZERO DP frontier expansions)

No devices are required: the search prices a MachineSpec, so the benchmark
runs anywhere (CPU backend, tiny import footprint). Results print as JSON;
--out writes the report to a file (one file per run, e.g.
BENCH_search_fastpath.json in the bench trajectory).

  python tools/bench_search.py                       # gpt2_small, budget 32
  python tools/bench_search.py --model gpt2_tiny --budget 16
  python tools/bench_search.py --check               # CI smoke: tiny graph,
      asserts warm >= 2x faster than cold, zero warm expansions, identical
      strategy — exits nonzero on regression (tier-1 safe, CPU backend)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_model(name: str, budget: int, cache_dir: str, use_cache: bool):
    from flexflow_tpu import FFConfig, FFModel

    cfg = FFConfig(batch_size=8, search_budget=budget,
                   strategy_cache=use_cache, strategy_cache_dir=cache_dir)
    if name.startswith("gpt2"):
        from flexflow_tpu.models import GPT2Config, build_gpt2

        gc = GPT2Config.tiny(seq=128) if name == "gpt2_tiny" else \
            GPT2Config(vocab=8192, seq=256, d_model=768, heads=12, layers=4,
                       dropout=0.0)
        gc.dropout = 0.0
        m = FFModel(cfg)
        build_gpt2(m, gc, batch=8)
        return m
    if name == "mlp":
        m = FFModel(cfg)
        x = m.create_tensor([8, 512], name="x")
        h = m.dense(x, 2048, activation="gelu", name="up")
        h = m.dense(h, 512, name="down")
        m.dense(h, 64, name="head")
        return m
    raise SystemExit(f"unknown --model {name!r}")


def _run(model_name: str, budget: int, cache_dir: str, machine,
         fastpath: bool, use_cache: bool):
    """One timed graph_optimize with fresh per-run counters."""
    from flexflow_tpu.search import memo
    from flexflow_tpu.search.dp import SEARCH_STATS, reset_search_stats
    from flexflow_tpu.search.optimize import graph_optimize

    memo.clear()
    memo.set_enabled(fastpath)
    reset_search_stats()
    m = _build_model(model_name, budget, cache_dir, use_cache)
    t0 = time.perf_counter()
    st = graph_optimize(m, machine)
    dt = time.perf_counter() - t0
    memo.set_enabled(True)
    return st, dt, dict(SEARCH_STATS)


def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench_search")
    p.add_argument("--model", default="gpt2_small",
                   choices=("gpt2_small", "gpt2_tiny", "mlp"))
    p.add_argument("--budget", type=int, default=32)
    p.add_argument("--mesh", default="data=4,model=2")
    p.add_argument("--chip", default="v5p")
    p.add_argument("--cache-dir", default="",
                   help="strategy-cache dir (default: fresh temp dir, so "
                        "cold is genuinely cold)")
    p.add_argument("--no-baseline", dest="baseline", action="store_false",
                   default=True, help="skip the fast-path-OFF reference run")
    p.add_argument("--out", default="", help="also write the JSON here")
    p.add_argument("--check", action="store_true",
                   help="CI smoke: tiny graph, assert warm >= 2x cold + "
                        "zero warm DP expansions + identical strategy")
    args = p.parse_args(argv)
    import jax  # a CPU-mesh counting tool: say what it ran on
    print(f"[bench_search] platform={jax.default_backend()} "
          f"devices={len(jax.devices())}: counts and parity "
          "facts, never a device metric", file=sys.stderr)

    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.search import strategy_cache as sc

    mesh = {k: int(v) for k, v in
            (part.split("=") for part in args.mesh.split(","))}
    machine = MachineSpec(mesh_axes=mesh, chip=args.chip)
    if args.check:
        args.model, args.budget, args.baseline = "mlp", 8, False
    cache_dir = args.cache_dir or tempfile.mkdtemp(prefix="ff_bench_cache_")

    report = {"model": args.model, "budget": args.budget, "mesh": mesh,
              "chip": args.chip, "cache_dir": cache_dir}

    st_base = None
    if args.baseline:
        st_base, dt, stats = _run(args.model, args.budget, cache_dir,
                                  machine, fastpath=False, use_cache=False)
        report["baseline"] = {"wallclock_s": round(dt, 6),
                              "dp_expansions": stats.get("expansions", 0)}

    st_cold, dt_cold, stats_cold = _run(args.model, args.budget, cache_dir,
                                        machine, fastpath=True,
                                        use_cache=True)
    report["cold"] = {
        "wallclock_s": round(dt_cold, 6),
        "dp_expansions": stats_cold.get("expansions", 0),
        "prefix_skipped_layers": stats_cold.get("layers_skipped", 0),
        "cost_s": getattr(st_cold, "_cache_info", {}).get(
            "meta", {}).get("cost_s"),
    }

    st_warm, dt_warm, stats_warm = _run(args.model, args.budget, cache_dir,
                                        machine, fastpath=True,
                                        use_cache=True)
    report["warm"] = {
        "wallclock_s": round(dt_warm, 6),
        "dp_expansions": stats_warm.get("expansions", 0),
        "dp_calls": stats_warm.get("calls", 0),
    }
    report["cache_stats"] = sc.STATS.as_dict()
    report["warm_speedup_vs_cold"] = round(dt_cold / max(dt_warm, 1e-9), 2)
    if args.baseline:
        report["cold_speedup_vs_baseline"] = round(
            report["baseline"]["wallclock_s"] / max(dt_cold, 1e-9), 2)

    same = json.loads(json.dumps(st_cold.to_json())) == \
        json.loads(json.dumps(st_warm.to_json()))
    report["warm_strategy_identical"] = same
    if st_base is not None:
        # the fast path must be a pure accelerator: identical winner (and
        # therefore identical predicted cost — the name embeds it)
        report["cold_strategy_matches_baseline"] = (
            json.loads(json.dumps(st_base.to_json())) ==
            json.loads(json.dumps(st_cold.to_json())))

    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)

    if args.check:
        ok = True
        if stats_warm.get("expansions", 0) != 0:
            print("CHECK FAIL: warm search ran DP expansions "
                  f"({stats_warm.get('expansions')})", file=sys.stderr)
            ok = False
        if not same:
            print("CHECK FAIL: warm strategy differs from cold",
                  file=sys.stderr)
            ok = False
        if dt_warm * 2 > dt_cold:
            print(f"CHECK FAIL: warm {dt_warm * 1e3:.1f}ms not >=2x faster "
                  f"than cold {dt_cold * 1e3:.1f}ms", file=sys.stderr)
            ok = False
        print("CHECK " + ("PASS" if ok else "FAIL"))
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
