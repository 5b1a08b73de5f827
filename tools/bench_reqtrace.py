"""Request-tracing + SLO observability benchmark: the ISSUE 15 evidence
artifact.

Builds the gpt2 CPU serving twin and drives four legs:

  overhead — interleaved best-of-N tracing-on vs tracing-off runs of the
      same open-loop Poisson trace. Tracing is zero-sync (it only re-reads
      timestamps the scheduler already materialized at dispatch-window
      boundaries), so the headline overhead_pct must stay <= 2% of
      tokens/s/chip.
  accounting — mixed-priority run with tracing on; every request's stage
      spans (queue -> prefill waves -> decode windows / spec rounds ->
      outcome) must tile >= 95% of its wall time
      (headline accounting_frac_min).
  swap_mid_trace — the engine watch()es a durable checkpoint root while a
      writer thread drops a fresh snapshot mid-run; at least one request's
      lifecycle trace must carry the param-swap landing inside its
      timeline.
  slo — SLO objectives armed (the --serve-slo grammar) against an
      overloaded arrival rate with admission control on, producing the
      error-budget scoreboard headlines: ttft_budget_remaining,
      burn_rate_1m, shed_rate.

  python tools/bench_reqtrace.py                       # full twin bench
  python tools/bench_reqtrace.py --out BENCH_reqtrace.json
  python tools/bench_reqtrace.py --check   # CI smoke (tiny twin):
      asserts every leg invariant and exits nonzero on any failure

Headline keys (bench_history "slo" family): overhead_pct,
accounting_frac_min, ttft_budget_remaining, burn_rate_1m, shed_rate,
legs_passed.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from collections import deque

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _gc(check: bool):
    from flexflow_tpu.models import GPT2Config
    return (GPT2Config(vocab=256, seq=16, d_model=64, heads=2, layers=1,
                       dropout=0.0) if check else
            GPT2Config(vocab=512, seq=32, d_model=128, heads=4, layers=2,
                       dropout=0.0))


def _build_engine(gc, serve_slo: str = ""):
    import jax

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models import build_gpt2
    from flexflow_tpu.serving import compile_serving

    n_dev = len(jax.devices())
    mesh = ({"data": 2, "model": n_dev // 2} if n_dev % 2 == 0 and n_dev > 1
            else {"data": max(1, n_dev)})
    cfg = FFConfig(search_budget=16, mesh_shape=mesh, log_level="warning",
                   max_batch_slots=4, kv_page_size=4, serve_slo=serve_slo)
    m = FFModel(cfg)
    build_gpt2(m, gc, batch=8)
    eng = compile_serving(m, max_decode_len=4 if gc.seq <= 16 else 8)
    eng.init(seed=0)
    return eng, n_dev


def _build_trainer(gc):
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.models import build_gpt2

    cfg = FFConfig(search_budget=0, only_data_parallel=True,
                   log_level="warning", max_batch_slots=4, kv_page_size=4,
                   async_checkpoint=False)
    m = FFModel(cfg)
    build_gpt2(m, gc, batch=8)
    cm = m.compile(SGDOptimizer(lr=0.01),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=0)
    return cm


def _snapshot(cm, root: str, step: int):
    from flexflow_tpu.runtime.resilience import save_durable
    cm.init(seed=step)
    cm._iteration = step
    return save_durable(cm, root, block=True)


def _trace(rng, n, rate, vocab, prompt_len, max_new, priorities=(1,)):
    from flexflow_tpu.serving import Request
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
    return [Request(rid=i,
                    prompt=list(rng.integers(1, vocab, size=prompt_len)),
                    max_new_tokens=max_new,
                    arrival_s=float(arrivals[i]),
                    priority=int(priorities[i % len(priorities)]))
            for i in range(n)]


def _scheduler(eng, **kw):
    from flexflow_tpu.serving import (ContinuousBatchingScheduler,
                                      gpt2_prompt_inputs, gpt2_step_inputs)
    return ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                       gpt2_step_inputs, eos_id=None,
                                       dispatch_ahead=4, **kw)


class Checks:
    def __init__(self):
        self.items = []

    def add(self, name: str, ok: bool, detail: str = ""):
        self.items.append({"check": name, "ok": bool(ok), "detail": detail})
        if not ok:
            print(f"CHECK FAIL: {name}: {detail}", file=sys.stderr)

    def ok(self):
        return all(c["ok"] for c in self.items)


# ------------------------------------------------------------------ leg 1
def leg_overhead(eng, gc, n_dev, n_requests, rate, seed, reps, checks):
    """Interleaved best-of-N A/B: same arrivals, tracer on vs off. Best-of
    damps scheduler-vs-OS noise on the CPU twin; interleaving keeps cache
    and clock drift from favoring either arm."""
    def run(rt_on, s):
        rng = np.random.default_rng(s)
        reqs = _trace(rng, n_requests, rate, gc.vocab, max(2, gc.seq // 4),
                      eng.max_decode_len)
        sched = _scheduler(eng, reqtrace=rt_on)
        t0 = time.perf_counter()
        done = sched.run(reqs)
        wall = time.perf_counter() - t0
        tokens = sum(len(r.tokens) for r in done)
        return tokens / wall / n_dev

    run(True, seed)  # warmup: first run pays any residual jit/compile
    on_best = off_best = 0.0
    for i in range(reps):
        off_best = max(off_best, run(False, seed + i))
        on_best = max(on_best, run(True, seed + i))
    overhead_pct = 100.0 * (off_best - on_best) / max(off_best, 1e-9)
    checks.add("overhead/tracing_leq_2pct", overhead_pct <= 2.0,
               f"on {on_best:.1f} vs off {off_best:.1f} tok/s/chip "
               f"({overhead_pct:.2f}%)")
    return {
        "reps": reps,
        "tokens_per_s_per_cpu_device_traced": round(on_best, 2),
        "tokens_per_s_per_cpu_device_untraced": round(off_best, 2),
        "overhead_pct": round(overhead_pct, 3),
    }


# ------------------------------------------------------------------ leg 2
def leg_accounting(eng, gc, n_requests, rate, seed, checks):
    rng = np.random.default_rng(seed)
    reqs = _trace(rng, n_requests, rate, gc.vocab, max(2, gc.seq // 4),
                  eng.max_decode_len, priorities=(0, 1, 2))
    sched = _scheduler(eng, reqtrace=True)
    done = sched.run(reqs)
    tr = sched.tracer
    fracs = [t["accounted_frac"] for t in tr.ring
             if "accounted_frac" in t]
    min_frac = min(fracs) if fracs else 0.0
    checks.add("accounting/every_request_traced",
               len(fracs) == n_requests,
               f"{len(fracs)} traces for {n_requests} requests")
    checks.add("accounting/spans_tile_95pct", min_frac >= 0.95,
               f"min accounted_frac={min_frac:.3f}")
    checks.add("accounting/all_complete",
               len(done) == n_requests
               and all(len(r.tokens) == r.max_new_tokens for r in done),
               f"{len(done)}/{n_requests} complete")
    return {
        "requests": n_requests,
        "traced": len(fracs),
        "accounting_frac_min": round(min_frac, 4),
        "accounting_frac_mean": (round(float(np.mean(fracs)), 4)
                                 if fracs else None),
    }


# ------------------------------------------------------------------ leg 3
def leg_swap_mid_trace(eng, gc, cm, root, n_requests, seed, checks):
    """A sustained time-zero backlog with STAGGERED token budgets keeps
    the decode slots occupied and desynchronized for the whole run, so
    the watcher's pointer flip lands while requests are in flight and the
    tracer stamps it into their timelines. The snapshot path is
    pre-warmed (throwaway drop to a scratch root) so the mid-run drop is
    fast relative to the backlog; up to 3 attempts absorb scheduler-vs-
    writer timing noise on loaded CI hosts."""
    from flexflow_tpu.serving import Request

    rng = np.random.default_rng(seed)
    prompt_len = max(2, gc.seq // 4)

    def backlog(n, rid0):
        return [Request(rid=rid0 + i,
                        prompt=list(rng.integers(1, gc.vocab,
                                                 size=prompt_len)),
                        max_new_tokens=1 + i % eng.max_decode_len,
                        arrival_s=0.0)
                for i in range(n)]

    scratch = tempfile.mkdtemp(prefix="ff_reqtrace_warm_")
    try:
        t0 = time.perf_counter()
        _snapshot(cm, scratch, 1)  # warm the init-jit + checkpoint IO path
        snap_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    # size the backlog off a timing probe: the run must comfortably
    # outlast prefill-wait + snapshot-drop + watcher-poll, or the flip
    # slips past the end of the run and lands at the NEXT run's first
    # (empty) poll instead of inside live timelines
    probe_n = max(48, 2 * n_requests)
    t0 = time.perf_counter()
    _scheduler(eng, reqtrace=True).run(backlog(probe_n, 10_000_000))
    probe_wall = max(1e-3, time.perf_counter() - t0)
    target_wall = max(1.0, 4.0 * snap_s)
    n_requests = min(2048, max(probe_n,
                               int(probe_n * target_wall / probe_wall)))

    eng.watch(root, poll_interval_s=0.02, retain=3)
    total = {"swaps": 0, "done": 0, "failed": 0, "attempts": 0}
    swapped_traces: list = []
    in_timeline = False
    for attempt in range(3):
        total["attempts"] = attempt + 1
        # drain any snapshot a previous attempt left pending, so a stale
        # flip can't land at this run's first (still-empty) poll
        eng.poll_swap(force=True)
        reqs = backlog(n_requests, attempt * n_requests)
        sched = _scheduler(eng, reqtrace=True)
        # the swap lands early in the run; keep EVERY terminal trace so
        # the default 512-ring can't evict the swap-carrying ones before
        # we inspect them
        sched.tracer.ring = deque(maxlen=n_requests + 8)

        def dropper():
            deadline = time.monotonic() + 30.0
            while sched.prefills < 1 and time.monotonic() < deadline:
                time.sleep(0.002)
            _snapshot(cm, root, attempt + 1)

        th = threading.Thread(target=dropper, daemon=True)
        th.start()
        done = sched.run(reqs)
        th.join(timeout=60.0)
        total["swaps"] += sched.stats["swaps"]
        total["done"] += len(done)
        total["failed"] += len(sched.failed)
        swapped_traces = [t for t in sched.tracer.ring if t.get("swaps")]
        in_timeline = any(
            any(s.get("stage") == "swap" for s in t.get("stages", []))
            for t in swapped_traces)
        if swapped_traces and in_timeline:
            break

    checks.add("swap/landed_during_run", total["swaps"] >= 1,
               f"{total['swaps']} swaps across {total['attempts']} attempts")
    checks.add("swap/inside_request_timeline",
               bool(swapped_traces) and in_timeline,
               f"{len(swapped_traces)} in-flight traces carry the swap")
    checks.add("swap/zero_dropped",
               total["done"] == total["attempts"] * n_requests
               and total["failed"] == 0,
               f"{total['done']}/{total['attempts'] * n_requests} done")
    return {
        "requests_per_attempt": n_requests,
        "attempts": total["attempts"],
        "swaps_during_run": total["swaps"],
        "traces_with_swap": len(swapped_traces),
        "swap_in_timeline": bool(swapped_traces) and in_timeline,
    }


# ------------------------------------------------------------------ leg 4
def leg_slo(eng, gc, n_requests, rate, budget_ms, queue_cap, seed, spec,
            checks):
    from flexflow_tpu import health

    # fresh scoreboard so this leg's report isn't diluted by earlier legs
    eng.slo = health.SLOTracker(health.parse_slo(spec))
    rng = np.random.default_rng(seed)
    reqs = _trace(rng, n_requests, rate, gc.vocab, max(2, gc.seq // 4),
                  eng.max_decode_len, priorities=(0, 1, 2))
    sched = _scheduler(eng, reqtrace=True, ttft_budget_ms=budget_ms,
                       queue_cap=queue_cap)
    done = sched.run(reqs)
    rep = eng.slo.report()
    obs = rep["objectives"]
    ttft_budget = (obs.get("ttft_p99_ms") or {}).get("budget_remaining")
    burn_1m = max((float(ob.get("burn_rate_60s", 0.0))
                   for ob in obs.values()), default=0.0)
    checks.add("slo/objectives_parsed",
               set(obs) == set(health.parse_slo(spec)),
               f"objectives={sorted(obs)}")
    checks.add("slo/every_terminal_classified",
               rep["requests"] == n_requests,
               f"{rep['requests']} classified of {n_requests}")
    checks.add("slo/overload_burns_availability",
               rep["shed_rate"] > 0.0 and burn_1m > 0.0,
               f"shed_rate={rep['shed_rate']:.3f} burn_1m={burn_1m:.2f}")
    checks.add("slo/budget_fields_finite",
               ttft_budget is not None and np.isfinite(ttft_budget),
               f"ttft_budget_remaining={ttft_budget}")
    return {
        "slo_spec": spec,
        "requests": n_requests,
        "served": len(done),
        "shed": len(sched.shed),
        "report": rep,
        "ttft_budget_remaining": ttft_budget,
        "burn_rate_1m": round(burn_1m, 4),
        "shed_rate": round(float(rep["shed_rate"]), 4),
    }


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench_reqtrace")
    p.add_argument("--requests", type=int, default=24)
    p.add_argument("--rate", type=float, default=8.0,
                   help="open-loop arrival rate of the traced legs")
    p.add_argument("--overload-rate", type=float, default=600.0,
                   help="arrival rate of the SLO leg (forces shedding)")
    p.add_argument("--reps", type=int, default=3,
                   help="best-of-N interleaved A/B reps for the overhead leg")
    p.add_argument("--slo", default=("ttft_p99_ms=2000,per_token_p99_ms=500,"
                                     "availability=0.999"),
                   help="--serve-slo objective string for the SLO leg")
    p.add_argument("--ttft-budget-ms", type=float, default=3000.0)
    p.add_argument("--queue-cap", type=int, default=4)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default="", help="also write the JSON here")
    p.add_argument("--check", action="store_true",
                   help="CI smoke: tiny twin, assert every leg invariant")
    args = p.parse_args(argv)
    import jax  # a CPU-mesh counting tool: say what it ran on
    print(f"[bench_reqtrace] platform={jax.default_backend()} "
          f"devices={len(jax.devices())}: counts and parity "
          "facts, never a device metric", file=sys.stderr)
    if args.check:
        args.requests = min(args.requests, 12)
        args.rate = min(args.rate, 6.0)
        args.reps = min(args.reps, 2)

    gc = _gc(args.check)
    eng, n_dev = _build_engine(gc)
    cm = _build_trainer(gc)
    root = tempfile.mkdtemp(prefix="ff_reqtrace_bench_")
    checks = Checks()
    try:
        over = leg_overhead(eng, gc, n_dev, args.requests, args.rate,
                            args.seed, args.reps, checks)
        acct = leg_accounting(eng, gc, args.requests, args.rate,
                              args.seed + 1, checks)
        swap = leg_swap_mid_trace(eng, gc, cm, root, args.requests,
                                  args.seed + 2, checks)
        slo = leg_slo(eng, gc, max(args.requests, 24), args.overload_rate,
                      args.ttft_budget_ms, args.queue_cap, args.seed + 3,
                      args.slo, checks)
    finally:
        shutil.rmtree(root, ignore_errors=True)

    report = {
        "model": "gpt2 CPU twin" + (" (check)" if args.check else ""),
        "devices": n_dev,
        "slots": eng.slots,
        "max_decode_len": eng.max_decode_len,
        "legs": {"overhead": over, "accounting": acct,
                 "swap_mid_trace": swap, "slo": slo},
        "checks": checks.items,
        # headline metrics (bench_history "slo" family)
        "overhead_pct": over["overhead_pct"],
        "accounting_frac_min": acct["accounting_frac_min"],
        "ttft_budget_remaining": slo["ttft_budget_remaining"],
        "burn_rate_1m": slo["burn_rate_1m"],
        "shed_rate": slo["shed_rate"],
        "legs_passed": sum(c["ok"] for c in checks.items),
    }
    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    if args.check:
        print("CHECK " + ("PASS" if checks.ok() else "FAIL"))
        return 0 if checks.ok() else 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
