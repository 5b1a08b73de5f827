"""Serving-under-fire benchmark: the ISSUE 11 evidence artifact.

Builds the gpt2 CPU serving twin plus a training-side model of the SAME
graph, then drives three legs:

  hot_swap_under_load — the engine `watch()`es a durable-checkpoint
      root while the continuous-batching scheduler serves an open-loop
      trace; a background thread drops fresh snapshots mid-run
      (`save_durable`, block=True). Asserts ZERO dropped in-flight
      requests across the swaps, then proves post-swap decode parity
      (bitwise vs a fresh engine with the snapshot's params loaded
      directly) and bitwise rollback to the previous retained version.
  overload_shed — an arrival rate far above the twin's capacity with
      `--serve-queue-cap`/`--serve-ttft-budget-ms` armed: sheds are
      counted while every SERVED request still completes with its full
      token budget and a TTFT p99 inside the budget.
  fault_injection — the four serve/* fault sites: a transient plan
      (prefill + kv_admit + decode_step, one fire each) costs retries
      and NOTHING else; a permanent decode fault (`@N*T`, T = the retry
      budget) fails exactly the affected request while every other
      request completes; a permanent `serve/param_swap` fault aborts the
      swap, increments `rejected`, and leaves the engine serving — the
      same snapshot activates cleanly once the fault clears.

  python tools/bench_swap.py                      # full twin bench
  python tools/bench_swap.py --out BENCH_swap.json
  python tools/bench_swap.py --check   # CI smoke (tiny twin): asserts
      every leg's invariants and exits nonzero on any failure

Headline keys (bench_history "swap" family): swaps_completed,
swap_p99_s, dropped_inflight, overload_shed, served_ttft_p99_s,
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

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _quantile(xs, q):
    if not xs:
        return None
    return float(np.quantile(np.asarray(xs, np.float64), q))


def _gc(check: bool):
    from flexflow_tpu.models import GPT2Config
    return (GPT2Config(vocab=256, seq=16, d_model=64, heads=2, layers=1,
                       dropout=0.0) if check else
            GPT2Config(vocab=512, seq=32, d_model=128, heads=4, layers=2,
                       dropout=0.0))


def _build_engine(gc):
    import jax

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models import build_gpt2
    from flexflow_tpu.serving import compile_serving

    n_dev = len(jax.devices())
    mesh = ({"data": 2, "model": n_dev // 2} if n_dev % 2 == 0 and n_dev > 1
            else {"data": max(1, n_dev)})
    cfg = FFConfig(search_budget=16, mesh_shape=mesh, log_level="warning",
                   max_batch_slots=4, kv_page_size=4)
    m = FFModel(cfg)
    build_gpt2(m, gc, batch=8)
    eng = compile_serving(m, max_decode_len=4 if gc.seq <= 16 else 8)
    eng.init(seed=0)
    return eng, n_dev


def _build_trainer(gc):
    """Training-side model of the SAME graph (the snapshot producer).
    Data-parallel/zero-budget compile: the graph fingerprint only hangs
    off layer names + weight schemas, not the partitioning."""
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
    """Drop durable snapshot `step` with seed-deterministic weights (so a
    parity reference can be reconstructed with cm.init(seed=step))."""
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
    from flexflow_tpu.runtime.resilience import RetryPolicy
    from flexflow_tpu.serving import (ContinuousBatchingScheduler,
                                      gpt2_prompt_inputs, gpt2_step_inputs)
    kw.setdefault("retry_policy", RetryPolicy(attempts=3, base_delay=0.01,
                                              seed=7))
    return ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                       gpt2_step_inputs, eos_id=None,
                                       dispatch_ahead=4, **kw)


def _probe(eng, gc):
    """Full-window prefill logits: the bitwise parity fingerprint."""
    ids = np.arange(gc.seq, dtype=np.int32)[None, :].repeat(eng.slots, 0) \
        % gc.vocab
    lg, _ = eng.prefill(eng.params, [ids, np.ascontiguousarray(
        np.broadcast_to(np.arange(gc.seq, dtype=np.int32), ids.shape))])
    return np.asarray(lg)


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
def leg_hot_swap(eng, eng_ref, gc, cm, root, n_requests, rate, seed, checks):
    l_init = _probe(eng, gc)
    eng.watch(root, poll_interval_s=0.05, retain=2)
    rng = np.random.default_rng(seed)
    reqs = _trace(rng, n_requests, rate, gc.vocab, max(2, gc.seq // 4),
                  eng.max_decode_len)
    sched = _scheduler(eng)
    # the run is sized by EVENTS, not by wall time: the tail of the trace is
    # held back (arrival = never) until the first swap has landed, so the
    # trace cannot drain before the snapshot is written and the watcher's
    # poll has seen it. The scheduler idles (and keeps polling) meanwhile.
    tail = reqs[-max(2, n_requests // 4):]
    for r in tail:
        r.arrival_s = float("inf")

    def dropper():
        # first snapshot once serving has actually started (slots are in
        # flight); once the first swap landed, the held tail arrives and the
        # second snapshot drops — both pointer flips happen with live traffic
        deadline = time.monotonic() + 30.0
        while sched.prefills < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        _snapshot(cm, root, 1)
        deadline = time.monotonic() + 30.0
        while sched.stats["swaps"] < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        now = sched._now()
        for i, r in enumerate(tail):   # released on a deadline too, so the
            r.arrival_s = now + 0.01 * i  # run ends and the check can fail
        _snapshot(cm, root, 2)

    th = threading.Thread(target=dropper, daemon=True)
    th.start()
    t0 = time.perf_counter()
    done = sched.run(reqs)
    wall = time.perf_counter() - t0
    th.join(timeout=60.0)

    dropped = n_requests - len(done) - len(sched.shed) - len(sched.failed)
    checks.add("swap/zero_dropped_inflight",
               dropped == 0 and not sched.shed and not sched.failed,
               f"{len(done)}/{n_requests} done, {len(sched.shed)} shed, "
               f"{len(sched.failed)} failed")
    checks.add("swap/all_full_budget",
               all(len(r.tokens) == r.max_new_tokens for r in done),
               "a served request came back short")
    checks.add("swap/at_least_one_live_swap", sched.stats["swaps"] >= 1,
               f"{sched.stats['swaps']} swaps during the run")

    # post-swap decode parity: force-advance to the newest snapshot, then
    # compare against a FRESH engine with that snapshot's params loaded
    eng.poll_swap(force=True)
    ver = eng.active_version
    checks.add("swap/advanced_to_snapshot", ver in (1, 2),
               f"active_version={ver}")
    cm.init(seed=int(ver))
    eng_ref.load_params(cm.params)
    parity = np.array_equal(_probe(eng, gc), _probe(eng_ref, gc))
    checks.add("swap/post_swap_parity_bitwise", parity,
               f"vs fresh engine @ version {ver}")

    # rollback: bitwise restore of the previous retained version + pin
    rb = eng.rollback()
    l_rb = _probe(eng, gc)
    if rb is None:
        rb_parity = np.array_equal(l_rb, l_init)
    else:
        cm.init(seed=int(rb))
        eng_ref.load_params(cm.params)
        rb_parity = np.array_equal(l_rb, _probe(eng_ref, gc))
    checks.add("swap/rollback_bitwise", rb_parity, f"rolled back to {rb}")
    checks.add("swap/rollback_pins", not eng.poll_swap(force=True),
               "pinned engine auto-advanced")
    eng.unpin()
    eng.poll_swap(force=True)  # back on the newest version for later legs

    rep = eng.health_report()["serving"]
    return {
        "requests": n_requests,
        "completed": len(done),
        "dropped_inflight": dropped,
        "wall_s": round(wall, 3),
        "swaps_during_run": sched.stats["swaps"],
        "rollbacks": rep["rollbacks"],
        "swap_p50_s": rep["swap_p50_s"],
        "swap_p99_s": rep["swap_p99_s"],
        "active_version": eng.active_version,
        "post_swap_parity_bitwise": bool(parity),
        "rollback_bitwise": bool(rb_parity),
        "ttft_p99_s": _quantile([r.ttft_s for r in done
                                 if r.ttft_s is not None], 0.99),
    }


# ------------------------------------------------------------------ leg 2
def leg_overload(eng, gc, n_requests, rate, budget_ms, queue_cap, seed,
                 checks):
    rng = np.random.default_rng(seed)
    reqs = _trace(rng, n_requests, rate, gc.vocab, max(2, gc.seq // 4),
                  eng.max_decode_len, priorities=(0, 1, 2))
    sched = _scheduler(eng, ttft_budget_ms=budget_ms, queue_cap=queue_cap)
    t0 = time.perf_counter()
    done = sched.run(reqs)
    wall = time.perf_counter() - t0
    ttfts = [r.ttft_s for r in done if r.ttft_s is not None]
    p99 = _quantile(ttfts, 0.99)
    shed = len(sched.shed)
    service_rate = len(done) / wall if wall > 0 else 0.0
    checks.add("overload/sheds_counted", shed > 0 and shed == sum(
        v for k, v in sched.stats.items() if k.startswith("shed_")),
        f"{shed} shed vs stats {sched.stats}")
    checks.add("overload/served_complete",
               len(done) > 0 and all(len(r.tokens) == r.max_new_tokens
                                     for r in done),
               f"{len(done)} served")
    checks.add("overload/accounted",
               len(done) + shed + len(sched.failed) == n_requests,
               f"{len(done)}+{shed}+{len(sched.failed)} != {n_requests}")
    checks.add("overload/served_ttft_within_budget",
               p99 is not None and p99 * 1e3 <= budget_ms,
               f"ttft_p99={p99}s vs budget {budget_ms}ms")
    return {
        "requests": n_requests,
        "arrival_rate_req_s": rate,
        "service_rate_req_s": round(service_rate, 2),
        "overload_factor": (round(rate / service_rate, 2)
                            if service_rate > 0 else None),
        "ttft_budget_ms": budget_ms,
        "queue_cap": queue_cap,
        "served": len(done),
        "shed": shed,
        "shed_by_reason": {k: v for k, v in sched.stats.items()
                           if k.startswith("shed_") and v},
        "failed": len(sched.failed),
        "wall_s": round(wall, 3),
        "served_ttft_p50_s": _quantile(ttfts, 0.5),
        "served_ttft_p99_s": p99,
    }


# ------------------------------------------------------------------ leg 3
def leg_faults(eng, gc, cm, root, n_requests, seed, checks):
    from flexflow_tpu.runtime import faults

    rng = np.random.default_rng(seed)
    out = {}
    mk = lambda: _trace(rng, n_requests, 50.0, gc.vocab,
                        max(2, gc.seq // 4), eng.max_decode_len)

    # transient: one fire at each request-path site, absorbed by retry
    faults.configure("serve/prefill@1,serve/kv_admit@2,serve/decode_step@2")
    sched = _scheduler(eng)
    done = sched.run(mk())
    fired = dict(faults.fired())
    faults.clear()
    checks.add("faults/transient_all_complete",
               len(done) == n_requests and not sched.failed,
               f"{len(done)}/{n_requests} done, {len(sched.failed)} failed")
    checks.add("faults/transient_fired",
               all(fired.get(s, 0) >= 1 for s in
                   ("serve/prefill", "serve/kv_admit", "serve/decode_step")),
               f"fired={fired}")
    out["transient"] = {"completed": len(done), "fired": fired}

    # permanent decode fault: T matches the retry budget, so the 3rd
    # decode dispatch escalates — exactly one slot evicted, engine lives
    faults.configure("serve/decode_step@3*3")
    sched = _scheduler(eng)
    done = sched.run(mk())
    faults.clear()
    checks.add("faults/permanent_fails_only_one",
               len(sched.failed) == 1 and len(done) == n_requests - 1,
               f"{len(sched.failed)} failed, {len(done)} done")
    checks.add("faults/permanent_rest_complete",
               all(len(r.tokens) == r.max_new_tokens for r in done),
               "a surviving request came back short")
    out["permanent_decode"] = {
        "completed": len(done), "failed": len(sched.failed),
        "evicted_wedged": sched.stats["evicted_wedged"],
        "failed_outcome": sched.failed[0].outcome if sched.failed else None,
    }

    # permanent swap fault: the snapshot is rejected, the engine keeps
    # its version; the SAME snapshot activates once the fault clears
    _snapshot(cm, root, 3)
    before = eng.active_version
    rej0 = eng.health_report()["serving"]["rejected"]
    faults.configure("serve/param_swap@1!")
    swapped = eng.poll_swap(force=True)
    rej1 = eng.health_report()["serving"]["rejected"]
    faults.clear()
    checks.add("faults/permanent_swap_rejected",
               not swapped and eng.active_version == before
               and rej1 == rej0 + 1,
               f"swapped={swapped} version {before}->{eng.active_version} "
               f"rejected {rej0}->{rej1}")
    sched = _scheduler(eng)
    done = sched.run(mk()[: max(2, n_requests // 2)])
    checks.add("faults/engine_survives_swap_fault",
               bool(done) and not sched.failed,
               f"{len(done)} done after aborted swap")
    # the rejected snapshot was NOT blacklisted (the read failure could
    # have been a transient mount hiccup) — with the fault cleared the
    # very same snapshot activates, either during the run above or here
    eng.poll_swap(force=True)
    checks.add("faults/swap_recovers_after_clear",
               eng.active_version == 3,
               f"active_version={eng.active_version}")
    out["permanent_swap"] = {"rejected_delta": rej1 - rej0,
                             "recovered_version": eng.active_version}
    return out


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench_swap")
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--rate", type=float, default=8.0,
                   help="open-loop arrival rate of the hot-swap leg")
    p.add_argument("--overload-rate", type=float, default=600.0,
                   help="arrival rate of the shedding leg — far above the "
                        "twin's service rate (the leg reports the measured "
                        "overload_factor)")
    p.add_argument("--ttft-budget-ms", type=float, default=3000.0)
    p.add_argument("--queue-cap", type=int, default=4)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default="", help="also write the JSON here")
    p.add_argument("--check", action="store_true",
                   help="CI smoke: tiny twin, assert every leg invariant")
    args = p.parse_args(argv)
    import jax  # a CPU-mesh counting tool: say what it ran on
    print(f"[bench_swap] platform={jax.default_backend()} "
          f"devices={len(jax.devices())}: counts and parity "
          "facts, never a device metric", file=sys.stderr)
    if args.check:
        args.requests = min(args.requests, 16)
        args.rate = min(args.rate, 6.0)

    gc = _gc(args.check)
    eng, n_dev = _build_engine(gc)
    eng_ref, _ = _build_engine(gc)  # fresh twin: the parity reference
    cm = _build_trainer(gc)
    root = tempfile.mkdtemp(prefix="ff_swap_bench_")
    checks = Checks()
    try:
        swap_leg = leg_hot_swap(eng, eng_ref, gc, cm, root, args.requests,
                                args.rate, args.seed, checks)
        over_leg = leg_overload(eng, gc, max(args.requests, 24),
                                args.overload_rate, args.ttft_budget_ms,
                                args.queue_cap, args.seed + 1, checks)
        fault_leg = leg_faults(eng, gc, cm, root,
                               min(8, max(4, args.requests // 2)),
                               args.seed + 2, checks)
    finally:
        shutil.rmtree(root, ignore_errors=True)

    report = {
        "model": "gpt2 CPU twin" + (" (check)" if args.check else ""),
        "devices": n_dev,
        "slots": eng.slots,
        "max_decode_len": eng.max_decode_len,
        "legs": {"hot_swap_under_load": swap_leg,
                 "overload_shed": over_leg,
                 "fault_injection": fault_leg},
        "checks": checks.items,
        # headline metrics (bench_history "swap" family)
        "swaps_completed": swap_leg["swaps_during_run"],
        "swap_p99_s": swap_leg["swap_p99_s"],
        "dropped_inflight": swap_leg["dropped_inflight"],
        "overload_shed": over_leg["shed"],
        "served_ttft_p99_s": over_leg["served_ttft_p99_s"],
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
