"""Open-loop serving benchmark: the ISSUE 10 evidence artifact.

Builds the gpt2 CPU twin, compiles the two searched serving programs
(`compile_serving` — compute-priced prefill, bandwidth-priced decode),
and drives the continuous-batching scheduler with an OPEN-LOOP Poisson
arrival trace (seeded — arrivals don't wait for the server, so queueing
delay shows up in TTFT exactly as it would against a real frontend).
Per arrival-rate leg it reports:

  tokens_per_s_per_cpu_device — generated tokens / wall / device count
  ttft_p50_s/ttft_p99_s — time-to-first-token quantiles (arrival ->
      first prefill logit materialization, queueing included)
  per_token_p50_s/per_token_p99_s — decode-step latency quantiles at
      the scheduler's dispatch-window materialization granularity

plus the serving memory accounting (predicted vs measured params + KV
pool residency per device) through the PR 8 watermark check.

  python tools/bench_serve.py                        # full twin bench
  python tools/bench_serve.py --rates 2,8 --requests 24
  python tools/bench_serve.py --out BENCH_serve.json
  python tools/bench_serve.py --check   # CI smoke (tiny twin): asserts
      every request completes with its full token budget, quantiles are
      finite and ordered, KV bytes are accounted in memory_stats, and
      the measured watermark sits within the predicted envelope.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
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


def _build_engine(check: bool, kv_cache_dtype: str = "auto"):
    import jax

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models import GPT2Config, build_gpt2
    from flexflow_tpu.serving import compile_serving

    n_dev = len(jax.devices())
    mesh = ({"data": 2, "model": n_dev // 2} if n_dev % 2 == 0 and n_dev > 1
            else {"data": max(1, n_dev)})
    cfg = FFConfig(search_budget=16, mesh_shape=mesh, log_level="warning",
                   max_batch_slots=4, kv_page_size=4,
                   kv_cache_dtype=kv_cache_dtype)
    gc = (GPT2Config(vocab=256, seq=16, d_model=64, heads=2, layers=1,
                     dropout=0.0) if check else
          GPT2Config(vocab=512, seq=32, d_model=128, heads=4, layers=2,
                     dropout=0.0))
    m = FFModel(cfg)
    build_gpt2(m, gc, batch=8)
    eng = compile_serving(m, max_decode_len=4 if check else 8)
    eng.init(seed=0)
    return eng, gc, n_dev


def _make_trace(rng, n_requests, rate, vocab, prompt_len, max_new):
    """Open-loop Poisson arrivals via tracefmt (ISSUE 20): the generator
    IS the trace format, so every bench leg doubles as a replayable twin
    scenario. Arrival/prompt rng order is the pre-tracefmt one — fixed
    seeds reproduce the identical request sequence (pinned in tests)."""
    from flexflow_tpu.serving import tracefmt

    return tracefmt.records_to_requests(
        tracefmt.poisson_records(rng, n_requests, rate, vocab, prompt_len,
                                 max_new))


def _run_leg(eng, gc, n_dev, rate, n_requests, seed):
    from flexflow_tpu.serving import (ContinuousBatchingScheduler,
                                      gpt2_prompt_inputs, gpt2_step_inputs)

    rng = np.random.default_rng(seed)
    max_new = eng.max_decode_len
    prompt_len = max(2, gc.seq // 4)
    reqs = _make_trace(rng, n_requests, rate, gc.vocab, prompt_len, max_new)
    sched = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                        gpt2_step_inputs, eos_id=None,
                                        dispatch_ahead=4)
    t0 = time.perf_counter()
    done = sched.run(reqs)
    wall = time.perf_counter() - t0
    tokens = sum(len(r.tokens) for r in done)

    # ISSUE 15: quantiles come from the scheduler's live streaming
    # histograms — the SAME series the monitor panel and prometheus
    # export read, so bench and dashboard can never disagree. The
    # timestamp-list recompute survives only as the reqtrace-off
    # fallback.
    def hq(metric, q, fallback):
        h = sched.tracer.hists.get(metric) if sched.tracer else None
        if h is not None and h.count:
            return h.quantile(q)
        return fallback()

    ttfts = [r.ttft_s for r in done if r.ttft_s is not None]
    return {
        "arrival_rate_req_s": rate,
        "requests": len(done),
        "tokens": tokens,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(tokens / wall, 2),
        "tokens_per_s_per_cpu_device": round(tokens / wall / n_dev, 2),
        "ttft_p50_s": hq("ttft", 0.5, lambda: _quantile(ttfts, 0.5)),
        "ttft_p99_s": hq("ttft", 0.99, lambda: _quantile(ttfts, 0.99)),
        "per_token_p50_s": hq("decode_step", 0.5,
                              lambda: _quantile(sched.step_times, 0.5)),
        "per_token_p99_s": hq("decode_step", 0.99,
                              lambda: _quantile(sched.step_times, 0.99)),
        "decode_steps": sched.decode_steps,
        "prefill_batches": sched.prefills,
        "spec_accept_rate": (
            round(sched.stats["spec_accepted_tokens"]
                  / sched.stats["spec_drafted_tokens"], 4)
            if sched.stats.get("spec_drafted_tokens") else None),
        "all_complete": all(len(r.tokens) == r.max_new_tokens for r in done),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench_serve")
    p.add_argument("--rates", default="2,8",
                   help="comma-separated open-loop arrival rates (req/s)")
    p.add_argument("--requests", type=int, default=24)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default="", help="also write the JSON here")
    p.add_argument("--kv-cache-dtype", default="auto",
                   choices=("auto", "bf16", "int8"),
                   help="KV-cache storage dtype for the bench engine")
    p.add_argument("--check", action="store_true",
                   help="CI smoke: tiny twin, assert completion + ordered "
                        "finite quantiles + KV memory accounting")
    args = p.parse_args(argv)
    import jax  # a CPU-mesh counting tool: say what it ran on
    print(f"[bench_serve] platform={jax.default_backend()} "
          f"devices={len(jax.devices())}: counts and parity "
          "facts, never a device metric", file=sys.stderr)
    if args.check:
        args.requests = min(args.requests, 8)

    eng, gc, n_dev = _build_engine(args.check, args.kv_cache_dtype)
    ms = eng.memory_stats()
    hr = eng.health_report()["watermarks"]
    legs = []
    for i, r in enumerate(s for s in args.rates.split(",") if s.strip()):
        legs.append(_run_leg(eng, gc, n_dev, float(r), args.requests,
                             args.seed + i))
    report = {
        "model": "gpt2 CPU twin" + (" (check)" if args.check else ""),
        "devices": n_dev,
        "slots": eng.slots,
        "max_decode_len": eng.max_decode_len,
        "kv_page_size": eng.kv_spec.page_size,
        "prefill_vs_decode_strategy_differ": (
            eng.prefill_strategy.op_shardings != eng.decode_strategy.op_shardings),
        "kv_shard_degree": ms["kv_shard_degree"],
        "memory": ms,
        "watermark": hr,
        "legs": legs,
        # ISSUE 13: KV storage + speculation provenance on the artifact
        "kv_cache_dtype": str(eng.kv_dtype),
        "kv_itemsize": eng.kv_spec.itemsize,
        "kv_scale_itemsize": eng.kv_spec.scale_itemsize,
        "spec_tokens": eng.spec_tokens,
        # headline metrics (bench_history "serve" family)
        "tokens_per_s_per_cpu_device": max(l["tokens_per_s_per_cpu_device"] for l in legs),
        "ttft_p99_s": legs[-1]["ttft_p99_s"],
        "per_token_p99_s": legs[-1]["per_token_p99_s"],
        "spec_accept_rate": next(
            (l["spec_accept_rate"] for l in reversed(legs)
             if l["spec_accept_rate"] is not None), None),
    }
    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)

    if args.check:
        ok = True

        def fail(msg):
            nonlocal ok
            ok = False
            print("CHECK FAIL: " + msg, file=sys.stderr)

        for leg in legs:
            if leg["requests"] != args.requests or not leg["all_complete"]:
                fail(f"rate {leg['arrival_rate_req_s']}: "
                     f"{leg['requests']}/{args.requests} requests complete")
            for lo, hi in (("ttft_p50_s", "ttft_p99_s"),
                           ("per_token_p50_s", "per_token_p99_s")):
                if not (leg[lo] is not None and leg[hi] is not None
                        and 0.0 <= leg[lo] <= leg[hi]):
                    fail(f"rate {leg['arrival_rate_req_s']}: quantiles "
                         f"{lo}={leg[lo]} {hi}={leg[hi]} not ordered/finite")
            if leg["tokens_per_s_per_cpu_device"] <= 0:
                fail("zero serving throughput")
        if ms["predicted_kv_cache_bytes"] <= 0 or \
                ms["actual_kv_cache_bytes_per_device"] != \
                ms["predicted_kv_cache_bytes"]:
            fail(f"KV accounting mismatch: predicted "
                 f"{ms['predicted_kv_cache_bytes']} vs actual "
                 f"{ms['actual_kv_cache_bytes_per_device']}")
        if hr["ratio"] > hr["warn_ratio"]:
            fail(f"measured watermark {hr['ratio']:.2f}x predicted "
                 f"(warn at {hr['warn_ratio']}x)")
        print("CHECK " + ("PASS" if ok else "FAIL"))
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
