"""Speculative decoding + quantized KV bench: the ISSUE 13 evidence artifact.

Two legs, both on the 8-device gpt2 CPU twin:

1. **Speculation speedup + parity.** Trains a target gpt2 and a ~20x
   smaller draft on the deterministic successor task (`y = (x+1) % vocab`)
   so draft/target agreement is high, then serves the SAME open-loop trace
   through (a) the plain bf16-KV engine and (b) speculative engines at each
   draft depth K. Every committed token is the verify program's argmax, so
   the greedy streams must be BITWISE identical to the baseline — asserted
   per request, not sampled. Headline: `spec_speedup_best` (tokens/s/chip
   at the best K over the non-speculative baseline; the full run gates on
   >= 1.3x). The speedup is real amortization, not batching slack: a round
   is ONE fused program launch (K draft steps + the K+1-token verify,
   `engine.build_spec_program`) that commits ~accept*K+1 tokens, where the
   baseline pays one target launch per token.

2. **int8 KV strategy divergence.** Compiles the decode program twice at a
   geometry where the searched sharding answer flips with KV itemsize:
   bf16 pages push the bandwidth-priced search to head-sharded attention
   (kv_shard_degree 4) while int8 halves the page bytes and the pure-DP
   plan wins (degree 1). Asserts the degrees DIFFER and that the int8
   engine's predicted KV bytes equal the measured per-device residency
   exactly (pools + per-entry-per-head scales).

  python tools/bench_spec.py                  # full run, gates enforced
  python tools/bench_spec.py --out BENCH_spec.json
  python tools/bench_spec.py --check          # CI smoke: untrained tiny
      twin, parity + divergence + accounting asserted, speedup not gated
      (acceptance ~0 without training, which is the parity worst case)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

VOCAB, SEQ = 128, 32
PROMPT_LEN, MAX_NEW = 8, 24


def _mesh():
    import jax

    n_dev = len(jax.devices())
    return ({"data": 2, "model": n_dev // 2}
            if n_dev % 2 == 0 and n_dev > 1 else {"data": max(1, n_dev)}), n_dev


def _gpt2_pair(check: bool):
    from flexflow_tpu.models import GPT2Config

    if check:
        tgt = GPT2Config(vocab=64, seq=16, d_model=32, heads=2, layers=1,
                         dropout=0.0)
        draft = GPT2Config(vocab=64, seq=16, d_model=16, heads=2, layers=1,
                           dropout=0.0)
    else:
        tgt = GPT2Config(vocab=VOCAB, seq=SEQ, d_model=128, heads=4,
                         layers=2, dropout=0.0)
        draft = GPT2Config(vocab=VOCAB, seq=SEQ, d_model=32, heads=4,
                           layers=1, dropout=0.0)
    return tgt, draft


def _train(gc, epochs: int, seed: int):
    """Fit the successor task y=(x+1)%vocab — deterministic, learnable to
    ~100% argmax accuracy in a few epochs, so draft and target generate the
    same chains and acceptance is high (the speedup-side regime; the
    0-acceptance worst case is covered by --check and test_serving)."""
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
    from flexflow_tpu.losses import LossType
    from flexflow_tpu.models import build_gpt2

    cfg = FFConfig(batch_size=16, only_data_parallel=True, seed=seed,
                   log_level="warning")
    m = FFModel(cfg)
    build_gpt2(m, gc, batch=16)
    cm = m.compile(AdamOptimizer(alpha=3e-3),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=seed)
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, gc.vocab, size=(256, gc.seq)).astype(np.int32)
    pos = np.broadcast_to(np.arange(gc.seq, dtype=np.int32),
                          (256, gc.seq)).copy()
    y = ((ids + 1) % gc.vocab).astype(np.int32)
    hist = cm.fit([ids, pos], y, epochs=epochs, verbose=False)
    return cm.params, float(hist[-1]["loss"])


def _serve_cfg(cache_dir: str, mesh, **kw):
    from flexflow_tpu import FFConfig

    return FFConfig(search_budget=16, mesh_shape=mesh, log_level="warning",
                    strategy_cache_dir=cache_dir, **kw)


def _build(gc, cfg):
    from flexflow_tpu import FFModel
    from flexflow_tpu.models import build_gpt2

    m = FFModel(cfg)
    build_gpt2(m, gc, batch=8)
    return m


def _trace(n, gc, prompt_len, max_new):
    from flexflow_tpu.serving import Request

    rng = np.random.default_rng(7)
    return [Request(rid=i,
                    prompt=list(rng.integers(1, gc.vocab, size=prompt_len)),
                    max_new_tokens=max_new, arrival_s=0.0)
            for i in range(n)]


def _run(eng, gc, n, prompt_len, max_new, n_dev):
    """Warm (compile) then time one closed-burst trace; returns per-leg
    metrics plus the full per-request token streams for parity checks."""
    from flexflow_tpu.serving import (ContinuousBatchingScheduler,
                                      gpt2_prompt_inputs, gpt2_step_inputs)

    warm = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                       gpt2_step_inputs, eos_id=None)
    warm.run(_trace(2, gc, prompt_len, max_new))
    sched = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                        gpt2_step_inputs, eos_id=None)
    t0 = time.perf_counter()
    done = sched.run(_trace(n, gc, prompt_len, max_new))
    wall = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in done)
    drafted = sched.stats["spec_drafted_tokens"]
    return {
        "requests": len(done),
        "tokens": toks,
        "wall_s": round(wall, 3),
        "tokens_per_s_per_cpu_device": round(toks / wall / n_dev, 2),
        "spec_rounds": sched.stats["spec_rounds"],
        "spec_accept_rate": (
            round(sched.stats["spec_accepted_tokens"] / drafted, 4)
            if drafted else None),
        "all_complete": all(len(r.tokens) == r.max_new_tokens for r in done),
    }, {r.rid: list(r.tokens) for r in done}


def _speculation_legs(check: bool, depths, n_requests: int, cache_dir: str,
                      fails: list):
    from flexflow_tpu.serving import compile_serving

    mesh, n_dev = _mesh()
    tgt_gc, draft_gc = _gpt2_pair(check)
    prompt_len = 4 if check else PROMPT_LEN
    max_new = 8 if check else MAX_NEW
    if check:
        tgt_params = draft_params = None
        train_loss = None
    else:
        tgt_params, train_loss = _train(tgt_gc, 6, seed=0)
        draft_params, _ = _train(draft_gc, 6, seed=1)

    cfg = _serve_cfg(cache_dir, mesh, max_batch_slots=4, kv_page_size=4,
                     max_decode_len=max_new, kv_cache_dtype="bf16")
    base = compile_serving(_build(tgt_gc, cfg))
    if tgt_params is None:
        base.init(seed=0)
        tgt_params = base.params
    else:
        base.load_params(tgt_params)
    base_leg, base_streams = _run(base, tgt_gc, n_requests, prompt_len,
                                  max_new, n_dev)
    base_leg["name"] = "baseline-bf16"
    legs = [base_leg]

    best = None
    for K in depths:
        eng = compile_serving(_build(tgt_gc, cfg), draft=_build(draft_gc, cfg),
                              spec_tokens=K)
        eng.load_params(tgt_params)
        if draft_params is None:
            eng.draft.init(seed=1)
        else:
            eng.draft.load_params(draft_params)
        leg, streams = _run(eng, tgt_gc, n_requests, prompt_len, max_new,
                            n_dev)
        leg["name"] = f"spec-K{K}"
        leg["spec_tokens"] = K
        leg["speedup_vs_baseline"] = round(
            leg["tokens_per_s_per_cpu_device"] / base_leg["tokens_per_s_per_cpu_device"],
            3)
        leg["bitwise_parity"] = streams == base_streams
        if not leg["bitwise_parity"]:
            bad = [rid for rid in base_streams
                   if streams.get(rid) != base_streams[rid]]
            fails.append(f"spec K={K}: greedy stream diverged from "
                         f"non-speculative baseline for rids {bad[:4]}")
        if not leg["all_complete"]:
            fails.append(f"spec K={K}: incomplete requests")
        legs.append(leg)
        if best is None or leg["tokens_per_s_per_cpu_device"] > \
                best["tokens_per_s_per_cpu_device"]:
            best = leg
    return {
        "devices": n_dev,
        "mesh": mesh,
        "train_loss": train_loss,
        "legs": legs,
        "spec_speedup_best": best["speedup_vs_baseline"],
        "spec_accept_rate_best": best["spec_accept_rate"],
        "spec_tokens_best": best["spec_tokens"],
        "baseline_tokens_per_s_per_cpu_device": base_leg["tokens_per_s_per_cpu_device"],
    }


def _int8_divergence_leg(check: bool, cache_dir: str, fails: list):
    """The search-priced leg: same model, same mesh, only the KV itemsize
    changes — and the searched decode sharding flips. Geometry sits inside
    the window where bf16's KV page traffic still beats the tp all-reduce
    (head-sharded, degree 4) but int8's halved pages don't (pure DP)."""
    from flexflow_tpu.models import GPT2Config
    from flexflow_tpu.serving import compile_serving

    mesh, n_dev = _mesh()
    slots = 12 if check else 16
    gc = GPT2Config(vocab=256, seq=16, d_model=64, heads=4, layers=1,
                    dropout=0.0)
    out = {"slots": slots, "geometry": "gpt2 d_model=64 heads=4 layers=1"}
    engines = {}
    for dt in ("bf16", "int8"):
        cfg = _serve_cfg(cache_dir, mesh, max_batch_slots=slots,
                         kv_page_size=4, max_decode_len=8,
                         kv_cache_dtype=dt)
        eng = compile_serving(_build(gc, cfg))
        eng.init(seed=0)
        engines[dt] = eng
        ms = eng.memory_stats()
        out[f"{dt}_kv_shard_degree"] = ms["kv_shard_degree"]
        out[f"{dt}_predicted_kv_cache_bytes"] = ms["predicted_kv_cache_bytes"]
        out[f"{dt}_actual_kv_cache_bytes"] = \
            ms["actual_kv_cache_bytes_per_device"]
        if ms["actual_kv_cache_bytes_per_device"] != \
                ms["predicted_kv_cache_bytes"]:
            fails.append(f"{dt}: predicted KV bytes "
                         f"{ms['predicted_kv_cache_bytes']} != measured "
                         f"{ms['actual_kv_cache_bytes_per_device']}")
    if out["bf16_kv_shard_degree"] == out["int8_kv_shard_degree"]:
        fails.append(
            "searched decode strategy did NOT diverge with KV dtype: "
            f"bf16 degree {out['bf16_kv_shard_degree']} == int8 degree "
            f"{out['int8_kv_shard_degree']}")
    leg, _ = _run(engines["int8"], gc, 8 if check else 16, 4, 8, n_dev)
    if not leg["all_complete"]:
        fails.append("int8 serving leg: incomplete requests")
    out["int8_serve"] = leg
    out["int8_tokens_per_s_per_cpu_device"] = leg["tokens_per_s_per_cpu_device"]
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench_spec")
    p.add_argument("--depths", default="2,4",
                   help="comma-separated draft depths K to sweep")
    p.add_argument("--requests", type=int, default=12)
    p.add_argument("--min-speedup", type=float, default=1.3,
                   help="full-run gate on spec_speedup_best")
    p.add_argument("--out", default="", help="also write the JSON here")
    p.add_argument("--check", action="store_true",
                   help="CI smoke: untrained tiny twin, parity + strategy "
                        "divergence + KV accounting asserted; the speedup "
                        "gate is skipped (acceptance ~0 untrained)")
    args = p.parse_args(argv)
    import jax  # a CPU-mesh counting tool: say what it ran on
    print(f"[bench_spec] platform={jax.default_backend()} "
          f"devices={len(jax.devices())}: counts and parity "
          "facts, never a device metric", file=sys.stderr)
    depths = [int(s) for s in args.depths.split(",") if s.strip()]
    if args.check:
        depths = depths[:1]
        args.requests = min(args.requests, 6)

    fails: list = []
    cache_dir = tempfile.mkdtemp(prefix="bench_spec_strategies_")
    spec = _speculation_legs(args.check, depths, args.requests, cache_dir,
                             fails)
    if not args.check and spec["spec_speedup_best"] < args.min_speedup:
        fails.append(f"spec_speedup_best {spec['spec_speedup_best']} < "
                     f"gate {args.min_speedup}")
    int8 = _int8_divergence_leg(args.check, cache_dir, fails)

    report = {
        "model": "gpt2 CPU twin" + (" (check)" if args.check else ""),
        "speculation": spec,
        "int8_divergence": int8,
        # headline metrics (bench_history "spec" family)
        "spec_speedup_best": spec["spec_speedup_best"],
        "spec_accept_rate_best": spec["spec_accept_rate_best"],
        "spec_tokens_best": spec["spec_tokens_best"],
        "int8_tokens_per_s_per_cpu_device": int8["int8_tokens_per_s_per_cpu_device"],
        "int8_kv_shard_degree": int8["int8_kv_shard_degree"],
        "bf16_kv_shard_degree": int8["bf16_kv_shard_degree"],
        "legs_passed": int(not fails),
    }
    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    for msg in fails:
        print("CHECK FAIL: " + msg, file=sys.stderr)
    print("CHECK " + ("PASS" if not fails else "FAIL"))
    return 0 if not fails else 1


if __name__ == "__main__":
    raise SystemExit(main())
