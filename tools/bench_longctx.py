"""Tiered KV cache long-context bench: the ISSUE 16 evidence artifact.

Three legs, all on the 8-device gpt2 CPU twin:

1. **Context capacity at fixed HBM pages** (the headline). Two engines
   with the SAME device KV pool (24 data pages, 4 slots): the HBM-only
   engine caps each sequence at 24 pages / 4 slots = 6 pages -> 24
   positions of context, while the tiered engine (--kv-host-pages moves
   3/4 of the slots' footprint to host) serves 96 positions per sequence
   through spill/prefetch rotation. Both are PROVEN by serving: the long
   trace completes fully on the tiered engine (every request all tokens)
   and is permanently shed by the HBM-only twin (its two-tier capacity
   IS its device pool). Headline: `context_gain_vs_hbm_only` (gates
   >= 4.0 on the full run).

2. **Spill-path parity.** The same short trace through an HBM-only
   engine and a tiered one whose device pool is HALVED: greedy streams
   must be bitwise identical (the tier moves committed pages; it never
   touches numerics), the run must really spill, and the prefetch
   hit/stall ledger must cover every rejoin. Reports
   `prefetch_hit_rate` (hits / rejoins — stalls are counted, never
   silent).

3. **Ring-vs-flash prefill crossover.** The serving prefill search must
   route a 16k-token prompt to the sequence-parallel ring candidate
   (priced with its forward-only comm) and keep a 512-token prompt on
   flash — the crossover comes out of the DP's pricing, not a hardcoded
   rule.

  python tools/bench_longctx.py                     # full run, gates on
  python tools/bench_longctx.py --out BENCH_longctx.json
  python tools/bench_longctx.py --check             # CI smoke: smaller
      host tier (2x context), capacity gate skipped, parity + ledger +
      crossover still asserted
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

MESH = {"data": 2, "model": 4}
SLOTS, PAGE = 4, 4


def _build_engine(gc_seq, max_new, host_pages, slots=SLOTS):
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models import GPT2Config, build_gpt2
    from flexflow_tpu.serving import compile_serving

    cfg = FFConfig(search_budget=16, mesh_shape=dict(MESH),
                   max_batch_slots=slots, kv_page_size=PAGE,
                   max_decode_len=max_new, log_level="warning",
                   kv_host_pages=host_pages, kv_prefetch_ahead=2,
                   strategy_cache=False)
    m = FFModel(cfg)
    gc = GPT2Config(vocab=256, seq=gc_seq, d_model=64, heads=4, layers=1,
                    dropout=0.0)
    build_gpt2(m, gc, batch=8)
    eng = compile_serving(m)
    eng.init(seed=0)
    return eng


def _serve(eng, n, prompt_len, max_new):
    from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,
                                      gpt2_prompt_inputs, gpt2_step_inputs)

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=list(rng.integers(1, 255, size=prompt_len)),
                    max_new_tokens=max_new, arrival_s=0.0) for i in range(n)]
    sched = ContinuousBatchingScheduler(
        eng, eng.params, gpt2_prompt_inputs, gpt2_step_inputs, eos_id=None,
        dispatch_ahead=2)
    t0 = time.perf_counter()
    done = sched.run(reqs)
    wall = time.perf_counter() - t0
    streams = {r.rid: list(r.tokens) for r in done}
    return streams, sched, wall


def _capacity_leg(check: bool, fails: list):
    """Same 24-page device pool, 4x (2x for --check) the servable context
    via the host tier — proven by serving the long trace to completion on
    the tiered engine and watching the HBM-only twin permanently shed it."""
    base_seq, base_new = 16, 6              # pps 6 -> 24 data pages, ctx 24
    if check:
        long_seq, long_new = 40, 8          # pps 12 -> host 24, ctx 48 (2x)
    else:
        long_seq, long_new = 88, 8          # pps 24 -> host 72, ctx 96 (4x)
    long_pps = -(-(long_seq + long_new) // PAGE)
    base_pps = -(-(base_seq + base_new) // PAGE)
    dev_pages = SLOTS * base_pps
    host = SLOTS * long_pps - dev_pages

    base = _build_engine(base_seq, base_new, 0)
    tier = _build_engine(long_seq, long_new, host)
    if tier.kv_spec.pool_pages != base.kv_spec.pool_pages:
        fails.append(
            f"device pools differ: tiered {tier.kv_spec.pool_pages} vs "
            f"HBM-only {base.kv_spec.pool_pages} — the gain would not be "
            "at fixed HBM pages")
    ctx_base = base.kv_spec.padded_len
    ctx_tier = tier.kv_spec.padded_len
    n = 4 if check else 6
    prompt_len = long_seq - 8
    streams, sched, wall = _serve(tier, n, prompt_len, long_new)
    complete = (len(streams) == n
                and all(len(t) == long_new for t in streams.values()))
    if not complete:
        fails.append(f"long-context trace incomplete on the tiered engine: "
                     f"{ {k: len(v) for k, v in streams.items()} }")
    ts = sched.kv.tier_stats()
    if not ts["kv_spills"]:
        fails.append("long-context leg never spilled — the device pool "
                     "covered everything, the gain is not tier-backed")
    # the HBM-only twin can NEVER hold one long sequence: permanent shed
    from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,
                                      gpt2_prompt_inputs, gpt2_step_inputs)
    shed_sched = ContinuousBatchingScheduler(
        base, base.params, gpt2_prompt_inputs, gpt2_step_inputs, eos_id=None)
    shed_sched.run([Request(rid=0, prompt=[1] * prompt_len,
                            max_new_tokens=long_new, arrival_s=0.0)])
    if shed_sched.stats["shed_prompt_too_long"] != 1:
        fails.append("HBM-only twin did not shed the long request as "
                     "permanent (capacity check regressed)")
    toks = sum(len(t) for t in streams.values())
    return {
        "device_data_pages": dev_pages,
        "host_pages": host,
        "context_hbm_only": ctx_base,
        "context_tiered": ctx_tier,
        "context_gain_vs_hbm_only": round(ctx_tier / ctx_base, 2),
        "requests": n,
        "prompt_len": prompt_len,
        "all_complete": complete,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(toks / wall, 2),
        "tier": ts,
        "hbm_only_shed": shed_sched.stats["shed_prompt_too_long"],
    }


def _parity_leg(check: bool, fails: list):
    """Bitwise greedy-stream parity across the spill path, plus the
    hit/stall ledger: every rejoin is a hit or a counted stall."""
    n = 4 if check else 6
    base = _build_engine(16, 6, 0)
    tier = _build_engine(16, 6, 12)         # device pool halved: 12 + 12
    base_streams, _s0, _w0 = _serve(base, n, 8, 6)
    tier_streams, sched, _w1 = _serve(tier, n, 8, 6)
    parity = base_streams == tier_streams
    if not parity:
        bad = [rid for rid in base_streams
               if tier_streams.get(rid) != base_streams[rid]]
        fails.append(f"spill-path streams diverged for rids {bad[:4]}")
    ts = sched.kv.tier_stats()
    if not ts["kv_spills"]:
        fails.append("parity leg never spilled — it proved nothing")
    joins = ts["kv_prefetch_hits"] + ts["kv_prefetch_stalls"]
    if joins != ts["kv_refills"]:
        fails.append(f"rejoin ledger leaks: {joins} classified vs "
                     f"{ts['kv_refills']} refills")
    return {
        "requests": n,
        "bitwise_parity": parity,
        "spills": ts["kv_spills"],
        "refills": ts["kv_refills"],
        "prefetch_hits": ts["kv_prefetch_hits"],
        "prefetch_stalls": ts["kv_prefetch_stalls"],
        "prefetch_hit_rate": (round(ts["kv_prefetch_hits"] / joins, 4)
                              if joins else 1.0),
        "spilled_bytes": ts["kv_spilled_bytes"],
    }


def _crossover_leg(fails: list):
    """The serving prefill search finds the ring/flash crossover from its
    own pricing: ring past the flash VMEM budget, flash below it."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.serving.program import clone_for_serving, serving_optimize

    mach = MachineSpec(mesh_axes=dict(MESH), chip="v5p")

    def probe(seq):
        cfg = FFConfig(search_budget=16, mesh_shape=dict(MESH),
                       log_level="warning", strategy_cache=False)
        m = FFModel(cfg)
        x = m.create_tensor((2, seq, 128), name="x")
        m.multihead_attention(x, x, x, embed_dim=128, num_heads=2,
                              name="attn")
        sm, attn = clone_for_serving(m, "prefill", 2)
        st = serving_optimize(sm, mach, "prefill", attn)
        sh = st.op_shardings.get("attn")
        return (sh.attrs or {}).get("seq_parallel") if sh else None

    ring_long = probe(16384) == "model"
    flash_short = probe(512) is None
    if not ring_long:
        fails.append("prefill search did not pick sp_ring at 16k")
    if not flash_short:
        fails.append("prefill search picked sp_ring at 512 (ring hops "
                     "are pure overhead there)")
    return {"ring_at_16k": ring_long, "flash_at_512": flash_short,
            "crossover_ok": ring_long and flash_short}


def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench_longctx")
    p.add_argument("--min-gain", type=float, default=4.0,
                   help="full-run gate on context_gain_vs_hbm_only")
    p.add_argument("--out", default="", help="also write the JSON here")
    p.add_argument("--check", action="store_true",
                   help="CI smoke: 2x host tier, capacity gate skipped; "
                        "parity, ledger and crossover still asserted")
    args = p.parse_args(argv)
    import jax  # a CPU-mesh counting tool: say what it ran on
    print(f"[bench_longctx] platform={jax.default_backend()} "
          f"devices={len(jax.devices())}: counts and parity "
          "facts, never a device metric", file=sys.stderr)

    fails: list = []
    capacity = _capacity_leg(args.check, fails)
    if not args.check and \
            capacity["context_gain_vs_hbm_only"] < args.min_gain:
        fails.append(f"context gain {capacity['context_gain_vs_hbm_only']} "
                     f"< gate {args.min_gain}")
    parity = _parity_leg(args.check, fails)
    crossover = _crossover_leg(fails)

    report = {
        "model": "gpt2 CPU twin" + (" (check)" if args.check else ""),
        "capacity": capacity,
        "parity": parity,
        "crossover": crossover,
        # headline metrics (bench_history "longctx" family)
        "context_gain_vs_hbm_only": capacity["context_gain_vs_hbm_only"],
        "prefetch_hit_rate": parity["prefetch_hit_rate"],
        "spill_parity": int(parity["bitwise_parity"]),
        "ring_crossover": int(crossover["crossover_ok"]),
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
