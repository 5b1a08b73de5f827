"""Disaggregated serving fleet benchmark: the ISSUE 18 evidence artifact.

Builds N gpt2 CPU serving twins (same compiled graph, independent KV
pools, a host cold tier on every replica so the disagg handoff path is
live) and drives the `ServingFleet` control plane through four legs:

  scaling — weak-scaling throughput: N replicas serve N x `--per-rep`
      requests arriving open-loop at N x `--rate` (offered load grows
      with the fleet). On one host the replicas share a single XLA CPU
      runtime whose collectives would deadlock if interleaved, so the
      fleet serializes program execution and paces each replica on its
      own virtual device timeline (`step_floor_s` of occupancy per
      step — the floor models a real accelerator's per-step latency,
      which the CPU twin's microsecond steps under-represent; host-side
      scheduling overlaps it exactly as on a pipelined device). Gates:
      aggregate decode tokens/s >= 1.8x at 2 replicas and >= 3.2x at 4
      vs the identically-paced single replica, zero drops everywhere.
  mixed_priority — 2 replicas under bursty mixed-class load
      (priorities 0/1/2): every request completes and the urgent
      class's TTFT p99 is no worse than the batch class's.
  disagg — the same trace through colocated (2 mixed replicas) and
      disaggregated (1 prefill + 1 decode) topologies: committed KV
      pages travel prefill -> decode over the host tier, every request
      is handed off exactly once, greedy streams are BITWISE identical
      to colocated, and goodput stays within 2x of colocated (the
      honest price of the transfer on this twin).
  rolling_swap — a fine-tuning sibling commits durable snapshots into
      a watched root; the RollingSwapController advances the fleet one
      replica at a time at each replica's between-windows safe point.
      Gates: every replica swaps, ZERO requests dropped fleet-wide.

  python tools/bench_fleet.py                      # full bench
  python tools/bench_fleet.py --out BENCH_fleet.json
  python tools/bench_fleet.py --check   # CI smoke (2 replicas): asserts
      single-replica identity vs the pre-fleet scheduler, zero drops,
      disagg bitwise parity, and a complete rolling swap

Headline keys (bench_history "fleet" family): scale2_x, scale4_x,
fleet_tokens_per_s, mixed_ttft_p99_s, rolling_swaps,
rolling_dropped_inflight, disagg_goodput_ratio, legs_passed.
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
    xs = [x for x in xs if x is not None]
    if not xs:
        return None
    return float(np.quantile(np.asarray(xs, np.float64), q))


def _gc():
    # The tiny twin in BOTH modes: this bench measures the fleet layer
    # (routing, pacing, handoff, rollout), not model compute, and the
    # small twin maximizes replicas per host.
    from flexflow_tpu.models import GPT2Config
    return GPT2Config(vocab=256, seq=16, d_model=64, heads=2, layers=1,
                      dropout=0.0)


def _build_engine(gc, kv_host_pages=16):
    """One replica twin. Every replica gets a host cold tier so the
    disagg handoff (which travels through it) is live fleet-wide."""
    import jax

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models import build_gpt2
    from flexflow_tpu.serving import compile_serving

    n_dev = len(jax.devices())
    mesh = ({"data": 2, "model": n_dev // 2} if n_dev % 2 == 0 and n_dev > 1
            else {"data": max(1, n_dev)})
    cfg = FFConfig(search_budget=16, mesh_shape=mesh, log_level="warning",
                   max_batch_slots=4, kv_page_size=4,
                   kv_host_pages=kv_host_pages)
    m = FFModel(cfg)
    build_gpt2(m, gc, batch=8)
    eng = compile_serving(m, max_decode_len=4)
    eng.init(seed=0)
    return eng, n_dev


def _build_trainer(gc):
    """Training-side sibling of the SAME graph — the rolling leg's
    snapshot producer (fingerprint hangs off names + schemas only)."""
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


def _snapshot(cm, root, step):
    from flexflow_tpu.runtime.resilience import save_durable
    cm.init(seed=step)
    cm._iteration = step
    return save_durable(cm, root, block=True)


def _trace(rng, n, rate, vocab, prompt_len, max_new, priorities=(1,)):
    # tracefmt-backed (ISSUE 20): same rng draw order as the historical
    # inline generator, so fixed seeds reproduce identical traces — and
    # every fleet leg is save_trace()-able for twin replay.
    from flexflow_tpu.serving import tracefmt
    return tracefmt.records_to_requests(
        tracefmt.poisson_records(rng, n, rate, vocab, prompt_len, max_new,
                                 priorities=priorities))


def _fleet(engines, floor=0.0, **kw):
    from flexflow_tpu.serving import (ServingFleet, gpt2_prompt_inputs,
                                      gpt2_step_inputs)
    kw.setdefault("dispatch_ahead", 4)
    return ServingFleet(engines, gpt2_prompt_inputs, gpt2_step_inputs,
                        eos_id=None, step_floor_s=floor, **kw)


class Checks:
    def __init__(self):
        self.items = []

    def add(self, name, ok, detail=""):
        self.items.append({"check": name, "ok": bool(ok), "detail": detail})
        if not ok:
            print(f"CHECK FAIL: {name}: {detail}", file=sys.stderr)

    def ok(self):
        return all(c["ok"] for c in self.items)


def _run_leg(engines, gc, floor, per_rep, rate_per_rep, seed,
             priorities=(1,), **kw):
    """One fleet leg: fresh trace, fresh fleet, returns (fleet, row)."""
    n_rep = len(engines)
    rng = np.random.default_rng(seed)
    n = per_rep * n_rep
    reqs = _trace(rng, n, rate_per_rep * n_rep, gc.vocab, 4,
                  engines[0].max_decode_len, priorities=priorities)
    fleet = _fleet(engines, floor=floor, **kw)
    t0 = time.perf_counter()
    done = fleet.serve(reqs)
    wall = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in done)
    row = {"replicas": n_rep, "requests": n, "completed": len(done),
           "shed": len(fleet.shed), "failed": len(fleet.failed),
           "tokens_out": toks, "wall_s": wall,
           "tokens_per_s": toks / wall,
           "prefills": sum(h.sched.prefills for h in fleet.replicas
                           if h.sched is not None),
           "decode_steps": sum(h.sched.decode_steps for h in fleet.replicas
                               if h.sched is not None)}
    return fleet, done, row


# ------------------------------------------------------------------ leg 1
def leg_scaling(engines, gc, floor, per_rep, rate_per_rep, seed, checks,
                sizes=(1, 2, 4)):
    sizes = tuple(n for n in sizes if n <= len(engines))
    # compile-warm every engine (first program execution JITs inside the
    # fleet lock otherwise) + one paced single-replica throwaway
    _run_leg(engines, gc, 0.0, 4, 500.0, seed + 90)
    _run_leg(engines[:1], gc, floor, 8, rate_per_rep, seed + 91)
    rows = {}
    for n_rep in sizes:
        fleet, done, row = _run_leg(engines[:n_rep], gc, floor, per_rep,
                                    rate_per_rep, seed)
        checks.add(f"scaling_{n_rep}r_all_served",
                   row["completed"] == row["requests"]
                   and row["shed"] == 0 and row["failed"] == 0,
                   f"{row['completed']}/{row['requests']} shed={row['shed']}")
        rows[n_rep] = row
    base = rows[sizes[0]]["tokens_per_s"]
    out = {"step_floor_s": floor, "per_replica_requests": per_rep,
           "rate_per_replica": rate_per_rep,
           "legs": {str(k): v for k, v in rows.items()},
           "scale2_x": rows[2]["tokens_per_s"] / base if 2 in rows else None,
           "scale4_x": rows[4]["tokens_per_s"] / base if 4 in rows else None,
           "fleet_tokens_per_s": rows[max(sizes)]["tokens_per_s"]}
    if 2 in rows:
        checks.add("scaling_2x_gate", out["scale2_x"] >= 1.8,
                   f"scale2={out['scale2_x']:.2f} < 1.8")
    if 4 in rows:
        checks.add("scaling_4x_gate", out["scale4_x"] >= 3.2,
                   f"scale4={out['scale4_x']:.2f} < 3.2")
    return out


# ------------------------------------------------------------------ leg 2
def leg_mixed(engines, gc, floor, per_rep, seed, checks):
    # bursty mixed-class load: arrivals faster than the paced service
    # chain so queues form and the priority order actually decides TTFT
    fleet, done, row = _run_leg(engines, gc, floor, per_rep, 20.0, seed,
                                priorities=(0, 1, 1, 2))
    checks.add("mixed_all_served",
               row["completed"] == row["requests"] and row["shed"] == 0,
               f"{row['completed']}/{row['requests']} shed={row['shed']}")
    by_cls = {}
    for r in done:
        by_cls.setdefault(r.priority, []).append(r.ttft_s)
    p99 = {c: _quantile(v, 0.99) for c, v in sorted(by_cls.items())}
    urgent, batch = p99.get(0), p99.get(2)
    if urgent is not None and batch is not None:
        checks.add("mixed_priority_ordering", urgent <= batch,
                   f"urgent p99 {urgent:.3f}s > batch p99 {batch:.3f}s")
    row.update({"ttft_p99_s": _quantile([r.ttft_s for r in done], 0.99),
                "ttft_p99_by_priority":
                    {str(c): v for c, v in p99.items()},
                "ttft_p99_urgent_s": urgent, "ttft_p99_batch_s": batch})
    return row


# ------------------------------------------------------------------ leg 3
def leg_disagg(engines, gc, floor, per_rep, rate_per_rep, seed, checks):
    colo_fleet, colo_done, colo = _run_leg(
        engines, gc, floor, per_rep, rate_per_rep, seed,
        topology="colocated")
    dis_fleet, dis_done, dis = _run_leg(
        engines, gc, floor, per_rep, rate_per_rep, seed,
        topology="disagg", prefill_replicas=1)
    n = colo["requests"]
    checks.add("disagg_all_served",
               dis["completed"] == n and dis["shed"] == 0
               and dis["failed"] == 0,
               f"{dis['completed']}/{n} shed={dis['shed']}")
    handoffs = dis_fleet.stats["handoffs"]
    checks.add("disagg_every_request_handed_off", handoffs == n,
               f"handoffs={handoffs} != {n}")
    colo_toks = {r.rid: list(r.tokens) for r in colo_done}
    dis_toks = {r.rid: list(r.tokens) for r in dis_done}
    checks.add("disagg_bitwise_parity", colo_toks == dis_toks,
               "disagg greedy streams differ from colocated")
    ratio = dis["tokens_per_s"] / max(1e-9, colo["tokens_per_s"])
    checks.add("disagg_goodput_within_2x", ratio >= 0.5,
               f"goodput ratio {ratio:.2f} < 0.5")
    # the import side (decode pool) counts the adopted bytes
    moved = sum(h.engine.kv.tier_counters.get("kv_handoff_bytes", 0)
                for h in dis_fleet.replicas)
    return {"colocated": colo, "disagg": dis, "handoffs": handoffs,
            "kv_handoff_bytes": int(moved), "goodput_ratio": ratio}


# ------------------------------------------------------------------ leg 4
def leg_rolling(engines, gc, cm, root, floor, per_rep, seed, checks,
                second_snapshot=True):
    # stage snapshot 1 before serving: the rollout itself still happens
    # mid-traffic (safe points only exist while the fleet is serving)
    _snapshot(cm, root, 1)
    n_rep = len(engines)
    rng = np.random.default_rng(seed)
    n = per_rep * n_rep
    reqs = _trace(rng, n, 10.0 * n_rep, gc.vocab, 4,
                  engines[0].max_decode_len)
    fleet = _fleet(engines, floor=floor)

    def dropper():
        # a second snapshot once the first finished rolling across the
        # fleet — proves the cursor wraps and keeps rolling under load
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            rolling = fleet.rolling
            if rolling is not None and len(rolling.swaps) >= n_rep:
                _snapshot(cm, root, 2)
                return
            time.sleep(0.01)

    th = threading.Thread(target=dropper, daemon=True) \
        if second_snapshot else None
    if th:
        th.start()
    t0 = time.perf_counter()
    done = fleet.serve(reqs, watch_root=root, poll_interval_s=0.01)
    wall = time.perf_counter() - t0
    if th:
        th.join(timeout=5.0)
    dropped = len(fleet.shed) + len(fleet.failed)
    swaps = fleet.stats.get("rollout_swaps", 0)
    checks.add("rolling_zero_dropped",
               len(done) == n and dropped == 0,
               f"completed={len(done)}/{n} dropped={dropped}")
    checks.add("rolling_every_replica_swapped", swaps >= n_rep,
               f"rollout_swaps={swaps} < {n_rep}")
    versions = [getattr(e, "active_version", None) for e in engines]
    if not second_snapshot:
        checks.add("rolling_fleet_on_new_version",
                   all(v == 1 for v in versions), f"versions={versions}")
    toks = sum(len(r.tokens) for r in done)
    return {"replicas": n_rep, "requests": n, "completed": len(done),
            "dropped_inflight": dropped, "rollout_swaps": swaps,
            "rollout_rollbacks": fleet.stats.get("rollout_rollbacks", 0),
            "rollout_halted": fleet.stats.get("rollout_halted", False),
            "versions": versions, "wall_s": wall,
            "tokens_per_s": toks / wall}


# --------------------------------------------------------------- identity
def leg_identity(eng, gc, seed, checks):
    """Single-replica fleet == the pre-fleet scheduler: bitwise token
    streams, identical dispatch/host-sync counters."""
    from flexflow_tpu.serving import (ContinuousBatchingScheduler,
                                      gpt2_prompt_inputs, gpt2_step_inputs)
    def mk():
        return _trace(np.random.default_rng(seed), 8, 500.0, gc.vocab, 4,
                      eng.max_decode_len)
    sched = ContinuousBatchingScheduler(
        eng, eng.params, gpt2_prompt_inputs, gpt2_step_inputs,
        eos_id=None, dispatch_ahead=4)
    direct = sched.run(mk())
    fleet, done, _ = _run_leg([eng], gc, 0.0, 8, 500.0, seed)
    d_toks = {r.rid: list(r.tokens) for r in direct}
    f_toks = {r.rid: list(r.tokens) for r in done}
    checks.add("single_replica_bitwise", d_toks == f_toks,
               "fleet(1) token streams differ from the plain scheduler")
    fs = fleet.replicas[0].sched
    counters = ("prefills", "decode_steps", "materializations")
    same = all(getattr(sched, c) == getattr(fs, c) for c in counters)
    checks.add("single_replica_counters", same,
               "; ".join(f"{c}: {getattr(sched, c)} vs {getattr(fs, c)}"
                         for c in counters))
    return {"counters": {c: getattr(fs, c) for c in counters}}


def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench_fleet")
    p.add_argument("--per-rep", type=int, default=12,
                   help="requests per replica (weak scaling)")
    p.add_argument("--rate", type=float, default=10.0,
                   help="arrival rate per replica (offered load scales "
                        "with the fleet)")
    p.add_argument("--step-floor-ms", type=float, default=100.0,
                   help="simulated per-step device occupancy (the CPU "
                        "twin's microsecond steps under-represent a real "
                        "accelerator; recorded in the artifact)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default="", help="also write the JSON here")
    p.add_argument("--check", action="store_true",
                   help="CI smoke: 2 replicas, identity/parity/rollout "
                        "invariants only (no timing gates)")
    args = p.parse_args(argv)
    import jax  # a CPU-mesh counting tool: say what it ran on
    print(f"[bench_fleet] platform={jax.default_backend()} "
          f"devices={len(jax.devices())}: counts and parity "
          "facts, never a device metric", file=sys.stderr)
    floor = args.step_floor_ms / 1e3
    n_engines = 2 if args.check else 4
    if args.check:
        args.per_rep = min(args.per_rep, 6)
        floor = min(floor, 0.02)

    gc = _gc()
    engines = []
    for _ in range(n_engines):
        eng, n_dev = _build_engine(gc)
        engines.append(eng)
    cm = _build_trainer(gc)
    root = tempfile.mkdtemp(prefix="ff_fleet_bench_")
    checks = Checks()
    try:
        ident = leg_identity(engines[0], gc, args.seed + 1, checks)
        scaling = leg_scaling(engines, gc, floor, args.per_rep, args.rate,
                              args.seed + 2, checks,
                              sizes=(1, 2) if args.check else (1, 2, 4))
        if args.check:
            # no timing gates in CI: drop the scaling-ratio verdicts,
            # keep the zero-drop ones
            checks.items = [c for c in checks.items
                            if not c["check"].endswith("x_gate")]
        mixed = leg_mixed(engines[:2], gc, floor, 16 if not args.check
                          else args.per_rep, args.seed + 3, checks)
        disagg = leg_disagg(engines[:2], gc, floor, args.per_rep,
                            args.rate, args.seed + 4, checks)
        rolling = leg_rolling(engines[:2], gc, cm, root, min(floor, 0.05),
                              args.per_rep, args.seed + 5, checks,
                              second_snapshot=not args.check)
    finally:
        shutil.rmtree(root, ignore_errors=True)

    report = {
        "model": "gpt2 CPU twin" + (" (check)" if args.check else ""),
        "devices": n_dev,
        "replicas_built": n_engines,
        "slots": engines[0].slots,
        "max_decode_len": engines[0].max_decode_len,
        "step_floor_s": floor,
        "legs": {"identity": ident, "scaling": scaling,
                 "mixed_priority": mixed, "disagg": disagg,
                 "rolling_swap": rolling},
        "checks": checks.items,
        # headline metrics (bench_history "fleet" family)
        "scale2_x": scaling["scale2_x"],
        "scale4_x": scaling["scale4_x"],
        "fleet_tokens_per_s": scaling["fleet_tokens_per_s"],
        "mixed_ttft_p99_s": mixed["ttft_p99_s"],
        "rolling_swaps": rolling["rollout_swaps"],
        "rolling_dropped_inflight": rolling["dropped_inflight"],
        "disagg_goodput_ratio": disagg["goodput_ratio"],
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
    sys.exit(main())
