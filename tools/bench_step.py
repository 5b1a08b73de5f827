"""Training-step pipeline benchmark: sync vs async vs fused-dispatch fit.

Times the three fit-loop regimes (compiler/compile.py _fit_epochs) on a CPU
twin of the gpt2_small workload (same architecture, scaled so the per-step
dispatch/host-sync overhead the async pipeline removes is visible on the
8-virtual-device CPU mesh — the MULTICHIP twin convention):

  sync   — sync_every=1, steps_per_dispatch=1: the pre-pipeline loop
           (float(loss) + per-metric pulls every step)
  async  — sync_every=0 (default): device-resident loss/metric
           accumulation, zero mid-epoch host syncs
  fused  — async + steps_per_dispatch=K: K steps per dispatch via
           make_multi_step over stacked prefetched batches

Each mode trains a fresh identically-seeded model: identical data order and
init, so final losses must agree (async bit-identical to sync; fused within
float32 reassociation, <= 1e-6). Epoch 0 pays jit compile and is excluded
from timing. Results print as JSON; --out writes the report (committed as
BENCH_step_pipeline.json in the bench trajectory).

  python tools/bench_step.py                      # gpt2 CPU twin, K=8
  python tools/bench_step.py --model mlp --steps-per-dispatch 4
  python tools/bench_step.py --check              # CI smoke (tiny twin):
      asserts the fused loop issues <= ceil(num_batches/K) dispatches/epoch,
      zero mid-epoch host syncs in the async modes, and final losses match
      sync to 1e-6 — exits nonzero on regression (tier-1 safe, CPU backend).
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


def _build(name: str, batch: int):
    """Fresh model + synthetic dataset; identical across modes (fixed
    seeds) so loss trajectories are comparable."""
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.losses import LossType

    cfg = FFConfig(batch_size=batch, only_data_parallel=True, seed=3,
                   log_level="warning")
    rng = np.random.default_rng(0)
    if name.startswith("gpt2"):
        from flexflow_tpu.models import GPT2Config, build_gpt2

        # CPU twin of gpt2_small: same shape family, scaled until the step
        # is sub-10ms i.e. DISPATCH-bound — the regime the async pipeline
        # targets (per-step dispatch dominates sub-10ms steps; at CPU-sized
        # compute the sync loop's overhead is the majority cost). Dropout off so the fused
        # rng stream can't perturb the loss comparison.
        gc = GPT2Config(vocab=512, seq=16, d_model=64, heads=2, layers=1,
                        dropout=0.0)
        m = FFModel(cfg)
        build_gpt2(m, gc, batch=batch)
        n = (32 if name == "gpt2_check" else 64) * batch
        ids = rng.integers(0, gc.vocab, size=(n, gc.seq)).astype(np.int32)
        pos = np.broadcast_to(np.arange(gc.seq, dtype=np.int32),
                              (n, gc.seq)).copy()
        y = rng.integers(0, gc.vocab, size=(n, gc.seq)).astype(np.int32)
        x = [ids, pos]
    elif name == "mlp":
        m = FFModel(cfg)
        t = m.create_tensor([batch, 64], name="x")
        h = m.dense(t, 256, activation="gelu", name="up")
        h = m.dense(h, 64, name="down")
        m.dense(h, 8, name="head")
        n = 32 * batch
        x = [rng.normal(size=(n, 64)).astype(np.float32)]
        y = rng.integers(0, 8, size=(n,)).astype(np.int32)
    else:
        raise SystemExit(f"unknown --model {name!r}")
    cm = m.compile(SGDOptimizer(lr=0.01),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    return cm, x, y


def _run_mode(name: str, model: str, batch: int, epochs: int,
              sync_every: int, k: int, repeats: int = 1):
    """Train a fresh model under one pipeline regime; report steps/sec over
    the post-compile epochs plus the loop's own dispatch/sync counters.
    Best-of-`repeats` full runs: ambient load on a shared host depresses
    whole runs, so the fastest run is the least-contended measurement
    (losses/counters are identical across repeats — same seeds)."""
    best = None
    for _ in range(max(1, repeats)):
        r = _run_mode_once(name, model, batch, epochs, sync_every, k)
        if best is None or r["steps_per_sec"] > best["steps_per_sec"]:
            best = r
    return best


def _run_mode_once(name, model, batch, epochs, sync_every, k):
    cm, x, y = _build(model, batch)
    t0 = time.perf_counter()
    hist = cm.fit(x, y, epochs=epochs, verbose=False,
                  sync_every=sync_every, steps_per_dispatch=k)
    wall = time.perf_counter() - t0
    nb = len(y) // batch
    timed = hist[1:] if len(hist) > 1 else hist  # epoch 0 = jit compile
    # median of per-epoch rates (same convention as bench.py's median
    # windows): robust to a concurrent-load blip hitting one epoch
    rates = sorted(nb / e["epoch_time_s"] for e in timed if e["epoch_time_s"])
    sps = rates[len(rates) // 2] if rates else 0.0
    return {
        "mode": name,
        "sync_every": sync_every,
        "steps_per_dispatch": k,
        "steps_per_sec": round(sps, 2),
        "spread_steps_per_sec": [round(rates[0], 2), round(rates[-1], 2)]
        if rates else [0.0, 0.0],
        "samples_per_sec": round(batch * sps, 1),
        "final_loss": hist[-1]["loss"],
        "dispatches_per_epoch": int(hist[-1]["dispatches"]),
        "host_syncs_per_epoch": int(hist[-1]["host_syncs"]),
        "num_batches_per_epoch": nb,
        "wallclock_s": round(wall, 3),
        "step_stats": dict(cm.step_stats),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench_step")
    p.add_argument("--model", default="gpt2_twin",
                   choices=("gpt2_twin", "gpt2_check", "mlp"))
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--epochs", type=int, default=4)
    p.add_argument("--steps-per-dispatch", type=int, default=8)
    p.add_argument("--repeats", type=int, default=2,
                   help="best-of-N runs per mode (load-spike robustness)")
    p.add_argument("--out", default="", help="also write the JSON here")
    p.add_argument("--check", action="store_true",
                   help="CI smoke: tiny twin, assert dispatch count, zero "
                        "mid-epoch host syncs, and 1e-6 loss parity")
    args = p.parse_args(argv)
    import jax  # a CPU-mesh counting tool: say what it ran on
    print(f"[bench_step] platform={jax.default_backend()} "
          f"devices={len(jax.devices())}: counts and parity "
          "facts, never a device metric", file=sys.stderr)
    if args.check:
        args.model, args.epochs, args.repeats = "gpt2_check", 2, 1
        args.steps_per_dispatch = min(args.steps_per_dispatch, 4)
    k = max(2, args.steps_per_dispatch)

    sync = _run_mode("sync", args.model, args.batch, args.epochs,
                     sync_every=1, k=1, repeats=args.repeats)
    async_ = _run_mode("async", args.model, args.batch, args.epochs,
                       sync_every=0, k=1, repeats=args.repeats)
    fused = _run_mode("fused", args.model, args.batch, args.epochs,
                      sync_every=0, k=k, repeats=args.repeats)

    report = {
        "model": args.model,
        "model_note": "CPU twin of gpt2_small (scaled; dispatch-bound steps)"
        if args.model.startswith("gpt2") else args.model,
        "batch": args.batch,
        "epochs": args.epochs,
        "timed_epochs": max(1, args.epochs - 1),
        "modes": {"sync": sync, "async": async_, "fused": fused},
        "async_vs_sync_speedup": round(
            async_["steps_per_sec"] / max(sync["steps_per_sec"], 1e-9), 3),
        "fused_vs_sync_speedup": round(
            fused["steps_per_sec"] / max(sync["steps_per_sec"], 1e-9), 3),
        "loss_async_minus_sync": async_["final_loss"] - sync["final_loss"],
        "loss_fused_minus_sync": fused["final_loss"] - sync["final_loss"],
    }
    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)

    if args.check:
        ok = True
        nb = fused["num_batches_per_epoch"]
        max_disp = -(-nb // k) + 1  # ceil(nb/K) fused dispatches (+1 slack)
        if fused["dispatches_per_epoch"] > max_disp:
            print(f"CHECK FAIL: fused loop issued "
                  f"{fused['dispatches_per_epoch']} dispatches/epoch for "
                  f"{nb} batches at K={k} (max {max_disp})", file=sys.stderr)
            ok = False
        for mode in (async_, fused):
            if mode["host_syncs_per_epoch"] != 0:
                print(f"CHECK FAIL: {mode['mode']} loop made "
                      f"{mode['host_syncs_per_epoch']} mid-epoch host syncs "
                      "(expected 0 in the default config)", file=sys.stderr)
                ok = False
        tol = 1e-6 * max(1.0, abs(sync["final_loss"]))
        for mode in (async_, fused):
            if abs(mode["final_loss"] - sync["final_loss"]) > tol:
                print(f"CHECK FAIL: {mode['mode']} final loss "
                      f"{mode['final_loss']!r} != sync "
                      f"{sync['final_loss']!r} (tol {tol:g})",
                      file=sys.stderr)
                ok = False
        print("CHECK " + ("PASS" if ok else "FAIL"))
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
