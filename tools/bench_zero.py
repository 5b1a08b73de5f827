"""ZeRO-sharded optimizer-state benchmark: replicated vs zero1 memory + speed.

Trains the gpt2 CPU twin (8-virtual-device data-parallel mesh — the
MULTICHIP twin convention) under the two optimizer-state regimes
(compiler/compile.py):

  replicated — zero_sharding=off: Adam moments replicated over the data
               axis (the reference's fully-replicated NCCL regime)
  zero1      — moments sharded over the data axis; the update runs as
               reduce-scatter(grads) -> sharded moment update ->
               all-gather(updates)

and reports, per mode:

  * PREDICTED per-device optimizer-state bytes (the search cost model's
    OptMemSpec accounting, CompiledModel.memory_stats)
  * ACTUAL per-device optimizer-state bytes measured from the live
    buffers (addressable-shard bytes of the opt_state tree on device 0)
  * steps/sec over the post-compile epochs, and the final loss

Identical seeds/data across modes, so final losses must agree to <= 1e-6
(the update arithmetic is elementwise-identical; only the layout moves).
Results print as JSON; --out writes the report (committed as
BENCH_zero.json in the bench trajectory).

  python tools/bench_zero.py                      # gpt2 CPU twin
  python tools/bench_zero.py --model mlp --accum-steps 4
  python tools/bench_zero.py --check              # CI smoke (tiny twin):
      asserts predicted AND actual per-device optimizer-state bytes shrink
      by ~the data-axis degree under zero1, 1e-6 final-loss parity with the
      replicated baseline, and accum_steps=4 equivalence with a 4x batch —
      exits nonzero on regression (tier-1 safe, CPU backend).
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


def _build(name: str, batch: int, zero: str, accum: int = 1,
           state_dtype: str = "float32", n_samples: int = 0):
    """Fresh model + synthetic dataset; identical across modes (fixed
    seeds) so loss trajectories are comparable. `n_samples` pins the
    dataset size (the accum-vs-big-batch check needs IDENTICAL data under
    different graph batch sizes)."""
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
    from flexflow_tpu.losses import LossType

    cfg = FFConfig(batch_size=batch, only_data_parallel=True, seed=3,
                   zero_sharding=zero, accum_steps=accum,
                   log_level="warning")
    rng = np.random.default_rng(0)
    if name.startswith("gpt2"):
        from flexflow_tpu.models import GPT2Config, build_gpt2

        # CPU twin of gpt2_small (bench_step's convention): same shape
        # family, scaled to the 8-virtual-device CPU mesh. Dropout off so
        # the rng stream can't perturb the loss comparison.
        gc = GPT2Config(vocab=512, seq=16, d_model=64, heads=2, layers=1,
                        dropout=0.0)
        m = FFModel(cfg)
        build_gpt2(m, gc, batch=batch)
        n = n_samples or (16 if name == "gpt2_check" else 48) * batch
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
        n = n_samples or 32 * batch
        x = [rng.normal(size=(n, 64)).astype(np.float32)]
        y = rng.integers(0, 8, size=(n,)).astype(np.int32)
    else:
        raise SystemExit(f"unknown --model {name!r}")
    cm = m.compile(AdamOptimizer(alpha=0.001, state_dtype=state_dtype),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    return cm, x, y


def _run_mode(mode: str, model: str, batch: int, epochs: int, accum: int,
              repeats: int = 1, state_dtype: str = "float32",
              n_samples: int = 0):
    """Train a fresh model under one optimizer-state regime; report the
    memory split and steps/sec. Best-of-`repeats` (ambient-load
    robustness; losses/memory identical across repeats — same seeds)."""
    best = None
    for _ in range(max(1, repeats)):
        r = _run_mode_once(mode, model, batch, epochs, accum, state_dtype,
                           n_samples)
        if best is None or r["steps_per_sec"] > best["steps_per_sec"]:
            best = r
    return best


def _run_mode_once(mode, model, batch, epochs, accum, state_dtype,
                   n_samples=0):
    zero = "off" if mode == "replicated" else mode
    cm, x, y = _build(model, batch, zero, accum, state_dtype, n_samples)
    mem0 = cm.memory_stats()  # at init: sharded-from-birth (jitted tx.init)
    t0 = time.perf_counter()
    hist = cm.fit(x, y, epochs=epochs, verbose=False)
    wall = time.perf_counter() - t0
    mem = cm.memory_stats()
    nb = len(y) // (batch * accum)
    timed = hist[1:] if len(hist) > 1 else hist  # epoch 0 = jit compile
    rates = sorted(nb / e["epoch_time_s"] for e in timed if e["epoch_time_s"])
    sps = rates[len(rates) // 2] if rates else 0.0
    return {
        "mode": mode,
        "zero_sharding": zero,
        "accum_steps": accum,
        "steps_per_sec": round(sps, 2),
        "samples_per_sec": round(batch * accum * sps, 1),
        "final_loss": hist[-1]["loss"],
        "updates_per_epoch": nb,
        "wallclock_s": round(wall, 3),
        "data_axis_degree": mem["data_axis_degree"],
        "predicted_opt_state_bytes": mem["predicted_opt_state_bytes"],
        "actual_opt_state_bytes_per_device":
            mem["actual_opt_state_bytes_per_device"],
        "actual_opt_state_bytes_at_init":
            mem0["actual_opt_state_bytes_per_device"],
        "predicted_weight_state_bytes": mem["predicted_weight_state_bytes"],
        "actual_param_bytes_per_device": mem["actual_param_bytes_per_device"],
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench_zero")
    p.add_argument("--model", default="gpt2_twin",
                   choices=("gpt2_twin", "gpt2_check", "mlp"))
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--accum-steps", type=int, default=1)
    p.add_argument("--state-dtype", default="float32",
                   choices=("float32", "bfloat16"))
    p.add_argument("--repeats", type=int, default=2,
                   help="best-of-N runs per mode (load-spike robustness)")
    p.add_argument("--out", default="", help="also write the JSON here")
    p.add_argument("--check", action="store_true",
                   help="CI smoke: tiny twin, assert the ~data-degree "
                        "opt-state reduction (predicted AND actual), 1e-6 "
                        "loss parity, and accum equivalence")
    args = p.parse_args(argv)
    import jax  # a CPU-mesh counting tool: say what it ran on
    print(f"[bench_zero] platform={jax.default_backend()} "
          f"devices={len(jax.devices())}: counts and parity "
          "facts, never a device metric", file=sys.stderr)
    if args.check:
        args.model, args.epochs, args.repeats = "gpt2_check", 2, 1

    repl = _run_mode("replicated", args.model, args.batch, args.epochs,
                     args.accum_steps, args.repeats, args.state_dtype)
    zero = _run_mode("zero1", args.model, args.batch, args.epochs,
                     args.accum_steps, args.repeats, args.state_dtype)

    def ratio(a, b):
        return round(a / max(1, b), 2)

    report = {
        "model": args.model,
        "model_note": "CPU twin of gpt2_small (8-virtual-device data mesh)"
        if args.model.startswith("gpt2") else args.model,
        "batch": args.batch,
        "epochs": args.epochs,
        "accum_steps": args.accum_steps,
        "state_dtype": args.state_dtype,
        "modes": {"replicated": repl, "zero1": zero},
        "opt_state_reduction_predicted": ratio(
            repl["predicted_opt_state_bytes"],
            zero["predicted_opt_state_bytes"]),
        "opt_state_reduction_actual": ratio(
            repl["actual_opt_state_bytes_per_device"],
            zero["actual_opt_state_bytes_per_device"]),
        "data_axis_degree": zero["data_axis_degree"],
        "loss_zero_minus_replicated":
            zero["final_loss"] - repl["final_loss"],
        "zero_vs_replicated_speed": ratio(
            zero["steps_per_sec"] * 100, repl["steps_per_sec"] * 100),
    }
    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)

    if args.check:
        ok = True
        deg = zero["data_axis_degree"]
        # ~data-axis-degree reduction: the step-count scalar and any
        # non-divisible weight keep a replicated sliver, so accept >= deg/2
        for k in ("opt_state_reduction_predicted",
                  "opt_state_reduction_actual"):
            if report[k] < deg / 2:
                print(f"CHECK FAIL: {k}={report[k]} < {deg / 2} "
                      f"(data degree {deg})", file=sys.stderr)
                ok = False
        # sharded-from-birth: the jitted tx.init must not allocate the
        # replicated worst case even transiently at rest
        if zero["actual_opt_state_bytes_at_init"] > \
                repl["actual_opt_state_bytes_at_init"] / (deg / 2):
            print("CHECK FAIL: zero1 opt state not sharded at init "
                  f"({zero['actual_opt_state_bytes_at_init']}B vs replicated "
                  f"{repl['actual_opt_state_bytes_at_init']}B)",
                  file=sys.stderr)
            ok = False
        tol = 1e-6 * max(1.0, abs(repl["final_loss"]))
        if abs(report["loss_zero_minus_replicated"]) > tol:
            print(f"CHECK FAIL: zero1 final loss {zero['final_loss']!r} != "
                  f"replicated {repl['final_loss']!r} (tol {tol:g})",
                  file=sys.stderr)
            ok = False
        # accumulation equivalence: accum=4 at batch B == one step at 4B
        # on the SAME dataset (n pinned — the default dataset size scales
        # with the graph batch, which would change the data)
        n = 16 * args.batch * 4
        acc = _run_mode("replicated", args.model, args.batch, args.epochs,
                        4, n_samples=n)
        big = _run_mode("replicated", args.model, args.batch * 4,
                        args.epochs, 1, n_samples=n)
        dtol = 1e-5 * max(1.0, abs(big["final_loss"]))
        if abs(acc["final_loss"] - big["final_loss"]) > dtol:
            print(f"CHECK FAIL: accum=4 loss {acc['final_loss']!r} != "
                  f"batch x4 loss {big['final_loss']!r} (tol {dtol:g})",
                  file=sys.stderr)
            ok = False
        print("CHECK " + ("PASS" if ok else "FAIL"))
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
