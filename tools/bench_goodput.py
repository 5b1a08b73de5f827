"""Goodput-accounting benchmark: where does fit wall-clock actually go?

Runs the gpt2 CPU twin (bench_step.py's MULTICHIP twin convention) under
two regimes and reports the health.GoodputMeter accounting for each:

  baseline    — the default async fit loop (no checkpointing): goodput
                should be dominated by the dispatch bucket
  ckpt_heavy  — --checkpoint-every-steps 1 forced: every optimizer step
                snapshots + commits a durable checkpoint on the fit
                thread, so the checkpoint bucket swells and goodput%
                visibly drops — the bench's evidence that the accounting
                attributes real lost time, not noise

Both legs must tile their wall-clock: the buckets + explicit residual
account for >= 95% of the measured fit wall (the ISSUE 9 acceptance
bar, asserted under --check). Results print as JSON; --out writes the
report (committed as BENCH_goodput.json in the bench trajectory).

  python tools/bench_goodput.py                    # gpt2 CPU twin
  python tools/bench_goodput.py --model mlp --epochs 3
  python tools/bench_goodput.py --check            # CI smoke (tiny twin):
      asserts accounted fraction >= 0.95 in both legs, a nonzero
      checkpoint bucket and lower goodput in the ckpt_heavy leg, and
      identical final losses (checkpointing must not perturb training).
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


def _build(name: str, batch: int):
    """Fresh model + synthetic dataset (fixed seeds — identical across
    legs so final losses are comparable); bench_step.py's twin builder."""
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.losses import LossType

    cfg = FFConfig(batch_size=batch, only_data_parallel=True, seed=3,
                   log_level="warning")
    rng = np.random.default_rng(0)
    if name.startswith("gpt2"):
        from flexflow_tpu.models import GPT2Config, build_gpt2

        gc = GPT2Config(vocab=512, seq=16, d_model=64, heads=2, layers=1,
                        dropout=0.0)
        m = FFModel(cfg)
        build_gpt2(m, gc, batch=batch)
        n = (16 if name == "gpt2_check" else 64) * batch
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


def _run_leg(leg: str, model: str, batch: int, epochs: int,
             ckpt_every: int = 0):
    """One fresh fit; report the goodput accounting for it. Epoch 0 pays
    jit compile — its dispatch bucket absorbs that (still accounted), so
    the headline goodput uses the post-compile epochs from history."""
    cm, x, y = _build(model, batch)
    kw = {}
    td = None
    if ckpt_every:
        td = tempfile.TemporaryDirectory(prefix="ff_bench_goodput_")
        kw = {"checkpoint_dir": td.name,
              "checkpoint_every_steps": ckpt_every}
    t0 = time.perf_counter()
    hist = cm.fit(x, y, epochs=epochs, verbose=False, **kw)
    wall = time.perf_counter() - t0
    rep = cm.goodput_report()
    if td is not None:
        from flexflow_tpu.runtime import checkpoint as ck

        ck.wait_pending()  # async writers must drain before rmtree
        td.cleanup()
    timed = hist[1:] if len(hist) > 1 else hist
    gps = sorted(e["goodput"] for e in timed)
    return {
        "leg": leg,
        "checkpoint_every_steps": ckpt_every,
        "goodput": round(gps[len(gps) // 2], 4) if gps else 0.0,
        "goodput_per_epoch": [round(e["goodput"], 4) for e in hist],
        "accounted_frac": round(rep.get("accounted_frac", 0.0), 4),
        "residual_s": round(rep.get("residual_s", 0.0), 4),
        "buckets_s": {k: round(v, 4)
                      for k, v in rep.get("buckets", {}).items() if v},
        "fit_wall_s": round(rep.get("wall_s", 0.0), 3),
        "measured_wall_s": round(wall, 3),
        "final_loss": hist[-1]["loss"],
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench_goodput")
    p.add_argument("--model", default="gpt2_twin",
                   choices=("gpt2_twin", "gpt2_check", "mlp"))
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--epochs", type=int, default=4)
    p.add_argument("--out", default="", help="also write the JSON here")
    p.add_argument("--check", action="store_true",
                   help="CI smoke: tiny twin, assert >=95%% accounting, "
                        "checkpoint-induced goodput drop, loss parity")
    args = p.parse_args(argv)
    import jax  # a CPU-mesh counting tool: say what it ran on
    print(f"[bench_goodput] platform={jax.default_backend()} "
          f"devices={len(jax.devices())}: counts and parity "
          "facts, never a device metric", file=sys.stderr)
    if args.check:
        args.model, args.epochs = "gpt2_check", 2

    base = _run_leg("baseline", args.model, args.batch, args.epochs)
    heavy = _run_leg("ckpt_heavy", args.model, args.batch, args.epochs,
                     ckpt_every=1)
    report = {
        "model": args.model,
        "model_note": "CPU twin of gpt2_small (scaled; dispatch-bound "
        "steps)" if args.model.startswith("gpt2") else args.model,
        "batch": args.batch,
        "epochs": args.epochs,
        "legs": {"baseline": base, "ckpt_heavy": heavy},
        "goodput_baseline": base["goodput"],
        "goodput_ckpt_heavy": heavy["goodput"],
        "goodput_drop": round(base["goodput"] - heavy["goodput"], 4),
        "accounted_frac_min": min(base["accounted_frac"],
                                  heavy["accounted_frac"]),
    }
    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)

    if args.check:
        ok = True
        for leg in (base, heavy):
            if leg["accounted_frac"] < 0.95:
                print(f"CHECK FAIL: {leg['leg']} accounted only "
                      f"{leg['accounted_frac']:.1%} of fit wall "
                      "(need >= 95%)", file=sys.stderr)
                ok = False
        if heavy["buckets_s"].get("checkpoint", 0.0) <= 0.0:
            print("CHECK FAIL: ckpt_heavy leg recorded no checkpoint "
                  "bucket time", file=sys.stderr)
            ok = False
        if heavy["goodput"] >= base["goodput"]:
            print(f"CHECK FAIL: per-step checkpointing did not lower "
                  f"goodput ({heavy['goodput']} >= {base['goodput']})",
                  file=sys.stderr)
            ok = False
        tol = 1e-6 * max(1.0, abs(base["final_loss"]))
        if abs(heavy["final_loss"] - base["final_loss"]) > tol:
            print(f"CHECK FAIL: checkpointing perturbed the loss "
                  f"({heavy['final_loss']!r} != {base['final_loss']!r})",
                  file=sys.stderr)
            ok = False
        print("CHECK " + ("PASS" if ok else "FAIL"))
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
