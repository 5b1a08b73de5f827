"""Pipeline-parallel benchmark: stage placement + schedule vs pure data
parallelism on the 8-device gpt2 CPU twin (the MULTICHIP twin convention).

Per mode (dp baseline, then a stages x schedule sweep at fixed microbatch
count M = accum_steps), reports:

  * steps/sec (optimizer updates/sec, median post-compile epoch) and final
    loss — identical data/seeds across modes, so losses must agree to the
    float-reassociation tolerance (pipeline splits the graph and the grad
    sum, nothing else)
  * per-device LIVE-BUFFER param + optimizer-state bytes (max over one
    representative device per stage) — the owned-stage residency must show
    the ~S x reduction against the dp twin's replicated buffers
  * bubble, MEASURED vs PREDICTED: both run the same event-driven schedule
    replay (search/simulator.py simulate_pipeline); "predicted" feeds it
    the cost model's analytic per-stage times, "measured" feeds it this
    host's measured per-stage forward/backward kernel times (isolated,
    block_until_ready). Wall-clock concurrency across the 8 VIRTUAL cpu
    devices shares the host's cores, so a wall-clock bubble would mostly
    measure the host scheduler — the twin measures the schedule with real
    kernel times instead (a CPU-mesh wall clock is not a device time).

  python tools/bench_pipeline.py                 # full sweep
  python tools/bench_pipeline.py --check         # CI smoke (tiny twin):
      asserts (a) >= S/2 per-device param+opt reduction at S=2 (live
      buffers), (b) measured bubble within 25% of predicted for BOTH
      schedules, (c) 1f1b >= gpipe throughput (equal-bubble schedules; 10%
      noise floor), (d) <= 1e-5 rel final-loss parity with the sequential
      accum baseline. Exits nonzero on regression (tier-1 safe, CPU).
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


def _build(stages: int, schedule: str, accum: int, batch: int,
           layers: int, zero: str = "off"):
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
    from flexflow_tpu.losses import LossType
    from flexflow_tpu.models import GPT2Config, build_gpt2

    cfg = FFConfig(batch_size=batch, only_data_parallel=True, seed=3,
                   pipeline_stages=stages, pipeline_schedule=schedule,
                   accum_steps=accum, zero_sharding=zero,
                   log_level="warning")
    gc = GPT2Config(vocab=512, seq=16, d_model=64, heads=2, layers=layers,
                    dropout=0.0)
    m = FFModel(cfg)
    build_gpt2(m, gc, batch=batch)
    cm = m.compile(AdamOptimizer(alpha=0.001),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    return cm, gc


def _data(gc, n, batch):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, gc.vocab, size=(n, gc.seq)).astype(np.int32)
    pos = np.broadcast_to(np.arange(gc.seq, dtype=np.int32),
                          (n, gc.seq)).copy()
    y = rng.integers(0, gc.vocab, size=(n, gc.seq)).astype(np.int32)
    return [ids, pos], y


def _measured_stage_times(pm, micro_xs, micro_y, lab_sh, repeats=3):
    """Isolated per-stage forward/backward kernel times on THIS host
    (block_until_ready, best of `repeats`) — the measured inputs to the
    schedule replay. The last stage's forward slot is free by construction
    (loss+grad fuse into its backward, parallel/pipeline.py)."""
    import jax

    S = pm.num_stages
    rng = jax.random.PRNGKey(0)
    fwd_t, bwd_t = [0.0] * S, [0.0] * S
    x = [pm._put(a[0], sh) for a, sh in zip(micro_xs, pm._in_sh0)]
    for s in range(S):
        if s < S - 1:
            def run_f():
                y, _ = pm._f_fns[s](pm.stage_params[s], pm.stage_state[s],
                                    x, rng)
                return y
            y = run_f()  # compile
            jax.block_until_ready(y)
            fwd_t[s] = min(_timed(run_f) for _ in range(repeats))
            gy = y  # cotangent values don't matter for timing

            def run_b():
                gp, _gx, _rv = pm._b_fns[s](pm.stage_params[s],
                                            pm.stage_state[s], x, gy, rng)
                return gp

            jax.block_until_ready(run_b())
            bwd_t[s] = min(_timed(run_b) for _ in range(repeats))
            x = [pm._put(y, pm._bound_in_sh[s])]
        else:
            lab = pm._put(micro_y[0], lab_sh)

            def run_last():
                loss, gp, gx, _st, _mv = pm._b_fns[s](
                    pm.stage_params[s], pm.stage_state[s], x, lab, rng)
                return loss
            jax.block_until_ready(run_last())
            bwd_t[s] = min(_timed(run_last) for _ in range(repeats))
            fwd_t[s] = 0.0
    return fwd_t, bwd_t


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return time.perf_counter() - t0


def _run_mode(stages, schedule, accum, batch, layers, epochs, repeats,
              n_samples):
    best = None
    for _ in range(max(1, repeats)):
        r = _run_mode_once(stages, schedule, accum, batch, layers, epochs,
                           n_samples)
        if best is None or r["steps_per_sec"] > best["steps_per_sec"]:
            keep = best["final_loss"] if best else r["final_loss"]
            best = r
            assert best["final_loss"] == keep  # same seeds: loss invariant
    return best


def _run_mode_once(stages, schedule, accum, batch, layers, epochs,
                   n_samples):
    cm, gc = _build(stages, schedule, accum, batch, layers)
    x, y = _data(gc, n_samples, batch)
    t0 = time.perf_counter()
    hist = cm.fit(x, y, epochs=epochs, verbose=False)
    wall = time.perf_counter() - t0
    nb = n_samples // (batch * accum)
    timed = hist[1:] if len(hist) > 1 else hist  # epoch 0 pays the jit
    rates = sorted(nb / e["epoch_time_s"] for e in timed if e["epoch_time_s"])
    sps = rates[len(rates) // 2] if rates else 0.0
    out = {
        "mode": f"pipe{stages}_{schedule}" if stages > 1 else "dp",
        "stages": stages,
        "schedule": schedule if stages > 1 else "none",
        "microbatches": accum,
        "steps_per_sec": round(sps, 3),
        "samples_per_sec": round(batch * accum * sps, 1),
        "final_loss": hist[-1]["loss"],
        "updates_per_epoch": nb,
        "wallclock_s": round(wall, 3),
    }
    mem = cm.memory_stats()
    if stages > 1:
        out["per_stage_param_bytes"] = mem["per_stage_param_bytes"]
        out["per_stage_opt_bytes"] = mem["per_stage_opt_bytes"]
        out["param_plus_opt_bytes_per_device"] = (
            mem["actual_param_bytes_per_device"]
            + mem["actual_opt_state_bytes_per_device"])
        pred = cm.predicted_schedule(accum)
        out["predicted_bubble"] = round(pred["bubble"], 4)
        out["predicted_stage_costs_s"] = pred["stage_costs_s"]
        # measured bubble: the SAME event replay, fed this host's measured
        # per-stage kernel times
        from flexflow_tpu.search.simulator import simulate_pipeline

        from flexflow_tpu.search.cost_model import pipeline_bubble_fraction

        lab_sh = cm._label_sharding((batch,) + np.asarray(y).shape[1:])
        # one (1, batch, ...) microbatch stack per input for the timer
        gxs = [a[:batch][None] for a in x]
        fwd_t, bwd_t = _measured_stage_times(cm, gxs, y[:batch][None],
                                             lab_sh)
        rep = simulate_pipeline(fwd_t, bwd_t, schedule, accum)
        out["measured_stage_fwd_s"] = [round(t, 6) for t in fwd_t]
        out["measured_stage_bwd_s"] = [round(t, 6) for t in bwd_t]
        out["measured_bubble"] = round(rep["bubble"], 4)
        out["closed_form_bubble"] = round(
            pipeline_bubble_fraction(schedule, stages, accum), 4)
    else:
        out["param_plus_opt_bytes_per_device"] = (
            mem["actual_param_bytes_per_device"]
            + mem["actual_opt_state_bytes_per_device"])
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench_pipeline")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--layers", type=int, default=4,
                   help="gpt2 twin depth (block count)")
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--microbatches", type=int, default=8,
                   help="M = accum_steps: microbatches per update")
    p.add_argument("--stages", type=str, default="2,4",
                   help="comma list of stage counts to sweep")
    p.add_argument("--repeats", type=int, default=3,
                   help="best-of-N per mode (load-spike robustness)")
    p.add_argument("--out", default="", help="also write the JSON here")
    p.add_argument("--check", action="store_true",
                   help="CI smoke: tiny twin, assert memory reduction, "
                        "bubble accuracy, 1f1b >= gpipe, loss parity")
    args = p.parse_args(argv)
    import jax  # a CPU-mesh counting tool: say what it ran on
    print(f"[bench_pipeline] platform={jax.default_backend()} "
          f"devices={len(jax.devices())}: counts and parity "
          "facts, never a device metric", file=sys.stderr)
    stages_list = [int(s) for s in args.stages.split(",") if s]
    if args.check:
        # repeats=2: the schedule-throughput comparison is wall clock on a
        # possibly loaded CI host; best-of-2 bounds the one-off stalls
        args.layers, args.epochs, args.repeats = 2, 2, 2
        args.microbatches = 4
        stages_list = [2]
    n = args.microbatches * args.batch * 8

    dp = _run_mode(1, "none", args.microbatches, args.batch, args.layers,
                   args.epochs, args.repeats, n)
    modes = {"dp": dp}
    for s in stages_list:
        for sched in ("gpipe", "1f1b"):
            modes[f"pipe{s}_{sched}"] = _run_mode(
                s, sched, args.microbatches, args.batch, args.layers,
                args.epochs, args.repeats, n)

    def ratio(a, b):
        return round(a / max(b, 1e-12), 3)

    s0 = stages_list[0]
    g, f = modes[f"pipe{s0}_gpipe"], modes[f"pipe{s0}_1f1b"]
    report = {
        "model": f"gpt2 CPU twin (8 virtual devices, {args.layers} blocks)",
        "batch": args.batch,
        "microbatches": args.microbatches,
        "epochs": args.epochs,
        "modes": modes,
        "mem_reduction_vs_dp": {
            k: ratio(dp["param_plus_opt_bytes_per_device"],
                     m["param_plus_opt_bytes_per_device"])
            for k, m in modes.items() if m["stages"] > 1},
        "bubble_measured_over_predicted": {
            k: ratio(m["measured_bubble"], m["predicted_bubble"])
            for k, m in modes.items() if m["stages"] > 1},
        "one_f1b_vs_gpipe_speed": ratio(f["steps_per_sec"],
                                        g["steps_per_sec"]),
        "loss_rel_delta_vs_dp": {
            k: abs(m["final_loss"] - dp["final_loss"])
            / max(1.0, abs(dp["final_loss"]))
            for k, m in modes.items() if m["stages"] > 1},
    }
    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=1)

    if args.check:
        ok = True
        for k, red in report["mem_reduction_vs_dp"].items():
            S = modes[k]["stages"]
            if red < S / 2:
                print(f"CHECK FAIL: {k} per-device param+opt reduction "
                      f"{red} < {S / 2}", file=sys.stderr)
                ok = False
        for k, r in report["bubble_measured_over_predicted"].items():
            if not (0.75 <= r <= 1.25):
                print(f"CHECK FAIL: {k} measured/predicted bubble {r} "
                      f"outside [0.75, 1.25] "
                      f"(measured {modes[k]['measured_bubble']}, "
                      f"predicted {modes[k]['predicted_bubble']})",
                      file=sys.stderr)
                ok = False
        # the two schedules do IDENTICAL work (equal bubble; 1f1b's win is
        # stash memory) — the check guards against 1f1b regressing, with a
        # noise floor for shared-core CI hosts; the committed
        # BENCH_pipeline.json runs the full best-of-N protocol
        if report["one_f1b_vs_gpipe_speed"] < 0.85:
            print(f"CHECK FAIL: 1f1b/gpipe speed "
                  f"{report['one_f1b_vs_gpipe_speed']} < 0.85",
                  file=sys.stderr)
            ok = False
        for k, d in report["loss_rel_delta_vs_dp"].items():
            if d > 1e-5:
                print(f"CHECK FAIL: {k} loss delta {d} > 1e-5 rel",
                      file=sys.stderr)
                ok = False
        print("CHECK " + ("PASS" if ok else "FAIL"))
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
