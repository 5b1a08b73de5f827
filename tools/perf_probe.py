"""Flagship step-time decomposition — where does the non-MFU time go?

Times GPT-2 medium (bench.py's flagship config) under controlled variants
and prints the deltas:

  adam_step      the benchmarked full training step (baseline)
  sgd_step       optimizer delta: Adam's moment traffic vs plain SGD
  identity_loss  CE delta: softmax-CE over the 50k vocab vs mean(logits)
  fwd_only       forward pass alone (bwd+update = step - fwd)

All timings use the bench protocol: chained steps, block_until_ready on the
last step's outputs, median of windows.

    python tools/perf_probe.py [--iters 20] [--windows 3]
"""

from __future__ import annotations

import argparse
import sys
import time


def probe(iters: int = 20, windows: int = 3):
    import jax
    import numpy as np

    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.models import GPT2Config, build_gpt2

    cfg = GPT2Config.medium()
    cfg.dropout = 0.0
    batch = 8
    rng = np.random.default_rng(0)
    ids = jax.device_put(rng.integers(0, cfg.vocab, size=(batch, cfg.seq))
                         .astype(np.int32))
    pos = jax.device_put(np.tile(np.arange(cfg.seq, dtype=np.int32),
                                 (batch, 1)))
    labels = jax.device_put(rng.integers(0, cfg.vocab, size=(batch, cfg.seq))
                            .astype(np.int32))
    key = jax.random.PRNGKey(0)

    def build(optimizer, loss_type):
        m = FFModel(FFConfig(batch_size=batch, compute_dtype="bfloat16",
                             only_data_parallel=True))
        build_gpt2(m, cfg, batch=batch)
        cm = m.compile(optimizer, loss_type=loss_type, metrics=[])
        cm.init(seed=0)
        return cm

    def time_steps(cm):
        # train_step DONATES params/opt_state — thread the returned trees
        # and write them back, or any later use of cm.params hits deleted
        # buffers (compile.py donate_state)
        p, o, s = cm.params, cm.opt_state, cm.state
        p, o, s, loss, _ = cm.train_step(p, o, s, [ids, pos], labels, key)
        jax.block_until_ready((loss, p, o))  # compile + warm
        meds = []
        for w in range(windows):
            t0 = time.perf_counter()
            for i in range(iters):
                p, o, s, loss, _ = cm.train_step(
                    p, o, s, [ids, pos], labels, jax.random.fold_in(key, i))
            jax.block_until_ready((loss, p, o))
            meds.append((time.perf_counter() - t0) / iters)
        cm.params, cm.opt_state, cm.state = p, o, s
        return float(np.median(meds)) * 1e3

    def time_fwd(cm):
        # the jitted inference step with pre-placed device arrays (the
        # public forward() does a host->device put per call)
        arrs = [ids, pos]
        jax.block_until_ready(cm.infer_step(cm.params, cm.state, arrs))
        meds = []
        for w in range(windows):
            t0 = time.perf_counter()
            for _ in range(iters):
                y = cm.infer_step(cm.params, cm.state, arrs)
            jax.block_until_ready(y)
            meds.append((time.perf_counter() - t0) / iters)
        return float(np.median(meds)) * 1e3

    out = {}
    cm = build(AdamOptimizer(alpha=1e-4), "sparse_categorical_crossentropy")
    out["fwd_only_ms"] = time_fwd(cm)  # before training donates the params
    out["adam_step_ms"] = time_steps(cm)
    del cm
    cm = build(SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy")
    out["sgd_step_ms"] = time_steps(cm)
    del cm
    cm = build(AdamOptimizer(alpha=1e-4), "identity")
    out["identity_loss_step_ms"] = time_steps(cm)
    del cm

    out["optimizer_delta_ms"] = out["adam_step_ms"] - out["sgd_step_ms"]
    out["ce_delta_ms"] = out["adam_step_ms"] - out["identity_loss_step_ms"]
    out["bwd_update_ms"] = out["adam_step_ms"] - out["fwd_only_ms"]
    _emit_telemetry(out, iters=iters, windows=windows)
    return out


def _emit_telemetry(out, **meta):
    """Land the probe's measurements in the unified span stream when a sink
    is active (--telemetry-dir here, or a prior telemetry.configure in the
    process): one `probe/<variant>` span per measurement, dur = the
    measured per-step time, so probe runs join the same corpus
    trace_report reads instead of living on stdout only
    (ISSUE 7 satellite)."""
    from flexflow_tpu import telemetry as tel

    if not tel.enabled():
        return
    now = tel.now_us()
    for k, v in out.items():
        # deltas are derived, not measurements — record the timed variants
        if not k.endswith("_ms") or k.endswith("_delta_ms") \
                or k == "bwd_update_ms":
            continue
        tel.record(f"probe/{k[:-3]}", now - v * 1e3, now, cat="probe",
                   step_ms=float(v), **meta)
    tel.event("probe/summary", cat="probe",
              **{k: float(v) for k, v in out.items()}, **meta)
    tel.flush()


if __name__ == "__main__":
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--telemetry-dir", default="",
                    help="also emit probe/<variant> spans into this "
                         "telemetry dir (unified span stream)")
    args = ap.parse_args()
    if args.telemetry_dir:
        from flexflow_tpu import telemetry

        telemetry.configure(args.telemetry_dir)
    for k, v in probe(args.iters, args.windows).items():
        print(f"{k:26s} {v:9.2f}")
