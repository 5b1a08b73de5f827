"""Resilience smoke + micro-bench: kill-and-resume trajectory parity.

Drives the ISSUE-6 acceptance scenario end to end with REAL processes and
REAL signals (no mocks): a training run writing durable atomic-commit
checkpoints (runtime/resilience.py) is SIGKILLed mid-epoch, relaunched
with resume="auto", and must finish with the loss trajectory of an
uninterrupted run — on the same mesh AND on a resized mesh (elastic
resume re-shards via the PR 3/4 cross-mesh restore). A fourth leg runs
with a deterministic fault plan (runtime/faults.py) injecting transient
failures at the dataloader-transfer, dispatch and checkpoint-write sites:
retry/backoff must recover every one of them with the trajectory
bit-unperturbed (injected faults fire BEFORE any state mutation).

  python tools/bench_resilience.py            # full run: 2x the epochs,
      prints JSON including the measured durable-checkpoint overhead
      (checkpoint_parity leg seconds vs the no-checkpoint reference)
  python tools/bench_resilience.py --check    # CI smoke (tier-1 safe,
      wired into tests/test_resilience.py): the same legs at the short
      epoch count, no overhead stats; exits nonzero when any leg's
      relaunched trajectory diverges from the uninterrupted reference,
      when the killed run failed to leave a committed snapshot behind, or
      when an injected fault escaped recovery.

The worker (--worker) is this same file: a tiny Adam MLP (moments make
resume correctness observable), fixed seeds, ~8 steps/epoch; it prints
`HISTORY <json losses>` on completion. --step-sleep paces the steps so
the parent's SIGKILL reliably lands mid-epoch.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

EPOCHS = 3
BATCH = 16
N_SAMPLES = 128  # 8 steps/epoch
CKPT_EVERY = 3


def _data():
    import numpy as np

    rng = np.random.default_rng(0)
    x = rng.normal(size=(N_SAMPLES, 32)).astype(np.float32)
    w = rng.normal(size=(32, 4)).astype(np.float32)
    y = (x @ w).argmax(axis=1).astype(np.int32)
    return x, y


def _build(mesh: str):
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel

    mesh_shape = {}
    for part in (mesh or "").split(","):
        if part.strip():
            k, v = part.split("=")
            mesh_shape[k.strip()] = int(v)
    cfg = FFConfig(batch_size=BATCH, only_data_parallel=True, seed=5,
                   log_level="warning", mesh_shape=mesh_shape)
    m = FFModel(cfg)
    x = m.create_tensor([BATCH, 32], name="x")
    h = m.dense(x, 64, activation="relu", name="fc1")
    m.dense(h, 4, name="head")
    return m.compile(AdamOptimizer(alpha=0.01),
                     loss_type="sparse_categorical_crossentropy", metrics=[])


class _Pacer:
    """Per-step sleep so the parent's SIGKILL lands mid-epoch (a per-batch
    callback also pins the fit loop to per-step dispatch — deterministic
    step/checkpoint interleaving across every leg)."""

    def __init__(self, secs: float):
        self.secs = secs

    def on_batch_end(self, it, logs):
        if self.secs:
            time.sleep(self.secs)


def worker(args) -> int:
    from flexflow_tpu.runtime.resilience import Preempted

    cm = _build(args.mesh)
    cm.init(seed=0)
    x, y = _data()
    try:
        hist = cm.fit(x, y, epochs=args.epochs or EPOCHS, verbose=False,
                      checkpoint_dir=args.ckpt_dir or None,
                      checkpoint_every_steps=CKPT_EVERY if args.ckpt_dir
                      else None,
                      resume=args.resume or None,
                      callbacks=[_Pacer(args.step_sleep)])
    except Preempted as e:
        print(f"PREEMPTED {e.checkpoint_path}", flush=True)
        raise
    cm.wait_checkpoints()
    print("HISTORY " + json.dumps([h["loss"] for h in hist]), flush=True)
    return 0


# --------------------------------------------------------------- the parent
def _spawn(extra, env_extra=None):
    env = dict(os.environ)
    env.pop("FF_FAULT_PLAN", None)
    env.update(env_extra or {})
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker"] + extra,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))


def _finish(proc, timeout=240):
    out, _ = proc.communicate(timeout=timeout)
    return proc.returncode, out


def _history(out: str):
    for line in reversed(out.splitlines()):
        if line.startswith("HISTORY "):
            return json.loads(line[len("HISTORY "):])
    return None


def _wait_for_commit(root: str, proc, timeout=180.0) -> bool:
    """Poll until the running worker commits its first durable snapshot
    (True), or it exits / the deadline passes (False)."""
    from flexflow_tpu.runtime.resilience import committed_snapshots

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if committed_snapshots(root):
            return True
        if proc.poll() is not None:
            return False
        time.sleep(0.02)
    return False


def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench_resilience")
    p.add_argument("--check", action="store_true")
    p.add_argument("--worker", action="store_true")
    p.add_argument("--ckpt-dir", type=str, default="")
    p.add_argument("--resume", type=str, default="")
    p.add_argument("--mesh", type=str, default="")
    p.add_argument("--step-sleep", type=float, default=0.0)
    p.add_argument("--epochs", type=int, default=0)
    p.add_argument("--out", type=str, default="")
    args = p.parse_args(argv)
    import jax  # a CPU-mesh counting tool: say what it ran on
    print(f"[bench_resilience] platform={jax.default_backend()} "
          f"devices={len(jax.devices())}: counts and parity "
          "facts, never a device metric", file=sys.stderr)
    if args.worker:
        return worker(args)

    import numpy as np

    # --check = the fast CI scope; the full bench doubles the epochs and
    # adds the measured durable-checkpoint overhead to the report
    n_epochs = EPOCHS if args.check else 2 * EPOCHS
    base = ["--epochs", str(n_epochs)]
    work = tempfile.mkdtemp(prefix="ff_resilience_")
    report = {"legs": {}, "mode": "check" if args.check else "full",
              "epochs": n_epochs}
    ok = True

    def leg(name, passed, **info):
        nonlocal ok
        ok = ok and passed
        report["legs"][name] = dict(info, passed=bool(passed))
        print(f"[{'ok' if passed else 'FAIL'}] {name}: {info}", flush=True)

    def close(losses, ref, tol=1e-5):
        return (losses is not None and len(losses) == len(ref)
                and bool(np.allclose(losses, ref, rtol=tol, atol=1e-7)))

    try:
        # --- reference: uninterrupted run, no checkpointing ---
        t0 = time.time()
        rc, out = _finish(_spawn(base))
        ref = _history(out)
        leg("reference", rc == 0 and ref is not None,
            seconds=round(time.time() - t0, 2), losses=ref)
        if ref is None:
            print(out[-4000:])
            return 1

        # --- checkpointing overhead: same run writing durable snapshots ---
        root = os.path.join(work, "ck")
        t0 = time.time()
        rc, out = _finish(_spawn(base + ["--ckpt-dir", root]))
        h = _history(out)
        leg("checkpoint_parity", rc == 0 and close(h, ref, 1e-7),
            seconds=round(time.time() - t0, 2),
            note="durable snapshots must not perturb the trajectory")

        # --- kill-and-resume: SIGKILL mid-epoch, relaunch resume=auto ---
        root = os.path.join(work, "kill")
        proc = _spawn(base + ["--ckpt-dir", root, "--step-sleep", "0.08"])
        committed = _wait_for_commit(root, proc)
        time.sleep(0.3)  # let it run past the snapshot before the kill
        killed_mid_run = proc.poll() is None
        proc.kill()
        rc, out = _finish(proc)
        leg("sigkill_landed", committed and killed_mid_run
            and _history(out) is None, returncode=rc,
            note="worker must die mid-run with >=1 committed snapshot")
        # relaunch on the SAME mesh
        elastic_root = os.path.join(work, "kill_elastic")
        shutil.copytree(root, elastic_root)  # pristine copy for the 3rd leg
        rc, out = _finish(_spawn(base + ["--ckpt-dir", root, "--resume", "auto"]))
        h = _history(out)
        leg("kill_resume_same_mesh", rc == 0 and close(h, ref),
            losses=h)
        # relaunch on a RESIZED mesh (elastic resume re-shards)
        rc, out = _finish(_spawn(base + ["--ckpt-dir", elastic_root,
                                  "--resume", "auto",
                                  "--mesh", "data=4,model=2"]))
        h = _history(out)
        leg("kill_resume_resized_mesh", rc == 0 and close(h, ref),
            losses=h)

        # --- injected transient faults: recovered, trajectory untouched ---
        root = os.path.join(work, "faults")
        plan = "dataloader/transfer@2*2,fit/dispatch@3,checkpoint/write@1"
        rc, out = _finish(_spawn(base + ["--ckpt-dir", root],
                                 env_extra={"FF_FAULT_PLAN": plan}))
        h = _history(out)
        leg("injected_fault_recovery", rc == 0 and close(h, ref, 1e-7),
            plan=plan)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    if not args.check:
        # full-bench extra: durable checkpointing's wall-clock overhead
        legs = report["legs"]
        r, c = (legs.get("reference", {}).get("seconds"),
                legs.get("checkpoint_parity", {}).get("seconds"))
        if r and c:
            report["checkpoint_overhead_pct"] = round(100.0 * (c - r) / r, 1)
    report["passed"] = ok
    print(json.dumps(report, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
