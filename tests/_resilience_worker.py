"""Worker for the SIGKILL tests of tests/test_resilience.py: one training
process that can die without running a handler. A tiny Adam MLP (the
moments make a wrong resume visible), fixed seeds, 8 steps an epoch, a
durable snapshot every 5 steps; on completion it prints `HISTORY <json
losses>`. With park_after=N it touches park_file after optimizer step N
and then sleeps until it is killed, so the parent's SIGKILL lands mid-epoch
without anybody pacing steps against a clock. Like _multihost_worker.py it
pins ITSELF to the CPU platform."""

import json
import os
import sys
import time

EPOCHS = 3
BATCH = 16
N_SAMPLES = 128  # 8 steps/epoch
CKPT_EVERY = 5  # not a divisor of the 24 steps: ROADMAP D16


def data():
    import numpy as np

    rng = np.random.default_rng(0)
    x = rng.normal(size=(N_SAMPLES, 32)).astype(np.float32)
    w = rng.normal(size=(32, 4)).astype(np.float32)
    return x, (x @ w).argmax(axis=1).astype(np.int32)


def build(mesh_shape=None):
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel

    cfg = FFConfig(batch_size=BATCH, only_data_parallel=True, seed=5,
                   log_level="warning", mesh_shape=mesh_shape or {})
    m = FFModel(cfg)
    x = m.create_tensor([BATCH, 32], name="x")
    h = m.dense(x, 64, activation="relu", name="fc1")
    m.dense(h, 4, name="head")
    cm = m.compile(AdamOptimizer(alpha=0.01),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=0)
    return cm


class ParkAfter:
    """Per-batch callback (which also pins fit to one step a dispatch, so
    steps and snapshots interleave the same way in every run): after
    `step` optimizer steps, say so and wait to be killed."""

    def __init__(self, step=0, marker=""):
        self.left, self.marker = step, marker

    def on_batch_end(self, it, logs):
        self.left -= 1
        if self.left == 0:
            open(self.marker, "w").close()
            while True:
                time.sleep(3600)


def fit(cm, ckpt_dir=None, resume=None, park=None):
    x, y = data()
    hist = cm.fit(x, y, epochs=EPOCHS, verbose=False,
                  checkpoint_dir=ckpt_dir,
                  checkpoint_every_steps=CKPT_EVERY if ckpt_dir else None,
                  resume=resume, callbacks=[park or ParkAfter()])
    cm.wait_checkpoints()
    return [h["loss"] for h in hist]


if __name__ == "__main__":
    # started by the tests, whose environment (8 virtual CPU devices, no
    # compile cache) it inherits; arguments are key=value
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("FF_FAULT_PLAN", None)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    a = dict(arg.split("=", 1) for arg in sys.argv[1:])
    mesh = {k: int(v) for k, v in
            (part.split("=") for part in a.get("mesh", "").split(",") if part)}
    losses = fit(build(mesh), a["ckpt_dir"], a.get("resume"),
                 ParkAfter(int(a.get("park_after", 0)), a.get("park_file")))
    print("HISTORY " + json.dumps(losses), flush=True)
