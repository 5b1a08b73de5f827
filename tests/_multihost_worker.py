"""Worker for the 2-process multi-host test (mpi_wrapper analog) — run by
tests/test_multihost.py, one subprocess per "host", each with 4 virtual CPU
devices; jax.distributed stitches them into one 8-device world. CPU
cross-process collectives ride gloo (jax's default CPU collectives). The
worker pins ITSELF to the CPU platform: a child must never reach for a chip
its parent may hold."""

import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

port, nproc, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import jax


_PHASE = "start"


def phase(name):
    # main-thread progress marker: the parent's watchdog treats a rank
    # whose heartbeat PHASE stops advancing as hung — an unconditional
    # beat would keep ticking right through a coordinator deadlock or a
    # wedged collective (the heartbeat thread doesn't need the main
    # thread to run)
    global _PHASE
    _PHASE = name
    print(f"PHASE {name}", flush=True)


def _heartbeat():
    n = 0
    while True:
        print(f"HB pid={pid} ph={_PHASE} n={n}", flush=True)
        n += 1
        time.sleep(2.0)


threading.Thread(target=_heartbeat, daemon=True).start()

from flexflow_tpu.runtime.distributed import init_distributed, is_multiprocess

# retry-with-backoff lives inside init_distributed (the distributed/init
# resilience site): a worker that races the coordinator's socket retries
phase("init_distributed")
init_distributed(coordinator_address=f"127.0.0.1:{port}",
                 num_processes=nproc, process_id=pid)
phase("init_done")

assert jax.process_count() == nproc, jax.process_count()
assert jax.device_count() == 4 * nproc, jax.device_count()
assert len(jax.local_devices()) == 4
assert is_multiprocess()

import numpy as np

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer

cfg = FFConfig(batch_size=32, epochs=2, mesh_shape={"data": 4 * nproc},
               only_data_parallel=True, seed=7)
m = FFModel(cfg)
x = m.create_tensor([32, 16], name="x")
h = m.dense(x, 64, activation="relu", name="fc1")
m.dense(h, 4, name="head")
phase("compile")
cm = m.compile(SGDOptimizer(lr=0.05),
               loss_type="sparse_categorical_crossentropy", metrics=[])
cm.init(seed=0)
phase("fit")

rng = np.random.default_rng(0)  # identical dataset on every process
xv = rng.normal(size=(128, 16)).astype(np.float32)
w = rng.normal(size=(16, 4)).astype(np.float32)
yv = np.argmax(xv @ w, axis=1).astype(np.int32)
hist = cm.fit(xv, yv, verbose=False)
phase("evaluate")
losses = [h["loss"] for h in hist]
assert all(np.isfinite(l) for l in losses), losses
assert losses[-1] < losses[0], losses
# every host->device data path must be multi-process-safe (round-4 review):
ev = cm.evaluate(xv, yv)
assert np.isfinite(ev["loss"]), ev
out = cm.forward(xv[:32])
assert out.shape == (32, 4)  # global shape; values span both processes
local = np.concatenate([np.asarray(s.data) for s in out.addressable_shards])
assert local.shape == (16, 4) and np.isfinite(local).all()
# distributed checkpoint: orbax coordinates the per-process shard writes;
# both ranks must call save/restore collectively
import tempfile

phase("checkpoint")
ckdir = sys.argv[4] if len(sys.argv) > 4 else tempfile.gettempdir() + "/mh_ck"
cm.save_checkpoint(ckdir)
before = float(np.abs(np.asarray(jax.device_get(
    cm.params["fc1"]["kernel"]))).sum())
cm.init(seed=99)  # clobber
cm.load_checkpoint(ckdir)
after = float(np.abs(np.asarray(jax.device_get(
    cm.params["fc1"]["kernel"]))).sum())
assert abs(before - after) < 1e-5, (before, after)
cm.set_weight("head", "kernel", np.zeros((64, 4), np.float32))
assert float(np.abs(cm.get_weight("head", "kernel")).sum()) == 0.0
# the global weight state must be identical across processes: fetch a
# replicated weight and print its hash for the parent to compare
wk = np.asarray(jax.device_get(cm.params["fc1"]["kernel"]))
print(f"RESULT pid={pid} loss={losses[-1]:.6f} wsum={float(np.abs(wk).sum()):.6f}",
      flush=True)
