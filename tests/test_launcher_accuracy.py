"""F5 launcher (`python -m flexflow_tpu script.py`) + accuracy-asserting
training on the (learnable) synthetic datasets — the reference's
examples/python/keras/accuracy.py pattern (weak item #10, rounds 2-3)."""

import os
import subprocess
import sys

import numpy as np


def test_launcher_runs_script_with_flags():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "flexflow_tpu", "-b", "128", "--lr", "0.5",
         "-e", "5", "examples/native/mnist_mlp.py"],
        cwd="/root/repo", env=env, capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr[-3000:]}"
    assert "FINAL loss=" in out.stdout, out.stdout
    assert "[epoch 4]" in out.stdout  # the launcher's -e 5 reached the script
    final = [l for l in out.stdout.splitlines() if l.startswith("FINAL")][-1]
    acc = float(final.split("test_accuracy=")[1])
    assert acc > 0.45, f"learnable synthetic MNIST should beat chance 10x: {final}"


def test_keras_accuracy_on_synthetic_cifar(devices):
    """The synthetic fallback datasets carry LEARNABLE labels (argmax of a
    fixed linear probe), so accuracy genuinely rises above chance — the
    finite-loss-only smoke of earlier rounds can now assert learning."""
    from flexflow_tpu.keras.datasets import cifar10
    from flexflow_tpu.keras.layers import Dense, Flatten, Input
    from flexflow_tpu.keras.models import Model
    import flexflow_tpu.keras.optimizers as opt

    (x, y), (xt, yt) = cifar10.load_data(num_samples=4096)
    x = (x.astype(np.float32) / 255.0) - 0.5
    xt = (xt.astype(np.float32) / 255.0) - 0.5

    inp = Input(shape=(3, 32, 32), dtype="float32")
    t = Flatten()(inp)
    t = Dense(128, activation="relu")(t)
    out = Dense(10)(t)
    model = Model(inp, out)
    model.compile(optimizer=opt.SGD(learning_rate=0.1),
                  loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    model.fit(x, y.reshape(-1).astype(np.int32), batch_size=64, epochs=4,
              verbose=False)
    ev = model.evaluate(xt, yt.reshape(-1).astype(np.int32))
    assert ev.get("accuracy", 0.0) > 0.3, ev  # 10-class chance is 0.1
