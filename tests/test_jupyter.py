"""Jupyter integration (flexflow_tpu/jupyter) + the quickstart notebook.

Reference analog: jupyter_notebook/ (install.py registering the Legion
kernel configured by flexflow_jupyter.json). The TPU kernel is a plain
ipykernel spec whose ENVIRONMENT carries the machine config (FF_LAUNCH_ARGS
consumed by FFConfig.parse_args); the notebook itself is executed here cell
by cell against the virtual mesh, so the shipped example is provably
runnable."""

import json
import os

import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.jupyter import kernelspec, load_config
from flexflow_tpu.jupyter.install import install

NB = os.path.join(os.path.dirname(__file__), "..",
                  "examples", "notebooks", "quickstart.ipynb")


def test_install_kernelspec_prefix(tmp_path):
    cfg = tmp_path / "kernel.json.in"
    cfg.write_text(json.dumps({
        "name": "FlexFlow TPU (virtual mesh)",
        "mesh": "data=4,model=2",
        "budget": 8,
        "virtual_devices": 8,
    }))
    kdir = install(config=str(cfg), prefix=str(tmp_path / "pfx"), mute=True)
    spec = json.loads(open(os.path.join(kdir, "kernel.json")).read())
    assert spec["display_name"] == "FlexFlow TPU (virtual mesh)"
    assert "ipykernel_launcher" in " ".join(spec["argv"])
    assert "--mesh data=4,model=2" in spec["env"]["FF_LAUNCH_ARGS"]
    assert "--budget 8" in spec["env"]["FF_LAUNCH_ARGS"]
    assert "device_count=8" in spec["env"]["XLA_FLAGS"]
    assert spec["env"]["JAX_PLATFORMS"] == "cpu"


def test_reference_config_vocabulary(tmp_path):
    """The reference's flexflow_jupyter.json field style ({"cmd", "value"})
    maps onto FF flags; Legion-only memory knobs are dropped."""
    cfg = tmp_path / "flexflow_jupyter.json"
    cfg.write_text(json.dumps({
        "name": "FlexFlow",
        "gpus": {"cmd": "-ll:gpu", "value": 4},
        "ranks_per_node": {"cmd": "--npernode", "value": 2},
        "nodes": {"cmd": "-n", "value": 2},
        "fbmem": {"cmd": "-ll:fsize", "value": 4096},
        "sysmem": {"cmd": "-ll:csize", "value": None},
    }))
    with pytest.warns(UserWarning, match="no TPU meaning"):
        name, argv, env = load_config(str(cfg))
    assert name == "FlexFlow"
    assert argv[argv.index("--nodes") + 1] == "2"
    # per-node workers = ranks_per_node x gpus-per-rank
    assert argv[argv.index("--workers-per-node") + 1] == "8"
    assert "-ll:fsize" not in argv  # no TPU meaning


def test_ff_launch_args_env(monkeypatch):
    """FFConfig.parse_args absorbs the kernel's FF_LAUNCH_ARGS only on real
    CLI invocations (argv=None); CLI flags override the environment, and an
    explicit programmatic argv is never silently altered by the env
    (a kernelspec-installed env var must not leak into
    tests/scripts that pass their own argv)."""
    import sys

    monkeypatch.setenv("FF_LAUNCH_ARGS", "--mesh data=2,model=4 -b 32")
    monkeypatch.setattr(sys, "argv", ["prog"])
    c = FFConfig.parse_args()
    assert c.mesh_shape == {"data": 2, "model": 4}
    assert c.batch_size == 32
    monkeypatch.setattr(sys, "argv", ["prog", "-b", "64"])
    c2 = FFConfig.parse_args()
    assert c2.batch_size == 64  # CLI wins
    assert c2.mesh_shape == {"data": 2, "model": 4}
    # explicit programmatic argv: the env must NOT merge in
    c3 = FFConfig.parse_args([])
    assert c3.mesh_shape == {} and c3.batch_size == 64  # pure defaults


def test_kernelspec_body():
    spec = kernelspec("X", ["--budget", "4"], {"FOO": "1"})
    assert spec["env"] == {"FF_LAUNCH_ARGS": "--budget 4", "FOO": "1"}
    assert spec["language"] == "python"


def test_quickstart_notebook_executes(devices):
    """Execute every code cell of the shipped notebook in one namespace —
    the notebook must be runnable as published (search, sharded init,
    training that actually learns, strategy export)."""
    nb = json.load(open(NB))
    ns = {}
    for cell in nb["cells"]:
        if cell["cell_type"] != "code":
            continue
        src = "".join(cell["source"])
        exec(compile(src, "<quickstart-cell>", "exec"), ns)
    assert ns["history"][-1]["loss"] < ns["history"][0]["loss"]
    assert ns["history"][-1]["accuracy"] > 0.3
    assert "up" in ns["st"]["ops"]
