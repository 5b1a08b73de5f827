"""Jupyter kernel integration — run the framework interactively.

Reference analog: `jupyter_notebook/` (install.py + flexflow_jupyter.json +
flexflow_kernel_nocr.py): the reference must launch a CUSTOM kernel because
its runtime (Legion) has to own the process and be configured with machine
flags (-ll:gpu, -ll:fsize, ...) BEFORE user code runs. The TPU runtime needs
no process takeover — JAX initializes lazily — so the analog is a standard
ipykernel kernelspec whose launch ENVIRONMENT carries the machine
configuration: FF launch flags (mesh shape, search budget, ...) in
`FF_LAUNCH_ARGS` (consumed by FFConfig.parse_args() with argv=None — real
CLI/kernel invocations only, never explicit programmatic argv — and by the
launcher), the
platform pin in `JAX_PLATFORMS`, and XLA device-count flags for
virtual-mesh notebooks.

`python -m flexflow_tpu.jupyter.install --config cfg.json` installs the
kernelspec; `load_config` maps the reference's flexflow_jupyter.json field
vocabulary onto FF flags so existing configs carry over.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Tuple

# reference flexflow_jupyter.json fields -> FF launcher flags. Legion-only
# memory knobs (sysmem/fbmem/zcmem/regmem, utility/openmp threads) have no
# TPU meaning and are dropped with a warning, like the launcher does for
# -ll: flags it subsumes.
_FIELD_TO_FLAG = {
    "nodes": "--nodes",
    "batch_size": "-b",
    "epochs": "-e",
    "budget": "--budget",
    "mesh": "--mesh",
}
_DROPPED_FIELDS = ("cpus", "openmp", "ompthreads", "utility", "sysmem",
                   "fbmem", "zcmem", "regmem", "not_control_replicable",
                   "launcher", "other_options")


def _value(cfg: dict, field: str):
    v = cfg.get(field)
    if isinstance(v, dict):  # reference style: {"cmd": ..., "value": ...}
        v = v.get("value")
    return v


def load_config(path: str) -> Tuple[str, List[str], Dict[str, str]]:
    """Parse a kernel config (reference flexflow_jupyter.json vocabulary or
    the native one) -> (display_name, ff_argv, extra_env)."""
    with open(path) as f:
        cfg = json.load(f)
    name = cfg.get("name", "FlexFlow TPU")
    argv: List[str] = []
    for field, flag in _FIELD_TO_FLAG.items():
        v = _value(cfg, field)
        if v is not None:
            argv += [flag, str(v)]
    # per-node worker count: ranks_per_node x gpus-per-rank (the reference
    # config typically sets both; the TPU launcher has one workers knob)
    ranks, gpus = _value(cfg, "ranks_per_node"), _value(cfg, "gpus")
    if ranks is not None or gpus is not None:
        argv += ["--workers-per-node",
                 str(int(ranks or 1) * int(gpus or 1))]
    dropped = [f for f in _DROPPED_FIELDS if _value(cfg, f) is not None]
    if dropped:
        import warnings

        warnings.warn(f"kernel config fields with no TPU meaning dropped: "
                      f"{dropped} (Legion machine knobs; the XLA runtime "
                      f"manages memory itself)")
    env = dict(cfg.get("env", {}))
    platform = _value(cfg, "platform")
    if platform:
        env["JAX_PLATFORMS"] = str(platform)
    vdev = _value(cfg, "virtual_devices")
    if vdev:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{int(vdev)}").strip()
        env.setdefault("JAX_PLATFORMS", "cpu")
    return name, argv, env


def kernelspec(display_name: str, ff_argv: List[str],
               extra_env: Optional[Dict[str, str]] = None) -> dict:
    """The kernel.json body: plain ipykernel launch with the FF machine
    configuration riding the environment (the no-process-takeover analog of
    the reference's custom kernel_json argv)."""
    import shlex
    import sys

    # shlex round-trip: FFConfig.parse_args consumes FF_LAUNCH_ARGS with
    # shlex.split, so values containing spaces must be quoted here
    spec = {
        "argv": [sys.executable, "-m", "ipykernel_launcher",
                 "-f", "{connection_file}"],
        "display_name": display_name,
        "language": "python",
        "env": {"FF_LAUNCH_ARGS": shlex.join(ff_argv), **(extra_env or {})},
    }
    return spec
