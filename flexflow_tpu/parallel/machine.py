"""Machine description: logical mesh + hardware coefficients.

Reference analog: MachineView/MachineResource (include/flexflow/machine_view.h)
and the simulator's MachineModel hierarchy (include/flexflow/simulator.h:
212-605, src/runtime/machine_model.cc) describing NVLink/PCIe/NIC topology.
The TPU equivalent is much simpler by design: placement is a named
`jax.sharding.Mesh`, and the cost model needs only per-chip compute/HBM rates
plus per-mesh-axis interconnect bandwidth (ICI for intra-slice axes, DCN for
multi-slice axes). Numbers are per-chip, bidirectional-link aggregate.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np


# Built-in chip models (public spec-sheet numbers).
CHIP_PRESETS = {
    # name: (bf16 FLOP/s, HBM bytes/s, HBM bytes, ICI bytes/s per axis)
    "v5e": (197e12, 819e9, 16e9, 2 * 45e9),
    "v5p": (459e12, 2765e9, 95e9, 2 * 100e9),
    "v4": (275e12, 1228e9, 32e9, 2 * 50e9),
    "cpu-sim": (1e11, 50e9, 8e9, 1e9),
}

# `jax.devices()[0].device_kind` as the runtime reports it -> CHIP_PRESETS
# key. Only strings that were READ from a device are listed (v5e: on the
# chip, PR 22); a chip that is not here is an error, never priced as
# another chip. Add a row when the string has been read on that chip.
DEVICE_KINDS = {
    "TPU v5 lite": "v5e",
}


def chip_for_device_kind(kind: str) -> str:
    try:
        return DEVICE_KINDS[kind]
    except KeyError:
        raise ValueError(
            f"device_kind {kind!r} is not in machine.DEVICE_KINDS "
            f"({sorted(DEVICE_KINDS)}): add its peaks to CHIP_PRESETS "
            "instead of pricing it as another chip") from None


def _preset(chip: str):
    try:
        return CHIP_PRESETS[chip]
    except KeyError:
        raise ValueError(f"unknown chip {chip!r}; known: "
                         f"{sorted(CHIP_PRESETS)}") from None


@dataclasses.dataclass
class MachineSpec:
    """The machine the search optimizes for (may be larger than the real one,
    reference: --search-num-nodes, config.h:154-155)."""

    mesh_axes: Dict[str, int] = dataclasses.field(default_factory=dict)  # ordered
    chip: str = "v5e"
    flops: float = 0.0  # bf16 peak per chip
    hbm_bw: float = 0.0
    hbm_bytes: float = 0.0
    ici_bw: Dict[str, float] = dataclasses.field(default_factory=dict)  # per axis
    dcn_axes: Tuple[str, ...] = ()  # axes that cross slices (DCN bandwidth)
    dcn_bw: float = 25e9
    mxu_flop_overhead: float = 1.4  # achievable-fraction fudge: peak/this
    mxu_min_dim: int = 128  # lane width; shards thinner than this waste the MXU
    # per-axis link topology (reference NetworkedMachineModel's topology
    # generators, src/runtime/machine_model.cc / network.cc): "ring" = torus
    # wraparound (full TPU slices; ring collectives use both directions, the
    # preset bw), "line" = no wraparound (partial/twisted slices; ring
    # algorithms lose the wrap link, halving effective bandwidth),
    # "switch" = full-bisection fabric (DCN default).
    axis_type: Dict[str, str] = dataclasses.field(default_factory=dict)
    # compute/comm overlap (reference: the event-driven simulator's
    # concurrent compute+transfer replay, simulator.h:785-827 — here a
    # closed-form factor): fraction of a segment's pure-compute time that
    # XLA's async collectives / latency-hiding scheduler can hide collective
    # time behind. 0 = fully additive costing. Collectives are async
    # ICI/HBM DMAs, which genuinely overlap compute; the single-chip
    # compute proxy CANNOT observe this (a TPU core runs compute HLOs
    # serially — tools/calibrate.py's negative control). The 0.7 default rests
    # on the async-DMA architecture, stays below 1.0 because collectives
    # sit on dataflow edges (their producer must finish first), and is
    # cross-checked by the whole-model scheduling calibration
    # (tools/calibrate.py, simulated/step). search/simulator.py replaces
    # this factor entirely with event-driven replay (simulator_mode=
    # "taskgraph").
    overlap_frac: float = 0.7
    # host link bandwidth (bytes/s per chip): the PCIe/DCN-tier path the
    # tiered KV cache's spill/prefetch traffic rides (jax.device_put /
    # device_get to pinned host buffers). Far below hbm_bw by construction —
    # this gap is what the decode roofline charges for unhidden prefetch
    # traffic when a host tier is on.
    host_bw: float = 0.0

    def __post_init__(self):
        preset = _preset(self.chip)
        if not self.flops:
            self.flops = preset[0]
        if not self.hbm_bw:
            self.hbm_bw = preset[1]
        if not self.hbm_bytes:
            self.hbm_bytes = preset[2]
        if not self.host_bw:
            self.host_bw = 16e9  # PCIe-class default
        for ax in self.mesh_axes:
            if ax not in self.ici_bw:
                self.ici_bw[ax] = self.dcn_bw if ax in self.dcn_axes else preset[3]

    @property
    def num_devices(self) -> int:
        return math.prod(self.mesh_axes.values()) if self.mesh_axes else 1

    def axis_bw(self, axis: str) -> float:
        return self.ici_bw.get(axis, _preset(self.chip)[3])

    def axis_topology(self, axis: str) -> str:
        if axis in self.axis_type:
            return self.axis_type[axis]
        return "switch" if axis in self.dcn_axes else "ring"

    def axis_bw_eff(self, axis: str) -> float:
        """Effective bandwidth for ring-style collectives on this axis: a
        line (no torus wraparound) loses the wrap link, halving throughput;
        rings and switched fabrics use the full figure."""
        bw = self.axis_bw(axis)
        return bw * 0.5 if self.axis_topology(axis) == "line" else bw

    # -------------------------------------------------------------- io
    def to_json(self) -> dict:
        return {
            "mesh_axes": self.mesh_axes,
            "chip": self.chip,
            "flops": self.flops,
            "hbm_bw": self.hbm_bw,
            "hbm_bytes": self.hbm_bytes,
            "ici_bw": self.ici_bw,
            "dcn_axes": list(self.dcn_axes),
            "dcn_bw": self.dcn_bw,
            "mxu_flop_overhead": self.mxu_flop_overhead,
            "mxu_min_dim": self.mxu_min_dim,
            "axis_type": self.axis_type,
            "overlap_frac": self.overlap_frac,
            "host_bw": self.host_bw,
        }

    @staticmethod
    def from_json(d: dict) -> "MachineSpec":
        return MachineSpec(
            mesh_axes=dict(d["mesh_axes"]),
            chip=d.get("chip", "v5e"),
            flops=d.get("flops", 0.0),
            hbm_bw=d.get("hbm_bw", 0.0),
            hbm_bytes=d.get("hbm_bytes", 0.0),
            ici_bw=dict(d.get("ici_bw", {})),
            dcn_axes=tuple(d.get("dcn_axes", ())),
            dcn_bw=d.get("dcn_bw", 25e9),
            mxu_flop_overhead=d.get("mxu_flop_overhead", 1.4),
            mxu_min_dim=d.get("mxu_min_dim", 128),
            axis_type=dict(d.get("axis_type", {})),
            overlap_frac=d.get("overlap_frac", 0.7),
            host_bw=d.get("host_bw", 0.0),
        )

    @staticmethod
    def from_file(path: str) -> "MachineSpec":
        with open(path) as f:
            return MachineSpec.from_json(json.load(f))

    @staticmethod
    def detect(mesh_axes: Optional[Dict[str, int]] = None,
               dcn_axes: Tuple[str, ...] = ()) -> "MachineSpec":
        """Build a spec for the visible devices (the reference's machine
        discovery in FFConfig; src/runtime/model.cc FFConfig ctor).
        `dcn_axes` marks cross-slice axes so their bandwidth binds to DCN."""
        devs = jax.devices()
        if devs[0].platform == "cpu":
            chip = "cpu-sim"
        else:
            chip = chip_for_device_kind(devs[0].device_kind)
        if not mesh_axes:
            mesh_axes = {"data": len(devs)}
        return MachineSpec(mesh_axes=dict(mesh_axes), chip=chip,
                           dcn_axes=tuple(dcn_axes))


def build_mesh(spec: MachineSpec) -> jax.sharding.Mesh:
    """Materialize the logical mesh over the visible devices. A mesh that
    spans ALL of them is laid out by mesh_utils.create_device_mesh, which
    reads the chips' coordinates: on a real 2x2 host enumeration order is
    not ring order (ids 0,1,2,3 sit at (0,0),(1,0),(0,1),(1,1)), and a
    logical axis should ride physical neighbours. A sub-mesh (fewer devices
    than visible) has no torus of its own and takes enumeration order; the
    CPU's virtual devices have no coordinates and are just reshaped."""
    from jax.experimental import mesh_utils

    shape = tuple(spec.mesh_axes.values())
    names = tuple(spec.mesh_axes.keys())
    n = math.prod(shape)
    devs = jax.devices()
    if n > len(devs):
        raise ValueError(f"mesh {spec.mesh_axes} needs {n} devices, have {len(devs)}")
    if n == len(devs):
        arr = mesh_utils.create_device_mesh(shape, devices=devs)
    else:
        arr = np.array(devs[:n]).reshape(shape)
    return jax.sharding.Mesh(arr, names)
