"""Measured per-op costs — the on-device microbenchmark path.

Reference analog: `Op::inner_measure_operator_cost` (src/runtime/model.cu:
38-74): run the op's kernels on a real device with warmup + repeats under
cudaEvent timing, cached by (op params, machine view)
(Simulator::measure_operator_cost, src/runtime/simulator.cc:537-560).

TPU version: jit the op's lowering at **shard-local shapes** for the
candidate's layout on one real chip, block_until_ready-time it, and cache by
(params_key, layout). Forward and backward are timed INDEPENDENTLY (like the
reference's separate fwd/bwd kernel timings): backward is the jitted VJP of
the lowering wrt (weights, float inputs), and its time is the grad-step time
minus the forward time. The known fidelity limit (SURVEY.md §7 hard part #1):
XLA fuses across ops, so isolated measurements over-predict; the analytic
model is the default and this path is opt-in calibration.
"""

from __future__ import annotations

import json
import os
import time
from typing import TYPE_CHECKING, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

if TYPE_CHECKING:
    from flexflow_tpu.core.layer import Layer
    from flexflow_tpu.search.candidates import Candidate

from flexflow_tpu.ops.registry import LoweringCtx, get_op_def
from flexflow_tpu.parallel.machine import MachineSpec
from flexflow_tpu.parallel.ptensor import ParallelTensor
from flexflow_tpu.search import cost_model as cm


def _shard_shape(spec, dims, machine):
    return ParallelTensor.build(spec, list(dims or []), machine).shard_shape


class MeasuredCost:
    def __init__(self, machine: MachineSpec, repeats: int = 5, warmup: int = 2,
                 windows: int = 3, cache_dir: Optional[str] = None):
        self.machine = machine
        self.repeats = repeats
        self.warmup = warmup
        # median-of-windows: each measurement is `windows` independent
        # timed windows of `repeats` runs, reduced by MEDIAN — one window
        # stolen by a concurrent process (the tier-1 test_measure flake)
        # can no longer zero out a bwd = total - fwd difference
        self.windows = max(1, windows)
        self.cache: Dict[Tuple, Tuple[float, float]] = {}
        # persistent (params_key, layout, machine) -> (fwd, bwd) store (the
        # reference's measure_operator_cost cache made cross-process,
        # simulator.cc:537-560): microbenchmarks are the expensive part of
        # the measured path, so they outlive the process. One file per
        # machine fingerprint; its content hash doubles as the strategy
        # cache's calibration fingerprint (search/strategy_cache.py).
        if cache_dir is None:
            cache_dir = os.environ.get("FF_MEASURE_CACHE_DIR", "")
        self.cache_path: Optional[str] = None
        if cache_dir:
            from flexflow_tpu.search import memo

            self.cache_path = os.path.join(
                os.path.expanduser(cache_dir),
                f"measured-{memo.machine_fingerprint(machine)}.json")
            self._load_disk()

    def _load_disk(self):
        # keys persist as repr() of the in-memory tuple key — enums, shapes
        # and dtypes all repr canonically, so the string is process-stable
        self._disk: Dict[str, list] = {}
        self._dirty: Dict[str, list] = {}  # keys THIS process measured
        self._disk_mtime = 0.0
        try:
            with open(self.cache_path) as f:
                self._disk = dict(json.load(f))
            self._disk_mtime = os.path.getmtime(self.cache_path)
        except (OSError, ValueError):
            pass

    def _persist(self, key, val):
        if not self.cache_path:
            return
        try:
            os.makedirs(os.path.dirname(self.cache_path), exist_ok=True)
            # merge-on-write: overlay ONLY the keys this process measured
            # (the dirty set) onto a re-read of the file, so a concurrent
            # measurer's fresher entries for other keys survive. The mtime
            # gate skips the re-read when nobody else wrote, keeping
            # per-measurement I/O at one O(n) dump.
            self._dirty[repr(key)] = list(val)
            try:
                mtime = os.path.getmtime(self.cache_path)
            except OSError:
                mtime = 0.0
            if mtime != self._disk_mtime:
                try:
                    with open(self.cache_path) as f:
                        current = dict(json.load(f))
                except (OSError, ValueError):
                    current = {}
                current.update(self._dirty)
                self._disk = current
            else:
                self._disk[repr(key)] = list(val)
            tmp = self.cache_path + f".tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(self._disk, f, indent=0, sort_keys=True)
            os.replace(tmp, self.cache_path)
            self._disk_mtime = os.path.getmtime(self.cache_path)
        except OSError:
            self.cache_path = None  # unwritable dir: degrade to in-memory

    def op_times(self, layer: "Layer", cand: "Candidate") -> Tuple[float, float]:
        """(fwd_seconds, bwd_seconds), measured INDEPENDENTLY — the reference
        times forward and backward as separate kernel launches
        (src/runtime/model.cu:38-74); ops whose bwd/fwd ratio is far from 2
        (embedding scatter-add, attention recompute, layernorm) make the old
        bwd≈2×fwd approximation exactly the error measurement exists to fix."""
        key = (layer.params_key(),
               tuple(tuple(map(str, d)) for d in cand.out_dims),
               tuple(sorted((w, tuple(map(str, d))) for w, d in cand.weight_dims.items())))
        if key in self.cache:
            return self.cache[key]
        if self.cache_path:
            hit = self._disk.get(repr(key))
            if hit is not None:
                self.cache[key] = (float(hit[0]), float(hit[1]))
                return self.cache[key]
        try:
            fwd, bwd = self._measure(layer, cand)
            self._persist(key, (fwd, bwd))
        except Exception:
            # fall back to the analytic COMPUTE-ONLY time: cand.op_time
            # includes extra_comm + grad_sync, which op_time() below adds
            # again — subtract them or collective-heavy candidates would be
            # double-charged exactly when measurement fails
            from flexflow_tpu.search.candidates import _batch_axes

            t = cand.op_time(layer, self.machine)
            t -= cand.extra_comm + cm.grad_sync_time(
                layer.weight_specs, cand.weight_dims, self.machine,
                _batch_axes(self.machine))
            t = max(0.0, t)
            fwd, bwd = t / 3.0, 2.0 * t / 3.0
        self.cache[key] = (fwd, bwd)
        return fwd, bwd

    def op_time(self, layer: "Layer", cand: "Candidate") -> float:
        fwd, bwd = self.op_times(layer, cand)
        from flexflow_tpu.search.candidates import _batch_axes

        return fwd + bwd + cand.extra_comm + cm.grad_sync_time(
            layer.weight_specs, cand.weight_dims, self.machine,
            _batch_axes(self.machine))

    def op_time_fwd(self, layer: "Layer", cand: "Candidate") -> float:
        """Forward-pass-only total (serving attribution — ISSUE 14): the
        measured fwd leg plus the candidate's inherent collectives; no
        backward, no grad sync (inference never runs either)."""
        fwd, _bwd = self.op_times(layer, cand)
        return fwd + cand.extra_comm

    def _time(self, fn, *args) -> float:
        """Median over `windows` timed windows of `repeats` dispatches
        each, every window ending in block_until_ready (the device runs one
        stream, so waiting on the LAST call covers all queued repeats). The
        shared timing protocol: every consumer — the measured search,
        tools/calibrate.py, profile_report — gets the same robustness to a
        scheduler hiccup landing inside one window, instead of a single
        wall-clock delta the hiccup corrupts outright."""
        for _ in range(1 + self.warmup):
            jax.block_until_ready(fn(*args))
        ts = []
        for _ in range(self.windows):
            t0 = time.perf_counter()
            for _ in range(self.repeats):
                out = fn(*args)
            jax.block_until_ready(out)
            ts.append((time.perf_counter() - t0) / self.repeats)
        return float(np.median(ts))

    def _measure(self, layer: "Layer", cand: "Candidate") -> Tuple[float, float]:
        machine = self.machine
        rng = np.random.default_rng(0)
        ins = []
        for i, tin in enumerate(layer.inputs):
            shp = _shard_shape(tin.spec, cand.in_dims[i] if i < len(cand.in_dims) else None, machine)
            dt = tin.spec.dtype.jnp_dtype
            if jnp.issubdtype(dt, jnp.integer):
                ins.append(jnp.asarray(rng.integers(0, 2, size=shp), dt))
            else:
                ins.append(jnp.asarray(rng.normal(size=shp), dt))
        weights = {}
        for w, spec in layer.weight_specs.items():
            shp = _shard_shape(spec, cand.weight_dims.get(w), machine)
            weights[w] = jnp.asarray(rng.normal(size=shp), spec.dtype.jnp_dtype)

        lower = get_op_def(layer.op_type).lower
        fidx = tuple(i for i, a in enumerate(ins)
                     if jnp.issubdtype(a.dtype, jnp.floating))
        fins = [ins[i] for i in fidx]
        iins = [a for i, a in enumerate(ins) if i not in fidx]

        def apply(weights, fins, iins):
            merged, fi, ii = [], iter(fins), iter(iins)
            for i in range(len(ins)):
                merged.append(next(fi) if i in fidx else next(ii))
            ctx = LoweringCtx(training=False, rng=jax.random.PRNGKey(0))
            return lower(layer, merged, weights, ctx)

        run_fwd = jax.jit(apply)
        fwd = self._time(run_fwd, weights, fins, iins)

        # backward: actual VJP of the lowering wrt (weights, float inputs),
        # timed as a separate jit; bwd = grad-step time minus forward time
        def loss_fn(weights, fins, iins):
            outs = apply(weights, fins, iins)
            return sum(jnp.sum(o.astype(jnp.float32)) for o in outs
                       if jnp.issubdtype(o.dtype, jnp.floating))

        out_shapes = jax.eval_shape(apply, weights, fins, iins)
        has_float_out = any(jnp.issubdtype(o.dtype, jnp.floating)
                            for o in out_shapes)
        has_diff = (bool(weights) or bool(fins)) and has_float_out
        if has_diff:
            run_grad = jax.jit(jax.grad(loss_fn, argnums=(0, 1)))
            total = self._time(run_grad, weights, fins, iins)
            bwd = max(0.0, total - fwd)
        else:
            bwd = 0.0
        return fwd, bwd
