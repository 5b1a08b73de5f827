#!/usr/bin/env python
"""Per-op attribution evidence run (ISSUE 7 acceptance).

Fits the gpt2 CPU twin with telemetry + `--profiling` + `--profile-ops`
semantics, runs the per-op attribution join (flexflow_tpu/attribution.py) on
the profile the fit wrote and verifies the acceptance contract end to end:

  * the rows are measured from the real profile (`source == "trace"`: the
    `.xplane.pb`'s per-instruction events joined with the compiled step's
    own HLO, forward and backward apart),
  * per-op attributed times sum to the MEASURED per-update step time
    within attribution.SUM_TOLERANCE (15%),
  * every op row carries predicted cost, measured time, roofline bound
    and MFU,
  * the per-op drift top-K names the worst-mispriced op,
  * the run's telemetry dir holds the `op/attr` rows
    (tools/trace_report.py's [ops] section).

Usage:
    python tools/profile_attribution.py [--out attribution.json]
                                        [--epochs N] [--blocks N]
    python tools/profile_attribution.py --check    # CI smoke (small twin)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _build_twin(tdir: str, blocks: int, batch: int = 8):
    """The gpt2 CPU twin (the bench family's standard subject): a scaled
    GPT-2 on the virtual data mesh, compiled with telemetry on and the
    fit profiled (jax.profiler.trace into <tdir>/profile)."""
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.models import GPT2Config, build_gpt2

    cfg = FFConfig(batch_size=batch, only_data_parallel=True,
                   telemetry_dir=tdir, log_level="warning", profiling=True,
                   profile_dir=os.path.join(tdir, "profile"))
    m = FFModel(cfg)
    gcfg = GPT2Config(vocab=256, seq=16, d_model=64, heads=4,
                      layers=blocks, dropout=0.0)
    build_gpt2(m, gcfg, batch=batch)
    cm = m.compile(SGDOptimizer(lr=0.01),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=0)
    return m, cm, gcfg


def run(epochs: int = 3, blocks: int = 2, batch: int = 8,
        telemetry_dir: Optional[str] = None,
        verbose: bool = True) -> Dict[str, Any]:
    import numpy as np

    from flexflow_tpu import attribution, telemetry
    import trace_report

    own_tmp = None
    if telemetry_dir is None:
        own_tmp = tempfile.TemporaryDirectory()
        telemetry_dir = os.path.join(own_tmp.name, "telemetry")
    try:
        m, cm, gcfg = _build_twin(telemetry_dir, blocks, batch)
        rng = np.random.default_rng(0)
        n = batch * 8
        ids = rng.integers(0, gcfg.vocab, size=(n, gcfg.seq)).astype("int32")
        pos = np.broadcast_to(np.arange(gcfg.seq, dtype="int32"),
                              (n, gcfg.seq)).copy()
        y = rng.integers(0, gcfg.vocab, size=(n, gcfg.seq)).astype("int32")
        # >= 2 epochs: the drift monitor needs a post-compilation window
        # for an honest measured step time
        cm.fit([ids, pos], y, epochs=max(2, epochs), verbose=False)
        report = cm.op_attribution(print_table=verbose)
        telemetry.flush()
        op_rows = trace_report.op_attr_rows(
            telemetry.read_events(telemetry_dir))

        step = report["step_time_s"]
        att = report["attributed_total_s"]
        rows = report["rows"]
        result: Dict[str, Any] = {
            "model": f"gpt2 CPU twin ({blocks} blocks, vocab={gcfg.vocab}, "
                     f"seq={gcfg.seq}, d_model={gcfg.d_model})",
            "batch": batch,
            "epochs": max(2, epochs),
            "source": report["source"],
            "rows": len(rows),
            "step_time_s": step,
            "attributed_total_s": att,
            "attributed_over_step": (att / step) if step else None,
            "coverage": report["coverage"],
            "sum_tolerance": attribution.SUM_TOLERANCE,
            "worst_mispriced_op": (report["top_drift"]["rows"][0]["layer"]
                                   if report["top_drift"]["rows"] else None),
            "top_drift_explained": report["top_drift"]["explained"],
            "bandwidth_bound_ops": sum(1 for r in rows
                                       if r["bound"] == "bandwidth"),
            "compute_bound_ops": sum(1 for r in rows
                                     if r["bound"] == "compute"),
            "telemetry_op_rows": len(op_rows),
            "outside_layers_s": report["outside_s"],
            "top_ops": [{k: r.get(k) for k in
                         ("layer", "op", "predicted_s", "attributed_s",
                          "roofline_s", "mfu", "bound", "phases_s")}
                        for r in rows[:8]],
        }
        return result
    finally:
        from flexflow_tpu import telemetry

        telemetry.shutdown()
        if own_tmp is not None:
            own_tmp.cleanup()


def verify(result: Dict[str, Any], report_rows_checked: bool = True) -> None:
    """The acceptance assertions (shared by --check and the full run)."""
    from flexflow_tpu import attribution

    assert result["rows"] > 0, "no op rows attributed"
    assert result["source"] == "trace", \
        f"rows are {result['source']!r}, not measured from the fit's profile"
    step, att = result["step_time_s"], result["attributed_total_s"]
    assert step and step > 0, "no measured step time (fit didn't record " \
                              "drift windows)"
    assert abs(att - step) / step <= attribution.SUM_TOLERANCE, \
        f"attributed {att:.6f}s vs measured step {step:.6f}s " \
        f"(> {attribution.SUM_TOLERANCE:.0%})"
    assert result["worst_mispriced_op"], "per-op drift top-K is empty"
    assert result["telemetry_op_rows"] > 0, \
        "the telemetry dir holds no op/attr rows"
    if report_rows_checked:
        for r in result["top_ops"]:
            for k in ("predicted_s", "attributed_s", "roofline_s", "mfu"):
                assert r.get(k) is not None, (k, r)
            assert r.get("bound") in ("compute", "bandwidth"), r


def _check() -> int:
    result = run(epochs=2, blocks=1, verbose=False)
    verify(result)
    print(f"profile_attribution --check OK ({result['rows']} op rows, "
          f"attributed/step={result['attributed_over_step']:.3f}, "
          f"worst={result['worst_mispriced_op']}, "
          f"telemetry={result['telemetry_op_rows']} op/attr rows)")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        "profile_attribution", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--out", default="attribution.json")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--blocks", type=int, default=2)
    ap.add_argument("--telemetry-dir", default=None,
                    help="keep the run's telemetry (default: temp dir)")
    ap.add_argument("--check", action="store_true",
                    help="CI smoke: small twin, assert the acceptance "
                         "contract, write nothing")
    args = ap.parse_args(argv)
    if args.check:
        return _check()
    result = run(epochs=args.epochs, blocks=args.blocks,
                 telemetry_dir=args.telemetry_dir)
    verify(result)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {args.out}: {result['rows']} op rows, "
          f"attributed/step={result['attributed_over_step']:.3f}, "
          f"worst mispriced={result['worst_mispriced_op']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
