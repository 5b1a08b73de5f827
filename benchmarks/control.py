"""Is a serving cell's `correct` tight? Put an engine that computes BELOW the
configuration's precision through the cell's own comparison.

    python benchmarks/control.py --workload granite-4.0-h-small.serve-chat --seeds 7,2147483659

One process, one engine, per seed two short windows of the cell's own
traffic through the scheduler, each judged by cells/serve.py's `parity` (the
served-token rule that decides `correct`) against the plain reference with
the weights as initialised:
  sound   the engine as the cell runs it: must come out ok;
  low     the same engine with every matrix rounded to fp8 (4 exponent and
          3 mantissa bits under a per-tensor scale: the nearest precision
          below bf16): must come out NOT ok.
Prints one JSON line per window and, last, {"tight": ...} with the largest
sound reading and the smallest low one; exits 0 only when every sound window
passed and every low one failed. With --rehearsal <manifest> it runs a tiny
cell of that rehearsal manifest (a file beside this one) on whatever backend
JAX has. Needs the cell's chips like run.py; not part of a check.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402  (puts the checkout on sys.path)
from harness import manifest as mf  # noqa: E402
from harness.facts import CompileCounter, emit  # noqa: E402


def round_to_fp8(w):
    """A matrix rounded to e4m3 under a per-tensor scale, in its own dtype;
    vectors and scalars (norm weights, biases, decay rates) stay."""
    import jax
    import jax.numpy as jnp

    if w.ndim < 2 or not jnp.issubdtype(w.dtype, jnp.floating):
        return w
    f = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(f)) / 224.0     # IEEE-style e4m3 overflows over 240
    # reduce_precision, not a cast there and back: XLA may drop such a pair
    # (xla_allow_excess_precision), and on the chip it did
    low = jax.lax.reduce_precision(f / s, exponent_bits=4, mantissa_bits=3)
    return (low * s).astype(w.dtype)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="7")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--rehearsal", metavar="MANIFEST", default=None,
                    help="a rehearsal manifest beside this file")
    args = ap.parse_args(argv)
    manifest = mf.load_manifest(
        Path(__file__).with_name(args.rehearsal) if args.rehearsal
        else mf.MANIFEST)
    cell = mf.load_cell(manifest, args.workload)
    device, _peaks = run.require_device(cell.chips, bool(args.rehearsal))

    import jax

    from cells import serve
    from cells.common import Ctx

    tr = cell.traffic
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = Ctx(cell=cell, seed=seeds[0], seconds=args.seconds, trace=False,
              trace_dir="", counter=CompileCounter())
    served = serve.build_engine(ctx, {})
    eng = served.eng
    emit(fact="control", workload=cell.name, device=device,
         seconds=args.seconds)
    lower = jax.jit(lambda p: jax.tree.map(round_to_fp8, p), donate_argnums=0)

    def window(seed):
        _sched, reqs, _records, _wall = serve.serve_window(
            served, tr, args.seconds, seed)
        return reqs

    def judged(reqs, seed):
        # as cells/serve.py's run() calls it
        return serve.parity(
            served, reqs, seed, int(tr["parity_requests"]),
            int(tr["prompt_len"]["max"]) + int(tr["output_len"]["max"]))

    readings = {"sound": [], "low": []}
    for seed in seeds:
        seed32 = seed % (2 ** 31 - 1)
        eng.params = None               # two sets of weights need not fit
        eng.init(seed=seed32)
        sound = judged(window(seed), seed)
        eng.params = lower(eng.params)
        reqs = window(seed)
        eng.params = None
        eng.init(seed=seed32)           # the weights as initialised, again
        low = judged(reqs, seed)
        for what, out in (("sound", sound), ("low", low)):
            emit(fact="control_window", seed=seed, engine=what, **out)
            readings[what].append(out["worst_gap_bf16_ulps"])
    tight = (max(readings["sound"]) <= serve.NEAR_TIE_ULPS
             < min(readings["low"]))
    emit(tight=tight, tolerance_bf16_ulps=serve.NEAR_TIE_ULPS,
         sound_worst_gap_bf16_ulps=max(readings["sound"]),
         low_worst_gap_bf16_ulps=min(readings["low"]), seeds=seeds)
    return 0 if tight else 1


if __name__ == "__main__":
    sys.exit(main())
