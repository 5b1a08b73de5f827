"""The timed programs' own logits against the reference's full forward pass,
for a cell of the brumby family: the comparison that the cell's served-token
rule does not make (ISSUE 43, `correct`).

    python benchmarks/logits_check_brumby.py --seeds 4300000269,2147483659

Prefill of one padded wave through the program the scheduler runs
(engine.prefill_first_tokens: first tokens on the device, the wave's state
written into its slots in place; the full-logits program's `[16, 1024,
151936]` rows would not fit beside the weights), prompts of 16-512 tokens,
then --steps decode steps through the per-slot state (engine.decode_step),
greedy; the reference (harness/reference_brumby.py: the pair form over the
whole sequence, no state) runs layer by layer over prompt + generated tokens,
its head a block of the vocabulary at a time. Per seed:
- `logits`: max and mean |program - reference| over the logits' scale (the
  reference's largest |logit|) for every decode step, the served tokens'
  gaps (the wave's first tokens among them) under the reference's maximum in
  bf16 ulps of each row's own scale (the cell's rule allows 8 x the family's
  GAP_UNIT_ROW_SCALES);
- four WRONG references through the same comparison, each against the sound
  one: `fp8_reference` (control.round_to_fp8 on every matrix: the nearest
  precision below the configuration's bf16), `bf16_state_reference` (the
  layers as the literal recurrence, the state rounded to bfloat16 after
  every step), `no_normaliser_reference` (y = the numerator),
  `gate_reference` (every gate times 0.9).
Last line {"holds": ...}: every program reading of the MEAN distance within
--tolerance (of the scale) and every wrong reading outside it, by the
margins it prints; and the served tokens' worst gap within the cell's limit
for the program and outside it for fp8; exit 0 only then. A variant named in
--report-only is printed and left out of `holds`; where the cell computes in
bfloat16 that is `bf16_state_reference` by default: a reference whose state
is rounded to bfloat16 lies 0.0167-0.0196 of the scale from the sound one,
which is where the bf16 program itself lies (0.0170-0.0173: its weights and
activations are bfloat16 everywhere else), so no distance to the f32
reference can tell the two apart; what parts a bf16 state from the program
is tier-1's float32 test (tests/test_brumby.py) and the --rehearsal run
here (float32 compute: all four variants in `holds`).

Needs the cell's chips like run.py; not part of a check.
"""

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
REHEARSAL = "rehearsal_brumby.json"   # this family's tiny cells
for p in (str(BENCH_DIR), str(BENCH_DIR.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.ops.registry import STATS_KEY
from flexflow_tpu.serving import (compile_serving,
                                  positions_valid_prompt_inputs,
                                  positions_valid_step_inputs)
from control import round_to_fp8
from families import family_of
from harness import manifest as mf
from harness import reference_brumby as reference

# the limit on the mean distance, of the logits' scale, at the published
# widths in bf16: the geometric middle of the program's largest reading,
# 0.0173, and the least of a wrong variant's, 0.169 (fp8 weights 0.169-0.176,
# every gate a tenth off 0.194-0.199, no normaliser 1.09-1.12: my chip run,
# PR 43, call 7, two seeds): 3.1 times of room to either side
TOLERANCE = 0.054
LENGTHS = [16, 37, 64, 90, 100, 128, 128, 150, 200, 256, 300, 350, 400, 450,
           500, 512]
VARIANTS = {
    "fp8_reference": {},
    "bf16_state_reference": {"state_dtype": "bfloat16"},
    "no_normaliser_reference": {"normaliser": False},
    "gate_reference": {"gate_factor": 0.9},
}


def emit(**kw):
    print(json.dumps(kw), flush=True)


def ulps_of(want, tokens):
    """Gap of `tokens` under each row's largest reference logit, in bf16 ulps
    of the row's own scale (the cell's `worst_gap_bf16_ulps` is this over
    the family's GAP_UNIT_ROW_SCALES)."""
    gap = want.max(-1) - np.take_along_axis(want, tokens[..., None],
                                            axis=-1)[..., 0]
    return gap / (np.abs(want).max(-1) * 2.0 ** -8)


def gap_facts(ulps) -> dict:
    return {"tokens": int(ulps.size), "not_argmax": int((ulps > 0).sum()),
            "gap_ulps_p99": float(np.quantile(ulps, 0.99)),
            "gap_ulps_max": float(ulps.max()),
            "over_8_ulps": int((ulps > 8).sum()),
            "over_16_ulps": int((ulps > 16).sum())}


@jax.jit
def _head_block(x, block):
    with jax.default_matmul_precision("highest"):
        return x @ block.astype(jnp.float32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="Brumby-14B-Base.serve-longanswer")
    ap.add_argument("--seeds", default="4300000269,2147483659")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--tolerance", type=float, default=TOLERANCE)
    ap.add_argument("--report-only", default=None,
                    help="comma-separated variants left out of `holds` "
                         "(default: bf16_state_reference where the cell "
                         "computes in bfloat16, else none)")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    steps = args.steps
    manifest = mf.load_manifest(BENCH_DIR / REHEARSAL if args.rehearsal
                                else mf.MANIFEST)
    cell = mf.load_cell(manifest, args.workload)
    cfg, sysm = cell.config, cell.system
    if args.report_only is None:
        args.report_only = "bf16_state_reference" if sysm["ffconfig"].get(
            "compute_dtype") == "bfloat16" else ""
    report_only = {v for v in args.report_only.split(",") if v}
    family = family_of(cfg)
    slots = int(sysm["max_batch_slots"])
    model = FFModel(FFConfig(batch_size=slots, seed=1, strategy_cache=False,
                             log_level="warning", **sysm["ffconfig"]))
    g = family.build(model, cfg, slots)
    eng = compile_serving(model, max_batch_slots=slots,
                          max_decode_len=int(sysm["max_decode_len"]),
                          kv_page_size=int(sysm["kv_page_size"]))
    hp = family.hyper(cfg)
    emit(fact="device", kind=jax.devices()[0].device_kind, vocab=g.vocab,
         seq=g.seq, slots=slots, state_in_place=eng.kv.writes_state_in_place)
    fp8 = jax.jit(lambda w: round_to_fp8(jnp.asarray(w, jnp.float32)))

    program, wrong = [], {name: [] for name in VARIANTS}
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        eng.params = ref_params = None          # one set of weights at a time
        eng.init(seed=seed % (2 ** 31 - 1))
        rng = np.random.default_rng(seed)
        lengths = np.minimum(np.asarray(LENGTHS[:slots], np.int32),
                             g.seq - steps - 8)
        ids = np.zeros((slots, g.seq), np.int32)
        for r, n in enumerate(lengths):
            ids[r, :n] = rng.integers(0, g.vocab, n)
        kv = eng.kv
        for r, n in enumerate(lengths):
            if kv._active[r]:
                kv.evict(r)
            kv.admit(r, int(n), int(n) + steps + 8)
        kv.push()
        first, kv_state = eng.prefill_first_tokens(
            eng.params, positions_valid_prompt_inputs(ids, lengths), lengths)
        kv_state.pop(STATS_KEY, None)
        kv.commit_prefill(kv_state, np.arange(slots, dtype=np.int32), lengths)
        del kv_state
        toks = [np.asarray(first).astype(np.int32)]
        rows = []                                 # [step][slots, vocab]
        state = kv.state
        for _ in range(steps):
            nxt = jnp.asarray(toks[-1][:, None])
            step_logits, state = eng.decode_step(
                eng.params, state, positions_valid_step_inputs(nxt, state))
            state.pop(STATS_KEY)      # the step's counters: not state
            rows.append(np.asarray(step_logits[:, 0].astype(jnp.float32)))
            toks.append(rows[-1].argmax(-1).astype(np.int32))
        kv.adopt(state)
        kv.sync_after(steps)
        t_program = time.perf_counter() - t0

        # the reference over prompt + generated tokens
        width = int(lengths.max()) + steps
        full = np.zeros((slots, width), np.int32)
        for r, n in enumerate(lengths):
            full[r, :n] = ids[r, :n]
            full[r, n:n + steps] = [t[r] for t in toks[:steps]]
        at = lengths[:, None] - 1 + np.arange(steps + 1)[None, :]   # [slots, steps + 1]
        ref_params = family.reference_params(eng.params, cfg)
        positions = np.tile(np.arange(width, dtype=np.int32), (slots, 1))

        def reference_rows(cast=lambda w: w, hp=hp):
            """[slots, steps + 1, vocab] on the host; `cast` is applied to
            one layer's weights, and one block of the head, at a time."""
            h = reference._embed(cast(ref_params["embed"]), full)
            for layer in ref_params["layers"]:
                h = reference.layer_step(
                    h, positions, {k: cast(v) for k, v in layer.items()}, hp)
            x = reference.rms(
                jnp.take_along_axis(h, jnp.asarray(at)[..., None], axis=1),
                reference._f32(ref_params["norm_f"]), hp["eps"])
            head = ref_params["head"]
            out = np.empty(x.shape[:2] + (head.shape[1],), np.float32)
            for lo in range(0, head.shape[1], reference.VOCAB_BLOCK):
                block = cast(head[:, lo:lo + reference.VOCAB_BLOCK])
                out[..., lo:lo + block.shape[1]] = np.asarray(
                    _head_block(x, block))
            return out

        t0 = time.perf_counter()
        want = reference_rows()
        t_reference = time.perf_counter() - t0
        got = np.stack(rows, axis=1)                  # [slots, steps, vocab]
        scale = float(np.abs(want).max())
        diff = np.abs(got - want[:, 1:]).max(axis=-1)          # [slots, steps]
        served = ulps_of(want, np.stack(toks, axis=1))
        emit(fact="logits", seed=seed, scale=scale,
             decode_max_diff_over_scale=float(diff.max() / scale),
             mean_diff_over_scale=float(diff.mean() / scale),
             mean_diff_over_scale_by_step=np.round(
                 diff.mean(axis=0) / scale, 5).tolist(),
             equal_argmax=int((got.argmax(-1) == want[:, 1:].argmax(-1)).sum()),
             rows=int(diff.size), served=gap_facts(served),
             first_token_gap_ulps=np.round(served[:, 0], 2).tolist(),
             program_s=t_program, reference_s=t_reference)
        program.append((float(diff.mean() / scale), float(served.max())))
        del got

        for name, switches in VARIANTS.items():
            t0 = time.perf_counter()
            rows_w = reference_rows(cast=fp8) if name == "fp8_reference" \
                else reference_rows(hp=dict(hp, **switches))
            d = np.abs(rows_w - want).max(axis=-1)[:, 1:]
            gaps = ulps_of(want, rows_w.argmax(-1))
            emit(fact=name, seed=seed,
                 max_diff_over_scale=float(d.max() / scale),
                 mean_diff_over_scale=float(d.mean() / scale),
                 served=gap_facts(gaps), seconds=time.perf_counter() - t0)
            wrong[name].append((float(d.mean() / scale), float(gaps.max())))
            del rows_w
    limit = 8.0 * family.GAP_UNIT_ROW_SCALES
    least = {name: min(m for m, _ in seen) for name, seen in wrong.items()}
    worst = max(m for m, _ in program)
    held = {name: args.tolerance < m for name, m in least.items()
            if name not in report_only}
    holds = worst <= args.tolerance and all(held.values()) \
        and max(u for _, u in program) <= limit \
        < min(u for _, u in wrong["fp8_reference"])
    emit(holds=holds, tolerance_over_scale=args.tolerance,
         program_mean_diff_over_scale=worst,
         wrong_mean_diff_over_scale=least,
         margin_over_program={n: m / worst for n, m in least.items()},
         report_only=sorted(report_only),
         served_gap_limit_ulps=limit,
         program_served_gap_ulps=max(u for _, u in program),
         fp8_served_gap_ulps=min(u for _, u in wrong["fp8_reference"]))
    return 0 if holds else 1


if __name__ == "__main__":
    sys.exit(main())
