"""The timed programs' own logits against the reference's full forward pass,
for a cell of the bailing_hybrid family: the comparison that the cell's
served-token rule does not make (ISSUE 41, item 9).

    python benchmarks/logits_check_bailing_hybrid.py --seeds 4100000269,2147483659

Prefill of one padded wave (engine.prefill, prompts of 16-512 tokens), then
--steps decode steps through the paged latent cache and the per-slot matrix
state (engine.decode_step: the absorbed form, one step of the delta rule),
greedy; the reference (the token-by-token recurrence, attention with K and V
decompressed, no cache) runs layer by layer over prompt + generated tokens.
Per seed:
- `logits`: max and mean |program - reference| over the logits' scale (the
  reference's largest |logit|) for the wave's last-position rows and for
  every decode step, and the served tokens' gaps under the reference's
  maximum in bf16 ulps of each row's own scale (the cell's rule allows 8 x
  the family's GAP_UNIT_ROW_SCALES);
- `router`: how far the program's selection scores (from the inputs that
  the program's own lowering hands its expert layers, bf16) lie from the
  reference's (f32) near the top (what ROUTING_NOISE is set from), how often
  its chosen set differs, and how often the difference touches a HELD expert
  (the only way it reaches this holder's result);
- `fp8_reference`: the same distances for the reference computed with fp8
  weights (control.round_to_fp8: the nearest precision below the
  configuration's bf16).
- `bf16_state_reference`: the same for the reference with every KDA layer's
  matrix state rounded to bfloat16 after every token (`hp["state_dtype"]`).
  Reported, and NOT part of `holds` at bf16 widths: the program's products
  take the state as a bf16 operand, so this variant lies where the program
  lies; what parts a bf16 state from the program is tier-1's float32 test
  (tests/test_bailing_hybrid.py) and the --rehearsal run here (float32).
Last line {"holds": ...}: every program reading of the MEAN distance within
--tolerance (of the scale) and every fp8 reading outside it, and the served
tokens' worst gap AS THE CELL'S RULE JUDGES IT (each token over its
neighbourhood of 8: families/bailing_hybrid.py) within the cell's limit for the
program and outside it for fp8; exit 0 only then. The mean and not the maximum: bf16 hidden states flip
a held expert for two thirds of the tokens by the last expert layer
(`router`: a quarter of the experts are held and a flip is a gate's worth,
so flips cascade from layer to layer), and one such row lies as far off as
fp8's typical one, while the means lie apart: at the published widths the
program reads 0.228-0.230 of the scale and the fp8 reference 0.531-0.562,
the default --tolerance 0.35 is their geometric middle (PERF.md, PR 41).

The witness, `--routed-scale 0`: program and reference are both built from
the cell's configuration with `routed_scaling_factor` 0 in place of 2.5, so
the routers still choose and the experts still compute, and no choice can
reach the result: what is left between the bf16 program and the float32
reference is the mixers' (six KDA layers, one latent-attention layer), the
dense MLP's, the shared experts' and the head's own rounding. That is the
distance that says whether the 0.23 above is the routing's or the new
operator's, and the one a tight limit can hold: beside fp8 and the bf16 state
the witness puts two WRONG layers through the same comparison, the reference
with the decay's bound a tenth off (`lower_bound` x 0.9) and the reference
whose beta is the constant 1/2 (the beta columns of `in_proj` zeroed).
`holds` is then: every program reading of the mean distance within
--tolerance (default WITNESS_TOLERANCE), and every fp8, wrong-decay and
constant-beta reading outside it; the bf16-state reading is reported beside
them with `mean_outside_tolerance`: on the chip it read 0.034, where the
program itself reads (0.034-0.036: the chunked form takes the state as a
bf16 operand of its products), so at bf16 widths it is tier-1's to part.

Needs the cell's chips like run.py; not part of a check.
"""

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
REHEARSAL = "rehearsal_bailing_hybrid.json"   # this family's tiny cells
for p in (str(BENCH_DIR), str(BENCH_DIR.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.compiler.lowering import build_forward
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.ops.registry import STATS_KEY
from flexflow_tpu.serving import (compile_serving,
                                  positions_valid_prompt_inputs,
                                  positions_valid_step_inputs)
from control import round_to_fp8
from families import family_of
from harness import manifest as mf
from harness import reference_bailing_hybrid as reference

# the witness's limit on the mean distance, of the logits' scale: the
# geometric middle of the program's largest reading, 0.0356, and the least
# of a wrong layer's, 0.141 (the decay's bound a tenth off; constant beta
# 0.64-0.68, fp8 0.36: my chip run, PR 41, call 11, two seeds)
WITNESS_TOLERANCE = 0.07
LENGTHS = [16, 37, 64, 90, 100, 128, 128, 150, 200, 256, 300, 350, 400, 450,
           500, 512]


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
    if not ulps.size:
        return {"tokens": 0}
    return {"tokens": int(ulps.size), "not_argmax": int((ulps > 0).sum()),
            "gap_ulps_p99": float(np.quantile(ulps, 0.99)),
            "gap_ulps_max": float(ulps.max()),
            "over_8_ulps": int((ulps > 8).sum()),
            "over_16_ulps": int((ulps > 16).sum())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload",
                    default="Ling-3.0-flash.serve-chat")
    ap.add_argument("--seeds", default="4100000269,2147483659")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--tolerance", type=float, default=None)
    ap.add_argument("--routed-scale", type=float, default=None,
                    help="the witness: routed_scaling_factor on both sides; "
                         "at 0 no router's choice reaches the result")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    steps = args.steps
    witness = args.routed_scale is not None
    if args.tolerance is None:
        args.tolerance = WITNESS_TOLERANCE if witness else 0.35
    manifest = mf.load_manifest(BENCH_DIR / REHEARSAL if args.rehearsal
                                else mf.MANIFEST)
    cell = mf.load_cell(manifest, args.workload)
    cfg, sysm = cell.config, cell.system
    if witness:
        cfg = dict(cfg, routed_scaling_factor=args.routed_scale)
    family = family_of(cfg)
    slots = int(sysm["max_batch_slots"])
    model = FFModel(FFConfig(batch_size=slots, seed=1, strategy_cache=False,
                             log_level="warning", **sysm["ffconfig"]))
    g = family.build(model, cfg, slots)
    eng = compile_serving(model, max_batch_slots=slots,
                          max_decode_len=int(sysm["max_decode_len"]),
                          kv_page_size=int(sysm["kv_page_size"]))
    hp = family.hyper(cfg)
    lo, hi = hp["held"]
    emit(fact="device", kind=jax.devices()[0].device_kind, vocab=g.vocab,
         seq=g.seq, slots=slots,
         routed_scaling_factor=hp["routed_scaling_factor"])

    # the inputs the program's own lowering hands each expert layer
    pm = eng.prefill_model
    moe = [l for l in pm.layers if l.op_type is OperatorType.MOE_LAYER]
    routed_fwd = build_forward(pm.layers, pm.input_tensors,
                               [l.inputs[0] for l in moe], eng.mesh,
                               eng.prefill_strategy,
                               compute_dtype=eng.cfg.compute_dtype)

    @jax.jit
    def program_scores(params, inputs):
        """[layers, slots, seq, E]: the selection scores the program's
        routers see."""
        xs, _ = routed_fwd(params, {}, inputs, False, jax.random.PRNGKey(0))
        return jnp.stack([
            jax.nn.sigmoid(jnp.dot(
                x.astype(jnp.float32),
                params[l.name]["router"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            + params[l.name]["score_bias"] for l, x in zip(moe, xs)])

    def judged_worst(ulps) -> float:
        """The worst served token as the cell's rule judges it: over its
        neighbourhood (families/bailing_hybrid.py), in ulps at the row's scale."""
        return float(family.neighbourhood_gaps(jnp.asarray(ulps)).max())

    def beta_one_half(layer):
        """A KDA layer whose beta is sigmoid(0): `in_proj`'s last `heads`
        columns zeroed."""
        if "in_proj" not in layer:
            return layer
        return dict(layer, in_proj=jnp.asarray(layer["in_proj"])
                    .at[:, -hp["heads"]:].set(0))

    program_worst, low_least, state_least, wrong_least = [], [], [], []
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
        logits, kv_state = eng.prefill(eng.params,
                                       positions_valid_prompt_inputs(ids, lengths))
        kv_state.pop(STATS_KEY, None)
        last = np.asarray(jnp.take_along_axis(
            logits, jnp.asarray(lengths - 1)[:, None, None], axis=1)[:, 0]
            .astype(jnp.float32))
        del logits
        kv.commit_prefill(kv_state, np.arange(slots, dtype=np.int32), lengths)
        del kv_state
        rows = [last]                             # [step][slots, vocab]
        toks = [last.argmax(-1).astype(np.int32)]
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
        valid = np.zeros((slots, g.seq), np.int32)
        for r, n in enumerate(lengths):
            full[r, :n] = ids[r, :n]
            full[r, n:n + steps] = [t[r] for t in toks[:steps]]
            valid[r, :n + steps] = 1
        at = lengths[:, None] - 1 + np.arange(steps + 1)[None, :]   # [slots, steps + 1]
        ref_params = family.reference_params(eng.params, cfg)

        positions = np.tile(np.arange(width, dtype=np.int32), (slots, 1))

        def reference_rows(params, cast=lambda w: w, hp=hp,
                           alter=lambda layer: layer):
            """`cast` and `alter` are applied to one layer's weights at a
            time."""
            h = reference._embed(cast(params["embed"]), full)
            choices, selected = [], []
            for layer in params["layers"]:
                h, e, c = reference.layer_step(
                    h, positions,
                    alter({k: cast(v) for k, v in layer.items()}), hp,
                    scores=True)
                if e is not None:
                    choices.append(np.asarray(e))
                    selected.append(c)
            picked = jnp.take_along_axis(h, jnp.asarray(at)[..., None], axis=1)
            out = reference._head(picked, params["norm_f"], cast(params["head"]),
                                  hp["eps"])
            return np.asarray(out), np.stack(choices), selected

        t0 = time.perf_counter()
        want, ref_choices, ref_scores = reference_rows(ref_params)
        t_reference = time.perf_counter() - t0
        got = np.stack(rows, axis=1)                      # [slots, steps + 1, vocab]
        scale = float(np.abs(want).max())
        diff = np.abs(got - want).max(axis=-1)            # [slots, steps + 1]
        served = ulps_of(want, got.argmax(-1))
        emit(fact="logits", seed=seed, scale=scale,
             prefill_max_diff_over_scale=float(diff[:, 0].max() / scale),
             decode_max_diff_over_scale=float(diff[:, 1:].max() / scale),
             mean_diff_over_scale=float(diff.mean() / scale),
             equal_argmax=int((got.argmax(-1) == want.argmax(-1)).sum()),
             rows=int(diff.size), served=gap_facts(served),
             served_gap_ulps_by_slot=np.round(served, 2).tolist(),
             program_s=t_program, reference_s=t_reference)
        program_worst.append((float(diff.mean() / scale), judged_worst(served)))

        # the router: the program's scores and choices against the reference's
        full_padded = np.zeros((slots, g.seq), np.int32)
        full_padded[:, :width] = full
        prog_scores = np.asarray(program_scores(
            eng.params, [jnp.asarray(full_padded),
                         jnp.asarray(np.tile(np.arange(g.seq, dtype=np.int32),
                                             (slots, 1))),
                         jnp.asarray(valid)]))
        real = valid[:, :width].astype(bool)
        k = hp["top_k"]
        near_moved, flips, held_flips = [], [], []
        for layer, ref_c in enumerate(ref_scores):
            ref_c = np.asarray(ref_c)[real]               # [tokens, E]
            prog_c = prog_scores[layer][:, :width][real]
            order = np.argsort(-ref_c, axis=-1)[:, :2 * k]    # around rank k
            near_moved.append(np.abs(np.take_along_axis(prog_c - ref_c, order, -1)))
            a = np.zeros(ref_c.shape, bool)
            b = np.zeros(ref_c.shape, bool)
            np.put_along_axis(a, np.asarray(reference.chosen(
                jnp.asarray(prog_c), hp)), True, -1)
            np.put_along_axis(b, np.sort(ref_choices[layer], -1)[real], True, -1)
            flips.append(float((a != b).any(-1).mean()))
            held_flips.append(float((a != b)[:, lo:hi].any(-1).mean()))
        near = np.stack(near_moved)
        gap_k = np.stack([
            -np.diff(np.sort(np.asarray(c)[real], -1)[:, -(k + 1):-(k - 1)], axis=-1)[:, 0]
            for c in ref_scores])
        emit(fact="router", seed=seed, tokens=int(real.sum()),
             what="|program's selection score - reference's| over each "
                  "token's 2k largest scores, by expert layer; the program's "
                  "hidden state is its own from layer to layer",
             moved_rms_by_layer=[float(np.sqrt((n ** 2).mean())) for n in near],
             moved_p99_first_layer=float(np.quantile(near[0], 0.99)),
             gap_between_rank_k_and_k_plus_1_median=float(np.median(np.abs(gap_k))),
             share_of_tokens_whose_choice_differs_by_layer=flips,
             share_of_tokens_whose_held_experts_differ_by_layer=held_flips)
        del prog_scores, near

        # the reference at the nearest precision below bf16
        low_rows, _, _ = reference_rows(
            ref_params, cast=jax.jit(lambda w: round_to_fp8(
                jnp.asarray(w, jnp.float32))))
        low_diff = np.abs(low_rows - want).max(axis=-1)
        low_served = ulps_of(want, low_rows.argmax(-1))
        emit(fact="fp8_reference", seed=seed,
             max_diff_over_scale=float(low_diff.max() / scale),
             mean_diff_over_scale=float(low_diff.mean() / scale),
             served=gap_facts(low_served),
             served_gap_ulps_by_slot=np.round(low_served, 2).tolist())
        low_least.append((float(low_diff.mean() / scale),
                          judged_worst(low_served)))
        del low_rows
        # the reference with the matrix state kept in bfloat16
        state_rows, _, _ = reference_rows(
            ref_params, hp=dict(hp, state_dtype="bfloat16"))
        state_diff = np.abs(state_rows - want).max(axis=-1)
        state_served = ulps_of(want, state_rows.argmax(-1))
        emit(fact="bf16_state_reference", seed=seed,
             max_diff_over_scale=float(state_diff.max() / scale),
             mean_diff_over_scale=float(state_diff.mean() / scale),
             mean_outside_tolerance=bool(
                 state_diff.mean() / scale > args.tolerance),
             served=gap_facts(state_served),
             served_gap_judged_ulps=judged_worst(state_served))
        state_least.append(float(state_diff.mean() / scale))
        del state_rows
        if not witness:
            continue
        # two wrong layers: the decay's bound a tenth off, beta a constant
        for name, kw in (
                ("decay_bound_reference",
                 {"hp": dict(hp, lower_bound=0.9 * hp["lower_bound"])}),
                ("constant_beta_reference", {"alter": beta_one_half})):
            wrong_rows, _, _ = reference_rows(ref_params, **kw)
            wrong_diff = np.abs(wrong_rows - want).max(axis=-1)
            emit(fact=name, seed=seed,
                 max_diff_over_scale=float(wrong_diff.max() / scale),
                 mean_diff_over_scale=float(wrong_diff.mean() / scale),
                 served_gap_judged_ulps=judged_worst(
                     ulps_of(want, wrong_rows.argmax(-1))))
            wrong_least.append(float(wrong_diff.mean() / scale))
            del wrong_rows
    limit = 8.0 * family.GAP_UNIT_ROW_SCALES
    holds = max(m for m, _ in program_worst) <= args.tolerance \
        < min(m for m, _ in low_least)
    if witness:     # the means alone: the cell's limit is the whole model's
        holds = holds and args.tolerance < min(wrong_least)
    else:
        holds = holds and max(g for _, g in program_worst) <= limit \
            < min(g for _, g in low_least)
    emit(holds=holds, tolerance_over_scale=args.tolerance,
         **({"witness_routed_scale": args.routed_scale,
             "wrong_layer_mean_diff_over_scale": min(wrong_least)}
            if witness else {}),
         program_mean_diff_over_scale=max(m for m, _ in program_worst),
         fp8_mean_diff_over_scale=min(m for m, _ in low_least),
         served_gap_limit_ulps=limit,
         program_served_gap_ulps=max(g for _, g in program_worst),
         fp8_served_gap_ulps=min(g for _, g in low_least),
         bf16_state_mean_diff_over_scale=min(state_least))
    return 0 if holds else 1


if __name__ == "__main__":
    sys.exit(main())
