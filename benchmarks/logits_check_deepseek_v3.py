"""The timed programs' own logits against the reference's full forward pass,
for a cell of the deepseek_v3 family: the comparison that the cell's
served-token rule does not make (ISSUE 32, Tentpole 4).

    python benchmarks/logits_check_deepseek_v3.py --seeds 3200000269,2147483659

Prefill of one padded wave (engine.prefill, prompts of 16-512 tokens), then
--steps decode steps through the latent cache (engine.decode_step, the
absorbed form), greedy; the reference (un-absorbed, no cache) runs layer by
layer over prompt + generated tokens. Per seed:
- `logits`: max |program - reference| over the logits' scale (the
  reference's largest |logit|) for the wave's last-position rows and for
  every decode step, and the served tokens' gaps under the reference's
  maximum in bf16 ulps of each row's own scale (the cell's rule allows 16);
- `router_flips`: how often the program's chosen expert set (from the inputs
  that the program's own lowering hands its expert layers, bf16) differs from
  the reference's (f32), and how often the difference touches a HELD expert
  (the only way it reaches this holder's result);
- `fp8_reference`: the same distances for the reference computed with fp8
  weights (control.round_to_fp8: the nearest precision below the
  configuration's bf16).
- `score_perturbation`: how far the program's selection scores lie from the
  reference's (what families/deepseek_v3.py's ROUTING_NOISE is set from);
  rows whose routing the reference does not decide are reported apart
  (`undecided_*`) and not held to the tolerance.
Last line {"holds": ...}: every program reading within --tolerance (0.10 of
the scale at the published widths; PERF.md has the two readings it lies
between, PR 32) and every fp8 reading outside it; exit 0 only then. Needs the
cell's chips like run.py; not part of a check.
"""

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
REHEARSAL = "rehearsal_deepseek_v3.json"   # this family's tiny cells
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
from harness import reference_deepseek_v3 as reference

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
    q = np.quantile(ulps, [0.99, 0.999])
    return {"tokens": int(ulps.size), "not_argmax": int((ulps > 0).sum()),
            "gap_ulps_p99": float(q[0]), "gap_ulps_p999": float(q[1]),
            "gap_ulps_max": float(ulps.max()),
            "over_8_ulps": int((ulps > 8).sum()),
            "over_16_ulps": int((ulps > 16).sum())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="GigaChat3.1-702B-A36B.serve-chat")
    ap.add_argument("--seeds", default="3200000269,2147483659")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--tolerance", type=float, default=0.10)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    steps = args.steps
    manifest = mf.load_manifest(BENCH_DIR / REHEARSAL if args.rehearsal
                                else mf.MANIFEST)
    cell = mf.load_cell(manifest, args.workload)
    cfg, sysm = cell.config, cell.system
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
         seq=g.seq, slots=slots)

    # the inputs the program's own lowering hands each expert layer
    pm = eng.prefill_model
    moe_inputs = [l.inputs[0] for l in pm.layers
                  if l.op_type is OperatorType.MOE_LAYER]
    routed_fwd = build_forward(pm.layers, pm.input_tensors, moe_inputs,
                               eng.mesh, eng.prefill_strategy,
                               compute_dtype=eng.cfg.compute_dtype)
    routers = [l.name for l in pm.layers if l.op_type is OperatorType.MOE_LAYER]

    from flexflow_tpu.ops.moe_ops import _choose

    moe_params = {l.name: l.params for l in pm.layers
                  if l.op_type is OperatorType.MOE_LAYER}

    @jax.jit
    def program_choices(params, inputs):
        xs, _ = routed_fwd(params, {}, inputs, False, jax.random.PRNGKey(0))
        out = []
        for name, x in zip(routers, xs):
            scores = jnp.dot(x.astype(jnp.float32),
                             params[name]["router"].astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            flat = scores.reshape(-1, scores.shape[-1])
            chosen = _choose(flat, params[name], moe_params[name])[1]
            out.append((chosen.reshape(scores.shape[:-1] + chosen.shape[-1:]),
                        jax.nn.sigmoid(scores) + params[name]["score_bias"]))
        # [layers, slots, seq, k] chosen, [layers, slots, seq, E] scores
        return jnp.stack([o[0] for o in out]), jnp.stack([o[1] for o in out])

    program_worst, low_least = [], []
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

        def reference_rows(params, cast=lambda w: w):
            """`cast` is applied to one layer's weights at a time."""
            h = reference._embed(cast(params["embed"]), full)
            choices, selected = [], []
            for layer in params["layers"]:
                h, e, c = reference.layer_step(
                    h, positions, {k: cast(v) for k, v in layer.items()}, hp,
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
        got = np.stack(rows, axis=1)                      # [slots, 33, vocab]
        scale = float(np.abs(want).max())
        diff = np.abs(got - want).max(axis=-1)            # [slots, steps + 1]
        # the rows whose routing the reference decides (families/deepseek_v3.py)
        decided_all = np.asarray(family.routing_decided(ref_scores, hp))
        decided = np.take_along_axis(decided_all, at, axis=1)
        emit(fact="logits", seed=seed, scale=scale,
             prefill_max_diff_over_scale=float(diff[:, 0].max() / scale),
             decode_max_diff_over_scale=float(diff[:, 1:].max() / scale),
             decode_diff_over_scale_by_step=[float(x) for x in
                                             diff[:, 1:].max(axis=0) / scale],
             mean_diff_over_scale=float(diff.mean() / scale),
             equal_argmax=int((got.argmax(-1) == want.argmax(-1)).sum()),
             rows=int(diff.size),
             served=gap_facts(ulps_of(want, got.argmax(-1))),
             rows_decided=int(decided.sum()),
             decided_max_diff_over_scale=float(diff[decided].max() / scale),
             decided_mean_diff_over_scale=float(diff[decided].mean() / scale),
             undecided_max_diff_over_scale=float(
                 diff[~decided].max() / scale) if (~decided).any() else None,
             served_decided=gap_facts(
                 ulps_of(want, got.argmax(-1))[decided]),
             program_s=t_program, reference_s=t_reference)
        program_worst.append(float(diff[decided].max() / scale))

        # router flips: the program's top-k set against the reference's
        full_padded = np.zeros((slots, g.seq), np.int32)
        full_padded[:, :width] = full
        prog_choices, prog_scores = program_choices(
            eng.params, [jnp.asarray(full_padded),
                         jnp.asarray(np.tile(np.arange(g.seq, dtype=np.int32),
                                             (slots, 1))),
                         jnp.asarray(valid)])
        prog_choices = np.asarray(prog_choices)
        real = valid[:, :width].astype(bool)
        lo_, hi_ = hp["held"]
        moved = np.stack([np.abs(np.asarray(p_)[:, :width] - np.asarray(r_))[real]
                          for p_, r_ in zip(prog_scores, ref_scores)])
        # the scores that decide: each token's 16 largest by the reference,
        # where the program's own routing of the token was the reference's
        # in every EARLIER layer (a token that went another way before is
        # another token by now)
        top = np.stack([np.argsort(-np.asarray(r_)[real], axis=-1)[:, :16]
                        for r_ in ref_scores])
        near = np.take_along_axis(moved, top, axis=-1)   # [layers, tokens, 16]
        same = np.stack([
            (np.sort(prog_choices[layer][:, :width], axis=-1)[real]
             == np.sort(ref_choices[layer], axis=-1)[real]).all(axis=-1)
            for layer in range(len(routers))])
        so_far = np.concatenate([np.ones_like(same[:1]),
                                 np.logical_and.accumulate(same, axis=0)[:-1]])
        near_same = near[so_far]
        emit(fact="score_perturbation", seed=seed,
             what="|program's selection score - reference's|, real tokens, "
                  "all expert layers",
             rms=float(np.sqrt((moved ** 2).mean())),
             p99=float(np.quantile(moved, 0.99)),
             p9999=float(np.quantile(moved, 0.9999)), max=float(moved.max()),
             held_rms=float(np.sqrt((moved[..., lo_:hi_] ** 2).mean())),
             held_max=float(moved[..., lo_:hi_].max()),
             near_top_rms_by_layer=[float(np.sqrt((near[l] ** 2).mean()))
                                    for l in range(len(routers))],
             near_top_same_path_rms=float(np.sqrt((near_same ** 2).mean())),
             near_top_same_path_p99=float(np.quantile(near_same, 0.99)),
             near_top_same_path_p999=float(np.quantile(near_same, 0.999)),
             near_top_same_path_max=float(near_same.max()),
             tokens_decided_share=float(decided_all[real].mean()))
        del prog_scores, moved
        flips, swapped, held_flips = [], [], []
        lo, hi = hp["held"]
        for layer in range(len(routers)):
            a = np.sort(prog_choices[layer][:, :width], axis=-1)[real]
            b = np.sort(ref_choices[layer], axis=-1)[real]
            differs = (a != b).any(axis=-1)
            flips.append(float(differs.mean()))
            held_flips.append(float(np.mean([
                any(lo <= e < hi for e in set(x) ^ set(y))
                for x, y in zip(a, b)])))
            swapped.append(float(np.mean([len(set(x) - set(y))
                                          for x, y in zip(a[differs], b[differs])]))
                           if differs.any() else 0.0)
        emit(fact="router_flips", seed=seed, tokens=int(real.sum()),
             share_of_tokens_by_layer=flips,
             share_of_tokens_whose_held_experts_differ_by_layer=held_flips,
             held_flips_where_decided=int(sum(
                 (decided_all[real] & np.asarray([
                     any(lo <= e < hi for e in set(x) ^ set(y))
                     for x, y in zip(
                         np.sort(prog_choices[layer][:, :width], axis=-1)[real],
                         np.sort(ref_choices[layer], axis=-1)[real])])).sum()
                 for layer in range(len(routers)))),
             experts_swapped_where_it_differs=swapped)

        # the reference at the nearest precision below bf16
        low_rows, _, _ = reference_rows(
            ref_params, cast=jax.jit(lambda w: round_to_fp8(
                jnp.asarray(w, jnp.float32))))
        low_diff = np.abs(low_rows - want).max(axis=-1)
        emit(fact="fp8_reference", seed=seed,
             max_diff_over_scale=float(low_diff.max() / scale),
             mean_diff_over_scale=float(low_diff.mean() / scale),
             served=gap_facts(ulps_of(want, low_rows.argmax(-1))))
        low_least.append(float(low_diff.max() / scale))
        del low_rows
    holds = max(program_worst) <= args.tolerance < min(low_least)
    emit(holds=holds, tolerance_over_scale=args.tolerance,
         program_max_diff_over_scale=max(program_worst),
         fp8_max_diff_over_scale=min(low_least))
    return 0 if holds else 1


if __name__ == "__main__":
    sys.exit(main())
