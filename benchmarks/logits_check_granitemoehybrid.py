"""The timed programs' own logits against the reference's full forward pass,
for a cell of the granitemoehybrid family: the comparison that the cell's
served-token rule does not make (ISSUE 28, point 7(c)).

    python benchmarks/logits_check_granitemoehybrid.py --seeds 3200000269,2147483659

Prefill of one padded wave (engine.prefill, prompts of 16-512 tokens), then
--steps decode steps through the cache (engine.decode_step), greedy; the
reference runs layer by layer over prompt + generated tokens. Per seed:
- `logits`: max |program - reference| over the logits' scale (the
  reference's largest |logit|) for the wave's last-position rows and for
  every decode step, and the served tokens' gaps under the reference's
  maximum in bf16 ulps of each row's own scale (the cell's rule allows 16);
- `router_flips`: how often the program's top-k expert set (from the inputs
  that the program's own lowering hands its expert layers, bf16) differs from
  the reference's (f32);
- `fp8_reference`: the same distances for the reference computed with fp8
  weights (control.round_to_fp8: the nearest precision below the
  configuration's bf16).
Last line {"holds": ...}: every program reading within --tolerance (0.10 of
the scale at the published widths; PERF.md has the two readings it lies
between) and every fp8 reading outside it; exit 0 only then. Needs the
cell's chips like run.py; not part of a check.
"""

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
REHEARSAL = "rehearsal_granitemoehybrid.json"   # this family's tiny cells
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
from flexflow_tpu.serving import (compile_serving, valid_prompt_inputs,
                                  valid_step_inputs)
from control import round_to_fp8
from families import family_of
from harness import manifest as mf
from harness import reference_granitemoehybrid as reference

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
    ap.add_argument("--workload", default="granite-4.0-h-small.serve-chat")
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

    @jax.jit
    def program_choices(params, inputs):
        xs, _ = routed_fwd(params, {}, inputs, False, jax.random.PRNGKey(0))
        out = []
        for name, x in zip(routers, xs):
            scores = jnp.dot(x.astype(jnp.float32),
                             params[name]["router"].astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            out.append(jax.lax.top_k(scores, hp["top_k"])[1])
        return jnp.stack(out)                     # [layers, slots, seq, k]

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
                                       valid_prompt_inputs(ids, lengths))
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
                eng.params, state, valid_step_inputs(nxt, state))
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

        def reference_rows(params, cast=lambda w: w):
            """`cast` is applied to one layer's weights at a time."""
            h = reference._embed(cast(params["embed"]), full,
                                 hp["embedding_multiplier"])
            choices = []
            for layer in params["layers"]:
                h, e = reference.layer_step(
                    h, {k: cast(v) for k, v in layer.items()}, hp, choices=True)
                choices.append(np.asarray(e))
            picked = jnp.take_along_axis(h, jnp.asarray(at)[..., None], axis=1)
            out = reference._head(picked, params["norm_f"], cast(params["head"]),
                                  hp["eps"], hp["logits_scaling"])
            return np.asarray(out), np.stack(choices)

        t0 = time.perf_counter()
        want, ref_choices = reference_rows(ref_params)   # [slots, 33, vocab]
        t_reference = time.perf_counter() - t0
        got = np.stack(rows, axis=1)                      # [slots, 33, vocab]
        scale = float(np.abs(want).max())
        diff = np.abs(got - want).max(axis=-1)            # [slots, steps + 1]
        emit(fact="logits", seed=seed, scale=scale,
             prefill_max_diff_over_scale=float(diff[:, 0].max() / scale),
             decode_max_diff_over_scale=float(diff[:, 1:].max() / scale),
             decode_diff_over_scale_by_step=[float(x) for x in
                                             diff[:, 1:].max(axis=0) / scale],
             mean_diff_over_scale=float(diff.mean() / scale),
             equal_argmax=int((got.argmax(-1) == want.argmax(-1)).sum()),
             rows=int(diff.size),
             served=gap_facts(ulps_of(want, got.argmax(-1))),
             program_s=t_program, reference_s=t_reference)
        program_worst.append(float(diff.max() / scale))

        # router flips: the program's top-k set against the reference's
        full_padded = np.zeros((slots, g.seq), np.int32)
        full_padded[:, :width] = full
        prog_choices = np.asarray(program_choices(
            eng.params, [jnp.asarray(full_padded), jnp.asarray(valid)]))
        real = valid[:, :width].astype(bool)
        flips, swapped = [], []
        for layer in range(len(routers)):
            a = np.sort(prog_choices[layer][:, :width], axis=-1)[real]
            b = np.sort(ref_choices[layer], axis=-1)[real]
            differs = (a != b).any(axis=-1)
            flips.append(float(differs.mean()))
            swapped.append(float(np.mean([len(set(x) - set(y))
                                          for x, y in zip(a[differs], b[differs])]))
                           if differs.any() else 0.0)
        emit(fact="router_flips", seed=seed, tokens=int(real.sum()),
             share_of_tokens_by_layer=flips,
             experts_swapped_where_it_differs=swapped)

        # the reference at the nearest precision below bf16
        low_rows, _ = reference_rows(
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
