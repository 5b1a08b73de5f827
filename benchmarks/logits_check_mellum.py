"""The timed programs' own logits against the reference's full forward pass,
for a cell of the mellum family: the comparison that the cell's served-token
rule does not make (ISSUE 56, `correct`).

    python benchmarks/logits_check_mellum.py --seeds 5600000273,2147483659

Per seed, at the published widths and the timed lengths: --rows prompts
(LENGTH_SHARES of the longest) go into their slots' pages and rings by the
chunk program the scheduler runs (engine.prefill_chunk: `[1, 2048]` blocks;
a windowed layer's ring of 3088 positions is lapped five times by the longest
prompt), then --steps decode steps of all of them through both pools
(engine.decode_step), greedy; the reference (harness/reference_mellum.py: the
whole sequence at once, no cache, no chunks, no ring) runs a row and a layer
at a time over prompt + generated tokens, its head a block of the vocabulary
at a time.
- `logits`: mean and max |program - reference| over the logits' scale (the
  reference's largest |logit|) over every decode step's row, and the served
  tokens' gaps under the reference's maximum in bf16 ulps of each row's own
  scale, each token alone (`served`: a token that is not the reference's
  argmax is an undecided tie or a moved routing choice; counted apart as
  `not_argmax`) and as the cell's rule judges it (over its neighbourhood:
  families/mellum.py);
- `attention`: ONE layer of each kind's attention output before the residual
  (layer 0's, windowed, and the first full layer's, W_o o over the first
  PROBE_POSITIONS embedded tokens of a prompt), the program's sequence form in the configuration's bf16 through
  the program's own ops against the reference's, as the mean |difference|
  over that output's own scale. Under random weights the experts and the
  head lie between attention and the logits: here a wrong window or wrong
  tables read tens of times the sound program's distance;
- three WRONG references through both comparisons, each against the sound
  one: `fp8_reference` (control.round_to_fp8 on every matrix: the nearest
  precision below the configuration's bf16), `window_512_reference` (half
  the window: a program that hid the older half of every window) and
  `no_yarn_reference` (plain tables and no attention factor on the full
  layers).
Last line {"holds": ...}: every program reading of the MEAN logits distance
within TOLERANCE and every wrong reading outside it; the program's attention
readings within ATTENTION_TOLERANCE, every wrong reference's reading of a
layer it changes outside it, and each mask variant's at least ten times the
program's; the served tokens' worst
neighbourhood gap within the cell's limit; exit 0 only then.

Needs the cell's chips like run.py; not part of a check.
"""

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
REHEARSAL = "rehearsal_mellum.json"   # this family's tiny cells
for p in (str(BENCH_DIR), str(BENCH_DIR.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.compiler.lowering import build_forward
from flexflow_tpu.ops.registry import STATS_KEY
from flexflow_tpu.parallel.default_strategy import data_parallel_strategy
from flexflow_tpu.serving import (compile_serving,
                                  positions_valid_prompt_inputs,
                                  positions_valid_step_inputs)
from control import round_to_fp8
from families import family_of
from families.nemotron_h import neighbourhood_gaps
from harness import manifest as mf
from harness import reference_mellum as reference

# The limits, at the published widths in bf16 (my chip run, PR 56, call 2,
# two seeds, three rows of 16384 / 8192 / 11468 prompt tokens and 32 steps;
# PERF.md, Findings PR 56, has every reading), each the geometric middle of
# the sound program's largest reading and the least wrong reference's. On the
# mean logits distance, of the logits' scale: the program 0.0097 and 0.0104,
# fp8 weights 0.0639 and 0.0746, plain tables on the full layers 0.123 and
# 0.149, half the window 0.448 and 0.510. On one layer's attention output
# over a prompt's first 4096 positions, of that output's scale: the program
# 0.00009-0.00011 (windowed) and 0.00016-0.00022 (full); fp8 weights 0.0009-
# 0.0011 and 0.0019-0.0027 (10-12x the program's), half the window 0.0093-
# 0.0110 (96-106x), plain tables 0.0155-0.0212 (95-97x).
TOLERANCE = 0.026
ATTENTION_TOLERANCE = 0.00045
# prompt lengths of the rows, as shares of the longest prompt the cell sends
LENGTH_SHARES = [1.0, 0.5, 0.7, 0.25]
# positions of row 0 the attention probes run over: the program's sequence
# form of a windowed layer is the masked XLA path (kernels/flash_attention.py
# knows no window), whose bf16 scores of 32 heads are 1 GB at 4096 positions
# and 17.7 GB at 16 640; four windows of 1024 and half YaRN's original range
PROBE_POSITIONS = 4096
VARIANTS = ("fp8_reference", "window_512_reference", "no_yarn_reference")
# the layer whose attention output a mask variant changes
PROBED = {"window_512_reference": "sliding_attention",
          "no_yarn_reference": "full_attention"}


def emit(**kw):
    print(json.dumps(kw), flush=True)


def ulps_of(want, tokens):
    gap = want.max(-1) - np.take_along_axis(want, tokens[..., None],
                                            axis=-1)[..., 0]
    return gap / (np.abs(want).max(-1) * 2.0 ** -8)


def gap_facts(ulps) -> dict:
    return {"tokens": int(ulps.size), "not_argmax": int((ulps > 0).sum()),
            "gap_ulps_p99": float(np.quantile(ulps, 0.99)),
            "gap_ulps_max": float(ulps.max())}


@jax.jit
def _head_block(x, block):
    with jax.default_matmul_precision("highest"):
        return x @ block.astype(jnp.float32)


def attention_probe(cfg, family, sysm, width, layer: int):
    """The program's own embedding, layer `layer`'s first norm and attention
    as a graph of their own over the embedded tokens, `[1, width]`."""
    from flexflow_tpu.compiler.compile import resolve_machine
    from flexflow_tpu.dtype import DataType

    g = family.program_config(cfg)
    fc = FFConfig(batch_size=1, seed=1, strategy_cache=False,
                  log_level="warning", **sysm["ffconfig"])
    m = FFModel(fc)
    dtype = DataType.from_any(g.dtype)
    full = g.layer_types[layer] == "full_attention"
    ids = m.create_tensor([1, width], DataType.INT32, name="input_ids")
    pos = m.create_tensor([1, width], DataType.INT32, name="positions")
    t = m.embedding(ids, g.vocab, g.d_model, dtype=dtype, name="embed")
    x = m.rms_norm(t, eps=g.eps, name=f"l{layer}_norm_op")
    y = m.multihead_attention(
        x, x, x, g.heads * g.head_dim, g.heads, bias=False, causal=True,
        num_kv_heads=g.kv_heads, positions=pos, rope_theta=g.rope_theta,
        qk_norm=g.eps, out_dim=g.d_model, window=0 if full else g.window,
        rope_scaling=g.full_rope_scaling if full else None,
        name=f"l{layer}_attn")
    fwd = build_forward(m.layers, m.input_tensors, [y], None,
                        data_parallel_strategy(m, resolve_machine(fc)),
                        compute_dtype=fc.compute_dtype)
    names = [l.name for l in m.layers]
    return jax.jit(lambda params, a, b: fwd(
        {n: params[n] for n in names}, {}, [a, b], False,
        jax.random.PRNGKey(0))[0][0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload",
                    default="Mellum2-12B-A2.5B-Instruct.serve-longprompt")
    ap.add_argument("--seeds", default="5600000273,2147483659")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--rows", type=int, default=3)
    ap.add_argument("--tolerance", type=float, default=TOLERANCE)
    ap.add_argument("--attention-tolerance", type=float,
                    default=ATTENTION_TOLERANCE)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    steps = args.steps
    manifest = mf.load_manifest(BENCH_DIR / REHEARSAL if args.rehearsal
                                else mf.MANIFEST)
    cell = mf.load_cell(manifest, args.workload)
    cfg, sysm, tr = cell.config, cell.system, cell.traffic
    family = family_of(cfg)
    slots = int(sysm["max_batch_slots"])
    model = FFModel(FFConfig(batch_size=slots, seed=1, strategy_cache=False,
                             log_level="warning", **sysm["ffconfig"]))
    g = family.build(model, cfg, slots)
    eng = compile_serving(model, max_batch_slots=slots,
                          max_decode_len=int(sysm["max_decode_len"]),
                          kv_page_size=int(sysm["kv_page_size"]))
    hp = family.hyper(cfg)
    chunk = eng.chunk_tokens
    longest = min(int(tr["prompt_len"]["max"]), g.seq - steps - 1)
    lengths = [max(2, int(longest * share))
               for share in LENGTH_SHARES[:min(args.rows, slots)]]
    width = -(-(max(lengths) + steps) // 256) * 256
    ring = eng.kv_spec.window_pages * eng.kv_spec.page_size
    emit(fact="device", kind=jax.devices()[0].device_kind, vocab=g.vocab,
         seq=g.seq, slots=slots, state_kinds=eng.kv.state_kinds, chunk=chunk,
         lengths=lengths, reference_width=width, ring_positions=ring,
         ring_laps=[round(n / ring, 2) for n in lengths])
    fp8 = jax.jit(lambda w: round_to_fp8(jnp.asarray(w, jnp.float32)))
    # one layer of each kind: (layer index, the program's graph of it)
    probed = min(width, PROBE_POSITIONS)
    probes = {kind: (g.layer_types.index(kind), attention_probe(
        cfg, family, sysm, probed, g.layer_types.index(kind)))
        for kind in ("sliding_attention", "full_attention")}
    switches = dict(zip(VARIANTS, ({}, {"window": hp["window"] // 2},
                                   {"yarn_on": False})))

    program, attention, wrong = [], [], {n: [] for n in VARIANTS}
    wrong_attention = {n: [] for n in VARIANTS}
    pools = {n: {leaf: (x.shape, x.dtype) for leaf, x in eng.kv.state[n].items()}
             for n in eng.attn_layers}
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        eng.params = ref_params = None          # one set of weights at a time
        eng.init(seed=seed % (2 ** 31 - 1))
        # the pools are given up below, once the program has spoken: the
        # references' float32 layers take their room
        eng.kv.state.update({n: {leaf: jnp.zeros(*sd) for leaf, sd in
                                 leaves.items()} for n, leaves in pools.items()})
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(1, g.vocab, n).astype(np.int32)
                   for n in lengths]
        kv = eng.kv
        for r in range(slots):
            if kv._active[r] or r in kv._prefilling:
                kv.evict(r)
        first = []
        for r, prompt in enumerate(prompts):
            kv.admit(r, len(prompt), len(prompt) + steps + 8, True)
            kv.push()
            for done in range(0, len(prompt), chunk):
                part = prompt[done:done + chunk]
                ids = np.zeros((1, chunk), np.int32)
                ids[0, :len(part)] = part
                n = np.array([len(part)], np.int32)
                at = np.array([done], np.int32)
                tok, state = eng.prefill_chunk(
                    eng.params, kv.state,
                    positions_valid_prompt_inputs(ids, n, at),
                    kv.prefill_row(r)[None], at, n)
                state.pop(STATS_KEY, None)
                kv.adopt(state)
            kv.activate(r, len(prompt))
            first.append(int(np.asarray(tok)[0]))
        kv.push()
        toks = [np.asarray(first, np.int32)]
        rows = []                                 # [step][rows, vocab]
        state = kv.state
        for _ in range(steps):
            nxt = np.zeros((slots, 1), np.int32)
            nxt[:len(prompts), 0] = toks[-1]
            step_logits, state = eng.decode_step(
                eng.params, state,
                positions_valid_step_inputs(jnp.asarray(nxt), state))
            state.pop(STATS_KEY)
            rows.append(np.asarray(
                step_logits[:len(prompts), 0].astype(jnp.float32)))
            toks.append(rows[-1].argmax(-1).astype(np.int32))
        kv.adopt(state)
        kv.sync_after(steps)
        del state, step_logits
        for n in pools:
            kv.state[n] = None
        t_program = time.perf_counter() - t0

        # the reference over prompt + generated tokens, a row at a time
        full = np.zeros((len(prompts), width), np.int32)
        for r, prompt in enumerate(prompts):
            full[r, :len(prompt)] = prompt
            full[r, len(prompt):len(prompt) + steps] = \
                [t[r] for t in toks[:steps]]
        at = np.asarray(lengths)[:, None] - 1 + np.arange(steps + 1)[None, :]
        ref_params = family.reference_params(eng.params, cfg)
        positions = np.tile(np.arange(width, dtype=np.int32),
                            (len(prompts), 1))

        def cast_tree(cast, tree):
            return jax.tree_util.tree_map(cast, tree)

        def reference_rows(cast=lambda w: w, hp=hp):
            """[rows, steps + 1, vocab] on the host; `cast` is applied to
            one layer's weights, and one block of the head, at a time."""
            out = np.empty((len(prompts), steps + 1,
                            ref_params["head"].shape[1]), np.float32)
            for r in range(len(prompts)):
                h = reference._embed(cast(ref_params["embed"]),
                                     full[r:r + 1])
                for layer, kind in zip(ref_params["layers"],
                                       hp["layer_types"]):
                    h = reference.layer_step(h, positions[r:r + 1],
                                             cast_tree(cast, layer), hp, kind)
                x = reference.rms(
                    jnp.take_along_axis(h, jnp.asarray(at[r:r + 1])[..., None],
                                        axis=1),
                    reference._f32(ref_params["norm_f"]), hp["eps"])
                head = ref_params["head"]
                for lo in range(0, head.shape[1], reference.VOCAB_BLOCK):
                    blk = cast(head[:, lo:lo + reference.VOCAB_BLOCK])
                    out[r, :, lo:lo + blk.shape[1]] = np.asarray(
                        _head_block(x, blk))[0]
            return out

        def reference_attention(layer, cast=lambda w: w, hp=hp):
            """Layer `layer`'s attention output over row 0's EMBEDDED tokens
            (what the probe's graph computes), [width, d]."""
            op = {k: v for k, v in cast_tree(
                cast, ref_params["layers"][layer]).items()
                if k not in reference.FEED_FORWARD_KEYS}
            h = reference._embed(cast(ref_params["embed"]),
                                 full[:1, :probed])
            return np.asarray(reference._attention_step(
                h, positions[:1, :probed], op, reference._hp_key(hp),
                hp["layer_types"][layer], False))[0]

        t0 = time.perf_counter()
        want = reference_rows()
        t_reference = time.perf_counter() - t0
        got = np.stack(rows, axis=1)                  # [rows, steps, vocab]
        scale = float(np.abs(want).max())
        diff = np.abs(got - want[:, 1:]).max(axis=-1)          # [rows, steps]
        served = ulps_of(want, np.stack(toks, axis=1))
        judged = np.asarray(neighbourhood_gaps(jnp.asarray(served),
                                               family.GAP_WINDOW))
        emit(fact="logits", seed=seed, scale=scale,
             decode_max_diff_over_scale=float(diff.max() / scale),
             mean_diff_over_scale=float(diff.mean() / scale),
             mean_diff_over_scale_by_row=np.round(
                 diff.mean(axis=1) / scale, 5).tolist(),
             equal_argmax=int((got.argmax(-1) == want[:, 1:].argmax(-1)).sum()),
             rows=int(diff.size), served=gap_facts(served),
             served_by_the_rule=gap_facts(judged),
             first_token_gap_ulps=np.round(served[:, 0], 2).tolist(),
             program_s=t_program, reference_s=t_reference)
        program.append((float(diff.mean() / scale), float(judged.max())))
        del got

        # one layer of each kind's attention output before the residual, row
        # 0, over the real positions
        real = min(lengths[0] + steps, probed)
        sound, a_scale, a_program = {}, {}, {}
        for kind, (layer, probe) in probes.items():
            t0 = time.perf_counter()
            mine = np.asarray(probe(eng.params, full[:1, :probed],
                                    positions[:1, :probed])
                              .astype(jnp.float32))[0, :real]
            sound[kind] = reference_attention(layer)[:real]
            a_scale[kind] = float(np.abs(sound[kind]).max())
            a_program[kind] = float(np.abs(mine - sound[kind]).mean()
                                    / a_scale[kind])
            emit(fact="attention", seed=seed, kind=kind, layer=layer,
                 positions=real, scale=a_scale[kind],
                 mean_diff_over_scale=a_program[kind],
                 max_diff_over_scale=float(np.abs(mine - sound[kind]).max()
                                           / a_scale[kind]),
                 seconds=time.perf_counter() - t0)
            attention.append(a_program[kind])
            del mine

        for name, sw in switches.items():
            t0 = time.perf_counter()
            cast = fp8 if name == "fp8_reference" else (lambda w: w)
            rows_w = reference_rows(cast=cast, hp=dict(hp, **sw))
            d = np.abs(rows_w - want).max(axis=-1)[:, 1:]
            facts = {}
            for kind, (layer, _probe) in probes.items():
                att_w = reference_attention(layer, cast=cast,
                                            hp=dict(hp, **sw))[:real]
                a = float(np.abs(att_w - sound[kind]).mean() / a_scale[kind])
                facts[kind] = {"mean_diff_over_scale": a,
                               "over_program": a / a_program[kind]}
                if PROBED.get(name, kind) == kind:  # fp8 changes both kinds
                    wrong_attention[name].append((a, a / a_program[kind]))
            emit(fact=name, seed=seed,
                 max_diff_over_scale=float(d.max() / scale),
                 mean_diff_over_scale=float(d.mean() / scale),
                 attention=facts, seconds=time.perf_counter() - t0)
            wrong[name].append(float(d.mean() / scale))
            del rows_w, att_w
    limit = 8.0 * family.GAP_UNIT_ROW_SCALES
    least = {name: min(seen) for name, seen in wrong.items()}
    least_attention = {n: min(a for a, _ in seen)
                       for n, seen in wrong_attention.items()}
    least_margin = {n: min(m for _, m in seen)
                    for n, seen in wrong_attention.items()}
    worst, worst_attention = max(m for m, _ in program), max(attention)
    holds = worst <= args.tolerance \
        and all(args.tolerance < m for m in least.values()) \
        and worst_attention <= args.attention_tolerance \
        and all(a > args.attention_tolerance
                for a in least_attention.values()) \
        and all(least_margin[n] >= 10 for n in PROBED) \
        and max(u for _, u in program) <= limit
    emit(holds=holds, tolerance_over_scale=args.tolerance,
         program_mean_diff_over_scale=worst,
         wrong_mean_diff_over_scale=least,
         attention_tolerance_over_scale=args.attention_tolerance,
         program_attention_diff_over_scale=worst_attention,
         wrong_attention_diff_over_scale=least_attention,
         attention_margin_over_program=least_margin,
         served_gap_limit_ulps=limit,
         program_served_gap_ulps=max(u for _, u in program))
    return 0 if holds else 1


if __name__ == "__main__":
    sys.exit(main())
