"""The timed programs' own logits against the reference's full forward pass,
for a cell of the jamba family: the comparison that the cell's served-token
rule does not make (ISSUE 62, `correct`).

    python benchmarks/logits_check_jamba.py --seeds 6200000273,2147483659

Per seed, at the published widths and the timed lengths: --rows prompts
(LENGTH_SHARES of the longest: the first ends ON a chunk's edge, the second
INSIDE a chunk) go in by the chunk program the scheduler runs
(engine.prefill_chunk: `[1, 2048]` blocks, every Mamba layer started from its
slot's state and every attention layer over its slot's pages), then --steps
decode steps of all of them (engine.decode_step), greedy; the reference
(harness/reference_jamba.py: the whole sequence at once, the recurrence a
position at a time, no cache, no chunks, no kernels) runs a row and a layer at
a time over prompt + generated tokens, its head a block of the vocabulary at
a time.
- `logits`: mean and max |program - reference| over the logits' scale (the
  reference's largest |logit|) over every decode step's row, each row apart,
  and the served tokens' gaps under the reference's maximum in bf16 ulps of
  each row's own scale (the cell's rule: families/jamba.py);
- three WRONG references through the same comparison, each against the sound
  one: `bf16_state_reference` (the recurrence's state rounded to bfloat16 at
  every position: a program that kept S in the compute type),
  `bf16_decay_reference` (exp(dt A) rounded to bfloat16: a program that made
  the decay in the compute type) and `fp8_reference` (control.round_to_fp8 on
  every matrix: the nearest precision below the configuration's bf16);
- `scan` (with --scan): ONE layer's selective scan at the served shapes
  (`[2048, 5120]`, N 16, bf16 operands) from a state that is not zeros, the
  kernel against the float32 recurrence a position at a time, `y` over its
  scale and the last state over its; and the same recurrence with a bf16
  state and with a bf16 decay against the sound one.
Last line {"holds": ...}: every program reading of the MEAN logits distance
within TOLERANCE and the fp8 reference's outside it (the two bf16 recurrences
are reported there: at the logits the bf16 program's own rounding of every
activation is of their size, so they are judged where they act); the served
tokens' worst gap within the cell's limit; with --scan the program's scan
within SCAN_TOLERANCE of the float32 recurrence on the last state and both
bf16 recurrences outside it; exit 0 only then.

Needs the cell's chips like run.py; not part of a check.
"""

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
REHEARSAL = "rehearsal_jamba.json"   # this family's tiny cells
for p in (str(BENCH_DIR), str(BENCH_DIR.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.ops.registry import STATS_KEY
from flexflow_tpu.serving import (compile_serving, valid_prompt_inputs,
                                  valid_step_inputs)
from control import round_to_fp8
from families import family_of
from harness import manifest as mf
from harness import reference_jamba as reference

# The limits, at the published widths in bf16 (my chip run, PR 62, call 2,
# seed 6200000273, two rows of 16384 and 11468 prompt tokens and 32 steps;
# PERF.md, Findings PR 62, has every reading), each the geometric middle of
# the sound program's largest reading and the least wrong reference's. On the
# mean logits distance, of the logits' scale: the program 0.0154 and 0.0165 a
# row, fp8 weights 0.171 (a bf16 state 0.0179: the program's own size, which
# is why it is judged by the scan; a bf16 decay 0.467). On the last state of
# one layer's scan over 2048 positions from a state that is not zeros, of the
# state's scale: the program 0.00021, a bf16 decay 0.77, a bf16 state 1.39
# (the state takes in dt B u of 1e-3 a position and holds it for up to 1000:
# bf16 swallows the increments).
TOLERANCE = 0.053
SCAN_TOLERANCE = 0.013
# prompt lengths of the rows, as shares of the longest prompt the cell sends:
# 16384 ends on a chunk's edge, 11468 inside its sixth chunk
LENGTH_SHARES = [1.0, 0.7, 0.5, 0.25]
VARIANTS = {"bf16_state_reference": {"bf16_state": True},
            "bf16_decay_reference": {"bf16_decay": True},
            "fp8_reference": {}}


def emit(**kw):
    print(json.dumps(kw), flush=True)


def ulps_of(want, tokens):
    gap = want.max(-1) - np.take_along_axis(want, tokens[..., None],
                                            axis=-1)[..., 0]
    return gap / (np.abs(want).max(-1) * 2.0 ** -8)


@jax.jit
def _head_block(x, block):
    with jax.default_matmul_precision("highest"):
        return x @ block.astype(jnp.float32)


def scan_check(seed: int) -> dict:
    """One layer's scan at the served shapes from a state that is not zeros:
    the program's (the form `scan_path` says) against the float32 recurrence
    a position at a time, and that recurrence in two lower precisions."""
    from flexflow_tpu.ops import mamba_ops

    b, length, c, n = 1, 2048, 5120, 16
    rng = np.random.default_rng(seed)

    def normal(shape, scale=1.0, dt=jnp.float32):
        return jnp.asarray(rng.normal(0, scale, shape), dt)

    dt0 = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), c))
    bf = jnp.bfloat16
    u, dt_raw, z = (normal((b, length, c), s, bf) for s in (1.0, 0.5, 1.0))
    bm, cm = normal((b, length, n)), normal((b, length, n))
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None],
                          (n, c))
    d_skip = jnp.ones((c,))
    bias = jnp.asarray(dt0 + np.log(-np.expm1(-dt0)), jnp.float32)
    s0 = normal((b, n, c))
    path = mamba_ops.scan_path(c, n, bf)
    got_y, got_s = jax.jit(lambda *t: mamba_ops.selective_scan(
        *t, jnp.ones((b, length), bool), path))(
        u, dt_raw, z, bm, cm, a, d_skip, bias, s0)

    def literal(state_bits, decay_bits):
        def low(x, on):
            return jax.lax.reduce_precision(x, 8, 7) if on else x

        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + bias)
        uf = u.astype(jnp.float32)

        def step(s, t):
            dt_t, u_t, b_t, c_t = t
            s = low(low(jnp.exp(dt_t[:, None, :] * a), decay_bits) * s
                    + (dt_t * u_t)[:, None, :] * b_t[:, :, None], state_bits)
            return s, jnp.sum(s * c_t[:, :, None], axis=1)

        last, y = jax.lax.scan(step, s0, tuple(
            jnp.moveaxis(t, 1, 0) for t in (dt, uf, bm, cm)))
        return (jnp.moveaxis(y, 0, 1) + d_skip * uf) \
            * jax.nn.silu(z.astype(jnp.float32)), last

    want_y, want_s = jax.jit(lambda: literal(False, False))()
    y_scale, s_scale = float(jnp.abs(want_y).max()), float(jnp.abs(want_s).max())

    def off(y, s):
        return {"y_mean": float(jnp.abs(y.astype(jnp.float32) - want_y).mean()
                                / y_scale),
                "y_max": float(jnp.abs(y.astype(jnp.float32) - want_y).max()
                               / y_scale),
                "state_max": float(jnp.abs(s - want_s).max() / s_scale)}

    # y leaves the program in bf16: the sound recurrence rounded the same way
    # is the floor of any reading of y
    facts = {"path": path, "program": off(got_y, got_s),
             "bf16_y_alone": off(want_y.astype(bf), want_s),
             "bf16_state": off(*jax.jit(lambda: literal(True, False))()),
             "bf16_decay": off(*jax.jit(lambda: literal(False, True))())}
    emit(fact="scan", seed=seed, **facts)
    return facts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="AI21-Jamba2-3B.serve-longprompt")
    ap.add_argument("--seeds", default="6200000273,2147483659")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--tolerance", type=float, default=TOLERANCE)
    ap.add_argument("--scan", action="store_true")
    ap.add_argument("--scan-tolerance", type=float, default=SCAN_TOLERANCE)
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
    lengths[0] -= lengths[0] % chunk        # the first ends on a chunk's edge
    width = -(-(max(lengths) + steps) // 256) * 256
    emit(fact="device", kind=jax.devices()[0].device_kind, vocab=g.vocab,
         seq=g.seq, slots=slots, state_kinds=eng.kv.state_kinds, chunk=chunk,
         lengths=lengths, ends_on_a_chunks_edge=[n % chunk == 0
                                                 for n in lengths],
         reference_width=width)
    fp8 = jax.jit(lambda w: round_to_fp8(jnp.asarray(w, jnp.float32)))

    program, wrong, scans = [], {n: [] for n in VARIANTS}, []
    held = {n: {leaf: (x.shape, x.dtype) for leaf, x in eng.kv.state[n].items()}
            for n in eng.attn_layers + list(eng.kv.recurrent)}
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        eng.params = ref_params = None          # one set of weights at a time
        eng.init(seed=seed % (2 ** 31 - 1))
        # pools and state are given up below, once the program has spoken:
        # the references' float32 layers take their room
        eng.kv.state.update({n: {leaf: jnp.zeros(*sd) for leaf, sd in
                                 leaves.items()} for n, leaves in held.items()})
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
                    eng.params, kv.state, valid_prompt_inputs(ids, n, at),
                    kv.prefill_row(r)[None], at, n, np.array([r], np.int32))
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
                valid_step_inputs(jnp.asarray(nxt), state))
            state.pop(STATS_KEY)
            rows.append(np.asarray(
                step_logits[:len(prompts), 0].astype(jnp.float32)))
            toks.append(rows[-1].argmax(-1).astype(np.int32))
        kv.adopt(state)
        kv.sync_after(steps)
        del state, step_logits
        for n in held:
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

        def reference_rows(cast=lambda w: w, hp=hp):
            """[rows, steps + 1, vocab] on the host; `cast` is applied to
            one layer's weights, and one block of the head, at a time."""
            out = np.empty((len(prompts), steps + 1,
                            ref_params["head"].shape[1]), np.float32)
            for r in range(len(prompts)):
                h = reference._embed(cast(ref_params["embed"]), full[r:r + 1])
                for layer in ref_params["layers"]:
                    h = reference.layer_step(
                        h, jax.tree_util.tree_map(cast, layer), hp)
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

        t0 = time.perf_counter()
        want = reference_rows()
        t_reference = time.perf_counter() - t0
        got = np.stack(rows, axis=1)                  # [rows, steps, vocab]
        scale = float(np.abs(want).max())
        diff = np.abs(got - want[:, 1:]).max(axis=-1)          # [rows, steps]
        served = ulps_of(want, np.stack(toks, axis=1))
        emit(fact="logits", seed=seed, scale=scale,
             decode_max_diff_over_scale=float(diff.max() / scale),
             mean_diff_over_scale=float(diff.mean() / scale),
             mean_diff_over_scale_by_row=np.round(
                 diff.mean(axis=1) / scale, 5).tolist(),
             equal_argmax=int((got.argmax(-1) == want[:, 1:].argmax(-1)).sum()),
             rows=int(diff.size),
             served={"tokens": int(served.size),
                     "not_argmax": int((served > 0).sum()),
                     "gap_ulps_max": float(served.max())},
             first_token_gap_ulps=np.round(served[:, 0], 2).tolist(),
             program_s=t_program, reference_s=t_reference)
        program.append((float(diff.mean() / scale), float(served.max())))
        del got
        for name, switches in VARIANTS.items():
            t0 = time.perf_counter()
            cast = fp8 if name == "fp8_reference" else (lambda w: w)
            rows_w = reference_rows(cast=cast, hp=dict(hp, **switches))
            d = np.abs(rows_w - want).max(axis=-1)[:, 1:]
            emit(fact=name, seed=seed,
                 max_diff_over_scale=float(d.max() / scale),
                 mean_diff_over_scale=float(d.mean() / scale),
                 served_gap_ulps_max=float(ulps_of(
                     want, rows_w.argmax(-1)).max()),
                 seconds=time.perf_counter() - t0)
            wrong[name].append(float(d.mean() / scale))
            del rows_w
        if args.scan:
            scans.append(scan_check(seed))
    limit = 8.0 * family.GAP_UNIT_ROW_SCALES
    least = {name: min(seen) for name, seen in wrong.items()}
    worst = max(m for m, _ in program)
    # at the logits the bf16 program's own rounding (every activation) is of
    # the size of a bf16 state's or decay's: those two are judged where they
    # act, by the scan check; here they are reported
    holds = worst <= args.tolerance < least["fp8_reference"] \
        and max(u for _, u in program) <= limit
    out = {}
    if scans:
        out = {"scan_tolerance": args.scan_tolerance,
               "scan_program_state": max(s["program"]["state_max"]
                                         for s in scans),
               "scan_wrong_state": {k: min(s[k]["state_max"] for s in scans)
                                    for k in ("bf16_state", "bf16_decay")}}
        holds = holds and out["scan_program_state"] <= args.scan_tolerance \
            and all(v > args.scan_tolerance
                    for v in out["scan_wrong_state"].values())
    emit(holds=holds, tolerance_over_scale=args.tolerance,
         program_mean_diff_over_scale=worst,
         wrong_mean_diff_over_scale=least, served_gap_limit_ulps=limit,
         program_served_gap_ulps=max(u for _, u in program), **out)
    return 0 if holds else 1


if __name__ == "__main__":
    sys.exit(main())
