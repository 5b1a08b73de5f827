"""Serving cells: FFModel + the family's build -> compile_serving -> engine.init(seed)
-> ContinuousBatchingScheduler.run over an open-loop arrival schedule.
Traffic parameters: rate_rps, arrivals, prompt_len, output_len, shape_seed,
drain_limit_s, warmup_requests, trace_seconds, trace_ramp_s, parity_requests.
System settings: ffconfig, max_batch_slots, max_decode_len, kv_page_size."""

from __future__ import annotations

import dataclasses
import statistics
import time

from cells.common import NEAR_TIE_ULPS, Ctx, no_compile_in_window
from families import family_of
from harness import stats, traffic
from harness.facts import emit, peak_bytes


class TokenStream(list):
    """A request's output list, handed to the program as `Request.tokens`: it
    notes the time of the first and of the latest delivery (the scheduler
    appends the first token and extends with each decode window). So the
    end-to-end latencies are taken by the benchmark at the point where
    tokens become visible, on the scheduler's own thread, and no change to
    the program can redefine them. (A watcher thread polling the lists was
    tried first: it waits for the interpreter lock in this host-bound loop
    and saw first tokens up to 81 ms late; PERF.md, Findings.)"""

    first_at = last_at = None

    def _stamp(self):
        self.last_at = time.perf_counter()
        if self.first_at is None:
            self.first_at = self.last_at

    def append(self, token):
        super().append(token)
        self._stamp()

    def extend(self, tokens):
        super().extend(tokens)
        self._stamp()


def _requests(plain):
    from flexflow_tpu.serving import Request

    return [Request(rid=r["rid"], prompt=r["prompt"],
                    max_new_tokens=r["max_new_tokens"],
                    arrival_s=r["arrival_s"], tokens=TokenStream())
            for r in plain]


def _records(reqs, t0):
    """Plain request records for harness/stats.py. Times of the first token
    and of the end are the TokenStream's, from t0 = the start of the run; the
    due time is the schedule's; admit_s (per-layer metrics only) and the
    outcome are the program's."""
    return [{"rid": r.rid, "arrival_s": r.arrival_s, "admit_s": r.admit_s,
             "ttft_s": (None if r.tokens.first_at is None
                        else r.tokens.first_at - t0 - r.arrival_s),
             "finish_s": (None if r.tokens.last_at is None
                          else r.tokens.last_at - t0),
             "n_tokens": len(r.tokens), "outcome": r.outcome,
             "program_ttft_s": r.ttft_s} for r in reqs]


@dataclasses.dataclass
class Served:
    """The engine with what its model's family says about it."""
    eng: object
    family: object      # families/<family>.py
    config: dict        # configs/<config>.json
    vocab: int

    def scheduler(self):
        from flexflow_tpu.serving import ContinuousBatchingScheduler

        prompt_inputs, step_inputs = self.family.serving_inputs()
        return ContinuousBatchingScheduler(self.eng, self.eng.params,
                                           prompt_inputs, step_inputs,
                                           eos_id=None)


def serve_window(served, tr, seconds, seed, rate_scale=1.0):
    """One open-loop run: (scheduler, requests, request records, wall s)."""
    reqs = _requests(traffic.serve_requests(tr, seconds, seed, served.vocab,
                                            rate_scale))
    sched = served.scheduler()
    t0 = time.perf_counter()
    sched.run(reqs)
    return sched, reqs, _records(reqs, t0), time.perf_counter() - t0


def window_facts(sched, reqs, records, seconds, drain_limit_s) -> dict:
    out = stats.serve_summary(records, seconds, drain_limit_s)
    # how far the benchmark's clock lies from the program's own stamps
    out["own_minus_program_ttft_ms_max"] = max(
        (1e3 * abs(r["ttft_s"] - r["program_ttft_s"]) for r in records
         if r["ttft_s"] is not None and r["program_ttft_s"] is not None),
        default=None)
    out["decode_steps"] = sched.decode_steps
    out["prefill_waves"] = sched.prefills
    out["decode_tokens_committed"] = sum(max(0, len(r.tokens) - 1)
                                         for r in reqs)
    out["decode_slot_steps"] = sched.decode_steps * sched.slots
    if sched.step_times:
        out["decode_step_ms"] = 1e3 * statistics.median(sched.step_times)
    out["accounted"] = (len(sched.completed) + len(sched.shed)
                        + len(sched.failed)) == len(reqs)
    return out


def parity(served, reqs, seed, n_sample, pad_to):
    """Served tokens of a seeded sample of completed requests against the
    plain reference's logits over prompt + served tokens."""
    import numpy as np

    done = [r for r in reqs if r.outcome == "done" and r.tokens]
    if not done:
        return {"requests": 0, "tokens": 0, "ok": False}
    rng = np.random.default_rng([seed % (2 ** 63), 4])
    pick = [done[i] for i in rng.permutation(len(done))[:n_sample]]
    pick += pick[:1] * (n_sample - len(pick))   # one shape, one compiled program
    ids = np.zeros((len(pick), pad_to), np.int32)
    for row, r in enumerate(pick):
        seq = r.prompt + r.tokens
        ids[row, :len(seq)] = seq
    pos = np.tile(np.arange(pad_to, dtype=np.int32), (len(pick), 1))
    gap, scale = served.family.reference_token_gaps(
        served.config, served.eng.params, ids, pos)
    gap, scale = np.asarray(gap), np.asarray(scale)
    equal = n_tok = 0
    worst = 0.0
    for row, r in enumerate(pick):
        p, n = len(r.prompt), len(r.tokens)
        g = gap[row, p - 1:p - 1 + n]
        ulps = g / (np.maximum(1.0, scale[row, p - 1:p - 1 + n]) * 2.0 ** -8)
        equal += int((g == 0).sum())
        n_tok += n
        worst = max(worst, float(ulps.max()))
    return {"requests": len({r.rid for r in pick}), "rows": len(pick),
            "tokens": n_tok, "equal_argmax": equal,
            "worst_gap_bf16_ulps": worst, "tolerance_bf16_ulps": NEAR_TIE_ULPS,
            "ok": worst <= NEAR_TIE_ULPS}


def build_engine(ctx: Ctx, facts: dict) -> Served:
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.serving import compile_serving

    sysm = ctx.cell.system
    family = family_of(ctx.cell.config)
    slots = int(sysm["max_batch_slots"])
    with ctx.span("setup/search"):
        cfg = FFConfig(batch_size=slots, seed=ctx.seed32, strategy_cache=False,
                       log_level="warning", **sysm["ffconfig"])
        model = FFModel(cfg)
        gcfg = family.build(model, ctx.cell.config, slots)
        t0 = time.perf_counter()
        eng = compile_serving(model, max_batch_slots=slots,
                              max_decode_len=int(sysm["max_decode_len"]),
                              kv_page_size=int(sysm["kv_page_size"]))
        facts["search_s"] = time.perf_counter() - t0
    t_strategy = time.perf_counter()
    served = Served(eng, family, ctx.cell.config, gcfg.vocab)
    with ctx.span("setup/init"):
        eng.init(seed=ctx.seed32)
    with ctx.span("setup/warmup"):
        # one prefill wave and a few decode windows, a finish among them
        tr = ctx.cell.traffic
        warm = traffic.serve_requests(
            dict(tr, rate_rps=float(tr["warmup_requests"])), 1.0,
            ctx.seed + 1, gcfg.vocab)
        for i, r in enumerate(warm):
            r["arrival_s"] = 0.0
            r["max_new_tokens"] = 6 + 3 * (i % 3)
        served.scheduler().run(_requests(warm))
    facts["compile_s"] = time.perf_counter() - t_strategy
    return served


def run(ctx: Ctx) -> dict:
    tr = ctx.cell.traffic
    facts = {}
    served = build_engine(ctx, facts)
    eng = served.eng
    facts["setup_s"] = ctx.since_start()
    emit(fact="engine", slots=eng.slots, kv_dtype=str(eng.kv_dtype),
         prefill_strategy=eng.prefill_strategy.name,
         decode_strategy=eng.decode_strategy.name, **ctx.counter.facts())

    compiles_before = ctx.counter.requests
    with ctx.span("window"):
        sched, reqs, records, wall = serve_window(served, tr, ctx.seconds,
                                                  ctx.seed)
    clean = no_compile_in_window(ctx, compiles_before, "serve")
    facts["memory_peak_bytes"] = peak_bytes(list(eng.mesh.devices.flat))
    facts.update(window_facts(sched, reqs, records, ctx.seconds,
                              tr["drain_limit_s"]))
    emit(fact="serve_window", offered_rps=tr["rate_rps"], run_wall_s=wall,
         prompt_tokens=traffic.length_quantiles([len(r.prompt) for r in reqs]),
         output_tokens=traffic.length_quantiles(
             [r.max_new_tokens for r in reqs]),
         shed=len(sched.shed), failed_in_scheduler=len(sched.failed),
         **{k: v for k, v in facts.items()
            if k not in ("memory_peak_bytes",)})

    if ctx.trace:
        t_sec, ramp = float(tr["trace_seconds"]), float(tr["trace_ramp_s"])
        with ctx.traced():
            with ctx.span("traced_run"):
                serve_window(served, tr, t_sec, ctx.seed + 2)
        ctx.trace_window = {"anchor": "bench/traced_run", "from_s": ramp,
                            "to_s": t_sec}

    check = parity(served, reqs, ctx.seed, int(tr["parity_requests"]),
                   int(tr["prompt_len"]["max"]) + int(tr["output_len"]["max"]))
    checks = {"no_compile_in_window": clean, "token_parity": check["ok"],
              "every_request_accounted": facts["accounted"]}
    emit(fact="correctness", parity=check, checks=checks)
    facts["correct"] = all(checks.values())
    return facts
