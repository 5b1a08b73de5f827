"""ISSUE 45 — the decode loop keeps the chip fed across syncs.

In steady decode the scheduler materializes the OLDEST dispatched step
while newer ones run, dispatches the replacement, and commits afterwards;
it empties the pipeline only for a finish, an admission that can be placed,
a safe point or a fault. Pinned here, on the CPU with tiny models:

(a) token for token, the overlapped loop serves what a loop forced to drain
    every window serves (a scheduler built with the decode watchdog keeps
    the old cadence), for GPT-2 and for a model with recurrent state, paged
    K/V and routed experts, under staggered budgets, an EOS finish seen with
    steps in flight, page backpressure, an arrival that lands mid-flight
    and a permanent dispatch fault behind an overlapped sync;
(b) from the span ring: a sync without a `drain` arg has steps in flight
    behind it, every finish and every admission sees the pipeline empty
    after a sync that names its reason, nobody decodes past max-len;
(c) the decode intervals the benchmark reads (first `dispatch` of a
    `window` to the end of its `window_sync`, `readers/span_device.py`)
    hold every dispatch once, do not overlap, and their `steps` add up.
"""

import dataclasses
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "benchmarks"), str(ROOT / "tools")):
    if p not in sys.path:
        sys.path.insert(0, p)

from flexflow_tpu import FFConfig, FFModel  # noqa: E402
from flexflow_tpu import telemetry as tel  # noqa: E402
from flexflow_tpu.runtime import faults  # noqa: E402
from flexflow_tpu.runtime.resilience import RetryPolicy  # noqa: E402
from flexflow_tpu.models import (GPT2Config, GraniteHybridConfig,  # noqa: E402
                                 build_gpt2, build_granite_hybrid)
from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,  # noqa: E402
                                  compile_serving, gpt2_prompt_inputs,
                                  gpt2_step_inputs, valid_prompt_inputs,
                                  valid_step_inputs)
from flexflow_tpu.serving.scheduler import OVERLAP_DEPTH  # noqa: E402
from readers import span_device  # noqa: E402

SLOTS = 4
AHEAD = 4
FAMILIES = ("gpt2", "granite")
SCENARIOS = ("staggered", "eos", "backpressure", "arrival", "fault")
CASES = [(f, s) for f in FAMILIES for s in SCENARIOS]


@dataclasses.dataclass
class Served:
    eng: object
    vocab: int
    prompt_inputs: object
    step_inputs: object


def _build(family: str) -> Served:
    cfg = FFConfig(batch_size=SLOTS, seed=3, strategy_cache=False,
                   log_level="warning", mesh_shape={"data": 1})
    model = FFModel(cfg)
    if family == "gpt2":
        gc = GPT2Config(vocab=256, seq=32, d_model=64, heads=4, layers=1,
                        dropout=0.0)
        build_gpt2(model, gc, batch=SLOTS)
        inputs = (gpt2_prompt_inputs, gpt2_step_inputs)
    else:
        gc = GraniteHybridConfig.tiny(seq=32)
        build_granite_hybrid(model, gc, batch=SLOTS)
        inputs = (valid_prompt_inputs, valid_step_inputs)
    eng = compile_serving(model, max_batch_slots=SLOTS, max_decode_len=32,
                          kv_page_size=8)
    eng.init(seed=3)
    return Served(eng, gc.vocab, *inputs)


@pytest.fixture(scope="module")
def served():
    made = {}

    def get(family):
        if family not in made:
            made[family] = _build(family)
        return made[family]
    return get


def _requests(vocab, budgets, seed=45):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=[int(t) for t in
                                   rng.integers(1, vocab, 3 + 2 * i)],
                    max_new_tokens=new, arrival_s=0.0)
            for i, new in enumerate(budgets)]


@dataclasses.dataclass
class Leg:
    sched: ContinuousBatchingScheduler
    reqs: list
    spans: list

    @property
    def tokens(self):
        return {r.rid: list(r.tokens) for r in self.reqs}


def _serve(sv: Served, scenario: str, overlapped: bool, eos=None) -> Leg:
    """One run of a scenario. `overlapped=False` builds the scheduler with
    the decode watchdog (a budget no step reaches), which needs a safe
    point every window and so drains at the old cadence."""
    kv = sv.eng.kv
    budgets = {"staggered": (12, 7, 9, 14, 6, 10, 5),
               "eos": (20, 18, 19),
               "backpressure": (10, 13, 9, 11, 8),
               "arrival": (16, 18, 15, 9),
               "fault": (16, 18, 15)}[scenario]
    reqs = _requests(sv.vocab, budgets)
    step_inputs = sv.step_inputs
    held = []
    if scenario == "backpressure":
        # leave pages for two requests' reservations: the others wait for a
        # finish to return pages, with slots free all the while
        need = max(kv.pages_needed(len(r.prompt) + r.max_new_tokens + AHEAD)
                   for r in reqs)
        held = [kv.free_pages.pop() for _ in
                range(len(kv.free_pages) - 2 * need)]
    if scenario == "arrival":
        # the last request falls due at the seventh dispatch, whatever the
        # host's clock does: three slots decode, two steps are in flight
        late, calls = reqs[-1], [0]
        late.arrival_s = 1e9

        def step_inputs(tokens, state):
            calls[0] += 1
            if calls[0] == 7:
                late.arrival_s = 0.0
            return sv.step_inputs(tokens, state)
    if scenario == "fault":
        # the seventh dispatch fails for good (as often as the policy
        # retries): the overlapped loop has just pulled a step and holds
        # one in flight, the draining one holds two
        faults.configure("serve/decode_step@7*3")
    sched = ContinuousBatchingScheduler(
        sv.eng, sv.eng.params, sv.prompt_inputs, step_inputs, eos_id=eos,
        dispatch_ahead=AHEAD,
        decode_timeout_ms=None if overlapped else 1e12,
        retry_policy=RetryPolicy(attempts=3, base_delay=0.001, seed=3))
    t_run = time.perf_counter_ns()
    try:
        sched.run(reqs)
    finally:
        kv.free_pages.extend(held)
        faults.clear()
    assert len(sched.completed) + len(sched.failed) == len(reqs)
    assert len(sched.failed) == (scenario == "fault")
    assert len(kv.free_slots()) == SLOTS
    return Leg(sched, reqs, tel.ring_spans(since_ns=t_run))


@pytest.fixture(scope="module")
def legs(served):
    """(overlapped leg, forced-drain leg) of a case, served once a module."""
    made = {}

    def get(family, scenario):
        if (family, scenario) not in made:
            sv = served(family)
            eos = None
            if scenario == "eos":
                # a token of request 0's mid-answer becomes the EOS id,
                # the one no request emits earlier than any other candidate:
                # its first occurrence lies between two drains
                probe = _serve(sv, scenario, overlapped=False).tokens
                eos = max(probe[0][6:13], key=lambda t: min(
                    toks.index(t) for toks in probe.values() if t in toks))
            made[family, scenario] = (
                _serve(sv, scenario, overlapped=True, eos=eos),
                _serve(sv, scenario, overlapped=False, eos=eos))
        return made[family, scenario]
    return get


def _decode_spans(leg):
    return sorted((s for s in leg.spans
                   if s.name.startswith("serve/decode/")
                   or s.name == "serve/admit"), key=lambda s: s.start_ns)


# --------------------------------------------------------------- (a) tokens
@pytest.mark.parametrize("family,scenario", CASES)
def test_the_overlapped_loop_serves_the_draining_loops_tokens(
        legs, family, scenario):
    over, forced = legs(family, scenario)
    assert over.tokens == forced.tokens
    assert over.sched.prefills == forced.sched.prefills
    if scenario != "eos":   # which sees an EOS up to a window late
        assert over.sched.decode_steps == forced.sched.decode_steps
    # the forced loop never leaves a step in flight behind a sync ...
    assert forced.sched.stats["overlapped_syncs"] == 0
    assert forced.sched.stats["drains"] == forced.sched.materializations
    # ... the overlapped one does, and empties the pipeline less often
    st = over.sched.stats
    assert st["overlapped_syncs"] > 0
    assert st["overlapped_syncs"] + st["drains"] \
        == over.sched.materializations
    assert st["drains"] < forced.sched.stats["drains"]
    assert sum(st["drains_by_reason"].values()) == st["drains"]
    assert set(st["drains_by_reason"]) <= {"finish", "admit", "fault"}
    if scenario == "eos":
        # seen with steps in flight: what they decoded for it is dropped
        assert any(r.tokens[-1] == over.sched.eos_id
                   and len(r.tokens) < r.max_new_tokens for r in over.reqs)
        assert st["overdecode_tokens"] > 0
    else:
        assert st["overdecode_tokens"] == 0     # empty AT a max-len finish
        assert all(len(r.tokens) == r.max_new_tokens for r in over.reqs
                   if r.outcome == "done")
    if scenario == "arrival":
        assert st["drains_by_reason"].get("admit", 0) >= 1
        late = over.reqs[-1]
        assert late.slot is not None and len(late.tokens) == 9
    if scenario == "backpressure":
        assert over.sched.prefills >= 3     # pages came back at finishes
    if scenario == "fault":
        # the step pulled before the fault and the one in flight behind it
        # were both committed before the wedged slot went
        assert st["drains_by_reason"]["fault"] == 1
        (lost,) = over.sched.failed
        assert lost.outcome == "failed" and len(lost.tokens) == 1 + 6


# ---------------------------------------------------------------- (b) spans
@pytest.mark.parametrize("family,scenario", CASES)
def test_syncs_overlap_and_finishes_and_admissions_see_an_empty_pipeline(
        legs, family, scenario):
    over, _ = legs(family, scenario)
    sched = over.sched
    in_flight, last_sync, syncs = 0, None, []
    t0_ns = sched._t0 * 1e9
    finishes = sorted(t0_ns + r.finish_s * 1e9 for r in over.reqs
                      if r.outcome == "done")
    seen_finishes = 0
    for s in _decode_spans(over):
        args = s.args or {}
        if s.name == "serve/decode/dispatch":
            in_flight += "error" not in args    # a failed launch queued none
        elif s.name == "serve/decode/window_sync":
            in_flight -= args["steps"]
            assert args["in_flight"] == in_flight
            if "drain" in args:
                assert in_flight == 0
            else:
                assert in_flight >= 1
            last_sync = args
            syncs.append(args)
        elif s.name == "serve/admit":
            # an admission publishes the host mirrors: nothing in flight,
            # and what emptied the pipeline said why
            assert in_flight == 0
            assert last_sync is None or last_sync.get("drain")
        else:   # serve/decode/commit
            inside = [t for t in finishes if s.start_ns <= t <= s.end_ns]
            if inside:
                assert in_flight == 0 and last_sync.get("drain") \
                    and last_sync["in_flight"] == 0, (args, last_sync)
                assert args["window"] == last_sync["window"]
                seen_finishes += len(inside)
    assert in_flight == 0
    assert seen_finishes == len(sched.completed)    # each at a drain
    assert sum(a["steps"] for a in syncs) == sched.decode_steps
    assert sum(1 for a in syncs if "drain" not in a) \
        == sched.stats["overlapped_syncs"]
    by_reason = {}
    for a in syncs:
        if "drain" in a:
            by_reason[a["drain"]] = by_reason.get(a["drain"], 0) + 1
    assert by_reason == sched.stats["drains_by_reason"]
    # the programs' counters still ride the sync that drained their steps
    if family == "granite":
        assert all(a["moe_routed_pairs"] > 0 and a["ssm_state_bytes"] > 0
                   for a in syncs)
    assert all(len(r.tokens) <= r.max_new_tokens for r in over.reqs)


# ------------------------------------------------------------ (c) intervals
@pytest.mark.parametrize("family,scenario", CASES)
def test_the_benchmarks_decode_intervals_tile(legs, family, scenario):
    over, _ = legs(family, scenario)
    spans = over.spans
    ivs = sorted(span_device.intervals(
        spans, "serve/decode/window_sync", "serve/decode/dispatch",
        "window", "steps"))
    assert ivs
    for (s0, e0, _w0), (s1, _e1, _w1) in zip(ivs, ivs[1:]):
        assert e0 <= s1                         # no device time twice
    dispatches = [s for s in spans if s.name == "serve/decode/dispatch"
                  and "error" not in (s.args or {})]
    assert len(dispatches) == over.sched.decode_steps
    for d in dispatches:                        # each step's launch in one
        assert sum(1 for s, e, _w in ivs
                   if s <= d.start_ns and d.end_ns <= e) == 1
    stamped = {s.args["window"] for s in spans     # as the reader sees it:
               if s.name == "serve/decode/dispatch"}    # a failed one too
    unstarted = sum(s.args["steps"] for s in spans
                    if s.name == "serve/decode/window_sync"
                    and s.args["window"] not in stamped)
    assert sum(w for _s, _e, w in ivs) == over.sched.decode_steps - unstarted
    # a sync that no dispatch of its id precedes is a drain of steps that
    # were in flight already (a budget ran out): never an overlapped one
    assert all("drain" in s.args for s in spans
               if s.name == "serve/decode/window_sync"
               and s.args["window"] not in stamped)
    # in steady overlap an interval is one step's: the sync of the oldest
    overlapped = [s for s in spans if s.name == "serve/decode/window_sync"
                  and "drain" not in s.args]
    assert overlapped and all(s.args["steps"] == 1 for s in overlapped)


# ------------------------------------------------------------------- report
def test_trace_report_prints_the_decode_loops_line(legs):
    import trace_report

    over, _ = legs("gpt2", "staggered")
    events = [{"ph": "X", "name": s.name, "args": s.args or {}}
              for s in over.spans]
    (line,) = trace_report.decode_loop_lines(events)
    st = over.sched.stats
    assert line.startswith("[serve] decode loop: ")
    assert f"{over.sched.materializations} syncs" in line
    share = 100.0 * st["overlapped_syncs"] / over.sched.materializations
    assert f"{share:.1f}% overlapped" in line
    for reason, n in st["drains_by_reason"].items():
        assert f"{reason} {n}" in line
    behind = sorted(s.args["in_flight"] for s in over.spans
                    if s.name == "serve/decode/window_sync")
    assert f"median in_flight {behind[(len(behind) - 1) // 2]}" in line
    assert behind[-1] == OVERLAP_DEPTH - 1  # one queued behind the oldest
    assert trace_report.decode_loop_lines(
        [{"ph": "X", "name": "fit/dispatch", "args": {}}]) == []
