"""ISSUE 20 — the capacity twin: deterministic replay, what-if pricing,
capacity bisection, burn-driven scaling signals, and the CI smokes.

Unit pins cover the pure-twin pieces (no engines, bit-deterministic):
replay determinism, live-report schema parity, what-if monotonicity,
the capacity curve, scaling_signal's action table, and the
window-overhead calibration identity. One test builds the real 8-dev CPU
engine, records its traffic and replays it through the twin priced from
the run's own histograms; tools/twin.py --check rides along as a tier-1
smoke.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from flexflow_tpu.health import SLOTracker, parse_slo, scaling_signal
from flexflow_tpu.serving.tracefmt import poisson_records
from flexflow_tpu.serving.twin import (TwinCosts, TwinSpec,
                                       calibrate_window_overhead,
                                       capacity_curve, simulate, validate)


def _recs(n=40, rate=10.0, seed=0, max_new=8):
    rng = np.random.default_rng(seed)
    return poisson_records(rng, n, rate=rate, vocab=256, prompt_len=4,
                           max_new=max_new)


def _spec(**kw):
    base = dict(replicas=1, slots=4, seq=16, page_size=4,
                max_decode_len=8, slo="ttft_p99_ms=500")
    base.update(kw)
    return TwinSpec(**base)


# ------------------------------------------------------------ replay core
def test_replay_deterministic_and_complete():
    """Same trace + spec + costs => identical stats and report (no wall
    clock, no rng anywhere in the event loop)."""
    recs = _recs()
    spec = _spec()
    costs = TwinCosts.analytic(spec.kv_spec())
    r1, r2 = simulate(recs, spec, costs), simulate(recs, spec, costs)
    assert r1.stats == r2.stats
    assert r1.report() == r2.report()
    assert r1.stats["completed"] == len(recs)
    assert r1.stats["shed"] == 0
    # every completed request produced its full decode budget
    assert r1.stats["tokens_out"] == sum(r.max_tokens for r in recs)


def test_report_speaks_the_live_schema():
    """The twin emits the SAME report shape live serving does: terminal
    records feed a real SLOTracker (objectives/burn/budget keys) and the
    stage histograms carry count/mean/p50/p99 — so every live dashboard
    renders a twin report unchanged."""
    res = simulate(_recs(), _spec(), TwinCosts.analytic(_spec().kv_spec()))
    rep = res.report()
    assert {"stats", "hists", "slo", "scaling", "signals",
            "priced_by"} <= set(rep)
    obj = rep["slo"]["objectives"]["ttft_p99_ms"]
    assert {"budget_remaining", "burn_rate_60s", "burn_rate_300s",
            "bad_frac"} <= set(obj)
    assert rep["scaling"]["action"] in ("steady", "scale_in", "scale_out",
                                        "objective_flip")
    for h in rep["hists"].values():
        assert {"count", "mean", "p50", "p99"} <= set(h)
    # terminal records are the live reqtrace schema
    assert all(t["outcome"] == "done" and "ttft_s" in t
               for t in res.completed)


def test_what_if_sweeps_move_the_right_way():
    """The whole point of the twin: config deltas price directionally
    sanely offline. More replicas never lengthen the virtual wall;
    slower decode steps never raise tok/s; speculative decoding with a
    decent accept rate beats greedy on the same trace."""
    recs = _recs(n=60, rate=30.0)
    spec = _spec()
    costs = TwinCosts.analytic(spec.kv_spec())
    wall1 = simulate(recs, spec, costs).stats["wall_s"]
    wall4 = simulate(recs, dataclasses.replace(spec, replicas=4),
                     costs).stats["wall_s"]
    assert wall4 <= wall1
    slow = dataclasses.replace(costs, decode_step_s=costs.decode_step_s * 4)
    assert simulate(recs, spec, slow).stats["tokens_per_s"] < \
        simulate(recs, spec, costs).stats["tokens_per_s"]
    specd = dataclasses.replace(spec, spec_tokens=4, spec_accept_rate=0.8)
    assert simulate(recs, specd, costs).stats["wall_s"] < wall1


def test_capacity_curve_monotone_in_replicas():
    recs = _recs(n=80, rate=10.0)
    spec = _spec(slo="ttft_p99_ms=30000")
    costs = TwinCosts.analytic(spec.kv_spec(), step_floor_s=0.05)
    curve = capacity_curve(recs, spec, costs, replicas=(1, 2, 4), iters=5)
    caps = [c["capacity_rps"] for c in curve]
    assert [c["replicas"] for c in curve] == [1, 2, 4]
    assert caps[0] < caps[1] < caps[2]
    assert all(c > 0 for c in caps)


def test_window_overhead_calibration_identity():
    """calibrate_window_overhead solves the twin's only free temporal
    parameter from a live wall clock: replaying at the calibrated
    overhead must land the twin's wall on the probe's (the fixed-point
    the bench's twin-vs-live leg relies on)."""
    # a genuinely SATURATED probe (slots=1 -> no batching slack to
    # absorb the overhead, expensive steps -> busy ≫ arrival span):
    # the calibration contract assumes wall ≈ busy time
    recs = _recs(n=40, rate=200.0)
    spec = _spec(slo="", slots=1)
    costs = TwinCosts.analytic(spec.kv_spec(), step_floor_s=0.01)
    base_wall = simulate(recs, spec, costs).stats["wall_s"]
    live_wall = base_wall * 1.5
    oh = calibrate_window_overhead(recs, spec, costs, live_wall)
    assert oh > 0
    walled = dataclasses.replace(costs, window_overhead_s=oh)
    got = simulate(recs, spec, walled).stats["wall_s"]
    assert got == pytest.approx(live_wall, rel=0.05)
    # a live wall FASTER than the ideal twin clamps to zero, never
    # negative overhead
    assert calibrate_window_overhead(recs, spec, costs,
                                     base_wall * 0.5) == 0.0


def test_overhead_is_charged_a_drain_not_a_window():
    """The live loop hides a sync behind the steps still in flight and
    empties its pipeline for an admission or a finish alone: one long
    answer is many windows of `dispatch_ahead` and two drains, and the
    twin's wall grows by the overhead once a drain."""
    recs = _recs(n=1, max_new=32)
    spec = _spec(slo="", max_decode_len=32)
    costs = TwinCosts.analytic(spec.kv_spec(), step_floor_s=0.01)
    base = simulate(recs, spec, costs).stats
    assert base["windows"] >= 32 // spec.dispatch_ahead
    assert base["drains"] == 2      # the admission's turn, the finish's
    walled = simulate(recs, spec, dataclasses.replace(
        costs, window_overhead_s=0.5)).stats
    assert walled["drains"] == 2
    assert walled["wall_s"] == pytest.approx(base["wall_s"] + 2 * 0.5)


def test_validate_gates_on_worst_metric():
    live = {"tokens_per_s_per_cpu_device": 100.0, "ttft_p99_s": 0.10}
    twin = {"tokens_per_s_per_cpu_device": 110.0, "ttft_p99_s": 0.13}
    v = validate(live, twin, max_rel_err=0.25)
    assert v["max_rel_err"] == pytest.approx(0.30)
    assert not v["ok"]  # ttft is off by 30%: the worst metric gates
    assert validate(live, twin, max_rel_err=0.35)["ok"]
    assert not validate({}, {"other": 1.0})["ok"]  # no shared metrics


# --------------------------------------------------------- scaling policy
def _burny_report(fast, slow, budget):
    return {"objectives": {"ttft_p99_ms": {
        "budget_remaining": budget, "burn_rate_60s": fast,
        "burn_rate_300s": slow}},
        "windows_s": [60.0, 300.0], "worst_burn_rate": fast}


def test_scaling_signal_action_table():
    """The multi-window policy's four actions, pinned: hot fast window
    + slow confirm => scale_out while budget remains; exhausted budget
    => objective_flip (capacity can't un-burn history) even if burns are
    hot; everything cold => scale_in; in between => steady."""
    assert scaling_signal(_burny_report(8.0, 2.0, 0.4))["action"] == \
        "scale_out"
    assert scaling_signal(_burny_report(8.0, 0.5, 0.4))["action"] == \
        "steady"  # slow window does NOT confirm: a blip, not a trend
    assert scaling_signal(_burny_report(8.0, 2.0, 0.0))["action"] == \
        "objective_flip"
    assert scaling_signal(_burny_report(0.1, 0.1, 0.95))["action"] == \
        "scale_in"
    assert scaling_signal(_burny_report(2.0, 1.5, 0.5))["action"] == \
        "steady"
    assert scaling_signal({"objectives": {}})["action"] == "steady"


def test_scale_out_fires_before_budget_exhausts():
    """The ordering the autoscale bench leg gates on, in miniature: fed
    a long good history then a hot burst, the tracker's windowed burn
    crosses the scale-out bar while cumulative budget_remaining is still
    positive."""
    objectives = parse_slo("ttft_p95_ms=100")
    tr = SLOTracker(dict(objectives))
    t = 0.0
    for _ in range(800):  # ~67 min of healthy traffic
        t += 5.0
        tr.observe({"outcome": "done", "ttft_s": 0.01}, now_s=t)
    for _ in range(30):   # then a hot 30 s
        t += 1.0
        tr.observe({"outcome": "done", "ttft_s": 0.5}, now_s=t)
    sig = scaling_signal(tr.report(now_s=t))
    assert sig["action"] == "scale_out", sig
    assert sig["budget_remaining"] > 0


def _min_budget(res):
    rep = res.slo.report(now_s=res.stats["wall_s"])
    return min(o["budget_remaining"] for o in rep["objectives"].values())


def test_burst_signals_scale_out_and_the_sized_fleet_holds_budget():
    """The policy through the twin's own event loop: twenty minutes at 1
    req/s, then 30 s at ten times that. One replica ends with its ttft
    budget spent, but its scale_out signal fired while budget was left;
    the capacity curve sizes the fleet for the burst's peak rate, and that
    fleet replays the same burst with budget to spare and nothing shed."""
    rng = np.random.default_rng(3)
    steady = poisson_records(rng, 1200, 1.0, 256, 4, 8)
    burst = poisson_records(rng, 300, 10.0, 256, 4, 8,
                            t0=steady[-1].arrival_ts)
    for i, r in enumerate(burst):
        r.rid = len(steady) + i
    recs = steady + burst
    spec = _spec(slo="ttft_p95_ms=1000")
    costs = TwinCosts.analytic(spec.kv_spec(), step_floor_s=0.1)

    static = simulate(recs, spec, costs, signal_every_s=5.0)
    assert _min_budget(static) <= 0.0
    sig = next(s for s in static.signals if s["action"] == "scale_out")
    assert sig["budget_remaining"] > 0

    ts = [r.arrival_ts for r in recs]
    peak = max(sum(1 for u in ts if t - 10.0 <= u <= t) for t in ts[-300:]) \
        / 10.0
    curve = capacity_curve(steady, spec, costs, replicas=(1, 2, 4, 8))
    n = next(c["replicas"] for c in curve
             if c["capacity_rps"] >= 1.15 * peak)
    scaled = simulate(recs, dataclasses.replace(spec, replicas=n), costs)
    assert n > 1 and scaled.stats["shed"] == 0
    assert _min_budget(scaled) > 0.0


def test_recorded_trace_replays_through_the_measured_twin(devices, tmp_path):
    """The loop the twin exists for, on a live engine: --serve-trace-out
    records the offered load as a trace file; the twin configured from
    that engine, priced from the run's own histograms ("measured"),
    replays the file to completion; without a live report the same
    resolve prices from the roofline."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models import GPT2Config, build_gpt2
    from flexflow_tpu.serving import (ContinuousBatchingScheduler,
                                      compile_serving, gpt2_prompt_inputs,
                                      gpt2_step_inputs, tracefmt)

    trace_path = str(tmp_path / "live.jsonl")
    cfg = FFConfig(search_budget=16, mesh_shape={"data": 2, "model": 4},
                   log_level="warning", max_batch_slots=4, kv_page_size=4,
                   serve_trace_out=trace_path)
    m = FFModel(cfg)
    build_gpt2(m, GPT2Config(vocab=256, seq=16, d_model=64, heads=2,
                             layers=1, dropout=0.0), batch=8)
    eng = compile_serving(m, max_decode_len=4)
    eng.init(seed=0)
    sched = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                        gpt2_step_inputs, eos_id=None,
                                        dispatch_ahead=4)
    n = 24
    done = sched.run(tracefmt.records_to_requests(_recs(n, rate=500.0,
                                                        max_new=4)))
    assert len(done) == n

    trace = tracefmt.load_trace(trace_path)
    assert len(trace) == n and trace.skipped == 0
    assert trace.meta.get("source") == "scheduler"

    spec = TwinSpec.from_engine(eng, replicas=1)
    ks = spec.kv_spec()
    live = {"hists": sched.tracer.hists}
    costs = TwinCosts.resolve(ks, live_report=live)
    assert costs.source == "measured"
    assert costs.decode_step_s == pytest.approx(
        sched.tracer.hists["decode_step"].mean())
    assert TwinCosts.resolve(ks).source == "analytic"
    sim = simulate(trace.records, spec, costs)
    assert sim.stats["completed"] == n and sim.stats["shed"] == 0


# ------------------------------------------------------------- CI smokes
def test_twin_cli_check_smoke(capsys):
    """tools/twin.py --check: generate -> save -> load -> replay ->
    report -> capacity curve, no engine, deterministic."""
    import twin as twin_cli
    assert twin_cli.main(["--check"]) == 0
