"""ISSUE 15 — request-level tracing, live latency histograms, SLO budgets.

Covers the tentpole's three pieces plus the satellites: the streaming
histogram's quantile/merge/snapshot math is pinned against np.percentile
on random draws and its Prometheus rendering against the cumulative-`le`
contract; per-request stage spans tile >=95% of each request's wall time
on the 8-device twin under mixed priorities; with --no-serve-reqtrace the
scheduler's decoded streams AND its dispatch/host-sync counts are bitwise
the traced run (the zero-sync pin — tracing must not change scheduling);
all four terminal outcomes (done/shed/failed/timeout) emit the unified
TERMINAL_FIELDS record; the SLO tracker's burn-rate classification counts
sheds and timeouts against the availability objective (and never against
latency ones); the serve/hist + serve/slo events round-trip through
telemetry -> monitor -> Prometheus as real histogram series and labeled
budget gauges; and tools/trace_report.py --rid renders one request's
stage timeline.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from flexflow_tpu import FFConfig, FFModel, health
from flexflow_tpu.models import GPT2Config, build_gpt2
from flexflow_tpu.runtime import faults
from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,
                                  StreamingHistogram, TERMINAL_FIELDS,
                                  compile_serving, gpt2_prompt_inputs,
                                  gpt2_step_inputs)
from flexflow_tpu.serving.reqtrace import HIST_BUCKETS_PER_DECADE, HIST_EDGES

MESH = {"data": 2, "model": 4}

# one log-spaced bucket is a factor of 10^(1/10) wide — the histogram's
# quantile estimate can never be further from the truth than that
BUCKET_RATIO = 10.0 ** (1.0 / HIST_BUCKETS_PER_DECADE)


def _gpt2_cfg():
    return GPT2Config(vocab=256, seq=16, d_model=32, heads=4, layers=1,
                      dropout=0.0)


def _serve_cfg(**kw):
    kw.setdefault("search_budget", 16)
    kw.setdefault("mesh_shape", dict(MESH))
    kw.setdefault("max_batch_slots", 4)
    kw.setdefault("kv_page_size", 4)
    kw.setdefault("max_decode_len", 6)
    kw.setdefault("log_level", "warning")
    return FFConfig(**kw)


@pytest.fixture(scope="module")
def rt_serve(devices):
    gc = _gpt2_cfg()
    m = FFModel(_serve_cfg())
    build_gpt2(m, gc, batch=8)
    eng = compile_serving(m)
    eng.init(seed=0)
    return eng, gc


def _sched(eng, **kw):
    return ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                       gpt2_step_inputs, eos_id=None,
                                       dispatch_ahead=4, **kw)


def _reqs(n, gc, max_new=4, prompt_len=4, **kw):
    rng = np.random.default_rng(41)
    return [Request(rid=i,
                    prompt=list(rng.integers(1, gc.vocab, size=prompt_len)),
                    max_new_tokens=max_new, arrival_s=0.0, **kw)
            for i in range(n)]


# ------------------------------------------------------- histogram math
def test_histogram_quantiles_vs_numpy():
    """Quantile estimates land within one log bucket of np.percentile on
    random draws spanning the realistic latency range."""
    rng = np.random.default_rng(0)
    for draws in (np.exp(rng.normal(np.log(5e-3), 1.2, size=4000)),
                  rng.exponential(0.08, size=4000) + 1e-5,
                  rng.uniform(1e-4, 2.0, size=999)):
        h = StreamingHistogram()
        h.add_many(draws)
        assert h.count == len(draws)
        assert np.isclose(h.sum, draws.sum())
        for q in (0.1, 0.5, 0.9, 0.99):
            est = h.quantile(q)
            true = float(np.percentile(draws, 100 * q))
            assert true / BUCKET_RATIO <= est <= true * BUCKET_RATIO, \
                (q, est, true)


def test_histogram_merge_equals_concat():
    """Fixed shared edges make the merge exact: merging two histograms is
    bitwise identical to one histogram fed the concatenated samples."""
    rng = np.random.default_rng(1)
    a, b = rng.exponential(0.01, size=500), rng.exponential(0.3, size=700)
    ha, hb, hab = (StreamingHistogram() for _ in range(3))
    ha.add_many(a)
    hb.add_many(b)
    hab.add_many(np.concatenate([a, b]))
    ha.merge(hb)
    assert np.array_equal(ha.counts, hab.counts)
    assert ha.count == hab.count
    assert np.isclose(ha.sum, hab.sum)
    # snapshot -> from_snapshot round-trips exactly (the monitor's path)
    rt = StreamingHistogram.from_snapshot(ha.snapshot())
    assert np.array_equal(rt.counts, ha.counts)
    assert rt.count == ha.count and np.isclose(rt.sum, ha.sum)
    with pytest.raises(ValueError):
        StreamingHistogram.from_snapshot({"buckets": {}, "sum": 0.0,
                                          "count": 0, "n_edges": 7})
    with pytest.raises(ValueError):
        ha.merge(StreamingHistogram(edges=np.array([0.1, 1.0])))


def test_histogram_prom_lines():
    """The Prometheus rendering honors the histogram contract: cumulative
    monotone `le` buckets, `+Inf` == `_count`, `_sum` matches."""
    h = StreamingHistogram()
    h.add(0.003, n=5)
    h.add(0.2, n=2)
    h.add(1e-9)    # underflow bucket
    h.add(1e3)     # overflow bucket
    lines = h.prom_lines("flexflow_serve_ttft_seconds", "test")
    assert lines[0].startswith("# HELP flexflow_serve_ttft_seconds")
    assert lines[1] == "# TYPE flexflow_serve_ttft_seconds histogram"
    cums = [int(ln.rsplit(" ", 1)[1]) for ln in lines
            if "_bucket{" in ln and "+Inf" not in ln]
    assert len(cums) == len(HIST_EDGES)
    assert cums == sorted(cums)
    inf = next(ln for ln in lines if '+Inf' in ln)
    assert int(inf.rsplit(" ", 1)[1]) == h.count == 9
    count_ln = next(ln for ln in lines if ln.startswith(
        "flexflow_serve_ttft_seconds_count"))
    assert int(count_ln.rsplit(" ", 1)[1]) == 9
    # the overflow sample is only in +Inf, not in the last finite bucket
    assert cums[-1] == 8


# ------------------------------------------------- stage-span accounting
def test_accounting_mixed_priorities(rt_serve):
    """On the 8-device twin under mixed priorities and staggered arrivals
    every request's stage spans tile >=95% of its wall time, and every
    finished trace carries the full unified terminal record."""
    eng, gc = rt_serve
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=list(rng.integers(1, gc.vocab, size=4)),
                    max_new_tokens=3 + i % 4, arrival_s=0.02 * i,
                    priority=i % 3)
            for i in range(10)]
    sched = _sched(eng)
    done = sched.run(reqs)
    assert len(done) == 10
    assert sched.tracer is not None
    frac = sched.tracer.min_accounted_frac()
    assert frac is not None and frac >= 0.95, frac
    assert len(sched.tracer.ring) == 10
    for tr in sched.tracer.ring:
        for field in TERMINAL_FIELDS:
            assert field in tr, (field, sorted(tr))
        assert tr["outcome"] == "done"
        assert tr["outcome_reason"] == "max_new_tokens"
        assert tr["kv_pages"] > 0          # captured BEFORE eviction
        assert tr["tokens_out"] == tr["rid"] % 4 + 3
        stages = [s["stage"] for s in tr["stages"]]
        assert stages[0] == "queue"
        assert "prefill" in stages
    # the live histograms saw every request
    assert sched.tracer.hists["ttft"].count == 10
    assert sched.tracer.hists["queue_wait"].count == 10
    assert sched.tracer.hists["decode_step"].count > 0
    # live query by rid works for finished requests
    assert sched.tracer.get(3)["rid"] == 3


# --------------------------------------------------- tracing-off baseline
def test_reqtrace_off_bitwise_and_sync_pin(rt_serve):
    """The zero-sync contract: tracing off produces BITWISE identical
    decoded streams and identical dispatch/host-sync counts — the tracer
    only ever re-reads timestamps the scheduler already took, so turning
    it off cannot change scheduling."""
    eng, gc = rt_serve

    def leg(rt_on):
        sched = _sched(eng, reqtrace=rt_on)
        done = sched.run(_reqs(6, gc, max_new=5))
        return ({r.rid: list(r.tokens) for r in done},
                sched.decode_steps, sched.prefills, sched.materializations,
                sched)

    toks_on, steps_on, pre_on, mat_on, s_on = leg(True)
    toks_off, steps_off, pre_off, mat_off, s_off = leg(False)
    assert s_on.tracer is not None and s_off.tracer is None
    assert toks_on == toks_off
    assert steps_on == steps_off
    assert pre_on == pre_off
    assert mat_on == mat_off
    # the config gate wires the same switch (scheduler arg just overrides)
    assert FFConfig().serve_reqtrace is True


def test_reqtrace_off_emits_no_req_spans(rt_serve, tmp_path):
    """--no-serve-reqtrace: zero serve/req/* spans and zero serve/hist
    events reach the telemetry stream; the unified terminal events still
    do (the schema holds without the tracer)."""
    from flexflow_tpu import telemetry as tel

    eng, gc = rt_serve
    tdir = str(tmp_path / "tel")
    tel.configure(tdir)
    try:
        _sched(eng, reqtrace=False).run(_reqs(3, gc))
    finally:
        tel.shutdown()
    evs = tel.read_events(tdir)
    names = [e.get("name") for e in evs]
    assert not any(str(n).startswith("serve/req/") for n in names), names
    assert "serve/hist" not in names
    dones = [e for e in evs if e.get("name") == "serve/request_done"]
    assert len(dones) == 3
    for ev in dones:
        assert set(TERMINAL_FIELDS) <= set(ev["args"]), ev["args"]


# ----------------------------------------------- unified terminal schema
def test_unified_terminal_schema_all_outcomes(rt_serve, tmp_path):
    """done, shed, failed, AND watchdog-timeout all emit the full
    rid/priority/queue_wait/ttft/tokens/outcome_reason record (pre-15 the
    three non-done paths each had their own ad-hoc field set)."""
    from flexflow_tpu import telemetry as tel

    eng, gc = rt_serve
    tdir = str(tmp_path / "tel")
    tel.configure(tdir)
    try:
        # done
        _sched(eng).run(_reqs(2, gc))
        # shed (queue_full displacement, driven directly with explicit
        # clocks like the resilience suite does)
        sq = _sched(eng, queue_cap=1)
        waiting = []
        sq._enqueue(Request(rid=50, prompt=[1, 2], max_new_tokens=2,
                            priority=2), waiting, now_s=0.1)
        sq._enqueue(Request(rid=51, prompt=[1, 2], max_new_tokens=2,
                            priority=3), waiting, now_s=0.2)
        assert sq.shed
        # timeout (absurdly tight per-step watchdog budget)
        st = _sched(eng, decode_timeout_ms=1e-6)
        st.run(_reqs(2, gc, max_new=6))
        assert st.failed and st.failed[0].outcome == "timeout"
        # failed (permanent decode fault escalates past the retry budget)
        from flexflow_tpu.runtime.resilience import RetryPolicy

        faults.configure("serve/decode_step@3*3")
        sf = _sched(eng, retry_policy=RetryPolicy(attempts=3,
                                                  base_delay=0.001, seed=3))
        sf.run(_reqs(4, gc))
        faults.clear()
        assert sf.failed and sf.failed[0].outcome == "failed"
    finally:
        faults.clear()
        tel.shutdown()
    evs = tel.read_events(tdir)
    by_outcome = {}
    for ev in evs:
        if ev.get("name") in ("serve/request_done", "serve/request_shed",
                              "serve/request_failed"):
            by_outcome.setdefault(ev["args"]["outcome"], []).append(ev)
    assert set(by_outcome) == {"done", "shed", "failed", "timeout"}, \
        sorted(by_outcome)
    for outcome, events in by_outcome.items():
        for ev in events:
            missing = set(TERMINAL_FIELDS) - set(ev["args"])
            assert not missing, (outcome, missing)
    # sheds never admitted: their whole life is queue_wait; no ttft
    for ev in by_outcome["shed"]:
        assert ev["args"]["ttft_s"] is None
        assert ev["args"]["tokens_out"] == 0
        assert ev["args"]["outcome_reason"] == "queue_full"


# --------------------------------------------------------- SLO tracking
def test_parse_slo_grammar():
    obs = health.parse_slo(
        "ttft_p99_ms=25,per_token_p99_ms=10,queue_wait_p50_ms=5,"
        "availability=0.999")
    assert obs["ttft_p99_ms"] == {"kind": "latency", "metric": "ttft",
                                  "pct": 0.99, "threshold_s": 0.025}
    assert obs["queue_wait_p50_ms"]["pct"] == 0.5
    assert obs["availability"] == {"kind": "availability", "target": 0.999}
    assert health.parse_slo("") == {}
    for bad in ("latency_p99_ms=5", "ttft_p99_ms=nope", "availability=1.5",
                "ttft_p0_ms=5", "gibberish"):
        with pytest.raises(ValueError):
            health.parse_slo(bad)


def test_slo_burn_rate_classification():
    """Sheds and timeouts burn the availability budget; latency
    objectives only ever judge COMPLETED requests. Burn rate is the
    windowed bad-fraction over the objective's allowance."""
    tr = health.SLOTracker(
        health.parse_slo("ttft_p99_ms=25,availability=0.9"),
        windows_s=(60.0, 300.0))
    t = 1000.0
    for i in range(80):  # fast completions: nothing burns
        tr.observe({"outcome": "done", "ttft_s": 0.001}, now_s=t + i * 0.1)
    for i in range(10):  # sheds + timeouts: availability-only burn
        tr.observe({"outcome": "shed" if i % 2 else "timeout",
                    "ttft_s": None}, now_s=t + 10 + i * 0.1)
    for i in range(10):  # slow completions: latency-only burn
        tr.observe({"outcome": "done", "ttft_s": 0.5}, now_s=t + 20 + i * 0.1)
    rep = tr.report(now_s=t + 30)
    av = rep["objectives"]["availability"]
    lat = rep["objectives"]["ttft_p99_ms"]
    # availability: 10 bad of 100 -> bad_frac 0.1 vs allowance 0.1
    assert av["total"] == 100 and av["bad"] == 10
    assert np.isclose(av["burn_rate_60s"], 1.0)
    assert np.isclose(av["budget_remaining"], 0.0)
    # latency: 10 bad of 90 DONE (sheds/timeouts excluded from the sample)
    assert lat["total"] == 90 and lat["bad"] == 10
    assert lat["burn_rate_60s"] > 1.0   # 11.1% bad vs 1% allowance
    assert lat["budget_remaining"] < 0.0  # budget blown (goes negative)
    assert rep["shed_rate"] == 0.1
    assert rep["worst_burn_rate"] >= lat["burn_rate_60s"]
    # outside the window there is no burn sample, but totals persist
    rep2 = tr.report(now_s=t + 1000)
    assert rep2["objectives"]["availability"]["burn_rate_60s"] is None
    assert rep2["objectives"]["availability"]["bad"] == 10


def test_engine_health_report_exposes_slo(devices):
    """--serve-slo lands on the engine: terminal classifications flow
    scheduler -> engine.slo and surface in health_report()["serving"]."""
    gc = _gpt2_cfg()
    cfg = _serve_cfg(only_data_parallel=True, search_budget=0,
                     serve_slo="ttft_p99_ms=30000,availability=0.5")
    m = FFModel(cfg)
    build_gpt2(m, gc, batch=8)
    eng = compile_serving(m)
    eng.init(seed=0)
    done = _sched(eng).run(_reqs(3, gc))
    assert len(done) == 3
    slo = eng.health_report()["serving"]["slo"]
    assert slo["requests"] == 3
    assert slo["outcomes"] == {"done": 3}
    assert set(slo["objectives"]) == {"ttft_p99_ms", "availability"}
    assert slo["objectives"]["availability"]["bad"] == 0
    assert slo["objectives"]["availability"]["budget_remaining"] == 1.0


# ----------------------------------- telemetry -> monitor -> prometheus
def test_hist_slo_monitor_prom_roundtrip(devices, tmp_path):
    """The serve/hist snapshots and the serve/slo scoreboard flow through
    the telemetry sink into the monitor's serving panel (histogram
    quantiles become the panel's numbers) and out the Prometheus export
    as real histogram series + labeled budget/burn gauges."""
    import monitor

    from flexflow_tpu import telemetry as tel

    gc = _gpt2_cfg()
    tdir = str(tmp_path / "tel")
    tel.configure(tdir)
    try:
        cfg = _serve_cfg(only_data_parallel=True, search_budget=0,
                         serve_slo="ttft_p99_ms=25,availability=0.999")
        m = FFModel(cfg)
        build_gpt2(m, gc, batch=8)
        eng = compile_serving(m)
        eng.init(seed=0)
        sched = _sched(eng)
        sched.run(_reqs(4, gc))
        want_p50 = sched.tracer.hists["ttft"].quantile(0.5)
    finally:
        tel.shutdown()
    evs = tel.read_events(tdir)
    names = {e.get("name") for e in evs}
    assert "serve/hist" in names and "serve/slo" in names
    state = monitor.gather(evs)
    sv = monitor._serve_stats(state["serve"])
    assert set(sv["hists"]) >= {"ttft", "queue_wait", "decode_step"}
    # the histogram IS the panel's source of truth, not the done-events
    assert sv["ttft_p50_s"] == pytest.approx(want_p50)
    assert sv["slo"]["requests"] == 4
    txt = "\n".join(monitor.render(state))
    assert "slo" in txt and "budget" in txt
    prom = str(tmp_path / "node.prom")
    monitor.prom_export(state, prom)
    with open(prom) as f:
        ptxt = f.read()
    assert "flexflow_serve_ttft_seconds_bucket" in ptxt
    assert 'le="+Inf"' in ptxt
    assert "flexflow_serve_decode_step_seconds_count" in ptxt
    assert ('flexflow_serve_slo_budget_remaining{objective="ttft_p99_ms"}'
            in ptxt)
    assert ('flexflow_serve_slo_burn_rate{objective="availability",'
            'window="60s"}' in ptxt)
    assert "flexflow_serve_slo_shed_rate" in ptxt


def test_trace_report_rid_timeline(rt_serve, tmp_path, capsys):
    """tools/trace_report.py --rid: one request's stage timeline (queue ->
    prefill -> decode -> outcome) with >=95% of its wall accounted, and
    the Chrome export names one thread row per slot."""
    import trace_report

    from flexflow_tpu import telemetry as tel

    eng, gc = rt_serve
    tdir = str(tmp_path / "tel")
    tel.configure(tdir)
    try:
        _sched(eng).run(_reqs(3, gc))
    finally:
        tel.shutdown()
    evs = trace_report.load_events(tdir)
    tl = trace_report.request_timeline(evs, 1)
    assert tl is not None
    assert tl["accounted_frac"] >= 0.95
    stages = [s["stage"] for s in tl["stages"]]
    assert stages[0] == "queue"
    assert "prefill" in stages
    assert tl["terminal"]["outcome"] == "done"
    assert tl["terminal"]["event"] == "serve/request_done"
    # decode-slot spans carry their slot's tid -> per-slot Chrome rows
    slot_tids = {s["tid"] for s in tl["stages"] if s["stage"] != "queue"}
    assert any(str(t).startswith("slot") for t in slot_tids), slot_tids
    chrome = trace_report.to_chrome(evs)
    thread_names = {ev["args"]["name"] for ev in chrome["traceEvents"]
                    if ev.get("ph") == "M"}
    assert any(n.startswith("slot") for n in thread_names), thread_names
    # the CLI path: --rid prints the timeline, unknown rid exits 1
    assert trace_report.main([tdir, "--rid", "1"]) == 0
    out = capsys.readouterr().out
    assert "rid=1" in out and "queue" in out and "prefill" in out
    assert trace_report.main([tdir, "--rid", "999"]) == 1


# ----------------------------------------------- scheduler spans (PR 25)
def _ring_run(eng, gc, n=6, **kw):
    from flexflow_tpu import telemetry as tel

    tel.ring_clear()
    sched = _sched(eng)
    reqs = _reqs(n, gc, **kw)
    sched.run(reqs)
    spans = tel.ring_spans()
    run = [s for s in spans if s.name == "serve/run"][-1]
    return sched, reqs, spans, run


def test_serve_run_children_cover_it(devices):
    """The scheduler's own spans account for its loop: the direct children
    of serve/run cover at least 95 % of it. The run is sized like a served
    one in what matters here: a decode step of some milliseconds (two
    layers of width 128, not one of 32) and 46 of them, inside the
    engine's decode length, so that the Python between a turn's three
    spans (some 60 us a turn) and the fixed millisecond around the waves
    are no larger a share than they are of a served run."""
    gc = GPT2Config(vocab=256, seq=64, d_model=128, heads=4, layers=2,
                    dropout=0.0)
    m = FFModel(_serve_cfg(max_decode_len=32))
    build_gpt2(m, gc, batch=8)
    eng = compile_serving(m)
    eng.init(seed=0)
    _ring_run(eng, gc, n=4, max_new=4)      # compiled: the run below is warm
    _sched_, reqs, spans, run = _ring_run(eng, gc, n=8, max_new=24)
    assert run.args == {"requests": 8}
    assert all(len(r.tokens) == 24 for r in reqs)
    kids = sorted((s for s in spans if s.parent == run.id),
                  key=lambda s: s.start_ns)
    assert {s.name for s in kids} >= {"serve/admit", "serve/decode/dispatch",
                                      "serve/decode/window_sync",
                                      "serve/decode/commit"}
    covered, edge = 0, run.start_ns
    for s in kids:
        assert run.start_ns <= s.start_ns and s.end_ns <= run.end_ns
        covered += max(0, s.end_ns - max(edge, s.start_ns))
        edge = max(edge, s.end_ns)
    assert covered >= 0.95 * (run.end_ns - run.start_ns)


def test_prefill_wave_spans_carry_the_counts(rt_serve):
    eng, gc = rt_serve
    sched, reqs, spans, run = _ring_run(eng, gc, n=6, prompt_len=5)
    waves = [s for s in spans if s.name == "serve/admit"]
    assert len(waves) == sched.prefills >= 2      # 6 requests, 4 slots
    assert [w.args["wave"] for w in waves] == list(range(1, len(waves) + 1))
    assert sum(w.args["requests"] for w in waves) == 6
    assert sum(w.args["prompt_tokens"] for w in waves) == 6 * 5
    assert all(w.args["padded_tokens"] == sched.slots * sched.seq
               for w in waves)
    copies = [s for s in spans if s.name == "serve/prefill/logits_to_host"]
    # `slots` int32 first tokens cross to the host, not the logits
    assert [c.args["bytes"] for c in copies] == [sched.slots * 4] * len(waves)
    # each wave holds its parts, in order
    for w in waves:
        parts = [s.name for s in spans if s.parent == w.id]
        assert parts == ["serve/admit/place", "serve/prefill/dispatch",
                         "serve/prefill/commit", "serve/prefill/device_wait",
                         "serve/prefill/logits_to_host",
                         "serve/prefill/first_tokens"]


def test_request_stage_spans_name_their_wave_and_window(rt_serve):
    eng, gc = rt_serve
    sched, reqs, spans, run = _ring_run(eng, gc, n=6)
    waves = {s.args["wave"]: s for s in spans if s.name == "serve/admit"}
    queued = {s.args["rid"]: s for s in spans if s.name == "serve/req/queue"}
    assert set(queued) == {r.rid for r in reqs}
    for r in reqs:
        wave = waves[queued[r.rid].args["wave"]]
        # the wave that admitted it: the request's prefill dispatch stamp
        # (admit_s, from the scheduler's start) lies inside that span
        t_admit = run.start_ns + int(r.admit_s * 1e9)
        assert wave.start_ns - 1_000_000 <= t_admit <= wave.end_ns
    windows = {s.args["window"] for s in spans
               if s.name == "serve/decode/window_sync"}
    # a drained window's stage names it (the residual stage that a
    # finishing request closes has no steps and no window)
    decoded = [s for s in spans if s.name == "serve/req/decode"
               and "steps" in s.args]
    assert decoded and {s.args["window"] for s in decoded} <= windows
    commits = [s for s in spans if s.name == "serve/decode/commit"]
    assert sum(c.args["tokens_committed"] for c in commits) == \
        sum(len(r.tokens) - 1 for r in reqs)
    steps = sum(s.args["steps"] for s in spans
                if s.name == "serve/decode/window_sync")
    assert steps == sched.decode_steps == \
        len([s for s in spans if s.name == "serve/decode/dispatch"])


def test_spans_change_no_token_and_no_dispatch(rt_serve, monkeypatch):
    """Tokens, decode steps, prefill waves and materializations equal a
    run in which every span is a no-op: the spans only time."""
    import contextlib

    from flexflow_tpu import telemetry as tel

    eng, gc = rt_serve

    def leg():
        sched = _sched(eng)
        done = sched.run(_reqs(6, gc, max_new=5))
        return ({r.rid: list(r.tokens) for r in done}, sched.decode_steps,
                sched.prefills, sched.materializations)

    with_spans = leg()

    class _Null(contextlib.nullcontext):
        def __enter__(self):
            return self

        def set(self, **kw):
            pass

        def cancel(self):
            pass

    monkeypatch.setattr(tel, "span", lambda *a, **kw: _Null())
    monkeypatch.setattr(tel, "record", lambda *a, **kw: None)
    assert leg() == with_spans
