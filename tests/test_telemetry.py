"""Unified telemetry (flexflow_tpu/telemetry.py — ISSUE 5 tentpole):
span/counter JSONL stream across compile + fit + pipeline + dataloader +
checkpoint, the cost-model drift monitor, Chrome-trace export via
tools/trace_report.py, the disabled-path zero-overhead guard (PR-2
baseline counters + bit-identical numerics), and the failed-async-
checkpoint surfacing satellite."""

import json
import os
import sys
import time

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
from flexflow_tpu import telemetry as tel
from flexflow_tpu.losses import LossType

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import trace_report  # noqa: E402


@pytest.fixture(autouse=True)
def _telemetry_isolated():
    """Telemetry is process-global: every test here must leave it OFF so
    the rest of the suite keeps its zero-overhead disabled path."""
    yield
    tel.shutdown()


def _mlp_model(cfg):
    m = FFModel(cfg)
    x = m.create_tensor([32, 16], name="x")
    h = m.dense(x, 32, activation="relu", name="fc1")
    m.dense(h, 4, name="fc2")
    return m


def _fit(telemetry_dir="", epochs=2, n=256, **cfg_kw):
    cfg = FFConfig(batch_size=32, only_data_parallel=True,
                   telemetry_dir=telemetry_dir, log_level="warning",
                   **cfg_kw)
    m = _mlp_model(cfg)
    cm = m.compile(SGDOptimizer(lr=0.05),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 16)).astype(np.float32)
    y = rng.integers(0, 4, size=(n,)).astype(np.int32)
    hist = cm.fit(x, y, epochs=epochs, verbose=False)
    return cm, hist


# ------------------------------------------------------------- core module
def test_span_event_counter_roundtrip(tmp_path):
    tdir = str(tmp_path / "tele")
    assert not tel.enabled()
    tel.configure(tdir)
    assert tel.enabled()
    with tel.span("unit/span", cat="test", foo=1):
        time.sleep(0.001)
    t0 = tel.now_us()
    tel.record("unit/record", t0, t0 + 42.0, cat="test", bar="x")
    tel.event("unit/event", cat="test")
    tel.error("unit/error", what="boom")
    tel.counter("unit/counter", 3)
    tel.flush()
    evs = tel.read_events(tdir)
    by_name = {e["name"]: e for e in evs}
    sp = by_name["unit/span"]
    assert sp["ph"] == "X" and sp["dur"] >= 1000.0  # slept >= 1ms
    assert sp["cat"] == "test" and sp["args"] == {"foo": 1}
    assert by_name["unit/record"]["dur"] == 42.0
    assert by_name["unit/event"]["ph"] == "i"
    assert by_name["unit/error"]["cat"] == "error"
    assert by_name["unit/counter"]["ph"] == "C"
    assert by_name["unit/counter"]["args"]["value"] == 3.0
    # ts-sorted, every record carries the schema basics
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    assert all({"name", "ph", "ts", "pid", "tid"} <= set(e) for e in evs)
    tel.shutdown()
    assert not tel.enabled()
    # with the sink off a span still lands in the ring, and only there
    tel.ring_clear()
    with tel.span("unit/after_shutdown"):
        pass
    assert [s.name for s in tel.ring_spans()] == ["unit/after_shutdown"]
    assert "unit/after_shutdown" not in {e["name"]
                                         for e in tel.read_events(tdir)}


def test_fit_emits_spans_and_drift(tmp_path, capsys):
    tdir = str(tmp_path / "tele")
    cm, hist = _fit(telemetry_dir=tdir)
    tel.flush()
    evs = tel.read_events(tdir)
    names = {e["name"] for e in evs}
    # every layer reported in: compile, fit loop, dataloader
    assert {"compile/compile_model", "fit/dispatch", "fit/prefetch_wait",
            "fit/epoch_end_sync", "fit/epoch",
            "dataloader/queue_depth"} <= names, names
    # one dispatch span per dispatch the loop counted
    disp = [e for e in evs if e["name"] == "fit/dispatch"]
    assert len(disp) == cm.step_stats["dispatches"] == 16
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in disp)
    # drift monitor: prediction stamped, windows measured, event emitted
    d = cm.drift_stats()
    assert d["predicted_step_time_s"] and d["predicted_step_time_s"] > 0
    assert d["measured_step_time_s"] and d["measured_step_time_s"] > 0
    assert d["windows"] == 2 and d["ratio"] is not None
    drift_evs = [e for e in evs if e["name"] == "fit/drift"]
    assert drift_evs and drift_evs[-1]["args"]["ratio"] == d["ratio"]
    # profile_report prints the [drift] section
    cm.profile_report(print_table=True)
    out = capsys.readouterr().out
    assert "[drift] predicted_step=" in out and "ratio=" in out


def test_disabled_telemetry_zero_overhead_and_bit_identical():
    """The acceptance bar: with telemetry disabled the fit path performs
    exactly the PR-2 baseline dispatch/host-sync counts, and numerics are
    bit-identical to a telemetry-enabled run (instrumentation only times,
    never reorders or adds math)."""
    import tempfile

    cm_off, h_off = _fit(telemetry_dir="")
    assert not tel.enabled()
    # PR-2 baseline counters (test_step_pipeline pins the same numbers)
    assert cm_off.step_stats == {"dispatches": 16, "host_syncs": 0,
                                 "barriers": 0, "fused_steps": 0,
                                 "epoch_end_syncs": 2}
    with tempfile.TemporaryDirectory() as td:
        cm_on, h_on = _fit(telemetry_dir=os.path.join(td, "tele"))
        tel.shutdown()
    # same counters with telemetry on — no extra dispatches or syncs
    assert cm_on.step_stats == cm_off.step_stats
    for eo, en in zip(h_off, h_on):
        assert en["loss"] == eo["loss"]  # bit-identical
        assert en["host_syncs"] == eo["host_syncs"] == 0.0


# ------------------------------------------------------------ trace_report
def test_trace_report_chrome_export(tmp_path):
    tdir = str(tmp_path / "tele")
    out = str(tmp_path / "trace.json")
    _fit(telemetry_dir=tdir, epochs=1)
    tel.flush()
    rep = trace_report.render(tdir, out_path=out, quiet=True)
    assert any(r["name"] == "fit/dispatch" and r["count"] == 8
               for r in rep["summary"])
    with open(out) as f:
        doc = json.load(f)
    assert trace_report.validate_chrome(doc) == []
    # thread metadata + mapped numeric tids (Perfetto-loadable shape)
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert metas and all(isinstance(e["tid"], int)
                         for e in doc["traceEvents"])
    # counters survive the export with their value args
    assert any(e["ph"] == "C" and "value" in e["args"]
               for e in doc["traceEvents"])


def test_trace_report_check_smoke():
    """tools/trace_report.py --check wired into CI."""
    assert trace_report.main(["--check"]) == 0
    assert not tel.enabled()  # --check cleans up the global sink


def test_validate_chrome_catches_garbage():
    assert trace_report.validate_chrome({"traceEvents": "nope"})
    assert trace_report.validate_chrome(
        {"traceEvents": [{"name": "x", "ph": "X", "ts": 0.0}]})  # no dur
    assert trace_report.validate_chrome(
        {"traceEvents": [{"ph": "i", "ts": 1.0}]})  # no name
    assert trace_report.validate_chrome(
        {"traceEvents": [{"name": "c", "ph": "C", "ts": 1.0,
                          "args": {}}]})  # counter without value


def test_gpt2_twin_fit_renders_trace(devices, tmp_path):
    """Acceptance shape: a small gpt2-twin fit with --telemetry-dir set
    produces a JSONL trace that trace_report renders into a span summary
    and valid Chrome trace-event JSON, with the [drift] ratio present."""
    from flexflow_tpu.models import GPT2Config, build_gpt2

    tdir = str(tmp_path / "tele")
    cfg = FFConfig(batch_size=4, only_data_parallel=True,
                   telemetry_dir=tdir, log_level="warning")
    m = FFModel(cfg)
    build_gpt2(m, GPT2Config(vocab=128, seq=8, d_model=32, heads=2,
                             layers=1, dropout=0.0), batch=4)
    cm = m.compile(SGDOptimizer(lr=0.01),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, size=(16, 8)).astype(np.int32)
    pos = np.broadcast_to(np.arange(8, dtype=np.int32), (16, 8)).copy()
    y = rng.integers(0, 128, size=(16, 8)).astype(np.int32)
    cm.fit([ids, pos], y, epochs=1, verbose=False)
    tel.flush()
    out = str(tmp_path / "trace.json")
    rep = trace_report.render(tdir, out_path=out, quiet=True)
    assert any(r["name"] == "fit/dispatch" for r in rep["summary"])
    assert rep["drift"] and rep["drift"][-1].get("ratio") is not None
    with open(out) as f:
        assert trace_report.validate_chrome(json.load(f)) == []


# ------------------------------------------------------------ pipeline path
def _pipelined_fit(tmp_path, sched, telemetry=True, epochs=1):
    tdir = str(tmp_path / f"tele_{sched}") if telemetry else ""
    cfg = FFConfig(batch_size=8, only_data_parallel=True, seed=3,
                   pipeline_stages=2, pipeline_schedule=sched,
                   accum_steps=4, telemetry_dir=tdir, log_level="warning")
    m = FFModel(cfg)
    t = m.create_tensor([8, 64], name="x")
    h = m.dense(t, 256, activation="gelu", name="up")
    h = m.dense(h, 64, name="down")
    h = m.dense(h, 128, activation="relu", name="mid")
    m.dense(h, 8, name="head")
    cm = m.compile(SGDOptimizer(lr=0.05),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 64)).astype(np.float32)
    y = rng.integers(0, 8, size=(32,)).astype(np.int32)
    hist = cm.fit([x], y, epochs=epochs, verbose=False)
    return cm, hist, tdir


@pytest.mark.parametrize("sched", ["gpipe", "1f1b"])
def test_pipeline_bubble_matches_executor(devices, tmp_path, sched):
    """Acceptance: the per-stage pipeline events' computed bubble fraction
    (trace_report, from the executed timeline in the JSONL) matches the
    executor's reported step_stats['measured_bubble'] — both go through
    telemetry.bubble_from_ops, so they must agree to float equality."""
    cm, _hist, tdir = _pipelined_fit(tmp_path, sched)
    tel.flush()
    mb = cm.step_stats.get("measured_bubble")
    assert mb is not None and 0.0 <= mb < 1.0
    evs = tel.read_events(tdir)
    pipe = [e for e in evs if e.get("cat") == "pipeline"]
    # per-(stage, phase, microbatch) coverage: every update dispatches
    # S*M - M forwards (last stage fuses F into B) and S*M backwards
    stages = {e["args"]["stage"] for e in pipe}
    assert stages == {0, 1}
    micros = {e["args"]["micro"] for e in pipe if e["name"] == "pipe/B"}
    assert micros == {0, 1, 2, 3}
    rep_bubble = trace_report.pipeline_bubble(evs)
    assert rep_bubble == pytest.approx(mb, rel=1e-9)


@pytest.mark.parametrize("sched", ["gpipe", "1f1b"])
def test_pipeline_stats_and_profile_report(devices, tmp_path, sched,
                                           capsys):
    """Satellite: profile_report / memory_stats / step_stats under the
    pipelined path (S>=2, both schedules) — per-stage stats present, no
    crash, drift section populated."""
    cm, hist, _ = _pipelined_fit(tmp_path, sched)
    # step_stats: n=32 samples / batch 8 = 4 microbatches, M=4 -> exactly
    # 1 update per epoch
    assert cm.step_stats["updates"] == 1 * len(hist)
    assert cm.step_stats["microbatches"] == 4 * len(hist)
    assert cm.step_stats["stages"] == 2
    assert cm.step_stats["schedule"] == sched
    # memory_stats: per-stage lists sized by stage count
    mem = cm.memory_stats()
    assert len(mem["per_stage_param_bytes"]) == 2
    assert len(mem["per_stage_opt_bytes"]) == 2
    assert all(b > 0 for b in mem["per_stage_param_bytes"])
    # profile_report: rows tagged per stage, both stages present
    rows = cm.profile_report(print_table=True)
    assert {r["stage"] for r in rows} == {0, 1}
    assert all(np.isfinite(r["measured_us"]) for r in rows)
    out = capsys.readouterr().out
    assert "[pipeline] stages=2" in out
    assert f"schedule={sched}" in out
    assert "[drift] predicted_step=" in out  # drift section populated
    assert "[memory] stage 0" in out and "[memory] stage 1" in out
    # drift monitor populated from the fit
    d = cm.drift_stats()
    assert d["windows"] == 1 and d["measured_step_time_s"] > 0
    assert d["predicted_step_time_s"] and d["ratio"] is not None


# ---------------------------------------------------- checkpoint satellite
def test_failed_async_checkpoint_surfaces(devices, tmp_path, capsys):
    """Satellite: a failed async checkpoint write must not stay silent
    until wait_pending — it lands in failed_writes() (telemetry error
    event included when enabled), the fit-end summary warns, and
    profile_report prints it; wait_checkpoints still re-raises (clearing
    the registry exactly when the error is reported)."""
    from flexflow_tpu.runtime.checkpoint import failed_writes

    tdir = str(tmp_path / "tele")
    cm, _ = _fit(telemetry_dir=tdir, epochs=1)
    blocker = tmp_path / "blocker"
    blocker.write_text("not a directory")
    bad = str(blocker / "ckpt")  # parent is a FILE: the write must fail
    cm.save_checkpoint(bad, block=False)
    for _ in range(200):  # writer thread fails fast; poll briefly
        if failed_writes():
            break
        time.sleep(0.05)
    fw = failed_writes()
    assert fw and fw[0]["path"].endswith("ckpt")
    # telemetry carries the error event
    tel.flush()
    errs = [e for e in tel.read_events(tdir)
            if e["name"] == "checkpoint/write_failed"]
    assert errs and errs[0]["cat"] == "error"
    # the next fit's end-of-fit summary surfaces it loudly
    rng = np.random.default_rng(1)
    x = rng.normal(size=(64, 16)).astype(np.float32)
    y = rng.integers(0, 4, size=(64,)).astype(np.int32)
    cm.fit(x, y, epochs=1, verbose=True)
    out = capsys.readouterr().out
    assert "[checkpoint] WARNING" in out and "FAILED" in out
    # profile_report shows it too
    cm.profile_report(print_table=True)
    assert "[checkpoint] FAILED async write" in capsys.readouterr().out
    # wait_checkpoints re-raises and clears the registry (reported once)
    with pytest.raises(BaseException):
        cm.wait_checkpoints()
    assert failed_writes() == []


# ------------------------------------------------------------ shared helpers
def test_bubble_from_ops_accounting():
    """bubble = 1 - busy/(stages * span): hand-checkable tiny timelines."""
    # two stages, fully overlapped and fully busy -> zero bubble
    ops = [(0, 0.0, 10.0), (1, 0.0, 10.0)]
    assert tel.bubble_from_ops(2, ops) == pytest.approx(0.0)
    # two stages strictly serialized -> half the grid idle
    ops = [(0, 0.0, 10.0), (1, 10.0, 20.0)]
    assert tel.bubble_from_ops(2, ops) == pytest.approx(0.5)
    assert tel.bubble_from_ops(2, []) is None
    assert tel.bubble_from_ops(0, ops) is None


def test_pipeline_bubble_groups_by_run():
    """Runs appended into one telemetry stream must NOT merge into one
    timeline: update ids restart per process AND per fit (init() resets
    the iteration counter), so grouping keys on (pid, fit, update) with
    per-group stage counts."""
    def op(pid, fit, upd, stage, ts, dur):
        return {"name": "pipe/B", "ph": "X", "cat": "pipeline", "ts": ts,
                "dur": dur, "pid": pid, "tid": "MainThread",
                "args": {"stage": stage, "micro": 0, "update": upd,
                         "fit": fit}}

    # run A (pid 1): 2 stages fully overlapped -> bubble 0
    # run B (pid 2): same update id 0, clock ~1e9 us later, serialized
    # 2 stages -> bubble 0.5
    evs = [op(1, 0, 0, 0, 0.0, 10.0), op(1, 0, 0, 1, 0.0, 10.0),
           op(2, 0, 0, 0, 1e9, 10.0), op(2, 0, 0, 1, 1e9 + 10.0, 10.0)]
    assert tel.pipeline_bubble_from_events(evs) == pytest.approx(0.25)
    # SAME pid, two fits whose update counters both restarted at 0 —
    # seconds of inter-fit idle must not read as bubble
    evs = [op(1, 0, 0, 0, 0.0, 10.0), op(1, 0, 0, 1, 0.0, 10.0),
           op(1, 1, 0, 0, 5e6, 10.0), op(1, 1, 0, 1, 5e6 + 10.0, 10.0)]
    assert tel.pipeline_bubble_from_events(evs) == pytest.approx(0.25)


def test_drift_stats_thresholds():
    # first window excluded as jit-compile warmup when more exist:
    # median over the steady windows (1.1, 1.2) = 1.15
    d = tel.drift_stats(1.0, [(10, 50.0), (10, 11.0), (10, 12.0)])
    assert d["measured_step_time_s"] == pytest.approx(1.15)
    assert d["ratio"] == pytest.approx(1.15) and not d["warn"]
    assert d["windows"] == 3
    # warn needs >= 2 windows (a 1-epoch fit can't separate drift from
    # compilation cost) and a steady ratio past the threshold
    assert tel.drift_stats(1.0, [(1, 10.0), (1, 10.0)])["warn"]   # slow
    assert tel.drift_stats(1.0, [(100, 10.0),
                                 (100, 10.0)])["warn"]            # fast
    assert not tel.drift_stats(1.0, [(1, 10.0)])["warn"]  # single window
    # a compile-heavy FIRST epoch alone must not trip the monitor
    assert not tel.drift_stats(1.0, [(1, 100.0), (10, 10.0)])["warn"]
    assert tel.drift_stats(None, [(10, 1.0)])["ratio"] is None
    assert tel.drift_stats(1.0, [])["measured_step_time_s"] is None
    # the formatter always yields a [drift] line for every shape
    for d2 in (d, tel.drift_stats(None, []), tel.drift_stats(1.0, []),
               tel.drift_stats(None, [(10, 1.0)]),
               tel.drift_stats(1.0, [(1, 10.0), (1, 10.0)])):
        lines = tel.format_drift(d2)
        assert lines and all(l.startswith("[drift]") for l in lines)


# ------------------------------------------------------------------ the ring
def test_ring_is_on_with_no_sink():
    """Spans land in the in-memory ring with no telemetry_dir anywhere;
    events and counters stay sink-only."""
    assert not tel.enabled()
    tel.ring_clear()
    with tel.span("unit/ring", cat="test", n=1) as sp:
        sp.set(found=2)
    tel.event("unit/event")
    tel.counter("unit/counter", 1)
    (rec,) = tel.ring_spans()
    assert rec.name == "unit/ring" and rec.args == {"n": 1, "found": 2}
    assert rec.end_ns >= rec.start_ns and rec.parent == 0
    assert tel.ring_spans("unit/other") == []
    assert tel.ring_spans(since_ns=rec.end_ns + 1) == []


def test_ring_is_bounded_and_reports_its_peak():
    tel.ring_clear()
    t = tel.now_us()
    for i in range(tel.RING_SIZE + 10):
        tel.record("unit/fill", t, t + 1.0, i=i)
    spans = tel.ring_spans()
    assert len(spans) == tel.RING_SIZE == tel.ring_peak()
    assert spans[0].args == {"i": 10}       # the oldest ten are gone
    tel.ring_clear()
    assert tel.ring_spans() == []
    assert tel.ring_peak() == tel.RING_SIZE  # the peak outlives a clear


def test_ring_keeps_parent_ids_and_cancelled_spans_out():
    tel.ring_clear()
    with tel.span("unit/outer") as outer:
        with tel.span("unit/inner"):
            tel.record("unit/afterwards", tel.now_us() - 5.0)
        with tel.span("unit/nothing") as none:
            none.cancel()
    by = {s.name: s for s in tel.ring_spans()}
    assert set(by) == {"unit/outer", "unit/inner", "unit/afterwards"}
    assert by["unit/inner"].parent == by["unit/outer"].id == outer.id
    assert by["unit/afterwards"].parent == by["unit/inner"].id
    assert by["unit/outer"].parent == 0
    # a child is recorded when it ends: before its parent
    assert [s.name for s in tel.ring_spans()][-1] == "unit/outer"


def test_ring_is_safe_from_two_threads():
    """Two threads (and a reader) at once: no record lost, every child's
    parent is a span of its own thread."""
    import threading

    tel.ring_clear()
    n = 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def work(tag):
        for _ in range(n):
            with tel.span(f"unit/{tag}/outer"):
                with tel.span(f"unit/{tag}/inner"):
                    pass

    try:
        threads = [threading.Thread(target=work, args=(t,), name=f"w-{t}")
                   for t in ("a", "b")]
        for th in threads:
            th.start()
        while any(th.is_alive() for th in threads):
            tel.ring_spans()            # a reader while both write
        for th in threads:
            th.join(timeout=60)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(old)
    spans = tel.ring_spans()
    assert len(spans) == 4 * n
    by_id = {s.id: s for s in spans}
    assert len(by_id) == 4 * n
    for s in spans:
        if s.name.endswith("/inner"):
            parent = by_id[s.parent]
            assert parent.thread == s.thread
            assert parent.name == s.name.replace("inner", "outer")


def test_sink_receives_the_same_records_as_the_ring(tmp_path):
    tdir = str(tmp_path / "tele")
    tel.configure(tdir)
    tel.ring_clear()
    with tel.span("unit/both", cat="test", k=3):
        with tel.span("unit/child"):
            pass
    tel.flush()
    ring = tel.ring_spans()         # by end; read_events sorts by start
    lines = [e for e in tel.read_events(tdir) if e["ph"] == "X"]
    assert [s.name for s in ring] == ["unit/child", "unit/both"]
    assert [e["name"] for e in lines] == ["unit/both", "unit/child"]
    for e, s in zip(lines, reversed(ring)):
        assert e["dur"] == pytest.approx((s.end_ns - s.start_ns) / 1e3)
        assert e.get("args") == s.args and e["tid"] == s.thread


def test_nested_compile_phases_count_once():
    """A jit traced while another is traced: the totals (and each
    record's `seconds`) hold a phase's own time, so they add up to time
    spent; phases under a millisecond gather into one record."""
    ev = "/jax/core/compile/jaxpr_trace_duration"
    tel.ring_clear()
    before = list(tel.totals["jax/trace"])
    time.sleep(0.02)
    tel._on_jax_duration(ev, 0.0004)          # short, nested in the next
    tel._on_jax_duration(ev, 0.004)           # nested in the next
    tel._on_jax_duration(ev, 0.010, fun_name="outer")
    tel._on_jax_duration("/jax/some/other_event", 5.0)
    count, secs = tel.totals["jax/trace"]
    assert count - before[0] == 3
    assert secs - before[1] == pytest.approx(0.010)
    recs = tel.ring_spans("jax/trace")
    assert sorted(round(r.args["seconds"], 6) for r in recs) == \
        [0.0004, 0.0036, 0.006]
    assert [r.args.get("fun") for r in recs if "count" not in r.args] == \
        [None, "outer"]


def test_fit_call_span_tree_and_epoch_end_counter():
    """fit/call holds fit/setup, one fit/dispatch per dispatch, one
    fit/epoch_end_sync per epoch and fit/finish; the epoch-end
    materialization is a program counter now."""
    tel.ring_clear()
    cm, _ = _fit(epochs=2)
    assert cm.step_stats["epoch_end_syncs"] == 2
    spans = tel.ring_spans()
    call = [s for s in spans if s.name == "fit/call"][-1]
    assert call.args == {"steps": 16}
    kids = [s for s in spans if s.parent == call.id]
    names = [s.name for s in kids]
    assert names[0] == "fit/setup" and names[-1] == "fit/finish"
    assert names.count("fit/dispatch") == cm.step_stats["dispatches"] == 16
    assert names.count("fit/epoch_end_sync") == 2
    assert names.count("fit/epoch") == 2
    assert names.count("fit/host_sync") == cm.step_stats["host_syncs"] == 0
    assert all(call.start_ns <= s.start_ns and s.end_ns <= call.end_ns
               for s in kids)
    disp = [s for s in kids if s.name == "fit/dispatch"]
    assert [s.args["iteration"] for s in disp] == list(range(1, 17))
    assert all(s.args["steps"] == 1 and s.args["kind"] == "1" for s in disp)


def test_recompile_inside_a_step_names_the_dispatch():
    """The first dispatch of a fresh model compiles the step: the
    jax/backend_compile span's parent is that fit/dispatch."""
    tel.ring_clear()
    _fit(epochs=1, n=64)
    spans = tel.ring_spans()
    by_id = {s.id: s for s in spans}
    first = [s for s in spans if s.name == "fit/dispatch"][0]
    compiles = [s for s in spans if s.name == "jax/backend_compile"
                and s.parent == first.id]
    assert compiles, [(s.name, by_id.get(s.parent)) for s in spans
                      if s.name.startswith("jax/")]
    assert all(first.start_ns <= s.start_ns and s.end_ns <= first.end_ns
               for s in compiles)
    later = [s for s in spans if s.name == "fit/dispatch"][1:]
    assert not any(s.parent in {d.id for d in later} for s in spans
                   if s.name == "jax/backend_compile")
