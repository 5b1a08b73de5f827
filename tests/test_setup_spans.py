"""Set-up's anatomy from inside the program (ISSUE 54): the gathered compile
phases keep their program and their function, every first call of a serving
and of a training set-up lies under a span that names its program, the
package's import and the backend's start are spans, a file sink begins with
them, and tools/trace_report.py prints them as one [setup] block."""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
from flexflow_tpu import telemetry as tel
from flexflow_tpu.losses import LossType

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.join(ROOT, "tools"))
import trace_report  # noqa: E402

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@pytest.fixture(autouse=True)
def _telemetry_isolated():
    yield
    tel.shutdown()


def _phase(event, secs, fun):
    """One compile phase that has just ended (and began after the last)."""
    time.sleep(secs + 0.0003)
    tel._on_jax_duration(event, secs, fun_name=fun)


def _jax_records():
    return [s for s in tel.ring_spans() if s.name.startswith("jax/")]


# ------------------------------------------------------- the gathered records
def test_gathered_records_keep_parent_and_fun_and_add_up_to_the_totals():
    tel.ring_clear()
    before = {n: list(v) for n, v in tel.totals.items()}
    with tel.span("unit/wave", cat="serve") as wave:
        for _ in range(5):
            _phase(TRACE, 0.0002, "body")
        _phase(TRACE, 0.0003, "other")
        _phase(LOWER, 0.0004, "body")
    with tel.span("unit/step", cat="serve") as step:
        _phase(TRACE, 0.0002, "body")
        _phase(TRACE, 0.005, "long")    # its own record
    recs = _jax_records()
    got = {(r.name, r.parent, r.args["fun"]):
           (r.args.get("count"), round(r.args["seconds"], 6)) for r in recs}
    assert got == {("jax/trace", wave.id, "body"): (5, 0.001),
                   ("jax/trace", wave.id, "other"): (1, 0.0003),
                   ("jax/lower", wave.id, "body"): (1, 0.0004),
                   ("jax/trace", step.id, "body"): (1, 0.0002),
                   ("jax/trace", step.id, "long"): (None, 0.005)}
    assert all(r.thread == "MainThread" and r.cat == "compile" for r in recs)
    # each gathered record lies inside its parent
    by_id = {s.id: s for s in tel.ring_spans()}
    assert all(by_id[r.parent].start_ns <= r.start_ns
               and r.end_ns <= by_id[r.parent].end_ns for r in recs)
    for name in ("jax/trace", "jax/lower"):
        count, secs = tel.totals[name]
        mine = [r for r in recs if r.name == name]
        assert count - before[name][0] == sum(r.args.get("count", 1)
                                              for r in mine)
        assert secs - before[name][1] == pytest.approx(
            sum(r.args["seconds"] for r in mine))


def test_a_phase_under_a_trace_time_span_is_keyed_by_its_name_and_anchor():
    """`lower/flash_attention` and its kin open once a lowered CALL: 24
    calls in one dispatch are one record a function, under the dispatch."""
    tel.ring_clear()
    with tel.span("unit/run", cat="serve"):
        with tel.span("unit/dispatch", cat="serve") as dispatch:
            for layer in range(24):
                with tel.span("lower/unit_kernel", cat="compile", layer=layer):
                    _phase(TRACE, 0.0002, "kernel")
                    _phase(TRACE, 0.0001, "index_map")
            _phase(TRACE, 0.0002, "kernel")
    # all of them `cat="compile"` (the search): anchored at the outermost
    with tel.span("unit/compile", cat="compile") as outer:
        for _ in range(3):
            with tel.span("unit/search", cat="compile"):
                _phase(TRACE, 0.0002, "cost")
    got = {(r.parent, r.args.get("under"), r.args["fun"]): r.args["count"]
           for r in _jax_records()}
    assert got == {(dispatch.id, "lower/unit_kernel", "kernel"): 24,
                   (dispatch.id, "lower/unit_kernel", "index_map"): 24,
                   (dispatch.id, None, "kernel"): 1,
                   (outer.id, "unit/search", "cost"): 3}


def test_phases_under_no_span_are_gathered_by_thread_and_function():
    tel.ring_clear()
    _phase(TRACE, 0.0002, "eager")
    _phase(TRACE, 0.0002, "eager")
    (rec,) = _jax_records()
    assert rec.parent == 0 and rec.args == {"fun": "eager", "count": 2,
                                            "seconds": pytest.approx(0.0004)}


# ------------------------------------------------- a set-up names its programs
def _unparented(min_s=0.010):
    return [(s.name, s.args.get("fun"), s.args["seconds"])
            for s in _jax_records()
            if s.parent == 0 and s.args["seconds"] >= min_s]


def test_a_training_setup_leaves_no_compile_phase_without_a_parent():
    tel.ring_clear()
    cfg = FFConfig(batch_size=32, only_data_parallel=True,
                   log_level="warning")
    m = FFModel(cfg)
    x = m.create_tensor([32, 16], name="x")
    m.dense(m.dense(x, 32, activation="relu", name="fc1"), 4, name="fc2")
    cm = m.compile(SGDOptimizer(lr=0.05),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    rng = np.random.default_rng(0)
    cm.fit(rng.normal(size=(64, 16)).astype(np.float32),
           rng.integers(0, 4, size=(64,)).astype(np.int32), epochs=1,
           verbose=False)
    spans = tel.ring_spans()
    (init,) = [s for s in spans if s.name == "compile/init"]
    leaves = [l for d in cm.params.values() for l in d.values()]
    assert init.args == {"parameters": sum(l.size for l in leaves),
                         "bytes": sum(l.nbytes for l in leaves),
                         "leaves": len(leaves)}
    # the init programs' phases lie under it, the step's under a dispatch
    under = {s.parent for s in _jax_records()}
    assert init.id in under
    assert under & {s.id for s in spans if s.name == "fit/dispatch"}
    assert _unparented() == []


def test_a_serving_setup_leaves_no_compile_phase_without_a_parent():
    from flexflow_tpu.models import GPT2Config, build_gpt2
    from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,
                                      compile_serving, gpt2_prompt_inputs,
                                      gpt2_step_inputs)

    tel.ring_clear()
    cfg = FFConfig(only_data_parallel=True, max_batch_slots=2,
                   kv_page_size=4, max_decode_len=4, log_level="warning")
    model = FFModel(cfg)
    build_gpt2(model, GPT2Config(vocab=64, seq=16, d_model=32, heads=2,
                                 layers=1, dropout=0.0), batch=2)
    eng = compile_serving(model)
    eng.init(seed=0)
    sched = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                        gpt2_step_inputs, eos_id=None)
    sched.run([Request(rid=i, prompt=[3, 5, 7 + i], max_new_tokens=3)
               for i in range(2)])
    spans = tel.ring_spans()
    (init,) = [s for s in spans if s.name == "serve/init"]
    assert init.args["leaves"] == len(
        [l for d in eng.params.values() for l in d.values()])
    assert init.args["bytes"] > 0 and init.args["parameters"] > 0
    by_id = {s.id: s for s in spans}

    def ancestors(s):
        out = []
        while s.parent in by_id:
            s = by_id[s.parent]
            out.append(s.name)
        return out

    programs = {a for s in _jax_records() for a in ancestors(s)}
    assert {"serve/init", "serve/compile_serving", "serve/admit",
            "serve/decode/dispatch"} <= programs
    assert _unparented() == []


# ------------------------------------------------ the import and the backend
def test_the_backends_start_says_that_it_was_up():
    """In this process JAX is up long since: a compile's first question is
    microseconds and says so, once a compile."""
    from flexflow_tpu.compiler.compile import resolve_machine

    tel.ring_clear()
    resolve_machine(FFConfig(mesh_shape={"data": 2}))
    (span,) = tel.ring_spans("start/backend")
    assert span.args == {"already_up": True} and span.cat == "start"


COLD = textwrap.dedent("""
    import json, sys
    import numpy as np
    import flexflow_tpu
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer, telemetry as tel
    from jax._src import xla_bridge

    assert not xla_bridge.backends_are_initialized()
    early = [s._asdict() for s in tel.ring_spans()]
    cfg = FFConfig(batch_size=16, only_data_parallel=True,
                   telemetry_dir=sys.argv[1], log_level="warning")
    m = FFModel(cfg)
    x = m.create_tensor([16, 8], name="x")
    m.dense(m.dense(x, 16, activation="relu", name="fc1"), 4, name="fc2")
    cm = m.compile(SGDOptimizer(lr=0.01),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=0)
    rng = np.random.default_rng(0)
    cm.fit(rng.normal(size=(64, 8)).astype(np.float32),
           rng.integers(0, 4, size=(64,)).astype(np.int32), epochs=1,
           verbose=False)
    import flexflow_tpu.serving
    tel.flush()
    print(json.dumps({"early": early, "t0": tel._T0_NS,
                      "ring": [s._asdict() for s in tel.ring_spans()
                               if s.name.startswith("start/")]}))
""")


def test_a_fresh_process_records_its_import_and_its_backends_start(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_ENABLE_COMPILATION_CACHE="0")
    done = subprocess.run([sys.executable, "-c", COLD, str(tmp_path)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    said = json.loads(done.stdout.strip().splitlines()[-1])
    # the import: once, first, from the epoch, in two parts that tile it
    (imp,) = said["early"]
    assert imp["name"] == "start/import" and imp["parent"] == 0
    assert imp["start_ns"] == said["t0"]
    parts = imp["args"]["jax_s"] + imp["args"]["package_s"]
    assert parts == pytest.approx((imp["end_ns"] - imp["start_ns"]) / 1e9,
                                  rel=0.01)
    assert imp["args"]["jax_s"] > 0 and imp["args"]["package_s"] > 0
    names = [s["name"] for s in said["ring"]]
    assert sorted(names) == ["start/backend", "start/import",
                             "start/import_serving"]
    backend = said["ring"][names.index("start/backend")]
    assert backend["args"] == {"already_up": False}     # the program began it
    serving = said["ring"][names.index("start/import_serving")]
    assert serving["start_ns"] >= imp["end_ns"]
    # the file: `start/import` was made before the sink opened and is its
    # first line; no time is negative; the report prints the block
    events = tel.read_events(str(tmp_path))
    assert events[0]["name"] == "start/import" and events[0]["ts"] == 0.0
    assert [e["name"] for e in events].count("start/import") == 1
    assert min(e["ts"] for e in events) >= 0.0
    assert not trace_report.validate_chrome(trace_report.to_chrome(events))
    block = trace_report.setup_lines(events)
    assert len(block) == 4 and all(l.startswith("[setup]") for l in block)
    assert "import" in block[0] and "jax" in block[0]
    assert "already_up=False" in block[1]
    assert "search" in block[2] and "init" in block[2] \
        and "caller" in block[2] and "4 leaves" in block[2]
    assert "trace+lower" in block[3] and "init" in block[3] \
        and "step" in block[3] and "backend compile" in block[3]


def test_a_sink_begins_with_the_start_records_it_has_not_had(tmp_path):
    tel.ring_clear()
    tel.record("start/unit", tel.now_us() - 5.0, cat="start", k=1)
    with tel.span("unit/before", cat="test"):
        pass
    first = str(tmp_path / "a")
    tel.configure(first)
    with tel.span("unit/after", cat="test"):
        pass
    tel.flush()
    assert [e["name"] for e in tel.read_events(first)] == ["start/unit",
                                                           "unit/after"]
    # the same file again after a shutdown: nothing twice
    tel.shutdown()
    tel.configure(first)
    tel.flush()
    assert [e["name"] for e in tel.read_events(first)].count("start/unit") == 1


def test_the_report_attributes_compile_phases_by_the_span_that_holds_them():
    def x(name, ts, dur, tid="MainThread", **args):
        return {"name": name, "ph": "X", "ts": ts * 1e6, "dur": dur * 1e6,
                "pid": 7, "tid": tid, "args": args}

    events = [
        x("start/import", 0, 10, jax_s=7.5, package_s=2.5),
        x("start/import_serving", 12, 0.5),
        x("serve/compile_serving", 13, 1.0),
        x("start/backend", 13, 0.25, already_up=False),
        x("jax/trace", 13.5, 0.2, fun="cost", seconds=0.2),
        x("serve/init", 15, 2.0, bytes=5e9, leaves=291, parameters=2.5e9),
        x("jax/lower", 15.5, 1.0, fun="jit(init_fn)", seconds=1.0),
        x("serve/run", 20, 30),
        x("serve/admit", 20, 12),
        x("jax/trace", 20.5, 5.0, fun="_prefill", seconds=4.0),
        x("jax/trace", 21.0, 9.0, fun="body", count=300, seconds=1.0),
        x("jax/backend_compile", 26, 3.0, fun="jit(_prefill)", seconds=3.0),
        x("serve/decode/dispatch", 33, 8),
        x("jax/lower", 34, 6.0, fun="jit(_decode)", seconds=6.0),
        x("jax/trace", 45, 0.5, fun="token_gaps", seconds=0.5),
        x("jax/trace", 33.5, 1.0, tid="worker", fun="elsewhere", seconds=1.0),
    ]
    assert trace_report.setup_lines(events) == [
        "[setup] pid 7: import 10.00s (jax 7.50s, package 2.50s) "
        "+ import_serving 0.50s",
        "[setup]   backend 0.25s (already_up=False)",
        # 10..20 less 12-12.5, 13-14 and 15-17
        "[setup]   search 1.00s  init 2.00s (5.00 GB, 291 leaves)  caller "
        "6.50s (from import's end to the first serve/run/fit/call, under no "
        "span)",
        "[setup]   trace+lower 13.70s: step 6.00s, wave 5.00s, other 1.50s, "
        "init 1.00s, search 0.20s; backend compile 3.00s"]
    assert trace_report.setup_lines([e for e in events
                                     if e["name"] != "start/import"]) == []
