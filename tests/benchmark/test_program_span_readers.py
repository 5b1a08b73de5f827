"""The readers of the program's own spans (benchmarks/readers/ring_stat.py,
span_device.py, span_idle.py) on a hand-made ring and a hand-made reduced
trace: window selection, every statistic, the clock alignment at either end,
device time inside a span, idle time by span, and None with a note where
there is nothing to read. Nothing here times anything."""

import importlib
import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"
sys.path.insert(0, str(BENCH))

from flexflow_tpu import telemetry as tel  # noqa: E402
from harness import manifest as mf  # noqa: E402
from harness.trace_reduce import Op, Trace  # noqa: E402
from readers import ring_stat, span_device, span_idle  # noqa: E402

NEW = ["prefill_logits_copy_ms", "prefill_logits_host_mb",
       "prefill_useful_token_share", "prefill_device_ms",
       "decode_step_device_ms", "decode_host_ms_per_step",
       "idle_unattributed.serve", "fit_call_ends_ms.train",
       "fit_materializations_per_100steps", "fit_stall_ms.train",
       "trace_lower_s", "backend_compile_s"]
MS = 1_000_000      # ns
# the ring's clock and the trace's differ by this much in these tests
SKEW = 7_000_000_000


class Run:
    """What run.py's RunView gives a reader."""

    def __init__(self, kind, facts=None, trace=None, window=None):
        self.cell = types.SimpleNamespace(traffic={"kind": kind})
        self.facts = facts or {}
        self.trace, self.window = trace, window
        self.notes = []

    def note(self, **kw):
        self.notes.append(kw)


class Ring:
    """Builds Span records the way telemetry numbers them."""

    def __init__(self):
        self.spans, self._ids = [], iter(range(1, 10_000))

    def add(self, name, start_ms, end_ms, parent=0, **args):
        s = tel.Span(name, int(start_ms * MS), int(end_ms * MS), "MainThread",
                     parent, args or None, next(self._ids))
        self.spans.append(s)
        return s

    def install(self, monkeypatch):
        monkeypatch.setattr(tel, "ring_spans", lambda: list(self.spans))


def read(run, name):
    """A metric through its metrics/<name>.json, as run.py reads it."""
    spec = mf.read_named("metrics", name)
    reader = importlib.import_module(f"readers.{spec['reader']}")
    return reader.read(run, name, **spec["args"])


# ------------------------------------------------------------ serving ring
def serve_ring():
    """Warm-up run (long: it compiles), the window's run, the traced run."""
    r = Ring()
    r.add("jax/trace", 0, 2000, fun="step", seconds=1.5)
    r.add("jax/trace", 100, 600, count=40, seconds=0.02)   # short ones gathered
    r.add("jax/lower", 2000, 5000, fun="step", seconds=3.0)
    r.add("jax/backend_compile", 5000, 9000, fun="step", seconds=4.0)
    warm = r.add("serve/run", 1000, 9500, requests=6)
    r.add("serve/prefill/logits_to_host", 9100, 9200, parent=warm.id, bytes=999)
    win = r.add("serve/run", 10_000, 14_000, requests=3)
    for wave, (t, prompt) in enumerate([(10_000, 100), (11_000, 300)], 1):
        a = r.add("serve/admit", t, t + 500, parent=win.id, wave=wave,
                  requests=1, prompt_tokens=prompt, padded_tokens=2000)
        r.add("serve/prefill/logits_to_host", t + 100, t + 300 + 100 * wave,
              parent=a.id, bytes=4_000_000 * wave)
    for w, t in enumerate([12_000, 12_100], 1):
        for k in range(2):
            r.add("serve/decode/dispatch", t + 10 * k, t + 10 * k + 4,
                  parent=win.id, window=w)
        r.add("serve/decode/window_sync", t + 30, t + 40, parent=win.id,
              window=w, steps=2)
        r.add("serve/decode/commit", t + 40, t + 46, parent=win.id, window=w,
              tokens_committed=6)
    r.add("jax/backend_compile", 14_100, 14_200, fun="late", seconds=0.1)
    return r, win


def test_serving_ring_metrics_read_the_window_before_the_traced_run(monkeypatch):
    ring, win = serve_ring()
    traced = ring.add("serve/run", 15_000, 15_500, requests=1)
    ring.add("serve/prefill/logits_to_host", 15_100, 15_200, parent=traced.id,
             bytes=1)
    ring.install(monkeypatch)
    run = Run("serve", trace=Trace({}, []))
    assert read(run, "prefill_logits_copy_ms") == pytest.approx(350.0)
    assert read(run, "prefill_logits_host_mb") == pytest.approx(6.0)
    assert read(run, "prefill_useful_token_share") == pytest.approx(10.0)
    # (4 dispatches x 4 ms + 2 commits x 6 ms) / 4 steps
    assert read(run, "decode_host_ms_per_step") == pytest.approx(7.0)
    # compile phases that ended before the window began, each counted by its
    # own seconds; the late one is the reference's, after the window
    assert read(run, "trace_lower_s") == pytest.approx(1.5 + 0.02 + 3.0)
    assert read(run, "backend_compile_s") == pytest.approx(4.0)
    # without a traced run the window's root is the last one
    assert ring_stat.window_roots(Run("serve"), ring.spans) == [traced]


# ----------------------------------------------------------- training ring
def fit_ring(stall_ms=0.0):
    r = Ring()
    r.add("jax/lower", 0, 900, fun="train_step", seconds=0.9)
    warm = r.add("fit/call", 1000, 1900, steps=2)
    r.add("fit/epoch_end_sync", 1800, 1850, parent=warm.id)
    calls, t = [], 1000
    for i in range(3):
        t += 1000 + (stall_ms if i == 2 else 0.0)   # one call after the other
        extra = stall_ms if i == 1 else 0.0
        c = r.add("fit/call", t, t + 900 + extra, steps=20)
        r.add("fit/setup", t, t + 10 + i, parent=c.id)
        for k in range(20):
            r.add("fit/dispatch", t + 20 + 40 * k, t + 22 + 40 * k,
                  parent=c.id, kind="1", steps=1)
        r.add("fit/barrier_sync", t + 810, t + 840 + extra, parent=c.id)
        r.add("fit/epoch_end_sync", t + 860 + extra, t + 880 + extra,
              parent=c.id)
        r.add("fit/epoch", t + 15, t + 885 + extra, parent=c.id, steps=20)
        r.add("fit/finish", t + 890 + extra, t + 895 + extra, parent=c.id)
        calls.append(c)
    return r, calls


def test_training_ring_metrics_read_the_calls_before_the_traced_one(monkeypatch):
    ring, calls = fit_ring(stall_ms=6000.0)
    ring.install(monkeypatch)
    # three fit/call after the warm-up; the last is the traced one
    run = Run("train", facts={"fit_seconds": [0.9, 6.9]}, trace=Trace({}, []))
    assert ring_stat.window_roots(run, ring.spans) == calls[:2]
    assert read(run, "fit_call_ends_ms.train") == pytest.approx(15.5)
    assert read(run, "fit_materializations_per_100steps") == pytest.approx(5.0)
    assert read(run, "trace_lower_s") == pytest.approx(0.9)
    assert read(run, "fit_stall_ms.train") == pytest.approx(3000.0)
    (note,) = [n for n in run.notes if n["metric"] == "fit_stall_ms.train"]
    # the child that holds the excess, against its usual length; fit/epoch
    # spans the others and is not one of them
    assert note["excess_by_child_ms"][0] == ["fit/barrier_sync",
                                             pytest.approx(6000.0)]
    assert "fit/epoch" not in dict(note["excess_by_child_ms"])
    assert note["outside_children_ms"] == pytest.approx(
        6900.0 - (10 + 1 + 40 + 6030 + 20 + 5))
    # a window of one call has no stall to speak of
    one = Run("train", facts={"fit_seconds": [0.9]}, trace=Trace({}, []))
    assert read(one, "fit_stall_ms.train") is None


def test_nothing_to_read_is_none_with_a_note(monkeypatch):
    ring, _ = fit_ring()
    ring.install(monkeypatch)
    # the cell says five calls, the ring holds two before the traced one
    run = Run("train", facts={"fit_seconds": [1.0] * 5}, trace=Trace({}, []))
    assert read(run, "fit_call_ends_ms.train") is None
    assert "fit/call" in run.notes[-1]["nothing_to_read"]
    # a program from before the ring: nothing, and no note needed
    monkeypatch.delattr(tel, "ring_spans")
    old = Run("train", facts={"fit_seconds": [1.0]}, trace=Trace({}, []))
    assert [read(old, n) for n in NEW if mf.read_named("metrics", n)["reader"]
            == "ring_stat"] == [None] * 9
    assert span_device.aligned_root(old, "x") is None
    assert read(old, "idle_unattributed.serve") is None and old.notes == []


# ------------------------------------------------- alignment, device, idle
def traced_serving(monkeypatch, root_early_ms=0.0):
    """A traced run of 1 s on the ring's clock from 20 000 ms; the trace's
    clock is SKEW ahead. One wave, one decode window of two steps, a nap."""
    r = Ring()
    r.add("serve/run", 1000, 5000, requests=9)           # the window's
    t0 = 20_000
    root = r.add("serve/run", t0 - root_early_ms, t0 + 1000, requests=2)
    a = r.add("serve/admit", t0 + 100, t0 + 400, parent=root.id, wave=1,
              requests=1, prompt_tokens=10, padded_tokens=100)
    r.add("serve/prefill/device_wait", t0 + 150, t0 + 250, parent=a.id)
    r.add("serve/prefill/logits_to_host", t0 + 250, t0 + 390, parent=a.id,
          bytes=8)
    r.add("serve/req/prefill", t0, t0 + 1000, parent=a.id, rid=0)  # tiles all
    for k in range(2):
        r.add("serve/decode/dispatch", t0 + 500 + 12 * k, t0 + 510 + 12 * k,
              parent=root.id, window=1)
    r.add("serve/decode/window_sync", t0 + 540, t0 + 560, parent=root.id,
          window=1, steps=2)
    r.add("serve/idle_wait", t0 + 700, t0 + 900, parent=root.id)
    r.install(monkeypatch)

    def at(ms):
        return int((20_000 + ms) * MS) + SKEW
    ops = [Op("fusion.1", at(160), at(240)),            # the wave: 80 ms,
           Op("while.2", at(200), at(245)),             # nested: union 85 ms
           Op("fusion.3", at(505), at(509)), Op("fusion.4", at(525), at(531)),
           Op("fusion.5", at(950), at(960))]            # under no span
    host = [Op("bench/traced_run", at(-5), at(1000))]
    trace = Trace({0: ops}, host)
    return Run("serve", trace=trace, window=(at(0), at(1000))), root


def test_end_alignment_and_device_time_inside_a_span(monkeypatch):
    run, root = traced_serving(monkeypatch)
    got_root, kids, offset = span_device.aligned_root(run, "x")
    assert got_root == root and offset == SKEW
    assert len(kids) == 8 and root not in kids
    assert read(run, "prefill_device_ms") == pytest.approx(85.0)
    # first dispatch (500) to the end of the sync (560): 4 + 6 ms over 2 steps
    assert read(run, "decode_step_device_ms") == pytest.approx(5.0)
    assert run.notes[-1] == {"metric": "decode_step_device_ms",
                             "intervals": 1, "per": 2, "wall_ms": 60.0,
                             "device_busy_ms": 10.0}
    # an interval that is not wholly in the steady window is left out
    run.window = (run.window[0] + 450 * MS, run.window[1])
    assert read(run, "prefill_device_ms") is None
    assert read(run, "decode_step_device_ms") == pytest.approx(5.0)


def test_idle_time_goes_to_the_innermost_span_and_the_rest_is_unattributed(
        monkeypatch):
    run, _ = traced_serving(monkeypatch)
    # idle gaps, ms from the run's start: 0-160 (midpoint 80: no span),
    # 245-505 (375: logits_to_host inside admit), 509-525 (517: dispatch),
    # 531-950 (740.5: idle_wait), 960-1000 (980: no span)
    assert read(run, "idle_unattributed.serve") == pytest.approx(
        100.0 * (160 + 40) / (160 + 260 + 16 + 419 + 40))
    (note,) = run.notes
    by_span = dict(note["idle_by_span_s"])
    assert by_span == {"serve/idle_wait": pytest.approx(0.419),
                       "serve/prefill/logits_to_host": pytest.approx(0.260),
                       "serve/decode/dispatch": pytest.approx(0.016)}
    assert note["unattributed_s"] == pytest.approx(0.2)
    assert note["idle_s"] == pytest.approx(0.895)


def test_start_alignment_for_training(monkeypatch):
    ring, calls = fit_ring()
    ring.install(monkeypatch)
    start = calls[-1].start_ns + SKEW
    host = [Op("bench/fit", 1, 2),
            Op("bench/traced_fit", start, start + 2000 * MS)]  # blocks on params
    run = Run("train", trace=Trace({0: []}, host))
    root, kids, offset = span_device.aligned_root(run, "x")
    assert root == calls[-1] and offset == SKEW
    assert {s.name for s in kids} >= {"fit/setup", "fit/dispatch", "fit/finish"}


@pytest.mark.parametrize("why, kw", [
    ("the root sticks out of the anchor", {"root_early_ms": 50.0}),
    ("no anchor in the trace", {"drop": "anchor"}),
    ("no root in the ring", {"drop": "root"}),
])
def test_no_alignment_is_none_with_a_note(monkeypatch, why, kw):
    drop = kw.pop("drop", None)
    run, _ = traced_serving(monkeypatch, **kw)
    if drop == "anchor":
        run.trace.host.clear()
    if drop == "root":
        monkeypatch.setattr(tel, "ring_spans", lambda: [])
    for name in ("prefill_device_ms", "decode_step_device_ms",
                 "idle_unattributed.serve"):
        assert read(run, name) is None
    assert len(run.notes) == 3 and all("not_aligned" in n for n in run.notes)


def test_innermost_stops_where_nothing_earlier_reaches():
    r = Ring()
    spans = [r.add("a", 0, 100), r.add("b", 10, 20), r.add("c", 30, 40),
             r.add("d", 200, 300)]
    starts = [s.start_ns for s in spans]
    ends = [100 * MS, 100 * MS, 100 * MS, 300 * MS]
    at = lambda ms: span_idle.innermost(spans, starts, ends, int(ms * MS))  # noqa: E731
    assert [at(5), at(15), at(25), at(35), at(50), at(150), at(250), at(301)] \
        == ["a", "b", "a", "c", "a", None, "d", None]


# --------------------------------------------------------------- manifest
def test_every_new_metric_is_declared_with_a_file_and_a_reader():
    manifest = mf.load_manifest()
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert [n for n in NEW if n not in entries] == []
    aligned = {"prefill_device_ms", "decode_step_device_ms",
               "idle_unattributed.serve"}
    for name in NEW:
        spec = mf.read_named("metrics", name)           # raises where missing
        reader = importlib.import_module(f"readers.{spec['reader']}")
        assert callable(reader.read) and spec["doc"]
        if spec["reader"] == "ring_stat":
            assert spec["args"]["stat"] in ring_stat.STATS
        assert entries[name]["source"] == ("device_trace" if name in aligned
                                           else "program_span")
    both = {"gpt2-medium.train-b8", "gpt2-medium.serve-chat"}
    assert set(entries["trace_lower_s"]["workloads"]) == both == \
        set(entries["backend_compile_s"]["workloads"])
    # appended: what the benchmark had keeps its place
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-len(NEW):] == NEW and names[0] == "search_s"


def test_rehearsal_lists_the_new_names_under_would_report():
    """A traced rehearsal of the serving cell on the CPU: the new spans and
    readers do not disturb the run, and the line lists the new metrics."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="0")
    done = subprocess.run(
        [sys.executable, str(BENCH / "rehearse.py"), "--workload",
         "gpt2-tiny.serve", "--seed", str(2 ** 31 + 5), "--seconds", "1",
         "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and "metrics" not in last
    serving = [n for n in NEW if "fit_" not in n]
    assert [n for n in serving if n not in last["would_report"]] == []
    train = mf.load_cell(mf.load_manifest(BENCH / "rehearsal.json"),
                         "gpt2-tiny.train")
    assert {n for n in NEW if "fit_" in n} | {"trace_lower_s"} <= \
        {m["name"] for m in train.per_layer}
