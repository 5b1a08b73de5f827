"""benchmarks/readers/setup_span.py on a hand-made ring: each of the six
set-up metrics through its metrics/<name>.json, the union in the caller's
interval with nested and overlapping spans, the closing sum whose parts add
up to the interval with jax/* records under a search and an init span, and
None where the ring has no such spans. Nothing here times anything."""

import importlib
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"
sys.path.insert(0, str(BENCH))

from flexflow_tpu import telemetry as tel  # noqa: E402
from harness import manifest as mf  # noqa: E402

NEW = ["setup_import_s", "setup_init_s", "setup_caller_s",
       "setup_trace_lower_s.wave", "setup_trace_lower_s.step",
       "setup_search_s"]
MS = 1_000_000      # ns


class Run:
    """What run.py's RunView gives a reader."""

    def __init__(self, kind, facts=None, trace=None):
        self.cell = types.SimpleNamespace(traffic={"kind": kind})
        self.facts = facts or {}
        self.trace, self.window = trace, None
        self.notes = []

    def note(self, **kw):
        self.notes.append(kw)


class Ring:
    """Builds Span records the way telemetry numbers them."""

    def __init__(self):
        self.spans, self._ids = [], iter(range(1, 10_000))

    def add(self, name, start_ms, end_ms, parent=None, thread="MainThread",
            **args):
        s = tel.Span(name, int(start_ms * MS), int(end_ms * MS), thread,
                     parent.id if parent else 0, args or None,
                     next(self._ids))
        self.spans.append(s)
        return s

    def install(self, monkeypatch):
        monkeypatch.setattr(tel, "ring_spans", lambda: list(self.spans))


def read(run, name):
    """A metric through its metrics/<name>.json, as run.py reads it."""
    spec = mf.read_named("metrics", name)
    reader = importlib.import_module(f"readers.{spec['reader']}")
    return reader.read(run, name, **spec["args"])


def serving_ring():
    """A serving process up to its traced run, times in ms from the
    package's first line. What no span covers: 10 000-12 000, 12 500-13 000,
    14 000-14 500, 16 800-17 000, 27 000-27 500 (3.7 s)."""
    r = Ring()
    r.add("start/import", 0, 10_000, jax_s=7.5, package_s=2.5)
    r.add("start/import_serving", 12_000, 12_500)
    # the search: a child (nested), phases gathered under the child's NAME
    # and anchored at the search, one of its own
    search = r.add("serve/compile_serving", 13_000, 14_000, slots=16)
    r.add("start/backend", 13_000, 13_001, parent=search, already_up=True)
    r.add("serve/search_prefill", 13_100, 13_600, parent=search)
    r.add("jax/trace", 13_150, 13_550, parent=search, fun="cost", count=90,
          seconds=0.2, under="serve/search_prefill")
    r.add("jax/lower", 13_700, 13_800, parent=search, fun="jit(zeros)",
          seconds=0.1)
    # weight init: 2.0 s, 1.8 s of them JAX's phases
    init = r.add("serve/init", 14_500, 16_500, bytes=5e9, leaves=291)
    r.add("jax/trace", 14_500, 14_800, parent=init, fun="init_fn",
          seconds=0.3)
    r.add("jax/lower", 14_800, 15_300, parent=init, fun="jit(init_fn)",
          seconds=0.5)
    r.add("jax/backend_compile", 15_300, 16_300, parent=init,
          fun="jit(init_fn)", seconds=1.0)
    # a span that overlaps the init's end, and one on another track
    r.add("unit/overlaps", 16_000, 16_800)
    r.add("serve/req/prefill", 16_000, 27_400, thread="slot0", rid=0)
    # warm-up's run: the wave with its commit, then the step
    warm = r.add("serve/run", 17_000, 27_000, requests=6)
    admit = r.add("serve/admit", 17_000, 23_000, parent=warm, wave=1)
    disp = r.add("serve/prefill/dispatch", 17_100, 22_000, parent=admit)
    r.add("jax/trace", 17_100, 20_100, parent=disp, fun="_prefill",
          seconds=3.0)
    r.add("jax/trace", 17_200, 20_000, parent=disp, fun="body", count=300,
          seconds=1.0, under="lower/flash_attention")
    r.add("jax/lower", 20_100, 20_600, parent=disp, fun="jit(_prefill)",
          seconds=0.5)
    r.add("jax/backend_compile", 20_600, 21_000, parent=disp,
          fun="jit(_prefill)", seconds=0.4)
    commit = r.add("serve/prefill/commit", 22_000, 22_900, parent=admit)
    kv = r.add("serve/prefill/commit_kv", 22_000, 22_800, parent=commit)
    r.add("jax/trace", 22_000, 22_200, parent=kv, fun="_commit_prefill",
          seconds=0.2)
    step = r.add("serve/decode/dispatch", 23_000, 26_000, parent=warm,
                 window=1)
    r.add("jax/trace", 23_000, 24_500, parent=step, fun="_decode",
          seconds=1.5)
    r.add("jax/lower", 24_500, 25_500, parent=step, fun="jit(_decode)",
          seconds=1.0)
    r.add("jax/backend_compile", 25_500, 25_800, parent=step,
          fun="jit(_decode)", seconds=0.3)
    # the caller's own jit between warm-up and the window: under no span
    r.add("jax/trace", 27_100, 27_200, fun="traffic", seconds=0.1)
    win = r.add("serve/run", 27_500, 80_000, requests=18)
    r.add("serve/decode/dispatch", 30_000, 30_004, parent=win, window=1)
    r.add("jax/trace", 81_000, 82_000, fun="token_gaps", seconds=1.0)
    r.add("serve/run", 90_000, 95_000, requests=2)      # the traced run
    return r


EXPECTED = {"setup_import_s": 10.5, "setup_init_s": 2.0,
            "setup_caller_s": 3.7, "setup_trace_lower_s.wave": 4.7,
            "setup_trace_lower_s.step": 2.5, "setup_search_s": 1.0}


def test_each_metric_reads_its_part_of_a_serving_setup(monkeypatch):
    serving_ring().install(monkeypatch)
    run = Run("serve", facts={"setup_s": 27.9}, trace=object())
    assert {n: read(run, n) for n in NEW} == {
        n: pytest.approx(v) for n, v in EXPECTED.items()}
    # one note a run, whichever metric is read first
    assert len(run.notes) == 1 and run.notes[0]["metric"] == "setup_s"


def test_the_closing_sums_parts_add_up_to_the_interval(monkeypatch):
    serving_ring().install(monkeypatch)
    run = Run("serve", facts={"setup_s": 27.9}, trace=object())
    read(run, "setup_caller_s")
    (note,) = run.notes
    assert note["interval_s"] == pytest.approx(27.5)
    assert note["setup_s"] == 27.9
    assert note["setup_s_minus_interval_s"] == pytest.approx(0.4)
    # the three that tile the interval; the import counts the serving
    # package's too, which is a span and is not counted as covered
    assert (note["import_s"], note["caller_s"], note["covered_s"]) == (
        pytest.approx(10.5), pytest.approx(3.7), pytest.approx(13.3))
    assert note["import_s"] + note["caller_s"] + note["covered_s"] == \
        pytest.approx(note["interval_s"])
    assert note["imports"] == {
        "start/import": {"jax_s": 7.5, "package_s": 2.5, "seconds": 10.0},
        "start/import_serving": {"seconds": 0.5}}
    # the covered time, in parts that do not overlap: a record under the
    # search or the init is in trace_lower_s / backend_compile_s and NOT in
    # the span's self time
    assert note["trace_lower_s"] == {
        "wave": pytest.approx(4.7), "step": pytest.approx(2.5),
        "init": pytest.approx(0.8),
        "serve/search_prefill": pytest.approx(0.2),
        "serve/compile_serving": pytest.approx(0.1)}
    assert list(note["trace_lower_s"])[:3] == ["wave", "step", "init"]
    assert note["backend_compile_s"] == pytest.approx(1.7)
    assert note["search_self_s"] == pytest.approx(1.0 - 0.3)
    assert note["init_self_s"] == pytest.approx(2.0 - 1.8)
    assert note["program_other_s"] == pytest.approx(2.4)
    assert sum(note["trace_lower_s"].values()) + note["backend_compile_s"] \
        + note["search_self_s"] + note["init_self_s"] \
        + note["program_other_s"] == pytest.approx(note["covered_s"])
    # the largest records by program, and what lies under no span at all
    assert note["largest"]["wave"][:2] == [
        ["jax/trace", "_prefill", 1, 3.0], ["jax/trace", "body", 300, 1.0]]
    assert note["largest"]["step"][0] == ["jax/trace", "_decode", 1, 1.5]
    assert note["largest"]["init"] == [
        ["jax/lower", "jit(init_fn)", 1, 0.5], ["jax/trace", "init_fn", 1, 0.3]]
    assert note["unparented"] == {
        "seconds": pytest.approx(0.1),
        "largest": [["jax/trace", "traffic", 1, 0.1]]}
    assert note["backend_start"] == {"seconds": pytest.approx(0.001),
                                     "already_up": [True]}
    assert note["records"] == 28     # everything that ended before it
    assert note["gathered"] == 2


def test_a_training_setup_reads_the_step_under_fit_dispatch(monkeypatch):
    r = Ring()
    r.add("start/import", 0, 9_000, jax_s=7.0, package_s=2.0)
    search = r.add("compile/compile_model", 9_500, 9_700)
    r.add("start/backend", 9_500, 9_501, parent=search, already_up=True)
    init = r.add("compile/init", 10_000, 13_000, leaves=389)
    r.add("jax/backend_compile", 10_500, 12_500, parent=init,
          fun="jit(init_fn)", seconds=2.0)
    warm = r.add("fit/call", 14_000, 30_000, steps=2)
    disp = r.add("fit/dispatch", 14_100, 29_000, parent=warm, kind="1")
    r.add("jax/trace", 14_100, 20_000, parent=disp, fun="train_step",
          seconds=5.0)
    r.add("jax/trace", 14_200, 19_000, parent=disp, fun="kernel", count=48,
          seconds=0.9, under="lower/flash_attention")
    r.add("jax/lower", 20_000, 24_000, parent=disp, fun="jit(train_step)",
          seconds=4.0)
    calls = [r.add("fit/call", t, t + 900, steps=20)
             for t in (31_000, 32_000, 33_000)]
    r.add("compile/init", 40_000, 41_000, leaves=389)   # the reference's
    r.install(monkeypatch)
    run = Run("train", facts={"fit_seconds": [0.9, 0.9]}, trace=object())
    assert read(run, "setup_trace_lower_s.step") == pytest.approx(9.9)
    assert read(run, "setup_init_s") == pytest.approx(3.0)
    assert read(run, "setup_search_s") == pytest.approx(0.2)
    assert read(run, "setup_import_s") == pytest.approx(9.0)
    # 9 000 to the first window call at 31 000, less 0.2 + 3 + 16
    assert read(run, "setup_caller_s") == pytest.approx(22.0 - 19.2)
    # no wave in a training set-up: nothing to read, not 0
    assert read(run, "setup_trace_lower_s.wave") is None
    (note,) = run.notes
    assert note["interval_s"] == pytest.approx(calls[0].start_ns / 1e9)
    assert "setup_s" not in note        # the cell stamped none here


def test_a_ring_without_the_spans_is_none(monkeypatch):
    # a parent commit: the compile phases and the roots, no start/import
    r = Ring()
    r.add("jax/trace", 0, 2000, fun="step", seconds=1.5)
    r.add("jax/trace", 100, 600, count=40, seconds=0.02)
    warm = r.add("serve/run", 1000, 9500, requests=6)
    r.add("serve/admit", 1000, 5000, parent=warm)
    r.add("serve/run", 10_000, 14_000, requests=3)
    r.add("serve/run", 15_000, 15_500, requests=1)
    r.install(monkeypatch)
    run = Run("serve", trace=object())
    assert [read(run, n) for n in NEW] == [None] * 6 and run.notes == []
    # the spans, and no root of the window: a note says so
    only = Ring()
    only.add("start/import", 0, 9_000, jax_s=7.0, package_s=2.0)
    only.install(monkeypatch)
    run = Run("serve", trace=object())
    assert [read(run, n) for n in NEW] == [None] * 6
    assert len(run.notes) == 1 and "serve/run" in \
        run.notes[0]["nothing_to_read"]
    # a program from before the ring
    monkeypatch.delattr(tel, "ring_spans")
    old = Run("train", facts={"fit_seconds": [1.0]}, trace=object())
    assert [read(old, n) for n in NEW] == [None] * 6 and old.notes == []


def test_the_six_metrics_are_declared_with_a_file_and_the_reader():
    manifest = mf.load_manifest()
    entries = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]]
    serving = [c for c in cells if mf.load_cell(manifest, c).traffic["kind"]
               == "serve"]
    for name in NEW:
        spec = mf.read_named("metrics", name)
        assert spec["reader"] == "setup_span" and spec["doc"]
        entry = entries[name]
        assert mf.NAME_RE.match(name) and mf.UNIT_RE.match(entry["unit"])
        assert (entry["source"], entry["moves"], entry["better"],
                entry["unit"]) == ("program_span", "setup_s", "lower", "s")
        listed = set(entry["workloads"])
        # every cell that stood when the metrics were added, the wave in
        # the serving ones alone
        assert listed <= set(serving if name.endswith(".wave") else cells)
        assert {"gpt2-medium.serve-chat",
                "Keye-VL-2.0-30B-A3B.serve-longprompt"} <= listed
        assert ("gpt2-medium.train-b8" in listed) == \
            (not name.endswith(".wave"))
    assert {entries[n]["layer"] for n in NEW} == {"start-up", "compiler",
                                                  "search"}
