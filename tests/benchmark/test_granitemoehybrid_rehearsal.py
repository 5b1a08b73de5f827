"""The `granitemoehybrid` family in the benchmark: its tiny cell through
rehearse_granitemoehybrid.py (the serving cell's whole control flow on the
CPU backend; the family's own manifest rehearsal_granitemoehybrid.json, since
rehearsal.json is the benchmark's and not a model PR's to edit), and
the metrics this family brought, read from a hand-made ring and a hand-made
reduced trace: the routing counters that ride on the program's spans, the
state commit, and the two shares of a peak (readers/span_need.py); and how
tight the cell's `correct` is: the served-token rule at the logits' own
scale, with an fp8 engine put through it (control.py) and the logits check
(logits_check_granitemoehybrid.py), both at the tiny size. Nothing here
times anything."""

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
from harness import flops_granitemoehybrid as flops  # noqa: E402
from harness import manifest as mf  # noqa: E402
from harness import peaks  # noqa: E402
from harness.trace_reduce import Op, Trace  # noqa: E402

CELL = "granite-4.0-h-small.serve-chat"
NEW = ["moe_held_pair_share.decode", "moe_expert_load_max_over_mean.decode",
       "decode_step_hbm_roofline.granite", "prefill_mfu.granite",
       "state_commit_ms"]
CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="0")
MS = 1_000_000
SKEW = 7_000_000_000
REHEARSAL = "rehearsal_granitemoehybrid.json"


def test_every_cell_config_and_reader_of_the_familys_manifest_is_found_by_name():
    """test_benchmark_harness.py's check of BENCHMARK.json and rehearsal.json,
    asked of this family's rehearsal manifest as well."""
    from test_benchmark_harness import (
        test_every_cell_config_and_reader_is_found_by_name as found_by_name)

    found_by_name(BENCH / REHEARSAL)
    manifest = mf.load_manifest(BENCH / REHEARSAL)
    real = {w["name"] for w in mf.load_manifest()["workloads"]}
    assert [w["stands_for"] for w in manifest["workloads"]] == [CELL]
    assert CELL in real


def test_rehearsal_runs_the_cells_control_flow_and_reports_no_metric():
    done = subprocess.run(
        [sys.executable, str(BENCH / "rehearse_granitemoehybrid.py"), "--workload",
         "granite-tiny.serve", "--seed", str(2 ** 31 + 3), "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=CPU_ENV, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
    assert {"setup_s", "serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms"} \
        <= set(last["would_report"])


def _tool(script, *args, rehearsal=("--rehearsal",)):
    done = subprocess.run(
        [sys.executable, str(BENCH / script), *rehearsal, "--workload",
         "granite-tiny.serve", *args],
        cwd=ROOT, env=CPU_ENV, capture_output=True, text=True, timeout=600)
    lines = [json.loads(l) for l in done.stdout.strip().splitlines()]
    return done, lines


def test_the_served_token_rule_counts_at_the_logits_own_scale(monkeypatch):
    """cells/serve.py floors the scale at 1; this family's logits are a
    seventh of that wide, so it hands the gaps out in units of two of the
    row's own scales (and the scale as 1): the rule's 8 are 16 bf16 ulps of
    what the logits are, and not a fifth of their range."""
    import numpy as np

    from families import granitemoehybrid as family

    cfg = mf.load_cell(mf.load_manifest(BENCH / REHEARSAL),
                       "granite-tiny.serve").config
    gap = np.array([[0.0, 0.001, 0.002]])
    scale = np.array([[0.125, 0.125, 0.25]])
    monkeypatch.setattr(family, "reference_params", lambda params, cfg: params)
    monkeypatch.setattr(family.reference, "token_gaps",
                        lambda params, ids, hp: (gap, scale))
    got, unit = family.reference_token_gaps(cfg, None, None, None)
    assert (unit == 1).all()
    # as cells/serve.py counts them
    ulps = got / (np.maximum(1.0, unit) * 2.0 ** -8)
    assert np.allclose(ulps, [[0.0, 1.024, 1.024]])
    assert 8.0 * family.GAP_UNIT_ROW_SCALES == 16.0


def test_an_fp8_engine_goes_through_the_cells_rule():
    """control.py's flow: per seed a sound and a lowered window, each judged
    by cells/serve.py's parity against the weights as initialised. At the
    tiny size (some 40 served tokens a window) only the order of the two
    readings is asked for; the chip run at the published widths must come
    out `tight` (PERF.md has its readings)."""
    done, lines = _tool("control.py", "--seeds", f"5,{2 ** 31 + 11}",
                        "--seconds", "2", rehearsal=("--rehearsal", REHEARSAL))
    windows = [l for l in lines if l.get("fact") == "control_window"]
    assert [(w["seed"], w["engine"]) for w in windows] == [
        (5, "sound"), (5, "low"), (2 ** 31 + 11, "sound"), (2 ** 31 + 11, "low")]
    assert all(w["ok"] for w in windows if w["engine"] == "sound")
    last = lines[-1]
    assert done.returncode == (0 if last["tight"] else 1)
    assert last["sound_worst_gap_bf16_ulps"] < 1 < last["low_worst_gap_bf16_ulps"]


def test_the_logits_check_parts_bf16_from_fp8():
    """Prefill, then decode through the cache, against the reference's full
    forward on logits: the bf16 program within the tolerance, the reference
    with fp8 weights outside it (0.045 of the scale at this size, between
    0.031 and 0.063; 0.10 at the published widths, on the chip)."""
    done, lines = _tool("logits_check_granitemoehybrid.py", "--seeds",
                        f"5,{2 ** 31 + 11}", "--tolerance", "0.045")
    assert done.returncode == 0, done.stderr[-2000:]
    last = lines[-1]
    assert last["holds"] is True
    assert last["program_max_diff_over_scale"] < 0.045 \
        < last["fp8_max_diff_over_scale"]
    served = [l["served"] for l in lines if l.get("fact") == "logits"]
    assert all(s["over_8_ulps"] == 0 and s["tokens"] == 4 * 33 for s in served)


def test_the_cell_lists_every_metric_it_reports():
    manifest = mf.load_manifest()
    cell = mf.load_cell(manifest, CELL)
    assert cell.chips == 1 and cell.traffic_name == "serve-chat"
    assert cell.system["max_batch_slots"] == 16
    # the cell's own rate on the untouched traffic file
    assert cell.traffic["rate_rps"] == cell.system["traffic"]["rate_rps"]
    assert cell.traffic["shape_seed"] == 24
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names and "decode_step_device_ms" in names
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    for name in NEW:
        entry = [m for m in manifest["per_layer"] if m["name"] == name][0]
        assert entry["workloads"] == [CELL]
    cfg = cell.config
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_local_experts", "vocab_size"]
    assert cfg["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert (cfg["num_local_experts"], cfg["published"]["num_local_experts"],
            cfg["num_experts_per_tok"]) == (36, 72, 10)
    # what the device holds: over the floor of a quarter of the chip
    held = 2 * flops.param_count(cfg) + 16 * flops.state_bytes_per_slot(cfg)
    assert 0.6 < held / peaks.peaks_for("TPU v5 lite")["hbm_bytes"] < 0.7


class Run:
    def __init__(self, cell, trace=None, window=None):
        self.cell, self.facts = cell, {}
        self.trace, self.window = trace, window
        self.peaks = peaks.peaks_for("TPU v5 lite")
        self.notes = []

    def note(self, **kw):
        self.notes.append(kw)


class Ring:
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
    spec = mf.read_named("metrics", name)
    reader = importlib.import_module(f"readers.{spec['reader']}")
    return reader.read(run, name, **spec["args"])


def cell():
    return mf.load_cell(mf.load_manifest(), CELL)


def counters(steps, live=16, hit=33):
    """What `steps` decode steps of 10 expert layers report: `live` slots'
    pairs, about half of them held, `hit` held experts with a row a layer."""
    routed = steps * 10 * live * 10
    return {"moe_routed_pairs": routed, "moe_held_pairs": routed // 2,
            "moe_load_max": steps * 10 * 6,
            "moe_load_mean": routed / 2 / 36, "moe_experts_hit": steps * 10 * hit}


def traced_serving(monkeypatch, with_counters=True):
    """The window's run (profiler off), then a traced run of 1 s from
    20 000 ms whose clock in the trace is SKEW ahead: one wave with its
    commit, one decode window of two steps."""
    r = Ring()
    win = r.add("serve/run", 1000, 5000, requests=9)
    a = r.add("serve/admit", 1100, 1700, parent=win.id, wave=1)
    c = r.add("serve/prefill/commit", 1200, 1230, parent=a.id)
    r.add("serve/prefill/commit_kv", 1200, 1204, parent=c.id, bytes=67_108_864)
    r.add("serve/prefill/commit_state", 1204, 1210, parent=c.id, bytes=612_000_000)
    r.add("serve/prefill/commit_state", 2204, 2206, parent=win.id, bytes=612_000_000)
    r.add("serve/decode/window_sync", 3000, 3010, parent=win.id, window=1,
          steps=4, **(counters(4) if with_counters else {}))
    r.add("serve/decode/window_sync", 3100, 3110, parent=win.id, window=2,
          steps=2, **(counters(2, live=8) if with_counters else {}))
    t0 = 20_000
    root = r.add("serve/run", t0, t0 + 1000, requests=2)
    a = r.add("serve/admit", t0 + 100, t0 + 700, parent=root.id, wave=1,
              requests=4, prompt_tokens=640, padded_tokens=16384)
    wave = {"moe_held_pairs": 10 * 640 * 5} if with_counters else {}
    r.add("serve/prefill/device_wait", t0 + 150, t0 + 650, parent=a.id, **wave)
    for k in range(2):
        r.add("serve/decode/dispatch", t0 + 800 + 20 * k, t0 + 802 + 20 * k,
              parent=root.id, window=1)
    r.add("serve/decode/window_sync", t0 + 840, t0 + 850, parent=root.id,
          window=1, steps=2, **(counters(2) if with_counters else {}))
    r.install(monkeypatch)

    def at(ms):
        return int((t0 + ms) * MS) + SKEW
    ops = [Op("fusion.1", at(160), at(640)),                # the wave: 480 ms
           Op("fusion.3", at(801), at(816)), Op("fusion.4", at(821), at(836))]
    host = [Op("bench/traced_run", at(-5), at(1000))]
    return Run(cell(), trace=Trace({0: ops}, host), window=(at(0), at(1000)))


def test_routing_counters_and_the_state_commit_from_the_windows_ring(monkeypatch):
    run = traced_serving(monkeypatch)
    # the window's spans (the run before the traced one), not the traced run's
    assert read(run, "moe_held_pair_share.decode") == pytest.approx(50.0)
    routed = counters(4)["moe_routed_pairs"] + counters(2, live=8)["moe_routed_pairs"]
    assert read(run, "moe_expert_load_max_over_mean.decode") == pytest.approx(
        6 * 10 * 6 / (routed / 2 / 36))
    assert read(run, "state_commit_ms") == pytest.approx(4.0)    # median of 6, 2


def test_shares_of_the_peaks_from_the_traced_runs_spans(monkeypatch):
    run = traced_serving(monkeypatch)
    c = run.cell
    # one decode window of two steps, 15 ms of device time each
    got = read(run, "decode_step_hbm_roofline.granite")
    per_step = {k: v / 2 for k, v in counters(2).items()}
    need = flops.decode_step_need(c.config, c.system, c.traffic, per_step)
    assert got == pytest.approx(100 * need["bytes"] / 819e9 / 15e-3)
    assert 80 < got < 85            # 10.2 GB a step at 819 GB/s is 12.4 ms
    note = run.notes[-1]
    assert note["bound"] == "memory" and note["per"] == 2
    assert note["measured_ms"] == pytest.approx(15.0)
    # one wave, 480 ms of device time
    got = read(run, "prefill_mfu.granite")
    need = flops.prefill_wave_need(c.config, c.system, c.traffic,
                                   {"moe_held_pairs": 10 * 640 * 5})
    assert got == pytest.approx(100 * need["flops"] / 197e12 / 0.48)
    assert 35 < got < 45 and run.notes[-1]["bound"] == "compute"
    # an interval that is not wholly in the steady window is left out
    run.window = (run.window[0] + 750 * MS, run.window[1])
    assert read(run, "prefill_mfu.granite") is None
    assert read(run, "decode_step_hbm_roofline.granite") is not None


def test_nothing_to_read_is_none(monkeypatch):
    # a program whose spans carry no counters (a parent from before them)
    run = traced_serving(monkeypatch, with_counters=False)
    assert [read(run, n) for n in NEW if n != "state_commit_ms"] == [None] * 4
    # a family without a flops module of its own
    gpt2 = types.SimpleNamespace(config={"family": "gpt2"}, system={},
                                 traffic={"kind": "serve"}, chips=1)
    other = Run(gpt2, trace=run.trace, window=run.window)
    assert read(other, "prefill_mfu.granite") is None
    # a program from before the ring
    monkeypatch.delattr(tel, "ring_spans")
    assert [read(run, n) for n in NEW] == [None] * 5
