"""readers/scope_device.py: device time by (op type, phase) from the join of
the device trace's instruction names with the program's own compiled HLO.

`recorded_scope_fit.xplane.pb` + `recorded_scope_fit.json.gz` were recorded
on a TPU v5e (PR 37, `_proof/record_fixture.py`): one traced `fit` of the
tiny rehearsal cell's model with four steps a dispatch (the first in the
entry computation, three inside a `while`), that program's optimized HLO text, the ring's `fit/*` spans of
the traced call and the graph's layers."""

import gzip
import json
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "benchmarks"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from flexflow_tpu import attribution, telemetry  # noqa: E402
from harness import trace_reduce  # noqa: E402
from readers import ring_stat, scope_device  # noqa: E402

HERE = Path(__file__).parent
TRACE = HERE / "recorded_scope_fit.xplane.pb"
FACTS = HERE / "recorded_scope_fit.json.gz"
S = attribution.OpScope


class Run:
    """What run.py's RunView shows a reader."""

    def __init__(self, kind, trace, window):
        self.facts = {}
        self.cell = types.SimpleNamespace(traffic={"kind": kind})
        self.peaks = None
        self.trace, self.window = trace, window
        self.notes = []

    def note(self, **kw):
        self.notes.append(kw)


def _span(name, start, end, args=None, id_=0, parent=0):
    return telemetry.Span(name, start, end, "main", parent, args, id_)


@pytest.fixture
def recorded(monkeypatch):
    with gzip.open(FACTS, "rt") as f:
        facts = json.load(f)
    layers = [types.SimpleNamespace(
        name=n, op_type=types.SimpleNamespace(value=t))
        for n, t in facts["layers"]]
    scopes = attribution.op_scope_map(facts["hlo_text"], layers)
    monkeypatch.setattr(attribution, "op_scopes",
                        lambda name: [scopes] if name == "train_step" else [])
    ring = [telemetry.Span(*s) for s in facts["ring"]]
    monkeypatch.setattr(ring_stat, "ring", lambda: ring)
    trace = trace_reduce.load(TRACE)
    run = Run("train", trace, trace_reduce.window_of(trace, "bench/traced_fit"))
    return run, scopes, facts


def test_the_chips_own_names_join_with_the_programs_hlo(recorded):
    run, scopes, facts = recorded
    names = {o.name for o in run.trace.devices[0]}
    # the chip names an event by its instruction: numbered fusions, the
    # flash kernels' custom calls, a `while` that spans its body
    assert any(n.startswith("fusion.") for n in names)
    assert any(n.startswith("ff_flash_attention_") for n in names)
    assert any(attribution.fold_name(n) == "while" for n in names)
    assert len(names & set(scopes)) > 0.95 * len(names)

    ms = {ph: scope_device.read(run, f"step_{ph}", "train_step",
                                {"phase": ph}, per="steps")
          for ph in ("forward", "backward", "update", "loss")}
    assert all(v is not None and v > 0 for v in ms.values()), ms
    assert ms["backward"] > ms["forward"] > ms["loss"]
    lost = scope_device.read(run, "op_scope_unattributed.train", "train_step",
                             {"phase": ["unattributed", "ambiguous"]},
                             share_of_busy=True)
    assert 0 <= lost < 5.0
    attn = scope_device.read(run, "attn", "train_step",
                             {"op_types": ["multihead_attention"]},
                             per="steps")
    both = scope_device.read(run, "attn_fwd", "train_step",
                             {"op_types": "multihead_attention",
                              "phase": "forward"}, per="steps")
    assert 0 < both < attn < ms["forward"] + ms["backward"]

    # ONE note a program and run, with the whole table; its rows add up
    # to the intervals' device-busy time
    notes = [n for n in run.notes if n.get("program") == "train_step"]
    assert len(notes) == 1
    note = notes[0]
    assert note["per"] == "steps" and note["units"] == facts["steps"]
    assert note["table_sum_ms"] == pytest.approx(
        sum(note["ms_by_phase"].values()) + note["ambiguous_ms"]
        + note["unattributed_ms"])
    # three of the four steps run in a `while`: the gaps between its body's
    # operations are busy time of the container and of no row
    assert 0 < note["container_self_ms"] < 0.1 * note["device_busy_ms"]
    assert note["table_sum_ms"] + note["container_self_ms"] == pytest.approx(
        note["device_busy_ms"], rel=0.02)
    assert sum(ms.values()) == pytest.approx(
        sum(v for ph, v in note["ms_by_phase"].items() if ph != "other"))
    kinds = note["ms_by_op_type_and_op_name"]["multihead_attention"]
    assert any(k.startswith("ff_flash_attention_") for k in kinds)
    assert "fusion" in kinds
    assert len(note["costliest_layers_ms"]) == 10
    assert 0 < note["mixed_fusion_share"]["backward"] < 1
    assert set(note["ms_by_op_type_and_phase"][scope_device.OUTSIDE]) >= {
        "update", "loss"}


def test_a_program_from_before_the_map_reports_nothing(recorded, monkeypatch):
    run, _scopes, _facts = recorded
    monkeypatch.delattr(attribution, "op_scopes")
    assert scope_device.read(run, "m", "train_step", {"phase": "forward"},
                             per="steps") is None
    assert run.notes == []


def test_per_has_to_be_the_programs_own_unit(recorded):
    run, _scopes, _facts = recorded
    with pytest.raises(ValueError, match="per"):
        scope_device.read(run, "m", "serve/prefill", {"phase": "forward"},
                          per="steps")


def test_waves_and_decode_windows_take_their_own_programs(monkeypatch):
    """Hand-made serving run: a wave runs the prefill program, then the
    commit program; a decode window the decode program. A name that
    prefill and commit scope differently is ambiguous inside the wave, a
    name nobody maps is unattributed, an interval that straddles the
    window's edge is left out."""
    op = trace_reduce.Op
    attn = S("h0_attn", "multihead_attention", "forward", "fusion", True, False)
    moe = S("h0_moe", "moe_layer", "forward", "ragged-dot", True, False)
    maps = {
        "serve/prefill": [{"fusion.1": attn, "ragged-dot.2": moe,
                           "while.9": S("h0_moe", "moe_layer", "forward",
                                        "while", False, False)}],
        "serve/commit": [{"fusion.1": S("", "", "other", "fusion", False, False),
                          "scatter.3": S("", "", "other", "scatter", False,
                                         False)}],
        "serve/decode": [{"fusion.1": attn._replace(layer="h0_attn_dec"),
                          "ragged-dot.2": moe}],
    }
    monkeypatch.setattr(attribution, "op_scopes", lambda name: maps[name])
    ms = 1_000_000
    ops = [
        # wave 1 (ring 10..20 ms -> trace 110..120 ms)
        op("while.9", 110 * ms, 116 * ms), op("ragged-dot.2", 111 * ms, 115 * ms),
        op("fusion.1", 116 * ms, 118 * ms), op("scatter.3", 118 * ms, 119 * ms),
        op("mystery.7", 119 * ms, 119 * ms + ms // 2),
        # decode window 5 (ring 30..40 ms): 4 steps
        op("fusion.1", 131 * ms, 133 * ms), op("ragged-dot.2", 133 * ms, 139 * ms),
        # wave 2 reaches past the steady window's end: not counted
        op("fusion.1", 158 * ms, 162 * ms),
    ]
    trace = trace_reduce.Trace({0: ops}, [op("bench/traced_run", 100 * ms, 200 * ms)])
    ring = [
        _span("serve/admit", 10 * ms, 20 * ms, {"wave": 1}, 2, 1),
        _span("serve/decode/dispatch", 30 * ms, 31 * ms, {"window": 5}, 3, 1),
        _span("serve/decode/dispatch", 32 * ms, 33 * ms, {"window": 5}, 4, 1),
        _span("serve/decode/window_sync", 38 * ms, 40 * ms,
              {"window": 5, "steps": 4}, 5, 1),
        _span("serve/admit", 57 * ms, 63 * ms, {"wave": 2}, 6, 1),
        _span("serve/run", 0, 100 * ms, {"requests": 3}, 1, 0),
    ]
    monkeypatch.setattr(ring_stat, "ring", lambda: ring)
    run = Run("serve", trace, (100 * ms, 160 * ms))

    experts = {"op_types": ["moe_layer"]}
    assert scope_device.read(run, "wave_experts", "serve/prefill", experts,
                             per="waves") == pytest.approx(4.0)
    assert scope_device.read(run, "decode_experts", "serve/decode", experts,
                             per="steps") == pytest.approx(6.0 / 4)
    attention = {"op_types": ["multihead_attention", "latent_attention"]}
    # in the wave `fusion.1` is the prefill's or the commit's: nobody's
    assert scope_device.read(run, "wave_attention", "serve/prefill",
                             attention, per="waves") == 0.0
    assert scope_device.read(run, "decode_attention", "serve/decode",
                             attention, per="steps") == pytest.approx(2.0 / 4)
    lost = scope_device.read(run, "op_scope_unattributed.serve",
                             ["serve/prefill", "serve/decode"],
                             {"phase": ["unattributed", "ambiguous"]},
                             share_of_busy=True)
    # 2 ms ambiguous + 0.5 ms unattributed of 9.5 + 8 busy ms
    assert lost == pytest.approx(100 * 2.5 / 17.5)
    wave, = [n for n in run.notes if n.get("program") == "serve/prefill"]
    assert wave["units"] == 1 and wave["ambiguous_ms"] == pytest.approx(2.0)
    assert wave["costliest_unattributed_ms"] == [["mystery.7", 0.5]]
    assert wave["device_busy_ms"] == pytest.approx(9.5)
    assert wave["table_sum_ms"] == pytest.approx(7.5)   # 2 ms under the while
    assert wave["container_self_ms"] == pytest.approx(2.0)
    assert len(run.notes) == 2


def test_what_the_nemotron_cells_pinned_test_no_longer_reaches():
    """`test_nemotron_h_rehearsal.py::test_the_cell_lists_every_metric_it_
    reports` pins PR 34's five metrics to the END of `per_layer`; a PR that
    adds metrics has to append them (the benchmark's contract: an entry put
    in the middle reads as a change to what was there), so that test stops
    at its line 172 from now on. What it checked after that line, and the
    five entries themselves, wherever they stand."""
    from harness import flops_nemotron_h as flops
    from harness import manifest as mf
    from harness import peaks

    config = "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
    cell_name = config + ".serve-chat"
    five = ["prefill_mfu.nemotron", "decode_step_hbm_roofline.nemotron",
            "moe_held_pair_share.decode.nemotron",
            "moe_expert_load_max_over_mean.decode.nemotron",
            "state_commit_ms.nemotron"]
    manifest = mf.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(five[0])
    assert names[at:at + 5] == five          # together, in their order
    # every metric file that reads through scope_device is declared
    mine = [f.stem for f in (ROOT / "benchmarks" / "metrics").glob("*.json")
            if json.loads(f.read_text()).get("reader") == "scope_device"]
    assert len(mine) == 13 and set(mine) <= set(names)
    assert manifest["workloads"][-1]["name"] == cell_name
    three = {"gpt2-medium.serve-chat", "granite-4.0-h-small.serve-chat",
             "GigaChat3.1-702B-A36B.serve-chat"}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if three <= set(m.get("workloads", [])):
            assert m["workloads"][-1] == cell_name, m["name"]
    entry = manifest["configs"][-1]
    cfg = mf.load_cell(manifest, cell_name).config
    assert entry["name"] == config and entry["source"] == cfg["source"]
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["hybrid_override_pattern"],
            cfg["n_routed_experts"], cfg["vocab_size"]) \
        == (11, "MEMEMEM*EME", 128, 32768)
    assert set(cfg["departures"]) >= {"num_nextn_predict_layers",
                                      "max_position_embeddings", "weights",
                                      "tie_word_embeddings"}
    assert "4 chips" in cfg["deployment"]
    assert len(manifest["workloads"][-1]["why"]) <= 200 \
        and len(entry["why"]) <= 200
    held = 2 * flops.param_count(cfg) + 16 * flops.state_bytes_per_slot(cfg)
    assert 0.59 < held / peaks.peaks_for("TPU v5 lite")["hbm_bytes"] < 0.62
