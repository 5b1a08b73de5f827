"""Per-op performance attribution (ISSUE 7): op-level measured vs predicted
vs roofline joins, the per-op drift top-K, the op/attr telemetry rows,
and the CI wiring of the new tools' --check smokes.

Acceptance anchors: per-op attributed times sum to the measured step time
within attribution.SUM_TOLERANCE on the gpt2 CPU twin (single-device data
mesh, sharded mesh, and pipelined S=2), a profiled fit's op/attr rows carry
stable feature keys and reach trace_report, and the drift top-K is
populated after a fit with telemetry on.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import profile_attribution
import trace_report

from flexflow_tpu import (FFConfig, FFModel, LossType, SGDOptimizer,
                          attribution, telemetry as tel)
from flexflow_tpu.models import GPT2Config, build_gpt2


def _gpt2_twin_fit(tmp_path, tag, epochs=2, profile_ops=False, **cfg_kw):
    """Tiny gpt2 CPU twin fit with telemetry on; returns (cm, tdir)."""
    tdir = str(tmp_path / f"tele_{tag}")
    cfg = FFConfig(batch_size=8, only_data_parallel=True,
                   telemetry_dir=tdir, profile_ops=profile_ops,
                   log_level="warning", **cfg_kw)
    m = FFModel(cfg)
    gcfg = GPT2Config(vocab=128, seq=8, d_model=32, heads=2, layers=1,
                      dropout=0.0)
    build_gpt2(m, gcfg, batch=8)
    cm = m.compile(SGDOptimizer(lr=0.01),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, size=(32, 8)).astype(np.int32)
    pos = np.broadcast_to(np.arange(8, dtype=np.int32), (32, 8)).copy()
    y = rng.integers(0, 128, size=(32, 8)).astype(np.int32)
    cm.fit([ids, pos], y, epochs=epochs, verbose=False)
    return cm, tdir


def _assert_report_shape(report):
    """Every row carries predicted cost, measured time, roofline bound and
    MFU; attributed times sum to the measured step within tolerance."""
    rows = report["rows"]
    assert rows
    for r in rows:
        for k in ("predicted_s", "measured_s", "attributed_s",
                  "roofline_s", "mfu", "mfu_ceiling"):
            assert isinstance(r[k], float), (k, r)
        assert r["bound"] in ("compute", "bandwidth"), r
        assert r["roofline_s"] >= 0.0
        assert r["key"] == attribution.feature_key(r["features"])
    step = report["step_time_s"]
    assert step and step > 0
    att = report["attributed_total_s"]
    assert abs(att - step) / step <= attribution.SUM_TOLERANCE, (att, step)


# ------------------------------------------------------- single-device path
def test_attribution_gpt2_twin(devices, tmp_path):
    cm, tdir = _gpt2_twin_fit(tmp_path, "single")
    report = cm.op_attribution(print_table=False)
    _assert_report_shape(report)
    # the drift top-K names the worst-mispriced op
    td = report["top_drift"]
    assert td["rows"] and td["rows"][0]["layer"]
    assert 0.0 < td["explained"] <= 1.0 + 1e-9
    # attribution emitted the op/attr events
    tel.flush()
    evs = tel.read_events(tdir)
    assert any(e.get("name") == attribution.OP_EVENT for e in evs)
    assert any(e.get("name") == attribution.DRIFT_EVENT for e in evs)
    tel.shutdown()


def test_attribution_without_fit_uses_isolated_times(devices, tmp_path):
    """No fit yet -> no measured step time: attributed == isolated
    measured (scale 1), still a complete per-op roofline/MFU join."""
    cfg = FFConfig(batch_size=8, only_data_parallel=True,
                   log_level="warning")
    m = FFModel(cfg)
    x = m.create_tensor([8, 16], name="x")
    m.dense(m.dense(x, 32, activation="relu", name="fc1"), 4, name="fc2")
    cm = m.compile(SGDOptimizer(),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    report = cm.op_attribution(print_table=False)
    assert report["step_time_s"] is None and report["scale"] == 1.0
    for r in report["rows"]:
        assert r["attributed_s"] == r["measured_s"]
        assert r["bound"] in ("compute", "bandwidth")


# ------------------------------------------------------------- sharded path
def test_attribution_sharded_with_search_stamps(devices, tmp_path):
    """Searched compile on a data x model mesh: the strategy carries the
    DP's per-op predicted costs, attribution joins against them, and the
    warm (cached) compile restores the stamp."""
    def compile_once(tag):
        cfg = FFConfig(batch_size=8, mesh_shape={"data": 4, "model": 2},
                       search_budget=16, telemetry_dir="",
                       log_level="warning",
                       strategy_cache_dir=str(tmp_path / "cache"))
        m = FFModel(cfg)
        x = m.create_tensor([8, 16], name="x")
        h = m.dense(x, 64, activation="relu", name="up")
        m.dense(h, 16, name="down")
        return m.compile(SGDOptimizer(),
                         LossType.SPARSE_CATEGORICAL_CROSSENTROPY)

    cm = compile_once("cold")
    stamped = getattr(cm.strategy, "_predicted_op_costs", None)
    assert stamped, "search did not stamp per-op predicted costs"
    assert all(v > 0 for v in stamped.values())
    report = cm.op_attribution(print_table=False)
    by_layer = {r["layer"]: r for r in report["rows"]}
    for lname, cost in stamped.items():
        if lname in by_layer:
            assert by_layer[lname]["predicted_s"] == pytest.approx(cost)
    # warm compile: the cache restores the per-op stamp with the strategy
    cm2 = compile_once("warm")
    info = cm2.search_cache_info
    assert info and info.get("event") == "hit"
    assert getattr(cm2.strategy, "_predicted_op_costs", None) == stamped


# ----------------------------------------------------------- pipelined path
def test_attribution_pipelined_s2(devices, tmp_path):
    tdir = str(tmp_path / "tele_pipe")
    cfg = FFConfig(batch_size=8, only_data_parallel=True, seed=3,
                   pipeline_stages=2, pipeline_schedule="1f1b",
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
    cm.fit([x], y, epochs=2, verbose=False)
    report = cm.op_attribution(print_table=False)
    _assert_report_shape(report)
    assert {r["stage"] for r in report["rows"]} == {0, 1}
    assert report["top_drift"]["rows"]
    tel.shutdown()


# ------------------------------------------------ telemetry -> trace_report
def test_profiled_fit_op_rows_reach_trace_report(devices, tmp_path):
    cm, tdir = _gpt2_twin_fit(tmp_path, "corpus", profile_ops=True)
    tel.flush()
    # trace_report surfaces the fit's op/attr events in its [ops] section
    rep = trace_report.render(tdir, out_path=None, quiet=True)
    assert rep["ops"], "trace_report found no op/attr rows"
    for r in rep["ops"]:
        # stable feature keys: recomputing from the features that went
        # through the telemetry file reproduces the key
        assert attribution.feature_key(r["features"]) == r["key"]
        assert r["predicted_s"] is not None
        assert r["roofline_s"] is not None
    assert rep["op_drift"], "trace_report found no op/drift_topk event"
    tel.shutdown()


def test_feature_key_dedups_structural_twins(devices):
    """Two identically-shaped layers (different names) produce the SAME
    feature key — structural twins share one — while a different
    shape changes the key."""
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.search.candidates import layer_candidates

    cfg = FFConfig(batch_size=8, only_data_parallel=True,
                   log_level="warning")
    m = FFModel(cfg)
    x = m.create_tensor([8, 16], name="x")
    h = m.dense(x, 16, name="twin_a")
    h = m.dense(h, 16, name="twin_b")
    m.dense(h, 4, name="odd_one")
    machine = MachineSpec.detect()
    keys = {}
    for lname in ("twin_a", "twin_b", "odd_one"):
        layer = m.get_layer_by_name(lname)
        cand = layer_candidates(layer, machine, {8})[0]
        keys[lname] = attribution.feature_key(
            attribution.op_features(layer, cand, machine))
    assert keys["twin_a"] == keys["twin_b"]
    assert keys["odd_one"] != keys["twin_a"]


# --------------------------------------------------------- trace primary path
def _twin(profile_dir=None, layers=2, **cfg_kw):
    """The tiny GPT-2 twin, compiled with Adam on one device, and a batch."""
    from flexflow_tpu import AdamOptimizer

    cfg = FFConfig(batch_size=8, only_data_parallel=True,
                   mesh_shape={"data": 1}, log_level="warning",
                   profiling=profile_dir is not None,
                   profile_dir=profile_dir or "", **cfg_kw)
    m = FFModel(cfg)
    gcfg = GPT2Config(vocab=128, seq=8, d_model=32, heads=2, layers=layers,
                      dropout=0.0)
    build_gpt2(m, gcfg, batch=8)
    cm = m.compile(AdamOptimizer(alpha=0.01),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, size=(32, 8)).astype(np.int32)
    pos = np.broadcast_to(np.arange(8, dtype=np.int32), (32, 8)).copy()
    y = rng.integers(0, 128, size=(32, 8)).astype(np.int32)
    return cm, [ids, pos], y


class _Layer:
    def __init__(self, name, op="linear"):
        self.name = name
        self.op_type = type("T", (), {"value": op})


@pytest.mark.parametrize("op_name, want", [
    ("jit(train_step)/jvp(up)/dot_general", ("up", "forward")),
    ("jit(train_step)/transpose(jvp(up))/dot_general", ("up", "backward")),
    ("jit(prefill)/up/dot_general", ("up", "forward")),
    # a layer named "up" absorbs neither the update scope nor a longer name
    ("jit(train_step)/ff.update/mul", ("", "update")),
    ("jit(train_step)/update/up_cast/mul", ("", "other")),
    ("jit(train_step)/jvp(ffn_up_2)/mul", ("ffn_up_2", "forward")),
    ("jit(train_step)/jvp(ff.loss)/reduce_sum", ("", "loss")),
    ("jit(train_step)/transpose(jvp(ff.loss))/mul", ("", "loss")),
    # the slash inside an einsum's parentheses splits nothing
    ("jit(f)/while/body/transpose(jvp(down))/a,b->(a/b)/mul",
     ("down", "backward")),
    ("jit(train_step)/mul", ("", "other")),
    # forward recomputed under jax.checkpoint runs in the backward pass
    ("jit(train_step)/transpose(jvp(up))/jvp(up)/checkpoint/"
     "rematted_computation/tanh", ("up", "backward")),
])
def test_scope_of_op_name_matches_whole_segments(op_name, want):
    op_types = {"up": "linear", "down": "linear", "ffn_up_2": "linear"}
    assert attribution.scope_of_op_name(op_name, op_types) == want


def test_op_scope_map_on_the_twins_train_step(devices):
    """The real optimized HLO of the twin's train_step: every graph layer
    appears, all four phases are there, and `mixed` fusions are flagged."""
    cm, x, y = _twin()
    cm.fit(x, y, epochs=1, verbose=False)
    maps = attribution.op_scopes("train_step")
    prog = cm._programs[1]
    assert prog.scopes is not None and prog.scopes in maps
    scopes = prog.scopes
    span = tel.ring_spans(attribution.SPAN)[-1]
    assert span.args["program"] == "train_step"
    assert span.args["instructions"] == len(scopes) > 100
    # the name stacks are this tree's own (no stale compile cache)
    assert span.args["layers_named"] >= 0.7 * span.args["layers"] > 0
    # every layer that holds weights has instructions of its own (a
    # residual add may live wholly inside a neighbour's fusion)
    layers = {s.layer for s in scopes.values()}
    assert {l.name for l in cm.model.layers if l.weight_specs} <= layers
    by_phase = {}
    for s in scopes.values():
        by_phase.setdefault(s.phase, []).append(s)
    assert {"forward", "backward", "update", "loss"} <= set(by_phase)
    assert len(by_phase.get("other", ())) < 0.05 * len(scopes)
    assert all(not s.layer for s in by_phase["update"] + by_phase["loss"])
    ops = {l.name: l.op_type.value for l in cm.model.layers}
    assert all(s.op_type == ops.get(s.layer, "") for s in scopes.values())
    # a weight-gradient product is the backward of its layer
    assert any(s.has_dot and s.phase == "backward"
               and s.op_type == "linear" for s in scopes.values())
    assert any(s.mixed for s in scopes.values())
    # asked again: the kept map, no second span
    n = len(tel.ring_spans(attribution.SPAN))
    assert attribution.op_scopes("train_step") == maps
    assert len(tel.ring_spans(attribution.SPAN)) == n


HLO_WITH_A_FUSED_DOT = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8,8], p1: f32[8,8], p2: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %p2 = f32[8,8]{1,0} parameter(2)
  %dot.5 = f32[8,8]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp(up))/dot_general"}
  ROOT %sub.7 = f32[8,8]{1,0} subtract(%p2, %dot.5), metadata={op_name="jit(step)/ff.update/sub"}
}

%fused_computation.2 (p0.1: f32[8,8]) -> f32[8,8] {
  %p0.1 = f32[8,8]{1,0} parameter(0)
  ROOT %tanh.3 = f32[8,8]{1,0} tanh(%p0.1), metadata={op_name="jit(step)/jvp(up)/tanh"}
}

%body (c: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %c = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%c), index=1
  %fusion.2 = f32[8,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/jvp(up)/tanh"}
  ROOT %t = (s32[], f32[8,8]{1,0}) tuple(%i, %fusion.2)
}

%cond (c.1: (s32[], f32[8,8])) -> pred[] {
  %c.1 = (s32[], f32[8,8]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main.9 (a: f32[8,8], b: f32[8,8], w: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0:T(8,128)} parameter(0)
  %b = f32[8,8]{1,0} parameter(1)
  %w = f32[8,8]{1,0} parameter(2)
  %fusion.1 = f32[8,8]{1,0:T(8,128)} fusion(%a, %b, %w), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/ff.update/sub"}
  %ff_flash_attention_fwd.4 = (f32[8,8]{1,0}, f32[8]{0}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(attn)/pallas_call"}
  %ragged-dot-none.6 = f32[8,8]{1,0} custom-call(%a, %w), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %convert.7 = f32[8,8]{1,0} convert(%ragged-dot-none.6), metadata={op_name="jit(step)/jvp(moe)/convert_element_type"}
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,8]{1,0}) tuple(%zero, %fusion.1)
  %while.3 = (s32[], f32[8,8]{1,0}) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%while.3), index=1
}
"""


def test_op_scope_map_credits_a_fusion_to_its_one_dot():
    scopes = attribution.op_scope_map(
        HLO_WITH_A_FUSED_DOT,
        [_Layer("up"), _Layer("attn", "multihead_attention"),
         _Layer("moe", "moe_layer")])
    # the weight-gradient product with the update in its epilogue: the
    # dot's scope, not the root's, and flagged
    assert scopes["fusion.1"] == attribution.OpScope(
        "up", "linear", "backward", "fusion", True, True)
    # an instruction inside a while body is an event of its own
    assert scopes["fusion.2"] == attribution.OpScope(
        "up", "linear", "forward", "fusion", False, False)
    assert scopes["while.3"].opcode == "while"
    # a named kernel keeps its ff_ name as its opcode
    assert scopes["ff_flash_attention_fwd.4"] == attribution.OpScope(
        "attn", "multihead_attention", "forward", "ff_flash_attention_fwd",
        True, False)
    # what the chip's compiler makes of a ragged-dot carries its own name
    # for a name stack: it belongs to the layer that uses its result
    assert scopes["ragged-dot-none.6"] == attribution.OpScope(
        "moe", "moe_layer", "forward", "ragged-dot-none", True, False, True)
    # what has no event of its own is not in the map
    assert not {"a", "zero", "init", "out", "dot.5", "lt"} & set(scopes)


STEP_WITH_A_UNIT = """HloModule jit_step, is_scheduled=true

ENTRY %main.9 (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %ff_flash_attention_fwd.1 = (f32[8,8]{1,0}, f32[8]{0}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/checkpoint/attn/jit(_fwd_call)/pallas_call"}
  %ff_flash_attention_fwd.2 = (f32[8,8]{1,0}, f32[8]{0}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/checkpoint/attn2/jit(_fwd_call)/pallas_call"}
%AGAIN%  %ff_flash_attention_dq.3 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp())/checkpoint/attn/jit(_dq_call)/pallas_call"}
  ROOT %out = f32[8,8]{1,0} copy(%ff_flash_attention_dq.3), metadata={op_name="jit(step)/ff.update/sub"}
}
"""
AGAIN = '  %ff_flash_attention_fwd.5 = (f32[8,8]{1,0}, f32[8]{0}) ' \
    'custom-call(%a), custom_call_target="tpu_custom_call", ' \
    'metadata={op_name="jit(step)/transpose(jvp())/checkpoint/' \
    'rematted_computation/LAYER/jit(_fwd_call)/pallas_call"}\n'


# what the swapped entry leaves around a flash call: the operand swapped (q's
# size: counted) and the compiler's own copy of the layer's result (no name
# stack: the layer's by what it serves; q's size too), beside a small copy
# (a statistic's: not counted) and the optimizer's (no attention layer's)
RELAYOUTS = """  %swap.7 = f32[8,8]{0,1} transpose(%a), dimensions={1,0}, metadata={op_name="jit(step)/jvp()/checkpoint/attn/transpose"}
  %copy.8 = f32[8,8]{1,0} copy(%swap.7)
  %ff_flash_attention_dkv.9 = f32[8,8]{1,0} custom-call(%copy.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp())/checkpoint/attn/jit(_dkv_call)/pallas_call"}
  %small.10 = f32[8]{0} copy(%a), metadata={op_name="jit(step)/jvp()/checkpoint/attn/reduce"}
"""


@pytest.mark.parametrize("again,passes,relayouts", [
    ("", 1.0, 0.0), (AGAIN.replace("LAYER", "attn"), 1.5, 0.0),
    (AGAIN.replace("LAYER", "attn") + AGAIN.replace("LAYER", "attn2"), 2.0,
     0.0), (RELAYOUTS, 1.0, 1.0)])
def test_step_passes_counts_the_recomputations_flash_calls(again, passes,
                                                           relayouts):
    """The forward kernel's calls under the graph's layers, all phases over
    the forward's: a checkpoint's recomputation carries the backward
    pass's wrapper. Beside them `flash_relayouts` (PR 63): the `copy` /
    `transpose` instructions of an attention layer as large as its q, a
    layer that calls the kernel (two of them over two layers here; 0 where
    the kernels read what the projections wrote). A program without the
    kernel says nothing."""
    op_types = {"attn": "multihead_attention", "attn2": "multihead_attention"}
    text = STEP_WITH_A_UNIT.replace("%AGAIN%", again)
    assert attribution.step_passes(text, op_types) \
        == {"flash_fwd_passes": passes, "flash_relayouts": relayouts}
    assert attribution.step_passes(
        text.replace("ff_flash_attention_fwd", "fusion"), op_types) == {}
    assert attribution.routing_passes(text, op_types) is None


# an expert layer's rows (PR 64): the kernel's call in the forward pass and
# in the block's backward, whose rule differentiates the taken branch on the
# spot (the scope then reads `jvp(..)` / `transpose(jvp(..))`); %UNIT%: the
# call a `remat_blocks` unit's recomputation makes where it does not keep
# the layer's result
ROWS = """HloModule jit_step

ENTRY %main.9 (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %ROWS.1 = f32[8,8]{1,0} CALL(%a), metadata={op_name="jit(step)/jvp()/checkpoint/moe/while/body/checkpoint/cond/branch_1_fun/ff_moe_experts/pallas_call"}
%UNIT%  %ROWS.3 = f32[8,8]{1,0} CALL(%a), metadata={op_name="jit(step)/transpose(jvp())/checkpoint/moe/while/body/checkpoint/cond/branch_1_fun/jvp(ff_moe_experts)/pallas_call"}
  %dot.4 = f32[8,8]{1,0} dot(%ROWS.3, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp())/checkpoint/moe/while/body/checkpoint/cond/branch_1_fun/transpose(jvp(ff_moe_experts))/dot_general"}
  ROOT %out = f32[8,8]{1,0} copy(%dot.4), metadata={op_name="jit(step)/ff.update/sub"}
}
"""
UNIT = '  %ROWS.2 = f32[8,8]{1,0} CALL(%a), metadata={op_name="jit(step)/' \
    'transpose(jvp())/checkpoint/rematted_computation/moe/while/body/' \
    'checkpoint/cond/branch_1_fun/ff_moe_experts/pallas_call"}\n'


@pytest.mark.parametrize("unit,passes", [("", 2.0), (UNIT, 3.0)])
@pytest.mark.parametrize("name,call", [
    ("ff_moe_rows", 'custom-call(%a), custom_call_target="tpu_custom_call"'),
    ("dot", "dot(%a, %a)")])
def test_step_passes_counts_the_rows_forward_evaluations(name, call, unit,
                                                         passes):
    """`moe_rows_passes`: the rows kernel's calls under an expert layer,
    all phases over the forward's; where no kernel runs, the products under
    `ff_moe_experts` that are no transpose (the backward's own product is
    not a forward evaluation). And a scope is found inside JAX's wrappers:
    all of it is under `ff_moe_experts`."""
    text = ROWS.replace("%UNIT%", unit).replace("ROWS", name).replace(
        "CALL(%a)", call)
    assert attribution.step_passes(text, {"moe": "moe_layer"}) \
        == {"moe_rows_passes": passes}
    assert attribution.instructions_in_scope(text, "ff_moe_experts") \
        == {f"{name}.{n}" for n in ((1, 2, 3) if unit else (1, 3))} \
        | {"dot.4"}


def test_join_ambiguous_unattributed_and_containers():
    S = attribution.OpScope
    prefill = {"fusion.1": S("attn", "multihead_attention", "forward",
                             "fusion", True, False),
               "copy.2": S("attn", "multihead_attention", "forward", "copy",
                           False, False),
               "while.3": S("", "", "other", "while", False, False)}
    commit = {"fusion.1": S("", "", "other", "fusion", False, False),
              "copy.2": prefill["copy.2"],
              "scatter.4": S("", "", "other", "scatter", False, False)}
    events = [("while.3", 0, 100),          # a container: its body counts
              ("fusion.1", 10, 40), ("copy.2", 40, 50), ("copy.2", 50, 55),
              ("scatter.4", 100, 120), ("fusion.99", 120, 127),
              ("while.8", 130, 140)]        # unmapped, a container by name
    by = attribution.device_time_by_scope(events, [prefill, commit])
    assert by[prefill["copy.2"]] == 15            # same scope in both: kept
    assert by[S("", "", attribution.AMBIGUOUS, "fusion", False, False)] == 30
    assert by[commit["scatter.4"]] == 20
    assert by[S("", "", attribution.UNATTRIBUTED, "fusion.99", False,
                False)] == 7
    assert sum(by.values()) == 30 + 15 + 20 + 7
    # one map alone: nothing is ambiguous
    alone = attribution.device_time_by_scope(events, prefill)
    assert alone[prefill["fusion.1"]] == 30
    assert attribution.AMBIGUOUS not in {s.phase for s in alone}


def test_measured_from_trace_on_a_real_profile(devices, tmp_path, capsys):
    """One profiled fit of the twin through jax.profiler.trace
    (--profiling): the written .xplane.pb joined with the step's own HLO
    gives most layers a time, by phase, and the report says `trace`."""
    pdir = str(tmp_path / "prof")
    cm, x, y = _twin(profile_dir=pdir)
    cm.fit(x, y, epochs=2, verbose=False)
    totals = attribution.measured_from_trace(pdir, cm._programs.values())
    named = [l.name for l in cm.model.layers]
    timed = [n for n in named if sum(totals.get(n, {}).values()) > 0]
    assert len(timed) >= 0.7 * len(named), (timed, named)
    assert totals["h0_attn"]["forward"] > 0 < totals["h0_attn"]["backward"]
    assert totals[""]["update"] > 0 and totals[""]["loss"] > 0
    assert not totals[""].get(attribution.UNATTRIBUTED)
    report = cm.op_attribution(source="trace", print_table=True)
    assert report["source"] == "trace"
    _assert_report_shape(report)
    by = {r["layer"]: r for r in report["rows"]}
    assert by["h0_attn"]["phases_s"]["backward"] > 0
    assert report["outside_s"]["update"] > 0
    # the layers' rows and what runs outside every layer make up the step
    # (pass-through placements have no row)
    whole = report["measured_total_s"] + sum(report["outside_s"].values())
    assert whole <= report["step_time_s"] * 1.0001
    assert report["coverage"] > 0.5
    out = capsys.readouterr().out
    assert "source=trace" in out and "fwd" in out and "update=" in out
    # no profile, no trace path: explicit where asked for, silent in auto
    with pytest.raises(ValueError, match="no parseable profiler trace"):
        attribution.build_report([], step_time_s=0.01, source="trace",
                                 profile_dir=str(tmp_path / "none"),
                                 programs=cm._programs.values())
    with pytest.raises(ValueError, match="step"):
        attribution.build_report([], step_time_s=None, source="trace",
                                 profile_dir=pdir,
                                 programs=cm._programs.values())


def test_a_chips_events_count_for_the_module_that_ran_them(tmp_path):
    """A TPU profile names an event by its instruction and says on the
    "XLA Modules" line which program ran when: `fusion.7` of the rng's
    little programs is not the step's `fusion.7`. The chip's own recorded
    profile of a fused fit (tests/benchmark/recorded_scope_fit.*)."""
    import gzip
    import json
    import shutil

    here = os.path.join(os.path.dirname(__file__), "benchmark")
    shutil.copy(os.path.join(here, "recorded_scope_fit.xplane.pb"),
                tmp_path / "host.xplane.pb")
    events = attribution.profile_events(str(tmp_path))
    assert {"jit_multi", "jit__threefry_seed"} <= set(events)
    n = {m: len(evs) for m, evs in events.items()}
    assert n["jit_multi"] > 0.9 * sum(n.values())
    with gzip.open(os.path.join(here, "recorded_scope_fit.json.gz"), "rt") as f:
        facts = json.load(f)

    def program(text):
        prog = attribution.Program("train_step", lambda: None,
                                   [_Layer(n, t) for n, t in facts["layers"]])
        prog.compiled = type("C", (), {"as_text": lambda self: text})()
        return prog

    step = program(facts["hlo_text"])
    totals = attribution.measured_from_trace(str(tmp_path), [step])
    assert step.module == "jit_multi"
    busy = sum(e - s for _n, s, e in events["jit_multi"]
               if attribution.fold_name(_n) not in attribution.CONTAINERS)
    assert sum(us for ph in totals.values() for us in ph.values()) == \
        pytest.approx(busy / 1e3)
    assert totals[""]["update"] > 0 and totals[""]["loss"] > 0
    # the same text under another module's name: none of the profile's
    # events are that program's
    other = program(facts["hlo_text"].replace("HloModule jit_multi",
                                              "HloModule jit_eval_step", 1))
    assert attribution.measured_from_trace(str(tmp_path), [other]) is None


def test_registration_renders_nothing(devices):
    """compile + fit with nobody asking: no compile/op_scopes span, no HLO
    text, and the loop's counters are the baseline's
    (tests/test_telemetry.py pins the same numbers)."""
    tel.ring_clear()
    cm, x, y = _twin(layers=1)
    cm.fit(x, y, epochs=2, verbose=False)
    assert not tel.enabled()
    assert tel.ring_spans(attribution.SPAN) == []
    prog = cm._programs[1]
    assert prog.scopes is None and prog.compiled is not None
    assert cm.step_stats == {"dispatches": 8, "host_syncs": 0, "barriers": 0,
                             "fused_steps": 0, "epoch_end_syncs": 2}
    # the fused loop registers its own program under the same name
    cm.fit(x, y, epochs=1, verbose=False, steps_per_dispatch=2)
    assert cm._programs[2].compiled is not None
    assert cm._programs[2].scopes is None
    assert tel.ring_spans(attribution.SPAN) == []
    # the last owner's programs outlive it, for whoever reads after the
    # model is gone (the benchmark's reader); they go when the next
    # program registers under the name
    import gc
    mine = {id(p) for p in cm._programs.values()}
    del cm, prog
    gc.collect()
    held = [p for p in attribution._PROGRAMS["train_step"] if id(p) in mine]
    assert len(held) == 2 and all(p._owner() is None for p in held)
    maps = attribution.op_scopes("train_step")
    assert all(p.scopes and p.scopes in maps for p in held)
    del held
    cm2, _x, _y = _twin(layers=1)
    assert cm2._programs[1] in attribution._PROGRAMS["train_step"]
    assert all(p._owner() is not None
               for p in attribution._PROGRAMS["train_step"])


def test_a_registration_shares_its_owners_life():
    """A cache registers a module's jit (which never dies) at its own
    shapes: the registration lives as long as the cache, stays readable
    after it, and goes when the next one registers under the name."""
    import gc

    class Owner:
        pass

    def jitted():
        pass

    first = Owner()
    p1 = attribution.register_program("test/owner", jitted, (), owner=first)
    del first
    gc.collect()
    assert attribution._PROGRAMS["test/owner"] == [p1]
    second = Owner()
    p2 = attribution.register_program("test/owner", jitted, (), owner=second)
    p3 = attribution.register_program("test/owner", jitted, (), owner=second)
    assert attribution._PROGRAMS["test/owner"] == [p2, p3]


# ------------------------------------------------------ probe -> telemetry
def test_perf_probe_emits_into_sink(tmp_path):
    """tools/perf_probe.py lands its measurements in the span stream when
    a sink is active (stdout-only otherwise) — unit-level: the emit helper
    with a fake measurement dict."""
    import perf_probe

    out = {"adam_step_ms": 12.5, "sgd_step_ms": 10.0, "fwd_only_ms": 4.0,
           "identity_loss_step_ms": 11.0, "optimizer_delta_ms": 2.5,
           "ce_delta_ms": 1.5, "bwd_update_ms": 8.5}
    # no sink: a no-op
    tel.shutdown()
    perf_probe._emit_telemetry(dict(out), iters=2, windows=1)
    tdir = str(tmp_path / "tele_probe")
    tel.configure(tdir)
    perf_probe._emit_telemetry(dict(out), iters=2, windows=1)
    tel.flush()
    evs = tel.read_events(tdir)
    spans = [e for e in evs if e.get("ph") == "X"
             and str(e.get("name", "")).startswith("probe/")]
    names = {e["name"] for e in spans}
    assert names == {"probe/adam_step", "probe/sgd_step", "probe/fwd_only",
                     "probe/identity_loss_step"}, names
    for e in spans:
        assert e["dur"] == pytest.approx(e["args"]["step_ms"] * 1e3,
                                         rel=1e-6)
    assert any(e.get("name") == "probe/summary" for e in evs)
    tel.shutdown()


# ------------------------------------------------------------- CI wiring
def test_profile_attribution_check_smoke():
    """tools/profile_attribution.py --check: the ISSUE 7 acceptance chain
    (attributed sums to step within 15%, full rows, drift top-K named,
    op/attr rows in the telemetry dir) on the gpt2 CPU twin."""
    assert profile_attribution.main(["--check"]) == 0
    assert not tel.enabled()
