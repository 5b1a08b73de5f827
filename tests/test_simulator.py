"""Event-driven task-graph simulator (search/simulator.py).

Reference analog: LogicalTaskgraphBasedSimulator (simulator.h:785-827,
simulator.cc:1251-1480) — the task-graph replay with concurrent device
timelines, segmented transfers, and emergent compute/comm overlap. The tests
pin the behaviors the closed-form additive model cannot express: gradient
all-reduces hiding behind the backward pass, POSITION-dependent comm
exposure (an early layer's grad sync cannot hide — its backward runs last),
transfer segmentation, and the re-rank integration."""

import math

import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.core.graph import topo_order
from flexflow_tpu.parallel.machine import MachineSpec
from flexflow_tpu.search.candidates import layer_candidates
from flexflow_tpu.search.dp import SearchResult, search_graph
from flexflow_tpu.search.simulator import (
    SimTask,
    build_step_tasks,
    replay,
    rerank,
    simulate_strategy,
)

MESH22 = dict(mesh_axes={"data": 2, "model": 2}, chip="v5e", overlap_frac=0.0)


def chain_model(d=4096, n=8, b=8, s=512):
    m = FFModel(FFConfig(batch_size=b))
    x = m.create_tensor([b, s, d], name="x")
    h = x
    for i in range(n):
        h = m.dense(h, d, activation="relu", name=f"fc{i}")
    return m


def plan(model, machine, shard=()):
    """All-dp assignment, with the named layers flipped to tp_row:model."""
    layers = topo_order(model.layers)
    bs = {t.shape[0] for t in model.input_tensors if t.ndim > 0}
    cls = {l.name: layer_candidates(l, machine, bs) for l in layers}
    a = {l.name: 0 for l in layers}
    for nm in shard:
        a[nm] = [c.name for c in cls[nm]].index("tp_row:model")
    choices = {nm: cls[nm][i] for nm, i in a.items()}
    # the additive cost of this full assignment: the DP with every layer pinned
    additive = search_graph(model, machine,
                            pins={nm: c.name for nm, c in choices.items()}).cost
    return choices, additive


def test_single_device_chain_is_serial():
    """No mesh parallelism -> no comm tasks; makespan == sum of compute."""
    mach = MachineSpec(mesh_axes={"data": 1}, chip="v5e")
    m = chain_model(d=512, n=3, b=4, s=64)
    choices, _ = plan(m, mach)
    rep = simulate_strategy(m, choices, mach)
    assert not any(t.kind == "comm" for t in rep.tasks)
    assert rep.makespan == pytest.approx(
        sum(t.duration for t in rep.tasks), rel=1e-9)


def test_gradsync_hides_behind_backward():
    """Compute-bound DP chain: grad all-reduces of late layers ride link:data
    while the MXU runs earlier layers' backward — most comm time hides, and
    the simulated step beats the additive sum even though the simulator
    *additionally* prices optimizer updates the additive model ignores."""
    mach = MachineSpec(**MESH22)
    m = chain_model()
    choices, additive = plan(m, mach)
    rep = simulate_strategy(m, choices, mach)
    assert rep.hidden_frac > 0.8
    assert rep.makespan < additive


def test_position_dependent_exposure():
    """THE fidelity gap vs additive costing: sharding an early layer halves
    an *exposed* grad sync (its backward runs last — nothing left to hide
    behind); sharding a late layer halves a *hidden* one. The additive model
    prices the same candidate multiset identically regardless of position;
    the replay strictly prefers shard-early."""
    mach = MachineSpec(**MESH22)
    m = chain_model()
    ch0, add0 = plan(m, mach, shard=("fc0",))
    ch7, add7 = plan(m, mach, shard=("fc7",))
    assert add0 == pytest.approx(add7, rel=1e-9)  # additive cannot see it
    r0 = simulate_strategy(m, ch0, mach)
    r7 = simulate_strategy(m, ch7, mach)
    assert r0.makespan < r7.makespan * 0.995


def test_rerank_breaks_additive_tie():
    """The taskgraph re-rank (simulator_mode='taskgraph') decides between DP
    finalists the additive model scores identically."""
    mach = MachineSpec(**MESH22)
    m = chain_model()
    ch0, add0 = plan(m, mach, shard=("fc0",))
    ch7, add7 = plan(m, mach, shard=("fc7",))
    finalists = [SearchResult(choices=ch7, cost=add7, mem_bytes=0),
                 SearchResult(choices=ch0, cost=add0, mem_bytes=0)]
    best, reports = rerank(m, mach, finalists)
    assert best.choices is ch0
    assert len(reports) == 2
    assert reports[1].makespan < reports[0].makespan


def test_segmented_transfers():
    """A big grad sync splits into 16MB-chunk tasks chained on the link
    (reference --simulator-segment-size); a short transfer interleaves
    between chunks instead of waiting for the whole thing."""
    mach = MachineSpec(**MESH22)
    m = chain_model(d=4096, n=2)
    choices, _ = plan(m, mach)
    tasks = build_step_tasks(m, choices, mach)
    seg = [t for t in tasks if "[0/" in t.name]
    assert seg, "expected segmented comm tasks for 67MB grad syncs"

    # manual interleave: long 10-seg transfer (no dependents) + short
    # transfer gating a compute task, all ready at t=0 on one link
    def manual(seg_long):
        ts = []
        prev = None
        for i in range(seg_long):
            t = SimTask(f"long[{i}]", "comm", "link:x", 1.0)
            if prev is not None:
                prev.add_next(t)
            ts.append(t)
            prev = t
        short = SimTask("short", "comm", "link:x", 1.0)
        comp = SimTask("comp", "comp", "mxu", 1.0)
        short.add_next(comp)
        return ts + [short, comp], comp

    tasks, comp = manual(10)
    replay(tasks)
    t_seg = comp.end
    tasks, comp = manual(1)  # unsegmented: one 10s task... scaled to 1s x1
    # emulate unsegmented long transfer of the same total duration
    tasks[0].duration = 10.0
    replay(tasks)
    t_unseg = comp.end
    assert t_seg < t_unseg  # short xfer squeezed between segments


def test_replay_deadlock_guard():
    a = SimTask("a", "comp", "mxu", 1.0)
    b = SimTask("b", "comp", "mxu", 1.0)
    a.add_next(b)
    b.add_next(a)
    with pytest.raises(RuntimeError, match="deadlock"):
        replay([a, b])


def test_timeline_resources_disjoint(tmp_path):
    """Each resource's scheduled intervals never overlap; the exported
    chrome trace is valid JSON."""
    mach = MachineSpec(**MESH22)
    m = chain_model(d=1024, n=4)
    choices, _ = plan(m, mach, shard=("fc1",))
    rep = simulate_strategy(m, choices, mach)
    by_res = {}
    for t in rep.tasks:
        by_res.setdefault(t.resource, []).append((t.start, t.end))
    for res, spans in by_res.items():
        spans.sort()
        for (s1, e1), (s2, e2) in zip(spans, spans[1:]):
            assert e1 <= s2 + 1e-12, f"overlap on {res}"
    out = tmp_path / "trace.json"
    rep.export_trace(str(out))
    import json

    data = json.loads(out.read_text())
    assert any(e.get("cat") == "comm" for e in data["traceEvents"])


def test_unity_taskgraph_mode():
    """simulator_mode='taskgraph' runs the DP -> topk -> replay re-rank
    inside unity_optimize and still yields an executable strategy."""
    from flexflow_tpu.search.unity import unity_optimize

    cfg = FFConfig(batch_size=8, search_budget=8,
                   simulator_mode="taskgraph", simulator_topk=3)
    m = FFModel(cfg)
    x = m.create_tensor([8, 256, 1024], name="x")
    h = m.dense(x, 4096, activation="gelu", name="up")
    h = m.dense(h, 1024, name="down")
    mach = MachineSpec(**MESH22)
    st, stats = unity_optimize(m, mach)
    assert st.op_shardings
    assert stats.best_cost > 0


def test_simulator_trace_export_flag(tmp_path, devices):
    """--simulator-trace: compiling writes a chrome trace of the compiled
    strategy's event-driven replay (the reference simulator's
    export_file_name analog), including comm tasks on link timelines."""
    import json as _json

    import numpy as np

    from flexflow_tpu import FFModel, FFConfig, SGDOptimizer

    out = tmp_path / "step_trace.json"
    cfg = FFConfig(batch_size=16, mesh_shape={"data": 4, "model": 2},
                   search_budget=8, simulator_trace=str(out))
    m = FFModel(cfg)
    x = m.create_tensor([16, 64], name="x")
    h = m.dense(x, 2048, activation="relu", name="up")
    m.dense(h, 64, name="down")
    m.compile(SGDOptimizer(lr=0.01), "mean_squared_error", [])
    data = _json.loads(out.read_text())
    names = {e.get("name", "") for e in data["traceEvents"]}
    assert any(n.startswith("up:fwd") for n in names), names
    assert any(":gradsync" in n for n in names), names
    # flag parse path
    c2 = FFConfig.parse_args(["--simulator-trace", "/tmp/x.json"])
    assert c2.simulator_trace == "/tmp/x.json"


def test_dcn_axis_priced_on_its_own_link():
    """Multi-slice machine: grad syncs over the node+data batch axes bind
    to the SLOWEST involved link (the DCN node axis — _link_of picks the
    stage that dominates the hierarchical collective), while a tp layer's
    all-reduce rides the ICI model link; DCN tasks carry the DCN-priced
    duration."""
    from flexflow_tpu.search import cost_model as cm

    mach = MachineSpec(mesh_axes={"node": 2, "data": 2, "model": 2},
                       chip="v5e", dcn_axes=("node",), overlap_frac=0.0)
    m = chain_model(d=2048, n=4, b=16, s=256)
    choices, _ = plan(m, mach, shard=("fc1",))
    rep = simulate_strategy(m, choices, mach)
    links = {t.resource for t in rep.tasks if t.kind == "comm"}
    assert "link:node" in links, links   # gradsync binds to the DCN stage
    assert "link:model" in links, links  # tp_row's all-reduce rides ICI
    gs = [t for t in rep.tasks if t.resource == "link:node"
          and t.name.startswith("fc0:kernel:gradsync")]
    assert gs, [t.name for t in rep.tasks if t.kind == "comm"]
    w = m.get_layer_by_name("fc0").weight_specs["kernel"]
    expect = cm.all_reduce_time(w.size_bytes, ("node", "data"), mach)
    assert sum(t.duration for t in gs) == pytest.approx(expect, rel=1e-6)
