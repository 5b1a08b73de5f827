"""Search fast path (tiers 1-3): persistent strategy cache, memoized
candidate costing, incremental DP re-costing — plus the fork_join
batch-sharding candidate gate and the persistent measured-cost store."""

import json
import os

import pytest

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.parallel.machine import MachineSpec
from flexflow_tpu.search import memo
from flexflow_tpu.search import strategy_cache as sc
from flexflow_tpu.search.candidates import layer_candidates
from flexflow_tpu.search.dp import SEARCH_STATS, reset_search_stats, search_graph
from flexflow_tpu.search.optimize import graph_optimize

V5P8 = MachineSpec(mesh_axes={"data": 4, "model": 2}, chip="v5p")


@pytest.fixture(autouse=True)
def _fresh_fastpath():
    """Each test starts with clean memo tables / DP counters and never
    leaks a disabled fast path to its neighbors."""
    memo.clear()
    reset_search_stats()
    yield
    memo.set_enabled(True)
    memo.clear()


def _mlp(cache_dir, budget=8, extra=False, batch=32):
    m = FFModel(FFConfig(batch_size=batch, search_budget=budget,
                         strategy_cache_dir=str(cache_dir)))
    x = m.create_tensor([batch, 512], name="x")
    h = m.dense(x, 2048, activation="gelu", name="up")
    h = m.dense(h, 512, name="down")
    if extra:
        h = m.dense(h, 512, name="extra")
    m.dense(h, 16, name="head")
    return m


def _gpt2_block(batch=8, d=256):
    """Transformer block with two structural-twin sub-chains (the memo's
    target workload)."""
    m = FFModel(FFConfig(batch_size=batch))
    x = m.create_tensor([batch, 16, d], name="x")
    att = m.multihead_attention(x, x, x, d, 8, name="mha")
    h = m.add(att, x, name="res1")
    h = m.layer_norm(h, name="ln1")
    up = m.dense(h, 4 * d, activation="gelu", name="ffn_up")
    down = m.dense(up, d, name="ffn_down")
    m.add(down, h, name="res2")
    return m


# --------------------------------------------------- tier 1: strategy cache
def test_warm_search_skips_dp_and_returns_identical_strategy(tmp_path):
    st1 = graph_optimize(_mlp(tmp_path), V5P8)
    assert SEARCH_STATS["expansions"] > 0
    assert st1._cache_info["event"] == "store"
    reset_search_stats()
    st2 = graph_optimize(_mlp(tmp_path), V5P8)
    # the search-call counter: a warm hit runs NO DP at all
    assert SEARCH_STATS["expansions"] == 0
    assert SEARCH_STATS["calls"] == 0
    assert st2._cache_info["event"] == "hit"
    assert json.loads(json.dumps(st1.to_json())) == \
        json.loads(json.dumps(st2.to_json()))


def test_warm_compile_hits_cache(devices, tmp_path):
    def compile_once():
        cfg = FFConfig(batch_size=32, mesh_shape={"data": 4, "model": 2},
                       search_budget=8, strategy_cache_dir=str(tmp_path))
        m = FFModel(cfg)
        x = m.create_tensor([32, 512], name="x")
        h = m.dense(x, 2048, activation="gelu", name="up")
        m.dense(h, 16, name="head")
        return m.compile(SGDOptimizer(lr=0.01),
                         LossType.SPARSE_CATEGORICAL_CROSSENTROPY)

    cm1 = compile_once()
    assert cm1.search_cache_info["event"] == "store"
    reset_search_stats()
    cm2 = compile_once()
    assert cm2.search_cache_info["event"] == "hit"
    assert SEARCH_STATS["expansions"] == 0  # zero DP frontier expansions
    assert cm2.strategy.name == cm1.strategy.name
    stats = cm2.search_cache_stats()
    assert stats["strategy_cache"]["hits"] >= 1
    assert stats["dp"]["expansions"] == 0


def test_cache_invalidates_on_graph_mesh_and_knob_change(tmp_path):
    graph_optimize(_mlp(tmp_path), V5P8)  # seed the cache
    # graph edit
    reset_search_stats()
    graph_optimize(_mlp(tmp_path, extra=True), V5P8)
    assert SEARCH_STATS["expansions"] > 0
    # mesh change
    reset_search_stats()
    graph_optimize(_mlp(tmp_path),
                   MachineSpec(mesh_axes={"data": 2, "model": 4}, chip="v5p"))
    assert SEARCH_STATS["expansions"] > 0
    # search-knob change
    reset_search_stats()
    graph_optimize(_mlp(tmp_path, budget=12), V5P8)
    assert SEARCH_STATS["expansions"] > 0
    # and the original key still hits
    reset_search_stats()
    graph_optimize(_mlp(tmp_path), V5P8)
    assert SEARCH_STATS["expansions"] == 0


def test_cache_invalidates_on_fork_join_branch_edit(tmp_path):
    """Branch sub-layers live outside the composite's params/weight_specs;
    editing a branch body (activation change — same weight names/shapes,
    same output shape) must change the graph fingerprint, not serve the
    strategy searched against the old branch costs."""
    def build(act):
        m = FFModel(FFConfig(batch_size=8, search_budget=8,
                             strategy_cache_dir=str(tmp_path)))
        x = m.create_tensor([8, 32], name="x")
        m.fork_join(x, [lambda mm, t: mm.dense(t, 32, activation=act,
                                               name="d1"),
                        lambda mm, t: mm.dense(t, 32, name="d2")],
                    join="add", name="fj")
        return m

    graph_optimize(build(None), V5P8)
    reset_search_stats()
    graph_optimize(build("gelu"), V5P8)
    assert SEARCH_STATS["expansions"] > 0  # miss: branch content re-keyed


def test_stale_entry_is_invalidated_not_applied(tmp_path):
    m = _mlp(tmp_path)
    st = graph_optimize(m, V5P8)
    key = st._cache_info["key"]
    # corrupt the entry: point a sharding at a layer the graph doesn't have
    path = os.path.join(str(tmp_path), f"{key}.json")
    with open(path) as f:
        entry = json.load(f)
    entry["strategy"]["ops"]["ghost_layer"] = {"outputs": [["data"]],
                                               "weights": {}}
    with open(path, "w") as f:
        json.dump(entry, f)
    before = sc.STATS.invalidated
    reset_search_stats()
    st2 = graph_optimize(_mlp(tmp_path), V5P8)
    assert sc.STATS.invalidated == before + 1
    assert SEARCH_STATS["expansions"] > 0  # fell back to a real search
    assert "ghost_layer" not in st2.op_shardings


def test_validate_strategy_flags_rank_and_axis_drift(tmp_path):
    m = _mlp(tmp_path)
    st = graph_optimize(m, V5P8)
    assert sc.validate_strategy(st, m, V5P8) == []
    bad = json.loads(json.dumps(st.to_json()))
    bad["ops"]["up"]["outputs"] = [["data"]]  # rank 1 vs rank-2 tensor
    from flexflow_tpu.parallel.sharding import Strategy

    assert sc.validate_strategy(Strategy.from_json(bad), m, V5P8)
    bad2 = json.loads(json.dumps(st.to_json()))
    bad2["ops"]["up"]["weights"] = {"kernel": [None, "expert"]}  # no such axis
    assert sc.validate_strategy(Strategy.from_json(bad2), m, V5P8)


# ------------------------------------------------ tier 2: memoized costing
def test_memoized_costing_bitwise_equal_on_gpt2_block():
    memo.set_enabled(False)
    r_off = search_graph(_gpt2_block(), V5P8, beam_width=32)
    memo.set_enabled(True)
    memo.clear()
    r_on = search_graph(_gpt2_block(), V5P8, beam_width=32)
    assert r_on.cost == r_off.cost  # bitwise: memo only reuses, never recomputes
    assert r_on.mem_bytes == r_off.mem_bytes
    assert {k: c.name for k, c in r_on.choices.items()} == \
        {k: c.name for k, c in r_off.choices.items()}
    # and the tables actually saw traffic on the twin sub-chains
    s = memo.stats()
    assert sum(v["hits"] for v in s.values()) > 0


def test_incremental_dp_matches_full_recosting():
    """The substitution loop with the tier-3 prefix cache must land on the
    same winner at the same cost as full per-graph re-costing."""
    from flexflow_tpu.search.unity import unity_optimize

    def run():
        m = _gpt2_block()
        m.config.search_budget = 16
        return unity_optimize(m, V5P8)

    memo.set_enabled(False)  # disables memo AND the prefix cache
    st_off, stats_off = run()
    memo.set_enabled(True)
    memo.clear()
    reset_search_stats()
    st_on, stats_on = run()
    assert stats_on.best_cost == stats_off.best_cost
    assert st_on.to_json()["ops"] == st_off.to_json()["ops"]
    assert SEARCH_STATS["layers_skipped"] > 0  # the fast path actually fired


# ---------------------------------------------- measured-cost persistence
def test_measured_cost_persists_across_processes(tmp_path, monkeypatch):
    from flexflow_tpu.search.measure import MeasuredCost

    m = _mlp(tmp_path)
    layer = m.get_layer_by_name("up")
    cand = layer_candidates(layer, V5P8, {32})[0]

    mc1 = MeasuredCost(V5P8, cache_dir=str(tmp_path))
    monkeypatch.setattr(mc1, "_measure", lambda l, c: (0.5, 1.25))
    assert mc1.op_times(layer, cand) == (0.5, 1.25)
    assert os.path.exists(mc1.cache_path)

    mc2 = MeasuredCost(V5P8, cache_dir=str(tmp_path))  # "new process"
    def boom(l, c):
        raise AssertionError("disk-cached measurement was re-run")
    monkeypatch.setattr(mc2, "_measure", boom)
    assert mc2.op_times(layer, cand) == (0.5, 1.25)
    # the store doubles as the calibration fingerprint: content-addressed
    fp = sc.calibration_fingerprint(mc1.cache_path)
    assert fp.startswith("measured:") and fp != "measured:empty"


def test_measured_path_rekeys_on_post_search_calibration(tmp_path, monkeypatch):
    """The measured search writes new microbenchmarks into the store its
    cache key fingerprints — the entry must be stored under the POST-search
    calibration fingerprint so the very next run hits."""
    from flexflow_tpu.search.measure import MeasuredCost

    monkeypatch.setattr(MeasuredCost, "_measure",
                        lambda self, l, c: (1e-4, 2e-4))
    st1 = graph_optimize(_mlp(tmp_path), V5P8, measured=True)
    assert st1._cache_info["event"] == "store"
    assert st1._cache_info["meta"]["calibration"].startswith("measured:")
    reset_search_stats()
    st2 = graph_optimize(_mlp(tmp_path), V5P8, measured=True)
    assert st2._cache_info["event"] == "hit"
    assert SEARCH_STATS["calls"] == 0


# ----------------------------------- satellite: fork_join candidate gate
def _fork_join_model(batch):
    m = FFModel(FFConfig(batch_size=batch))
    x = m.create_tensor([batch, 32], name="x")
    m.fork_join(x, [lambda mm, t: mm.dense(t, 32, name="d1"),
                    lambda mm, t: mm.dense(t, 32, name="d2")], join="add",
                name="fj")
    return m


def test_inter_candidates_gated_on_batch_sharding():
    """Batch 6 on data=4 cannot shard the batch, and inter:
    placement's backward fails at trace time under a replicated batch — the
    search must not offer what compile cannot run."""
    fj6 = next(l for l in _fork_join_model(6).layers
               if l.op_type is OperatorType.FORK_JOIN)
    names6 = {c.name for c in layer_candidates(fj6, V5P8, {6})}
    assert not any(n.startswith("inter:") for n in names6), names6
    # divisible batch keeps the candidates
    fj8 = next(l for l in _fork_join_model(8).layers
               if l.op_type is OperatorType.FORK_JOIN)
    names8 = {c.name for c in layer_candidates(fj8, V5P8, {8})}
    assert any(n.startswith("inter:") for n in names8), names8


def test_warm_compile_restores_searched_remat_with_zero_expansions(tmp_path):
    """ISSUE-12 cache contract: the knob fingerprint keys on the remat
    knobs and the per-layer policy block rides the serialized strategy —
    a warm compile at the same knobs restores the remat assignment with
    ZERO DP expansions, and flipping --remat-search re-searches."""
    from flexflow_tpu.parallel.machine import MachineSpec as MS

    def chain(remat_search=True):
        cfg = FFConfig(batch_size=8192, search_budget=8,
                       memory_search=True, remat_search=remat_search,
                       strategy_cache_dir=str(tmp_path))
        m = FFModel(cfg)
        x = m.create_tensor([8192, 2048], name="x")
        h = x
        for i in range(6):
            h = m.dense(h, 2048, activation="gelu", name=f"blk{i}")
        m.dense(h, 256, name="head")
        return m

    # hbm cap ~0.4x the unconstrained high-water: remat must be chosen
    mach = MS(mesh_axes={"data": 2, "model": 4}, chip="v5e",
              hbm_bytes=75e6)
    st1 = graph_optimize(chain(), mach)
    assert SEARCH_STATS["expansions"] > 0
    assert st1._cache_info["event"] == "store"
    assert st1.remat, "memory cap should force a remat assignment"
    assert set(st1.remat.values()) <= {"dots", "full"}

    reset_search_stats()
    st2 = graph_optimize(chain(), mach)
    assert st2._cache_info["event"] == "hit"
    assert SEARCH_STATS["expansions"] == 0  # the headline: no DP at all
    assert SEARCH_STATS["calls"] == 0
    assert st2.remat == st1.remat

    # knob change (search off) is a different cache key: fresh search,
    # and the plain DP assigns no remat
    reset_search_stats()
    st3 = graph_optimize(chain(remat_search=False), mach)
    assert SEARCH_STATS["expansions"] > 0
    assert not st3.remat
