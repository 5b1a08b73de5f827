"""ISSUE 11 — serving under fire.

Covers the tentpole's three pillars plus the satellites: live hot-swap
from a watched durable-checkpoint root (bitwise rollback, pinning,
fingerprint rejection of a mismatched snapshot, `load_params` schema
validation), SLO-aware admission (typed KV-pool exhaustion + page-churn
accounting, prompt-too-long shedding, queue-cap priority displacement,
deadline/TTFT-budget sweeps, the decode watchdog), and the serve/* fault
sites (a transient fault at each request-path site costs a retry and
nothing else; a permanent one fails only the affected request while the
engine keeps serving). The over-decode waste fix rides along: with the
window capped at the smallest remaining budget, `overdecode_tokens`
stays zero without EOS. The monitor's serving panel is exercised on a
synthetic event stream (pure `gather`)."""

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer, health
from flexflow_tpu.models import GPT2Config, build_gpt2
from flexflow_tpu.runtime import faults
from flexflow_tpu.runtime.checkpoint import CheckpointMismatchError
from flexflow_tpu.runtime.resilience import RetryPolicy, save_durable
from flexflow_tpu.search.cost_model import KVCacheSpec
from flexflow_tpu.serving import (ContinuousBatchingScheduler, KVPoolExhausted,
                                  PagedKVCache, Request, compile_serving,
                                  gpt2_prompt_inputs, gpt2_step_inputs)

MESH = {"data": 2, "model": 4}


def _gpt2_cfg():
    return GPT2Config(vocab=256, seq=16, d_model=64, heads=2, layers=1,
                      dropout=0.0)


@pytest.fixture(scope="module")
def serve_env(devices, tmp_path_factory):
    """One searched serving engine + a training-side snapshot producer of
    the SAME graph, shared across the module (the compiles are the
    expensive bit). Tests that swap params leave the engine unpinned and
    un-watched behind themselves."""
    gc = _gpt2_cfg()
    cfg = FFConfig(search_budget=16, mesh_shape=dict(MESH),
                   log_level="warning", max_batch_slots=4, kv_page_size=4)
    m = FFModel(cfg)
    build_gpt2(m, gc, batch=8)
    eng = compile_serving(m, max_decode_len=6)
    eng.init(seed=0)

    tcfg = FFConfig(search_budget=0, only_data_parallel=True,
                    log_level="warning", max_batch_slots=4, kv_page_size=4,
                    async_checkpoint=False)
    tm = FFModel(tcfg)
    build_gpt2(tm, gc, batch=8)
    cm = tm.compile(SGDOptimizer(lr=0.01),
                    loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=0)
    root = str(tmp_path_factory.mktemp("swap_root"))
    return eng, gc, cm, root


def _snapshot(cm, root, step, seed=None):
    cm.init(seed=step if seed is None else seed)
    cm._iteration = step
    return save_durable(cm, root, block=True)


def _sched(eng, **kw):
    kw.setdefault("retry_policy", RetryPolicy(attempts=3, base_delay=0.001,
                                              seed=3))
    return ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                       gpt2_step_inputs, eos_id=None,
                                       dispatch_ahead=4, **kw)


def _reqs(n, gc, max_new=4, **kw):
    rng = np.random.default_rng(41)
    return [Request(rid=i, prompt=list(rng.integers(1, gc.vocab, size=4)),
                    max_new_tokens=max_new, arrival_s=0.0, **kw)
            for i in range(n)]


def _probe(eng, gc):
    ids = np.arange(gc.seq, dtype=np.int32)[None, :].repeat(eng.slots, 0) \
        % gc.vocab
    pos = np.ascontiguousarray(np.broadcast_to(
        np.arange(gc.seq, dtype=np.int32), ids.shape))
    lg, _ = eng.prefill(eng.params, [ids, pos])
    return np.asarray(lg)


# ------------------------------------------------------ KV pool (satellite)
def test_kv_admit_raises_typed_exhaustion():
    """`admit` surfaces a short free list as KVPoolExhausted (carrying
    slot/need/have), not a bare free-list IndexError — and the type is
    deliberately NOT retryable (not a RuntimeError): pool exhaustion is
    backpressure only an eviction can clear, so the scheduler's
    shed-or-queue path must see it immediately."""
    spec = KVCacheSpec(layers=1, heads=2, head_dim=4, slots=2,
                       pages_per_slot=4, page_size=2)
    kv = PagedKVCache(spec, ["attn0"])
    assert kv.admit(0, prompt_len=2, total_tokens=8) is True
    # a lost race below can_admit: the free list shrank under us
    kv.free_pages = kv.free_pages[:1]
    with pytest.raises(KVPoolExhausted) as ei:
        kv.admit(1, prompt_len=2, total_tokens=8)
    assert (ei.value.slot, ei.value.need, ei.value.have) == (1, 4, 1)
    assert not isinstance(ei.value, RuntimeError)
    assert not kv._active[1]  # the failed admit left no partial state


def test_kv_churn_conserves_pages():
    """Admission/eviction churn never leaks or duplicates pages: the free
    list plus every live slot's pages always partition the pool, and a
    masked `sync_after` advance only moves active slots."""
    spec = KVCacheSpec(layers=1, heads=2, head_dim=4, slots=3,
                       pages_per_slot=3, page_size=4)
    kv = PagedKVCache(spec, ["attn0"])
    pool = set(range(1, spec.pool_pages))  # page 0 is scratch
    rng = np.random.default_rng(7)
    held = {}
    for _ in range(200):
        if held and (len(held) == spec.slots or rng.random() < 0.5):
            slot = int(rng.choice(sorted(held)))
            kv.evict(slot)
            held.pop(slot)
        else:
            slot = [s for s in range(spec.slots) if s not in held][0]
            tot = int(rng.integers(1, spec.padded_len + 1))
            kv.admit(slot, prompt_len=1, total_tokens=tot)
            held[slot] = set(kv._slot_pages[slot])
        live = set().union(*held.values()) if held else set()
        assert live | set(kv.free_pages) == pool
        assert len(live) + len(kv.free_pages) == len(pool)  # no dupes
    for s in list(held):
        kv.evict(s)
    assert set(kv.free_pages) == pool
    # masked advance: finished slots (advance 0) and inactive slots stay
    kv.admit(0, prompt_len=3, total_tokens=8)
    kv.admit(1, prompt_len=5, total_tokens=8)
    kv.sync_after(4, advances=np.array([4, 0, 4], np.int32))
    assert kv._pos[0] == 7 and kv._pos[1] == 5 and kv._pos[2] == 0


# ------------------------------------------- admission control / shedding
def test_prompt_too_long_shed_at_admit(serve_env):
    """A prompt the prefill window can never hold is shed as
    prompt_too_long at enqueue (the PR-10 gap: it used to be silently
    truncated into serving a different request)."""
    eng, gc, _, _ = serve_env
    sched = _sched(eng)
    good = _reqs(1, gc)[0]
    bad = Request(rid=99, prompt=list(range(1, gc.seq + 2)),
                  max_new_tokens=4, arrival_s=0.0)
    done = sched.run([good, bad])
    assert [r.rid for r in done] == [0]
    assert sched.shed and sched.shed[0].rid == 99
    assert sched.shed[0].outcome == "shed"
    assert sched.shed[0].shed_reason == "prompt_too_long"
    assert sched.stats["shed_prompt_too_long"] == 1


def test_queue_cap_displaces_by_priority(serve_env):
    """Shed-or-queue at a full queue: an urgent arrival displaces the
    worst waiter; a non-urgent one is shed itself."""
    eng, gc, _, _ = serve_env
    sched = _sched(eng, queue_cap=2)
    waiting = _reqs(2, gc, priority=2)
    urgent = Request(rid=10, prompt=[1, 2], max_new_tokens=4, priority=0)
    lazy = Request(rid=11, prompt=[1, 2], max_new_tokens=4, priority=3)
    sched._enqueue(urgent, waiting, now_s=0.1)
    assert urgent in waiting and len(waiting) == 2
    assert sched.stats["shed_queue_full"] == 1
    sched._enqueue(lazy, waiting, now_s=0.2)
    assert lazy not in waiting
    assert sched.stats["shed_queue_full"] == 2
    assert all(r.shed_reason == "queue_full" for r in sched.shed)


def test_deadline_and_ttft_budget_sweep(serve_env):
    """The stale sweep sheds deadline-expired waiters and waiters whose
    elapsed wait + EMA service time already blows the TTFT budget."""
    eng, gc, _, _ = serve_env
    sched = _sched(eng, ttft_budget_ms=100.0)
    expired = Request(rid=0, prompt=[1], max_new_tokens=2, arrival_s=0.0,
                      deadline_s=0.5)
    hopeless = Request(rid=1, prompt=[1], max_new_tokens=2, arrival_s=0.9)
    fresh = Request(rid=2, prompt=[1], max_new_tokens=2, arrival_s=0.99)
    sched._ema_serve_ms = 50.0
    waiting = [expired, hopeless, fresh]
    sched._shed_stale(waiting, now_s=1.0)
    assert waiting == [fresh]
    assert sched.stats["shed_deadline"] == 1
    assert sched.stats["shed_ttft_budget"] == 1
    reasons = {r.rid: r.shed_reason for r in sched.shed}
    assert reasons == {0: "deadline", 1: "ttft_budget"}


def test_overload_sheds_are_counted_and_the_served_complete(serve_env):
    """Twelve mixed-priority arrivals at once against four slots and a
    queue of two: every request ends in exactly one of done / shed /
    failed, the sheds are the ones the counters name, whoever was served
    got a full budget, and the SLO scoreboard classified every terminal
    (sheds burn the availability budget)."""
    eng, gc, _, _ = serve_env
    spec = "ttft_p99_ms=30000,availability=0.99"
    slo0, eng.slo = eng.slo, health.SLOTracker(health.parse_slo(spec))
    try:
        sched = _sched(eng, queue_cap=2, ttft_budget_ms=30000.0)
        reqs = _reqs(12, gc)
        for r in reqs:
            r.priority = r.rid % 3
        done = sched.run(reqs)
        rep = eng.slo.report()
    finally:
        eng.slo = slo0
    assert sched.shed and not sched.failed
    assert len(done) + len(sched.shed) == 12
    assert len(sched.shed) == sum(v for k, v in sched.stats.items()
                                  if k.startswith("shed_"))
    assert done and all(len(r.tokens) == r.max_new_tokens for r in done)
    assert set(rep["objectives"]) == set(health.parse_slo(spec))
    assert rep["requests"] == 12 and rep["shed_rate"] > 0.0
    assert rep["outcomes"] == {"done": len(done), "shed": len(sched.shed)}
    avail = rep["objectives"]["availability"]
    assert avail["bad"] == len(sched.shed) and avail["burn_rate_60s"] > 0.0
    assert np.isfinite(rep["objectives"]["ttft_p99_ms"]["budget_remaining"])


def test_decode_watchdog_evicts_wedged_slot(serve_env):
    """With an (absurdly tight) per-step budget every materialization
    trips the watchdog: the longest-resident slot is evicted with outcome
    "timeout" and the remaining slots keep decoding."""
    eng, gc, _, _ = serve_env
    sched = _sched(eng, decode_timeout_ms=1e-6)
    # max_new > dispatch_ahead so nobody finishes inside the first window
    done = sched.run(_reqs(2, gc, max_new=6))
    assert sched.stats["decode_timeouts"] >= 1
    assert sched.failed and sched.failed[0].outcome == "timeout"
    assert sched.stats["evicted_wedged"] >= 1
    assert len(done) + len(sched.failed) == 2
    assert all(len(r.tokens) == r.max_new_tokens for r in done)


def test_overdecode_zero_without_eos(serve_env):
    """The over-decode waste fix: the dispatch window is capped at the
    smallest remaining budget, so with no EOS in play NOTHING is decoded
    past a max-len finish (PR 10 overshot by up to dispatch_ahead-1)."""
    eng, gc, _, _ = serve_env
    sched = _sched(eng)
    done = sched.run(_reqs(5, gc, max_new=3))  # 3 < dispatch_ahead=4
    assert len(done) == 5
    assert all(len(r.tokens) == 3 for r in done)
    assert sched.stats["overdecode_tokens"] == 0


# ------------------------------------------------------- fault injection
def test_transient_serve_faults_cost_only_retries(serve_env):
    """One injected transient at each request-path site: every request
    still completes; the faults show up as fired + retry telemetry."""
    eng, gc, _, _ = serve_env
    faults.configure("serve/prefill@1,serve/kv_admit@1,serve/decode_step@1")
    try:
        sched = _sched(eng)
        done = sched.run(_reqs(4, gc))
        fired = dict(faults.fired())
    finally:
        faults.clear()
    assert len(done) == 4 and not sched.failed and not sched.shed
    for site in ("serve/prefill", "serve/kv_admit", "serve/decode_step"):
        assert fired.get(site, 0) == 1, (site, fired)


def test_permanent_decode_fault_evicts_only_affected(serve_env):
    """A decode fault armed to outlast the retry budget fails exactly one
    request (the evicted wedged slot); every other request completes and
    the engine keeps serving."""
    eng, gc, _, _ = serve_env
    faults.configure("serve/decode_step@2*3")  # *3 == the retry budget
    try:
        sched = _sched(eng)
        done = sched.run(_reqs(4, gc))
    finally:
        faults.clear()
    assert len(sched.failed) == 1
    assert sched.failed[0].outcome == "failed"
    assert len(done) == 3
    assert all(len(r.tokens) == r.max_new_tokens for r in done)
    assert sched.stats["evicted_wedged"] == 1


def test_permanent_kv_admit_fault_sheds_only_that_request(serve_env):
    """A permanent kv_admit fault fails the one request being admitted;
    the rest of the wave admits normally."""
    eng, gc, _, _ = serve_env
    faults.configure("serve/kv_admit@1*3")
    try:
        sched = _sched(eng)
        done = sched.run(_reqs(3, gc))
    finally:
        faults.clear()
    assert len(sched.failed) == 1 and len(done) == 2
    assert all(len(r.tokens) == r.max_new_tokens for r in done)


# ------------------------------------------------------ hot-swap / rollback
def test_load_params_rejects_mismatched_tree(serve_env):
    """Satellite (PR-10 gap): `load_params` validates the incoming tree
    against the serving graph instead of silently device_put-ing a
    mismatched one into the jitted programs."""
    eng, _, _, _ = serve_env
    with pytest.raises(CheckpointMismatchError):
        eng.load_params({"bogus_layer": {"w": np.zeros((2, 2), np.float32)}})


def test_hot_swap_rollback_pin_cycle(serve_env):
    """The full lifecycle on a watched root: discover+swap to each new
    snapshot, bitwise rollback to the retained previous version, pin
    blocks auto-advance, unpin resumes it."""
    eng, gc, cm, root = serve_env
    try:
        _snapshot(cm, root, 1)
        eng.watch(root, poll_interval_s=0.0, retain=2)
        assert eng.poll_swap(force=True)
        assert eng.active_version == 1
        l1 = _probe(eng, gc)
        _snapshot(cm, root, 2)
        assert eng.poll_swap(force=True)
        assert eng.active_version == 2
        l2 = _probe(eng, gc)
        assert not np.array_equal(l1, l2)
        rep = eng.health_report()["serving"]
        assert rep["swaps"] == 2 and rep["swap_p99_s"] > 0

        assert eng.rollback() == 1
        assert np.array_equal(_probe(eng, gc), l1)  # bitwise restore
        assert not eng.poll_swap(force=True)  # pinned: no auto re-deploy
        assert eng.active_version == 1
        eng.unpin()
        assert eng.poll_swap(force=True)
        assert eng.active_version == 2
        assert np.array_equal(_probe(eng, gc), l2)
        assert eng.health_report()["serving"]["rollbacks"] == 1
    finally:
        eng.unpin()
        eng._watch_root = None  # leave the module engine un-watched


def test_swap_rejects_mismatched_snapshot(serve_env, tmp_path):
    """A snapshot whose graph fingerprint differs (other d_model) is
    rejected + blacklisted: the engine keeps its version, counts the
    rejection once, and never re-reads the bad path."""
    eng, _, _, _ = serve_env
    bad_gc = GPT2Config(vocab=256, seq=16, d_model=32, heads=2, layers=1,
                        dropout=0.0)
    tcfg = FFConfig(search_budget=0, only_data_parallel=True,
                    log_level="warning", async_checkpoint=False)
    tm = FFModel(tcfg)
    build_gpt2(tm, bad_gc, batch=8)
    cm_bad = tm.compile(SGDOptimizer(lr=0.01),
                        loss_type="sparse_categorical_crossentropy",
                        metrics=[])
    cm_bad.init(seed=0)
    root = str(tmp_path / "bad_root")
    _snapshot(cm_bad, root, 5)
    before = eng.active_version
    rej0 = eng.health_report()["serving"]["rejected"]
    try:
        eng.watch(root, poll_interval_s=0.0)
        assert not eng.poll_swap(force=True)
        assert eng.active_version == before
        assert eng.health_report()["serving"]["rejected"] == rej0 + 1
        assert not eng.poll_swap(force=True)  # blacklisted: no re-read
        assert eng.health_report()["serving"]["rejected"] == rej0 + 1
    finally:
        eng._watch_root = None


def test_permanent_swap_fault_rejects_then_recovers(serve_env, tmp_path):
    """A snapshot whose read fails past the retry budget is rejected, not
    blacklisted (the mount may come back): the engine keeps its version
    and keeps serving, and the same snapshot activates once the fault is
    gone."""
    eng, gc, cm, _ = serve_env
    step = (eng.active_version or 0) + 1
    root = str(tmp_path / "root")
    _snapshot(cm, root, step)
    before, policy0 = eng.active_version, eng._swap_policy
    rej0 = eng.health_report()["serving"]["rejected"]
    try:
        eng.watch(root, poll_interval_s=0.0,
                  policy=RetryPolicy(attempts=3, base_delay=0.001, seed=3))
        faults.configure("serve/param_swap@1!")
        try:
            assert not eng.poll_swap(force=True)
        finally:
            faults.clear()
        assert eng.active_version == before
        assert eng.health_report()["serving"]["rejected"] == rej0 + 1
        eng._watch_root = None                  # serve on the old version
        sched = _sched(eng)
        assert len(sched.run(_reqs(3, gc))) == 3 and not sched.failed
        eng.watch(root, poll_interval_s=0.0)
        assert eng.poll_swap(force=True)
        assert eng.active_version == step
    finally:
        eng.unpin()
        eng._watch_root, eng._swap_policy = None, policy0


def test_hot_swap_under_load_drops_nothing(serve_env, tmp_path):
    """Other weights land while every slot is occupied: the swap waits for
    the drained window, no request is dropped, shed or cut short, the
    requests in flight carry the swap in their timelines, and what is
    served afterwards is what an engine built on the new weights serves."""
    eng, gc, cm, _ = serve_env
    step = (eng.active_version or 0) + 1
    root = str(tmp_path / "root")
    try:
        eng.watch(root, poll_interval_s=0.0)

        def during(dispatches):
            if dispatches == 3:
                _snapshot(cm, root, step, seed=31)
        got, sched, alive = _serve_watching_state(eng, gc, during=during)
        assert sched.stats["swaps"] == 1 and eng.active_version == step
        assert len(got) == 6 and not sched.shed and not sched.failed
        assert all(len(t) == 6 for t in got.values()) and all(alive)
        swapped = [t for t in sched.tracer.ring if t.get("swaps")]
        assert swapped and all(
            any(st["stage"] == "swap" for st in t["stages"]) for t in swapped)
        eng._watch_root = None
        after = _serve_watching_state(eng, gc)[0]
    finally:
        eng.unpin()
        eng._watch_root = None
    fresh = _small_engine()
    cm.init(seed=31)
    fresh.load_params(cm.params)
    assert after == _serve_watching_state(fresh, gc)[0]
    assert after != got                         # the weights did change


# ------------------------------------------- donated cache state (ISSUE 29)
def _alive(kv):
    return not any(x.is_deleted()
                   for x in jax.tree_util.tree_leaves(kv.state))


def _serve_watching_state(eng, gc, during=None, host_inputs=False):
    """Serve six requests through four slots with windows of up to 4 steps
    in flight. Every program that writes the pools donates the state it is
    handed, so at every dispatch (a fused speculative round: after every
    round) the caches must hold the newest trees, alive in every leaf.
    `host_inputs` makes the step inputs untraceable, which sends a
    speculative engine down its unfused rounds. Returns (tokens by rid,
    sched, the checks made)."""
    alive = []
    kvs = [eng.kv] + ([eng.draft.kv] if eng.draft is not None else [])

    def step_inputs(tokens, state):
        if host_inputs:
            tokens = jax.numpy.asarray(np.asarray(tokens))
        alive.append(any(state is kv.state for kv in kvs)
                     and all(_alive(kv) for kv in kvs))
        if during is not None:
            during(len(alive))
        return gpt2_step_inputs(tokens, state)

    fused = eng.draft is not None and not host_inputs
    sched = ContinuousBatchingScheduler(
        eng, eng.params, gpt2_prompt_inputs,
        gpt2_step_inputs if fused else step_inputs, eos_id=None,
        dispatch_ahead=4,
        retry_policy=RetryPolicy(attempts=3, base_delay=0.001, seed=3))
    if fused:   # the round traces its step inputs: watch the rounds
        one_round = sched._spec_round

        def watched_round(active, next_host):
            out = one_round(active, next_host)
            alive.append(all(_alive(kv) for kv in kvs))
            return out
        sched._spec_round = watched_round
    done = sched.run(_reqs(6, gc, max_new=6))
    alive.append(all(_alive(kv) for kv in kvs))
    return {r.rid: list(r.tokens) for r in done}, sched, alive


def _small_engine(draft=None, **cfg_kw):
    cfg = FFConfig(search_budget=16, mesh_shape=dict(MESH),
                   log_level="warning", max_batch_slots=4, kv_page_size=4,
                   strategy_cache=False, **cfg_kw)
    m = FFModel(cfg)
    build_gpt2(m, _gpt2_cfg(), batch=8)
    if draft is None:
        return compile_serving(m, max_decode_len=6)
    dm = FFModel(cfg)
    build_gpt2(dm, draft, batch=8)
    return compile_serving(m, max_decode_len=6, draft=dm, spec_tokens=2)


def _fault_mid_window(eng, gc, cm, root):
    """A transient dispatch fault with steps of the window in flight: the
    retry replays the same dispatch (an injected fault fires ahead of it,
    so its state was not consumed)."""
    want = _serve_watching_state(eng, gc)[0]
    faults.configure("serve/decode_step@2")
    try:
        got, _, alive = _serve_watching_state(eng, gc)
        assert dict(faults.fired()).get("serve/decode_step") == 1
    finally:
        faults.clear()
    return want, got, alive


def _spec_round_with_rollback(eng, gc, cm, root, fused=True):
    """A draft that is nearly always wrong: every round rolls both caches
    back to the committed extent (POS_KEY re-published, the state kept)."""
    want = _serve_watching_state(eng, gc)[0]
    spec = _small_engine(draft=GPT2Config(vocab=256, seq=16, d_model=16,
                                          heads=2, layers=1, dropout=0.0))
    spec.load_params(eng.params)
    spec.draft.init(seed=7)
    got, sched, alive = _serve_watching_state(spec, gc,
                                              host_inputs=not fused)
    assert (sched._spec_fused is not None) == fused
    assert 0 < sched.stats["spec_rounds"]
    assert sched.stats["spec_accepted_tokens"] \
        < sched.stats["spec_drafted_tokens"]
    return want, got, alive


def _spec_round_unfused(eng, gc, cm, root):
    """The same through K + 1 launches a round, each adopted at once."""
    return _spec_round_with_rollback(eng, gc, cm, root, fused=False)


def _spill_prefetch_join(eng, gc, cm, root):
    """Half the pages in the host tier: slots park (spill), come back
    (prefetch writes the pools outside any step) and rejoin."""
    want = _serve_watching_state(eng, gc)[0]
    tier = _small_engine(kv_host_pages=12, kv_prefetch_ahead=2)
    tier.load_params(eng.params)
    got, sched, alive = _serve_watching_state(tier, gc)
    ts = sched.kv.tier_stats()
    assert 0 < ts["kv_spills"] == ts["kv_refills"]
    return want, got, alive


def _hot_swap_between_windows(eng, gc, cm, root):
    """A snapshot of the weights being served lands mid-run: the swap
    happens at the next drained window; the params (never donated) change
    hands, the cache state (always donated) stays the one the last step
    returned."""
    step = (eng.active_version or 0) + 1
    root = os.path.join(root, "same_weights")   # the module's root has others
    try:
        _snapshot(cm, root, step, seed=29)
        eng.watch(root, poll_interval_s=0.0)
        assert eng.poll_swap(force=True)
        eng._watch_root = None                  # the clean run: no swap
        want = _serve_watching_state(eng, gc)[0]
        eng.watch(root, poll_interval_s=0.0)

        def during(dispatches):
            if dispatches == 3:                 # the same weights, newer
                _snapshot(cm, root, step + 1, seed=29)
        got, sched, alive = _serve_watching_state(eng, gc, during=during)
        assert sched.stats["swaps"] == 1 and eng.active_version == step + 1
    finally:
        eng.unpin()
        eng._watch_root = None
    return want, got, alive


def _spec_fault_after_a_launch(eng, gc, cm, root):
    """A draft that is always right (the target's own weights), and the
    verify launch of the second round fails after the draft's K launches
    consumed and advanced the draft cache: the round rolls the device
    positions back before the fault surfaces, so the retry drafts from the
    committed extent and is accepted as a clean run's round is."""
    want = _serve_watching_state(eng, gc)[0]
    spec = _small_engine(draft=_gpt2_cfg())
    spec.load_params(eng.params)
    spec.draft.load_params(eng.params)
    clean = _serve_watching_state(spec, gc, host_inputs=True)[1]
    verify, calls = spec.verify_step, []

    def flaky(*a, **kw):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("injected: verify launch refused")
        return verify(*a, **kw)
    spec.verify_step = flaky
    got, sched, alive = _serve_watching_state(spec, gc, host_inputs=True)
    assert sched._spec_fused is None and len(calls) > 2
    assert sched.stats["spec_accepted_tokens"] \
        == clean.stats["spec_accepted_tokens"] > 0
    return want, got, alive


@pytest.mark.parametrize("case", [
    _fault_mid_window, _spec_round_with_rollback, _spec_round_unfused,
    _spec_fault_after_a_launch,
    _spill_prefetch_join, _hot_swap_between_windows],
    ids=lambda f: f.__name__.lstrip("_"))
def test_donated_state_serves_the_same_tokens(serve_env, case):
    """The decode, verify and speculative-round steps and the prefill
    commit consume the cache state they are handed. None of what goes on
    around a window may meet a consumed tree, and each serves the tokens
    of a run without it."""
    want, got, alive = case(*serve_env)
    assert len(want) == 6 and got == want
    assert len(alive) > 6 and all(alive)


# ---------------------------------------------------------- observability
def test_monitor_serving_panel_from_synthetic_stream():
    """tools/monitor.py folds the ISSUE 11 event stream (swaps, sheds,
    evictions, serve retries) into the serving panel + prometheus export
    without a live run (gather is pure)."""
    import monitor

    events = [
        {"name": "serve/request_done", "ts": 0, "cat": "serve",
         "args": {"rid": 0, "tokens": 4, "ttft_s": 0.02}},
        {"name": "serve/param_swap", "ph": "X", "ts": 10, "dur": 52_000,
         "cat": "serve", "args": {"version": 7, "rollback": False}},
        {"name": "serve/version", "ts": 11, "cat": "serve",
         "args": {"version": 7, "rollback": False}},
        {"name": "serve/version", "ts": 12, "cat": "serve",
         "args": {"version": 6, "rollback": True}},
        {"name": "serve/request_shed", "ts": 13, "cat": "serve",
         "args": {"rid": 1, "reason": "queue_full"}},
        {"name": "serve/request_failed", "ts": 14, "cat": "serve",
         "args": {"rid": 2, "outcome": "timeout"}},
        {"name": "serve/slot_evicted", "ts": 14, "cat": "serve",
         "args": {"rid": 2, "slot": 0}},
        {"name": "retry", "ts": 15, "cat": "retry",
         "args": {"site": "serve/decode_step", "attempt": 1}},
        {"name": "retry", "ts": 16, "cat": "retry",
         "args": {"site": "fit/dispatch", "attempt": 1}},  # not serving
    ]
    state = monitor.gather(events)
    sv = monitor._serve_stats(state["serve"])
    assert sv["swaps"] == 1 and sv["swap_p99_ms"] == pytest.approx(52.0)
    assert sv["active_version"] == 6 and sv["rollbacks"] == 1
    assert (sv["shed"], sv["failed"], sv["evicted"]) == (1, 1, 1)
    assert sv["serve_retries"] == 1
    text = "\n".join(monitor.render(state))
    assert "swaps=1" in text and "rollbacks=1" in text and "shed=1" in text
