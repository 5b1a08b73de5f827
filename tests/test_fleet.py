"""ISSUE 18 — disaggregated serving fleet.

Covers the control-plane pieces in isolation (no engines): the lifted
AdmissionControl policy brain, exact cross-replica histogram merges, the
merged SLO scoreboard vs a union-fed tracker, the least-loaded/burn-aware
router and rolling-swap cursor gating + rollback-on-burn. Two real engines
then serve through the fleet: a single-replica fleet is bitwise the plain
scheduler, a tiered scheduler's rotation lead is `--kv-prefetch-ahead`
throughout, a disaggregated pair hands every request's KV pages off once
and serves the colocated pair's tokens, and a rollout swaps every replica
under load without a drop.
"""

import time

import numpy as np
import pytest

from flexflow_tpu.health import SLOTracker, parse_slo
from flexflow_tpu.serving import (AdmissionControl, FleetRouter,
                                  Request, RollingSwapController,
                                  merge_histograms, merge_slo_trackers)
from flexflow_tpu.serving.fleet import ReplicaHandle
from flexflow_tpu.serving.reqtrace import StreamingHistogram


# ------------------------------------------------------------- aggregation
def test_hist_merge_matches_pooled_bucket_for_bucket(rng):
    """The fleet's cross-replica histogram merge is EXACT: fixed shared
    bucket edges make merged counts identical — bucket for bucket — to one
    histogram fed the pooled samples, so fleet p99s are the true fleet
    quantiles, not an approximation over per-replica summaries."""
    per_replica = [np.abs(rng.lognormal(-3.0, 1.5, size=n))
                   for n in (137, 41, 260)]
    hists = []
    for samples in per_replica:
        h = StreamingHistogram()
        h.add_many(samples)
        hists.append(h)
    merged = merge_histograms(hists)
    pooled = StreamingHistogram()
    pooled.add_many(np.concatenate(per_replica))
    assert np.array_equal(merged.counts, pooled.counts)
    assert merged.count == pooled.count
    assert merged.sum == pytest.approx(pooled.sum)
    for q in (0.5, 0.9, 0.99):
        assert merged.quantile(q) == pooled.quantile(q)
    # merging never mutates the per-replica sources' identity semantics:
    # the originals still hold only their own counts
    assert sum(h.count for h in hists) == merged.count


def _rec(outcome="done", ttft_s=None):
    rec = {"outcome": outcome}
    if ttft_s is not None:
        rec["ttft_s"] = ttft_s
    return rec


def test_merged_slo_matches_union_fed_tracker():
    """merge_slo_trackers rebuilds the scoreboard a single tracker would
    hold had it seen the union of every replica's terminal records:
    totals, outcome tallies, windowed burn rates, and budgets all match a
    union-fed tracker exactly (events interleave by timestamp)."""
    objectives = parse_slo("ttft_p90_ms=100,availability=0.9")
    # two replicas observing interleaved streams (explicit now_s so the
    # window math is deterministic)
    stream_a = [(1.0, _rec(ttft_s=0.05)), (3.0, _rec(ttft_s=0.25)),
                (5.0, _rec("shed")), (7.0, _rec(ttft_s=0.08))]
    stream_b = [(2.0, _rec(ttft_s=0.15)), (4.0, _rec(ttft_s=0.04)),
                (6.0, _rec("failed")), (8.0, _rec(ttft_s=0.30))]
    ta = SLOTracker(dict(objectives))
    tb = SLOTracker(dict(objectives))
    for ts, rec in stream_a:
        ta.observe(rec, now_s=ts)
    for ts, rec in stream_b:
        tb.observe(rec, now_s=ts)
    merged = merge_slo_trackers([ta, tb, None])  # None slots are skipped
    union = SLOTracker(dict(objectives))
    for ts, rec in sorted(stream_a + stream_b):
        union.observe(rec, now_s=ts)
    now = 10.0
    assert merged.report(now_s=now) == union.report(now_s=now)
    assert merged.requests == 8
    assert merged.outcomes == union.outcomes
    # and the merged events really are time-ordered (the window walk
    # assumes it)
    ts_seq = [ts for ts, _ in merged.events]
    assert ts_seq == sorted(ts_seq)


def test_merged_slo_preserves_windowed_state_across_wrapped_rings():
    """The ISSUE 20 windowed-state fix: merge_slo_trackers must carry
    the event ring's BOUND through the merge (not fall back to the
    100k default) and keep window burn rates equal to a union-fed
    tracker's even after the per-replica rings have wrapped. An old bad
    burst that wrapped OUT of the rings must not haunt burn_rate_60s."""
    objectives = parse_slo("ttft_p90_ms=100")
    cap = 6
    # replica A: an ancient bad burst (t~10s) that its ring then wraps
    # away under `cap` recent good events; replica B: a recent good tail
    old_bad = [(10.0 + i, _rec(ttft_s=0.5)) for i in range(4)]
    recent_a = [(1000.0 + i, _rec(ttft_s=0.01)) for i in range(cap)]
    recent_b = [(1000.5 + i, _rec(ttft_s=0.02)) for i in range(4)]
    ta = SLOTracker(dict(objectives), max_events=cap)
    tb = SLOTracker(dict(objectives), max_events=cap)
    for ts, rec in old_bad + recent_a:
        ta.observe(rec, now_s=ts)
    for ts, rec in recent_b:
        tb.observe(rec, now_s=ts)
    assert len(ta.events) == cap  # A's ring really wrapped
    merged = merge_slo_trackers([ta, tb])
    assert merged.events.maxlen == cap  # bound inherited, not defaulted
    # union-fed twin with the same bound, fed the events the rings
    # actually retained, in time order
    union = SLOTracker(dict(objectives), max_events=cap)
    for ts, rec in sorted(recent_a + recent_b)[-cap:]:
        union.observe(rec, now_s=ts)
    now = 1006.0
    mrep = merged.report(now_s=now)
    urep = union.report(now_s=now)
    obj = mrep["objectives"]["ttft_p90_ms"]
    # windowed burn: only the recent (good) tail is in the 60s window
    assert obj["burn_rate_60s"] == \
        urep["objectives"]["ttft_p90_ms"]["burn_rate_60s"] == 0.0
    # cumulative totals still count the wrapped-away burst
    assert obj["total"] == 14 and obj["bad"] == 4
    assert merged.requests == 14


def test_merge_slo_trackers_empty_pool():
    merged = merge_slo_trackers([None, None])
    assert merged.requests == 0
    assert merged.report(now_s=0.0)["objectives"] == {}


# ---------------------------------------------------------- admission brain
def _req(rid, prompt_len=4, max_new=4, arrival=0.0, priority=1,
         deadline=None):
    return Request(rid=rid, prompt=list(range(prompt_len)),
                   max_new_tokens=max_new, arrival_s=arrival,
                   priority=priority, deadline_s=deadline)


def test_admission_permanent_vs_transient():
    """Permanent sheds are decided by capacity, not occupancy: a prompt
    over the prefill window or over the two-tier page capacity can NEVER
    be served, while a merely-busy fleet queues."""
    adm = AdmissionControl(seq=8, max_context=16,
                           overhead_tokens=2,
                           pages_needed=lambda toks: -(-toks // 4),
                           capacity_pages=lambda: 4)
    assert adm.permanent_shed_reason(_req(0, prompt_len=9)) == \
        "prompt_too_long"
    assert adm.permanent_shed_reason(_req(1, prompt_len=8, max_new=9)) == \
        "over_max_context"
    # 8 prompt + 6 new + 2 overhead = 16 tokens -> 4 pages == capacity: ok
    assert adm.permanent_shed_reason(_req(2, prompt_len=8, max_new=6)) \
        is None
    # one token more blows the BOTH-tiers capacity -> permanent
    assert adm.permanent_shed_reason(_req(3, prompt_len=8, max_new=7)) == \
        "prompt_too_long"


def test_admission_queue_displacement():
    """Queue-cap shed-or-queue: a more urgent arrival displaces the
    lowest-priority waiter; a less urgent one is itself the victim; and
    with no cap everything queues."""
    adm = AdmissionControl(seq=8, queue_cap=2)
    waiting = []
    assert adm.queue_or_displace(_req(0, priority=1), waiting) is None
    assert adm.queue_or_displace(_req(1, priority=2), waiting) is None
    # full queue, urgent arrival: the priority-2 waiter is displaced
    victim = adm.queue_or_displace(_req(2, priority=0), waiting)
    assert victim is not None and victim.rid == 1
    assert [r.rid for r in waiting] == [0, 2]
    # full queue, batch arrival: the arrival itself is the victim
    late = _req(3, priority=3)
    assert adm.queue_or_displace(late, waiting) is late
    assert [r.rid for r in waiting] == [0, 2]
    uncapped = AdmissionControl(seq=8)
    w2 = []
    for i in range(5):
        assert uncapped.queue_or_displace(_req(i), w2) is None
    assert len(w2) == 5


def test_admission_stale_sweep():
    """The deadline/TTFT-budget sweep removes exactly the waiters that can
    no longer make it: elapsed wait + the EMA prefill estimate vs the
    budget, and hard per-request deadlines."""
    adm = AdmissionControl(seq=8, ttft_budget_ms=100.0)
    fresh = _req(0, arrival=0.95)
    doomed = _req(1, arrival=0.80)          # waited 200ms > 100ms budget
    dead = _req(2, arrival=0.0, deadline=0.5)
    waiting = [fresh, doomed, dead]
    out = adm.stale(waiting, now_s=1.0, ema_serve_ms=30.0)
    assert sorted((r.rid, why) for r, why in out) == \
        [(1, "ttft_budget"), (2, "deadline")]
    assert waiting == [fresh]


# ------------------------------------------------------------------ router
class _FakeSched:
    def __init__(self, queue_depth=0, ema_ms=50.0, done=0):
        self.queue_depth = queue_depth
        self._ema_serve_ms = ema_ms
        self.completed = [None] * done
        self.shed = []
        self.failed = []
        self.handoffs = 0


class _FakeSLO:
    def __init__(self, burn):
        self.objectives = {"ttft_p99_ms": {}}
        self._burn = burn

    def report(self):
        return {"worst_burn_rate": self._burn}


class _FakeEngine:
    def __init__(self, burn=None, watching=True, swap_ok=True, version=0):
        if burn is not None:
            self.slo = _FakeSLO(burn)
        self.watching = watching
        self._swap_ok = swap_ok
        self.active_version = version
        self.rolled_back = False

    def poll_swap(self, force=False):
        if self._swap_ok:
            self.active_version += 1
            return True
        return False

    def rollback(self):
        self.rolled_back = True
        self.active_version -= 1


def _handle(idx, assigned=0, done=0, depth=0, ema_ms=50.0, burn=None):
    h = ReplicaHandle(idx, _FakeEngine(burn=burn))
    h.sched = _FakeSched(queue_depth=depth, ema_ms=ema_ms, done=done)
    h.assigned = assigned
    return h


def test_router_least_loaded_picks_min_outstanding():
    # replica 0 has 3 outstanding, replica 1 has 1 -> pick 1
    a = _handle(0, assigned=5, done=2)
    b = _handle(1, assigned=3, done=2)
    assert FleetRouter().pick([a, b]) is b
    # tie on outstanding -> estimated TTFT (queue depth x EMA) breaks it
    c = _handle(2, assigned=3, done=2, depth=4, ema_ms=100.0)
    d = _handle(3, assigned=3, done=2, depth=1, ema_ms=100.0)
    assert FleetRouter().pick([c, d]) is d
    # and the estimator is the same quantity the TTFT-budget shed prices
    assert FleetRouter().estimated_ttft_s(d) == pytest.approx(0.2)


def test_router_burn_ceiling_steers_away():
    """A replica whose SLO worst burn crossed the ceiling only receives
    work when EVERY alternative crossed too (never starves the fleet)."""
    hot = _handle(0, assigned=0, burn=3.0)      # idle but burning
    busy = _handle(1, assigned=4, burn=0.1)
    r = FleetRouter(burn_max=1.0)
    assert r.pick([hot, busy]) is busy
    # without the ceiling the idle replica wins on load
    assert FleetRouter().pick([hot, busy]) is hot
    # everyone burning -> load order again (no starvation)
    both = [_handle(0, assigned=9, burn=3.0), _handle(1, assigned=1,
                                                      burn=2.0)]
    assert r.pick(both) is both[1]


def test_router_round_robin_and_validation():
    h = [_handle(i) for i in range(3)]
    r = FleetRouter("round_robin")
    assert [r.pick(h).index for _ in range(5)] == [0, 1, 2, 0, 1]
    with pytest.raises(ValueError):
        FleetRouter("random")
    with pytest.raises(ValueError):
        FleetRouter().pick([])


# ------------------------------------------------------------ rolling swap
def test_rolling_swap_cursor_gates_one_at_a_time():
    """Replica k may only take the new version after replicas 0..k-1 did
    — the rollout advances one replica per safe point, in order."""
    engines = [_FakeEngine() for _ in range(3)]
    ctl = RollingSwapController(engines)
    # replica 1 and 2 hit their safe points first: refused (cursor at 0)
    assert ctl.at_safe_point(1) is False
    assert ctl.at_safe_point(2) is False
    assert ctl.at_safe_point(0) is True
    # replica 0 took it; a SECOND snapshot must wait for the ring to close
    assert ctl.at_safe_point(0) is False
    # NOW replica 1 may advance; 2 still gated behind it
    assert ctl.at_safe_point(2) is False
    assert ctl.at_safe_point(1) is True
    assert ctl.at_safe_point(2) is True
    assert [r for r, _ in ctl.swaps] == [0, 1, 2]
    # ring closed: replica 0 is eligible again (the next rollout)
    assert ctl.at_safe_point(0) is True
    assert not ctl.halted and not ctl.rollbacks


def test_rolling_swap_skips_non_watching_and_empty_poll():
    engines = [_FakeEngine(watching=False), _FakeEngine(swap_ok=False)]
    ctl = RollingSwapController(engines)
    assert ctl.at_safe_point(0) is False      # not watching
    ctl2 = RollingSwapController([engines[1]])
    assert ctl2.at_safe_point(0) is False     # watching, nothing staged
    assert not ctl.swaps and not ctl2.swaps


def test_rolling_swap_rollback_on_burn_freezes_rollout():
    """A swapped replica that starts burning its SLO budget past the
    ceiling is rolled back to the pinned version and the rollout HALTS —
    a bad model stops at one replica instead of deploying fleet-wide."""
    engines = [_FakeEngine(burn=0.0), _FakeEngine(burn=0.0)]
    ctl = RollingSwapController(engines, burn_max=1.0)
    assert ctl.at_safe_point(0) is True
    assert engines[0].active_version == 1
    # bake period: replica 0's SLO goes bad before replica 1 advances
    engines[0].slo._burn = 5.0
    assert ctl.at_safe_point(0) is True       # params changed: rollback
    assert engines[0].rolled_back and engines[0].active_version == 0
    assert ctl.halted is True
    assert ctl.rollbacks == [(0, 0)]
    # frozen: replica 1 never takes the bad version
    assert ctl.at_safe_point(1) is False
    assert engines[1].active_version == 0
    # a rolled-back replica is not rolled back twice
    assert ctl.at_safe_point(0) is False


def test_rolling_swap_no_burn_objectives_never_rolls_back():
    engines = [_FakeEngine()]                 # no slo attribute at all
    ctl = RollingSwapController(engines, burn_max=1.0)
    assert ctl.at_safe_point(0) is True
    assert ctl.at_safe_point(0) is True       # keeps swapping, no rollback
    assert not ctl.rollbacks and not ctl.halted


# ------------------------------------------------- shared-runtime engine proxy
@pytest.mark.parametrize("call", ["prefill", "prefill_first_tokens",
                                  "decode_step"])
def test_shared_runtime_proxy_locks_and_floors_every_program(call):
    """Every compiled program a replica's scheduler dispatches, the
    first-token prefill it serves with included, runs under the fleet lock
    and reserves its simulated device-step floor."""
    import threading
    import time

    from flexflow_tpu.serving.fleet import _SharedRuntimeEngine

    assert call in _SharedRuntimeEngine._DEVICE_CALLS
    assert call in _SharedRuntimeEngine._FLOORED
    lock = threading.Lock()
    held = []

    class Eng:
        slots = 4

    setattr(Eng, call, lambda self, *a: held.append(lock.locked()) or a)
    proxy = _SharedRuntimeEngine(Eng(), lock, step_floor_s=0.02)
    assert proxy.slots == 4            # plain attributes pass through
    t0 = time.perf_counter()
    assert getattr(proxy, call)(1, 2) == (1, 2)
    assert held == [True] and not lock.locked()
    assert time.perf_counter() - t0 >= 0.02


# ------------------------------------------------------- two real engines
@pytest.fixture(scope="module")
def fleet_env(devices, tmp_path_factory):
    """Two replicas of one searched serving graph, each with its own KV
    pools and a host tier (the disaggregated handoff travels through it),
    and a training-side model of the same graph to drop snapshots."""
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.models import GPT2Config, build_gpt2
    from flexflow_tpu.serving import compile_serving

    gc = GPT2Config(vocab=256, seq=16, d_model=64, heads=2, layers=1,
                    dropout=0.0)
    engines = []
    for _ in range(2):
        m = FFModel(FFConfig(search_budget=16,
                             mesh_shape={"data": 2, "model": 4},
                             log_level="warning", max_batch_slots=4,
                             kv_page_size=4, kv_host_pages=16))
        build_gpt2(m, gc, batch=8)
        eng = compile_serving(m, max_decode_len=4)
        eng.init(seed=0)
        engines.append(eng)
    tm = FFModel(FFConfig(search_budget=0, only_data_parallel=True,
                          log_level="warning", max_batch_slots=4,
                          kv_page_size=4, async_checkpoint=False))
    build_gpt2(tm, gc, batch=8)
    cm = tm.compile(SGDOptimizer(lr=0.01),
                    loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=0)
    return engines, gc, cm, str(tmp_path_factory.mktemp("rollout"))


def _trace(gc, n, seed, max_new, priorities=(1,)):
    from flexflow_tpu.serving import tracefmt

    return tracefmt.records_to_requests(tracefmt.poisson_records(
        np.random.default_rng(seed), n, 500.0, gc.vocab, 4, max_new,
        priorities=priorities))


def _fleet(engines, **kw):
    from flexflow_tpu.serving import (ServingFleet, gpt2_prompt_inputs,
                                      gpt2_step_inputs)

    return ServingFleet(engines, gpt2_prompt_inputs, gpt2_step_inputs,
                        eos_id=None, dispatch_ahead=4, **kw)


def _tokens(done):
    return {r.rid: list(r.tokens) for r in done}


def test_single_replica_fleet_is_the_plain_scheduler(fleet_env):
    """One replica behind the router serves the scheduler's own token
    streams with the scheduler's own prefill and decode counts."""
    from flexflow_tpu.serving import (ContinuousBatchingScheduler,
                                      gpt2_prompt_inputs, gpt2_step_inputs)

    (eng, _), gc, _, _ = fleet_env
    sched = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                        gpt2_step_inputs, eos_id=None,
                                        dispatch_ahead=4)
    direct = sched.run(_trace(gc, 8, 1, eng.max_decode_len))
    fleet = _fleet([eng])
    assert _tokens(fleet.serve(_trace(gc, 8, 1, eng.max_decode_len))) \
        == _tokens(direct)
    fs = fleet.replicas[0].sched
    for c in ("prefills", "decode_steps"):
        assert getattr(fs, c) == getattr(sched, c), c
    # a replica under the fleet's feed and run lock empties its pipeline at
    # every window; the plain scheduler no more often than that
    assert fs.stats["drains"] == fs.materializations
    assert sched.stats["drains"] <= fs.stats["drains"]


def test_tiered_schedulers_prefetch_lead_is_the_flags_value(
        fleet_env, monkeypatch):
    """`--kv-prefetch-ahead` is the lead a tiered scheduler rotates by,
    when it is built and after it has timed decode steps: nothing
    re-derives it during a run."""
    from flexflow_tpu.serving import (ContinuousBatchingScheduler,
                                      gpt2_prompt_inputs, gpt2_step_inputs)

    (eng, _), gc, _, _ = fleet_env
    monkeypatch.setattr(eng.cfg, "kv_prefetch_ahead", 3)
    sched = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                        gpt2_step_inputs, eos_id=None,
                                        dispatch_ahead=4)
    assert sched.tiered and sched.prefetch_ahead == 3
    done = sched.run(_trace(gc, 8, 1, eng.max_decode_len))
    assert len(done) == 8 and sched.step_times
    assert sched.prefetch_ahead == 3


def test_disagg_hands_every_request_off_once(fleet_env):
    """The same mixed-priority trace through two mixed replicas and through
    one prefill + one decode replica: both serve every request in full,
    the split hands each request's committed pages over exactly once, and
    the greedy streams are bitwise the colocated ones."""
    engines, gc, _, _ = fleet_env
    n, max_new = 12, engines[0].max_decode_len
    colo = _fleet(engines, topology="colocated")
    dis = _fleet(engines, topology="disagg", prefill_replicas=1)
    toks = []
    for fleet in (colo, dis):
        done = fleet.serve(_trace(gc, n, 4, max_new, (0, 1, 1, 2)))
        assert len(done) == n and not fleet.shed and not fleet.failed
        assert all(len(r.tokens) == max_new for r in done)
        toks.append(_tokens(done))
    assert dis.stats["handoffs"] == n
    assert sum(h.engine.kv.tier_counters.get("kv_handoff_bytes", 0)
               for h in dis.replicas) > 0
    assert toks[1] == toks[0]


def test_rolling_swap_under_load_drops_nothing(fleet_env):
    """A snapshot in the watched root rolls across the fleet one replica
    at a time, each at its own drained window, while the fleet serves:
    every replica ends on the new version and no request is lost. The last
    third of the trace is routed only once the rollout is through, so it
    is served by the new weights however long the swaps take."""
    from flexflow_tpu.runtime.resilience import save_durable

    engines, gc, cm, root = fleet_env
    cm.init(seed=1)
    cm._iteration = 1
    save_durable(cm, root, block=True)
    fleet = _fleet(engines)
    pick, routed = fleet.router.pick, []

    def pick_after_rollout(pool):
        routed.append(1)
        deadline = time.monotonic() + 120.0
        while len(routed) > 8 and len(fleet.rolling.swaps) < len(engines) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        return pick(pool)
    fleet.router.pick = pick_after_rollout
    try:
        done = fleet.serve(_trace(gc, 12, 5, engines[0].max_decode_len),
                           watch_root=root, poll_interval_s=0.01)
    finally:
        for e in engines:
            e._watch_root = None
    assert len(done) == 12 and not fleet.shed and not fleet.failed
    assert sorted(i for i, _ in fleet.rolling.swaps) == [0, 1]
    assert [e.active_version for e in engines] == [1, 1]
