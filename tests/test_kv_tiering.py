"""ISSUE 16 — tiered KV cache (HBM hot tier + host cold tier).

Covers the acceptance pins: greedy decode streams through the spill/
prefetch/join path are BITWISE identical to the HBM-only engine on the
same request trace (the tier moves committed pages, it never touches the
numerics); page accounting conserves across admit/spill/prefetch/join/
evict churn and spans BOTH tiers; admission distinguishes the permanent
sheds (over the operator's --serve-max-context ceiling, or over total
two-tier capacity) from transient pool pressure, which queues; the three
new flags ride FFConfig.build_parser; and the host tier is accounted in
memory_stats/health_report separately from the HBM watermark figures.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu import telemetry as tel
from flexflow_tpu.health import format_kv_tier
from flexflow_tpu.models import GPT2Config, build_gpt2
from flexflow_tpu.search.cost_model import KVCacheSpec
from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,
                                  compile_serving, gpt2_prompt_inputs,
                                  gpt2_step_inputs)
from flexflow_tpu.serving.kv_cache import PagedKVCache

MESH = {"data": 2, "model": 4}


def _serve_cfg(**kw):
    kw.setdefault("search_budget", 16)
    kw.setdefault("mesh_shape", dict(MESH))
    kw.setdefault("max_batch_slots", 4)
    kw.setdefault("kv_page_size", 4)
    kw.setdefault("max_decode_len", 6)
    kw.setdefault("log_level", "warning")
    kw.setdefault("strategy_cache", False)
    return FFConfig(**kw)


def _build_engine(host_pages):
    model = FFModel(_serve_cfg(kv_host_pages=host_pages,
                               kv_prefetch_ahead=2))
    gc = GPT2Config(vocab=256, seq=16, d_model=64, heads=4, layers=1,
                    dropout=0.1)
    build_gpt2(model, gc, batch=8)
    eng = compile_serving(model)
    eng.init(seed=0)
    return eng


def _serve(eng, n=6):
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=list(rng.integers(1, 255, size=8)),
                    max_new_tokens=6, arrival_s=0.0) for i in range(n)]
    sched = ContinuousBatchingScheduler(
        eng, eng.params, gpt2_prompt_inputs, gpt2_step_inputs, eos_id=None,
        dispatch_ahead=2)
    done = sched.run(reqs)
    return {r.rid: list(r.tokens) for r in done}, sched


@pytest.fixture(scope="module")
def tier_parity(devices, tmp_path_factory):
    """Serve the SAME trace through an HBM-only engine and a tiered one
    whose device pool is half the slots' footprint (4 slots x 6 pages,
    12 of the 24 data pages moved to host) — every rotation exercises a
    real spill + prefetch. The tiered serve runs under a telemetry sink
    so the observability tests read REAL events. One module-scoped
    pair: the two searches / compiles / serves are the expensive bit."""
    base_streams, base_sched = _serve(_build_engine(0))
    tier_eng = _build_engine(12)
    tdir = str(tmp_path_factory.mktemp("tier_tel"))
    tel.configure(tdir)
    try:
        tier_streams, tier_sched = _serve(tier_eng)
    finally:
        tel.shutdown()
    events = tel.read_events(tdir)
    return base_streams, tier_streams, tier_eng, tier_sched, events


# ------------------------------------------------------------ decode parity
def test_spill_path_greedy_streams_bitwise(tier_parity):
    """The acceptance headline: 6 requests through 4 slots with only 12
    device data pages produce byte-for-byte the streams of the untiered
    engine — and the run REALLY spilled (tier counters nonzero), so the
    parity is over the spill/prefetch path, not a degenerate all-resident
    schedule."""
    base, tier, _eng, sched, _evs = tier_parity
    assert base == tier
    ts = sched.kv.tier_stats()
    assert ts["kv_spills"] > 0 and ts["kv_refills"] > 0
    assert ts["kv_spilled_bytes"] > 0
    # every spill eventually refilled: nothing stranded in the cold tier
    assert ts["kv_refills"] == ts["kv_spills"]
    assert ts["kv_parked_slots"] == 0 and ts["kv_cold_pages"] == 0


def test_stalls_and_hits_are_counted(tier_parity):
    """Every rejoin lands in exactly one ledger bucket — a prefetch that
    had < prefetch_ahead decode steps to hide is a counted stall, never a
    silent block."""
    _b, _t, _eng, sched, _evs = tier_parity
    ts = sched.kv.tier_stats()
    joins = ts["kv_prefetch_hits"] + ts["kv_prefetch_stalls"]
    assert joins == ts["kv_refills"]
    # the scheduler publishes the final ledger into run stats (the bench
    # and ops dashboards read it from there)
    assert sched.stats["kv_spills"] == ts["kv_spills"]
    assert sched.stats["kv_prefetch_stalls"] == ts["kv_prefetch_stalls"]


def test_tiered_geometry_shrinks_device_pool(tier_parity):
    """--kv-host-pages substitutes host pages for device pages at fixed
    slot count: the device pool drops by the host allotment while total
    two-tier capacity stays the full slots' footprint."""
    _b, _t, eng, _s, _evs = tier_parity
    spec = eng.kv_spec
    assert spec.host_pages == 12
    assert spec.pool_pages == 12 + 1           # 24 - 12 data pages + scratch
    assert eng.kv.capacity_pages() == spec.slots * spec.pages_per_slot


# ------------------------------------------------------- page conservation
def _small_cache(host_pages=4, slots=3, pps=2):
    spec = KVCacheSpec(layers=2, heads=2, head_dim=4, slots=slots,
                       pages_per_slot=pps, page_size=4,
                       host_pages=host_pages,
                       device_pages=max(pps, slots * pps - host_pages)
                       if host_pages else 0)
    return PagedKVCache(spec, ["attn0", "attn1"])


def test_page_conservation_across_tier_churn():
    """No page is ever leaked or double-owned: after any interleaving of
    admit/spill/prefetch/join/evict, free + owned equals each tier's
    total, and evicting a PARKED slot returns its pages to the HOST free
    list (where they live), not the device one."""
    kv = _small_cache(host_pages=2)            # device pool: 4 data pages
    dev_total = kv.spec.pool_pages - 1
    host_total = kv.host_pages

    def check():
        owned_dev = sum(len(p) for p in kv._slot_pages.values())
        owned_host = sum(len(p) for p in kv._cold.values())
        assert len(kv.free_pages) + owned_dev == dev_total
        assert len(kv.free_host_pages) + owned_host == host_total
        # a slot owns pages in BOTH tiers only while a prefetch is in
        # flight (join releases the host copies)
        assert not (set(kv._slot_pages) & set(kv._cold)
                    - set(kv._inflight))

    kv.admit(0, 4, 8)
    kv.admit(1, 4, 8)
    check()
    assert kv.can_spill(0)
    kv.spill(0, decode_step=10)
    check()
    assert 0 not in kv.free_slots()            # parked slots stay occupied
    with pytest.raises(ValueError):
        kv.admit(0, 4, 8)                      # and can't be re-admitted
    assert kv.prefetch(0, decode_step=12)
    check()
    stalled = kv.join(0, decode_step=13, prefetch_ahead=2)
    assert stalled                             # 1 step of lead < 2
    check()
    kv.spill(1, decode_step=14)
    kv.evict(1)                                # evict while PARKED
    check()
    assert len(kv.free_host_pages) == host_total
    kv.evict(0)
    check()
    assert len(kv.free_pages) == dev_total


def test_spill_parity_roundtrip_values():
    """What goes to the host comes back bitwise: fill a slot's pages via
    commit-style writes, spill, prefetch, and compare the pool rows."""
    kv = _small_cache()
    kv.admit(0, 4, 8)
    pages = list(kv._slot_pages[0])
    rng = np.random.default_rng(0)
    vals = {}
    for n in kv.attn_layers:
        st = dict(kv.state[n])
        for key in ("k", "v"):
            rows = rng.normal(size=(len(pages),) + tuple(
                st[key].shape[1:])).astype(np.float32)
            st[key] = st[key].at[np.asarray(pages)].set(rows)
            vals[(n, key)] = rows
        kv.state[n] = st
    kv.spill(0, decode_step=0)
    assert kv.prefetch(0, decode_step=4)
    kv.join(0, decode_step=8, prefetch_ahead=2)
    new_pages = kv._slot_pages[0]
    for n in kv.attn_layers:
        for key in ("k", "v"):
            got = np.asarray(kv.state[n][key][np.asarray(new_pages)])
            np.testing.assert_array_equal(got, vals[(n, key)])


def test_prefetch_backpressure_and_join_ledger():
    """prefetch returns False (no-op, retry later) when the device free
    list can't cover the parked slot; a join with >= prefetch_ahead steps
    of lead is a HIT."""
    kv = _small_cache(host_pages=4, slots=3, pps=2)   # device pool: 2 pages
    kv.admit(0, 4, 8)
    kv.spill(0, decode_step=0)
    kv.admit(1, 4, 8)                          # takes the freed pages
    assert not kv.prefetch(0, decode_step=1)   # device full: no-op
    assert 0 in kv.parked_slots()              # still rotation-eligible
    kv.evict(1)
    assert kv.prefetch(0, decode_step=2)
    assert not kv.join(0, decode_step=10, prefetch_ahead=2)  # hit
    assert kv.tier_counters["kv_prefetch_hits"] == 1


# ------------------------------------------------------- admission shedding
class _AdmitProbe(ContinuousBatchingScheduler):
    """The _enqueue policy under test, detached from a live engine."""

    def __init__(self, kv, seq=16, max_context=0):
        self.tracer = None
        self.slo = None
        self.kv = kv
        self.seq = seq
        self.max_context = max_context
        self.dispatch_ahead = 0
        self.spec_tokens = 0
        self.queue_cap = 0
        self.shed = []
        self.stats = {"shed_prompt_too_long": 0, "shed_over_max_context": 0,
                      "shed_queue_full": 0}
        # the decisions live in the policy class a fleet shares
        from flexflow_tpu.serving.admission import AdmissionControl
        self.admission = AdmissionControl(
            seq=seq, max_context=max_context, queue_cap=self.queue_cap,
            overhead_tokens=self.dispatch_ahead + self.spec_tokens,
            pages_needed=kv.pages_needed, capacity_pages=kv.capacity_pages)


def test_admission_sheds_permanent_keeps_transient():
    """over_max_context and over-capacity sheds are PERMANENT (no
    eviction sequence can ever serve them); a merely-occupied pool
    queues the request instead."""
    kv = _small_cache(host_pages=0, slots=2, pps=2)
    sched = _AdmitProbe(kv, seq=16, max_context=10)
    waiting = []
    # over the operator ceiling: its own reason, distinct from too-long
    sched._enqueue(Request(rid=0, prompt=[1] * 8, max_new_tokens=8),
                   waiting, 0.0)
    assert sched.stats["shed_over_max_context"] == 1
    assert sched.shed[-1].shed_reason == "over_max_context"
    # within ceiling and capacity: queues
    sched._enqueue(Request(rid=1, prompt=[1] * 4, max_new_tokens=4),
                   waiting, 0.0)
    assert [r.rid for r in waiting] == [1]
    # transient: pool fully occupied but capacity would fit it -> queues
    kv.admit(0, 4, 8)
    kv.admit(1, 4, 8)
    assert not kv.can_admit(8)
    sched._enqueue(Request(rid=2, prompt=[1] * 4, max_new_tokens=4),
                   waiting, 0.0)
    assert [r.rid for r in waiting] == [1, 2]
    assert sched.stats["shed_prompt_too_long"] == 0


def test_admission_capacity_spans_both_tiers():
    """The capacity shed compares against HBM + host pages: a request a
    shrunken device pool alone could never hold is admissible once the
    host tier's pages are counted in (and permanent-shed without them)."""

    def _cache(dev, host):
        spec = KVCacheSpec(layers=1, heads=2, head_dim=4, slots=2,
                           pages_per_slot=4, page_size=4,
                           host_pages=host, device_pages=dev)
        return PagedKVCache(spec, ["attn0"])

    # 14 tokens -> 4 pages. device 2 + host 2 = 4: fits across the tiers
    tiered = _cache(2, 2)
    assert tiered.capacity_pages() == 4
    sched = _AdmitProbe(tiered, seq=128)
    waiting = []
    sched._enqueue(Request(rid=0, prompt=[1] * 10, max_new_tokens=4),
                   waiting, 0.0)
    assert [r.rid for r in waiting] == [0]
    # the same 2-page device pool WITHOUT the host tier: permanent shed
    hbm_only = _cache(2, 0)
    assert hbm_only.capacity_pages() == 2
    sched0 = _AdmitProbe(hbm_only, seq=128)
    sched0._enqueue(Request(rid=1, prompt=[1] * 10, max_new_tokens=4),
                    waiting, 0.0)
    assert sched0.stats["shed_prompt_too_long"] == 1
    assert sched0.shed[-1].shed_reason == "prompt_too_long"


# ---------------------------------------------------------- config wiring
def test_tier_flags_ride_build_parser():
    cfg = FFConfig.parse_args(["--kv-host-pages", "24",
                               "--kv-prefetch-ahead", "3",
                               "--serve-max-context", "4096"])
    assert cfg.kv_host_pages == 24
    assert cfg.kv_prefetch_ahead == 3
    assert cfg.serve_max_context == 4096
    dflt = FFConfig.parse_args([])
    assert dflt.kv_host_pages == 0             # untiered by default
    assert dflt.kv_prefetch_ahead == 2
    assert dflt.serve_max_context == 0
    # added via build_parser only -> the launcher's derived value-flag
    # set covers them automatically
    vf = FFConfig.launcher_value_flags()
    for flag in ("--kv-host-pages", "--kv-prefetch-ahead",
                 "--serve-max-context"):
        assert flag in vf, flag


def test_tier_fingerprints_fork_strategy_cache_keys():
    """A tiered spec must MISS the untiered spec's strategy-cache entry:
    the fingerprint carries the tier geometry."""
    a = KVCacheSpec(layers=1, heads=2, head_dim=4, slots=2,
                    pages_per_slot=2, page_size=4)
    b = KVCacheSpec(layers=1, heads=2, head_dim=4, slots=2,
                    pages_per_slot=2, page_size=4,
                    host_pages=2, device_pages=2)
    assert a.fingerprint() != b.fingerprint()


# ------------------------------------------------------- accounting surface
def test_host_tier_accounted_separately(tier_parity):
    """Host bytes are reported as their OWN memory_stats fields — they
    never inflate predicted_total_bytes (the HBM watermark pin) — and
    predicted equals actual on the host side too."""
    _b, _t, eng, _s, _evs = tier_parity
    ms = eng.memory_stats()
    assert ms["predicted_kv_host_bytes"] == ms["actual_kv_host_bytes"] > 0
    assert ms["predicted_kv_host_bytes"] == \
        eng.kv_spec.layers * 12 * eng.kv_spec.page_bytes()
    # the HBM prediction prices the SHRUNKEN device pool, host excluded
    assert ms["predicted_kv_cache_bytes"] == \
        eng.kv_spec.per_device_bytes(eng.kv_shard_degree)
    assert ms["predicted_total_bytes"] == \
        ms["predicted_kv_cache_bytes"] + ms["predicted_param_bytes"]


def test_health_report_carries_tier_panel(tier_parity):
    _b, _t, eng, _s, _evs = tier_parity
    panel = eng.health_report()["serving"]["kv_tier"]
    assert panel["spills"] > 0
    assert 0.0 <= panel["prefetch_hit_rate"] <= 1.0
    assert panel["host_pages_total"] == 12


def test_tier_observability_end_to_end(tier_parity, tmp_path):
    """The tiered serve's REAL telemetry stream carries the whole ISSUE
    16 surface: spill/prefetch spans, tier counters, the request-trace
    kv_prefetch stage, and the monitor panel + prom gauges built from
    them."""
    import monitor

    _b, _t, _eng, sched, evs = tier_parity
    names = {e.get("name") for e in evs}
    for want in ("serve/kv_spill", "serve/kv_prefetch",
                 "serve/kv_tier_hot_pages", "serve/kv_tier_cold_pages",
                 "serve/kv_prefetch_stalls", "serve/kv_spills",
                 "serve/slot_parked", "serve/slot_rejoined"):
        assert want in names, (want, sorted(names))
    # the parked interval tiles into the request timeline as its own stage
    assert any(e.get("name") == "serve/req/kv_prefetch" for e in evs)
    # monitor panel + prom gauges
    state = monitor.gather(evs)
    sv = monitor._serve_stats(state["serve"])
    assert sv["kv_spills"] == sched.kv.tier_stats()["kv_spills"]
    assert sv["kv_hot_pages"] is not None
    assert sv["kv_prefetch_hit_rate"] is not None
    prom = str(tmp_path / "node.prom")
    monitor.prom_export(state, prom)
    with open(prom) as f:
        txt = f.read()
    for g in ("flexflow_serve_kv_tier_hot_pages",
              "flexflow_serve_kv_tier_spills_total",
              "flexflow_serve_kv_prefetch_stalls_total",
              "flexflow_serve_kv_prefetch_hit_rate"):
        assert g in txt, g


def test_format_kv_tier_hit_rate():
    got = format_kv_tier({"kv_prefetch_hits": 3, "kv_prefetch_stalls": 1,
                          "kv_spills": 4, "kv_refills": 4,
                          "kv_hot_pages": 5, "kv_cold_pages": 2,
                          "kv_parked_slots": 1, "kv_host_pages_total": 8,
                          "kv_spilled_bytes": 10, "kv_refilled_bytes": 10})
    assert got["prefetch_hit_rate"] == pytest.approx(0.75)
    assert got["hot_pages"] == 5 and got["cold_pages"] == 2
    # an idle tier has missed nothing
    assert format_kv_tier({})["prefetch_hit_rate"] == 1.0
