"""ISSUE 13 — speculative decoding + quantized KV cache.

Covers the acceptance gates: greedy speculative decode is BITWISE identical
to non-speculative decode (every committed token is the verify program's
argmax) at both acceptance extremes — a self-draft (draft == target, near-
total acceptance, exercising the full-accept bonus cap and the accepted-KV
reuse path) and an adversarial random draft (near-zero acceptance,
exercising per-round rollback) — on gpt2 AND a generic token transformer
under the {data:2, model:4} mesh; int8 KV quantization round-trips within
the per-(entry, head) scale bound and holds decode-vs-full-forward parity
to a pinned tolerance; the speculative engines warm-restore draft AND
target strategies from the cache with zero DP expansions; admission grows
its page `need` by the K-token lookahead and every page returns to the
free list in both caches; and the spec/kv telemetry feeds the monitor.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.dtype import DataType
from flexflow_tpu.models import GPT2Config, build_gpt2
from flexflow_tpu.models.transformer import transformer_block
from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,
                                  compile_serving, gpt2_prompt_inputs,
                                  gpt2_step_inputs)
from flexflow_tpu.serving.kv_cache import kv_dequantize, kv_quantize

MESH = {"data": 2, "model": 4}


def _serve_cfg(**kw):
    kw.setdefault("search_budget", 16)
    kw.setdefault("mesh_shape", dict(MESH))
    kw.setdefault("max_batch_slots", 4)
    kw.setdefault("kv_page_size", 4)
    kw.setdefault("max_decode_len", 6)
    kw.setdefault("log_level", "warning")
    return FFConfig(**kw)


def _gpt2_cfg():
    # small on purpose: jit-compile time, not math, dominates these tests
    return GPT2Config(vocab=256, seq=16, d_model=32, heads=4, layers=1,
                      dropout=0.0)


def _draft_cfg():
    return GPT2Config(vocab=256, seq=16, d_model=16, heads=4, layers=1,
                      dropout=0.0)


def _build(gc, cfg):
    m = FFModel(cfg)
    build_gpt2(m, gc, batch=8)
    return m


def _reqs(rng, gc, n, max_new=6):
    return [Request(rid=i, prompt=list(rng.integers(1, gc.vocab, size=3)),
                    max_new_tokens=max_new, arrival_s=0.0) for i in range(n)]


def _streams(eng, reqs):
    sched = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                        gpt2_step_inputs, eos_id=None)
    done = sched.run(reqs)
    return {r.rid: list(r.tokens) for r in done}, sched


@pytest.fixture(scope="module")
def spec_serve(devices):
    """Baseline + two speculative engines sharing target params: the
    self-draft (draft graph == target graph, same params -> acceptance ~1)
    and the adversarial draft (small random-init model -> acceptance ~0).
    Compiled once per module; the searches warm-hit after the first."""
    cfg = _serve_cfg()
    gc = _gpt2_cfg()
    base = compile_serving(_build(gc, cfg))
    base.init(seed=0)
    hi = compile_serving(_build(gc, cfg), draft=_build(gc, cfg),
                         spec_tokens=2)
    hi.load_params(base.params)
    hi.draft.load_params(base.params)
    lo = compile_serving(_build(gc, cfg), draft=_build(_draft_cfg(), cfg),
                         spec_tokens=2)
    lo.load_params(base.params)
    lo.draft.init(seed=7)
    return base, hi, lo, gc


# ------------------------------------------------------- bitwise parity
def test_spec_bitwise_parity_gpt2(spec_serve, rng):
    """The tentpole invariant, at both acceptance extremes: speculative
    greedy streams are byte-for-byte the baseline streams."""
    base, hi, lo, gc = spec_serve
    reqs = lambda: _reqs(rng, gc, 4)  # noqa: E731 — same trace thrice
    rng = np.random.default_rng(3)
    want, _ = _streams(base, reqs())
    rng = np.random.default_rng(3)
    got_hi, s_hi = _streams(hi, reqs())
    rng = np.random.default_rng(3)
    got_lo, s_lo = _streams(lo, reqs())
    assert got_hi == want
    assert got_lo == want
    # the two engines really sit at opposite acceptance regimes
    r_hi = s_hi.stats["spec_accepted_tokens"] / s_hi.stats[
        "spec_drafted_tokens"]
    r_lo = s_lo.stats["spec_accepted_tokens"] / s_lo.stats[
        "spec_drafted_tokens"]
    assert r_hi > 0.5, (r_hi, s_hi.stats)
    assert r_lo < 0.5, (r_lo, s_lo.stats)
    assert s_hi.stats["spec_rounds"] < s_lo.stats["spec_rounds"]


def _build_token_transformer(cfg, vocab, seq, d_model, heads, layers):
    """Generic causal stack fed by token ids: embedding -> transformer
    blocks -> LM head. No position table — the causal mask carries order —
    so it exercises the serving clones on a non-gpt2 graph shape."""
    m = FFModel(cfg)
    ids = m.create_tensor([8, seq], DataType.INT32, name="ids")
    t = m.embedding(ids, vocab, d_model, name="tok_emb")
    for i in range(layers):
        t = transformer_block(m, t, d_model, heads, 4 * d_model, f"blk{i}",
                              dropout=0.0, causal=True)
    m.dense(t, vocab, use_bias=False, name="lm_head")
    return m


def test_spec_bitwise_parity_transformer(devices, rng):
    """Same parity bar for a generic token transformer under the searched
    {data:2, model:4} mesh, driven through the scheduler with custom
    (traceable) input adapters — the fused spec round is model-agnostic."""
    vocab, seq = 128, 16
    cfg = _serve_cfg(max_batch_slots=2)
    prompt_fn = lambda ids, lengths: [ids.astype(np.int32)]  # noqa: E731
    step_fn = lambda toks, state: [toks]                     # noqa: E731

    base = compile_serving(_build_token_transformer(cfg, vocab, seq, 32, 4, 1))
    base.init(seed=0)
    spec = compile_serving(
        _build_token_transformer(cfg, vocab, seq, 32, 4, 1),
        draft=_build_token_transformer(cfg, vocab, seq, 16, 2, 1),
        spec_tokens=2)
    spec.load_params(base.params)
    spec.draft.init(seed=5)

    def run(eng):
        sched = ContinuousBatchingScheduler(eng, eng.params, prompt_fn,
                                            step_fn, eos_id=None)
        rr = np.random.default_rng(11)
        done = sched.run([Request(rid=i,
                                  prompt=list(rr.integers(1, vocab, size=3)),
                                  max_new_tokens=5, arrival_s=0.0)
                          for i in range(4)])
        return {r.rid: list(r.tokens) for r in done}, sched

    want, _ = run(base)
    got, sched = run(spec)
    assert got == want
    assert sched.stats["spec_rounds"] > 0
    assert sched._spec_fused is not None  # fused single-dispatch rounds


# ------------------------------------------------------ int8 quantization
def test_kv_int8_roundtrip_error_bound(rng):
    """Symmetric per-(entry, head) quantization: the reconstruction error
    is bounded by half a quantization step of THAT row's scale."""
    x = jnp.asarray(rng.normal(size=(3, 5, 4, 8)).astype(np.float32) * 3.0)
    q, s = kv_quantize(x)
    assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
    err = np.abs(np.asarray(kv_dequantize(q, s)) - np.asarray(x))
    bound = np.asarray(s)[..., None] * 0.5 + 1e-6
    assert (err <= bound).all(), float((err - bound).max())
    # scales really are per-row: amax/127
    amax = np.abs(np.asarray(x)).max(axis=-1)
    assert np.allclose(np.asarray(s), np.maximum(amax, 1e-8) / 127.0)
    # all-zero rows (fresh pages) stay exactly zero through the round-trip
    z, zs = kv_quantize(jnp.zeros((2, 3, 4)))
    assert (np.asarray(kv_dequantize(z, zs)) == 0.0).all()


def test_decode_parity_int8_quantized(devices, rng):
    """Incremental decode through the int8 paged cache tracks the full f32
    forward within a pinned tolerance — wrong-scale or wrong-page bugs blow
    far past it, while honest per-row quantization noise sits well under."""
    cfg = _serve_cfg(kv_cache_dtype="int8")
    gc = _gpt2_cfg()
    eng = compile_serving(_build(gc, cfg))
    eng.init(seed=0)
    assert eng.kv_quantized and str(eng.kv_dtype) == "int8"
    toks = rng.integers(1, gc.vocab, size=12).astype(np.int32)

    slots, seq = eng.slots, 16
    L, P = len(toks), 4
    ids_full = np.zeros((slots, seq), np.int32)
    ids_full[0, :L] = toks
    full, _ = eng.prefill(eng.params, gpt2_prompt_inputs(
        ids_full, np.full((slots,), L, np.int32)))
    full = np.asarray(full)

    ids = np.zeros((slots, seq), np.int32)
    ids[0, :P] = toks[:P]
    lengths = np.zeros((slots,), np.int32)
    lengths[0] = P
    assert eng.kv.admit(0, P, L + 2)
    eng.kv.push()
    pre, kv_state = eng.prefill(eng.params, gpt2_prompt_inputs(ids, lengths))
    eng.kv.commit_prefill(kv_state, np.arange(slots, dtype=np.int32), lengths)
    errs = []
    state = eng.kv.state
    for t in range(P, L):
        step = np.zeros((slots, 1), np.int32)
        step[0, 0] = toks[t]
        logits, state = eng.decode_step(
            eng.params, state, gpt2_step_inputs(jnp.asarray(step), state))
        errs.append(float(np.abs(np.asarray(logits)[0, 0] - full[0, t]).max()))
    eng.kv.adopt(state)
    eng.kv.evict(0)
    eng.kv.push()
    assert max(errs) <= 0.05, errs         # quantization noise, pinned
    assert max(errs) > 1e-7, errs          # and the int8 path really ran


# ------------------------------------------------------------ engine guards
def test_verify_without_draft_raises(spec_serve):
    base, _, _, _ = spec_serve
    with pytest.raises(RuntimeError, match="draft"):
        base.verify_step(base.params, base.kv.state, [])
    with pytest.raises(RuntimeError, match="draft"):
        base.build_spec_program(gpt2_step_inputs)


def test_draft_seq_mismatch_raises(devices):
    cfg = _serve_cfg()
    bad = GPT2Config(vocab=256, seq=8, d_model=32, heads=4, layers=1,
                     dropout=0.0)
    with pytest.raises(ValueError, match="seq"):
        compile_serving(_build(_gpt2_cfg(), cfg),
                        draft=_build(bad, cfg), spec_tokens=2)


def test_unknown_kv_dtype_raises(devices):
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        compile_serving(_build(_gpt2_cfg(), _serve_cfg(kv_cache_dtype="fp4")))


# --------------------------------------------------------- strategy cache
def test_spec_warm_cache_restore_draft_and_target(spec_serve):
    """Recompiling the speculative pair is search-free: target prefill +
    decode AND draft prefill + decode all warm-hit the strategy cache (the
    verify program overlays the searched decode strategy — no extra key)."""
    from flexflow_tpu.search.dp import SEARCH_STATS

    _, _, _, gc = spec_serve
    cfg = _serve_cfg()
    SEARCH_STATS["expansions"] = 0
    eng = compile_serving(_build(gc, cfg), draft=_build(_draft_cfg(), cfg),
                          spec_tokens=2)
    assert SEARCH_STATS["expansions"] == 0
    for e in (eng, eng.draft):
        for st in (e.prefill_strategy, e.decode_strategy):
            info = getattr(st, "_cache_info", None)
            assert info and info["event"] == "hit"
    assert eng.verify_model is not None
    assert eng.spec_tokens == 2


# ------------------------------------------------- admission + conservation
def test_spec_admission_need_includes_lookahead(spec_serve, rng):
    """Admission must reserve K extra positions: the verify pass writes up
    to pos+K before acceptance rolls back, so a slot sized without the
    lookahead would scatter into another slot's pages."""
    _, hi, _, gc = spec_serve
    seen = []
    orig = hi.kv.admit

    def spy(slot, prompt_len, need):
        seen.append((prompt_len, need))
        return orig(slot, prompt_len, need)

    hi.kv.admit = spy
    try:
        _streams(hi, _reqs(rng, gc, 2, max_new=4))
    finally:
        hi.kv.admit = orig
    assert seen
    for prompt_len, need in seen:
        # prompt + max_new + dispatch_ahead + spec_tokens
        assert need == prompt_len + 4 + 4 + hi.spec_tokens


def test_spec_page_conservation_both_caches(spec_serve, rng):
    """After a full speculative serve (rollback + acceptance + eviction
    traffic on every request) BOTH paged caches return every page to the
    free list — only the reserved scratch page stays out."""
    _, hi, lo, gc = spec_serve
    for eng in (hi, lo):
        _streams(eng, _reqs(rng, gc, 6))
        for kv in (eng.kv, eng.draft.kv):
            assert len(kv.free_slots()) == eng.slots
            assert len(kv.free_pages) == kv.spec.pool_pages - 1


# ----------------------------------------------------- telemetry + monitor
def test_spec_telemetry_monitor_roundtrip(devices, rng, tmp_path):
    """serve/spec_* counters and the engine's kv-dtype event flow through
    the telemetry sink into the monitor's serving panel and the Prometheus
    export."""
    import monitor

    from flexflow_tpu import telemetry as tel

    tdir = str(tmp_path / "tel")
    tel.configure(tdir)
    try:
        # only_data_parallel: the events under test (engine kv-dtype info,
        # per-round spec counters) are strategy-agnostic — skip the search
        cfg = _serve_cfg(kv_cache_dtype="int8", only_data_parallel=True)
        gc = _gpt2_cfg()
        eng = compile_serving(_build(gc, cfg), draft=_build(_draft_cfg(), cfg),
                              spec_tokens=2)
        eng.init(seed=0)
        eng.draft.init(seed=7)
        _streams(eng, _reqs(rng, gc, 2, max_new=4))
    finally:
        tel.shutdown()
    evs = tel.read_events(tdir)
    names = {e.get("name") for e in evs}
    for want in ("serve/engine", "serve/spec_drafted_tokens",
                 "serve/spec_accepted_tokens", "serve/spec_accept_rate"):
        assert want in names, (want, sorted(names))
    state = monitor.gather(evs)
    sv = monitor._serve_stats(state["serve"])
    assert sv["spec_tokens"] == 2
    assert sv["kv_dtype"] == "int8"
    assert sv["spec_drafted"] > 0
    assert sv["spec_accept_rate"] is not None
    assert any("kv_dtype=int8" in ln for ln in monitor.render(state))
    prom = str(tmp_path / "node.prom")
    monitor.prom_export(state, prom)
    with open(prom) as f:
        txt = f.read()
    assert "flexflow_serve_spec_drafted_tokens_total" in txt
    assert "flexflow_serve_spec_accept_rate" in txt
    assert 'flexflow_serve_kv_cache_dtype_info{dtype="int8"} 1' in txt


# ---------------------------------------------------- strategy divergence
def test_int8_searched_strategy_diverges(devices):
    """The acceptance pin, tier-1 cheap: same model, same mesh, only the
    KV itemsize changes — and the searched decode sharding flips (bf16
    head-shards the pool at degree 4, int8's halved page traffic keeps it
    resident at degree 1), with predicted KV bytes exact against the live
    pools for both."""
    # the pinned divergence window: d_model=64 heads=4 at 12 slots is where
    # bf16's page traffic beats the tp all-reduce but int8's halved pages
    # don't
    gc = GPT2Config(vocab=256, seq=16, d_model=64, heads=4, layers=1,
                    dropout=0.0)
    degs = {}
    for dt in ("bf16", "int8"):
        cfg = _serve_cfg(max_batch_slots=12, max_decode_len=8,
                         kv_cache_dtype=dt)
        eng = compile_serving(_build(gc, cfg))
        eng.init(seed=0)
        ms = eng.memory_stats()
        assert ms["predicted_kv_cache_bytes"] == \
            ms["actual_kv_cache_bytes_per_device"], (dt, ms)
        degs[dt] = ms["kv_shard_degree"]
    assert degs["bf16"] == 4, degs
    assert degs["int8"] == 1, degs
