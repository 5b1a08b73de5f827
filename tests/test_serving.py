"""ISSUE 10 — auto-parallel inference serving.

Covers the acceptance gates: `compile_serving` produces DIFFERENT searched
strategies for the prefill and decode programs on the 8-device gpt2 CPU
twin; incremental decode through the paged, model-axis-sharded KV cache is
numerically bit-close (<= 1e-5) to the full-sequence forward at every
position (gpt2 AND the generic transformer); serving is deterministic by
construction (dropout hard-zeroed in the clones, fixed rng); both serving
programs warm-hit the strategy cache under independent keys; KV-cache
residency is accounted in memory_stats within the watermark envelope; and
the continuous-batching scheduler admits/evicts correctly under EOS,
max-len, and page backpressure.
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models import GPT2Config, build_gpt2
from flexflow_tpu.models.transformer import build_transformer
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,
                                  compile_serving, gpt2_prompt_inputs,
                                  gpt2_step_inputs)

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
    # dropout INTENTIONALLY nonzero: the serving clones must hard-zero it
    return GPT2Config(vocab=256, seq=16, d_model=64, heads=4, layers=1,
                      dropout=0.1)


@pytest.fixture(scope="module")
def gpt2_serve(devices):
    """One searched serving engine per module — the expensive bit (two
    DP searches + two jit compiles + sharded init) paid once."""
    cfg = _serve_cfg()
    model = FFModel(cfg)
    gc = _gpt2_cfg()
    build_gpt2(model, gc, batch=8)
    eng = compile_serving(model)
    eng.init(seed=0)
    return eng, gc


# --------------------------------------------------------- searched programs
def test_prefill_decode_strategies_differ(gpt2_serve):
    """The acceptance headline: the two programs SEARCHED to different
    strategies on the 8-device twin. The divergence is physical: decode's
    [slots, 1, e] activations make vocab-/row-sharded embeddings nearly
    free to all-reduce, while prefill's [slots, S, e] activations push the
    embedding tables to feature sharding."""
    eng, _ = gpt2_serve
    pre, dec = eng.prefill_strategy, eng.decode_strategy
    assert pre.op_shardings != dec.op_shardings
    diff = [n for n in pre.op_shardings
            if (dict(pre.op_shardings[n].weights),
                pre.op_shardings[n].outputs) !=
               (dict(dec.op_shardings[n].weights),
                dec.op_shardings[n].outputs)]
    assert diff, "strategies compare unequal but no op-level diff found"


def test_serving_clones_zero_dropout(gpt2_serve):
    """Inference determinism is a property of the PROGRAM: every dropout
    in both clones is rate-0 / p=0 even though the training graph trains
    with dropout=0.1, and layer names/topo order are preserved so params
    transfer 1:1."""
    eng, _ = gpt2_serve
    for sm in (eng.prefill_model, eng.decode_model):
        names = [l.name for l in sm.layers]
        assert names == [l.name for l in eng.model.layers]
        for l in sm.layers:
            if l.op_type is OperatorType.DROPOUT:
                assert l.params["rate"] == 0.0
            elif l.op_type is OperatorType.MULTIHEAD_ATTENTION:
                assert l.params["dropout"] == 0.0
    # the training graph really does carry nonzero dropout
    assert any(l.params.get("rate", 0) == 0.1 for l in eng.model.layers
               if l.op_type is OperatorType.DROPOUT)


def test_kv_pools_sharded_on_model_axis(gpt2_serve):
    """The paged pools shard their heads dim along the axis the decode
    search put on the attention weights — cache ops never reshard."""
    eng, _ = gpt2_serve
    assert eng.kv.heads_axis is not None
    assert eng.kv_shard_degree > 1
    k = eng.kv.state[eng.attn_layers[0]]["k"]
    shard0 = k.addressable_shards[0].data
    # heads and head_dim are one axis at rest: a shard holds whole heads
    assert shard0.shape[2] * eng.kv_shard_degree \
        == eng.kv_spec.heads * eng.kv_spec.head_dim
    assert shard0.shape[2] % eng.kv_spec.head_dim == 0


# ----------------------------------------------------------- decode parity
def _gpt2_parity_errs(eng, toks, prompt_len):
    """Max |decode - full forward| per generated position (teacher-forced:
    the decode path sees the same token stream as the full forward)."""
    slots, seq = eng.slots, int(eng.prefill_model.input_tensors[0].spec.shape[1])
    L = len(toks)
    ids_full = np.zeros((slots, seq), np.int32)
    ids_full[0, :L] = toks
    full, _ = eng.prefill(eng.params, gpt2_prompt_inputs(
        ids_full, np.full((slots,), L, np.int32)))
    full = np.asarray(full)

    ids = np.zeros((slots, seq), np.int32)
    ids[0, :prompt_len] = toks[:prompt_len]
    lengths = np.zeros((slots,), np.int32)
    lengths[0] = prompt_len
    assert eng.kv.admit(0, prompt_len, L + 2)
    eng.kv.push()
    pre, kv_state = eng.prefill(eng.params, gpt2_prompt_inputs(ids, lengths))
    eng.kv.commit_prefill(kv_state, np.arange(slots, dtype=np.int32), lengths)
    errs = [float(np.abs(np.asarray(pre)[0, :prompt_len]
                         - full[0, :prompt_len]).max())]
    state = eng.kv.state
    for t in range(prompt_len, L):
        step = np.zeros((slots, 1), np.int32)
        step[0, 0] = toks[t]
        logits, state = eng.decode_step(
            eng.params, state, gpt2_step_inputs(jnp.asarray(step), state))
        errs.append(float(np.abs(np.asarray(logits)[0, 0] - full[0, t]).max()))
    eng.kv.adopt(state)
    eng.kv.evict(0)
    eng.kv.push()
    return errs


def test_decode_parity_gpt2(gpt2_serve, rng):
    """Incremental decode with the paged sharded cache == full-sequence
    forward, at EVERY position, to 1e-5 — under the searched (model-axis
    sharded) strategies."""
    eng, gc = gpt2_serve
    toks = rng.integers(1, gc.vocab, size=12).astype(np.int32)
    errs = _gpt2_parity_errs(eng, toks, prompt_len=4)
    assert max(errs) <= 1e-5, errs


def test_decode_parity_transformer(devices, rng):
    """Same parity bar for the GENERIC transformer stack (raw embedding
    inputs, no position table) under a searched model-axis mesh."""
    cfg = _serve_cfg(max_batch_slots=2)
    model = FFModel(cfg)
    seq, d_model = 12, 32
    build_transformer(model, batch=8, seq=seq, d_model=d_model, heads=4,
                      d_ff=64, layers=1, classes=0, causal=True, dropout=0.1)
    eng = compile_serving(model, max_decode_len=4)
    eng.init(seed=0)
    assert eng.kv.heads_axis is not None  # sharded pools, not a dp fallback

    slots, L, P = eng.slots, 10, 3
    x = rng.normal(size=(slots, seq, d_model)).astype(np.float32)
    full, _ = eng.prefill(eng.params, [x])
    full = np.asarray(full)

    xp = np.zeros_like(x)
    xp[0, :P] = x[0, :P]
    lengths = np.zeros((slots,), np.int32)
    lengths[0] = P
    assert eng.kv.admit(0, P, L + 2)
    eng.kv.push()
    pre, kv_state = eng.prefill(eng.params, [xp])
    eng.kv.commit_prefill(kv_state, np.arange(slots, dtype=np.int32), lengths)
    errs = [float(np.abs(np.asarray(pre)[0, :P] - full[0, :P]).max())]
    state = eng.kv.state
    for t in range(P, L):
        logits, state = eng.decode_step(eng.params, state,
                                        [jnp.asarray(x[:, t:t + 1])])
        errs.append(float(np.abs(np.asarray(logits)[0, 0] - full[0, t]).max()))
    assert max(errs) <= 1e-5, errs


def test_inference_determinism(gpt2_serve, rng):
    """Two identical serving passes are BITWISE identical — dropout is
    structurally gone and the rng is pinned, with no flag to forget."""
    eng, gc = gpt2_serve
    toks = rng.integers(1, gc.vocab, size=8).astype(np.int32)
    slots = eng.slots
    seq = int(eng.prefill_model.input_tensors[0].spec.shape[1])
    ids = np.zeros((slots, seq), np.int32)
    ids[0, :8] = toks
    lengths = np.full((slots,), 8, np.int32)
    a, _ = eng.prefill(eng.params, gpt2_prompt_inputs(ids, lengths))
    b, _ = eng.prefill(eng.params, gpt2_prompt_inputs(ids, lengths))
    assert (np.asarray(a) == np.asarray(b)).all()
    step = np.ones((slots, 1), np.int32)

    def one_step():
        # a step consumes the state it is handed: each pass gets a copy
        state = jax.tree_util.tree_map(jnp.copy, eng.kv.state)
        return eng.decode_step(eng.params, state,
                               gpt2_step_inputs(jnp.asarray(step), state))[0]

    assert (np.asarray(one_step()) == np.asarray(one_step())).all()


# ------------------------------------------- first tokens taken on the device
def _first_token_case(name, slots, seq):
    return {
        "one": np.ones((slots,), np.int32),
        "mid": np.full((slots,), seq // 2, np.int32),
        "seq": np.full((slots,), seq, np.int32),
        "mixed": np.array([1 + (5 * i) % seq for i in range(slots)], np.int32),
        "empty_slot": np.array([0] + [3 + i for i in range(slots - 1)],
                               np.int32),
    }[name]


@pytest.mark.parametrize("case", ["one", "mid", "seq", "mixed", "empty_slot"])
def test_prefill_first_tokens_match_full_logits(gpt2_serve, rng, case):
    """The program the scheduler runs returns, per slot, the argmax of
    `prefill`'s logits at the last real position (row 0 for an empty slot),
    and the same K/V bit for bit."""
    eng, gc = gpt2_serve
    lengths = _first_token_case(case, eng.slots, gc.seq)
    ids = rng.integers(1, gc.vocab, size=(eng.slots, gc.seq)).astype(np.int32)
    for s, n in enumerate(lengths):
        ids[s, n:] = 0
    inputs = gpt2_prompt_inputs(ids, lengths)
    logits, kv_full = eng.prefill(eng.params, inputs)
    tokens, kv_state = eng.prefill_first_tokens(eng.params, inputs, lengths)
    tokens = np.asarray(tokens)
    assert tokens.shape == (eng.slots,) and tokens.dtype == np.int32
    rows = np.asarray(logits)[np.arange(eng.slots), np.maximum(lengths - 1, 0)]
    np.testing.assert_array_equal(tokens, rows.argmax(axis=-1))
    assert set(kv_state) == set(kv_full)
    for name in kv_full:
        for a, b in zip(jax.tree_util.tree_leaves(kv_state[name]),
                        jax.tree_util.tree_leaves(kv_full[name])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prefill_first_tokens_gathers_on_another_last_layers_output(devices,
                                                                    rng):
    """The generic transformer ends in a LayerNorm, not a Dense: its first
    tokens are gathered on the program's output, with the same answer."""
    from flexflow_tpu.serving.engine import _positionwise_head
    model = FFModel(_serve_cfg(max_batch_slots=2))
    seq, d_model = 12, 32
    build_transformer(model, batch=8, seq=seq, d_model=d_model, heads=4,
                      d_ff=64, layers=1, classes=0, causal=True, dropout=0.1)
    eng = compile_serving(model, max_decode_len=4)
    eng.init(seed=0)
    assert _positionwise_head(eng.prefill_model) is None
    x = rng.normal(size=(eng.slots, seq, d_model)).astype(np.float32)
    lengths = np.array([seq, 0], np.int32)
    out, kv_full = eng.prefill(eng.params, [x])
    tokens, kv_state = eng.prefill_first_tokens(eng.params, [x], lengths)
    rows = np.asarray(out)[np.arange(eng.slots), np.maximum(lengths - 1, 0)]
    np.testing.assert_array_equal(np.asarray(tokens), rows.argmax(axis=-1))
    for a, b in zip(jax.tree_util.tree_leaves(kv_state),
                    jax.tree_util.tree_leaves(kv_full)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_head_is_applied_after_the_gather_only_when_positionwise(gpt2_serve):
    """Chosen from the graph: gpt2's lm_head is a Dense over the last axis
    of a layer's `[slots, S, d]` output; a Dense on a graph input, a Dense
    with an opaque activation, or any other last layer is gathered on its
    output instead."""
    from flexflow_tpu.serving.engine import _positionwise_head
    eng, _ = gpt2_serve
    assert _positionwise_head(eng.prefill_model) is eng.prefill_model.layers[-1]
    m = FFModel(FFConfig(log_level="warning"))
    x = m.create_tensor([4, 8, 16], name="x")
    m.dense(x, 32, name="on_input")
    assert _positionwise_head(m) is None
    m.dense(m.dense(x, 16), 32, activation=lambda v: v.sum(1, keepdims=True))
    assert _positionwise_head(m) is None
    m.softmax(m.dense(x, 32))
    assert _positionwise_head(m) is None
    m.dense(m.dense(x, 16), 32)
    assert _positionwise_head(m) is m.layers[-1]


# ----------------------------------------------------------- strategy cache
def test_strategy_cache_warm_hit_both_programs(gpt2_serve):
    """A second compile_serving of the same graph/machine/knobs restores
    BOTH searched strategies from the cache — zero DP expansions — and the
    two programs live under INDEPENDENT cache keys."""
    from flexflow_tpu.search.dp import SEARCH_STATS

    _, gc = gpt2_serve  # fixture's compile populated the hermetic cache
    model = FFModel(_serve_cfg())
    build_gpt2(model, gc, batch=8)
    SEARCH_STATS["expansions"] = 0
    eng = compile_serving(model)
    assert SEARCH_STATS["expansions"] == 0
    pre_info = getattr(eng.prefill_strategy, "_cache_info", None)
    dec_info = getattr(eng.decode_strategy, "_cache_info", None)
    assert pre_info and pre_info["event"] == "hit"
    assert dec_info and dec_info["event"] == "hit"
    assert pre_info["key"] != dec_info["key"]
    assert pre_info["meta"]["kind"] == "prefill"
    assert dec_info["meta"]["kind"] == "decode"


# --------------------------------------------------------- memory accounting
def test_kv_memory_accounted_in_watermarks(gpt2_serve):
    """KV-cache bytes appear in memory_stats, the measured pool residency
    matches the KVCacheSpec prediction exactly (fixed-size pools), and the
    total predicted envelope holds against the measured watermark."""
    eng, _ = gpt2_serve
    ms = eng.memory_stats()
    assert ms["predicted_kv_cache_bytes"] > 0
    assert ms["actual_kv_cache_bytes_per_device"] == \
        ms["predicted_kv_cache_bytes"]
    assert ms["predicted_total_bytes"] == \
        ms["predicted_kv_cache_bytes"] + ms["predicted_param_bytes"]
    spec = eng.kv_spec
    per_dev = spec.total_bytes() // eng.kv_shard_degree
    assert ms["predicted_kv_cache_bytes"] == per_dev
    wm = eng.health_report()["watermarks"]
    assert wm["samples"] >= 1
    assert wm["ratio"] <= wm["warn_ratio"], wm
    assert not wm["warn"]


# -------------------------------------------------------------- scheduler
def test_scheduler_continuous_batching(gpt2_serve, rng):
    """More requests than slots: admission waves, max-len eviction, every
    request completes with exactly its token budget, and all pages return
    to the free list."""
    eng, gc = gpt2_serve
    n = eng.slots + 3
    reqs = [Request(rid=i, prompt=list(rng.integers(1, gc.vocab, size=3)),
                    max_new_tokens=4, arrival_s=0.0) for i in range(n)]
    sched = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                        gpt2_step_inputs, dispatch_ahead=3)
    done = sched.run(reqs)
    assert len(done) == n
    assert sorted(r.rid for r in done) == list(range(n))
    for r in done:
        assert len(r.tokens) == r.max_new_tokens
        assert r.ttft_s is not None and r.ttft_s >= 0.0
        assert r.finish_s is not None
    assert sched.prefills >= 2  # continuous batching: a second wave joined
    assert len(eng.kv.free_slots()) == eng.slots
    assert len(eng.kv.free_pages) == eng.kv_spec.pool_pages - 1


def test_a_run_keeps_the_heap_it_found_from_the_collector(gpt2_serve, rng):
    """While requests are in flight a full collection walks what the run
    itself made: the objects held before it are frozen when the first token
    arrives and still when the last does, and handed back when the run ends
    (a failed run too)."""
    import gc as collector

    eng, gc = gpt2_serve
    before = collector.get_freeze_count()
    held = len(collector.get_objects())
    seen = []

    class Stream(list):
        def append(self, token):
            seen.append(collector.get_freeze_count())
            super().append(token)

        def extend(self, tokens):
            seen.append(collector.get_freeze_count())
            super().extend(tokens)

    reqs = [Request(rid=i, prompt=list(rng.integers(1, gc.vocab, size=3)),
                    max_new_tokens=4, tokens=Stream()) for i in range(2)]
    sched = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                        gpt2_step_inputs)
    assert len(sched.run(reqs)) == 2
    assert len(seen) >= 4 and min(seen) >= before + held // 2
    assert collector.get_freeze_count() <= before
    with pytest.raises(TypeError):
        sched.run(None)
    assert collector.get_freeze_count() <= before


# what the parent of ISSUE 29 served for this request set, with 4-D pools
# (`[pages, page, heads, head_dim]`) and nothing donated: pinned once
PARENT_TOKENS = {
    0: [159, 177, 170, 13, 170, 179], 1: [178, 24, 130, 198, 198, 41],
    2: [29, 13, 25, 198, 67, 83], 3: [13, 25, 198, 156, 83, 61],
    4: [61, 198, 36, 173, 61, 253], 5: [198, 87, 182, 96, 131, 126]}


def test_dense_donated_pools_serve_the_parents_tokens(gpt2_serve):
    """Heads and head_dim merged at rest and the state donated to every
    step: the same values land in the same (page, offset, head) cells, so
    the served tokens are the 4-D un-donated layout's, bit for bit. And
    with a window in flight the scheduler never holds a consumed tree:
    what it hands each dispatch is `kv.state`, alive in every leaf."""
    eng, gc = gpt2_serve
    assert eng.kv.state[eng.attn_layers[0]]["k"].shape[2:] \
        == (eng.kv_spec.heads * eng.kv_spec.head_dim,)
    rng = np.random.default_rng(29)
    reqs = [Request(rid=i, prompt=list(rng.integers(1, gc.vocab, size=3 + i)),
                    max_new_tokens=6, arrival_s=0.0) for i in range(6)]
    handed = []

    def step_inputs(tokens, state):
        handed.append(state is eng.kv.state and not any(
            x.is_deleted() for x in jax.tree_util.tree_leaves(eng.kv.state)))
        return gpt2_step_inputs(tokens, state)

    sched = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                        step_inputs, eos_id=None,
                                        dispatch_ahead=4)
    done = sched.run(reqs)
    assert {r.rid: list(r.tokens) for r in done} == PARENT_TOKENS
    assert len(handed) == sched.decode_steps and all(handed)
    # steps stay in flight behind the syncs: the pipeline runs empty less
    # often than a step is dispatched, and some syncs leave it running
    assert sched.stats["drains"] < sched.decode_steps
    assert sched.stats["overlapped_syncs"] > 0
    assert sched.stats["overlapped_syncs"] + sched.stats["drains"] \
        == sched.materializations
    assert not any(x.is_deleted()
                   for x in jax.tree_util.tree_leaves(eng.kv.state))


def test_scheduler_eos_eviction(gpt2_serve, rng):
    """EOS evicts early: pick the token the (deterministic) model emits at
    step 2 as the EOS id and re-serve — the sequence truncates right after
    it while the non-matching request still runs to its budget."""
    eng, gc = gpt2_serve
    prompt = list(rng.integers(1, gc.vocab, size=3))
    probe = [Request(rid=0, prompt=list(prompt), max_new_tokens=5)]
    sched = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                        gpt2_step_inputs, dispatch_ahead=2)
    ref = sched.run(probe)[0].tokens
    eos = ref[2]  # _truncate cuts at the FIRST occurrence, so the
    # expected output is ref up to wherever eos first appears
    reqs = [Request(rid=0, prompt=list(prompt), max_new_tokens=5)]
    sched2 = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                         gpt2_step_inputs, eos_id=eos,
                                         dispatch_ahead=2)
    out = sched2.run(reqs)[0]
    assert out.tokens == ref[:ref.index(eos) + 1]
    assert len(eng.kv.free_slots()) == eng.slots


def test_scheduler_page_backpressure(gpt2_serve, rng):
    """Backpressure is the free LIST draining (a single request is always
    capped at its slot's page budget): with every slot holding its full
    budget nothing more admits; eviction restores admissibility, and the
    scheduler serves admissible requests to completion."""
    eng, gc = gpt2_serve
    kv = eng.kv
    for s in range(eng.slots):  # drain: each slot takes its whole budget
        assert kv.admit(s, 1, kv.spec.padded_len)
    assert not kv.free_pages
    assert not kv.can_admit(1)
    for s in range(eng.slots):
        kv.evict(s)
    kv.push()
    assert kv.can_admit(kv.spec.padded_len)
    reqs = [Request(rid=i, prompt=list(rng.integers(1, gc.vocab, size=2)),
                    max_new_tokens=3, arrival_s=0.0) for i in range(2)]
    sched = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                        gpt2_step_inputs, dispatch_ahead=2)
    assert len(sched.run(reqs)) == 2


def test_scheduler_never_compiles_the_full_logits_program(gpt2_serve, rng):
    """A scheduler run takes every first token from the device-side program:
    the full-logits jit compiles nothing for it, the wave brings `slots`
    int32 to the host, and each request's first token is the one the
    full-logits path reads."""
    from flexflow_tpu import telemetry as tel
    eng, gc = gpt2_serve
    n = eng.slots + 2
    reqs = [Request(rid=i, prompt=list(rng.integers(1, gc.vocab, size=2 + i)),
                    max_new_tokens=3, arrival_s=0.0) for i in range(n)]
    sched = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                        gpt2_step_inputs, dispatch_ahead=2)
    full_before = eng._prefill_jit._cache_size()
    t_run = time.perf_counter_ns()
    done = sched.run(reqs)
    assert eng._prefill_jit._cache_size() == full_before
    assert eng._prefill_first_tokens_jit._cache_size() == 1
    copies = tel.ring_spans("serve/prefill/logits_to_host", since_ns=t_run)
    assert [c.args["bytes"] for c in copies] == [eng.slots * 4] * sched.prefills
    assert len(done) == n
    for r in done:
        ids = np.zeros((eng.slots, gc.seq), np.int32)
        lengths = np.zeros((eng.slots,), np.int32)
        ids[0, :len(r.prompt)] = r.prompt
        lengths[0] = len(r.prompt)
        logits, _ = eng.prefill(eng.params, gpt2_prompt_inputs(ids, lengths))
        assert r.tokens[0] == int(np.asarray(logits)[0, len(r.prompt) - 1].argmax())


def test_serve_profile_ops_emits_corpus_rows(gpt2_serve, rng, tmp_path):
    """--profile-ops on a serving engine: a served batch featurizes its
    prefill + decode placements into op/attr rows priced by the serving
    search's OWN cost fns (the bandwidth-bound seq=1 decode regime that
    training fits never exercise)."""
    from flexflow_tpu import telemetry as tel
    from flexflow_tpu.attribution import OP_EVENT

    eng, gc = gpt2_serve
    tdir = str(tmp_path / "tel")
    tel.configure(tdir)
    old = eng.cfg.profile_ops
    eng.cfg.profile_ops = True
    try:
        reqs = [Request(rid=i, prompt=list(rng.integers(1, gc.vocab, size=3)),
                        max_new_tokens=3, arrival_s=0.0) for i in range(2)]
        sched = ContinuousBatchingScheduler(eng, eng.params,
                                            gpt2_prompt_inputs,
                                            gpt2_step_inputs)
        sched.run(reqs)
    finally:
        eng.cfg.profile_ops = old
        tel.shutdown()
    rows = [e.get("args") or {} for e in tel.read_events(tdir)
            if e.get("name") == OP_EVENT]
    srcs = {a.get("source") for a in rows}
    assert {"serve_prefill", "serve_decode"} <= srcs, srcs
    # every row is a full corpus row: featurized, with the serving
    # regime's own predicted price
    assert all(isinstance(a.get("features"), dict) for a in rows)
    dec = [a for a in rows if a.get("source") == "serve_decode"]
    assert any((a.get("predicted_s") or 0) > 0 for a in dec)


def test_a_profiled_run_gives_each_program_its_own_events(gpt2_serve, rng,
                                                           tmp_path):
    """engine.op_attribution(profile_dir=...): rows measured from a real
    profile (`source == "trace"`), and each program's report takes only
    the events of its own module: `fusion.3` exists in the prefill, the
    commit and the decode program alike."""
    from flexflow_tpu import attribution

    eng, gc = gpt2_serve
    pdir = str(tmp_path / "prof")
    reqs = [Request(rid=i, prompt=list(rng.integers(1, gc.vocab, size=3)),
                    max_new_tokens=4, arrival_s=0.0) for i in range(3)]
    sched = ContinuousBatchingScheduler(eng, eng.params, gpt2_prompt_inputs,
                                        gpt2_step_inputs)
    with jax.profiler.trace(pdir):
        sched.run(reqs)
        jax.block_until_ready(eng.kv.state)
    events = attribution.profile_events(pdir)
    pre, dec = (eng._programs["prefill_first_tokens"],
                eng._programs["decode"])
    reports = eng.op_attribution(step_time_s=1e-3, prefill_step_time_s=1e-2,
                                 profile_dir=pdir)
    assert {pre.module, dec.module} <= set(events)
    assert pre.module != dec.module
    assert all(r["source"] == "trace" for r in reports.values())
    for prog, tag in ((pre, "serve_prefill"), (dec, "serve_decode")):
        own = attribution.measured_from_trace(pdir, [prog])
        busy = sum(e - s for n, s, e in events[prog.module]
                   if prog.scopes[n].opcode not in attribution.CONTAINERS)
        assert sum(us for ph in own.values() for us in ph.values()) \
            == pytest.approx(busy / 1e3)
        assert not any(attribution.UNATTRIBUTED in ph for ph in own.values())
        rows = reports[tag]["rows"]
        assert sum(r["measured_s"] for r in rows) > 0
        assert all(set(r["phases_s"]) <= {"forward"} for r in rows)


def test_serve_telemetry_stream(gpt2_serve, rng, tmp_path):
    """serve/prefill + serve/decode_step spans, queue/slot counters and
    per-request lifecycle events flow through the PR 5 sink and feed the
    monitor's serving panel."""
    import monitor

    from flexflow_tpu import telemetry as tel

    eng, gc = gpt2_serve
    tdir = str(tmp_path / "tel")
    tel.configure(tdir)
    try:
        reqs = [Request(rid=i, prompt=list(rng.integers(1, gc.vocab, size=3)),
                        max_new_tokens=3, arrival_s=0.0) for i in range(2)]
        sched = ContinuousBatchingScheduler(eng, eng.params,
                                            gpt2_prompt_inputs,
                                            gpt2_step_inputs,
                                            dispatch_ahead=2)
        sched.run(reqs)
    finally:
        tel.shutdown()
    evs = tel.read_events(tdir)
    names = {e.get("name") for e in evs}
    for want in ("serve/prefill", "serve/decode_step", "serve/queue_depth",
                 "serve/active_slots", "serve/request_admitted",
                 "serve/request_done"):
        assert want in names, (want, sorted(names))
    state = monitor.gather(evs)
    sv = monitor._serve_stats(state["serve"])
    assert sv["requests_done"] == 2 and sv["tokens"] == 6
    assert sv["ttft_p99_s"] is not None and sv["decode_p99_ms"] is not None
    prom = str(tmp_path / "node.prom")
    monitor.prom_export(state, prom)
    with open(prom) as f:
        txt = f.read()
    assert "flexflow_serve_tokens_per_second" in txt
    assert "flexflow_serve_ttft_p99_seconds" in txt


def test_compile_serving_span_says_how_the_step_appends(devices, tmp_path,
                                                        capsys):
    """The in-place append's engagement counters: `serve/compile_serving`
    carries the pool's shape at rest and the bytes of the state leaves a
    decode step is told to donate, and trace_report prints them with the
    span's other args."""
    import trace_report

    from flexflow_tpu import telemetry as tel

    tdir = str(tmp_path / "tel")
    model = FFModel(_serve_cfg(telemetry_dir=tdir, search_budget=0,
                               only_data_parallel=True))
    build_gpt2(model, _gpt2_cfg(), batch=8)
    try:
        eng = compile_serving(model)
    finally:
        tel.shutdown()
    spec = eng.kv_spec
    shape = [spec.pool_pages, spec.page_size, spec.heads * spec.head_dim]
    donated = sum(int(x.nbytes)
                  for x in jax.tree_util.tree_leaves(eng.kv.state))
    made = [sp.args for sp in tel.ring_spans()
            if sp.name == "serve/compile_serving"][-1]
    assert made["kv_pool_shape"] == shape
    assert made["decode_state_donated_bytes"] == donated \
        >= 2 * spec.layers * int(np.prod(shape)) * 4
    trace_report.render(tdir, out_path=None)
    out = capsys.readouterr().out
    assert f"decode_state_donated_bytes={donated}" in out
    assert f"kv_pool_shape={shape}" in out
