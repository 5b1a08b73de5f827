"""The language model of Keye-VL (flexflow_tpu/models/keye_vl.py: a learned
indexer that keeps `topk` keys a query in ops/sparse_attention_ops.py,
attention over the kept keys and three-axis rotary positions in
ops/attention_ops.py and ops/rotary.py, the indexer's key paged beside K and V
in serving/kv_cache.py, prefill in chunks over the slot's own cache in
serving/engine.py and scheduler.py) against its plain reference
(benchmarks/harness/reference_keye_vl.py), at a small size on the CPU with
seeded random weights and `topk` 8 well under every context.

Tolerance: program and reference both compute in float32, so they differ by
the order of their sums alone: about 1e-6 of the result's scale. RTOL 1e-4
leaves two orders for that and none for a fault: a key kept that the
reference drops moves a logit row by 1e-2 and more (the wrong-model tests).
"""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer  # noqa: E402
from flexflow_tpu import telemetry as tel  # noqa: E402
from flexflow_tpu.models import KeyeVLConfig, build_keye_vl  # noqa: E402
from flexflow_tpu.ops import sparse_attention_ops as sparse  # noqa: E402
from flexflow_tpu.ops.registry import STATS_KEY  # noqa: E402
from flexflow_tpu.ops.rotary import half_tables  # noqa: E402
from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,  # noqa: E402
                                  compile_serving,
                                  positions3_valid_prompt_inputs,
                                  positions3_valid_step_inputs)
from flexflow_tpu.serving.program import page_geometry  # noqa: E402
from families import keye_vl as family  # noqa: E402
from harness import flops_keye_vl as flops  # noqa: E402
from harness import manifest as mf  # noqa: E402
from harness import reference_keye_vl as reference  # noqa: E402
from served import Served, off_by  # noqa: E402

RTOL = 1e-4
SLOTS = 4
PUBLISHED = "Keye-VL-2.0-30B-A3B"
CELL = "Keye-VL-2.0-30B-A3B.serve-longprompt"


def ffconfig(batch, **kw):
    return FFConfig(batch_size=batch, seed=3, strategy_cache=False,
                    log_level="warning", mesh_shape={"data": 1}, **kw)


def tiny_file() -> dict:
    return mf.read_named("configs", "keye-vl-tiny")


def text_positions(ids):
    """Text's three position axes: one number three times."""
    pos = np.broadcast_to(np.arange(ids.shape[1], dtype=np.int32), ids.shape)
    return np.ascontiguousarray(np.repeat(pos[..., None], 3, axis=-1))


def reference_logits(params, cfg, ids, positions=None, **switches):
    hp = dict(family.hyper(cfg), **switches)
    ids = np.asarray(ids)
    return reference.forward(
        family.reference_params(params, cfg), ids,
        text_positions(ids) if positions is None else positions, hp)


def compiled(g, batch=4, **kw):
    m = FFModel(ffconfig(batch, **kw))
    build_keye_vl(m, g, batch=batch)
    cm = m.compile(SGDOptimizer(lr=1.0),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=3)
    return cm


def engine_for(g, seed=3, chunk=0, max_decode_len=16, page=8, **kw):
    model = FFModel(ffconfig(SLOTS, serve_prefill_chunk=chunk, **kw))
    build_keye_vl(model, g, batch=SLOTS)
    eng = compile_serving(model, max_batch_slots=SLOTS,
                          max_decode_len=max_decode_len, kv_page_size=page)
    eng.init(seed=seed)
    return eng


# ------------------------------------------------------------- the selection
@pytest.mark.parametrize("topk,keys,ties", [(8, 40, False), (8, 40, True),
                                             (5, 512, False), (64, 40, False),
                                             (16, 300, True)])
def test_the_kept_set_is_the_references(topk, keys, ties):
    """`keep_mask` (a threshold found bit by bit) against the reference's
    `kept_keys` (the topk-th value by sorting), query by query: the same
    set, with ties to the lower position, under the causal mask, and every
    allowed key while there are `topk` or fewer."""
    rng = np.random.default_rng(topk * keys)
    scores = rng.standard_normal((2, keys, keys)).astype(np.float32)
    if ties:        # a few distinct values only: most scores are tied
        scores = np.round(scores * 2) / 2
        scores[0, :, 3] = -np.inf
        scores[1, 7] = 0.0
    at = np.arange(keys)
    allowed = (np.arange(keys)[None, :] <= at[:, None])[None]
    got = np.asarray(jax.jit(lambda s: sparse.keep_mask(s, allowed, topk))(
        scores))
    for b in range(2):
        want = np.asarray(reference.kept_keys(jnp.asarray(scores[b]),
                                              jnp.asarray(at), topk))
        assert (got[b] == want).all()
        assert (got[b].sum(-1) == np.minimum(at + 1, topk)).all()


@pytest.mark.parametrize("n,k", [(40, 8), (300, 16), (1000, 256), (128, 128)])
def test_kept_positions_are_the_masks_ones_in_order(n, k):
    """The compaction a decode step runs instead of a sort: rows with none,
    some and exactly `k` kept keys, across the blocks of 128."""
    rng = np.random.default_rng(n + k)
    mask = np.zeros((3, 2, n), bool)
    for row, count in zip(mask.reshape(-1, n), (0, 1, k // 2, k - 1, k, k)):
        row[rng.permutation(n)[:count]] = True
    mask[2, 1] = False
    mask[2, 1, n - k:] = True               # the last k positions
    got = np.asarray(jax.jit(lambda m: sparse.kept_positions(m, k))(mask))
    assert got.shape == (3, 2, k) and got.dtype == np.int32
    for m, row in zip(mask.reshape(-1, n), got.reshape(-1, k)):
        want = np.nonzero(m)[0]
        assert (row[:len(want)] == want).all()
        assert (row[len(want):] == n).all()


def test_the_context_rungs_hold_every_context():
    assert sparse.context_rungs(1056) == (264, 528, 792, 1056)
    assert sparse.context_rungs(3) == (1, 2, 3)
    seen = []
    for end in (1, 16 * 264, 16 * 264 + 1, 16 * 1056):
        seen.append(int(sparse.over_context(
            lambda pages: jnp.asarray(pages), jnp.asarray(end), 1056, 16)))
    assert seen == [264, 264, 528, 1056]


def test_query_blocks_equal_one_block(monkeypatch):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((2, 64, 5)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((2, 64)).astype(np.float32))
    fn = lambda x, y: x * y[..., None] + 1.0
    whole = sparse.query_blocks(fn, 64, a, b)
    for block in (16, 24):      # whole blocks, and a last one that is not
        monkeypatch.setattr(sparse, "Q_BLOCK", block)
        got = sparse.query_blocks(fn, 64, a, b)
        assert got.shape == whole.shape
        assert np.allclose(np.asarray(got), np.asarray(whole), rtol=1e-6,
                           atol=1e-6)


# ------------------------------------------------------------------ positions
def test_equal_axes_are_one_axis_rotary_and_unequal_ones_are_not():
    pos = np.arange(40, dtype=np.int32)[None].repeat(2, 0)
    one = half_tables(jnp.asarray(pos), 16, 1e7)
    three = half_tables(jnp.asarray(np.repeat(pos[..., None], 3, -1)), 16,
                        1e7, (2, 3, 3))
    for a, b in zip(one, three):
        assert (np.asarray(a) == np.asarray(b)).all()
    apart = np.repeat(pos[..., None], 3, -1).copy()
    apart[..., 1] += 5
    cos, _ = half_tables(jnp.asarray(apart), 16, 1e7, (2, 3, 3))
    cos, base = np.asarray(cos), np.asarray(one[0])
    assert (cos[..., :2] == base[..., :2]).all()            # time's pairs
    assert not np.allclose(cos[..., 2:5], base[..., 2:5])   # height's
    assert (cos[..., 5:8] == base[..., 5:8]).all()          # width's
    with pytest.raises(ValueError):
        half_tables(jnp.asarray(apart), 16, 1e7, (2, 3, 4))


def test_unequal_position_axes_against_the_reference():
    g, cfg = KeyeVLConfig.tiny(seq=40), tiny_file()
    cm = compiled(g)
    rng = np.random.default_rng(5)
    ids = rng.integers(0, g.vocab, (4, 40)).astype(np.int32)
    pos = text_positions(ids).copy()
    pos[1, :, 1] += 3                   # an image's rows and columns
    pos[2, :, 2] = pos[2, :, 2] // 2
    pos[3, 10:, :] += rng.integers(0, 4, (30, 3))
    got = cm.forward(ids, pos, np.ones_like(ids))
    assert off_by(got, reference_logits(cm.params, cfg, ids, pos)) < RTOL
    # and the axes matter: text's positions give other logits
    assert off_by(got, reference_logits(cm.params, cfg, ids)) > 100 * RTOL


# ------------------------------------------------------------ the whole model
def test_the_tiny_file_is_the_programs_tiny_config():
    g, cfg = KeyeVLConfig.tiny(seq=128), tiny_file()
    assert family.program_config(cfg) == g
    assert g.indexer_topk == 8 and g.seq >= 8 * g.indexer_topk


def test_forward_logits_and_gradients_against_the_reference():
    g, cfg = KeyeVLConfig.tiny(seq=40), tiny_file()
    cm = compiled(g)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, g.vocab, (4, 40)).astype(np.int32)
    pos = text_positions(ids)
    got = cm.forward(ids, pos, np.ones_like(ids))
    assert got.shape == (4, g.seq, g.vocab)
    assert off_by(got, reference_logits(cm.params, cfg, ids)) < RTOL
    # the indexer does its work: with every key kept the logits are others
    assert off_by(got, reference_logits(cm.params, cfg, ids, indexer=False)) \
        > 100 * RTOL
    assert off_by(got, reference_logits(cm.params, cfg, ids, topk=4)) \
        > 100 * RTOL
    # gradients of the next-token loss, through the program's own layers
    from flexflow_tpu.compiler.lowering import build_forward
    labels = rng.integers(0, g.vocab, (4, 40)).astype(np.int32)
    fwd = build_forward(cm.model.layers, cm.model.input_tensors,
                        cm.model.layers[-1].outputs[:1], None, cm.strategy)

    def loss(params):
        logits = fwd(params, {}, [ids, pos, np.ones_like(ids)], False,
                     jax.random.PRNGKey(0))[0][0]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))

    got_g = jax.jit(jax.grad(loss))(cm.params)
    want_g = jax.grad(lambda p: reference.next_token_loss(
        family.reference_params(p, cfg), ids, pos, labels,
        family.hyper(cfg)))(cm.params)
    for layer in ("embed", "l0_attn", "l1_attn", "l0_moe", "l1_moe",
                  "lm_head", "l0_norm_op"):
        for w in got_g[layer]:
            assert off_by(got_g[layer][w], want_g[layer][w]) < 10 * RTOL, \
                (layer, w)
    # the selection is a choice, not a function with a slope: on both sides
    # the loss has no gradient into the indexer
    for w in got_g["l0_index"]:
        assert not np.asarray(got_g["l0_index"][w]).any()
        assert not np.asarray(want_g["l0_index"][w]).any()


def test_blocks_of_queries_change_no_logit(monkeypatch):
    """The sequence form and the reference in blocks of 16 and 12 queries
    over 40 positions (a last block that is not whole on either side)."""
    g, cfg = KeyeVLConfig.tiny(seq=40), tiny_file()
    ids = np.random.default_rng(9).integers(0, g.vocab, (4, 40)).astype(
        np.int32)
    whole = compiled(g)
    want = whole.forward(ids, text_positions(ids), np.ones_like(ids))
    monkeypatch.setattr(sparse, "Q_BLOCK", 16)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 12)
    cm = compiled(g)
    got = cm.forward(ids, text_positions(ids), np.ones_like(ids))
    assert off_by(got, want) < RTOL
    jax.clear_caches()
    assert off_by(got, reference_logits(cm.params, cfg, ids)) < RTOL
    jax.clear_caches()


def test_a_context_under_topk_is_plain_attention_of_the_same_weights():
    """With `topk` at or over the sequence the indexer keeps every key: the
    logits are the reference's with the indexer switched off."""
    g = KeyeVLConfig.tiny(seq=40)
    g.indexer_topk = 64
    cfg = dict(tiny_file(), sa_config=dict(tiny_file()["sa_config"], topk=64))
    cm = compiled(g)
    ids = np.random.default_rng(2).integers(0, g.vocab, (4, 40)).astype(
        np.int32)
    got = cm.forward(ids, text_positions(ids), np.ones_like(ids))
    assert off_by(got, reference_logits(cm.params, cfg, ids, indexer=False)) \
        < RTOL


# ------------------------------------------------------------- through caches
def test_one_wave_prefill_then_decode_through_the_cache():
    """Logits, not tokens, through the `[slots, seq]` wave (no chunks): K, V
    and the indexer's key committed to their pages, then steps that score
    the cached keys, keep 8 and gather their rows."""
    g, cfg = KeyeVLConfig.tiny(seq=48), tiny_file()
    eng = engine_for(g)
    assert eng.kv.state_kinds == "paged_kv+paged_index"
    assert len(eng.attn_layers) == 4 and len(eng.kv.index_layers) == 2
    assert eng.kv.state["l0_index"]["ik"].shape[-1] == 128
    rng = np.random.default_rng(7)

    def step_stats(s, stats):
        live = [len(seq) for seq in s.seqs.values()]
        assert int(stats["sparse_keys_live"]) == 2 * sum(live)
        assert int(stats["sparse_keys_kept"]) \
            == 2 * sum(min(n, 8) for n in live)
        assert float(stats["indexer_cache_bytes_read"]) \
            == 2 * sum(live) * 8 * 4
        assert float(stats["kv_bytes_gathered"]) \
            == 2 * sum(min(n, 8) for n in live) * 2 * 2 * 16 * 4
        assert int(stats["moe_held_pairs"]) == 2 * 2 * len(live)

    s = Served(eng, lambda ids: reference_logits(eng.params, cfg, ids),
               positions3_valid_prompt_inputs, positions3_valid_step_inputs,
               RTOL, step_stats=step_stats)

    def prompt(n):
        return [int(t) for t in rng.integers(0, g.vocab, n)]

    s.wave({0: prompt(3), 1: prompt(19), 2: prompt(33)})
    s.decode(3)
    s.evict(1)
    s.wave({1: prompt(9), 3: prompt(17)})
    s.decode(3)
    assert s.checked == 3 + 9 + 2 + 12


class Chunked:
    """Drives `engine.prefill_chunk` and `decode_step` by hand: a prompt in
    chunks into its slot's pages, then steps, every logits row against the
    reference's full forward."""

    def __init__(self, eng, cfg):
        self.eng, self.cfg, self.seqs = eng, cfg, {}
        self.checked = 0

    def prefill(self, slot, prompt, steps_between=0):
        eng, kv, c = self.eng, self.eng.kv, self.eng.chunk_tokens
        kv.admit(slot, len(prompt), len(prompt) + 16, prefilling=True)
        kv.push()
        chunks = 0
        for done in range(0, len(prompt), c):
            part = prompt[done:done + c]
            ids = np.zeros((1, c), np.int32)
            ids[0, :len(part)] = part
            lengths, context = np.asarray([len(part)]), np.asarray([done])
            tok, state = eng.prefill_chunk(
                eng.params, kv.state,
                positions3_valid_prompt_inputs(ids, lengths, context),
                kv.prefill_row(slot)[None], context, lengths)
            stats = state.pop(STATS_KEY)
            kv.adopt(state)
            at = done + np.arange(len(part))
            assert int(stats["sparse_keys_live"]) == 2 * int((at + 1).sum())
            assert int(stats["sparse_keys_kept"]) \
                == 2 * int(np.minimum(at + 1, 8).sum())
            assert int(stats["moe_held_pairs"]) == 2 * 2 * len(part)
            chunks += 1
            if steps_between and self.seqs:
                self.decode(steps_between)      # the live slots, in between
        kv.activate(slot, len(prompt))
        kv.push()
        self.seqs[slot] = list(prompt) + [int(np.asarray(tok)[0])]
        want = np.asarray(reference_logits(
            eng.params, self.cfg, np.asarray([prompt], np.int32)))[0, -1]
        assert self.seqs[slot][-1] == int(want.argmax())
        return chunks

    def decode(self, steps):
        eng, kv = self.eng, self.eng.kv
        for _ in range(steps):
            nxt = np.zeros((eng.slots, 1), np.int32)
            for slot, seq in self.seqs.items():
                nxt[slot, 0] = seq[-1]
            logits, state = eng.decode_step(
                eng.params, kv.state,
                positions3_valid_step_inputs(jnp.asarray(nxt), kv.state))
            state.pop(STATS_KEY)
            kv.adopt(state)
            kv.sync_after(1)
            logits = np.asarray(logits)
            for slot, seq in self.seqs.items():
                want = np.asarray(reference_logits(
                    eng.params, self.cfg, np.asarray([seq], np.int32)))[0, -1]
                assert off_by(logits[slot, 0], want) <= RTOL, (slot, len(seq))
                self.checked += 1
                seq.append(int(logits[slot, 0].argmax()))


def test_prefill_in_two_and_in_three_chunks_then_decode_on_logits():
    """Chunks of 16 over a cache of 64 positions a slot: a prompt of 29
    tokens goes in by two chunks, one of 41 by three (the last of each part
    filled), one of 16 by exactly one; decode steps of the live slots run
    between another prompt's chunks and do not touch its pages; every step's
    logits are the reference's full forward over the slot's tokens."""
    g, cfg = KeyeVLConfig.tiny(seq=64), tiny_file()
    eng = engine_for(g, chunk=16, max_decode_len=12)
    assert eng.chunk_tokens == 16
    assert eng.kv_spec.padded_len == 64     # the answer lies inside `seq`
    rng = np.random.default_rng(11)

    def prompt(n):
        return [int(t) for t in rng.integers(0, g.vocab, n)]

    c = Chunked(eng, cfg)
    assert c.prefill(0, prompt(29)) == 2
    c.decode(2)
    assert c.prefill(2, prompt(41), steps_between=1) == 3
    assert c.prefill(1, prompt(16), steps_between=1) == 1
    c.decode(3)
    assert c.checked == 2 + 3 * 1 + 1 * 2 + 3 * 3


def test_an_evicted_slots_keys_do_not_reach_its_next_owner():
    """A slot's pages go back to the free list with their K, V and indexer
    keys in them; the next owner's chunks and steps read none of it: its
    logits are bit for bit those of the same request on a fresh engine."""
    g, cfg = KeyeVLConfig.tiny(seq=64), tiny_file()
    rng = np.random.default_rng(13)
    first = [int(t) for t in rng.integers(0, g.vocab, 47)]
    second = [int(t) for t in rng.integers(0, g.vocab, 22)]

    def serve(eng, prompt, steps):
        c = Chunked(eng, cfg)
        c.prefill(0, prompt)
        c.decode(steps)
        return c.seqs[0]

    used = engine_for(g, chunk=16, max_decode_len=12)
    serve(used, first, 6)
    pages = list(used.kv._slot_pages[0])
    stale = np.asarray(used.kv.state["l0_index"]["ik"])[pages].copy()
    assert np.abs(stale).max() > 0
    used.kv.evict(0)
    used.kv.push()
    again = serve(used, second, 4)
    assert set(used.kv._slot_pages[0]) <= set(pages)    # the same pages
    fresh = serve(engine_for(g, chunk=16, max_decode_len=12), second, 4)
    assert again == fresh


def test_chunked_prefill_refuses_what_it_cannot_do():
    from flexflow_tpu.models import Lfm2MoeConfig, build_lfm2_moe
    m = FFModel(ffconfig(SLOTS, serve_prefill_chunk=16))
    build_lfm2_moe(m, Lfm2MoeConfig.tiny(seq=48), batch=SLOTS)
    with pytest.raises(NotImplementedError, match="recurrent"):
        compile_serving(m, max_batch_slots=SLOTS, max_decode_len=8)
    g = KeyeVLConfig.tiny(seq=64)
    for kw, what in (({"kv_host_pages": 8}, "host KV tier"),
                     ({"kv_cache_dtype": "int8"}, "quantized")):
        m = FFModel(ffconfig(SLOTS, **kw))
        build_keye_vl(m, g, batch=SLOTS)
        with pytest.raises(NotImplementedError, match=what):
            compile_serving(m, max_batch_slots=SLOTS, max_decode_len=8)
    eng = engine_for(g)
    with pytest.raises(RuntimeError, match="prefill_chunk"):
        eng.prefill_chunk(eng.params, eng.kv.state, [], [], [], [])


# ------------------------------------------------------------ the scheduler
def test_the_scheduler_prefills_in_chunks_and_its_spans_say_so():
    """Eight requests through ContinuousBatchingScheduler on four slots
    (slots are reused; a request waits while others decode): every served
    token is the reference's argmax; a prompt of exactly `seq -
    max_decode_len` is served and one token more is shed; each chunk is one
    `serve/admit` span with the facts the benchmark's readers take, decode
    windows lie outside them, `admit_s` is the first chunk's dispatch."""
    g, cfg = KeyeVLConfig.tiny(seq=64), tiny_file()
    eng = engine_for(g, chunk=16, max_decode_len=12, page=4)
    rng = np.random.default_rng(0)
    shapes = [(40, 10), (17, 12), (52, 6), (33, 8), (5, 9), (48, 12),
              (30, 5), (20, 7), (53, 4)]
    reqs = [Request(rid=i, prompt=[int(t) for t in
                                   rng.integers(1, g.vocab, n)],
                    max_new_tokens=k, arrival_s=0.0)
            for i, (n, k) in enumerate(shapes)]
    sched = ContinuousBatchingScheduler(
        eng, eng.params, positions3_valid_prompt_inputs,
        positions3_valid_step_inputs, eos_id=None)
    before = len(tel.ring_spans())
    done = sched.run(reqs)
    spans = tel.ring_spans()[before:]
    assert [r.rid for r in sched.shed] == [8]
    assert sched.shed[0].shed_reason == "prompt_too_long"
    assert len(done) == 8 and not sched.failed
    for r in done:
        seq = r.prompt + r.tokens
        want = np.asarray(reference_logits(
            eng.params, cfg, np.asarray([seq], np.int32)))[0]
        assert r.tokens == [int(t) for t in
                            want[len(r.prompt) - 1:len(seq) - 1].argmax(-1)]
        assert len(r.tokens) == r.max_new_tokens
        assert r.admit_s is not None and r.ttft_s > 0
    admits = [s for s in spans if s.name == "serve/admit"]
    chunks_wanted = sum(-(-n // 16) for n, _ in shapes[:8])
    assert len(admits) == chunks_wanted == sched.prefills
    for s in admits:
        a = s.args
        assert a["requests"] == 1 and a["padded_tokens"] == 16
        assert 1 <= a["prompt_tokens"] <= 16
        assert a["context_before"] == 16 * a["chunk_index"]
        assert a["chunk_index"] < a["chunks_of_request"]
        assert a["requests_started"] == (a["chunk_index"] == 0)
    assert sum(a.args["prompt_tokens"] for a in admits) \
        == sum(n for n, _ in shapes[:8])
    assert sum(a.args["requests_started"] for a in admits) == 8
    waits = [s for s in spans if s.name == "serve/prefill/device_wait"]
    assert len(waits) == len(admits)
    for s in waits:
        for counter in ("sparse_keys_kept", "sparse_keys_live",
                        "indexer_cache_bytes_read", "kv_bytes_gathered",
                        "moe_held_pairs", "moe_experts_hit",
                        "moe_experts_held"):
            assert counter in s.args, counter
    syncs = [s for s in spans if s.name == "serve/decode/window_sync"]
    assert syncs
    for s in syncs:
        for counter in ("sparse_keys_kept", "sparse_keys_live",
                        "indexer_cache_bytes_read", "kv_bytes_gathered",
                        "moe_experts_hit", "moe_experts_held",
                        "moe_step_kernel_experts"):
            assert counter in s.args, counter
        assert 0 < s.args["sparse_keys_kept"] <= s.args["sparse_keys_live"]
        # a decode window lies outside every chunk's span
        assert not any(a.start_ns < s.end_ns and s.start_ns < a.end_ns
                       for a in admits)
    for name in ("serve/prefill/dispatch", "serve/prefill/commit"):
        inside = [s for s in spans if s.name == name]
        assert len(inside) == len(admits)
        assert all(any(a.start_ns <= s.start_ns and s.end_ns <= a.end_ns
                       for a in admits) for s in inside)
    compile_span = [s for s in tel.ring_spans()
                    if s.name == "serve/compile_serving"][-1].args
    assert compile_span["prefill_chunk"] == 16
    assert compile_span["sparse_topk"] == 8
    assert compile_span["mrope_section"] == [2, 3, 3]
    assert compile_span["experts_held"] == 8


def test_both_programs_are_registered_and_carry_the_two_scopes():
    from flexflow_tpu import attribution
    g = KeyeVLConfig.tiny(seq=64)
    eng = engine_for(g, chunk=16, max_decode_len=12)
    c = Chunked(eng, tiny_file())
    c.prefill(0, [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 3])
    c.decode(1)
    for program in ("serve/prefill", "serve/decode"):
        for scope in (sparse.INDEX_SCOPE, sparse.ATTEND_SCOPE):
            found = [names for names in
                     attribution.instructions_under(program, scope) if names]
            assert found, (program, scope)
        types = {s.op_type for m in attribution.op_scopes(program)
                 for s in m.values()}
        assert {"sparse_indexer", "multihead_attention", "moe_layer"} <= types


# ------------------------------------------------------- counts and the file
def test_the_configuration_file_against_the_catalog_and_the_issue():
    cfg = mf.read_named("configs", PUBLISHED)
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["num_hidden_layers"] == 6
    widths = {"hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32,
              "num_key_value_heads": 4, "moe_intermediate_size": 768,
              "num_experts": 128, "num_experts_per_tok": 8,
              "vocab_size": 151936, "rope_theta": 10000000}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    for key in ("source", "deployment", "departures", "assumed", "why"):
        assert cfg[key]
    for key in ("qk_norm", "indexer_input", "indexer_k_norm",
                "indexer_rotary", "indexer_score", "chunk_tiles", "router"):
        assert cfg["assumed"][key]
    assert cfg["assumed"]["serve_positions"] == 16896
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(r for r in map(json.loads, catalog.read_text().splitlines())
                   if r["name"] == PUBLISHED)
        assert cfg["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == set(cfg["reduced"])


def test_the_cells_files_hold_the_issues_parameters():
    tr = mf.read_named("traffic", "serve-longprompt")
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 8192,
                                "sigma": 0.5, "min": 4096, "max": 16384}
    assert tr["output_len"] == {"dist": "lognormal", "median": 256,
                                "sigma": 0.6, "min": 32, "max": 512}
    assert (tr["shape_seed"], tr["drain_limit_s"], tr["warmup_requests"],
            tr["trace_seconds"], tr["trace_ramp_s"], tr["parity_requests"]) \
        == (52, 20, 6, 14, 4, 4)
    system = mf.read_named("workloads", CELL)
    assert (system["max_batch_slots"], system["max_decode_len"],
            system["kv_page_size"]) == (16, 512, 16)
    assert system["ffconfig"]["serve_prefill_chunk"] == 2048
    assert system["ffconfig"]["compute_dtype"] == "bfloat16"
    cfg = mf.read_named("configs", PUBLISHED)
    assert tr["prompt_len"]["max"] + system["max_decode_len"] \
        == cfg["assumed"]["serve_positions"]
    assert tr["prompt_len"]["min"] >= 2 * cfg["sa_config"]["topk"]
    manifest = mf.load_manifest()
    cell = mf.load_cell(manifest, CELL)
    assert cell.chips == 1 and cell.config["family"] == "keye_vl"
    names = {m["name"] for m in cell.per_layer}
    for name in ("prefill_mfu.keye", "decode_step_hbm_roofline.keye",
                 "wave_sparse_indexer_device_ms.keye",
                 "decode_sparse_indexer_device_ms_per_step.keye",
                 "sparse_index_hbm_roofline.decode.keye",
                 "sparse_attend_hbm_roofline.decode.keye",
                 "sparse_keys_kept_share.decode.keye",
                 "prefill_chunks_per_request.keye",
                 "moe_experts_hit_share.decode.keye",
                 "moe_held_pair_share.decode.keye",
                 "moe_expert_load_max_over_mean.decode.keye",
                 "wave_attention_device_ms", "wave_experts_device_ms",
                 "decode_attention_device_ms_per_step",
                 "prefill_useful_token_share", "queue_wait_p95_ms"):
        assert name in names, name
        if name.endswith(".keye"):
            spec = mf.read_named("metrics", name)
            need = spec["args"].get("need")
            assert need is None or hasattr(flops, need)
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}


@pytest.mark.parametrize("name", (PUBLISHED, "keye-vl-tiny"))
def test_flop_and_byte_functions_against_the_program(name):
    cfg = mf.read_named("configs", name)
    g = family.program_config(cfg)
    assert flops.train_flops_per_token(cfg, g.seq) == g.flops_per_token()
    assert flops.param_count(cfg) == g.param_count()
    m = FFModel(ffconfig(2))
    build_keye_vl(m, g, batch=2)
    assert sum(spec.num_elements for l in m.layers
               for spec in l.weight_specs.values()) == g.param_count()
    assert page_geometry(m) == {"heads": g.kv_heads, "head_dim": g.head_dim,
                                "index_dim": g.indexer_head_dim}
    # what the equations keep of a token, and what lies at rest (the
    # indexer's key in whole lanes)
    lanes = -(-g.indexer_head_dim // 128) * 128
    assert g.cache_bytes_per_token() == flops.cache_bytes_per_token(cfg) \
        + g.layers * 2 * (lanes - g.indexer_head_dim)


def test_the_issues_arithmetic():
    cfg = mf.read_named("configs", PUBLISHED)
    assert 128 * flops.expert_params(cfg) == 603979776
    assert flops.attention_matmul_params(cfg) == 18874368
    assert flops.indexer_matmul_params(cfg) == 2260992
    assert flops.layer_dense_params(cfg, small=False) \
        == 18874368 + 2260992 + 262144
    assert flops.param_count(cfg) == 4374622464 == KeyeVLConfig(
        layers=6).param_count()
    assert round(2 * flops.param_count(cfg) / 1e9, 2) == 8.75
    assert flops.cache_bytes_per_token(cfg) == 6 * (2048 + 128) == 13056
    assert KeyeVLConfig(layers=6).cache_bytes_per_token() == 6 * (2048 + 256)
    # the published 48 layers: 30.6 B held, 3.15 B of them a token's
    full = dict(cfg, num_hidden_layers=48)
    assert round(flops.param_count(full) / 1e9, 1) == 30.6
    assert round(flops.matmul_params_per_token(full) / 1e9, 2) == 3.15
    system = mf.read_named("workloads", CELL)
    traffic = mf.read_named("traffic", "serve-longprompt")
    # a decode step at 6 live slots of 9 k context: hit experts most of it
    live, context = 6, 9000
    counters = {"moe_routed_pairs": 6 * 8 * live, "moe_experts_hit": 6 * 41,
                "indexer_cache_bytes_read": 6 * live * context * 128,
                "kv_bytes_gathered": 6 * live * 2048 * 2048}
    need = flops.decode_step_need(cfg, system, traffic, counters)
    assert 0.6 < 6 * 41 * 9437184 / need["bytes"] < 0.8
    assert flops.sparse_index_need(cfg, system, traffic, dict(
        counters, steps=1))["bytes"] == 6 * live * context * 128
    assert flops.sparse_attend_need(cfg, system, traffic, dict(
        counters, steps=2))["bytes"] == 6 * live * 2048 * 2048 / 2
    # a whole chunk of 2048 tokens at a context of 8192 before it
    at = 8192 + np.arange(2048)
    chunk = flops.prefill_chunk_need(cfg, system, traffic, {
        "moe_held_pairs": 6 * 8 * 2048,
        "sparse_keys_live": 6 * int((at + 1).sum()),
        "sparse_keys_kept": 6 * 2048 * 2048})
    assert 1.9e12 < chunk["flops"] < 2.3e12
