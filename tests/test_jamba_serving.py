"""Jamba served (flexflow_tpu/models/jamba.py through compile_serving: the
Mamba-1 op's chunk form started from a slot's state, ops/mamba_ops.py and
serving/engine.py; 4 : 1 multi-query attention over pages,
ops/attention_ops.py) against its plain reference
(benchmarks/harness/reference_jamba.py), at a small size on the CPU with
seeded random weights.

Tolerance: program and reference both compute in float32, so they differ by
the order of their sums alone (the scan by blocks against the literal
recurrence, chunks through a slot's state and pages against one full pass):
about 1e-6 of the result's scale. RTOL 1e-4 leaves two orders for that and
none for a fault: a state that leaks, a conv tail one row off or a wrong
head group is off by 1e-2 and more.
"""

import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))

from flexflow_tpu import FFConfig, FFModel  # noqa: E402
from flexflow_tpu import telemetry as tel  # noqa: E402
from flexflow_tpu.models import (BailingHybridConfig, BrumbyConfig,  # noqa: E402
                                 DeepseekV3Config, GraniteHybridConfig,
                                 JambaConfig, Lfm2MoeConfig,
                                 build_bailing_hybrid, build_brumby,
                                 build_deepseek_v3, build_granite_hybrid,
                                 build_jamba, build_lfm2_moe)
from flexflow_tpu.ops.registry import STATS_KEY  # noqa: E402
from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,  # noqa: E402
                                  compile_serving, valid_prompt_inputs,
                                  valid_step_inputs)
from families import jamba as family  # noqa: E402
from harness import manifest as mf  # noqa: E402
from harness import reference_jamba as reference  # noqa: E402
from served import off_by  # noqa: E402

RTOL = 1e-4
SLOTS = 4


def ffconfig(batch, **kw):
    return FFConfig(batch_size=batch, seed=3, strategy_cache=False,
                    log_level="warning", mesh_shape={"data": 1}, **kw)


def tiny_file(**changed) -> dict:
    return dict(mf.read_named("configs", "jamba-tiny"), **changed)


def reference_logits(params, cfg, ids):
    return reference.forward(family.reference_params(params, cfg),
                             np.asarray(ids), family.hyper(cfg))


def engine_for(g, seed=3, chunk=16, max_decode_len=12, page=4, **kw):
    model = FFModel(ffconfig(SLOTS, serve_prefill_chunk=chunk, **kw))
    build_jamba(model, g, batch=SLOTS)
    eng = compile_serving(model, max_batch_slots=SLOTS,
                          max_decode_len=max_decode_len, kv_page_size=page)
    eng.init(seed=seed)
    return eng


class Chunked:
    """Drives `engine.prefill_chunk` and `decode_step` by hand: a prompt in
    chunks through its slot's state and pages, then steps, every logits row
    against the reference's full forward."""

    def __init__(self, eng, cfg, rtol=RTOL):
        self.eng, self.cfg, self.seqs, self.rtol = eng, cfg, {}, rtol
        self.progress = {}
        self.checked = 0
        self.counters = []

    def want(self, seq):
        return np.asarray(reference_logits(
            self.eng.params, self.cfg, np.asarray([seq], np.int32)))[0, -1]

    def admit(self, slot, prompt):
        self.eng.kv.admit(slot, len(prompt), len(prompt) + 16,
                          prefilling=True)
        self.eng.kv.push()
        self.progress[slot] = [list(prompt), 0]

    def chunk(self, slot):
        """The slot's next chunk; True once its prompt is whole."""
        eng, kv, c = self.eng, self.eng.kv, self.eng.chunk_tokens
        prompt, done = self.progress[slot]
        part = prompt[done:done + c]
        ids = np.zeros((1, c), np.int32)
        ids[0, :len(part)] = part
        lengths, context = np.asarray([len(part)]), np.asarray([done])
        tok, state = eng.prefill_chunk(
            eng.params, kv.state, valid_prompt_inputs(ids, lengths, context),
            kv.prefill_row(slot)[None], context, lengths, np.asarray([slot]))
        self.counters.append((done, len(part), state.pop(STATS_KEY)))
        kv.adopt(state)
        self.progress[slot][1] = done + len(part)
        if done + len(part) < len(prompt):
            return False
        del self.progress[slot]
        kv.activate(slot, len(prompt))
        kv.push()
        self.seqs[slot] = prompt + [int(np.asarray(tok)[0])]
        assert self.seqs[slot][-1] == int(self.want(prompt).argmax())
        return True

    def prefill(self, slot, prompt):
        self.admit(slot, prompt)
        chunks = 1
        while not self.chunk(slot):
            chunks += 1
        return chunks

    def decode(self, steps):
        eng, kv = self.eng, self.eng.kv
        for _ in range(steps):
            nxt = np.zeros((eng.slots, 1), np.int32)
            for slot, seq in self.seqs.items():
                nxt[slot, 0] = seq[-1]
            logits, state = eng.decode_step(
                eng.params, kv.state,
                valid_step_inputs(jnp.asarray(nxt), kv.state))
            self.step_stats = state.pop(STATS_KEY)
            kv.adopt(state)
            kv.sync_after(1)
            logits = np.asarray(logits)
            for slot, seq in self.seqs.items():
                assert off_by(logits[slot, 0], self.want(seq)) <= self.rtol, \
                    (slot, len(seq))
                self.checked += 1
                seq.append(int(logits[slot, 0].argmax()))

    def finish(self, slot):
        self.eng.kv.evict(slot)
        self.eng.kv.push()
        return self.seqs.pop(slot)


def prompts(g, seed):
    rng = np.random.default_rng(seed)
    return lambda n: [int(t) for t in rng.integers(0, g.vocab, n)]


def test_the_tiny_file_is_the_programs_tiny_config():
    assert family.program_config(tiny_file()) == JambaConfig.tiny(seq=128)
    assert JambaConfig.tiny().layer_types == ("mamba", "mamba", "attention",
                                              "mamba")
    assert JambaConfig().layer_types.count("attention") == 2
    assert [i for i, k in enumerate(JambaConfig().layer_types)
            if k == "attention"] == [7, 21]


def test_prefill_by_chunks_then_decode_through_state_and_pages():
    """Chunks of 16: a prompt of 29 ends inside its second chunk, one of 32
    on a chunk's edge, one of 41 inside its third; then steps of all three:
    every logits row is the reference's full forward over the slot's tokens,
    and the counters are the reckoned ones."""
    g, cfg = JambaConfig.tiny(seq=80), tiny_file()
    eng = engine_for(g)
    assert eng.chunk_tokens == 16 and eng.kv.state_kinds == "paged_kv+recurrent"
    new = prompts(g, 11)
    c = Chunked(eng, cfg)
    assert c.prefill(0, new(29)) == 2
    c.decode(2)
    assert c.prefill(2, new(32)) == 2
    assert c.prefill(1, new(41)) == 3
    c.decode(6)
    assert c.checked == 2 + 6 * 3
    row = 2 * 1 * 16 * 4        # K and V, one head of 16, float32
    for done, n, stats in c.counters:
        assert float(stats["mamba_layers"]) == 3
        assert float(stats["mamba_rows"]) == 3 * n
        assert float(stats["chunk_state_in"]) == (done > 0)
        assert float(stats["full_keys_seen"]) == (done + np.arange(n) + 1).sum()
        assert float(stats["full_kv_bytes_needed"]) == row * (done + 16)
    state = 3 * (8 * 128 * 4 + 3 * 128 * 4)     # S and the tail, float32
    assert float(c.step_stats["ssm_state_bytes"]) == 2 * 3 * state
    assert int(c.step_stats["ssm_step_kernel_slots"]) == 0
    live = [len(seq) - 1 for seq in c.seqs.values()]
    assert float(c.step_stats["full_kv_bytes_needed"]) == row * sum(live)


def test_two_slots_chunks_interleaved_equal_each_prompt_alone():
    """A1, B1, A2, B2 with a decode step of a third slot in between: a chunk
    starts from ITS slot's state and no other's."""
    g, cfg = JambaConfig.tiny(seq=80), tiny_file()
    eng = engine_for(g)
    new = prompts(g, 5)
    c = Chunked(eng, cfg)
    c.prefill(3, new(9))
    a, b = new(30), new(27)
    c.admit(0, a)
    c.admit(2, b)
    assert not c.chunk(0)
    assert not c.chunk(2)
    c.decode(1)
    assert c.chunk(0)       # the first token is the reference's (asserted)
    assert c.chunk(2)
    c.decode(4)
    assert c.checked == 1 + 3 * 4


def test_a_reused_slot_starts_from_zeros():
    """A finished request's state stays in its slot's arrays; the next
    request's first chunk starts from zeros all the same: its logits are
    the reference's, and bit for bit those of the same request on a fresh
    engine."""
    g, cfg = JambaConfig.tiny(seq=80), tiny_file()
    new = prompts(g, 7)
    first, second = new(37), new(21)
    eng = engine_for(g)
    c = Chunked(eng, cfg)
    c.prefill(1, first)
    c.decode(3)
    c.finish(1)
    assert float(jnp.abs(eng.kv.state["l0_mamba"]["ssm"][1]).max()) > 0
    c.prefill(1, second)
    c.decode(3)
    fresh = Chunked(engine_for(g), cfg)
    fresh.prefill(1, second)
    fresh.decode(3)
    assert c.seqs[1] == fresh.seqs[1]
    for leaf in ("ssm", "conv"):
        assert np.array_equal(
            np.asarray(eng.kv.state["l3_mamba"][leaf][1]),
            np.asarray(fresh.eng.kv.state["l3_mamba"][leaf][1]))


def test_multi_query_attention_with_heads_that_differ():
    """20 query heads over ONE K/V head of 8 (the published ratio), every
    layer attention: the whole sequence, and chunks then steps over the
    pages, against the reference; a model whose query heads all read the
    same row would pass only if they were equal, and they are not."""
    g = JambaConfig(vocab=256, seq=48, d_model=160, layers=2,
                    attn_layer_period=1, attn_layer_offset=0, heads=20,
                    kv_heads=1, dense_width=64, mamba_d_state=8,
                    mamba_dt_rank=8)
    assert g.layer_types == ("attention", "attention")
    cfg = tiny_file(hidden_size=160, num_hidden_layers=2, attn_layer_period=1,
                    attn_layer_offset=0, num_attention_heads=20,
                    intermediate_size=64, vocab_size=256)
    model = FFModel(ffconfig(SLOTS, serve_prefill_chunk=16))
    build_jamba(model, g, batch=SLOTS)
    eng = compile_serving(model, max_batch_slots=SLOTS, max_decode_len=8,
                          kv_page_size=4)
    eng.init(seed=3)
    assert eng.kv.state_kinds == "paged_kv" and eng.kv_spec.heads == 1
    wq = np.asarray(eng.params["l0_attn"]["wq"]).reshape(160, 20, 8)
    assert not np.allclose(wq[:, 0], wq[:, 1])
    c = Chunked(eng, cfg)
    new = prompts(g, 2)
    assert c.prefill(0, new(23)) == 2
    assert c.prefill(3, new(32)) == 2
    c.decode(4)
    assert c.checked == 8


def test_the_scheduler_serves_it_in_chunks():
    """Nine requests through ContinuousBatchingScheduler on four slots (slots
    and their state rows are reused): every served token is the reference's
    argmax; the spans carry the counters the benchmark's readers take."""
    g, cfg = JambaConfig.tiny(seq=80), tiny_file()
    eng = engine_for(g)
    rng = np.random.default_rng(0)
    shapes = [(40, 10), (17, 12), (68, 6), (33, 8), (5, 9), (48, 12),
              (30, 5), (61, 7), (69, 4)]
    reqs = [Request(rid=i, prompt=[int(t) for t in
                                   rng.integers(1, g.vocab, n)],
                    max_new_tokens=k, arrival_s=0.0)
            for i, (n, k) in enumerate(shapes)]
    sched = ContinuousBatchingScheduler(
        eng, eng.params, valid_prompt_inputs, valid_step_inputs, eos_id=None)
    before = len(tel.ring_spans())
    done = sched.run(reqs)
    spans = tel.ring_spans()[before:]
    assert [r.rid for r in sched.shed] == [8]
    assert len(done) == 8 and not sched.failed
    for r in done:
        seq = r.prompt + r.tokens
        want = np.asarray(reference_logits(
            eng.params, cfg, np.asarray([seq], np.int32)))[0]
        assert r.tokens == [int(t) for t in
                            want[len(r.prompt) - 1:len(seq) - 1].argmax(-1)]
    waits = [s for s in spans if s.name == "serve/prefill/device_wait"]
    syncs = [s for s in spans if s.name == "serve/decode/window_sync"]
    assert len(waits) == sum(-(-n // 16) for n, _ in shapes[:8]) and syncs
    for s in waits:
        for name in ("mamba_layers", "mamba_rows", "chunk_state_in",
                     "full_keys_seen"):
            assert name in s.args, name
    assert sum(s.args["chunk_state_in"] for s in waits) \
        == len(waits) - 8
    for s in syncs:
        assert s.args["ssm_state_bytes"] > 0
        assert s.args["ssm_step_kernel_slots"] == 0
    named = {s.name: s.args for s in tel.ring_spans()}
    assert named["mamba/scan_path"]["path"] == "xla"
    assert named["mamba/step_path"]["path"] == "xla"
    assert named["full_attend/chunk_path"]["path"] == "xla"
    compiled = [s for s in tel.ring_spans()
                if s.name == "serve/compile_serving"][-1].args
    assert compiled["state_layers"] == 3 == compiled["chunk_state_layers"]
    assert compiled["state_bytes_per_slot"] == 3 * (8 * 128 * 4 + 3 * 128 * 4)


def test_the_model_with_the_moe_keys_raises_by_name():
    with pytest.raises(NotImplementedError, match="num_experts 16"):
        JambaConfig(num_experts=16, experts_per_tok=2)
    with pytest.raises(NotImplementedError, match="num_experts_per_tok 2"):
        family.program_config(tiny_file(num_experts=16,
                                        num_experts_per_tok=2))


def _granite(m):
    build_granite_hybrid(m, GraniteHybridConfig.tiny(seq=48), batch=SLOTS)


def _ling(m):
    build_bailing_hybrid(m, BailingHybridConfig.tiny(seq=48), batch=SLOTS)


def _brumby(m):
    build_brumby(m, BrumbyConfig.tiny(seq=48), batch=SLOTS)


def _lfm2(m):
    build_lfm2_moe(m, Lfm2MoeConfig.tiny(seq=48), batch=SLOTS)


def _deepseek(m):
    build_deepseek_v3(m, DeepseekV3Config.tiny(seq=48), batch=SLOTS)


@pytest.mark.parametrize("build, named", [
    (_granite, "recurrent layers of mamba2"),
    (_ling, "paged_latent.*recurrent layers of kda"),
    (_brumby, "recurrent layers of power_retention.*none that pages"),
    (_lfm2, "recurrent layers of short_conv"),
    (_deepseek, "paged_latent")],
    ids=["mamba2", "kda", "power_retention", "short_conv", "paged_latent"])
def test_chunked_prefill_is_refused_by_the_name_of_what_lacks_it(build, named):
    """`compile_serving` with `serve_prefill_chunk`: a recurrent op that
    declares no sequence form from a state (OpDef.chunk_from_state) is named,
    a latent cache is named; the tiny Jamba, whose op declares it,
    compiles."""
    m = FFModel(ffconfig(SLOTS, serve_prefill_chunk=16))
    build(m)
    with pytest.raises(NotImplementedError,
                       match=f"chunked prefill.*{named}"):
        compile_serving(m, max_batch_slots=SLOTS, max_decode_len=8)


def test_the_tiny_jamba_compiles_with_chunks_and_by_waves():
    g = JambaConfig.tiny(seq=48)
    eng = engine_for(g)
    assert eng.chunk_tokens == 16 and sorted(eng.kv.recurrent) == [
        "l0_mamba", "l1_mamba", "l3_mamba"]
    with pytest.raises(ValueError, match="needs the chunk's slot"):
        eng.prefill_chunk(eng.params, eng.kv.state, [], [[0]], [0], [1])
    model = FFModel(ffconfig(SLOTS))
    build_jamba(model, g, batch=SLOTS)
    waves = compile_serving(model, max_batch_slots=SLOTS, max_decode_len=8)
    assert waves.chunk_tokens == 0 and len(waves.kv.recurrent) == 3
