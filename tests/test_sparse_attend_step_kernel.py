"""A decode step's attention over the keys an indexer kept is one kernel over
the live slots' pages (kernels/sparse_attend_step.py behind
ops/attention_ops.py: `_selected_cache_attention`, chosen by its
`step_path`): against the XLA form every step took before (the mask compacted
to the kept positions, their rows gathered; here what a mesh keeps) on the
same pools, page tables and kept sets, at lane-aligned toy widths, the kernel
interpreted.

Tolerance. In float32 both forms are a softmax over the same keys whose sums
run in another order: RTOL 1e-5 of the result's scale (a key kept that the
other form drops, or a wrong page, is off by the size of a row). In bfloat16
the XLA form scores and sums in bfloat16 and the kernel in float32: 2e-2.
Pages no live slot's context reaches hold NaN: a copy of one would show.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))

from flexflow_tpu.core.layer import Layer  # noqa: E402
from flexflow_tpu.core.tensor import Tensor, TensorSpec  # noqa: E402
from flexflow_tpu.dtype import DataType  # noqa: E402
from flexflow_tpu.kernels import sparse_attend_step as step_kernel  # noqa: E402
from flexflow_tpu.models import KeyeVLConfig  # noqa: E402
from flexflow_tpu.ops import attention_ops, get_op_def  # noqa: E402
from flexflow_tpu.ops.op_type import OperatorType  # noqa: E402
from flexflow_tpu.ops.registry import STATS_KEY, LoweringCtx  # noqa: E402
from flexflow_tpu.serving import (positions3_valid_prompt_inputs,  # noqa: E402
                                  positions3_valid_step_inputs)
from flexflow_tpu import telemetry as tel  # noqa: E402
from served import Served, off_by  # noqa: E402
from test_keye_vl import engine_for, reference_logits, tiny_file  # noqa: E402

SLOTS, PAGE, PER_SLOT, TOPK = 6, 16, 12, 16
CONTEXT = PAGE * PER_SLOT           # 192 positions a slot: a block and a half
LIVE = {"none": [], "one": [4], "some": [0, 2, 5], "all": list(range(SLOTS))}
# a slot's position t: under topk, at a page's edges, in the second block, at
# the cache's end
POSITIONS = {"under_topk": [5, 0, 9, 15, 3, 11],
             "page_edges": [15, 16, 31, 32, 127, 128],
             "cache_end": [191, 190, 129, 100, 191, 60]}
# name: (K/V heads, query heads a group, head_dim, pools' type)
SHAPES = {"head_spans_f32": (2, 4, 128, jnp.float32),
          "head_spans_bf16": (2, 8, 128, jnp.bfloat16)}


@pytest.fixture(autouse=True)
def key_blocks_of_128(monkeypatch):
    """A key block of 8 pages: a slot's 12 pages are a block and a half."""
    monkeypatch.setattr(step_kernel, "_BLOCK_TOKENS", 128)


def operands(shape, live, positions, seed=0):
    """(qg `[b, 1, g, r, d]`, pools, table, t `[b, 1]`, the kept mask `[b, 1,
    L]`, live `[b]`): scattered tables, `TOPK` keys kept at random under each
    position (all of them where there are fewer), NaN in every page no live
    slot's context reaches."""
    g, r, d, dt = SHAPES[shape]
    rng = np.random.default_rng(seed)
    pages = SLOTS * PER_SLOT + 1
    k, v = (rng.standard_normal((pages, PAGE, g * d)).astype(np.float32)
            for _ in range(2))
    table = rng.permutation(np.arange(1, pages)).reshape(
        SLOTS, PER_SLOT).astype(np.int32)
    t = np.asarray(POSITIONS[positions], np.int32)
    mask = np.zeros(SLOTS, bool)
    mask[LIVE[live]] = True
    keep = np.zeros((SLOTS, 1, CONTEXT), bool)
    for s in range(SLOTS):
        keep[s, 0, rng.permutation(t[s] + 1)[:TOPK]] = True
        reach = -(-(t[s] + 1) // PAGE) if mask[s] else 0
        k[table[s, reach:]] = np.nan
        v[table[s, reach:]] = np.nan
    qg = rng.standard_normal((SLOTS, 1, g, r, d)).astype(np.float32)
    return (jnp.asarray(qg, dt), jnp.asarray(k, dt), jnp.asarray(v, dt),
            jnp.asarray(table), jnp.asarray(t)[:, None], jnp.asarray(keep),
            jnp.asarray(mask))


def two_devices():
    return jax.sharding.Mesh(np.array(jax.devices()[:2]), ("model",))


def indexer_layer(topk):
    ins = [Tensor(TensorSpec((SLOTS, 1, 64), DataType.FLOAT), name="x"),
           Tensor(TensorSpec((SLOTS, 1), DataType.INT32), name="positions"),
           Tensor(TensorSpec((SLOTS, 1), DataType.INT32), name="valid")]
    layer = Layer(OperatorType.SPARSE_INDEXER,
                  {"heads": 2, "head_dim": 8, "topk": topk,
                   "rope_theta": 1e4, "eps": 1e-6, "decode": True}, ins,
                  name="index")
    layer.add_output(get_op_def(OperatorType.SPARSE_INDEXER).infer(layer)[0])
    return layer


def attention_layer():
    """An attention layer whose last input is an indexer's output: what
    `_selected_cache_attention` reads of its layer (the name, the indexer's
    `topk`)."""
    index = indexer_layer(TOPK)
    x = index.inputs[0]
    return Layer(OperatorType.MULTIHEAD_ATTENTION, {"selected": True},
                 [x, x, x, index.outputs[0]], name="attn")


def attend(qg, k, v, table, t, keep, live, stats=None, mesh=None):
    """`_selected_cache_attention` as the decode twin calls it."""
    ctx = LoweringCtx(state={"serve/active": live.astype(jnp.int32)},
                      stats=stats, mesh=mesh)
    return attention_ops._selected_cache_attention(
        attention_layer(), qg, k, v, table, t, keep,
        1.0 / np.sqrt(qg.shape[-1]), ctx)


def xla_form(qg, k, v, table, t, keep, live, stats=None):
    """The form a program lowered for a mesh keeps: the mask compacted, the
    kept rows gathered. Pools without the NaN (the gather takes rows for
    every slot; what it reads of them is masked)."""
    k, v = (jnp.nan_to_num(x) for x in (k, v))
    return attend(qg, k, v, table, t, keep, live, stats, two_devices())


def said_since(mark):
    """The `sparse_attend/step_path` spans' facts since `mark` spans."""
    return [s.args for s in tel.ring_spans()[mark:]
            if s.name == "sparse_attend/step_path"]


@pytest.mark.parametrize("positions", list(POSITIONS))
@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_kernel_against_the_xla_form(shape, live, positions):
    """Every live slot's rows are the XLA form's; a slot that is not live
    reads zeros, and none of its pages (NaN) reached the result."""
    qg, k, v, table, t, keep, mask = operands(shape, live, positions)
    path = attention_ops.step_path(qg.shape[-1], PAGE, PER_SLOT, k.dtype)
    assert path == {"path": "kernel", "block_pages": 8}
    got = np.asarray(attend(qg, k, v, table, t, keep, mask), np.float32)
    want = np.asarray(xla_form(qg, k, v, table, t, keep, mask), np.float32)
    assert got.shape == want.shape == qg.shape
    mask = np.asarray(mask)
    assert np.isfinite(got).all()
    assert not got[~mask].any()
    if mask.any():
        rtol = 1e-5 if k.dtype == jnp.float32 else 2e-2
        assert np.abs(got[mask] - want[mask]).max() \
            <= rtol * np.abs(want[mask]).max()
        assert np.abs(got[mask]).max() > 1e-2


@pytest.mark.parametrize("live", list(LIVE))
def test_the_counters_of_two_layers_on_either_path(live):
    """`sparse_attend_kernel_slots` = layers x live slots and
    `kv_bytes_streamed` = the live slots' pages under their positions, whole,
    on the kernel path, both 0 on the XLA path; `kv_bytes_gathered` (the kept
    keys' rows of the live slots) equal on both; a lowered layer says its
    path in a span."""
    qg, k, v, table, t, keep, mask = operands("head_spans_f32", live,
                                              "page_edges")
    ours, theirs = {}, {}
    mark = len(tel.ring_spans())
    for _layer in range(2):
        attend(qg, k, v, table, t, keep, mask, ours)
        xla_form(qg, k, v, table, t, keep, mask, theirs)
    assert said_since(mark) == [
        {"layer": "attn", "path": "kernel", "block_pages": 8},
        {"layer": "attn", "path": "xla"}] * 2
    at = np.asarray(t)[np.asarray(mask), 0]
    row = 2 * k.shape[-1] * 4
    assert int(ours["sparse_attend_kernel_slots"]) == 2 * len(at)
    assert float(ours["kv_bytes_streamed"]) \
        == 2 * row * PAGE * sum(-(-(int(x) + 1) // PAGE) for x in at)
    assert float(ours["kv_bytes_gathered"]) \
        == float(theirs["kv_bytes_gathered"]) \
        == 2 * row * sum(min(int(x) + 1, TOPK) for x in at)
    assert int(theirs["sparse_attend_kernel_slots"]) == 0
    assert float(theirs["kv_bytes_streamed"]) == 0.0


REFUSED = {  # name: (head_dim, page, pages a slot, pools' type, mesh)
    "a_mesh": (128, 16, 12, jnp.bfloat16, two_devices),
    "a_head_not_whole_slabs": (64, 16, 12, jnp.bfloat16, None),
    "a_page_not_whole_tiles": (128, 8, 12, jnp.bfloat16, None),
    # 5 pages of 24 float32 rows: a block of 120 tokens
    "a_block_not_whole_lanes": (128, 24, 12, jnp.float32, None)}


@pytest.mark.parametrize("case", list(REFUSED))
def test_step_path_keeps_the_xla_form(case):
    head_dim, page, per_slot, dt, mesh = REFUSED[case]
    assert attention_ops.step_path(head_dim, page, per_slot, dt,
                                   mesh() if mesh else None) == {"path": "xla"}


def test_step_path_takes_the_kernel_at_the_served_shapes(monkeypatch):
    """Keye's cell: K/V heads of 128 in bfloat16, pages of 16, 1056 pages a
    slot; and a context that is one block, whatever its lanes."""
    monkeypatch.undo()
    pages = step_kernel._BLOCK_TOKENS // 16
    assert attention_ops.step_path(128, 16, 1056, jnp.bfloat16) \
        == {"path": "kernel", "block_pages": pages}
    assert attention_ops.step_path(128, 8, 6, jnp.float32) \
        == {"path": "kernel", "block_pages": 6}


def test_the_indexer_hands_the_mask_on_at_a_step():
    """A decode step's indexer hands the membership mask `[b, 1, L]` on, as
    its block form does, and compacts nothing: `topk` keys a slot under its
    position, all of them where there are fewer."""
    layer = indexer_layer(TOPK)
    rng = np.random.default_rng(3)
    weights = {n: jnp.asarray(rng.normal(size=s.shape) / np.sqrt(s.shape[0]),
                              jnp.float32)
               for n, s in layer.weight_specs.items()}
    pages = SLOTS * PER_SLOT + 1
    t = np.asarray(POSITIONS["page_edges"], np.int32)
    state = {"index": {"ik": jnp.asarray(
                 rng.normal(size=(pages, PAGE, 128)), jnp.float32)},
             "serve/page_table": jnp.asarray(rng.permutation(
                 np.arange(1, pages)).reshape(SLOTS, PER_SLOT), jnp.int32),
             "serve/pos": jnp.asarray(t)}
    ctx = LoweringCtx(state=state, new_state={}, stats={})
    x = jnp.asarray(rng.normal(size=(SLOTS, 1, 64)), jnp.float32)
    out = get_op_def(OperatorType.SPARSE_INDEXER).lower(
        layer, [x, jnp.asarray(t)[:, None], jnp.ones((SLOTS, 1), jnp.int32)],
        weights, ctx)[0]
    assert layer.outputs[0].spec.dtype == DataType.BOOL
    kept = np.minimum(t + 1, TOPK)
    assert out.dtype == jnp.bool_ and out.shape == (SLOTS, 1, CONTEXT)
    assert np.array_equal(np.asarray(out).sum(-1)[:, 0], kept)
    assert not any(np.asarray(out)[s, 0, t[s] + 1:].any()
                   for s in range(SLOTS))
    assert int(ctx.stats["sparse_keys_kept"]) == kept.sum()


# ------------------------------------------- through the programs, on logits
WIDE = dict(heads=2, kv_heads=1, head_dim=128, mrope_section=(16, 24, 24))


def wide(seq=128):
    """The tiny model with one K/V head of 128 (a whole slab) and pages of
    16, so its decode step takes the kernel."""
    g = KeyeVLConfig.tiny(seq=seq)
    for key, value in WIDE.items():
        setattr(g, key, value)
    cfg = dict(tiny_file(), head_dim=128, num_attention_heads=2,
               num_key_value_heads=1)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], mrope_section=[16, 24, 24])
    return g, cfg


def test_prefill_then_decode_through_the_kernel_on_logits():
    """The `[slots, seq]` wave, then steps whose attention is the kernel:
    every logits row against the reference's full forward, the counters of a
    step, a slot evicted and its pages reused."""
    g, cfg = wide(seq=112)          # + 16 positions of answer: 8 pages
    eng = engine_for(g, page=16)
    assert eng.kv.state["l0_attn"]["k"].shape[1:] == (16, 128)
    assert eng.kv_spec.pages_per_slot == 8
    rng = np.random.default_rng(11)

    def step_stats(s, stats):
        live = [len(seq) for seq in s.seqs.values()]
        assert int(stats["sparse_attend_kernel_slots"]) == 2 * len(live)
        assert float(stats["kv_bytes_streamed"]) \
            == 2 * sum(-(-n // 16) for n in live) * 16 * 2 * 128 * 4
        assert float(stats["kv_bytes_gathered"]) \
            == 2 * sum(min(n, 8) for n in live) * 2 * 128 * 4

    s = Served(eng, lambda ids: reference_logits(eng.params, cfg, ids),
               positions3_valid_prompt_inputs, positions3_valid_step_inputs,
               1e-4, step_stats=step_stats)

    def prompt(n):
        return [int(x) for x in rng.integers(0, g.vocab, n)]

    s.wave({0: prompt(3), 1: prompt(31), 3: prompt(70)})
    s.decode(3)
    s.evict(1)
    s.wave({1: prompt(15), 2: prompt(48)})
    s.decode(2)
    assert s.checked == 3 + 9 + 2 + 8
    said = said_since(0)
    assert said and said[-1]["path"] == "kernel"


def test_a_prefilling_slot_is_not_live_to_the_steps_between_its_chunks():
    """A prompt goes in by chunks of 16 while another slot decodes: between
    two chunks `serve/active` leaves the prefilling slot out, so the step's
    kernel takes one slot a layer and touches none of the pages the chunks
    have written; the prompt's first token and the steps after it are the
    reference's."""
    g, cfg = wide()
    eng = engine_for(g, chunk=16, page=16)
    assert eng.kv_spec.pages_per_slot == 8
    kv, rng = eng.kv, np.random.default_rng(5)
    seqs = {}

    def reference_row(seq):
        return np.asarray(reference_logits(
            eng.params, cfg, np.asarray([seq], np.int32)))[0, -1]

    def decode():
        nxt = np.zeros((eng.slots, 1), np.int32)
        for slot, seq in seqs.items():
            nxt[slot, 0] = seq[-1]
        logits, state = eng.decode_step(
            eng.params, kv.state,
            positions3_valid_step_inputs(jnp.asarray(nxt), kv.state))
        stats = state.pop(STATS_KEY)
        kv.adopt(state)
        kv.sync_after(1)
        assert int(stats["sparse_attend_kernel_slots"]) == 2 * len(seqs)
        for slot, seq in seqs.items():
            row = np.asarray(logits)[slot, 0]
            assert off_by(row, reference_row(seq)) <= 1e-4
            seq.append(int(row.argmax()))

    def prefill(slot, prompt):
        kv.admit(slot, len(prompt), len(prompt) + 16, prefilling=True)
        kv.push()
        for done in range(0, len(prompt), 16):
            part = prompt[done:done + 16]
            ids = np.zeros((1, 16), np.int32)
            ids[0, :len(part)] = part
            lengths, context = np.asarray([len(part)]), np.asarray([done])
            tok, state = eng.prefill_chunk(
                eng.params, kv.state,
                positions3_valid_prompt_inputs(ids, lengths, context),
                kv.prefill_row(slot)[None], context, lengths)
            state.pop(STATS_KEY)
            kv.adopt(state)
            assert not np.asarray(kv.state["serve/active"])[slot]
            if seqs:
                decode()            # the live slots alone, in between
        kv.activate(slot, len(prompt))
        kv.push()
        seqs[slot] = list(prompt) + [int(np.asarray(tok)[0])]
        assert seqs[slot][-1] == int(reference_row(prompt).argmax())

    prefill(2, [int(x) for x in rng.integers(0, g.vocab, 21)])
    prefill(0, [int(x) for x in rng.integers(0, g.vocab, 50)])
    assert len(seqs[2]) == 21 + 1 + 4       # four steps between 0's chunks
    decode()
    decode()
