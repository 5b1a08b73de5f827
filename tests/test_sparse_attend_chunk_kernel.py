"""A prefill chunk's attention over the keys an indexer kept is one kernel
that keeps its scores in VMEM (kernels/sparse_attend_chunk.py behind
ops/attention_ops.py: `_selected_cache_attention`, chosen by its
`chunk_path`): against the XLA block form every chunk took before (dense
under the mask over the rung of the slot's pages that holds the context,
queries in blocks; here what a mesh keeps) on the same pools, page tables and
kept sets, at head_dim 128 and pages of 16, the kernel interpreted.

Tolerance. In float32 both forms are a softmax over the same keys whose sums
run in another order: RTOL 1e-5 of the result's scale (a key kept that the
other form drops, or a wrong page, is off by the size of a row). In bfloat16
both score in float32 and multiply `probs` in bfloat16; the XLA form rounds
them after the division and the kernel before it: 2e-2. Every page behind a
row's context, and every page of the pools no row owns, holds NaN: a key
behind a query block's end that reached the result would show.
"""

import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))

from flexflow_tpu.kernels import sparse_attend_chunk as chunk_kernel  # noqa: E402
from flexflow_tpu.ops import attention_ops  # noqa: E402
from flexflow_tpu.ops.registry import STATS_KEY, LoweringCtx  # noqa: E402
from flexflow_tpu.ops.sparse_attention_ops import context_rungs  # noqa: E402
from flexflow_tpu.serving import (positions3_valid_prompt_inputs,  # noqa: E402
                                  positions3_valid_step_inputs)
from flexflow_tpu import telemetry as tel  # noqa: E402
from served import off_by  # noqa: E402
from test_keye_vl import engine_for, reference_logits  # noqa: E402
from test_sparse_attend_step_kernel import (attention_layer,  # noqa: E402
                                            two_devices, wide)

PAGE, PER_SLOT, CHUNK, TOPK = 16, 48, 64, 64
CONTEXT = PAGE * PER_SLOT       # 768 positions a slot: rungs of 192
QB, KB = 32, 128                # two query blocks a chunk, six key blocks
# name: (each row's context before the chunk, the keys a query keeps at most)
CHUNKS = {"first_chunk_under_topk": ([0], TOPK),
          "first_rung": ([64], TOPK),
          "second_rung": ([256], TOPK),
          "third_rung": ([448], TOPK),
          "fourth_rung_to_the_caches_end": ([704], TOPK),
          "a_key_blocks_edge_inside_the_chunk": ([352], TOPK),
          "positions_past_the_caches_end": ([736], TOPK),
          "rows_at_different_positions": ([0, 192, 640], TOPK),
          "fewer_kept_than_topk": ([256], 5)}
# name: (K/V heads, query heads a group, head_dim, pools' type)
SHAPES = {"f32_2x4": (2, 4, 128, jnp.float32),
          "bf16_2x8": (2, 8, 128, jnp.bfloat16)}


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(chunk_kernel, "_QUERY_BLOCK", QB)
    monkeypatch.setattr(chunk_kernel, "_KEY_BLOCK", KB)


def operands(shape, case, seed=0):
    """(qg `[b, s, g, r, d]`, pools, table, t `[b, s]`, the kept mask `[b, s,
    L]`, live `[b]`): scattered tables, at most `kept` keys at random under
    each query's position (all of them where there are fewer; under the
    cache's end where the position is past it), NaN in every page behind a
    row's chunk and in the pages no row owns."""
    g, r, d, dt = SHAPES[shape]
    before, kept = CHUNKS[case]
    b = len(before)
    rng = np.random.default_rng(seed)
    pages = b * PER_SLOT + 9
    k, v = (rng.standard_normal((pages, PAGE, g * d)).astype(np.float32)
            for _ in range(2))
    owned = rng.permutation(np.arange(1, pages))
    table = owned[:b * PER_SLOT].reshape(b, PER_SLOT).astype(np.int32)
    k[owned[b * PER_SLOT:]] = v[owned[b * PER_SLOT:]] = np.nan
    t = np.asarray(before, np.int32)[:, None] + np.arange(CHUNK)[None, :]
    keep = np.zeros((b, CHUNK, CONTEXT), bool)
    for row in range(b):
        for i in range(CHUNK):
            under = min(t[row, i] + 1, CONTEXT)
            keep[row, i, rng.permutation(under)[:kept]] = True
        reach = -(-(before[row] + CHUNK) // PAGE)
        k[table[row, reach:]] = v[table[row, reach:]] = np.nan
    qg = rng.standard_normal((b, CHUNK, g, r, d)).astype(np.float32)
    return (jnp.asarray(qg, dt), jnp.asarray(k, dt), jnp.asarray(v, dt),
            jnp.asarray(table), jnp.asarray(t), jnp.asarray(keep),
            jnp.ones(b, bool))


def attend(qg, k, v, table, t, keep, live, stats=None, mesh=None):
    """`_selected_cache_attention` as the decode twin calls it."""
    ctx = LoweringCtx(state={"serve/active": live.astype(jnp.int32)},
                      stats=stats, mesh=mesh)
    return attention_ops._selected_cache_attention(
        attention_layer(), qg, k, v, table, t, keep,
        1.0 / np.sqrt(qg.shape[-1]), ctx)


def xla_form(qg, k, v, table, t, keep, live, stats=None):
    """The form a program lowered for a mesh keeps. Pools without the NaN
    (it gathers a whole rung's pages and multiplies what it masked by 0)."""
    k, v = (jnp.nan_to_num(x) for x in (k, v))
    return attend(qg, k, v, table, t, keep, live, stats, two_devices())


def said_since(mark):
    """The `sparse_attend/chunk_path` spans' facts since `mark` spans."""
    return [s.args for s in tel.ring_spans()[mark:]
            if s.name == "sparse_attend/chunk_path"]


def tiles_of(case):
    """(the tiles the kernel visits, the tiles of the XLA form's rectangle):
    a query block's key blocks up to its last position; every query block
    against the rung that holds the chunk's last position."""
    before = CHUNKS[case][0]
    visited = sum(-(-min(at + (i + 1) * QB, CONTEXT) // KB)
                  for at in before for i in range(CHUNK // QB))
    rung = next(r for r in context_rungs(PER_SLOT)
                if r * PAGE >= min(max(before) + CHUNK, CONTEXT))
    return visited, len(before) * CHUNK * rung * PAGE / (QB * KB)


@pytest.mark.parametrize("case", list(CHUNKS))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_kernel_against_the_xla_form(shape, case):
    """Every query's row is the XLA form's, and nothing behind a query
    block's end (NaN) reached the result."""
    qg, k, v, table, t, keep, live = operands(shape, case)
    path = attention_ops.chunk_path(qg.shape[-1], PAGE, PER_SLOT, CHUNK,
                                    k.dtype)
    assert path == {"path": "kernel", "query_block": QB, "key_block": KB}
    got = np.asarray(attend(qg, k, v, table, t, keep, live), np.float32)
    want = np.asarray(xla_form(qg, k, v, table, t, keep, live), np.float32)
    assert got.shape == want.shape == qg.shape
    assert np.isfinite(got).all()
    rtol = 1e-5 if k.dtype == jnp.float32 else 2e-2
    assert np.abs(got - want).max() <= rtol * np.abs(want).max()
    assert np.abs(got).max() > 1e-2


@pytest.mark.parametrize("case", ["first_rung",
                                  "fourth_rung_to_the_caches_end",
                                  "positions_past_the_caches_end"])
def test_a_last_key_block_that_is_not_whole(case, monkeypatch):
    """Key blocks of 512 over a context of 768: the second block reaches 256
    positions past the cache's end, and what the kernel is handed there
    (whatever the buffer held) reaches no result."""
    monkeypatch.setattr(chunk_kernel, "_KEY_BLOCK", 512)
    qg, k, v, table, t, keep, live = operands("bf16_2x8", case)
    assert attention_ops.chunk_path(128, PAGE, PER_SLOT, CHUNK, k.dtype) \
        == {"path": "kernel", "query_block": QB, "key_block": 512}
    got = np.asarray(attend(qg, k, v, table, t, keep, live), np.float32)
    want = np.asarray(xla_form(qg, k, v, table, t, keep, live), np.float32)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 2e-2 * np.abs(want).max()


def test_a_query_that_kept_nothing_reads_zeros():
    """No query of a served chunk keeps nothing (the indexer keeps a key of
    every position's own past); the kernel gives such a row zeros, not the
    0 / 0 the division would."""
    qg, k, v, table, t, keep, live = operands("f32_2x4", "second_rung")
    keep = keep.at[0, 7].set(False)
    got = np.asarray(attend(qg, k, v, table, t, keep, live))
    assert np.isfinite(got).all() and not got[0, 7].any()
    assert np.abs(got[0, 6]).max() > 1e-3


@pytest.mark.parametrize("case", ["first_chunk_under_topk", "third_rung",
                                  "a_key_blocks_edge_inside_the_chunk",
                                  "positions_past_the_caches_end",
                                  "rows_at_different_positions"])
def test_the_counters_of_two_layers_on_either_path(case):
    """`sparse_attend_chunk_tiles` = the (query block, key block) tiles up to
    each query block's last position and `sparse_attend_chunk_tiles_dense` =
    the rung's whole rectangle in tiles, summed over layers, on the kernel
    path, both 0 on the XLA path; `kv_bytes_gathered` (the rows under each
    row's last position) equal on both; a lowered layer says its path in a
    span."""
    qg, k, v, table, t, keep, live = operands("f32_2x4", case)
    ours, theirs = {}, {}
    mark = len(tel.ring_spans())
    for _layer in range(2):
        attend(qg, k, v, table, t, keep, live, ours)
        xla_form(qg, k, v, table, t, keep, live, theirs)
    assert said_since(mark) == [
        {"layer": "attn", "path": "kernel", "query_block": QB,
         "key_block": KB},
        {"layer": "attn", "path": "xla"}] * 2
    visited, dense = tiles_of(case)
    assert float(ours["sparse_attend_chunk_tiles"]) == 2 * visited
    assert float(ours["sparse_attend_chunk_tiles_dense"]) == 2 * dense
    assert visited <= dense
    assert float(theirs["sparse_attend_chunk_tiles"]) == 0.0
    assert float(theirs["sparse_attend_chunk_tiles_dense"]) == 0.0
    row = 2 * k.shape[-1] * 4
    assert float(ours["kv_bytes_gathered"]) \
        == float(theirs["kv_bytes_gathered"]) \
        == 2 * row * sum(min(at + CHUNK, CONTEXT) for at in CHUNKS[case][0])


# name: (head_dim, page, pages a slot, chunk, pools' type, mesh) -> the path
PATHS = {
    "the_served_shapes": ((128, 16, 1056, 2048, jnp.bfloat16, None),
                          {"path": "kernel", "query_block": 128,
                           "key_block": 1024}),
    "a_chunk_and_a_context_of_one_tile": (
        (128, 8, 6, 16, jnp.float32, None),
        {"path": "kernel", "query_block": 16, "key_block": 48}),
    "a_tiny_model": ((16, 8, 16, 16, jnp.float32, None), {"path": "xla"}),
    "a_mesh": ((128, 16, 1056, 2048, jnp.bfloat16, two_devices),
               {"path": "xla"}),
    "a_page_not_whole_tiles": ((128, 8, 2112, 2048, jnp.bfloat16, None),
                               {"path": "xla"}),
    "a_chunk_not_whole_query_blocks": (
        (128, 16, 1056, 2000, jnp.bfloat16, None), {"path": "xla"}),
    "a_block_of_five_tokens": ((128, 16, 1056, 5, jnp.bfloat16, None),
                               {"path": "xla"}),
    "a_mask_tile_not_whole_int8_tiles": (
        (128, 16, 1056, 2048 + 16, jnp.bfloat16, None), {"path": "xla"})}


@pytest.mark.parametrize("case", list(PATHS))
def test_chunk_path_from_the_shapes_and_the_mesh(case, monkeypatch):
    """Keye's cell (K/V heads of 128 in bfloat16, pages of 16, 1056 pages a
    slot, chunks of 2048) takes the kernel at the shipped tiles; a tiny
    model's heads, a mesh, a page that is not whole tiles and a chunk that is
    no whole number of query blocks keep the XLA form."""
    monkeypatch.undo()
    (head_dim, page, per_slot, chunk, dt, mesh), path = PATHS[case]
    assert attention_ops.chunk_path(head_dim, page, per_slot, chunk, dt,
                                    mesh() if mesh else None) == path


# ------------------------------------------- through the programs, on logits
def test_prefill_in_chunks_through_the_kernel_then_decode_on_logits():
    """A prompt goes in by chunks of 64 over a slot of 256 positions (two
    query blocks a chunk, two key blocks a context: the kernel), a last chunk
    that is not whole: the first token and the steps after it are the
    reference's full forward's, and a chunk's counters say which tiles it
    visited."""
    g, cfg = wide(seq=256)          # a slot's whole context: 16 pages
    eng = engine_for(g, chunk=CHUNK, page=PAGE)
    assert eng.kv_spec.pages_per_slot * PAGE == 256
    kv, rng = eng.kv, np.random.default_rng(7)
    prompt = [int(x) for x in rng.integers(0, g.vocab, 64 * 3 + 21)]
    slot = 1
    mark = len(tel.ring_spans())
    kv.admit(slot, len(prompt), len(prompt) + 16, prefilling=True)
    kv.push()
    for done in range(0, len(prompt), CHUNK):
        part = prompt[done:done + CHUNK]
        ids = np.zeros((1, CHUNK), np.int32)
        ids[0, :len(part)] = part
        lengths, context = np.asarray([len(part)]), np.asarray([done])
        tok, state = eng.prefill_chunk(
            eng.params, kv.state,
            positions3_valid_prompt_inputs(ids, lengths, context),
            kv.prefill_row(slot)[None], context, lengths)
        stats = state.pop(STATS_KEY)
        kv.adopt(state)
        # two layers, two query blocks: up to positions done + 32, done + 64
        assert float(stats["sparse_attend_chunk_tiles"]) \
            == 2 * sum(-(-(done + n) // KB) for n in (QB, CHUNK))
    said = said_since(mark)
    assert said and all(s["path"] == "kernel" for s in said)
    kv.activate(slot, len(prompt))
    kv.push()

    def reference_row(seq):
        return np.asarray(reference_logits(
            eng.params, cfg, np.asarray([seq], np.int32)))[0, -1]

    seq = list(prompt) + [int(np.asarray(tok)[0])]
    assert seq[-1] == int(reference_row(prompt).argmax())
    for _step in range(3):
        nxt = np.zeros((eng.slots, 1), np.int32)
        nxt[slot, 0] = seq[-1]
        logits, state = eng.decode_step(
            eng.params, kv.state,
            positions3_valid_step_inputs(jnp.asarray(nxt), kv.state))
        state.pop(STATS_KEY)
        kv.adopt(state)
        kv.sync_after(1)
        row = np.asarray(logits)[slot, 0]
        assert off_by(row, reference_row(seq)) <= 1e-4
        seq.append(int(row.argmax()))
