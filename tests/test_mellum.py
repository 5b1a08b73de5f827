"""Mellum decoders (flexflow_tpu/models/mellum.py: attention under a window
by position on three layers of four and over the whole context with YaRN on
the fourth in ops/attention_ops.py and ops/rotary.py, the two cache kernels
with bounds by position in kernels/sparse_attend_step.py and
sparse_attend_chunk.py, a ring of the window's pages a slot beside the full
layers' pages in serving/kv_cache.py, prefill in chunks over both in
serving/engine.py) against the plain reference
(benchmarks/harness/reference_mellum.py), at a small size on the CPU with
seeded random weights, a window of 8 and rings that every prompt laps.

Tolerance: program and reference both compute in float32, so they differ by
the order of their sums alone: about 1e-6 of the result's scale. RTOL 1e-4
leaves two orders for that and none for a fault: a key seen that the
reference hides moves a logit row by 1e-2 and more (the wrong-model tests).
The kernels against their XLA forms: 1e-5 in float32 (the same softmax in
another order), 2e-2 in bfloat16 (the XLA form scores in bfloat16).
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
from flexflow_tpu.core.layer import Layer  # noqa: E402
from flexflow_tpu.core.tensor import Tensor, TensorSpec  # noqa: E402
from flexflow_tpu.dtype import DataType  # noqa: E402
from flexflow_tpu.kernels import sparse_attend_chunk as chunk_kernel  # noqa: E402
from flexflow_tpu.kernels import sparse_attend_step as step_kernel  # noqa: E402
from flexflow_tpu.models import MellumConfig, build_mellum  # noqa: E402
from flexflow_tpu.ops import attention_ops, rotary  # noqa: E402
from flexflow_tpu.ops.op_type import OperatorType  # noqa: E402
from flexflow_tpu.ops.registry import STATS_KEY, LoweringCtx  # noqa: E402
from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,  # noqa: E402
                                  compile_serving,
                                  positions_valid_prompt_inputs,
                                  positions_valid_step_inputs)
from flexflow_tpu.serving.program import page_geometry  # noqa: E402
from families import mellum as family  # noqa: E402
from harness import flops_mellum as flops  # noqa: E402
from harness import manifest as mf  # noqa: E402
from harness import reference_mellum as reference  # noqa: E402
from served import off_by  # noqa: E402

RTOL = 1e-4
SLOTS = 4
PUBLISHED = "Mellum2-12B-A2.5B-Instruct"
CELL = "Mellum2-12B-A2.5B-Instruct.serve-longprompt"


def ffconfig(batch, **kw):
    return FFConfig(batch_size=batch, seed=3, strategy_cache=False,
                    log_level="warning", mesh_shape={"data": 1}, **kw)


def tiny_file(**changed) -> dict:
    return dict(mf.read_named("configs", "mellum-tiny"), **changed)


def text_positions(ids):
    return np.ascontiguousarray(np.broadcast_to(
        np.arange(ids.shape[1], dtype=np.int32), ids.shape))


def reference_logits(params, cfg, ids, **switches):
    hp = dict(family.hyper(cfg), **switches)
    ids = np.asarray(ids)
    return reference.forward(family.reference_params(params, cfg), ids,
                             text_positions(ids), hp)


def compiled(g, batch=4, **kw):
    m = FFModel(ffconfig(batch, **kw))
    build_mellum(m, g, batch=batch)
    cm = m.compile(SGDOptimizer(lr=1.0),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=3)
    return cm


def engine_for(g, seed=3, chunk=16, max_decode_len=12, page=4, **kw):
    model = FFModel(ffconfig(SLOTS, serve_prefill_chunk=chunk, **kw))
    build_mellum(model, g, batch=SLOTS)
    eng = compile_serving(model, max_batch_slots=SLOTS,
                          max_decode_len=max_decode_len, kv_page_size=page)
    eng.init(seed=seed)
    return eng


# ------------------------------------------------------------------- rotary
def test_yarns_tables_are_the_references_and_plain_at_factor_one():
    pos = jnp.asarray(np.arange(0, 4000, 37, dtype=np.int32)[None])
    for hd, theta, scaling in (
            (16, 5e5, {"factor": 4.0, "original_max_position_embeddings": 16,
                       "beta_fast": 32.0, "beta_slow": 1.0}),
            (128, 5e5, {"factor": 16.0,
                        "original_max_position_embeddings": 8192,
                        "beta_fast": 32.0, "beta_slow": 1.0,
                        "attention_factor": 1.2772588722239782})):
        yarn = (scaling["factor"], scaling["original_max_position_embeddings"],
                scaling["beta_fast"], scaling["beta_slow"],
                scaling.get("attention_factor"))
        f = reference.frequencies(hd, theta, yarn)
        assert np.allclose(rotary.yarn_inv_freq(
            hd, theta, scaling["factor"],
            scaling["original_max_position_embeddings"]), f, rtol=1e-12)
        cos, sin = rotary.half_tables(pos, hd, theta, scaling=scaling)
        m = reference.attention_factor(yarn)
        angle = np.asarray(pos, np.float32)[..., None] * np.tile(f, 2).astype(
            np.float32)
        assert np.allclose(np.asarray(cos), np.cos(angle) * m, atol=2e-6)
        assert np.allclose(np.asarray(sin), np.sin(angle) * m, atol=2e-6)
        # the far pairs turn `factor` times slower, the near ones as before
        plain = reference.frequencies(hd, theta)
        assert f[0] == plain[0] and np.isclose(f[-1],
                                               plain[-1] / scaling["factor"])
    one = {"factor": 1.0, "original_max_position_embeddings": 16}
    a = rotary.half_tables(pos, 16, 5e5, scaling=one)
    b = rotary.half_tables(pos, 16, 5e5)
    assert all((np.asarray(x) == np.asarray(y)).all() for x, y in zip(a, b))
    assert rotary.yarn_attention_factor({"factor": 16.0}) \
        == pytest.approx(0.1 * np.log(16.0) + 1.0)


def test_the_two_correction_indices_at_the_published_keys():
    """floor(18.08) and ceil(34.98) of the 64 pairs, by the rule's formula;
    the latent attention op reads the same function (one, two readers)."""
    from flexflow_tpu.ops import latent_attention_ops as mla
    assert rotary.yarn_correction_range(128, 500000.0, 8192, 32, 1) == (18, 35)
    assert reference.correction_indices(128, 500000.0, 8192, 32, 1) == (18, 35)
    assert mla.yarn_inv_freq is rotary.yarn_inv_freq
    cfg = mf.read_named("configs", PUBLISHED)
    full = cfg["rope_parameters"]["full_attention"]
    assert full["attention_factor"] == pytest.approx(0.1 * np.log(16) + 1)
    f = rotary.yarn_inv_freq(128, 500000.0, 16.0, 8192)
    plain = rotary.inv_freq(128, 500000.0)
    assert (f[:19] == plain[:19]).all() and np.allclose(f[35:],
                                                        plain[35:] / 16)
    assert (f[19:35] < plain[19:35]).all() and (f[19:35]
                                                > plain[19:35] / 16).all()


# ------------------------------------------------------------ the whole model
def test_the_tiny_file_is_the_programs_tiny_config():
    g, cfg = MellumConfig.tiny(seq=128), tiny_file()
    assert family.program_config(cfg) == g
    assert g.window == 8 and g.layer_types.count("full_attention") == 1


def test_forward_logits_and_gradients_against_the_reference():
    g, cfg = MellumConfig.tiny(seq=40), tiny_file()
    cm = compiled(g)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, g.vocab, (4, 40)).astype(np.int32)
    pos = text_positions(ids)
    got = cm.forward(ids, pos, np.ones_like(ids))
    assert got.shape == (4, g.seq, g.vocab)
    assert off_by(got, reference_logits(cm.params, cfg, ids)) < RTOL
    # the window and YaRN do their work: another window, or plain tables on
    # the full layer, give other logits
    assert off_by(got, reference_logits(cm.params, cfg, ids, window=9)) \
        > 100 * RTOL
    assert off_by(got, reference_logits(cm.params, cfg, ids, window=64)) \
        > 100 * RTOL
    assert off_by(got, reference_logits(cm.params, cfg, ids, yarn_on=False)) \
        > 100 * RTOL
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
    for layer in ("embed", "l0_attn", "l2_attn", "l3_attn", "l0_moe",
                  "l3_moe", "lm_head", "l0_norm_op"):
        for w in got_g[layer]:
            assert off_by(got_g[layer][w], want_g[layer][w]) < 10 * RTOL, \
                (layer, w)


def test_the_references_blocks_and_slices_change_no_logit(monkeypatch):
    """The reference in blocks of 12 queries over 40 positions (a last block
    that is not whole; a windowed layer's block slices its keys: 40 > 8 +
    12) against the reference whole."""
    g, cfg = MellumConfig.tiny(seq=40), tiny_file()
    cm = compiled(g)
    ids = np.random.default_rng(9).integers(0, g.vocab, (4, 40)).astype(
        np.int32)
    want = np.asarray(reference_logits(cm.params, cfg, ids))
    jax.clear_caches()
    monkeypatch.setattr(reference, "QUERY_BLOCK", 12)
    assert off_by(reference_logits(cm.params, cfg, ids), want) < RTOL
    jax.clear_caches()


def test_a_window_over_the_context_is_plain_causal_attention():
    """With `window` at or over the sequence every layer sees every s <= t:
    the logits are those of the same weights with every layer full, plain
    tables on the three that were windowed."""
    g = MellumConfig.tiny(seq=40)
    g.window = 64
    cm = compiled(g)
    ids = np.random.default_rng(2).integers(0, g.vocab, (4, 40)).astype(
        np.int32)
    got = cm.forward(ids, text_positions(ids), np.ones_like(ids))
    cfg = tiny_file(sliding_window=64)
    assert off_by(got, reference_logits(cm.params, cfg, ids)) < RTOL
    # the same through the reference with no window at all
    assert off_by(got, reference_logits(cm.params, cfg, ids, window=10 ** 6)) \
        < RTOL


@pytest.mark.parametrize("kind", MellumConfig().layer_types[2:4])
def test_a_layer_of_each_kind_alone(kind):
    g = MellumConfig.tiny(seq=40)
    g.layer_types = (kind,)
    cfg = tiny_file(layer_types=[kind], mlp_layer_types=["sparse"],
                    num_hidden_layers=1)
    cm = compiled(g)
    ids = np.random.default_rng(4).integers(0, g.vocab, (4, 40)).astype(
        np.int32)
    got = cm.forward(ids, text_positions(ids), np.ones_like(ids))
    assert off_by(got, reference_logits(cm.params, cfg, ids)) < RTOL
    other = [k for k in ("sliding_attention", "full_attention") if k != kind]
    wrong = dict(cfg, layer_types=other)
    assert off_by(got, reference_logits(cm.params, wrong, ids)) > 100 * RTOL


def test_a_layer_without_a_window_lowers_to_what_it_lowered_to():
    """`window` and `rope_scaling` enter the params only where set."""
    m = FFModel(ffconfig(2))
    x = m.create_tensor([2, 8, 32], name="x")
    pos = m.create_tensor([2, 8], DataType.INT32, name="p")
    m.multihead_attention(x, x, x, 32, 2, positions=pos, causal=True,
                          name="plain")
    m.multihead_attention(x, x, x, 32, 2, positions=pos, causal=True,
                          window=0, name="full")
    plain, full = (m.get_layer_by_name(n).params for n in ("plain", "full"))
    assert "window" not in plain and "rope_scaling" not in plain
    assert full["window"] == 0
    with pytest.raises(NotImplementedError, match="window"):
        m.multihead_attention(x, x, x, 32, 2, window=4, name="not_causal")
    with pytest.raises(ValueError, match="window"):
        m.multihead_attention(x, x, x, 32, 2, causal=True, window=-1)


# --------------------------------------------------- the kernels, by position
PAGE, HEAD = 16, 128
# name: (K/V heads, query heads a group, pools' type)
SHAPES = {"f32_2x4": (2, 4, jnp.float32), "bf16_2x8": (2, 8, jnp.bfloat16)}
# name: (window, table entries a slot, each slot's position t). A ring of 5
# pages = 80 positions; positions past 80 lap it (at 395: four times)
STEPS = {"full_table": (0, 12, [5, 0, 16, 127, 128, 191]),
         "ring_not_lapped": (24, 5, [5, 0, 15, 23, 24, 40]),
         "ring_lapped": (24, 5, [79, 80, 81, 163, 255, 395]),
         "ring_window_of_a_block": (64, 9, [63, 64, 100, 143, 144, 700]),
         "ring_two_blocks": (200, 18, [199, 200, 287, 288, 1000, 5000])}


def two_devices():
    return jax.sharding.Mesh(np.array(jax.devices()[:2]), ("model",))


def bounded_layer(window):
    x = Tensor(TensorSpec((1, 1, 64), DataType.FLOAT), name="x")
    return Layer(OperatorType.MULTIHEAD_ATTENTION, {"window": window},
                 [x, x, x], name="attn")


def attend(qg, k, v, table, t, window, live, stats=None, mesh=None,
           lengths=None):
    state = {"serve/active": live.astype(jnp.int32)}
    if lengths is not None:
        state[attention_ops.BLOCK_LENGTHS_KEY] = lengths
    ctx = LoweringCtx(state=state, stats=stats, mesh=mesh)
    return attention_ops._bounded_cache_attention(
        bounded_layer(window), qg, k, v, table, t, window,
        1.0 / np.sqrt(qg.shape[-1]), ctx)


def pools_with(shape, rows, per_slot, positions, window, seed):
    """Pools and scattered tables whose entries hold what a slot that wrote
    positions 0 .. `positions[row][-1]` in order would have left (a ring:
    the newest page of each entry), NaN in every page of the pools that no
    row's query may see (a kernel that fetched one would show)."""
    g, r, dt = SHAPES[shape]
    rng = np.random.default_rng(seed)
    pages = rows * per_slot + 1
    k, v = (rng.standard_normal((pages, PAGE, g * HEAD)).astype(np.float32)
            for _ in range(2))
    table = rng.permutation(np.arange(1, pages)).reshape(
        rows, per_slot).astype(np.int32)
    for row in range(rows):
        last = int(positions[row][-1])
        first = max(int(positions[row][0]) - window + 1, 0) if window else 0
        seen = {(n % per_slot if window else n)
                for n in range(first // PAGE, last // PAGE + 1)}
        for e in set(range(per_slot)) - seen:
            k[table[row, e]] = v[table[row, e]] = np.nan
    k[0] = v[0] = np.nan
    return jnp.asarray(k, dt), jnp.asarray(v, dt), jnp.asarray(table)


@pytest.fixture
def small_blocks(monkeypatch):
    """A step's key block of 8 pages, a chunk's tiles of 32 x 128."""
    monkeypatch.setattr(step_kernel, "_BLOCK_TOKENS", 128)
    monkeypatch.setattr(chunk_kernel, "_QUERY_BLOCK", 32)
    monkeypatch.setattr(chunk_kernel, "_KEY_BLOCK", 128)


@pytest.mark.parametrize("live", [[], [4], [0, 2, 5], list(range(6))],
                         ids=["none", "one", "some", "all"])
@pytest.mark.parametrize("case", list(STEPS))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_step_kernel_by_position_against_the_xla_form(
        shape, case, live, small_blocks):
    """Every live slot's rows are the XLA form's under the same bounds; a
    slot that is not live reads zeros; no page outside `first // page ..
    t // page` of the slot's table or ring (NaN) reached the result."""
    window, per_slot, at = STEPS[case]
    g, r, dt = SHAPES[shape]
    t = np.asarray(at, np.int32)[:, None]
    k, v, table = pools_with(shape, 6, per_slot, t, window, seed=len(live))
    qg = jnp.asarray(np.random.default_rng(1).standard_normal(
        (6, 1, g, r, HEAD)), dt)
    mask = np.zeros(6, bool)
    mask[live] = True
    path = attention_ops.step_path(HEAD, PAGE, per_slot, k.dtype)
    assert path["path"] == "kernel"
    ours, theirs = {}, {}
    got = np.asarray(attend(qg, k, v, table, jnp.asarray(t), window,
                            jnp.asarray(mask), ours), np.float32)
    want = np.asarray(attend(
        qg, jnp.nan_to_num(k), jnp.nan_to_num(v), table, jnp.asarray(t),
        window, jnp.asarray(mask), theirs, two_devices()), np.float32)
    assert np.isfinite(got).all() and not got[~mask].any()
    if mask.any():
        rtol = 1e-5 if dt == jnp.float32 else 2e-2
        assert np.abs(got[mask] - want[mask]).max() \
            <= rtol * np.abs(want[mask]).max()
        assert np.abs(got[mask]).max() > 1e-2
    # the counters: rows needed on either path; whole pages on the kernel's,
    # never more than the rows needed and a page at each end
    kind = "window" if window else "full"
    row = 2 * g * HEAD * k.dtype.itemsize
    first = np.maximum(t[:, 0] - window + 1, 0) if window else 0 * t[:, 0]
    rows = (t[:, 0] - first + 1)[mask]
    pages = (t[:, 0] // PAGE - first // PAGE + 1)[mask]
    assert float(ours[f"{kind}_kv_bytes_needed"]) \
        == float(theirs[f"{kind}_kv_bytes_needed"]) == row * rows.sum()
    assert float(ours[f"{kind}_keys_seen"]) == rows.sum()
    assert float(ours[f"{kind}_kv_bytes_streamed"]) == row * PAGE * pages.sum()
    assert float(theirs[f"{kind}_kv_bytes_streamed"]) == 0.0
    assert (pages * PAGE <= rows + 2 * (PAGE - 1)).all()


# name: (window, table entries a slot, each row's context before the chunk)
CHUNKS = {"full_first_chunk": (0, 24, [0]),
          "full_behind_a_context": (0, 24, [200]),
          "full_rows_apart": (0, 24, [0, 64, 320]),
          "ring_first_chunk": (40, 8, [0]),
          "ring_lapped_twice": (40, 8, [300]),
          "ring_rows_apart": (40, 8, [0, 64, 1000]),
          "ring_window_of_key_blocks": (200, 18, [500, 7])}
CHUNK = 64


@pytest.mark.parametrize("case", list(CHUNKS))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_chunk_kernel_by_position_against_the_xla_form(
        shape, case, small_blocks):
    window, per_slot, before = CHUNKS[case]
    g, r, dt = SHAPES[shape]
    b = len(before)
    t = np.asarray(before, np.int32)[:, None] + np.arange(CHUNK)[None, :]
    k, v, table = pools_with(shape, b, per_slot, t, window, seed=b)
    qg = jnp.asarray(np.random.default_rng(2).standard_normal(
        (b, CHUNK, g, r, HEAD)), dt)
    live = jnp.ones(b, bool)
    lengths = jnp.asarray([CHUNK] + [CHUNK - 7] * (b - 1), jnp.int32)
    path = attention_ops.chunk_path(HEAD, PAGE, per_slot, CHUNK, k.dtype)
    assert path == {"path": "kernel", "query_block": 32, "key_block": 128}
    ours, theirs = {}, {}
    mark = len(tel.ring_spans())
    got = np.asarray(attend(qg, k, v, table, jnp.asarray(t), window, live,
                            ours, lengths=lengths), np.float32)
    want = np.asarray(attend(
        qg, jnp.nan_to_num(k), jnp.nan_to_num(v), table, jnp.asarray(t),
        window, live, theirs, two_devices(), lengths), np.float32)
    assert np.isfinite(got).all()
    rtol = 1e-5 if dt == jnp.float32 else 2e-2
    assert np.abs(got - want).max() <= rtol * np.abs(want).max()
    kind = "window" if window else "full"
    said = [s.args for s in tel.ring_spans()[mark:]
            if s.name == f"{kind}_attend/chunk_path"]
    assert [a["path"] for a in said] == ["kernel", "xla"]
    assert said[0]["window"] == window and said[0]["layer"] == "attn"
    # the tiles: a query block's key blocks from the one that holds its
    # first query's first key to the one that holds its last position
    base = (np.maximum(t[:, 0] - window + 1, 0) // PAGE * PAGE) \
        if window else 0 * t[:, 0]
    tiles = 0
    for row in range(b):
        for q0 in range(0, CHUNK, 32):
            lo = max(t[row, q0] - window + 1, 0) if window else 0
            tiles += (t[row, q0 + 31] - base[row]) // 128 \
                - (lo - base[row]) // 128 + 1
    assert int(ours[f"{kind}_attend_chunk_tiles"]) == tiles
    assert int(ours[f"{kind}_attend_chunk_tiles_dense"]) \
        == b * 2 * -(-per_slot * PAGE // 128)
    assert int(theirs[f"{kind}_attend_chunk_tiles"]) == 0
    seen = sum(int((np.minimum(t[row, :n] + 1, window) if window
                    else t[row, :n] + 1).sum())
               for row, n in enumerate(np.asarray(lengths)))
    assert float(ours[f"{kind}_keys_seen"]) \
        == float(theirs[f"{kind}_keys_seen"]) == seen
    assert float(ours[f"{kind}_kv_bytes_streamed"]) \
        == b * per_slot * PAGE * 2 * g * HEAD * k.dtype.itemsize


def test_the_paths_at_the_served_shapes():
    """The cell's pools: K/V heads of 128 in bfloat16, pages of 16; a full
    layer's 1056 pages a slot and a windowed layer's ring of 193."""
    assert attention_ops.step_path(128, 16, 1056, jnp.bfloat16) \
        == {"path": "kernel", "block_pages": 64}
    assert attention_ops.step_path(128, 16, 193, jnp.bfloat16) \
        == {"path": "kernel", "block_pages": 64}
    for per_slot in (1056, 193):
        assert attention_ops.chunk_path(128, 16, per_slot, 2048,
                                        jnp.bfloat16) \
            == {"path": "kernel", "query_block": 128, "key_block": 1024}
    assert attention_ops.step_path(16, 4, 7, jnp.float32) == {"path": "xla"}


# ------------------------------------------------------------- through caches
class Chunked:
    """Drives `engine.prefill_chunk` and `decode_step` by hand: a prompt in
    chunks into its slot's pages and ring, then steps, every logits row
    against the reference's full forward."""

    def __init__(self, eng, cfg, rtol=RTOL):
        self.eng, self.cfg, self.seqs, self.rtol = eng, cfg, {}, rtol
        self.checked = 0
        self.counters = []

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
                positions_valid_prompt_inputs(ids, lengths, context),
                kv.prefill_row(slot)[None], context, lengths)
            stats = state.pop(STATS_KEY)
            kv.adopt(state)
            self.counters.append((done, len(part), stats))
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
                positions_valid_step_inputs(jnp.asarray(nxt), kv.state))
            self.step_stats = state.pop(STATS_KEY)
            kv.adopt(state)
            kv.sync_after(1)
            logits = np.asarray(logits)
            for slot, seq in self.seqs.items():
                want = np.asarray(reference_logits(
                    eng.params, self.cfg, np.asarray([seq], np.int32)))[0, -1]
                assert off_by(logits[slot, 0], want) <= self.rtol, \
                    (slot, len(seq))
                self.checked += 1
                seq.append(int(logits[slot, 0].argmax()))


def test_prefill_in_two_and_in_three_chunks_then_decode_through_both_pools():
    """Chunks of 16, pages of 4, a window of 8: the ring is ceil((8 + 16) /
    4) + 1 = 7 pages = 28 positions, so a prompt of 29 laps it once and one
    of 61 (four chunks) twice; a prompt of 41 goes in by three chunks with a
    decode step of the live slots between two of them; every step's logits
    are the reference's full forward over the slot's tokens, and the counters
    are the reckoned ones."""
    g, cfg = MellumConfig.tiny(seq=80), tiny_file()
    eng = engine_for(g, chunk=16, max_decode_len=12)
    assert eng.chunk_tokens == 16 and eng.kv_spec.padded_len == 80
    assert eng.kv.state_kinds == "paged_kv+paged_kv_ring"
    assert eng.kv_spec.window_pages == 7 and eng.kv_spec.window_layers == 3
    assert eng.kv_spec.layers == 1
    rng = np.random.default_rng(11)

    def prompt(n):
        return [int(t) for t in rng.integers(0, g.vocab, n)]

    c = Chunked(eng, cfg)
    assert c.prefill(0, prompt(29)) == 2
    c.decode(2)
    assert c.prefill(2, prompt(41), steps_between=1) == 3
    assert c.prefill(1, prompt(61), steps_between=1) == 4
    c.decode(8)
    assert c.checked == 2 + 3 * 1 + 4 * 2 + 8 * 3
    row = 2 * 2 * 16 * 4       # K and V, 2 heads of 16, float32
    for done, n, stats in c.counters:
        at = done + np.arange(n)
        assert float(stats["window_keys_seen"]) \
            == 3 * np.minimum(at + 1, 8).sum()
        assert float(stats["full_keys_seen"]) == (at + 1).sum()
        last = done + 16 - 1        # the block's last position, padding too
        assert float(stats["full_kv_bytes_needed"]) == row * (last + 1)
        assert float(stats["window_kv_bytes_needed"]) \
            == 3 * row * (last - max(done - 7, 0) + 1)
        assert int(stats["moe_held_pairs"]) == 4 * 2 * n
    live = [len(seq) - 1 for seq in c.seqs.values()]    # the last step's t + 1
    assert float(c.step_stats["full_kv_bytes_needed"]) == row * sum(live)
    assert float(c.step_stats["window_kv_bytes_needed"]) \
        == 3 * row * sum(min(n, 8) for n in live)


def test_an_evicted_slots_ring_does_not_reach_its_next_owner():
    """A slot's ring is the slot's for good and keeps what its last occupant
    wrote; the next owner's chunks and steps read none of it: its logits are
    bit for bit those of the same request on a fresh engine. The full
    layers' pages go back to the free list, the ring's never."""
    g, cfg = MellumConfig.tiny(seq=80), tiny_file()
    rng = np.random.default_rng(13)
    first = [int(t) for t in rng.integers(0, g.vocab, 63)]
    second = [int(t) for t in rng.integers(0, g.vocab, 22)]

    def serve(eng, prompt, steps):
        c = Chunked(eng, cfg)
        c.prefill(0, prompt)
        c.decode(steps)
        return c.seqs[0]

    used = engine_for(g)
    free = len(used.kv.free_pages)
    serve(used, first, 6)
    rows = np.asarray(used.kv._rings[0])
    stale = np.asarray(used.kv.state["l0_attn"]["k"])[rows].copy()
    assert np.abs(stale).min(axis=(1, 2)).max() > 0     # every entry written
    assert (np.asarray(used.kv.state["serve/window_table"])[0] == rows).all()
    used.kv.evict(0)
    used.kv.push()
    assert len(used.kv.free_pages) == free
    assert not np.asarray(used.kv.state["serve/window_table"]).any()
    again = serve(used, second, 4)
    assert (np.asarray(used.kv._rings[0]) == rows).all()
    fresh = serve(engine_for(g), second, 4)
    assert again == fresh


def test_admission_counts_the_full_layers_pages_and_the_pools_are_reckoned():
    g = MellumConfig.tiny(seq=80)
    eng = engine_for(g)
    kv, spec = eng.kv, eng.kv_spec
    assert spec.pages_per_slot == 20 and spec.pool_pages == 4 * 20 + 1
    assert spec.window_pool_pages == 4 * 7 + 1
    assert kv.state["l3_attn"]["k"].shape == (81, 4, 32)
    assert kv.state["l0_attn"]["k"].shape == (29, 4, 32)
    assert len(kv.free_pages) == 80 and kv.pages_needed(33) == 9
    kv.admit(1, 30, 33)
    assert len(kv.free_pages) == 80 - 9
    page = 2 * 4 * 32 * 4
    assert spec.layer_bytes() == 81 * page
    assert spec.window_bytes() == 3 * 29 * page
    assert spec.total_bytes() == 81 * page + 3 * 29 * page == kv.device_bytes()
    assert spec.one_extent_bytes() == 4 * 81 * page
    want = flops.pool_bytes(tiny_file(), 4, 80, 28)
    assert (spec.layers * spec.layer_bytes() - page,
            spec.window_bytes() - 3 * page,
            spec.one_extent_bytes() - 4 * page) \
        == (2 * want["full"], 2 * want["window"], 2 * want["one_extent"])
    args = [s for s in tel.ring_spans()
            if s.name == "serve/compile_serving"][-1].args
    assert (args["kv_pool_bytes_full"], args["kv_pool_bytes_window"],
            args["kv_pool_bytes_one_extent"], args["window_ring_pages"],
            args["kv_pool_pages_full"], args["kv_pool_pages_window"],
            args["window"]) == (81 * page, 3 * 29 * page, 4 * 81 * page, 7,
                                81, 29, 8)
    assert args["rope_theta"] == 500000.0 and args["qk_norm"] is True
    # the decode search prices a windowed layer at its ring, a full one at
    # the context
    from flexflow_tpu.search.candidates import layer_candidates
    from flexflow_tpu.serving.program import _decode_cost_fn
    machine = eng.machine
    cost = _decode_cost_fn(machine, spec.layer_bytes(), kv_spec=spec)
    by_name = {l.name: l for l in eng.decode_model.layers}
    costs = {n: cost(by_name[n],
                     layer_candidates(by_name[n], machine, {SLOTS})[0])
             for n in ("l0_attn", "l3_attn")}
    assert costs["l3_attn"] - costs["l0_attn"] == pytest.approx(
        (spec.layer_bytes() - spec.window_layer_bytes()) / machine.hbm_bw)


def test_what_the_window_does_not_support_raises_by_name():
    g = MellumConfig.tiny(seq=64)
    for kw, what in (({"kv_host_pages": 8}, "host KV tier"),
                     ({"kv_cache_dtype": "int8"}, "quantized")):
        m = FFModel(ffconfig(SLOTS, serve_prefill_chunk=16, **kw))
        build_mellum(m, g, batch=SLOTS)
        with pytest.raises(NotImplementedError,
                           match=f"window of 8 positions.*{what}"):
            compile_serving(m, max_batch_slots=SLOTS, max_decode_len=8)
    m = FFModel(ffconfig(SLOTS))
    build_mellum(m, g, batch=SLOTS)
    with pytest.raises(NotImplementedError, match="by chunks"):
        compile_serving(m, max_batch_slots=SLOTS, max_decode_len=8)
    draft = FFModel(ffconfig(SLOTS, serve_prefill_chunk=16))
    build_mellum(draft, g, batch=SLOTS)
    m = FFModel(ffconfig(SLOTS, serve_prefill_chunk=16))
    build_mellum(m, g, batch=SLOTS)
    with pytest.raises(NotImplementedError, match="speculative"):
        compile_serving(m, max_batch_slots=SLOTS, max_decode_len=8,
                        draft=draft, spec_tokens=2)
    eng = engine_for(g)
    eng.kv.admit(0, 10, 20)
    for call, what in ((lambda: eng.kv.spill(0, 0), "spill"),
                       (lambda: eng.kv.export_parked(0), "export_parked"),
                       (lambda: eng.kv.import_parked(1, {"pages": 1}),
                        "import_parked")):
        with pytest.raises(NotImplementedError, match=f"{what}.*ring"):
            call()
    with pytest.raises(NotImplementedError, match="ring"):
        eng.kv.commit_prefill({}, [0], [4])
    # two windows in one model
    m = FFModel(ffconfig(SLOTS))
    x = m.create_tensor([SLOTS, 8, 32], name="x")
    pos = m.create_tensor([SLOTS, 8], DataType.INT32, name="p")
    for w in (4, 6):
        m.multihead_attention(x, x, x, 32, 2, positions=pos, causal=True,
                              window=w, name=f"w{w}")
    with pytest.raises(NotImplementedError, match="one window a model"):
        page_geometry(m)


# ------------------------------------------------------------ the scheduler
def test_the_scheduler_serves_it_in_chunks_through_both_pools():
    """Nine requests through ContinuousBatchingScheduler on four slots (slots
    and their rings are reused): every served token is the reference's
    argmax; the spans carry the counters the benchmark's readers take."""
    g, cfg = MellumConfig.tiny(seq=80), tiny_file()
    eng = engine_for(g, chunk=16, max_decode_len=12)
    rng = np.random.default_rng(0)
    shapes = [(40, 10), (17, 12), (68, 6), (33, 8), (5, 9), (48, 12),
              (30, 5), (61, 7), (69, 4)]
    reqs = [Request(rid=i, prompt=[int(t) for t in
                                   rng.integers(1, g.vocab, n)],
                    max_new_tokens=k, arrival_s=0.0)
            for i, (n, k) in enumerate(shapes)]
    sched = ContinuousBatchingScheduler(
        eng, eng.params, positions_valid_prompt_inputs,
        positions_valid_step_inputs, eos_id=None)
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
    assert len(eng.kv.free_pages) == 80     # the full layers' pages are back
    waits = [s for s in spans if s.name == "serve/prefill/device_wait"]
    syncs = [s for s in spans if s.name == "serve/decode/window_sync"]
    assert len(waits) == sum(-(-n // 16) for n, _ in shapes[:8]) and syncs
    counters = [f"{kind}_{what}" for kind in ("window", "full")
                for what in ("kv_bytes_needed", "kv_bytes_streamed",
                             "keys_seen")]
    for s in waits + syncs:
        for name in counters + ["moe_experts_hit", "moe_held_pairs"]:
            assert name in s.args, name
    for s in waits:
        assert "window_attend_chunk_tiles" in s.args
        assert "full_attend_chunk_tiles_dense" in s.args
    for s in syncs:
        assert 0 < s.args["window_kv_bytes_needed"] \
            <= 3 * s.args["full_kv_bytes_needed"]
    paths = {s.name: s.args["path"] for s in tel.ring_spans()
             if s.name.endswith(("_attend/step_path", "_attend/chunk_path"))}
    assert paths == {"window_attend/step_path": "xla",
                     "window_attend/chunk_path": "xla",
                     "full_attend/step_path": "xla",
                     "full_attend/chunk_path": "xla"}


def test_both_programs_carry_the_two_scopes():
    from flexflow_tpu import attribution
    g = MellumConfig.tiny(seq=64)
    eng = engine_for(g)
    c = Chunked(eng, tiny_file())
    c.prefill(0, [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 3])
    c.decode(1)
    for program in ("serve/prefill", "serve/decode"):
        both = set()
        for scope in (attention_ops.WINDOW_SCOPE, attention_ops.FULL_SCOPE):
            found = [names for names in
                     attribution.instructions_under(program, scope) if names]
            assert found, (program, scope)
            both |= set.union(*found)
        outer = set.union(*attribution.instructions_under(
            program, attention_ops.BOUNDED_SCOPE))
        assert both and both <= outer


# ----------------------------------------- the kernels through the programs
def test_chunks_then_decode_at_a_head_of_128_through_the_kernels():
    """One period at a head of 128 and pages of 8 (whole float32 tiles), the
    kernels interpreted in both programs: prefill in three chunks of 32,
    then decode, against the reference on logits; a window of 24 in a ring
    of (24 + 32) / 8 + 1 = 8 pages that the prompt laps."""
    g = MellumConfig.tiny(seq=128)
    g.head_dim, g.heads, g.kv_heads, g.window = 128, 2, 1, 24
    cfg = tiny_file(head_dim=128, num_attention_heads=2,
                    num_key_value_heads=1, sliding_window=24)
    mark = len(tel.ring_spans())
    eng = engine_for(g, chunk=32, max_decode_len=12, page=8)
    assert eng.kv_spec.window_pages == 8
    rng = np.random.default_rng(5)
    c = Chunked(eng, cfg)
    assert c.prefill(1, [int(t) for t in rng.integers(0, g.vocab, 90)]) == 3
    c.decode(3)
    assert c.checked == 3
    paths = {(s.name, s.args["path"]) for s in tel.ring_spans()[mark:]
             if s.name.endswith(("_attend/step_path", "_attend/chunk_path"))}
    assert paths == {("window_attend/step_path", "kernel"),
                     ("window_attend/chunk_path", "kernel"),
                     ("full_attend/step_path", "kernel"),
                     ("full_attend/chunk_path", "kernel")}
    # a step streams the window's pages and a page at each end at most
    row = 2 * 128 * 4
    stats = c.step_stats
    assert float(stats["window_kv_bytes_streamed"]) \
        <= float(stats["window_kv_bytes_needed"]) + 3 * 2 * 8 * row
    assert float(stats["window_kv_bytes_streamed"]) == 3 * 4 * 8 * row
    assert float(stats["full_kv_bytes_streamed"]) == -(-92 // 8) * 8 * row


# ------------------------------------------------------- counts and the file
def test_the_configuration_file_against_the_catalog_and_the_issue():
    cfg = mf.read_named("configs", PUBLISHED)
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "mlp_layer_types"]
    assert cfg["published"]["num_hidden_layers"] == 28
    built = cfg["num_hidden_layers"]
    assert built in (12, 8)
    assert cfg["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * (built // 4)
    assert cfg["mlp_layer_types"] == ["sparse"] * built
    widths = {"hidden_size": 2304, "head_dim": 128, "num_attention_heads": 32,
              "num_key_value_heads": 4, "moe_intermediate_size": 896,
              "num_experts": 64, "num_experts_per_tok": 8,
              "vocab_size": 98304, "sliding_window": 1024,
              "intermediate_size": 7168, "max_position_embeddings": 131072,
              "n_embd": 2304, "n_head": 32}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["rope_parameters"]["full_attention"] == {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
    for key in ("source", "deployment", "departures", "assumed", "why"):
        assert cfg[key]
    for key in ("qk_norm", "rotary", "router", "yarn", "window"):
        assert cfg["assumed"][key]
    assert cfg["assumed"]["serve_positions"] == 16896
    assert cfg["assumed"]["yarn_correction_indices"] == [18, 35]
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(r for r in map(json.loads, catalog.read_text().splitlines())
                   if r["name"] == PUBLISHED)
        assert cfg["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == set(cfg["reduced"])


def test_the_cells_files_hold_the_issues_parameters():
    tr = mf.read_named("traffic", "serve-longprompt")
    system = mf.read_named("workloads", CELL)
    assert (system["max_batch_slots"], system["max_decode_len"],
            system["kv_page_size"]) == (16, 512, 16)
    assert system["ffconfig"] == {"compute_dtype": "bfloat16",
                                  "mesh_shape": {"data": 1},
                                  "serve_prefill_chunk": 2048}
    assert set(system["traffic"]) <= {"rate_rps", "parity_requests"}
    cfg = mf.read_named("configs", PUBLISHED)
    assert tr["prompt_len"]["max"] + system["max_decode_len"] \
        == cfg["assumed"]["serve_positions"]
    assert tr["prompt_len"]["min"] >= 4 * cfg["sliding_window"]
    manifest = mf.load_manifest()
    cell = mf.load_cell(manifest, CELL)
    assert cell.chips == 1 and cell.config["family"] == "mellum"
    assert cell.traffic["rate_rps"] == system["traffic"]["rate_rps"]
    names = {m["name"] for m in cell.per_layer}
    ours = [m["name"] for m in manifest["per_layer"]
            if m["name"].endswith(".mellum")]
    assert len(ours) == 14 and set(ours) <= names
    for name in ours:
        spec = mf.read_named("metrics", name)
        need = spec["args"].get("need")
        assert need is None or hasattr(flops, need), name
    for name in ("wave_attention_device_ms", "wave_experts_device_ms",
                 "decode_attention_device_ms_per_step",
                 "decode_experts_device_ms_per_step",
                 "moe_rows_computed_share.prefill",
                 "prefill_useful_token_share", "queue_wait_p95_ms"):
        assert name in names, name
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}


@pytest.mark.parametrize("name", (PUBLISHED, "mellum-tiny"))
def test_flop_and_byte_functions_against_the_program(name):
    cfg = mf.read_named("configs", name)
    g = family.program_config(cfg)
    assert flops.train_flops_per_token(cfg, g.seq) == g.flops_per_token()
    assert flops.param_count(cfg) == g.param_count()
    assert flops.cache_bytes_per_token(cfg) == g.cache_bytes_per_token()
    m = FFModel(ffconfig(2))
    build_mellum(m, g, batch=2)
    assert sum(spec.num_elements for l in m.layers
               for spec in l.weight_specs.values()) == g.param_count()
    assert page_geometry(m) == {"heads": g.kv_heads, "head_dim": g.head_dim,
                                "window": g.window}


def test_the_issues_arithmetic():
    cfg = mf.read_named("configs", PUBLISHED)
    published = dict(cfg, num_hidden_layers=28)
    assert flops.param_count(published) == 12149923072 \
        == MellumConfig().param_count()
    assert flops.attention_matmul_params(cfg) == 21233664
    assert 64 * flops.expert_params(cfg) == 396361728
    assert flops.layer_dense_params(cfg) + 64 * flops.expert_params(cfg) \
        == 417747712
    assert round(flops.matmul_params_per_token(published) / 1e9, 1) == 2.2
    twelve = dict(cfg, num_hidden_layers=12, layer_types=(
        ["sliding_attention"] * 3 + ["full_attention"]) * 3)
    assert flops.param_count(twelve) == 5465959680
    pools = flops.pool_bytes(twelve, 16, 16896, 3088)
    assert pools == {"full": 3 * 2048 * 16 * 16896,
                     "window": 9 * 2048 * 16 * 3088,
                     "one_extent": 12 * 2048 * 16 * 16896}
    assert round((pools["full"] + pools["window"]) / pools["one_extent"],
                 2) == 0.39
    # the ring: ceil((1024 + 2048) / 16) + 1 pages
    assert -(-(1024 + 2048) // 16) + 1 == 193 and 193 * 16 == 3088
    system = mf.read_named("workloads", CELL)
    traffic = mf.read_named("traffic", "serve-longprompt")
    # a decode step at 6 live slots of 9 k context at twelve layers: the hit
    # experts are most of it, the windowed layers' K/V a third of the full's
    live, context = 6, 9000
    counters = {"moe_routed_pairs": 12 * 8 * live, "moe_experts_hit": 12 * 34,
                "window_kv_bytes_needed": 9 * live * 1024 * 2048,
                "full_kv_bytes_needed": 3 * live * context * 2048}
    need = flops.decode_step_need(twelve, system, traffic, counters)
    assert 0.7 < 12 * 34 * 12386304 / need["bytes"] < 0.85
    assert flops.window_attend_need(twelve, system, traffic, dict(
        counters, steps=2))["bytes"] == 9 * live * 1024 * 2048 / 2
    # a whole chunk of 2048 tokens behind a context of 6144
    at = 6144 + np.arange(2048)
    chunk = {"moe_held_pairs": 12 * 8 * 2048,
             "window_keys_seen": 9 * 2048 * 1024,
             "full_keys_seen": 3 * int((at + 1).sum())}
    total = flops.prefill_chunk_need(twelve, system, traffic, chunk)["flops"]
    attend = flops.chunk_attend_need(twelve, system, traffic, chunk)["flops"]
    routed = 2 * chunk["moe_held_pairs"] * flops.expert_params(cfg)
    assert 0.5 < routed / total < 0.62 and 0.1 < attend / total < 0.25
