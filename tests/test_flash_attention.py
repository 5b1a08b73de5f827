"""Flash-attention kernel numerics vs the reference einsum path.

Reference capability: fused cuDNN attention (src/ops/attention.cu:35). On the
CPU test mesh the pallas kernels run in interpreter mode; on TPU they compile.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels.flash_attention import (entry_of, flash_attention,
                                                  flash_attention_merged,
                                                  flash_attention_qkv)


def _merge(x):
    """(b, h, s, d) -> [b, s, h * d], as a projection writes it."""
    b, h, s, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b, s, h * d)


def _split(x, heads):
    b, s, e = x.shape
    return jnp.swapaxes(x.reshape(b, s, heads, e // heads), 1, 2)


def _through(entry):
    """`attend(q, k, v (b, h, s, d), **kw) -> (b, h, s, d)` through one of
    the kernels' entries: `swapped` reads the operands as they are given,
    `merged` (d = 128) and `two_heads` (d = 64) read them merged, the head
    (or pair of heads) the block index along the lanes. The shape has to
    be one that takes the entry (`entry_of`)."""
    if entry == "swapped":
        return flash_attention

    def attend(q, k, v, **kw):
        assert entry_of(q.shape[3], q.shape[1], k.shape[1]) == entry
        return _split(flash_attention_merged(_merge(q), _merge(k), _merge(v),
                                             q.shape[1], **kw), q.shape[1])

    return attend


def _reference(q, k, v, causal, scale):
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = logits.shape[-2:]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v)


@pytest.mark.parametrize("causal,entry,h", [
    (False, "two_heads", 4), (True, "swapped", 3)])
def test_forward_matches_einsum(causal, entry, h):
    """Without a mask through two heads a 128-lane block (a width-64
    encoder's path), causal through the swapped entry (three heads are no
    pairs)."""
    rng = np.random.default_rng(0)
    b, s, d = 2, 256, 64
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    out = _through(entry)(q, k, v, causal=causal)
    ref = _reference(q, k, v, causal, 1.0 / np.sqrt(d))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("entry,d", [("swapped", 32), ("merged", 128)])
def test_cross_attention_lengths(entry, d):
    """sq != sk, through the swapped entry and from the projections' own
    layout: q's blocks and the keys' follow their own lengths."""
    rng = np.random.default_rng(1)
    b, h = 2, 2
    q = jnp.asarray(rng.normal(size=(b, h, 128, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, 256, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, 256, d)), jnp.float32)
    out = _through(entry)(q, k, v, causal=False)
    ref = _reference(q, k, v, False, 1.0 / np.sqrt(d))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal,entry,d", [
    (False, "two_heads", 64), (True, "swapped", 32)])
def test_gradients_match_einsum(causal, entry, d):
    rng = np.random.default_rng(2)
    b, h, s = 1, 2, 128
    flash_attention = _through(entry)
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_reference(q, k, v, causal, scale) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-4, rtol=5e-4)


def test_unsupported_shapes_raise():
    q = jnp.zeros((1, 1, 100, 32))  # 100 not divisible by any block
    with pytest.raises(ValueError):
        flash_attention(q, q, q)
    q2 = jnp.zeros((1, 1, 128, 32))
    k2 = jnp.zeros((1, 1, 256, 32))
    with pytest.raises(ValueError):
        flash_attention(q2, k2, k2, causal=True)  # causal needs sq == sk


def test_qkv_layout_wrapper():
    rng = np.random.default_rng(3)
    b, s, h, d = 2, 128, 2, 32
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    out = flash_attention_qkv(q, q, q, causal=True)
    assert out.shape == (b, s, h, d)
    ref = jnp.swapaxes(
        _reference(jnp.swapaxes(q, 1, 2), jnp.swapaxes(q, 1, 2),
                   jnp.swapaxes(q, 1, 2), True, 1.0 / np.sqrt(d)), 1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_mha_layer_uses_flash():
    """FFModel MHA with impl='flash' matches impl='xla' end to end."""
    from flexflow_tpu import FFConfig, FFModel

    rng = np.random.default_rng(4)
    x = rng.normal(size=(2, 128, 64)).astype(np.float32)
    outs = {}
    for impl in ("xla", "flash"):
        cfg = FFConfig(batch_size=2)
        m = FFModel(cfg)
        t = m.create_tensor((2, 128, 64), name="x")
        y = m.multihead_attention(t, t, t, embed_dim=64, num_heads=2,
                                  causal=True, impl=impl, name="attn")
        cm = m.compile(loss_type="mean_squared_error")
        cm.init(seed=0)
        outs[impl] = np.asarray(cm.forward(x))
    np.testing.assert_allclose(outs["flash"], outs["xla"], atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal,entry", [(False, "merged"), (True, "swapped")])
def test_head_dim_128_parity(causal, entry):
    """Satellite (round-5 MFU note): the block-shape ceiling was sized for
    head_dim 64 — head_dim 128 must pick a depth-aware block (512-row f32
    blocks would double the per-operand VMEM footprint) and still match
    the einsum reference in fwd AND grads."""
    from flexflow_tpu.kernels.flash_attention import _pick_block

    # f32 head_dim 128 drops the 512 block; bf16 keeps it; d=64 unchanged
    assert _pick_block(512, 64, 4) == 512
    assert _pick_block(512, 128, 4) == 256
    assert _pick_block(512, 128, 2) == 512

    rng = np.random.default_rng(5)
    b, h, s, d = 1, 2, 256, 128
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    scale = 1.0 / np.sqrt(d)
    attend = _through(entry)
    out = attend(q, k, v, causal=causal)
    ref = _reference(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=5e-5)

    def f_flash(q, k, v):
        return jnp.sum(attend(q, k, v, causal=causal) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_reference(q, k, v, causal, scale) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("causal,entry", [
    (False, "swapped"), (True, "two_heads")])
def test_head_dim_64_tile_rule_parity(causal, entry):
    """ISSUE 36: the VMEM budget bounds a tile from above (narrow heads up
    to 1024 rows) and a causal call picks under it: never the whole causal
    square where a smaller tile divides the sequence, so the tiles above
    the diagonal are skipped. A non-causal call keeps the bound. Forward
    and gradients match the einsum reference at the tiles the rule takes."""
    from flexflow_tpu.kernels.flash_attention import (KERNELS, _pick_block,
                                                      _tiles)

    # the bound, as before: d=64 at seq 1024 may take the 1024 block
    assert _pick_block(1024, 64, 4) == 1024
    # the d=128 pins of the round-5 retune still hold
    assert _pick_block(512, 64, 4) == 512
    assert _pick_block(512, 128, 4) == 256
    assert _pick_block(512, 128, 2) == 512
    for kern in KERNELS:
        for itemsize in (2, 4):
            bq, bk = _tiles(kern, 1024, 1024, 64, itemsize, causal)
            if causal:
                assert max(bq, bk) < 1024 and 1024 % bq == 0 == 1024 % bk
            else:
                assert (bq, bk) == (1024, 1024)
        # a sequence of one candidate tile has no smaller tile to take
        assert _tiles(kern, 128, 128, 64, 2, causal) == (128, 128)

    rng = np.random.default_rng(6)
    b, h, s, d = 1, 2, 1024, 64
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    scale = 1.0 / np.sqrt(d)
    attend = _through(entry)
    out = attend(q, k, v, causal=causal)
    ref = _reference(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=5e-5)

    def f_flash(q, k, v):
        return jnp.sum(attend(q, k, v, causal=causal) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_reference(q, k, v, causal, scale) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-3, rtol=2e-3)


TILES = (128, 256, 512, 1024)


@pytest.mark.parametrize("kernel,seq,depth,itemsize,tile", [
    ("fwd", 1024, 128, 2, 512), ("dq", 1024, 128, 2, 512),
    ("dkv", 1024, 128, 2, 512),          # granite, Nemotron
    ("fwd", 1024, 192, 2, 256),          # GigaChat
    ("fwd", 1024, 128, 4, 256), ("dkv", 1024, 128, 4, 256)])
def test_wide_heads_keep_the_parents_tiles(kernel, seq, depth, itemsize, tile):
    """Head widths over 64 take the tile they took before ISSUE 36, causal
    or not: the budget's bound, which at seq 1024 never spans the square."""
    from flexflow_tpu.kernels.flash_attention import _pick_block, _tiles

    assert _pick_block(seq, depth, itemsize) == tile
    assert _tiles(kernel, seq, seq, depth, itemsize, True) == (tile, tile)
    assert _tiles(kernel, seq, seq, depth, itemsize, False) == (tile, tile)


def test_block_overrides_still_force_a_tile(monkeypatch):
    """The two tuning overrides win over the causal rule (a sweep may ask
    for the whole square), each for its own kernels."""
    from flexflow_tpu.kernels.flash_attention import _tiles

    monkeypatch.setenv("FLEXFLOW_FLASH_BLOCK", "1024")
    assert _tiles("fwd", 1024, 1024, 64, 2, True) == (1024, 1024)
    assert _tiles("dq", 1024, 1024, 64, 2, True) == (1024, 1024)
    monkeypatch.setenv("FLEXFLOW_FLASH_BLOCK_BWD", "128")
    assert _tiles("fwd", 1024, 1024, 64, 2, True) == (1024, 1024)
    assert _tiles("dkv", 1024, 1024, 64, 2, True) == (128, 128)
    monkeypatch.setenv("FLEXFLOW_FLASH_BLOCK_BWD", "100")     # unusable
    assert _tiles("dkv", 1024, 1024, 64, 2, True) == (1024, 1024)


@pytest.mark.parametrize("seq", [1024, 640, 384])
def test_visited_tiles_are_those_with_an_unmasked_pair(seq):
    """The loop bounds, for every pair of tiles that divide the sequence:
    the tiles a kernel visits are exactly those holding a pair at or under
    the diagonal, and the ones it masks exactly those the diagonal
    crosses, whichever of q and k is tiled finer."""
    from flexflow_tpu.kernels.flash_attention import (KERNELS, _k_tile_bounds,
                                                      _q_tile_bounds,
                                                      _schedule)

    under = np.tril(np.ones((seq, seq), bool))
    tiles = [t for t in TILES if seq % t == 0]
    assert tiles
    for bq in tiles:
        for bk in tiles:
            cells = under.reshape(seq // bq, bq, seq // bk, bk)
            some, every = cells.any(axis=(1, 3)), cells.all(axis=(1, 3))
            want = (int(some.sum()), int((some & ~every).sum()), some.size)
            for kern in KERNELS:
                assert _schedule(kern, seq, seq, bq, bk, True) == want
                assert _schedule(kern, seq, seq, bq, bk, False) == \
                    (some.size, 0, some.size)
            for i in range(seq // bq):
                full, visit = _k_tile_bounds(i * bq, bq, bk)
                assert list(every[i]) == [j < full for j in range(seq // bk)]
                assert list(some[i]) == [j < visit for j in range(seq // bk)]
            for j in range(seq // bk):
                first, full = _q_tile_bounds(j * bk, bq, bk)
                assert list(some[:, j]) == [i >= first for i in range(seq // bq)]
                assert list(every[:, j]) == [i >= full for i in range(seq // bq)]


def _three_kernels(q, k, v, g, scale, bq, bk, entry="swapped"):
    """The three calls at tiles (bq, bk), operands (b, h, s, d) handed to
    the entry's form; `lse` and `delta` `(b, h, 1, s)`, the sequence on the
    lanes, whatever the entry."""
    from flexflow_tpu.kernels.flash_attention import (_dkv_call, _dq_call,
                                                      _fwd_call)

    heads = 0 if entry == "swapped" else q.shape[1]
    form = {"heads": heads}
    if heads:
        assert entry_of(q.shape[3], heads, k.shape[1]) == entry
        q, k, v, g = (_merge(x) for x in (q, k, v, g))
    o, lse = _fwd_call(q, k, v, True, scale, bq, bk, **form)
    dq, delta = _dq_call(q, k, v, g, o, lse, True, scale, bq, bk, **form)
    dk, dv = _dkv_call(q, k, v, g, lse, delta, True, scale, bq, bk, **form)
    assert lse.shape == delta.shape == (o.shape[0], heads or o.shape[1], 1,
                                        o.shape[1 if heads else 2])
    if heads:
        o, dq, dk, dv = (_split(x, heads) for x in (o, dq, dk, dv))
    return o, lse, dq, dk, dv


@pytest.mark.parametrize("seq,bq,bk,entry", [
    (1024, 128, 128, "swapped"), (1024, 256, 128, "two_heads"),
    (1024, 128, 256, "swapped"), (1024, 512, 256, "two_heads"),
    (1024, 256, 512, "swapped"), (384, 128, 128, "two_heads")])
def test_unequal_tiles_match_einsum_at_head_dim_64(seq, bq, bk, entry):
    """Each of the three kernels at its own (bq, bk), causal, d = 64:
    forward and both gradients against einsum + mask + softmax, to the
    tolerances of the d = 64 parity test above; through the swapped entry
    and two heads a 128-lane block by turns."""
    rng = np.random.default_rng(7)
    b, h, d = 1, 2, 64
    q, k, v, g = (jnp.asarray(rng.normal(size=(b, h, seq, d)), jnp.float32)
                  for _ in range(4))
    scale = 1.0 / np.sqrt(d)
    o, _lse, dq, dk, dv = _three_kernels(q, k, v, g, scale, bq, bk, entry)
    ref, vjp = jax.vjp(lambda q, k, v: _reference(q, k, v, True, scale),
                       q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               atol=5e-5, rtol=5e-5)
    for a, b_ in zip((dq, dk, dv), vjp(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-3, rtol=2e-3)


def test_a_sequence_of_several_blocks_loops_over_the_earlier_ones():
    """A causal sequence longer than the block a grid step holds (384 = 3
    x 128: only 128 divides it): a step meets the key blocks before its
    own whole, in a loop, and carries the statistics into its own block.
    Through the public entry, forward and gradients (two heads a block at
    this length: the unequal-tiles case above)."""
    rng = np.random.default_rng(9)
    b, h, s, d = 1, 2, 384, 64
    q, k, v = (jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
               for _ in range(3))
    scale = 1.0 / np.sqrt(d)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True)),
        np.asarray(_reference(q, k, v, True, scale)), atol=5e-5, rtol=5e-5)
    g_flash = jax.grad(lambda *a: jnp.sum(flash_attention(*a, causal=True) ** 2),
                       argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: jnp.sum(_reference(*a, True, scale) ** 2),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("bq,bk", [(256, 128), (128, 256), (256, 256)])
def test_rows_that_meet_only_diagonal_tiles_normalise(bq, bk):
    """The first q tile visits no tile wholly under the diagonal, and with
    bk < bq its upper rows are wholly masked in the later tiles: every row
    still divides by its own sum. Row 0 attends key 0 alone."""
    rng = np.random.default_rng(8)
    b, h, s, d = 1, 1, 512, 64
    q, k, v = (jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
               for _ in range(3))
    scale = 1.0 / np.sqrt(d)
    from flexflow_tpu.kernels.flash_attention import _fwd_call

    o, lse = _fwd_call(q, k, v, True, scale, bq, bk)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(lse)).all()
    logits = np.einsum("qd,kd->qk", np.asarray(q[0, 0]), np.asarray(k[0, 0])) * scale
    logits = np.where(np.tril(np.ones((s, s), bool)), logits, -np.inf)
    want = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) \
        + logits.max(-1)
    np.testing.assert_allclose(np.asarray(lse[0, 0, 0, :]), want,
                               atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(np.asarray(o[0, 0, 0]), np.asarray(v[0, 0, 0]),
                               atol=1e-6)


def test_lowering_span_carries_the_tile_plan_and_trace_report_prints_it():
    """ISSUE 36: a lowered call leaves one `lower/flash_attention` span
    with, per kernel, its tile and the visited / total tile counts, and
    tools/trace_report.py prints one line a shape."""
    import os
    import sys

    from flexflow_tpu import telemetry as tel
    from flexflow_tpu.kernels.flash_attention import KERNELS, _schedule

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import trace_report

    since = tel.ring_spans()[-1].end_ns if tel.ring_spans() else 0
    q = jnp.zeros((2, 3, 512, 64), jnp.bfloat16)
    jax.eval_shape(lambda q: flash_attention(q, q, q, causal=True), q)
    spans = [s for s in tel.ring_spans("lower/flash_attention")
             if s.start_ns >= since]
    assert len(spans) == 1
    args = spans[0].args
    assert (args["batch_heads"], args["seq_q"], args["depth"],
            args["causal"]) == (6, 512, 64, True)
    # how the operands and the rows' statistics lie (PR 63): this entry
    # takes (b, h, s, d); the projections' own layout says what it took
    assert (args["entry"], args["residual"]) == ("swapped", "lanes")
    wide = jnp.zeros((2, 512, 4, 128), jnp.bfloat16)
    jax.eval_shape(lambda q: flash_attention_qkv(q, q, q, causal=True), wide)
    assert tel.ring_spans("lower/flash_attention")[-1].args["entry"] \
        == "merged"
    for kern in KERNELS:
        got = args["kernels"][kern]
        bq, bk = got["flash_tile_q"], got["flash_tile_k"]
        assert max(bq, bk) < 512
        assert (got["flash_tiles_visited"], got["flash_tiles_masked"],
                got["flash_tiles_total"]) == _schedule(kern, 512, 512, bq,
                                                       bk, True)
        assert got["flash_tiles_visited"] < got["flash_tiles_total"]
    events = [{"ph": "X", "name": s.name, "args": s.args} for s in spans] * 2
    lines = trace_report.flash_attention_lines(events)
    assert len(lines) == 1
    assert lines[0].startswith("[lower] flash attention x2 [6, 512x512, 64] "
                               "causal swapped/lanes: fwd ")
    fwd = args["kernels"]["fwd"]
    assert (f"{fwd['flash_tiles_visited']} of {fwd['flash_tiles_total']} "
            f"visited ({fwd['flash_tiles_masked']} masked)") in lines[0]
    assert trace_report.flash_attention_lines(
        [{"ph": "X", "name": "serve/admit", "args": {"wave": 1}}]) == []


def test_vmem_reject_falls_back_to_reference_path():
    """A shape past the VMEM-resident budget raises ValueError at TRACE
    time (the graceful Mosaic-reject precheck), and the MHA auto path
    swallows it — the layer still lowers, via the einsum reference."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.kernels.flash_attention import flash_supported

    # seq * depth past the k/v-resident budget: supported == False and the
    # kernel refuses up front
    assert not flash_supported(8192, 128, 4)
    q = jnp.zeros((1, 1, 8192, 128), jnp.float32)
    with pytest.raises(ValueError):
        flash_attention(q, q, q)

    # auto mode: the same shape inside an MHA layer falls back silently
    cfg = FFConfig(batch_size=1)
    m = FFModel(cfg)
    t = m.create_tensor((1, 8192, 128), name="x")
    m.multihead_attention(t, t, t, embed_dim=128, num_heads=1,
                          causal=True, name="attn")
    cm = m.compile(loss_type="mean_squared_error")
    cm.init(seed=0)
    out = cm.forward(np.zeros((1, 8192, 128), np.float32))
    assert np.asarray(out).shape == (1, 8192, 128)


def _banded_reference(q, k, v, window):
    """Causal attention under `window` (0: none) as the masked XLA form
    computes it: q [b, h, s, d], k/v [b, kv heads, s, d]."""
    s, group = q.shape[2], q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    at = jnp.arange(s)
    seen = at[None, :] <= at[:, None]
    if window:
        seen &= at[None, :] > at[:, None] - window
    probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@pytest.mark.parametrize("seq,window,heads,kv_heads,entry", [
    # under a block: the band crosses the own block
    (512, 100, 2, 1, "merged"),
    # no multiple of the tile; whole, crossed, skipped
    (1024, 300, 2, 2, "swapped"),
    (1024, 511, 4, 2, "merged"),        # one short of two blocks
    (512, 512, 2, 2, "swapped"),        # window == seq: the plain causal call
    (512, 1000, 2, 1, "merged"),        # window > seq
])
def test_a_window_matches_the_masked_form(seq, window, heads, kv_heads, entry):
    """Forward, dq and dk/dv of the three kernels (interpret mode) under a
    window against the masked XLA form, at grouped K/V heads too (a group's
    dk, dv summed by lane slabs on the merged axis); blocks of 256 at
    head_dim 128 in float32, so 1024 positions are four grid steps."""
    import importlib

    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    keys = jax.random.split(jax.random.PRNGKey(seq + window), 4)
    q = jax.random.normal(keys[0], (1, heads, seq, 128))
    k = jax.random.normal(keys[1], (1, kv_heads, seq, 128))
    v = jax.random.normal(keys[2], (1, kv_heads, seq, 128))
    ct = jax.random.normal(keys[3], q.shape)
    band = window if window < seq else 0

    attend = _through(entry)

    def flash(q, k, v):
        return jnp.sum(attend(q, k, v, causal=True, window=window) * ct)

    def masked(q, k, v):
        return jnp.sum(_banded_reference(q, k, v, band) * ct)

    np.testing.assert_allclose(
        attend(q, k, v, causal=True, window=window),
        _banded_reference(q, k, v, band), atol=2e-5)
    for got, want in zip(jax.grad(flash, (0, 1, 2))(q, k, v),
                         jax.grad(masked, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(got, want, atol=2e-4)
    plan = fa.tile_plan(seq, seq, 128, 4, True, band)
    plain = fa.tile_plan(seq, seq, 128, 4, True)
    for kernel in fa.KERNELS:
        if band and seq > 512:      # key blocks wholly before the band
            assert plan[kernel]["flash_tiles_visited"] \
                < plain[kernel]["flash_tiles_visited"]
        if not band:
            assert plan[kernel] == plain[kernel]


def test_what_a_window_cannot_be_raises():
    import importlib

    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    q = jnp.zeros((1, 3, 256, 64))
    with pytest.raises(ValueError, match="not causal"):
        fa.flash_attention(q, q, q, causal=False, window=8)
    with pytest.raises(ValueError, match="K/V heads"):
        fa.flash_attention(q, q[:, :2], q[:, :2], causal=True)


def equations(jaxpr):
    """Every equation of a jaxpr, through every sub-jaxpr."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub)


def kernel_calls(jaxpr):
    """{kernel name: `pallas_call` equations}."""
    return dict(collections.Counter(
        e.params["name"] for e in equations(jaxpr)
        if e.primitive.name == "pallas_call"))


def named(jaxpr, name):
    """The shapes of what `checkpoint_name` tagged `name`."""
    return [e.outvars[0].aval.shape for e in equations(jaxpr)
            if e.primitive.name == "name" and e.params["name"] == name]


@pytest.mark.parametrize("window,heads,kv_heads,shards", [
    (0, 2, 2, 1), (50, 2, 2, 1), (0, 2, 1, 1), (50, 4, 2, 1), (50, 4, 2, 2)])
def test_a_checkpoint_that_keeps_the_residuals_runs_the_forward_kernel_once(
        window, heads, kv_heads, shards):
    """PR 61: the forward rule names `o` and `lse` `FLASH_KEPT`. Under a
    `jax.checkpoint` whose policy keeps that name the gradient's program
    holds `ff_flash_attention_fwd` once (twice under one that does not),
    and dq, dk, dv are the un-checkpointed call's to the bit; so too where
    the call is a shard's, its heads split over a mesh as `per_shard` in
    `ops/attention_ops.py` splits them (the name is inside the shard's
    function). `lse` is named flat: `(b, h, s, 1)` would be padded to 128
    lanes where it is kept."""
    import functools
    import importlib

    from jax.sharding import Mesh, PartitionSpec

    from flexflow_tpu.kernels.partition import per_shard

    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    seq, depth = 128, 64
    keys = jax.random.split(jax.random.PRNGKey(window + heads), 4)
    q = jax.random.normal(keys[0], (1, heads, seq, depth))
    k = jax.random.normal(keys[1], (1, kv_heads, seq, depth))
    v = jax.random.normal(keys[2], (1, kv_heads, seq, depth))
    ct = jax.random.normal(keys[3], q.shape)
    by_head = PartitionSpec(None, "model")
    attend = per_shard(
        functools.partial(fa.flash_attention, causal=True, window=window),
        Mesh(np.array(jax.devices()[:shards]), ("model",)),
        (by_head, by_head, by_head), by_head)

    def total(q, k, v):
        return jnp.sum(attend(q, k, v) * ct)

    keeps = jax.checkpoint_policies.save_only_these_names(fa.FLASH_KEPT)
    plain = jax.grad(total, (0, 1, 2))
    kept = jax.grad(jax.checkpoint(total, policy=keeps), (0, 1, 2))
    again = jax.grad(jax.checkpoint(total), (0, 1, 2))
    once = {"ff_flash_attention_fwd": 1, "ff_flash_attention_dq": 1,
            "ff_flash_attention_dkv": 1}
    traced = jax.make_jaxpr(kept)(q, k, v).jaxpr
    assert kernel_calls(traced) == once
    assert kernel_calls(jax.make_jaxpr(plain)(q, k, v).jaxpr) == once
    assert kernel_calls(jax.make_jaxpr(again)(q, k, v).jaxpr) \
        == dict(once, ff_flash_attention_fwd=2)
    assert sorted(set(named(traced, fa.FLASH_KEPT))) \
        == [(1, heads // shards, 1, seq), (1, heads // shards, seq, depth)]
    # a call that is not differentiated names nothing
    assert not named(jax.make_jaxpr(total)(q, k, v).jaxpr, fa.FLASH_KEPT)
    for got, want in zip(jax.jit(kept)(q, k, v), jax.jit(plain)(q, k, v)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("norm,turn,dt,tol,shape", [
    (True, True, jnp.float32, 1e-4, (2, 48, 16)),
    (True, False, jnp.bfloat16, 2.0 ** -7, (2, 48, 16)),
    (False, True, jnp.bfloat16, 2.0 ** -7, (1, 16, 2)),
    (True, True, jnp.bfloat16, 2.0 ** -5, (1, 16, 2))])
def test_the_head_turn_on_the_merged_axis_is_the_ops_own(norm, turn, dt, tol,
                                                         shape):
    """kernels/head_turn.py (PR 63) against what it replaces where a head is
    whole lanes: `rms_norm` a head, then `apply_rope_half`, on `[b, s, h,
    d]`: the value, d x and d gamma, the same steps (the kernel
    interpreted): in float32 to rounding, in bfloat16 to one unit in the
    last place (a product and a sum contracted or not; and with both a norm
    and a turn the kernel keeps the head float32 between them, as the chip's
    compiler does with the two ops, where this CPU rounds it to bfloat16). Two sequences of 48 positions and 16 heads are a grid of 2 x 3 x
    2 (rows in blocks of 16, eight heads a step): the tables' block follows
    the batch and the row block and stays put over the head steps, and d
    gamma is begun at the first head step of each row block and added to at
    the second."""
    from flexflow_tpu.kernels import head_turn as ht
    from flexflow_tpu.kernels.head_turn import head_turn
    from flexflow_tpu.ops.norm_ops import rms_norm
    from flexflow_tpu.ops.rotary import apply_rope_half, half_tables

    (b, s, h), d = shape, 128
    rows, step = ht._tiles(s, h, d)
    assert (b, s // rows, h // step) == ((2, 3, 2) if b == 2 else (1, 1, 1))
    rng = np.random.default_rng(11)
    cos, sin = half_tables(jnp.asarray(rng.integers(0, 4000, (b, s))), d, 1e4)
    signed = sin * jnp.where(jnp.arange(d) < d // 2, -1.0, 1.0)
    ct = jnp.asarray(rng.normal(size=(b, s, h * d)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(b, s, h * d)), dt)
    gamma = jnp.asarray(1 + 0.1 * rng.normal(size=(d,)), dt)

    def ops(x, gamma):
        y = x.reshape(b, s, h, d)
        y = rms_norm(y, gamma, 1e-6) if norm else y
        y = apply_rope_half(y, cos[:, :, None], sin[:, :, None]) \
            if turn else y
        return jnp.sum(y.reshape(x.shape).astype(jnp.float32) * ct)

    def kernel(x, gamma):
        y = head_turn(x, gamma if norm else None, cos if turn else None,
                      signed if turn else None, h, 1e-6)
        return jnp.sum(y.astype(jnp.float32) * ct)

    if turn and b > 1:      # a table broadcast over the batch is refused
        with pytest.raises(ValueError, match="a table"):
            head_turn(x, None, cos[:1], signed[:1], h, 1e-6)
    want = jax.value_and_grad(ops, (0, 1))(x, gamma)
    got = jax.value_and_grad(kernel, (0, 1))(x, gamma)
    for a, b_ in zip(jax.tree_util.tree_leaves(got),
                     jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32),
                                   atol=tol, rtol=tol)


def _flash_call_values(jaxpr):
    """The avals that enter or leave a flash kernel call of a jaxpr."""
    return [x.aval for e in equations(jaxpr)
            if e.primitive.name == "pallas_call"
            and e.params["name"].startswith("ff_flash_attention")
            for x in list(e.invars) + list(e.outvars)]


@pytest.mark.parametrize("entry,heads,kv_heads,depth", [
    ("swapped", 16, 16, 192), ("merged", 32, 4, 128), ("two_heads", 16, 16, 64)])
def test_no_lane_sparse_statistic_enters_or_leaves_a_flash_call(
        entry, heads, kv_heads, depth, monkeypatch):
    """PR 63: in the jaxpr of value-and-gradient no float32 value of shape
    `[.., 1]` (a number a 128-lane tile on the chip: `lse` and `delta` as
    they lay) enters or leaves one of the three kernels, whatever the
    entry; the two statistics are `(b, h, 1, s)`, `delta` is made by the dq
    kernel and read by the dk/dv kernel, and a call nobody differentiates
    writes no `lse` at all. Traced for the chip, nothing runs."""
    import importlib

    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    b, s = 2, 1024
    q = jax.ShapeDtypeStruct((b, s, heads, depth), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, s, kv_heads, depth), jnp.bfloat16)
    assert fa.entry_of(depth, heads, kv_heads) == entry

    def total(q, k, v):
        return jnp.sum(fa.flash_attention_qkv(q, k, v, causal=True)
                       .astype(jnp.float32))

    traced = jax.make_jaxpr(jax.value_and_grad(total, (0, 1, 2)))(q, kv, kv)
    values = _flash_call_values(traced.jaxpr)
    assert kernel_calls(traced.jaxpr) == {
        "ff_flash_attention_fwd": 1, "ff_flash_attention_dq": 1,
        "ff_flash_attention_dkv": 1}
    stats = [a for a in values if a.dtype == jnp.float32 and a.ndim == 4
             and a.shape[2] == 1]
    assert len(stats) == 5 and {a.shape for a in stats} == {(b, heads, 1, s)}
    assert not [a for a in values if a.shape[-1] == 1]
    # the operands as the projections hold them, but for the swapped entry
    wide = {a.shape for a in values if a.dtype == jnp.bfloat16}
    assert wide == ({(b, heads, s, depth)} if entry == "swapped" else
                    {(b, s, heads * depth), (b, s, kv_heads * depth)})
    primal = jax.make_jaxpr(total)(q, kv, kv).jaxpr
    assert not [a for a in _flash_call_values(primal)
                if a.dtype == jnp.float32]


# sha256 of the jaxpr of value-and-gradient of a call WITHOUT a window at
# GPT-2 medium's shape ([8, 16, 1024, 64] bf16, Mosaic path), source
# locations taken out. Re-pinned by PR 63 (from 6b28aa81.. / 79b2b8a4..,
# PR 61's), whose whole point is a different program at the kernels'
# boundary: the blocks' leading dimensions are squeezed (one kernel body
# for every entry), `lse` leaves as `(b, h, 1, s)` and is named as it lies
# (PR 61's reshape pair is gone), `delta` is made in the dq kernel from
# `o`'s rows and read by the dk/dv kernel, which works on the transposed
# score tile. What the pin still holds: a window, grouped K/V heads and a
# VMEM scope of its own enter a call's program only where the call states
# them.
WINDOWLESS_JAXPR = {
    True: "a5a22f5dfb1e4871d6d79552f8a295e64690faf28c3242cc4d25c17bcbb460dd",
    False: "7f048dcdaf1bbfd518fc1202b6d9c4583fca77721c90386f2414f0957a319003",
}


@pytest.mark.parametrize("causal", [True, False])
def test_a_call_without_a_window_traces_to_the_program_it_was(causal,
                                                              monkeypatch):
    """gpt2-medium.train-b8 reads these kernels under a bound of 1 %: the
    window, the grouped K/V heads and the VMEM scope enter a call's program
    only where the call states them. A JAX upgrade that prints a jaxpr
    otherwise moves both digests: re-pin them from the parent commit."""
    import hashlib
    import importlib
    import re

    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((8, 16, 1024, 64), jnp.bfloat16)

    def total(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=causal)
                       .astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(total, (0, 1, 2)))(q, q, q))
    text = re.sub(r" at [^\s\]]+:\d+", "", text)
    text = re.sub(r"/[^\s\"']*flash_attention\.py", "flash_attention.py", text)
    assert hashlib.sha256(text.encode()).hexdigest() == WINDOWLESS_JAXPR[causal]
