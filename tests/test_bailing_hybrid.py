"""The bailing hybrid decoder (flexflow_tpu/models/bailing_hybrid.py: Kimi
Delta Attention layers, a gated delta rule over a matrix state a head in
ops/kda_ops.py, beside latent attention without a query latent and with a
head gate, the grouped sigmoid expert layer of ops/moe_ops.py, and a cache
that pages latents and holds recurrent state in one) against its plain
reference (benchmarks/harness/reference_bailing_hybrid.py: the token-by-token
recurrence), at a small size on the CPU with seeded random weights.

Tolerance: program and reference both compute in float32, so they differ by
the order of their sums alone (the chunked form with its triangular solve
against the literal recurrence, the absorbed decode against decompressed K
and V, the grouped product against a loop over experts, the cache against
one full pass): 1e-6 to 1e-5 of the result's scale. RTOL 1e-4 leaves one
to two orders for that and none for a fault: a dropped beta, gate, norm or
mask is off by 1e-2 and more, a state kept in bfloat16 by 2e-3 and more
(test_a_bf16_state_and_a_dropped_beta_fail_the_tolerance), the same program
computing in bfloat16 by about 1e-2.
"""

import contextlib
import io
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))
sys.path.insert(0, str(ROOT / "tools"))

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer  # noqa: E402
from flexflow_tpu import attribution  # noqa: E402
from flexflow_tpu import telemetry as tel  # noqa: E402
from flexflow_tpu.core.layer import Layer  # noqa: E402
from flexflow_tpu.core.tensor import Tensor, TensorSpec  # noqa: E402
from flexflow_tpu.dtype import DataType  # noqa: E402
from flexflow_tpu.models import (BailingHybridConfig,  # noqa: E402
                                 build_bailing_hybrid)
from flexflow_tpu.ops import get_op_def, kda_ops  # noqa: E402
from flexflow_tpu.ops.op_type import OperatorType  # noqa: E402
from flexflow_tpu.ops.registry import LoweringCtx  # noqa: E402
from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,  # noqa: E402
                                  compile_serving,
                                  positions_valid_prompt_inputs,
                                  positions_valid_step_inputs)
from flexflow_tpu.serving.program import (clone_for_serving,  # noqa: E402
                                          page_geometry, recurrent_layers)
from families import bailing_hybrid as family  # noqa: E402
from harness import flops_bailing_hybrid as flops  # noqa: E402
from harness import manifest as mf  # noqa: E402
from harness import reference_bailing_hybrid as reference  # noqa: E402
from served import Served, off_by  # noqa: E402

RTOL = 1e-4
SLOTS = 4
PUBLISHED = "Ling-3.0-flash"
CELL = PUBLISHED + ".serve-chat"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def file_config(g: BailingHybridConfig) -> dict:
    """`g` in the keys of a configuration file, as the family reads them."""
    lo, hi = g.experts_held
    assert lo == 0
    return {"hidden_size": g.d_model, "num_hidden_layers": g.layers,
            "layer_group_size": g.layer_group_size,
            "first_k_dense_replace": g.first_k_dense,
            "num_attention_heads": g.heads, "head_dim": g.head_dim,
            "kv_lora_rank": g.kv_lora_rank,
            "qk_nope_head_dim": g.qk_nope_head_dim,
            "qk_rope_head_dim": g.qk_rope_head_dim,
            "v_head_dim": g.v_head_dim, "short_conv_kernel_size": g.d_conv,
            "kda_lower_bound": g.kda_lower_bound,
            "intermediate_size": g.dense_width, "num_experts": hi,
            "published": {"num_experts": g.num_experts},
            "num_experts_per_tok": g.experts_per_tok,
            "moe_intermediate_size": g.expert_width,
            "moe_shared_expert_intermediate_size": g.shared_width,
            "num_shared_experts": 1, "n_group": g.n_group,
            "topk_group": g.topk_group, "norm_topk_prob": g.norm_topk_prob,
            "routed_scaling_factor": g.routed_scaling_factor,
            "rope_theta": g.rope_theta, "rms_norm_eps": g.eps,
            "vocab_size": g.vocab,
            "assumed": {"serve_positions": g.seq, "weights_dtype": g.dtype,
                        "expert_bias_range": g.score_bias_range,
                        "kda_dt_bias_range": list(g.kda_dt_bias_range)}}


def ffconfig(batch, **kw):
    return FFConfig(batch_size=batch, seed=3, strategy_cache=False,
                    log_level="warning", mesh_shape={"data": 1}, **kw)


def compiled(g, batch=2, **kw):
    model = FFModel(ffconfig(batch, **kw))
    build_bailing_hybrid(model, g, batch=batch)
    cm = model.compile(SGDOptimizer(lr=1.0),
                       loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=3)
    return cm


def close(got, want, rtol=RTOL):
    return off_by(got, want) <= rtol


def tokens(g, batch, seed=0):
    return np.random.default_rng(seed).integers(
        0, g.vocab, (batch, g.seq)).astype(np.int32)


def positions_of(ids):
    return np.tile(np.arange(ids.shape[1], dtype=np.int32), (ids.shape[0], 1))


def reference_logits(params, g, ids):
    cfg = file_config(g)
    return reference.forward(family.reference_params(params, cfg), ids,
                             positions_of(ids), family.hyper(cfg))


# ---------------------------------------------------------------- the scan
def scan_inputs(length, regime, seed=0, b=2, heads=3, hd=16):
    """Unit keys with a common direction (as SiLU's positive mean gives
    them: `k_t . k_s` about a half, the triangular solve's hard case),
    decays by `regime`: every channel near 1, every channel near e^-5, or
    each channel at one of the two ends and switching, or `lower_bound` on
    every step and channel."""
    rng = np.random.default_rng(seed)
    shape = (b, length, heads, hd)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=shape)) / np.sqrt(hd)
    k = unit(unit(rng.normal(size=shape)) * 0.5 + 0.5 / np.sqrt(hd))
    v = rng.normal(size=shape)
    share = {"near_one": rng.uniform(0.0, 0.01, shape),
             "near_e-5": rng.uniform(0.99, 1.0, shape),
             "at_the_bound": np.ones(shape),
             "both_ends": (rng.uniform(size=shape) > 0.5) * 0.998 + 0.001}[regime]
    beta = rng.uniform(0.0, 1.0, shape[:3])
    return tuple(jnp.asarray(t, jnp.float32)
                 for t in (q, k, v, -5.0 * share, beta))


def recurrence(q, k, v, g, beta, store=lambda s: s, step=kda_ops.kda_step):
    """The literal recurrence a token at a time; `store` is applied to the
    state between steps."""
    b, _length, heads, hd = q.shape

    def one(state, xs):
        out, state = step(state, *xs)
        return store(state), out

    with jax.default_matmul_precision("highest"):
        state, out = jax.lax.scan(
            one, jnp.zeros((b, heads, hd, hd), jnp.float32),
            tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), state


@pytest.mark.parametrize("regime", ("near_one", "near_e-5", "both_ends"))
@pytest.mark.parametrize("length", (64, 100, 37, 128))
@pytest.mark.parametrize("hd", (16, 128))
def test_the_chunked_scan_against_the_literal_recurrence(regime, length, hd):
    """Values at every position and the state handed on, at lengths that
    the chunk of 16 does and does not divide (the rest is padded with steps
    that change nothing), with decays at both ends of (e^-5, 1): near 1 the
    chunk's pairs all count and the solve is dense, near e^-5 G falls by 5 a
    step and every exponent must be formed as a bounded difference. Heads
    of 16 take the XLA form, heads of 128 the kernel (interpreted here), in
    f32: lengths that its tile of 128 does and does not divide."""
    q, k, v, g, beta = scan_inputs(length, regime, b=1 if hd == 128 else 2,
                                   heads=2 if hd == 128 else 3, hd=hd)
    assert kda_ops.scan_path(q, -5.0)["path"] == ("kernel" if hd == 128
                                                  else "xla")
    want_o, want_s = recurrence(q, k, v, g, beta)
    with jax.default_matmul_precision("highest"):
        got_o, got_s = jax.jit(
            lambda *t: kda_ops.kda_chunk_scan(*t, -5.0))(q, k, v, g, beta)
    assert bool(jnp.isfinite(got_o).all()) and bool(jnp.isfinite(got_s).all())
    assert off_by(got_o, want_o) < 2e-5 and off_by(got_s, want_s) < 2e-5


@pytest.mark.parametrize("dtype, limit", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("length", (256, 300))
def test_the_kernel_over_whole_tiles_at_the_decay_bound(length, dtype, limit):
    """`g = lower_bound` on every step and channel of whole tiles of 128
    (eight chunks a grid step, the state carried from tile to tile; 300 is
    padded): every exponent the kernel forms is a chunk's own, so nothing
    leaves float32, in f32 to the recurrence's rounding and in bfloat16
    (where the solve's products are three bf16 passes) to bfloat16's."""
    q, k, v, g, beta = scan_inputs(length, "at_the_bound", b=1, heads=2, hd=128)
    assert kda_ops.scan_path(q, -5.0) == {"path": "kernel", "tile": 128,
                                          "head_block": 2}
    want_o, want_s = recurrence(q, k, v, g, beta)
    dt = jnp.dtype(dtype)
    got_o, got_s = jax.jit(lambda *t: kda_ops.kda_chunk_scan(*t, -5.0))(
        q.astype(dt), k.astype(dt), v.astype(dt), g, beta)
    assert bool(jnp.isfinite(got_o).all()) and bool(jnp.isfinite(got_s).all())
    assert off_by(got_o, want_o) < limit and off_by(got_s, want_s) < limit


def test_the_kernel_at_heads_of_two_lane_slabs():
    """The tile rule takes any head of whole 128-lane slabs: at 256 the
    state is `[256, 256]` a head and the pair products contract two slabs."""
    q, k, v, g, beta = scan_inputs(40, "both_ends", b=1, heads=2, hd=256)
    assert kda_ops.scan_path(q, -5.0)["path"] == "kernel"
    want_o, want_s = recurrence(q, k, v, g, beta)
    with jax.default_matmul_precision("highest"):
        got_o, got_s = kda_ops.kda_chunk_scan(q, k, v, g, beta, -5.0)
    assert off_by(got_o, want_o) < 2e-5 and off_by(got_s, want_s) < 2e-5


def test_the_kernel_in_bfloat16_is_as_close_as_the_xla_form():
    """bfloat16 operands, both forms against the f32 recurrence of the same
    rounded operands: the kernel (interpreted) is no further off than the
    XLA form is, and the two lie closer to each other than to it."""
    q, k, v, g, beta = scan_inputs(200, "both_ends", b=1, heads=2, hd=128)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    want_o, want_s = recurrence(*(t.astype(jnp.float32) for t in (q, k, v)),
                                g, beta)
    got_o, got_s = kda_ops.kda_chunk_scan(q, k, v, g, beta, -5.0)
    xla_o, xla_s = kda_ops._chunk_scan(q, k, v, g, beta, 16)
    assert off_by(got_o, want_o) < 1.5 * off_by(xla_o, want_o) < 2e-2
    assert off_by(got_s, want_s) < 1.5 * off_by(xla_s, want_s) < 2e-2
    assert off_by(got_o, xla_o) < 1e-2 and off_by(got_s, xla_s) < 1e-2


def test_gradients_through_the_kernel_are_the_xla_forms():
    """The kernel is forward only: its `custom_vjp` recomputes the XLA form,
    so the gradients of a loss over both results are that form's own."""
    q, k, v, g, beta = scan_inputs(40, "both_ends", b=1, heads=2, hd=128)

    def loss(form):
        def f(*t):
            o, s = form(*t)
            return jnp.sum(jnp.sin(o)) + jnp.sum(s * s)
        return f

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda *t: kda_ops.kda_chunk_scan(*t, -5.0)),
                       argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
        want = jax.grad(loss(lambda *t: kda_ops._chunk_scan(*t, 16)),
                        argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    for a, b in zip(got, want):
        assert off_by(a, b) < 1e-5


def test_the_scan_path_by_shape_and_its_span():
    """The form is chosen from what the scan sees: the kernel at the served
    wave (heads of one 128-lane slab), the XLA form at the tiny cell's
    widths; a lowered layer says which in a `kda/scan_path` span."""
    from flexflow_tpu import telemetry

    wave = jax.ShapeDtypeStruct((16, 1024, 32, 128), jnp.bfloat16)
    assert kda_ops.scan_path(wave, -5.0) == {"path": "kernel", "tile": 128,
                                             "head_block": 8}
    tiny = jax.ShapeDtypeStruct((4, 64, 4, 16), jnp.float32)
    assert kda_ops.scan_path(tiny, -5.0) == {"path": "xla", "tile": 16}
    # a chunk under a sublane tile of bfloat16 (lower_bound -10: 8 steps)
    assert kda_ops.scan_path(wave, -10.0) == {"path": "xla", "tile": 8}
    for hd, path in ((8, "xla"), (128, "kernel")):
        layer = kda_layer(b=1, s=24, heads=2, hd=hd)
        x = jnp.asarray(np.random.default_rng(7).normal(size=(1, 24, 32)),
                        jnp.float32)
        lower_kda(layer, x, kda_weights(layer), jnp.ones((1, 24), jnp.int32))
        span = telemetry.ring_spans("kda/scan_path")[-1]
        assert span.args["layer"] == "kda" and span.args["path"] == path
        assert ("head_block" in span.args) == (path == "kernel")


def test_a_form_that_multiplies_by_e_to_the_minus_g_overflows():
    """What sizing the chunk by the decay's bound is for: the textbook chunk
    form takes the pairs as (k e^{G}) . (k e^{-G}); at -5 a step e^{-G}
    leaves float32 after 17 steps of a chunk of 64 and the result is not a
    number, while the same inputs through `kda_chunk_scan` (chunks of 16,
    exponents about the middle row) are the recurrence's."""
    q, k, v, g, beta = scan_inputs(64, "near_e-5")
    run = jnp.cumsum(g, axis=1)
    pairs = jnp.einsum("bthd,bshd->bhts", k * jnp.exp(run), k * jnp.exp(-run))
    assert not bool(jnp.isfinite(pairs).all())
    assert kda_ops.chunk_steps(-5.0) == 16 and kda_ops.chunk_steps(-1.0) == 64
    assert kda_ops.chunk_steps(-0.01) == kda_ops.MAX_CHUNK
    with jax.default_matmul_precision("highest"):
        got_o, _ = kda_ops.kda_chunk_scan(q, k, v, g, beta, -5.0)
    assert close(got_o, recurrence(q, k, v, g, beta)[0])


def test_a_bf16_state_and_a_dropped_beta_fail_the_tolerance():
    """How tight RTOL is: the recurrence with its state rounded to bfloat16
    between steps, and the recurrence without beta, both lie far outside
    what the chunked form is held to."""
    q, k, v, g, beta = scan_inputs(100, "near_one")
    want_o, want_s = recurrence(q, k, v, g, beta)
    low_o, low_s = recurrence(
        q, k, v, g, beta,
        store=lambda s: s.astype(jnp.bfloat16).astype(jnp.float32))
    assert off_by(low_o, want_o) > 10 * RTOL and off_by(low_s, want_s) > 10 * RTOL
    no_beta, _ = recurrence(q, k, v, g, jnp.ones_like(beta))
    assert off_by(no_beta, want_o) > 100 * RTOL


def test_the_triangular_inverse_is_exact_block_substitution():
    rng = np.random.default_rng(0)
    a = np.tril(rng.normal(size=(3, 2, 32, 32)), -1).astype(np.float32)
    got = kda_ops._unit_lower_inverse(jnp.asarray(a))
    want = np.linalg.inv(np.eye(32) + a.astype(np.float64))
    assert off_by(got, want) < 1e-5


# ------------------------------------------------------------------ the op
def kda_layer(mode=None, b=2, s=40, d=32, heads=4, hd=8, valid=True):
    ins = [Tensor(TensorSpec((b, s, d), DataType.FLOAT), name="x")]
    if valid:
        ins.append(Tensor(TensorSpec((b, s), DataType.INT32), name="valid"))
    params = {"heads": heads, "head_dim": hd, "d_conv": 4, "lower_bound": -5.0,
              "eps": 1e-6}
    if mode:
        params["mode"] = mode
    layer = Layer(OperatorType.KDA, params, ins, name="kda")
    get_op_def(OperatorType.KDA).infer(layer)
    return layer


def kda_weights(layer, seed=0):
    rng = np.random.default_rng(seed)
    w = {}
    for name, spec in layer.weight_specs.items():
        scale = {"A_log": 1.0, "dt_bias": 1.0, "conv_w": 0.5}.get(
            name, 1.0 / np.sqrt(spec.shape[0]))
        w[name] = jnp.asarray(rng.normal(size=spec.shape) * scale, jnp.float32)
    w["norm"] = jnp.asarray(rng.uniform(0.5, 1.5, layer.weight_specs["norm"].shape),
                            jnp.float32)
    return w


def lower_kda(layer, x, w, valid=None, state=None, stats=None):
    ctx = LoweringCtx(state={"kda": state} if state is not None else {},
                      stats=stats)
    ins = [x] + ([valid] if valid is not None else [])
    with jax.default_matmul_precision("highest"):
        out = get_op_def(OperatorType.KDA).lower(layer, ins, w, ctx)[0]
    return out, ctx.new_state.get("kda")


def reference_kda(x, w, heads=4, hd=8):
    hp = {"heads": heads, "head_dim": hd, "d_conv": 4, "lower_bound": -5.0,
          "eps": 1e-6}
    ref_w = dict(w, gate_norm=w["norm"])
    with jax.default_matmul_precision("highest"):
        return jnp.stack([reference.kda(row, ref_w, hp) for row in x])


def test_the_layer_against_the_reference_and_its_weights():
    layer = kda_layer()
    shapes = {k: v.shape for k, v in layer.weight_specs.items()}
    assert shapes == {"in_proj": (32, 5 * 32 + 4), "conv_w": (4, 96),
                      "A_log": (4,), "dt_bias": (32,), "norm": (8,),
                      "out_proj": (32, 32)}
    w = kda_weights(layer)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 40, 32)), jnp.float32)
    out, handed = lower_kda(layer, x, w, jnp.ones((2, 40), jnp.int32))
    assert handed is None and close(out, reference_kda(x, w))
    # both ends of the decay's range occur under these weights
    with jax.default_matmul_precision("highest"):
        _q, _k, _v, g, _beta, _z = reference.kda_inputs(
            x[0], dict(w, gate_norm=w["norm"]),
            {"heads": 4, "head_dim": 8, "d_conv": 4, "lower_bound": -5.0})
    assert float(g.min()) < -4.9 and float(g.max()) > -0.1
    assert get_op_def(OperatorType.KDA).flop_count(layer) == \
        2.0 * 80 * (32 * 164 + 32 * 32) + 80 * 7 * 4 * 8 * 8
    with pytest.raises(ValueError, match="lower_bound"):
        get_op_def(OperatorType.KDA).infer(
            Layer(OperatorType.KDA, dict(layer.params, lower_bound=0.0),
                  layer.inputs, name="k"))


def test_the_layer_through_the_kernel_against_the_reference(monkeypatch):
    """Heads of 128 take the kernel (interpreted here) with the unit vectors
    in its prologue and the gated head norm in its epilogue: the layer's
    output is the reference's, and its gradients are those of the XLA form
    (the `custom_vjp` recomputes it)."""
    layer = kda_layer(b=1, s=40, heads=2, hd=128)
    w = kda_weights(layer, seed=6)
    x = jnp.asarray(np.random.default_rng(8).normal(size=(1, 40, 32)), jnp.float32)
    valid = jnp.ones((1, 40), jnp.int32)
    out, _ = lower_kda(layer, x, w, valid)
    assert close(out, reference_kda(x, w, heads=2, hd=128))

    def loss(x, in_proj):
        return jnp.sum(jnp.sin(lower_kda(layer, x, dict(w, in_proj=in_proj),
                                         valid)[0]))

    got = jax.grad(loss, argnums=(0, 1))(x, w["in_proj"])
    # the same layer through the XLA form
    monkeypatch.setattr(kda_ops.kda_scan, "heads_a_step", lambda *a: None)
    want = jax.grad(loss, argnums=(0, 1))(x, w["in_proj"])
    assert close(got[0], want[0]) and close(got[1], want[1])


@pytest.mark.parametrize("heads, hd", [(4, 8), (2, 128)])
def test_a_padded_wave_hands_out_each_rows_state_at_its_last_real_token(
        heads, hd):
    """Rows of 7, 23 and 40 real tokens in one wave of 40: the state and the
    convolution tail handed out are those of each row alone at its own
    length, and the outputs at the real positions are unchanged; through
    the XLA form (heads of 8) and through the kernel (heads of 128)."""
    layer = kda_layer("state_out", b=3, heads=heads, hd=hd)
    w = kda_weights(layer, seed=2)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(3, 40, 32)), jnp.float32)
    lengths = (7, 23, 40)
    valid = jnp.asarray(np.arange(40)[None, :] < np.asarray(lengths)[:, None],
                        jnp.int32)
    stats = {}
    out, handed = lower_kda(layer, x, w, valid, stats=stats)
    assert int(stats["kda_layers"]) == 1
    assert handed["state"].shape == (3, heads, hd, hd)
    assert handed["conv"].shape == (3, 3, 3 * heads * hd)
    for row, n in enumerate(lengths):
        alone = kda_layer("state_out", b=1, s=n, heads=heads, hd=hd)
        o1, h1 = lower_kda(alone, x[row:row + 1, :n], w,
                           jnp.ones((1, n), jnp.int32))
        assert close(out[row, :n], o1[0])
        assert close(handed["state"][row], h1["state"][0])
        assert close(handed["conv"][row], h1["conv"][0])


def test_prefill_state_then_one_step_equals_the_sequence():
    """The state a wave hands out, then decode steps on it, against the
    whole sequence through the sequence form; a slot that `valid` does not
    name keeps its state and its tail; the step reports the bytes it moved."""
    s = 21
    full = kda_layer(b=2, s=s + 2)
    w = kda_weights(full, seed=4)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, s + 2, 32)),
                    jnp.float32)
    want, _ = lower_kda(full, x, w, jnp.ones((2, s + 2), jnp.int32))
    _, state = lower_kda(kda_layer("state_out", b=2, s=s), x[:, :s], w,
                         jnp.ones((2, s), jnp.int32))
    step = kda_layer("decode", b=2, s=1)
    for t in (s, s + 1):
        stats = {}
        live = jnp.asarray([[1], [1 if t == s else 0]], jnp.int32)
        got, new = lower_kda(step, x[:, t:t + 1], w, live, state=state,
                             stats=stats)
        per_slot = 4 * 8 * 8 * 4 + 3 * 96 * 4
        assert float(stats["linear_state_bytes"]) == 2.0 * int(live.sum()) * per_slot
        assert close(got[0, 0], want[0, t])
        if t == s:
            assert close(got[1, 0], want[1, t])
        else:   # the slot that sat the step out
            assert bool((new["state"][1] == state["state"][1]).all())
            assert bool((new["conv"][1] == state["conv"][1]).all())
        state = new
    with pytest.raises(NotImplementedError, match="one token a step"):
        lower_kda(kda_layer("decode", b=2, s=2), x[:, :2], w, state=state)


def test_the_op_declares_recurrent_state_and_no_groups():
    d = get_op_def(OperatorType.KDA)
    layer = kda_layer()
    assert d.state_kind == "recurrent" and d.span_facts is None
    assert d.slot_state(layer) == {"state": ((4, 8, 8), jnp.float32),
                                   "conv": ((3, 96), jnp.float32)}
    assert d.serving_params(layer.params, "decode")["mode"] == "decode"
    assert d.serving_params(layer.params, "prefill")["mode"] == "state_out"
    mamba = get_op_def(OperatorType.MAMBA2)
    assert mamba.span_facts(Layer(OperatorType.MAMBA2, {"n_groups": 8},
                                  layer.inputs[:1], name="m")) \
        == {"ssm_groups": 8}


# ------------------------------------------------------- latent attention
def latent_layer(mode=None, b=2, s=24, d=32):
    ins = [Tensor(TensorSpec((b, s, d), DataType.FLOAT), name="x"),
           Tensor(TensorSpec((b, s), DataType.INT32), name="positions")]
    params = {"heads": 4, "q_lora_rank": 0, "kv_lora_rank": 16,
              "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
              "eps": 1e-6, "rope_theta": 6e6, "impl": "einsum",
              "head_gate": True}
    if mode:
        params["mode"] = mode
    layer = Layer(OperatorType.LATENT_ATTENTION, params, ins, name="attn")
    get_op_def(OperatorType.LATENT_ATTENTION).infer(layer)
    return layer


def test_latent_attention_without_a_query_latent_and_with_the_head_gate():
    """One `wq` matrix, no `wq_a` / `q_norm`, a `[d, H]` gate; against the
    reference's decompressed attention with plain rotary frequencies at
    theta 6e6; the gate matters (without it the output is another)."""
    layer = latent_layer()
    assert list(layer.weight_specs) == ["wq", "wkv_a", "kv_norm", "wkv_b",
                                        "wo", "w_gate"]
    assert layer.weight_specs["wq"].shape == (32, 4 * 12)
    assert layer.weight_specs["w_gate"].shape == (32, 4)
    rng = np.random.default_rng(0)
    w = {k: jnp.asarray(rng.normal(size=s.shape) / np.sqrt(s.shape[0]),
                        jnp.float32) for k, s in layer.weight_specs.items()}
    x = jnp.asarray(rng.normal(size=(2, 24, 32)), jnp.float32)
    pos = jnp.asarray(positions_of(np.zeros((2, 24))))
    hp = {"heads": 4, "dn": 8, "dr": 4, "dv": 8, "rank": 16, "eps": 1e-6,
          "rope_theta": 6e6}
    with jax.default_matmul_precision("highest"):
        got = get_op_def(OperatorType.LATENT_ATTENTION).lower(
            layer, [x, pos], w, LoweringCtx())[0]
        want = jnp.stack([reference.attention(x[i], pos[i], w, hp)
                          for i in range(2)])
        ungated = get_op_def(OperatorType.LATENT_ATTENTION).lower(
            layer, [x, pos], dict(w, w_gate=jnp.zeros_like(w["w_gate"])),
            LoweringCtx())[0]
    assert close(got, want)
    with jax.default_matmul_precision("highest"):
        assert close(ungated, jnp.stack([reference.attention(
            x[i], pos[i], dict(w, w_gate=jnp.zeros_like(w["w_gate"])), hp)
            for i in range(2)]))
    assert not close(got, ungated, 0.01)
    from flexflow_tpu.ops.latent_attention_ops import projection_params
    assert projection_params(layer.params, 32) == 32 * 48 + 32 * 20 \
        + 16 * 4 * 16 + 32 * 32 + 32 * 4


def test_a_layer_with_a_query_latent_lowers_to_the_parents_text():
    """GigaChat's latent attention (a query latent, YaRN, no gate) in the
    whole-sequence form lowers to the StableHLO that commit f4e2f17 (PR 39)
    lowered it to, weight for weight: the two variants enter a graph only
    where a layer's params set them, so its logits are the parent's to the
    bit (its serving programs are pinned alike in tests/test_nemotron_h.py)."""
    import hashlib

    from flexflow_tpu.models import DeepseekV3Config, build_deepseek_v3

    m = FFModel(ffconfig(2))
    build_deepseek_v3(m, DeepseekV3Config.tiny(seq=40), batch=2)
    layer = m.get_layer_by_name("l1_attn")
    d = get_op_def(layer.op_type)
    assert list(layer.weight_specs) == ["wq_a", "q_norm", "wq_b", "wkv_a",
                                        "kv_norm", "wkv_b", "wo"]
    assert "head_gate" not in layer.params and layer.params["q_lora_rank"] == 32
    w = {k: jax.ShapeDtypeStruct(s.shape, s.dtype.jnp_dtype)
         for k, s in layer.weight_specs.items()}
    ins = [jax.ShapeDtypeStruct(t.spec.shape, t.spec.dtype.jnp_dtype)
           for t in layer.inputs]
    text = jax.jit(lambda ins, w: d.lower(layer, ins, w, LoweringCtx())
                   ).lower(ins, w).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:24] \
        == "d2a1bd0a22537000361b9939"


# ------------------------------------------------------------------ forward
def test_forward_logits_against_the_reference():
    g = BailingHybridConfig.tiny(seq=40)        # no multiple of the chunk of 16
    assert g.kinds == ("kda", "kda", "latent", "kda")
    cm = compiled(g)
    ids = tokens(g, 2)
    got = cm.forward(ids, positions_of(ids), np.ones_like(ids))
    assert got.shape == (2, g.seq, g.vocab)
    assert close(got, reference_logits(cm.params, g, ids))
    dt_bias = np.asarray(cm.params["l0_kda"]["dt_bias"])
    assert dt_bias.dtype == np.float32 and -1.5 <= dt_bias.min() < -1.0 \
        and 0.0 < dt_bias.max() <= 0.5


def test_bf16_program_fails_the_f32_tolerance():
    """The comparison is tight enough to catch a lower precision."""
    g = BailingHybridConfig.tiny(seq=40)
    cm = compiled(g, compute_dtype="bfloat16")
    ids = tokens(g, 2)
    got = cm.forward(ids, positions_of(ids), np.ones_like(ids))
    assert not close(got, reference_logits(cm.params, g, ids), 10 * RTOL)


# ------------------------------------------------------------------ serving
def engine_for(g, **compile_kw):
    model = FFModel(ffconfig(SLOTS))
    build_bailing_hybrid(model, g, batch=SLOTS)
    eng = compile_serving(model, max_batch_slots=SLOTS, max_decode_len=16,
                          kv_page_size=8, **compile_kw)
    eng.init(seed=3)
    return eng


def served(g):
    """The shared harness on this family's engine, input builders and
    reference."""
    eng = engine_for(g)

    def wave_stats(s, stats, prompts):
        assert int(stats["kda_layers"]) == g.kinds.count("kda")

    def step_stats(s, stats):
        per_slot = eng.kv_spec.state_bytes_per_slot
        assert float(stats["linear_state_bytes"]) \
            == 2 * len(s.seqs) * per_slot
        assert "ssm_state_bytes" not in stats

    return Served(eng, lambda ids: reference_logits(eng.params, g, ids),
                  positions_valid_prompt_inputs, positions_valid_step_inputs,
                  RTOL, wave_stats=wave_stats, step_stats=step_stats)


def test_prefill_then_decode_through_cache_and_state_equals_the_full_forward():
    """Logits, not tokens. Prompts of different lengths in one padded wave
    (one of 2 tokens, one past four pages): latent pages and the matrix
    state are committed at each row's last real token; a slot that sits out
    the second wave keeps its state and decodes correctly; a second wave
    into a freed slot and into one never used. The absorbed decode (no
    query latent, the head gate) and the delta rule's single step."""
    g = BailingHybridConfig.tiny(seq=48)
    rng = np.random.default_rng(7)
    s = served(g)
    assert s.eng.kv.state_kinds == "paged_latent+recurrent"

    def prompt(n):
        return [int(t) for t in rng.integers(0, g.vocab, n)]

    s.wave({0: prompt(2), 1: prompt(19), 2: prompt(33)})
    s.decode(3)
    s.evict(1)
    s.wave({1: prompt(9), 3: prompt(17)})       # 0 and 2 sit it out
    s.decode(3)
    assert s.checked == 3 + 3 * 3 + 2 + 4 * 3
    assert len(s.seqs[0]) == 2 + 1 + 6 and len(s.seqs[1]) == 9 + 1 + 3


def test_the_cache_comes_from_the_layers_own_declarations():
    g = BailingHybridConfig.tiny(seq=48)
    model = FFModel(ffconfig(SLOTS))
    build_bailing_hybrid(model, g, batch=SLOTS)
    assert page_geometry(model) == {"latent_dim": 40}
    dec, attn = clone_for_serving(model, "decode", SLOTS)
    assert attn == ["l2_attn"]
    rec = recurrent_layers(dec)
    assert list(rec) == ["l0_kda", "l1_kda", "l3_kda"]
    assert rec["l0_kda"] == {"state": ((4, 16, 16), jnp.float32),
                             "conv": ((3, 192), jnp.float32)}
    eng = engine_for(g)
    assert eng.kv_spec.state_bytes_per_slot == 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4) \
        == flops.state_bytes_per_slot(dict(file_config(g))) \
        + 3 * 3 * 192 * 2      # the file counts tails in bf16, tiny holds f32
    assert eng.kv.state["l2_attn"]["latent"].shape[-1] % 128 == 0
    assert eng.kv.state["l0_kda"]["state"].shape == (SLOTS, 4, 16, 16)


def test_scheduler_serves_it_and_reports_its_spans_and_counters(tmp_path):
    """Through ContinuousBatchingScheduler, with nothing model-specific in
    it: every served token is the reference's argmax over the request's own
    tokens, and the spans and counters the benchmark reads are there."""
    import trace_report

    g = BailingHybridConfig.tiny(seq=48)
    tel.ring_clear()
    tel.configure(str(tmp_path))
    try:
        eng = engine_for(g)
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i, prompt=[int(t) for t in rng.integers(0, g.vocab, n)],
                        max_new_tokens=new, arrival_s=0.0)
                for i, (n, new) in enumerate([(5, 10), (17, 6), (30, 12), (9, 8),
                                              (12, 7), (20, 9), (3, 5)])]
        sched = ContinuousBatchingScheduler(
            eng, eng.params, positions_valid_prompt_inputs,
            positions_valid_step_inputs, eos_id=None)
        sched.run(reqs)
    finally:
        tel.shutdown()
    assert len(sched.completed) == len(reqs) and sched.prefills >= 2
    for r in reqs:
        logits = np.asarray(reference_logits(
            eng.params, g, np.asarray([r.prompt + r.tokens], np.int32)))[0]
        rows = logits[len(r.prompt) - 1:len(r.prompt) - 1 + len(r.tokens)]
        assert len(r.tokens) == r.max_new_tokens
        assert (rows.argmax(-1) == np.asarray(r.tokens)).all(), r.rid
    spans = {}
    for sp in tel.ring_spans():
        spans.setdefault(sp.name, []).append(sp.args or {})
    made = spans["serve/compile_serving"][-1]
    assert (made["kv_layers"], made["state_layers"], made["expert_layers"]) \
        == (1, 3, 3)
    assert "ssm_groups" not in made and made["paged_state"] == "paged_latent"
    assert (made["experts_held"], made["experts_routed_over"]) == (8, 16)
    assert made["state_bytes_per_slot"] == eng.kv_spec.state_bytes_per_slot
    commits = spans["serve/prefill/commit_state"]
    assert len(commits) == sched.prefills
    assert all(c["bytes"] > 0 for c in commits)
    assert {a["state"] for a in spans["serve/prefill/commit"]} \
        == {"paged_latent+recurrent"}
    steps = 0
    for a in spans["serve/decode/window_sync"]:
        steps += a["steps"]
        assert 0 <= a["moe_held_pairs"] <= a["moe_routed_pairs"] \
            <= a["steps"] * 3 * SLOTS * g.experts_per_tok
        assert 0 < a["moe_experts_hit"] <= a["steps"] * 3 * 8
        assert 0 < a["linear_state_bytes"] <= a["steps"] * 2 * SLOTS \
            * eng.kv_spec.state_bytes_per_slot
        assert a["latent_cache_bytes"] > 0 and "ssm_state_bytes" not in a
    assert steps == sched.decode_steps
    wave = spans["serve/prefill/device_wait"][0]
    assert wave["kda_layers"] == 3 and wave["moe_held_pairs"] > 0
    assert wave["moe_routed_pairs"] == 3 * g.experts_per_tok * sum(
        len(r.prompt) for r in reqs[:SLOTS])
    assert "linear_state_bytes" not in wave
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        trace_report.render(str(next(tmp_path.glob("*.jsonl"))))
    text = out.getvalue()
    assert "state_layers=3" in text and "ssm_groups" not in text
    assert re.search(r"\[serve\] linear-attention layers in "
                     r"serve/decode/window_sync: [\d.]+ MB of recurrent state "
                     r"read and written a step, [\d.]+ held experts hit", text)


def test_what_this_state_does_not_support_fails_loudly():
    g = BailingHybridConfig.tiny(seq=48)

    def model(**kw):
        m = FFModel(ffconfig(SLOTS, **kw))
        build_bailing_hybrid(m, g, batch=SLOTS)
        return m

    def serve(m, **kw):
        return compile_serving(m, max_batch_slots=SLOTS, max_decode_len=16,
                               kv_page_size=8, **kw)

    with pytest.raises(NotImplementedError, match="3 kda layers.*host KV tier"):
        serve(model(kv_host_pages=8))
    with pytest.raises(NotImplementedError, match="recurrent state.*speculative"):
        serve(model(), draft=model(), spec_tokens=2)
    with pytest.raises(NotImplementedError, match="paged_latent.*quantized cache"):
        serve(model(), kv_cache_dtype="int8")
    eng = serve(model())
    eng.init(seed=3)
    with pytest.raises(NotImplementedError, match="paged_latent|recurrent state"):
        ContinuousBatchingScheduler(
            eng, eng.params, positions_valid_prompt_inputs,
            positions_valid_step_inputs, handoff=lambda req, payload: None)


# -------------------------------------------------------------- the share
def test_the_shares_of_four_holders_add_up_to_the_uncut_layer():
    """One of 4 chips: the four holders' partial results of an expert layer
    (the program's moe_layer told which experts it holds), with the shared
    expert (computed alike on every chip) counted once, add up to what the
    uncut reference gives for the whole layer; the router, its groups and
    the top-k stay 16 wide for every holder."""
    d, experts, width, k = 32, 16, 24, 3
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 12, d)), jnp.float32)
    w = {"router": jnp.asarray(rng.normal(size=(d, experts)) / 4, jnp.float32),
         "score_bias": jnp.asarray(rng.uniform(-0.05, 0.05, experts), jnp.float32),
         "w_in": jnp.asarray(rng.normal(size=(experts, d, 2 * width)) / 6, jnp.float32),
         "w_out": jnp.asarray(rng.normal(size=(experts, width, d)) / 6, jnp.float32)}
    shared_in = jnp.asarray(rng.normal(size=(d, 2 * 20)) / 6, jnp.float32)
    shared_out = jnp.asarray(rng.normal(size=(20, d)) / 6, jnp.float32)
    hp = {"top_k": k, "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
          "routed_scaling_factor": 2.5, "held": (0, experts)}
    with jax.default_matmul_precision("highest"):
        once = reference.gated_mlp(x, shared_in, shared_out)
        whole = reference.moe(x, w, hp) + once

    def program_part(held):
        ins = [Tensor(TensorSpec(x.shape, DataType.FLOAT), name="x"),
               Tensor(TensorSpec(x.shape[:2], DataType.INT32), name="valid")]
        layer = Layer(OperatorType.MOE_LAYER, {
            "num_experts": experts, "top_k": k, "expert_width": width,
            "experts_held": held, "scoring": "sigmoid", "n_group": 4,
            "topk_group": 2, "norm_topk_prob": True,
            "routed_scaling_factor": 2.5, "score_bias": True}, ins, name="moe")
        get_op_def(OperatorType.MOE_LAYER).infer(layer)
        lo, hi = held
        with jax.default_matmul_precision("highest"):
            return get_op_def(OperatorType.MOE_LAYER).lower(
                layer, [x, jnp.ones(x.shape[:2], jnp.int32)],
                dict(w, w_in=w["w_in"][lo:hi],
                                 w_out=w["w_out"][lo:hi]), LoweringCtx())[0]

    parts = []
    for lo in range(0, experts, 4):
        held = (lo, lo + 4)
        part = program_part(held)
        with jax.default_matmul_precision("highest"):
            ref_part = reference.moe(
                x, dict(w, w_in=w["w_in"][lo:lo + 4], w_out=w["w_out"][lo:lo + 4]),
                dict(hp, held=held))
        assert close(part, ref_part)
        parts.append(part)
    assert close(sum(parts) + once, whole)
    assert not close(sum(parts[:3]) + once, whole, 0.01)
    assert not close(sum(parts) + 4 * once, whole, 0.01)


# ------------------------------------------------------- counts and the file
def test_the_configuration_file_against_the_catalog_and_the_issue():
    cfg = mf.read_named("configs", PUBLISHED)
    reduced = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
               "vocab_size", "expert_swiglu_limit_list",
               "share_expert_swiglu_limit_list"]
    assert cfg["reduced"] == reduced and cfg["family"] == "bailing_hybrid"
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_experts"], cfg["vocab_size"]) == (7, 1, 128, 39296)
    assert cfg["published"]["num_hidden_layers"] == 42 \
        and cfg["published"]["num_experts"] == 512 \
        and cfg["published"]["vocab_size"] == 157184 \
        and cfg["published"]["first_k_dense_replace"] == 2
    assert cfg["expert_swiglu_limit_list"] == [0] * 7 \
        == cfg["share_expert_swiglu_limit_list"]
    # every width as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
            cfg["kv_lora_rank"], cfg["qk_rope_head_dim"],
            cfg["qk_nope_head_dim"], cfg["v_head_dim"],
            cfg["moe_intermediate_size"], cfg["intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"],
            cfg["short_conv_kernel_size"], cfg["kda_lower_bound"],
            cfg["q_lora_rank"], cfg["rope_theta"], cfg["rope_scaling"]) == (
        2560, 32, 128, 512, 64, 128, 128, 768, 6144, 8, 8, 4, 4, -5, None,
        6000000, None)
    assert flops.kinds(cfg) == ["kda"] * 5 + ["latent", "kda"]
    assert "4 chips" in cfg["deployment"] and "rank 0" in cfg["deployment"]
    assert set(cfg["departures"]) >= {
        "num_nextn_predict_layers", "weights", "described_as",
        "expert_swiglu_limit_list, share_expert_swiglu_limit_list"}
    assert set(cfg["assumed"]) >= {
        "serve_positions", "kda_positions", "kda_output_gate", "kda_head_norm",
        "expert_bias_range", "kda_dt_bias_range"}
    assert (cfg["n_embd"], cfg["n_head"]) == (2560, 32)
    if CATALOG.exists():
        row = next(json.loads(l) for l in CATALOG.read_text().splitlines()
                   if json.loads(l)["name"] == PUBLISHED)
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in reduced:
                assert cfg[key] == value, key
            else:
                assert cfg["published"][key] == value, key
    # the manifest: membership, and order by index (never "the last entry")
    manifest = mf.load_manifest()
    names = [c["name"] for c in manifest["configs"]]
    cells = [w["name"] for w in manifest["workloads"]]
    assert PUBLISHED in names and CELL in cells
    assert names.index(PUBLISHED) > names.index(
        "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert cells.index(CELL) > cells.index(
        "NVIDIA-Nemotron-3-Super-120B-A12B-BF16.serve-chat")
    entry = manifest["configs"][names.index(PUBLISHED)]
    assert entry["reduced"] == reduced and entry["source"] == cfg["source"]
    assert len(entry["why"]) <= 200
    assert all(w["chips"] == 1 for w in manifest["workloads"])


def test_flop_and_byte_functions_against_hand_counts_and_the_program():
    for name in (PUBLISHED, "bailing-hybrid-tiny"):
        cfg = mf.read_named("configs", name)
        g = family.program_config(cfg)
        assert flops.train_flops_per_token(cfg, g.seq) == g.flops_per_token()
        assert flops.param_count(cfg) == g.param_count()
        assert flops.kinds(cfg) == list(g.kinds)
    cfg = mf.read_named("configs", PUBLISHED)
    # the issue's arithmetic
    assert flops.mixer_params(cfg, "kda") == 2560 * (12288 + 4096 + 4096 + 32) \
        + 4096 * 2560
    assert round(flops.mixer_params(cfg, "kda") / 1e6, 1) == 63.0
    assert flops.mixer_small_params(cfg, "kda") == 4 * 12288 + 32 + 4096 + 128
    assert flops.mixer_params(cfg, "latent") == 2560 * 6144 + 2560 * 576 \
        + 512 * 8192 + 4096 * 2560 + 2560 * 32
    assert round(flops.mixer_params(cfg, "latent") / 1e6, 1) == 32.0
    assert flops.expert_params(cfg) == 3 * 2560 * 768
    assert flops.feed_forward_params(cfg, True) == 3 * 2560 * 6144
    assert flops.feed_forward_params(cfg, False) == 2560 * 512 + 3 * 2560 * 768
    assert round(flops.param_count(cfg) / 1e6) == 5232
    assert round(2 * flops.param_count(cfg) / 1e9, 2) == 10.46
    whole = dict(cfg, num_hidden_layers=42, first_k_dense_replace=2,
                 num_experts=512, vocab_size=157184)
    assert 124e9 < flops.param_count(whole) < 125e9
    assert flops.state_bytes_per_slot(cfg) == 6 * (32 * 128 * 128 * 4
                                                   + 3 * 12288 * 2)
    assert flops.cache_bytes_per_token(cfg) == 576 * 2
    tiny = BailingHybridConfig.tiny()
    cm = compiled(tiny)
    held = sum(int(np.prod(w.shape)) for lw in cm.params.values()
               for w in lw.values())
    assert held == tiny.param_count() == flops.param_count(file_config(tiny))
    bias = np.asarray(cm.params["l1_moe"]["score_bias"])
    assert bias.dtype == np.float32 and 0 < np.abs(bias).max() <= 0.02
    chat = mf.read_named("traffic", "serve-chat")
    system = {"max_batch_slots": 16}
    state = 2.0 * 16 * flops.state_bytes_per_slot(cfg)
    counters = {"moe_routed_pairs": 16 * 8 * 6, "moe_experts_hit": 6 * 24,
                "linear_state_bytes": state, "latent_cache_bytes": 16 * 200 * 1280}
    step = flops.decode_step_need(cfg, system, chat, counters)
    dense = 6 * 63.05e6 + 32.03e6 + 47.19e6 + 6 * 7.21e6 + 100.6e6
    assert step["flops"] == 0.0
    assert step["bytes"] == pytest.approx(
        2 * (dense + 6 * 24 * 5.898e6) + state + 16 * 200 * 1280, rel=2e-3)
    assert 3.2e9 < step["bytes"] < 3.5e9
    wave = flops.prefill_wave_need(cfg, system, chat,
                                   {"moe_held_pairs": 6 * 32768})
    positions = 16 * 1024
    assert wave["flops"] == pytest.approx(
        2 * positions * (6 * 63.0e6 + 32.03e6 + 47.19e6 + 6 * 7.21e6)
        + 2 * 6 * 32768 * 5.898e6 + 16 * 524800 * 2 * 32 * 320
        + 6 * positions * 7 * 32 * 128 * 128 + 2 * 16 * 2560 * 39296, rel=1e-3)
    assert 18e12 < wave["flops"] < 21e12
    scan = flops.kda_scan_need(cfg, system, chat, {"kda_layers": 6})
    assert scan["flops"] == 6 * positions * 7 * 32 * 128 * 128
    assert scan["bytes"] == 6 * (positions * (5 * 4096 * 2 + 32 * 4)
                                 + 16 * 32 * 128 * 128 * 4)


# -------------------------------------------------------------- attribution
@pytest.mark.parametrize("hd", (16, 128))
def test_instructions_under_a_named_scope_of_a_compiled_program(hd):
    """What `kda_scan_roofline` joins the device trace with: the names of
    the compiled program's instructions under `ff_kda_chunk_scan`, loop
    bodies included, and none of the work outside the scope; of the XLA
    form (heads of 16) and of the kernel (heads of 128; interpreted here,
    its grid a loop: `tests/test_chip_compile.py` finds the Mosaic call
    under the scope)."""
    q, k, v, g, beta = scan_inputs(64, "both_ends", b=1, hd=hd)
    assert kda_ops.scan_path(q, -5.0)["path"] == ("kernel" if hd == 128
                                                  else "xla")

    def program(q, k, v, g, beta, w):
        out, _state = kda_ops.kda_chunk_scan(q, k, v, g, beta, -5.0)
        with jax.named_scope("after"):
            return jnp.tanh(out.reshape(out.shape[:2] + (-1,)) @ w)

    w = jnp.ones((3 * hd, 8), jnp.float32)
    text = jax.jit(program).lower(q, k, v, g, beta, w).compile().as_text()
    inside = attribution.instructions_in_scope(text, kda_ops.SCAN_SCOPE)
    after = attribution.instructions_in_scope(text, "after")
    assert inside and after and not inside & after
    assert not attribution.instructions_in_scope(text, "no_such_scope")
    assert not any(n.startswith("while") for n in inside)
    # the scan's loop body is counted: more instructions than the entry's
    comps, entry = attribution._parse_computations(text)
    in_entry = {i.name for i in comps[entry]}
    assert inside - in_entry
    assert attribution.instructions_under("no/such/program", "x") == []
