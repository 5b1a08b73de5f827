"""The Nemotron-H decoder (flexflow_tpu/models/nemotron_h.py: a layer is a
mixer or an expert layer alone; Mamba-2 with B/C groups and a grouped gated
norm in ops/ssm_ops.py, un-gated squared-ReLU experts in a latent in
ops/moe_ops.py's moe_layer, 2-K/V-head NoPE attention, and a cache in which
state-holding layers are a minority) against its plain reference
(benchmarks/harness/reference_nemotron_h.py), at a small size on the CPU
with seeded random weights.

Tolerance: program and reference both compute in float32, so they differ by
the order of their sums alone (the chunked scan against the literal
recurrence, the grouped product against a loop over experts, the cache
against one full pass): about 1e-6 of the result's scale. RTOL 1e-4 leaves
two orders for that and none for a fault: a wrong group, norm, gate, mask or
activation is off by 1e-2 and more, and the same program computing in
bfloat16 is off by about 1e-2 (test_bf16_program_fails_the_f32_tolerance).
"""

import hashlib
import io
import contextlib
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
from flexflow_tpu import telemetry as tel  # noqa: E402
from flexflow_tpu.core.layer import Layer  # noqa: E402
from flexflow_tpu.core.tensor import Tensor, TensorSpec  # noqa: E402
from flexflow_tpu.dtype import DataType  # noqa: E402
from flexflow_tpu.models import (DeepseekV3Config, GPT2Config,  # noqa: E402
                                 GraniteHybridConfig, NemotronHConfig,
                                 build_deepseek_v3, build_gpt2,
                                 build_granite_hybrid, build_nemotron_h)
from flexflow_tpu.ops import get_op_def, moe_ops, ssm_ops  # noqa: E402
from flexflow_tpu.ops.op_type import OperatorType  # noqa: E402
from flexflow_tpu.ops.registry import LoweringCtx  # noqa: E402
from flexflow_tpu.search.cost_model import KVCacheSpec  # noqa: E402
from flexflow_tpu.search.strategy_cache import graph_fingerprint  # noqa: E402
from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,  # noqa: E402
                                  compile_serving, valid_prompt_inputs,
                                  valid_step_inputs)
from flexflow_tpu.serving.program import (clone_for_serving,  # noqa: E402
                                          page_geometry, recurrent_layers)
from families import nemotron_h as family  # noqa: E402
from harness import flops_nemotron_h as flops  # noqa: E402
from harness import manifest as mf  # noqa: E402
from harness import reference_nemotron_h as reference  # noqa: E402
from served import Served, scheduler_reports_the_step_path  # noqa: E402

RTOL = 1e-4
SLOTS = 4
PUBLISHED = "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"


def file_config(g: NemotronHConfig) -> dict:
    """`g` in the keys of a configuration file, as the family reads them."""
    lo, hi = g.experts_held
    assert lo == 0
    return {"hidden_size": g.d_model, "hybrid_override_pattern": g.pattern,
            "num_hidden_layers": g.layers, "num_attention_heads": g.heads,
            "num_key_value_heads": g.kv_heads, "head_dim": g.head_dim,
            "mamba_num_heads": g.mamba_heads,
            "mamba_head_dim": g.mamba_head_dim,
            "ssm_state_size": g.mamba_d_state, "n_groups": g.mamba_n_groups,
            "conv_kernel": g.mamba_d_conv, "chunk_size": g.mamba_chunk,
            "time_step_min": g.time_step_min, "time_step_max": g.time_step_max,
            "time_step_floor": g.time_step_floor, "n_routed_experts": hi,
            "published": {"n_routed_experts": g.num_experts},
            "n_shared_experts": 1, "num_experts_per_tok": g.experts_per_tok,
            "moe_intermediate_size": g.expert_width,
            "moe_latent_size": g.latent_size,
            "moe_shared_expert_intermediate_size": g.shared_width,
            "norm_topk_prob": g.norm_topk_prob,
            "routed_scaling_factor": g.routed_scaling_factor,
            "layer_norm_epsilon": g.eps, "vocab_size": g.vocab,
            "assumed": {"serve_positions": g.seq, "weights_dtype": g.dtype,
                        "e_score_correction_bias_range": g.score_bias_range}}


def ffconfig(batch, **kw):
    return FFConfig(batch_size=batch, seed=3, strategy_cache=False,
                    log_level="warning", mesh_shape={"data": 1}, **kw)


def compiled(g, batch=2, **kw):
    model = FFModel(ffconfig(batch, **kw))
    build_nemotron_h(model, g, batch=batch)
    cm = model.compile(SGDOptimizer(lr=1.0),
                       loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=3)
    return cm


def close(got, want, rtol=RTOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    return float(np.abs(got - want).max()) <= rtol * float(np.abs(want).max())


def tokens(g, batch, seed=0):
    return np.random.default_rng(seed).integers(
        0, g.vocab, (batch, g.seq)).astype(np.int32)


def reference_logits(params, g, ids):
    cfg = file_config(g)
    return reference.forward(family.reference_params(params, cfg), ids,
                             family.hyper(cfg))


# ------------------------------------------------------------------ forward
def test_forward_logits_against_the_reference():
    g = NemotronHConfig.tiny(seq=40)        # no multiple of the chunk of 16
    cm = compiled(g)
    ids = tokens(g, 2)
    got = cm.forward(ids, np.ones_like(ids))
    assert got.shape == (2, g.seq, g.vocab)
    assert close(got, reference_logits(cm.params, g, ids))


def test_bf16_program_fails_the_f32_tolerance():
    """The comparison is tight enough to catch a lower precision."""
    g = NemotronHConfig.tiny(seq=40)
    cm = compiled(g, compute_dtype="bfloat16")
    ids = tokens(g, 2)
    got = cm.forward(ids, np.ones_like(ids))
    want = reference_logits(cm.params, g, ids)
    assert not close(got, want)
    assert close(got, want, rtol=0.3)       # lower precision, not another model


def test_the_graph_is_one_mixer_a_layer_with_one_residual():
    g = NemotronHConfig.tiny()
    m = FFModel(ffconfig(2))
    build_nemotron_h(m, g, batch=2)
    kinds = {OperatorType.MAMBA2: "M", OperatorType.MULTIHEAD_ATTENTION: "*",
             OperatorType.MOE_LAYER: "E"}
    assert "".join(kinds[l.op_type] for l in m.layers
                   if l.op_type in kinds) == g.pattern == "MEM*E"
    # one norm and one residual add a layer (+ norm_f), no multiplier
    assert sum(l.op_type is OperatorType.RMSNORM for l in m.layers) == 6
    assert sum(l.name.endswith("_res") for l in m.layers) == 5
    assert not any(l.op_type is OperatorType.SCALAR_MULTIPLY for l in m.layers)
    assert [t.name for t in m.input_tensors] == ["input_ids", "valid"]
    attn = m.get_layer_by_name("l3_attn")
    assert attn.params["num_kv_heads"] == 2 and "scale" not in attn.params
    with pytest.raises(KeyError):
        NemotronHConfig(pattern="M-").kinds


# -------------------------------------------------------------- the mixer
def mamba_layer(groups, mode=None, b=2, s=40, d=32, heads=8, hd=8, n=8,
                chunk=16):
    x = Tensor(TensorSpec((b, s, d), DataType.FLOAT), name="x")
    valid = Tensor(TensorSpec((b, s), DataType.INT32), name="valid")
    params = {"heads": heads, "head_dim": hd, "d_state": n, "d_conv": 4,
              "chunk": chunk, "n_groups": groups, "eps": 1e-5}
    if mode:
        params["mode"] = mode
    layer = Layer(OperatorType.MAMBA2, params, [x, valid], name="mix")
    get_op_def(OperatorType.MAMBA2).infer(layer)
    return layer


def mamba_weights(layer, seed=0):
    rng = np.random.default_rng(seed)
    w = {k: jnp.asarray(rng.normal(size=spec.shape) * 0.3, jnp.float32)
         for k, spec in layer.weight_specs.items()}
    w["A_log"] = jnp.asarray(np.log(rng.uniform(1, 16, w["A_log"].shape)),
                             jnp.float32)
    w["dt_bias"] = jnp.asarray(rng.uniform(-4, -2, w["dt_bias"].shape),
                               jnp.float32)
    w["norm"] = jnp.asarray(rng.uniform(0.5, 1.5, w["norm"].shape), jnp.float32)
    return w


def reference_mamba(x, w, groups, heads=8, hd=8, n=8):
    hp = {"mamba_heads": heads, "mamba_head_dim": hd, "d_state": n,
          "n_groups": groups, "eps": 1e-5}
    ref = {**w, "conv_b": w["bias_conv"], "gate_norm": w["norm"]}
    with jax.default_matmul_precision("highest"):
        return reference.mamba2(x, ref, hp)


# the served cells' tile shape in small: heads of 64, a state of 128, a
# group's heads in whole lanes, so the scan, the skip, the gate and the norm
# are the kernel's (interpreted here); 200 positions are two tiles of 128,
# the second padded
SERVED = dict(s=200, heads=16, hd=64, n=128, chunk=128)


@pytest.mark.parametrize("groups, geometry", [
    (1, {}), (2, {}), (8, {}), (1, SERVED), (8, SERVED),
    (1, dict(SERVED, heads=32))],
    ids=["1", "2", "8", "kernel_1", "kernel_8", "kernel_1_two_sub_blocks"])
def test_the_scan_in_groups_against_the_literal_recurrence(groups, geometry):
    """B and C in `groups` groups, head h reading group h // (H / G), and
    the gated norm over each group apart: the chunked form (40 positions in
    chunks of 16 through the XLA form; the served tile shape through the
    kernel, a group of 16 heads a grid step, of 2, and of 32 in two
    sub-blocks whose norm is one) against the reference's recurrence a position at a time, and
    the gradient of the input and of every weight against the
    reference's."""
    layer = mamba_layer(groups, **geometry)
    heads, hd, n = (layer.params[k] for k in ("heads", "head_dim", "d_state"))
    b, s, d = layer.inputs[0].spec.shape
    w = mamba_weights(layer)
    d_inner = heads * hd
    assert layer.weight_specs["in_proj"].shape \
        == (d, d_inner + d_inner + 2 * groups * n + heads)
    assert layer.weight_specs["conv_w"].shape == (4, d_inner + 2 * groups * n)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(b, s, d)), jnp.float32)
    op = get_op_def(OperatorType.MAMBA2)
    tel.ring_clear()

    def program(x, w):
        return op.lower(layer, [x, jnp.ones((b, s), jnp.int32)], w,
                        LoweringCtx())[0]

    def reference_of(x, w):
        return reference_mamba(x, w, groups, heads, hd, n)

    assert close(program(x, w), reference_of(x, w))
    path, = tel.ring_spans("ssm/scan_path")
    assert path.args["layer"] == "mix"
    assert path.args["path"] == ("kernel" if geometry else "xla")
    if geometry:
        assert (path.args["tile"], path.args["head_block"]) \
            == (128, min(16, heads // groups))
    g = jnp.asarray(np.random.default_rng(2).normal(size=(b, s, d)), jnp.float32)
    got = jax.grad(lambda x, w: (program(x, w) * g).sum(), argnums=(0, 1))(x, w)
    want = jax.grad(lambda x, w: (reference_of(x, w) * g).sum(),
                    argnums=(0, 1))(x, w)
    assert close(got[0], want[0], 10 * RTOL)
    for name in w:
        assert close(got[1][name], want[1][name], 10 * RTOL), name


def test_a_groups_heads_read_their_own_b_and_c():
    """A per-group loop: the layer with G groups is G layers of H / G heads
    and one group each, as far as the scan goes (`ssd_scan` over `[b, L, G,
    N]` against G calls over `[b, L, N]`)."""
    rng = np.random.default_rng(0)
    b, length, heads, hd, n, g = 2, 24, 8, 4, 8, 4
    u = jnp.asarray(rng.normal(size=(b, length, heads, hd)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (b, length, heads)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 4, heads), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, length, g, n)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, length, g, n)), jnp.float32)
    y, state = ssm_ops.ssd_scan(u, dt, a, bm, cm, 8)
    per = heads // g
    for i in range(g):
        hs = slice(i * per, (i + 1) * per)
        y_i, state_i = ssm_ops.ssd_scan(u[:, :, hs], dt[:, :, hs], a[hs],
                                        bm[:, :, i], cm[:, :, i], 8)
        assert close(y[:, :, hs], y_i, 1e-6) and close(state[:, hs], state_i, 1e-6)
    # and they are not the heads of ONE group: group 0's B and C for all
    y_one, _ = ssm_ops.ssd_scan(u, dt, a, bm[:, :, 0], cm[:, :, 0], 8)
    assert not close(y, y_one, 1e-2)


def test_the_grouped_norm_is_not_the_whole_width_norm():
    layer = mamba_layer(4)
    w = mamba_weights(layer)
    rng = np.random.default_rng(2)
    y = jnp.asarray(rng.normal(size=(2, 5, 64)) * np.repeat([0.1, 1, 3, 10], 16),
                    jnp.float32)
    z = jnp.asarray(rng.normal(size=(2, 5, 64)), jnp.float32)
    got = np.asarray(ssm_ops._gated(y, z, w["norm"], 4, 1e-5, jnp.float32))
    g = np.asarray(y, np.float64) * np.asarray(jax.nn.silu(z), np.float64)
    grouped = g.reshape(2, 5, 4, 16)
    grouped = grouped / np.sqrt((grouped ** 2).mean(-1, keepdims=True) + 1e-5)
    want = grouped.reshape(2, 5, 64) * np.asarray(w["norm"], np.float64)
    whole = g / np.sqrt((g ** 2).mean(-1, keepdims=True) + 1e-5) \
        * np.asarray(w["norm"], np.float64)
    assert close(got, want, 1e-5) and not close(got, whole, 0.1)
    one = np.asarray(ssm_ops._gated(y, z, w["norm"], 1, 1e-5, jnp.float32))
    assert close(one, whole, 1e-5)


@pytest.mark.parametrize("groups, geometry", [
    (1, {}), (4, {}), (4, dict(heads=8, hd=64, n=128, chunk=128))],
    ids=["1", "4", "kernel_4"])
def test_prefill_state_then_one_step_equals_the_sequence(groups, geometry):
    """The prefill twin hands out each row's state after its LAST REAL token
    (rows of unequal length: a right-padded `valid`), and the decode twin's
    one step from it gives the sequence form's output at the next position;
    the step reports the state it read and wrote for the live slots alone.
    At the served tile shape the prefill twin's scan is the kernel's."""
    b, s = 3, 24
    op = get_op_def(OperatorType.MAMBA2)
    whole = mamba_layer(groups, b=b, s=s, **geometry)
    heads, hd, n = (whole.params[k] for k in ("heads", "head_dim", "d_state"))
    w = mamba_weights(whole)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(b, s, 32)), jnp.float32)
    lengths = np.array([23, 9, 1])
    want = op.lower(whole, [x, jnp.ones((b, s), jnp.int32)], w, LoweringCtx())[0]
    valid = jnp.asarray(np.arange(s)[None] < lengths[:, None], jnp.int32)
    ctx = LoweringCtx()
    tel.ring_clear()
    op.lower(mamba_layer(groups, "state_out", b=b, s=s, **geometry), [x, valid],
             w, ctx)
    assert tel.ring_spans("ssm/scan_path")[0].args["path"] \
        == ("kernel" if geometry else "xla")
    st = ctx.new_state["mix"]
    conv_dim = heads * hd + 2 * groups * n
    assert st["ssm"].shape == (b, heads, hd, n)
    assert st["conv"].shape == (b, 3, conv_dim)
    assert ssm_ops._mamba_slot_state(whole) == {
        "ssm": ((heads, hd, n), jnp.float32), "conv": ((3, conv_dim), jnp.float32)}
    nxt = jnp.stack([x[r, n_] for r, n_ in enumerate(lengths)])[:, None]
    live = jnp.asarray([[1], [1], [0]], jnp.int32)
    dctx = LoweringCtx(state={"mix": st}, stats={})
    got = op.lower(mamba_layer(groups, "decode", b=b, s=1, **geometry),
                   [nxt, live], w, dctx)[0]
    for r, n_ in enumerate(lengths[:2]):
        assert close(got[r, 0], want[r, n_])
    # a slot that is not live keeps its state
    assert np.array_equal(dctx.new_state["mix"]["ssm"][2], st["ssm"][2])
    assert np.array_equal(dctx.new_state["mix"]["conv"][2], st["conv"][2])
    assert float(dctx.stats["ssm_state_bytes"]) \
        == 2 * 2 * (heads * hd * n * 4 + 3 * conv_dim * 4)


def test_a_served_prefill_layer_holds_no_chunk_by_chunk_value_outside_the_kernel(
        monkeypatch):
    """The program of a `state_out` layer at the served tile shape, lowered
    for the TPU (the kernel is then one `tpu_custom_call` and the text
    around it is all XLA's): no f32 value of three axes or more holds the
    tile twice, i.e. no `[.., heads, tile, tile]` decay mask or masked
    matrix crosses HBM. With the kernel refused the same text holds them,
    so the check sees what it is for."""
    from flexflow_tpu.kernels import ssd_scan as kernel

    tile = 128
    layer = mamba_layer(1, "state_out", b=2, s=3 * tile, heads=4, hd=64, n=128,
                        chunk=tile)
    weights = {k: jax.ShapeDtypeStruct(spec.shape, jnp.float32)
               for k, spec in layer.weight_specs.items()}

    def chunk_by_chunk():
        def program(x, valid, w):
            ctx = LoweringCtx()
            out = get_op_def(OperatorType.MAMBA2).lower(layer, [x, valid], w, ctx)
            return out[0], ctx.new_state["mix"]

        text = jax.jit(program).trace(
            jax.ShapeDtypeStruct((2, 3 * tile, 32), jnp.float32),
            jax.ShapeDtypeStruct((2, 3 * tile), jnp.int32),
            weights).lower(lowering_platforms=("tpu",)).as_text()
        shapes = {tuple(int(n) for n in dims.split("x"))
                  for dims in re.findall(r"tensor<([0-9x]+)xf32>", text)}
        return text, sorted(shape for shape in shapes
                            if len(shape) >= 3 and shape.count(tile) >= 2)

    monkeypatch.setattr(kernel, "_interpret", lambda: False)
    text, found = chunk_by_chunk()
    assert "tpu_custom_call" in text and "ff_ssd_chunk_scan" in text
    assert found == []
    monkeypatch.setattr(ssm_ops, "scan_tiles", lambda *a: None)
    text, found = chunk_by_chunk()
    assert "tpu_custom_call" not in text
    assert (2, 3, 1, 4, tile, tile) in found


def test_mamba_refuses_groups_that_do_not_divide_the_heads():
    with pytest.raises(ValueError, match="3 groups over 8 heads"):
        mamba_layer(3)


# ---------------------------------------------------------- the expert layer
D, LATENT, WIDTH, EXPERTS, TOP_K = 32, 16, 24, 16, 5


def moe_layer(held, shape=(2, 12), **extra):
    b, s = shape
    x = Tensor(TensorSpec((b, s, D), DataType.FLOAT), name="x")
    valid = Tensor(TensorSpec((b, s), DataType.INT32), name="valid")
    params = {"num_experts": EXPERTS, "top_k": TOP_K, "expert_width": WIDTH,
              "experts_held": held, "scoring": "sigmoid",
              "norm_topk_prob": True, "routed_scaling_factor": 5.0,
              "score_bias": True, "expert_activation": "relu2",
              "latent_size": LATENT}
    params.update(extra)
    layer = Layer(OperatorType.MOE_LAYER, params, [x, valid], name="moe")
    get_op_def(OperatorType.MOE_LAYER).infer(layer)
    return layer


def moe_weights(seed=0, bias=0.05):
    rng = np.random.default_rng(seed)

    def mat(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]),
                           jnp.float32)

    return {"router": mat(D, EXPERTS), "w_in": mat(EXPERTS, LATENT, WIDTH),
            "w_out": mat(EXPERTS, WIDTH, LATENT),
            "w_latent_in": mat(D, LATENT), "w_latent_out": mat(LATENT, D),
            "score_bias": jnp.asarray(rng.uniform(-bias, bias, EXPERTS),
                                      jnp.float32)}


def held_weights(w, held):
    lo, hi = held
    return dict(w, w_in=w["w_in"][lo:hi], w_out=w["w_out"][lo:hi])


def lower_moe(layer, x, w, valid=None, stats=None):
    valid = jnp.ones(x.shape[:2], jnp.int32) if valid is None else valid
    return get_op_def(OperatorType.MOE_LAYER).lower(
        layer, [x, valid], w, LoweringCtx(stats=stats))[0]


def numpy_layer(x, w, held):
    """The issue's sentences in float64, a token and an expert at a time."""
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    x = np.asarray(x, np.float64)
    out = np.zeros_like(x)
    lo, hi = held
    for idx in np.ndindex(x.shape[:-1]):
        row = x[idx]
        s = 1.0 / (1.0 + np.exp(-(row @ w["router"])))
        chosen = np.argsort(-(s + w["score_bias"]), kind="stable")[:TOP_K]
        gates = s[chosen] / (s[chosen].sum() + 1e-20) * 5.0
        latent = row @ w["w_latent_in"]
        acc = np.zeros(LATENT)
        for e, g in zip(chosen, gates):
            if lo <= e < hi:
                acc += g * (np.maximum(latent @ w["w_in"][e], 0.0) ** 2
                            @ w["w_out"][e])
        out[idx] = acc @ w["w_latent_out"]
    return out


def test_relu2_experts_in_the_latent_against_a_float64_loop():
    """Un-gated squared-ReLU experts (`w_in` one matrix wide) between the
    layer's two latent projections; the router reads the d-wide row."""
    held = (0, EXPERTS)
    layer = moe_layer(held)
    assert layer.weight_specs["w_in"].shape == (EXPERTS, LATENT, WIDTH)
    assert layer.weight_specs["w_out"].shape == (EXPERTS, WIDTH, LATENT)
    assert layer.weight_specs["w_latent_in"].shape == (D, LATENT)
    assert layer.weight_specs["w_latent_out"].shape == (LATENT, D)
    assert layer.weight_specs["router"].shape == (D, EXPERTS)
    w = moe_weights()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 12, D)), jnp.float32)
    assert close(lower_moe(layer, x, w), numpy_layer(x, w, held))
    with pytest.raises(ValueError, match="expert_activation 'gelu'"):
        moe_layer(held, expert_activation="gelu")


def test_the_shares_of_four_holders_add_up_to_the_uncut_layer():
    """Each holder's part is projected back from the latent by itself (the
    projection is linear and bias-free); the four parts, with the shared
    expert and the router counted once, add up to what the uncut reference
    gives for the whole layer."""
    w = moe_weights(seed=3)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, 12, D)), jnp.float32)
    shared = {"shared_in": jnp.asarray(rng.normal(size=(D, 40)) / 6, jnp.float32),
              "shared_out": jnp.asarray(rng.normal(size=(40, D)) / 6, jnp.float32)}
    ref_w = {"router": w["router"], "score_bias": w["score_bias"],
             "latent_in": w["w_latent_in"], "latent_out": w["w_latent_out"],
             "w_in": w["w_in"], "w_out": w["w_out"], **shared}
    hp = {"top_k": TOP_K, "held": (0, EXPERTS), "routed_scaling_factor": 5.0}
    with jax.default_matmul_precision("highest"):
        whole = reference.moe(x, ref_w, hp) + reference.shared(x, ref_w)
        once = reference.shared(x, ref_w)
    parts = []
    for lo in range(0, EXPERTS, 4):
        held = (lo, lo + 4)
        part = lower_moe(moe_layer(held), x, held_weights(w, held))
        with jax.default_matmul_precision("highest"):
            ref_part = reference.moe(
                x, dict(ref_w, w_in=w["w_in"][lo:lo + 4],
                        w_out=w["w_out"][lo:lo + 4]), dict(hp, held=held))
        assert close(part, ref_part)
        parts.append(part)
    assert close(sum(parts) + once, whole)
    assert not close(sum(parts[:3]) + once, whole, 0.01)


def test_the_bias_changes_who_is_chosen_and_no_gate_and_gates_sum_to_5():
    w = moe_weights(seed=5, bias=0.2)
    p = moe_layer((0, EXPERTS)).params
    x = np.random.default_rng(6).normal(size=(64, D)).astype(np.float32)
    scores = jnp.asarray(x) @ w["router"]
    gate, experts = moe_ops._choose(scores, w, p)
    assert np.allclose(np.asarray(gate).sum(-1), 5.0, rtol=1e-5)
    no_bias = dict(w, score_bias=jnp.zeros(EXPERTS))
    gate0, experts0 = moe_ops._choose(scores, no_bias, p)
    differs = (np.sort(experts, -1) != np.sort(experts0, -1)).any(-1)
    assert 0 < differs.sum()
    # an expert chosen with and without the bias has the same score, so the
    # same gate up to the normalisation over the (other) chosen
    s = np.asarray(jax.nn.sigmoid(scores))
    picked = np.take_along_axis(s, np.asarray(experts), -1)
    assert np.allclose(np.asarray(gate), picked / picked.sum(-1, keepdims=True) * 5,
                       rtol=1e-5)
    ref_gate, ref_experts = reference.route(
        jnp.asarray(x), w, {"top_k": TOP_K, "routed_scaling_factor": 5.0})
    assert np.array_equal(np.sort(ref_experts, -1), np.sort(experts, -1))
    assert np.allclose(np.sort(ref_gate, -1), np.sort(gate, -1), rtol=1e-5)


def test_a_decode_step_of_352_pairs_takes_the_whole_block():
    """16 slots x top-22 = 352 pairs reach MOE_MIN_RUNG_ROWS, but a rung is a
    PART of the block (1/16 = 22 rows, 1/4 = 88) and both lie under it: the
    step gets no ladder and lowers with no conditional."""
    assert moe_ops._row_capacities(16 * 22) == [16 * 22]
    assert moe_ops._row_capacities(4096 * 22) == [0, 5632, 22528, 90112]
    layer = moe_layer((0, 4), shape=(16, 1), top_k=22, num_experts=64)
    w = {k: jnp.zeros(s.shape, jnp.float32) for k, s in layer.weight_specs.items()}
    text = jax.jit(lambda x, w: lower_moe(layer, x, w)).lower(
        jnp.zeros((16, 1, D)), w).as_text()
    assert not re.search(r"stablehlo\.(case|if)\b", text)


@pytest.mark.parametrize("live", (0, 3, 16), ids=("rung_0", "rung_88", "all"))
def test_352_pairs_through_each_rung_equal_the_whole_block(live, monkeypatch):
    """With rungs a quarter as small allowed, a step of 16 slots x top-22
    over 64 experts of which 16 are held gets the ladder [0, 22, 88, 352];
    each rung (no slot live, 3 live: about 17 held pairs, all 16: about 88
    of which some steps overflow to the whole block) gives what the
    whole-block path gives, latent projections inside the branch."""
    layer = moe_layer((0, 16), shape=(16, 1), top_k=22, num_experts=64)
    rng = np.random.default_rng(live)
    w = {k: jnp.asarray(rng.normal(size=s.shape) / 4, jnp.float32)
         for k, s in layer.weight_specs.items()}
    x = jnp.asarray(rng.normal(size=(16, 1, D)), jnp.float32)
    valid = jnp.asarray(np.arange(16)[:, None] < live, jnp.int32)
    want = lower_moe(layer, x, w, valid)
    monkeypatch.setattr(moe_ops, "MOE_MIN_RUNG_ROWS", 16)
    assert moe_ops._row_capacities(352) == [0, 22, 88, 352]
    stats = {}
    got = lower_moe(layer, x, w, valid, stats)
    assert close(got, want, 1e-6)
    held = int(stats["moe_held_pairs"])
    rung = int(stats["moe_rows_computed"])
    assert rung == min(c for c in (0, 22, 88, 352) if c >= held)
    assert (live == 0) == (rung == 0) and int(stats["moe_rows_static"]) == 352
    if live:
        assert np.abs(np.asarray(got)[:live]).max() > 0
        assert not np.asarray(got)[live:].any()


def test_only_what_is_set_enters_the_params_of_either_op():
    m = FFModel(ffconfig(2))
    x = m.create_tensor([2, 4, 16], name="x")
    m.moe_layer(x, 8, 2, 8, name="plain")
    m.moe_layer(x, 8, 2, 8, expert_activation="relu2", latent_size=4,
                name="latent")
    plain, latent = m.layers[-2], m.layers[-1]
    assert set(plain.params) == {"num_experts", "top_k", "expert_width",
                                 "experts_held"}
    assert set(plain.weight_specs) == {"router", "w_in", "w_out"}
    assert plain.weight_specs["w_in"].shape == (8, 16, 16)
    assert set(latent.params) - set(plain.params) == {"expert_activation",
                                                      "latent_size"}
    assert latent.weight_specs["w_in"].shape == (8, 4, 8)
    flops_of = get_op_def(OperatorType.MOE_LAYER).flops
    assert flops_of(plain) == 2 * 8 * (16 * 8) + 2 * 8 * 2 * 3 * 16 * 8
    assert flops_of(latent) == 2 * 8 * (16 * 8 + 2 * 16 * 4) \
        + 2 * 8 * 2 * 2 * 4 * 8
    mamba = mamba_layer(4)
    assert get_op_def(OperatorType.MAMBA2).flops(mamba) == \
        2 * 80 * (32 * (64 + 128 + 8) + 64 * 32) + 4 * 80 * 8 * 8 * 8


# ------------------------------------------------------------------ serving
def engine_for(g, **compile_kw):
    model = FFModel(ffconfig(SLOTS))
    build_nemotron_h(model, g, batch=SLOTS)
    eng = compile_serving(model, max_batch_slots=SLOTS, max_decode_len=16,
                          kv_page_size=8, **compile_kw)
    eng.init(seed=3)
    return eng


def served(g):
    """The shared harness on this family's engine, input builders and
    reference."""
    eng = engine_for(g)

    kernel = ssm_ops.step_path(g.mamba_heads, g.mamba_head_dim,
                               g.mamba_d_state, g.mamba_n_groups)["path"] \
        == "kernel"

    def step_stats(s, stats):
        # two state-holding layers, the live slots' state read and written
        per_slot = eng.kv_spec.state_bytes_per_slot
        assert float(stats["ssm_state_bytes"]) == 2 * len(s.seqs) * per_slot
        # the step kernel's grid: a (layer, live slot) pair a step, or none
        assert int(stats["ssm_step_kernel_slots"]) \
            == (2 * len(s.seqs) if kernel else 0)

    return Served(eng, lambda ids: reference_logits(eng.params, g, ids),
                  valid_prompt_inputs, valid_step_inputs, RTOL,
                  step_stats=step_stats)


@pytest.mark.parametrize("heads, d_state", ((8, 16), (32, 16), (8, 128)),
                         ids=("4_a_kv_head", "16_a_kv_head", "step_kernel"))
def test_prefill_then_decode_through_cache_and_state_equals_the_full_forward(
        heads, d_state):
    """Logits, not tokens. Prompts of different lengths in one padded wave
    (one of 2 tokens, one past four pages): K/V pages and recurrent state
    are committed at each row's last real token; a slot that sits out the
    second wave keeps its state and decodes correctly; a second wave into a
    freed slot and into one never used. 2 K/V heads under 8 or 32 query
    heads (16 a K/V head, as published) in the prefill and the paged decode.
    With a state of 128 a head the decode step's recurrence is the step
    kernel (interpreted; four B/C groups of eight heads), over the live slots
    alone: `served` holds its counter to the live slots a step."""
    g = NemotronHConfig.tiny(seq=48)
    g.heads = heads
    g.mamba_d_state = d_state
    if d_state == 128:      # a B/C group of eight heads: whole sublane tiles
        g.mamba_heads = 32
    rng = np.random.default_rng(7)
    s = served(g)

    def prompt(n):
        return [int(t) for t in rng.integers(0, g.vocab, n)]

    s.wave({0: prompt(2), 1: prompt(19), 2: prompt(33)})
    s.decode(3)
    s.evict(1)
    s.wave({1: prompt(9), 3: prompt(17)})       # 0 and 2 sit it out
    s.decode(3)
    assert s.checked == 3 + 3 * 3 + 2 + 4 * 3
    assert len(s.seqs[0]) == 2 + 1 + 6 and len(s.seqs[1]) == 9 + 1 + 3


@pytest.mark.parametrize("d_state, want", [
    (16, {"path": "xla", "groups": 4}),
    (128, {"path": "kernel", "head_block": 32, "groups": 4})],
    ids=["tiny", "whole-lanes"])
def test_the_decode_step_on_either_path_through_the_scheduler(d_state, want):
    """The form of the decode step's recurrence is chosen from the state's
    width and the heads of a B/C group (`ssm_ops.step_path`): through the
    scheduler either form serves the reference's argmax and reports itself
    and its counter."""
    g = NemotronHConfig.tiny(seq=48)
    g.mamba_d_state = d_state
    if d_state == 128:      # a B/C group of eight heads: whole sublane tiles
        g.mamba_heads = 32
    eng = engine_for(g)
    scheduler_reports_the_step_path(
        eng, lambda ids: reference_logits(eng.params, g, ids),
        valid_prompt_inputs, valid_step_inputs, g.vocab, want,
        {"l0_mamba", "l2_mamba"})


def test_the_cache_comes_from_the_layers_own_declarations():
    """2 of the tiny model's 5 layers keep recurrent state, 1 pages K/V, 2
    keep nothing: pool geometry, state bytes a slot, and the search's cache
    term, from what the layers declare."""
    g = NemotronHConfig.tiny(seq=48)
    eng = engine_for(g)
    assert eng.attn_layers == ["l3_attn"]
    assert page_geometry(eng.decode_model) == {"heads": 2, "head_dim": 8}
    rec = recurrent_layers(eng.decode_model)
    assert list(rec) == ["l0_mamba", "l2_mamba"]
    conv_dim = g.conv_dim
    assert conv_dim == 128 + 2 * 4 * 16
    assert rec["l0_mamba"] == {"ssm": ((8, 16, 16), jnp.float32),
                               "conv": ((3, conv_dim), jnp.float32)}
    per_slot = 2 * (8 * 16 * 16 * 4 + 3 * conv_dim * 4)
    spec = eng.kv_spec
    assert (spec.layers, spec.heads, spec.head_dim) == (1, 2, 8)
    assert spec.state_bytes_per_slot == per_slot \
        == flops.state_bytes_per_slot(dict(file_config(g))) \
        + 2 * 3 * conv_dim * 2          # the file's tail is bf16, this f32
    assert eng.kv.state["l3_attn"]["k"].shape == (SLOTS * 8 + 1, 8, 2 * 8)
    assert eng.kv.state_kinds == "paged_kv+recurrent"
    assert spec.total_bytes() == sum(
        x.size * x.dtype.itemsize for n in rec
        for x in eng.kv.state[n].values()) + 2 * eng.kv.state["l3_attn"]["k"].nbytes
    published = KVCacheSpec(layers=1, heads=2, head_dim=128, slots=16,
                            pages_per_slot=80, page_size=16, itemsize=2,
                            state_bytes_per_slot=5 * (128 * 64 * 128 * 4
                                                      + 3 * 10240 * 2))
    assert published.row_widths() == {"k": 256, "v": 256}
    assert published.page_bytes() == 2 * 16 * 256 * 2
    assert published.total_bytes() == 2 * (16 * 80 + 1) * 16 * 256 * 2 \
        + 16 * 5 * 4255744
    assert 0.36e9 < published.total_bytes() < 0.37e9


def test_scheduler_serves_it_and_reports_its_spans_and_counters(tmp_path):
    """Through ContinuousBatchingScheduler, with nothing model-specific in
    it: every served token is the reference's argmax over the request's own
    tokens, and the spans and counters the benchmark reads are there."""
    import trace_report

    g = NemotronHConfig.tiny(seq=48)
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
            eng, eng.params, valid_prompt_inputs, valid_step_inputs, eos_id=None)
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
        == (1, 2, 2)
    assert (made["experts_latent_dim"], made["ssm_groups"]) == (32, 4)
    assert (made["experts_held"], made["experts_routed_over"]) == (4, 16)
    assert made["paged_state"] == "paged_kv"
    assert made["state_bytes_per_slot"] == eng.kv_spec.state_bytes_per_slot
    assert len(spans["serve/prefill/commit_state"]) == sched.prefills
    steps = 0
    for a in spans["serve/decode/window_sync"]:
        steps += a["steps"]
        assert 0 <= a["moe_held_pairs"] <= a["moe_routed_pairs"] \
            <= a["steps"] * 2 * SLOTS * g.experts_per_tok
        assert a["moe_experts_hit"] <= a["steps"] * 2 * 4
        assert 0 < a["ssm_state_bytes"] <= a["steps"] * 2 * SLOTS \
            * eng.kv_spec.state_bytes_per_slot
    assert steps == sched.decode_steps
    wave = spans["serve/prefill/device_wait"][0]
    assert wave["moe_routed_pairs"] == 2 * g.experts_per_tok * sum(
        len(r.prompt) for r in reqs[:SLOTS])
    assert "ssm_state_bytes" not in wave
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        trace_report.render(str(next(tmp_path.glob("*.jsonl"))))
    text = out.getvalue()
    assert "experts_latent_dim=32" in text and "ssm_groups=4" in text
    assert re.search(r"\[serve\] state-space layers in serve/decode/window_sync: "
                     r"[\d.]+ MB of recurrent state read and written a step, "
                     r"[\d.]+ held experts hit", text)


def test_what_per_slot_state_does_not_support_fails_loudly():
    g = NemotronHConfig.tiny(seq=48)

    def model(**kw):
        m = FFModel(ffconfig(SLOTS, **kw))
        build_nemotron_h(m, g, batch=SLOTS)
        return m

    def serve(m, **kw):
        return compile_serving(m, max_batch_slots=SLOTS, max_decode_len=16,
                               kv_page_size=8, **kw)

    with pytest.raises(NotImplementedError, match="2 mamba2 layers.*host KV tier"):
        serve(model(kv_host_pages=8))
    with pytest.raises(NotImplementedError, match="recurrent state.*speculative"):
        serve(model(), draft=model(), spec_tokens=2)
    eng = serve(model())
    eng.init(seed=3)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        ContinuousBatchingScheduler(
            eng, eng.params, valid_prompt_inputs, valid_step_inputs,
            handoff=lambda req, payload: None)
    # a quantized K/V pool beside the (unquantized) recurrent state works
    q = serve(model(), kv_cache_dtype="int8")
    assert q.kv.state["l3_attn"]["k"].dtype == jnp.int8
    assert q.kv.state["l0_mamba"]["ssm"].dtype == jnp.float32


# ------------------------------------------------- the models that were there
def serving_fingerprints(build):
    m = FFModel(FFConfig(batch_size=4, only_data_parallel=True))
    build(m)
    return [graph_fingerprint(m)] + [
        graph_fingerprint(clone_for_serving(m, kind, 4)[0])
        for kind in ("prefill", "decode")]


BUILDERS = {
    "granite": (lambda m: build_granite_hybrid(m, GraniteHybridConfig.tiny(),
                                               batch=4), 2),
    "gigachat": (lambda m: build_deepseek_v3(m, DeepseekV3Config.tiny(),
                                             batch=4), 3),
    "gpt2": (lambda m: build_gpt2(m, GPT2Config.tiny(), batch=4), 2)}


@pytest.mark.parametrize("name, want", [
    ("granite", ["8b7a580f398078156384a364", "a7f4b2a09b01abb4d69b112c",
                 "f384227b8b52f51c125a423d"]),
    ("gpt2", ["ac4194f91a1d6595b2d39edd", "7707646a5c42ff7f8d94f5a3",
              "007d0b7bd9f8c75f1880699d"])])
def test_the_other_models_graphs_keep_their_fingerprints(name, want):
    """The training graph and both serving clones as PR 31's tree hashed
    them: `n_groups` was a param of every Mamba layer already, and the
    expert layer's activation and latent enter a graph only where set."""
    assert serving_fingerprints(BUILDERS[name][0]) == want
    g = NemotronHConfig.tiny()
    assert serving_fingerprints(
        lambda m: build_nemotron_h(m, g, batch=4))[0] not in want


@pytest.mark.parametrize("name, want", [
    ("granite", ("51a714e712928d03a37fb7d2", "0bda3c11ca01d9853ea14ab2")),
    ("gigachat", ("36f4009cc3f4d59289b6ea26", "24ba0154583078d90c426c1c")),
    ("gpt2", ("3b829f8cf8936d6cca5c5efd", "8cb59350cdb130454afef717"))])
def test_the_other_models_serving_programs_lower_to_the_parents(name, want,
                                                                monkeypatch):
    """sha256 of the StableHLO of the scheduler's prefill program and of the
    decode step, tiny size, as commit 35217a7 (PR 33) lowered them: a layer
    with one B/C group, gated-SiLU experts at the layer's own width and no
    latent takes the code it took. The one difference, granite's decode
    step, is the new `ssm_state_bytes` counter alone: with the report taken
    out the step lowers to the parent's text. GPT-2's prefill hash is PR
    63's (c0d11d3b.. was PR 36's): its causal attention goes through the
    flash kernel (interpreted here), whose schedule under the diagonal PR 36
    rewrote and whose layout at the boundary PR 63 changed (four heads of
    64 are read two a 128-lane block from the projections `[b, s, h * d]`
    as they lie, so the layer splits and swaps nothing; a wave, which nobody
    differentiates, writes no `lse`); the decode step, which does not run the kernel, kept
    PR 33's. Granite's prefill
    hash is PR 39's, on purpose: that PR rewrote the Mamba-2 op's sequence
    form (this size takes its XLA form: the scan batched over the chunks
    with the heads leading, the conv and the gate over the wave whole); its
    decode hash is still PR 33's (but for the counter above), as are
    GigaChat's pair and GPT-2's decode step: the decode form and the other
    models took the code they took."""
    monkeypatch.setattr(ssm_ops, "_report_state_bytes", lambda *a: None)
    # and PR 47's `moe_experts_held`, a constant a layer beside the seven:
    # with the report taken out the programs lower to the pinned text
    monkeypatch.setattr(moe_ops, "_report_experts_held", lambda *a: None)
    # and PR 48's `moe_step_kernel_experts`, 0 in every block of these
    monkeypatch.setattr(moe_ops, "_report_step_kernel", lambda *a: None)
    # and PR 57's `moe_rows_kernel`, 0 at these widths (no whole slab)
    monkeypatch.setattr(moe_ops, "_report_rows_kernel", lambda *a: None)
    # and PR 50's `ssm_step_kernel_slots`, 0 at d_state 16 (the XLA form)
    monkeypatch.setattr(ssm_ops, "_report_step_kernel", lambda *a: None)
    build, inputs = BUILDERS[name]
    model = FFModel(FFConfig(batch_size=4, seed=3, strategy_cache=False,
                             log_level="warning", mesh_shape={"data": 1}))
    build(model)
    seq = model.input_tensors[0].spec.shape[1]
    eng = compile_serving(model, max_batch_slots=4, max_decode_len=16,
                          kv_page_size=8)
    eng.init(seed=3)
    wave = [jnp.zeros((4, seq), jnp.int32)] * inputs
    step = [jnp.zeros((4, 1), jnp.int32)] * inputs
    texts = (eng._prefill_first_tokens_jit.lower(
                 eng.params, wave, jnp.zeros((4,), jnp.int32)).as_text(),
             eng._decode_jit.lower(eng.params, eng.kv.state, step).as_text())
    assert tuple(hashlib.sha256(t.encode()).hexdigest()[:24]
                 for t in texts) == want


# ------------------------------------------------------- counts and the file
def test_the_configuration_file_against_the_catalog_and_the_issue():
    cfg = mf.read_named("configs", PUBLISHED)
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts", "vocab_size"]
    assert cfg["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512, "vocab_size": 131072,
        "hybrid_override_pattern": cfg["published"]["hybrid_override_pattern"]}
    full = cfg["published"]["hybrid_override_pattern"]
    assert len(full) == 88 and full.startswith(cfg["hybrid_override_pattern"])
    assert (full.count("M"), full.count("*"), full.count("E")) == (40, 8, 40)
    cut = cfg["hybrid_override_pattern"]
    assert len(cut) == cfg["num_hidden_layers"] == 11
    assert (cut.count("M"), cut.count("*"), cut.count("E")) == (5, 1, 5)
    widths = {"hidden_size": 4096, "moe_latent_size": 1024,
              "moe_intermediate_size": 2688, "num_experts_per_tok": 22,
              "moe_shared_expert_intermediate_size": 5376,
              "mamba_num_heads": 128, "mamba_head_dim": 64, "n_groups": 8,
              "ssm_state_size": 128, "chunk_size": 128, "head_dim": 128,
              "num_attention_heads": 32, "num_key_value_heads": 2}
    assert {k: cfg[k] for k in widths} == widths
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(r for r in map(json.loads, catalog.read_text().splitlines())
                   if r["name"] == PUBLISHED)
        assert cfg["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == set(cfg["reduced"])


def test_flop_and_byte_functions_against_hand_counts_and_the_program():
    for name in (PUBLISHED, "nemotron-h-tiny"):
        cfg = mf.read_named("configs", name)
        g = family.program_config(cfg)
        assert flops.train_flops_per_token(cfg, g.seq) == g.flops_per_token()
        assert flops.param_count(cfg) == g.param_count()
    cfg = mf.read_named("configs", PUBLISHED)
    # the issue's arithmetic
    assert round(flops.layer_dense_params(cfg, "mamba") / 1e6, 2) == 109.58
    assert flops.layer_dense_params(cfg, "mamba") + flops.small_params(
        cfg, "mamba") - 4096 == 4096 * 18560 + 8192 * 4096 + 5 * 10240 \
        + 3 * 128 + 8192
    assert flops.layer_dense_params(cfg, "attention") == 2 * 4096 * 4096 \
        + 2 * 4096 * 256
    assert flops.layer_dense_params(cfg, "experts") == 4096 * 512 \
        + 2 * 4096 * 1024 + 2 * 4096 * 5376
    assert flops.expert_params(cfg) == 2 * 1024 * 2688
    assert round(flops.param_count(cfg) / 1e6) == 4648
    assert abs(flops.param_count(cfg) / 4648e6 - 1) < 1e-3
    assert round(2 * flops.param_count(cfg) / 1e9, 2) == 9.30
    whole = dict(cfg, hybrid_override_pattern=cfg["published"][
        "hybrid_override_pattern"], n_routed_experts=512, vocab_size=131072)
    assert round(flops.param_count(whole) / 1e9, 2) == 120.67
    assert flops.state_bytes_per_slot(cfg) == 5 * (128 * 64 * 128 * 4
                                                   + 3 * 10240 * 2)
    assert flops.kv_bytes_per_token(cfg) == 2 * 2 * 128 * 2
    tiny = NemotronHConfig.tiny()
    cm = compiled(tiny)
    held = sum(int(np.prod(w.shape)) for lw in cm.params.values()
               for w in lw.values())
    assert held == tiny.param_count() == flops.param_count(file_config(tiny))
    bias = np.asarray(cm.params["l1_moe"]["score_bias"])
    assert bias.dtype == np.float32 and 0 < np.abs(bias).max() <= 0.02
    chat = mf.read_named("traffic", "serve-chat")
    system = {"max_batch_slots": 16}
    counters = {"moe_routed_pairs": 16 * 22 * 5, "moe_experts_hit": 5 * 64,
                "ssm_state_bytes": 2.0 * 16 * flops.state_bytes_per_slot(cfg)}
    step = flops.decode_step_need(cfg, system, chat, counters)
    dense = 5 * 109.64e6 + 35.66e6 + 5 * 54.6e6 + 134.2e6
    assert step["flops"] == 0.0
    assert step["bytes"] == pytest.approx(
        2 * (dense + 5 * 64 * 5.505e6) + 0.681e9 + 16 * 16 * 1024, rel=2e-3)
    assert 6.1e9 < step["bytes"] < 6.4e9
    wave = flops.prefill_wave_need(cfg, system, chat, {"moe_held_pairs": 5 * 22528})
    positions = 16 * 1024
    assert wave["flops"] == pytest.approx(
        2 * positions * (5 * 109.58e6 + 35.65e6 + 5 * 54.53e6)
        + 2 * 5 * 22528 * 5.505e6 + 16 * 4 * 524800 * 4096
        + 5 * positions * 4 * 8192 * 128 + 2 * 16 * 4096 * 32768, rel=1e-3)
    assert 28e12 < wave["flops"] < 31e12
