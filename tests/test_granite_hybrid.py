"""The hybrid state-space / attention decoder with routed experts
(flexflow_tpu/models/granite_hybrid.py, ops/ssm_ops.py, ops/moe_ops.py's
moe_layer, grouped K/V heads and `scale` in ops/attention_ops.py, the
recurrent state beside the paged pools in serving/) against its plain
reference (benchmarks/harness/reference_granitemoehybrid.py), at a small
size on the CPU with seeded random weights.

Tolerance: program and reference both compute in float32, so they differ by
the order of their sums alone (the scan by chunks against the literal
recurrence, the grouped product against a loop over experts, the cache
against one full pass): about 1e-6 of the result's scale. RTOL 1e-4 leaves
two orders for that and none for a fault: a wrong mask, scale, head group or
state position is off by 1e-2 and more, and the same program computing in
bfloat16 is off by about 1e-2 (test_bf16_program_fails_the_f32_tolerance).
"""

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
from flexflow_tpu.models import GraniteHybridConfig, build_granite_hybrid  # noqa: E402
from flexflow_tpu.ops import get_op_def, moe_ops, ssm_ops  # noqa: E402
from flexflow_tpu.ops.op_type import OperatorType  # noqa: E402
from flexflow_tpu.ops.registry import LoweringCtx  # noqa: E402
from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,  # noqa: E402
                                  compile_serving, valid_prompt_inputs,
                                  valid_step_inputs)
from families import granitemoehybrid as family  # noqa: E402
from harness import reference_granitemoehybrid as reference  # noqa: E402
from served import Served, scheduler_reports_the_step_path  # noqa: E402

RTOL = 1e-4
SLOTS = 4


def file_config(g: GraniteHybridConfig) -> dict:
    """`g` in the keys of a configuration file, as the family reads them."""
    lo, hi = g.experts_held
    assert lo == 0
    return {"layer_types": list(g.layer_types), "hidden_size": g.d_model,
            "num_attention_heads": g.heads, "num_key_value_heads": g.kv_heads,
            "mamba_n_heads": g.mamba_heads, "mamba_d_head": g.mamba_head_dim,
            "mamba_d_state": g.mamba_d_state, "mamba_n_groups": 1,
            "mamba_d_conv": g.mamba_d_conv, "mamba_chunk_size": g.mamba_chunk,
            "num_experts_per_tok": g.experts_per_tok, "num_local_experts": hi,
            "published": {"num_local_experts": g.num_experts},
            "intermediate_size": g.expert_width,
            "shared_intermediate_size": g.shared_width,
            "vocab_size": g.vocab,
            "embedding_multiplier": g.embedding_multiplier,
            "residual_multiplier": g.residual_multiplier,
            "attention_multiplier": g.attention_multiplier,
            "logits_scaling": g.logits_scaling, "rms_norm_eps": g.eps,
            "assumed": {"serve_positions": g.seq, "weights_dtype": g.dtype}}


def ffconfig(batch, **kw):
    return FFConfig(batch_size=batch, seed=3, strategy_cache=False,
                    log_level="warning", mesh_shape={"data": 1}, **kw)


def compiled(g, batch=2, lr=1.0, **kw):
    model = FFModel(ffconfig(batch, **kw))
    build_granite_hybrid(model, g, batch=batch)
    cm = model.compile(SGDOptimizer(lr=lr),
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


# ------------------------------------------------------------------ forward
def test_forward_logits_against_the_reference():
    g = GraniteHybridConfig.tiny(seq=40)        # 2.5 chunks of 16
    cm = compiled(g)
    ids = tokens(g, 2)
    got = cm.forward(ids, np.ones_like(ids))
    cfg = file_config(g)
    want = reference.forward(family.reference_params(cm.params, cfg), ids,
                             family.hyper(cfg))
    assert got.shape == (2, g.seq, g.vocab)
    assert close(got, want)


def test_bf16_program_fails_the_f32_tolerance():
    """The comparison is tight enough to catch a lower precision."""
    g = GraniteHybridConfig.tiny(seq=40)
    cm = compiled(g, compute_dtype="bfloat16")
    ids = tokens(g, 2)
    got = cm.forward(ids, np.ones_like(ids))
    cfg = file_config(g)
    want = reference.forward(family.reference_params(cm.params, cfg), ids,
                             family.hyper(cfg))
    assert not close(got, want)
    assert close(got, want, rtol=0.2)       # lower precision, not another model


def test_fit_first_loss_and_gradients_against_the_reference():
    """Three steps of fit: the first step's loss and, through plain SGD
    (p1 = p0 - lr * grad), its gradients for one layer of each kind against
    jax.grad of the reference's next_token_loss; then the loss falls."""
    g = GraniteHybridConfig.tiny(seq=24)
    lr = 1.0
    cm = compiled(g, lr=lr)
    cfg, hp = file_config(g), family.hyper(file_config(g))
    ids = tokens(g, 2)
    labels = np.roll(ids, -1, axis=1)
    x = [ids, np.ones_like(ids)]
    before = jax.tree_util.tree_map(np.asarray, cm.params)
    want_loss, want_grad = jax.value_and_grad(reference.next_token_loss)(
        family.reference_params(before, cfg), ids, labels, hp)
    losses = [cm.fit(x, labels, epochs=1, verbose=False)[-1]["loss"]]
    after = jax.tree_util.tree_map(np.asarray, cm.params)
    assert abs(losses[0] - float(want_loss)) <= RTOL * float(want_loss)
    layer = {i: want_grad["layers"][i] for i in range(g.layers)}
    checks = [("l0_mamba", "in_proj", layer[0]["in_proj"]),
              ("l0_mamba", "A_log", layer[0]["A_log"]),
              ("l0_mamba", "conv_w", layer[0]["conv_w"]),
              ("l2_attn", "wk", layer[2]["wk"]),
              ("l1_moe", "w_in", layer[1]["w_in"]),
              ("l1_moe", "router", layer[1]["router"]),
              ("l1_shared_out", "kernel", layer[1]["shared_out"])]
    for name, w, want in checks:
        got = (before[name][w] - after[name][w]) / lr
        # a step of lr 1 is read back from f32 weights: their rounding, at
        # the weights' scale, is the floor of this comparison
        floor = 4e-7 * float(np.abs(before[name][w]).max())
        assert float(np.abs(got - np.asarray(want)).max()) <= \
            RTOL * float(np.abs(want).max()) + floor, (name, w)
    cm2 = compiled(g, lr=0.05)
    losses = [cm2.fit(x, labels, epochs=1, verbose=False)[-1]["loss"]
              for _ in range(3)]
    assert losses[2] < losses[0]


# --------------------------------------------------------------------- scan
def recurrence(u, dt, a, bm, cm):
    """S_t = exp(dt_t a) S_{t-1} + dt_t u_t (x) B_t; y_t = S_t C_t, one
    position at a time, in float64; bm, cm [b, L, N], or [b, L, G, N] with
    head h reading group h // (H / G)."""
    b, length, heads, hd = u.shape
    if bm.ndim == 3:
        bm, cm = bm[:, :, None], cm[:, :, None]
    bm, cm = (np.repeat(t, heads // t.shape[2], axis=2) for t in (bm, cm))
    state = np.zeros((b, heads, hd, bm.shape[-1]))
    ys = []
    for t in range(length):
        state = state * np.exp(dt[:, t] * a)[:, :, None, None] \
            + (dt[:, t, :, None] * u[:, t])[..., None] * bm[:, t, :, None, :]
        ys.append(np.einsum("bhpn,bhn->bhp", state, cm[:, t]))
    return np.stack(ys, axis=1), state


def recurrence_in_jax(u, dt, a, bm, cm):
    """`recurrence` as a `lax.scan`, in the arrays' own type, for its
    gradient."""
    heads = u.shape[2]
    if bm.ndim == 3:
        bm, cm = bm[:, :, None], cm[:, :, None]
    bm, cm = (jnp.repeat(t, heads // t.shape[2], axis=2) for t in (bm, cm))

    def step(state, xs):
        u_t, dt_t, b_t, c_t = xs
        state = state * jnp.exp(dt_t * a)[:, :, None, None] \
            + (dt_t[..., None] * u_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    state, ys = jax.lax.scan(
        step, jnp.zeros(u.shape[:1] + u.shape[2:] + bm.shape[-1:], u.dtype),
        tuple(jnp.moveaxis(t, 1, 0) for t in (u, dt, bm, cm)))
    return jnp.moveaxis(ys, 0, 1), state


# (length, heads, head_dim, d_state, groups, chunk, operands' type, path):
# the tiny geometry of this file's model, which the XLA form takes, at
# lengths that are, and are not, multiples of the chunk, and one shorter than
# a chunk; and the served cells' tile shape in small (whole lanes of heads
# and state, P 64, N 128: the kernel, interpreted here) at tile 128 and 256,
# one group and eight, a length no tile divides, a group of more heads than a
# grid step computes (32: two sub-blocks of 16), bf16 operands
SCANS = {
    "32": (32, 3, 4, 5, 1, 16, np.float32, "xla"),
    "16": (16, 3, 4, 5, 1, 16, np.float32, "xla"),
    "37": (37, 3, 4, 5, 1, 16, np.float32, "xla"),
    "5": (5, 3, 4, 5, 1, 16, np.float32, "xla"),
    "tile128": (256, 4, 64, 128, 1, 128, np.float32, "kernel"),
    "tile256_ragged": (300, 4, 64, 128, 1, 256, np.float32, "kernel"),
    "tile128_groups8_ragged": (150, 16, 64, 128, 8, 128, np.float32, "kernel"),
    "tile128_two_sub_blocks": (256, 32, 64, 128, 1, 128, np.float32, "kernel"),
    "tile256_bf16": (256, 4, 64, 128, 1, 256, jnp.bfloat16, "kernel"),
    "tile128_groups8_bf16": (128, 16, 64, 128, 8, 128, jnp.bfloat16, "kernel"),
}
# bf16 operands: the masked matrix and the weighted inputs are rounded to 8
# bits before their products (2^-9 an element, sums of a few hundred): 1e-2
# of the result's scale holds them and no fault (a wrong mask is off by 1)
RTOL_BF16 = 1e-2


@pytest.mark.parametrize("case", list(SCANS))
def test_scan_by_chunks_against_the_literal_recurrence(case):
    """`y`, the last state, the state of a row that ends early (dt = 0
    after its last real step) and, where the operands are f32, the gradient
    of every operand, against the literal recurrence in float64."""
    length, heads, hd, n, groups, chunk, dtype, path = SCANS[case]
    rng = np.random.default_rng(length)
    b = 2
    lead = (b, length) + ((groups,) if groups > 1 else ())
    u = rng.normal(size=(b, length, heads, hd)).astype(np.float32)
    dt = rng.uniform(0.01, 0.5, (b, length, heads)).astype(np.float32)
    dt[1, length // 2:] = 0.0       # a row that ends early
    a = -rng.uniform(1.0, 8.0, heads).astype(np.float32)
    bm = rng.normal(size=lead + (n,)).astype(np.float32)
    cm = rng.normal(size=lead + (n,)).astype(np.float32)
    u, bm, cm = (np.asarray(jnp.asarray(t, dtype), np.float32) for t in (u, bm, cm))
    operands = (jnp.asarray(u, dtype), jnp.asarray(dt), jnp.asarray(a),
                jnp.asarray(bm, dtype), jnp.asarray(cm, dtype))
    assert ssm_ops.scan_path(operands[0], operands[3].reshape(b, length, groups, n),
                             chunk)["path"] == path
    y, state = ssm_ops.ssd_scan(*operands, chunk=chunk)
    assert y.dtype == state.dtype == jnp.float32
    f64 = [t.astype(np.float64) for t in (u, dt, a, bm, cm)]
    want_y, want_state = recurrence(*f64)
    rtol = RTOL if dtype == np.float32 else RTOL_BF16
    assert close(y, want_y, rtol) and close(state, want_state, rtol)
    # the masked row's state is the state after its last real step
    cut = length // 2
    _, half = recurrence(*(t[1:, :cut] for t in f64[:2]), f64[2],
                         *(t[1:, :cut] for t in f64[3:]))
    assert close(state[1:], half, rtol)
    if dtype != np.float32:
        return
    # the gradient (the kernel's is the XLA form's, by its custom_vjp)
    gy = rng.normal(size=y.shape).astype(np.float32)
    gs = rng.normal(size=state.shape).astype(np.float32)

    def loss(scan, gy, gs):
        def of(*operands):
            y, state = scan(*operands)
            return (y * gy).sum() + (state * gs).sum()
        return of

    got = jax.jit(jax.grad(
        loss(lambda *t: ssm_ops.ssd_scan(*t, chunk=chunk), gy, gs),
        argnums=(0, 1, 2, 3, 4)))(*operands)
    with jax.enable_x64(True):
        want = jax.jit(jax.grad(
            loss(recurrence_in_jax, gy.astype(np.float64), gs.astype(np.float64)),
            argnums=(0, 1, 2, 3, 4)))(*(jnp.asarray(t) for t in f64))
        want = [np.asarray(t) for t in want]
    for name, g, w in zip("u dt a B C".split(), got, want):
        assert close(g, w, 10 * RTOL), name


def test_rows_and_tokens_in_blocks_equal_the_whole(monkeypatch):
    """A long input goes through the scan's XLA form (this size's) by groups
    of rows and through the expert layer by blocks of tokens (lax.map): the
    same result as in one piece."""
    g = GraniteHybridConfig.tiny(seq=32)
    ids = tokens(g, 4)
    valid = np.ones_like(ids)
    valid[1, 20:] = 0
    whole = np.asarray(compiled(g, batch=4).forward(ids, valid))
    monkeypatch.setattr(ssm_ops, "MAMBA_TOKEN_BLOCK", 64)    # 2 rows a block
    monkeypatch.setattr(moe_ops, "MOE_TOKEN_BLOCK", 32)      # 4 blocks
    blocked = np.asarray(compiled(g, batch=4).forward(ids, valid))
    real = valid.astype(bool)
    assert close(blocked[real], whole[real], rtol=1e-5)


# ------------------------------------------------------------- expert layer
def moe_layer_output(x, weights, num_experts, top_k, width, held, valid=None):
    spec = TensorSpec(x.shape, DataType.FLOAT)
    ins = [Tensor(spec, name="x")]
    if valid is not None:
        ins.append(Tensor(TensorSpec(valid.shape, DataType.INT32), name="valid"))
    layer = Layer(OperatorType.MOE_LAYER,
                  {"num_experts": num_experts, "top_k": top_k,
                   "expert_width": width, "experts_held": held}, ins, name="moe")
    op = get_op_def(OperatorType.MOE_LAYER)
    op.infer(layer)
    lo, hi = held
    w = {"router": weights["router"], "w_in": weights["w_in"][lo:hi],
         "w_out": weights["w_out"][lo:hi]}
    ctx = LoweringCtx(stats={})
    arrays = [jnp.asarray(x)] + ([jnp.asarray(valid)] if valid is not None else [])
    return np.asarray(op.lower(layer, arrays, w, ctx)[0]), ctx.stats


def moe_weights(d=64, experts=8, width=32, shared=48, seed=5):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return (rng.normal(size=shape) / np.sqrt(shape[-2])).astype(np.float32)

    return {"router": w(d, experts), "w_in": w(experts, d, 2 * width),
            "w_out": w(experts, width, d), "shared_in": w(d, 2 * shared),
            "shared_out": w(shared, d)}


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts 0-3 held plus experts 4-7 held, the shared MLP counted once,
    equals the uncut reference's layer: what one chip of a two-chip
    deployment computes is its part of the whole, no more and no less."""
    weights = moe_weights()
    x = np.random.default_rng(1).normal(size=(2, 12, 64)).astype(np.float32)
    first, s0 = moe_layer_output(x, weights, 8, 3, 32, (0, 4))
    second, s1 = moe_layer_output(x, weights, 8, 3, 32, (4, 8))
    hp = {"top_k": 3, "held": (0, 8)}
    w = {k: jnp.asarray(v) for k, v in weights.items()}
    with jax.default_matmul_precision("highest"):
        shared = reference.shared(jnp.asarray(x), w)
        want = reference.moe(jnp.asarray(x), w, hp) + shared
        part = reference.moe(jnp.asarray(x), dict(w, w_in=w["w_in"][:4],
                                                  w_out=w["w_out"][:4]),
                             {"top_k": 3, "held": (0, 4)})
    assert close(first + second + np.asarray(shared), want)
    assert close(first, part)           # the reference given the same share
    assert not close(first + np.asarray(shared), want, rtol=1e-2)
    # every token reaches all of its experts: no capacity, no drops
    assert int(s0["moe_held_pairs"]) + int(s1["moe_held_pairs"]) == 2 * 12 * 3
    assert int(s0["moe_routed_pairs"]) == 2 * 12 * 3
    assert int(s0["moe_load_max"]) >= float(s0["moe_load_mean"]) > 0
    assert 0 < int(s0["moe_experts_hit"]) <= 4


def test_tokens_that_do_not_exist_are_not_routed():
    weights = moe_weights()
    x = np.random.default_rng(2).normal(size=(2, 6, 64)).astype(np.float32)
    valid = np.array([[1, 1, 1, 1, 0, 0], [1, 0, 0, 0, 0, 0]], np.int32)
    got, stats = moe_layer_output(x, weights, 8, 3, 32, (0, 8), valid)
    want, _ = moe_layer_output(x, weights, 8, 3, 32, (0, 8))
    real = valid.astype(bool)
    assert close(got[real], want[real]) and not got[~real].any()
    assert int(stats["moe_routed_pairs"]) == int(stats["moe_held_pairs"]) == 5 * 3


# ------------------------------------------------------------------ serving
def engine_for(g, **compile_kw):
    model = FFModel(ffconfig(SLOTS))
    build_granite_hybrid(model, g, batch=SLOTS)
    eng = compile_serving(model, max_batch_slots=SLOTS, max_decode_len=16,
                          kv_page_size=8, **compile_kw)
    eng.init(seed=3)
    return eng


def served(g):
    """The shared harness on this family's engine, input builders and
    reference; a step reports the routed pairs as one number."""
    eng = engine_for(g)
    cfg = file_config(g)
    ref, hp = family.reference_params(eng.params, cfg), family.hyper(cfg)

    def step_stats(s, stats):
        assert stats["moe_routed_pairs"].shape == ()

    return Served(eng, lambda ids: reference.forward(ref, ids, hp),
                  valid_prompt_inputs, valid_step_inputs, RTOL,
                  step_stats=step_stats)


@pytest.mark.parametrize("layer_types, d_state", [
    (("mamba", "mamba", "attention", "mamba"), 16),
    (("attention", "attention"), 16),
    (("mamba", "mamba", "attention", "mamba"), 128)],
    ids=["hybrid", "attention-only", "hybrid-step-kernel"])
def test_prefill_then_decode_through_the_cache_equals_the_full_forward(
        layer_types, d_state):
    """Logits, not tokens. Prompts of different lengths in one padded wave
    (one shorter than the conv's width, one past a page and a chunk); a
    slot that sits out the second wave and keeps decoding correctly; a
    second wave into a freed slot and into one never used. The
    attention-only model holds grouped K/V heads and `scale` alone, on the
    prefill (kv_out) and the paged decode paths. With a state of 128 a head
    the decode step's recurrence is the step kernel (interpreted), over the
    slots that are live alone."""
    import dataclasses

    g = dataclasses.replace(GraniteHybridConfig.tiny(seq=48),
                            layer_types=layer_types, mamba_d_state=d_state)
    assert ssm_ops.step_path(g.mamba_heads, g.mamba_head_dim, d_state, 1)[
        "path"] == ("kernel" if d_state == 128 else "xla")
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


def test_scheduler_serves_it_and_reports_its_spans_and_counters():
    """Through ContinuousBatchingScheduler, with nothing model-specific in
    it: every served token is the reference's argmax over the request's own
    tokens, and the spans the benchmark reads are there."""
    g = GraniteHybridConfig.tiny(seq=48)
    tel.ring_clear()
    eng = engine_for(g)
    cfg = file_config(g)
    ref, hp = family.reference_params(eng.params, cfg), family.hyper(cfg)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=[int(t) for t in rng.integers(0, g.vocab, n)],
                    max_new_tokens=new, arrival_s=0.0)
            for i, (n, new) in enumerate([(5, 10), (17, 6), (30, 12), (9, 8),
                                          (12, 7), (20, 9), (3, 5)])]
    sched = ContinuousBatchingScheduler(eng, eng.params, valid_prompt_inputs,
                                        valid_step_inputs, eos_id=None)
    sched.run(reqs)
    assert len(sched.completed) == len(reqs) and sched.prefills >= 2
    for r in reqs:
        logits = np.asarray(reference.forward(
            ref, np.asarray([r.prompt + r.tokens], np.int32), hp))[0]
        rows = logits[len(r.prompt) - 1:len(r.prompt) - 1 + len(r.tokens)]
        assert len(r.tokens) == r.max_new_tokens
        assert (rows.argmax(-1) == np.asarray(r.tokens)).all(), r.rid
    spans = {}
    for sp in tel.ring_spans():
        spans.setdefault(sp.name, []).append(sp.args or {})
    made = spans["serve/compile_serving"][-1]
    assert (made["kv_layers"], made["state_layers"]) == (1, 3)
    assert (made["experts_held"], made["experts_routed_over"]) == (4, 8)
    per_slot = 3 * (8 * 16 * 16 * 4 + 3 * (8 * 16 + 2 * 16) * 4)
    assert made["state_bytes_per_slot"] == per_slot
    assert eng.kv_spec.state_bytes_per_slot == per_slot
    mem = eng.memory_stats()
    assert mem["predicted_kv_cache_bytes"] == mem["actual_kv_cache_bytes_per_device"]
    assert len(spans["serve/prefill/commit_state"]) == sched.prefills \
        == len(spans["serve/prefill/commit_kv"])
    assert all(a["bytes"] == SLOTS * per_slot
               for a in spans["serve/prefill/commit_state"])
    steps = 0
    for a in spans["serve/decode/window_sync"]:
        steps += a["steps"]
        # 4 expert layers, top 3: at most slots * 3 pairs a layer and step
        assert 0 < a["moe_held_pairs"] <= a["moe_routed_pairs"] \
            <= a["steps"] * g.layers * SLOTS * g.experts_per_tok
        assert a["moe_load_max"] >= a["moe_load_mean"] > 0
        assert a["moe_experts_hit"] <= a["steps"] * g.layers * 4
        # a step's 12 pairs a layer are under every rung: all rows computed
        assert a["moe_rows_computed"] == a["moe_rows_static"] \
            == a["steps"] * g.layers * SLOTS * g.experts_per_tok
    assert steps == sched.decode_steps
    wave = spans["serve/prefill/device_wait"][0]
    assert wave["moe_routed_pairs"] == g.layers * g.experts_per_tok * sum(
        len(r.prompt) for r in reqs[:SLOTS])
    # 576 pairs a layer and wave, 183 of them routed and about half of
    # those held: the rung of 144 rows in every layer
    assert wave["moe_rows_static"] == g.layers * SLOTS * g.seq * 3
    assert wave["moe_held_pairs"] <= wave["moe_rows_computed"] \
        == g.layers * 144


@pytest.mark.parametrize("d_state, want", [
    (16, {"path": "xla", "groups": 1}),
    (128, {"path": "kernel", "head_block": 8, "groups": 1})],
    ids=["tiny", "whole-lanes"])
def test_the_decode_step_on_either_path_through_the_scheduler(d_state, want):
    """The form of the decode step's recurrence is chosen from the state's
    width (`ssm_ops.step_path`): through the scheduler either form serves
    the reference's argmax and reports itself and its counter."""
    import dataclasses

    g = dataclasses.replace(GraniteHybridConfig.tiny(seq=48),
                            mamba_d_state=d_state)
    eng = engine_for(g)
    cfg = file_config(g)
    ref, hp = family.reference_params(eng.params, cfg), family.hyper(cfg)
    scheduler_reports_the_step_path(
        eng, lambda ids: reference.forward(ref, ids, hp), valid_prompt_inputs,
        valid_step_inputs, g.vocab, want, {"l0_mamba", "l1_mamba", "l3_mamba"})


def test_what_recurrent_state_does_not_support_fails_loudly():
    g = GraniteHybridConfig.tiny(seq=48)

    def model(**kw):
        m = FFModel(ffconfig(SLOTS, **kw))
        build_granite_hybrid(m, g, batch=SLOTS)
        return m

    with pytest.raises(NotImplementedError, match="mamba2.*host KV tier"):
        compile_serving(model(kv_host_pages=8), max_batch_slots=SLOTS,
                        max_decode_len=16, kv_page_size=8)
    with pytest.raises(NotImplementedError, match="mamba2.*speculative"):
        compile_serving(model(), max_batch_slots=SLOTS, max_decode_len=16,
                        kv_page_size=8, draft=model(), spec_tokens=2)
    eng = compile_serving(model(), max_batch_slots=SLOTS, max_decode_len=16,
                          kv_page_size=8)
    eng.init(seed=3)
    with pytest.raises(NotImplementedError, match="handoff.*recurrent"):
        ContinuousBatchingScheduler(eng, eng.params, valid_prompt_inputs,
                                    valid_step_inputs,
                                    handoff=lambda req, payload: None)
    with pytest.raises(NotImplementedError, match="export_parked"):
        eng.kv.export_parked(0)
    with pytest.raises(NotImplementedError, match="import_parked"):
        eng.kv.import_parked(0, {"pages": 1, "pos": 1, "layers": {}})


def test_flop_and_byte_functions_against_the_program():
    """benchmarks/harness/flops_granitemoehybrid.py counts what the
    program's own configuration counts, and its parameters are the ones
    the program initialises."""
    from harness import flops_granitemoehybrid as flops
    from harness import manifest as mf

    for name in ("granite-4.0-h-small", "granite-tiny"):
        cfg = mf.read_named("configs", name)
        g = family.program_config(cfg)
        assert flops.train_flops_per_token(cfg, g.seq) == g.flops_per_token()
        assert flops.param_count(cfg) == g.param_count()
    small = mf.read_named("configs", "granite-4.0-h-small")
    # the issue's arithmetic: 4757 M as published for this share, and the
    # untied head's 205.5 M
    assert round((flops.param_count(small) - 50176 * 4096) / 1e6) == 4757
    assert flops.state_bytes_per_slot(small) == 9 * (128 * 64 * 128 * 4
                                                     + 3 * 8448 * 2)
    assert flops.kv_bytes_per_token(small) == 4096
    tiny = GraniteHybridConfig.tiny()
    cm = compiled(tiny)
    held = sum(int(np.prod(w.shape)) for lw in cm.params.values()
               for w in lw.values())
    assert held == tiny.param_count() == flops.param_count(file_config(tiny))
    counters = {"moe_routed_pairs": 16 * 10 * 10, "moe_experts_hit": 330,
                "moe_held_pairs": 800}
    chat = mf.read_named("traffic", "serve-chat")
    step = flops.decode_step_need(small, {"max_batch_slots": 16}, chat, counters)
    # weights outside the experts ~1.36 G parameters, 330 experts of 9.44 M,
    # 16 slots' state twice
    assert 8.5e9 < step["bytes"] < 10.5e9 and step["flops"] == 0
    wave = flops.prefill_wave_need(small, {"max_batch_slots": 16}, chat,
                                   {"moe_held_pairs": 10 * 16 * 128 * 5})
    assert 3.5e13 < wave["flops"] < 4.5e13 and wave["bytes"] == 0
