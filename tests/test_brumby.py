"""The brumby decoder (flexflow_tpu/models/brumby.py: every layer power
retention, ops/power_retention_ops.py: linear attention with the kernel (q .
k)^2 over a gated recurrent state a K/V head, grouped heads, RMS-normed and
rotated q and k; a cache that pages nothing) against its plain reference
(benchmarks/harness/reference_brumby.py: the pair form over the whole
sequence, and the literal recurrence over the plain k (x) k), at a small size
on the CPU with seeded random weights.

Tolerance: program and reference both compute in float32, so they differ by
the order of their sums alone (the symmetric embedding against the plain
outer product, the state form against the pair form, the cache against one
full pass): 1e-6 to 3e-5 of the result's scale. RTOL 1e-4 leaves room for
that and none for a fault: a dropped normaliser or a gate a tenth off is off
by 1e-2 and more, a state kept in bfloat16 by 5e-4 and more
(test_a_wrong_layer_fails_the_tolerance), the same program computing in
bfloat16 by about 1e-2.
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
from flexflow_tpu.models import BrumbyConfig, build_brumby  # noqa: E402
from flexflow_tpu.ops import get_op_def  # noqa: E402
from flexflow_tpu.ops import power_retention_ops as pr  # noqa: E402
from flexflow_tpu.ops import rotary  # noqa: E402
from flexflow_tpu.ops.op_type import OperatorType  # noqa: E402
from flexflow_tpu.ops.registry import STATS_KEY  # noqa: E402
from flexflow_tpu.serving import (ContinuousBatchingScheduler, Request,  # noqa: E402
                                  compile_serving, kv_cache,
                                  positions_valid_prompt_inputs,
                                  positions_valid_step_inputs)
from flexflow_tpu.serving.program import (clone_for_serving,  # noqa: E402
                                          page_geometry, recurrent_layers)
from families import brumby as family  # noqa: E402
from harness import flops_brumby as flops  # noqa: E402
from harness import manifest as mf  # noqa: E402
from harness import reference_brumby as reference  # noqa: E402
from served import Served, off_by  # noqa: E402

RTOL = 1e-4
SLOTS = 4
PUBLISHED = "Brumby-14B-Base"
CELL = PUBLISHED + ".serve-longanswer"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
HP = {"eps": 1e-6}


def file_config(g: BrumbyConfig) -> dict:
    """`g` in the keys of a configuration file, as the family reads them."""
    return {"hidden_size": g.d_model, "num_hidden_layers": g.layers,
            "num_attention_heads": g.heads, "num_key_value_heads": g.kv_heads,
            "head_dim": g.head_dim, "intermediate_size": g.dense_width,
            "rope_theta": g.rope_theta, "rms_norm_eps": g.eps,
            "vocab_size": g.vocab,
            "assumed": {"serve_positions": g.seq, "weights_dtype": g.dtype,
                        "gate_logit_std": g.gate_logit_std}}


def ffconfig(batch, **kw):
    return FFConfig(batch_size=batch, seed=3, strategy_cache=False,
                    log_level="warning", mesh_shape={"data": 1}, **kw)


def compiled(g, batch=2, **kw):
    model = FFModel(ffconfig(batch, **kw))
    build_brumby(model, g, batch=batch)
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


# ---------------------------------------------------------------- the op
def scan_inputs(length, seed=0, b=2, heads=4, kv=2, hd=16, gates=(0.3, 0.999)):
    """Queries and keys with a common direction, so that no (q . k)^2 is a
    difference of nearly equal numbers: a row whose normaliser is one tiny
    term is ill-conditioned in ANY form (the first tokens of a row with q .
    k near 0), and the forms are compared, not float32."""
    rng = np.random.default_rng(seed)
    q = (1 + 0.5 * rng.standard_normal((b, length, heads, hd))).astype(np.float32)
    k = (1 + 0.5 * rng.standard_normal((b, length, kv, hd))).astype(np.float32)
    v = rng.standard_normal((b, length, kv, hd)).astype(np.float32)
    log_g = np.log(rng.uniform(*gates, (b, length, kv))).astype(np.float32)
    return tuple(jnp.asarray(t) for t in (q, k, v, log_g))


def symmetric_half(full):
    """The reference's plain `[.., D, D, ...]` state (axes 1 and 2 of a
    row's) as the program lays it: the rows of `pr._row_pairs` as they are
    (the weights of the rows are the query side's)."""
    full = np.asarray(full)
    a, b, _weight = pr._row_pairs(full.shape[1])
    return full[:, a, b]


def normaliser_at_rest(full):
    """The reference's `[J, D, D]` normaliser as the program lays it: the
    square itself where a head is whole 128-lane slabs, else the rows."""
    return np.asarray(pr._normaliser_at_rest(jnp.asarray(full)))


def test_the_embedding_squares_the_dot_product():
    rng = np.random.default_rng(1)
    for hd in (8, 16, 128):
        q = jnp.asarray(rng.standard_normal((5, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((5, hd)), jnp.float32)
        square = jnp.square(jnp.sum(q * k, -1))
        # phi itself: the rows off the diagonal times sqrt 2
        root = np.sqrt(pr._embedding_tables(hd)[2])
        pq, pk = pr.key_rows(q) * root, pr.key_rows(k) * root
        assert pr.state_rows(hd) == hd * (hd + 1) // 2
        assert pq.shape == (5, pr.laid_rows(hd))
        assert close(jnp.sum(pq * pk, -1), square, 1e-5)
        # as the program holds it: the sqrt 2, squared, on the query side
        assert close(jnp.sum(pr.query_rows(q) * pr.key_rows(k), -1), square,
                     1e-5)
    # the rows as they lie: the symmetric half exactly where a head is not
    # whole 128-lane slabs; at 128 each a of a block of 8 with the run b
    # from the block's first a, every run whole tiles, the mirror rows that
    # adds read with weight 0
    assert (pr.state_rows(16), pr.laid_rows(16)) == (136, 136)
    for got, want in zip(pr._row_pairs(16), np.triu_indices(16)):
        assert (got == want).all()
    assert (pr.state_rows(128), pr.laid_rows(128)) == (8256, 8704)
    a, b, weight = pr._row_pairs(128)
    assert (a // 8 * 8 <= b).all() and (weight == np.where(
        a == b, 1, np.where(a < b, 2, 0))).all()
    assert int((weight > 0).sum()) == 8256
    starts = np.flatnonzero(np.diff(a, prepend=-1))
    assert len(starts) == 128 and (starts % 8 == 0).all() \
        and (np.diff(starts) % 8 == 0).all()
    # from bfloat16 the key rows are EXACT in float32, and two bfloat16
    # terms hold them: the state's weights are the pair form's
    kb = k.astype(jnp.bfloat16)
    want = np.asarray(kb, np.float32)[:, a] * np.asarray(kb, np.float32)[:, b]
    got = pr.key_rows(kb)
    assert got.dtype == jnp.float32 and (np.asarray(got) == want).all()
    hi = got.astype(jnp.bfloat16)
    lo = (got - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    assert (np.asarray(hi, np.float32) + np.asarray(lo, np.float32)
            == want).all()


# D = 16: chunks of 128, the crossover at 128 + 136 / 2 = 196 positions
@pytest.mark.parametrize("length, path", [
    (40, "pair"), (196, "pair"), (197, "chunked_state"),
    (300, "chunked_state"), (384, "chunked_state")])
def test_the_sequence_form_against_the_literal_recurrence(length, path):
    """Both regimes and either side of the crossover: y, and the state and
    the normaliser after the last step, against the recurrence over the
    plain k (x) k; and the pair form against it too."""
    assert pr.sequence_path(length, 16)["path"] == path
    q, k, v, log_g = scan_inputs(length, seed=length)
    y, state, total = pr.retention_sequence(q, k, v, log_g, 1e-6)
    for row in range(q.shape[0]):
        want, (s_want, z_want) = reference.retention_recurrence(
            q[row], k[row], v[row], log_g[row], HP)
        assert close(y[row], want), (row, off_by(y[row], want))
        assert close(reference.retention_pairs(q[row], k[row], v[row],
                                               log_g[row], HP), want)
        assert close(state[row], symmetric_half(s_want), 1e-5)
        assert close(total[row], symmetric_half(z_want), 1e-5)


def test_the_two_regimes_are_the_same_numbers(monkeypatch):
    """One input through the pair form and through the chunked state form
    (the chunk forced down): the crossover moves cost, not results."""
    q, k, v, log_g = scan_inputs(160, seed=3)
    assert pr.sequence_path(160, 16)["path"] == "pair"
    whole = pr.retention_sequence(q, k, v, log_g, 1e-6)
    monkeypatch.setattr(pr, "MAX_CHUNK", 32)
    assert pr.sequence_path(160, 16) == {"path": "chunked_state", "chunk": 32}
    chunked = pr.retention_sequence(q, k, v, log_g, 1e-6)
    for got, want in zip(chunked, whole):
        assert close(got, want, 2e-5)


def test_the_rows_of_a_wave_go_through_in_blocks_and_empty_ones_are_skipped(
        monkeypatch):
    q, k, v, log_g = scan_inputs(24, seed=5, b=4)
    monkeypatch.setattr(pr, "RETENTION_TOKEN_BLOCK", 10 ** 6)
    whole = pr.retention_sequence(q, k, v, log_g, 1e-6)     # all rows at once
    monkeypatch.setattr(pr, "RETENTION_TOKEN_BLOCK", 48)    # two rows a block
    for got, want in zip(pr.retention_sequence(q, k, v, log_g, 1e-6), whole):
        assert close(got, want, 1e-6)
    # rows 2 and 3 hold no token (k = 0, log g = 0 there): their block is
    # not computed, and reads what the computation would give: nothing
    valid = jnp.asarray([[True] * 24, [True] * 5 + [False] * 19,
                         [False] * 24, [False] * 24])
    k0 = jnp.where(valid[..., None, None], k, 0)
    lg0 = jnp.where(valid[..., None], log_g, 0)
    assert int(pr.rows_computed(valid)) == 2
    skipped = pr.retention_sequence(q, k0, v, lg0, 1e-6, valid)
    # into slot arrays: rows 0 and 1 overwritten, 2 and 3 as they were
    old = (jnp.full((4, 2, 136, 16), 7.0), jnp.full((4, 2, 136), 5.0))
    _y, s_in, z_in = pr.retention_sequence(q, k0, v, lg0, 1e-6, valid, into=old)
    assert (np.asarray(s_in[:2]) == np.asarray(skipped[1][:2])).all()
    assert (np.asarray(s_in[2:]) == 7.0).all() and (np.asarray(z_in[2:]) == 5.0).all()
    monkeypatch.setattr(pr, "RETENTION_TOKEN_BLOCK", 10 ** 6)
    for got, want in zip(skipped, pr.retention_sequence(q, k0, v, lg0, 1e-6)):
        assert close(got[:2], want[:2], 1e-6)
        assert not np.asarray(got[2:]).any() and not np.asarray(want[2:]).any()
    # all rows at once, into slot arrays: the same selection by rows
    _y, s_in, _z = pr.retention_sequence(q, k0, v, lg0, 1e-6, valid, into=old)
    assert close(s_in[:2], skipped[1][:2], 1e-6)
    assert (np.asarray(s_in[2:]) == 7.0).all()


def test_a_groups_query_heads_read_one_state():
    """Five query heads on one K/V head against five separate heads with
    the keys, values and gates repeated."""
    q, k, v, log_g = scan_inputs(33, seed=9, heads=10, kv=2)
    y, state, _ = pr.retention_sequence(q, k, v, log_g, 1e-6)
    rep = lambda t: jnp.repeat(t, 5, axis=2)                # noqa: E731
    y_sep, state_sep, _ = pr.retention_sequence(q, rep(k), rep(v), rep(log_g),
                                                1e-6)
    assert close(y, y_sep, 1e-6)
    assert close(jnp.repeat(state, 5, axis=1), state_sep, 1e-6)


def test_one_step_on_the_live_slots_alone():
    q, k, v, log_g = scan_inputs(21, seed=4, b=3)
    _y, state, total = pr.retention_sequence(q[:, :-1], k[:, :-1], v[:, :-1],
                                             log_g[:, :-1], 1e-6)
    live = jnp.asarray([True, False, True])
    y, new_state, new_total = jax.jit(pr.retention_step, static_argnums=7)(
        state, total, q[:, -1], k[:, -1], v[:, -1], log_g[:, -1], live, 1e-6)
    want, want_state, want_total = pr.retention_sequence(q, k, v, log_g, 1e-6)
    for row in (0, 2):
        assert close(y[row], want[row, -1])
        assert close(new_state[row], want_state[row], 1e-5)
        assert close(new_total[row], want_total[row], 1e-5)
    # the slot that is not live: nothing read out, nothing written
    assert not np.asarray(y[1]).any()
    assert (np.asarray(new_state[1]) == np.asarray(state[1])).all()
    assert (np.asarray(new_total[1]) == np.asarray(total[1])).all()


def wide_step_inputs(steps, seed, dtype=jnp.float32, b=4, kv=2, group=5,
                     hd=128):
    """`steps` tokens a slot at the published head width, and which slots
    are live at each step: a strict subset that changes between steps."""
    q, k, v, log_g = scan_inputs(steps, seed=seed, b=b, heads=kv * group,
                                 kv=kv, hd=hd)
    q, k, v = (t.astype(dtype) for t in (q, k, v))
    rng = np.random.default_rng(seed)
    live = np.zeros((steps, b), bool)
    for t in range(steps):
        live[t, rng.permutation(b)[:1 + (t + seed) % (b - 1)]] = True
    assert not live.all(axis=1).any() and live.any(axis=1).all()
    assert len({tuple(row) for row in live}) > 1
    return q, k, v, log_g, live


@pytest.mark.parametrize("dtype", ("float32", "bfloat16"))
def test_the_step_kernel_against_the_recurrence_and_the_xla_loop(dtype):
    """The decode step's kernel (interpreted here) at the published head
    width, 2 K/V heads x 5 query heads, four steps from an empty state with
    a live set that changes: each live slot's y against the token-by-token
    float32 recurrence over the tokens that slot has taken, and y, S and z
    against the XLA loop on the same state; a slot that is not live keeps
    its bytes, through both forms. From bfloat16 q, k and v too: the kernel
    computes in float32 from numbers float32 holds exactly."""
    steps, b = 4, 4
    q, k, v, log_g, live = wide_step_inputs(steps, seed=6,
                                            dtype=jnp.dtype(dtype))
    assert pr.step_path(10, 2, 128) == {"path": "kernel", "laid_rows": 8704}
    shapes = pr._state_shapes(2, 128)
    assert shapes == ((2, 8704, 128), (2, 128, 128))
    held = tuple(jnp.zeros((b,) + shape, jnp.float32) for shape in shapes)
    step = jax.jit(pr.retention_step, static_argnums=7)
    loop = jax.jit(pr._step_xla, static_argnums=7)
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda *t: pr.retention_step(*t, 1e-6))(*held, q[:, 0], k[:, 0],
                                                v[:, 0], log_g[:, 0], live[0]))
    taken = [[] for _ in range(b)]
    f32 = lambda t: jnp.asarray(t, jnp.float32)             # noqa: E731
    for t in range(steps):
        args = (q[:, t], k[:, t], v[:, t], log_g[:, t], jnp.asarray(live[t]))
        y, state, total = step(*held, *args, 1e-6)
        y_loop, state_loop, total_loop = loop(*held, *args, 1e-6)
        for slot in range(b):
            if not live[t, slot]:
                assert not np.asarray(y[slot]).any()
                for new, old in ((state, held[0]), (total, held[1]),
                                 (state_loop, held[0]), (total_loop, held[1])):
                    assert (np.asarray(new[slot])
                            == np.asarray(old[slot])).all()
                continue
            taken[slot].append(t)
            at = np.asarray(taken[slot])
            want, (s_want, z_want) = reference.retention_recurrence(
                f32(q[slot, at]), f32(k[slot, at]), f32(v[slot, at]),
                log_g[slot, at], HP)
            assert close(y[slot], want[-1]), (
                t, slot, off_by(y[slot], want[-1]))
            assert close(y[slot], y_loop[slot], 1e-5)
            assert close(state[slot], symmetric_half(s_want), 1e-5)
            assert close(total[slot], normaliser_at_rest(z_want), 1e-5)
            assert close(state[slot], state_loop[slot], 1e-6)
            assert close(total[slot], total_loop[slot], 1e-6)
        held = (state, total)
    assert all(len(t) >= 1 for t in taken) and max(map(len, taken)) >= 2


def test_the_step_path_by_shape_and_its_span():
    """The kernel where a head is whole 128-lane slabs, the XLA loop at the
    tiny width (no knob: the shapes say); a lowered decode layer says which
    in its `retention/step_path` span."""
    assert pr.step_path(40, 8, 128) == {"path": "kernel", "laid_rows": 8704}
    assert pr.step_path(4, 2, 16) == {"path": "xla", "laid_rows": 136}
    assert pr.step_path(4, 2, 64) == {"path": "xla", "laid_rows": 2080}
    q, k, v, log_g = scan_inputs(1, seed=2, b=3)
    held = tuple(jnp.zeros((3,) + shape, jnp.float32)
                 for shape in pr._state_shapes(2, 16))
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *t: pr.retention_step(*t, 1e-6))(
            *held, q[:, 0], k[:, 0], v[:, 0], log_g[:, 0],
            jnp.asarray([True, False, True])))
    for g, path, rows in ((BrumbyConfig.tiny(seq=48), "xla", 136),
                          (wide_config(), "kernel", 8704)):
        tel.ring_clear()
        eng = engine_for(g)
        state = eng.kv.state
        eng.decode_step(eng.params, state, positions_valid_step_inputs(
            jnp.ones((SLOTS, 1), jnp.int32), state))
        said = tel.ring_spans("retention/step_path")
        assert [(s.args["layer"], s.args["path"], s.args["laid_rows"])
                for s in said] == [(f"l{i}_ret", path, rows)
                                   for i in range(g.layers)]


def recurrence_read_out_in_bfloat16(q, k, v, log_g, hp):
    """The literal recurrence with a float32 state whose READ-OUT takes
    bfloat16 operands (the state and phi(q) rounded, products summed in
    float32): what a decode step on the matrix unit would compute."""
    def rounded(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    ys, held = [], None
    for t in range(q.shape[0]):
        _y, held = reference.retention_recurrence(
            q[t:t + 1], k[t:t + 1], v[t:t + 1], log_g[t:t + 1], hp, state=held)
        s, z = held
        qg = q[t].reshape(k.shape[1], -1, q.shape[-1])
        qq = rounded(qg[..., :, None] * qg[..., None, :])
        num = jnp.einsum("jgab,jabd->jgd", qq, rounded(s))
        den = jnp.einsum("jgab,jab->jg", qq, rounded(z))
        ys.append((num / (den[..., None] + hp["eps"])).reshape(q.shape[1:]))
    return jnp.stack(ys), held


@pytest.mark.parametrize("fault, least", [
    ({"state_dtype": jnp.bfloat16}, 5e-4), ({"normaliser": False}, 1e-1),
    ({"gate_factor": 0.9}, 1e-2), ({"read_out": "bfloat16"}, 5e-4)])
def test_a_wrong_layer_fails_the_tolerance(fault, least):
    """What RTOL must catch, through the reference's own switches: a state
    rounded to bfloat16 after every step, the numerator without its
    normaliser, a gate a tenth off; and a read-out whose operands are
    bfloat16, the state itself float32."""
    q, k, v, log_g = scan_inputs(64, seed=8, b=1, gates=(0.9, 0.999))
    y, _s, _z = pr.retention_sequence(q, k, v, log_g, 1e-6)
    hp = dict(HP, **{n: x for n, x in fault.items()
                     if n not in ("state_dtype", "read_out")})
    log_wrong = log_g[0] + jnp.log(hp.pop("gate_factor", 1.0))
    if "read_out" in fault:
        wrong, _ = recurrence_read_out_in_bfloat16(q[0], k[0], v[0],
                                                   log_wrong, hp)
    else:
        wrong, _ = reference.retention_recurrence(
            q[0], k[0], v[0], log_wrong, hp,
            state_dtype=fault.get("state_dtype"))
    assert off_by(y[0], wrong) > least >= 5 * RTOL


def test_rotate_half_against_the_reference_and_the_moved_helper():
    from flexflow_tpu.ops import latent_attention_ops as mla

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((7, 3, 16)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 5000, 7))
    cos, sin = rotary.half_tables(pos, 16, 1e6)
    got = rotary.apply_rope_half(x, cos[:, None], sin[:, None])
    assert close(got, reference.rope(x, pos, {"rope_theta": 1e6}), 1e-6)
    # latent attention still finds its interleaved helper under its name
    assert mla.apply_rope is rotary.apply_rope
    assert np.allclose(mla.yarn_inv_freq(8, 100.0), rotary.inv_freq(8, 100.0))


# ------------------------------------------------------------------ forward
def test_forward_logits_against_the_reference():
    g = BrumbyConfig.tiny(seq=40)
    cm = compiled(g)
    ids = tokens(g, 2)
    got = cm.forward(ids, positions_of(ids), np.ones_like(ids))
    assert got.shape == (2, g.seq, g.vocab)
    assert close(got, reference_logits(cm.params, g, ids))
    # the gate's draw: u W_g about N(0, 0.5^2) for a unit-RMS u
    wg = np.asarray(cm.params["l0_ret"]["wg"])
    assert wg.shape == (64, 2) and abs(wg.std() * 8 - 0.5) < 0.15


def test_bf16_program_fails_the_f32_tolerance():
    """The comparison is tight enough to catch a lower precision."""
    g = BrumbyConfig.tiny(seq=40)
    cm = compiled(g, compute_dtype="bfloat16")
    ids = tokens(g, 2)
    got = cm.forward(ids, positions_of(ids), np.ones_like(ids))
    assert not close(got, reference_logits(cm.params, g, ids), 10 * RTOL)


def test_the_sequence_path_by_shape_and_its_span():
    assert pr.chunk_steps(128) == 1024 and pr.state_rows(128) // 2 == 4128
    assert pr.sequence_path(1024, 128) == {"path": "pair", "chunk": 1024}
    assert pr.sequence_path(5152, 128) == {"path": "pair", "chunk": 5152}
    assert pr.sequence_path(5153, 128) == {"path": "chunked_state",
                                           "chunk": 1024}
    g = BrumbyConfig.tiny(seq=40)
    tel.ring_clear()
    compiled(g).forward(tokens(g, 2), positions_of(tokens(g, 2)),
                        np.ones((2, g.seq), np.int32))
    said = tel.ring_spans("retention/path")
    assert [(s.args["layer"], s.args["path"], s.args["chunk"]) for s in said] \
        == [(f"l{i}_ret", "pair", 40) for i in range(g.layers)]


# ------------------------------------------------------------------ serving
def engine_for(g, **compile_kw):
    model = FFModel(ffconfig(SLOTS))
    build_brumby(model, g, batch=SLOTS)
    eng = compile_serving(model, max_batch_slots=SLOTS, max_decode_len=16,
                          kv_page_size=8, **compile_kw)
    eng.init(seed=3)
    return eng


def wide_config(seq=48):
    """One layer at the published head width, 2 K/V heads x 5 query heads:
    the state in whole tiles, the decode step the kernel."""
    return dataclass_replace(BrumbyConfig.tiny(seq=seq), layers=1, heads=10,
                             kv_heads=2, head_dim=128)


def served(g, scheduler_path=False):
    """The shared harness on this family's engine, input builders and
    reference. `scheduler_path`: the prefill the scheduler runs instead of
    the full-logits one."""
    eng = engine_for(g)
    # what a live slot's step must move: the rows the recurrence needs
    need = flops.state_bytes_per_slot(file_config(g))
    assert need == g.state_bytes_per_slot() \
        <= eng.kv_spec.state_bytes_per_slot

    def wave_stats(s, stats, prompts):
        if scheduler_path and eng.kv.writes_state_in_place:
            assert float(stats["state_written_bytes"]) == len(prompts) \
                * eng.kv_spec.state_bytes_per_slot
        assert int(stats["retention_layers"]) == g.layers
        # (at 48 positions a block of 1024 tokens holds all four rows: no
        # row is skipped; a `[16, 1024]` wave goes row by row)
        assert int(stats["retention_rows"]) == g.layers * SLOTS

    def step_stats(s, stats):
        assert float(stats["linear_state_bytes"]) == 2 * len(s.seqs) * need

    return Served(eng, lambda ids: reference_logits(eng.params, g, ids),
                  positions_valid_prompt_inputs, positions_valid_step_inputs,
                  RTOL, wave_stats=wave_stats, step_stats=step_stats,
                  scheduler_path=scheduler_path)


@pytest.mark.parametrize("in_place, wide", [(False, False), (True, False),
                                            (False, True), (True, True)])
def test_prefill_then_decode_through_the_state_equals_the_full_forward(
        in_place, wide, monkeypatch):
    """Logits, not tokens. Prompts of different lengths in one padded wave
    (one of 2 tokens): the state is handed out at each row's last real
    token; a slot that sits out the second wave keeps its state and decodes
    correctly; a second wave into a freed slot and into one never used.
    Both ways a wave's state reaches its slots: the commit program, and (the
    threshold forced to 0) the prefill program writing the donated slot
    arrays itself. `wide`: one layer at the published head width, where the
    wave builds the state in whole tiles (8704 rows for 8256) and the step
    is the kernel."""
    if in_place:
        monkeypatch.setattr(kv_cache, "IN_PLACE_STATE_BYTES", 0)
    g = wide_config() if wide else BrumbyConfig.tiny(seq=48)
    rng = np.random.default_rng(7)
    s = served(g, scheduler_path=in_place)
    assert s.eng.kv.state_kinds == "recurrent"
    assert s.eng.kv.writes_state_in_place == in_place

    def prompt(n):
        return [int(t) for t in rng.integers(0, g.vocab, n)]

    s.wave({0: prompt(2), 1: prompt(19), 2: prompt(33)})
    s.decode(3)
    s.evict(1)
    s.wave({1: prompt(9), 3: prompt(17)})       # 0 and 2 sit it out
    s.decode(3)
    assert s.checked == (0 if in_place else 3 + 2) + 3 * 3 + 4 * 3
    assert len(s.seqs[0]) == 2 + 1 + 6 and len(s.seqs[1]) == 9 + 1 + 3
    if wide:
        assert s.eng.kv_spec.state_bytes_per_slot \
            == 2 * (8704 * 128 + 128 * 128) * 4 > g.state_bytes_per_slot() == 2 * 8256 * 129 * 4


def test_a_padded_wave_hands_out_each_rows_state_at_its_last_real_token():
    g = BrumbyConfig.tiny(seq=48)
    eng = engine_for(g)
    rng = np.random.default_rng(11)
    lengths = np.asarray([5, 48, 0, 17], np.int32)
    ids = np.zeros((SLOTS, g.seq), np.int32)
    for row, n in enumerate(lengths):
        ids[row, :n] = rng.integers(1, g.vocab, n)
    _logits, kv_state = eng.prefill(
        eng.params, positions_valid_prompt_inputs(ids, lengths))
    cfg = file_config(g)
    w = family.reference_params(eng.params, cfg)
    hp = family.hyper(cfg)
    h = reference._embed(w["embed"], ids)
    layer = w["layers"][0]
    u = reference.rms(h, layer["norm_in"], hp["eps"])
    got = kv_state["l0_ret"]
    for row, n in enumerate(lengths):
        if n == 0:
            assert not np.asarray(got["S"][row]).any()
            continue
        q, k, v, log_g = reference.retention_inputs(
            u[row, :n], jnp.arange(n), layer, hp)
        _y, (s_want, z_want) = reference.retention_recurrence(q, k, v, log_g,
                                                              hp)
        assert close(got["S"][row], symmetric_half(s_want), 1e-5)
        assert close(got["z"][row], symmetric_half(z_want), 1e-5)


def test_a_model_with_no_paged_layer_compiles_and_admits_by_slots():
    g = BrumbyConfig.tiny(seq=48)
    model = FFModel(ffconfig(SLOTS))
    build_brumby(model, g, batch=SLOTS)
    assert page_geometry(model) == {}
    dec, attn = clone_for_serving(model, "decode", SLOTS)
    assert attn == []
    rec = recurrent_layers(dec)
    assert list(rec) == [f"l{i}_ret" for i in range(g.layers)]
    assert rec["l0_ret"] == {"S": ((2, 136, 16), jnp.float32),
                             "z": ((2, 136), jnp.float32)}
    assert get_op_def(OperatorType.POWER_RETENTION).state_kind == "recurrent"
    eng = engine_for(g)
    kv = eng.kv
    assert kv.state_kinds == "recurrent" and eng.attn_layers == []
    assert (eng.kv_spec.layers, eng.kv_spec.heads, eng.kv_spec.latent_dim) \
        == (0, 0, 0)
    per_slot = 3 * 2 * 136 * 17 * 4
    assert eng.kv_spec.state_bytes_per_slot == per_slot \
        == flops.state_bytes_per_slot(file_config(g)) \
        == g.state_bytes_per_slot()
    # no pools, no page accounting: a request of any length needs 0 pages
    assert set(kv.state) == {f"l{i}_ret" for i in range(3)} | {
        kv_cache.PAGE_TABLE_KEY, kv_cache.POS_KEY, kv_cache.ACTIVE_KEY}
    assert kv.device_bytes() == SLOTS * per_slot \
        == eng.kv_spec.per_device_bytes()
    assert kv.pages_needed(10 ** 6) == 0 and kv.can_admit(10 ** 6)
    for slot in range(SLOTS):
        assert kv.free_slots()[0] == slot
        kv.admit(slot, 5, 10 ** 6)
    assert kv.free_slots() == [] and len(kv.free_pages) \
        == eng.kv_spec.pool_pages - 1
    with pytest.raises(ValueError, match="occupied"):
        kv.admit(0, 5, 8)
    # a model that carries no state at all is still refused
    plain = FFModel(ffconfig(SLOTS))
    x = plain.create_tensor([SLOTS, 8, 16], name="x")
    plain.dense(x, 16, name="only")
    with pytest.raises(ValueError, match="nothing to cache"):
        compile_serving(plain, max_batch_slots=SLOTS)


def test_what_this_state_does_not_support_fails_loudly():
    g = BrumbyConfig.tiny(seq=48)

    def model(**kw):
        m = FFModel(ffconfig(SLOTS, **kw))
        build_brumby(m, g, batch=SLOTS)
        return m

    def serve(m, **kw):
        return compile_serving(m, max_batch_slots=SLOTS, max_decode_len=16,
                               kv_page_size=8, **kw)

    with pytest.raises(NotImplementedError,
                       match="3 power_retention layers.*host KV tier"):
        serve(model(kv_host_pages=8))
    with pytest.raises(NotImplementedError, match="recurrent state.*speculative"):
        serve(model(), draft=model(), spec_tokens=2)
    eng = serve(model())
    eng.init(seed=3)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        ContinuousBatchingScheduler(
            eng, eng.params, positions_valid_prompt_inputs,
            positions_valid_step_inputs, handoff=lambda req, payload: None)
    eng.kv.admit(0, 4, 8)
    for path, args in (("spill", (0, 0)), ("export_parked", (0,)),
                       ("import_parked", (1, {"pages": 0}))):
        with pytest.raises(NotImplementedError, match=f"{path}.*recurrent"):
            getattr(eng.kv, path)(*args)


@pytest.mark.parametrize("in_place", (False, True))
def test_scheduler_serves_it_and_reports_its_spans_and_counters(
        in_place, tmp_path, monkeypatch):
    """Through ContinuousBatchingScheduler, with nothing model-specific in
    it: every served token is the reference's argmax over the request's own
    tokens, and the spans and counters the benchmark reads are there."""
    import trace_report

    if in_place:
        monkeypatch.setattr(kv_cache, "IN_PLACE_STATE_BYTES", 0)
    g = BrumbyConfig.tiny(seq=48)
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
    assert (made["kv_layers"], made["state_layers"]) == (0, 3)
    assert made["paged_state"] == "none" and made["kv_pool_shape"] == []
    assert made["state_in_place"] == in_place
    assert (made["retention_kv_heads"], made["retention_state_rows"]) == (2, 136)
    per_slot = eng.kv_spec.state_bytes_per_slot
    assert made["state_bytes_per_slot"] == per_slot
    assert "serve/prefill/commit_kv" not in spans
    commits = spans["serve/prefill/commit_state"]
    assert len(commits) == sched.prefills
    waves = spans["serve/prefill/device_wait"]
    if in_place:    # the wave wrote its slots itself and says how much
        assert all(c["bytes"] == 0 for c in commits)
        assert sum(w["state_written_bytes"] for w in waves) \
            == len(reqs) * per_slot
    else:
        assert all(c["bytes"] == SLOTS * per_slot for c in commits)
        assert "state_written_bytes" not in waves[0]
    assert {a["state"] for a in spans["serve/prefill/commit"]} == {"recurrent"}
    assert {a["state"] for a in spans["serve/admit/place"]} == {"recurrent"}
    steps = 0
    for a in spans["serve/decode/window_sync"]:
        steps += a["steps"]
        assert 0 < a["linear_state_bytes"] <= a["steps"] * 2 * SLOTS * per_slot
        assert a["linear_state_bytes"] % (2 * per_slot) == 0
    assert steps == sched.decode_steps
    assert waves[0]["retention_layers"] == 3
    assert waves[0]["retention_rows"] == 3 * SLOTS
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        trace_report.render(str(next(tmp_path.glob("*.jsonl"))))
    text = out.getvalue()
    assert "state_layers=3" in text and "paged_state=none" in text
    assert re.search(r"\[serve\] recurrent state alone \(no paged layer\): "
                     r"[\d.]+ MB of it read and written a decode step", text)


@pytest.mark.parametrize("family_name", ("granite", "ling"))
def test_the_other_recurrent_models_take_either_way_to_the_same_tokens(
        family_name, monkeypatch):
    """A Mamba-2 and a KDA model (state far under the threshold: the commit
    program writes it) forced through the in-place way: the same served
    tokens, so the rule moves memory and a dispatch, not results."""
    from flexflow_tpu.models import (BailingHybridConfig, GraniteHybridConfig,
                                     build_bailing_hybrid,
                                     build_granite_hybrid)
    from flexflow_tpu.serving import valid_prompt_inputs, valid_step_inputs

    build, g, inputs = {
        "granite": (build_granite_hybrid, GraniteHybridConfig.tiny(seq=48),
                    (valid_prompt_inputs, valid_step_inputs)),
        "ling": (build_bailing_hybrid, BailingHybridConfig.tiny(seq=48),
                 (positions_valid_prompt_inputs, positions_valid_step_inputs)),
    }[family_name]
    served = {}
    for in_place in (False, True):
        if in_place:
            monkeypatch.setattr(kv_cache, "IN_PLACE_STATE_BYTES", 0)
        model = FFModel(ffconfig(SLOTS))
        build(model, g, batch=SLOTS)
        eng = compile_serving(model, max_batch_slots=SLOTS, max_decode_len=16,
                              kv_page_size=8)
        eng.init(seed=3)
        assert eng.kv.writes_state_in_place == in_place
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i, prompt=[int(t) for t in rng.integers(1, g.vocab, n)],
                        max_new_tokens=new, arrival_s=0.0)
                for i, (n, new) in enumerate([(5, 10), (17, 6), (30, 12),
                                              (9, 8), (12, 7), (20, 9)])]
        ContinuousBatchingScheduler(eng, eng.params, *inputs,
                                    eos_id=None).run(reqs)
        served[in_place] = [r.tokens for r in reqs]
    assert served[True] == served[False]
    assert all(len(t) for t in served[True])


# ------------------------------------------------------- counts and the file
def test_the_configuration_file_against_the_catalog_and_the_issue():
    cfg = mf.read_named("configs", PUBLISHED)
    assert cfg["family"] == "brumby" and cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 6
    assert cfg["published"]["num_hidden_layers"] == 40
    widths = {"hidden_size": 5120, "intermediate_size": 17408,
              "num_attention_heads": 40, "num_key_value_heads": 8,
              "head_dim": 128, "vocab_size": 151936, "rope_theta": 1000000,
              "rms_norm_eps": 1e-06, "tie_word_embeddings": False}
    assert {k: cfg[k] for k in widths} == widths
    for key in ("source", "deployment", "departures", "assumed"):
        assert cfg[key]
    assumed = cfg["assumed"]
    assert (assumed["power_degree"], assumed["eps"], assumed["gate_logit_std"],
            assumed["serve_positions"]) == (2, 1e-6, 0.5, 1024)
    if CATALOG.exists():
        row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
                   if r["name"] == PUBLISHED)
        assert cfg["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers"}
    manifest = mf.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == PUBLISHED)
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    cell = mf.load_cell(manifest, CELL)
    assert cell.chips == 1 and cell.traffic_name == "serve-longanswer"
    assert (cell.system["max_batch_slots"], cell.system["max_decode_len"]) \
        == (16, 512)
    tr = cell.traffic
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 128,
                                "sigma": 0.8, "min": 16, "max": 512}
    assert tr["output_len"] == {"dist": "lognormal", "median": 256,
                                "sigma": 0.6, "min": 32, "max": 512}
    assert (tr["shape_seed"], tr["drain_limit_s"], tr["warmup_requests"],
            tr["parity_requests"], tr["trace_seconds"], tr["trace_ramp_s"]) \
        == (24, 20, 6, 8, 10, 4)
    assert tr["rate_rps"] == cell.system["traffic"]["rate_rps"]


def test_flop_and_byte_functions_against_hand_counts_and_the_program():
    cfg = mf.read_named("configs", PUBLISHED)
    g = family.program_config(cfg)
    layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 2 * 128 \
        + 3 * 5120 * 17408 + 2 * 5120
    assert layer == 330352896 == g.layer_params() == flops.layer_params(cfg)
    held = 6 * layer + 2 * 151936 * 5120 + 5120
    assert held == 3537947136 == g.param_count() == flops.param_count(cfg)
    assert round(flops.param_count(cfg, layers=40) / 1e9, 2) == 14.77
    assert flops.param_count(cfg, layers=40) \
        == family.program_config(dict(cfg, num_hidden_layers=40)).param_count()
    # the graph's own weights, to the parameter
    model = FFModel(ffconfig(1))
    build_brumby(model, dataclass_replace(g, seq=8), batch=1)
    assert sum(int(np.prod(s.shape)) for l in model.layers
               for s in l.weight_specs.values()) == held
    assert flops.state_rows(cfg) == 8256
    assert flops.state_bytes_per_slot(cfg) == 6 * 34080768 \
        == g.state_bytes_per_slot()
    per_token = (3 * 8 + 2 * 40) * 8256 * 129
    assert flops.retention_flops_per_token(cfg) == per_token \
        == pr.recurrence_flops_per_token(40, 8, 128)
    assert flops.train_flops_per_token(cfg, 1024) == g.flops_per_token()
    system = {"max_batch_slots": 16}
    # a wave's least: the pair form under the diagonal and the state built
    # once, a quarter of the recurrence's own products at 1024 positions
    least = 40 * 4 * 128 * 1025 / 2 + 8 * 2 * 8256 * 129
    assert flops.retention_wave_flops_per_token(cfg, 1024) == least \
        == 27536384.0 < per_token / 4
    assert flops.retention_wave_flops_per_token(cfg, 32768) == per_token
    wave = flops.prefill_wave_need(cfg, system, {}, {"retention_rows": 6 * 3})
    plain = 2 * 16384 * 6 * (layer - 256 - 10240) + 2 * 16 * 5120 * 151936
    assert wave == {"flops": float(plain + 18 * 1024 * least), "bytes": 0.0}
    live = 7
    moved = 2.0 * live * 6 * 34080768
    step = flops.decode_step_need(cfg, system, {},
                                  {"linear_state_bytes": moved})
    assert step["bytes"] == 2 * (held - 151936 * 5120 + live * 5120) + moved
    ret = flops.retention_step_need(
        cfg, system, {}, {"linear_state_bytes": 3 * moved, "steps": 3})
    assert ret == {"flops": float(live * 6 * per_token), "bytes": moved}
    scan = flops.retention_scan_need(cfg, system, {}, {"retention_rows": 18})
    assert scan["flops"] == 18 * 1024 * least
    assert scan["bytes"] == 18 * (1024 * ((80 + 16) * 128 * 2 + 32) + 34080768)
    # the program's own counter at the published head width: a step reports
    # 2 x live x what `state_bytes_per_slot` counts (the 8256 rows the
    # recurrence needs), whatever the layout allocates (8704 rows and a
    # square normaliser), so that live slots derived from it are whole
    wide = wide_config()
    eng = engine_for(wide)
    for slot in (0, 2, 3):
        eng.kv.admit(slot, 4, 16)
    eng.kv.push()
    state = eng.kv.state
    _logits, state = eng.decode_step(
        eng.params, state,
        positions_valid_step_inputs(jnp.ones((SLOTS, 1), jnp.int32), state))
    need = flops.state_bytes_per_slot(file_config(wide))
    assert need == 2 * 8256 * 129 * 4 < eng.kv_spec.state_bytes_per_slot
    assert float(state[STATS_KEY]["linear_state_bytes"]) == 2 * 3 * need
    ret = flops.retention_step_need(
        file_config(wide), system, {},
        {"linear_state_bytes": 2.0 * 3 * need, "steps": 1})
    assert ret["bytes"] == 2.0 * 3 * need


def dataclass_replace(g, **kw):
    import dataclasses

    return dataclasses.replace(g, **kw)


@pytest.mark.parametrize("wide", (False, True))
def test_instructions_under_the_named_scopes_of_the_compiled_programs(wide):
    """What `retention_scan_roofline.brumby` and `retention_step_roofline
    .brumby` read: the operations the compiled programs put under the two
    named scopes. `wide`: at the published head width the step under
    `ff_power_retention_step` is the kernel's call (interpreted here: the
    loop over its grid; `tests/test_chip_compile.py` finds the Mosaic call
    under the scope in the chip's own program)."""
    g = wide_config() if wide else BrumbyConfig.tiny(seq=48)
    eng = engine_for(g)
    ids = np.ones((SLOTS, g.seq), np.int32)
    lengths = np.full(SLOTS, 7, np.int32)
    for slot in range(SLOTS):
        eng.kv.admit(slot, 7, 16)
    eng.kv.push()
    _tok, kv_state = eng.prefill_first_tokens(
        eng.params, positions_valid_prompt_inputs(ids, lengths), lengths)
    kv_state.pop(STATS_KEY)
    eng.kv.commit_prefill(kv_state, np.arange(SLOTS, dtype=np.int32), lengths)
    state = eng.kv.state
    _logits, state = eng.decode_step(
        eng.params, state,
        positions_valid_step_inputs(jnp.ones((SLOTS, 1), jnp.int32), state))
    state.pop(STATS_KEY)
    eng.kv.adopt(state)
    for program, scope in (("serve/prefill", pr.SCAN_SCOPE),
                           ("serve/decode", pr.STEP_SCOPE)):
        found = attribution.instructions_under(program, scope)
        assert found and all(found), (program, scope)
    lowered = eng._decode_jit.lower(
        eng.params, eng.kv.state, positions_valid_step_inputs(
            jnp.ones((SLOTS, 1), jnp.int32), eng.kv.state)).as_text(
                debug_info=True)
    calls = [line for line in lowered.splitlines()
             if "ff_power_retention_step/" in line and "pallas_call" in line]
    assert bool(calls) == wide
