"""The Mamba-1 mixer (flexflow_tpu/ops/mamba_ops.py, kernels/
selective_scan.py) against the one-position recurrence of the plain
reference (benchmarks/harness/reference_jamba.py), one layer at a small size
on the CPU with seeded random weights, float32.

Tolerance: both sides compute in float32 and differ by the order of their
sums (an associative scan inside blocks of 64, or the kernel's stepped loop,
against the literal recurrence): about 1e-6 of the result's scale. RTOL 1e-4
leaves two orders for that and none for a fault.
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
from flexflow_tpu.kernels import selective_scan as kernel  # noqa: E402
from flexflow_tpu.ops import get_op_def, mamba_ops  # noqa: E402
from flexflow_tpu.ops.op_type import OperatorType  # noqa: E402
from flexflow_tpu.ops.registry import STATS_KEY, LoweringCtx  # noqa: E402
from harness import reference_jamba as reference  # noqa: E402
from served import off_by  # noqa: E402

RTOL = 1e-4
D, C, N, R, K = 32, 64, 8, 4, 4
HP = {"eps": 1e-6}


def layer_for(batch, seq, mode=None, valid=True, c=C):
    x = Tensor(TensorSpec((batch, seq, D), DataType.FLOAT), name="x")
    ins = [x] + ([Tensor(TensorSpec((batch, seq), DataType.INT32), name="v")]
                 if valid else [])
    params = {"d_inner": c, "d_state": N, "dt_rank": R, "d_conv": K,
              "eps": 1e-6}
    if mode:
        params["mode"] = mode
    layer = Layer(OperatorType.MAMBA, params, ins, name="m")
    get_op_def(OperatorType.MAMBA).infer(layer)
    return layer


def weights_for(seed=0, C=C):
    rng = np.random.default_rng(seed)

    def normal(shape, scale):
        return jnp.asarray(rng.normal(0, scale, shape), jnp.float32)

    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), C))
    return {"in_proj": normal((D, 2 * C), D ** -0.5),
            "conv_w": normal((K, C), 0.5), "bias_conv": normal((C,), 0.1),
            "x_proj": normal((C, R + 2 * N), C ** -0.5),
            "dt_norm": jnp.asarray(rng.uniform(0.5, 1.5, R), jnp.float32),
            "b_norm": jnp.asarray(rng.uniform(0.5, 1.5, N), jnp.float32),
            "c_norm": jnp.asarray(rng.uniform(0.5, 1.5, N), jnp.float32),
            "dt_proj": normal((R, C), R ** -0.5),
            "dt_bias": jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32),
            "A_log": jnp.asarray(np.log(rng.uniform(1, 16, (N, C))),
                                 jnp.float32),
            "D": normal((C,), 1.0), "out_proj": normal((C, D), C ** -0.5)}


def reference_weights(w):
    out = {k: v for k, v in w.items() if k not in ("bias_conv", "A_log")}
    return dict(out, conv_b=w["bias_conv"], A_log=w["A_log"].T)


def lower(layer, inputs, w, **ctx):
    ctx = LoweringCtx(stats={}, **ctx)
    out = get_op_def(OperatorType.MAMBA).lower(layer, inputs, w, ctx)[0]
    return out, ctx


def inputs_for(batch, seq, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(
        0, 1, (batch, seq, D)), jnp.float32)


def test_the_sequence_form_is_the_one_position_recurrence():
    """150 positions: two blocks of 64 and a part of one."""
    w, x = weights_for(), inputs_for(2, 150)
    got, _ = lower(layer_for(2, 150, valid=False), [x], w)
    want = reference.mamba(x, reference_weights(w), HP)
    assert off_by(got, want) <= RTOL


@pytest.mark.parametrize("cut", [1, 3, 64, 77, 149])
def test_a_sequence_cut_anywhere_and_continued_equals_the_whole(cut):
    """The chunk form: the first part from zeros hands out its state (S and
    the conv tail), the second starts from it; together they are the whole,
    and the state at the end is the reference's."""
    w, x = weights_for(), inputs_for(2, 150)
    rw = reference_weights(w)
    zeros = (jnp.zeros((2, C, N)), jnp.zeros((2, K - 1, C)))
    want, (s_want, tail_want) = reference.mamba(x, rw, HP, zeros)
    state = {"m": {"ssm": jnp.zeros((2, N, C)),
                   "conv": jnp.zeros((2, K - 1, C))}}
    parts = []
    for part in (x[:, :cut], x[:, cut:]):
        n = part.shape[1]
        out, ctx = lower(layer_for(2, n, "decode"),
                         [part, jnp.ones((2, n), jnp.int32)], w, state=state)
        state = {"m": ctx.new_state["m"]}
        if n > 1:       # a block of one position is a decode step
            assert float(ctx.stats["mamba_rows"]) == 2 * n
        parts.append(out)
    assert off_by(jnp.concatenate(parts, axis=1), want) <= RTOL
    assert off_by(state["m"]["ssm"], jnp.swapaxes(s_want, 1, 2)) <= RTOL
    assert off_by(state["m"]["conv"], tail_want) <= RTOL


@pytest.mark.parametrize("mode", ["state_out", "decode"])
def test_right_padding_hands_out_the_state_after_the_last_real_token(mode):
    """Rows of 150 positions holding 150, 70, 2 and 0 tokens: each row's
    state is that of its own tokens alone, and a row without a token keeps
    what it had (zeros from a wave, its own state in a chunk)."""
    w, x = weights_for(), inputs_for(4, 150)
    rw = reference_weights(w)
    lengths = [150, 70, 2, 0]
    valid = jnp.asarray(np.arange(150)[None] < np.asarray(lengths)[:, None],
                        jnp.int32)
    had = {"ssm": jnp.full((4, N, C), 0.5), "conv": jnp.full((4, K - 1, C), 2.0)}
    zeros = {"ssm": jnp.zeros((4, N, C)), "conv": jnp.zeros((4, K - 1, C))}
    start = had if mode == "decode" else zeros
    out, ctx = lower(layer_for(4, 150, mode), [x, valid], w,
                     state={"m": start} if mode == "decode" else {})
    got = ctx.new_state["m"]
    for row, n in enumerate(lengths[:3]):
        first = (jnp.swapaxes(start["ssm"][row:row + 1], 1, 2),
                 start["conv"][row:row + 1])
        want, (s_want, tail_want) = reference.mamba(
            x[row:row + 1, :n], rw, HP, first)
        assert off_by(out[row:row + 1, :n], want) <= RTOL
        assert off_by(got["ssm"][row], s_want[0].T) <= RTOL
        assert off_by(got["conv"][row], tail_want[0]) <= RTOL
    assert np.array_equal(np.asarray(got["ssm"][3]), np.asarray(start["ssm"][3]))
    assert np.array_equal(np.asarray(got["conv"][3]),
                          np.asarray(start["conv"][3]))


def test_the_decode_step_advances_only_the_live_slots():
    w = weights_for()
    rw = reference_weights(w)
    rng = np.random.default_rng(4)
    state = {"ssm": jnp.asarray(rng.normal(0, 1, (3, N, C)), jnp.float32),
             "conv": jnp.asarray(rng.normal(0, 1, (3, K - 1, C)), jnp.float32)}
    x = inputs_for(3, 1)
    live = jnp.asarray([[1], [0], [1]], jnp.int32)
    out, ctx = lower(layer_for(3, 1, "decode"), [x, live], w,
                     state={"m": state})
    got = ctx.new_state["m"]
    want, (s_want, tail_want) = reference.mamba(
        x, rw, HP, (jnp.swapaxes(state["ssm"], 1, 2), state["conv"]))
    for row in (0, 2):
        assert off_by(out[row], want[row]) <= RTOL
        assert off_by(got["ssm"][row], s_want[row].T) <= RTOL
        assert off_by(got["conv"][row], tail_want[row]) <= RTOL
    assert np.array_equal(np.asarray(got["ssm"][1]), np.asarray(state["ssm"][1]))
    assert np.array_equal(np.asarray(got["conv"][1]),
                          np.asarray(state["conv"][1]))
    per_slot = N * C * 4 + (K - 1) * C * 4
    assert float(ctx.stats["ssm_state_bytes"]) == 2 * 2 * per_slot
    assert int(ctx.stats["ssm_step_kernel_slots"]) == 0


def scan_operands(b, length, channels, n, dtype, seed=0):
    rng = np.random.default_rng(seed)

    def normal(shape, scale=1.0, dt=jnp.float32):
        return jnp.asarray(rng.normal(0, scale, shape), dt)

    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), channels))
    return (normal((b, length, channels), dt=dtype),
            normal((b, length, channels), 0.5, dtype),
            normal((b, length, channels), dt=dtype),
            normal((b, length, n)), normal((b, length, n)),
            -jnp.asarray(rng.uniform(1, 16, (n, channels)), jnp.float32),
            normal((channels,)),
            jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32),
            normal((b, n, channels)))


@pytest.mark.parametrize("lengths", [(40, 21), (64, 0)])
def test_the_kernel_interpreted_equals_the_xla_form_from_a_state(lengths):
    """Two rows of 40 positions at 512 channels (one lane tile) from a state
    that is not zeros, time blocks of 16 (two and a part), rows that end
    inside a block, on an edge and before the first: y where a token is and
    the last state are the XLA form's."""
    length = 40 if lengths[0] == 40 else 64
    ops = scan_operands(2, length, 512, 8, jnp.float32)
    valid = jnp.asarray(np.arange(length)[None] < np.asarray(lengths)[:, None])
    want_y, want_s = mamba_ops._scan_xla(*ops, valid)
    got_y, got_s = kernel._call(*ops, jnp.asarray(lengths), 16, 512, 8, True)
    for row, n in enumerate(lengths):
        if n:
            assert off_by(got_y[row, :n], want_y[row, :n]) <= RTOL
    assert off_by(got_s, want_s) <= RTOL
    assert np.array_equal(np.asarray(got_s[1]), np.asarray(ops[-1][1])) \
        == (lengths[1] == 0)


def test_the_path_follows_from_the_shapes_and_the_mesh():
    served = mamba_ops.scan_path(5120, 16, jnp.bfloat16)
    assert served == {"path": "kernel", "time_block": 256, "lane_tile": 512}
    assert mamba_ops.scan_path(128, 8, jnp.float32)["path"] == "xla"
    assert mamba_ops.scan_path(5120, 12, jnp.bfloat16)["path"] == "xla"
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("data",))
    assert mamba_ops.scan_path(5120, 16, jnp.bfloat16, mesh)["path"] == "xla"


def test_the_op_takes_the_kernel_at_whole_lane_tiles_and_says_so():
    """One layer at 512 channels (the kernel, interpreted) against the
    reference, from a state; its gradient is the XLA form's."""
    from flexflow_tpu import telemetry as tel

    c = 512
    w, x = weights_for(2, c), inputs_for(1, 24)
    state = {"m": {"ssm": jnp.full((1, N, c), 0.3),
                   "conv": jnp.full((1, K - 1, c), -0.2)}}
    out, ctx = lower(layer_for(1, 24, "decode", c=c),
                     [x, jnp.ones((1, 24), jnp.int32)], w, state=state)
    want, (s_want, _tail) = reference.mamba(
        x, reference_weights(w), HP,
        (jnp.swapaxes(state["m"]["ssm"], 1, 2), state["m"]["conv"]))
    assert off_by(out, want) <= RTOL
    assert off_by(ctx.new_state["m"]["ssm"][0], s_want[0].T) <= RTOL
    span = [s for s in tel.ring_spans() if s.name == "mamba/scan_path"][-1]
    assert span.args["path"] == "kernel" and span.args["lane_tile"] == 512

    def loss(fn):
        return lambda w, x: jnp.sum(jnp.square(fn(w, x)))

    got = jax.grad(loss(lambda w, x: lower(
        layer_for(1, 24, valid=False, c=c), [x], w)[0]), argnums=(0, 1))(w, x)
    ref = jax.grad(loss(lambda w, x: reference.mamba(
        x, reference_weights(w), HP)), argnums=(0, 1))(w, x)
    for name in w:
        assert off_by(got[0][name], ref[0][name]) <= 10 * RTOL, name
    assert off_by(got[1], ref[1]) <= 10 * RTOL


def test_the_gradient_through_the_op_is_the_references():
    w, x = weights_for(), inputs_for(2, 70)

    def loss(fn):
        return lambda w, x: jnp.sum(jnp.square(fn(w, x)))

    got = jax.grad(loss(lambda w, x: lower(
        layer_for(2, 70, valid=False), [x], w)[0]), argnums=(0, 1))(w, x)
    want = jax.grad(loss(lambda w, x: reference.mamba(
        x, reference_weights(w), HP)), argnums=(0, 1))(w, x)
    for name in w:
        assert off_by(got[0][name], want[0][name]) <= 10 * RTOL, name
    assert off_by(got[1], want[1]) <= 10 * RTOL


@pytest.mark.parametrize("steps", [1, 12])
def test_a_bf16_state_raises(steps):
    w = weights_for()
    state = {"m": {"ssm": jnp.zeros((2, N, C), jnp.bfloat16),
                   "conv": jnp.zeros((2, K - 1, C))}}
    with pytest.raises(TypeError, match="state is float32, not bfloat16"):
        lower(layer_for(2, steps, "decode"),
              [inputs_for(2, steps), jnp.ones((2, steps), jnp.int32)], w,
              state=state)


def test_a_bf16_decay_fails_the_tolerance():
    """The comparison is tight enough to catch `exp(dt A)` made in the next
    precision down: the same scan with the decay rounded to bfloat16 is off
    by a hundred times the tolerance over 150 positions."""
    ops = scan_operands(1, 150, 64, 8, jnp.float32, seed=3)
    u, dt_raw, z, bm, cm, a, d_skip, dt_bias, s0 = ops
    valid = jnp.ones((1, 150), bool)
    want, _ = mamba_ops._scan_xla(*ops, valid)
    dt = jax.nn.softplus(dt_raw + dt_bias)

    def step(s, t):
        dt_t, u_t, b_t, c_t = t
        decay = jnp.exp(dt_t[:, None, :] * a).astype(jnp.bfloat16)
        s = decay.astype(jnp.float32) * s \
            + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    _, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (dt, u, bm, cm)))
    low = (jnp.moveaxis(y, 0, 1) + d_skip * u) * jax.nn.silu(z)
    assert off_by(low, want) > 10 * RTOL
