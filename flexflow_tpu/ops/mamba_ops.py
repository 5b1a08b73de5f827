"""State-space mixer: Mamba-1 (Gu & Dao 2023, "Mamba: linear-time sequence
modeling with selective state spaces"), in the layout of Hugging Face's
Jamba mixer (three inner RMS norms on dt, B and C).

    [u | z] = x W_in                                  d -> C | C, C = d_inner
    u = silu(causal depthwise conv1d(u, width d_conv) + b_conv)
    [dt_r | B | C] = u W_x                            C -> R | N | N
    dt_r = RMS(dt_r; w_dt);  B = RMS(B; w_B);  C = RMS(C; w_C)
    dt = softplus(dt_r W_dt + b_dt)                   R -> C, float32
    A = -exp(A_log)                                   [N, C], float32
    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] B_t[n] u_t[c]
    y_t[c] = sum_n S_t[n, c] C_t[n] + D[c] u_t[c]
    out = (y * silu(z)) W_out

Unlike Mamba-2 (ops/ssm_ops.py: one scalar decay a head, so a chunk is a
masked matrix product) the decay differs for every channel c and state index
n: there are no heads, no gated norm and no matrix form of a chunk; the
recurrence is stepped. `A_log` and the state lie `[N, C]`, the channels on the
lanes (a `[C, 16]` f32 array pads its 16 to 128 lanes at rest: eight times the
bytes, in every slot's state and in every read of it); the published `[C, N]`
is the transpose.

Three forms of one op, chosen by `params["mode"]`, as the Mamba-2 op has
them, and a fourth that no other recurrent op has yet:

- None (training, evaluation): the whole sequence from a zero state.
- "state_out" (serving prefill wave): the same, and the state is handed out
  through `ctx.hand_out_slot_state`: `{"ssm": [b, N, C] f32, "conv": [b,
  d_conv - 1, C]}`.
- "decode" with one position (serving decode): one step of the recurrence on
  `ctx.state[layer.name]`, written back to `ctx.new_state`, under the named
  scope `ff_selective_step`. Reports `ssm_state_bytes` and
  `ssm_step_kernel_slots` (0: the step is the XLA form), as Mamba-2 does.
- "decode" with a block of `s > 1` positions (a prefill CHUNK): THE SEQUENCE
  FORM STARTED FROM A STATE. `ctx.state[layer.name]` holds the rows' state
  before the block (the conv tail and S: zeros for a prompt's first chunk),
  `ctx.new_state[layer.name]` what they are after the block's last REAL
  position. The op declares it (`OpDef.chunk_from_state`), and the serving
  engine then hands a chunk its slot's leaves and writes back what comes out
  (serving/engine.py). Reports `mamba_layers` and `mamba_rows` (the real
  positions scanned, summed over the layers).

The second input, `valid` `[b, s]` (int, 1 = a real token; a right-padded
prefix of ones a row), says which positions exist: at the others `dt` is 0
(the state neither decays nor takes anything in) and nothing enters the conv
tail. Without the input every position is real.

The scan of the sequence forms is one algorithm at every size, its form
chosen from the shapes and the mesh alone (`scan_path`, reported a lowered
layer by the `mamba/scan_path` span), under the named scope
`ff_selective_scan`:

- the kernel (`kernels/selective_scan.py`, `ff_selective_scan`) where the
  channels are whole tiles of 512 lanes, N whole sublane tiles and the
  program runs on one device: the state in VMEM over a row's time blocks,
  `exp(dt A)` made on the tile, the skip and the gate applied before the tile
  is written. Its gradient is the XLA form's (`custom_vjp`, recomputed).
- the XLA form (`_scan_xla`; gradients from JAX) elsewhere (every tiny model,
  a mesh of several devices): a `lax.scan` over blocks of `XLA_TIME_BLOCK`
  positions, an associative scan inside a block, so the f32 `[b, block, N,
  C]` intermediates are a block's.

`dt`, its softplus, `exp(dt A)` and the state are float32 whatever the
compute type; a state handed in any other type raises.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

if TYPE_CHECKING:
    from flexflow_tpu.core.layer import Layer
from flexflow_tpu import telemetry as tel
from flexflow_tpu.core.tensor import TensorSpec
from flexflow_tpu.dtype import DataType
from flexflow_tpu.kernels import selective_scan as scan_kernel
from flexflow_tpu.kernels.partition import multi_device
from flexflow_tpu.ops.norm_ops import rms_norm
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.ops.registry import LoweringCtx, register_op
from flexflow_tpu.ops.ssm_ops import (_mamba_serving_params,
                                      _report_state_bytes,
                                      _report_step_kernel, conv_tail)

SCAN_SCOPE = "ff_selective_scan"
STEP_SCOPE = "ff_selective_step"
# positions of a block of the XLA form: its f32 [b, block, N, C]
# intermediates (21 MB each at the served widths and one row) are a block's
XLA_TIME_BLOCK = 64


def _sizes(p):
    return p["d_inner"], p["d_state"], p["dt_rank"], p["d_conv"]


def _mamba_infer(layer: Layer):
    x = layer.inputs[0].spec
    c, n, r, k = _sizes(layer.params)
    d = x.shape[-1]
    f32 = DataType.FLOAT
    layer.weight_specs = {
        "in_proj": TensorSpec((d, 2 * c), x.dtype),
        "conv_w": TensorSpec((k, c), x.dtype),
        "bias_conv": TensorSpec((c,), x.dtype),
        "x_proj": TensorSpec((c, r + 2 * n), x.dtype),
        "dt_norm": TensorSpec((r,), x.dtype),
        "b_norm": TensorSpec((n,), x.dtype),
        "c_norm": TensorSpec((n,), x.dtype),
        "dt_proj": TensorSpec((r, c), x.dtype),
        "dt_bias": TensorSpec((c,), f32),
        "A_log": TensorSpec((n, c), f32),
        "D": TensorSpec((c,), f32),
        "out_proj": TensorSpec((c, d), x.dtype),
    }
    return [x]


def _softplus_dt(dt_raw, dt_bias, valid):
    """dt `[b, L, C]` f32: 0 where no token is."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias)
    return jnp.where(valid[..., None], dt, 0.0)


def _scan_xla(u, dt_raw, z, bm, cm, a, d_skip, dt_bias, s0, valid):
    """`selective_scan` in plain XLA (jax.numpy), differentiable by JAX: a
    `lax.scan` over blocks of XLA_TIME_BLOCK positions that carries the state,
    inside a block an associative scan of the pairs (decay, input) under
    (a1, x1) . (a2, x2) = (a1 a2, a2 x1 + x2)."""
    b, length, c = u.shape
    q = min(XLA_TIME_BLOCK, length)
    pad = -length % q
    dt = _softplus_dt(dt_raw, dt_bias, valid)
    uf = u.astype(jnp.float32)
    dtu = dt * uf
    bm, cm = bm.astype(jnp.float32), cm.astype(jnp.float32)
    if pad:     # steps with dt = 0: the state stays, y is dropped
        dt, dtu, bm, cm = (
            jnp.pad(t, [(0, 0), (0, pad), (0, 0)]) for t in (dt, dtu, bm, cm))

    def blocks(t):      # [b, L, ...] -> [L / q, b, q, ...]
        return jnp.moveaxis(t.reshape((b, -1, q) + t.shape[2:]), 1, 0)

    def combine(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]

    def block(state, xs):
        dt_b, dtu_b, b_b, c_b = xs
        decay = jnp.exp(dt_b[:, :, None, :] * a)                # [b, q, N, C]
        taken = dtu_b[:, :, None, :] * b_b[..., None]
        decays, sums = jax.lax.associative_scan(combine, (decay, taken),
                                                axis=1)
        states = decays * state[:, None] + sums
        return states[:, -1], jnp.einsum("bqnc,bqn->bqc", states, c_b)

    last, y = jax.lax.scan(block, s0.astype(jnp.float32),
                           tuple(blocks(t) for t in (dt, dtu, bm, cm)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, -1, c)[:, :length]
    y = (y + d_skip * uf) * jax.nn.silu(z.astype(jnp.float32))
    return y.astype(u.dtype), last


def _scan_forward(u, dt_raw, z, bm, cm, a, d_skip, dt_bias, s0, valid, tiles):
    return scan_kernel.selective_scan(
        u, dt_raw, z, bm, cm, a, d_skip, dt_bias, s0,
        jnp.sum(valid, axis=1), *tiles)


def _scan_backward(tiles, operands, g):
    *diff, valid = operands
    return jax.vjp(lambda *t: _scan_xla(*t, valid), *diff)[1](g) + (None,)


# The kernel forward, the XLA form's gradient (recomputed: no cell trains a
# Mamba-1 layer, so the backward's speed is nobody's). Static: the tile.
_scan_kernel = jax.custom_vjp(_scan_forward, nondiff_argnums=(10,))
_scan_kernel.defvjp(lambda *args: (_scan_forward(*args), args[:10]),
                    _scan_backward)


def scan_path(channels: int, d_state: int, dtype, mesh=None) -> dict:
    """Which form the scan takes, from the shapes and the mesh the program
    is lowered for: what a lowered layer reports in its `mamba/scan_path`
    span. `{"path": "kernel", "time_block": tq, "lane_tile": L}` or `{"path":
    "xla", "time_block": XLA_TIME_BLOCK}`. On a mesh of several devices the
    XLA form stays: GSPMD partitions it, and cannot partition a Mosaic
    call."""
    tiles = None if multi_device(mesh) else scan_kernel.scan_tiles(
        channels, d_state, jnp.dtype(dtype).itemsize)
    if tiles is None:
        return {"path": "xla", "time_block": XLA_TIME_BLOCK}
    return {"path": "kernel", "time_block": tiles[0], "lane_tile": tiles[1]}


def selective_scan(u, dt_raw, z, bm, cm, a, d_skip, dt_bias, s0, valid,
                   path: dict):
    """The recurrence from `s0`, the skip and the gate: u, dt_raw, z `[b, L,
    C]` in the compute type (dt_raw: dt's projection before its bias and
    softplus), bm, cm `[b, L, N]`, a `[N, C]` f32 (< 0), d_skip, dt_bias
    `[C]` f32, s0 `[b, N, C]` f32, valid `[b, L]` bool (a right-padded prefix
    of ones a row), `path` as `scan_path` says -> (`(y + D u) * silu(z)` `[b,
    L, C]` in u's type, the state after each row's last real position `[b,
    N, C]` f32). One result in either form."""
    with jax.named_scope(SCAN_SCOPE):
        if path["path"] == "kernel":
            return _scan_kernel(u, dt_raw, z, bm, cm, a, d_skip, dt_bias, s0,
                                valid, (path["time_block"], path["lane_tile"]))
        return _scan_xla(u, dt_raw, z, bm, cm, a, d_skip, dt_bias, s0, valid)


def _coefficients(act, weights, p, dt_):
    """dt's projection (before bias and softplus, in the compute type), B
    and C (f32) out of the activated conv output `[..., C]`."""
    _c, n, r, _k = _sizes(p)
    eps = p.get("eps", 1e-6)
    proj = act @ weights["x_proj"].astype(dt_)
    dt_r = rms_norm(proj[..., :r], weights["dt_norm"], eps)
    bm = rms_norm(proj[..., r:r + n].astype(jnp.float32),
                  weights["b_norm"].astype(jnp.float32), eps)
    cm = rms_norm(proj[..., r + n:].astype(jnp.float32),
                  weights["c_norm"].astype(jnp.float32), eps)
    return dt_r.astype(dt_) @ weights["dt_proj"].astype(dt_), bm, cm


def _mamba_lower(layer: Layer, inputs, weights, ctx: LoweringCtx):
    x = inputs[0]
    p = layer.params
    c, n, _r, k = _sizes(p)
    dt_ = x.dtype
    b, s, _d = x.shape
    valid = (inputs[1] > 0) if len(inputs) > 1 else jnp.ones((b, s), bool)
    a = -jnp.exp(weights["A_log"].astype(jnp.float32))          # [N, C]
    d_skip = weights["D"].astype(jnp.float32)
    dt_bias = weights["dt_bias"].astype(jnp.float32)
    conv_w = weights["conv_w"].astype(jnp.float32)
    conv_b = weights["bias_conv"].astype(jnp.float32)
    mode = p.get("mode")

    uz = x @ weights["in_proj"].astype(dt_)
    raw, z = uz[..., :c], uz[..., c:]
    st = ctx.state.get(layer.name) if mode == "decode" else None
    if st is not None and st["ssm"].dtype != jnp.float32:
        raise TypeError(f"mamba: the state is float32, not {st['ssm'].dtype}: "
                        "a state in the compute type loses what a long "
                        "context adds to it")

    if mode == "decode" and s == 1:
        window = jnp.concatenate([st["conv"], raw.astype(st["conv"].dtype)],
                                 axis=1)                        # [b, k, C]
        with tel.span("mamba/step_path", cat="compile", layer=layer.name,
                      path="xla"), jax.named_scope(STEP_SCOPE):
            conv = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32), conv_w)
            act = jax.nn.silu(conv + conv_b).astype(dt_)
            dt_raw, b_t, c_t = _coefficients(act, weights, p, dt_)
            dt = _softplus_dt(dt_raw, dt_bias, valid[:, 0])     # [b, C]
            u = act.astype(jnp.float32)
            ssm = jnp.exp(dt[:, None, :] * a) * st["ssm"] \
                + (dt * u)[:, None, :] * b_t[:, :, None]
            y = jnp.sum(ssm * c_t[:, :, None], axis=1) + d_skip * u
            g = (y[:, None] * jax.nn.silu(z.astype(jnp.float32))).astype(dt_)
        ctx.new_state[layer.name] = {
            "ssm": ssm,
            "conv": jnp.where(valid[:, :1, None], window[:, 1:], st["conv"])}
        _report_state_bytes(ctx, valid, st)
        _report_step_kernel(ctx, jnp.int32(0))
        return [g @ weights["out_proj"].astype(dt_)]

    # the sequence forms: from the state handed in (a chunk) or from zeros
    if st is None:
        tail0 = jnp.zeros((b, k - 1, c), dt_)
        s0 = jnp.zeros((b, n, c), jnp.float32)
    else:
        tail0, s0 = st["conv"].astype(dt_), st["ssm"]
    # causal depthwise conv over the tail and the block:
    # out[t] = sum_j w[j] x[t - k + 1 + j]
    xp = jnp.concatenate([tail0, raw], axis=1)
    conv = sum(xp[:, j:j + s].astype(jnp.float32) * conv_w[j] for j in range(k))
    act = jax.nn.silu(conv + conv_b).astype(dt_)
    dt_raw, bm, cm = _coefficients(act, weights, p, dt_)
    path = scan_path(c, n, dt_, ctx.mesh)
    # one span a lowered layer (trace time): the form its scan took
    with tel.span("mamba/scan_path", cat="compile", layer=layer.name, **path):
        g, ssm = selective_scan(act, dt_raw, z, bm, cm, a, d_skip, dt_bias,
                                s0, valid, path)
    if mode is None:
        return [g @ weights["out_proj"].astype(dt_)]
    # each row's last k - 1 real rows of the tail and the block
    tail = conv_tail(xp, jnp.concatenate(
        [jnp.ones((b, k - 1), bool), valid], axis=1), k)
    out, tail = jax.lax.optimization_barrier(
        (g @ weights["out_proj"].astype(dt_), tail))
    fresh = {"ssm": ssm, "conv": tail}
    if mode == "state_out":
        ctx.hand_out_slot_state(layer.name, fresh, valid)
        return [out]
    ctx.new_state[layer.name] = {
        key: leaf.astype(st[key].dtype) for key, leaf in fresh.items()}
    ctx.add_stat("mamba_layers", jnp.float32(1))
    ctx.add_stat("mamba_rows", jnp.sum(valid).astype(jnp.float32))
    return [out]


def _mamba_flops(layer: Layer):
    """Forward: the four projections, and the recurrence's own multiply-adds
    (the decay's product, the input's, the update and the read-out: 4 a
    channel, state index and token, counted as 2 operations each)."""
    x = layer.inputs[0].spec
    c, n, r, _k = _sizes(layer.params)
    tokens = x.num_elements // x.shape[-1]
    proj = x.shape[-1] * 2 * c + c * (r + 2 * n) + r * c + c * x.shape[-1]
    return 2.0 * tokens * proj + 8.0 * tokens * c * n


def _mamba_slot_state(layer: Layer) -> dict:
    c, n, _r, k = _sizes(layer.params)
    return {"ssm": ((n, c), jnp.float32),
            "conv": ((k - 1, c), layer.inputs[0].spec.dtype.jnp_dtype)}


register_op(OperatorType.MAMBA, _mamba_infer, _mamba_lower, _mamba_flops,
            serving_params=_mamba_serving_params, state_kind="recurrent",
            slot_state=_mamba_slot_state, chunk_from_state=True,
            uncast_weights=("A_log", "D", "dt_bias"),
            span_facts=lambda layer: {
                "ssm_state_shape": [layer.params["d_state"],
                                    layer.params["d_inner"]]})
