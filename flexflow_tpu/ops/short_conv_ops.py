"""Gated short convolution: the mixer of Liquid's LFM2 blocks (Hugging Face
`Lfm2ShortConv`), a depthwise causal convolution of a few taps between two
elementwise gates and two projections.

    [B | C | X] = u W_in                    three d-wide parts, in this order
    z_t = B_t * X_t                         the in-gate
    c_t = sum_{j < k} w_j * z_{t-k+1+j}     depthwise, causal, zeros before
                                            the sequence's start; no bias, no
                                            activation
    y_t = C_t * c_t                         the out-gate
    out = y W_out

As a recurrence the layer's state is its last k - 1 gated inputs `(z_{t-k+1},
.., z_{t-1})`, `[k - 1, d]` in the compute type: the smallest state a layer
here keeps (8 KB a slot at k = 3, d = 2048 in bf16). z is rounded to the
compute type where it is made, in every form, so that a decode step that
reads it from the state sees what the sequence form saw; the taps' products
and the two gates are f32.

Three forms of one op, chosen by `params["mode"]`, as the other recurrent ops
have them (ops/ssm_ops.py):

- None (training, evaluation): the whole sequence, the convolution as k
  shifted products.
- "state_out" (serving prefill): the same, and the state is handed out in
  `ctx.new_state[layer.name] = {"conv": [b, k - 1, d]}`: each row's last k - 1
  REAL gated inputs (`ssm_ops.conv_tail`'s rule: a right-padded row's state
  is taken at its last real token, a row with fewer than k - 1 real tokens
  keeps zeros ahead of them).
- "decode" (serving decode): one step on `ctx.state[layer.name]`, written
  back to `ctx.new_state`; a slot that `valid` does not name keeps its state.
  Reports (ctx.add_stat) `ssm_state_bytes`, the counter the state-space op
  reports its own under: the live slots' state, read and written.

The second input, `valid` `[b, s]` (int, 1 = a real token), says which
positions exist. Without it every position is real. Plain XLA throughout:
the layer's time is its two projections.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

if TYPE_CHECKING:
    from flexflow_tpu.core.layer import Layer
from flexflow_tpu.core.tensor import TensorSpec
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.ops.registry import LoweringCtx, register_op
from flexflow_tpu.ops.ssm_ops import conv_tail, _report_state_bytes


def _short_conv_infer(layer: Layer):
    x = layer.inputs[0].spec
    k = layer.params["kernel"]
    if k < 2:
        raise ValueError(f"short_conv: a kernel of {k} taps keeps no state")
    d = x.shape[-1]
    layer.weight_specs = {
        "in_proj": TensorSpec((d, 3 * d), x.dtype),
        "conv_w": TensorSpec((k, d), x.dtype),
        "out_proj": TensorSpec((d, d), x.dtype),
    }
    return [x]


def _short_conv_lower(layer: Layer, inputs, weights, ctx: LoweringCtx):
    x = inputs[0]
    k = layer.params["kernel"]
    mode = layer.params.get("mode")
    dt = x.dtype
    b, s, d = x.shape
    f32 = jnp.float32
    valid = (inputs[1] > 0) if len(inputs) > 1 else jnp.ones((b, s), bool)
    conv_w = weights["conv_w"].astype(f32)

    bcx = x @ weights["in_proj"].astype(dt)
    gate_in, gate_out, xs = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    z = (gate_in.astype(f32) * xs.astype(f32)).astype(dt)       # [b, s, d]

    if mode == "decode":
        if s != 1:
            raise NotImplementedError(
                "short_conv decode takes one token a step (a verify pass "
                "over several would have to roll the state back)")
        st = ctx.state[layer.name]
        window = jnp.concatenate([st["conv"], z.astype(st["conv"].dtype)],
                                 axis=1)                        # [b, k, d]
        conv = jnp.einsum("bkc,kc->bc", window.astype(f32), conv_w)[:, None]
        ctx.new_state[layer.name] = {
            "conv": jnp.where(valid[:, :1, None], window[:, 1:], st["conv"])}
        _report_state_bytes(ctx, valid, st)
    else:
        # causal depthwise conv: out[t] = sum_j w[j] z[t - k + 1 + j]
        zp = jnp.pad(z, [(0, 0), (k - 1, 0), (0, 0)])
        conv = sum(zp[:, j:j + s].astype(f32) * conv_w[j] for j in range(k))
    y = (gate_out.astype(f32) * conv).astype(dt)
    out = y @ weights["out_proj"].astype(dt)
    if mode == "state_out":
        # the tail is taken now, with the layer's output, so that no layer's
        # z stays live to the program's end (ssm_ops.py)
        out, tail = jax.lax.optimization_barrier(
            (out, conv_tail(z, valid, k)))
        ctx.hand_out_slot_state(layer.name, {"conv": tail}, valid)
    return [out]


def _short_conv_flops(layer: Layer):
    """Forward: the two projections, the taps and the two gates."""
    x = layer.inputs[0].spec
    d = x.shape[-1]
    tokens = x.num_elements // d
    return 2.0 * tokens * 4 * d * d \
        + 2.0 * tokens * d * (layer.params["kernel"] + 1)


def _short_conv_serving_params(params: dict, kind: str) -> dict:
    return dict(params, mode="decode" if kind == "decode" else "state_out")


def _short_conv_slot_state(layer: Layer) -> dict:
    x = layer.inputs[0].spec
    return {"conv": ((layer.params["kernel"] - 1, x.shape[-1]),
                     x.dtype.jnp_dtype)}


register_op(OperatorType.SHORT_CONV, _short_conv_infer, _short_conv_lower,
            _short_conv_flops, serving_params=_short_conv_serving_params,
            state_kind="recurrent", slot_state=_short_conv_slot_state,
            span_facts=lambda layer: {"conv_kernel": layer.params["kernel"]})
