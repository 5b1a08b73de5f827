"""LayerNorm, Softmax, Dropout.

Reference analog: src/ops/layer_norm.cc (601 LoC custom CUDA), softmax.cc
(418, cuDNN), dropout.cc (362, cuDNN dropout states). Dropout keys derive from
the trace rng folded with the layer guid, so every layer and step draws an
independent stream without any device-side state objects.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from typing import TYPE_CHECKING
if TYPE_CHECKING:
    from flexflow_tpu.core.layer import Layer
from flexflow_tpu.core.tensor import TensorSpec
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.ops.registry import register_op, LoweringCtx


def _ln_infer(layer: Layer):
    x = layer.inputs[0].spec
    axes = layer.params.get("axes")
    if axes is None:
        axes = [x.ndim - 1]
    axes = [a % x.ndim for a in axes]
    layer.params["axes"] = tuple(sorted(axes))
    if layer.params.get("elementwise_affine", True):
        nshape = tuple(x.shape[a] for a in layer.params["axes"])
        layer.weight_specs = {
            "gamma": TensorSpec(nshape, x.dtype),
            "beta": TensorSpec(nshape, x.dtype),
        }
    return [x]


def _ln_lower(layer: Layer, inputs, weights, ctx):
    x = inputs[0]
    axes = layer.params["axes"]
    eps = layer.params.get("eps", 1e-5)
    # statistics in f32 for bf16 stability; output back in the activation dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    if "gamma" in weights:
        bshape = [1] * x.ndim
        for a in axes:
            bshape[a] = x.shape[a]
        y = (y * weights["gamma"].astype(jnp.float32).reshape(bshape)
             + weights["beta"].astype(jnp.float32).reshape(bshape))
    return [y.astype(x.dtype)]


register_op(OperatorType.LAYERNORM, _ln_infer, _ln_lower)


def _softmax_infer(layer: Layer):
    return [layer.inputs[0].spec]


def _softmax_lower(layer: Layer, inputs, weights, ctx):
    axis = layer.params.get("axis", -1)
    fn = jax.nn.log_softmax if layer.op_type is OperatorType.LOG_SOFTMAX else jax.nn.softmax
    return [fn(inputs[0], axis=axis)]


register_op(OperatorType.SOFTMAX, _softmax_infer, _softmax_lower)
register_op(OperatorType.LOG_SOFTMAX, _softmax_infer, _softmax_lower)


def _dropout_infer(layer: Layer):
    return [layer.inputs[0].spec]


def _dropout_lower(layer: Layer, inputs, weights, ctx: LoweringCtx):
    x = inputs[0]
    rate = layer.params.get("rate", 0.5)
    if not ctx.training or rate <= 0.0:
        return [x]
    keep = 1.0 - rate
    mask = jax.random.bernoulli(ctx.rng_for(layer), keep, x.shape)
    return [jnp.where(mask, x / keep, 0.0).astype(x.dtype)]


register_op(OperatorType.DROPOUT, _dropout_infer, _dropout_lower,
            serving_params=lambda params, kind: dict(params, rate=0.0))


def _rms_infer(layer: Layer):
    x = layer.inputs[0].spec
    layer.weight_specs = {"gamma": TensorSpec((x.shape[-1],), x.dtype)}
    return [x]


def rms_norm(x, gamma, eps):
    """x / sqrt(mean(x^2) + eps) * gamma over the last axis, statistics in
    f32, result in x's dtype."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)


def _rms_lower(layer: Layer, inputs, weights, ctx):
    return [rms_norm(inputs[0], weights["gamma"], layer.params.get("eps", 1e-5))]


register_op(OperatorType.RMSNORM, _rms_infer, _rms_lower,
            flops=lambda layer: 4.0 * layer.inputs[0].spec.num_elements)
