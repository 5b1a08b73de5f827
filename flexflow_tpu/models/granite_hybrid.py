"""Granite 4.0-H (Hugging Face `GraniteMoeHybridForCausalLM`): a hybrid
decoder whose mixers are Mamba-2 layers with a grouped-query NoPE attention
layer every few, and every layer ends in a routed top-k expert layer beside
a shared gated MLP.

    h_0 = E[ids] * embedding_multiplier
    for l in layers:
        y = Mixer_l(RMS(h))          Mamba2 if layer_types[l] == "mamba" else Attn
        h = h + residual_multiplier * y
        x = RMS(h)
        h = h + residual_multiplier * (MoE(x) + Shared(x))
    logits = (RMS(h_L) / logits_scaling) W_head

No positions (NoPE), no biases but the conv's. The head is a weight of its
own: a layer of this graph reads its own weights only, so it cannot be tied
to the embedding as published. The division by `logits_scaling` comes
before the head, so that the head stays the graph's last layer (the serving
prefill applies it to each slot's last row alone); it is the same product.

The graph takes two inputs: `input_ids` and `valid` `[batch, seq]` (1 = a
token is there). The model has no position input, so `valid` is what tells
the state-space and expert layers which positions of a padded wave exist.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from flexflow_tpu.core.model import FFModel
from flexflow_tpu.dtype import DataType
from flexflow_tpu.initializers import (GlorotUniformInitializer, Initializer,
                                       OneInitializer, UniformInitializer)


@dataclasses.dataclass
class GraniteHybridConfig:
    vocab: int = 100352
    seq: int = 1024
    d_model: int = 4096
    layer_types: Tuple[str, ...] = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    heads: int = 32
    kv_heads: int = 8
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk: int = 256
    num_experts: int = 72
    experts_per_tok: int = 10
    expert_width: int = 768
    shared_width: int = 1536
    # expert ids [lo, hi) that this holder computes; the router stays
    # num_experts wide
    experts_held: Tuple[int, int] = (0, 72)
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    eps: float = 1e-5
    dtype: str = "float32"      # the graph's (and so the weights') type

    @staticmethod
    def tiny(seq: int = 48):
        return GraniteHybridConfig(
            vocab=512, seq=seq, d_model=64,
            layer_types=("mamba", "mamba", "attention", "mamba"),
            heads=4, kv_heads=2, mamba_heads=8, mamba_head_dim=16,
            mamba_d_state=16, mamba_chunk=16, num_experts=8,
            experts_per_tok=3, expert_width=32, shared_width=48,
            experts_held=(0, 4))

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.heads

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    def mixer_params(self, kind: str) -> int:
        d = self.d_model
        if kind == "attention":
            return 2 * d * d + 2 * d * self.kv_heads * self.head_dim
        conv_dim = self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state
        return (d * (self.d_inner + conv_dim + self.mamba_heads)
                + self.d_inner * d)

    def matmul_params_per_token(self) -> float:
        """Parameters a token is multiplied with here: mixers' projections,
        the router, the shared MLP, the EXPECTED share of its k experts that
        is held here, and the head."""
        d = self.d_model
        lo, hi = self.experts_held
        per_layer = (d * self.num_experts + 3 * d * self.shared_width
                     + self.experts_per_tok * (hi - lo) / self.num_experts
                     * 3 * d * self.expert_width)
        return (sum(self.mixer_params(k) for k in self.layer_types)
                + self.layers * per_layer + d * self.vocab)

    def flops_per_token(self) -> float:
        """Training (forward + backward) FLOPs a token needs: 6 a multiplied
        parameter, the attention layers' scores and values over the full
        square (the MFU convention, as GPT2Config counts), and the
        state-space recurrence's own products (2 * 2 * P * N a head)."""
        n_attn = sum(k == "attention" for k in self.layer_types)
        n_mamba = self.layers - n_attn
        attn = n_attn * 2 * 2 * self.seq * self.d_model
        ssm = n_mamba * 4 * self.mamba_heads * self.mamba_head_dim \
            * self.mamba_d_state
        return 6.0 * self.matmul_params_per_token() + 3.0 * (attn + ssm)

    def param_count(self) -> int:
        d = self.d_model
        lo, hi = self.experts_held
        conv_dim = self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state
        mamba_small = (self.mamba_d_conv * conv_dim + conv_dim
                       + 3 * self.mamba_heads + self.d_inner)
        per_layer = (2 * d + d * self.num_experts + 3 * d * self.shared_width
                     + (hi - lo) * 3 * d * self.expert_width)
        mixers = sum(self.mixer_params(k) + (mamba_small if k == "mamba" else 0)
                     for k in self.layer_types)
        return 2 * self.vocab * d + d + mixers + self.layers * per_layer


class _ALog(Initializer):
    """A = -exp(A_log), A uniform in [1, 16] (the Mamba-2 convention)."""

    def __call__(self, key, spec):
        return jnp.log(jax.random.uniform(key, spec.shape, jnp.float32, 1.0, 16.0))


class _DtBias(Initializer):
    """softplus(dt_bias) log-uniform in [lo, hi], no smaller than `floor`:
    the inverse softplus of such a dt (the Mamba-2 convention), so that the
    state neither blows up nor vanishes under random weights."""

    def __init__(self, lo: float = 1e-3, hi: float = 1e-1, floor: float = 0.0):
        self.lo, self.hi, self.floor = lo, hi, floor

    def __call__(self, key, spec):
        dt = jnp.exp(jax.random.uniform(key, spec.shape, jnp.float32,
                                        math.log(self.lo), math.log(self.hi)))
        dt = jnp.maximum(dt, self.floor)
        return dt + jnp.log(-jnp.expm1(-dt))


class _PerExpertGlorot(Initializer):
    """Glorot over each expert's own [in, out] matrix of an [experts, in,
    out] weight."""

    def __call__(self, key, spec):
        limit = math.sqrt(6.0 / (spec.shape[1] + spec.shape[2]))
        return jax.random.uniform(key, spec.shape, spec.dtype.jnp_dtype,
                                  -limit, limit)


def _mamba_initializers(cfg: GraniteHybridConfig):
    # conv_w as torch's Conv1d default: uniform in +-1/sqrt(fan_in = d_conv)
    bound = 1.0 / math.sqrt(cfg.mamba_d_conv)
    return {"A_log": _ALog(), "dt_bias": _DtBias(), "D": OneInitializer(),
            "norm": OneInitializer(),
            "conv_w": UniformInitializer(min_value=-bound, max_value=bound),
            "in_proj": GlorotUniformInitializer(),
            "out_proj": GlorotUniformInitializer()}


def build_granite_hybrid(model: FFModel, cfg: GraniteHybridConfig,
                         batch: int = 8):
    """Adds the graph to `model`; returns ((ids, valid), logits). Trains
    through `model.compile` / `fit` (x = [ids, valid]) and serves through
    `compile_serving`, whose programs find the layers that carry state by
    their kind."""
    dtype = DataType.from_any(cfg.dtype)
    ids = model.create_tensor([batch, cfg.seq], DataType.INT32, name="input_ids")
    valid = model.create_tensor([batch, cfg.seq], DataType.INT32, name="valid")
    t = model.embedding(ids, cfg.vocab, cfg.d_model, dtype=dtype, name="embed")
    t = model.scalar_multiply(t, cfg.embedding_multiplier, name="embed_scale")
    experts_init = {"w_in": _PerExpertGlorot(), "w_out": _PerExpertGlorot()}
    for i, kind in enumerate(cfg.layer_types):
        h = model.rms_norm(t, eps=cfg.eps, name=f"l{i}_norm_in")
        if kind == "mamba":
            y = model.mamba2(h, cfg.mamba_heads, cfg.mamba_head_dim,
                             cfg.mamba_d_state, d_conv=cfg.mamba_d_conv,
                             chunk=cfg.mamba_chunk, n_groups=cfg.mamba_n_groups,
                             eps=cfg.eps, valid=valid,
                             initializers=_mamba_initializers(cfg),
                             name=f"l{i}_mamba")
        elif kind == "attention":
            y = model.multihead_attention(
                h, h, h, cfg.d_model, cfg.heads, bias=False, causal=True,
                num_kv_heads=cfg.kv_heads, scale=cfg.attention_multiplier,
                name=f"l{i}_attn")
        else:
            raise ValueError(f"layer_types[{i}] = {kind!r}")
        y = model.scalar_multiply(y, cfg.residual_multiplier, name=f"l{i}_mix_scale")
        t = model.add(t, y, name=f"l{i}_res1")
        x = model.rms_norm(t, eps=cfg.eps, name=f"l{i}_norm_post")
        routed = model.moe_layer(x, cfg.num_experts, cfg.experts_per_tok,
                                 cfg.expert_width, cfg.experts_held,
                                 valid=valid, initializers=experts_init,
                                 name=f"l{i}_moe")
        ab = model.dense(x, 2 * cfg.shared_width, use_bias=False,
                         name=f"l{i}_shared_in")
        a, b = model.split(ab, 2, axis=-1, name=f"l{i}_shared_split")
        gated = model.multiply(model.silu(a, name=f"l{i}_shared_act"), b,
                               name=f"l{i}_shared_gate")
        shared = model.dense(gated, cfg.d_model, use_bias=False,
                             name=f"l{i}_shared_out")
        ff = model.add(routed, shared, name=f"l{i}_ff")
        ff = model.scalar_multiply(ff, cfg.residual_multiplier,
                                   name=f"l{i}_ff_scale")
        t = model.add(t, ff, name=f"l{i}_res2")
    t = model.rms_norm(t, eps=cfg.eps, name="norm_f")
    t = model.scalar_multiply(t, 1.0 / cfg.logits_scaling, name="logits_scale")
    logits = model.dense(t, cfg.vocab, use_bias=False, name="lm_head")
    return (ids, valid), logits
