"""Bailing hybrid decoders (Hugging Face model_type `bailing_hybrid`;
Ling-3.0-flash is one): linear-attention layers (Kimi Delta Attention: a
gated delta rule over a matrix state a head, ops/kda_ops.py) with every
`layer_group_size`-th layer multi-head latent attention instead, the first
`first_k_dense` layers ending in a gated-SiLU MLP and the others in a routed
expert layer (sigmoid scores, a selection bias, group-limited top-k,
normalised and scaled gates) beside a shared expert.

    h_0 = E[ids]
    for l in layers:
        mix = LatentAttn_l if (l + 1) % layer_group_size == 0 else KDA_l
        h = h + mix(RMS(h))
        x = RMS(h)
        h = h + (MLP_l(x)  if l < first_k_dense  else  MoE_l(x) + Shared_l(x))
    logits = RMS(h_L) W_head

The latent-attention layers have no query latent (`q_lora_rank` null), plain
rotary frequencies and a sigmoid gate a head on their output; the KDA layers
have no positions. No biases; an untied head. The graph takes three inputs:
`input_ids`, `positions` and `valid` `[batch, seq]` (1 = a token is there:
the KDA layers' state stops at a row's last token, the expert layers route
only those). The multi-token-prediction module of the published model is
not built (the main model's logits do not depend on it), nor are the SwiGLU
clamps of its last layers (`*_swiglu_limit_list`: 0, no clamp, on the
layers a configuration here keeps).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

from flexflow_tpu.core.model import FFModel
from flexflow_tpu.dtype import DataType
from flexflow_tpu.initializers import (GlorotUniformInitializer,
                                       OneInitializer, UniformInitializer)
from flexflow_tpu.models.deepseek_v3 import _gated_mlp
from flexflow_tpu.models.granite_hybrid import _ALog, _PerExpertGlorot


@dataclasses.dataclass
class BailingHybridConfig:
    vocab: int = 157184
    seq: int = 1024
    d_model: int = 2560
    layers: int = 42
    layer_group_size: int = 6
    first_k_dense: int = 2
    heads: int = 32
    head_dim: int = 128             # the KDA layers' keys, queries and values
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_conv: int = 4
    kda_lower_bound: float = -5.0
    # the decay gate g = lower_bound * sigmoid(exp(A_log) (f + dt_bias)):
    # exp(A_log) uniform in [1, 16] a head (Mamba-2's convention), dt_bias
    # uniform in this range a channel, so that under random weights decays
    # near both ends of (e^lower_bound, 1) occur
    kda_dt_bias_range: Tuple[float, float] = (-1.5, 0.5)
    dense_width: int = 6144
    num_experts: int = 512
    experts_per_tok: int = 8
    expert_width: int = 768
    shared_width: int = 768
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    # expert ids [lo, hi) that this holder computes; the router, the groups
    # and the top-k stay num_experts wide
    experts_held: Tuple[int, int] = (0, 512)
    rope_theta: float = 6000000.0
    # the selection bias is drawn uniform in +-this (models/deepseek_v3.py)
    score_bias_range: float = 0.02
    eps: float = 1e-6
    dtype: str = "float32"      # the graph's (and so the weights') type

    @staticmethod
    def tiny(seq: int = 48):
        return BailingHybridConfig(
            vocab=512, seq=seq, d_model=64, layers=4, layer_group_size=3,
            first_k_dense=1, heads=4, head_dim=16, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            dense_width=96, num_experts=16, experts_per_tok=3,
            expert_width=32, shared_width=32, n_group=4, topk_group=2,
            experts_held=(0, 8))

    @property
    def kinds(self) -> Tuple[str, ...]:
        """A layer's mixer, layer by layer: "latent" or "kda"."""
        return tuple("latent" if (i + 1) % self.layer_group_size == 0
                     else "kda" for i in range(self.layers))

    @property
    def latent_dim(self) -> int:
        """Values a token leaves in a latent layer's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kda_inner(self) -> int:
        return self.heads * self.head_dim

    def mixer_params(self, kind: str) -> int:
        """A mixer's matrices, as multiplied with every token."""
        d, h = self.d_model, self.heads
        if kind == "kda":   # q, k, v, decay gate, output gate, beta; out
            return d * (5 * self.kda_inner + h) + self.kda_inner * d
        return (d * h * (self.qk_nope_head_dim + self.qk_rope_head_dim)
                + d * self.latent_dim
                + self.kv_lora_rank * h * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + h * self.v_head_dim * d + d * h)

    def mixer_small_params(self, kind: str) -> int:
        """A mixer's vectors: a KDA layer's convolution, A_log, dt_bias and
        head norm; a latent layer's K/V norm."""
        if kind == "kda":
            return (self.d_conv * 3 * self.kda_inner + self.heads
                    + self.kda_inner + self.head_dim)
        return self.kv_lora_rank

    def expert_params(self) -> int:
        return 3 * self.d_model * self.expert_width

    def feed_forward_params(self, layer: int) -> int:
        """What every token is multiplied with behind layer `layer`'s mixer:
        the MLP, or the router and the shared expert."""
        d = self.d_model
        if layer < self.first_k_dense:
            return 3 * d * self.dense_width
        return d * self.num_experts + 3 * d * self.shared_width

    def matmul_params_per_token(self) -> float:
        """Parameters a token is multiplied with here: the mixers'
        projections, the dense layers' MLPs, the router, the shared expert,
        the EXPECTED share of its k experts that is held here, and the head."""
        lo, hi = self.experts_held
        routed = (self.experts_per_tok * (hi - lo) / self.num_experts
                  * self.expert_params())
        return (sum(self.mixer_params(k) + self.feed_forward_params(i)
                    + (routed if i >= self.first_k_dense else 0)
                    for i, k in enumerate(self.kinds))
                + self.d_model * self.vocab)

    def flops_per_token(self) -> float:
        """Training (forward + backward) FLOPs a token needs: 6 a multiplied
        parameter, the latent layers' scores and values over the full square
        (the MFU convention, as GPT2Config counts) and the delta rule's own
        products (7 D^2 a head, ops/kda_ops.py)."""
        attn = self.kinds.count("latent") * 2 * self.seq * self.heads * (
            self.qk_nope_head_dim + self.qk_rope_head_dim + self.v_head_dim)
        kda = self.kinds.count("kda") * 7 * self.heads * self.head_dim ** 2
        return 6.0 * self.matmul_params_per_token() + 3.0 * (attn + kda)

    def param_count(self) -> int:
        d = self.d_model
        lo, hi = self.experts_held
        return (2 * self.vocab * d + d + sum(
            self.mixer_params(k) + self.mixer_small_params(k) + 2 * d
            + self.feed_forward_params(i)
            + ((hi - lo) * self.expert_params() + self.num_experts
               if i >= self.first_k_dense else 0)
            for i, k in enumerate(self.kinds)))


def build_bailing_hybrid(model: FFModel, cfg: BailingHybridConfig,
                         batch: int = 8):
    """Adds the graph to `model`; returns ((ids, positions, valid), logits).
    Trains through `model.compile` / `fit` (x = [ids, positions, valid]) and
    serves through `compile_serving`, whose programs find the layers that
    carry state by their kind: the latent layers page a latent a token, the
    KDA layers keep a matrix state and a convolution tail a slot."""
    dtype = DataType.from_any(cfg.dtype)
    ids = model.create_tensor([batch, cfg.seq], DataType.INT32, name="input_ids")
    positions = model.create_tensor([batch, cfg.seq], DataType.INT32,
                                    name="positions")
    valid = model.create_tensor([batch, cfg.seq], DataType.INT32, name="valid")
    t = model.embedding(ids, cfg.vocab, cfg.d_model, dtype=dtype, name="embed")
    # conv_w as torch's Conv1d default: uniform in +-1/sqrt(fan_in = d_conv)
    bound = 1.0 / math.sqrt(cfg.d_conv)
    kda_init = {
        "A_log": _ALog(), "norm": OneInitializer(),
        "dt_bias": UniformInitializer(min_value=cfg.kda_dt_bias_range[0],
                                      max_value=cfg.kda_dt_bias_range[1]),
        "conv_w": UniformInitializer(min_value=-bound, max_value=bound),
        "in_proj": GlorotUniformInitializer(),
        "out_proj": GlorotUniformInitializer()}
    experts_init = {
        "w_in": _PerExpertGlorot(), "w_out": _PerExpertGlorot(),
        "score_bias": UniformInitializer(min_value=-cfg.score_bias_range,
                                         max_value=cfg.score_bias_range)}
    for i, kind in enumerate(cfg.kinds):
        h = model.rms_norm(t, eps=cfg.eps, name=f"l{i}_norm_in")
        if kind == "kda":
            y = model.kda(h, cfg.heads, cfg.head_dim, d_conv=cfg.d_conv,
                          lower_bound=cfg.kda_lower_bound, eps=cfg.eps,
                          valid=valid, initializers=kda_init,
                          name=f"l{i}_kda")
        else:
            y = model.latent_attention(
                h, positions, cfg.heads, None, cfg.kv_lora_rank,
                cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                eps=cfg.eps, rope_theta=cfg.rope_theta, rope_scaling=None,
                valid=valid, head_gate=True, name=f"l{i}_attn")
        t = model.add(t, y, name=f"l{i}_res1")
        x = model.rms_norm(t, eps=cfg.eps, name=f"l{i}_norm_post")
        if i < cfg.first_k_dense:
            ff = _gated_mlp(model, x, cfg.dense_width, cfg.d_model, f"l{i}_mlp")
        else:
            routed = model.moe_layer(
                x, cfg.num_experts, cfg.experts_per_tok, cfg.expert_width,
                cfg.experts_held, valid=valid, initializers=experts_init,
                scoring="sigmoid", n_group=cfg.n_group,
                topk_group=cfg.topk_group, norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                score_bias=True, name=f"l{i}_moe")
            shared = _gated_mlp(model, x, cfg.shared_width, cfg.d_model,
                                f"l{i}_shared")
            ff = model.add(routed, shared, name=f"l{i}_ff")
        t = model.add(t, ff, name=f"l{i}_res2")
    t = model.rms_norm(t, eps=cfg.eps, name="norm_f")
    logits = model.dense(t, cfg.vocab, use_bias=False, name="lm_head")
    return (ids, positions, valid), logits
