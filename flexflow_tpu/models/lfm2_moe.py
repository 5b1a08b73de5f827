"""LFM2-MoE decoders (Hugging Face `Lfm2MoeForCausalLM`, model_type
`lfm2_moe`; LFM2-24B-A2B is one): a layer's mixer is a gated short
convolution (ops/short_conv_ops.py) or grouped-query attention with an RMS
norm a head on q and k and rotary positions, as `layer_types` says a layer
at a time; the first `num_dense_layers` layers end in a dense SwiGLU MLP,
the others in a layer of routed experts with no shared expert.

    h_0 = E[ids]
    for l, kind in enumerate(layer_types):
        h = h + Op_l(RMS(h), positions)     Op_l: ShortConv | Attn by kind
        x = RMS(h)
        h = h + (MLP_l(x) if l < num_dense_layers else MoE_l(x))
    logits = RMS(h_L) W_head

    MoE(x): s = sigmoid(x W_r) over ALL experts, f32; the top k of s + b (b
            the selection bias) are chosen; g = s[chosen] / (sum + 1e-6)
            * scale;  MoE = sum_i g_i W_2i (silu(W_1i x) * W_3i x)

No biases. The family ties the head to the embedding; a layer of this graph
reads its own weights only (models/granite_hybrid.py says so), so the head
is a weight of its own, as in every model here. The graph takes three
inputs: `input_ids`, `positions` and `valid` `[batch, seq]` (1 = a token is
there), which tells the convolution where a row's state stops and the
expert layers which positions of a padded wave exist.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

from flexflow_tpu.core.model import FFModel
from flexflow_tpu.dtype import DataType
from flexflow_tpu.initializers import (GlorotUniformInitializer,
                                       UniformInitializer)
from flexflow_tpu.models.deepseek_v3 import _gated_mlp
from flexflow_tpu.models.granite_hybrid import _PerExpertGlorot

KINDS = ("conv", "full_attention")


@dataclasses.dataclass
class Lfm2MoeConfig:
    vocab: int = 65536
    seq: int = 1024
    d_model: int = 2048
    layer_types: Tuple[str, ...] = ("conv", "conv", "full_attention",
                                    "conv") * 10
    num_dense_layers: int = 2
    heads: int = 32
    kv_heads: int = 8
    dense_width: int = 11776
    num_experts: int = 64
    experts_per_tok: int = 4
    expert_width: int = 1536
    conv_kernel: int = 3
    rope_theta: float = 1000000.0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    gate_norm_eps: float = 1e-6
    # expert ids [lo, hi) that this holder computes; the router and the
    # top-k stay num_experts wide
    experts_held: Tuple[int, int] = (0, 64)
    # the selection bias is drawn uniform in +-this (a trained model's comes
    # from its checkpoint): of the size of the gaps between neighbouring
    # selection scores, so that a router that leaves it out chooses otherwise
    score_bias_range: float = 0.02
    eps: float = 1e-5
    dtype: str = "float32"      # the graph's (and so the weights') type

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        unknown = set(self.layer_types) - set(KINDS)
        if unknown:
            raise ValueError(f"lfm2_moe layer_types {sorted(unknown)}")

    @staticmethod
    def tiny(seq: int = 48):
        return Lfm2MoeConfig(
            vocab=512, seq=seq, d_model=64,
            layer_types=("conv", "conv", "full_attention", "conv") * 2,
            num_dense_layers=1, heads=4, kv_heads=2, dense_width=96,
            num_experts=8, experts_per_tok=2, expert_width=48,
            experts_held=(0, 8))

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.heads

    def expert_params(self) -> int:
        """One routed expert: W_1, W_3 [d, w] and W_2 [w, d]."""
        return 3 * self.d_model * self.expert_width

    def mixer_matmul_params(self, kind: str) -> int:
        """The matrices of a layer's operator: the convolution's two
        projections, or attention's four."""
        d = self.d_model
        if kind == "conv":
            return 4 * d * d
        return 2 * d * d + 2 * d * self.kv_heads * self.head_dim

    def layer_small_params(self, layer: int) -> int:
        """A layer's vectors: its two norms, the convolution's taps or the
        two head norms, an expert layer's selection bias."""
        n = 2 * self.d_model + (
            self.conv_kernel * self.d_model
            if self.layer_types[layer] == "conv" else 2 * self.head_dim)
        return n + (self.num_experts if layer >= self.num_dense_layers else 0)

    def feed_forward_matmul_params(self, layer: int) -> int:
        """What every token meets after the operator, outside the routed
        experts: the dense MLP's three matrices, or the router."""
        if layer < self.num_dense_layers:
            return 3 * self.d_model * self.dense_width
        return self.d_model * self.num_experts

    def matmul_params_per_token(self) -> float:
        """Parameters a token is multiplied with here: every layer's own, the
        EXPECTED share of its k experts that is held here, and the head."""
        lo, hi = self.experts_held
        routed = (self.experts_per_tok * (hi - lo) / self.num_experts
                  * self.expert_params())
        return (sum(self.mixer_matmul_params(kind)
                    + self.feed_forward_matmul_params(i)
                    + (routed if i >= self.num_dense_layers else 0)
                    for i, kind in enumerate(self.layer_types))
                + self.d_model * self.vocab)

    def flops_per_token(self) -> float:
        """Training (forward + backward) FLOPs a token needs: 6 a multiplied
        parameter and the attention layers' scores and values over the full
        square (the MFU convention, as GPT2Config counts)."""
        attn = self.layer_types.count("full_attention") * 2 * 2 * self.seq \
            * self.d_model
        return 6.0 * self.matmul_params_per_token() + 3.0 * attn

    def param_count(self) -> int:
        lo, hi = self.experts_held
        return (2 * self.vocab * self.d_model + self.d_model
                + sum(self.mixer_matmul_params(kind)
                      + self.feed_forward_matmul_params(i)
                      + self.layer_small_params(i)
                      + ((hi - lo) * self.expert_params()
                         if i >= self.num_dense_layers else 0)
                      for i, kind in enumerate(self.layer_types)))

    def state_bytes_per_slot(self, itemsize: int = 2) -> int:
        """A slot's convolution state, all layers."""
        return self.layer_types.count("conv") * (self.conv_kernel - 1) \
            * self.d_model * itemsize


def build_lfm2_moe(model: FFModel, cfg: Lfm2MoeConfig, batch: int = 8):
    """Adds the graph to `model`; returns ((ids, positions, valid), logits).
    Evaluates through `model.compile` (x = [ids, positions, valid]) and
    serves through `compile_serving`, whose programs find the layers that
    carry state by their kind: the attention layers page K/V, the
    convolutions keep their last inputs a slot, the expert layers keep
    nothing."""
    dtype = DataType.from_any(cfg.dtype)
    ids = model.create_tensor([batch, cfg.seq], DataType.INT32, name="input_ids")
    positions = model.create_tensor([batch, cfg.seq], DataType.INT32,
                                    name="positions")
    valid = model.create_tensor([batch, cfg.seq], DataType.INT32, name="valid")
    t = model.embedding(ids, cfg.vocab, cfg.d_model, dtype=dtype, name="embed")
    # the taps as torch's Conv1d default: uniform in +-1/sqrt(fan_in = k)
    bound = 1.0 / math.sqrt(cfg.conv_kernel)
    conv_init = {
        "conv_w": UniformInitializer(min_value=-bound, max_value=bound),
        "in_proj": GlorotUniformInitializer(),
        "out_proj": GlorotUniformInitializer()}
    # a trained norm's weights lie about 1; drawn apart so that a layer that
    # leaves the norm out, or takes q's for k's, computes otherwise
    norm_init = {"q_norm": UniformInitializer(min_value=0.5, max_value=1.5),
                 "k_norm": UniformInitializer(min_value=0.5, max_value=1.5)}
    experts_init = {
        "w_in": _PerExpertGlorot(), "w_out": _PerExpertGlorot(),
        "score_bias": UniformInitializer(min_value=-cfg.score_bias_range,
                                         max_value=cfg.score_bias_range)}
    for i, kind in enumerate(cfg.layer_types):
        h = model.rms_norm(t, eps=cfg.eps, name=f"l{i}_norm_op")
        if kind == "conv":
            y = model.short_conv(h, cfg.conv_kernel, valid=valid,
                                 initializers=conv_init, name=f"l{i}_conv")
        else:
            y = model.multihead_attention(
                h, h, h, cfg.d_model, cfg.heads, bias=False, causal=True,
                num_kv_heads=cfg.kv_heads, positions=positions,
                rope_theta=cfg.rope_theta, qk_norm=cfg.eps,
                initializers=norm_init, name=f"l{i}_attn")
        t = model.add(t, y, name=f"l{i}_res1")
        x = model.rms_norm(t, eps=cfg.eps, name=f"l{i}_norm_ffn")
        if i < cfg.num_dense_layers:
            ff = _gated_mlp(model, x, cfg.dense_width, cfg.d_model, f"l{i}_mlp")
        else:
            ff = model.moe_layer(
                x, cfg.num_experts, cfg.experts_per_tok, cfg.expert_width,
                cfg.experts_held, valid=valid, initializers=experts_init,
                scoring="sigmoid", norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                score_bias=True, gate_norm_eps=cfg.gate_norm_eps,
                name=f"l{i}_moe")
        t = model.add(t, ff, name=f"l{i}_res2")
    t = model.rms_norm(t, eps=cfg.eps, name="norm_f")
    logits = model.dense(t, cfg.vocab, use_bias=False, name="lm_head")
    return (ids, positions, valid), logits
