"""DeepSeek-V3 decoders (Hugging Face `DeepseekV3ForCausalLM`, model_type
`deepseek_v3`; GigaChat3.1-702B-A36B is one with other numbers): every
mixer is multi-head latent attention with YaRN rotary positions, the first
`first_k_dense` layers end in a gated-SiLU MLP, the others in a routed
expert layer (sigmoid scores, a selection bias, group-limited top-k,
normalised and scaled gates) beside a shared expert.

    h_0 = E[ids]
    for l in layers:
        h = h + LatentAttn_l(RMS(h), positions)
        x = RMS(h)
        h = h + (MLP_l(x)  if l < first_k_dense  else  MoE_l(x) + Shared_l(x))
    logits = RMS(h_L) W_head

No biases; an untied head. The graph takes three inputs: `input_ids`,
`positions` and `valid` `[batch, seq]` (1 = a token is there; the expert
layers route only those). The multi-token-prediction module of the
published model is not built: the main model's logits do not depend on it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

from flexflow_tpu.core.model import FFModel
from flexflow_tpu.dtype import DataType
from flexflow_tpu.initializers import UniformInitializer
from flexflow_tpu.models.granite_hybrid import _PerExpertGlorot


@dataclasses.dataclass
class DeepseekV3Config:
    vocab: int = 128256
    seq: int = 1024
    d_model: int = 7168
    layers: int = 64
    first_k_dense: int = 3
    heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 192
    dense_width: int = 18432
    num_experts: int = 256
    experts_per_tok: int = 8
    expert_width: int = 2048
    shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    # expert ids [lo, hi) that this holder computes; the router, the groups
    # and the top-k stay num_experts wide
    experts_held: Tuple[int, int] = (0, 256)
    rope_theta: float = 100000.0
    # Hugging Face's YaRN keys, or None for plain rotary frequencies
    rope_scaling: Optional[Dict[str, Any]] = dataclasses.field(
        default_factory=lambda: {
            "factor": 64, "original_max_position_embeddings": 4096,
            "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1})
    # the selection bias is drawn uniform in +-this (a trained model's comes
    # from its checkpoint): of the size of the gaps between neighbouring
    # selection scores, so that a router that leaves it out chooses otherwise
    score_bias_range: float = 0.02
    eps: float = 1e-6
    dtype: str = "float32"      # the graph's (and so the weights') type

    @staticmethod
    def tiny(seq: int = 48):
        return DeepseekV3Config(
            vocab=512, seq=seq, d_model=64, layers=3, first_k_dense=1,
            heads=4, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=24, dense_width=96,
            num_experts=16, experts_per_tok=3, expert_width=32, n_group=4,
            topk_group=2, experts_held=(0, 8),
            rope_scaling={"factor": 4, "original_max_position_embeddings": 32,
                          "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                          "mscale_all_dim": 1})

    @property
    def latent_dim(self) -> int:
        """Values a token leaves in a layer's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def shared_width(self) -> int:
        return self.shared_experts * self.expert_width

    def attention_params(self) -> int:
        d, h = self.d_model, self.heads
        return (d * self.q_lora_rank
                + self.q_lora_rank * h * (self.qk_nope_head_dim
                                          + self.qk_rope_head_dim)
                + d * self.latent_dim
                + self.kv_lora_rank * h * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + h * self.v_head_dim * d)

    def expert_params(self) -> int:
        return 3 * self.d_model * self.expert_width

    def matmul_params_per_token(self) -> float:
        """Parameters a token is multiplied with here: attention's
        projections, the dense layers' MLPs, the router, the shared expert,
        the EXPECTED share of its k experts that is held here, and the head."""
        d = self.d_model
        lo, hi = self.experts_held
        expert_layer = (d * self.num_experts + 3 * d * self.shared_width
                        + self.experts_per_tok * (hi - lo) / self.num_experts
                        * self.expert_params())
        return (self.layers * self.attention_params()
                + self.first_k_dense * 3 * d * self.dense_width
                + (self.layers - self.first_k_dense) * expert_layer
                + d * self.vocab)

    def flops_per_token(self) -> float:
        """Training (forward + backward) FLOPs a token needs: 6 a multiplied
        parameter, and attention's scores and values over the full square
        (the MFU convention, as GPT2Config counts) at the heads' own widths."""
        attn = self.layers * 2 * self.seq * self.heads * (
            self.qk_nope_head_dim + self.qk_rope_head_dim + self.v_head_dim)
        return 6.0 * self.matmul_params_per_token() + 3.0 * attn

    def param_count(self) -> int:
        d = self.d_model
        lo, hi = self.experts_held
        norms = 2 * d + self.q_lora_rank + self.kv_lora_rank
        expert_layer = (d * self.num_experts + self.num_experts
                        + 3 * d * self.shared_width
                        + (hi - lo) * self.expert_params())
        return (2 * self.vocab * d + d
                + self.layers * (self.attention_params() + norms)
                + self.first_k_dense * 3 * d * self.dense_width
                + (self.layers - self.first_k_dense) * expert_layer)


def _gated_mlp(model: FFModel, x, width: int, d_model: int, name: str):
    """(silu(a) * b) W_out with [a | b] = x W_in."""
    ab = model.dense(x, 2 * width, use_bias=False, name=f"{name}_in")
    a, b = model.split(ab, 2, axis=-1, name=f"{name}_split")
    gated = model.multiply(model.silu(a, name=f"{name}_act"), b,
                           name=f"{name}_gate")
    return model.dense(gated, d_model, use_bias=False, name=f"{name}_out")


def build_deepseek_v3(model: FFModel, cfg: DeepseekV3Config, batch: int = 8):
    """Adds the graph to `model`; returns ((ids, positions, valid), logits).
    Trains through `model.compile` / `fit` (x = [ids, positions, valid]) and
    serves through `compile_serving`, whose programs find the layers that
    carry state by their kind."""
    dtype = DataType.from_any(cfg.dtype)
    ids = model.create_tensor([batch, cfg.seq], DataType.INT32, name="input_ids")
    positions = model.create_tensor([batch, cfg.seq], DataType.INT32,
                                    name="positions")
    valid = model.create_tensor([batch, cfg.seq], DataType.INT32, name="valid")
    t = model.embedding(ids, cfg.vocab, cfg.d_model, dtype=dtype, name="embed")
    experts_init = {
        "w_in": _PerExpertGlorot(), "w_out": _PerExpertGlorot(),
        "score_bias": UniformInitializer(min_value=-cfg.score_bias_range,
                                         max_value=cfg.score_bias_range)}
    for i in range(cfg.layers):
        h = model.rms_norm(t, eps=cfg.eps, name=f"l{i}_norm_in")
        y = model.latent_attention(
            h, positions, cfg.heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            eps=cfg.eps, rope_theta=cfg.rope_theta,
            rope_scaling=cfg.rope_scaling, valid=valid, name=f"l{i}_attn")
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
