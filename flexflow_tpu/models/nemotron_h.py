"""Nemotron-H decoders (Hugging Face `NemotronHForCausalLM`, model_type
`nemotron_h`; NVIDIA-Nemotron-3-Super-120B-A12B is one): a layer is ONE
mixer behind one pre-norm residual, and `hybrid_override_pattern` says which
a layer at a time: `M` a Mamba-2 mixer (B and C in `n_groups` groups, a
grouped gated norm), `*` grouped-query attention without positions, `E` a
routed expert layer beside a shared expert (the family's `-`, a plain MLP,
is not built: no configuration here has one).

    h_0 = E[ids]
    for l, kind in enumerate(pattern):
        h = h + f_l(RMS(h))       f_l: Mamba2 | Attn | MoE + Shared
    logits = RMS(h_L) W_head

    MoE(x):    s = sigmoid(x W_r) over ALL experts, f32; the top k of s + b
               (b the selection bias) are chosen; g = s[chosen] / sum * scale
               l = x W_down                          the experts' latent
               MoE = (sum_i g_i relu(l U_i)^2 V_i) W_up     held experts only
    Shared(x): relu(x U_s)^2 V_s                    on x itself, d wide

No biases but the conv's, no positions, no multipliers, an untied head. The
graph takes two inputs: `input_ids` and `valid` `[batch, seq]` (1 = a token
is there), which tells the state-space and expert layers which positions of
a padded wave exist. The multi-token-prediction module of the published
model is not built: the next-token logits do not depend on it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

from flexflow_tpu.core.model import FFModel
from flexflow_tpu.dtype import DataType
from flexflow_tpu.initializers import (GlorotUniformInitializer,
                                       OneInitializer, UniformInitializer)
from flexflow_tpu.models.granite_hybrid import (_ALog, _DtBias,
                                                _PerExpertGlorot)

KINDS = {"M": "mamba", "*": "attention", "E": "experts"}


@dataclasses.dataclass
class NemotronHConfig:
    vocab: int = 131072
    seq: int = 1024
    d_model: int = 4096
    pattern: str = "MEMEMEM*EME"
    heads: int = 32
    kv_heads: int = 2
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 8
    mamba_d_conv: int = 4
    mamba_chunk: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    num_experts: int = 512
    experts_per_tok: int = 22
    expert_width: int = 2688
    latent_size: int = 1024
    shared_width: int = 5376
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    # expert ids [lo, hi) that this holder computes; the router and the
    # top-k stay num_experts wide
    experts_held: Tuple[int, int] = (0, 512)
    # the selection bias is drawn uniform in +-this (a trained model's comes
    # from its checkpoint): of the size of the gaps between neighbouring
    # selection scores, so that a router that leaves it out chooses otherwise
    score_bias_range: float = 0.02
    eps: float = 1e-5
    dtype: str = "float32"      # the graph's (and so the weights') type

    @staticmethod
    def tiny(seq: int = 48):
        return NemotronHConfig(
            vocab=512, seq=seq, d_model=64, pattern="MEM*E", heads=8,
            kv_heads=2, mamba_heads=8, mamba_head_dim=16, mamba_d_state=16,
            mamba_n_groups=4, mamba_chunk=16, num_experts=16,
            experts_per_tok=5, expert_width=48, latent_size=32,
            shared_width=96, experts_held=(0, 4))

    @property
    def kinds(self) -> Tuple[str, ...]:
        return tuple(KINDS[c] for c in self.pattern)

    @property
    def layers(self) -> int:
        return len(self.pattern)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.heads

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def expert_params(self) -> int:
        """One routed expert: [latent, w] in and [w, latent] out."""
        return 2 * self.latent_size * self.expert_width

    def layer_matmul_params(self, kind: str) -> int:
        """What every token of a layer is multiplied with (an expert layer:
        outside its routed experts)."""
        d = self.d_model
        if kind == "attention":
            return 2 * d * d + 2 * d * self.kv_heads * self.head_dim
        if kind == "mamba":
            return (d * (self.d_inner + self.conv_dim + self.mamba_heads)
                    + self.d_inner * d)
        return (d * self.num_experts + 2 * d * self.latent_size
                + 2 * d * self.shared_width)

    def layer_small_params(self, kind: str) -> int:
        """A layer's vectors: its norm, a Mamba layer's conv, A_log, D,
        dt_bias and gated norm, an expert layer's selection bias."""
        n = self.d_model
        if kind == "mamba":
            n += ((self.mamba_d_conv + 1) * self.conv_dim
                  + 3 * self.mamba_heads + self.d_inner)
        if kind == "experts":
            n += self.num_experts
        return n

    def matmul_params_per_token(self) -> float:
        """Parameters a token is multiplied with here: every layer's own, the
        EXPECTED share of its k experts that is held here, and the head."""
        lo, hi = self.experts_held
        routed = (self.experts_per_tok * (hi - lo) / self.num_experts
                  * self.expert_params())
        return (sum(self.layer_matmul_params(k)
                    + (routed if k == "experts" else 0) for k in self.kinds)
                + self.d_model * self.vocab)

    def flops_per_token(self) -> float:
        """Training (forward + backward) FLOPs a token needs: 6 a multiplied
        parameter, the attention layers' scores and values over the full
        square (the MFU convention, as GPT2Config counts), and the
        state-space recurrence's own products (2 * 2 * P * N a head)."""
        attn = self.kinds.count("attention") * 2 * 2 * self.seq * self.d_model
        ssm = self.kinds.count("mamba") * 4 * self.d_inner * self.mamba_d_state
        return 6.0 * self.matmul_params_per_token() + 3.0 * (attn + ssm)

    def param_count(self) -> int:
        lo, hi = self.experts_held
        return (2 * self.vocab * self.d_model + self.d_model
                + sum(self.layer_matmul_params(k) + self.layer_small_params(k)
                      + ((hi - lo) * self.expert_params()
                         if k == "experts" else 0) for k in self.kinds))


def _relu2_mlp(model: FFModel, x, width: int, d_model: int, name: str):
    """relu(x U)^2 V: no gate matrix, no bias."""
    r = model.dense(x, width, activation="relu", use_bias=False,
                    name=f"{name}_in")
    return model.dense(model.multiply(r, r, name=f"{name}_sq"), d_model,
                       use_bias=False, name=f"{name}_out")


def build_nemotron_h(model: FFModel, cfg: NemotronHConfig, batch: int = 8):
    """Adds the graph to `model`; returns ((ids, valid), logits). Trains
    through `model.compile` / `fit` (x = [ids, valid]) and serves through
    `compile_serving`, whose programs find the layers that carry state by
    their kind: of this graph's layers some page K/V, some keep per-slot
    recurrent state and the expert layers keep none."""
    dtype = DataType.from_any(cfg.dtype)
    ids = model.create_tensor([batch, cfg.seq], DataType.INT32, name="input_ids")
    valid = model.create_tensor([batch, cfg.seq], DataType.INT32, name="valid")
    t = model.embedding(ids, cfg.vocab, cfg.d_model, dtype=dtype, name="embed")
    # conv_w as torch's Conv1d default: uniform in +-1/sqrt(fan_in = d_conv)
    bound = 1.0 / math.sqrt(cfg.mamba_d_conv)
    mamba_init = {
        "A_log": _ALog(), "D": OneInitializer(), "norm": OneInitializer(),
        "dt_bias": _DtBias(cfg.time_step_min, cfg.time_step_max,
                           cfg.time_step_floor),
        "conv_w": UniformInitializer(min_value=-bound, max_value=bound),
        "in_proj": GlorotUniformInitializer(),
        "out_proj": GlorotUniformInitializer()}
    experts_init = {
        "w_in": _PerExpertGlorot(), "w_out": _PerExpertGlorot(),
        "score_bias": UniformInitializer(min_value=-cfg.score_bias_range,
                                         max_value=cfg.score_bias_range)}
    for i, kind in enumerate(cfg.kinds):
        h = model.rms_norm(t, eps=cfg.eps, name=f"l{i}_norm")
        if kind == "mamba":
            y = model.mamba2(h, cfg.mamba_heads, cfg.mamba_head_dim,
                             cfg.mamba_d_state, d_conv=cfg.mamba_d_conv,
                             chunk=cfg.mamba_chunk, n_groups=cfg.mamba_n_groups,
                             eps=cfg.eps, valid=valid, initializers=mamba_init,
                             name=f"l{i}_mamba")
        elif kind == "attention":
            y = model.multihead_attention(
                h, h, h, cfg.d_model, cfg.heads, bias=False, causal=True,
                num_kv_heads=cfg.kv_heads, name=f"l{i}_attn")
        else:
            routed = model.moe_layer(
                h, cfg.num_experts, cfg.experts_per_tok, cfg.expert_width,
                cfg.experts_held, valid=valid, initializers=experts_init,
                scoring="sigmoid", norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                score_bias=True, expert_activation="relu2",
                latent_size=cfg.latent_size, name=f"l{i}_moe")
            y = model.add(routed, _relu2_mlp(model, h, cfg.shared_width,
                                             cfg.d_model, f"l{i}_shared"),
                          name=f"l{i}_ff")
        t = model.add(t, y, name=f"l{i}_res")
    t = model.rms_norm(t, eps=cfg.eps, name="norm_f")
    logits = model.dense(t, cfg.vocab, use_bias=False, name="lm_head")
    return (ids, valid), logits
