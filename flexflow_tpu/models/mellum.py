"""Mellum decoders (Hugging Face model_type `mellum`; JetBrains'
Mellum2-12B-A2.5B-Instruct is one): a Qwen3-MoE stack whose attention layers
are of two kinds, as `layer_types` says a layer at a time: `sliding_attention`
(query t sees the keys t - window < s <= t, itself among them, plain rotary
frequencies) and `full_attention` (every s <= t, YaRN's frequencies and
attention factor); every layer ends in routed experts with no shared expert.

    h_0 = E[ids]
    for l, kind in enumerate(layer_types):
        h = h + Attn_l(RMS(h), positions)     window | whole context by kind
        h = h + MoE_l(RMS(h))
    logits = RMS(h_L) W_head

    Attn: grouped-query, an RMS norm a head on q and on k, then rotate-half
          rotary positions over the whole head at `rope_theta`; a full layer's
          tables are YaRN's (ops/rotary.py: pairs up to the low correction
          index keep their frequency, pairs from the high one on turn `factor`
          times slower, a linear ramp between; cos and sin times the attention
          factor, so its scores carry the square)
    MoE(x): softmax(x W_r) over ALL experts, the top k renormalised to sum
            1; sum_i g_i W_2i (silu(W_1i x) * W_3i x)

No biases. The head is a weight of its own (`tie_word_embeddings` false).
The graph takes three inputs: `input_ids`, `positions` and `valid` `[batch,
seq]` (1 = a token is there), which tells the expert layers which positions
of a padded block exist. Served, a windowed layer keeps a RING of its
window's pages a slot and a full layer the slot's whole context
(serving/kv_cache.py): both state `window` (a full layer 0), so that their
cache attention is stated by position (ops/attention_ops.py).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from flexflow_tpu.core.model import FFModel
from flexflow_tpu.dtype import DataType
from flexflow_tpu.initializers import UniformInitializer
from flexflow_tpu.models.granite_hybrid import _PerExpertGlorot

KINDS = ("sliding_attention", "full_attention")


@dataclasses.dataclass
class MellumConfig:
    vocab: int = 98304
    seq: int = 16896
    d_model: int = 2304
    layer_types: Tuple[str, ...] = ("sliding_attention", "sliding_attention",
                                    "sliding_attention", "full_attention") * 7
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    window: int = 1024
    num_experts: int = 64
    experts_per_tok: int = 8
    expert_width: int = 896
    rope_theta: float = 500000.0
    # YaRN on the full layers: {"factor", "original_max_position_embeddings",
    # "beta_fast", "beta_slow", "attention_factor"}; None: plain tables
    full_rope_scaling: Optional[dict] = dataclasses.field(
        default_factory=lambda: {
            "factor": 16.0, "original_max_position_embeddings": 8192,
            "beta_fast": 32.0, "beta_slow": 1.0,
            "attention_factor": 1.2772588722239782})
    # expert ids [lo, hi) that this holder computes; the router and the
    # top-k stay num_experts wide
    experts_held: Tuple[int, int] = (0, 64)
    eps: float = 1e-6
    dtype: str = "float32"      # the graph's (and so the weights') type

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        unknown = set(self.layer_types) - set(KINDS)
        if unknown:
            raise ValueError(f"mellum layer_types {sorted(unknown)}")

    @staticmethod
    def tiny(seq: int = 64):
        """One period, a window of 8, YaRN by 4 over 16 positions."""
        return MellumConfig(
            vocab=512, seq=seq, d_model=64,
            layer_types=("sliding_attention",) * 3 + ("full_attention",),
            heads=4, kv_heads=2, head_dim=16, window=8, num_experts=8,
            experts_per_tok=2, expert_width=48,
            full_rope_scaling={"factor": 4.0,
                               "original_max_position_embeddings": 16,
                               "beta_fast": 32.0, "beta_slow": 1.0},
            experts_held=(0, 8))

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    def expert_params(self) -> int:
        """One routed expert: W_1, W_3 [d, w] and W_2 [w, d]."""
        return 3 * self.d_model * self.expert_width

    def attention_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return 2 * d * self.heads * hd + 2 * d * self.kv_heads * hd

    def matmul_params_per_token(self) -> float:
        """Parameters a token is multiplied with here: every layer's own, the
        EXPECTED share of its k experts that is held here, and the head."""
        lo, hi = self.experts_held
        routed = (self.experts_per_tok * (hi - lo) / self.num_experts
                  * self.expert_params())
        return self.layers * (self.attention_matmul_params()
                              + self.d_model * self.num_experts + routed) \
            + self.d_model * self.vocab

    def flops_per_token(self) -> float:
        """Training (forward + backward) FLOPs a token needs: 6 a multiplied
        parameter, and attention's scores and values over the full square
        (the MFU convention, as GPT2Config counts; the sequence form computes
        the square under its mask)."""
        square = 2 * 2 * self.seq * self.heads * self.head_dim
        return 6.0 * self.matmul_params_per_token() \
            + 3.0 * self.layers * square

    def param_count(self) -> int:
        lo, hi = self.experts_held
        small = 2 * self.d_model + 2 * self.head_dim
        return 2 * self.vocab * self.d_model + self.d_model + self.layers * (
            self.attention_matmul_params() + self.d_model * self.num_experts
            + small + (hi - lo) * self.expert_params())

    def cache_bytes_per_token(self, itemsize: int = 2) -> int:
        """What a token leaves in ONE layer's pages: K and V."""
        return 2 * self.kv_heads * self.head_dim * itemsize


def build_mellum(model: FFModel, cfg: MellumConfig, batch: int = 8):
    """Adds the graph to `model`; returns ((ids, positions, valid), logits).
    Evaluates through `model.compile` (x = [ids, positions, valid]) and
    serves through `compile_serving` with `serve_prefill_chunk`, whose
    programs find the layers that carry state by their kind: every attention
    layer pages K/V, the windowed ones in a ring of their own extent, the
    expert layers keep nothing."""
    dtype = DataType.from_any(cfg.dtype)
    ids = model.create_tensor([batch, cfg.seq], DataType.INT32, name="input_ids")
    positions = model.create_tensor([batch, cfg.seq], DataType.INT32,
                                    name="positions")
    valid = model.create_tensor([batch, cfg.seq], DataType.INT32, name="valid")
    t = model.embedding(ids, cfg.vocab, cfg.d_model, dtype=dtype, name="embed")
    # a trained norm's weights lie about 1; drawn apart so that a layer that
    # leaves the norm out, or takes q's for k's, computes otherwise
    spread = UniformInitializer(min_value=0.5, max_value=1.5)
    norm_init = {"q_norm": spread, "k_norm": spread}
    experts_init = {"w_in": _PerExpertGlorot(), "w_out": _PerExpertGlorot()}
    embed = cfg.heads * cfg.head_dim
    for i, kind in enumerate(cfg.layer_types):
        x = model.rms_norm(t, eps=cfg.eps, name=f"l{i}_norm_op")
        full = kind == "full_attention"
        y = model.multihead_attention(
            x, x, x, embed, cfg.heads, bias=False, causal=True,
            num_kv_heads=cfg.kv_heads, positions=positions,
            rope_theta=cfg.rope_theta, qk_norm=cfg.eps, out_dim=cfg.d_model,
            window=0 if full else cfg.window,
            rope_scaling=cfg.full_rope_scaling if full else None,
            initializers=norm_init, name=f"l{i}_attn")
        t = model.add(t, y, name=f"l{i}_res1")
        x = model.rms_norm(t, eps=cfg.eps, name=f"l{i}_norm_ffn")
        ff = model.moe_layer(
            x, cfg.num_experts, cfg.experts_per_tok, cfg.expert_width,
            cfg.experts_held, valid=valid, initializers=experts_init,
            name=f"l{i}_moe")
        t = model.add(t, ff, name=f"l{i}_res2")
    t = model.rms_norm(t, eps=cfg.eps, name="norm_f")
    logits = model.dense(t, cfg.vocab, use_bias=False, name="lm_head")
    return (ids, positions, valid), logits
