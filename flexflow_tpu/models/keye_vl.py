"""The language model of Keye-VL decoders (Kwai-Keye/Keye-VL-2.0-30B-A3B is
one): a Qwen3-MoE stack, every layer grouped-query attention with an RMS norm
a head on q and k and three-axis rotary positions, whose keys a learned
indexer chooses (DeepSeek sparse attention: ops/sparse_attention_ops.py),
then a layer of routed experts with no shared expert.

    h_0 = E[ids]
    for l in range(layers):
        x   = RMS(h)
        S   = Indexer_l(x, positions)            topk keys a token, s <= t
        h   = h + Attn_l(x, positions, over S)
        h   = h + MoE_l(RMS(h))
    logits = RMS(h_L) W_head

    MoE(x): softmax(x W_r) over ALL experts, the top k renormalised to sum
            1 (equal to a softmax over the k chosen scores, which is what
            `moe_layer` computes); sum_i g_i W_2i (silu(W_1i x) * W_3i x)

No biases but the indexer key's LayerNorm. The head is a weight of its own
(`tie_word_embeddings` false). The graph takes three inputs: `input_ids`
`[batch, seq]`, `positions` `[batch, seq, 3]` (time, height, width; text
gives one number three times) and `valid` `[batch, seq]` (1 = a token is
there), which tells the expert layers which positions of a padded block
exist and the indexer's counters which queries do. The vision tower is not
built: the catalog has no configuration of it.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

from flexflow_tpu.core.model import FFModel
from flexflow_tpu.dtype import DataType
from flexflow_tpu.initializers import (ConstantInitializer,
                                       GlorotUniformInitializer,
                                       UniformInitializer)
from flexflow_tpu.models.granite_hybrid import _PerExpertGlorot


@dataclasses.dataclass
class KeyeVLConfig:
    vocab: int = 151936
    seq: int = 16896
    d_model: int = 2048
    layers: int = 48
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    experts_per_tok: int = 8
    expert_width: int = 768
    rope_theta: float = 10000000.0
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    # the indexer (`sa_config`): heads, head_dim, ONE key head, topk
    indexer_heads: int = 16
    indexer_head_dim: int = 64
    indexer_topk: int = 2048
    # the indexer's rotary sections over its head_dim // 2 pairs
    indexer_mrope_section: Tuple[int, ...] = (8, 12, 12)
    # expert ids [lo, hi) that this holder computes; the router and the
    # top-k stay num_experts wide
    experts_held: Tuple[int, int] = (0, 128)
    eps: float = 1e-6
    dtype: str = "float32"      # the graph's (and so the weights') type

    def __post_init__(self):
        self.mrope_section = tuple(self.mrope_section)
        self.indexer_mrope_section = tuple(self.indexer_mrope_section)
        for what, sections, dim in (
                ("mrope_section", self.mrope_section, self.head_dim),
                ("indexer_mrope_section", self.indexer_mrope_section,
                 self.indexer_head_dim)):
            if sum(sections) != dim // 2:
                raise ValueError(f"keye_vl {what} {sections} over "
                                 f"{dim // 2} pairs")

    @staticmethod
    def tiny(seq: int = 48):
        return KeyeVLConfig(
            vocab=512, seq=seq, d_model=64, layers=2, heads=4, kv_heads=2,
            head_dim=16, num_experts=8, experts_per_tok=2, expert_width=48,
            mrope_section=(2, 3, 3), indexer_heads=2, indexer_head_dim=8,
            indexer_topk=8, indexer_mrope_section=(1, 1, 2),
            experts_held=(0, 8))

    def expert_params(self) -> int:
        """One routed expert: W_1, W_3 [d, w] and W_2 [w, d]."""
        return 3 * self.d_model * self.expert_width

    def attention_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return 2 * d * self.heads * hd + 2 * d * self.kv_heads * hd

    def indexer_matmul_params(self) -> int:
        return self.d_model * (self.indexer_heads * self.indexer_head_dim
                               + self.indexer_head_dim + self.indexer_heads)

    def matmul_params_per_token(self) -> float:
        """Parameters a token is multiplied with here: every layer's own, the
        EXPECTED share of its k experts that is held here, and the head."""
        lo, hi = self.experts_held
        routed = (self.experts_per_tok * (hi - lo) / self.num_experts
                  * self.expert_params())
        return self.layers * (self.attention_matmul_params()
                              + self.indexer_matmul_params()
                              + self.d_model * self.num_experts + routed) \
            + self.d_model * self.vocab

    def flops_per_token(self) -> float:
        """Training (forward + backward) FLOPs a token needs: 6 a multiplied
        parameter, and attention's and the indexer's scores over the full
        square (the MFU convention, as GPT2Config counts; the dense form
        under the mask does that work)."""
        square = 2 * 2 * self.seq * self.heads * self.head_dim \
            + 2 * self.seq * self.indexer_heads * self.indexer_head_dim
        return 6.0 * self.matmul_params_per_token() \
            + 3.0 * self.layers * square

    def param_count(self) -> int:
        lo, hi = self.experts_held
        small = 2 * self.d_model + 2 * self.head_dim \
            + 2 * self.indexer_head_dim
        return 2 * self.vocab * self.d_model + self.d_model + self.layers * (
            self.attention_matmul_params() + self.indexer_matmul_params()
            + self.d_model * self.num_experts + small
            + (hi - lo) * self.expert_params())

    def cache_bytes_per_token(self, itemsize: int = 2) -> int:
        """What a token leaves in the pages, all layers: K, V and the
        indexer's key (as stored: in whole lanes)."""
        index = -(-self.indexer_head_dim // 128) * 128
        return self.layers * itemsize * (
            2 * self.kv_heads * self.head_dim + index)


def build_keye_vl(model: FFModel, cfg: KeyeVLConfig, batch: int = 8):
    """Adds the graph to `model`; returns ((ids, positions, valid), logits).
    Evaluates through `model.compile` (x = [ids, positions, valid]) and
    serves through `compile_serving`, whose programs find the layers that
    carry state by their kind: the attention layers page K/V, the indexers
    their key beside it, the expert layers keep nothing."""
    dtype = DataType.from_any(cfg.dtype)
    ids = model.create_tensor([batch, cfg.seq], DataType.INT32,
                              name="input_ids")
    positions = model.create_tensor(
        [batch, cfg.seq, len(cfg.mrope_section)], DataType.INT32,
        name="positions")
    valid = model.create_tensor([batch, cfg.seq], DataType.INT32, name="valid")
    t = model.embedding(ids, cfg.vocab, cfg.d_model, dtype=dtype, name="embed")
    # a trained norm's weights lie about 1; drawn apart so that a layer that
    # leaves the norm out, or takes q's for k's, computes otherwise
    spread = UniformInitializer(min_value=0.5, max_value=1.5)
    norm_init = {"q_norm": spread, "k_norm": spread}
    index_init = {"wq": GlorotUniformInitializer(),
                  "wk": GlorotUniformInitializer(),
                  "ww": GlorotUniformInitializer(),
                  "k_norm": spread,
                  "k_norm_bias": UniformInitializer(min_value=-0.1,
                                                    max_value=0.1)}
    experts_init = {"w_in": _PerExpertGlorot(), "w_out": _PerExpertGlorot()}
    embed = cfg.heads * cfg.head_dim
    for i in range(cfg.layers):
        x = model.rms_norm(t, eps=cfg.eps, name=f"l{i}_norm_op")
        kept = model.sparse_indexer(
            x, positions, cfg.indexer_heads, cfg.indexer_head_dim,
            cfg.indexer_topk, rope_theta=cfg.rope_theta,
            mrope_section=cfg.indexer_mrope_section, eps=cfg.eps,
            valid=valid, initializers=index_init, name=f"l{i}_index")
        y = model.multihead_attention(
            x, x, x, embed, cfg.heads, bias=False, causal=True,
            num_kv_heads=cfg.kv_heads, positions=positions,
            rope_theta=cfg.rope_theta, qk_norm=cfg.eps,
            mrope_section=cfg.mrope_section, selected=kept,
            out_dim=cfg.d_model,
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
