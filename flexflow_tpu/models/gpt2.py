"""GPT-2 (config #5 of BASELINE.md: GPT-2 medium, the Unity OSDI'22
pipeline+tensor-parallel workload; north-star model for the v5p target).

Pre-LN decoder blocks with learned positional embeddings, causal attention,
gelu FFN, weight-tied-free LM head (reference Transformer example has no
embedding layer; GPT-2 here follows the standard architecture so torch/HF
checkpoints map 1:1)."""

from __future__ import annotations

import dataclasses

from flexflow_tpu.core.model import FFModel
from flexflow_tpu.dtype import DataType


@dataclasses.dataclass
class GPT2Config:
    vocab: int = 50257
    seq: int = 1024
    d_model: int = 768
    heads: int = 12
    layers: int = 12
    d_ff: int = 0  # 0 -> 4*d_model
    dropout: float = 0.1

    @staticmethod
    def small():
        return GPT2Config()

    @staticmethod
    def medium():
        return GPT2Config(d_model=1024, heads=16, layers=24)

    @staticmethod
    def tiny(seq: int = 128):
        return GPT2Config(vocab=5120, seq=seq, d_model=256, heads=4, layers=2)

    @property
    def ff(self):
        return self.d_ff or 4 * self.d_model

    def flops_per_token(self) -> float:
        """Training (fwd + bwd) matmul FLOPs per token: 6 * N_matmul +
        attention scores. Embedding lookups (wte/wpe) are gathers — zero
        matmul FLOPs; the lm_head projection (d_model x vocab) IS a matmul
        and is counted."""
        n_matmul = (self.layers * (4 * self.d_model * self.d_model
                                   + 2 * self.d_model * self.ff)
                    + self.d_model * self.vocab)  # lm_head
        attn = self.layers * 2 * 2 * self.seq * self.d_model  # qk^T + av, fwd
        return 6.0 * n_matmul + 3.0 * attn

    def param_count(self) -> int:
        d = self.d_model
        return (self.vocab * d + self.seq * d
                + self.layers * (4 * d * d + 2 * d * self.ff
                                 + 9 * d + self.ff)  # biases + 2 LN per block
                + 2 * d + d * self.vocab)  # ln_f + lm_head


def gpt2_block(model: FFModel, t, cfg: GPT2Config, name: str,
               decode: bool = False):
    h = model.layer_norm(t, name=f"{name}_ln1")
    att = model.multihead_attention(h, h, h, cfg.d_model, cfg.heads,
                                    dropout=0.0 if decode else cfg.dropout,
                                    causal=True, decode=decode,
                                    name=f"{name}_attn")
    t = model.add(att, t, name=f"{name}_res1")
    h = model.layer_norm(t, name=f"{name}_ln2")
    up = model.dense(h, cfg.ff, activation="gelu", name=f"{name}_mlp_up")
    down = model.dense(up, cfg.d_model, name=f"{name}_mlp_down")
    return model.add(down, t, name=f"{name}_res2")


def build_gpt2(model: FFModel, cfg: GPT2Config, batch: int = 8,
               decode: bool = False):
    """decode=True builds the single-token serving twin: ids/pos are
    [batch, 1], every attention reads/writes the paged KV cache through
    lowering state (flexflow_tpu/serving), and dropout is inert. Layer
    names, weight specs, and topo order match the training build exactly,
    so params transfer 1:1 and build_init_fn produces identical init."""
    seq = 1 if decode else cfg.seq
    ids = model.create_tensor([batch, seq], DataType.INT32, name="input_ids")
    pos = model.create_tensor([batch, seq], DataType.INT32, name="position_ids")
    tok = model.embedding(ids, cfg.vocab, cfg.d_model, name="wte")
    pe = model.embedding(pos, cfg.seq, cfg.d_model, name="wpe")
    t = model.add(tok, pe, name="embed_add")
    if cfg.dropout:
        t = model.dropout(t, 0.0 if decode else cfg.dropout, name="embed_drop")
    for i in range(cfg.layers):
        t = gpt2_block(model, t, cfg, f"h{i}", decode=decode)
    t = model.layer_norm(t, name="ln_f")
    logits = model.dense(t, cfg.vocab, use_bias=False, name="lm_head")
    return (ids, pos), logits
