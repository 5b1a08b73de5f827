"""Jamba decoders (Hugging Face model_type `jamba`; AI21-Jamba2-3B is one): a
hybrid whose mixers are Mamba-1 layers (the selective scan: a decay a channel
and state index, ops/mamba_ops.py) with a position-free attention layer every
`attn_layer_period` layers, and every layer ends in a dense gated MLP.

    h_0 = E[ids]
    for l in layers:
        h = h + Mixer_l(RMS(h))       Attn if l % period == offset else Mamba
        h = h + W_down(silu(W_gate x) * W_up x),  x = RMS(h)
    logits = RMS(h_L) W_head

    Attn: grouped-query (multi-query where one K/V head is published: 20
          query heads over 1), no bias, NO positional encoding, causal
          softmax of q k^T / sqrt(head_dim)

The family's MoE members put routed experts into the MLP slot of every
`expert_layer_period`-th layer (`num_experts` > 1): not built here; the
config raises by that name.

The head is a weight of its own: a layer of this graph reads its own weights
only, so it cannot be tied to the embedding as published.

The graph takes two inputs: `input_ids` and `valid` `[batch, seq]` (1 = a
token is there). The model has no position input, so `valid` is what tells
the state-space layers which positions of a padded block exist. Served, an
attention layer states `window` 0: its cache attention is then stated by
position (ops/attention_ops.py: the kernels over a slot's pages, as a
windowed model's full layers take them).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax.numpy as jnp

from flexflow_tpu.core.model import FFModel
from flexflow_tpu.dtype import DataType
from flexflow_tpu.initializers import (GlorotUniformInitializer, Initializer,
                                       OneInitializer, UniformInitializer)
from flexflow_tpu.models.deepseek_v3 import _gated_mlp
from flexflow_tpu.models.granite_hybrid import _DtBias


@dataclasses.dataclass
class JambaConfig:
    vocab: int = 65536
    seq: int = 16896
    d_model: int = 2560
    layers: int = 28
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    heads: int = 20
    kv_heads: int = 1
    dense_width: int = 8192
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_dt_rank: int = 160
    mamba_d_conv: int = 4
    # the family's MoE members: routed experts in the MLP slot (not built)
    num_experts: int = 1
    experts_per_tok: int = 1
    eps: float = 1e-6
    dtype: str = "float32"      # the graph's (and so the weights') type

    def __post_init__(self):
        if self.num_experts != 1 or self.experts_per_tok != 1:
            raise NotImplementedError(
                f"jamba: num_experts {self.num_experts} / num_experts_per_tok "
                f"{self.experts_per_tok}: the family's routed experts in the "
                "MLP slot (expert_layer_period / expert_layer_offset) are not "
                "built; only the dense members (num_experts 1) are")

    @staticmethod
    def tiny(seq: int = 64):
        """One attention layer among three Mamba layers, 4 query heads over
        one K/V head."""
        return JambaConfig(
            vocab=512, seq=seq, d_model=64, layers=4, attn_layer_period=4,
            attn_layer_offset=2, heads=4, kv_heads=1, dense_width=96,
            mamba_d_state=8, mamba_dt_rank=8)

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """The family's rule: attention where `l % period == offset`."""
        return tuple(
            "attention" if l % self.attn_layer_period == self.attn_layer_offset
            else "mamba" for l in range(self.layers))

    @property
    def head_dim(self) -> int:
        return self.d_model // self.heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    def mixer_matmul_params(self, kind: str) -> int:
        d = self.d_model
        if kind == "attention":
            return 2 * d * d + 2 * d * self.kv_heads * self.head_dim
        c, n, r = self.d_inner, self.mamba_d_state, self.mamba_dt_rank
        return d * 2 * c + c * (r + 2 * n) + r * c + c * d

    def matmul_params_per_token(self) -> float:
        return sum(self.mixer_matmul_params(k) for k in self.layer_types) \
            + self.layers * 3 * self.d_model * self.dense_width \
            + self.d_model * self.vocab

    def flops_per_token(self) -> float:
        """Training (forward + backward) FLOPs a token needs: 6 a multiplied
        parameter, attention's scores and values over the full square (the
        MFU convention, as GPT2Config counts), and the recurrence's own
        multiply-adds (4 a channel and state index)."""
        kinds = self.layer_types
        attn = kinds.count("attention") * 2 * 2 * self.seq * self.d_model
        scan = kinds.count("mamba") * 8 * self.d_inner * self.mamba_d_state
        return 6.0 * self.matmul_params_per_token() + 3.0 * (attn + scan)

    def param_count(self) -> int:
        c, n, r = self.d_inner, self.mamba_d_state, self.mamba_dt_rank
        mamba_small = self.mamba_d_conv * c + 3 * c + n * c + r + 2 * n
        mixers = sum(self.mixer_matmul_params(k)
                     + (mamba_small if k == "mamba" else 0)
                     for k in self.layer_types)
        per_layer = 2 * self.d_model + 3 * self.d_model * self.dense_width
        return 2 * self.vocab * self.d_model + self.d_model + mixers \
            + self.layers * per_layer

    def state_bytes_per_slot(self, itemsize: int = 2) -> int:
        """A slot's recurrent state, all Mamba layers: S float32, the conv
        tail in the graph's type."""
        c = self.d_inner
        return self.layer_types.count("mamba") * (
            self.mamba_d_state * c * 4 + (self.mamba_d_conv - 1) * c * itemsize)


class _S4DReal(Initializer):
    """A_log `[N, C]` = log(1 .. N) a channel: A = -(n + 1), the family's
    S4D-real start."""

    def __call__(self, key, spec):
        n, c = spec.shape
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None], (n, c))


def _mamba_initializers(cfg: JambaConfig):
    # conv_w as torch's Conv1d default: uniform in +-1/sqrt(fan_in = d_conv);
    # dt log-uniform in [1e-3, 1e-1] through its bias, A = -(1 .. N), D = 1:
    # the state neither blows up nor vanishes over a long context
    bound = 1.0 / math.sqrt(cfg.mamba_d_conv)
    spread = UniformInitializer(min_value=0.5, max_value=1.5)
    return {"A_log": _S4DReal(), "dt_bias": _DtBias(), "D": OneInitializer(),
            # a trained norm's weights lie about 1; drawn apart so that a
            # layer that leaves one out, or takes B's for C's, computes
            # otherwise
            "dt_norm": spread, "b_norm": spread, "c_norm": spread,
            "conv_w": UniformInitializer(min_value=-bound, max_value=bound),
            "in_proj": GlorotUniformInitializer(),
            "x_proj": GlorotUniformInitializer(),
            "dt_proj": GlorotUniformInitializer(),
            "out_proj": GlorotUniformInitializer()}


def build_jamba(model: FFModel, cfg: JambaConfig, batch: int = 8):
    """Adds the graph to `model`; returns ((ids, valid), logits). Evaluates
    and trains through `model.compile` (x = [ids, valid]) and serves through
    `compile_serving`, by padded waves or, with `serve_prefill_chunk`, by
    chunks that start each Mamba layer from its slot's state: the programs
    find the layers that carry state by their kind."""
    dtype = DataType.from_any(cfg.dtype)
    ids = model.create_tensor([batch, cfg.seq], DataType.INT32, name="input_ids")
    valid = model.create_tensor([batch, cfg.seq], DataType.INT32, name="valid")
    t = model.embedding(ids, cfg.vocab, cfg.d_model, dtype=dtype, name="embed")
    for i, kind in enumerate(cfg.layer_types):
        h = model.rms_norm(t, eps=cfg.eps, name=f"l{i}_norm_in")
        if kind == "mamba":
            y = model.mamba(h, cfg.d_inner, cfg.mamba_d_state,
                            cfg.mamba_dt_rank, d_conv=cfg.mamba_d_conv,
                            eps=cfg.eps, valid=valid,
                            initializers=_mamba_initializers(cfg),
                            name=f"l{i}_mamba")
        else:
            y = model.multihead_attention(
                h, h, h, cfg.d_model, cfg.heads, bias=False, causal=True,
                num_kv_heads=cfg.kv_heads, window=0, name=f"l{i}_attn")
        t = model.add(t, y, name=f"l{i}_res1")
        x = model.rms_norm(t, eps=cfg.eps, name=f"l{i}_norm_ff")
        ff = _gated_mlp(model, x, cfg.dense_width, cfg.d_model, f"l{i}_mlp")
        t = model.add(t, ff, name=f"l{i}_res2")
    t = model.rms_norm(t, eps=cfg.eps, name="norm_f")
    logits = model.dense(t, cfg.vocab, use_bias=False, name="lm_head")
    return (ids, valid), logits
