"""Brumby decoders (Hugging Face model_type `brumby`; Brumby-14B-Base is
one): the Qwen3 block with every attention layer replaced by power
retention (ops/power_retention_ops.py: linear attention with the kernel (q .
k)^2 over a gated recurrent state a K/V head), dense.

    h_0 = E[ids]
    for l in layers:
        h = h + Ret_l(RMS(h))
        u = RMS(h)
        h = h + W_down (silu(W_gate u) * W_up u)
    logits = RMS(h_L) W_head

No biases; an untied head; grouped heads (H query heads read J states), RMS
norms on q and k a head and rotate-half rotary positions over the whole
head, as the Qwen3 keys this config repeats say. No layer pages anything:
the only per-request state is the retention layers' `[J, P, D]` f32 state
and `[J, P]` normaliser a slot. The graph takes three inputs: `input_ids`,
`positions` and `valid` `[batch, seq]` (1 = a token is there: the state
stops at a row's last token).
"""

from __future__ import annotations

import dataclasses
import math

from flexflow_tpu.core.model import FFModel
from flexflow_tpu.dtype import DataType
from flexflow_tpu.initializers import NormInitializer
from flexflow_tpu.models.deepseek_v3 import _gated_mlp
from flexflow_tpu.ops.power_retention_ops import (recurrence_flops_per_token,
                                                  state_rows)


@dataclasses.dataclass
class BrumbyConfig:
    vocab: int = 151936
    seq: int = 1024
    d_model: int = 5120
    layers: int = 40
    heads: int = 40
    kv_heads: int = 8
    head_dim: int = 128
    dense_width: int = 17408
    rope_theta: float = 1000000.0
    # the gate log g = logsigmoid(u W_g) has no bias, and u (an RMS norm's
    # output) has no mean under random weights, so u W_g has none either:
    # W_g is drawn normal with this standard deviation of u W_g, and g lies
    # about 1/2 (0.5: g in (0.27, 0.73) for 95 % of the tokens)
    gate_logit_std: float = 0.5
    eps: float = 1e-6
    dtype: str = "float32"      # the graph's (and so the weights') type

    @staticmethod
    def tiny(seq: int = 48):
        return BrumbyConfig(vocab=512, seq=seq, d_model=64, layers=3, heads=4,
                            kv_heads=2, head_dim=16, dense_width=96)

    def mixer_params(self) -> int:
        """A retention layer's matrices: W_q, W_k, W_v, W_g, W_o."""
        d, inner = self.d_model, self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return d * (inner + 2 * kv + self.kv_heads) + inner * d

    def layer_params(self) -> int:
        """One block: the mixer, the q and k norms, the MLP's three
        matrices, the two RMS norms."""
        return (self.mixer_params() + 2 * self.head_dim
                + 3 * self.d_model * self.dense_width + 2 * self.d_model)

    def matmul_params_per_token(self) -> float:
        return (self.layers * (self.mixer_params()
                               + 3 * self.d_model * self.dense_width)
                + self.d_model * self.vocab)

    def flops_per_token(self) -> float:
        """Training (forward + backward) FLOPs a token needs: 6 a multiplied
        parameter and the recurrence's own products (ops/
        power_retention_ops.recurrence_flops_per_token), times 3."""
        return 6.0 * self.matmul_params_per_token() + 3.0 * self.layers \
            * recurrence_flops_per_token(self.heads, self.kv_heads,
                                         self.head_dim)

    def param_count(self) -> int:
        return (2 * self.vocab * self.d_model + self.d_model
                + self.layers * self.layer_params())

    def state_bytes_per_slot(self) -> int:
        """A slot's recurrent state, all layers: S and z, float32."""
        return self.layers * self.kv_heads * state_rows(self.head_dim) \
            * (self.head_dim + 1) * 4


def build_brumby(model: FFModel, cfg: BrumbyConfig, batch: int = 8):
    """Adds the graph to `model`; returns ((ids, positions, valid), logits).
    Evaluates through `model.compile` (x = [ids, positions, valid]) and
    serves through `compile_serving`, whose programs find the layers that
    carry state by their kind: every one of them keeps a fixed-size state a
    slot, none pages anything."""
    dtype = DataType.from_any(cfg.dtype)
    ids = model.create_tensor([batch, cfg.seq], DataType.INT32, name="input_ids")
    positions = model.create_tensor([batch, cfg.seq], DataType.INT32,
                                    name="positions")
    valid = model.create_tensor([batch, cfg.seq], DataType.INT32, name="valid")
    t = model.embedding(ids, cfg.vocab, cfg.d_model, dtype=dtype, name="embed")
    gate_init = {"wg": NormInitializer(
        stddev=cfg.gate_logit_std / math.sqrt(cfg.d_model))}
    for i in range(cfg.layers):
        h = model.rms_norm(t, eps=cfg.eps, name=f"l{i}_norm_in")
        y = model.power_retention(
            h, positions, cfg.heads, cfg.kv_heads, cfg.head_dim,
            rope_theta=cfg.rope_theta, eps=cfg.eps, valid=valid,
            initializers=gate_init, name=f"l{i}_ret")
        t = model.add(t, y, name=f"l{i}_res1")
        x = model.rms_norm(t, eps=cfg.eps, name=f"l{i}_norm_post")
        ff = _gated_mlp(model, x, cfg.dense_width, cfg.d_model, f"l{i}_mlp")
        t = model.add(t, ff, name=f"l{i}_res2")
    t = model.rms_norm(t, eps=cfg.eps, name="norm_f")
    logits = model.dense(t, cfg.vocab, use_bias=False, name="lm_head")
    return (ids, positions, valid), logits
