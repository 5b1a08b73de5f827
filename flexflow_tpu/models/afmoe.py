"""AFMoE decoders (Hugging Face model_type `afmoe`; Arcee's Trinity-Mini,
26B-A3B, is one): attention layers of two kinds, as `layer_types` says a
layer at a time, over leading dense layers and then layers of sigmoid-routed
experts beside a shared one.

    h_0 = E[ids] * sqrt(d)                                   (`mup_enabled`)
    for l, kind in enumerate(layer_types):
        h = h + N2_l(Attn_l(N1_l(h), positions))      four RMS norms a layer
        h = h + N4_l(F_l(N3_l(h)))
    logits = RMS(h_L) W_head

    Attn: grouped-query, an RMS norm a head on q and on k. A
          `sliding_attention` layer: rotate-half rotary positions over the
          whole head at `rope_theta`, and query t sees t - window < s <= t.
          A `full_attention` layer: every s <= t and NO rotation (the family
          turns q and k on its local layers only). Both: the heads'
          concatenated output times sigmoid(N1(h) W_g) before W_o.
    F:    a gated-SiLU MLP `dense_width` wide in the first `dense_layers`
          layers; from there Shared(x) + MoE(x), one always-on gated-SiLU
          expert and the routed ones, all `expert_width` wide.
    MoE(x): scores sigmoid(x W_r) in f32 over ALL experts; the top k of
            score + b; gates the chosen experts' own scores over their sum
            (+ 1e-20), times `route_scale`; sum_i g_i W_2i (silu(W_1i x) *
            W_3i x). `b` is the layer's STATE (ops/moe_ops.py): no gradient;
            a training step moves it by `bias_rate` against the load.

No biases, no auxiliary loss. The head is a weight of its own. The graph
takes `input_ids` and `positions` `[batch, seq]`; with `with_valid` a third,
`valid` (1 = a token is there), which tells the expert layers which positions
of a padded block exist (serving). `experts_held = (lo, hi)`: the routed
experts this holder computes; the router, its top k, the bias, the shared
expert, attention and the norms are whole on every holder, so the holders'
parts of a layer add up to the whole layer once the shared expert is counted
once. Served, a sliding layer keeps a ring of its window's pages a slot and a
full one the slot's whole context, as models/mellum.py's do.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

from flexflow_tpu.core.model import FFModel
from flexflow_tpu.dtype import DataType
from flexflow_tpu.initializers import NormInitializer, UniformInitializer
from flexflow_tpu.models.deepseek_v3 import _gated_mlp
from flexflow_tpu.models.granite_hybrid import _PerExpertGlorot

KINDS = ("sliding_attention", "full_attention")
# how the embedding is DRAWN: normal, this deviation. Times sqrt(d) a token's
# own row then outweighs what the first layers add to every token alike (a
# post-normed sub-layer's output has RMS 1 whatever went in, and under random
# weights attention's is nearly one vector for all tokens), so the routers see
# tokens that differ, as a trained model's do. At the default Glorot draw
# (0.009 at 25 024 ids) more than half of a step's 16 384 tokens chose ONE
# expert of 128 (PERF.md, Findings PR 58)
EMBED_STD = 0.3


@dataclasses.dataclass
class AfmoeConfig:
    vocab: int = 200192
    seq: int = 8192
    d_model: int = 2048
    layer_types: Tuple[str, ...] = ("sliding_attention", "sliding_attention",
                                    "sliding_attention", "full_attention") * 8
    dense_layers: int = 2
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    window: int = 2048
    dense_width: int = 6144
    num_experts: int = 128
    experts_per_tok: int = 8
    expert_width: int = 1024
    shared_experts: int = 1
    route_scale: float = 2.826
    bias_rate: float = 0.001            # `load_balance_coeff`
    rope_theta: float = 10000.0
    # expert ids [lo, hi) that this holder computes; the router and the
    # top-k stay num_experts wide
    experts_held: Tuple[int, int] = (0, 128)
    eps: float = 1e-5
    dtype: str = "float32"      # the graph's (and so the weights') type

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        unknown = set(self.layer_types) - set(KINDS)
        if unknown:
            raise ValueError(f"afmoe layer_types {sorted(unknown)}")

    @staticmethod
    def tiny(seq: int = 64):
        """One dense layer and one period behind it, a window of 8."""
        return AfmoeConfig(
            vocab=512, seq=seq, d_model=64,
            layer_types=("sliding_attention",) * 4 + ("full_attention",),
            dense_layers=1, heads=4, kv_heads=2, head_dim=16, window=8,
            dense_width=96, num_experts=8, experts_per_tok=2, expert_width=48,
            experts_held=(0, 8))

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    def expert_params(self) -> int:
        """One expert, routed or shared: W_1, W_3 [d, w] and W_2 [w, d]."""
        return 3 * self.d_model * self.expert_width

    def attention_params(self) -> int:
        """W_q, W_g, W_o, W_k, W_v, the two head norms."""
        d, hd = self.d_model, self.head_dim
        return 3 * d * self.heads * hd + 2 * d * self.kv_heads * hd + 2 * hd

    def layer_params(self, i: int) -> int:
        """Layer i as held here: attention, four norms, and the dense MLP
        or router + bias + shared expert + the held routed experts."""
        lo, hi = self.experts_held
        own = self.attention_params() + 4 * self.d_model
        if i < self.dense_layers:
            return own + 3 * self.d_model * self.dense_width
        return own + self.d_model * self.num_experts + self.num_experts \
            + (self.shared_experts + hi - lo) * self.expert_params()

    def param_count(self) -> int:
        """Everything held here, the selection bias (state) among it."""
        return 2 * self.vocab * self.d_model + self.d_model \
            + sum(self.layer_params(i) for i in range(self.layers))

    def matmul_params_per_token(self) -> float:
        """Parameters a token is multiplied with here: every layer's own,
        the EXPECTED share of its k experts that is held here, the head."""
        lo, hi = self.experts_held
        d = self.d_model
        attn = self.attention_params() - 2 * self.head_dim
        routed = (self.experts_per_tok * (hi - lo) / self.num_experts
                  + self.shared_experts) * self.expert_params()
        dense = min(self.dense_layers, self.layers)
        return self.layers * attn + dense * 3 * d * self.dense_width \
            + (self.layers - dense) * (d * self.num_experts + routed) \
            + d * self.vocab

    def keys_seen(self, kind: str) -> int:
        """(query, key) pairs of one head of one sequence in a layer of
        `kind`: the triangle, or the band under it."""
        s = self.seq
        out = max(0, s - self.window) if kind == "sliding_attention" else 0
        return s * (s + 1) // 2 - out * (out + 1) // 2

    def flops_per_token(self) -> float:
        """Training (forward + backward) FLOPs a token needs: 6 a multiplied
        parameter, and attention's scores and values over the pairs a query
        may SEE (the band, the triangle: the flash kernels skip the rest)."""
        pairs = sum(self.keys_seen(kind) for kind in self.layer_types)
        return 6.0 * self.matmul_params_per_token() \
            + 3.0 * 2 * 2 * self.heads * self.head_dim * pairs / self.seq


def build_afmoe(model: FFModel, cfg: AfmoeConfig, batch: int = 2,
                with_valid: bool = False):
    """Adds the graph to `model`; returns ((ids, positions[, valid]),
    logits). Trains through `model.compile` (x = [ids, positions]) and, with
    `with_valid`, serves through `compile_serving`."""
    dtype = DataType.from_any(cfg.dtype)
    ids = model.create_tensor([batch, cfg.seq], DataType.INT32, name="input_ids")
    positions = model.create_tensor([batch, cfg.seq], DataType.INT32,
                                    name="positions")
    valid = model.create_tensor([batch, cfg.seq], DataType.INT32,
                                name="valid") if with_valid else None
    t = model.embedding(ids, cfg.vocab, cfg.d_model, dtype=dtype, name="embed",
                        kernel_initializer=NormInitializer(
                            stddev=EMBED_STD))
    t = model.scalar_multiply(t, math.sqrt(cfg.d_model), name="embed_scale")
    # a trained norm's weights lie about 1; drawn apart so that a layer that
    # leaves a norm out, or takes q's for k's, computes otherwise
    spread = UniformInitializer(min_value=0.5, max_value=1.5)
    attn_init = {"q_norm": spread, "k_norm": spread}
    # and a bias large enough to move the selection off the scores' own
    moe_init = {"w_in": _PerExpertGlorot(), "w_out": _PerExpertGlorot(),
                "score_bias": UniformInitializer(min_value=-0.01,
                                                 max_value=0.01)}
    embed = cfg.heads * cfg.head_dim

    def norm(x, name):
        return model.rms_norm(x, eps=cfg.eps, name=name,
                              gamma_initializer=spread)

    for i, kind in enumerate(cfg.layer_types):
        x = norm(t, f"l{i}_norm_in")
        sliding = kind == "sliding_attention"
        y = model.multihead_attention(
            x, x, x, embed, cfg.heads, bias=False, causal=True,
            num_kv_heads=cfg.kv_heads,
            positions=positions if sliding else None,
            rope_theta=cfg.rope_theta if sliding else None,
            qk_norm=cfg.eps, out_dim=cfg.d_model,
            window=cfg.window if sliding else 0, output_gate=True,
            initializers=attn_init, name=f"l{i}_attn")
        t = model.add(t, norm(y, f"l{i}_norm_post_attn"), name=f"l{i}_res1")
        x = norm(t, f"l{i}_norm_pre_mlp")
        if i < cfg.dense_layers:
            ff = _gated_mlp(model, x, cfg.dense_width, cfg.d_model,
                            f"l{i}_mlp")
        else:
            ff = model.moe_layer(
                x, cfg.num_experts, cfg.experts_per_tok, cfg.expert_width,
                cfg.experts_held, valid=valid, initializers=moe_init,
                scoring="sigmoid", norm_topk_prob=True,
                routed_scaling_factor=cfg.route_scale, score_bias="state",
                score_bias_rate=cfg.bias_rate, name=f"l{i}_moe")
            if cfg.shared_experts:
                ff = model.add(ff, _gated_mlp(
                    model, x, cfg.shared_experts * cfg.expert_width,
                    cfg.d_model, f"l{i}_shared"), name=f"l{i}_ffn")
        t = model.add(t, norm(ff, f"l{i}_norm_post_mlp"), name=f"l{i}_res2")
    t = norm(t, "norm_f")
    logits = model.dense(t, cfg.vocab, use_bias=False, name="lm_head")
    inputs = (ids, positions) + ((valid,) if with_valid else ())
    return inputs, logits
