"""LFM2-MoE decoders (configs with Hugging Face `lfm2_moe` keys;
LFM2-24B-A2B is one): the program's build_lfm2_moe against
harness/reference_lfm2_moe.py.

In a configuration file `num_experts` is the number of experts HELD here (ids
0 .. num_experts - 1; the benchmark's configuration holds all 64), and
`num_hidden_layers`, `num_dense_layers` and `layer_types` the layers built;
the published values stand beside them as `published` (the router's width is
published.num_experts where the file states one)."""

from __future__ import annotations

from families.nemotron_h import neighbourhood_gaps
from harness import flops_lfm2_moe as flops
from harness import reference_lfm2_moe as reference

train_flops_per_token = flops.train_flops_per_token


def program_config(cfg: dict):
    """The configuration file as the program's Lfm2MoeConfig."""
    from flexflow_tpu.models import Lfm2MoeConfig

    assumed = cfg["assumed"]
    return Lfm2MoeConfig(
        vocab=cfg["vocab_size"], seq=assumed["serve_positions"],
        d_model=cfg["hidden_size"], layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        dense_width=cfg["intermediate_size"],
        num_experts=flops.routed_over(cfg),
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        conv_kernel=cfg["conv_L_cache"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        gate_norm_eps=float(assumed["gate_norm_eps"]),
        experts_held=(0, cfg["num_experts"]),
        score_bias_range=float(assumed["score_bias_range"]),
        eps=cfg["norm_eps"], dtype=assumed["weights_dtype"])


def build(model, cfg: dict, batch: int):
    """Adds the graph to `model`; returns the program's own configuration
    (`.vocab`, `.seq`, `.flops_per_token()`)."""
    from flexflow_tpu.models import build_lfm2_moe

    pcfg = program_config(cfg)
    build_lfm2_moe(model, pcfg, batch=batch)
    return pcfg


def serving_inputs():
    """(prompt inputs, step inputs) as ContinuousBatchingScheduler takes
    them: token ids, rotary positions, and which positions of a wave, and
    which slots of a step, exist (where the convolution's state stops, and
    which tokens the experts are given)."""
    from flexflow_tpu.serving import (positions_valid_prompt_inputs,
                                      positions_valid_step_inputs)

    return positions_valid_prompt_inputs, positions_valid_step_inputs


def hyper(cfg: dict) -> dict:
    """The reference's hyper-parameters from the configuration file."""
    return {"heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "rope_theta": float(cfg["rope_parameters"]["rope_theta"]),
            "top_k": cfg["num_experts_per_tok"],
            "held": (0, cfg["num_experts"]),
            "routed_scaling_factor": float(cfg["routed_scaling_factor"]),
            "gate_norm_eps": float(cfg["assumed"]["gate_norm_eps"]),
            "eps": float(cfg["norm_eps"])}


def reference_params(params, cfg: dict) -> dict:
    """The program's parameter tree, where it lies, in the layout of
    harness/reference_lfm2_moe.py. No copy: the same device arrays."""
    def layer(i, kind):
        out = {"norm_op": params[f"l{i}_norm_op"]["gamma"],
               "norm_ffn": params[f"l{i}_norm_ffn"]["gamma"]}
        out.update(params[f"l{i}_conv" if kind == "conv" else f"l{i}_attn"])
        if i < cfg["num_dense_layers"]:
            out.update(mlp_in=params[f"l{i}_mlp_in"]["kernel"],
                       mlp_out=params[f"l{i}_mlp_out"]["kernel"])
        else:
            out.update(params[f"l{i}_moe"])
        return out

    return {"embed": params["embed"]["kernel"],
            "norm_f": params["norm_f"]["gamma"],
            "head": params["lm_head"]["kernel"],
            "layers": [layer(i, k) for i, k in enumerate(flops.kinds(cfg))]}


def reference_loss(cfg: dict, params, ids, pos, labels):
    """The reference's next-token loss with the program's parameters."""
    return reference.next_token_loss(reference_params(params, cfg), ids, pos,
                                     labels, hyper(cfg))


# The served-token rule here is Nemotron's and Ling's NEIGHBOURHOOD rule
# (families/nemotron_h.py says what it is), at a window of 16 and a WIDE unit,
# because of what this configuration is under random weights (PERF.md,
# Findings PR 47, has every reading with its call). One chip holds all 64
# experts of eight expert layers and a token takes the top 4: a bf16 hidden
# state that flips a token's fourth choice moves that token by a quarter of
# the routed sum, the next layer's router sees the moved state, and the flips
# cascade: the bf16 engine lies 0.41-0.49 of the logits' scale from the f32
# reference ON AVERAGE from the first decode step on (an fp8 engine 0.79-0.88),
# and three served tokens in four are not the reference's argmax. Nothing is
# wrong with a layer: ONE expert layer at the published widths on the chip lies
# 0.003 of its output's scale from the reference on the same input at every
# rung, and with the routed sum scaled by 0 on both sides the whole program
# lies 0.023 of the logits' scale from the reference (fp8 weights 0.27): that
# witness (logits_check_lfm2_moe.py --routed-scale 0) is the tight comparison.
# What this rule can still do is part an engine a precision below: a token's
# gap is the MEAN of the gaps of GAP_WINDOW consecutive served tokens that hold
# it (the window that starts at it or the one that ends at it, whichever reads
# less; an answer is at least 32 tokens, so one of the two always lies inside
# it), in units of GAP_UNIT_ROW_SCALES x the row's own scale, of which
# cells/serve.py allows 8 bf16 ulps: 0.5625 of the row's scale. Over 21 windows
# of the sound engine on 21 seeds the worst token read 0.334-0.447 of the scale
# (4.8-6.4 of the 8 ulps), over 6 windows of an fp8 engine
# 0.726-0.805 (10.3-11.4): the limit is their geometric middle, a factor 1.27
# from either. At Nemotron's window of 8 the same windows read 0.436-0.640
# against 0.771-0.812: a factor 1.2 apart at the worst seed, too thin to stand
# on; 16 is the longest window every answer of this traffic holds.
GAP_UNIT_ROW_SCALES = 18.0
GAP_WINDOW = 16


def reference_token_gaps(cfg: dict, params, ids, pos):
    """(gap of each next token to the reference's maximum logit, the
    logits' scale) with the program's parameters, layer by layer, both in
    units of GAP_UNIT_ROW_SCALES x the row's own scale (the scale handed
    back is 1 everywhere, so cells/serve.py's floor does not bite), each
    token's gap taken over its neighbourhood."""
    gap, scale = reference.token_gaps(reference_params(params, cfg), ids, pos,
                                      hyper(cfg))
    return (neighbourhood_gaps(gap / scale, GAP_WINDOW) / GAP_UNIT_ROW_SCALES,
            scale / scale)
