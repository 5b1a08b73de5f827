"""Hybrid state-space / attention decoders with routed experts (configs with
Hugging Face `granitemoehybrid` keys): the program's build_granite_hybrid
against harness/reference_granitemoehybrid.py.

In a configuration file `num_local_experts` is the number of experts HELD
here (ids 0 .. num_local_experts - 1) and `vocab_size` the slice of the
vocabulary held here; the published counts stand beside them as
`published` (the router's width is published.num_local_experts)."""

from __future__ import annotations

from harness import flops_granitemoehybrid as flops
from harness import reference_granitemoehybrid as reference

train_flops_per_token = flops.train_flops_per_token


def program_config(cfg: dict):
    """The configuration file as the program's GraniteHybridConfig."""
    from flexflow_tpu.models import GraniteHybridConfig

    return GraniteHybridConfig(
        vocab=cfg["vocab_size"], seq=cfg["assumed"]["serve_positions"],
        d_model=cfg["hidden_size"], layer_types=tuple(cfg["layer_types"]),
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        mamba_heads=cfg["mamba_n_heads"], mamba_head_dim=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"], mamba_n_groups=cfg["mamba_n_groups"],
        mamba_d_conv=cfg["mamba_d_conv"], mamba_chunk=cfg["mamba_chunk_size"],
        num_experts=flops.routed_over(cfg),
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["intermediate_size"],
        shared_width=cfg["shared_intermediate_size"],
        experts_held=(0, cfg["num_local_experts"]),
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        logits_scaling=cfg["logits_scaling"], eps=cfg["rms_norm_eps"],
        dtype=cfg["assumed"]["weights_dtype"])


def build(model, cfg: dict, batch: int):
    """Adds the graph to `model`; returns the program's own configuration
    (`.vocab`, `.seq`, `.flops_per_token()`)."""
    from flexflow_tpu.models import build_granite_hybrid

    pcfg = program_config(cfg)
    build_granite_hybrid(model, pcfg, batch=batch)
    return pcfg


def serving_inputs():
    """(prompt inputs, step inputs) as ContinuousBatchingScheduler takes
    them: the model has no positions, so beside the token ids it is told
    which positions of a wave, and which slots of a step, exist."""
    from flexflow_tpu.serving import valid_prompt_inputs, valid_step_inputs

    return valid_prompt_inputs, valid_step_inputs


def hyper(cfg: dict) -> dict:
    """The reference's hyper-parameters from the configuration file."""
    return {"heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "mamba_heads": cfg["mamba_n_heads"],
            "mamba_head_dim": cfg["mamba_d_head"],
            "d_state": cfg["mamba_d_state"],
            "top_k": cfg["num_experts_per_tok"],
            "held": (0, cfg["num_local_experts"]),
            "embedding_multiplier": float(cfg["embedding_multiplier"]),
            "residual_multiplier": float(cfg["residual_multiplier"]),
            "attention_multiplier": float(cfg["attention_multiplier"]),
            "logits_scaling": float(cfg["logits_scaling"]),
            "eps": float(cfg["rms_norm_eps"])}


def reference_params(params, cfg: dict) -> dict:
    """The program's parameter tree, where it lies, in the layout of
    harness/reference_granitemoehybrid.py. No copy: the same device arrays."""
    def layer(i, kind):
        moe = params[f"l{i}_moe"]
        out = {"norm_in": params[f"l{i}_norm_in"]["gamma"],
               "norm_post": params[f"l{i}_norm_post"]["gamma"],
               "router": moe["router"], "w_in": moe["w_in"],
               "w_out": moe["w_out"],
               "shared_in": params[f"l{i}_shared_in"]["kernel"],
               "shared_out": params[f"l{i}_shared_out"]["kernel"]}
        if kind == "mamba":
            m = params[f"l{i}_mamba"]
            out.update({k: m[k] for k in ("in_proj", "conv_w", "A_log", "D",
                                          "dt_bias", "norm", "out_proj")},
                       conv_b=m["bias_conv"])
        else:
            a = params[f"l{i}_attn"]
            out.update({k: a[k] for k in ("wq", "wk", "wv", "wo")})
        return out

    return {"embed": params["embed"]["kernel"],
            "norm_f": params["norm_f"]["gamma"],
            "head": params["lm_head"]["kernel"],
            "layers": [layer(i, k) for i, k in enumerate(cfg["layer_types"])]}


def reference_loss(cfg: dict, params, ids, pos, labels):
    """The reference's next-token loss with the program's parameters
    (`pos` is the cell kinds' common argument; this model has no positions)."""
    return reference.next_token_loss(reference_params(params, cfg), ids,
                                     labels, hyper(cfg))


# The served-token rule's unit, in row scales (a row's scale is its largest
# |logit|). cells/serve.py counts a gap in bf16 ulps of max(1, scale) against
# its fixed 8. A random head behind `logits_scaling` 16 gives logits 0.14
# wide, and under a scale floored at 1 eight ulps are a fifth of their range:
# an fp8 engine passes. So the gaps go out in units of the row's own scale,
# times 2: the rule then allows 16 bf16 ulps at the logits' scale. Not 8,
# because top-10 routing over a bf16 hidden state swaps one expert of ten for
# 4-19 % of the tokens a layer (PERF.md, PR 28): over 13 windows of 1-2 k
# served tokens on the chip the sound engine's worst gap read 4.8-9.0 ulps
# at the logits' scale (over 8 in 3 of them), the fp8 engine's 37.6-55.9
# (control.py; fp8 weights in the reference: 41.2-51.6). 16 lies between,
# a factor 1.8 and 2.3 from either.
GAP_UNIT_ROW_SCALES = 2.0


def reference_token_gaps(cfg: dict, params, ids, pos):
    """(gap of each next token to the reference's maximum logit, the
    logits' scale) with the program's parameters, layer by layer, both in
    units of GAP_UNIT_ROW_SCALES x the row's own scale: the scale handed
    back is 1 everywhere, so cells/serve.py's floor does not bite."""
    gap, scale = reference.token_gaps(reference_params(params, cfg), ids,
                                      hyper(cfg))
    return gap / (GAP_UNIT_ROW_SCALES * scale), scale / scale
