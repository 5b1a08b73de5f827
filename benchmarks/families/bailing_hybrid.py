"""Bailing hybrid decoders (configs with Hugging Face `bailing_hybrid` keys;
Ling-3.0-flash is one): the program's build_bailing_hybrid against
harness/reference_bailing_hybrid.py.

In a configuration file `num_experts` is the number of experts HELD here
(ids 0 .. num_experts - 1) and `vocab_size` the slice of the vocabulary held
here; the published counts stand beside them as `published` (the router's
width is published.num_experts)."""

from __future__ import annotations

from families.nemotron_h import neighbourhood_gaps
from harness import flops_bailing_hybrid as flops
from harness import reference_bailing_hybrid as reference

train_flops_per_token = flops.train_flops_per_token


def program_config(cfg: dict):
    """The configuration file as the program's BailingHybridConfig."""
    from flexflow_tpu.models import BailingHybridConfig

    assumed = cfg["assumed"]
    return BailingHybridConfig(
        vocab=cfg["vocab_size"], seq=assumed["serve_positions"],
        d_model=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        layer_group_size=cfg["layer_group_size"],
        first_k_dense=cfg["first_k_dense_replace"],
        heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], d_conv=cfg["short_conv_kernel_size"],
        kda_lower_bound=cfg["kda_lower_bound"],
        kda_dt_bias_range=tuple(assumed["kda_dt_bias_range"]),
        dense_width=cfg["intermediate_size"],
        num_experts=flops.routed_over(cfg),
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        shared_width=cfg["moe_shared_expert_intermediate_size"]
        * cfg["num_shared_experts"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        experts_held=(0, cfg["num_experts"]), rope_theta=cfg["rope_theta"],
        score_bias_range=assumed["expert_bias_range"],
        eps=cfg["rms_norm_eps"], dtype=assumed["weights_dtype"])


def build(model, cfg: dict, batch: int):
    """Adds the graph to `model`; returns the program's own configuration
    (`.vocab`, `.seq`, `.flops_per_token()`)."""
    from flexflow_tpu.models import build_bailing_hybrid

    pcfg = program_config(cfg)
    build_bailing_hybrid(model, pcfg, batch=batch)
    return pcfg


def serving_inputs():
    """(prompt inputs, step inputs) as ContinuousBatchingScheduler takes
    them: token ids, rotary positions (the latent layers'), and which
    positions of a wave, and which slots of a step, exist (the KDA and
    expert layers')."""
    from flexflow_tpu.serving import (positions_valid_prompt_inputs,
                                      positions_valid_step_inputs)

    return positions_valid_prompt_inputs, positions_valid_step_inputs


def hyper(cfg: dict) -> dict:
    """The reference's hyper-parameters from the configuration file."""
    return {"heads": cfg["num_attention_heads"], "head_dim": cfg["head_dim"],
            "d_conv": cfg["short_conv_kernel_size"],
            "lower_bound": float(cfg["kda_lower_bound"]),
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "rank": cfg["kv_lora_rank"],
            "top_k": cfg["num_experts_per_tok"], "n_group": cfg["n_group"],
            "topk_group": cfg["topk_group"],
            "norm_topk_prob": bool(cfg["norm_topk_prob"]),
            "routed_scaling_factor": float(cfg["routed_scaling_factor"]),
            "held": (0, cfg["num_experts"]),
            "eps": float(cfg["rms_norm_eps"]),
            "rope_theta": float(cfg["rope_theta"])}


def reference_params(params, cfg: dict) -> dict:
    """The program's parameter tree, where it lies, in the layout of
    harness/reference_bailing_hybrid.py. No copy: the same device arrays."""
    def layer(i, kind):
        out = {"norm_in": params[f"l{i}_norm_in"]["gamma"],
               "norm_post": params[f"l{i}_norm_post"]["gamma"]}
        if kind == "kda":
            m = params[f"l{i}_kda"]
            out.update({k: m[k] for k in ("in_proj", "conv_w", "A_log",
                                          "dt_bias", "out_proj")},
                       gate_norm=m["norm"])
        else:
            out.update(params[f"l{i}_attn"])
        if i < cfg["first_k_dense_replace"]:
            out.update(mlp_in=params[f"l{i}_mlp_in"]["kernel"],
                       mlp_out=params[f"l{i}_mlp_out"]["kernel"])
        else:
            out.update(params[f"l{i}_moe"],
                       shared_in=params[f"l{i}_shared_in"]["kernel"],
                       shared_out=params[f"l{i}_shared_out"]["kernel"])
        return out

    return {"embed": params["embed"]["kernel"],
            "norm_f": params["norm_f"]["gamma"],
            "head": params["lm_head"]["kernel"],
            "layers": [layer(i, k) for i, k in enumerate(flops.kinds(cfg))]}


def reference_loss(cfg: dict, params, ids, pos, labels):
    """The reference's next-token loss with the program's parameters."""
    return reference.next_token_loss(reference_params(params, cfg), ids, pos,
                                     labels, hyper(cfg))


# The served-token rule here: each token is judged by its NEIGHBOURHOOD, as
# the `nemotron_h` family's cell judges it (families/nemotron_h.py has the
# whole argument and the window's arithmetic, `neighbourhood_gaps`; the
# numbers here are this family's own).
#
# cells/serve.py counts a served token's gap under the reference's largest
# logit in bf16 ulps of max(1, scale) against its fixed 8, and takes the worst
# token. Two things are the family's to say: the unit, and what a token's gap
# is. The gaps go out in units of GAP_UNIT_ROW_SCALES x the row's own scale
# (the scale as 1). And a token's gap is the MEAN of the gaps of GAP_WINDOW
# consecutive tokens that hold it: the window that starts at it or the one
# that ends at it, whichever reads less (an answer is at least 16 tokens, so
# one of the two always lies inside it; a window that reaches into the prompt
# or past the end reads far more, never less).
#
# Why not the `deepseek_v3` family's rule (judge a token only where the
# reference's routing of it is decided), which ISSUE 41 expected to carry
# over from GigaChat: the router chooses 8 of 512 sigmoid scores, and on the
# chip that rule judged 0.16 % of 6136 positions (my chip run, PR 41, call
# 1): with twice GigaChat's experts the scores near the top lie closer than
# the 0.004 that bf16 hidden states move them by, so some held expert is on
# the edge for nearly every token (128 of 512 are held, 16 of 256 there).
#
# The unit (PERF.md, PR 41, has the readings with their calls). Under
# random weights the sound bf16 engine serves HALF its tokens under the f32
# reference's best (mean gap 10.7-12.0 bf16 ulps at the row's scale over
# 940-1396 tokens a seed, worst single token 84-89), an fp8 engine nearly
# all of them (mean 61-65, worst 191-207), and its logits lie 0.21-0.23 of
# their scale from the reference's on average. That distance is the
# ROUTING's, not the new operator's: a quarter of the experts are held and a
# flip is a gate's worth, so a flipped token's next router sees another
# token (a held expert differs for 7 % of the tokens at the first expert
# layer, 65 % at the sixth). The witness (logits_check_bailing_hybrid.py
# --routed-scale 0: the same program and reference with the routed sum
# scaled by 0, call 11) reads 0.034-0.036 of the scale and a worst
# neighbourhood gap of 2.4 ulps where the whole model reads 0.21-0.23 and
# 29-31; a decay bound a tenth off reads 0.14-0.16 there and a constant
# beta 0.64-0.68, and that script holds the witness at 0.07. This cell's
# rule sees only served tokens and the weights, not the program's choices,
# so it has to leave room for the cascade: the worst token over its
# neighbourhood of 8 parts a sound engine from an fp8 one best of the
# statistics tried (1, 4, 8, 16): sound 27.8-28.8 on three seeds, fp8
# 95.0-99.1 (call 4); through the cell's own comparison (control.py, two
# more seeds, call 6) sound 22.5 and 26.5, fp8 89.4 and 90.4; the cell's
# own runs (calls 6, 9 and 11; PERF.md has the later ones) read 24.6-40.6.
# The limit is 8 x 7.5 = 60 ulps at the row's scale, the geometric middle of
# 40.6 and 89.4: 1.5 times of room to either side. It fails a wrong layer (garbage reads hundreds)
# and an fp8 engine; a decay a tenth off or a bf16 state it does not see:
# those are the witness's and tier-1's (tests/test_bailing_hybrid.py).
GAP_UNIT_ROW_SCALES = 7.5
GAP_WINDOW = 8


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
