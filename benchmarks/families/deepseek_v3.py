"""DeepSeek-V3 decoders (configs with Hugging Face `deepseek_v3` keys;
GigaChat3.1-702B-A36B is one): the program's build_deepseek_v3 against
harness/reference_deepseek_v3.py.

In a configuration file `n_routed_experts` is the number of experts HELD
here (ids 0 .. n_routed_experts - 1) and `vocab_size` the slice of the
vocabulary held here; the published counts stand beside them as
`published` (the router's width is published.n_routed_experts)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness import flops_deepseek_v3 as flops
from harness import reference_deepseek_v3 as reference
from harness.facts import emit

train_flops_per_token = flops.train_flops_per_token


def program_config(cfg: dict):
    """The configuration file as the program's DeepseekV3Config."""
    from flexflow_tpu.models import DeepseekV3Config

    return DeepseekV3Config(
        vocab=cfg["vocab_size"], seq=cfg["assumed"]["serve_positions"],
        d_model=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], dense_width=cfg["intermediate_size"],
        num_experts=flops.routed_over(cfg),
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        shared_experts=cfg["n_shared_experts"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        experts_held=(0, cfg["n_routed_experts"]),
        rope_theta=cfg["rope_theta"], rope_scaling=cfg["rope_scaling"],
        score_bias_range=cfg["assumed"]["e_score_correction_bias_range"],
        eps=cfg["rms_norm_eps"], dtype=cfg["assumed"]["weights_dtype"])


def build(model, cfg: dict, batch: int):
    """Adds the graph to `model`; returns the program's own configuration
    (`.vocab`, `.seq`, `.flops_per_token()`)."""
    from flexflow_tpu.models import build_deepseek_v3

    pcfg = program_config(cfg)
    build_deepseek_v3(model, pcfg, batch=batch)
    return pcfg


def serving_inputs():
    """(prompt inputs, step inputs) as ContinuousBatchingScheduler takes
    them: token ids, rotary positions, and which positions of a wave, and
    which slots of a step, exist."""
    from flexflow_tpu.serving import (positions_valid_prompt_inputs,
                                      positions_valid_step_inputs)

    return positions_valid_prompt_inputs, positions_valid_step_inputs


def hyper(cfg: dict) -> dict:
    """The reference's hyper-parameters from the configuration file."""
    rs = cfg["rope_scaling"]
    return {"heads": cfg["num_attention_heads"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "rank": cfg["kv_lora_rank"],
            "top_k": cfg["num_experts_per_tok"], "n_group": cfg["n_group"],
            "topk_group": cfg["topk_group"],
            "norm_topk_prob": bool(cfg["norm_topk_prob"]),
            "routed_scaling_factor": float(cfg["routed_scaling_factor"]),
            "held": (0, cfg["n_routed_experts"]),
            "eps": float(cfg["rms_norm_eps"]),
            "rope_theta": float(cfg["rope_theta"]),
            "rope_factor": float(rs["factor"]),
            "rope_original_len": int(rs["original_max_position_embeddings"]),
            "beta_fast": float(rs["beta_fast"]),
            "beta_slow": float(rs["beta_slow"]),
            "mscale": float(rs["mscale"]),
            "mscale_all_dim": float(rs["mscale_all_dim"])}


def reference_params(params, cfg: dict) -> dict:
    """The program's parameter tree, where it lies, in the layout of
    harness/reference_deepseek_v3.py. No copy: the same device arrays."""
    def layer(i):
        out = {"norm_in": params[f"l{i}_norm_in"]["gamma"],
               "norm_post": params[f"l{i}_norm_post"]["gamma"],
               **params[f"l{i}_attn"]}
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
            "layers": [layer(i) for i in range(cfg["num_hidden_layers"])]}


def reference_loss(cfg: dict, params, ids, pos, labels):
    """The reference's next-token loss with the program's parameters."""
    return reference.next_token_loss(reference_params(params, cfg), ids, pos,
                                     labels, hyper(cfg))


# The served-token rule here, and why it judges only some tokens.
#
# cells/serve.py counts a served token's gap under the reference's largest
# logit in bf16 ulps of max(1, scale) against its fixed 8. Two things are
# the family's to say: the unit, and which tokens the reference can judge.
#
# The unit: the gaps go out in units of GAP_UNIT_ROW_SCALES x the row's own
# scale (and the scale as 1), so the rule allows 8 x GAP_UNIT_ROW_SCALES = 16
# bf16 ulps at the logits' scale whatever that scale is, as granite's does.
#
# Which tokens: the router chooses 8 of 256 sigmoid scores (plus a bias,
# within 4 of 8 groups) that lie about 0.004 apart near the top, from a
# hidden state that the program keeps in bf16 (the configuration's compute
# type). Its selection scores therefore differ from the f32 reference's by
# ROUTING_NOISE-sized amounts (PERF.md, PR 32: measured on the chip), and
# where two candidates lie closer than that the program's choice is as good
# as the reference's and may differ. Here it matters only where a HELD
# expert enters or leaves the chosen set (16 of 256 are held), but then it
# moves that token's logits by up to a third of their scale (gates are
# scaled by 2.5 and nothing damps the residual stream): on the chip the
# plain rule read 84 ulps on a sound engine. No unit parts that from a lower
# precision. So a token is JUDGED only where the reference's routing of it
# is DECIDED: in every expert layer, the held experts among the chosen stay
# the same under ROUTING_DRAWS random perturbations of the selection scores
# of ROUTING_NOISE each. The others' gaps go out as 0 (not judged), and the
# share judged is a fact of the run (`routing_decided`). Everything else
# about the rule is cells/serve.py's.
GAP_UNIT_ROW_SCALES = 2.0
ROUTING_NOISE = 0.004
ROUTING_DRAWS = 16


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _layer_decided(c, hp_key, noise, draws):
    hp = dict(hp_key)
    lo, hi = hp["held"]

    def held_chosen(scores):            # [.., held] bool
        experts = reference.chosen(scores, hp)
        return jnp.any(experts[..., None] == jnp.arange(lo, hi), axis=-2)

    base = held_chosen(c)
    keys = jax.random.split(jax.random.PRNGKey(0), draws)

    def same(key):
        moved = c + noise * jax.random.normal(key, c.shape, c.dtype)
        return jnp.all(held_chosen(moved) == base, axis=-1)

    return jnp.all(jax.lax.map(same, keys), axis=0)


def routing_decided(selected, hp, noise=ROUTING_NOISE, draws=ROUTING_DRAWS):
    """[batch, seq] bool from the reference's selection scores of every
    expert layer (`[batch, seq, E]` each): the held experts chosen for the
    token are the same under `draws` perturbations of `noise` (normal, each
    score its own), in every layer."""
    decided = None
    for c in selected:
        layer = _layer_decided(c, reference._hp_key(hp), float(noise), int(draws))
        decided = layer if decided is None else decided & layer
    return decided


def reference_token_gaps(cfg: dict, params, ids, pos):
    """(gap of each next token to the reference's maximum logit, the
    logits' scale) with the program's parameters, layer by layer, both in
    units of GAP_UNIT_ROW_SCALES x the row's own scale (the scale handed
    back is 1 everywhere, so cells/serve.py's floor does not bite), and 0
    where the reference's routing of the token is not decided."""
    hp = hyper(cfg)
    gap, scale, selected = reference.token_gaps(
        reference_params(params, cfg), ids, pos, hp, scores=True)
    decided = routing_decided(selected, hp)[:, :-1]
    emit(fact="routing_decided", judged_share=float(jnp.mean(decided)),
         positions=int(decided.size), noise=ROUTING_NOISE, draws=ROUTING_DRAWS)
    return (jnp.where(decided, gap / (GAP_UNIT_ROW_SCALES * scale), 0.0),
            scale / scale)
