"""Mellum decoders (configs with the published `mellum` keys;
Mellum2-12B-A2.5B-Instruct is one): the program's build_mellum against
harness/reference_mellum.py.

In a configuration file `num_hidden_layers`, `layer_types` and
`mlp_layer_types` are of the layers built (the published depth stands beside
them as `published`); `num_experts` the experts held here (all of them in the
benchmark's configuration)."""

from __future__ import annotations

from harness import flops_mellum as flops
from harness import reference_mellum as reference

train_flops_per_token = flops.train_flops_per_token


def _theta(cfg: dict) -> float:
    ropes = cfg["rope_parameters"]
    theta = float(ropes["sliding_attention"]["rope_theta"])
    if float(ropes["full_attention"]["rope_theta"]) != theta:
        raise ValueError("mellum: one rope_theta for both kinds of layer")
    return theta


def _yarn(cfg: dict):
    """rope_parameters.full_attention as YaRN's numbers, or None."""
    full = cfg["rope_parameters"]["full_attention"]
    if full.get("rope_type") != "yarn":
        return None
    return {"factor": float(full["factor"]),
            "original_max_position_embeddings":
                int(full["original_max_position_embeddings"]),
            "beta_fast": float(full["beta_fast"]),
            "beta_slow": float(full["beta_slow"]),
            **({"attention_factor": float(full["attention_factor"])}
               if full.get("attention_factor") is not None else {})}


def program_config(cfg: dict):
    """The configuration file as the program's MellumConfig."""
    from flexflow_tpu.models import MellumConfig

    assumed = cfg["assumed"]
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"] \
            or set(cfg["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("mellum: layer_types of num_hidden_layers layers, "
                         "every one with sparse experts")
    return MellumConfig(
        vocab=cfg["vocab_size"], seq=assumed["serve_positions"],
        d_model=cfg["hidden_size"], layer_types=tuple(cfg["layer_types"]),
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], window=cfg["sliding_window"],
        num_experts=cfg["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"], rope_theta=_theta(cfg),
        full_rope_scaling=_yarn(cfg), experts_held=(0, cfg["num_experts"]),
        eps=cfg["rms_norm_eps"], dtype=assumed["weights_dtype"])


def build(model, cfg: dict, batch: int):
    """Adds the graph to `model`; returns the program's own configuration
    (`.vocab`, `.seq`, `.flops_per_token()`)."""
    from flexflow_tpu.models import build_mellum

    pcfg = program_config(cfg)
    build_mellum(model, pcfg, batch=batch)
    return pcfg


def serving_inputs():
    """(prompt inputs, step inputs) as ContinuousBatchingScheduler takes
    them: token ids, their positions (a prefill chunk's start from where its
    slot's context ends), and which positions of a block, and which slots of
    a step, exist."""
    from flexflow_tpu.serving import (positions_valid_prompt_inputs,
                                      positions_valid_step_inputs)

    return positions_valid_prompt_inputs, positions_valid_step_inputs


def hyper(cfg: dict) -> dict:
    """The reference's hyper-parameters from the configuration file."""
    yarn = _yarn(cfg)
    return {"heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "rope_theta": _theta(cfg),
            "layer_types": tuple(cfg["layer_types"]),
            "window": cfg["sliding_window"],
            "yarn": None if yarn is None else (
                yarn["factor"], yarn["original_max_position_embeddings"],
                yarn["beta_fast"], yarn["beta_slow"],
                yarn.get("attention_factor")),
            "top_k": cfg["num_experts_per_tok"],
            "held": (0, cfg["num_experts"]),
            "eps": float(cfg["rms_norm_eps"])}


def reference_params(params, cfg: dict) -> dict:
    """The program's parameter tree, where it lies, in the layout of
    harness/reference_mellum.py. No copy: the same device arrays."""
    def layer(i):
        out = {"norm_op": params[f"l{i}_norm_op"]["gamma"],
               "norm_ffn": params[f"l{i}_norm_ffn"]["gamma"]}
        out.update(params[f"l{i}_attn"])
        out.update(params[f"l{i}_moe"])
        return out

    return {"embed": params["embed"]["kernel"],
            "norm_f": params["norm_f"]["gamma"],
            "head": params["lm_head"]["kernel"],
            "layers": [layer(i) for i in range(cfg["num_hidden_layers"])]}


def reference_loss(cfg: dict, params, ids, pos, labels):
    """The reference's next-token loss with the program's parameters."""
    import jax.numpy as jnp

    return reference.next_token_loss(reference_params(params, cfg), ids,
                                     jnp.asarray(pos), labels, hyper(cfg))


# The served-token rule is the NEIGHBOURHOOD rule of the other whole-holder
# expert cells (families/nemotron_h.py says what it is; families/lfm2_moe.py
# why a whole holder of top-k experts under random weights needs it: a bf16
# hidden state that flips a token's last choice moves that token, the next
# router sees the moved state, and the flips cascade through every expert
# layer): a token's gap is the MEAN of the gaps of GAP_WINDOW consecutive
# served tokens that hold it (the window that starts at it or the one that
# ends at it, whichever reads less; an answer is at least 32 tokens, so one
# of the two always lies inside it, and 16 is the longest window of which
# that holds), in units of GAP_UNIT_ROW_SCALES x the row's own scale, of
# which cells/serve.py allows 8 bf16 ulps: 1.44 ulps of the row's scale. Set
# from this cell's own readings on the chip (my chip run, PR 56, calls 1 and
# 2: the cell's windows and parity sample, and benchmarks/control.py, two
# seeds, 4 rows a side; PERF.md, Findings PR 56, has every later reading):
# the sound engine's worst token read 0.28, 0.33 and 0.54 bf16 ulps of its
# row's scale, an fp8 engine's (every matrix rounded to e4m3: the nearest
# precision below the configuration's bf16) 3.80 and 6.97; the limit is the
# geometric middle of the sound engine's largest and the fp8 engine's
# smallest, a factor 2.6 from each. This model reads closer to its reference
# than Keye's (0.010 of the logits' scale against 0.06:
# logits_check_mellum.py): top-8 of 64 under a softmax renormalised over the
# chosen moves a token less when its eighth choice flips, and nine of twelve
# layers average over at most 1024 keys whatever the context.
GAP_UNIT_ROW_SCALES = 0.18
GAP_WINDOW = 16


def reference_token_gaps(cfg: dict, params, ids, pos):
    """(gap of each next token to the reference's maximum logit, the
    logits' scale) with the program's parameters, both in units of
    GAP_UNIT_ROW_SCALES x the row's own scale (the scale handed back is 1
    everywhere, so cells/serve.py's floor does not bite), each token's gap
    taken over its neighbourhood. A row at a time: at the timed lengths one
    row's float32 layer is what fits beside the engine."""
    import jax.numpy as jnp
    from families.nemotron_h import neighbourhood_gaps

    rp, hp = reference_params(params, cfg), hyper(cfg)
    ids, pos = jnp.asarray(ids), jnp.asarray(pos)
    rows = [reference.token_gaps(rp, ids[r:r + 1], pos[r:r + 1], hp)
            for r in range(ids.shape[0])]
    gap = jnp.concatenate([g for g, _ in rows])
    scale = jnp.concatenate([s for _, s in rows])
    return (neighbourhood_gaps(gap / scale, GAP_WINDOW) / GAP_UNIT_ROW_SCALES,
            scale / scale)
