"""The language model of Keye-VL decoders (configs with the published
KeyeVL2 keys; Keye-VL-2.0-30B-A3B is one): the program's build_keye_vl
against harness/reference_keye_vl.py.

In a configuration file `num_hidden_layers` is the layers built (the
published value stands beside it as `published`); `num_experts` the experts
held here (all of them in the benchmark's configuration)."""

from __future__ import annotations

from harness import flops_keye_vl as flops
from harness import reference_keye_vl as reference

train_flops_per_token = flops.train_flops_per_token


def program_config(cfg: dict):
    """The configuration file as the program's KeyeVLConfig."""
    from flexflow_tpu.models import KeyeVLConfig

    assumed, sa = cfg["assumed"], cfg["sa_config"]
    return KeyeVLConfig(
        vocab=cfg["vocab_size"], seq=assumed["serve_positions"],
        d_model=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], num_experts=cfg["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        mrope_section=tuple(cfg["rope_scaling"]["mrope_section"]),
        indexer_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], indexer_topk=sa["topk"],
        indexer_mrope_section=tuple(assumed["indexer_mrope_section"]),
        experts_held=(0, cfg["num_experts"]), eps=cfg["rms_norm_eps"],
        dtype=assumed["weights_dtype"])


def build(model, cfg: dict, batch: int):
    """Adds the graph to `model`; returns the program's own configuration
    (`.vocab`, `.seq`, `.flops_per_token()`)."""
    from flexflow_tpu.models import build_keye_vl

    pcfg = program_config(cfg)
    build_keye_vl(model, pcfg, batch=batch)
    return pcfg


def serving_inputs():
    """(prompt inputs, step inputs) as ContinuousBatchingScheduler takes
    them: token ids, the three position axes (text gives its one position
    three times; a prefill chunk's start from where its slot's context
    ends), and which positions of a block, and which slots of a step,
    exist."""
    from flexflow_tpu.serving import (positions3_valid_prompt_inputs,
                                      positions3_valid_step_inputs)

    return positions3_valid_prompt_inputs, positions3_valid_step_inputs


def hyper(cfg: dict) -> dict:
    """The reference's hyper-parameters from the configuration file."""
    sa = cfg["sa_config"]
    return {"heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "rope_theta": float(cfg["rope_theta"]),
            "mrope_section": tuple(cfg["rope_scaling"]["mrope_section"]),
            "indexer_heads": sa["indexer_num_heads"],
            "indexer_head_dim": sa["indexer_head_dim"],
            "indexer_mrope_section":
                tuple(cfg["assumed"]["indexer_mrope_section"]),
            "topk": sa["topk"], "top_k": cfg["num_experts_per_tok"],
            "held": (0, cfg["num_experts"]),
            "eps": float(cfg["rms_norm_eps"])}


def reference_params(params, cfg: dict) -> dict:
    """The program's parameter tree, where it lies, in the layout of
    harness/reference_keye_vl.py. No copy: the same device arrays."""
    def layer(i):
        out = {"norm_op": params[f"l{i}_norm_op"]["gamma"],
               "norm_ffn": params[f"l{i}_norm_ffn"]["gamma"],
               "index": params[f"l{i}_index"]}
        out.update(params[f"l{i}_attn"])
        out.update(params[f"l{i}_moe"])
        return out

    return {"embed": params["embed"]["kernel"],
            "norm_f": params["norm_f"]["gamma"],
            "head": params["lm_head"]["kernel"],
            "layers": [layer(i) for i in range(cfg["num_hidden_layers"])]}


def positions3(pos):
    """The harness's one-axis positions `[rows, seq]` as text's three."""
    import jax.numpy as jnp

    return jnp.repeat(jnp.asarray(pos)[..., None], 3, axis=-1)


def reference_loss(cfg: dict, params, ids, pos, labels):
    """The reference's next-token loss with the program's parameters."""
    return reference.next_token_loss(reference_params(params, cfg), ids,
                                     positions3(pos), labels, hyper(cfg))


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
# which cells/serve.py allows 8 bf16 ulps: 0.0225 of the row's scale. Set
# from this cell's own readings on the chip (my chip run, PR 52, call 5: the
# cell's windows and parity sample through benchmarks/control.py, four seeds,
# 16 rows a side; PERF.md, Findings PR 52): the sound engine's worst token
# read 2.22-3.63 bf16 ulps of its row's scale (2.0-3.0 in six other windows
# and checks), an fp8 engine's 7.67-12.72; the limit of 5.76 leaves the sound
# engine a factor 1.59 and the fp8 engine 1.33 (the room is the larger on the
# side where a fresh seed's `correct` false would refuse a sound program).
# This model reads far closer to its reference than LFM2's (0.41-0.49 of the
# logits' scale there, 0.055-0.062 here: logits_check_keye_vl.py): attention
# over thousands of kept keys is nearly an average and top-8 of 128 moves a
# token less than top-4 of 64. At a window of 8 the same rows read 2.81-4.77
# against 9.68-15.99 (a factor 2.03 apart where 16 gives 2.12).
GAP_UNIT_ROW_SCALES = 0.72
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
    ids, pos3 = jnp.asarray(ids), positions3(pos)
    rows = [reference.token_gaps(rp, ids[r:r + 1], pos3[r:r + 1], hp)
            for r in range(ids.shape[0])]
    gap = jnp.concatenate([g for g, _ in rows])
    scale = jnp.concatenate([s for _, s in rows])
    return (neighbourhood_gaps(gap / scale, GAP_WINDOW) / GAP_UNIT_ROW_SCALES,
            scale / scale)
