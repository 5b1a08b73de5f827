"""Jamba decoders (configs with the published `jamba` keys; AI21-Jamba2-3B is
one): the program's build_jamba against harness/reference_jamba.py.

A configuration file holds the published keys as they are; the layer order is
the family's rule (attention where `l % attn_layer_period ==
attn_layer_offset`, a Mamba layer elsewhere), which the program's JambaConfig
and `layer_kinds` here both apply."""

from __future__ import annotations

from harness import flops_jamba as flops
from harness import reference_jamba as reference

train_flops_per_token = flops.train_flops_per_token
layer_kinds = flops.layer_kinds


def program_config(cfg: dict):
    """The configuration file as the program's JambaConfig."""
    from flexflow_tpu.models import JambaConfig

    assumed = cfg["assumed"]
    return JambaConfig(
        vocab=cfg["vocab_size"], seq=assumed["serve_positions"],
        d_model=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        attn_layer_period=cfg["attn_layer_period"],
        attn_layer_offset=cfg["attn_layer_offset"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        dense_width=cfg["intermediate_size"],
        mamba_expand=cfg["mamba_expand"], mamba_d_state=cfg["mamba_d_state"],
        mamba_dt_rank=cfg["mamba_dt_rank"], mamba_d_conv=cfg["mamba_d_conv"],
        num_experts=cfg["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        eps=cfg["rms_norm_eps"], dtype=assumed["weights_dtype"])


def build(model, cfg: dict, batch: int):
    """Adds the graph to `model`; returns the program's own configuration
    (`.vocab`, `.seq`, `.flops_per_token()`)."""
    from flexflow_tpu.models import build_jamba

    pcfg = program_config(cfg)
    build_jamba(model, pcfg, batch=batch)
    return pcfg


def serving_inputs():
    """(prompt inputs, step inputs) as ContinuousBatchingScheduler takes
    them: the model has no positions, so beside the token ids it is told
    which positions of a block, and which slots of a step, exist."""
    from flexflow_tpu.serving import valid_prompt_inputs, valid_step_inputs

    return valid_prompt_inputs, valid_step_inputs


def hyper(cfg: dict) -> dict:
    """The reference's hyper-parameters from the configuration file."""
    return {"heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "eps": float(cfg["rms_norm_eps"])}


def reference_params(params, cfg: dict) -> dict:
    """The program's parameter tree in the layout of
    harness/reference_jamba.py: the same device arrays, but the merged MLP
    input split into its gate and up halves and `A_log`, which the program
    keeps `[N, C]` (channels on the lanes), as the published `[C, N]`."""
    width = cfg["intermediate_size"]

    def layer(i, kind):
        w_in = params[f"l{i}_mlp_in"]["kernel"]
        out = {"norm_in": params[f"l{i}_norm_in"]["gamma"],
               "norm_ff": params[f"l{i}_norm_ff"]["gamma"],
               "w_gate": w_in[:, :width], "w_up": w_in[:, width:],
               "w_down": params[f"l{i}_mlp_out"]["kernel"]}
        if kind == "mamba":
            m = params[f"l{i}_mamba"]
            out.update({k: m[k] for k in (
                "in_proj", "conv_w", "x_proj", "dt_norm", "b_norm", "c_norm",
                "dt_proj", "dt_bias", "D", "out_proj")},
                conv_b=m["bias_conv"], A_log=m["A_log"].T)
        else:
            a = params[f"l{i}_attn"]
            out.update({k: a[k] for k in ("wq", "wk", "wv", "wo")})
        return out

    return {"embed": params["embed"]["kernel"],
            "norm_f": params["norm_f"]["gamma"],
            "head": params["lm_head"]["kernel"],
            "layers": [layer(i, k) for i, k in enumerate(layer_kinds(cfg))]}


def reference_loss(cfg: dict, params, ids, pos, labels):
    """The reference's next-token loss with the program's parameters
    (`pos` is the cell kinds' common argument; this model has no positions)."""
    return reference.next_token_loss(reference_params(params, cfg), ids,
                                     labels, hyper(cfg))


# The served-token rule's unit, in row scales (a row's scale is its largest
# |logit|). cells/serve.py counts a gap in bf16 ulps of max(1, scale) against
# its fixed 8; the gaps go out in units of GAP_UNIT_ROW_SCALES x the row's own
# scale, so the rule allows 8 x GAP_UNIT_ROW_SCALES = 18.4 bf16 ulps at the
# logits' scale. Set from this cell's own readings on the chip (my chip run,
# PR 62, call 2: benchmarks/control.py, two seeds, 4 rows a side, and the
# cell's own parity sample and logits check; PERF.md, Findings PR 62, has
# every later reading): the sound engine's worst token read 4.37, 4.96, 5.28
# and 5.78 bf16 ulps of its row's scale, an fp8 engine's (every matrix
# rounded to e4m3: the nearest precision below the configuration's bf16)
# 64.5 and 72.8; the limit is the geometric middle of the sound engine's
# largest and the fp8 engine's smallest, a factor 3.2 from each. A dense
# model: no routing flips a token's path, so each token is judged alone (no
# neighbourhood rule); the sound engine's 5 ulps are 28 layers of bf16
# activations behind a recurrence that remembers up to 1000 positions.
GAP_UNIT_ROW_SCALES = 2.3


def reference_token_gaps(cfg: dict, params, ids, pos):
    """(gap of each next token to the reference's maximum logit, the
    logits' scale) with the program's parameters, both in units of
    GAP_UNIT_ROW_SCALES x the row's own scale (the scale handed back is 1
    everywhere, so cells/serve.py's floor does not bite). A row at a time:
    at the timed lengths one row's float32 layer is what fits beside the
    engine."""
    import jax.numpy as jnp

    rp, hp = reference_params(params, cfg), hyper(cfg)
    ids = jnp.asarray(ids)
    rows = [reference.token_gaps(rp, ids[r:r + 1], hp)
            for r in range(ids.shape[0])]
    gap = jnp.concatenate([g for g, _ in rows])
    scale = jnp.concatenate([s for _, s in rows])
    return gap / (GAP_UNIT_ROW_SCALES * scale), scale / scale
