"""Brumby decoders (configs with Hugging Face `brumby` keys; Brumby-14B-Base
is one): the program's build_brumby against harness/reference_brumby.py.

A dense model: no expert share and the whole vocabulary; a configuration
file's `num_hidden_layers` is the depth held here, the published depth
stands beside it as `published`."""

from __future__ import annotations

from harness import flops_brumby as flops
from harness import reference_brumby as reference

train_flops_per_token = flops.train_flops_per_token


def program_config(cfg: dict):
    """The configuration file as the program's BrumbyConfig."""
    from flexflow_tpu.models import BrumbyConfig

    assumed = cfg["assumed"]
    return BrumbyConfig(
        vocab=cfg["vocab_size"], seq=assumed["serve_positions"],
        d_model=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], dense_width=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        gate_logit_std=float(assumed["gate_logit_std"]),
        eps=cfg["rms_norm_eps"], dtype=assumed["weights_dtype"])


def build(model, cfg: dict, batch: int):
    """Adds the graph to `model`; returns the program's own configuration
    (`.vocab`, `.seq`, `.flops_per_token()`)."""
    from flexflow_tpu.models import build_brumby

    pcfg = program_config(cfg)
    build_brumby(model, pcfg, batch=batch)
    return pcfg


def serving_inputs():
    """(prompt inputs, step inputs) as ContinuousBatchingScheduler takes
    them: token ids, rotary positions, and which positions of a wave, and
    which slots of a step, exist (where the state stops)."""
    from flexflow_tpu.serving import (positions_valid_prompt_inputs,
                                      positions_valid_step_inputs)

    return positions_valid_prompt_inputs, positions_valid_step_inputs


def hyper(cfg: dict) -> dict:
    """The reference's hyper-parameters from the configuration file."""
    return {"heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "rope_theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"])}


def reference_params(params, cfg: dict) -> dict:
    """The program's parameter tree, where it lies, in the layout of
    harness/reference_brumby.py. No copy: the same device arrays."""
    def layer(i):
        return dict(params[f"l{i}_ret"],
                    norm_in=params[f"l{i}_norm_in"]["gamma"],
                    norm_post=params[f"l{i}_norm_post"]["gamma"],
                    mlp_in=params[f"l{i}_mlp_in"]["kernel"],
                    mlp_out=params[f"l{i}_mlp_out"]["kernel"])

    return {"embed": params["embed"]["kernel"],
            "norm_f": params["norm_f"]["gamma"],
            "head": params["lm_head"]["kernel"],
            "layers": [layer(i) for i in range(cfg["num_hidden_layers"])]}


def reference_loss(cfg: dict, params, ids, pos, labels):
    """The reference's next-token loss with the program's parameters."""
    return reference.next_token_loss(reference_params(params, cfg), ids, pos,
                                     labels, hyper(cfg))


# The served-token rule's unit, in row scales (a row's scale is its largest
# |logit|). cells/serve.py counts a gap in bf16 ulps of max(1, scale) against
# its fixed 8. A dense model has no router whose choice a bf16 hidden state
# could flip, so the rule is granite's (families/granitemoehybrid.py), each
# token judged alone at the logits' own scale: the gaps go out in units of
# GAP_UNIT_ROW_SCALES x the row's scale: the rule then allows 16 bf16 ulps at
# the logits' scale. On the chip (PERF.md, PR 43, has every reading with its
# call) the sound engine's worst served token read 3.9-7.8 ulps at the
# logits' scale over its first fourteen windows of 1.4-2.2 k served tokens
# (5-8 % of them not the f32 reference's argmax: near-ties under bf16), an
# fp8 engine's 53.5 and 69.8 (control.py: `tight`); 16 lies between, a
# factor 2.1 and 3.3 from either. An engine whose state's rows or read-out
# are rounded to bfloat16 read 256 (one served token in ten wrong: a
# read-out's 8256 terms cancel to a fortieth of their size,
# ops/power_retention_ops.py), so the rule catches that too.
GAP_UNIT_ROW_SCALES = 2.0


def reference_token_gaps(cfg: dict, params, ids, pos):
    """(gap of each next token to the reference's maximum logit, the
    logits' scale) with the program's parameters, layer by layer, both in
    units of GAP_UNIT_ROW_SCALES x the row's own scale: the scale handed
    back is 1 everywhere, so cells/serve.py's floor does not bite."""
    gap, scale = reference.token_gaps(reference_params(params, cfg), ids, pos,
                                      hyper(cfg))
    return gap / (GAP_UNIT_ROW_SCALES * scale), scale / scale
