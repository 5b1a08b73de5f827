"""GPT-2-shaped decoders (configs with Hugging Face GPT-2 keys): the
program's build_gpt2 against harness/reference_gpt2.py."""

from __future__ import annotations

from harness import flops, reference_gpt2

train_flops_per_token = flops.train_flops_per_token


def program_config(cfg: dict):
    """The configuration file as the program's GPT2Config. dropout 0.0: see
    the file's `departures`."""
    from flexflow_tpu.models import GPT2Config

    return GPT2Config(vocab=cfg["vocab_size"], seq=cfg["n_positions"],
                      d_model=cfg["n_embd"], heads=cfg["n_head"],
                      layers=cfg["n_layer"], d_ff=cfg.get("n_inner") or 0,
                      dropout=0.0)


def build(model, cfg: dict, batch: int):
    """Adds the graph to `model`; returns the program's own configuration
    (`.vocab`, `.seq`, `.flops_per_token()`)."""
    from flexflow_tpu.models import build_gpt2

    pcfg = program_config(cfg)
    build_gpt2(model, pcfg, batch=batch)
    return pcfg


def serving_inputs():
    """(prompt inputs, step inputs) as ContinuousBatchingScheduler takes them."""
    from flexflow_tpu.serving import gpt2_prompt_inputs, gpt2_step_inputs

    return gpt2_prompt_inputs, gpt2_step_inputs


def reference_params(params, cfg: dict) -> dict:
    """The program's parameter tree, where it lies, in the layout of
    harness/reference_gpt2.py. No copy: the same device arrays."""
    def block(i):
        a, up, down = (params[f"h{i}_attn"], params[f"h{i}_mlp_up"],
                       params[f"h{i}_mlp_down"])
        ln1, ln2 = params[f"h{i}_ln1"], params[f"h{i}_ln2"]
        return {"ln1_g": ln1["gamma"], "ln1_b": ln1["beta"],
                "wq": a["wq"], "bq": a["bq"], "wk": a["wk"], "bk": a["bk"],
                "wv": a["wv"], "bv": a["bv"], "wo": a["wo"], "bo": a["bo"],
                "ln2_g": ln2["gamma"], "ln2_b": ln2["beta"],
                "w_up": up["kernel"], "b_up": up["bias"],
                "w_down": down["kernel"], "b_down": down["bias"]}

    return {"wte": params["wte"]["kernel"], "wpe": params["wpe"]["kernel"],
            "lnf_g": params["ln_f"]["gamma"], "lnf_b": params["ln_f"]["beta"],
            "head": params["lm_head"]["kernel"],
            "blocks": [block(i) for i in range(cfg["n_layer"])]}


def reference_loss(cfg: dict, params, ids, pos, labels):
    """The reference's next-token loss with the program's parameters."""
    import jax

    return jax.jit(reference_gpt2.next_token_loss, static_argnums=(4,))(
        reference_params(params, cfg), ids, pos, labels, cfg["n_head"])


def reference_token_gaps(cfg: dict, params, ids, pos):
    """(gap of each next token to the reference's maximum logit, the
    logits' scale) with the program's parameters."""
    import jax

    return jax.jit(reference_gpt2.token_gaps, static_argnums=(3,))(
        reference_params(params, cfg), ids, pos, cfg["n_head"])
