"""AFMoE decoders (configs with the published `afmoe` keys; Arcee's
Trinity-Mini is one): the program's build_afmoe against
harness/reference_afmoe.py.

In a configuration file `num_hidden_layers`, `num_dense_layers` and
`layer_types` are of the layers built, `num_experts` the routed experts held
here (ids 0 .. num_experts - 1) and `vocab_size` the slice of the vocabulary
held here; the published counts stand beside them as `published` (the
router's width is published.num_experts). `gcfg.vocab` is the slice, so the
training traffic draws its ids from it."""

from __future__ import annotations

from harness import flops_afmoe as flops
from harness import reference_afmoe as reference

train_flops_per_token = flops.train_flops_per_token

# the models `build` made, newest last: the selection bias is the compiled
# model's STATE, which `reference_loss` is not handed (cells/train.py passes
# the parameters): it reads it off the model it was built on
_BUILT = []


def held(cfg: dict):
    return (0, cfg["num_experts"])


def program_config(cfg: dict):
    """The configuration file as the program's AfmoeConfig."""
    from flexflow_tpu.models import AfmoeConfig

    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("afmoe: layer_types of num_hidden_layers layers")
    if cfg["score_func"] != "sigmoid" or not cfg["route_norm"] \
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("afmoe: a sigmoid router with normalised gates and "
                         "no groups")
    assumed = cfg["assumed"]
    return AfmoeConfig(
        vocab=cfg["vocab_size"], seq=assumed["train_positions"],
        d_model=cfg["hidden_size"], layer_types=tuple(cfg["layer_types"]),
        dense_layers=cfg["num_dense_layers"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], window=cfg["sliding_window"],
        dense_width=cfg["intermediate_size"],
        num_experts=flops.router_width(cfg),
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        shared_experts=cfg["num_shared_experts"],
        route_scale=cfg["route_scale"], bias_rate=cfg["load_balance_coeff"],
        rope_theta=float(cfg["rope_theta"]), experts_held=held(cfg),
        eps=cfg["rms_norm_eps"], dtype=assumed["weights_dtype"])


def build(model, cfg: dict, batch: int):
    """Adds the graph to `model` (inputs: ids, positions); returns the
    program's own configuration (`.vocab`, `.seq`, `.flops_per_token()`)."""
    from flexflow_tpu.models import build_afmoe

    pcfg = program_config(cfg)
    build_afmoe(model, pcfg, batch=batch)
    _BUILT.append(model)
    return pcfg


def reference_params(params, state, cfg: dict) -> dict:
    """The program's parameters and state (the selection biases), where
    they lie, in the layout of harness/reference_afmoe.py. No copy: the
    same device arrays."""
    def layer(i):
        out = {name: params[f"l{i}_{name}"]["gamma"]
               for name in ("norm_in", "norm_post_attn", "norm_pre_mlp",
                            "norm_post_mlp")}
        out.update(params[f"l{i}_attn"])
        if i < cfg["num_dense_layers"]:
            out.update(w_in=params[f"l{i}_mlp_in"]["kernel"],
                       w_out=params[f"l{i}_mlp_out"]["kernel"])
        else:
            moe = params[f"l{i}_moe"]
            out.update(router=moe["router"],
                       bias=state[f"l{i}_moe/score_bias"],
                       experts_in=moe["w_in"], experts_out=moe["w_out"],
                       shared_in=params[f"l{i}_shared_in"]["kernel"],
                       shared_out=params[f"l{i}_shared_out"]["kernel"])
        return out

    return {"embed": params["embed"]["kernel"],
            "norm_f": params["norm_f"]["gamma"],
            "head": params["lm_head"]["kernel"],
            "layers": [layer(i) for i in range(cfg["num_hidden_layers"])]}


def reference_loss(cfg: dict, params, ids, pos, labels):
    """The reference's next-token loss with the program's parameters and
    the state of the model `build` made last."""
    import jax
    import jax.numpy as jnp

    state = _BUILT[-1].compiled.state
    rp = reference_params(params, state, cfg)
    # a row at a time through ONE compiled program of a row
    row = jax.jit(lambda p, i, t, y: reference.next_token_loss(
        p, i[None], t[None], y[None], cfg, held(cfg)))
    ids, pos, labels = (jnp.asarray(a) for a in (ids, pos, labels))
    return sum(row(rp, ids[r], pos[r], labels[r])
               for r in range(ids.shape[0])) / ids.shape[0]
