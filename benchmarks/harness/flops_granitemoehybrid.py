"""Operations and bytes the algorithm NEEDS for the `granitemoehybrid`
family, from shapes and the program's own routing counters alone. `cfg` is a
configuration file's dict (Hugging Face granitemoehybrid keys, with
`num_local_experts` the experts held here and `vocab_size` the slice held
here); `system` is the cell's workloads/<cell>.json and `traffic` its traffic
parameters."""

from __future__ import annotations

BF16 = 2


def routed_over(cfg: dict) -> int:
    """The router's width: the published expert count."""
    return cfg.get("published", {}).get("num_local_experts",
                                        cfg["num_local_experts"])


def d_inner(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def conv_dim(cfg: dict) -> int:
    return d_inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def mixer_matmul_params(cfg: dict, kind: str) -> int:
    d = cfg["hidden_size"]
    if kind == "attention":
        hd = d // cfg["num_attention_heads"]
        return 2 * d * d + 2 * d * cfg["num_key_value_heads"] * hd
    return d * (d_inner(cfg) + conv_dim(cfg) + cfg["mamba_n_heads"]) \
        + d_inner(cfg) * d


def expert_params(cfg: dict) -> int:
    """One routed expert: [d, 2w] in and [w, d] out."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_dense_params(cfg: dict, kind: str) -> int:
    """What every token of a layer is multiplied with: the mixer's
    projections, the router and the shared MLP."""
    d = cfg["hidden_size"]
    return mixer_matmul_params(cfg, kind) + d * routed_over(cfg) \
        + 3 * d * cfg["shared_intermediate_size"]


def small_params(cfg: dict, kind: str) -> int:
    """A layer's vectors: two norms, and a Mamba layer's conv, A_log, D,
    dt_bias and gated norm."""
    n = 2 * cfg["hidden_size"]
    if kind == "mamba":
        n += (cfg["mamba_d_conv"] + 1) * conv_dim(cfg) \
            + 3 * cfg["mamba_n_heads"] + d_inner(cfg)
    return n


def param_count(cfg: dict) -> int:
    """Parameters held here, the head a weight of its own (untied)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    layers = sum(layer_dense_params(cfg, k) + small_params(cfg, k)
                 + cfg["num_local_experts"] * expert_params(cfg)
                 for k in cfg["layer_types"])
    return 2 * v * d + d + layers


def matmul_params_per_token(cfg: dict) -> float:
    """Multiplied parameters a token meets here: the EXPECTED share of its
    top-k experts is held / routed over."""
    share = cfg["num_local_experts"] / routed_over(cfg)
    return sum(layer_dense_params(cfg, k)
               + cfg["num_experts_per_tok"] * share * expert_params(cfg)
               for k in cfg["layer_types"]) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def ssm_flops_per_token(cfg: dict) -> int:
    """The recurrence's own products, one Mamba layer, forward: the state
    update and the read-out, 2 * 2 * P * N a head."""
    return 4 * cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs a token needs: 6 a multiplied parameter, the
    attention layers' scores and values over the full square (the MFU
    convention, as harness/flops.py counts GPT-2) and the recurrence's own
    products, times 3 for forward + backward."""
    n_attn = sum(k == "attention" for k in cfg["layer_types"])
    n_mamba = len(cfg["layer_types"]) - n_attn
    attn = n_attn * 2 * 2 * seq * cfg["hidden_size"]
    return 6.0 * matmul_params_per_token(cfg) \
        + 3.0 * (attn + n_mamba * ssm_flops_per_token(cfg))


def state_bytes_per_slot(cfg: dict) -> int:
    """Recurrent state of one slot, all Mamba layers: the f32 SSM state and
    the conv tail in the weights' type."""
    n_mamba = sum(k == "mamba" for k in cfg["layer_types"])
    ssm = cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"] * 4
    tail = (cfg["mamba_d_conv"] - 1) * conv_dim(cfg) * BF16
    return n_mamba * (ssm + tail)


def kv_bytes_per_token(cfg: dict) -> int:
    n_attn = sum(k == "attention" for k in cfg["layer_types"])
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return n_attn * 2 * cfg["num_key_value_heads"] * hd * BF16


def decode_step_need(cfg: dict, system: dict, traffic: dict,
                     counters: dict) -> dict:
    """The least one decode step must READ and WRITE, as bytes, from the
    step's own counters (means over the steps read): every weight outside
    the routed experts once (the head among them, the embedding only the
    live slots' rows), the held experts that received a row
    (`moe_experts_hit`, summed over the layers), the live slots' recurrent
    state read and written, and the K/V of the live context, counted at the
    shortest prompt the traffic sends (a floor: it is what is surely there).
    Live slots = routed pairs / (k * layers). A LOWER bound: whatever the
    program reads beyond this (the pools it copies, inactive slots' state)
    is not needed."""
    layers = cfg["layer_types"]
    d = cfg["hidden_size"]
    live = counters["moe_routed_pairs"] / (cfg["num_experts_per_tok"] * len(layers))
    dense = sum(layer_dense_params(cfg, k) + small_params(cfg, k) for k in layers) \
        + d + d * cfg["vocab_size"]
    experts = counters["moe_experts_hit"] * expert_params(cfg)
    floor_context = int(traffic["prompt_len"]["min"])
    return {"flops": 0.0,
            "bytes": float(BF16 * (dense + experts + live * d)
                           + 2 * live * state_bytes_per_slot(cfg)
                           + live * floor_context * kv_bytes_per_token(cfg))}


def prefill_wave_need(cfg: dict, system: dict, traffic: dict,
                      counters: dict) -> dict:
    """Matmul FLOPs of one padded prefill wave `[slots, serve_positions]`:
    every position through the mixers' projections, the router and the
    shared MLP; the routed experts by the rows the wave's own counter says
    were routed here (`moe_held_pairs`, summed over the layers), not
    positions x experts held; attention under the diagonal; the
    recurrence's own products; the head on each slot's last row alone."""
    slots = int(system["max_batch_slots"])
    seq = int(cfg["assumed"]["serve_positions"])
    positions = slots * seq
    layers = cfg["layer_types"]
    n_attn = sum(k == "attention" for k in layers)
    dense = sum(layer_dense_params(cfg, k) for k in layers)
    attn = n_attn * slots * 2 * 2 * (seq * (seq + 1) // 2) * cfg["hidden_size"]
    ssm = (len(layers) - n_attn) * positions * ssm_flops_per_token(cfg)
    return {"flops": float(2 * positions * dense
                           + 2 * counters["moe_held_pairs"] * expert_params(cfg)
                           + attn + ssm
                           + 2 * slots * cfg["hidden_size"] * cfg["vocab_size"]),
            "bytes": 0.0}
