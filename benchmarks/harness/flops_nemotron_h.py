"""Operations and bytes the algorithm NEEDS for the `nemotron_h` family,
from shapes and the program's own counters alone: the work of the equations
(harness/reference_nemotron_h.py), whatever implements it. `cfg` is a
configuration file's dict (Hugging Face nemotron_h keys, with
`n_routed_experts` the experts held here and `vocab_size` the slice held
here); `system` is the cell's workloads/<cell>.json and `traffic` its traffic
parameters."""

from __future__ import annotations

BF16 = 2
F32 = 4
KINDS = {"M": "mamba", "*": "attention", "E": "experts"}


def kinds(cfg: dict) -> list:
    """A layer's kind, layer by layer, from `hybrid_override_pattern`."""
    return [KINDS[c] for c in cfg["hybrid_override_pattern"]]


def routed_over(cfg: dict) -> int:
    """The router's width: the published expert count."""
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def d_inner(cfg: dict) -> int:
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def conv_dim(cfg: dict) -> int:
    return d_inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert: [latent, w] in and [w, latent] out, no gate."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def layer_dense_params(cfg: dict, kind: str) -> int:
    """What every token of a layer is multiplied with: a mixer's projections;
    an expert layer's router, two latent projections and shared expert."""
    d = cfg["hidden_size"]
    if kind == "attention":
        return 2 * d * cfg["num_attention_heads"] * cfg["head_dim"] \
            + 2 * d * cfg["num_key_value_heads"] * cfg["head_dim"]
    if kind == "mamba":
        return d * (d_inner(cfg) + conv_dim(cfg) + cfg["mamba_num_heads"]) \
            + d_inner(cfg) * d
    return d * routed_over(cfg) + 2 * d * cfg["moe_latent_size"] \
        + 2 * d * cfg["moe_shared_expert_intermediate_size"] \
        * cfg["n_shared_experts"]


def small_params(cfg: dict, kind: str) -> int:
    """A layer's vectors: its norm, a Mamba layer's conv, A_log, D, dt_bias
    and gated norm, an expert layer's selection bias."""
    n = cfg["hidden_size"]
    if kind == "mamba":
        n += (cfg["conv_kernel"] + 1) * conv_dim(cfg) \
            + 3 * cfg["mamba_num_heads"] + d_inner(cfg)
    if kind == "experts":
        n += routed_over(cfg)
    return n


def param_count(cfg: dict) -> int:
    """Parameters held here (embedding and head are weights of their own)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return 2 * v * d + d + sum(
        layer_dense_params(cfg, k) + small_params(cfg, k)
        + (cfg["n_routed_experts"] * expert_params(cfg) if k == "experts" else 0)
        for k in kinds(cfg))


def matmul_params_per_token(cfg: dict) -> float:
    """Multiplied parameters a token meets here: the EXPECTED share of its
    top-k experts is held / routed over."""
    routed = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / routed_over(cfg) * expert_params(cfg)
    return sum(layer_dense_params(cfg, k) + (routed if k == "experts" else 0)
               for k in kinds(cfg)) + cfg["hidden_size"] * cfg["vocab_size"]


def ssm_flops_per_token(cfg: dict) -> int:
    """The recurrence's own products, one Mamba layer, forward: the state
    update and the read-out, 2 * 2 * P * N a head."""
    return 4 * d_inner(cfg) * cfg["ssm_state_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs a token needs: 6 a multiplied parameter, the
    attention layers' scores and values over the full square (the MFU
    convention, as harness/flops.py counts GPT-2) and the recurrence's own
    products, times 3 for forward + backward."""
    ks = kinds(cfg)
    attn = ks.count("attention") * 2 * 2 * seq * cfg["hidden_size"]
    return 6.0 * matmul_params_per_token(cfg) \
        + 3.0 * (attn + ks.count("mamba") * ssm_flops_per_token(cfg))


def state_bytes_per_slot(cfg: dict) -> int:
    """Recurrent state of one slot, all Mamba layers: the f32 SSM state and
    the conv tail in the weights' type."""
    ssm = d_inner(cfg) * cfg["ssm_state_size"] * F32
    tail = (cfg["conv_kernel"] - 1) * conv_dim(cfg) * BF16
    return kinds(cfg).count("mamba") * (ssm + tail)


def kv_bytes_per_token(cfg: dict) -> int:
    return kinds(cfg).count("attention") * 2 * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * BF16


def decode_step_need(cfg: dict, system: dict, traffic: dict,
                     counters: dict) -> dict:
    """The least one decode step must READ and WRITE, as bytes, from the
    step's own counters (means over the steps read): every weight outside
    the routed experts once (the head among them, the embedding only the
    live slots' rows), the held experts that received a row
    (`moe_experts_hit`, summed over the layers), the recurrent state the
    live slots read and wrote (`ssm_state_bytes`, summed over the layers),
    and the K/V of the live context, counted at the shortest prompt the
    traffic sends (a floor: it is what is surely there). Live slots =
    routed pairs / (k * expert layers). A LOWER bound: whatever the program
    reads beyond this is not needed."""
    ks = kinds(cfg)
    d = cfg["hidden_size"]
    live = counters["moe_routed_pairs"] / (cfg["num_experts_per_tok"]
                                           * ks.count("experts"))
    dense = sum(layer_dense_params(cfg, k) + small_params(cfg, k) for k in ks) \
        + d + d * cfg["vocab_size"]
    experts = counters["moe_experts_hit"] * expert_params(cfg)
    floor_context = int(traffic["prompt_len"]["min"])
    return {"flops": 0.0,
            "bytes": float(BF16 * (dense + experts + live * d)
                           + counters["ssm_state_bytes"]
                           + live * floor_context * kv_bytes_per_token(cfg))}


def prefill_wave_need(cfg: dict, system: dict, traffic: dict,
                      counters: dict) -> dict:
    """Matmul FLOPs of one padded prefill wave `[slots, serve_positions]`:
    every position through the mixers' projections, the router, the latent
    projections and the shared expert; the routed experts at the latent
    width by the rows the wave's own counter says were routed here
    (`moe_held_pairs`, summed over the layers), not positions x experts
    held; attention under the diagonal; the recurrence's own products; the
    head on each slot's last row alone."""
    slots = int(system["max_batch_slots"])
    seq = int(cfg["assumed"]["serve_positions"])
    positions = slots * seq
    ks = kinds(cfg)
    dense = sum(layer_dense_params(cfg, k) for k in ks)
    attn = ks.count("attention") * slots * 2 * 2 * (seq * (seq + 1) // 2) \
        * cfg["num_attention_heads"] * cfg["head_dim"]
    ssm = ks.count("mamba") * positions * ssm_flops_per_token(cfg)
    return {"flops": float(2 * positions * dense
                           + 2 * counters["moe_held_pairs"] * expert_params(cfg)
                           + attn + ssm
                           + 2 * slots * cfg["hidden_size"] * cfg["vocab_size"]),
            "bytes": 0.0}
