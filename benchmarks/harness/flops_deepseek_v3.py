"""Operations and bytes the algorithm NEEDS for the `deepseek_v3` family,
from shapes and the program's own counters alone: the work of the equations
(harness/reference_deepseek_v3.py), whatever implements it. `cfg` is a
configuration file's dict (Hugging Face deepseek_v3 keys, with
`n_routed_experts` the experts held here and `vocab_size` the slice held
here); `system` is the cell's workloads/<cell>.json and `traffic` its traffic
parameters."""

from __future__ import annotations

BF16 = 2


def routed_over(cfg: dict) -> int:
    """The router's width: the published expert count."""
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def latent_dim(cfg: dict) -> int:
    """Values a token leaves in a layer's cache: its K/V latent and the
    shared rotary key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attention_params(cfg: dict) -> int:
    """The five matrices of a latent-attention layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
            + d * latent_dim(cfg)
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def expert_params(cfg: dict) -> int:
    """One routed expert: [d, 2w] in and [w, d] out."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_layers(cfg: dict) -> int:
    return min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - dense_layers(cfg)


def layer_dense_params(cfg: dict, dense: bool) -> int:
    """What every token of a layer is multiplied with: attention's
    projections and the gated MLP (a dense layer), or the router and the
    shared expert (an expert layer)."""
    d = cfg["hidden_size"]
    if dense:
        return attention_params(cfg) + 3 * d * cfg["intermediate_size"]
    return attention_params(cfg) + d * routed_over(cfg) \
        + 3 * d * cfg["n_shared_experts"] * cfg["moe_intermediate_size"]


def small_params(cfg: dict, dense: bool) -> int:
    """A layer's vectors: four norms, and an expert layer's selection bias."""
    n = 2 * cfg["hidden_size"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    return n if dense else n + routed_over(cfg)


def dense_params(cfg: dict) -> int:
    """Every parameter outside the routed experts, the embedding and the
    head: what each token meets in every layer."""
    return dense_layers(cfg) * (layer_dense_params(cfg, True)
                                + small_params(cfg, True)) \
        + expert_layers(cfg) * (layer_dense_params(cfg, False)
                                + small_params(cfg, False))


def param_count(cfg: dict) -> int:
    """Parameters held here (embedding and untied head over the slice)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return 2 * v * d + d + dense_params(cfg) \
        + expert_layers(cfg) * cfg["n_routed_experts"] * expert_params(cfg)


def matmul_params_per_token(cfg: dict) -> float:
    """Multiplied parameters a token meets here: the EXPECTED share of its
    top-k experts is held / routed over."""
    share = cfg["n_routed_experts"] / routed_over(cfg)
    return dense_layers(cfg) * layer_dense_params(cfg, True) \
        + expert_layers(cfg) * (layer_dense_params(cfg, False)
                                + cfg["num_experts_per_tok"] * share
                                * expert_params(cfg)) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops_per_pair(cfg: dict) -> int:
    """Scores and values of one (query, key) pair, all heads, decompressed:
    2 (dn + dr) + 2 dv a head."""
    return 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs a token needs: 6 a multiplied parameter and
    attention over the full square (the MFU convention, as harness/flops.py
    counts GPT-2), times 3 for forward + backward."""
    return 6.0 * matmul_params_per_token(cfg) \
        + 3.0 * cfg["num_hidden_layers"] * seq * attention_flops_per_pair(cfg)


def cache_bytes_per_token(cfg: dict) -> int:
    """What a token leaves in the cache, all layers, in bf16."""
    return cfg["num_hidden_layers"] * latent_dim(cfg) * BF16


def decode_step_need(cfg: dict, system: dict, traffic: dict,
                     counters: dict) -> dict:
    """The least one decode step must READ, as bytes, from the step's own
    counters (means over the steps read): every weight outside the routed
    experts once (the head among them, the embedding only the live slots'
    rows), the held experts that received a row (`moe_experts_hit`, summed
    over the layers), and the latent cache the live slots attended over
    (`latent_cache_bytes`, summed over the layers, as the pools store it).
    Live slots = routed pairs / (k * expert layers). A LOWER bound:
    whatever the program reads beyond this is not needed."""
    d = cfg["hidden_size"]
    live = counters["moe_routed_pairs"] / (cfg["num_experts_per_tok"]
                                           * expert_layers(cfg))
    dense = dense_params(cfg) + d + d * cfg["vocab_size"]
    experts = counters["moe_experts_hit"] * expert_params(cfg)
    return {"flops": 0.0,
            "bytes": float(BF16 * (dense + experts + live * d)
                           + counters["latent_cache_bytes"])}


def prefill_wave_need(cfg: dict, system: dict, traffic: dict,
                      counters: dict) -> dict:
    """Matmul FLOPs of one padded prefill wave `[slots, serve_positions]` as
    run: every position through attention's projections, the dense layers'
    MLPs, the router and the shared expert; the routed experts by the rows
    the wave's own counter says were routed here (`moe_held_pairs`, summed
    over the layers), not positions x experts held; attention under the
    diagonal, K and V decompressed; the head on each slot's last row alone."""
    slots = int(system["max_batch_slots"])
    seq = int(cfg["assumed"]["serve_positions"])
    positions = slots * seq
    dense = dense_layers(cfg) * layer_dense_params(cfg, True) \
        + expert_layers(cfg) * layer_dense_params(cfg, False)
    attn = cfg["num_hidden_layers"] * slots * (seq * (seq + 1) // 2) \
        * attention_flops_per_pair(cfg)
    return {"flops": float(2 * positions * dense
                           + 2 * counters["moe_held_pairs"] * expert_params(cfg)
                           + attn
                           + 2 * slots * cfg["hidden_size"] * cfg["vocab_size"]),
            "bytes": 0.0}
