"""Operations and bytes the algorithm NEEDS for the `bailing_hybrid` family,
from shapes and the program's own counters alone: the work of the equations
(harness/reference_bailing_hybrid.py), whatever implements it. `cfg` is a
configuration file's dict (Hugging Face bailing_hybrid keys, with
`num_experts` the experts held here and `vocab_size` the slice held here);
`system` is the cell's workloads/<cell>.json and `traffic` its traffic
parameters."""

from __future__ import annotations

BF16 = 2
F32 = 4


def kinds(cfg: dict) -> list:
    """A layer's mixer, layer by layer: every `layer_group_size`-th layer is
    latent attention, the others linear attention (KDA)."""
    return ["latent" if (i + 1) % cfg["layer_group_size"] == 0 else "kda"
            for i in range(cfg["num_hidden_layers"])]


def routed_over(cfg: dict) -> int:
    """The router's width: the published expert count."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def kda_inner(cfg: dict) -> int:
    return cfg["num_attention_heads"] * cfg["head_dim"]


def latent_dim(cfg: dict) -> int:
    """Values a token leaves in a latent layer's cache."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def dense_layers(cfg: dict) -> int:
    return min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - dense_layers(cfg)


def expert_params(cfg: dict) -> int:
    """One routed expert: [d, 2w] in and [w, d] out."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def mixer_params(cfg: dict, kind: str) -> int:
    """A mixer's matrices. KDA: q, k, v, the decay gate and the output gate
    (H D wide each), beta (H), and the output projection. Latent attention:
    W_q, W_kva, W_kvb, W_o and the head gate."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if kind == "kda":
        return d * (5 * kda_inner(cfg) + h) + kda_inner(cfg) * d
    return d * h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) \
        + d * latent_dim(cfg) \
        + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                     + cfg["v_head_dim"]) \
        + h * cfg["v_head_dim"] * d + d * h


def mixer_small_params(cfg: dict, kind: str) -> int:
    """A mixer's vectors: a KDA layer's three convolutions, A_log, dt_bias
    and head norm; a latent layer's K/V norm."""
    if kind == "kda":
        return cfg["short_conv_kernel_size"] * 3 * kda_inner(cfg) \
            + cfg["num_attention_heads"] + kda_inner(cfg) + cfg["head_dim"]
    return cfg["kv_lora_rank"]


def feed_forward_params(cfg: dict, dense: bool) -> int:
    """What every token is multiplied with behind a mixer: the gated MLP,
    or the router and the shared expert."""
    d = cfg["hidden_size"]
    if dense:
        return 3 * d * cfg["intermediate_size"]
    return d * routed_over(cfg) + 3 * d * cfg["num_shared_experts"] \
        * cfg["moe_shared_expert_intermediate_size"]


def dense_params(cfg: dict, small: bool = True) -> int:
    """Every parameter outside the routed experts, the embedding and the
    head: what each token meets in every layer (`small`: with the vectors:
    norms, convolutions, decay parameters, the selection bias)."""
    total = 0
    for i, kind in enumerate(kinds(cfg)):
        dense = i < dense_layers(cfg)
        total += mixer_params(cfg, kind) + feed_forward_params(cfg, dense)
        if small:
            total += mixer_small_params(cfg, kind) + 2 * cfg["hidden_size"] \
                + (0 if dense else routed_over(cfg))
    return total


def param_count(cfg: dict) -> int:
    """Parameters held here (embedding and untied head over the slice)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return 2 * v * d + d + dense_params(cfg) \
        + expert_layers(cfg) * cfg["num_experts"] * expert_params(cfg)


def matmul_params_per_token(cfg: dict) -> float:
    """Multiplied parameters a token meets here: the EXPECTED share of its
    top-k experts is held / routed over."""
    share = cfg["num_experts"] / routed_over(cfg)
    return dense_params(cfg, small=False) \
        + expert_layers(cfg) * cfg["num_experts_per_tok"] * share \
        * expert_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]


def kda_flops_per_token(cfg: dict) -> int:
    """The delta rule's own products, one KDA layer, forward, D x D a head:
    the decay (one product a state entry), S'^T k, the rank-one update and
    the read-out (two each)."""
    return 7 * cfg["num_attention_heads"] * cfg["head_dim"] ** 2


def attention_flops_per_pair(cfg: dict) -> int:
    """Scores and values of one (query, key) pair of a latent layer, all
    heads, decompressed: 2 (dn + dr) + 2 dv a head."""
    return 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs a token needs: 6 a multiplied parameter, the
    latent layers' attention over the full square (the MFU convention, as
    harness/flops.py counts GPT-2) and the delta rule's own products, times
    3 for forward + backward."""
    ks = kinds(cfg)
    return 6.0 * matmul_params_per_token(cfg) + 3.0 * (
        ks.count("latent") * seq * attention_flops_per_pair(cfg)
        + ks.count("kda") * kda_flops_per_token(cfg))


def state_bytes_per_slot(cfg: dict) -> int:
    """Recurrent state of one slot, all KDA layers: the f32 matrix state
    and the three convolution tails in the weights' type."""
    state = cfg["num_attention_heads"] * cfg["head_dim"] ** 2 * F32
    tails = (cfg["short_conv_kernel_size"] - 1) * 3 * kda_inner(cfg) * BF16
    return kinds(cfg).count("kda") * (state + tails)


def cache_bytes_per_token(cfg: dict) -> int:
    """What a token leaves in the paged cache, all latent layers, in bf16."""
    return kinds(cfg).count("latent") * latent_dim(cfg) * BF16


def decode_step_need(cfg: dict, system: dict, traffic: dict,
                     counters: dict) -> dict:
    """The least one decode step must READ and WRITE, as bytes, from the
    step's own counters (means over the steps read): every weight outside
    the routed experts once (the head among them, the embedding only the
    live slots' rows), the held experts that received a row
    (`moe_experts_hit`, summed over the layers), the matrix state and
    convolution tails the live slots read and wrote (`linear_state_bytes`,
    summed over the KDA layers), and the latent cache the live slots
    attended over (`latent_cache_bytes`, as the pools store it). Live slots
    = routed pairs / (k * expert layers). A LOWER bound: whatever the
    program reads beyond this is not needed."""
    d = cfg["hidden_size"]
    live = counters["moe_routed_pairs"] / (cfg["num_experts_per_tok"]
                                           * expert_layers(cfg))
    dense = dense_params(cfg) + d + d * cfg["vocab_size"]
    experts = counters["moe_experts_hit"] * expert_params(cfg)
    return {"flops": 0.0,
            "bytes": float(BF16 * (dense + experts + live * d)
                           + counters["linear_state_bytes"]
                           + counters["latent_cache_bytes"])}


def prefill_wave_need(cfg: dict, system: dict, traffic: dict,
                      counters: dict) -> dict:
    """Matmul FLOPs of one padded prefill wave `[slots, serve_positions]` as
    run: every position through the mixers' projections, the dense layers'
    MLPs, the router and the shared expert; the routed experts by the rows
    the wave's own counter says were routed here (`moe_held_pairs`, summed
    over the layers), not positions x experts held; the latent layers'
    attention under the diagonal, K and V decompressed; the delta rule's
    own products (not the chunked form's price); the head on each slot's
    last row alone."""
    slots = int(system["max_batch_slots"])
    seq = int(cfg["assumed"]["serve_positions"])
    positions = slots * seq
    ks = kinds(cfg)
    attn = ks.count("latent") * slots * (seq * (seq + 1) // 2) \
        * attention_flops_per_pair(cfg)
    kda = ks.count("kda") * positions * kda_flops_per_token(cfg)
    return {"flops": float(2 * positions * dense_params(cfg, small=False)
                           + 2 * counters["moe_held_pairs"] * expert_params(cfg)
                           + attn + kda
                           + 2 * slots * cfg["hidden_size"] * cfg["vocab_size"]),
            "bytes": 0.0}


def kda_scan_need(cfg: dict, system: dict, traffic: dict,
                  counters: dict) -> dict:
    """What the delta-rule scans of one padded prefill wave need, all KDA
    layers (`kda_layers`, the wave's own counter): the recurrence's own
    products a position, and as bytes q, k, v and the decay gate read and
    the output written in the compute type, beta, and a slot's f32 state
    written once. The projections, the convolution and the gated norm are
    not the scan's."""
    slots = int(system["max_batch_slots"])
    positions = slots * int(cfg["assumed"]["serve_positions"])
    heads = cfg["num_attention_heads"]
    per_position = 5 * kda_inner(cfg) * BF16 + heads * F32
    state = slots * heads * cfg["head_dim"] ** 2 * F32
    layers = counters["kda_layers"]
    return {"flops": float(layers * positions * kda_flops_per_token(cfg)),
            "bytes": float(layers * (positions * per_position + state))}
