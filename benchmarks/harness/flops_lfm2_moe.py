"""Operations and bytes the algorithm NEEDS for the `lfm2_moe` family, from
shapes and the program's own counters alone: the work of the equations
(harness/reference_lfm2_moe.py), whatever implements it. `cfg` is a
configuration file's dict (Hugging Face lfm2_moe keys; `num_experts` is the
experts held here, the router's width `published.num_experts` where the file
states one); `system` is the cell's workloads/<cell>.json and `traffic` its
traffic parameters."""

from __future__ import annotations

BF16 = 2


def kinds(cfg: dict) -> list:
    """A layer's operator, layer by layer: "conv" or "full_attention"."""
    return list(cfg["layer_types"])


def routed_over(cfg: dict) -> int:
    """The router's width: the published expert count."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def expert_params(cfg: dict) -> int:
    """One routed expert: W_1, W_3 [d, w] and W_2 [w, d]."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def operator_matmul_params(cfg: dict, kind: str) -> int:
    """The matrices of a layer's operator: the convolution's two
    projections, or attention's four."""
    d = cfg["hidden_size"]
    if kind == "conv":
        return 4 * d * d
    return 2 * d * d + 2 * d * cfg["num_key_value_heads"] * head_dim(cfg)


def operator_small_params(cfg: dict, kind: str) -> int:
    """Its vectors: the convolution's taps, or the two head norms."""
    return cfg["conv_L_cache"] * cfg["hidden_size"] if kind == "conv" \
        else 2 * head_dim(cfg)


def feed_forward_matmul_params(cfg: dict, layer: int) -> int:
    """What every token meets after the operator, outside the routed
    experts: the dense MLP's three matrices, or the router."""
    d = cfg["hidden_size"]
    if layer < cfg["num_dense_layers"]:
        return 3 * d * cfg["intermediate_size"]
    return d * routed_over(cfg)


def dense_params(cfg: dict, small: bool = True) -> int:
    """Every parameter of the layers outside the routed experts (with
    `small`: the vectors too: norms, taps, head norms, selection bias)."""
    total = 0
    for i, kind in enumerate(kinds(cfg)):
        total += operator_matmul_params(cfg, kind) \
            + feed_forward_matmul_params(cfg, i)
        if small:
            total += operator_small_params(cfg, kind) + 2 * cfg["hidden_size"] \
                + (routed_over(cfg) if i >= cfg["num_dense_layers"] else 0)
    return total


def param_count(cfg: dict, tied_head: bool = False) -> int:
    """Parameters held here. The program's head is a weight of its own;
    `tied_head`: the published count, the head tied to the embedding."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return (1 if tied_head else 2) * v * d + d + dense_params(cfg) \
        + expert_layers(cfg) * cfg["num_experts"] * expert_params(cfg)


def matmul_params_per_token(cfg: dict) -> float:
    """Multiplied parameters a token meets here: the EXPECTED share of its
    top-k experts is held / routed over."""
    routed = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / routed_over(cfg) * expert_params(cfg)
    return dense_params(cfg, small=False) + expert_layers(cfg) * routed \
        + cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs a token needs: 6 a multiplied parameter and
    the attention layers' scores and values over the full square (the MFU
    convention, as harness/flops.py counts GPT-2), times 3 for forward +
    backward."""
    attn = kinds(cfg).count("full_attention") * 2 * 2 * seq * cfg["hidden_size"]
    return 6.0 * matmul_params_per_token(cfg) + 3.0 * attn


def state_bytes_per_slot(cfg: dict) -> int:
    """The convolution layers' state of one slot: the last conv_L_cache - 1
    gated inputs, in the weights' type."""
    return kinds(cfg).count("conv") * (cfg["conv_L_cache"] - 1) \
        * cfg["hidden_size"] * BF16


def kv_bytes_per_token(cfg: dict) -> int:
    return kinds(cfg).count("full_attention") * 2 \
        * cfg["num_key_value_heads"] * head_dim(cfg) * BF16


def live_slots(cfg: dict, counters: dict) -> float:
    """Live slots of a decode step from its own counter: routed pairs /
    (k * expert layers)."""
    return counters["moe_routed_pairs"] / (cfg["num_experts_per_tok"]
                                           * expert_layers(cfg))


def decode_step_need(cfg: dict, system: dict, traffic: dict,
                     counters: dict) -> dict:
    """The least one decode step must READ and WRITE, as bytes, from the
    step's own counters (means over the steps read): every weight outside
    the embedding and the routed experts once (the head among them), the
    live slots' embedding rows, the held experts that received a row
    (`moe_experts_hit`, summed over the layers) once each, the convolution
    state the live slots read and wrote (`ssm_state_bytes`, summed over the
    layers), and the K/V of the live context, counted at the shortest
    prompt the traffic sends (a floor: it is what is surely there). A LOWER
    bound: whatever the program reads beyond this is not needed."""
    d = cfg["hidden_size"]
    live = live_slots(cfg, counters)
    dense = dense_params(cfg) + d + d * cfg["vocab_size"]
    experts = counters["moe_experts_hit"] * expert_params(cfg)
    floor_context = int(traffic["prompt_len"]["min"])
    return {"flops": 0.0,
            "bytes": float(BF16 * (dense + experts + live * d)
                           + counters["ssm_state_bytes"]
                           + live * floor_context * kv_bytes_per_token(cfg))}


def moe_decode_need(cfg: dict, system: dict, traffic: dict,
                    counters: dict) -> dict:
    """What the expert layers' grouped products need in ONE decode step:
    every expert that received a row read once (its three matrices; the
    rows themselves are a few KB). `counters` are means per decode WINDOW
    (readers/scope_roofline.py): `moe_experts_hit`, the steps' own counter
    summed over the layers and the window's steps, over `steps`."""
    hit = counters["moe_experts_hit"] / counters["steps"]
    return {"flops": 0.0, "bytes": float(hit * expert_params(cfg) * BF16)}


def prefill_wave_need(cfg: dict, system: dict, traffic: dict,
                      counters: dict) -> dict:
    """Matmul FLOPs of one padded prefill wave `[slots, serve_positions]`:
    every position through the operators' projections, the dense MLP and
    the router; the routed experts by the rows the wave's own counter says
    were routed here (`moe_held_pairs`, summed over the layers: the pairs of
    the tokens that exist), not positions x k; attention under the
    diagonal; the head on each slot's last row alone."""
    slots = int(system["max_batch_slots"])
    seq = int(cfg["assumed"]["serve_positions"])
    positions = slots * seq
    attn = kinds(cfg).count("full_attention") * slots * 2 * 2 \
        * (seq * (seq + 1) // 2) * cfg["num_attention_heads"] * head_dim(cfg)
    return {"flops": float(2 * positions * dense_params(cfg, small=False)
                           + 2 * counters["moe_held_pairs"] * expert_params(cfg)
                           + attn
                           + 2 * slots * cfg["hidden_size"] * cfg["vocab_size"]),
            "bytes": 0.0}
