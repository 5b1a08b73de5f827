"""Operations and bytes the algorithm NEEDS for the `mellum` family, from
shapes and the program's own counters alone: the work of the equations
(harness/reference_mellum.py), whatever implements it. `cfg` is a
configuration file's dict (the published `mellum` keys; `num_hidden_layers`
and `layer_types` are of the layers built, `num_experts` the experts held
here); `system` is the cell's workloads/<cell>.json and `traffic` its traffic
parameters.

The attention counters are sums over the layers of a kind that report
(`window_*` the `sliding_attention` layers, `full_*` the `full_attention`
ones): `*_keys_seen` the (query, key) pairs of the queries that exist (a
block's real tokens, a step's live slots) and the keys each may SEE (`min(t +
1, window)` or `t + 1`), `*_kv_bytes_needed` the K and V rows a live slot's
queries may see (a step: the same keys x 2048 B; a block: from its first
query's first key to its last position, once)."""

from __future__ import annotations

BF16 = 2


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def layers_of(cfg: dict, kind: str) -> int:
    return list(cfg["layer_types"]).count(kind)


def expert_params(cfg: dict) -> int:
    """One routed expert: W_1, W_3 [d, w] and W_2 [w, d]."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_matmul_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * cfg["num_attention_heads"] * hd \
        + 2 * d * cfg["num_key_value_heads"] * hd


def layer_dense_params(cfg: dict, small: bool = True) -> int:
    """A layer's parameters outside the routed experts: attention's four
    matrices and the router (with `small`: the two norms and the head
    norms)."""
    return attention_matmul_params(cfg) \
        + cfg["hidden_size"] * cfg["num_experts"] \
        + (2 * cfg["hidden_size"] + 2 * cfg["head_dim"] if small else 0)


def param_count(cfg: dict) -> int:
    """Parameters held here (the head is a weight of its own, as published)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return 2 * v * d + d + layers(cfg) * (
        layer_dense_params(cfg) + cfg["num_experts"] * expert_params(cfg))


def matmul_params_per_token(cfg: dict) -> float:
    """Multiplied parameters a token meets here."""
    return layers(cfg) * (layer_dense_params(cfg, small=False)
                          + cfg["num_experts_per_tok"] * expert_params(cfg)) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs a token needs: 6 a multiplied parameter and
    attention's scores and values over the full square (the MFU convention,
    as harness/flops.py counts GPT-2), times 3 for forward + backward."""
    square = 2 * 2 * seq * cfg["num_attention_heads"] * cfg["head_dim"]
    return 6.0 * matmul_params_per_token(cfg) + 3.0 * layers(cfg) * square


def cache_bytes_per_token(cfg: dict) -> int:
    """What the equations keep of a token in ONE layer: K and V."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BF16


def pool_bytes(cfg: dict, slots: int, positions: int, ring: int) -> dict:
    """The cache the equations ask for, {"full", "window", "one_extent"}
    bytes: `positions` a slot in each full layer, `ring` (the window's pages)
    in each windowed one, and what one extent for every layer would be."""
    row = cache_bytes_per_token(cfg) * slots
    full = layers_of(cfg, "full_attention")
    window = layers_of(cfg, "sliding_attention")
    return {"full": full * row * positions, "window": window * row * ring,
            "one_extent": (full + window) * row * positions}


def chunk_tokens(cfg: dict, counters: dict) -> float:
    """Real tokens of a prefill chunk from its own counter: the pairs of
    tokens that exist / (k * layers)."""
    return counters["moe_held_pairs"] / (cfg["num_experts_per_tok"]
                                         * layers(cfg))


def attend_flops(cfg: dict, counters: dict) -> float:
    """The two attention products (q k^T and probs v) of the (query, key)
    pairs that exist, both kinds of layer, every query head."""
    return 2.0 * 2 * (counters["window_keys_seen"]
                      + counters["full_keys_seen"]) \
        * cfg["num_attention_heads"] * cfg["head_dim"]


def prefill_chunk_need(cfg: dict, system: dict, traffic: dict,
                       counters: dict) -> dict:
    """Matmul FLOPs of one prefill chunk, from the chunk's own counters
    (means a chunk): its real tokens through attention's projections and the
    router; the routed experts by the pairs of tokens that exist
    (`moe_held_pairs`); attention's scores and values over the keys a real
    query may SEE (`window_keys_seen`: the band, `full_keys_seen`: the
    triangle and the context before it), not over a padded rectangle; the
    head on one row."""
    tokens = chunk_tokens(cfg, counters)
    return {"flops": float(
        2 * tokens * layers(cfg) * layer_dense_params(cfg, small=False)
        + 2 * counters["moe_held_pairs"] * expert_params(cfg)
        + attend_flops(cfg, counters)
        + 2 * cfg["hidden_size"] * cfg["vocab_size"]), "bytes": 0.0}


def chunk_attend_need(cfg: dict, system: dict, traffic: dict,
                      counters: dict) -> dict:
    """What both kinds of layer's attention over the cache needs in ONE
    prefill chunk: the two products of the pairs a real query may see."""
    return {"flops": float(attend_flops(cfg, counters)), "bytes": 0.0}


def live_slots(cfg: dict, counters: dict) -> float:
    """Live slots of a decode step from its own counter: routed pairs /
    (k * layers)."""
    return counters["moe_routed_pairs"] / (cfg["num_experts_per_tok"]
                                           * layers(cfg))


def decode_step_need(cfg: dict, system: dict, traffic: dict,
                     counters: dict) -> dict:
    """The least one decode step must READ, as bytes, from the step's own
    counters (means over the steps read): every weight outside the
    embedding and the routed experts once (the head among them), the live
    slots' embedding rows, the experts that received a row
    (`moe_experts_hit`, summed over the layers) once each, and the K and V
    rows the live slots' queries may see (`window_kv_bytes_needed`: at most
    the window's, `full_kv_bytes_needed`: the context's). A LOWER bound:
    whatever the program reads beyond this (a page's other rows, a dead
    slot's pages) is not needed."""
    d = cfg["hidden_size"]
    dense = layers(cfg) * layer_dense_params(cfg) + d + d * cfg["vocab_size"]
    return {"flops": 0.0,
            "bytes": float(BF16 * (dense + live_slots(cfg, counters) * d
                                   + counters["moe_experts_hit"]
                                   * expert_params(cfg))
                           + counters["window_kv_bytes_needed"]
                           + counters["full_kv_bytes_needed"])}


def window_attend_need(cfg: dict, system: dict, traffic: dict,
                       counters: dict) -> dict:
    """What the windowed layers' attention needs in ONE decode step: the K
    and V rows of each live slot's window read once. `counters` are means
    per decode WINDOW (readers/scope_roofline.py), over its `steps`."""
    return {"flops": 0.0, "bytes": float(
        counters["window_kv_bytes_needed"] / counters["steps"])}


def full_attend_need(cfg: dict, system: dict, traffic: dict,
                     counters: dict) -> dict:
    """The same of the full layers: each live slot's whole context."""
    return {"flops": 0.0, "bytes": float(
        counters["full_kv_bytes_needed"] / counters["steps"])}


def moe_decode_need(cfg: dict, system: dict, traffic: dict,
                    counters: dict) -> dict:
    """What the expert layers need in ONE decode step: every expert that
    received a row read once (its three matrices; the rows themselves are a
    few KB). `moe_experts_hit` is the steps' own counter summed over the
    layers and the window's steps, over `steps`."""
    hit = counters["moe_experts_hit"] / counters["steps"]
    return {"flops": 0.0, "bytes": float(hit * expert_params(cfg) * BF16)}
