"""Operations and bytes the algorithm NEEDS for the `keye_vl` family, from
shapes and the program's own counters alone: the work of the equations
(harness/reference_keye_vl.py), whatever implements it. `cfg` is a
configuration file's dict (the published KeyeVL2 keys; `num_experts` is the
experts held here); `system` is the cell's workloads/<cell>.json and
`traffic` its traffic parameters.

The sparse counters are sums over the layers that report: `sparse_keys_live`
the keys s <= t a real query could keep, `sparse_keys_kept` those it kept
(min(t + 1, topk)), `indexer_cache_bytes_read` the cached indexer keys a real
query's scores read (64 values of 2 bytes each: what the equations read, not
the 128 lanes a row lies in), `kv_bytes_gathered` the K and V rows its
attention read."""

from __future__ import annotations

BF16 = 2


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def expert_params(cfg: dict) -> int:
    """One routed expert: W_1, W_3 [d, w] and W_2 [w, d]."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_matmul_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * cfg["num_attention_heads"] * hd \
        + 2 * d * cfg["num_key_value_heads"] * hd


def indexer_matmul_params(cfg: dict) -> int:
    sa = cfg["sa_config"]
    return cfg["hidden_size"] * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def layer_dense_params(cfg: dict, small: bool = True) -> int:
    """A layer's parameters outside the routed experts: attention's four
    matrices, the indexer's three, the router (with `small`: the two norms,
    the head norms, the indexer key's norm and bias)."""
    sa = cfg["sa_config"]
    return attention_matmul_params(cfg) + indexer_matmul_params(cfg) \
        + cfg["hidden_size"] * cfg["num_experts"] \
        + (2 * cfg["hidden_size"] + 2 * cfg["head_dim"]
           + 2 * sa["indexer_head_dim"] if small else 0)


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
    attention's and the indexer's scores over the full square (the MFU
    convention, as harness/flops.py counts GPT-2), times 3 for forward +
    backward."""
    sa = cfg["sa_config"]
    square = 2 * 2 * seq * cfg["num_attention_heads"] * cfg["head_dim"] \
        + 2 * seq * sa["indexer_num_heads"] * sa["indexer_head_dim"]
    return 6.0 * matmul_params_per_token(cfg) + 3.0 * layers(cfg) * square


def cache_bytes_per_token(cfg: dict) -> int:
    """What the equations keep of a token, all layers: K, V, the indexer's
    key."""
    return layers(cfg) * BF16 * (
        2 * cfg["num_key_value_heads"] * cfg["head_dim"]
        + cfg["sa_config"]["indexer_head_dim"])


def chunk_tokens(cfg: dict, counters: dict) -> float:
    """Real tokens of a prefill chunk from its own counter: the pairs of
    tokens that exist / (k * layers)."""
    return counters["moe_held_pairs"] / (cfg["num_experts_per_tok"]
                                         * layers(cfg))


def prefill_chunk_need(cfg: dict, system: dict, traffic: dict,
                       counters: dict) -> dict:
    """Matmul FLOPs of one prefill chunk, from the chunk's own counters
    (means a chunk): its real tokens through attention's and the indexer's
    projections and the router; the routed experts by the pairs of tokens
    that exist (`moe_held_pairs`); the indexer's scores of the keys a real
    query could keep (`sparse_keys_live`: s <= t, the cached context
    included); attention's scores and values over the keys it KEPT
    (`sparse_keys_kept`), not over the masked square; the head on one row. A
    program that runs attention dense under the mask does more than this
    and reads low here: that is the finding, not a fault of the count."""
    sa = cfg["sa_config"]
    tokens = chunk_tokens(cfg, counters)
    return {"flops": float(
        2 * tokens * layers(cfg) * layer_dense_params(cfg, small=False)
        + 2 * counters["moe_held_pairs"] * expert_params(cfg)
        + 2 * counters["sparse_keys_live"]
        * sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + 2 * 2 * counters["sparse_keys_kept"]
        * cfg["num_attention_heads"] * cfg["head_dim"]
        + 2 * cfg["hidden_size"] * cfg["vocab_size"]), "bytes": 0.0}


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
    (`moe_experts_hit`, summed over the layers) once each, the live
    context's indexer keys (`indexer_cache_bytes_read`) and the K and V rows
    of the keys kept (`kv_bytes_gathered`). A LOWER bound: whatever the
    program reads beyond this (the rows' other 64 lanes, a dead slot's
    pages) is not needed."""
    d = cfg["hidden_size"]
    dense = layers(cfg) * layer_dense_params(cfg) + d + d * cfg["vocab_size"]
    return {"flops": 0.0,
            "bytes": float(BF16 * (dense + live_slots(cfg, counters) * d
                                   + counters["moe_experts_hit"]
                                   * expert_params(cfg))
                           + counters["indexer_cache_bytes_read"]
                           + counters["kv_bytes_gathered"])}


def sparse_index_need(cfg: dict, system: dict, traffic: dict,
                      counters: dict) -> dict:
    """What the indexers' scores need in ONE decode step: the live slots'
    cached indexer keys read once. `counters` are means per decode WINDOW
    (readers/scope_roofline.py), over its `steps`."""
    return {"flops": 0.0, "bytes": float(
        counters["indexer_cache_bytes_read"] / counters["steps"])}


def sparse_attend_need(cfg: dict, system: dict, traffic: dict,
                       counters: dict) -> dict:
    """What attention over the kept keys needs in ONE decode step: the K and
    V rows of each live slot's kept keys read once."""
    return {"flops": 0.0, "bytes": float(
        counters["kv_bytes_gathered"] / counters["steps"])}


def moe_decode_need(cfg: dict, system: dict, traffic: dict,
                    counters: dict) -> dict:
    """What the expert layers need in ONE decode step: every expert that
    received a row read once (its three matrices; the rows themselves are a
    few KB). `moe_experts_hit` is the steps' own counter summed over the
    layers and the window's steps, over `steps`."""
    hit = counters["moe_experts_hit"] / counters["steps"]
    return {"flops": 0.0, "bytes": float(hit * expert_params(cfg) * BF16)}
