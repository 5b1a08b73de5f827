"""Operations and bytes the algorithm NEEDS for the `jamba` family, from
shapes and the program's own counters alone: the work of the equations
(harness/reference_jamba.py), whatever implements it. `cfg` is a
configuration file's dict (the published `jamba` keys); `system` is the
cell's workloads/<cell>.json and `traffic` its traffic parameters.

Counters: `mamba_rows` the real positions a prefill chunk scanned, summed
over its `mamba_layers` Mamba layers; `full_keys_seen` the (query, key) pairs
of the queries that exist and the keys each may see, summed over the
attention layers; `full_kv_bytes_needed` the K and V rows a live slot's
queries may see (a step: `pos + 1` rows of 512 B in each attention layer);
`ssm_state_bytes` the recurrent state the live slots' steps read and wrote
(both leaves, twice)."""

from __future__ import annotations

BF16 = 2
F32 = 4


def layer_kinds(cfg: dict) -> tuple:
    """The family's rule: attention where `l % period == offset`."""
    return tuple(
        "attention" if l % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
        else "mamba" for l in range(cfg["num_hidden_layers"]))


def layers_of(cfg: dict, kind: str) -> int:
    return layer_kinds(cfg).count(kind)


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def mamba_matmul_params(cfg: dict) -> int:
    """in_proj, x_proj, dt_proj, out_proj."""
    d, c = cfg["hidden_size"], d_inner(cfg)
    r, n = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    return d * 2 * c + c * (r + 2 * n) + r * c + c * d


def mamba_small_params(cfg: dict) -> int:
    """conv weights and bias, dt_bias and D, A_log, the three inner norms."""
    c, n = d_inner(cfg), cfg["mamba_d_state"]
    return cfg["mamba_d_conv"] * c + 3 * c + n * c \
        + cfg["mamba_dt_rank"] + 2 * n


def attention_matmul_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return 2 * d * d + 2 * d * cfg["num_key_value_heads"] * head_dim(cfg)


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def matmul_params_per_token(cfg: dict) -> float:
    """Multiplied parameters a token meets (the head among them)."""
    return layers_of(cfg, "mamba") * mamba_matmul_params(cfg) \
        + layers_of(cfg, "attention") * attention_matmul_params(cfg) \
        + cfg["num_hidden_layers"] * mlp_params(cfg) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def param_count(cfg: dict) -> int:
    """Parameters held here (the head is a weight of its own)."""
    d = cfg["hidden_size"]
    return 2 * cfg["vocab_size"] * d + d \
        + layers_of(cfg, "mamba") * (mamba_matmul_params(cfg)
                                     + mamba_small_params(cfg)) \
        + layers_of(cfg, "attention") * attention_matmul_params(cfg) \
        + cfg["num_hidden_layers"] * (mlp_params(cfg) + 2 * d)


def scan_flops_per_token(cfg: dict) -> float:
    """The recurrence's own multiply-adds of one Mamba layer and token: the
    decay's product, the input's two, the update and the read-out, 4 a
    channel and state index, 2 operations each."""
    return 8.0 * d_inner(cfg) * cfg["mamba_d_state"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs a token needs: 6 a multiplied parameter,
    attention's scores and values over the full square (the MFU convention,
    as harness/flops.py counts GPT-2) and the recurrence's own, times 3."""
    square = layers_of(cfg, "attention") * 2 * 2 * seq * cfg["hidden_size"]
    scan = layers_of(cfg, "mamba") * scan_flops_per_token(cfg)
    return 6.0 * matmul_params_per_token(cfg) + 3.0 * (square + scan)


def state_bytes_per_slot(cfg: dict) -> int:
    """A slot's recurrent state, every Mamba layer: S `[N, C]` float32 and
    the conv tail `[d_conv - 1, C]` bf16."""
    c = d_inner(cfg)
    return layers_of(cfg, "mamba") * (
        cfg["mamba_d_state"] * c * F32 + (cfg["mamba_d_conv"] - 1) * c * BF16)


def cache_bytes_per_token(cfg: dict) -> int:
    """What the equations keep of a token in ONE attention layer: K and V."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * BF16


def chunk_tokens(cfg: dict, counters: dict) -> float:
    """Real tokens of a prefill chunk from its own counters."""
    return counters["mamba_rows"] / counters["mamba_layers"]


def attend_flops(cfg: dict, counters: dict) -> float:
    """The two attention products (q k^T and probs v) of the (query, key)
    pairs that exist, every query head."""
    return 2.0 * 2 * counters["full_keys_seen"] * cfg["hidden_size"]


def prefill_chunk_need(cfg: dict, system: dict, traffic: dict,
                       counters: dict) -> dict:
    """FLOPs of one prefill chunk, from the chunk's own counters (means a
    chunk): its real tokens through every layer's matrices, the recurrence's
    own multiply-adds of the positions scanned (`mamba_rows`), attention's
    scores and values over the keys a real query may SEE (`full_keys_seen`:
    the triangle and the context before it), the head on one row."""
    tokens = chunk_tokens(cfg, counters)
    body = matmul_params_per_token(cfg) \
        - cfg["hidden_size"] * cfg["vocab_size"]
    return {"flops": float(
        2 * tokens * body + counters["mamba_rows"] * scan_flops_per_token(cfg)
        + attend_flops(cfg, counters)
        + 2 * cfg["hidden_size"] * cfg["vocab_size"]), "bytes": 0.0}


def selective_scan_need(cfg: dict, system: dict, traffic: dict,
                        counters: dict) -> dict:
    """What the selective scans of ONE prefill chunk need: the recurrence's
    multiply-adds of the positions scanned and, what binds, its bytes: `u`,
    `dt`, `z` read and `y` written in the compute type a position and
    channel, B and C read (float32, N each a position), and the float32
    state `[N, C]` once in and once out a layer."""
    c, n = d_inner(cfg), cfg["mamba_d_state"]
    rows, layers = counters["mamba_rows"], counters["mamba_layers"]
    return {"flops": float(rows * scan_flops_per_token(cfg)),
            "bytes": float(rows * (4 * c * BF16 + 2 * n * F32)
                           + layers * 2 * n * c * F32)}


def decode_step_need(cfg: dict, system: dict, traffic: dict,
                     counters: dict) -> dict:
    """The least one decode step must READ and WRITE, as bytes, from the
    step's own counters (means over the steps read): every weight outside
    the embedding once (the head among them), the live slots' embedding
    rows, the live slots' recurrent state read and written
    (`ssm_state_bytes`), and the K and V rows the live slots' queries may
    see (`full_kv_bytes_needed`). A LOWER bound: whatever the program moves
    beyond this (a slot that is not live, a page's other rows) is not
    needed."""
    d = cfg["hidden_size"]
    weights = param_count(cfg) - cfg["vocab_size"] * d
    live = counters["ssm_state_bytes"] / (2.0 * state_bytes_per_slot(cfg))
    return {"flops": 0.0,
            "bytes": float(BF16 * (weights + live * d)
                           + counters["ssm_state_bytes"]
                           + counters["full_kv_bytes_needed"])}
