"""What AFMoE decoders need, from a configuration file's keys (Hugging Face
`afmoe` names; `num_experts` the routed experts HELD here, the router's width
`published.num_experts`) and from the counters a training step reports
(flexflow_tpu's `fit/step_stats` span: means a step).

FLOPs are matrix FLOPs: 2 a multiply-add of a product, forward, and twice
that again backward (x 3); no recomputation is counted, no elementwise work.
Attention's two products count the (query, key) pairs a query may SEE: the
band of a sliding layer, the triangle of a full one, never the square."""

from __future__ import annotations

BF16 = 2
PEAK_PRODUCTS_FWD, PEAK_PRODUCTS_BWD = 2, 5     # QK^T, PV | QK^T, dV, dP, dQ, dK


def router_width(cfg: dict) -> int:
    return int(cfg.get("published", {}).get("num_experts", cfg["num_experts"]))


def expert_params(cfg: dict) -> int:
    """One expert, routed or shared: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_matmul_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 3 * d * cfg["num_attention_heads"] * hd \
        + 2 * d * cfg["num_key_value_heads"] * hd


def layer_params(cfg: dict, dense: bool, experts: int) -> int:
    """A layer's parameters with `experts` routed experts: attention (W_q,
    W_k, W_v, W_o, W_g), the head norms, four norms, and the dense MLP or
    router + bias + shared experts + routed experts."""
    d = cfg["hidden_size"]
    own = attention_matmul_params(cfg) + 2 * cfg["head_dim"] + 4 * d
    if dense:
        return own + 3 * d * cfg["intermediate_size"]
    width = router_width(cfg)
    return own + d * width + width \
        + (cfg["num_shared_experts"] + experts) * expert_params(cfg)


def param_count(cfg: dict, published: bool = False) -> int:
    """Parameters of the configuration as held here (the selection biases
    among them), or with `published` of the model the file was cut from."""
    pub = cfg.get("published", {}) if published else {}
    layers = int(pub.get("num_hidden_layers", cfg["num_hidden_layers"]))
    dense = int(pub.get("num_dense_layers", cfg["num_dense_layers"]))
    experts = int(pub.get("num_experts", cfg["num_experts"]))
    vocab = int(pub.get("vocab_size", cfg["vocab_size"]))
    d = cfg["hidden_size"]
    return 2 * vocab * d + d + dense * layer_params(cfg, True, 0) \
        + (layers - dense) * layer_params(cfg, False, experts)


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def dense_matmul_params_per_token(cfg: dict) -> float:
    """Parameters EVERY token is multiplied with: attention's five, the
    dense MLPs, each expert layer's router and shared experts, the head."""
    d = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * attention_matmul_params(cfg) \
        + cfg["num_dense_layers"] * 3 * d * cfg["intermediate_size"] \
        + expert_layers(cfg) * (d * router_width(cfg)
                                + cfg["num_shared_experts"]
                                * expert_params(cfg)) \
        + d * cfg["vocab_size"]


def keys_seen(cfg: dict, kind: str, seq: int) -> int:
    """(query, key) pairs of one head of one sequence of `seq`."""
    out = max(0, seq - cfg["sliding_window"]) \
        if kind == "sliding_attention" else 0
    return seq * (seq + 1) // 2 - out * (out + 1) // 2


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs a token needs, with the EXPECTED share of a
    token's k experts that is held here (k * held / width)."""
    held = cfg["num_experts_per_tok"] * cfg["num_experts"] / router_width(cfg)
    pairs = sum(keys_seen(cfg, kind, seq) for kind in cfg["layer_types"])
    attend = PEAK_PRODUCTS_FWD * 2 * cfg["num_attention_heads"] \
        * cfg["head_dim"] * pairs / seq
    return 3.0 * (2.0 * (dense_matmul_params_per_token(cfg)
                         + expert_layers(cfg) * held * expert_params(cfg))
                  + attend)


def _attend_flops(cfg: dict, pairs: float, products: int) -> float:
    """`pairs`: (query, key) pairs summed over heads, rows and layers."""
    return products * 2.0 * cfg["head_dim"] * pairs


def train_step_need(cfg: dict, system: dict, traffic: dict,
                    counters: dict) -> dict:
    """The matrix FLOPs of `counters["steps"]` training steps from the steps'
    own counters (means a step): the held (token, expert) pairs that were
    routed, the pairs under the band and the triangle; forward x 3."""
    tokens = traffic["global_batch"] * cfg["assumed"]["train_positions"]
    forward = 2.0 * (tokens * dense_matmul_params_per_token(cfg)
                     + counters["moe_held_pairs"] * expert_params(cfg)) \
        + _attend_flops(cfg, counters["window_keys_seen"]
                        + counters["full_keys_seen"], PEAK_PRODUCTS_FWD)
    return {"flops": counters["steps"] * 3.0 * forward, "bytes": 0.0}


def _attend_train_need(cfg: dict, traffic: dict, kind: str,
                       pairs: float) -> dict:
    layers = sum(k == kind for k in cfg["layer_types"])
    rows = traffic["global_batch"] * cfg["assumed"]["train_positions"]
    q = rows * cfg["num_attention_heads"] * cfg["head_dim"] * BF16
    kv = rows * cfg["num_key_value_heads"] * cfg["head_dim"] * BF16
    lse = rows * cfg["num_attention_heads"] * 4
    # forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    # writes dq, dk, dv; the f32 log-sum-exp row written once and read once
    return {"flops": _attend_flops(cfg, pairs,
                                   PEAK_PRODUCTS_FWD + PEAK_PRODUCTS_BWD),
            "bytes": float(layers * (6 * q + 6 * kv + 2 * lse))}


def window_attend_train_need(cfg: dict, system: dict, traffic: dict,
                             counters: dict) -> dict:
    """What the sliding layers' attention needs in ONE training step: two
    products forward and five backward over the band's pairs."""
    return _attend_train_need(cfg, traffic, "sliding_attention",
                              counters["window_keys_seen"])


def full_attend_train_need(cfg: dict, system: dict, traffic: dict,
                           counters: dict) -> dict:
    """The same for the full layers, over the triangle's pairs."""
    return _attend_train_need(cfg, traffic, "full_attention",
                              counters["full_keys_seen"])


def moe_experts_train_need(cfg: dict, system: dict, traffic: dict,
                           counters: dict) -> dict:
    """What the routed experts need in ONE training step: each held pair's
    three products, forward and twice backward; every held expert's
    matrices read once a pass."""
    held = expert_layers(cfg) * cfg["num_experts"] * expert_params(cfg)
    return {"flops": 3.0 * 2.0 * counters["moe_held_pairs"]
            * expert_params(cfg), "bytes": float(3 * BF16 * held)}
