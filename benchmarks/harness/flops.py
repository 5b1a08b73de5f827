"""Operations and bytes the algorithm NEEDS, from shapes alone. These are
the yardstick's: a PR that claims a gain cannot change them. `cfg` is a
configuration file's dict (Hugging Face GPT-2 keys)."""

from __future__ import annotations


def d_ff(cfg: dict) -> int:
    return cfg.get("n_inner") or 4 * cfg["n_embd"]


def matmul_params(cfg: dict) -> int:
    """Parameters that are multiplied: the blocks' four attention and two
    MLP matrices, and the lm_head. Embedding tables are gathers."""
    d = cfg["n_embd"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * d_ff(cfg)) + d * cfg["vocab_size"]


def param_count(cfg: dict, tied_head: bool = False) -> int:
    d, v, s = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    block = 4 * d * d + 2 * d * d_ff(cfg) + 9 * d + d_ff(cfg)
    return v * d + s * d + cfg["n_layer"] * block + 2 * d + (0 if tied_head else d * v)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs a token needs: 6 per multiplied parameter,
    plus the attention scores and values (QK^T and PV: 2 * 2 * seq * d_model
    forward per layer, times 3 for forward + backward), over the FULL square
    as the MFU convention has it (PaLM, appendix B) and as
    GPT2Config.flops_per_token() counts. Recomputation is not counted."""
    attn = cfg["n_layer"] * 2 * 2 * seq * cfg["n_embd"]
    return 6.0 * matmul_params(cfg) + 3.0 * attn


def flash_attention_train_need(cfg: dict, batch: int, seq: int) -> dict:
    """What causal attention needs in one training step, all layers, all
    chips together: {"flops", "bytes"}.

    FLOPs: only the pairs at or under the diagonal, seq * (seq + 1) / 2 per
    head (the kernel skips masked blocks; counting the full square would let
    the share pass 100 %). Forward is two matmuls over those pairs (QK^T,
    PV); backward needs five (QK^T again, dV, dP, dQ, dK). A split dq/dkv
    backward that recomputes QK^T and dP twice does 7: the two extra are
    recomputation and do not count.
    Bytes: forward reads Q, K, V and writes O; backward reads Q, K, V, O, dO
    and writes dQ, dK, dV; in the compute type (bf16, 2 bytes), plus the f32
    log-sum-exp row written once and read once."""
    d, h, layers = cfg["n_embd"], cfg["n_head"], cfg["n_layer"]
    pairs = seq * (seq + 1) // 2
    flops_per_matmul = 2 * batch * pairs * d       # all heads: h * (d / h)
    tensor = batch * seq * d * 2
    lse = batch * h * seq * 4
    return {"flops": float(layers * 7 * flops_per_matmul),
            "bytes": float(layers * (12 * tensor + 2 * lse))}


def roofline_seconds(need: dict, peaks: dict, chips: int = 1) -> dict:
    """The least time `chips` chips could take for `need`, and which peak
    bounds it."""
    t_flops = need["flops"] / (peaks["bf16_flops_per_s"] * chips)
    t_bytes = need["bytes"] / (peaks["hbm_bytes_per_s"] * chips)
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}


def mfu(tokens_per_s: float, flops_per_token: float, peaks: dict,
        chips: int) -> float:
    return tokens_per_s * flops_per_token / (peaks["bf16_flops_per_s"] * chips)
