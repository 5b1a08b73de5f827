"""Operations and bytes the algorithm NEEDS for the `brumby` family, from
shapes and the program's own counters alone: the work of the equations
(harness/reference_brumby.py), whatever implements it. `cfg` is a
configuration file's dict (Hugging Face brumby keys); `system` is the
cell's workloads/<cell>.json and `traffic` its traffic parameters."""

from __future__ import annotations

BF16 = 2
F32 = 4


def state_rows(cfg: dict) -> int:
    """P: the rows of a K/V head's state, the symmetric half of D x D
    (8256 at D = 128)."""
    return cfg["head_dim"] * (cfg["head_dim"] + 1) // 2


def mixer_params(cfg: dict) -> int:
    """A retention layer's matrices: W_q, W_k, W_v, W_g and W_o."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * (heads * hd + 2 * kv * hd + kv) + heads * hd * d


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_params(cfg: dict, small: bool = True) -> int:
    """One block (`small`: with the vectors: the q and k norms a head and
    the two RMS norms)."""
    return mixer_params(cfg) + mlp_params(cfg) \
        + (2 * cfg["head_dim"] + 2 * cfg["hidden_size"] if small else 0)


def param_count(cfg: dict, layers: int = None) -> int:
    """Parameters of `layers` blocks (the file's own depth), an untied
    embedding and head over the whole vocabulary, the final norm."""
    d = cfg["hidden_size"]
    n = cfg["num_hidden_layers"] if layers is None else layers
    return 2 * cfg["vocab_size"] * d + d + n * layer_params(cfg)


def matmul_params_per_token(cfg: dict) -> float:
    return cfg["num_hidden_layers"] * layer_params(cfg, small=False) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def retention_flops_per_token(cfg: dict) -> int:
    """The recurrence's own products, one retention layer, forward: a K/V
    head's state decays (one product an entry) and takes a rank-one update
    in (two), a query head reads it out (two), P x D entries each, and the
    normaliser's P-long twins."""
    return (3 * cfg["num_key_value_heads"] + 2 * cfg["num_attention_heads"]) \
        * state_rows(cfg) * (cfg["head_dim"] + 1)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs a token needs: 6 a multiplied parameter and
    the recurrence's own products times 3."""
    return 6.0 * matmul_params_per_token(cfg) \
        + 3.0 * cfg["num_hidden_layers"] * retention_flops_per_token(cfg)


def state_bytes_per_slot(cfg: dict) -> int:
    """Recurrent state of one slot, all layers: S `[J, P, D]` and z `[J,
    P]`, float32 (34.08 MB a layer at the published widths)."""
    return cfg["num_hidden_layers"] * cfg["num_key_value_heads"] \
        * state_rows(cfg) * (cfg["head_dim"] + 1) * F32


def decode_step_need(cfg: dict, system: dict, traffic: dict,
                     counters: dict) -> dict:
    """The least one decode step must READ and WRITE, as bytes, from the
    step's own counter (a mean over the steps read): every weight outside
    the embedding once (the head among them), the live slots' embedding
    rows, and the state the live slots read and wrote
    (`linear_state_bytes`, summed over the layers). Live slots = that
    counter over a slot's state twice. A LOWER bound: whatever the program
    reads beyond this is not needed."""
    d = cfg["hidden_size"]
    live = counters["linear_state_bytes"] / (2.0 * state_bytes_per_slot(cfg))
    weights = param_count(cfg) - cfg["vocab_size"] * d
    return {"flops": 0.0,
            "bytes": float(BF16 * (weights + live * d)
                           + counters["linear_state_bytes"])}


def retention_wave_flops_per_token(cfg: dict, seq: int) -> float:
    """The LEAST one retention layer needs a position of a row of `seq`
    positions that starts from an empty state and hands its state out: the
    recurrence's own products, or (fewer, under a few thousand positions)
    the pair form under the diagonal (scores and values, 4 D a pair and
    query head, (seq + 1) / 2 pairs a position) and the state built once
    (2 P (D + 1) a K/V head), whichever is less. Whatever a program
    multiplies beyond this is its form's price."""
    hd = cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pairs = heads * 4 * hd * (seq + 1) / 2.0 \
        + kv * 2 * state_rows(cfg) * (hd + 1)
    return min(float(retention_flops_per_token(cfg)), pairs)


def prefill_wave_need(cfg: dict, system: dict, traffic: dict,
                      counters: dict) -> dict:
    """Matmul FLOPs of one padded prefill wave `[slots, serve_positions]` as
    run: every position through the projections and the MLPs; the retention
    layers' least (`retention_wave_flops_per_token`) over the rows the
    wave's own counter says were computed (`retention_rows`, summed over
    the layers: a row that holds no request is skipped); the head on each
    slot's last row alone."""
    slots = int(system["max_batch_slots"])
    seq = int(cfg["assumed"]["serve_positions"])
    positions = slots * seq
    layers = cfg["num_hidden_layers"]
    return {"flops": float(
        2 * positions * layers * layer_params(cfg, small=False)
        + counters["retention_rows"] * seq
        * retention_wave_flops_per_token(cfg, seq)
        + 2 * slots * cfg["hidden_size"] * cfg["vocab_size"]),
        "bytes": 0.0}


def retention_scan_need(cfg: dict, system: dict, traffic: dict,
                        counters: dict) -> dict:
    """What the retention layers' sequence form of one padded prefill wave
    needs, all layers, over the rows it computed (`retention_rows`, the
    wave's own counter summed over the layers): the least products a
    position (`retention_wave_flops_per_token`), and as bytes q, k, v read
    and y written in the compute type, the gate, and a row's f32 state
    written once. The projections, the norms and the rotary embedding are
    not the scan's."""
    seq = int(cfg["assumed"]["serve_positions"])
    hd = cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per_position = (2 * heads + 2 * kv) * hd * BF16 + kv * F32
    state = state_bytes_per_slot(cfg) / cfg["num_hidden_layers"]
    rows = counters["retention_rows"]
    return {"flops": float(rows * seq
                           * retention_wave_flops_per_token(cfg, seq)),
            "bytes": float(rows * (seq * per_position + state))}


def retention_step_need(cfg: dict, system: dict, traffic: dict,
                        counters: dict) -> dict:
    """What the retention layers need in ONE decode step: the live slots'
    state read and written once, and the recurrence's products on it.
    `counters` are means per decode WINDOW (readers/scope_roofline.py):
    `linear_state_bytes`, the steps' own counter summed over the layers and
    the window's steps, over `steps`."""
    per_step = counters["linear_state_bytes"] / counters["steps"]
    live = per_step / (2.0 * state_bytes_per_slot(cfg))
    return {"flops": float(live * cfg["num_hidden_layers"]
                           * retention_flops_per_token(cfg)),
            "bytes": float(per_step)}
