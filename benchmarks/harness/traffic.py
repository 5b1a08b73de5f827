"""The one general load generator. A traffic mix is a data file
(traffic/<name>.json); this module turns its parameters and a seed into the
inputs of a run. The program under test sees only the generated inputs.

Every seed gets the SAME schedule: arrival offsets and the prompt and answer
length of each arrival are drawn from the mix's own `shape_seed`; --seed draws
the token ids (and, in the cell, the weights). So the work of a run is fixed
by the mix and the window. Why not the same lengths in another order for each
seed: at the rates this system sustains a window holds 10-20 requests, and
which request a prefill wave stalls is then decided by the order; measured on
the chip (PERF.md, Findings, PR 24), tpot_p95_ms moved 36 % between orders of
one multiset against 1 % between two runs of one order.

The generator knows what the mixes in traffic/ use: log-normal lengths and
Poisson arrivals. A mix that needs another law brings it in its own PR.
"""

from __future__ import annotations

import numpy as np

STRIDES = np.array([1, 2, 3, 5, 7, 11, 13, 17])


def _rng(seed: int, stream: int) -> np.random.Generator:
    # --seed may be any whole number a little over 2**31
    return np.random.default_rng([int(seed) % (2 ** 63), stream])


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """`n` whole-number lengths, log-normal around `median` with `sigma`,
    clipped to [min, max]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrival_times(spec: dict, n: int, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """`n` arrival offsets inside [0, seconds): exponential gaps, a Poisson
    process given its count."""
    if spec["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['arrivals']!r}")
    gaps = rng.exponential(1.0, size=n + 1)
    return np.cumsum(gaps)[:n] / gaps.sum() * seconds


def serve_requests(traffic: dict, seconds: float, seed: int, vocab: int,
                   rate_scale: float = 1.0) -> list:
    """The requests of one serving window as plain dicts
    {rid, arrival_s, prompt (token ids), max_new_tokens}, sorted by arrival."""
    n = max(1, int(round(traffic["rate_rps"] * rate_scale * seconds)))
    shape = _rng(traffic["shape_seed"], 0)
    prompt_len = draw_lengths(traffic["prompt_len"], n, shape)
    output_len = draw_lengths(traffic["output_len"], n, shape)
    arrivals = arrival_times(traffic, n, seconds, shape)
    tokens = _rng(seed, 2)
    return [{"rid": i, "arrival_s": float(arrivals[i]),
             "prompt": tokens.integers(1, vocab, size=int(prompt_len[i])
                                       ).astype(np.int32).tolist(),
             "max_new_tokens": int(output_len[i])}
            for i in range(n)]


def length_quantiles(values) -> dict:
    v = np.asarray(values, dtype=np.float64)
    q = np.percentile(v, [5, 50, 95])
    return {"n": int(v.size), "min": float(v.min()), "p5": float(q[0]),
            "median": float(q[1]), "p95": float(q[2]), "max": float(v.max()),
            "sum": float(v.sum())}


def stride_dataset(vocab: int, seq: int, n: int, seed: int):
    """`n` sequences of a learnable next-token task (chip_smoke.py's, copied):
    each walks the vocabulary with one of eight fixed strides, so a few Adam
    steps already lower the loss. Returns ([ids, positions], labels)."""
    rng = _rng(seed, 3)
    start = rng.integers(0, vocab, size=(n, 1))
    stride = rng.choice(STRIDES, size=(n, 1))
    walk = (start + stride * np.arange(seq + 1)[None, :]) % vocab
    ids = walk[:, :-1].astype(np.int32)
    labels = walk[:, 1:].astype(np.int32)
    pos = np.tile(np.arange(seq, dtype=np.int32), (n, 1))
    return [ids, pos], labels
