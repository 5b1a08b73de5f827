"""Rotary position embeddings, for whichever op rotates something.

Two layouts of a rotated axis of even width `dim`, `dim // 2` pairs each
turned by its own angle `position * f_i`, `f_i = base^(-2i/dim)`:

- interleaved (DeepSeek's latent attention): the pairs are (2i, 2i + 1):
  `apply_rope`, with tables that hold each pair's angle twice in a row;
- rotate-half (Hugging Face's Llama / Qwen layout): the pairs are (i, i +
  dim // 2): `apply_rope_half`, with tables that hold the `dim // 2` angles
  once and then again (`half_tables`).

Positions of several axes (a vision-language model's time, height, width;
text gives all three the same number): `half_tables(.., sections=)` turns
the first `sections[0]` pairs by axis 0's position, the next `sections[1]`
by axis 1's, and so on (Qwen2-VL's `mrope_section`, the sections laid in a
row: no interleaving).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def inv_freq(dim: int, base: float) -> np.ndarray:
    """The frequencies of the `dim // 2` pairs, `[dim // 2]` float64."""
    return base ** (-2.0 * np.arange(dim // 2, dtype=np.float64) / dim)


def yarn_inv_freq(dim: int, base: float, factor: float = 1.0,
                  original_len: int = 4096, beta_fast: float = 32,
                  beta_slow: float = 1) -> np.ndarray:
    """The rotary frequencies of the `dim // 2` pairs, `[dim // 2]` float64:
    f_i = base^(-2i/dim), and under YaRN (factor > 1) pairs that turn fewer
    than `beta_slow` times over the original length are slowed by `factor`,
    pairs that turn more than `beta_fast` times are kept, and those between
    are blended along a linear ramp over the pair index."""
    f = inv_freq(dim, base)
    if factor <= 1:
        return f
    low, high = yarn_correction_range(dim, base, original_len, beta_fast,
                                      beta_slow)
    i = np.arange(dim // 2, dtype=np.float64)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + f / factor * ramp


def yarn_correction_range(dim: int, base: float, original_len: int,
                          beta_fast: float = 32, beta_slow: float = 1):
    """(low, high): the pair indices between which YaRN's ramp runs: pairs
    up to `low` keep their frequency, pairs from `high` on turn `factor`
    times slower."""
    def pair_turning(n):    # the (real) pair index that turns n times
        return dim * math.log(original_len / (2 * math.pi * n)) \
            / (2 * math.log(base))

    return (max(math.floor(pair_turning(beta_fast)), 0),
            min(math.ceil(pair_turning(beta_slow)), dim - 1))


def yarn_attention_factor(scaling: dict) -> float:
    """What YaRN multiplies cos and sin with: the configuration's
    `attention_factor`, else 0.1 ln(factor) + 1."""
    given = scaling.get("attention_factor")
    factor = float(scaling.get("factor", 1.0))
    return float(given) if given is not None else \
        (0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0)


def apply_rope(x, cos, sin):
    """Rotates the pairs (2i, 2i+1) of x's last axis by their angles:
    (a, b) -> (a cos - b sin, a sin + b cos), in f32, result in x's dtype.
    The pairs' partners come from one product with a signed permutation
    (exact: each output is one input, times +-1), so the interleaved axis is
    never split into pairs, which the chip would relay."""
    dr = x.shape[-1]
    swap = np.zeros((dr, dr), np.float32)
    swap[np.arange(1, dr, 2), np.arange(0, dr, 2)] = -1.0   # out[2i] = -x[2i+1]
    swap[np.arange(0, dr, 2), np.arange(1, dr, 2)] = 1.0    # out[2i+1] = x[2i]
    xf = x.astype(jnp.float32)
    partner = jnp.einsum("...d,de->...e", xf, jnp.asarray(swap),
                         precision=jax.lax.Precision.HIGHEST)
    return (xf * cos + partner * sin).astype(x.dtype)


def half_tables(positions, dim: int, base: float, sections=None,
                scaling=None):
    """(cos, sin) `[..., dim]` float32 for `apply_rope_half`: the `dim // 2`
    angles, then the same again. `positions` `[...]`, or with `sections`
    (whole numbers that sum to `dim // 2`) `[..., len(sections)]`: pair i
    is turned by the position of the axis whose section holds i. `scaling`:
    YaRN, by Hugging Face's rule (`{"factor", "original_max_position_
    embeddings", "beta_fast", "beta_slow", "attention_factor"}`): the
    frequencies are `yarn_inv_freq`'s and cos and sin are multiplied with
    the attention factor (so a score carries its square)."""
    if scaling:
        inv = yarn_inv_freq(
            dim, base, float(scaling["factor"]),
            int(scaling["original_max_position_embeddings"]),
            scaling.get("beta_fast", 32), scaling.get("beta_slow", 1))
    else:
        inv = inv_freq(dim, base)
    inv = jnp.asarray(np.tile(inv, 2), jnp.float32)
    pos = positions.astype(jnp.float32)[..., None]
    if sections is not None:
        if sum(sections) != dim // 2 or \
                positions.shape[-1] != len(sections):
            raise ValueError(f"rotary sections {list(sections)} over "
                             f"{dim // 2} pairs and positions "
                             f"{positions.shape}")
        # each pair's own axis, by selection (exact: no product)
        half = jnp.concatenate(
            [jnp.broadcast_to(pos[..., a, :], pos.shape[:-2] + (n,))
             for a, n in enumerate(sections)], axis=-1)
        pos = jnp.concatenate([half, half], axis=-1)
    angles = pos * inv
    if scaling:
        m = jnp.float32(yarn_attention_factor(scaling))
        return jnp.cos(angles) * m, jnp.sin(angles) * m
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope_half(x, cos, sin):
    """Rotates the pairs (i, i + dim // 2) of x's last axis: x cos +
    rotate_half(x) sin with rotate_half(x) = [-x_2 | x_1], in f32, result in
    x's dtype. The partner is the axis rolled by half its width (whole
    lanes for a head of 128), signed."""
    dim = x.shape[-1]
    sign = jnp.asarray(np.where(np.arange(dim) < dim // 2, -1.0, 1.0),
                       jnp.float32)
    xf = x.astype(jnp.float32)
    return (xf * cos + jnp.roll(xf, dim // 2, axis=-1) * sign * sin
            ).astype(x.dtype)
