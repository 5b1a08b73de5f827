"""The device-side page format: how a position maps to a page and an offset,
how K/V rows are quantized and laid in a pool, and the reserved state names
the paged programs read. The ops that append to the pools, the attention
kernels' callers and the cache manager (`serving/kv_cache.py`, which owns
the pools and says why they lie as they do) all read it from here."""

from __future__ import annotations

import jax.numpy as jnp

PAGE_TABLE_KEY = "serve/page_table"
POS_KEY = "serve/pos"
ACTIVE_KEY = "serve/active"


def kv_quantize(x):
    """Symmetric per-(position, head) int8 quantization over head_dim:
    `scale = max|x| / 127` along the last axis, values rounded into
    [-127, 127]. Returns (int8 values, f32 scales) with the scales one
    rank lower — the per-page-entry-per-head arrays the quantized pools
    store next to the values. The scale floor keeps all-zero rows (fresh
    pages, padding routed to scratch) exactly representable as zeros."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def kv_dequantize(q, scale):
    """Inverse of kv_quantize: f32 values from int8 + per-row scales."""
    return q.astype(jnp.float32) * scale[..., None]


def pad_row(rows, width: int):
    """Token rows `[.., n]` as a pool `[pages, page, width]` holds them:
    zeros up to `width` (a latent row's whole lanes)."""
    short = width - rows.shape[-1]
    return rows if short == 0 else jnp.pad(
        rows, [(0, 0)] * (rows.ndim - 1) + [(0, short)])


def append_slots(pt, pos, s: int, page: int, ring: bool = False):
    """Where a block of `s` tokens a slot lies in the pools: (`t` `[slots,
    s]` the tokens' positions `pos + i`, the page of each, its offset in the
    page). A position past the table's last page goes to the scratch page
    (as `_commit_prefill` routes padding), so the scatter that follows has
    one shape whatever a slot holds. With `ring` the table is a ring: page
    `n` of the context lies at entry `n % entries`, whatever `n`."""
    rows = jnp.arange(pt.shape[0])
    t = pos[:, None] + jnp.arange(s)[None, :]
    pg = t // page
    if ring:
        return t, pt[rows[:, None], pg % pt.shape[1]], t % page
    in_range = pg < pt.shape[1]
    pageix = jnp.where(in_range,
                       pt[rows[:, None], jnp.minimum(pg, pt.shape[1] - 1)], 0)
    return t, pageix, t % page


def merge_heads(x):
    """`[.., heads, head_dim]` token rows as the pools hold them:
    `[.., heads * head_dim]`, heads-major."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))
