"""Multi-head latent attention (DeepSeek-V2/V3's MLA): queries and K/V go
through low-rank latents, rotary positions act on a slice of each query
head and on ONE key vector a token that all heads share, and what a token
leaves behind for later tokens is its K/V latent and that key vector.

With d the model width, H heads, r_q / r the query / K/V ranks, dn / dr the
widths of a head's position-free and rotary parts, dv its value width, and
RMS(x; w) = x / sqrt(mean(x^2) + eps) * w:

    c_q = RMS(x W_qa; w_q)                    [r_q]
    [q_n | q_r] = c_q W_qb                    H x (dn + dr)
    [c | k_r] = x W_kva                       r + dr
    c_kv = RMS(c; w_kv)
    q_r, k_r <- RoPE(., position)             pairs (2i, 2i+1), YaRN frequencies
    [k_n | v] = c_kv W_kvb                    H x (dn + dv)
    s = softmax_causal(scale (q_n^j . k_n^j + q_r^j . k_r));  o^j = s v^j
    out = concat_j(o^j) W_o                   H dv -> d

`scale` = (dn + dr)^-1/2 m^2, m = yarn_mscale(factor, mscale_all_dim).

Two variants by the layer's params. `q_lora_rank` 0 (a model whose config
says null): no query latent, `[q_n | q_r] = x W_q` with one `[d, H (dn +
dr)]` matrix and no `q_norm`. `head_gate`: a sigmoid gate a head on the
attention output, `o^j <- o^j sigmoid(x w_gate)_j` with `w_gate` `[d, H]`,
before W_o, in every form. Without rotary scaling (`rope_factor` absent) the
frequencies are the plain `base^(-2i/dr)` and m = 1.

Three forms of one op, chosen by `params["mode"]`:

- None (training, evaluation): the whole sequence, K and V decompressed
  from the latent; the fused flash kernel where the shape qualifies (one
  device, dv == dn + dr), else einsums.
- "latent_out" (serving prefill): the same, and the wave's latent
  `[c_kv | k_r]` `[b, s, r + dr]` is handed out in
  `ctx.new_state[layer.name] = {"latent": ...}` for the cache's commit.
- "decode" (serving decode): one step (or a few) against the paged latent
  pool `ctx.state[layer.name] = {"latent": [pages, page, r + dr in whole
  lanes]}`, in the ABSORBED form. With W_kvb = [W_k^j | W_v^j] a head,
      q_lat^j = q_n^j (W_k^j)^T                         [r]
      s = softmax(scale ([q_lat^j | q_r^j] . [c_kv | k_r]))   over cached rows
      o^j = (s c_kv) W_v^j
  which equals the form above and never decompresses the cache: no
  `[context, H, dn + dv]` tensor exists in the step, and a cached position
  costs 2 (2 r + dr) FLOPs a head, not 2 r (dn + dv). The step's own row is
  appended to the pool in place (the state is donated, kv_cache.py).

Inputs: x `[b, s, d]`, positions `[b, s]` int, and optionally `valid`
`[b, s]` int (1 = a token is there; in a decode step: the slot is live;
absent: every position, and in a decode step the cache's active slots),
which only the counters read. Reports (ctx.add_stat): in a prefill
`latent_tokens_committed`; in a decode step `latent_cache_tokens` (cached
positions the live slots' queries attended over) and `latent_cache_bytes`
(the same in bytes as the pool stores them), each summed over the layers.

Plain XLA but for the flash kernel; gradients come from JAX.
"""

from __future__ import annotations

import math
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

if TYPE_CHECKING:
    from flexflow_tpu.core.layer import Layer
from flexflow_tpu.core.tensor import TensorSpec
from flexflow_tpu.kernels.partition import multi_device
from flexflow_tpu.ops.norm_ops import rms_norm
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.ops.pages import (ACTIVE_KEY, PAGE_TABLE_KEY, POS_KEY,
                                    pad_row)
from flexflow_tpu.ops.registry import LoweringCtx, register_op
from flexflow_tpu.ops.rotary import (apply_rope,  # noqa: F401 (this module's names for them too)
                                     inv_freq, yarn_inv_freq)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_params(p):
    return dict(dim=p["qk_rope_head_dim"], base=p.get("rope_theta", 10000.0),
                factor=p.get("rope_factor", 1.0),
                original_len=p.get("rope_original_len", 4096),
                beta_fast=p.get("rope_beta_fast", 32),
                beta_slow=p.get("rope_beta_slow", 1))


def softmax_scale(p) -> float:
    """(dn + dr)^-1/2 m^2, m = yarn_mscale(factor, mscale_all_dim) where the
    model states `mscale_all_dim` under YaRN, else 1."""
    m = yarn_mscale(p.get("rope_factor", 1.0), p["rope_mscale_all_dim"]) \
        if p.get("rope_mscale_all_dim") else 1.0
    return (p["qk_nope_head_dim"] + p["qk_rope_head_dim"]) ** -0.5 * m * m


def rope_tables(positions, p):
    """(cos, sin) `positions.shape + [dr]` float32, each pair's angle twice
    (at 2i and 2i + 1), times yarn_mscale(factor, mscale) /
    yarn_mscale(factor, mscale_all_dim)."""
    inv = jnp.asarray(np.repeat(yarn_inv_freq(**_rope_params(p)), 2),
                      jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * inv
    factor = p.get("rope_factor", 1.0)
    m = yarn_mscale(factor, p.get("rope_mscale", 1.0)) \
        / yarn_mscale(factor, p.get("rope_mscale_all_dim") or 1.0) \
        if factor > 1 else 1.0
    return jnp.cos(angles) * m, jnp.sin(angles) * m


def _sizes(p):
    return (p["heads"], p["q_lora_rank"], p["kv_lora_rank"],
            p["qk_nope_head_dim"], p["qk_rope_head_dim"], p["v_head_dim"])


def _latent_infer(layer: Layer):
    x = layer.inputs[0].spec
    heads, r_q, r, dn, dr, dv = _sizes(layer.params)
    if dr % 2:
        raise ValueError("qk_rope_head_dim must be even (rotary pairs)")
    d = x.shape[-1]
    queries = {
        "wq_a": TensorSpec((d, r_q), x.dtype),
        "q_norm": TensorSpec((r_q,), x.dtype),
        "wq_b": TensorSpec((r_q, heads * (dn + dr)), x.dtype),
    } if r_q else {"wq": TensorSpec((d, heads * (dn + dr)), x.dtype)}
    layer.weight_specs = {
        **queries,
        "wkv_a": TensorSpec((d, r + dr), x.dtype),
        "kv_norm": TensorSpec((r,), x.dtype),
        "wkv_b": TensorSpec((r, heads * (dn + dv)), x.dtype),
        "wo": TensorSpec((heads * dv, d), x.dtype),
    }
    if layer.params.get("head_gate"):
        layer.weight_specs["w_gate"] = TensorSpec((d, heads), x.dtype)
    return [x]


def _whole_sequence_attention(q, k, v, scale, ctx: LoweringCtx, impl: str):
    """Causal attention of q, k `[b, s, H, dn + dr]` and v `[b, s, H, dv]`
    over the decompressed K/V of the same sequence: `[b, s, H, dv]`."""
    from flexflow_tpu.kernels.flash_attention import (flash_attention_qkv,
                                                      flash_supported)

    s, depth = q.shape[1], q.shape[3]
    covered = v.shape[3] == depth and not multi_device(ctx.mesh) \
        and flash_supported(s, depth, q.dtype.itemsize)
    if impl == "flash" or (impl == "auto" and ctx.enable_fusion and covered):
        return flash_attention_qkv(q, k, v, causal=True, scale=scale)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _absorbed_decode(layer, q_n, q_r, latent, live, weights, ctx: LoweringCtx):
    """The step's rows against the paged latent pool; returns `[b, s, H dv]`
    before the output projection."""
    p = layer.params
    heads, _r_q, r, dn, _dr, dv = _sizes(p)
    dt = q_n.dtype
    b, s = q_n.shape[0], q_n.shape[1]
    pool = ctx.state[layer.name]["latent"]
    pt, pos = ctx.state[PAGE_TABLE_KEY], ctx.state[POS_KEY]
    page = pool.shape[1]
    t = pos[:, None] + jnp.arange(s)[None, :]          # [b, s] write positions
    pg = t // page
    pageix = jnp.where(pg < pt.shape[1],
                       pt[jnp.arange(b)[:, None],
                          jnp.minimum(pg, pt.shape[1] - 1)], 0)
    width = pool.shape[-1]      # r + dr in whole lanes (kv_cache.py)
    pool = pool.at[pageix, t % page].set(
        pad_row(latent, width).astype(pool.dtype))
    ctx.new_state[layer.name] = {"latent": pool}
    # each slot's pages as they lie: [b, L, width]; the query side carries
    # zeros over the rows' padding, the value side reads the first r of
    # each row (a lane-aligned slice where r % 128 == 0)
    ctxt = pool[pt].reshape(b, -1, width).astype(dt)
    wkv_b = weights["wkv_b"].astype(dt).reshape(r, heads, dn + dv)
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_n, wkv_b[..., :dn])
    q_full = pad_row(jnp.concatenate([q_lat, q_r], axis=-1), width)
    logits = jnp.einsum("bqhe,bke->bhqk", q_full, ctxt,
                        preferred_element_type=jnp.float32) * softmax_scale(p)
    # query i (at position pos + i, just written) attends 0..pos+i
    keep = jnp.arange(ctxt.shape[1])[None, None, None, :] \
        <= t[:, None, :, None]
    logits = jnp.where(keep, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(dt)
    o_lat = jnp.einsum("bhqk,bkr->bqhr", probs, ctxt[..., :r])
    out = jnp.einsum("bqhr,rhv->bqhv", o_lat, wkv_b[..., dn:])
    if live is None:
        live = ctx.state[ACTIVE_KEY][:, None] > 0
    attended = jnp.sum(jnp.where(live, t + 1, 0)).astype(jnp.int32)
    ctx.add_stat("latent_cache_tokens", attended)
    ctx.add_stat("latent_cache_bytes", attended.astype(jnp.float32)
                 * (pool.shape[-1] * pool.dtype.itemsize))
    return out.reshape(b, s, heads * dv)


def _latent_lower(layer: Layer, inputs, weights, ctx: LoweringCtx):
    x, positions = inputs[0], inputs[1]
    p = layer.params
    heads, _r_q, r, dn, dr, dv = _sizes(p)
    eps = p.get("eps", 1e-6)
    dt = x.dtype
    b, s, _d = x.shape
    exists = (inputs[2] > 0) if len(inputs) > 2 else None

    if "wq" in weights:
        q = x @ weights["wq"].astype(dt)
    else:
        c_q = rms_norm(x @ weights["wq_a"].astype(dt), weights["q_norm"], eps)
        q = c_q @ weights["wq_b"].astype(dt)
    q = q.reshape(b, s, heads, dn + dr)
    ckr = x @ weights["wkv_a"].astype(dt)              # [b, s, r + dr]
    c_kv = rms_norm(ckr[..., :r], weights["kv_norm"], eps)
    cos, sin = rope_tables(positions, p)               # [b, s, dr] f32
    k_r = apply_rope(ckr[..., r:], cos, sin)
    q_n = q[..., :dn]
    q_r = apply_rope(q[..., dn:], cos[:, :, None], sin[:, :, None])
    latent = jnp.concatenate([c_kv, k_r], axis=-1)     # what is cached

    def projected(out):     # [b, s, H dv] -> the layer's output
        if "w_gate" in weights:
            gate = jax.nn.sigmoid(
                (x @ weights["w_gate"].astype(dt)).astype(jnp.float32))
            out = (out.reshape(b, s, heads, dv) * gate[..., None].astype(dt)
                   ).reshape(b, s, heads * dv)
        return [out @ weights["wo"].astype(dt)]

    mode = p.get("mode")
    if mode == "decode":
        return projected(_absorbed_decode(layer, q_n, q_r, latent, exists,
                                          weights, ctx))
    if mode == "latent_out":
        ctx.new_state[layer.name] = {"latent": latent}
        ctx.add_stat("latent_tokens_committed",
                     jnp.asarray(b * s, jnp.int32) if exists is None
                     else jnp.sum(exists).astype(jnp.int32))
    kv = (c_kv @ weights["wkv_b"].astype(dt)).reshape(b, s, heads, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, :, None], (b, s, heads, dr))],
        axis=-1)
    out = _whole_sequence_attention(
        jnp.concatenate([q_n, q_r], axis=-1), k, kv[..., dn:],
        softmax_scale(p), ctx, p.get("impl", "auto"))
    return projected(out.reshape(b, s, heads * dv))


def projection_params(p, d: int) -> int:
    """The op's matrices, as multiplied with every token."""
    heads, r_q, r, dn, dr, dv = _sizes(p)
    queries = d * r_q + r_q * heads * (dn + dr) if r_q \
        else d * heads * (dn + dr)
    return queries + d * (r + dr) + r * heads * (dn + dv) + heads * dv * d \
        + (d * heads if p.get("head_gate") else 0)


def _latent_flops(layer: Layer):
    """Forward. Whole sequence: the projections and the scores and values
    over the full square (the MFU convention, as _mha_flops counts). A
    decode step: the projections but W_kvb, which the absorbed form meets
    as q_n (W_k)^T and (s c_kv) W_v once a query row; the cached positions
    it attends over are priced by their bytes (the decode search's K/V
    term), 2 (2 r + dr) FLOPs a head each being nothing beside them."""
    x = layer.inputs[0].spec
    p = layer.params
    heads, _r_q, _r, dn, dr, dv = _sizes(p)
    b, s, d = x.shape
    proj = 2.0 * b * s * projection_params(p, d)
    if p.get("mode") == "decode":
        return proj
    return proj + 2.0 * b * s * s * heads * (dn + dr + dv)


def _latent_serving_params(params: dict, kind: str) -> dict:
    return dict(params, mode="decode" if kind == "decode" else "latent_out")


def _latent_page_state(layer: Layer) -> dict:
    """A token's row in this layer's one pool: its K/V latent after the
    norm, then the shared rotary key after RoPE."""
    p = layer.params
    return {"latent_dim": p["kv_lora_rank"] + p["qk_rope_head_dim"]}


register_op(OperatorType.LATENT_ATTENTION, _latent_infer, _latent_lower,
            _latent_flops, serving_params=_latent_serving_params,
            state_kind="paged_latent", page_state=_latent_page_state)
