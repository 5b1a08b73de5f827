"""Multi-head attention.

Reference analog: src/ops/attention.cc (926) + attention.cu (372), which wrap
cuDNN MultiHeadAttn (cudnnMultiHeadAttnForward, src/ops/attention.cu:35). The
TPU lowering is einsum-based scaled-dot-product attention that XLA maps onto
the MXU; a fused pallas flash-attention kernel
(flexflow_tpu/kernels/flash_attention.py) is used instead when shapes qualify
(seq multiple of block size) and `impl` is not forced to "xla".

Head-parallel tensor parallelism (reference substitutions
create_partition_attention_combine, src/runtime/substitution.cc:1763-1770) is
expressed by sharding the per-head projection weights on a model axis.

Inputs: query, key, value `[batch, seq, features]`, and optionally a fourth,
`positions` `[batch, seq]` int: rotary positions, q and k turned over the
whole head in the rotate-half layout (ops/rotary.py) at the layer's
`rope_theta`. With `qk_norm` in the params each head of q and of k is
RMS-normed over its head_dim first, with one learned weight each (`q_norm`,
`k_norm` `[head_dim]`, eps `qk_norm_eps`): norm, then rotation, then the
scores. All three forms do it (the whole sequence; the prefill twin, whose
`kv_out` hands out k AFTER norm and rotation, so that is what the pool
holds; the paged decode twin, which norms and rotates the step's q and k at
the slot's position before the append). Both enter a layer's params only
where a model sets them: a layer without them lowers to the program it
lowered to before they existed. With `mrope_section` in the params the
positions are `[batch, seq, axes]` and the head's pairs follow them by
sections (ops/rotary.py).

With `selected` in the params the LAST input is the key set a learned indexer
chose for each query (ops/sparse_attention_ops.py): one set a token for all
heads, a membership mask `[batch, seq, keys]`. The whole sequence runs dense
under it, queries in blocks. Over a slot's cache each of the two block
lengths has two forms, chosen from the cache's shapes, the block and the mesh
before tracing (`step_path`, `chunk_path`; a lowered layer says which in its
`sparse_attend/step_path` or `sparse_attend/chunk_path` span): a Pallas
kernel where a K/V head is whole 128-lane slabs, a page whole tiles and the
program runs on one device; an XLA form everywhere else (every tiny model, a
mesh). A block of `s > 1` tokens (a prefill chunk) is dense under the mask:
ONE kernel a layer (kernels/sparse_attend_chunk.py, `ff_sparse_attend_chunk`:
the slot's pages gathered once as the pools hold them, tiles of queries
against tiles of keys up to each query block's last position, the float32
scores and the online softmax in VMEM), or the XLA form over the rung of the
slot's pages that holds the context, queries in blocks, whose scores pass
through HBM. A decode step takes the mask `[slots, 1, keys]`: ONE kernel a
layer (kernels/sparse_attend_step.py, `ff_sparse_attend_step`: the live slots
alone, each slot's pages under its position fetched by page from the pools
where they lie, the same online softmax under the mask), or the XLA form: the
mask compacted to the kept positions `[slots, 1, k]`, whose K and V rows it
gathers from the pages, `k` a slot, every slot. The work lies under the named
scope `ff_sparse_attend`.

With `window` in the params the kept keys are a FIXED mask by position: query
`t` sees the keys `t - window < s <= t` (itself among them), every `s <= t`
at `window` 0 (the layer of a windowed model that sees the whole context).
The whole sequence goes through the flash kernels, which take the window
(kernels/flash_attention.py: the key blocks wholly before the band are not
visited, forward and backward; K/V heads fewer than the query heads are read
through the block index, no repeated copy), where `impl` and the shapes say
flash, and through the causal mask with the window under it on the XLA path
elsewhere (every tiny model); `impl="flash"` under a window is honoured. Ring
attention (kernels/ring_attention.py) knows no window: a windowed layer that a
strategy places on the ring path raises by that name. A lowered layer says
which form it took in a trace-time span (`flash/window`, `flash/full`,
`xla/masked`, with its window and the forward kernel's tile), its work lies
under `ff_window_attend` / `ff_full_attend`, and it reports `window_keys_seen`
/ `full_keys_seen`: the (query, key) pairs under its band or triangle (and
`window_keys_causal`, the triangle a band lies under).
Over the cache both kinds take the two kernels above with the kept set stated
by position (`first <= s <= t`; no mask operand, a page walk and a key axis
that start at `first`) where `step_path` / `chunk_path` say so, an XLA form
under the positions' mask elsewhere; a layer with `window > 0` keeps its K
and V in a RING of pages a slot under `serve/window_table` (position `t` at
entry `(t // page) % ring`, serving/kv_cache.py), one with `window` 0 in the
slot's pages under `serve/page_table`. The work lies under the named scopes
`ff_window_attend` and `ff_full_attend`. `rope_scaling` in the params: YaRN's
tables (ops/rotary.py) for the layer's rotary positions.

With `output_gate` in the params the heads' concatenated output is multiplied
by `sigmoid(x W_g)` before `wo`, `x` the QUERY input and `wg` a fifth weight
`[features, embed_dim]`: in all three forms (scope `ff_attn_gate`).
"""

from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from typing import TYPE_CHECKING
if TYPE_CHECKING:
    from flexflow_tpu.core.layer import Layer
from flexflow_tpu import telemetry as tel
from flexflow_tpu.core.tensor import TensorSpec
from flexflow_tpu.kernels import sparse_attend_chunk, sparse_attend_step
from flexflow_tpu.kernels.flash_attention import (FLASH_KEPT, entry_of,
                                                  flash_supported)
from flexflow_tpu.kernels.head_turn import head_turn, turn_supported
from flexflow_tpu.kernels.partition import dividing, multi_device, per_shard
from flexflow_tpu.ops.norm_ops import rms_norm
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.ops.pages import append_slots, kv_quantize, merge_heads
from flexflow_tpu.ops.registry import register_op, LoweringCtx
from flexflow_tpu.ops.rotary import apply_rope_half, half_tables
from flexflow_tpu.ops.sparse_attention_ops import (ATTEND_SCOPE,
                                                   context_rungs,
                                                   kept_positions,
                                                   over_context, query_blocks)


# the named scopes of a layer's cache attention under bounds by position:
# a windowed layer's and a full one's (both phases)
WINDOW_SCOPE = "ff_window_attend"
FULL_SCOPE = "ff_full_attend"
# the scope both lie under (what reads the two kinds together names this one)
BOUNDED_SCOPE = "ff_bounded_attend"
GATE_SCOPE = "ff_attn_gate"
WINDOW_TABLE_KEY = "serve/window_table"
# `[rows]` int: how many of a block's positions hold a token (the chunk
# program says; absent in a step, whose every live slot's one position does)
BLOCK_LENGTHS_KEY = "serve/block_lengths"


def _mha_infer(layer: Layer):
    q, k, v = [t.spec for t in layer.inputs[:3]]
    p = layer.params
    embed = p["embed_dim"]
    heads = p["num_heads"]
    if embed % heads:
        raise ValueError("num_heads must divide embed_dim")
    kv_embed = _kv_heads(p) * (embed // heads)
    # the output's width where the heads do not add up to it (a model whose
    # hidden size is not heads * head_dim): wo is [embed, out_dim]
    out_dim = int(p.get("out_dim") or embed)
    # kdim/vdim are the key/value input feature dims (torch/reference
    # semantics); they must match the actual inputs if given.
    if p.get("kdim") and p["kdim"] != k.shape[-1]:
        raise ValueError(f"kdim={p['kdim']} != key feature dim {k.shape[-1]}")
    if p.get("vdim") and p["vdim"] != v.shape[-1]:
        raise ValueError(f"vdim={p['vdim']} != value feature dim {v.shape[-1]}")
    layer.weight_specs = {
        "wq": TensorSpec((q.shape[-1], embed), q.dtype),
        "wk": TensorSpec((k.shape[-1], kv_embed), q.dtype),
        "wv": TensorSpec((v.shape[-1], kv_embed), q.dtype),
        "wo": TensorSpec((embed, out_dim), q.dtype),
    }
    if p.get("bias", True):
        layer.weight_specs.update(
            {
                "bq": TensorSpec((embed,), q.dtype),
                "bk": TensorSpec((kv_embed,), q.dtype),
                "bv": TensorSpec((kv_embed,), q.dtype),
                "bo": TensorSpec((out_dim,), q.dtype),
            }
        )
    if p.get("add_bias_kv", False):
        layer.weight_specs["bias_k"] = TensorSpec((embed,), q.dtype)
        layer.weight_specs["bias_v"] = TensorSpec((embed,), q.dtype)
    if _positioned(layer) and (p.get("add_bias_kv") or p.get("add_zero_attn")):
        raise NotImplementedError("rotary positions or a q/k norm with "
                                  "add_bias_kv/add_zero_attn")
    if p.get("output_gate"):
        layer.weight_specs["wg"] = TensorSpec((q.shape[-1], embed), q.dtype)
    if p.get("qk_norm"):
        layer.weight_specs["q_norm"] = TensorSpec((embed // heads,), q.dtype)
        layer.weight_specs["k_norm"] = TensorSpec((embed // heads,), q.dtype)
    if "window" in p and (not p.get("causal") or p.get("selected")
                          or p.get("add_bias_kv") or p.get("add_zero_attn")):
        raise NotImplementedError("a window on attention that is not plain "
                                  "causal self-attention")
    if p.get("rope_scaling") and not _has_positions(layer):
        raise ValueError("rope_scaling without a positions input")
    return [q.with_shape(q.shape[:-1] + (out_dim,))]


def _has_positions(layer: Layer) -> bool:
    """Whether the fourth input is the rotary positions (the last one may
    be an indexer's key set instead)."""
    return len(layer.inputs) - bool(layer.params.get("selected")) > 3


def _positioned(layer: Layer) -> bool:
    """Whether q and k are normed or rotated before the scores."""
    return _has_positions(layer) or bool(layer.params.get("qk_norm"))


def _turner(layer: Layer, inputs, weights, merged: bool = False):
    """`turn(heads [b, s, h, d], norm weight's name)`: the per-head RMS norm
    where the layer has one, then the rotation at `positions` (the fourth
    input) where it has them. `merged`: `turn` takes and returns the
    projection as it lies, `[b, s, h * d]`, for a sequence that goes on to
    the flash kernels so: one pass over the merged axis
    (kernels/head_turn.py) where a head is whole lanes, else the steps
    above between a split and a merge."""
    p = layer.params
    hd = p["embed_dim"] // p["num_heads"]
    tables = None
    if _has_positions(layer):
        cos, sin = half_tables(inputs[3], hd, p.get("rope_theta", 10000.0),
                               p.get("mrope_section"), p.get("rope_scaling"))
        tables = cos[:, :, None], sin[:, :, None]           # [b, s, 1, d]

    def turn(heads, norm):
        if p.get("qk_norm"):
            heads = rms_norm(heads, weights[norm], p.get("qk_norm_eps", 1e-6))
        return heads if tables is None else apply_rope_half(heads, *tables)

    def turn_merged(x, norm):
        b, s, e = x.shape
        if not turn_supported(s, hd):
            return turn(x.reshape(b, s, e // hd, hd), norm).reshape(x.shape)
        # a table a position of each sequence, the sine's sign folded in
        signed = (None, None) if tables is None else (
            jnp.broadcast_to(cos, (b, s, hd)),
            jnp.broadcast_to(sin * jnp.where(jnp.arange(hd) < hd // 2,
                                             -1.0, 1.0), (b, s, hd)))
        return head_turn(x, weights[norm] if p.get("qk_norm") else None,
                         *signed, e // hd, float(p.get("qk_norm_eps", 1e-6)))

    return turn_merged if merged else turn


def _kv_heads(p) -> int:
    """Grouped-query attention: `num_kv_heads` K/V heads, each read by
    num_heads / num_kv_heads query heads in a row (query head j reads K/V
    head j // group). Absent or 0: one K/V head a query head."""
    kv = int(p.get("num_kv_heads") or p["num_heads"])
    if p["num_heads"] % kv:
        raise ValueError("num_kv_heads must divide num_heads")
    if kv != p["num_heads"] and (p.get("add_bias_kv") or p.get("add_zero_attn")):
        raise NotImplementedError("grouped K/V heads with add_bias_kv/"
                                  "add_zero_attn")
    return kv


def _scale(p, head_dim: int) -> float:
    """The factor on q k^T: `scale` where the model states one (an
    attention multiplier), else 1 / sqrt(head_dim)."""
    return float(p["scale"]) if p.get("scale") is not None \
        else 1.0 / math.sqrt(head_dim)


def _gated(out, x, weights):
    """`out * sigmoid(x W_g)` where the layer has an output gate (`wg`),
    `out` `[b, s, embed]` the heads' concatenated output, `x` the query
    input; `out` as it is elsewhere."""
    if "wg" not in weights:
        return out
    with jax.named_scope(GATE_SCOPE):
        gate = jax.nn.sigmoid((x @ weights["wg"].astype(x.dtype))
                              .astype(jnp.float32))
        return out * gate.astype(out.dtype)


def _split_heads(x, heads):
    b, s, e = x.shape
    return x.reshape(b, s, heads, e // heads)


def _mha_decode_lower(layer: Layer, inputs, weights, ctx: LoweringCtx):
    """Decode step(s) against the paged KV cache (serving path).

    Inputs are [slots, s, embed] — s=1 for the plain decode program, s=K+1
    for the speculative-verify program (one batched pass teacher-forcing
    the K drafted tokens). The cache lives in lowering state:
      ctx.state[layer.name]    = {"k": [pages, page, h * d], "v": ...,
                                  optionally "k_scale"/"v_scale"
                                  [pages, page, h] for int8}
      ctx.state["serve/page_table"] = [slots, pages_per_slot] int32 page ids
      ctx.state["serve/pos"]        = [slots] int32 count of cached tokens

    Token i's K/V is scattered into page (pos+i)//page_size at offset
    (pos+i)%page_size (out-of-range positions route to the scratch page,
    mirroring commit_prefill), then attention runs over the gathered
    per-slot pages with the causal extent mask (query i attends cached
    positions <= pos+i). A quantized cache (int8 pools + per-entry-per-head
    scales) quantizes on append and dequantizes in the gather — fused into
    the attention by the pallas dequant kernel when fusion is enabled,
    einsum fallback otherwise. Inactive slots point every page-table entry
    at the reserved scratch page 0 with pos 0, so their writes land in
    scratch and their (garbage but finite) outputs are ignored by the
    scheduler. Everything is a fixed-shape gather/scatter — no resharding,
    no recompilation across steps. Heads and head_dim are one axis in the
    pools (kv_cache.py says why): the token rows are merged before the
    scatter, and the gathered context (as large as a pool) stays merged —
    the query rows are laid over the merged axis instead — so with the
    state donated a step appends in place and the page gather is the only
    op that touches a whole pool. The int8 kernel takes the gathered
    `[b, L, h, d]`."""
    q = inputs[0]
    p = layer.params
    heads = p["num_heads"]
    embed = p["embed_dim"]
    hd = embed // heads
    dt = q.dtype

    def proj(x, w, b):
        y = x @ weights[w].astype(dt)
        if b in weights:
            y = y + weights[b].astype(dt)
        return y

    kvh = _kv_heads(p)
    qh = _split_heads(proj(inputs[0], "wq", "bq"), heads)  # (slots, s, h, d)
    kh = _split_heads(proj(inputs[1], "wk", "bk"), kvh)    # (slots, s, kvh, d)
    vh = _split_heads(proj(inputs[2], "wv", "bv"), kvh)
    if _positioned(layer):      # at the slot's position, before the append
        turn = _turner(layer, inputs, weights)
        qh, kh = turn(qh, "q_norm"), turn(kh, "k_norm")
    if kvh != heads and "k_scale" in ctx.state[layer.name]:
        raise NotImplementedError("grouped K/V heads with a quantized cache")
    selected = inputs[-1] if p.get("selected") else None
    if selected is not None and "k_scale" in ctx.state[layer.name]:
        raise NotImplementedError("a selected key set with a quantized cache")

    cache = ctx.state[layer.name]
    k_pool, v_pool = cache["k"], cache["v"]
    quantized = "k_scale" in cache
    # a windowed layer's pages are its slot's ring, under a table of its own
    window = int(p.get("window") or 0)
    if "window" in p and quantized:
        raise NotImplementedError("a window with a quantized cache")
    pt = ctx.state[WINDOW_TABLE_KEY if window else "serve/page_table"]
    pos = ctx.state["serve/pos"]
    page = k_pool.shape[1]
    b, s = q.shape[0], q.shape[1]
    t, pageix, off = append_slots(pt, pos, s, page, ring=bool(window))

    if quantized:
        qk, ks = kv_quantize(kh)
        qv, vs = kv_quantize(vh)
        k_pool = k_pool.at[pageix, off].set(merge_heads(qk))
        v_pool = v_pool.at[pageix, off].set(merge_heads(qv))
        k_scale = cache["k_scale"].at[pageix, off].set(ks)
        v_scale = cache["v_scale"].at[pageix, off].set(vs)
        ctx.new_state[layer.name] = {"k": k_pool, "v": v_pool,
                                     "k_scale": k_scale, "v_scale": v_scale}
    else:
        k_pool = k_pool.at[pageix, off].set(
            merge_heads(kh).astype(k_pool.dtype))
        v_pool = v_pool.at[pageix, off].set(
            merge_heads(vh).astype(v_pool.dtype))
        ctx.new_state[layer.name] = {"k": k_pool, "v": v_pool}

    scale = _scale(p, hd)
    out = None
    if selected is not None:
        out = _selected_cache_attention(
            layer, qh.reshape(b, s, kvh, heads // kvh, hd), k_pool, v_pool,
            pt, t, selected, scale, ctx)
    elif "window" in p:
        out = _bounded_cache_attention(
            layer, qh.reshape(b, s, kvh, heads // kvh, hd), k_pool, v_pool,
            pt, t, window, scale, ctx)
    elif quantized:
        # gather the int8 context + scales: [slots, L, h, (d)]
        Kq = k_pool[pt].reshape(b, -1, heads, hd)
        Vq = v_pool[pt].reshape(b, -1, heads, hd)
        Ks = k_scale[pt].reshape(b, -1, heads)
        Vs = v_scale[pt].reshape(b, -1, heads)
        from flexflow_tpu.kernels.dequant_attention import (
            dequant_decode_attention, dequant_supported)

        # the path is chosen from the shape (and, on a multi-device mesh,
        # from whether the strategy says how to split the kernel), BEFORE
        # tracing; a kernel that was chosen and then fails to trace/compile
        # must fail the program
        spec = _attn_pspec(layer, ctx, None, heads)
        if ctx.enable_fusion and dequant_supported(Kq.shape[1], hd) \
                and spec is not None:
            sspec = PartitionSpec(*spec[:3])
            out = per_shard(
                functools.partial(dequant_decode_attention, scale=scale),
                ctx.mesh, (spec, spec, sspec, spec, sspec, PartitionSpec()),
                spec)(qh, Kq, Ks, Vq, Vs, pos)
        else:
            K = merge_heads((Kq.astype(jnp.float32) * Ks[..., None]).astype(dt))
            V = merge_heads((Vq.astype(jnp.float32) * Vs[..., None]).astype(dt))
    else:
        # gather each slot's pages, heads still merged: [slots, L, kvh * d]
        K = k_pool[pt].reshape(b, -1, kvh * hd).astype(dt)
        V = v_pool[pt].reshape(b, -1, kvh * hd).astype(dt)
    if out is None:
        # query heads as [kvh groups, r in a group] against their group's
        # K/V head (r = 1: one K/V head a query head); on a mesh each shard
        # attends over the heads it holds, as the pools are split
        qg = qh.reshape(b, s, kvh, heads // kvh, hd)
        attend = functools.partial(_merged_axis_attention, scale=scale)
        spec = _attn_pspec(layer, ctx, b, kvh)
        if spec is not None:
            rows = PartitionSpec(spec[0], None, spec[2], None, None)
            merged = PartitionSpec(spec[0], None, spec[2])
            attend = per_shard(
                attend, ctx.mesh,
                (rows, merged, merged, PartitionSpec(spec[0], None)), rows)
        out = attend(qg, K, V, t)
    out = _gated(out.reshape(b, s, embed), q, weights)
    y = out @ weights["wo"].astype(dt)
    if "bo" in weights:
        y = y + weights["bo"].astype(dt)
    return [y]


def step_path(head_dim: int, page: int, pages_per_slot: int, pool_dtype,
              mesh=None) -> dict:
    """Which form a decode step's attention over the keys an indexer kept
    takes, from the cache's shapes and the mesh the program is lowered for:
    what a lowered layer reports in its `sparse_attend/step_path` span.
    `{"path": "kernel", "block_pages": P}` (kernels/sparse_attend_step.py)
    where a K/V head is whole 128-lane slabs, a page whole tiles of the
    pools' type and the program runs on one device (GSPMD cannot partition a
    Mosaic call); `{"path": "xla"}` everywhere else (every tiny model)."""
    pages = None if multi_device(mesh) else sparse_attend_step.block_pages(
        page, head_dim, jnp.dtype(pool_dtype).itemsize, pages_per_slot)
    if pages is None:
        return {"path": "xla"}
    return {"path": "kernel", "block_pages": pages}


def chunk_path(head_dim: int, page: int, pages_per_slot: int, chunk: int,
               pool_dtype, mesh=None) -> dict:
    """Which form the attention of a block of `chunk > 1` tokens (a prefill
    chunk) over the keys an indexer kept takes, from the same facts as
    `step_path` and the block's length: what a lowered layer reports in its
    `sparse_attend/chunk_path` span. `{"path": "kernel", "query_block": qb,
    "key_block": kb}` (kernels/sparse_attend_chunk.py) where a K/V head is
    whole 128-lane slabs, a page whole tiles of the pools' type, the chunk a
    whole number of query blocks and the program runs on one device;
    `{"path": "xla"}` everywhere else (every tiny model)."""
    tiles = None if multi_device(mesh) else sparse_attend_chunk.chunk_tiles(
        head_dim, page, pages_per_slot, chunk, jnp.dtype(pool_dtype).itemsize)
    if tiles is None:
        return {"path": "xla"}
    return {"path": "kernel", "query_block": tiles[0], "key_block": tiles[1]}


def _selected_cache_attention(layer: Layer, qg, k_pool, v_pool, pt, t,
                              selected, scale, ctx: LoweringCtx):
    """Attention of `qg` `[b, s, g, r, d]` at positions `t` `[b, s]` over the
    keys an indexer chose among the slot's pages: `selected` `[b, s, L]`
    bool, its membership mask over the slot's padded context.

    A decode step (`s == 1`) takes the form `step_path` says. The kernel
    `ff_sparse_attend_step` (kernels/sparse_attend_step.py): the live slots
    alone, each slot's pages under its position fetched by page where they
    lie, online softmax under the mask; a slot that is not live costs nothing
    and reads zeros. Or the XLA form: the mask compacted to the kept
    positions (`kept_positions`: `k` a slot, `L` where fewer are kept), those
    rows of K and V gathered from the pools, every slot's, and the places
    that hold none masked.

    A block (`s > 1`: a prefill chunk) runs dense under the mask in the form
    `chunk_path` says. The kernel `ff_sparse_attend_chunk`
    (kernels/sparse_attend_chunk.py): the slot's pages gathered once as the
    pools hold them, tiles of queries against tiles of keys up to each query
    block's last position, the scores and the online softmax in VMEM. Or the
    XLA form (`_rung_attention`): the pages the context reaches (the rung of
    `sparse_attention_ops.context_rungs` that holds it), queries in blocks,
    each block's float32 scores a value of the program.

    Reports `kv_bytes_gathered`: the K and V rows a live slot's attention
    had to read (the kept keys' in a step, in either form; the rows under a
    block's last position); a step also `kv_bytes_streamed`, the pages' bytes
    the kernel fetched (the live slots' pages under their positions, whole),
    and `sparse_attend_kernel_slots`, its grid steps on the first axis; a
    block `sparse_attend_chunk_tiles`, the (query block, key block) tiles its
    kernel visited, and `sparse_attend_chunk_tiles_dense`, the tiles of the
    rectangle the XLA form would compute (every query block against the
    whole rung): all 0 in the XLA form."""
    b, s, g, r, d = qg.shape
    dt = qg.dtype
    page = k_pool.shape[1]
    padded = pt.shape[1] * page      # L: the slot's padded context
    row_bytes = 2.0 * g * d * k_pool.dtype.itemsize
    live = ctx.state["serve/active"] > 0

    def report_gathered(rows):
        """`kv_bytes_gathered` from the K/V rows `[b]` a slot's attention
        had to read."""
        ctx.add_stat("kv_bytes_gathered", row_bytes * jnp.sum(
            jnp.where(live, rows, 0)).astype(jnp.float32))

    with jax.named_scope(ATTEND_SCOPE):
        if s == 1:
            report_gathered(jnp.sum(selected, axis=(1, 2)))
            path = step_path(d, page, pt.shape[1], k_pool.dtype, ctx.mesh)
            fetched = slots = jnp.int32(0)
            # one span a lowered layer (trace time): the form its step took
            with tel.span("sparse_attend/step_path", cat="compile",
                          layer=layer.name, **path):
                if path["path"] == "kernel":
                    out = sparse_attend_step.sparse_attend_step(
                        qg[:, 0], selected, k_pool, v_pool, pt, t[:, 0], live,
                        scale, path["block_pages"])[:, None].astype(dt)
                    reach = -(-jnp.minimum(t[:, 0] + 1, padded) // page)
                    fetched = jnp.sum(jnp.where(live, reach, 0))
                    slots = jnp.sum(live.astype(jnp.int32))
                else:
                    # the indexer's `topk`: the places the positions take
                    topk = layer.inputs[-1].owner.params["topk"]
                    # [b, 1, k]
                    at = kept_positions(selected, min(topk, padded))
                    idx = jnp.minimum(at[:, 0], padded - 1)
                    rows = pt[jnp.arange(b)[:, None], idx // page] * page \
                        + idx % page
                    K = k_pool.reshape(-1, g * d)[rows].astype(dt)  # [b,k,g*d]
                    V = v_pool.reshape(-1, g * d)[rows].astype(dt)
                    out = _merged_axis_attention(qg, K, V, t, scale=scale,
                                                 keep=at <= t[:, :, None])
            ctx.add_stat("kv_bytes_streamed",
                         row_bytes * page * fetched.astype(jnp.float32))
            ctx.add_stat("sparse_attend_kernel_slots", slots)
            return out
        report_gathered(jnp.minimum(t[:, -1] + 1, padded))
        path = chunk_path(d, page, pt.shape[1], s, k_pool.dtype, ctx.mesh)
        end = jnp.max(t) + 1
        tiles = dense = jnp.float32(0)
        # one span a lowered layer (trace time): the form its block took
        with tel.span("sparse_attend/chunk_path", cat="compile",
                      layer=layer.name, **path):
            if path["path"] == "kernel":
                qb, kb = path["query_block"], path["key_block"]
                # the slot's pages as the pools hold them: [b, L, g * d]
                out, visited = sparse_attend_chunk.sparse_attend_chunk(
                    qg, selected, k_pool[pt].reshape(b, padded, g * d),
                    v_pool[pt].reshape(b, padded, g * d), t, scale, qb, kb)
                out = out.astype(dt)
                tiles = visited.astype(jnp.float32)
                # the rectangle the XLA form computes: every query block
                # against the rung that holds the chunk's last position
                rungs = jnp.asarray(context_rungs(pt.shape[1]), jnp.float32)
                rung = rungs[jnp.sum(end > rungs[:-1] * page)]
                dense = rung * (b * s * page / float(qb * kb))
            else:
                out = over_context(
                    functools.partial(_rung_attention, qg, k_pool, v_pool,
                                      pt, selected, scale),
                    end, pt.shape[1], page)
        ctx.add_stat("sparse_attend_chunk_tiles", tiles)
        ctx.add_stat("sparse_attend_chunk_tiles_dense", dense)
        return out


def _bounded_cache_attention(layer: Layer, qg, k_pool, v_pool, pt, t,
                             window: int, scale, ctx: LoweringCtx):
    """Attention of `qg` `[b, s, g, r, d]` at positions `t` `[b, s]` over the
    slot's cached keys at positions `first <= s <= t`, `first` = `t - window
    + 1` (a windowed layer: `pt` `[b, ring]` is the slot's RING, page `n` of
    the context at entry `n % ring`) or 0 (`window` 0, a full layer: `pt` the
    slot's pages in order). The forms are `_selected_cache_attention`'s, the
    kept set stated by position in place of a membership mask: a decode step
    (`s == 1`) the kernel `ff_sparse_attend_step` (the pages between `first
    // page` and `t // page` fetched where they lie and no others), a block
    the kernel `ff_sparse_attend_chunk` over the slot's pages gathered in the
    context's order from the block's first visible page on (a first key
    block as well as a last), where `step_path` / `chunk_path` say
    "kernel"; elsewhere (every tiny model, a mesh) the XLA form: the table's
    pages gathered, each entry's position reckoned, the bounds a mask.

    Under the named scope `ff_window_attend` or `ff_full_attend` (both inside
    `ff_bounded_attend`); the trace-time spans `window_attend/step_path`,
    `full_attend/chunk_path`, .. say the form taken. Reports, `<kind>`
    `window` or `full`: `<kind>_kv_bytes_needed` (the K and V rows a live
    slot's queries may see: `min(pos + 1, window)` or `pos + 1` a step),
    `<kind>_keys_seen` (the (query, key) pairs of the queries that exist, a
    block's by `serve/block_lengths`, and the keys each may see),
    `<kind>_kv_bytes_streamed` (whole pages fetched: a
    step's walk, a block's gather; 0 for the XLA form of a step, which gathers
    the table), and a block `<kind>_attend_chunk_tiles` (the (query block, key
    block) tiles its kernel visited) and `<kind>_attend_chunk_tiles_dense`
    (every query block against all the gathered keys), 0 in the XLA form."""
    b, s, g, r, d = qg.shape
    dt = qg.dtype
    page, per_slot = k_pool.shape[1], pt.shape[1]
    kind = "window" if window else "full"
    row_bytes = 2.0 * g * d * k_pool.dtype.itemsize
    live = ctx.state["serve/active"] > 0
    first = jnp.maximum(t - window + 1, 0) if window else jnp.zeros_like(t)
    # a table holds no position past its last page; a ring any
    last = t[:, -1] if window else jnp.minimum(t[:, -1], per_slot * page - 1)

    def report(name, per_slot_count, unit):
        ctx.add_stat(f"{kind}_{name}", unit * jnp.sum(
            jnp.where(live, per_slot_count, 0)).astype(jnp.float32))

    def xla_form():
        """The table's pages gathered `[b, L, g * d]`, each row's position
        (a ring's entry `e` holds the newest page `n <= last // page` with
        `n % ring == e`), the bounds a mask `[b, s, L]`."""
        K = k_pool[pt].reshape(b, -1, g * d).astype(dt)
        V = v_pool[pt].reshape(b, -1, g * d).astype(dt)
        entry = jnp.broadcast_to(jnp.arange(per_slot), (b, per_slot))
        held = entry if not window else \
            (last // page)[:, None] - ((last // page)[:, None] - entry) \
            % per_slot
        at = (held[:, :, None] * page + jnp.arange(page)).reshape(b, 1, -1)
        keep = (at >= first[:, :, None]) & (at <= t[:, :, None])
        return _merged_axis_attention(qg, K, V, t, scale=scale, keep=keep)

    report("kv_bytes_needed", last - first[:, 0] + 1, row_bytes)
    lengths = ctx.state.get(BLOCK_LENGTHS_KEY)
    exists = True if lengths is None else \
        jnp.arange(s)[None, :] < lengths[:, None]
    report("keys_seen", jnp.sum(jnp.where(exists, t - first + 1, 0), axis=1),
           1.0)
    with jax.named_scope(BOUNDED_SCOPE), \
            jax.named_scope(WINDOW_SCOPE if window else FULL_SCOPE):
        if s == 1:
            path = step_path(d, page, per_slot, k_pool.dtype, ctx.mesh)
            with tel.span(f"{kind}_attend/step_path", cat="compile",
                          layer=layer.name, window=window, **path):
                if path["path"] == "kernel":
                    out = sparse_attend_step.sparse_attend_step(
                        qg[:, 0], None, k_pool, v_pool, pt, t[:, 0], live,
                        scale, path["block_pages"], first=first[:, 0],
                        ring=bool(window))[:, None].astype(dt)
                    report("kv_bytes_streamed",
                           last // page - first[:, 0] // page + 1,
                           row_bytes * page)
                else:
                    out = xla_form()
                    report("kv_bytes_streamed", 0, 0.0)
            return out
        path = chunk_path(d, page, per_slot, s, k_pool.dtype, ctx.mesh)
        tiles = dense = jnp.float32(0)
        with tel.span(f"{kind}_attend/chunk_path", cat="compile",
                      layer=layer.name, window=window, **path):
            if path["path"] == "kernel":
                qb, kb = path["query_block"], path["key_block"]
                # the pages in the context's order from the first one a query
                # of the block may see: [b, L, g * d], row 0 at `base`
                start = first[:, 0] // page
                pages = pt if not window else jnp.take_along_axis(
                    pt, (start[:, None] + jnp.arange(per_slot)) % per_slot,
                    axis=1)
                out, visited = sparse_attend_chunk.sparse_attend_chunk(
                    qg, None, k_pool[pages].reshape(b, -1, g * d),
                    v_pool[pages].reshape(b, -1, g * d), t, scale, qb, kb,
                    base=start * page, window=window)
                out = out.astype(dt)
                tiles = visited.astype(jnp.float32)
                dense = jnp.float32(b * (s // qb) * -(-per_slot * page // kb))
                report("kv_bytes_streamed", per_slot, row_bytes * page)
            else:
                out = xla_form()
                report("kv_bytes_streamed", 0, 0.0)
        ctx.add_stat(f"{kind}_attend_chunk_tiles", tiles)
        ctx.add_stat(f"{kind}_attend_chunk_tiles_dense", dense)
        return out


def _rung_attention(qg, k_pool, v_pool, pt, selected, scale, pages: int):
    """The XLA form of a block's attention under the mask: `qg` `[b, s, g,
    r, d]` over the first `pages` pages of every row's table, gathered once,
    dense under `selected` `[b, s, L]`, queries in blocks, each K/V head
    against its group's r x block query rows as one product with float32
    scores (which pass through HBM: the kernel's reason)."""
    b, s, g, r, d = qg.shape
    dt = qg.dtype
    n = pages * k_pool.shape[1]
    # [b, g, n, d]: a K/V head's keys in a row, once a block
    K = k_pool[pt[:, :pages]].reshape(b, n, g, d).astype(dt)
    V = v_pool[pt[:, :pages]].reshape(b, n, g, d).astype(dt)
    K, V = K.transpose(0, 2, 1, 3), V.transpose(0, 2, 1, 3)

    def block(q, keep):
        qb = q.shape[1]
        rows = q.transpose(0, 2, 3, 1, 4).reshape(b, g, r * qb, d)
        logits = jnp.einsum("bgmd,bgkd->bgmk", rows, K,
                            preferred_element_type=jnp.float32)
        logits = jnp.where(
            keep[:, None, None, :, :n],
            logits.reshape(b, g, r, qb, n) * scale,
            jnp.finfo(jnp.float32).min).reshape(b, g, r * qb, n)
        probs = jax.nn.softmax(logits, axis=-1).astype(dt)
        out = jnp.einsum("bgmk,bgkd->bgmd", probs, V)
        return out.reshape(b, g, r, qb, d).transpose(0, 3, 1, 2, 4)

    return query_blocks(block, s, qg, selected)


def _merged_axis_attention(qg, K, V, t, *, scale, keep=None):
    """Decode attention against a gathered context that stays as the pools
    hold it: `qg` `[b, s, g, r, d]` query rows (g K/V heads, r query heads
    a group), `K`/`V` `[b, L, g * d]`, `t` `[b, s]` the rows' positions
    (or `keep` `[b, s, L]` bool: which of the gathered rows a query may
    read, where they are not the context in order);
    returns `[b, s, g, r, d]`. The context is as large as a pool, and
    splitting its merged axis into heads of under 128 would relay all of
    it. So the query side, a few rows, is laid over the merged axis
    instead: group g's rows are zero outside K/V head g's span, one product
    over the merged axis is then each head's own q k^T (the other spans add
    exact zeros), and of probs @ V each group keeps its own span."""
    b, s, g, r, d = qg.shape
    own = jnp.eye(g, dtype=qg.dtype)
    qm = jnp.einsum("bqgrd,gh->bqgrhd", qg, own).reshape(b, s, g, r, g * d)
    logits = jnp.einsum("bqgre,bke->bgrqk", qm, K) * scale
    # causal-by-construction: query token i (at position pos+i, just
    # written) attends cached positions 0..pos+i inclusive
    keep = (jnp.arange(K.shape[1])[None, None, None, None, :]
            <= t[:, None, None, :, None]) if keep is None \
        else keep[:, None, None]
    logits = jnp.where(keep, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits, axis=-1)
    spans = jnp.einsum("bgrqk,bke->bqgre", probs, V)
    return jnp.einsum("bqgrhd,gh->bqgrd", spans.reshape(b, s, g, r, g, d),
                      own)


def _attn_pspec(layer: Layer, ctx: LoweringCtx, batch, heads: int):
    """(b, s, h, d) PartitionSpec the strategy puts on this attention's
    per-head tensors — batch on the op output's batch axes, heads on wq's
    column axes (each kept only where it divides; nothing to split on one
    device). None when a Pallas kernel cannot be placed: a multi-device
    mesh and a layer the strategy does not know (a fork_join branch
    sub-layer). `batch=None` leaves the batch dim replicated (decode
    slots)."""
    if not multi_device(ctx.mesh):
        return PartitionSpec(None, None, None, None)
    sh = ctx.op_shardings.get(layer.name)
    if sh is None:
        return None
    out0 = sh.outputs[0] if sh.outputs else []
    wq = sh.weights.get("wq") or []
    bdim = dividing(out0[0], batch, ctx.mesh) if out0 and batch else None
    taken = (bdim,) if isinstance(bdim, str) else tuple(bdim or ())
    hdim = dividing(wq[1], heads, ctx.mesh, taken) if len(wq) > 1 else None
    return PartitionSpec(bdim, None, hdim, None)


def _flash_covers(sq: int, sk: int, sv: int, depth: int, itemsize: int,
                  causal: bool) -> bool:
    """The auto path's precheck, from shapes alone: exactly the conditions
    flash_attention() validates (the lengths of q, k and v, a head's
    width)."""
    if sv != sk or (causal and sq != sk):
        return False
    return flash_supported(sq, depth, itemsize) \
        and flash_supported(sk, depth, itemsize)


def _einsum_attention(layer: Layer, qh, kh, vh, sk_orig: int, scale,
                      window: int, ctx: LoweringCtx):
    """The XLA form of a whole sequence: q, k, v `[b, s, h, d]`, the scores
    a value of the program; `sk_orig` the keys that are positions of the
    sequence (add_bias_kv / add_zero_attn append others), `window` the
    band under the causal mask (0: none)."""
    p = layer.params
    logits = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) * scale
    if p.get("causal", False):
        sq, sk = logits.shape[-2], logits.shape[-1]
        # causal band over the ORIGINAL key positions only; positions
        # appended by add_bias_kv/add_zero_attn (indices >= sk_orig, at the
        # end) are always attendable and must not shift the band
        mask = jnp.tril(jnp.ones((sq, sk_orig), bool), k=sk_orig - sq)
        if window:    # the keys t - window < s <= t
            mask &= ~jnp.tril(jnp.ones((sq, sk_orig), bool),
                              k=sk_orig - sq - window)
        if sk > sk_orig:
            mask = jnp.concatenate(
                [mask, jnp.ones((sq, sk - sk_orig), bool)], axis=1)
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits, axis=-1)
    if ctx.training and p.get("dropout", 0.0) > 0.0:
        import jax.random as jrandom

        keep = 1.0 - p["dropout"]
        mask = jrandom.bernoulli(ctx.rng_for(layer), keep, probs.shape)
        probs = jnp.where(mask, probs / keep, 0.0).astype(probs.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vh)


def _mha_lower(layer: Layer, inputs, weights, ctx: LoweringCtx):
    q, k, v = inputs[:3]
    p = layer.params
    if p.get("decode", False) or p.get("kv_out", False):
        if p.get("add_bias_kv", False) or p.get("add_zero_attn", False):
            raise NotImplementedError(
                "KV-cache decode/prefill does not support add_bias_kv/"
                "add_zero_attn (extra key positions would enter the cache)")
    if p.get("decode", False):
        return _mha_decode_lower(layer, inputs, weights, ctx)
    heads = p["num_heads"]
    kvh = _kv_heads(p)
    embed = p["embed_dim"]
    dt = q.dtype

    def proj(x, w, b):
        y = x @ weights[w].astype(dt)
        if b in weights:
            y = y + weights[b].astype(dt)
        return y

    kp = proj(k, "wk", "bk")
    vp = proj(v, "wv", "bv")
    impl = p.get("impl", "auto")
    causal = p.get("causal", False)
    head_dim = embed // heads
    scale = _scale(p, head_dim)
    # flash kernel has no probs-dropout path: fall back (or fail under
    # impl="flash") rather than silently dropping the dropout mask
    needs_dropout = ctx.training and p.get("dropout", 0.0) > 0.0
    if impl == "flash" and needs_dropout:
        raise NotImplementedError("impl='flash' does not support attention-prob "
                                  "dropout; use dropout=0.0 or impl='xla'")
    # "auto" uses the fused pallas kernel only when fusion is enabled
    # (--fusion, reference FusedOp gate) AND the shape qualifies — decided
    # here from shapes, before tracing; impl="flash" forces it regardless. A
    # kernel that was chosen and then raises (trace, Mosaic compile)
    # propagates: it never silently becomes the einsum path.
    extra = ("bias_k" in weights) + bool(p.get("add_zero_attn", False))
    spec = _attn_pspec(layer, ctx, q.shape[0], heads)
    flash = not p.get("selected") and not needs_dropout and (
        impl == "flash" or (impl == "auto" and ctx.enable_fusion
                            and _flash_covers(q.shape[1], k.shape[1] + extra,
                                              v.shape[1] + extra, head_dim,
                                              dt.itemsize, causal)
                            and spec is not None))
    # one device and a head in whole 128-lane blocks (or two heads a block):
    # the flash kernels read q, k, v as the projections wrote them and hand
    # `o` back so, `[b, s, h * d]`; nothing from here to `@ wo` splits them
    # (a mesh's shards split first: a shard's heads are the mesh's)
    merged = flash and not multi_device(ctx.mesh) \
        and entry_of(head_dim, heads, kvh) != "swapped"
    heads_of = (lambda x, n: x) if merged else _split_heads
    turn = _turner(layer, inputs, weights, merged) if _positioned(layer) \
        else None
    if p.get("kv_out", False):
        # serving prefill: expose the per-head K/V of the prompt tokens so
        # the engine can commit them into the paged cache (captured BEFORE
        # any bias_kv/zero_attn positions could pollute the cache; k as the
        # scores see it: after its norm and rotation)
        k_out = heads_of(kp, kvh)
        if turn is not None:
            k_out = turn(k_out, "k_norm")
        ctx.new_state[layer.name] = {
            "k": _split_heads(k_out, kvh) if merged else k_out,
            "v": _split_heads(vp, kvh)}
    if "bias_k" in weights:  # add_bias_kv: learned extra kv position
        b_ = k.shape[0]
        kp = jnp.concatenate([kp, jnp.broadcast_to(weights["bias_k"].astype(dt), (b_, 1, embed))], axis=1)
        vp = jnp.concatenate([vp, jnp.broadcast_to(weights["bias_v"].astype(dt), (b_, 1, embed))], axis=1)
    if p.get("add_zero_attn", False):
        b_ = k.shape[0]
        kp = jnp.concatenate([kp, jnp.zeros((b_, 1, embed), dt)], axis=1)
        vp = jnp.concatenate([vp, jnp.zeros((b_, 1, embed), dt)], axis=1)
    qh = heads_of(proj(q, "wq", "bq"), heads)   # (b, sq, h, d) or as it lies
    kh = heads_of(kp, kvh)
    vh = heads_of(vp, kvh)
    if turn is not None:    # k once: what the prefill twin handed out
        qh = turn(qh, "q_norm")
        kh = k_out if p.get("kv_out", False) else turn(kh, "k_norm")
    out = None
    # a window that holds the sequence is the plain causal mask: `band` is
    # the window where it masks anything, else 0
    window = int(p.get("window") or 0)
    band = window if 0 < window < k.shape[1] else 0
    # the flash kernels on one device read a group's K/V head through the
    # block index; every other form sees one K/V head a query head
    grouped = flash and kvh != heads and not multi_device(ctx.mesh)
    if kvh != heads and not grouped:
        kh = jnp.repeat(kh, heads // kvh, axis=2)
        vh = jnp.repeat(vh, heads // kvh, axis=2)
    if p.get("selected"):
        if not causal or "bias_k" in weights or p.get("add_zero_attn") \
                or q.shape[1] != k.shape[1]:
            raise NotImplementedError("a selected key set on attention that "
                                      "is not causal self-attention")
        out = _selected_sequence_attention(qh, kh, vh, inputs[-1], scale)
    # sequence parallelism: the searched strategy may place this attention
    # on the ring path (sp_ring candidate -> {"seq_parallel": axis} attr)
    sp_axis = ctx.op_attrs.get(layer.name, {}).get("seq_parallel")
    if out is None and sp_axis and multi_device(ctx.mesh) \
            and sp_axis in ctx.mesh.shape \
            and impl != "xla" and qh.shape[1] == kh.shape[1] == vh.shape[1] \
            and qh.shape[1] % ctx.mesh.shape[sp_axis] == 0 \
            and not needs_dropout and "bias_k" not in weights \
            and not p.get("add_zero_attn", False):
        if band:
            raise NotImplementedError(
                f"{layer.name}: ring attention (kernels/ring_attention.py) "
                f"knows no window, and this layer's is {window} under a "
                f"sequence of {k.shape[1]}")
        from flexflow_tpu.kernels.ring_attention import ring_attention_qkv

        out = ring_attention_qkv(qh, kh, vh, ctx.mesh, sp_axis,
                                 causal=causal, scale=scale)
    # a layer that states a window (0: the whole context) says which form
    # its sequence took and what it saw, under its kind's scope
    scope = contextlib.nullcontext() if "window" not in p else \
        jax.named_scope(WINDOW_SCOPE if window else FULL_SCOPE)
    if out is None and "window" in p:
        sq, rows = q.shape[1], q.shape[0] * heads
        triangle, outside = sq * (sq + 1) // 2, (sq - band) * (sq - band + 1) // 2
        ctx.add_stat("window_keys_seen" if window else "full_keys_seen",
                     jnp.float32(rows * (triangle - (outside if band else 0))))
        if window:      # the triangle its band lies under
            ctx.add_stat("window_keys_causal", jnp.float32(rows * triangle))
        form, tile = "xla/masked", {}
        if flash:
            from flexflow_tpu.kernels.flash_attention import tile_plan

            form = "flash/window" if band else "flash/full"
            tile = tile_plan(sq, sq, head_dim, dt.itemsize, causal,
                             band)["fwd"]
        tel.record(form, tel.now_us(), cat="compile", layer=layer.name,
                   window=window, seq=sq, **tile)
    if out is None and flash:
        from flexflow_tpu.kernels.flash_attention import (
            flash_attention_merged, flash_attention_qkv)

        # seq and depth stay whole per shard, so _flash_covers holds there
        # (a forced impl="flash" with no placement goes in unsplit); a call
        # without a window is the call it was before windows existed
        attend = functools.partial(flash_attention_merged, heads=heads) \
            if merged else flash_attention_qkv
        with scope:
            out = per_shard(
                functools.partial(attend, causal=causal, scale=scale,
                                  **({"window": band} if band else {})),
                ctx.mesh if spec is not None else None,
                (spec, spec, spec), spec)(qh, kh, vh)
    if out is None:
        with scope:
            out = _einsum_attention(layer, qh, kh, vh, k.shape[1], scale,
                                    band, ctx)
    b, sq = q.shape[0], q.shape[1]
    out = _gated(out.reshape(b, sq, embed), q, weights)
    y = out @ weights["wo"].astype(dt)
    if "bo" in weights:
        y = y + weights["bo"].astype(dt)
    return [y]


def _selected_sequence_attention(qh, kh, vh, keep, scale):
    """Causal attention of a whole sequence under an indexer's membership
    mask `keep` `[b, s, s]` (already causal): dense, queries in blocks;
    q, k, v `[b, s, h, d]`, one K/V head a query head."""
    dt = qh.dtype
    with jax.named_scope(ATTEND_SCOPE):
        def block(q, m):
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, kh,
                                preferred_element_type=jnp.float32) * scale
            logits = jnp.where(m[:, None], logits,
                               jnp.finfo(jnp.float32).min)
            probs = jax.nn.softmax(logits, axis=-1).astype(dt)
            return jnp.einsum("bhqk,bkhd->bqhd", probs, vh)

        return query_blocks(block, qh.shape[1], qh, keep)


def _mha_flops(layer: Layer):
    """Forward: the four projections (and the output gate's, where the layer
    has one) and the two products over the scores. A layer that states a
    `window` is priced at the (query, key) pairs it may see, the band or (at
    0) the triangle, which is what the flash kernels visit; one without, as
    it always was, at the whole square."""
    q, k = layer.inputs[0].spec, layer.inputs[1].spec
    p = layer.params
    b, sq, e = q.shape
    sk = k.shape[1]
    kv_share = _kv_heads(p) / p["num_heads"]
    # q,k,v,o projections (approx sq≈sk); grouped K/V heads project less
    proj = 2.0 * b * (2 * sq + 2 * sq * kv_share) * e * e
    if p.get("output_gate"):
        proj += 2.0 * b * sq * e * p["embed_dim"]
    pairs = sq * sk
    if "window" in p and sq == sk:
        out = max(0, sq - p["window"]) if p["window"] else 0
        pairs = sq * (sq + 1) // 2 - out * (out + 1) // 2
    attn = 2.0 * b * pairs * e * 2  # qk^T and att@v
    return proj + attn


def _mha_serving_params(params: dict, kind: str) -> dict:
    """The prefill twin hands out its K/V (kv_out), the decode twin reads
    the paged cache; no dropout in either (inference determinism is a
    property of the PROGRAM, not a flag callers must remember)."""
    if kind == "decode":
        # the decode path is its own fixed lowering
        return dict(params, dropout=0.0, decode=True, impl="xla")
    return dict(params, dropout=0.0, kv_out=True)


def _mha_page_state(layer: Layer) -> dict:
    """A token's rows in this layer's K and V pools: the K/V heads (fewer
    than the query heads where they are grouped) of head_dim each."""
    p = layer.params
    state = {"heads": _kv_heads(p),
             "head_dim": int(p["embed_dim"]) // int(p["num_heads"])}
    if p.get("window"):     # a second extent: a ring of the window's pages
        state["window"] = int(p["window"])
    return state


def _mha_span_facts(layer: Layer) -> dict:
    """What a layer with positions or a q/k norm says of them on the serving
    compile span; nothing for a layer without."""
    p = layer.params
    facts = {}
    if _has_positions(layer):
        facts["rope_theta"] = float(p.get("rope_theta", 10000.0))
    if p.get("mrope_section"):
        facts["mrope_section"] = list(p["mrope_section"])
    if p.get("qk_norm"):
        facts["qk_norm"] = True
    if p.get("rope_scaling"):
        facts["rope_scaling_factor"] = float(p["rope_scaling"]["factor"])
    return facts


register_op(OperatorType.MULTIHEAD_ATTENTION, _mha_infer, _mha_lower, _mha_flops,
            serving_params=_mha_serving_params, state_kind="paged_kv",
            page_state=_mha_page_state, span_facts=_mha_span_facts,
            kept_names=(FLASH_KEPT,))


def _sdpa_infer(layer: Layer):
    """Core scaled-dot-product attention (torch.nn.functional.
    scaled_dot_product_attention semantics): q (..., sq, d), k (..., sk, d),
    v (..., sk, dv) -> (..., sq, dv). Optional 4th input: additive float mask
    or boolean keep-mask, broadcastable to (..., sq, sk)."""
    q, k, v = [t.spec for t in layer.inputs[:3]]
    if q.shape[-1] != k.shape[-1]:
        raise ValueError(f"q/k depth mismatch {q.shape} vs {k.shape}")
    return [q.with_shape(q.shape[:-1] + (v.shape[-1],))]


def _sdpa_lower(layer: Layer, inputs, weights, ctx: LoweringCtx):
    q, k, v = inputs[:3]
    mask = inputs[3] if len(inputs) > 3 else None
    p = layer.params
    scale = p.get("scale")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    neg = jnp.finfo(logits.dtype).min
    if mask is not None:
        if jnp.issubdtype(mask.dtype, jnp.bool_):
            logits = jnp.where(mask, logits, neg)
        else:
            logits = logits + mask.astype(logits.dtype)
    if p.get("is_causal", False):
        # torch semantics: TOP-LEFT aligned causal band (tril diagonal=0),
        # not bottom-right like a decode-step band
        sq, sk = logits.shape[-2], logits.shape[-1]
        cmask = jnp.tril(jnp.ones((sq, sk), bool))
        logits = jnp.where(cmask, logits, neg)
    probs = jax.nn.softmax(logits, axis=-1)
    if ctx.training and p.get("dropout_p", 0.0) > 0.0:
        keep = 1.0 - p["dropout_p"]
        dmask = jax.random.bernoulli(ctx.rng_for(layer), keep, probs.shape)
        probs = jnp.where(dmask, probs / keep, 0.0).astype(probs.dtype)
    return [jnp.einsum("...qk,...kd->...qd", probs, v)]


def _sdpa_flops(layer: Layer):
    q, k = layer.inputs[0].spec, layer.inputs[1].spec
    batch = 1
    for d in q.shape[:-2]:
        batch *= d
    sq, d = q.shape[-2], q.shape[-1]
    sk = k.shape[-2]
    return 2.0 * batch * sq * sk * d * 2


register_op(OperatorType.SDPA, _sdpa_infer, _sdpa_lower, _sdpa_flops)
