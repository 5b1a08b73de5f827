"""The learned indexer of DeepSeek sparse attention (DeepSeek-V3.2-Exp's
"lightning indexer"): for every query token a cheap score of every earlier
token, of which the `topk` largest are the keys that token's attention reads.

    qI_t = W_qI h_t                  [heads, head_dim]
    kI_s = LayerNorm(W_kI h_s)       [head_dim]: ONE key head, with a bias
    w_t  = W_w h_t                   [heads]
    qI, kI turned by the rotary positions (rotate-half over the whole head;
    with `mrope_section` the pairs follow the positions' axes by sections)
    I[t, s] = (heads * head_dim)^-1/2 * sum_j w[t, j] relu(qI[t, j] . kI[s])
    S_t = the `topk` positions s <= t of largest I[t, s], ties to the lower
          s; every s <= t while t < topk

The op's output is S_t, and `multihead_attention(selected=)` reads it
(ops/attention_ops.py). Its per-request state is the indexer's key, a page
leaf beside K and V (`state_kind` "paged_index", `{"ik": [pages, page,
width]}`: serving/kv_cache.py): a token leaves `head_dim` values a layer for
later queries to score.

Three forms of one op, as attention has them:

- the whole sequence (training, evaluation; the prefill twin with `kv_out`
  also hands out kI `[b, s, head_dim]` for the cache's commit): the output
  is the membership mask `[b, s, s]` bool, queries in blocks of `Q_BLOCK`;
- a block of `s > 1` tokens over a slot's cache (a prefill chunk, a verify
  pass): the block's kI is appended to the slot's pages at `pos ..`, the
  scores run over the pages the block's context reaches (a quarter, a half,
  three quarters or all of the slot's padded context `L`, whichever rung
  holds it: `context_rungs`, a `lax.switch`), and the output is the mask
  `[b, s, L]`;
- a decode step (`s == 1`): the same append, the same threshold over the
  slot's cached keys, all `L` of them, and the output is the mask `[b, 1,
  L]` as well. Attention's step reads it as it is (its kernel over the live
  slots' pages) or compacts it first (`kept_positions`, below: the kept
  positions `[.., min(topk, L)]` int32 in rising order by counts and two
  small products, no sort, no scatter) and gathers those rows of K and V;
  which, is attention's to choose (ops/attention_ops.py: `step_path`).

The selection is exact. In the mask forms it is a threshold, not a sort: the
scores as order-preserving integers, the `topk`-th largest of a row found
bit by bit (32 counts over the row), and where more than `topk` reach it the
ties by their rank in the row. The scores are float32 sums of float32
products of the compute type's q and k: two keys tie only where their sums
agree to the last bit.

Inputs: h `[b, s, d]`, positions `[b, s]` (or `[b, s, axes]` with
`mrope_section`), and optionally `valid` `[b, s]` (1 = a token is there; a
decode step's live slots), which only the counters read: `sparse_keys_live`
(the s <= t a real query could have kept), `sparse_keys_kept` (those it
kept), `indexer_cache_bytes_read` (the cached kI rows a real query scored),
summed over the layers that report.
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
from flexflow_tpu.dtype import DataType
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.ops.pages import append_slots, pad_row
from flexflow_tpu.ops.registry import LoweringCtx, register_op
from flexflow_tpu.ops.rotary import apply_rope_half, half_tables

INDEX_SCOPE = "ff_sparse_index"
ATTEND_SCOPE = "ff_sparse_attend"
# queries a block of the mask forms: a block's scores of every head are
# [heads, Q_BLOCK, keys] float32 at once (277 MB at 16 heads and 16896 keys)
Q_BLOCK = 256
_INT_MIN = np.int32(-2 ** 31)


def query_blocks(fn, s: int, *arrays):
    """`fn` over blocks of `Q_BLOCK` queries of `arrays` (each `[b, s, ...]`),
    results laid back along axis 1; one call where `s` is a block or less. A
    last block that is not whole is filled with zeros (queries that keep
    nothing and are cut off again)."""
    if s <= Q_BLOCK:
        return fn(*arrays)
    n = -(-s // Q_BLOCK)
    short = n * Q_BLOCK - s
    if short:
        arrays = [jnp.pad(a, [(0, 0), (0, short)] + [(0, 0)] * (a.ndim - 2))
                  for a in arrays]
    split = [jnp.moveaxis(a.reshape(a.shape[:1] + (n, Q_BLOCK) + a.shape[2:]),
                          1, 0) for a in arrays]
    out = jax.lax.map(lambda xs: fn(*xs), tuple(split))   # [n, b, Q_BLOCK, ..]
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape(out.shape[:1] + (n * Q_BLOCK,) + out.shape[3:])[:, :s]


def index_scores(qi, w, ki):
    """I `[b, q, keys]` float32 from qI `[b, q, heads, hd]`, w `[b, q,
    heads]` and kI `[b, keys, hd]`."""
    heads, hd = qi.shape[-2:]
    dots = jnp.einsum("bqjd,bkd->bjqk", qi, ki,
                      preferred_element_type=jnp.float32)
    scale = 1.0 / math.sqrt(heads * hd)
    return jnp.einsum("bjqk,bqj->bqk", jax.nn.relu(dots),
                      w.astype(jnp.float32) * scale)


def _ordered(scores, allowed):
    """The scores as unsigned integers in the same order (-0.0 as +0.0: the
    two compare equal), 0 where a key is not allowed (below every real
    score's, -inf's included)."""
    scores = scores.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0.0, 0.0, scores), jnp.int32)
    keys = jnp.where(bits < 0, bits ^ np.int32(0x7fffffff), bits)
    keys = jnp.where(allowed, keys, _INT_MIN)
    return jax.lax.bitcast_convert_type(keys ^ _INT_MIN, jnp.uint32)


def keep_mask(scores, allowed, topk: int):
    """`[.., keys]` bool: of each row's `allowed` keys the `topk` of largest
    score, ties to the lower index; all of them where there are `topk` or
    fewer."""
    n = scores.shape[-1]
    allowed = jnp.broadcast_to(allowed, scores.shape)
    if topk >= n:
        return allowed
    keys = _ordered(scores, allowed)

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= cand[..., None], axis=-1,
                         dtype=jnp.int32) >= topk
        return jnp.where(enough, cand, thr)

    # the topk-th largest key of each row, from its highest bit down
    thr = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(keys.shape[:-1], jnp.uint32))[..., None]
    reach = (keys >= thr) & allowed
    excess = jnp.sum(reach, axis=-1, dtype=jnp.int32) - topk

    def by_rank(_):
        above = keys > thr
        tied = (keys == thr) & allowed
        room = topk - jnp.sum(above, axis=-1, dtype=jnp.int32)
        rank = jnp.cumsum(tied.astype(jnp.int32), axis=-1)
        return (above & allowed) | (tied & (rank <= room[..., None]))

    return jax.lax.cond(jnp.any(excess > 0), by_rank, lambda _: reach, None)


def kept_positions(mask, k: int):
    """`[.., n]` bool with at most `k` set -> `[.., k]` int32: the set
    positions in rising order, then `n` in the places that are left (what
    the XLA form of attention's decode step gathers rows by). No sort
    and no scatter: the row in blocks of 128, a key's rank inside its block
    by one product with a triangle, the block of output place j from the
    blocks' running counts, that block's ranks by one product with a one-hot
    row, and the lane whose rank is j's. Every product is of whole numbers
    up to 128 with ones: exact in any float type."""
    n = mask.shape[-1]
    lead = mask.shape[:-1]
    blocks = -(-n // 128)
    m = jnp.pad(mask, [(0, 0)] * len(lead) + [(0, blocks * 128 - n)])
    m = m.reshape(lead + (blocks, 128))
    tri = jnp.asarray(np.triu(np.ones((128, 128), np.float32)), jnp.bfloat16)
    rank = jnp.einsum("...bi,ij->...bj", m.astype(jnp.bfloat16), tri,
                      preferred_element_type=jnp.float32)    # 1-based, kept
    ends = jnp.cumsum(rank[..., -1], axis=-1).astype(jnp.int32)   # [.., blocks]
    place = jnp.arange(k, dtype=jnp.int32)
    block = jnp.sum(ends[..., None, :] <= place[:, None], axis=-1,
                    dtype=jnp.int32)                         # [.., k]
    valid = block < blocks
    block = jnp.minimum(block, blocks - 1)
    start = jnp.take_along_axis(
        jnp.concatenate([jnp.zeros(lead + (1,), jnp.int32), ends[..., :-1]],
                        axis=-1), block, axis=-1)
    onehot = (block[..., None] == jnp.arange(blocks)).astype(jnp.bfloat16)
    ranks = jnp.einsum("...kb,...bl->...kl", onehot,
                       jnp.where(m, rank, 0.0).astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)   # [.., k, 128]
    want = (place - start + 1).astype(jnp.float32)[..., None]
    lane = jnp.sum(jnp.where(ranks == want, jnp.arange(128, dtype=jnp.int32),
                             0), axis=-1, dtype=jnp.int32)
    return jnp.where(valid, block * 128 + lane, n).astype(jnp.int32)


def context_rungs(pages: int) -> tuple:
    """The key extents, in pages, a block over a slot's cache may run over:
    a quarter, a half, three quarters and all of the slot's pages."""
    return tuple(sorted({max(1, -(-pages * i // 4)) for i in (1, 2, 3, 4)}))


def over_context(fn, end, pages: int, page: int):
    """`fn(key pages)` at the smallest rung of `context_rungs(pages)` whose
    keys hold every position under `end` (a traced scalar: the block's
    largest position + 1); every rung's result has one shape."""
    rungs = context_rungs(pages)
    which = sum((end > r * page).astype(jnp.int32) for r in rungs[:-1])
    return jax.lax.switch(which, [lambda r=r: fn(r) for r in rungs])


def _infer(layer: Layer):
    x = layer.inputs[0].spec
    p = layer.params
    d = x.shape[-1]
    heads, hd = p["heads"], p["head_dim"]
    layer.weight_specs = {
        "wq": TensorSpec((d, heads * hd), x.dtype),
        "wk": TensorSpec((d, hd), x.dtype),
        "k_norm": TensorSpec((hd,), x.dtype),
        "k_norm_bias": TensorSpec((hd,), x.dtype),
        "ww": TensorSpec((d, heads), x.dtype),
    }
    b, s = x.shape[:2]
    # the membership mask; over a cache (a block, a decode step) its key axis
    # is the slot's padded context, which the cache knows and the graph does
    # not
    return [TensorSpec((b, s, s), DataType.BOOL)]


def _projections(layer: Layer, inputs, weights):
    """(qI `[b, s, heads, hd]`, kI `[b, s, hd]`, w `[b, s, heads]`), kI
    normed, both turned: what the scores and the cache see."""
    x, positions = inputs[0], inputs[1]
    p = layer.params
    heads, hd = p["heads"], p["head_dim"]
    dt = x.dtype
    b, s, _ = x.shape
    qi = (x @ weights["wq"].astype(dt)).reshape(b, s, heads, hd)
    ki = (x @ weights["wk"].astype(dt)).astype(jnp.float32)
    mean = jnp.mean(ki, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(ki - mean), axis=-1, keepdims=True)
    ki = ((ki - mean) * jax.lax.rsqrt(var + p["eps"])
          * weights["k_norm"].astype(jnp.float32)
          + weights["k_norm_bias"].astype(jnp.float32)).astype(dt)
    w = x @ weights["ww"].astype(dt)
    cos, sin = half_tables(positions, hd, p["rope_theta"],
                           p.get("mrope_section"))
    qi = apply_rope_half(qi, cos[:, :, None], sin[:, :, None])
    ki = apply_rope_half(ki, cos, sin)
    return qi, ki, w


def _report(ctx, live, kept, cached_rows, layer: Layer, itemsize: int):
    """The counters: `live` and `kept` `[b, s]` keys a query could keep and
    kept (0 where no token is), `cached_rows` the kI rows read from pages."""
    ctx.add_stat("sparse_keys_live", jnp.sum(live).astype(jnp.float32))
    ctx.add_stat("sparse_keys_kept", jnp.sum(kept).astype(jnp.float32))
    ctx.add_stat("indexer_cache_bytes_read",
                 jnp.sum(cached_rows).astype(jnp.float32)
                 * float(layer.params["head_dim"] * itemsize))


def _lower_cached(layer: Layer, inputs, weights, ctx: LoweringCtx):
    """A block of `s` tokens a slot over the paged cache: the append, the
    scores over the slot's pages, the selection."""
    p = layer.params
    x = inputs[0]
    b, s = x.shape[:2]
    qi, ki, w = _projections(layer, inputs, weights)
    pool = ctx.state[layer.name]["ik"]
    pt = ctx.state["serve/page_table"]
    pos = ctx.state["serve/pos"]
    page = pool.shape[1]
    t, pageix, off = append_slots(pt, pos, s, page)
    pool = pool.at[pageix, off].set(
        pad_row(ki, pool.shape[-1]).astype(pool.dtype))
    ctx.new_state[layer.name] = {"ik": pool}
    valid = (inputs[2] > 0) if len(inputs) > 2 else jnp.ones((b, s), bool)
    n = pt.shape[1] * page
    topk = min(p["topk"], n)
    live = jnp.where(valid, jnp.minimum(t + 1, n), 0)

    def kept_over(pages):
        """The mask `[b, s, n]` from the scores of the first `pages` pages
        of every row's table (False behind them)."""
        cached = pool[pt[:, :pages]].reshape(b, pages * page, -1)
        cached = cached[..., :p["head_dim"]].astype(x.dtype)
        where = jnp.arange(pages * page)[None, None, :]
        mask = query_blocks(
            lambda q, wt, tq: keep_mask(
                index_scores(q, wt, cached), where <= tq[:, :, None], topk),
            s, qi, w, t)
        return jnp.pad(mask, [(0, 0), (0, 0), (0, n - pages * page)])

    with jax.named_scope(INDEX_SCOPE):
        if s == 1:
            out = kept_over(pt.shape[1])
            kept = jnp.minimum(live, topk)
        else:
            out = over_context(kept_over, jnp.max(t) + 1, pt.shape[1], page)
            kept = jnp.where(valid, jnp.sum(out, axis=-1, dtype=jnp.int32), 0)
    _report(ctx, live, kept, live, layer, pool.dtype.itemsize)
    return [out]


def _lower(layer: Layer, inputs, weights, ctx: LoweringCtx):
    p = layer.params
    if p.get("decode"):
        return _lower_cached(layer, inputs, weights, ctx)
    x = inputs[0]
    b, s = x.shape[:2]
    qi, ki, w = _projections(layer, inputs, weights)
    if p.get("kv_out"):
        ctx.new_state[layer.name] = {"ik": ki}
    with jax.named_scope(INDEX_SCOPE):
        where = jnp.arange(s)[None, None, :]
        at = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        mask = query_blocks(
            lambda q, wt, tq: keep_mask(index_scores(q, wt, ki),
                                        where <= tq[:, :, None],
                                        min(p["topk"], s)),
            s, qi, w, at)
    if ctx.stats is not None:
        valid = (inputs[2] > 0) if len(inputs) > 2 else jnp.ones((b, s), bool)
        _report(ctx, jnp.where(valid, at + 1, 0),
                jnp.where(valid, jnp.sum(mask, axis=-1, dtype=jnp.int32), 0),
                jnp.zeros((), jnp.int32), layer, x.dtype.itemsize)
    return [mask]


def _flops(layer: Layer):
    """Forward: the three projections and the scores of the causal half."""
    x = layer.inputs[0].spec
    p = layer.params
    b, s, d = x.shape
    width = p["heads"] * p["head_dim"]
    return 2.0 * b * s * d * (width + p["head_dim"] + p["heads"]) \
        + 2.0 * b * s * s / 2 * width


def _serving_params(params: dict, kind: str) -> dict:
    return dict(params, decode=True) if kind == "decode" \
        else dict(params, kv_out=True)


register_op(OperatorType.SPARSE_INDEXER, _infer, _lower, _flops,
            serving_params=_serving_params, state_kind="paged_index",
            page_state=lambda layer: {"index_dim": layer.params["head_dim"]},
            span_facts=lambda layer: {
                "sparse_topk": layer.params["topk"],
                "index_heads": layer.params["heads"],
                "index_dim": layer.params["head_dim"]})
