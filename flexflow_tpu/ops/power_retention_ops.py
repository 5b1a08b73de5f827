"""Power retention (Manifest AI, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239): linear attention whose kernel is the degree-2
power of the dot product, over a gated recurrent state a K/V head, in the
layout of the `brumby` decoders' retention layers.

With x `[T, d]`, H query heads and J K/V heads of width D (query head h reads
group j = h // (H / J)), RoPE the rotate-half rotary embedding over the whole
head and RMS_D the RMS norm over a head:

    q = RoPE(RMS_D(x W_q; w_q))   [H, D]     k = RoPE(RMS_D(x W_k; w_k))  [J, D]
    v = x W_v                     [J, D]     log g = logsigmoid(x W_g)    [J]
    G_t = sum_{r <= t} log g_r
    pair form, s <= t:  a_{t,s} = exp(G_t - G_s) (q_t . k_s)^2
                        y_t = sum_s a_{t,s} v_s / (sum_s a_{t,s} + eps)
    state form:         S_t = g_t S_{t-1} + phi(k_t) v_t^T      [P, D] f32
                        z_t = g_t z_{t-1} + phi(k_t)            [P]    f32
                        y_t = S_t^T phi(q_t) / (z_t . phi(q_t) + eps)
    out = concat_h(y_h) W_o

phi(w) is the symmetric power embedding of degree 2: the P = D (D + 1) / 2
products w_a w_b with a <= b, those with a < b times sqrt 2, so that phi(q) .
phi(k) = (q . k)^2 and the two forms are the same numbers.

How the state lies at rest has ONE definition, `_row_pairs` (the one-hot
picks of `_embedding_tables`, which every form builds and reads the state
through): row r holds the pair (a_r, b_r) and is read with the weight c_r. In
a head that is not whole 128-lane slabs the rows are the symmetric half
exactly, in `np.triu_indices(D)` order, and z is `[P]` in the same order.
Where a head is whole slabs (D = 128, the published width) the values a lie
in blocks of 8 and every a of block A has the run b in [8 A, D)
(`kernels/retention_step.row_block`): `laid_rows` = 8704 rows for P = 8256,
every run whole f32 tiles; the rows with b < a that this adds inside a
diagonal block hold the mirror products and are read with weight 0; z lies as
the `[D, D]` square of k_a k_b. `state_rows` stays P, the rows the
recurrence needs: what `linear_state_bytes` and the models' byte counts
report is the need, not the allocation.

Three forms of one op, chosen by `params["mode"]`:

- None (training, evaluation): the whole sequence (`retention_sequence`).
- "state_out" (serving prefill): the same, and the state after each row's
  last real token is handed out in `ctx.new_state[layer.name] = {"S": [b, J,
  P, D] f32, "z": [b, J, P] f32}`. Reports (ctx.add_stat)
  `retention_layers`, 1 a layer, and `retention_rows`, the rows of the wave
  it computed (a row that holds no request is skipped).
- "decode" (serving decode): one step of the recurrence on
  `ctx.state[layer.name]` for the slots `valid` names, written back to
  `ctx.new_state` (`retention_step`). Reports `linear_state_bytes`: the
  state those slots must read and write (`state_rows`, not the rows laid),
  and in a `retention/step_path` span which form the step took.

The inputs: x, `positions` `[b, s]` (the rotary angle's position) and
`valid` `[b, s]` (int, 1 = a real token). At a position that does not exist
log g = 0 and k = 0: the state neither decays nor takes anything in, so a
right-padded prompt wave hands out each row's state after its LAST REAL
token.

The sequence form has two regimes, chosen from the shapes alone
(`sequence_chunk`): up to `chunk + P / 2` positions the masked pair form over
the whole sequence (4 D L operations a query head and token as XLA computes
the square, against the state form's 2 P D read-out), with the state built
once at the end (`phi(k)^T v` over the sequence: the decode step needs it);
past that crossover chunks of `chunk_steps(D)` positions, the pair form
inside a chunk and the state between chunks. One code path: the first
regime is the second with one chunk. A lowered layer says which in a
`retention/path` span.

Two named scopes: `ff_power_retention_scan` (everything between the rotated
q, k, v and y, the state build among it; plain XLA) and
`ff_power_retention_step`. Products take their
operands in the compute type and accumulate in float32; gates, cumulative
sums, decays, S and z are float32. phi of a `[.., D]` operand is two
products with constant one-hot `[D, R]` matrices (exact: each output is one
input) and their product a row, so that no gather and no `[.., D, D]` value
is formed.

The decode step has two forms, chosen from the shapes (`step_path`), and in
both a slot that is not live is neither read nor written. Where a head is
whole 128-lane slabs: the Pallas kernel `kernels/retention_step.py`, a grid
over (live slot, K/V head) whose first bound is the live count, the update
and the float32 read-out of the new state on one tile in VMEM, the slot's
state read from HBM once and written once in place. Elsewhere, and as the
form the kernel is tested against: plain XLA, a `fori_loop` over a
compaction of `valid`, each turn taking one slot's state out of the array
(a copy), updating it in place and reading it out in float32 sums: five
passes over the slot's state where the kernel makes two.
"""

from __future__ import annotations

import functools
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

if TYPE_CHECKING:
    from flexflow_tpu.core.layer import Layer
from flexflow_tpu import telemetry as tel
from flexflow_tpu.core.tensor import TensorSpec
from flexflow_tpu.kernels import retention_step as step_kernel
from flexflow_tpu.ops.norm_ops import rms_norm
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.ops.registry import LoweringCtx, register_op, rows_taken
from flexflow_tpu.ops.rotary import apply_rope_half, half_tables

SCAN_SCOPE = "ff_power_retention_scan"
STEP_SCOPE = "ff_power_retention_step"
# the most positions a chunk of the state form holds (its `[chunk, chunk]`
# pairs), and the least
MAX_CHUNK = 1024
MIN_CHUNK = 16
# the sequence form takes a long input in blocks of about this many tokens
# (lax.map over groups of rows: one row of a `[16, 1024]` prompt wave), so
# that the `[rows, heads, L, L]` pairs and the `[rows, L, J, P]` key rows
# stay a fraction of a prefill wave's, and so that a row without a request
# costs nothing
RETENTION_TOKEN_BLOCK = 1024


def _sizes(p):
    return p["heads"], p["kv_heads"], p["head_dim"]


def state_rows(head_dim: int) -> int:
    """P: the rows of a K/V head's state that the recurrence needs, the
    symmetric half of D x D."""
    return head_dim * (head_dim + 1) // 2


def laid_rows(head_dim: int) -> int:
    """The rows of a K/V head's state as it lies at rest (`_row_pairs`):
    P, or 8704 for 8256 where a head is whole 128-lane slabs."""
    return sum(step_kernel.block_rows(head_dim))


@functools.lru_cache(maxsize=None)
def _row_pairs(head_dim: int):
    """(a [R], b [R], weight [R]): row r of the state holds the products of
    k_a and k_b and is read with `weight` (1 where a = b, 2 where a < b:
    the square of the symmetric embedding's sqrt 2; 0 where a > b, the
    mirror rows that whole tiles add). The values a in blocks of
    `row_block(D)`, each a of a block with the run b from the block's first
    a to D: with blocks of 1, `np.triu_indices(D)`."""
    blk = step_kernel.row_block(head_dim)      # 1, or 8 dividing D
    firsts = range(0, head_dim, blk)
    a = np.concatenate([np.repeat(np.arange(lo, lo + blk), head_dim - lo)
                        for lo in firsts])
    b = np.concatenate([np.tile(np.arange(lo, head_dim), blk)
                        for lo in firsts])
    weight = np.where(a == b, 1.0, np.where(a < b, 2.0, 0.0))
    return a, b, weight.astype(np.float32)


def _square_normaliser(head_dim: int) -> bool:
    """Whether z lies as the `[D, D]` square of k_a k_b (where the rows
    are laid in whole tiles) and not as `[P]` in the rows' order."""
    return step_kernel.row_block(head_dim) > 1


def _rows_of_square(square):
    """`[.., D, D]` -> `[.., R]`: entry (a_r, b_r) a row of `_row_pairs`."""
    a, b, _weight = _row_pairs(square.shape[-1])
    return square[..., a, b]


def _normaliser_at_rest(pairs):
    """The `[.., D, D]` sums of decayed k_a k_b as z lies at rest."""
    return pairs if _square_normaliser(pairs.shape[-1]) \
        else _rows_of_square(pairs)


def _normaliser_rows(total, head_dim: int):
    """z at rest -> `[.., R]` in the order of the state's rows."""
    return _rows_of_square(total) if _square_normaliser(head_dim) else total


def chunk_steps(head_dim: int) -> int:
    """The positions of a chunk of the state form: 8 D, within [MIN_CHUNK,
    MAX_CHUNK] (1024 at D = 128)."""
    return max(MIN_CHUNK, min(MAX_CHUNK, 8 * head_dim))


def sequence_chunk(length: int, head_dim: int) -> int:
    """The chunk the sequence form takes `length` positions in: all of them
    (the pair form, the state built at the end) up to the crossover `chunk +
    P / 2`, `chunk_steps` past it."""
    chunk = chunk_steps(head_dim)
    return length if length <= chunk + state_rows(head_dim) // 2 else chunk


def sequence_path(length: int, head_dim: int) -> dict:
    """What a lowered layer reports in its `retention/path` span."""
    chunk = sequence_chunk(length, head_dim)
    return {"path": "pair" if chunk == length else "chunked_state",
            "chunk": chunk}


def _state_shapes(kv_heads: int, head_dim: int):
    """(S, z) of one slot as they lie at rest."""
    z = (head_dim, head_dim) if _square_normaliser(head_dim) \
        else (state_rows(head_dim),)
    return (kv_heads, laid_rows(head_dim), head_dim), (kv_heads,) + z


def _embedding_tables(head_dim: int):
    """(left [D, R], right [D, R], twice [R]): row r = (a, b) of
    `_row_pairs` picks w_a and w_b; `twice` is the row's weight on the
    query side."""
    a, b, weight = _row_pairs(head_dim)
    rows = np.arange(a.size)
    left = np.zeros((head_dim, a.size), np.float32)
    right = np.zeros((head_dim, a.size), np.float32)
    left[a, rows] = 1.0
    right[b, rows] = 1.0
    return left, right, weight


def key_rows(k):
    """The key side of the embedding over k's last axis, `[.., D] -> [..,
    R]` float32: k_a k_b a row of `_row_pairs`, EXACT (the two picks copy
    one input each, and a product of two bfloat16 numbers has 16
    significant bits)."""
    left, right, _twice = _embedding_tables(k.shape[-1])
    exact = jax.lax.Precision.HIGHEST if k.dtype == jnp.float32 else None
    pick = lambda m: jnp.einsum(                         # noqa: E731
        "...d,dp->...p", k, jnp.asarray(m, k.dtype), precision=exact,
        preferred_element_type=k.dtype).astype(jnp.float32)
    return pick(left) * pick(right)


def query_rows(q):
    """The query side, float32: q_a q_b times the row's weight, so that
    `query_rows(q) . key_rows(k) = (q . k)^2`."""
    return key_rows(q) * _embedding_tables(q.shape[-1])[2]


def _rows_product(spec: str, rows, other):
    """einsum(spec, rows, other) with f32 accumulation where `rows` is
    float32 holding products of two numbers of `other`'s type: as it is in
    float32, else as two terms of that type (hi + lo: exact for 16
    significant bits), two products."""
    f32 = jnp.float32
    if other.dtype == f32:
        return jnp.einsum(spec, rows, other,
                          precision=jax.lax.Precision.HIGHEST)
    hi = rows.astype(other.dtype)
    lo = (rows - hi.astype(f32)).astype(other.dtype)
    return jnp.einsum(spec, hi, other, preferred_element_type=f32) \
        + jnp.einsum(spec, lo, other, preferred_element_type=f32)


def _retention_infer(layer: Layer):
    x = layer.inputs[0].spec
    heads, kv, hd = _sizes(layer.params)
    if heads % kv:
        raise ValueError(f"power_retention: {heads} query heads do not "
                         f"divide into {kv} K/V heads")
    if hd % 2:
        raise ValueError("power_retention: head_dim must be even (rotary "
                         "pairs)")
    d = x.shape[-1]
    layer.weight_specs = {
        "wq": TensorSpec((d, heads * hd), x.dtype),
        "wk": TensorSpec((d, kv * hd), x.dtype),
        "wv": TensorSpec((d, kv * hd), x.dtype),
        "wg": TensorSpec((d, kv), x.dtype),
        "q_norm": TensorSpec((hd,), x.dtype),
        "k_norm": TensorSpec((hd,), x.dtype),
        "wo": TensorSpec((heads * hd, d), x.dtype),
    }
    return [x]


def _chunk_pairs(q, k, v, cum):
    """The pair form inside chunks: q `[b, n, c, J, G, D]`, k and v `[b, n,
    c, J, D]`, cum `[b, n, c, J]` f32 (G_t from the chunk's start) -> (num
    `[b, n, c, J, G, D]` f32, den `[b, n, c, J, G]` f32)."""
    f32 = jnp.float32
    c = q.shape[2]
    scores = jnp.einsum("bntjgd,bnsjd->bnjgts", q, k,
                        preferred_element_type=f32)
    run = jnp.moveaxis(cum, 2, 3)                           # [b, n, J, c]
    row, col = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    fall = jnp.where(row >= col, run[..., :, None] - run[..., None, :],
                     -jnp.inf)                              # G_t - G_s <= 0
    a = jnp.square(scores) * jnp.exp(fall)[:, :, :, None]
    num = jnp.einsum("bnjgts,bnsjd->bntjgd", a.astype(q.dtype), v,
                     preferred_element_type=f32)
    return num, jnp.moveaxis(jnp.sum(a, axis=-1), -1, 2)


def _chunk_state(k, v, cum):
    """What a chunk adds to the state by its end: k, v `[b, c, J, D]`, cum
    `[b, c, J]` -> (`sum_s key_rows(k_s) (e^{G_c - G_s} v_s)^T` `[b, J, R,
    D]`, `sum_s e^{G_c - G_s} k_s (x) k_s` as z lies at rest), float32. The
    key rows enter exactly (`_rows_product`); the decay rides on v, whose
    rounding is a relative error of the token's own term."""
    f32 = jnp.float32
    left = jnp.exp(cum[:, -1:] - cum)                       # [b, c, J]
    vd = (v.astype(f32) * left[..., None]).astype(v.dtype)
    # the normaliser's sum as the `[D, D]` product it is: no pass over the
    # `[.., c, J, R]` rows
    kf = k.astype(f32)
    pairs = jnp.einsum("rcjx,rcjy->rjxy", kf * left[..., None], kf,
                       precision=jax.lax.Precision.HIGHEST)
    return (_rows_product("bcjp,bcjd->bjpd", key_rows(k), vd),
            _normaliser_at_rest(pairs))


def _sequence(q, k, v, log_g, eps, chunk):
    b, length, heads, hd = q.shape
    kv = k.shape[2]
    group = heads // kv
    f32 = jnp.float32
    pad = -length % chunk
    if pad:     # steps with log g = 0 and k = 0: the state stays
        q, k, v, log_g = (
            jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
            for t in (q, k, v, log_g))
    n = (length + pad) // chunk
    q = q.reshape(b, n, chunk, kv, group, hd)
    k, v = (t.reshape(b, n, chunk, kv, hd) for t in (k, v))
    cum = jnp.cumsum(log_g.astype(f32).reshape(b, n, chunk, kv), axis=2)
    if n == 1:
        num, den = _chunk_pairs(q, k, v, cum)
        state, total = _chunk_state(k[:, 0], v[:, 0], cum[:, 0])
    else:
        def carry(held, xs):
            state, total = held
            q_c, k_c, v_c, cum_c = xs
            num_c, den_c = (t[:, 0] for t in _chunk_pairs(
                q_c[:, None], k_c[:, None], v_c[:, None], cum_c[:, None]))
            pq = query_rows(q_c)                            # [b, c, J, G, P]
            decayed = jnp.exp(cum_c)[..., None]             # [b, c, J, 1]
            hi = jax.lax.Precision.HIGHEST
            num_c += decayed[..., None] * jnp.einsum(
                "bcjgp,bjpd->bcjgd", pq, state, precision=hi)
            den_c += decayed * jnp.einsum(
                "bcjgp,bjp->bcjg", pq, _normaliser_rows(total, hd),
                precision=hi)
            add_s, add_z = _chunk_state(k_c, v_c, cum_c)
            fall = jnp.exp(cum_c[:, -1])                    # [b, J]
            return ((state * fall[..., None, None] + add_s,
                     total * fall.reshape(fall.shape + (1,) * (total.ndim - 2))
                     + add_z), (num_c, den_c))

        (state, total), (num, den) = jax.lax.scan(
            carry, tuple(jnp.zeros((b,) + shape, f32)
                         for shape in _state_shapes(kv, hd)),
            tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, cum)))
        num, den = jnp.moveaxis(num, 0, 1), jnp.moveaxis(den, 0, 1)
    y = (num / (den[..., None] + eps)).reshape(b, n * chunk, heads, hd)
    return y[:, :length], state, total


def retention_sequence(q, k, v, log_g, eps: float, valid=None, into=None):
    """The whole sequence from an empty state: q `[b, L, H, D]`, k and v
    `[b, L, J, D]` (the compute type), log_g `[b, L, J]` f32 (<= 0; 0 and k
    = 0 where no token is). Returns (y `[b, L, H, D]` in q's type, S `[b, J,
    R, D]` f32, z `[b, J, P]` or `[b, J, D, D]` f32: the state after step L
    as it lies at rest).

    One algorithm for every caller, the regime from the shapes
    (`sequence_chunk`), rows in blocks of RETENTION_TOKEN_BLOCK tokens, one
    block a turn of a loop that puts each block's y and state where they
    belong. With `valid` `[b, L]` bool the loop visits only the blocks that
    hold a token (a compaction, as the decode step's): a prompt wave is
    mostly rows without a request, whose y and state are 0 and cost nothing.
    With `into` = (S, z) `[b, ...]` (a serving wave's slot arrays) the state
    goes into them: a row that holds a token is overwritten, a row that
    holds none is neither read nor written."""
    b, length, heads, hd = q.shape
    kv = k.shape[2]
    chunk = sequence_chunk(length, hd)
    rows = max(1, RETENTION_TOKEN_BLOCK // length)
    f32 = jnp.float32

    with jax.named_scope(SCAN_SCOPE):
        if b <= rows or b % rows:
            y, state, total = _sequence(q, k, v, log_g, eps, chunk)
            if into is not None:
                took = jnp.any(valid, axis=1)
                state, total = (rows_taken(state, into[0], took),
                                rows_taken(total, into[1], took))
            return y.astype(q.dtype), state, total
        blocks = b // rows
        if valid is None:       # every block, a static count (differentiable)
            order, count = jnp.arange(blocks), blocks
        else:
            held = jnp.any(valid.reshape(blocks, -1), axis=1)
            order = jnp.argsort(jnp.logical_not(held), stable=True)
            count = jnp.sum(held.astype(jnp.int32))
        if into is None:
            into = tuple(jnp.zeros((b,) + shape, f32)
                         for shape in _state_shapes(kv, hd))

        def turn(i, held_so_far):
            y_all, s_all, z_all = held_so_far
            at = order[i] * rows
            take = lambda t: jax.lax.dynamic_slice_in_dim(      # noqa: E731
                t, at, rows, 0)
            put = lambda t, x: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731
                t, x.astype(t.dtype), at, 0)
            y, state, total = _sequence(take(q), take(k), take(v),
                                        take(log_g), eps, chunk)
            if valid is not None and rows > 1:
                took = jnp.any(take(valid), axis=1)
                state = rows_taken(state, take(s_all), took)
                total = rows_taken(total, take(z_all), took)
            return put(y_all, y), put(s_all, state), put(z_all, total)

        return jax.lax.fori_loop(
            0, count, turn, (jnp.zeros(q.shape, q.dtype),) + tuple(into))


def rows_computed(valid):
    """How many rows of a `[b, L]` wave `retention_sequence` computes:
    those of the blocks that hold a token."""
    b, length = valid.shape
    rows = max(1, RETENTION_TOKEN_BLOCK // length)
    if b <= rows or b % rows:
        return jnp.asarray(b, jnp.int32)
    return rows * jnp.sum(jnp.any(valid.reshape(b // rows, -1), axis=1)
                          .astype(jnp.int32))


def step_path(heads: int, kv_heads: int, head_dim: int) -> dict:
    """Which form a decode step takes, from the shapes: what a lowered layer
    reports in its `retention/step_path` span."""
    kernel = step_kernel.step_supported(head_dim, heads // kv_heads)
    return {"path": "kernel" if kernel else "xla",
            "laid_rows": laid_rows(head_dim)}


def _step_xla(state, total, q, k, v, log_g, live, eps):
    """`retention_step` in plain XLA: a loop over the live slots alone, each
    turn taking one slot's state where it lies (a dynamic slice of the
    donated array: a copy), and putting it back."""
    b, heads, hd = q.shape
    kv = k.shape[1]
    f32 = jnp.float32
    pq = query_rows(q).reshape(b, kv, heads // kv, -1)
    pk = key_rows(k)
    # phi(k) as z lies at rest
    kf = k.astype(f32)
    pk_z = kf[..., :, None] * kf[..., None, :] if _square_normaliser(hd) \
        else pk
    order = jnp.argsort(jnp.logical_not(live), stable=True)

    def turn(i, held):
        state, total, y = held
        slot = order[i]
        take = lambda t: jax.lax.dynamic_index_in_dim(      # noqa: E731
            t, slot, keepdims=False)
        gate = jnp.exp(take(log_g))                          # [J]
        pk_i, pq_i = take(pk), take(pq)
        v_i = take(v).astype(f32)
        old, sums = take(state), take(total)
        # the read-out of the NEW state from the old one and the step's
        # own term, so that the slot's state is read by two independent
        # passes (this sum, and the update below) and no `[J, R, D]`
        # value lies between them. float32 throughout, as sums and not
        # as products on the MXU: the rows of a read-out cancel to a
        # fortieth of their size
        own = jnp.sum(pq_i * pk_i[:, None], axis=2)          # (q . k)^2
        num = gate[:, None, None] \
            * jnp.sum(pq_i[..., None] * old[:, None], axis=2) \
            + own[..., None] * v_i[:, None]
        den = gate[:, None] * jnp.sum(
            pq_i * _normaliser_rows(sums, hd)[:, None], axis=2) + own
        new = old * gate[:, None, None] + pk_i[..., None] * v_i[:, None, :]
        sums = sums * gate.reshape((kv,) + (1,) * (sums.ndim - 1)) \
            + take(pk_z)
        put = lambda t, x: jax.lax.dynamic_update_index_in_dim(  # noqa: E731
            t, x, slot, 0)
        return (put(state, new), put(total, sums),
                put(y, num / (den[..., None] + eps)))

    state, total, y = jax.lax.fori_loop(
        0, jnp.sum(live.astype(jnp.int32)), turn,
        (state, total, jnp.zeros((b, kv, heads // kv, hd), f32)))
    return y.reshape(b, heads, hd), state, total


def retention_step(state, total, q, k, v, log_g, live, eps: float):
    """One step of the recurrence for the slots `live` names: state `[b, J,
    R, D]` and total (`[b, J, P]` or `[b, J, D, D]`) f32 as they lie at
    rest, q `[b, H, D]`, k and v `[b, J, D]` (the compute type), log_g `[b,
    J]` f32, live `[b]` bool -> (y `[b, H, D]` f32, 0 for a slot that is not
    live; the new state and total; a slot that is not live keeps its bytes).

    One result for every caller, the form from the shapes (`step_path`): the
    kernel where a head is whole 128-lane slabs (the state passes through
    the chip once), the XLA loop elsewhere."""
    heads, hd = q.shape[1:]
    with jax.named_scope(STEP_SCOPE):
        if step_path(heads, k.shape[1], hd)["path"] == "kernel":
            return step_kernel.retention_step(
                state, total, q, k, v, jnp.exp(log_g), live, eps)
        return _step_xla(state, total, q, k, v, log_g, live, eps)


def _retention_lower(layer: Layer, inputs, weights, ctx: LoweringCtx):
    x, positions = inputs[0], inputs[1]
    p = layer.params
    heads, kv, hd = _sizes(p)
    eps = p.get("eps", 1e-6)
    dt = x.dtype
    b, s, _d = x.shape
    valid = (inputs[2] > 0) if len(inputs) > 2 else jnp.ones((b, s), bool)
    f32 = jnp.float32

    cos, sin = half_tables(positions, hd, p.get("rope_theta", 10000.0))
    cos, sin = cos[:, :, None], sin[:, :, None]             # [b, s, 1, D]

    def rotated(w, norm, n):
        t = (x @ weights[w].astype(dt)).reshape(b, s, n, hd)
        return apply_rope_half(rms_norm(t, weights[norm], eps), cos, sin)

    q = rotated("wq", "q_norm", heads)
    k = jnp.where(valid[..., None, None], rotated("wk", "k_norm", kv), 0)
    v = (x @ weights["wv"].astype(dt)).reshape(b, s, kv, hd)
    log_g = jnp.where(valid[..., None], jax.nn.log_sigmoid(
        (x @ weights["wg"].astype(dt)).astype(f32)), 0.0)   # [b, s, J]

    if p.get("mode") == "decode":
        if s != 1:
            raise NotImplementedError(
                "power_retention decode takes one token a step (a verify "
                "pass over several would have to roll the state back)")
        st = ctx.state[layer.name]
        # one span a lowered layer (trace time): the form its step took
        with tel.span("retention/step_path", cat="compile", layer=layer.name,
                      **step_path(heads, kv, hd)):
            y, state, total = retention_step(
                st["S"], st["z"], q[:, 0], k[:, 0], v[:, 0], log_g[:, 0],
                valid[:, 0], eps)
        ctx.new_state[layer.name] = {"S": state, "z": total}
        # what the live slots MUST move, read and written once: the rows
        # the recurrence needs, whatever the layout allocates
        ctx.add_stat("linear_state_bytes", jnp.sum(valid).astype(f32)
                     * (2.0 * kv * state_rows(hd) * (hd + 1) * 4))
        y = y[:, None]
    else:
        # one span a lowered layer (trace time): the regime its sequence took
        handing_out = p.get("mode") == "state_out"
        # where the program was handed this layer's slot arrays (a serving
        # wave that writes its own slots: LoweringCtx.hand_out_slot_state
        # says when), the sequence form writes the rows that hold a request
        # into them and touches no other
        slots = ctx.state.get(layer.name) if handing_out else None
        with tel.span("retention/path", cat="compile", layer=layer.name,
                      **sequence_path(s, hd)):
            # (training and evaluation visit every row: a static count,
            # which differentiates)
            y, state, total = retention_sequence(
                q, k, v, log_g, eps, valid if handing_out else None,
                into=None if slots is None else (slots["S"], slots["z"]))
    out = y.reshape(b, s, heads * hd).astype(dt) @ weights["wo"].astype(dt)
    if p.get("mode") == "state_out":
        # the state goes out now, with the layer's output: left to the
        # scheduler, every layer's fresh state (0.5 GB a layer in a prefill
        # wave) would stay live to the program's end
        out, ctx.new_state[layer.name] = jax.lax.optimization_barrier(
            (out, {"S": state, "z": total}))
        ctx.add_stat("retention_layers", jnp.asarray(1, jnp.int32))
        ctx.add_stat("retention_rows", rows_computed(valid))
    return [out]


def recurrence_flops_per_token(heads: int, kv_heads: int, hd: int) -> int:
    """The recurrence's own products a token: a K/V head's state decays (one
    product an entry) and takes a rank-one update in (two), a query head
    reads it out (two), P x D entries each; the normaliser's P-long twins."""
    rows = state_rows(hd)
    return (3 * kv_heads + 2 * heads) * rows * (hd + 1)


def _retention_flops(layer: Layer):
    """Forward: the projections and the recurrence's own products. What the
    pair form or the embedding multiplies instead is the form's price, not
    the layer's need, as `_kda_flops` has it."""
    x = layer.inputs[0].spec
    heads, kv, hd = _sizes(layer.params)
    d = x.shape[-1]
    tokens = x.num_elements // d
    proj = d * (heads * hd + 2 * kv * hd + kv) + heads * hd * d
    return 2.0 * tokens * proj \
        + float(tokens) * recurrence_flops_per_token(heads, kv, hd)


def _retention_serving_params(params: dict, kind: str) -> dict:
    return dict(params, mode="decode" if kind == "decode" else "state_out")


def _retention_slot_state(layer: Layer) -> dict:
    _heads, kv, hd = _sizes(layer.params)
    state, total = _state_shapes(kv, hd)
    return {"S": (state, jnp.float32), "z": (total, jnp.float32)}


def _retention_span_facts(layer: Layer) -> dict:
    _heads, kv, hd = _sizes(layer.params)
    return {"retention_kv_heads": kv, "retention_state_rows": state_rows(hd)}


register_op(OperatorType.POWER_RETENTION, _retention_infer, _retention_lower,
            _retention_flops, serving_params=_retention_serving_params,
            state_kind="recurrent", slot_state=_retention_slot_state,
            span_facts=_retention_span_facts)
