"""MoE ops: group_by (dispatch), aggregate (combine), experts, cache.

Reference analog: src/ops/{group_by.cc (534), aggregate.cc (569),
aggregate_spec.cc (519), cache.cc (291)} — dynamic CUDA scatter/gather kernels.
XLA needs static shapes, so the reference framework's three ops (`GROUP_BY` /
`EXPERTS` / `AGGREGATE`; NOT `MOE_LAYER` further down, which is dropless and
has no capacity factor) use **capacity-factor
routing** (the standard TPU MoE recipe): group_by emits a dense
(n_experts, capacity, d) dispatch buffer + per-(token, choice) positions with
overflow drops; `experts` is a batched per-expert dense (einsum over the expert
dim, shardable on an "expert" mesh axis → expert parallelism with XLA
all_to_alls); aggregate gathers back weighted by gate values.

Semantics deviation from the reference (documented): the reference's group_by
emits n separate variable-occupancy tensors; here occupancy is fixed at
capacity = ceil(alpha * k * batch / n_experts) and overflow tokens are dropped
(contribute zero), which is the established static-shape equivalent.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from typing import TYPE_CHECKING
if TYPE_CHECKING:
    from flexflow_tpu.core.layer import Layer
from flexflow_tpu.core.tensor import TensorSpec
from flexflow_tpu.dtype import DataType
from flexflow_tpu.kernels.partition import multi_device
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.ops.registry import register_op
from flexflow_tpu.ops.activations import apply_activation


def _group_by_infer(layer: Layer):
    data, assign = layer.inputs[0].spec, layer.inputs[1].spec
    n_experts = layer.params["n_experts"]
    alpha = layer.params.get("alpha", 1.0)
    b, k = assign.shape
    cap = max(1, int(math.ceil(alpha * k * b / n_experts)))
    layer.params["capacity"] = cap
    return [
        TensorSpec((n_experts, cap, data.shape[-1]), data.dtype),
        TensorSpec((b, k), DataType.INT32),
    ]


def _group_by_lower(layer: Layer, inputs, weights, ctx):
    data, assign = inputs
    n_experts = layer.params["n_experts"]
    cap = layer.params["capacity"]
    b, k = assign.shape
    flat = assign.reshape(-1).astype(jnp.int32)  # (b*k,)
    oh = jax.nn.one_hot(flat, n_experts, dtype=jnp.int32)  # (b*k, E)
    # occurrence rank of each (token, choice) within its expert
    pos = jnp.cumsum(oh, axis=0) * oh - 1
    pos_own = jnp.max(pos, axis=1)  # (-1 cols elsewhere)
    valid = pos_own < cap
    slot = jnp.where(valid, pos_own, cap)  # collisions land in the overflow slot
    tokens = jnp.repeat(data, k, axis=0)
    buf = jnp.zeros((n_experts, cap + 1, data.shape[-1]), data.dtype)
    buf = buf.at[flat, slot].set(tokens, mode="drop")
    positions = jnp.where(valid, pos_own, -1).astype(jnp.int32).reshape(b, k)
    return [buf[:, :cap], positions]


register_op(OperatorType.GROUP_BY, _group_by_infer, _group_by_lower)


def _experts_infer(layer: Layer):
    x = layer.inputs[0].spec  # (E, cap, d)
    p = layer.params
    e, cap, d = x.shape
    out_dim = p["out_dim"]
    layer.weight_specs = {"kernel": TensorSpec((e, d, out_dim), x.dtype)}
    if p.get("use_bias", True):
        layer.weight_specs["bias"] = TensorSpec((e, out_dim), x.dtype)
    return [x.with_shape((e, cap, out_dim))]


def _experts_lower(layer: Layer, inputs, weights, ctx):
    x = inputs[0]
    y = jnp.einsum("ecd,edo->eco", x, weights["kernel"].astype(x.dtype))
    if "bias" in weights:
        y = y + weights["bias"].astype(y.dtype)[:, None, :]
    return [apply_activation(layer.params.get("activation"), y)]


def _experts_flops(layer: Layer):
    x = layer.inputs[0].spec
    return 2.0 * x.num_elements * layer.params["out_dim"]


register_op(OperatorType.EXPERTS, _experts_infer, _experts_lower, _experts_flops)


def _aggregate_infer(layer: Layer):
    gates, assign, positions, exp = [t.spec for t in layer.inputs]
    b, k = gates.shape
    return [TensorSpec((b, exp.shape[-1]), exp.dtype)]


def _aggregate_lower(layer: Layer, inputs, weights, ctx):
    gates, assign, positions, exp = inputs
    valid = positions >= 0
    slot = jnp.where(valid, positions, 0)
    gathered = exp[assign.astype(jnp.int32), slot]  # (b, k, dout)
    w = jnp.where(valid, gates, 0.0).astype(exp.dtype)
    return [jnp.einsum("bk,bkd->bd", w, gathered)]


register_op(OperatorType.AGGREGATE, _aggregate_infer, _aggregate_lower)
# aggregate_spec (reference: speculative-assignment variant used with Cache):
# combine semantics are identical on the forward path.
register_op(OperatorType.AGGREGATE_SPEC, _aggregate_infer, _aggregate_lower)


def _cache_infer(layer: Layer):
    return [layer.inputs[0].spec]


def _cache_lower(layer: Layer, inputs, weights, ctx):
    # Reference Cache (src/ops/cache.cc) memoizes expert assignments and scores
    # drift via a user score function to drive recompile_on_condition. The TPU
    # port keeps the passthrough + score in non-trainable state.
    x = inputs[0]
    key = f"{layer.name}/cached"
    if ctx.training:
        ctx.new_state[key] = x
    return [x]


register_op(OperatorType.CACHE, _cache_infer, _cache_lower)


# ---------------------------------------------------------------- moe_layer
def _in_parts(p) -> int:
    """Matrices an expert's `w_in` holds side by side: the gated-SiLU
    expert's two, the un-gated squared-ReLU expert's one."""
    return 1 if p.get("expert_activation") == "relu2" else 2


def _moe_layer_infer(layer: Layer):
    x = layer.inputs[0].spec
    p = layer.params
    lo, hi = p["experts_held"]
    if not 0 <= lo < hi <= p["num_experts"]:
        raise ValueError(f"experts_held {lo, hi} outside 0..{p['num_experts']}")
    d, width = x.shape[-1], p["expert_width"]
    if p.get("expert_activation") not in (None, "relu2"):
        raise ValueError(f"moe_layer expert_activation "
                         f"{p['expert_activation']!r}")
    # the experts' own width: the layer's d, or the latent they work in
    d_e = p.get("latent_size", d)
    layer.weight_specs = {
        "router": TensorSpec((d, p["num_experts"]), x.dtype),
        "w_in": TensorSpec((hi - lo, d_e, width * _in_parts(p)), x.dtype),
        "w_out": TensorSpec((hi - lo, width, d_e), x.dtype),
    }
    if "latent_size" in p:
        layer.weight_specs["w_latent_in"] = TensorSpec((d, d_e), x.dtype)
        layer.weight_specs["w_latent_out"] = TensorSpec((d_e, d), x.dtype)
    if p.get("scoring", "softmax") not in ("softmax", "sigmoid"):
        raise ValueError(f"moe_layer scoring {p['scoring']!r}")
    groups = p.get("n_group", 0)
    if groups and (p["num_experts"] % groups
                   or not 0 < p.get("topk_group", 0) <= groups):
        raise ValueError(f"moe_layer: {groups} groups over "
                         f"{p['num_experts']} experts, topk_group "
                         f"{p.get('topk_group')}")
    if p.get("score_bias") == "state":
        # no gradient moves it: the layer's own state, which a training
        # step updates from its counts (`_balance_bias`)
        layer.state_specs["score_bias"] = TensorSpec((p["num_experts"],),
                                                     DataType.FLOAT)
    elif p.get("score_bias"):
        layer.weight_specs["score_bias"] = TensorSpec((p["num_experts"],),
                                                      DataType.FLOAT)
    return [x]


# tokens routed at a time: a longer input goes through in blocks of this
# many (lax.map), so that the `tokens * k` row buffers of the grouped
# product stay a fraction of a prefill wave's
MOE_TOKEN_BLOCK = 4096
# a block's row buffers are sized at run time, by the smallest of these
# parts of its `tokens * k` pairs that holds the pairs held here
# (`_row_capacities`: ahead of them no row at all, behind them always the
# whole block). Chosen on the chip (PERF.md, PR 33: the layer alone over a
# `[16, 1024]` wave in which a few prompts of 16-512 tokens exist, ms a
# layer, whole block -> ladder). 1/16: what a served block holds, 2048 rows
# at 16 of 256 experts held (four 512-token prompts hold 918; three short
# prompts 37.9 -> 19.7), 2560 rows at 36 of 72 (one 512-token prompt holds
# 2540; three short prompts 24.1 -> 11.0). 1/4: four 512-token prompts in
# one block at 36 of 72 hold 10 200 (20.6 -> 8.2).
# The empty rung: a wave's other blocks (1.6 of 100 rows computed in both
# serving cells; an all-empty wave 6.9 -> 5.3)
MOE_ROW_RUNGS = (16, 4)
# and no rung is smaller than this: the products work on whole row tiles
# (the rows kernel's of 256, `moe_rows.ROW_TILE`; `jax.lax.ragged_dot`,
# which a buffer under one such tile keeps, on the compiler's own, 128 when
# PR 33 chose this), so a smaller buffer computes no fewer. A decode step's
# 64-352 pairs get no ladder, and where its widths allow no grouped product
# either: top-k picks distinct experts, so none of its groups holds more
# rows than the step has tokens (16, one bf16 sublane tile), and the hit
# experts are streamed once over all of them (`_step_tile`, `_route_step`).
# 80 tokens x top-8 (a verifier's block) get [0, 160, 640] and lose
# nothing by it (2.45 -> 2.15 ms)
MOE_MIN_RUNG_ROWS = 128


def _choose(scores, weights, p, training: bool = False):
    """(gates `[tokens, k]` f32, experts `[tokens, k]`) from the router's
    scores of ALL experts `[tokens, E]` f32; `training`: the chosen experts
    and their scores are kept as they leave the `top_k` or the gather
    (`_kept`: a recomputation needs the values, and the gradient goes
    through the name). As granite routes (no key of
    the ones below set): the top k of the scores, a softmax over those k.
    Where set (DeepSeek-V3's `noaux_tc`):
    - `scoring` "sigmoid": an expert's score is sigmoid(x W_r);
    - `score_bias`: `[E]` added to the scores for the SELECTION only (the
      gates use the scores without it): a weight, or with the layer's
      `score_bias` "state" its non-trainable state, which a training step
      moves against the load (`_balance_bias`);
    - `n_group`, `topk_group`: experts lie in `n_group` groups of
      consecutive ids; a group's score is the sum of its two largest
      selection scores, and only the `topk_group` best groups' experts can
      be chosen;
    - `norm_topk_prob`: the k gates are divided by their sum plus
      `gate_norm_eps` (1e-20 where the layer's params name none);
    - `routed_scaling_factor`: and multiplied by this."""
    k = p["top_k"]
    sigmoid = p.get("scoring") == "sigmoid"
    groups = p.get("n_group", 0)
    if not (sigmoid or groups or "score_bias" in weights):
        top, experts = jax.lax.top_k(scores, k)
        top, experts = _kept(top, training), _kept(experts, training)
        gate = jax.nn.softmax(top, axis=-1)
    else:
        own = jax.nn.sigmoid(scores) if sigmoid else scores
        choice = own + weights["score_bias"].astype(jnp.float32) \
            if "score_bias" in weights else own
        if groups:
            tokens, n = choice.shape
            best2 = jax.lax.top_k(choice.reshape(tokens, groups, n // groups),
                                  2)[0]
            _, kept = jax.lax.top_k(jnp.sum(best2, axis=-1), p["topk_group"])
            open_ = jnp.any(kept[:, :, None] == jnp.arange(groups)[None, None],
                            axis=1)                            # [tokens, groups]
            choice = jnp.where(jnp.repeat(open_, n // groups, axis=1), choice,
                               -jnp.inf)
        experts = _kept(jax.lax.top_k(choice, k)[1], training)
        picked = _kept(jnp.take_along_axis(own, experts, axis=-1), training)
        gate = picked if sigmoid else jax.nn.softmax(picked, axis=-1)
    if p.get("norm_topk_prob"):
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True)
                       + p.get("gate_norm_eps", 1e-20))
    if p.get("routed_scaling_factor") is not None:     # 0 is a factor too
        gate = gate * float(p["routed_scaling_factor"])
    return gate, experts


def _row_capacities(pairs: int):
    """The static ladder of row-buffer sizes for a block of `pairs`
    (token, choice) pairs, smallest first: no row at all (a block none of
    whose pairs is held here), the parts of `MOE_ROW_RUNGS`, and last the
    whole block. The whole block alone where no part is worth a rung."""
    parts = [pairs // part for part in MOE_ROW_RUNGS
             if pairs // part >= MOE_MIN_RUNG_ROWS]
    return ([0] + parts if parts else []) + [pairs]


# the named scopes of the router (its product, the choice, the gates) and
# of a stateful selection bias's update
ROUTER_SCOPE = "ff_moe_router"
BIAS_SCOPE = "ff_bias_update"
# the `jax.named_scope` around the experts' own work (the two grouped
# products and the activation between them), in every expert layer of a
# program: one name selects them all (`attribution.instructions_under`),
# where a layer's own scope is its name
EXPERTS_SCOPE = "ff_moe_experts"
# the `checkpoint_name` of what a TRAINING step's routing decides (each
# pair's expert and its score as gathered, the pairs' order by expert, the
# rows a held expert gets, the router's load): half a megabyte a block, and
# dear to make again (a `top_k`, a gather of 8 from 128 a token, a stable
# sort of every pair, a scatter a count: 0.26-0.8 ms each on the chip).
# Every `jax.checkpoint` around an expert layer keeps them
# (`save_only_these_names`): the block's own below, and a `remat_blocks`
# unit's through the op's `kept_names` (`compiler/lowering.run_block`)
ROUTING_KEPT = "ff_moe_routing"
# the `checkpoint_name` of a TRAINING step's result of the whole layer
# (`[b, s, d]` in the compute dtype: 67 MB at 16 384 tokens of 2048): a
# `remat_blocks` unit keeps it (`kept_names`), and its recomputation then
# holds none of the layer's own work (the cast, the gathers, the products,
# the combine: all of them fed this tensor alone); the layer's backward
# reads the unit's inputs made again, the kept decision, and this
LAYER_KEPT = "ff_moe_y"


def _kept(x, training: bool):
    """`x`, a part of the routing decision: named `ROUTING_KEPT` in a
    training step, so that a checkpoint's recomputation reads what the
    forward pass decided; as it is elsewhere (a serving program has no
    `name` equation). Kept FLAT: the chip pads a `[tokens, 8]` tensor's
    rows to 128 lanes, sixteen times its 128 KB a block."""
    if not training:
        return x
    return checkpoint_name(x.reshape(-1), ROUTING_KEPT).reshape(x.shape)


def _params_of(w_out, relu2: bool):
    """What `_experts` reads of a layer's params, from a kernel's operands
    (its backward has no layer)."""
    return {"expert_width": w_out.shape[1],
            "expert_activation": "relu2" if relu2 else None}


def _rows_forward(rows, sizes, w_in, w_out, relu2, tile):
    from flexflow_tpu.kernels import moe_rows

    return moe_rows.moe_rows(rows, sizes, w_in, w_out, relu2, *tile)


def _rows_backward(relu2, _tile, operands, ct):
    """The grouped-product form's, over the same rows, recomputed. A row
    past the last group takes cotangent ZERO (nothing of the result
    depends on it): the grouped product's transpose does not write those
    rows on the chip either, and the callers' gather adds every row's
    cotangent to its token's (PERF.md, PR 64: what lay there read as 1.2 %
    of the tokens' gradient at the 8192-row rung, 120 times it at 2048)."""
    rows, sizes, w_in, w_out = operands

    def grouped(rows, w_in, w_out):
        return _experts(rows, sizes, {"w_in": w_in, "w_out": w_out},
                        _params_of(w_out, relu2))

    d_rows, d_in, d_out = jax.vjp(grouped, rows, w_in, w_out)[1](ct)
    written = jnp.arange(rows.shape[0])[:, None] < jnp.sum(sizes)
    return jnp.where(written, d_rows, 0), None, d_in, d_out


def _rows_forward_kept(rows, sizes, *rest):
    """Under differentiation: the kernel does not write the rows past the
    last group, and the callers' selections keep what lies there out of
    the result but not out of the gates' gradient (0 times it)."""
    written = jnp.arange(rows.shape[0])[:, None] < jnp.sum(sizes)
    return (jnp.where(written, _rows_forward(rows, sizes, *rest), 0),
            (rows, sizes) + rest[:2])


_rows_kernel = jax.custom_vjp(_rows_forward, nondiff_argnums=(4, 5))
_rows_kernel.defvjp(_rows_forward_kept, _rows_backward)


def _experts(rows, sizes, weights, p, tile=None):
    """The experts over `rows` sorted by expert, `sizes[e]` of them on held
    expert e: one grouped product in, the activation in f32, one out. Rows
    are as wide as the experts work (the layer's d, or its latent), the
    middle `expert_width`. Gated SiLU, `silu(a) * b` with `[a | b]` the
    product in, unless the layer's `expert_activation` is "relu2":
    `relu(a)^2`, no gate matrix. Rows past the last group are not
    multiplied (and hold nothing a caller may read). Two forms with the
    same roundings of `ab`, `mid` and the result. With a `tile`
    (`_rows_tile`: one device, K and the width in whole 128-lane slabs,
    whole row tiles) both products and the activation are ONE kernel,
    `kernels/moe_rows.py`, `ab` and `mid` never in HBM: `_all_rows` and
    `_held_rows` call it so for a block of a wave or a chunk. Without one,
    two `jax.lax.ragged_dot`: the same callers on a mesh of several
    devices or at widths that are no whole slabs (every tiny model), a
    decode step's where `_step_tile` gives none, and the backward of both
    kernels (`_step_backward`, `_rows_backward`)."""
    dt = rows.dtype
    width = p["expert_width"]
    with jax.named_scope(EXPERTS_SCOPE):
        if tile is not None:
            return _rows_kernel(rows, sizes, weights["w_in"].astype(dt),
                                weights["w_out"].astype(dt),
                                p.get("expert_activation") == "relu2", tile)
        ab = jax.lax.ragged_dot(rows, weights["w_in"].astype(dt), sizes)
        if p.get("expert_activation") == "relu2":
            mid = jnp.square(jax.nn.relu(ab.astype(jnp.float32))).astype(dt)
        else:
            mid = (jax.nn.silu(ab[:, :width].astype(jnp.float32))
                   * ab[:, width:].astype(jnp.float32)).astype(dt)
        return jax.lax.ragged_dot(mid, weights["w_out"].astype(dt), sizes)


def _all_rows(xt, gate, held, order, where, sizes, weights, p, tile=None):
    """Every (token, choice) pair of the block has a row: the whole
    `tokens * k` buffer, whatever is held. `tile`: as `_experts` takes.
    `where`: the order's inverse (each pair's row), None where the caller
    has not made it (`_order_inverse`: a training step's, made once)."""
    tokens, k = gate.shape
    out = _experts(xt[order // k], sizes, weights, p, tile)    # [tokens*k, d]
    # back to (token, choice) order, one choice at a time (a [tokens, k, d]
    # buffer in f32 would be the largest of the program); rows past the
    # last group hold nothing that was computed, so they are selected
    # away, not multiplied by 0
    where = (jnp.argsort(order) if where is None else where).reshape(
        tokens, k)
    y = jnp.zeros(xt.shape, jnp.float32)
    for j in range(k):
        y = y + jnp.where(held[:, j:j + 1],
                          gate[:, j:j + 1] * out[where[:, j]].astype(jnp.float32),
                          0.0)
    return y.astype(xt.dtype)


def _no_rows(xt, *_):
    """The same for a block that holds no pair."""
    return jnp.zeros_like(xt)


def _held_rows(cap, xt, gate, held, order, _where, sizes, weights, p,
               tile=None):
    """The same for a block that holds at most `cap` pairs: they are the
    first `cap` of `order` (absent pairs sort last), and gather, products,
    gate and combine are over those rows alone. A token's rows lie apart
    (one in each of its experts' groups): each is gated and added to its
    token's row of the output; rows past the last group hold nothing that
    was computed and add 0."""
    k = gate.shape[1]
    pair = order[:cap]
    token = pair // k
    out = _experts(xt[token], sizes, weights, p, tile)         # [cap, d]
    live = jnp.arange(cap) < jnp.sum(sizes)
    part = jnp.where(live[:, None],
                     gate.reshape(-1)[pair][:, None] * out.astype(jnp.float32),
                     0.0)
    return jnp.zeros(xt.shape, jnp.float32).at[token].add(part).astype(xt.dtype)


def _through_latent(rows_fn):
    """`rows_fn` (`_all_rows`, a `_held_rows` or `_step_rows`: the block's
    rows, what routing says of them, last the weights) for a layer whose
    experts work in a latent: the block's tokens are projected into it,
    the row buffers (gather, products, gate, combine) are latent-wide, and
    this holder's combined part is projected back. Both projections are
    linear and bias-free, so the holders' parts still add up to the whole
    layer."""
    def rows(xt, *routing_and_weights):
        dt, weights = xt.dtype, routing_and_weights[-1]
        y = rows_fn(xt @ weights["w_latent_in"].astype(dt),
                    *routing_and_weights)
        return y @ weights["w_latent_out"].astype(dt)

    return rows


def _in_experts_width(rows_fn, p):
    """`rows_fn` as the layer's experts work: through the latent where the
    layer has one, as it is elsewhere."""
    return _through_latent(rows_fn) if "latent_size" in p else rows_fn


def _routing(xt, exists, weights, p, training: bool = False):
    """What the router says of a block's (token, choice) pairs: (gates
    `[tokens, k]` f32, which pairs are held here and exist `[tokens, k]`,
    each pair's held expert `[tokens * k]`, counted from this holder's
    first; an absent pair's is `held`, one past the last; and each pair's
    expert among ALL `[tokens, k]`, `num_experts` for a token that does not
    exist). `training`: the chosen experts and their scores are kept
    (`_choose`): a recomputation reruns the product, the sigmoid and the
    gates' arithmetic, which carry the gradient, and neither the `top_k`
    nor the gather, whose vjps read the indices alone."""
    lo, hi = p["experts_held"]
    with jax.named_scope(ROUTER_SCOPE):
        scores = jnp.dot(xt.astype(jnp.float32),
                         weights["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        gate, experts = _choose(scores, weights, p, training)  # [tokens, k]
    held = (experts >= lo) & (experts < hi) & exists
    local = jnp.where(held, experts - lo, hi - lo).reshape(-1)  # absent: last
    return gate, held, local, jnp.where(exists, experts, p["num_experts"])


# the weights a block's rows read (`_experts`, `_through_latent`): what the
# rule below takes and hands a cotangent; the router's and a selection
# bias get theirs from `_routing`
ROWS_WEIGHTS = ("w_in", "w_out", "w_latent_in", "w_latent_out")


def _order_inverse(order, rung, rungs: int, training: bool):
    """Each pair's row in the whole block's buffer (`order`'s inverse: a
    sort of every pair too) for a TRAINING step: made once, where the rung
    taken is the whole block (zeros elsewhere: no other branch reads it),
    and kept (`_kept`), so that neither a recomputation nor the backward's
    branch sorts again. None outside training: `_all_rows` makes its own,
    inside its branch."""
    if not training:
        return None
    if rungs == 1:
        return _kept(jnp.argsort(order), True)
    return _kept(jax.lax.cond(rung == rungs - 1, jnp.argsort, jnp.zeros_like,
                              order), True)


def _switched_rows(branches):
    """A TRAINING block's rows, `lax.switch(rung, branches, ...)`, as ONE
    `jax.custom_vjp` of `(rung, xt, gate, held, order, where, sizes,
    weights)`. The forward rule is the same switch and keeps its operands
    alone (the block's tokens, the gates, the integers of the routing
    decision that are kept anyway, the weights as they lie). The backward
    rule is one `lax.switch` over the SAME rung whose branch i is `jax.vjp`
    of branch i on the spot (gather, products, gate, combine: the rows
    kernel's own `_rows_backward` inside it) and returns the cotangents of
    `xt`, `gate` and the weights: every branch the same shapes, so nothing
    else leaves the conditional, and `_no_rows`' zeros are written only
    where it is the branch that runs. Left to JAX under the block's
    `jax.checkpoint`, the differentiated conditional is partially
    evaluated: every branch hands on the residuals of ALL branches, so the
    rung that runs writes zeros the size of the whole block's row buffers
    (and of a transposed copy of the weights), and the results are copied
    out (PERF.md, PR 64). The integers, the booleans and the rung take no
    cotangent."""
    def switched(rung, *operands):
        return jax.lax.switch(rung, branches, *operands)

    def forward(*operands):
        return switched(*operands), operands

    def backward(operands, ct):
        rung, xt, gate, *routing, weights = operands

        def pulled(branch):
            def pull(xt, gate, routing, weights, ct):
                return jax.vjp(
                    lambda xt, gate, weights: branch(xt, gate, *routing,
                                                     weights),
                    xt, gate, weights)[1](ct)
            return pull

        d_xt, d_gate, d_weights = jax.lax.switch(
            rung, [pulled(branch) for branch in branches],
            xt, gate, routing, weights, ct)
        return (None, d_xt, d_gate) + (None,) * len(routing) + (d_weights,)

    rows = jax.custom_vjp(switched)
    rows.defvjp(forward, backward)
    return rows


def _rows_tile(rows: int, d: int, itemsize: int, p):
    """The rows kernel's tiles for a block's buffer of `rows` rows, None
    where the grouped product multiplies it. From what the shapes say
    (`moe_rows.row_tiles`): whole row tiles, and the experts' K and width
    in whole 128-lane slabs (the tiny test configurations' are not)."""
    from flexflow_tpu.kernels import moe_rows

    return moe_rows.row_tiles(rows, p.get("latent_size", d),
                              p["expert_width"], _in_parts(p), itemsize)


def _route_tokens(xt, exists, weights, p, told_what_exists: bool,
                  kernel: bool = False, count_all: bool = False,
                  training: bool = False):
    """One block of tokens `[tokens, d]` through the routed layer: (this
    holder's part of the output `[tokens, d]`, rows on each held expert
    `[held]`, rows the experts' buffer was sized for; where a rung's
    buffer can take the rows kernel, how many of those rows took it; and
    last, where `count_all` asks for them, the tokens routed to each of ALL
    the experts `[num_experts]`).
    `told_what_exists`: the layer has its `valid` input, so `exists` may
    name fewer than all. `kernel`: one device, so a buffer whose shapes
    admit `kernels/moe_rows.py` takes it (`_rows_tile`). `training`: what
    the routing decides (the chosen experts and their scores, the order
    and its inverse, both counts) is kept under `ROUTING_KEPT`; `held`,
    `local`, the rung and a row's token are elementwise of those and made
    again; and the rows (one rung or the ladder's switch) go through
    `_switched_rows`, whose gradient is the taken branch's own vjp."""
    k = p["top_k"]
    lo, hi = p["experts_held"]
    held_n = hi - lo
    tokens, d = xt.shape
    gate, held, local, experts = _routing(xt, exists, weights, p, training)
    routed = _kept(jnp.bincount(
        experts.reshape(-1), length=p["num_experts"] + 1)[
        :p["num_experts"]].astype(jnp.int32), training) if count_all else None
    order = _kept(jnp.argsort(local, stable=True), training)
    sizes = _kept(jnp.bincount(local, length=held_n + 1)[:held_n].astype(
        jnp.int32), training)
    # a block's rows are the pairs that are held here AND exist: the ladder
    # is worth its conditional where either can leave some out. A holder of
    # every expert that is told of no absent token has a row for every
    # pair, and a block of a few rows has nothing to save: no ladder
    caps = _row_capacities(tokens * k) \
        if held_n < p["num_experts"] or told_what_exists else [tokens * k]
    tiles = [_rows_tile(cap, d, xt.dtype.itemsize, p) if kernel and cap
             else None for cap in caps]

    def rows_fn(fn, tile):
        return _in_experts_width(functools.partial(fn, p=p, tile=tile), p)

    branches = [rows_fn(functools.partial(_held_rows, cap), tile) if cap
                else _no_rows for cap, tile in zip(caps[:-1], tiles)] \
        + [rows_fn(_all_rows, tiles[-1])]
    ladder = len(caps) > 1
    rung = jnp.sum(jnp.sum(sizes) > jnp.asarray(caps[:-1], jnp.int32)) \
        if ladder else 0
    where = _order_inverse(order, rung, len(caps), training)
    if training:
        y = _switched_rows(branches)(
            rung, xt, gate, held, order, where, sizes,
            {name: w for name, w in weights.items() if name in ROWS_WEIGHTS})
    else:           # one branch: called as it is, no conditional
        y = jax.lax.switch(rung, branches, xt, gate, held, order, where,
                           sizes, weights)
    computed = jnp.asarray(caps, jnp.int32)[rung] if ladder \
        else jnp.int32(tokens * k)
    # the rows of each rung that the kernel multiplies: handed out only
    # where some rung's are (a program none of whose buffers takes it
    # lowers to the text it lowered to)
    by_kernel = [cap if tile else 0 for cap, tile in zip(caps, tiles)]
    return (y, sizes, computed) \
        + ((jnp.asarray(by_kernel, jnp.int32)[rung],) if any(by_kernel)
           else ()) + ((routed,) if count_all else ())


def _step_forward(xt, gate, local, ids, count, w_in, w_out, relu2, tn):
    from flexflow_tpu.kernels import moe_step

    held = jnp.arange(w_in.shape[0], dtype=local.dtype)[:, None, None]
    # an expert's gate of each token, 0 where the token did not choose it
    # (or is not live, or the expert is not held): compare and sum
    gates = jnp.sum(jnp.where(local.reshape((1,) + gate.shape) == held,
                              gate[None], 0.0), axis=2)      # [held, tokens]
    return moe_step.moe_step(ids, count, xt, gates, w_in, w_out, relu2, tn)


def _step_backward(relu2, tn, operands, ct):
    """The grouped-product form's: `_all_rows` over the same pairs,
    recomputed."""
    xt, gate, local, _ids, _count, w_in, w_out = operands
    held_n = w_in.shape[0]
    p = _params_of(w_out, relu2)

    held = (local < held_n).reshape(gate.shape)
    order = jnp.argsort(local, stable=True)
    sizes = jnp.bincount(local, length=held_n + 1)[:held_n].astype(jnp.int32)

    def grouped(xt, gate, w_in, w_out):
        return _all_rows(xt, gate, held, order, None, sizes,
                         {"w_in": w_in, "w_out": w_out},
                         p).astype(jnp.float32)

    d_xt, d_gate, d_in, d_out = jax.vjp(grouped, xt, gate, w_in, w_out)[1](ct)
    return d_xt, d_gate, None, None, None, d_in, d_out


_step_kernel = jax.custom_vjp(_step_forward, nondiff_argnums=(7, 8))
_step_kernel.defvjp(lambda *args: (_step_forward(*args), args[:7]),
                    _step_backward)


def _step_rows(tn, xt, gate, local, ids, count, weights, p):
    """A decode step's block through the held experts it hits, as one
    kernel (`kernels/moe_step.py`): no pair is sorted, no row gathered, and
    a token's experts are summed in f32 on the chip, in expert order."""
    dt = xt.dtype
    with jax.named_scope(EXPERTS_SCOPE):
        y = _step_kernel(xt, gate, local, ids, count,
                         weights["w_in"].astype(dt),
                         weights["w_out"].astype(dt),
                         p.get("expert_activation") == "relu2", tn)
    return y.astype(dt)


def _step_tile(tokens: int, d: int, itemsize: int, p):
    """The kernel's tile for a block that is a decode step, None for every
    other block. From what the block's shapes say: no ladder and at most a
    sublane tile's 16 tokens (top-k picks distinct experts, so no expert's
    group then holds more than 16 rows: the speculative verifier's `[slots,
    1 + draft]` blocks and a wave's do), and widths in whole 128-lane slabs
    (`moe_step.tile_width`; the tiny test configurations' are not)."""
    from flexflow_tpu.kernels import moe_step

    if len(_row_capacities(tokens * p["top_k"])) > 1:
        return None
    return moe_step.tile_width(tokens, p.get("latent_size", d),
                               p["expert_width"], _in_parts(p), itemsize)


def _route_step(xt, exists, weights, p, tn: int):
    """`_route_tokens` for a block that `_step_tile` gave a tile: (this
    holder's part of the output, rows on each held expert, the hit experts:
    the kernel's grid steps on its first axis)."""
    from flexflow_tpu.kernels import moe_step

    held_n = weights["w_in"].shape[0]
    gate, _held, local, _experts = _routing(xt, exists, weights, p)
    # by compare and sum over the block's few pairs: no scatter
    sizes = jnp.sum(
        local[None] == jnp.arange(held_n, dtype=local.dtype)[:, None],
        axis=1, dtype=jnp.int32)
    ids, count = moe_step.hit_experts(sizes, local.shape[0])
    rows = _in_experts_width(functools.partial(_step_rows, tn, p=p), p)
    return rows(xt, gate, local, ids, count, weights), sizes, count


def _report_experts_held(ctx, held_n: int) -> None:
    """`moe_experts_held`: what `moe_experts_hit` is a share of."""
    ctx.add_stat("moe_experts_held", jnp.int32(held_n))


def _report_step_kernel(ctx, experts) -> None:
    """`moe_step_kernel_experts`: the kernel's grid steps on its first axis
    (equal to `moe_experts_hit` where it ran), 0 where the block took the
    grouped product."""
    ctx.add_stat("moe_step_kernel_experts", experts)


def _report_rows_kernel(ctx, rows) -> None:
    """`moe_rows_kernel`: the rows of the buffers that `ff_moe_rows`
    multiplied, summed over blocks (equal to `moe_rows_computed` where it
    ran), 0 where the grouped product did."""
    ctx.add_stat("moe_rows_kernel", rows)


def _balance_bias(layer: Layer, bias, routed, ctx) -> None:
    """A stateful selection bias after a block of tokens: reported
    (`moe_router_load_max` / `_mean`: tokens on the fullest of ALL the
    experts and on the mean one, by the router's own choice;
    `moe_bias_abs_max`, with `moe_bias_layers` to divide a sum over layers
    by), and, training, moved against the load without a gradient
    (torchtitan's rule): `b <- b + d - mean(d)`, `d = rate * sign(mean(c) -
    c)`, `c[e]` the tokens the router sent to expert `e` in this call, `rate`
    the layer's `score_bias_rate`. Under gradient accumulation every
    microbatch is such a call."""
    c = routed.astype(jnp.float32)
    ctx.add_stat("moe_router_load_max", jnp.max(c))
    ctx.add_stat("moe_router_load_mean", jnp.mean(c))
    ctx.add_stat("moe_bias_abs_max", jnp.max(jnp.abs(bias)))
    ctx.add_stat("moe_bias_layers", jnp.float32(1))
    if not ctx.training:
        return
    with jax.named_scope(BIAS_SCOPE):
        d = float(layer.params.get("score_bias_rate", 0.0)) \
            * jnp.sign(jnp.mean(c) - c)
        ctx.new_state[f"{layer.name}/score_bias"] = bias + d - jnp.mean(d)


def _moe_serving_params(params: dict, kind: str) -> dict:
    """Served, nothing moves a selection bias: the layer's state is a
    weight of its prefill and decode twins."""
    if params.get("score_bias") == "state":
        return dict(params, score_bias=True)
    return params


def _moe_layer_lower(layer: Layer, inputs, weights, ctx):
    """Dropless top-k layer of experts (gated SiLU, or as `_experts` says)
    over `[batch, seq, d]`, for a holder of the experts `experts_held =
    (lo, hi)`. With `latent_size` in the layer's params the experts work
    in a latent of that width (`_through_latent`): the router still reads
    the d-wide row, and everything sized by rows below is latent-wide.

    The router scores ALL `num_experts` in f32 (the matmul at HIGHEST
    precision: a lower one moves the k-th and (k+1)-th scores past each
    other), takes the top k and a softmax over those k in f32, or chooses
    and gates as the layer's params say (`_choose`). Each (token,
    choice) whose expert is held here is computed; the others contribute
    nothing, so the result is this holder's part of the layer's output
    (the parts of all holders add up to the whole layer).

    Three forms, chosen from the block's shapes and the mesh (`_step_tile`,
    `_rows_tile`; no flag). A DECODE STEP (no ladder, at most 16 tokens, the
    experts' K and width in whole 128-lane slabs, one device): top-k picks
    distinct experts, so an expert's group holds at most the step's 16 rows,
    one bf16 sublane tile, and nothing is sorted, gathered or combined:
    `kernels/moe_step.py` streams each hit expert's two matrices once over
    ALL the step's rows and adds `gate * out` into one f32 result on the
    chip, the gate 0 where a token did not choose the expert (`_route_step`;
    per-expert rows and gates by compare-and-sum over the `tokens * k` pairs;
    the backward is the grouped form's, a `custom_vjp`). EVERY OTHER BLOCK
    (a wave's 4096 tokens, a chunk's 2048, a verifier's `[slots, 1 + draft]`,
    a tiny model's widths): pairs are sorted by expert, absent ones behind
    the last group, the held ones' rows gathered into a buffer, multiplied
    group by group (`_experts`) and combined under their gates. The row
    buffers (gather, products, f32 gate, combine) are sized per block of
    `MOE_TOKEN_BLOCK` tokens at run time: the smallest rung of
    `_row_capacities(tokens * k)` that holds the block's held pairs, chosen
    by a `lax.switch` on their count, so the layer's cost follows the rows
    held here and not the static `tokens * k`. The last rung is the whole
    block: no capacity factor, no drops, whatever the routing. The rows a
    block needs are the pairs that are held here AND exist, so the ladder
    is there wherever either can leave pairs out: for a holder of a part
    of the experts, and for any holder that has the optional second input
    `valid` `[batch, seq]`, which names the tokens that exist (the others
    are not routed; a padded prefill wave is mostly such). A holder of
    every expert without `valid`, and a block too small for a smaller rung
    (a decode step in either form), lower with no conditional. What
    multiplies a rung's buffer is chosen a rung: A BUFFER WHOSE SHAPES
    ADMIT `ff_moe_rows` (one device, K and the width in whole 128-lane
    slabs, whole row tiles of 256: every served cell's rungs) goes through
    `kernels/moe_rows.py`, both products and the activation in one pass in
    which `ab` and `mid` stay on the chip and an expert with no row is not
    read (the backward is the grouped form's, a `custom_vjp`); EVERY OTHER
    BUFFER (a mesh of several devices, which GSPMD partitions; widths that
    are no whole slabs; fewer rows than a tile) through two
    `jax.lax.ragged_dot` with the activation between them.

    TRAINING (`ctx.training`): the layer says itself what outlives its
    forward pass. (1) A token block is a `jax.checkpoint` that keeps its
    tokens and its ROUTING DECISION and recomputes the rest in the
    backward pass. The decision: the chosen experts, their scores as
    gathered, the pairs' order by expert (and its inverse, made where the
    whole block is combined: `_order_inverse`), the rows on each held
    expert, the router's load: 0.5 MB a block of 4096 tokens, tagged
    `ROUTING_KEPT` (`_kept`), where a recomputation would make the same
    values again with a `top_k`, a gather, a sort of every pair and a
    scatter a count. What carries the gradient and is cheap is made again:
    the router's product, the sigmoid, the gates' normalisation. (2) A
    block's rows (the `lax.switch` over the rungs) are ONE `jax.custom_vjp`
    (`_switched_rows`) that keeps its operands alone and whose backward is
    a `lax.switch` over the same rung of the branches' own vjps: nothing
    but the cotangents of the tokens, the gates and the weights leaves the
    backward's conditional, where JAX's partial evaluation of the
    conditional under the block's checkpoint made every branch hand on the
    residuals of all (zeros the size of the whole block's row buffers,
    written by the rung that runs). The gates' cotangent reads the
    products' result, so the rows kernel runs once more there: twice a
    step. (3) The layer's result is tagged `LAYER_KEPT`. A `remat_blocks`
    unit around the layer keeps (1)'s decision and (3) (the op's
    `kept_names`): its recomputation then runs NONE of the layer's own work
    (no cast, no gather, no product, no combine), only what feeds the
    layer's backward from outside (the norm before it), and the chain of
    (1) runs once a step, not three times (PERF.md, PRs 59 and 64). Outside
    training nothing is tagged, nothing checkpointed, and the conditional
    is JAX's own.

    Reports (ctx.add_stat): moe_routed_pairs, moe_held_pairs, moe_load_max
    (rows on the fullest held expert), moe_load_mean (held pairs over
    experts held), moe_experts_hit (held experts with a row),
    moe_experts_held (experts held: what moe_experts_hit could reach),
    moe_rows_static (`tokens * k`) and moe_rows_computed (the rungs taken,
    summed over blocks: equal to moe_rows_static where there is no
    ladder), moe_step_kernel_experts (the step kernel's grid steps on its
    first axis: moe_experts_hit where it ran, 0 where the block took the
    grouped product), moe_rows_kernel (the rows of the buffers that the
    rows kernel multiplied, summed over blocks: moe_rows_computed where it
    ran, 0 where `ragged_dot` did)."""
    x = inputs[0]
    p = layer.params
    b, s, d = x.shape
    tokens = b * s
    xt = x.reshape(tokens, d)
    told = len(inputs) > 1
    exists = jnp.ones((tokens, 1), bool) if not told \
        else inputs[1].reshape(tokens, 1) > 0
    # on a mesh of several devices the grouped product stays: GSPMD
    # partitions it, and cannot partition a Mosaic call
    one_device = not multi_device(ctx.mesh)
    # a selection bias that is the layer's state: read from it (no
    # gradient), and a training step moves it by the step's own counts
    stateful = p.get("score_bias") == "state"
    if stateful:
        bias = jax.lax.stop_gradient(ctx.state[f"{layer.name}/score_bias"])
        weights = dict(weights, score_bias=bias)
    tn = _step_tile(tokens, d, x.dtype.itemsize, p) \
        if one_device and not stateful else None
    kernel_experts, kernel_rows = jnp.int32(0), ()
    if tn is not None:
        y, sizes, kernel_experts = _route_step(xt, exists, weights, p, tn)
        computed = jnp.int32(tokens * p["top_k"])
    elif tokens > MOE_TOKEN_BLOCK and tokens % MOE_TOKEN_BLOCK == 0:
        blocks = tokens // MOE_TOKEN_BLOCK

        def one_block(block):
            return _route_tokens(block[0], block[1], weights, p, told,
                                 one_device, stateful, ctx.training)

        # training: a block keeps its tokens and its routing decision
        # (`ROUTING_KEPT`: half a megabyte) and the rest is recomputed in
        # the backward pass; what a block's products save
        # (a kernel's operands, the weights among them; its row buffers at
        # the largest rung) would else be kept once a block
        y, sizes, computed, *kernel_rows = jax.lax.map(
            jax.checkpoint(
                one_block,
                policy=jax.checkpoint_policies.save_only_these_names(
                    ROUTING_KEPT)) if ctx.training else one_block,
            (xt.reshape(blocks, MOE_TOKEN_BLOCK, d),
             exists.reshape(blocks, MOE_TOKEN_BLOCK, 1)))
        sizes, computed = jnp.sum(sizes, axis=0), jnp.sum(computed)
        if stateful:
            kernel_rows[-1] = jnp.sum(kernel_rows[-1], axis=0)
    else:
        y, sizes, computed, *kernel_rows = _route_tokens(
            xt, exists, weights, p, told, one_device, stateful, ctx.training)
    if stateful:        # the counts over all the experts came last
        _balance_bias(layer, bias, kernel_rows.pop(), ctx)
    n_held = jnp.sum(sizes)
    ctx.add_stat("moe_routed_pairs",
                 jnp.sum(exists).astype(jnp.int32) * p["top_k"])
    ctx.add_stat("moe_held_pairs", n_held)
    ctx.add_stat("moe_load_max", jnp.max(sizes))
    ctx.add_stat("moe_load_mean",
                 n_held.astype(jnp.float32) / sizes.shape[0])
    ctx.add_stat("moe_experts_hit", jnp.sum(sizes > 0).astype(jnp.int32))
    _report_experts_held(ctx, sizes.shape[0])
    ctx.add_stat("moe_rows_static", jnp.int32(tokens * p["top_k"]))
    ctx.add_stat("moe_rows_computed", computed)
    _report_step_kernel(ctx, kernel_experts)
    _report_rows_kernel(ctx, jnp.sum(kernel_rows[0]) if kernel_rows
                        else jnp.int32(0))
    y = y.reshape(b, s, d)
    return [checkpoint_name(y, LAYER_KEPT) if ctx.training else y]


def _moe_layer_flops(layer: Layer):
    """Forward, for the EXPECTED rows here: each token's k choices fall on
    the held experts in the share held / num_experts; an expert's two or
    three matrices at the width it works in, and the latent's two
    projections on every token."""
    x = layer.inputs[0].spec
    p = layer.params
    lo, hi = p["experts_held"]
    d = x.shape[-1]
    tokens = x.num_elements // d
    pairs = tokens * p["top_k"] * (hi - lo) / p["num_experts"]
    d_e = p.get("latent_size", d)
    latent = 2 * d * d_e if "latent_size" in p else 0
    return 2.0 * tokens * (d * p["num_experts"] + latent) \
        + 2.0 * pairs * (_in_parts(p) + 1) * d_e * p["expert_width"]


register_op(OperatorType.MOE_LAYER, _moe_layer_infer, _moe_layer_lower,
            _moe_layer_flops, serving_params=_moe_serving_params,
            uncast_weights=("score_bias",),
            kept_names=(ROUTING_KEPT, LAYER_KEPT))
