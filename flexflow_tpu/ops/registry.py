"""Op registry: shape inference + JAX lowering + cost facts per OperatorType.

Reference analog: the per-op C++ classes under src/ops/ (each with shape
inference in its constructor, init/forward/backward Legion glue, and
measure_operator_cost). In the TPU rebuild an op needs only:

- ``infer(layer)``   — output TensorSpecs (+ fills layer.weight_specs);
  the analog of the reference constructors' dim math.
- ``lower(layer, inputs, weights, ctx)`` — a pure JAX function; XLA autodiff
  replaces the reference's hand-written backward kernels, XLA fusion replaces
  FusedOp's kernel dispatch loop (src/ops/fused.cu).
- ``flops(layer)`` / default byte counts — feed the search cost model
  (the measure_operator_cost analog is in flexflow_tpu/search/cost_model.py).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from typing import TYPE_CHECKING
if TYPE_CHECKING:
    from flexflow_tpu.core.layer import Layer
from flexflow_tpu.core.tensor import TensorSpec
from flexflow_tpu.ops.op_type import OperatorType


def rows_taken(fresh, old, took):
    """`[rows, ...]` with the rows `took` `[rows]` bool names from `fresh`
    (in `old`'s type) and the others from `old`."""
    return jnp.where(took.reshape((-1,) + (1,) * (old.ndim - 1)),
                     fresh.astype(old.dtype), old)


@dataclasses.dataclass
class LoweringCtx:
    """Per-trace context threaded through op lowerings."""

    training: bool = False
    rng: Optional[jax.Array] = None
    seq_length: Optional[int] = None  # FFIterationConfig.seq_length analog
    # mixed-precision policy (reference: --allow-tensor-op-math-conversion,
    # the cuDNN tensor-op analog → bf16 on the MXU). None = keep input dtypes.
    compute_dtype: Optional[str] = None
    # non-trainable state (batch-norm running stats, cache scores):
    state: Dict[str, Any] = dataclasses.field(default_factory=dict)
    new_state: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # placement channel (strategy -> lowering): the device mesh and per-op
    # strategy attributes (e.g. fork_join's {"placement": axis} for inter-op
    # placement on disjoint device subsets)
    mesh: Optional[Any] = None
    op_attrs: Dict[str, Dict[str, Any]] = dataclasses.field(default_factory=dict)
    # the strategy's per-layer OpSharding (layer name -> outputs/weights dim
    # shardings): what a lowering needs to run a Pallas kernel per shard on
    # a multi-device mesh (kernels/partition.py)
    op_shardings: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # --fusion flag (reference FusedOp gate, model.cc apply_fusion): False
    # disables fused custom kernels (pallas flash attention) in "auto" mode
    enable_fusion: bool = True
    # small per-call counters an op reports beside its outputs (routed rows
    # of an expert layer): None = not collected (training, evaluation); a
    # dict = summed over the layers that report under one name, and handed
    # out as new_state[STATS_KEY] by build_forward (the serving programs)
    stats: Optional[Dict[str, Any]] = None

    def add_stat(self, name: str, value) -> None:
        if self.stats is not None:
            self.stats[name] = self.stats.get(name, 0) + value

    def hand_out_slot_state(self, name: str, fresh: Dict[str, Any],
                            valid) -> None:
        """A recurrent layer's prefill form ("state_out") hands out the
        state its wave left, `{leaf: [rows, ...]}`, through this. Where the
        program was handed the layer's slot arrays (`state[name]`: the
        serving engine does so for a state too large to exist twice), a row
        that holds a request (`valid` `[rows, seq]` bool names a token in
        it) goes into them and a row that sat the wave out keeps what the
        slot had; else the fresh rows go out as they are, for the cache's
        commit program."""
        old = self.state.get(name)
        if old is not None:
            took = jnp.any(valid, axis=1)
            fresh = {key: rows_taken(rows, old[key], took)
                     for key, rows in fresh.items()}
        self.new_state[name] = fresh

    def rng_for(self, layer: Layer) -> jax.Array:
        if self.rng is None:
            raise ValueError(f"layer {layer.name} needs an rng but none was provided")
        return jax.random.fold_in(self.rng, layer.guid)


@dataclasses.dataclass
class OpDef:
    infer: Callable[[Layer], List[TensorSpec]]
    lower: Callable[[Layer, List[jnp.ndarray], Dict[str, jnp.ndarray], LoweringCtx], List[jnp.ndarray]]
    flops: Optional[Callable[[Layer], float]] = None  # per forward pass
    # what the serving stack asks of an op (the serving/ package):
    # serving_params(params, kind) -> the params of its prefill / decode
    # twin (kind "prefill" | "decode"; None: the layer's own);
    # state_kind: the per-request state it carries ("paged_kv": K/V pages
    # of the paged pool, "paged_latent": pages of ONE pool a layer whose
    # rows are a token's latent, "paged_index": pages of one pool a layer
    # whose rows are a sparse-attention indexer's key, beside the K/V pages
    # of the attention it selects for, "recurrent": fixed-size per-slot
    # arrays);
    # page_state(layer) -> what a token's row holds, for the paged kinds
    # ({"heads", "head_dim"} of the K/V pools, {"latent_dim"} of a latent
    # pool): the cache's geometry comes from the layers' own declarations;
    # slot_state(layer) -> {name: (per-slot shape, dtype)} for "recurrent"
    serving_params: Optional[Callable[[Dict[str, Any], str], Dict[str, Any]]] = None
    state_kind: Optional[str] = None
    page_state: Optional[Callable[[Layer], Dict[str, int]]] = None
    slot_state: Optional[Callable[[Layer], Dict[str, Any]]] = None
    # whether a "recurrent" op's decode twin at a block of `s > 1` positions
    # is its sequence form STARTED FROM A STATE: `state[name]` holds the rows'
    # leaves before the block, `new_state[name]` what they are after its last
    # real position. What a prefill chunk (serving/engine.py) needs of every
    # recurrent layer; an op that does not declare it is refused there
    chunk_from_state: bool = False
    # span_facts(layer) -> what a layer that carries state says of it on the
    # serving compile span (a "recurrent" layer of its state's layout,
    # {"ssm_groups": n}; a paged one of what the pool's rows went through,
    # {"rope_theta": t}); None, or an empty dict: nothing
    span_facts: Optional[Callable[[Layer], Dict[str, Any]]] = None
    # weights that keep their own type under a compute_dtype (a selection
    # bias whose size is that of the gaps it decides)
    uncast_weights: tuple = ()
    # the `jax.ad_checkpoint.checkpoint_name`s under which a training
    # lowering of the op tags what is cheap to keep and dear to make again
    # (an expert layer's routing decision, and its result `y`, which is
    # all the unit's later layers read of it; what the flash forward
    # kernel of an attention layer wrote): the op decides, and a
    # `remat_blocks` unit around it keeps them
    # (`compiler/lowering.run_block`: `save_only_these_names`) and
    # recomputes the rest
    kept_names: tuple = ()

    def flop_count(self, layer: Layer) -> float:
        if self.flops is not None:
            return float(self.flops(layer))
        # default: one vector op per output element
        return float(sum(o.spec.num_elements for o in layer.outputs))


_REGISTRY: Dict[OperatorType, OpDef] = {}

# where build_forward(collect_stats=True) puts LoweringCtx.stats
STATS_KEY = "serve/stats"
# the kinds of per-request state that live in pages of the paged pools
PAGED_STATE_KINDS = ("paged_kv", "paged_latent", "paged_index")


def register_op(op_type: OperatorType, infer, lower, flops=None, **extra) -> OpDef:
    d = OpDef(infer=infer, lower=lower, flops=flops, **extra)
    _REGISTRY[op_type] = d
    return d


def get_op_def(op_type: OperatorType) -> OpDef:
    if op_type not in _REGISTRY:
        raise NotImplementedError(f"no OpDef registered for {op_type}")
    return _REGISTRY[op_type]


def has_op_def(op_type: OperatorType) -> bool:
    return op_type in _REGISTRY


def io_bytes(layer: Layer) -> int:
    """Bytes moved through HBM for one forward pass (inputs+weights+outputs)."""
    n = sum(i.spec.size_bytes for i in layer.inputs)
    n += sum(s.size_bytes for s in layer.weight_specs.values())
    n += sum(o.spec.size_bytes for o in layer.outputs)
    return n
