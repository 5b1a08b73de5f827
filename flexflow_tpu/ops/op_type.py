"""Operator vocabulary (reference: include/flexflow/ffconst.h:69-163 OperatorType).

The vocabulary covers every op type the reference framework names, including the
parallel ops; not every entry needs a distinct lowering (many elementwise ops
share one), but the names are the stable identity used by graph hashing, the
substitution engine, and frontends.
"""

from __future__ import annotations

import enum


class OperatorType(enum.Enum):
    # anchors
    INPUT = "input"
    WEIGHT = "weight"
    NOOP = "noop"
    # dense / conv family
    CONV2D = "conv2d"
    DROPOUT = "dropout"
    LINEAR = "linear"
    BATCHMATMUL = "batch_matmul"
    POOL2D = "pool2d"
    SCALAR_MULTIPLY = "scalar_multiply"
    SCALAR_ADD = "scalar_add"
    SCALAR_SUB = "scalar_sub"
    SCALAR_TRUE_DIV = "scalar_truediv"
    SCALAR_FLOOR_DIV = "scalar_floordiv"
    # normalization
    BATCHNORM = "batch_norm"
    LAYERNORM = "layer_norm"
    RMSNORM = "rms_norm"
    # element binary
    EW_ADD = "add"
    EW_SUB = "subtract"
    EW_MUL = "multiply"
    EW_DIV = "divide"
    EW_MAX = "max"
    EW_MIN = "min"
    EW_EQUAL = "equal"
    EW_GREATER = "greater"
    EW_LESS = "less"
    # element unary
    RELU = "relu"
    IDENTITY = "identity"
    SIGMOID = "sigmoid"
    TANH = "tanh"
    ELU = "elu"
    GELU = "gelu"
    EXP = "exp"
    LOG = "log"
    SIN = "sin"
    COS = "cos"
    SQRT = "sqrt"
    RSQRT = "rsqrt"
    POW = "pow"
    SILU = "silu"
    ERF = "erf"
    # shape / movement
    RESHAPE = "reshape"
    TRANSPOSE = "transpose"
    FLAT = "flat"
    CONCAT = "concat"
    SPLIT = "split"
    REVERSE = "reverse"
    PAD = "pad"
    CAST = "cast"
    GATHER = "gather"
    SLICE = "slice"
    EXPAND = "expand"
    CONSTANT = "constant"
    MASKED_FILL = "masked_fill"
    WHERE = "where"
    # reductions
    REDUCE_SUM = "reduce_sum"
    REDUCE_MEAN = "reduce_mean"
    REDUCE_MAX = "reduce_max"
    REDUCE_MIN = "reduce_min"
    MEAN = "mean"
    ARGMAX = "argmax"
    ARGMIN = "argmin"
    # embeddings / softmax / attention
    EMBEDDING = "embedding"
    SOFTMAX = "softmax"
    LOG_SOFTMAX = "log_softmax"
    MULTIHEAD_ATTENTION = "multihead_attention"
    SDPA = "scaled_dot_product_attention"
    # MoE family (reference: src/ops/{topk,group_by,aggregate,aggregate_spec,cache}.cc)
    TOPK = "topk"
    GROUP_BY = "group_by"
    AGGREGATE = "aggregate"
    AGGREGATE_SPEC = "aggregate_spec"
    CACHE = "cache"
    EXPERTS = "experts"
    # dropless top-k routed layer of gated experts, told which experts it
    # holds (ops/moe_ops.py; the capacity-factor ops above stay as the
    # reference framework's group_by/aggregate)
    MOE_LAYER = "moe_layer"
    # state-space mixer (Mamba-2, ops/ssm_ops.py)
    MAMBA2 = "mamba2"
    # state-space mixer (Mamba-1: a decay a channel and state index, the
    # selective scan; ops/mamba_ops.py)
    MAMBA = "mamba"
    # multi-head latent attention (low-rank q and K/V, rotary positions on a
    # slice, a latent cache; ops/latent_attention_ops.py)
    LATENT_ATTENTION = "latent_attention"
    # linear attention over a per-head matrix state (the gated delta rule
    # with a decay a channel: Kimi Delta Attention; ops/kda_ops.py)
    KDA = "kda"
    # linear attention with a degree-2 power kernel over a gated recurrent
    # state a K/V head (power retention; ops/power_retention_ops.py)
    POWER_RETENTION = "power_retention"
    # gated short convolution (a depthwise causal convolution of a few
    # taps between two gates and two projections; ops/short_conv_ops.py)
    SHORT_CONV = "short_conv"
    # the learned indexer of sparse attention: for each query the top-k
    # earlier tokens by a cheap score (ops/sparse_attention_ops.py)
    SPARSE_INDEXER = "sparse_indexer"
    # fused compute op (reference: src/ops/fused.cc)
    FUSED = "fused"
    # inter-op placement composite (reference: nonsequence splits,
    # src/runtime/graph.cc:187-321; branches on disjoint device subsets)
    FORK_JOIN = "fork_join"
    # parallel ops (reference: src/parallel_ops/)
    REPARTITION = "repartition"
    COMBINE = "combine"
    REPLICATE = "replicate"
    REDUCTION = "reduction"
    ALLTOALL = "all_to_all"
    FUSED_PARALLEL = "fused_parallel"
    PIPELINE = "pipeline"
    # loss-side
    CROSS_ENTROPY = "cross_entropy"
    MSE = "mse"

    def __repr__(self):  # terse for dot/debug output
        return self.value


# Ops that carry trainable weights.
WEIGHTED_OPS = frozenset(
    {
        OperatorType.CONV2D,
        OperatorType.LINEAR,
        OperatorType.EMBEDDING,
        OperatorType.BATCHNORM,
        OperatorType.LAYERNORM,
        OperatorType.RMSNORM,
        OperatorType.MULTIHEAD_ATTENTION,
        OperatorType.EXPERTS,
        OperatorType.MOE_LAYER,
        OperatorType.MAMBA2,
        OperatorType.MAMBA,
        OperatorType.LATENT_ATTENTION,
        OperatorType.KDA,
        OperatorType.POWER_RETENTION,
        OperatorType.SHORT_CONV,
        OperatorType.SPARSE_INDEXER,
        OperatorType.FORK_JOIN,
    }
)

# Pure elementwise unary ops sharing one lowering path.
UNARY_OPS = frozenset(
    {
        OperatorType.RELU,
        OperatorType.IDENTITY,
        OperatorType.SIGMOID,
        OperatorType.TANH,
        OperatorType.ELU,
        OperatorType.GELU,
        OperatorType.EXP,
        OperatorType.LOG,
        OperatorType.SIN,
        OperatorType.COS,
        OperatorType.SQRT,
        OperatorType.RSQRT,
        OperatorType.POW,
        OperatorType.SILU,
        OperatorType.ERF,
        OperatorType.SCALAR_MULTIPLY,
        OperatorType.SCALAR_ADD,
        OperatorType.SCALAR_SUB,
        OperatorType.SCALAR_TRUE_DIV,
        OperatorType.SCALAR_FLOOR_DIV,
    }
)

BINARY_OPS = frozenset(
    {
        OperatorType.EW_ADD,
        OperatorType.EW_SUB,
        OperatorType.EW_MUL,
        OperatorType.EW_DIV,
        OperatorType.EW_MAX,
        OperatorType.EW_MIN,
        OperatorType.EW_EQUAL,
        OperatorType.EW_GREATER,
        OperatorType.EW_LESS,
    }
)

PARALLEL_OPS = frozenset(
    {
        OperatorType.REPARTITION,
        OperatorType.COMBINE,
        OperatorType.REPLICATE,
        OperatorType.REDUCTION,
        OperatorType.ALLTOALL,
        OperatorType.FUSED_PARALLEL,
    }
)
