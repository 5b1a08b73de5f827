"""Op library: importing this package registers every OpDef."""

from flexflow_tpu.ops.op_type import OperatorType  # noqa: F401
from flexflow_tpu.ops.registry import (  # noqa: F401
    LoweringCtx,
    OpDef,
    get_op_def,
    has_op_def,
    io_bytes,
    register_op,
)

# registration side effects
from flexflow_tpu.ops import (  # noqa: F401
    elementwise,
    dense_ops,
    conv_ops,
    norm_ops,
    shape_ops,
    reduce_ops,
    embed_ops,
    sparse_attention_ops,
    attention_ops,
    moe_ops,
    ssm_ops,
    mamba_ops,
    latent_attention_ops,
    kda_ops,
    power_retention_ops,
    short_conv_ops,
    parallel_ops,
    fork_join,
)
