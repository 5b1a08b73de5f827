"""FFModel — the model-builder and training entry point.

Reference analog: `FFModel` (include/flexflow/model.h:326, Python mirror
python/flexflow/core/flexflow_cffi.py:887). The builder methods append Layers
to the frontend graph; `compile()` is the pivot (reference
src/runtime/model.cc:2803): it lowers the layer graph to a PCG, runs the
strategy search (or data-parallel fallback), and builds one jitted SPMD train
step; `fit()` is the training loop (flexflow_cffi.py:2062).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from flexflow_tpu.config import FFConfig
from flexflow_tpu.core.graph import topo_order, to_dot
from flexflow_tpu.core.layer import Layer
from flexflow_tpu.core.tensor import Tensor, TensorSpec
from flexflow_tpu.dtype import DataType
from flexflow_tpu.losses import LossType
from flexflow_tpu.metrics import MetricsType
from flexflow_tpu.ops import get_op_def
from flexflow_tpu.ops.op_type import OperatorType


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None):
        self.config = config or FFConfig()
        self.layers: List[Layer] = []
        self.input_tensors: List[Tensor] = []
        self._dedup: Dict[Tuple, Layer] = {}
        self.label_tensor: Optional[Tensor] = None
        self._compiled = None  # CompiledModel after compile()
        self._initializer_overrides: Dict[Tuple[str, str], Any] = {}
        # (layer, wname) -> [("l1"|"l2", coeff)]: penalty terms the compiled
        # train step adds to the loss (keras kernel_regularizer analog —
        # reference RegularizerMode, python/flexflow/keras/regularizers.py)
        self._weight_regularizers: Dict[Tuple[str, str], List[Tuple[str, float]]] = {}

    def add_weight_regularizer(self, layer_name: str, wname: str,
                               mode: str, coeff: float) -> None:
        """Register an L1/L2 penalty on a weight; differentiated as part of
        the training loss (compiler/compile.py)."""
        if mode not in ("l1", "l2"):
            raise ValueError(f"unknown regularizer mode {mode!r}")
        self._weight_regularizers.setdefault(
            (layer_name, wname), []).append((mode, float(coeff)))

    # ---------------------------------------------------------------- builder
    def create_tensor(self, dims: Sequence[int], dtype=DataType.FLOAT,
                      name: Optional[str] = None) -> Tensor:
        t = Tensor(TensorSpec(tuple(dims), DataType.from_any(dtype)), name=name)
        self.input_tensors.append(t)
        return t

    def _add_layer(self, op_type: OperatorType, params: Dict[str, Any],
                   inputs: Sequence[Tensor], name: Optional[str] = None,
                   initializers: Optional[Dict[str, Any]] = None) -> List[Tensor]:
        layer = Layer(op_type, params, list(inputs), name=name)
        specs = get_op_def(op_type).infer(layer)
        for i, spec in enumerate(specs):
            layer.add_output(spec, idx=i)
        self.layers.append(layer)
        if initializers:
            for wname, init in initializers.items():
                if init is not None:
                    self._initializer_overrides[(layer.name, wname)] = init
        return layer.outputs

    # dense / conv family -------------------------------------------------
    def dense(self, input: Tensor, out_dim: int, activation=None, use_bias: bool = True,
              kernel_initializer=None, bias_initializer=None, name=None) -> Tensor:
        return self._add_layer(
            OperatorType.LINEAR,
            {"out_dim": int(out_dim), "activation": activation, "use_bias": use_bias},
            [input], name,
            {"kernel": kernel_initializer, "bias": bias_initializer},
        )[0]

    def conv2d(self, input: Tensor, out_channels: int, kernel_h: int, kernel_w: int,
               stride_h: int = 1, stride_w: int = 1, padding_h: int = 0, padding_w: int = 0,
               activation=None, groups: int = 1, use_bias: bool = True,
               kernel_initializer=None, bias_initializer=None, name=None) -> Tensor:
        return self._add_layer(
            OperatorType.CONV2D,
            {"out_channels": int(out_channels), "kernel_h": kernel_h, "kernel_w": kernel_w,
             "stride_h": stride_h, "stride_w": stride_w, "padding_h": padding_h,
             "padding_w": padding_w, "activation": activation, "groups": groups,
             "use_bias": use_bias},
            [input], name,
            {"kernel": kernel_initializer, "bias": bias_initializer},
        )[0]

    def pool2d(self, input: Tensor, kernel_h: int, kernel_w: int, stride_h: int = 1,
               stride_w: int = 1, padding_h: int = 0, padding_w: int = 0,
               pool_type: str = "max", activation=None, name=None) -> Tensor:
        return self._add_layer(
            OperatorType.POOL2D,
            {"kernel_h": kernel_h, "kernel_w": kernel_w, "stride_h": stride_h,
             "stride_w": stride_w, "padding_h": padding_h, "padding_w": padding_w,
             "pool_type": pool_type, "activation": activation},
            [input], name)[0]

    def embedding(self, input: Tensor, num_entries: int, out_dim: int, aggr: str = "none",
                  dtype=DataType.FLOAT, kernel_initializer=None, name=None) -> Tensor:
        return self._add_layer(
            OperatorType.EMBEDDING,
            {"num_entries": int(num_entries), "out_dim": int(out_dim), "aggr": aggr,
             "dtype": DataType.from_any(dtype).value},
            [input], name, {"kernel": kernel_initializer})[0]

    def batch_matmul(self, A: Tensor, B: Tensor, a_seq_length_dim: int = -1,
                     b_seq_length_dim: int = -1, name=None) -> Tensor:
        # FFIterationConfig.seq_length analog: captured at BUILD time so
        # shape inference sees the truncated lengths and downstream specs
        # stay consistent (XLA static shapes; the reference truncates at
        # runtime over full-size regions instead)
        return self._add_layer(
            OperatorType.BATCHMATMUL,
            {"a_seq_length_dim": a_seq_length_dim,
             "b_seq_length_dim": b_seq_length_dim,
             "seq_length": int(self.config.seq_length or 0)},
            [A, B], name)[0]

    def multihead_attention(self, query: Tensor, key: Tensor, value: Tensor,
                            embed_dim: int, num_heads: int, kdim: int = 0, vdim: int = 0,
                            dropout: float = 0.0, bias: bool = True, add_bias_kv: bool = False,
                            add_zero_attn: bool = False, causal: bool = False,
                            kernel_initializer=None, impl: str = "auto",
                            decode: bool = False, kv_out: bool = False,
                            num_kv_heads: int = 0,
                            scale: Optional[float] = None,
                            positions: Optional[Tensor] = None,
                            rope_theta: Optional[float] = None,
                            qk_norm: Optional[float] = None,
                            initializers: Optional[Dict[str, Any]] = None,
                            mrope_section: Optional[Sequence[int]] = None,
                            selected: Optional[Tensor] = None,
                            out_dim: int = 0,
                            window: Optional[int] = None,
                            rope_scaling: Optional[Dict[str, Any]] = None,
                            output_gate: bool = False,
                            name=None) -> Tensor:
        # decode: single-token serving step reading/writing the paged KV
        # cache via lowering state; kv_out: prefill variant that exposes
        # per-head K/V for cache commit (flexflow_tpu/serving).
        # num_kv_heads: grouped-query attention (0 = one K/V head a query
        # head); scale: the factor on q k^T (None = 1/sqrt(head_dim));
        # positions `[batch, seq]` int: rotary positions, q and k turned
        # over the whole head (rotate-half) at rope_theta; qk_norm: an RMS
        # norm a head on q and on k before the rotation, the value its eps
        # (weights q_norm, k_norm [head_dim]); mrope_section: positions are
        # `[batch, seq, axes]` and the head's pairs follow them by these
        # sections; selected: the key set a `sparse_indexer` chose for each
        # query, the LAST input; out_dim: the output's width where it is not
        # embed_dim (= num_heads * head_dim); window: a FIXED mask by
        # position, query t sees the keys t - window < s <= t (0: every
        # s <= t, the layer of a windowed model that sees the whole context:
        # its cache attention is stated by position too); rope_scaling: YaRN
        # for the positions' tables ({"factor", "original_max_position_
        # embeddings", "beta_fast", "beta_slow", "attention_factor"});
        # output_gate: the heads' output times sigmoid(query input @ wg), a
        # fifth weight, before wo. All
        # enter the params (and the inputs) only
        # where set, so graphs without them keep their fingerprints.
        params = {"embed_dim": int(embed_dim), "num_heads": int(num_heads), "kdim": kdim,
                  "vdim": vdim, "dropout": dropout, "bias": bias, "add_bias_kv": add_bias_kv,
                  "add_zero_attn": add_zero_attn, "causal": causal, "impl": impl,
                  "decode": decode, "kv_out": kv_out}
        if num_kv_heads and int(num_kv_heads) != int(num_heads):
            params["num_kv_heads"] = int(num_kv_heads)
        if scale is not None:
            params["scale"] = float(scale)
        if positions is not None:
            params["rope_theta"] = float(10000.0 if rope_theta is None
                                         else rope_theta)
        if qk_norm is not None:
            params.update(qk_norm=True, qk_norm_eps=float(qk_norm))
        if mrope_section is not None:
            params["mrope_section"] = [int(n) for n in mrope_section]
        if selected is not None:
            params["selected"] = True
        if out_dim and int(out_dim) != int(embed_dim):
            params["out_dim"] = int(out_dim)
        if window is not None:
            if int(window) < 0:
                raise ValueError(f"window {window} < 0")
            params["window"] = int(window)
        if output_gate:
            params["output_gate"] = True
        if rope_scaling:
            params["rope_scaling"] = {
                k: float(v) for k, v in rope_scaling.items()
                if isinstance(v, (int, float))}
        return self._add_layer(
            OperatorType.MULTIHEAD_ATTENTION, params,
            [query, key, value] + ([positions] if positions is not None else [])
            + ([selected] if selected is not None else []),
            name,
            dict({"wq": kernel_initializer, "wk": kernel_initializer,
                  "wv": kernel_initializer, "wo": kernel_initializer},
                 **(initializers or {})))[0]

    # elementwise ---------------------------------------------------------
    def _unary(self, op, input, name=None, **params) -> Tensor:
        return self._add_layer(op, params, [input], name)[0]

    def _binary(self, op, a, b, name=None) -> Tensor:
        return self._add_layer(op, {}, [a, b], name)[0]

    def add(self, a, b, name=None):
        return self._binary(OperatorType.EW_ADD, a, b, name)

    def subtract(self, a, b, name=None):
        return self._binary(OperatorType.EW_SUB, a, b, name)

    def multiply(self, a, b, name=None):
        return self._binary(OperatorType.EW_MUL, a, b, name)

    def divide(self, a, b, name=None):
        return self._binary(OperatorType.EW_DIV, a, b, name)

    def max(self, a, b, name=None):
        return self._binary(OperatorType.EW_MAX, a, b, name)

    def min(self, a, b, name=None):
        return self._binary(OperatorType.EW_MIN, a, b, name)

    def relu(self, input, name=None):
        return self._unary(OperatorType.RELU, input, name)

    def identity(self, input, name=None):
        return self._unary(OperatorType.IDENTITY, input, name)

    def sigmoid(self, input, name=None):
        return self._unary(OperatorType.SIGMOID, input, name)

    def tanh(self, input, name=None):
        return self._unary(OperatorType.TANH, input, name)

    def elu(self, input, name=None):
        return self._unary(OperatorType.ELU, input, name)

    def gelu(self, input, name=None):
        return self._unary(OperatorType.GELU, input, name)

    def erf(self, input, name=None):
        return self._unary(OperatorType.ERF, input, name)

    def silu(self, input, name=None):
        return self._unary(OperatorType.SILU, input, name)

    def exp(self, input, name=None):
        return self._unary(OperatorType.EXP, input, name)

    def log(self, input, name=None):
        return self._unary(OperatorType.LOG, input, name)

    def sin(self, input, name=None):
        return self._unary(OperatorType.SIN, input, name)

    def cos(self, input, name=None):
        return self._unary(OperatorType.COS, input, name)

    def sqrt(self, input, name=None):
        return self._unary(OperatorType.SQRT, input, name)

    def rsqrt(self, input, name=None):
        return self._unary(OperatorType.RSQRT, input, name)

    def pow(self, input, exponent: float, name=None):
        return self._unary(OperatorType.POW, input, name, exponent=exponent)

    def scalar_multiply(self, input, scalar: float, name=None):
        return self._unary(OperatorType.SCALAR_MULTIPLY, input, name, scalar=scalar)

    def scalar_add(self, input, scalar: float, name=None):
        return self._unary(OperatorType.SCALAR_ADD, input, name, scalar=scalar)

    def scalar_sub(self, input, scalar: float, name=None):
        return self._unary(OperatorType.SCALAR_SUB, input, name, scalar=scalar)

    def scalar_true_divide(self, input, scalar: float, name=None):
        return self._unary(OperatorType.SCALAR_TRUE_DIV, input, name, scalar=scalar)

    # norm / softmax / dropout -------------------------------------------
    def batch_norm(self, input, relu: bool = True, momentum: float = 0.9,
                   eps: float = 1e-5, name=None):
        return self._add_layer(OperatorType.BATCHNORM,
                               {"relu": relu, "momentum": momentum, "eps": eps},
                               [input], name)[0]

    def layer_norm(self, input, axes=None, elementwise_affine: bool = True,
                   eps: float = 1e-5, name=None):
        return self._add_layer(OperatorType.LAYERNORM,
                               {"axes": axes, "elementwise_affine": elementwise_affine,
                                "eps": eps},
                               [input], name)[0]

    def rms_norm(self, input, eps: float = 1e-5, name=None,
                 gamma_initializer=None):
        """x / sqrt(mean(x^2) + eps) * gamma over the last axis."""
        return self._add_layer(OperatorType.RMSNORM, {"eps": eps}, [input],
                               name, {"gamma": gamma_initializer})[0]

    def mamba2(self, input: Tensor, heads: int, head_dim: int, d_state: int,
               d_conv: int = 4, chunk: int = 256, n_groups: int = 1,
               eps: float = 1e-5, valid: Optional[Tensor] = None,
               initializers: Optional[Dict[str, Any]] = None,
               name=None) -> Tensor:
        """Mamba-2 mixer over `[batch, seq, d]` (ops/ssm_ops.py). `valid`
        `[batch, seq]` int: which positions hold a token."""
        ins = [input] + ([valid] if valid is not None else [])
        return self._add_layer(
            OperatorType.MAMBA2,
            {"heads": int(heads), "head_dim": int(head_dim),
             "d_state": int(d_state), "d_conv": int(d_conv),
             "chunk": int(chunk), "n_groups": int(n_groups), "eps": eps},
            ins, name, initializers)[0]

    def mamba(self, input: Tensor, d_inner: int, d_state: int, dt_rank: int,
              d_conv: int = 4, eps: float = 1e-6,
              valid: Optional[Tensor] = None,
              initializers: Optional[Dict[str, Any]] = None,
              name=None) -> Tensor:
        """Mamba-1 mixer over `[batch, seq, d]`: the selective scan over a
        `[d_state, d_inner]` state (ops/mamba_ops.py). `valid` `[batch, seq]`
        int: which positions hold a token."""
        ins = [input] + ([valid] if valid is not None else [])
        return self._add_layer(
            OperatorType.MAMBA,
            {"d_inner": int(d_inner), "d_state": int(d_state),
             "dt_rank": int(dt_rank), "d_conv": int(d_conv), "eps": eps},
            ins, name, initializers)[0]

    def kda(self, input: Tensor, heads: int, head_dim: int, d_conv: int = 4,
            lower_bound: float = -5.0, eps: float = 1e-6,
            valid: Optional[Tensor] = None,
            initializers: Optional[Dict[str, Any]] = None,
            name=None) -> Tensor:
        """Kimi-Delta-Attention mixer over `[batch, seq, d]`: linear
        attention over a `[head_dim, head_dim]` state a head, decayed a
        channel and corrected by the delta rule (ops/kda_ops.py). `valid`
        `[batch, seq]` int: which positions hold a token."""
        ins = [input] + ([valid] if valid is not None else [])
        return self._add_layer(
            OperatorType.KDA,
            {"heads": int(heads), "head_dim": int(head_dim),
             "d_conv": int(d_conv), "lower_bound": float(lower_bound),
             "eps": eps},
            ins, name, initializers)[0]

    def short_conv(self, input: Tensor, kernel: int = 3,
                   valid: Optional[Tensor] = None,
                   initializers: Optional[Dict[str, Any]] = None,
                   name=None) -> Tensor:
        """Gated short convolution over `[batch, seq, d]`: a depthwise
        causal convolution of `kernel` taps between two elementwise gates
        and two projections (ops/short_conv_ops.py). `valid` `[batch, seq]`
        int: which positions hold a token."""
        ins = [input] + ([valid] if valid is not None else [])
        return self._add_layer(OperatorType.SHORT_CONV,
                               {"kernel": int(kernel)}, ins, name,
                               initializers)[0]

    def sparse_indexer(self, input: Tensor, positions: Tensor, heads: int,
                       head_dim: int, topk: int, rope_theta: float = 10000.0,
                       mrope_section: Optional[Sequence[int]] = None,
                       eps: float = 1e-6, valid: Optional[Tensor] = None,
                       initializers: Optional[Dict[str, Any]] = None,
                       name=None) -> Tensor:
        """The learned indexer of sparse attention over `[batch, seq, d]`:
        for each token the `topk` earlier tokens of largest score, one key
        head of `head_dim` scored by `heads` query heads
        (ops/sparse_attention_ops.py); what `multihead_attention(selected=)`
        reads. `positions` as attention's; `valid` `[batch, seq]` int: which
        positions hold a token (the counters' alone)."""
        params = {"heads": int(heads), "head_dim": int(head_dim),
                  "topk": int(topk), "rope_theta": float(rope_theta),
                  "eps": float(eps)}
        if mrope_section is not None:
            params["mrope_section"] = [int(n) for n in mrope_section]
        ins = [input, positions] + ([valid] if valid is not None else [])
        return self._add_layer(OperatorType.SPARSE_INDEXER, params, ins,
                               name, initializers)[0]

    def power_retention(self, input: Tensor, positions: Tensor, heads: int,
                        kv_heads: int, head_dim: int,
                        rope_theta: float = 10000.0, eps: float = 1e-6,
                        valid: Optional[Tensor] = None,
                        initializers: Optional[Dict[str, Any]] = None,
                        name=None) -> Tensor:
        """Power-retention mixer over `[batch, seq, d]`: linear attention
        with the kernel (q . k)^2 over a gated `[head_dim (head_dim + 1) / 2,
        head_dim]` state a K/V head, `heads / kv_heads` query heads reading
        each, RMS-normed and rotated q and k (ops/power_retention_ops.py).
        `positions` `[batch, seq]` int: the rotary positions; `valid`
        `[batch, seq]` int: which positions hold a token."""
        ins = [input, positions] + ([valid] if valid is not None else [])
        return self._add_layer(
            OperatorType.POWER_RETENTION,
            {"heads": int(heads), "kv_heads": int(kv_heads),
             "head_dim": int(head_dim), "rope_theta": float(rope_theta),
             "eps": eps},
            ins, name, initializers)[0]

    def softmax(self, input, axis: int = -1, name=None):
        return self._add_layer(OperatorType.SOFTMAX, {"axis": axis}, [input], name)[0]

    def log_softmax(self, input, axis: int = -1, name=None):
        return self._add_layer(OperatorType.LOG_SOFTMAX, {"axis": axis}, [input], name)[0]

    def dropout(self, input, rate: float = 0.5, seed: int = 0, name=None):
        return self._add_layer(OperatorType.DROPOUT, {"rate": rate, "seed": seed},
                               [input], name)[0]

    # shape ops -----------------------------------------------------------
    def reshape(self, input, shape: Sequence[int], name=None):
        return self._add_layer(OperatorType.RESHAPE, {"shape": tuple(shape)}, [input], name)[0]

    def transpose(self, input, perm: Sequence[int], name=None):
        return self._add_layer(OperatorType.TRANSPOSE, {"perm": tuple(perm)}, [input], name)[0]

    def flat(self, input, name=None):
        return self._add_layer(OperatorType.FLAT, {}, [input], name)[0]

    def concat(self, tensors: Sequence[Tensor], axis: int, name=None):
        return self._add_layer(OperatorType.CONCAT, {"axis": axis}, list(tensors), name)[0]

    def split(self, input, sizes: Union[int, Sequence[int]], axis: int, name=None) -> List[Tensor]:
        if isinstance(sizes, int):
            d = input.shape[axis % input.ndim]
            assert d % sizes == 0
            sizes = [d // sizes] * sizes
        return self._add_layer(OperatorType.SPLIT, {"sizes": tuple(sizes), "axis": axis},
                               [input], name)

    def reverse(self, input, axis: int, name=None):
        return self._add_layer(OperatorType.REVERSE, {"axis": axis}, [input], name)[0]

    def pad(self, input, pads, value=0.0, name=None):
        return self._add_layer(OperatorType.PAD, {"pads": tuple(map(tuple, pads)), "value": value},
                               [input], name)[0]

    def cast(self, input, dtype, name=None):
        return self._add_layer(OperatorType.CAST,
                               {"dtype": DataType.from_any(dtype).value}, [input], name)[0]

    def gather(self, input, index: Tensor, dim: int, name=None):
        return self._add_layer(OperatorType.GATHER, {"dim": dim}, [input, index], name)[0]

    def slice_tensor(self, input, starts, limits, name=None):
        return self._add_layer(OperatorType.SLICE,
                               {"starts": tuple(starts), "limits": tuple(limits)},
                               [input], name)[0]

    def expand(self, input, sizes: Sequence[int], name=None):
        """torch.Tensor.expand semantics (-1 keeps the dim)."""
        return self._add_layer(OperatorType.EXPAND, {"sizes": tuple(sizes)},
                               [input], name)[0]

    def constant(self, value, name=None) -> Tensor:
        """A fixed array baked into the graph (torch registered buffers,
        traced torch.tensor/ones/zeros literals)."""
        return self._add_layer(OperatorType.CONSTANT,
                               {"value": np.asarray(value)}, [], name)[0]

    def masked_fill(self, input, mask: Tensor, value: float, name=None):
        """Where mask is true, replace with value (torch.masked_fill)."""
        return self._add_layer(OperatorType.MASKED_FILL, {"value": float(value)},
                               [input, mask], name)[0]

    def where(self, cond: Tensor, a: Tensor, b: Tensor, name=None):
        """Elementwise select (torch.where): a where cond else b."""
        return self._add_layer(OperatorType.WHERE, {}, [cond, a, b], name)[0]

    def scaled_dot_product_attention(self, query, key, value, attn_mask=None,
                                     dropout_p: float = 0.0, is_causal: bool = False,
                                     scale=None, name=None) -> Tensor:
        """Core attention without projections (torch F.scaled_dot_product_attention)."""
        ins = [query, key, value] + ([attn_mask] if attn_mask is not None else [])
        return self._add_layer(
            OperatorType.SDPA,
            {"dropout_p": dropout_p, "is_causal": is_causal, "scale": scale},
            ins, name)[0]

    # reductions ----------------------------------------------------------
    def reduce_sum(self, input, axes, keepdims: bool = False, name=None):
        return self._add_layer(OperatorType.REDUCE_SUM,
                               {"axes": tuple(axes), "keepdims": keepdims}, [input], name)[0]

    def reduce_mean(self, input, axes, keepdims: bool = False, name=None):
        return self._add_layer(OperatorType.REDUCE_MEAN,
                               {"axes": tuple(axes), "keepdims": keepdims}, [input], name)[0]

    def mean(self, input, axes, keepdims: bool = False, name=None):
        return self._add_layer(OperatorType.MEAN,
                               {"axes": tuple(axes), "keepdims": keepdims}, [input], name)[0]

    def argmax(self, input, axis: int = -1, name=None):
        return self._add_layer(OperatorType.ARGMAX, {"axis": axis}, [input], name)[0]

    def top_k(self, input, k: int, sorted: bool = True, name=None) -> List[Tensor]:
        return self._add_layer(OperatorType.TOPK, {"k": int(k), "sorted": sorted}, [input], name)

    # MoE -----------------------------------------------------------------
    def group_by(self, data: Tensor, assign: Tensor, n_experts: int, alpha: float = 1.0,
                 name=None) -> List[Tensor]:
        return self._add_layer(OperatorType.GROUP_BY,
                               {"n_experts": int(n_experts), "alpha": alpha},
                               [data, assign], name)

    def experts(self, dispatched: Tensor, out_dim: int, activation=None,
                use_bias: bool = True, name=None) -> Tensor:
        return self._add_layer(OperatorType.EXPERTS,
                               {"out_dim": int(out_dim), "activation": activation,
                                "use_bias": use_bias},
                               [dispatched], name)[0]

    def aggregate(self, gates: Tensor, assign: Tensor, positions: Tensor,
                  expert_outputs: Tensor, name=None) -> Tensor:
        return self._add_layer(OperatorType.AGGREGATE, {},
                               [gates, assign, positions, expert_outputs], name)[0]

    def aggregate_spec(self, gates, assign, positions, expert_outputs, name=None) -> Tensor:
        return self._add_layer(OperatorType.AGGREGATE_SPEC, {},
                               [gates, assign, positions, expert_outputs], name)[0]

    def moe_layer(self, input: Tensor, num_experts: int, top_k: int,
                  expert_width: int, experts_held=None,
                  valid: Optional[Tensor] = None,
                  initializers: Optional[Dict[str, Any]] = None,
                  scoring: Optional[str] = None, n_group: int = 0,
                  topk_group: int = 0, norm_topk_prob: bool = False,
                  routed_scaling_factor: Optional[float] = None,
                  score_bias=False,
                  expert_activation: Optional[str] = None,
                  latent_size: int = 0,
                  gate_norm_eps: Optional[float] = None,
                  score_bias_rate: Optional[float] = None,
                  name=None) -> Tensor:
        """Dropless top-k layer of gated-SiLU experts over `[batch, seq,
        d]`, routed over all `num_experts`, computing those in
        `experts_held = (lo, hi)` (default: all); ops/moe_ops.py. How it
        chooses and gates beyond top-k + softmax (`scoring` "sigmoid", the
        choice limited to `topk_group` of `n_group` groups, gates
        normalised (over their sum + `gate_norm_eps`) and scaled, a
        `score_bias` for the selection: True a weight, "state" the layer's
        non-trainable state that a training step moves by `score_bias_rate`
        against the load; `moe_ops._choose`, `_balance_bias`) and what
        its experts are beyond gated SiLU at
        the layer's own width (`expert_activation` "relu2": un-gated
        squared ReLU; `latent_size`: the experts work in a latent of that
        width, between two projections of the layer) enters the params
        only where set, so graphs without them keep their fingerprints."""
        lo, hi = experts_held if experts_held is not None else (0, num_experts)
        ins = [input] + ([valid] if valid is not None else [])
        params = {"num_experts": int(num_experts), "top_k": int(top_k),
                  "expert_width": int(expert_width),
                  "experts_held": (int(lo), int(hi))}
        if scoring is not None:
            params["scoring"] = str(scoring)
        if n_group:
            params.update(n_group=int(n_group), topk_group=int(topk_group))
        if norm_topk_prob:
            params["norm_topk_prob"] = True
        if routed_scaling_factor is not None:
            params["routed_scaling_factor"] = float(routed_scaling_factor)
        if score_bias:
            params["score_bias"] = "state" if score_bias == "state" else True
        if score_bias_rate is not None:
            params["score_bias_rate"] = float(score_bias_rate)
        if expert_activation is not None:
            params["expert_activation"] = str(expert_activation)
        if latent_size:
            params["latent_size"] = int(latent_size)
        if gate_norm_eps is not None:
            params["gate_norm_eps"] = float(gate_norm_eps)
        return self._add_layer(OperatorType.MOE_LAYER, params, ins, name,
                               initializers)[0]

    def latent_attention(self, input: Tensor, positions: Tensor, heads: int,
                         q_lora_rank: Optional[int], kv_lora_rank: int,
                         qk_nope_head_dim: int, qk_rope_head_dim: int,
                         v_head_dim: int, eps: float = 1e-6,
                         rope_theta: float = 10000.0,
                         rope_scaling: Optional[Dict[str, Any]] = None,
                         valid: Optional[Tensor] = None, impl: str = "auto",
                         initializers: Optional[Dict[str, Any]] = None,
                         head_gate: bool = False, name=None) -> Tensor:
        """Multi-head latent attention over `[batch, seq, d]` with rotary
        `positions` `[batch, seq]` (ops/latent_attention_ops.py).
        `rope_scaling`: a YaRN dict with Hugging Face's keys (factor,
        original_max_position_embeddings, beta_fast, beta_slow, mscale,
        mscale_all_dim), or None for plain rotary frequencies. `valid`
        `[batch, seq]` int: which positions hold a token (counters only).
        `q_lora_rank` None or 0: queries from one matrix, no latent;
        `head_gate`: a sigmoid gate a head on the attention output."""
        params = {"heads": int(heads), "q_lora_rank": int(q_lora_rank or 0),
                  "kv_lora_rank": int(kv_lora_rank),
                  "qk_nope_head_dim": int(qk_nope_head_dim),
                  "qk_rope_head_dim": int(qk_rope_head_dim),
                  "v_head_dim": int(v_head_dim), "eps": float(eps),
                  "rope_theta": float(rope_theta), "impl": impl}
        if rope_scaling:
            params.update(
                rope_factor=float(rope_scaling["factor"]),
                rope_original_len=int(
                    rope_scaling["original_max_position_embeddings"]),
                rope_beta_fast=float(rope_scaling.get("beta_fast", 32)),
                rope_beta_slow=float(rope_scaling.get("beta_slow", 1)),
                rope_mscale=float(rope_scaling.get("mscale", 1)),
                rope_mscale_all_dim=float(
                    rope_scaling.get("mscale_all_dim", 0)))
        if head_gate:
            params["head_gate"] = True
        ins = [input, positions] + ([valid] if valid is not None else [])
        return self._add_layer(OperatorType.LATENT_ATTENTION, params, ins,
                               name, initializers)[0]

    def cache(self, input: Tensor, num_batches: int = 1, name=None) -> Tensor:
        return self._add_layer(OperatorType.CACHE, {"num_batches": num_batches}, [input], name)[0]

    def moe(self, input: Tensor, num_exp: int, num_select: int, expert_hidden_size: int,
            alpha: float = 2.0, lambda_bal: float = 0.0, name=None) -> Tensor:
        """Composite MoE block (reference: FFModel::moe include/flexflow/model.h:509-514,
        src/ops/moe.cc): topk gating + group_by + per-expert dense + aggregate."""
        gate_logits = self.dense(input, num_exp, name=f"{name or 'moe'}_gate")
        gate_probs = self.softmax(gate_logits)
        topk_vals, topk_idx = self.top_k(gate_probs, num_select)
        dispatched, positions = self.group_by(input, topk_idx, num_exp, alpha)
        hidden = self.experts(dispatched, expert_hidden_size, activation="relu",
                              name=f"{name or 'moe'}_experts")
        return self.aggregate(topk_vals, topk_idx, positions, hidden, name=f"{name or 'moe'}_agg")

    def fork_join(self, input: Tensor, branches, join: str = "add",
                  name=None) -> Tensor:
        """Inter-op placement composite: parallel branches that the search may
        place on disjoint device subsets (reference: Unity nonsequence splits,
        src/runtime/graph.cc:187-321; here a first-class composite like moe).

        Each branch is a callable f(sub_model: FFModel, x: Tensor) -> Tensor
        building an ordinary layer sub-graph. join: "add" sums branch
        outputs, "concat" concatenates along the last dim. Branch weights
        surface on this layer as "b{i}.{sub_layer}.{wname}"."""
        subs = []
        overrides = []
        for bi, build in enumerate(branches):
            bm = FFModel(self.config)
            bx = bm.create_tensor(list(input.shape), dtype=input.spec.dtype,
                                  name=f"_fj_b{bi}_in")
            out = build(bm, bx)
            # auto-generated sub-layer names embed the process-global Layer
            # guid; rename positionally so identically-built models get
            # identical weight keys (init determinism + name-based transfer)
            rename = {}
            for j, l in enumerate(bm.layers):
                if l.name == f"{l.op_type.value}_{l.guid}":
                    rename[l.name] = f"{l.op_type.value}{j}"
                    l.name = rename[l.name]
            subs.append((bm.layers, bx, out))
            overrides.append({(rename.get(ln, ln), wn): init
                              for (ln, wn), init in bm._initializer_overrides.items()})
        layer = Layer(OperatorType.FORK_JOIN,
                      {"join": join, "n_branches": len(branches)},
                      [input], name=name)
        layer.branches = subs
        for i, spec in enumerate(get_op_def(OperatorType.FORK_JOIN).infer(layer)):
            layer.add_output(spec, idx=i)
        self.layers.append(layer)
        # lift branch initializer overrides onto the prefixed weight names
        for bi, ov in enumerate(overrides):
            for (lname, wname), init in ov.items():
                self._initializer_overrides[
                    (layer.name, f"b{bi}.{lname}.{wname}")] = init
        return layer.outputs[0]

    # parallel ops (reference: src/parallel_ops/) --------------------------
    def repartition(self, input: Tensor, dim: int, axis: str = "data", name=None) -> Tensor:
        return self._add_layer(OperatorType.REPARTITION, {"dim": dim, "axis": axis},
                               [input], name)[0]

    def combine(self, input: Tensor, dim: int, axis: str, name=None) -> Tensor:
        return self._add_layer(OperatorType.COMBINE, {"dim": dim, "axis": axis},
                               [input], name)[0]

    def replicate(self, input: Tensor, name=None) -> Tensor:
        return self._add_layer(OperatorType.REPLICATE, {}, [input], name)[0]

    def reduction(self, input: Tensor, axis: str, name=None) -> Tensor:
        return self._add_layer(OperatorType.REDUCTION, {"axis": axis}, [input], name)[0]

    def all_to_all(self, input: Tensor, src_dim: int, dst_dim: int, axis: str,
                   name=None) -> Tensor:
        return self._add_layer(OperatorType.ALLTOALL,
                               {"src_dim": src_dim, "dst_dim": dst_dim, "axis": axis},
                               [input], name)[0]

    def fused_parallel(self, input: Tensor, dims: Sequence, name=None) -> Tensor:
        return self._add_layer(OperatorType.FUSED_PARALLEL, {"dims": tuple(dims)},
                               [input], name)[0]

    # ------------------------------------------------------------- compile
    def compile(self, optimizer=None, loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                metrics: Sequence = (MetricsType.ACCURACY,), comp_mode=None,
                outputs: Optional[Sequence[Tensor]] = None):
        from flexflow_tpu.compiler.compile import compile_model

        self._compiled = compile_model(self, optimizer, LossType.from_any(loss_type),
                                       [MetricsType.from_any(m) for m in metrics],
                                       outputs=outputs)
        if self.config.export_dot:
            with open(self.config.export_dot, "w") as f:
                f.write(self.dot())
        if self.config.simulator_trace:
            self._compiled.export_sim_trace(self.config.simulator_trace)
        return self._compiled

    @property
    def compiled(self):
        if self._compiled is None:
            raise RuntimeError("call compile() first")
        return self._compiled

    # ------------------------------------------------------------ training
    def fit(self, x, y, batch_size: Optional[int] = None, epochs: Optional[int] = None,
            callbacks=None, verbose: bool = True,
            sync_every: Optional[int] = None,
            steps_per_dispatch: Optional[int] = None):
        """Train. sync_every/steps_per_dispatch override the config's
        async-pipeline knobs for this call (see FFConfig)."""
        return self.compiled.fit(x, y, batch_size=batch_size, epochs=epochs,
                                 callbacks=callbacks, verbose=verbose,
                                 sync_every=sync_every,
                                 steps_per_dispatch=steps_per_dispatch)

    def save_checkpoint(self, path: str, block: Optional[bool] = None) -> str:
        """Full-state checkpoint (async by default — cfg.async_checkpoint);
        see CompiledModel.save_checkpoint."""
        return self.compiled.save_checkpoint(path, block=block)

    def load_checkpoint(self, path: str) -> None:
        self.compiled.load_checkpoint(path)

    def forward(self, *inputs):
        return self.compiled.forward(*inputs)

    def eval(self, x, y, batch_size: Optional[int] = None):
        return self.compiled.evaluate(x, y, batch_size=batch_size)

    # --------------------------------------------------------------- misc
    def get_layers(self) -> List[Layer]:
        return list(self.layers)

    def get_layer_by_name(self, name: str) -> Layer:
        for l in self.layers:
            if l.name == name:
                return l
        raise KeyError(name)

    def get_parameter_by_name(self, layer_name: str, wname: str = "kernel"):
        return self.compiled.get_weight(layer_name, wname)

    def set_parameter_by_name(self, layer_name: str, wname: str, value: np.ndarray):
        self.compiled.set_weight(layer_name, wname, value)

    def dot(self, include_costs: Optional[bool] = None) -> str:
        """Graphviz export with sharding annotations; include_costs (the
        --include-costs-dot-graph flag, reference model.cc:3666-3676) adds
        each op's predicted roofline time on the compiled machine."""
        if include_costs is None:
            include_costs = self.config.include_costs_dot_graph
        ann = {}
        if self._compiled is not None:
            ann = {l: str(self._compiled.strategy.op_shardings.get(l.name, ""))
                   for l in self.layers}
            if include_costs:
                from flexflow_tpu.ops.registry import io_bytes
                from flexflow_tpu.search import cost_model as cm_

                machine = self._compiled.machine
                for l in self.layers:
                    t = cm_.compute_time(get_op_def(l.op_type).flop_count(l),
                                         io_bytes(l), machine)
                    ann[l] = (ann.get(l, "") + f"\\n{t * 1e6:.1f}us").lstrip("\\n")
        return to_dot(topo_order(self.layers), ann)
