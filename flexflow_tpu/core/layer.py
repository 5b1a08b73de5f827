"""Layer — a node in the frontend computation graph.

Reference analog: `Layer` (include/flexflow/layer.h, src/runtime/layer.cc).
A Layer records op type, a params dict (the analog of the reference's per-op
XParams structs, e.g. include/flexflow/ops/linear_params.h), input tensors, and
produces output tensors. Layers are hash-consable via `params_key()` — the
analog of the reference's Params-hash node dedup
(include/flexflow/model.h:678-706 get_or_create_node).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from flexflow_tpu.core.tensor import Tensor, TensorSpec
from flexflow_tpu.ops.op_type import OperatorType, WEIGHTED_OPS


def _freeze(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if hasattr(v, "tobytes") and hasattr(v, "shape"):  # ndarray constants
        return (tuple(v.shape), str(getattr(v, "dtype", "")), v.tobytes())
    return v


class Layer:
    _next_guid = [100]

    def __init__(
        self,
        op_type: OperatorType,
        params: Dict[str, Any],
        inputs: List[Tensor],
        name: Optional[str] = None,
    ):
        self.op_type = op_type
        self.params = dict(params)
        self.inputs = list(inputs)
        self.outputs: List[Tensor] = []
        self.guid = Layer._next_guid[0]
        Layer._next_guid[0] += 1
        self.name = name or f"{op_type.value}_{self.guid}"
        # filled by compile: weight specs {wname: TensorSpec}
        self.weight_specs: Dict[str, TensorSpec] = {}
        # and non-trainable state {name: TensorSpec} that `init` draws
        # beside the weights (zeros, or the layer's initializer of that
        # name; `state["<layer>/<name>"]`) and that a step hands on: no
        # gradient, no optimizer moments
        self.state_specs: Dict[str, TensorSpec] = {}

    @property
    def has_weights(self) -> bool:
        return self.op_type in WEIGHTED_OPS

    def add_output(self, spec: TensorSpec, idx: int = 0, name: Optional[str] = None) -> Tensor:
        t = Tensor(spec, owner=self, owner_idx=idx, name=name or f"{self.name}:out{idx}")
        self.outputs.append(t)
        return t

    def params_key(self) -> Tuple:
        """Hashable identity for node dedup (op type + params + input specs)."""
        return (
            self.op_type,
            _freeze(self.params),
            tuple((i.spec.shape, i.spec.dtype) for i in self.inputs),
        )

    def __repr__(self):
        ins = ", ".join(str(list(i.shape)) for i in self.inputs)
        outs = ", ".join(str(list(o.shape)) for o in self.outputs)
        return f"Layer[{self.name}]({ins} -> {outs})"
