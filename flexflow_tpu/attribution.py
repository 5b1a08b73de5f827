"""Per-op performance attribution: where does the step actually go?

Motivation (ISSUE 7): PR 5's drift monitor sees the step as ONE number — it
can say "the search mispredicted step time by 3x" but not which op the
analytic cost model misprices, and the BASELINE.md MFU gap (attention
matmuls ~50% vs MLP 88.8% at head_dim 64) was found by hand. This module is
the op-level join the next ROADMAP waves stand on: for every (graph layer,
compiled placement) it lines up

  * the DP's PREDICTED cost (stamped on the strategy at search time —
    `Strategy._predicted_op_costs` — restored from the strategy cache on
    warm compiles; analytic fallback for imported/data-parallel
    strategies),
  * the MEASURED time — primary path: the device events of the
    `.xplane.pb` that `jax.profiler` writes under `--profiling`, joined BY
    INSTRUCTION NAME with the compiled programs' own optimized HLO, whose
    per-instruction `metadata.op_name` carries the `jax.named_scope(
    layer.name)` the lowering stamps (compiler/lowering.py) and JAX's
    jvp / transpose wrappers (`op_scope_map`, `register_program`,
    `device_time_by_scope`: the same map the benchmark's
    readers/scope_device.py reads); fallback path: a partitioned re-execution that
    times each layer's jitted fwd/bwd at shard-local shapes on the live
    machine (search/measure.MeasuredCost — works on CPU CI), rescaled so
    attributed times sum to the REAL measured step time,
  * the ROOFLINE bound (search/cost_model.op_roofline): the machine-floor
    time, which leg (compute vs HBM bandwidth) binds, and the MFU ceiling,

yielding per-op MFU, compute-/bandwidth-bound classification, and a per-op
drift top-K ("these 3 ops explain 87% of the step-time misprediction").
This is FlexFlow's calibrated per-op prediction-vs-measurement discipline
("Beyond Data and Model Parallelism", arXiv 1807.05358) applied at RUN
time, and every row is featurized exactly the way "A Learned Performance
Model for TPUs" (arXiv 2008.01040) featurizes ops — (op kind, shapes,
dtype, layout, sharding, machine) — so a profiled fit with telemetry on
emits `op/attr` events (tools/trace_report.py's [ops] section).

Entry points: `CompiledModel.op_attribution()` / `PipelinedModel.
op_attribution()` (both also feed `profile_report`), `--profile-ops`
(runs attribution at fit end), and `tools/profile_attribution.py`.
"""

from __future__ import annotations

import bisect
import glob
import hashlib
import json
import math
import os
import re
import weakref
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

from flexflow_tpu import telemetry as tel
from flexflow_tpu.search import cost_model as cmod
from flexflow_tpu.search import memo

# telemetry event names (cat "op"): one op/attr per attributed row, one
# op/drift_topk per report — both surfaced by tools/trace_report.py
OP_EVENT = "op/attr"
DRIFT_EVENT = "op/drift_topk"

# acceptance tolerance: attributed per-op times must sum to the measured
# step time within this fraction (tools/profile_attribution.py --check)
SUM_TOLERANCE = 0.15


# ------------------------------------------------------------ featurization
def op_features(layer, cand, machine) -> Dict[str, Any]:
    """The featurization of one placed op (2008.01040:
    opcode + shapes + dtype + layout/fusion context, here + sharding +
    machine fingerprint). Everything JSON-serializable; `feature_key`
    hashes the identity-relevant subset (the layer NAME is instance
    identity, not a feature — two gpt2 blocks' identical matmuls must
    share one key)."""
    out0 = layer.outputs[0].spec if layer.outputs else None
    return {
        "op": layer.op_type.value,
        "in_shapes": [list(t.spec.shape) for t in layer.inputs],
        "out_shapes": [list(t.spec.shape) for t in layer.outputs],
        "weight_shapes": {w: list(s.shape)
                          for w, s in sorted(layer.weight_specs.items())},
        "dtype": out0.dtype.value if out0 is not None else "",
        "params": repr(layer.params_key()),
        "layout": cand.name,
        "sharding": {
            "out": [list(map(_ax_str, d)) for d in cand.out_dims],
            "weights": {w: list(map(_ax_str, d))
                        for w, d in sorted(cand.weight_dims.items())},
        },
        "machine": memo.machine_fingerprint(machine),
    }


def _ax_str(d) -> str:
    if d is None:
        return ""
    return d if isinstance(d, str) else "+".join(d)


def feature_key(features: Dict[str, Any]) -> str:
    """Stable dedup key of a feature row: sha1 over the canonical JSON of
    the identity fields. Process-stable (sorted keys, no floats), so
    rows from different runs/machines compare equal."""
    ident = {k: features.get(k) for k in
             ("op", "in_shapes", "out_shapes", "weight_shapes", "dtype",
              "params", "layout", "sharding", "machine")}
    blob = json.dumps(ident, sort_keys=True, separators=(",", ":"))
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


# --------------------------------------------- compiled HLO -> (layer, phase)
# What a compiled program's device time is made of. The profiler's device
# events carry no source names: an event of the TPU plane's "XLA Ops" line is
# named by the HLO instruction's own text ("%fusion.12 = bf16[...] fusion(
# ...)"), a CPU thunk event by `hlo_op`. The optimized HLO text of the SAME
# executable says, per instruction, `metadata={op_name="jit(train_step)/
# transpose(jvp(h0_attn))/dot_general"}`: the name stack at trace time, with
# the `jax.named_scope(layer.name)` of compiler/lowering.py and JAX's own
# transform wrappers in it. The instruction's name is the join key.
PHASES = ("forward", "backward", "update", "loss", "other")
LOSS_SCOPE = "ff.loss"          # jax.named_scope names in the step function
UPDATE_SCOPE = "ff.update"      # (compiler/compile.py: _build_steps)
_STEP_SCOPES = {LOSS_SCOPE: "loss", UPDATE_SCOPE: "update"}
# instructions that span the events of the computations they call
CONTAINERS = ("while", "conditional", "call")
UNATTRIBUTED = "unattributed"   # the name is in no registered program's map
AMBIGUOUS = "ambiguous"         # two programs of the interval disagree on it
SPAN = "compile/op_scopes"


class OpScope(NamedTuple):
    """Where one instruction of a compiled program belongs."""
    layer: str      # graph layer name ("" outside every layer)
    op_type: str    # the layer's op_type.value ("" outside every layer)
    phase: str      # one of PHASES (AMBIGUOUS / UNATTRIBUTED after a join)
    opcode: str     # HLO opcode; a named custom call by its (folded) name
    has_dot: bool   # a dot, convolution, ragged-dot or ff_* kernel is inside
    mixed: bool     # a fusion whose body spans more than one (layer, phase)
    inferred: bool = False  # the compiler made it: scoped by what it serves


_INSTR = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = (.*)$")
_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPED = re.compile(r"^([A-Za-z_][\w.]*)\((.*)\)$")
_NUMBERED = re.compile(r"[.\-_]?\d+$")
_DOT_OPCODES = ("dot", "convolution", "ragged-dot")
# custom calls that are a matrix product inside: a Pallas kernel by its
# stable `name=`, and what the chip's compiler makes of a ragged-dot (a
# Mosaic call named `ragged-dot-none.N`)
_DOT_KERNELS = ("ff_", "ragged-dot")


def fold_name(name: str) -> str:
    """`fusion.12` -> `fusion`, `ff_flash_attention_fwd.30` ->
    `ff_flash_attention_fwd` (the benchmark's top_ops folds alike)."""
    return _NUMBERED.sub("", name)


def _opcode_of(rest: str) -> str:
    """The opcode of `<shape> <opcode>(<operands>), <attributes>`; a tuple
    shape holds parentheses (and TPU layouts `T(8,128)` do), so the shape
    is stepped over by bracket matching."""
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else rest
    return rest.split("(", 1)[0].strip()


def _segments(op_name: str) -> List[str]:
    """`op_name` split at the slashes outside parentheses."""
    out, depth, cur = [], 0, []
    for c in op_name:
        if c == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += (c == "(") - (c == ")")
        cur.append(c)
    out.append("".join(cur))
    return out


def _unwrap(seg: str) -> "tuple[list, str]":
    """A segment of a name stack as (JAX's transform wrappers around it,
    outermost first; what they wrap): `transpose(jvp(up))` -> (["transpose",
    "jvp"], "up")."""
    wrappers = []
    m = _WRAPPED.match(seg)
    while m is not None:
        wrappers.append(m.group(1))
        seg = m.group(2)
        m = _WRAPPED.match(seg)
    return wrappers, seg


def scope_of_op_name(op_name: str, op_types: Dict[str, str]
                     ) -> "tuple[str, str]":
    """(layer, phase) of one `metadata.op_name`. A layer is matched as a
    WHOLE path segment inside JAX's transform wrappers (`jvp(up)`,
    `transpose(jvp(up))`), so a layer named "up" never absorbs "update" or
    "ffn_up_2", and an op that merely mentions a layer mid-word matches
    nothing. The first segment that names a layer or a step scope decides:
    under `transpose(...)` a layer's work is `backward`, else `forward`;
    everything under `ff.loss` (its own backward too) is `loss`, under
    `ff.update` `update`. Recomputed forward under `jax.checkpoint` carries
    the wrappers of the backward pass it is recomputed in, so it counts as
    `backward`: it is time the backward pass costs. Where a checkpointed
    unit holds the layers' scopes INSIDE it (`remat_blocks`), the backward
    pass's wrapper stands on an earlier segment (`transpose(jvp(jvp()))/
    checkpoint/l1_attn/...`): a `transpose` seen on the way counts too."""
    wrappers = []
    for seg in _segments(op_name):
        around, seg = _unwrap(seg)
        wrappers += around
        if seg in _STEP_SCOPES:
            return "", _STEP_SCOPES[seg]
        if seg in op_types:
            return seg, "backward" if "transpose" in wrappers else "forward"
    return "", "other"


class _Instr(NamedTuple):
    name: str
    opcode: str
    op_name: str        # metadata.op_name, "" where the compiler made it
    rest: str           # the text after " = "
    root: bool
    operands: tuple


def _operands(rest: str, opcode: str) -> tuple:
    """Names of the instructions between the opcode's parentheses."""
    at = rest.find(opcode + "(")
    if at < 0:
        return ()
    depth, start = 0, at + len(opcode)
    for i in range(start, len(rest)):
        depth += (rest[i] == "(") - (rest[i] == ")")
        if depth == 0:
            return tuple(_OPERAND.findall(rest[start:i]))
    return ()


def _parse_computations(hlo_text: str):
    """{computation: [_Instr]} and the entry computation's name."""
    comps: Dict[str, list] = {}
    entry, cur = None, None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m is not None:
                cur = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        rest = m.group(3)
        meta = _OP_NAME.search(rest)
        opcode = _opcode_of(rest)
        # a name stack starts "jit(f)/..."; an `op_name` with no path is the
        # compiler's own ("ragged-dot-none" on the call it rewrote a
        # ragged-dot into): like none at all
        op_name = meta.group(1) if meta and "/" in meta.group(1) else ""
        cur.append(_Instr(m.group(2), opcode, op_name, rest,
                          bool(m.group(1)), _operands(rest, opcode)))
    return comps, entry


def _called(rest: str) -> List[str]:
    names = [m.group(2) for m in _CALLED.finditer(rest)]
    b = _BRANCHES.search(rest)
    if b is not None:
        names += [n.strip().lstrip("%") for n in b.group(1).split(",")]
    return names


_NO_EVENT = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")
_UNDECIDED = ("", "")       # no name stack anywhere on the instruction


def op_scope_map(hlo_text: str, layers) -> Dict[str, OpScope]:
    """{instruction name: OpScope} of one compiled program's optimized HLO
    text (`jitted.lower(...).compile().as_text()`): every instruction of
    the entry computation and of every computation that runs as events of
    its own (while bodies and conditions, conditional branches, called and
    asynchronous computations), and for a fusion what its fused computation
    holds. `layers` are the graph's layers (anything with `.name` and
    `.op_type.value`).

    A fusion is credited to the (layer, phase) of its dot-like instruction
    where its body has exactly one such scope, else to its root's (the
    fusion's own metadata where the root has none; where that names no
    layer, the scope most of its body carries); `mixed` says the body held
    more than one: a weight-gradient product with the optimizer update in
    its epilogue is `backward`, `mixed`.

    An instruction the compiler made (no `op_name` on it or in its body: a
    relayout copy, a broadcast constant, a loop-carried copy) belongs to
    what it was made for: inside a layer's loop or branch to that layer
    (the scope of the `while` / `conditional` that calls its computation),
    else to the scope of the instruction that uses it, else of the one it
    reads; `other` where none of them has one."""
    op_types = layers if isinstance(layers, dict) else \
        {l.name: l.op_type.value for l in layers}
    comps, entry = _parse_computations(hlo_text)
    if entry is None:
        return {}
    scope_cache: Dict[str, tuple] = {"": _UNDECIDED}

    def scope(op_name):
        got = scope_cache.get(op_name)
        if got is None:
            got = scope_cache[op_name] = scope_of_op_name(op_name, op_types)
        return got

    def dot_like(i):
        return i.opcode in _DOT_OPCODES or (
            i.opcode == "custom-call" and i.name.startswith(_DOT_KERNELS))

    def own_scope(i):
        """((layer, phase) or _UNDECIDED, has_dot, mixed)."""
        if i.opcode != "fusion":
            return scope(i.op_name), dot_like(i), False
        called = _called(i.rest)
        body = comps.get(called[0], []) if called else []
        inner = [(scope(b.op_name), b) for b in body if b.op_name]
        dots = {s for s, b in inner if dot_like(b)}
        named = [s for s, _b in inner if s[1] != "other"]
        if len(dots) == 1:
            got = next(iter(dots))
        else:
            got = next((s for s, b in inner if b.root), _UNDECIDED)
            if got[1] in ("", "other"):
                got = scope(i.op_name) if i.op_name else got
            if got[1] in ("", "other") and named:
                got = max(set(named), key=named.count)
        return got, any(dot_like(b) for b in body), len(set(named)) > 1

    out: Dict[str, OpScope] = {}
    seen, todo = set(), [(entry, _UNDECIDED)]
    while todo:
        comp, inherited = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        instrs = comps[comp]
        found = {i.name: own_scope(i) for i in instrs}
        made = {n for n, f in found.items() if f[0] == _UNDECIDED}
        users: Dict[str, list] = {}
        for i in instrs:
            for o in i.operands:
                users.setdefault(o, []).append(i.name)
        if inherited != _UNDECIDED:
            for name, (s, dot, mixed) in found.items():
                if s == _UNDECIDED:
                    found[name] = (inherited, dot, mixed)
        for order in (instrs[::-1], instrs) * 2:
            # through chains of copies: a pass against program order takes
            # a chain from its last user, a pass along it from its source
            changed = False
            for i in order:
                if found[i.name][0] != _UNDECIDED:
                    continue
                near = [found[n][0] for n in
                        users.get(i.name, []) + list(i.operands)
                        if n in found]
                got = next((s for s in near if s[1] not in ("", "other")),
                           None)
                if got is not None:
                    found[i.name] = (got,) + found[i.name][1:]
                    changed = True
            if not changed:
                break
        for i in instrs:
            (layer, phase), has_dot, mixed = found[i.name]
            if i.opcode in CONTAINERS or i.opcode.endswith("-start"):
                todo.extend((c, (layer, phase)) for c in _called(i.rest))
            if i.opcode in _NO_EVENT:
                continue
            # a custom call that was given a name goes by it
            # (`ff_flash_attention_fwd`, `ragged-dot-none`)
            kernel = i.opcode == "custom-call" and \
                not i.name.startswith("custom-call")
            out[i.name] = OpScope(layer, op_types.get(layer, ""),
                                  phase or "other",
                                  fold_name(i.name) if kernel else i.opcode,
                                  has_dot, mixed, i.name in made)
    return out


def _decides(i: _Instr, op_type: str) -> bool:
    """A `top_k` or a sort by expert of an expert layer's routing chain:
    an instruction `sort`, or the CPU's `TopK` call, whose own `op_name`
    ends in `top_k` or `sort` (a scatter's sort of its indices ends in
    `scatter-add`)."""
    return op_type == "moe_layer" and i.opcode in ("sort", "custom-call") \
        and _segments(i.op_name)[-1] in ("top_k", "sort")


def _runs_flash_forward(i: _Instr, op_type: str) -> bool:
    """A call of the flash forward kernel under one of the graph's layers."""
    return bool(op_type) and i.opcode == "custom-call" \
        and fold_name(i.name) == "ff_flash_attention_fwd"


def _runs_rows_kernel(i: _Instr, op_type: str) -> bool:
    """A call of the rows kernel (`kernels/moe_rows.py`: forward only) under
    an expert layer."""
    return op_type == "moe_layer" and i.opcode == "custom-call" \
        and fold_name(i.name) == "ff_moe_rows"


def _multiplies_rows(i: _Instr, op_type: str) -> bool:
    """A product of an expert layer's rows where no kernel runs, in a
    forward evaluation of `moe_ops._experts`: a dot-like instruction whose
    name stack holds `moe_ops.EXPERTS_SCOPE` as it is or inside `jvp(..)`;
    inside `transpose(..)` it is the backward's own (a training block's
    rows are differentiated on the spot, `moe_ops._switched_rows`, so the
    scope carries the wrapper)."""
    if op_type != "moe_layer" or i.opcode not in _DOT_OPCODES:
        return False
    return any(inner == "ff_moe_experts" and "transpose" not in around
               for around, inner in map(_unwrap, _segments(i.op_name)))


# what a checkpoint around an op may keep (`OpDef.kept_names`), by the
# instructions that run again where it does not: a kind's ways of telling
# them, the first that finds one in a forward phase counts (the rows'
# forward by the kernel's calls where it runs, since its backward multiplies
# the rows again without it; by the products elsewhere)
_KEPT_WORK = {"moe_routing_passes": (_decides,),
              "flash_fwd_passes": (_runs_flash_forward,),
              "moe_rows_passes": (_runs_rows_kernel, _multiplies_rows)}


_RESULT = re.compile(r"^\(?(\w+)\[([\d,]*)\]")
_ITEMSIZE = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
             "bf16": 2, "f16": 2, "s16": 2, "u16": 2}


def _result_bytes(i: _Instr) -> int:
    """Bytes of an instruction's result (of a tuple's first array)."""
    m = _RESULT.match(i.rest)
    if m is None:
        return 0
    return _ITEMSIZE.get(m.group(1), 1) * math.prod(
        int(d) for d in m.group(2).split(",") if d)


def flash_relayouts(comps: Dict[str, list], scopes: Dict[str, OpScope]
                    ) -> Optional[float]:
    """`copy` / `transpose` instructions of a compiled program (`comps`: its
    parsed computations) that belong to a `multihead_attention` layer (by
    their own scope, or the compiler's by what they serve: `scopes`, the
    program's `op_scope_map`) and whose result is at least as large as the
    layer's q (the first result of its flash forward call), for each layer
    that calls the flash forward kernel; None where none does (every CPU
    program). 0 where the kernels read q, k, v and write o as the
    projections hold them (`flash_attention.entry_of`: `merged`,
    `two_heads`); the `swapped` entry's `[b, s, h, d]` <-> `[b, h, s, d]`
    and whatever the compiler relays for the head norm and the rotation
    show here. A `copy-start` is the compiler's prefetch, no relayout."""
    q_bytes: Dict[str, int] = {}
    moved: list = []
    for items in comps.values():
        for i in items:
            at = scopes.get(i.name)
            if at is None or at.op_type != "multihead_attention":
                continue
            if at.opcode == "ff_flash_attention_fwd":
                q_bytes[at.layer] = min(_result_bytes(i),
                                        q_bytes.get(at.layer, 1 << 62))
            elif i.opcode in ("copy", "transpose"):
                moved.append((at.layer, _result_bytes(i)))
    if not q_bytes:
        return None
    return sum(size >= q_bytes[layer] for layer, size in moved
               if layer in q_bytes) / len(q_bytes)


def step_passes(hlo_text: str, op_types: Dict[str, str],
                scopes: Optional[Dict[str, OpScope]] = None
                ) -> Dict[str, float]:
    """How often a compiled training step runs a piece of work for once
    that its forward pass does: the instructions of each kind of
    `_KEPT_WORK` in all phases over those of the forward phase (a
    checkpoint's recomputation carries the backward pass's wrappers). A
    kind with no instruction in a forward phase is left out.

    `moe_routing_passes`: 1 where every checkpoint around an expert layer
    keeps the decision (`moe_ops.ROUTING_KEPT`), 3 where the layer's block
    and the `remat_blocks` unit around it each decide again.
    `flash_fwd_passes`: 1 with no checkpoint around the flash call, and
    where the one around it keeps what the forward kernel wrote
    (`flash_attention.FLASH_KEPT`: a `remat_blocks` unit's); 2 where the
    recomputation runs the kernel again; absent from every CPU program,
    whose kernels are interpreted. `moe_rows_passes`: the forward
    evaluations of an expert layer's rows products (`ff_moe_rows`' calls,
    or where no kernel runs the grouped products that are no transpose):
    2 where a `remat_blocks` unit keeps the layer's result
    (`moe_ops.LAYER_KEPT`: the forward pass, and the token block's own
    recomputation for the gates' gradient), 3 where the unit's
    recomputation runs the layer again. Beside them `flash_relayouts`, no
    ratio of passes: the q-sized relayouts a flash layer (`scopes`: the
    program's `op_scope_map` where the caller has it)."""
    by_phase: Dict[Any, Dict[str, int]] = {
        counted: {} for ways in _KEPT_WORK.values() for counted in ways}
    comps = _parse_computations(hlo_text)[0]
    for items in comps.values():
        for i in items:
            if not i.op_name:
                continue
            layer, phase = scope_of_op_name(i.op_name, op_types)
            for counted, seen in by_phase.items():
                if counted(i, op_types.get(layer, "")):
                    seen[phase] = seen.get(phase, 0) + 1
    out = {}
    for kind, ways in _KEPT_WORK.items():
        seen = next((by_phase[counted] for counted in ways
                     if by_phase[counted].get("forward")), None)
        if seen is not None:
            out[kind] = sum(seen.values()) / seen["forward"]
    relayouts = flash_relayouts(
        comps, op_scope_map(hlo_text, op_types) if scopes is None else scopes)
    if relayouts is not None:
        out["flash_relayouts"] = relayouts
    return out


def routing_passes(hlo_text: str, op_types: Dict[str, str]
                   ) -> Optional[float]:
    return step_passes(hlo_text, op_types).get("moe_routing_passes")


# ------------------------------------------------------------- the registry
class Program:
    """One jitted program that puts work on the device: a weak reference to
    it, the graph layers' op types, and from its first run on the executable
    that run used (`jax.stages.Compiled`: the handle the jit's own cache
    holds, no arrays). The HLO text is rendered and parsed only when
    `op_scopes` asks."""

    def __init__(self, name: str, jitted, layers, owner=None):
        self.name = name
        self._jitted = weakref.ref(jitted)
        self._owner = weakref.ref(jitted if owner is None else owner)
        self.op_types = {l.name: l.op_type.value for l in layers}
        self.compiled = None
        self.scopes: Optional[Dict[str, OpScope]] = None
        self.module = ""        # "jit_train_step": the profile's module name

    def first_run(self, *args, **kwargs) -> None:
        """The owner calls this once, just before the program's first call
        (`if prog.compiled is None`), with that call's arguments: lowering
        and compiling here is what the call itself would do next, and the
        call then finds both done."""
        self.compiled = self._jitted().lower(*args, **kwargs).compile()

    def render(self) -> Optional[Dict[str, OpScope]]:
        if self.scopes is not None or self.compiled is None:
            return self.scopes
        with tel.span(SPAN, cat="compile", program=self.name) as sp:
            text = self.compiled.as_text()
            self.module = text[:text.find(",")].replace("HloModule ", "").strip()
            self.scopes = op_scope_map(text, self.op_types)
            fusions = [s for s in self.scopes.values() if s.opcode == "fusion"]
            # `layers_named`: how many of the registered layers the text
            # names (pass-through layers have no instruction of their own).
            # Near 0: the executable came from a persistent compile cache
            # that another graph filled (the cache's key leaves metadata
            # out, so the name stacks are whoever compiled it first)
            sp.set(instructions=len(self.scopes), fusions=len(fusions),
                   mixed=sum(s.mixed for s in fusions),
                   inferred=sum(s.inferred for s in self.scopes.values()),
                   text_bytes=len(text), module=self.module,
                   layers=len(self.op_types),
                   layers_named=len({s.layer for s in self.scopes.values()
                                     if s.layer}))
            # a training step: how often it runs what a checkpoint around
            # an op may keep, a property of the program
            if any(s.phase == "backward" for s in self.scopes.values()):
                sp.set(**step_passes(text, self.op_types, self.scopes))
        return self.scopes


# name -> the programs registered under it. A program's owner (a
# CompiledModel, an engine, a cache) keeps the handle too; the registry
# lets go of a dead owner's programs when the next program registers under
# the name, so a reader that comes after the owner is gone (the benchmark's,
# once its cell has returned) still finds the last programs that ran.
_PROGRAMS: Dict[str, List[Program]] = {}


def register_program(name: str, jitted, layers, owner=None) -> Program:
    """Register a jitted program under `name` ("train_step", "serve/prefill",
    "serve/decode", "serve/commit") and return its handle, which the caller
    keeps; nothing is lowered or rendered. `layers`: the graph layers it
    runs (anything with `.name` and `.op_type.value`). `owner`: whose life
    the registration shares where that is not the jitted function's own (a
    cache that runs a module's jit at its own shapes)."""
    prog = Program(name, jitted, layers, owner)
    held = _PROGRAMS.setdefault(name, [])
    held[:] = [p for p in held if p._owner() is not None]
    held.append(prog)
    return prog


def op_scopes(name: str) -> List[Dict[str, OpScope]]:
    """The instruction -> OpScope maps of the programs registered under
    `name` that have run, rendered on first demand and kept. This is the
    only place HLO text is rendered: with nobody asking (tracing off),
    a program costs its handle."""
    maps = [p.render() for p in _PROGRAMS.get(name, ())]
    return [m for m in maps if m]


def instructions_in_scope(hlo_text: str, scope: str) -> "set[str]":
    """Names of the instructions of one compiled program's optimized HLO
    text that run as events of their own under the `jax.named_scope`
    `scope` (a whole segment of the name stack, as it is or inside JAX's
    transform wrappers: a scope entered under a `jax.vjp` that a backward
    rule calls reads `jvp(scope)` and `transpose(jvp(scope))`): an
    instruction whose own `op_name` holds it, a fusion most of whose named
    body does, what the compiler made (no name stack) inside a loop or
    branch that does, and
    what the chip's compiler made OF a matrix product that does (a
    ragged-dot becomes a Mosaic call named `ragged-dot-none.N` with no name
    stack: it is under the scope where an instruction it reads, or one that
    reads it, is). Containers themselves are left out (their bodies are
    counted)."""
    comps, entry = _parse_computations(hlo_text)
    if entry is None:
        return set()

    def named(op_name):
        return any(_unwrap(seg)[1] == scope for seg in _segments(op_name))

    def under(i, inherited):
        if i.opcode == "fusion":
            called = _called(i.rest)
            inner = [named(b.op_name) for b in
                     (comps.get(called[0], []) if called else []) if b.op_name]
            if inner:
                return 2 * sum(inner) > len(inner)
        return named(i.op_name) if i.op_name else inherited

    out, seen, todo = set(), set(), [(entry, False)]
    while todo:
        comp, inherited = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        found = {i.name: under(i, inherited) for i in comps[comp]}
        users: Dict[str, list] = {}
        for i in comps[comp]:
            for o in i.operands:
                users.setdefault(o, []).append(i.name)
        for i in comps[comp]:
            inside = found[i.name]
            if not inside and not i.op_name and i.opcode == "custom-call" \
                    and i.name.startswith(_DOT_KERNELS):
                inside = any(found.get(n) for n in
                             users.get(i.name, []) + list(i.operands))
            if i.opcode in CONTAINERS or i.opcode.endswith("-start"):
                todo.extend((c, inside) for c in _called(i.rest))
            elif inside and i.opcode not in _NO_EVENT:
                out.add(i.name)
    return out


def instructions_under(name: str, scope: str) -> List["set[str]"]:
    """`instructions_in_scope` of every program registered under `name` that
    has run, one set a program (empty where the scope is not in it)."""
    return [instructions_in_scope(p.compiled.as_text(), scope)
            for p in _PROGRAMS.get(name, ()) if p.compiled is not None]


def merge_scopes(maps: Sequence[Dict[str, OpScope]]) -> Dict[str, OpScope]:
    """The union of several programs' maps: a name that two of them give
    different scopes maps to an AMBIGUOUS scope, not to a guess."""
    out: Dict[str, OpScope] = {}
    for m in maps:
        for name, s in m.items():
            had = out.setdefault(name, s)
            if had != s:
                out[name] = OpScope("", "", AMBIGUOUS, fold_name(name),
                                    False, False)
    return out


def device_time_by_scope(events, scopes, by_name: bool = False
                         ) -> Dict[Any, float]:
    """Device time by OpScope. `events`: [(instruction name, start ns,
    end ns)] of one device line; `scopes`: one map or several (the programs
    that may run inside the interval the events come from). Containers
    (`while`, `conditional`, `call`) are left out and their bodies counted.
    A name in no map goes to an UNATTRIBUTED scope that keeps the
    instruction's name as its opcode. `by_name`: keyed by (OpScope, the
    instruction's folded name), which parts `convert_reduce_fusion` from
    `fusion` as the benchmark's `device_ops` does."""
    merged = scopes if isinstance(scopes, dict) else merge_scopes(scopes)
    out: Dict[Any, float] = {}
    for name, start, end in events:
        s = merged.get(name)
        if s is None:
            if fold_name(name) in CONTAINERS:
                continue
            s = OpScope("", "", UNATTRIBUTED, name, False, False)
        elif s.opcode in CONTAINERS:
            continue
        key = (s, fold_name(name)) if by_name else s
        out[key] = out.get(key, 0.0) + (end - start)
    return out


# ------------------------------------------------------- the profile's events
_TPU_PLANE = re.compile(r"^/device:TPU:\d+$")


def profile_events(profile_dir: str):
    """{hlo module: [(instruction name, start ns, end ns)]} of the newest
    `.xplane.pb` under `profile_dir`, read with jax.profiler.ProfileData.
    On a TPU the first chip's plane: an event of its "XLA Ops" line is
    named by the instruction's text and belongs to the module whose event
    on the "XLA Modules" line ("jit_train_step(<fingerprint>)") holds it;
    on a CPU the host plane's thunk events (`hlo_op`, `hlo_module`).
    Instruction names repeat from program to program (`fusion.7`), so an
    event counts only for the program whose module ran it. None where
    there is no profile."""
    if not profile_dir or not os.path.isdir(profile_dir):
        return None
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None
    from jax.profiler import ProfileData

    try:
        data = ProfileData.from_file(paths[-1])
    except Exception:
        return None
    by_module: Dict[str, list] = {}
    planes = list(data.planes)
    tpu = sorted((p for p in planes if _TPU_PLANE.match(p.name)),
                 key=lambda p: p.name)
    if tpu:
        lines = {line.name: line for line in tpu[0].lines}
        if "XLA Ops" not in lines or "XLA Modules" not in lines:
            return None
        modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                          e.name.split("(", 1)[0])
                         for e in lines["XLA Modules"].events)
        starts = [m[0] for m in modules]
        for e in lines["XLA Ops"].events:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i < 0 or e.start_ns >= modules[i][1]:
                continue            # outside every module's run
            by_module.setdefault(modules[i][2], []).append(
                (e.name.split(" = ", 1)[0].lstrip("%"), e.start_ns,
                 e.start_ns + e.duration_ns))
        return by_module or None
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_op" in stats:
                    by_module.setdefault(str(stats.get("hlo_module", "")),
                                         []).append(
                        (str(stats["hlo_op"]), e.start_ns,
                         e.start_ns + e.duration_ns))
    return by_module or None


def measured_from_trace(profile_dir: str, programs: Sequence[Program]
                        ) -> Optional[Dict[str, Dict[str, float]]]:
    """Primary measurement path: the profiler's device events under
    `profile_dir` (`jax.profiler.trace` under --profiling writes
    `plugins/profile/<run>/*.xplane.pb`) joined with the registered
    programs' instruction -> scope maps. Returns layer -> {phase: device
    microseconds} across the profile (work outside every layer under the
    layer ""; UNATTRIBUTED / AMBIGUOUS time under those phases), or None
    when there is no profile or no program that has run (the caller falls
    back to partitioned re-execution). Totals are only meaningful as
    FRACTIONS of the step — the caller normalizes against the measured
    step time."""
    rendered = [p for p in programs if p.render()]
    events = profile_events(profile_dir) if rendered else None
    if not events:
        return None
    totals: Dict[str, Dict[str, float]] = {}
    for module, evs in events.items():
        maps = [p.scopes for p in rendered if p.module == module]
        if not maps:
            continue        # another program's run (eval_step, a commit)
        for s, ns in device_time_by_scope(evs, maps).items():
            by_phase = totals.setdefault(s.layer, {})
            by_phase[s.phase] = by_phase.get(s.phase, 0.0) + ns / 1e3
    return totals or None


# ------------------------------------------------------------- the report
def build_report(items: List[Dict[str, Any]],
                 step_time_s: Optional[float] = None,
                 mult: int = 1,
                 profile_dir: Optional[str] = None,
                 source: str = "auto",
                 measure_repeats: int = 3,
                 measure_warmup: int = 1,
                 emit: Optional[bool] = None,
                 inference: bool = False,
                 tag: Optional[str] = None,
                 programs: Sequence["Program"] = ()) -> Dict[str, Any]:
    """Assemble the attribution report.

    items: one dict per placed op — {"layer", "cand", "machine",
    "predicted_s" (per fwd+bwd pass; None -> analytic), "stage" (or None)}.
    mult: passes per optimizer update (accum_steps, or the pipeline's M
    microbatches) — per-op numbers scale by it so every column is per
    UPDATE, directly comparable to the drift monitor's measured windows.
    step_time_s: the REAL measured per-update wall time (drift monitor);
    measured per-op times are rescaled so attributed times sum to it
    (proportional attribution — the partitioned re-execution measures ops
    in isolation, so XLA cross-op fusion makes the raw sum overshoot; the
    trace path's totals are fractions of the stream and need the same
    normalization). When None, attributed == measured and scale == 1.
    source: "auto" (trace when available, else measure), "trace",
    "measure".
    programs: the `register_program` handles of the jitted programs the
    profile under `profile_dir` ran (the trace path joins their compiled
    instructions with the profile's events; without them there is no
    trace path).
    emit: write op/attr + op/drift_topk telemetry events (default: when
    the telemetry sink is enabled).
    inference: forward-pass-only regime (serving prefill/decode — ISSUE
    14 satellite): measures each op's jitted FORWARD at shard-local
    shapes and prices the roofline's forward leg: the bandwidth-bound
    decode regime training rows never show.
    tag: emitted as the op/attr events' "source" (e.g. "serve_decode"),
    so rows record which execution regime measured them.
    """
    from flexflow_tpu.search.measure import MeasuredCost

    if emit is None:
        emit = tel.enabled()
    trace_totals = None
    if source in ("auto", "trace"):
        # trace totals are WHOLE-RUN device-time sums (every step of every
        # epoch) — only their proportions are meaningful, so the trace
        # path requires a measured step time to normalize against; "auto"
        # without one falls back to the per-update re-execution path
        if step_time_s:
            trace_totals = measured_from_trace(profile_dir or "", programs)
        if source == "trace":
            if not step_time_s:
                raise ValueError("source='trace' needs a measured step "
                                 "time (run fit() first)")
            if trace_totals is None:
                raise ValueError(f"no parseable profiler trace under "
                                 f"{profile_dir!r} (run with --profiling)")
    used_source = "trace" if trace_totals else "measure"

    mcs: Dict[str, MeasuredCost] = {}  # one per machine fingerprint

    def mc_for(machine):
        fp = memo.machine_fingerprint(machine)
        if fp not in mcs:
            mcs[fp] = MeasuredCost(machine, repeats=measure_repeats,
                                   warmup=measure_warmup, cache_dir="")
        return mcs[fp]

    rows: List[Dict[str, Any]] = []
    for it in items:
        layer, cand, machine = it["layer"], it["cand"], it["machine"]
        roof = cmod.op_roofline(layer, cand, machine)
        if inference:
            # forward leg only: op_roofline prices fwd+bwd (the 3x-flops /
            # 2x-bytes training convention), a serving step runs forward
            roof = dict(roof)
            t_flop = roof["t_flop_s"] / 3.0
            t_mem = roof["t_mem_s"] / 2.0
            roof["roofline_s"] = max(t_flop, t_mem)
            roof["device_flops"] = roof["device_flops"] / 3.0
            roof["hbm_bytes"] = roof["hbm_bytes"] / 2.0
            roof["bound"] = "bandwidth" if t_mem > t_flop else "compute"
            roof["mfu_ceiling"] = (
                roof["device_flops"] / (roof["roofline_s"] * machine.flops)
                if roof["roofline_s"] > 0 else 0.0)
        if trace_totals is not None:
            # whole-run device us; normalized to per-update seconds below
            measured = sum(trace_totals.get(layer.name, {}).values()) * 1e-6
        elif inference:
            measured = mc_for(machine).op_time_fwd(layer, cand) * mult
        else:
            measured = mc_for(machine).op_time(layer, cand) * mult
        predicted = it.get("predicted_s")
        if predicted is None:
            predicted = cand.op_time(layer, machine)
        feats = op_features(layer, cand, machine)
        rows.append({
            "stage": it.get("stage"),
            "layer": layer.name,
            "op": layer.op_type.value,
            "candidate": cand.name,
            "predicted_s": float(predicted) * mult,
            "measured_s": float(measured),
            "roofline_s": roof["roofline_s"] * mult,
            "bound": roof["bound"],
            "mfu_ceiling": roof["mfu_ceiling"],
            "flops": roof["flops"],
            "device_flops": roof["device_flops"] * mult,
            "hbm_bytes": roof["hbm_bytes"],
            "machine_flops": machine.flops,
            "key": feature_key(feats),
            "features": feats,
        })
        if trace_totals is not None:
            rows[-1]["phases_s"] = {
                ph: us * 1e-6
                for ph, us in trace_totals.get(layer.name, {}).items()}

    outside: Dict[str, float] = {}
    if used_source == "trace":
        # per-update measured time = the op's share of the profiled stream
        # x the real step time (trace totals span every profiled step, so
        # only the proportions carry over). The stream is ALL the device
        # time the join saw: the layers' rows, the layers that have no row
        # (pass-through placements), and what runs outside every layer
        # (update, loss, other, unattributed), reported as `outside_s`
        raw = sum(us for by_phase in trace_totals.values()
                  for us in by_phase.values()) * 1e-6
        if raw > 0:
            f = float(step_time_s) / raw
            for r in rows:
                r["measured_s"] *= f
                r["phases_s"] = {ph: v * f for ph, v in r["phases_s"].items()}
            outside = {ph: us * 1e-6 * f
                       for ph, us in trace_totals.get("", {}).items()}
    total_meas = sum(r["measured_s"] for r in rows)
    scale = 1.0
    if step_time_s and total_meas > 0:
        scale = float(step_time_s) / total_meas
    for r in rows:
        r["attributed_s"] = r["measured_s"] * scale
        denom = (r["attributed_s"] if step_time_s else r["measured_s"])
        r["mfu"] = (r["device_flops"] / (denom * r["machine_flops"])
                    if denom > 0 else 0.0)
    rows.sort(key=lambda r: -r["attributed_s"])
    report = {
        "rows": rows,
        "step_time_s": float(step_time_s) if step_time_s else None,
        "measured_total_s": total_meas,
        "attributed_total_s": sum(r["attributed_s"] for r in rows),
        # isolated-measurement over-coverage of the real step (fusion /
        # overlap the isolated path can't see; trace path: stream fraction)
        "coverage": (total_meas / step_time_s) if step_time_s else None,
        "scale": scale,
        "mult": mult,
        "source": used_source,
        # trace source only: per-update device seconds outside every layer,
        # by phase (update / loss / other / unattributed / ambiguous)
        "outside_s": outside,
    }
    report["top_drift"] = drift_top_k(rows)
    if emit:
        for r in rows:
            args = {k: r[k] for k in
                    ("layer", "op", "candidate", "predicted_s",
                     "measured_s", "attributed_s", "roofline_s", "bound",
                     "mfu", "mfu_ceiling", "key")}
            if r["stage"] is not None:
                args["stage"] = r["stage"]
            args["source"] = tag or used_source
            if "phases_s" in r:
                args["phases_s"] = r["phases_s"]
            args["features"] = r["features"]
            tel.event(OP_EVENT, cat="op", **args)
        td = report["top_drift"]
        if td["rows"]:
            tel.event(DRIFT_EVENT, cat="op",
                      worst=td["rows"][0]["layer"],
                      explained=td["explained"],
                      rows=[{"layer": x["layer"], "err_s": x["err_s"],
                             "share": x["share"]} for x in td["rows"]])
    return report


def drift_top_k(rows: Sequence[Dict[str, Any]], k: int = 3
                ) -> Dict[str, Any]:
    """The per-op drift localization: which ops explain the step-time
    misprediction? err = attributed - predicted per op; the top-k by |err|
    with their share of the total absolute error. `explained` is the
    cumulative share — "these 3 ops explain 87% of the misprediction" is
    the cue to recalibrate exactly those measurements (tools/calibrate.py)
    or reroute the search around the mispriced placement."""
    errs = []
    for r in rows:
        meas = r.get("attributed_s", r.get("measured_s", 0.0))
        errs.append((abs(meas - r["predicted_s"]),
                     meas - r["predicted_s"], r))
    total = sum(a for a, _e, _r in errs)
    errs.sort(key=lambda x: -x[0])
    out = []
    cum = 0.0
    for a, e, r in errs[:max(0, k)]:
        share = a / total if total > 0 else 0.0
        cum += share
        out.append({"layer": r["layer"], "op": r["op"],
                    "predicted_s": r["predicted_s"],
                    "measured_s": r.get("attributed_s",
                                        r.get("measured_s", 0.0)),
                    "err_s": e, "share": share})
    return {"rows": out, "explained": cum,
            "total_abs_err_s": total, "k": min(k, len(errs))}


# ------------------------------------------------------------- rendering
def format_report(report: Dict[str, Any], top: int = 0) -> List[str]:
    """The [ops] table + [drift] top-K lines (profile_report and
    tools/profile_attribution.py share this formatting)."""
    rows = report["rows"][:top] if top else report["rows"]
    has_stage = any(r["stage"] is not None for r in rows)
    traced = report["source"] == "trace"
    lines = []
    head = ("st " if has_stage else "") + \
        f"{'layer':24} {'op':14} {'pred':>9} {'attr':>9} {'roof':>9} " \
        f"{'mfu':>5} {'bound':>9} {'%':>5}" + \
        (f" {'fwd':>9} {'bwd':>9}" if traced else "")
    lines.append(head)
    total = report["attributed_total_s"] or 1.0
    for r in rows:
        st = f"{r['stage']:2d} " if has_stage else ""
        ph = r.get("phases_s", {})
        lines.append(
            f"{st}{r['layer'][:24]:24} {r['op'][:14]:14} "
            f"{r['predicted_s'] * 1e6:8.1f}u {r['attributed_s'] * 1e6:8.1f}u "
            f"{r['roofline_s'] * 1e6:8.1f}u {r['mfu']:5.2f} "
            f"{r['bound']:>9} {100 * r['attributed_s'] / total:4.1f}%"
            + (f" {ph.get('forward', 0.0) * 1e6:8.1f}u"
               f" {ph.get('backward', 0.0) * 1e6:8.1f}u" if traced else ""))
    if traced and report.get("outside_s"):
        lines.append("[ops] device time outside every layer, per update: "
                     + ", ".join(f"{ph}={v * 1e6:.1f}us" for ph, v in
                                 sorted(report["outside_s"].items(),
                                        key=lambda kv: -kv[1])))
    st_ = report.get("step_time_s")
    lines.append(
        f"[ops] source={report['source']} "
        f"attributed_total={report['attributed_total_s'] * 1e3:.3f}ms"
        + (f" step={st_ * 1e3:.3f}ms coverage={report['coverage']:.2f}x"
           if st_ else " (no measured step time; run fit() first)"))
    td = report["top_drift"]
    if td["rows"]:
        worst = ", ".join(f"{x['layer']} ({x['err_s'] * 1e6:+.1f}us)"
                          for x in td["rows"])
        lines.append(f"[drift] top-{td['k']} mispriced ops explain "
                     f"{100 * td['explained']:.0f}% of the per-op "
                     f"misprediction: {worst}")
    return lines
