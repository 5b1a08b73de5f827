"""Serving programs: clone-by-replay + the serving strategy search.

The serving stack runs TWO programs per decoder model (the prefill/decode
split of the TPU-serving literature — PAPERS.md 2605.25645): a prefill
program over the full prompt `[slots, S]` and a single-token decode program
over `[slots, 1]` that reads/writes the paged KV cache. Both are built here
by REPLAYING the training graph into a fresh FFModel with transformed input
shapes and per-op param overrides — layer names, weight specs, and topo
order are preserved exactly, so trained params transfer 1:1 and
`build_init_fn` produces bitwise-identical init for all three graphs.

Each program then gets its OWN strategy from the existing candidates/DP
search (`search_graph`) under serving-specific pricing:

- prefill is compute-bound like training: candidates are priced by the
  forward compute leg of the roofline (`op_roofline`'s t_flop), so the
  search behaves like the training search minus grad-sync — data
  parallelism over slots usually wins (tensor parallelism would pay an
  output all-reduce that scales with S for zero training-time benefit).
- decode is memory-bandwidth-bound: candidates are priced by the forward
  memory leg (weight + activation streaming) plus the KV-cache traffic of
  one step, divided by the candidate's head-shard degree — so
  weight-sharded layouts (tp_heads / tp_col) win because they divide the
  per-step HBM stream, exactly the physics that makes prefill and decode
  want DIFFERENT shardings.

KV-cache residency enters the decode search's memory cap: the HBM budget
is reduced by `KVCacheSpec.per_device_bytes(degree)` where degree is the
model-axis degree the search chose for the attention weights (iterated to
a fixed point — the budget depends on the winner, the winner on the
budget; one re-search converges because more headroom never shrinks the
chosen degree's feasibility).

Both strategies persist in the strategy cache (search/strategy_cache.py)
under independent keys — the graph fingerprints already differ (shapes +
decode/kv_out params) and the opt fingerprint carries kind/objective/KV
geometry — so a warm `compile_serving` restores both programs with zero
DP expansions.
"""

from __future__ import annotations

import math
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from flexflow_tpu.core.graph import topo_order
from flexflow_tpu.core.layer import Layer
from flexflow_tpu.core.model import FFModel
from flexflow_tpu.core.tensor import Tensor, TensorSpec
from flexflow_tpu.ops import get_op_def
from flexflow_tpu.ops.registry import PAGED_STATE_KINDS
from flexflow_tpu.parallel.machine import MachineSpec
from flexflow_tpu.search import cost_model as cm


def _serving_params(layer: Layer, kind: str) -> dict:
    """A layer's params in a serving clone: what its op's `serving_params`
    says its prefill / decode twin looks like (attention's kv_out and
    paged-cache modes, dropout hard-zeroed, a state-space mixer's state_out
    and one-step forms), the layer's own where the op has none."""
    hook = get_op_def(layer.op_type).serving_params
    p = dict(layer.params)
    return p if hook is None else hook(p, kind)


def page_geometry(model) -> Dict[str, int]:
    """What a token's row holds in the paged pools of `model`'s layers, from
    the layers' own declarations (their op's `page_state`): `{"heads",
    "head_dim"}` of K and V pools, `{"latent_dim"}` of a latent pool, with
    `{"index_dim"}` where indexer layers page their key beside them, or `{}`
    where no layer pages anything (every layer that carries state keeps it
    a slot: `recurrent_layers`). One geometry a kind of state: layers of a
    kind that declare different ones raise, and so do K/V and a latent in
    one model. K/V layers may declare two EXTENTS of that geometry: those
    that state a `window` keep a ring of its pages a slot, the others the
    whole context (`{"window": w}` then, one window a model)."""
    found: Dict[str, Dict[str, Dict[str, int]]] = {}
    for l in topo_order(model.layers):
        d = get_op_def(l.op_type)
        if d.state_kind in PAGED_STATE_KINDS:
            found.setdefault(d.state_kind, {})[l.name] = dict(d.page_state(l))
    if len(set(found) - {"paged_index"}) > 1:
        raise NotImplementedError(
            f"one cache geometry a model: layers page {sorted(found)}")
    out: Dict[str, int] = {}
    windows = {g.pop("window") for g in found.get("paged_kv", {}).values()
               if "window" in g}
    if len(windows) > 1:
        raise NotImplementedError(
            f"one window a model: layers keep {sorted(windows)}")
    out.update({"window": w for w in windows})
    for layers in found.values():
        first = next(iter(layers))
        for name, geometry in layers.items():
            if geometry != layers[first]:
                raise NotImplementedError(
                    f"one cache geometry a model: {first} pages "
                    f"{layers[first]}, {name} pages {geometry}")
        out.update(layers[first])
    return out


def recurrent_layers(model) -> Dict[str, Dict[str, tuple]]:
    """The layers of `model` that carry fixed-size per-request state (their
    op's `state_kind` is "recurrent"), in topo order: {layer name: {leaf:
    (per-slot shape, dtype)}}. The paged K/V of attention layers is the
    other kind, listed by `clone_for_serving`."""
    out: Dict[str, Dict[str, tuple]] = {}
    for l in topo_order(model.layers):
        d = get_op_def(l.op_type)
        if d.state_kind == "recurrent":
            out[l.name] = d.slot_state(l)
    return out


def clone_for_serving(model, kind: str, slots: int,
                      decode_seq: int = 1) -> Tuple[FFModel, List[str]]:
    """Replay `model`'s graph into a fresh FFModel shaped for serving.

    Inputs follow the decoder contract `[batch, seq, ...]`: the batch dim
    becomes `slots` and, for kind="decode", the seq dim becomes `decode_seq`
    (1 for the plain decode program; K+1 for the speculative-verify program
    that teacher-forces K drafted tokens in one batched pass). Weight specs
    depend only on feature dims, so every layer re-infers cleanly and
    params transfer by (layer name, weight name).

    Returns (serving_model, attention_layer_names) — the latter is the set
    of layers whose pages the paged cache holds (K/V, or a latent), in topo
    order.
    """
    if kind not in ("prefill", "decode"):
        raise ValueError(f"unknown serving program kind {kind!r}")
    if not model.input_tensors:
        raise ValueError("model has no inputs")
    orig_batch = model.input_tensors[0].spec.shape[0]

    def map_shape(shape):
        s = list(shape)
        if s and s[0] == orig_batch:
            s[0] = slots
        if kind == "decode" and len(s) > 1:
            s[1] = int(decode_seq)
        return tuple(s)

    sm = FFModel(model.config)
    tmap = {}
    for t in model.input_tensors:
        nt = Tensor(TensorSpec(map_shape(t.spec.shape), t.spec.dtype),
                    name=t.name)
        tmap[t.guid] = nt
        sm.input_tensors.append(nt)
    attn: List[str] = []
    for l in topo_order(model.layers):
        if getattr(l, "branches", None):
            raise NotImplementedError(
                "serving clone does not support composite fork_join layers")
        nl = Layer(l.op_type, _serving_params(l, kind),
                   [tmap[t.guid] for t in l.inputs], name=l.name)
        specs = get_op_def(nl.op_type).infer(nl)
        for i, spec in enumerate(specs):
            nt = nl.add_output(spec, idx=i, name=l.outputs[i].name)
            tmap[l.outputs[i].guid] = nt
        sm.layers.append(nl)
        if get_op_def(l.op_type).state_kind in PAGED_STATE_KINDS:
            attn.append(l.name)
    return sm, attn


def attn_head_degree(strategy_or_result, attn_layers, machine: MachineSpec) -> int:
    """The model-axis degree the search put on the attention heads: the
    sharding degree of wq's output-features dim (the concatenated heads).
    Accepts a SearchResult (choices) or a Strategy (op_shardings)."""
    deg = 1
    for name in attn_layers:
        dims = None
        choices = getattr(strategy_or_result, "choices", None)
        if choices is not None:
            cand = choices.get(name)
            dims = cand.weight_dims.get("wq") if cand is not None else None
        else:
            sh = strategy_or_result.op_shardings.get(name)
            dims = sh.weights.get("wq") if sh is not None else None
        if dims and len(dims) > 1 and dims[1] is not None:
            deg = max(deg, cm.dims_degree([dims[1]], machine))
    return deg


def _fwd_comm(cand) -> float:
    """Forward-only collectives of a candidate: serving programs never run
    the backward pass, so prefer extra_comm_fwd (set by sp_ring and the
    flash-infeasibility penalty) over the fwd+bwd extra_comm. Without the
    split, sp_ring's bwd double-ring would be charged against forward-only
    prefill and the DP could never find the honest ring-vs-flash crossover."""
    fwd = getattr(cand, "extra_comm_fwd", None)
    return cand.extra_comm if fwd is None else fwd


def _prefill_cost_fn(machine: MachineSpec):
    """Forward-only roofline: compute leg vs memory leg (op_roofline's legs
    are fwd+bwd — 3x flops, 2x bytes — so divide back to the forward pass)
    plus the candidate's inherent forward collectives. Prefill over a full
    prompt is compute-bound, so t_flop dominates and the search ranks
    layouts by how well they split the matmuls without adding output
    all-reduces — until the prompt outgrows the flash kernel's VMEM budget,
    where the logits-materialization penalty makes sp_ring's ring hops the
    cheaper forward path (the searched ring-vs-flash crossover)."""

    def cost(layer, cand):
        rf = cm.op_roofline(layer, cand, machine)
        return max(rf["t_flop_s"] / 3.0, rf["t_mem_s"] / 2.0) + _fwd_comm(cand)

    return cost


def slot_state_bytes(leaves: Dict[str, tuple]) -> int:
    """Bytes of one slot's recurrent state in a layer, from its leaves
    `{leaf: (per-slot shape, dtype)}` (`recurrent_layers`' values)."""
    return sum(math.prod(shape) * np.dtype(dt).itemsize
               for shape, dt in leaves.values())


def _layer_state_bytes(layer: Layer) -> int:
    """The same of `layer` itself (0: it keeps no state a slot)."""
    d = get_op_def(layer.op_type)
    return slot_state_bytes(d.slot_state(layer)) \
        if d.state_kind == "recurrent" else 0


def _decode_cost_fn(machine: MachineSpec, kv_layer_bytes: int,
                    kv_spec: Optional["cm.KVCacheSpec"] = None,
                    prefetch_ahead: int = 1):
    """Bandwidth-bound pricing for the single-token step: the forward
    memory leg (dominated by streaming the layer's weight shard — seq=1
    makes every matmul a matvec) plus this layer's share of the live KV
    working set, divided by the candidate's head-shard degree (the pools
    are sharded over heads along the same axis as wq/wk/wv).

    With a host tier (kv_spec.host_pages > 0) each step also carries the
    tier's refill traffic: rotating a parked slot back moves one slot-layer
    over the host link, amortized over the `prefetch_ahead` steps the
    scheduler issues it early — traffic hidden behind more decode steps
    costs less per step, which is exactly the knob --kv-prefetch-ahead
    turns.

    A layer with per-slot recurrent state reads and writes it every step
    (every slot's, at worst): the cache term of a model that pages nothing
    is this alone. It is never sharded, so it moves no ranking."""
    slots = kv_spec.slots if kv_spec is not None else 0

    def cost(layer, cand):
        rf = cm.op_roofline(layer, cand, machine)
        t = rf["t_mem_s"] / 2.0 \
            + 2.0 * slots * _layer_state_bytes(layer) / machine.hbm_bw
        if kv_layer_bytes and get_op_def(layer.op_type).state_kind \
                in PAGED_STATE_KINDS:
            wq = cand.weight_dims.get("wq")
            deg = cm.dims_degree([wq[1]], machine) if wq and len(wq) > 1 else 1
            # a windowed layer's step reads its window's rows, not the context
            held = kv_spec.window_layer_bytes() if kv_spec is not None \
                and layer.params.get("window") else kv_layer_bytes
            t += held / max(1, deg) / machine.hbm_bw
            if kv_spec is not None and kv_spec.host_pages > 0:
                t += (kv_spec.pages_per_slot * kv_spec.page_bytes()
                      / max(1, deg) / machine.host_bw
                      / max(1, prefetch_ahead))
        return t + _fwd_comm(cand)

    return cost


def serving_optimize(smodel: FFModel, machine: MachineSpec, kind: str,
                     attn_layers: List[str],
                     kv_spec: Optional["cm.KVCacheSpec"] = None,
                     prefetch_ahead: int = 0):
    """Run the frontier DP on one serving program and return its Strategy.

    Warm path: the strategy cache keys on the serving graph's fingerprint
    (decode/kv_out params + shapes make prefill/decode/training all
    distinct) plus an opt fingerprint carrying kind/objective/KV geometry,
    so both serving programs cache and restore independently.
    """
    from flexflow_tpu import telemetry as tel
    from flexflow_tpu.search import strategy_cache as sc
    from flexflow_tpu.search.dp import search_graph
    from flexflow_tpu.search.optimize import result_to_strategy

    cfg = smodel.config
    objective = getattr(cfg, "serve_objective", "latency")
    # inference memory model: no optimizer moments; weight_mem_bytes'
    # param+grad pair over-counts by the grad slot, uniformly across
    # candidates, so the ranking is unaffected and the cap stays safe
    opt_mem = cm.OptMemSpec(moments=0)
    kv_fp = kv_spec.fingerprint() if kv_spec is not None else ()
    opt_fp = f"serve-{kind}-{objective}-{kv_fp}"
    if kv_spec is not None and kv_spec.host_pages > 0:
        # prefetch-ahead changes the decode pricing, so tiered configs key
        # separately; untiered fingerprints stay byte-identical to before
        opt_fp += f"-pf{int(prefetch_ahead)}"
    use_cache = bool(getattr(cfg, "strategy_cache", True))
    cache_dir = sc.resolve_dir(cfg) if use_cache else None
    key = None
    if use_cache:
        key = sc.cache_key(smodel, machine, cfg, "analytic", opt_fp)
        cached = sc.lookup(cache_dir, key, smodel, machine)
        if cached is not None:
            return cached
    beam = max(8, min(64, int(getattr(cfg, "search_budget", 16) or 16)))
    kv_layer = kv_spec.layer_bytes() if (kv_spec and kind == "decode") else 0
    cost_fn = (_decode_cost_fn(machine, kv_layer, kv_spec=kv_spec,
                               prefetch_ahead=prefetch_ahead)
               if kind == "decode" else _prefill_cost_fn(machine))
    t0 = time.perf_counter()
    degree = 1
    result = None
    with tel.span(f"serve/search_{kind}", cat="compile",
                  objective=objective, slots=smodel.input_tensors[0].shape[0]):
        for _ in range(2):
            budget = float(machine.hbm_bytes)
            if kind == "decode" and kv_spec is not None:
                budget -= kv_spec.per_device_bytes(degree)
            result = search_graph(
                smodel, machine, beam_width=beam,
                enable_parameter=getattr(cfg, "enable_parameter_parallel", True),
                enable_attribute=getattr(cfg, "enable_attribute_parallel", True),
                mem_budget=budget, cost_fn=cost_fn, opt_mem=opt_mem,
                objective=objective, inference=True)
            new_degree = attn_head_degree(result, attn_layers, machine)
            if kind != "decode" or kv_spec is None or new_degree == degree:
                break
            degree = new_degree  # re-cap with the KV shard the winner buys
    st = result_to_strategy(smodel, machine, result)
    st._predicted_cost = result.cost
    tel.event("serve/search_result", cat="compile", kind=kind,
              cost_s=result.cost, objective=objective)
    if use_cache:
        sc.store(cache_dir, key, st, meta={
            "cost_s": result.cost, "kind": kind, "objective": objective,
            "kv_fingerprint": list(kv_fp),
            "search_wallclock_s": time.perf_counter() - t0})
    return st
