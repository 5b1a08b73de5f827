"""compile_serving — two searched programs + a paged cache per model.

`compile_serving(model)` is the serving counterpart of `compile_model`:
it replays the training graph into a prefill twin (`[slots, S]`, attention
exposing per-head K/V) and a decode twin (`[slots, 1]`, attention
reading/writing the paged KV cache), runs the frontier DP on EACH under
serving pricing (serving/program.py — compute-priced prefill, bandwidth-
priced decode with the KV working set in both the cost and the memory
cap), and returns a `ServingCompiled` holding both jitted programs, the
`PagedKVCache` laid out by the winning decode strategy, and the memory/
watermark accounting the health layer checks.

Determinism is a hard default here, not a caller flag: both programs are
traced with training=False and a FIXED rng, and every dropout in the
clones is rate-0 — two runs of the same requests produce bitwise-identical
logits (the inference-determinism satellite of ISSUE 10).

Live hot-swap (ISSUE 11): `watch(root)` points the engine at a durable-
checkpoint root (the resilience layer's MANIFEST.json atomic-commit
protocol makes discovery race-free); `poll_swap()` — called by the
scheduler between decode steps, when no dispatched window is in flight —
loads any newer committed snapshot into a SECOND param tree (graph
fingerprint validated first, `CheckpointMismatchError` on a foreign
model), then activates it with a pointer flip. In-flight work holds
references to the old tree (params are never donated; cache state always
is), so no request is dropped or corrupted. Previous versions are retained in
memory (`retain` trees, default 2 = double buffer); `rollback()` re-pins
one — pinning stops `poll_swap` auto-advancing until `unpin()`.

Donation: `decode_step`, `verify_step`, `spec_round_step` and
`kv.commit_prefill` donate the cache STATE they are handed (argument 1 of
the step programs; target and draft state of the speculative round) and
append the step's K/V to the pools in place; `params` are argument 0 and
never donated. The one rule for callers: the state handed in is dead after
the call — use what comes back (`kv.adopt` it before anything else reads
`kv.state`). kv_cache.py says why the pools are `[pages, page, heads *
head_dim]`.
"""

from __future__ import annotations

import json
import logging
import os
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from flexflow_tpu import attribution, health
from flexflow_tpu import telemetry as tel
from flexflow_tpu.runtime.checkpoint import (CheckpointMismatchError,
                                             _graph_fingerprint)
from flexflow_tpu.runtime.resilience import (RetryPolicy, committed_snapshots,
                                             run_resilient)
from flexflow_tpu.compiler.compile import (_overlay_parallel_ops,
                                           build_init_fn, resolve_machine,
                                           weights_facts)
from flexflow_tpu.config import ensure_compile_cache
from flexflow_tpu.compiler.lowering import build_forward, constrainable
from flexflow_tpu.core.graph import topo_order
from flexflow_tpu.ops.op_type import OperatorType
from flexflow_tpu.ops.registry import STATS_KEY, get_op_def, rows_taken
from flexflow_tpu.ops.attention_ops import BLOCK_LENGTHS_KEY
from flexflow_tpu.parallel.default_strategy import data_parallel_strategy
from flexflow_tpu.parallel.machine import MachineSpec, build_mesh
from flexflow_tpu.search import cost_model as cm
from flexflow_tpu.serving.kv_cache import (ACTIVE_KEY, PAGE_TABLE_KEY, POS_KEY,
                                           WINDOW_TABLE_KEY, PagedKVCache,
                                           _tree_bytes)
from flexflow_tpu.serving.program import (attn_head_degree, clone_for_serving,
                                          page_geometry, recurrent_layers,
                                          serving_optimize, slot_state_bytes)

log = logging.getLogger("flexflow_tpu")

def _wq_heads_axis(strategy, attn_layers):
    """The mesh axis (or axis tuple) the decode strategy put on the
    attention heads — dim 1 of wq. The KV pools shard their heads dim on
    the same axis so cache reads/writes never reshard."""
    for name in attn_layers:
        sh = strategy.op_shardings.get(name)
        dims = sh.weights.get("wq") if sh is not None else None
        if dims and len(dims) > 1 and dims[1] is not None:
            d = dims[1]
            return tuple(d) if isinstance(d, list) else d
    return None


def _resolve_kv_dtype(cfg, kv_cache_dtype: Optional[str]):
    """Resolve --kv-cache-dtype into (pool dtype, itemsize, scale_itemsize,
    quantized). "auto" follows compute_dtype (the pre-quantization
    behavior); "bf16" forces bf16 pools; "int8" stores int8 pools with
    per-(page entry, head) f32 scales."""
    choice = (kv_cache_dtype or getattr(cfg, "kv_cache_dtype", "auto")
              or "auto").lower()
    if choice == "int8":
        return jnp.dtype(jnp.int8), 1, 4, True
    if choice == "bf16":
        return jnp.dtype(jnp.bfloat16), 2, 0, False
    if choice != "auto":
        raise ValueError(f"unknown kv_cache_dtype {choice!r} "
                         "(choose auto, bf16, or int8)")
    cdt = cfg.compute_dtype
    dt = jnp.dtype(cdt) if cdt and cdt not in ("float32", "f32") \
        else jnp.dtype(jnp.float32)
    return dt, int(dt.itemsize), 0, False


def _draft_from_spec(cfg, path: str, batch: int):
    """Build the --serve-draft-model graph: `path` is a JSON file of
    GPT2Config overrides (e.g. {"d_model": 64, "layers": 1, ...}) for a
    small gpt2-family draft sharing the target's vocab/seq contract.
    Programmatic callers pass `draft=` directly and skip this."""
    import json as _json

    from flexflow_tpu.core.model import FFModel
    from flexflow_tpu.models.gpt2 import GPT2Config, build_gpt2

    with open(path) as f:
        spec = _json.load(f)
    dm = FFModel(cfg)
    build_gpt2(dm, GPT2Config(**spec), batch=batch)
    return dm


def compile_serving(model, max_batch_slots: Optional[int] = None,
                    max_decode_len: Optional[int] = None,
                    kv_page_size: Optional[int] = None,
                    draft=None, spec_tokens: Optional[int] = None,
                    kv_cache_dtype: Optional[str] = None
                    ) -> "ServingCompiled":
    """Build the serving programs for a decoder `model` (inputs shaped
    `[batch, seq, ...]`). Knob precedence: explicit args > FFConfig flags
    (--max-batch-slots / --max-decode-len / --kv-page-size /
    --serve-draft-model / --serve-spec-tokens / --kv-cache-dtype) >
    defaults.

    Speculative decoding: `draft` (an FFModel twin-shaped like the target,
    or --serve-draft-model naming a GPT2Config JSON) is compiled through
    this same function recursively — its own prefill/decode programs, its
    own searched strategies, its own paged cache with the TARGET's slot/
    page geometry — and a third VERIFY program (`[slots, K+1]` decode-mode
    clone lowered with the searched decode strategy) batch-verifies the K
    drafted tokens in one pass.

    Chunked prefill: with `FFConfig.serve_prefill_chunk`
    (--serve-prefill-chunk) tokens a chunk the prompt program is a `[1, prefill_chunk]` block over one
    slot's own pages (`ServingCompiled.prefill_chunk`), the
    model's `seq` is a slot's whole context (prompt + answer), and the
    `[slots, seq]` wave is never compiled. A recurrent layer whose op
    declares it (`OpDef.chunk_from_state`) starts the block from its slot's
    state and leaves it the state after the block's last real position."""
    cfg = model.config
    ensure_compile_cache()
    # --telemetry-dir arms the process-global span stream for serving-only
    # flows too (compile_model does the same; request traces, serve/hist
    # and serve/slo events all ride this sink)
    if getattr(cfg, "telemetry_dir", ""):
        tel.configure(cfg.telemetry_dir,
                      max_mb=getattr(cfg, "telemetry_max_mb", None))
    slots = int(max_batch_slots or getattr(cfg, "max_batch_slots", 8) or 8)
    max_new = int(max_decode_len or getattr(cfg, "max_decode_len", 0) or 32)
    page = int(kv_page_size or getattr(cfg, "kv_page_size", 16) or 16)
    spec_k = int(spec_tokens if spec_tokens is not None
                 else getattr(cfg, "serve_spec_tokens", 0) or 0)
    kv_dtype, kv_itemsize, kv_scale_itemsize, kv_quantized = \
        _resolve_kv_dtype(cfg, kv_cache_dtype)
    # what a token's row holds in the pools, as the layers that page
    # declare it: the K/V heads (fewer than the query heads where they are
    # grouped) of head_dim each, or a latent; nothing where no layer pages
    geometry = page_geometry(model)
    latent = "latent_dim" in geometry
    seq = int(model.input_tensors[0].spec.shape[1])
    chunk = int(getattr(cfg, "serve_prefill_chunk", 0) or 0)
    if draft is None and spec_k > 0 and getattr(cfg, "serve_draft_model", ""):
        draft = _draft_from_spec(cfg, cfg.serve_draft_model,
                                 int(model.input_tensors[0].spec.shape[0]))
    with tel.span("serve/compile_serving", cat="compile", slots=slots,
                  max_decode_len=max_new, kv_page_size=page,
                  spec_tokens=spec_k if draft is not None else 0,
                  kv_dtype=str(kv_dtype)) as compile_span:
        machine = resolve_machine(cfg)
        mesh = build_mesh(machine)
        pre_model, attn = clone_for_serving(model, "prefill", slots)
        dec_model, _ = clone_for_serving(model, "decode", slots)
        recurrent = recurrent_layers(dec_model)
        if not attn and not recurrent:
            raise ValueError("compile_serving needs a model with layers that "
                             "carry per-request state: none pages K/V or a "
                             "latent, none keeps a state a slot (nothing to "
                             "cache)")
        state_bytes = sum(slot_state_bytes(leaves)
                          for leaves in recurrent.values())
        if recurrent:
            # what a layer with per-slot recurrent state does not support
            # yet fails here, not silently later
            kind = dec_model.get_layer_by_name(next(iter(recurrent))).op_type
            unsupported = [
                what for what, asked in (
                    ("the host KV tier (--kv-host-pages): parking a slot "
                     "would have to move its state with its pages",
                     int(getattr(cfg, "kv_host_pages", 0) or 0) > 0),
                    ("speculative decoding: the verify pass would have to "
                     "roll the state back", draft is not None and spec_k > 0))
                if asked]
            if unsupported:
                raise NotImplementedError(
                    f"compile_serving: the model has {len(recurrent)} "
                    f"{kind.value} layers with per-slot recurrent state, "
                    "which do not support " + "; ".join(unsupported))
        index_layers = [n for n in attn if get_op_def(
            dec_model.get_layer_by_name(n).op_type).state_kind
            == "paged_index"]
        # the layers of windowed attention: a second extent of K and V
        window = int(geometry.get("window", 0))
        window_layers = [n for n in attn if dec_model.get_layer_by_name(
            n).params.get("window")]
        beside = [w for w, a in (
            ("the host KV tier (--kv-host-pages)",
             int(getattr(cfg, "kv_host_pages", 0) or 0) > 0),
            ("speculative decoding", draft is not None and spec_k > 0),
            ("a quantized cache (--kv-cache-dtype int8)", kv_quantized)) if a]
        for what, asked in (
                (f"{len(index_layers)} layers page a sparse-attention "
                 "indexer's key beside K and V", bool(index_layers)),
                (f"{len(window_layers)} layers keep a window of {window} "
                 "positions in a ring of pages a slot", bool(window_layers)),
                (f"the prompt goes in by chunks of {chunk}", chunk > 0)):
            if asked and beside:
                raise NotImplementedError(
                    f"compile_serving: {what}, which does not support "
                    + "; ".join(beside) + " yet")
        if window_layers and not chunk:
            # the ring holds the window behind a chunk's first query and the
            # chunk: a wave's commit would have to write a prompt's last
            # window into it, which does not exist yet
            raise NotImplementedError(
                f"compile_serving: {len(window_layers)} layers keep a window "
                f"of {window} positions in a ring of pages a slot, which "
                "needs the prompt to go in by chunks (--serve-prefill-chunk)")
        # the recurrent ops whose sequence form cannot start from a state
        stateless_start = sorted({
            op.value for op in (dec_model.get_layer_by_name(n).op_type
                                for n in recurrent)
            if not get_op_def(op).chunk_from_state})
        lacking = [what for what, found in (
            ("paged_latent layers", latent),
            (f"recurrent layers of {', '.join(stateless_start)}, whose ops "
             "declare no such form (OpDef.chunk_from_state)", stateless_start),
            ("none that pages", not attn)) if found]
        if chunk and lacking:
            # a chunk attends over what its slot has cached: K/V pages can be
            # read back as they were written, and a recurrent layer whose op
            # declares it (OpDef.chunk_from_state) starts its sequence form
            # from the slot's state; a latent's chunk form (absorbed or
            # decompressed over the cache) does not exist yet, nor the other
            # recurrent ops' start from a state
            raise NotImplementedError(
                "compile_serving: chunked prefill (--serve-prefill-chunk) "
                "needs every stateful layer to page K/V or to start its "
                "sequence form from a slot's state; this model has "
                + "; ".join(lacking))
        if latent:
            unsupported = [
                what for what, asked in (
                    ("the host KV tier (--kv-host-pages)",
                     int(getattr(cfg, "kv_host_pages", 0) or 0) > 0),
                    ("speculative decoding", draft is not None and spec_k > 0),
                    ("a quantized cache (--kv-cache-dtype int8)",
                     kv_quantized))
                if asked]
            if unsupported:
                raise NotImplementedError(
                    f"compile_serving: the model's {len(attn)} layers page "
                    "paged_latent state (a token's latent, no heads axis), "
                    "which does not support " + "; ".join(unsupported)
                    + " yet")
        compile_span.set(kv_layers=len(attn), state_layers=len(recurrent),
                         state_bytes_per_slot=state_bytes,
                         chunk_state_layers=len(recurrent) if chunk else 0,
                         paged_state="paged_latent" if latent
                         else "paged_kv" if attn else "none")
        expert_layers = [l for l in model.layers
                         if l.op_type is OperatorType.MOE_LAYER]
        if expert_layers:
            p = expert_layers[0].params
            lo, hi = p["experts_held"]
            # the width the experts' row buffers have: the layer's own, or
            # the latent its experts work in
            compile_span.set(
                experts_held=hi - lo, experts_routed_over=p["num_experts"],
                expert_layers=len(expert_layers),
                experts_latent_dim=p.get("latent_size", 0))
        # what the first paged and the first recurrent layer's ops say of
        # their state (its layout, what a pool's rows went through)
        kv_layers = [n for n in attn if n not in index_layers]
        for name in kv_layers[:1] + index_layers[:1] + list(recurrent)[:1]:
            first = dec_model.get_layer_by_name(name)
            facts = get_op_def(first.op_type).span_facts
            if facts is not None:
                compile_span.set(**facts(first))
        # tiered KV (--kv-host-pages H > 0): host pages SUBSTITUTE device
        # pages — the HBM pool shrinks to slots*pages_per_slot - H (floored
        # at one slot's worth, the minimum a decoding slot must keep hot),
        # so total two-tier capacity stays slots*pages_per_slot while the
        # HBM-page budget drops. H = 0 keeps the exact untiered geometry.
        # chunked: `seq` is the slot's whole context, the answer included
        pages_per_slot = -(-(seq + (0 if chunk else max_new)) // page)
        host_pages = max(0, int(getattr(cfg, "kv_host_pages", 0) or 0))
        device_pages = 0
        if host_pages:
            device_pages = max(pages_per_slot,
                               slots * pages_per_slot - host_pages)
        prefetch_ahead = max(1, int(getattr(cfg, "kv_prefetch_ahead", 2)
                                    or 2))
        # a ring: the window behind a chunk's first query, the chunk, and a
        # page for where the two ends fall in theirs; never past the context
        ring_pages = min(-(-(window + chunk) // page) + 1, pages_per_slot) \
            if window_layers else 0
        kv_spec = cm.KVCacheSpec(
            layers=len(kv_layers) - len(window_layers),
            window_layers=len(window_layers), window_pages=ring_pages,
            heads=int(geometry.get("heads", 0)),
            head_dim=int(geometry.get("head_dim", 0)),
            latent_dim=int(geometry.get("latent_dim", 0)),
            index_dim=int(geometry.get("index_dim", 0)),
            index_layers=len(index_layers),
            slots=slots, pages_per_slot=pages_per_slot,
            page_size=page, itemsize=kv_itemsize,
            scale_itemsize=kv_scale_itemsize,
            host_pages=host_pages, device_pages=device_pages,
            state_bytes_per_slot=state_bytes)
        searched = (getattr(cfg, "search_budget", 0) > 0
                    and not cfg.only_data_parallel
                    and machine.num_devices > 1)
        if searched:
            pre_st = serving_optimize(pre_model, machine, "prefill", attn)
            dec_st = serving_optimize(dec_model, machine, "decode", attn,
                                      kv_spec, prefetch_ahead=prefetch_ahead)
        else:
            pre_st = data_parallel_strategy(pre_model, machine)
            dec_st = data_parallel_strategy(dec_model, machine)
        _overlay_parallel_ops(pre_model, pre_st)
        _overlay_parallel_ops(dec_model, dec_st)
        chunk_model = None
        if chunk:
            # the decode twin at ONE row of `chunk` tokens: it appends the
            # block to the slot's pages and attends over them, under the
            # decode strategy (layer names are preserved), as the verifier
            # does. One request a call: at the rates a chip sustains a
            # second row would be empty in most chunks, and an empty row is
            # half the call's positions (one row reads 92 % useful
            # positions: PERF.md, Findings PR 52)
            chunk_model, _ = clone_for_serving(model, "decode", 1,
                                               decode_seq=chunk)
            _overlay_parallel_ops(chunk_model, dec_st)
        ver_model = None
        draft_engine = None
        if draft is not None and spec_k > 0:
            dseq = int(draft.input_tensors[0].spec.shape[1])
            if dseq != seq:
                raise ValueError(
                    f"draft model seq {dseq} != target seq {seq}: the "
                    "scheduler prefills both from one prompt batch")
            # the verify program reuses the SEARCHED decode strategy
            # (op_shardings key on preserved layer names) — no extra
            # search, no extra strategy-cache entry
            ver_model, _ = clone_for_serving(model, "decode", slots,
                                             decode_seq=spec_k + 1)
            _overlay_parallel_ops(ver_model, dec_st)
            draft_engine = compile_serving(
                draft, max_batch_slots=slots, max_decode_len=max_new,
                kv_page_size=page, spec_tokens=0,
                kv_cache_dtype=kv_cache_dtype)
        log.info("compile_serving: mesh=%s slots=%d kv=%d pages x %d tok "
                 "(%.1f MiB/device, dtype %s)%s",
                 dict(machine.mesh_axes), slots,
                 kv_spec.pool_pages, page,
                 kv_spec.per_device_bytes(
                     attn_head_degree(dec_st, attn, machine)) / 2**20,
                 kv_dtype,
                 f" spec_tokens={spec_k}" if draft_engine else "")
        engine = ServingCompiled(model, machine, mesh, pre_model, dec_model,
                                 pre_st, dec_st, attn, kv_spec, max_new,
                                 kv_dtype=kv_dtype, kv_quantized=kv_quantized,
                                 verify_model=ver_model,
                                 spec_tokens=spec_k if draft_engine else 0,
                                 draft=draft_engine, recurrent=recurrent,
                                 index_layers=index_layers,
                                 window_layers=window_layers,
                                 chunk_model=chunk_model)
        # the in-place append's engagement: the pool as it lies at rest and
        # the bytes of the state leaves a decode step is told to donate
        compile_span.set(
            kv_pool_shape=list(
                next(iter(engine.kv.state[kv_layers[0]].values())).shape)
            if kv_layers else [],
            prefill_chunk=chunk,
            **({"kv_pool_pages_full": kv_spec.pool_pages,
                "kv_pool_bytes_full": kv_spec.layers * kv_spec.layer_bytes(),
                "kv_pool_pages_window": kv_spec.window_pool_pages,
                "kv_pool_bytes_window": kv_spec.window_bytes(),
                "window_ring_pages": ring_pages, "window": window,
                "kv_pool_bytes_one_extent": kv_spec.one_extent_bytes()}
               if window_layers else {}),
            state_in_place=engine.kv.writes_state_in_place,
            decode_state_donated_bytes=_tree_bytes(engine.kv.state))
        return engine


def _positionwise_head(prefill_model) -> Optional[Any]:
    """The prefill graph's last layer when it acts on every position alone
    (a Dense over the last axis of a `[slots, S, d]` tensor some layer
    made), else None."""
    last = prefill_model.layers[-1]
    if last.op_type is not OperatorType.LINEAR or len(last.inputs) != 1 \
            or callable(last.params.get("activation")):
        return None
    x = last.inputs[0]
    if x.owner is None or len(x.spec.shape) != 3:
        return None
    return last


class ServingCompiled:
    """The two jitted serving programs + the paged cache they share."""

    def __init__(self, model, machine: MachineSpec, mesh, prefill_model,
                 decode_model, prefill_strategy, decode_strategy,
                 attn_layers: List[str], kv_spec: "cm.KVCacheSpec",
                 max_decode_len: int, kv_dtype=None, kv_quantized: bool = False,
                 verify_model=None, spec_tokens: int = 0, draft=None,
                 recurrent: Optional[Dict[str, Dict[str, tuple]]] = None,
                 index_layers: Optional[List[str]] = None,
                 chunk_model=None,
                 window_layers: Optional[List[str]] = None):
        self.model = model
        self.cfg = model.config
        self.machine = machine
        self.mesh = mesh
        self.prefill_model = prefill_model
        self.decode_model = decode_model
        self.prefill_strategy = prefill_strategy
        self.decode_strategy = decode_strategy
        self.attn_layers = list(attn_layers)
        self.kv_spec = kv_spec
        self.max_decode_len = int(max_decode_len)
        self.slots = int(kv_spec.slots)
        self._watermarks = health.WatermarkTracker()
        self.kv_quantized = bool(kv_quantized)
        self.spec_tokens = int(spec_tokens)
        self.draft: Optional["ServingCompiled"] = draft
        self.verify_model = verify_model

        if kv_dtype is None:
            cdt = self.cfg.compute_dtype
            kv_dtype = jnp.dtype(cdt) \
                if cdt and cdt not in ("float32", "f32") else jnp.float32
        self.kv_dtype = jnp.dtype(kv_dtype)
        heads_axis = _wq_heads_axis(decode_strategy, self.attn_layers)
        self.kv = PagedKVCache(kv_spec, self.attn_layers, mesh,
                               heads_axis=heads_axis, dtype=self.kv_dtype,
                               quantized=self.kv_quantized,
                               recurrent=recurrent, index_layers=index_layers,
                               window_layers=window_layers)
        deg = 1
        if self.kv.heads_axis is not None:
            axes = (self.kv.heads_axis,) if isinstance(self.kv.heads_axis, str) \
                else tuple(self.kv.heads_axis)
            for a in axes:
                deg *= mesh.shape.get(a, 1)
        self.kv_shard_degree = deg

        pre_out = prefill_model.layers[-1].outputs[:1]
        dec_out = decode_model.layers[-1].outputs[:1]
        fwd_kw = dict(seq_length=self.cfg.seq_length or None,
                      compute_dtype=self.cfg.compute_dtype,
                      enable_fusion=self.cfg.enable_fusion,
                      collect_stats=True)
        pre_fwd = build_forward(prefill_model.layers,
                                prefill_model.input_tensors, pre_out, mesh,
                                prefill_strategy, **fwd_kw)
        dec_fwd = build_forward(decode_model.layers,
                                decode_model.input_tensors, dec_out, mesh,
                                decode_strategy, **fwd_kw)
        rng0 = jax.random.PRNGKey(0)  # deterministic-mode hard default

        def _prefill(params, inputs):
            outs, kv_state = pre_fwd(params, {}, inputs, False, rng0)
            return outs[0], kv_state

        # the program the scheduler runs: each slot's first token, taken on
        # the device. A position-wise last layer (a Dense over the last
        # axis, as the graph shows it) is applied to the gathered
        # `[slots, 1, d]` rows alone, so the `[slots, S, vocab]` logits are
        # never formed; any other last layer is gathered on its output.
        def split_head(graph, strategy, whole=None):
            """(body, head) forwards of `graph`: the head alone where its
            last layer is position-wise, else (the whole graph's, None)."""
            head = _positionwise_head(graph)
            if head is None:
                return whole or build_forward(
                    graph.layers, graph.input_tensors,
                    graph.layers[-1].outputs[:1], mesh, strategy,
                    **fwd_kw), None
            return (build_forward(graph.layers[:-1], graph.input_tensors,
                                  head.inputs, mesh, strategy, **fwd_kw),
                    build_forward([head], head.inputs,
                                  graph.layers[-1].outputs[:1], mesh,
                                  strategy, **fwd_kw))

        def last_row_tokens(params, hidden, last, head):
            """Each row's greedy token after position `last`."""
            rows = jnp.take_along_axis(hidden, last[:, None, None], axis=1,
                                       mode="clip")
            if head is not None:
                rows = head(params, {}, [rows], False, rng0)[0][0]
            return jnp.argmax(rows[:, 0, :], axis=-1).astype(jnp.int32)

        body_fwd, head_fwd = split_head(prefill_model, prefill_strategy,
                                        whole=pre_fwd)

        def _prefill_first_tokens(params, inputs, lengths, slot_state=None):
            last = jnp.maximum(lengths.astype(jnp.int32) - 1, 0)
            # with `slot_state` the wave writes its own slots: each recurrent
            # layer is handed its slot arrays and hands back what they hold
            # after the wave (LoweringCtx.hand_out_slot_state)
            outs, kv_state = body_fwd(params, slot_state or {}, inputs, False,
                                      rng0)
            tokens = last_row_tokens(params, outs[0], last, head_fwd)
            if slot_state is not None:
                kv_state.setdefault(STATS_KEY, {})["state_written_bytes"] = \
                    jnp.sum(lengths > 0).astype(jnp.float32) \
                    * float(kv_spec.state_bytes_per_slot)
            return tokens, kv_state

        def _decode(params, state, inputs):
            outs, ns = dec_fwd(params, state, inputs, False, rng0)
            # device-side sequence advance: every ACTIVE slot cached one
            # more token this step (inactive slots stay parked), so the
            # bounded dispatch-ahead loop never syncs to bump positions
            ns[POS_KEY] = state[POS_KEY] + state[ACTIVE_KEY].astype(
                state[POS_KEY].dtype)
            return outs[0], ns

        self._prefill_jit = jax.jit(_prefill)
        # where the cache says so (`writes_state_in_place`) the program is
        # handed the recurrent layers' slot arrays, donated, as a fourth
        # argument; else it takes three and lowers to what it lowered to
        self._prefill_first_tokens_jit = jax.jit(
            _prefill_first_tokens,
            donate_argnums=(3,) if self.kv.writes_state_in_place else ())
        # the state is donated (the step appends to the pools it was
        # handed), the params never (hot-swap: in-flight work holds them)
        self._decode_jit = jax.jit(_decode, donate_argnums=(1,))
        self._decode_fn = _decode
        # what each program's device time is made of, for whoever asks
        # (attribution.op_scopes): weak references here, the executable
        # at a program's first run, its HLO text only on demand
        self._programs = {
            "prefill": attribution.register_program(
                "serve/prefill", self._prefill_jit, prefill_model.layers),
            "prefill_first_tokens": attribution.register_program(
                "serve/prefill", self._prefill_first_tokens_jit,
                prefill_model.layers),
            "decode": attribution.register_program(
                "serve/decode", self._decode_jit, decode_model.layers)}
        # chunked prefill: the prompt program is a `[1, chunk]` block over
        # one slot's own pages (registered as this engine's serve/prefill)
        self.chunk_model = chunk_model
        self.chunk_tokens = 0
        self._chunk_jit = None
        if chunk_model is not None:
            self.chunk_tokens = int(
                chunk_model.input_tensors[0].spec.shape[1])
            c_body, c_head = split_head(chunk_model, decode_strategy)
            paged = list(self.attn_layers)
            # the layers a chunk starts from its slot's state
            carried = list(self.kv.recurrent)
            # whether a layer's cache attention is stated by position (a
            # window, or `window` 0: the whole context) and counts the keys
            # its real queries see
            by_position = any("window" in l.params
                              for l in chunk_model.layers)

            def _prefill_chunk(params, state, inputs, page_rows, context,
                               lengths, slots=None):
                # the block sees its slot's own pages and position; the
                # cache's table, positions and live set pass through as they
                # are (a prefilling slot is not live until its last chunk)
                view = {n: state[n] for n in paged}
                # and each recurrent layer's leaves of its slot: zeros for a
                # prompt's first chunk, whatever the slot held before
                for n in carried:
                    view[n] = {
                        key: rows_taken(leaf[slots],
                                        jnp.zeros_like(leaf[slots]),
                                        context > 0)
                        for key, leaf in state[n].items()}
                view[PAGE_TABLE_KEY] = page_rows
                if window_layers:   # the slot's two rows: its pages, its ring
                    view[PAGE_TABLE_KEY], view[WINDOW_TABLE_KEY] = jnp.split(
                        page_rows, [kv_spec.pages_per_slot], axis=1)
                if by_position:
                    # which of the block's positions hold a token
                    view[BLOCK_LENGTHS_KEY] = lengths
                view[POS_KEY] = context
                view[ACTIVE_KEY] = (lengths > 0).astype(
                    state[ACTIVE_KEY].dtype)
                outs, ns = c_body(params, view, inputs, False, rng0)
                tokens = last_row_tokens(
                    params, outs[0],
                    jnp.maximum(lengths.astype(jnp.int32) - 1, 0), c_head)
                new = dict(state)
                new.update({n: ns[n] for n in paged})
                # what the block left goes into the slot's rows, in place
                # (the state is donated); a row without a token keeps its own
                for n in carried:
                    new[n] = {
                        key: leaf.at[slots].set(rows_taken(
                            ns[n][key], leaf[slots], lengths > 0))
                        for key, leaf in state[n].items()}
                stats = dict(ns.get(STATS_KEY, {}))
                if carried:
                    stats["chunk_state_in"] = jnp.any(
                        (context > 0) & (lengths > 0)).astype(jnp.float32)
                if stats:
                    new[STATS_KEY] = stats
                return tokens, new

            self._chunk_jit = jax.jit(_prefill_chunk, donate_argnums=(1,))
            self._programs["prefill_chunk"] = attribution.register_program(
                "serve/prefill", self._chunk_jit, chunk_model.layers)
        self._verify_jit = None
        self._verify_fn = None
        self._spec_jit = None
        self._spec_src = None
        if verify_model is not None and self.spec_tokens > 0:
            ver_out = verify_model.layers[-1].outputs[:1]
            ver_fwd = build_forward(verify_model.layers,
                                    verify_model.input_tensors, ver_out, mesh,
                                    decode_strategy, **fwd_kw)
            ver_steps = self.spec_tokens + 1

            def _verify(params, state, inputs):
                outs, ns = ver_fwd(params, state, inputs, False, rng0)
                # the verify pass teacher-forces K+1 tokens, so active
                # slots cached K+1 more entries; the scheduler re-publishes
                # the COMMITTED extent (<= this) after acceptance
                ns[POS_KEY] = state[POS_KEY] + ver_steps * state[
                    ACTIVE_KEY].astype(state[POS_KEY].dtype)
                return outs[0], ns

            self._verify_jit = jax.jit(_verify, donate_argnums=(1,))
            self._verify_fn = _verify
        self.params: Optional[Dict[str, Any]] = None
        if tel.enabled():
            tel.event("serve/engine", cat="serve",
                      kv_dtype=str(self.kv_dtype),
                      kv_quantized=self.kv_quantized,
                      spec_tokens=self.spec_tokens)

        # SLO error budgets (ISSUE 15): terminal requests from every
        # scheduler driving this engine classify into one shared tracker,
        # so health_report()["serving"]["slo"] is the engine-lifetime view
        # the fleet router will poll
        self.slo = health.SLOTracker(
            health.parse_slo(getattr(self.cfg, "serve_slo", "") or ""))

        # hot-swap state (ISSUE 11): watch root + retained version trees
        self.swap_stats = health.SwapStats()
        self._watch_root: Optional[str] = None
        self._watch_poll_s = 0.25
        self._last_poll = 0.0
        self._retain = 2
        self._versions: "OrderedDict[Any, Dict[str, Any]]" = OrderedDict()
        self._pinned = False
        self._bad_snapshots: set = set()
        self._swap_policy = RetryPolicy.from_config(self.cfg)
        if getattr(self.cfg, "serve_watch_dir", ""):
            self.watch(self.cfg.serve_watch_dir)

    # ------------------------------------------------------------- weights
    def _weight_sharding(self, layer_name: str, wname: str, shape):
        pspec = self.decode_strategy.sharding_for(layer_name).weight_pspec(wname)
        if not constrainable(pspec, shape, self.mesh):
            pspec = PartitionSpec()
        return NamedSharding(self.mesh, pspec)

    def init(self, seed: Optional[int] = None):
        """Weights sharded-at-birth in the DECODE strategy's layout (the
        steady-state program; prefill's jit reshards on entry via GSPMD).
        Identical names/specs/topo order to the training graph mean this is
        bitwise-identical to CompiledModel.init of the same model. The
        span `serve/init` is the HOST's part: tracing, lowering, compile (or
        cache read) and dispatch of the init program, which is asynchronous:
        the device's time to fill the weights is waited for in warm-up."""
        seed = self.cfg.seed if seed is None else seed
        layers = topo_order(self.decode_model.layers)
        shardings = {
            layer.name: {w: self._weight_sharding(layer.name, w, s.shape)
                         for w, s in layer.weight_specs.items()}
            for layer in layers if layer.weight_specs}
        init_fn = build_init_fn(layers, self.model._initializer_overrides)
        with tel.span("serve/init", cat="compile") as sp:
            self.params = jax.jit(init_fn, out_shardings=shardings)(
                jax.random.PRNGKey(seed))
            sp.set(**weights_facts(self.params))
        self._watermarks.sample("serve_init", (self.params, self.kv.state))
        return self.params

    def _validate_incoming(self, params, source: str) -> None:
        """Structural check of an incoming params tree against the decode
        graph: layer-name sets and per-weight shapes must match. Raises
        `CheckpointMismatchError` listing the diffs — a silent zip over
        mismatched layers would serve garbage weights."""
        live = {l.name: l for l in topo_order(self.decode_model.layers)
                if l.weight_specs}
        diffs: List[str] = []
        only_in = sorted(set(params) - set(live))
        only_live = sorted(set(live) - set(params))
        if only_in:
            diffs.append(f"layers only in incoming tree: {only_in[:8]}")
        if only_live:
            diffs.append(f"layers only in serving graph: {only_live[:8]}")
        for name in sorted(set(live) & set(params)):
            lp, layer = params[name], live[name]
            for w, s in sorted(layer.weight_specs.items()):
                if w not in lp:
                    diffs.append(f"{name}: missing weight {w!r}")
                elif tuple(np.shape(lp[w])) != tuple(s.shape):
                    diffs.append(f"{name}.{w}: shape {tuple(np.shape(lp[w]))}"
                                 f" vs expected {tuple(s.shape)}")
        if diffs:
            raise CheckpointMismatchError(
                f"params tree from {source} does not match the serving "
                "graph:\n  " + "\n  ".join(diffs))

    def _place_params(self, params, source: str = "load_params"
                      ) -> Dict[str, Any]:
        """Validate + place a host/training params tree into the decode
        strategy's layout (the standby buffer of a hot-swap, or the live
        tree for `load_params`)."""
        self._validate_incoming(params, source)
        return {
            layer.name: {
                w: jax.device_put(jnp.asarray(params[layer.name][w]),
                                  self._weight_sharding(layer.name, w, s.shape))
                for w, s in layer.weight_specs.items()}
            for layer in topo_order(self.decode_model.layers)
            if layer.weight_specs}

    def load_params(self, params) -> Dict[str, Any]:
        """Adopt trained params (e.g. from CompiledModel.params), placed
        into the decode strategy's layout. Raises `CheckpointMismatchError`
        when the tree's layer names or weight shapes don't match the
        serving graph."""
        self.params = self._place_params(params)
        self._watermarks.sample("serve_load", (self.params, self.kv.state))
        return self.params

    # ------------------------------------------------------------ hot-swap
    @property
    def watching(self) -> bool:
        return bool(self._watch_root)

    @property
    def active_version(self) -> Optional[int]:
        """Training step of the live weights (None = init/load_params)."""
        return self.swap_stats.active_version

    def watch(self, root: str, poll_interval_s: float = 0.25,
              retain: int = 2, policy: Optional[RetryPolicy] = None
              ) -> "ServingCompiled":
        """Arm hot-swapping: poll `root` (a durable-checkpoint root) for
        newer committed snapshots at `poll_interval_s` granularity,
        retaining `retain` param trees in memory for rollback."""
        self._watch_root = os.path.abspath(root)
        self._watch_poll_s = float(poll_interval_s)
        self._retain = max(1, int(retain))
        if policy is not None:
            self._swap_policy = policy
        self._last_poll = 0.0
        return self

    def poll_swap(self, force: bool = False) -> bool:
        """Discover-and-swap: if the watch root holds a committed snapshot
        newer than the active version (and no rollback pin is set), load
        and activate it. Called by the scheduler between decode steps —
        never while a dispatched window is in flight (the serving programs
        donate their cache state, never `params`, so a step already
        dispatched keeps the tree it was given). Returns True iff the
        live params changed. A snapshot that fails validation or whose
        read escalates past the retry budget is rejected (counted +
        telemetry `error` event) and the engine keeps serving the current
        version — a bad checkpoint must never take serving down."""
        if not self._watch_root or self._pinned:
            return False
        now = time.monotonic()
        if not force and now - self._last_poll < self._watch_poll_s:
            return False
        self._last_poll = now
        snaps = committed_snapshots(self._watch_root)
        if not snaps:
            return False
        step, path, _man = snaps[-1]
        cur = self.swap_stats.active_version
        if (cur is not None and step <= cur) or path in self._bad_snapshots:
            return False
        try:
            self.hot_swap(path, step)
            return True
        except CheckpointMismatchError as e:
            self._bad_snapshots.add(path)
            self.swap_stats.record_rejected()
            tel.error("serve/swap_rejected", path=path, error=str(e)[:400])
            log.warning("hot-swap rejected %s: %s", path, e)
            return False
        except Exception as e:  # noqa: BLE001 — escalated read failure
            self.swap_stats.record_rejected()
            tel.error("serve/swap_failed", path=path, error=repr(e)[:400])
            log.warning("hot-swap failed for %s (will retry next poll): %s",
                        path, e)
            return False

    def hot_swap(self, path: str, step: Optional[int] = None,
                 rollback: bool = False) -> Dict[str, Any]:
        """Load the durable snapshot at `path` into a standby param tree
        (fingerprint-validated, `run_resilient` around the read so a
        transient IO fault costs a retry) and activate it with a pointer
        flip. In-flight dispatches keep their references to the previous
        tree — params are never donated; cache state always is — so
        nothing is dropped."""
        t0 = time.perf_counter()
        t0_us = tel.now_us() if tel.enabled() else 0

        def read():
            with open(os.path.join(path, "meta.json")) as f:
                meta = json.load(f)
            saved = (meta.get("fingerprint") or {}).get("graph")
            if saved is not None:
                self._validate_graph_fp(saved, path)
            import orbax.checkpoint as ocp
            tree = ocp.StandardCheckpointer().restore(
                os.path.join(path, "tree"))
            return meta, tree["params"]

        meta, raw = run_resilient("serve/param_swap", read,
                                  policy=self._swap_policy)
        placed = self._place_params(raw, source=path)
        if step is None:
            step = int(meta.get("iteration", -1))
        prev, prev_version = self.params, self.swap_stats.active_version
        self.params = placed  # THE swap: one pointer flip between steps
        if prev is not None and prev_version not in self._versions:
            self._versions[prev_version] = prev
        self._versions[step] = placed
        self._versions.move_to_end(step)
        while len(self._versions) > self._retain:
            oldest = next(iter(self._versions))
            if oldest == step:
                break
            del self._versions[oldest]
        lat = time.perf_counter() - t0
        self.swap_stats.record_swap(step, lat, rollback=rollback)
        if tel.enabled():
            tel.record("serve/param_swap", t0_us, cat="serve",
                       version=int(step), path=path, rollback=bool(rollback))
        self._watermarks.sample("serve_swap", (self.params, self.kv.state))
        log.info("hot-swap: version %s live in %.1f ms (%s)", step,
                 1e3 * lat, path)
        return placed

    def _validate_graph_fp(self, saved_graph: Dict[str, str],
                           path: str) -> None:
        live = _graph_fingerprint(self.decode_model)
        diffs: List[str] = []
        only_ck = sorted(set(saved_graph) - set(live))
        only_live = sorted(set(live) - set(saved_graph))
        changed = sorted(k for k in set(saved_graph) & set(live)
                         if saved_graph[k] != live[k])
        if only_ck:
            diffs.append(f"layers only in checkpoint: {only_ck[:8]}")
        if only_live:
            diffs.append(f"layers only in serving graph: {only_live[:8]}")
        if changed:
            diffs.append("layers with different weight schema "
                         f"(op/shape/dtype): {changed[:8]}")
        if diffs:
            raise CheckpointMismatchError(
                f"snapshot {path} does not match the serving graph:\n  "
                + "\n  ".join(diffs))

    def rollback(self, step: Any = "previous") -> Optional[int]:
        """Re-pin a retained version: flip the live params back to `step`
        (default: the most recently retained non-active version) and PIN —
        `poll_swap` stops auto-advancing until `unpin()`, so a bad new
        model can't immediately re-deploy itself. Falls back to reloading
        from the watch root when the version aged out of memory."""
        cur = self.swap_stats.active_version
        if step == "previous":
            candidates = [k for k in self._versions if k != cur]
            if not candidates:
                raise ValueError("rollback: no retained version to re-pin")
            step = candidates[-1]
        t0 = time.perf_counter()
        if step in self._versions:
            self.params = self._versions[step]
            self._versions.move_to_end(step)
            self.swap_stats.record_swap(step, time.perf_counter() - t0,
                                        rollback=True)
        else:
            on_disk = {s: p for s, p, _m in
                       committed_snapshots(self._watch_root or "")}
            if step not in on_disk:
                raise ValueError(f"rollback: version {step!r} not retained "
                                 "in memory or on disk")
            self.hot_swap(on_disk[step], step, rollback=True)
        self._pinned = True
        log.info("rollback: version %s re-pinned (auto-swap paused)", step)
        return step if isinstance(step, int) else None

    def unpin(self) -> None:
        """Resume auto-swapping after a rollback pin."""
        self._pinned = False
        self._last_poll = 0.0

    # ------------------------------------------------------------ programs
    def _run_prefill(self, jitted, *args):
        prog = self._programs["prefill" if jitted is self._prefill_jit
                              else "prefill_first_tokens"]
        if prog.compiled is None:
            prog.first_run(*args)
        if not tel.enabled():
            return jitted(*args)
        t0 = tel.now_us()
        out = jitted(*args)
        tel.record("serve/prefill", t0, cat="serve", slots=self.slots)
        return out

    def prefill(self, params, input_arrays):
        """Run the full-logits prefill program: returns (logits, kv_state)
        where kv_state maps each attention layer to its `[slots, S, h, d]`
        per-head K/V for `PagedKVCache.commit_prefill`. For callers that
        want rows; the scheduler serves with `prefill_first_tokens`."""
        return self._run_prefill(self._prefill_jit, params, list(input_arrays))

    def prefill_first_tokens(self, params, input_arrays, lengths):
        """The prefill the scheduler serves with: returns (tokens, kv_state)
        where tokens is `[slots]` int32, the greedy token after each slot's
        last real position (`lengths - 1`, row 0 for an empty slot), taken
        inside the program so no logits leave the device. `kv_state` is
        `prefill`'s: per layer that carries state, what `commit_prefill`
        takes, and under STATS_KEY the wave's counters where ops report
        any."""
        if not self.kv.writes_state_in_place:
            return self._run_prefill(self._prefill_first_tokens_jit, params,
                                     list(input_arrays), lengths)
        # the program writes the recurrent state into the slot arrays it is
        # handed (donated: dead after the call); the cache adopts them at
        # once and `commit_prefill` finds nothing fresh for those layers
        tokens, kv_state = self._run_prefill(
            self._prefill_first_tokens_jit, params, list(input_arrays),
            jnp.asarray(lengths), self.kv.slot_state())
        self.kv.state.update({n: kv_state.pop(n) for n in self.kv.recurrent})
        return tokens, kv_state

    def prefill_chunk(self, params, state, input_arrays, page_rows, context,
                      lengths, slots=None):
        """One chunk of one prompt, `chunk_tokens` tokens (every array has
        a leading axis of 1): the tokens sit at positions `context[0] ..` of
        the slot whose table row is `page_rows[0]` (`kv.prefill_row`),
        `lengths[0]` of them real. The block's K, V and indexer keys are
        appended to those pages and it attends over what they hold and over
        itself. Returns (tokens `[1]` int32: the greedy token after the last
        real position, which is the request's first token where the chunk
        is its prompt's last; the new cache state, the step's counters
        under STATS_KEY). `slots` `[1]` int: the slot itself, for a model
        with recurrent layers: each starts the block from that slot's state
        (from zeros where `context[0]` is 0, whatever the slot held) and
        leaves it what it is after the block's last real position. `state`
        is DONATED, as in `decode_step`."""
        if self._chunk_jit is None:
            raise RuntimeError("prefill_chunk: engine compiled without "
                               "--serve-prefill-chunk")
        args = (params, state, list(input_arrays),
                jnp.asarray(page_rows, jnp.int32),
                jnp.asarray(context, jnp.int32),
                jnp.asarray(lengths, jnp.int32))
        if self.kv.recurrent:
            # the program of a model without such layers takes no slot
            if slots is None:
                raise ValueError("prefill_chunk: a model with recurrent "
                                 "layers needs the chunk's slot (`slots`)")
            args += (jnp.asarray(slots, jnp.int32),)
        prog = self._programs["prefill_chunk"]
        if prog.compiled is None:
            prog.first_run(*args)
        t0 = tel.now_us() if tel.enabled() else None
        out = self._chunk_jit(*args)
        if t0 is not None:
            tel.record("serve/prefill", t0, cat="serve", slots=1,
                       chunk=self.chunk_tokens)
        return out

    def decode_step(self, params, state, input_arrays):
        """One single-token step over all slots: returns (logits
        `[slots, 1, vocab]`, new cache state with positions advanced).
        `state` is DONATED: the step appends this token's K/V to the pools
        it was handed, and the tree passed in is dead once this returns —
        use the one that comes back. `params` are not donated.
        Dispatch-only from the host's view — no sync, so the scheduler can
        keep a bounded number of steps in flight. Where ops report
        counters, the step's ride in the new state under STATS_KEY, as a
        prefill's do in its kv_state: whoever reads them pops them, so what
        goes into the next step has the shape of what came into this one."""
        t0 = tel.now_us() if tel.enabled() else None
        inputs = list(input_arrays)
        if self._programs["decode"].compiled is None:
            self._programs["decode"].first_run(params, state, inputs)
        logits, new_state = self._decode_jit(params, state, inputs)
        if t0 is not None:
            tel.record("serve/decode_step", t0, cat="serve")
        return logits, new_state

    def verify_step(self, params, state, input_arrays):
        """One speculative-verify pass: the `[slots, K+1]` decode-mode
        program teacher-forces the last committed token plus the K drafted
        tokens and returns logits `[slots, K+1, vocab]` — K+1 next-token
        distributions from ONE bandwidth-amortized weight stream. The
        cache caches all K+1 entries; the scheduler rolls positions back
        to the accepted extent afterwards. `state` is donated, as in
        `decode_step`."""
        if self._verify_jit is None:
            raise RuntimeError("verify_step: engine compiled without a "
                               "draft (pass draft=/--serve-draft-model and "
                               "spec_tokens>0)")
        if not tel.enabled():
            return self._verify_jit(params, state, list(input_arrays))
        t0 = tel.now_us()
        out = self._verify_jit(params, state, list(input_arrays))
        tel.record("serve/decode_step", t0, cat="serve",
                   verify=True, steps=self.spec_tokens + 1)
        return out

    def build_spec_program(self, step_inputs_fn):
        """Fuse one whole speculative round — the K chained greedy draft
        steps AND the batched verify pass — into ONE jitted dispatch:

            (params, draft_params, state, draft_state, last[slots,1])
                -> (t_pred[slots,K+1], ver_in[slots,K+1],
                    new_state, new_draft_state)

        `state` and `draft_state` are donated (both caches append in
        place); neither params tree is.

        Per-dispatch host overhead is what kills speculation on a fast
        decode path: run unfused, a round pays K+1 program launches to
        commit ~a*K+1 tokens, which can be SLOWER than plain decode's one
        launch per token. Fused, the round is one launch regardless of K —
        the draft chain's argmax feedback stays on device.

        `step_inputs_fn(tokens, state) -> [input_arrays]` must be
        jax-traceable (pure jnp on the token array and cache state, as
        `gpt2_step_inputs` is); a host-side fn raises at trace time and
        the scheduler falls back to the unfused round. The program is
        cached per step_inputs_fn identity."""
        if self.draft is None or self._verify_fn is None:
            raise RuntimeError("build_spec_program requires an engine "
                               "compiled with draft= and spec_tokens>0")
        if self._spec_jit is not None and self._spec_src is step_inputs_fn:
            return self._spec_jit
        K = self.spec_tokens
        draft_fn = self.draft._decode_fn
        verify_fn = self._verify_fn

        def _spec_round(params, dparams, state, dstate, last):
            cur = last
            drafts = []
            for _ in range(K):  # unrolled: K is small and fixed
                dlogits, dstate = draft_fn(dparams, dstate,
                                           step_inputs_fn(cur, dstate))
                cur = jnp.argmax(dlogits[:, -1, :],
                                 axis=-1).astype(jnp.int32)[:, None]
                drafts.append(cur)
            ver_in = jnp.concatenate([last] + drafts, axis=1)
            vlogits, state = verify_fn(params, state,
                                       step_inputs_fn(ver_in, state))
            t_pred = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)
            return t_pred, ver_in, state, dstate

        self._spec_jit = jax.jit(_spec_round, donate_argnums=(2, 3))
        self._spec_src = step_inputs_fn
        return self._spec_jit

    def spec_round_step(self, params, draft_params, state, draft_state,
                        last, step_inputs_fn):
        """Dispatch one fused speculative round (see build_spec_program)."""
        fn = self.build_spec_program(step_inputs_fn)
        if not tel.enabled():
            return fn(params, draft_params, state, draft_state, last)
        t0 = tel.now_us()
        out = fn(params, draft_params, state, draft_state, last)
        tel.record("serve/decode_step", t0, cat="serve",
                   spec_round=True, steps=self.spec_tokens + 1)
        return out

    # ---------------------------------------------------------- accounting
    def memory_stats(self) -> Dict[str, int]:
        """Predicted vs measured per-device residency, KV cache included —
        the serving face of CompiledModel.memory_stats()."""
        pred_params = 0
        for layer in self.decode_model.layers:
            sh = self.decode_strategy.op_shardings.get(layer.name)
            for w, spec in layer.weight_specs.items():
                dims = (sh.weights.get(w, []) if sh is not None else [])
                pred_params += cm.shard_bytes(spec, dims, self.machine)
        pred_kv = self.kv_spec.per_device_bytes(self.kv_shard_degree)

        def per_device_bytes(tree):
            if tree is None:
                return 0
            dev = self.mesh.devices.flat[0]
            total = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                shards = getattr(leaf, "addressable_shards", None)
                if shards is None:
                    total += int(getattr(leaf, "nbytes", 0))
                    continue
                total += sum(s.data.nbytes for s in shards if s.device == dev)
            return total

        return {
            "kv_shard_degree": int(self.kv_shard_degree),
            "predicted_kv_cache_bytes": int(pred_kv),
            "predicted_param_bytes": int(pred_params),
            "predicted_total_bytes": int(pred_kv + pred_params),
            "actual_param_bytes_per_device": per_device_bytes(self.params),
            "actual_kv_cache_bytes_per_device": self.kv.device_bytes(),
            # host cold tier: accounted SEPARATELY from the HBM figures
            # above (predicted==actual pins on the device numbers stay
            # exact; host bytes never compete for the HBM budget)
            "predicted_kv_host_bytes": int(self.kv_spec.host_bytes()),
            "actual_kv_host_bytes": int(self.kv.host_bytes()),
        }

    def health_report(self) -> Dict[str, Any]:
        """Predicted-vs-measured HBM watermark for the serving footprint
        (params + KV pools) through the training path's WatermarkTracker,
        plus the hot-swap ledger (active version, swap/rollback counts,
        swap latency quantiles) and the SLO scoreboard (error budget
        remaining + windowed burn rates per objective, ISSUE 15)."""
        serving = self.swap_stats.report()
        serving["slo"] = self.slo.report()
        # ROADMAP item 5: the multi-window burn policy's recommendation
        # (scale_out/scale_in/objective_flip/steady) rides the report
        serving["scaling"] = health.scaling_signal(serving["slo"])
        if self.kv.host_pages:
            serving["kv_tier"] = health.format_kv_tier(self.kv.tier_stats())
        return {"watermarks":
                self._watermarks.report(
                    self.memory_stats()["predicted_total_bytes"]),
                "serving": serving}

    def op_attribution(self, kind: str = "both",
                       step_time_s: Optional[float] = None,
                       prefill_step_time_s: Optional[float] = None,
                       print_table: bool = False, top: int = 0,
                       profile_dir: Optional[str] = None
                       ) -> Dict[str, Any]:
        """Serving-regime per-op attribution (ISSUE 14 satellite): the
        serving face of CompiledModel.op_attribution. One report per
        program (prefill / decode), each row featurized against the
        placement that actually compiled and priced by the SAME serving
        cost functions the search ranked with, so the op/attr events the
        telemetry sink collects show the bandwidth-bound seq=1 decode
        regime that training fits never exercise. step_time_s normalizes
        decode rows (the scheduler passes its median per-token wall),
        prefill_step_time_s the prefill rows (the shed estimator's EMA). With
        `profile_dir` (a `jax.profiler.trace` of a serving run of this
        engine) and a step time, a program's rows are measured from the
        profile: its device events joined by instruction name with the
        program's own compiled HLO (`source == "trace"`); else each op is
        re-executed alone."""
        from flexflow_tpu.search.candidates import compiled_candidate
        from flexflow_tpu.serving.program import (_decode_cost_fn,
                                                  _prefill_cost_fn)

        programs = []
        if kind in ("both", "prefill"):
            programs.append(("serve_prefill", self.prefill_model,
                             self.prefill_strategy,
                             _prefill_cost_fn(self.machine),
                             prefill_step_time_s,
                             [self._programs["prefill"],
                              self._programs["prefill_first_tokens"]]))
        if kind in ("both", "decode"):
            programs.append(("serve_decode", self.decode_model,
                             self.decode_strategy,
                             _decode_cost_fn(self.machine,
                                             self.kv_spec.layer_bytes()),
                             step_time_s, [self._programs["decode"]]))
        reports: Dict[str, Any] = {}
        for tag, smodel, strategy, cost, t_step, compiled in programs:
            batch_sizes = {t.spec.shape[0] for t in smodel.input_tensors
                           if t.spec.ndim > 0}
            items = []
            for layer in topo_order(smodel.layers):
                cand = compiled_candidate(layer, strategy, self.machine,
                                          batch_sizes)
                if cand.passthrough:
                    continue
                try:
                    predicted = float(cost(layer, cand))
                except Exception:
                    predicted = None
                items.append({"layer": layer, "cand": cand,
                              "machine": self.machine,
                              "predicted_s": predicted, "stage": None})
            report = attribution.build_report(
                items, step_time_s=t_step, mult=1,
                source="auto" if profile_dir else "measure",
                profile_dir=profile_dir, programs=compiled,
                inference=True, tag=tag)
            if print_table:
                print(f"[{tag}]")
                for line in attribution.format_report(report, top=top):
                    print(line)
            reports[tag] = report
        return reports
