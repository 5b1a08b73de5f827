"""Paged, sharded KV cache for the decode program.

Layout (per attention layer): one K pool and one V pool of shape
`[pool_pages, page_size, heads * head_dim]` (heads-major in the merged
axis), where `pool_pages = slots * pages_per_slot + 1` — page 0 is a
reserved SCRATCH page that inactive slots (and any out-of-range write) land
in, so every decode step is a fixed-shape scatter/gather with no branches.
Heads and head_dim are ONE axis at rest because of the chip's default
layout: with a minor dimension under 128 (GPT-2's head_dim 64) a
`[pages, page, heads, head_dim]` array is tiled with the page index in the
lanes, scatter and gather want it row-major, and every step then relaid
every pool there and back (96 whole-pool copies, 23 of a 36 ms step). A
merged axis is at least 128 wide for every model here, row-major is its
default, and nothing is relaid. Writers merge the token rows to
`[.., heads * head_dim]` before the scatter; the decode attention reads the
gathered pages as they lie (splitting them would relay a pool's worth
again: it lays the query rows over the merged axis instead), the int8
kernel splits what it gathers. The pools are sharded over the merged axis
along the model axis the decode strategy chose for the attention weights'
heads (a shard holds whole heads; q/k/v projections write their head shard,
attention reads it — no resharding anywhere in the cache path, the
layout-derivation requirement of ISSUE 10).

Ownership: every program that writes the pools — the decode, verify and
speculative-round steps, `commit_prefill` — DONATES the cache state it is
handed and appends in place. The tree handed in is dead after the call;
use what comes back. `self.state` is therefore always the newest tree
(`adopt` is a pointer set, and the scheduler adopts after every dispatch):
nothing may keep an older one. Parameters are never donated.

Paging: a per-slot page table `[slots, pages_per_slot]` of int32 page ids
maps token position t to `table[slot, t // page_size]` at offset
`t % page_size`. Allocation assigns page ids from a host free list on
admission (only as many pages as the request's prompt + decode budget
needs — unused tail entries stay pointed at scratch) and returns them on
eviction; the device-side table is refreshed by a tiny replicated
device_put at scheduler sync points. Freed pages still hold stale K/V but
are never attended: the per-slot position mask only exposes positions
written by the CURRENT occupant.

The pools + table + per-slot position/active vectors travel through the
decode program as lowering state (`compile.build_forward`'s state →
new_state channel): `state[layer_name] = {"k", "v"}`,
`state["serve/page_table"]`, `state["serve/pos"]`, `state["serve/active"]`.

A LATENT cache (multi-head latent attention, `spec.latent_dim` set) is the
same paging with other rows: a layer has ONE pool `[pool_pages, page_size,
latent_dim]`, `state[layer_name] = {"latent": ...}`, whose row is what a
token leaves behind for later ones (its K/V latent and the shared rotary
key: 576 values for DeepSeek-V3's widths, not 64 heads x 384). No heads
axis, so nothing to shard the pool over (it is replicated), and no V pool.
At rest the row is one axis as a K/V row is, padded with zeros to whole
lanes (576 -> 640, `KVCacheSpec.row_widths`): 576 is no multiple of 128, and
the chip's default layout of a `[pages, page, 576]` array puts the page
index in the lanes, so that every step relaid every pool for its scatter
(12 whole-pool copies a step in the described chip's compile), the case
above; splitting it 512 + 64 would leave a 64-wide pool with the same
fault. 11 % of a pool that is a hundredth of the weights buys row-major
pools that scatter, gather and the in-place append find as they find K/V
rows. Writers pad the rows they hand in (`pad_row`); the decode attention
reads the gathered rows as they lie (its query side carries zeros there).
What it does not support yet raises NotImplementedError: a quantized pool,
the host tier, park/spill, the hand-off and speculative roll-back.

A sparse-attention indexer's key (`spec.index_dim` set) is a third kind of
row beside K and V: each INDEXER layer (ops/sparse_attention_ops.py, a layer
of its own ahead of the attention it selects for) has one pool `[pool_pages,
page_size, index_row_width]`, `state[layer_name] = {"ik": ...}`, under the
same page table: a token's K, V and indexer key lie at the same page and
offset of their layers' pools, are written by the same block and freed by
the same eviction, and the position mask that hides a freed page's stale K/V
hides its stale key. 64 values lie in 128 lanes (the latent's reason). The
host tier, park/spill, the hand-off and a quantized pool raise
NotImplementedError beside it.

Two EXTENTS of K and V (`spec.window_layers` set): the layers of windowed
attention (a query sees its last `window` keys) keep pools of their own, each
a RING of `spec.window_pages` pages a slot (`ceil((window + chunk) / page) +
1`: the window behind a prefill chunk's first query and the chunk itself),
`[slots * window_pages + 1, page_size, heads * head_dim]` under a second table
`state["serve/window_table"]` `[slots, window_pages]`: page `n` of a slot's
context lies at entry `n % window_pages`, so a long context laps the ring and
a position overwrites the one a ring's length behind it, which no query sees
any more. A slot owns its ring for good: nothing is allocated or freed, the
free list and admission count the full layers' pages alone, and the bounds by
position (`first <= s <= t`, ops/attention_ops.py) hide what a former occupant
or a lapped position left. The device's row stays at scratch while the slot is
not live, as the page table's does, for the same reason. The host tier,
park/spill, the hand-off, speculative roll-back and a quantized pool raise
NotImplementedError beside it.

A prompt longer than one program's window is prefilled in CHUNKS over the
slot's own pages (serving/engine.py: `prefill_chunk`; scheduler.py). Such a
slot is admitted `prefilling`: it owns its pages on the host, but the device's
table row stays at the scratch page and the slot inactive, so that a decode
step between two chunks writes nothing into them; the chunk program is handed
the slot's real row (`prefill_row`), and `activate` publishes it with the
prompt's length once the last chunk is in. Beside the row it is told the slot
itself: a recurrent layer's chunk starts from that slot's rows of the state
arrays below (zeros for a prompt's first chunk, whatever a former occupant
left there) and writes what the chunk leaves back into them, in place.

Recurrent layers (a state-space mixer) keep the other kind of per-request
state in the same manager: fixed-size arrays per slot, `state[layer_name] =
{leaf: [slots, ...]}` (an SSM state and a conv tail), never paged.
`commit_prefill` writes them for the slots a wave prefilled (`lengths > 0`)
and leaves every other slot's untouched; the decode program advances them
for the slots its inputs name as live. What they do not support yet raises
NotImplementedError: the host tier, park/spill and the hand-off, which
would all have to move this state with the pages.

A model NONE of whose layers pages anything (every layer recurrent) has no
pools and no page accounting: `state_kinds` is "recurrent", a request needs
0 pages, and admission is by free slots alone.

A wave's recurrent state reaches its slots one of two ways, by the state's
size (`writes_state_in_place`): the commit program `_commit_state` takes the
prefill program's fresh `[slots, ...]` tree, gathers the old rows, selects
and scatters (three whole copies live at once: fine for a state that is a
few percent of the device), or, from IN_PLACE_STATE_BYTES up, the prefill
program itself is handed the slot arrays DONATED and writes each layer's
rows of the slots the wave prefilled into them (serving/engine.py), so that
no second whole copy exists; `commit_prefill` then finds no fresh state and
moves nothing.

Host cold tier (--kv-host-pages > 0): causal decode streams a slot's whole
committed working set every step, so pages cannot go cold while their slot
decodes — the tier works at SLOT granularity. `spill` parks an active slot:
its pages' K/V move to pinned host buffers (`jax.device_get`), the device
pages return to the free list, and the slot deactivates with its position
preserved. `prefetch` issues the host→HBM copy for a parked slot (async
`jax.device_put` + pool scatter — dispatch returns immediately, the copy
rides the dataflow edge into the next decode step, never a silent block);
`join` reactivates the slot and classifies the rejoin as a prefetch hit
(issued ≥ prefetch-ahead steps early) or a counted stall. Host pages come
from their own free list, so `admit`/`evict` capacity accounting spans
both tiers.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from flexflow_tpu import attribution
# the windowed layers' rings: the key the layers' lowering reads them under
from flexflow_tpu.ops.attention_ops import WINDOW_TABLE_KEY  # noqa: F401
# the page format (the ops' lowering reads the same definitions)
from flexflow_tpu.ops.pages import (ACTIVE_KEY, PAGE_TABLE_KEY,  # noqa: F401
                                    POS_KEY, append_slots, kv_dequantize,
                                    kv_quantize, merge_heads, pad_row)
from flexflow_tpu.search.cost_model import KVCacheSpec

# a wave's fresh recurrent state (slots x the state a slot, every layer) at
# or above this is written in place by the prefill program; under it the
# commit program writes it (the module docstring says what each costs). A
# sixteenth of a v5e's memory: granite's, Nemotron's and Ling's states are
# 0.2-0.6 GB a wave, a power-retention model's 3.3
IN_PLACE_STATE_BYTES = 1 << 30


class KVPoolExhausted(Exception):
    """`admit` could not allocate the requested pages: the free list is
    shorter than the request's prompt + decode budget. Deliberately NOT a
    RuntimeError — pool exhaustion is backpressure, not a transient fault,
    so `run_resilient`'s retry filter must let it surface immediately to
    the scheduler's shed-or-queue path instead of burning backoff sleeps
    on a condition only an eviction can clear."""

    def __init__(self, slot: int, need: int, have: int):
        super().__init__(
            f"KV pool exhausted admitting slot {slot}: need {need} pages, "
            f"{have} free")
        self.slot = slot
        self.need = need
        self.have = have


@functools.partial(jax.jit, donate_argnums=(0,))
def _commit_prefill(cache_state, kv_state, slot_ids, lengths):
    """Scatter the prefill program's rows (per layer `{leaf: [Bp, S, h, d]}`
    per-head K/V from kv_out, or `[Bp, S, width]` rows as the pool holds
    them: a latent) into the pools of the slots in `slot_ids`, in place:
    `cache_state` is donated. Positions >= lengths[r] (right padding) and
    positions past the slot's allocated pages are routed to the scratch
    page."""
    new = dict(cache_state)
    pt = cache_state[PAGE_TABLE_KEY]
    for name, kv in kv_state.items():
        pools = cache_state[name]
        first = next(iter(kv.values()))
        page = next(iter(pools.values())).shape[1]
        s = first.shape[1]
        pages = pt[slot_ids]                      # [Bp, pages_per_slot]
        t = jnp.arange(s)
        pg = t // page                            # [S]
        in_range = pg < pages.shape[1]
        pageix = jnp.where(in_range[None, :],
                           pages[:, jnp.minimum(pg, pages.shape[1] - 1)], 0)
        valid = t[None, :] < lengths[:, None]
        pageix = jnp.where(valid, pageix, 0)      # padding -> scratch
        off = jnp.broadcast_to(t % page, pageix.shape)
        if "k_scale" in pools:
            # quantized pools: scatter int8 values + per-(entry, head) scales
            qk, ks = kv_quantize(kv["k"])
            qv, vs = kv_quantize(kv["v"])
            new[name] = {
                "k": pools["k"].at[pageix, off].set(merge_heads(qk)),
                "v": pools["v"].at[pageix, off].set(merge_heads(qv)),
                "k_scale": pools["k_scale"].at[pageix, off].set(ks),
                "v_scale": pools["v_scale"].at[pageix, off].set(vs),
            }
        else:
            new[name] = {
                leaf: pools[leaf].at[pageix, off].set(
                    (merge_heads(rows) if rows.ndim == 4
                     else pad_row(rows, pools[leaf].shape[-1]))
                    .astype(pools[leaf].dtype))
                for leaf, rows in kv.items()}
    return new


@functools.partial(jax.jit, donate_argnums=(0,))
def _commit_state(old_state, new_state, slot_ids, lengths):
    """Write the prefill program's recurrent state (`[Bp, ...]` per leaf)
    into the per-slot arrays of the slots in `slot_ids` that the wave
    prefilled (`lengths > 0`), in place (`old_state` is donated); a slot
    that sat the wave out keeps what it had."""
    took = lengths > 0
    out = {}
    for name, leaves in new_state.items():
        out[name] = {}
        for key, fresh in leaves.items():
            old = old_state[name][key]
            sel = took.reshape((-1,) + (1,) * (fresh.ndim - 1))
            out[name][key] = old.at[slot_ids].set(
                jnp.where(sel, fresh.astype(old.dtype), old[slot_ids]))
    return out


def _tree_bytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))


class PagedKVCache:
    """Device-resident paged KV pools + host-side page accounting, and the
    per-slot state of recurrent layers beside them."""

    def __init__(self, spec: KVCacheSpec, attn_layers: List[str],
                 mesh: Optional[Mesh] = None, heads_axis=None,
                 dtype=jnp.float32, quantized: bool = False,
                 recurrent: Optional[Dict[str, Dict[str, tuple]]] = None,
                 index_layers: Optional[List[str]] = None,
                 window_layers: Optional[List[str]] = None):
        self.spec = spec
        # the commit programs as this cache runs them (attribution.op_scopes
        # "serve/commit"): they hold no graph layer, so their device time
        # is a wave's `other`: found, because they are registered
        self._commit_kv = attribution.register_program(
            "serve/commit", _commit_prefill, (), owner=self)
        self._commit_state = attribution.register_program(
            "serve/commit", _commit_state, (), owner=self)
        # every layer that pages: attention's K/V (or latent) pools and, among
        # them in graph order, the indexer layers' key pools
        self.attn_layers = list(attn_layers)
        self.index_layers = list(index_layers or [])
        # those of them that keep a ring of the window's pages a slot
        self.window_layers = list(window_layers or [])
        # {layer: {leaf: (per-slot shape, dtype)}} (program.recurrent_layers)
        # (compile_serving refuses a host tier beside them)
        self.recurrent = dict(recurrent or {})
        self.mesh = mesh
        self.heads_axis = None
        self.quantized = bool(quantized)
        pool_pspec = PartitionSpec()
        if mesh is not None and heads_axis is not None:
            axes = (heads_axis,) if isinstance(heads_axis, str) \
                else tuple(heads_axis)
            deg = 1
            for a in axes:
                deg *= mesh.shape.get(a, 1)
            if all(a in mesh.shape for a in axes) and spec.heads \
                    and spec.heads % deg == 0:
                self.heads_axis = heads_axis
                # a shard of the merged axis holds whole heads, so the
                # pools and the scales' heads dim split alike
                pool_pspec = PartitionSpec(None, None, heads_axis)
        self._pool_sharding = (NamedSharding(mesh, pool_pspec)
                               if mesh is not None else None)
        self._repl = (NamedSharding(mesh, PartitionSpec())
                      if mesh is not None else None)
        if spec.latent_dim and self.quantized:
            raise NotImplementedError(
                "a quantized (int8) cache of paged_latent state: the "
                "per-head scales have no heads to belong to")
        if self.window_layers and (self.quantized or spec.host_pages):
            raise NotImplementedError(
                f"{len(self.window_layers)} layers keep a window's ring of "
                "pages, which does not support a quantized (int8) pool or "
                "the host tier yet")
        shape = (spec.pool_pages, spec.page_size)

        def pool(width, shape=shape):
            z = jnp.zeros(shape + (width,),
                          jnp.int8 if self.quantized else dtype)
            return (jax.device_put(z, self._pool_sharding)
                    if self._pool_sharding is not None else z)

        def scales():
            # per-(page entry, head) f32 scales, sharded like the pools'
            # heads dim so the quantized cache needs no resharding either
            z = jnp.zeros(shape + (spec.heads,), jnp.float32)
            return (jax.device_put(z, self._pool_sharding)
                    if self._pool_sharding is not None else z)

        def layer_state(name):
            if name in self.index_layers:
                return {"ik": pool(spec.index_row_width())}
            if name in self.window_layers:
                return {leaf: pool(width, (spec.window_pool_pages,
                                           spec.page_size))
                        for leaf, width in spec.row_widths().items()}
            st = {leaf: pool(width)
                  for leaf, width in spec.row_widths().items()}
            if self.quantized:
                st["k_scale"] = scales()
                st["v_scale"] = scales()
            return st

        self.state: Dict = {n: layer_state(n) for n in self.attn_layers}
        for n, leaves in self.recurrent.items():
            self.state[n] = {
                key: (jax.device_put(z, self._repl) if self._repl is not None
                      else z)
                for key, (shape, dt) in leaves.items()
                for z in [jnp.zeros((spec.slots,) + tuple(shape), dt)]}
        # host mirrors (authoritative at scheduler sync points)
        self._table = np.zeros((spec.slots, spec.pages_per_slot), np.int32)
        # the rings: slot i's is pages 1 + i * ring .. of the windowed pools,
        # for good; the device sees the row while the slot is live
        self._rings = 1 + np.arange(
            spec.slots * spec.window_pages, dtype=np.int32).reshape(
                spec.slots, spec.window_pages)
        self._window_table = np.zeros_like(self._rings)
        self._pos = np.zeros((spec.slots,), np.int32)
        self._active = np.zeros((spec.slots,), np.int32)
        self.free_pages: List[int] = list(range(1, spec.pool_pages))
        self._slot_pages: Dict[int, List[int]] = {}
        # slots whose prompt is being prefilled in chunks -> their table row,
        # which the device does not see until `activate`
        self._prefilling: Dict[int, np.ndarray] = {}
        # host cold tier: per-layer pinned buffers shaped like the pools
        # minus the page dim ([host_pages, page_size, heads * head_dim] for
        # values, [host_pages, page_size, heads] for quantized scales)
        self.host_pages = int(spec.host_pages)
        self._host: Dict[str, Dict[str, np.ndarray]] = {}
        if self.host_pages:
            for n in self.attn_layers:
                self._host[n] = {
                    key: np.zeros((self.host_pages,) + tuple(leaf.shape[1:]),
                                  leaf.dtype)
                    for key, leaf in self.state[n].items()}
        self.free_host_pages: List[int] = list(range(self.host_pages))
        self._cold: Dict[int, List[int]] = {}   # parked slot -> host page ids
        self._inflight: Dict[int, int] = {}     # slot -> prefetch issue step
        self.tier_counters: Dict[str, int] = {
            "kv_spills": 0, "kv_refills": 0, "kv_prefetch_hits": 0,
            "kv_prefetch_stalls": 0, "kv_spilled_bytes": 0,
            "kv_refilled_bytes": 0, "kv_handoffs": 0, "kv_handoff_bytes": 0}
        self._push_tables()

    # ------------------------------------------------------------ host ops
    def _put_repl(self, arr):
        # a COPY of the host mirror: on the CPU backend `jnp.asarray` may
        # alias an aligned numpy buffer, the serving programs donate the
        # state they are handed, and a donated alias of `_pos` is then
        # advanced in place by the program AND by `sync_after`
        x = jnp.array(arr)
        return jax.device_put(x, self._repl) if self._repl is not None else x

    def _push_tables(self) -> None:
        self.state[PAGE_TABLE_KEY] = self._put_repl(self._table)
        if self.window_layers:
            self.state[WINDOW_TABLE_KEY] = self._put_repl(self._window_table)
        self.state[POS_KEY] = self._put_repl(self._pos)
        self.state[ACTIVE_KEY] = self._put_repl(self._active)

    def free_slots(self) -> List[int]:
        # parked (cold/inflight) slots are inactive on device but occupied:
        # their KV lives in the host tier under the same slot id
        return [i for i in range(self.spec.slots)
                if not self._active[i] and i not in self._cold
                and i not in self._prefilling]

    def pages_needed(self, total_tokens: int) -> int:
        # nothing pages, or every layer that does keeps a ring its slot owns:
        # a slot is all it takes
        if len(self.attn_layers) == len(self.window_layers):
            return 0
        cap = min(int(total_tokens), self.spec.padded_len)
        return -(-cap // self.spec.page_size)

    def can_admit(self, total_tokens: int) -> bool:
        return len(self.free_pages) >= self.pages_needed(total_tokens)

    def capacity_pages(self) -> int:
        """Total data pages across BOTH tiers — the figure `prompt_too_long`
        and admission shedding must compare against (ISSUE 16: capacity
        spans HBM + host, not HBM-only)."""
        return (self.spec.pool_pages - 1) + self.host_pages

    def total_free_pages(self) -> int:
        return len(self.free_pages) + len(self.free_host_pages)

    def admit(self, slot: int, prompt_len: int, total_tokens: int,
              prefilling: bool = False) -> bool:
        """Assign pages for a sequence that will hold up to `total_tokens`
        positions (prompt + decode budget + dispatch-ahead headroom); the
        slot's position starts at `prompt_len` (the index the first decode
        step writes). `prefilling`: the prompt goes in by chunks; the slot
        owns its pages but stays inactive, its device row at scratch, until
        `activate`. Raises `KVPoolExhausted` when the free list is short
        — the scheduler's shed-or-queue path decides whether the request
        waits (backpressure) or is shed, instead of a bare free-list
        IndexError mid-drain."""
        if self._active[slot] or slot in self._cold \
                or slot in self._prefilling:
            raise ValueError(f"slot {slot} is occupied")
        need = self.pages_needed(total_tokens)
        if len(self.free_pages) < need:
            raise KVPoolExhausted(slot, need, len(self.free_pages))
        pages = [self.free_pages.pop() for _ in range(need)]
        self._slot_pages[slot] = pages
        row = np.zeros(self.spec.pages_per_slot, np.int32)
        row[:need] = pages
        if prefilling:
            self._prefilling[slot] = row
            return True
        self._table[slot] = row
        self._window_table[slot] = self._rings[slot]
        self._pos[slot] = prompt_len
        self._active[slot] = 1
        return True

    def prefill_row(self, slot: int) -> np.ndarray:
        """The table row `[pages_per_slot]` of a slot admitted `prefilling`,
        for the chunk program; with windowed layers its two rows in one,
        `[pages_per_slot + window_pages]`: the pages, then the ring."""
        return np.concatenate([self._prefilling[slot], self._rings[slot]])

    def activate(self, slot: int, prompt_len: int) -> None:
        """The last chunk of a `prefilling` slot's prompt is in its pages:
        the slot joins the decode steps at position `prompt_len` (the
        caller pushes)."""
        self._table[slot] = self._prefilling.pop(slot)
        self._window_table[slot] = self._rings[slot]
        self._pos[slot] = prompt_len
        self._active[slot] = 1

    def evict(self, slot: int) -> None:
        """Return the slot's pages to the free list(s); stale pool contents
        are never attended (position mask) and get overwritten on reuse.
        A parked slot's pages live in the host tier — those return to the
        host free list instead."""
        self.free_pages.extend(self._slot_pages.pop(slot, []))
        self.free_host_pages.extend(self._cold.pop(slot, []))
        self._inflight.pop(slot, None)
        self._prefilling.pop(slot, None)
        self._table[slot] = 0
        self._window_table[slot] = 0
        self._pos[slot] = 0
        self._active[slot] = 0

    def sync_after(self, decode_steps: int,
                   advances: Optional[np.ndarray] = None) -> None:
        """Host mirror of the device-side position increments: each decode
        step advanced every active slot by one. Called at scheduler sync
        points BEFORE admissions/evictions mutate the mirrors. `advances`
        (per-slot committed step counts) masks finished slots: a request
        that hit EOS mid-window only advances to its finish position, so
        tokens speculatively decoded past the finish line never accrue to
        its committed KV extent."""
        if advances is not None:
            self._pos += np.asarray(advances, np.int32) * self._active
        else:
            self._pos += self._active * int(decode_steps)

    def push(self) -> None:
        """Publish the host mirrors to the device state (after a batch of
        admissions/evictions)."""
        self._push_tables()

    # ------------------------------------------------------- host tier ops
    def parked_slots(self) -> List[int]:
        """Slots whose KV sits in the host tier with no prefetch in flight
        — the scheduler's rotation candidates."""
        return [s for s in self._cold if s not in self._inflight]

    @property
    def state_kinds(self) -> str:
        """The kinds of per-request state this cache holds, as the ops'
        `state_kind` names them (what the cache's spans say they moved)."""
        kinds = [] if not self.attn_layers else \
            ["paged_latent" if self.spec.latent_dim else "paged_kv"]
        return "+".join(kinds + (["paged_kv_ring"] if self.window_layers else [])
                        + (["paged_index"] if self.index_layers else [])
                        + (["recurrent"] if self.recurrent else []))

    @property
    def writes_state_in_place(self) -> bool:
        """Whether the prefill program writes the recurrent state into the
        slot arrays itself (IN_PLACE_STATE_BYTES)."""
        return self.spec.slots * self.spec.state_bytes_per_slot \
            >= IN_PLACE_STATE_BYTES

    def slot_state(self) -> Dict:
        """The recurrent layers' slot arrays, `{layer: {leaf: [slots,
        ...]}}`: what a program that writes them is handed (donated: what
        it returns takes their place in `state`)."""
        return {n: self.state[n] for n in self.recurrent}

    def _kv_pages_only(self, what: str) -> None:
        if self.spec.latent_dim:
            raise NotImplementedError(
                f"{what}: the cache holds paged_latent state "
                f"({len(self.attn_layers)} layers), which this path does "
                "not move yet")
        if self.window_layers:
            raise NotImplementedError(
                f"{what}: the cache holds a window's ring of pages "
                f"({len(self.window_layers)} layers), which this path does "
                "not move yet")
        if self.index_layers:
            raise NotImplementedError(
                f"{what}: the cache holds an indexer's key beside K and V "
                f"({len(self.index_layers)} layers), which this path does "
                "not move yet")
        if self.recurrent:
            raise NotImplementedError(
                f"{what}: a model with recurrent layers "
                f"({sorted(self.recurrent)[0]}, ...) keeps per-slot state "
                "that this path does not move")

    def can_spill(self, slot: int) -> bool:
        return bool(self.host_pages) and bool(self._active[slot]) and \
            len(self.free_host_pages) >= len(self._slot_pages.get(slot, []))

    def spill(self, slot: int, decode_step: int) -> None:
        """Park an active slot: gather its pages from every layer's pools
        to the host buffers (one `jax.device_get` per leaf), return the
        device pages, and deactivate the slot keeping its position. The
        caller (scheduler) batches `push()` after a rotation round."""
        from flexflow_tpu import telemetry as tel
        self._kv_pages_only("spill")
        if not self.can_spill(slot):
            raise ValueError(f"cannot spill slot {slot}")
        pages = self._slot_pages.pop(slot)
        host_ids = [self.free_host_pages.pop() for _ in pages]
        idx = jnp.asarray(np.asarray(pages, np.int32))
        with tel.span("serve/kv_spill", cat="serve", slot=int(slot),
                      pages=len(pages)):
            for n in self.attn_layers:
                for key, leaf in self.state[n].items():
                    rows = jax.device_get(leaf[idx])
                    self._host[n][key][host_ids] = rows
        self.free_pages.extend(pages)
        self._cold[slot] = host_ids
        self._table[slot] = 0
        self._active[slot] = 0
        moved = self.spec.layers * len(pages) * self.spec.page_bytes()
        self.tier_counters["kv_spills"] += 1
        self.tier_counters["kv_spilled_bytes"] += moved

    def prefetch(self, slot: int, decode_step: int) -> bool:
        """Issue the host→HBM refill for a parked slot: allocate device
        pages, dispatch the async copy + pool scatter (jax returns before
        the transfer lands — the decode step that first reads these pages
        waits on the dataflow edge, never on a host sync), and restore the
        slot's table row. The slot stays INACTIVE until `join` so the hit/
        stall ledger reflects when the scheduler actually needed it.
        Returns False (no-op) when the device free list can't cover it."""
        from flexflow_tpu import telemetry as tel
        host_ids = self._cold.get(slot)
        if host_ids is None or slot in self._inflight:
            raise ValueError(f"slot {slot} is not parked")
        need = len(host_ids)
        if len(self.free_pages) < need:
            return False
        pages = [self.free_pages.pop() for _ in range(need)]
        idx = jnp.asarray(np.asarray(pages, np.int32))
        with tel.span("serve/kv_prefetch", cat="serve", slot=int(slot),
                      pages=need, step=int(decode_step)):
            for n in self.attn_layers:
                st = dict(self.state[n])
                for key, leaf in st.items():
                    rows = jnp.asarray(self._host[n][key][host_ids])
                    if self._pool_sharding is not None:
                        rows = jax.device_put(rows, self._pool_sharding)
                    st[key] = leaf.at[idx].set(rows.astype(leaf.dtype))
                self.state[n] = st
        row = np.zeros(self.spec.pages_per_slot, np.int32)
        row[:need] = pages
        self._table[slot] = row
        self._slot_pages[slot] = pages
        self._inflight[slot] = int(decode_step)
        moved = self.spec.layers * need * self.spec.page_bytes()
        self.tier_counters["kv_refills"] += 1
        self.tier_counters["kv_refilled_bytes"] += moved
        return True

    def join(self, slot: int, decode_step: int, prefetch_ahead: int) -> bool:
        """Reactivate a slot whose refill was issued by `prefetch`. Returns
        True when the rejoin STALLED: the copy was issued fewer than
        `prefetch_ahead` decode steps ago, so by the tier's own pricing
        model the transfer had not had time to hide behind decode compute.
        Stalls are counted, never silent (ISSUE 16)."""
        issued = self._inflight.pop(slot, None)
        if issued is None:
            raise ValueError(f"slot {slot} has no prefetch in flight")
        self.free_host_pages.extend(self._cold.pop(slot))
        self._active[slot] = 1
        stalled = (int(decode_step) - issued) < max(1, int(prefetch_ahead))
        if stalled:
            self.tier_counters["kv_prefetch_stalls"] += 1
        else:
            self.tier_counters["kv_prefetch_hits"] += 1
        return stalled

    # ------------------------------------------------------ replica handoff
    def export_parked(self, slot: int) -> Dict:
        """Serialize a PARKED slot's host-tier K/V + committed position for
        a cross-replica handoff (prefill/decode disaggregation, ISSUE 18):
        the prefill replica spills the slot after commit, exports it here,
        evicts, and the fleet delivers the payload to a decode replica's
        `import_parked`. Non-destructive — the caller evicts afterwards."""
        self._kv_pages_only("export_parked")
        host_ids = self._cold.get(slot)
        if host_ids is None:
            raise ValueError(f"slot {slot} is not parked (spill it first)")
        return {
            "pos": int(self._pos[slot]),
            "pages": len(host_ids),
            "layers": {n: {key: buf[host_ids].copy()
                           for key, buf in self._host[n].items()}
                       for n in self.attn_layers},
        }

    def can_import(self, payload: Dict) -> bool:
        return bool(self.host_pages) and \
            len(self.free_host_pages) >= int(payload["pages"])

    def import_parked(self, slot: int, payload: Dict) -> None:
        """Adopt a handed-off slot into this cache's host tier (the decode
        side of the disaggregated handoff). The slot lands PARKED with its
        position preserved, so the ordinary rotation (prefetch + join)
        carries it into HBM — the handoff rides the exact spill/prefetch
        path and stays bitwise-identical to a colocated prefill. Raises
        `KVPoolExhausted` when the host free list is short — backpressure,
        the fleet retries the delivery."""
        self._kv_pages_only("import_parked")
        if self._active[slot] or slot in self._cold:
            raise ValueError(f"slot {slot} is occupied")
        need = int(payload["pages"])
        if not self.can_import(payload):
            raise KVPoolExhausted(slot, need, len(self.free_host_pages))
        host_ids = [self.free_host_pages.pop() for _ in range(need)]
        for n in self.attn_layers:
            for key, rows in payload["layers"][n].items():
                self._host[n][key][host_ids] = rows
        self._cold[slot] = host_ids
        self._pos[slot] = int(payload["pos"])
        self._table[slot] = 0
        self._active[slot] = 0
        moved = self.spec.layers * need * self.spec.page_bytes()
        self.tier_counters["kv_handoffs"] += 1
        self.tier_counters["kv_handoff_bytes"] += moved

    def tier_stats(self) -> Dict[str, int]:
        """Counters + occupancy snapshot for telemetry/monitoring."""
        hot = (self.spec.pool_pages - 1) - len(self.free_pages)
        cold = self.host_pages - len(self.free_host_pages)
        out = dict(self.tier_counters)
        out.update(kv_hot_pages=hot, kv_cold_pages=cold,
                   kv_parked_slots=len(self._cold),
                   kv_host_pages_total=self.host_pages)
        return out

    def host_bytes(self) -> int:
        """Cold-tier buffer bytes actually allocated on the host."""
        return sum(int(buf.nbytes) for layer in self._host.values()
                   for buf in layer.values())

    # ---------------------------------------------------------- device ops
    def commit_prefill(self, kv_state, slot_ids, lengths) -> None:
        """Write the prefill program's captured K/V into the pools, and
        its recurrent state into the slots the wave prefilled."""
        from flexflow_tpu import telemetry as tel

        if self.window_layers:
            raise NotImplementedError(
                "commit_prefill: a wave's K/V into a window's ring of pages "
                f"({len(self.window_layers)} layers): the prompt goes in by "
                "chunks (--serve-prefill-chunk)")
        slot_ids = self._put_repl(np.asarray(slot_ids, np.int32))
        lengths = self._put_repl(np.asarray(lengths, np.int32))
        if self.attn_layers:
            paged = {k: v for k, v in self.state.items()
                     if k not in self.recurrent}
            fresh = {n: kv_state[n] for n in self.attn_layers}
            # each commit consumes the leaves it is handed: what comes back
            # is adopted before the next one reads `self.state`
            with tel.span("serve/prefill/commit_kv", cat="serve",
                          bytes=_tree_bytes(fresh), state=self.state_kinds):
                if self._commit_kv.compiled is None:
                    self._commit_kv.first_run(paged, fresh, slot_ids, lengths)
                self.state = {**self.state,
                              **_commit_prefill(paged, fresh, slot_ids,
                                                lengths)}
        if self.recurrent:
            # a prefill program that wrote the slots itself hands no fresh
            # state on: the span stays (bytes 0), nothing is dispatched
            fresh = {n: kv_state[n] for n in self.recurrent if n in kv_state}
            with tel.span("serve/prefill/commit_state", cat="serve",
                          bytes=_tree_bytes(fresh)):
                if fresh:
                    had = {n: self.state[n] for n in fresh}
                    if self._commit_state.compiled is None:
                        self._commit_state.first_run(had, fresh, slot_ids,
                                                     lengths)
                    self.state.update(_commit_state(had, fresh, slot_ids,
                                                    lengths))

    def adopt(self, new_state) -> None:
        """Take ownership of the state returned by a decode step (a pointer
        set). The step consumed the tree it was handed, so whoever
        dispatches one adopts what came back before anything else reads
        `self.state`."""
        self.state = new_state

    def device_bytes(self) -> int:
        """Pool bytes resident on device 0 (the measured side of the
        KV-cache watermark accounting)."""
        dev = (self.mesh.devices.flat[0] if self.mesh is not None
               else jax.devices()[0])
        total = 0
        for n in self.attn_layers + list(self.recurrent):
            # every leaf of the layer's cache state — values AND, for a
            # quantized cache, the per-(entry, head) scale arrays
            for leaf in self.state[n].values():
                shards = getattr(leaf, "addressable_shards", None)
                if shards is None:
                    total += int(leaf.nbytes)
                else:
                    total += sum(s.data.nbytes for s in shards
                                 if s.device == dev)
        return total
