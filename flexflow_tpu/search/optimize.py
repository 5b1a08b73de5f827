"""graph_optimize — the search entry point.

Reference analog: `Graph::graph_optimize_task` →
`GraphSearchHelper::graph_optimize` (src/runtime/substitution.cc:1898-1945):
construct PCG, search, serialize strategy. Here: candidates + frontier DP →
Strategy (the per-op PartitionSpec map). The search budget scales the beam
width (the best-first budget analog); alpha is accepted for interface parity.
"""

from __future__ import annotations

from typing import Optional

from flexflow_tpu.core.graph import topo_order
from flexflow_tpu.parallel.machine import MachineSpec
from flexflow_tpu.parallel.sharding import OpSharding, Strategy
from flexflow_tpu.search.candidates import _dp_dims
from flexflow_tpu.search.dp import SearchResult, search_graph


def result_to_strategy(model, machine: MachineSpec, result: SearchResult) -> Strategy:
    st = Strategy(mesh_axes=dict(machine.mesh_axes), name="searched")
    batch_sizes = {t.shape[0] for t in model.input_tensors if t.ndim > 0}
    for t in model.input_tensors:
        st.input_shardings[t.name] = _dp_dims(t.shape, machine, batch_sizes)
    from flexflow_tpu.search.candidates import candidate_attrs

    for layer in topo_order(model.layers):
        cand = result.choices[layer.name]
        st.op_shardings[layer.name] = OpSharding(
            outputs=[list(d) for d in cand.out_dims],
            weights={w: list(d) for w, d in cand.weight_dims.items()},
            attrs=candidate_attrs(cand),
        )
    return st


def graph_optimize(model, machine: MachineSpec,
                   measured: bool = False, optimizer=None) -> Strategy:
    """Unity search: graph substitutions (best-first under budget/alpha) over
    the frontier DP. Falls back to the plain DP when the engine is disabled
    (enable_parameter_parallel=False etc. restricts candidates either way).

    Fast path (search/strategy_cache.py): unless cfg.strategy_cache is off,
    the winning Strategy is persisted keyed by (graph hash, machine
    fingerprint, search knobs, calibration fingerprint) — a warm call on an
    unchanged model returns the validated cached strategy without running
    the substitution loop or a single DP expansion."""
    import time

    from flexflow_tpu.search import cost_model as cm
    from flexflow_tpu.search import strategy_cache as sc

    cfg = model.config
    # the optimizer's memory model (moment count/dtype + ZeRO divisor):
    # changes what memory-constrained searches predict, so it rides the
    # cache key below
    opt_mem = cm.opt_mem_spec(optimizer, cfg, machine)
    opt_fp = repr(opt_mem.fingerprint()) if opt_mem is not None else ""
    use_cache = bool(getattr(cfg, "strategy_cache", True))
    cache_dir = sc.resolve_dir(cfg) if use_cache else None
    cost_fn = None
    measure_cache_path = None
    if measured or cfg.profiling:
        try:
            from flexflow_tpu.search.measure import MeasuredCost

            # the measured-cost store is its own fast-path tier: it keeps
            # persisting under the resolved cache dir even when the
            # STRATEGY cache is off (--no-strategy-cache asks for fresh
            # searches, not for re-running every on-device microbenchmark)
            mc = MeasuredCost(machine, cache_dir=sc.resolve_dir(cfg))
            cost_fn = mc.op_time
            measure_cache_path = mc.cache_path
        except Exception:
            cost_fn = None
    if use_cache:
        calib = sc.calibration_fingerprint(
            measure_cache_path if measure_cache_path else None)
        key = sc.cache_key(model, machine, cfg, calib, opt_fp)
        cached = sc.lookup(cache_dir, key, model, machine)
        if cached is not None:
            return cached
    from flexflow_tpu import telemetry as tel
    from flexflow_tpu.search.unity import unity_optimize

    t0 = time.perf_counter()
    with tel.span("search/unity", cat="compile",
                  measured=bool(cost_fn is not None)):
        st, stats = unity_optimize(model, machine, cost_fn=cost_fn,
                                   opt_mem=opt_mem)
    # stamp the search's own per-step prediction: the drift monitor
    # compares THIS number (what the search believed when it chose the
    # strategy) against what fit actually measures — and the PER-OP costs,
    # so the attribution layer (flexflow_tpu/attribution.py) can localize
    # a mispredicted step to the ops the DP misprices
    st._predicted_cost = stats.best_cost
    st._predicted_op_costs = dict(stats.op_costs)
    tel.event("search/result", cat="compile", cost_s=stats.best_cost,
              baseline_cost_s=stats.baseline_cost,
              expansions=stats.expansions)
    if use_cache:
        if measure_cache_path is not None:
            # the measured search wrote new microbenchmarks into the store
            # it is fingerprinted by: re-key on the POST-search content so
            # the next run's lookup (which hashes the populated store)
            # finds this entry instead of orphaning it
            calib = sc.calibration_fingerprint(measure_cache_path)
            key = sc.cache_key(model, machine, cfg, calib, opt_fp)
        meta = {
            "cost_s": stats.best_cost,
            "op_costs_s": dict(stats.op_costs),
            "baseline_cost_s": stats.baseline_cost,
            "expansions": stats.expansions,
            "search_wallclock_s": time.perf_counter() - t0,
            "calibration": calib,
        }
        sc.store(cache_dir, key, st, meta=meta)
    return st


def predict_step_time(model, machine: MachineSpec, beam_width: int = 64) -> float:
    """Predicted per-step time of the best found strategy (simulator query)."""
    return search_graph(model, machine, beam_width=beam_width).cost
