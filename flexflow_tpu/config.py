"""FFConfig — runtime knobs + CLI parsing.

Reference analog: `FFConfig` (include/flexflow/config.h:92-160) and
`FFConfig::parse_args` (src/runtime/model.cc:3566-3720). Flags keep the
reference's spellings where they exist (-e, -b, --lr, --budget, ...) plus
TPU-specific knobs (mesh shape, dtype policy, remat).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Dict, List, Optional, Tuple

# Everything this package caches on disk lives INSIDE the checkout (the
# parent of the package directory) at fixed, git-ignored paths — never in
# the home directory, where a run could be steered by files that are not in
# the repository: .ff_cache/ (searched strategies, measured op costs) and
# .jax_cache/ (XLA executables).
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FF_CACHE_ROOT = os.path.join(CHECKOUT_ROOT, ".ff_cache")
_compile_cache_dir: Optional[str] = None


def ensure_compile_cache() -> str:
    """Give JAX's persistent compilation cache a home; returns the
    directory. Called by every compile entry point (compile_model,
    compile_serving). $JAX_COMPILATION_CACHE_DIR wins: JAX reads it itself
    and this sets nothing. Otherwise the FIXED path <checkout>/.jax_cache —
    the path is part of the cache key, so it must never move."""
    global _compile_cache_dir
    if _compile_cache_dir is None:
        _compile_cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
        if not _compile_cache_dir:
            import jax

            _compile_cache_dir = os.path.join(CHECKOUT_ROOT, ".jax_cache")
            jax.config.update("jax_compilation_cache_dir", _compile_cache_dir)
    return _compile_cache_dir


@dataclasses.dataclass
class FFConfig:
    # training
    epochs: int = 1
    batch_size: int = 64
    learning_rate: float = 0.01
    weight_decay: float = 1e-4
    iterations: int = 0  # 0 = derive from dataset size
    # FFIterationConfig.seq_length analog (reference config.h:162-167):
    # truncate seq-aware ops (batch_matmul a/b_seq_length_dim) to this many
    # positions. The reference varies it per iteration; XLA static shapes
    # make it a compile-time choice here (0 = full length).
    seq_length: int = 0
    seed: int = 0
    # machine: logical mesh. Empty -> 1D mesh over all visible devices ("data",).
    mesh_shape: Dict[str, int] = dataclasses.field(default_factory=dict)
    num_nodes: int = 1
    workers_per_node: int = 0  # 0 = all local devices
    # search (reference: --budget/--alpha/--only-data-parallel/...)
    search_budget: int = 0
    search_alpha: float = 1.05
    only_data_parallel: bool = False
    enable_parameter_parallel: bool = True
    enable_attribute_parallel: bool = True
    base_optimize_threshold: int = 10
    search_num_nodes: int = 0  # search for a machine larger than the real one
    search_num_workers: int = 0
    import_strategy_file: str = ""
    export_strategy_file: str = ""
    memory_search: bool = False
    substitution_json: str = ""
    # persistent strategy cache (search/strategy_cache.py): warm compile()
    # of an unchanged (graph, machine, knobs, calibration) skips the search.
    # dir "" -> $FF_STRATEGY_CACHE_DIR or <checkout>/.ff_cache/strategy
    strategy_cache: bool = True
    strategy_cache_dir: str = ""
    # event-driven task-graph re-rank of the DP finalists (reference
    # LogicalTaskgraphBasedSimulator, simulator.h:785-827): "additive"
    # trusts the frontier DP's closed-form costing; "taskgraph" replays the
    # top finalists on per-stream timelines and picks by makespan
    simulator_mode: str = "additive"
    simulator_segment_size: int = 16 * 1024 * 1024  # model.cc:3493
    simulator_topk: int = 4
    # machine model (cost model) description file; "" = default v5p-like model
    machine_model_file: str = ""
    # training-loop pipeline (compiler/compile.py _fit_epochs): the fit loop
    # dispatches ahead of the device and never round-trips per step.
    #   sync_every N>0 — materialize deferred loss/metrics to host every N
    #     steps (live metrics at the cost of a host sync); 0 = epoch end
    #     only (default: ZERO per-step host transfers). 1 reproduces the
    #     old fully synchronous loop.
    #   steps_per_dispatch K>1 — drive make_multi_step: K steps fused into
    #     one dispatch (lax.fori_loop over stacked prefetched batches);
    #     falls back to 1 when per-batch callbacks or a recompile trigger
    #     need per-step host control.
    #   dispatch_ahead — block_until_ready barrier every N dispatches so
    #     the host can't queue unboundedly ahead of the device.
    sync_every: int = 0
    steps_per_dispatch: int = 1
    dispatch_ahead: int = 32
    # non-blocking checkpointing (runtime/checkpoint.py): params snapshot to
    # host on the caller thread (donation-safe), serialization + fsync on a
    # background writer thread; restore/exit wait for pending writes
    async_checkpoint: bool = True
    # resilience (runtime/resilience.py): durable atomic-commit checkpoints
    # + preemption-safe shutdown + auto-resume.
    #   checkpoint_dir — root for durable `ckpt-<step>` snapshots ("" = the
    #     whole resilience layer is off; fit then carries zero extra work)
    #   checkpoint_every_steps / checkpoint_every_secs — periodic snapshot
    #     policy inside fit (both 0 = only the end-of-fit/preemption
    #     snapshots); either trigger fires a durable save
    #   resume — "" (fresh start), "auto" (newest committed snapshot under
    #     checkpoint_dir; corrupt ones are skipped), or an explicit path
    #   keep_checkpoints — retention: committed snapshots beyond the newest
    #     N are pruned after each commit (<= 0 keeps everything)
    checkpoint_dir: str = ""
    checkpoint_every_steps: int = 0
    checkpoint_every_secs: float = 0.0
    resume: str = ""
    keep_checkpoints: int = 3
    # transient-fault retry policy (resilience.RetryPolicy.from_config):
    # bounded attempts + exponential backoff with jitter from the run's
    # seeded rng, wrapped around dataloader transfers, checkpoint writes,
    # jax.distributed init and the pipeline boundary hop
    retry_attempts: int = 3
    retry_base_delay: float = 0.05
    # deterministic fault injection (runtime/faults.py plan grammar, e.g.
    # "dataloader/transfer@3*2,checkpoint/write@1!"); also FF_FAULT_PLAN
    fault_plan: str = ""
    # zero-redundancy data parallelism (compiler/compile.py): shard the
    # optimizer moments over the batch ("data"/"node") mesh axes instead of
    # replicating them, and rewrite the update as reduce-scatter(grads) ->
    # sharded moment update -> all-gather(updates).
    #   "off"   — moments replicated over the data axes (the reference's
    #             fully-replicated NCCL regime)
    #   "zero1" — moments sharded; gradients/accumulators stay full-size
    #   "zero2" — zero1 + gradient ACCUMULATORS (accum_steps > 1) stored
    #             reduce-scattered, so long accumulation windows don't pay
    #             a full-size gradient residency either
    # The search's memory model follows the knob (search/cost_model.py
    # OptMemSpec), so --memory-search prices the sharded moments.
    zero_sharding: str = "off"
    # gradient accumulation: fold N consecutive loader microbatches into ONE
    # optimizer update (device-resident accumulators, effective batch =
    # N x batch_size). Composes with steps_per_dispatch (K fused UPDATES per
    # dispatch) and the deferred-metrics loop. Microbatches beyond the last
    # full group of an epoch are dropped (drop_remainder semantics).
    accum_steps: int = 1
    # pipeline parallelism (parallel/pipeline.py): split the layer graph
    # into N sequential stages on DISJOINT device groups over a "pipe" mesh
    # axis — each group holds only its stage's weights + optimizer state
    # (per-device persistent memory divides by N, composing with
    # --zero-sharding). accum_steps is the microbatch count M the schedule
    # pipelines over; 1 < N requires accum_steps > 1 for any overlap.
    #   pipeline_schedule: "gpipe" (all forwards, then all backwards; M
    #   in-flight boundary activations per stage) or "1f1b" (one-forward-
    #   one-backward steady state; <= N in-flight activations). Both have
    #   bubble fraction (N-1)/(M+N-1); 1f1b's win is activation memory.
    pipeline_stages: int = 1
    pipeline_schedule: str = "1f1b"
    # execution
    enable_fusion: bool = True
    profiling: bool = False
    profile_dir: str = ""  # xplane trace output dir ("" = ./ff_profile)
    # per-op attribution (flexflow_tpu/attribution.py): at fit end, join
    # per-op measured times (under --profiling the profile's device events
    # joined by instruction name with the compiled step's HLO, else
    # partitioned re-execution) against the search's stamped per-op
    # predicted costs and the roofline bound — per-op MFU, compute-vs-
    # bandwidth classification and the per-op drift top-K, printed via
    # profile_report and emitted as op/attr telemetry events
    # (tools/trace_report.py's [ops] section)
    profile_ops: bool = False
    allow_tensor_op_math_conversion: bool = True  # = bf16 matmul policy
    compute_dtype: str = "float32"  # params dtype; "bfloat16" enables mixed policy
    # rematerialization. --remat is the legacy GLOBAL bool (deprecated in
    # favor of the searched form): it now maps to a uniform "full"
    # per-layer policy at compile. --remat-search promotes remat to a
    # per-layer SEARCH dimension: the frontier DP prices each layer's
    # policy candidates (--remat-policies, from none/dots/full) with the
    # real memory-saved vs recompute-time tradeoff under --memory-search's
    # HBM cap, so activation memory trades against FLOPs deliberately
    # instead of forcing ZeRO or pipelining. The two flags contradict:
    # combining them is rejected (see _check_remat_knobs).
    remat: bool = False  # DEPRECATED alias: uniform "full" policy
    # every layer recomputed in the backward pass, in UNITS of several
    # layers (compiler/lowering.py `checkpoint_units`): a unit ends where a
    # tensor has more than one consumer (the residual stream, a norm's
    # output that two branches read), so what the forward pass keeps is
    # those tensors alone, where per-layer "full" keeps every tensor
    # between two layers. What a long sequence under a full chip needs.
    remat_blocks: bool = False
    remat_search: bool = False
    remat_policies: str = "none,dots,full"
    donate_state: bool = True
    # observability
    # unified telemetry (flexflow_tpu/telemetry.py): span/counter JSONL
    # stream across compile, fit, pipeline executor, dataloader prefetch
    # and async checkpointing, rendered by tools/trace_report.py into a
    # span summary + Chrome trace. "" = disabled (near-zero overhead).
    telemetry_dir: str = ""
    # size cap per telemetry JSONL segment in MB (flexflow_tpu/health.py
    # era): long elastic runs rotate to telemetry-<pid>.<seq>.jsonl past
    # this; readers (trace_report / monitor) merge segments
    # transparently. 0 = unbounded (the pre-rotation behavior).
    telemetry_max_mb: float = 512.0
    # numerics sentinels (flexflow_tpu/health.py): device-resident
    # finite-checks + grad-norm/loss-spike detectors folded into the
    # deferred metrics (zero extra host syncs); halt_on_nonfinite escalates
    # a NaN/Inf window to NonFiniteError through the checkpoint drain so
    # the last durable checkpoint is the recovery point
    health_sentinels: bool = True
    halt_on_nonfinite: bool = False
    export_dot: str = ""  # --compgraph analog
    include_costs_dot_graph: bool = False
    # chrome-trace export of the COMPILED strategy's event-driven replay
    # (search/simulator.py SimReport.export_trace) — the taskgraph export
    # analog of the reference simulator's export_file_name
    simulator_trace: str = ""
    log_level: str = "info"
    # inference serving (flexflow_tpu/serving): compile_serving() lowers the
    # graph twice — a compute-priced prefill program and a bandwidth-priced
    # single-token decode program, each with its own searched strategy — and
    # serves them through a paged KV cache + continuous-batching scheduler.
    #   serve            — gate: launcher builds the serving engine instead
    #                      of the training executable
    #   max_decode_len   — per-request decode budget (0 = serving default)
    #   kv_page_size     — tokens per KV-cache page
    #   max_batch_slots  — concurrent decode slots (the decode batch dim)
    #   serve_objective  — _score objective for the serving searches:
    #                      "latency" (pure time) or "throughput" (time
    #                      discounted by memory headroom for bigger batches)
    serve: bool = False
    max_decode_len: int = 0
    kv_page_size: int = 16
    max_batch_slots: int = 8
    serve_objective: str = "latency"
    # serving resilience (ISSUE 11): hot-swap watching + SLO admission.
    #   serve_watch_dir        — durable-checkpoint root the engine polls
    #                            for new committed snapshots to hot-swap
    #                            ("" = swapping off)
    #   serve_ttft_budget_ms   — shed a request when its estimated TTFT
    #                            exceeds this budget (0 = no budget)
    #   serve_queue_cap        — max waiting requests before the lowest-
    #                            priority one is shed (0 = unbounded)
    #   serve_decode_timeout_ms— decode-window watchdog: a materialization
    #                            slower than this per step evicts the
    #                            longest-resident slot (0 = no watchdog)
    serve_watch_dir: str = ""
    serve_ttft_budget_ms: float = 0.0
    serve_queue_cap: int = 0
    serve_decode_timeout_ms: float = 0.0
    # decode throughput (ISSUE 13): speculative decoding + quantized KV.
    #   serve_draft_model   — checkpoint/model spec for the small DRAFT
    #                         model compile_serving lowers through the same
    #                         search ("" = no speculation); programmatic
    #                         callers pass draft= directly
    #   serve_spec_tokens   — tokens the draft proposes per slot per round
    #                         before ONE batched target verify pass (0 =
    #                         speculation off even with a draft attached)
    #   kv_cache_dtype      — paged-KV storage dtype: "auto" follows
    #                         compute_dtype (today's behavior), "bf16"
    #                         forces bf16 pools, "int8" stores int8 pools
    #                         with per-page-entry-per-head f32 scales —
    #                         the search prices the smaller pools (memory
    #                         cap loosens, decode bandwidth term drops)
    serve_draft_model: str = ""
    serve_spec_tokens: int = 0
    kv_cache_dtype: str = "auto"
    # serving observability (ISSUE 15): per-request lifecycle traces +
    # live latency histograms + SLO error budgets.
    #   serve_slo        — comma-separated SLO objectives, e.g.
    #                      "ttft_p99_ms=25,per_token_p99_ms=10,
    #                       availability=0.999" (health.parse_slo grammar;
    #                      "" = no objectives, the tracker still counts
    #                      outcomes). Surfaced via
    #                      health_report()["serving"]["slo"], the monitor
    #                      serving panel, and prom burn-rate gauges.
    #   serve_reqtrace   — per-request stage tracing (serve/req/* spans,
    #                      streaming histograms, bounded trace ring).
    #                      Defaults ON and is zero-sync (reuses the
    #                      scheduler's existing window-boundary
    #                      timestamps); --no-serve-reqtrace restores the
    #                      bitwise PR-13 dispatch behavior.
    serve_slo: str = ""
    serve_reqtrace: bool = True
    # long-context serving (ISSUE 16): tiered KV cache + prefetch-ahead.
    #   kv_host_pages     — host-memory cold-tier pages per KV pool. > 0
    #                       shrinks the HBM pool by the same amount
    #                       (floored at one slot's worth) and lets the
    #                       scheduler park idle-enough slots on the host,
    #                       so total servable context at a fixed HBM-page
    #                       budget grows by rotation. 0 = untiered, the
    #                       exact pre-tier geometry.
    #   kv_prefetch_ahead — decode steps before a parked slot's rejoin
    #                       that its host→HBM refill is issued; a rejoin
    #                       with less lead counts a prefetch stall. Also
    #                       the denominator the decode roofline amortizes
    #                       unhidden prefetch traffic over.
    #   serve_max_context — operator context ceiling (prompt + decode
    #                       budget, tokens): arrivals over it shed
    #                       permanently as over_max_context, distinct from
    #                       a transiently full pool (which queues).
    #                       0 = no ceiling.
    kv_host_pages: int = 0
    kv_prefetch_ahead: int = 2
    serve_max_context: int = 0
    # chunked prefill (ISSUE 52): a prompt goes into its slot's pages in
    # chunks of ONE compiled shape, each attending over what the slot has
    # cached and over itself, decode steps of the live slots between them.
    #   serve_prefill_chunk      — tokens a chunk (0 = off: one padded
    #                              `[slots, seq]` wave, as before). With it
    #                              the model's `seq` is a slot's whole
    #                              context: prompt + answer.
    serve_prefill_chunk: int = 0
    # fleet serving (ISSUE 18): replica pools behind one control plane.
    #   serve_replicas         — in-process engine replicas behind the
    #                            fleet router. 1 = the plain pre-fleet
    #                            single-engine path (no pump threads).
    #   serve_fleet_topology   — "colocated" (every replica prefills and
    #                            decodes) or "disagg" (dedicated prefill
    #                            replicas hand committed KV pages to the
    #                            decode pool over the host tier; needs
    #                            kv_host_pages > 0 on every replica).
    #   serve_prefill_replicas — replicas assigned to the prefill pool
    #                            under disagg; clamped to [1, replicas-1].
    #   serve_router           — placement policy: "least_loaded"
    #                            (outstanding work + estimated TTFT, SLO
    #                            burn as tie-breaker) or "round_robin".
    #   serve_rollout_burn_max — rolling-swap rollback ceiling: a swapped
    #                            replica whose SLO worst burn rate crosses
    #                            it rolls back and freezes the rollout.
    #                            0 = no rollback monitor.
    serve_replicas: int = 1
    serve_fleet_topology: str = "colocated"
    serve_prefill_replicas: int = 1
    serve_router: str = "least_loaded"
    serve_rollout_burn_max: float = 0.0
    # capacity twin (ISSUE 20): replayable traces + offline what-if replay.
    #   serve_trace_out — export the offered load (arrival_ts, tokens_in,
    #                     max_tokens, priority, deadline, prompt) as a
    #                     versioned tracefmt JSONL at serve end; "" = off.
    #                     A recorded trace replays through tools/twin.py
    #                     (offline capacity questions) or a live engine.
    #   twin_trace      — trace file the twin CLI replays.
    #   twin_replicas   — replica count the twin simulates (0 = follow
    #                     --serve-replicas).
    #   twin_out        — write the twin report JSON here ("" = stdout).
    serve_trace_out: str = ""
    twin_trace: str = ""
    twin_replicas: int = 0
    twin_out: str = ""

    REMAT_POLICY_NAMES = ("none", "dots", "full")

    def __post_init__(self):
        self._check_remat_knobs()
        if self.serve_slo:
            # fail loud at config build, not mid-serve
            from flexflow_tpu.health import parse_slo
            parse_slo(self.serve_slo)

    def _check_remat_knobs(self):
        """--remat (the deprecated global bool) and the searched-remat
        knobs contradict each other: the alias pins every layer to "full"
        while the search exists to pick per-layer policies. Fail loud
        instead of silently letting one win."""
        if self.remat_blocks and (self.remat or self.remat_search):
            raise ValueError(
                "remat_blocks (every layer recomputed, in units that end at "
                "the residual stream) contradicts --remat / --remat-search")
        if self.remat and self.remat_search:
            raise ValueError(
                "--remat (deprecated: uniform 'full' remat) contradicts "
                "--remat-search (per-layer searched remat); drop --remat "
                "— the search's candidate set already includes 'full'")
        bad = [pol for pol in self.remat_policy_list()
               if pol not in self.REMAT_POLICY_NAMES]
        if bad:
            raise ValueError(
                f"unknown remat policies {bad!r} in "
                f"remat_policies={self.remat_policies!r} "
                f"(choose from {', '.join(self.REMAT_POLICY_NAMES)})")

    def remat_policy_list(self) -> Tuple[str, ...]:
        """The per-layer remat-policy candidate set the DP searches over
        (parsed from --remat-policies; "none" is always a candidate so the
        search can keep a layer unrematerialized)."""
        pols = tuple(s.strip() for s in self.remat_policies.split(",")
                     if s.strip())
        if "none" not in pols:
            pols = ("none",) + pols
        return pols

    @property
    def total_devices(self) -> int:
        if self.mesh_shape:
            n = 1
            for v in self.mesh_shape.values():
                n *= v
            return n
        import jax

        return len(jax.devices())

    @staticmethod
    def build_parser() -> argparse.ArgumentParser:
        """The ONE FFConfig argument parser. The launcher's value-flag set
        (launcher_value_flags) is derived from this parser's actions, so a
        flag added here is automatically launcher-safe — PRs 2 and 3 both
        had to hand-register their new flags in __main__.py, and the
        regression class being guarded is `python -m flexflow_tpu
        --new-flag VALUE train.py` treating VALUE as the script."""
        p = argparse.ArgumentParser("flexflow_tpu", allow_abbrev=False)
        p.add_argument("-e", "--epochs", type=int, default=1)
        p.add_argument("-b", "--batch-size", type=int, default=64)
        p.add_argument("--lr", "--learning-rate", dest="lr", type=float, default=0.01)
        p.add_argument("--wd", "--weight-decay", dest="wd", type=float, default=1e-4)
        p.add_argument("--iterations", type=int, default=0)
        p.add_argument("--seq-length", type=int, default=0)
        p.add_argument("--seed", type=int, default=0)
        p.add_argument("--mesh", type=str, default="", help="e.g. data=4,model=2")
        p.add_argument("--nodes", type=int, default=1)
        p.add_argument("-ll:tpu", "--workers-per-node", dest="workers", type=int, default=0)
        p.add_argument("--budget", "--search-budget", dest="budget", type=int, default=0)
        p.add_argument("--alpha", "--search-alpha", dest="alpha", type=float, default=1.05)
        p.add_argument("--only-data-parallel", action="store_true")
        p.add_argument("--enable-parameter-parallel", action=argparse.BooleanOptionalAction,
                       default=True)
        p.add_argument("--enable-attribute-parallel", action=argparse.BooleanOptionalAction,
                       default=True)
        p.add_argument("--base-optimize-threshold", type=int, default=10)
        p.add_argument("--search-num-nodes", type=int, default=0)
        p.add_argument("--search-num-workers", type=int, default=0)
        p.add_argument("--import", dest="import_file", type=str, default="")
        p.add_argument("--export", dest="export_file", type=str, default="")
        p.add_argument("--memory-search", action="store_true")
        p.add_argument("--substitution-json", type=str, default="")
        p.add_argument("--strategy-cache", action=argparse.BooleanOptionalAction,
                       default=True)
        p.add_argument("--strategy-cache-dir", type=str, default="")
        p.add_argument("--simulator-mode", type=str, default="additive",
                       choices=("additive", "taskgraph"))
        p.add_argument("--simulator-segment-size", type=int,
                       default=16 * 1024 * 1024)
        p.add_argument("--simulator-topk", type=int, default=4)
        p.add_argument("--simulator-trace", type=str, default="")
        p.add_argument("--machine-model-file", type=str, default="")
        p.add_argument("--sync-every", type=int, default=0)
        p.add_argument("--steps-per-dispatch", type=int, default=1)
        p.add_argument("--dispatch-ahead", type=int, default=32)
        p.add_argument("--async-checkpoint", action=argparse.BooleanOptionalAction,
                       default=True)
        p.add_argument("--checkpoint-dir", type=str, default="")
        p.add_argument("--checkpoint-every-steps", type=int, default=0)
        p.add_argument("--checkpoint-every-secs", type=float, default=0.0)
        p.add_argument("--resume", type=str, default="")
        p.add_argument("--keep-checkpoints", type=int, default=3)
        p.add_argument("--retry-attempts", type=int, default=3)
        p.add_argument("--retry-base-delay", type=float, default=0.05)
        p.add_argument("--fault-plan", type=str, default="")
        p.add_argument("--zero-sharding", type=str, default="off",
                       choices=("off", "zero1", "zero2"))
        p.add_argument("--accum-steps", type=int, default=1)
        p.add_argument("--pipeline-stages", type=int, default=1)
        p.add_argument("--pipeline-schedule", type=str, default="1f1b",
                       choices=("gpipe", "1f1b"))
        p.add_argument("--fusion", dest="fusion", action="store_true", default=True)
        p.add_argument("--no-fusion", dest="fusion", action="store_false")
        p.add_argument("--profiling", action="store_true")
        p.add_argument("--profile-dir", type=str, default="")
        p.add_argument("--profile-ops", action="store_true")
        p.add_argument("--telemetry-dir", type=str, default="")
        p.add_argument("--telemetry-max-mb", type=float, default=512.0)
        p.add_argument("--health-sentinels",
                       action=argparse.BooleanOptionalAction, default=True)
        p.add_argument("--halt-on-nonfinite", action="store_true")
        p.add_argument("--compute-dtype", type=str, default="float32")
        p.add_argument("--remat", action="store_true",
                       help="DEPRECATED: uniform full remat; prefer "
                            "--remat-search")
        p.add_argument("--remat-search", action="store_true")
        p.add_argument("--remat-policies", type=str,
                       default="none,dots,full")
        p.add_argument("--compgraph", dest="export_dot", type=str, default="")
        p.add_argument("--include-costs-dot-graph", action="store_true")
        p.add_argument("--serve", action="store_true")
        p.add_argument("--max-decode-len", type=int, default=0)
        p.add_argument("--kv-page-size", type=int, default=16)
        p.add_argument("--max-batch-slots", type=int, default=8)
        p.add_argument("--serve-objective", type=str, default="latency",
                       choices=("latency", "throughput"))
        p.add_argument("--serve-watch-dir", type=str, default="")
        p.add_argument("--serve-ttft-budget-ms", type=float, default=0.0)
        p.add_argument("--serve-queue-cap", type=int, default=0)
        p.add_argument("--serve-decode-timeout-ms", type=float, default=0.0)
        p.add_argument("--serve-draft-model", type=str, default="")
        p.add_argument("--serve-spec-tokens", type=int, default=0)
        p.add_argument("--kv-cache-dtype", type=str, default="auto",
                       choices=("auto", "bf16", "int8"))
        p.add_argument("--serve-slo", type=str, default="",
                       help='SLO objectives, e.g. "ttft_p99_ms=25,'
                            'per_token_p99_ms=10,availability=0.999"')
        p.add_argument("--serve-reqtrace",
                       action=argparse.BooleanOptionalAction, default=True)
        p.add_argument("--kv-host-pages", type=int, default=0)
        p.add_argument("--kv-prefetch-ahead", type=int, default=2)
        p.add_argument("--serve-max-context", type=int, default=0)
        p.add_argument("--serve-prefill-chunk", type=int, default=0)
        p.add_argument("--serve-replicas", type=int, default=1)
        p.add_argument("--serve-fleet-topology", type=str,
                       default="colocated", choices=("colocated", "disagg"))
        p.add_argument("--serve-prefill-replicas", type=int, default=1)
        p.add_argument("--serve-router", type=str, default="least_loaded",
                       choices=("least_loaded", "round_robin"))
        p.add_argument("--serve-rollout-burn-max", type=float, default=0.0)
        p.add_argument("--serve-trace-out", type=str, default="",
                       help="export the served load as a replayable "
                            "tracefmt JSONL trace at serve end")
        p.add_argument("--twin-trace", type=str, default="",
                       help="trace file the capacity twin replays")
        p.add_argument("--twin-replicas", type=int, default=0,
                       help="replica count the twin simulates "
                            "(0 = --serve-replicas)")
        p.add_argument("--twin-out", type=str, default="",
                       help="twin report JSON path ('' = stdout)")
        return p

    @staticmethod
    def launcher_value_flags() -> set:
        """Option strings that CONSUME the next argv token — derived from
        the parser instead of hand-maintained in __main__.py, so the
        launcher's script-vs-flag-value split can never drift behind a
        newly added flag. argparse encodes the distinction as nargs: flag
        actions (store_true / BooleanOptionalAction / help) carry nargs=0,
        value-taking ones nargs=None (one token) or an int/str spec."""
        flags = set()
        for a in FFConfig.build_parser()._actions:
            if a.nargs == 0:
                continue
            flags.update(a.option_strings)
        return flags

    @staticmethod
    def parse_args(argv: Optional[List[str]] = None) -> "FFConfig":
        # FF_LAUNCH_ARGS: machine config injected by the Jupyter kernelspec
        # (flexflow_tpu/jupyter — the reference custom-kernel analog) or a
        # launcher wrapper. Honored ONLY for real CLI invocations
        # (argv=None): a kernelspec-installed env var must not silently
        # alter explicit programmatic configs in tests/scripts.
        # CLI flags still override the environment.
        if argv is None:
            import shlex
            import sys

            env_args = shlex.split(os.environ.get("FF_LAUNCH_ARGS", ""))
            argv = env_args + list(sys.argv[1:])
        # parse_known_args passes unknown flags by in silence (they are the
        # user script's): a flag of ours that is gone is refused by name
        gone = {
            "--fused-optimizer": "the optimizer update is always tx.update "
                                 "+ optax.apply_updates, one XLA fusion per "
                                 "leaf",
            "--fused-loss": "the loss is always the optax form "
                            "(losses.compute_loss on float32 logits)",
            "--cost-model-path": "the learned pricing tier is gone; "
                                 "--simulator-mode is additive or taskgraph",
            "--auto-refit": "the learned pricing tier is gone",
        }
        for flag in (a.split("=")[0] for a in argv):
            if flag in gone:
                raise SystemExit(f"{flag} is gone: {gone[flag]}")
        args, _unknown = FFConfig.build_parser().parse_known_args(argv)

        mesh: Dict[str, int] = {}
        if args.mesh:
            for part in args.mesh.split(","):
                k, v = part.split("=")
                mesh[k.strip()] = int(v)
        return FFConfig(
            epochs=args.epochs,
            batch_size=args.batch_size,
            learning_rate=args.lr,
            weight_decay=args.wd,
            iterations=args.iterations,
            seq_length=args.seq_length,
            seed=args.seed,
            mesh_shape=mesh,
            num_nodes=args.nodes,
            workers_per_node=args.workers,
            search_budget=args.budget,
            search_alpha=args.alpha,
            only_data_parallel=args.only_data_parallel,
            enable_parameter_parallel=args.enable_parameter_parallel,
            enable_attribute_parallel=args.enable_attribute_parallel,
            base_optimize_threshold=args.base_optimize_threshold,
            search_num_nodes=args.search_num_nodes,
            search_num_workers=args.search_num_workers,
            import_strategy_file=args.import_file,
            export_strategy_file=args.export_file,
            memory_search=args.memory_search,
            substitution_json=args.substitution_json,
            strategy_cache=args.strategy_cache,
            strategy_cache_dir=args.strategy_cache_dir,
            simulator_mode=args.simulator_mode,
            simulator_segment_size=args.simulator_segment_size,
            simulator_topk=args.simulator_topk,
            simulator_trace=args.simulator_trace,
            machine_model_file=args.machine_model_file,
            sync_every=args.sync_every,
            steps_per_dispatch=args.steps_per_dispatch,
            dispatch_ahead=args.dispatch_ahead,
            async_checkpoint=args.async_checkpoint,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every_steps=args.checkpoint_every_steps,
            checkpoint_every_secs=args.checkpoint_every_secs,
            resume=args.resume,
            keep_checkpoints=args.keep_checkpoints,
            retry_attempts=args.retry_attempts,
            retry_base_delay=args.retry_base_delay,
            fault_plan=args.fault_plan,
            zero_sharding=args.zero_sharding,
            accum_steps=args.accum_steps,
            pipeline_stages=args.pipeline_stages,
            pipeline_schedule=args.pipeline_schedule,
            enable_fusion=args.fusion,
            profiling=args.profiling,
            profile_dir=args.profile_dir,
            profile_ops=args.profile_ops,
            telemetry_dir=args.telemetry_dir,
            telemetry_max_mb=args.telemetry_max_mb,
            health_sentinels=args.health_sentinels,
            halt_on_nonfinite=args.halt_on_nonfinite,
            compute_dtype=args.compute_dtype,
            remat=args.remat,
            remat_search=args.remat_search,
            remat_policies=args.remat_policies,
            export_dot=args.export_dot,
            include_costs_dot_graph=args.include_costs_dot_graph,
            serve=args.serve,
            max_decode_len=args.max_decode_len,
            kv_page_size=args.kv_page_size,
            max_batch_slots=args.max_batch_slots,
            serve_objective=args.serve_objective,
            serve_watch_dir=args.serve_watch_dir,
            serve_ttft_budget_ms=args.serve_ttft_budget_ms,
            serve_queue_cap=args.serve_queue_cap,
            serve_decode_timeout_ms=args.serve_decode_timeout_ms,
            serve_draft_model=args.serve_draft_model,
            serve_spec_tokens=args.serve_spec_tokens,
            kv_cache_dtype=args.kv_cache_dtype,
            serve_slo=args.serve_slo,
            serve_reqtrace=args.serve_reqtrace,
            kv_host_pages=args.kv_host_pages,
            kv_prefetch_ahead=args.kv_prefetch_ahead,
            serve_max_context=args.serve_max_context,
            serve_prefill_chunk=args.serve_prefill_chunk,
            serve_replicas=args.serve_replicas,
            serve_fleet_topology=args.serve_fleet_topology,
            serve_prefill_replicas=args.serve_prefill_replicas,
            serve_router=args.serve_router,
            serve_rollout_burn_max=args.serve_rollout_burn_max,
            serve_trace_out=args.serve_trace_out,
            twin_trace=args.twin_trace,
            twin_replicas=args.twin_replicas,
            twin_out=args.twin_out,
        )
