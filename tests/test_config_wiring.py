"""The remaining runtime-config fields are wired (zero
accepted-and-ignored, extending round-2's bar to every field): num_nodes/
workers_per_node machine description, donate_state, tensor-op math gate,
log_level, seq_length (tested in test_core_graph)."""

import logging

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer


def _tiny(cfg):
    m = FFModel(cfg)
    x = m.create_tensor([16, 8], name="x")
    m.dense(x, 4, name="fc")
    return m


def test_num_nodes_builds_dcn_node_axis(devices):
    cfg = FFConfig(batch_size=16, num_nodes=2, workers_per_node=4,
                   only_data_parallel=True)
    m = _tiny(cfg)
    cm = m.compile(SGDOptimizer(lr=0.01),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
    assert dict(cm.machine.mesh_axes) == {"node": 2, "data": 4}
    assert cm.machine.dcn_axes == ("node",)
    assert cm.machine.axis_bw("node") == cm.machine.dcn_bw  # DCN-priced


def test_donate_state_false_keeps_buffers(devices):
    import jax

    cfg = FFConfig(batch_size=16, only_data_parallel=True, donate_state=False)
    m = _tiny(cfg)
    cm = m.compile(SGDOptimizer(lr=0.01),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=0)
    old_params = cm.params
    x = np.zeros((16, 8), np.float32)
    y = np.zeros((16,), np.int32)
    cm.train_step(cm.params, cm.opt_state, cm.state, [jax.device_put(x)],
                  jax.device_put(y), jax.random.PRNGKey(0))
    # without donation the original buffers remain readable
    _ = float(np.asarray(old_params["fc"]["kernel"]).sum())


def test_tensor_op_math_gate_sets_matmul_precision(devices):
    import jax

    def jaxpr_for(allow):
        cfg = FFConfig(batch_size=16, only_data_parallel=True,
                       allow_tensor_op_math_conversion=allow)
        m = _tiny(cfg)
        cm = m.compile(SGDOptimizer(lr=0.01),
                       loss_type="sparse_categorical_crossentropy",
                       metrics=[])
        cm.init(seed=0)
        x = [np.zeros((16, 8), np.float32)]
        y = np.zeros((16,), np.int32)
        return str(jax.make_jaxpr(
            lambda p, o, s: cm.train_step.__wrapped__(p, o, s, x, y,
                                                      jax.random.PRNGKey(0))
        )(cm.params, cm.opt_state, cm.state))

    assert "Precision.HIGHEST" in jaxpr_for(False)
    assert "Precision.HIGHEST" not in jaxpr_for(True)


def test_log_level_wired(devices, caplog):
    lg = logging.getLogger("flexflow_tpu")
    old = lg.level
    try:
        # pristine logger: cfg.log_level applies
        lg.setLevel(logging.NOTSET)
        m = _tiny(FFConfig(batch_size=16, only_data_parallel=True,
                           log_level="debug"))
        m.compile(SGDOptimizer(lr=0.01),
                  loss_type="sparse_categorical_crossentropy", metrics=[])
        assert lg.level == logging.DEBUG
        # application config wins: an explicit level is never clobbered
        lg.setLevel(logging.WARNING)
        m2 = _tiny(FFConfig(batch_size=16, only_data_parallel=True,
                            log_level="info"))
        m2.compile(SGDOptimizer(lr=0.01),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
        assert lg.level == logging.WARNING
        # invalid names fail loud instead of silently meaning INFO
        with pytest.raises(ValueError):
            _tiny(FFConfig(batch_size=16, only_data_parallel=True,
                           log_level="trace")).compile(
                SGDOptimizer(lr=0.01),
                loss_type="sparse_categorical_crossentropy", metrics=[])
        # the compile log line exists
        with caplog.at_level(logging.INFO, logger="flexflow_tpu"):
            m3 = _tiny(FFConfig(batch_size=16, only_data_parallel=True))
            m3.compile(SGDOptimizer(lr=0.01),
                       loss_type="sparse_categorical_crossentropy", metrics=[])
        assert any("compile: mesh=" in r.getMessage() for r in caplog.records)
    finally:
        lg.setLevel(old)


def test_telemetry_dir_wired(devices, tmp_path):
    """--telemetry-dir flows parse_args -> FFConfig -> compile_model,
    which enables the process-global telemetry stream (ISSUE 5). Added
    via FFConfig.build_parser only, so the launcher's value-flag set
    covers it automatically (test_launcher_accuracy's derived-flags
    regression)."""
    from flexflow_tpu import telemetry as tel
    from flexflow_tpu.config import FFConfig as Cfg

    cfg = Cfg.parse_args(["--telemetry-dir", "/tmp/tele_x"])
    assert cfg.telemetry_dir == "/tmp/tele_x"
    assert Cfg().telemetry_dir == ""  # off by default
    # --telemetry-dir consumes its value token: the launcher must not
    # mistake the dir for the user script
    assert "--telemetry-dir" in Cfg.launcher_value_flags()
    try:
        tdir = str(tmp_path / "tele")
        m = _tiny(FFConfig(batch_size=16, only_data_parallel=True,
                           telemetry_dir=tdir, log_level="warning"))
        m.compile(SGDOptimizer(lr=0.01),
                  loss_type="sparse_categorical_crossentropy", metrics=[])
        assert tel.enabled()
        tel.flush()
        evs = tel.read_events(tdir)
        assert any(e["name"] == "compile/compile_model" for e in evs)
    finally:
        tel.shutdown()


def test_resilience_flags_wired(devices):
    """The ISSUE-6 resilience knobs flow parse_args -> FFConfig, and —
    because they are added via FFConfig.build_parser only — the launcher's
    derived value-flag set covers every value-taking one automatically."""
    from flexflow_tpu.config import FFConfig as Cfg

    cfg = Cfg.parse_args([
        "--checkpoint-dir", "/tmp/ck", "--checkpoint-every-steps", "50",
        "--checkpoint-every-secs", "30.5", "--resume", "auto",
        "--keep-checkpoints", "5", "--retry-attempts", "4",
        "--retry-base-delay", "0.2", "--fault-plan",
        "dataloader/transfer@3*2"])
    assert cfg.checkpoint_dir == "/tmp/ck"
    assert cfg.checkpoint_every_steps == 50
    assert cfg.checkpoint_every_secs == 30.5
    assert cfg.resume == "auto"
    assert cfg.keep_checkpoints == 5
    assert cfg.retry_attempts == 4
    assert cfg.retry_base_delay == 0.2
    assert cfg.fault_plan == "dataloader/transfer@3*2"
    # resilience is fully off by default: fit carries zero extra work
    d = Cfg()
    assert (d.checkpoint_dir, d.resume, d.fault_plan) == ("", "", "")
    assert d.checkpoint_every_steps == 0 and d.checkpoint_every_secs == 0.0
    vf = Cfg.launcher_value_flags()
    for flag in ("--checkpoint-dir", "--checkpoint-every-steps",
                 "--checkpoint-every-secs", "--resume",
                 "--keep-checkpoints", "--retry-attempts",
                 "--retry-base-delay", "--fault-plan"):
        assert flag in vf, flag


def test_serving_flags_wired():
    """The ISSUE-10 serving knobs flow parse_args -> FFConfig via
    build_parser only (the launcher's value-flag set derives from it):
    --serve is a boolean gate, the rest consume a value token, and
    --serve-objective is constrained to the two _score objectives."""
    import pytest

    from flexflow_tpu.config import FFConfig as Cfg

    cfg = Cfg.parse_args(["--serve", "--max-decode-len", "64",
                          "--kv-page-size", "32", "--max-batch-slots", "16",
                          "--serve-objective", "throughput"])
    assert cfg.serve is True
    assert cfg.max_decode_len == 64
    assert cfg.kv_page_size == 32
    assert cfg.max_batch_slots == 16
    assert cfg.serve_objective == "throughput"
    d = Cfg()
    assert d.serve is False           # serving is an explicit opt-in
    assert d.max_decode_len == 0      # 0 = compile_serving's default
    assert d.kv_page_size == 16
    assert d.max_batch_slots == 8
    assert d.serve_objective == "latency"
    with pytest.raises(SystemExit):
        Cfg.parse_args(["--serve-objective", "goodput"])
    vf = Cfg.launcher_value_flags()
    for flag in ("--max-decode-len", "--kv-page-size",
                 "--max-batch-slots", "--serve-objective"):
        assert flag in vf, flag
    assert "--serve" not in vf        # the gate takes no value token


def test_serving_resilience_flags_wired():
    """The ISSUE-11 serving-under-fire knobs flow parse_args -> FFConfig
    via build_parser only: hot-swap watch root, TTFT-budget shedding,
    queue cap, and the decode watchdog. All default OFF — a scheduler
    built without them carries zero admission-control overhead."""
    from flexflow_tpu.config import FFConfig as Cfg

    cfg = Cfg.parse_args(["--serve-watch-dir", "/tmp/ckpts",
                          "--serve-ttft-budget-ms", "250.5",
                          "--serve-queue-cap", "32",
                          "--serve-decode-timeout-ms", "75.0"])
    assert cfg.serve_watch_dir == "/tmp/ckpts"
    assert cfg.serve_ttft_budget_ms == 250.5
    assert cfg.serve_queue_cap == 32
    assert cfg.serve_decode_timeout_ms == 75.0
    d = Cfg()
    assert d.serve_watch_dir == ""          # no watch -> no polling
    assert d.serve_ttft_budget_ms == 0.0    # 0 = shedding off
    assert d.serve_queue_cap == 0           # 0 = unbounded queue
    assert d.serve_decode_timeout_ms == 0.0  # 0 = watchdog off
    vf = Cfg.launcher_value_flags()
    for flag in ("--serve-watch-dir", "--serve-ttft-budget-ms",
                 "--serve-queue-cap", "--serve-decode-timeout-ms"):
        assert flag in vf, flag


def test_spec_kv_flags_wired():
    """The ISSUE-13 decode-throughput knobs flow parse_args -> FFConfig via
    build_parser only: draft-model JSON path, speculation depth, and the
    KV-cache dtype (constrained to the engine's supported set). All default
    OFF/auto — an engine built without them is byte-identical to before."""
    import pytest

    from flexflow_tpu.config import FFConfig as Cfg

    cfg = Cfg.parse_args(["--serve-draft-model", "/tmp/draft.json",
                          "--serve-spec-tokens", "4",
                          "--kv-cache-dtype", "int8"])
    assert cfg.serve_draft_model == "/tmp/draft.json"
    assert cfg.serve_spec_tokens == 4
    assert cfg.kv_cache_dtype == "int8"
    d = Cfg()
    assert d.serve_draft_model == ""     # no draft -> plain decode
    assert d.serve_spec_tokens == 0      # 0 = speculation off
    assert d.kv_cache_dtype == "auto"    # auto = follow compute dtype
    with pytest.raises(SystemExit):
        Cfg.parse_args(["--kv-cache-dtype", "fp4"])
    vf = Cfg.launcher_value_flags()
    for flag in ("--serve-draft-model", "--serve-spec-tokens",
                 "--kv-cache-dtype"):
        assert flag in vf, flag


def test_health_flags_wired():
    """The ISSUE-9 health knobs flow parse_args -> FFConfig via
    build_parser only (launcher value-flag set derives automatically):
    sentinels default ON (BooleanOptionalAction), halt opt-in, and the
    telemetry sink's size-based rotation cap generous by default."""
    from flexflow_tpu.config import FFConfig as Cfg

    cfg = Cfg.parse_args(["--telemetry-max-mb", "64",
                          "--no-health-sentinels", "--halt-on-nonfinite"])
    assert cfg.telemetry_max_mb == 64.0
    assert cfg.health_sentinels is False
    assert cfg.halt_on_nonfinite is True
    d = Cfg()
    assert d.telemetry_max_mb == 512.0  # generous: rotation rarely fires
    assert d.health_sentinels is True   # zero-sync checks ride the defaults
    assert d.halt_on_nonfinite is False  # halting is an explicit opt-in
    assert Cfg.parse_args(["--health-sentinels"]).health_sentinels is True
    # --telemetry-max-mb consumes a value token; the boolean gates don't
    vf = Cfg.launcher_value_flags()
    assert "--telemetry-max-mb" in vf
    assert "--halt-on-nonfinite" not in vf


def test_slo_reqtrace_flags_wired():
    """The ISSUE-15 observability knobs flow parse_args -> FFConfig via
    build_parser only: the SLO objective string (validated by parse_slo at
    construction, so a bad grammar fails loud at startup, not mid-serve)
    and the request-tracer gate (default ON, BooleanOptionalAction)."""
    import pytest

    from flexflow_tpu.config import FFConfig as Cfg

    cfg = Cfg.parse_args(["--serve-slo",
                          "ttft_p99_ms=25,per_token_p99_ms=10,"
                          "availability=0.999",
                          "--no-serve-reqtrace"])
    assert cfg.serve_slo == ("ttft_p99_ms=25,per_token_p99_ms=10,"
                             "availability=0.999")
    assert cfg.serve_reqtrace is False
    d = Cfg()
    assert d.serve_slo == ""          # no objectives -> tracker idles
    assert d.serve_reqtrace is True   # tracing is on by default (zero-sync)
    assert Cfg.parse_args(["--serve-reqtrace"]).serve_reqtrace is True
    with pytest.raises(ValueError):
        Cfg(serve_slo="ttft_p99_ms=nope")
    with pytest.raises(ValueError):
        Cfg(serve_slo="unknown_metric_p99_ms=5")
    # --serve-slo consumes a value token; the boolean gate doesn't
    vf = Cfg.launcher_value_flags()
    assert "--serve-slo" in vf
    assert "--serve-reqtrace" not in vf


def test_twin_trace_flags_wired():
    """The ISSUE-20 capacity-twin knobs flow parse_args -> FFConfig via
    build_parser only: live trace export (--serve-trace-out) and the
    twin CLI's replay inputs (--twin-trace/--twin-replicas/--twin-out).
    All default off — recording and replay are strictly opt-in."""
    from flexflow_tpu.config import FFConfig as Cfg

    cfg = Cfg.parse_args(["--serve-trace-out", "/tmp/live.jsonl",
                          "--twin-trace", "/tmp/replay.jsonl",
                          "--twin-replicas", "4",
                          "--twin-out", "/tmp/twin.json"])
    assert cfg.serve_trace_out == "/tmp/live.jsonl"
    assert cfg.twin_trace == "/tmp/replay.jsonl"
    assert cfg.twin_replicas == 4
    assert cfg.twin_out == "/tmp/twin.json"
    d = Cfg()
    assert d.serve_trace_out == ""   # no export unless asked
    assert d.twin_trace == ""
    assert d.twin_replicas == 0      # 0 = follow --serve-replicas
    assert d.twin_out == ""          # report to stdout
    # all four consume value tokens (launcher passthrough safety)
    vf = Cfg.launcher_value_flags()
    for flag in ("--serve-trace-out", "--twin-trace",
                 "--twin-replicas", "--twin-out"):
        assert flag in vf, flag


def test_fleet_flags_wired():
    """The ISSUE-18 fleet knobs flow parse_args -> FFConfig via
    build_parser only: replica count, colocated/disagg topology split,
    prefill-pool size, router policy (choices-validated), and the rolling
    rollout's rollback burn ceiling. All default to the single-replica
    colocated fleet — behaviorally identical to the pre-fleet scheduler."""
    import pytest

    from flexflow_tpu.config import FFConfig as Cfg

    cfg = Cfg.parse_args(["--serve-replicas", "4",
                          "--serve-fleet-topology", "disagg",
                          "--serve-prefill-replicas", "2",
                          "--serve-router", "round_robin",
                          "--serve-rollout-burn-max", "2.0"])
    assert cfg.serve_replicas == 4
    assert cfg.serve_fleet_topology == "disagg"
    assert cfg.serve_prefill_replicas == 2
    assert cfg.serve_router == "round_robin"
    assert cfg.serve_rollout_burn_max == 2.0
    d = Cfg()
    assert d.serve_replicas == 1                  # one replica = no fleet
    assert d.serve_fleet_topology == "colocated"  # every replica does both
    assert d.serve_prefill_replicas == 1
    assert d.serve_router == "least_loaded"
    assert d.serve_rollout_burn_max == 0.0        # 0 = never roll back
    with pytest.raises(SystemExit):
        Cfg.parse_args(["--serve-fleet-topology", "sharded"])
    with pytest.raises(SystemExit):
        Cfg.parse_args(["--serve-router", "random"])
    vf = Cfg.launcher_value_flags()
    for flag in ("--serve-replicas", "--serve-fleet-topology",
                 "--serve-prefill-replicas", "--serve-router",
                 "--serve-rollout-burn-max"):
        assert flag in vf, flag


def test_fault_plan_flag_arms_injector(devices):
    """--fault-plan reaches runtime/faults.py at compile time (the same
    hook order as --telemetry-dir): a bad plan fails loud at compile, a
    good one arms the named site."""
    from flexflow_tpu.runtime import faults

    try:
        m = _tiny(FFConfig(batch_size=16, only_data_parallel=True,
                           fault_plan="checkpoint/write@2",
                           log_level="warning"))
        m.compile(SGDOptimizer(lr=0.01),
                  loss_type="sparse_categorical_crossentropy", metrics=[])
        assert faults.active()
        with pytest.raises(ValueError, match="unknown fault site"):
            _tiny(FFConfig(batch_size=16, only_data_parallel=True,
                           fault_plan="bogus/site@1",
                           log_level="warning")).compile(
                SGDOptimizer(lr=0.01),
                loss_type="sparse_categorical_crossentropy", metrics=[])
    finally:
        faults.clear()


def test_multi_node_mesh_shards_batch_over_node_axis(devices):
    """--nodes must buy sample parallelism: the batch dim rides BOTH the
    node (DCN) axis and the intra-node data axis (round-4 review fix — a
    replicated node axis would make --nodes 2 a no-op)."""
    cfg = FFConfig(batch_size=16, num_nodes=2, workers_per_node=4,
                   only_data_parallel=True)
    m = _tiny(cfg)
    cm = m.compile(SGDOptimizer(lr=0.01),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
    dims = cm.strategy.input_shardings["x"]
    assert dims[0] in (("node", "data"), ["node", "data"]), dims
    pv = cm.parallel_view("fc")
    assert pv.dims[0].degree == 8  # 2 nodes x 4 workers all split samples
    cm.init(seed=0)
    out = cm.forward(np.zeros((16, 8), np.float32))
    assert np.asarray(out).shape == (16, 4)


def test_remat_and_fused_kernel_flags_wired():
    """The ISSUE-12 MFU knobs flow parse_args -> FFConfig via
    build_parser only (launcher value-flag coverage is derived):
    --remat-search/--remat-policies select the searched-remat dimension,
    and the deprecated --remat alias survives but cannot combine with the
    search."""
    from flexflow_tpu.config import FFConfig as Cfg

    cfg = Cfg.parse_args(["--remat-search", "--remat-policies",
                          "none,dots"])
    assert cfg.remat_search is True
    assert cfg.remat_policies == "none,dots"
    assert cfg.remat_policy_list() == ("none", "dots")
    # defaults: remat fully off
    d = Cfg()
    assert (d.remat, d.remat_search) == (False, False)
    assert d.remat_policy_list() == ("none", "dots", "full")
    # deprecated alias still parses on its own
    assert Cfg.parse_args(["--remat"]).remat is True
    # ...but contradicts the searched dimension, loudly
    with pytest.raises(ValueError, match="contradicts"):
        Cfg.parse_args(["--remat", "--remat-search"])
    # unknown policy names fail at construction, not deep in the DP
    with pytest.raises(ValueError, match="unknown remat policies"):
        Cfg.parse_args(["--remat-policies", "none,sometimes"])
    assert "--remat-policies" in Cfg.launcher_value_flags()


@pytest.mark.parametrize("argv", [["--simulator-mode", "learned"],
                                  ["--cost-model-path", "x"],
                                  ["--auto-refit"]])
def test_the_learned_tiers_flags_are_refused(argv, capsys):
    """PR 60 took the learned pricing tier out: argparse refuses the
    `--simulator-mode` value itself (exit 2), and its two flags are refused
    by name as every flag of ours that is gone (`parse_known_args` would
    take them for the user script's)."""
    with pytest.raises(SystemExit) as exc:
        FFConfig.parse_args(argv)
    # argparse exits 2 and names the flag on stderr; the table's refusal
    # is the exit's own message
    assert argv[0] in capsys.readouterr().err + str(exc.value.code)
    assert (exc.value.code == 2) == (argv[0] == "--simulator-mode")
    assert FFConfig.parse_args(["--simulator-mode", "taskgraph"]) \
        .simulator_mode == "taskgraph"


def test_the_default_strategy_cache_key_is_the_parents():
    """The key of a default configuration, taken at PR 59's tree (where the
    learned tier's fingerprint was appended only when that tier ran): a
    strategy stored before PR 60 is still found."""
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.search import strategy_cache as sc

    m = _tiny(FFConfig(batch_size=16))
    mach = MachineSpec(mesh_axes={"data": 2, "model": 2}, chip="v5e")
    assert sc.cache_key(m, mach, m.config) == \
        "0b38863d2a4520803bed194657dcc37b"
