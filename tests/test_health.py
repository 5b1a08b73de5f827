"""Run health (ISSUE 9 — flexflow_tpu/health.py): goodput wall-clock
bucket accounting on both fit loops (buckets + explicit residual tile the
measured wall), numerics sentinels (device-resident finite checks with
zero extra host syncs, fault-injected NaN → telemetry → halt with a
durable recovery checkpoint whose resume reproduces the clean
trajectory), HBM watermarks vs the memory model's prediction, size-based
telemetry rotation read transparently by every reader, the pipelined
loop's session-only resume windows, and the monitor CI smoke."""

import os
import sys
import time

import numpy as np
import pytest

from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, SGDOptimizer
from flexflow_tpu import health
from flexflow_tpu import telemetry as tel
from flexflow_tpu.losses import LossType
from flexflow_tpu.runtime import faults
from flexflow_tpu.runtime import resilience as rz

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _build(seed=5, kind="mlp", **cfg_kw):
    cfg = FFConfig(batch_size=16, only_data_parallel=True, seed=seed,
                   log_level="warning", mesh_shape={"data": 4, "model": 2},
                   **cfg_kw)
    m = FFModel(cfg)
    if kind == "gpt2":
        from flexflow_tpu.models import GPT2Config, build_gpt2

        build_gpt2(m, GPT2Config(vocab=512, seq=16, d_model=64, heads=2,
                                 layers=1, dropout=0.0), batch=16)
    else:
        x = m.create_tensor([16, 32], name="x")
        h = m.dense(x, 64, activation="relu", name="fc1")
        m.dense(h, 4, name="head")
    cm = m.compile(AdamOptimizer(alpha=0.01),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=0)
    return cm


def _data(n=96, kind="mlp"):
    rng = np.random.default_rng(0)
    if kind == "gpt2":
        ids = rng.integers(0, 512, size=(n, 16)).astype(np.int32)
        pos = np.broadcast_to(np.arange(16, dtype=np.int32), (n, 16)).copy()
        return [ids, pos], rng.integers(0, 512, size=(n, 16)).astype(np.int32)
    x = rng.normal(size=(n, 32)).astype(np.float32)
    y = rng.integers(0, 4, size=(n,)).astype(np.int32)
    return x, y


def _losses(hist):
    return [h["loss"] for h in hist]


# ------------------------------------------------------------ goodput meter
def test_goodput_meter_buckets_residual_and_bubble():
    """Pure accounting: add()ed buckets + the explicit residual tile the
    wall; goodput counts the productive buckets minus the bubble
    carve-out (derived from the dispatch bucket)."""
    gm = health.GoodputMeter()
    gm.add("dispatch", 0.8)
    gm.add("checkpoint", 0.1)
    rec = gm.epoch_end(1.0, epoch=0, bubble_frac=0.25)
    assert rec["buckets"]["dispatch"] == pytest.approx(0.8)
    assert rec["bubble_s"] == pytest.approx(0.2)  # 0.25 * dispatch
    assert rec["residual_s"] == pytest.approx(0.1)  # 1.0 - 0.9 accounted
    assert rec["accounted_frac"] == pytest.approx(0.9)
    assert rec["goodput"] == pytest.approx(0.6)  # (0.8 - 0.2) / 1.0
    # the lap cursor: intervals between laps land in the named bucket
    gm.tick()
    time.sleep(0.01)
    gm.lap("dispatch")
    rec2 = gm.epoch_end(0.05, epoch=1)
    assert rec2["buckets"]["dispatch"] >= 0.009
    assert rec2["buckets"]["checkpoint"] == 0.0  # reset between epochs
    rep = gm.report()
    assert rep["epochs"] == 2
    lines = health.format_goodput(rep)
    assert lines[0].startswith("[goodput]") and "residual" in lines[0]
    assert health.format_goodput({})[0].startswith("[goodput] no closed")


@pytest.mark.parametrize("kind", ["mlp", "gpt2"])
def test_goodput_accounts_fit_wall(devices, kind):
    """The acceptance bar on the flat loop: buckets account for >= 95% of
    the measured epoch wall, the residual is explicit, and goodput lands
    in history + the fit-level report."""
    cm = _build(kind=kind)
    x, y = _data(kind=kind)
    hist = cm.fit(x, y, epochs=2, verbose=False)
    assert all("goodput" in h for h in hist)
    assert all(0.0 <= h["goodput"] <= 1.0 for h in hist)
    rep = cm.goodput_report()
    assert rep["epochs"] == 2
    assert rep["accounted_frac"] >= 0.95
    wall = sum(h["epoch_time_s"] for h in hist)
    assert rep["wall_s"] == pytest.approx(wall, rel=1e-6)
    assert sum(rep["buckets"].values()) + rep["residual_s"] >= 0.95 * wall
    assert rep["buckets"]["dispatch"] > 0.0


@pytest.mark.parametrize("kind", ["mlp", "gpt2"])
def test_goodput_drops_under_heavy_checkpointing(devices, tmp_path, kind):
    """--checkpoint-every-steps 1 forces a durable snapshot per step; the
    lost time must land in the checkpoint bucket (not vanish into
    residual) and come off goodput, and the snapshots leave the losses of
    the unperturbed twin as they were. Every assertion reads ONE run's own
    buckets: two fits timed on a loaded CPU have no order (ROADMAP D19)."""
    x, y = _data(kind=kind)
    cm0 = _build(kind=kind)
    h0 = cm0.fit(x, y, epochs=2, verbose=False)
    base = cm0.goodput_report()
    cm1 = _build(kind=kind, checkpoint_dir=str(tmp_path / "ck"))
    h1 = cm1.fit(x, y, epochs=2, verbose=False, checkpoint_every_steps=1)
    heavy = cm1.goodput_report()
    np.testing.assert_allclose(_losses(h1), _losses(h0), rtol=1e-6)
    assert heavy["buckets"]["checkpoint"] > 0.0
    assert base["buckets"]["checkpoint"] == pytest.approx(0.0)
    ck_share = heavy["buckets"]["checkpoint"] / heavy["wall_s"]
    assert ck_share > 0.0
    # the snapshots' time is a bucket beside the residual, and no part of
    # what goodput counts as productive
    assert heavy["residual_s"] + heavy["buckets"]["checkpoint"] <= \
        heavy["wall_s"] * (1.0 + 1e-9)
    assert heavy["goodput"] <= 1.0 - ck_share + 1e-9
    assert heavy["accounted_frac"] >= 0.95


# ---------------------------------------------------------------- sentinels
def test_sentinel_state_detectors():
    """Pure host-side detectors: grad-norm spike vs the EMA, loss spike
    vs the previous window, NaN/Inf fatal."""
    st = health.SentinelState()
    assert st.observe(1, loss_mean=1.0, grad_norm=1.0) is None
    assert st.observe(2, loss_mean=1.1, grad_norm=50.0) is None  # warn only
    assert [e["kind"] for e in st.events] == ["grad_spike"]
    st2 = health.SentinelState()
    st2.observe(1, loss_mean=1.0, grad_norm=1.0)
    st2.observe(2, loss_mean=100.0, grad_norm=1.0)
    assert [e["kind"] for e in st2.events] == ["loss_spike"]
    st3 = health.SentinelState()
    assert st3.observe(3, loss_mean=float("nan"), grad_norm=1.0,
                       nonfinite=1.0) == "nonfinite"
    assert st3.observe(4, loss_mean=1.0,
                       grad_norm=float("nan")) == "nonfinite"
    s = st3.status()
    assert s["nonfinite_steps"] == 2 and s["grad_spikes"] == 0


def test_sentinel_metrics_device_flags(devices):
    import jax.numpy as jnp

    m = health.sentinel_metrics(jnp.float32(1.5), jnp.float32(2.0))
    assert float(m[health.NONFINITE_KEY]) == 0.0
    assert float(m[health.GRAD_NORM_KEY]) == pytest.approx(2.0)
    m2 = health.sentinel_metrics(jnp.float32(np.nan), jnp.float32(2.0))
    assert float(m2[health.NONFINITE_KEY]) == 1.0
    m3 = health.sentinel_metrics(jnp.float32(1.0), jnp.float32(np.inf))
    assert float(m3[health.NONFINITE_KEY]) == 1.0


def test_sentinels_on_keep_baseline_counters(devices):
    """Healthy-path overhead bar: with sentinels ON at the default
    sync_every the loop performs exactly the PR-2 baseline dispatch /
    host-sync counts (test_telemetry pins the same numbers), the
    reserved health/* keys never leak into user-facing history, and the
    loss trajectory matches a sentinels-OFF run."""
    def fit(**kw):
        cfg = FFConfig(batch_size=32, only_data_parallel=True,
                       log_level="warning", **kw)
        m = FFModel(cfg)
        x = m.create_tensor([32, 16], name="x")
        h = m.dense(x, 32, activation="relu", name="fc1")
        m.dense(h, 4, name="fc2")
        cm = m.compile(SGDOptimizer(lr=0.05),
                       LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                       metrics=[])
        cm.init(seed=0)
        rng = np.random.default_rng(0)
        xs = rng.normal(size=(256, 16)).astype(np.float32)
        ys = rng.integers(0, 4, size=(256,)).astype(np.int32)
        return cm, cm.fit(xs, ys, epochs=2, verbose=False)

    cm_on, h_on = fit()  # health_sentinels defaults ON
    assert cm_on.cfg.health_sentinels is True
    assert cm_on.step_stats == {"dispatches": 16, "host_syncs": 0,
                                "barriers": 0, "fused_steps": 0,
                                "epoch_end_syncs": 2}
    assert not any(k.startswith("health/") for e in h_on for k in e)
    assert cm_on._sentinels is not None
    assert cm_on._sentinels.state.status()["nonfinite_steps"] == 0
    cm_off, h_off = fit(health_sentinels=False)
    assert cm_off.step_stats == cm_on.step_stats
    assert cm_off._sentinels is None
    for eo, en in zip(h_off, h_on):
        assert en["loss"] == pytest.approx(eo["loss"], rel=1e-6)


def test_nan_inject_halts_with_durable_checkpoint_and_resumes(
        devices, tmp_path):
    """The ISSUE 9 acceptance path end-to-end: a fault-plan NaN poison
    (health/nonfinite site) trips the sentinel at the next sync, emits
    the health/nonfinite + health/halt telemetry events, and — under
    halt_on_nonfinite — raises NonFiniteError through the drain carrying
    the last DURABLE (pre-fault) checkpoint; resuming from it reproduces
    the uninterrupted run's loss trajectory."""
    x, y = _data(96)  # 6 steps/epoch
    ref = _losses(_build().fit(x, y, epochs=2, verbose=False))

    root = str(tmp_path / "ck")
    tdir = str(tmp_path / "tel")
    try:
        tel.configure(tdir)
        faults.configure("health/nonfinite@3")
        cm = _build(checkpoint_dir=root, halt_on_nonfinite=True)
        with pytest.raises(health.NonFiniteError) as ei:
            # sync_every=1: the sentinel window closes every step, so the
            # poison at step 3 halts before the step-4 durable snapshot
            # could capture NaN params (checkpoints land at steps 2,4,..)
            cm.fit(x, y, epochs=2, verbose=False, sync_every=1,
                   checkpoint_every_steps=2)
        assert ei.value.step == 3
        assert ei.value.checkpoint  # a durable recovery point exists
        assert ei.value.checkpoint == rz.latest_checkpoint(root)
        man = rz.load_manifest(ei.value.checkpoint)
        assert man["progress"]["epoch"] == 0
        assert man["progress"]["step_in_epoch"] == 2  # pre-fault
        tel.flush()
        evs = tel.read_events(tdir)
        names = [e["name"] for e in evs]
        assert "fault/injected" in names
        nf = [e for e in evs if e["name"] == "health/nonfinite"]
        assert nf and nf[0]["cat"] == "error"
        halt = [e for e in evs if e["name"] == "health/halt"]
        assert halt and halt[0]["args"]["checkpoint"] == ei.value.checkpoint
    finally:
        tel.shutdown()

    faults.clear()
    cm2 = _build(checkpoint_dir=root)
    h2 = cm2.fit(x, y, epochs=2, verbose=False, resume="auto")
    np.testing.assert_allclose(_losses(h2), ref, rtol=1e-6)


# ----------------------------------------------------------------- pipeline
def _pipe_build(**cfg_kw):
    cfg = FFConfig(batch_size=8, only_data_parallel=True, seed=3,
                   pipeline_stages=2, pipeline_schedule="1f1b",
                   accum_steps=2, log_level="warning", **cfg_kw)
    m = FFModel(cfg)
    t = m.create_tensor([8, 64], name="x")
    h = m.dense(t, 256, activation="gelu", name="up")
    h = m.dense(h, 64, name="down")
    h = m.dense(h, 128, activation="relu", name="mid")
    m.dense(h, 8, name="head")
    cm = m.compile(AdamOptimizer(alpha=0.01),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    cm.init(seed=0)
    return cm


def _pipe_data(n=96):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 64)).astype(np.float32)
    y = rng.integers(0, 8, size=(n,)).astype(np.int32)
    return x, y


def test_pipeline_goodput_sentinels_and_watermarks(devices):
    """The same health surface on the pipelined executor: goodput in
    history + >= 95% accounting, a clean sentinel state (per-stage
    grad-norm-sq accumulators checked at epoch end), and a watermark
    sample per epoch boundary with no under-prediction warning."""
    cm = _pipe_build()
    x, y = _pipe_data()
    hist = cm.fit([x], y, epochs=2, verbose=False)
    assert all("goodput" in h for h in hist)
    rep = cm.goodput_report()
    assert rep["epochs"] == 2 and rep["accounted_frac"] >= 0.95
    hr = cm.health_report()
    assert hr["sentinels"]["nonfinite_steps"] == 0
    assert hr["sentinels"]["grad_ema"] is not None  # detectors really fed
    wm = hr["watermarks"]
    assert wm["samples"] >= 3  # init + 2 epoch boundaries
    assert wm["ratio"] is not None and not wm["warn"]


def test_pipeline_nan_inject_trips_sentinel(devices, tmp_path):
    """health/nonfinite on the pipelined path: the stage-0 poison
    surfaces as a fatal epoch-end window; with halt_on_nonfinite the fit
    raises through the drain with a durable checkpoint. The sentinel
    window is the EPOCH here, so the fault (update 5) is placed after
    the only due periodic snapshot (update 4, every_steps=4) — that
    checkpoint is deterministically pre-fault and clean."""
    root = str(tmp_path / "ck")
    faults.configure("health/nonfinite@5")
    cm = _pipe_build(halt_on_nonfinite=True, checkpoint_dir=root)
    x, y = _pipe_data()  # 6 updates/epoch
    with pytest.raises(health.NonFiniteError) as ei:
        cm.fit([x], y, epochs=2, verbose=False, checkpoint_every_steps=4)
    assert ei.value.checkpoint  # durable pre-fault recovery point
    man = rz.load_manifest(ei.value.checkpoint)
    assert man["progress"]["epoch"] == 0
    assert man["progress"]["step_in_epoch"] == 4  # pre-fault
    assert cm._sentinel_state.nonfinite_steps == 1


def test_pipeline_resume_windows_count_session_steps_only(
        devices, tmp_path):
    """Satellite (c): on a resumed pipelined run the drift windows and
    samples/sec denominators count only THIS session's updates (the
    re-seeded pre-snapshot steps ran before this wall clock started)."""
    root = str(tmp_path / "ck")
    x, y = _pipe_data(96)  # 6 updates/epoch at batch 8 x M=2
    faults.configure("fit/dispatch@4!")  # permanent: escalates mid-epoch
    cm = _pipe_build(checkpoint_dir=root, retry_base_delay=0.001)
    with pytest.raises(faults.PermanentInjectedFault):
        cm.fit([x], y, epochs=2, verbose=False, checkpoint_every_steps=2)
    from flexflow_tpu.runtime import checkpoint as ck
    ck.wait_pending()  # the update-2 async snapshot commits off-thread
    man = rz.load_manifest(rz.latest_checkpoint(root))
    assert man["progress"] == {**man["progress"], "epoch": 0,
                               "step_in_epoch": 2}

    faults.clear()
    cm2 = _pipe_build(checkpoint_dir=root, retry_base_delay=0.001)
    h2 = cm2.fit([x], y, epochs=2, verbose=False, resume="auto")
    # epoch 0 resumed past 2 of its 6 updates -> 4 session updates;
    # epoch 1 ran in full
    assert [w[0] for w in cm2._drift_windows] == [4, 6]
    e0 = h2[0]
    session_samples = 4 * 2 * 8  # updates x M x batch
    assert e0["samples_per_sec"] == pytest.approx(
        session_samples / e0["epoch_time_s"], rel=1e-6)


# --------------------------------------------------------------- watermarks
def test_watermark_drift_and_tracker(devices):
    d = health.watermark_drift(300, 100)
    assert d["warn"] and d["ratio"] == pytest.approx(3.0)
    assert not health.watermark_drift(120, 100)["warn"]
    assert not health.watermark_drift(None, 100)["warn"]
    assert not health.watermark_drift(100, None)["warn"]

    cm = _build()
    x, y = _data()
    cm.fit(x, y, epochs=2, verbose=False)
    hr = cm.health_report()
    wm = hr["watermarks"]
    assert wm["samples"] >= 3  # init + 2 epoch boundaries
    # CPU fallback measures exactly the persistent trees: prediction in
    # the right ballpark, no drift warning on the honest config
    assert wm["peak_bytes"] and not wm["warn"]
    # an under-predicting memory model must warn (the OOM direction)
    under = cm._watermarks.report(max(1, wm["peak_bytes"] // 4))
    assert under["warn"]
    lines = health.format_health(None, under)
    assert any("WARNING" in ln for ln in lines)
    # and the healthy report renders without warning
    ok_lines = health.format_health(hr["sentinels"], wm)
    assert any(ln.startswith("[health] sentinels") for ln in ok_lines)
    assert not any("WARNING" in ln for ln in ok_lines)


# --------------------------------------------------------- rotation (tele)
def test_telemetry_rotation_and_readers(tmp_path):
    """Satellite (b): a small --telemetry-max-mb cap rotates the sink to
    numbered segments (no renames — concurrent readers never chase a
    moved file) and read_events / trace_report read the
    segment family transparently, ts-sorted."""
    tdir = str(tmp_path / "tele")
    try:
        tel.configure(tdir, max_mb=0.0005)  # ~524-byte segments
        for i in range(200):
            tel.event("rot/ev", cat="test", i=i)
        tel.flush()
        segs = sorted(f for f in os.listdir(tdir)
                      if f.startswith("telemetry-"))
        assert len(segs) > 2  # actually rotated
        assert any(".jsonl" == f[-6:] and f.count(".") == 2 for f in segs)
        evs = [e for e in tel.read_events(tdir) if e["name"] == "rot/ev"]
        assert [e["args"]["i"] for e in evs] == list(range(200))
        import trace_report
        assert len(trace_report.load_events(tdir)) >= 200
    finally:
        tel.shutdown()


def test_telemetry_unbounded_without_cap(tmp_path):
    tdir = str(tmp_path / "tele")
    try:
        tel.configure(tdir)  # no cap
        for i in range(500):
            tel.event("rot/ev", cat="test", i=i)
        tel.flush()
        segs = [f for f in os.listdir(tdir) if f.startswith("telemetry-")]
        assert len(segs) == 1  # never rotates uncapped
    finally:
        tel.shutdown()


# ------------------------------------------------------------- monitor tool
def test_monitor_gather_render_prom(tmp_path):
    """tools/monitor.py unit surface on a synthetic stream: goodput bar,
    sparkline, sentinel status, watermark lines, Prometheus export."""
    import monitor

    events = [
        {"name": "health/goodput", "ph": "i", "ts": 1.0,
         "args": {"epoch": 0, "wall_s": 2.0, "goodput": 0.8,
                  "residual_s": 0.05, "dispatch_s": 1.6,
                  "checkpoint_s": 0.3}},
        {"name": "fit/dispatch", "ph": "X", "ts": 2.0, "dur": 1500.0},
        {"name": "fit/dispatch", "ph": "X", "ts": 3.0, "dur": 2500.0},
        {"name": "health/nonfinite", "ph": "i", "ts": 4.0, "cat": "error",
         "args": {"step": 7, "grad_norm": None, "loss": None}},
        {"name": "health/halt", "ph": "i", "ts": 5.0, "cat": "error",
         "args": {"step": 7, "checkpoint": "/ck/step7"}},
        {"name": "health/hbm", "ph": "i", "ts": 6.0,
         "args": {"tag": "epoch0", "peak_bytes": 4 << 20,
                  "live_bytes": 3 << 20, "devices": 8}},
    ]
    state = monitor.gather(events)
    assert len(state["goodputs"]) == 1
    assert state["steps_ms"] == [1.5, 2.5]
    assert state["sentinels"]["nonfinite"] == 1
    assert len(state["halts"]) == 1 and state["errors"] == 2
    out = "\n".join(monitor.render(state))
    assert "80.0%" in out and "FATAL" in out and "epoch0" in out
    assert "/ck/step7" in out
    assert monitor.sparkline([]) == "(no steps yet)"
    prom = str(tmp_path / "ff.prom")
    monitor.prom_export(state, prom)
    with open(prom) as f:
        txt = f.read()
    assert "flexflow_goodput_ratio 0.8" in txt
    assert "flexflow_nonfinite_windows_total 1" in txt
    assert "flexflow_hbm_peak_bytes" in txt
    assert not os.path.exists(prom + ".tmp")  # atomic rename


def test_monitor_check_smoke(devices, capsys):
    import monitor

    assert monitor.main(["--check"]) == 0
    assert "CHECK PASS" in capsys.readouterr().out
