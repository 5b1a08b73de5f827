"""Elastic fault tolerance (ISSUE 6 — runtime/resilience.py +
runtime/faults.py): durable atomic-commit checkpoints and discovery,
per-site deterministic fault injection (transient → recovered within the
retry budget with telemetry `retry` events; permanent → clean escalation),
corrupt-newest-snapshot fallback, SIGTERM drain + resume="auto" trajectory
parity on the same AND a resized mesh, elastic pipeline stage-count
restore, CheckpointMismatchError, wait_pending timeout / exit-drain
reporting, and a real SIGKILL mid-epoch resumed in a fresh process."""

import json
import os
import shutil
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, SGDOptimizer
from flexflow_tpu import telemetry as tel
from flexflow_tpu.runtime import checkpoint as ck
from flexflow_tpu.runtime import faults
from flexflow_tpu.runtime import resilience as rz


@pytest.fixture(autouse=True)
def _clean_faults():
    """The fault plan is process-global (like telemetry): never leak an
    armed plan into the next test."""
    faults.clear()
    yield
    faults.clear()


def _build(mesh=None, width=64, opt=None, seed=5, **cfg_kw):
    cfg = FFConfig(batch_size=16, only_data_parallel=True, seed=seed,
                   log_level="warning",
                   mesh_shape=mesh or {"data": 4, "model": 2}, **cfg_kw)
    m = FFModel(cfg)
    x = m.create_tensor([16, 32], name="x")
    h = m.dense(x, width, activation="relu", name="fc1")
    m.dense(h, 4, name="head")
    cm = m.compile(opt or AdamOptimizer(alpha=0.01),
                   loss_type="sparse_categorical_crossentropy", metrics=[])
    cm.init(seed=0)
    return cm


def _data(n=64):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 32)).astype(np.float32)
    y = rng.integers(0, 4, size=(n,)).astype(np.int32)
    return x, y


def _losses(hist):
    return [h["loss"] for h in hist]


# ------------------------------------------------------------- plan grammar
def test_fault_plan_grammar():
    specs = faults.parse_plan(
        "dataloader/transfer@3, checkpoint/write@1*2 ,fit/dispatch@5!")
    assert [(s.site, s.at, s.times, s.permanent) for s in specs] == [
        ("dataloader/transfer", 3, 1, False),
        ("checkpoint/write", 1, 2, False),
        ("fit/dispatch", 5, 1, True)]
    with pytest.raises(ValueError, match="unknown fault site"):
        faults.parse_plan("no/such_site@1")
    with pytest.raises(ValueError, match="bad fault spec"):
        faults.parse_plan("dataloader/transfer@")
    assert faults.parse_plan("") == []


def test_check_rejects_unknown_site():
    with pytest.raises(ValueError, match="unknown fault site"):
        faults.check("typo/site")


# --------------------------------------------------------- retry mechanics
def test_run_resilient_transient_recovers_with_retry_events(tmp_path):
    tdir = str(tmp_path / "tel")
    try:
        tel.configure(tdir)
        faults.configure("checkpoint/write@1*2")
        pol = rz.RetryPolicy(attempts=3, base_delay=0.001, seed=0)
        calls = []
        out = rz.run_resilient("checkpoint/write", lambda: calls.append(1)
                               or "ok", pol)
        assert out == "ok" and len(calls) == 1  # fn ran once, AFTER recovery
        assert faults.fired() == {"checkpoint/write": 2}
        tel.flush()
        evs = tel.read_events(tdir)
        retries = [e for e in evs if e.get("cat") == "retry"]
        assert len(retries) == 2
        assert all(e["args"]["site"] == "checkpoint/write" for e in retries)
        assert [e["args"]["attempt"] for e in retries] == [1, 2]
    finally:
        tel.shutdown()


def test_run_resilient_permanent_escalates(tmp_path):
    tdir = str(tmp_path / "tel")
    try:
        tel.configure(tdir)
        faults.configure("distributed/init@1!")
        pol = rz.RetryPolicy(attempts=2, base_delay=0.001, seed=0)
        with pytest.raises(faults.PermanentInjectedFault):
            rz.run_resilient("distributed/init", lambda: "never", pol)
        assert faults.fired()["distributed/init"] == 2  # full budget burned
        tel.flush()
        errs = [e for e in tel.read_events(tdir) if e.get("cat") == "error"]
        assert any(e["name"] == "retry/exhausted" and
                   e["args"]["site"] == "distributed/init" for e in errs)
    finally:
        tel.shutdown()


def test_retry_attempts_do_not_shift_fault_indices():
    """Retries of one operation re-check the SAME fault index, so a
    second spec on the same site fires at the N-th REAL operation — not
    shifted by however many retry attempts earlier faults consumed."""
    faults.configure("checkpoint/write@1,checkpoint/write@3")
    pol = rz.RetryPolicy(attempts=3, base_delay=0.001, seed=0)
    for _ in range(4):  # 4 real operations, all recover
        rz.run_resilient("checkpoint/write", lambda: None, pol)
    assert faults.counts()["checkpoint/write"] == 4  # operations, not attempts
    assert faults.fired()["checkpoint/write"] == 2   # fired at ops 1 and 3


def test_retry_policy_backoff_is_seeded_and_bounded():
    p1 = rz.RetryPolicy(attempts=5, base_delay=0.05, max_delay=0.2, seed=7)
    p2 = rz.RetryPolicy(attempts=5, base_delay=0.05, max_delay=0.2, seed=7)
    d1 = [p1.delay(a) for a in range(1, 6)]
    assert d1 == [p2.delay(a) for a in range(1, 6)]  # deterministic
    assert all(0.0 <= d <= 0.2 * 1.25 for d in d1)   # max_delay * jitter cap


def test_distributed_init_site_is_wired():
    """init_distributed runs under the distributed/init site: a permanent
    armed fault escalates BEFORE jax.distributed.initialize is ever
    reached (which would hang in-process)."""
    from flexflow_tpu.runtime.distributed import init_distributed

    faults.configure("distributed/init@1!")
    pol = rz.RetryPolicy(attempts=2, base_delay=0.001)
    with pytest.raises(faults.PermanentInjectedFault):
        init_distributed(coordinator_address="127.0.0.1:1",
                         num_processes=1, process_id=0, retry_policy=pol)
    assert faults.fired()["distributed/init"] == 2


# --------------------------------------------- per-site recovery inside fit
@pytest.mark.parametrize("plan", [
    "dataloader/transfer@2*2",   # transient transfer failures, step 2
    "fit/dispatch@3",            # one dispatch admission failure, step 3
    "checkpoint/write@1",        # first checkpoint write attempt fails
    # all three armed in one run
    "dataloader/transfer@2*2,fit/dispatch@3,checkpoint/write@1",
])
def test_fit_recovers_injected_transient_faults(devices, tmp_path, plan):
    """Each instrumented fit-path site, armed transiently, must be
    recovered by retry/backoff with the loss trajectory untouched
    (injected faults fire BEFORE any state mutation)."""
    x, y = _data()
    ref = _losses(_build().fit(x, y, epochs=2, verbose=False))

    cm = _build(fault_plan=plan, retry_base_delay=0.001,
                checkpoint_dir=str(tmp_path / "ck"),
                checkpoint_every_steps=3)
    hist = cm.fit(x, y, epochs=2, verbose=False)
    cm.wait_checkpoints()
    for spec in plan.split(","):
        site = spec.split("@")[0]
        assert faults.fired().get(site, 0) >= 1, f"{site} never fired"
    np.testing.assert_allclose(_losses(hist), ref, rtol=1e-7)


def test_fit_dispatch_fault_fires_inside_fused_dispatch(devices):
    """The faults.py contract: "fail step 3" is fit/dispatch@3 regardless
    of how steps batch into dispatches — a K-fused dispatch must run the
    admission check for EVERY global step it covers, not just its first."""
    x, y = _data()  # 4 steps/epoch at batch 16 -> one fused dispatch at K=4
    ref = _losses(_build(steps_per_dispatch=4).fit(x, y, epochs=2,
                                                   verbose=False))
    cm = _build(steps_per_dispatch=4, fault_plan="fit/dispatch@3",
                retry_base_delay=0.001)
    hist = cm.fit(x, y, epochs=2, verbose=False)
    assert faults.fired().get("fit/dispatch", 0) == 1, \
        "mid-dispatch step never reached the fault site"
    np.testing.assert_allclose(_losses(hist), ref, rtol=1e-7)


def test_fit_permanent_fault_escalates_cleanly(devices):
    """A permanent fault outlasts the retry budget and surfaces to the
    fit caller as the injected error (prefetch workers forward it),
    not a hang or a silent skip."""
    x, y = _data()
    cm = _build(fault_plan="dataloader/transfer@2!", retry_attempts=2,
                retry_base_delay=0.001)
    with pytest.raises(faults.PermanentInjectedFault):
        cm.fit(x, y, epochs=1, verbose=False)


@pytest.mark.parametrize("plan,site", [
    ("pipe/boundary_hop@3*2", "pipe/boundary_hop"),
    ("dataloader/transfer@2*2", "dataloader/transfer"),  # stage-0 input put
    ("fit/dispatch@2", "fit/dispatch"),  # update admission, global step 2
])
def test_pipeline_boundary_hop_fault_recovery(devices, plan, site):
    """Every fit-path fault site must be LIVE on the pipelined path too
    (an armed plan that never reaches its site would green-light a broken
    recovery path): transient faults at the stage-boundary hop, the
    stage-0 microbatch input transfer, and the update admission all
    recover with the pipelined trajectory untouched."""
    def run(**kw):
        cfg = FFConfig(batch_size=8, only_data_parallel=True, seed=3,
                       pipeline_stages=2, accum_steps=4,
                       log_level="warning", **kw)
        m = FFModel(cfg)
        t = m.create_tensor([8, 64], name="x")
        h = m.dense(t, 128, activation="gelu", name="up")
        h = m.dense(h, 64, name="down")
        m.dense(h, 8, name="head")
        cm = m.compile(SGDOptimizer(lr=0.05),
                       loss_type="sparse_categorical_crossentropy",
                       metrics=[])
        cm.init(seed=0)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 64)).astype(np.float32)
        y = rng.integers(0, 8, size=(64,)).astype(np.int32)
        return _losses(cm.fit(x, y, epochs=2, verbose=False))

    ref = run()
    faults.clear()
    injected = run(fault_plan=plan, retry_base_delay=0.001)
    assert faults.fired().get(site, 0) >= 1, f"{site} never fired"
    np.testing.assert_allclose(injected, ref, rtol=1e-7)


# ------------------------------------------------ durable commit + discovery
def test_durable_commit_discovery_skips_uncommitted(devices, tmp_path):
    root = str(tmp_path / "ck")
    cm = _build()
    x, y = _data()
    cm.fit(x, y, epochs=1, verbose=False)
    p1 = rz.save_durable(cm, root, {"epoch": 1}, block=True)
    cm.fit(x, y, epochs=1, verbose=False)
    p2 = rz.save_durable(cm, root, {"epoch": 2}, block=True)
    assert os.path.basename(p1) == "ckpt-0000000004"
    assert rz.latest_checkpoint(root) == p2
    snaps = rz.committed_snapshots(root)
    assert [s for s, _, _ in snaps] == [4, 8]
    assert all(m["committed"] for _, _, m in snaps)

    # a torn write (SIGKILLed writer): .tmp- dirs are never discovered,
    # and clean_stale_tmp removes them
    os.makedirs(os.path.join(root, ".tmp-0000000012-dead"))
    # a fake "newer" dir without a valid manifest is skipped too
    fake = os.path.join(root, "ckpt-0000000099")
    os.makedirs(fake)
    with open(os.path.join(fake, rz.MANIFEST), "w") as f:
        f.write("{ torn json")
    assert rz.latest_checkpoint(root) == p2
    rz.clean_stale_tmp(root)
    assert not [n for n in os.listdir(root) if n.startswith(".tmp-")]

    # a structurally complete dir whose manifest carries a garbled step
    # (valid JSON, non-integer) is skipped as corrupt — it must not crash
    # discovery for the whole root
    bad = os.path.join(root, "ckpt-0000000777")
    os.makedirs(os.path.join(bad, "tree"))
    open(os.path.join(bad, "meta.json"), "w").write("{}")
    with open(os.path.join(bad, rz.MANIFEST), "w") as f:
        json.dump({"committed": True, "step": "7a"}, f)
    assert rz.load_manifest(bad) is None
    assert rz.latest_checkpoint(root) == p2


def test_corrupt_newest_snapshot_falls_back(devices, tmp_path):
    """resume="auto" with a committed-but-corrupt newest snapshot (torn
    orbax payload) falls back to the previous durable one instead of
    crashing — the ISSUE 6 acceptance case."""
    root = str(tmp_path / "ck")
    x, y = _data()
    cm = _build()
    cm.fit(x, y, epochs=1, verbose=False)
    good = rz.save_durable(cm, root, {"epoch": 1, "step_in_epoch": 0,
                                      "history": []}, block=True)
    w_good = np.asarray(cm.get_weight("fc1")).copy()
    cm.fit(x, y, epochs=1, verbose=False)
    newest = rz.save_durable(cm, root, {"epoch": 2, "step_in_epoch": 0,
                                        "history": []}, block=True)
    # corrupt the newest payload but leave its manifest committed
    shutil.rmtree(os.path.join(newest, "tree"))
    os.makedirs(os.path.join(newest, "tree"))  # structurally present, empty

    cm2 = _build()
    prog = rz.restore_auto(cm2, "auto", root)
    assert prog is not None and prog.get("epoch") == 1
    assert cm2._iteration == 4
    np.testing.assert_array_equal(np.asarray(cm2.get_weight("fc1")), w_good)
    assert rz.latest_checkpoint(root) == newest  # discovery alone keeps it


def test_restore_auto_empty_root_is_fresh_start(devices, tmp_path):
    cm = _build()
    assert rz.restore_auto(cm, "auto", str(tmp_path / "nothing")) is None
    with pytest.raises(FileNotFoundError):
        rz.restore_auto(cm, str(tmp_path / "nope"), "")


# ------------------------------------------- preemption drain + auto-resume
class _KillAt:
    """Send SIGTERM to ourselves after `n` optimizer steps (a per-batch
    callback also pins fit to per-step dispatch, so the drain point is
    deterministic)."""

    def __init__(self, n):
        self.n = n

    def on_batch_end(self, it, logs):
        self.n -= 1
        if self.n == 0:
            os.kill(os.getpid(), signal.SIGTERM)


def test_sigterm_drain_and_resume_same_and_resized_mesh(devices, tmp_path):
    """The full preemption story in-process: SIGTERM mid-epoch → drain +
    final coordinated snapshot + clean Preempted exit; relaunch with
    resume="auto" finishes on the uninterrupted trajectory — on the SAME
    mesh and on a RESIZED mesh ({data:4,model:2} → {data:2,model:4},
    elastic cross-mesh restore)."""
    x, y = _data(96)  # 6 steps/epoch: the kill at step 3 is mid-epoch
    ref = _losses(_build().fit(x, y, epochs=2, verbose=False))

    root = str(tmp_path / "ck")
    cm = _build(checkpoint_dir=root)
    with pytest.raises(rz.Preempted) as ei:
        cm.fit(x, y, epochs=2, verbose=False, callbacks=[_KillAt(3)])
    assert ei.value.code == 0  # SystemExit(0): clean preemption contract
    assert ei.value.checkpoint_path == rz.latest_checkpoint(root)
    man = rz.load_manifest(ei.value.checkpoint_path)
    assert man["progress"]["epoch"] == 0
    assert 0 < man["progress"]["step_in_epoch"] < 6  # genuinely mid-epoch

    resized_root = str(tmp_path / "ck_resized")
    shutil.copytree(root, resized_root)

    cm2 = _build(checkpoint_dir=root)
    h2 = cm2.fit(x, y, epochs=2, verbose=False, resume="auto")
    np.testing.assert_allclose(_losses(h2), ref, rtol=1e-6)

    cm3 = _build(mesh={"data": 2, "model": 4}, checkpoint_dir=resized_root)
    h3 = cm3.fit(x, y, epochs=2, verbose=False, resume="auto")
    np.testing.assert_allclose(_losses(h3), ref, rtol=1e-5)


def test_resume_rejects_trajectory_defining_config_change(devices, tmp_path):
    """seed / batch_size / accum_steps define what the manifest's progress
    counters MEAN: resuming under different values would silently skip or
    duplicate samples, so restore_auto fails loud (the mesh may change —
    that is the elastic part)."""
    root = str(tmp_path / "ck")
    x, y = _data()
    cm = _build()
    cm.fit(x, y, epochs=1, verbose=False)
    rz.save_durable(cm, root, {"epoch": 1}, block=True)
    other = _build(seed=6)
    with pytest.raises(ValueError, match="seed"):
        rz.restore_auto(other, "auto", root)


def test_second_signal_escalates_past_wedged_drain(devices):
    """First SIGINT defers to the drain poll; a second one (the drain is
    stuck — wedged prefetch, hung collective) restores the previous
    disposition and acts immediately, so Ctrl-C Ctrl-C still interrupts."""
    g = rz.PreemptionGuard().install()
    try:
        signal.raise_signal(signal.SIGINT)
        assert g.requested and g.signum == signal.SIGINT  # deferred
        with pytest.raises(KeyboardInterrupt):
            signal.raise_signal(signal.SIGINT)
        assert not g._installed  # disposition handed back
    finally:
        g.uninstall()


def test_resume_only_does_not_convert_signals(devices):
    """Resilience active for resume only (no checkpoint root): signals
    keep their default behavior — a converted SIGTERM would exit 0 with
    NOTHING saved, masking lost progress as success."""
    cm = _build()
    res = rz.FitResilience.build(cm, resume="auto", checkpoint_dir="")
    assert res is not None and not res.root
    prev = signal.getsignal(signal.SIGTERM)
    res.install_guard()
    try:
        assert signal.getsignal(signal.SIGTERM) is prev
        assert not res.guard._installed
    finally:
        res.guard.uninstall()


def test_resume_after_completed_fit_returns_history(devices, tmp_path):
    """The end-of-fit snapshot records epoch==epochs: a relaunch of a
    FINISHED run returns the stored history instead of retraining."""
    root = str(tmp_path / "ck")
    x, y = _data()
    cm = _build(checkpoint_dir=root)
    h1 = cm.fit(x, y, epochs=2, verbose=False)
    cm.wait_checkpoints()
    cm2 = _build(checkpoint_dir=root)
    w = np.asarray(cm2.get_weight("fc1")).copy()
    h2 = cm2.fit(x, y, epochs=2, verbose=False, resume="auto")
    np.testing.assert_allclose(_losses(h2), _losses(h1), rtol=1e-7)
    assert not np.array_equal(np.asarray(cm2.get_weight("fc1")), w)
    assert cm2._iteration == 8  # restored, not retrained past the end


def test_dataloader_cursor_advance_epochs(devices):
    from flexflow_tpu.runtime.dataloader import SingleDataLoader

    x, y = _data(32)
    a = SingleDataLoader([x], y, 16, shuffle=True, seed=9)
    for _ in range(2):  # consume two epochs' permutations
        list(a.epoch())
    b = SingleDataLoader([x], y, 16, shuffle=True, seed=9)
    b.advance_epochs(2)
    for (xs1, y1), (xs2, y2) in zip(a.epoch(), b.epoch()):
        np.testing.assert_array_equal(y1, y2)
        np.testing.assert_array_equal(xs1[0], xs2[0])


# ----------------------------------------------- elastic pipeline stage count
def test_pipeline_elastic_stage_count_restore(devices, tmp_path):
    """A pipeline snapshot saved at S=2 restores onto S=4 (different cuts,
    different per-stage opt-state partition): the per-layer checkpoint
    schema makes stage ownership a placement detail. The continued
    trajectory matches the S=2 continuation to reassociation tolerance."""
    def build(stages):
        cfg = FFConfig(batch_size=8, only_data_parallel=True, seed=3,
                       pipeline_stages=stages, accum_steps=4,
                       log_level="warning")
        m = FFModel(cfg)
        t = m.create_tensor([8, 64], name="x")
        h = m.dense(t, 128, activation="gelu", name="up")
        h = m.dense(h, 64, name="down")
        h = m.dense(h, 128, activation="relu", name="mid")
        m.dense(h, 8, name="head")
        cm = m.compile(SGDOptimizer(lr=0.05),
                       loss_type="sparse_categorical_crossentropy",
                       metrics=[])
        cm.init(seed=0)
        return cm

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 64)).astype(np.float32)
    y = rng.integers(0, 8, size=(64,)).astype(np.int32)

    pm2 = build(2)
    pm2.fit(x, y, epochs=1, verbose=False)
    ckpt = str(tmp_path / "pipe_ck")
    pm2.save_checkpoint(ckpt, block=True)
    it_at_ck = pm2._iteration
    w_at_ck = {ln: {w: np.asarray(v).copy() for w, v in sub.items()}
               for ln, sub in pm2.merged_params().items()}
    ref = _losses(pm2.fit(x, y, epochs=1, verbose=False))

    pm4 = build(4)
    assert pm4.num_stages == 4 and pm4.cuts != pm2.cuts
    pm4.load_checkpoint(ckpt)
    assert pm4._iteration == it_at_ck
    restored = pm4.merged_params()
    for ln, sub in w_at_ck.items():
        for wname, wval in sub.items():
            np.testing.assert_array_equal(np.asarray(restored[ln][wname]),
                                          wval)
    got = _losses(pm4.fit(x, y, epochs=1, verbose=False))
    np.testing.assert_allclose(got, ref, rtol=1e-5)


# -------------------------------------------------- checkpoint mismatch error
def test_checkpoint_mismatch_lists_differences(devices, tmp_path):
    x, y = _data()
    cm = _build(width=64)
    cm.fit(x, y, epochs=1, verbose=False)
    path = str(tmp_path / "ck")
    cm.save_checkpoint(path, block=True)

    other = _build(width=48)  # same layer names, different schema
    with pytest.raises(ck.CheckpointMismatchError) as ei:
        other.load_checkpoint(path)
    msg = str(ei.value)
    assert "fc1" in msg and "weight schema" in msg

    sgd = _build(width=64, opt=SGDOptimizer(lr=0.01))
    with pytest.raises(ck.CheckpointMismatchError) as ei:
        sgd.load_checkpoint(path)
    assert "optimizer" in str(ei.value)
    # the matching model still restores fine
    ok = _build(width=64)
    ok.load_checkpoint(path)
    assert ok._iteration == 4


# ------------------------------------------------- wait_pending / exit drain
def test_wait_pending_timeout_on_wedged_writer(devices, tmp_path):
    h = ck._AsyncSave(str(tmp_path / "wedged"))
    release = {"t": time.monotonic() + 2.0}
    with ck._PENDING_LOCK:
        ck._PENDING[h.path] = h
    h.start(lambda: time.sleep(max(0.0, release["t"] - time.monotonic())))
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        ck.wait_pending(timeout=0.2)
    assert time.monotonic() - t0 < 1.5  # bounded, did not ride out the write
    h.result()  # writer finishes; registry drains clean


def test_exit_drain_reports_failed_writes(devices, tmp_path, capsys):
    """A write that fails during interpreter shutdown must not vanish:
    _wait_pending_at_exit re-raises nothing but REPORTS every failed
    write (satellite: the old drain swallowed them silently)."""
    for i in range(2):
        h = ck._AsyncSave(str(tmp_path / f"boom{i}"))
        with ck._PENDING_LOCK:
            ck._PENDING[h.path] = h
        h.start(lambda: (_ for _ in ()).throw(OSError("disk gone")))
    deadline = time.monotonic() + 5
    while len(ck.failed_writes()) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    ck._wait_pending_at_exit()  # must not raise
    out = capsys.readouterr().out
    assert "FAILED" in out and "disk gone" in out
    # reported once: the registry is consumed by the report
    with ck._PENDING_LOCK:
        ck._FAILED.clear()
        ck._PENDING.clear()


# ------------------------------------------- a process that dies unwarned
def _worker(*args):
    """Start tests/_resilience_worker.py as a process of its own."""
    return subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(__file__),
                                      "_resilience_worker.py"), *args],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def _history(out):
    for line in reversed(out.splitlines()):
        if line.startswith("HISTORY "):
            return json.loads(line[len("HISTORY "):])
    return None


@pytest.fixture(scope="module")
def sigkilled(devices, tmp_path_factory):
    """The uninterrupted trajectory (run here, no checkpoints), and a
    checkpointing run of the same model in a process that is SIGKILLed
    mid-epoch: no handler runs, no drain, no final snapshot. The child
    parks itself after step 13 (epoch 1, step 5 of 8) and the kill waits
    for that and for a committed snapshot: nothing is paced by a clock."""
    import _resilience_worker as w

    ref = w.fit(w.build())
    work = tmp_path_factory.mktemp("sigkill")
    root, parked = str(work / "ck"), str(work / "parked")
    proc = _worker(f"ckpt_dir={root}", "park_after=13",
                   f"park_file={parked}")
    try:
        deadline = time.monotonic() + 240.0
        while not (os.path.exists(parked) and rz.committed_snapshots(root)) \
                and proc.poll() is None and time.monotonic() < deadline:
            time.sleep(0.02)
        alive = proc.poll() is None
    finally:
        proc.kill()
    out, _ = proc.communicate(timeout=60)
    return {"ref": ref, "root": root, "alive_at_kill": alive,
            "returncode": proc.returncode, "out": out}


def test_sigkill_leaves_a_committed_snapshot(sigkilled):
    """The killed process was mid-run (alive, no history printed, dead by
    signal 9) and what it left behind is discoverable: a committed
    snapshot from before the kill, mid-trajectory."""
    k = sigkilled
    assert k["alive_at_kill"], k["out"][-2000:]
    assert k["returncode"] == -signal.SIGKILL
    assert _history(k["out"]) is None
    snaps = rz.committed_snapshots(k["root"])
    assert snaps and all(m["committed"] for _, _, m in snaps)
    assert 0 < snaps[-1][0] <= 13          # parked after 13 of 24 steps


@pytest.mark.parametrize("mesh", ["", "data=4,model=2"],
                         ids=["same_mesh", "resized_mesh"])
def test_sigkill_resume_matches_uninterrupted(sigkilled, tmp_path, mesh):
    """resume="auto" in a fresh process, on the mesh that died and on
    another one (the snapshot re-shards), finishes on the trajectory of
    the run that was never interrupted."""
    root = str(tmp_path / "ck")
    shutil.copytree(sigkilled["root"], root)
    proc = _worker(f"ckpt_dir={root}", "resume=auto", f"mesh={mesh}")
    out, _ = proc.communicate(timeout=240)
    assert proc.returncode == 0, out[-2000:]
    np.testing.assert_allclose(_history(out), sigkilled["ref"], rtol=1e-5,
                               atol=1e-7)
