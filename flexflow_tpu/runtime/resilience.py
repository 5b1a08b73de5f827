"""Elastic fault tolerance: durable checkpoints, preemption, retries.

Reference gap (ISSUE 6): the reference rides Legion's resilient task
runtime — a preempted worker re-executes its tasks from the mapper's
recorded state. The JAX rebuild gets the equivalent from four explicit
pieces, built on the PR 2-5 ingredients (async copy-then-write
checkpointing, cross-mesh resharding restore, telemetry, MPMD stages):

  * Durable checkpoints — an atomic commit protocol. `save_durable` writes
    the full training state into a hidden temp dir (the existing orbax
    save), then COMMITS: MANIFEST.json (step + model fingerprint + mesh +
    training progress) fsync'd into the temp dir, one `os.replace` rename
    into `ckpt-<step>`, parent-dir fsync. A reader can never observe a
    half-written snapshot: either the rename happened (manifest present,
    write complete) or the dir is still `.tmp-*` and discovery ignores it.
    Composes with the async writer — the commit runs at the END of the
    writer thread's serialization, so the step loop still only pays the
    device->host snapshot.

  * Preemption-safe shutdown — `PreemptionGuard` converts SIGTERM/SIGINT
    into a flag the fit loop polls per dispatch: drain in-flight work,
    take a final durable snapshot, raise `Preempted` (a SystemExit with
    code 0 — an unhandled preemption exits CLEANLY, the contract a
    preempting scheduler expects).

  * Auto-resume — `restore_auto` finds the newest COMMITTED snapshot
    (skipping uncommitted/corrupt ones, falling back to older snapshots
    when the newest fails to load), restores params/opt/rng-iteration and
    the manifest's training progress (epoch, step-in-epoch, metric sums,
    history) so `fit(resume="auto")` continues the identical trajectory.
    Elastic: the restore targets carry the RELAUNCH mesh's shardings, so
    a checkpoint saved under {data:4} resumes onto {data:2,model:2} (or a
    different pipeline stage partition) via the PR 3/4 cross-mesh restore.

  * Retries — `run_resilient(site, fn)`: bounded attempts, exponential
    backoff with jitter from a seeded rng (deterministic tests), telemetry
    `retry` events, escalation after the budget. Wrapped around dataloader
    prefetch transfers, checkpoint writes, jax.distributed init and the
    pipeline boundary hop; each callsite doubles as a fault-injection
    site (runtime/faults.py), so every recovery path here is exercised
    deterministically by tests/test_resilience.py.
"""

from __future__ import annotations

import json
import logging
import os
import shutil
import signal
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from flexflow_tpu import telemetry as tel
from flexflow_tpu.runtime import faults

MANIFEST = "MANIFEST.json"
_LOG = logging.getLogger("flexflow_tpu")


class Preempted(SystemExit):
    """Raised by fit after a preemption signal has been drained and the
    final durable snapshot committed. Subclasses SystemExit with code 0:
    an unhandled preemption exits the process CLEANLY (the relaunch picks
    up from the snapshot via resume="auto")."""

    def __init__(self, signum: int, checkpoint_path: Optional[str] = None):
        super().__init__(0)
        self.signum = signum
        self.checkpoint_path = checkpoint_path

    def __str__(self) -> str:
        return (f"training preempted by signal {self.signum}; final "
                f"snapshot: {self.checkpoint_path or '<none>'}")


# ------------------------------------------------------------------- retries
@dataclass
class RetryPolicy:
    """Bounded exponential backoff with jitter. `attempts` counts TOTAL
    tries; the jitter rng is seeded (the run's seed) so fault-injection
    tests replay the exact same schedule."""

    attempts: int = 3
    base_delay: float = 0.05
    max_delay: float = 2.0
    jitter: float = 0.25
    seed: int = 0
    # plausibly-transient failures only: XlaRuntimeError (collective
    # hiccups) and InjectedFault are RuntimeErrors, filesystem/socket races
    # are OS/Connection/Timeout errors. Deterministic programming errors
    # (ValueError/TypeError — a sharding bug, a bad serialization tree)
    # must surface immediately, not after backoff sleeps.
    retryable: tuple = (RuntimeError, OSError, ConnectionError, TimeoutError)

    def __post_init__(self):
        self._rng = np.random.default_rng(self.seed)
        self._lock = threading.Lock()

    @staticmethod
    def from_config(cfg) -> "RetryPolicy":
        """Config-derived policy. The jitter seed mixes in the PID:
        every rank of a multi-process run shares cfg.seed, and identical
        jitter schedules would re-synchronize the thundering herd the
        jitter exists to break (all ranks re-hitting the coordinator at
        the same instant on every attempt). Ranks are distinct processes,
        so the pid decorrelates them; tests needing an exact replayable
        schedule construct RetryPolicy(seed=...) directly."""
        return RetryPolicy(attempts=max(1, int(getattr(cfg, "retry_attempts", 3))),
                           base_delay=float(getattr(cfg, "retry_base_delay", 0.05)),
                           seed=int(getattr(cfg, "seed", 0)) ^ (os.getpid() << 8))

    def delay(self, attempt: int) -> float:
        d = min(self.max_delay, self.base_delay * (2.0 ** max(0, attempt - 1)))
        with self._lock:
            j = 1.0 + self.jitter * (2.0 * float(self._rng.random()) - 1.0)
        return max(0.0, d * j)


DEFAULT_POLICY = RetryPolicy()


def run_resilient(site: str, fn, policy: Optional[RetryPolicy] = None,
                  index: Optional[int] = None):
    """faults.check(site) + fn() under the retry policy. The fault check
    runs BEFORE fn on every attempt (injected faults fire pre-mutation, so
    a retry re-runs identical work); transient failures are retried with
    backoff and a telemetry `retry` event, permanent ones escalate with a
    telemetry `error` event once the budget is spent."""
    pol = policy or DEFAULT_POLICY
    attempt = 0
    fault_idx = index  # allocated once: retries re-check the SAME
    while True:       # operation index (faults.next_index docstring)
        try:
            if faults.active():
                if fault_idx is None:
                    fault_idx = faults.next_index(site)
                faults.check(site, index=fault_idx)
            return fn()
        except pol.retryable as e:
            attempt += 1
            if attempt >= max(1, pol.attempts):
                tel.error("retry/exhausted", site=site, attempts=attempt,
                          error=repr(e))
                raise
            d = pol.delay(attempt)
            tel.retry(site, attempt, e, delay_s=d)
            _LOG.warning("transient failure at %s (attempt %d/%d, retrying "
                         "in %.3fs): %s", site, attempt, pol.attempts, d, e)
            time.sleep(d)


# -------------------------------------------------------- durable checkpoints
def _fsync_dir(path: str) -> None:
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass  # some filesystems reject directory fsync; rename is still atomic
    finally:
        os.close(fd)


def _is_pipelined(model) -> bool:
    return hasattr(model, "stage_params")


def load_manifest(path: str) -> Optional[Dict[str, Any]]:
    """The snapshot's manifest, or None when `path` is not a committed,
    structurally complete durable snapshot (missing/corrupt manifest,
    missing meta.json or orbax tree — a torn write or a plain non-durable
    checkpoint dir)."""
    try:
        with open(os.path.join(path, MANIFEST)) as f:
            man = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(man, dict) or not man.get("committed"):
        return None
    try:
        man["step"] = int(man["step"])
    except (KeyError, TypeError, ValueError):
        return None  # a garbled step would crash discovery for the whole root
    if not os.path.exists(os.path.join(path, "meta.json")):
        return None
    if not os.path.isdir(os.path.join(path, "tree")):
        return None
    return man


def committed_snapshots(root: str) -> List[Tuple[int, str, Dict[str, Any]]]:
    """(step, path, manifest) for every committed snapshot under `root`,
    step-ascending. Uncommitted `.tmp-*` dirs and dirs whose manifest
    doesn't validate are skipped."""
    out: List[Tuple[int, str, Dict[str, Any]]] = []
    if not root or not os.path.isdir(root):
        return out
    for name in sorted(os.listdir(root)):
        if not name.startswith("ckpt-"):
            continue
        path = os.path.join(root, name)
        man = load_manifest(path)
        if man is None:
            continue
        out.append((int(man["step"]), path, man))
    out.sort(key=lambda t: (t[0], t[1]))
    return out


def latest_checkpoint(root: str) -> Optional[str]:
    """Path of the newest committed durable snapshot under `root`."""
    snaps = committed_snapshots(root)
    return snaps[-1][1] if snaps else None


def _prune(root: str, keep: int) -> None:
    if keep <= 0:
        return
    snaps = committed_snapshots(root)
    for _step, path, _man in snaps[:-keep]:
        shutil.rmtree(path, ignore_errors=True)


def clean_stale_tmp(root: str) -> None:
    """Drop leftover `.tmp-*` dirs (a SIGKILLed writer's torn output).
    Called at fit start, after pending writes have been joined — but the
    join is BOUNDED, so a dir some still-wedged writer thread is actively
    serializing into is NOT stale and must survive the sweep."""
    from flexflow_tpu.runtime import checkpoint as ck

    if not root or not os.path.isdir(root):
        return
    live = set(ck.active_writes())
    for name in os.listdir(root):
        path = os.path.join(root, name)
        if name.startswith(".tmp-") and path not in live:
            shutil.rmtree(path, ignore_errors=True)


def progress_dict(epoch: int, step_in_epoch: int, loss_sum: float,
                  metric_sums: Optional[Dict[str, Any]], samples: int,
                  history: List[Dict[str, Any]]) -> Dict[str, Any]:
    """THE manifest progress schema — every producer (both fit loops'
    make_progress closures, epoch_end, final_save) builds it here, so
    adding a field is one edit, not a flat-loop/pipeline-loop lockstep
    change. Consumed by `start_state` + the loops' accumulator re-seed."""
    return {"epoch": int(epoch), "step_in_epoch": int(step_in_epoch),
            "loss_sum": float(loss_sum),
            "metric_sums": {k: float(v)
                            for k, v in (metric_sums or {}).items()},
            "samples": int(samples), "history": list(history)}


def start_state(progress: Optional[Dict[str, Any]],
                ) -> Tuple[int, int, List[Dict[str, Any]]]:
    """(start_epoch, step_in_epoch, history) from a restored snapshot's
    progress — the fit loops' resume cursor; (0, 0, []) on a fresh start."""
    if not progress:
        return 0, 0, []
    return (int(progress.get("epoch", 0)),
            int(progress.get("step_in_epoch", 0)),
            [dict(h) for h in progress.get("history", [])])


def effective_config(model, batch_size: Optional[int] = None,
                     accum_steps: Optional[int] = None) -> Dict[str, int]:
    """The trajectory-defining knobs a snapshot's progress counters are
    denominated in. fit() accepts per-call batch_size/accum_steps
    overrides that never touch cfg, so the fit loops pass the EFFECTIVE
    values — validating against cfg alone would let a changed override
    slip through."""
    cfg = model.cfg
    return {
        "seed": int(getattr(cfg, "seed", 0)),
        "batch_size": int(batch_size if batch_size is not None
                          else getattr(cfg, "batch_size", 0)),
        "accum_steps": int(accum_steps if accum_steps is not None
                           else getattr(cfg, "accum_steps", 1)),
    }


def save_durable(model, root: str, progress: Optional[Dict[str, Any]] = None,
                 block: Optional[bool] = None, keep: int = 0,
                 policy: Optional[RetryPolicy] = None,
                 config: Optional[Dict[str, int]] = None) -> str:
    """Atomic-commit durable snapshot of a CompiledModel/PipelinedModel:
    write into `.tmp-*` (the PR-2/PR-4 checkpoint writers, async-capable),
    then commit = manifest fsync + rename to `ckpt-<step>` + parent fsync.
    With block=False the commit runs at the end of the writer thread, so
    the caller only pays the device->host snapshot. Returns the COMMITTED
    path (the rename target; with block=False the commit is pending until
    `wait_pending()` / the exit drain joins the writer)."""
    import jax

    from flexflow_tpu.runtime import checkpoint as ck

    root = os.path.abspath(root)
    os.makedirs(root, exist_ok=True)
    step = int(model._iteration)
    if jax.process_count() > 1:
        # the orbax save below is COLLECTIVE in multi-process runs: every
        # process must hand it the SAME directory (each writes only its
        # addressable shards). The name must therefore be derivable from
        # shared state alone — step only, no pid/random tag. Safe from
        # concurrent-save collisions because multi-process writes are
        # always synchronous (save_checkpoint forces block=True there).
        tmp = os.path.join(root, f".tmp-{step:010d}")
    else:
        tag = f"{os.getpid():x}-{os.urandom(3).hex()}"
        tmp = os.path.join(root, f".tmp-{step:010d}-{tag}")
    final = os.path.join(root, f"ckpt-{step:010d}")
    pipelined = _is_pipelined(model)
    machine = model.stage_machine if pipelined else model.machine
    manifest = {
        "version": 1,
        "committed": True,
        "step": step,
        "format": "pipeline" if pipelined else "flat",
        "mesh_axes": dict(machine.mesh_axes),
        "progress": dict(progress or {}),
        "config": dict(config) if config else effective_config(model),
    }
    if pipelined:
        manifest["pipeline"] = {"stages": model.num_stages,
                                "schedule": model.schedule,
                                "cuts": list(model.cuts)}

    def commit():
        if jax.process_index() != 0:
            return
        if not os.path.isdir(tmp) and os.path.isdir(final):
            return  # a retry after the rename landed: already committed
        mpath = os.path.join(tmp, MANIFEST)
        with open(mpath, "w") as f:
            json.dump(manifest, f, default=float)
            f.flush()
            os.fsync(f.fileno())
        _fsync_dir(tmp)
        old = None
        if os.path.exists(final):
            # re-save of the same step (e.g. resume-after-completed-fit
            # re-running final_save): move the existing snapshot ASIDE
            # first — an rmtree-then-replace would open a crash window
            # with the committed snapshot destroyed and only an
            # uncommitted .tmp-* on disk
            old = final + ".old"
            shutil.rmtree(old, ignore_errors=True)
            os.replace(final, old)
        os.replace(tmp, final)
        _fsync_dir(root)
        if old is not None:
            shutil.rmtree(old, ignore_errors=True)
        tel.event("checkpoint/committed", cat="checkpoint", path=final,
                  step=step)
        _prune(root, keep)

    if block is None:
        block = not getattr(model.cfg, "async_checkpoint", True)
    saver = ck.save_pipeline_checkpoint if pipelined else ck.save_checkpoint
    saver(model, tmp, block=block, commit=commit, retry_policy=policy)
    return final


def _validate_resume_config(model, man: Dict[str, Any], path: str,
                            expected: Optional[Dict[str, int]] = None) -> None:
    """The identical-trajectory contract depends on seed (data order),
    batch_size and accum_steps (what one `step_in_epoch` unit means):
    resuming under different values would silently skip/duplicate samples.
    `expected` carries the fit call's EFFECTIVE knobs (per-call overrides
    included); mesh shape is deliberately NOT checked — changing it is
    the elastic feature."""
    saved = dict(man.get("config") or {})
    if not saved:
        return
    live_all = expected or effective_config(model)
    diffs = []
    for key in ("seed", "batch_size", "accum_steps"):
        live = live_all[key]
        if key in saved and int(saved[key]) != live:
            diffs.append(f"{key}: checkpoint={saved[key]} run={live}")
    if diffs:
        raise ValueError(
            f"cannot resume from {path}: the snapshot's training config "
            "differs in trajectory-defining knobs (" + ", ".join(diffs)
            + "); relaunch with the saved values (the mesh MAY change — "
            "that is the elastic part)")


def _drain_before_resume(ck) -> None:
    """Join pending async writes before snapshot discovery — BOUNDED
    (checkpoint.DRAIN_TIMEOUT / FF_CKPT_EXIT_TIMEOUT): a wedged writer
    from a previous fit must not hang resume forever; past the bound we
    warn and fall back to discovery of already-committed snapshots
    (torn `.tmp-*` output is invisible to discovery anyway). A FAILED
    write still re-raises — that is a real lost checkpoint, not a hang."""
    try:
        ck.wait_pending(timeout=ck.DRAIN_TIMEOUT)
    except TimeoutError as e:
        tel.error("resume/drain_timeout", error=repr(e))
        _LOG.warning("pending checkpoint write(s) did not drain in %ss "
                     "(%s); resuming from the newest already-committed "
                     "snapshot", ck.DRAIN_TIMEOUT, e)


def restore_auto(model, resume: str, root: str = "", verbose: bool = False,
                 expected_config: Optional[Dict[str, int]] = None,
                 ) -> Optional[Dict[str, Any]]:
    """Restore the newest usable durable snapshot. resume="auto" scans
    `root` newest-first, skipping snapshots that fail to load (corrupt /
    truncated — a telemetry error is emitted and the next-older committed
    snapshot is tried); an explicit `resume` path restores that snapshot
    (or the newest under it when it is a root dir), and a plain
    non-durable checkpoint dir restores with empty progress. Returns the
    manifest's training progress, or None when nothing was restored
    (fresh start). CheckpointMismatchError (wrong model/optimizer) is NOT
    swallowed — resuming a different model is a caller bug, not a corrupt
    snapshot."""
    from flexflow_tpu.runtime import checkpoint as ck

    _drain_before_resume(ck)  # pending async commits land before discovery
    if resume == "auto":
        if not root:
            raise ValueError('fit(resume="auto") needs a checkpoint root: '
                             "set checkpoint_dir / --checkpoint-dir")
        cands = committed_snapshots(root)[::-1]
    else:
        p = os.path.abspath(resume)
        man = load_manifest(p)
        if man is not None:
            cands = [(int(man["step"]), p, man)]
        elif os.path.exists(os.path.join(p, "meta.json")):
            # a plain (non-durable) checkpoint: restore, no progress
            model.load_checkpoint(p)
            return {}
        else:
            cands = committed_snapshots(p)[::-1]
            if not cands:
                raise FileNotFoundError(
                    f"resume={resume!r}: no committed durable snapshot "
                    f"found at or under {p}")
    for step, path, man in cands:
        _validate_resume_config(model, man, path, expected_config)
        try:
            model.load_checkpoint(path)
        except ck.CheckpointMismatchError:
            raise
        except Exception as e:
            tel.error("resume/snapshot_unusable", path=path, error=repr(e))
            _LOG.warning("durable snapshot %s unusable (%s); falling back "
                         "to the previous one", path, e)
            continue
        tel.event("resume/restored", cat="checkpoint", path=path, step=step)
        _LOG.info("resumed from %s (step %d)", path, step)
        if verbose:
            print(f"[resume] restored {path} (step {step})")
        return dict(man.get("progress") or {})
    if resume == "auto":
        _LOG.info("resume='auto': no usable snapshot under %s; fresh start",
                  root)
        return None
    raise FileNotFoundError(f"resume={resume!r}: no usable snapshot")


# ----------------------------------------------------------------- preemption
class PreemptionGuard:
    """Deferred SIGTERM/SIGINT: the handler only sets a flag; the fit loop
    polls `requested` per dispatch and runs the drain + final-snapshot +
    `Preempted` sequence from safe code. Installs only in the main thread
    (signal.signal's constraint); elsewhere it is inert."""

    SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self):
        self.requested = False
        self.signum: Optional[int] = None
        self._prev: Dict[int, Any] = {}
        self._installed = False

    def _handler(self, signum, frame):
        # flag-only: no telemetry emit here — the handler runs between
        # bytecodes on the main thread and tel's sink lock/file IO are not
        # reentrant (a signal landing mid-emit would self-deadlock). The
        # drain path emits the preempt events from safe code.
        if self.requested:
            # second signal: the drain isn't progressing (wedged prefetch,
            # stuck collective) — restore the previous disposition and let
            # it act (Ctrl-C Ctrl-C still interrupts, 2x SIGTERM kills)
            self.uninstall()
            signal.raise_signal(signum)
            return
        self.requested = True
        self.signum = signum

    def install(self) -> "PreemptionGuard":
        try:
            for s in self.SIGNALS:
                self._prev[s] = signal.signal(s, self._handler)
            self._installed = True
        except ValueError:  # not the main thread: stay inert
            self._prev.clear()
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        for s, h in self._prev.items():
            try:
                signal.signal(s, h)
            except ValueError:
                pass
        self._prev.clear()
        self._installed = False


# ------------------------------------------------------------ fit integration
class FitResilience:
    """Everything the fit loops need, in one handle: the checkpoint policy
    (every N steps / every T seconds, both 0 = off), the preemption guard,
    the retry policy threaded to the dataloader, and resume. Built per
    fit() call; None when resilience is fully off (the default — the hot
    loop then carries zero extra work)."""

    def __init__(self, model, root: str, every_steps: int, every_secs: float,
                 resume: str, keep: int, policy: RetryPolicy):
        self.model = model
        self.root = os.path.abspath(root) if root else ""
        self.every_steps = max(0, int(every_steps))
        self.every_secs = max(0.0, float(every_secs))
        self.resume_spec = resume
        self.keep = int(keep)
        self.policy = policy
        self.guard = PreemptionGuard()
        # the fit call's EFFECTIVE trajectory knobs (set_effective) —
        # stamped into every manifest and matched on resume
        self.effective: Dict[str, int] = {}
        self._last_iter = int(model._iteration)
        self._last_time = time.monotonic()

    @staticmethod
    def build(model, resume=None, checkpoint_dir=None, every_steps=None,
              every_secs=None) -> Optional["FitResilience"]:
        """Resolve per-call overrides against the config (None = config
        value, the fit-knob convention); returns None when neither a
        checkpoint root nor a resume request is active."""
        cfg = model.cfg
        resume = cfg.resume if resume is None else (resume or "")
        root = cfg.checkpoint_dir if checkpoint_dir is None else checkpoint_dir
        es = cfg.checkpoint_every_steps if every_steps is None else every_steps
        esec = cfg.checkpoint_every_secs if every_secs is None else every_secs
        if not root and not resume:
            return None
        if root and (esec or 0) > 0 and not (es or 0):
            import jax

            if jax.process_count() > 1:
                # the time trigger is single-process-only (due(): one
                # rank's clock must not enter a collective save alone)
                # and multi-process preemption skips the final snapshot —
                # a secs-only policy here would silently never snapshot.
                # Say so NOW, while the work is still recoverable.
                _LOG.warning(
                    "checkpoint_every_secs is ignored in multi-process "
                    "runs (host-local clocks can't coordinate a "
                    "collective save) and no checkpoint_every_steps is "
                    "set: NO periodic durable snapshots will be written. "
                    "Set --checkpoint-every-steps.")
        return FitResilience(model, root or "", es or 0, esec or 0.0,
                             resume, getattr(cfg, "keep_checkpoints", 3),
                             RetryPolicy.from_config(cfg))

    def set_effective(self, batch_size: Optional[int],
                      accum_steps: Optional[int]) -> None:
        """Record the fit call's effective batch_size/accum_steps (the
        per-call overrides, not cfg) BEFORE resume_now: they define what
        the manifest's progress counters mean."""
        self.effective = effective_config(self.model, batch_size,
                                          accum_steps)

    # --- resume ---
    def resume_now(self, verbose: bool = False) -> Optional[Dict[str, Any]]:
        if not self.resume_spec:
            if self.root:
                from flexflow_tpu.runtime import checkpoint as ck

                _drain_before_resume(ck)
                clean_stale_tmp(self.root)
            return None
        progress = restore_auto(self.model, self.resume_spec, self.root,
                                verbose=verbose,
                                expected_config=self.effective or None)
        clean_stale_tmp(self.root)
        self._last_iter = int(self.model._iteration)
        self._last_time = time.monotonic()
        return progress

    # --- periodic checkpoints ---
    def due(self) -> bool:
        if not self.root or not (self.every_steps or self.every_secs):
            return False
        it = int(self.model._iteration)
        if self.every_steps and it - self._last_iter >= self.every_steps:
            return True
        if self.every_secs and \
                time.monotonic() - self._last_time >= self.every_secs:
            # multi-process saves are COLLECTIVE: a host-local clock must
            # not let one process enter the save alone (deadlock). The
            # step trigger is deterministic across processes; the time
            # trigger only fires single-process.
            import jax

            return jax.process_count() == 1
        return False

    def save(self, progress: Dict[str, Any],
             block: Optional[bool] = None) -> str:
        path = save_durable(self.model, self.root, progress, block=block,
                            keep=self.keep, policy=self.policy,
                            config=self.effective or None)
        self._last_iter = int(self.model._iteration)
        self._last_time = time.monotonic()
        return path

    def install_guard(self) -> None:
        """Arm the preemption guard — only when there is a checkpoint root
        to save the final snapshot into. With resume-only resilience (no
        root) a converted signal would exit 0 with NOTHING saved, masking
        lost progress as success; the default KeyboardInterrupt/SIGTERM
        behavior (nonzero, visible) is the honest outcome there."""
        if self.root:
            self.guard.install()

    def maybe_checkpoint(self, loss, make_progress) -> None:
        """The per-dispatch poll both fit loops share: when preemption
        was requested or a periodic snapshot is due, drain in-flight
        dispatches, build the durable progress counters (`make_progress`
        materializes the epoch accumulators), and save. Preemption takes
        the synchronous save and raises Preempted; periodic saves use the
        async copy-then-write path, with backpressure — while the previous
        snapshot is still serializing the new one is skipped (due() keeps
        returning True, so it fires as soon as the writer drains) instead
        of piling up writer threads that each hold a host copy of the
        full state."""
        if not (self.guard.requested or self.due()):
            return  # the hot-path exit: nothing due — not even an import
        import jax

        from flexflow_tpu.runtime import checkpoint as ck

        if not self.guard.requested and \
                ck.active_writes(os.path.join(self.root, ".tmp-")):
            return
        jax.block_until_ready(loss)
        prog = make_progress()
        if self.guard.requested:
            self.preempt_now(prog)
        self.save(prog)

    def epoch_end(self, epoch: int, history: List[Dict[str, Any]]) -> None:
        """Epoch-boundary preemption point: a signal that landed after the
        last dispatch drains here with clean epoch-start progress."""
        if self.guard.requested:
            self.preempt_now(progress_dict(epoch + 1, 0, 0.0, {}, 0,
                                           history))

    def final_save(self, epochs: int, history: List[Dict[str, Any]]) -> None:
        """End-of-fit durable snapshot: a relaunch with resume="auto"
        continues (or, when all epochs are done, returns the stored
        history) instead of restarting the last epoch."""
        if self.root:
            self.save(progress_dict(epochs, 0, 0.0, {}, 0, history))

    # --- preemption ---
    @property
    def preempt_requested(self) -> bool:
        return self.guard.requested

    def preempt_now(self, progress: Dict[str, Any]):
        """Final coordinated snapshot (synchronous — the process is about
        to exit) and the clean-exit raise. The caller has already drained
        in-flight dispatches and materialized the progress counters.
        Multi-process runs SKIP the final snapshot: the orbax save is
        collective, and a signal reaches ranks at different steps — one
        rank entering the collective alone would deadlock. Durability
        there comes from the periodic step-based snapshots, whose trigger
        is deterministic across ranks."""
        import jax

        path = None
        if self.root and jax.process_count() == 1:
            path = self.save(progress, block=True)
        elif self.root:
            _LOG.warning(
                "preempted in a multi-process run: final snapshot skipped "
                "(collective save can't be entered from one rank's "
                "signal); newest periodic snapshot is the resume point")
        signum = self.guard.signum or signal.SIGTERM
        tel.event("preempt/drained", cat="preempt", signum=signum,
                  checkpoint=path)
        _LOG.warning("preempted by signal %s: drained, snapshot %s; "
                     "exiting cleanly", signum, path or "<no checkpoint dir>")
        raise Preempted(signum, path)
