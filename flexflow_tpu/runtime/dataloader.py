"""Dataloaders.

Reference analog: `SingleDataLoader` (include/flexflow/dataloader.h:34-120,
src/dataloader/dataloader.cc) — full dataset pinned in zero-copy CPU memory,
per-iteration index task scattering shard slices to device. The TPU-native
equivalent keeps the dataset in host numpy and device_puts each batch with its
NamedSharding: jax dispatches the host→HBM copies per shard asynchronously,
which is the same scatter. A double-buffered prefetcher overlaps the next
batch's transfer with the current step (the Legion-async analog); the native
C++ loader (flexflow_tpu/native) accelerates shuffled batch assembly.
"""

from __future__ import annotations

import threading
import queue as queue_mod
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import numpy as np

from flexflow_tpu import telemetry as tel


class SingleDataLoader:
    def __init__(self, xs: Sequence[np.ndarray], y: np.ndarray, batch_size: int,
                 shuffle: bool = True, seed: int = 0, drop_remainder: bool = True):
        self.xs = [np.asarray(x) for x in xs]
        self.y = np.asarray(y)
        n = self.y.shape[0]
        for x in self.xs:
            assert x.shape[0] == n, "all arrays must share the sample dim"
        self.num_samples = n
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.rng = np.random.default_rng(seed)
        self.drop_remainder = drop_remainder
        try:
            from flexflow_tpu.native import batch_gather  # C++ fast path

            self._gather = batch_gather
        except Exception:
            self._gather = None

    @property
    def num_batches(self) -> int:
        if self.drop_remainder:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size

    def _take(self, arr: np.ndarray, idx: np.ndarray) -> np.ndarray:
        if self._gather is not None and arr.dtype != object:
            out = self._gather(arr, idx)
            if out is not None:
                return out
        return arr[idx]

    def epoch(self, skip_batches: int = 0,
              ) -> Iterator[Tuple[List[np.ndarray], np.ndarray]]:
        """One shuffled pass. `skip_batches` resumes MID-epoch (the
        auto-resume cursor): the shuffle still draws the full permutation
        (identical rng consumption to a skip-less epoch), but the skipped
        leading batches are never gathered — an O(1) fast-forward instead
        of materializing and discarding thousands of batches."""
        order = np.arange(self.num_samples)
        if self.shuffle:
            self.rng.shuffle(order)
        for b in range(max(0, int(skip_batches)), self.num_batches):
            idx = order[b * self.batch_size:(b + 1) * self.batch_size]
            yield [self._take(x, idx) for x in self.xs], self._take(self.y, idx)

    def advance_epochs(self, n: int) -> None:
        """Fast-forward the shuffle rng past `n` epochs WITHOUT touching
        data — the auto-resume dataloader cursor (runtime/resilience.py):
        a relaunched fit rebuilds the loader with the run's seed, advances
        past the completed epochs, and the next epoch() draws the exact
        permutation the interrupted run was consuming. Must mirror
        epoch()'s rng consumption (one shuffle per epoch) exactly."""
        for _ in range(max(0, int(n))):
            if self.shuffle:
                self.rng.shuffle(np.arange(self.num_samples))


def _batch_shapes(xs, y):
    """Shape fingerprint of one (inputs, label) batch — the ragged-batch
    guards in group_microbatches and prefetch_multi key on it."""
    return tuple(np.asarray(x).shape for x in xs) + (np.asarray(y).shape,)


def group_microbatches(it, n: int):
    """Gradient-accumulation grouper (CompiledModel accum_steps): stack `n`
    consecutive host batches into (n, ...) arrays — ONE yielded item feeds
    one accumulating train step (n fwd/bwd passes, one optimizer update).
    Runs BELOW prefetch_multi in the fit pipeline, so K accum-groups can
    still fuse into a single (K, n, ...) dispatch. Microbatches that can't
    complete a shape-uniform group are dropped (drop_remainder semantics —
    a partial or ragged group would need its own jitted step shape): the
    trailing short tail, and any group broken by a ragged batch (e.g. a
    short remainder from a drop_remainder=False loader, which must not
    crash np.stack — prefetch_multi's guard, same file)."""
    if n <= 1:
        yield from it
        return
    buf = []
    for xs, y in it:
        if buf and _batch_shapes(xs, y) != _batch_shapes(*buf[0]):
            buf = []  # ragged boundary: the partial group can't stack
        buf.append((xs, y))
        if len(buf) == n:
            yield ([np.stack([b[0][i] for b in buf])
                    for i in range(len(buf[0][0]))],
                   np.stack([b[1] for b in buf]))
            buf = []


def prefetch_to_device(it, input_shardings, label_sharding, depth: int = 2,
                       put=None, retry_policy=None):
    """Overlap host→device transfer with compute (double buffering).
    `put(arr, sharding)` overrides the transfer (multi-host runs pass the
    global-array assembler from runtime/distributed.py). Implemented as
    the k=1 case of prefetch_multi, untagged."""
    for _kind, dx, dy in prefetch_multi(it, 1, input_shardings,
                                        label_sharding, depth=depth, put=put,
                                        retry_policy=retry_policy):
        yield dx, dy


def prefetch_multi(it, k, input_shardings, label_sharding,
                   stacked_input_shardings=None, stacked_label_sharding=None,
                   depth: int = 2, put=None, retry_policy=None):
    """K-step prefetcher for the fused-dispatch training loop
    (CompiledModel.make_multi_step): groups `k` consecutive host batches,
    np.stacks them into (k, ...) arrays, and transfers each group with the
    STACKED shardings (leading step dim unsharded) — one transfer feeds one
    k-step dispatch. Tail batches that don't fill a group transfer singly.

    Yields ("k", dx, dy) for full stacked groups and ("1", dx, dy) for
    singles: the epoch tail, and any batch whose shapes differ from its
    group's (a ragged remainder batch flushes the partial group singly
    rather than crashing np.stack). With k <= 1 it degenerates to tagged
    prefetch_to_device. Worker exceptions are forwarded to the consumer
    like prefetch_to_device (the queued items ahead of the exception still
    drain first).

    Transfers run under the retry/backoff + fault-injection site
    `dataloader/transfer` (runtime/resilience.py): a transient device_put
    failure is retried with
    backoff inside the worker thread instead of killing the epoch;
    `retry_policy` defaults to the module default (fit passes the
    config-derived policy)."""
    from flexflow_tpu.runtime.resilience import run_resilient

    q: "queue_mod.Queue" = queue_mod.Queue(maxsize=depth)
    _DONE = object()
    if put is None:
        put = jax.device_put
    # telemetry: per-transfer spans + queue-occupancy counter samples from
    # the worker thread (captured once — zero added work when disabled)
    rec = tel.enabled()

    def _xfer(xs, y, in_sh, lab_sh):
        t0 = tel.now_us() if rec else 0.0

        def move():
            dx = [put(x, s) if s is not None else jax.device_put(x)
                  for x, s in zip(xs, in_sh)]
            dy = put(y, lab_sh) if lab_sh is not None else jax.device_put(y)
            return dx, dy

        dx, dy = run_resilient("dataloader/transfer", move, retry_policy)
        if rec:
            tel.record("dataloader/transfer", t0, cat="dataloader")
        return dx, dy

    def _enqueue(item):
        q.put(item)
        if rec:
            tel.counter("dataloader/queue_depth", q.qsize(),
                        cat="dataloader")

    def worker():
        try:
            buf: List = []
            for xs, y in it:
                if k <= 1:
                    _enqueue(("1",) + _xfer(xs, y, input_shardings,
                                            label_sharding))
                    continue
                if buf and _batch_shapes(xs, y) != _batch_shapes(*buf[0]):
                    # ragged batch (e.g. short remainder): flush the
                    # partial group singly — stacking would crash
                    for bxs, by in buf:
                        _enqueue(("1",) + _xfer(bxs, by, input_shardings,
                                                label_sharding))
                    buf = []
                buf.append((xs, y))
                if len(buf) == k:
                    sx = [np.stack([b[0][i] for b in buf])
                          for i in range(len(buf[0][0]))]
                    sy = np.stack([b[1] for b in buf])
                    _enqueue(("k",) + _xfer(
                        sx, sy,
                        stacked_input_shardings or input_shardings,
                        stacked_label_sharding
                        if stacked_label_sharding is not None
                        else label_sharding))
                    buf = []
            for xs, y in buf:  # tail: fewer than k batches left
                _enqueue(("1",) + _xfer(xs, y, input_shardings,
                                        label_sharding))
            q.put(_DONE)
        except BaseException as e:  # forward to the consumer, don't swallow
            q.put(e)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    while True:
        item = q.get()
        if item is _DONE:
            break
        if isinstance(item, BaseException):
            raise item
        yield item
