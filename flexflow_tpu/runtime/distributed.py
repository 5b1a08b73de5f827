"""Multi-host (multi-process) runtime support.

Reference analog: Legion control replication (`enable_control_replication`,
/root/reference/include/flexflow/config.h:157) — the top-level task runs
once per rank and Legion shards the index launches; plus the fake-multi-node
test trick (/root/reference/tests/multinode_helpers/mpi_wrapper2.sh:14-15:
mpirun with per-rank CUDA_VISIBLE_DEVICES carving one machine into "nodes").

TPU-native formulation: every process runs the SAME program (SPMD — the
control-replication analog is jax.distributed + jit over a global mesh whose
devices span processes; XLA runs collectives over ICI within a slice and DCN
across slices). This module wraps the two pieces the framework needs:

  - `init_distributed(...)`: jax.distributed.initialize for a multi-process
    run (on real multi-host TPU pods the arguments auto-detect; on CPU the
    coordinator/num_processes/process_id come from the launcher — the
    mpi_wrapper analog is tests/test_multihost.py spawning N local
    processes).
  - `host_local_batch(...)`: converts each process's LOCAL batch shard into
    a global jax.Array over the mesh (the dataloader's multi-host path;
    single-process meshes fall back to a plain device_put).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     local_device_ids: Optional[Sequence[int]] = None,
                     retry_policy=None) -> None:
    """Initialize the multi-process JAX runtime (control-replication
    analog). Call once per process BEFORE any jax computation; on real
    multi-host TPU the arguments are auto-detected from the environment.

    Coordinator handshakes are a classic transient-failure source (the
    coordinator's socket isn't up yet when a fast worker arrives), so the
    initialize runs under the `distributed/init` retry/backoff +
    fault-injection site — bounded attempts, then escalation."""
    from flexflow_tpu.runtime.resilience import run_resilient

    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = list(local_device_ids)
    run_resilient("distributed/init",
                  lambda: jax.distributed.initialize(**kwargs),
                  retry_policy)


def is_multiprocess() -> bool:
    return jax.process_count() > 1


def host_local_batch(arr: np.ndarray, mesh: Mesh,
                     pspec: PartitionSpec) -> jax.Array:
    """Assemble a global array from each process's LOCAL shard of the batch.

    `arr` holds THIS process's rows (global_batch / process_count of them
    when the batch dim is sharded across processes). Single-process meshes
    take the plain device_put path."""
    sharding = NamedSharding(mesh, pspec)
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_process_local_data(sharding, np.asarray(arr))


def global_batch_from_full(arr: np.ndarray, mesh: Mesh,
                           pspec: PartitionSpec) -> jax.Array:
    """Assemble a global array when EVERY process holds the FULL array
    (small datasets / synthetic data): each process contributes the rows its
    addressable shards own."""
    sharding = NamedSharding(mesh, pspec)
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    arr = np.asarray(arr)

    def cb(index):
        return arr[index]

    return jax.make_array_from_callback(arr.shape, sharding, cb)
