"""Multi-host bootstrap: process initialization, pod meshes, global batches.

The reference is strictly single-process/single-device (SURVEY.md S2.3 — no
torch.distributed, no NCCL/MPI anywhere). The TPU framework's communication
backend is XLA itself: collectives ride ICI within a slice and DCN across
slices, and what the framework owes is the *bootstrap* — process group
initialization, a mesh laid out so the fast axes stay on ICI, and the
host-local -> globally-sharded batch hand-off. That is this module:

- :func:`initialize` — ``jax.distributed.initialize`` wrapper. On TPU pods
  everything is auto-detected from the metadata server; on CPU/GPU clusters
  the coordinator/rank come from standard env vars (COORDINATOR_ADDRESS,
  NUM_PROCESSES, PROCESS_ID) or arguments. Safe to call when single-process
  (no-op without a coordinator).
- :func:`pod_mesh` — an (dp, sp) mesh over ALL processes' devices via
  ``mesh_utils.create_device_mesh``, which orders devices so the trailing
  mesh axis maps to physically-adjacent chips: put ``sp`` last so ring
  attention's ppermute hops ride single ICI links, and dp spans DCN.
- :func:`global_batch` — build globally-sharded arrays from each host's
  local batch shard (``jax.make_array_from_process_local_data``): every
  host feeds ``global_batch_size / num_processes`` examples and the result
  is one logical array sharded P(dp, ...) over the pod, without any host
  ever materializing the full batch.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from alphafold2_tpu.parallel.sharding import DATA_AXIS, SEQ_AXIS


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Initialize the JAX process group for multi-host execution.

    Returns True if distributed init ran, False for single-process.
    Initialization requires an EXPLICIT multi-process signal — a
    coordinator address (argument or COORDINATOR_ADDRESS env), a
    multi-worker TPU slice environment (TPU_WORKER_HOSTNAMES with >1
    host), or AF2TPU_MULTIHOST=1 to force jax's own pod auto-detection.
    Single-chip and single-host runs must not call
    jax.distributed.initialize, so silence is the safe default; on pod
    launchers that set none of these vars, export AF2TPU_MULTIHOST=1.
    """
    coordinator_address = coordinator_address or os.environ.get(
        "COORDINATOR_ADDRESS"
    )
    if num_processes is None and os.environ.get("NUM_PROCESSES"):
        num_processes = int(os.environ["NUM_PROCESSES"])
    if process_id is None and os.environ.get("PROCESS_ID"):
        process_id = int(os.environ["PROCESS_ID"])

    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    multihost_tpu = len([h for h in hosts.split(",") if h]) > 1
    forced = os.environ.get("AF2TPU_MULTIHOST") == "1"
    if coordinator_address is None and not multihost_tpu and not forced:
        return False  # single-process run; nothing to initialize
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def pod_mesh(
    n_data: int = -1,
    n_seq: int = 1,
    *,
    allow_split_physical_axes: bool = False,
) -> Mesh:
    """(dp, sp) mesh over every device in the (possibly multi-host) runtime.

    ``n_data=-1`` fills dp with all remaining devices. The sp axis is placed
    LAST in the mesh shape so ``create_device_mesh`` keeps its devices
    physically contiguous — ring-attention ppermute then uses nearest-
    neighbor ICI links, and the dp all-reduce crosses DCN only once per
    step.
    """
    total = jax.device_count()
    if n_data == -1:
        if total % n_seq != 0:
            raise ValueError(
                f"{total} devices do not divide by sp={n_seq}"
            )
        n_data = total // n_seq
    if n_data * n_seq != total:
        raise ValueError(f"mesh {n_data}x{n_seq} != {total} devices")
    devices = mesh_utils.create_device_mesh(
        (n_data, n_seq), allow_split_physical_axes=allow_split_physical_axes
    )
    return Mesh(devices, (DATA_AXIS, SEQ_AXIS))


def global_batch(batch: dict, mesh: Mesh) -> dict:
    """Assemble a globally batch-sharded batch from this host's local shard.

    Each process passes its own slice of the global batch (same dict schema,
    local batch size = global / num_processes); the returned arrays are
    jax.Arrays sharded P(dp) over the full pod. Single-process this reduces
    to a device_put.
    """
    out = {}
    for key, value in batch.items():
        value = np.asarray(value)
        sharding = NamedSharding(mesh, P(DATA_AXIS))
        out[key] = jax.make_array_from_process_local_data(sharding, value)
    return out
