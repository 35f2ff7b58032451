"""Multi-host mesh runtime: `jax.distributed` across worker processes.

A real TPU pod slice spans HOSTS — each process addresses only its local
chips (4 on v5e), and the global mesh exists only after every process
calls `jax.distributed.initialize` with a shared coordinator. The
reference's multi-worker scale-out is its TCP shuffle
(/root/reference/crates/arroyo-worker/src/network_manager.rs:551-605);
the TPU-native replacement keeps the shuffle INSIDE the jitted step as
XLA collectives over ICI, which requires this process-spanning mesh.

Wiring (SURVEY.md §5.8): the controller assigns
(coordinator address, process count, process id) at scheduling time —
`controller/scheduler.py` injects them into each spawned worker's env as
`ARROYO__TPU__MESH_*` config overrides — and the `worker` command calls
`ensure_initialized()` BEFORE any jax backend init. Operators then build
meshes from the global device list exactly as in single-host mode.

Execution model: mesh-mode operators run SPMD — every mesh process packs
the SAME batch (the host data plane broadcasts batches to mesh peers)
and executes the same jitted step in lockstep; each process materializes
only its addressable shards (`put_global`) and reads back replicated
outputs from its local copy (`to_host`).
"""

from __future__ import annotations

import os
import threading
from typing import Optional, Tuple

from ..utils.logging import get_logger

logger = get_logger("multihost")

_lock = threading.Lock()
_initialized: Optional[Tuple[int, int]] = None  # (num_processes, process_id)


def _settings() -> Tuple[str, int, int]:
    from ..config import config

    tpu = config().tpu
    return tpu.mesh_coordinator, int(tpu.mesh_processes), int(
        tpu.mesh_process_id)


def ensure_initialized() -> Tuple[int, int]:
    """Idempotently initialize `jax.distributed` when this process is
    part of a multi-process mesh (tpu.mesh_processes >= 2, assigned by
    the controller). Returns (num_processes, process_id) — (1, 0) in
    single-process deployments. Must run before the first jax backend
    init in the process."""
    global _initialized
    with _lock:
        if _initialized is not None:
            return _initialized
        coord, n_proc, pid = _settings()
        if n_proc < 2:
            _initialized = (1, 0)
            return _initialized
        if not coord or pid < 0:
            raise ValueError(
                f"tpu.mesh_processes={n_proc} requires mesh_coordinator "
                f"and mesh_process_id (got {coord!r}, {pid})"
            )
        from ..ops._jax import get_jax

        jax = get_jax()
        logger.info(
            "joining %d-process mesh as rank %d (coordinator %s)",
            n_proc, pid, coord,
        )
        try:
            jax.distributed.initialize(
                coordinator_address=coord, num_processes=n_proc,
                process_id=pid,
            )
        except Exception as e:
            # the most common cause: the controller auto-picked the
            # coordinator port (bind-then-close in controller/scheduler.py
            # pick_coordinator) and something else bound it before rank 0's
            # jax coordinator service came up — name the address and the
            # fix instead of surfacing jax's bare connect error
            raise RuntimeError(
                f"worker rank {pid}/{n_proc} failed to join the "
                f"jax.distributed mesh at coordinator {coord!r}: {e!r}. "
                "If the coordinator address was auto-picked by the "
                "controller, the bind-then-close port reservation may have "
                "been lost to a race; pin a stable address with "
                "tpu.mesh_coordinator (env ARROYO__TPU__MESH_COORDINATOR), "
                "reachable from every worker — rank 0 binds it."
            ) from e
        _initialized = (n_proc, pid)
        return _initialized


def process_info() -> Tuple[int, int]:
    """(num_processes, process_id) as initialized; (1, 0) before/without
    multi-process init."""
    return _initialized if _initialized is not None else (1, 0)


def is_multiprocess_mesh(mesh) -> bool:
    """Does this mesh span devices owned by more than one process?"""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def put_global(np_arr, mesh, spec):
    """Place a host array onto a (possibly multi-process) mesh sharding.

    Every mesh process passes the SAME global value (lockstep SPMD — the
    data plane broadcast guarantees it); only locally-addressable shards
    are materialized. Single-process meshes take the direct device_put
    fast path."""
    from ..ops._jax import get_jax

    jax = get_jax()
    sharding = jax.sharding.NamedSharding(mesh, spec)
    if not is_multiprocess_mesh(mesh):
        return jax.device_put(np_arr, sharding)
    return jax.make_array_from_callback(
        np_arr.shape, sharding, lambda idx: np_arr[idx]
    )


def to_host(arr):
    """Read a device array back to numpy. Fully-addressable arrays (all
    single-process cases) convert directly; a replicated output on a
    multi-process mesh is read from this process's local copy."""
    import numpy as np

    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(arr)
    return np.asarray(arr.addressable_data(0))


def env_overrides(coordinator: str, num_processes: int,
                  process_id: int) -> dict:
    """Config-layer env vars the scheduler injects into a spawned
    worker so its `ensure_initialized()` joins the job's mesh."""
    return {
        "ARROYO__TPU__MESH_COORDINATOR": coordinator,
        "ARROYO__TPU__MESH_PROCESSES": str(num_processes),
        "ARROYO__TPU__MESH_PROCESS_ID": str(process_id),
    }
