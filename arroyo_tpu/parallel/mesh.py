"""Device mesh helpers.

The engine's multi-chip axis is the KEY dimension of the keyed stream
(SURVEY.md §5.7/§5.8): hash-range key shards map onto devices of a 1-D
mesh, so the keyed shuffle becomes an on-device all-to-all over ICI inside
a slice, while the host data plane (engine/network.py) carries batches
across slices and to connectors.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

_MESH_CACHE: Dict[Tuple, object] = {}


def _get_jnp():
    """jax.numpy behind the shared bootstrap (ops/_jax.py: x64 and the
    compile cache are configured before anything can trace)."""
    from ..ops._jax import get_jax

    return get_jax().numpy


def key_mesh(devices: Optional[Sequence] = None, axis: str = "keys"):
    """The 1-D key mesh over `devices`. Cached per (device ids, axis):
    every operator over the same device set shares ONE Mesh instance, so
    the process-level jitted-program cache in sharded_state.py (keyed by
    mesh identity among other things) actually hits across operators —
    distinct Mesh objects would re-trace identical programs per stage."""
    from ..ops._jax import get_jax

    jax = get_jax()
    if devices is None:
        devices = jax.devices()
    key = (tuple(d.id for d in devices), axis)
    mesh = _MESH_CACHE.get(key)
    if mesh is None:
        import numpy as np

        mesh = _MESH_CACHE.setdefault(
            key, jax.sharding.Mesh(np.array(devices), (axis,)))
    return mesh


def mesh_is_virtual(mesh) -> bool:
    """True when the mesh's "devices" are host-platform (CPU) devices of
    ONE process — the `--xla_force_host_platform_device_count` dryrun/CI
    configuration. There is no ICI underneath such a mesh: collectives
    are memcpys between buffers of the same host and every shard's
    compute shares the same cores, which inverts the cost model the
    device-routed exchange is built for (sharded_state.py picks the
    host-fed exchange and the single-device salted tier here)."""
    devs = list(mesh.devices.flat)
    return all(d.platform == "cpu" for d in devs) and len(
        {d.process_index for d in devs}
    ) == 1
