"""Shared deferred-jax bootstrap.

jax is imported lazily so host-only deployments can import the module
tree without pulling in the accelerator stack; every device-path module
must see the same config (x64 enabled — the engine's timestamps, keys
and integer accumulators are 64-bit) and the same persistent compile
cache, so every `import jax` in the package goes through `get_jax()`.

A host deployment is a CHOICE (`tpu.enabled = false`, or
`JAX_PLATFORMS=cpu`); an accelerator that fails to initialise is an
error and propagates — nothing here converts it into the numpy tier."""

from __future__ import annotations

import os

_jax = None
_accel: bool | None = None

# Persistent XLA compile cache when JAX_COMPILATION_CACHE_DIR is unset: one
# fixed path inside the checkout. The directory is part of what a cache
# hit depends on, so it never derives from $HOME, a temp name, a pid or
# a time.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def get_jax():
    global _jax
    if _jax is None:
        import jax

        jax.config.update("jax_enable_x64", True)
        # compiled programs survive process exit, so repeat runs (worker
        # restarts, bench medians, a second chip_smoke) skip XLA
        # compilation. jax reads JAX_COMPILATION_CACHE_DIR itself at
        # import; the directory is only set here when it is unset.
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        _jax = jax
    return _jax


def accelerator_present() -> bool:
    """True when jax's default backend is a real accelerator (TPU/GPU).
    The device execution tiers engage on this by default; on a host
    without one the numpy/arrow host paths run instead of jitted
    kernels on XLA's CPU backend. Backend discovery errors propagate:
    a chip that failed to initialise must not look like a host without
    one."""
    global _accel
    if _accel is None:
        if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
            # explicit CPU pin: answer without importing jax at all (a
            # default-config host-only deployment shouldn't pay jax
            # import + backend discovery just to learn "use numpy")
            _accel = False
        else:
            try:
                _accel = get_jax().default_backend() != "cpu"
            except RuntimeError as e:
                # measured on a v5e (PR 21): a second process gets
                # "ABORTED: The TPU is already in use by process with
                # pid N" from libtpu within a second — say what to do
                raise RuntimeError(
                    f"jax could not initialise its accelerator backend "
                    f"in pid {os.getpid()}: {e} — a chip belongs to one "
                    "process at a time: run ONE device-tier worker "
                    "process per chip (more subtasks = --parallelism "
                    "inside that process) and pin every other process "
                    "to JAX_PLATFORMS=cpu"
                ) from e
    return _accel


def float64_is_ieee() -> bool:
    """Does the device compute float64 as the host does? Not a TPU: its
    float64 is emulated on float32 hardware — float32's exponent range
    (1e300 and 3.5e38 arrive as inf, 1e-300 as 0), a shorter mantissa
    (1 + 2^-52 arrives as 1.0), and a scatter-add of 4096 values near
    1.7e18 came back off by 2e-14 relative; the host->device transfer
    alone rounds (all measured on a v5e, PR 21). int64 is emulated exactly. So state
    and programs that must equal the host tier's keep float64 off a TPU
    (ops/aggregates.make_accumulator, engine/segments.py)."""
    return get_jax().default_backend() != "tpu"


def platform() -> str:
    """jax's default backend, for the one line each operator logs at
    open with the tier it took. A process whose tier decisions never
    needed jax reports that instead of importing it."""
    return _jax.default_backend() if _jax is not None else "host (jax not loaded)"


def device_tier_active() -> bool:
    """tpu.enabled AND (an accelerator exists OR the config explicitly
    waives the requirement — tests and CPU-jax measurement runs)."""
    from ..config import config

    cfg = config().tpu
    if not cfg.enabled:
        return False
    return accelerator_present() if cfg.require_accelerator else True


def device_join_active() -> bool:
    """Gate for the merge-join probe, shared by the instant/expiring and
    updating join operators: the device tier plus the join-specific
    switch."""
    from ..config import config

    return config().tpu.device_join and device_tier_active()
