"""The jax bootstrap (ops/_jax.py) and chip_smoke.py's contract.

The bootstrap decides where compiled programs are cached and whether the
device tiers engage; a chip that failed to initialise must be an error,
never a quiet numpy run. chip_smoke.py must refuse to report anything
without a TPU, pass its CPU rehearsal, and reject a run whose device
programs never ran.
"""

import json
import os
import subprocess
import sys

import pytest

from arroyo_tpu.ops import _jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def fresh_bootstrap(monkeypatch):
    """get_jax() as a new process would run it, with every
    jax.config.update it makes recorded."""
    jax = _jax.get_jax()
    updates = {}
    real_update = jax.config.update

    def recording_update(key, value):
        updates[key] = value
        real_update(key, value)

    monkeypatch.setattr(jax.config, "update", recording_update)
    monkeypatch.setattr(_jax, "_jax", None)
    return updates


def test_cache_dir_from_environment_is_left_to_jax(fresh_bootstrap,
                                                   monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    _jax.get_jax()
    assert "jax_compilation_cache_dir" not in fresh_bootstrap
    # the rest of the bootstrap still ran
    assert fresh_bootstrap["jax_enable_x64"] is True
    assert fresh_bootstrap["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_cache_dir_default_is_one_fixed_path_in_the_checkout(
        fresh_bootstrap, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    _jax.get_jax()
    assert fresh_bootstrap["jax_compilation_cache_dir"] == os.path.join(
        REPO, ".jax_cache")
    assert _jax.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")


def test_backend_discovery_error_propagates(monkeypatch):
    """A chip another process holds (libtpu: ABORTED ... already in use)
    must not select the numpy tier."""
    from arroyo_tpu.ops.aggregates import AggSpec, make_accumulator

    class HeldChip:
        @staticmethod
        def default_backend():
            raise RuntimeError(
                "Unable to initialize backend 'tpu': ABORTED: The TPU is "
                "already in use by process with pid 272.")

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setattr(_jax, "_accel", None)
    monkeypatch.setattr(_jax, "get_jax", lambda: HeldChip)
    with pytest.raises(RuntimeError, match="one process at a time") as e:
        make_accumulator([AggSpec("count", None, "c")])
    assert "already in use by process with pid 272" in str(e.value)
    assert _jax._accel is None  # nothing cached a "no accelerator" answer


def test_explicit_cpu_pin_is_a_host_deployment(monkeypatch):
    """JAX_PLATFORMS=cpu is a choice: answered without touching jax."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(_jax, "_accel", None)
    monkeypatch.setattr(_jax, "get_jax", lambda: pytest.fail("jax touched"))
    assert _jax.accelerator_present() is False


@pytest.fixture()
def tpu_like_float64(monkeypatch):
    """The device tier on, on a device whose float64 is not the host's
    (what a TPU is; ops/_jax.float64_is_ieee)."""
    from arroyo_tpu.config import update

    monkeypatch.setattr(_jax, "float64_is_ieee", lambda: False)
    with update(tpu={"enabled": True, "require_accelerator": False}):
        yield


def test_float_accumulators_stay_on_host_without_ieee_float64(
        tpu_like_float64):
    from arroyo_tpu.ops.aggregates import AggSpec, make_accumulator

    ints = [AggSpec("count", None, "c"), AggSpec("sum", 0, "s"),
            AggSpec("max", 0, "m")]
    assert make_accumulator(ints).backend == "jax"
    for kind in ("avg", "var_pop", "regr_slope"):
        spec = AggSpec(kind, 0, "x", col2=1 if kind == "regr_slope" else None)
        assert make_accumulator(ints + [spec]).backend == "numpy"
    assert make_accumulator(
        [AggSpec("sum", 0, "s", is_float=True)]).backend == "numpy"


def run_smoke(*argv, **env):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        env={**os.environ, **env}, cwd=REPO, capture_output=True, text=True,
        timeout=600)


def test_chip_smoke_without_a_tpu_exits_nonzero_and_prints_no_result():
    out = run_smoke()
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU (platform=cpu)" in out.stderr


def test_chip_smoke_rehearsal_passes_and_says_so_on_every_line():
    out = run_smoke("--rehearsal")
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    results = [ln for ln in lines if ln.startswith("[")]
    assert len(results) > 20
    assert all(ln.startswith("[platform=cpu, rehearsal]") for ln in results)
    # the 8 virtual devices of the test environment reach the mesh phase
    assert any("q5-mesh: accumulator exchange=device shards on "
               "[0, 1, 2, 3]" in ln for ln in results)


def test_chip_smoke_rejects_a_run_forced_onto_numpy():
    out = run_smoke("--rehearsal", ARROYO__TPU__ENABLED="0")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no device call recorded for program" in out.stderr
