"""Test configuration: tests run on XLA's CPU backend with 8 virtual
devices, so sharding/collective paths are exercised without a chip and a
test can never claim one. Must run before jax is imported anywhere.
(The chip is exercised by chip_smoke.py, through the chip tool.)"""

import os

# force, don't setdefault: a machine with a chip exports JAX_PLATFORMS
# naming it
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture()
def tmp_storage(tmp_path):
    return str(tmp_path / "storage")


# -- per-test watchdog --------------------------------------------------------
# A test that waits forever used to cost the whole run (the driver's run of
# this suite was once cut by its clock with nothing naming the test), and
# pytest-timeout is not installed. So every test gets two timers, armed when
# its setup begins and cancelled when its teardown ends: at 300 s
# `faulthandler` dumps every thread's stack to stderr (and the test goes on);
# at 420 s a SIGALRM fails the test in the worker's main thread, wherever it
# waits. A hang then costs one named failure. Nothing is marked slow.

import faulthandler  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

DUMP_AFTER_S = 300
FAIL_AFTER_S = 420
_watch = {"test": None, "inside": False}


def _on_alarm(signum, frame):
    if not _watch["inside"]:
        # between two phases of a test pytest's own code runs: raising
        # there would take the worker down, so come back in a moment
        signal.alarm(1)
        return
    pytest.fail(f"watchdog: {_watch['test']} ran for over {FAIL_AFTER_S} s "
                "(conftest.py's per-test limit); the stacks of all threads "
                f"were dumped to stderr at {DUMP_AFTER_S} s", pytrace=True)


def _alarm_usable() -> bool:
    return (hasattr(signal, "SIGALRM")
            and threading.current_thread() is threading.main_thread())


def _watched_phase():
    """The body shared by the three hook wrappers below: an alarm that
    fires while setup, call or teardown runs is raised inside it, and pytest
    books it as that phase's failure."""
    _watch["inside"] = True
    try:
        yield
    finally:
        _watch["inside"] = False


@pytest.hookimpl(hookwrapper=True, tryfirst=True)
def pytest_runtest_setup(item):
    _watch["test"] = item.nodeid
    faulthandler.dump_traceback_later(
        DUMP_AFTER_S, exit=False, file=sys.__stderr__)
    if _alarm_usable():
        signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(FAIL_AFTER_S)
    yield from _watched_phase()


@pytest.hookimpl(hookwrapper=True, tryfirst=True)
def pytest_runtest_call(item):
    yield from _watched_phase()


@pytest.hookimpl(hookwrapper=True, trylast=True)
def pytest_runtest_teardown(item):
    try:
        yield from _watched_phase()
    finally:
        if _alarm_usable():
            signal.alarm(0)
        faulthandler.cancel_dump_traceback_later()
