"""Test configuration: the suite runs on the CPU, on a virtual
8-device mesh so the multi-chip sharding tests exercise real
Mesh/shard_map paths, against the native library built from the
committed sources.

Both environment variables are read when jax first initialises a
backend, so they are set here, before anything imports jax; server and
shard subprocesses started by tests inherit them.

Every test runs under ``TEST_LIMIT_S``: a case that waits for ever (a
``zmq_ctx_term`` with a socket left open sat twenty-one minutes in
tier-1, PERF.md PR 43) fails by name and the run goes on.
"""

import faulthandler
import fcntl
import os
import signal
import subprocess
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

NATIVE_DIR = Path(__file__).resolve().parent.parent / "native"


def _build_native() -> Path:
    """``make -C native`` under an exclusive lock: the library is not
    committed, make is idempotent, and the lock keeps xdist workers
    from racing each other into a half-written ``.so``."""
    with open(NATIVE_DIR / ".build.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        subprocess.run(["make", "-C", str(NATIVE_DIR)], check=True)
    return NATIVE_DIR / "libwqlcodec.so"


# Built at import, not first use: codec.py and native_keys.py bind the
# library when THEY are imported, which collection does before any
# fixture runs.
_NATIVE_LIB = _build_native()

from worldql_server_tpu.spatial import jaxconf  # noqa: E402,F401  (x64 on)

from tests.client_util import ZmqClient  # noqa: E402


@pytest.fixture(scope="session")
def native_lib() -> Path:
    """The one native build every test shares."""
    assert _NATIVE_LIB.exists(), "make -C native produced no library"
    return _NATIVE_LIB


# region: the one limit of a test

#: Seconds a test's setup, its call and its teardown may each take.
TEST_LIMIT_S = 120

_running = ""       # the nodeid the timer is armed for
_expired_in = ""    # ... and the one it ran out for, if it did
_stderr = None      # the process's own stderr, from before any capture


def _timed_out():
    pytest.fail(f"TIMED OUT: {_expired_in} ran into the limit of "
                f"{TEST_LIMIT_S} s a test (tests/conftest.py); every "
                f"thread's stack is on stderr")


def _expired(signum, frame):
    """SIGALRM on the main thread: a blocked libzmq call returns EINTR
    and pyzmq runs this before it looks at the code, a sleep, a join
    and a select are interrupted alike, so the exception below comes
    out of whatever the test was waiting in."""
    global _expired_in
    _expired_in = _running
    try:
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
    except (AttributeError, OSError, ValueError):   # no fd behind it
        faulthandler.dump_traceback(file=_stderr, all_threads=True)
    _timed_out()


def pytest_configure(config):
    global _stderr
    # (capture is suspended here, so fd 2 is the real one; a test's
    # own fd 2 is its capture file, which dies with a killed worker)
    _stderr = os.fdopen(os.dup(2), "w")
    signal.signal(signal.SIGALRM, _expired)


@pytest.hookimpl(wrapper=True)
def _limited(item):
    """Arms the limit around one phase of a test. Where the main
    thread sits in a call no signal interrupts, the watchdog thread
    prints the stacks at twice the limit and ends the process: xdist
    reports the case as failed and replaces the worker."""
    global _running, _expired_in
    _running, _expired_in = item.nodeid, ""
    faulthandler.dump_traceback_later(
        2 * TEST_LIMIT_S, exit=True, file=_stderr)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        result = yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
    if _expired_in:
        # the handler's exception was swallowed on its way out (the
        # wait was inside a finalizer: the collector's ``__del__`` of a
        # leaked zmq context is where tier-1 hung): the case pays all
        # the same
        _timed_out()
    return result


pytest_runtest_setup = pytest_runtest_call = pytest_runtest_teardown = _limited


@pytest.fixture(autouse=True)
def _no_client_outlives_its_test():
    """A ``ZmqClient`` the test did not close (it failed first, or
    forgot) is ended here, on this thread, and not by the collector
    (``ZmqClient._open`` says why)."""
    yield
    ZmqClient.close_leftovers()

# endregion
