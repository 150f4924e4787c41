"""Test configuration: the suite runs on the CPU, on a virtual
8-device mesh so the multi-chip sharding tests exercise real
Mesh/shard_map paths, against the native library built from the
committed sources.

Both environment variables are read when jax first initialises a
backend, so they are set here, before anything imports jax; server and
shard subprocesses started by tests inherit them.
"""

import fcntl
import os
import subprocess
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


@pytest.fixture(scope="session")
def native_lib() -> Path:
    """The one native build every test shares."""
    assert _NATIVE_LIB.exists(), "make -C native produced no library"
    return _NATIVE_LIB
