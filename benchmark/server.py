"""The system under test: one `python -m worldql_server_tpu` child,
booted as a configuration's `server_args` say. It is the only process
of a run that imports jax, and so the only one that holds the chip.
(Process model copied from `chip_smoke.Server`, PR 22.)
"""

from __future__ import annotations

import ctypes
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BOOT_TIMEOUT = 1000.0      # a cold 1M-row boot compiles for ~5 minutes
HTTP_TIMEOUT = 20.0        # chip_smoke's hold on every answer (PR 22)


class RunFailed(SystemExit):
    """The run cannot give a result: exit non-zero, print no result."""

    def __init__(self, why: str):
        super().__init__(f"benchmark FAILED: {why}")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def build_native() -> None:
    """`make -C native`, then load every leg the served path uses: a
    missing leg would fall back to its Python twin in silence."""
    made = subprocess.run(["make", "-C", str(ROOT / "native")],
                          stdout=subprocess.DEVNULL)
    if made.returncode != 0:
        raise RunFailed("`make -C native` failed: the benchmark runs from a "
                        "checkout of the repository, not from its own files")
    from worldql_server_tpu.protocol import codec, entity_wire
    from worldql_server_tpu.spatial import native_keys

    wire = entity_wire.shared()
    keys = native_keys._native
    legs = {
        "message_codec": codec._native is not None,
        "can_decode": wire is not None and wire.can_decode,
        "can_encode_frames": wire is not None and wire.can_encode_frames,
        "key_kernel": keys is not None,
        "wql_encode_queries": getattr(keys, "_encode", None) is not None,
    }
    missing = [name for name, live in legs.items() if not live]
    if missing:
        raise RunFailed(f"native legs missing after make: {missing}")
    if "jax" in sys.modules:
        raise RunFailed("the harness must stay off jax")


def _die_with_parent() -> None:
    # PR_SET_PDEATHSIG: a killed harness leaves no process on the chip
    ctypes.CDLL("libc.so.6").prctl(1, signal.SIGTERM)


class Server:
    def __init__(self, workdir: Path, server_args: list[str],
                 module: str = "worldql_server_tpu", trace: bool = False):
        self.http_port = free_port()
        self.zmq_port = free_port()
        self.host = "127.0.0.1"
        self.log_path = workdir / "server.log"
        self.cmd = [
            sys.executable, "-m", module, *server_args,
            *(["--trace"] if trace else []),
            "--http-host", self.host, "--http-port", str(self.http_port),
            "--zmq-server-host", self.host,
            "--zmq-server-port", str(self.zmq_port),
        ]
        # the compile cache lives in the checkout, at a fixed path, so
        # that two checkouts share nothing and a second run compiles
        # nothing (the program takes JAX_COMPILATION_CACHE_DIR as given)
        self.env = dict(os.environ,
                        JAX_COMPILATION_CACHE_DIR=str(ROOT / ".jax_cache"))
        self.proc: subprocess.Popen | None = None

    def start(self) -> None:
        with open(self.log_path, "w") as log:
            self.proc = subprocess.Popen(
                self.cmd, cwd=ROOT, env=self.env, stdout=log,
                stderr=subprocess.STDOUT, preexec_fn=_die_with_parent,
            )
        t0 = time.monotonic()
        while True:
            if self.proc.poll() is not None:
                raise RunFailed(f"server exited {self.proc.returncode} "
                                f"during boot:\n{self.log_tail()}")
            if time.monotonic() - t0 > BOOT_TIMEOUT:
                raise RunFailed(f"server not healthy after {BOOT_TIMEOUT} s:"
                                f"\n{self.log_tail()}")
            try:
                self.get("/healthz", timeout=2.0)
                return
            except OSError:
                time.sleep(0.2)

    def get(self, path: str, timeout: float = HTTP_TIMEOUT) -> dict:
        req = urllib.request.Request(
            f"http://{self.host}:{self.http_port}{path}",
            headers={"Accept": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read())

    def post(self, path: str, body: dict) -> dict:
        req = urllib.request.Request(
            f"http://{self.host}:{self.http_port}{path}",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=60.0) as resp:
                return json.loads(resp.read())
        except urllib.error.HTTPError as e:
            raise RunFailed(f"POST {path} {body}: {e.code} {e.read()!r}")

    def metrics(self) -> dict:
        try:
            return self.get("/metrics")
        except OSError as e:
            raise RunFailed(f"the server did not answer /metrics within "
                            f"{HTTP_TIMEOUT:.0f} s ({e}):\n{self.log_tail()}")

    def log_tail(self, n: int = 40) -> str:
        lines = self.log_path.read_text(errors="replace").splitlines()
        return "\n".join(lines[-n:])

    def stop(self) -> int:
        """SIGTERM the child itself, wait for it; -> its exit code."""
        if self.proc is None:
            return 0
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                raise RunFailed("server ignored SIGTERM for 120 s:\n"
                                + self.log_tail())
        return self.proc.returncode

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
