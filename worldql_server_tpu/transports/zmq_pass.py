"""The flush's native ZeroMQ writer (ctypes binding for
native/sendpass.cpp, ISSUE 33).

A peer's first frame of a flush hands its pipe to libzmq's one I/O
thread with a command, and a command that finds that thread asleep
wakes it: a write on an eventfd, paid by the SENDER. :class:`SendPass`
is one C loop over the flush's sockets: the batch's message buffers,
the peers' libzmq socket handles and, for each peer, the indices of its
frames go down in one call, with no interpreter between two sends. On a
plain kernel the wakes are a handful a pass and cheap, and what the
loop saves is the Python a peer and a frame. On a host whose system
calls are dear (the v5e hosts' sandboxed kernel: 6 us a call, 38 us a
write that wakes a sleeper) the sender sits in the waking write while
the I/O thread serves that one peer and goes back to sleep, so EVERY
peer pays a wake whatever the sender's speed (45 us a peer, 24 ms a
flush of 512); there (:func:`poll_cost_us`, measured once at load) the
pass is cut into :data:`DEAR_SYSCALL_THREADS` shares, one a thread, and
the wakes are paid side by side.

``zmq_send`` is resolved from the libzmq instance pyzmq has loaded (a
second copy of the library would not know the sockets); the handles
are ``Socket.underlying``. A library without the symbol, or a pyzmq
backend without a loadable extension, leaves :func:`shared` None and
the per-peer closure (``transports/zeromq.py``) serves, as before.
"""

from __future__ import annotations

import ctypes
import logging
import os
import select
import time
from array import array
from itertools import accumulate, chain

from ..protocol.native_codec import resolve_lib_path

logger = logging.getLogger(__name__)


def _zmq_send_address() -> int:
    """Where ``zmq_send`` lives in the libzmq pyzmq's extension is
    linked to: dlopen of an object that is already loaded returns that
    instance, and dlsym on it searches its dependencies too, so this
    holds for a bundled, a system and a statically linked libzmq."""
    from zmq.backend.cython import _zmq

    ext = ctypes.CDLL(_zmq.__file__)
    return ctypes.cast(ext.zmq_send, ctypes.c_void_p).value


#: A system call dearer than this (us) makes the pass spread its peers
#: over threads (a plain kernel reads 0.3-0.5, the v5e hosts 6.0-6.2).
DEAR_SYSCALL_US = 2.0
#: ... over this many, the caller's included (on a v5e host a pass of
#: 512 peers: 24.4 ms on one thread, 13.6 on two, 9.1 on four, 6.7 on
#: eight; PERF.md section 6, PR 33).
DEAR_SYSCALL_THREADS = 4


def poll_cost_us() -> float:
    """What a system call costs on this host, as one ``poll(eventfd,
    0)`` (the call a ``zmq_send`` makes on a socket that was idle for a
    tick): the least mean of a few short bursts."""
    fd = os.eventfd(0, os.EFD_NONBLOCK)
    try:
        poller = select.poll()
        poller.register(fd, select.POLLIN)
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(20):
                poller.poll(0)
            best = min(best, (time.perf_counter() - t0) / 20)
        return best * 1e6
    finally:
        os.close(fd)


class SendPass:
    def __init__(self, lib: ctypes.CDLL, zmq_send: int, threads: int = 1):
        #: shares a pass is cut into, one a thread (1 = the caller's)
        self.threads = threads
        self._zmq_send = zmq_send
        self._fn = lib.wql_send_pass
        self._fn.restype = ctypes.c_int64
        self._fn.argtypes = [ctypes.c_void_p] * 2 + [ctypes.c_int64] \
            + [ctypes.c_void_p] * 6 + [ctypes.c_int64]

    def __call__(self, payloads: list[bytes], handles: list[int],
                 frames: list[list[int]]):
        """``handles[p]`` takes ``payloads[i] for i in frames[p]``, one
        non-blocking message each, peer after peer. Returns ``(total,
        taken, err)``: the frames taken in all, and for each peer how
        many its socket took from the front and the errno that stopped
        it (0 = none; ``EAGAIN`` = its high-water mark)."""
        n = len(handles)
        socks = array("Q", handles)
        off = array("q", accumulate(map(len, frames), initial=0))
        idx = array("i", chain.from_iterable(frames))
        try:
            bufs = (ctypes.c_char_p * len(payloads))(*payloads)
        except TypeError:   # a buffer that is not ``bytes``
            payloads = [bytes(payload) for payload in payloads]
            bufs = (ctypes.c_char_p * len(payloads))(*payloads)
        lens = array("q", map(len, payloads))
        taken = array("i", bytes(4 * n))
        err = array("i", bytes(4 * n))
        total = self._fn(
            self._zmq_send, socks.buffer_info()[0], n,
            off.buffer_info()[0], idx.buffer_info()[0],
            bufs, lens.buffer_info()[0],
            taken.buffer_info()[0], err.buffer_info()[0], self.threads,
        )
        return total, taken, err


def load() -> SendPass | None:
    """Load the native send pass, or None (the per-peer closure
    serves). Honors WQL_NATIVE_CODEC exactly like the message codec."""
    lib_path = resolve_lib_path()
    if lib_path is None or not lib_path.exists():
        return None
    try:
        lib = ctypes.CDLL(str(lib_path))
        abi = getattr(lib, "wql_sendpass_abi", None)
        if abi is None:
            logger.warning(
                "native library has no send pass (stale build) — "
                "ZeroMQ flushes are written peer by peer"
            )
            return None
        abi.restype = ctypes.c_int64
        abi.argtypes = []
        if abi() != 2:
            logger.warning("native send pass ABI mismatch — peer by peer")
            return None
        dear = poll_cost_us() > DEAR_SYSCALL_US
        return SendPass(lib, _zmq_send_address(),
                        DEAR_SYSCALL_THREADS if dear else 1)
    except (OSError, AttributeError, ImportError) as exc:
        logger.warning("native send pass unavailable: %s", exc)
        return None


_shared: SendPass | None = None
_shared_loaded = False


def shared() -> SendPass | None:
    """Process-wide lazily-loaded instance."""
    global _shared, _shared_loaded
    if not _shared_loaded:
        _shared = load()
        _shared_loaded = True
    return _shared
