"""Concurrent hammer for the GIL-releasing native entry points.

    LD_PRELOAD=$(g++ -print-file-name=libtsan.so) \
    TSAN_OPTIONS="halt_on_error=1 report_signal_unsafe=0" \
    WQL_NATIVE_CODEC=native/libwqlcodec-tsan.so \
      python -m tools.tsan_hammer [--threads 8] [--iters 150]

All six exported entry points release the GIL for their whole body
(``wql_decode_entities``, ``wql_encode_queries``,
``wql_encode_entity_frames``, ``wql_encode_interest_frames``,
``wql_areamap_probe``, ``wql_send_pass``), so any hidden shared state inside
``native/codec.cpp`` / ``spatial.cpp`` / ``sendpass.cpp`` — a static
scratch buffer, an unguarded counter, lazily-built tables — is a real
data race the moment two event loops, a collect worker, and a bench
run call in concurrently. This driver creates genuine overlap:
N threads (>=8 in CI), each with its OWN ``EntityWire`` (the Python
scratch columns are per-instance by design — the domain analyzer's
cross-domain-state rule polices the Python side; THIS tool polices
the native side), all calling into one loaded library behind a start
barrier. Under the TSan build, any race aborts the process
(halt_on_error); uninstrumented, the determinism check still catches
cross-thread result corruption.

Exits 0 on success, 1 on corruption or a thread exception, 2 when the
native library is missing (CI must build it first — a vacuous green
is worse than a red).
"""

from __future__ import annotations

import argparse
import sys
import threading
import uuid

import numpy as np
import zmq

from worldql_server_tpu.protocol import (
    Instruction,
    Message,
    entity_wire,
    serialize_message,
)
from worldql_server_tpu.protocol.types import Entity, Vector3
from worldql_server_tpu.spatial import native_keys
from worldql_server_tpu.transports import zmq_pass


def _batch(tid: int, n: int = 24) -> list[bytes]:
    """A decode batch with per-thread content: fast-path entity
    updates, slow-path shapes, and one malformed buffer."""
    rng = np.random.default_rng(tid)
    datas: list[bytes] = []
    sender = uuid.UUID(int=(tid << 64) | 0x1234)
    for i in range(n - 3):
        ent = Entity(
            uuid=uuid.UUID(int=(tid << 64) | i),
            position=Vector3(*(rng.uniform(-512, 512, 3).tolist())),
            world_name="w",
        )
        datas.append(serialize_message(Message(
            instruction=Instruction.LOCAL_MESSAGE, sender_uuid=sender,
            world_name="w", entities=[ent],
        )))
    datas.append(serialize_message(Message(
        instruction=Instruction.LOCAL_MESSAGE, sender_uuid=sender,
        world_name="w", parameter="entity.remove", entities=[],
    )))
    datas.append(serialize_message(Message(
        instruction=Instruction.RECORD_CREATE, sender_uuid=sender,
        world_name="w", entities=[],
    )))
    datas.append(bytes([tid & 0xFF]) * 11)   # malformed
    return datas


def _expected(wire: entity_wire.EntityWire, datas: list[bytes]) -> tuple:
    """Single-threaded reference outcome for the determinism check."""
    batch = wire.decode(datas)
    return (batch.status.tolist(), batch.total,
            bytes(batch.sender_keys[0]))


def hammer(threads: int, iters: int) -> int:
    wire0 = entity_wire.load()
    if wire0 is None or native_keys._native is None:
        print("tsan-hammer: native library not loaded — build "
              "native/ first (make -C native [tsan])", file=sys.stderr)
        return 2
    if not (wire0.can_decode and wire0.can_encode_frames
            and wire0.can_encode_interest):
        print("tsan-hammer: stale library without the entity entry "
              "points", file=sys.stderr)
        return 2
    if zmq_pass.load() is None:
        print("tsan-hammer: stale library without the send pass",
              file=sys.stderr)
        return 2

    barrier = threading.Barrier(threads)
    errors: list[str] = []

    def worker(tid: int) -> None:
        try:
            wire = entity_wire.load()        # own scratch, same .so
            datas = _batch(tid)
            want = _expected(wire, datas)
            n = 16
            wid = np.full(n, tid % 7, np.int32)
            pos = np.arange(n * 3, dtype=np.float64).reshape(n, 3) + tid
            sid = np.arange(n, dtype=np.int32)
            rep = np.zeros(n, np.int8)
            keys = np.frombuffer(
                b"".join(uuid.UUID(int=(tid << 64) | i).bytes
                         for i in range(n)),
                np.uint8).reshape(n, 16)
            # this thread's own batch of interest frames: three worlds,
            # an empty frame, tombstones; its frames as one thread alone
            # encodes them are what every later call must give
            interest = (
                [b"entity.frame.delta:%08x:%08x" % (tid, f)
                 for f in range(4)],
                [b"w", b"annex", b"", b"w"],
                np.array([0, 9, 9, 13, n], np.int64),
                keys, pos, (np.arange(n) % 3 == tid % 3).astype(np.uint8),
            )
            want_frames = wire.encode_interest_frames(*interest)
            # the flush's send pass: this thread's own sockets (a
            # libzmq socket belongs to one thread), the one library
            send_pass = zmq_pass.load()
            send_pass.threads = 1 + tid % 3     # its own helpers too
            ctx = zmq.Context()
            pulls, pushes = [], []
            for k in range(24):                 # three whole shares
                pulls.append(ctx.socket(zmq.PULL))
                pulls[-1].bind(f"inproc://hammer-{tid}-{k}")
                pushes.append(ctx.socket(zmq.PUSH))
                pushes[-1].connect(f"inproc://hammer-{tid}-{k}")
            handles = [push.underlying for push in pushes]
            payloads = [bytes([tid, i]) * (i + 1) for i in range(4)]
            table = [[0, 1, 2, 3], [], [1, 3]] * 8
            passed = (sum(map(len, table)), list(map(len, table)),
                      [0] * len(table))
            barrier.wait()
            for it in range(iters):
                # 1. wql_decode_entities — per-thread scratch, shared .so
                batch = wire.decode(datas)
                got = (batch.status.tolist(), batch.total,
                       bytes(batch.sender_keys[0]))
                if got != want:
                    raise AssertionError(
                        f"decode corrupted under concurrency: "
                        f"{got[:2]} != {want[:2]}")
                # 2. wql_encode_queries (+ fused key twin)
                native_keys.query_keys(wid, pos, 16, seed=tid)
                enc = native_keys.encode_queries(
                    wid, pos, sid, rep, cap=n + 8, cube_size=16,
                    seed=it & 0xFF)
                if enc is not None and len(enc[0]) != n + 8:
                    raise AssertionError("encode_queries capacity drift")
                # 3. wql_encode_entity_frames
                frames = wire.encode_frames(keys, keys, pos, b"w")
                if len(frames) != n or not all(frames):
                    raise AssertionError("encode_frames dropped a frame")
                # 4. wql_encode_interest_frames
                if wire.encode_interest_frames(*interest) != want_frames:
                    raise AssertionError(
                        "interest frames corrupted under concurrency")
                # 5. wql_areamap_probe (every few iters: it builds a
                # whole probe table per call)
                if it % 16 == 0:
                    probe = native_keys.areamap_probe(64, 64, seed=tid)
                    if probe is not None and probe["matched_rows"] < 0:
                        raise AssertionError("areamap probe corrupt")
                # 6. wql_send_pass
                total, taken, errs = send_pass(payloads, handles, table)
                got = [[pull.recv(zmq.DONTWAIT) for _ in owed]
                       for pull, owed in zip(pulls, table)]
                if (total, list(taken), list(errs)) != passed \
                        or got != [[payloads[i] for i in owed]
                                   for owed in table]:
                    raise AssertionError(
                        f"send pass corrupted under concurrency: "
                        f"{total} {list(taken)} {list(errs)}")
            ctx.destroy(linger=0)   # (this thread's own sockets)
        except Exception as exc:  # noqa: BLE001 — reported, not dropped
            errors.append(f"thread {tid}: {type(exc).__name__}: {exc}")

    ts = [threading.Thread(target=worker, args=(i,), name=f"hammer-{i}")
          for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errors:
        for e in errors:
            print(f"tsan-hammer: {e}", file=sys.stderr)
        return 1
    print(f"tsan-hammer: OK — {threads} threads x {iters} iters over "
          "wql_decode_entities / wql_encode_queries / "
          "wql_encode_entity_frames / wql_encode_interest_frames / "
          "wql_areamap_probe / wql_send_pass")
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.tsan_hammer",
        description="Hammer the GIL-releasing native entry points "
                    "from many threads (run under the TSan build).",
    )
    parser.add_argument("--threads", type=int, default=8)
    parser.add_argument("--iters", type=int, default=150)
    args = parser.parse_args(argv)
    if args.threads < 2:
        print("need >= 2 threads for overlap", file=sys.stderr)
        return 2
    return hammer(args.threads, args.iters)


if __name__ == "__main__":
    raise SystemExit(main())
