"""ColumnarIngest: the wire→SoA entity fast path (PR 11).

Sits between the transport recv loop and the EntityPlane. The
transport hands every buffer it takes from its socket to ``hold``; the
HELD batch is staged by ONE ``process_batch`` a tick edge (the pump
calls the transport's drain before each of its flushes, so an update
received, or still in the socket, when a flush starts folds in that
flush), or sooner when ``hold`` says so: the batch reached
``RECV_DRAIN_MAX`` messages or ``_RUN_ROWS_MAX`` rows, or the buffer is
anything but an entity-update LocalMessage, which is then staged with
everything held before it and so routed on receipt, in arrival order.
A staging is a whole batch's wire buffers through ONE GIL-releasing
native decode (``protocol/entity_wire.wql_decode_entities``) that
classifies each buffer and lands every fast buffer's entities in
shared SoA columns (a call costs ~150 us whatever it holds and ~26 us
a message: staged a receive, a tick's 80 messages cost 15 ms of loop,
staged once 2.3; PERF.md section 6, PR 37).
This module then walks the batch IN ARRIVAL ORDER, coalescing
consecutive fast buffers into one ``EntityPlane.ingest_columns`` run
(zero per-entity Python) and routing everything else — removals,
non-entity instructions, exotic encodings, malformed bytes — through
the transport's ordinary per-message path, so semantics never depend
on the fast path being available.

Admission parity with the router choke point: each fast message still
pays the governor's ``admit`` (entity class: token buckets + counting,
sheds only rate-limited abusers), the transport's unknown-sender drop
(``sender_known``), and the ``codec.decode``/``router.dispatch``
failpoints — fault injection and overload control see the columnar
path exactly as they see the object path.

A stale native library (``active`` False) degrades the whole batch to
the slow route: identical behavior, object-path speed.
"""

from __future__ import annotations

import asyncio
import logging
import uuid as uuid_mod

import numpy as np

from ..protocol import Instruction, entity_wire
from ..protocol.entity_wire import RECV_DRAIN_MAX  # noqa: F401 (re-export)
from ..robustness import failpoints

logger = logging.getLogger(__name__)

#: rows staged in one synchronous columnar pass before the event loop
#: gets a turn. Updates of live entities are vectorized and a pass this
#: size costs them under a millisecond; rows that REGISTER an entity
#: take the per-entity path inside the pass (~0.1 ms each), and a
#: drained burst of them — 100,000 in one recv batch — held the loop,
#: ticker and /healthz included, for 10 s and more.
_RUN_ROWS_MAX = 4096

_MSG_COUNTER = {
    int(Instruction.GLOBAL_MESSAGE): "messages.global_message",
    int(Instruction.LOCAL_MESSAGE): "messages.local_message",
}


class ColumnarIngest:
    """One per server (``--entity-sim``). Event-loop owned.

    ``hold`` takes a buffer (and, on a cluster shard, its trace ctx in
    lockstep) and says whether the held batch must be staged now;
    ``stage`` cuts the batch and stages it. Cuts are made without an
    await, in the order the buffers left the socket, and staged one at
    a time in the order they were cut (the decoder's scratch columns
    are shared, and a slow-routed message may await): so whichever
    task cuts (the recv loop at a bound or a buffer that cannot wait,
    the pump at its flush's start, ``stop``), arrival order holds
    across fast and slow messages."""

    def __init__(self, plane, sender_known, governor=None, metrics=None,
                 wire="auto", on_error=None):
        self.plane = plane
        self._sender_known = sender_known
        self._governor = governor
        self.metrics = metrics
        self._wire = entity_wire.shared() if wire == "auto" else wire
        self._on_error = on_error
        # the held batch: wire buffers awaiting the next staging, the
        # cluster trace ctxs in lockstep (none on a plain server), and
        # the rows the buffers looked like holding when they were taken
        self._held: list[bytes] = []
        self._held_ctxs: list[tuple[int, int]] = []
        self._held_rows = 0
        self._staging = asyncio.Lock()  # one staging at a time, FIFO
        # stats (entity_ingest gauge)
        self.batches = 0        # held batches through the native decode
        self.fast_messages = 0  # messages consumed columnar
        self.edge_messages = 0  # ... of them staged by a flush-start drain
        self.slow_messages = 0  # messages routed through the object path
        self.dropped = 0        # unknown sender / shed / decode-contained
        self.rows = 0           # entity rows staged columnar
        self.decode_fallbacks = 0  # native decode errors → object path

    @property
    def active(self) -> bool:
        """The native columnar decode is available (a stale ``.so``
        turns this off and every message takes the slow route)."""
        return (
            self._wire is not None
            and self._wire.can_decode
            and self.plane is not None
        )

    def stats(self) -> dict:
        return {
            "active": int(self.active),  # 0/1: prometheus-friendly
            "batches": self.batches,
            "fast_messages": self.fast_messages,
            "edge_messages": self.edge_messages,
            "slow_messages": self.slow_messages,
            "dropped": self.dropped,
            "rows": self.rows,
            "decode_fallbacks": self.decode_fallbacks,
        }

    def hold(self, data: bytes, ctx: tuple[int, int] | None = None) -> bool:
        """Take one buffer into the held batch. True = stage now: the
        batch is at a bound, or this buffer is not an entity update
        and must not wait (``stage`` routes it behind what was held
        before it)."""
        rows = entity_wire.peek_update_rows(data)
        self._held.append(data)  # wql: allow(unbounded-ingest) — staged at RECV_DRAIN_MAX messages, below
        if ctx is not None:
            self._held_ctxs.append(ctx)  # wql: allow(unbounded-ingest) — lockstep with _held, same bound
        self._held_rows += rows
        return (
            rows == 0
            or len(self._held) >= RECV_DRAIN_MAX
            or self._held_rows >= _RUN_ROWS_MAX
        )

    async def stage(self, slow_route, edge: bool = False) -> None:
        """Cut the held batch and stage it (``process_batch``).
        ``edge``: the cut is a flush-start drain's, and its fast
        messages count as ``edge_messages``. Never raises."""
        datas, ctxs, rows = self._held, self._held_ctxs, self._held_rows
        if not datas:
            return
        self._held, self._held_ctxs, self._held_rows = [], [], 0
        try:
            await self._staging.acquire()
        except BaseException:
            # cancelled in the queue (a stopping pump): the cut goes
            # back to the head of the batch for stop()'s staging
            self._held[:0] = datas
            self._held_ctxs[:0] = ctxs
            self._held_rows += rows
            raise
        try:
            fast = self.fast_messages
            await self.process_batch(datas, slow_route, ctxs=ctxs or None)
            if edge:
                self.edge_messages += self.fast_messages - fast
        finally:
            self._staging.release()

    async def process_batch(self, datas: list[bytes], slow_route,
                            ctxs: list[tuple[int, int]] | None = None) -> None:
        """Stage one batch. ``slow_route(data, ctx)`` is the
        transport's ordinary single-message path (decode → router);
        per-message errors are contained here exactly like the
        transport's own loop contains them. Never raises.

        ``ctxs`` (clustered shards) carries the per-message router
        trace context the transport stripped off before the native
        classifier — slow-routed messages get theirs back so the
        object path still threads ``Message.trace_ctx``; columnar-
        consumed updates never materialize a Message (same as the
        single-process fast path) and close the e2e clock in the
        delivery plane instead."""
        if not self.active:
            for i, data in enumerate(datas):
                await self._slow(data, slow_route,
                                 ctxs[i] if ctxs else None)
            return
        self.batches += 1
        try:
            # entities.decode_native: the PR 11 fast path's loss
            # boundary — a native decode failure (or an armed chaos
            # fault) degrades THIS batch to the object route, counted,
            # with identical semantics
            failpoints.fire("entities.decode_native")
            res = self._wire.decode(datas)
        except Exception:
            self.decode_fallbacks += 1
            if self.metrics is not None:
                self.metrics.inc("sim.decode_fallbacks")
            logger.exception(
                "native entity decode failed — batch of %d messages "
                "degraded to the object path", len(datas),
            )
            for i, data in enumerate(datas):
                await self._slow(data, slow_route,
                                 ctxs[i] if ctxs else None)
            return
        run_idx: list[int] = []
        run_senders: list[uuid_mod.UUID] = []
        run_rows = 0
        for i in range(len(datas)):
            if res.status[i]:
                try:
                    sender = self._admit(i, res)
                except Exception:
                    self._contain("columnar admission failed — "
                                  "message dropped")
                    continue
                if sender is not None:
                    run_idx.append(i)  # wql: allow(unbounded-ingest) — bounded by RECV_DRAIN_MAX, behind governor admit above
                    run_senders.append(sender)  # wql: allow(unbounded-ingest) — same bound
                    run_rows += int(res.ent_count[i])
                    if run_rows >= _RUN_ROWS_MAX:
                        self._flush_run(run_idx, run_senders, datas, res)
                        run_rows = 0
                        await asyncio.sleep(0)
                    continue
                self.dropped += 1
                continue
            # a slow message breaks the run: flush staged work first so
            # per-entity arrival order survives (a removal after an
            # update must see the update already staged)
            self._flush_run(run_idx, run_senders, datas, res)
            run_rows = 0
            await self._slow(datas[i], slow_route,
                             ctxs[i] if ctxs else None)
        self._flush_run(run_idx, run_senders, datas, res)

    async def _slow(self, data: bytes, slow_route,
                    ctx: tuple[int, int] | None = None) -> None:
        self.slow_messages += 1
        try:
            if ctx is not None:
                await slow_route(data, ctx)
            else:
                await slow_route(data)
        except Exception:
            self._contain("error processing inbound message — dropped")

    def _admit(self, i: int, res) -> uuid_mod.UUID | None:
        """Transport + governor admission for one fast message; None =
        drop (unknown sender, or shed by the governor — counted
        there). Mirrors the object path: codec.decode and
        router.dispatch failpoints fire here too."""
        failpoints.fire("codec.decode")
        sender = uuid_mod.UUID(bytes=res.sender_keys[i].tobytes())
        if not self._sender_known(sender):
            return None  # transport policy: unknown senders are ignored
        if self.metrics is not None:
            counter = _MSG_COUNTER.get(int(res.instr[i]))
            if counter is not None:
                self.metrics.inc(counter)
        failpoints.fire("router.dispatch")
        governor = self._governor
        if governor is not None and not governor.admit(
            Instruction(int(res.instr[i])), sender, True
        ):
            return None  # shed — classified and counted by the governor
        return sender

    def _flush_run(self, run_idx: list[int], run_senders: list,
                   datas: list[bytes], res) -> None:
        """Stage one run of consecutive fast messages as a single
        columnar pass through the plane."""
        if not run_idx:
            return
        try:
            worlds = []
            for i in run_idx:
                off = int(res.world_off[i])
                raw = datas[i][off:off + int(res.world_len[i])]
                worlds.append(raw.decode("utf-8"))
            counts = res.ent_count[run_idx]
            row_idx = np.concatenate([
                np.arange(
                    res.ent_start[i], res.ent_start[i] + res.ent_count[i]
                )
                for i in run_idx
            ])
            applied = self.plane.ingest_columns(
                run_senders, worlds, counts,
                res.uuid_keys[row_idx], res.pos[row_idx],
                res.vel[row_idx], res.has_vel[row_idx],
            )
            self.fast_messages += len(run_idx)
            self.rows += int(counts.sum())
            if self.metrics is not None:
                self.metrics.inc("messages.entity_batches", len(run_idx))
                if applied:
                    self.metrics.inc("messages.entity_ops", applied)
        except UnicodeDecodeError:
            # the object path would raise DeserializeError → dropped
            self._contain("invalid world bytes in entity batch — dropped")
        except Exception:
            self._contain("columnar staging failed — run dropped")
        finally:
            run_idx.clear()
            run_senders.clear()

    def _contain(self, msg: str) -> None:
        self.dropped += 1
        logger.exception(msg)
        if self._on_error is not None:
            try:
                self._on_error()
            except Exception:
                pass
