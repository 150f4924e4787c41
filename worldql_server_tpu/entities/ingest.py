"""ColumnarIngest: the wire→SoA entity fast path (PR 11).

Sits between the transport recv loop and the EntityPlane: a whole recv
batch's wire buffers go through ONE GIL-releasing native decode
(``protocol/entity_wire.wql_decode_entities``) that classifies each
buffer and lands every fast buffer's entities in shared SoA columns.
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
    """One per server (``--entity-sim``). Event-loop owned."""

    def __init__(self, plane, sender_known, governor=None, metrics=None,
                 wire="auto", on_error=None):
        self.plane = plane
        self._sender_known = sender_known
        self._governor = governor
        self.metrics = metrics
        self._wire = entity_wire.shared() if wire == "auto" else wire
        self._on_error = on_error
        # stats (entity_ingest gauge)
        self.batches = 0        # recv batches through the native decode
        self.fast_messages = 0  # messages consumed columnar
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
            "slow_messages": self.slow_messages,
            "dropped": self.dropped,
            "rows": self.rows,
            "decode_fallbacks": self.decode_fallbacks,
        }

    async def process_batch(self, datas: list[bytes], slow_route,
                            ctxs: list[tuple[int, int]] | None = None) -> None:
        """Consume one recv batch. ``slow_route(data, ctx)`` is the
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
