"""BASELINE benchmark harness — all five load configs (BASELINE.md).

Default (no --config) runs config 5, the north star: batched
LocalMessage fan-out at 1M entities. Prints ONE JSON line on stdout:

  {"metric": "local_fanout_engine_tick_ms", "value": ..., "unit": "ms",
   "vs_baseline": <cpu_p99 / engine_tick>, "engine_p99_ms": ...,
   "sustained_e2e_tick_ms": ..., "p50_ms_depth1": ...,
   "p99_ms_depth1": ..., "p50_ms_depth2": ..., "p99_ms_depth2": ...,
   "target_p99_ms": 5.0}

The headline ``value`` is the ENGINE-side tick — host encode + H2D
enqueue (``dispatch_ms``) + device compute, link excluded: the
concurrency probe (``pair_overlap_ratio``) says whether the link
serializes independent dispatches; where it does, a wall that includes
the link measures the link, not the code. The e2e numbers stay alongside: ``sustained_e2e_tick_ms``
(best-of-3 depth-8 pipelined wall) and the p50/p99 keys — per-tick
dispatch→collect wall at depth 1 (unpipelined: the honest request
latency on THIS link) and depth 2 (double buffered). ``vs_baseline``
for config 5 is the CPU reference backend's p99 over the engine tick
(throughput advantage); for the latency-budget configs (1, 2, 3, 4)
it is budget/actual, so > 1.0 means the budget is met.

`--config N` selects a BASELINE config (one JSON line each):
  1  256 WS clients echo loop through the REAL server on the CPU
     backend — correctness oracle + CPU transport baseline
     (metric: end-to-end delivery p99 vs the 5 ms budget)
  2  10k random-walk clients, churn resubscribes + radius broadcast,
     20 tick/s budget on the device backend
  3  100k entities, fully-on-device kNN (k=32) tick, single chip
  4  64 worlds x 10k clients on the mesh-sharded backend
  5  1M-entity Zipf-hotspot fan-out (default)
  6  record-op durability workload: RecordCreate handler latency on
     the SQLite store with durability off / wal / sync (metric:
     wal-mode handler p99; vs_baseline = inline-commit p99 over it)
`--all` runs every config, one JSON line per config, config order.

Diagnostics go to stderr. --quick shrinks every shape for smoke runs.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import sys
import time
import uuid as uuid_mod
from collections import deque

import numpy as np

from worldql_server_tpu.observability import FlightRecorder, Tracer
from worldql_server_tpu.observability.spans import NULL_TRACE
from worldql_server_tpu.spatial.hashing import next_pow2


TARGET_P99_MS = 5.0  # BASELINE.md: p99 broadcast fan-out < 5 ms
TICK_BUDGET_MS = 50.0  # BASELINE.md: 20 ticks/s


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def pctl(samples_ms, q: float) -> float:
    return float(np.percentile(np.asarray(samples_ms), q))


def chained_slopes_ms(chains: dict, args: tuple, reps_pair: tuple,
                      *, max_reps: int = 4096) -> dict:
    """Per-iteration DEVICE time of one or more jitted chained loops:
    best-of-3 wall at two rep counts (first call per count excluded —
    compile), then the slope. The fixed per-call overhead — link round
    trip, dispatch, D2H of the scalar result — cancels in the
    difference; only the per-iteration device work scales with reps.
    Single timing discipline for EVERY device probe in this file.

    When several chains are passed (the stage-attribution prefixes),
    every sampling sweep times ALL of them round-robin at the same rep
    count, so a link-congestion epoch inflates each chain's sample
    alike and cancels in the stage DIFFERENCES. Timing the chains in
    separate passes put them in different congestion epochs and made
    the per-stage splits swing run to run — up to a zero-by-difference
    artifact on the largest stage (VERDICT r4 weak #2).

    Three rules the timing follows (whether each still bites on
    today's device is ROADMAP D6):
    * each chain takes a SALT as its first argument, folded into the
      loop-carried state, so that every timed call differs and none
      can be answered from a cache of an identical dispatch;
    * the result is FETCHED (``int()``), never just
      ``block_until_ready`` — a D2H read is the one barrier that
      cannot return before the device finishes;
    * if the hi-lo wall delta of the CHEAPEST chain doesn't clear link
      jitter, the rep pair escalates (×4) until it does or hits
      ``max_reps`` — a slope inside the noise floor would otherwise
      clamp to a fake 0.
    """
    import jax.numpy as jnp

    salt_rng = np.random.default_rng(0xC0FFEE)
    jitter_floor_s = 0.08

    def timed_all(reps: int) -> dict:
        for fn in chains.values():
            int(fn(jnp.int32(1), *args, reps))  # compile
        best = {name: float("inf") for name in chains}
        for _ in range(3):
            for name, fn in chains.items():
                salt = jnp.int32(salt_rng.integers(1, 1 << 20))
                t0 = time.perf_counter()
                int(fn(salt, *args, reps))
                best[name] = min(best[name], time.perf_counter() - t0)
        return best

    lo, hi = reps_pair
    t_lo, t_hi = timed_all(lo), timed_all(hi)
    while (min(t_hi[n] - t_lo[n] for n in chains) < jitter_floor_s
           and hi * 4 <= max_reps):
        lo, t_lo = hi, t_hi
        hi *= 4
        t_hi = timed_all(hi)
    return {n: (t_hi[n] - t_lo[n]) / (hi - lo) * 1e3 for n in chains}


def chained_slope_ms(chained, args: tuple, reps_pair: tuple,
                     *, max_reps: int = 4096) -> float:
    """Single-chain convenience wrapper over :func:`chained_slopes_ms`."""
    return chained_slopes_ms(
        {"_": chained}, args, reps_pair, max_reps=max_reps
    )["_"]


# --------------------------------------------------------------------
# shared workload generation (configs 2, 4, 5)
# --------------------------------------------------------------------


#: config-5 crowd model (BASELINE "Zipf hotspot"): cube popularity
ZIPF_S = 1.0
#: physical occupancy bound per 16 m subscription cube — an MMO siege
#: packs a few hundred players into one cube, not tens of thousands;
#: overflow spills down the popularity ranking like a crowd overflowing
#: a plaza. Also the fan-out degree bound (K = next_pow2 of max run).
OCCUPANCY_CAP = 256

_zipf_stats: dict = {}


def make_positions(rng: np.random.Generator, n: int) -> np.ndarray:
    """Zipf(s=ZIPF_S)-popularity crowd over subscription cubes: cube
    rank r draws mass ∝ 1/r^s, occupancy capped at OCCUPANCY_CAP with
    waterfill spill to the next ranks. Positions are uniform WITHIN
    each entity's cube. This is the distribution the two-tier gather's
    overflow budget was built for — the uniform-core model it replaces
    (5% of entities in a ±40 box) concentrated orders of magnitude
    less (VERDICT r4 weak #4). Stats of the LAST build are published
    via ``_zipf_stats``."""
    span, cube = 800.0, 16.0
    cells_axis = int(span * 2 / cube)              # 100 per axis
    n_ranked = min(max(n // 4, 1024), cells_axis ** 3)
    # ranked cube list: a shuffled slice of the grid, so popularity is
    # spatially scattered (hotspots are towns, not one mega-blob)
    cell_ids = rng.permutation(cells_axis ** 3)[:n_ranked]
    p = 1.0 / np.arange(1, n_ranked + 1, dtype=np.float64) ** ZIPF_S
    counts = rng.multinomial(n, p / p.sum())
    # waterfill the over-cap excess down the ranking
    excess = int(np.maximum(counts - OCCUPANCY_CAP, 0).sum())
    counts = np.minimum(counts, OCCUPANCY_CAP)
    if excess:
        free = OCCUPANCY_CAP - counts
        take = np.minimum(free, np.maximum(
            excess - (np.cumsum(free) - free), 0
        ))
        counts += take
        assert int(counts.sum()) == n, "waterfill must conserve entities"
    _zipf_stats.update(
        zipf_s=ZIPF_S,
        occupancy_cap=OCCUPANCY_CAP,
        max_cube_occupancy=int(counts.max()),
        occupied_cubes=int((counts > 0).sum()),
        top10_occupancy=[int(c) for c in np.sort(counts)[::-1][:10]],
    )
    cid = np.repeat(cell_ids, counts)
    ix = cid % cells_axis
    iy = (cid // cells_axis) % cells_axis
    iz = cid // (cells_axis * cells_axis)
    corners = np.stack([ix, iy, iz], axis=1) * cube - span
    return corners + rng.uniform(0.0, cube, (n, 3))


def build_index(backend, rng: np.random.Generator, n_subs: int, n_worlds: int):
    from worldql_server_tpu.spatial.quantize import cube_coords_batch

    positions = make_positions(rng, n_subs)
    cubes = cube_coords_batch(positions, backend.cube_size)
    peers = [uuid_mod.UUID(int=i + 1) for i in range(n_subs)]
    world_ids = np.arange(n_subs) * n_worlds // n_subs
    t0 = time.perf_counter()
    for w in range(n_worlds):
        sel = world_ids == w
        backend.bulk_add_subscriptions(
            f"world_{w}", [peers[i] for i in np.flatnonzero(sel)], cubes[sel]
        )
    log(f"index build: {n_subs} subs in {time.perf_counter() - t0:.1f}s")
    return peers, positions, world_ids


def make_query_batch(rng, sub_positions, sub_world_ids, m: int):
    """Queries model entities broadcasting at their own positions: each
    draws a random subscriber and speaks from its cube (20% from a
    fresh random point — mostly-miss traffic)."""
    n_subs = len(sub_positions)
    senders = rng.integers(0, n_subs, m)
    world_ids = sub_world_ids[senders].astype(np.int32)
    positions = sub_positions[senders].copy()
    miss = rng.random(m) < 0.2
    positions[miss] = make_positions(rng, int(miss.sum()))
    return world_ids, positions, senders.astype(np.int32), np.zeros(m, np.int8)


def _force(result) -> int:
    """Materialize a CSR result triple on host (full fetch — warmups
    and paths that need the whole flat array); returns total fan-out."""
    counts, flat, total = result
    np.asarray(counts)
    np.asarray(flat)
    return int(total)


def _collect_compact(backend, result) -> int:
    """Materialize a CSR result the way the server's collect does
    (ISSUE 3): total → counts → on-device pack of the lanes actually
    owed, full fetch only as the fallback — so the timed D2H scales
    with the tick's real fan-out, not the capacity tier. Returns the
    total fan-out."""
    counts, flat, total = result
    total = int(total)
    t_cap = flat.shape[0]
    if total > t_cap:
        return total     # overflow — caller retries with a bigger cap
    np.asarray(counts)
    if backend._compact_fetch(counts, flat, total, t_cap) is None:
        np.asarray(flat)
    return total


def run_pipelined(backend, batches, csr_cap: int, depth: int, tracer=None):
    """Drive the fan-out engine at a fixed pipeline depth.

    Returns ``(per_tick_latency_ms, sustained_ms, total_fanout)`` where
    latency is each tick's dispatch→collect wall time (the fan-out
    latency a client observes) and sustained is wall/ticks (the
    throughput figure). depth=1 is the unpipelined request latency;
    deeper overlaps transfer and compute of adjacent ticks. The
    collect path is the server's compacted fetch (_collect_compact).

    With an observability ``tracer``, each tick records a span trace
    (dispatch / collect stages) into the tracer's sink — the same
    flight-recorder substrate the server runs, so a 207 s outlier in a
    BENCH run now leaves its own span tree behind (ISSUE 5).
    """
    lat, inflight, total_fanout = [], deque(), 0
    overflow = 0
    # The device buffer is the next power-of-two tier above csr_cap —
    # results are intact (and exact) up to that, so only count a real
    # truncation/overflow-tier sentinel as overflow.
    t_cap = next_pow2(csr_cap)
    t_start = time.perf_counter()

    def drain():
        nonlocal total_fanout, overflow
        t0, trace, (m, result) = inflight.popleft()
        with trace.span("tick.collect"):
            n = _collect_compact(backend, result)
        if n > t_cap:
            overflow += 1
        else:
            total_fanout += n
        trace.tag(fanout=n, overflowed=n > t_cap)
        trace.finish()
        lat.append((time.perf_counter() - t0) * 1e3)

    for i, b in enumerate(batches):
        trace = (
            tracer.begin("tick", tick=i, depth=depth)
            if tracer is not None else NULL_TRACE
        )
        t0 = time.perf_counter()
        with trace.span("tick.dispatch"):
            handle = backend.match_arrays_async(*b, csr_cap=csr_cap)
        inflight.append((t0, trace, handle))
        if len(inflight) >= depth:
            drain()
    while inflight:
        drain()
    sustained = (time.perf_counter() - t_start) / len(batches) * 1e3
    return np.asarray(lat), sustained, total_fanout, overflow


def steady(lat, depth: int):
    """Steady-state latency samples: at depth > 1 the FIRST drained
    tick's wall clock includes the pipeline fill (depth-1 extra
    dispatch walls) plus any first-use-at-this-shape stall — BENCH_r05
    recorded a 207 s first depth-2 tick against a ~1 s steady state
    (see CHANGES.md). It is reported separately, never inside a
    percentile."""
    return lat[1:] if depth > 1 and len(lat) > 1 else lat


def run_pipelined_adaptive(backend, batches, csr_cap: int, depth: int,
                           tracer=None):
    """run_pipelined with capacity retry: the CSR result buffer is the
    dominant device→host payload, so it is sized to the workload's real
    fan-out rather than a worst-case bound — on overflow (total >
    csr_cap, tail dropped on device) the run repeats with double the
    capacity. Returns (lat, sustained, total_fanout, csr_cap)."""
    while True:
        lat, sustained, total, overflow = run_pipelined(
            backend, batches, csr_cap, depth, tracer=tracer
        )
        if not overflow:
            return lat, sustained, total, csr_cap
        csr_cap *= 2
        log(f"csr overflow x{overflow} — retrying with csr_cap={csr_cap}")
        # compile the new shape tier OUTSIDE the timed retry
        _force(backend.match_arrays_async(*batches[0], csr_cap=csr_cap)[1])


# --------------------------------------------------------------------
# real-server delivery phase (part of config 5's JSON): ticker →
# router → PeerMap → live WS sockets, counted at the clients
# --------------------------------------------------------------------


class _RawWs:
    """Minimal RFC 6455 client over raw asyncio streams, for the
    delivery benchmark's counting clients: the measurement must stress
    the SERVER's pump, so the client side cannot afford a full
    WebSocket library parse per frame (~25 µs — it was the bottleneck
    and capped the observed rate at ~10K/s). Sends use a zero mask key
    (legal per RFC: masked bit set, key 0 ⇒ payload XOR is identity),
    so a connection's broadcast frame serializes exactly once."""

    def __init__(self, reader, writer):
        self.reader = reader
        self.writer = writer

    @classmethod
    async def connect(cls, port: int) -> "_RawWs":
        import base64
        import os

        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        key = base64.b64encode(os.urandom(16)).decode()
        writer.write(
            (f"GET / HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
             "Upgrade: websocket\r\nConnection: Upgrade\r\n"
             f"Sec-WebSocket-Key: {key}\r\n"
             "Sec-WebSocket-Version: 13\r\n\r\n").encode()
        )
        await writer.drain()
        status = await reader.readuntil(b"\r\n\r\n")
        if b" 101 " not in status.split(b"\r\n", 1)[0]:
            raise ConnectionError(f"upgrade refused: {status[:80]!r}")
        return cls(reader, writer)

    async def recv_frame(self) -> tuple[int, bytes]:
        """→ (opcode, payload). Server frames are unmasked."""
        h = await self.reader.readexactly(2)
        ln = h[1] & 0x7F
        if ln == 126:
            ln = int.from_bytes(await self.reader.readexactly(2), "big")
        elif ln == 127:
            ln = int.from_bytes(await self.reader.readexactly(8), "big")
        return h[0] & 0x0F, await self.reader.readexactly(ln)

    @staticmethod
    def frame(payload: bytes, opcode: int = 0x2) -> bytes:
        """Complete client→server frame (FIN, zero mask)."""
        n = len(payload)
        if n < 126:
            head = bytes([0x80 | opcode, 0x80 | n])
        elif n < 1 << 16:
            head = bytes([0x80 | opcode, 0x80 | 126]) + n.to_bytes(2, "big")
        else:
            head = bytes([0x80 | opcode, 0x80 | 127]) + n.to_bytes(8, "big")
        return head + b"\x00\x00\x00\x00" + payload

    def send_binary(self, payload: bytes) -> None:
        self.writer.write(self.frame(payload))

    async def close(self) -> None:
        try:
            self.writer.write(self.frame(b"\x03\xe8", opcode=0x8))
            self.writer.close()
        except Exception:
            pass


def _delivery_client_main(port, n_conns, group_base, group, rounds,
                          round_interval, out_q, barrier, done_barrier):
    """One client process: ``n_conns`` live WS connections, co-located
    in cubes of ``group`` peers. Every connection broadcasts once per
    round; every LOCAL_MESSAGE frame any connection receives is counted
    (instruction peeked from the raw frame — no full parse). Reports
    (sent, received, recv_elapsed_s) where recv_elapsed runs from the
    barrier to the LAST delivery — the honest pump window even when
    the server saturates."""
    import asyncio
    import time

    async def run():
        from worldql_server_tpu.protocol import (
            Instruction, Message, deserialize_message, serialize_message,
        )
        from worldql_server_tpu.protocol.types import Replication, Vector3
        import uuid as uuid_mod

        sem = asyncio.Semaphore(64)

        async def connect_one(i):
            async with sem:
                c = await _RawWs.connect(port)
                # server-assigned-uuid handshake (websocket.rs:51-87)
                op, payload = await c.recv_frame()
                handshake = deserialize_message(payload)
                assert handshake.instruction == Instruction.HANDSHAKE
                my_uuid = uuid_mod.UUID(handshake.parameter)
                gid = group_base + i // group
                pos = Vector3(100.0 * gid, 5.0, 5.0)
                c.send_binary(serialize_message(Message(
                    instruction=Instruction.HANDSHAKE,
                    sender_uuid=my_uuid,
                )))
                c.send_binary(serialize_message(Message(
                    instruction=Instruction.AREA_SUBSCRIBE,
                    world_name="bench", position=pos,
                    sender_uuid=my_uuid,
                )))
                await c.writer.drain()
                return c, my_uuid, gid

        clients = await asyncio.gather(
            *(connect_one(i) for i in range(n_conns))
        )
        state = {"count": 0, "last": 0.0}

        async def drain(c: _RawWs):
            """Chunked frame counter: between the barriers the ONLY
            binary frames the server sends are the LocalMessage
            fan-out (connect/disconnect storms happen outside the
            measured window), so counting opcode-0x2 frames measures
            deliveries without paying any parse. Chunked reads +
            manual walk keep the client at well under 1 µs/frame —
            on this single-core machine every client cycle is stolen
            from the server under test."""
            reader = c.reader
            buf = b""
            need_skip = 0       # oversized-frame payload left to skip
            try:
                while True:
                    chunk = await reader.read(1 << 16)
                    if not chunk:
                        return
                    buf += chunk
                    pos = 0
                    n = len(buf)
                    counted = 0
                    while True:
                        if need_skip:
                            skip = min(need_skip, n - pos)
                            pos += skip
                            need_skip -= skip
                            if need_skip:
                                break
                        if pos + 2 > n:
                            break
                        b0, b1 = buf[pos], buf[pos + 1]
                        ln = b1 & 0x7F
                        head = 2
                        if ln == 126:
                            if pos + 4 > n:
                                break
                            ln = int.from_bytes(buf[pos + 2:pos + 4], "big")
                            head = 4
                        elif ln == 127:
                            if pos + 10 > n:
                                break
                            ln = int.from_bytes(buf[pos + 2:pos + 10], "big")
                            head = 10
                        op = b0 & 0x0F
                        if ln > (1 << 16):
                            # larger than a read chunk: count and
                            # stream-skip (control frames are <= 125 B
                            # by RFC, so never take this path)
                            if op == 0x2:
                                counted += 1
                            pos += head
                            need_skip = ln
                            continue
                        if pos + head + ln > n:
                            break   # wait for the rest of the frame
                        if op == 0x2:
                            counted += 1
                        elif op == 0x9:
                            # pong MUST echo the ping payload (RFC 6455
                            # §5.5.3) or the server's keepalive treats
                            # the connection as dead after ~40 s
                            c.writer.write(_RawWs.frame(
                                buf[pos + head:pos + head + ln],
                                opcode=0xA,
                            ))
                        elif op == 0x8:   # close
                            return
                        pos += head + ln
                    buf = buf[pos:]
                    if counted:
                        state["count"] += counted
                        state["last"] = time.perf_counter()
            except Exception:
                pass

        drains = [asyncio.create_task(drain(c)) for c, _, _ in clients]
        # each connection's broadcast frame, fully framed, built once
        frames = [
            _RawWs.frame(serialize_message(Message(
                instruction=Instruction.LOCAL_MESSAGE,
                world_name="bench",
                position=Vector3(100.0 * gid, 5.0, 5.0),
                replication=Replication.EXCEPT_SELF,
                sender_uuid=my_uuid,
            )))
            for _, my_uuid, gid in clients
        ]

        # quiesce before the barrier: the connection storm's
        # PeerConnect broadcasts (O(n²) frames) must fully drain, or
        # their tail is counted as deliveries (observed: +66%)
        quiet = 0
        while quiet < 10:
            before = state["count"]
            await asyncio.sleep(0.1)
            quiet = quiet + 1 if state["count"] == before else 0
        state["count"] = 0
        await asyncio.to_thread(barrier.wait)
        t0 = time.perf_counter()
        state["last"] = t0
        sent = 0
        for r in range(rounds):
            for (c, _, _), data in zip(clients, frames):
                c.writer.write(data)
            for c, _, _ in clients:
                await c.writer.drain()
            sent += len(clients)
            pace = t0 + (r + 1) * round_interval - time.perf_counter()
            if pace > 0:
                await asyncio.sleep(pace)
        # wait for the delivery tail: done the moment the full expected
        # count lands (groups never span processes, so this process
        # knows its own total), else when the count stops moving for
        # 2 s — a warm server can pause >0.5 s mid-flush (GC, tick
        # stalls), and a short settle window mistook that pause for
        # the end of the tail (observed: 85% delivery on a re-run in
        # the same interpreter vs 100% fresh)
        expected_here = len(clients) * (group - 1) * rounds
        settled = 0
        while settled < 20 and state["count"] < expected_here:
            before = state["count"]
            await asyncio.sleep(0.1)
            settled = settled + 1 if state["count"] == before else 0
        out_q.put((sent, state["count"], state["last"] - t0))
        # hold the connections until EVERY process has reported: an
        # early close floods the server with PeerDisconnect broadcast
        # storms that stall the other processes' still-running
        # measurement (observed as a cascading early-settle)
        await asyncio.to_thread(done_barrier.wait)
        for d in drains:
            d.cancel()
        for c, _, _ in clients:
            await c.close()

    asyncio.run(run())


def bench_delivery(args, *, delivery_workers: int = 0,
                   n_procs: int = 2, conns_per_proc: int | None = None,
                   ) -> dict:
    """Drive the REAL server's full delivery path at config-5 message
    rates: N live WS peers in co-located groups, every peer
    broadcasting per round, resolution through the tick batcher and
    delivery through PeerMap.deliver_batch's sync fast path — or,
    with ``delivery_workers`` > 0, through the sharded delivery plane
    (shared-memory rings + sender worker processes, ISSUE 6). The
    metric is deliveries/s observed at the client side of the sockets
    — the number the engine's queries/s has to be multiplied down by
    until this path keeps up (VERDICT r4 weak #3)."""
    import asyncio
    import multiprocessing as mp

    # one client process per ~512 connections: this sandbox is a
    # single core, so every client process cycle competes with the
    # server under test — fewer, leaner processes measure more server
    if conns_per_proc is None:
        conns_per_proc = 64 if args.quick else 512
    group = 8
    rounds = 20 if args.quick else 100
    round_interval = 0.05          # every peer speaks at 20 Hz
    n_clients = n_procs * conns_per_proc

    async def scenario():
        from tests.client_util import free_port
        from worldql_server_tpu.engine.config import Config
        from worldql_server_tpu.engine.server import WorldQLServer

        config = Config()
        config.store_url = "memory://"
        config.ws_port = free_port()
        config.http_enabled = False
        config.zmq_enabled = False
        config.spatial_backend = "cpu"
        config.tick_interval = 0.05
        config.delivery_workers = delivery_workers
        # one tick's worth of frames per shard at peak, with headroom
        config.delivery_ring_bytes = 32 * 1024 * 1024
        server = WorldQLServer(config)
        await server.start()
        ctx = mp.get_context("spawn")
        barrier = ctx.Barrier(n_procs + 1)
        done_barrier = ctx.Barrier(n_procs)
        out_q = ctx.Queue()
        procs = [
            ctx.Process(
                target=_delivery_client_main,
                args=(config.ws_port, conns_per_proc,
                      p * (conns_per_proc // group), group, rounds,
                      round_interval, out_q, barrier, done_barrier),
                daemon=True,
            )
            for p in range(n_procs)
        ]
        # per-core efficiency (ROADMAP item 1): deliveries ÷ CPU-seconds
        # actually burned by the server-side processes (this process +
        # sender workers) over the measured window — the same
        # /proc-based accounting behind the router's live
        # deliveries_per_s_per_core gauge, so the gate floor and the
        # fleet gauge speak one unit
        from worldql_server_tpu.cluster.federation import _proc_cpu_s

        clk_tck = float(os.sysconf("SC_CLK_TCK"))

        def server_cpu_s() -> float:
            total = _proc_cpu_s(os.getpid(), clk_tck)
            plane_ = server.delivery_plane
            if plane_ is not None:
                for shard in plane_._shards:
                    if shard.proc is not None and shard.proc.pid:
                        total += _proc_cpu_s(shard.proc.pid, clk_tck)
            return total

        try:
            for p in procs:
                p.start()
            # the barrier releases once every client is connected and
            # subscribed; connection-storm traffic (PeerConnect
            # broadcasts) happens before it and is not counted. A dead
            # child would strand the barrier — bounded wait + liveness
            # check instead of hanging the whole bench.
            await asyncio.to_thread(barrier.wait, 120)
            cpu0 = server_cpu_s()
            results = [
                await asyncio.to_thread(out_q.get, True, 180)
                for _ in procs
            ]
            cpu_used_s = max(server_cpu_s() - cpu0, 0.0)
            for p in procs:
                p.join(timeout=30)
            ticker = server.ticker
            plane = server.delivery_plane
            plane_stats = None
            if plane is not None:
                await asyncio.sleep(0.4)  # one worker-stats interval
                plane_stats = {
                    "plane": plane.stats(),
                    "per_worker": [
                        plane.worker_stats(i)
                        for i in range(delivery_workers)
                    ],
                }
            # frame clock (ISSUE 7): dispatch-stamp → socket-write-
            # complete, closed in the worker for the sharded plane and
            # at batch completion for the in-process pump — the honest
            # p99-fan-out number the 5 ms SLO is quoted against
            lat = server.metrics.snapshot()["latency"]
            e2e = {
                "frame": lat.get("frame.e2e_ms"),
                "delivery": lat.get("delivery.e2e_ms"),
            }
            return results, e2e, {
                "ticks": ticker.ticks if ticker else 0,
                "server_cpu_s": cpu_used_s,
                # outbound frame bytes at the delivery boundary
                # (PeerMap.bytes_delivered, ISSUE 18) — the volume the
                # interest manager exists to shrink
                "bytes_delivered": server.peer_map.bytes_delivered,
                "delta_ratio": server.metrics.snapshot()["gauges"].get(
                    "frame.delta_ratio"
                ),
                "last_batch": ticker.last_batch if ticker else 0,
                "last_tick_ms": round(ticker.last_tick_ms, 2)
                if ticker else None,
                "last_resolve_ms": round(ticker.last_resolve_ms, 2)
                if ticker else None,
                "last_deliver_ms": round(ticker.last_deliver_ms, 2)
                if ticker else None,
            }, plane_stats
        finally:
            for p in procs:
                if p.is_alive():
                    p.terminate()
            await server.stop()

    results, e2e, tick_stats, plane_stats = asyncio.run(scenario())
    sent = sum(r[0] for r in results)
    received = sum(r[1] for r in results)
    elapsed = max(r[2] for r in results)
    expected = sent * (group - 1)
    rate = received / elapsed if elapsed > 0 else 0.0
    frame_e2e = e2e.get("frame") or {}
    log(f"delivery[workers={delivery_workers}]: {n_clients} WS peers "
        f"x{group} groups, {sent} msgs in, {received}/{expected} "
        f"deliveries in {elapsed:.2f}s ({rate:,.0f}/s)  "
        f"e2e p50 {frame_e2e.get('p50_ms', 0):.2f} "
        f"p99 {frame_e2e.get('p99_ms', 0):.2f} ms  ticks={tick_stats}")
    out = {
        "clients": n_clients,
        "groups_of": group,
        "messages_sent": sent,
        "deliveries": received,
        "deliveries_expected": expected,
        "deliveries_per_s": round(rate, 1),
        "elapsed_s": round(elapsed, 2),
        # honest fan-out latency: ticker-flush dispatch stamp →
        # socket-write-complete (in the owning worker for the sharded
        # plane), histogram-estimated percentiles
        "e2e_p50_ms": round(frame_e2e.get("p50_ms", 0.0), 3),
        "e2e_p99_ms": round(frame_e2e.get("p99_ms", 0.0), 3),
        "e2e_frames": frame_e2e.get("count", 0),
        # plane-entry → write-complete (ring dwell + write for worker
        # shards; the same stamp on the in-process pump, so the two
        # variants compare like for like)
        "delivery_e2e": e2e.get("delivery"),
        "server_ticks": tick_stats["ticks"],
        # byte-volume accounting (ISSUE 18): lower is better — the
        # perf gate pins these via tools/bench_diff's _BYTES_LOWER
        "delivered_bytes_per_tick": round(
            tick_stats["bytes_delivered"]
            / max(tick_stats["ticks"], 1), 1
        ),
        "bytes_per_recipient_per_s": round(
            tick_stats["bytes_delivered"] / n_clients
            / max(elapsed, 1e-9), 1
        ),
        "frame_delta_ratio": tick_stats["delta_ratio"] or 0.0,
        # per-core efficiency floor (ROADMAP item 1): deliveries per
        # CPU-second burned server-side over the measured window —
        # tools/bench_diff treats this higher-is-better and the CI
        # gate holds an absolute floor on it, so a change that keeps
        # raw throughput by burning proportionally more CPU still fails
        "server_cpu_s": round(tick_stats["server_cpu_s"], 3),
        "deliveries_per_s_per_core": round(
            received / tick_stats["server_cpu_s"], 1
        ) if tick_stats["server_cpu_s"] > 0 else 0.0,
    }
    if plane_stats is not None:
        out["n_workers"] = delivery_workers
        out["per_worker"] = plane_stats["per_worker"]
        out["ring_full_drops"] = plane_stats["plane"]["ring_full_drops"]
        alive = max(plane_stats["plane"]["alive"], 1)
        per_worker_rate = rate / alive
        out["per_worker_deliveries_per_s"] = round(per_worker_rate, 1)
        # the 1M deliveries/s sizing doc: shards are share-nothing, so
        # the config scales by adding workers until N × per-worker rate
        # clears the target — ON HARDWARE WITH N CORES; this container
        # time-shares every process on one core, which caps the
        # observed aggregate (the per-worker rate is the honest unit)
        out["workers_for_1m_per_s"] = (
            int(np.ceil(1_000_000 / per_worker_rate))
            if per_worker_rate > 0 else None
        )
    return out


def bench_delivery_suite(args) -> dict:
    """``server_delivery`` block: the single-loop pump (comparable to
    every prior round) plus the sharded-plane ``workers`` variant —
    same workload through ``--delivery-workers N`` at the ISSUE 6
    acceptance shape (≥4K live clients in full mode; override with
    ``--delivery-clients`` to bound a CI run)."""
    single = bench_delivery(args)
    n_workers = 2 if args.quick else 4
    clients = args.delivery_clients
    if clients is None:
        clients = 128 if args.quick else 4096
    n_procs = max(2, min(4, clients // 512))
    workers = bench_delivery(
        args,
        delivery_workers=n_workers,
        n_procs=n_procs,
        conns_per_proc=max(1, clients // n_procs),
    )
    single_rate = single["deliveries_per_s"] or 1.0
    workers["speedup_vs_single_loop"] = round(
        workers["deliveries_per_s"] / single_rate, 2
    )
    workers["lost_frames"] = (
        workers["deliveries_expected"] - workers["deliveries"]
    )
    single["workers"] = workers
    return single


# --------------------------------------------------------------------
# config 5 (default): 1M-entity Zipf-hotspot fan-out
# --------------------------------------------------------------------


def bench_config5(args) -> dict:
    # Real-server delivery pump first (multiprocessing spawn + live
    # sockets — cleanest before the device backend spins up). Smoke
    # mode (CI regression gate) skips it: the pump needs websockets +
    # spawned client processes and exercises nothing the compaction/
    # pipeline gate cares about.
    delivery = None if args.smoke else bench_delivery_suite(args)

    from worldql_server_tpu.spatial.backend import LocalQuery
    from worldql_server_tpu.spatial.cpu_backend import CpuSpatialBackend
    from worldql_server_tpu.spatial.tpu_backend import TpuSpatialBackend
    from worldql_server_tpu.protocol.types import Replication, Vector3

    import jax

    n_worlds = 8
    rng = np.random.default_rng(42)
    tpu = TpuSpatialBackend(cube_size=16)
    if args.smoke:
        # tiny smoke shapes sit under the compaction's min-cap gate;
        # open it so the CI pass exercises the pack/decode path
        tpu.compact_fetch_min_cap = 0
        tpu.compact_min_bucket = 8
    peers, sub_positions, sub_world_ids = build_index(
        tpu, rng, args.subs, n_worlds
    )
    # snapshot the SUBSCRIBER build's crowd stats before per-tick miss
    # traffic (also Zipf-drawn) overwrites them
    zipf_info = dict(_zipf_stats)
    log(f"zipf crowd: {zipf_info}")

    t0 = time.perf_counter()
    tpu.flush()
    log(f"device flush: {time.perf_counter() - t0:.1f}s "
        f"stats={tpu.device_stats()} device={jax.devices()[0].platform}")

    batches = [
        make_query_batch(rng, sub_positions, sub_world_ids, args.queries)
        for _ in range(args.ticks)
    ]

    # Warmup: compile + size the CSR result to the observed ROW-PADDED
    # footprint (1.5x headroom) — counts are exact even when the warm
    # dispatch itself overflows, so sizing needs no retry ladder.
    from worldql_server_tpu.spatial.tpu_backend import padded_slots

    warm_padded = 1
    for b in batches[:2]:
        _, res = tpu.match_arrays_async(*b, csr_cap=args.queries * 4)
        warm_padded = max(warm_padded, padded_slots(np.asarray(res[0])))
    csr_cap = max(2048, warm_padded * 5 // 4)
    # Steady state: the bulk load leaves most rows in the delta log
    # with a compaction in flight; measuring against that transient
    # (compile + device folds contending with dispatches) would time
    # the warmup, not the engine.
    t0 = time.perf_counter()
    tpu.wait_compaction()
    log(f"compaction drain: {time.perf_counter() - t0:.1f}s "
        f"stats={tpu.device_stats()}")
    for b in batches[:2]:
        _, res = tpu.match_arrays_async(*b, csr_cap=csr_cap)
        _force(res)                  # full-fetch path (fallback tier)
        _collect_compact(tpu, res)   # pack kernel at this bucket tier

    # Boot-time tier precompilation (ISSUE 8): walk every CSR capacity
    # tier, pack bucket and query-cap shape the run can reach, so the
    # sustained passes below hit only warm kernel caches — the retrace
    # GUARD delta across them is the acceptance number (== 0).
    from worldql_server_tpu.spatial.precompile import precompile_tiers
    from worldql_server_tpu.utils.retrace import GUARD

    t0 = time.perf_counter()
    pc_stats = precompile_tiers(
        tpu, max_batch=args.queries, t_tiers=4, max_compiles=64,
        delivery_cap=csr_cap,
    )
    log(f"tier precompile: {pc_stats} "
        f"({time.perf_counter() - t0:.1f}s)")
    guard_before = GUARD.snapshot()

    profile_ctx = (
        jax.profiler.trace(args.profile) if args.profile
        else contextlib.nullcontext()
    )
    # Best-of-3 sustained passes: the host↔device link can swing a
    # single pass several-fold while device compute stays flat — the
    # min is the code's number, the attribution probes below say how
    # much link remains even in it.
    sust_runs = []
    with profile_ctx:
        for _ in range(3):
            _, sustained, total_fanout, csr_cap = run_pipelined_adaptive(
                tpu, batches, csr_cap, depth=8
            )
            sust_runs.append(sustained)
    sustained = min(sust_runs)
    # retrace-GUARD verification of the precompilation: the sustained
    # window must compile NOTHING (a mid-serving trace inside a 5 ms
    # budget is the regression precompile exists to kill)
    retrace_delta = GUARD.delta(guard_before)
    retraces = sum(retrace_delta.values())
    log(f"sustained-window retraces: {retraces} {retrace_delta or ''}")
    if args.profile:
        log(f"jax profiler trace written to {args.profile}")
    log(f"tpu: sustained {sustained:.2f} ms/tick "
        f"(runs: {', '.join(f'{s:.1f}' for s in sust_runs)})  "
        f"avg fan-out {total_fanout / (len(batches) * args.queries):.2f}  "
        f"csr_cap {csr_cap}  "
        f"({args.queries / (sustained / 1e3):,.0f} queries/s)")

    # Run-length accounting under the Zipf crowd: the run-window CSR
    # has no per-query gather bound, so the honest load descriptors are
    # the raw run-length distribution a tick resolves and the CSR
    # retry (capacity-overflow) frequency.
    runlens = []
    for b in batches[:4]:
        cnts = np.asarray(
            tpu.match_arrays_async(*b, csr_cap=csr_cap)[1][0]
        )
        runlens.append(cnts.sum(axis=1)[: args.queries])
    rl = np.concatenate(runlens)
    zipf_info.update(
        run_p50=int(np.percentile(rl, 50)),
        run_p99=int(np.percentile(rl, 99)),
        run_max=int(rl.max()),
        # fraction of queries resolving a hot run (> one CSR row)
        overflow_rate=round(float((rl > 8).mean()), 4),
    )
    log(f"zipf runs: p50 {zipf_info['run_p50']}  p99 "
        f"{zipf_info['run_p99']}  max {zipf_info['run_max']}  "
        f"hot-rate {zipf_info['overflow_rate']}")

    # The north-star metric: per-tick fan-out latency, unpipelined and
    # double-buffered. The first depth-2 tick (pipeline fill + any
    # first-use stall — the BENCH_r05 207 s outlier) reports
    # separately, outside the percentiles.
    # Flight recorder on for the latency runs (ISSUE 5): every tick
    # leaves a span trace, and the WORST tick reports its per-stage
    # breakdown instead of hiding inside a bare p99 — the next 207 s
    # outlier (BENCH_r05) names its stage.
    tracer = Tracer(enabled=True)
    flight = FlightRecorder(depth=2 * len(batches) + 8)
    tracer.on_trace = flight.record
    lat1, _, _, _ = run_pipelined_adaptive(tpu, batches, csr_cap, depth=1,
                                           tracer=tracer)
    lat2_all, _, _, _ = run_pipelined_adaptive(tpu, batches, csr_cap,
                                               depth=2, tracer=tracer)
    lat2 = steady(lat2_all, 2)
    first_tick2 = float(lat2_all[0])
    worst = flight.worst_tick()
    worst_tick = None
    if worst is not None:
        worst_tick = {
            "wall_ms": round(worst.dur_ms, 3),
            "tags": dict(worst.tags),
            "stage_ms": {
                k: round(v, 3) for k, v in sorted(worst.stage_ms().items())
            },
        }
    log(f"latency depth1: p50 {pctl(lat1, 50):.2f} p99 {pctl(lat1, 99):.2f} ms"
        f"  depth2: p50 {pctl(lat2, 50):.2f} p99 {pctl(lat2, 99):.2f} ms"
        f"  first depth-2 tick {first_tick2:.2f} ms"
        f"  (budget {TARGET_P99_MS} ms)")
    log(f"worst recorded tick: {worst_tick}")

    # Attribution probes: how much of the latency is host↔device link
    # round trip vs device compute —
    # and which kernel stage owns the compute.
    rtt_ms, compute_ms, stages = _device_probes(tpu, batches[0], csr_cap)
    log(f"probes: link rtt {rtt_ms:.2f} ms  "
        f"device compute {compute_ms:.3f} ms/tick  stages={stages}")
    lat_attr = _latency_probe(tpu, batches, csr_cap)
    log(f"latency attribution: {lat_attr}")

    # Dispatch-path probe (ISSUE 8): the per-tick encode/h2d/compute/
    # d2h split through the SERVER's dispatch surface, staged columnar
    # vs legacy object-list, parity pinned lane-for-lane.
    path_probe = _dispatch_path_probe(tpu, peers, batches[0])
    log(f"dispatch paths: staged {path_probe['staged']}  "
        f"list {path_probe['list']}  parity {path_probe['parity']}")

    # CPU reference baseline: identical index + queries, per-message
    # dict resolution like the reference's hot path.
    cpu = CpuSpatialBackend(cube_size=16)
    rng2 = np.random.default_rng(42)
    build_index(cpu, rng2, args.subs, n_worlds)

    cpu_times = []
    for b in batches[: args.cpu_ticks]:
        world_ids, positions, sender_ids, repls = b
        queries = [
            LocalQuery(
                f"world_{world_ids[i]}",
                Vector3(*positions[i]),
                peers[sender_ids[i]],
                Replication.EXCEPT_SELF,
            )
            for i in range(len(world_ids))
        ]
        t0 = time.perf_counter()
        cpu.match_local_batch(queries)
        cpu_times.append(time.perf_counter() - t0)
    cpu_times_ms = np.array(cpu_times) * 1e3
    cpu_p99 = pctl(cpu_times_ms, 99)
    log(f"cpu: mean {cpu_times_ms.mean():.2f} ms  p99 {cpu_p99:.2f} ms")

    _parity_check(tpu, cpu, peers, batches[0])

    # Uniform-crowd reference point: the SAME engine over a 1M-sub
    # index with the pre-Zipf uniform-core crowd (5% in a ±40 box) —
    # the distribution the <5 ms budget was originally quoted under,
    # kept for round-over-round comparability.
    uniform = None
    if not args.quick:
        uniform = _uniform_reference(args)
        log(f"uniform-crowd reference: {uniform}")

    # Queries-per-tick scaling sweep (device compute by chained slope,
    # CPU reference at the SAME batch size). Workload model: each tick,
    # M of the 1M subscribed entities broadcast a LocalMessage from
    # their own position (20% from a fresh random point — miss
    # traffic). M/subs is the per-tick speak fraction: 16K/tick at
    # 20 t/s = every entity broadcasting every ~3s (MMO presence
    # cadence); the 1M point is every entity broadcasting every tick —
    # the literal 20M queries/s reading of the north star.
    sweep = []
    if not args.quick:
        sweep = _sweep_config5(tpu, cpu, rng, sub_positions, sub_world_ids,
                               peers, args)

    # Temporal-coherence low-churn sustained pass (ROADMAP 2): runs
    # LAST among the tpu probes — its index churn would desync the CPU
    # twin the parity probes above compare against.
    delta_probe = _delta_probe(
        tpu, peers, sub_positions, sub_world_ids, batches[0], args
    )
    log(f"delta ticks: {delta_probe}")

    # Headline: the ENGINE-side tick (host encode + H2D enqueue +
    # device compute, link excluded) — where the pair probe shows the
    # link serializing independent dispatches (pair_overlap_ratio
    # ~0.7-1.0), the e2e wall measures the link, not the code. The
    # e2e sustained/percentile numbers stay in the JSON below.
    # engine_p99's tail is the HOST side (p99 over up to 15 dispatch
    # walls); the compute term is the chained-slope estimate — device
    # compute is flat across trials (±0.02 ms on back-to-back stage
    # probes), so the host is where an engine-tick tail lives.
    engine_tick_ms = lat_attr["dispatch_ms"] + compute_ms
    engine_p99_ms = lat_attr["dispatch_p99_ms"] + compute_ms
    if args.smoke:
        # the CI gate's whole point: the compacted collect path must
        # have actually run (a regression that silently reverts to the
        # full fetch fails the build here, not the nightly bench)
        assert tpu.compact_fetches > 0, \
            "smoke: compacted collect path never fired"
        log(f"smoke: {tpu.compact_fetches} compacted / "
            f"{tpu.full_fetches} full fetches")
        # ISSUE 8 gates: the staged columnar path actually fired, its
        # output is lane-identical to the object-list path, its encode
        # leg is strictly below the list path's on the same shapes, and
        # the precompiled sustained window re-traced NOTHING
        assert tpu.staged_dispatches > 0, \
            "smoke: staged dispatch path never fired"
        assert path_probe["parity"], \
            "smoke: staged/list dispatch outputs diverged"
        assert (
            path_probe["staged"]["encode_ms"]
            < path_probe["list"]["encode_ms"]
        ), (
            "smoke: staged encode not below list-path encode: "
            f"{path_probe['staged']['encode_ms']} vs "
            f"{path_probe['list']['encode_ms']}"
        )
        assert retraces == 0, (
            "smoke: sustained window re-traced despite precompilation: "
            f"{retrace_delta}"
        )
        log(f"smoke: staged encode {path_probe['staged']['encode_ms']}"
            f" ms < list encode {path_probe['list']['encode_ms']} ms; "
            f"retraces {retraces}")
        # ISSUE 13 gates: delta ticks replayed the clean majority of a
        # low-churn pass, lane-for-lane identical to full recompute,
        # and the per-tick device wall dropped >= 5x vs the full
        # recompute path at identical shapes (the acceptance bar;
        # measured 30x at smoke shapes on the 1-core container)
        assert delta_probe["parity"], \
            "smoke: delta ticks diverged from full recompute"
        assert delta_probe["reuse_fraction"] > 0.8, (
            "smoke: delta reuse collapsed: "
            f"{delta_probe['reuse_fraction']}"
        )
        assert delta_probe["speedup"] >= 5.0, (
            "smoke: delta device wall not >= 5x below full recompute: "
            f"{delta_probe['delta_update_ms']} vs "
            f"{delta_probe['rebuild_ms']} ms"
        )
        log(f"smoke: delta reuse {delta_probe['reuse_fraction']}  "
            f"update {delta_probe['delta_update_ms']} ms vs rebuild "
            f"{delta_probe['rebuild_ms']} ms "
            f"({delta_probe['speedup']}x)")
    return {
        "metric": "local_fanout_engine_tick_ms",
        "value": round(engine_tick_ms, 3),
        "unit": "ms",
        "vs_baseline": round(cpu_p99 / engine_tick_ms, 2),
        # honest-baseline calibration (ROADMAP 5a): vs_baseline grades
        # us against our OWN Python oracle; vs_reference grades the
        # same shapes against a native micro-port of the reference
        # implementation's AreaMap lookup (single thread, lookup only
        # — a floor for the reference's per-query cost, deliberately
        # generous to it). Absent when the native library predates the
        # probe symbol.
        "vs_reference": _vs_reference(args, engine_tick_ms),
        "engine_p99_ms": round(engine_p99_ms, 3),
        "sustained_e2e_tick_ms": round(sustained, 3),
        "p50_ms_depth1": round(pctl(lat1, 50), 3),
        "p99_ms_depth1": round(pctl(lat1, 99), 3),
        "p50_ms_depth2": round(pctl(lat2, 50), 3),
        "p99_ms_depth2": round(pctl(lat2, 99), 3),
        # pipeline-fill tick, excluded from the p50/p99 above (the
        # BENCH_r05 207 s outlier was this sample)
        "first_tick_ms_depth2": round(first_tick2, 3),
        # flight-recorder attribution of the slowest latency-run tick:
        # wall + per-stage span breakdown (dispatch vs compacted fetch)
        "worst_tick": worst_tick,
        "compact_fetches": tpu.compact_fetches,
        "full_fetches": tpu.full_fetches,
        # per-tick device-timing split through the server's dispatch
        # surface (ISSUE 8, satellite: top-level so the encode win is
        # visible in the BENCH_*.json trajectory without /debug/ticks);
        # encode_ms is the STAGED columnar path — the serving
        # configuration — with the legacy object-list encode alongside
        # for the wall the staging removed
        "encode_ms": path_probe["staged"]["encode_ms"],
        "h2d_ms": path_probe["staged"]["h2d_ms"],
        "compute_ms": path_probe["staged"]["compute_ms"],
        "d2h_ms": path_probe["staged"]["d2h_ms"],
        "encode_ms_list": path_probe["list"]["encode_ms"],
        "staged_parity": path_probe["parity"],
        "staged_dispatches": tpu.staged_dispatches,
        # retrace-GUARD accounting of the sustained window with
        # precompilation on (acceptance: retraces == 0)
        "device": {
            "retraces": retraces,
            "retrace_delta": retrace_delta,
            "precompile": pc_stats,
        },
        # temporal-coherence pass (ROADMAP 2): reuse_fraction +
        # delta_update_ms vs rebuild_ms at identical shapes; the
        # acceptance bar is speedup >= 5 on the full-shape pass
        "delta": delta_probe,
        "link_rtt_ms": round(rtt_ms, 3),
        "device_compute_ms": round(compute_ms, 4),
        # the engine's own rate, net of the link, per chip (null when the
        # kernel is too small for the slope to resolve — quick mode)
        "device_queries_per_s": (
            round(args.queries / (compute_ms / 1e3))
            if compute_ms >= MIN_RESOLVED_MS else None
        ),
        "device_stage_ms": stages,
        "latency_attribution": lat_attr,
        "uniform_crowd": uniform,
        "zipf": zipf_info,
        "server_delivery": delivery,
        # frame-clock fan-out latency through the REAL server (ISSUE
        # 7): dispatch-stamp → socket-write-complete percentiles from
        # the in-process server_delivery variant, surfaced at top
        # level next to the engine numbers (null in --smoke, which
        # skips the delivery pump)
        "e2e_p50_ms": delivery.get("e2e_p50_ms") if delivery else None,
        "e2e_p99_ms": delivery.get("e2e_p99_ms") if delivery else None,
        "sustained_runs_ms": [round(s, 3) for s in sust_runs],
        "queries_per_tick_sweep": sweep,
        # chunk-tier characterization of the 262K-query throughput dip
        # (BENCH_r05: 2.68M q/s vs 3.65M at 16K) — the 262K sweep
        # record carries the full per-tier table under "tier_sweep"
        "sweep_notes": next(
            (rec["tier_sweep"]["notes"] for rec in sweep
             if rec.get("tier_sweep")), None,
        ),
        "target_p99_ms": TARGET_P99_MS,
        "config": 5,
    }


def _vs_reference(args, engine_tick_ms: float) -> dict | None:
    """The ``vs_reference`` calibration row: the native AreaMap probe
    (spatial.cpp::wql_areamap_probe — a micro-port of the reference
    Rust server's cube→peers HashMap hot path) timed at THIS run's
    sub/query shapes on THIS machine, next to the engine's measured
    per-query cost. The ratio is engine queries/s over reference-probe
    lookups/s; the note spells out the asymmetry so nobody reads a
    lookup-only floor as an end-to-end comparison."""
    from worldql_server_tpu.spatial.native_keys import areamap_probe

    probe = areamap_probe(args.subs, args.queries, cube_size=16, seed=11)
    if probe is None:
        return None
    lookup_ns = probe["lookup_ns_per_query"]
    ref_qps = 1e9 / lookup_ns if lookup_ns > 0 else None
    engine_qps = (
        args.queries / (engine_tick_ms / 1e3) if engine_tick_ms > 0 else None
    )
    ratio = (
        round(engine_qps / ref_qps, 3)
        if engine_qps and ref_qps else None
    )
    return {
        "probe": "areamap_native",
        **probe,
        "ref_lookups_per_s": round(ref_qps) if ref_qps else None,
        "engine_queries_per_s": round(engine_qps) if engine_qps else None,
        "engine_per_ref_ratio": ratio,
        "note": (
            "reference probe is the AreaMap lookup alone — no fan-out "
            "assembly, no serialization, no transport; a calibration "
            "floor for the reference's cost, not an e2e comparison"
        ),
    }


def _uniform_reference(args) -> dict:
    """Device compute at 16K queries / 1M subs under the UNIFORM-core
    crowd (the round-3/4 workload: 5% of entities in a ±40 box) — the
    comparability anchor for the <=1.5 ms engine target."""
    from worldql_server_tpu.spatial.tpu_backend import (
        TpuSpatialBackend, padded_slots,
    )

    rng = np.random.default_rng(77)
    tpu = TpuSpatialBackend(cube_size=16)
    n = args.subs

    def uniform_positions(rng_, k):
        hot = rng_.random(k) < 0.05
        pos = rng_.uniform(-800.0, 800.0, (k, 3))
        pos[hot] = rng_.uniform(-40.0, 40.0, (int(hot.sum()), 3))
        return pos

    global make_positions
    zipf_fn = make_positions
    make_positions = uniform_positions
    try:
        peers, sub_positions, sub_world_ids = build_index(tpu, rng, n, 8)
        tpu.flush()
        tpu.wait_compaction()
        batch = make_query_batch(
            rng, sub_positions, sub_world_ids, 16_384
        )
        cnts = np.asarray(
            tpu.match_arrays_async(*batch, csr_cap=16_384 * 16)[1][0]
        )
        csr_cap = max(2048, padded_slots(cnts) * 5 // 4)
        _, dev_ms, stages = _device_probes(tpu, batch, csr_cap)
        return {
            "queries": 16_384,
            "device_compute_ms": round(dev_ms, 3),
            "device_stage_ms": stages,
            "engine_target_ms": 1.5,
        }
    finally:
        make_positions = zipf_fn
        del tpu


def _sweep_config5(tpu, cpu, rng, sub_positions, sub_world_ids, peers,
                   args) -> list[dict]:
    """Device + CPU cost vs queries-per-tick batch size over the same
    1M-subscription index. Device numbers are chained-slope (link
    cancelled); CPU is the reference backend resolving the identical
    batch."""
    from worldql_server_tpu.spatial.backend import LocalQuery
    from worldql_server_tpu.protocol.types import Replication, Vector3

    out = []
    for m in (16_384, 65_536, 262_144, 1_048_576):
        batch = make_query_batch(rng, sub_positions, sub_world_ids, m)
        # size the CSR buffer off the row-padded footprint at this
        # batch (counts stay exact even if the warm dispatch overflows)
        from worldql_server_tpu.spatial.tpu_backend import padded_slots

        cnts = np.asarray(
            tpu.match_arrays_async(*batch, csr_cap=m * 4)[1][0]
        )
        csr_cap = max(2048, padded_slots(cnts) * 5 // 4)
        try:
            _, dev_ms, _ = _device_probes(
                tpu, batch, csr_cap, stages=False,
                reps_pair=(2, 8) if m >= 262_144 else (8, 64),
            )
        except Exception as exc:  # e.g. HBM OOM on the Zipf 1M batch
            log(f"sweep m={m}: device probe failed "
                f"({type(exc).__name__}) — result footprint "
                f"{csr_cap} slots")
            out.append({
                "queries": m,
                "speak_fraction": round(m / args.subs, 4),
                "device_compute_ms": None,
                "device_queries_per_s": None,
                "error": type(exc).__name__,
            })
            continue

        world_ids, positions, sender_ids, repls = batch
        cpu_n = min(m, 65_536)  # CPU cost is linear; sample and scale
        queries = [
            LocalQuery(
                f"world_{world_ids[i]}", Vector3(*positions[i]),
                peers[sender_ids[i]], Replication.EXCEPT_SELF,
            )
            for i in range(cpu_n)
        ]
        t0 = time.perf_counter()
        cpu.match_local_batch(queries)
        cpu_ms = (time.perf_counter() - t0) * 1e3 * (m / cpu_n)
        resolved = dev_ms >= MIN_RESOLVED_MS
        rec = {
            "queries": m,
            "speak_fraction": round(m / args.subs, 4),
            "device_compute_ms": round(dev_ms, 3),
            "device_queries_per_s": (
                round(m / (dev_ms / 1e3)) if resolved else None
            ),
            "cpu_ms": round(cpu_ms, 1),
            "vs_cpu": round(cpu_ms / dev_ms, 1) if resolved else None,
        }
        if m == 262_144:
            # the BENCH_r05 throughput dip (2.68M q/s here vs 3.65M at
            # 16K and 3.1M at 1M): sweep the zone-B chunk tiers at
            # exactly this shape so the JSON carries the
            # characterization (ISSUE 6 satellite / VERDICT weak #7's
            # sibling). Each tier pair re-traces the assembly with a
            # different (full, tail) lax.map block split.
            rec["tier_sweep"] = _zone_b_tier_sweep(
                tpu, batch, csr_cap, round(dev_ms, 3)
            )
        out.append(rec)
        log(f"sweep m={m}: device {dev_ms:.2f} ms "
            f"({rec['device_queries_per_s']}/s)  cpu {cpu_ms:.0f} ms  "
            f"({rec['vs_cpu']}x)")
    return out


def _zone_b_tier_sweep(tpu, batch, csr_cap: int, default_ms: float) -> dict:
    """Re-time the device kernel at one batch shape under alternate
    zone-B chunk tiers (tpu_backend._ZONE_B_CHUNK/_ZONE_B_TAIL_CHUNK).
    The probes build FRESH jitted closures, so the patched globals
    re-trace cleanly; the backend's registered kernels are untouched.
    Returns the per-tier timings plus a ``notes`` string naming either
    the better boundary or the measured root cause."""
    import worldql_server_tpu.spatial.tpu_backend as tb

    orig = (tb._ZONE_B_CHUNK, tb._ZONE_B_TAIL_CHUNK)
    tiers = [(17, 14), (16, 14), (16, 13), (15, 13), (14, 12), (17, 16)]
    results = []
    try:
        for chunk_exp, tail_exp in tiers:
            tb._ZONE_B_CHUNK = 1 << chunk_exp
            tb._ZONE_B_TAIL_CHUNK = 1 << tail_exp
            try:
                _, ms, _ = _device_probes(
                    tpu, batch, csr_cap, stages=False, reps_pair=(2, 8),
                )
                results.append({
                    "chunk": f"2^{chunk_exp}", "tail": f"2^{tail_exp}",
                    "device_compute_ms": round(ms, 3),
                })
                log(f"tier sweep 2^{chunk_exp}/2^{tail_exp}: {ms:.3f} ms")
            except Exception as exc:
                results.append({
                    "chunk": f"2^{chunk_exp}", "tail": f"2^{tail_exp}",
                    "device_compute_ms": None,
                    "error": type(exc).__name__,
                })
    finally:
        tb._ZONE_B_CHUNK, tb._ZONE_B_TAIL_CHUNK = orig
    timed = [r for r in results if r["device_compute_ms"] is not None]
    notes = "tier sweep produced no timings"
    if timed:
        best = min(timed, key=lambda r: r["device_compute_ms"])
        default = next(
            (r for r in timed if r["chunk"] == "2^17" and r["tail"] == "2^14"),
            None,
        )
        base_ms = default["device_compute_ms"] if default else default_ms
        if base_ms and best["device_compute_ms"] < 0.9 * base_ms:
            notes = (
                f"262K dip: tier {best['chunk']}/{best['tail']} beats the "
                f"default 2^17/2^14 by "
                f"{base_ms / best['device_compute_ms']:.2f}x at this shape "
                "— the default boundary leaves the batch mostly in one "
                "full chunk + a long tail-tier run; consider a shape-"
                "keyed tier table"
            )
        else:
            notes = (
                "262K dip: chunk-tier split is NOT the cause (all tiers "
                f"within 10% of {base_ms} ms at this shape) — the dip "
                "tracks the zone-B rows/query ratio of the Zipf crowd at "
                "this speak fraction, not assembly codegen"
            )
    return {"default_ms": default_ms, "tiers": results, "notes": notes}


def _device_probes(tpu, batch, csr_cap: int, *, stages: bool = True,
                   reps_pair: tuple = (8, 64)):
    """(link round-trip ms, device compute ms/tick, per-stage ms dict).

    The rtt probe is a 4-byte H2D+D2H. The compute probes chain R
    kernel iterations inside ONE jitted ``fori_loop`` (every iteration
    runs the SAME multiset of queries rotated by a result-derived
    shift, so the workload is representative AND nothing is cached,
    hoisted, or dead-code stripped) and report the slope between two
    rep counts: per-tick DEVICE time with the link round-trip fully
    subtracted out. Naive probes (timing pipelined dispatches) measure
    the link's pipelining limit instead.

    Three chained loops of increasing prefix depth attribute the total
    over the run-window CSR kernel (tpu_backend.match_run_csr):
    ``bounds`` (per-segment probe-table run-bounds lookup), ``layout``
    (+ the row-padded CSR layout: prefix sums and the owner map —
    index math, no data movement), ``full`` (+ the window gathers that
    assemble the flat result and the filter lanes). The differences
    are the per-stage costs; ``full`` is the headline
    device_compute_ms."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from worldql_server_tpu.spatial.tpu_backend import (
        CSR_ROW, CSR_ROW_B, SEG_ARRAYS, csr_layout, match_run_csr,
        run_bounds_all, zone_b_cnts,
    )

    one = np.zeros(1, np.int32)
    rtts = []
    for _ in range(12):
        t0 = time.perf_counter()
        np.asarray(jax.device_put(one))
        rtts.append((time.perf_counter() - t0) * 1e3)

    world_ids, positions, sender_ids, repls = batch
    m, result = tpu.match_arrays_async(
        world_ids, positions, sender_ids, repls, csr_cap=csr_cap
    )
    jax.block_until_ready(result)
    segs, ks, kinds = tpu._segments()
    flat_segs = tuple(a for seg in segs for a in seg)
    t_cap = next_pow2(csr_cap)
    nseg = len(segs)
    queries = tuple(jax.device_put(q) for q in tpu._prepare_queries(
        world_ids, positions, sender_ids, repls
    ))
    jax.block_until_ready(queries)
    mq = queries[0].shape[0]
    na = SEG_ARRAYS

    def make_chained(stage: str):
        @partial(jax.jit, static_argnames=("reps",))
        def chained(salt, queries, flat_segs, reps):
            q_key, q_key2, q_sender, q_repl = queries
            seg_tuples = [
                tuple(flat_segs[na * i:na * i + na])
                for i in range(nseg)
            ]

            def body(i, carry):
                acc, shift = carry
                rolled = tuple(jnp.roll(q, shift) for q in
                               (q_key, q_key2, q_sender, q_repl))
                if stage == "bounds":
                    los, cnts = run_bounds_all(seg_tuples, rolled)
                    fold = jnp.int32(0)
                    for lo, cnt in zip(los, cnts):
                        fold = fold ^ lo.sum(dtype=jnp.int32) \
                            ^ cnt.sum(dtype=jnp.int32)
                elif stage == "layout":
                    los, cnts = run_bounds_all(seg_tuples, rolled)
                    counts, row_start, owner, total_rows = csr_layout(
                        zone_b_cnts(cnts),
                        max((t_cap - mq * CSR_ROW * nseg) // CSR_ROW_B,
                            1),
                        CSR_ROW_B,
                    )
                    fold = (
                        counts.sum(dtype=jnp.int32)
                        ^ owner.sum(dtype=jnp.int32)
                        ^ row_start.sum(dtype=jnp.int32)
                        ^ total_rows
                    )
                    for lo in los:
                        fold = fold ^ lo.sum(dtype=jnp.int32)
                else:
                    counts, flat, total = match_run_csr(
                        flat_segs + rolled, nseg, t_cap,
                    )
                    # consume `flat` too, so the window-gather assembly
                    # stays live inside the timed loop
                    fold = total ^ flat.sum(dtype=jnp.int32) \
                        ^ counts.sum(dtype=jnp.int32)
                nxt = (fold & jnp.int32(mq - 1)) + jnp.int32(1)
                return acc + fold.astype(jnp.int64), nxt
            acc, _ = jax.lax.fori_loop(
                0, reps, body, (jnp.int64(0), (salt & jnp.int32(mq - 1)) + 1)
            )
            return acc
        return chained

    # monotone clamp chain (0 <= bounds <= layout <= full): a
    # sub-jitter kernel (tiny quick-mode shapes) can produce
    # meaningless negative slopes, and the emitted stages must never
    # sum past the total they attribute. All prefixes are timed
    # INTERLEAVED (see chained_slopes_ms) so link drift cancels in the
    # differences instead of masquerading as a stage.
    stage_ms = {}
    if stages:
        slopes = chained_slopes_ms(
            {s: make_chained(s) for s in ("bounds", "layout", "full")},
            (queries, flat_segs), reps_pair,
        )
        bounds_ms = max(slopes["bounds"], 0.0)
        layout_ms = max(slopes["layout"], bounds_ms)
        full_ms = max(slopes["full"], layout_ms)
        stage_ms = {
            "run_bounds_ms": round(bounds_ms, 4),
            "csr_layout_ms": round(layout_ms - bounds_ms, 4),
            "window_gather_ms": round(full_ms - layout_ms, 4),
        }
    else:
        full_ms = max(
            chained_slope_ms(
                make_chained("full"), (queries, flat_segs), reps_pair
            ),
            0.0,
        )
    return pctl(rtts, 50), full_ms, stage_ms


def _latency_probe(tpu, batches, csr_cap: int) -> dict:
    """Attribute the depth-1 dispatch→collect latency (VERDICT r4
    weak #1: 265 ms p50 vs a 109 ms link RTT, unexplained).

    Phases of ONE tick, wall-timed separately over several reps:
    ``dispatch`` (host encode + H2D + launch — returns immediately),
    then the sequential D2H phases the server's collect pays:
    ``total`` (scalar sync), ``counts`` ([M, nseg]), and ``flat`` —
    which since ISSUE 3 is the ON-DEVICE COMPACTED fetch (pack the
    owed lanes into a power-of-two bucket, ship O(actual fan-out)
    bytes; the cap-padded full fetch only as fallback). fetch_ms.flat
    therefore scales with real fan-out, not the capacity tier —
    BENCH_r05 measured ≈ 956 ms of cap padding here.

    Concurrency probe: two INDEPENDENT dispatches (different batches —
    neither can be answered from the other) collected in dispatch
    order. If the link pipelines, the pair's wall is ~1 RTT over a
    single tick's; a hard-serializing link costs ~2x a single."""

    def one(batch):
        t0 = time.perf_counter()
        _, res = tpu.match_arrays_async(*batch, csr_cap=csr_cap)
        t1 = time.perf_counter()
        parts = {}
        ta = time.perf_counter()
        total = int(res[2])
        parts["total"] = (time.perf_counter() - ta) * 1e3
        ta = time.perf_counter()
        np.asarray(res[0])
        parts["counts"] = (time.perf_counter() - ta) * 1e3
        ta = time.perf_counter()
        t_cap = res[1].shape[0]
        if (
            total > t_cap
            or tpu._compact_fetch(res[0], res[1], total, t_cap) is None
        ):
            np.asarray(res[1])   # overflow / fallback: full fetch
        parts["flat"] = (time.perf_counter() - ta) * 1e3
        return (t1 - t0) * 1e3, parts, (time.perf_counter() - t0) * 1e3

    # warm
    one(batches[0])
    reps = [one(batches[i % len(batches)]) for i in range(5)]
    dispatch_walls = [r[0] for r in reps]
    # Extra dispatch samples for the p99, on DISTINCT batches only (an
    # identical re-dispatch might be answered from a cache) and
    # synced via the scalar ``total`` fetch (~1 RTT) instead of the
    # full flat-result fetch — the flat fetch
    # adds nothing to a dispatch-wall sample.
    for b in batches[5:15]:
        t0 = time.perf_counter()
        _, res = tpu.match_arrays_async(*b, csr_cap=csr_cap)
        dispatch_walls.append((time.perf_counter() - t0) * 1e3)
        np.asarray(res[2])
    dispatch_ms = float(np.median(dispatch_walls))
    dispatch_p99_ms = pctl(dispatch_walls, 99)
    fetch = {
        k: round(float(np.median([r[1][k] for r in reps])), 1)
        for k in ("counts", "flat", "total")
    }
    single_ms = float(np.median([r[2] for r in reps]))

    # two independent ticks, dispatched back-to-back, collected in
    # dispatch order — overlap measurement
    def pair():
        t0 = time.perf_counter()
        h1 = tpu.match_arrays_async(*batches[0], csr_cap=csr_cap)[1]
        h2 = tpu.match_arrays_async(*batches[1], csr_cap=csr_cap)[1]
        _collect_compact(tpu, h1)
        _collect_compact(tpu, h2)
        return (time.perf_counter() - t0) * 1e3

    pair()
    pair_ms = float(np.median([pair() for _ in range(3)]))
    return {
        "dispatch_ms": round(dispatch_ms, 1),
        "dispatch_p99_ms": round(dispatch_p99_ms, 1),
        "fetch_ms": fetch,
        "single_tick_ms": round(single_ms, 1),
        "independent_pair_ms": round(pair_ms, 1),
        "pair_overlap_ratio": round(pair_ms / (2 * single_ms), 3),
        # what the LAST collect shipped (pack bucket 0 = full fetch)
        "compaction": dict(tpu.last_collect_stats),
    }


def _dispatch_path_probe(tpu, peers, batch, reps: int = 7) -> dict:
    """Drive the SERVER's two dispatch paths over the same batch and
    report the per-tick device-timing split of each (ISSUE 8):

    * ``list`` — ``dispatch_local_batch`` over LocalQuery objects (the
      legacy path: per-query interning loops inside the dispatch wall);
    * ``staged`` — ``dispatch_staged_batch`` over the columnar arrays
      the ticker's staging buffers would hold (interning already done
      at enqueue time; the dispatch wall is just the fused vectorized
      encode + launch).

    Collect output is compared lane-for-lane (identical UUID fan-out
    lists), and the encode legs are the bench JSON's top-level
    ``encode_ms`` (staged — the serving path) vs ``encode_ms_list``.
    Medians over ``reps`` so one scheduler hiccup can't flip the
    comparison."""
    from worldql_server_tpu.spatial.backend import LocalQuery
    from worldql_server_tpu.protocol.types import Replication, Vector3

    world_ids, positions, sender_ids, repls = batch
    m = len(world_ids)
    queries = [
        LocalQuery(
            f"world_{world_ids[i]}",
            Vector3(*positions[i]),
            peers[sender_ids[i]],
            Replication.EXCEPT_SELF,
        )
        for i in range(m)
    ]
    # the staged columns: exactly what engine/staging.py's enqueue-time
    # encode produces — ids interned through the backend's own dicts
    wid_col = np.fromiter(
        (tpu._world_ids.get(f"world_{w}", -1) for w in world_ids),
        np.int32, count=m,
    )
    sid_col = np.fromiter(
        (tpu._peer_ids.get(peers[s], -1) for s in sender_ids),
        np.int32, count=m,
    )
    pos_col = np.ascontiguousarray(positions, np.float64)
    repl_col = np.full(m, int(Replication.EXCEPT_SELF), np.int8)

    legs = ("encode_ms", "h2d_ms", "compute_ms", "d2h_ms")

    def run(dispatch):
        out, timings = None, []
        for _ in range(reps):
            out = tpu.collect_local_batch(dispatch())
            timings.append(dict(tpu.last_device_timing))
        med = {
            leg: round(float(np.median(
                [t.get(leg, 0.0) for t in timings]
            )), 4)
            for leg in legs
        }
        med["path"] = timings[-1].get("path")
        return out, med

    out_list, t_list = run(lambda: tpu.dispatch_local_batch(queries))
    out_staged, t_staged = run(
        lambda: tpu.dispatch_staged_batch(
            wid_col, pos_col, sid_col, repl_col
        )
    )
    return {
        "queries": m,
        "parity": out_staged == out_list,
        "staged": t_staged,
        "list": t_list,
    }


#: slopes under this are link noise, not a resolved kernel time — rates
#: derived from them would be absurd (a 16K-query tick is never 10 µs)
MIN_RESOLVED_MS = 0.01


def _parity_check(tpu, cpu, peers, batch, samples: int = 64) -> None:
    from worldql_server_tpu.spatial.backend import LocalQuery
    from worldql_server_tpu.protocol.types import Replication, Vector3

    world_ids, positions, sender_ids, repls = batch
    idx = np.linspace(0, len(world_ids) - 1, samples).astype(int)
    tgt = tpu.match_arrays(*batch)
    for i in idx:
        want = cpu.match_local_batch([
            LocalQuery(
                f"world_{world_ids[i]}",
                Vector3(*positions[i]),
                peers[sender_ids[i]],
                Replication.EXCEPT_SELF,
            )
        ])[0]
        got = {int(t) for t in tgt[i] if t >= 0}
        want_ids = {tpu._peer_ids[p] for p in want}
        assert got == want_ids, f"parity diverged at query {i}"
    log(f"parity check: {samples} sampled queries agree with CPU reference")


def _delta_parity_check(args) -> bool:
    """Dual-backend lane-for-lane parity of delta ticks vs full
    recompute over a churned schedule (small shapes; the randomized
    property suite in tests/test_delta_ticks.py is the exhaustive
    version — this is the bench-smoke pin that the gate asserts)."""
    from worldql_server_tpu.spatial.quantize import cube_coords_batch
    from worldql_server_tpu.spatial.tpu_backend import TpuSpatialBackend

    bes = [TpuSpatialBackend(16), TpuSpatialBackend(16)]
    assert bes[0].configure_delta_ticks("on")
    n, mq = 512, 128
    peers = [uuid_mod.UUID(int=i + 1) for i in range(n)]
    pos = np.random.default_rng(5).uniform(-300, 300, (n, 3))
    cubes = cube_coords_batch(pos, 16)
    for be in bes:
        be.bulk_add_subscriptions("w", peers, cubes)
        be.flush()
    qrng = np.random.default_rng(7)
    q_pos = pos[qrng.integers(0, n, mq)].copy()
    wid = np.zeros(mq, np.int32)
    sid = np.full(mq, -1, np.int32)
    repl = np.zeros(mq, np.int8)
    crng = np.random.default_rng(11)
    for _ in range(12):
        rows = np.unique(crng.integers(0, mq, 4))
        q_pos[rows] = pos[crng.integers(0, n, rows.size)]
        mv = np.unique(crng.integers(0, n, 4))
        new_cubes = cube_coords_batch(
            crng.uniform(-300, 300, (mv.size, 3)), 16
        )
        for be in bes:
            be.bulk_move_subscriptions(
                "w", [peers[i] for i in mv], cubes[mv],
                [peers[i] for i in mv], new_cubes,
            )
        cubes[mv] = new_cubes
        outs = [
            be.collect_local_batch(
                be.dispatch_staged_batch(wid, q_pos, sid, repl)
            )
            for be in bes
        ]
        if outs[0] != outs[1]:
            return False
    return bes[0].delta_reused > 0


def _delta_probe(tpu, peers, sub_positions, sub_world_ids, batch,
                 args) -> dict:
    """Low-churn sustained delta pass (ROADMAP 2 acceptance): the SAME
    query batch re-dispatches tick over tick with ~1% fresh query rows
    and ~0.05% index churn per tick — the steady-MMO regime — once
    with delta ticks off (full recompute: every tick re-resolves all M
    queries) and once on (only the dirty partition enters the device
    batch; clean queries replay). ``delta_update_ms`` vs ``rebuild_ms``
    is the mean per-tick device wall (compute + H2D launch) of each
    mode at IDENTICAL shapes; the acceptance bar is a >= 5x drop.
    Runs LAST in config 5 — the index churn it applies would desync
    the earlier CPU-reference parity probes."""
    from worldql_server_tpu.protocol.types import Replication
    from worldql_server_tpu.spatial.quantize import cube_coords_batch

    ticks = 8 if args.quick else 24
    warm = 3
    world_ids, positions, sender_ids, _ = batch
    m = len(world_ids)
    wid_col = np.fromiter(
        (tpu._world_ids.get(f"world_{w}", -1) for w in world_ids),
        np.int32, count=m,
    )
    sid_col = np.fromiter(
        (tpu._peer_ids.get(peers[s], -1) for s in sender_ids),
        np.int32, count=m,
    )
    repl_col = np.full(m, int(Replication.EXCEPT_SELF), np.int8)
    n_subs = len(sub_positions)
    churn_q = max(2, m // 100)
    churn_s = max(2, n_subs // 2000)
    sub_cubes = cube_coords_batch(sub_positions, tpu.cube_size)

    def run(mode):
        tpu.configure_delta_ticks(mode)
        rng = np.random.default_rng(4242)
        pos_col = np.ascontiguousarray(positions, np.float64).copy()
        walls, reuse, dirty, churn_rows = [], [], [], []
        for t in range(warm + ticks):
            rows = np.unique(rng.integers(0, m, churn_q))
            pos_col[rows] = sub_positions[
                rng.integers(0, n_subs, rows.size)
            ]
            mv = np.unique(rng.integers(0, n_subs, churn_s))
            new_cubes = cube_coords_batch(
                make_positions(rng, mv.size), tpu.cube_size
            )
            for w in np.unique(sub_world_ids[mv]):
                sel = sub_world_ids[mv] == w
                tpu.bulk_move_subscriptions(
                    f"world_{w}",
                    [peers[i] for i in mv[sel]], sub_cubes[mv[sel]],
                    [peers[i] for i in mv[sel]], new_cubes[sel],
                )
            sub_cubes[mv] = new_cubes
            tpu.collect_local_batch(tpu.dispatch_staged_batch(
                wid_col, pos_col, sid_col, repl_col
            ))
            if t < warm:
                continue  # sub-tier compiles land in the warmup
            timing = tpu.last_device_timing
            walls.append(
                timing.get("compute_ms", 0.0) + timing.get("h2d_ms", 0.0)
            )
            if mode == "on":
                st = tpu.last_delta_stats
                reuse.append(st["reused"] / max(st["batch"], 1))
                dirty.append(st["dirty_cubes"])
                churn_rows.append(st["churn_rows"])
        return walls, reuse, dirty, churn_rows

    rebuild_walls, _, _, _ = run("off")
    scat0, sort0 = tpu.delta_sync_scatters, tpu.delta_sync_sorts
    update_walls, reuse, dirty, churn_rows = run("on")
    tpu.configure_delta_ticks("off")  # leave the shared backend as built
    # medians: the per-tick wall at steady state — a residual one-off
    # tier compile (new dirty-count pow2 mid-pass) must not masquerade
    # as recurring device work in either mode
    rebuild_ms = float(np.median(rebuild_walls))
    update_ms = float(np.median(update_walls))
    reuse_fraction = float(np.mean(reuse)) if reuse else 0.0
    return {
        "ticks": ticks,
        "churn_queries_per_tick": churn_q,
        "churn_subs_per_tick": churn_s,
        "reuse_fraction": round(reuse_fraction, 4),
        # the CI perf-gate leaf (bench_diff direction-aware,
        # percentage-scaled so a collapse clears the --min-abs floor)
        "reuse_pct": round(reuse_fraction * 100.0, 2),
        "dirty_cubes": int(np.mean(dirty)) if dirty else 0,
        "churn_rows_per_tick": int(np.mean(churn_rows)) if churn_rows
        else 0,
        "delta_update_ms": round(update_ms, 4),
        "rebuild_ms": round(rebuild_ms, 4),
        "speedup": round(rebuild_ms / max(update_ms, 1e-9), 2),
        "sync_scatters": tpu.delta_sync_scatters - scat0,
        "sync_sorts": tpu.delta_sync_sorts - sort0,
        "parity": 1 if _delta_parity_check(args) else 0,
    }


# --------------------------------------------------------------------
# config 1: 256 WS clients echo loop through the real server
# --------------------------------------------------------------------


def bench_config1(args) -> dict:
    import asyncio

    n_clients = 64 if args.quick else 256
    rounds = 5 if args.quick else 20
    group = 8  # co-located clients per cube: each message fans to 7

    async def scenario():
        from tests.client_util import WsClient, free_port
        from worldql_server_tpu.engine.config import Config
        from worldql_server_tpu.engine.server import WorldQLServer
        from worldql_server_tpu.protocol.types import (
            Instruction, Message, Replication, Vector3,
        )

        config = Config()
        config.store_url = "memory://"
        config.ws_port = free_port()
        config.http_enabled = False
        config.zmq_enabled = False
        config.spatial_backend = "cpu"
        server = WorldQLServer(config)
        await server.start()
        latencies: list[float] = []
        try:
            clients = []
            for i in range(n_clients):
                c = await WsClient.connect(config.ws_port)
                clients.append(c)
            positions = [
                Vector3(100.0 * (i // group), 5.0, 5.0)
                for i in range(n_clients)
            ]
            for c, pos in zip(clients, positions):
                await c.send(Message(
                    instruction=Instruction.AREA_SUBSCRIBE,
                    world_name="bench", position=pos,
                ))
            await asyncio.sleep(0.3)

            expected_per_client = group - 1

            async def recv_all(c):
                got = 0
                while got < expected_per_client * rounds:
                    m = await asyncio.wait_for(c.recv(timeout=30), 30)
                    if m.instruction != Instruction.LOCAL_MESSAGE:
                        continue
                    sent_at = float(m.parameter)
                    latencies.append((time.perf_counter() - sent_at) * 1e3)
                    got += 1

            receivers = [asyncio.create_task(recv_all(c)) for c in clients]
            # Rounds are paced by COMPLETION, not a fixed sleep: each
            # round's wall time runs from the first send until every
            # delivery of that round has landed, so the throughput
            # figure is the server's, not the pacer's.
            elapsed = 0.0
            expected_total = n_clients * expected_per_client
            for r in range(rounds):
                t0 = time.perf_counter()
                for c, pos in zip(clients, positions):
                    await c.send(Message(
                        instruction=Instruction.LOCAL_MESSAGE,
                        world_name="bench", position=pos,
                        parameter=repr(time.perf_counter()),
                        replication=Replication.EXCEPT_SELF,
                    ))
                # Bounded wait that surfaces receiver failures: a lost
                # delivery (e.g. a subscription that raced round 0) must
                # fail crisply, not spin this loop forever.
                deadline = t0 + 60.0
                while len(latencies) < expected_total * (r + 1):
                    dead = next(
                        (t for t in receivers
                         if t.done() and t.exception() is not None),
                        None,
                    )
                    if dead is not None:
                        raise dead.exception()
                    if time.perf_counter() > deadline:
                        raise RuntimeError(
                            f"config1 round {r}: {len(latencies)} of "
                            f"{expected_total * (r + 1)} deliveries after 60s"
                        )
                    await asyncio.sleep(0.002)
                elapsed += time.perf_counter() - t0
            await asyncio.gather(*receivers)
            for c in clients:
                await c.close()
            return latencies, elapsed
        finally:
            await server.stop()

    latencies, elapsed = asyncio.run(scenario())
    deliveries = len(latencies)
    p50, p99 = pctl(latencies, 50), pctl(latencies, 99)
    log(f"ws echo: {n_clients} clients, {deliveries} deliveries in "
        f"{elapsed:.2f}s ({deliveries / elapsed:,.0f}/s)  "
        f"p50 {p50:.2f} ms  p99 {p99:.2f} ms")
    return {
        "metric": "ws_echo_delivery_p99_ms",
        "value": round(p99, 3),
        "unit": "ms",
        "vs_baseline": round(TARGET_P99_MS / p99, 2),
        "p50_ms": round(p50, 3),
        "deliveries_per_s": round(deliveries / elapsed, 1),
        "clients": n_clients,
        "target_p99_ms": TARGET_P99_MS,
        "config": 1,
    }


# --------------------------------------------------------------------
# config 2: 10k random-walk clients, churn + broadcast @ 20 tick/s
# --------------------------------------------------------------------


def bench_config2(args) -> dict:
    from worldql_server_tpu.spatial.quantize import cube_coords_batch
    from worldql_server_tpu.spatial.tpu_backend import TpuSpatialBackend

    n = 1_000 if args.quick else 10_000
    ticks = 10 if args.quick else 50
    world = "walk"
    rng = np.random.default_rng(11)

    backend = TpuSpatialBackend(cube_size=16)
    positions = rng.uniform(-400.0, 400.0, (n, 3))
    velocities = rng.uniform(-30.0, 30.0, (n, 3))
    peers = [uuid_mod.UUID(int=i + 1) for i in range(n)]
    peer_arr = np.array(peers)
    cubes = cube_coords_batch(positions, backend.cube_size)
    backend.bulk_add_subscriptions(world, peers, cubes)
    backend.flush()

    world_ids = np.zeros(n, np.int32)
    sender_ids = np.arange(n, dtype=np.int32)
    repls = np.zeros(n, np.int8)
    csr_cap = n * 8

    # per-phase wall accumulators: churn (host bulk mutation
    # bookkeeping), flush (delta chunk H2D + device sort dispatch),
    # dispatch (query launch). Separating them is the attribution the
    # 50 ms budget claim needs — the link inflates flush+dispatch, the
    # device probes below say by how much.
    phase = {"churn": 0.0, "flush": 0.0, "dispatch": 0.0, "ticks": 0}

    def churn_tick():
        nonlocal positions
        t0 = time.perf_counter()
        positions += velocities * 0.05
        out = np.abs(positions) > 400.0
        velocities[out] = -velocities[out]
        np.clip(positions, -400.0, 400.0, out=positions)
        new_cubes = cube_coords_batch(positions, backend.cube_size)
        moved = (new_cubes != cubes).any(axis=1)
        n_moved = 0
        if moved.any():
            midx = np.flatnonzero(moved)
            backend.bulk_remove_subscriptions(
                world, peer_arr[midx].tolist(), cubes[midx]
            )
            backend.bulk_add_subscriptions(
                world, peer_arr[midx].tolist(), new_cubes[midx]
            )
            cubes[midx] = new_cubes[midx]
            n_moved = int(midx.size)
        t1 = time.perf_counter()
        backend.flush()
        t2 = time.perf_counter()
        handle = backend.match_arrays_async(
            world_ids, positions, sender_ids, repls, csr_cap=csr_cap
        )[1]
        t3 = time.perf_counter()
        phase["churn"] += t1 - t0
        phase["flush"] += t2 - t1
        phase["dispatch"] += t3 - t2
        phase["ticks"] += 1
        return n_moved, handle

    def collect(handle) -> None:
        total = _force(handle)
        assert total <= next_pow2(csr_cap), "csr_cap overflow"

    # Warmup: churn until the index has been through full compaction
    # cycles AND the shape-tier set has stabilized — a tier first seen
    # inside the measured loop would charge a 10s+ XLA compile to one
    # tick (observed as a 7s p99 outlier with a count-based warmup).
    warm, stable, seen = 0, 0, set()
    while warm < 80 and (backend.compactions < 2 or stable < 10):
        collect(churn_tick()[1])
        warm += 1
        tier = (backend._delta_buf_cap, backend._delta_k, backend._base_k)
        if tier in seen:
            stable += 1
        else:
            seen.add(tier)
            stable = 0
    backend.wait_compaction()
    log(f"warmup: {warm} churn ticks, {backend.compactions} compactions, "
        f"{len(seen)} shape tiers")

    # Double-buffered like the server's tick batcher: tick t's fan-out
    # is collected after tick t+1 dispatches, overlapping the device
    # round trip with the next tick's host-side churn. Primed with one
    # untimed tick so EVERY timed iteration includes a collect.
    lat = []
    churn_total = 0
    _, pending = churn_tick()
    collect_pending = pending
    phase.update(churn=0.0, flush=0.0, dispatch=0.0, ticks=0)
    t_start = time.perf_counter()
    for _ in range(ticks):
        t0 = time.perf_counter()
        moved, handle = churn_tick()
        churn_total += moved
        collect(collect_pending)
        collect_pending = handle
        lat.append((time.perf_counter() - t0) * 1e3)
    collect(collect_pending)
    sustained = (time.perf_counter() - t_start) / ticks * 1e3
    p50, p99 = pctl(lat, 50), pctl(lat, 99)
    nt = max(phase["ticks"], 1)
    churn_ms = phase["churn"] / nt * 1e3
    flush_ms = phase["flush"] / nt * 1e3
    dispatch_ms = phase["dispatch"] / nt * 1e3

    # device-side attribution, net of the link: chained-slope the delta
    # sort at the steady-state shape (the only device work flush does).
    # Clamped at 0: a sub-0.1ms sort can drown in link-jitter noise.
    sort_ms = max(_churn_sort_slope_ms(backend), 0.0)

    log(f"random-walk: {n} clients, {churn_total / ticks:.0f} resubs/tick, "
        f"sustained {sustained:.2f} ms/tick  iter p50 {p50:.2f}  "
        f"p99 {p99:.2f} (budget {TICK_BUDGET_MS} ms)")
    log(f"phases: churn {churn_ms:.2f}  flush {flush_ms:.2f} "
        f"(device sort {sort_ms:.2f})  dispatch {dispatch_ms:.2f} ms/tick")
    return {
        "metric": "random_walk_tick_ms",
        "value": round(sustained, 3),
        "unit": "ms",
        "vs_baseline": round(TICK_BUDGET_MS / max(sustained, 1e-9), 2),
        # pipelined loop-iteration time (dispatch t + collect t-1), NOT
        # per-message dispatch→collect latency — config 5 reports that
        "iter_p50_ms": round(p50, 3),
        "iter_p99_ms": round(p99, 3),
        # per-tick attribution: host churn bookkeeping; flush wall
        # (delta H2D + sort dispatch, link included); the flush's true
        # device sort cost by chained slope;
        # dispatch wall (query launch, link-inflated)
        "churn_host_ms": round(churn_ms, 3),
        "flush_ms": round(flush_ms, 3),
        "flush_device_sort_ms": round(sort_ms, 3),
        "dispatch_ms": round(dispatch_ms, 3),
        "measurement": "pipelined-depth2-v3",
        "clients": n,
        "resubs_per_tick": round(churn_total / ticks, 1),
        "budget_ms": TICK_BUDGET_MS,
        "config": 2,
    }


def _churn_sort_slope_ms(backend) -> float:
    """Per-flush DEVICE cost of the delta sort (sort + run-remainder +
    probe build — the fused launch `_sort_segment_dev`), by chained
    slope at the backend's current delta-buffer shape. Each iteration
    sorts the same rows rotated by a result-derived shift: identical
    workload, nothing hoistable."""
    import jax.numpy as jnp
    from functools import partial

    import jax

    from worldql_server_tpu.spatial.tpu_backend import (
        _sort_segment_dev, probe_buckets_for,
    )

    bufs = backend._delta_buf
    if bufs is None:
        return 0.0
    n_buckets = probe_buckets_for(len(backend._delta_key_count))

    @partial(jax.jit, static_argnames=("reps",))
    def chained(salt, bufs, reps):
        k, k2, p = bufs

        def body(i, carry):
            acc, shift = carry
            out = _sort_segment_dev(
                jnp.roll(k, shift), jnp.roll(k2, shift), jnp.roll(p, shift),
                n_buckets=n_buckets,
            )
            fold = jnp.int64(0)
            for o in out:  # every output stays live
                fold = fold ^ o.sum(dtype=jnp.int64)
            nxt = (fold.astype(jnp.int32) & jnp.int32(1023)) + jnp.int32(1)
            return acc + fold, nxt

        acc, _ = jax.lax.fori_loop(
            0, reps, body,
            (jnp.int64(0), (salt & jnp.int32(1023)) + jnp.int32(1))
        )
        return acc

    return chained_slope_ms(chained, (bufs,), (4, 16))


# --------------------------------------------------------------------
# config 3: 100k entities, on-device kNN (k=32) tick, single chip
# --------------------------------------------------------------------


def _tick_parity_check(n: int = 8_192) -> None:
    """Run one batch through BOTH fan-out resolvers on the current
    device — the fused Pallas kernel and the XLA stencil — and assert
    exact equality before anything is timed. On TPU this is the real
    (non-interpret) Pallas lowering; the CPU test suite only ever sees
    interpret mode."""
    import jax

    from worldql_server_tpu.ops.tick import example_state, make_tick_fn

    state = example_state(n=n, n_worlds=8)
    _, tgt_p, cnt_p = jax.jit(make_tick_fn(cube_size=16, k=32,
                                           pallas=True))(state)
    _, tgt_x, cnt_x = jax.jit(make_tick_fn(cube_size=16, k=32,
                                           pallas=False))(state)
    assert (np.asarray(cnt_p) == np.asarray(cnt_x)).all(), \
        "pallas/xla count divergence"
    assert (np.asarray(tgt_p) == np.asarray(tgt_x)).all(), \
        "pallas/xla target divergence"
    log(f"pallas parity: {n} entities, pallas == xla stencil on "
        f"{jax.devices()[0].platform}")


def _tick_device_slope_ms(n: int, k: int, reps_pair=(2, 8)) -> float:
    """Per-tick DEVICE time for the n-entity simulation tick by
    chained slope: the tick naturally threads state, and the fan-out
    targets fold back into the velocity via a +0-magnitude term (an
    f32 add of ~1e-30 — real data dependency, zero value change), so
    no stage can be elided or hoisted and the link round-trip cancels
    in the slope."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from worldql_server_tpu.ops.tick import (
        EntityState, example_state, make_tick_fn,
    )

    tick = make_tick_fn(cube_size=16, k=k)
    state = example_state(n=n, n_worlds=8)

    @partial(jax.jit, static_argnames=("reps",))
    def chained(salt, state, reps):
        # salt perturbs the initial state below f32 resolution: every
        # dispatch differs while the workload doesn't
        state = EntityState(
            state.position,
            state.velocity + salt.astype(jnp.float32) * jnp.float32(1e-30),
            state.world, state.peer,
        )

        def body(i, st):
            new, targets, counts = tick(st)
            fold = (targets.sum(dtype=jnp.int32)
                    ^ counts.sum(dtype=jnp.int32)).astype(jnp.float32)
            return EntityState(
                new.position,
                new.velocity + fold * jnp.float32(1e-30),
                new.world, new.peer,
            )
        out = jax.lax.fori_loop(0, reps, body, state)
        # scalar fold: the caller FETCHES the result to synchronize
        return out.velocity.sum(dtype=jnp.float32)

    return chained_slope_ms(chained, (state,), reps_pair)


def bench_config3(args) -> dict:
    import jax

    from worldql_server_tpu.ops.tick import example_state, make_tick_fn

    n = 8_192 if args.quick else 100_000
    n_big = 4_096 if args.quick else 1_000_000
    ticks = 10 if args.quick else 30
    tick = jax.jit(make_tick_fn(cube_size=16, k=32))
    state = example_state(n=n, n_worlds=8)

    # the two resolver paths must agree on-device before timing (quick
    # mode shrinks it: Pallas interpret on CPU is minutes at 8K)
    _tick_parity_check(512 if args.quick else 8_192)

    # warmup / compile — and force a readback so the device is in real
    # (non-elided) execution mode before anything is timed
    state, targets, counts = tick(state)
    np.asarray(counts)

    # Sustained: the tick chains state on device, so the honest
    # steady-state figure streams the whole run and syncs once — a
    # per-tick block would measure the host↔device link RTT, not the
    # simulation (the game loop only reads results it needs, it never
    # round-trips per frame). The sync is a FETCH: a D2H read is the
    # barrier this file's timing rules trust (see chained_slopes_ms).
    t_start = time.perf_counter()
    for _ in range(ticks):
        state, targets, counts = tick(state)
    np.asarray(counts)
    sustained = (time.perf_counter() - t_start) / ticks * 1e3

    # Latency: one synchronized tick — execution complete with the
    # per-entity counts on host. The dense [N, K] fan-out table stays
    # on device: a real consumer CSR-compacts it (config 5's path)
    # rather than shipping N*K ints, so fetching it here would time an
    # access pattern nothing uses.
    lat = []
    for _ in range(max(5, ticks // 4)):
        t0 = time.perf_counter()
        state, targets, counts = tick(state)
        np.asarray(counts)
        lat.append((time.perf_counter() - t0) * 1e3)
    p50, p99 = pctl(lat, 50), pctl(lat, 99)
    rate = n / (sustained / 1e3)
    log(f"knn tick: {n} entities k=32, sustained {sustained:.2f} ms/tick "
        f"sync p50 {p50:.2f} p99 {p99:.2f} ({rate:,.0f} entity-queries/s)")

    # the literal BASELINE config-5 workload: per-tick spatial-hash
    # rebuild at 1M entities, device time by chained slope
    big_ms = _tick_device_slope_ms(
        n_big, k=32, reps_pair=(1, 3) if args.quick else (2, 8)
    )
    big_rate = n_big / (big_ms / 1e3)
    log(f"knn tick {n_big}: device {big_ms:.2f} ms/tick "
        f"({big_rate:,.0f} entity-queries/s)")

    return {
        "metric": "knn_tick_ms",
        "value": round(sustained, 3),
        "unit": "ms",
        "vs_baseline": round(TICK_BUDGET_MS / max(sustained, 1e-9), 2),
        # fully-synchronized single-tick latency (small sample)
        "sync_p50_ms": round(p50, 3),
        "sync_p99_ms": round(p99, 3),
        "measurement": "streamed-v2",
        "entities": n,
        "entity_queries_per_s": round(rate),
        # 1M-entity per-tick rebuild (BASELINE config 5's literal
        # workload), device compute by chained slope
        "tick_1m_entities": n_big,
        "tick_1m_device_ms": round(big_ms, 3),
        "tick_1m_entity_queries_per_s": round(big_rate),
        "pallas_parity": "pass",
        "budget_ms": TICK_BUDGET_MS,
        "config": 3,
    }


# --------------------------------------------------------------------
# config 4: 64 worlds x 10k clients, mesh-sharded backend
# --------------------------------------------------------------------


def bench_config4(args) -> dict:
    import jax

    from worldql_server_tpu.parallel import (
        ShardedTpuSpatialBackend, make_fanout_mesh,
    )

    n_worlds = 8 if args.quick else 64
    per_world = 1_000 if args.quick else 10_000
    n_subs = n_worlds * per_world
    queries = 2_048 if args.quick else 16_384
    ticks = 10 if args.quick else 30

    mesh = make_fanout_mesh(1, len(jax.devices()))
    backend = ShardedTpuSpatialBackend(cube_size=16, mesh=mesh)
    rng = np.random.default_rng(21)
    peers, sub_positions, sub_world_ids = build_index(
        backend, rng, n_subs, n_worlds
    )
    t0 = time.perf_counter()
    backend.flush()
    log(f"device flush: {time.perf_counter() - t0:.1f}s "
        f"mesh={dict(mesh.shape)} stats={backend.device_stats()}")

    batches = [
        make_query_batch(rng, sub_positions, sub_world_ids, queries)
        for _ in range(ticks)
    ]
    csr_cap = queries * 4
    for b in batches[:2]:
        _, res = backend.match_arrays_async(*b, csr_cap=csr_cap)
        _force(res)                      # full-fetch path
        _collect_compact(backend, res)   # sharded pack kernel
    backend.wait_compaction()

    _, sustained, total_fanout, csr_cap = run_pipelined_adaptive(
        backend, batches, csr_cap, depth=8
    )
    lat2, _, _, _ = run_pipelined_adaptive(backend, batches, csr_cap, depth=2)
    lat2 = steady(lat2, 2)   # pipeline-fill tick: see steady()
    p50, p99 = pctl(lat2, 50), pctl(lat2, 99)
    log(f"sharded {n_worlds} worlds: sustained {sustained:.2f} ms/tick  "
        f"depth2 p50 {p50:.2f} p99 {p99:.2f}  "
        f"avg fan-out {total_fanout / (ticks * queries):.2f}")
    return {
        "metric": "sharded_worlds_tick_ms",
        "value": round(sustained, 3),
        "unit": "ms",
        "vs_baseline": round(TARGET_P99_MS / max(p99, 1e-9), 2),
        "p50_ms_depth2": round(p50, 3),
        "p99_ms_depth2": round(p99, 3),
        "worlds": n_worlds,
        "subscriptions": n_subs,
        "mesh": dict(mesh.shape),
        "target_p99_ms": TARGET_P99_MS,
        "config": 4,
    }


# --------------------------------------------------------------------
# config 7: sharded-backend scaling curve (ROADMAP item 3)
# --------------------------------------------------------------------


def bench_config7(args) -> dict:
    """``sharded_overhead``: ShardedTpuSpatialBackend per-tick cost on
    a 1→8-device mesh vs the single-device backend on the SAME
    workload — the shard_map dispatch + pmax merge overhead the
    multi-chip story pays per tick (ROADMAP item 3 / VERDICT weak #7:
    the sharded backend had parity proof but zero perf evidence). On a
    host without >= 8 attached devices the bench re-execs itself with
    ``--xla_force_host_platform_device_count=8``: a VIRTUAL host-device
    mesh times real dispatch/collective overhead, not kernel FLOP
    scaling — the ``platform`` field names which regime produced the
    numbers."""
    import os
    import jax

    if len(jax.devices()) >= 8:
        return _sharded_overhead_inner(args)

    import re
    import subprocess

    env = dict(os.environ)
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""),
    )
    env["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
    # a TPU-less host with libtpu installed would hang enumerating the
    # plugin; the virtual mesh is host-platform by definition
    env.setdefault("JAX_PLATFORMS", "cpu")
    cmd = [
        sys.executable, os.path.abspath(__file__), "--config", "7",
        "--subs", str(args.subs), "--queries", str(args.queries),
        "--ticks", str(args.ticks),
    ]
    if args.quick:
        cmd.append("--quick")
    log("config 7: re-exec with 8 virtual host devices "
        f"(this process has {len(jax.devices())})")
    out = subprocess.run(
        cmd, capture_output=True, text=True, env=env, timeout=3000,
    )
    for line in out.stderr.splitlines():
        log(f"[sharded-overhead] {line}")
    if out.returncode != 0:
        raise RuntimeError(
            f"sharded-overhead child failed (rc={out.returncode})"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def _sharded_overhead_inner(args) -> dict:
    import jax

    from worldql_server_tpu.parallel import (
        ShardedTpuSpatialBackend, make_fanout_mesh,
    )
    from worldql_server_tpu.spatial.tpu_backend import TpuSpatialBackend

    devices = jax.devices()
    platform = devices[0].platform
    # quick (CI) keeps the compile bill to two meshes; the full curve
    # needs the 4- and 8-shard points that expose collective scaling
    shard_counts = [c for c in ((1, 2) if args.quick else (1, 2, 4, 8))
                    if c <= len(devices)]
    n_worlds = 8
    subs = min(args.subs, 200_000)  # 5 index builds — bound the bill
    queries = args.queries
    ticks = max(4, min(args.ticks, 12))

    def measure(backend) -> float:
        from worldql_server_tpu.spatial.tpu_backend import padded_slots

        rng = np.random.default_rng(5)
        _, sub_positions, sub_world_ids = build_index(
            backend, rng, subs, n_worlds
        )
        backend.flush()
        backend.wait_compaction()
        batches = [
            make_query_batch(rng, sub_positions, sub_world_ids, queries)
            for _ in range(ticks)
        ]
        # size the CSR buffer from the observed row-padded footprint
        # (config-5 discipline) so every backend runs the SAME capacity
        # tier — mid-measure overflow retries would skew the comparison
        cnts = np.asarray(
            backend.match_arrays_async(*batches[0], csr_cap=queries * 16)[1][0]
        )
        csr_cap = max(2048, padded_slots(cnts) * 3 // 2)
        # warm EVERY batch once through the compacted collect: each
        # distinct fan-out total can land a new pack-bucket tier, and
        # at these small tick counts one stray compile would dominate
        # the sustained mean (the 207s-outlier lesson, in miniature)
        for b in batches:
            _collect_compact(
                backend, backend.match_arrays_async(*b, csr_cap=csr_cap)[1]
            )
        best = None
        for _ in range(3):
            _, sustained, _, _ = run_pipelined_adaptive(
                backend, batches, csr_cap, depth=1
            )
            best = sustained if best is None else min(best, sustained)
        return best

    single_ms = measure(TpuSpatialBackend(cube_size=16))
    log(f"sharded_overhead: single-device {single_ms:.3f} ms/tick "
        f"({platform})")
    curve = []
    for c in shard_counts:
        mesh = make_fanout_mesh(1, c, devices[:c])
        ms = measure(ShardedTpuSpatialBackend(cube_size=16, mesh=mesh))
        curve.append({
            "devices": c,
            "tick_ms": round(ms, 3),
            "vs_single": round(ms / single_ms, 2),
        })
        log(f"sharded_overhead: {c} space shards {ms:.3f} ms/tick "
            f"({ms / single_ms:.2f}x single)")
    return {
        "metric": "sharded_overhead_tick_ms",
        "value": curve[-1]["tick_ms"],
        "unit": "ms",
        # < 1 means the mesh run is SLOWER than single-device — the
        # honest overhead framing, not a speedup claim
        "vs_baseline": round(single_ms / max(curve[-1]["tick_ms"], 1e-9), 2),
        "platform": platform,
        "sharded_overhead": {
            "single_device_tick_ms": round(single_ms, 3),
            "curve": curve,
            # the 1-shard point IS the pure shard_map+pmax wrapper cost
            "shard_map_pmax_overhead_x": curve[0]["vs_single"],
            "note": (
                "virtual host-device mesh: dispatch + collective "
                "overhead is real, kernel FLOP scaling is not"
                if platform == "cpu" else
                "attached accelerator mesh: end-to-end per-tick scaling"
            ),
        },
        "subscriptions": subs,
        "queries": queries,
        "config": 7,
    }


def bench_config6(args) -> dict:
    """Record-op durability workload (ISSUE 2): RecordCreate handler
    latency through the REAL Router against the SQLite store, once per
    durability mode. 'off' awaits the store commit inline (the
    reference's synchronous-persist shape), 'wal' acks after the
    group-commit fsync + enqueue, 'sync' pays WAL fsync AND the inline
    commit. The headline is wal-mode p99 — what a record write costs
    the event loop with durability ON."""
    import shutil
    import tempfile

    from worldql_server_tpu.durability import (
        DurabilityPipeline, WriteAheadLog,
    )
    from worldql_server_tpu.engine.config import Config
    from worldql_server_tpu.engine.peers import PeerMap
    from worldql_server_tpu.engine.router import Router
    from worldql_server_tpu.protocol import Instruction, Message, Record
    from worldql_server_tpu.protocol.types import Vector3
    from worldql_server_tpu.spatial.cpu_backend import CpuSpatialBackend
    from worldql_server_tpu.storage.store import open_store

    ops = 300 if args.quick else 2_000
    recs_per_op = 4
    rng = np.random.default_rng(17)
    sender = uuid_mod.uuid4()

    def make_messages():
        msgs = []
        for i in range(ops):
            records = [
                Record(
                    uuid=uuid_mod.UUID(int=i * recs_per_op + j + 1),
                    position=Vector3(*rng.uniform(-500, 500, 3)),
                    world_name="bench",
                    data="x" * 64,
                )
                for j in range(recs_per_op)
            ]
            msgs.append(Message(
                instruction=Instruction.RECORD_CREATE,
                sender_uuid=sender, world_name="bench", records=records,
            ))
        return msgs

    results = {}
    for mode in ("off", "wal", "sync"):
        tmp = tempfile.mkdtemp(prefix=f"wql-bench6-{mode}-")

        async def scenario(mode=mode, tmp=tmp):
            config = Config(
                store_url=f"sqlite://{tmp}/records.db",
                durability=mode, wal_dir=f"{tmp}/wal",
            )
            store = open_store(config.store_url, config)
            await store.init()
            wal = None
            durability = None
            if mode != "off":
                wal = WriteAheadLog(
                    config.wal_dir,
                    fsync_ms=0.0 if mode == "sync" else config.wal_fsync_ms,
                    segment_bytes=config.wal_segment_bytes,
                )
                wal.start()
                durability = DurabilityPipeline(
                    store, mode=mode, wal=wal, config=config,
                )
                durability.start()
            router = Router(
                PeerMap(), CpuSpatialBackend(config.sub_region_size),
                store, durability=durability,
            )
            lat = []
            for msg in make_messages():
                t0 = time.perf_counter()
                await router.handle_message(msg)
                lat.append((time.perf_counter() - t0) * 1e3)
            if durability is not None:
                drained = await durability.stop()
                assert drained, "write-behind queue failed to drain"
                await wal.close()
            await store.close()
            return lat

        lat = asyncio.run(scenario())
        shutil.rmtree(tmp, ignore_errors=True)
        results[mode] = (pctl(lat, 50), pctl(lat, 99))
        log(f"durability={mode}: handler p50 {results[mode][0]:.3f} ms "
            f"p99 {results[mode][1]:.3f} ms  ({ops} ops x "
            f"{recs_per_op} records)")

    return {
        "metric": "record_op_handler_p99_ms",
        "value": round(results["wal"][1], 4),
        "unit": "ms",
        # speedup of the write-behind handler over the reference's
        # inline-commit shape (> 1.0 = durability off the hot path)
        "vs_baseline": round(
            results["off"][1] / max(results["wal"][1], 1e-9), 2
        ),
        "off_p50_ms": round(results["off"][0], 4),
        "off_p99_ms": round(results["off"][1], 4),
        "wal_p50_ms": round(results["wal"][0], 4),
        "wal_p99_ms": round(results["wal"][1], 4),
        "sync_p50_ms": round(results["sync"][0], 4),
        "sync_p99_ms": round(results["sync"][1], 4),
        "ops": ops,
        "records_per_op": recs_per_op,
        "config": 6,
    }


def bench_config8(args) -> dict:
    """Entity simulation workload (ISSUE 9 + 11): the device-resident
    moving-object plane. Three legs:

    * **ingest** — PRE-ENCODED wire buffers through the columnar
      wire→SoA path (``ColumnarIngest`` → ``wql_decode_entities`` →
      ``EntityPlane.ingest_columns``, zero per-entity Python) with the
      per-tick index churn flowing through the LSM base+delta path
      (``bulk_move_subscriptions``) → ``updates_per_s`` (wire→staged
      columns) and ``updates_per_s_sustained`` (including every device
      tick in the wall), plus ``churn_rows_per_s``;
    * **device tick** — steady-state integrate + kNN resolve
      (one fused ops/tick.py kernel) → ``knn_ms`` (p50 of the
      dispatch+collect wall over a quiet window), with incremental H2D
      (only touched slots ship — ``h2d_scatter``/``h2d_full``);
    * **e2e** — a REAL server over ZMQ: clients register entities and
      stream updates through the transport's columnar drain, neighbor
      frames ride the delivery path cohort-encoded in native code, and
      ``frame.e2e_ms`` p99 (the PR 7 frame clock) is the honest
      dispatch→socket-write number → ``e2e_p99_ms``.

    ``--smoke`` shrinks shapes, forces a small compaction threshold,
    and asserts the device path fired, the NATIVE columnar decode fired
    (both legs), at least one delta compaction ran mid-stream, the
    steady window re-traced nothing, and frames were delivered — the
    CI gate for the subsystem."""
    import struct
    import uuid as _uuid

    from tests.client_util import ZmqClient, free_port
    from worldql_server_tpu.engine.config import Config
    from worldql_server_tpu.engine.peers import PeerMap
    from worldql_server_tpu.engine.server import WorldQLServer
    from worldql_server_tpu.entities import ColumnarIngest, EntityPlane
    from worldql_server_tpu.protocol import (
        Instruction,
        Message,
        deserialize_message,
        serialize_message,
    )
    from worldql_server_tpu.protocol.types import Entity, Vector3
    from worldql_server_tpu.spatial.tpu_backend import TpuSpatialBackend
    from worldql_server_tpu.utils.retrace import GUARD

    quick = args.quick
    n_entities = 768 if quick else 16_384
    n_peers = 32 if quick else 512
    ticks = 8 if quick else 30
    batch_per_msg = 64
    rng = np.random.default_rng(23)

    backend = TpuSpatialBackend(
        16, compact_threshold=(256 if args.smoke else None)
    )
    plane = EntityPlane(
        backend, PeerMap(), cube_size=16, k=8, dt=0.05,
        bounds=1000.0, max_entities=max(n_entities * 2, 1 << 16),
    )
    peers = [_uuid.uuid4() for _ in range(n_peers)]
    ents = [_uuid.uuid4() for _ in range(n_entities)]
    positions = rng.uniform(-800, 800, (n_entities, 3))
    velocities = rng.uniform(-120, 120, (n_entities, 3)).astype(np.float32)

    def owner_msgs(idx) -> list:
        """Update batches grouped BY OWNER (ownership is enforced)."""
        by_peer: dict[int, list[int]] = {}
        for i in idx:
            by_peer.setdefault(int(i) % n_peers, []).append(int(i))
        msgs = []
        for p, rows in by_peer.items():
            for lo in range(0, len(rows), batch_per_msg):
                chunk = rows[lo:lo + batch_per_msg]
                msgs.append(Message(
                    instruction=Instruction.LOCAL_MESSAGE,
                    sender_uuid=peers[p], world_name="bench",
                    entities=[
                        Entity(
                            uuid=ents[i],
                            position=Vector3(*positions[i]),
                            world_name="bench",
                            flex=struct.pack("<3f", *velocities[i]),
                        ) for i in chunk
                    ],
                ))
        return msgs

    def tick_once() -> float:
        t0 = time.perf_counter()
        handle = plane.dispatch_tick()
        result = plane.collect_tick(handle)
        device_ms = (time.perf_counter() - t0) * 1e3
        plane.apply(result)
        return device_ms

    # -- leg 1: registration (object path — control plane), then the
    # columnar wire ingest: every round's update batches are encoded
    # to wire bytes OUTSIDE the timed loop (the measured leg is
    # wire→SoA→device, not the client-side encoder), then batch-decode
    # + stage through the same ColumnarIngest the transport uses --
    t0 = time.perf_counter()
    for msg in owner_msgs(np.arange(n_entities)):
        plane.ingest(msg)
    register_wall = time.perf_counter() - t0
    plane.precompile()  # tick tier + scatter ladder, PR 8 discipline
    tick_once()  # first tick: full-tier twin upload
    compile_guard = GUARD.snapshot()

    ingest = ColumnarIngest(plane, sender_known=lambda u: True)
    wire_native = ingest.active
    rounds = []
    for t in range(ticks):
        # re-position a rotating half of the population onto fresh
        # random cubes: the NEXT applied tick re-quantizes them and
        # the move flows through bulk_move_subscriptions (delta path)
        half = np.arange(t % 2, n_entities, 2)
        positions[half] = rng.uniform(-800, 800, (half.size, 3))
        rounds.append([serialize_message(m) for m in owner_msgs(half)])

    churn0 = plane.index_moves
    applied_box = [0]
    ingest_wall_box = [0.0]

    async def drive():
        async def slow_route(data):
            plane.ingest(deserialize_message(data))

        for datas in rounds:
            before = plane.updates
            ti = time.perf_counter()
            await ingest.process_batch(list(datas), slow_route)
            ingest_wall_box[0] += time.perf_counter() - ti
            applied_box[0] += plane.updates - before
            tick_once()

    t0 = time.perf_counter()
    asyncio.run(drive())
    ingest_e2e_wall = time.perf_counter() - t0
    total_updates = applied_box[0]
    ingest_wall = max(ingest_wall_box[0], 1e-9)
    backend.wait_compaction()
    churn_rows = plane.index_moves - churn0

    # -- leg 2: quiet device window (no ingest) → knn_ms + retrace --
    quiet_ms = sorted(tick_once() for _ in range(max(5, ticks // 2)))
    knn_ms = quiet_ms[len(quiet_ms) // 2]
    retrace_delta = GUARD.delta(compile_guard)
    sim_retraces = retrace_delta.get("entities.sim_tick", 0)

    # CPU-reference ratio: the reference-class per-tick work is one
    # proximity resolve per entity against a dict cube index (the
    # per-message hot loop of SURVEY §3.2, batch-shaped). It skips
    # integration and ordering entirely, so the ratio UNDERSTATES the
    # device tick — an honest floor, not a flattering one.
    from worldql_server_tpu.spatial.backend import LocalQuery
    from worldql_server_tpu.spatial.cpu_backend import CpuSpatialBackend

    cpu = CpuSpatialBackend(16)
    live = plane._live[: plane._cap]
    for slot in np.flatnonzero(live).tolist():
        cpu.add_subscription(
            plane._world_names[int(plane._wid[slot])],
            plane._peer_uuids[int(plane._pid[slot])],
            tuple(int(c) for c in plane._cube[slot]),
        )
    queries = [
        LocalQuery(
            world=plane._world_names[int(plane._wid[slot])],
            position=Vector3(*plane._pos[slot].tolist()),
            sender=plane._peer_uuids[int(plane._pid[slot])],
        )
        for slot in np.flatnonzero(live).tolist()
    ]
    t0 = time.perf_counter()
    cpu.match_local_batch(queries)
    cpu_ref_ms = (time.perf_counter() - t0) * 1e3

    # -- leg 3: e2e over a real server + ZMQ transport. Shapes are
    # sized for SUSTAINABLE load (every co-cube entity produces a
    # frame every tick): the number is per-frame latency at steady
    # state, not a saturation probe — server_delivery (config 5)
    # already owns the throughput-ceiling question. --
    e2e_entities = 32 if quick else 512
    e2e_seconds = 2.0 if quick else 6.0
    e2e_tick = 0.05

    async def e2e_scenario():
        config = Config()
        config.store_url = "memory://"
        config.http_enabled = False
        config.ws_enabled = False
        config.zmq_server_port = free_port()
        config.zmq_server_host = "127.0.0.1"
        config.spatial_backend = "tpu"
        config.tick_interval = e2e_tick
        config.entity_sim = True
        config.entity_k = 8
        server = WorldQLServer(config)
        await server.start()
        try:
            a = await ZmqClient.connect(config.zmq_server_port)
            b = await ZmqClient.connect(config.zmq_server_port)
            # pairwise co-cube entities from DIFFERENT peers so every
            # tick produces cross-peer neighbor frames
            eids = [_uuid.uuid4() for _ in range(e2e_entities)]
            for i, eid in enumerate(eids):
                client = a if i % 2 == 0 else b
                base = (i // 2) * 64.0
                await client.send(Message(
                    instruction=Instruction.LOCAL_MESSAGE,
                    world_name="bench",
                    entities=[Entity(
                        uuid=eid,
                        position=Vector3(base + 1.0 + (i % 2), 1.0, 1.0),
                        world_name="bench",
                    )],
                ))

            async def drain(client):
                try:
                    while True:
                        await client.recv(timeout=0.5)
                except (asyncio.TimeoutError, asyncio.CancelledError):
                    pass

            drains = [asyncio.ensure_future(drain(a)),
                      asyncio.ensure_future(drain(b))]
            # warmup: wait until the simulation actually ticks at
            # rate (the first tick jit-compiles the sim kernel — whole
            # seconds on a CPU container) and the compile caches went
            # quiet, THEN restart the frame clock: the measured window
            # is steady-state serving, not jit walls
            plane_ = server.entity_plane
            expect = max(3, int(0.5 / e2e_tick) - 3)
            prev_ticks, prev_compiles, stable = -1, -1, 0
            for _ in range(60):  # bounded: <= 30 s
                await asyncio.sleep(0.5)
                ticks_now = plane_.applied_ticks
                compiles = sum(GUARD.counts().values())
                if (prev_ticks >= 0
                        and ticks_now - prev_ticks >= expect
                        and compiles == prev_compiles):
                    stable += 1
                    if stable >= 2:
                        break
                else:
                    stable = 0
                prev_ticks, prev_compiles = ticks_now, compiles
            server.metrics.histograms.pop("frame.e2e_ms", None)
            bytes0 = server.peer_map.bytes_delivered
            ticks0 = plane_.applied_ticks
            end = time.perf_counter() + e2e_seconds
            while time.perf_counter() < end:
                # stream updates to a rotating slice
                for i in range(0, e2e_entities, 8):
                    client = a if i % 2 == 0 else b
                    base = (i // 2) * 64.0
                    await client.send(Message(
                        instruction=Instruction.LOCAL_MESSAGE,
                        world_name="bench",
                        entities=[Entity(
                            uuid=eids[i],
                            position=Vector3(base + 1.0 + (i % 2), 1.0, 1.0),
                            world_name="bench",
                        )],
                    ))
                await asyncio.sleep(e2e_tick * 2)
            for d in drains:
                d.cancel()
            await asyncio.gather(*drains, return_exceptions=True)
            hist = server.metrics.histograms.get("frame.e2e_ms")
            snap = hist.snapshot() if hist is not None else None
            stats = server.entity_plane.stats()
            # byte volume over the measured window (ISSUE 18):
            # bytes/tick at the delivery boundary plus the per-client
            # wire rate — the leaves the interest bench (config 13)
            # compares off vs on
            bytes_win = server.peer_map.bytes_delivered - bytes0
            ticks_win = plane_.applied_ticks - ticks0
            vol = {
                "delivered_bytes_per_tick": round(
                    bytes_win / max(ticks_win, 1), 1
                ),
                "bytes_per_recipient_per_s": round(
                    bytes_win / 2 / e2e_seconds, 1
                ),
                "frame_delta_ratio": server.metrics.snapshot()[
                    "gauges"
                ].get("frame.delta_ratio") or 0.0,
            }
            await a.close()
            await b.close()
            return snap, stats, vol
        finally:
            await server.stop()

    e2e_hist, e2e_stats, e2e_vol = asyncio.run(e2e_scenario())

    if args.smoke:
        assert plane.dispatches > 0, "smoke: sim device path never fired"
        assert wire_native and plane.wire_rows > 0, (
            "smoke: native columnar decode never fired on the ingest "
            f"leg ({ingest.stats()})"
        )
        assert ingest.slow_messages == 0, (
            f"smoke: update batches fell off the fast path "
            f"({ingest.stats()})"
        )
        assert e2e_stats["wire_rows"] > 0, (
            "smoke: e2e server ingest never took the columnar path "
            f"({e2e_stats})"
        )
        assert plane.h2d_scatter > 0, (
            "smoke: incremental H2D scatter never fired"
        )
        assert backend.compactions >= 1, (
            "smoke: churn never forced a delta compaction"
        )
        assert sim_retraces == 0, (
            f"smoke: quiet sim window re-traced: {retrace_delta}"
        )
        assert e2e_stats["frames"] > 0, (
            "smoke: no neighbor frames delivered e2e"
        )
        log(f"smoke: {backend.compactions} compactions, "
            f"{e2e_stats['frames']} e2e frames "
            f"({e2e_stats['frames_native']} native-encoded), "
            f"{plane.wire_rows} columnar rows, 0 quiet retraces")

    updates_per_s = total_updates / max(ingest_wall, 1e-9)
    updates_sustained = total_updates / max(ingest_e2e_wall, 1e-9)
    result = {
        "metric": "entity_sim_knn_ms",
        "value": round(knn_ms, 4),
        "unit": "ms",
        # CPU dict-index resolve of the same per-entity queries vs the
        # device integrate+kNN tick (see leg-2 comment: a floor)
        "vs_baseline": round(cpu_ref_ms / max(knn_ms, 1e-9), 2),
        "entity_sim": {
            "cpu_reference_ms": round(cpu_ref_ms, 4),
            # wire→staged-columns ingest throughput (the PR 11 lever)
            "updates_per_s": round(updates_per_s, 1),
            # the same updates with every device tick in the wall —
            # the sustainable end-to-end rate on this host
            "updates_per_s_sustained": round(updates_sustained, 1),
            "wire_native": wire_native,
            "wire_rows": plane.wire_rows,
            "wire_slow_rows": plane.wire_slow_rows,
            "column_flips": plane.column_flips,
            "h2d_scatter": plane.h2d_scatter,
            "h2d_full": plane.h2d_full,
            "frames_native": plane.frames_native,
            "knn_ms": round(knn_ms, 4),
            "e2e_p99_ms": (
                round(e2e_hist["p99_ms"], 3) if e2e_hist else None
            ),
            "e2e_p50_ms": (
                round(e2e_hist["p50_ms"], 3) if e2e_hist else None
            ),
            "e2e_frames": e2e_stats["frames"],
            "e2e_wire_rows": e2e_stats["wire_rows"],
            "delivered_bytes_per_tick": e2e_vol[
                "delivered_bytes_per_tick"
            ],
            "bytes_per_recipient_per_s": e2e_vol[
                "bytes_per_recipient_per_s"
            ],
            "frame_delta_ratio": e2e_vol["frame_delta_ratio"],
            "entities": n_entities,
            "peers": n_peers,
            "k": 8,
            "register_per_s": round(n_entities / max(register_wall, 1e-9), 1),
            "churn_rows_per_s": round(
                churn_rows / max(ingest_e2e_wall, 1e-9), 1
            ),
            "index_moves": churn_rows,
            "compactions": backend.compactions,
            "sim_retraces_quiet": sim_retraces,
            "delta_rows": backend.device_stats()["delta_rows"],
        },
        "config": 8,
    }
    log(f"entity_sim: {updates_per_s:,.0f} updates/s wire ingest "
        f"({updates_sustained:,.0f}/s sustained incl. ticks), "
        f"knn {knn_ms:.3f} ms @ {n_entities} entities, "
        f"e2e p99 {result['entity_sim']['e2e_p99_ms']} ms, "
        f"{backend.compactions} compactions")
    return result


# --------------------------------------------------------------------


def bench_config9(args) -> dict:
    """Overload-storm admission workload (ISSUE 10): a real server
    over real ZMQ with the OverloadGovernor on, deliberately throttled
    (tiny tick budget → degraded admitted tier) so a single client can
    offer sustained multiples of the sustainable rate even on a 1-core
    container. Three legs:

    * **sustainable** — unpaced flood, governor engaged → the admitted
      ceiling ``sustainable_per_s`` (the 1x reference);
    * **2x / 10x** — offered load paced to 2x and 10x of that ceiling
      while a record-op stream (durability='wal', acked at the fsync)
      runs through the SAME router → per-phase admitted-vs-offered
      rate, shed fraction by class, governor peak state, and the
      admitted record-op p99;
    * **audit** — after each phase drains, offered == flushed +
      drop-oldest + shed-at-ingest, exactly (shed work is never
      silent).

    ``--smoke`` shrinks the windows and asserts the 10x phase actually
    engaged the governor, shed work, kept the audit exact, and landed
    every record op — the CI gate for the overload plane."""
    import tempfile

    from tests.client_util import ZmqClient, free_port
    from worldql_server_tpu.engine.config import Config
    from worldql_server_tpu.engine.server import WorldQLServer
    from worldql_server_tpu.protocol import Instruction, Message
    from worldql_server_tpu.protocol.types import Record, Vector3

    quick = args.quick
    base_s = 0.8 if quick else 3.0
    phase_s = 1.0 if quick else 4.0
    record_rate = 25  # record ops per second, through the wal path

    tmp = tempfile.TemporaryDirectory(prefix="wql-overload-bench-")
    config = Config(
        store_url=f"sqlite://{tmp.name}/bench.db",
        durability="wal", wal_dir=f"{tmp.name}/wal",
        checkpoint_interval=0.5,
        http_enabled=False, ws_enabled=False,
        zmq_server_host="127.0.0.1", zmq_server_port=free_port(),
        spatial_backend="cpu", tick_interval=0.01,
        max_batch=256, overload="on",
        overload_tick_budget_ms=0.5, overload_min_batch=8,
        overload_deadline_k=2, overload_recover_ticks=5,
    )

    async def scenario() -> dict:
        server = WorldQLServer(config)
        await server.start()
        gov = server.governor
        metrics = server.metrics
        try:
            client = await ZmqClient.connect(config.zmq_server_port)

            def counters() -> dict:
                snap = metrics.snapshot()["counters"]
                return {
                    "seen": snap.get("messages.local_message", 0),
                    "flushed": snap.get("tick.messages", 0),
                    "dropped": gov.drop_oldest,
                    "shed": gov.shed["local"],
                    "limited": gov.rate_limited,
                }

            async def flood(duration: float, rate: float | None):
                """Offer locals for ``duration``; None = unpaced.
                Returns (offered, wall)."""
                sent = 0
                t0 = time.perf_counter()
                end = t0 + duration
                while time.perf_counter() < end:
                    for _ in range(32):
                        await client.send(Message(
                            instruction=Instruction.LOCAL_MESSAGE,
                            world_name="bench",
                            position=Vector3(1.0, 1.0, 1.0),
                            parameter="s",
                        ))
                        sent += 1
                    if rate is not None:
                        pace = t0 + sent / rate - time.perf_counter()
                        if pace > 0:
                            await asyncio.sleep(pace)
                        else:
                            await asyncio.sleep(0)
                return sent, time.perf_counter() - t0

            async def drain():
                for _ in range(1000):
                    if not server.ticker._queue:
                        return
                    await asyncio.sleep(0.01)

            record_seq = [0]

            async def record_ops(duration: float) -> list:
                walls = []
                end = time.perf_counter() + duration
                while time.perf_counter() < end:
                    record_seq[0] += 1
                    i = record_seq[0]
                    t0 = time.perf_counter()
                    await server.router.durability.insert_records([
                        Record(
                            uuid=uuid_mod.UUID(int=i), world_name="w",
                            position=Vector3(1, 2, 3), data=f"r{i}",
                        )
                    ])
                    walls.append((time.perf_counter() - t0) * 1e3)
                    await asyncio.sleep(1.0 / record_rate)
                return walls

            async def run_phase(duration: float, rate: float | None):
                """One offered-load window: flood (paced or unpaced)
                + the concurrent record stream, drained, audited."""
                before = counters()
                gov.peak_level = gov.level  # peak WITHIN this phase
                (offered, wall), walls = await asyncio.gather(
                    flood(duration, rate), record_ops(duration),
                )
                await drain()
                after = counters()
                delta = {k: after[k] - before[k] for k in after}
                walls.sort()
                shed_total = delta["dropped"] + delta["shed"]
                return {
                    "offered_per_s": round(offered / wall, 1),
                    "admitted_per_s": round(delta["flushed"] / wall, 1),
                    "shed_fraction_local": round(
                        shed_total / max(delta["seen"], 1), 4
                    ),
                    "drop_oldest": delta["dropped"],
                    "shed_at_ingest": delta["shed"],
                    "rate_limited": delta["limited"],
                    "governor_peak_level": gov.peak_level,
                    "record_ops": len(walls),
                    "record_p99_ms": round(
                        walls[max(0, int(len(walls) * 0.99) - 1)], 3
                    ) if walls else None,
                    # the exactness invariant, reported not assumed
                    "audit_exact": (
                        delta["seen"] == delta["flushed"] + shed_total
                    ),
                }

            # -- leg 1: saturation storm (unpaced = everything the
            # client can offer). What the governed server SERVES under
            # it is the sustainable ceiling — the 1x reference for the
            # paced legs — and the shedding here is guaranteed, which
            # is what the smoke gate pins.
            saturation = await run_phase(base_s, None)
            sustainable = max(saturation["admitted_per_s"], 1.0)
            phases = {"saturation": saturation}

            # -- legs 2+3: paced at 2x and 10x the sustained ceiling --
            for factor in (2, 10):
                phase = await run_phase(phase_s, sustainable * factor)
                phase["target_factor"] = factor
                phase["achieved_factor"] = round(
                    phase["offered_per_s"] / sustainable, 2
                )
                phases[f"{factor}x"] = phase

            # recovery: back to OK after the storm (bounded wait)
            recovered_ticks = None
            ticks0 = gov.ticks
            for _ in range(600):
                if gov.state == "ok" and not gov.degraded():
                    recovered_ticks = gov.ticks - ticks0
                    break
                await asyncio.sleep(0.01)

            await client.close()
            return {
                "sustainable_per_s": round(sustainable, 1),
                "phases": phases,
                "recovered_to_ok_within_ticks": recovered_ticks,
                "transitions": gov.transitions,
                "coalesced": int(
                    metrics.snapshot()["counters"].get(
                        "overload.coalesced", 0
                    )
                ),
                "record_ops_total": record_seq[0],
            }
        finally:
            await server.stop()
            tmp.cleanup()

    overload = asyncio.run(scenario())

    if args.smoke:
        sat = overload["phases"]["saturation"]
        assert sat["governor_peak_level"] >= 1, (
            "smoke: saturation storm never escalated the governor"
        )
        assert sat["drop_oldest"] + sat["shed_at_ingest"] > 0, (
            "smoke: saturation storm shed nothing"
        )
        for phase in overload["phases"].values():
            assert phase["audit_exact"], (
                f"smoke: shed accounting mismatch: {phase}"
            )
        assert sat["record_ops"] > 0 and sat["record_p99_ms"], (
            "smoke: record stream never ran under the storm"
        )
        assert overload["recovered_to_ok_within_ticks"] is not None, (
            "smoke: governor never returned to OK after the storm"
        )
        log(
            f"smoke: saturation shed {sat['shed_fraction_local']:.1%}, "
            f"audit exact, record p99 {sat['record_p99_ms']} ms, "
            f"OK after {overload['recovered_to_ok_within_ticks']} ticks"
        )

    p10 = overload["phases"]["10x"]
    result = {
        "metric": "overload_admitted_at_10x_per_s",
        "value": p10["admitted_per_s"],
        "unit": "per_s",
        "overload": overload,
        "config": 9,
    }
    log(
        f"overload: sustainable {overload['sustainable_per_s']:,.0f}/s; "
        f"10x offered {p10['offered_per_s']:,.0f}/s -> admitted "
        f"{p10['admitted_per_s']:,.0f}/s, shed "
        f"{p10['shed_fraction_local']:.1%}, record p99 "
        f"{p10['record_p99_ms']} ms"
    )
    return result


def bench_config10(args) -> dict:
    """Adversarial scenario suite (ISSUE 12, ROADMAP 5b): run the
    first-class scenario library — flash-crowd migration, battle-royale
    shrinking bounds, hostile-swarm reconnect storm, mixed game-tick —
    each a REAL server over real ZMQ with declared survival + SLO
    checks, and emit the suite as one bench record. ``--smoke`` asserts
    every check green (the CI gate); the perf gate then diffs the
    stable leaves (check_failures, lost_subscriptions/entities,
    resumed counts) against the baseline, so one newly failing
    scenario assertion — or one lost resumed row — fails the build."""
    from worldql_server_tpu.scenarios import run_scenario

    shape = "smoke" if args.quick else "full"
    names = ["flash_crowd", "battle_royale", "reconnect_storm", "game_tick"]
    reports = {}
    check_failures = 0
    for name in names:
        log(f"scenario {name} ({shape})...")
        report = run_scenario(name, shape=shape)
        reports[name] = report
        check_failures += report["checks_failed"]
        log(
            f"scenario {name}: "
            f"{'PASS' if report['checks_failed'] == 0 else 'FAIL'} "
            f"in {report['wall_s']}s "
            f"({report['checks_failed']} failed checks)"
        )

    if args.smoke:
        for name, report in reports.items():
            failed = [c["name"] for c in report["checks"] if not c["ok"]]
            assert not failed, (
                f"smoke: scenario {name} failed checks: {failed}"
            )
        log("smoke: all scenario survival + SLO checks green")

    storm = reports["reconnect_storm"]["slo"]
    return {
        "metric": "scenario_check_failures",
        "value": check_failures,
        "unit": "count",
        # the tentpole guarantee as first-class gated leaves: resumed
        # sessions lose NOTHING ("lost"-named → lower-is-better gated)
        "lost_subscriptions": max(
            0,
            storm.get("subscriptions_before", 0)
            - storm.get("subscriptions_after", 0),
        ),
        "lost_entities": (
            storm.get("entities_before", 0)
            - storm.get("entities_after", 0)
        ),
        "sessions_resumed": storm.get("resumed", 0),
        "resume_p99_ms": storm.get("resume_p99_ms"),
        "scenarios": {
            name: {
                "survived": report["survived"],
                "check_failures": report["checks_failed"],
                "wall_s": report["wall_s"],
                "slo": report["slo"],
            }
            for name, report in reports.items()
        },
        "config": 10,
    }


async def _cluster_point(n_shards: int, window_s: float,
                         max_batch: int) -> dict:
    """One cluster_scaling point: boot a router + ``n_shards`` shard
    server subprocesses, drive a paced-burst LocalMessage storm spread
    over one world per shard, and close the books with the EXACT shed
    audit: offered == admitted + shed-at-router + shed-at-shard
    (admitted = shard-arrived − shard-shed; the router's forward leg
    is lossless ZMQ, so offered − router-shed must equal arrived)."""
    import uuid as uuid_mod

    from worldql_server_tpu.cluster import ClusterRuntime, WorldMap
    from worldql_server_tpu.engine.config import Config
    from worldql_server_tpu.protocol.types import (
        Instruction as Ins, Message as Msg, Vector3 as V3,
    )
    from worldql_server_tpu.scenarios.client import ZmqPeer, free_port_block

    config = Config(
        store_url="memory://",
        http_enabled=False, ws_enabled=False,
        zmq_server_host="127.0.0.1",
        zmq_server_port=free_port_block(n_shards + 1),
        spatial_backend="cpu", tick_interval=0.02,
        max_batch=max_batch, overload="on",
        supervisor_backoff=0.005,
        cluster_shards=n_shards,
    )
    world_map = WorldMap(n_shards)

    def world_for(shard: int) -> str:
        for i in range(10_000):
            name = f"scale{i}"
            if world_map.shard_of_world(name) == shard:
                return name
        raise AssertionError("no world for shard")

    def uuid_for(shard: int) -> uuid_mod.UUID:
        while True:
            u = uuid_mod.uuid4()
            if world_map.shard_of_peer(u) == shard:
                return u

    worlds = [world_for(i) for i in range(n_shards)]
    pos = V3(5.0, 5.0, 5.0)
    runtime = ClusterRuntime(config)
    await runtime.start()
    # prime the per-core efficiency gauge's sampling window: the final
    # read then rates Δdeliveries / Δcpu-seconds over the load phases
    runtime.router.federation.deliveries_per_s_per_core()
    clients: list[ZmqPeer] = []
    try:
        async def connect(**kw) -> ZmqPeer:
            last = None
            for _ in range(100):
                try:
                    peer = await ZmqPeer.connect(
                        config.zmq_server_port, **kw
                    )
                    clients.append(peer)
                    return peer
                except Exception as exc:
                    last = exc
                    await asyncio.sleep(0.05)
            raise AssertionError(f"bench client connect failed: {last!r}")

        flooders = [
            (await connect(), worlds[i % n_shards])
            for i in range(2 * n_shards)
        ]
        for client, world in flooders:
            await client.send(Msg(
                instruction=Ins.AREA_SUBSCRIBE, world_name=world,
                position=pos,
            ))
        # cross-shard latency pair (n >= 2): receiver homed on shard
        # 0, world owned by shard 1 — every frame crosses the 1→0 ring.
        # Latency is NOT timed harness-side anymore: the shards close
        # cluster.e2e_ms / cluster.xshard_ms live at socket-write-
        # complete and the router federates them (ISSUE 15); the
        # receiver below only drains its socket.
        rx = tx = None
        xshard_received = 0
        if n_shards >= 2:
            rx = await connect(peer_uuid=uuid_for(0))
            tx = await connect(peer_uuid=uuid_for(1))
            for c in (rx, tx):
                await c.send(Msg(
                    instruction=Ins.AREA_SUBSCRIBE,
                    world_name=worlds[1], position=pos,
                ))
        await asyncio.sleep(0.3)

        stop = asyncio.Event()

        async def flood(client: ZmqPeer, world: str,
                        pace_s: float) -> int:
            sent = 0
            while not stop.is_set():
                for _ in range(16):
                    await client.send(Msg(
                        instruction=Ins.LOCAL_MESSAGE, world_name=world,
                        position=pos, parameter="load",
                    ))
                    sent += 1
                await asyncio.sleep(pace_s)
            return sent

        async def xshard_traffic() -> int:
            sent = 0
            while not stop.is_set():
                await tx.send(Msg(
                    instruction=Ins.LOCAL_MESSAGE, world_name=worlds[1],
                    position=pos, parameter=f"x:{time.monotonic_ns()}",
                ))
                sent += 1
                await asyncio.sleep(0.05)
            return sent

        async def xshard_receiver() -> None:
            nonlocal xshard_received
            while True:
                got = await rx.recv(30)
                if (
                    got.instruction == Ins.LOCAL_MESSAGE
                    and got.parameter
                    and got.parameter.startswith("x:")
                ):
                    xshard_received += 1

        async def stopper(for_s: float):
            await asyncio.sleep(for_s)
            stop.set()

        # settle helper: shard counters arrive on ~1s state pushes —
        # wait until two consecutive reads agree (queues drained,
        # books closed) before reading a phase's totals
        def shard_counters() -> list[dict]:
            return [
                dict(runtime.supervisor.shard_state(i).get(
                    "counters", {}
                ))
                for i in range(n_shards)
            ]

        async def settle() -> list[dict]:
            prev = shard_counters()
            deadline = time.perf_counter() + 20
            while time.perf_counter() < deadline:
                await asyncio.sleep(1.3)
                cur = shard_counters()
                if cur == prev and all(c for c in cur):
                    return cur
                prev = cur
            return prev

        def totals(counters: list[dict]) -> tuple[int, int]:
            arrived = sum(
                c.get("messages.local_message", 0) for c in counters
            )
            shed = sum(
                c.get("overload.shed_local", 0)
                + c.get("overload.drop_oldest", 0)
                for c in counters
            )
            return arrived, shed

        receiver = (
            asyncio.ensure_future(xshard_receiver())
            if rx is not None else None
        )
        try:
            # phase 1 — BALANCED: every flooder bursts its own shard's
            # world; this is the admitted-throughput measurement
            tasks = [flood(c, w, 0.002) for c, w in flooders]
            if tx is not None:
                tasks.append(xshard_traffic())
            tasks.append(stopper(window_s))
            results = await asyncio.gather(*tasks)
            offered_balanced = sum(results[: len(flooders)])
            offered = offered_balanced
            if tx is not None:
                offered += results[len(flooders)]
            await asyncio.sleep(1.0)  # in-flight frames land
            arrived1, shed1 = totals(await settle())
            admitted_balanced = arrived1 - shed1

            # phase 2 — HOTSPOT: the whole fleet converges on shard
            # 0's world until it REJECTs and the refusals move to the
            # router tier (the shed-accounting leg of the audit)
            stop.clear()
            hot_tasks = [
                flood(c, worlds[0], 0.001) for c, _ in flooders
            ]
            hot_tasks.append(stopper(min(window_s, 1.5)))
            hot_results = await asyncio.gather(*hot_tasks)
            offered += sum(hot_results[: len(flooders)])
            await asyncio.sleep(1.0)
        finally:
            if receiver is not None:
                receiver.cancel()
                try:
                    await receiver
                except (asyncio.CancelledError, Exception):
                    pass

        arrived, shed_shard = totals(await settle())
        snapshot = runtime.metrics.snapshot()
        router_counters = snapshot["counters"]
        shed_router = router_counters.get("cluster.router_shed_local", 0)
        admitted = arrived - shed_shard
        audit_exact = offered == admitted + shed_shard + shed_router
        # ISSUE 15: latency leaves come from the LIVE federated
        # histograms the shards closed at socket-write-complete —
        # the router's one /metrics registry, not harness clocks
        latency = snapshot["latency"]
        e2e = latency.get("cluster.e2e_ms") or {}
        xshard = latency.get("cluster.xshard_ms") or {}
        per_core = runtime.router.federation.deliveries_per_s_per_core()
        return {
            "shards": n_shards,
            "offered": offered,
            "arrived": arrived,
            "admitted": admitted,
            "admitted_per_s": round(admitted_balanced / window_s, 1),
            "shed_router": shed_router,
            "shed_shard": shed_shard,
            "audit_exact": bool(audit_exact),
            "cluster_e2e_frames": int(e2e.get("count", 0)),
            "cluster_e2e_p99_ms": (
                round(e2e["p99_ms"], 2) if e2e.get("count") else None
            ),
            "xshard_frames": int(xshard.get("count", 0)),
            "xshard_received": xshard_received,
            "xshard_p99_ms": (
                round(xshard["p99_ms"], 2) if xshard.get("count") else None
            ),
            "deliveries_per_s_per_core": per_core,
            "router_forwarded":
                router_counters.get("cluster.router_forwarded", 0),
        }
    finally:
        for client in clients:
            try:
                client.close()
            except Exception:
                pass
        await runtime.stop()


def bench_config11(args) -> dict:
    """Cluster horizontal-serving scaling curve (ISSUE 14): 1→N shard
    processes behind the router tier on THIS container, admitted
    LocalMessage throughput and cross-shard delivery p99 per point,
    with the router-tier shed accounting closed EXACTLY per point
    (offered == admitted + shed-at-router + shed-at-shard). On a
    1-core box the shards time-share the core, so the curve measures
    the serving stack's overhead and accounting honesty, not speedup —
    the near-linear claim belongs to a multi-core/multi-chip run.
    Latency leaves (``cluster_e2e_p99_ms`` / ``xshard_p99_ms``) read
    the LIVE federated histograms the shards close at socket-write-
    complete (ISSUE 15), not harness-side clocks, and
    ``deliveries_per_s_per_core`` is the ROADMAP item 4 efficiency
    gauge (Δdeliveries ÷ Δcpu-seconds across the fleet).
    ``--smoke`` asserts every point's audit is exact, the router tier
    provably shed for a drowning shard, cross-shard delivery flowed,
    and the live histograms + per-core gauge advanced. NOTE: shard
    subprocesses inherit the environment — on a
    TPU-less box with libtpu installed, JAX_PLATFORMS=cpu must be set
    (the CI bench step does)."""
    shard_counts = [1, 2] if args.quick else [1, 2, 4]
    window_s = 1.5 if args.quick else 5.0
    max_batch = 32 if args.quick else 256
    points = []
    for n in shard_counts:
        log(f"cluster point: {n} shard(s), {window_s}s window...")
        point = asyncio.run(_cluster_point(n, window_s, max_batch))
        log(
            f"  {n} shard(s): offered {point['offered']:,} -> admitted "
            f"{point['admitted']:,} ({point['admitted_per_s']:,}/s), "
            f"router shed {point['shed_router']:,}, shard shed "
            f"{point['shed_shard']:,}, audit "
            f"{'EXACT' if point['audit_exact'] else 'BROKEN'}, "
            f"e2e p99 {point['cluster_e2e_p99_ms']} ms (live hist, "
            f"{point['cluster_e2e_frames']:,} frames), xshard p99 "
            f"{point['xshard_p99_ms']} ms, "
            f"{point['deliveries_per_s_per_core']:,}/s/core"
        )
        points.append(point)

    audit_failures = sum(1 for p in points if not p["audit_exact"])
    if args.smoke:
        assert audit_failures == 0, (
            f"smoke: shed accounting broke: {points}"
        )
        assert all(p["shed_router"] > 0 for p in points), (
            "smoke: the router tier never shed for a drowning shard"
        )
        assert all(p["admitted"] > 0 for p in points)
        multi = [p for p in points if p["shards"] >= 2]
        assert multi and all(p["xshard_frames"] > 0 for p in multi), (
            "smoke: cross-shard delivery never flowed"
        )
        # ISSUE 15: the latency leaves must come from the LIVE
        # federated histograms — frames closed on the shards, merged
        # at the router — and the per-core gauge must have rated
        assert all(p["cluster_e2e_frames"] > 0 for p in points), (
            "smoke: no shard ever closed the router-ingress frame "
            "clock (cluster.e2e_ms empty in the federated registry)"
        )
        assert all(
            p["xshard_p99_ms"] is not None for p in multi
        ), "smoke: live cluster.xshard_ms histogram never advanced"
        assert any(
            p["deliveries_per_s_per_core"] > 0 for p in points
        ), "smoke: deliveries_per_s_per_core never rated"
        log("smoke: cluster audit exact at every point, router-tier "
            "shed fired, cross-shard delivery flowed, live e2e/xshard "
            "histograms + per-core gauge advanced")
    return {
        "metric": "cluster_audit_failures",
        "value": audit_failures,
        "unit": "count",
        "audit_failures": audit_failures,
        "max_admitted_per_s": max(p["admitted_per_s"] for p in points),
        "deliveries_per_s_per_core": max(
            p["deliveries_per_s_per_core"] for p in points
        ),
        "points": points,
        "config": 11,
    }


def _kind_cols(rng, m: int, kind_id: int):
    """→ (kinds i8 [m], params f64 [m, 6]) staged columns for one kind,
    parameters drawn exactly as the wire parsers clamp them (cube 16,
    stencil 3, ray steps 64)."""
    from worldql_server_tpu.queries.kinds import (
        KIND_CONE, KIND_DENSITY, KIND_KNN, KIND_RAYCAST, PARAM_LANES,
        RAY_ALL_HITS, RAY_FIRST_HIT,
    )

    kinds = np.full(m, kind_id, np.int8)
    params = np.zeros((m, PARAM_LANES), np.float64)
    if kind_id in (KIND_CONE, KIND_RAYCAST):
        d = rng.normal(size=(m, 3))
        d /= np.linalg.norm(d, axis=1, keepdims=True)
        params[:, 0:3] = d
    if kind_id == KIND_CONE:
        params[:, 3] = np.cos(np.radians(rng.uniform(20.0, 80.0, m)))
        params[:, 4] = rng.uniform(12.0, 48.0, m)
    elif kind_id == KIND_RAYCAST:
        params[:, 3] = rng.uniform(16.0, 64.0, m)          # max_t
        params[:, 4] = np.where(
            rng.random(m) < 0.5, RAY_FIRST_HIT, RAY_ALL_HITS
        )
    elif kind_id == KIND_KNN:
        params[:, 0] = rng.integers(1, 12, m).astype(np.float64)
        params[:, 1] = rng.uniform(12.0, 48.0, m)          # max_range
    elif kind_id == KIND_DENSITY:
        params[:, 0] = rng.integers(1, 3, m).astype(np.float64)
        params[:, 1] = 8.0                                 # top_n
    return kinds, params


def _query_results_match(got, want) -> bool:
    """Lane-for-lane result equality across the two collect shapes:
    KindResult triples for library kinds, peer sets for radius rows
    (radius peer ORDER is an index-layout artifact on both paths)."""
    from worldql_server_tpu.queries.results import KindResult

    if isinstance(got, KindResult) or isinstance(want, KindResult):
        return (
            isinstance(got, KindResult)
            and isinstance(want, KindResult)
            and got.kind == want.kind
            and list(got.peers) == list(want.peers)
            and got.extra == want.extra
        )
    return set(got) == set(want)


def bench_config12(args) -> dict:
    """Spatial query library (ISSUE 17): per-kind device throughput of
    the staged kind pipeline (cone / raycast / filtered-kNN / density
    expanded into probe rows riding the radius hash-probe), the
    mixed-kind batch's p50/p99 next to a pure-radius batch of the SAME
    size (the cost of carrying the library), and CPU-oracle parity
    sampled across every kind in the mixed batch. ``--smoke`` asserts
    the kind-expansion path actually fired, parity held on every
    sampled lane, and the timed window re-traced nothing after the
    boot tier walk (precompile.py's kind leg). The gate leaves are the
    parity/retrace COUNTS — the rates are 1-core-bound and pruned from
    the checked-in baseline."""
    from worldql_server_tpu.queries.kinds import (
        KIND_CONE, KIND_DENSITY, KIND_KNN, KIND_RADIUS, KIND_RAYCAST,
        PARAM_LANES,
    )
    from worldql_server_tpu.spatial.backend import LocalQuery
    from worldql_server_tpu.spatial.cpu_backend import CpuSpatialBackend
    from worldql_server_tpu.spatial.precompile import precompile_tiers
    from worldql_server_tpu.spatial.tpu_backend import TpuSpatialBackend
    from worldql_server_tpu.utils.retrace import GUARD
    from worldql_server_tpu.protocol.types import Replication, Vector3

    n_worlds = 4
    m = min(args.queries, 512 if args.quick else 4096)
    reps = 5 if args.quick else 11
    rng = np.random.default_rng(17)
    tpu = TpuSpatialBackend(cube_size=16)
    peers, sub_positions, sub_world_ids = build_index(
        tpu, rng, args.subs, n_worlds
    )
    tpu.flush()
    tpu.wait_compaction()

    # staged columns, interned exactly as engine/staging.py encodes
    senders = rng.integers(0, len(peers), m)
    wid_col = np.fromiter(
        (tpu._world_ids.get(f"world_{w}", -1)
         for w in sub_world_ids[senders]),
        np.int32, count=m,
    )
    sid_col = np.fromiter(
        (tpu._peer_ids.get(peers[s], -1) for s in senders),
        np.int32, count=m,
    )
    pos_col = np.ascontiguousarray(sub_positions[senders], np.float64)
    repl_col = np.full(m, int(Replication.EXCEPT_SELF), np.int8)

    kind_ids = {
        "cone": KIND_CONE, "raycast": KIND_RAYCAST,
        "knn": KIND_KNN, "density": KIND_DENSITY,
    }
    pure = {
        name: _kind_cols(rng, m, kid) for name, kid in kind_ids.items()
    }
    # mixed batch: every kind plus a radius share, interleaved
    mixed_kinds = np.zeros(m, np.int8)
    mixed_params = np.zeros((m, PARAM_LANES), np.float64)
    lanes = [KIND_RADIUS, *kind_ids.values()]
    for j, kid in enumerate(lanes):
        sel = np.flatnonzero(np.arange(m) % len(lanes) == j)
        mixed_kinds[sel] = kid
        if kid != KIND_RADIUS:
            _, p = _kind_cols(rng, sel.size, kid)
            mixed_params[sel] = p

    def run_once(kinds, params):
        t0 = time.perf_counter()
        out = tpu.collect_local_batch(
            tpu.dispatch_staged_batch(
                wid_col, pos_col, sid_col, repl_col, kinds, params
            )
        )
        return out, (time.perf_counter() - t0) * 1e3

    # discovery pass: kind expansion turns m queries into (many more)
    # probe rows, and THOSE are the tiers the radius pipeline runs at —
    # size the boot walk to the largest probe batch, not to m
    probe_rows = m
    for kinds, params in (*pure.values(), (mixed_kinds, mixed_params)):
        handle = tpu.dispatch_staged_batch(
            wid_col, pos_col, sid_col, repl_col, kinds, params
        )
        probe_rows = max(
            probe_rows, int(handle[1][1].probe_owner.shape[0])
        )
        tpu.collect_local_batch(handle)
    pc_stats = precompile_tiers(
        tpu, max_batch=probe_rows, t_tiers=2, max_compiles=128
    )
    log(f"tier precompile (probe tier {probe_rows}): {pc_stats}")
    for kinds, params in (*pure.values(), (mixed_kinds, mixed_params),
                          (None, None)):
        run_once(kinds, params)        # warm every shape once
        run_once(kinds, params)
    guard_before = GUARD.snapshot()

    per_kind = {}
    for name, (kinds, params) in pure.items():
        walls = [run_once(kinds, params)[1] for _ in range(reps)]
        wall = float(np.median(walls))
        per_kind[name] = {
            "device_queries_per_s": round(m / (wall / 1e3)),
            "wall_ms": round(wall, 3),
        }
        log(f"{name}: {wall:.2f} ms/batch "
            f"({per_kind[name]['device_queries_per_s']:,}/s)")
    mixed_out, _ = run_once(mixed_kinds, mixed_params)
    mixed_walls = np.array(
        [run_once(mixed_kinds, mixed_params)[1] for _ in range(reps)]
    )
    radius_walls = np.array(
        [run_once(None, None)[1] for _ in range(reps)]
    )
    retrace_delta = GUARD.delta(guard_before)
    retraces = sum(retrace_delta.values())
    log(f"mixed: p50 {pctl(mixed_walls, 50):.2f} p99 "
        f"{pctl(mixed_walls, 99):.2f} ms  radius: p50 "
        f"{pctl(radius_walls, 50):.2f} p99 {pctl(radius_walls, 99):.2f} "
        f"ms  retraces {retraces} {retrace_delta or ''}")

    # CPU-oracle parity, stratified across every kind in the mixed
    # batch (the randomized property suite in tests/test_queries.py is
    # the exhaustive version; this pins the BENCH shapes)
    cpu = CpuSpatialBackend(cube_size=16)
    build_index(cpu, np.random.default_rng(17), args.subs, n_worlds)
    parity = {name: True for name in ("radius", *kind_ids)}
    by_id = {0: "radius", **{v: k for k, v in kind_ids.items()}}
    sample = []
    for kid in (KIND_RADIUS, *kind_ids.values()):
        sample.extend(np.flatnonzero(mixed_kinds == kid)[:12])
    for i in sample:
        want = cpu.match_local_batch([
            LocalQuery(
                f"world_{sub_world_ids[senders[i]]}",
                Vector3(*pos_col[i]),
                peers[senders[i]],
                Replication.EXCEPT_SELF,
                kind=int(mixed_kinds[i]),
                params=tuple(mixed_params[i]),
            )
        ])[0]
        if not _query_results_match(mixed_out[i], want):
            parity[by_id[int(mixed_kinds[i])]] = False
            log(f"parity diverged: query {i} kind {mixed_kinds[i]}: "
                f"{mixed_out[i]!r} vs {want!r}")
    parity_failures = sum(1 for ok in parity.values() if not ok)
    log(f"parity: {parity_failures} failure(s) across "
        f"{len(sample)} sampled lanes {parity}")

    if args.smoke:
        assert tpu.kind_expansions > 0, \
            "smoke: the kind-expansion path never fired"
        assert parity_failures == 0, \
            f"smoke: kind results diverged from the CPU oracle: {parity}"
        assert retraces == 0, (
            "smoke: the timed window re-traced despite the kind tier "
            f"walk: {retrace_delta}"
        )
        log(f"smoke: {tpu.kind_expansions} kind expansions, parity "
            f"green on every kind, retraces {retraces}")
    return {
        "metric": "query_parity_failures",
        "value": parity_failures,
        "unit": "count",
        "parity_failures": parity_failures,
        "parity": {k: int(v) for k, v in parity.items()},
        "retraces": retraces,
        "kind_expansions": int(tpu.kind_expansions),
        "kinds": per_kind,
        "mixed_p50_ms": round(pctl(mixed_walls, 50), 3),
        "mixed_p99_ms": round(pctl(mixed_walls, 99), 3),
        "radius_p50_ms": round(pctl(radius_walls, 50), 3),
        "radius_p99_ms": round(pctl(radius_walls, 99), 3),
        "mixed_over_radius": round(
            float(np.median(mixed_walls) / np.median(radius_walls)), 2
        ),
        "config": 12,
    }


# --------------------------------------------------------------------


def bench_config13(args) -> dict:
    """Interest-managed fan-out (ISSUE 18): the game_tick shape — a
    mostly-static population with a small moving minority — run twice
    at IDENTICAL shapes over real ZMQ sockets, ``--interest off`` then
    ``on``. The off leg re-broadcasts every visible entity every tick;
    the on leg ships per-recipient deltas on the stamped epoch:seq
    wire. Reported: delivered bytes/tick and bytes/recipient/s for
    both legs, the reduction ratio, the on-leg ``frame.delta_ratio``,
    and the eventual-state parity verdict — one observer's socket is
    replayed through the :class:`ReplayClient` oracle and compared
    against the server's own per-peer ledger after quiescing.

    ``--smoke`` asserts parity is green (zero refused deltas, zero
    gaps, snapshot == ledger), deltas actually flowed, and the
    reduction clears 2x; the record run must clear the ISSUE's 5x."""
    import struct
    import uuid as _uuid

    from tests.client_util import ZmqClient, free_port
    from worldql_server_tpu.engine.config import Config
    from worldql_server_tpu.engine.server import WorldQLServer
    from worldql_server_tpu.interest import ReplayClient
    from worldql_server_tpu.protocol import Instruction, Message
    from worldql_server_tpu.protocol.types import Entity, Vector3
    from worldql_server_tpu.utils.retrace import GUARD

    quick = args.quick
    n_watchers = 4 if quick else 8
    ents_per_watcher = 4 if quick else 12
    n_movers = 2 if quick else 8
    measure_s = 2.0 if quick else 6.0
    tick = 0.05
    rng = np.random.default_rng(1813)

    async def variant(interest: str) -> dict:
        config = Config()
        config.store_url = "memory://"
        config.http_enabled = False
        config.ws_enabled = False
        config.zmq_server_port = free_port()
        config.zmq_server_host = "127.0.0.1"
        config.spatial_backend = "tpu"
        config.tick_interval = tick
        config.entity_sim = True
        config.entity_k = 8
        config.interest = interest
        server = WorldQLServer(config)
        await server.start()
        try:
            clients = [
                await ZmqClient.connect(config.zmq_server_port)
                for _ in range(n_watchers)
            ]
            observer = clients[-1]
            # static majority: a co-located cluster inside one cube
            for c in clients:
                await c.send(Message(
                    instruction=Instruction.LOCAL_MESSAGE,
                    world_name="bench",
                    entities=[Entity(
                        uuid=_uuid.uuid4(),
                        position=Vector3(*rng.uniform(4, 12, 3)),
                        world_name="bench",
                    ) for _ in range(ents_per_watcher)],
                ))
            # moving minority: velocity-integrated by the device tick,
            # no further client sends needed to generate churn
            movers = [_uuid.uuid4() for _ in range(n_movers)]
            await clients[0].send(Message(
                instruction=Instruction.LOCAL_MESSAGE,
                world_name="bench",
                entities=[Entity(
                    uuid=m, position=Vector3(*rng.uniform(6, 10, 3)),
                    world_name="bench",
                    flex=struct.pack("<3f", 1.0, 0.5, 0.0),
                ) for m in movers],
            ))

            oracle = ReplayClient() if interest == "on" else None
            observed = [0]

            async def drain(client, sink=None):
                try:
                    while True:
                        m = await client.recv(timeout=0.5)
                        if sink is not None \
                                and m.instruction == Instruction.LOCAL_MESSAGE:
                            sink.apply(m)
                            observed[0] += 1
                except (asyncio.TimeoutError, asyncio.CancelledError):
                    pass

            drains = [
                asyncio.ensure_future(drain(c, oracle if c is observer
                                            else None))
                for c in clients
            ]
            # warmup: past the jit walls, ticking at rate (config 8's
            # bounded stability loop)
            plane_ = server.entity_plane
            expect = max(3, int(0.5 / tick) - 3)
            prev_ticks, prev_compiles, stable = -1, -1, 0
            for _ in range(60):
                await asyncio.sleep(0.5)
                ticks_now = plane_.applied_ticks
                compiles = sum(GUARD.counts().values())
                if (prev_ticks >= 0
                        and ticks_now - prev_ticks >= expect
                        and compiles == prev_compiles):
                    stable += 1
                    if stable >= 2:
                        break
                else:
                    stable = 0
                prev_ticks, prev_compiles = ticks_now, compiles

            bytes0 = server.peer_map.bytes_delivered
            ticks0 = plane_.applied_ticks
            await asyncio.sleep(measure_s)
            bytes_win = server.peer_map.bytes_delivered - bytes0
            ticks_win = max(plane_.applied_ticks - ticks0, 1)
            # sample the per-tick delta ratio INSIDE the loaded window
            # — after quiescing the last tick carries no frames and
            # the gauge honestly reads 0
            ratio_at_load = (
                server.interest.stats()["delta_ratio"]
                if server.interest is not None else None
            )

            out = {
                "delivered_bytes_per_tick": round(
                    bytes_win / ticks_win, 1
                ),
                "bytes_per_recipient_per_s": round(
                    bytes_win / n_watchers / measure_s, 1
                ),
                "measured_ticks": ticks_win,
                "frames_observed": 0,
            }
            parity = None
            if interest == "on":
                # quiesce: zero the movers' velocity, let the last
                # deltas land, then the oracle must equal the server's
                # own ledger for the observer — eventual-state parity
                await clients[0].send(Message(
                    instruction=Instruction.LOCAL_MESSAGE,
                    world_name="bench",
                    entities=[Entity(
                        uuid=m,
                        position=Vector3(*rng.uniform(6, 10, 3)),
                        world_name="bench",
                        flex=struct.pack("<3f", 0.0, 0.0, 0.0),
                    ) for m in movers],
                ))
                settled = observed[0] - 1
                for _ in range(40):
                    await asyncio.sleep(0.25)
                    if observed[0] == settled:
                        break
                    settled = observed[0]
                mgr = server.interest
                ledger = {}
                for key, (_wid, pos_b) in mgr.ledger(
                    observer.uuid,
                    server.entity_plane._peer_ids.get(observer.uuid, -1),
                ).items():
                    x, y, z = np.frombuffer(pos_b, np.float32)
                    ledger[_uuid.UUID(bytes=key)] = (
                        float(x), float(y), float(z)
                    )
                got = oracle.snapshot().get("bench", {})
                s = oracle.stats()
                parity = {
                    "entities_match": int(got == ledger),
                    "entities": len(got),
                    "deltas_refused": s["deltas_refused"],
                    "gaps_seen": s["gaps_seen"],
                    "deltas_applied": s["deltas_applied"],
                    "fulls_applied": s["fulls_applied"],
                }
                ist = mgr.stats()
                out["frame_delta_ratio"] = ratio_at_load
                out["resyncs"] = ist["resyncs"]
                out["templates_reused"] = ist["templates_reused"]
                out["bytes_shed"] = ist["bytes_shed"]
            for d in drains:
                d.cancel()
            await asyncio.gather(*drains, return_exceptions=True)
            out["frames_observed"] = observed[0] if oracle else None
            for c in clients:
                await c.close()
            return out, parity
        finally:
            await server.stop()

    off, _ = asyncio.run(variant("off"))
    on, parity = asyncio.run(variant("on"))
    reduction = (
        off["delivered_bytes_per_tick"]
        / max(on["delivered_bytes_per_tick"], 1e-9)
    )

    if args.smoke:
        assert parity is not None and parity["entities_match"], (
            f"smoke: replay oracle diverged from the server ledger: "
            f"{parity}"
        )
        assert parity["deltas_refused"] == 0 and parity["gaps_seen"] == 0, (
            f"smoke: sequencing broke on a clean stream: {parity}"
        )
        assert parity["deltas_applied"] > 0, (
            "smoke: movement never rode a delta frame"
        )
        floor = 2.0
        assert reduction >= floor, (
            f"smoke: interest reduced bytes/tick only {reduction:.2f}x "
            f"(off {off['delivered_bytes_per_tick']} -> on "
            f"{on['delivered_bytes_per_tick']}), need >= {floor}x"
        )
        log(f"smoke: {reduction:.1f}x byte reduction, parity green "
            f"({parity['deltas_applied']} deltas, "
            f"{parity['fulls_applied']} fulls, 0 refused)")
    else:
        assert reduction >= 5.0, (
            f"ISSUE 18 acceptance: need >= 5x fewer bytes/tick with "
            f"interest on, got {reduction:.2f}x"
        )

    log(f"interest: off {off['delivered_bytes_per_tick']:,.0f} B/tick "
        f"-> on {on['delivered_bytes_per_tick']:,.0f} B/tick "
        f"({reduction:.1f}x), delta_ratio "
        f"{on.get('frame_delta_ratio')}, parity {parity}")
    return {
        "metric": "interest_bytes_reduction_x",
        "value": round(reduction, 2),
        "unit": "x",
        # named like vs_baseline so the perf gate reads shrinkage of
        # this leaf as the good direction
        "vs_baseline": round(reduction, 2),
        "interest": {
            "off": off,
            "on": on,
            "parity": parity,
            "watchers": n_watchers,
            "entities": n_watchers * ents_per_watcher + n_movers,
            "movers": n_movers,
        },
        "config": 13,
    }


async def _reshard_run(window_s: float) -> dict:
    """One live-resharding run: boot a 2-shard cluster, home a hot
    world on shard 0 with a cross-shard subscriber, keep LocalMessage
    + record traffic flowing, migrate the world to shard 1 mid-stream,
    and close the books: per-state wall times (harness-polled state
    transitions), the longest delivery gap the subscriber saw across
    the freeze window, parked/replayed/shed counts from the transfer
    buffer, and the zero-loss audit (every record offered before,
    during and after the migration reads back from the new owner)."""
    import uuid as uuid_mod

    from worldql_server_tpu.cluster import ClusterRuntime, WorldMap
    from worldql_server_tpu.engine.config import Config
    from worldql_server_tpu.protocol.types import (
        Instruction as Ins, Message as Msg, Record as Rec, Vector3 as V3,
    )
    from worldql_server_tpu.scenarios.client import ZmqPeer, free_port_block

    config = Config(
        store_url="memory://",
        http_enabled=False, ws_enabled=False,
        zmq_server_host="127.0.0.1",
        zmq_server_port=free_port_block(3),
        spatial_backend="cpu", tick_interval=0.02,
        overload="on",
        supervisor_backoff=0.005,
        cluster_shards=2,
    )
    world_map = WorldMap(2)
    world = next(
        f"hot{i}" for i in range(10_000)
        if world_map.shard_of_world(f"hot{i}") == 0
    )
    pos = V3(5.0, 5.0, 5.0)
    runtime = ClusterRuntime(config)
    await runtime.start()
    clients: list[ZmqPeer] = []
    try:
        async def connect(**kw) -> ZmqPeer:
            last = None
            for _ in range(100):
                try:
                    peer = await ZmqPeer.connect(
                        config.zmq_server_port, **kw
                    )
                    clients.append(peer)
                    return peer
                except Exception as exc:
                    last = exc
                    await asyncio.sleep(0.05)
            raise AssertionError(f"bench client connect failed: {last!r}")

        router = runtime.router

        def uuid_for(shard: int) -> uuid_mod.UUID:
            while True:
                u = uuid_mod.uuid4()
                if world_map.shard_of_peer(u) == shard:
                    return u

        # subscriber homed on the DESTINATION shard: its deliveries
        # ride the ring before the flip and stay local after it
        rx = await connect(peer_uuid=uuid_for(1))
        tx = await connect(peer_uuid=uuid_for(0))
        await rx.send(Msg(
            instruction=Ins.AREA_SUBSCRIBE, world_name=world,
            position=pos,
        ))
        await asyncio.sleep(0.3)

        want: set = set()

        async def put_record(tag: str) -> None:
            rec = uuid_mod.uuid4()
            await tx.send(Msg(
                instruction=Ins.RECORD_CREATE, world_name=world,
                records=[Rec(uuid=rec, position=pos, world_name=world,
                             data=tag)],
            ))
            want.add(rec)

        for i in range(50):
            await put_record(f"pre{i}")

        stop = asyncio.Event()
        offered_locals = 0
        arrivals: list[float] = []

        async def traffic() -> None:
            nonlocal offered_locals
            n = 0
            while not stop.is_set():
                await tx.send(Msg(
                    instruction=Ins.LOCAL_MESSAGE, world_name=world,
                    position=pos, parameter="load",
                ))
                offered_locals += 1
                n += 1
                if n % 4 == 0:
                    await put_record(f"mid{n}")
                # paced fast relative to the ~10ms migration so the
                # freeze window reliably parks frames (replayed > 0
                # is a smoke gate, not a coincidence)
                await asyncio.sleep(0.002)

        async def receiver() -> None:
            while True:
                got = await rx.recv(30)
                if got.instruction == Ins.LOCAL_MESSAGE:
                    arrivals.append(time.perf_counter())

        traffic_task = asyncio.ensure_future(traffic())
        receiver_task = asyncio.ensure_future(receiver())
        state_at: dict[str, float] = {}
        try:
            await asyncio.sleep(window_s)

            t_start = time.perf_counter()
            xfer = router.start_reshard(world, 1, reason="bench")
            assert xfer is not None, "reshard refused"
            while router.migration.state not in ("done", "aborted"):
                state_at.setdefault(
                    router.migration.state, time.perf_counter()
                )
                await asyncio.sleep(0.001)
            state_at.setdefault(
                router.migration.state, time.perf_counter()
            )
            migration_ms = (time.perf_counter() - t_start) * 1e3

            await asyncio.sleep(window_s)  # post-flip delivery window
            stop.set()
            await traffic_task
        finally:
            stop.set()
            for task in (traffic_task, receiver_task):
                task.cancel()
                try:
                    await task
                except (asyncio.CancelledError, Exception):
                    pass

        mig = router.migration
        # zero-loss audit: every offered record reads back through the
        # router from the NEW owner (retry: creates are async)
        seen: set = set()
        deadline = time.perf_counter() + 30
        while time.perf_counter() < deadline and not want <= seen:
            await tx.send(Msg(
                instruction=Ins.RECORD_READ, world_name=world,
                position=pos,
            ))
            try:
                reply = await tx.recv_until(Ins.RECORD_REPLY, 5)
            except asyncio.TimeoutError:
                continue
            seen |= {r.uuid for r in reply.records}
        lost = len(want - seen)

        # per-state wall times from the first-seen transition stamps
        order = [s for s in (
            "freeze", "streaming", "importing", "flipping",
            "replaying", "tombstoning", "done", "aborted",
        ) if s in state_at]
        state_ms = {
            a: round((state_at[b] - state_at[a]) * 1e3, 2)
            for a, b in zip(order, order[1:])
        }
        # the longest gap between consecutive subscriber deliveries
        # that overlaps the migration — the freeze-window pause
        pause_ms = 0.0
        for a, b in zip(arrivals, arrivals[1:]):
            if b >= t_start and a <= t_start + migration_ms / 1e3:
                pause_ms = max(pause_ms, (b - a) * 1e3)
        post_flip = sum(1 for t in arrivals if t > state_at[order[-1]])

        return {
            "state": mig.state,
            "lost_records": lost,
            "records_offered": len(want),
            "buffer": mig.buffer.stats(),
            "replayed": mig.replayed,
            "rerouted": runtime.metrics.snapshot()["counters"].get(
                "cluster.router_reroutes", 0
            ),
            "epoch": router.world_map.epoch,
            "owner": router.world_map.shard_of_world(world),
            "offered_locals": offered_locals,
            "delivered_locals": len(arrivals),
            "delivered_post_flip": post_flip,
            "migration_ms": round(migration_ms, 2),
            "state_ms": state_ms,
            "delivery_pause_ms": round(pause_ms, 2),
        }
    finally:
        for client in clients:
            try:
                client.close()
            except Exception:
                pass
        await runtime.stop()


def bench_config14(args) -> dict:
    """Live resharding under load (ISSUE 19): migrate a hot world
    between two real shard subprocesses while LocalMessage + record
    traffic flows, and report the migration wall time split by
    protocol state, the longest delivery gap a cross-shard subscriber
    saw across the freeze window, the transfer-buffer park/replay/shed
    books, and the zero-loss audit. ``--smoke`` asserts the migration
    COMPLETED, no record was lost, the freeze window actually parked
    and replayed traffic, nothing was shed, and delivery resumed on
    the new owner after the flip. The gate leaves are the counts
    (``lost_records`` / ``shed`` / ``aborted``); the wall times are
    1-core-box noise and pruned from the checked-in baseline."""
    window_s = 0.4 if args.quick else 1.5
    log(f"resharding: 2 shards, {window_s}s load windows...")
    run = asyncio.run(_reshard_run(window_s))
    log(
        f"  migration {run['state']} in {run['migration_ms']} ms "
        f"(states {run['state_ms']}), parked "
        f"{run['buffer']['parked_frames']} -> replayed "
        f"{run['replayed']}, shed {run['buffer']['shed']}, rerouted "
        f"{run['rerouted']}, pause {run['delivery_pause_ms']} ms, "
        f"records {run['records_offered'] - run['lost_records']}/"
        f"{run['records_offered']}, epoch {run['epoch']}, owner "
        f"shard {run['owner']}"
    )
    aborted = 1 if run["state"] != "done" else 0
    if args.smoke:
        assert aborted == 0, f"smoke: migration did not complete: {run}"
        assert run["lost_records"] == 0, (
            f"smoke: records lost across the migration: {run}"
        )
        assert run["replayed"] > 0, (
            "smoke: the freeze window never parked+replayed traffic — "
            "the migration raced no load"
        )
        assert run["buffer"]["shed"] == 0, (
            f"smoke: transfer buffer shed under bench load: {run}"
        )
        assert run["owner"] == 1 and run["epoch"] >= 1, (
            f"smoke: placement never flipped: {run}"
        )
        assert run["delivered_post_flip"] > 0, (
            "smoke: no delivery observed on the new owner post-flip"
        )
        log("smoke: migration done, zero loss, freeze window "
            "parked+replayed, nothing shed, delivery resumed post-flip")
    return {
        "metric": "reshard_lost_records",
        "value": run["lost_records"],
        "unit": "count",
        "lost_records": run["lost_records"],
        "reshard_aborted": aborted,
        "reshard": run,
        "config": 14,
    }


def bench_config15(args) -> dict:
    """SLO compliance under the game-tick shape (ISSUE 20): boot the
    REAL server with the burn-rate engine ON — the DEFAULT objective
    set (frame e2e p99, ring drops, interest resyncs, …) at
    bench-tight windows so a few seconds of load fills both burn
    windows the way a minute fills production's — and drive the
    config-13 game_tick shape over real ZMQ: a static co-located
    majority plus velocity-integrated movers with interest-managed
    fan-out. Reported per objective: compliance (fraction of
    evaluations spent at OK, as a percentage so the perf gate's
    --min-abs floor can't mute it) and the worst burn rate either
    window saw. ``--smoke`` asserts the supervised slo-eval task
    judged every objective, the frame clock closed real frames (the
    e2e objective must not be grading an empty series), and nothing
    entered BURNING at the quick shape — then the compliance_pct
    leaves diff against the baseline (higher is better): a latency
    regression that starts torching the error budget fails CI even
    while every raw *_per_s leaf holds."""
    import struct
    import tempfile
    import uuid as _uuid

    from tests.client_util import ZmqClient, free_port
    from worldql_server_tpu.engine.config import Config
    from worldql_server_tpu.engine.server import WorldQLServer
    from worldql_server_tpu.observability.slo import (
        BURNING, DEFAULT_OBJECTIVES, OK,
    )
    from worldql_server_tpu.protocol import Instruction, Message
    from worldql_server_tpu.protocol.types import Entity, Vector3
    from worldql_server_tpu.utils.retrace import GUARD

    quick = args.quick
    n_watchers = 4 if quick else 8
    ents_per_watcher = 4 if quick else 12
    n_movers = 2 if quick else 8
    measure_s = 3.0 if quick else 8.0
    tick = 0.05
    fast_s, slow_s, eval_s = 1.0, 3.0, 0.2
    rng = np.random.default_rng(2013)

    # the DEFAULT objective set at bench-tight windows — except the
    # frame-clock target, which is re-quoted at the tick budget: the
    # production 5 ms p99 belongs to hardware (ROADMAP item 1), while
    # this 1-core box time-shares the device tick with every client
    # and honestly lands most frames past 5 ms. Judging against the
    # 50 ms tick budget keeps the baseline at 100% compliance, so a
    # latency regression (frames creeping past a tick) flags instead
    # of drowning in an always-burning leaf. 50 is a bucket edge, so
    # the burn accounting stays exact.
    objectives = []
    for obj in DEFAULT_OBJECTIVES:
        obj = dict(obj, fast_s=fast_s, slow_s=slow_s)
        if obj["name"] == "frame_e2e_p99":
            obj["target_ms"] = TICK_BUDGET_MS
        objectives.append(obj)
    slo_spec = {"eval_interval_s": eval_s, "objectives": objectives}

    async def run() -> tuple[dict, dict, int]:
        with tempfile.NamedTemporaryFile(
            "w", suffix=".json", delete=False
        ) as fh:
            json.dump(slo_spec, fh)
            slo_file = fh.name
        config = Config()
        config.store_url = "memory://"
        config.http_enabled = False
        config.ws_enabled = False
        config.zmq_server_port = free_port()
        config.zmq_server_host = "127.0.0.1"
        config.spatial_backend = "tpu"
        config.tick_interval = tick
        config.entity_sim = True
        config.entity_k = 8
        config.interest = "on"
        config.slo_file = slo_file
        server = WorldQLServer(config)
        await server.start()
        try:
            clients = [
                await ZmqClient.connect(config.zmq_server_port)
                for _ in range(n_watchers)
            ]
            for c in clients:
                await c.send(Message(
                    instruction=Instruction.LOCAL_MESSAGE,
                    world_name="bench",
                    entities=[Entity(
                        uuid=_uuid.uuid4(),
                        position=Vector3(*rng.uniform(4, 12, 3)),
                        world_name="bench",
                    ) for _ in range(ents_per_watcher)],
                ))
            # moving minority: velocity-integrated by the device tick
            await clients[0].send(Message(
                instruction=Instruction.LOCAL_MESSAGE,
                world_name="bench",
                entities=[Entity(
                    uuid=_uuid.uuid4(),
                    position=Vector3(*rng.uniform(6, 10, 3)),
                    world_name="bench",
                    flex=struct.pack("<3f", 1.0, 0.5, 0.0),
                ) for _ in range(n_movers)],
            ))

            async def drain(client):
                try:
                    while True:
                        await client.recv(timeout=0.5)
                except (asyncio.TimeoutError, asyncio.CancelledError):
                    pass

            drains = [asyncio.ensure_future(drain(c)) for c in clients]
            # warmup: past the jit walls, ticking at rate (config 8's
            # bounded stability loop)
            plane_ = server.entity_plane
            expect = max(3, int(0.5 / tick) - 3)
            prev_ticks, prev_compiles, stable = -1, -1, 0
            for _ in range(60):
                await asyncio.sleep(0.5)
                ticks_now = plane_.applied_ticks
                compiles = sum(GUARD.counts().values())
                if (prev_ticks >= 0
                        and ticks_now - prev_ticks >= expect
                        and compiles == prev_compiles):
                    stable += 1
                    if stable >= 2:
                        break
                else:
                    stable = 0
                prev_ticks, prev_compiles = ticks_now, compiles
            # age the warmup (jit-wall latencies included) out of the
            # slow burn window before judging — the engine's ring only
            # looks back slow_s, so after this sleep every window the
            # measured evaluations see is pure steady-state load
            await asyncio.sleep(slow_s + 2 * eval_s)
            t0 = time.monotonic()
            await asyncio.sleep(measure_s)
            status = server.slo.status()
            frame_hist = server.metrics.export_histograms(
                ("frame.e2e_ms",)
            ).get("frame.e2e_ms")
            frames = frame_hist["total"] if frame_hist else 0
            trajs = {
                name: [
                    e for e in server.slo.trajectory(name)
                    if e["t"] >= t0
                ]
                for name in status["objectives"]
            }
            for d in drains:
                d.cancel()
            await asyncio.gather(*drains, return_exceptions=True)
            for c in clients:
                await c.close()
            return status, trajs, frames
        finally:
            await server.stop()
            os.unlink(slo_file)

    log(f"slo_compliance: game_tick shape, {n_watchers} watchers, "
        f"{n_movers} movers, windows {fast_s}/{slow_s}s at "
        f"{eval_s}s evals, {measure_s}s judged window...")
    status, trajs, frames = asyncio.run(run())

    objectives = {}
    breaches = 0
    worst_level = 0
    for name, entries in trajs.items():
        ok = sum(1 for e in entries if e["level"] == OK)
        burning = sum(1 for e in entries if e["level"] == BURNING)
        breaches += burning
        worst_level = max(
            worst_level, max((e["level"] for e in entries), default=0)
        )
        objectives[name] = {
            "compliance_pct": round(
                100.0 * ok / max(len(entries), 1), 1
            ),
            "worst_burn_fast": max(
                (e["burn_fast"] for e in entries), default=0.0
            ),
            "worst_burn_slow": max(
                (e["burn_slow"] for e in entries), default=0.0
            ),
            "evals": len(entries),
            "final_state": status["objectives"][name]["state"],
        }
        log(f"  {name}: {objectives[name]['compliance_pct']}% ok "
            f"({len(entries)} evals, worst burn "
            f"{objectives[name]['worst_burn_slow']}x slow), final "
            f"{objectives[name]['final_state']}")

    if args.smoke:
        assert set(objectives) == {o["name"] for o in DEFAULT_OBJECTIVES}, (
            f"smoke: objective set drifted: {sorted(objectives)}"
        )
        assert all(o["evals"] >= 5 for o in objectives.values()), (
            f"smoke: the slo-eval task barely ran inside the judged "
            f"window: {objectives}"
        )
        assert frames > 0, (
            "smoke: the frame clock never closed a frame — "
            "frame_e2e_p99 judged an empty series (burn 0 would be a "
            "dead green light, not compliance)"
        )
        assert breaches == 0, (
            f"smoke: an objective entered BURNING at the quick "
            f"shape: {objectives}"
        )
        log(f"smoke: all {len(objectives)} objectives judged on live "
            f"series ({frames} frames closed), zero breach evals")

    return {
        "metric": "slo_breach_evals",
        "value": breaches,
        "unit": "count",
        "slo_breach_evals": breaches,
        "worst_level": worst_level,
        # volatile (wall-clock frame count) — pruned from the gate
        # baseline; the bench keeps reporting it
        "frames_judged": frames,
        "windows": {
            "fast_s": fast_s, "slow_s": slow_s,
            "eval_interval_s": eval_s,
        },
        "objectives": objectives,
        "config": 15,
    }


# --------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int,
                    choices=[1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13,
                             14, 15],
                    help="BASELINE config to run (default: 5); 6 = "
                         "record-op durability workload; 7 = sharded-"
                         "backend 1→8-device scaling curve "
                         "(sharded_overhead); 8 = entity-simulation "
                         "plane (update ingest through the delta "
                         "path, device kNN tick, e2e frame latency); "
                         "9 = overload-storm admission (admitted vs "
                         "offered at 2x/10x, shed fractions, record "
                         "p99 under storm); 10 = adversarial scenario "
                         "suite (flash crowd, battle royale, "
                         "reconnect storm, game tick — survival + SLO "
                         "checks over real ZMQ); 11 = cluster_scaling "
                         "(1→N shard server processes behind the "
                         "router tier: admitted msgs/s + cross-shard "
                         "p99 per point, exact router/shard shed "
                         "audit); 12 = query_library (per-kind "
                         "cone/raycast/kNN/density device throughput, "
                         "mixed-kind batch p50/p99 vs a pure-radius "
                         "batch of the same size, CPU-oracle parity); "
                         "13 = interest-managed fan-out (delivered "
                         "bytes/tick --interest off vs on at the "
                         "game_tick shape over real ZMQ, replay-"
                         "oracle parity, ISSUE 18 5x acceptance); "
                         "14 = live resharding (migrate a hot world "
                         "between shard processes under load: "
                         "per-state wall times, freeze-window "
                         "delivery pause, park/replay/shed books, "
                         "zero-loss audit); 15 = slo_compliance (the "
                         "burn-rate engine judging the game_tick "
                         "shape live: per-objective compliance "
                         "fractions + worst burn rate)")
    ap.add_argument("--all", action="store_true",
                    help="run every config, one JSON line each")
    ap.add_argument("--subs", type=int, default=None)
    ap.add_argument("--queries", type=int, default=None)
    ap.add_argument("--ticks", type=int, default=None)
    ap.add_argument("--cpu-ticks", type=int, default=5)
    ap.add_argument("--delivery-clients", type=int, default=None,
                    help="live WS clients for the server_delivery "
                         "workers variant (default: 4096 full / 128 "
                         "quick — lower it to bound a CI run)")
    ap.add_argument("--quick", action="store_true",
                    help="small shapes for smoke-testing the harness")
    ap.add_argument("--smoke", action="store_true",
                    help="CI regression gate: --quick shapes on the "
                         "CPU backend with the result compaction "
                         "forced on and the WS delivery pump skipped — "
                         "fails if the compacted collect path never "
                         "fires (config 5), or if the entity-sim "
                         "device path / delta compaction / e2e frames "
                         "never fire (config 8)")
    ap.add_argument("--profile", metavar="DIR",
                    help="capture a jax.profiler trace of the sustained "
                         "run (config 5) into DIR (view with xprof/"
                         "tensorboard)")
    args = ap.parse_args()
    if args.smoke:
        args.quick = True
    # --quick shrinks the DEFAULT shapes; explicit flags still win
    quick_defaults = (20_000, 1_024, 10) if args.quick \
        else (1_000_000, 16_384, 50)
    for name, dflt in zip(("subs", "queries", "ticks"), quick_defaults):
        if getattr(args, name) is None:
            setattr(args, name, dflt)

    benches = {
        1: bench_config1, 2: bench_config2, 3: bench_config3,
        4: bench_config4, 5: bench_config5, 6: bench_config6,
        7: bench_config7, 8: bench_config8, 9: bench_config9,
        10: bench_config10, 11: bench_config11, 12: bench_config12,
        13: bench_config13, 14: bench_config14, 15: bench_config15,
    }
    if args.all:
        # config 7 is EXCLUDED from --all on purpose: it re-execs with
        # a forced 8-device host topology (where needed), which cannot
        # compose with the other configs' already-initialized runtime —
        # run it standalone like the multichip bench.
        selected = [1, 2, 3, 4, 5, 6, 8, 9, 10, 11, 12, 13, 14, 15]
    else:
        selected = [args.config or 5]
    for n in selected:
        log(f"=== BASELINE config {n} ===")
        emit(benches[n](args))


if __name__ == "__main__":
    main()
