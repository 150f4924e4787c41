#!/usr/bin/env python3
"""chip_smoke.py — the served path on the chip, at real size.

Boots the real server (``python -m worldql_server_tpu``) as a child on
one TPU with a 1,000,000-row subscription index restored from a
snapshot, drives it with real ZeroMQ peers, and compares every
delivery with a plain numpy reference of the same rows; then boots it
again with the entity plane and holds 100,000 entities' neighbor
streams to a reference. The quickest proof that the system still
starts on the accelerator:

    python chip_smoke.py                    # one chip; what the driver runs
    python chip_smoke.py --chips 4          # tpu vs sharded backend, 4 chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rows 20000 --peers 8 \\
        --allow-cpu                         # rehearsal on a chip-less box

This process never imports jax: a chip belongs to one process at a
time, and that process is the server child. Any failed phase raises, so
the run ends non-zero; only a green run prints the last line,
``{"ok": true, "device": {...}}``, with the device as the server
reported it. Seconds printed here are for the builder's chip budget —
they are not metrics.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import ctypes
import json
import os
import signal
import struct
import subprocess
import sys
import tempfile
import time
import urllib.request
import uuid
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
CUBE = 16                 # the server's default sub_region_size
OCCUPANCY_CAP = 256       # the north-star crowd: Zipf, capped
SPAN = 800.0              # crowd lives in ±SPAN per axis
TICK = 0.05
#: a cold 1M-row boot compiles for five minutes; none has come near this
BOOT_TIMEOUT = 900.0
#: once it serves, the server answers HTTP within this whatever else it
#: is doing: every wait below polls it, so an event loop held by a
#: compile or a sweep fails the run instead of being waited out. The
#: longest honest hold is the entity plane's host-side apply leg: an
#: answer took 6.9 and 10.7 s on the v5e host around the tick that
#: first shows the peers 100,000 entities (ROADMAP S7)
HTTP_TIMEOUT = 20.0
#: what is too long for the end of the output: the server's log and its
#: last /metrics (git-ignored; the chip tool brings this directory back)
OUT = ROOT / "chiprun_out"


def say(*parts) -> None:
    print("[chip_smoke]", *parts, flush=True)


def require(ok, why) -> None:
    """Fail the run (``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {why}")


# --------------------------------------------------------------------
# phase 1: the native library, from the committed sources
# --------------------------------------------------------------------


def build_native() -> None:
    """``make -C native`` — the one build site — then load every leg
    the device path uses. On this path a missing leg is an error: the
    loaders would otherwise fall back to their Python twins in silence
    and the run would exercise those."""
    subprocess.run(["make", "-C", str(ROOT / "native")], check=True)
    from worldql_server_tpu.protocol import codec, entity_wire
    from worldql_server_tpu.spatial import native_keys
    from worldql_server_tpu.transports import zmq_pass

    wire = entity_wire.shared()
    keys = native_keys._native
    legs = {
        "message_codec": codec._native is not None,
        "can_decode": wire is not None and wire.can_decode,
        "can_encode_frames": wire is not None and wire.can_encode_frames,
        "key_kernel": keys is not None,
        "wql_encode_queries": getattr(keys, "_encode", None) is not None,
        "wql_send_pass": zmq_pass.shared() is not None,
    }
    missing = [name for name, live in legs.items() if not live]
    require(not missing, f"native legs missing after make: {missing}")
    say("native legs live:", ", ".join(legs))


# --------------------------------------------------------------------
# phase 2: the index snapshot, from the seed
# --------------------------------------------------------------------


def zipf_cube_counts(rng, n: int) -> tuple[np.ndarray, np.ndarray]:
    """→ (cell ids, occupancy) of a Zipf(1)-popularity crowd over the
    cube grid, occupancy capped and the excess waterfilled down the
    ranking (BASELINE configs[4]'s crowd)."""
    cells_axis = int(SPAN * 2 / CUBE)
    n_ranked = min(max(n // 4, 1024), cells_axis ** 3)
    cell_ids = rng.permutation(cells_axis ** 3)[:n_ranked]
    p = 1.0 / np.arange(1, n_ranked + 1, dtype=np.float64)
    counts = rng.multinomial(n, p / p.sum())
    excess = int(np.maximum(counts - OCCUPANCY_CAP, 0).sum())
    counts = np.minimum(counts, OCCUPANCY_CAP)
    free = OCCUPANCY_CAP - counts
    counts += np.minimum(
        free, np.maximum(excess - (np.cumsum(free) - free), 0)
    )
    require(int(counts.sum()) == n, "waterfill must conserve rows")
    return cell_ids, counts


def cube_corners(cell_ids: np.ndarray) -> np.ndarray:
    """Grid cell ids → the low corner of each cell's cube."""
    axis = int(SPAN * 2 / CUBE)
    return np.stack([
        cell_ids % axis, (cell_ids // axis) % axis, cell_ids // (axis * axis),
    ], axis=1) * float(CUBE) - SPAN


class World:
    """The seeded deployment: one subscription row per peer, Zipf-
    crowded cubes, ``n_worlds`` worlds. Row i belongs to peer i."""

    def __init__(self, seed: int, rows: int, n_worlds: int):
        from worldql_server_tpu.spatial.quantize import cube_coords_batch

        rng = np.random.default_rng(seed)
        self.rows = rows
        self.names = [f"world_{w}" for w in range(n_worlds)]
        self.row_wid = (np.arange(rows) * n_worlds // rows).astype(np.int32)
        per_world = np.bincount(self.row_wid, minlength=n_worlds)
        cid = np.concatenate([
            np.repeat(*zipf_cube_counts(rng, int(n))) for n in per_world
        ])
        # strictly inside the cube: the golden quantizer, not this
        # script, decides which cube a position is in
        self.positions = cube_corners(cid) + rng.uniform(
            1.0, CUBE - 1.0, (rows, 3)
        )
        self.row_cube = cube_coords_batch(self.positions, CUBE)
        # peer i's UUID: the seed in the high half, i + 1 in the low
        self.peer_hi = np.full(rows, 0x57514C0000000000 | seed, np.uint64)
        self.peer_lo = np.arange(1, rows + 1, dtype=np.uint64)
        # reference index: rows grouped by (world, cube)
        keyed = np.column_stack([self.row_wid, self.row_cube])
        uniq, inverse = np.unique(keyed, axis=0, return_inverse=True)
        self._order = np.argsort(inverse, kind="stable")
        self._starts = np.concatenate(
            [[0], np.cumsum(np.bincount(inverse, minlength=len(uniq)))]
        )
        self._group = {tuple(k): g for g, k in enumerate(uniq.tolist())}

    def peer_uuid(self, i: int) -> uuid.UUID:
        return uuid.UUID(
            int=(int(self.peer_hi[i]) << 64) | int(self.peer_lo[i])
        )

    def members(self, wid: int, position) -> np.ndarray:
        """Reference resolve: every row (peer index) subscribed to the
        cube ``position`` quantizes to, in world ``wid``."""
        from worldql_server_tpu.spatial.quantize import cube_coords_batch

        cube = cube_coords_batch(np.asarray([position], np.float64), CUBE)[0]
        g = self._group.get((wid, *cube.tolist()))
        if g is None:
            return np.empty(0, np.int64)
        return self._order[self._starts[g]:self._starts[g + 1]]

    def occupancy(self) -> np.ndarray:
        return np.diff(self._starts)

    def write_snapshot(self, path: str) -> None:
        """The product's own format (spatial/snapshot.py, version 1)."""
        np.savez(
            path,
            version=np.int64(1),
            cube_size=np.int64(CUBE),
            worlds=np.frombuffer(json.dumps(self.names).encode(), np.uint8),
            peer_hi=self.peer_hi,
            peer_lo=self.peer_lo,
            row_wid=self.row_wid,
            row_cube=self.row_cube.astype(np.int64),
            row_pid=np.arange(self.rows, dtype=np.int64),
        )

    def pick_clients(self, n: int) -> list[int]:
        """Rows whose peers will connect: a quarter packed into the
        hottest cube, a quarter into the next, the rest one per cube
        down the ranking to the coldest — some share a cube, some do
        not."""
        occ = self.occupancy()
        ranked = np.argsort(-occ, kind="stable")
        quarter = max(n // 4, 1)
        picked: list[int] = []
        for g in ranked[:2]:
            take = min(quarter, int(occ[g]))
            picked += self._order[self._starts[g]:][:take].tolist()
        lone = np.linspace(2, len(ranked) - 1, n - len(picked)).astype(int)
        picked += [int(self._order[self._starts[ranked[r]]]) for r in lone]
        require(len(set(picked)) == n, "client rows must be distinct")
        return picked


# --------------------------------------------------------------------
# phase 3: the server child
# --------------------------------------------------------------------


def http_json(port: int, path: str, timeout: float = HTTP_TIMEOUT) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        headers={"Accept": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def cache_dir() -> Path:
    """Where every process of this run keeps compiled programs: the
    standard variable when set, the checkout's .jax_cache otherwise
    (spatial/jaxconf.py applies the same rule in the child)."""
    return Path(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or ROOT / ".jax_cache")


def cache_entries() -> int:
    d = cache_dir()
    return sum(1 for _ in d.iterdir()) if d.is_dir() else 0


def die_with_parent() -> None:
    """Runs in the child before exec: have the kernel SIGTERM the
    server should this script die first (PR_SET_PDEATHSIG) — a killed
    parent must not leave a process behind that holds the chip."""
    ctypes.CDLL("libc.so.6").prctl(1, signal.SIGTERM)


class Server:
    """One ``python -m worldql_server_tpu`` child. The only process of
    the run that touches jax, and so the only one that holds the chip."""

    def __init__(self, workdir: Path, server_args: list[str]):
        from worldql_server_tpu.scenarios.client import free_port

        self.http_port = free_port()
        self.zmq_port = free_port()
        self.log_path = workdir / f"server-{self.http_port}.log"
        self.slowest_answer = 0.0
        self.cmd = [
            sys.executable, "-m", "worldql_server_tpu", "-v",
            *server_args,
            "--tick-interval", str(TICK),
            "--store-url", "memory://",
            "--no-ws",
            "--http-host", "127.0.0.1", "--http-port", str(self.http_port),
            "--zmq-server-host", "127.0.0.1",
            "--zmq-server-port", str(self.zmq_port),
            # restored rows of peers that never connect are swept one
            # staleness window after boot, and the reference holds them:
            # keep the window past the run (the driver's limit is 1200 s)
            "--zmq-timeout-secs", "3600",
        ]
        self.proc: subprocess.Popen | None = None

    def start(self) -> None:
        warm = cache_entries()
        say(f"compile cache {cache_dir()}: {warm} entries"
            f" ({'warm' if warm else 'cold'})")
        say("starting:", " ".join(self.cmd))
        t0 = time.monotonic()
        with open(self.log_path, "w") as log:
            self.proc = subprocess.Popen(
                self.cmd, cwd=ROOT, stdout=log, stderr=subprocess.STDOUT,
                preexec_fn=die_with_parent,
            )
        while True:
            require(
                self.proc.poll() is None,
                f"server exited {self.proc.returncode} during boot:\n"
                + self.log_tail(),
            )
            require(
                time.monotonic() - t0 < BOOT_TIMEOUT,
                f"server not healthy after {BOOT_TIMEOUT}s:\n"
                + self.log_tail(),
            )
            try:
                http_json(self.http_port, "/healthz")
                break
            except OSError:
                time.sleep(0.25)
        say(f"boot seconds (restore + compile included): "
            f"{time.monotonic() - t0:.1f}")
        say(f"compile cache entries after boot: {cache_entries()}")

    def metrics(self) -> dict:
        t0 = time.monotonic()
        try:
            snap = http_json(self.http_port, "/metrics")
        except OSError as e:
            raise SystemExit(
                f"chip_smoke FAILED: the server did not answer /metrics "
                f"within {HTTP_TIMEOUT:.0f} s ({e}):\n" + self.log_tail()
            )
        self.slowest_answer = max(self.slowest_answer, time.monotonic() - t0)
        return snap

    def log_tail(self, n: int = 40) -> str:
        lines = self.log_path.read_text(errors="replace").splitlines()
        return "\n".join(lines[-n:])

    def shm_names(self) -> set[str]:
        """Shared-memory segments the child has mapped right now."""
        maps = Path(f"/proc/{self.proc.pid}/maps").read_text()
        return {
            line.split("/dev/shm/", 1)[1].split()[0]
            for line in maps.splitlines() if "/dev/shm/" in line
        }

    def stop(self) -> int:
        """SIGTERM the child itself and wait for it."""
        if self.proc is None or self.proc.poll() is not None:
            return self.proc.returncode if self.proc else 0
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            raise SystemExit(
                "chip_smoke FAILED: server ignored SIGTERM for 120 s:\n"
                + self.log_tail()
            )

    @contextlib.contextmanager
    def running(self):
        """Boot, lend the server out, then SIGTERM it and hold it to a
        clean exit: code 0, and none of the shared-memory segments it
        had mapped left behind."""
        held: set[str] = set()
        try:
            self.start()
            yield self
            held = self.shm_names()
        finally:
            rc = self.stop()
            (OUT / self.log_path.name).write_text(self.log_path.read_text())
        require(rc == 0, f"server exited {rc} on SIGTERM:\n{self.log_tail()}")
        leaked = held & set(os.listdir("/dev/shm"))
        require(not leaked, f"leaked shared memory: {sorted(leaked)}")
        say(f"server stopped cleanly, /dev/shm clean; its slowest /metrics "
            f"answer took {self.slowest_answer:.2f} s")


# --------------------------------------------------------------------
# phase 4: real peers, real traffic
# --------------------------------------------------------------------


class Client:
    """One connected ZMQ peer with a background reader."""

    def __init__(self, row: int, peer, sink=None):
        self.row = row
        self.peer = peer
        self.received: list = []
        self.sink = sink or self.received.append
        self.reader = asyncio.create_task(self._read())

    async def _read(self) -> None:
        from worldql_server_tpu.protocol import deserialize_message

        while True:
            self.sink(deserialize_message(await self.peer.pull.recv()))

    async def close(self) -> None:
        self.reader.cancel()
        await asyncio.gather(self.reader, return_exceptions=True)
        self.peer.close()


async def wait_for(server: Server, predicate, timeout: float,
                   what: str) -> dict:
    """Poll until ``predicate(fresh /metrics)`` holds; → that snapshot.
    Each poll is an HTTP round trip held to HTTP_TIMEOUT, so however
    long ``timeout`` is, a server that stops answering fails here."""
    deadline = time.monotonic() + timeout
    while True:
        snap = await asyncio.to_thread(server.metrics)
        if predicate(snap):
            return snap
        require(time.monotonic() < deadline, f"timed out waiting for {what}")
        await asyncio.sleep(TICK / 2)


def plan_traffic(world: World, client_rows: list[int],
                 seed: int) -> list[dict]:
    """The seeded LocalMessage schedule: 20 messages a round, a round a
    tick — 120 rounds (2,400 messages) at deployment size, fewer for a
    rehearsal's small index; round-robin senders, 80% from the sender's
    own position, 20% from a fresh random point (miss traffic), every
    tenth IncludingSelf."""
    rng = np.random.default_rng(seed + 1)
    rounds = min(120, max(30, world.rows // 5000))
    n_messages = 20 * rounds
    plan = []
    for i in range(n_messages):
        row = client_rows[i % len(client_rows)]
        fresh = rng.random() < 0.2
        plan.append({
            "id": i,
            "round": i * rounds // n_messages,
            "row": row,
            "wid": int(world.row_wid[row]),
            "position": (rng.uniform(-SPAN, SPAN, 3) if fresh
                         else world.positions[row]),
            "including_self": i % 10 == 0,
        })
    return plan


def reference_deliveries(world: World, client_rows: list[int],
                         plan: list[dict]) -> tuple[dict, int]:
    """→ ({message id: set of connected rows that must receive it},
    targets resolved whether connected or not)."""
    connected = set(client_rows)
    expected, resolved = {}, 0
    for m in plan:
        members = world.members(m["wid"], m["position"])
        if not m["including_self"]:
            members = members[members != m["row"]]
        resolved += len(members)
        expected[m["id"]] = connected.intersection(members.tolist())
    return expected, resolved


async def drive(server: Server, world: World, client_rows: list[int],
                plan: list[dict], expected: dict) -> dict:
    from worldql_server_tpu.protocol import Instruction, Message
    from worldql_server_tpu.protocol.types import (
        Record, Replication, Vector3,
    )
    from worldql_server_tpu.scenarios.client import ZmqPeer

    clients = [
        Client(row, await ZmqPeer.connect(
            server.zmq_port, peer_uuid=world.peer_uuid(row), timeout=30.0,
        ))
        for row in client_rows
    ]
    by_row = {c.row: c for c in clients}
    say(f"{len(clients)} ZMQ peers connected")
    try:
        for i, m in enumerate(plan):
            if i and m["round"] != plan[i - 1]["round"]:
                # a round per tick, whatever a tick takes: the next
                # round goes out once the ticker has flushed this one
                await wait_for(
                    server,
                    lambda snap: snap["counters"].get("tick.messages", 0) >= i,
                    30.0, f"the ticker to flush the first {i} messages",
                )
            x, y, z = (float(v) for v in m["position"])
            await by_row[m["row"]].peer.send(Message(
                instruction=Instruction.LOCAL_MESSAGE,
                world_name=world.names[m["wid"]],
                position=Vector3(x, y, z),
                replication=(Replication.INCLUDING_SELF if m["including_self"]
                             else Replication.EXCEPT_SELF),
                flex=b"smoke:%d" % m["id"],
            ))
        want = sum(len(rows) for rows in expected.values())

        def local_count() -> int:
            return sum(
                1 for c in clients for msg in c.received
                if msg.instruction == Instruction.LOCAL_MESSAGE
            )

        await wait_for(server, lambda _: local_count() >= want, 60.0,
                       f"{want} deliveries")
        await asyncio.sleep(1.0)   # anything extra would arrive now

        # the rest of the protocol, once each
        first, second = clients[0], clients[1]
        await first.peer.send(Message(
            instruction=Instruction.GLOBAL_MESSAGE, world_name="@global",
            flex=b"smoke:global",
        ))
        await second.peer.send(Message(instruction=Instruction.HEARTBEAT))
        rec_uuid = uuid.UUID(int=0x5245434F5244)
        rec_pos = Vector3(1.5, 2.5, 3.5)
        await first.peer.send(Message(
            instruction=Instruction.RECORD_CREATE, world_name=world.names[0],
            records=[Record(uuid=rec_uuid, position=rec_pos,
                            world_name=world.names[0], data="smoke-record")],
        ))
        await first.peer.send(Message(
            instruction=Instruction.RECORD_READ, world_name=world.names[0],
            position=rec_pos,
        ))

        def got(c: Client, instruction) -> list:
            return [m for m in c.received if m.instruction == instruction]

        await wait_for(
            server,
            lambda _: all(got(c, Instruction.GLOBAL_MESSAGE)
                          for c in clients[1:])
            and got(second, Instruction.HEARTBEAT)
            and got(first, Instruction.RECORD_REPLY),
            30.0, "global message, heartbeat echo and record reply",
        )
        require(
            not got(first, Instruction.GLOBAL_MESSAGE),
            "GlobalMessage came back to its ExceptSelf sender",
        )
        reply = got(first, Instruction.RECORD_REPLY)[0]
        require(
            [(r.uuid, r.data) for r in reply.records]
            == [(rec_uuid, "smoke-record")],
            f"record read back wrong: {reply}",
        )
        say("global message, heartbeat echo, record create->read: ok")

        # delivered sets, outside any timing
        delivered: dict[int, list[int]] = {m["id"]: [] for m in plan}
        for c in clients:
            for msg in got(c, Instruction.LOCAL_MESSAGE):
                delivered[int(msg.flex.split(b":")[1])].append(c.row)
        missing = extra = twice = 0
        for mid, rows in delivered.items():
            twice += len(rows) - len(set(rows))
            missing += len(expected[mid] - set(rows))
            extra += len(set(rows) - expected[mid])
        n_delivered = sum(len(r) for r in delivered.values())
        say(f"messages {len(plan)}, deliveries {n_delivered} "
            f"(reference {want}): missing {missing}, extra {extra}, "
            f"delivered twice {twice}")
        require(
            (missing, extra, twice) == (0, 0, 0),
            "delivered sets differ from the reference",
        )
        return {"delivered": delivered}
    finally:
        for c in clients:
            await c.close()


# --------------------------------------------------------------------
# one served run: boot, traffic, compare, read the gauges, stop
# --------------------------------------------------------------------

#: counters that must not move while the device resolves targets that
#: are not connected (the delivery path drops those) — or at all
ERROR_COUNTERS = (
    "messages.errors", "broadcast.send_errors", "zmq.recv_errors",
    "peers.evicted_send_failed", "tick.staging_fallbacks",
    "sweeper.remove_errors",
)


def served_run(args, world: World, snapshot: str, workdir: Path,
               backend_args: list[str], check_device) -> dict:
    client_rows = world.pick_clients(args.peers)
    plan = plan_traffic(world, client_rows, args.seed)
    expected, resolved = reference_deliveries(world, client_rows, plan)
    say(f"reference: {len(plan)} messages resolve {resolved} targets, "
        f"{sum(len(e) for e in expected.values())} of them connected")

    # written per run: a stopping server saves its index back to the
    # file, minus the peers that never connected
    world.write_snapshot(snapshot)
    server = Server(workdir, [*backend_args, "--index-snapshot", snapshot])
    with server.running():
        before = server.metrics()
        device = before["gauges"]["spatial_device"]
        say("server reports device:", json.dumps({
            k: device.get(k) for k in
            ("platform", "device_kind", "device_count", "mesh",
             "base_bytes_per_device", "subscriptions", "capacity")
        }))
        check_device(device)
        require(
            device["subscriptions"] == world.rows,
            f"{device['subscriptions']} rows on the device, "
            f"expected {world.rows}",
        )
        result = asyncio.run(drive(server, world, client_rows, plan, expected))
        after = server.metrics()
        (OUT / f"metrics-{server.http_port}.json").write_text(
            json.dumps(after, indent=1))
        counters, gauges = after["counters"], after["gauges"]
        device = gauges["spatial_device"]
        moved = {
            name: counters.get(name, 0) - before["counters"].get(name, 0)
            for name in ERROR_COUNTERS
        }
        require(not any(moved.values()), f"error counters moved: {moved}")
        flushes = counters.get("tick.flushes", 0)
        say(f"ticks with traffic {flushes}, tick messages "
            f"{counters.get('tick.messages', 0)}, staged dispatches "
            f"{device['staged_dispatches']}, list dispatches "
            f"{device['list_dispatches']}, compact fetches "
            f"{device['compact_fetches']}, full fetches "
            f"{device['full_fetches']}")
        say("device telemetry:", json.dumps(gauges.get("device")))
        say("boot precompile:", json.dumps(gauges.get("precompile")))
        say("last tick:", json.dumps({
            k: gauges.get("tick", {}).get(k) for k in
            ("last_batch", "last_tick_ms", "last_dispatch_ms",
             "last_collect_ms")
        }))
        require(
            flushes >= plan[-1]["round"] + 1,
            f"only {flushes} ticks carried traffic",
        )
        require(
            counters.get("tick.messages", 0) >= len(plan),
            "the ticker saw fewer messages than were sent",
        )
        require(
            device["staged_dispatches"] + device["list_dispatches"] > 0,
            "no batch was dispatched to the device backend",
        )
        require(
            device["subscriptions"] == world.rows,
            "index rows changed during the run",
        )
        check_device(device)
        result["device"] = device
    return result


# --------------------------------------------------------------------
# phase two: the entity plane (the one place the kNN kernel runs)
# --------------------------------------------------------------------

ENTITIES_PER_CUBE = 16    # <= k: the kNN window then covers the cube
ENTITY_K = 32


class Swarm:
    """The seeded entity population: ``n`` entities, 16 to a cube, each
    owned by a random peer; one in 500 drifts slowly (so deltas flow
    every tick), the rest stand still. Coordinates are multiples of
    1/8, exact in the plane's f32 columns."""

    def __init__(self, seed: int, n: int, n_peers: int):
        rng = np.random.default_rng(seed + 2)
        n_cells = int(SPAN * 2 / CUBE) ** 3
        n_cubes = -(-n // ENTITIES_PER_CUBE)
        self.cube_id = np.repeat(rng.permutation(n_cells)[:n_cubes],
                                 ENTITIES_PER_CUBE)[:n]
        self.pos = (cube_corners(self.cube_id)
                    + rng.integers(16, 96, (n, 3)) / 8.0)
        self.owner = rng.integers(0, n_peers, n)
        self.vel = np.zeros((n, 3))
        self.vel[rng.random(n) < 0.002, 0] = 0.0078125    # 1/128 per s
        self.n = n

    def entity_uuid(self, i: int) -> uuid.UUID:
        return uuid.UUID(int=(0x454E54 << 64) | (i + 1))

    def visible_to(self, peer: int) -> np.ndarray:
        """Reference: with cube occupancy <= k every co-cube entity of
        another peer is a kNN neighbor, so a peer sees exactly the
        entities of other peers in the cubes where it owns one."""
        mine = self.owner == peer
        return np.flatnonzero(
            np.isin(self.cube_id, self.cube_id[mine]) & ~mine
        )


async def drive_entities(server: Server, swarm: Swarm,
                         peer_uuids: list) -> dict:
    from worldql_server_tpu.interest import ReplayClient
    from worldql_server_tpu.protocol import Instruction, Message
    from worldql_server_tpu.protocol.types import Entity, Vector3
    from worldql_server_tpu.scenarios.client import ZmqPeer

    world = "world_0"
    oracles = [ReplayClient() for _ in peer_uuids]
    clients = [
        Client(i, await ZmqPeer.connect(
            server.zmq_port, peer_uuid=peer_uuids[i], timeout=30.0,
        ), sink=oracles[i].apply)
        for i in range(len(peer_uuids))
    ]
    say(f"{len(clients)} ZMQ peers connected")

    def sim_of(snap: dict) -> dict:
        return snap["gauges"]["entity_sim"]

    try:
        t_sent = time.monotonic()
        for c in clients:
            mine = np.flatnonzero(swarm.owner == c.row)
            for lo in range(0, len(mine), 400):
                await c.peer.send(Message(
                    instruction=Instruction.LOCAL_MESSAGE, world_name=world,
                    entities=[
                        Entity(
                            uuid=swarm.entity_uuid(int(i)),
                            position=Vector3(*map(float, swarm.pos[i])),
                            world_name=world,
                            flex=(struct.pack("<3f", *swarm.vel[i])
                                  if swarm.vel[i].any() else None),
                        )
                        for i in mine[lo:lo + 400]
                    ],
                ))
        expected = [swarm.visible_to(c.row) for c in clients]

        def settled() -> bool:
            return all(
                len(o.worlds.get(world, ())) == len(e)
                for o, e in zip(oracles, expected)
            )

        # 100,000 registrations in one burst: the plane compiled every
        # tier the population grows through at boot and the recv path
        # gives way, so the server ticks and answers while it takes
        # them in
        snap = await wait_for(server, lambda _: settled(), 180.0,
                              "every peer's neighbor ledger")
        require(sim_of(snap)["entities"] == swarm.n, sim_of(snap))
        settled_at = sim_of(snap)["applied_ticks"]
        sim = sim_of(await wait_for(    # then 40 ticks of steady state
            server,
            lambda snap: sim_of(snap)["applied_ticks"] >= settled_at + 40,
            300.0, "40 ticks after the ledgers settled",
        ))
        elapsed = time.monotonic() - t_sent + 1.0

        wrong = 0
        for c, oracle, exp in zip(clients, oracles, expected):
            ledger = oracle.worlds.get(world, {})
            want = {swarm.entity_uuid(int(i)): i for i in exp}
            require(
                ledger.keys() == want.keys(),
                f"peer {c.row}: {len(ledger.keys() ^ want.keys())} "
                "entities differ from the reference's neighbor set",
            )
            for eid, i in want.items():
                drift = np.asarray(ledger[eid]) - swarm.pos[i]
                hi = swarm.vel[i] * elapsed
                wrong += not (np.all(drift >= -1e-6)
                              and np.all(drift <= hi + 1e-6))
        refused = sum(o.deltas_refused for o in oracles)
        gaps = sum(o.gaps_seen for o in oracles)
        say(f"entity phase: {swarm.n} entities, {sim['applied_ticks']} "
            f"ticks applied, {sum(map(len, expected))} ledger entries "
            f"equal to the reference, {wrong} positions off, frames "
            f"{sum(o.frames_applied for o in oracles)} "
            f"(full {sum(o.fulls_applied for o in oracles)}, delta "
            f"{sum(o.deltas_applied for o in oracles)}), deltas refused "
            f"{refused}, gaps {gaps}")
        require(
            (wrong, refused, gaps) == (0, 0, 0),
            "entity ledgers differ from the reference",
        )
        require(
            sum(o.deltas_applied for o in oracles) > 0,
            "no delta frame was ever applied",
        )
        return sim
    finally:
        for c in clients:
            await c.close()


def entity_run(args, workdir: Path, check_device) -> None:
    swarm = Swarm(args.seed, args.entities, args.peers)
    peer_uuids = [uuid.UUID(int=(0x50454552 << 64) | (i + 1))
                  for i in range(args.peers)]
    server = Server(workdir, [
        "--spatial-backend", "tpu", "--entity-sim", "--interest", "on",
        "--entity-k", str(ENTITY_K),
        "--entity-max", str(1 << max(args.entities - 1, 255).bit_length()),
    ])
    with server.running():
        booted = server.metrics()["gauges"]
        check_device(booted["spatial_device"])
        say("boot precompile:", json.dumps(booted["precompile"]["entities"]))
        sim = asyncio.run(drive_entities(server, swarm, peer_uuids))
        after = server.metrics()
        say("compiled while serving:", json.dumps({
            k: round(after["gauges"]["device"][k] - booted["device"][k], 1)
            for k in ("compiles", "compile_ms_total")
        }))
        (OUT / f"metrics-{server.http_port}.json").write_text(
            json.dumps(after, indent=1))
        moved = {name: after["counters"].get(name, 0)
                 for name in ERROR_COUNTERS}
        require(not any(moved.values()), f"error counters moved: {moved}")
        say("entity sim:", json.dumps({k: sim[k] for k in (
            "pallas", "k", "capacity", "full_sim_ticks", "delta_sim_ticks",
            "last_knn_ms", "last_integrate_ms", "last_apply_ms",
        )}))
        first_tick = [ln for ln in server.log_path.read_text().splitlines()
                      if "entity sim first tick" in ln]
        require(first_tick, "the plane never logged its first tick")
        say(first_tick[0].split(": ", 1)[-1])
        if not args.allow_cpu:
            require(
                sim["pallas"] and "pallas=True" in first_tick[0],
                "the kNN resolve did not take the compiled Pallas kernel",
            )
        check_device(after["gauges"]["spatial_device"])


def device_check(args, count: int):
    def check(device: dict) -> None:
        if not args.allow_cpu:
            require(
                device["platform"] == "tpu",
                f"the index lives on platform {device['platform']!r} "
                f"({device['device_kind']}), not on a TPU — refusing to "
                "report a chip run (--allow-cpu is for rehearsals)",
            )
        require(
            device["device_count"] == count,
            f"index on {device['device_count']} devices, expected {count}",
        )
    return check


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=None,
                    help="subscription rows (default 1,000,000; "
                         "640,000 with --chips 4)")
    ap.add_argument("--peers", type=int, default=64)
    ap.add_argument("--entities", type=int, default=100_000,
                    help="entities of the second, entity-plane phase "
                         "(0 skips it)")
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal: accept a server whose index is not "
                         "on a TPU; the last line then says so")
    args = ap.parse_args()

    t_start = time.monotonic()
    build_native()
    OUT.mkdir(exist_ok=True)
    workdir = Path(tempfile.mkdtemp(prefix="chip_smoke."))
    snapshot = str(workdir / "index.npz")
    four = args.chips == 4
    rows = args.rows or (640_000 if four else 1_000_000)
    world = World(args.seed, rows, n_worlds=64 if four else 1)
    occ = world.occupancy()
    say(f"snapshot: {rows} rows, {len(world.names)} world(s), "
        f"{len(occ)} cubes, max occupancy {int(occ.max())}, seed {args.seed}")

    if not four:
        result = served_run(args, world, snapshot, workdir,
                            ["--spatial-backend", "tpu"],
                            device_check(args, 1))
        if args.entities:
            entity_run(args, workdir, device_check(args, 1))
    else:
        single = served_run(args, world, snapshot, workdir,
                            ["--spatial-backend", "tpu"],
                            device_check(args, 1))
        result = served_run(args, world, snapshot, workdir,
                            ["--spatial-backend", "sharded",
                             "--mesh-batch", "1", "--mesh-space", "4"],
                            device_check(args, 4))
        require(
            result["delivered"] == single["delivered"],
            "sharded and single-chip servers delivered different sets",
        )
        device = result["device"]
        require(device["mesh"] == {"batch": 1, "space": 4}, device["mesh"])
        per_device = device["base_bytes_per_device"]
        say("sharded base bytes per device:", json.dumps(per_device))
        require(
            len(per_device) == 4 and all(per_device.values()),
            "not every device holds a shard of the index",
        )
        say("single-chip and sharded servers delivered identical sets")

    say(f"total seconds {time.monotonic() - t_start:.1f}")
    device = result["device"]
    require("jax" not in sys.modules, "the parent must stay off jax")
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"],
        "kind": device["device_kind"],
        "count": device["device_count"],
    }}), flush=True)


if __name__ == "__main__":
    main()
