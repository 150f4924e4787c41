"""Cluster e2e (ISSUE 14): real ZMQ through the router tier.

Boots the full horizontal-serving stack — router in this process, two
shard SERVER subprocesses (``python -m worldql_server_tpu
--cluster-role shard``) — and proves over real sockets:

* same-world LocalMessages between peers homed on DIFFERENT shards
  (delivery to the remote peer rides the inter-shard ring);
* GlobalMessages resolved on the world's owner shard and delivered
  cross-shard;
* records durable PER SHARD: created with ``--durability wal``, they
  survive a shard SIGKILL → supervised restart → WAL replay, and read
  back through the router from either side of the cluster;
* session continuity through the router: a hard-dropped peer resumes
  by token onto its home shard with its subscriptions intact on BOTH
  shards (zero re-subscribe) — and after its home shard is killed and
  restarted, the same client re-handshakes through the router and
  traffic flows again;
* the overlap acceptance: a shard tick trace shows ``cluster.drain``
  INSIDE the local device window (starting at/after ``tick.dispatch``
  begins, before ``tick.collect`` ends) — the cross-shard leg hides
  behind the dispatch instead of serializing in front of it.

No device mesh is involved anywhere: shards run the CPU backend, so
this suite needs no multi-process collectives.
"""

import asyncio
import json
import os
import signal
import socket
import time
import urllib.request
import uuid as uuid_mod

# Children spawned by the supervisor inherit this env: without it a
# `python -m worldql_server_tpu` child may initialize the installed-
# but-hardwareless libtpu plugin and hang in device discovery.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

from worldql_server_tpu.cluster import ClusterRuntime, WorldMap
from worldql_server_tpu.cluster.supervisor import shard_http_port
from worldql_server_tpu.engine.config import Config
from worldql_server_tpu.protocol.types import (
    Instruction,
    Message,
    Record,
    Vector3,
)
from worldql_server_tpu.scenarios.client import ZmqPeer

from tests.prom_parser import parse_exposition, validate_exposition

POS = Vector3(5.0, 5.0, 5.0)


def _http_text(url: str) -> str:
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.read().decode()


def _monotone_series(text: str) -> dict:
    """Federated-series snapshot for monotonicity checks: every
    counter sample and histogram bucket/count of the cluster.* family,
    keyed by (name, le) — gauges are excluded (they may move down)."""
    types, samples = parse_exposition(text)
    out = {}
    for name, labels, value in samples:
        base = name
        for suffix in ("_bucket", "_sum", "_count", "_total"):
            if name.endswith(suffix):
                base = name[: -len(suffix)]
                break
        kind = types.get(base) or types.get(name)
        if kind not in ("counter", "histogram"):
            continue
        if name.endswith("_sum"):
            continue  # float sums jitter; counts are the contract
        if not name.startswith("wql_cluster"):
            continue
        out[(name, labels.get("le", ""))] = value
    return out


def _port_block(n: int, attempts: int = 64) -> int:
    """A base port such that base..base+n are all currently free (the
    cluster derives shard ports as base+1+i)."""
    for _ in range(attempts):
        socks = []
        try:
            s0 = socket.socket()
            s0.bind(("127.0.0.1", 0))
            base = s0.getsockname()[1]
            socks.append(s0)
            for off in range(1, n + 1):
                s = socket.socket()
                s.bind(("127.0.0.1", base + off))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("could not find a free port block")


def _world_for_shard(world_map: WorldMap, shard: int, stem: str) -> str:
    for i in range(10_000):
        name = f"{stem}{i}"
        if world_map.shard_of_world(name) == shard:
            return name
    raise AssertionError("no world name found for shard")


def _uuid_for_shard(world_map: WorldMap, shard: int) -> uuid_mod.UUID:
    while True:
        u = uuid_mod.uuid4()
        if world_map.shard_of_peer(u) == shard:
            return u


def _cluster_config(tmp_path, n_shards: int = 2) -> Config:
    # ONE block for both port families: two separate probes could
    # overlap each other once the first probe's sockets close
    base = _port_block(2 * n_shards + 1)
    http_base = base + n_shards + 1
    return Config(
        store_url=f"sqlite://{tmp_path}/records.db",
        http_enabled=True, http_host="127.0.0.1", http_port=http_base,
        ws_enabled=False,
        zmq_server_host="127.0.0.1", zmq_server_port=base,
        spatial_backend="cpu",
        tick_interval=0.02,
        durability="wal", wal_dir=str(tmp_path / "wal"),
        checkpoint_interval=0,   # SIGKILL must find the WAL un-truncated
        session_ttl=30.0,
        # these clients never heartbeat, and a case gives a killed shard
        # 90 s to come back: with the default 25 s a peer that only
        # waited was swept as stale on loaded cores, then torn down
        zmq_timeout_secs=600,
        trace=True,              # shards inherit --trace for /debug/ticks
        cluster_shards=n_shards,
        verbose=0,
    )


async def _wait(predicate, timeout_s: float, what: str, interval=0.1):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        result = predicate()
        if result:
            return result
        await asyncio.sleep(interval)
    raise AssertionError(f"timed out waiting for {what}")


def _http_json(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=10) as resp:
        return json.loads(resp.read())


def _maybe(fn):
    """Poll helper: a predicate's transient error (scrape racing a
    shard restart, half-federated series) reads as not-ready."""
    try:
        return fn()
    except Exception:
        return None


async def _connect(port: int, peers: list, **kw) -> ZmqPeer:
    """A peer through the router, kept in ``peers`` for the teardown;
    retried while the tier is still binding."""
    last = None
    for _ in range(100):
        try:
            peer = await ZmqPeer.connect(port, **kw)
            peers.append(peer)
            return peer
        except Exception as exc:
            last = exc
            await asyncio.sleep(0.05)
    raise AssertionError(f"client could not connect: {last!r}")


async def _drain_cluster_e2e(tmp_path):
    config = _cluster_config(tmp_path)
    world_map = WorldMap(2)
    w0 = _world_for_shard(world_map, 0, "arena")   # owned by shard 0
    w1 = _world_for_shard(world_map, 1, "lobby")   # owned by shard 1
    uuid_a = _uuid_for_shard(world_map, 0)         # homed on shard 0
    uuid_b = _uuid_for_shard(world_map, 1)         # homed on shard 1

    runtime = ClusterRuntime(config)
    await runtime.start()
    peers: list[ZmqPeer] = []
    try:
        async def connect(peer_uuid, token=None):
            return await _connect(
                config.zmq_server_port, peers,
                peer_uuid=peer_uuid, token=token,
            )

        a = await connect(uuid_a)
        b = await connect(uuid_b)
        assert a.token and b.token, "session tokens minted through router"

        # --- subscriptions: same position, both worlds (w0 rows land
        # on shard 0's index, w1 rows on shard 1's) -----------------
        for world in (w0, w1):
            for c in (a, b):
                await c.send(Message(
                    instruction=Instruction.AREA_SUBSCRIBE,
                    world_name=world, position=POS,
                ))
        await asyncio.sleep(0.3)  # let the subscribe forwards land

        async def recv_param(client, instruction, parameter, timeout=15.0):
            """recv until BOTH instruction and parameter match — stale
            frames from earlier phases must not satisfy a later one."""
            deadline = time.monotonic() + timeout
            while True:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise AssertionError(
                        f"never received {instruction.name} "
                        f"{parameter!r}"
                    )
                got = await client.recv_until(instruction, left)
                if got.parameter == parameter:
                    return got

        # --- LocalMessage in w0: resolved on shard 0; A's copy is a
        # direct socket write, B's rides the 0→1 ring ----------------
        async def local_roundtrip(tag: str):
            await a.send(Message(
                instruction=Instruction.LOCAL_MESSAGE, world_name=w0,
                position=POS, parameter=f"{tag}-from-a",
            ))
            await recv_param(
                b, Instruction.LOCAL_MESSAGE, f"{tag}-from-a"
            )
            await b.send(Message(
                instruction=Instruction.LOCAL_MESSAGE, world_name=w0,
                position=POS, parameter=f"{tag}-from-b",
            ))
            await recv_param(
                a, Instruction.LOCAL_MESSAGE, f"{tag}-from-b"
            )

        await local_roundtrip("local")

        # --- GlobalMessage in w1: resolved on shard 1 (the owner);
        # A's copy crosses the 1→0 ring --------------------------------
        await b.send(Message(
            instruction=Instruction.GLOBAL_MESSAGE, world_name=w1,
            parameter="global-from-b",
        ))
        await recv_param(a, Instruction.GLOBAL_MESSAGE, "global-from-b")

        # --- records, one per shard, acked through the WAL ----------
        rec0, rec1 = uuid_mod.uuid4(), uuid_mod.uuid4()
        await a.send(Message(
            instruction=Instruction.RECORD_CREATE, world_name=w0,
            records=[Record(uuid=rec0, position=POS, world_name=w0,
                            data="on-shard-0")],
        ))
        await b.send(Message(
            instruction=Instruction.RECORD_CREATE, world_name=w1,
            records=[Record(uuid=rec1, position=POS, world_name=w1,
                            data="on-shard-1")],
        ))

        async def read_records(client, world, timeout=15):
            await client.send(Message(
                instruction=Instruction.RECORD_READ, world_name=world,
                position=POS,
            ))
            reply = await client.recv_until(
                Instruction.RECORD_REPLY, timeout
            )
            return {r.uuid: r for r in reply.records}

        # cross-shard read: B reads shard 0's world — the reply rides
        # the 0→1 ring home. Retry: the create is async wrt the read.
        async def wait_record(client, world, rec_uuid, what):
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                try:
                    rows = await read_records(client, world, timeout=5)
                except asyncio.TimeoutError:
                    continue
                if rec_uuid in rows:
                    return rows[rec_uuid]
                await asyncio.sleep(0.1)
            raise AssertionError(f"record never visible: {what}")

        got0 = await wait_record(b, w0, rec0, "rec0 via cross-shard read")
        assert got0.data == "on-shard-0"
        await wait_record(a, w1, rec1, "rec1 via cross-shard read")

        # --- span-verified overlap: drive local dispatch on shard 0
        # (A's locals in w0) while cross-shard frames flow INTO shard
        # 0 (B's globals in w1 delivered to A), then find one shard-0
        # tick whose cluster.drain sits inside the device window ------
        for _ in range(40):
            await a.send(Message(
                instruction=Instruction.LOCAL_MESSAGE, world_name=w0,
                position=POS, parameter="overlap",
            ))
            await b.send(Message(
                instruction=Instruction.GLOBAL_MESSAGE, world_name=w1,
                parameter="overlap",
            ))
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.5)

        def overlapping_tick():
            ticks = _http_json(
                f"http://127.0.0.1:{shard_http_port(config, 0)}"
                "/debug/ticks"
            )["ticks"]
            for tick in ticks:
                spans = {s["name"]: s for s in tick["spans"]}
                dispatch = spans.get("tick.dispatch")
                drain = spans.get("cluster.drain")
                collect = spans.get("tick.collect")
                if not (dispatch and drain and collect):
                    continue
                if drain["tags"].get("frames", 0) < 1:
                    continue
                # the drain ran inside the device window: not before
                # the dispatch began, done before the collect ended
                if (
                    drain["t0_ms"] >= dispatch["t0_ms"]
                    and drain["t0_ms"] + drain["dur_ms"]
                    <= collect["t0_ms"] + collect["dur_ms"] + 1e-3
                ):
                    return tick
            return None

        assert await _wait(
            overlapping_tick, 20,
            "a shard-0 tick with cluster.drain inside the "
            "dispatch→collect device window",
        )

        # --- ISSUE 15: ONE federated /metrics for the fleet ---------
        # drive w1 locals too so BOTH shards close the router-ingress
        # frame clock (shard 1 on its local delivery leg, shard 0 on
        # the ring drain of A's copies)
        for i in range(10):
            await a.send(Message(
                instruction=Instruction.LOCAL_MESSAGE, world_name=w1,
                position=POS, parameter=f"fed-{i}",
            ))
            await asyncio.sleep(0.01)
        await recv_param(b, Instruction.LOCAL_MESSAGE, "fed-9")
        metrics_url = f"http://127.0.0.1:{config.http_port}/metrics"

        def federated_series():
            text = _http_text(metrics_url)
            validate_exposition(text)  # strict-parse, no collisions
            _, samples = parse_exposition(text)
            counts = {
                name: value for name, labels, value in samples
                if not labels
            }
            # per-shard AND aggregate e2e series advancing, plus the
            # cross-shard histogram and the per-core efficiency gauge
            if (
                counts.get("wql_cluster_e2e_seconds_count", 0) > 0
                and counts.get(
                    "wql_cluster_shard_0_e2e_seconds_count", 0) > 0
                and counts.get(
                    "wql_cluster_shard_1_e2e_seconds_count", 0) > 0
                and counts.get("wql_cluster_xshard_seconds_count", 0) > 0
                and "wql_deliveries_per_s_per_core" in counts
            ):
                return counts
            return None

        # the router's HTTP runs on THIS loop — every fetch must go
        # off-thread (the existing healthz idiom)
        fed_counts = None
        fed_deadline = time.monotonic() + 30
        while time.monotonic() < fed_deadline:
            fed_counts = await asyncio.to_thread(_maybe, federated_series)
            if fed_counts:
                break
            await asyncio.sleep(0.5)
        assert fed_counts, (
            "per-shard + aggregate cluster.e2e_ms series never "
            "advanced in the router's federated /metrics"
        )
        assert (
            fed_counts["wql_cluster_e2e_seconds_count"]
            >= fed_counts["wql_cluster_shard_0_e2e_seconds_count"]
        )
        before_kill = _monotone_series(
            await asyncio.to_thread(_http_text, metrics_url)
        )

        # --- ISSUE 15: /debug/cluster — one Chrome trace, three
        # processes, a cross-shard frame's router→home→remote chain
        # sharing ONE trace id --------------------------------------
        def chain_trace_ids():
            dump = _http_json(
                f"http://127.0.0.1:{config.http_port}/debug/cluster"
            )
            shards = dump.get("shards", {})
            if set(shards) != {"0", "1"}:
                return None
            router_ids = {
                s["tags"].get("trace_id")
                for t in dump["router"]["traces"]
                for s in t.get("spans", ())
                if s["name"] == "router.forward"
            }
            # home shard (1): the w1 local's recv tree is tagged
            home_ids = {
                s["tags"].get("trace_id")
                for t in shards["1"].get("loose", ())
                for s in t.get("spans", ())
                if "trace_id" in (s.get("tags") or {})
            }
            # remote shard (0): stitched ring spans under tick traces
            remote_ids = {
                s["tags"].get("trace_id")
                for t in shards["0"].get("ticks", ())
                for s in t.get("spans", ())
                if s["name"] in ("router.forward", "cluster.ring_dwell")
            }
            chain = (router_ids & home_ids & remote_ids) - {None}
            return chain or None

        async def drive_and_find_chain():
            for attempt in range(10):
                for i in range(6):
                    # locals in w0 keep shard 0 ticking WITH a batch
                    # (only traced ticks get the stitched ring spans)…
                    await a.send(Message(
                        instruction=Instruction.LOCAL_MESSAGE,
                        world_name=w0, position=POS,
                        parameter=f"chainload-{attempt}-{i}",
                    ))
                    # …while B's globals in w1 cross the 1→0 ring into
                    # those ticks — the frames whose chain we assert
                    await b.send(Message(
                        instruction=Instruction.GLOBAL_MESSAGE,
                        world_name=w1,
                        parameter=f"chainx-{attempt}-{i}",
                    ))
                    await asyncio.sleep(0.01)
                await recv_param(
                    a, Instruction.GLOBAL_MESSAGE,
                    f"chainx-{attempt}-5",
                )
                await asyncio.sleep(0.3)
                chain = await asyncio.to_thread(_maybe, chain_trace_ids)
                if chain:
                    return chain
            return None

        chain = await drive_and_find_chain()
        assert chain, (
            "no cross-shard frame's trace id chained across router, "
            "home-shard and remote-shard spans in /debug/cluster"
        )

        # chrome format: one NAMED pid lane per process
        chrome = await asyncio.to_thread(
            _http_json,
            f"http://127.0.0.1:{config.http_port}"
            "/debug/cluster?format=chrome",
        )
        events = chrome["traceEvents"]
        lanes = {
            e["args"]["name"]: e["pid"] for e in events
            if e.get("ph") == "M" and e["name"] == "process_name"
        }
        assert {"router", "shard-0", "shard-1"} <= set(lanes)
        assert len(set(lanes.values())) == 3  # three real pids
        assert any(e.get("ph") == "X" for e in events)

        # --- session resume over a LIVE home shard: A hard-drops and
        # resumes by token — no re-subscribe, rows intact on BOTH
        # shards ------------------------------------------------------
        a.close()
        peers.remove(a)
        a = await connect(uuid_a, token=a.token)
        assert not a.refused
        await local_roundtrip("resumed")          # w0 rows still live
        await b.send(Message(
            instruction=Instruction.GLOBAL_MESSAGE, world_name=w1,
            parameter="resumed-global",
        ))
        await recv_param(a, Instruction.GLOBAL_MESSAGE, "resumed-global")

        # --- SIGKILL shard 0 → supervised restart → WAL replay ------
        proc0 = runtime.supervisor._shards[0].proc
        os.kill(proc0.pid, signal.SIGKILL)
        await _wait(
            lambda: not runtime.supervisor.shard_alive(0), 30,
            "shard 0 death detection",
        )
        await _wait(
            lambda: runtime.supervisor.shard_alive(0), 90,
            "shard 0 supervised restart",
        )
        assert runtime.supervisor.stats()["restarts"] >= 1

        # A's home shard died: its socket and parked state went with
        # it. The client re-handshakes THROUGH THE ROUTER (token from
        # the dead incarnation is simply unknown → fresh session) and
        # re-subscribes its shard-0 world; its shard-1 rows were never
        # touched by the restart.
        a.close()
        peers.remove(a)
        a = await connect(uuid_a, token=a.token)
        assert not a.refused
        # the restarted shard's in-memory subscription index died with
        # it (only records ride the WAL): BOTH subscribers of its
        # world re-subscribe — B's rides the router like any other
        # world-scoped op, proving the restarted shard accepts remote
        # subscribers again
        for c in (a, b):
            await c.send(Message(
                instruction=Instruction.AREA_SUBSCRIBE, world_name=w0,
                position=POS,
            ))
        await asyncio.sleep(0.3)

        # records survived the kill: WAL replay on the restarted
        # shard, read back from BOTH sides of the cluster
        got0 = await wait_record(b, w0, rec0, "rec0 after SIGKILL+replay")
        assert got0.data == "on-shard-0"
        await wait_record(a, w0, rec0, "rec0 direct after replay")
        await wait_record(a, w1, rec1, "rec1 untouched on live shard")

        # cross-shard traffic flows again through the restarted shard
        # (proxy re-adoption replayed by the router)
        await local_roundtrip("post-restart")

        # --- ISSUE 15: federated series stay MONOTONE across the
        # SIGKILL→restart (the restarted shard re-baselines; merged
        # counts only ever grow — no counter-reset sawtooth) ---------
        async def monotone_after_restart():
            text = await asyncio.to_thread(_http_text, metrics_url)
            after = _monotone_series(text)
            for key, value in before_kill.items():
                if key not in after or after[key] < value:
                    return None
            # and the aggregate e2e count moved FORWARD on the
            # post-restart traffic, through the fresh baseline
            if (
                after[("wql_cluster_e2e_seconds_count", "")]
                <= before_kill[("wql_cluster_e2e_seconds_count", "")]
            ):
                return None
            return after

        mono_deadline = time.monotonic() + 30
        after_restart = None
        while time.monotonic() < mono_deadline:
            after_restart = await monotone_after_restart()
            if after_restart:
                break
            await local_roundtrip(f"mono-{int(time.monotonic()*1e3)}")
            await asyncio.sleep(0.7)
        assert after_restart, (
            "federated cluster.* series regressed (or stalled) across "
            "the shard SIGKILL→restart"
        )

        # HTTP /global_message injected at the ROUTER reaches wire
        # subscribers — it rides the private control channel, because
        # the shard's public PULL (rightly) drops nil-sender wire
        # messages as spoofing
        def post_global():
            req = urllib.request.Request(
                f"http://127.0.0.1:{config.http_port}/global_message",
                data=json.dumps({
                    "world_name": w0, "parameter": "http-inject",
                }).encode(),
                headers={"Content-Type": "application/json"},
            )
            return urllib.request.urlopen(req, timeout=10).status

        assert await asyncio.to_thread(post_global) == 204
        await recv_param(a, Instruction.GLOBAL_MESSAGE, "http-inject")

        # router /healthz aggregation sees both shards serving (the
        # router's HTTP runs on THIS loop — fetch off-thread)
        health = await asyncio.to_thread(
            _http_json, f"http://127.0.0.1:{config.http_port}/healthz"
        )
        assert health["cluster"]["alive"] == 2
        assert health["cluster"]["restarts"] >= 1
    finally:
        for peer in peers:
            try:
                peer.close()
            except Exception:
                pass
        await runtime.stop()


def test_cluster_end_to_end(tmp_path):
    """The ISSUE 14 acceptance path, one cluster boot end to end."""
    asyncio.run(asyncio.wait_for(_drain_cluster_e2e(tmp_path), 300))


async def _shed_audit():
    n_shards = 2
    config = Config(
        store_url="memory://",
        http_enabled=False, ws_enabled=False,
        zmq_server_host="127.0.0.1",
        zmq_server_port=_port_block(n_shards + 1),
        spatial_backend="cpu", tick_interval=0.02,
        max_batch=32, overload="on",
        supervisor_backoff=0.005,
        cluster_shards=n_shards,
    )
    world_map = WorldMap(n_shards)
    worlds = [
        _world_for_shard(world_map, i, "scale") for i in range(n_shards)
    ]
    runtime = ClusterRuntime(config)
    await runtime.start()
    peers: list[ZmqPeer] = []
    try:
        async def connect(**kw):
            return await _connect(config.zmq_server_port, peers, **kw)

        flooders = [
            (await connect(), worlds[i % n_shards])
            for i in range(2 * n_shards)
        ]
        for client, world in flooders:
            await client.send(Message(
                instruction=Instruction.AREA_SUBSCRIBE,
                world_name=world, position=POS,
            ))
        # receiver homed on shard 0, world owned by shard 1: every one
        # of its frames crosses the 1→0 ring
        rx = await connect(peer_uuid=_uuid_for_shard(world_map, 0))
        tx = await connect(peer_uuid=_uuid_for_shard(world_map, 1))
        for c in (rx, tx):
            await c.send(Message(
                instruction=Instruction.AREA_SUBSCRIBE,
                world_name=worlds[1], position=POS,
            ))
        await asyncio.sleep(0.3)

        def router_shed() -> int:
            return runtime.metrics.snapshot()["counters"].get(
                "cluster.router_shed_local", 0
            )

        async def flood(client, world, pace_s, until) -> int:
            sent = 0
            while not until():
                for _ in range(16):
                    await client.send(Message(
                        instruction=Instruction.LOCAL_MESSAGE,
                        world_name=world, position=POS, parameter="load",
                    ))
                    sent += 1
                await asyncio.sleep(pace_s)
            return sent

        # balanced: every flooder bursts its own shard's world, and the
        # pair sends across the ring
        end = time.monotonic() + 1.0
        offered = sum(await asyncio.gather(
            *(flood(c, w, 0.002, lambda: time.monotonic() > end)
              for c, w in flooders),
            flood(tx, worlds[1], 0.05, lambda: time.monotonic() > end),
        ))
        # hotspot: the whole fleet converges on shard 0's world until
        # it REJECTs and the refusals move to the router tier
        end = time.monotonic() + 20.0
        offered += sum(await asyncio.gather(*(
            flood(c, worlds[0], 0.001,
                  lambda: router_shed() > 0 or time.monotonic() > end)
            for c, _ in flooders
        )))
        assert router_shed() > 0, (
            "the router tier never shed for a drowning shard"
        )

        def books():
            counters = [
                runtime.supervisor.shard_state(i).get("counters", {})
                for i in range(n_shards)
            ]
            arrived = sum(
                c.get("messages.local_message", 0) for c in counters
            )
            shed_shard = sum(
                c.get("overload.shed_local", 0)
                + c.get("overload.drop_oldest", 0)
                for c in counters
            )
            return arrived, shed_shard, router_shed()

        # shard counters ride ~1 s state pushes: the books close when
        # the last push has landed, and never if a message was lost
        deadline = time.monotonic() + 40
        while True:
            arrived, shed_shard, at_router = books()
            if offered == arrived + at_router or time.monotonic() > deadline:
                break
            await asyncio.sleep(0.1)
        admitted = arrived - shed_shard
        assert offered == admitted + shed_shard + at_router, (
            offered, arrived, shed_shard, at_router
        )
        assert admitted > 0
        latency = runtime.metrics.snapshot()["latency"]
        # the federated histograms the shards close at socket-write-
        # complete advanced, the cross-shard one among them
        assert (latency.get("cluster.e2e_ms") or {}).get("count", 0) > 0
        assert (latency.get("cluster.xshard_ms") or {}).get("count", 0) > 0
    finally:
        for peer in peers:
            try:
                peer.close()
            except Exception:
                pass
        await runtime.stop()


def test_cluster_shed_audit_is_exact_across_both_tiers():
    """A hotspot storm behind the router: every LocalMessage offered is
    admitted by a shard, shed by a shard's governor or shed at the
    router for it: offered == admitted + shed-at-shard + shed-at-router,
    to the message (the router's forward leg loses nothing)."""
    asyncio.run(asyncio.wait_for(_shed_audit(), 180))


# ---------------------------------------------------------------------
# process-free units: placement + shed mirror
# ---------------------------------------------------------------------


def test_world_map_stable_and_covering():
    wm = WorldMap(4)
    worlds = [f"world-{i}" for i in range(64)]
    placed = [wm.shard_of_world(w) for w in worlds]
    assert set(placed) == {0, 1, 2, 3}          # no empty shard at 64 worlds
    assert placed == [WorldMap(4).shard_of_world(w) for w in worlds]
    u = uuid_mod.uuid4()
    assert WorldMap(4).shard_of_peer(u) == WorldMap(4).shard_of_peer(u)
    # world and peer domains are separated: a world named like a hex
    # uuid does not have to co-place with that peer
    assert wm.shard_of_world("@global") in range(4)
    with pytest.raises(ValueError):
        WorldMap(0)


class _TwoShards:
    """All a ``ClusterRouter`` asks of its supervisor before traffic."""
    n_shards = 2

    def ctl_send(self, *a, **k):
        return True


def test_shed_mirror_admission_classes():
    """Router-side admission mirrors the governor's class semantics:
    records/entity/subscribe/control always pass; locals+globals shed
    only at REJECT; new handshakes shed at SHED_HIGH+."""
    from worldql_server_tpu.cluster.router import ClusterRouter

    config = Config(ws_enabled=False, zmq_enabled=True,
                    cluster_shards=2, http_enabled=False)
    router = ClusterRouter(config, _TwoShards())

    def admit(instruction, level, **kwargs):
        router.mirror.levels[0] = level
        message = Message(instruction=instruction, **kwargs)
        return router._admit(message, instruction, 0)

    # records and subscriptions always pass, even in REJECT
    for instr in (Instruction.RECORD_CREATE, Instruction.RECORD_READ,
                  Instruction.AREA_SUBSCRIBE, Instruction.HEARTBEAT):
        assert admit(instr, 3)
    # locals/globals pass below REJECT, shed at REJECT (counted)
    assert admit(Instruction.LOCAL_MESSAGE, 2)
    assert not admit(Instruction.LOCAL_MESSAGE, 3)
    assert not admit(Instruction.GLOBAL_MESSAGE, 3)
    counters = router.metrics.snapshot()["counters"]
    assert counters["cluster.router_shed_local"] == 1
    assert counters["cluster.router_shed_global"] == 1
    # entity updates never shed at the router
    from worldql_server_tpu.protocol.types import Entity

    assert admit(Instruction.LOCAL_MESSAGE, 3,
                 entities=[Entity(uuid=uuid_mod.uuid4())])
    # new handshakes shed at SHED_HIGH; resumes (flex token) ride
    assert admit(Instruction.HANDSHAKE, 1)
    assert not admit(Instruction.HANDSHAKE, 2)
    assert admit(Instruction.HANDSHAKE, 2, flex=b"token")
    router.ctx.destroy(linger=0)


@pytest.mark.parametrize("left_open", ["orphan", "refusal"])
def test_router_stop_closes_a_socket_that_no_list_names(left_open):
    """``stop()`` ends the context with every socket made from it, not
    only ``_pull`` and ``_push``: one that nothing files, and a refusal
    hint's, whose task ``stop()`` cancels but does not wait for. A bare
    ``term()`` waited for either for ever, on the loop's own thread."""
    import zmq

    from tests.client_util import free_port, stops_on_a_thread
    from worldql_server_tpu.cluster.router import ClusterRouter

    async def scenario(stopping):
        config = Config(ws_enabled=False, zmq_enabled=True,
                        cluster_shards=2, http_enabled=False,
                        zmq_server_host="127.0.0.1",
                        zmq_server_port=free_port())
        router = ClusterRouter(config, _TwoShards())
        await router.start()
        if left_open == "orphan":
            held = router.ctx.socket(zmq.PUSH)
        else:
            # the hint's PUSH gets no pipe before its peer listens,
            # and nobody ever does: its send waits
            router.ctx.setsockopt(zmq.IMMEDIATE, 1)
            router._send_refusal(Message(
                instruction=Instruction.HANDSHAKE,
                parameter=f"127.0.0.1:{free_port()}"))
            await asyncio.sleep(0.1)
            [held] = router._refusals
            assert not held.done()
        stopping.set()
        await router.stop()
        return router.ctx.closed, held

    closed, held = stops_on_a_thread(scenario)
    assert closed
    assert held.closed if left_open == "orphan" else held.cancelled()
