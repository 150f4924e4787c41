"""Seeded chaos suite (ISSUE 4 acceptance): failpoints firing at every
instrumented boundary of a full WorldQLServer, asserting

* the process SURVIVES (still serves after the storm),
* no acked record write is lost (PR 2's recovery invariants: stop,
  reboot on the same WAL/store, every acked insert is served),
* every injected fault is accounted for in metrics (the ``failpoints``
  gauge must equal the registry's audit, and each boundary fired),
* killing the ticker pump or ZMQ recv loop triggers the documented
  supervisor policy — restart with backoff, then escalation — visible
  in /metrics and /healthz.

Two phases inside the smoke: a DETERMINISTIC sweep arming one boundary
at a time (proves each site is live and contained), then a seeded
probabilistic storm over the full spec (proves the combination holds).
The long randomized variant is marked ``slow``.
"""

import asyncio
import json
import urllib.request
import uuid

import pytest

from tests.client_util import ZmqClient, free_port
from worldql_server_tpu.engine.config import Config
from worldql_server_tpu.engine.server import WorldQLServer
from worldql_server_tpu.protocol import Instruction, Message
from worldql_server_tpu.protocol.types import Record, Vector3
from worldql_server_tpu.robustness import failpoints

#: the probabilistic storm: every boundary armed at once (loop-killing
#: points ride the deterministic sweep instead — they exhaust restart
#: budgets, which the escalation tests cover on purpose)
STORM_SPEC = (
    "wal.append=error:0.15,"
    "wal.fsync=delay:1ms:0.5,"
    "durability.apply=error:0.25,"
    "backend.dispatch=error:0.3,"
    "backend.collect=error:0.3,"
    "router.dispatch=error:0.1,"
    "codec.decode=error:0.2,"
    "transport.send=error:0.5"
)


def run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout))


@pytest.fixture(autouse=True)
def clean_global_registry():
    failpoints.registry.reset()
    yield
    failpoints.registry.reset()


async def wait_for(predicate, timeout=5.0, interval=0.01):
    for _ in range(int(timeout / interval)):
        if predicate():
            return True
        await asyncio.sleep(interval)
    return predicate()


def chaos_config(tmp_path, **overrides) -> Config:
    config = Config(
        store_url=f"sqlite://{tmp_path}/chaos.db",
        durability="wal",
        wal_dir=str(tmp_path / "wal"),
        checkpoint_interval=0.25,   # checkpoints run DURING the chaos
        http_enabled=True, http_host="127.0.0.1", http_port=free_port(),
        ws_enabled=False,
        zmq_server_host="127.0.0.1", zmq_server_port=free_port(),
        tick_interval=0.02,
        spatial_backend="cpu",
        resilience="on", failover_after=100,
        supervisor_budget=20, supervisor_backoff=0.005,
    )
    for k, v in overrides.items():
        setattr(config, k, v)
    return config


def make_record(i: int, pos: Vector3) -> Record:
    return Record(
        uuid=uuid.UUID(int=i + 1), position=pos,
        world_name="w", data=f"payload-{i}",
    )


async def fetch_json(port, path):
    def get():
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}"
        ) as resp:
            return json.loads(resp.read())

    return await asyncio.to_thread(get)


async def try_connect(port, attempts=30):
    for _ in range(attempts):
        try:
            return await asyncio.wait_for(ZmqClient.connect(port), 1.0)
        except Exception:
            await asyncio.sleep(0.02)
    raise AssertionError("could not connect a zmq client")


async def heartbeat_roundtrip(client, timeout=2.0):
    await client.send(Message(instruction=Instruction.HEARTBEAT))
    return await client.recv_until(Instruction.HEARTBEAT, timeout)


# region: deterministic boundary sweep


async def _sweep_boundaries(server, port):
    """Arm each instrumented boundary once (error, exactly one fire)
    and drive an op through it: each fault must fire, be contained (or
    follow its documented policy), and leave the server serving."""
    reg = failpoints.registry
    durability = server.router.durability
    listener = uuid.uuid4()
    server.backend.add_subscription("world", listener, Vector3(5, 5, 5))

    async def local_message(tag):
        await server.router.handle_message(Message(
            instruction=Instruction.LOCAL_MESSAGE, sender_uuid=uuid.uuid4(),
            world_name="world", position=Vector3(5, 5, 5), parameter=tag,
        ))

    # wal.append: the handler sees the failure; the op still reaches
    # the store through the queue (at-least-once, never acked-lost)
    reg.set("wal.append", "error:1:x1")
    with pytest.raises(failpoints.FailpointError):
        await durability.insert_records([make_record(9000, Vector3(1, 2, 3))])
    assert reg.fired("wal.append") == 1

    # wal.fsync delay: acked, just slower
    reg.set("wal.fsync", "delay:10ms:x1")
    await durability.insert_records([make_record(9001, Vector3(1, 2, 3))])
    assert await wait_for(lambda: reg.fired("wal.fsync") == 1)

    # durability.apply: the write-behind batch is dropped → WAL
    # truncation blocked → boot-time replay re-applies (asserted by
    # the caller after reboot)
    reg.set("durability.apply", "error:1:x1")
    await durability.insert_records([make_record(9002, Vector3(1, 2, 3))])
    assert await wait_for(lambda: reg.fired("durability.apply") == 1)
    assert await wait_for(lambda: durability.dropped_batches >= 1)

    # backend dispatch + collect: contained by ResilientBackend, tick
    # keeps delivering (mirror fallback)
    reg.set("backend.dispatch", "error:1:x1")
    await local_message("t-dispatch")
    assert await wait_for(lambda: reg.fired("backend.dispatch") == 1)
    reg.set("backend.collect", "error:1:x1")
    await local_message("t-collect")
    assert await wait_for(lambda: reg.fired("backend.collect") == 1)
    assert server.backend.failed_over is False  # contained, not failed over

    # router.dispatch: the message is dropped inside handle_message's
    # containment and counted
    errors_before = server.metrics.counters["messages.errors"]
    reg.set("router.dispatch", "error:1:x1")
    await local_message("t-router")
    assert reg.fired("router.dispatch") == 1
    assert server.metrics.counters["messages.errors"] == errors_before + 1

    # codec.decode: one inbound zmq message dropped + counted; the
    # loop survives
    client = await try_connect(port)
    reg.set("codec.decode", "error:1:x1")
    await client.send(Message(instruction=Instruction.HEARTBEAT))
    assert await wait_for(lambda: reg.fired("codec.decode") == 1)
    assert await wait_for(
        lambda: server.metrics.counters["zmq.recv_errors"] >= 1
    )
    assert await heartbeat_roundtrip(client) is not None

    # zmq.recv: kills the recv LOOP itself → supervisor restarts it →
    # the transport keeps serving
    reg.set("zmq.recv", "error:1:x1")
    await client.send(Message(instruction=Instruction.HEARTBEAT))
    assert await wait_for(lambda: reg.fired("zmq.recv") == 1)
    assert await wait_for(
        lambda: server.supervisor.get("zmq-recv").restarts >= 1
    )
    assert await heartbeat_roundtrip(client) is not None

    # ticker.pump: kills the pump → supervisor restarts → ticking
    # resumes
    reg.set("ticker.pump", "error:1:x1")
    assert await wait_for(lambda: reg.fired("ticker.pump") == 1)
    assert await wait_for(
        lambda: server.supervisor.get("tick-batcher").restarts >= 1
    )

    # transport.send: a failed outbound send evicts THAT peer (failed-
    # send semantics) and nothing else
    victim = await try_connect(port)
    reg.set("transport.send", "error:1:x1")
    for _ in range(50):
        try:
            await victim.send(Message(instruction=Instruction.HEARTBEAT))
        except Exception:
            pass
        if failpoints.registry.fired("transport.send") >= 1:
            break
        await asyncio.sleep(0.02)
    assert reg.fired("transport.send") == 1
    assert await wait_for(
        lambda: server.metrics.counters["peers.evicted_send_failed"] >= 1
    )
    await victim.close()

    reg.clear()  # disarm (audit counts survive for the accounting check)
    assert await heartbeat_roundtrip(client) is not None
    await client.close()

    return {
        "wal.append", "wal.fsync", "durability.apply", "backend.dispatch",
        "backend.collect", "router.dispatch", "codec.decode", "zmq.recv",
        "ticker.pump", "transport.send",
    }


# endregion

# region: probabilistic storm


async def _storm(server, port, seed, n_records, duration):
    """Seeded storm over STORM_SPEC: record traffic + tick traffic +
    zmq chatter while every boundary misbehaves probabilistically.
    Returns the set of acked insert uuids never touched by a delete."""
    failpoints.registry.configure(STORM_SPEC, seed=seed)
    durability = server.router.durability
    listener = uuid.uuid4()
    server.backend.add_subscription("world", listener, Vector3(5, 5, 5))
    regions = [Vector3(8.0 + 40.0 * r, 2.0, 3.0) for r in range(4)]

    clients = []
    for _ in range(2):
        try:
            clients.append(
                await asyncio.wait_for(ZmqClient.connect(port), 1.0)
            )
        except Exception:
            pass  # chaotic handshake loss is part of the exercise

    acked, delete_touched = set(), set()
    for i in range(n_records):
        rec = make_record(i, regions[i % len(regions)])
        try:
            await durability.insert_records([rec])
            acked.add(rec.uuid)
        except Exception:
            pass
        if i % 7 == 3:
            candidates = sorted(acked - delete_touched, key=lambda u: u.int)
            if candidates:
                victim_uuid = candidates[0]
                victim = make_record(
                    victim_uuid.int - 1, regions[(victim_uuid.int - 1) % 4]
                )
                delete_touched.add(victim_uuid)
                try:
                    await durability.delete_records([victim])
                except Exception:
                    pass
        if i % 4 == 0:
            try:
                await server.router.handle_message(Message(
                    instruction=Instruction.LOCAL_MESSAGE,
                    sender_uuid=uuid.uuid4(), world_name="world",
                    position=Vector3(5, 5, 5), parameter=f"storm-{i}",
                ))
            except Exception:
                pass
            for c in clients:
                try:
                    await c.send(
                        Message(instruction=Instruction.HEARTBEAT)
                    )
                except Exception:
                    pass
        if i % 16 == 0:
            await asyncio.sleep(duration / (n_records / 16))

    # health is answerable mid-chaos and reflects the supervised state
    health = await fetch_json(server.config.http_port, "/healthz")
    assert health["durability"]["mode"] == "wal"
    assert "tasks_unhealthy" in health
    assert "tick-batcher" in health["supervisor"]["tasks"]

    for c in clients:
        try:
            await c.close()
        except Exception:
            pass
    failpoints.registry.clear()
    return acked - delete_touched


# endregion


def test_chaos_smoke(tmp_path):
    """The CI chaos gate: deterministic boundary sweep + seeded storm,
    then the three acceptance invariants (survival, accounting,
    zero acked-write loss across a reboot)."""
    acked_survivors = set()
    swept = set()

    async def serve_chaos():
        server = WorldQLServer(chaos_config(tmp_path))
        await server.start()
        try:
            port = server.config.zmq_server_port
            swept.update(await _sweep_boundaries(server, port))
            acked_survivors.update(
                await _storm(server, port, seed=1234,
                             n_records=120, duration=0.8)
            )

            # SURVIVAL: with everything disarmed, a fresh client gets a
            # clean heartbeat roundtrip
            client = await try_connect(port)
            assert await heartbeat_roundtrip(client) is not None
            await client.close()

            # ACCOUNTING: every injected fault is visible in /metrics —
            # the failpoints gauge must equal the registry's audit, and
            # every boundary the sweep armed actually fired
            snap = server.metrics.snapshot()
            gauge = snap["gauges"]["failpoints"]
            assert gauge == failpoints.registry.fired_counts()
            for name in swept:
                assert gauge.get(name, 0) >= 1, f"{name} never fired"
            # the storm must also have injected real faults
            assert sum(gauge.values()) > len(swept)
        finally:
            await server.stop()

    run(serve_chaos())
    assert acked_survivors, "storm acked nothing — not a real exercise"

    async def reboot_and_verify():
        # ZERO ACKED-WRITE LOSS: a fresh boot on the same store+WAL
        # replays whatever the storm dropped (durability.apply faults
        # blocked WAL truncation), and every acked insert that no
        # delete ever touched is served
        server = WorldQLServer(chaos_config(tmp_path, checkpoint_interval=0))
        await server.start()
        try:
            assert server.last_recovery is not None
            present = set()
            for r in range(4):
                rows = await server.router.durability.get_records_in_region(
                    "w", Vector3(8.0 + 40.0 * r, 2.0, 3.0)
                )
                present.update(sr.record.uuid for sr in rows)
            # the deterministic sweep's acked records too (9001: fsync
            # delay; 9002: dropped apply batch — exists ONLY via replay)
            rows = await server.router.durability.get_records_in_region(
                "w", Vector3(1, 2, 3)
            )
            present.update(sr.record.uuid for sr in rows)
            lost = acked_survivors - present
            assert not lost, f"acked writes lost across reboot: {lost}"
            assert uuid.UUID(int=9002) in present
            assert uuid.UUID(int=9003) in present
        finally:
            await server.stop()

    run(reboot_and_verify())


def test_ticker_escalation_policy(tmp_path):
    """Killing the ticker pump repeatedly: restart-with-backoff until
    the budget is gone, then escalation — visible in /metrics,
    /healthz, and the server's shutdown request."""

    async def scenario():
        config = chaos_config(
            tmp_path, zmq_enabled=False, durability="off",
            store_url="memory://", supervisor_budget=2,
        )
        server = WorldQLServer(config)
        await server.start()
        try:
            failpoints.registry.set("ticker.pump", "error")
            await asyncio.wait_for(server.shutdown_requested.wait(), 15)
            failpoints.registry.clear()

            st = server.supervisor.get("tick-batcher")
            assert st.state == "failed"
            assert st.restarts == 2 and st.crashes == 3
            counters = server.metrics.counters
            assert counters["supervisor.restarts.tick-batcher"] == 2
            assert counters["supervisor.escalations"] == 1
            assert counters["server.escalations"] == 1

            health = await fetch_json(config.http_port, "/healthz")
            assert health["status"] == "degraded"
            assert health["tasks_unhealthy"] == 1
            assert health["supervisor"]["tasks"]["tick-batcher"]["state"] \
                == "failed"
        finally:
            await server.stop()

    run(scenario())


def test_zmq_recv_escalation_policy(tmp_path):
    """Same policy for the ZMQ recv loop: a permanently-crashing recv
    loop must escalate instead of leaving a deaf transport up."""

    async def scenario():
        config = chaos_config(
            tmp_path, durability="off", store_url="memory://",
            tick_interval=0, supervisor_budget=1,
        )
        server = WorldQLServer(config)
        await server.start()
        try:
            failpoints.registry.set("zmq.recv", "error")
            await asyncio.wait_for(server.shutdown_requested.wait(), 15)
            failpoints.registry.clear()

            st = server.supervisor.get("zmq-recv")
            assert st.state == "failed"
            assert st.restarts == 1
            assert server.metrics.counters["supervisor.escalations"] == 1
            health = await fetch_json(config.http_port, "/healthz")
            assert health["status"] == "degraded"
            assert health["tasks_unhealthy"] == 1
        finally:
            await server.stop()

    run(scenario())


def test_inline_store_boundaries_off_and_boot(tmp_path):
    """The off/sync-mode store boundaries: store.init fails the boot
    loudly; store.insert/store.delete failures are contained by the
    router handler exactly like real store errors."""

    async def boot_fails():
        failpoints.registry.set("store.init", "error:1:x1")
        server = WorldQLServer(Config(
            store_url="memory://", http_enabled=False, ws_enabled=False,
            zmq_enabled=False,
        ))
        with pytest.raises(failpoints.FailpointError):
            await server.start()
        assert failpoints.registry.fired("store.init") == 1

    run(boot_fails())
    failpoints.registry.reset()

    async def handlers_contain():
        server = WorldQLServer(Config(
            store_url="memory://", http_enabled=False, ws_enabled=False,
            zmq_enabled=False,
        ))
        await server.start()
        try:
            failpoints.registry.set("store.insert", "error:1:x1")
            failpoints.registry.set("store.delete", "error:1:x1")
            rec = make_record(1, Vector3(1, 2, 3))
            for instruction in (
                Instruction.RECORD_CREATE, Instruction.RECORD_DELETE,
            ):
                await server.router.handle_message(Message(
                    instruction=instruction, sender_uuid=uuid.uuid4(),
                    world_name="w", records=[rec],
                ))
            assert failpoints.registry.fired("store.insert") == 1
            assert failpoints.registry.fired("store.delete") == 1
            # contained: the next create goes through inline
            failpoints.registry.clear()
            await server.router.durability.insert_records([rec])
            rows = await server.router.durability.get_records_in_region(
                "w", Vector3(1, 2, 3)
            )
            assert [sr.record.uuid for sr in rows] == [rec.uuid]
        finally:
            await server.stop()

    run(handlers_contain())


@pytest.mark.slow
@pytest.mark.parametrize("seed", [1, 77, 20260804])
def test_chaos_randomized_long(tmp_path, seed):
    """Longer randomized storms across seeds: same survival +
    accounting + zero-acked-loss invariants, more records, more wall
    time. Not part of tier-1 (marked slow); CI runs the smoke."""
    wal_tmp = tmp_path / f"s{seed}"
    wal_tmp.mkdir()
    survivors = set()

    async def serve():
        server = WorldQLServer(chaos_config(wal_tmp))
        await server.start()
        try:
            survivors.update(await _storm(
                server, server.config.zmq_server_port, seed=seed,
                n_records=600, duration=4.0,
            ))
            client = await try_connect(server.config.zmq_server_port)
            assert await heartbeat_roundtrip(client) is not None
            await client.close()
            snap = server.metrics.snapshot()
            assert snap["gauges"]["failpoints"] == \
                failpoints.registry.fired_counts()
            assert sum(snap["gauges"]["failpoints"].values()) > 0
        finally:
            await server.stop()

    run(serve(), timeout=300)

    async def verify():
        server = WorldQLServer(
            chaos_config(wal_tmp, checkpoint_interval=0)
        )
        await server.start()
        try:
            present = set()
            for r in range(4):
                rows = await server.router.durability.get_records_in_region(
                    "w", Vector3(8.0 + 40.0 * r, 2.0, 3.0)
                )
                present.update(sr.record.uuid for sr in rows)
            lost = survivors - present
            assert not lost, f"acked writes lost: {lost}"
        finally:
            await server.stop()

    run(verify(), timeout=120)
