"""Overload-storm chaos suite (ISSUE 10 acceptance, CI-gated).

Two legs:

* **in-process storm** — a real server over real ZMQ takes sustained
  offered load far beyond what its (deliberately tiny) tick budget can
  drain: the process must stay up and answering, the ticker queue must
  stay bounded by the admission cap, record ops must all land with a
  sane p99 (never shed), every shed message must be accounted
  (counters == the storm audit, exactly), and the governor must walk
  back to OK within its documented recovery window once load drops;
* **SIGKILL mid-storm** — a subprocess server with the WAL on is
  stormed while a client streams record creates and CONFIRMS them via
  RecordRead replies (read-your-writes = acked and visible); SIGKILL
  mid-storm, reboot on the same store+WAL, and every confirmed record
  must be served — zero acked-write loss while the overload plane was
  actively shedding around the record class.
"""

import asyncio
import json
import os
import signal
import subprocess
import sys
import time
import urllib.request
import uuid

import pytest

from worldql_server_tpu.engine.config import Config
from worldql_server_tpu.engine.server import WorldQLServer
from worldql_server_tpu.protocol.types import (
    Instruction,
    Message,
    Record,
    Vector3,
)
from worldql_server_tpu.robustness import failpoints
from worldql_server_tpu.robustness.overload import OK

from tests.client_util import ZmqClient, free_port

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout))


@pytest.fixture(autouse=True)
def clean_failpoints():
    failpoints.registry.reset()
    yield
    failpoints.registry.reset()


async def try_connect(port, attempts=100):
    for _ in range(attempts):
        try:
            return await asyncio.wait_for(ZmqClient.connect(port), 1.0)
        except Exception:
            await asyncio.sleep(0.05)
    raise AssertionError("could not connect a zmq client")


def storm_config(**overrides) -> Config:
    """Tiny tick budget + tiny admitted floor: any sustained flood
    busts the deadline, degrades the tier, and fills the queue — the
    10x-regime shape scaled to a 1-core CI container."""
    config = Config(
        store_url="memory://",
        http_enabled=True, http_host="127.0.0.1", http_port=free_port(),
        ws_enabled=False,
        zmq_server_host="127.0.0.1", zmq_server_port=free_port(),
        spatial_backend="cpu", tick_interval=0.02,
        max_batch=64, overload="on",
        overload_tick_budget_ms=0.5, overload_min_batch=8,
        overload_deadline_k=2, overload_recover_ticks=5,
        overload_rss_limit_mb=8192,
        trace=True,  # loop monitor: the bounded-lag evidence
        supervisor_backoff=0.005,
    )
    for k, v in overrides.items():
        setattr(config, k, v)
    return config


async def _fetch_json(port, path):
    def get():
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5
        ) as resp:
            return json.loads(resp.read())

    return await asyncio.to_thread(get)


def test_overload_storm_survival_accounting_recovery():
    async def scenario():
        server = WorldQLServer(storm_config())
        await server.start()
        gov = server.governor
        try:
            port = server.config.zmq_server_port
            flooders = [await try_connect(port) for _ in range(2)]

            offered = 0
            record_walls = []

            async def flood(client, duration):
                nonlocal offered
                end = time.perf_counter() + duration
                i = 0
                while time.perf_counter() < end:
                    await client.send(Message(
                        instruction=Instruction.LOCAL_MESSAGE,
                        world_name="world",
                        position=Vector3(1.0, 1.0, 1.0),
                        parameter=f"s{i}",
                    ))
                    offered += 1
                    i += 1

            async def record_ops(n):
                # record ops ride THROUGH the storm: never shed, and
                # their handler latency stays sane
                for i in range(n):
                    t0 = time.perf_counter()
                    await server.router.handle_message(Message(
                        instruction=Instruction.RECORD_CREATE,
                        sender_uuid=uuid.uuid4(), world_name="w",
                        records=[Record(
                            uuid=uuid.UUID(int=i + 1),
                            position=Vector3(1, 2, 3),
                            world_name="w", data=f"r{i}",
                        )],
                    ))
                    record_walls.append(time.perf_counter() - t0)
                    await asyncio.sleep(0.02)

            await asyncio.gather(
                *(flood(c, 1.5) for c in flooders), record_ops(40),
            )

            # SURVIVAL mid-pressure: health answers and reports the
            # governor; the queue gauge sits within the admission cap
            health = await _fetch_json(server.config.http_port, "/healthz")
            assert "overload" in health
            assert health["overload"]["queue_depth"] <= 2 * 64

            # the storm actually pressured the governor
            assert gov.peak_level >= 1, "storm never escalated the governor"
            shed_total = (
                gov.drop_oldest + gov.shed["local"] + gov.rate_limited
            )
            assert shed_total > 0, "storm shed nothing — not a real storm"

            # drain: stop offering, let the recv loop and the pump chew
            # through the rest (the recv loop gives the event loop a
            # turn every 10 ms of backlog, so the socket can still hold
            # messages when the flooders are done)
            for _ in range(1500):
                if (
                    server.metrics.counters["messages.local_message"]
                    == offered
                    and not server.ticker._queue
                    and not server.ticker._flushing.locked()
                ):
                    break
                await asyncio.sleep(0.01)
            assert not server.ticker._queue

            # ACCOUNTING, exactly: every local the router saw either
            # flushed through a tick, was dropped-oldest from the
            # queue, or was refused at the door. offered-over-the-wire
            # equals router-seen (libzmq loses nothing below HWM
            # backpressure, and the flooders awaited every send).
            counters = server.metrics.snapshot()["counters"]
            seen = counters["messages.local_message"]
            assert seen == offered
            flushed = counters.get("tick.messages", 0)
            assert seen == flushed + gov.drop_oldest + gov.shed["local"]
            # the same numbers the audit used are the exported ones
            assert counters.get("overload.drop_oldest", 0) == gov.drop_oldest
            assert (
                counters.get("overload.shed_local", 0) == gov.shed["local"]
            )

            # RECORD CLASS: all 40 landed (never shed), p99 sane
            assert counters["messages.record_create"] == 40
            rows = await server.router.durability.get_records_in_region(
                "w", Vector3(1, 2, 3)
            )
            assert len({sr.record.uuid for sr in rows}) == 40
            record_walls.sort()
            p99 = record_walls[int(len(record_walls) * 0.99) - 1]
            assert p99 < 0.5, f"record-op p99 {p99:.3f}s under storm"

            # BOUNDED LAG + RSS: the loop stayed schedulable and the
            # governor's memory signal stayed far from its ceiling.
            # The bound catches unbounded stalls, not scheduler jitter:
            # under full-suite load on a 1-core container the storm's
            # max lag has been observed at ~5.1s (standalone: <1s), so
            # leave headroom above that while still failing hard on a
            # genuinely wedged loop.
            assert server.loop_monitor.max_lag_ms < 10_000
            status = gov.status()
            assert 0 < status["rss_mb"] < 8192

            # RECOVERY: back to OK within the documented window
            # (3 x recover_ticks ticks of the 20 ms pump, plus slack)
            for _ in range(400):
                if gov.state == OK and not gov.degraded():
                    break
                await asyncio.sleep(0.02)
            assert gov.state == OK, f"stuck in {gov.state} after the storm"
            assert gov.admitted_batch == 64  # tier restored

            # and the broker still serves: clean heartbeat roundtrip
            probe = await try_connect(port)
            await probe.send(Message(instruction=Instruction.HEARTBEAT))
            assert await probe.recv_until(Instruction.HEARTBEAT, 5.0)
            await probe.close()
        finally:
            for c in flooders:
                try:
                    await c.close()
                except Exception:
                    pass
            await server.stop()

    run(scenario())


# region: SIGKILL mid-storm (subprocess + WAL replay)


def _spawn_server(tmp_path, port, http_port):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",  # never let a child probe the TPU plugin
        WQL_DEVICE_DEFAULTS="0",
    )
    return subprocess.Popen(
        [
            sys.executable, "-m", "worldql_server_tpu",
            "--spatial-backend", "cpu", "--tick-interval", "0.02",
            "--max-batch", "64", "--overload", "on",
            "--overload-tick-budget-ms", "0.5",
            "--overload-min-batch", "8", "--overload-deadline-k", "2",
            "--durability", "wal",
            "--wal-dir", str(tmp_path / "wal"),
            "--store-url", f"sqlite://{tmp_path}/storm.db",
            "--checkpoint-interval", "0.25",
            "--no-ws", "--http-host", "127.0.0.1",
            "--http-port", str(http_port),
            "--zmq-server-host", "127.0.0.1",
            "--zmq-server-port", str(port),
        ],
        cwd=ROOT, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


def test_sigkill_mid_storm_zero_acked_write_loss(tmp_path):
    """Acked = CONFIRMED over the wire: a record only enters the
    verification set once a RecordRead reply served it (the WAL fsync
    acked it and read-your-writes surfaced it). SIGKILL lands while
    the flood still runs and checkpoints race the WAL — the reboot
    must serve every confirmed record from store+WAL replay alone."""
    port, http_port = free_port(), free_port()
    proc = _spawn_server(tmp_path, port, http_port)
    confirmed: set = set()

    async def storm_and_kill():
        flooder = await try_connect(port)
        writer = await try_connect(port)
        # overload plane is live on this boot (probed before the flood
        # monopolizes the 1-core container's scheduler)
        health = await _fetch_json(http_port, "/healthz")
        assert "overload" in health
        region = Vector3(1, 2, 3)
        stop_flood = False

        async def flood():
            i = 0
            while not stop_flood:
                try:
                    await flooder.send(Message(
                        instruction=Instruction.LOCAL_MESSAGE,
                        world_name="world", position=Vector3(1, 1, 1),
                        parameter=f"s{i}",
                    ))
                except Exception:
                    return  # the SIGKILL landed mid-send
                i += 1
                # a send the socket takes at once never yields: give
                # the writer its turn (a server that drained as fast
                # as this loop sent starved it for the case's 120 s)
                await asyncio.sleep(0)

        async def write_and_confirm():
            for i in range(60):
                await writer.send(Message(
                    instruction=Instruction.RECORD_CREATE,
                    world_name="w",
                    records=[Record(
                        uuid=uuid.UUID(int=i + 1), position=region,
                        world_name="w", data=f"r{i}",
                    )],
                ))
                if i % 5 == 4:
                    await writer.send(Message(
                        instruction=Instruction.RECORD_READ,
                        world_name="w", position=region,
                    ))
                    try:
                        reply = await writer.recv_until(
                            Instruction.RECORD_REPLY, 5.0
                        )
                        confirmed.update(r.uuid for r in reply.records)
                    except asyncio.TimeoutError:
                        pass
                await asyncio.sleep(0.01)

        flood_task = asyncio.ensure_future(flood())
        await write_and_confirm()
        proc.kill()  # SIGKILL, mid-storm — no drain, no checkpoint
        stop_flood = True
        # the dead server stops pulling: the flooder's PUSH can wedge
        # at its HWM mid-send — cancel, don't wait
        flood_task.cancel()
        try:
            await flood_task
        except (asyncio.CancelledError, Exception):
            pass
        await flooder.close()
        await writer.close()

    try:
        run(storm_and_kill())
        proc.wait(timeout=10)
        assert confirmed, "no record was ever confirmed — not a real run"

        # reboot on the same store + WAL: replay must restore every
        # confirmed (read-acked) record
        port2, http2 = free_port(), free_port()
        proc2 = _spawn_server(tmp_path, port2, http2)
        try:
            async def verify():
                client = await try_connect(port2)
                await client.send(Message(
                    instruction=Instruction.RECORD_READ,
                    world_name="w", position=Vector3(1, 2, 3),
                ))
                reply = await client.recv_until(
                    Instruction.RECORD_REPLY, 10.0
                )
                present = {r.uuid for r in reply.records}
                await client.close()
                lost = confirmed - present
                assert not lost, (
                    f"acked records lost across SIGKILL+replay: {lost}"
                )

            run(verify())
        finally:
            proc2.send_signal(signal.SIGTERM)
            try:
                proc2.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc2.kill()
                proc2.wait(timeout=10)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


# endregion
