"""Clustered columnar fast path (closes PR 15's KNOWN GAP).

The router stamps every router→shard forward with a 20-byte WQTX
trace prefix (cluster/tracectx.py). The native entity classifier
sees bare wire bytes only — a prefixed buffer fails classification,
which used to push every clustered entity update onto the object
path. The fix strips the prefix in the shard's recv loop BEFORE the
batch reaches ``ColumnarIngest.process_batch``, carries the trace
context alongside for slow-routed messages, and counts each stripped
frame (``zmq.ctx_unwrapped``) so the fast-path-through-router claim
is measurable, not assumed.
"""

import asyncio
import uuid

import pytest

from tests.client_util import ZmqClient, free_port
from worldql_server_tpu.cluster import tracectx
from worldql_server_tpu.cluster.resharding import FENCE_MAGIC
from worldql_server_tpu.engine.config import Config
from worldql_server_tpu.engine.server import WorldQLServer
from worldql_server_tpu.protocol import (
    Instruction,
    Message,
    deserialize_message,
    entity_wire,
    serialize_message,
)
from worldql_server_tpu.protocol.types import Entity, Vector3


def run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def ent_msg(sender, entities, world="w", parameter=None):
    return Message(
        instruction=Instruction.LOCAL_MESSAGE, sender_uuid=sender,
        world_name=world, parameter=parameter, entities=entities,
    )


@pytest.fixture(scope="module")
def wire() -> entity_wire.EntityWire:
    ew = entity_wire.load()
    assert ew is not None, "native entity codec failed to load"
    return ew


def test_wqtx_prefix_defeats_classifier_and_strip_restores_it(wire):
    """The gap's mechanics, pinned: the SAME entity update classifies
    fast bare, slow with the router prefix, and fast again after
    ``tracectx.unwrap`` — byte-identical columns both fast times."""
    sender, ent = uuid.uuid4(), uuid.uuid4()
    data = serialize_message(ent_msg(sender, [Entity(
        uuid=ent, position=Vector3(1, 2, 3), world_name="w",
    )]))
    wrapped = tracectx.wrap(data, trace_id=tracectx.new_trace_id(),
                            t_ingress_ns=123456)

    bare = wire.decode([data])
    assert bare.status.tolist() == [1]

    through_router = wire.decode([wrapped])
    assert through_router.status.tolist() == [0], \
        "prefixed bytes must NOT classify (conservative, correct)"

    trace_id, t_ctx, stripped = tracectx.unwrap(wrapped)
    assert trace_id != 0 and t_ctx == 123456 and stripped == data
    restored = wire.decode([stripped])
    assert restored.status.tolist() == [1]
    assert bytes(restored.sender_keys[0]) == bytes(bare.sender_keys[0])
    assert bytes(restored.uuid_keys[0]) == bytes(bare.uuid_keys[0])


class _ShardStub:
    """The minimal cluster surface the transport + teardown touch.

    Installed AFTER server.start(), so the ticker (which captured
    cluster=None at construction) never drains through it — only the
    recv loop's unwrap/fence/staleness hooks and the peer-teardown
    hook are live, which is exactly the surface under test. Mirrors
    ClusterShardExtension: epoch-aware unwrap (v1/bare frames decode
    as epoch 0), no fences in flight, nothing ever stale."""

    unwrap = staticmethod(tracectx.unwrap_epoch)
    FENCE_MAGIC = FENCE_MAGIC

    def frame_stale(self, epoch: int) -> bool:
        return False

    def on_fence(self, payload: bytes) -> None:
        raise AssertionError("no fence frames in this test")

    def on_peer_torn_down(self, peer_uuid) -> None:
        pass

    async def stop(self) -> None:
        pass


async def shard_server():
    """A started ``--entity-sim`` server that believes it is a cluster
    shard (router-framed bytes on its PULL), and its config."""
    config = Config()
    config.store_url = "memory://"
    config.http_enabled = False
    config.ws_enabled = False
    config.zmq_server_port = free_port()
    config.zmq_server_host = "127.0.0.1"
    config.spatial_backend = "tpu"
    config.tick_interval = 0.03
    config.entity_sim = True
    config.entity_k = 4
    server = WorldQLServer(config)
    await server.start()
    server.cluster = _ShardStub()
    return server, config


def test_router_framed_updates_keep_columnar_fast_path():
    """e2e over real ZMQ: WQTX-wrapped entity updates (as the router
    would forward them) ride the columnar fast path — fast_messages
    advances, rows stage, zmq.ctx_unwrapped counts every stripped
    frame — and neighbor frames keep serving."""

    async def scenario():
        server, config = await shard_server()
        try:
            ingest = server.entity_ingest
            assert ingest is not None and ingest.active
            a = await ZmqClient.connect(config.zmq_server_port)
            b = await ZmqClient.connect(config.zmq_server_port)
            ea, eb = uuid.uuid4(), uuid.uuid4()

            def routered(msg) -> bytes:
                return tracectx.wrap(
                    serialize_message(msg),
                    trace_id=tracectx.new_trace_id(),
                    t_ingress_ns=1,
                )

            fast0 = ingest.fast_messages
            await a.send_raw(routered(ent_msg(a.uuid, [Entity(
                uuid=ea, position=Vector3(1, 2, 3), world_name="w",
            )])))
            await b.send_raw(routered(ent_msg(b.uuid, [Entity(
                uuid=eb, position=Vector3(2, 2, 3), world_name="w",
            )])))
            frame = await b.recv_until(Instruction.LOCAL_MESSAGE,
                                       timeout=20)
            assert frame.parameter == "entity.frame"
            for _ in range(3):
                await b.send_raw(routered(ent_msg(b.uuid, [Entity(
                    uuid=eb, position=Vector3(2, 2, 3), world_name="w",
                )])))
                await b.recv_until(Instruction.LOCAL_MESSAGE, timeout=20)

            assert ingest.fast_messages > fast0, ingest.stats()
            assert ingest.rows > 0
            counters = server.metrics.snapshot()["counters"]
            stripped = counters.get("zmq.ctx_unwrapped", 0)
            assert stripped >= ingest.fast_messages - fast0 > 0, counters
            await a.close()
            await b.close()
        finally:
            server.cluster = None
            await server.stop()

    run(scenario())


@pytest.mark.parametrize("slow_at", [0, 2, 4])
def test_held_ctxs_stay_in_lockstep_with_their_buffers(wire, slow_at):
    """A shard holds (buffer, ctx) pairs: whichever buffer the
    classifier routes slow gets ITS ctx back, wherever it sat in the
    held batch, and a cut leaves both lists empty together."""
    from worldql_server_tpu.entities import ColumnarIngest, EntityPlane
    from worldql_server_tpu.engine.peers import PeerMap
    from worldql_server_tpu.spatial.cpu_backend import CpuSpatialBackend

    plane = EntityPlane(CpuSpatialBackend(16), PeerMap(), cube_size=16,
                        dt=0.05, bounds=1000.0, k=4)
    ingest = ColumnarIngest(plane, sender_known=lambda u: True, wire=wire)
    owner = uuid.uuid4()
    ents = [uuid.uuid4() for _ in range(5)]
    routed = []

    async def slow(data, ctx):
        routed.append((deserialize_message(data).entities[0].uuid, ctx))

    for i, e in enumerate(ents):
        # a per-entity world keeps the buffer a LocalMessage with
        # entities (it is held) that the classifier still routes slow
        world = "elsewhere" if i == slow_at else "w"
        asks = ingest.hold(
            serialize_message(ent_msg(owner, [Entity(
                uuid=e, position=Vector3(i, 1, 1), world_name=world)])),
            (100 + i, 1000 + i),
        )
        assert not asks
    assert len(ingest._held) == len(ingest._held_ctxs) == 5
    run(ingest.stage(slow, edge=True))
    assert routed == [(ents[slow_at], (100 + slow_at, 1000 + slow_at))]
    assert ingest._held == [] and ingest._held_ctxs == []
    st = ingest.stats()
    assert (st["fast_messages"], st["slow_messages"], st["edge_messages"],
            st["batches"]) == (4, 1, 4, 1)


def test_router_framed_slow_messages_keep_their_own_trace_ctx():
    """e2e over real ZMQ on a shard: updates and a removal, all
    router-framed, from one sender. The removal is routed on receipt,
    behind the update held before it, with the trace ctx IT arrived
    with; the updates ride the columns."""

    async def scenario():
        server, config = await shard_server()
        handled = []
        handle = server.router.handle_message

        async def spy(message):
            handled.append((message.parameter, message.trace_ctx))
            await handle(message)

        server.router.handle_message = spy
        try:
            ingest = server.entity_ingest
            a = await ZmqClient.connect(config.zmq_server_port)
            e = uuid.uuid4()
            ids = [tracectx.new_trace_id() for _ in range(3)]

            def routered(msg, i) -> bytes:
                return tracectx.wrap(serialize_message(msg),
                                     trace_id=ids[i], t_ingress_ns=7 + i)

            fast0 = ingest.fast_messages
            await a.send_raw(routered(ent_msg(a.uuid, [Entity(
                uuid=e, position=Vector3(1, 2, 3), world_name="w")]), 0))
            await a.send_raw(routered(ent_msg(
                a.uuid, [Entity(uuid=e)], parameter="entity.remove"), 1))
            await a.send_raw(routered(ent_msg(a.uuid, [Entity(
                uuid=e, position=Vector3(9, 2, 3), world_name="w")]), 2))
            for _ in range(400):
                if ingest.fast_messages - fast0 == 2 and not ingest._held:
                    break
                await asyncio.sleep(0.01)
            assert ingest.fast_messages - fast0 == 2, ingest.stats()
            assert ("entity.remove", (ids[1], 8)) in handled, handled
            assert ingest._held_ctxs == []
            plane = server.entity_plane
            assert plane.entities_registered == 2   # removed in between
            await a.close()
        finally:
            server.cluster = None
            await server.stop()

    run(scenario())
