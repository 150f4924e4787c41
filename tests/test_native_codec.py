"""Native C++ codec: parity with the pure-Python codec + fuzz safety.

Builds native/libwqlcodec.so on demand (g++ is baked into the image).
Parity is semantic: both codecs must decode each other's buffers into
equal Messages; byte-identical output is NOT required (different
builders may lay out vtables differently).
"""

import random
import uuid

import pytest

from worldql_server_tpu.protocol import codec
from worldql_server_tpu.protocol.native_codec import (
    NativeCodec,
    _TooManyObjects,
    load,
)
from worldql_server_tpu.protocol.types import (
    Entity,
    Instruction,
    Message,
    Record,
    Replication,
    Vector3,
)



@pytest.fixture(scope="module")
def native(native_lib) -> NativeCodec:
    n = load()
    assert n is not None, "native codec failed to load"
    return n


def rand_message(rng: random.Random) -> Message:
    def maybe(v):
        return v if rng.random() < 0.7 else None

    def rand_obj(cls):
        return cls(
            uuid=uuid.UUID(int=rng.getrandbits(128)),
            position=(
                Vector3(rng.uniform(-1e6, 1e6), rng.uniform(-1e6, 1e6),
                        rng.uniform(-1e6, 1e6))
                if (cls is Entity or rng.random() < 0.7) else None
            ),
            world_name=rng.choice(["overworld", "nether", "w", "x" * 60]),
            data=maybe(rng.choice(["", "payload", "üñïçødé ✓", "a" * 500])),
            flex=maybe(bytes(rng.randrange(256) for _ in range(rng.randrange(64)))),
        )

    return Message(
        instruction=rng.choice(list(Instruction)),
        parameter=maybe(rng.choice(["", "p", "párám", "x" * 300])),
        sender_uuid=uuid.UUID(int=rng.getrandbits(128)),
        world_name=rng.choice(["overworld", "a_b", "@global"]),
        replication=rng.choice(list(Replication)),
        records=[rand_obj(Record) for _ in range(rng.randrange(4))],
        entities=[rand_obj(Entity) for _ in range(rng.randrange(3))],
        position=maybe(Vector3(rng.uniform(-1e9, 1e9), 0.0, -0.0)),
        flex=maybe(bytes(rng.randrange(256) for _ in range(rng.randrange(128)))),
    )


def assert_messages_equal(a: Message, b: Message):
    assert a.instruction == b.instruction
    assert a.parameter == b.parameter
    assert a.sender_uuid == b.sender_uuid
    assert a.world_name == b.world_name
    assert a.replication == b.replication
    assert a.position == b.position
    assert a.flex == b.flex
    assert len(a.records) == len(b.records)
    assert len(a.entities) == len(b.entities)
    for x, y in zip(a.records + a.entities, b.records + b.entities):
        assert x.uuid == y.uuid
        assert x.position == y.position
        assert x.world_name == y.world_name
        assert x.data == y.data
        assert x.flex == y.flex


def test_python_encode_native_decode(native):
    rng = random.Random(1)
    for _ in range(200):
        msg = rand_message(rng)
        buf = codec.py_serialize_message(msg)
        got = native.decode(buf, codec.DeserializeError)
        assert_messages_equal(msg, got)


def test_native_encode_python_decode(native):
    rng = random.Random(2)
    for _ in range(200):
        msg = rand_message(rng)
        buf = native.encode(msg)
        got = codec.py_deserialize_message(buf)
        assert_messages_equal(msg, got)


def test_native_roundtrip(native):
    rng = random.Random(3)
    for _ in range(200):
        msg = rand_message(rng)
        got = native.decode(native.encode(msg), codec.DeserializeError)
        assert_messages_equal(msg, got)


def test_truncated_buffers_raise(native):
    msg = rand_message(random.Random(4))
    buf = native.encode(msg)
    for cut in range(0, len(buf), max(1, len(buf) // 40)):
        try:
            native.decode(buf[:cut], codec.DeserializeError)
        except codec.DeserializeError:
            pass  # raising is fine; crashing is not


def test_fuzzed_garbage_never_crashes(native):
    rng = random.Random(5)
    for _ in range(500):
        blob = bytes(rng.randrange(256) for _ in range(rng.randrange(200)))
        try:
            native.decode(blob, codec.DeserializeError)
        except codec.DeserializeError:
            pass


def test_bitflip_fuzz_matches_python_error_tolerance(native):
    """Bit-flipped valid buffers: native must never crash, and when the
    Python codec accepts a flipped buffer, native must agree on it."""
    rng = random.Random(6)
    base = codec.py_serialize_message(rand_message(rng))
    for _ in range(500):
        b = bytearray(base)
        for _ in range(rng.randrange(1, 4)):
            b[rng.randrange(len(b))] ^= 1 << rng.randrange(8)
        data = bytes(b)
        try:
            py_msg = codec.py_deserialize_message(data)
        except codec.DeserializeError:
            py_msg = None
        try:
            nat_msg = native.decode(data, codec.DeserializeError)
        except codec.DeserializeError:
            nat_msg = None
        except _TooManyObjects:
            continue  # dispatch falls back to the Python codec here
        if py_msg is not None and nat_msg is not None:
            assert_messages_equal(py_msg, nat_msg)


def test_dispatch_uses_native_when_built(native):
    # codec.load() happened at import; if the lib existed then, the
    # module-level functions are the native ones. Either way both
    # entry points must round-trip.
    msg = rand_message(random.Random(7))
    got = codec.deserialize_message(codec.serialize_message(msg))
    assert_messages_equal(msg, got)


def _entity_batch(n: int) -> Message:
    return Message(
        instruction=Instruction.LOCAL_MESSAGE,
        sender_uuid=uuid.UUID(int=5),
        world_name="w",
        entities=[
            Entity(uuid=uuid.UUID(int=i + 1),
                   position=Vector3(float(i), 1.0, 2.0), world_name="w")
            for i in range(n)
        ],
    )


def test_max_objs_boundary_roundtrips_and_overflow_is_counted(native):
    """The WQL_MAX_OBJS cliff (ISSUE 11 satellite): exactly MAX_OBJS
    entities stays native; MAX_OBJS + 1 falls back to the Python codec
    — still correct, but COUNTED (codec.obj_overflow), never silent."""
    from worldql_server_tpu.protocol.native_codec import MAX_OBJS

    at_cap = _entity_batch(MAX_OBJS)
    wire = native.encode(at_cap)
    got = native.decode(wire, codec.DeserializeError)
    assert len(got.entities) == MAX_OBJS
    assert_messages_equal(at_cap, got)

    over = _entity_batch(MAX_OBJS + 1)
    with pytest.raises(_TooManyObjects):
        native.encode(over)
    wire_over = codec.py_serialize_message(over)
    with pytest.raises(_TooManyObjects):
        native.decode(wire_over, codec.DeserializeError)

    if codec._native is None:
        pytest.skip("module-level dispatch is pure Python here")
    before = codec.codec_stats["obj_overflow"]
    wire2 = codec.serialize_message(over)     # encode fallback: +1
    got2 = codec.deserialize_message(wire2)   # decode fallback: +1
    assert len(got2.entities) == MAX_OBJS + 1
    assert_messages_equal(over, got2)
    assert codec.codec_stats["obj_overflow"] == before + 2

    before = codec.codec_stats["obj_overflow"]
    at_wire = codec.serialize_message(at_cap)
    codec.deserialize_message(at_wire)
    assert codec.codec_stats["obj_overflow"] == before  # boundary: native


@pytest.mark.parametrize("counts", [[], [0], [10_000], [3, 0, 10_000, 1]])
def test_interest_frames_batch_export_bounds(native_lib, counts):
    """``wql_encode_interest_frames`` at its edges (zero frames, a frame
    without entities, 10,000 entries past every object cap): the
    sanitizer build runs this file, so the record writer's every store
    is bounds-checked here. Frames are read back by the Python codec."""
    import numpy as np

    from worldql_server_tpu.protocol import entity_wire

    wire = entity_wire.load()
    assert wire is not None and wire.can_encode_interest
    rng = np.random.default_rng(len(counts))
    total = sum(counts)
    keys = rng.integers(0, 256, (total, 16), dtype=np.uint8)
    pos = rng.normal(0.0, 1e4, (total, 3))
    tomb = (rng.random(total) < 0.25).astype(np.uint8)
    params = [b"entity.frame.delta:%08x:%08x" % (f, 7)
              for f in range(len(counts))]
    worlds = [b"world-%d" % f * (f + 1) for f in range(len(counts))]
    bounds = np.concatenate(([0], np.cumsum(counts, dtype=np.int64)))
    frames, at, recorded = wire.encode_interest_frames(
        params, worlds, bounds, keys, pos, tomb,
    )
    assert recorded == total
    assert len(frames) == len(at) == len(counts)
    for f, frame in enumerate(map(bytes, frames)):
        assert frame[at[f]:at[f] + len(params[f])] == params[f]
        msg = codec.py_deserialize_message(frame)
        assert msg.instruction == Instruction.LOCAL_MESSAGE
        assert msg.parameter == params[f].decode()
        assert msg.sender_uuid == uuid.UUID(int=0)
        assert msg.world_name == worlds[f].decode()
        lo, hi = int(bounds[f]), int(bounds[f + 1])
        assert len(msg.entities) == hi - lo
        for row in {lo, (lo + hi) // 2, hi - 1} if hi > lo else ():
            ent = msg.entities[row - lo]
            assert ent.uuid.bytes == keys[row].tobytes()
            assert ent.world_name == msg.world_name
            assert (ent.position.x, ent.position.y, ent.position.z) \
                == tuple(pos[row])
            assert (ent.flex == b"\x00") == bool(tomb[row])


@pytest.mark.parametrize("frames, bounds, rows", [
    (2, [0, 5, 3], 5),      # a frame that ends before it starts
    (1, [0, 6], 5),         # past the columns
    (1, [-1, 2], 5),
    (1, [0, 2, 4], 5),      # one bound too many
])
def test_interest_frames_batch_refuses_columns_that_do_not_fit(
        native_lib, frames, bounds, rows):
    """The pointers go to native code unchecked past this point: a
    batch whose bounds leave the columns is refused in Python."""
    import numpy as np

    from worldql_server_tpu.protocol import entity_wire

    wire = entity_wire.load()
    with pytest.raises(ValueError):
        wire.encode_interest_frames(
            [b"p"] * frames, [b"w"] * frames, np.array(bounds),
            np.zeros((rows, 16), np.uint8), np.zeros((rows, 3)),
            np.zeros(rows, np.uint8),
        )
