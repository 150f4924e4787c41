"""Golden wire-compatibility fixtures: buffers in the REFERENCE
writer's layout (flatc-generated Rust, WorldQLFB_generated.rs) that
both codecs must decode.

Three pins:
1. the vendored bytes stay reproducible from the stock FlatBuffers
   runtime (catches generator or runtime drift — the fixtures are the
   contract, not a build artifact);
2. the pure-Python codec decodes every fixture to the exact expected
   Message (slot layout, default omission, reverse push order — none of
   which our forward-order writer produces itself);
3. the C++ codec agrees byte-for-byte-of-meaning with the Python one on
   the same fixtures, and both codecs' re-encodes round-trip.
"""

from __future__ import annotations


import pytest

from worldql_server_tpu.protocol import codec
from worldql_server_tpu.protocol.native_codec import load

from wire_fixtures import (
    BAD_CASES, CASES, FIXTURE_DIR, build_reference_bytes, expected_message,
)

GOOD = sorted(set(CASES) - BAD_CASES)
BAD = sorted(BAD_CASES)



def fixture_bytes(name: str) -> bytes:
    p = FIXTURE_DIR / f"{name}.bin"
    assert p.exists(), (
        f"missing vendored fixture {p} — run python tests/wire_fixtures.py"
    )
    return p.read_bytes()


@pytest.mark.parametrize("name", sorted(CASES))
def test_vendored_bytes_reproducible(name):
    """The checked-in buffer is exactly what the stock runtime emits
    for the reference writer's call sequence."""
    assert fixture_bytes(name) == build_reference_bytes(CASES[name])


@pytest.mark.parametrize("name", GOOD)
def test_python_codec_decodes_reference_layout(name):
    got = codec.py_deserialize_message(fixture_bytes(name))
    assert got == expected_message(CASES[name])


@pytest.mark.parametrize("name", GOOD)
def test_python_reencode_roundtrips(name):
    """decode(fixture) → our writer (different layout) → decode again
    must be lossless."""
    msg = codec.py_deserialize_message(fixture_bytes(name))
    assert codec.py_deserialize_message(codec.py_serialize_message(msg)) == msg


@pytest.mark.parametrize("name", BAD)
def test_python_codec_rejects_contract_violations(name):
    with pytest.raises(codec.DeserializeError):
        codec.py_deserialize_message(fixture_bytes(name))


@pytest.fixture(scope="module")
def native(native_lib):
    n = load()
    assert n is not None, "native codec failed to load"
    return n


@pytest.mark.parametrize("name", GOOD)
def test_native_codec_decodes_reference_layout(native, name):
    got = native.decode(fixture_bytes(name), codec.DeserializeError)
    assert got == expected_message(CASES[name])


@pytest.mark.parametrize("name", GOOD)
def test_native_reencode_roundtrips_through_python(native, name):
    msg = native.decode(fixture_bytes(name), codec.DeserializeError)
    assert codec.py_deserialize_message(native.encode(msg)) == msg


@pytest.mark.parametrize("name", BAD)
def test_native_codec_rejects_contract_violations(native, name):
    with pytest.raises(codec.DeserializeError):
        native.decode(fixture_bytes(name), codec.DeserializeError)
