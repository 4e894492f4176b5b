"""A source ifunc's fat-bitcode archive: read-only once built, its wire
bytes and sha256 made once per archive however many frames carry it, and
the same bytes on the wire as the format written out by hand."""

import hashlib
import struct
from dataclasses import FrozenInstanceError

import jax
import numpy as np
import pytest

from repro.core import CompletionQueue, FatBitcode, Frame, IFunc, Toolchain, make_tsi, spans
from repro.core.ifunc import PE
from repro.core.transport import Fabric

I32 = np.int32


def by_hand(slices) -> bytes:
    """The archive format: magic, slice count, then per triple in sorted
    order its name and blob lengths, its name and its blob."""
    out = b"FBC1" + struct.pack("<H", len(slices))
    for t in sorted(slices):
        out += struct.pack("<HI", len(t), len(slices[t])) + t.encode() + slices[t]
    return out


def adder(scale: int = 1, name: str = "add") -> IFunc:
    """An update-ABI ifunc: ``counter += scale * payload[3]``; its payload
    is 4 words, so a completion-tracked submit's 3-word header fits."""

    def entry(payload, counter):
        return counter + scale * payload[3]

    return IFunc.build(
        name=name,
        fn=entry,
        payload_aval=jax.ShapeDtypeStruct((4,), I32),
        dep_avals=(jax.ShapeDtypeStruct((1,), I32),),
        deps=("region:counter",),
        abi="update",
        targets=("cpu-host",),
    )


@pytest.fixture()
def pair():
    fabric = Fabric("ideal")
    tc = Toolchain()
    names = ["server0", "client"]
    server = PE("server0", fabric, triple="cpu-host", toolchain=tc, peers=names)
    client = PE("client", fabric, triple="cpu-host", toolchain=tc, peers=names)
    server.register_region("counter", np.zeros(1, I32))
    return client, server


@pytest.fixture()
def traced_spans():
    spans.enable(True)  # the totals start over
    try:
        yield spans
    finally:
        spans.enable(None)


@pytest.mark.parametrize("targets", [("cpu-host",), ("cpu-host", "cpu-bf2", "cpu-a64fx", "tpu-v5e")])
def test_wire_bytes_and_digest_match_the_format_by_hand(targets):
    ifn = make_tsi(targets=targets)
    want = by_hand(ifn.fat.slices)
    assert ifn.code_bytes == want == ifn.fat.to_bytes()
    assert ifn.digest == hashlib.sha256(want).digest() == ifn.fat.sha256
    assert ifn.fat.digest == hashlib.sha256(want).hexdigest()
    assert ifn.fat.nbytes == len(want)


def test_archive_round_trips():
    ifn = make_tsi()
    back = FatBitcode.from_bytes(ifn.code_bytes)
    assert back == ifn.fat
    assert back.triples() == ifn.fat.triples()
    assert back.to_bytes() == ifn.code_bytes and back.digest == ifn.fat.digest


def test_archive_is_read_only():
    given = {"cpu-host": b"H" * 24, "tpu-v5e": b"T" * 56}
    fat = FatBitcode(slices=given)
    before = fat.to_bytes()
    with pytest.raises(TypeError):
        fat.slices["cpu-host"] = b"X"
    with pytest.raises(TypeError):
        del fat.slices["tpu-v5e"]
    with pytest.raises(FrozenInstanceError):
        fat.slices = {}
    given["cpu-host"] = b"X"  # the archive holds its own copy
    assert fat.slices["cpu-host"] == b"H" * 24
    assert fat.to_bytes() == before == by_hand(fat.slices)


def test_every_send_path_serialises_the_archive_once(pair, traced_spans):
    """Five sends each through ``send_ifunc``, a framed ``return_payload``
    and ``submit``: one ``pe/archive`` span, at the first, for the
    archive's bytes; the receiver reads the frame's code as it came."""
    client, server = pair
    ifn = client.register_source(adder())
    cq = CompletionQueue(client, shape=(1,), dtype=I32, max_slots=8)
    for v in range(1, 6):
        client.send_ifunc("server0", "add", np.array([0, 0, 0, v], I32))
        client.return_payload("server0", "add", np.array([0, 0, 0, 10 * v], I32))
        assert client.submit("server0", "add", np.array([100 * v], I32), cq, expected=1)
    while server.poll():
        pass
    assert server.region("counter")[0] == 111 * 15
    count, _, nbytes = traced_spans.totals()["pe/archive"]
    assert (count, nbytes) == (1, len(by_hand(ifn.fat.slices)))


def test_frames_carry_the_same_bytes_as_the_format_by_hand():
    ifn = make_tsi()
    code = by_hand(ifn.fat.slices)
    pay = np.array([7], I32).tobytes()
    want = Frame(kind=ifn.kind, name=ifn.name, payload=pay, code=code, deps=ifn.deps,
                 digest=hashlib.sha256(code).digest(), seq=3)
    first, later = ifn.make_frame(pay, seq=3), ifn.make_frame(pay, seq=3)
    assert first.pack() == later.pack() == want.pack()
    for cached in (False, True):
        assert first.wire_bytes(cached=cached) == want.wire_bytes(cached=cached)


def test_republished_body_gets_a_new_digest_and_installs(pair):
    client, server = pair
    v1 = client.register_source(adder(scale=1))
    client.send_ifunc("server0", "add", np.array([0, 0, 0, 5], I32))
    server.poll()
    v2 = client.register_source(adder(scale=10))
    assert v2.digest != v1.digest and v2.code_bytes != v1.code_bytes
    assert v1.digest == hashlib.sha256(by_hand(v1.fat.slices)).digest()  # unchanged
    client.send_ifunc("server0", "add", np.array([0, 0, 0, 5], I32))
    server.poll()
    assert server.region("counter")[0] == 5 + 50
    assert server.target_cache.stats.jit_compiles == 2
    assert server.target_cache.lookup("add").digest == v2.fat.digest
