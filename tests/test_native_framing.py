"""Native chunked-framing parser: C and Python twins must be
byte-identical on every input shape the relay sees."""

import numpy as np
import pytest

from inference_gateway_tpu.native import framing
from inference_gateway_tpu.netio.client import _parse_chunked_py


def _encode(payloads, terminal=True, ext_every=0, trailer=b"\r\n"):
    out = b""
    for i, p in enumerate(payloads):
        size = f"{len(p):X}"
        if ext_every and i % ext_every == 0:
            size += ";ext=1"
        out += size.encode() + b"\r\n" + p + b"\r\n"
    if terminal:
        out += b"0\r\n" + trailer
    return out


needs_native = pytest.mark.skipif(framing is None, reason="no C toolchain")


@needs_native
def test_native_matches_python_on_random_streams():
    rng = np.random.default_rng(0)
    for trial in range(200):
        n = int(rng.integers(0, 8))
        payloads = [rng.bytes(int(rng.integers(0, 300))) for _ in range(n)]
        wire = _encode(payloads, terminal=bool(rng.integers(0, 2)),
                       ext_every=int(rng.integers(0, 3)))
        # Every split point: partial buffers must behave identically.
        cut = int(rng.integers(0, len(wire) + 1))
        for buf in (wire, wire[:cut]):
            for maxp in (65536, 64, 1):
                assert framing.parse_chunked(buf, maxp) == _parse_chunked_py(buf, maxp), (
                    trial, cut, maxp)


@needs_native
def test_native_edge_cases_match():
    cases = [
        b"",
        b"2",
        b"2\r",
        b"2\r\nhi",
        b"2\r\nhi\r\n",
        b"0\r\n",
        b"0\r\n\r\n",
        b"  A  ;x=y\r\n0123456789\r\n",
        b"\r\n\r\n",  # empty size field parses as 0 (done)
        b"2\r\nhi\r\n0;last\r\n\r\nSTRAY",
    ]
    for buf in cases:
        assert framing.parse_chunked(buf, 65536) == _parse_chunked_py(buf, 65536), buf


@needs_native
def test_native_rejects_bad_hex_like_python():
    with pytest.raises(ValueError):
        framing.parse_chunked(b"zz\r\nxx\r\n", 65536)
    with pytest.raises(ValueError):
        _parse_chunked_py(b"zz\r\nxx\r\n", 65536)


@needs_native
def test_iter_raw_uses_whichever_parser_identically(aloop):
    """End to end through ClientResponse.iter_raw with each parser."""
    import asyncio

    from inference_gateway_tpu.netio import client as client_mod
    from inference_gateway_tpu.netio.client import ClientResponse
    from inference_gateway_tpu.netio.server import Headers

    wire = _encode([b"hello ", b"world", b"x" * 1000])

    def run(parser):
        async def go():
            reader = asyncio.StreamReader()
            reader.feed_data(wire)
            reader.feed_eof()
            h = Headers()
            h.set("Transfer-Encoding", "chunked")
            resp = ClientResponse(status=200, headers=h, _reader=reader)
            out = []
            async for block in resp.iter_raw():
                out.append(block)
            return b"".join(out), resp._drained
        return aloop.run(go())

    orig = client_mod._parse_chunked
    try:
        client_mod._parse_chunked = framing.parse_chunked
        native_out = run(framing.parse_chunked)
        client_mod._parse_chunked = _parse_chunked_py
        py_out = run(_parse_chunked_py)
    finally:
        client_mod._parse_chunked = orig
    assert native_out == py_out == (b"hello world" + b"x" * 1000, True)


@needs_native
def test_whitespace_and_overflow_shapes_identical():
    """ADVICE round 5: the C twin must trim the FULL ASCII whitespace set
    (bytes.strip semantics, not just space/tab) and treat >=2^59 size
    lines exactly like Python's arbitrary-precision parser (incomplete
    chunk — break, don't raise)."""
    shapes = [
        b"\v5\r\n01234\r\n",            # leading \v padding
        b"\x0c5\r\n01234\r\n",          # leading \f padding
        b"5\v\r\n01234\r\n",            # trailing \v padding
        b"\n5 \r\n01234\r\n",           # mixed \n + space padding
        b" \v ; ext\r\n",               # all-whitespace field + extension
        b"FFFFFFFFFFFFFFFF\r\nAAAA",    # 2^64-1: incomplete in both twins
        b"8000000000000000\r\nAAAA",    # 2^63: first digit past the guard
        b"FFFFFFFFFFFFFFFFFF\r\nAAAA",  # 18 digits, far past Py_ssize_t
    ]
    for buf in shapes:
        assert framing.parse_chunked(buf, 65536) == _parse_chunked_py(buf, 65536), buf
    # The whitespace-padded well-formed shapes actually parse payloads.
    assert framing.parse_chunked(b"\v5\r\n01234\r\n", 65536) == (b"01234", 11, 0)
    # Oversized size lines are an incomplete tail, not an error.
    assert framing.parse_chunked(b"FFFFFFFFFFFFFFFFFF\r\nAAAA", 65536) == (b"", 0, 0)


@needs_native
def test_hostile_inputs_safe_and_identical():
    """Near-PY_SSIZE_T_MAX sizes must not overflow the C parser's bounds
    math (code-review round 5: verified SIGSEGV before the guard), and
    int(x,16)-isms (sign, 0x, underscores) are rejected by BOTH twins."""
    hostile = b"7FFFFFFFFFFFFFFF\r\nAAAA"
    assert framing.parse_chunked(hostile, 65536) == _parse_chunked_py(hostile, 65536) \
        == (b"", 0, 0)
    for bad in (b"-5\r\nAB\r\n", b"0x5\r\nxxxxx\r\n", b"1_0\r\nxx\r\n", b"+A\r\nxx\r\n"):
        with pytest.raises(ValueError):
            framing.parse_chunked(bad, 65536)
        with pytest.raises(ValueError):
            _parse_chunked_py(bad, 65536)


@needs_native
def test_binary_name_carries_the_source_hash():
    """A copy of the tree scrambles mtimes; the hash in the name is what
    keeps a binary built from other source from being loaded."""
    import hashlib
    import pathlib

    from inference_gateway_tpu import native

    source = pathlib.Path(native.__file__).with_name("framing.c").read_bytes()
    assert hashlib.sha1(source).hexdigest()[:12] in pathlib.Path(framing.__file__).name
    assert native.status == "native"
