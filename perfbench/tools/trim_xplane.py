#!/usr/bin/env python3
"""trim_xplane.py <in.xplane.pb> <out.xplane.pb> [plane name to drop ...]

Copies a profiler trace without the named planes (default: `/host:metadata`,
the HLO text of every program, and `/host:CPU`, the host threads), at the
protobuf wire level: XSpace.planes is field 1, XPlane.name is field 2. That
is how tests/fixtures/v5e_window.xplane.pb was cut from 2.6 MB to 165 KB; the
device planes are byte for byte what the profiler wrote.
"""

import sys

DROP = ("/host:metadata", "/host:CPU")


def _varint(b: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return out, i


def fields(b: bytes):
    """(field number, payload of a length-delimited field or None, the raw bytes) of each field."""
    i = 0
    while i < len(b):
        start = i
        key, i = _varint(b, i)
        wire = key & 7
        payload = None
        if wire == 0:
            _, i = _varint(b, i)
        elif wire == 1:
            i += 8
        elif wire == 2:
            n, i = _varint(b, i)
            payload = b[i : i + n]
            i += n
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {start}")
        yield key >> 3, payload, b[start:i]


def trim(data: bytes, drop=DROP) -> bytes:
    out = bytearray()
    for number, payload, raw in fields(data):
        if number == 1 and payload is not None:
            name = next((p.decode() for f, p, _ in fields(payload) if f == 2 and p is not None), "")
            if name in drop:
                continue
        out += raw
    return bytes(out)


if __name__ == "__main__":
    src, dst, *drop = sys.argv[1:]
    with open(src, "rb") as f:
        trimmed = trim(f.read(), tuple(drop) or DROP)
    with open(dst, "wb") as f:
        f.write(trimmed)
    print(f"{dst}: {len(trimmed)} bytes")
