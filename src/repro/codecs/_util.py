"""Shared helpers for codec implementations: header packing, width logic."""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from repro.core.message import Stream, SType
from repro.core.wire import read_varint, write_varint

UNSIGNED = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}
SIGNED = {1: np.int8, 2: np.int16, 4: np.int32, 8: np.int64}


class HeaderWriter:
    def __init__(self):
        self.buf = bytearray()

    def u8(self, v: int) -> "HeaderWriter":
        self.buf.append(v & 0xFF)
        return self

    def varint(self, v: int) -> "HeaderWriter":
        write_varint(self.buf, int(v))
        return self

    def svarint(self, v: int) -> "HeaderWriter":
        v = int(v)
        return self.varint((v << 1) ^ (v >> 63) if v >= 0 else ((-v) << 1) - 1)

    def bytes_(self, b: bytes) -> "HeaderWriter":
        self.varint(len(b))
        self.buf += b
        return self

    def done(self) -> bytes:
        return bytes(self.buf)


class HeaderReader:
    def __init__(self, header: bytes):
        self.buf = header
        self.pos = 0

    def u8(self) -> int:
        v = self.buf[self.pos]
        self.pos += 1
        return v

    def varint(self) -> int:
        v, self.pos = read_varint(self.buf, self.pos)
        return v

    def svarint(self) -> int:
        v = self.varint()
        return (v >> 1) ^ -(v & 1)

    def bytes_(self) -> bytes:
        n = self.varint()
        b = self.buf[self.pos : self.pos + n]
        self.pos += n
        return b

    def expect_end(self) -> None:
        if self.pos != len(self.buf):
            raise ValueError("trailing bytes in codec header")


# ------------------------------------------------------- device-backend glue
_JAX_OK: bool = None  # tri-state: None = not probed yet


def device_available() -> bool:
    """True when jax is importable (the device backend can be offered)."""
    global _JAX_OK
    if _JAX_OK is None:
        try:
            import jax  # noqa: F401

            _JAX_OK = True
        except Exception:  # pragma: no cover - container always has jax
            _JAX_OK = False
    return _JAX_OK


def min_uint_width(max_value: int) -> int:
    if max_value < 1 << 8:
        return 1
    if max_value < 1 << 16:
        return 2
    if max_value < 1 << 32:
        return 4
    return 8


def numeric_stream(arr: np.ndarray) -> Stream:
    """Wrap an unsigned/signed integer array as a NUMERIC stream."""
    return Stream(np.ascontiguousarray(arr.ravel()), SType.NUMERIC, arr.dtype.itemsize)


def fixed_records(s: Stream) -> Tuple[np.ndarray, int]:
    """View a fixed-width stream (SERIAL/STRUCT/NUMERIC) as (n, width) uint8."""
    if s.stype == SType.STRING:
        raise ValueError("fixed_records on string stream")
    raw = np.frombuffer(s.content_bytes(), dtype=np.uint8)
    w = s.width if s.stype != SType.SERIAL else 1
    return raw.reshape(-1, w), w


def rebuild_like(template_stype: SType, width: int, raw: np.ndarray) -> Stream:
    """Rebuild a stream of (stype, width) from raw little-endian bytes."""
    from repro.core.message import from_wire

    return from_wire(template_stype, width, raw.tobytes(), None)
