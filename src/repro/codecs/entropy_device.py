"""Device-backend twins of the entropy coders (huffman / fse).

Whole-codec encoders routed through the jit'd kernel wrappers
(``repro.kernels.ops``): exact device histogram -> host table construction
(the same O(256) functions the host encoder uses, so wire descriptors match
byte-for-byte) -> device map/scan -> device scatter-add bit packing straight
into the concatenated wire layout.  Bit-identity with the host encoders
holds end to end: identical tables give identical per-symbol codes and bit
offsets, the packer writes exactly the bits the host bit-matrix writer does
(every output bit has one writer), and unwritten bits are zero on both
paths.  Verified by the device-backend golden-vector conformance suite.

Decode stays on the host universal-decoder path by design
(``register_backend_codec`` is encode-only); the decode kernels' twins are
exercised by the kernel equivalence tests.
"""
from __future__ import annotations

import threading

import numpy as np

from repro.core.codec import register_backend_codec
from repro.core.message import Stream, SType
from repro.device import to_device, to_host

from ._util import HeaderWriter, device_available, numeric_stream
from .entropy import (
    BLOCK_LOG,
    FSE_BLOCK_LOG,
    _as_u8,
    _fse_tables_cached,
    _huffman_code_lengths,
    _huffman_codes_cached,
    _normalize_counts,
)

# Routability window: below _DEV_MIN the transfer + dispatch overhead beats
# any kernel win; above _DEV_MAX the int32 bit-offset cumsums (15 bits/code
# max) would overflow.  The engine's host fallback covers both ends.
_DEV_MIN = 1 << 10
_DEV_MAX = 1 << 27


def _bytes_ok(s: Stream) -> bool:
    return s.stype == SType.SERIAL or (
        s.stype in (SType.NUMERIC, SType.STRUCT) and s.width == 1
    )


def _dev_entropy_ready(streams) -> bool:
    s = streams[0]
    return (
        device_available()
        and _bytes_ok(s)
        and _DEV_MIN <= s.n_elts <= _DEV_MAX
    )


def _cap_bucket(nbytes: int) -> int:
    """Power-of-two capacity for the packer's static output shape: bounds
    jit recompiles to one per bucket instead of one per content size."""
    return 1 << max(12, (nbytes - 1).bit_length())


# ------------------------------------------------------------------- huffman
def _huffman_applies_device(streams, params):
    return _dev_entropy_ready(streams)


def _huffman_enc_device(streams, params):
    from repro.kernels import ops

    x = _as_u8(streams[0], "huffman")
    n = x.size
    xj = to_device(x)
    counts = to_host(ops.histogram_exact(xj)).astype(np.int64)
    lens = _huffman_code_lengths(counts)
    codes = _huffman_codes_cached(lens)
    code, _nb, offs = ops.huffman_map(
        xj, to_device(codes), to_device(lens.astype(np.int32))
    )
    total = int(to_host(offs[-1]))
    total_bytes = (total + 7) >> 3
    packed = to_host(
        ops.pack_bits(code, offs[:-1], _cap_bucket(total_bytes))
    )[:total_bytes]
    block = 1 << BLOCK_LOG
    block_offs = to_host(offs[: n : block]).astype(np.uint64)
    h = HeaderWriter().varint(n).u8(BLOCK_LOG).u8(int(streams[0].stype))
    nib = (lens[0::2] | (lens[1::2] << 4)).astype(np.uint8)
    h.bytes_(nib.tobytes())
    return [
        Stream(packed, SType.SERIAL, 1),
        numeric_stream(block_offs),
    ], h.done()


register_backend_codec(
    "device", "huffman", _huffman_enc_device, _huffman_applies_device
)


# ----------------------------------------------------------------------- fse
def _fse_applies_device(streams, params):
    return _dev_entropy_ready(streams)


_fse_lock = threading.Lock()
_fse_counts = {"calls": 0, "symbols": 0, "lane_steps": 0}


def _count_fse(n_symbols: int, lane_steps: int) -> None:
    with _fse_lock:
        _fse_counts["calls"] += 1
        _fse_counts["symbols"] += n_symbols
        _fse_counts["lane_steps"] += lane_steps


def fse_device_info() -> dict:
    """Device fse encodes in this process: calls, symbols encoded, and
    lane-steps walked (lanes x (1 << FSE_BLOCK_LOG) per call)."""
    with _fse_lock:
        return dict(_fse_counts)


def _fse_enc_device(streams, params):
    from repro.kernels import ops

    x = _as_u8(streams[0], "fse")
    n = x.size
    table_log = int(params.get("table_log", 11))
    stype_tag = int(streams[0].stype)
    xj = to_device(x)
    counts = to_host(ops.histogram_exact(xj)).astype(np.int64)
    norm = _normalize_counts(counts, table_log)
    tabs = _fse_tables_cached(norm, table_log)

    block = 1 << FSE_BLOCK_LOG
    n_blocks = (n + block - 1) // block
    padded = np.zeros(n_blocks * block, dtype=np.uint8)
    padded[:n] = x
    lanesT = padded.reshape(n_blocks, block).T
    rem = np.minimum(
        n - np.arange(n_blocks, dtype=np.int64) * block, block
    ).astype(np.int32)
    vals, goffs, state, bitpos, byte_off = ops.fse_encode(
        to_device(lanesT),
        to_device(rem),
        to_device(tabs.nb0),
        to_device(tabs.thr),
        to_device(tabs.st0),
        to_device(tabs.delta),
        to_device(tabs.state_table),
    )
    _count_fse(n, n_blocks * block)
    total_bytes = int(to_host(byte_off[-1]))
    stream_out = to_host(
        ops.pack_bits(
            vals.reshape(-1), goffs.reshape(-1), _cap_bucket(total_bytes)
        )
    )[:total_bytes]
    meta = np.empty(n_blocks * 2, dtype=np.uint32)
    meta[0::2] = to_host(bitpos).astype(np.uint32)
    meta[1::2] = to_host(state).astype(np.uint32)

    h = HeaderWriter().varint(n).u8(FSE_BLOCK_LOG).u8(table_log).u8(stype_tag)
    nz = np.nonzero(norm)[0]
    hw = HeaderWriter()
    hw.varint(nz.size)
    for s in nz:
        hw.varint(int(s))
        hw.varint(int(norm[s]))
    h.bytes_(hw.done())
    return [Stream(stream_out, SType.SERIAL, 1), numeric_stream(meta)], h.done()


register_backend_codec("device", "fse", _fse_enc_device, _fse_applies_device)
