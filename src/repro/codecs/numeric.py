"""Numeric transforms (paper §II-B/C, §IV): delta, zigzag, transpose,
transpose_split, bitpack, range_pack, rle, tokenize, fused_delta_bitpack.

All are reversible; delta/zigzag are *reversible transforms*, rle/tokenize/
bitpack/range_pack are *reductive*.  Everything is numpy-vectorized.

Device twins: for the transform nodes that have Pallas kernels
(``repro.kernels.ops``) this module also registers *device-backend* encoders
(``register_backend_codec``) that are bit-exact with the host encoders — same
output streams, same headers — so frames are byte-identical regardless of
which backend produced them.
"""
from __future__ import annotations

import threading
from typing import List, Optional, Tuple

import numpy as np

from repro.core.codec import (
    ANY_STYPES,
    FIXED_STYPES,
    CodecSig,
    CodecSpec,
    InPort,
    ParamSpec,
    register_backend_codec,
    register_codec,
)
from repro.core.message import Stream, SType, from_wire
from repro.device import to_device, to_host

from ._util import (
    UNSIGNED,
    HeaderReader,
    HeaderWriter,
    device_available,
    min_uint_width,
    numeric_stream,
)


def _require_numeric(s: Stream, op: str) -> np.ndarray:
    if s.stype != SType.NUMERIC:
        raise ValueError(f"{op}: numeric streams only, got {s.stype.name}")
    return s.data.view(UNSIGNED[s.width])


_SERIAL = int(SType.SERIAL)
_NUMERIC = int(SType.NUMERIC)
_NUM_PORT = InPort(frozenset((_NUMERIC,)))
_BYTEPLANE_PORT = InPort(frozenset((int(SType.STRUCT), _NUMERIC)))


# --------------------------------------------------------------------- delta
def _delta_enc(streams, params):
    x = _require_numeric(streams[0], "delta")
    d = np.empty_like(x)
    if x.size:
        d[0] = x[0]
        # wrapping subtraction on the unsigned view: always reversible
        np.subtract(x[1:], x[:-1], out=d[1:])
    return [numeric_stream(d)], b""


def _delta_dec(outs, header):
    d = _require_numeric(outs[0], "delta")
    with np.errstate(over="ignore"):
        x = np.cumsum(d, dtype=d.dtype)
    return [numeric_stream(x)]


register_codec(
    CodecSpec(
        "delta",
        codec_id=3,
        encode=_delta_enc,
        decode=_delta_dec,
        doc="wrapping first-difference on the unsigned view (paper §II-B)",
        sig=CodecSig(
            inputs=(_NUM_PORT,),
            transfer=lambda atoms, params, n_out: [atoms[0]],
        ),
    )
)


# -------------------------------------------------------------------- zigzag
def _zigzag_enc(streams, params):
    s = streams[0]
    u = _require_numeric(s, "zigzag")
    bits = s.width * 8
    x = u.view(np.dtype(f"int{bits}"))
    zz = (u << u.dtype.type(1)) ^ (x >> (bits - 1)).view(u.dtype)
    return [numeric_stream(zz)], b""


def _zigzag_dec(outs, header):
    s = outs[0]
    u = _require_numeric(s, "zigzag")
    one = u.dtype.type(1)
    x = (u >> one) ^ (np.zeros_like(u) - (u & one))
    return [numeric_stream(x)]


register_codec(
    CodecSpec(
        "zigzag",
        codec_id=4,
        encode=_zigzag_enc,
        decode=_zigzag_dec,
        doc="signed -> small-unsigned mapping ((x<<1) ^ (x>>w-1))",
        sig=CodecSig(
            inputs=(_NUM_PORT,),
            transfer=lambda atoms, params, n_out: [atoms[0]],
        ),
    )
)


# ----------------------------------------------------------------- transpose
def _transpose_enc(streams, params):
    s = streams[0]
    if s.stype not in (SType.STRUCT, SType.NUMERIC):
        raise ValueError("transpose wants struct/numeric input")
    raw = np.frombuffer(s.content_bytes(), dtype=np.uint8)
    w = s.width
    planes = np.ascontiguousarray(raw.reshape(-1, w).T).reshape(-1)
    h = HeaderWriter().u8(int(s.stype)).varint(w).done()
    return [Stream(planes, SType.SERIAL, 1)], h


def _transpose_dec(outs, header):
    r = HeaderReader(header)
    stype = SType(r.u8())
    w = r.varint()
    r.expect_end()
    planes = outs[0].data
    n = planes.size // w
    raw = np.ascontiguousarray(planes.reshape(w, n).T).reshape(-1)
    return [from_wire(stype, w, raw.tobytes(), None)]


register_codec(
    CodecSpec(
        "transpose",
        codec_id=5,
        encode=_transpose_enc,
        decode=_transpose_dec,
        doc="byte-plane shuffle (Blosc-style); makes high bytes runs (paper §IV)",
        sig=CodecSig(
            inputs=(_BYTEPLANE_PORT,),
            transfer=lambda atoms, params, n_out: [(_SERIAL, 1)],
        ),
    )
)


# ----------------------------------------------------------- transpose_split
def _transpose_split_enc(streams, params):
    s = streams[0]
    if s.stype not in (SType.STRUCT, SType.NUMERIC):
        raise ValueError("transpose_split wants struct/numeric input")
    raw = np.frombuffer(s.content_bytes(), dtype=np.uint8)
    w = s.width
    mat = raw.reshape(-1, w)
    outs = [Stream(np.ascontiguousarray(mat[:, j]), SType.SERIAL, 1) for j in range(w)]
    h = HeaderWriter().u8(int(s.stype)).varint(w).done()
    return outs, h


def _transpose_split_dec(outs, header):
    r = HeaderReader(header)
    stype = SType(r.u8())
    w = r.varint()
    r.expect_end()
    n = outs[0].data.size
    mat = np.empty((n, w), dtype=np.uint8)
    for j, o in enumerate(outs):
        mat[:, j] = o.data
    return [from_wire(stype, w, mat.reshape(-1).tobytes(), None)]


register_codec(
    CodecSpec(
        "transpose_split",
        codec_id=22,
        encode=_transpose_split_enc,
        decode=_transpose_split_dec,
        n_outputs=-1,
        doc="byte planes as separate outputs so each plane gets its own backend",
        sig=CodecSig(
            inputs=(_BYTEPLANE_PORT,),
            transfer=lambda atoms, params, n_out: (
                None
                if atoms[0][1] is not None and atoms[0][1] != n_out
                else [(_SERIAL, 1)] * n_out
            ),
        ),
    )
)


# ------------------------------------------------------------------- bitpack
# Fixed-width packing in groups of 64 values: value k of a group starts at
# bit k * bits of it, so every group fills exactly ``bits`` little-endian
# 64-bit words, laid out the same way in every group.
_PACK_ROWS = 1024  # groups a transpose moves at once (512 KiB of uint64)
_pack_lock = threading.Lock()
_pack_counts = {c: {"calls": 0, "values": 0}
                for c in ("bitpack", "range_pack", "fused_delta_bitpack")}


def _count_pack(codec: str, n: int) -> None:
    with _pack_lock:
        _pack_counts[codec]["calls"] += 1
        _pack_counts[codec]["values"] += n


def pack_bits_info() -> dict:
    """Host bit-packs in this process by the codec that asked for them:
    ``{codec: {"calls", "values"}}`` for ``bitpack``, ``range_pack`` and
    ``fused_delta_bitpack``."""
    with _pack_lock:
        return {c: dict(v) for c, v in _pack_counts.items()}


def _transpose_into(src: np.ndarray, dst: np.ndarray) -> None:
    """``dst[:, :len(src)] = src.T``, a block of rows at a time: one strided
    copy of the whole array runs about 3x slower once it leaves the cache."""
    rows = src.shape[0]
    for i in range(0, rows, _PACK_ROWS):
        j = min(i + _PACK_ROWS, rows)
        dst[:, i:j] = src[i:j].T


def _pack_bits(vals: np.ndarray, bits: int) -> np.ndarray:
    """Pack unsigned values (< 2^bits) LSB-first into bytes.  bits <= 57 so a
    single unaligned 8-byte window always covers a value (see _unpack_bits)."""
    if bits > 57:
        raise ValueError("bitpack supports <= 57 bits per value; store instead")
    n = vals.size
    full, tail = divmod(n, 64)
    groups = full + (tail > 0)
    slots = np.empty((64, groups), dtype=np.uint64)  # row k: value k of each group
    _transpose_into(vals[: full * 64].reshape(full, 64), slots)
    if tail:
        slots[:, full] = 0
        slots[:tail, full] = vals[full * 64 :]
    words = np.zeros((bits, groups), dtype=np.uint64)  # row w: word w of each group
    tmp = np.empty(groups, dtype=np.uint64)
    for k in range(64):
        w, s = divmod(k * bits, 64)
        np.left_shift(slots[k], np.uint64(s), out=tmp)
        np.bitwise_or(words[w], tmp, out=words[w])
        if s + bits > 64:  # the value runs on into the next word
            np.right_shift(slots[k], np.uint64(64 - s), out=tmp)
            np.bitwise_or(words[w + 1], tmp, out=words[w + 1])
    out = np.empty((groups, bits), dtype="<u8")
    _transpose_into(words, out)
    return out.view(np.uint8).reshape(-1)[: (n * bits + 7) // 8]


def _unpack_bits(buf: np.ndarray, bits: int, n: int, out_width: int) -> np.ndarray:
    padded = np.zeros(buf.size + 8, dtype=np.uint8)
    padded[: buf.size] = buf
    offs = np.arange(n, dtype=np.int64) * bits
    byte0 = offs >> 3
    # gather 8 consecutive bytes -> u64 window, shift, mask
    gathered = np.zeros(n, dtype=np.uint64)
    for b in range(8):
        gathered |= padded[byte0 + b].astype(np.uint64) << np.uint64(8 * b)
    vals = (gathered >> (offs & 7).astype(np.uint64)) & np.uint64((1 << bits) - 1)
    return vals.astype(UNSIGNED[out_width])


def _bitpack_enc(streams, params):
    s = streams[0]
    x = _require_numeric(s, "bitpack")
    maxv = int(x.max()) if x.size else 0
    bits = int(params.get("bits", 0)) or max(int(maxv).bit_length(), 1)
    if maxv >= (1 << bits):
        raise ValueError(f"bitpack: values need more than {bits} bits")
    packed = _pack_bits(x, bits)
    _count_pack("bitpack", x.size)
    h = HeaderWriter().u8(bits).u8(s.width).varint(x.size).done()
    return [Stream(packed, SType.SERIAL, 1)], h


def _bitpack_dec(outs, header):
    r = HeaderReader(header)
    bits = r.u8()
    width = r.u8()
    n = r.varint()
    r.expect_end()
    vals = _unpack_bits(outs[0].data, bits, n, width)
    return [numeric_stream(vals)]


register_codec(
    CodecSpec(
        "bitpack",
        codec_id=6,
        encode=_bitpack_enc,
        decode=_bitpack_dec,
        doc="pack values into ceil(log2(max+1)) bits, LSB-first",
        sig=CodecSig(
            inputs=(_NUM_PORT,),
            transfer=lambda atoms, params, n_out: [(_SERIAL, 1)],
            params=(ParamSpec("bits", "int", doc="explicit bits/value (0 = fit to max)"),),
            packed_outputs=(0,),
        ),
    )
)


# ---------------------------------------------------------------- range_pack
def _range_pack_enc(streams, params):
    s = streams[0]
    x = _require_numeric(s, "range_pack")
    lo = int(x.min()) if x.size else 0
    shifted = (x - x.dtype.type(lo)).astype(np.uint64)
    maxv = int(shifted.max()) if x.size else 0
    bits = max(int(maxv).bit_length(), 1)
    packed = _pack_bits(shifted, bits)
    _count_pack("range_pack", x.size)
    h = HeaderWriter().u8(bits).u8(s.width).varint(x.size).varint(lo).done()
    return [Stream(packed, SType.SERIAL, 1)], h


def _range_pack_dec(outs, header):
    r = HeaderReader(header)
    bits = r.u8()
    width = r.u8()
    n = r.varint()
    lo = r.varint()
    r.expect_end()
    vals = _unpack_bits(outs[0].data, bits, n, 8)
    vals = (vals + np.uint64(lo)).astype(UNSIGNED[width])
    return [numeric_stream(vals)]


register_codec(
    CodecSpec(
        "range_pack",
        codec_id=13,
        encode=_range_pack_enc,
        decode=_range_pack_dec,
        doc="bounded ints: subtract min then bitpack (paper §IV SDEC0 idea)",
        sig=CodecSig(
            inputs=(_NUM_PORT,),
            transfer=lambda atoms, params, n_out: [(_SERIAL, 1)],
            packed_outputs=(0,),
        ),
    )
)


# ----------------------------------------------------------------------- rle
def _rle_enc(streams, params):
    s = streams[0]
    if s.stype == SType.STRING:
        raise ValueError("rle: fixed-width streams only")
    raw = np.frombuffer(s.content_bytes(), dtype=np.uint8)
    w = s.width if s.stype != SType.SERIAL else 1
    mat = raw.reshape(-1, w)
    n = mat.shape[0]
    if n == 0:
        starts = np.zeros(0, dtype=np.int64)
    else:
        change = np.any(mat[1:] != mat[:-1], axis=1)
        starts = np.concatenate([[0], np.nonzero(change)[0] + 1])
    runs = np.diff(np.concatenate([starts, [n]])).astype(np.uint32)
    values_raw = np.ascontiguousarray(mat[starts]).reshape(-1)
    values = from_wire(s.stype, s.width, values_raw.tobytes(), None)
    h = HeaderWriter().u8(int(s.stype)).varint(s.width).done()
    return [values, numeric_stream(runs)], h


def _rle_dec(outs, header):
    values, runs = outs
    r = HeaderReader(header)
    stype = SType(r.u8())
    width = r.varint()
    r.expect_end()
    w = width if stype != SType.SERIAL else 1
    mat = np.frombuffer(values.content_bytes(), dtype=np.uint8).reshape(-1, w)
    rep = np.repeat(mat, runs.data.astype(np.int64), axis=0).reshape(-1)
    return [from_wire(stype, width, rep.tobytes(), None)]


register_codec(
    CodecSpec(
        "rle",
        codec_id=7,
        encode=_rle_enc,
        decode=_rle_dec,
        n_outputs=2,
        doc="run-length: (values, u32 run lengths) (paper §II-C)",
        sig=CodecSig(
            inputs=(InPort(FIXED_STYPES),),
            transfer=lambda atoms, params, n_out: [atoms[0], (_NUMERIC, 4)],
            expansion=5.0,  # worst case: no runs -> values + 4B/element
        ),
    )
)


# ------------------------------------------------------------------ tokenize
_tokenize_lock = threading.Lock()
_tokenize_paths = {"int_view": 0, "rows": 0, "strings": 0}


def _count_tokenize(path: str) -> None:
    with _tokenize_lock:
        _tokenize_paths[path] += 1


def tokenize_info() -> dict:
    """Tokenize encodes in this process by the path that grouped their
    elements: ``int_view`` (rows of up to 8 bytes, one integer each),
    ``rows`` (wider rows, several 8-byte words each), ``strings``."""
    with _tokenize_lock:
        return dict(_tokenize_paths)


def _row_keys(mat: np.ndarray) -> np.ndarray:
    """Sort keys of the (n, w) uint8 rows ``mat``, equal exactly where the
    rows' bytes are: one unsigned integer a row (zero-extended to 4 or 8
    bytes for widths 3, 5, 6, 7), or (n, k) uint64 words for w > 8."""
    n, w = mat.shape
    if w in UNSIGNED:
        return mat.view(UNSIGNED[w]).reshape(n)
    kw = 4 if w < 4 else 8 * -(-w // 8)
    padded = np.zeros((n, kw), dtype=np.uint8)
    padded[:, :w] = mat
    keys = padded.view(UNSIGNED[min(kw, 8)])
    return keys.reshape(n) if kw <= 8 else keys


def _first_occurrence_ids(mat: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """-> (row of each distinct value's first occurrence, in order of first
    occurrence; each row's id in that order). Only equality of the keys
    shows, never their sort order, so any sort will do: numpy's radix sort
    for 1- and 2-byte keys, its default sort for wider ones."""
    n = mat.shape[0]
    if n == 0:
        return np.zeros(0, dtype=np.intp), np.zeros(0, dtype=np.intp)
    keys = _row_keys(mat)
    start = np.ones(n, dtype=bool)
    if keys.ndim == 1:
        perm = np.argsort(keys, kind="stable" if keys.itemsize <= 2 else None)
        sk = keys[perm]
        np.not_equal(sk[1:], sk[:-1], out=start[1:])
    else:
        perm = np.lexsort(keys.T)
        sk = keys[perm]
        np.any(sk[1:] != sk[:-1], axis=1, out=start[1:])
    first = np.minimum.reduceat(perm, np.flatnonzero(start))
    order = np.argsort(first, kind="stable")
    rank = np.empty_like(order)
    rank[order] = np.arange(order.size)
    inv = np.empty(n, dtype=np.intp)
    inv[perm] = rank[np.cumsum(start) - 1]
    return first[order], inv


def _tokenize_enc(streams, params):
    s = streams[0]
    if s.stype == SType.STRING:
        _count_tokenize("strings")
        items = s.to_strings()
        seen = {}
        order: List[bytes] = []
        idx = np.empty(len(items), dtype=np.int64)
        for i, it in enumerate(items):
            j = seen.get(it)
            if j is None:
                j = len(order)
                seen[it] = j
                order.append(it)
            idx[i] = j
        from repro.core.message import strings as mk_strings

        alphabet = mk_strings(order)
        # indices are ALWAYS u32: predictable output types keep the graph
        # type system static (downstream bitpack/range_pack reclaim the bits)
        indices = numeric_stream(idx.astype(np.uint32))
        h = HeaderWriter().u8(1).u8(4).done()
        return [alphabet, indices], h
    raw = np.frombuffer(s.content_bytes(), dtype=np.uint8)
    w = s.width if s.stype != SType.SERIAL else 1
    mat = raw.reshape(-1, w)
    _count_tokenize("int_view" if w <= 8 else "rows")
    # first-occurrence ordering keeps the alphabet stable for delta-friendly ids
    first, inv = _first_occurrence_ids(mat)
    alphabet = from_wire(s.stype, s.width, mat[first].tobytes(), None)
    indices = numeric_stream(inv.astype(np.uint32))  # always u32 (see above)
    h = HeaderWriter().u8(0).u8(4).done()
    return [alphabet, indices], h


def _tokenize_dec(outs, header):
    alphabet, indices = outs
    r = HeaderReader(header)
    is_string = r.u8()
    _iw = r.u8()
    r.expect_end()
    idx = indices.data.astype(np.int64)
    if is_string:
        items = alphabet.to_strings()
        from repro.core.message import strings as mk_strings

        return [mk_strings([items[i] for i in idx.tolist()])]
    w = alphabet.width if alphabet.stype != SType.SERIAL else 1
    mat = np.frombuffer(alphabet.content_bytes(), dtype=np.uint8).reshape(-1, w)
    out = np.ascontiguousarray(mat[idx]).reshape(-1)
    return [from_wire(alphabet.stype, alphabet.width, out.tobytes(), None)]


register_codec(
    CodecSpec(
        "tokenize",
        codec_id=9,
        encode=_tokenize_enc,
        decode=_tokenize_dec,
        n_outputs=2,
        min_version=2,
        doc="(alphabet, indices) split — the paper's motivating codec (§III-C)",
        sig=CodecSig(
            inputs=(InPort(ANY_STYPES),),
            transfer=lambda atoms, params, n_out: [atoms[0], (_NUMERIC, 4)],
            expansion=5.0,  # worst case: all-unique u8 -> alphabet + 4B indices
        ),
    )
)


# ------------------------------------------------- fused delta+bitpack (K1)
# Wire twin of kernels/fused_delta_bitpack.py: one HBM pass instead of two.
# Semantics are fixed in the u32 domain (matching the kernel): d[0] = x[0],
# d[i] = (x[i] - x[i-1]) mod 2^32, packed LSB-first at `bits` per value with
# bits | 32 — which makes the packed words' little-endian bytes identical to
# the host bitpack's continuous bitstream.
FUSED_BITS_CHOICES = (1, 2, 4, 8, 16, 32)
# dynamic bit selection stops here: packing >16 bits per delta loses to
# running delta+bitpack separately (which adapts to the stream width)
_FUSED_DYNAMIC_MAX_BITS = 16


def _u32_delta(s: Stream) -> np.ndarray:
    x = s.data.view(UNSIGNED[s.width]).astype(np.uint32, copy=False)
    d = np.empty_like(x)
    if x.size:
        d[0] = x[0]
        np.subtract(x[1:], x[:-1], out=d[1:])
    return d


def _bits_for_need(need: int, explicit_bits: int) -> Optional[int]:
    """Packing width for a max-delta bit length, or None to refuse.

    Dynamic selection only fuses when the width is *exact* (need is itself a
    32-divisor <= 16): rounding 3 bits up to 4 would inflate the packed
    stream vs separate delta+bitpack, which the executor runs in place of a
    refused step.  Explicit widths are the caller's ratio decision and are
    honored as long as the kernel can express them.
    """
    if explicit_bits:
        if explicit_bits not in FUSED_BITS_CHOICES or need > explicit_bits:
            return None
        return explicit_bits
    if need in FUSED_BITS_CHOICES and need <= _FUSED_DYNAMIC_MAX_BITS:
        return need
    return None


def _bits_for_delta(d: np.ndarray, explicit_bits: int) -> Optional[int]:
    maxd = int(d.max()) if d.size else 0
    return _bits_for_need(max(maxd.bit_length(), 1), explicit_bits)


def _fused_enc(streams, params):
    s = streams[0]
    if s.stype != SType.NUMERIC or s.width not in (1, 2, 4):
        raise ValueError("fused_delta_bitpack: numeric(1/2/4) streams only")
    d = _u32_delta(s)  # computed once: precondition check and packing share it
    bits = _bits_for_delta(d, int(params.get("bits", 0)))
    if bits is None:
        raise ValueError(
            "fused_delta_bitpack: lossless precondition failed (delta too wide)"
        )
    packed = _pack_bits(d, bits)
    _count_pack("fused_delta_bitpack", d.size)
    h = HeaderWriter().u8(bits).u8(s.width).varint(s.n_elts).done()
    return [Stream(packed, SType.SERIAL, 1)], h


def _fused_dec(outs, header):
    r = HeaderReader(header)
    bits = r.u8()
    width = r.u8()
    n = r.varint()
    r.expect_end()
    d = _unpack_bits(outs[0].data, bits, n, 4)
    with np.errstate(over="ignore"):
        x = np.cumsum(d, dtype=np.uint32)
    return [numeric_stream(x.astype(UNSIGNED[width], copy=False))]


register_codec(
    CodecSpec(
        "fused_delta_bitpack",
        codec_id=26,
        encode=_fused_enc,
        decode=_fused_dec,
        min_version=4,
        doc="single-pass delta+bitpack (device kernel K1); u32-domain deltas",
        sig=CodecSig(
            inputs=(InPort(frozenset((_NUMERIC,)), frozenset((1, 2, 4))),),
            transfer=lambda atoms, params, n_out: [(_SERIAL, 1)],
            params=(ParamSpec("bits", "int", choices=FUSED_BITS_CHOICES,
                              doc="explicit packing width (0 = dynamic exact fit)"),),
            packed_outputs=(0,),
        ),
    )
)


# --------------------------------------------------------------- device twins
# Encoders routed through the jit'd Pallas wrappers (kernels/ops.py).  Each
# `applies` predicate gates on exactly the shapes the kernel expresses; the
# engine falls back to the host encoder otherwise.  Outputs and headers are
# bit-identical to the host path — verified by tests/test_engine_phases.py.
def _dev_ready(s: Stream, widths=(1, 2, 4)) -> bool:
    return device_available() and s.stype == SType.NUMERIC and s.width in widths


def _delta_applies_device(streams, params):
    return _dev_ready(streams[0])


def _delta_enc_device(streams, params):
    from repro.kernels import ops

    s = streams[0]
    x = s.data.view(UNSIGNED[s.width])
    d32 = to_host(ops.delta_encode(to_device(x.astype(np.uint32, copy=False))))
    # truncating back to the stream width is exact: subtraction mod 2^32
    # then mod 2^(8w) equals subtraction mod 2^(8w)
    return [numeric_stream(d32.astype(UNSIGNED[s.width], copy=False))], b""


register_backend_codec("device", "delta", _delta_enc_device, _delta_applies_device)


def _bitpack_applies_device(streams, params):
    """One max() pass decides routability; the chosen bits are stashed in
    ``params`` (run_encode_via passes the same dict to applies and encode) so
    the encoder does not rescan the array."""
    s = streams[0]
    if not _dev_ready(s):
        return False
    x = s.data.view(UNSIGNED[s.width])
    maxv = int(x.max()) if x.size else 0
    bits = int(params.get("bits", 0)) or max(maxv.bit_length(), 1)
    # the kernel packs u32 words: bits must divide 32 and values must fit
    if bits not in FUSED_BITS_CHOICES or maxv >= (1 << bits):
        return False
    params["_device_bits"] = bits
    return True


def _packed_words_to_bytes(words: np.ndarray, n: int, bits: int) -> np.ndarray:
    """LE word bytes truncated to the host codec's ceil(n*bits/8) length."""
    nbytes = (n * bits + 7) // 8
    return np.ascontiguousarray(words.view(np.uint8)[:nbytes])


def _bitpack_enc_device(streams, params):
    from repro.kernels import ops

    s = streams[0]
    x = s.data.view(UNSIGNED[s.width])
    bits = params.get("_device_bits") or int(params.get("bits", 0)) or max(
        (int(x.max()) if x.size else 0).bit_length(), 1
    )
    words = to_host(ops.bitpack(to_device(x.astype(np.uint32, copy=False)), bits))
    packed = _packed_words_to_bytes(words, x.size, bits)
    h = HeaderWriter().u8(bits).u8(s.width).varint(x.size).done()
    return [Stream(packed, SType.SERIAL, 1)], h


register_backend_codec("device", "bitpack", _bitpack_enc_device, _bitpack_applies_device)


def _fused_applies_device(streams, params):
    # static checks only; the encoder validates the data-dependent lossless
    # precondition itself and refuses, as the host encoder does
    explicit = int(params.get("bits", 0))
    return _dev_ready(streams[0]) and (
        not explicit or explicit in FUSED_BITS_CHOICES
    )


def _fused_enc_device(streams, params):
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    s = streams[0]
    if s.stype != SType.NUMERIC or s.width not in (1, 2, 4):
        raise ValueError("fused_delta_bitpack: numeric(1/2/4) streams only")
    x = s.data.view(UNSIGNED[s.width]).astype(np.uint32, copy=False)
    xj = to_device(x)
    # precondition check stays on device — the host never touches the deltas
    maxd = int(to_host(jnp.max(ref.delta_encode(xj)))) if x.size else 0
    bits = _bits_for_need(max(maxd.bit_length(), 1), int(params.get("bits", 0)))
    if bits is None:
        raise ValueError(
            "fused_delta_bitpack: lossless precondition failed (delta too wide)"
        )
    words = to_host(ops.fused_delta_bitpack(xj, bits))
    packed = _packed_words_to_bytes(words, x.size, bits).copy()
    # the kernel zero-pads the *input*, so the padding deltas (0 - x[-1]) can
    # smear garbage into the final partial byte; the host bitstream is zero
    # there — mask to stay bit-identical
    tail_bits = (x.size * bits) % 8
    if tail_bits and packed.size:
        packed[-1] &= (1 << tail_bits) - 1
    h = HeaderWriter().u8(bits).u8(s.width).varint(x.size).done()
    return [Stream(packed, SType.SERIAL, 1)], h


register_backend_codec(
    "device", "fused_delta_bitpack", _fused_enc_device, _fused_applies_device
)


def _shuffle_planes(s: Stream) -> np.ndarray:
    """(w, n) byte planes of a fixed-width stream via the byteshuffle kernel."""
    from repro.kernels import ops

    raw = np.frombuffer(s.content_bytes(), dtype=np.uint8)
    mat = raw.reshape(-1, s.width)
    return to_host(ops.byteshuffle(to_device(mat)))


def _transpose_applies_device(streams, params):
    s = streams[0]
    return (
        device_available()
        and s.stype in (SType.STRUCT, SType.NUMERIC)
        and s.width >= 1
    )


def _transpose_enc_device(streams, params):
    s = streams[0]
    planes = _shuffle_planes(s)
    h = HeaderWriter().u8(int(s.stype)).varint(s.width).done()
    return [Stream(np.ascontiguousarray(planes).reshape(-1), SType.SERIAL, 1)], h


register_backend_codec(
    "device", "transpose", _transpose_enc_device, _transpose_applies_device
)


def _transpose_split_enc_device(streams, params):
    s = streams[0]
    planes = _shuffle_planes(s)
    outs = [
        Stream(np.ascontiguousarray(planes[j]), SType.SERIAL, 1)
        for j in range(s.width)
    ]
    h = HeaderWriter().u8(int(s.stype)).varint(s.width).done()
    return outs, h


register_backend_codec(
    "device", "transpose_split", _transpose_split_enc_device, _transpose_applies_device
)
