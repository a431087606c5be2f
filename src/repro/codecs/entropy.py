"""Entropy coders (paper §II-A): canonical Huffman and tANS (FSE).

Both are implemented **block-parallel**: the input is cut into fixed-size
blocks; each block carries its own bit offset (and, for tANS, final state) in
a *separate output stream*.  Decoding then runs one vectorized "lane" per
block — the per-symbol loop is over positions-within-block while every block
advances simultaneously.  This is the TPU adaptation of OpenZL's byte-serial
CPU kernels (DESIGN.md §2): on TPU the lanes map onto the 8×128 VPU; on this
CPU host they map onto numpy vectors.  The block-offset stream is itself a
numeric stream, so a graph can delta+bitpack it — metadata is just more data
for the graph to compress (very much in the paper's spirit).

Lane-refill scheme
------------------
Every lane keeps a bit cursor into its block's bitstream.  One decode step
refills all lanes' 64-bit windows with a *single* gather — an 8-byte
``sliding_window_view`` row per lane, viewed as one little-endian ``uint64``
— instead of the historical 8-iteration per-byte loop.  A refilled window
holds >= 57 valid bits after cursor alignment, so Huffman decode consumes up
to three symbols (3 x 15-bit max codes = 45 bits) per refill.  Tail lanes
are handled mask-free: every lane is full except the last, so the hot loop
runs unmasked and the final partial lane is trimmed at concatenation (the
bitstream buffer is padded so overrunning lanes read zeros, never OOB).
``repro.kernels.ops.lane_refill`` is the device-backend twin of the gather.

Coder-table cache
-----------------
Decode LUTs (2^15 entries) and tANS spread/state tables (2^table_log) are
pure functions of wire-visible descriptors (code lengths / normalized
counts), so they are memoized in ``repro.codecs.coder_cache`` — repeated
chunks and the engine's ``chunk_bytes=N`` thread pool stop rebuilding
identical tables per chunk.  All table construction is vectorized; no
``O(2^table_log)`` Python loops remain on any per-call path.

Wire layout per codec (unchanged — frames are bit-identical to the
pre-vectorization implementation):
  huffman: outputs = [bitstream SERIAL, block_bit_offsets NUMERIC u64]
           header  = n_symbols, block_size_log, 256 nibble-packed code lengths
  fse:     outputs = [bitstream SERIAL, block_meta NUMERIC u32 (offset, state)]
           header  = n_symbols, block_size_log, table_log, normalized counts
"""
from __future__ import annotations

import heapq
from typing import List, NamedTuple, Tuple

import numpy as np

from repro.core.codec import CodecSig, CodecSpec, InPort, register_codec
from repro.core.message import Stream, SType

from ._stages import stage as _stage
from ._util import HeaderReader, HeaderWriter, numeric_stream
from .coder_cache import active_cache

BLOCK_LOG = 12  # 4096 symbols per lane-block
MAX_CODE_LEN = 15

# Cache blocking (same story as codecs/lz.py): the histogram, the bit-matrix
# writer and the lane decoders chunk their passes so per-pass scratch stays
# LLC-resident — at tens of MiB the unblocked versions streamed multi-hundred
# MiB index/scratch arrays per pass and went DRAM-bound.
_HIST_CHUNK = 1 << 20  # bytes per histogram pass (bincount's intp temp stays small)
_WRITE_CHUNK = 1 << 18  # symbols per bit-writer pass
_DEC_GROUP_BYTES = 1 << 22  # decoded bytes per lane-decoder group

_U64_1 = np.uint64(1)

# byte streams only: serial, numeric(1), struct(1) — exactly what _as_u8 takes
_BYTE_PORT = InPort(
    frozenset((int(SType.SERIAL), int(SType.NUMERIC), int(SType.STRUCT))),
    frozenset((1,)),
)
_U64_7 = np.uint64(7)
_U64_3 = np.uint64(3)


def _as_u8(s: Stream, op: str) -> np.ndarray:
    if s.stype == SType.SERIAL or (s.stype == SType.NUMERIC and s.width == 1):
        return np.frombuffer(s.content_bytes(), dtype=np.uint8)
    if s.stype == SType.STRUCT and s.width == 1:
        return s.data
    raise ValueError(f"{op}: byte streams only (serial / numeric(1)); transpose first")


def _rebuild(stype_tag: int, result: np.ndarray) -> Stream:
    """Type-faithful reconstruction (codecs are bijections INCLUDING type)."""
    from repro.core.message import from_wire

    return from_wire(SType(stype_tag), 1, result.tobytes(), None)


def _freeze(*arrays: np.ndarray) -> Tuple[np.ndarray, ...]:
    """Mark cached tables read-only: they are shared across pool threads."""
    for a in arrays:
        a.setflags(write=False)
    return arrays


def _hist_u8(x: np.ndarray) -> np.ndarray:
    """256-bin byte histogram, chunked.  ``np.bincount`` widens its input to
    intp first; chunking keeps that 8-bytes-per-symbol temporary cache-sized
    instead of materializing it for the whole stream."""
    counts = np.zeros(256, dtype=np.int64)
    for lo in range(0, x.size, _HIST_CHUNK):
        counts += np.bincount(x[lo : lo + _HIST_CHUNK], minlength=256)
    return counts


# =====================================================================
# Canonical Huffman
# =====================================================================
def _huffman_code_lengths(counts: np.ndarray) -> np.ndarray:
    """Package-merge-free Huffman with length cap via count flattening."""
    sym = np.nonzero(counts)[0]
    if sym.size == 0:
        return np.zeros(256, dtype=np.uint8)
    if sym.size == 1:
        lens = np.zeros(256, dtype=np.uint8)
        lens[sym[0]] = 1
        return lens
    c = counts.astype(np.float64)
    for _ in range(16):  # flatten until the cap holds
        heap: List[Tuple[float, int]] = [(c[s], int(s)) for s in sym]
        heapq.heapify(heap)
        parent = {}
        next_id = 256
        while len(heap) > 1:
            a = heapq.heappop(heap)
            b = heapq.heappop(heap)
            parent[a[1]] = next_id
            parent[b[1]] = next_id
            heapq.heappush(heap, (a[0] + b[0], next_id))
            next_id += 1
        lens = np.zeros(256, dtype=np.uint8)
        for s in sym:
            d = 0
            node = int(s)
            while node in parent:
                node = parent[node]
                d += 1
            lens[s] = d
        if lens.max() <= MAX_CODE_LEN:
            return lens
        c = np.maximum(c, c[sym].sum() / (1 << MAX_CODE_LEN))  # flatten tail
    raise AssertionError("huffman length cap failed to converge")


def _canonical_order(lens: np.ndarray) -> np.ndarray:
    """Present symbols sorted by (code length, symbol) — canonical order."""
    order = np.lexsort((np.arange(256), lens))
    return order[np.count_nonzero(lens == 0) :]


def _canonical_codes(lens: np.ndarray) -> np.ndarray:
    """Assign canonical codes; returned bit-reversed for LSB-first packing."""
    codes = np.zeros(256, dtype=np.uint32)
    order = _canonical_order(lens)
    if order.size == 0:
        return codes
    ol = lens[order].astype(np.int64)
    # canonical recurrence code(k) = (code(k-1) + 1) << (L_k - L_{k-1}) in
    # closed form via MSB start positions: start_k = sum over earlier symbols
    # of 2^(15 - L_j), code_k = start_k >> (15 - L_k) — exact because
    # canonical codes tile [0, 2^15) contiguously in canonical order
    widths = (np.int64(1) << (MAX_CODE_LEN - ol)).astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(widths)[:-1]])
    code = (starts >> (MAX_CODE_LEN - ol)).astype(np.int64)
    # bit-reverse each code over its own length: reverse over 15 bits, then
    # shift out the (15 - L) low zeros
    rev = np.zeros_like(code)
    c = code.copy()
    for _ in range(MAX_CODE_LEN):
        rev = (rev << 1) | (c & 1)
        c >>= 1
    codes[order] = (rev >> (MAX_CODE_LEN - ol)).astype(np.uint32)
    return codes


def _rev15_table() -> np.ndarray:
    """idx -> its 15-bit reversal; built once, module-cached."""
    global _REV15
    try:
        return _REV15
    except NameError:
        pass
    x = np.arange(1 << MAX_CODE_LEN, dtype=np.int32)
    r = np.zeros_like(x)
    for _ in range(MAX_CODE_LEN):
        r = (r << 1) | (x & 1)
        x >>= 1
    _REV15 = r
    return _REV15


def _huffman_codes_cached(lens: np.ndarray) -> np.ndarray:
    return active_cache().get_or_build(
        ("huff_enc", lens.tobytes()),
        lambda: _freeze(_canonical_codes(lens))[0],
    )


def _huffman_decode_lut(lens: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(lut_sym u8, lut_len u64): LSB-first 15-bit decode LUT, vectorized.

    Canonical codes tile the MSB-first index space contiguously in canonical
    order, so the MSB-first LUT is a single ``np.repeat``; the LSB-first LUT
    (what the lane decoder indexes with its low window bits) is that table
    permuted by 15-bit reversal.
    """
    order = _canonical_order(lens)
    lut_sym = np.zeros(1 << MAX_CODE_LEN, dtype=np.uint8)
    lut_len = np.zeros(1 << MAX_CODE_LEN, dtype=np.uint64)
    if order.size:
        widths = (np.int64(1) << (MAX_CODE_LEN - lens[order].astype(np.int64)))
        total = int(widths.sum())
        msb_sym = np.zeros(1 << MAX_CODE_LEN, dtype=np.uint8)
        msb_len = np.zeros(1 << MAX_CODE_LEN, dtype=np.uint8)
        msb_sym[:total] = np.repeat(order.astype(np.uint8), widths)
        msb_len[:total] = np.repeat(lens[order], widths)
        rev = _rev15_table()
        lut_sym = msb_sym[rev]
        lut_len = msb_len[rev].astype(np.uint64)
    return _freeze(lut_sym, lut_len)


def _write_bits_blocked(
    values: np.ndarray, nbits: np.ndarray, block: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Pack (value, nbits) pairs LSB-first; returns (bytes, per-symbol bit offs).

    Bit-matrix writer: global bit offsets by cumsum, then one masked scatter
    per bit plane (<= MAX_CODE_LEN planes, each target bit index unique) and
    a single ``np.packbits(bitorder="little")``.  Replaces the historical
    4-round ``bitwise_or.at`` packer, whose buffered ufunc scatter was the
    encode bottleneck at tens of MiB — output bytes are identical.
    """
    n = values.size
    offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(nbits, out=offs[1:])
    total = int(offs[-1])
    out = np.zeros((total + 7) // 8, dtype=np.uint8)
    # chunked by symbols: the unpacked bit matrix, gather indices and plane
    # masks for one chunk stay cache-resident (the full-stream versions were
    # the encode bottleneck at tens of MiB).  A chunk's bit range is aligned
    # down to a byte; the shared boundary byte is OR-merged — exact, because
    # every output bit is written by exactly one symbol.
    for lo in range(0, n, _WRITE_CHUNK):
        hi = min(lo + _WRITE_CHUNK, n)
        base_bit = int(offs[lo]) & ~7
        nbits_c = nbits[lo:hi]
        values_c = values[lo:hi]
        start = offs[lo:hi] - base_bit
        local = int(offs[hi]) - base_bit
        bits = np.zeros((local + 7) // 8 * 8, dtype=np.uint8)
        min_nb = int(nbits_c.min()) if hi > lo else 0
        for b in range(int(nbits_c.max()) if hi > lo else 0):
            if b < min_nb:  # plane present in every symbol: mask-free
                bits[start + b] = (values_c >> b) & 1
            else:
                m = nbits_c > b
                bits[start[m] + b] = (values_c[m] >> b) & 1
        packed = np.packbits(bits, bitorder="little")
        byte0 = base_bit >> 3
        if packed.size:
            out[byte0] |= packed[0]
            out[byte0 + 1 : byte0 + packed.size] = packed[1:]
    return out, offs


def _huffman_enc(streams, params):
    x = _as_u8(streams[0], "huffman")
    n = x.size
    with _stage("table_build"):
        counts = _hist_u8(x)
        lens = _huffman_code_lengths(counts)
        codes = _huffman_codes_cached(lens)
    with _stage("bit_io"):
        nbits = lens[x].astype(np.int64)
        packed, offs = _write_bits_blocked(codes[x], nbits, 1 << BLOCK_LOG)
    block = 1 << BLOCK_LOG
    block_offs = offs[:-1:block] if n else np.zeros(0, np.int64)
    h = HeaderWriter().varint(n).u8(BLOCK_LOG).u8(int(streams[0].stype))
    nib = (lens[0::2] | (lens[1::2] << 4)).astype(np.uint8)  # nibble-pack lengths
    h.bytes_(nib.tobytes())
    return [
        Stream(packed, SType.SERIAL, 1),
        numeric_stream(block_offs.astype(np.uint64)),
    ], h.done()


def _huffman_dec(outs, header):
    bitstream, block_offs_s = outs
    r = HeaderReader(header)
    n = r.varint()
    block_log = r.u8()
    stype_tag = r.u8()
    nib_raw = r.bytes_()
    r.expect_end()
    nib = np.frombuffer(nib_raw, dtype=np.uint8)
    lens = np.zeros(256, dtype=np.uint8)
    lens[0::2] = nib & 0xF
    lens[1::2] = nib >> 4
    with _stage("table_build"):
        lut_sym, lut_len = active_cache().get_or_build(
            ("huff_dec", nib_raw if isinstance(nib_raw, bytes) else bytes(nib_raw)),
            lambda: _huffman_decode_lut(lens),
        )

    block = 1 << block_log
    n_blocks = (n + block - 1) // block
    pos_all = block_offs_s.data.astype(np.uint64).copy()
    if pos_all.size != n_blocks:
        raise ValueError("huffman: block offset count mismatch")
    rem = np.minimum(n - np.arange(n_blocks, dtype=np.int64) * block, block)
    max_rem = int(rem.max()) if n_blocks else 0
    # mask-free loop: exhausted lanes keep decoding zero bits from the pad
    # region (never OOB; the pad absorbs <= 15 bits/symbol of overrun) and
    # their surplus columns are trimmed at concatenation.
    pad = 16 + ((MAX_CODE_LEN * max_rem + 7) >> 3)
    buf = np.zeros(bitstream.data.size + pad, dtype=np.uint8)
    buf[: bitstream.data.size] = bitstream.data
    sliding = np.lib.stride_tricks.sliding_window_view(buf, 8)
    out = np.empty((block, n_blocks), dtype=np.uint8)  # row-major hot stores
    low_mask = np.uint64((1 << MAX_CODE_LEN) - 1)
    # lanes decode in groups so one group's bitstream range and output
    # columns stay cache-resident; small inputs are one group (no change)
    G = max(1, _DEC_GROUP_BYTES // block)
    with _stage("bit_io"):
        for g0 in range(0, n_blocks, G):
            g1 = min(g0 + G, n_blocks)
            pos = pos_all[g0:g1].copy()
            max_rem_g = int(rem[g0:g1].max())
            i = 0
            while i < max_rem_g:
                # one gather refills >= 57 valid bits -> up to 3 symbols/refill
                w = sliding[(pos >> _U64_3)].view(np.uint64)[:, 0]
                w >>= pos & _U64_7
                low = w & low_mask
                ln = lut_len[low]
                out[i, g0:g1] = lut_sym[low]
                if i + 1 < max_rem_g:
                    w >>= ln
                    low = w & low_mask
                    l2 = lut_len[low]
                    out[i + 1, g0:g1] = lut_sym[low]
                    ln += l2
                    if i + 2 < max_rem_g:
                        w >>= l2
                        low = w & low_mask
                        out[i + 2, g0:g1] = lut_sym[low]
                        ln += lut_len[low]
                        pos += ln
                        i += 3
                        continue
                    pos += ln
                    i += 2
                    continue
                pos += ln
                i += 1
    if n_blocks:
        lanes = out.T  # (n_blocks, block); full lanes except possibly the last
        result = np.concatenate(
            [np.ascontiguousarray(lanes[:-1]).reshape(-1), lanes[-1, : rem[-1]]]
        )
    else:
        result = np.zeros(0, np.uint8)
    return [_rebuild(stype_tag, result)]


register_codec(
    CodecSpec(
        "huffman",
        codec_id=14,
        encode=_huffman_enc,
        decode=_huffman_dec,
        n_outputs=2,
        min_version=2,
        doc="canonical Huffman, lane-blocked for parallel decode",
        sig=CodecSig(
            inputs=(_BYTE_PORT,),
            transfer=lambda atoms, params, n_out: [
                (int(SType.SERIAL), 1),
                (int(SType.NUMERIC), 8),
            ],
            expansion=2.0,  # <= 15 bits/byte worst case + lane offsets
            packed_outputs=(0,),
        ),
    )
)


# =====================================================================
# FSE / tANS
# =====================================================================
FSE_BLOCK_LOG = 10  # 1024 symbols/lane-block (encode loops positions, not lanes)


def _normalize_counts(counts: np.ndarray, table_log: int) -> np.ndarray:
    """Largest-remainder normalization of symbol counts to sum 2^table_log."""
    total = 1 << table_log
    n = counts.sum()
    if n == 0:
        raise ValueError("fse: empty input")
    scaled = counts.astype(np.float64) * total / n
    norm = np.floor(scaled).astype(np.int64)
    norm[(counts > 0) & (norm == 0)] = 1  # every present symbol needs a slot
    diff = total - norm.sum()
    if diff > 0:
        order = np.argsort(-(scaled - norm))
        for i in range(int(diff)):
            norm[order[i % order.size]] += 1
    elif diff < 0:
        # remove from the largest (keeping >=1 for present symbols)
        for _ in range(int(-diff)):
            cand = np.argmax(norm - (counts > 0))
            if norm[cand] <= 1:
                cand = int(np.argmax(norm))
            norm[cand] -= 1
    assert norm.sum() == total and (norm[counts > 0] >= 1).all()
    return norm


def _bit_length(x: np.ndarray) -> np.ndarray:
    """Vectorized int bit_length for small non-negative ints (exact)."""
    return np.ceil(np.log2(x.astype(np.float64) + 1.0)).astype(np.int64)


def _spread_symbols(norm: np.ndarray, table_log: int) -> np.ndarray:
    """tANS symbol spread — vectorized: occurrence k lands at (k*step) & mask."""
    total = 1 << table_log
    step = (total >> 1) + (total >> 3) + 3
    positions = (np.arange(total, dtype=np.int64) * step) & (total - 1)
    spread = np.zeros(total, dtype=np.int64)
    spread[positions] = np.repeat(np.arange(norm.size, dtype=np.int64), norm)
    return spread


def _build_tables(norm: np.ndarray, table_log: int):
    """Build tANS encode/decode tables from normalized counts (vectorized).

    Slot-order occurrence ranks come from a stable argsort of the spread:
    slots grouped by symbol, slot order preserved inside each group — which
    is exactly the x' = norm[s]+k numbering of the serial construction.

    Returns (dec_sym, dec_nb, dec_base, enc_table, state_table, delta).
    ``state_table`` is that argsort, FSE's compact ``stateTable``: symbol
    s's row of ``enc_table`` is ``state_table[group_start[s]:][:norm[s]]``,
    so the next state from x' is ``state_table[x' + delta[s]]`` with
    ``delta = group_start - norm`` (2^table_log entries in all, where
    ``enc_table`` pads every symbol to max(norm)).
    """
    total = 1 << table_log
    spread = _spread_symbols(norm, table_log)
    order = np.argsort(spread, kind="stable")
    sym_sorted = spread[order]
    group_start = np.concatenate([[0], np.cumsum(norm)[:-1]])
    rank = np.arange(total, dtype=np.int64) - group_start[sym_sorted]
    x = norm[sym_sorted] + rank  # x' in [norm[s], 2*norm[s])
    nb_sorted = table_log - (_bit_length(x) - 1)
    dec_sym = spread.astype(np.uint8)
    # int32 throughout: slot ids / bases / bit counts all fit, and table
    # memory is what bounds the coder cache's footprint
    dec_nb = np.zeros(total, dtype=np.int32)
    dec_base = np.zeros(total, dtype=np.int32)
    dec_nb[order] = nb_sorted
    dec_base[order] = (x << nb_sorted) - total
    width = int(norm.max()) if norm.max() else 1
    enc_table = np.zeros((norm.size, width), dtype=np.int32)
    enc_table[sym_sorted, rank] = order
    state_table = order.astype(np.int32)
    delta = (group_start - norm).astype(np.int32)
    return dec_sym, dec_nb, dec_base, enc_table, state_table, delta


class FseTables(NamedTuple):
    """Every FSE table for one (norm, table_log); see ``_build_tables``.

    nb0/thr give the emitted bit count as ``nb0 - (X < thr)`` without any
    per-position bit-length loop; st0 is the lane-start state.  The host
    encoder steps through ``enc_table``, the device walk through
    ``state_table`` and ``delta``.
    """

    dec_sym: np.ndarray
    dec_nb: np.ndarray
    dec_base: np.ndarray
    enc_table: np.ndarray
    state_table: np.ndarray
    delta: np.ndarray
    nb0: np.ndarray
    thr: np.ndarray
    st0: np.ndarray


def _fse_tables_cached(norm: np.ndarray, table_log: int) -> FseTables:
    """All FSE tables for (norm, table_log), memoized in the active cache."""

    def build():
        tables = _build_tables(norm, table_log)
        enc_table = tables[3]
        nb0 = (table_log + 1) - _bit_length(norm)
        thr = (norm << np.maximum(nb0, 0)).astype(np.int32)
        st0 = enc_table[:, 0].copy()
        return FseTables(*_freeze(*tables, nb0.astype(np.int32), thr, st0))

    return active_cache().get_or_build(
        ("fse", norm.tobytes(), table_log), build
    )


def _fse_enc(streams, params):
    x = _as_u8(streams[0], "fse")
    n = x.size
    table_log = int(params.get("table_log", 11))
    stype_tag = int(streams[0].stype)
    if n == 0:
        h = (
            HeaderWriter().varint(0).u8(FSE_BLOCK_LOG).u8(table_log)
            .u8(stype_tag).bytes_(b"").done()
        )
        return [Stream(np.zeros(0, np.uint8), SType.SERIAL, 1), numeric_stream(np.zeros(0, np.uint32))], h
    with _stage("table_build"):
        counts = _hist_u8(x)
        norm = _normalize_counts(counts, table_log)
        tabs = _fse_tables_cached(norm, table_log)
    enc_table, nb0t, thrt, st0t = tabs.enc_table, tabs.nb0, tabs.thr, tabs.st0
    total = 1 << table_log

    block = 1 << FSE_BLOCK_LOG
    n_blocks = (n + block - 1) // block
    padded = np.zeros(n_blocks * block, dtype=np.uint8)
    padded[:n] = x
    # transposed lanes: the hot loop reads one *contiguous* row per position
    lanesT = np.ascontiguousarray(padded.reshape(n_blocks, block).T)
    rem = np.minimum(n - np.arange(n_blocks, dtype=np.int64) * block, block)
    max_rem = int(rem.max())

    # tANS encodes backward; every lane is full except the last, so the
    # closed-form masks below replace the historical started/newly state:
    # a lane of length r initializes at position r-1 and emits for i < r-1.
    width = enc_table.shape[1]
    enc_flat = enc_table.reshape(-1)
    state = np.zeros(n_blocks, dtype=np.int64)
    max_bits_per_sym = table_log + 1
    max_flush_bytes = (7 + max_bits_per_sym) // 8
    cap = (block * max_bits_per_sym + 7) // 8 + 8
    bitbuf = np.zeros((n_blocks, cap), dtype=np.uint8)
    flat = bitbuf.reshape(-1)
    lane_base = np.arange(n_blocks, dtype=np.int64) * cap
    acc = np.zeros(n_blocks, dtype=np.uint64)  # pending bits, LSB = oldest
    cnt = np.zeros(n_blocks, dtype=np.int64)  # live bits in acc (< 8 + tl+1)
    bytepos = np.zeros(n_blocks, dtype=np.int64)
    with _stage("bit_io"):
        for i in range(max_rem - 1, -1, -1):
            s = lanesT[i].astype(np.int64)
            emit = rem > i + 1
            X = state + total  # representative value in [total, 2*total)
            nb = nb0t[s] - (X < thrt[s])
            nbe = np.where(emit, nb, 0)
            nbe_u = nbe.astype(np.uint64)
            val = X.astype(np.uint64) & ((_U64_1 << nbe_u) - _U64_1)
            acc |= val << cnt.astype(np.uint64)
            cnt += nbe
            nfl = cnt >> 3
            m = nfl > 0
            if m.any():
                # cnt < 8 + (table_log+1), so a step flushes up to
                # (8 + table_log) // 8 whole bytes — loop the slots, not two
                for slot in range(max_flush_bytes):
                    if slot and not (nfl > slot).any():
                        break
                    ms = m if slot == 0 else nfl > slot
                    flat[lane_base[ms] + bytepos[ms] + slot] = (
                        (acc[ms] >> np.uint64(8 * slot)) & np.uint64(0xFF)
                    ).astype(np.uint8)
                acc >>= (nfl << 3).astype(np.uint64)
                bytepos += nfl
                cnt -= nfl << 3
            # state transition (masked: emitting lanes step, new lanes init)
            xprime = np.clip((X >> nb) - norm[s], 0, width - 1)
            new_state = enc_flat[s * width + xprime]
            state = np.where(
                emit, new_state, np.where(rem == i + 1, st0t[s], state)
            )
        # final partial byte per lane (zero-padded high bits, as the
        # OR-writer did)
        mfin = cnt > 0
        if mfin.any():
            flat[lane_base[mfin] + bytepos[mfin]] = acc[mfin].astype(np.uint8)
        bitpos = (bytepos << 3) + cnt

        # concatenate lane bitstreams: one ragged gather instead of a
        # per-lane Python loop (the loop was ~n/1024 iterations — real time
        # at tens of MiB)
        nbytes = bytepos + (cnt > 0)
        offsets = np.zeros(n_blocks + 1, dtype=np.int64)
        np.cumsum(nbytes, out=offsets[1:])
        total_bytes = int(offsets[-1])
        intra = np.arange(total_bytes, dtype=np.int64) - np.repeat(
            offsets[:-1], nbytes
        )
        stream_out = flat[np.repeat(lane_base, nbytes) + intra]
    # block meta: (bit length, final state) as u32 pairs
    meta = np.empty(n_blocks * 2, dtype=np.uint32)
    meta[0::2] = bitpos.astype(np.uint32)
    meta[1::2] = state.astype(np.uint32)

    h = HeaderWriter().varint(n).u8(FSE_BLOCK_LOG).u8(table_log).u8(stype_tag)
    nz = np.nonzero(norm)[0]
    hw = HeaderWriter()
    hw.varint(nz.size)
    for s in nz:
        hw.varint(int(s))
        hw.varint(int(norm[s]))
    h.bytes_(hw.done())
    return [Stream(stream_out, SType.SERIAL, 1), numeric_stream(meta)], h.done()


def _fse_dec(outs, header):
    bitstream, meta_s = outs
    r = HeaderReader(header)
    n = r.varint()
    block_log = r.u8()
    table_log = r.u8()
    stype_tag = r.u8()
    tbl = HeaderReader(r.bytes_())
    r.expect_end()
    if n == 0:
        return [_rebuild(stype_tag, np.zeros(0, np.uint8))]
    norm = np.zeros(256, dtype=np.int64)
    for _ in range(tbl.varint()):
        s = tbl.varint()
        norm[s] = tbl.varint()
    with _stage("table_build"):
        tabs = _fse_tables_cached(norm, table_log)
    dec_sym, dec_nb, dec_base = tabs.dec_sym, tabs.dec_nb, tabs.dec_base

    block = 1 << block_log
    n_blocks = (n + block - 1) // block
    meta = meta_s.data.astype(np.int64)
    bitlen = meta[0::2]
    state_all = meta[1::2]
    nbytes = (bitlen + 7) // 8
    offsets = np.zeros(n_blocks + 1, dtype=np.int64)
    np.cumsum(nbytes, out=offsets[1:])
    # per-lane padded buffers for vectorized backward reads, filled with one
    # ragged scatter (the historical per-lane Python loop was ~n/1024
    # iterations — real time at tens of MiB)
    cap = int(nbytes.max()) + 16 if n_blocks else 16
    bitbuf = np.zeros((n_blocks, cap), dtype=np.uint8)
    flat = bitbuf.reshape(-1)
    lane_base = np.arange(n_blocks, dtype=np.int64) * cap
    total_bytes = int(offsets[-1])
    intra = np.arange(total_bytes, dtype=np.int64) - np.repeat(
        offsets[:-1], nbytes
    )
    flat[np.repeat(lane_base, nbytes) + intra] = bitstream.data
    sliding = np.lib.stride_tricks.sliding_window_view(flat, 8)
    rem = np.minimum(n - np.arange(n_blocks, dtype=np.int64) * block, block)
    out = np.empty((block, n_blocks), dtype=np.uint8)
    # mask-free: exhausted lanes walk garbage states over the zero pad —
    # always in-table (base+bits stays in [0, total)), trimmed at the end.
    # Lanes decode in groups so one group's bitstream slice and output
    # columns stay cache-resident; small inputs are one group (no change).
    G = max(1, _DEC_GROUP_BYTES // block)
    with _stage("bit_io"):
        for g0 in range(0, n_blocks, G):
            g1 = min(g0 + G, n_blocks)
            state = state_all[g0:g1].copy()
            cursor = bitlen[g0:g1].copy()  # read backward from the end
            lb = lane_base[g0:g1]
            for i in range(int(rem[g0:g1].max())):
                out[i, g0:g1] = dec_sym[state]
                nb = dec_nb[state]
                base = dec_base[state]
                cursor -= nb
                byte0 = np.maximum(cursor >> 3, 0)
                w = sliding[lb + byte0].view(np.uint64)[:, 0]
                bits = (w >> (cursor & 7).astype(np.uint64)) & (
                    (_U64_1 << nb.astype(np.uint64)) - _U64_1
                )
                state = base + bits.astype(np.int64)
    lanes = out.T
    result = np.concatenate(
        [np.ascontiguousarray(lanes[:-1]).reshape(-1), lanes[-1, : rem[-1]]]
    )
    return [_rebuild(stype_tag, result)]


register_codec(
    CodecSpec(
        "fse",
        codec_id=15,
        encode=_fse_enc,
        decode=_fse_dec,
        n_outputs=2,
        min_version=2,
        doc="tANS (FSE): table-driven ANS, lane-blocked (paper §II-A; Duda/Collet)",
        sig=CodecSig(
            inputs=(_BYTE_PORT,),
            transfer=lambda atoms, params, n_out: [
                (int(SType.SERIAL), 1),
                (int(SType.NUMERIC), 4),
            ],
            expansion=2.0,
            packed_outputs=(0,),
        ),
    )
)
