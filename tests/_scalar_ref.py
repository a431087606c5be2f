"""Scalar reference implementations — the PRE-vectorization codec code.

Copied verbatim from the seed implementations of ``codecs/lz.py`` and
``codecs/entropy.py`` (commit 09cade9) with codec registration stripped,
``_tokenize_rows_ref``: ``codecs/numeric.py``'s tokenize encoder as it
was while it grouped fixed-width rows with ``np.unique(axis=0)``, and
``_pack_bits``: its bit packer as it was while it scattered each value's
bytes with ``np.bitwise_or.at`` (commit a3b64db).
The cross-check suite (``test_vectorized_equiv.py``) pins the vectorized
implementations against these: same inputs -> bit-identical output streams
and headers, which is the wire-compatibility guarantee for every frame any
older build ever produced.  Do not "fix" or modernize this module; it is the
specification.
"""
from __future__ import annotations


import zlib
from typing import List

import numpy as np

from repro.core.message import Stream, SType, from_wire

from repro.codecs._util import HeaderReader, HeaderWriter, numeric_stream

MIN_MATCH = 4
MAX_MATCH = 1 << 16


def _prev_occurrence(data: np.ndarray) -> np.ndarray:
    """For each position i, the most recent j<i with the same 4-gram hash."""
    n = data.size
    if n < MIN_MATCH:
        return np.full(n, -1, dtype=np.int64)
    g = (
        data[:-3].astype(np.uint32)
        | (data[1:-2].astype(np.uint32) << 8)
        | (data[2:-1].astype(np.uint32) << 16)
        | (data[3:].astype(np.uint32) << 24)
    )
    h = (g * np.uint32(2654435761)) >> np.uint32(16)  # Knuth hash -> 16 bits
    order = np.argsort(h, kind="stable")
    prev = np.full(n, -1, dtype=np.int64)
    sh = h[order]
    same = np.zeros(order.size, dtype=bool)
    same[1:] = sh[1:] == sh[:-1]
    prev_sorted = np.where(same, np.concatenate([[0], order[:-1]]), -1)
    prev[order] = prev_sorted
    return prev


def _lz77_enc(streams, params):
    s = streams[0]
    if s.stype == SType.STRING:
        raise ValueError("lz77: fixed-width streams only (string_split first)")
    data = np.frombuffer(s.content_bytes(), dtype=np.uint8)
    n = data.size
    prev = _prev_occurrence(data)
    buf = data.tobytes()

    lit_runs: List[int] = []
    match_lens: List[int] = []
    offsets: List[int] = []
    literals = bytearray()
    i = 0
    lit_start = 0
    while i + MIN_MATCH <= n:
        j = prev[i]
        if j >= 0 and j < i and buf[j : j + MIN_MATCH] == buf[i : i + MIN_MATCH]:
            L = _extend(data, j, i, n)
            lit_runs.append(i - lit_start)
            literals += buf[lit_start:i]
            match_lens.append(L)
            offsets.append(i - j)
            i += L
            lit_start = i
        else:
            i += 1
    lit_runs.append(n - lit_start)
    literals += buf[lit_start:n]

    h = HeaderWriter().u8(int(s.stype)).varint(s.width).varint(n).done()
    return [
        Stream(np.frombuffer(bytes(literals), dtype=np.uint8), SType.SERIAL, 1),
        numeric_stream(np.asarray(lit_runs, dtype=np.uint32)),
        numeric_stream(np.asarray(match_lens, dtype=np.uint32)),
        numeric_stream(np.asarray(offsets, dtype=np.uint32)),
    ], h


def _extend(data: np.ndarray, j: int, i: int, n: int) -> int:
    """Longest common extension of data[i:] vs data[j:] (j < i).

    Overlapping matches (dist < L) are legal in LZ77: the copy source keeps
    reading bytes the copy itself just produced, which for the *extension
    check* is equivalent to comparing data[j+L] vs data[i+L] directly —
    data[] already holds the final bytes on the encode side.  So plain
    chunked comparison is correct regardless of overlap.
    """
    L = 0
    limit = min(n - i, MAX_MATCH)
    while L < limit:
        chunk = min(256, limit - L)
        a = data[j + L : j + L + chunk]
        b = data[i + L : i + L + chunk]
        neq = np.nonzero(a != b)[0]
        if neq.size:
            return L + int(neq[0])
        L += chunk
    return L


def _lz77_dec(outs, header):
    literals, lit_runs, match_lens, offsets = outs
    r = HeaderReader(header)
    stype = SType(r.u8())
    width = r.varint()
    n = r.varint()
    r.expect_end()
    out = np.empty(n, dtype=np.uint8)
    lit = literals.data
    runs = lit_runs.data.astype(np.int64)
    mls = match_lens.data.astype(np.int64)
    offs = offsets.data.astype(np.int64)
    pos = 0
    lpos = 0
    for k in range(runs.size):
        rl = int(runs[k])
        if rl:
            out[pos : pos + rl] = lit[lpos : lpos + rl]
            pos += rl
            lpos += rl
        if k < mls.size:
            L = int(mls[k])
            d = int(offs[k])
            src = pos - d
            if d >= L:
                out[pos : pos + L] = out[src : src + L]
            else:  # overlapping copy: replicate the period
                reps = -(-L // d)
                pattern = out[src:pos]
                out[pos : pos + L] = np.tile(pattern, reps)[:L]
            pos += L
    if pos != n:
        raise ValueError("lz77: corrupt token streams")
    from repro.core.message import from_wire

    return [from_wire(stype, width, out.tobytes(), None)]







import heapq
from typing import List, Tuple

import numpy as np

from repro.core.message import Stream, SType

from repro.codecs._util import HeaderReader, HeaderWriter, numeric_stream

BLOCK_LOG = 12  # 4096 symbols per lane-block
MAX_CODE_LEN = 15


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


def _canonical_codes(lens: np.ndarray) -> np.ndarray:
    """Assign canonical codes; returned bit-reversed for LSB-first packing."""
    codes = np.zeros(256, dtype=np.uint32)
    code = 0
    for length in range(1, MAX_CODE_LEN + 1):
        for s in range(256):
            if lens[s] == length:
                # bit-reverse `code` over `length` bits
                rev = int(f"{code:0{length}b}"[::-1], 2)
                codes[s] = rev
                code += 1
        code <<= 1
    return codes


def _write_bits_blocked(
    values: np.ndarray, nbits: np.ndarray, block: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Pack (value, nbits) pairs LSB-first; returns (bytes, per-symbol bit offs).

    Vectorized: global bit offsets by cumsum; each value ORs into <=3 bytes...
    values here are <= 2^15 wide so <= 3 byte-touches after alignment.
    """
    n = values.size
    offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(nbits, out=offs[1:])
    total = int(offs[-1])
    out = np.zeros((total + 7) // 8 + 8, dtype=np.uint8)
    v = values.astype(np.uint64)
    start = offs[:-1]
    for b in range(4):
        byte_idx = (start >> 3) + b
        shift = (np.int64(b) << 3) - (start & 7)
        pos = shift >= 0
        contrib = np.where(
            pos,
            v >> np.where(pos, shift, 0).clip(max=63).astype(np.uint64),
            v << np.where(~pos, -shift, 0).astype(np.uint64),
        )
        contrib = np.where(shift >= 64, 0, contrib)
        np.bitwise_or.at(out, byte_idx, (contrib & 0xFF).astype(np.uint8))
    return out[: (total + 7) // 8], offs


def _huffman_enc(streams, params):
    x = _as_u8(streams[0], "huffman")
    n = x.size
    counts = np.bincount(x, minlength=256)
    lens = _huffman_code_lengths(counts)
    codes = _canonical_codes(lens)
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
    nib = np.frombuffer(r.bytes_(), dtype=np.uint8)
    r.expect_end()
    lens = np.zeros(256, dtype=np.uint8)
    lens[0::2] = nib & 0xF
    lens[1::2] = nib >> 4
    codes = _canonical_codes(lens)

    # build the 2^15 LSB-first decode LUT: lookup[low15] = (symbol, length)
    lut_sym = np.zeros(1 << MAX_CODE_LEN, dtype=np.uint8)
    lut_len = np.zeros(1 << MAX_CODE_LEN, dtype=np.uint8)
    for s in range(256):
        L = int(lens[s])
        if L == 0:
            continue
        base = int(codes[s])
        step = 1 << L
        idx = np.arange(base, 1 << MAX_CODE_LEN, step)
        lut_sym[idx] = s
        lut_len[idx] = L

    block = 1 << block_log
    n_blocks = (n + block - 1) // block
    buf = np.zeros(bitstream.data.size + 16, dtype=np.uint8)
    buf[: bitstream.data.size] = bitstream.data
    pos = block_offs_s.data.astype(np.int64).copy()
    if pos.size != n_blocks:
        raise ValueError("huffman: block offset count mismatch")
    out = np.zeros(n_blocks * block, dtype=np.uint8)
    rem = np.minimum(n - np.arange(n_blocks, dtype=np.int64) * block, block)
    for i in range(block):
        active = rem > i
        if not active.any():
            break
        byte0 = pos >> 3
        window = np.zeros(n_blocks, dtype=np.uint64)
        for b in range(8):
            window |= buf[byte0 + b].astype(np.uint64) << np.uint64(8 * b)
        low = ((window >> (pos & 7).astype(np.uint64)) & np.uint64((1 << MAX_CODE_LEN) - 1)).astype(np.int64)
        sym = lut_sym[low]
        ln = lut_len[low].astype(np.int64)
        out[np.arange(n_blocks) * block + i] = np.where(active, sym, 0)
        pos += np.where(active, ln, 0)
    result = np.concatenate(
        [out[k * block : k * block + int(rem[k])] for k in range(n_blocks)]
    ) if n_blocks else np.zeros(0, np.uint8)
    return [_rebuild(stype_tag, result)]




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


def _spread_symbols(norm: np.ndarray, table_log: int) -> np.ndarray:
    total = 1 << table_log
    step = (total >> 1) + (total >> 3) + 3
    spread = np.zeros(total, dtype=np.int64)
    position = 0
    for s in range(norm.size):
        for _ in range(int(norm[s])):
            spread[position] = s
            position = (position + step) & (total - 1)
    assert position == 0
    return spread


def _build_tables(norm: np.ndarray, table_log: int):
    """Build tANS encode/decode tables from normalized counts."""
    total = 1 << table_log
    spread = _spread_symbols(norm, table_log)
    # decode table: state j -> (symbol, nbits, new_state_base)
    occ = norm.copy()  # next x' per symbol starts at norm[s]
    dec_sym = spread.astype(np.uint8)
    dec_nb = np.zeros(total, dtype=np.int64)
    dec_base = np.zeros(total, dtype=np.int64)
    # encode: k-th (in slot order) occurrence of s maps x' = norm[s]+k -> slot
    enc_slot = {}
    counters = np.zeros(norm.size, dtype=np.int64)
    for j in range(total):
        s = spread[j]
        x = norm[s] + counters[s]
        counters[s] += 1
        nb = table_log - (int(x).bit_length() - 1)
        dec_nb[j] = nb
        dec_base[j] = (int(x) << nb) - total
        enc_slot[(int(s), int(x))] = j
    # per-symbol encode arrays: for x' in [norm[s], 2 norm[s]) -> slot id
    enc_table = np.zeros((norm.size, int(norm.max()) if norm.max() else 1), dtype=np.int64)
    for (s, x), j in enc_slot.items():
        enc_table[s, x - norm[s]] = j
    return dec_sym, dec_nb, dec_base, enc_table


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
    counts = np.bincount(x, minlength=256)
    norm = _normalize_counts(counts, table_log)
    dec_sym, dec_nb, dec_base, enc_table = _build_tables(norm, table_log)
    total = 1 << table_log

    block = 1 << FSE_BLOCK_LOG
    n_blocks = (n + block - 1) // block
    padded = np.zeros(n_blocks * block, dtype=np.uint8)
    padded[:n] = x
    lanes = padded.reshape(n_blocks, block)
    rem = np.minimum(n - np.arange(n_blocks, dtype=np.int64) * block, block)

    norm_l = norm.astype(np.int64)
    # vectorized across blocks; loop positions in reverse (tANS encodes backward)
    state = np.zeros(n_blocks, dtype=np.int64)  # slot ids in [0, total)
    first = True
    max_bits_per_sym = table_log + 1
    cap_bytes = (block * max_bits_per_sym + 7) // 8 + 8
    bitbuf = np.zeros((n_blocks, cap_bytes), dtype=np.uint8)
    bitpos = np.zeros(n_blocks, dtype=np.int64)
    lane_idx = np.arange(n_blocks)
    for i in range(block - 1, -1, -1):
        s = lanes[:, i].astype(np.int64)
        active = rem > i
        f = norm_l[s]
        if first:
            # initial state: x' = f + (something deterministic); use slot of x'=f
            st = enc_table[s, 0]
            state = np.where(active, st, state)
            started = active.copy()
            first = False
            continue
        X = state + total  # representative value in [total, 2*total)
        # nb such that (X >> nb) in [f, 2f): since bit_length(X) == tl+1 exactly,
        # nb0 = tl+1-bit_length(f) gives x0 with bit_length(f) bits; correct -1
        # when x0 < f (see tANS construction; property-tested in tests/).
        bl = np.zeros_like(f)
        ftmp = f.copy()
        while (ftmp > 0).any():
            bl += (ftmp > 0).astype(np.int64)
            ftmp >>= 1
        nb = (table_log + 1) - bl
        nb = np.where((X >> np.maximum(nb, 0)) < f, nb - 1, nb)
        nb = np.maximum(nb, 0)
        newly = active & ~started
        # lanes that start mid-stream (shorter tail lanes): initialize instead
        st_init = enc_table[s, 0]
        sub2 = X >> nb.astype(np.int64)
        emit_mask = active & started
        # emit nb low bits of X for emitting lanes
        val = (X & ((np.int64(1) << nb) - 1)).astype(np.uint64)
        nbe = np.where(emit_mask, nb, 0).astype(np.int64)
        _scatter_bits(bitbuf, bitpos, val, nbe, lane_idx)
        bitpos += nbe
        xprime = np.clip(sub2 - f, 0, enc_table.shape[1] - 1)
        new_state = enc_table[s, xprime]
        state = np.where(emit_mask, new_state, np.where(newly, st_init, state))
        started |= active

    # concatenate lane bitstreams
    nbytes = ((bitpos + 7) // 8).astype(np.int64)
    offsets = np.zeros(n_blocks + 1, dtype=np.int64)
    np.cumsum(nbytes, out=offsets[1:])
    stream_out = np.zeros(int(offsets[-1]), dtype=np.uint8)
    for k in range(n_blocks):
        stream_out[offsets[k] : offsets[k + 1]] = bitbuf[k, : nbytes[k]]
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


def _scatter_bits(bitbuf, bitpos, val, nbits, lane_idx):
    """OR `val` (LSB-first, nbits wide) at per-lane bit cursor `bitpos`."""
    active = nbits > 0
    if not active.any():
        return
    for b in range(4):
        byte_idx = (bitpos >> 3) + b
        shift = (np.int64(b) << 3) - (bitpos & 7)
        pos = shift >= 0
        contrib = np.where(
            pos,
            val >> np.where(pos, shift, 0).clip(max=63).astype(np.uint64),
            val << np.where(~pos, -shift, 0).astype(np.uint64),
        )
        contrib = (contrib & 0xFF).astype(np.uint8)
        contrib = np.where(active & (shift < 64), contrib, 0)
        np.bitwise_or.at(bitbuf, (lane_idx, byte_idx), contrib)


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
    dec_sym, dec_nb, dec_base, _enc = _build_tables(norm, table_log)

    block = 1 << block_log
    n_blocks = (n + block - 1) // block
    meta = meta_s.data.astype(np.int64)
    bitlen = meta[0::2]
    state = meta[1::2].copy()
    nbytes = (bitlen + 7) // 8
    offsets = np.zeros(n_blocks + 1, dtype=np.int64)
    np.cumsum(nbytes, out=offsets[1:])
    # per-lane padded buffers for vectorized backward reads
    cap = int(nbytes.max()) + 16 if n_blocks else 16
    bitbuf = np.zeros((n_blocks, cap), dtype=np.uint8)
    for k in range(n_blocks):
        bitbuf[k, : nbytes[k]] = bitstream.data[offsets[k] : offsets[k + 1]]
    cursor = bitlen.copy()  # read backward from the end
    rem = np.minimum(n - np.arange(n_blocks, dtype=np.int64) * block, block)
    out = np.zeros((n_blocks, block), dtype=np.uint8)
    lane = np.arange(n_blocks)
    for i in range(block):
        active = rem > i
        if not active.any():
            break
        sym = dec_sym[state]
        out[:, i] = np.where(active, sym, 0)
        nb = np.where(active, dec_nb[state], 0)
        base = dec_base[state]
        cursor2 = cursor - nb
        byte0 = (cursor2 >> 3).clip(min=0)
        window = np.zeros(n_blocks, dtype=np.uint64)
        for b in range(8):
            window |= bitbuf[lane, byte0 + b].astype(np.uint64) << np.uint64(8 * b)
        bits = (window >> (cursor2 & 7).astype(np.uint64)) & (
            (np.uint64(1) << nb.astype(np.uint64)) - np.uint64(1)
        )
        state = np.where(active, base + bits.astype(np.int64), state)
        cursor = np.where(active, cursor2, cursor)
    result = np.concatenate([out[k, : rem[k]] for k in range(n_blocks)])
    return [_rebuild(stype_tag, result)]


def _tokenize_rows_ref(streams, params):
    s = streams[0]
    if s.stype == SType.STRING:
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
        indices = numeric_stream(idx.astype(np.uint32))
        h = HeaderWriter().u8(1).u8(4).done()
        return [alphabet, indices], h
    raw = np.frombuffer(s.content_bytes(), dtype=np.uint8)
    w = s.width if s.stype != SType.SERIAL else 1
    mat = raw.reshape(-1, w)
    uniq, first_idx, inv = np.unique(mat, axis=0, return_index=True, return_inverse=True)
    order = np.argsort(first_idx, kind="stable")
    rank = np.empty_like(order)
    rank[order] = np.arange(order.size)
    inv = rank[inv]
    uniq = uniq[order]
    alphabet = from_wire(s.stype, s.width, np.ascontiguousarray(uniq).tobytes(), None)
    indices = numeric_stream(inv.astype(np.uint32))
    h = HeaderWriter().u8(0).u8(4).done()
    return [alphabet, indices], h


def _pack_bits(vals: np.ndarray, bits: int) -> np.ndarray:
    """Pack unsigned values (< 2^bits) LSB-first into bytes.  bits <= 57 so a
    single unaligned 8-byte window always covers a value (see _unpack_bits)."""
    if bits > 57:
        raise ValueError("bitpack supports <= 57 bits per value; store instead")
    n = vals.size
    total_bits = n * bits
    out = np.zeros((total_bits + 7) // 8 + 8, dtype=np.uint8)
    offs = np.arange(n, dtype=np.int64) * bits
    v = vals.astype(np.uint64)
    # each value touches at most ceil(bits/8)+1 bytes
    for b in range((bits + 7) // 8 + 1):
        byte_idx = (offs >> 3) + b
        shift = (np.int64(b) << 3) - (offs & 7)
        pos = shift >= 0
        # two-sided shift without UB: clamp each direction's amount to >= 0
        contrib = np.where(
            pos,
            v >> np.where(pos, shift, 0).clip(max=63).astype(np.uint64),
            v << np.where(~pos, -shift, 0).astype(np.uint64),
        )
        contrib = np.where(shift >= 64, 0, contrib)  # avoid x86 shift-mod-64 UB
        np.bitwise_or.at(out, byte_idx, (contrib & 0xFF).astype(np.uint8))
    return out[: (total_bits + 7) // 8]
