"""Plain reference decoder: the wire format, written from its description.

It imports nothing of the program under test.  A frame is correct when this
decoder turns it back into exactly the bytes the benchmark handed to the
compressor; that is the guarantee every configuration states (lossless).

    frames:     b"OZLJ" u8 version, varint n_inputs, varint n_nodes,
                per node: varint codec_id, varint n_in, n_in x varint edge,
                          varint n_out, varint header_len, header;
                varint n_stored, per stored edge: varint edge, u8 type,
                          varint width, [string: varint count, lengths],
                          varint payload_len, payload;
                u32 crc32 (little endian) of everything before it.
    containers: b"OZLC" u8 version, varint n_chunks,
                per chunk: varint frame_len, frame; u32 crc32.

Node outputs take consecutive edge ids after the graph inputs.  Decoding
walks the nodes backwards, turning each node's outputs into its inputs.
Besides the regenerated inputs, :func:`decode` returns one record per node
(codec, header fields, input and output sizes), from which ``bench/work.py``
counts each kernel's work.
"""
from __future__ import annotations

import bz2
import lzma
import struct
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

SERIAL, STRUCT, NUMERIC, STRING = 0, 1, 2, 3
UINT = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}
FRAME_MAGIC = b"OZLJ"
CONTAINER_MAGIC = b"OZLC"

# codec ids as the wire format numbers them
CODEC_NAMES = {
    1: "store", 2: "dup", 3: "delta", 4: "zigzag", 5: "transpose",
    6: "bitpack", 7: "rle", 8: "constant", 9: "tokenize", 10: "field_split",
    11: "split_n", 12: "concat", 13: "range_pack", 14: "huffman", 15: "fse",
    16: "lz77", 17: "zlib_backend", 18: "float_split", 21: "string_split",
    22: "transpose_split", 23: "interpret_numeric", 24: "lzma_backend",
    25: "bz2_backend", 26: "fused_delta_bitpack",
}


class RefError(ValueError):
    """The frame is malformed or uses a codec the reference does not cover."""


@dataclass
class Edge:
    """One stream: its type tag, element width and little-endian content."""

    stype: int
    width: int
    buf: np.ndarray  # uint8
    lengths: Optional[np.ndarray] = None  # STRING only

    def values(self) -> np.ndarray:
        if self.stype != NUMERIC:
            raise RefError("numeric stream expected")
        return self.buf.view(UINT[self.width])

    @property
    def nbytes(self) -> int:
        return int(self.buf.size)


@dataclass
class NodeRecord:
    """What one node did: enough to count the work of its kernel."""

    codec: str
    header: Dict[str, int]
    ins: List[Tuple[int, int, int]]  # (type, width, bytes) per input
    outs: List[Tuple[int, int, int]] = field(default_factory=list)


def _u8(raw: bytes) -> np.ndarray:
    return np.frombuffer(raw, dtype=np.uint8)


def _num(values: np.ndarray) -> Edge:
    v = np.ascontiguousarray(values)
    return Edge(NUMERIC, v.dtype.itemsize, v.view(np.uint8).reshape(-1))


class _Reader:
    def __init__(self, buf: bytes, pos: int = 0, end: Optional[int] = None):
        self.buf = buf
        self.pos = pos
        self.end = len(buf) if end is None else end

    def u8(self) -> int:
        if self.pos >= self.end:
            raise RefError("truncated")
        v = self.buf[self.pos]
        self.pos += 1
        return v

    def varint(self) -> int:
        value, shift = 0, 0
        while True:
            b = self.u8()
            value |= (b & 0x7F) << shift
            if not b & 0x80:
                return value
            shift += 7
            if shift > 63:
                raise RefError("varint too long")

    def take(self, n: int) -> bytes:
        if self.pos + n > self.end:
            raise RefError("truncated")
        out = self.buf[self.pos : self.pos + n]
        self.pos += n
        return out

    def blob(self) -> bytes:
        return self.take(self.varint())

    def done(self) -> None:
        if self.pos != self.end:
            raise RefError("trailing bytes")


# ------------------------------------------------------------------- frames
def split_container(blob: bytes) -> List[bytes]:
    """A container's chunk frames, or ``[blob]`` for a single frame."""
    if blob[:4] == FRAME_MAGIC:
        return [blob]
    if blob[:4] != CONTAINER_MAGIC or len(blob) < 10:
        raise RefError("neither a frame nor a container")
    (crc,) = struct.unpack("<I", blob[-4:])
    if zlib.crc32(blob[:-4]) & 0xFFFFFFFF != crc:
        raise RefError("container crc mismatch")
    r = _Reader(blob, 5, len(blob) - 4)
    frames = [r.blob() for _ in range(r.varint())]
    r.done()
    if not frames:
        raise RefError("empty container")
    return frames


def _parse_frame(frame: bytes):
    if frame[:4] != FRAME_MAGIC or len(frame) < 9:
        raise RefError("bad frame magic")
    (crc,) = struct.unpack("<I", frame[-4:])
    if zlib.crc32(frame[:-4]) & 0xFFFFFFFF != crc:
        raise RefError("frame crc mismatch")
    r = _Reader(frame, 5, len(frame) - 4)
    n_inputs = r.varint()
    nodes = []
    for _ in range(r.varint()):
        codec_id = r.varint()
        ins = [r.varint() for _ in range(r.varint())]
        n_out = r.varint()
        nodes.append((codec_id, ins, n_out, r.blob()))
    stored: Dict[int, Edge] = {}
    for _ in range(r.varint()):
        eid = r.varint()
        stype = r.u8()
        width = r.varint()
        lengths = None
        if stype == STRING:
            lengths = np.array([r.varint() for _ in range(r.varint())], np.int64)
        payload = _u8(r.take(r.varint()))
        if eid in stored:
            raise RefError(f"edge {eid} stored twice")
        if stype == NUMERIC and width not in UINT:
            raise RefError(f"numeric width {width}")
        stored[eid] = Edge(stype, width, payload, lengths)
    r.done()
    return n_inputs, nodes, stored


def decode(blob: bytes) -> Tuple[List[Edge], List[NodeRecord]]:
    """Frame or container -> (regenerated inputs, one record per node)."""
    frames = split_container(blob)
    records: List[NodeRecord] = []
    parts = []
    for frame in frames:
        ins, recs = _decode_frame(frame)
        parts.append(ins)
        records.extend(recs)
    if len(parts) == 1:
        return parts[0], records
    if any(len(p) != 1 for p in parts):
        raise RefError("container chunks must each hold one input")
    first = parts[0][0]
    if any(p[0].stype != first.stype or p[0].width != first.width for p in parts):
        raise RefError("container chunks disagree on the stream type")
    buf = np.concatenate([p[0].buf for p in parts])
    lengths = None
    if first.stype == STRING:
        lengths = np.concatenate([p[0].lengths for p in parts])
    return [Edge(first.stype, first.width, buf, lengths)], records


def _decode_frame(frame: bytes) -> Tuple[List[Edge], List[NodeRecord]]:
    n_inputs, nodes, edges = _parse_frame(frame)
    out_ids = []
    nxt = n_inputs
    for _, _, n_out, _ in nodes:
        out_ids.append(list(range(nxt, nxt + n_out)))
        nxt += n_out
    records = []
    for (codec_id, in_ids, _, header), outs_ids in zip(reversed(nodes), reversed(out_ids)):
        name = CODEC_NAMES.get(codec_id)
        if name is None:
            raise RefError(f"codec id {codec_id} is not covered by the reference")
        try:
            outs = [edges.pop(e) for e in outs_ids]
        except KeyError as err:
            raise RefError(f"edge {err} missing") from None
        ins, fields = _DECODERS[name](outs, header)
        if len(ins) != len(in_ids):
            raise RefError(f"{name}: {len(ins)} inputs regenerated, frame says {len(in_ids)}")
        for eid, s in zip(in_ids, ins):
            if eid in edges:
                raise RefError(f"edge {eid} regenerated twice")
            edges[eid] = s
        records.append(NodeRecord(
            name, fields,
            [(s.stype, s.width, s.nbytes) for s in ins],
            [(s.stype, s.width, s.nbytes) for s in outs],
        ))
    try:
        inputs = [edges.pop(i) for i in range(n_inputs)]
    except KeyError as err:
        raise RefError(f"input edge {err} not regenerated") from None
    if edges:
        raise RefError(f"edges {sorted(edges)} left unused")
    records.reverse()
    return inputs, records


# ------------------------------------------------------------- bit streams
def _unpack_fixed(buf: np.ndarray, bits: int, n: int) -> np.ndarray:
    """n values of ``bits`` bits each, packed LSB-first one after another."""
    if bits < 1 or bits > 64:
        raise RefError(f"bit width {bits}")
    if (n * bits + 7) // 8 > buf.size:
        raise RefError("bit-packed payload too short")
    if bits <= 57:  # each value lies inside the 8 bytes from its first byte
        pos = np.arange(n, dtype=np.uint64) * np.uint64(bits)
        words = _words(_padded(buf), pos)
        return words & np.uint64((1 << bits) - 1)
    out = np.empty(n, np.uint64)
    step = 1 << 16  # values per block; a block starts on a byte boundary
    for lo in range(0, n, step):
        hi = min(n, lo + step)
        b0 = lo * bits // 8
        nb = ((hi - lo) * bits + 7) // 8
        raw = np.unpackbits(buf[b0 : b0 + nb], bitorder="little")
        mat = raw[: (hi - lo) * bits].reshape(hi - lo, bits).astype(np.uint64)
        acc = np.zeros(hi - lo, np.uint64)
        for j in range(bits):
            acc |= mat[:, j] << np.uint64(j)
        out[lo:hi] = acc
    return out


def _padded(buf: np.ndarray) -> np.ndarray:
    """The stream with 16 zero bytes after it, so 8-byte reads never overrun."""
    pad = np.zeros(buf.size + 16, np.uint8)
    pad[: buf.size] = buf
    return pad


def _words(pad: np.ndarray, bitpos: np.ndarray) -> np.ndarray:
    """For each bit position, the 64 stream bits that start there (LSB first;
    the top bits may be short by the position's offset within its byte)."""
    byte = (bitpos >> np.uint64(3)).astype(np.int64)
    rows = np.lib.stride_tricks.sliding_window_view(pad, 8)[byte]
    words = np.ascontiguousarray(rows).view("<u8").reshape(-1)
    return words >> (bitpos & np.uint64(7)).astype(np.uint64)


# ------------------------------------------------------------------ codecs
def _store(outs, header):
    return [outs[0]], {}


def _dup(outs, header):
    return [outs[0]], {}


def _delta(outs, header):
    d = outs[0].values()
    return [_num(np.cumsum(d, dtype=d.dtype))], {}


def _zigzag(outs, header):
    u = outs[0].values()
    one = u.dtype.type(1)
    return [_num((u >> one) ^ (np.zeros_like(u) - (u & one)))], {}


def _transpose(outs, header):
    r = _Reader(header)
    stype, w = r.u8(), r.varint()
    r.done()
    planes = outs[0].buf
    if w < 1 or planes.size % w:
        raise RefError("transpose: planes do not divide")
    raw = np.ascontiguousarray(planes.reshape(w, -1).T).reshape(-1)
    return [Edge(stype, w, raw)], {"width": w}


def _transpose_split(outs, header):
    r = _Reader(header)
    stype, w = r.u8(), r.varint()
    r.done()
    if len(outs) != w:
        raise RefError("transpose_split: plane count")
    raw = np.ascontiguousarray(np.stack([o.buf for o in outs], axis=1)).reshape(-1)
    return [Edge(stype, w, raw)], {"width": w}


def _bitpack(outs, header):
    r = _Reader(header)
    bits, width, n = r.u8(), r.u8(), r.varint()
    r.done()
    vals = _unpack_fixed(outs[0].buf, bits, n)
    return [_num(vals.astype(UINT[width]))], {"bits": bits, "width": width, "n": n}


def _range_pack(outs, header):
    r = _Reader(header)
    bits, width, n, lo = r.u8(), r.u8(), r.varint(), r.varint()
    r.done()
    vals = _unpack_fixed(outs[0].buf, bits, n) + np.uint64(lo & (2**64 - 1))
    return [_num(vals.astype(UINT[width]))], {"bits": bits, "width": width, "n": n}


def _fused(outs, header):
    r = _Reader(header)
    bits, width, n = r.u8(), r.u8(), r.varint()
    r.done()
    d = _unpack_fixed(outs[0].buf, bits, n).astype(np.uint32)
    x = np.cumsum(d, dtype=np.uint32)
    return [_num(x.astype(UINT[width]))], {"bits": bits, "width": width, "n": n}


def _rle(outs, header):
    values, runs = outs
    r = _Reader(header)
    stype, width = r.u8(), r.varint()
    r.done()
    w = width if stype != SERIAL else 1
    mat = values.buf.reshape(-1, w)
    rep = np.repeat(mat, runs.values().astype(np.int64), axis=0).reshape(-1)
    return [Edge(stype, width, rep)], {}


def _constant(outs, header):
    r = _Reader(header)
    stype, width, n = r.u8(), r.varint(), r.varint()
    value = r.blob()
    r.done()
    return [Edge(stype, width, _u8(value * n))], {}


def _tokenize(outs, header):
    alphabet, indices = outs
    r = _Reader(header)
    is_string = r.u8()
    r.u8()
    r.done()
    idx = indices.values().astype(np.int64)
    if is_string:
        offs = np.concatenate([[0], np.cumsum(alphabet.lengths)])
        items = [bytes(alphabet.buf[offs[i] : offs[i + 1]]) for i in range(alphabet.lengths.size)]
        picked = [items[i] for i in idx.tolist()]
        lens = np.array([len(p) for p in picked], np.int64)
        return [Edge(STRING, 1, _u8(b"".join(picked)), lens)], {}
    w = alphabet.width if alphabet.stype != SERIAL else 1
    mat = alphabet.buf.reshape(-1, w)
    if idx.size and idx.max() >= mat.shape[0]:
        raise RefError("tokenize: index out of the alphabet")
    return [Edge(alphabet.stype, alphabet.width, np.ascontiguousarray(mat[idx]).reshape(-1))], {}


def _field_split(outs, header):
    r = _Reader(header)
    stype, rec_w = r.u8(), r.varint()
    widths = [r.varint() for _ in range(r.varint())]
    r.done()
    n = outs[0].buf.size // widths[0]
    mat = np.concatenate([o.buf.reshape(n, w) for w, o in zip(widths, outs)], axis=1)
    return [Edge(stype, rec_w if stype == STRUCT else 1, mat.reshape(-1))], {}


def _split_n(outs, header):
    r = _Reader(header)
    k = r.varint()
    r.done()
    if len(outs) != k:
        raise RefError("split_n: output count")
    s0 = outs[0]
    return [Edge(s0.stype, s0.width, np.concatenate([o.buf for o in outs]))], {}


def _concat(outs, header):
    s = outs[0]
    r = _Reader(header)
    sizes = [r.varint() for _ in range(r.varint())]
    r.done()
    res = []
    if s.stype == STRING:
        so = co = 0
        for sz in sizes:
            lens = s.lengths[so : so + sz]
            nb = int(lens.sum())
            res.append(Edge(STRING, 1, s.buf[co : co + nb], lens))
            so, co = so + sz, co + nb
        return res, {}
    # sizes count data items: numbers for numeric streams, bytes otherwise
    per = s.width if s.stype == NUMERIC else 1
    off = 0
    for sz in sizes:
        res.append(Edge(s.stype, s.width, s.buf[off * per : (off + sz) * per]))
        off += sz
    return res, {}


def _string_split(outs, header):
    content, lens = outs
    return [Edge(STRING, 1, content.buf, lens.values().astype(np.int64))], {}


def _interpret_numeric(outs, header):
    r = _Reader(header)
    stype, width = r.u8(), r.varint()
    r.done()
    return [Edge(stype, width, outs[0].buf)], {}


def _byte_backend(fn):
    def dec(outs, header):
        r = _Reader(header)
        stype, width = r.u8(), r.varint()
        r.done()
        return [Edge(stype, width, _u8(fn(outs[0].buf.tobytes())))], {}

    return dec


def _float_split(outs, header):
    signs, exp, man = outs
    r = _Reader(header)
    fmt, n = r.u8(), r.varint()
    r.done()
    formats = {0: (2, 8, 7), 1: (2, 5, 10), 2: (4, 8, 23), 3: (8, 11, 52)}
    if fmt not in formats:
        raise RefError(f"float_split fmt {fmt}")
    width, e_bits, m_bits = formats[fmt]
    s = np.unpackbits(signs.buf)[:n].astype(np.uint64)  # MSB-first bit order
    e = exp.values().astype(np.uint64)
    m = man.values().astype(np.uint64)
    if s.size != n or e.size != n or m.size != n:
        raise RefError("float_split: plane sizes")
    u = (s << np.uint64(e_bits + m_bits)) | (e << np.uint64(m_bits)) | m
    return [_num(u.astype(UINT[width]))], {"fmt": fmt, "n": n}


def _huffman(outs, header):
    """Canonical Huffman in lanes: block k of 2^block_log symbols starts at
    bit offset ``offs[k]``; codes are written LSB-first, first code bit
    lowest, codes ordered by (length, symbol) as canonical Huffman does."""
    bitstream, offs_s = outs
    r = _Reader(header)
    n, block_log, stype = r.varint(), r.u8(), r.u8()
    nib = np.frombuffer(r.blob(), np.uint8)
    r.done()
    if nib.size != 128:
        raise RefError("huffman: code length table")
    lens = np.empty(256, np.int64)
    lens[0::2] = nib & 0xF
    lens[1::2] = nib >> 4
    max_len = 15
    sym_of = np.zeros(1 << max_len, np.uint8)
    len_of = np.zeros(1 << max_len, np.int64)  # 0: no code starts this way
    order = sorted((int(lens[s]), s) for s in range(256) if lens[s])
    code, prev = 0, order[0][0] if order else 0
    for k, (length, sym) in enumerate(order):
        if k:
            code = (code + 1) << (length - prev)
        prev = length
        if code >= 1 << length:
            raise RefError("huffman: code lengths oversubscribed")
        rev = int(format(code, f"0{length}b")[::-1], 2)
        slots = rev + (np.arange(1 << (max_len - length)) << length)
        sym_of[slots] = sym
        len_of[slots] = length
    block = 1 << block_log
    n_blocks = -(-n // block)
    starts = offs_s.values().astype(np.uint64)
    if starts.size != n_blocks:
        raise RefError("huffman: lane count")
    rem = np.minimum(n - np.arange(n_blocks) * block, block)
    out = np.zeros((n_blocks, block), np.uint8)
    pos = starts.copy()
    pad = _padded(bitstream.buf)
    if n_blocks and int(starts.max()) > bitstream.buf.size * 8:
        raise RefError("huffman: lane offset beyond the stream")
    mask = np.uint64((1 << max_len) - 1)
    for i in range(int(rem.max()) if n_blocks else 0):
        live = rem > i
        window = (_words(pad, pos) & mask).astype(np.int64)
        ln = np.where(live, len_of[window], 0)
        if (ln[live] == 0).any():
            raise RefError("huffman: no code matches the stream")
        out[:, i] = sym_of[window]
        pos += ln.astype(np.uint64)
    # lanes are one bit stream cut at symbol boundaries: each lane ends
    # where the next begins, and the last ends in the stream's last byte
    if n_blocks and (
        (pos[:-1] != starts[1:]).any()
        or (int(pos[-1]) + 7) // 8 != bitstream.buf.size
    ):
        raise RefError("huffman: lane boundaries do not match the stream")
    data = np.concatenate([out[k, : rem[k]] for k in range(n_blocks)]) if n_blocks else np.zeros(0, np.uint8)
    return [Edge(stype, 1, data)], {"n": n}


def _fse_tables(norm: np.ndarray, table_log: int):
    total = 1 << table_log
    if int(norm.sum()) != total:
        raise RefError("fse: normalized counts do not sum to the table size")
    mask = total - 1
    step = (total >> 1) + (total >> 3) + 3
    table_sym = np.zeros(total, np.int64)
    p = 0
    for s in np.nonzero(norm)[0]:
        for _ in range(int(norm[s])):
            table_sym[p] = s
            p = (p + step) & mask
    nxt = norm.copy()
    nb = np.zeros(total, np.int64)
    base = np.zeros(total, np.int64)
    for u in range(total):
        s = table_sym[u]
        x = int(nxt[s])
        nxt[s] += 1
        k = table_log - (x.bit_length() - 1)
        nb[u] = k
        base[u] = (x << k) - total
    return table_sym.astype(np.uint8), nb, base


def _fse(outs, header):
    """tANS in lanes of 2^block_log symbols.  Each lane stores its bit length
    and final state; its bits are read backwards from the end, and each
    state names its symbol, its bit count and the base of the next state."""
    bitstream, meta_s = outs
    r = _Reader(header)
    n, block_log, table_log, stype = r.varint(), r.u8(), r.u8(), r.u8()
    t = _Reader(r.blob())
    norm = np.zeros(256, np.int64)
    for _ in range(t.varint()):
        s = t.varint()
        norm[s] = t.varint()
    t.done()
    r.done()
    if n == 0:
        return [Edge(stype, 1, np.zeros(0, np.uint8))], {"n": 0}
    sym, nb, base = _fse_tables(norm, table_log)
    total = 1 << table_log
    block = 1 << block_log
    n_blocks = -(-n // block)
    meta = meta_s.values().astype(np.int64)
    if meta.size != 2 * n_blocks:
        raise RefError("fse: lane count")
    bitlen, state = meta[0::2], meta[1::2].copy()
    if (state >= total).any() or (state < 0).any():
        raise RefError("fse: state out of the table")
    nbytes = (bitlen + 7) // 8
    lane_start = np.concatenate([[0], np.cumsum(nbytes)[:-1]]) * 8
    if int(nbytes.sum()) != bitstream.buf.size:
        raise RefError("fse: lane sizes do not add up to the stream")
    rem = np.minimum(n - np.arange(n_blocks) * block, block)
    out = np.zeros((n_blocks, block), np.uint8)
    cursor = bitlen.copy()
    pad = _padded(bitstream.buf)
    for i in range(int(rem.max())):
        out[:, i] = sym[state]
        read = rem > i + 1  # the last symbol of a lane reads no bits
        k = np.where(read, nb[state], 0)
        cursor -= k
        if (cursor < 0).any():
            raise RefError("fse: a lane reads before its start")
        words = _words(pad, (lane_start + cursor).astype(np.uint64))
        bits = (words & ((np.uint64(1) << k.astype(np.uint64)) - np.uint64(1))).astype(np.int64)
        state = np.where(read, base[state] + bits, state)
    if (cursor != 0).any():
        raise RefError("fse: lane bits left unread")
    data = np.concatenate([out[k, : rem[k]] for k in range(n_blocks)])
    return [Edge(stype, 1, data)], {"n": n}


def _lz77(outs, header):
    literals, runs_s, mls_s, offs_s = outs
    r = _Reader(header)
    stype, width, n = r.u8(), r.varint(), r.varint()
    r.done()
    lit = literals.buf.tobytes()
    runs = runs_s.values().tolist()
    mls = mls_s.values().tolist()
    offs = offs_s.values().tolist()
    out = bytearray()
    li = 0
    for k, run in enumerate(runs):
        out += lit[li : li + run]
        li += run
        if k < len(mls) and k < len(offs):
            length, dist = mls[k], offs[k]
            if dist <= 0 or dist > len(out):
                raise RefError("lz77: bad match distance")
            for _ in range(length):  # byte by byte: overlapping copies repeat
                out.append(out[-dist])
    if len(out) != n or li != len(lit):
        raise RefError("lz77: token streams do not add up")
    return [Edge(stype, width, _u8(bytes(out)))], {}


_DECODERS = {
    "store": _store, "dup": _dup, "delta": _delta, "zigzag": _zigzag,
    "transpose": _transpose, "bitpack": _bitpack, "rle": _rle,
    "constant": _constant, "tokenize": _tokenize, "field_split": _field_split,
    "split_n": _split_n, "concat": _concat, "range_pack": _range_pack,
    "huffman": _huffman, "fse": _fse, "lz77": _lz77,
    "zlib_backend": _byte_backend(zlib.decompress),
    "float_split": _float_split, "string_split": _string_split,
    "transpose_split": _transpose_split,
    "interpret_numeric": _interpret_numeric,
    "lzma_backend": _byte_backend(lzma.decompress),
    "bz2_backend": _byte_backend(bz2.decompress),
    "fused_delta_bitpack": _fused,
}


def mismatched_bytes(blob: bytes, want: np.ndarray) -> Tuple[int, List[NodeRecord]]:
    """Bytes of ``want`` that the frame does not give back (every byte when
    it cannot be decoded at all), and the frame's node records."""
    want = np.ascontiguousarray(want).view(np.uint8).reshape(-1)
    try:
        ins, records = decode(blob)
    except (RefError, ValueError, zlib.error, lzma.LZMAError, OSError, IndexError) as err:
        raise RefError(str(err)) from err
    if len(ins) != 1:
        return int(want.size), records
    got = ins[0].buf
    common = min(got.size, want.size)
    diff = int(np.count_nonzero(got[:common] != want[:common]))
    return diff + abs(int(got.size) - int(want.size)), records
