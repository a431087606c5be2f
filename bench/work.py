"""The work of each device kernel, counted from the codec and never from one
implementation's traffic, and the table of peaks.

A node's work is the bytes its codec must read and write, from the sizes the
plain reference decoder regenerates (``reference.NodeRecord``):

    delta                      read n*w, write n*w
    bitpack, fused delta+pack  read n*w, write ceil(n*bits/8)
    transpose(_split)          read n*w, write n*w
    float_split                read n*w, write its sign, exponent and
                               mantissa planes as the codec defines them
    huffman, fse               read n, write the packed bit stream

All of these are bound by memory, so a node's least time on the chip is its
bytes over the peak HBM bandwidth.  A kernel group's roofline share is the sum
of those least times over the device time its compiled programs took.
"""
from __future__ import annotations

import json
from typing import Dict, Iterable, Optional

from bench.common import BENCH
from bench.reference import NUMERIC, SERIAL, STRUCT, NodeRecord

# kernel group -> (codecs whose work counts, compiled programs whose time counts)
GROUPS = {
    "numeric": (
        ("delta", "bitpack", "fused_delta_bitpack", "transpose", "transpose_split",
         "float_split"),
        ("jit_delta_encode", "jit_bitpack", "jit_fused_delta_bitpack",
         "jit_byteshuffle", "jit_float_split"),
    ),
    "entropy": (
        ("huffman", "fse"),
        ("jit_histogram_exact", "jit_huffman_map", "jit_fse_encode", "jit_pack_bits"),
    ),
}

ENTROPY_MIN, ENTROPY_MAX = 1 << 10, 1 << 27  # elements the device twins take
PACK_BITS = (1, 2, 4, 8, 16, 32)  # widths the 32-bit-word packers express


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def on_device(rec: NodeRecord) -> bool:
    """Whether the device backend runs this node, by the routing rules the
    device twins state: numeric twins take widths 1, 2 and 4; transposes take
    fixed-width records; float_split takes float32; the entropy twins take
    byte streams of 1 KiB to 128 MiB."""
    stype, width, nbytes = rec.ins[0]
    if rec.codec in ("delta", "fused_delta_bitpack"):
        return stype == NUMERIC and width in (1, 2, 4)
    if rec.codec == "bitpack":
        return stype == NUMERIC and width in (1, 2, 4) and rec.header.get("bits") in PACK_BITS
    if rec.codec in ("transpose", "transpose_split"):
        return stype in (STRUCT, NUMERIC)
    if rec.codec == "float_split":
        return stype == NUMERIC and width == 4 and rec.header.get("fmt") == 2
    if rec.codec in ("huffman", "fse"):
        byte_stream = stype == SERIAL or (stype in (NUMERIC, STRUCT) and width == 1)
        return byte_stream and ENTROPY_MIN <= nbytes <= ENTROPY_MAX
    return False


def node_bytes(rec: NodeRecord) -> int:
    """Bytes the codec reads and writes (the table in the module docstring)."""
    read = sum(b for _, _, b in rec.ins)
    if rec.codec in ("huffman", "fse"):
        return read + rec.outs[0][2]
    return read + sum(b for _, _, b in rec.outs)


def group_work(records: Iterable[NodeRecord], group: str) -> Dict[str, int]:
    """{codec: (device nodes, bytes)} for one kernel group's device nodes."""
    codecs, _ = GROUPS[group]
    out: Dict[str, list] = {}
    for rec in records:
        if rec.codec in codecs and on_device(rec):
            n, b = out.get(rec.codec, (0, 0))
            out[rec.codec] = (n + 1, b + node_bytes(rec))
    return out


def roofline_share(work_bytes: int, device_s: float, hbm_bytes_per_s: float) -> Optional[float]:
    """Least time over device time, in percent; None when nothing ran."""
    if work_bytes <= 0 or device_s <= 0:
        return None
    return 100.0 * (work_bytes / hbm_bytes_per_s) / device_s
