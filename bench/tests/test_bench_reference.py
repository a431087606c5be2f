"""The plain reference decoder against frames the program writes, for every
codec the cells' profiles can choose, on both backends, chunked and not;
and its refusals."""
import struct
import zlib

import numpy as np
import pytest

from bench import reference as R

N = 20_000


def _cases():
    from repro.codecs.profiles import bfloat16_profile, float32_profile, numeric_profile
    from repro.core import GraphBuilder, pipeline

    rng = np.random.default_rng(2**31 + 11)
    g = GraphBuilder(1)
    alpha, idx = g.add("tokenize", g.input(0))
    g.add("transpose", alpha)
    g.add("range_pack", idx)
    w = (rng.standard_normal(N).astype(np.float32) * 0.02).view(np.uint32)
    return {
        "delta+range_pack": (pipeline("delta", "range_pack"),
                             np.cumsum(rng.integers(0, 100, N)).astype(np.uint64)),
        "bitpack": (pipeline("bitpack"), rng.integers(0, 1000, N).astype(np.uint32)),
        "fused": (pipeline("fused_delta_bitpack"),
                  np.cumsum(rng.integers(0, 15, N)).astype(np.uint32)),
        "transpose+huffman": (pipeline("transpose", "huffman"),
                              rng.integers(0, 5000, N).astype(np.uint32)),
        "transpose+fse": (pipeline("transpose", "fse"), rng.integers(0, 300, N).astype(np.uint16)),
        "zigzag": (pipeline("delta", "zigzag", "range_pack"),
                   rng.integers(0, 1 << 40, N).astype(np.uint64)),
        "tokenize": (g.build("tok"), rng.choice(np.array([3, 7, 100, 5000], np.uint64), N)),
        "zlib": (pipeline("transpose", ("zlib_backend", {"level": 5})),
                 rng.integers(0, 50, N).astype(np.uint32)),
        "lzma": (pipeline(("lzma_backend", {})), rng.integers(0, 5, N).astype(np.uint8)),
        "rle": (pipeline("rle"), np.repeat(rng.integers(0, 5, N // 10), 10).astype(np.uint8)),
        "transpose_split": (pipeline(("transpose_split", {"n_out": 4})),
                            rng.integers(0, 5000, N).astype(np.uint32)),
        "float32": (float32_profile(), w),
        "bfloat16": (bfloat16_profile(), (w >> 16).astype(np.uint16)),
        "numeric": (numeric_profile(), np.cumsum(rng.integers(0, 3, N)).astype(np.uint32)),
    }


CASES = sorted(_cases())


@pytest.mark.parametrize("backend", ["host", "device"])
@pytest.mark.parametrize("chunk", [0, 1 << 14])
@pytest.mark.parametrize("case", CASES)
def test_reference_decodes_program_frames(case, chunk, backend):
    from repro.core import compress, numeric

    plan, arr = _cases()[case]
    frame = compress(plan, numeric(arr), backend=backend, chunk_bytes=chunk or None)
    bad, records = R.mismatched_bytes(frame, arr)
    assert bad == 0
    assert records and all(r.codec in R.CODEC_NAMES.values() for r in records)


def _frame(arr):
    from repro.core import compress, numeric, pipeline

    return compress(pipeline("transpose", "huffman"), numeric(arr))


def _refit_crc(body: bytearray) -> bytes:
    return bytes(body) + struct.pack("<I", zlib.crc32(bytes(body)) & 0xFFFFFFFF)


def test_reference_sees_an_altered_payload():
    arr = np.arange(N, dtype=np.uint32) * 7
    body = bytearray(_frame(arr)[:-4])
    body[len(body) // 2] ^= 0x10
    try:
        bad, _ = R.mismatched_bytes(_refit_crc(body), arr)
    except R.RefError:
        bad = arr.nbytes
    assert bad > 0


def test_reference_refuses_crc_truncation_and_unknown_codecs():
    arr = np.arange(N, dtype=np.uint32)
    frame = _frame(arr)
    with pytest.raises(R.RefError):
        R.decode(frame[:-1] + bytes([frame[-1] ^ 1]))
    with pytest.raises(R.RefError):
        R.decode(frame[:-9])
    # codec id 27 (edge_list) is not one the cells' profiles choose
    body = bytearray(frame[:-4])
    assert body[7] == 5  # first node's codec id: transpose
    body[7] = 27
    with pytest.raises(R.RefError):
        R.decode(_refit_crc(body))


def test_reference_counts_missing_and_extra_bytes():
    arr = np.arange(N, dtype=np.uint32)
    bad, _ = R.mismatched_bytes(_frame(arr[: N // 2]), arr)
    assert bad == arr.nbytes // 2
