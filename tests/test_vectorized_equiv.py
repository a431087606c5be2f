"""Cross-checks pinning the vectorized LZ/Huffman/FSE hot paths against the
pre-existing scalar behavior (tests/_scalar_ref.py, the seed implementations).

THE invariant of this PR: for every input, the vectorized encoders emit
bit-identical output streams AND headers — so every frame any older build
produced still decodes, and every new frame is byte-for-byte what the old
build would have written.  Property-tested over random, constant, periodic,
and already-compressed inputs (hypothesis, guarded via tests/_hyp.py), plus
deterministic adversarial cases.
"""
import zlib

import numpy as np
import pytest
from _hyp import given, settings, st

import _scalar_ref as sr
from repro.codecs import entropy as vec_entropy
from repro.codecs import lz as vec_lz
from repro.codecs import numeric as vec_numeric
from repro.core.message import numeric, serial, strings, struct


def _assert_bitwise_equal(codec, data):
    pairs = {
        "lz77": (sr._lz77_enc, vec_lz._lz77_enc, sr._lz77_dec, vec_lz._lz77_dec),
        "huffman": (
            sr._huffman_enc,
            vec_entropy._huffman_enc,
            sr._huffman_dec,
            vec_entropy._huffman_dec,
        ),
        "fse": (sr._fse_enc, vec_entropy._fse_enc, sr._fse_dec, vec_entropy._fse_dec),
    }
    ref_enc, new_enc, ref_dec, new_dec = pairs[codec]
    s = serial(data)
    ref_outs, ref_h = ref_enc([s], {})
    new_outs, new_h = new_enc([s], {})
    assert ref_h == new_h, f"{codec}: header diverged on {len(data)}-byte input"
    assert len(ref_outs) == len(new_outs)
    for i, (a, b) in enumerate(zip(ref_outs, new_outs)):
        assert a.stype == b.stype and a.width == b.width
        assert a.data.tobytes() == b.data.tobytes(), f"{codec}: stream {i} diverged"
    # old decoder reads new frames; new decoder reads (identical) old frames
    assert ref_dec(new_outs, new_h)[0].content_bytes() == data
    assert new_dec(ref_outs, ref_h)[0].content_bytes() == data


CODECS = ["lz77", "huffman", "fse"]


def _check_all(data: bytes) -> None:
    for codec in CODECS:
        _assert_bitwise_equal(codec, data)


@given(st.binary(min_size=0, max_size=8192))
@settings(max_examples=25, deadline=None)
def test_equiv_random(b):
    _check_all(b)


@given(st.integers(0, 255), st.integers(0, 12000))
@settings(max_examples=15, deadline=None)
def test_equiv_constant(byte, n):
    _check_all(bytes([byte]) * n)


@given(st.binary(min_size=1, max_size=16), st.integers(1, 2000))
@settings(max_examples=20, deadline=None)
def test_equiv_periodic(period, reps):
    _check_all(period * reps)


@given(st.binary(min_size=0, max_size=4096))
@settings(max_examples=15, deadline=None)
def test_equiv_already_compressed(b):
    _check_all(zlib.compress(b, 9))


@pytest.mark.parametrize("codec", CODECS)
def test_equiv_deterministic_corpus(codec):
    rng = np.random.default_rng(1234)
    cases = [
        b"",
        b"a",
        b"abc",
        b"abcd",
        b"abcdabcd",
        b"the quick brown fox jumps over the lazy dog " * 250,
        bytes(rng.integers(0, 256, 70000).astype(np.uint8)),
        bytes(rng.integers(0, 4, 70000).astype(np.uint8)),
        np.cumsum(rng.integers(0, 3, 50000)).astype(np.uint8).tobytes(),
        b"\x00" * 70000,  # match length beyond MAX_MATCH
        (b"xy" + bytes(rng.integers(0, 256, 30000).astype(np.uint8))) * 2,
    ]
    for data in cases:
        _assert_bitwise_equal(codec, data)


def test_equiv_lane_block_boundaries():
    """Sizes straddling the entropy lane-block and LZ segment boundaries."""
    rng = np.random.default_rng(5)
    for n in [1023, 1024, 1025, 4095, 4096, 4097, 8192, 12289, 65536 + 17]:
        data = bytes(rng.choice(16, n).astype(np.uint8) + 97)
        for codec in CODECS:
            _assert_bitwise_equal(codec, data)


def test_prev_occurrence_matches_scalar():
    """The threaded half-sort hash chain equals the seed's global argsort."""
    rng = np.random.default_rng(9)
    for n in [0, 1, 3, 4, 100, 5000, (1 << 18) + 7, (1 << 18) + 4096]:
        data = rng.integers(0, 8, n).astype(np.uint8)
        got = vec_lz._prev_occurrence(data)
        want = sr._prev_occurrence(data)
        assert np.array_equal(got.astype(np.int64), want.astype(np.int64)), n


def test_trained_plans_still_roundtrip():
    """Wire compatibility: every shipped trained plan still encodes/decodes
    (and its frames hit the rewritten lz/entropy leaves)."""
    import json
    from pathlib import Path

    from repro.core import Compressor
    from repro.core.serialize import deserialize_plan

    cache = Path(__file__).resolve().parents[1] / "results" / "trained"
    blobs = sorted(cache.glob("*.ozp"))
    assert blobs, "trained plan cache missing"
    rng = np.random.default_rng(3)
    payload = bytes(rng.choice(32, 20000).astype(np.uint8) + 48)
    checked = 0
    for blob in blobs[:12]:
        plan, _meta = deserialize_plan(blob.read_bytes())
        if plan.n_inputs != 1:
            continue
        try:
            ok = Compressor(plan).roundtrip_check(payload)
        except ValueError:
            continue  # plan requires a typed/structured input shape
        assert ok, blob.name
        checked += 1
    assert checked >= 1


def test_lz77_segment_overshoot_sizes():
    """Regression: lane start positions arange(S)*ceil(n/S) can exceed n for
    sizes where ceil overshoots (e.g. 1200*1024 + 1) — must clamp, not crash,
    and stay bit-identical to the scalar parse."""
    rng = np.random.default_rng(21)
    for n in [1200 * 1024 + 1, 1536 * 1024 + 7]:
        data = bytes(rng.choice(8, n).astype(np.uint8) + 97)
        _assert_bitwise_equal("lz77", data)


def test_fse_large_table_log_flush():
    """Regression: at table_log >= 17 a single step can flush 3 whole bytes;
    the accumulator writer must not drop the third (bit-identical to the
    scalar 4-byte OR-writer, and roundtrip-exact)."""
    from repro.core.message import serial as mk_serial

    data = b"a" * 200_000 + bytes(range(98, 130))
    for table_log in (16, 17, 18):
        s = mk_serial(data)
        ref_outs, ref_h = sr._fse_enc([s], {"table_log": table_log})
        new_outs, new_h = vec_entropy._fse_enc([s], {"table_log": table_log})
        assert ref_h == new_h
        for a, b in zip(ref_outs, new_outs):
            assert a.data.tobytes() == b.data.tobytes(), table_log
        back = vec_entropy._fse_dec(new_outs, new_h)[0].content_bytes()
        assert back == data, table_log


def _assert_tokenize_equal(s):
    """Tokenize's alphabet, u32 indices and header are byte for byte the
    row-unique encoder's, and decode back to the input."""
    ref_outs, ref_h = sr._tokenize_rows_ref([s], {})
    new_outs, new_h = vec_numeric._tokenize_enc([s], {})
    assert ref_h == new_h
    assert len(ref_outs) == len(new_outs) == 2
    for a, b in zip(ref_outs, new_outs):
        assert (a.stype, a.width) == (b.stype, b.width)
        assert a.data.dtype == b.data.dtype
        assert a.data.tobytes() == b.data.tobytes()
    back = vec_numeric._tokenize_dec(new_outs, new_h)[0]
    assert (back.stype, back.width) == (s.stype, s.width)
    assert back.content_bytes() == s.content_bytes()


def _repeats(rng, n, width, k):
    """``n`` rows of ``width`` bytes drawn from ``k`` random distinct-ish rows."""
    alphabet = rng.integers(0, 256, (k, width), dtype=np.uint8)
    return alphabet[rng.integers(0, k, n)].reshape(-1)


def _tokenize_case(name):
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    sized = {"u8": np.uint8, "u16": np.uint16, "u32": np.uint32, "u64": np.uint64}
    if name in sized:
        dt = sized[name]
        return numeric(rng.integers(0, 40, 5000).astype(dt) * dt(3))
    if name.startswith("struct"):
        return struct(_repeats(rng, 4000, int(name[6:]), 37), int(name[6:]))
    cases = {
        "serial": lambda: serial(bytes(_repeats(rng, 9000, 1, 23))),
        "empty_numeric": lambda: numeric(np.zeros(0, dtype=np.int64)),
        "empty_struct3": lambda: struct(b"", 3),
        "empty_serial": lambda: serial(b""),
        "single_u64": lambda: numeric(np.array([7], dtype=np.uint64)),
        "single_struct12": lambda: struct(bytes(range(12)), 12),
        "all_equal_u32": lambda: numeric(np.full(3000, 0xDEADBEEF, dtype=np.uint32)),
        "all_equal_struct3": lambda: struct(b"abc" * 3000, 3),
        "all_distinct_u64": lambda: numeric(rng.permutation(4000).astype(np.uint64) << np.uint64(40)),
        "all_distinct_struct12": lambda: struct(
            rng.permutation(5000).astype("<u4").view(np.uint8).repeat(3), 12),
        "l_quantity": lambda: numeric(rng.integers(1, 51, 60000).astype(np.int64) * 100),
        "negative_i64": lambda: numeric(rng.integers(-(1 << 62), 1 << 62, 3000).astype(np.int64)
                                        .repeat(2) * np.int64(-1)),
        # little-endian byte order and integer order disagree: 0x0100 sorts
        # above 0x0001 as an integer but below it as bytes, so ids must not
        # follow either sort
        "byte_order_u16": lambda: numeric(np.array([0x0100, 0x0001, 0x0100, 0x00FF, 0xFF00, 0x0001],
                                                   dtype=np.uint16)),
        "byte_order_u64": lambda: numeric(np.array([1 << 56, 1, 1 << 8, 1, 1 << 56, 255, 1 << 63],
                                                   dtype=np.uint64)),
        "byte_order_struct3": lambda: struct(bytes([1, 0, 0, 0, 0, 1, 0, 1, 0, 1, 0, 0, 0, 0, 1]), 3),
        "byte_order_struct12": lambda: struct(
            np.array([[0] * 11 + [1], [1] + [0] * 11, [0] * 8 + [1, 0, 0, 0], [0] * 11 + [1]],
                     dtype=np.uint8).reshape(-1), 12),
    }
    return cases[name]()


TOKENIZE_CASES = [
    "u8", "u16", "u32", "u64",
    "struct2", "struct3", "struct4", "struct8", "struct12", "struct5", "struct16", "serial",
    "empty_numeric", "empty_struct3", "empty_serial", "single_u64", "single_struct12",
    "all_equal_u32", "all_equal_struct3", "all_distinct_u64", "all_distinct_struct12",
    "l_quantity", "negative_i64",
    "byte_order_u16", "byte_order_u64", "byte_order_struct3", "byte_order_struct12",
]


@pytest.mark.parametrize("case", TOKENIZE_CASES)
def test_tokenize_matches_row_unique(case):
    _assert_tokenize_equal(_tokenize_case(case))


@given(st.sampled_from(["numeric", "struct", "serial"]),
       st.sampled_from([1, 2, 3, 4, 5, 6, 7, 8, 9, 12, 16, 17]),
       st.integers(0, 600), st.integers(1, 300), st.integers(0, 2**32 - 1))
@settings(max_examples=60, deadline=None)
def test_tokenize_matches_row_unique_random(kind, width, n, k, seed):
    rng = np.random.default_rng(seed)
    if kind == "numeric":
        width = next(w for w in (1, 2, 4, 8) if w >= min(width, 8))
        s = numeric(_repeats(rng, n, width, k).view(f"<u{width}"))
    elif kind == "struct":
        s = struct(_repeats(rng, n, width, k), width)
    else:
        s = serial(bytes(_repeats(rng, n, 1, k)))
    _assert_tokenize_equal(s)


def test_tokenize_info_counts_paths():
    """One ``int_view`` per encode of rows up to 8 bytes wide (widths 3 and
    5 zero-extended), ``rows`` for wider rows, ``strings`` for STRING."""
    streams = [
        (numeric(np.arange(10, dtype=np.uint8)), "int_view"),
        (numeric(np.arange(10, dtype=np.uint16)), "int_view"),
        (numeric(np.arange(10, dtype=np.uint32)), "int_view"),
        (numeric(np.arange(10, dtype=np.int64)), "int_view"),
        (struct(b"abcabcxyz", 3), "int_view"),
        (struct(bytes(15), 5), "int_view"),
        (serial(b"hello"), "int_view"),
        (struct(bytes(range(24)), 12), "rows"),
        (strings([b"a", b"bb", b"a"]), "strings"),
    ]
    for s, path in streams:
        before = vec_numeric.tokenize_info()
        vec_numeric._tokenize_enc([s], {})
        after = vec_numeric.tokenize_info()
        assert {k: after[k] - before[k] for k in after} == {
            k: int(k == path) for k in after
        }, (s, path)


PACK_SIZES = (0, 1, 7, 8, 9, 4097, 65539)
PACK_DTYPES = (np.uint8, np.uint16, np.uint32, np.uint64)


@pytest.mark.parametrize("bits", range(1, 58))
def test_pack_bits_matches_scatter_packer(bits):
    """The 64-value group packer writes the scatter packer's bytes for every
    width, length and input dtype, on random and all-max values, and
    ``_unpack_bits`` reads the values back."""
    rng = np.random.default_rng(bits)
    for n in PACK_SIZES:
        for dtype in PACK_DTYPES:
            top = min(bits, 8 * np.dtype(dtype).itemsize)
            cases = [rng.integers(0, 1 << top, n, dtype=np.uint64).astype(dtype)]
            if top == bits:
                cases.append(np.full(n, (1 << bits) - 1, dtype=dtype))
            for vals in cases:
                got = vec_numeric._pack_bits(vals, bits)
                want = sr._pack_bits(vals, bits)
                assert got.dtype == want.dtype == np.uint8
                assert got.tobytes() == want.tobytes(), (n, dtype, bits)
                back = vec_numeric._unpack_bits(got, bits, n, 8)
                assert np.array_equal(back, vals.astype(np.uint64)), (n, dtype, bits)


def test_pack_bits_refuses_over_57_bits():
    vals = np.arange(10, dtype=np.uint64)
    for pack in (vec_numeric._pack_bits, sr._pack_bits):
        with pytest.raises(ValueError, match="<= 57 bits"):
            pack(vals, 58)


def _lineitem_like(rng, n):
    """INT64 cents as TPC-H's l_quantity: whole units 1..50, times 100."""
    return rng.integers(1, 51, n).astype(np.int64) * 100


def _leaf_bits(rng, n, dtype):
    import jax.numpy as jnp

    leaf = np.asarray(jnp.asarray(0.02 * rng.normal(size=n), dtype))
    return leaf.view(np.uint16 if dtype == "bfloat16" else np.uint32)


FRAME_CASES = [
    ("numeric", lambda rng: _lineitem_like(rng, 1 << 16), 0),
    ("bfloat16", lambda rng: _leaf_bits(rng, 96 << 10, "bfloat16"), 64 << 10),
    ("float32", lambda rng: _leaf_bits(rng, 48 << 10, "float32"), 64 << 10),
]


@pytest.mark.parametrize("backend", ["host", "device"])
@pytest.mark.parametrize("profile,make,chunk", FRAME_CASES, ids=[c[0] for c in FRAME_CASES])
def test_frames_unchanged_with_scatter_packer(monkeypatch, backend, profile, make, chunk):
    """A lineitem-like column and bf16/fp32 checkpoint-like leaves, through
    the benchmark's plans: the frame is sha256 for sha256 the one made with
    the scatter packer, and the group packer ran on their range_pack nodes."""
    import hashlib

    from repro.codecs._util import device_available
    from repro.codecs.profiles import resolve_profile_spec
    from repro.core import CompressorSession, numeric

    if backend == "device" and not device_available():
        pytest.skip("no jax")
    data = make(np.random.default_rng(zlib.crc32(profile.encode())))

    def frame():
        with CompressorSession(resolve_profile_spec(profile), backend=backend) as s:
            digest = hashlib.sha256(s.compress(numeric(data), chunk_bytes=chunk)).hexdigest()
            assert s.stats["nodes"]["host"]["range_pack"] >= 1
        return digest

    before = vec_numeric.pack_bits_info()["range_pack"]["calls"]
    got = frame()
    assert vec_numeric.pack_bits_info()["range_pack"]["calls"] > before
    monkeypatch.setattr(vec_numeric, "_pack_bits", sr._pack_bits)
    assert got == frame()


def _pack_counts_after(encode):
    before = vec_numeric.pack_bits_info()
    encode()
    after = vec_numeric.pack_bits_info()
    return {c: {k: after[c][k] - before[c][k] for k in after[c]} for c in after}


def test_pack_bits_info_counts_by_codec():
    """One call and its values under the codec that packed them."""
    x = numeric(np.arange(100, dtype=np.uint32))
    encodes = {
        "bitpack": lambda: vec_numeric._bitpack_enc([x], {}),
        "range_pack": lambda: vec_numeric._range_pack_enc([x], {}),
        "fused_delta_bitpack": lambda: vec_numeric._fused_enc([x], {}),
    }
    for codec, encode in encodes.items():
        assert _pack_counts_after(encode) == {
            c: {"calls": int(c == codec), "values": 100 * (c == codec)} for c in encodes
        }, codec


def test_pack_bits_info_sums_across_threads():
    """Concurrent encodes from 4 threads, switching as often as the
    interpreter allows, lose no count."""
    import sys
    from concurrent.futures import ThreadPoolExecutor

    x = numeric(np.arange(5000, dtype=np.uint16))

    def work(_):
        for _ in range(25):
            vec_numeric._range_pack_enc([x], {})

    def encode():
        with ThreadPoolExecutor(4) as pool:
            list(pool.map(work, range(4), timeout=60))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        counts = _pack_counts_after(encode)
    finally:
        sys.setswitchinterval(interval)
    assert counts["range_pack"] == {"calls": 100, "values": 500_000}
