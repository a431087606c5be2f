"""Per-kernel validation: sweep shapes/dtypes, assert bit-exact match between
the Pallas kernel (``use_pallas=True``: interpret mode off the TPU) and the
ref.py pure-jnp oracle, plus cross-checks against the numpy host codecs.
tests/test_tpu_compile.py compiles the same kernels for a v5e."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

SIZES = [0, 1, 7, 128, 2048, 2049, 5000, 16384]
rng = np.random.default_rng(42)


def _u32(n, hi=None):
    return rng.integers(0, hi if hi is not None else (1 << 32), size=n, dtype=np.uint64).astype(np.uint32)


# --------------------------------------------------------------------- delta
@pytest.mark.parametrize("n", SIZES)
def test_delta_encode_matches_ref(n):
    x = _u32(n)
    got = np.asarray(ops.delta_encode(jnp.asarray(x), use_pallas=True))
    want = np.asarray(ref.delta_encode(jnp.asarray(x)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n", SIZES)
def test_delta_roundtrip_kernel(n):
    x = _u32(n)
    d = ops.delta_encode(jnp.asarray(x), use_pallas=True)
    back = np.asarray(ops.delta_decode(d, use_pallas=True))
    np.testing.assert_array_equal(back, x)


def test_delta_matches_host_codec():
    """Device kernel and numpy wire codec agree bit-for-bit."""
    from repro.core import numeric
    from repro.core.codec import get_codec

    x = _u32(4999)
    (host_out,), _ = get_codec("delta").run_encode([numeric(x)], {})
    dev_out = np.asarray(ops.delta_encode(jnp.asarray(x), use_pallas=True))
    np.testing.assert_array_equal(host_out.data, dev_out)


# --------------------------------------------------------------- byteshuffle
@pytest.mark.parametrize("n", [0, 1, 100, 2048, 4097])
@pytest.mark.parametrize("w", [2, 4, 8])
def test_byteshuffle_matches_ref(n, w):
    x = rng.integers(0, 256, size=(n, w), dtype=np.uint8)
    got = np.asarray(ops.byteshuffle(jnp.asarray(x), use_pallas=True))
    want = np.asarray(ref.byteshuffle_encode(jnp.asarray(x)))
    np.testing.assert_array_equal(got, want)
    back = np.asarray(ops.byteunshuffle(jnp.asarray(got), use_pallas=True))
    np.testing.assert_array_equal(back, x)


# ------------------------------------------------------------------- bitpack
@pytest.mark.parametrize("bits", [1, 2, 4, 8, 16, 32])
@pytest.mark.parametrize("n", [0, 1, 31, 32, 1000, 8192, 140000])
def test_bitpack_roundtrip_and_ref(bits, n):
    x = _u32(n, hi=1 << bits)
    packed = ops.bitpack(jnp.asarray(x), bits, use_pallas=True)
    per = 32 // bits
    want = np.asarray(ref.bitpack_encode(jnp.asarray(np.pad(x, (0, (-n) % per))), bits))[
        : -(-n // per) if n else 0
    ]
    np.testing.assert_array_equal(np.asarray(packed), want)
    back = np.asarray(ops.bitunpack(packed, bits, n, use_pallas=True))
    np.testing.assert_array_equal(back, x)


# ----------------------------------------------------------------- histogram
@pytest.mark.parametrize("n", [0, 1, 4096, 5000, 65536])
def test_histogram_matches_numpy(n):
    x = rng.integers(0, 256, size=n, dtype=np.uint8)
    got = np.asarray(ops.histogram_exact(jnp.asarray(x)))
    want = np.bincount(x, minlength=256).astype(np.int32)
    np.testing.assert_array_equal(got, want)


def test_histogram_matches_ref():
    """The jit'd wrapper casts any integer input to its u8 symbols first."""
    x = rng.integers(0, 1 << 16, size=4096, dtype=np.int64).astype(np.uint32)
    got = np.asarray(ops.histogram_exact(jnp.asarray(x)))
    want = np.asarray(ref.histogram_exact(jnp.asarray(x.astype(np.uint8))))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        got, np.bincount(x.astype(np.uint8), minlength=256).astype(np.int32)
    )


# --------------------------------------------------------------- float_split
@pytest.mark.parametrize("n", [0, 1, 2048, 3000])
@pytest.mark.parametrize("fmt", [(8, 23), (8, 7), (5, 10)])  # f32, bf16, f16
def test_float_split_roundtrip_and_ref(n, fmt):
    exp_bits, man_bits = fmt
    width_bits = 1 + exp_bits + man_bits
    u = _u32(n, hi=1 << min(width_bits, 32))
    sign, exp, man = ops.float_split(
        jnp.asarray(u), exp_bits, man_bits, use_pallas=True
    )
    rs, re, rm = ref.float_split_encode(jnp.asarray(u), exp_bits, man_bits)
    np.testing.assert_array_equal(np.asarray(sign), np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(exp), np.asarray(re))
    np.testing.assert_array_equal(np.asarray(man), np.asarray(rm))
    back = np.asarray(
        ops.float_merge(sign, exp, man, exp_bits, man_bits, use_pallas=True)
    )
    np.testing.assert_array_equal(back, u)


def test_float_split_matches_host_codec():
    from repro.core import numeric
    from repro.core.codec import get_codec

    f = rng.normal(size=5000).astype(np.float32)
    outs, _ = get_codec("float_split").run_encode([numeric(f)], {"fmt": 2})
    u = f.view(np.uint32)
    sign, exp, man = ops.float_split(jnp.asarray(u), 8, 23, use_pallas=True)
    np.testing.assert_array_equal(np.unpackbits(outs[0].data)[: f.size], np.asarray(sign))
    np.testing.assert_array_equal(outs[1].data, np.asarray(exp).astype(np.uint8))
    np.testing.assert_array_equal(outs[2].data, np.asarray(man))


# ------------------------------------------------- fused delta+bitpack (K1)
@pytest.mark.parametrize("bits", [4, 8, 16])
@pytest.mark.parametrize("n", [0, 1, 100, 8192, 10000, 140000])
def test_fused_delta_bitpack_roundtrip(bits, n):
    # monotone stream with deltas < 2^bits: the documented lossless domain
    steps = rng.integers(0, 1 << bits, size=n).astype(np.uint32)
    x = np.cumsum(steps, dtype=np.uint32)
    assert bool(ops.fused_delta_bitpack_fits(jnp.asarray(x), bits)) or n == 0
    packed = ops.fused_delta_bitpack(jnp.asarray(x), bits, use_pallas=True)
    want = np.asarray(
        ref.fused_delta_bitpack_encode(
            jnp.asarray(np.pad(x, (0, (-n) % (32 // bits)), mode="edge" if n else "constant")), bits
        )
    )
    np.testing.assert_array_equal(np.asarray(packed), want[: packed.shape[0]])
    back = np.asarray(
        ops.fused_delta_bitpack_decode(packed, bits, n, use_pallas=True)
    )
    np.testing.assert_array_equal(back, x)


def test_fused_equals_unfused_composition():
    """K1 invariant: fused kernel == delta ∘ bitpack composition."""
    bits = 8
    x = np.cumsum(rng.integers(0, 200, size=7000).astype(np.uint32), dtype=np.uint32)
    fused = np.asarray(ops.fused_delta_bitpack(jnp.asarray(x), bits, use_pallas=True))
    d = ops.delta_encode(jnp.asarray(x), use_pallas=True)
    unfused = np.asarray(ops.bitpack(d, bits, use_pallas=True))
    np.testing.assert_array_equal(fused, unfused)


# --------------------------------------------------------------- lane refill
@pytest.mark.parametrize("n_lanes", [0, 1, 7, 256, 300])
def test_lane_refill_matches_ref_and_host(n_lanes):
    """Pallas refill == jnp oracle == the numpy sliding-window gather that
    the entropy lane decoders use (truncated to the device's 32-bit window)."""
    buf = rng.integers(0, 256, 4096, dtype=np.int64).astype(np.uint8)
    bufp = np.concatenate([buf, np.zeros(8, np.uint8)])
    pos = rng.integers(0, buf.size * 8 - 40, size=n_lanes).astype(np.int32)
    got_pl = np.asarray(
        ops.lane_refill(jnp.asarray(bufp), jnp.asarray(pos), use_pallas=True)
    )
    got_ref = np.asarray(
        ops.lane_refill(jnp.asarray(bufp), jnp.asarray(pos), use_pallas=False)
    )
    sw = np.lib.stride_tricks.sliding_window_view(bufp, 8)
    w64 = sw[pos >> 3].copy().view("<u8")[:, 0] >> (pos & 7).astype(np.uint64)
    want = (w64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_array_equal(got_pl, want)


def test_lane_refill_feeds_huffman_window():
    """The refilled window's low 15 bits are exactly the Huffman LUT index
    the host decoder derives for the same cursor."""
    from repro.core.codec import get_codec
    from repro.core.message import serial

    data = bytes(rng.integers(97, 123, 20000, dtype=np.int64).astype(np.uint8))
    outs, header = get_codec("huffman").run_encode([serial(data)], {})
    bitstream = outs[0].data
    offs = outs[1].data.astype(np.int64)
    bufp = np.concatenate([bitstream, np.zeros(16, np.uint8)])
    pos = offs.astype(np.int32)
    win = np.asarray(
        ops.lane_refill(jnp.asarray(bufp), jnp.asarray(pos), use_pallas=True)
    )
    sw = np.lib.stride_tricks.sliding_window_view(bufp, 8)
    w64 = sw[pos >> 3].copy().view("<u8")[:, 0] >> (pos & 7).astype(np.uint64)
    np.testing.assert_array_equal(
        win & np.uint32(0x7FFF), (w64 & np.uint64(0x7FFF)).astype(np.uint32)
    )


# ------------------------------------------------- entropy: huffman (device)
def _skewed(n, seed=7):
    r = np.random.default_rng(seed)
    return (r.zipf(1.4, n) % 256).astype(np.uint8)


@pytest.mark.parametrize("n", [1, 100, 4096, 50000, 140000])
def test_huffman_map_pack_matches_host_encoder(n):
    """Device map + scatter-add packer == the host bit-matrix writer, byte
    for byte (and the pallas map == the jnp oracle)."""
    from repro.codecs import entropy as E

    data = _skewed(n)
    lens = E._huffman_code_lengths(E._hist_u8(data))
    codes = E._canonical_codes(lens)
    host_packed, host_offs = E._write_bits_blocked(
        codes[data], lens[data].astype(np.int64), 1 << E.BLOCK_LOG
    )
    for up in (True, False):
        code, nb, offs = ops.huffman_map(
            jnp.asarray(data),
            jnp.asarray(codes),
            jnp.asarray(lens.astype(np.int32)),
            use_pallas=up,
        )
        np.testing.assert_array_equal(np.asarray(offs), host_offs)
        total_bytes = (int(offs[-1]) + 7) >> 3
        packed = np.asarray(ops.pack_bits(code, offs[:-1], 1 << 17))[:total_bytes]
        assert packed.tobytes() == host_packed.tobytes()


@pytest.mark.parametrize("n", [1, 100, 4097, 50000])
def test_huffman_decode_kernel_roundtrip(n):
    """Device lane decode of a host-encoded bitstream recovers the input,
    pallas and oracle paths identical."""
    from repro.codecs import entropy as E

    data = _skewed(n, seed=n)
    lens = E._huffman_code_lengths(E._hist_u8(data))
    codes = E._canonical_codes(lens)
    packed, offs = E._write_bits_blocked(
        codes[data], lens[data].astype(np.int64), 1 << E.BLOCK_LOG
    )
    lut_sym, lut_len = E._huffman_decode_lut(lens)
    block = 1 << E.BLOCK_LOG
    n_blocks = (n + block - 1) // block
    rem = np.minimum(n - np.arange(n_blocks) * block, block)
    max_rem = int(rem.max())
    pad = 16 + ((E.MAX_CODE_LEN * max_rem + 7) >> 3)
    buf = np.zeros(packed.size + pad, np.uint8)
    buf[: packed.size] = packed
    results = []
    for up in (True, False):
        out = np.asarray(
            ops.huffman_decode(
                jnp.asarray(buf),
                jnp.asarray(offs[:-1:block].astype(np.int32)),
                jnp.asarray(lut_sym.astype(np.int32)),
                jnp.asarray(lut_len.astype(np.int32)),
                max_rem,
                use_pallas=up,
            )
        )
        lanes = out.T
        results.append(
            np.concatenate([lanes[:-1].reshape(-1), lanes[-1, : rem[-1]]])
        )
    np.testing.assert_array_equal(results[0], data)
    np.testing.assert_array_equal(results[1], data)


# ----------------------------------------------------- entropy: fse (device)
def _fse_fixture(n, table_log=11, seed=3):
    from repro.codecs import entropy as E

    data = _skewed(n, seed=seed)
    norm = E._normalize_counts(E._hist_u8(data), table_log)
    tabs = E._build_tables(norm, table_log)
    return data, norm, tabs


def _exponent_plane(kind, n, seed=5):
    """fp32 exponent bytes as the checkpoint cell makes them: N(0, 0.02)
    weights, squared N(0, 1e-3) second moments; zipf bytes; one symbol."""
    r = np.random.default_rng(seed)
    if kind == "skewed":
        return _skewed(n, seed=seed)
    if kind == "one_symbol":
        return np.full(n, 121, np.uint8)
    f = r.normal(0.0, 0.02 if kind == "weights" else 1e-3, n).astype(np.float32)
    if kind == "sq_moments":
        f = np.square(f)
    return ((f.view(np.uint32) >> 23) & 0xFF).astype(np.uint8)


FSE_TAIL = 3 * 1024 + 17
FSE_ENCODE_CASES = (
    [("skewed", n, 11) for n in (1, 100, 1023, 1024, 1025, FSE_TAIL, 50000)]
    + [("weights", FSE_TAIL, tl) for tl in (5, 9, 11, 12)]
    + [("sq_moments", FSE_TAIL, tl) for tl in (5, 9, 11, 12)]
    + [("weights", 1 << 16, 11), ("sq_moments", 1 << 16, 11)]
    + [("one_symbol", n, tl) for n, tl in ((1, 11), (1025, 5), (FSE_TAIL, 12))]
)


@pytest.mark.parametrize("use_pallas", [True, False])
@pytest.mark.parametrize("kind,n,table_log", FSE_ENCODE_CASES)
def test_fse_encode_kernel_matches_host_encoder(kind, n, table_log, use_pallas):
    """Device backward walk on the compact state table (Pallas kernel and
    its jnp oracle) + packer == the host tANS encoder's bitstream and
    (bit length, final state) meta, byte for byte."""
    from repro.codecs import entropy as E
    from repro.core.message import Stream, SType

    data = _exponent_plane(kind, n)
    norm = E._normalize_counts(E._hist_u8(data), table_log)
    tabs = E._fse_tables_cached(norm, table_log)
    block = 1 << E.FSE_BLOCK_LOG
    n_blocks = (n + block - 1) // block
    padded = np.zeros(n_blocks * block, np.uint8)
    padded[:n] = data
    lanesT = padded.reshape(n_blocks, block).T
    rem = np.minimum(n - np.arange(n_blocks) * block, block).astype(np.int32)
    host_outs, _ = E._fse_enc(
        [Stream(data, SType.SERIAL, 1)], {"table_log": table_log}
    )
    vals, goffs, state, bitpos, byte_off = ops.fse_encode(
        jnp.asarray(lanesT),
        jnp.asarray(rem),
        jnp.asarray(tabs.nb0),
        jnp.asarray(tabs.thr),
        jnp.asarray(tabs.st0),
        jnp.asarray(tabs.delta),
        jnp.asarray(tabs.state_table),
        use_pallas=use_pallas,
    )
    tb = int(byte_off[-1])
    stream = np.asarray(
        ops.pack_bits(vals.reshape(-1), goffs.reshape(-1), 1 << 17)
    )[:tb]
    assert stream.tobytes() == host_outs[0].content_bytes()
    meta = np.empty(n_blocks * 2, np.uint32)
    meta[0::2] = np.asarray(bitpos).astype(np.uint32)
    meta[1::2] = np.asarray(state).astype(np.uint32)
    assert meta.tobytes() == host_outs[1].content_bytes()


@pytest.mark.parametrize(
    "kind,table_log",
    [(k, tl) for k in ("weights", "sq_moments", "one_symbol") for tl in (5, 9, 11, 12)]
    + [("skewed", tl) for tl in (9, 11, 12)],  # > 32 symbols: no table_log 5
)
def test_fse_compact_state_table_is_the_encode_table(kind, table_log):
    """The device walk's 2^table_log state table holds every symbol's row
    of the padded host encode table: ``enc_table[s, :norm[s]]`` ==
    ``state_table[k + delta[s]]`` for k in [norm[s], 2 norm[s])."""
    from repro.codecs import entropy as E

    data = _exponent_plane(kind, FSE_TAIL)
    norm = E._normalize_counts(E._hist_u8(data), table_log)
    tabs = E._fse_tables_cached(norm, table_log)
    assert tabs.state_table.shape == (1 << table_log,)
    assert tabs.state_table.dtype == np.int32 and tabs.delta.dtype == np.int32
    for s in np.nonzero(norm)[0]:
        k = np.arange(norm[s], 2 * norm[s])
        np.testing.assert_array_equal(
            tabs.state_table[k + tabs.delta[s]], tabs.enc_table[s, : norm[s]]
        )
        assert tabs.st0[s] == tabs.state_table[norm[s] + tabs.delta[s]]
    np.testing.assert_array_equal(
        np.sort(tabs.state_table), np.arange(1 << table_log)
    )


def test_fse_device_info_counts_device_encodes():
    """``fse_device_info()`` counts each device fse encode: one call, its
    symbols, and its lanes x 1024 lane-steps; a stream under the device
    window stays on the host and counts nothing."""
    from repro.codecs.entropy_device import fse_device_info
    from repro.core import compress, decompress, pipeline, serial

    big, small = _exponent_plane("weights", FSE_TAIL), _skewed(500)
    before = fse_device_info()
    for data in (big, small):
        frame = compress(pipeline("fse"), serial(data.tobytes()), backend="device")
        assert decompress(frame)[0].content_bytes() == data.tobytes()
    after = fse_device_info()
    grew = {k: after[k] - before[k] for k in after}
    assert grew == {"calls": 1, "symbols": FSE_TAIL, "lane_steps": 4 * 1024}


def test_fse_device_info_loses_no_update_under_threads():
    """Session pools encode on many threads: every count lands."""
    import sys
    import threading

    from repro.codecs import entropy_device as ED

    before = ED.fse_device_info()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [
            threading.Thread(
                target=lambda: [ED._count_fse(3, 1024) for _ in range(2000)]
            )
            for _ in range(32)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    after = ED.fse_device_info()
    grew = {k: after[k] - before[k] for k in after}
    assert grew == {"calls": 64000, "symbols": 192000, "lane_steps": 65536000}


@pytest.mark.parametrize("n", [1, 100, 1025, 50000])
def test_fse_decode_kernel_roundtrip(n):
    """Device forward walk over host-encoded lanes recovers the input."""
    from repro.codecs import entropy as E
    from repro.core.message import Stream, SType

    table_log = 11
    data, norm, (dec_sym, dec_nb, dec_base, *_enc) = _fse_fixture(n, table_log)
    host_outs, _ = E._fse_enc([Stream(data, SType.SERIAL, 1)], {})
    meta = np.frombuffer(host_outs[1].content_bytes(), np.uint32)
    bitlen = meta[0::2].astype(np.int64)
    n_blocks = bitlen.size
    block = 1 << E.FSE_BLOCK_LOG
    nbytes = (bitlen + 7) // 8
    offsets = np.zeros(n_blocks + 1, np.int64)
    np.cumsum(nbytes, out=offsets[1:])
    cap = int(nbytes.max()) + 16
    flat = np.zeros(n_blocks * cap, np.uint8)
    lane_base = np.arange(n_blocks, dtype=np.int64) * cap
    intra = np.arange(int(offsets[-1]), dtype=np.int64) - np.repeat(
        offsets[:-1], nbytes
    )
    flat[np.repeat(lane_base, nbytes) + intra] = np.frombuffer(
        host_outs[0].content_bytes(), np.uint8
    )
    rem = np.minimum(n - np.arange(n_blocks) * block, block)
    for up in (True, False):
        out = np.asarray(
            ops.fse_decode(
                jnp.asarray(flat),
                jnp.asarray(lane_base.astype(np.int32)),
                jnp.asarray(bitlen.astype(np.int32)),
                jnp.asarray(meta[1::2].astype(np.int32)),
                jnp.asarray(dec_sym.astype(np.int32)),
                jnp.asarray(dec_nb),
                jnp.asarray(dec_base),
                int(rem.max()),
                use_pallas=up,
            )
        )
        lanes = out.T
        result = np.concatenate([lanes[:-1].reshape(-1), lanes[-1, : rem[-1]]])
        np.testing.assert_array_equal(result, data)


def test_histogram_exact_is_exact():
    x = _skewed(200000)
    np.testing.assert_array_equal(
        np.asarray(ops.histogram_exact(jnp.asarray(x))),
        np.bincount(x, minlength=256).astype(np.int32),
    )
