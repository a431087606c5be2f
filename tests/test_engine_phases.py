"""The two-phase engine: resolve caching, backend dispatch + bit-exactness
(one frame per plan on every backend), and multi-chunk container frames."""
import numpy as np
import pytest

from repro.core import (
    CompressionCtx,
    Compressor,
    GraphBuilder,
    StreamMeta,
    available_backends,
    compress,
    decompress,
    decompress_bytes,
    execute,
    numeric,
    pipeline,
    resolve,
    resolve_cache_clear,
    resolve_cache_info,
    serial,
    stream_meta,
    strings,
)
from repro.core.wire import FrameError, is_container, read_container, read_frame

rng = np.random.default_rng(0)


def sorted_u32(n=2000, step=200):
    return numeric(np.cumsum(rng.integers(0, step, n)).astype(np.uint32))


# ------------------------------------------------------------------ resolve
def test_resolve_is_selector_free():
    from repro.codecs import generic_profile

    r = resolve(generic_profile(), numeric(np.arange(5000, dtype=np.uint32)))
    assert r.steps, "resolution produced an empty program"
    from repro.core.codec import get_codec

    for step in r.steps:
        assert get_codec(step.name).codec_id == step.codec_id


def test_resolve_cache_hit_on_same_meta():
    from repro.codecs import generic_profile

    resolve_cache_clear()
    plan = generic_profile()
    x1 = numeric(np.arange(4096, dtype=np.uint32))
    x2 = numeric(np.arange(4096, dtype=np.uint32) * 3)  # same meta, new data
    r1 = resolve(plan, x1)
    misses_after_first = resolve_cache_info()["misses"]
    r2 = resolve(plan, x2)
    info = resolve_cache_info()
    assert r2 is r1, "same stream meta must reuse the cached ResolvedPlan"
    assert info["hits"] >= 1
    assert info["misses"] == misses_after_first


def test_resolve_cache_miss_on_level_change():
    from repro.codecs import generic_profile

    resolve_cache_clear()
    plan = generic_profile()
    x = numeric(np.arange(4096, dtype=np.uint32))
    r5 = resolve(plan, x, CompressionCtx(level=5))
    before = resolve_cache_info()["misses"]
    r9 = resolve(plan, x, CompressionCtx(level=9))
    assert resolve_cache_info()["misses"] > before, "level is part of the key"
    assert r9 is not r5


def test_resolve_cache_miss_on_meta_change():
    resolve_cache_clear()
    plan = pipeline("delta", "range_pack")
    resolve(plan, numeric(np.arange(100, dtype=np.uint32)))
    before = resolve_cache_info()["misses"]
    resolve(plan, numeric(np.arange(100, dtype=np.uint16)))  # width changed
    assert resolve_cache_info()["misses"] > before


def test_resolve_from_metas_only_static_plan():
    plan = pipeline("delta", "range_pack")
    x = numeric(np.arange(100, dtype=np.uint32))
    r = resolve(plan, [stream_meta(x)])
    frame = execute(r, x)
    assert decompress(frame)[0].content_bytes() == x.content_bytes()


def test_resolve_from_metas_only_dynamic_plan_rejected():
    from repro.codecs import generic_profile

    meta = StreamMeta(numeric(np.arange(4, dtype=np.uint32)).stype, 4, 3)
    with pytest.raises(ValueError, match="concrete streams"):
        resolve(generic_profile(), [meta], use_cache=False)


def test_resolve_rejects_wrong_input_count():
    plan = pipeline("delta", "bitpack")  # 1-input plan
    a = numeric(np.arange(10, dtype=np.uint32))
    with pytest.raises(ValueError, match="wants 1 inputs"):
        resolve(plan, [a, a], use_cache=False)
    g = GraphBuilder(2)
    g.add("concat", g.input(0), g.input(1))
    with pytest.raises(ValueError, match="wants 2 inputs"):
        resolve(g.build(), [a], use_cache=False)


def test_cached_resolution_falls_back_on_inapplicable_values():
    """Same stream meta, but values that break the cached selector choice:
    compress() must re-expand instead of propagating the codec refusal."""
    from repro.codecs import generic_profile

    resolve_cache_clear()
    plan = generic_profile()
    n = 4096
    small = numeric(np.arange(n, dtype=np.uint64))  # tiny range: range_pack wins
    frame1 = compress(plan, small)
    assert decompress(frame1)[0].content_bytes() == small.content_bytes()
    # same meta (u64, same size bucket), range needs > 57 bits -> cached
    # range_pack plan is inapplicable to these values
    wide = numeric(
        np.linspace(0, (1 << 63) - 1, n, dtype=np.uint64) + np.arange(n, dtype=np.uint64)
    )
    frame2 = compress(plan, wide)
    assert decompress(frame2)[0].content_bytes() == wide.content_bytes()


def test_compressor_chunking_disable_override():
    x = np.arange(50_000, dtype=np.uint32).tobytes()
    c = Compressor(pipeline("huffman"), chunk_bytes=1 << 14)
    assert is_container(c.compress(x))
    assert not is_container(c.compress(x, chunk_bytes=0)), "0 forces a plain frame"


def test_execute_rejects_unknown_backend():
    x = numeric(np.arange(10, dtype=np.uint32))
    r = resolve(pipeline("store"), x)
    with pytest.raises(ValueError, match="unknown backend"):
        execute(r, x, backend="quantum")


# ------------------------------------------------------------------ backends
def _routed_cases():
    f32 = (rng.normal(size=300) * 0.1).astype(np.float32)
    g = GraphBuilder(1)
    g.add("transpose_split", g.input(0), n_out=4)
    tsplit = g.build("tsplit")
    g = GraphBuilder(1)
    a, b = g.add("dup", g.input(0))
    g.add("bitpack", g.add("delta", a))
    g.add("transpose", b)
    dup_delta_bitpack = g.build("dup_delta_bitpack")
    delta_bitpack = pipeline("delta", "bitpack")
    return [
        ("delta_u8", pipeline("delta"), numeric(np.arange(777, dtype=np.uint8))),
        ("delta_u16", pipeline("delta"), numeric(np.arange(777, dtype=np.uint16))),
        ("delta_u32", pipeline("delta"), numeric(np.arange(777, dtype=np.uint32))),
        ("delta_u64_fallback", pipeline("delta"), numeric(np.arange(77, dtype=np.uint64))),
        (
            "bitpack_8",
            pipeline("bitpack"),
            numeric(rng.integers(0, 200, 500).astype(np.uint32)),
        ),
        (
            "bitpack_13_fallback",
            pipeline("bitpack"),
            numeric(rng.integers(0, 5000, 500).astype(np.uint32)),
        ),
        ("transpose", pipeline("transpose"), numeric(rng.integers(0, 1 << 30, 400).astype(np.uint32))),
        ("transpose_split", tsplit, numeric(rng.integers(0, 1 << 30, 400).astype(np.uint32))),
        ("float_split", pipeline(("float_split", {"fmt": 2})), numeric(f32)),
        ("float_split_f64_fallback", pipeline(("float_split", {"fmt": 3})), numeric(rng.integers(0, 1 << 60, 100).astype(np.uint64))),
        ("fused", pipeline("fused_delta_bitpack"), sorted_u32()),
        ("empty", pipeline("delta"), numeric(np.zeros(0, dtype=np.uint32))),
        # delta -> bitpack runs as two nodes (codecs 3 and 6) on every backend
        ("delta_bitpack_sorted_u32", delta_bitpack, sorted_u32()),
        (
            "delta_bitpack_wide_wrapped",
            delta_bitpack,
            numeric(rng.integers(0, 1 << 31, 2000).astype(np.uint32)),
        ),
        (
            "delta_bitpack_3bit",
            delta_bitpack,
            numeric(np.cumsum(rng.integers(0, 8, 2000)).astype(np.uint32)),
        ),
        (
            "delta_bitpack_explicit_8",
            pipeline("delta", ("bitpack", {"bits": 8})),
            sorted_u32(),
        ),
        ("dup_delta_bitpack_transpose", dup_delta_bitpack, sorted_u32(1000)),
        ("delta_bitpack_v3", delta_bitpack, sorted_u32()),
        # a named fused_delta_bitpack whose deltas do not fit runs as
        # delta -> bitpack on every backend; later edges shift past it
        (
            "fused_refused_wide",
            pipeline("fused_delta_bitpack"),
            numeric(rng.integers(0, 1 << 31, 2000).astype(np.uint32)),
        ),
        (
            "fused_refused_then_zlib",
            pipeline("fused_delta_bitpack", "zlib_backend"),
            numeric(rng.integers(0, 1 << 31, 2000).astype(np.uint32)),
        ),
    ]


# cases compressed at an older wire format; every other case uses the default
_CASE_CTX = {"delta_bitpack_v3": CompressionCtx(format_version=3)}


@pytest.mark.parametrize("name,plan,stream", _routed_cases(), ids=lambda c: c if isinstance(c, str) else "")
def test_host_device_frames_byte_identical(name, plan, stream):
    ctx = _CASE_CTX.get(name)
    assert "device" in available_backends()
    fh = compress(plan, stream, backend="host", ctx=ctx)
    fd = compress(plan, stream, backend="device", ctx=ctx)
    assert fh == fd, f"{name}: device frame differs from host frame"
    assert decompress(fd)[0].content_bytes() == stream.content_bytes()
    codecs = [n.codec_id for n in read_frame(fd)[2]]
    if name.startswith("fused_refused"):
        assert codecs[:2] == [3, 6] and 26 not in codecs
    elif name.startswith("fused"):
        assert codecs == [26], "a plan naming fused_delta_bitpack writes codec 26"
    elif "delta_bitpack" in name:
        assert 26 not in codecs and codecs.count(3) == codecs.count(6) == 1


def test_resolve_cache_bypass():
    from repro.codecs import generic_profile

    resolve_cache_clear()
    plan = generic_profile()
    x = numeric(np.arange(4096, dtype=np.uint32))
    r1 = resolve(plan, x)
    assert resolve(plan, x) is r1, "cached path returns the memoized object"
    r3 = resolve(plan, x, use_cache=False)
    assert r3 is not r1, "bypass must re-expand"
    assert r3.steps == r1.steps, "same data -> same expansion"


# ------------------------------------------------------------------ chunking
CHUNK_PLAN = pipeline("delta", "range_pack")


def test_chunked_roundtrip_numeric():
    x = numeric(np.arange(100_000, dtype=np.uint32))
    frame = compress(CHUNK_PLAN, x, chunk_bytes=1 << 15)
    assert is_container(frame)
    assert decompress(frame)[0].content_bytes() == x.content_bytes()


def test_chunked_at_one_byte_granularity():
    x = numeric(np.arange(257, dtype=np.uint32))
    frame = compress(CHUNK_PLAN, x, chunk_bytes=1)
    assert is_container(frame)
    version, chunks = read_container(frame)
    assert len(chunks) == 257, "element-aligned: one u32 per chunk"
    (out,) = decompress(frame)
    assert out.content_bytes() == x.content_bytes()
    assert out.stype == x.stype and out.width == x.width


def test_chunked_roundtrip_serial_and_strings():
    blob = b"the quick brown fox " * 4096
    frame = compress(pipeline("huffman"), serial(blob), chunk_bytes=10_000)
    assert is_container(frame)
    assert decompress_bytes(frame) == blob

    ss = strings([b"alpha", b"", b"gamma" * 10, b"x", b"y" * 100])
    sf = compress(pipeline("store"), ss, chunk_bytes=8)
    assert is_container(sf)
    (out,) = decompress(sf)
    assert out.to_strings() == ss.to_strings()
    assert np.array_equal(out.lengths, ss.lengths)


def test_chunked_device_backend():
    x = sorted_u32(50_000)
    frame = compress(pipeline("delta", "bitpack"), x, chunk_bytes=1 << 15, backend="device")
    assert is_container(frame)
    assert decompress(frame)[0].content_bytes() == x.content_bytes()


def test_small_input_stays_single_frame():
    x = numeric(np.arange(100, dtype=np.uint32))
    frame = compress(CHUNK_PLAN, x, chunk_bytes=1 << 20)
    assert not is_container(frame)
    assert decompress(frame)[0].content_bytes() == x.content_bytes()


def test_chunked_with_selector_profile():
    from repro.codecs import generic_profile

    x = numeric(np.cumsum(rng.integers(0, 9, 60_000)).astype(np.uint32))
    frame = compress(generic_profile(), x, chunk_bytes=1 << 16)
    assert is_container(frame)
    assert decompress(frame)[0].content_bytes() == x.content_bytes()


def test_chunking_requires_v4():
    x = numeric(np.arange(1000, dtype=np.uint32))
    with pytest.raises(ValueError, match="format version"):
        compress(CHUNK_PLAN, x, ctx=CompressionCtx(format_version=3), chunk_bytes=16)


def test_chunking_rejects_multi_input():
    g = GraphBuilder(2)
    g.add("concat", g.input(0), g.input(1))
    plan = g.build()
    a, b = serial(b"x" * 100), serial(b"y" * 100)
    with pytest.raises(ValueError, match="one input"):
        compress(plan, [a, b], chunk_bytes=16)


def test_container_corruption_fails_closed():
    x = numeric(np.arange(10_000, dtype=np.uint32))
    frame = bytearray(compress(CHUNK_PLAN, x, chunk_bytes=1 << 12))
    frame[len(frame) // 2] ^= 0xFF
    with pytest.raises((FrameError, ValueError)):
        decompress(bytes(frame))


def test_container_truncation_fails_closed():
    x = numeric(np.arange(10_000, dtype=np.uint32))
    frame = compress(CHUNK_PLAN, x, chunk_bytes=1 << 12)
    for cut in range(0, len(frame) - 1, max(len(frame) // 53, 1)):
        with pytest.raises((FrameError, ValueError, KeyError, IndexError)):
            decompress(frame[:cut])


def test_container_decode_in_fresh_process():
    """Regression: parallel chunk decode in a process that never compressed
    must not race the lazy codec-registry load (flag set before import done)."""
    import subprocess
    import sys
    import tempfile
    from pathlib import Path

    x = numeric(np.arange(80_000, dtype=np.uint32))
    frame = compress(CHUNK_PLAN, x, chunk_bytes=1 << 13)
    assert is_container(frame)
    with tempfile.TemporaryDirectory() as td:
        p = Path(td) / "frame.bin"
        p.write_bytes(frame)
        src = Path(__file__).resolve().parents[1] / "src"
        out = subprocess.run(
            [
                sys.executable,
                "-c",
                "import sys; sys.path.insert(0, sys.argv[1])\n"
                "from repro.core import decompress\n"
                "(s,) = decompress(open(sys.argv[2], 'rb').read())\n"
                "print('DECODED', s.nbytes)",
                str(src),
                str(p),
            ],
            capture_output=True,
            text=True,
            timeout=240,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert "DECODED 320000" in out.stdout


def test_compressor_chunking_facade():
    x = np.arange(50_000, dtype=np.uint32).tobytes()
    c = Compressor(pipeline("huffman"), chunk_bytes=1 << 14)
    assert c.roundtrip_check(x)
    assert is_container(c.compress(x))


# ----------------------------------------------------- serialized compressors
def test_deserialize_preserves_version_and_level():
    c = Compressor(CHUNK_PLAN, format_version=3, level=8, name="deployed")
    c2 = Compressor.deserialize(c.serialize())
    # the blob's single name field becomes both plan and compressor name on
    # reload (longstanding wire shape), so compare plan structure
    assert c2.plan.nodes == c.plan.nodes and c2.plan.n_inputs == c.plan.n_inputs
    assert c2.format_version == 3, "format_version must survive deployment"
    assert c2.level == 8, "level must survive deployment"
    assert c2.name == "deployed"


def test_deserialize_legacy_blob_defaults():
    """Blobs written before the fix carry no knobs -> current defaults."""
    from repro.core.serialize import deserialize_plan, serialize_plan
    from repro.core.versioning import CURRENT_FORMAT_VERSION

    blob = serialize_plan(CHUNK_PLAN, name="old")  # no knobs, legacy shape
    plan, meta = deserialize_plan(blob)
    assert "format_version" not in meta and "level" not in meta
    c = Compressor.deserialize(blob)
    assert c.format_version == CURRENT_FORMAT_VERSION and c.level == 5


# ------------------------------------------------- which backend ran a node
def test_session_counts_nodes_by_encoding_backend():
    """``stats["nodes"]`` records which backend encoded every executed node:
    device twins count under "device", a codec with no twin under "host"."""
    from repro.core import CompressorSession

    x = sorted_u32(5000)
    plan = pipeline("delta", "transpose", "zlib_backend")
    with CompressorSession(plan, backend="device") as dev:
        frame = dev.compress(x)
        dev.compress(x, chunk_bytes=4000)  # 5 chunks, same routing each
    assert frame == compress(plan, x, backend="host")
    assert dev.stats["nodes"] == {
        "device": {"delta": 6, "transpose": 6},
        "host": {"zlib_backend": 6},
    }
    # delta+bitpack stays two nodes: the deltas of x fit 8 bits, so both twins
    # take them; 3-bit deltas leave bitpack to the host (widths dividing 32)
    with CompressorSession(pipeline("delta", "bitpack"), backend="device") as s:
        s.compress(x)
        s.compress(numeric(np.cumsum(rng.integers(0, 8, 2000)).astype(np.uint32)))
    assert s.stats["nodes"] == {
        "device": {"delta": 2, "bitpack": 1},
        "host": {"bitpack": 1},
    }
    with CompressorSession(plan) as host:
        host.compress(x)
    assert host.stats["nodes"] == {
        "host": {"delta": 1, "transpose": 1, "zlib_backend": 1}
    }


def test_session_counts_failed_over_nodes_on_host():
    """A chunk whose device kernel faults re-runs on the host; its nodes
    count where they actually ran."""
    from repro.core import CompressorSession
    from repro.reliability import BackendHealth, FaultPlan

    x = sorted_u32(5000)
    plan = pipeline("delta", "transpose")
    health = BackendHealth(threshold=2)  # one fault does not quarantine
    sess = CompressorSession(plan, backend="device", failover=health)
    with FaultPlan().at("device.encode.device.transpose", times=1).arm():
        assert sess.compress(x) == compress(plan, x, backend="host")
    assert sess.stats["nodes"] == {"host": {"delta": 1, "transpose": 1}}
    assert health.stats()["device"]["failures"] == 1
    sess.compress(x)
    assert sess.stats["nodes"] == {
        "host": {"delta": 1, "transpose": 1},
        "device": {"delta": 1, "transpose": 1},
    }
    sess.close()
