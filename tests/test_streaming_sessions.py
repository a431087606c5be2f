"""Streaming-session tests (ISSUE 3 tentpole + satellites).

Invariants:
  * ``CompressorSession`` output is byte-identical to the one-shot
    ``compress()`` for chunked and unchunked inputs, warm or cold.
  * Session roundtrips cross chunk boundaries for NUMERIC/STRUCT/STRING.
  * The vectorized STRING ``_split_chunks`` matches the scalar reference on
    ragged inputs (zero-length strings, oversize strings, exact boundaries).
  * ``stream_io.compress_file`` never loads the input whole, produces the
    same bytes as the in-memory path, and the in-flight window bounds
    concurrency.
  * The ``python -m repro`` CLI compresses/inspects/decompresses end to end.
"""
import io

import numpy as np
import pytest
from _hyp import given, settings, st

from repro.codecs import generic_profile, text_profile
from repro.core import (
    Compressor,
    CompressorSession,
    DecompressorSession,
    compress,
    decompress,
    numeric,
    pipeline,
    serial,
    strings,
    struct,
)
from repro.core import stream_io
from repro.core.engine import _split_chunks
from repro.core.message import Stream, SType


def _scalar_split_strings(s: Stream, chunk_bytes: int):
    """The pre-vectorization per-string loop, kept as the reference."""
    out = []
    lens = s.lengths if s.lengths is not None else np.zeros(0, np.uint32)
    i, off = 0, 0
    while i < lens.size:
        j, nb = i, 0
        while j < lens.size and (j == i or nb + int(lens[j]) <= chunk_bytes):
            nb += int(lens[j])
            j += 1
        out.append(Stream(s.data[off : off + nb], SType.STRING, 1, lens[i:j]))
        i, off = j, off + nb
    return out or [s]


# ----------------------------------------------------------- split equivalence
def _assert_same_split(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x.data, y.data)
        assert np.array_equal(x.lengths, y.lengths)


@pytest.mark.parametrize(
    "lens,chunk_bytes",
    [
        ([], 8),
        ([0, 0, 0], 4),
        ([5], 3),  # single oversize string
        ([10, 1, 1], 10),  # exact boundary then spill
        ([3, 3, 3, 3], 6),  # clean pairs
        ([0, 7, 0, 0, 2, 9, 0], 9),  # zeros around boundaries
        ([1] * 100, 1),  # one string per chunk
    ],
)
def test_split_chunks_string_matches_scalar_reference(lens, chunk_bytes):
    rng = np.random.default_rng(0)
    s = strings([bytes(rng.integers(0, 256, l, dtype=np.uint8)) for l in lens])
    _assert_same_split(
        _split_chunks(s, chunk_bytes), _scalar_split_strings(s, chunk_bytes)
    )


@given(st.data())
@settings(max_examples=120, deadline=None)
def test_split_chunks_string_matches_scalar_reference_fuzz(data):
    lens = data.draw(st.lists(st.integers(0, 33), max_size=60))
    chunk_bytes = data.draw(st.integers(1, 64))
    rng = np.random.default_rng(1)
    s = strings([bytes(rng.integers(0, 256, l, dtype=np.uint8)) for l in lens])
    _assert_same_split(
        _split_chunks(s, chunk_bytes), _scalar_split_strings(s, chunk_bytes)
    )


# ------------------------------------------------------- session byte-identity
def test_session_byte_identical_to_oneshot_chunked():
    plan = pipeline("delta", "range_pack")
    data = numeric(np.arange(60000, dtype=np.uint32))
    oneshot = compress(plan, data, chunk_bytes=4096)
    with CompressorSession(plan, chunk_bytes=4096) as sess:
        cold = sess.compress(data)
        warm = sess.compress(data)
        buf = io.BytesIO()
        n = sess.compress_to(data, buf)
    assert cold == oneshot and warm == oneshot
    assert buf.getvalue() == oneshot and n == len(oneshot)


def test_session_byte_identical_to_oneshot_unchunked():
    plan = pipeline("delta", "range_pack")
    data = numeric(np.arange(5000, dtype=np.uint32))
    with CompressorSession(plan) as sess:
        assert sess.compress(data) == compress(plan, data)


def test_session_byte_identical_selector_profile():
    """Dynamic plans: selector expansion happens once per shape per session,
    yet every call's wire output matches the throwaway path."""
    prof = generic_profile()
    rng = np.random.default_rng(3)
    data = numeric(rng.integers(0, 40, 1 << 15, dtype=np.int64).cumsum().astype(np.uint32))
    oneshot = compress(prof, data, chunk_bytes=8192)
    with CompressorSession(prof, chunk_bytes=8192) as sess:
        assert sess.compress(data) == oneshot
        assert sess.compress(data) == oneshot


def test_decompressor_session_matches_module_decompress():
    plan = pipeline("delta", "range_pack")
    data = numeric(np.arange(30000, dtype=np.uint32))
    frame = compress(plan, data, chunk_bytes=4096)
    with DecompressorSession() as sess:
        for _ in range(2):  # warm reuse
            (out,) = sess.decompress(frame)
            assert out.content_bytes() == data.content_bytes()
        (via_reader,) = sess.decompress_from(io.BytesIO(frame))
        assert via_reader.content_bytes() == data.content_bytes()


# ------------------------------------------- roundtrips across chunk boundaries
@pytest.mark.parametrize("chunk_bytes", [256, 1000, 4096])
def test_session_roundtrip_numeric_across_boundaries(chunk_bytes):
    rng = np.random.default_rng(5)
    data = numeric(rng.integers(0, 9999, 4001, dtype=np.uint16))
    with CompressorSession(generic_profile(), chunk_bytes=chunk_bytes) as sess:
        frame = sess.compress(data)
    (back,) = decompress(frame)
    assert back.stype == SType.NUMERIC and back.width == 2
    assert back.content_bytes() == data.content_bytes()


@pytest.mark.parametrize("chunk_bytes", [128, 777])
def test_session_roundtrip_struct_across_boundaries(chunk_bytes):
    rng = np.random.default_rng(6)
    data = struct(rng.integers(0, 256, 12 * 500, dtype=np.uint8).tobytes(), 12)
    with CompressorSession(generic_profile(), chunk_bytes=chunk_bytes) as sess:
        frame = sess.compress(data)
    (back,) = decompress(frame)
    assert back.stype == SType.STRUCT and back.width == 12
    assert back.content_bytes() == data.content_bytes()


@pytest.mark.parametrize("chunk_bytes", [64, 512])
def test_session_roundtrip_string_across_boundaries(chunk_bytes):
    rng = np.random.default_rng(7)
    items = [
        bytes(rng.integers(97, 123, int(l), dtype=np.uint8))
        for l in rng.integers(0, 40, 300)
    ]
    data = strings(items)
    with CompressorSession(generic_profile(), chunk_bytes=chunk_bytes) as sess:
        frame = sess.compress(data)
    (back,) = decompress(frame)
    assert back.stype == SType.STRING
    assert back.content_bytes() == data.content_bytes()
    assert np.array_equal(back.lengths, data.lengths)
    # and through the streaming reader
    with DecompressorSession() as dsess:
        (srt,) = dsess.decompress_from(io.BytesIO(frame))
    assert srt.content_bytes() == data.content_bytes()
    assert np.array_equal(srt.lengths, data.lengths)


# ------------------------------------------------------------- bounded window
def test_window_bounds_inflight_chunks():
    plan = pipeline("delta", "range_pack")
    data = numeric(np.arange(100000, dtype=np.uint32))
    with CompressorSession(plan, chunk_bytes=1024, window=3) as sess:
        frame = sess.compress(data)
        assert sess.stats["chunks"] > 20
        assert 1 <= sess.stats["max_inflight"] <= 3
    assert frame == compress(plan, data, chunk_bytes=1024)


def test_decode_window_bounds_inflight_chunks():
    plan = pipeline("delta", "range_pack")
    data = numeric(np.arange(100000, dtype=np.uint32))
    frame = compress(plan, data, chunk_bytes=1024)
    with DecompressorSession(window=2) as sess:
        (out,) = sess.decompress_from(io.BytesIO(frame))
        assert sess.stats["max_inflight"] <= 2
    assert out.content_bytes() == data.content_bytes()


# ------------------------------------------------------------------ stream_io
def test_compress_file_byte_identical_and_lazy(tmp_path):
    rng = np.random.default_rng(8)
    data = b"repeat me " * 30000 + bytes(rng.integers(0, 256, 10000, dtype=np.uint8))
    src = tmp_path / "in.bin"
    dst = tmp_path / "out.ozl"
    src.write_bytes(data)

    stats = stream_io.compress_file(src, dst, text_profile(), chunk_bytes=16384)
    assert stats["container"] and stats["chunks"] == -(-len(data) // 16384)
    assert dst.read_bytes() == compress(text_profile(), serial(data), chunk_bytes=16384)

    rt = tmp_path / "rt.bin"
    dstats = stream_io.decompress_file(dst, rt)
    assert rt.read_bytes() == data
    assert dstats["chunks"] == stats["chunks"]


def test_compress_file_small_input_bare_frame(tmp_path):
    data = b"tiny payload"
    src = tmp_path / "in.bin"
    dst = tmp_path / "out.ozl"
    src.write_bytes(data)
    stats = stream_io.compress_file(src, dst, text_profile(), chunk_bytes=1 << 20)
    assert not stats["container"]
    assert dst.read_bytes() == compress(text_profile(), serial(data))
    rt = tmp_path / "rt.bin"
    stream_io.decompress_file(dst, rt)
    assert rt.read_bytes() == data


def test_compress_file_unknown_length_source(tmp_path):
    """Non-seekable sources stream through the backpatching container mode;
    the result decodes identically (bytes differ only at the count field)."""

    class NoSeek:
        def __init__(self, b):
            self._f = io.BytesIO(b)

        def read(self, n=-1):
            return self._f.read(n)

        def seekable(self):
            return False

    data = b"0123456789abcdef" * 8192
    dst = tmp_path / "out.ozl"
    stats = stream_io.compress_file(
        NoSeek(data), dst, text_profile(), chunk_bytes=16384
    )
    assert stats["container"] and stats["bytes_in"] == len(data)
    rt = tmp_path / "rt.bin"
    stream_io.decompress_file(dst, rt)
    assert rt.read_bytes() == data


def test_session_reuse_across_files(tmp_path):
    """One long-lived session serving many files (the serve.py shape)."""
    plan = text_profile()
    with CompressorSession(plan, chunk_bytes=4096) as sess, DecompressorSession() as dsess:
        for i in range(3):
            data = (b"payload %d " % i) * 5000
            src = tmp_path / f"in{i}.bin"
            dst = tmp_path / f"out{i}.ozl"
            rt = tmp_path / f"rt{i}.bin"
            src.write_bytes(data)
            stream_io.compress_file(src, dst, plan, session=sess)
            stream_io.decompress_file(dst, rt, session=dsess)
            assert rt.read_bytes() == data
        assert sess.stats["calls"] == 3
        assert dsess.stats["chunks"] >= 3


def test_compress_file_rejects_mismatched_session_plan(tmp_path):
    from repro.codecs import numeric_profile

    src = tmp_path / "in.bin"
    src.write_bytes(b"x" * 100)
    with CompressorSession(text_profile(), chunk_bytes=64) as sess:
        with pytest.raises(ValueError, match="does not match"):
            stream_io.compress_file(src, tmp_path / "o", numeric_profile(), session=sess)


def test_compress_to_mirrors_compress_errors():
    data = numeric(np.arange(100, dtype=np.uint32))
    with CompressorSession(pipeline("delta", "range_pack"), chunk_bytes=64) as sess:
        with pytest.raises(ValueError, match="exactly one input"):
            sess.compress([data, data])
        with pytest.raises(ValueError, match="exactly one input"):
            sess.compress_to([data, data], io.BytesIO())


def test_compressor_session_helper():
    comp = Compressor(pipeline("delta", "range_pack"), chunk_bytes=2048, level=7)
    data = numeric(np.arange(20000, dtype=np.uint32))
    with comp.session() as sess:
        assert sess.compress(data) == comp.compress(data)
        assert sess.ctx.level == 7


# ------------------------------------------------------------------------ CLI
def test_cli_end_to_end(tmp_path, capsys):
    from repro.cli import main

    data = b"level=INFO svc=auth msg=handled in 42us\n" * 5000
    src = tmp_path / "corpus.bin"
    frame = tmp_path / "corpus.ozl"
    rt = tmp_path / "corpus.rt"
    src.write_bytes(data)

    assert main(
        ["compress", str(src), "-o", str(frame), "--profile", "text",
         "--chunk-bytes", "32KiB"]
    ) == 0
    assert main(["inspect", str(frame)]) == 0
    out = capsys.readouterr().out
    assert "container" in out and "zlib_backend" in out
    assert main(["decompress", str(frame), "-o", str(rt)]) == 0
    assert rt.read_bytes() == data


def test_cli_plan_roundtrip(tmp_path):
    from repro.cli import main

    plan_file = tmp_path / "trained.ozp"
    plan_file.write_bytes(Compressor(text_profile(), name="t").serialize())
    data = b"x,y,z\n1,2,3\n" * 2000
    src = tmp_path / "in.csv"
    frame = tmp_path / "in.ozl"
    rt = tmp_path / "in.rt"
    src.write_bytes(data)
    assert main(["compress", str(src), "-o", str(frame), "--plan", str(plan_file)]) == 0
    assert main(["decompress", str(frame), "-o", str(rt)]) == 0
    assert rt.read_bytes() == data


def test_cli_profiles_and_errors(tmp_path, capsys):
    from repro.cli import main

    assert main(["profiles"]) == 0
    assert "generic" in capsys.readouterr().out
    bad = tmp_path / "bad.ozl"
    bad.write_bytes(b"definitely not a frame")
    assert main(["decompress", str(bad), "-o", str(tmp_path / "x")]) == 2
    assert main(["inspect", str(bad)]) == 2


def test_session_pipeline_overlap_stats_and_prefetch():
    """The double-buffered window reports overlap accounting and changes
    scheduling only — frames stay byte-identical to the one-shot call."""
    plan = pipeline("delta", "range_pack")
    data = numeric(np.arange(120000, dtype=np.uint32))
    oneshot = compress(plan, data, chunk_bytes=4096)
    with CompressorSession(plan, chunk_bytes=4096, n_workers=2) as sess:
        assert sess.compress(data) == oneshot
        st = sess.stats
        assert st["prefetch_hits"] + st["prefetch_misses"] > 0
        assert st["draw_wait_s"] >= 0.0 and st["encode_wait_s"] >= 0.0
        assert st["max_inflight"] >= 1


def test_session_pipeline_prefetch_draws_overlap_lazy_source():
    """A lazy chunk source is drawn on the draw thread while encodes run;
    the in-order container output is unaffected."""
    plan = pipeline("delta", "range_pack")
    data = numeric(np.arange(120000, dtype=np.uint32))
    chunks = _split_chunks(data, 4096)
    oneshot = compress(plan, data, chunk_bytes=4096)
    with CompressorSession(plan, n_workers=2) as sess:
        buf = io.BytesIO()
        sess.compress_chunks(iter(chunks), buf, n_chunks=len(chunks))
        assert buf.getvalue() == oneshot
        assert (
            sess.stats["prefetch_hits"] + sess.stats["prefetch_misses"]
            >= len(chunks) - 1
        )


def test_prefetch_source_error_propagates_promptly():
    """A lazy source that dies mid-stream fails the call with its own error
    as soon as the draw thread reports it — it must not hide behind a full
    window of in-flight encodes — and the session stays usable after."""
    import time

    plan = pipeline("delta", "range_pack")
    data = numeric(np.arange(120000, dtype=np.uint32))
    chunks = _split_chunks(data, 4096)

    class SourceDied(Exception):
        pass

    def source():
        for c in chunks[:3]:
            yield c
        raise SourceDied("lazy source died mid-stream")

    with CompressorSession(plan, chunk_bytes=4096, n_workers=2) as sess:
        buf = io.BytesIO()
        t0 = time.perf_counter()
        with pytest.raises(SourceDied, match="died mid-stream"):
            sess.compress_chunks(source(), buf, n_chunks=len(chunks))
        assert time.perf_counter() - t0 < 5.0  # surfaced, not deadlocked
        # the pool survives a poisoned source: the next request is clean
        assert sess.compress(data) == compress(plan, data, chunk_bytes=4096)
