"""Property-based roundtrip tests: decode(encode(x)) == x for every codec,
over adversarial shapes/dtypes/values (hypothesis).  This is THE invariant of
the graph model — codecs must be bijective on their domains (paper §III-B)."""
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis, or skip-at-call-time stubs

from repro.core import Compressor, GraphBuilder, numeric, pipeline, serial, strings
from repro.core.codec import all_codecs


def chk(plan, stream):
    assert Compressor(plan).roundtrip_check(stream)


bytes_st = st.binary(min_size=0, max_size=4096)
small_bytes_st = st.binary(min_size=0, max_size=512)

uint_dtypes = st.sampled_from([np.uint8, np.uint16, np.uint32, np.uint64])


@st.composite
def numeric_arrays(draw, max_len=2048):
    dt = draw(uint_dtypes)
    n = draw(st.integers(0, max_len))
    bits = 8 * np.dtype(dt).itemsize
    vals = draw(
        st.lists(st.integers(0, (1 << bits) - 1), min_size=n, max_size=n)
    )
    return np.asarray(vals, dtype=dt)


@given(bytes_st)
@settings(max_examples=50, deadline=None)
def test_store_roundtrip(b):
    chk(pipeline("store"), serial(b))


@given(numeric_arrays())
@settings(max_examples=50, deadline=None)
def test_delta_roundtrip(x):
    chk(pipeline("delta"), numeric(x))


@given(numeric_arrays())
@settings(max_examples=50, deadline=None)
def test_zigzag_roundtrip(x):
    chk(pipeline("zigzag"), numeric(x))


@given(numeric_arrays())
@settings(max_examples=50, deadline=None)
def test_delta_zigzag_chain(x):
    chk(pipeline("delta", "zigzag"), numeric(x))


@given(numeric_arrays())
@settings(max_examples=50, deadline=None)
def test_transpose_roundtrip(x):
    chk(pipeline("transpose"), numeric(x))


@given(numeric_arrays())
@settings(max_examples=50, deadline=None)
def test_transpose_split_roundtrip(x):
    w = x.dtype.itemsize
    g = GraphBuilder(1)
    g.add("transpose_split", g.input(0), n_out=w)
    chk(g.build(), numeric(x))


@given(numeric_arrays(max_len=512))
@settings(max_examples=50, deadline=None)
def test_range_pack_roundtrip(x):
    if x.size and int(x.max()) - int(x.min()) >= (1 << 57):
        return  # documented bitpack limit
    chk(pipeline("range_pack"), numeric(x))


@given(st.lists(st.integers(0, 255), min_size=0, max_size=2048))
@settings(max_examples=50, deadline=None)
def test_bitpack_roundtrip(vals):
    chk(pipeline("bitpack"), numeric(np.asarray(vals, dtype=np.uint8)))


@given(numeric_arrays(max_len=512))
@settings(max_examples=50, deadline=None)
def test_rle_roundtrip(x):
    g = GraphBuilder(1)
    g.add("rle", g.input(0))
    chk(g.build(), numeric(x))


@given(numeric_arrays(max_len=512))
@settings(max_examples=50, deadline=None)
def test_tokenize_roundtrip(x):
    g = GraphBuilder(1)
    g.add("tokenize", g.input(0))
    chk(g.build(), numeric(x))


@given(st.lists(small_bytes_st, min_size=0, max_size=128))
@settings(max_examples=50, deadline=None)
def test_tokenize_strings_roundtrip(items):
    g = GraphBuilder(1)
    g.add("tokenize", g.input(0))
    chk(g.build(), strings(items))


@given(st.lists(small_bytes_st, min_size=0, max_size=64))
@settings(max_examples=50, deadline=None)
def test_string_split_roundtrip(items):
    g = GraphBuilder(1)
    g.add("string_split", g.input(0))
    chk(g.build(), strings(items))


@given(bytes_st)
@settings(max_examples=60, deadline=None)
def test_huffman_roundtrip(b):
    g = GraphBuilder(1)
    g.add("huffman", g.input(0))
    chk(g.build(), serial(b))


@given(bytes_st)
@settings(max_examples=60, deadline=None)
def test_fse_roundtrip(b):
    g = GraphBuilder(1)
    g.add("fse", g.input(0))
    chk(g.build(), serial(b))


@given(st.binary(min_size=0, max_size=8192))
@settings(max_examples=40, deadline=None)
def test_lz77_roundtrip(b):
    g = GraphBuilder(1)
    g.add("lz77", g.input(0))
    chk(g.build(), serial(b))


@given(st.binary(min_size=0, max_size=8192))
@settings(max_examples=25, deadline=None)
def test_lz77_on_repetitive(b):
    data = b * 4 + b[::-1] * 2
    g = GraphBuilder(1)
    g.add("lz77", g.input(0))
    chk(g.build(), serial(data))


@given(bytes_st)
@settings(max_examples=30, deadline=None)
def test_zlib_backend_roundtrip(b):
    chk(pipeline("zlib_backend"), serial(b))


@given(st.lists(st.floats(allow_nan=False, width=32), min_size=0, max_size=1024))
@settings(max_examples=40, deadline=None)
def test_float_split_f32_roundtrip(vals):
    x = np.asarray(vals, dtype=np.float32)
    g = GraphBuilder(1)
    g.add("float_split", g.input(0), fmt=2)
    chk(g.build(), numeric(x))


@given(st.lists(st.integers(0, (1 << 16) - 1), min_size=0, max_size=1024))
@settings(max_examples=40, deadline=None)
def test_float_split_bf16_roundtrip(vals):
    x = np.asarray(vals, dtype=np.uint16)  # arbitrary bf16 bit patterns
    g = GraphBuilder(1)
    g.add("float_split", g.input(0), fmt=0)
    chk(g.build(), numeric(x))


@given(st.lists(st.integers(0, (1 << 64) - 1), min_size=0, max_size=256))
@settings(max_examples=30, deadline=None)
def test_float_split_f64_roundtrip(vals):
    x = np.asarray(vals, dtype=np.uint64)
    g = GraphBuilder(1)
    g.add("float_split", g.input(0), fmt=3)
    chk(g.build(), numeric(x))


@given(
    st.lists(
        st.text(
            alphabet=st.characters(codec="ascii", exclude_characters=",\n"),
            max_size=12,
        ),
        min_size=1,
        max_size=40,
    ),
    st.integers(1, 4),
)
@settings(max_examples=40, deadline=None)
def test_csv_profile_roundtrip(cells, n_cols):
    rows = [cells[i : i + n_cols] for i in range(0, len(cells) - n_cols + 1, n_cols)]
    if not rows:
        return
    data = ("\n".join(",".join(r) for r in rows) + "\n").encode()
    if data == b"\n":
        return  # empty body: csv_split rejects by design (trainer falls back)
    from repro.codecs import csv_profile

    chk(csv_profile(n_cols), serial(data))


@given(st.lists(small_bytes_st, min_size=0, max_size=64))
@settings(max_examples=40, deadline=None)
def test_parse_numeric_roundtrip(items):
    # mix in genuine numbers to hit both branches
    mixed = items + [b"123", b"-987654321", b"0", b"007", b"-0", b"99999999999999999999999"]
    g = GraphBuilder(1)
    g.add("parse_numeric", g.input(0))
    chk(g.build(), strings(mixed))


@given(numeric_arrays(max_len=256))
@settings(max_examples=30, deadline=None)
def test_generic_profile_numeric(x):
    from repro.codecs import generic_profile

    chk(generic_profile(), numeric(x))


@given(bytes_st)
@settings(max_examples=30, deadline=None)
def test_generic_profile_bytes(b):
    from repro.codecs import generic_profile

    chk(generic_profile(), serial(b))


def test_every_registered_codec_is_exercised_somewhere():
    """Meta-test: the registry matches the documented id map."""
    ids = {spec.codec_id for spec in all_codecs().values()}
    assert ids == set(range(1, 30)), sorted(ids)


# --------------------------------------------------- csv_split regressions
def test_csv_split_multibyte_separator_roundtrip():
    """Regression: the header stored only sep_b[0], so decode rejoined with
    one byte and multi-byte separators corrupted silently."""
    raw = b"a::b::c\n1::2::3\nx::y::z\n"
    g = GraphBuilder(1)
    g.add("csv_split", g.input(0), n_out=3, sep="::")
    chk(g.build(), serial(raw))


@given(
    st.text(
        alphabet=st.characters(codec="ascii", exclude_characters="\r\n"),
        min_size=1,
        max_size=4,
    ),
    st.lists(st.integers(0, 999), min_size=2, max_size=40),
)
@settings(max_examples=40, deadline=None)
def test_csv_split_any_separator_roundtrip(sep, vals):
    rows = [f"{v}{sep}{v * 7}".encode() for v in vals]
    n_cols = rows[0].count(sep.encode()) + 1
    if any(r.count(sep.encode()) + 1 != n_cols for r in rows):
        return  # digits colliding with the separator: not rectangular
    g = GraphBuilder(1)
    g.add("csv_split", g.input(0), n_out=n_cols, sep=sep)
    chk(g.build(), serial(b"\n".join(rows) + b"\n"))


def test_csv_split_crlf_roundtrip():
    """Regression twin of the sniff_csv CRLF bug: \\r\\n files must
    round-trip byte-exactly and must NOT leave \\r glued to the last
    column (the per-column streams are clean)."""
    from repro.core.codec import get_codec

    raw = b"a,b\r\n1,2\r\n33,44\r\n"
    outs, h = get_codec("csv_split").run_encode([serial(raw)], {"sep": ","})
    assert outs[1].to_strings() == [b"b", b"2", b"44"]  # no \r suffixes
    rec = get_codec("csv_split").run_decode(outs, h)[0]
    assert rec.data.tobytes() == raw


@pytest.mark.parametrize(
    "raw,n_cols",
    [
        (b"a,b\r\n1,2\r", 2),  # final line CR without LF
        (b"a,b\r\n1,2\n", 2),  # mixed endings
        (b"\r\n", 1),
    ],
)
def test_csv_split_cr_edge_cases_roundtrip(raw, n_cols):
    g = GraphBuilder(1)
    g.add("csv_split", g.input(0), n_out=n_cols, sep=",")
    chk(g.build(), serial(raw))


@pytest.mark.parametrize("sep", ["", "a\nb", "\r", "x\ry"])
def test_csv_split_rejects_bad_separators(sep):
    from repro.core.codec import get_codec

    with pytest.raises(ValueError):
        get_codec("csv_split").run_encode([serial(b"a,b\n")], {"sep": sep})


# ---------------------------------------------------- graph codec roundtrips
def _edge_text(pairs, sep=b"\t", junk=(), trailing=True):
    lines = list(junk) + [b"%d%s%d" % (u, sep, v) for u, v in pairs]
    return b"\n".join(lines) + (b"\n" if trailing else b"")


@given(
    st.lists(st.tuples(st.integers(0, 500), st.integers(0, 500)), max_size=200),
    st.lists(small_bytes_st.filter(lambda b: b"\n" not in b), max_size=5),
    st.booleans(),
)
@settings(max_examples=40, deadline=None)
def test_edge_list_roundtrip(pairs, junk, trailing):
    raw = _edge_text(sorted(pairs), junk=junk, trailing=trailing)
    g = GraphBuilder(1)
    g.add("edge_list", g.input(0), sep="\t")
    chk(g.build(), serial(raw))


@given(bytes_st)
@settings(max_examples=40, deadline=None)
def test_edge_list_lossless_on_arbitrary_bytes(b):
    """edge_list is total: any byte blob round-trips (unparsed lines are
    byte-exact exceptions), under explicit and auto separators."""
    g = GraphBuilder(1)
    g.add("edge_list", g.input(0), sep="auto")
    chk(g.build(), serial(b))


@given(
    st.lists(st.integers(0, 2**64 - 1), max_size=300),
    st.integers(0, 8),
)
@settings(max_examples=40, deadline=None)
def test_adj_gap_roundtrip_unsorted(flat, window):
    """adj_gap must be lossless on ANY (src, dst) columns — unsorted,
    duplicate, full-u64-range — not just sorted adjacency."""
    n = len(flat) // 2
    src = np.asarray(flat[:n], dtype=np.uint64)
    dst = np.asarray(flat[n : 2 * n], dtype=np.uint64)
    g = GraphBuilder(2)
    g.add("adj_gap", g.input(0), g.input(1), window=window)
    assert Compressor(g.build()).roundtrip_check([numeric(src), numeric(dst)])


@given(st.integers(1, 200), st.integers(1, 12), st.integers(0, 8))
@settings(max_examples=30, deadline=None)
def test_adj_gap_roundtrip_sorted_adjacency(n_nodes, max_deg, window):
    """The reference/copy-list path: sorted adjacency with repeated
    neighborhoods (every run similar), all widths."""
    rng = np.random.default_rng(n_nodes * 13 + max_deg)
    src, dst = [], []
    for u in range(n_nodes):
        for v in np.unique(rng.integers(0, n_nodes, max_deg)):
            src.append(u)
            dst.append(int(v))
    for dt in (np.uint16, np.uint32, np.uint64):
        s = np.asarray(src, dtype=dt)
        d = np.asarray(dst, dtype=dt)
        g = GraphBuilder(2)
        g.add("adj_gap", g.input(0), g.input(1), window=window)
        assert Compressor(g.build()).roundtrip_check([numeric(s), numeric(d)])


@given(
    st.lists(st.tuples(st.integers(0, 2**32 - 1), st.integers(0, 2**32 - 1)),
             max_size=200),
    st.sampled_from([2, 4, 8]),
)
@settings(max_examples=40, deadline=None)
def test_edge_list_bin_roundtrip(pairs, width):
    hi = (1 << (8 * width)) - 1
    arr = np.asarray(
        [(u & hi, v & hi) for u, v in pairs], dtype=np.uint64
    ).astype({2: np.uint16, 4: np.uint32, 8: np.uint64}[width])
    raw = arr.tobytes()
    g = GraphBuilder(1)
    g.add("edge_list_bin", g.input(0), width=width)
    chk(g.build(), serial(raw))


def test_edge_list_bin_rejects_misaligned():
    from repro.core.codec import get_codec

    with pytest.raises(ValueError):
        get_codec("edge_list_bin").run_encode([serial(b"\x00" * 7)], {"width": 4})
    with pytest.raises(ValueError):
        get_codec("edge_list_bin").run_encode([serial(b"\x00" * 8)], {"width": 3})


def _snap_edges(nbytes: int, seed: int) -> bytes:
    """SNAP-style text edge list: ``#`` header, then sorted ``u\\tv`` lines
    with power-law target popularity (hubs shared across adjacency lists)."""
    rng = np.random.default_rng(seed)
    n_edges = nbytes // 8 + 64
    while True:  # dedup and short ids shrink the text: grow until it covers
        n_nodes = max(n_edges // 16, 64)
        w = 1.0 / np.arange(1, n_nodes + 1) ** 1.1
        dst = rng.choice(n_nodes, size=n_edges, p=w / w.sum()).astype(np.uint64)
        src = np.sort(rng.integers(0, n_nodes, n_edges)).astype(np.uint64)
        pairs = np.unique(np.stack([src, dst], axis=1), axis=0)
        head = b"# Nodes: %d  Edges: %d\n# FromNodeId\tToNodeId\n" % (
            n_nodes, len(pairs)
        )
        raw = head + b"\n".join(b"%d\t%d" % (u, v) for u, v in pairs) + b"\n"
        if len(raw) >= nbytes:
            return raw[:nbytes]
        n_edges += n_edges // 2


@pytest.mark.parametrize("nbytes,seed", [(64 << 10, 0), (64 << 10, 1), (256 << 10, 2)])
def test_graph_profile_beats_text_and_numeric_on_edge_lists(nbytes, seed):
    """The acceptance floor for the edge-list frontend: on an edge-list corpus
    the structure-aware ``graph`` profile out-compresses the generic ``text``
    and ``numeric`` profiles, each resolved on this data."""
    from repro.codecs.profiles import resolve_profile_spec
    from repro.core import compress, decompress

    data = _snap_edges(nbytes, seed)
    ratios = {}
    for prof in ("graph", "text", "numeric"):
        frame = compress(resolve_profile_spec(prof), [serial(data)], use_resolve_cache=False)
        assert decompress(frame)[0].content_bytes() == data, prof
        ratios[prof] = len(data) / len(frame)
    assert ratios["graph"] > max(ratios["text"], ratios["numeric"]), ratios


@given(
    st.lists(st.tuples(st.integers(0, 300), st.integers(0, 300)), max_size=300)
)
@settings(max_examples=30, deadline=None)
def test_graph_profile_roundtrip(pairs):
    from repro.codecs import graph_profile

    raw = _edge_text(sorted(set(pairs)), junk=[b"# hdr"])
    chk(graph_profile(), serial(raw))


@given(bytes_st)
@settings(max_examples=30, deadline=None)
def test_graph_profile_lossless_on_arbitrary_bytes(b):
    from repro.codecs import graph_profile

    chk(graph_profile(), serial(b))


def test_concat_mixed_signedness_is_bit_exact():
    """Regression: np.concatenate(int64, uint64) promotes to float64 and
    silently rounds large values — concat must use unsigned bit views."""
    from repro.core.codec import get_codec

    big = np.array([2**63 + 12345, 2**64 - 1], dtype=np.uint64)
    signed = np.array([-7, 2**62], dtype=np.int64)
    cat = get_codec("concat")
    outs, h = cat.run_encode([numeric(signed), numeric(big)], {})
    back = cat.run_decode(outs, h)
    assert back[0].content_bytes() == numeric(signed).content_bytes()
    assert back[1].content_bytes() == numeric(big).content_bytes()


@pytest.mark.parametrize(
    "codec,stype_width",
    [
        ("huffman", ("serial", 1)),
        ("huffman", ("numeric", 1)),
        ("huffman", ("struct", 1)),
        ("fse", ("serial", 1)),
        ("fse", ("numeric", 1)),
        ("lz77", ("numeric", 2)),
        ("rle", ("numeric", 4)),
        ("tokenize", ("struct", 3)),
        ("zlib_backend", ("numeric", 8)),
        ("lzma_backend", ("numeric", 4)),
        ("bz2_backend", ("numeric", 2)),
        ("transpose", ("numeric", 4)),
    ],
)
def test_codecs_are_type_faithful(codec, stype_width):
    """decode(encode(x)) must reproduce the TYPE, not just the bytes —
    regression for the huffman/fse SERIAL-flattening bug."""
    from repro.core import struct as mk_struct
    from repro.core.codec import get_codec

    kind, w = stype_width
    rng = np.random.default_rng(0)
    if kind == "serial":
        s = serial(rng.integers(0, 9, 500).astype(np.uint8).tobytes())
    elif kind == "numeric":
        s = numeric(rng.integers(0, 7, 300).astype(f"uint{8*w}"))
    else:
        s = mk_struct(rng.integers(0, 5, 300 * w).astype(np.uint8), w)
    spec = get_codec(codec)
    outs, header = spec.run_encode([s], {})
    (back,) = spec.run_decode(outs, header)
    assert back.stype == s.stype and back.width == s.width
    assert back.content_bytes() == s.content_bytes()
