"""The work functions behind the kernel rooflines: bytes from the codec's
sizes, which nodes the device runs, and the share arithmetic."""
import numpy as np
import pytest

from bench import reference as R
from bench import work
from bench.reference import NUMERIC, SERIAL, STRUCT, NodeRecord


def rec(codec, ins, outs, **header):
    return NodeRecord(codec, header, ins, outs)


@pytest.mark.parametrize("codec,ins,outs,header,want", [
    ("delta", [(NUMERIC, 4, 400)], [(NUMERIC, 4, 400)], {}, 800),
    ("bitpack", [(NUMERIC, 2, 200)], [(SERIAL, 1, 38)], {"bits": 3}, 238),
    ("fused_delta_bitpack", [(NUMERIC, 4, 400)], [(SERIAL, 1, 50)], {"bits": 4}, 450),
    ("transpose", [(NUMERIC, 8, 800)], [(SERIAL, 1, 800)], {}, 1600),
    ("transpose_split", [(NUMERIC, 4, 400)], [(SERIAL, 1, 100)] * 4, {}, 800),
    ("float_split", [(NUMERIC, 4, 400)],
     [(SERIAL, 1, 13), (NUMERIC, 1, 100), (NUMERIC, 4, 400)], {"fmt": 2}, 913),
    ("huffman", [(SERIAL, 1, 5000)], [(SERIAL, 1, 1200), (NUMERIC, 8, 16)], {}, 6200),
    ("fse", [(NUMERIC, 1, 5000)], [(SERIAL, 1, 900), (NUMERIC, 4, 40)], {}, 5900),
])
def test_node_bytes(codec, ins, outs, header, want):
    assert work.node_bytes(rec(codec, ins, outs, **header)) == want


@pytest.mark.parametrize("r,device", [
    (rec("delta", [(NUMERIC, 4, 8)], []), True),
    (rec("delta", [(NUMERIC, 8, 8)], []), False),
    (rec("bitpack", [(NUMERIC, 4, 8)], [], bits=8), True),
    (rec("bitpack", [(NUMERIC, 4, 8)], [], bits=3), False),
    (rec("transpose", [(STRUCT, 3, 9)], []), True),
    (rec("transpose", [(SERIAL, 1, 9)], []), False),
    (rec("float_split", [(NUMERIC, 4, 8)], [], fmt=2), True),
    (rec("float_split", [(NUMERIC, 2, 8)], [], fmt=0), False),
    (rec("huffman", [(SERIAL, 1, 1024)], []), True),
    (rec("huffman", [(SERIAL, 1, 1023)], []), False),
    (rec("fse", [(NUMERIC, 2, 4096)], []), False),
    (rec("range_pack", [(NUMERIC, 4, 4096)], []), False),
    (rec("zlib_backend", [(SERIAL, 1, 4096)], []), False),
])
def test_on_device_follows_the_twins_routing(r, device):
    assert work.on_device(r) is device


def test_group_work_counts_only_device_nodes_of_the_group():
    recs = [
        rec("delta", [(NUMERIC, 4, 400)], [(NUMERIC, 4, 400)]),
        rec("delta", [(NUMERIC, 8, 800)], [(NUMERIC, 8, 800)]),
        rec("huffman", [(SERIAL, 1, 5000)], [(SERIAL, 1, 1000), (NUMERIC, 8, 16)]),
        rec("zlib_backend", [(SERIAL, 1, 5000)], [(SERIAL, 1, 10)]),
    ]
    assert work.group_work(recs, "numeric") == {"delta": (1, 800)}
    assert work.group_work(recs, "entropy") == {"huffman": (1, 6000)}


def test_work_from_a_real_frame():
    from repro.codecs.profiles import float32_profile
    from repro.core import compress, numeric

    n = 50_000
    x = (np.random.default_rng(2**31 + 3).standard_normal(n) * 0.02).astype(np.float32)
    frame = compress(float32_profile(), numeric(x.view(np.uint32)), backend="device")
    _, records = R.mismatched_bytes(frame, x.view(np.uint32))
    split = [r for r in records if r.codec == "float_split"]
    assert len(split) == 1 and work.on_device(split[0])
    assert work.node_bytes(split[0]) == 4 * n + (-(-n // 8) + n + 4 * n)


def test_roofline_share_arithmetic():
    assert work.roofline_share(819_000_000_000, 1.0, 819e9) == pytest.approx(100.0)
    assert work.roofline_share(819_000_000, 0.01, 819e9) == pytest.approx(10.0)
    assert work.roofline_share(0, 1.0, 819e9) is None
    assert work.roofline_share(10, 0.0, 819e9) is None


def test_peaks_table_names_the_v5e_and_refuses_others():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
