"""The attribution of device idle time to the program's own spans
(bench/spans.py): made-up intervals for each rule, and the recorded v5e trace
of a program without spans, which the new readers must read as nothing."""
import shutil
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import spans, trace
from bench.common import load_module

CHIP_TRACE = Path(__file__).resolve().parent / "data" / "v5e_compress_window.xplane.pb"
READERS = ("host_encode_idle_share.bulk", "transfer_share.bulk", "wire_share.bulk",
           "resolve_s_in_setup.bulk")


def iv(*pairs):
    return np.array(pairs, float).reshape(-1, 2)


def thread(*named):
    """One thread's spans given as (name, start, end)."""
    return spans.innermost(iv(*[(a, b) for _, a, b in named]), [n for n, _, _ in named])


def test_innermost_cuts_nested_spans_into_named_pieces():
    starts, ends, names = thread(("ozl.encode.device.transpose", 0, 100),
                                 ("ozl.h2d", 10, 20), ("ozl.d2h", 60, 90))
    assert names == ["ozl.encode.device.transpose", "ozl.h2d", "ozl.encode.device.transpose",
                     "ozl.d2h", "ozl.encode.device.transpose"]
    np.testing.assert_array_equal(starts, [0, 10, 20, 60, 90])
    np.testing.assert_array_equal(ends, [10, 20, 60, 90, 100])


def test_innermost_of_spans_starting_together_is_the_one_ending_first():
    starts, ends, names = thread(("ozl.resolve", 0, 50), ("ozl.encode.host.zlib", 0, 20))
    assert names == ["ozl.encode.host.zlib", "ozl.resolve"]
    np.testing.assert_array_equal(ends, [20, 50])


def test_nested_spans_on_one_thread_take_the_innermost():
    t = thread(("ozl.encode.device.transpose", 0, 100), ("ozl.d2h", 60, 90))
    got = spans.attribute(iv((0, 100)), [[t]])
    assert got == pytest.approx({"ozl.encode.device.transpose": 70.0, "ozl.d2h": 30.0})


def test_two_threads_covering_one_instant_split_it_evenly():
    a = thread(("ozl.encode.host.zlib", 0, 10))
    b = thread(("ozl.wire.write_frame", 5, 15))
    got = spans.attribute(iv((0, 20)), [[a, b]])
    assert got == pytest.approx({"ozl.encode.host.zlib": 7.5, "ozl.wire.write_frame": 7.5,
                                 trace.UNANNOTATED: 5.0})


def test_uncovered_idle_falls_to_the_bench_span_then_unannotated():
    program = thread(("ozl.encode.host.range_pack", 10, 20))
    bench = thread(("bench.compress", 0, 30))
    got = spans.attribute(iv((0, 40)), [[program], [bench]])
    assert got == pytest.approx({"ozl.encode.host.range_pack": 10.0, "bench.compress": 20.0,
                                 trace.UNANNOTATED: 10.0})


def test_only_idle_time_is_attributed():
    program = thread(("ozl.encode.host.zlib", 0, 100))
    holes = trace.gaps(iv((20, 30), (50, 80)), 0, 100)
    got = spans.attribute(holes, [[program]])
    assert got == pytest.approx({"ozl.encode.host.zlib": 60.0})
    assert spans.attribute(iv(), [[program]]) == {}


def test_idle_by_program_sums_to_the_window_minus_busy_time():
    rng = np.random.default_rng(7)
    lo, hi = 0.0, 1000.0
    busy = trace.union(np.sort(rng.uniform(lo, hi, (40, 2)), axis=1))
    holes = trace.gaps(busy, lo, hi)
    threads = []
    for _ in range(3):
        outer = np.sort(rng.uniform(lo, hi, (6, 2)), axis=1)
        inner = np.stack([outer[:, 0] + 1, outer[:, 0] + 1 + (outer[:, 1] - outer[:, 0]) / 3], 1)
        names = [f"ozl.encode.host.c{k}" for k in range(6)] + ["ozl.d2h"] * 6
        threads.append(spans.innermost(np.concatenate([outer, inner]), names))
    bench = [thread(("bench.compress", 100, 900))]
    got = spans.attribute(holes, [threads, bench])
    idle = hi - lo - float((busy[:, 1] - busy[:, 0]).sum())
    assert sum(got.values()) == pytest.approx(idle, rel=1e-12)
    assert all(v > 0 for v in got.values())


@pytest.fixture(scope="module")
def chip():
    return spans.reduce(CHIP_TRACE), trace.reduce(CHIP_TRACE)


def test_chip_trace_without_program_spans_falls_back_to_bench_spans(chip):
    prog, red = chip
    assert prog.spans == {}
    assert prog.window_s == pytest.approx(red.window_s, rel=1e-12)
    assert prog.busy_s == pytest.approx(red.busy_s, rel=1e-12)
    assert sum(prog.idle_by_program.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    assert set(prog.idle_by_program) <= set(red.idle_by_span) | {trace.UNANNOTATED}
    # instant by instant, the loop's own work between calls is no call's:
    # it stays unannotated, where the whole-gap rule gives it to the call
    assert set(prog.idle_by_program) == {"bench.compress", trace.UNANNOTATED}
    assert prog.idle_by_program["bench.compress"] > 0.8 * (red.window_s - red.busy_s)
    assert prog.idle_by_program["bench.compress"] <= red.idle_by_span["bench.compress"]


@pytest.mark.parametrize("name", READERS)
def test_new_readers_read_nothing_from_a_program_without_spans(name, tmp_path, monkeypatch):
    from bench import harness

    dest = tmp_path / "trace" / "plugins" / "profile" / "run"
    dest.mkdir(parents=True)
    shutil.copy(CHIP_TRACE, dest / CHIP_TRACE.name)
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    assert load_module("metrics", name).read(SimpleNamespace(trace=object())) is None
    assert load_module("metrics", name).read(SimpleNamespace(trace=None)) is None
