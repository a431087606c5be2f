"""The trace reduction: interval arithmetic on made-up intervals, and the
whole reduction on a small trace recorded on a TPU v5e (bench/tests/data),
against a plain recount of its events."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"
CHIP_TRACE = DATA / "v5e_compress_window.xplane.pb"  # a traced window of closed-loop compress calls


def iv(*pairs):
    return np.array(pairs, float).reshape(-1, 2)


def test_union_merges_overlaps_and_touching_intervals():
    u = trace.union(iv((5, 7), (0, 2), (1, 3), (3, 4), (10, 11), (6, 9)))
    np.testing.assert_array_equal(u, iv((0, 4), (5, 9), (10, 11)))
    assert trace.union(iv()).shape == (0, 2)


def test_clip_and_gaps():
    busy = trace.clip(trace.union(iv((-5, 1), (2, 3), (9, 20))), 0, 10)
    np.testing.assert_array_equal(busy, iv((0, 1), (2, 3), (9, 10)))
    np.testing.assert_array_equal(trace.gaps(busy, 0, 10), iv((1, 2), (3, 9)))
    np.testing.assert_array_equal(trace.gaps(iv(), 0, 10), iv((0, 10)))


def test_covered_length_of_each_interval():
    cover = iv((0, 2), (4, 6), (8, 12))
    got = trace.covered(cover, iv((1, 5), (6, 8), (0, 20), (11, 11.5)))
    np.testing.assert_allclose(got, [2.0, 0.0, 8.0, 0.5])


def _plain_recount(path):
    """Busy union, module time and window by walking the events in Python."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "bench.window":
                        window = (e.start_ns, e.start_ns + e.duration_ns)
    lo, hi = window
    plane = pd.find_plane_with_name("/device:TPU:0")
    spans, modules = [], {}
    for line in plane.lines:
        for e in line.events:
            a, b = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
            if b <= a:
                continue
            if line.name in ("XLA Ops", "Async XLA Ops"):
                spans.append((a, b))
            elif line.name == "XLA Modules":
                name = e.name.split("(")[0]
                modules[name] = modules.get(name, 0.0) + (b - a) * 1e-9
    busy, end = 0.0, -np.inf
    for a, b in sorted(spans):
        if a > end:
            busy += b - a
            end = b
        elif b > end:
            busy += b - end
            end = b
    return (hi - lo) * 1e-9, busy * 1e-9, modules


@pytest.fixture(scope="module")
def chip():
    if not CHIP_TRACE.exists():
        pytest.fail(f"missing recorded trace {CHIP_TRACE}")
    return trace.reduce(CHIP_TRACE), _plain_recount(CHIP_TRACE)


def test_chip_trace_busy_time_and_idle_share(chip):
    red, (window_s, busy_s, _) = chip
    assert red.devices == 1
    assert red.window_s == pytest.approx(window_s, rel=1e-12)
    assert red.busy_s == pytest.approx(busy_s, rel=1e-9)
    assert 0.0 < red.busy_s < red.window_s
    assert red.idle_share() == pytest.approx(1 - busy_s / window_s)


def test_chip_trace_time_per_module(chip):
    red, (_, _, modules) = chip
    assert set(red.module_s) == set(modules)
    for name, s in modules.items():
        assert red.module_s[name] == pytest.approx(s, rel=1e-9)
    assert all(name.startswith("jit_") for name in red.module_s)


def test_chip_trace_idle_gaps_are_attributed_to_host_spans(chip):
    red, (window_s, busy_s, _) = chip
    assert sum(red.idle_by_span.values()) == pytest.approx(window_s - busy_s, rel=1e-6)
    assert set(red.idle_by_span) <= {"bench.compress", "bench.request", "bench.response",
                                     "bench.wait", trace.UNANNOTATED}
    # the closed loop keeps a compress call open for nearly the whole window
    assert red.idle_by_span.get("bench.compress", 0.0) > 0.9 * (window_s - busy_s)
