"""The end-to-end arithmetic: nearest-rank percentiles, request times from
when each request was due, and the window's rate."""
from types import SimpleNamespace

import pytest

from bench import load, measure
from bench.common import MIB, load_module


def call(due, end, nbytes=MIB, error=None, out=MIB // 4):
    return load.Call(0, nbytes, due, due, end, out, 0, error)


@pytest.mark.parametrize("q,want", [(50, 5), (95, 10), (100, 10), (1, 1), (10, 1), (11, 2)])
def test_nearest_rank_percentile(q, want):
    assert measure.percentile(list(range(10, 0, -1)), q) == want


def test_requests_are_timed_from_when_they_were_due_and_failures_run_to_the_grace():
    run = SimpleNamespace(t_end=10.0, mix={"grace_s": 60.0}, calls=[
        call(1.0, 1.25), call(2.0, 2.5), call(3.0, 3.0, error="shed"),
        call(4.0, 4.0, error=load.NO_ANSWER),
    ])
    assert measure.request_ms(run) == pytest.approx([250.0, 500.0, 67_000.0, 66_000.0])
    p95 = load_module("metrics", "request_p95_ms").read(run)
    p50 = load_module("metrics", "request_p50_ms.serve").read(run)
    assert p95 == pytest.approx(67_000.0) and p50 == pytest.approx(500.0)


def test_rate_counts_completed_calls_over_the_whole_window():
    run = SimpleNamespace(window_s=4.0, calls=[call(0, 1), call(1, 2), call(2, 4, error="x")])
    assert measure.compress_mibps(run) == pytest.approx(0.5)
    run.calls = [call(0, 1, error="x")]
    assert measure.compress_mibps(run) is None


def test_open_loop_ratio_is_over_answered_requests():
    run = SimpleNamespace(mix={"loop": "open"}, calls=[
        call(0, 1, out=MIB // 4), call(0, 1, out=MIB // 2), call(0, 1, error="shed", out=0),
    ])
    assert load_module("metrics", "ratio").read(run) == pytest.approx(2 * MIB / (3 * MIB / 4))
