"""Median (nearest rank) of every request of the window, timed from when it
was due (client clock)."""
from bench.measure import percentile, request_ms


def read(run):
    return percentile(request_ms(run), 50) if run.calls else None
