"""95th percentile (nearest rank) of every request of the window, each timed
from when it was due to be sent (client clock)."""
from bench.measure import percentile, request_ms


def read(run):
    return percentile(request_ms(run), 95) if run.calls else None
