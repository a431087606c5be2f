"""Share of the traced window in which the device idled while the host ran a
host encoder: idle time whose innermost program span is
``ozl.encode.host.<codec>``, over the window (profiler trace)."""
from bench.spans import for_run


def read(run):
    p = for_run(run)
    return None if p is None else 100.0 * p.idle_share("ozl.encode.host.")
