"""Share of the traced window spent in the device twins' host<->device
copies: the union of the program's ``ozl.h2d`` and ``ozl.d2h`` spans inside
the window, over the window (profiler trace)."""
from bench.spans import for_run


def read(run):
    p = for_run(run)
    return None if p is None else 100.0 * p.union_s("ozl.h2d", "ozl.d2h") / p.window_s
