"""Share of the traced window spent assembling frames: the union of the
program's ``ozl.wire.write_frame`` spans inside the window, over the window
(profiler trace)."""
from bench.spans import for_run


def read(run):
    p = for_run(run)
    return None if p is None else 100.0 * p.union_s("ozl.wire.write_frame") / p.window_s
