"""Seconds of set-up spent resolving plans: the program's counter
``resolve_cache_info()["miss_s"]`` at the window's start, less its value at
process start (0: the resolve cache lives in the process).  Read after the
window, as the counter less the resolve time the window's ``ozl.resolve``
spans hold (none in a warmed closed loop)."""
from bench.spans import for_run


def read(run):
    from repro.core.engine import resolve_cache_info

    p = for_run(run)
    miss_s = resolve_cache_info().get("miss_s")
    if p is None or miss_s is None:
        return None
    return miss_s - p.thread_s("ozl.resolve")
