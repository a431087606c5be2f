"""Arithmetic the metric readers share: percentiles of request times, shares
of session counters, and kernel roofline shares from the trace."""
from __future__ import annotations

import math
from typing import List, Optional

from bench import work
from bench.common import MIB


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with q% at or below it."""
    vals = sorted(values)
    return vals[max(0, math.ceil(q / 100.0 * len(vals)) - 1)]


def request_ms(run) -> List[float]:
    """Each request's time from when it was due to its answer.  A request
    that failed or was never answered counts as never answered in time: its
    time runs to the end of the grace period after the window."""
    cutoff = run.t_end + float(run.mix.get("grace_s", 0.0))
    return [((c.end if c.error is None else cutoff) - c.due) * 1e3 for c in run.calls]


def compress_mibps(run) -> Optional[float]:
    done = sum(c.nbytes for c in run.calls if c.error is None)
    return done / MIB / run.window_s if done else None


def session_share(run, num: str, other: str) -> Optional[float]:
    """num / (num + other) of two session counters over the window, in %."""
    d = run.in_window
    if not d or d[num] + d[other] == 0:
        return None
    return 100.0 * d[num] / (d[num] + d[other])


def host_node_share(run) -> Optional[float]:
    d = run.in_window
    if not d:
        return None
    total = sum(n for per in d["nodes"].values() for n in per.values())
    if not total:
        return None
    return 100.0 * sum(d["nodes"].get("host", {}).values()) / total


def compile_s(run) -> float:
    return float(sum(run.compiles.within(run.t0, run.t_end)))


def idle_share(run) -> Optional[float]:
    return None if run.trace is None else 100.0 * run.trace.idle_share()


def roofline(run, group: str) -> Optional[float]:
    """Kernel group's least time (work bytes / peak HBM bandwidth) over its
    device time in the trace, in %.  Nothing is read when the group did not
    run, or when the nodes the routing rules place on the device disagree
    with the device nodes the sessions counted (the work would be wrong)."""
    if run.trace is None or run.records is None:
        return None
    codecs, modules = work.GROUPS[group]
    total, counted = 0, {}
    for recs, n_calls in run.records:
        for codec, (n, b) in work.group_work(recs, group).items():
            total += b * n_calls
            counted[codec] = counted.get(codec, 0) + n * n_calls
    if run.in_window is not None:
        seen = run.in_window["nodes"].get("device", {})
        if any(seen.get(c, 0) != counted.get(c, 0) for c in codecs):
            return None
    device_s = sum(run.trace.module_s.get(m, 0.0) for m in modules)
    return work.roofline_share(total, device_s, run.peaks["hbm_bytes_per_s"])
