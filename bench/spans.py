"""The program's own spans in a window's trace, and where the device's idle
time went among them.

The program opens profiler spans named ``ozl.*`` in the layer where each
piece of work happens (``repro.device.span``): ``ozl.resolve``,
``ozl.encode.<backend>.<codec>``, ``ozl.h2d``/``ozl.d2h`` and
``ozl.wire.write_frame``.  They are host events of the same ``.xplane.pb``
that ``bench/trace.py`` reduces, on the clock of the device planes.  This
module reads them, with the same window (the longest ``bench.window``) and
the same device busy time, and gives:

* ``idle_by_program``: each idle instant of the window on each device goes
  to the innermost ``ozl.`` span covering it on each host thread (the
  latest-starting one); where spans of several threads cover it, the
  instant is split evenly between them.  An instant no ``ozl.`` span covers
  goes by the same rule to the benchmark's own ``bench.*`` spans, and to
  ``unannotated`` after that.  Averaged over devices, it sums to the window
  minus the busy time.
* ``spans``: every ``ozl.`` span inside the window, per thread, for the
  unions the per-layer metrics read.

A run whose window holds no ``ozl.`` span (a program without spans) reads
as None in ``for_run``, so its metrics are left out of the result.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import trace

PROGRAM = "ozl."
BENCH = "bench."

# one thread's spans cut into disjoint sorted pieces: (starts, ends, names)
Pieces = Tuple[np.ndarray, np.ndarray, List[str]]


@dataclass
class Programs:
    window_s: float
    busy_s: float  # averaged over devices, as bench/trace.py computes it
    idle_by_program: Dict[str, float] = field(default_factory=dict)  # averaged over devices
    # span name -> one (n, 2) array of intervals (ns, clipped to the window) per thread
    spans: Dict[str, List[np.ndarray]] = field(default_factory=dict)

    def union_s(self, *names: str) -> float:
        """Seconds of the window inside any span of these names, on any thread."""
        iv = [a for n in names for a in self.spans.get(n, [])]
        if not iv:
            return 0.0
        u = trace.union(np.concatenate(iv))
        return float((u[:, 1] - u[:, 0]).sum()) * 1e-9

    def thread_s(self, name: str) -> float:
        """Seconds inside spans of ``name``, each thread's union summed over
        threads (nested spans of one thread count once)."""
        per = [trace.union(iv) for iv in self.spans.get(name, [])]
        return sum(float((u[:, 1] - u[:, 0]).sum()) for u in per) * 1e-9

    def idle_share(self, prefix: str) -> float:
        """Share of the window the device idled under spans named ``prefix*``."""
        idle = sum(v for k, v in self.idle_by_program.items() if k.startswith(prefix))
        return idle / self.window_s


def innermost(iv: np.ndarray, names: Sequence[str]) -> Pieces:
    """One thread's spans -> disjoint sorted pieces, each named after the
    innermost span over it: the latest-starting one (of two starting
    together, the one that ends first)."""
    events = sorted([(a, 1, i) for i, (a, _) in enumerate(iv.tolist())]
                    + [(b, 0, i) for i, (_, b) in enumerate(iv.tolist())])
    active: List[tuple] = []  # (start, -end, i), ascending: the innermost last
    starts, ends, labels = [], [], []
    for k, (t, is_start, i) in enumerate(events):
        key = (iv[i, 0], -iv[i, 1], i)
        if is_start:
            bisect.insort(active, key)
        else:
            active.remove(key)
        nxt = events[k + 1][0] if k + 1 < len(events) else t
        if active and nxt > t:
            name = names[active[-1][2]]
            if labels and labels[-1] == name and ends[-1] == t:
                ends[-1] = nxt
            else:
                starts.append(t)
                ends.append(nxt)
                labels.append(name)
    return np.array(starts, float), np.array(ends, float), labels


def attribute(holes: np.ndarray, levels: Sequence[Sequence[Pieces]]) -> Dict[str, float]:
    """Lengths of the disjoint sorted ``holes`` by the name over them: the
    threads of the first level that cover an instant share it evenly, an
    instant none of them covers goes to the next level, and one no level
    covers to ``unannotated``."""
    if not len(holes):
        return {}
    cuts = [holes.reshape(-1)] + [np.r_[s, e] for lvl in levels for s, e, _ in lvl]
    edges = np.unique(np.concatenate(cuts))
    lo, hi = edges[:-1], edges[1:]
    mid = (lo + hi) / 2
    k = np.searchsorted(holes[:, 0], mid, side="right") - 1
    idle = (k >= 0) & (mid < holes[np.maximum(k, 0), 1])
    mid, length = mid[idle], (hi - lo)[idle]
    out: Dict[str, float] = {}
    left = np.ones(len(mid), bool)
    for threads in levels:
        hits = []
        for starts, ends, names in threads:
            j = np.searchsorted(starts, mid, side="right") - 1
            cov = left & (j >= 0) & (mid < ends[np.maximum(j, 0)])
            hits.append((cov, j, names))
        n = sum((cov.astype(int) for cov, _, _ in hits), np.zeros(len(mid), int))
        for cov, j, names in hits:
            for idx, s in zip(j[cov].tolist(), (length[cov] / n[cov]).tolist()):
                out[names[idx]] = out.get(names[idx], 0.0) + s
        left &= n == 0
    rest = float(length[left].sum())
    if rest:
        out[trace.UNANNOTATED] = out.get(trace.UNANNOTATED, 0.0) + rest
    return out


def _host_spans(planes) -> List[Dict[str, list]]:
    """Per host thread (line), the ``ozl.`` and ``bench.`` spans by name."""
    threads = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found: Dict[str, list] = {}
            for e in line.events:
                if e.name.startswith((PROGRAM, BENCH)):
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
            if found:
                threads.append(found)
    return threads


def _level(threads, prefix: str, lo: float, hi: float, skip=()) -> List[Pieces]:
    out = []
    for found in threads:
        ivs, names = [], []
        for name, spans in found.items():
            if name.startswith(prefix) and name not in skip:
                iv = trace.clip(np.array(spans, float), lo, hi)
                ivs.append(iv)
                names += [name] * len(iv)
        if names:
            out.append(innermost(np.concatenate(ivs), names))
    return out


def reduce(path) -> Programs:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(path)).planes)
    threads = _host_spans(planes)
    windows = [s for t in threads for s in t.get(trace.WINDOW, [])]
    if not windows:
        raise ValueError(f"{path}: no {trace.WINDOW} span in the trace")
    lo, hi = max(windows, key=lambda s: s[1] - s[0])
    levels = [_level(threads, PROGRAM, lo, hi),
              _level(threads, BENCH, lo, hi, skip=(trace.WINDOW,))]
    devices = [p for p in planes if p.name.startswith(trace.DEVICE_PREFIX)
               and p.name[len(trace.DEVICE_PREFIX):].isdigit()]
    if not devices:
        raise ValueError(f"{path}: no {trace.DEVICE_PREFIX}n plane")
    busy_total, idle = 0.0, {}
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        ops = [trace._events(lines[n]) for n in trace.BUSY_LINES if n in lines]
        busy = trace.union(trace.clip(np.concatenate(ops) if ops else np.zeros((0, 2)), lo, hi))
        busy_total += float((busy[:, 1] - busy[:, 0]).sum())
        for name, ns in attribute(trace.gaps(busy, lo, hi), levels).items():
            idle[name] = idle.get(name, 0.0) + ns * 1e-9 / len(devices)
    spans: Dict[str, List[np.ndarray]] = {}
    for found in threads:
        for name, iv in found.items():
            if name.startswith(PROGRAM):
                clipped = trace.clip(np.array(iv, float), lo, hi)
                if len(clipped):
                    spans.setdefault(name, []).append(clipped)
    return Programs((hi - lo) * 1e-9, busy_total * 1e-9 / len(devices), idle, spans)


_reduced: Dict[Path, Programs] = {}


def for_run(run) -> Optional[Programs]:
    """The program spans of a traced run's window (reduced once per trace
    file; the first reduction prints the idle breakdown), or None for an
    untraced run or a program without spans."""
    if run.trace is None:
        return None
    from bench.harness import TRACE_DIR

    found = sorted(TRACE_DIR.rglob("*.xplane.pb"))
    if not found:
        return None
    path = found[-1]
    if path not in _reduced:
        _reduced[path] = p = reduce(path)
        gaps = sorted(p.idle_by_program.items(), key=lambda kv: -kv[1])
        print("idle by program span: " + ", ".join(f"{k} {v:.9f} s" for k, v in gaps))
    p = _reduced[path]
    return p if p.spans else None
