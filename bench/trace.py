"""From a profiler trace (``.xplane.pb``) to the device numbers of a window.

The window is the benchmark's own host span ``bench.window``.  Inside it:

* busy time: the union of the intervals in which an operation ran on a
  device (lines ``XLA Ops`` and ``Async XLA Ops`` of each ``/device:TPU:n``
  plane), averaged over the devices;
* device time per compiled program: the ``XLA Modules`` line, by module name
  without its fingerprint (``jit_delta_encode(1234)`` -> ``jit_delta_encode``);
* idle gaps: the rest of the window on each device, each gap attributed to
  the benchmark host span (``bench.compress``, ``bench.request``, ...) that
  covers most of it, or ``unannotated``; summed by that name.

Host and device events share one clock in the trace.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
BUSY_LINES = ("XLA Ops", "Async XLA Ops")
MODULE_LINE = "XLA Modules"
UNANNOTATED = "unannotated"


@dataclass
class Reduced:
    window_s: float
    busy_s: float  # averaged over devices
    devices: int
    module_s: Dict[str, float] = field(default_factory=dict)  # summed over devices
    idle_by_span: Dict[str, float] = field(default_factory=dict)  # averaged over devices

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def union(iv: np.ndarray) -> np.ndarray:
    """Disjoint sorted intervals covering the (n, 2) intervals ``iv``."""
    if iv.size == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.flatnonzero(np.r_[new[1:], True])
    return np.stack([starts, ends[last]], axis=1)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if iv.size == 0:
        return iv.reshape(0, 2)
    out = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], axis=1)
    return out[out[:, 1] > out[:, 0]]


def gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The parts of [lo, hi] that the disjoint sorted ``busy`` leaves out."""
    edges = np.concatenate([[lo], busy.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def covered(cover: np.ndarray, iv: np.ndarray) -> np.ndarray:
    """For each interval of ``iv``, how much of it the disjoint sorted
    intervals ``cover`` cover."""
    if cover.size == 0 or iv.size == 0:
        return np.zeros(len(iv))
    cum = np.concatenate([[0.0], np.cumsum(cover[:, 1] - cover[:, 0])])

    def upto(t):
        j = np.searchsorted(cover[:, 0], t, side="right")  # intervals starting <= t
        inside = np.where(j > 0, np.minimum(t, cover[np.maximum(j - 1, 0), 1])
                          - cover[np.maximum(j - 1, 0), 0], 0.0)
        return cum[np.maximum(j - 1, 0)] * (j > 0) + np.maximum(inside, 0.0)

    return upto(iv[:, 1]) - upto(iv[:, 0])


def _events(line) -> np.ndarray:
    ev = [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
    return np.array(ev, float).reshape(-1, 2)


def reduce(path: str) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = list(pd.planes)
    spans: Dict[str, List[Tuple[float, float]]] = {}
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    if WINDOW not in spans:
        raise ValueError(f"{path}: no {WINDOW} span in the trace")
    lo, hi = max(spans.pop(WINDOW), key=lambda s: s[1] - s[0])
    covers = {k: union(np.array(v, float)) for k, v in spans.items()}
    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)
               and p.name[len(DEVICE_PREFIX):].isdigit()]
    if not devices:
        raise ValueError(f"{path}: no {DEVICE_PREFIX}n plane")
    busy_total = 0.0
    module_s: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        ops = [_events(lines[n]) for n in BUSY_LINES if n in lines]
        busy = union(clip(np.concatenate(ops) if ops else np.zeros((0, 2)), lo, hi))
        busy_total += float((busy[:, 1] - busy[:, 0]).sum())
        if MODULE_LINE in lines:
            for e in lines[MODULE_LINE].events:
                a, b = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
                if b > a:
                    name = e.name.split("(", 1)[0]
                    module_s[name] = module_s.get(name, 0.0) + (b - a) * 1e-9
        holes = gaps(busy, lo, hi)
        if len(holes):
            names = sorted(covers)
            over = np.stack([covered(covers[n], holes) for n in names], axis=1) \
                if names else np.zeros((len(holes), 0))
            best = over.argmax(axis=1) if names else np.zeros(len(holes), int)
            has = over.max(axis=1) > 0 if names else np.zeros(len(holes), bool)
            length = holes[:, 1] - holes[:, 0]
            for j, n in enumerate(names):
                s = float(length[has & (best == j)].sum())
                if s:
                    idle[n] = idle.get(n, 0.0) + s * 1e-9 / len(devices)
            rest = float(length[~has].sum())
            if rest:
                idle[UNANNOTATED] = idle.get(UNANNOTATED, 0.0) + rest * 1e-9 / len(devices)
    return Reduced((hi - lo) * 1e-9, busy_total * 1e-9 / len(devices), len(devices),
                   module_s, idle)
