"""One cell, from set-up to its result line.

``run_cell`` builds the cell from ``BENCHMARK.json``: the configuration's
items (``bench/configs/<config>.py``), the traffic mix
(``bench/traffic/<mix>.json``, driven by ``bench/load.py``) and one reader
per metric (``bench/metrics/<metric>.py``).  It measures one window, frees
the program's state, checks every frame the window produced against the
plain reference (``bench/reference.py``) and returns the result object that
``bench/run.py`` prints.  It never looks for a chip itself: ``run.py`` does
that before calling it, and the tests call it on the CPU at small sizes.
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

from bench import load, reference, work
from bench.common import ROOT, load_json, load_module, manifest

TRACE_DIR = ROOT / ".bench_out" / "trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COUNTERS = ("calls", "chunks", "bytes_in", "bytes_out", "prefetch_hits",
          "prefetch_misses", "draw_wait_s", "encode_wait_s")


class Compiles:
    """Every backend compile this process runs, with when it finished."""

    def __init__(self):
        self.events: List[tuple] = []

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), float(duration)))

    def within(self, lo: float, hi: float) -> List[float]:
        return [d for t, d in self.events if lo <= t <= hi]


class Window:
    """The measured window: its clock, its trace, and snapshots around it."""

    def __init__(self, trace_dir: Optional[Path], snapshot: Optional[Callable] = None):
        self.trace_dir = trace_dir
        self.snapshot = snapshot
        self.t0 = self.t_end = 0.0
        self.before = self.after = None

    def __enter__(self):
        import jax

        if self.snapshot is not None:
            self.before = self.snapshot()
        if self.trace_dir is not None:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.trace_dir.mkdir(parents=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        import jax

        self.t_end = time.perf_counter()
        self._span.__exit__(None, None, None)
        if self.trace_dir is not None:
            jax.profiler.stop_trace()
        if self.snapshot is not None:
            self.after = self.snapshot()


def merged_stats(sessions) -> dict:
    """Session counters summed over sessions, nodes by backend and codec."""
    out = {k: 0 for k in COUNTERS}
    nodes: Dict[str, Dict[str, int]] = {}
    for s in sessions:
        with s._stats_lock:
            for k in COUNTERS:
                out[k] += s.stats[k]
            for by, per in s.stats["nodes"].items():
                for codec, n in per.items():
                    nodes.setdefault(by, {})
                    nodes[by][codec] = nodes[by].get(codec, 0) + n
    out["nodes"] = nodes
    return out


def stats_delta(before: dict, after: dict) -> dict:
    out = {k: after[k] - before[k] for k in COUNTERS}
    out["nodes"] = {
        by: {c: n - before["nodes"].get(by, {}).get(c, 0) for c, n in per.items()}
        for by, per in after["nodes"].items()
    }
    return out


def metric_names(man: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics this cell reports: end-to-end ones without a trace,
    per-layer ones with it."""
    e2e = man["end_to_end"]
    reported = {m["name"] for m in e2e if cell in m.get("workloads", [cell])}
    if not trace:
        return [m for m in e2e if m["name"] in reported]
    return [m for m in man["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in reported)]


def read_metric(name: str, run) -> Optional[float]:
    return load_module("metrics", name).read(run)


def _device() -> dict:
    import jax

    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def check_frames(frames: load.Frames, inputs, keep_records: bool):
    """Every distinct frame the window produced, through the reference, on a
    few threads (numpy releases the interpreter lock in the big steps).

    -> (checks, records): counts of frames that did not give their input
    back and of bytes that differ, and the node records weighted by how
    many window calls returned each frame."""
    from concurrent.futures import ThreadPoolExecutor

    def one(job):
        (key, fid), n_calls = job
        want = inputs[key].data
        try:
            diff, recs = reference.mismatched_bytes(frames.by_key[key][fid], want)
        except reference.RefError as err:
            print(f"check: {inputs[key].name}: {err}", file=sys.stderr)
            diff, recs = int(want.nbytes), []
        return diff, (recs, n_calls)

    jobs = sorted(frames.in_window.items())
    with ThreadPoolExecutor(max(1, min(8, len(os.sched_getaffinity(0))))) as pool:
        results = list(pool.map(one, jobs))
    diffs = [d for d, _ in results]
    checks = {"checked_frames": len(jobs), "bad_frames": sum(d > 0 for d in diffs),
              "bad_bytes": sum(diffs)}
    return checks, [r for _, r in results] if keep_records else []


def run_cell(wl: dict, seed: int, seconds: float, trace: bool, t_start: float, *,
             cfg: Optional[dict] = None, mix: Optional[dict] = None,
             degrade: Optional[Callable] = None) -> dict:
    """Set up, measure and check one cell (its ``workloads`` entry) -> the
    result object.

    ``degrade`` (the control, ``bench/control.py``) replaces what the
    program is given with a lower-precision copy; the check still compares
    with the exact data, so a sound check must then come out incorrect."""
    import jax

    from repro.codecs.profiles import resolve_profile_spec
    from repro.core import CompressorSession, numeric

    man = manifest()
    cell = wl["name"]
    cfg = cfg or load_json("configs", wl["config"])
    mix = mix or load_json("traffic", wl["traffic"])
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    sessions: Dict[str, object] = {}
    extra: dict = {}
    try:
        items = load_module("configs", wl["config"]).items(cfg, seed)
        print(f"set-up: {len(items)} items, {sum(i.nbytes for i in items)} B generated"
              f" in {time.perf_counter() - t_start:.3f} s")
        if mix["loop"] == "closed":
            for it in items:
                if it.plan not in sessions:
                    plan = dataclasses.replace(resolve_profile_spec(it.profile), name=it.plan)
                    sessions[it.plan] = CompressorSession(plan, backend="device")

            sent = {id(it): degrade(it.data) if degrade else it.data for it in items}

            def compress(it):
                return sessions[it.plan].compress(numeric(sent[id(it)]), chunk_bytes=it.chunk_bytes)

            window = Window(TRACE_DIR if trace else None,
                            lambda: merged_stats(sessions.values()))
            calls, frames = load.closed(mix, items, compress, seconds, window)
            inputs = items
        else:
            window = Window(TRACE_DIR if trace else None)
            calls, frames, inputs = load.open_loop(
                mix, items, resolve_profile_spec, seconds, seed, window, extra, degrade)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
        for s in sessions.values():
            s.close()
    device = _device()
    in_window = stats_delta(window.before, window.after) if window.before else None
    sessions.clear()

    reduced = None
    if trace:
        from bench import trace as trace_mod

        found = sorted(TRACE_DIR.rglob("*.xplane.pb"))
        t_reduce = time.perf_counter()
        reduced = trace_mod.reduce(found[-1])
        print(f"trace: {found[-1].stat().st_size} B reduced in"
              f" {time.perf_counter() - t_reduce:.3f} s")
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s

    t_check = time.perf_counter()
    checks, records = check_frames(frames, inputs, keep_records=trace)
    print(f"reference check: {checks['checked_frames']} frames of"
          f" {sum(inputs[k].nbytes for k, _ in frames.in_window)} B"
          f" in {time.perf_counter() - t_check:.3f} s")
    unanswered = sum(c.error == load.NO_ANSWER for c in calls)
    if mix["loop"] == "open":
        checks["unanswered"] = unanswered
    correct = checks["checked_frames"] > 0 and all(
        v == 0 for k, v in checks.items() if k != "checked_frames")

    run = SimpleNamespace(
        cell=cell, mix=mix, seconds=seconds, t0=window.t0, t_end=window.t_end,
        setup_s=window.t0 - t_start, window_s=window.t_end - window.t0,
        calls=calls, inputs=inputs, frames=frames, in_window=in_window,
        compiles=compiles, trace=reduced, records=records, extra=extra,
        peaks=work.peaks(device["kind"]) if trace else None,
    )
    metrics = {}
    for m in metric_names(man, cell, trace):
        value = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    report(run, checks)
    result = {
        "correct": bool(correct),
        "attempted": len(calls),
        "failed": sum(c.error is not None for c in calls),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        result["breakdown"] = {
            "device_ops": sorted(([k, v] for k, v in reduced.module_s.items()),
                                 key=lambda kv: -kv[1])[:10],
            "idle_gaps": sorted(([k, v] for k, v in reduced.idle_by_span.items()),
                                key=lambda kv: -kv[1])[:10],
        }
    result["checks"] = {k: {"value": v, "limit": LIMITS[k]} for k, v in checks.items()}
    return result


# the comparison is exact: any frame that does not decode to its input, any
# differing byte and any request never answered makes the run incorrect
LIMITS = {"checked_frames": ">=1", "bad_frames": 0, "bad_bytes": 0, "unanswered": 0}


def report(run, checks: dict) -> None:
    """Earlier lines: what the window did, for the reader of the log."""
    errors: Dict[str, int] = {}
    for c in run.calls:
        if c.error:
            errors[c.error] = errors.get(c.error, 0) + 1
    window_compiles = run.compiles.within(run.t0, run.t_end)
    print(f"window: {len(run.calls)} calls in {run.window_s:.3f} s; errors {errors};"
          f" compiles in window {len(window_compiles)} ({sum(window_compiles):.3f} s),"
          f" in set-up {len(run.compiles.events) - len(window_compiles)}")
    late = run.extra.get("lateness_s")
    if late:
        late = sorted(late)
        print(f"generator lateness: p50 {late[len(late) // 2] * 1e3:.3f} ms,"
              f" p99 {late[min(len(late) - 1, int(0.99 * len(late)))] * 1e3:.3f} ms,"
              f" max {late[-1] * 1e3:.3f} ms over {len(late)} requests;"
              f" client pool {run.extra['clients']}")
    if run.in_window:
        print(f"session counters in window: {run.in_window}")
    srv = run.extra.get("server")
    if srv:
        keep = ("requests", "shed", "errors", "backend_health", "quarantine", "resolve_cache")
        print("server: " + str({k: srv.get(k) for k in keep}))
    print("checks: " + ", ".join(f"{k} {v}" for k, v in checks.items()))
