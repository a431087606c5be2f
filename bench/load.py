"""The one general load generator.

A traffic mix is a data file, ``bench/traffic/<mix>.json``, that this module
reads; a new mix is a new file.  Two loops:

* ``"loop": "closed"`` -- one caller compresses the configuration's items in
  file order, waiting for each frame, cycling until the window closes.
  Set-up runs one whole pass.
* ``"loop": "open"`` -- requests of ``page_bytes`` cut from the items go to
  an in-process ``CompressionServer`` at seeded Poisson arrival times, from
  a pool of ``clients`` connections, each timed from when it was due.

Both hand back every call (``Call``) and every distinct frame (``Frames``)
for the harness to check against the plain reference.
"""
from __future__ import annotations

import dataclasses
import queue
import tempfile
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench.common import Item

NO_ANSWER = "unanswered"


@dataclass
class Call:
    """One request or compress call of the window (times on perf_counter)."""

    key: int  # index of the input it compressed (item or page)
    nbytes: int
    due: float
    start: float
    end: float
    out_bytes: int = 0
    frame_id: int = -1  # which distinct frame of ``key`` it returned
    error: Optional[str] = None


class Frames:
    """The distinct frames each input produced, and which the window made."""

    def __init__(self):
        self.by_key: Dict[int, List[bytes]] = {}
        self.in_window: Dict[Tuple[int, int], int] = {}  # (key, id) -> calls

    def add(self, key: int, frame: bytes, window: bool) -> int:
        seen = self.by_key.setdefault(key, [])
        for fid, old in enumerate(seen):
            if old == frame:
                break
        else:
            seen.append(frame)
            fid = len(seen) - 1
        if window:
            self.in_window[(key, fid)] = self.in_window.get((key, fid), 0) + 1
        return fid

    def stored_bytes(self, key: int) -> int:
        return len(self.by_key[key][-1])


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------- closed loop
def closed(mix: dict, items: List[Item], compress: Callable[[Item], bytes],
           seconds: float, window) -> Tuple[List[Call], Frames]:
    frames = Frames()
    for k, it in enumerate(items):
        frames.add(k, compress(it), window=False)
    calls: List[Call] = []
    n = len(items)
    with window as w:
        i = 0
        while time.perf_counter() - w.t0 < seconds:
            k = i % n
            it = items[k]
            t = time.perf_counter()
            with annotate("bench.compress"):
                frame = compress(it)
            e = time.perf_counter()
            fid = frames.add(k, frame, window=True)
            calls.append(Call(k, it.nbytes, t, t, e, len(frame), fid))
            i += 1
    return calls, frames


# ------------------------------------------------------------------ open loop
def pages(items: List[Item], page_bytes: int) -> List[Item]:
    """Every item cut into pages of ``page_bytes`` (the last one shorter)."""
    out = []
    for it in items:
        per = max(1, page_bytes // it.data.itemsize)
        for k, lo in enumerate(range(0, it.data.size, per)):
            out.append(dataclasses.replace(
                it, name=f"{it.name}.p{k}", data=it.data[lo : lo + per],
            ))
    return out


def schedule(mix: dict, pool: List[Item], seconds: float, seed: int):
    """(due offset, page index) pairs in time order.

    The count of requests is fixed (rate x seconds), and so is each group's
    share of it, by its bytes (largest remainder); the seed draws the pages
    inside each group, by bytes, and the arrival times, which given their
    count are those of a Poisson process: sorted uniform draws."""
    rng = np.random.default_rng([seed, 1])
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    groups: Dict[str, List[int]] = {}
    for k, p in enumerate(pool):
        groups.setdefault(p.plan, []).append(k)
    names = sorted(groups)
    size = np.array([sum(pool[k].nbytes for k in groups[g]) for g in names], float)
    share = size / size.sum() * n
    count = np.floor(share).astype(int)
    for j in np.argsort(-(share - count), kind="stable")[: n - int(count.sum())]:
        count[j] += 1
    picks = []
    for g, c in zip(names, count):
        ks = np.array(groups[g])
        w = np.array([pool[k].nbytes for k in ks], float)
        picks.extend(rng.choice(ks, int(c), p=w / w.sum()).tolist())
    picks = rng.permutation(np.array(picks, dtype=np.int64))
    due = np.sort(rng.uniform(0.0, seconds, n))
    return list(zip(due.tolist(), picks.tolist()))


def served_plan(profile_plan, width: int, name: str):
    """The profile behind an ``interpret_numeric(width)`` node: the service
    receives raw bytes, and this names their element width."""
    from repro.core.graph import KIND_CODEC, Plan, PlanNode

    head = PlanNode(KIND_CODEC, "interpret_numeric", (0,), 1, (("width", width),))
    body = [dataclasses.replace(n, inputs=tuple(e + 1 for e in n.inputs))
            for n in profile_plan.nodes]
    return Plan(1, (head, *body), name).validate()


def _socket_dir() -> Tuple[Optional[tempfile.TemporaryDirectory], Optional[str]]:
    tmp = tempfile.TemporaryDirectory(prefix="ozs")
    path = str(Path(tmp.name) / "s.sock")
    if len(path) < 100:  # a Unix socket path has to fit sockaddr_un
        return tmp, path
    tmp.cleanup()
    return None, None


class ServeRig:
    """An in-process threaded ``CompressionServer`` on the device backend,
    one plan per element width, and a pool of client connections."""

    def __init__(self, mix: dict, items: List[Item], profiles: Callable[[str], object],
                 degrade: Optional[Callable] = None):
        from repro.core import Compressor
        from repro.service import CompressionServer, PlanRegistry, ServiceClient

        self.pool = pages(items, int(mix["page_bytes"]))
        self.plan_of: Dict[int, str] = {}
        registry = PlanRegistry()
        for p in self.pool:
            pid = f"{p.profile}.w{p.data.itemsize}"
            if pid not in registry:
                plan = served_plan(profiles(p.profile), p.data.itemsize, pid)
                registry.register_compressor(Compressor(plan), pid)
            self.plan_of[id(p)] = pid
        self.degrade = degrade
        self.timeout = float(mix["grace_s"])
        self.clients = int(mix["clients"])
        self._tmp, sock = _socket_dir()
        self.server = CompressionServer(
            registry,
            **({"socket_path": sock} if sock else {"host": "127.0.0.1"}),
            backend="device",
            max_clients=self.clients,
            sessions_per_plan=int(mix["sessions_per_plan"]),
            admission_timeout=float(mix["admission_timeout_s"]),
        )
        self.conns: List = []
        try:
            self.server.start()
            self.conns = [ServiceClient(self.server.address, timeout=self.timeout)
                          for _ in range(self.clients)]
        except BaseException:
            self.close()
            raise

    def body(self, k: int) -> bytes:
        data = self.pool[k].data
        return (self.degrade(data) if self.degrade else data).tobytes()

    def send(self, client, k: int) -> bytes:
        frame, _ = client.compress_bytes(self.body(k), self.plan_of[id(self.pool[k])],
                                         chunk_bytes=0)
        return frame

    def warm(self, keys, frames: Frames) -> None:
        """Every page once, in a fixed order, so each plan resolves and each
        shape compiles before a window."""
        for k in sorted(set(keys)):
            frames.add(k, self.send(self.conns[0], k), window=False)

    def run(self, reqs, frames: Frames, window) -> List[Call]:
        """Send ``reqs`` (due offset, page) on schedule inside ``window``."""
        calls: List[Optional[Call]] = [None] * len(reqs)
        lock = threading.Lock()
        todo: "queue.Queue" = queue.Queue()
        late = threading.Event()  # the grace period is over: send nothing more

        def worker(client):
            while True:
                job = todo.get()
                if job is None:
                    return
                if late.is_set():
                    continue
                j, due = job
                k = reqs[j][1]
                t = time.perf_counter()
                call = Call(k, self.pool[k].nbytes, due, t, t)
                try:
                    with annotate("bench.request"):
                        frame = self.send(client, k)
                except Exception as err:  # noqa: BLE001 - every failure is counted
                    call.end = time.perf_counter()
                    call.error = getattr(err, "kind", None) or type(err).__name__
                else:
                    call.end = time.perf_counter()
                    with annotate("bench.response"), lock:
                        call.frame_id = frames.add(k, frame, window=True)
                    call.out_bytes = len(frame)
                calls[j] = call

        threads = [threading.Thread(target=worker, args=(c,), daemon=True) for c in self.conns]
        for t in threads:
            t.start()
        with window as w:
            for j, (offset, _) in enumerate(reqs):
                due = w.t0 + offset
                delay = due - time.perf_counter()
                if delay > 0:
                    with annotate("bench.wait"):
                        time.sleep(delay)
                todo.put((j, due))
            for _ in threads:
                todo.put(None)
            deadline = time.perf_counter() + self.timeout
            for t in threads:
                t.join(max(0.0, deadline - time.perf_counter()))
            late.set()
        out = []
        for j, c in enumerate(calls):
            if c is None:  # never answered within the grace period
                offset, k = reqs[j]
                c = Call(k, self.pool[k].nbytes, window.t0 + offset,
                         window.t0 + offset, window.t_end, error=NO_ANSWER)
            out.append(c)
        return out

    def close(self) -> None:
        for c in self.conns:
            c.close()
        self.server.shutdown()
        if self._tmp is not None:
            self._tmp.cleanup()

    def __enter__(self) -> "ServeRig":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def open_loop(mix: dict, items: List[Item], profiles: Callable[[str], object],
              seconds: float, seed: int, window, stats_out: dict,
              degrade: Optional[Callable] = None):
    """Serve seeded pages through a ServeRig -> (calls, frames, pages).
    ``stats_out`` receives the server's stats and the generator's lateness."""
    frames = Frames()
    with ServeRig(mix, items, profiles, degrade) as rig:
        reqs = schedule(mix, rig.pool, seconds, seed)
        rig.warm([k for _, k in reqs], frames)
        calls = rig.run(reqs, frames, window)
        stats_out["server"] = rig.server.stats()
        stats_out["clients"] = rig.clients
    stats_out["lateness_s"] = [c.start - c.due for c in calls if c.error != NO_ANSWER]
    return calls, frames, rig.pool
