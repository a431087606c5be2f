"""Multi-core service plane: pre-forked session workers, one shared listener.

Python's GIL caps the threaded :class:`~repro.service.server.CompressionServer`
at roughly one core of entropy coding no matter how many clients connect.
:class:`ServicePlane` escapes it with processes:

* The supervisor binds the listener once, then **forks** ``workers`` session
  workers that all inherit the fd and accept from it directly — the kernel
  load-balances connections, no fd-passing hop, and the semantics are
  identical for Unix and TCP sockets.  Because the supervisor keeps the
  listener open, a dying worker never produces connection-refused: pending
  connections just queue until a sibling (or the respawned replacement)
  accepts them.
* Each worker runs its own :class:`~repro.service.frontend.ServiceFrontend`
  event loop over a **private** :class:`~repro.service.server.RequestCore` —
  session pools, coder caches, the decoder, quarantine, and backend health
  are all per-process, so workers share no locks and scale linearly until
  the socket or the disk runs out.
* The supervisor reaps dead workers (crash, OOM, injected ``SIGKILL``) and
  respawns them within a restart budget.  In-flight requests on a dead
  worker surface to clients as a torn connection; ``ServiceClient`` retries
  them against the next worker to accept.
* **Stats aggregate across processes.**  Every worker pushes a periodic
  snapshot over its control socketpair; a ``stats`` request received by any
  worker is answered with the supervisor's merged view (summed counters,
  per-digest session occupancy, per-worker rows) — one scrape sees the
  whole plane, whichever process happens to serve it.

Fault injection composes per the standing policy: ``worker_fault_json`` arms
a :class:`~repro.reliability.faults.FaultPlan` inside each *initially
spawned* worker (the inherited-arming hazard is impossible — forked children
always start disarmed, see ``faults._faults_after_fork``), and respawned
replacements come up clean unless ``fault_respawns=True`` — a kill rule
cannot crash-loop the plane.
"""
from __future__ import annotations

import os
import selectors
import signal
import socket
import struct
import sys
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

import msgpack

from . import protocol as P
from .frontend import ServiceFrontend
from .ratelimit import RateLimiter
from .registry import PlanRegistry
from .server import RequestCore

__all__ = ["ServicePlane"]

#: Seconds between worker snapshot pushes (staleness bound on aggregates).
HEARTBEAT_S = 0.5


# ---------------------------------------------------------------- messaging
class _MsgChannel:
    """Length-prefixed msgpack messages over one socketpair end.

    ``send`` is locked (the worker's loop thread heartbeats while a compute
    thread runs a stats query); reads come in two flavors — ``poll`` for the
    non-blocking selector side, ``recv_blocking`` for request/reply.
    """

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self._wlock = threading.Lock()
        self._rlock = threading.Lock()
        self._rbuf = bytearray()

    def send(self, obj) -> None:
        blob = msgpack.packb(obj, use_bin_type=True)
        with self._wlock:
            self.sock.sendall(struct.pack("<I", len(blob)) + blob)

    def recv_blocking(self, timeout: Optional[float]):
        """One message, blocking -> object (None on EOF/timeout)."""
        with self._rlock:
            self.sock.settimeout(timeout)
            try:
                while True:
                    msg = self._parse_one()
                    if msg is not None:
                        return msg
                    piece = self.sock.recv(65536)
                    if not piece:
                        return None
                    self._rbuf += piece
            except (socket.timeout, OSError):
                return None
            finally:
                try:
                    self.sock.settimeout(None)
                except OSError:
                    pass

    def poll(self) -> list:
        """Drain whatever is readable right now -> complete messages, with a
        trailing ``None`` sentinel when the peer is gone (EOF/reset)."""
        eof = False
        with self._rlock:
            try:
                while True:
                    piece = self.sock.recv(65536)
                    if not piece:
                        eof = True
                        break
                    self._rbuf += piece
            except (BlockingIOError, InterruptedError):
                pass
            except OSError:
                eof = True
            out = []
            while True:
                msg = self._parse_one()
                if msg is None:
                    break
                out.append(msg)
        if eof:
            out.append(None)
        return out

    def _parse_one(self):
        if len(self._rbuf) < 4:
            return None
        n = struct.unpack("<I", bytes(self._rbuf[:4]))[0]
        if len(self._rbuf) < 4 + n:
            return None
        blob = bytes(self._rbuf[4 : 4 + n])
        del self._rbuf[: 4 + n]
        return msgpack.unpackb(blob, raw=False)

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass


def _merge_numeric(into: dict, snap: dict) -> dict:
    """Merge ``snap`` into ``into``: numbers add, bools OR, dicts recurse,
    anything else last-wins.  The shape every per-worker counter dict shares."""
    for k, v in snap.items():
        if isinstance(v, dict):
            base = into.get(k)
            into[k] = _merge_numeric(base if isinstance(base, dict) else {}, v)
        elif isinstance(v, bool):
            into[k] = bool(into.get(k)) or v
        elif isinstance(v, (int, float)):
            prev = into.get(k)
            into[k] = (prev if isinstance(prev, (int, float)) else 0) + v
        else:
            into[k] = v
    return into


class _Worker:
    __slots__ = ("idx", "pid", "ctrl", "stat", "snap", "alive", "faulted")

    def __init__(self, idx, pid, ctrl, stat, faulted):
        self.idx = idx
        self.pid = pid
        self.ctrl = ctrl
        self.stat = stat
        self.snap: Optional[dict] = None
        self.alive = True
        self.faulted = faulted

    @property
    def ident(self) -> str:
        return f"w{self.idx}:{self.pid}"


# -------------------------------------------------------------------- plane
def check_device_workers(workers: int, backend: Optional[str]) -> None:
    """Refuse a plane that would fork several processes onto one chip.

    A chip belongs to one process: every forked worker gets ``backend``, and
    a second worker that touches the device fails or hangs."""
    if backend == "device" and workers > 1:
        raise ValueError(
            f"backend='device' needs one process per chip: got workers={workers};"
            " use workers=1 or the threaded server"
        )


class ServicePlane:
    """Supervisor for a pre-forked pool of session-worker processes."""

    def __init__(
        self,
        registry: Optional[PlanRegistry] = None,
        *,
        socket_path: Optional[str] = None,
        host: Optional[str] = None,
        port: int = 0,
        workers: int = 2,
        max_clients: int = 512,
        compute_threads: int = 4,
        sessions_per_plan: int = 2,
        n_workers: Optional[int] = None,
        window: Optional[int] = None,
        request_timeout: float = 60.0,
        idle_timeout: float = 300.0,
        spool_bytes: int = 32 << 20,
        max_body_bytes: int = 1 << 30,
        admission_timeout: Optional[float] = None,
        backend: Optional[str] = None,
        quarantine_threshold: int = 3,
        quarantine_cooldown_s: float = 10.0,
        rate_limit: Optional[float] = None,
        rate_burst: Optional[float] = None,
        max_restarts: int = 8,
        worker_fault_json: Optional[str] = None,
        fault_respawns: bool = False,
    ):
        if (socket_path is None) == (host is None):
            raise ValueError("pass exactly one of socket_path= or host=")
        if workers < 1:
            raise ValueError("workers must be >= 1")
        check_device_workers(workers, backend)
        self.registry = registry if registry is not None else PlanRegistry()
        self.workers = workers
        self.max_clients = max_clients
        self.compute_threads = compute_threads
        self.request_timeout = request_timeout
        self.idle_timeout = idle_timeout
        self.rate_limit = rate_limit
        self.rate_burst = rate_burst
        self.max_restarts = max_restarts
        self.worker_fault_json = worker_fault_json
        self.fault_respawns = fault_respawns
        self._core_kw = dict(
            sessions_per_plan=sessions_per_plan,
            n_workers=n_workers,
            window=window,
            request_timeout=request_timeout,
            spool_bytes=spool_bytes,
            max_body_bytes=max_body_bytes,
            admission_timeout=admission_timeout,
            backend=backend,
            quarantine_threshold=quarantine_threshold,
            quarantine_cooldown_s=quarantine_cooldown_s,
        )
        self._workers: List[_Worker] = []
        self._lock = threading.Lock()
        self._stopping = threading.Event()
        self._supervisor: Optional[threading.Thread] = None
        self._started = time.monotonic()
        self.worker_restarts = 0

        if socket_path is not None:
            self.socket_path: Optional[str] = str(socket_path)
            Path(self.socket_path).unlink(missing_ok=True)
            self._listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            self._listener.bind(self.socket_path)
            self.address = f"unix:{self.socket_path}"
        else:
            self.socket_path = None
            self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            self._listener.bind((host, port))
            bound_host, bound_port = self._listener.getsockname()[:2]
            self.address = f"{bound_host}:{bound_port}"
        self._listener.listen(max(128, max_clients))

    # ------------------------------------------------------------- lifecycle
    def start(self) -> "ServicePlane":
        for idx in range(self.workers):
            self._spawn(idx, self.worker_fault_json)
        self._supervisor = threading.Thread(
            target=self._supervise, name="ozl-plane-supervisor", daemon=True
        )
        self._supervisor.start()
        return self

    def __enter__(self) -> "ServicePlane":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> None:
        self.shutdown()

    def shutdown(self) -> None:
        self._stopping.set()
        with self._lock:
            workers = list(self._workers)
        for w in workers:
            if w.alive:
                try:
                    w.ctrl.send({"type": "stop"})
                except OSError:
                    pass
        deadline = time.monotonic() + 5.0
        for w in workers:
            if not w.alive:
                continue
            while time.monotonic() < deadline:
                try:
                    pid, _status = os.waitpid(w.pid, os.WNOHANG)
                except ChildProcessError:
                    # the supervisor's reaper won the waitpid race — done
                    w.alive = False
                    break
                if pid == w.pid:
                    w.alive = False
                    break
                time.sleep(0.02)
            if w.alive:
                try:
                    os.kill(w.pid, signal.SIGKILL)
                    os.waitpid(w.pid, 0)
                except (OSError, ChildProcessError):
                    pass
                w.alive = False
        if self._supervisor is not None:
            self._supervisor.join(timeout=5)
        for w in workers:
            w.ctrl.close()
            w.stat.close()
        try:
            self._listener.close()
        except OSError:
            pass
        if self.socket_path:
            Path(self.socket_path).unlink(missing_ok=True)

    # --------------------------------------------------------------- forking
    def _spawn(self, idx: int, fault_json: Optional[str]) -> None:
        ctrl_parent, ctrl_child = socket.socketpair()
        stat_parent, stat_child = socket.socketpair()
        # quiesce the registry across the fork so the child never inherits a
        # lock held mid-operation by some other parent thread
        reg_lock = getattr(self.registry, "_lock", None)
        if reg_lock is not None:
            reg_lock.acquire()
        try:
            pid = os.fork()
        finally:
            if reg_lock is not None:
                reg_lock.release()
        if pid == 0:
            # ---- child: never returns
            try:
                ctrl_parent.close()
                stat_parent.close()
                with self._lock:
                    inherited = list(self._workers)
                for w in inherited:
                    w.ctrl.close()
                    w.stat.close()
                self._worker_main(idx, ctrl_child, stat_child, fault_json)
                code = 0
            except BaseException as err:  # noqa: BLE001 - child must exit
                try:
                    sys.stderr.write(f"[ozl-worker w{idx}] died: {err!r}\n")
                    sys.stderr.flush()
                except OSError:
                    pass
                code = 70
            os._exit(code)
        # ---- parent
        ctrl_child.close()
        stat_child.close()
        worker = _Worker(
            idx, pid, _MsgChannel(ctrl_parent), _MsgChannel(stat_parent),
            faulted=fault_json is not None,
        )
        stat_parent.setblocking(False)
        with self._lock:
            self._workers.append(worker)

    # ---------------------------------------------------------- child process
    def _worker_main(self, idx, ctrl_sock, stat_sock, fault_json) -> None:
        signal.signal(signal.SIGINT, signal.SIG_IGN)  # the parent coordinates
        core = RequestCore(self.registry, **self._core_kw)
        limiter = (
            RateLimiter(self.rate_limit, self.rate_burst)
            if self.rate_limit
            else None
        )
        frontend = ServiceFrontend(
            core,
            self._listener,
            max_conns=self.max_clients,
            compute_threads=self.compute_threads,
            idle_timeout=self.idle_timeout,
            request_timeout=self.request_timeout,
            rate_limiter=limiter,
            name=f"ozl-w{idx}",
        )
        ctrl = _MsgChannel(ctrl_sock)
        stat = _MsgChannel(stat_sock)
        ident = f"w{idx}:{os.getpid()}"
        last_beat = [0.0]

        def snapshot() -> dict:
            snap = {**core.stats(), **frontend.transport_stats()}
            if limiter is not None:
                snap["rate_limiter"] = limiter.stats()
            return snap

        def aggregated_stats() -> dict:
            # compute-thread path: ship our fresh snapshot with the query so
            # the supervisor's merge always includes the serving worker
            try:
                stat.send(
                    {"type": "stats_query", "ident": ident, "snap": snapshot()}
                )
                reply = stat.recv_blocking(timeout=5.0)
            except OSError:
                reply = None
            if not reply or "aggregate" not in reply:
                return snapshot()  # supervisor gone: degrade to our own view
            return reply["aggregate"]

        def on_control() -> None:
            for msg in ctrl.poll():
                if msg is None or msg.get("type") == "stop":
                    frontend.stop()
                    return

        def heartbeat() -> None:
            now = time.monotonic()
            if now - last_beat[0] < HEARTBEAT_S:
                return
            last_beat[0] = now
            try:
                stat.send({"type": "snap", "ident": ident, "snap": snapshot()})
            except OSError:
                frontend.stop()  # supervisor is gone; no point serving on

        core.stats_provider = aggregated_stats
        frontend.add_reader(ctrl_sock, on_control)
        frontend.on_tick = heartbeat

        signal.signal(signal.SIGTERM, lambda *_: frontend.stop())

        if fault_json:
            from repro.reliability.faults import FaultPlan

            plan = FaultPlan.from_json(fault_json)
            with plan.arm(all_threads=True):
                frontend.serve_forever()
        else:
            frontend.serve_forever()
        core.close()

    # ------------------------------------------------------------ supervisor
    def _supervise(self) -> None:
        sel = selectors.DefaultSelector()
        registered: Dict[int, _Worker] = {}
        while not self._stopping.is_set():
            with self._lock:
                workers = list(self._workers)
            for w in workers:
                if w.alive and w.stat.sock.fileno() >= 0:
                    if w.stat.sock.fileno() not in registered:
                        try:
                            sel.register(w.stat.sock, selectors.EVENT_READ, w)
                            registered[w.stat.sock.fileno()] = w
                        except (KeyError, ValueError, OSError):
                            pass
            for key, _mask in sel.select(timeout=0.2):
                w = key.data
                for msg in w.stat.poll():
                    if msg is None:
                        # worker end gone: close our end too, or the selector
                        # would re-register and spin on a readable EOF
                        try:
                            sel.unregister(w.stat.sock)
                        except (KeyError, ValueError, OSError):
                            pass
                        registered.pop(key.fd, None)
                        w.stat.close()
                        break
                    if msg.get("snap") is not None:
                        w.snap = msg["snap"]
                    if msg.get("type") == "stats_query":
                        try:
                            w.stat.send({"aggregate": self._aggregate()})
                        except OSError:
                            pass
            self._reap(sel, registered)
        sel.close()

    def _reap(self, sel, registered) -> None:
        with self._lock:
            workers = list(self._workers)
        for w in workers:
            if not w.alive:
                continue
            try:
                pid, _status = os.waitpid(w.pid, os.WNOHANG)
            except ChildProcessError:
                pid = w.pid
            if pid != w.pid:
                continue
            w.alive = False
            try:
                sel.unregister(w.stat.sock)
            except (KeyError, ValueError, OSError):
                pass
            registered.pop(w.stat.sock.fileno(), None)
            w.ctrl.close()
            w.stat.close()
            if self._stopping.is_set():
                continue
            if self.worker_restarts >= self.max_restarts:
                continue  # restart budget exhausted: shrink rather than loop
            self.worker_restarts += 1
            self._spawn(
                w.idx,
                self.worker_fault_json if self.fault_respawns else None,
            )

    # ----------------------------------------------------------------- stats
    def worker_pids(self) -> List[int]:
        with self._lock:
            return [w.pid for w in self._workers if w.alive]

    def stats(self) -> dict:
        """Parent-side aggregate from the latest worker snapshots."""
        return self._aggregate()

    def _aggregate(self) -> dict:
        with self._lock:
            workers = list(self._workers)
        alive = [w for w in workers if w.alive]
        snaps = [(w.ident, w.snap) for w in workers if w.snap is not None]
        merged: dict = {}
        latencies: List[dict] = []
        per_worker: Dict[str, dict] = {}
        for ident, snap in snaps:
            body = {
                k: v
                for k, v in snap.items()
                if k
                not in (
                    "ok", "protocol_version", "plans", "uptime_s", "pid",
                    "registry", "latency",
                )
            }
            _merge_numeric(merged, body)
            latencies.append(snap.get("latency") or {})
            per_worker[ident] = {
                "pid": snap.get("pid"),
                "uptime_s": snap.get("uptime_s"),
                "requests": snap.get("requests"),
                "sessions": snap.get("sessions"),
                "coder_cache": snap.get("coder_cache"),
                "active_connections": snap.get("active_connections"),
            }
        return {
            "ok": True,
            "protocol_version": P.PROTOCOL_VERSION,
            "plans": len(self.registry),
            "uptime_s": round(time.monotonic() - self._started, 3),
            "address": self.address,
            "workers": self.workers,
            "workers_alive": len(alive),
            "worker_restarts": self.worker_restarts,
            **merged,
            "latency": _merge_latency(latencies),
            "registry": self.registry.entries(),
            "per_worker": per_worker,
        }


def _merge_latency(latencies: List[dict]) -> dict:
    """Cross-worker latency merge: counts and rates add, p50 is the
    count-weighted mean (an approximation), p99 is the worst worker's."""
    out: Dict[str, dict] = {}
    for lat in latencies:
        for verb, row in (lat or {}).items():
            agg = out.setdefault(
                verb, {"n": 0, "p50_ms": 0.0, "p99_ms": 0.0, "req_s": 0.0}
            )
            n = row.get("n") or 0
            agg["p50_ms"] += (row.get("p50_ms") or 0.0) * n
            agg["p99_ms"] = max(agg["p99_ms"], row.get("p99_ms") or 0.0)
            agg["req_s"] += row.get("req_s") or 0.0
            agg["n"] += n
    for agg in out.values():
        if agg["n"]:
            agg["p50_ms"] = round(agg["p50_ms"] / agg["n"], 3)
        agg["req_s"] = round(agg["req_s"], 3)
    return out
