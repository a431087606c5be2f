"""The multi-core service plane: pre-forked workers, one shared listener.

Availability claims come with their failure modes injected, per the standing
reliability policy: worker death is proven by SIGKILLing *real* forked
processes — both directly by pid and through enumerated ``svc.request.*``
crash sites armed inside the workers — and every scenario must end with the
client's retried request served and no session leaked anywhere in the plane.
"""
import io
import os
import signal
import socket
import time

import pytest

from repro.codecs import profiles as PR
from repro.core import compress, serial
from repro.reliability.faults import FaultPlan
from repro.service import (
    PlanRegistry,
    ServiceClient,
    ServicePlane,
    ServiceUnavailable,
)
from repro.service import protocol as SP

DATA = b"plane corpus: ts=171 dev=3 level=INFO handled\n" * 400


def _registry() -> PlanRegistry:
    registry = PlanRegistry()
    registry.register_profile("generic")
    return registry


def _plane(tmp_path, **kw) -> ServicePlane:
    kw.setdefault("workers", 2)
    kw.setdefault("request_timeout", 10.0)
    return ServicePlane(
        _registry(), socket_path=str(tmp_path / "plane.sock"), **kw
    )


def _client(plane, **kw) -> ServiceClient:
    kw.setdefault("timeout", 15.0)
    return ServiceClient(plane.address, **kw)


def _aggregate_in_use(stats: dict) -> int:
    return sum(s.get("in_use", 0) for s in (stats.get("sessions") or {}).values())


def _wait_for(predicate, timeout=10.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


# ------------------------------------------------------------------ basics
def test_plane_roundtrip_byte_identical(tmp_path):
    """Frames through the plane match the in-process engine byte for byte."""
    want = compress(PR.generic_profile(), serial(DATA), chunk_bytes=4096)
    with _plane(tmp_path) as plane, _client(plane) as c:
        frame, stats = c.compress_bytes(DATA, "generic", chunk_bytes=4096)
        assert frame == want
        back, _ = c.decompress_bytes(frame)
        assert back == DATA
        assert stats["digest"]


def test_plane_spreads_connections_across_processes(tmp_path):
    """Distinct worker processes actually serve: with enough fresh
    connections, at least two different pids answer ping."""
    with _plane(tmp_path, workers=2) as plane:
        pids = set()
        for _ in range(20):
            with _client(plane) as c:
                pids.add(c.ping()["pid"])
            if len(pids) >= 2:
                break
        assert pids <= set(plane.worker_pids())
        assert len(pids) >= 2, f"all connections served by one worker: {pids}"


def test_plane_aggregated_stats_and_metrics(tmp_path):
    with _plane(tmp_path) as plane, _client(plane) as c:
        for _ in range(3):
            c.compress_bytes(DATA, "generic", chunk_bytes=4096)
        # aggregation needs every worker's snapshot: the serving worker's
        # travels with the query, the idle sibling's arrives by heartbeat
        assert _wait_for(lambda: len(c.stats().get("per_worker", {})) >= 2)
        st = c.stats()
        assert st["workers"] == 2
        assert st["workers_alive"] == 2
        assert st["requests"]["compress"] >= 3
        assert _aggregate_in_use(st) == 0
        text = c.metrics().decode()
        assert "ozl_workers 2" in text
        assert 'ozl_requests_total{verb="compress"}' in text
        assert "ozl_worker_sessions_in_use" in text


def test_plane_stats_dict_shape_matches_threaded_server(tmp_path):
    """The aggregate keeps the single-process stats surface (plus plane
    keys), so dashboards and clients need no per-flavor switches."""
    with _plane(tmp_path) as plane, _client(plane) as c:
        c.compress_bytes(DATA, "generic", chunk_bytes=4096)
        st = c.stats()
        for key in (
            "ok", "protocol_version", "plans", "uptime_s", "address",
            "requests", "errors", "shed", "bytes_in", "bytes_out",
            "sessions", "latency", "resolve_cache", "coder_cache",
            "backend_health", "quarantine", "registry",
        ):
            assert key in st, f"aggregate missing {key!r}"


# ------------------------------------------------------------- worker death
def test_sigkill_serving_worker_mid_session_absorbed(tmp_path):
    """SIGKILL the worker a client is pinned to; the retried request must be
    served by a sibling (the shared listener never refuses) and the plane
    must end with zero checked-out sessions and a respawned worker."""
    with _plane(tmp_path) as plane:
        with _client(plane, retries=5, backoff_base=0.1) as c:
            want, _ = c.compress_bytes(DATA, "generic", chunk_bytes=4096)
            victim = c.ping()["pid"]
            assert victim in plane.worker_pids()
            os.kill(victim, signal.SIGKILL)
            frame, _ = c.compress_bytes(DATA, "generic", chunk_bytes=4096)
            assert frame == want, "retried request produced different bytes"
            assert _wait_for(lambda: victim not in plane.worker_pids())
            assert _wait_for(lambda: len(plane.worker_pids()) == 2)
            assert plane.worker_restarts >= 1
            st = c.stats()
            assert _aggregate_in_use(st) == 0, "leaked session after kill"


def test_crash_sites_enumerable_and_kill_mid_compress_absorbed(tmp_path):
    """Per the standing policy, the kill sites are enumerated from a record
    run, then a real worker is SIGKILLed at one of them mid-request."""
    # 1. enumerate: a record-mode plan sees the request-path crash sites
    recorder = FaultPlan(record=True)
    from repro.service.server import RequestCore

    core = RequestCore(_registry())
    try:
        with recorder.arm(all_threads=True):
            buf = io.BytesIO()
            SP.write_request(
                buf, SP.VERB_COMPRESS,
                {"plan": "generic", "size": len(DATA), "chunk_bytes": 4096},
                SP.iter_body_blocks(DATA, 4096),
            )
            _verb, header, body = SP.read_request(io.BytesIO(buf.getvalue()))
            resp, out = core.handle(SP.VERB_COMPRESS, header, body)
            out.close()
    finally:
        core.close()
    sites = {name for name, _n in recorder.sites}
    assert "svc.request.compress.begin" in sites
    assert "svc.request.compress.mid" in sites

    # 2. kill a real worker at the mid-compress site (after the session is
    # checked out, before the response) — the client's retry must succeed
    plan = FaultPlan().at("svc.request.compress.mid", nth=1, action="kill")
    with _plane(tmp_path, worker_fault_json=plan.to_json()) as plane:
        before = set(plane.worker_pids())
        with _client(plane, retries=6, backoff_base=0.1) as c:
            want = compress(PR.generic_profile(), serial(DATA), chunk_bytes=4096)
            frame, _ = c.compress_bytes(DATA, "generic", chunk_bytes=4096)
            assert frame == want
            # at least one worker died at the crash site and was replaced
            assert _wait_for(lambda: plane.worker_restarts >= 1)
            assert _wait_for(lambda: len(plane.worker_pids()) == 2)
            assert before - set(plane.worker_pids()), "no worker was killed"
            # respawned workers come up clean (fault_respawns=False):
            # a fresh request must succeed without burning retries
            with _client(plane) as c2:
                frame2, _ = c2.compress_bytes(DATA, "generic", chunk_bytes=4096)
                assert frame2 == want
            st = c.stats()
            assert _aggregate_in_use(st) == 0


def test_restart_budget_bounds_respawns(tmp_path):
    """A kill rule re-armed on every respawn cannot crash-loop the plane
    past its restart budget."""
    plan = FaultPlan().at("svc.request.compress.begin", nth=1, action="kill")
    with _plane(
        tmp_path,
        workers=1,
        worker_fault_json=plan.to_json(),
        fault_respawns=True,
        max_restarts=2,
    ) as plane:
        # short timeout: once the budget is spent there is no worker left to
        # accept, and the attempt must end at the deadline, not hang
        with _client(plane, retries=8, backoff_base=0.1, timeout=3.0) as c:
            # each attempt kills the (sole, re-faulted) worker until the
            # restart budget is spent; the plane must shrink, not crash-loop
            with pytest.raises(Exception):
                c.compress_bytes(DATA, "generic", chunk_bytes=4096)
        assert plane.worker_restarts <= 2


# ------------------------------------------------------------ rate limiting
def test_plane_rate_limit_rejects_with_retry_after(tmp_path):
    with _plane(tmp_path, workers=1, rate_limit=1.0, rate_burst=2.0) as plane:
        with _client(plane) as c:
            c.compress_bytes(DATA, "generic", chunk_bytes=4096)
            c.compress_bytes(DATA, "generic", chunk_bytes=4096)
            with pytest.raises(ServiceUnavailable) as exc:
                c.compress_bytes(DATA, "generic", chunk_bytes=4096)
            assert exc.value.kind == "rate_limited"
            assert exc.value.retry_after and exc.value.retry_after > 0
            # pings are free: control verbs are never rate limited
            assert c.ping()["ok"]
            st = c.stats()
            assert st["rate_limited"] >= 1
            assert _aggregate_in_use(st) == 0


def test_rate_limited_client_recovers_after_backoff(tmp_path):
    with _plane(tmp_path, workers=1, rate_limit=20.0, rate_burst=1.0) as plane:
        # retries honor the server's retry_after, so a client with budget
        # rides straight through the rejection window
        with _client(plane, retries=4, backoff_base=0.05) as c:
            for _ in range(3):
                frame, _ = c.compress_bytes(DATA, "generic", chunk_bytes=4096)
            assert frame


def test_threaded_server_rate_limit(tmp_path):
    """The per-connection limiter also guards the classic threaded server."""
    from repro.service import CompressionServer

    with CompressionServer(
        _registry(),
        socket_path=str(tmp_path / "thr.sock"),
        rate_limit=1.0,
        rate_burst=2.0,
        request_timeout=5.0,
    ) as srv:
        with ServiceClient(srv.address, timeout=10.0) as c:
            c.compress_bytes(DATA, "generic", chunk_bytes=4096)
            c.compress_bytes(DATA, "generic", chunk_bytes=4096)
            with pytest.raises(ServiceUnavailable) as exc:
                c.compress_bytes(DATA, "generic", chunk_bytes=4096)
            assert exc.value.kind == "rate_limited"
        st = srv.stats()
        assert st["rate_limited"] >= 1
        assert st["rate_limiter"]["rejected"] >= 1


# --------------------------------------------------------------- client side
def test_client_retries_connection_refused():
    """ECONNREFUSED during a restart window is retried under the jittered
    backoff budget, succeeding once the plane's listener is back.  TCP keeps
    the refused window deterministic: a closed port refuses instantly, and
    rebinding the same port (REUSEADDR) has no missing-path moment."""
    import threading

    lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lst.bind(("127.0.0.1", 0))
    port = lst.getsockname()[1]
    lst.listen(1)
    # the client connects eagerly in __init__, so dial the throwaway
    # listener first, then tear it down to open the refused window
    c = ServiceClient(("127.0.0.1", port), timeout=10.0, retries=6,
                      backoff_base=0.15, backoff_max=0.5)
    lst.close()
    c.close()  # drop the dead connection; the next call redials

    started = []

    def bring_up():
        time.sleep(0.4)
        plane = ServicePlane(_registry(), host="127.0.0.1", port=port, workers=1)
        plane.start()
        started.append(plane)

    t = threading.Thread(target=bring_up)
    t.start()
    try:
        assert c.ping()["ok"]  # retried through the refused window
    finally:
        t.join(10)
        c.close()
        for plane in started:
            plane.shutdown()


def test_connection_lost_is_hard_error_without_budget(tmp_path):
    """A server that dies before responding surfaces as ConnectionLost, and
    retries=0 keeps it a hard error (fail closed, never silently resend
    forever)."""
    from repro.service import ConnectionLost

    sock_path = str(tmp_path / "mute.sock")
    lst = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    lst.bind(sock_path)
    lst.listen(4)

    import threading

    def mute_server():
        # accept and slam the door without ever answering — the shape of a
        # worker crashing between request and response.  Exactly two accepts:
        # the client's eager connect and its one transparent redial (a third
        # would block in accept() forever; close() does not wake it)
        for _ in range(2):
            try:
                conn, _addr = lst.accept()
            except OSError:
                return
            conn.close()

    t = threading.Thread(target=mute_server)
    t.start()
    try:
        c = ServiceClient(f"unix:{sock_path}", timeout=5.0, retries=0)
        with pytest.raises(ConnectionLost):
            c.ping()
        c.close()
    finally:
        lst.close()
        t.join(10)


def test_device_backend_refuses_several_worker_processes(tmp_path):
    """A chip belongs to one process: a plane that would fork two workers
    onto it is refused before it binds or forks anything, by the library and
    by ``repro serve``."""
    from repro.cli import main

    sock = tmp_path / "dev.sock"
    with pytest.raises(ValueError, match="one process per chip"):
        ServicePlane(
            _registry(), socket_path=str(sock), workers=2, backend="device"
        )
    assert not sock.exists()
    with pytest.raises(SystemExit, match="one process per chip"):
        main(["serve", "--socket", str(sock), "--profile", "generic",
              "--workers", "2", "--backend", "device"])
    assert not sock.exists()
    # one worker on the device, or many on the host, stay allowed
    from repro.service.plane import check_device_workers

    check_device_workers(1, "device")
    check_device_workers(4, "host")
    check_device_workers(4, None)
