"""The deterministic fault-injection plane (repro.reliability.faults).

Standing policy under test: disarmed plans cost nothing and change nothing;
armed plans are seed-deterministic (same seed => same fault sequence over a
deterministic workload); injected failures are indistinguishable from the
real thing at every instrumented seam (file I/O, protocol framing, device
kernels) — and the backend-failover path they exercise stays bit-identical
on the wire.
"""
import io

import numpy as np
import pytest

from repro.codecs.profiles import resolve_profile_spec
from repro.core import CompressorSession, compress, numeric, pipeline, stream_io
from repro.reliability import (
    BackendHealth,
    FaultPlan,
    InjectedFault,
    Quarantine,
    current_plan,
    fault_point,
    wrap_io,
)


# ------------------------------------------------------------------ disarmed
def test_disarmed_is_pass_through():
    assert current_plan() is None
    f = io.BytesIO()
    assert wrap_io(f, "io.x") is f  # the original object, not a proxy
    fault_point("any.name")  # no-op, no state


# ----------------------------------------------------------------- schedules
def test_explicit_rule_fires_on_exact_occurrence():
    plan = FaultPlan().at("p.x", nth=3)
    with plan.arm():
        fault_point("p.x")
        fault_point("p.x")
        with pytest.raises(InjectedFault):
            fault_point("p.x")
        fault_point("p.x")  # times=1: only the 3rd fires
        fault_point("p.other")  # different point, own counter
    assert plan.fired == [("p.x", 3, "raise")]


def test_occurrences_count_per_point_name():
    plan = FaultPlan().at("a.*", nth=2)
    with plan.arm():
        fault_point("a.one")
        fault_point("a.two")  # each name is on its 1st occurrence
        with pytest.raises(InjectedFault):
            fault_point("a.one")
        with pytest.raises(InjectedFault):
            fault_point("a.two")


def test_seeded_random_schedule_is_deterministic():
    def run(seed):
        plan = FaultPlan(seed=seed).every("w.*", 0.3)
        fired = []
        with plan.arm():
            for i in range(200):
                try:
                    fault_point(f"w.{i % 5}")
                except InjectedFault:
                    fired.append(i)
        return fired

    a, b = run(7), run(7)
    assert a == b and a  # same seed => same sequence, and it does fire
    assert run(8) != a  # different seed => different sequence


def test_global_arming_is_exclusive():
    p1, p2 = FaultPlan(), FaultPlan()
    with p1.arm(all_threads=True):
        with pytest.raises(RuntimeError):
            with p2.arm(all_threads=True):
                pass
    with p2.arm(all_threads=True):  # slot released on exit
        pass
    assert current_plan() is None


def test_plan_json_roundtrip_for_subprocess_victims():
    plan = FaultPlan().at("a.x", nth=2, action="drop")
    clone = FaultPlan.from_json(plan.to_json())
    with clone.arm():
        fault_point("a.x")
        with pytest.raises(ConnectionResetError):
            fault_point("a.x")


# ----------------------------------------------------------------- I/O seams
def test_short_write_leaves_a_partial_prefix():
    buf = io.BytesIO()
    plan = FaultPlan().at("io.t.write", action="short")
    with plan.arm():
        f = wrap_io(buf, "io.t")
        with pytest.raises(InjectedFault):
            f.write(b"0123456789")
    assert 0 < len(buf.getvalue()) < 10  # torn, not absent and not complete


def test_compress_file_sink_fault_never_leaves_partial_output(tmp_path):
    src = tmp_path / "src.bin"
    src.write_bytes(b"log line payload\n" * 4000)
    dst = tmp_path / "out.ozl"
    plan_c = resolve_profile_spec("generic")
    with FaultPlan().at("io.sink.write", nth=3).arm(all_threads=True):
        with pytest.raises(InjectedFault):
            stream_io.compress_file(src, dst, plan_c, chunk_bytes=4096)
    assert not dst.exists()  # atomic sink: the final path never appeared
    assert not list(tmp_path.glob("*.tmp"))  # staging cleaned up on the error
    stream_io.compress_file(src, dst, plan_c, chunk_bytes=4096)  # disarmed: fine
    assert dst.exists()


def test_decompress_source_read_fault_propagates(tmp_path):
    src = tmp_path / "src.bin"
    src.write_bytes(b"abcdefgh" * 2000)
    dst = tmp_path / "out.ozl"
    back = tmp_path / "back.bin"
    stream_io.compress_file(src, dst, resolve_profile_spec("generic"))
    with FaultPlan().at("io.src.read").arm(all_threads=True):
        with pytest.raises(InjectedFault):
            stream_io.decompress_file(dst, back)
    assert not back.exists()


# ------------------------------------------------------------- protocol seam
def test_protocol_send_and_recv_drops():
    from repro.service import protocol as P

    buf = io.BytesIO()
    with FaultPlan().at("proto.send", action="drop").arm():
        with pytest.raises(ConnectionResetError):
            P.write_request(buf, P.VERB_PING, {})

    buf = io.BytesIO()
    P.write_response(buf, P.STATUS_OK, {"ok": True})
    buf.seek(0)
    with FaultPlan().at("proto.recv", action="drop").arm():
        with pytest.raises(ConnectionResetError):
            P.read_response(buf)


# ---------------------------------------------------- device faults, failover
DEV_PLAN = pipeline("delta", "bitpack")


def _payload():
    return numeric(np.arange(4096, dtype=np.uint32))


def test_device_fault_is_fatal_without_failover():
    with CompressorSession(DEV_PLAN, backend="device") as sess:
        with FaultPlan().at("device.encode.device.*", times=10**6).arm(
            all_threads=True
        ):
            with pytest.raises(InjectedFault):
                sess.compress(_payload())


def test_device_failover_serves_bit_identical_host_frames():
    ref = compress(DEV_PLAN, _payload())  # host path
    fo = BackendHealth(threshold=2, cooldown_s=1000.0)
    with CompressorSession(DEV_PLAN, backend="device", failover=fo) as sess:
        with FaultPlan().at("device.encode.device.*", times=10**6).arm(
            all_threads=True
        ):
            f1 = sess.compress(_payload())  # failover, failure 1 recorded
            f2 = sess.compress(_payload())  # failure 2 -> quarantined
        f3 = sess.compress(_payload())  # disarmed but benched: host directly
    assert f1 == ref and f2 == ref and f3 == ref
    st = fo.stats()["device"]
    assert st["quarantined"] and st["failovers"] >= 2


def test_device_failover_recovers_after_cooldown_probe():
    t = [0.0]
    fo = BackendHealth(threshold=1, cooldown_s=10.0, clock=lambda: t[0])
    with CompressorSession(DEV_PLAN, backend="device", failover=fo) as sess:
        with FaultPlan().at("device.encode.device.*").arm(all_threads=True):
            sess.compress(_payload())  # one failure -> quarantined
        assert fo.stats()["device"]["quarantined"]
        t[0] = 11.0  # cooldown expired: the next chunk is the probe
        assert sess.compress(_payload()) == compress(DEV_PLAN, _payload())
    assert not fo.stats()["device"]["quarantined"]  # probe succeeded


# --------------------------------------------------------- health unit tests
def test_backend_health_probe_protocol():
    t = [0.0]
    h = BackendHealth(threshold=1, cooldown_s=10.0, clock=lambda: t[0])
    assert not h.quarantined("dev")
    h.record_failure("dev")
    assert h.quarantined("dev")
    t[0] = 11.0
    assert not h.quarantined("dev")  # the single probe slot
    assert h.quarantined("dev")  # everyone else still benched
    h.record_failure("dev")  # probe failed: re-quarantined from now
    assert h.quarantined("dev")
    t[0] = 22.0
    assert not h.quarantined("dev")
    h.record_success("dev")  # probe succeeded: cleared
    assert not h.quarantined("dev")


def test_quarantine_breaker_protocol():
    t = [0.0]
    q = Quarantine(threshold=3, cooldown_s=5.0, clock=lambda: t[0])
    q.record_failure("d")
    q.record_failure("d")
    assert q.blocked("d") is None  # below threshold
    q.record_failure("d")
    remaining = q.blocked("d")
    assert remaining is not None and 0 < remaining <= 5.0
    t[0] = 6.0
    assert q.blocked("d") is None  # expiry admits a probe
    q.record_failure("d")  # probe failure re-trips immediately
    assert q.blocked("d") is not None
    t[0] = 12.0
    assert q.blocked("d") is None
    q.record_success("d")  # probe success clears the count entirely
    q.record_failure("d")
    q.record_failure("d")
    assert q.blocked("d") is None
