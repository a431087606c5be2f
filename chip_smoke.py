#!/usr/bin/env python
"""Drive the device-backend compress path once on one TPU chip, and check it.

    python chip_smoke.py [--seed N]

One process owns the chip for the whole run (a second process that touched
JAX could not reach it).  In order:

1. Device check: exits non-zero before any work unless JAX's first device is
   a TPU.
2. Session phase: seeded 64 MiB inputs through ``CompressorSession(...,
   backend="device")``: sorted u32 offsets under delta+bitpack, under
   fused_delta_bitpack and under delta+transpose+huffman, small-range u32
   under bitpack, a bf16-rounded float32 checkpoint tensor under the
   ``float32`` profile and under an explicit float_split -> transpose_split
   -> fse/huffman plan, and numeric(4) records under transpose.  Then one
   256 MiB input in 4 MiB chunks through the same delta+transpose+huffman
   session (thread pool + prefetch).
   Every frame must equal the ``backend="host"`` frame byte for byte and
   decode to the input; every device twin must have encoded at least one
   node, and no node expected on the device may have run on the host.
3. Service phase: an in-process threaded ``CompressionServer``
   (``backend="device"``, Unix socket) answers 8 compress and 8 decompress
   requests of 4-16 MiB from a ``ServiceClient``; responses must equal the
   offline frames and inputs, and ``backend_health`` must show no device
   failure and nothing quarantined.

Earlier lines report first-call (compile included) and steady times, the
per-twin node counts and the device's peak memory: informational, not
benchmark numbers.  The last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

MIB = 1 << 20
SIZE = 64 * MIB  # bytes per session-phase input
CHUNKED_SIZE = 256 * MIB
CHUNK = 4 * MIB
SERVICE_MIB = (4, 16, 6, 12, 8, 10, 14, 5)  # one compress request each

TWINS = (
    "delta", "bitpack", "fused_delta_bitpack", "transpose",
    "transpose_split", "float_split", "huffman", "fse",
)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    raise SystemExit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


# --------------------------------------------------------------------- inputs
def offsets_u32(rng, nbytes: int) -> np.ndarray:
    """Sorted u32 offsets (an index/offset table): steps below 2^8."""
    return np.cumsum(rng.integers(0, 200, nbytes // 4), dtype=np.uint32)


def small_range_u32(rng, nbytes: int) -> np.ndarray:
    return rng.integers(0, 13, nbytes // 4, dtype=np.uint32)


def bf16_checkpoint_f32(rng, nbytes: int) -> np.ndarray:
    """A float32 weight tensor holding bf16-rounded values (upcast bf16)."""
    w = (rng.standard_normal(nbytes // 4, dtype=np.float32) * 0.02).view(np.uint32)
    w = (w + np.uint32(0x7FFF) + ((w >> 16) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return w.view(np.float32)


def records_u32(rng, nbytes: int) -> np.ndarray:
    """numeric(4) records: small counters with a slowly varying high half."""
    n = nbytes // 4
    hi = np.repeat(rng.integers(0, 64, n // 4096 + 1, dtype=np.uint32), 4096)[:n]
    return (hi << np.uint32(16)) | rng.integers(0, 1 << 12, n, dtype=np.uint32)


def float_planes_plan():
    """float_split -> transpose_split(mantissa) -> fse/huffman per plane."""
    from repro.core import GraphBuilder

    g = GraphBuilder(1)
    _signs, exp, man = g.add("float_split", g.input(0), fmt=2)
    g.add("fse", exp)
    for j, plane in enumerate(g.add("transpose_split", man, n_out=4)):
        g.add("huffman" if j % 2 else "fse", plane)
    return g.build("float_planes")


# -------------------------------------------------------------------- phases
def _nodes(sess) -> dict:
    return {by: dict(per) for by, per in sess.stats["nodes"].items()}


def session_phase(seed: int, size: int, chunked_size: int, chunk: int) -> dict:
    """-> per-backend node counts summed over every device session."""
    from repro.codecs import float32_profile
    from repro.core import CompressorSession, decompress, numeric, pipeline

    rng = np.random.default_rng(seed)
    offsets = offsets_u32(rng, size)
    ckpt = bf16_checkpoint_f32(rng, size)
    cases = [
        ("offsets/delta+transpose+huffman",
         pipeline("delta", "transpose", "huffman"), offsets,
         {"delta", "transpose", "huffman"}),
        ("offsets/delta+bitpack", pipeline("delta", "bitpack"), offsets,
         {"delta", "bitpack"}),
        ("offsets/fused_delta_bitpack", pipeline("fused_delta_bitpack"), offsets,
         {"fused_delta_bitpack"}),
        ("small_range/bitpack", pipeline("bitpack"),
         small_range_u32(rng, size), {"bitpack"}),
        ("bf16_f32/float32_profile", float32_profile(), ckpt, {"float_split"}),
        ("bf16_f32/float_planes", float_planes_plan(), ckpt,
         {"float_split", "transpose_split", "fse", "huffman"}),
        ("records/transpose", pipeline("transpose"),
         records_u32(rng, size), {"transpose"}),
    ]
    totals: dict = {}
    sessions = []
    for name, plan, arr, expect in cases:
        stream = numeric(arr)
        with CompressorSession(plan, backend="host") as host:
            t0 = time.perf_counter()
            want = host.compress(stream)
            t_host = time.perf_counter() - t0
        dev = CompressorSession(plan, backend="device")
        sessions.append(dev)
        t0 = time.perf_counter()
        first = dev.compress(stream)
        t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        frame = dev.compress(stream)
        t_steady = time.perf_counter() - t0
        check(first == want and frame == want,
              f"{name}: device frame differs from the host frame")
        (back,) = decompress(frame)
        check(back.content_bytes() == stream.content_bytes(),
              f"{name}: decode does not return the input")
        nodes = _nodes(dev)
        for codec in expect:
            check(nodes.get("device", {}).get(codec, 0) > 0,
                  f"{name}: {codec} never encoded on the device ({nodes})")
            check(nodes.get("host", {}).get(codec, 0) == 0,
                  f"{name}: {codec} ran on the host ({nodes})")
        print(f"session {name}: {stream.nbytes} B -> {len(frame)} B;"
              f" device first call {t_first:.3f} s (compile included),"
              f" steady {t_steady:.3f} s; host {t_host:.3f} s; nodes {nodes}")

    # the chunked run reuses the delta+transpose+huffman session (every node
    # of each chunk stays on the device): thread pool + prefetch
    name, plan, _, expect = cases[0]
    dev = sessions[0]
    before = dev.stats["chunks"]
    big = numeric(offsets_u32(rng, chunked_size))
    with CompressorSession(plan, backend="host") as host:
        want = host.compress(big, chunk_bytes=chunk)
    t0 = time.perf_counter()
    frame = dev.compress(big, chunk_bytes=chunk)
    t_chunked = time.perf_counter() - t0
    n_chunks = dev.stats["chunks"] - before
    check(frame == want, "chunked: device container differs from the host one")
    (back,) = decompress(frame)
    check(back.content_bytes() == big.content_bytes(),
          "chunked: decode does not return the input")
    check(n_chunks == -(-big.nbytes // chunk), f"chunked: {n_chunks} chunks")
    nodes = _nodes(dev)
    for codec in expect:
        check(nodes.get("host", {}).get(codec, 0) == 0,
              f"chunked: {codec} ran on the host ({nodes})")
    print(f"session chunked {name}: {big.nbytes} B in {n_chunks} chunks"
          f" -> {len(frame)} B in {t_chunked:.3f} s;"
          f" prefetch hits {dev.stats['prefetch_hits']},"
          f" max in flight {dev.stats['max_inflight']}; nodes {nodes}")

    for sess in sessions:
        for by, per in _nodes(sess).items():
            for codec, k in per.items():
                totals.setdefault(by, {}).setdefault(codec, 0)
                totals[by][codec] += k
        sess.close()
    device = totals.get("device", {})
    missing = [t for t in TWINS if device.get(t, 0) == 0]
    check(not missing, f"device twins never counted on the device: {missing}")
    return totals


def service_phase(seed: int, sizes_mib, chunk: int) -> None:
    from repro.core import Compressor, pipeline, serial
    from repro.service import CompressionServer, PlanRegistry, ServiceClient

    rng = np.random.default_rng(seed + 1)
    registry = PlanRegistry()
    u32 = ("interpret_numeric", {"width": 4})
    plans = {
        "offsets": pipeline(u32, "delta", "bitpack"),
        "offsets_huffman": pipeline(u32, "delta", "transpose", "huffman"),
    }
    for plan_id, plan in plans.items():
        registry.register_compressor(Compressor(plan), plan_id)
    payloads = []
    for i, mib in enumerate(sizes_mib):
        plan_id = "offsets" if i % 2 == 0 else "offsets_huffman"
        data = offsets_u32(rng, mib * MIB).tobytes()
        want = Compressor(plans[plan_id]).compress(serial(data), chunk_bytes=chunk)
        payloads.append((plan_id, data, want))
    with tempfile.TemporaryDirectory(prefix="ozs") as tmp:
        server = CompressionServer(
            registry, socket_path=str(Path(tmp) / "s.sock"), backend="device",
            request_timeout=600.0,
        )
        with server, ServiceClient(server.address, timeout=600.0) as client:
            t0 = time.perf_counter()
            frames = []
            for plan_id, data, want in payloads:
                frame, _ = client.compress_bytes(data, plan_id, chunk_bytes=chunk)
                check(frame == want, f"service: {plan_id} frame differs offline")
                frames.append(frame)
            t_comp = time.perf_counter() - t0
            t0 = time.perf_counter()
            for (plan_id, data, _), frame in zip(payloads, frames):
                back, _ = client.decompress_bytes(frame)
                check(back == data, f"service: {plan_id} decode differs")
            t_dec = time.perf_counter() - t0
            st = server.stats()
    health = st["backend_health"].get("device", {})
    check(health.get("successes", 0) > 0, f"service: device never ran {health}")
    check(health.get("failures", 0) == 0 and health.get("failovers", 0) == 0
          and not health.get("quarantined"), f"service: device faulted {health}")
    tripped = {k: v for k, v in st["quarantine"].items()
               if v["quarantined"] or v["trips"]}
    check(not tripped, f"service: plans quarantined {tripped}")
    check(st["requests"]["compress"] == len(payloads)
          and st["requests"]["decompress"] == len(payloads),
          f"service: request counts {st['requests']}")
    print(f"service: {len(payloads)} compress in {t_comp:.3f} s,"
          f" {len(payloads)} decompress in {t_dec:.3f} s;"
          f" backend_health {health}")


# ---------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="input data seed")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's first device is {dev.platform!r}")
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        fail(f"no repro package under {src}")
    sys.path.insert(0, str(src))
    from repro.device import use_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)};"
          f" compile cache {use_compile_cache()}")
    t0 = time.perf_counter()
    totals = session_phase(args.seed, SIZE, CHUNKED_SIZE, CHUNK)
    print(f"session phase passed in {time.perf_counter() - t0:.3f} s;"
          f" nodes by encoding backend {totals}")
    t0 = time.perf_counter()
    service_phase(args.seed, SERVICE_MIB, CHUNK)
    print(f"service phase passed in {time.perf_counter() - t0:.3f} s")
    mem = dev.memory_stats() or {}
    print(f"peak device memory: {mem.get('peak_bytes_in_use')} B")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
