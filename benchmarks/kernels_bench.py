"""Pallas kernel micro-benchmarks: us/call (interpret mode off the TPU) for the
kernel and its jnp oracle, plus the fused-vs-unfused HBM-traffic model for
K1 (numbers feed EXPERIMENTS.md §Perf/K1)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

N = 1 << 18


def _time(fn, *args, reps=5):
    fn(*args).block_until_ready() if hasattr(fn(*args), "block_until_ready") else None
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run(print_rows: bool = True):
    rng = np.random.default_rng(0)
    x = jnp.asarray(np.cumsum(rng.integers(0, 200, N)).astype(np.uint32))
    planes = jnp.asarray(rng.integers(0, 256, (N, 4)), jnp.uint8)
    rows = []
    rows.append(("delta_encode_pallas", _time(lambda a: ops.delta_encode(a, use_pallas=True), x)))
    rows.append(("delta_encode_ref", _time(lambda a: ops.delta_encode(a, use_pallas=False), x)))
    rows.append(("delta_decode_pallas", _time(lambda a: ops.delta_decode(a, use_pallas=True), x)))
    rows.append(("byteshuffle_pallas", _time(lambda a: ops.byteshuffle(a, use_pallas=True), planes)))
    rows.append(("bitpack8_pallas", _time(lambda a: ops.bitpack(a & 0xFF, 8, use_pallas=True), x)))
    rows.append(("histogram_exact", _time(lambda a: ops.histogram_exact(a), x)))
    rows.append(("float_split_pallas", _time(lambda a: ops.float_split(a, 8, 23, use_pallas=True)[2], x)))
    rows.append(("fused_delta_bitpack", _time(lambda a: ops.fused_delta_bitpack(a, 8, use_pallas=True), x)))

    # K1 HBM-traffic model (bytes moved per element, bits=8):
    #   unfused: delta(read 4 + write 4) + pack(read 4 + write 1) = 13 B/elt
    #   fused:   read 4 (+ 1/512 tail reread) + write 1          =  5 B/elt
    unfused = 13.0
    fused = 5.0
    rows.append(("k1_traffic_model", 0.0))
    if print_rows:
        for name, us in rows[:-1]:
            print(f"kernels/{name},{us:.1f},n={N}")
        print(
            f"kernels/k1_traffic_model,0.0,"
            f"unfused_B_per_elt={unfused};fused_B_per_elt={fused};"
            f"traffic_cut={unfused/fused:.2f}x"
        )
    return rows


def main():
    run()


if __name__ == "__main__":
    main()
