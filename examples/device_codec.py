#!/usr/bin/env python
"""The device-side codec path: Pallas TPU kernels chained INSIDE jit —
float_split -> (exponent histogram for table stats) + fused delta+bitpack on
sorted index streams.  This is the layer that makes §VIII-style compression
run on the accelerator instead of the host.  On a TPU the kernels compile
to Mosaic; elsewhere the same calls run their jit'd jnp oracles.

    PYTHONPATH=src python examples/device_codec.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

rng = np.random.default_rng(0)

# ---- checkpoint-style payload: a bf16-ish f32 weight tensor ----------------
w = (rng.normal(size=(1 << 16,)) * 0.02).astype(np.float32)
u = jnp.asarray(w.view(np.uint32))

sign, exp, man = ops.float_split(u, 8, 23)  # one HBM pass, 3 planes
counts = ops.histogram_exact(exp)  # exact integer counts
probs = np.asarray(counts, np.float64)
probs = probs[probs > 0] / probs.sum()
H = float(-(probs * np.log2(probs)).sum())
print(f"float_split: sign/exp/mantissa planes on device")
print(f"exponent entropy: {H:.2f} bits/value (vs 8 raw) -> "
      f"{(8-H)/32*100:.1f}% of the f32 tensor is free to entropy coding")
back = ops.float_merge(sign, exp, man, 8, 23)
assert bool(jnp.all(back == u)), "bit-exact merge"
print("merge: bit-exact roundtrip OK")

# ---- offset-table payload: sorted indices, fused delta+bitpack -------------
offs = jnp.asarray(np.cumsum(rng.integers(0, 200, 1 << 16)).astype(np.uint32))
bits = 8
assert bool(ops.fused_delta_bitpack_fits(offs, bits))
packed = ops.fused_delta_bitpack(offs, bits)  # ONE pass vs two codecs
restored = ops.fused_delta_bitpack_decode(packed, bits, offs.shape[0])
assert bool(jnp.all(restored == offs))
print(f"fused delta+bitpack: {offs.nbytes} B -> {packed.nbytes} B "
      f"({offs.nbytes/packed.nbytes:.1f}x), single-pass, bit-exact")

# ---- byte-plane shuffle for struct data ------------------------------------
recs = jnp.asarray(rng.integers(0, 256, (1 << 14, 4)), jnp.uint8)
planes = ops.byteshuffle(recs)
assert bool(jnp.all(ops.byteunshuffle(planes) == recs))
print(f"byteshuffle: (n,4) records -> 4 byte planes, roundtrip OK")
print("\nall kernels ran under jit (Mosaic on TPU; jnp oracles elsewhere)")

# ---- the engine-level device backend ---------------------------------------
# The same kernels drive real compression: resolve once, execute per call
# with backend="device".  Each plan node runs as one wire node on either
# backend, so the device writes the host's frame byte for byte.
from repro.core import compress, decompress, numeric, pipeline
from repro.core.wire import is_container, read_frame

offsets = numeric(np.cumsum(rng.integers(0, 200, 1 << 16)).astype(np.uint32))
plan = pipeline("delta", "bitpack")
frame_host = compress(plan, offsets, backend="host")
frame_dev = compress(plan, offsets, backend="device")
assert frame_dev == frame_host, "device frame must equal the host frame"
_, _, nodes, _ = read_frame(frame_dev)
assert decompress(frame_dev)[0].content_bytes() == offsets.content_bytes()
print(f"\nengine backend=device: delta+bitpack as {len(nodes)} wire nodes "
      f"(codec ids {[n.codec_id for n in nodes]}), {offsets.nbytes} B -> "
      f"{len(frame_dev)} B, byte-identical to the host frame, "
      f"universal decode bit-exact")

chunked = compress(plan, offsets, chunk_bytes=1 << 16, backend="device")
assert is_container(chunked)
assert decompress(chunked)[0].content_bytes() == offsets.content_bytes()
print(f"chunked container frame: {len(chunked)} B across "
      f"{(offsets.nbytes + (1 << 16) - 1) >> 16} chunks, decodes bit-exact")
