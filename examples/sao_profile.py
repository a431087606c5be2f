#!/usr/bin/env python
"""The paper's §IV worked example: a hand-built compressor for the SAO star
catalogue, reproducing the Table I comparison.

    PYTHONPATH=src python examples/sao_profile.py
"""
import sys
import time
import zlib
import lzma
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np  # noqa: E402

from repro.codecs import sao_profile  # noqa: E402
from repro.codecs.profiles import SAO_HEADER_BYTES  # noqa: E402
from repro.core import Compressor  # noqa: E402


def make_sao(n_records: int, seed: int = 0) -> bytes:
    """Synthetic star catalogue with the SAO record layout: sorted
    right-ascension f64, bounded declination f64, low-cardinality
    spectral/magnitude/motion fields (paper §IV)."""
    rng = np.random.default_rng(seed)
    rec = np.zeros(
        n_records,
        dtype=[("sra", "<f8"), ("sdec", "<f8"), ("is", "<u2"), ("mag", "<i2"),
               ("xrpm", "<f4"), ("xdpm", "<f4")],
    )
    zipf = np.arange(1, 65, dtype=np.float64) ** -1.3
    rec["sra"] = np.sort(rng.uniform(0, 2 * np.pi, n_records))
    rec["sdec"] = rng.uniform(-np.pi / 2, np.pi / 2, n_records)
    rec["is"] = rng.choice(64, n_records, p=zipf / zipf.sum())
    rec["mag"] = rng.choice(np.arange(-149, 1450, 10, dtype=np.int16), n_records)
    for f, k in (("xrpm", 997), ("xdpm", 1009)):
        grid = np.round(np.linspace(-0.5, 0.5, k), 5).astype(np.float32)
        rec[f] = rng.choice(grid, n_records)
    return b"\x00" * SAO_HEADER_BYTES + rec.tobytes()


data = make_sao(50_000)
print(f"SAO: {len(data)} bytes ({len(data)/(1<<20):.2f} MiB), 28-byte star records")

rows = []
for name, enc in [
    ("zlib-6", lambda d: zlib.compress(d, 6)),
    ("xz-9", lambda d: lzma.compress(d, preset=9)),
]:
    t0 = time.perf_counter()
    blob = enc(data)
    dt = time.perf_counter() - t0
    rows.append((name, len(blob), len(data) / len(blob), dt))

c = Compressor(sao_profile())
t0 = time.perf_counter()
frame = c.compress(data)
dt = time.perf_counter() - t0
assert c.roundtrip_check(data), "lossless check failed"
rows.append(("OpenZL (sao graph)", len(frame), len(data) / len(frame), dt))

print(f"{'compressor':22s} {'size':>10s} {'ratio':>7s} {'seconds':>8s}")
for name, size, ratio, dt in rows:
    print(f"{name:22s} {size:>10d} {ratio:>7.2f} {dt:>8.2f}")
print(
    "\npaper Table I (real SAO, C impl): zstd-3 1.31x | xz-9 1.64x | OpenZL 2.06x"
    "\nthe graph (field_split + delta/transpose/tokenize per field, §IV) wins on"
    "\nratio here too; absolute speeds differ (numpy host kernels vs optimized C)."
)
print(f"\nserialized compressor: {len(c.serialize())} bytes")
