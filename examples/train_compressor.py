#!/usr/bin/env python
"""End-to-end compressor training (the paper's §VI-C zli-train workflow):
parse -> cluster -> parallel NSGA-II backend search -> Pareto tradeoff
points -> serialized deployable compressors.

Candidate evaluation fans out over a session-backed worker pool
(``workers=``); training is deterministic — the same seed yields
byte-identical plans for any worker count.  The shell equivalent is
``python -m repro train SAMPLES... --out plan.ozp``.

    PYTHONPATH=src python examples/train_compressor.py
"""
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.core import Compressor, numeric  # noqa: E402
from repro.training import MultiStreamFrontend, train  # noqa: E402


def make_tlc_columns(n_rows: int, seed: int):
    """Taxi trips: near-sorted pickup times, quantized fares/distances,
    low-cardinality location/vendor/passenger fields."""
    rng = np.random.default_rng(seed)
    zipf = np.arange(1, 266, dtype=np.float64) ** -1.1
    zipf /= zipf.sum()
    pickup = np.sort(
        1_735_000_000 + (rng.pareto(2.0, n_rows) * 5e6).astype(np.int64) % 7_800_000
    )
    dropoff = pickup + rng.lognormal(6.2, 0.8, n_rows).astype(np.int32)
    dist = np.round(rng.lognormal(0.8, 0.9, n_rows), 2)
    fare = np.round(3.0 + dist * 2.5 + rng.normal(0, 1, n_rows).clip(0), 2)
    tip = np.round(fare * rng.choice([0, 0.1, 0.15, 0.2, 0.25], n_rows), 2)
    loc_p = rng.choice(265, n_rows, p=zipf).astype(np.uint16)
    loc_d = rng.choice(265, n_rows, p=zipf).astype(np.uint16)
    vendor = rng.choice(3, n_rows).astype(np.uint8)
    passengers = rng.choice([1, 1, 1, 2, 2, 3, 5], n_rows).astype(np.uint8)
    return [
        numeric(c)
        for c in (pickup, dropoff, dist, fare, tip, loc_p, loc_d, vendor, passengers)
    ]


# taxi-trip-like columnar data (paper's TLC dataset family)
train_cols = make_tlc_columns(20_000, seed=1)
test_cols = make_tlc_columns(60_000, seed=2)
raw = sum(s.nbytes for s in test_cols)
print(f"columns: {len(train_cols)}, test data: {raw/(1<<20):.2f} MiB")

t0 = time.time()
tc = train(
    [train_cols],
    MultiStreamFrontend(k=len(train_cols)),
    pop_size=12,
    generations=4,
    seed=0,
    workers=os.cpu_count(),
    verbose=True,
)
print(f"\ntraining took {time.time()-t0:.1f}s; stats: "
      f"{tc.stats['train_speed_mib_min']:.2f} MiB/min, "
      f"{int(tc.stats['n_clusters'])} clusters from {int(tc.stats['n_streams'])} streams, "
      f"{int(tc.stats['evaluations'])} candidate evals on {int(tc.stats['workers'])} workers "
      f"({tc.stats['eval_wall_seconds']:.1f}s encode time)")

print("\nPareto tradeoff points (size estimate vs encode-time estimate):")
for plan, sz, tm in tc.pareto_plans():
    print(f"  {sz:>10.0f} B  {tm*1e3:>8.2f} ms  ({len(plan.nodes)} codec nodes)")

best = Compressor(tc.best_ratio_plan())
frame = best.compress(list(test_cols))
assert best.roundtrip_check(list(test_cols))
import zlib

zsize = len(zlib.compress(b"".join(s.content_bytes() for s in test_cols), 6))
print(f"\nheld-out test: OpenZL {len(frame)} B ({raw/len(frame):.2f}x)"
      f" vs zlib-6 {zsize} B ({raw/zsize:.2f}x)")
blob = best.serialize()
print(f"deployable serialized compressor: {len(blob)} bytes")
clone = Compressor.deserialize(blob)
assert clone.roundtrip_check(list(test_cols))
print("deserialized clone verified lossless — ship it (paper §V-D)")
