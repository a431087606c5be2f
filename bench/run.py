"""Run one benchmark cell on the accelerator this machine holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.  Otherwise it
generates the cell's data from the seed, warms up, measures one window of
``--seconds``, checks every frame the window produced against the plain
reference, and prints one JSON object as the last line of standard output;
the numbers compared are also the last lines of standard error.  With
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.common import find, manifest

    cell = find(manifest()["workloads"], args.workload, "workload")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"run: needs {cell['chips']} TPU chip(s); JAX found"
              f" {len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    from repro.device import use_compile_cache

    print(f"device: {devices[0].device_kind} x{len(devices)};"
          f" compile cache {use_compile_cache()}")
    from bench.harness import run_cell

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
