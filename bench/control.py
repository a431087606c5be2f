"""The control of a cell's correctness check, run on the chip at the cell's size.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, in one process: one run of the cell in which the program is
handed the configuration's ``control`` copy of its data (the step below the
precision it states; see ``bench/configs/<config>.py``) while the check
still compares with the exact data.  Prints, per run, the numbers the check
compares; the control must fail at least one of them.  The sound readings
are those of the benchmark's own runs, which never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench.common import find, load_module, manifest
    from bench.harness import run_cell

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    from repro.device import use_compile_cache

    use_compile_cache()
    cell = find(manifest()["workloads"], args.workload, "workload")
    lower = load_module("configs", cell["config"]).control
    for seed in args.seeds:
        res = run_cell(cell, seed, args.seconds, False, time.perf_counter(),
                       degrade=lower)
        print("CONTROL " + json.dumps({
            "workload": args.workload, "seed": seed, "correct": res["correct"],
            "checks": {k: v["value"] for k, v in res["checks"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
