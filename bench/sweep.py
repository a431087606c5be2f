"""Sweep the offered rate of an open-loop cell to find its knee: the highest
rate at which the backlog does not grow over a window.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates <r> [<r> ...]

One process on the chip: the cell's data is made once and every page any
rate will send is warmed, then one window per rate, lowest first, each on a
fresh server.
A window's backlog grows when the median time of its last quarter of
requests exceeds that of its first quarter by half, plus 50 ms; a shed or
failed request also marks the rate as past the knee.  One line per rate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def summary(rate: float, calls) -> dict:
    from bench.measure import percentile

    ms = [(c.end - c.due) * 1e3 for c in calls if c.error is None]
    q = max(1, len(calls) // 4)
    first = [(c.end - c.due) * 1e3 for c in calls[:q] if c.error is None]
    last = [(c.end - c.due) * 1e3 for c in calls[-q:] if c.error is None]
    failed = sum(c.error is not None for c in calls)
    grows = bool(failed) or not first or not last or (
        percentile(last, 50) > 1.5 * percentile(first, 50) + 50.0)
    return {
        "rate_per_s": rate, "offered": len(calls), "failed": failed,
        "p50_ms": percentile(ms, 50) if ms else None,
        "p95_ms": percentile(ms, 95) if ms else None,
        "first_quarter_p50_ms": percentile(first, 50) if first else None,
        "last_quarter_p50_ms": percentile(last, 50) if last else None,
        "backlog_grows": grows,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    from repro.codecs.profiles import resolve_profile_spec
    from repro.device import use_compile_cache

    from bench import load
    from bench.common import find, load_json, load_module, manifest
    from bench.harness import Window

    use_compile_cache()
    wl = find(manifest()["workloads"], args.workload, "workload")
    cfg, mix = load_json("configs", wl["config"]), load_json("traffic", wl["traffic"])
    items = load_module("configs", wl["config"]).items(cfg, args.seed)
    frames = load.Frames()
    with load.ServeRig(mix, items, resolve_profile_spec) as rig:
        plans = {r: load.schedule(dict(mix, rate_per_s=r), rig.pool, args.seconds, args.seed)
                 for r in args.rates}
        t0 = time.perf_counter()
        rig.warm([k for reqs in plans.values() for _, k in reqs], frames)
        print(f"warm-up {time.perf_counter() - t0:.3f} s", flush=True)
    for rate in sorted(args.rates):
        # a fresh server and client pool per rate: requests still in flight
        # past one window's grace period never share a connection with the
        # next window's (plans stay resolved and programs compiled)
        with load.ServeRig(mix, items, resolve_profile_spec) as rig:
            calls = rig.run(plans[rate], frames, Window(None))
        print("SWEEP " + json.dumps(summary(rate, calls)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
