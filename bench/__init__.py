"""The on-chip benchmark: one cell harness driven by the data in BENCHMARK.json.

Run one cell from the root of a checkout:

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a configuration, a traffic mix or a per-layer metric needs sits in
files of its own (``bench/configs/``, ``bench/traffic/``, ``bench/metrics/``)
that the harness finds by the names ``BENCHMARK.json`` gives them.
"""
