"""BENCHMARK.json against the benchmark's contract: every name resolves to
its files, names and units use the allowed characters, every per-layer metric
moves an end-to-end metric its cells report, and the runner refuses a CPU."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench.common import BENCH, ROOT, load_module, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.fixture(scope="module")
def man():
    return manifest()


def _reporting(man, metric):
    cells = [w["name"] for w in man["workloads"]]
    return metric.get("workloads", cells)


def test_top_level_and_entry_keys(man):
    assert set(man) == KEYS
    for section, keys in ENTRY_KEYS.items():
        for entry in man[section]:
            extra = set(entry) - keys - ({"workloads"} if section in ("end_to_end", "per_layer") else set())
            assert keys <= set(entry) and not extra, (section, entry["name"])
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_command_and_paths(man):
    assert man["paths"] == ["bench"] and (ROOT / "bench").is_dir()
    assert 1 <= len(man["command"]) <= 32
    assert (ROOT / man["command"][1]).is_file()
    for word in man["command"]:
        assert not word.startswith("/") and ".." not in word
    assert man["command"][1].startswith("bench/")


def test_every_name_resolves_to_its_files(man):
    cfgs = {c["name"]: c for c in man["configs"]}
    for c in man["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert callable(load_module("configs", c["name"]).items)
        assert callable(load_module("configs", c["name"]).control)
    for w in man["workloads"]:
        assert w["config"] in cfgs
        mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert mix["loop"] in ("closed", "open")
    for m in man["end_to_end"] + man["per_layer"]:
        assert callable(load_module("metrics", m["name"]).read), m["name"]
    used = {w["config"] for w in man["workloads"]}
    assert used == set(cfgs)


def test_names_units_and_text_fields(man):
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in man[section]:
            assert NAME.match(e["name"]), e["name"]
            names.append((section, e["name"]))
    assert len(names) == len(set(names))
    metric_names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for w in man["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in man["configs"]:
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
    for m in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for e in man["configs"] + man["workloads"] + man["per_layer"]:
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]


def test_bounds_sources_and_run_length(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in man["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert "bound" not in m
    seconds = man["run_seconds"]
    assert isinstance(seconds, int) and 1 <= seconds <= 51
    # a full check of 24 cells has to fit the check's time
    assert (2 + 14 * 24) * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_every_per_layer_metric_moves_a_metric_its_cells_report(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    for m in man["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell in _reporting(man, m):
            assert cell in _reporting(man, e2e[m["moves"]]), (m["name"], cell)
    layers = {}
    for m in man["per_layer"]:
        layers.setdefault(m["layer"], []).append(m["name"])
    assert set(layers) <= {"service", "session", "execute", "jit and compile", "kernels", "device"}


def test_every_cell_reports_setup_another_metric_and_a_layer(man):
    for w in man["workloads"]:
        e2e = [m["name"] for m in man["end_to_end"] if w["name"] in _reporting(man, m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in man["per_layer"] if w["name"] in _reporting(man, m)]
        assert layer, w["name"]


def test_four_chip_cells_stay_within_half(man):
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert four <= max(1, len(man["workloads"]) // 2)


def test_run_exits_nonzero_and_prints_no_result_without_a_tpu(man):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = man["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", str(2**31 + 7),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "TPU" in proc.stderr
