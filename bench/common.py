"""What the harness's parts share: the unit of work and the loaders that find
a configuration, a traffic mix or a metric reader by its name."""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MIB = 1 << 20


@dataclass
class Item:
    """One compress call's input: a column chunk, a checkpoint leaf, a page.

    ``plan`` names the compressor that takes it (one session per plan);
    ``profile`` is the program's named profile that plan is built from;
    ``chunk_bytes`` is 0 for one unchunked frame."""

    name: str
    plan: str
    profile: str
    data: np.ndarray
    chunk_bytes: int = 0

    @property
    def nbytes(self) -> int:
        return int(self.data.nbytes)


def manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``: a configuration or a traffic mix."""
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, imported by path (names may hold '-' and '.')."""
    path = BENCH / kind / f"{name}.py"
    mod_name = "bench_" + re.sub(r"[^A-Za-z0-9_]", "_", f"{kind}_{name}")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
