"""TPC-H LINEITEM rows from the seed, by the distributions of TPC-H v3
section 4.2.3, stored as a Parquet writer would store its 15 non-comment
columns: one compress call per column chunk of each row group."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.common import Item

START = int(np.datetime64("1992-01-01", "D").astype(np.int64))
END = int(np.datetime64("1998-12-31", "D").astype(np.int64))
CURRENT = int(np.datetime64("1995-06-17", "D").astype(np.int64))
RETURN_A, RETURN_N, RETURN_R = 0, 1, 2  # dictionary codes, as listed
STATUS_F, STATUS_O = 0, 1


def lines_per_order(rng, n_orders: int, rows: int) -> np.ndarray:
    """1..7 lines per order, nudged on seeded orders to exactly ``rows``."""
    per = rng.integers(1, 8, n_orders)
    diff = rows - int(per.sum())
    while diff:
        movable = np.flatnonzero(per < 7) if diff > 0 else np.flatnonzero(per > 1)
        pick = rng.choice(movable, min(abs(diff), movable.size), replace=False)
        per[pick] += 1 if diff > 0 else -1
        diff = rows - int(per.sum())
    return per


def table(cfg: dict, seed: int) -> Dict[str, np.ndarray]:
    """Column name -> array in its stored type, one entry per row."""
    rng = np.random.default_rng(seed)
    sf = int(cfg["scale_factor"])
    n_orders, rows = int(cfg["orders"]), int(cfg["rows"])
    per = lines_per_order(rng, n_orders, rows)
    i = np.arange(1, n_orders + 1, dtype=np.int64)
    order_key = ((i >> 3) << 5) | (i & 7)  # of every 32 keys the first 8
    order_date = rng.integers(START, END - 151 + 1, n_orders)
    first = np.repeat(np.cumsum(per) - per, per)
    odate = np.repeat(order_date, per)

    partkey = rng.integers(1, sf * 200_000 + 1, rows)
    s = sf * 10_000
    supp_i = rng.integers(0, 4, rows)
    suppkey = (partkey + supp_i * (s // 4 + (partkey - 1) // s)) % s + 1
    quantity = rng.integers(1, 51, rows)
    retail_cents = 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1000)
    shipdate = odate + rng.integers(1, 122, rows)
    commitdate = odate + rng.integers(30, 91, rows)
    receiptdate = shipdate + rng.integers(1, 31, rows)
    a_or_r = np.where(rng.integers(0, 2, rows) == 0, RETURN_A, RETURN_R)
    cols = {
        "l_orderkey": np.repeat(order_key, per),
        "l_partkey": partkey,
        "l_suppkey": suppkey,
        "l_linenumber": np.arange(rows) - first + 1,
        "l_quantity": quantity * 100,
        "l_extendedprice": quantity * retail_cents,
        "l_discount": rng.integers(0, 11, rows),
        "l_tax": rng.integers(0, 9, rows),
        "l_returnflag": np.where(receiptdate <= CURRENT, a_or_r, RETURN_N),
        "l_linestatus": np.where(shipdate > CURRENT, STATUS_O, STATUS_F),
        "l_shipdate": shipdate,
        "l_commitdate": commitdate,
        "l_receiptdate": receiptdate,
        "l_shipinstruct": rng.integers(0, 4, rows),
        "l_shipmode": rng.integers(0, 7, rows),
    }
    return {name: np.ascontiguousarray(cols[name].astype(dtype))
            for name, dtype in cfg["columns"]}


def items(cfg: dict, seed: int) -> List[Item]:
    """Every column chunk of every row group, in file order."""
    cols = table(cfg, seed)
    rows, group = int(cfg["rows"]), int(cfg["row_group_rows"])
    out = []
    for g, lo in enumerate(range(0, rows, group)):
        for name, _ in cfg["columns"]:
            out.append(Item(
                f"rg{g}.{name}", f"{cfg['profile']}.{name}", cfg["profile"],
                cols[name][lo : lo + group], int(cfg["chunk_bytes"]),
            ))
    return out


def control(data: np.ndarray) -> np.ndarray:
    """The step below the stated precision: DECIMAL(15,2) columns (INT64
    cents) kept to one decimal place.  Other columns are left exact."""
    if data.dtype != np.int64:
        return data
    return (data + 5) // 10 * 10
