"""The configurations' generators: the same seed gives the same data, another
seed other data, and the LINEITEM rows keep TPC-H v3 section 4.2.3's
domains and relations."""
import numpy as np
import pytest

from bench.common import load_json, load_module

SEED = 2**31 + 4242  # seeds run past 32 signed bits


def small_lineitem():
    cfg = load_json("configs", "tpch-lineitem-sf1")
    cfg.update(orders=6000, rows=24011, row_group_rows=10000)
    return cfg


def small_olmoe():
    cfg = load_json("configs", "olmoe-1b-7b-ckpt")
    cfg.update(hidden_size=128, intermediate_size=64, num_experts=16, chunk_bytes=1 << 14)
    return cfg


@pytest.fixture(scope="module")
def lineitem():
    return load_module("configs", "tpch-lineitem-sf1")


@pytest.fixture(scope="module")
def olmoe():
    return load_module("configs", "olmoe-1b-7b-ckpt")


def test_lineitem_is_a_function_of_the_seed(lineitem):
    cfg = small_lineitem()
    a, b, c = (lineitem.table(cfg, s) for s in (SEED, SEED, SEED + 1))
    assert list(a) == [n for n, _ in cfg["columns"]]
    for name, dtype in cfg["columns"]:
        assert a[name].dtype == np.dtype(dtype) and a[name].size == cfg["rows"]
        np.testing.assert_array_equal(a[name], b[name])
    assert any(not np.array_equal(a[n], c[n]) for n in a)


def test_lineitem_items_cut_row_groups_in_file_order(lineitem):
    cfg = small_lineitem()
    items = lineitem.items(cfg, SEED)
    assert len(items) == 3 * 15
    assert [it.data.size for it in items[::15]] == [10000, 10000, 4011]
    assert sum(it.nbytes for it in items) == 64 * cfg["rows"]
    assert {it.plan for it in items} == {f"numeric.{n}" for n, _ in cfg["columns"]}


def test_lineitem_keeps_the_specification(lineitem):
    cfg = small_lineitem()
    t = lineitem.table(cfg, SEED)
    ok = t["l_orderkey"]
    assert (np.diff(ok) >= 0).all() and ((ok & 31) < 8).all() and ok.min() >= 1
    starts = np.r_[0, np.flatnonzero(np.diff(ok)) + 1]
    per = np.diff(np.r_[starts, ok.size])
    assert per.min() >= 1 and per.max() <= 7 and starts.size <= cfg["orders"]
    ln = t["l_linenumber"]
    assert (ln[starts] == 1).all() and (ln >= 1).all() and (ln <= 7).all()
    pk, sk = t["l_partkey"].astype(np.int64), t["l_suppkey"].astype(np.int64)
    assert pk.min() >= 1 and pk.max() <= 200_000 and sk.min() >= 1 and sk.max() <= 10_000
    s = 10_000
    cands = np.stack([(pk + i * (s // 4 + (pk - 1) // s)) % s + 1 for i in range(4)])
    assert (cands == sk).any(axis=0).all()
    q = t["l_quantity"]
    assert (q % 100 == 0).all() and q.min() >= 100 and q.max() <= 5000
    retail = 90_000 + (pk // 10) % 20_001 + 100 * (pk % 1000)
    np.testing.assert_array_equal(t["l_extendedprice"], q // 100 * retail)
    assert t["l_discount"].min() >= 0 and t["l_discount"].max() <= 10
    assert t["l_tax"].min() >= 0 and t["l_tax"].max() <= 8
    ship, commit, receipt = (t[n].astype(np.int64) for n in
                             ("l_shipdate", "l_commitdate", "l_receiptdate"))
    assert ((receipt - ship >= 1) & (receipt - ship <= 30)).all()
    # ship and commit both count from the order date: 1..121 and 30..90 days
    assert ((commit - ship >= 30 - 121) & (commit - ship <= 90 - 1)).all()
    assert ship.min() >= lineitem.START + 1 and ship.max() <= lineitem.END - 151 + 121
    late = receipt > lineitem.CURRENT
    rf = t["l_returnflag"]
    assert (rf[late] == lineitem.RETURN_N).all()
    assert np.isin(rf[~late], [lineitem.RETURN_A, lineitem.RETURN_R]).all()
    ls = t["l_linestatus"]
    np.testing.assert_array_equal(ls == lineitem.STATUS_O, ship > lineitem.CURRENT)
    assert t["l_shipinstruct"].max() <= 3 and t["l_shipmode"].max() <= 6


def test_lineitem_control_drops_one_decimal_place(lineitem):
    x = np.array([0, 4, 5, 1234, 10485], np.int64)
    np.testing.assert_array_equal(lineitem.control(x), [0, 0, 10, 1230, 10490])
    y = np.arange(5, dtype=np.int32)
    assert lineitem.control(y) is y


def test_olmoe_state_is_a_function_of_the_seed(olmoe):
    cfg = small_olmoe()
    a, b, c = (olmoe.items(cfg, s) for s in (SEED, SEED, SEED + 1))
    assert [i.name for i in a] == [i.name for i in b]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.data, y.data)
    assert any(not np.array_equal(x.data, z.data) for x, z in zip(a, c))


def test_olmoe_shard_shapes_and_layout(olmoe):
    cfg = small_olmoe()
    h, ff, e, k = 128, 64, 16, cfg["deployment"]["chips_per_layer"]
    items = olmoe.items(cfg, SEED)
    n_params = sum(int(np.prod(s)) for _, s in olmoe.leaves(cfg))
    assert n_params == 4 * (h // k) * h + 4 * (h // k) + (e // k) * h + 3 * (e // k) * ff * h
    assert len(items) == 4 * len(olmoe.leaves(cfg))
    assert sum(it.nbytes for it in items) == 14 * n_params
    params = items[: len(olmoe.leaves(cfg))]
    assert all(it.data.dtype == np.uint16 and it.profile == "bfloat16" for it in params)
    assert all(it.data.dtype == np.uint32 and it.profile == "float32" for it in items[len(params):])
    # the bf16 param is the fp32 master rounded to nearest even
    masters = {it.name[: -len(".master")]: it for it in items if it.name.endswith(".master")}
    for p in params:
        m = masters[p.name[: -len(".param")]]
        np.testing.assert_array_equal(olmoe.control(m.data) >> 16, p.data)
    sq = [it for it in items if it.name.endswith(".exp_avg_sq")]
    assert all((it.data >> 31 == 0).all() for it in sq)  # second moments are >= 0


def test_olmoe_full_size_is_one_chips_share_of_a_layer(olmoe):
    cfg = load_json("configs", "olmoe-1b-7b-ckpt")
    n_params = sum(int(np.prod(s)) for _, s in olmoe.leaves(cfg))
    assert n_params == 52_446_208
    assert 14 * n_params == 734_246_912
