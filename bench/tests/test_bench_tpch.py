"""The TPC-H Q6 cell: its generators against TPC-H's Clause 4.2.3, its op's
reference against a brute-force sum, its kernel roofline reader, and
``correct`` false for faults planted in the timed path."""

import types

import numpy as np
import pytest

from bench import generate, spec, traffic
from bench.tests.helpers import run_tiny

CELL = "tpch-q6-scan"
SEED = 2 ** 33 + 15                  # a seed past 32 bits
ROWS = 20_000


@pytest.fixture(scope="module")
def cfg():
    return {**generate.load_config(spec.config_file("tpch-lineitem")),
            "shards": 2, "rows_per_shard": ROWS}


@pytest.fixture(scope="module")
def table(cfg):
    return generate.generate_table(cfg, SEED)


def _tpch():
    return spec.plugin("gen", "tpch")


def test_config_lists_clause_1_4_in_order(cfg):
    assert [c["name"] for c in cfg["columns"]] == list(_tpch().COLUMNS)
    assert generate.num_rows(generate.load_config(
        spec.config_file("tpch-lineitem"))) == 16 * 393_216


def test_each_column_alone_is_bit_for_bit_the_table_s(cfg, table):
    names = [c["name"] for c in cfg["columns"]]
    for shard in range(2):
        whole = generate.generate_shard(cfg, SEED, shard)
        for i in reversed(range(len(names))):     # alone, in another order
            alone = generate.generate_column(cfg, i, SEED, shard)
            if isinstance(alone, np.ndarray):
                assert alone.dtype == whole[names[i]].dtype
                assert np.array_equal(alone, whole[names[i]]), names[i]
            else:
                assert alone == whole[names[i]], names[i]
    other = generate.generate_column(cfg, 1, SEED + 1, 0)
    assert not np.array_equal(other, table["l_partkey"][:ROWS])


def test_columns_follow_clause_4_2_3(table):
    t, g = table, _tpch()
    assert t["l_quantity"].min() >= 1 and t["l_quantity"].max() <= 50
    assert set(np.unique(t["l_discount"])) == set(range(11))
    assert set(np.unique(t["l_tax"])) == set(range(9))
    assert t["l_partkey"].min() >= 1 and t["l_partkey"].max() <= 200_000
    # P_RETAILPRICE in cents, times the quantity
    pk = t["l_partkey"].astype(np.int64)
    retail = 90_000 + (pk // 10) % 20_001 + 100 * (pk % 1000)
    assert np.array_equal(t["l_extendedprice"], t["l_quantity"] * retail)
    assert t["l_extendedprice"].max() < 2 ** 24
    # the supplier is one of the part's four (Clause 4.2.3's PS_SUPPKEY)
    s = 10_000
    four = np.stack([(pk + i * (s // 4 + (pk - 1) // s)) % s + 1
                     for i in range(4)])
    assert (four == t["l_suppkey"]).any(axis=0).all()
    # orders: 1..7 lines numbered from 1, sparse ascending keys
    key = t["l_orderkey"]
    assert np.all(np.diff(key) >= 0) and np.all(key & 31 < 8)
    new = np.concatenate([[True], key[1:] != key[:-1]])
    assert np.all(t["l_linenumber"][new] == 1)
    assert np.all(t["l_linenumber"][~new]
                  == t["l_linenumber"][np.flatnonzero(~new) - 1] + 1)
    assert t["l_linenumber"].max() == 7
    # dates and the flags they decide
    ship, commit = t["l_shipdate"], t["l_commitdate"]
    receipt = t["l_receiptdate"]
    assert ship.min() > g.STARTDATE and ship.max() <= g.ENDDATE - 151 + 121
    assert np.all((receipt - ship >= 1) & (receipt - ship <= 30))
    assert np.all((commit - ship >= 30 - 121) & (commit - ship <= 90 - 1))
    flag = np.array(t["l_returnflag"])
    assert np.all((flag == b"N") == (receipt > g.CURRENTDATE))
    assert set(flag[receipt <= g.CURRENTDATE]) == {b"R", b"A"}
    status = np.array(t["l_linestatus"])
    assert np.all((status == b"O") == (ship > g.CURRENTDATE))
    assert set(t["l_shipinstruct"]) == set(g.INSTRUCTIONS)
    assert set(t["l_shipmode"]) == set(g.MODES)
    lens = np.fromiter(map(len, t["l_comment"]), int)
    assert lens.min() == 10 and lens.max() == 43


def _plan():
    mix = traffic.load_mix(spec.traffic_file("tpch-q6"))
    return mix, traffic.plan(mix, SEED, 51.0)


def test_mix_is_q6_s_80_substitution_points(table):
    mix, plan = _plan()
    assert len(plan["specs"]) == 80 and plan["connections"] == 2
    years = {tuple(s["where"][0][2]) for s in plan["specs"]}
    assert years == {(8401, 8766), (8766, 9131), (9131, 9496),
                     (9496, 9862), (9862, 10227)}    # 1993..1998-01-01
    op = spec.plugin("ops", "sum_product")
    sel = []
    for s in plan["specs"]:
        mask = np.ones(len(table["l_quantity"]), bool)
        for col, cmp, v in op._terms(s):
            mask &= cmp(table[col], v)
        sel.append(mask.mean())
    assert 0.014 < min(sel) and max(sel) < 0.023


def test_expected_equals_a_brute_force_sum(table):
    _, plan = _plan()
    op = spec.plugin("ops", "sum_product")
    cols = ("l_extendedprice", "l_discount", "l_shipdate", "l_quantity")
    rows = list(zip(*(table[c].tolist() for c in cols)))
    for s in plan["specs"][:6]:
        (d0, d1), (lo, hi), q = (w[2] for w in s["where"])
        total = count = 0
        for price, disc, ship, qty in rows:
            if d0 <= ship < d1 and lo <= disc <= hi and qty < q:
                total += price * disc
                count += 1
        assert op.expected(s, table, {}) == op.digest(total, count)
        assert total > 2 ** 31           # past int32, kept exact


def test_roofline_reader_counts_the_kernel_s_bytes():
    from bench import roofline_sum_product as rsp
    reader = spec.plugin("metrics", "aggregate_kernel_roofline.scan")
    mix, _ = _plan()
    cfg = generate.load_config(spec.config_file("tpch-lineitem"))
    assert reader.kernel_shapes(cfg, mix) == {(4, 65_536)}
    assert rsp.sum_product_bytes(4, 65_536) == \
        4 * 4 * 65_536 + 4 * 3 * 1024 + 4 * 128 * 9
    mod = [["jit_sum_product_pallas(17)", 1000.0 + 10_000 * i, 2000.0]
           for i in range(5)]
    tr = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": mod},
        {"name": "XLA Modules", "events": mod}]}]}
    run = types.SimpleNamespace(cfg=cfg, mix=mix, trace=tr,
                                trace_window_ns=(0.0, 1e9),
                                device_kind="TPU v5 lite")
    want = 100 * rsp.sum_product_bytes(4, 65_536) / 819e9 / 2e-6
    assert reader.read(run) == pytest.approx(want)
    assert 0 < want <= 100
    run.trace = None
    assert reader.read(run) is None


def test_tiny_cell_is_correct_and_reads_its_metrics():
    res = run_tiny(CELL, trace=1)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert {"aggregate_ms.scan", "decode_ms.scan", "bytes_read_per_row.scan",
            "device_idle.scan"} <= set(m)
    # no kernel module on the CPU: the share reads nothing here
    assert "aggregate_kernel_roofline.scan" not in m


def _float32_sum(pred, tbl, factors, bounds, use_kernel, rows_mask=None):
    from repro.scan.predicate import evaluate
    a, b = factors
    mask = evaluate(pred, tbl)
    prod = tbl[a][mask].astype(np.float32) * tbl[b][mask].astype(np.float32)
    return int(prod.sum(dtype=np.float32)), int(mask.sum())


def test_float32_accumulation_in_the_timed_path_is_not_correct(monkeypatch):
    from repro.dataset import executor
    monkeypatch.setattr(executor, "eval_sum_product", _float32_sum)
    res = run_tiny(CELL)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_one_group_left_out_in_the_timed_path_is_not_correct(monkeypatch):
    from repro.dataset import executor
    real = executor.aggregate_group

    def leave_one_out(reader, group, **kw):
        if group == 0 and reader.path.endswith("part-000.bln"):
            return 0, 0
        return real(reader, group, **kw)

    monkeypatch.setattr(executor, "aggregate_group", leave_one_out)
    res = run_tiny(CELL)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0
