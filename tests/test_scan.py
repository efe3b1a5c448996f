"""Scan subsystem tests: stats roundtrip, zone-map pruning vs brute force,
stat-less backward compatibility, predicate algebra, loader/deletion
integration."""

import os

import numpy as np
import pytest

from repro.core import (BullionReader, BullionWriter, ColumnSpec, QuantMode,
                        QuantSpec, delete_where)
from repro.core.footer import FORMAT_V0, FORMAT_VERSION, Sec, read_footer
from repro.scan import (C, HAS_MINMAX, In, LIST_ELEMENTS, STAT_DTYPE,
                        conjunctive_ranges, evaluate, merge_records,
                        stats_record)


def _write(path, *, n=4000, rows_per_group=500, collect_stats=True, seed=0,
           page_rows=None):
    """Clustered synthetic table: sorted ids -> disjoint per-group ranges."""
    rng = np.random.default_rng(seed)
    schema = [
        ColumnSpec("id", "int64"),
        ColumnSpec("score", "float32"),
        ColumnSpec("cat", "int32"),
        ColumnSpec("seq", "list<int64>"),
        ColumnSpec("tag", "string"),
    ]
    table = {
        "id": np.arange(n, dtype=np.int64),
        "score": rng.random(n).astype(np.float32),
        "cat": rng.integers(0, 8, n).astype(np.int32),
        "seq": [rng.integers(0, 50, int(rng.integers(0, 6))).astype(np.int64)
                for _ in range(n)],
        "tag": [b"t%d" % (i % 13) for i in range(n)],
    }
    w = BullionWriter(path, schema, rows_per_group=rows_per_group,
                      collect_stats=collect_stats, page_rows=page_rows)
    w.write_table(table)
    w.close()
    return table


# ---------------------------------------------------------------------------
# stats roundtrip through the footer
# ---------------------------------------------------------------------------


def test_stats_roundtrip(tmp_path):
    # single-page layout: chunk stats == page stats, distinct counts exact
    path = str(tmp_path / "t.bln")
    table = _write(path, n=2000, rows_per_group=500, page_rows=500)
    fv, _ = read_footer(path)
    assert fv.format_version == FORMAT_VERSION
    assert fv.has_stats
    cs, ps = fv.chunk_stats(), fv.page_stats()
    assert cs is not None and ps is not None
    assert len(cs) == fv.n_groups * fv.n_cols
    assert len(ps) == fv.n_pages
    n_cols = fv.n_cols
    for g in range(fv.n_groups):
        lo, hi = g * 500, (g + 1) * 500
        rec = cs[g * n_cols + fv.column_index("id")]
        assert int(rec["flags"]) & HAS_MINMAX
        assert rec["min"] == lo and rec["max"] == hi - 1
        assert int(rec["distinct"]) == 500
        assert int(rec["null_count"]) == 0
        srec = cs[g * n_cols + fv.column_index("score")]
        chunk = table["score"][lo:hi]
        assert srec["min"] <= chunk.min() and srec["max"] >= chunk.max()
        # list stats describe the elements
        lrec = cs[g * n_cols + fv.column_index("seq")]
        assert int(lrec["flags"]) & LIST_ELEMENTS
        # string columns carry only a distinct estimate
        trec = cs[g * n_cols + fv.column_index("tag")]
        assert not (int(trec["flags"]) & HAS_MINMAX)
        assert int(trec["distinct"]) == 13
    # page stats agree with chunk stats (single-page layout: the degenerate
    # case where a chunk is exactly one page)
    for g in range(fv.n_groups):
        for c in range(n_cols):
            s, e = fv.chunk_pages(g, c)
            assert e - s == 1
            assert ps[s] == cs[g * n_cols + c]


def test_stats_nan_null_count(tmp_path):
    path = str(tmp_path / "nan.bln")
    x = np.array([1.0, np.nan, 3.0, np.nan, 2.0] * 10, np.float32)
    w = BullionWriter(path, [ColumnSpec("x", "float32")], rows_per_group=50)
    w.write_table({"x": x})
    w.close()
    fv, _ = read_footer(path)
    rec = fv.chunk_stats()[0]
    assert int(rec["null_count"]) == 20
    assert rec["min"] == 1.0 and rec["max"] == 3.0


def test_stats_quantized_column_matches_decoded_domain(tmp_path):
    """Zone maps of quantized columns must bound what dequant=True returns."""
    path = str(tmp_path / "q.bln")
    rng = np.random.default_rng(3)
    x = (rng.normal(size=1000) * 5).astype(np.float32)
    w = BullionWriter(path, [ColumnSpec("x", "float32",
                                        quant=QuantSpec(QuantMode.BF16))],
                      rows_per_group=250)
    w.write_table({"x": x})
    w.close()
    with BullionReader(path) as r:
        decoded = r.read_column("x")
        cs = r.footer.chunk_stats()
        for g in range(r.footer.n_groups):
            chunk = decoded[g * 250:(g + 1) * 250]
            assert cs[g]["min"] <= chunk.min()
            assert cs[g]["max"] >= chunk.max()


def test_merge_records():
    a = stats_record(np.arange(10))
    b = stats_record(np.arange(100, 110))
    m = merge_records([a, b])
    assert m["min"] == 0 and m["max"] == 109
    assert int(m["flags"]) & HAS_MINMAX


def test_int64_outer_bounds():
    """float64-unrepresentable int64 extremes must round *outward*."""
    v = np.array([2**63 - 1, 2**63 - 2, 0], np.int64)
    rec = stats_record(v)
    assert float(rec["max"]) >= float(2**63 - 1)
    assert float(rec["min"]) <= 0


# ---------------------------------------------------------------------------
# pruning correctness vs brute force
# ---------------------------------------------------------------------------


def _brute_force(table, pred):
    return np.flatnonzero(evaluate(pred, table))


@pytest.mark.parametrize("pred_fn,desc", [
    (lambda: C("id") == 1234, "one group survives"),
    (lambda: C("id") >= 10**9, "all groups pruned"),
    (lambda: C("id") >= 0, "no group pruned"),
    (lambda: (C("id") >= 900) & (C("id") < 1600), "range straddles groups"),
    (lambda: In("id", [5, 1999, 3999]), "IN across groups"),
    (lambda: (C("score") >= 0.99) | (C("id") < 10), "OR of ranges"),
    (lambda: ~(C("id") < 3500), "NOT pushes through zone maps"),
    (lambda: (C("cat") == 3) & (C("score") < 0.25), "unclustered conjunct"),
])
def test_pruned_scan_matches_brute_force(tmp_path, pred_fn, desc):
    path = str(tmp_path / "t.bln")
    table = _write(path)
    pred = pred_fn()
    scalar = {k: v for k, v in table.items() if isinstance(v, np.ndarray)}
    expect = _brute_force(scalar, pred)
    with BullionReader(path) as r:
        got = r.scanner.find_rows(pred)
        assert np.array_equal(np.sort(got), expect), desc
        plan = r.scanner.plan(pred)
        # pruning must never drop a group containing a match
        bounds = np.arange(0, 4001, 500)
        need = set(np.searchsorted(bounds, expect, side="right") - 1)
        assert need <= set(plan.groups), desc


def test_pruning_actually_prunes(tmp_path):
    path = str(tmp_path / "t.bln")
    _write(path)
    with BullionReader(path) as r:
        plan = r.scanner.plan(C("id") == 1234)
        assert plan.groups == [2]
        assert len(plan.pruned_groups) == 7
        assert plan.pages_pruned > 0
        empty = r.scanner.plan(C("id") >= 10**9)
        assert empty.groups == [] and empty.selectivity_bound == 0.0
        full = r.scanner.plan(C("id") >= 0)
        assert full.selectivity_bound == 1.0


def test_pruned_scan_reads_fewer_bytes(tmp_path):
    path = str(tmp_path / "t.bln")
    _write(path)
    with BullionReader(path) as r:
        r.find_rows("id", [1234])
        pruned = r.stats.bytes_read - r.stats.footer_bytes
    with BullionReader(path) as r:
        r.read_column("id", drop_deleted=False, dequant=False)
        full = r.stats.bytes_read - r.stats.footer_bytes
    assert pruned < full / 4


def test_scan_payload_columns_and_project_predicate(tmp_path):
    path = str(tmp_path / "t.bln")
    table = _write(path)
    pred = (C("id") >= 990) & (C("id") < 1010)
    with BullionReader(path) as r:
        batches = list(r.scanner.scan(pred, columns=["score", "tag", "id"]))
        ids = np.concatenate([b.row_ids for b in batches])
        scores = np.concatenate([b.table["score"] for b in batches])
        tags = [t for b in batches for t in b.table["tag"]]
        assert np.array_equal(ids, np.arange(990, 1010))
        assert np.allclose(scores, table["score"][990:1010], atol=1e-6)
        assert tags == table["tag"][990:1010]
        # project(predicate=...) yields the same filtered tables
        out = list(r.project(["score"], predicate=pred))
        got = np.concatenate([t["score"] for t in out])
        assert np.allclose(got, table["score"][990:1010], atol=1e-6)


def test_scan_kernel_path_matches_numpy(tmp_path):
    path = str(tmp_path / "t.bln")
    table = _write(path)
    pred = (C("score") >= 0.25) & (C("score") < 0.75)
    with BullionReader(path) as r:
        via_kernel = r.scanner.find_rows(pred, use_kernel=True)
        via_numpy = r.scanner.find_rows(pred, use_kernel=False)
        assert np.array_equal(via_kernel, via_numpy)
        assert np.array_equal(np.sort(via_kernel),
                              _brute_force({"score": table["score"]}, pred))
        # kernel path rejects non-range predicates instead of silently
        # falling back
        with pytest.raises(ValueError):
            r.scanner.find_rows(C("id") != 3, use_kernel=True)


def test_scan_kernel_strict_bound_on_exact_value(tmp_path):
    """x < v with v an actual stored float32 must exclude v on both paths."""
    path = str(tmp_path / "b.bln")
    x = np.linspace(0, 1, 1000).astype(np.float32)
    w = BullionWriter(path, [ColumnSpec("x", "float32")], rows_per_group=250)
    w.write_table({"x": x})
    w.close()
    v = float(x[500])
    with BullionReader(path) as r:
        got = r.scanner.find_rows(C("x") < v, use_kernel=True)
        assert np.array_equal(np.sort(got), np.flatnonzero(x < v))


def test_find_rows_with_deletion_vectors(tmp_path):
    from repro.core import Compliance, delete_rows
    path = str(tmp_path / "t.bln")
    _write(path)
    delete_rows(path, np.arange(1200, 1300), level=Compliance.LEVEL1)
    with BullionReader(path) as r:
        # raw row space: DV'd rows still reported (legacy find_rows contract)
        raw = r.scanner.find_rows((C("id") >= 1190) & (C("id") < 1310))
        assert np.array_equal(np.sort(raw), np.arange(1190, 1310))
        # visible row space: DV'd rows dropped, ids still global/raw
        vis = r.scanner.find_rows((C("id") >= 1190) & (C("id") < 1310),
                                  drop_deleted=True)
        assert np.array_equal(np.sort(vis), np.concatenate(
            [np.arange(1190, 1200), np.arange(1300, 1310)]))


# ---------------------------------------------------------------------------
# stat-less (v0) backward compatibility
# ---------------------------------------------------------------------------


def test_statless_file_backward_compat(tmp_path):
    path = str(tmp_path / "v0.bln")
    table = _write(path, collect_stats=False)
    fv, _ = read_footer(path)
    assert fv.format_version == FORMAT_V0
    assert not fv.has_stats
    assert fv.chunk_stats() is None and fv.page_stats() is None
    with BullionReader(path) as r:
        # every group survives planning (nothing to prune with)...
        plan = r.scanner.plan(C("id") == 1234)
        assert plan.groups == list(range(8)) and plan.pruned_groups == []
        # ...and results are still exact
        assert np.array_equal(r.find_rows("id", [1234]), [1234])
        got = r.scanner.find_rows((C("score") >= 0.9))
        assert np.array_equal(np.sort(got),
                              np.flatnonzero(table["score"] >= 0.9))


def test_statless_sections_absent(tmp_path):
    path = str(tmp_path / "v0.bln")
    _write(path, collect_stats=False)
    fv, _ = read_footer(path)
    assert not fv.has(Sec.PAGE_STATS) and not fv.has(Sec.CHUNK_STATS)


# ---------------------------------------------------------------------------
# predicate algebra / zone-map soundness
# ---------------------------------------------------------------------------


def test_predicate_evaluator_matches_numpy():
    rng = np.random.default_rng(1)
    tbl = {"a": rng.integers(-50, 50, 500), "b": rng.random(500)}
    pred = ((C("a") > -10) & (C("a") <= 10)) | ~(C("b") < 0.5) | In("a", [42])
    ref = (((tbl["a"] > -10) & (tbl["a"] <= 10)) | ~(tbl["b"] < 0.5)
           | np.isin(tbl["a"], [42]))
    assert np.array_equal(evaluate(pred, tbl), ref)


def test_predicate_rejects_list_columns():
    with pytest.raises(TypeError):
        evaluate(C("x") == 1, {"x": [np.arange(3)]})


def test_find_rows_on_string_column(tmp_path):
    """Legacy find_rows contract: membership probes on string columns keep
    working via the full-decode path (predicates are scalar-only)."""
    path = str(tmp_path / "t.bln")
    _write(path, n=1000, rows_per_group=250)
    with BullionReader(path) as r:
        got = r.find_rows("tag", [b"t3"])
        assert np.array_equal(got, np.arange(3, 1000, 13))


def test_list_column_predicate_raises_consistently(tmp_path):
    """Element-level zone maps must not prune list-column predicates into
    silently-empty results: in-range and out-of-range values both raise."""
    path = str(tmp_path / "t.bln")
    _write(path, n=1000, rows_per_group=250)
    with BullionReader(path) as r:
        with pytest.raises(TypeError):
            r.scanner.find_rows(C("seq") == 2)        # inside element range
        with pytest.raises(TypeError):
            r.scanner.find_rows(C("seq") == -5)       # outside element range


def test_conjunctive_ranges():
    r = conjunctive_ranges((C("a") >= 1) & (C("a") < 5) & (C("b") == 2.5))
    assert r["a"][0] == 1 and r["a"][1] < 5
    assert r["b"] == (2.5, 2.5)
    assert conjunctive_ranges(C("a") != 3) is None
    assert conjunctive_ranges((C("a") > 0) | (C("b") > 0)) is None


def test_conjunctive_ranges_integer_columns_are_exact():
    pred = ((C("q") < 24) & (C("d") >= 2) & (C("d") <= 4) & (C("s") > 8400)
            & (C("f") < 0.5))
    r = conjunctive_ranges(pred, int_columns={"q", "d", "s"})
    assert r["q"] == (-np.inf, 23) and r["d"] == (2, 4)
    assert r["s"] == (8401, np.inf)
    assert all(type(v) is int for v in (r["q"][1], *r["d"], r["s"][0]))
    assert r["f"] == conjunctive_ranges(C("f") < 0.5)["f"]
    # float literals close to the integers inside them
    r = conjunctive_ranges((C("q") < 23.5) & (C("q") >= -0.5)
                           & (C("p") <= 7.9) & (C("p") > 2.1),
                           int_columns={"q", "p"})
    assert r == {"q": (0, 23), "p": (3, 7)}
    # no integer satisfies these
    r = conjunctive_ranges((C("a") == 2.5) & (C("b") > float("nan"))
                           & (C("c") > 5) & (C("c") < 6),
                           int_columns={"a", "b", "c"})
    assert all(lo > hi for lo, hi in r.values())
    # literals past float64's integers stay exact
    big = 2 ** 60 + 1
    assert conjunctive_ranges(C("k") < big, int_columns={"k"}) == \
        {"k": (-np.inf, big - 1)}


def test_conjunctive_ranges_integer_bounds_agree_with_numpy():
    from repro.scan.predicate import Cmp
    rng = np.random.default_rng(11)
    x = np.arange(-40, 41, dtype=np.int32)
    for _ in range(300):
        op = ["<", "<=", ">", ">=", "=="][rng.integers(5)]
        v = float(rng.integers(-30, 30)) + [0.0, 0.5, -0.25][rng.integers(3)]
        pred = Cmp("x", op, v)
        lo, hi = conjunctive_ranges(pred, int_columns={"x"})["x"]
        assert np.array_equal((x >= lo) & (x <= hi),
                              pred.mask({"x": x})), (op, v)


def test_conjunctive_ranges_float_intervals_unchanged():
    """The float filter path's intervals are what they were: strict sides
    closed by one float64 ULP, whatever other columns are integer."""
    pred = ((C("a") >= 1) & (C("a") < 5) & (C("b") > 0.25)
            & (C("b") <= 0.75) & (C("c") == 2.5))
    want = {"a": (1.0, float(np.nextafter(5.0, -np.inf))),
            "b": (float(np.nextafter(0.25, np.inf)), 0.75),
            "c": (2.5, 2.5)}
    assert conjunctive_ranges(pred) == want
    assert conjunctive_ranges(pred, int_columns={"z"}) == want
    assert all(type(v) is float for lo_hi in want.values() for v in lo_hi)


def test_zone_map_soundness_fuzz():
    """maybe_any must never return False for a page that contains a match."""
    rng = np.random.default_rng(7)
    ops = ["==", "!=", "<", "<=", ">", ">="]
    from repro.scan.predicate import Cmp, Not, Or, And
    for trial in range(200):
        data = rng.integers(-20, 20, 50)
        stats = {"x": stats_record(data)}
        v = int(rng.integers(-25, 25))
        leaf = Cmp("x", ops[trial % 6], v)
        pred = [leaf, Not(leaf), And(leaf, Cmp("x", "<=", v + 3)),
                Or(leaf, Cmp("x", ">", v))][trial % 4]
        mask = evaluate(pred, {"x": data})
        if mask.any():
            assert pred.maybe_any(stats), (pred, v, data)


# ---------------------------------------------------------------------------
# loader + deletion integration
# ---------------------------------------------------------------------------


def test_loader_quality_threshold_stream(tmp_path):
    from repro.data.loader import BullionLoader
    from repro.data.synthetic import write_lm_corpus
    path = str(tmp_path / "lm.bln")
    write_lm_corpus(path, n_docs=256, doc_len=256, rows_per_group=32)
    thresh = 0.5
    ld = BullionLoader(path, batch_size=2, seq_len=64, column="tokens",
                       predicate=C("quality") >= thresh)
    # quality presorting (§2.5) makes the survivor set a prefix of the file
    assert ld._groups == list(range(len(ld._groups)))
    assert 0 < len(ld._groups) < ld.n_groups
    it = iter(ld)
    batch, cursor = next(it)
    assert batch.shape == (2, 65)
    ld.close()
    # the stream must only contain tokens from qualifying docs
    with BullionReader(path) as r:
        rows = r.scanner.find_rows(C("quality") >= thresh)
        tables = list(r.project(["tokens"], predicate=C("quality") >= thresh))
        n_docs = sum(len(t["tokens"]) for t in tables)
        assert n_docs == len(rows)


def test_loader_close_does_not_deadlock(tmp_path):
    """close() while the producer is blocked on a full prefetch queue."""
    from repro.data.loader import BullionLoader
    from repro.data.synthetic import write_lm_corpus
    path = str(tmp_path / "lm.bln")
    write_lm_corpus(path, n_docs=128, doc_len=256, rows_per_group=16)
    for trial in range(3):
        ld = BullionLoader(path, batch_size=1, seq_len=32, prefetch=1,
                           column="tokens")
        it = iter(ld)
        next(it)            # producer now racing to refill a tiny queue
        ld.close()          # must not deadlock
        assert ld._thread is None


def test_delete_where_prunes_and_erases(tmp_path):
    from repro.core import verify_deleted
    path = str(tmp_path / "t.bln")
    _write(path)
    st = delete_where(path, (C("id") >= 700) & (C("id") < 705))
    assert st.rows_deleted == 5
    assert verify_deleted(path, "id", np.arange(700, 705)) == \
        {"visible_rows": 0, "raw_occurrences": 0}
    # empty predicate delete is a no-op
    st2 = delete_where(path, C("id") == 10**9)
    assert st2.rows_deleted == 0


def test_raw_scan_row_ids_after_compact_delete(tmp_path):
    """RLE pages compact-delete (§2.1): the decoded raw array shrinks, so
    raw-space row ids must be re-aligned through the deletion vector —
    otherwise delete_where would erase the wrong rows."""
    from repro.core import Compliance, delete_rows
    path = str(tmp_path / "rle.bln")
    flags = np.repeat(np.arange(50), 20).astype(np.int64)  # RLE-friendly
    w = BullionWriter(path, [ColumnSpec("flag", "int64")], rows_per_group=500)
    w.write_table({"flag": flags})
    w.close()
    delete_rows(path, np.arange(100, 120), level=Compliance.LEVEL2)
    with BullionReader(path) as r:
        # rows 200-219 hold flag==10; compacted decode must not shift them
        raw = r.scanner.find_rows(C("flag") == 10)
        assert np.array_equal(raw, np.arange(200, 220))
        vis = r.scanner.find_rows(C("flag") == 10, drop_deleted=True)
        assert np.array_equal(vis, np.arange(200, 220))
        # the erased flag==5 rows are gone from both row spaces
        assert len(r.scanner.find_rows(C("flag") == 5, drop_deleted=True)) == 0
    # predicate delete after compaction erases the right rows
    st = delete_where(path, C("flag") == 10)
    assert st.rows_deleted == 20
    with BullionReader(path) as r:
        visible = r.read_column("flag")
        assert not (np.asarray(visible) == 10).any()
        assert (np.asarray(visible) == 11).sum() == 20  # neighbors untouched


def test_predicate_on_quantized_column_with_raw_payload(tmp_path):
    """Predicates always evaluate in the dequantized domain (the domain the
    zone maps describe) even when the caller materializes raw values."""
    path = str(tmp_path / "q.bln")
    from repro.core import affine_spec_for
    x = (np.arange(1000) / 1000).astype(np.float32)
    spec = affine_spec_for(x, QuantMode.UINT8_AFFINE)
    w = BullionWriter(path, [ColumnSpec("x", "float32", quant=spec)],
                      rows_per_group=250)
    w.write_table({"x": x})
    w.close()
    with BullionReader(path) as r:
        dq = r.read_column("x")                    # dequantized domain
        expect = np.flatnonzero(dq >= 0.5)
        got = r.scanner.find_rows(C("x") >= 0.5, drop_deleted=True)
        assert np.array_equal(np.sort(got), expect)
        # dequant=False payload: raw uint8 values, same row selection
        out = list(r.project(["x"], predicate=C("x") >= 0.5, dequant=False))
        raw = np.concatenate([t["x"] for t in out])
        assert raw.dtype == np.uint8 and len(raw) == len(expect)


def test_zone_maps_widened_after_physical_masking(tmp_path):
    """L2 masking overwrites victims in place (zero or an encoding-specific
    placeholder like the FOR base); zone maps are widened to include 0 and
    raw scans must keep matching what is physically on disk."""
    path = str(tmp_path / "t.bln")
    schema = [ColumnSpec("id", "int64")]
    w = BullionWriter(path, schema, rows_per_group=100)
    w.write_table({"id": np.arange(1000, 2000, dtype=np.int64)})
    w.close()
    delete_where(path, C("id") == 1550)
    with BullionReader(path) as r:
        raw = r.read_column("id", drop_deleted=False, dequant=False)
        masked_val = int(raw[550])
        assert masked_val != 1550            # physically erased
        # pruned raw scan still finds every physically-present occurrence
        got = r.scanner.find_rows(C("id") == masked_val)
        assert np.array_equal(np.sort(got), np.flatnonzero(raw == masked_val))
        cs = r.footer.chunk_stats()
        assert cs[5]["min"] == 0.0           # widened for the touched chunk
        assert cs[4]["min"] == 1400.0        # untouched groups unchanged
