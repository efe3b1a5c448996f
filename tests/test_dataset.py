"""Unified lazy Dataset API tests: plan round-trips over v0/v1 files with
pruning on/off, multi-file directory datasets, schema checking, context
managers, head/with_rows/count_rows terminals, pruned-byte accounting."""

import os

import numpy as np
import pytest

from repro.core import (BullionReader, BullionWriter, ColumnSpec, Compliance,
                        QuantMode, QuantSpec, delete_rows)
from repro.dataset import (Dataset, SchemaMismatchError, dataset, discover,
                           split_conjuncts)
from repro.scan import C, In


def _write(path, *, n=2000, rows_per_group=250, collect_stats=True, seed=0,
           id_base=0):
    rng = np.random.default_rng(seed)
    schema = [
        ColumnSpec("id", "int64"),
        ColumnSpec("score", "float32"),
        ColumnSpec("qx", "float32", quant=QuantSpec(QuantMode.BF16)),
        ColumnSpec("tag", "string"),
    ]
    table = {
        "id": np.arange(id_base, id_base + n, dtype=np.int64),
        "score": rng.random(n).astype(np.float32),
        "qx": rng.normal(size=n).astype(np.float32),
        "tag": [b"t%d" % (i % 7) for i in range(n)],
    }
    w = BullionWriter(path, schema, rows_per_group=rows_per_group,
                      collect_stats=collect_stats)
    w.write_table(table)
    w.close()
    return table


def _write_shards(d, n_shards=4, rows_each=1000, rows_per_group=250):
    os.makedirs(d, exist_ok=True)
    tables = []
    for s in range(n_shards):
        tables.append(_write(os.path.join(d, f"part-{s:04d}.bln"),
                             n=rows_each, rows_per_group=rows_per_group,
                             seed=s, id_base=s * rows_each))
    return tables


# ---------------------------------------------------------------------------
# plan round-trips: v0 vs v1, pruning on vs off, legacy vs Dataset
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("collect_stats", [True, False],
                         ids=["v1-pruned", "v0-unpruned"])
def test_plan_roundtrip_matches_brute_force(tmp_path, collect_stats):
    path = str(tmp_path / "t.bln")
    table = _write(path, collect_stats=collect_stats)
    pred = (C("id") >= 300) & (C("id") < 900) & (C("score") < 0.5)
    expect = np.flatnonzero((table["id"] >= 300) & (table["id"] < 900)
                            & (table["score"] < 0.5))
    with dataset(path) as ds:
        q = ds.where(pred).select(["id", "score", "tag"])
        tbl = q.to_table()
        assert np.array_equal(tbl["id"], table["id"][expect])
        assert np.array_equal(tbl["score"], table["score"][expect])
        assert tbl["tag"] == [table["tag"][i] for i in expect]
        assert np.array_equal(q.row_ids(), expect)
        assert q.count_rows() == len(expect)
        phys = q.physical_plan()
        if collect_stats:
            assert phys.groups_pruned > 0 and phys.bytes_pruned > 0
        else:
            assert phys.groups_pruned == 0 and phys.bytes_pruned == 0


def test_v0_and_v1_results_identical(tmp_path):
    v0, v1 = str(tmp_path / "v0.bln"), str(tmp_path / "v1.bln")
    _write(v0, collect_stats=False)
    _write(v1, collect_stats=True)
    pred = (C("score") >= 0.25) & (C("score") < 0.3) | (C("id") < 40)
    for builder in (lambda ds: ds.where(pred).select(["id", "score"]),
                    lambda ds: ds.select(["qx"]).head(123),
                    lambda ds: ds.with_rows([3, 777, 1999]).select(["id"])):
        with dataset(v0) as d0, dataset(v1) as d1:
            t0, t1 = builder(d0).to_table(), builder(d1).to_table()
            for k in t0:
                assert np.array_equal(np.asarray(t0[k]), np.asarray(t1[k]))


def test_dataset_byte_identical_to_legacy_with_no_more_io(tmp_path):
    """Acceptance: where+select+to_table == legacy find_rows+project gather,
    byte for byte, reading no more data bytes."""
    path = str(tmp_path / "t.bln")
    _write(path)
    victim = 1234

    with BullionReader(path) as r:
        rows = r.find_rows("id", [victim])
        gathered = []
        for g, local in r.locate_rows(rows):
            (tbl,) = r.project(["id", "score"], groups=[g])
            gathered.append({k: v[local] for k, v in tbl.items()})
        legacy = {k: np.concatenate([t[k] for t in gathered])
                  for k in ("id", "score")}
        legacy_bytes = r.stats.bytes_read - r.stats.footer_bytes

    with dataset(path) as ds:
        got = ds.where(C("id") == victim).select(["id", "score"]).to_table()
        ds_bytes = ds.stats.bytes_read - ds.stats.footer_bytes
    assert got["id"].tobytes() == legacy["id"].tobytes()
    assert got["score"].tobytes() == legacy["score"].tobytes()
    assert ds_bytes <= legacy_bytes


def test_where_chaining_splits_conjuncts(tmp_path):
    path = str(tmp_path / "t.bln")
    _write(path)
    with dataset(path) as ds:
        q = ds.where(C("id") >= 100).where(C("id") < 200).where(C("score") >= 0)
        opt = q.plan()
        assert len(opt.conjuncts) == 3
        assert opt.pred_columns == ("id", "score")
        # projection narrowing: predicate columns join the read set once
        assert q.select(["tag", "id"]).plan().read_columns == \
            ("tag", "id", "score")
        assert q.count_rows() == 100
    assert split_conjuncts(None) == ()


# ---------------------------------------------------------------------------
# terminals: head / with_rows / count_rows / to_batches / dequantized
# ---------------------------------------------------------------------------


def test_head_limit_prunes_trailing_groups(tmp_path):
    path = str(tmp_path / "t.bln")
    table = _write(path)
    with dataset(path) as ds:
        q = ds.select(["id"]).head(300)
        phys = q.physical_plan()
        assert len(phys.tasks) == 2            # 250-row groups -> 2 needed
        assert phys.groups_pruned == 6 and phys.bytes_pruned > 0
        tbl = q.to_table()
        assert np.array_equal(tbl["id"], table["id"][:300])
        assert q.count_rows() == 300
        assert len(ds.select(["id"]).head(0).to_table()["id"]) == 0


def test_with_rows_reads_only_their_groups(tmp_path):
    path = str(tmp_path / "t.bln")
    table = _write(path)
    want = np.asarray([5, 260, 1999])
    with dataset(path) as ds:
        q = ds.with_rows(want).select(["id", "tag"])
        phys = q.physical_plan()
        assert [t.group for t in phys.tasks] == [0, 1, 7]
        assert phys.groups_pruned == 5
        tbl = q.to_table()
        assert np.array_equal(tbl["id"], table["id"][want])
        assert np.array_equal(q.row_ids(), want)
        # with_rows composes with where (AND semantics)
        both = ds.with_rows(want).where(C("id") >= 1000)
        assert np.array_equal(both.row_ids(), [1999])


def test_head_with_rows_counts_only_visible_pins(tmp_path):
    """A head limit must not be charged for pinned rows that deletion
    vectors hide — otherwise later groups are wrongly pruned."""
    path = str(tmp_path / "t.bln")
    table = _write(path, n=1000, rows_per_group=100)
    delete_rows(path, np.arange(0, 180), level=Compliance.LEVEL1)
    want = np.arange(0, 300)                  # 180 of these are deleted
    with dataset(path) as ds:
        got = ds.with_rows(want).select(["id"]).head(100).to_table()["id"]
        assert np.array_equal(got, table["id"][180:280])


def test_empty_result_has_typed_columns(tmp_path):
    path = str(tmp_path / "t.bln")
    _write(path)
    with dataset(path) as ds:
        tbl = ds.where(C("id") == 10**9) \
            .select(["id", "score", "qx", "tag"]).to_table()
        assert tbl["id"].dtype == np.int64 and tbl["id"].size == 0
        assert tbl["score"].dtype == np.float32
        assert tbl["qx"].dtype == np.float32           # logical domain
        assert ds.select(["qx"]).dequantized(False).head(0) \
            .to_table()["qx"].dtype != np.float32      # storage domain
        assert tbl["tag"] == []


def test_scan_batches_single_pass_ids_and_data(tmp_path):
    d = str(tmp_path / "shards")
    tables = _write_shards(d, n_shards=2)
    all_ids = np.concatenate([t["id"] for t in tables])
    with dataset(d) as ds:
        q = ds.where(C("id") >= 900).where(C("id") < 1100).select(["id"])
        batches = list(q.scan_batches())
        rows = np.concatenate([b.row_ids for b in batches])
        ids = np.concatenate([b.table["id"] for b in batches])
        assert np.array_equal(rows, np.arange(900, 1100))
        assert np.array_equal(ids, all_ids[900:1100])
        assert {b.shard for b in batches} == {0, 1}
        # one scan = one pruned-bytes credit
        assert ds.stats.bytes_pruned == q.physical_plan().bytes_pruned


def test_read_group_honors_pinned_rows(tmp_path):
    path = str(tmp_path / "t.bln")
    table = _write(path)
    with dataset(path) as ds:
        q = ds.with_rows([5, 7, 300]).select(["id"])
        assert np.array_equal(q.read_group(0)["id"], table["id"][[5, 7]])
        assert np.array_equal(q.read_group(1)["id"], [table["id"][300]])
        assert q.read_group(2) is None         # no pinned rows there


def test_tasks_then_terminal_credits_pruned_bytes_once(tmp_path):
    path = str(tmp_path / "t.bln")
    _write(path)
    with dataset(path) as ds:
        q = ds.where(C("id") == 7).select(["id"])
        q.tasks()
        q.to_table()
        q.row_ids()
        assert ds.stats.bytes_pruned == q.physical_plan().bytes_pruned


def test_count_rows_without_predicate_reads_no_data(tmp_path):
    path = str(tmp_path / "t.bln")
    _write(path)
    with dataset(path) as ds:
        assert ds.count_rows() == 2000
        assert ds.stats.preads == 0            # footer-only: no reader opened
    delete_rows(path, np.arange(100, 150), level=Compliance.LEVEL1)
    with dataset(path) as ds:
        assert ds.count_rows() == 1950
        assert ds.drop_deleted(False).count_rows() == 2000
        assert ds.stats.preads == 0
        assert all(r is None for r in ds._source._readers)


def test_to_batches_fixed_size(tmp_path):
    path = str(tmp_path / "t.bln")
    table = _write(path)
    with dataset(path) as ds:
        batches = list(ds.select(["id", "tag"]).to_batches(300))
        sizes = [len(b["id"]) for b in batches]
        assert sizes == [300] * 6 + [200]
        assert np.array_equal(np.concatenate([b["id"] for b in batches]),
                              table["id"])
        assert [t for b in batches for t in b["tag"]] == table["tag"]
        # natural batches: one per row group
        assert [len(b["id"]) for b in ds.select(["id"]).to_batches()] == \
            [250] * 8
        with pytest.raises(ValueError):
            next(ds.select(["id"]).to_batches(0))


def test_dequantized_toggle(tmp_path):
    path = str(tmp_path / "t.bln")
    _write(path)
    with dataset(path) as ds:
        logical = ds.select(["qx"]).to_table()["qx"]
        raw = ds.select(["qx"]).dequantized(False).to_table()["qx"]
        assert logical.dtype == np.float32
        assert raw.dtype != np.float32          # BF16 storage dtype
        # predicates still evaluate in the logical domain on raw reads
        n = len(ds.where(C("qx") >= 0).select(["qx"])
                .dequantized(False).to_table()["qx"])
        assert n == int((logical >= 0).sum())


# ---------------------------------------------------------------------------
# multi-file datasets
# ---------------------------------------------------------------------------


def test_directory_dataset_matches_per_shard_reads(tmp_path):
    d = str(tmp_path / "shards")
    tables = _write_shards(d, n_shards=4)
    all_ids = np.concatenate([t["id"] for t in tables])
    all_scores = np.concatenate([t["score"] for t in tables])
    with dataset(d) as ds:
        assert ds.n_shards == 4
        assert ds.num_rows == 4000
        assert ds.count_rows() == 4000
        tbl = ds.select(["id", "score"]).to_table()
        assert np.array_equal(tbl["id"], all_ids)
        assert np.array_equal(tbl["score"], all_scores)
        # the same plan that runs on one file runs unchanged over shards
        pred = (C("id") >= 1500) & (C("id") < 2500) & (C("score") < 0.5)
        expect = np.flatnonzero((all_ids >= 1500) & (all_ids < 2500)
                                & (all_scores < 0.5))
        q = ds.where(pred).select(["id"])
        assert np.array_equal(q.row_ids(), expect)
        assert np.array_equal(q.to_table()["id"], all_ids[expect])
        # shards 0 and 3 hold no matching ids: pruned without any pread
        shards_hit = {t.shard for t in q.physical_plan().tasks}
        assert shards_hit == {1, 2}


def test_glob_and_list_datasets(tmp_path):
    d = str(tmp_path / "shards")
    _write_shards(d, n_shards=4)
    paths = discover(os.path.join(d, "part-*.bln"))
    assert len(paths) == 4
    with dataset(os.path.join(d, "part-*.bln")) as ds:
        assert ds.n_shards == 4
    with dataset(paths[:2]) as ds:
        assert ds.num_rows == 2000
    # globs skip non-Bullion matches, same as directory discovery
    with open(os.path.join(d, "part-junk.bln"), "wb") as f:
        f.write(b"_SUCCESS marker, not a shard")
    with dataset(os.path.join(d, "part-*.bln")) as ds:
        assert ds.n_shards == 4
    with pytest.raises(FileNotFoundError, match="no Bullion"):
        discover(os.path.join(d, "part-junk*"))


def test_multi_shard_head_and_with_rows(tmp_path):
    d = str(tmp_path / "shards")
    tables = _write_shards(d, n_shards=4)
    all_ids = np.concatenate([t["id"] for t in tables])
    with dataset(d) as ds:
        assert np.array_equal(ds.select(["id"]).head(1100).to_table()["id"],
                              all_ids[:1100])
        want = np.asarray([0, 999, 1000, 3999])
        got = ds.with_rows(want).select(["id"]).to_table()["id"]
        assert np.array_equal(got, all_ids[want])


def test_loader_streams_every_shard(tmp_path):
    """A directory dataset must feed the loader all shards' groups, not
    shard 0 repeated (global group index = shard offset + local group)."""
    from repro.data import BullionLoader
    from repro.data.synthetic import write_lm_corpus
    d = str(tmp_path / "lm")
    os.makedirs(d)
    write_lm_corpus(os.path.join(d, "a.bln"), n_docs=64, doc_len=64,
                    rows_per_group=16, seed=0)
    write_lm_corpus(os.path.join(d, "b.bln"), n_docs=64, doc_len=64,
                    rows_per_group=16, seed=1)
    ld = BullionLoader(d, batch_size=2, seq_len=32, column="tokens")
    try:
        assert ld.n_groups == 8
        assert ld._groups == list(range(8))
        got = ld._read_group(5)            # shard b, local group 1
        with dataset(os.path.join(d, "b.bln")) as ds:
            tbl = ds.select(["tokens"])._with_groups([1]).to_table()
            expect = np.concatenate(
                [np.asarray(t, np.int32) for t in tbl["tokens"]])
        assert np.array_equal(got, expect)
    finally:
        ld.close()


def test_schema_mismatch_shard_raises(tmp_path):
    d = str(tmp_path / "shards")
    _write_shards(d, n_shards=3)
    bad = os.path.join(d, "part-9999.bln")
    w = BullionWriter(bad, [ColumnSpec("other", "int32")], rows_per_group=10)
    w.write_table({"other": np.arange(10, dtype=np.int32)})
    w.close()
    with pytest.raises(SchemaMismatchError, match="part-9999"):
        dataset(d)


def test_directory_discovery_skips_non_bullion(tmp_path):
    d = str(tmp_path / "shards")
    _write_shards(d, n_shards=2)
    with open(os.path.join(d, "README.txt"), "w") as f:
        f.write("not a shard")
    with dataset(d) as ds:
        assert ds.n_shards == 2
    with pytest.raises(FileNotFoundError):
        dataset(str(tmp_path / "empty_dir_missing"))


# ---------------------------------------------------------------------------
# lifecycle: idempotent close, context managers, aborted plans
# ---------------------------------------------------------------------------


def test_reader_close_idempotent(tmp_path):
    path = str(tmp_path / "t.bln")
    _write(path)
    r = BullionReader(path)
    r.close()
    r.close()                                   # must not raise
    assert r.closed
    with pytest.raises(ValueError, match="closed"):
        r._pread(0, 1)


def test_dataset_context_manager_closes_after_aborted_plan(tmp_path):
    d = str(tmp_path / "shards")
    _write_shards(d, n_shards=2)
    with dataset(d) as ds:
        for _ in ds.select(["id"]).to_batches():
            break                               # abort mid-execution
        live = [r for r in ds._source._readers if r is not None]
        assert live
    assert all(r is None for r in ds._source._readers)
    ds.close()                                  # idempotent on Dataset too
    # stats survive the close (retired accounting)
    assert ds.stats.preads > 0


def test_dataset_reopens_after_close(tmp_path):
    path = str(tmp_path / "t.bln")
    table = _write(path)
    ds = dataset(path)
    ds.close()
    assert np.array_equal(ds.select(["id"]).head(10).to_table()["id"],
                          table["id"][:10])
    ds.close()


def test_scanner_context_manager(tmp_path):
    path = str(tmp_path / "t.bln")
    _write(path)
    r = BullionReader(path)
    with r.scanner as sc:
        assert len(sc.plan(C("id") == 3).groups) == 1
    assert r.closed


# ---------------------------------------------------------------------------
# pruned-byte accounting + explain
# ---------------------------------------------------------------------------


def test_pruned_bytes_accounting(tmp_path):
    path = str(tmp_path / "t.bln")
    _write(path)
    with dataset(path) as ds:
        q = ds.where(C("id") == 77).select(["score"])
        phys = q.physical_plan()
        assert phys.bytes_pruned > 0
        assert phys.bytes_pruned < phys.bytes_total
        q.to_table()
        assert ds.stats.bytes_pruned == phys.bytes_pruned
        # legacy Scanner.scan credits the same accounting
    with BullionReader(path) as r:
        list(r.scanner.scan(C("id") == 77, columns=["score"]))
        assert r.stats.bytes_pruned == phys.bytes_pruned


def test_raw_scan_aligned_after_compact_delete(tmp_path):
    """drop_deleted=False always means raw row space: compact-deleted (RLE)
    pages are re-aligned so row_ids and every column agree in length."""
    path = str(tmp_path / "rle.bln")
    flags = np.repeat(np.arange(50), 20).astype(np.int64)
    w = BullionWriter(path, [ColumnSpec("flag", "int64")], rows_per_group=500)
    w.write_table({"flag": flags})
    w.close()
    delete_rows(path, np.arange(100, 120), level=Compliance.LEVEL2)
    with dataset(path) as ds:
        batches = list(ds.drop_deleted(False).select(["flag"]).scan_batches())
        for b in batches:
            assert len(b.row_ids) == len(b.table["flag"])
        raw = np.concatenate([b.table["flag"] for b in batches])
        assert len(raw) == 1000
        assert np.array_equal(np.flatnonzero(raw == 10),
                              np.arange(200, 220))   # no index shift
        assert not (raw[100:120] == 5).any()         # erased rows read 0


def test_legacy_shims_credit_pruned_bytes(tmp_path):
    path = str(tmp_path / "t.bln")
    _write(path)
    with BullionReader(path) as r:
        list(r.project(["score"], predicate=C("id") == 77))
        assert r.stats.bytes_pruned > 0
    with BullionReader(path) as r:
        r.find_rows("id", [77])
        assert r.stats.bytes_pruned > 0


def test_explain_smoke(tmp_path):
    path = str(tmp_path / "t.bln")
    _write(path)
    with dataset(path) as ds:
        text = ds.where((C("id") >= 5) & (C("score") < 0.5)) \
            .select(["tag"]).head(9).explain()
        assert "LogicalPlan" in text and "PhysicalPlan" in text
        assert "2 conjunct(s)" in text
        assert repr(ds.select(["id"]))


def test_unknown_column_errors_at_plan_time(tmp_path):
    path = str(tmp_path / "t.bln")
    _write(path)
    with dataset(path) as ds:
        with pytest.raises(KeyError, match="nope"):
            ds.select(["nope"]).plan()
        with pytest.raises(KeyError, match="nope"):
            ds.where(C("nope") == 1).count_rows()
        # the count_rows metadata fast path validates too
        with pytest.raises(KeyError, match="nope"):
            ds.select(["nope"]).count_rows()


# ---------------------------------------------------------------------------
# legacy shims stay equivalent
# ---------------------------------------------------------------------------


def test_legacy_shims_delegate_to_plans(tmp_path):
    path = str(tmp_path / "t.bln")
    table = _write(path)
    with BullionReader(path) as r:
        assert np.array_equal(r.read_column("id"), table["id"])
        assert np.array_equal(r.find_rows("id", [55, 1700]), [55, 1700])
        assert np.array_equal(
            r.find_rows("tag", [b"t3"]), np.arange(3, 2000, 7))
        got = np.concatenate(
            [t["score"] for t in r.project(["score"], predicate=C("id") < 10)])
        assert np.allclose(got, table["score"][:10])
    with dataset(path) as ds:
        assert np.array_equal(
            ds.where(In("id", [55, 1700])).drop_deleted(False).row_ids(),
            [55, 1700])


# ---------------------------------------------------------------------------
# the payload pass decodes string pages at the surviving rows only
# ---------------------------------------------------------------------------

SELECT_COLS = ["id", "score", "qx", "url", "text"]
SELECTED = "bullion.decode.selected_string_pages"


def _write_strings(d, *, page_rows, n_shards=2, rows_each=1000):
    """Shards of 500-row groups over ``page_rows``-row pages; ``id`` is
    clustered, so page zone maps prune on it."""
    os.makedirs(d, exist_ok=True)
    schema = [ColumnSpec("id", "int64"), ColumnSpec("score", "float32"),
              ColumnSpec("qx", "float32", quant=QuantSpec(QuantMode.BF16)),
              ColumnSpec("url", "string"), ColumnSpec("text", "string")]
    for s in range(n_shards):
        rng = np.random.default_rng(40 + s)
        ids = np.arange(s * rows_each, (s + 1) * rows_each, dtype=np.int64)
        w = BullionWriter(os.path.join(d, f"part-{s:04d}.bln"), schema,
                          rows_per_group=500, page_rows=page_rows)
        w.write_table({
            "id": ids,
            "score": rng.random(rows_each).astype(np.float32),
            "qx": rng.normal(size=rows_each).astype(np.float32),
            "url": [b"https://img.example.com/%d/%x.jpg" % (i % 89, i * 7919)
                    for i in ids],
            "text": [b"" if i % 6 == 0 else b"caption %d " % i * int(i % 5)
                     for i in ids]})
        w.close()


def _delete(level):
    return lambda d: delete_rows(os.path.join(d, "part-0000.bln"),
                                 np.arange(40, 460, 3), level=level)


def _quarantine_url_page(d):
    from repro.core import integrity
    from repro.core.footer import read_footer
    from repro.dataset import clear_footer_cache
    path = os.path.join(d, "part-0001.bln")
    fv, _ = read_footer(path)
    page = fv.chunk_pages(1, fv.column_index("url"))[0] + 1
    off, size = fv.page_extent(page)
    with open(path, "r+b") as f:
        f.seek(off + size // 2)
        b = f.read(1)
        f.seek(off + size // 2)
        f.write(bytes([b[0] ^ 0xFF]))
    clear_footer_cache()
    integrity.set_verify_policy("full")
    integrity.set_corruption_policy("mask")


# name -> (page_rows, drop_deleted, file edit, predicate, pinned rows)
STRING_SELECTIONS = {
    "drop_deleted": (100, True, _delete(Compliance.LEVEL1),
                     C("score") < 0.3, None),
    "raw_row_space": (100, False, _delete(Compliance.LEVEL1),
                      C("score") < 0.3, None),
    "erased_in_place": (100, False, _delete(Compliance.LEVEL2),
                        C("score") < 0.3, None),
    "pruned_pages": (100, True, None,
                     (C("id") >= 230) & (C("id") < 1470) & (C("score") < 0.5),
                     None),
    "with_rows": (100, True, None, None,
                  np.asarray([0, 99, 100, 333, 499, 500, 1234, 1999])),
    "quarantined_page": (100, True, _quarantine_url_page,
                         C("score") < 0.4, None),
    "pages_of_64": (64, True, None, C("score") < 0.3, None),
}


@pytest.mark.parametrize("case", sorted(STRING_SELECTIONS))
def test_selected_string_decode_matches_full_decode(tmp_path, case):
    """A filtered read equals the full decode then the take, in value,
    order and type; each string page read at a selection is counted,
    and a read with no selection counts none."""
    from repro.core import integrity
    from repro.core.integrity import QUARANTINE
    from repro.dataset.executor import _take
    from repro.obs import metrics, trace
    from repro.scan.predicate import evaluate
    page_rows, drop, edit, pred, rows = STRING_SELECTIONS[case]
    d = str(tmp_path / "shards")
    _write_strings(d, page_rows=page_rows)
    counted = metrics.counter(SELECTED)
    try:
        if edit is not None:
            edit(d)
        with dataset(d) as ds:
            ds = ds.drop_deleted(drop)
            before = counted.value
            full = ds.select(SELECT_COLS).to_table()
            full_ids = ds.row_ids()
            assert counted.value == before
            q = ds.select(SELECT_COLS)
            keep = np.ones(len(full_ids), bool)
            if pred is not None:
                q = q.where(pred)
                keep &= evaluate(pred, full)
            if rows is not None:
                q = q.with_rows(rows)
                keep &= np.isin(full_ids, rows)
            keep = np.flatnonzero(keep)
            assert 0 < len(keep) < len(full_ids)
            tasks = q.tasks()
            before = counted.value
            with trace.collect() as tracer:
                got = q.to_table()
            masked = len(QUARANTINE.lookup(os.path.join(d, "part-0001.bln"),
                                           ds._source.footer(1)))
            assert masked == (case == "quarantined_page")
            assert counted.value - before == sum(
                len(t.pages) if t.pages is not None
                else len(ds._source.footer(t.shard).chunk_page_rows(t.group, 0))
                for t in tasks) * 2 - masked
            assert np.array_equal(q.row_ids(), full_ids[keep])
        assert list(got) == SELECT_COLS
        for name in SELECT_COLS:
            want = _take(full[name], keep)
            if isinstance(want, np.ndarray):
                assert got[name].dtype == want.dtype
                assert np.array_equal(got[name], want)
            else:
                assert got[name] == want
                assert all(type(v) is bytes for v in got[name])
        for name in ("url", "text"):
            assert sum(sp.args["rows_out"] for sp in tracer.spans
                       if sp.name == "decode.decode"
                       and sp.args["column"] == name) == len(keep)
        if case == "pruned_pages":
            assert any(t.pages is not None for t in tasks)
        if case == "quarantined_page":
            assert b"" in got["url"]
    finally:
        integrity.set_verify_policy(None)
        integrity.set_corruption_policy(None)
        QUARANTINE.clear()
