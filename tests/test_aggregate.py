"""The aggregate node and its fused filter-and-sum kernel: exact integer
answers equal to the plain reference (``kernels/aggregate/ref.py``), through
the kernel (interpret mode here), over decoded columns and straight from
bit-packed pages, and through the host path, in process and over the
socket."""

import os

import numpy as np
import pytest

from repro.core import BullionWriter, ColumnSpec, Compliance, delete_rows
from repro.core import integrity
from repro.core.encodings import EncodeContext
from repro.core.encodings.numeric import FOR, FixedBitWidth, bit_packed
from repro.core.footer import read_footer
from repro.dataset import SumProduct, clear_footer_cache, dataset, optimize
from repro.kernels.aggregate import (exact_for, pack_column, sum_product,
                                     sum_product_packed, sum_product_ref)
from repro.kernels.aggregate.kernel import TILE_N
from repro.obs import metrics
from repro.scan import C
from repro.serve import DatasetServer, ServeClient

INT32 = (-(1 << 31), (1 << 31) - 1)
MAX_PRICE = 50 * 209_900          # TPC-H's largest l_extendedprice, cents
FACTORS = ("price", "disc")
Q6 = ((C("ship") >= 8766) & (C("ship") < 9131) & (C("disc") >= 5)
      & (C("disc") <= 7) & (C("qty") < 24))


def _table(rng, n):
    qty = rng.integers(1, 51, n)
    part = rng.integers(1, 200_001, n)
    retail = 90_000 + (part // 10) % 20_001 + 100 * (part % 1000)
    return {"ship": rng.integers(8036, 10_592, n).astype(np.int32),
            "disc": rng.integers(0, 11, n).astype(np.int32),
            "qty": qty.astype(np.int32),
            "price": (qty * retail).astype(np.int32),
            "score": rng.random(n).astype(np.float32)}


def _write(path, table, rows_per_group, page_rows=None):
    w = BullionWriter(path, [ColumnSpec(k, str(v.dtype))
                             for k, v in table.items()],
                      rows_per_group=rows_per_group, page_rows=page_rows)
    w.write_table(table)
    w.close()


@pytest.fixture(scope="module")
def shards(tmp_path_factory):
    """Three shards of 3,000 rows in groups of 1,024 (ragged last group),
    with LEVEL2 deletes in one and two rounds of LEVEL1 deletes in the
    others; the visible rows as one table."""
    d = str(tmp_path_factory.mktemp("lineitem"))
    rng = np.random.default_rng(15)
    visible = []
    for s in range(3):
        t = _table(rng, 3000)
        path = os.path.join(d, f"part-{s:03d}.bln")
        _write(path, t, 1024)
        gone = np.sort(rng.choice(3000, 150, replace=False))
        if s == 0:
            delete_rows(path, gone, level=Compliance.LEVEL2)
        else:
            delete_rows(path, gone[:75], level=Compliance.LEVEL1)
            delete_rows(path, gone[75:], level=Compliance.LEVEL1)
        keep = np.ones(3000, bool)
        keep[gone] = False
        visible.append({k: v[keep] for k, v in t.items()})
    return d, {k: np.concatenate([t[k] for t in visible])
               for k in visible[0]}


def _ref(table, pred):
    """The plain reference over a whole table: mask, then the exact sum."""
    mask = np.ones(len(table["price"]), bool) if pred is None \
        else np.asarray(pred.mask(table), bool)
    cols = np.stack([table["price"], table["disc"]])
    return sum_product_ref(cols[:, mask], [INT32[0]] * 2, [INT32[1]] * 2,
                           0, 1)


COUNTERS = ("kernel_calls", "packed_groups", "host_groups")


def _count(name):
    return metrics.counter(name).value


def _counted(fn):
    """``fn()``, and how far it moved each aggregate counter."""
    before = [_count(f"bullion.aggregate.{c}") for c in COUNTERS]
    out = fn()
    return out, {c: _count(f"bullion.aggregate.{c}") - b
                 for c, b in zip(COUNTERS, before)}


# ---------------------------------------------------------------------------
# the kernel against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 1000, 8192, 20_000])
def test_kernel_matches_reference(n):
    rng = np.random.default_rng(n)
    t = _table(rng, n)
    cols = np.stack([t["ship"], t["disc"], t["qty"], t["price"]])
    lo, hi = [8766, 5, INT32[0], INT32[0]], [9130, 7, 23, INT32[1]]
    want = sum_product_ref(cols, lo, hi, 3, 1)
    assert exact_for(n, MAX_PRICE, 10)
    assert sum_product(cols, lo, hi, 3, 1) == want


def test_kernel_signed_factors_and_empty_bounds():
    rng = np.random.default_rng(3)
    cols = np.stack([rng.integers(INT32[0], INT32[1], 5000),
                     rng.integers(-3, 4, 5000)]).astype(np.int32)
    full = ([INT32[0]] * 2, [INT32[1]] * 2)
    assert exact_for(5000, 1 << 31, 3)
    assert sum_product(cols, *full, 0, 1) == sum_product_ref(cols, *full,
                                                             0, 1)
    assert sum_product(cols, [5, INT32[0]], [4, INT32[1]], 0, 1) == (0, 0)


def test_worst_case_group_is_exact_where_float32_and_int32_fail():
    """Max price times max discount on every row of a 65,536-row group:
    each product passes float32's 2**24 and the sum passes int32."""
    n = 65_536
    cols = np.stack([np.full(n, MAX_PRICE), np.full(n, 10)]).astype(np.int32)
    full = ([INT32[0]] * 2, [INT32[1]] * 2)
    want = sum_product_ref(cols, *full, 0, 1)
    assert want == (n * MAX_PRICE * 10, n)
    assert want[0] > INT32[1] and MAX_PRICE * 10 > 2 ** 24
    assert exact_for(n, MAX_PRICE, 10)
    assert sum_product(cols, *full, 0, 1) == want
    # what a float32 or a single int32 accumulator would have answered
    prod = cols[0] * cols[1]
    assert int(np.cumsum(prod.astype(np.float32))[-1]) != want[0]
    assert int(prod.sum(dtype=np.int32)) != want[0]
    # per lane (1,024 lanes) an unsplit product sum would wrap too
    assert n // 1024 * MAX_PRICE * 10 > INT32[1]


def test_exact_bound_refuses_what_would_wrap():
    assert exact_for(65_536, MAX_PRICE, 10)
    assert not exact_for(65_536, MAX_PRICE, 1 << 20)
    assert not exact_for(65_536, 1 << 31, 10_000)


# ---------------------------------------------------------------------------
# Dataset.aggregate: kernel path and host path against the reference
# ---------------------------------------------------------------------------

PREDICATES = {
    "q6": Q6,
    "empty": (C("qty") < 1),
    "contradiction": (C("qty") >= 30) & (C("qty") < 20),
    "all_pass_range": (C("qty") >= 1) & (C("qty") <= 50),
    "lt_boundary": (C("qty") < 24) & (C("disc") > 9),
    "le_boundary": (C("qty") <= 24) & (C("disc") >= 10),
    "ge_boundary": (C("qty") >= 50) & (C("disc") < 1),
    "float_literal": (C("qty") < 23.5) & (C("ship") >= 8765.5),
    "equality": (C("disc") == 7) & (C("qty") == 13),
    "none": None,
}


@pytest.mark.parametrize("use_kernel", [None, True, False],
                         ids=["auto", "kernel", "host"])
@pytest.mark.parametrize("name", sorted(PREDICATES))
def test_dataset_aggregate_matches_reference(shards, name, use_kernel):
    d, table = shards
    pred = PREDICATES[name]
    with dataset(d) as ds:
        if pred is not None:
            ds = ds.where(pred)
        kernel0 = _count("bullion.aggregate.kernel_calls")
        host0 = _count("bullion.aggregate.host_groups")
        got = ds._with_kernel(use_kernel).aggregate(sum_product=FACTORS)
        kernel = _count("bullion.aggregate.kernel_calls") - kernel0
        host = _count("bullion.aggregate.host_groups") - host0
        groups = len(ds.tasks())      # what zone maps left of the 9
    assert tuple(got) == _ref(table, pred)
    assert isinstance(got.value, int)
    assert groups == 9 or name in ("empty", "contradiction")
    if use_kernel is False:
        assert kernel == 0 and host == groups
    elif name == "contradiction":     # an empty interval needs no kernel
        assert kernel == 0 and host == 0
    else:
        assert kernel == groups and host == 0


def test_bounds_at_the_literals_are_exact(shards):
    """``qty < 24`` is ``qty <= 23`` on the kernel path: the rows at 24 and
    at 23 decide the answer."""
    d, table = shards
    with dataset(d) as ds:
        for pred in (C("qty") < 24, C("qty") <= 23, C("qty") >= 24,
                     C("qty") > 23, C("qty") < 23.0001):
            got = ds.where(pred)._with_kernel(True).aggregate(
                sum_product=FACTORS)
            assert tuple(got) == _ref(table, pred), pred


def test_float_predicate_and_pinned_rows_take_the_host_path(shards):
    d, table = shards
    pred = (C("score") < 0.5) & (C("qty") < 24)
    with dataset(d) as ds:
        host0 = _count("bullion.aggregate.host_groups")
        got = ds.where(pred).aggregate(sum_product=FACTORS)
        assert _count("bullion.aggregate.host_groups") - host0 == 9
        assert tuple(got) == _ref(table, pred)
        with pytest.raises(ValueError, match="aggregate kernel"):
            ds.where(pred)._with_kernel(True).aggregate(sum_product=FACTORS)
        ids = ds.where(C("qty") < 10).row_ids()[::3]
        pinned = ds.with_rows(ids).where(C("disc") >= 2)
        rows = pinned.select(list(FACTORS)).to_table()
        want = sum((rows["price"].astype(np.int64)
                    * rows["disc"].astype(np.int64)).tolist())
        assert tuple(pinned.aggregate(sum_product=FACTORS)) == \
            (want, len(rows["price"]))


def test_parallel_and_prefetched_aggregate_equals_serial(shards):
    d, table = shards
    with dataset(d) as ds:
        serial = ds.where(Q6).aggregate(sum_product=FACTORS)
        par = ds.where(Q6).aggregate(sum_product=FACTORS, parallelism=3,
                                     io_depth=2)
    assert serial == par == _ref(table, Q6)


def test_aggregate_reads_only_predicate_and_factor_columns(shards):
    d, _ = shards
    with dataset(d) as ds:
        plan = ds.where(C("qty") < 24)._plan.replace(
            aggregate=SumProduct(*FACTORS))
        opt = optimize(plan, ds._source)
    assert opt.output_columns == ()
    assert opt.read_columns == ("qty", "price", "disc")
    assert opt.prefetch_columns() == opt.read_columns


def test_raw_row_space_aggregate_keeps_deleted_rows(tmp_path):
    rng = np.random.default_rng(5)
    t = _table(rng, 2000)
    path = str(tmp_path / "t.bln")
    _write(path, t, 512)
    delete_rows(path, np.arange(100, 400), level=Compliance.LEVEL1)
    with dataset(path) as ds:
        raw = ds.drop_deleted(False).where(C("qty") < 30).aggregate(
            sum_product=FACTORS)
        vis = ds.where(C("qty") < 30).aggregate(sum_product=FACTORS)
    keep = np.ones(2000, bool)
    keep[100:400] = False
    assert tuple(raw) == _ref(t, C("qty") < 30)
    assert tuple(vis) == _ref({k: v[keep] for k, v in t.items()},
                              C("qty") < 30)


def test_aggregate_validates_its_factors(shards):
    d, _ = shards
    with dataset(d) as ds:
        with pytest.raises(TypeError, match="integer"):
            ds.aggregate(sum_product=("price", "score"))
        with pytest.raises(KeyError, match="nope"):
            ds.aggregate(sum_product=("price", "nope"))
        with pytest.raises(ValueError, match="head"):
            ds.head(5).aggregate(sum_product=FACTORS)


# ---------------------------------------------------------------------------
# the packed kernel: bit-packed pages unpacked on the device
# ---------------------------------------------------------------------------

# rows of each page: every page but the last whole rows of 4,096 values,
# one spanning four of them, one three; two tiles in all
PAGES = (16_384, 8192, 12_288, 3000)


def _pages(values, encoding):
    """``values`` written page by page with the store's own encoder, as
    ``pack_column`` takes them."""
    out, at = [], 0
    for rows in PAGES:
        part = bit_packed(encoding.encode(values[at:at + rows],
                                          EncodeContext()))
        out.append((part.payload, part.n, part.base))
        at += rows
    return out


@pytest.mark.parametrize("width", range(1, 32))
def test_packed_kernel_matches_reference_at_every_width(width):
    """FOR pages of ``width`` bits whose bases lie at int32's two ends, at
    zero and below it, filtered and multiplied by a 2-bit FixedBitWidth
    column: bit for bit the reference's and the decoded kernel's answer."""
    rng = np.random.default_rng(width)
    span = (1 << width) - 1
    bases = (INT32[0], 0, -12_345, INT32[1] - span)
    parts = []
    for base, rows in zip(bases, PAGES):
        off = rng.integers(0, span + 1, rows)
        off[:2] = (0, span)                 # each page needs all its bits
        parts.append(base + off)
    x = np.concatenate(parts).astype(np.int32)
    y = rng.integers(0, 4, len(x)).astype(np.int32)
    y[::4096] = 3
    px, py = _pages(x, FOR()), _pages(y, FixedBitWidth())
    assert {p[2] for p in px} == set(bases) and {p[2] for p in py} == {0}
    cols = np.stack([x, y])
    lo = [int(np.quantile(x, 0.1)), 1]
    hi = [int(np.quantile(x, 0.8)), 3]
    n = len(x)
    assert exact_for(n, 1 << 31, 3, TILE_N) and exact_for(n, 1 << 31, 3)
    want = sum_product_ref(cols, lo, hi, 0, 1)
    got = sum_product_packed([pack_column(px, width), pack_column(py, 2)],
                             [width, 2], n, lo, hi, 0, 1)
    assert got == want == sum_product(cols, lo, hi, 0, 1)
    assert 0 < want[1] < n


@pytest.fixture(scope="module")
def packed(tmp_path_factory):
    """Two shards of 40,000 rows, no deletes, in groups of 16,384 rows and
    pages of 4,096 (a ragged last page), plus a clustered ``key`` column
    whose page zone maps select pages: every page the aggregates read is
    bit-packed, whole, at one width a chunk."""
    d = str(tmp_path_factory.mktemp("packed"))
    rng = np.random.default_rng(16)
    shards = []
    for s in range(2):
        t = _table(rng, 40_000)
        t["key"] = np.arange(s * 40_000, (s + 1) * 40_000, dtype=np.int32)
        _write(os.path.join(d, f"part-{s:03d}.bln"), t, 16_384, 4096)
        shards.append(t)
    return d, {k: np.concatenate([t[k] for t in shards]) for k in shards[0]}


@pytest.mark.parametrize("name", sorted(PREDICATES))
def test_packed_pages_answer_as_the_decoded_path(packed, name):
    """Every group straight from its packed pages: the same (value, rows)
    as the host path, which decodes, and the reference."""
    d, table = packed
    pred = PREDICATES[name]
    with dataset(d) as ds:
        if pred is not None:
            ds = ds.where(pred)
        got, moved = _counted(lambda: ds.aggregate(sum_product=FACTORS))
        host = ds._with_kernel(False).aggregate(sum_product=FACTORS)
        groups = len(ds.tasks())
    assert tuple(got) == tuple(host) == _ref(table, pred)
    assert groups == (0 if name == "empty" else 6)
    calls = 0 if name == "contradiction" else groups    # an empty interval
    assert moved == {"kernel_calls": calls, "packed_groups": calls,
                     "host_groups": 0}


def test_packed_pages_of_a_partial_page_selection(packed):
    """Page zone maps on the clustered key leave some of a group's pages;
    the kernel reads those alone."""
    d, table = packed
    pred = (C("key") >= 5000) & (C("key") < 30_000) & (C("qty") < 24)
    with dataset(d) as ds:
        ds = ds.where(pred)
        tasks = ds.tasks()
        got, moved = _counted(lambda: ds.aggregate(sum_product=FACTORS))
    assert [t.pages for t in tasks] == [(1, 2, 3), None]
    assert tuple(got) == _ref(table, pred)
    assert moved == {"kernel_calls": 2, "packed_groups": 2, "host_groups": 0}


def _flip(path, page):
    fv, _ = read_footer(path)
    off, size = fv.page_extent(page)
    with open(path, "r+b") as f:
        f.seek(off + size // 2)
        b = f.read(1)
        f.seek(off + size // 2)
        f.write(bytes([b[0] ^ 0xFF]))
    clear_footer_cache()


def _first_page(path, column):
    fv, _ = read_footer(path)
    return fv.chunk_pages(0, fv.column_index(column))[0]


def _narrow_first_page(t):
    """The first page's quantities fit 5 bits, the chunk's others need 6."""
    t["qty"][:4096] %= 31


def _constant_group(t):
    """One discount in all of group 0: Constant pages, not bit-packed."""
    t["disc"][:8192] = 6


# each breaks the packed path for group 0 of two: (table edit, file edit)
FALLBACKS = {
    "mixed_widths": (_narrow_first_page, None),
    "deleted_row": (None, lambda p: delete_rows(p, np.array([5]),
                                                level=Compliance.LEVEL1)),
    "quarantined_page": (None, lambda p: _flip(p, _first_page(p, "qty"))),
    "not_bit_packed": (_constant_group, None),
    "pinned_rows": (None, None),
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_fallback_groups_answer_as_today(tmp_path, case):
    """A group the packed path refuses is decoded, as before this path
    existed: the same answer as the host path, the same kernel calls, and
    only the other group counted as packed."""
    rng = np.random.default_rng(17)
    t = _table(rng, 16_384)
    edit_table, edit_file = FALLBACKS[case]
    if edit_table:
        edit_table(t)
    path = str(tmp_path / "t.bln")
    _write(path, t, 8192, 4096)
    if edit_file:
        edit_file(path)
    pred = (C("ship") >= 8766) & (C("disc") >= 5) & (C("qty") < 24)
    if case == "quarantined_page":
        integrity.set_verify_policy("full")
        integrity.set_corruption_policy("mask")
    try:
        with dataset(path) as ds:
            ds = ds.where(pred)
            if case == "pinned_rows":
                ds = ds.with_rows(np.arange(0, 16_384, 3))
            got, moved = _counted(lambda: ds.aggregate(sum_product=FACTORS))
            host = ds._with_kernel(False).aggregate(sum_product=FACTORS)
    finally:
        integrity.set_verify_policy(None)
        integrity.set_corruption_policy(None)
        integrity.QUARANTINE.clear()
        clear_footer_cache()
    assert tuple(got) == tuple(host)
    if case == "pinned_rows":
        keep = np.zeros(16_384, bool)
        keep[::3] = True
        assert tuple(got) == _ref({k: v[keep] for k, v in t.items()}, pred)
        assert moved == {"kernel_calls": 0, "packed_groups": 0,
                         "host_groups": 2}
        return
    if case == "deleted_row":
        t = {k: np.delete(v, 5) for k, v in t.items()}
    if case != "quarantined_page":      # masked rows read 0 on both paths
        assert tuple(got) == _ref(t, pred)
    assert moved == {"kernel_calls": 2, "packed_groups": 1, "host_groups": 0}


# ---------------------------------------------------------------------------
# served: the socket, the plan cache, the query log
# ---------------------------------------------------------------------------


def test_served_aggregate_over_the_socket_equals_in_process(shards):
    d, table = shards
    with DatasetServer({"li": d}) as srv:
        sock = srv.serve()
        local = srv.aggregate("li", sum_product=FACTORS, where=Q6)
        with ServeClient(sock) as cli:
            remote = cli.aggregate("li", sum_product=FACTORS, where=Q6,
                                   tenant="scan")
        rec = srv.query_log.tail(1)[0]
    want = _ref(table, Q6)
    assert (local.value, local.rows) == want
    assert (remote.value, remote.rows) == want
    assert remote.table == {} and remote.cache_hit
    assert rec.aggregate == "sum_product(price, disc)"
    assert rec.matched_rows == want[1] and rec.tenant == "scan"


def test_served_aggregate_passes_int32_over_the_wire(tmp_path):
    """A value far past int32 and float64's 2**53 comes back exact."""
    n = 4096
    t = {"price": np.full(n, INT32[1], np.int32),
         "disc": np.full(n, INT32[1], np.int32)}
    _write(str(tmp_path / "t.bln"), t, 4096)
    with DatasetServer({"t": str(tmp_path)}) as srv:
        with ServeClient(srv.serve()) as cli:
            res = cli.aggregate("t", sum_product=FACTORS)
    assert res.value == n * INT32[1] ** 2 and res.value > 2 ** 53


def test_plan_cache_keeps_aggregate_and_projection_plans_apart(shards):
    d, _ = shards
    with DatasetServer({"li": d}) as srv:
        rows = srv.query("li", where=Q6)
        agg = srv.aggregate("li", sum_product=FACTORS, where=Q6)
        again = srv.aggregate("li", sum_product=FACTORS, where=Q6)
        swapped = srv.aggregate("li", sum_product=FACTORS[::-1], where=Q6)
        assert not agg.cache_hit and again.cache_hit
        assert agg.fingerprint != rows.fingerprint
        assert swapped.fingerprint != agg.fingerprint
        assert swapped.value == agg.value
        assert rows.value is None and rows.rows == agg.rows
        assert srv.stats()["plan_cache"]["size"] == 3
