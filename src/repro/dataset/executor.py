"""Physical-plan execution: the one read pipeline.

Every read path in the system — ``Dataset`` terminals, the legacy
``BullionReader.project``/``find_rows`` shims, ``Scanner.scan``, the
training loader, quality-filtered reads, and predicate deletes — bottoms
out in ``execute_group``, which orders the stages exactly once:

    prune (done at plan time) -> pread (coalesced) -> decode ->
    deletion-mask -> dequantize -> filter -> gather

``decode_group`` is the pread+decode+mask+dequantize core (moved here from
``BullionReader.project``): ``read_pages``, then ``decode_pages``.
``execute_group`` layers predicate evaluation (NumPy or the Pallas batch
filter kernel) and raw-row-id selection on top. ``aggregate_group`` is the
same pipeline ending in a partial aggregate in place of the gather: the
group's sum of products and matching-row count, from the fused Pallas
filter-and-sum kernel or from NumPy, exact either way. Where every page it
reads is bit-packed (FixedBitWidth or FOR) and whole, the kernel takes the
pages' packed words and the host decodes nothing.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Callable, Optional, Sequence

import numpy as np

from ..core import integrity as _integrity
from ..core import pages as pages_mod
from ..core.encodings.base import code_dtype
from ..core.encodings.numeric import bit_packed
from ..core.footer import ColKind, PageType, Sec, ShardCorruptError
from ..core.quantization import QuantMode, dequantize
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from ..scan.predicate import Predicate, conjunctive_ranges, evaluate

if TYPE_CHECKING:  # pragma: no cover - import cycle guard
    from ..core.reader import BullionReader
    from ..kernels.aggregate.ops import Call


@dataclass
class GroupResult:
    """Matching rows of one row group (row ids are group-local, raw space)."""

    row_ids: np.ndarray
    table: dict = field(default_factory=dict)


# ---------------------------------------------------------------------------
# decode core: pread -> decode -> deletion-mask -> dequantize
# ---------------------------------------------------------------------------


def table_nbytes(table: dict) -> int:
    """Payload bytes of a result table: array ``nbytes`` plus per-row bytes
    for list/string columns. The query log's byte accounting — what a
    terminal handed back, not what the wire encoding costs."""
    total = 0
    for col in table.values():
        nbytes = getattr(col, "nbytes", None)
        if nbytes is not None:
            total += int(nbytes)
        else:
            for row in col:
                total += int(getattr(row, "nbytes", None) or len(row))
    return total


def _chunk_page_ids(fv, group: int, col: int,
                    pages: Optional[Sequence[int]]) -> list[int]:
    """Physical page indices of one chunk, restricted to the page-ordinal
    selection a plan produced (None = every page)."""
    s, e = fv.chunk_pages(group, col)
    return list(range(s, e)) if pages is None else [s + int(k) for k in pages]


def _pad_raw(decoded, dv: Optional[np.ndarray], page_rows: int):
    """Re-align one page's decode to its raw row space (drop_deleted=False):
    compact-deleted pages (§2.1 RLE rule) physically removed rows, so erased
    positions are re-padded with 0 — the same value in-place masking writes —
    to keep raw row ids stable."""
    if not isinstance(decoded, np.ndarray):
        return decoded
    if len(decoded) >= page_rows:
        return decoded[:page_rows]
    out = np.zeros(page_rows, decoded.dtype)
    out[np.flatnonzero(~dv)] = decoded
    return out


# page-type flag -> encoding family name, cached (``decode.decode`` spans)
_FAMILY: dict[int, str] = {}


def _encoding(flags: np.ndarray, pids: Sequence[int]) -> str:
    """The encoding family of a chunk's pages (``PageType`` names, lower
    case), or the families joined by ``+`` where the chunk mixes them."""
    fams = set()
    for p in pids:
        flag = int(flags[p]) & 0x7F
        name = _FAMILY.get(flag)
        if name is None:
            try:
                name = PageType(flag).name.lower()
            except ValueError:
                name = f"type{flag}"
            _FAMILY[flag] = name
        fams.add(name)
    return "+".join(sorted(fams))


def _mask_fill(fv, col: int, rows: int):
    """Shape-stable zero fill for a quarantined page under the ``mask``
    corruption policy: scalar/media_ref pages decode to zeros of the
    storage dtype, list pages to empty arrays, string pages to empty
    strings — same row count and types as a healthy decode."""
    kind = int(fv.arr(Sec.COL_KIND, np.uint8)[col])
    dt = code_dtype(int(fv.arr(Sec.COL_DTYPE, np.uint8)[col]))
    if kind == int(ColKind.LIST):
        return [np.zeros(0, dt)] * rows
    if kind == int(ColKind.STRING):
        return [b""] * rows
    return np.zeros(rows, dt)


def decode_group(reader: "BullionReader", names: Sequence[str], group: int, *,
                 drop_deleted: bool = True, dequant: bool = True,
                 pages: Optional[Sequence[int]] = None,
                 align_raw: bool = False,
                 masked_out: Optional[set] = None,
                 take: Optional[np.ndarray] = None) -> dict:
    """Decode one row group's columns via coalesced preads.

    ``pages`` restricts the read to a plan's surviving page ordinals (the
    same ordinals for every column — pages of one ordinal cover one row
    range group-wide). ``align_raw`` pads compact-deleted pages back to the
    raw row space (only meaningful with ``drop_deleted=False``); the default
    keeps physical page content, which ``verify_deleted`` audits. ``take``
    returns each column at those rows only (see ``decode_pages``).

    Each stage is a distinct span (``decode.pread`` / ``decode.decode``,
    which names the chunk's ``encoding`` / ``decode.mask`` /
    ``decode.dequantize``) so traces and
    ``explain(analyze=True)`` attribute time per stage; with tracing
    disabled the spans are shared no-ops and the stage order is the only
    (behavior-identical) difference from an uninstrumented decode.
    """
    return decode_pages(reader, names, group,
                        read_pages(reader, names, group, pages),
                        drop_deleted=drop_deleted, dequant=dequant,
                        pages=pages, align_raw=align_raw,
                        masked_out=masked_out, take=take)


def read_pages(reader: "BullionReader", names: Sequence[str], group: int,
               pages: Optional[Sequence[int]] = None) -> dict:
    """The coalesced pread of one row group's pages of ``names`` (the
    ``decode.pread`` span): page id -> verified page bytes."""
    fv = reader.footer
    wanted: list[int] = []
    for n in names:
        wanted.extend(_chunk_page_ids(fv, group, fv.column_index(n), pages))
    sp = _trace.span("decode.pread", cat="io", group=group, pages=len(wanted))
    with sp:
        raw = reader._read_pages(wanted)
        if sp.enabled:
            sp.set(bytes=sum(len(b) for b in raw.values()))
    return raw


def _page_shares(fv, pids: Sequence[int], take: np.ndarray,
                 drop_deleted: bool) -> list[np.ndarray]:
    """Each page's share of ``take`` (sorted indices into the row space the
    pages ``pids`` decode to), as indices into that page's own decoded
    rows. A page spans its row count, less its deleted rows under
    ``drop_deleted``; raw-aligned, it spans its row count."""
    page_rows = fv.arr(Sec.PAGE_ROWS, np.uint32)
    sizes = []
    for p in pids:
        dv = fv.deletion_vector(p) if drop_deleted else None
        sizes.append(int(page_rows[p]) - (0 if dv is None else int(dv.sum())))
    ends = np.cumsum(sizes, dtype=np.int64)
    cuts = np.searchsorted(take, ends)
    return [take[a:b] - (e - n) for a, b, e, n
            in zip([0, *cuts[:-1]], cuts, ends, sizes)]


def decode_pages(reader: "BullionReader", names: Sequence[str], group: int,
                 raw: dict, *, drop_deleted: bool = True,
                 dequant: bool = True,
                 pages: Optional[Sequence[int]] = None,
                 align_raw: bool = False,
                 masked_out: Optional[set] = None,
                 take: Optional[np.ndarray] = None) -> dict:
    """``decode_group`` after its pread: decode the pages ``read_pages``
    returned.

    ``take`` (sorted indices into the row space the pages decode to, with
    ``drop_deleted`` or ``align_raw``) returns each column at those rows
    only. A string page builds just the strings of its share of ``take``
    (string pages are erased in place, never compacted, so a share maps to
    in-page rows through the page's kept positions); every other page
    decodes whole, is masked or re-aligned as without ``take``, and is then
    indexed. Counts the string pages decoded at a selection in
    ``bullion.decode.selected_string_pages``."""
    if take is not None and not (drop_deleted or align_raw):
        raise ValueError("take needs drop_deleted or align_raw: a page's "
                         "share of the physical row space is unknown")
    fv = reader.footer
    cols = [fv.column_index(n) for n in names]
    kinds = fv.arr(Sec.COL_KIND, np.uint8)
    flags = fv.arr(Sec.PAGE_FLAGS, np.uint8)
    page_rows = fv.arr(Sec.PAGE_ROWS, np.uint32)
    out: dict = {}

    def _dec(c: int, p: int, share: Optional[np.ndarray]):
        """Page ``p`` decoded, and whether it is already cut to ``share``
        (its rows in the output row space; None: every row)."""
        blob = raw.get(p)
        if blob is None:
            # the verification gate removed a quarantined page (corruption
            # policy ``mask``): serve shape-stable zeros instead of failing
            # the whole group. Anything else missing is a real bug.
            if not _integrity.QUARANTINE.contains(reader.path, fv, p):
                raise KeyError(p)
            if masked_out is not None:
                masked_out.add(p)
            return _mask_fill(fv, c, int(page_rows[p])), False
        ptype = int(flags[p]) & 0x7F
        if share is None or ptype != PageType.STRING:
            return pages_mod.decode_page(ptype, blob), False
        dv = fv.deletion_vector(p) if drop_deleted else None
        rows = share if dv is None else np.flatnonzero(~dv)[share]
        return pages_mod.decode_page(ptype, blob, rows), True

    for name, c in zip(names, cols):
        pids = _chunk_page_ids(fv, group, c, pages)
        shares = [None] * len(pids) if take is None \
            else _page_shares(fv, pids, take, drop_deleted)
        sp = _trace.span("decode.decode", cat="decode", column=name,
                         pages=len(pids))
        if sp.enabled:
            sp.set(encoding=_encoding(flags, pids))
        with sp:
            parts, cut = [], []
            for p, share in zip(pids, shares):
                part, done = _dec(c, p, share)
                parts.append(part)
                cut.append(done)
            if take is not None:
                n_cut = sum(cut)
                if n_cut:
                    _metrics.counter(
                        "bullion.decode.selected_string_pages").inc(n_cut)
                if sp.enabled:
                    sp.set(rows_out=len(take))
        if drop_deleted or align_raw:
            with _trace.span("decode.mask", cat="decode", column=name):
                for i, p in enumerate(pids):
                    if cut[i]:
                        continue
                    if drop_deleted:
                        parts[i] = pages_mod.apply_dv(
                            parts[i], fv.deletion_vector(p),
                            int(page_rows[p]))
                    else:
                        parts[i] = _pad_raw(parts[i], fv.deletion_vector(p),
                                            int(page_rows[p]))
        if take is not None:
            parts = [part if done else _take(part, share)
                     for part, done, share in zip(parts, cut, shares)]
        val = parts[0] if len(parts) == 1 else _concat(parts)
        if dequant and kinds[c] == int(ColKind.SCALAR):
            spec = reader.quant_spec(c)
            if spec.mode != QuantMode.NONE:
                with _trace.span("decode.dequantize", cat="decode",
                                 column=name):
                    val = dequantize(np.asarray(val), spec)
        out[name] = val
    return out


# ---------------------------------------------------------------------------
# row-space helpers (footer-only: planning never needs a file handle)
# ---------------------------------------------------------------------------


def raw_row_count(fv, group: int) -> int:
    return int(fv.arr(Sec.ROWS_PER_GROUP, np.uint32)[group])


def group_keep(fv, group: int, col: int = 0,
               pages: Optional[Sequence[int]] = None) -> Optional[np.ndarray]:
    """Raw-row keep mask from deletion vectors (None = nothing deleted),
    over the selected pages' rows when ``pages`` restricts the chunk."""
    page_rows = fv.arr(Sec.PAGE_ROWS, np.uint32)
    parts, any_dv = [], False
    for p in _chunk_page_ids(fv, group, col, pages):
        dv = fv.deletion_vector(p)
        if dv is None:
            parts.append(np.ones(int(page_rows[p]), bool))
        else:
            parts.append(~dv)
            any_dv = True
    return np.concatenate(parts) if any_dv else None


def visible_row_count(fv, group: int) -> int:
    keep = group_keep(fv, group)
    return raw_row_count(fv, group) if keep is None else int(keep.sum())


def selected_raw_rows(fv, group: int,
                      pages: Optional[Sequence[int]]) -> Optional[np.ndarray]:
    """Group-local raw row ids covered by a page-ordinal selection (None =
    the whole group). Pages partition a chunk's rows in order, so ordinal k
    covers rows [starts[k], starts[k+1]) — identical for every column."""
    if pages is None:
        return None
    rows = fv.chunk_page_rows(group, 0).astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(rows)])
    if not len(pages):
        return np.zeros(0, np.int64)
    return np.concatenate([np.arange(starts[k], starts[k + 1])
                           for k in pages])


# ---------------------------------------------------------------------------
# predicate evaluation (NumPy or Pallas batch filter kernel)
# ---------------------------------------------------------------------------


def _f32_shrink(lo: float, hi: float) -> tuple[np.float32, np.float32]:
    """Tightest float32 interval inside the float64 one.

    Exact for float32 column data: a float32 x satisfies lo <= x <= hi iff
    it satisfies the shrunk float32 bounds.
    """
    lo32, hi32 = np.float32(lo), np.float32(hi)
    if np.float64(lo32) < lo:
        lo32 = np.nextafter(lo32, np.float32(np.inf), dtype=np.float32)
    if np.float64(hi32) > hi:
        hi32 = np.nextafter(hi32, np.float32(-np.inf), dtype=np.float32)
    return lo32, hi32


def _round_trip(layer: str, stage: Callable, fetch: Callable):
    """One device round trip of a kernel, its steps timed apart: host
    staging and the puts (``stage()``, which answers a ``kernels.Staged``),
    the jitted call's dispatch, and the wait for the output and its copy
    back (``fetch(out)``), under ``<layer>.stage``, ``.launch`` and
    ``.fetch``. Counts ``bullion.<layer>.kernel_calls``."""
    from ..kernels import launch
    with _trace.span(f"{layer}.stage", cat=layer):
        _metrics.counter(f"bullion.{layer}.kernel_calls").inc()
        staged = stage()
    with _trace.span(f"{layer}.launch", cat=layer):
        out = launch(staged)
    with _trace.span(f"{layer}.fetch", cat=layer):
        # drop the inputs' device arrays before the wait for the output;
        # inside the span, so that launch and fetch abut
        del staged
        return fetch(out)


def eval_mask(pred: Predicate, tbl: dict,
              use_kernel: Optional[bool]) -> np.ndarray:
    """Predicate -> row mask; Pallas kernel when the predicate compiles
    to conjunctive ranges over float32 columns (exact there), NumPy
    otherwise."""
    ranges = conjunctive_ranges(pred)
    kernel_ok = ranges is not None and all(
        isinstance(tbl[c], np.ndarray) and tbl[c].dtype == np.float32
        for c in ranges)
    if use_kernel and not kernel_ok:
        raise ValueError(
            "kernel filter path requires a conjunctive range predicate "
            "over float32 columns")
    if use_kernel is None:
        use_kernel = kernel_ok
    if not use_kernel:
        return evaluate(pred, tbl)
    from ..kernels.filter import ops
    names = list(ranges)

    def stage():
        bounds = [_f32_shrink(*ranges[c]) for c in names]
        return ops.stage(np.stack([np.asarray(tbl[c], np.float32)
                                   for c in names]),
                         np.asarray([b[0] for b in bounds], np.float32),
                         np.asarray([b[1] for b in bounds], np.float32))

    n = len(tbl[names[0]])
    return _round_trip("filter", stage, lambda out: ops.fetch(out, n))


# ---------------------------------------------------------------------------
# partial aggregates (NumPy or the fused Pallas filter-and-sum kernel)
# ---------------------------------------------------------------------------


def _int32_safe(dt) -> bool:
    """Does every value of this dtype (None: not an array) fit in int32?"""
    return dt is not None and (dt.kind == "i" and dt.itemsize <= 4
                               or dt.kind == "u" and dt.itemsize <= 2)


def factor_bounds(fv, group: int, factors: Sequence[str]
                  ) -> Optional[tuple[int, ...]]:
    """Largest magnitude of each factor column in the group, from its chunk
    zone map (outer bounds); None where a chunk has no min/max."""
    from ..scan.stats import HAS_MINMAX
    chunk = fv.chunk_stats()
    if chunk is None:
        return None
    out = []
    for name in factors:
        rec = chunk[group * fv.n_cols + fv.column_index(name)]
        if not int(rec["flags"]) & HAS_MINMAX:
            return None
        out.append(math.ceil(max(abs(float(rec["min"])),
                                 abs(float(rec["max"])))))
    return tuple(out)


def _host_sum_product(x: np.ndarray, y: np.ndarray) -> int:
    """Exact ``sum(x * y)`` of two integer arrays, as a Python int."""
    if max(x.dtype.itemsize, y.dtype.itemsize) > 4 \
            or x.dtype == y.dtype == np.uint32:
        # a product may pass int64: multiply Python ints
        return sum((x.astype(object) * y.astype(object)).tolist())
    p = x.astype(np.int64) * y.astype(np.int64)     # |p| < 2**63
    # 32-bit halves: neither half's sum can wrap below 2**31 rows
    return (int((p >> 32).sum()) << 32) + int((p & 0xFFFFFFFF).sum())


def _kernel_call(pred: Optional[Predicate], dtypes: dict, n: int,
                 factors: Sequence[str], bounds: Optional[Sequence[int]],
                 packed: bool) -> Optional["Call"]:
    """The fused kernel's call (``kernels.aggregate.ops.plan``) over ``n``
    rows whose columns have ``dtypes``, when the predicate is a conjunction
    of ranges, every column it reads is an integer column that fits int32,
    and the factors' magnitudes ``bounds`` keep the plain or the ``packed``
    kernel's lane sums exact; None where only NumPy is exact."""
    from ..kernels.aggregate import ops as kernel_ops
    cols = pred.columns() if pred is not None else set()
    if bounds is None or not all(_int32_safe(dtypes[c])
                                 for c in {*cols, *factors}):
        return None
    ranges = conjunctive_ranges(pred, int_columns=cols) \
        if pred is not None else {}
    if ranges is None:
        return None
    return kernel_ops.plan(ranges, factors, bounds, n, packed)


def _kernel_sum_product(call: "Call", stage: Callable) -> tuple[int, int]:
    """The fused kernel's answer to ``call``: ``stage()`` lays out and puts
    the call's columns."""
    from ..kernels.aggregate import ops as kernel_ops
    # an interval outside int32 admits no int32 value
    if any(lo > hi for lo, hi in zip(call.lo, call.hi)):
        return 0, 0
    return _round_trip("aggregate", stage, kernel_ops.fetch)


def eval_sum_product(pred: Optional[Predicate], tbl: dict,
                     factors: Sequence[str],
                     bounds: Optional[Sequence[int]],
                     use_kernel: Optional[bool],
                     rows_mask: Optional[np.ndarray] = None
                     ) -> tuple[int, int]:
    """(sum of ``factors[0] * factors[1]`` over the rows of ``tbl`` that
    pass ``pred`` and ``rows_mask``, their count), exact. The fused kernel
    runs when the predicate is a conjunction of ranges, every column it
    reads is an integer column that fits int32, and the factors'
    magnitudes ``bounds`` keep its lane sums exact; NumPy otherwise."""
    from ..kernels.aggregate import ops as kernel_ops
    a, b = factors
    n = len(tbl[a])
    call = None if rows_mask is not None else _kernel_call(
        pred, {c: getattr(v, "dtype", None) for c, v in tbl.items()}, n,
        factors, bounds, packed=False)
    if use_kernel and call is None:
        raise ValueError(
            "the aggregate kernel requires a conjunctive range predicate "
            "over int32 columns, no pinned rows, and factors whose zone "
            "maps keep its int32 lane sums exact")
    if use_kernel is None:
        use_kernel = call is not None
    if not use_kernel:
        _metrics.counter("bullion.aggregate.host_groups").inc()
        mask = evaluate(pred, tbl) if pred is not None \
            else np.ones(n, bool)
        if rows_mask is not None:
            mask &= rows_mask
        return (_host_sum_product(np.asarray(tbl[a])[mask],
                                  np.asarray(tbl[b])[mask]),
                int(mask.sum()))
    return _kernel_sum_product(call, lambda: kernel_ops.stage(
        np.stack([np.asarray(tbl[c], np.int32) for c in call.names]),
        call.lo, call.hi, call.a, call.b))


def _packed_call(reader: "BullionReader", group: int, names: Sequence[str],
                 raw: dict, *, predicate: Optional[Predicate],
                 factors: Sequence[str], bounds: Optional[Sequence[int]],
                 pages: Optional[Sequence[int]]
                 ) -> Optional[tuple[int, Callable]]:
    """The group's partial aggregate straight from the packed words of its
    pages of ``names`` (read into ``raw``), unpacked by the kernel: the
    host joins payloads and decodes nothing. Answers the group's row count
    and the call that runs the kernel. None where a page the kernel would
    read is not a whole bit-packed page of a plain int32-safe column at one
    width, carries a deletion vector or was quarantined, where the kernel
    cannot take the pages, or where only NumPy is exact: the group is then
    decoded."""
    from ..kernels.aggregate import ops as kernel_ops
    fv = reader.footer
    cols = {c: fv.column_index(c) for c in names}
    col_dtypes = fv.arr(Sec.COL_DTYPE, np.uint8)
    dtypes = {c: code_dtype(int(col_dtypes[i])) for c, i in cols.items()}
    page_rows = fv.arr(Sec.PAGE_ROWS, np.uint32)
    pids = {c: _chunk_page_ids(fv, group, i, pages) for c, i in cols.items()}
    n = sum(int(page_rows[p]) for p in pids[factors[0]])
    call = _kernel_call(predicate, dtypes, n, factors, bounds, packed=True)
    kinds = fv.arr(Sec.COL_KIND, np.uint8)
    if call is None or any(
            kinds[cols[c]] != ColKind.SCALAR
            or reader.quant_spec(cols[c]).mode != QuantMode.NONE
            or any(p not in raw or fv.deletion_vector(p) is not None
                   for p in pids[c])
            for c in call.names):
        return None
    flags = fv.arr(Sec.PAGE_FLAGS, np.uint8)
    columns, widths = [], []
    for c in call.names:
        ps = pids[c]
        with _trace.span("decode.decode", cat="decode", column=c,
                         pages=len(ps), encoding="packed"):
            parts = [bit_packed(raw[p])
                     if int(flags[p]) & 0x7F == PageType.SCALAR else None
                     for p in ps]
            if any(p is None or p.width != parts[0].width
                   or p.dtype != dtypes[c] or p.n != int(page_rows[pid])
                   for p, pid in zip(parts, ps)):
                return None
            column = kernel_ops.pack_column(
                [(p.payload, p.n, p.base) for p in parts], parts[0].width)
            if column is None:
                return None
            widths.append(parts[0].width)
            columns.append(column)

    def stage():
        _metrics.counter("bullion.aggregate.packed_groups").inc()
        return kernel_ops.stage_packed(columns, widths, n, call.lo, call.hi,
                                       call.a, call.b)

    return n, functools.partial(_kernel_sum_product, call, stage)


# ---------------------------------------------------------------------------
# the one per-group pipeline
# ---------------------------------------------------------------------------


def _page_ordinal(fv, group: int, page: int) -> int:
    """Page ordinal (position within its chunk) of a physical page. Every
    column of a group splits at the same row boundaries, so one ordinal
    names the same row range in every chunk."""
    for c in range(fv.n_cols):
        s, e = fv.chunk_pages(group, c)
        if s <= page < e:
            return page - s
    raise ValueError(f"page {page} not in group {group}")


def execute_group(reader: "BullionReader", group: int, *,
                  columns: Sequence[str] = (),
                  predicate: Optional[Predicate] = None,
                  rows: Optional[np.ndarray] = None,
                  drop_deleted: bool = True, dequant: bool = True,
                  use_kernel: Optional[bool] = None,
                  pages: Optional[Sequence[int]] = None
                  ) -> Optional[GroupResult]:
    """Decode + filter one row group with graceful degradation
    (``_under_policy``)."""
    return _under_policy(reader, group, pages, functools.partial(
        _execute_group_once, reader, group, columns=columns,
        predicate=predicate, rows=rows, drop_deleted=drop_deleted,
        dequant=dequant, use_kernel=use_kernel))


def _under_policy(reader: "BullionReader", group: int,
                  pages: Optional[Sequence[int]], once: Callable):
    """Run ``once(pages=..., masked_out=...)``, one pass over the group,
    under the corruption policy.

    The pass raises ``ShardCorruptError`` when decode-time verification
    quarantines a page. Under the ``skip`` corruption policy that page's
    *ordinal* is excluded (dropping the same row range from every column —
    the result stays rectangular) and the group retries; dropped rows are
    charged exactly once to ``IOStats.degraded_rows``. Under ``mask`` the
    verification gate already zero-filled the page; the masked rows are
    charged here. Under ``raise`` (the default) the error propagates with
    (shard, group, page).
    """
    fv = reader.footer
    policy = _integrity.corruption_policy()
    masked_out: Optional[set] = set() \
        if policy == _integrity.ON_CORRUPT_MASK else None
    if policy != _integrity.ON_CORRUPT_SKIP:
        res = once(pages=pages, masked_out=masked_out)
        if masked_out:
            page_rows = fv.arr(Sec.PAGE_ROWS, np.uint32)
            _charge_degraded(
                reader, sum(int(page_rows[p]) for p in masked_out))
        return res

    # skip mode: pre-exclude ordinals already quarantined for this exact
    # footer object, then retry as verification quarantines new ones
    n_ord = len(fv.chunk_page_rows(group, 0))
    excluded: set[int] = set()
    for p, (g, _reason) in _integrity.QUARANTINE.lookup(
            reader.path, fv).items():
        if g == group:
            excluded.add(_page_ordinal(fv, group, p))
    selected = set(range(n_ord)) if pages is None \
        else {int(k) for k in pages}
    for _ in range(n_ord + 1):
        if excluded:
            eff = sorted(selected - excluded)
        else:
            eff = pages
        try:
            res = once(pages=eff, masked_out=None)
        except ShardCorruptError as e:
            if e.page is None or e.path != reader.path:
                raise
            k = _page_ordinal(fv, group, e.page)
            if k in excluded:       # no progress: don't loop forever
                raise
            excluded.add(k)
            continue
        dropped = excluded & selected
        if dropped:
            rows_per = fv.chunk_page_rows(group, 0)
            _charge_degraded(
                reader, sum(int(rows_per[k]) for k in dropped))
        return res
    raise AssertionError("unreachable: every ordinal excluded")  # pragma: no cover


def _charge_degraded(reader: "BullionReader", n_rows: int) -> None:
    if not n_rows:
        return
    with reader._stats_lock:
        reader.stats.degraded_rows += n_rows
    _metrics.counter("bullion.integrity.degraded_rows").inc(n_rows)


def _row_space(fv, group: int, pages: Optional[Sequence[int]],
               drop_deleted: bool) -> tuple[Optional[np.ndarray], int]:
    """The raw rows a decode of the group's selected pages yields, in order
    (None = all of the group's rows), and how many there are."""
    sel_raw = selected_raw_rows(fv, group, pages)
    keep = group_keep(fv, group, pages=pages) if drop_deleted else None
    if keep is not None:
        space_raw = sel_raw[keep] if sel_raw is not None \
            else np.flatnonzero(keep)
    else:
        space_raw = sel_raw
    n_space = len(space_raw) if space_raw is not None \
        else raw_row_count(fv, group)
    return space_raw, n_space


def _rows_mask(rows: np.ndarray, space_raw: Optional[np.ndarray],
               n_space: int) -> np.ndarray:
    """Mask over the decoded rows of the pinned group-local raw ``rows``."""
    rmask = np.zeros(n_space, bool)
    if space_raw is None:
        rmask[rows[rows < n_space]] = True
    else:
        rmask[np.isin(space_raw, rows)] = True
    return rmask


def _execute_group_once(reader: "BullionReader", group: int, *,
                        columns: Sequence[str] = (),
                        predicate: Optional[Predicate] = None,
                        rows: Optional[np.ndarray] = None,
                        drop_deleted: bool = True, dequant: bool = True,
                        use_kernel: Optional[bool] = None,
                        pages: Optional[Sequence[int]] = None,
                        masked_out: Optional[set] = None
                        ) -> Optional[GroupResult]:
    """Decode + filter one row group. Returns None when a predicate or a
    row-id selection leaves no rows (payload pages are then never read).

    ``pages`` is a plan's surviving page-ordinal selection: only those
    pages are pread and decoded for every column, and reported row ids stay
    in the group's raw row space (each ordinal maps to its row range).

    Predicate columns are always evaluated in the dequantized (logical)
    domain — the domain the zone maps describe; ``dequant`` governs only the
    materialized payload. When the caller wants raw values of a predicate
    column, it is re-read in the payload pass instead of served from the
    evaluation copy.
    """
    fv = reader.footer
    if pages is not None and not len(pages):
        return None
    space_raw, n_space = _row_space(fv, group, pages, drop_deleted)

    pred_cols = sorted(predicate.columns()) if predicate is not None else []
    reuse = set(pred_cols) if dequant else set()
    tbl: dict = {}
    mask: Optional[np.ndarray] = None
    if predicate is not None:
        # compact-deleted pages shrink their decode; align_raw re-pads each
        # page to its raw row space so mask indices line up with space_raw
        tbl = decode_group(reader, pred_cols, group,
                           drop_deleted=drop_deleted, dequant=True,
                           pages=pages, align_raw=not drop_deleted,
                           masked_out=masked_out)
        sp = _trace.span("exec.filter", cat="exec", group=group)
        with sp:
            mask = eval_mask(predicate, tbl, use_kernel)
            if sp.enabled:
                sp.set(rows_in=int(len(mask)), rows_out=int(mask.sum()))
    if rows is not None:
        rmask = _rows_mask(rows, space_raw, n_space)
        mask = rmask if mask is None else mask & rmask

    if mask is None:
        local = np.arange(n_space)
        full = True
    else:
        if not mask.any():
            return None
        local = np.flatnonzero(mask)
        full = len(local) == n_space
    raw_local = local if space_raw is None else space_raw[local]

    out: dict = {}
    for name in columns:
        if name in reuse and name in tbl:
            out[name] = tbl[name] if full else _take(tbl[name], local)
    rest = [c for c in columns if c not in out]
    if rest:
        # drop_deleted=False means *raw row space*, always: compact-deleted
        # pages decode short, so every page is re-aligned (erased rows
        # read 0) to keep row_ids and all columns the same length. The
        # decode takes the surviving rows itself: string pages build only
        # their strings.
        out.update(decode_group(reader, rest, group,
                                drop_deleted=drop_deleted, dequant=dequant,
                                pages=pages, align_raw=not drop_deleted,
                                masked_out=masked_out,
                                take=None if full else local))
    return GroupResult(row_ids=raw_local, table=out)


def aggregate_group(reader: "BullionReader", group: int, *,
                    factors: Sequence[str],
                    predicate: Optional[Predicate] = None,
                    rows: Optional[np.ndarray] = None,
                    drop_deleted: bool = True,
                    use_kernel: Optional[bool] = None,
                    pages: Optional[Sequence[int]] = None
                    ) -> tuple[int, int]:
    """One row group's partial aggregate: (sum of the factors' products
    over its rows that pass ``predicate`` and the pinned ``rows``, their
    count), under the corruption policy as ``execute_group``. The group's
    rows never leave the executor."""
    return _under_policy(reader, group, pages, functools.partial(
        _aggregate_group_once, reader, group, factors=factors,
        predicate=predicate, rows=rows, drop_deleted=drop_deleted,
        use_kernel=use_kernel))


def _aggregate_group_once(reader: "BullionReader", group: int, *,
                          factors: Sequence[str],
                          predicate: Optional[Predicate],
                          rows: Optional[np.ndarray], drop_deleted: bool,
                          use_kernel: Optional[bool],
                          pages: Optional[Sequence[int]],
                          masked_out: Optional[set]) -> tuple[int, int]:
    fv = reader.footer
    if pages is not None and not len(pages):
        return 0, 0
    pred_cols = sorted(predicate.columns()) if predicate is not None else []
    names = list(dict.fromkeys([*pred_cols, *factors]))
    raw = read_pages(reader, names, group, pages)
    bounds = factor_bounds(fv, group, factors)
    trip = None
    if rows is None and use_kernel is not False:
        trip = _packed_call(reader, group, names, raw, predicate=predicate,
                            factors=factors, bounds=bounds, pages=pages)
    if trip is None:
        tbl = decode_pages(reader, names, group, raw,
                           drop_deleted=drop_deleted, dequant=True,
                           pages=pages, align_raw=not drop_deleted,
                           masked_out=masked_out)
        rows_mask = None if rows is None else _rows_mask(
            rows, *_row_space(fv, group, pages, drop_deleted))
        trip = len(tbl[factors[0]]), functools.partial(
            eval_sum_product, predicate, tbl, factors, bounds, use_kernel,
            rows_mask)
    n, run = trip
    sp = _trace.span("exec.aggregate", cat="exec", group=group)
    with sp:
        value, count = run()
        if sp.enabled:
            sp.set(rows_in=n, rows_out=count)
    return value, count


# ---------------------------------------------------------------------------
# parallel task execution (bounded thread pool, deterministic order)
# ---------------------------------------------------------------------------


def run_tasks(tasks, fn, parallelism: int = 1, io=None):
    """Execute ``fn(task)`` for every task, yielding ``(task, result)``
    strictly in task order.

    ``parallelism <= 1`` is the plain serial loop (zero overhead, the
    default). Above that, up to ``parallelism`` tasks run concurrently on a
    thread pool with a bounded in-flight window (results are buffered at
    most ``2 * parallelism`` deep), so a consumer that stops early — a
    ``head`` limit, an aborted iteration — never waits on more than the
    window. Per-(shard, row-group) tasks are independent and readers use
    positional I/O on one shared fd per shard, so ordering the *yields* is
    all determinism needs: parallel and serial runs produce identical
    streams.

    ``io`` is an optional pipelined I/O scheduler (``dataset.io
    .IOScheduler``) whose lifecycle this loop owns: started before the first
    task runs, closed when iteration finishes *or* is abandoned early, so
    its prefetch thread never outlives the scan. ``fn`` decides whether to
    pull its reader from the scheduler.
    """
    tasks = list(tasks)
    if io is not None:
        io.start()
    try:
        if parallelism <= 1 or len(tasks) <= 1:
            for t in tasks:
                yield t, fn(t)
            return
        from collections import deque
        from concurrent.futures import ThreadPoolExecutor

        ex = ThreadPoolExecutor(max_workers=parallelism,
                                thread_name_prefix="bullion-scan")
        pending: deque = deque()
        it = iter(tasks)
        try:
            def fill() -> None:
                while len(pending) < 2 * parallelism:
                    t = next(it, None)
                    if t is None:
                        return
                    pending.append((t, ex.submit(fn, t)))

            fill()
            while pending:
                t, fut = pending.popleft()
                yield t, fut.result()
                fill()
        finally:
            for _, fut in pending:
                fut.cancel()
            ex.shutdown(wait=True)
    finally:
        if io is not None:
            io.close()


def truncate_result(res: GroupResult, n: int) -> GroupResult:
    """Keep the first n rows of a group result (head limit)."""
    return GroupResult(row_ids=res.row_ids[:n],
                       table={k: v[:n] for k, v in res.table.items()})


def _take(values, idx: np.ndarray):
    if isinstance(values, np.ndarray):
        return values[idx]
    return [values[i] for i in idx]


def _concat(parts):
    if isinstance(parts[0], np.ndarray):
        return np.concatenate(parts)
    return [r for p in parts for r in p]
