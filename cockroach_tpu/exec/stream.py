"""Streamed-page data plane: chunk prefix offsets, zone-map page
skipping, and a bounded background prefetch pipeline.

Beyond-HBM execution pages the fact table through the device
(scanplane._stream_pages / session.Prepared.dispatch). Before this
module, every page was assembled on the host BETWEEN device
dispatches — slice the chunk list from index 0, concatenate, pad,
upload, compute, repeat — so the device idled during host work and
the host idled during device work. Theseus-style engines live or die
by overlapping those two (PAPERS.md); this module supplies the
overlap:

  PageSource     one-time setup per execution (sealed chunk snapshot,
                 prefix offsets, zone-pred column wiring),
                 then O(log chunks) page addressing instead of an
                 O(chunks) rescan per column per page.
  ZonePred       per-chunk min/max/null-count summaries (storage
                 Chunk.zone) checked against the plan's pushed-down
                 scan predicates: a page whose zone cannot satisfy
                 every conjunct never leaves the host (the
                 provenance-based data-skipping result — most pages
                 of a selective filtered scan never needed to move).
  prefetch()     a depth-bounded worker thread assembles+uploads page
                 i+1 while the device computes page i, with exception
                 propagation and deterministic shutdown.

Zone checks are CONSERVATIVE by construction: bounds cover all row
versions and all-NULL/NaN/object chunks report unknown bounds (never
skip), so MVCC visibility, deletes, and odd dtypes can only cause a
page to be kept, never wrongly dropped.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from ..ops.batch import (MVCC_COLUMNS, NEVER_TS, ColumnBatch,
                         alloc_mvcc_words, const_mvcc_words,
                         fill_mvcc_words, put_mvcc_words)
from ..sql import bound as B
from ..sql import plan as P

PREFETCH_DEPTH = 2


# ---------------------------------------------------------------------------
# zone-map predicates
# ---------------------------------------------------------------------------

@dataclass
class ZonePred:
    """One pushed-down conjunct compiled to a zone check.

    ``check(lo, hi, nulls, nvalid) -> bool`` answers "may any row of
    a page with this combined zone satisfy the conjunct?"; False
    means the whole page is skippable. ``lo``/``hi`` may be None
    (unknown bounds — checks must return True unless nvalid rules the
    page out on its own). ``col`` is None for row-independent
    conjuncts (a constant-folded FALSE filter skips every page).

    ``member`` optionally refines the range verdict per chunk: an
    object with ``chunk_ok(chunk, col) -> bool`` (a semi-join filter,
    exec/joinfilter.JoinFilter) consulted only when the range check
    passes — False means no key of that chunk can match. ``joinfilter``
    marks predicates derived from a join build side so skips they
    cause are attributed to exec.skip.joinfilter.* instead of the
    plain scan-predicate family."""
    col: object   # stored column name, or None (row-independent)
    check: object
    member: object = None
    joinfilter: bool = False


def _cmp_check(op: str, v):
    def check(lo, hi, nulls, nvalid):
        # NULL never satisfies a comparison, so an all-null page is
        # out regardless of bounds
        if nvalid == 0:
            return False
        if lo is None:
            return True
        if op == "<":
            return lo < v
        if op == "<=":
            return lo <= v
        if op == ">":
            return hi > v
        if op == ">=":
            return hi >= v
        if op == "=":
            return lo <= v <= hi
        return not (lo == hi == v)  # "!="
    return check


def _between_check(vlo, vhi):
    def check(lo, hi, nulls, nvalid):
        if nvalid == 0:
            return False
        if lo is None:
            return True
        return not (hi < vlo or lo > vhi)
    return check


def _inlist_check(values):
    def check(lo, hi, nulls, nvalid):
        if nvalid == 0:
            return False
        if lo is None:
            return True
        return any(lo <= v <= hi for v in values)
    return check


def _isnull_check(negated: bool):
    def check(lo, hi, nulls, nvalid):
        return nvalid > 0 if negated else nulls > 0
    return check


def _dict_check(table):
    # dictionary codes are small dense ints: the chunk's code range
    # indexes straight into the host-evaluated predicate mask
    def check(lo, hi, nulls, nvalid):
        if nvalid == 0:
            return False
        if lo is None:
            return True
        a = max(int(lo), 0)
        b = min(int(hi), len(table) - 1)
        return a <= b and bool(table[a:b + 1].any())
    return check


_CMP_OPS = {"<", "<=", ">", ">=", "=", "!="}


def _compile_conjunct(e, colmap: dict):
    """One conjunct -> ZonePred, or None for shapes zone maps cannot
    judge (those simply contribute no skipping)."""
    def col_of(x):
        if isinstance(x, B.BCol):
            return colmap.get(x.name)
        return None

    if isinstance(e, B.BConst):
        # the planner constant-folds unsatisfiable predicates (e.g.
        # equality against a value absent from a string dictionary)
        # to FALSE/NULL — neither admits any row, so every page skips
        if e.value:
            return None  # constant TRUE: no constraint
        return ZonePred(None, lambda lo, hi, nulls, nvalid: False)
    if isinstance(e, B.BBin) and e.op in _CMP_OPS:
        lc, rc = col_of(e.left), col_of(e.right)
        if lc is not None and isinstance(e.right, B.BConst):
            v = e.right.value
            return None if v is None else ZonePred(lc, _cmp_check(e.op, v))
        if rc is not None and isinstance(e.left, B.BConst):
            v = e.left.value
            if v is None:
                return None
            flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}
            return ZonePred(rc, _cmp_check(flip.get(e.op, e.op), v))
        return None
    if isinstance(e, B.BBetween) and not e.negated:
        c = col_of(e.expr)
        if c is not None and isinstance(e.lo, B.BConst) \
                and isinstance(e.hi, B.BConst) \
                and e.lo.value is not None and e.hi.value is not None:
            return ZonePred(c, _between_check(e.lo.value, e.hi.value))
        return None
    if isinstance(e, B.BInList) and not e.negated:
        c = col_of(e.expr)
        vals = [v for v in e.values if v is not None]
        if c is not None and vals:
            return ZonePred(c, _inlist_check(vals))
        return None
    if isinstance(e, B.BIsNull):
        c = col_of(e.expr)
        if c is not None:
            return ZonePred(c, _isnull_check(e.negated))
        return None
    if isinstance(e, B.BDictLookup):
        c = col_of(e.expr)
        if c is not None and e.table is not None:
            return ZonePred(c, _dict_check(np.asarray(e.table)))
        return None
    return None


def _split_and(e, out: list):
    if isinstance(e, B.BBin) and e.op == "and":
        _split_and(e.left, out)
        _split_and(e.right, out)
    else:
        out.append(e)


def extract_zone_preds(node: P.PlanNode, alias: str) -> tuple:
    """Compile the plan's pushed-down predicates over the streamed
    scan `alias` into zone checks: the scan's own fused filter plus
    any Filter separated from it only by Filter/Compact nodes
    (predicates above a Project or Join may reference renamed or
    joined columns and are not zone-judgeable)."""
    chain = _find_chain(node, alias)
    if chain is None:
        return ()
    scan = chain[0]
    conjuncts: list = []
    if scan.filter is not None:
        _split_and(scan.filter, conjuncts)
    for anc in chain[1:]:
        if isinstance(anc, P.Compact):
            continue
        if isinstance(anc, P.Filter):
            if anc.pred is not None:
                _split_and(anc.pred, conjuncts)
            continue
        break
    preds = [_compile_conjunct(e, scan.columns) for e in conjuncts]
    return tuple(p for p in preds if p is not None)


def _find_chain(node, alias):
    """Ancestor chain [scan, parent, ..., root] of the aliased scan."""
    if isinstance(node, P.Scan):
        return [node] if node.alias == alias else None
    for attr in ("child", "left", "right"):
        c = getattr(node, attr, None)
        if c is not None:
            r = _find_chain(c, alias)
            if r is not None:
                r.append(node)
                return r
    return None


# ---------------------------------------------------------------------------
# page assembly
# ---------------------------------------------------------------------------

class PageSource:
    """Assembles fixed-shape host pages from a sealed chunk snapshot.

    Setup (chunk snapshot, prefix offsets, zone-pred column wiring)
    happens once per execution; per page the chunk span is a binary
    search over the prefix array and each column is one fill of a
    buffer allocated for that page — no concatenate+pad double
    allocation, no per-page chunk-list rescan.

    A page's host buffers are its own and are never written again
    once the page is handed over; one set reused for every page is
    not safe behind any upload call. jnp.array does not own a copy
    when it returns: it hands the numpy buffer to the device (aliased
    zero-copy on the CPU backend, a transfer in flight on a TPU) and
    copies in a program dispatched asynchronously, so a prefetch
    worker's refill for page i+1 races the copy of page i and a
    streamed aggregate now and then sums the next page's rows
    (PERF.md PR 34)."""

    def __init__(self, td, cols, page_rows: int, zone_preds=(),
                 metrics=None, read_ts=None):
        self.chunks = list(td.chunks)
        self.page_rows = page_rows
        # MVCC window skipping (AS OF SYSTEM TIME / TTL / CDC): a
        # chunk whose seal-time [ts_min, del_max) window excludes
        # read_ts holds no visible version at all (storage/chunkstats
        # docstring has the no-invalidation argument)
        self.read_ts = None if read_ts is None else int(read_ts)
        self.offs = np.zeros(len(self.chunks) + 1, dtype=np.int64)
        if self.chunks:
            np.cumsum([c.n for c in self.chunks], out=self.offs[1:])
        self.total = int(self.offs[-1])
        self.names = [c.name for c in td.schema.columns
                      if cols is None or c.name in cols]
        self.dtypes = {c.name: np.dtype(c.type.np_dtype)
                       for c in td.schema.columns
                       if cols is None or c.name in cols}
        self.zone_preds = tuple(zone_preds)
        self.page_bytes = page_rows * (
            16 + sum(d.itemsize + 1 for d in self.dtypes.values()))
        self._m_pages = self._m_skipped = None
        self._m_bytes = self._m_bytes_skipped = None
        self._m_jf_pages = self._m_jf_bytes = None
        self._m_mv_pages = self._m_mv_bytes = None
        if metrics is not None:
            self._m_pages = metrics.counter(
                "exec.stream.pages", "streamed pages uploaded to HBM")
            self._m_skipped = metrics.counter(
                "exec.stream.pages_skipped",
                "streamed pages pruned by zone maps (never uploaded)")
            self._m_bytes = metrics.counter(
                "exec.stream.bytes",
                "host->device bytes moved by streamed pages")
            self._m_bytes_skipped = metrics.counter(
                "exec.stream.bytes_skipped",
                "host->device bytes avoided by zone-map page skipping")
            self._m_jf_pages = metrics.counter(
                "exec.skip.joinfilter.pages",
                "streamed pages pruned by a semi-join filter derived "
                "from a hash-join build side")
            self._m_jf_bytes = metrics.counter(
                "exec.skip.joinfilter.bytes",
                "host->device bytes avoided by join-induced skipping")
            self._m_mv_pages = metrics.counter(
                "exec.skip.mvcc.pages",
                "streamed pages pruned by the chunk MVCC window "
                "(every version outside the read timestamp)")
            self._m_mv_bytes = metrics.counter(
                "exec.skip.mvcc.bytes",
                "host->device bytes avoided by MVCC window skipping")

    def _alloc(self, n: int) -> dict:
        """One page's unwritten host buffers, n rows."""
        bufs = {cn: np.empty(n, dtype=dt)
                for cn, dt in self.dtypes.items()}
        bufs.update(alloc_mvcc_words(n))
        return bufs

    def _upload(self, bufs: dict, vmap: dict) -> ColumnBatch:
        """The device batch of one assembled page."""
        return ColumnBatch.from_dict(
            # graftlint: waive[no-aliasing-upload] bufs and vmap are
            # this page's own arrays (_alloc, and the np.ones vbufs of
            # _assemble/_gather_into); nothing writes them after this
            {cn: jnp.asarray(bufs[cn])
             for cn in (*self.names, *MVCC_COLUMNS)},
            {cn: jnp.asarray(v) for cn, v in vmap.items()})

    def _page_zone_ok(self, i0: int, i1: int) -> bool:
        ok, _ = self._page_verdict(i0, i1)
        return ok

    def _page_mvcc_ok(self, i0: int, i1: int) -> bool:
        """May any chunk in [i0..i1) hold a version visible at
        read_ts? Seal-time windows only: ts_min is exact forever and
        del_max only shrinks after seal, so the stored bound stays a
        valid upper bound (storage/chunkstats)."""
        rts = self.read_ts
        for ci in range(i0, i1):
            ts_min, del_max = self.chunks[ci].mvcc_window()
            if ts_min <= rts < del_max:
                return True
        return False

    def _page_verdict(self, i0: int, i1: int):
        """(may_match, by_joinfilter) for rows [chunks i0..i1) against
        every pushed-down conjunct. Chunk zones are supersets of any
        partial overlap, so combining them stays conservative; a
        pred's ``member`` refines the range verdict chunk by chunk
        (the page survives if ANY chunk's key set may match)."""
        for p in self.zone_preds:
            if p.col is None:  # row-independent (constant FALSE)
                if not p.check(None, None, 0, 0):
                    return False, p.joinfilter
                continue
            lo = hi = None
            nulls = nvalid = 0
            unknown = False
            absent = False
            for ci in range(i0, i1):
                try:
                    zlo, zhi, zn, zv = self.chunks[ci].zone(p.col)
                except KeyError:
                    absent = True  # column absent (shouldn't happen)
                    break
                nulls += zn
                nvalid += zv
                if zv > 0:
                    if zlo is None:
                        unknown = True
                    else:
                        lo = zlo if lo is None else min(lo, zlo)
                        hi = zhi if hi is None else max(hi, zhi)
            if absent:
                continue
            if unknown:
                lo = hi = None
            if not p.check(lo, hi, nulls, nvalid):
                return False, p.joinfilter
            if p.member is not None and not unknown:
                try:
                    if not any(p.member.chunk_ok(self.chunks[ci], p.col)
                               for ci in range(i0, i1)):
                        return False, p.joinfilter
                except Exception:
                    pass  # membership is an optimization: keep the page
        return True, False

    def _skip_page(self, by_joinfilter: bool, mvcc: bool = False):
        if self._m_skipped is not None:
            self._m_skipped.inc()
            self._m_bytes_skipped.inc(self.page_bytes)
            if mvcc:
                self._m_mv_pages.inc()
                self._m_mv_bytes.inc(self.page_bytes)
            elif by_joinfilter:
                self._m_jf_pages.inc()
                self._m_jf_bytes.inc(self.page_bytes)

    def pages(self):
        """Yield device ColumnBatch pages, skipping zone-pruned and
        MVCC-window-excluded ones."""
        start = 0
        while start < self.total:
            end = min(start + self.page_rows, self.total)
            i0 = int(np.searchsorted(self.offs, start, side="right")) - 1
            i1 = int(np.searchsorted(self.offs, end, side="left"))
            if self.read_ts is not None \
                    and not self._page_mvcc_ok(i0, i1):
                self._skip_page(False, mvcc=True)
                start = end
                continue
            if self.zone_preds:
                ok, jf = self._page_verdict(i0, i1)
                if not ok:
                    self._skip_page(jf)
                    start = end
                    continue
            yield self._assemble(start, end, i0, i1)
            start = end

    def _assemble(self, start: int, end: int, i0: int, i1: int):
        bufs = self._alloc(self.page_rows)
        n = end - start
        vmap: dict[str, np.ndarray] = {}
        for cn in self.names:
            buf = bufs[cn]
            any_invalid = False
            vbuf = None
            for ci in range(i0, i1):
                c = self.chunks[ci]
                coff = int(self.offs[ci])
                lo, hi = max(start - coff, 0), min(end - coff, c.n)
                dst = coff + lo - start
                buf[dst:dst + hi - lo] = c.data[cn][lo:hi]
                v = c.valid[cn][lo:hi]
                if not v.all():
                    if vbuf is None:
                        vbuf = np.ones(self.page_rows, dtype=bool)
                    vbuf[dst:dst + hi - lo] = v
                    any_invalid = True
            buf[n:] = 0
            if any_invalid:
                vbuf[n:] = False
                vmap[cn] = vbuf
        for ci in range(i0, i1):
            c = self.chunks[ci]
            coff = int(self.offs[ci])
            lo, hi = max(start - coff, 0), min(end - coff, c.n)
            put_mvcc_words(bufs, coff + lo - start,
                           c.mvcc_ts[lo:hi], c.mvcc_del[lo:hi])
        # padding rows are never visible (as scanplane.
        # _batch_from_chunks pads a resident upload)
        fill_mvcc_words(bufs, n, self.page_rows, NEVER_TS, 0)
        batch = self._upload(bufs, vmap)
        if self._m_pages is not None:
            self._m_pages.inc()
            self._m_bytes.inc(self.page_bytes)
        return batch

    # -- spill-tier gather assembly (exec/spill.py) ---------------------

    def _gather_into(self, bufs, idx: np.ndarray, n_pad: int) -> dict:
        """Fill ``bufs[:len(idx)]`` with the rows at ASCENDING global
        row indices ``idx`` and pad the tail never-visible. Ascending
        order makes chunk ids nondecreasing, so the gather is one
        fancy-index per (column, chunk-run) — the same cost shape as
        _assemble's contiguous fills. Returns the validity map."""
        n = len(idx)
        if n:
            ci = np.searchsorted(self.offs, idx, side="right") - 1
            bounds = np.flatnonzero(np.diff(ci)) + 1
            starts = np.concatenate(([0], bounds))
            ends = np.concatenate((bounds, [n]))
            runs = [(self.chunks[ci[s]],
                     idx[s:e] - self.offs[ci[s]], s, e)
                    for s, e in zip(starts, ends)]
        else:
            runs = []
        vmap: dict[str, np.ndarray] = {}
        for cn in self.names:
            buf = bufs[cn]
            vbuf = None
            for c, loc, s, e in runs:
                buf[s:e] = c.data[cn][loc]
                v = c.valid[cn][loc]
                if not v.all():
                    if vbuf is None:
                        vbuf = np.ones(n_pad, dtype=bool)
                    vbuf[s:e] = v
            buf[n:n_pad] = 0
            if vbuf is not None:
                vbuf[n:n_pad] = False
                vmap[cn] = vbuf
        for c, loc, s, _e in runs:
            put_mvcc_words(bufs, s, c.mvcc_ts[loc], c.mvcc_del[loc])
        fill_mvcc_words(bufs, n, n_pad, NEVER_TS, 0)
        return vmap

    def gather_batch(self, idx: np.ndarray, n_pad: int):
        """One device batch of exactly ``n_pad`` rows holding the rows
        at ascending global indices ``idx`` (a spill-join build
        partition: every partition pads to ONE shared shape-ladder
        bucket — exec/coldstart.ShapeLadder, the same ladder resident
        uploads and streamed pages use — so a single XLA program
        serves the whole partition sweep)."""
        bufs = self._alloc(n_pad)
        return self._upload(bufs, self._gather_into(bufs, idx, n_pad))

    def gather_pages(self, idx: np.ndarray):
        """Yield page_rows-shaped device pages of the rows at ascending
        global indices ``idx`` (a spill-join probe partition)."""
        for start in range(0, len(idx), self.page_rows):
            yield self.gather_batch(idx[start:start + self.page_rows],
                                    self.page_rows)

    def empty_page(self):
        """A page of only never-visible padding rows: runs the page
        program to its identity state when zone maps pruned every
        real page (an aggregate must still produce its empty
        result)."""
        cols = {cn: np.zeros(self.page_rows, dtype=dt)
                for cn, dt in self.dtypes.items()}
        cols.update(const_mvcc_words(self.page_rows, NEVER_TS, 0))
        return ColumnBatch.from_dict(
            # graftlint: waive[no-aliasing-upload] cols are np.zeros/
            # const_mvcc_words buffers allocated just above, never
            # written again
            {cn: jnp.asarray(v) for cn, v in cols.items()}, {})


# ---------------------------------------------------------------------------
# bounded prefetch
# ---------------------------------------------------------------------------

_DONE = ("done", None)


def prefetch(it, depth: int = PREFETCH_DEPTH, stall_hist=None):
    """Run iterator `it` on a background thread, at most `depth`
    items ahead of the consumer.

    Returns a generator yielding `it`'s items in order. A worker
    exception re-raises at the consumer's next pull; closing the
    generator (break / GC / .close()) stops and joins the worker —
    no thread outlives the iteration. `stall_hist` observes the
    consumer-side wait per item (zero when the pipeline is ahead —
    the number to watch when tuning depth/page size)."""
    q: queue.Queue = queue.Queue(maxsize=max(1, depth))
    stop = threading.Event()

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for item in it:
                if not _put(("ok", item)):
                    return
        except BaseException as e:  # propagate to the consumer
            _put(("err", e))
            return
        _put(_DONE)

    t = threading.Thread(target=worker, name="page-prefetch",
                         daemon=True)

    def gen():
        t.start()
        try:
            while True:
                t0 = time.monotonic()
                kind, val = q.get()
                if stall_hist is not None:
                    stall_hist.observe(time.monotonic() - t0)
                if kind == "done":
                    return
                if kind == "err":
                    raise val
                yield val
        finally:
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=10.0)

    return gen()
