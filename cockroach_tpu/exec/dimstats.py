"""What a dimension table's filter keeps, read off its host columns.

The planner's selectivities take a filter's columns as independent of
each other and of the join key (Engine._estimate_scan_selectivity): right
for a dimension whose key spans what the fact table references, wrong
by a factor of forty for a date dimension of two centuries that a fact
table references five years of (TPC-DS: `d_year = 2002` keeps 1/201 of
date_dim and a fifth of store_sales). A dimension is small, so where
it is (at most DIM_ROWS_MAX rows) this module evaluates the scan's
filter over the table's host columns instead:

  join_share   of the build rows a probe key's stored range reaches,
               the share the filter keeps: the share of probe rows an
               inner join keeps, for keys spread evenly over the range
  tuples       the distinct tuples of some columns among the rows the
               filter keeps: at most so many groups of those keys

Conjuncts it cannot read count as true, so a share is never below the
truth for that reason. Estimates only, for capacities that have their
own sentinels; every result is cached on the table's generation.
"""

from __future__ import annotations

import math
import threading

import numpy as np

from ..sql import plan as P
from ..sql.bound import BBetween, BBin, BCol, BConst, BInList, BUnary

DIM_ROWS_MAX = 1 << 21
_CMP = {"=": np.equal, "<>": np.not_equal, "!=": np.not_equal,
        "<": np.less, "<=": np.less_equal, ">": np.greater,
        ">=": np.greater_equal}


class DimStats:
    """Host-side facts of a store's small tables, cached by generation."""

    def __init__(self, store):
        self.store = store
        self._lock = threading.Lock()
        self._cache: dict = {}

    def _memo(self, key, make):
        with self._lock:
            if key in self._cache:
                return self._cache[key]
        val = make()
        with self._lock:
            if len(self._cache) > 4096:
                self._cache.clear()
            self._cache[key] = val
        return val

    def _column(self, table: str, col: str, gen: int):
        def make():
            td = self.store.table(table)
            parts = [c.data[col] for c in td.chunks]
            return np.concatenate(parts) if parts else np.zeros(0)
        return self._memo(("col", table, col, gen), make)

    def _small(self, table: str):
        """The table's generation, or None where it is no dimension."""
        try:
            self.store.seal(table)
            td = self.store.table(table)
        except KeyError:
            return None
        if td.row_count > DIM_ROWS_MAX or td.open_ts:
            return None
        return td.generation

    def _mask(self, scan: P.Scan, gen: int):
        """The rows of the table the scan's filter keeps (None: no
        filter, or nothing of it understood)."""
        def ev(e):
            if isinstance(e, BBin) and e.op in ("and", "or"):
                a, b = ev(e.left), ev(e.right)
                if e.op == "and":
                    return b if a is None else a if b is None else a & b
                return None if a is None or b is None else a | b
            if isinstance(e, BUnary) and e.op == "not":
                a = ev(e.operand)
                return None if a is None else ~a
            col = _col_of(e)
            if col is None or col not in scan.columns:
                return None
            x = self._column(scan.table, scan.columns[col], gen)
            if isinstance(e, BBin):
                l, r, op = e.left, e.right, e.op
                if not isinstance(l, BCol):
                    l, r = r, l
                    op = {"<": ">", "<=": ">=", ">": "<",
                          ">=": "<="}.get(op, op)
                v = _const(r)
                return _CMP[op](x, v) if op in _CMP and v is not None \
                    else None
            if isinstance(e, BBetween):
                lo, hi = _const(e.lo), _const(e.hi)
                if lo is None or hi is None:
                    return None
                m = (x >= lo) & (x <= hi)
                return ~m if e.negated else m
            if isinstance(e, BInList):
                m = np.isin(x, list(e.values))
                return ~m if e.negated else m
            return None
        if scan.filter is None:
            return None
        return self._memo(("mask", scan.table, repr(scan.filter), gen),
                          lambda: ev(scan.filter))

    def join_share(self, build: P.Scan, key_col: str, window) -> tuple:
        """(share, restricted): of the build rows whose key lies in
        `window` ((lo, hi) of the probe key's stored values, or None),
        the share the build's filter keeps; `restricted` where the
        window leaves out a tenth or more of the build's rows. (None,
        False) where the build is no small table, has no filter this
        module reads, or the window holds none of its keys."""
        gen = self._small(build.table)
        stored = build.columns.get(key_col)
        if gen is None or stored is None:
            return None, False
        mask = self._mask(build, gen)
        if mask is None:
            return None, False

        def make():
            keys = self._column(build.table, stored, gen)
            inwin = np.ones(len(keys), bool) if window is None else \
                (keys >= window[0]) & (keys <= window[1])
            n = int(inwin.sum())
            kept = int((mask & inwin).sum())
            if not n or not kept:
                return None, False
            # up to a quarter octave, so that a year of 366 days and
            # one of 365 give one plan (a Compact's capacity follows)
            share = 2.0 ** (math.ceil(4 * math.log2(kept / n)) / 4)
            return min(share, 1.0), n < 0.9 * len(keys)
        return self._memo(("share", build.table, repr(build.filter),
                           stored, window, gen), make)

    def tuples(self, scan: P.Scan, cols: tuple):
        """Distinct tuples of the stored columns `cols` among the rows
        the scan's filter keeps (all rows without one); None where the
        table is no small one."""
        gen = self._small(scan.table)
        if gen is None:
            return None

        def make():
            mask = self._mask(scan, gen)
            arrays = [self._column(scan.table, c, gen) for c in cols]
            if not arrays or not len(arrays[0]):
                return 0
            if mask is not None:
                arrays = [a[mask] for a in arrays]
            if not len(arrays[0]):
                return 0
            return int(len(np.unique(np.stack(arrays), axis=1)[0]))
        return self._memo(("tuples", scan.table, repr(scan.filter),
                           tuple(cols), gen), make)


def _const(e):
    """The value of a constant expression (Q67's `{dms}+11`), or None."""
    if isinstance(e, BConst):
        return e.value
    if isinstance(e, BBin) and e.op in ("+", "-", "*"):
        a, b = _const(e.left), _const(e.right)
        if isinstance(a, int) and isinstance(b, int):
            return a + b if e.op == "+" else a - b if e.op == "-" \
                else a * b
    return None


def _col_of(e):
    """The batch column a simple conjunct compares, or None."""
    if isinstance(e, BBin):
        for side in (e.left, e.right):
            if isinstance(side, BCol):
                return side.name
        return None
    if isinstance(e, (BBetween, BInList)) and isinstance(e.expr, BCol):
        return e.expr.name
    return None
