"""Table statistics + a simple cost model for the planner.

The analogue of pkg/sql/stats (table statistics + histograms feeding
the optimizer's costing, opt/memo/statistics_builder.go). ANALYZE
<table> computes exact per-column distinct counts and null fractions
over the live rows (our tables are host-resident columns, so "exact"
is one np.unique per column — the reference samples because its data
lives behind the KV API). Row counts are always exact and free.

The cost model is deliberately small: cardinality estimates drive two
real decisions — hash-join build-side selection and the EXPLAIN cost
column — matching the round-2 goal (VERDICT #10), not the reference's
full memo/xform search (opt/xform/optimizer.go:239, later rounds).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

from . import plan as P

# default selectivities when no stats apply (the reference's
# unknownFilterSelectivity-style constants, statistics_builder.go)
SEL_EQ = 0.1
SEL_RANGE = 1.0 / 3.0
SEL_OTHER = 0.5


@dataclass
class TableStats:
    row_count: int = 0
    distinct: dict = field(default_factory=dict)   # col -> n distinct
    null_frac: dict = field(default_factory=dict)  # col -> fraction
    analyzed: bool = False
    # where the numbers came from: "analyze" (exact, explicit pass),
    # "sketch" (seal-time HLL/zone summaries), "default" (row count
    # only). EXPLAIN ANALYZE prints this per scan; the optimizer
    # metrics classify plans by it.
    source: str = "default"
    # live rows when an ANALYZE computed these stats (-1 = not an
    # ANALYZE). The staleness check compares against the current
    # row_count so exact-but-wrong numbers stop winning forever.
    analyzed_rows: int = -1
    # sketch-derived per-chunk summaries (stored-column name ->
    # [(lo, hi, nulls, nvalid) per chunk] / [BlockedBloom|None per
    # chunk]): predicate selectivity sums per-chunk overlap fractions
    # instead of applying SEL_EQ/SEL_RANGE constants. Empty for
    # analyze/default stats.
    zones: dict = field(default_factory=dict)
    blooms: dict = field(default_factory=dict)


# widest integer domain ANALYZE counts with one flag a value (a byte
# each) in place of a sort
ANALYZE_FLAG_DOMAIN = 1 << 27


def _column_distinct(td, cn: str, lives: list) -> tuple[int, int]:
    """(distinct values, NULLs) of one column over the live rows.
    `lives[i]` is chunk i's live mask, None where every row is live.
    An integer column (dictionary codes among them) whose zone maps
    span a modest domain marks each value it meets in a flag array —
    one pass, a chunk at a time — where anything else is gathered and
    sorted (np.unique), as every column was before."""
    nulls = 0
    parts = []
    for chunk, live in zip(td.chunks, lives):
        v, d = chunk.valid[cn], chunk.data[cn]
        if live is not None:
            v, d = v[live], d[live]
        nvalid = int(v.sum())
        nulls += len(v) - nvalid
        parts.append(d if nvalid == len(v) else d[v])
    kind = {p.dtype.kind for p in parts}
    if kind <= {"i", "u"} and parts:
        zones = [c.zone(cn) for c in td.chunks]
        los = [z[0] for z in zones if z[0] is not None]
        if los:
            lo = min(los)
            span = max(z[1] for z in zones if z[1] is not None) - lo + 1
            if span <= ANALYZE_FLAG_DOMAIN:
                seen = np.zeros(span, dtype=bool)
                for p in parts:
                    seen[p.astype(np.int64) - lo] = True
                return int(seen.sum()), nulls
    arr = np.concatenate(parts) if parts else np.zeros(0)
    return (int(len(np.unique(arr))) if arr.size else 0), nulls


def analyze_columns(td) -> TableStats:
    """Exact stats over a table's live rows (ANALYZE). The columns are
    counted side by side (numpy gives up the interpreter lock in its
    loops): sixteen columns of a 60M-row table are a few seconds, not
    sixteen 60M-row sorts."""
    from ..storage.chunkstats import MAX_WORKERS
    from ..storage.columnstore import MAX_TS_INT

    st = TableStats(analyzed=True, source="analyze")
    total = 0
    lives = []
    for chunk in td.chunks:
        live = chunk.mvcc_del == MAX_TS_INT
        n_live = int(live.sum())
        total += n_live
        lives.append(None if n_live == chunk.n else live)
    st.row_count = total
    st.analyzed_rows = total
    names = [c.name for c in td.schema.columns]
    workers = min(len(names), os.cpu_count() or 1, MAX_WORKERS)
    if workers > 1 and len(td.chunks) > 1:
        with ThreadPoolExecutor(max_workers=workers) as pool:
            counted = list(pool.map(
                lambda cn: _column_distinct(td, cn, lives), names))
    else:
        counted = [_column_distinct(td, cn, lives) for cn in names]
    for cn, (distinct, nulls) in zip(names, counted):
        st.distinct[cn] = distinct
        st.null_frac[cn] = nulls / total if total else 0.0
    return st


def sketch_table_stats(td) -> TableStats:
    """Planner stats derived from seal-time chunk summaries — no
    ANALYZE pass, no row scan. HLL distinct sketches union mergeably
    across the table's chunks (register max), zones supply null
    fractions and per-chunk bounds, blooms allow equality containment
    zero-out. Open (unsealed) rows contribute to row_count but not to
    the summaries, so a table with no sealed chunks yields an empty
    `distinct` map and the memo gate falls back to greedy ordering.

    Dictionary-coded string columns keep their distinct estimate
    (distinct codes == distinct strings — exactly what join costing
    needs) but drop zones/blooms: their chunk arrays hold int32 codes
    whose order is dictionary-insertion order, meaningless against a
    SQL-level comparison constant."""
    from ..storage.chunkstats import DistinctSketch

    st = TableStats(source="sketch")
    st.row_count = td.row_count
    dict_cols = {c.name for c in td.schema.columns
                 if c.type.uses_dictionary}
    sketches: dict[str, DistinctSketch] = {}
    for chunk in td.chunks:
        if not chunk.stats_ready():
            chunk.finalize_stats()
        cs = chunk._stats
        for col, sk in cs.distinct.items():
            agg = sketches.get(col)
            if agg is None:
                sketches[col] = agg = DistinctSketch()
            agg.merge(sk)
        for col, z in cs.zones.items():
            if col in dict_cols:
                continue
            st.zones.setdefault(col, []).append(z)
            st.blooms.setdefault(col, []).append(cs.blooms.get(col))
    for col, sk in sketches.items():
        st.distinct[col] = max(1, sk.estimate())
    for col, zs in st.zones.items():
        nulls = sum(z[2] for z in zs)
        total = nulls + sum(z[3] for z in zs)
        st.null_frac[col] = nulls / total if total else 0.0
    return st


def _underlying_col(e):
    """Peel wrappers (dict remaps, casts) down to a column reference."""
    from .bound import BCol
    seen = 0
    while e is not None and not isinstance(e, BCol) and seen < 8:
        e = getattr(e, "expr", None)
        seen += 1
    return e if isinstance(e, BCol) else None


def _col_distinct(name: str, stats: TableStats | None):
    if stats is None:
        return None
    # bound columns are alias-qualified ("lineitem.l_returnflag");
    # stats key on stored names
    return (stats.distinct.get(name)
            or stats.distinct.get(name.split(".")[-1]))


def _zone_key(name: str, stats: TableStats):
    """Resolve an alias-qualified bound column name to the stored-name
    key the sketch zones use, or None when no zones exist for it."""
    if name in stats.zones:
        return name
    short = name.split(".")[-1]
    return short if short in stats.zones else None


_FLIP = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "="}


def _col_const(e):
    """(BCol, python constant, normalized op) for a col-vs-const
    comparison in either operand order, else None."""
    from .bound import BConst
    cl = _underlying_col(e.left)
    cr = _underlying_col(e.right)
    if cl is not None and isinstance(e.right, BConst):
        return cl, e.right.value, e.op
    if cr is not None and isinstance(e.left, BConst):
        return cr, e.left.value, _FLIP.get(e.op)
    return None


def _is_num(v) -> bool:
    return isinstance(v, (int, float, np.integer, np.floating)) \
        and not isinstance(v, bool)


def _zone_eq_sel(stats: TableStats, key: str, v) -> float | None:
    """Equality selectivity from per-chunk containment: chunks whose
    [lo, hi] excludes v — or whose bloom proves absence — contribute
    zero candidate rows; surviving chunks contribute their valid rows
    scaled by the per-value density 1/distinct."""
    zs = stats.zones.get(key)
    if not zs or not _is_num(v):
        return None
    blooms = stats.blooms.get(key) or [None] * len(zs)
    total = cand = 0
    probe = None
    for z, bl in zip(zs, blooms):
        lo, hi, nulls, nvalid = z
        total += nulls + nvalid
        if nvalid == 0:
            continue
        if lo is None:
            cand += nvalid            # unordered chunk: can't exclude
            continue
        if not (lo <= v <= hi):
            continue
        if bl is not None:
            if probe is None:
                probe = np.asarray([v]).astype(np.int64, copy=False) \
                    if float(v).is_integer() else None
            if probe is not None and not bl.might_contain(probe)[0]:
                continue
        cand += nvalid
    if total == 0:
        return None
    if cand == 0:
        # no chunk can contain v: half a row's worth, never exactly 0
        return 0.5 / total
    nd = stats.distinct.get(key)
    per_value = 1.0 / nd if nd else SEL_EQ
    return min(1.0, per_value) * cand / total


def _overlap_frac(lo, hi, a, b) -> float:
    """Fraction of a chunk's [lo, hi] value span falling inside the
    query interval [a, b], assuming uniform spread. Integer zones use
    inclusive +1 widths so single-value chunks behave."""
    if isinstance(lo, int) and isinstance(hi, int):
        width = hi - lo + 1
        inter = min(hi, b) - max(lo, a) + 1
    else:
        width = hi - lo
        inter = min(hi, b) - max(lo, a)
        if width <= 0.0:
            return 1.0 if a <= lo <= b else 0.0
    if width <= 0:
        return 1.0 if a <= lo <= b else 0.0
    return min(1.0, max(0.0, inter / width))


def _zone_interval_sel(stats: TableStats, key: str, a, b) -> float | None:
    """Selectivity of `a <= col <= b` (half-open ranges pass +/-inf)
    as the valid-row-weighted sum of per-chunk overlap fractions.
    NULL rows count in the denominator — they fail every comparison."""
    zs = stats.zones.get(key)
    if not zs:
        return None
    total = 0
    cand = 0.0
    for lo, hi, nulls, nvalid in zs:
        total += nulls + nvalid
        if nvalid == 0:
            continue
        if lo is None:
            cand += nvalid * SEL_RANGE
            continue
        cand += nvalid * _overlap_frac(lo, hi, a, b)
    if total == 0:
        return None
    return max(cand / total, 0.5 / total)


def _range_bounds(op: str, v):
    """The (a, b) closed interval a comparison op selects. Strict
    bounds nudge integers by one; float strictness is noise at
    estimate precision."""
    if op == "<":
        return -np.inf, (v - 1 if isinstance(v, (int, np.integer)) else v)
    if op == "<=":
        return -np.inf, v
    if op == ">":
        return (v + 1 if isinstance(v, (int, np.integer)) else v), np.inf
    if op == ">=":
        return v, np.inf
    return None


def _pred_selectivity(e, stats: TableStats | None) -> float:
    """Selectivity of one bound predicate expression.

    With sketch-derived stats (per-chunk zones + blooms) equality and
    range comparisons against constants estimate real surviving
    fractions; otherwise the reference-style constants apply."""
    from .bound import (BBetween, BBin, BDictLookup, BInList, BIsNull,
                        BUnary)

    if isinstance(e, BBin):
        if e.op == "and":
            return (_pred_selectivity(e.left, stats)
                    * _pred_selectivity(e.right, stats))
        if e.op == "or":
            a = _pred_selectivity(e.left, stats)
            b = _pred_selectivity(e.right, stats)
            return min(1.0, a + b)
        if e.op == "=":
            cc = _col_const(e)
            if cc is not None and stats is not None:
                key = _zone_key(cc[0].name, stats)
                if key is not None:
                    s = _zone_eq_sel(stats, key, cc[1])
                    if s is not None:
                        return s
            col = _underlying_col(e.left) or _underlying_col(e.right)
            nd = _col_distinct(col.name, stats) if col is not None else None
            if nd:
                return 1.0 / nd
            return SEL_EQ
        if e.op in ("<", "<=", ">", ">="):
            cc = _col_const(e)
            if cc is not None and cc[2] is not None and stats is not None \
                    and _is_num(cc[1]):
                key = _zone_key(cc[0].name, stats)
                if key is not None:
                    bounds = _range_bounds(cc[2], cc[1])
                    if bounds is not None:
                        s = _zone_interval_sel(stats, key, *bounds)
                        if s is not None:
                            return s
            return SEL_RANGE
    if isinstance(e, BBetween):
        from .bound import BConst
        col = _underlying_col(e.expr)
        if (col is not None and stats is not None
                and isinstance(e.lo, BConst) and isinstance(e.hi, BConst)
                and _is_num(e.lo.value) and _is_num(e.hi.value)):
            key = _zone_key(col.name, stats)
            if key is not None:
                s = _zone_interval_sel(stats, key, e.lo.value, e.hi.value)
                if s is not None:
                    return min(1.0, 1.0 - s) if e.negated else s
        return SEL_RANGE
    if isinstance(e, BInList):
        col = _underlying_col(e.expr)
        if col is not None and stats is not None:
            key = _zone_key(col.name, stats)
            if key is not None:
                sels = [_zone_eq_sel(stats, key, v) for v in e.values]
                if all(s is not None for s in sels):
                    s = min(1.0, sum(sels))
                    return min(1.0, 1.0 - s) if e.negated else s
        return min(1.0, SEL_EQ * max(len(e.values), 1))
    if isinstance(e, BIsNull):
        col = _underlying_col(e.expr)
        if col is not None and stats is not None:
            nf = stats.null_frac.get(col.name)
            if nf is None:
                nf = stats.null_frac.get(col.name.split(".")[-1])
            if nf is not None:
                return max(min(1.0 - nf if e.negated else nf, 1.0),
                           0.5 / max(stats.row_count, 1))
        return SEL_OTHER
    if isinstance(e, BDictLookup):
        # fraction of dictionary codes passing the precomputed
        # membership table — exact over values, approximate over rows
        try:
            tb = np.asarray(e.table, dtype=bool)
            if tb.size:
                return float(min(1.0, max(tb.mean(), 1e-4)))
        except Exception:
            pass
        return SEL_OTHER
    if isinstance(e, BUnary) and e.op == "not":
        return min(1.0, max(0.0, 1.0 - _pred_selectivity(e.operand,
                                                         stats)))
    return SEL_OTHER


def scan_rows(node: P.Scan, stats_map: dict) -> float:
    st = stats_map.get(node.table)
    rows = float(st.row_count) if st else 1000.0
    if node.filter is not None:
        rows *= _pred_selectivity(node.filter, st)
    return max(rows, 1.0)


def estimate(node: P.PlanNode, stats_map: dict) -> dict:
    """Bottom-up (est_rows, est_cost) per plan node, keyed by id().

    Costs are abstract row-touch units: scan = rows, filter = input
    rows, hash join = probe + build (build pays a table-build
    surcharge), aggregate = input + groups, sort = n log n.
    """
    out: dict[int, tuple[float, float]] = {}

    def walk(n) -> tuple[float, float]:
        if isinstance(n, P.Scan):
            st = stats_map.get(n.table)
            raw = float(st.row_count) if st else 1000.0
            rows = scan_rows(n, stats_map)
            r = (rows, raw)
        elif isinstance(n, P.Filter):
            crows, ccost = walk(n.child)
            st = None
            rows = crows * _pred_selectivity(n.pred, st)
            r = (max(rows, 1.0), ccost + crows)
        elif isinstance(n, P.HashJoin):
            prows, pcost = walk(n.left)
            brows, bcost = walk(n.right)
            # PK-FK: each probe row matches <= 1 build row
            rows = prows if n.join_type in ("inner", "left",
                                            "semi") else prows * 0.5
            r = (max(rows, 1.0), pcost + bcost + prows + 2.0 * brows)
        elif isinstance(n, P.Aggregate):
            crows, ccost = walk(n.child)
            groups = (min(float(n.max_groups), crows) if n.max_groups
                      else min(crows, 1 << 17) * 0.1)
            r = (max(groups if n.group_by else 1.0, 1.0),
                 ccost + crows + groups)
        elif isinstance(n, P.Project):
            crows, ccost = walk(n.child)
            r = (crows, ccost + crows)
        elif isinstance(n, P.Sort):
            crows, ccost = walk(n.child)
            r = (crows, ccost + crows * max(np.log2(max(crows, 2.0)), 1.0))
        elif isinstance(n, P.Limit):
            crows, ccost = walk(n.child)
            rows = crows
            if n.limit is not None:
                rows = min(crows, float(n.limit))
            r = (rows, ccost + crows)
        else:
            r = (1.0, 1.0)
        out[id(n)] = r
        return r

    walk(node)
    return out
