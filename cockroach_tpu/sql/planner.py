"""Heuristic logical planner: Select AST -> plan tree.

The reference runs a full cost-based optimizer (pkg/sql/opt: memo +
norm/xform rules); per SURVEY.md §7 step 7 we start heuristic:

- scans for each FROM table, filters split into conjuncts;
- equality conjuncts between two tables become hash joins (left-deep,
  in FROM order; the syntactically-later / ON-right table is the build
  side, so dimension tables join PK-side as in TPC-H/SSB);
- single-table conjuncts push down into the scan (fused with the MVCC
  visibility mask on device);
- aggregates extracted from SELECT/HAVING into an Aggregate node with
  post-projection expressions (BAggRef), mirroring how the reference's
  DistAggregationTable renders final AVG as SUM/COUNT;
- ORDER BY/LIMIT on top.
"""

from __future__ import annotations

from dataclasses import dataclass

from . import ast, plan
from .binder import Binder, BindError, ColumnBinding, Scope
from .bound import (BAggRef, BBin, BCol, BConst, BDictRemap, BExpr,
                    referenced_columns, walk)
from .types import INT8, Family, TableSchema


class PlanError(Exception):
    pass


class NotInPlace(PlanError):
    """A derived table that cannot stand in the outer plan where a Scan
    would (plan.Derived): the statement is right, and the engine
    materializes the table instead (Engine._exec_with_temps). Raised
    for nothing else, so that a user's plan or bind error is never
    taken for it."""


class CatalogView:
    """What the planner needs from the catalog: schema + dictionaries
    + table statistics (exact row counts; ANALYZE-computed distincts
    when available — sql/stats.py). ``key_distinct_fn(table, cols) ->
    (distinct, nonnull_rows)`` is the engine's exact uniqueness probe
    (cached per generation); None when no store is attached."""

    def __init__(self, schemas, dictionaries, stats=None,
                 key_distinct_fn=None, int_range_fn=None,
                 keys_unique_fn=None, indexes=None):
        self.schemas = schemas
        self.dictionaries = dictionaries
        self.stats = stats or {}
        # table -> [(index_name, (cols...), unique)] of PUBLIC
        # secondary indexes: access-path candidates for the memo's
        # scan costing (planner._choose_access_paths)
        self.indexes = indexes or {}
        self.key_distinct_fn = key_distinct_fn
        # keys_unique_fn(table, cols) -> bool: SNAPSHOT-AWARE
        # uniqueness at the statement's read timestamp — required for
        # correctness-bearing rewrites (FD group-key reduction), where
        # the live-rows distinct probe could disagree with an AS OF
        # read's visible rows
        self.keys_unique_fn = keys_unique_fn
        # int_range_fn(table, col) -> (lo, hi, count) | None: exact
        # all-versions value range of an int column (generation-
        # cached). Lets GROUP BY over small-range int keys (years,
        # status codes) take the dense segment-sum strategy instead of
        # the while-loop hash table. The engine withholds it for
        # txn-overlay reads (uncommitted rows could exceed the range).
        self.int_range_fn = int_range_fn

    def schema(self, name: str) -> TableSchema:
        s = self.schemas.get(name)
        if s is None:
            raise PlanError(f"table {name!r} does not exist")
        return s

    def row_count(self, name: str) -> float:
        st = self.stats.get(name)
        return float(st.row_count) if st is not None else 1000.0


def split_conjuncts(e: BExpr) -> list[BExpr]:
    if isinstance(e, BBin) and e.op == "and":
        return split_conjuncts(e.left) + split_conjuncts(e.right)
    return [e]


def and_all(conjuncts: list[BExpr]) -> BExpr:
    out = conjuncts[0]
    from .types import BOOL
    for c in conjuncts[1:]:
        out = BBin("and", out, c, BOOL)
    return out


def _prefix_aliases(sel: ast.Select, prefix: str) -> ast.Select:
    """A copy of a derived table's SELECT whose own FROM aliases start
    with `prefix`, references to them requalified. Bare references
    need nothing: they resolve in the sub-select's own scope. Nested
    SELECTs (a derived table's body, an expression subquery) are
    scopes of their own and are left as they are."""
    import copy
    import dataclasses

    # the nested scopes are left as they are, so they are not copied
    # (a derived table's planner copies its own body)
    keep: dict = {}

    def nested(x):
        if isinstance(x, (ast.Select, ast.SetOp)) and x is not sel:
            keep[id(x)] = x
        elif isinstance(x, (list, tuple)):
            for v in x:
                nested(v)
        elif dataclasses.is_dataclass(x) and not isinstance(x, type):
            for f in dataclasses.fields(x):
                nested(getattr(x, f.name))

    for f in dataclasses.fields(sel):
        nested(getattr(sel, f.name))
    sel = copy.deepcopy(sel, keep)
    refs = ([sel.table] if sel.table is not None else []) \
        + [j.table for j in sel.joins]
    renamed = {}
    for r in refs:
        old = r.alias or r.name
        renamed[old] = prefix + old
        r.alias = prefix + old

    def walk(x):
        if isinstance(x, ast.ColumnRef):
            if x.table in renamed:
                x.table = renamed[x.table]
            return
        if isinstance(x, ast.Select):
            return
        if isinstance(x, (list, tuple)):
            for v in x:
                walk(v)
            return
        if dataclasses.is_dataclass(x) and not isinstance(x, type):
            for f in dataclasses.fields(x):
                walk(getattr(x, f.name))

    for f in dataclasses.fields(sel):
        if f.name not in ("table", "ctes"):
            walk(getattr(sel, f.name))
    return sel


class Planner:
    # tables beyond this use the greedy orderer (2^n memo groups)
    MEMO_MAX_TABLES = 12

    def __init__(self, catalog: CatalogView, subquery_eval=None,
                 now_micros=None, sequence_ops=None,
                 use_memo: bool = True, volatile_fold_ok: bool = True,
                 dict_folds: bool = True, rules: bool = True,
                 trace=None, subquery_arg=None):
        self.catalog = catalog
        # False: dictionary-content-dependent constant folds disabled
        # so plan structure is shard-independent (distsql/shuffle.py)
        self.dict_folds = dict_folds
        # the normalization rule plane (sql/rules.py); the engine maps
        # SET optimizer_rules = 'off' here
        self.rules_on = rules
        # caller-provided RuleTrace so AST-layer firings (view
        # expansion, decorrelation — recorded by the engine) and
        # plan-layer firings land in one report
        self._trace = trace
        # alias -> chosen access path line (memo scan costing)
        self.access_paths: dict = {}
        # engine-supplied hooks: subquery execution + statement
        # timestamp for now()/current_date + sequence builtins
        # (binder.py)
        self.subquery_eval = subquery_eval
        self.subquery_arg = subquery_arg
        self.now_micros = now_micros
        self.sequence_ops = sequence_ops
        self.use_memo = use_memo
        self.volatile_fold_ok = volatile_fold_ok
        self.last_memo = None  # sql/memo.MemoResult of the last plan
        # "alias.col" of a derived table's integer column -> its
        # (lo, hi), from the derived plan's OutputMeta.int_ranges
        self._derived_ranges: dict = {}

    def _keys_unique(self, cand_alias: str, cand_table: str, pool,
                     other_side: set, _key_side, scans) -> bool:
        """Would ``cand_alias`` have unique join keys as a build side?
        Collect its side of the equality conjuncts against
        ``other_side`` and run the catalog's exact distinct probe.
        Conservative: unknown/computed keys or no probe -> False."""
        fn = self.catalog.key_distinct_fn
        if fn is None:
            return False
        stored = []
        colmap = scans[cand_alias].columns
        for c in pool:
            if not (isinstance(c, BBin) and c.op == "="):
                continue
            ta, na, ea = _key_side(c.left)
            tb, nb, eb = _key_side(c.right)
            cand_name = None
            if ta == cand_alias and tb in other_side:
                cand_name, cand_expr = na, ea
            elif tb == cand_alias and ta in other_side:
                cand_name, cand_expr = nb, eb
            else:
                continue
            if cand_name is None:
                # dictionary-remapped key: the remap is injective, so
                # the underlying column's distinctness carries over
                from .stats import _underlying_col
                inner = _underlying_col(cand_expr)
                cand_name = getattr(inner, "name", None)
            sname = colmap.get(cand_name) if cand_name else None
            if sname is None:
                return False
            stored.append(sname)
        if not stored:
            return False
        try:
            distinct, nonnull = fn(cand_table, tuple(stored))
        except KeyError:        # a derived table: nothing stored
            return False
        return distinct == nonnull

    def _choose_access_paths(self, tables, conjuncts,
                             tables_of) -> None:
        """Cost every table's access paths — full scan vs each index
        whose columns are fully bound by constant-equality conjuncts —
        and record the winner (idxconstraint + the memo's scan costing
        in one place; surfaced by EXPLAIN as 'access:' lines, fed to
        memo.search as scan_cost)."""
        from .bound import BConst
        for alias, tname in tables:
            rc = max(self.catalog.row_count(tname), 1.0)
            st = self.catalog.stats.get(tname)
            eq_cols: set[str] = set()
            for c in conjuncts:
                if isinstance(c, BBin) and c.op == "=" \
                        and tables_of(c) == {alias}:
                    for a, b in ((c.left, c.right),
                                 (c.right, c.left)):
                        if isinstance(a, BCol) and \
                                isinstance(b, BConst):
                            eq_cols.add(a.name.split(".", 1)[-1])
            cands = []
            try:
                pk = tuple(self.catalog.schema(tname).primary_key)
                if pk:
                    cands.append(("primary", pk, True))
            except PlanError:
                pass
            for nm, cols, uniq in self.catalog.indexes.get(tname, []):
                cands.append((nm, tuple(cols), uniq))
            best = ("full", rc, rc)
            for label, cols, uniq in cands:
                if not cols or not all(cn in eq_cols for cn in cols):
                    continue
                if uniq:
                    est = 1.0
                else:
                    est = rc
                    for cn in cols:
                        d = (st.distinct.get(cn)
                             if st is not None and st.distinct
                             else None)
                        est /= max(float(d) if d else rc ** 0.5, 1.0)
                    est = max(est, 1.0)
                cost = est + 2.0   # probe overhead
                if cost < best[2]:
                    best = (f"{label} eq({','.join(cols)})", est, cost)
            self.access_paths[alias] = best

    def _memo_order(self, tables, ordered, conjuncts, alias_table,
                    tables_of, _key_side):
        """Run the memoized join-order search over this query's join
        graph; None = not applicable (disconnected, or no orderable
        shape) — caller falls back to the greedy orderer."""
        from . import memo as memomod
        from .stats import _pred_selectivity
        aliases = [tables[0][0]] + [e[0] for e in ordered]
        if len(set(aliases)) != len(aliases):
            return None  # self-join aliasing handled by greedy path
        pool_all = (list(conjuncts)
                    + [c for _, _, oc in ordered for c in oc])
        stats_map = self.catalog.stats
        # cost-based search engages only when column statistics exist
        # for every table (ANALYZE); without distinct counts the
        # multiplicity/selectivity estimates are guesses and the
        # greedy smallest-build heuristic is safer (the reference
        # likewise falls back without table_statistics)
        for a in aliases:
            st = stats_map.get(alias_table[a])
            if st is None or not st.distinct:
                return None

        def scan_rows(alias: str) -> float:
            st = stats_map.get(alias_table[alias])
            rc = max(self.catalog.row_count(alias_table[alias]), 1.0)
            sel = 1.0
            for c in pool_all:
                if tables_of(c) == {alias}:
                    sel *= _pred_selectivity(c, st)
            return rc * sel

        def _distinct(al: str, cn) -> float | None:
            st = stats_map.get(alias_table[al])
            if st is None or cn is None:
                return None
            dd = st.distinct.get(cn.split(".", 1)[-1])
            return float(dd) if dd else None

        # resolve each equality conjunct's sides ONCE — join_info runs
        # per memo extension (O(2^n * n) calls), so per-call conjunct
        # rescans would dominate planning at the table cap
        edges = []
        for c in pool_all:
            if not (isinstance(c, BBin) and c.op == "="):
                continue
            ta, na, _ea = _key_side(c.left)
            tb, nb, _eb = _key_side(c.right)
            if ta is not None and tb is not None:
                edges.append((ta, na, tb, nb))

        int_range = self.catalog.int_range_fn

        def _direct_eligible(alias: str, key_cols: list) -> bool:
            """Mirror engine._maybe_direct_join's span caps: can a
            build on these key columns take the direct-address table?
            A unique build that can't still pays the while-loop hash
            path, so the memo must charge it accordingly."""
            if int_range is None or not key_cols:
                return False
            t = alias_table[alias]
            spans = []
            n_all = 0
            for qc in key_cols:
                col = qc.split(".", 1)[-1]
                try:
                    r = int_range(t, col)
                except (KeyError, TypeError, ValueError):
                    return False
                if r is None:
                    return False
                lo, hi, n_all = r
                spans.append(hi - lo + 1)
            if len(spans) == 1:
                return (spans[0] <= max(256 * n_all, 4096)
                        and spans[0] + 1 <= (1 << 22))
            total = 1
            for span in spans:
                total *= span
                if total > (1 << 27):
                    return False
            return total <= max(2048 * n_all, 4096)

        kd_fn = self.catalog.key_distinct_fn

        def _exact_distinct(alias: str, cols: tuple) -> float | None:
            """EXACT combined-key distinct via the store (generation-
            cached lexsort). Per-column independence MULTIPLIES
            distincts for composite keys, wildly overestimating when
            the columns are correlated (q9: lineitem (l_suppkey,
            l_partkey) -> 61M 'independent' pairs vs ~800K real; the
            resulting build_mult=1.0 + selectivity 1/61M made a 1M-row
            hash build of lineitem look free)."""
            if kd_fn is None:
                return None
            try:
                d, _nn = kd_fn(alias_table[alias],
                               tuple(c.split(".", 1)[-1]
                                     for c in cols))
            except (KeyError, TypeError):
                return None
            return float(d) if d else None

        def join_info(left_set, right):
            sel = None
            build_key_distinct = 1.0
            build_known = True
            build_cols = []
            probe_sides = []
            for ta, na, tb, nb in edges:
                if ta in left_set and tb == right:
                    sides = ((ta, na), (tb, nb))
                elif tb in left_set and ta == right:
                    sides = ((tb, nb), (ta, na))
                else:
                    continue
                # independence estimate: 1/max(distinct_l, distinct_r)
                d = 1.0
                for al, cn in sides:
                    dd = _distinct(al, cn)
                    if dd:
                        d = max(d, dd)
                if d <= 1.0:
                    d = max(*(self.catalog.row_count(alias_table[al])
                              for al, _ in sides), 1.0)
                s = 1.0 / d
                sel = s if sel is None else sel * s
                bd = _distinct(sides[1][0], sides[1][1])
                if bd:
                    build_key_distinct *= bd
                else:
                    build_known = False
                if sides[1][1] is not None:
                    build_cols.append(sides[1][1])
                probe_sides.append(sides[0])
            if sel is None:
                return None
            if len(build_cols) > 1:
                # composite key: replace the independence products
                # with exact combined distincts on both sides
                bd_exact = _exact_distinct(right, tuple(build_cols))
                if bd_exact is not None:
                    build_key_distinct = bd_exact
                    build_known = True
                    p_alias = {al for al, _ in probe_sides}
                    pd_exact = (_exact_distinct(
                        next(iter(p_alias)),
                        tuple(cn for _, cn in probe_sides
                              if cn is not None))
                        if len(p_alias) == 1
                        and all(cn is not None
                                for _, cn in probe_sides) else None)
                    sel = 1.0 / max(bd_exact, pd_exact or 1.0)
            # duplicate rows per key on the build side: the device
            # join expands these, capped by the engine — estimate
            # from the UNFILTERED base rows (pushdown filters do not
            # reduce per-key multiplicity reliably)
            base = max(self.catalog.row_count(alias_table[right]), 1.0)
            mult = (base / max(build_key_distinct, 1.0)
                    if build_known else 1.0)
            return sel, mult, _direct_eligible(right, build_cols)

        def scan_cost(alias: str) -> float:
            # access-path-aware: an index lookup costs its matched
            # rows; otherwise the post-filter scan estimate
            ap = self.access_paths.get(alias)
            rows = scan_rows(alias)
            if ap is not None and not ap[0].startswith("full"):
                return min(rows, ap[2])
            return rows

        return memomod.search(aliases, scan_rows, join_info,
                              scan_cost=scan_cost)

    def _sub_planner(self) -> "Planner":
        """A planner for a sub-select of this statement (a derived
        table's body, a branch of a UNION ALL), with this one's hooks
        and settings."""
        return Planner(self.catalog, subquery_eval=self.subquery_eval,
                       now_micros=self.now_micros,
                       sequence_ops=self.sequence_ops,
                       use_memo=self.use_memo,
                       volatile_fold_ok=self.volatile_fold_ok,
                       dict_folds=self.dict_folds, rules=self.rules_on,
                       trace=self._trace, subquery_arg=self.subquery_arg)

    def _plan_union_all(self, so: ast.SetOp, alias: str):
        """A derived table whose body is a UNION ALL of SELECTs, planned
        in place: each branch on its own (its tables' aliases prefixed
        `alias$<i>$`), a Project above each that names its columns as
        the first branch does, coerces a numeric column to the type
        the branches share, and translates a string column's codes
        into one dictionary over every branch's values (sorted, so
        that a code's order is its value's); then plan.UnionAll nodes,
        nested on the left. Anything else (UNION, INTERSECT, EXCEPT,
        an ORDER BY or LIMIT of the set operation, a branch with
        CTEs) says NotInPlace."""
        import numpy as np

        from ..storage.columnstore import Dictionary

        def branches(s):
            if isinstance(s, ast.SetOp):
                if s.op != "union" or not s.all or s.ctes \
                        or s.order_by or s.limit is not None \
                        or s.offset:
                    raise NotInPlace(
                        f"derived table {alias!r} is a set operation "
                        "other than a plain UNION ALL: it is "
                        "materialized, not planned in place")
                return branches(s.left) + branches(s.right)
            if not isinstance(s, ast.Select) or s.ctes:
                raise NotInPlace(
                    f"a branch of derived table {alias!r} has CTEs: "
                    "it is materialized, not planned in place")
            return [s]

        planned = [self._sub_planner().plan_select(
            _prefix_aliases(b, f"{alias}${i}$"))
            for i, b in enumerate(branches(so))]
        names = list(planned[0][1].names)
        width = len(names)
        for _, m in planned:
            if len(m.names) != width:
                raise PlanError(
                    "each UNION branch must have the same number of "
                    f"columns ({width} vs {len(m.names)})")
        from .types import common_numeric_type
        numeric = (Family.INT, Family.FLOAT, Family.DECIMAL)
        types = []
        for j in range(width):
            ts = [m.types[j] for _, m in planned]
            t = ts[0]
            for u in ts[1:]:
                if u.family == t.family and (
                        t.family != Family.DECIMAL or u.scale == t.scale):
                    continue
                if "unknown" in (u.family.value, t.family.value):
                    t = u if t.family.value == "unknown" else t
                elif t.family in numeric and u.family in numeric:
                    t = common_numeric_type(t, u)
                else:
                    raise PlanError(
                        f"UNION branch column types do not match: {t} "
                        f"vs {u}")
            types.append(t)
        dicts = {}
        for j, (name, t) in enumerate(zip(names, types)):
            if not t.uses_dictionary:
                continue
            ds = [m.dictionaries.get(m.names[j]) for _, m in planned]
            if any(d is None for d in ds):
                raise NotInPlace(
                    f"a string column of derived table {alias!r} has no "
                    "dictionary in one branch: it is materialized")
            d = Dictionary()
            d.seed(sorted({v for x in ds for v in x.values}))
            dicts[name] = d
        coerce = Binder(Scope()).coerce
        node = None
        for node_i, m in planned:
            items = []
            for j, (name, t) in enumerate(zip(names, types)):
                e = BCol(m.names[j], m.types[j])
                if name in dicts:
                    codes = dicts[name].codes
                    src = m.dictionaries[m.names[j]]
                    e = BDictRemap(e, np.array(
                        [codes[v] for v in src.values] or [0],
                        dtype=np.int32), t)
                elif m.types[j] != t:
                    e = coerce(e, t)
                items.append((name, e))
            if isinstance(node_i, plan.Project):
                # one projection: the branch's own items seen through
                items = [(name, _substitute(e, [
                    (BCol(n, b.type), b) for n, b in node_i.items]))
                    for name, e in items]
                node_i = node_i.child
            branch = plan.Project(node_i, items)
            node = branch if node is None \
                else plan.UnionAll(node, branch, list(names))
        meta = plan.OutputMeta(names=names, types=types,
                               dictionaries=dicts)
        for j, name in enumerate(names):
            rs = [m.int_ranges.get(m.names[j]) for _, m in planned]
            if types[j].family == Family.INT and None not in rs:
                meta.int_ranges[name] = (min(r[0] for r in rs),
                                         max(r[1] for r in rs))
        meta.subqueries = sum(m.subqueries for _, m in planned)
        return node, meta

    def plan_select(self, sel: ast.Select) -> tuple[plan.PlanNode, plan.OutputMeta]:
        if sel.table is None:
            raise PlanError("SELECT without FROM not supported")
        if any(j.join_type == "right" for j in sel.joins):
            # a RIGHT JOIN b == b LEFT JOIN a: rewrite when it is the
            # sole join (the general interior-right case needs full
            # join reassociation — memo/xform territory)
            if len(sel.joins) != 1:
                raise PlanError(
                    "RIGHT JOIN supported only as the sole join")
            import copy
            sel = copy.copy(sel)
            j = sel.joins[0]
            sel.table, sel.joins = j.table, [
                ast.JoinClause(sel.table, "left", j.on)]

        # ---- scopes & scans -------------------------------------------------
        scope = Scope()
        tables: list[tuple[str, str]] = []  # (alias, table_name)
        scans: dict[str, plan.Scan] = {}
        join_specs: list[ast.JoinClause] = list(sel.joins)

        derived_subqueries: list = []

        def add_derived(tref: ast.TableRef, hidden: bool):
            """FROM (SELECT ...) AS alias, planned in place: the
            sub-select's plan stands where a Scan would, under the
            alias's batch names. Its tables' aliases are prefixed with
            this alias, so that no two scans of one program share one."""
            alias = tref.alias or tref.name
            if isinstance(tref.subquery, ast.SetOp):
                subnode, submeta = self._plan_union_all(tref.subquery,
                                                        alias)
            elif tref.subquery.ctes or tref.subquery.table is None:
                raise NotInPlace(
                    f"derived table {alias!r} has CTEs or no FROM: it "
                    "is materialized, not planned in place")
            else:
                subnode, submeta = self._sub_planner().plan_select(
                    _prefix_aliases(tref.subquery, alias + "$"))
            derived_subqueries.append(submeta.subqueries)
            cols, colmap = {}, {}
            for name, ty in zip(submeta.names, submeta.types):
                bname = f"{alias}.{name}"
                cols[name] = ColumnBinding(
                    bname, ty, submeta.dictionaries.get(name))
                colmap[bname] = name
                if name in submeta.int_ranges:
                    self._derived_ranges[bname] = submeta.int_ranges[name]
            scope.add_table(alias, cols, hidden=hidden)
            tname = f"(derived {alias})"
            tables.append((alias, tname))
            scans[alias] = plan.Derived(subnode, alias, colmap,
                                        table=tname)

        def add_table(tref: ast.TableRef, hidden: bool = False):
            if tref.subquery is not None:
                return add_derived(tref, hidden)
            alias = tref.alias or tref.name
            schema = self.catalog.schema(tref.name)
            dicts = self.catalog.dictionaries.get(tref.name, {})
            cols = {}
            colmap = {}
            for c in schema.columns:
                bname = f"{alias}.{c.name}"
                cols[c.name] = ColumnBinding(bname, c.type, dicts.get(c.name))
                colmap[bname] = c.name
            scope.add_table(alias, cols, hidden=hidden)
            tables.append((alias, tref.name))
            scans[alias] = plan.Scan(tref.name, alias, colmap)

        add_table(sel.table)
        for j in join_specs:
            add_table(j.table, hidden=j.join_type in ("semi", "anti"))

        binder = Binder(scope, subquery_eval=self.subquery_eval,
                        now_micros=self.now_micros,
                        sequence_ops=self.sequence_ops,
                        volatile_fold_ok=self.volatile_fold_ok,
                        dict_folds=self.dict_folds,
                        subquery_arg=self.subquery_arg)

        # ---- gather predicates ---------------------------------------------
        conjuncts: list[BExpr] = []
        explicit_joins: list[tuple[str, str, BExpr]] = []  # (alias, type, on)
        for j in join_specs:
            alias = j.table.alias or j.table.name
            if j.on is not None:
                explicit_joins.append((alias, j.join_type, binder.bind(j.on)))
            else:
                explicit_joins.append((alias, j.join_type, None))
        if sel.where is not None:
            conjuncts.extend(split_conjuncts(binder.bind(sel.where)))

        alias_of_col: dict[str, str] = {}
        for alias, _ in tables:
            for b in scope.tables[alias].values():
                alias_of_col[b.batch_name] = alias

        def tables_of(e: BExpr) -> set[str]:
            return {alias_of_col[c] for c in referenced_columns(e)}

        # ---- assemble join tree --------------------------------------------
        # Left-deep: first table is the running probe side; each joined
        # table is a build side with equality keys from ON + WHERE.
        joined = {tables[0][0]}
        node: plan.PlanNode = scans[tables[0][0]]
        probe_root = tables[0][0]  # updated if the build-side swap fires
        remaining_conjuncts = list(conjuncts)
        self._choose_access_paths(tables, conjuncts, tables_of)

        jk_counter = [0]

        def _key_side(e: BExpr):
            """(alias, batch column name or None-if-computed, expr)."""
            if isinstance(e, BCol):
                return alias_of_col[e.name], e.name, None
            if isinstance(e, BDictRemap) and isinstance(e.expr, BCol):
                return alias_of_col[e.expr.name], None, e
            return None, None, None

        def _key_name(alias: str, name, expr) -> str:
            if name is not None:
                return name
            # computed join key (e.g. dictionary-code remap): evaluate it
            # in the owning scan
            kname = f"__jk{jk_counter[0]}"
            jk_counter[0] += 1
            scans[alias].computed.append((kname, expr))
            return kname

        def extract_equi_keys(pool: list[BExpr], left_tables: set[str],
                              right: str):
            lk, rk, used = [], [], []
            for c in pool:
                if not (isinstance(c, BBin) and c.op == "="):
                    continue
                ta, na, ea = _key_side(c.left)
                tb, nb, eb = _key_side(c.right)
                if ta is None or tb is None:
                    continue
                if ta in left_tables and tb == right:
                    lk.append(_key_name(ta, na, ea))
                    rk.append(_key_name(tb, nb, eb))
                    used.append(c)
                elif tb in left_tables and ta == right:
                    lk.append(_key_name(tb, nb, eb))
                    rk.append(_key_name(ta, na, ea))
                    used.append(c)
            return lk, rk, used

        ordered = []  # (alias, join_type, on_conjuncts)
        for alias, jt, on in explicit_joins:
            ordered.append((alias, jt, split_conjuncts(on) if on is not None else []))

        def _has_equi_keys(pool, left_tables: set, right: str) -> bool:
            """Dry-run of extract_equi_keys (no computed-key naming)."""
            for c in pool:
                if not (isinstance(c, BBin) and c.op == "="):
                    continue
                ta, _, _ = _key_side(c.left)
                tb, _, _ = _key_side(c.right)
                if ta is None or tb is None:
                    continue
                if ((ta in left_tables and tb == right)
                        or (tb in left_tables and ta == right)):
                    return True
            return False

        alias_table = dict(tables)

        def _rc(alias: str) -> float:
            return self.catalog.row_count(alias_table[alias])

        # LEFT JOINs whose ON references only the inner tables (the
        # decorrelated __exists/__sc derived joins, and plain
        # fact LEFT dim) pin to the TAIL, freeing the inner prefix
        # for cost-based reordering — without this, one decorrelated
        # subquery would force the whole FROM list into syntax order
        # (q2's five-table outer join graph is unorderable that way)
        pinned_lefts = []
        if ordered and not all(jt in ("inner", "cross")
                               for _, jt, _ in ordered):
            inners = [e for e in ordered if e[1] in ("inner", "cross")]
            lefts = [e for e in ordered
                     if e[1] in ("left", "semi", "anti")]
            if len(inners) + len(lefts) == len(ordered) and lefts:
                inner_aliases = {tables[0][0]} | {e[0] for e in inners}
                left_aliases = {e[0] for e in lefts}
                ok = True
                for la, _, lon in lefts:
                    for c in lon:
                        if not tables_of(c) <= inner_aliases | {la}:
                            ok = False  # left ON sees another left
                for _, _, oc in inners:
                    for c in oc:
                        if tables_of(c) & left_aliases:
                            ok = False  # inner keyed on a left output
                if ok:
                    # every inner must stay equi-reachable WITHOUT the
                    # left aliases: a WHERE key routed through a left
                    # table (FROM a LEFT b, c WHERE c.x = b.y) would
                    # otherwise strand the inner once lefts move to
                    # the tail
                    pool_noleft = [
                        c for c in conjuncts
                        if not (tables_of(c) & left_aliases)]
                    for _, _, oc in inners:
                        pool_noleft += oc
                    sim = {tables[0][0]}
                    rem = [e[0] for e in inners]
                    while rem and ok:
                        nxt = next((a for a in rem if _has_equi_keys(
                            pool_noleft, sim, a)), None)
                        if nxt is None:
                            ok = False
                        else:
                            sim.add(nxt)
                            rem.remove(nxt)
                if ok:
                    pinned_lefts = lefts
                    ordered = inners

        # Join ordering. Preferred: the memoized cost-based search
        # (sql/memo.py — the compact analogue of opt/xform's
        # exploration + costing), which chooses BOTH the probe root
        # and the build order over all connected left-deep plans.
        # Fallback: the greedy smallest-next heuristic.
        memo_done = False
        if ordered and self.use_memo \
                and len(tables) <= self.MEMO_MAX_TABLES \
                and all(jt in ("inner", "cross")
                        for _, jt, _ in ordered):
            res = self._memo_order(tables, ordered, conjuncts,
                                   alias_table, tables_of, _key_side)
            if res is not None:
                self.last_memo = res
                pool_all = [c for _, _, oc in ordered for c in oc]
                node = scans[res.root]
                probe_root = res.root
                joined = {res.root}
                # inner-join ON conditions pool with WHERE (identical
                # semantics); each reordered step draws its keys there
                remaining_conjuncts = list(conjuncts) + pool_all
                ordered = [(a, "inner", []) for a in res.order]
                memo_done = True
        if ordered and not memo_done and all(
                jt in ("inner", "cross") for _, jt, _ in ordered):
            remaining = list(ordered)
            reordered = []
            sim_joined = set(joined)
            pool_all = list(conjuncts)
            ok = True
            while remaining:
                joinable = [
                    e for e in remaining
                    if _has_equi_keys(e[2] + pool_all, sim_joined, e[0])]
                if not joinable:
                    ok = False  # fall back to syntax order
                    break
                pick = min(joinable, key=lambda e: _rc(e[0]))
                reordered.append(pick)
                remaining.remove(pick)
                sim_joined.add(pick[0])
            if ok:
                ordered = reordered
            # Build-side selection for the FIRST join: hash joins want
            # the SMALL side as the build, but a build's keys must be
            # unique (ops/join.py) — so only swap when the smaller
            # side's keys are verified unique via the store's exact
            # probe. If the syntax probe (root) is the smaller side,
            # swap roles.
            if ordered:
                first_alias, first_jt, first_on = ordered[0]
                root = tables[0][0]
                # a zero row count means "no local data here" (e.g. a
                # DistSQL gateway whose rows live on data nodes), not
                # "empty table" — no signal, keep syntax order
                if (first_jt in ("inner", "cross")
                        and 0 < _rc(root) < _rc(first_alias)
                        and self._keys_unique(
                            root, alias_table[root],
                            first_on + conjuncts, {first_alias},
                            _key_side, scans)):
                    node = scans[first_alias]
                    joined = {first_alias}
                    ordered[0] = (root, first_jt, first_on)
                    probe_root = first_alias

        ordered = ordered + pinned_lefts
        for alias, jt, on_conj in ordered:
            # LEFT JOIN must not consume WHERE conjuncts as join keys —
            # ON and WHERE have different outer-join semantics
            outer_like = jt in ("left", "semi", "anti")
            pool = on_conj + ([] if outer_like else remaining_conjuncts)
            lk, rk, used = extract_equi_keys(pool, joined, alias)
            if lk and jt == "cross":
                # comma-join with equality predicates in WHERE -> hash join
                jt = "inner"
            if not lk and not (jt == "cross"
                               and _few_rows(scans[alias])):
                raise PlanError(
                    f"no equality join condition for {alias} "
                    "(cartesian products unsupported)")
            for u in used:
                if u in remaining_conjuncts:
                    remaining_conjuncts.remove(u)
            residual = [c for c in on_conj if c not in used]
            build = scans[alias]
            build_local = []
            if outer_like:
                # residual ON conjuncts on the build side filter which
                # rows can MATCH (NULL-extension still happens; a SEMI
                # or ANTI join tests the rows that are left) — push
                # into the build scan; cross-side residuals would need
                # per-pair evaluation inside the join
                both_sided = [c for c in residual if tables_of(c) != {alias}]
                if both_sided:
                    raise PlanError(
                        f"{jt.upper()} JOIN ON conditions across both "
                        "sides (beyond equality keys) not supported yet")
                build_local = residual
                residual = []
            # build-side single-table WHERE conjuncts push into the build
            # scan (for LEFT joins, WHERE stays above the join: filtering
            # the build scan would wrongly null-extend filtered matches)
            if not outer_like:
                wl = [c for c in remaining_conjuncts
                      if tables_of(c) == {alias}]
                for c in wl:
                    remaining_conjuncts.remove(c)
                build_local += wl
            if build_local:
                build.filter = and_all(
                    ([build.filter] if build.filter is not None else [])
                    + build_local)
            payload = [b.batch_name for b in scope.tables[alias].values()]
            pack = [b.batch_name for b in scope.tables[alias].values()
                    if b.dictionary is not None]
            if jt in ("semi", "anti"):
                payload, pack = [], []   # tests rows, carries none
            node = plan.HashJoin(node, build, lk, rk, payload, jt,
                                 pack_payload=pack)
            joined.add(alias)
            # residual ON conjuncts of inner joins are plain filters
            remaining_conjuncts.extend(residual)

        # remaining single-table conjuncts on the probe root push into scan
        root_alias = probe_root
        root_local = [c for c in remaining_conjuncts
                      if tables_of(c) <= {root_alias}]
        for c in root_local:
            remaining_conjuncts.remove(c)
        if root_local:
            scans[root_alias].filter = and_all(
                ([scans[root_alias].filter] if scans[root_alias].filter
                 is not None else []) + root_local)
        if remaining_conjuncts:
            node = plan.Filter(node, and_all(remaining_conjuncts))

        # ---- SELECT items & aggregation ------------------------------------
        has_group = bool(sel.group_by)
        # expand stars; disambiguate duplicate output names (the batch is
        # name-keyed, so two items named "sum" would silently collapse)
        items: list[tuple[str, ast.Expr]] = []
        seen_names: dict[str, int] = {}

        def uniq(name: str) -> str:
            k = seen_names.get(name, 0)
            seen_names[name] = k + 1
            return name if k == 0 else f"{name}_{k}"

        for it in sel.items:
            if it.star:
                for alias, _ in tables:
                    if alias in scope.hidden:
                        continue
                    for colname, b in scope.tables[alias].items():
                        items.append((uniq(colname),
                                      ast.ColumnRef(colname, alias)))
            else:
                name = it.alias or _default_name(it.expr)
                items.append((uniq(name), it.expr))

        group_exprs: list[tuple[str, BExpr]] = []
        if has_group:
            item_by_name = {n: e for n, e in items}
            for i, g in enumerate(sel.group_by):
                # allow GROUP BY <position> and GROUP BY <alias>
                if isinstance(g, ast.Literal) and isinstance(g.value, int):
                    name, expr = items[g.value - 1]
                    bexpr = binder.bind(expr)
                elif isinstance(g, ast.ColumnRef) and g.table is None:
                    try:
                        bexpr = binder.bind(g)  # real columns win
                        name = _default_name(g)
                    except BindError:
                        if g.name not in item_by_name:
                            raise
                        bexpr = binder.bind(item_by_name[g.name])
                        name = g.name
                else:
                    bexpr = binder.bind(g)
                    name = _default_name(g)
                group_exprs.append((f"g{i}:{name}", bexpr))

        sets = sel.grouping_sets
        if has_group:
            binder.grouping_keys = [b for _, b in group_exprs]
            binder.grouping_sets = sets is not None
        bound_items: list[tuple[str, BExpr]] = []
        # ORDER BY expressions of a grouped query that are no output
        # column: hidden items `__ord<i>` beside the output's
        hidden: list[tuple[str, BExpr]] = []
        binder._collect_windows = True
        try:
            for name, expr in items:
                b = binder.bind_with_aggs(expr)
                b = _encode_const_string_item(b)
                bound_items.append((name, b))
            grouped = has_group or bool(binder.aggs)
            if grouped:
                names = [n for n, _ in items]
                for i, ob in enumerate(sel.order_by):
                    if _orders_by_output(ob, names):
                        continue
                    e = _alias_subst(ob.expr, dict(items), scope)
                    hidden.append((f"__ord{i}", _encode_const_string_item(
                        binder.bind_with_aggs(e))))
        finally:
            binder._collect_windows = False

        having_b = None
        if sel.having is not None:
            having_b = binder.bind_with_aggs(sel.having)

        meta = plan.OutputMeta()

        if grouped:
            if sets is not None and any(a.distinct for a in binder.aggs):
                raise PlanError("DISTINCT aggregates under ROLLUP or "
                                "GROUPING SETS are not supported yet")
            # FD reduction: engage only when it unlocks the dense
            # segment-sum strategy the hash path couldn't use — the
            # hash path handles multi-key groups fine as-is (never
            # under grouping sets: grouping() names keys by place)
            fd_repl = []
            if sets is None and len(group_exprs) >= 2 \
                    and self._static_group_bound(
                        group_exprs, scope, tables)[0] == 0:
                n_aggs = len(binder.aggs)
                reduced, repl = self._reduce_fd_group_keys(
                    group_exprs, node, tables, binder)
                if repl and self._static_group_bound(
                        reduced, scope, tables)[0] > 0:
                    group_exprs, fd_repl = reduced, repl
                else:
                    del binder.aggs[n_aggs:]  # undo speculative aggs
            # rewrite grouped output exprs: replace group-expr occurrences
            # with group column refs
            def grouped_expr(b):
                b2 = _replace_group_refs(b, group_exprs)
                if fd_repl:
                    b2 = _substitute(b2, fd_repl)
                _check_agg_valid(b2, group_exprs)
                return b2
            rewritten = [(name, grouped_expr(b)) for name, b in
                         bound_items + hidden]
            if having_b is not None:
                having_b = grouped_expr(having_b)
            max_groups, dims, glos = self._static_group_bound(
                group_exprs, scope, tables)
            sort_dims = []
            if sets is not None and max_groups <= 0:
                sort_dims = self._sort_dims(group_exprs, scope, tables)
                if sort_dims is None:
                    raise PlanError(
                        "ROLLUP / GROUPING SETS past the dense group "
                        "bound need every key's domain known (a string, "
                        "a bool, an integer column with a stored range) "
                        f"in a {self.SORT_CODE_BITS}-bit code")
                if plan.grouping_key_order(sets, len(group_exprs)) is None:
                    raise PlanError(
                        "GROUPING SETS past the dense group bound must "
                        "nest (each set a prefix of one order of the "
                        "keys, as ROLLUP's are)")
            elif max_groups <= 0 \
                    and not any(a.distinct for a in binder.aggs):
                # a plain GROUP BY past the dense bound whose keys pack
                # may group by the sorted layout as one set
                # (compile.aggregate_strategy); where they do not it
                # keeps the hash table
                sort_dims = self._sort_dims(group_exprs, scope,
                                            tables) or []
            if not binder.windows:
                node = plan.Aggregate(node, group_exprs, binder.aggs,
                                      having_b, rewritten, max_groups,
                                      dims, group_lo=glos,
                                      grouping_sets=sets,
                                      sort_dims=sort_dims)
            else:
                # a window over a grouped query: the Aggregate hands on
                # its keys, its aggregates (`__agg<i>`) and the keys'
                # grouping bits; the windows see those; a Project
                # makes the output
                lifts = [(BAggRef(i, a.type), BCol(f"__agg{i}", a.type))
                         for i, a in enumerate(binder.aggs)]
                carried = [(g, BCol(g, ge.type)) for g, ge in group_exprs] \
                    + [(f"__agg{r.index}", r) for r, _ in lifts]
                if sets is not None:
                    carried += [
                        (f"__grouping{j}", BCol(f"__grouping{j}", INT8))
                        for j in range(len(group_exprs))]
                node = plan.Aggregate(node, group_exprs, binder.aggs,
                                      having_b, carried, max_groups, dims,
                                      group_lo=glos, grouping_sets=sets,
                                      sort_dims=sort_dims)

                def lift(e):
                    return _substitute(grouped_expr(e), lifts)
                node = plan.Window(node, [
                    type(w)(w.func, lift(w.arg) if w.arg is not None
                            else None, [lift(x) for x in w.partition_by],
                            [(lift(x), d) for x, d in w.order_by],
                            w.offset, w.type) for w in binder.windows])
                node = plan.Project(node, [(n, _substitute(b, lifts))
                                           for n, b in rewritten])
            out_names = [n for n, _ in bound_items]
            out_types = [b.type for _, b in rewritten[:len(bound_items)]]
        elif sel.distinct:
            node = plan.Project(node, bound_items)
            group_exprs = [(n, BCol(n, b.type)) for n, b in bound_items]
            dmax, ddims, dlos = self._static_group_bound(
                group_exprs, scope, tables)
            node = plan.Aggregate(node, group_exprs, [], None,
                                  [(n, BCol(g, b.type))
                                   for (n, b), (g, _) in
                                   zip(bound_items, group_exprs)],
                                  dmax, ddims, group_lo=dlos)
            out_names = [n for n, _ in bound_items]
            out_types = [b.type for _, b in bound_items]
        else:
            if binder.windows:
                node = plan.Window(node, binder.windows)
            node = plan.Project(node, bound_items)
            out_names = [n for n, _ in bound_items]
            out_types = [b.type for _, b in bound_items]

        # ---- ORDER BY / LIMIT ----------------------------------------------
        if sel.order_by:
            keys = []
            hidden_names = {n for n, _ in hidden}
            for i, ob in enumerate(sel.order_by):
                if f"__ord{i}" in hidden_names:
                    hname = f"__ord{i}"
                    keys.append((hname, ob.desc, ob.nulls_first))
                    d = self._find_dict_for_output(
                        hname, rewritten, group_exprs, scope, node)
                    if d is not None:
                        meta.dictionaries[hname] = d
                elif isinstance(ob.expr, ast.Literal) and isinstance(ob.expr.value, int):
                    keys.append((out_names[ob.expr.value - 1], ob.desc,
                                 ob.nulls_first))
                elif isinstance(ob.expr, ast.ColumnRef) \
                        and ob.expr.name in out_names:
                    keys.append((ob.expr.name, ob.desc,
                                 ob.nulls_first))
                elif not grouped and not sel.distinct \
                        and isinstance(node, plan.Project):
                    # hidden sort column (ordering by a non-output expr)
                    b = binder.bind(ob.expr)
                    if not b.type.is_orderable:
                        # same guard as the visible-key check below: a
                        # hidden datum key would silently sort by
                        # dictionary insertion code
                        raise PlanError(
                            f"ORDER BY on {b.type} is not supported")
                    hname = f"__ord{i}"
                    node.items.append((hname, b))
                    keys.append((hname, ob.desc, ob.nulls_first))
                    # a hidden dict-encoded string key must still sort
                    # by value rank, not code (sort_batch consults
                    # meta.dictionaries by key name)
                    if b.type.family == Family.STRING:
                        d = self._find_dict_for_output(
                            hname, node.items, [], scope, node)
                        if d is not None:
                            meta.dictionaries[hname] = d
                else:
                    raise PlanError("ORDER BY must reference output columns")
            for key in keys:
                kname = key[0]
                if kname in out_names:
                    kty = out_types[out_names.index(kname)]
                    if not kty.is_orderable:
                        # codes rank by dictionary insertion (and text
                        # rank diverges from pg's elementwise array
                        # order: text says {9} > {10}) — reject rather
                        # than silently misorder
                        raise PlanError(
                            f"ORDER BY on {kty} is not supported")
            node = plan.Sort(node, keys)
        if sel.limit is not None or sel.offset is not None:
            node = plan.Limit(node, sel.limit, sel.offset or 0)

        meta.names = out_names
        meta.types = out_types
        keys = dict(group_exprs)
        for name, ty, (_, b) in zip(out_names, out_types,
                                    (rewritten if grouped else
                                     bound_items)):
            b = keys.get(b.name, b) if isinstance(b, BCol) else b
            if ty.family == Family.INT and isinstance(b, BCol):
                r = self._int_range_of(b.name, dict(tables))
                if r is not None:
                    meta.int_ranges[name] = r
        # attach dictionaries for string outputs
        for name, ty in zip(out_names, out_types):
            if ty.uses_dictionary:
                d = self._find_dict_for_output(name, bound_items, group_exprs,
                                               scope, node)
                if d is not None:
                    meta.dictionaries[name] = d
        from .rules import RuleTrace
        from .rules import normalize as normalize_rules
        trace = self._trace if self._trace is not None else RuleTrace()
        if self.rules_on:
            node = normalize_rules(node, trace)
        else:
            # rule plane off (SET optimizer_rules = 'off'): the two
            # load-bearing passes still run, untraced
            from .pushdown import push_build_exprs
            push_build_exprs(node)
            plan.prune_scan_columns(node)
        meta.rule_trace = trace
        meta.access_paths = dict(self.access_paths)
        meta.memo = self.last_memo
        meta.subqueries = binder.subqueries_run + sum(derived_subqueries)
        return node, meta

    MAX_INT_GROUP_SPAN = 1 << 12
    # a SINGLE int key may span much further: one dense scatter-add
    # buffer per agg at 2M slots is ~16MB HBM and runs in ~1ms on a
    # v5e, where the while-loop hash build takes seconds (q3's
    # 262K-group GROUP BY l_orderkey: measured 0.1-3.5ms dense vs
    # ~11s hashed, with compile 1s vs 385s)
    MAX_INT_GROUP_SPAN_SINGLE = 1 << 21

    def _reduce_fd_group_keys(self, group_exprs, node, tables, binder):
        """Functional-dependency reduction of GROUP BY keys (the one
        FD the reference's optimizer derives that dominates star
        queries, pkg/sql/opt/props/func_dep.go): a group key that is a
        column of a table equi-joined on its single-column PRIMARY KEY
        to another group key is constant within every group of that
        other key — drop it from the keys and carry its value as a
        max() aggregate instead. TPC-H q3's GROUP BY l_orderkey,
        o_orderdate, o_shippriority (orders PK-joined on o_orderkey =
        l_orderkey) collapses to the ONE dense int key l_orderkey.

        Returns (reduced_group_exprs, [(orig_expr, BAggRef), ...]);
        the second list is empty when nothing reduced."""
        from .bound import BAggRef, BoundAgg
        if len(group_exprs) < 2:
            return group_exprs, []
        alias_to_table = dict(tables or [])

        # directed equi-join derivations from the planned FROM tree:
        # (mine, other) means "if `other`'s value is fixed per group
        # and `mine` is unique in its table, `mine`'s whole row is
        # fixed". Inner joins derive both ways; LEFT joins only pin
        # the BUILD (right) side — an unmatched probe row carries NULL
        # build values, so the probe cannot be inferred from them.
        derivs = []

        def _collect(n):
            if isinstance(n, plan.HashJoin):
                if n.join_type == "inner":
                    for lk, rk in zip(n.left_keys, n.right_keys):
                        derivs.append((lk, rk))
                        derivs.append((rk, lk))
                elif n.join_type == "left":
                    for lk, rk in zip(n.left_keys, n.right_keys):
                        derivs.append((rk, lk))
                _collect(n.left)
                _collect(n.right)
            elif hasattr(n, "child"):
                _collect(n.child)
        _collect(node)
        if not derivs:
            return group_exprs, []

        def _is_unique(alias, qual_col):
            """qual_col ("alias.col") is unique within its table:
            single-column PK, or the SNAPSHOT-AWARE uniqueness probe
            (TPC-H schemas declare no PKs; o_orderkey is unique by
            data). The live-rows distinct probe is NOT enough here:
            an AS OF read could see rows the current generation
            deleted, merging distinct groups."""
            t = alias_to_table.get(alias)
            if t is None:
                return False
            sch = self.catalog.schemas.get(t)
            col = qual_col.split(".", 1)[1]
            if sch is not None and sch.primary_key == [col]:
                return True
            fn = self.catalog.keys_unique_fn
            if fn is None:
                return False
            try:
                return bool(fn(t, (col,)))
            except KeyError:
                return False

        def _alias(q):
            return q.split(".", 1)[0]

        def _pinned(keys: set) -> set:
            """Aliases whose row is constant within each group of
            `keys` — the TRANSITIVE closure of the reference's
            func_dep derivation (q18: o_orderkey pins orders, orders'
            o_custkey pins customer through c_custkey, so c_name and
            c_custkey both drop). A column's value is fixed when it
            is a group key or any column of a pinned alias."""
            pinned = set()
            for kc in keys:
                if _is_unique(_alias(kc), kc):
                    pinned.add(_alias(kc))
            changed = True
            while changed:
                changed = False
                for mine, other in derivs:
                    al = _alias(mine)
                    if al in pinned:
                        continue
                    if (other in keys or _alias(other) in pinned) \
                            and _is_unique(al, mine):
                        pinned.add(al)
                        changed = True
            return pinned

        names = [ge.name if isinstance(ge, BCol) and "." in ge.name
                 else None for _, ge in group_exprs]
        kept_flag = [True] * len(group_exprs)
        # try dropping dictionary-coded keys first (they block the
        # dense strategy hardest), then the rest in order; a key drops
        # only if the keys REMAINING afterwards still pin its alias
        order = sorted(range(len(group_exprs)),
                       key=lambda i: (0 if names[i] is not None and
                                      group_exprs[i][1].type
                                      .uses_dictionary else 1, i))
        for i in order:
            nm = names[i]
            if nm is None:
                continue
            remaining = {names[j] for j in range(len(group_exprs))
                         if kept_flag[j] and j != i
                         and names[j] is not None}
            if remaining and _alias(nm) in _pinned(remaining):
                kept_flag[i] = False
        kept = []
        repl = []
        for flag, (gname, ge) in zip(kept_flag, group_exprs):
            if flag:
                kept.append((gname, ge))
            else:
                # "any": per-group-constant by construction — the
                # scatter-SET kernel, not the (64-bit-emulated, ~12x
                # slower) scatter-max (ops/agg.py group_any)
                binder.aggs.append(BoundAgg("any", ge, type=ge.type))
                repl.append((ge, BAggRef(len(binder.aggs) - 1,
                                         ge.type)))
        if not repl or not kept:
            return group_exprs, []
        return kept, repl

    def _int_range_of(self, bname: str, alias_to_table: dict):
        """(lo, hi) of the batch column `bname`: a stored integer
        column's range, or a derived table's column's; None where
        neither is known."""
        if bname in self._derived_ranges:
            return self._derived_ranges[bname]
        if self.catalog.int_range_fn is None or "." not in bname:
            return None
        alias, col = bname.split(".", 1)
        tname = alias_to_table.get(alias)
        try:
            r = self.catalog.int_range_fn(tname, col) if tname else None
        except KeyError:
            r = None
        return None if r is None else (int(r[0]), int(r[1]))

    def _static_group_bound(self, group_exprs, scope: Scope,
                            tables=None):
        """If every group key is a dict-encoded column, bool, or an int
        column with a small PROVEN value range, the group count is
        bounded by the product of code-space sizes — the planner then
        uses dense codes + segment_sum with a static size (TPC-H Q1: 4;
        SSB's GROUP BY d_year) instead of the while-loop hash table.
        Returns (bound, dims, los); bound 0 when unbounded. Each dim
        gets one extra NULL slot at compile time; los are per-dim value
        offsets (code = value - lo)."""
        alias_to_table = dict(tables or [])
        bound = 1
        dims = []
        los = []
        span_cap = (self.MAX_INT_GROUP_SPAN_SINGLE if len(group_exprs) == 1
                    else self.MAX_INT_GROUP_SPAN)
        for _, e in group_exprs:
            got = self._key_dim(e, scope, alias_to_table, span_cap)
            if got is None:
                return 0, [], []
            dims.append(got[0])
            los.append(got[1])
            bound *= dims[-1] + 1
            if bound > ((1 << 21) + 2 if len(group_exprs) == 1
                        else 1 << 16):
                return 0, [], []
        return bound, dims, los

    def _key_dim(self, e, scope: Scope, alias_to_table: dict,
                 span_cap: int):
        """(code-space size, value offset) of one group key: a
        dictionary's length, 2 for a bool, a proven integer range of
        at most span_cap values (a stored column's, or a year's
        extracted from a stored date); None where nothing bounds it."""
        if isinstance(e, BCol) and e.type.uses_dictionary:
            d = self._dict_by_batch_name(e.name, scope)
            return None if d is None else (max(len(d), 1), 0)
        if isinstance(e, BCol) and e.type.family == Family.BOOL:
            return 2, 0
        if isinstance(e, BCol) and e.name in self._derived_ranges:
            lo, hi = self._derived_ranges[e.name]
        elif isinstance(e, BCol) and e.type.family == Family.INT \
                and self.catalog.int_range_fn is not None \
                and "." in e.name:
            alias, col = e.name.split(".", 1)
            tname = alias_to_table.get(alias)
            try:
                r = (self.catalog.int_range_fn(tname, col)
                     if tname else None)
            except KeyError:  # renamed/computed: not stored
                r = None
            if r is None:
                return None
            lo, hi, _n = r
        else:
            # GROUP BY extract(year FROM datecol): the stored
            # column's value range bounds the year span
            # (TPC-H q7/q8/q9's o_year — 7 years, not a hash
            # table)
            yr = self._year_extract_range(e, alias_to_table)
            if yr is None:
                return None
            lo, hi = yr
        span = hi - lo + 1
        if span > span_cap:
            return None
        return int(span), int(lo)

    # an Aggregate past the dense bound may group by one sort of a
    # packed code (exec/rollup.py sorted_sets): every key's code space,
    # its NULL code included, takes bits of it
    SORT_CODE_BITS = 62
    MAX_INT_SORT_SPAN = 1 << 32

    def _sort_dims(self, group_exprs, scope: Scope, tables):
        """[(code-space size, value offset)] of an Aggregate's keys, for
        the packed sort code; None where a key's domain is unknown or
        the code would not fit."""
        alias_to_table = dict(tables or [])
        out = []
        for _, e in group_exprs:
            got = self._key_dim(e, scope, alias_to_table,
                                self.MAX_INT_SORT_SPAN)
            if got is None:
                return None
            out.append(got)
        if sum(int(dim).bit_length() for dim, _ in out) \
                > self.SORT_CODE_BITS:
            return None
        return out

    def _year_extract_range(self, e, alias_to_table):
        """(lo_year, hi_year) when e is extract(year FROM <stored
        date/timestamp column>) and the column's value range is
        provable, else None."""
        from .bound import BExtract
        if not (isinstance(e, BExtract) and e.part == "year"
                and isinstance(e.expr, BCol)
                and e.expr.type.family in (Family.DATE,
                                           Family.TIMESTAMP)
                and self.catalog.int_range_fn is not None
                and "." in e.expr.name):
            return None
        alias, col = e.expr.name.split(".", 1)
        tname = alias_to_table.get(alias)
        if tname is None:
            return None
        try:
            r = self.catalog.int_range_fn(tname, col)
        except KeyError:
            return None
        if r is None:
            return None
        lo, hi, _n = r
        if e.expr.type.family == Family.TIMESTAMP:
            lo, hi = lo // 86_400_000_000, hi // 86_400_000_000
        import datetime as _dt
        epoch = _dt.date(1970, 1, 1)
        return ((epoch + _dt.timedelta(days=int(lo))).year,
                (epoch + _dt.timedelta(days=int(hi))).year)

    def _dict_by_batch_name(self, name, scope: Scope):
        for t in scope.tables.values():
            for b in t.values():
                if b.batch_name == name:
                    return b.dictionary
        return None

    def _find_dict_for_output(self, name, bound_items, group_exprs, scope, node):
        for n, b in bound_items:
            if n != name:
                continue
            d = getattr(b, "dictionary", None)  # ad-hoc (CASE constants)
            if d is not None:
                return d
            if isinstance(b, BCol):
                d = self._dict_by_batch_name(b.name, scope)
                if d is not None:
                    return d
                # grouped output referencing a group column
                for gn, ge in group_exprs:
                    if b.name != gn:
                        continue
                    gd = getattr(ge, "dictionary", None)
                    if gd is not None:
                        return gd  # string-builtin transform output
                    if isinstance(ge, BCol):
                        return self._dict_by_batch_name(ge.name, scope)
        return None


def _few_rows(build: plan.PlanNode) -> bool:
    """May `build` be the right side of a cartesian product? A derived
    table whose rows are the groups of a GROUP BY over keys of a small
    static domain (TPC-DS Q77's `cs, cr`, call centres on both sides):
    the product's rows are static and few. A stored table never is."""
    if not isinstance(build, plan.Derived):
        return False
    n = build.child
    while isinstance(n, (plan.Project, plan.Sort, plan.Limit,
                         plan.Filter)):
        n = n.child
    return isinstance(n, plan.Aggregate) and (
        not n.group_by or 0 < n.max_groups <= CROSS_MAX_GROUPS)


# groups of a cartesian product's build side at most (_few_rows)
CROSS_MAX_GROUPS = 1 << 12


def _encode_const_string_item(b: BExpr) -> BExpr:
    """A constant-string output item (SELECT 'lit' FROM t, or a folded
    string builtin like trim(' x ')) compiles to dictionary code 0 +
    an ad-hoc one-entry output dictionary — the same representation
    CASE gives its constant string branches (binder.bind_case)."""
    if isinstance(b, BConst) and b.type.uses_dictionary \
            and isinstance(b.value, str) \
            and getattr(b, "dictionary", None) is None:
        from ..storage.columnstore import Dictionary
        d = Dictionary()
        out = BConst(d.encode(b.value), b.type)
        out.dictionary = d
        return out
    return b


def _default_name(e: ast.Expr) -> str:
    if isinstance(e, ast.ColumnRef):
        return e.name
    if isinstance(e, ast.FuncCall):
        return e.name
    return "column"


def _replace_group_refs(e: BExpr, group_exprs) -> BExpr:
    """Replace occurrences of a group expression with a ref to the group
    output column (so post-agg projection sees [G]-shaped arrays)."""
    return _substitute(e, [(gexpr, BCol(gname, gexpr.type))
                           for gname, gexpr in group_exprs])


def _substitute(e: BExpr, pairs) -> BExpr:
    """Replace repr-equal occurrences of each (expr, replacement)."""
    for orig, repl in pairs:
        if repr(e) == repr(orig):
            return repl
    # recurse
    import copy
    e2 = copy.copy(e)
    from .bound import (BBetween, BCase, BCast, BCoalesce, BDictLookup,
                        BExtract, BFunc, BInList, BIsNull, BUnary)
    if isinstance(e2, BBin):
        e2.left = _substitute(e2.left, pairs)
        e2.right = _substitute(e2.right, pairs)
    elif isinstance(e2, BUnary):
        e2.operand = _substitute(e2.operand, pairs)
    elif isinstance(e2, BBetween):
        e2.expr = _substitute(e2.expr, pairs)
        e2.lo = _substitute(e2.lo, pairs)
        e2.hi = _substitute(e2.hi, pairs)
    elif isinstance(e2, (BInList, BIsNull, BCast, BDictLookup, BDictRemap)):
        e2.expr = _substitute(e2.expr, pairs)
    elif isinstance(e2, BExtract):
        e2.expr = _substitute(e2.expr, pairs)
    elif isinstance(e2, BCase):
        e2.whens = [(_substitute(c, pairs), _substitute(v, pairs))
                    for c, v in e2.whens]
        if e2.else_ is not None:
            e2.else_ = _substitute(e2.else_, pairs)
    elif isinstance(e2, (BCoalesce, BFunc)):
        e2.args = [_substitute(a, pairs) for a in e2.args]
    return e2


def _orders_by_output(ob: ast.OrderItem, names: list) -> bool:
    """Does an ORDER BY item name an output column (by position or by
    name)?"""
    return (isinstance(ob.expr, ast.Literal)
            and isinstance(ob.expr.value, int)) \
        or (isinstance(ob.expr, ast.ColumnRef) and ob.expr.name in names)


def _alias_subst(e, items: dict, scope: Scope):
    """An ORDER BY expression of a grouped query with each bare name
    that is no column of the FROM clause but an output alias replaced
    by that output's expression (TPC-DS Q36 orders by `case when
    lochierarchy = 0 then i_category end`)."""
    import dataclasses
    if isinstance(e, ast.ColumnRef):
        if e.table is None and e.name in items:
            try:
                scope.resolve(e.name, None)
            except BindError:
                return items[e.name]
        return e
    if isinstance(e, (ast.Subquery, ast.Exists, ast.InSubquery)) \
            or not dataclasses.is_dataclass(e):
        return e
    changes = {}
    for f in dataclasses.fields(e):
        v = getattr(e, f.name)
        if isinstance(v, ast.Expr):
            changes[f.name] = _alias_subst(v, items, scope)
        elif isinstance(v, list):
            changes[f.name] = [
                tuple(_alias_subst(x, items, scope) if isinstance(
                    x, ast.Expr) else x for x in t) if isinstance(t, tuple)
                else _alias_subst(t, items, scope)
                if isinstance(t, ast.Expr) else t for t in v]
    return dataclasses.replace(e, **changes) if changes else e


def _check_agg_valid(e: BExpr, group_exprs) -> None:
    """Every column in a grouped output must be a group col or inside an
    aggregate (the binder already folded aggregates into BAggRef); a
    key's grouping bit (`__grouping<j>`, grouping()) is the Aggregate's
    own output."""
    gnames = {n for n, _ in group_exprs}
    for n in walk(e):
        if isinstance(n, BCol) and n.name not in gnames \
                and not n.name.startswith("__grouping"):
            raise PlanError(
                f"column {n.name!r} must appear in GROUP BY or an aggregate")
