"""Build-side expression pushdown: evaluate join-build-only
subexpressions BEFORE the join, on the (small) build domain.

The reference's normalization rules push filters and projections
through joins (pkg/sql/opt/norm/rules/select.opt, prune_cols.opt).
On TPU the stakes are higher than CPU cycle counts: every payload
column an expression touches after the join is one probe-length
random GATHER (~44 ms per 8M rows measured on v5e), while the same
expression computed on the build side costs a build-length
elementwise pass — and a BOOL result packs into the direct join's
three-state table (ops/join.py), so the whole dimension predicate
rides the join's ONE gather.

TPC-H Q14's `p_type LIKE 'PROMO%'`, Q19's brand/container tests and
every SSB dimension filter are exactly this shape.

The pass runs after planning, before column pruning: BOOL-typed
maximal subtrees whose column refs all come from one hash-join build
scan are replaced by a reference to a computed build column, then
payload columns nothing references anymore are dropped (often the
original dictionary column itself — its probe gather disappears)."""

from __future__ import annotations

from . import plan
from .bound import (BAggRef, BCol, BConst, BExpr, BWinRef,
                    referenced_columns, walk)
from .types import Family


def expr_key(e: BExpr) -> str:
    """Structural dedup key. repr() alone is unsafe: numpy summarizes
    arrays >1000 elements ('[False False ... False]'), so two distinct
    dictionary LUTs could collide — include a digest of every table's
    full contents."""
    import hashlib
    h = hashlib.sha256(repr(e).encode())
    for x in walk(e):
        for name in ("table", "null_table"):
            t = getattr(x, name, None)
            if t is not None and hasattr(t, "tobytes"):
                h.update(t.tobytes())
            elif isinstance(t, (list, tuple)):
                h.update(repr(t).encode())
    return h.hexdigest()


def _rebuild(e, f):
    """Rebuild a bound expr with f applied to child expressions."""
    import dataclasses
    if not dataclasses.is_dataclass(e):
        return e
    changes = {}
    for fld in dataclasses.fields(e):
        v = getattr(e, fld.name)
        if isinstance(v, BExpr):
            nv = f(v)
            if nv is not v:
                changes[fld.name] = nv
        elif isinstance(v, list) and v and \
                isinstance(v[0], tuple) and len(v[0]) == 2 and \
                isinstance(v[0][0], BExpr):
            nv = [(f(a), f(b)) for a, b in v]
            changes[fld.name] = nv
        elif isinstance(v, list) and v and isinstance(v[0], BExpr):
            changes[fld.name] = [f(x) for x in v]
    return dataclasses.replace(e, **changes) if changes else e


def push_build_exprs(root: plan.PlanNode) -> list:
    """In-place pass over a plan spine (see module doc). Returns the
    names of the pushed computed columns (rule-trace fodder,
    sql/rules.py)."""
    joins: list = []

    def collect(n):
        if n is None or isinstance(n, plan.Scan):
            return
        if isinstance(n, plan.HashJoin):
            # inner joins only: a LEFT join NULL-extends build columns
            # for unmatched probe rows, and a pushed expression (e.g.
            # coalesce) would wrongly see build-side values instead of
            # those NULLs
            if isinstance(n.right, plan.Scan) and \
                    n.join_type == "inner":
                joins.append(n)
            collect(n.left)
            collect(n.right)
            return
        collect(getattr(n, "child", None))

    collect(root)
    if not joins:
        return []
    by_alias = {}
    for j in joins:
        cols = set(j.payload) | set(j.right.columns) | \
            {n for n, _ in j.right.computed}
        by_alias[j.right.alias] = (j, cols)
    counter = [0]
    created: dict = {}

    def try_push(e):
        if isinstance(e, (BCol, BConst)) or \
                getattr(e, "type", None) is None or \
                e.type.family != Family.BOOL:
            return None
        refs = referenced_columns(e)
        if not refs:
            return None
        if any(isinstance(x, (BAggRef, BWinRef)) for x in walk(e)):
            return None
        for alias, (j, cols) in by_alias.items():
            if refs <= cols:
                key = (alias, expr_key(e))
                name = created.get(key)
                if name is None:
                    name = f"{alias}.__push{counter[0]}"
                    counter[0] += 1
                    created[key] = name
                    j.right.computed.append((name, e))
                    j.payload.append(name)
                    j.pack_payload.append(name)
                return BCol(name, e.type)
        return None

    def rewrite(e):
        if e is None or not isinstance(e, BExpr):
            return e
        r = try_push(e)
        if r is not None:
            return r
        return _rebuild(e, rewrite)

    has_window = False

    def apply(n):
        nonlocal has_window
        if n is None:
            return
        if isinstance(n, plan.Scan):
            return
        if isinstance(n, plan.HashJoin):
            apply(n.left)
            apply(n.right)
            return
        if isinstance(n, plan.Filter):
            n.pred = rewrite(n.pred)
        elif isinstance(n, plan.Project):
            n.items = [(nm, rewrite(e)) for nm, e in n.items]
        elif isinstance(n, plan.Aggregate):
            n.group_by = [(nm, rewrite(e)) for nm, e in n.group_by]
            for a in n.aggs:
                if a.arg is not None:
                    a.arg = rewrite(a.arg)
            if n.having is not None:
                n.having = rewrite(n.having)
            n.items = [(nm, rewrite(e)) for nm, e in n.items]
        elif isinstance(n, plan.Window):
            has_window = True
        apply(getattr(n, "child", None))

    apply(root)
    if not created:
        return []
    if has_window:
        return []  # window specs not rewritten: keep payloads untouched

    # drop payload columns no STRICT ancestor references anymore
    # (their probe gathers disappear with them). A join's own keys
    # read the build batch directly, and the build scan's computed
    # exprs resolve below the join — neither is a payload use; only
    # nodes ABOVE the join on the probe spine are.
    def node_refs(n) -> set:
        out: set = set()
        if isinstance(n, plan.Filter):
            out |= referenced_columns(n.pred)
        elif isinstance(n, plan.Project):
            for _, e in n.items:
                out |= referenced_columns(e)
        elif isinstance(n, plan.Aggregate):
            for _, e in n.group_by:
                out |= referenced_columns(e)
            for a in n.aggs:
                if a.arg is not None:
                    out |= referenced_columns(a.arg)
            if n.having is not None:
                out |= referenced_columns(n.having)
            for _, e in n.items:
                out |= referenced_columns(e)
        elif isinstance(n, plan.HashJoin):
            out |= set(n.left_keys)   # probe keys may come from a
            # lower join's payload; right keys read its own build
        return out

    spine = []
    n = root
    while n is not None and not isinstance(n, plan.Scan):
        spine.append(n)
        n = n.left if isinstance(n, plan.HashJoin) \
            else getattr(n, "child", None)
    above: set = set()
    for n in spine:
        if isinstance(n, plan.HashJoin) and n in joins:
            n.payload = [p for p in n.payload if p in above]
            n.pack_payload = [p for p in n.pack_payload
                              if p in n.payload]
        above |= node_refs(n)
    return sorted(created.values())
