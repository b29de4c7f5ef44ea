"""Statement-shape plan parameterization for the analytic path.

The OLTP lane already strips literals from statement TEXT
(oltplane.normalize) so point reads share a compiled kernel. This
module does the same one level down, on the bound PLAN: eligible
filter literals are replaced by ``BParam`` placeholders whose values
ride the dispatch as runtime scalars, so 100 sessions running the
same parameterized q3/q6 with different dates/quantities share ONE
``_exec_cache`` entry instead of each paying a trace (the reference's
plan cache keyed on the statement fingerprint, pkg/sql/plan_cache).

What is lifted, and nothing else:

- literals: constants inside ``Filter.pred`` / ``Scan.filter`` /
  ``Derived.filter`` comparison spines. Conservative by construction:
  anything that shapes the compiled program stays baked and keeps the
  plan fingerprint distinct, so a shape-changing literal (LIMIT,
  Compact.frac derived from selectivity, dictionary masks, function
  args read at compile time) misses the cache instead of sharing a
  wrong executable;
- the result of an uncorrelated scalar subquery that stands in such a
  spine (``BSubqueryArg`` -> a nullable ``BParam``; its place among the
  values holds a ``SubqueryValue`` until ``Prepared.subquery_params``
  runs the subquery, at every dispatch). What a subquery returns never
  shapes the program, so the plan's cache key no longer follows the
  data. One left anywhere else is read once and written into the plan
  (``inline_subquery_args``), as every subquery's result was before;
- the tables of large dictionaries (``lift_tables``: what a LIKE or a
  substring makes of every value of a column's dictionary), where the
  engine asks for them: arguments padded to a power of two, so the
  program knows their length and not their content.
"""

from __future__ import annotations

import dataclasses
import hashlib
import re

import numpy as np

from ..sql import bound as B
from ..sql import plan as P
from ..sql.types import Family, SQLType

# Literal families whose physical scalars can ride as runtime args.
# STRING (and ARRAY/JSON) predicates are host-pre-evaluated into
# dictionary tables at bind time, so they are inherently baked; BOOL
# constants often fold control flow.
_ELIGIBLE = (Family.INT, Family.DECIMAL, Family.DATE, Family.TIMESTAMP,
             Family.FLOAT)

# The scalar result of an uncorrelated expression subquery (TPC-H
# Q22's `c_acctbal > (SELECT avg(c_acctbal) ...)`), which the binder
# leaves unread (sql/bound.py): found in a filter it is lifted here
# beside the literals, so it is an argument of the compiled program
# and not a constant of it, and one program serves whatever rows the
# subquery reads. benchmark/generators/tpch_full.py asks for this name.
SubqueryArg = B.BSubqueryArg


@dataclasses.dataclass(frozen=True)
class SubqueryValue:
    """Where a lifted SubqueryArg's value will stand among a
    statement's runtime arguments: the pair (value, is-not-NULL) that
    Prepared reads at each dispatch from the prepared subquery `slot`
    (exec/session.py). Before that it holds the place."""
    slot: int
    type: SQLType

    def pair(self, value):
        """The argument for a physical `value`, None for NULL."""
        dt = self.type.np_dtype
        return (np.asarray(0 if value is None else value, dtype=dt),
                np.bool_(value is not None))


def param_signature(values) -> tuple:
    """What of the runtime arguments the compiled program is traced
    for: each one's dtype and shape, never its value."""
    out = []
    for v in values:
        if isinstance(v, SubqueryValue):
            out.append(f"{np.dtype(v.type.np_dtype)}?")
        else:
            out.append(f"{v.dtype}{list(v.shape) or ''}")
    return tuple(out)

# Bound on lifted literals per statement: each becomes one extra jit
# argument; a pathological filter should fall back to text keying.
_MAX_PARAMS = 16


def _eligible_const(e) -> bool:
    return (isinstance(e, B.BConst) and e.value is not None
            and not isinstance(e.value, bool)
            and e.type is not None and e.type.family in _ELIGIBLE)


class _Lifter:
    def __init__(self, shared: bool = False):
        self.values: list = []
        self.overflow = False
        # with `shared`, (type, value) -> its slot: a literal a
        # statement repeats (a report's date window in each of its CTEs
        # and UNION ALL branches) is one argument, not one an occurrence
        self.shared = shared
        self.slots: dict = {}

    def const(self, e: B.BConst):
        dt = e.type.np_dtype
        v = np.asarray(e.value, dtype=dt)
        if v.item() != e.value:  # lossy physical round-trip: keep baked
            return e
        key = (e.type, v.dtype.str, v.item())
        if self.shared and key in self.slots:
            return B.BParam(self.slots[key], e.type)
        if len(self.values) >= _MAX_PARAMS:
            self.overflow = True
            return e
        self.values.append(v)
        self.slots[key] = len(self.values) - 1
        return B.BParam(len(self.values) - 1, e.type)

    def arg(self, e: B.BSubqueryArg):
        if e.type.family not in _ELIGIBLE \
                or len(self.values) >= _MAX_PARAMS:
            return e    # the engine reads it now, into the plan
        self.values.append(SubqueryValue(e.slot, e.type))
        return B.BParam(len(self.values) - 1, e.type, nullable=True)

    def expr(self, e):
        """Rewrite the comparison spine of a predicate. Recursion is a
        whitelist — BBin/BUnary/BBetween — because other nodes read
        constant args structurally at compile time (BFunc's round_n
        digits, BInList value lists, dictionary tables)."""
        if _eligible_const(e):
            return self.const(e)
        if isinstance(e, B.BSubqueryArg):
            return self.arg(e)
        if isinstance(e, B.BCast) and isinstance(e.expr, B.BSubqueryArg):
            # a subquery's result compared in another type than its own
            x = self.arg(e.expr)
            return e if x is e.expr else dataclasses.replace(e, expr=x)
        if isinstance(e, B.BBin):
            l, r = self.expr(e.left), self.expr(e.right)
            if l is not e.left or r is not e.right:
                return B.BBin(e.op, l, r, e.type)
            return e
        if isinstance(e, B.BUnary):
            o = self.expr(e.operand)
            if o is not e.operand:
                return B.BUnary(e.op, o, e.type)
            return e
        if isinstance(e, B.BBetween):
            x, lo, hi = self.expr(e.expr), self.expr(e.lo), self.expr(e.hi)
            if x is not e.expr or lo is not e.lo or hi is not e.hi:
                return B.BBetween(x, lo, hi, e.negated, e.type)
            return e
        return e

    def node(self, n):
        if isinstance(n, P.Scan):
            if n.filter is None:
                return n
            f = self.expr(n.filter)
            return n if f is n.filter else dataclasses.replace(n, filter=f)
        if isinstance(n, P.Derived):
            c = self.node(n.child)
            f = self.expr(n.filter) if n.filter is not None else None
            if c is n.child and f is n.filter:
                return n
            return dataclasses.replace(n, child=c, filter=f)
        if isinstance(n, P.Filter):
            c = self.node(n.child)
            p = self.expr(n.pred) if n.pred is not None else None
            if c is n.child and p is n.pred:
                return n
            return dataclasses.replace(n, child=c, pred=p)
        if isinstance(n, (P.HashJoin, P.UnionAll)):
            l, r = self.node(n.left), self.node(n.right)
            if l is n.left and r is n.right:
                return n
            return dataclasses.replace(n, left=l, right=r)
        if isinstance(n, (P.Project, P.Aggregate, P.Sort, P.Limit,
                          P.Window, P.Compact)):
            c = self.node(n.child)
            return n if c is n.child else dataclasses.replace(n, child=c)
        return n  # unknown node: leave baked (conservative)


# tables of dictionaries past this length leave the plan; shorter ones
# stay baked, where exec/expr.py turns them into one-hot matmuls
_TABLE_MIN = 512


# what lift_tables' walk does not open: a plan is prepared on every
# execution, and a column type alone has six fields
_LEAVES = (str, int, float, bool, type(None), np.generic, np.ndarray,
           SQLType, frozenset, B.BCol, B.BConst, B.BParam)


def _rewrite(node, replace):
    """`node` with every object `replace` answers for swapped for its
    answer (`replace(o)` is None for one it leaves alone, and what it
    replaces is not opened). Walks every expression of a plan by
    dataclass field, copying only what changes."""
    def walk(o):
        if isinstance(o, _LEAVES):
            return o        # most of a plan's fields: keep the walk cheap
        new = replace(o)
        if new is not None:
            return new
        if dataclasses.is_dataclass(o) and not isinstance(o, type):
            changes = {}
            for f in dataclasses.fields(o):
                v = getattr(o, f.name)
                w = walk(v)
                if w is not v:
                    changes[f.name] = w
            return dataclasses.replace(o, **changes) if changes else o
        if isinstance(o, (list, tuple)):
            out = [walk(v) for v in o]
            if all(a is b for a, b in zip(out, o)):
                return o
            return type(o)(out)
        if isinstance(o, dict):
            out = {k: walk(v) for k, v in o.items()}
            if all(out[k] is o[k] for k in o):
                return o
            return out
        return o

    return walk(node)


def lift_tables(node, values: list):
    """Replace the tables of large dictionaries in `node`'s expressions
    (BDictLookup masks, BDictGather value and null tables) by
    BTableParams, appending each table, padded to a power of two, to
    `values` (the runtime arguments, after the lifted scalars).

    A table is what a predicate or a function makes of every value of
    a column's dictionary: the rows' own data. Baked into the program
    it makes the program the data's (another load of the table,
    another program, compiled cold); as an argument it leaves a
    program that only knows the table's power-of-two length. Every
    expression of the plan is walked (a table may stand in a projected
    or grouped expression, TPC-H Q22's substring of c_phone)."""
    memo: dict = {}

    def table(t):
        t = np.asarray(t)
        if t.ndim != 1 or t.shape[0] <= _TABLE_MIN:
            return None
        key = id(t)
        if key not in memo:
            size = 1 << (int(t.shape[0]) - 1).bit_length()
            padded = np.zeros((size,), dtype=t.dtype)
            padded[:t.shape[0]] = t
            values.append(padded)
            memo[key] = (B.BTableParam(len(values) - 1, size), t)
        return memo[key][0]

    def replace(o):
        if not isinstance(o, (B.BDictLookup, B.BDictGather)) \
                or isinstance(o.table, B.BTableParam):
            return None
        tp = table(o.table)
        if tp is None:
            return None
        changes = {"expr": _rewrite(o.expr, replace), "table": tp}
        nt = getattr(o, "null_table", None)
        if nt is not None:
            changes["null_table"] = table(np.asarray(nt, dtype=bool))
        return dataclasses.replace(o, **changes)

    return _rewrite(node, replace)


def parameterize(node, tables: bool = True):
    """Lift eligible filter literals, and the filters' SubqueryArgs,
    out of ``node``.

    Returns ``(parameterized_node, values)`` — values is a tuple
    positionally matching the BParam indices: np scalars, and a
    SubqueryValue where a subquery's result will stand — or
    ``(node, None)`` when nothing was lifted. With `tables` the tables
    of large dictionaries follow the scalars (lift_tables); a
    distributed plan keeps them baked (its arguments are replicated
    scalars)."""
    lf = _Lifter()
    out = lf.node(node)
    if lf.overflow:
        # past _MAX_PARAMS occurrences, equal literals share one slot.
        # Only there: sharing makes the plan follow the values (two
        # literals that happen to be equal would be one argument for one
        # parameter set and two for the next), which a statement of few
        # literals need not pay for
        lf = _Lifter(shared=True)
        out = lf.node(node)
    if lf.overflow:
        out, lf = node, _Lifter()
    values = list(lf.values)
    if tables:
        out = lift_tables(out, values)
    if not values:
        return node, None
    return out, tuple(values)


def inline_subquery_args(node, const_of):
    """`node` with every SubqueryArg left in it replaced by
    ``const_of(arg)``, a BConst: the ones no filter held, or all of
    them where the plan is not parameterized. Returns (node, how many
    were replaced)."""
    found = []

    def replace(o):
        if isinstance(o, B.BSubqueryArg):
            found.append(o)
            return const_of(o)
        return None

    return _rewrite(node, replace), len(found)


def plan_fingerprint(node) -> str:
    """Deterministic structural fingerprint of a plan tree.

    Unlike ``hash(repr(node))``, ndarray payloads (dictionary masks,
    remap tables) hash their full bytes — repr truncates large arrays,
    which could collide two different plans once sql_text leaves the
    cache key. Fields marked repr=False (e.g. BDictGather.dictionary,
    a fresh object per bind) are skipped, matching the planner's
    structural-match convention."""
    h = hashlib.sha1()

    def feed(o):
        if isinstance(o, np.ndarray):
            h.update(b"nd|")
            h.update(str(o.dtype).encode())
            h.update(str(o.shape).encode())
            h.update(o.tobytes())
        elif dataclasses.is_dataclass(o) and not isinstance(o, type):
            h.update(type(o).__name__.encode())
            for f in dataclasses.fields(o):
                if not f.repr:
                    continue
                h.update(f.name.encode())
                feed(getattr(o, f.name))
        elif isinstance(o, (list, tuple)):
            h.update(b"[")
            for x in o:
                feed(x)
            h.update(b"]")
        elif isinstance(o, dict):
            h.update(b"{")
            for k, v in o.items():
                feed(k)
                feed(v)
            h.update(b"}")
        elif isinstance(o, frozenset):
            h.update(b"fs")
            for x in sorted(repr(x) for x in o):
                h.update(x.encode())
        else:
            h.update(repr(o).encode())
        h.update(b";")

    feed(node)
    return h.hexdigest()


# Statement-shape text: literals -> "?" so literal-varying texts key
# identically. Broader than oltplane._LIT_RE (floats too); string
# literals normalize here even though their plans stay distinct — the
# plan fingerprint disambiguates them.
_LIT_RE = re.compile(
    r"'(?:[^']|'')*'|(?<![\w.])\d+(?:\.\d+(?:[eE][+-]?\d+)?)?(?![\w.])")


def shape_text(sql: str) -> str:
    return _LIT_RE.sub("?", sql)
