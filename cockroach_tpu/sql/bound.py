"""Typed (bound) expression tree — the output of semantic analysis.

The reference separates AST (sem/tree) from the typed/normalized memo
expressions the optimizer works on (pkg/sql/opt/memo). Our bound tree
is the physical lowering: every node carries an SQLType whose physical
dtype the executor compiles against, decimals are already scaled ints,
date literals are already day numbers, and string literals against
dictionary-encoded columns are already dictionary codes.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from .types import SQLType


class BExpr:
    type: SQLType


@dataclass
class BConst(BExpr):
    value: object  # physical scalar (int/float/bool) or None for NULL
    type: SQLType = None


@dataclass
class BSubqueryArg(BExpr):
    """The scalar an uncorrelated expression subquery will return: not
    read yet when the statement is bound and planned, so nothing that
    plans can take it for a constant. `slot` names the subquery's own
    prepared statement in the list the engine keeps for this plan.
    Where exec/planparam.py finds it in a filter it becomes a nullable
    BParam, and the subquery runs at every dispatch, at the dispatch's
    read timestamp: the compiled program is the same whatever rows the
    subquery reads. Anywhere else the engine reads it once, when the
    statement is prepared, and writes the value into the plan as a
    BConst (engine counters exec.subquery.{args,inlined})."""
    slot: int
    type: SQLType = None


@dataclass
class BParam(BExpr):
    """Runtime statement parameter i — a literal the statement-shape
    plan cache (exec/planparam.py) stripped out of the plan so
    literal-varying statements share one compiled entry. Compiles to a
    broadcast of ``ctx.params[index]`` (exec/expr.py); the value rides
    the dispatch as a replicated runtime scalar instead of baking into
    the trace. ``repr`` deliberately shows index+type only, so the
    parameterized plan's fingerprint is literal-independent. A
    `nullable` one (a subquery's result) rides as a pair: the value
    and whether it is not NULL."""
    index: int
    type: SQLType = None
    nullable: bool = False


@dataclass
class BTableParam:
    """Where a dictionary table stood in a BDictLookup / BDictGather
    (its `table` or `null_table`): runtime parameter `index`, an array
    of `size` entries (the table padded to a power of two with entries
    no code reaches). exec/planparam.py lifts the tables of large
    dictionaries out of the plan this way: what a LIKE or a substring
    makes of a 1.5 M-value dictionary is data, so it is an argument of
    the compiled program and not a constant in it, and the program is
    the same for every load of the table."""
    index: int
    size: int


@dataclass
class BCol(BExpr):
    name: str  # unique batch column name ("alias.col")
    type: SQLType = None


@dataclass
class BBin(BExpr):
    op: str
    left: BExpr
    right: BExpr
    type: SQLType = None


@dataclass
class BUnary(BExpr):
    op: str  # "-" | "not"
    operand: BExpr
    type: SQLType = None


@dataclass
class BBetween(BExpr):
    expr: BExpr
    lo: BExpr
    hi: BExpr
    negated: bool = False
    type: SQLType = None


@dataclass
class BInList(BExpr):
    expr: BExpr
    values: list  # physical constants
    negated: bool = False
    type: SQLType = None


@dataclass
class BIsNull(BExpr):
    expr: BExpr
    negated: bool = False
    type: SQLType = None


@dataclass
class BCase(BExpr):
    whens: list[tuple[BExpr, BExpr]] = field(default_factory=list)
    else_: Optional[BExpr] = None
    type: SQLType = None


@dataclass
class BCast(BExpr):
    expr: BExpr
    type: SQLType = None


@dataclass
class BCoalesce(BExpr):
    args: list[BExpr] = field(default_factory=list)
    type: SQLType = None


@dataclass
class BExtract(BExpr):
    part: str
    expr: BExpr
    type: SQLType = None


@dataclass
class BDictLookup(BExpr):
    """mask_table[codes] — a predicate over a dictionary-encoded string
    column, pre-evaluated against the dictionary on the host (binder.py);
    on device it is a single gather."""
    expr: BExpr
    table: object = None  # np.ndarray bool[len(dictionary)]
    type: SQLType = None


@dataclass
class BDictRemap(BExpr):
    """remap_table[codes] — translate one string column's dictionary
    codes into another column's code space (for cross-table string
    equality, e.g. join keys); absent values map to -1 (never match).
    ``null_table`` (optional bool[len(dict)], True=non-null) marks
    entries whose RESULT is SQL NULL — json/array operators like
    ``j->'missing'`` yield NULL per dictionary entry; it ANDs into the
    output validity on device."""
    expr: BExpr
    table: object = None  # np.ndarray int32[len(src dictionary)]
    type: SQLType = None
    null_table: object = None  # np.ndarray bool[len(src dictionary)]


@dataclass
class BFunc(BExpr):
    """N-ary elementwise builtin on device (pow, atan2, greatest, ...).
    The kernel table lives in exec/expr.py; the binder (sql/builtins.py)
    has already coerced arguments to the kernel's expected families."""
    name: str
    args: list[BExpr] = field(default_factory=list)
    type: SQLType = None


@dataclass
class BDictGather(BExpr):
    """value_table[codes] — a scalar function of a dictionary-encoded
    string column, pre-evaluated against the dictionary on the host
    (sql/builtins.py); on device it is one typed gather. Generalizes
    BDictLookup (bool tables) to arbitrary result types: length() is an
    int64 table, upper() is a code table into a NEW output dictionary
    (carried in .dictionary). ``null_table`` as in BDictRemap: entries
    whose result is SQL NULL (e.g. arr[i] past the end)."""
    expr: BExpr
    table: object = None  # np.ndarray[len(dictionary)] of type's dtype
    type: SQLType = None
    null_table: object = None  # np.ndarray bool[len(dictionary)]
    # output Dictionary for string results. repr=False: two binds of
    # the same expression build distinct Dictionary objects, and the
    # planner matches group exprs structurally by repr
    dictionary: object = field(default=None, repr=False)


@dataclass
class BAggRef(BExpr):
    """Placeholder for aggregate i's result in a post-aggregation
    expression (the reference's execbuilder renders final-stage AVG as
    SUM/COUNT the same way, physicalplan/aggregator_funcs.go)."""
    index: int
    type: SQLType = None


@dataclass
class BWinRef(BExpr):
    """Placeholder for window function i's result column (the Window
    plan node materializes it as batch column __win{i})."""
    index: int
    type: SQLType = None


@dataclass
class BoundWindow:
    """One window function instance: func(arg) OVER (partition, order).
    Offset carries the lag/lead distance."""
    func: str  # row_number|rank|dense_rank|lag|lead|first_value|
    #            last_value|sum|sum_int|count|count_rows|min|max|avg
    arg: Optional[BExpr]
    partition_by: list[BExpr] = field(default_factory=list)
    order_by: list[tuple[BExpr, bool]] = field(default_factory=list)
    offset: int = 1  # lag/lead distance
    type: SQLType = None


@dataclass
class BoundAgg:
    """One aggregate instance: func(arg) [distinct]."""
    func: str  # sum | count | count_rows | min | max | avg | sum_int
    arg: Optional[BExpr]
    type: SQLType = None
    distinct: bool = False
    # the engine's value-range proof of an exact SUM / AVG argument
    # (sql/valuerange.py over the store's zone-map ranges): it is
    # never negative and fits arg_bits bits (0 = nothing proven). The
    # bit length and not the maximum, so the plan (these fields are in
    # its fingerprint) changes only when a value crosses a power of
    # two. Sizes the sum's words, limbs and overflow sentinel
    # (exec/compile.py large_layout, ops/agg.py _group_sum_i64_limbs)
    arg_bits: int = 0
    arg_nonneg: bool = False


def walk(e: BExpr):
    yield e
    for child in _children(e):
        yield from walk(child)


def _children(e: BExpr):
    if isinstance(e, BBin):
        return [e.left, e.right]
    if isinstance(e, BUnary):
        return [e.operand]
    if isinstance(e, BBetween):
        return [e.expr, e.lo, e.hi]
    if isinstance(e, (BInList, BIsNull, BDictLookup, BDictRemap,
                      BDictGather)):
        return [e.expr]
    if isinstance(e, BFunc):
        return list(e.args)
    if isinstance(e, BCase):
        out = []
        for c, v in e.whens:
            out += [c, v]
        if e.else_ is not None:
            out.append(e.else_)
        return out
    if isinstance(e, BCast):
        return [e.expr]
    if isinstance(e, BCoalesce):
        return list(e.args)
    if isinstance(e, BExtract):
        return [e.expr]
    return []


def referenced_columns(e: BExpr) -> set[str]:
    return {n.name for n in walk(e) if isinstance(n, BCol)}
