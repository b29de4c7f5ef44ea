"""Logical plan nodes (the analogue of memo relational expressions).

The plan tree the heuristic planner emits and the executor compiles.
Mirrors the reference's planNode/physicalPlan split loosely: this is
the single logical form; the distribution layer decides how a Scan's
spans map onto the device mesh (parallel/partition.py), like
PartitionSpans (distsql_physical_planner.go:1096) decides node
placement.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from .bound import BExpr, BoundAgg
from .types import SQLType


class PlanNode:
    pass


@dataclass
class Scan(PlanNode):
    table: str
    alias: str
    # batch column name -> stored column name
    columns: dict[str, str] = field(default_factory=dict)
    # conjuncts pushed down to the scan (evaluated fused with the read)
    filter: Optional[BExpr] = None
    # computed columns added by the planner (e.g. remapped join keys)
    computed: list[tuple[str, BExpr]] = field(default_factory=list)
    # stored columns uploaded to HBM as int32 (engine-proven value
    # range): the scan upcasts them back to int64, so programs see
    # identical semantics while the HBM read moves half the bytes —
    # int64 is software-emulated on TPU, so narrow uploads also shed
    # the emulation's limb ops on the first touch
    narrowed: frozenset = frozenset()


@dataclass
class Derived(PlanNode):
    """A derived table (FROM (SELECT ...) AS alias, or the grouped
    sub-select a correlated subquery unnests into) planned in place:
    the sub-select's plan is `child`, and this node is to the outer
    plan what a Scan is, the alias's source of columns. Its output
    renames the child's columns to the alias's batch names; `filter`
    and `computed` are the outer planner's pushed conjuncts and
    computed join keys, as on a Scan. Nothing of it is stored, so
    nothing about it is measured from data: a program with a Derived
    depends on the base tables' statistics alone."""
    child: PlanNode
    alias: str
    # batch column name ("alias.col") -> the child's output name
    columns: dict[str, str] = field(default_factory=dict)
    filter: Optional[BExpr] = None
    computed: list[tuple[str, BExpr]] = field(default_factory=list)
    # for messages and the catalog's misses; never a stored table
    table: str = ""


@dataclass
class UnionAll(PlanNode):
    """UNION ALL of two plans whose batches hold the same column names
    (`names`): the right batch's rows follow the left's, a column's
    data and validity concatenated and the selections beside them. A
    derived table's body of three branches is two of these, nested on
    the left. Each branch is planned on its own (its string columns
    already translated into one dictionary by the planner's Project
    above it), so nothing of a branch is decoded to the host."""
    left: PlanNode
    right: PlanNode
    names: list[str] = field(default_factory=list)


@dataclass
class Filter(PlanNode):
    child: PlanNode
    pred: BExpr = None


@dataclass
class HashJoin(PlanNode):
    left: PlanNode           # probe side
    right: PlanNode          # build side
    left_keys: list[str] = field(default_factory=list)
    right_keys: list[str] = field(default_factory=list)
    payload: list[str] = field(default_factory=list)  # build cols to carry
    join_type: str = "inner"
    # output copies per probe row: 1 for unique build keys; the
    # engine's host-side max-multiplicity probe sets K>1 for
    # duplicate-keyed builds (static expansion bound)
    expand: int = 1
    # direct-address join (the TPU fast path): when the single build
    # key is int-family with a dense value range (dimension pks, dict
    # codes), the engine sets (base, size) and the join becomes one
    # scatter to build + one gather to probe — no hash table, no
    # while_loop. None = open-addressing hash table.
    direct: Optional[tuple] = None  # (base, table_size)
    # payload columns that are dict codes (int32, >= 0): the direct
    # fold packs match/null/value into one table -> one probe gather
    pack_payload: list = field(default_factory=list)


@dataclass
class Compact(PlanNode):
    """Pack selected rows into a smaller batch (block by block, a
    log-step displacement network laid out from the selection mask:
    ops/pallas/compact.py; rows keep their order inside a block, and
    nothing may depend on it). Inserted by the engine
    (Engine._insert_compaction) on a probe spine under aggregation
    wherever the pack costs clearly less than the probes and the
    scatter above it save: every downstream per-row op — join probe
    gathers above all — then runs at ``frac`` of the batch instead of
    full width with masked lanes. The TPU analogue of the reference's
    selection vectors (coldata.Batch sel), which its operators consume
    implicitly; XLA needs the compaction to be an explicit op.
    Per-block capacity overflow raises the __compact_overflow sentinel
    and the engine replans uncompacted (exec.compact.overflows)."""
    child: PlanNode
    # per-block capacity, a share of the block: the estimated share of
    # the batch's rows that survive times a headroom, at most a half
    # (Engine._compact_frac); compile rounds block * frac up to 128
    # lanes. An estimate, never a bound: the sentinel is what is exact
    frac: float = 0.125
    block: int = 32768
    # 64-bit columns of the batch whose values the store proves within
    # int32 (Engine.narrow32_cols over the scans beneath): each goes
    # through the network as one word, not two
    narrow: frozenset = frozenset()


@dataclass
class Project(PlanNode):
    child: PlanNode
    items: list[tuple[str, BExpr]] = field(default_factory=list)


@dataclass
class Aggregate(PlanNode):
    child: PlanNode
    group_by: list[tuple[str, BExpr]] = field(default_factory=list)
    aggs: list[BoundAgg] = field(default_factory=list)
    having: Optional[BExpr] = None  # over BAggRef/group columns
    # output projections over group cols + agg refs
    items: list[tuple[str, BExpr]] = field(default_factory=list)
    max_groups: int = 0  # static bound if known (dict-encoded keys), else 0
    # per-key code-space sizes when max_groups > 0 (dense segment-sum
    # strategy: gid = mixed-radix code over these dims, +1 slot per dim
    # for NULL); empty when the hash-table strategy is required
    group_dims: list[int] = field(default_factory=list)
    # per-dim value offsets: code = value - lo (0 for dict/bool dims;
    # nonzero for small-range INT keys proven dense by stats)
    group_lo: list[int] = field(default_factory=list)
    # static upper bound on rows per group (engine-measured key
    # multiplicity), 0 = unknown. Sizes the i32 limb width of exact
    # int64 group sums (ops/agg.py group_sum): a tight bound means 3
    # fast i32 scatters instead of the software-emulated 64-bit one.
    max_group_rows: int = 0
    # GROUP BY ROLLUP / GROUPING SETS: each set a tuple of indexes into
    # group_by (None: a plain GROUP BY). The finest set (every key) is
    # aggregated over the child's rows, each other set from the groups
    # of a finer one; the output is every set's groups, a rolled-up
    # key NULL, and `__grouping<j>` 1 where key j is rolled up
    grouping_sets: Optional[list] = None
    # [(code-space size, value offset)] a key, where the finest set is
    # past the dense bound: the keys' packed code its rows sort by
    sort_dims: list = field(default_factory=list)
    # slots of the output over all sets, an estimate of their groups
    # (Engine._size_grouping_sets); 0 = every set's whole domain
    set_slots: int = 0


@dataclass
class Window(PlanNode):
    """Materialize window function results as __win{i} columns on the
    child batch (colexecwindow analogue; one lexsort + scans per spec,
    ops/window.py)."""
    child: PlanNode
    windows: list = field(default_factory=list)  # BoundWindow
    # leading rows ordered over a hash-strategy Aggregate, as
    # Sort.prefix (Engine._size_hash_sorts); 0 = all
    prefix: int = 0


@dataclass
class Sort(PlanNode):
    child: PlanNode
    keys: list[tuple[str, bool]] = field(default_factory=list)  # (col, desc)
    # leading slots of the child's batch that are sorted, 0 = all: set
    # by the engine over a hash-strategy Aggregate whose estimated
    # group count fits well inside them (Engine._size_hash_sorts)
    prefix: int = 0


@dataclass
class Limit(PlanNode):
    child: PlanNode
    limit: Optional[int] = None
    offset: int = 0


@dataclass
class OutputMeta:
    """Result schema: names + types (+ dictionaries for decode)."""
    names: list[str] = field(default_factory=list)
    types: list[SQLType] = field(default_factory=list)
    dictionaries: dict[str, object] = field(default_factory=dict)
    # set when the memoized join-order search ran (sql/memo.py):
    # EXPLAIN surfaces the exploration summary
    memo: object = None
    # normalization rule firings (sql/rules.RuleTrace) — EXPLAIN
    # renders them like the reference's opttester rule output
    rule_trace: object = None
    # alias -> access-path description chosen by the memo's scan
    # costing ("primary eq(l_orderkey) rows≈3" / "full rows≈6001215")
    access_paths: dict = field(default_factory=dict)
    # exec/compile.py JoinStats of the compiled plan, kept beside the
    # executable in the plan cache: what exec.join.* counts a dispatch
    join_stats: object = None
    # expression subqueries executed while the statement was bound,
    # whose results are constants of the plan (Binder.subqueries_run,
    # derived tables' bodies included)
    subqueries: int = 0
    # output name -> (lo, hi): an integer output column that is a
    # stored column (or a group key of one) and its stored range; what
    # an outer plan that reads it as a derived table's column groups
    # by densely (a UNION ALL's: the branches' ranges together)
    int_ranges: dict = field(default_factory=dict)


def grouping_key_order(sets: list, k: int):
    """The keys of a grouping-set Aggregate, most significant first in
    its packed sort code (Aggregate.sort_dims): those in more sets
    first (a plain key beside a ROLLUP is in every set), so that every
    set is a prefix of the order; None where no order makes every set
    a prefix (GROUPING SETS ((a), (b)))."""
    order = sorted(range(k), key=lambda j: (-sum(j in s for s in sets), j))
    for s in sets:
        if set(order[:len(s)]) != set(s):
            return None
    return order


def plan_tree_repr(node: PlanNode, indent: int = 0,
                   costs: dict | None = None,
                   actuals: dict | None = None,
                   sources: dict | None = None,
                   profile=None) -> str:
    """Render the plan tree; with ``costs`` (sql/stats.estimate output,
    id(node) -> (est_rows, est_cost)) each line gets the optimizer's
    cardinality/cost annotations, like EXPLAIN's estimated-row counts
    in the reference. EXPLAIN ANALYZE additionally passes ``actuals``
    (id(node) -> measured post-sel rows from the instrumented rerun)
    and ``sources`` (id(scan) -> "analyze"|"sketch"|"default", where
    the scan's cardinalities came from) so est-vs-actual drift — and
    which estimator produced the est — reads off each line. With
    ``profile`` (an exec/profile.ProfileSink from the same rerun) each
    operator additionally shows its measured device-seconds and moved
    bytes — the per-operator attribution the Theseus/Tailwind framing
    asks for."""
    pad = "  " * indent

    def ann() -> str:
        s = ""
        if costs is not None and id(node) in costs:
            rows, cost = costs[id(node)]
            src = ("" if sources is None or id(node) not in sources
                   else f" est={sources[id(node)]}")
            s += f"  (rows≈{rows:.0f} cost≈{cost:.0f}{src})"
        if actuals is not None and id(node) in actuals:
            s += f"  (actual rows={actuals[id(node)]})"
        if profile is not None:
            ent = profile.op_entry(node)
            if ent is not None:
                s += (f"  (device={ent.device_seconds * 1e3:.2f}ms"
                      + (f" bytes={ent.bytes_moved}"
                         if ent.bytes_moved else "") + ")")
        return s

    def child(n, extra_indent: int = 1) -> str:
        return plan_tree_repr(n, indent + extra_indent, costs,
                              actuals, sources, profile)

    if isinstance(node, Scan):
        f = f" filter={node.filter!r}" if node.filter is not None else ""
        return f"{pad}Scan {node.table} as {node.alias}{f}{ann()}\n"
    if isinstance(node, Derived):
        f = f" filter={node.filter!r}" if node.filter is not None else ""
        return f"{pad}Derived as {node.alias}{f}{ann()}\n" + child(node.child)
    if isinstance(node, Filter):
        return f"{pad}Filter {node.pred!r}{ann()}\n" + child(node.child)
    if isinstance(node, UnionAll):
        return (f"{pad}UnionAll {node.names}{ann()}\n"
                + child(node.left) + child(node.right))
    if isinstance(node, HashJoin):
        return (f"{pad}HashJoin[{node.join_type}] "
                f"{node.left_keys}={node.right_keys}{ann()}\n"
                + child(node.left) + child(node.right))
    if isinstance(node, Project):
        return (f"{pad}Project {[n for n, _ in node.items]}{ann()}\n"
                + child(node.child))
    if isinstance(node, Aggregate):
        sets = ("" if node.grouping_sets is None
                else f" sets={node.grouping_sets}")
        return (f"{pad}Aggregate groups={[n for n, _ in node.group_by]} "
                f"aggs={[a.func for a in node.aggs]}{sets}{ann()}\n"
                + child(node.child))
    if isinstance(node, Window):
        return (f"{pad}Window {[w.func for w in node.windows]}{ann()}\n"
                + child(node.child))
    if isinstance(node, Sort):
        return f"{pad}Sort {node.keys}{ann()}\n" + child(node.child)
    if isinstance(node, Limit):
        return (f"{pad}Limit {node.limit} offset {node.offset}{ann()}\n"
                + child(node.child))
    return f"{pad}{node!r}\n"


def prune_scan_columns(root: PlanNode) -> PlanNode:
    root, _ = _prune_impl(root)
    return root


def prune_scan_columns_traced(root: PlanNode):
    """prune_scan_columns, returning [(alias, n_dropped)] for the
    rule trace (sql/rules.py)."""
    _, dropped = _prune_impl(root)
    return dropped


def _prune_impl(root: PlanNode):
    """Projection pruning: shrink every Scan's column map to the batch
    columns the rest of the plan actually references. The engine
    uploads only these to HBM (the reference fetches only needed
    columns per index, colfetcher/cfetcher.go:668; here the win is
    device memory and PCIe, not just decode time).

    Conservative by name: a scan column survives if its batch name
    ("alias.col") appears in ANY expression/key list anywhere in the
    tree, so renames above Projects can never starve a real use.
    """
    from .bound import referenced_columns

    needed: set[str] = set()

    def collect(n: PlanNode):
        if isinstance(n, (Scan, Derived)):
            if n.filter is not None:
                needed.update(referenced_columns(n.filter))
            for _, e in n.computed:
                needed.update(referenced_columns(e))
        elif isinstance(n, Filter):
            needed.update(referenced_columns(n.pred))
        elif isinstance(n, HashJoin):
            needed.update(n.left_keys)
            needed.update(n.right_keys)
            needed.update(n.payload)
        elif isinstance(n, Project):
            for _, e in n.items:
                needed.update(referenced_columns(e))
        elif isinstance(n, Aggregate):
            for _, e in n.group_by:
                needed.update(referenced_columns(e))
            for a in n.aggs:
                if a.arg is not None:
                    needed.update(referenced_columns(a.arg))
            if n.having is not None:
                needed.update(referenced_columns(n.having))
            for _, e in n.items:
                needed.update(referenced_columns(e))
        elif isinstance(n, Window):
            for w in n.windows:
                if w.arg is not None:
                    needed.update(referenced_columns(w.arg))
                for p in w.partition_by:
                    needed.update(referenced_columns(p))
                for o, _ in w.order_by:
                    needed.update(referenced_columns(o))
        elif isinstance(n, Sort):
            needed.update(k[0] for k in n.keys)
        for attr in ("child", "left", "right"):
            c = getattr(n, attr, None)
            if c is not None:
                collect(c)

    collect(root)

    dropped: list[tuple[str, int]] = []

    def prune(n: PlanNode):
        if isinstance(n, Scan):
            kept = {bn: sn for bn, sn in n.columns.items()
                    if bn in needed}
            if not kept and n.columns:
                # count(*)-style plans touch no columns, but a batch
                # needs one to carry its shape
                bn = next(iter(n.columns))
                kept = {bn: n.columns[bn]}
            if len(kept) < len(n.columns):
                dropped.append((n.alias, len(n.columns) - len(kept)))
            n.columns = kept
        for attr in ("child", "left", "right"):
            c = getattr(n, attr, None)
            if c is not None:
                prune(c)

    prune(root)
    return root, dropped
