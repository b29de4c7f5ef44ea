"""AST nodes (the analogue of pkg/sql/sem/tree)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from .types import SQLType


class Expr:
    pass


@dataclass
class Literal(Expr):
    value: object  # python int/float/str/bool/None
    type_hint: Optional[SQLType] = None

    def __repr__(self):
        return f"Lit({self.value!r})"


@dataclass
class ColumnRef(Expr):
    name: str
    table: Optional[str] = None  # qualifier

    def __repr__(self):
        return f"Col({self.table + '.' if self.table else ''}{self.name})"


@dataclass
class BinOp(Expr):
    op: str  # + - * / % = != < <= > >= and or || like
    left: Expr
    right: Expr


@dataclass
class UnaryOp(Expr):
    op: str  # - not
    operand: Expr


@dataclass
class Between(Expr):
    expr: Expr
    lo: Expr
    hi: Expr
    negated: bool = False


@dataclass
class InList(Expr):
    expr: Expr
    items: list[Expr]
    negated: bool = False


@dataclass
class IsNull(Expr):
    expr: Expr
    negated: bool = False


@dataclass
class Case(Expr):
    whens: list[tuple[Expr, Expr]]
    else_: Optional[Expr] = None


@dataclass
class Cast(Expr):
    expr: Expr
    to: SQLType


@dataclass
class Subscript(Expr):
    """``arr[i]`` — 1-based array element access (pg semantics)."""
    expr: Expr
    index: Expr


@dataclass
class ArrayLit(Expr):
    """``ARRAY[e1, e2, ...]`` constructor."""
    items: list[Expr]


@dataclass
class FuncCall(Expr):
    name: str  # lowercased
    args: list[Expr]
    star: bool = False  # count(*)
    distinct: bool = False


@dataclass
class Extract(Expr):
    part: str  # year/month/day...
    expr: Expr


@dataclass
class WindowCall(Expr):
    """f(args) OVER (PARTITION BY ... ORDER BY ...)."""
    func: str
    args: list[Expr] = field(default_factory=list)
    star: bool = False
    partition_by: list[Expr] = field(default_factory=list)
    order_by: list["OrderItem"] = field(default_factory=list)


@dataclass
class Subquery(Expr):
    """Scalar subquery: (SELECT one column, at most one row). Executed
    before the main statement and inlined as a constant (the
    reference's planTop subquery execution, sql/subquery.go)."""
    select: "Select" = None


@dataclass
class Exists(Expr):
    """EXISTS (SELECT ...) — true iff the subquery returns any row."""
    select: "Select" = None


@dataclass
class InSubquery(Expr):
    """x IN (SELECT ...) — membership against a one-column subquery."""
    expr: Expr = None
    select: "Select" = None
    negated: bool = False


@dataclass
class Substring(Expr):
    expr: Expr
    start: Expr
    length: Optional[Expr] = None


# ---------------------------------------------------------------------------
# statements
# ---------------------------------------------------------------------------

class Statement:
    pass


@dataclass
class TableRef:
    name: str
    alias: Optional[str] = None
    # derived table: FROM (SELECT ...) alias — materialized before
    # planning like a single-use CTE; name is synthesized
    subquery: Optional["Select"] = None


@dataclass
class JoinClause:
    table: TableRef
    join_type: str  # inner/left/right/semi/anti/cross
    on: Optional[Expr] = None


@dataclass
class SelectItem:
    expr: Expr
    alias: Optional[str] = None
    star: bool = False


@dataclass
class OrderItem:
    expr: Expr
    desc: bool = False
    # None = pg default (NULLS LAST asc / NULLS FIRST desc)
    nulls_first: Optional[bool] = None


@dataclass
class Select(Statement):
    items: list[SelectItem] = field(default_factory=list)
    table: Optional[TableRef] = None
    joins: list[JoinClause] = field(default_factory=list)
    where: Optional[Expr] = None
    group_by: list[Expr] = field(default_factory=list)
    # GROUP BY ROLLUP / GROUPING SETS: each set as a tuple of indexes
    # into group_by (parser.parse_group_by); None for a plain GROUP BY
    grouping_sets: Optional[list] = None
    having: Optional[Expr] = None
    order_by: list[OrderItem] = field(default_factory=list)
    limit: Optional[int] = None
    offset: Optional[int] = None
    distinct: bool = False
    # WITH name [(col,...)] AS (SELECT ...) — non-recursive CTEs,
    # materialized in order before the main query
    ctes: list[tuple] = field(default_factory=list)  # (name, cols|None, Select)
    # AS OF SYSTEM TIME <expr>: historical read timestamp (CRDB's
    # time-travel queries; served by MVCC visibility at that ts)
    as_of: Optional[Expr] = None


@dataclass
class SetOp(Statement):
    """UNION / INTERSECT / EXCEPT [ALL]; ORDER BY/LIMIT hoisted from
    the last branch apply to the combined result (pg grammar)."""
    op: str  # union | intersect | except
    all: bool
    left: Statement  # Select or SetOp
    right: Statement
    order_by: list[OrderItem] = field(default_factory=list)
    limit: Optional[int] = None
    offset: Optional[int] = None
    ctes: list[tuple] = field(default_factory=list)  # WITH over a set op


@dataclass
class ColumnDef:
    name: str
    type: SQLType
    nullable: bool = True
    primary: bool = False
    unique: bool = False  # column UNIQUE -> auto unique index
    default: object = None  # DEFAULT expr (unbound AST)


@dataclass
class CreateTable(Statement):
    name: str
    columns: list[ColumnDef]
    primary_key: list[str]
    if_not_exists: bool = False
    # CHECK constraints: (name, bound-later Expr, source sql text)
    checks: list = field(default_factory=list)
    # FOREIGN KEYs (RESTRICT semantics):
    # (name, [cols], ref_table, [ref_cols])
    foreign_keys: list = field(default_factory=list)
    # table-level UNIQUE (cols) -> auto unique index
    uniques: list = field(default_factory=list)


@dataclass
class DropTable(Statement):
    name: str
    if_exists: bool = False


@dataclass
class CreateIndex(Statement):
    """CREATE [UNIQUE] INDEX <name> ON <table> (cols...). Unique
    indexes write KV entries at /Table/<tid>/<index_id>/<vals> so
    concurrent violations conflict in the KV plane, like the
    reference's index rows (pkg/sql/rowenc/index_encoding.go)."""
    name: str
    table: str
    columns: list[str] = field(default_factory=list)
    unique: bool = False
    if_not_exists: bool = False


@dataclass
class DropIndex(Statement):
    name: str
    if_exists: bool = False


@dataclass
class ShowIndexes(Statement):
    """SHOW INDEXES FROM <table>."""
    table: str


@dataclass
class ShowColumns(Statement):
    """SHOW COLUMNS FROM <table>."""
    table: str


@dataclass
class CreateView(Statement):
    """CREATE VIEW <name> [(cols)] AS <select>. The view body is
    stored as SQL text in the descriptor and re-planned (expanded as a
    derived table) at each use, like the reference's view descriptors
    (pkg/sql/create_view.go)."""
    name: str
    columns: Optional[list] = None
    select: Optional["Statement"] = None  # parsed body (validation)
    sql: str = ""                          # body text (persisted)
    if_not_exists: bool = False


@dataclass
class DropView(Statement):
    name: str
    if_exists: bool = False


@dataclass
class CreateSequence(Statement):
    name: str
    start: int = 1
    increment: int = 1
    if_not_exists: bool = False


@dataclass
class DropSequence(Statement):
    name: str
    if_exists: bool = False


@dataclass
class ShowSequences(Statement):
    pass


@dataclass
class Truncate(Statement):
    """TRUNCATE [TABLE] <t>: clear all rows + index entries, keep the
    schema (pkg/sql/truncate.go swaps in fresh empty indexes)."""
    table: str


@dataclass
class AlterTable(Statement):
    """ALTER TABLE <t> ADD COLUMN <def> [DEFAULT lit] | DROP COLUMN <c>.
    Executed as an online schema change (jobs/schemachange.py)."""
    table: str
    add: Optional[ColumnDef] = None
    default: Optional[Expr] = None
    drop: Optional[str] = None


@dataclass
class ConfigureZone(Statement):
    """ALTER TABLE <t> CONFIGURE ZONE USING k = v, ... — per-table
    config overrides (gc.ttl_seconds, range_max_bytes), the spanconfig
    analogue."""
    table: str
    options: dict = field(default_factory=dict)


@dataclass
class ShowZone(Statement):
    """SHOW ZONE CONFIGURATION FOR TABLE <t>."""
    table: str


@dataclass
class Insert(Statement):
    table: str
    columns: list[str]  # empty = all
    rows: list[list[Expr]] = field(default_factory=list)
    select: Optional[Select] = None
    # UPSERT: a duplicate primary key replaces the row instead of
    # erroring (CRDB's UPSERT whole-row semantics)
    upsert: bool = False


@dataclass
class Update(Statement):
    table: str
    assignments: list[tuple[str, Expr]] = field(default_factory=list)
    where: Optional[Expr] = None


@dataclass
class Delete(Statement):
    table: str
    where: Optional[Expr] = None


@dataclass
class SetVar(Statement):
    name: str
    value: object
    cluster: bool = False  # SET CLUSTER SETTING


@dataclass
class ShowVar(Statement):
    name: str


@dataclass
class ShowTables(Statement):
    pass


@dataclass
class CreateChangefeed(Statement):
    """CREATE CHANGEFEED FOR <table> INTO '<sink-uri>'."""
    table: str
    sink: str


@dataclass
class ShowJobs(Statement):
    pass


@dataclass
class ShowStatements(Statement):
    """SHOW STATEMENTS: per-fingerprint execution stats (sqlstats)."""
    pass


@dataclass
class ShowTrace(Statement):
    """SHOW TRACE FOR SESSION: spans recorded since SET tracing=on."""
    pass


@dataclass
class ShowAll(Statement):
    """SHOW ALL: every session variable and its current value."""
    pass


@dataclass
class ShowCreateTable(Statement):
    """SHOW CREATE TABLE <t>: reconstructed DDL from the descriptor."""
    table: str


@dataclass
class CancelJob(Statement):
    job_id: int


@dataclass
class Backup(Statement):
    """BACKUP TABLE a, b INTO '<dir>' (incremental when the directory
    already holds a backup)."""
    tables: list[str]
    dest: str


@dataclass
class Restore(Statement):
    """RESTORE TABLE a, b FROM '<dir>' (empty tables = all)."""
    tables: list[str]
    src: str


@dataclass
class Explain(Statement):
    stmt: Statement
    analyze: bool = False
    # EXPLAIN ANALYZE (DEBUG): capture a statement diagnostics bundle
    # (plan + operator profile + trace + settings) inline, the
    # reference's stmtdiagnostics bundle path
    debug: bool = False


@dataclass
class Analyze(Statement):
    """ANALYZE <table> — collect table statistics (pkg/sql/stats)."""
    table: str


@dataclass
class BeginTxn(Statement):
    pass


@dataclass
class CommitTxn(Statement):
    pass


@dataclass
class RollbackTxn(Statement):
    pass
