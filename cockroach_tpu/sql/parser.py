"""Pratt-style recursive-descent SQL parser.

Grammar coverage tracks what the execution engine supports (the TPC-H /
SSB / YCSB benchmark surface plus DDL/DML): SELECT with joins, GROUP
BY/HAVING, ORDER BY/LIMIT, CASE, CAST, BETWEEN, IN, LIKE, EXTRACT,
SUBSTRING, date/interval literals; CREATE/DROP TABLE; INSERT/UPDATE/
DELETE; SET/SHOW; EXPLAIN [ANALYZE]; BEGIN/COMMIT/ROLLBACK.

The reference's grammar is goyacc-generated from a 5MB sql.y
(pkg/sql/parser/BUILD.bazel:86-99); precedence below mirrors standard
PostgreSQL precedence.
"""

from __future__ import annotations

import itertools

from . import ast
from .lexer import Tok, Token, lex
from .types import (BOOL, DATE, FLOAT4, FLOAT8, INT2, INT4, INT8, INTERVAL,
                    STRING, TIMESTAMP, SQLType)


class ParseError(Exception):
    pass


# binding powers for binary operators
PRECEDENCE = {
    "or": 10,
    "and": 20,
    # NOT handled as prefix with bp 25
    "=": 40, "!=": 40, "<>": 40, "<": 40, "<=": 40, ">": 40, ">=": 40,
    "like": 40, "ilike": 40,
    "@>": 42, "<@": 42, "?": 42,   # json/array containment + key-exists
    "||": 45,
    "->": 65, "->>": 65,           # json access binds tighter than math
    "+": 50, "-": 50,
    "*": 60, "/": 60, "%": 60,
    "^": 70,  # below unary +/- (pg: -2 ^ 2 = (-2)^2 = 4)
    "::": 80,
}

TYPE_NAMES = {
    "int": INT8, "int2": INT2, "int4": INT4, "int8": INT8, "bigint": INT8,
    "smallint": INT2, "integer": INT4, "bool": BOOL, "boolean": BOOL,
    "float": FLOAT8, "float4": FLOAT4, "float8": FLOAT8, "real": FLOAT4,
    "double": FLOAT8, "date": DATE, "timestamp": TIMESTAMP,
    "timestamptz": TIMESTAMP, "interval": INTERVAL, "string": STRING,
    "text": STRING, "varchar": STRING, "char": STRING,
    "jsonb": SQLType.json(), "json": SQLType.json(),
}


class Parser:
    def __init__(self, sql: str):
        self.sql = sql  # kept for view-body text capture
        self.toks = lex(sql)
        self.i = 0

    # -- token helpers -----------------------------------------------------
    def peek(self, ahead: int = 0) -> Token:
        return self.toks[min(self.i + ahead, len(self.toks) - 1)]

    def next(self) -> Token:
        t = self.toks[self.i]
        if t.kind != Tok.EOF:
            self.i += 1
        return t

    def accept_kw(self, *kws: str) -> bool:
        if self.peek().is_kw(*kws):
            self.next()
            return True
        return False

    def expect_kw(self, kw: str) -> None:
        if not self.accept_kw(kw):
            raise ParseError(f"expected {kw.upper()}, got {self.peek()}")

    def accept_op(self, op: str) -> bool:
        t = self.peek()
        if t.kind == Tok.OP and t.text == op:
            self.next()
            return True
        return False

    def expect_op(self, op: str) -> None:
        if not self.accept_op(op):
            raise ParseError(f"expected {op!r}, got {self.peek()}")

    def expect_ident(self) -> str:
        t = self.next()
        if t.kind not in (Tok.IDENT, Tok.KEYWORD):
            raise ParseError(f"expected identifier, got {t}")
        return t.text

    def dotted_name(self) -> str:
        """a.b.c — setting/variable names."""
        parts = [self.expect_ident()]
        while self.accept_op("."):
            parts.append(self.expect_ident())
        return ".".join(parts)

    # -- entry -------------------------------------------------------------
    def parse_statement(self) -> ast.Statement:
        t = self.peek()
        if t.is_kw("select"):
            return self.parse_select_stmt()
        if t.is_kw("with"):
            return self.parse_with()
        if t.is_kw("create"):
            return self.parse_create()
        if t.is_kw("drop"):
            return self.parse_drop()
        if t.is_kw("alter"):
            return self.parse_alter()
        if t.is_kw("insert"):
            return self.parse_insert()
        if t.is_kw("upsert"):
            return self.parse_insert(upsert=True)
        if t.is_kw("update"):
            return self.parse_update()
        if t.is_kw("delete"):
            return self.parse_delete()
        if t.is_kw("set"):
            return self.parse_set()
        if t.is_kw("show"):
            self.next()
            if self.peek().kind in (Tok.IDENT, Tok.KEYWORD) \
                    and self.peek().text == "tables":
                self.next()
                return ast.ShowTables()
            if self.peek().kind in (Tok.IDENT, Tok.KEYWORD) \
                    and self.peek().text == "jobs":
                self.next()
                return ast.ShowJobs()
            if self.peek().kind in (Tok.IDENT, Tok.KEYWORD) \
                    and self.peek().text == "statements":
                self.next()
                return ast.ShowStatements()
            if self.peek().kind in (Tok.IDENT, Tok.KEYWORD) \
                    and self.peek().text == "indexes":
                self.next()
                self.expect_kw("from")
                return ast.ShowIndexes(self.expect_ident())
            if self.peek().kind in (Tok.IDENT, Tok.KEYWORD) \
                    and self.peek().text == "columns":
                self.next()
                self.expect_kw("from")
                return ast.ShowColumns(self.expect_ident())
            if self.peek().kind == Tok.IDENT \
                    and self.peek().text == "sequences":
                self.next()
                return ast.ShowSequences()
            if self.peek().is_kw("create"):
                self.next()
                self.expect_kw("table")
                return ast.ShowCreateTable(self.expect_ident())
            if self.peek().kind in (Tok.IDENT, Tok.KEYWORD) \
                    and self.peek().text == "zone":
                self.next()
                for word in ("configuration", "for"):
                    if not (self.peek().kind in (Tok.IDENT, Tok.KEYWORD)
                            and self.peek().text == word):
                        raise ParseError(
                            "expected ZONE CONFIGURATION FOR TABLE")
                    self.next()
                self.expect_kw("table")
                return ast.ShowZone(self.expect_ident())
            if self.peek().kind in (Tok.IDENT, Tok.KEYWORD) \
                    and self.peek().text == "trace":
                self.next()
                self.expect_kw("for")
                if not (self.peek().kind in (Tok.IDENT, Tok.KEYWORD)
                        and self.peek().text == "session"):
                    raise ParseError("expected SESSION after TRACE FOR")
                self.next()
                return ast.ShowTrace()
            if self.accept_kw("all"):
                return ast.ShowAll()
            self.accept_kw("cluster")
            self.accept_kw("setting")
            return ast.ShowVar(self.dotted_name())
        if t.is_kw("explain"):
            self.next()
            analyze = self.accept_kw("analyze")
            debug = False
            if analyze and self.accept_op("("):
                # EXPLAIN ANALYZE (DEBUG): the reference's option list
                # (sql.y explain_option_list); DEBUG — produce a
                # statement diagnostics bundle — is the only option
                # understood here
                while True:
                    o = self.next()
                    if o.kind not in (Tok.IDENT, Tok.KEYWORD) \
                            or o.text.lower() != "debug":
                        raise ParseError(
                            f"unsupported EXPLAIN ANALYZE option "
                            f"{o.text!r} (only DEBUG)")
                    debug = True
                    if not self.accept_op(","):
                        break
                if not self.accept_op(")"):
                    raise ParseError(
                        "expected ) closing EXPLAIN ANALYZE options")
            return ast.Explain(self.parse_statement(), analyze=analyze,
                               debug=debug)
        if t.is_kw("analyze"):
            self.next()
            return ast.Analyze(self.expect_ident())
        if t.kind == Tok.IDENT and t.text == "truncate":
            self.next()
            self.accept_kw("table")
            return ast.Truncate(self.expect_ident())
        if t.kind in (Tok.IDENT, Tok.KEYWORD) and t.text == "cancel":
            self.next()
            if not (self.peek().kind in (Tok.IDENT, Tok.KEYWORD)
                    and self.peek().text == "job"):
                raise ParseError("expected JOB after CANCEL")
            self.next()
            n = self.next()
            if n.kind != Tok.NUMBER:
                raise ParseError("expected job id")
            return ast.CancelJob(int(n.text))
        if t.is_kw("backup"):
            self.next()
            self.expect_kw("table")
            tables = [self.expect_ident()]
            while self.accept_op(","):
                tables.append(self.expect_ident())
            self.expect_kw("into")
            s = self.next()
            if s.kind != Tok.STRING:
                raise ParseError("expected destination string")
            return ast.Backup(tables, s.text)
        if t.is_kw("restore"):
            self.next()
            tables = []
            if self.accept_kw("table"):
                tables.append(self.expect_ident())
                while self.accept_op(","):
                    tables.append(self.expect_ident())
            self.expect_kw("from")
            s = self.next()
            if s.kind != Tok.STRING:
                raise ParseError("expected source string")
            return ast.Restore(tables, s.text)
        if t.is_kw("begin"):
            self.next()
            self.accept_kw("transaction")
            return ast.BeginTxn()
        if t.is_kw("commit"):
            self.next()
            return ast.CommitTxn()
        if t.is_kw("rollback"):
            self.next()
            return ast.RollbackTxn()
        raise ParseError(f"unexpected {t}")

    def finish(self) -> None:
        self.accept_op(";")
        if self.peek().kind != Tok.EOF:
            raise ParseError(f"trailing tokens at {self.peek()}")

    # -- SELECT ------------------------------------------------------------
    def parse_select_stmt(self) -> ast.Statement:
        """A select possibly chained with UNION/INTERSECT/EXCEPT
        (left-associative); ORDER BY/LIMIT parsed into the last branch
        hoist to the set op, matching pg's grammar."""
        node: ast.Statement = self.parse_select()
        while self.peek().is_kw("union", "intersect", "except"):
            op = self.next().text
            all_ = self.accept_kw("all")
            if self.accept_kw("distinct"):
                all_ = False
            right = self.parse_select()
            node = ast.SetOp(op, all_, node, right)
        if isinstance(node, ast.SetOp):
            last = node.right
            if isinstance(last, ast.Select) and (
                    last.order_by or last.limit is not None
                    or last.offset is not None):
                node.order_by = last.order_by
                node.limit, node.offset = last.limit, last.offset
                last.order_by = []
                last.limit = last.offset = None
        return node

    def parse_with(self) -> ast.Select:
        """WITH name [(cols)] AS (select) [, ...] SELECT ... — the CTEs
        attach to the main Select (non-recursive; RECURSIVE rejected)."""
        self.expect_kw("with")
        if self.accept_kw("recursive"):
            raise ParseError("WITH RECURSIVE not supported")
        ctes = []
        while True:
            name = self.expect_ident()
            cols = None
            if self.accept_op("("):
                cols = [self.expect_ident()]
                while self.accept_op(","):
                    cols.append(self.expect_ident())
                self.expect_op(")")
            self.expect_kw("as")
            self.expect_op("(")
            sub = self.parse_with() if self.peek().is_kw("with") \
                else self.parse_select_stmt()
            self.expect_op(")")
            ctes.append((name, cols, sub))
            if not self.accept_op(","):
                break
        sel = self.parse_select_stmt()
        sel.ctes = ctes + sel.ctes
        return sel

    def parse_select(self) -> ast.Select:
        self.expect_kw("select")
        sel = ast.Select()
        sel.distinct = self.accept_kw("distinct")
        while True:
            if self.accept_op("*"):
                sel.items.append(ast.SelectItem(expr=None, star=True))
            else:
                e = self.parse_expr()
                alias = None
                if self.accept_kw("as"):
                    alias = self.expect_ident()
                elif self.peek().kind == Tok.IDENT:
                    alias = self.next().text
                sel.items.append(ast.SelectItem(expr=e, alias=alias))
            if not self.accept_op(","):
                break
        if self.accept_kw("from"):
            sel.table = self.parse_table_ref()
            while True:
                jt = self.parse_join_type()
                if jt is None:
                    break
                tbl = self.parse_table_ref()
                on = None
                if jt != "cross":
                    self.expect_kw("on")
                    on = self.parse_expr()
                sel.joins.append(ast.JoinClause(tbl, jt, on))
            if self.peek().is_kw("as") and \
                    self.peek(1).kind == Tok.IDENT \
                    and self.peek(1).text == "of":
                # AS OF SYSTEM TIME <expr> (historical read)
                self.next()
                self.next()
                for word in ("system", "time"):
                    t = self.next()
                    if not (t.kind == Tok.IDENT and t.text == word):
                        raise ParseError("expected SYSTEM TIME after "
                                         "AS OF")
                sel.as_of = self.parse_expr()
        if self.accept_kw("where"):
            sel.where = self.parse_expr()
        if self.accept_kw("group"):
            self.expect_kw("by")
            self.parse_group_by(sel)
        if self.accept_kw("having"):
            sel.having = self.parse_expr()
        if self.accept_kw("order"):
            self.expect_kw("by")
            while True:
                e = self.parse_expr()
                desc = False
                if self.accept_kw("desc"):
                    desc = True
                else:
                    self.accept_kw("asc")
                nulls_first = None
                if self.accept_kw("nulls"):
                    if self.accept_kw("first"):
                        nulls_first = True
                    elif self.accept_kw("last"):
                        nulls_first = False
                    else:
                        raise ParseError("expected FIRST or LAST")
                sel.order_by.append(ast.OrderItem(e, desc, nulls_first))
                if not self.accept_op(","):
                    break
        if self.accept_kw("limit"):
            sel.limit = int(self.next().text)
        if self.accept_kw("offset"):
            sel.offset = int(self.next().text)
        return sel

    def _word(self, ahead: int = 0) -> str | None:
        t = self.peek(ahead)
        return t.text.lower() if t.kind == Tok.IDENT else None

    def _paren_exprs(self) -> list:
        """( [e, ...] ): a grouping set or a ROLLUP's keys."""
        self.expect_op("(")
        out: list = []
        if self.accept_op(")"):
            return out
        out.append(self.parse_expr())
        while self.accept_op(","):
            out.append(self.parse_expr())
        self.expect_op(")")
        return out

    def parse_group_by(self, sel: ast.Select) -> None:
        """GROUP BY e, ..., where an element may be ROLLUP (e, ...) or
        GROUPING SETS (set, ...). `sel.group_by` holds every distinct
        key in the order it first appears; where any element is not a
        plain key, `sel.grouping_sets` holds the sets, as tuples of
        indexes into it, the cross product of the elements' sets (pg's
        reading: ROLLUP (a, b) is the sets (a, b), (a), ())."""
        elements: list = []
        plain = True
        while True:
            word = self._word()
            opens = self.peek(1).kind == Tok.OP and self.peek(1).text == "("
            if word == "cube" and opens:
                raise ParseError("GROUP BY CUBE is not supported; write "
                                 "its sets out with GROUPING SETS")
            if word == "rollup" and opens:
                self.next()
                keys = self._paren_exprs()
                elements.append([keys[:j] for j in range(len(keys), -1, -1)])
                plain = False
            elif word == "grouping" and self._word(1) == "sets":
                self.next()
                self.next()
                self.expect_op("(")
                sets = []
                while True:
                    if self.peek().kind == Tok.OP and self.peek().text == "(":
                        sets.append(self._paren_exprs())
                    else:
                        sets.append([self.parse_expr()])
                    if not self.accept_op(","):
                        break
                self.expect_op(")")
                elements.append(sets)
                plain = False
            else:
                elements.append([[self.parse_expr()]])
            if not self.accept_op(","):
                break
        index: dict = {}
        for sets in elements:
            for keys in sets:
                for k in keys:
                    if repr(k) not in index:
                        index[repr(k)] = len(sel.group_by)
                        sel.group_by.append(k)
        if plain:
            return
        out = []
        for combo in itertools.product(*elements):
            idx = sorted({index[repr(k)] for keys in combo for k in keys})
            out.append(tuple(idx))
        sel.grouping_sets = out

    def parse_table_ref(self) -> ast.TableRef:
        if self.peek().kind == Tok.OP and self.peek().text == "(":
            # derived table: FROM (SELECT ...) [AS] alias
            self.next()
            sub = self.parse_with() if self.peek().is_kw("with") \
                else self.parse_select_stmt()
            self.expect_op(")")
            self.accept_kw("as")
            alias = self.expect_ident()
            return ast.TableRef(alias, alias, subquery=sub)
        name = self.expect_ident()
        if self.peek().kind == Tok.OP and self.peek().text == "(":
            # set-returning function in FROM position:
            #   FROM generate_series(a, b) [AS] g[(col)]
            # desugars to the supported derived-table shape
            #   (SELECT fn(...) AS col) AS g
            self.next()
            args = []
            if not (self.peek().kind == Tok.OP
                    and self.peek().text == ")"):
                args.append(self.parse_expr(0))
                while self.accept_op(","):
                    args.append(self.parse_expr(0))
            self.expect_op(")")
            self.accept_kw("as")
            alias = name
            if self.peek().kind == Tok.IDENT:
                alias = self.next().text
            col = alias
            if self.peek().kind == Tok.OP and self.peek().text == "(":
                self.next()
                col = self.expect_ident()
                self.expect_op(")")
            sub = ast.Select(
                items=[ast.SelectItem(
                    ast.FuncCall(name, args), alias=col)],
                table=None)
            return ast.TableRef(alias, alias, subquery=sub)
        alias = None
        if self.peek().is_kw("as") and not (
                self.peek(1).kind == Tok.IDENT
                and self.peek(1).text == "of"):
            self.next()
            alias = self.expect_ident()
        elif self.peek().kind == Tok.IDENT \
                and self.peek().text != "of":
            alias = self.next().text
        return ast.TableRef(name, alias)

    def parse_join_type(self):
        t = self.peek()
        if t.is_kw("join"):
            self.next()
            return "inner"
        if t.is_kw("inner"):
            self.next()
            self.expect_kw("join")
            return "inner"
        if t.is_kw("left"):
            self.next()
            self.accept_kw("outer")
            self.expect_kw("join")
            return "left"
        if t.is_kw("cross"):
            self.next()
            self.expect_kw("join")
            return "cross"
        if t.is_kw("right"):
            self.next()
            self.accept_kw("outer")
            self.expect_kw("join")
            return "right"
        if t.is_kw("full"):
            raise ParseError("FULL JOIN not supported yet")
        if t.kind == Tok.OP and t.text == ",":
            nxt = self.peek(1)
            # comma-join only when followed by a table name (not a
            # subquery); keyword-named tables ("date" in SSB) allowed
            if nxt.kind in (Tok.IDENT, Tok.KEYWORD):
                self.next()
                return "cross"
        return None

    # -- expressions -------------------------------------------------------
    def parse_expr(self, min_bp: int = 0) -> ast.Expr:
        left = self.parse_prefix()
        while True:
            t = self.peek()
            # postfix-ish constructs
            if t.is_kw("not") and self.peek(1).is_kw("between", "in", "like", "ilike"):
                if 35 < min_bp:
                    break
                self.next()
                left = self.parse_not_suffix(left, negated=True)
                continue
            if t.is_kw("between", "in"):
                if 35 < min_bp:
                    break
                left = self.parse_not_suffix(left, negated=False)
                continue
            if t.is_kw("is"):
                if 35 < min_bp:
                    break
                self.next()
                neg = self.accept_kw("not")
                if self.accept_kw("null"):
                    left = ast.IsNull(left, negated=neg)
                elif self.accept_kw("true"):
                    # IS TRUE never returns NULL: (x IS NOT NULL) AND x
                    cmp = ast.BinOp("and", ast.IsNull(left, negated=True),
                                    left)
                    left = ast.UnaryOp("not", cmp) if neg else cmp
                elif self.accept_kw("false"):
                    cmp = ast.BinOp("and", ast.IsNull(left, negated=True),
                                    ast.UnaryOp("not", left))
                    left = ast.UnaryOp("not", cmp) if neg else cmp
                elif self.accept_kw("distinct"):
                    # IS [NOT] DISTINCT FROM: null-safe comparison,
                    # desugared to a three-valued-logic-exact form that
                    # never yields NULL:
                    #   NOT DISTINCT = (a NULL AND b NULL)
                    #               OR (a NOT NULL AND b NOT NULL
                    #                   AND a = b)
                    if not self.accept_kw("from"):
                        raise ParseError(
                            f"expected FROM after IS DISTINCT at "
                            f"{self.peek()}")
                    rhs = self.parse_expr(36)
                    both_null = ast.BinOp(
                        "and", ast.IsNull(left),
                        ast.IsNull(rhs))
                    both_set_eq = ast.BinOp(
                        "and",
                        ast.BinOp("and",
                                  ast.IsNull(left, negated=True),
                                  ast.IsNull(rhs, negated=True)),
                        ast.BinOp("=", left, rhs))
                    not_distinct = ast.BinOp("or", both_null,
                                             both_set_eq)
                    # note the polarity: IS DISTINCT (neg=False)
                    # negates NOT-DISTINCT
                    left = not_distinct if neg \
                        else ast.UnaryOp("not", not_distinct)
                else:
                    raise ParseError(f"expected NULL/TRUE/FALSE/"
                                     f"DISTINCT FROM after IS at "
                                     f"{self.peek()}")
                continue
            if t.kind == Tok.OP and t.text == "[":
                # subscript binds tightest of the postfix operators
                if 85 < min_bp:
                    break
                self.next()
                idx = self.parse_expr()
                self.expect_op("]")
                left = ast.Subscript(left, idx)
                continue
            op = None
            if t.kind == Tok.OP and t.text in PRECEDENCE:
                op = t.text
            elif t.is_kw("and", "or", "like", "ilike"):
                op = t.text
            if op is None:
                break
            bp = PRECEDENCE[op]
            if bp < min_bp:
                break
            self.next()
            if op == "::":
                left = ast.Cast(left, self.parse_type())
                continue
            right = self.parse_expr(bp + 1)
            left = ast.BinOp(op, left, right)
        return left

    def parse_not_suffix(self, left: ast.Expr, negated: bool) -> ast.Expr:
        if self.accept_kw("between"):
            lo = self.parse_expr(41)
            self.expect_kw("and")
            hi = self.parse_expr(41)
            return ast.Between(left, lo, hi, negated=negated)
        if self.accept_kw("in"):
            self.expect_op("(")
            if self.peek().is_kw("select", "with"):
                sub = self.parse_with() if self.peek().is_kw("with") \
                    else self.parse_select_stmt()
                self.expect_op(")")
                return ast.InSubquery(left, sub, negated=negated)
            items = [self.parse_expr()]
            while self.accept_op(","):
                items.append(self.parse_expr())
            self.expect_op(")")
            return ast.InList(left, items, negated=negated)
        if self.accept_kw("like") or self.accept_kw("ilike"):
            right = self.parse_expr(41)
            e = ast.BinOp("like", left, right)
            return ast.UnaryOp("not", e) if negated else e
        raise ParseError(f"unexpected {self.peek()}")

    def parse_prefix(self) -> ast.Expr:
        t = self.next()
        if t.kind == Tok.NUMBER:
            txt = t.text
            if "." in txt or "e" in txt or "E" in txt:
                # decimal literal: keep string for scale-aware binding
                return ast.Literal(txt, None)
            return ast.Literal(int(txt), None)
        if t.kind == Tok.STRING:
            return ast.Literal(t.text, None)
        if t.is_kw("true"):
            return ast.Literal(True, BOOL)
        if t.is_kw("false"):
            return ast.Literal(False, BOOL)
        if t.is_kw("null"):
            return ast.Literal(None, None)
        if t.is_kw("date"):
            if self.peek().kind == Tok.STRING:
                return ast.Literal(self.next().text, DATE)
            return ast.ColumnRef("date")
        if t.is_kw("timestamp"):
            if self.peek().kind == Tok.STRING:
                return ast.Literal(self.next().text, TIMESTAMP)
            return ast.ColumnRef("timestamp")
        if t.is_kw("interval"):
            if self.peek().kind == Tok.STRING:
                return ast.Literal(self.next().text, INTERVAL)
            return ast.ColumnRef("interval")
        if t.is_kw("not"):
            return ast.UnaryOp("not", self.parse_expr(25))
        if t.kind == Tok.OP and t.text == "-":
            # pg precedence: unary minus binds TIGHTER than ^
            # (-2 ^ 2 is (-2)^2 = 4), so the operand stops before ^
            return ast.UnaryOp("-", self.parse_expr(75))
        if t.kind == Tok.OP and t.text == "+":
            return self.parse_expr(75)
        if t.kind == Tok.OP and t.text == "(":
            if self.peek().is_kw("select", "with"):
                sub = self.parse_with() if self.peek().is_kw("with") \
                    else self.parse_select_stmt()
                self.expect_op(")")
                return ast.Subquery(sub)
            e = self.parse_expr()
            self.expect_op(")")
            return e
        if t.is_kw("exists"):
            self.expect_op("(")
            sub = self.parse_with() if self.peek().is_kw("with") \
                else self.parse_select_stmt()
            self.expect_op(")")
            return ast.Exists(sub)
        if t.is_kw("case"):
            whens = []
            operand = None
            if not self.peek().is_kw("when"):
                operand = self.parse_expr()
            while self.accept_kw("when"):
                cond = self.parse_expr()
                if operand is not None:
                    cond = ast.BinOp("=", operand, cond)
                self.expect_kw("then")
                val = self.parse_expr()
                whens.append((cond, val))
            else_ = None
            if self.accept_kw("else"):
                else_ = self.parse_expr()
            self.expect_kw("end")
            return ast.Case(whens, else_)
        if t.is_kw("cast"):
            self.expect_op("(")
            e = self.parse_expr()
            self.expect_kw("as")
            ty = self.parse_type()
            self.expect_op(")")
            return ast.Cast(e, ty)
        if t.is_kw("coalesce"):
            self.expect_op("(")
            args = [self.parse_expr()]
            while self.accept_op(","):
                args.append(self.parse_expr())
            self.expect_op(")")
            return ast.FuncCall("coalesce", args)
        if t.is_kw("extract"):
            self.expect_op("(")
            if self.peek().kind == Tok.STRING:
                part = self.next().text  # extract('year' from x)
            else:
                part = self.expect_ident()
            self.expect_kw("from")
            e = self.parse_expr()
            self.expect_op(")")
            return ast.Extract(part, e)
        if t.kind in (Tok.IDENT, Tok.KEYWORD) and t.text == "position" \
                and self.peek().kind == Tok.OP \
                and self.peek().text == "(":
            # position(needle IN haystack) -> strpos(haystack, needle);
            # the comma form position(haystack, needle) stays a plain call
            self.expect_op("(")
            first = self.parse_expr(min_bp=36)  # stop before IN (bp 35)
            if self.accept_kw("in"):
                hay = self.parse_expr()
                self.expect_op(")")
                return ast.FuncCall("strpos", [hay, first])
            args = [first]
            while self.accept_op(","):
                args.append(self.parse_expr())
            self.expect_op(")")
            return ast.FuncCall("position", args)
        if t.is_kw("substring"):
            self.expect_op("(")
            e = self.parse_expr()
            if self.accept_op(","):
                # pg's comma form: substring(s, start [, length])
                start = self.parse_expr()
                length = None
                if self.accept_op(","):
                    length = self.parse_expr()
                self.expect_op(")")
                return ast.Substring(e, start, length)
            self.expect_kw("from")
            start = self.parse_expr()
            length = None
            if self.accept_kw("for"):
                length = self.parse_expr()
            elif self.accept_op(","):
                start2 = start
                length = self.parse_expr()
                start = start2
            self.expect_op(")")
            return ast.Substring(e, start, length)
        if t.kind in (Tok.IDENT, Tok.KEYWORD):
            name = t.text
            if name.lower() == "array" and self.peek().kind == Tok.OP \
                    and self.peek().text == "[":
                self.next()
                items = []
                if not (self.peek().kind == Tok.OP
                        and self.peek().text == "]"):
                    items.append(self.parse_expr())
                    while self.accept_op(","):
                        items.append(self.parse_expr())
                self.expect_op("]")
                return ast.ArrayLit(items)
            # parenless special-syntax functions (SQL standard)
            if name in ("current_date", "current_timestamp") and not (
                    self.peek().kind == Tok.OP and self.peek().text == "("):
                return ast.FuncCall(name, [])
            # function call?
            if self.peek().kind == Tok.OP and self.peek().text == "(":
                self.next()
                if self.accept_op("*"):
                    self.expect_op(")")
                    fc = ast.FuncCall(name, [], star=True)
                    if self.peek().is_kw("over"):
                        return self.parse_over(fc)
                    return fc
                distinct = self.accept_kw("distinct")
                args = []
                if not self.accept_op(")"):
                    args.append(self.parse_expr())
                    while self.accept_op(","):
                        args.append(self.parse_expr())
                    self.expect_op(")")
                fc = ast.FuncCall(name, args, distinct=distinct)
                if self.peek().is_kw("over"):
                    return self.parse_over(fc)
                return fc
            # qualified column a.b
            if self.peek().kind == Tok.OP and self.peek().text == ".":
                self.next()
                col = self.expect_ident()
                return ast.ColumnRef(col, table=name)
            return ast.ColumnRef(name)
        raise ParseError(f"unexpected token {t}")

    def parse_over(self, fc: ast.FuncCall) -> ast.WindowCall:
        """OVER ( [PARTITION BY e,...] [ORDER BY e [ASC|DESC],...] )."""
        self.expect_kw("over")
        self.expect_op("(")
        parts: list[ast.Expr] = []
        orders: list[ast.OrderItem] = []
        if self.accept_kw("partition"):
            self.expect_kw("by")
            parts.append(self.parse_expr())
            while self.accept_op(","):
                parts.append(self.parse_expr())
        if self.accept_kw("order"):
            self.expect_kw("by")
            while True:
                e = self.parse_expr()
                desc = False
                if self.accept_kw("desc"):
                    desc = True
                else:
                    self.accept_kw("asc")
                orders.append(ast.OrderItem(e, desc))
                if not self.accept_op(","):
                    break
        if self.peek().is_kw("rows", "range", "groups"):
            raise ParseError("explicit window frames not supported")
        self.expect_op(")")
        if fc.distinct:
            raise ParseError("DISTINCT in window functions not supported")
        return ast.WindowCall(fc.name, fc.args, fc.star, parts, orders)

    def parse_type(self) -> SQLType:
        t = self.next()
        name = t.text.lower()
        if name == "double" and self.peek().kind == Tok.IDENT \
                and self.peek().text == "precision":
            self.next()
            return FLOAT8
        if name in ("decimal", "numeric"):
            prec, scale = 19, 2
            if self.accept_op("("):
                prec = int(self.next().text)
                if self.accept_op(","):
                    scale = int(self.next().text)
                self.expect_op(")")
            return SQLType.decimal(prec, scale)
        if name in TYPE_NAMES:
            ty = TYPE_NAMES[name]
            if self.accept_op("("):  # varchar(n) etc. — length ignored
                self.next()
                self.expect_op(")")
            if self.accept_op("["):  # INT[] / TEXT[] array types
                self.expect_op("]")
                ty = SQLType.array(ty)
            return ty
        raise ParseError(f"unknown type {name!r}")

    # -- DDL/DML -----------------------------------------------------------
    def parse_create(self) -> ast.Statement:
        self.expect_kw("create")
        if self.peek().kind in (Tok.IDENT, Tok.KEYWORD) \
                and self.peek().text == "changefeed":
            self.next()
            self.expect_kw("for")
            table = self.expect_ident()
            if not (self.peek().kind in (Tok.IDENT, Tok.KEYWORD)
                    and self.peek().text == "into"):
                raise ParseError("expected INTO '<sink>'")
            self.next()
            t = self.next()
            if t.kind != Tok.STRING:
                raise ParseError("sink must be a string literal")
            return ast.CreateChangefeed(table, t.text)
        unique = False
        if self.peek().kind in (Tok.IDENT, Tok.KEYWORD) \
                and self.peek().text == "unique":
            self.next()
            unique = True
        if self.peek().kind in (Tok.IDENT, Tok.KEYWORD) \
                and self.peek().text == "index":
            self.next()
            if_not_exists = False
            if self.accept_kw("if"):
                self.expect_kw("not")
                self.expect_kw("exists")
                if_not_exists = True
            iname = self.expect_ident()
            self.expect_kw("on")
            table = self.expect_ident()
            self.expect_op("(")
            cols = [self.expect_ident()]
            while self.accept_op(","):
                cols.append(self.expect_ident())
            self.expect_op(")")
            return ast.CreateIndex(iname, table, cols, unique,
                                   if_not_exists)
        if unique:
            raise ParseError("expected INDEX after CREATE UNIQUE")
        if self.peek().kind == Tok.IDENT and self.peek().text == "view":
            self.next()
            if_not_exists = False
            if self.accept_kw("if"):
                self.expect_kw("not")
                self.expect_kw("exists")
                if_not_exists = True
            vname = self.expect_ident()
            cols = None
            if self.accept_op("("):
                cols = [self.expect_ident()]
                while self.accept_op(","):
                    cols.append(self.expect_ident())
                self.expect_op(")")
            self.expect_kw("as")
            body_start = self.peek().pos
            sel = self.parse_select_stmt()
            body = self.sql[body_start:].strip().rstrip(";").strip()
            return ast.CreateView(vname, cols, sel, body,
                                  if_not_exists)
        if self.peek().kind == Tok.IDENT \
                and self.peek().text == "sequence":
            self.next()
            if_not_exists = False
            if self.accept_kw("if"):
                self.expect_kw("not")
                self.expect_kw("exists")
                if_not_exists = True
            sname = self.expect_ident()
            start, increment = 1, 1
            while self.peek().kind == Tok.IDENT and \
                    self.peek().text in ("start", "increment"):
                which = self.next().text
                self.accept_kw("with")
                if self.peek().kind == Tok.IDENT \
                        and self.peek().text == "by":
                    self.next()
                t = self.next()
                if t.kind != Tok.NUMBER:
                    raise ParseError(f"expected number after {which}")
                if which == "start":
                    start = int(t.text)
                else:
                    increment = int(t.text)
            return ast.CreateSequence(sname, start, increment,
                                      if_not_exists)
        self.expect_kw("table")
        if_not_exists = False
        if self.accept_kw("if"):
            self.expect_kw("not")
            self.expect_kw("exists")
            if_not_exists = True
        name = self.expect_ident()
        self.expect_op("(")
        cols: list[ast.ColumnDef] = []
        pk: list[str] = []
        checks: list = []
        fks: list = []
        uniques: list = []  # table-level UNIQUE (cols)

        def _is_word(w: str) -> bool:
            return self.peek().kind in (Tok.IDENT, Tok.KEYWORD) \
                and self.peek().text == w

        def parse_check():
            self.expect_op("(")
            start = self.peek().pos
            e = self.parse_expr()
            end = self.peek().pos
            self.expect_op(")")
            text = self.sql[start:end].strip()
            checks.append((f"check_{name}_{len(checks) + 1}", e, text))

        def parse_references(local_cols: list[str]):
            rt = self.expect_ident()
            rcols = []
            if self.accept_op("("):
                rcols.append(self.expect_ident())
                while self.accept_op(","):
                    rcols.append(self.expect_ident())
                self.expect_op(")")
            fks.append((f"fk_{name}_{len(fks) + 1}", local_cols, rt,
                        rcols))

        while True:
            if self.accept_kw("primary"):
                self.expect_kw("key")
                self.expect_op("(")
                pk.append(self.expect_ident())
                while self.accept_op(","):
                    pk.append(self.expect_ident())
                self.expect_op(")")
            elif _is_word("check"):
                self.next()
                parse_check()
            elif _is_word("foreign"):
                self.next()
                self.expect_kw("key")
                self.expect_op("(")
                lcols = [self.expect_ident()]
                while self.accept_op(","):
                    lcols.append(self.expect_ident())
                self.expect_op(")")
                if not _is_word("references"):
                    raise ParseError("expected REFERENCES")
                self.next()
                parse_references(lcols)
            elif _is_word("unique") and self.peek(1).kind == Tok.OP \
                    and self.peek(1).text == "(":
                self.next()
                self.expect_op("(")
                ucols = [self.expect_ident()]
                while self.accept_op(","):
                    ucols.append(self.expect_ident())
                self.expect_op(")")
                uniques.append(ucols)
            else:
                cname = self.expect_ident()
                ctype = self.parse_type()
                nullable = True
                primary = False
                unique = False
                default = None
                while True:
                    if self.accept_kw("not"):
                        self.expect_kw("null")
                        nullable = False
                    elif self.accept_kw("null"):
                        pass
                    elif self.accept_kw("primary"):
                        self.expect_kw("key")
                        primary = True
                        nullable = False
                    elif self.accept_kw("default"):
                        default = self.parse_expr()
                    elif _is_word("check"):
                        self.next()
                        parse_check()
                    elif _is_word("references"):
                        self.next()
                        parse_references([cname])
                    elif _is_word("unique"):
                        self.next()
                        unique = True
                    else:
                        break
                cols.append(ast.ColumnDef(cname, ctype, nullable,
                                          primary, unique,
                                          default=default))
                if primary:
                    pk.append(cname)
            if not self.accept_op(","):
                break
        self.expect_op(")")
        return ast.CreateTable(name, cols, pk, if_not_exists,
                               checks=checks, foreign_keys=fks,
                               uniques=uniques)

    def parse_alter(self) -> ast.Statement:
        self.expect_kw("alter")
        self.expect_kw("table")
        table = self.expect_ident()
        if self.peek().kind in (Tok.IDENT, Tok.KEYWORD) \
                and self.peek().text == "configure":
            self.next()
            if not (self.peek().kind in (Tok.IDENT, Tok.KEYWORD)
                    and self.peek().text == "zone"):
                raise ParseError("expected ZONE after CONFIGURE")
            self.next()
            if not (self.peek().kind in (Tok.IDENT, Tok.KEYWORD)
                    and self.peek().text == "using"):
                raise ParseError("expected USING")
            self.next()
            opts = {}
            while True:
                name = self.dotted_name()
                self.expect_op("=")
                t = self.next()
                if t.kind == Tok.NUMBER:
                    opts[name] = (float(t.text) if "." in t.text
                                  else int(t.text))
                else:
                    opts[name] = t.text
                if not self.accept_op(","):
                    break
            return ast.ConfigureZone(table, opts)
        if self.accept_kw("add"):
            self.accept_kw("column")
            cname = self.expect_ident()
            ctype = self.parse_type()
            default = None
            nullable = True
            while True:
                if self.accept_kw("default"):
                    default = self.parse_expr()
                elif self.accept_kw("not"):
                    self.expect_kw("null")
                    nullable = False
                elif self.accept_kw("null"):
                    pass
                else:
                    break
            return ast.AlterTable(
                table, add=ast.ColumnDef(cname, ctype, nullable),
                default=default)
        if self.accept_kw("drop"):
            self.accept_kw("column")
            return ast.AlterTable(table, drop=self.expect_ident())
        raise ParseError("expected ADD or DROP after ALTER TABLE")

    def parse_drop(self) -> ast.Statement:
        self.expect_kw("drop")
        if self.peek().kind in (Tok.IDENT, Tok.KEYWORD) \
                and self.peek().text == "index":
            self.next()
            if_exists = False
            if self.accept_kw("if"):
                self.expect_kw("exists")
                if_exists = True
            return ast.DropIndex(self.expect_ident(), if_exists)
        if self.peek().kind == Tok.IDENT and self.peek().text in (
                "view", "sequence"):
            kind = self.next().text
            if_exists = False
            if self.accept_kw("if"):
                self.expect_kw("exists")
                if_exists = True
            name = self.expect_ident()
            return (ast.DropView(name, if_exists) if kind == "view"
                    else ast.DropSequence(name, if_exists))
        self.expect_kw("table")
        if_exists = False
        if self.accept_kw("if"):
            self.expect_kw("exists")
            if_exists = True
        return ast.DropTable(self.expect_ident(), if_exists)

    def parse_insert(self, upsert: bool = False) -> ast.Statement:
        if upsert:
            self.expect_kw("upsert")
        else:
            self.expect_kw("insert")
        self.expect_kw("into")
        table = self.expect_ident()
        columns: list[str] = []
        if self.accept_op("("):
            columns.append(self.expect_ident())
            while self.accept_op(","):
                columns.append(self.expect_ident())
            self.expect_op(")")
        if self.peek().is_kw("select"):
            return ast.Insert(table, columns,
                              select=self.parse_select_stmt(),
                              upsert=upsert)
        self.expect_kw("values")
        rows: list[list[ast.Expr]] = []
        while True:
            self.expect_op("(")
            row = [self.parse_expr()]
            while self.accept_op(","):
                row.append(self.parse_expr())
            self.expect_op(")")
            rows.append(row)
            if not self.accept_op(","):
                break
        return ast.Insert(table, columns, rows=rows,
                          upsert=upsert)

    def parse_update(self) -> ast.Statement:
        self.expect_kw("update")
        table = self.expect_ident()
        self.expect_kw("set")
        assigns: list[tuple[str, ast.Expr]] = []
        while True:
            col = self.expect_ident()
            self.expect_op("=")
            assigns.append((col, self.parse_expr()))
            if not self.accept_op(","):
                break
        where = self.parse_expr() if self.accept_kw("where") else None
        return ast.Update(table, assigns, where)

    def parse_delete(self) -> ast.Statement:
        self.expect_kw("delete")
        self.expect_kw("from")
        table = self.expect_ident()
        where = self.parse_expr() if self.accept_kw("where") else None
        return ast.Delete(table, where)

    def parse_set(self) -> ast.Statement:
        self.expect_kw("set")
        cluster = False
        if self.accept_kw("cluster"):
            self.expect_kw("setting")
            cluster = True
        name = self.dotted_name()
        if not self.accept_op("="):
            self.expect_kw("to")
        t = self.next()
        if t.kind == Tok.NUMBER:
            val: object = float(t.text) if "." in t.text else int(t.text)
        elif t.is_kw("true"):
            val = True
        elif t.is_kw("false"):
            val = False
        else:
            val = t.text
        return ast.SetVar(name, val, cluster)


def parse(sql: str) -> ast.Statement:
    p = Parser(sql)
    stmt = p.parse_statement()
    p.finish()
    return stmt


def parse_many(sql: str) -> list[ast.Statement]:
    p = Parser(sql)
    out = []
    while p.peek().kind != Tok.EOF:
        out.append(p.parse_statement())
        if not p.accept_op(";"):
            break
    if p.peek().kind != Tok.EOF:
        raise ParseError(f"trailing tokens at {p.peek()}")
    return out
