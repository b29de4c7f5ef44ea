"""The OLTP fast lane: statement-shape cache + native row plane.

Round-4's named limiter (BENCHMARKS.md:39-41): every OLTP op re-parses
its SQL (literals vary per op), re-matches the fastpath, and walks
rows as Python dicts — ~300µs of GIL-held Python per op, capping
16 concurrent YCSB-E drivers at ~3.7K ops/s. The reference's hot loop
is compiled Go end to end (conn_executor.go:1835 → kv →
pebbleMVCCScanner). This module is the equivalent compiled lane:

1. **Statement shapes** (`normalize`): literals are stripped from the
   SQL text (`SELECT … WHERE k = 42` → `… WHERE k = ?`, lits=[42]) and
   the shape keys a cache of prebuilt handlers — the same idea as the
   reference's plan cache keyed on fingerprint (sql/plan_cache.go),
   applied one level earlier so unparameterized client traffic still
   hits it.
2. **Native row plane** (`native/oltp.cpp`): eligible tables (single
   int primary key, all int64-representable columns) keep an MVCC
   version mirror in C++ — contiguous arrays + a key-ordered index.
   Point reads and ordered range scans run there with the GIL
   released; an internal shared_mutex admits truly parallel readers.
3. **Write lane + deferred publish**: single-row INSERT/UPDATE/DELETE
   still write through kv.Txn (latches, tscache floor, intents,
   commit — the concurrency truth is unchanged) and apply to the
   mirror at commit; the *columnstore* publish is queued and flushed
   in one batch before the next non-lane statement touches the table
   — the memtable pattern, which also stops the one-chunk-per-
   statement chunk explosion.

Serializability notes: lane reads bump the timestamp cache exactly
like the Python fastpath (a later writer can never commit beneath a
served read); lane writes take per-key latches and push above the
tscache floor; write-write conflicts surface as WriteTooOld/intent
pushes and retry through the same loop as `_dml`.
"""

from __future__ import annotations

import ctypes
import functools
import re
import threading
import time
from typing import Optional

import numpy as np

from ..native import get_oltp
from ..sql import ast
from ..kv.concurrency import Span
from ..sql.types import Family
from .session import EngineError, Result, Session

MAX_I64 = np.iinfo(np.int64).max

# literals: quoted strings first (so ints inside them don't match),
# then standalone integer tokens (not part of an identifier/number)
_LIT_RE = re.compile(r"'(?:[^']|'')*'|(?<![\w.])\d+(?![\w.\d])")


@functools.lru_cache(maxsize=8192)
def _normalize_text(sql: str):
    """Memoized (shape, literals-tuple) for one statement text: the
    regex pass runs once per DISTINCT text, not once per execution —
    YCSB-style drivers repeat a small set of literal combinations
    millions of times and this sat at the top of the lane profile."""
    lits: list = []

    def sub(m):
        tok = m.group(0)
        if tok.startswith("'"):
            lits.append(tok[1:-1].replace("''", "'"))
        else:
            lits.append(int(tok))
        return "?"

    return _LIT_RE.sub(sub, sql), tuple(lits)


def normalize(sql: str):
    """(shape, literals): literals replaced by ? placeholders."""
    shape, lits = _normalize_text(sql)
    return shape, list(lits)


# ---------------------------------------------------------------------------
# native table mirror
# ---------------------------------------------------------------------------

_INT_FAMS = (Family.INT, Family.BOOL, Family.DATE, Family.TIMESTAMP,
             Family.INTERVAL, Family.DECIMAL)


def mirror_eligible(schema) -> bool:
    """Single-column INT primary key, every column int64-representable
    in storage form, no hidden columns."""
    if len(schema.primary_key) != 1:
        return False
    pk = schema.primary_key[0]
    for c in schema.columns:
        if getattr(c, "hidden", False):
            return False
        if c.type.uses_dictionary or c.type.family not in _INT_FAMS:
            return False
        if np.dtype(c.type.np_dtype).kind not in "iub":
            return False
        if c.name == pk and c.type.family != Family.INT:
            return False
    return True


class TableMirror:
    """One table's native MVCC version mirror."""

    def __init__(self, lib, schema):
        self.lib = lib
        self.schema = schema
        self.pk = schema.primary_key[0]
        self.cols = [c.name for c in schema.columns]
        self.col_pos = {n: i for i, n in enumerate(self.cols)}
        self.ncols = len(self.cols)
        self.h = lib.oltp_create(self.ncols)
        self.synced_gen = -1
        # scratch buffers for point reads (per-mirror; guarded by the
        # caller holding no buffer across calls — each call copies out)
        self._local = threading.local()

    def __del__(self):
        try:
            self.lib.oltp_destroy(self.h)
        except Exception:
            pass

    def _bufs(self, cap: int):
        st = getattr(self._local, "bufs", None)
        if st is None or st[0] < cap:
            keys = np.empty(cap, dtype=np.int64)
            vals = np.empty(cap * self.ncols, dtype=np.int64)
            vld = np.empty(cap * self.ncols, dtype=np.uint8)
            st = (cap, keys, vals, vld,
                  keys.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                  vals.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                  vld.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
            self._local.bufs = st
        return st

    def rebuild(self, td) -> None:
        """Load every row version from the columnstore chunks (all
        versions: historical reads walk the same chains)."""
        self.lib.oltp_destroy(self.h)
        self.h = self.lib.oltp_create(self.ncols)
        parts = []
        for ch in td.chunks:
            n = ch.n
            if n == 0:
                continue
            keys = np.ascontiguousarray(ch.data[self.pk],
                                        dtype=np.int64)
            cols = np.empty((self.ncols, n), dtype=np.int64)
            vld = np.empty((self.ncols, n), dtype=np.uint8)
            for i, cn in enumerate(self.cols):
                cols[i] = ch.data[cn].astype(np.int64)
                vld[i] = ch.valid[cn].astype(np.uint8)
            parts.append((keys, ch.mvcc_ts.astype(np.int64),
                          ch.mvcc_del.astype(np.int64), cols, vld))
        if parts:
            keys = np.concatenate([p[0] for p in parts])
            ts = np.concatenate([p[1] for p in parts])
            del_ = np.concatenate([p[2] for p in parts])
            cols = np.concatenate([p[3] for p in parts], axis=1)
            vld = np.concatenate([p[4] for p in parts], axis=1)
            order = np.lexsort((ts, keys))
            keys = np.ascontiguousarray(keys[order])
            ts = np.ascontiguousarray(ts[order])
            del_ = np.ascontiguousarray(del_[order])
            cols = np.ascontiguousarray(cols[:, order])
            vld = np.ascontiguousarray(vld[:, order])
            i64p = ctypes.POINTER(ctypes.c_int64)
            self.lib.oltp_bulk(
                self.h, len(keys),
                keys.ctypes.data_as(i64p),
                ts.ctypes.data_as(i64p),
                del_.ctypes.data_as(i64p),
                cols.ctypes.data_as(i64p),
                vld.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
        self.synced_gen = td.generation

    def put(self, key: int, ts: int, vals: dict) -> None:
        v = np.empty(self.ncols, dtype=np.int64)
        m = np.empty(self.ncols, dtype=np.uint8)
        for i, cn in enumerate(self.cols):
            x = vals.get(cn)
            if x is None:
                v[i] = 0
                m[i] = 0
            else:
                v[i] = int(x)
                m[i] = 1
        self.lib.oltp_put(
            self.h, int(key), int(ts),
            v.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            m.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))

    def delete(self, key: int, ts: int) -> None:
        self.lib.oltp_del(self.h, int(key), int(ts))

    def read(self, key: int, read_ts: int):
        """(vals_i64_list, valid_list) or None."""
        _, _, vals, vld, _, vp, mp = self._bufs(max(64, self.ncols))
        ok = self.lib.oltp_read(self.h, int(key), int(read_ts), vp, mp)
        if not ok:
            return None
        return vals[:self.ncols].tolist(), vld[:self.ncols].tolist()

    def multiread(self, keys, read_ts: int):
        """Fused gather for one batch window: (vals row-major list,
        valid list, found list) across the whole key vector — a single
        native call (one shared-lock acquisition, one GIL release)
        instead of len(keys) point reads."""
        n = len(keys)
        karr = np.ascontiguousarray(keys, dtype=np.int64)
        vals = np.empty(max(n, 1) * self.ncols, dtype=np.int64)
        vld = np.empty(max(n, 1) * self.ncols, dtype=np.uint8)
        fnd = np.zeros(max(n, 1), dtype=np.uint8)
        i64p = ctypes.POINTER(ctypes.c_int64)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        self.lib.oltp_multiread(
            self.h, n, karr.ctypes.data_as(i64p), int(read_ts),
            vals.ctypes.data_as(i64p), vld.ctypes.data_as(u8p),
            fnd.ctypes.data_as(u8p))
        return vals.tolist(), vld.tolist(), fnd.tolist()

    def scan(self, lo, lo_strict, hi, hi_strict, read_ts: int,
             cap: int):
        """(nrows, keys[], vals row-major, valid row-major)."""
        _, keys, vals, vld, kp, vp, mp = self._bufs(
            max(cap * self.ncols, cap, 64))
        n = self.lib.oltp_scan(
            self.h,
            int(lo) if lo is not None else 0, int(lo is not None),
            int(bool(lo_strict)),
            int(hi) if hi is not None else 0, int(hi is not None),
            int(bool(hi_strict)),
            int(read_ts), int(cap), kp, vp, mp)
        return n, keys, vals, vld


# ---------------------------------------------------------------------------
# lane plans (one per statement shape)
# ---------------------------------------------------------------------------

class LanePlan:
    """Prebuilt executor for one statement shape. kind:
    'point' | 'scan' | 'insert' | 'update' | 'delete'."""

    __slots__ = ("kind", "table", "out_names", "out_types", "out_pos",
                 "out_decode", "out_pairs", "pk_lit", "lo_lit",
                 "lo_strict", "hi_lit", "hi_strict", "limit_lit",
                 "limit_const", "set_cols", "set_lits", "ins_cols",
                 "ins_lits", "nlits", "lit_kinds", "order_desc", "td",
                 "codec")

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw.get(k))


class ShapeIneligible(Exception):
    pass


# sentinel literal values used to discover slot roles: the shape text
# re-parses with slot i carrying SENT_BASE+i (or a marker string), so
# the role of each ? is read off the AST structurally — never guessed
# from runtime values (two slots can carry equal values)
SENT_BASE = 7_700_000_000
SENT_STR = "\x00slot{}"


class _Slot:
    """One literal slot reference discovered at sentinel position i;
    neg marks a sentinel consumed under unary minus."""

    __slots__ = ("i", "neg")

    def __init__(self, i: int, neg: bool = False):
        self.i = i
        self.neg = neg

    def get(self, lits):
        v = lits[self.i]
        return -v if self.neg else v


def _slot_of(value, nlits):
    """Map a parsed literal value back to its slot (or None for a
    constant baked into the shape)."""
    if isinstance(value, str) and value.startswith("\x00slot"):
        return _Slot(int(value[6:]))
    if isinstance(value, (int, float)) and not isinstance(value, bool):
        iv = int(value)
        if SENT_BASE <= iv < SENT_BASE + nlits:
            return _Slot(iv - SENT_BASE)
        if -SENT_BASE - nlits < iv <= -SENT_BASE:
            return _Slot(-iv - SENT_BASE, neg=True)
    return None


def _sentinel_sql(shape: str, lits: list) -> str:
    out = []
    i = 0
    for part in shape.split("?"):
        out.append(part)
        if i < len(lits):
            if isinstance(lits[i], str):
                out.append("'" + SENT_STR.format(i) + "'")
            else:
                out.append(str(SENT_BASE + i))
            i += 1
    return "".join(out)


class _Const:
    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v

    def get(self, _lits):
        return self.v


class OltpLaneMixin:
    """Engine methods for the OLTP fast lane (state on the Engine)."""

    def _lane_init(self) -> None:
        self._lane_lib = get_oltp()
        self._lane_shapes: dict = {}       # shape -> LanePlan | None
        self._lane_mirrors: dict = {}      # table -> TableMirror
        self._lane_pending: dict = {}      # table -> [(op, tsi), ...]
        self._lane_lock = threading.Lock()
        # commit-vs-snapshot fence: a lane COMMIT (active check + kv
        # commit + mirror/queue apply) and a full-path statement's
        # (active increment + pending check) each happen atomically
        # under this lock, so a full-path read can never take a
        # snapshot between a lane commit and its queue append
        # (review round-5 finding #3)
        self._lane_sync = threading.Lock()
        self._nonlane_active = 0
        # statement-scoped suspension: full-path statements whose base
        # table set is known suspend lane writes ONLY for those tables
        # (table -> active statement count, under _lane_sync). An
        # analytic tenant scanning other tables no longer stalls the
        # OLTP lane or forces its flush (engine.execute_stmt).
        self._nonlane_tables: dict = {}
        self.lane_hits = 0
        self.lane_misses = 0
        # cross-session batch windows (exec/oltpbatch.py): concurrent
        # point statements fuse into one multi-key probe / one group
        # commit. Session var oltp_batch=off restores the
        # per-statement path bit-for-bit.
        from .oltpbatch import LaneBatcher
        self._lane_batcher = LaneBatcher(self)

    # -- entry ------------------------------------------------------

    def lane_execute(self, sql: str,
                     session: Optional[Session]) -> Optional[Result]:
        """Serve `sql` from the fast lane, or None to take the normal
        path. Never raises for ineligibility — only for real statement
        errors (duplicate key etc.)."""
        if self._lane_lib is None or self.cluster is not None:
            return None
        if session is not None and (
                session.txn is not None or session.effects
                or session.txn_aborted
                or session.vars.get("index_scan", "on") == "off"
                or session.vars.get("tracing", "off") == "on"):
            return None
        got = normalize(sql)
        shape, lits = got
        plan = self._lane_shapes.get(shape, ShapeIneligible)
        if plan is ShapeIneligible:
            plan = self._lane_build(shape, lits)
        if plan is None:
            self.lane_misses += 1
            return None
        if len(lits) != plan.nlits:
            return None
        if plan.lit_kinds is not None and \
                plan.lit_kinds != [isinstance(v, str) for v in lits]:
            # literal-kind mismatch vs the cached classification
            # (e.g. WHERE k = 'abc' hitting a shape built for
            # WHERE k = 42): the full path binds it properly and
            # raises a real SQL type error instead of a bare
            # ValueError out of int()
            return None
        t0 = time.perf_counter()
        try:
            if plan.kind == "scan":
                # range scans stay per-statement: their native scan is
                # already one fused pass and their result sizes would
                # make window buffers unbounded
                res = self._lane_read(plan, lits, session)
            elif session is not None and \
                    session.vars.get("oltp_batch", "auto") == "off":
                # the A/B lever: off is exactly the per-statement path
                res = (self._lane_read(plan, lits, session)
                       if plan.kind == "point"
                       else self._lane_write(plan, lits, session))
            else:
                res = self._lane_batcher.submit(plan, lits, session)
        except ShapeIneligible:
            return None
        if res is not None:
            self.lane_hits += 1
            self.sqlstats.record_fp(shape, time.perf_counter() - t0,
                                    max(len(res.rows), res.row_count))
        return res

    # -- shape classification ---------------------------------------

    def _lane_build(self, shape: str, lits: list):
        try:
            plan = self._lane_classify(shape, lits)
        except Exception:
            plan = None
        if plan is not None:
            # the plan was classified against THESE literal kinds (the
            # sentinel SQL bakes int-vs-string into the parse); a later
            # statement with the same shape but a different kind in
            # some slot must take the full path, not int() a string
            plan.lit_kinds = [isinstance(v, str) for v in lits]
        if len(self._lane_shapes) > 4096:
            self._lane_shapes.clear()
        self._lane_shapes[shape] = plan
        return plan

    def _lane_table_ok(self, tname: str) -> bool:
        """Schema-level eligibility: mirrorable columns and none of
        the write-path features the lane skips (checks, FKs, secondary
        indexes, cdc) — those statements take the full path."""
        if tname not in self.store.tables:
            return False
        td = self.store.table(tname)
        if not mirror_eligible(td.schema):
            return False
        if self._table_indexes(tname):
            return False
        d = self.catalog.get_by_name(tname)
        if d is not None and (d.checks or d.fks):
            return False
        if self._fk_children_of(tname):
            return False
        if any(f.table == tname for f in self.cdc_feeds):
            return False
        if getattr(td, "column_defaults", None):
            return False
        return True

    def _lane_classify(self, shape: str, lits: list):
        from ..sql import parser as _parser
        stmt = _parser.parse(_sentinel_sql(shape, lits))
        n = len(lits)

        def lit_ref(e):
            if not isinstance(e, ast.Literal) or e.value is None:
                return None
            s = _slot_of(e.value, n)
            return s if s is not None else _Const(e.value)

        if isinstance(stmt, ast.Select):
            return self._classify_select(stmt, n, lit_ref)
        if isinstance(stmt, ast.Insert):
            return self._classify_insert(stmt, n, lit_ref)
        if isinstance(stmt, ast.Update):
            return self._classify_update(stmt, n, lit_ref)
        if isinstance(stmt, ast.Delete):
            return self._classify_delete(stmt, n, lit_ref)
        return None

    def _classify_select(self, sel, n, lit_ref):
        from .stmtutil import split_conjuncts_ast
        if (sel.table is None or sel.joins or sel.group_by
                or sel.having or sel.distinct or sel.ctes
                or getattr(sel, "as_of", None) is not None
                or sel.table.subquery is not None
                or getattr(sel, "windows", None)):
            return None
        tname = sel.table.name
        if sel.table.alias not in (None, tname):
            return None
        if not self._lane_table_ok(tname) or tname in self._view_map():
            return None
        schema = self.store.table(tname).schema
        pk = schema.primary_key[0]
        out = []
        for item in sel.items:
            if item.star:
                for c in schema.columns:
                    out.append((c.name, c.name))
            else:
                e = item.expr
                if not (isinstance(e, ast.ColumnRef)
                        and e.table in (None, tname)
                        and any(c.name == e.name
                                for c in schema.columns)):
                    return None
                out.append((item.alias or e.name, e.name))
        eq = lo = hi = None
        lo_strict = hi_strict = False
        if sel.where is None:
            return None
        for c in split_conjuncts_ast(sel.where):
            if not (isinstance(c, ast.BinOp)
                    and c.op in ("=", "<", "<=", ">", ">=")):
                return None
            lhs, rhs, op = c.left, c.right, c.op
            if isinstance(lhs, ast.Literal) and \
                    isinstance(rhs, ast.ColumnRef):
                lhs, rhs = rhs, lhs
                op = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}.get(
                    op, op)
            if not (isinstance(lhs, ast.ColumnRef) and lhs.name == pk
                    and lhs.table in (None, tname)):
                return None
            ref = lit_ref(rhs)
            if ref is None or isinstance(
                    getattr(rhs, "value", None), str):
                return None
            if op == "=":
                if eq is not None:
                    return None
                eq = ref
            elif op in (">", ">="):
                if lo is not None:
                    return None
                lo, lo_strict = ref, op == ">"
            else:
                if hi is not None:
                    return None
                hi, hi_strict = ref, op == "<"
        if eq is not None and (lo is not None or hi is not None):
            return None
        if sel.order_by:
            if len(sel.order_by) != 1:
                return None
            ob = sel.order_by[0]
            if not (isinstance(ob.expr, ast.ColumnRef)
                    and ob.expr.name == pk and not ob.desc):
                return None
        limit_ref = None
        if sel.limit is not None:
            limit_ref = lit_ref(ast.Literal(sel.limit)) \
                if not isinstance(sel.limit, ast.Literal) \
                else lit_ref(sel.limit)
            if limit_ref is None:
                return None
        if getattr(sel, "offset", None):
            return None
        types = {c.name: c.type for c in schema.columns}
        pos = {c.name: i for i, c in enumerate(schema.columns)}
        if eq is not None:
            kind = "point"
        else:
            if lo is None and hi is None:
                return None
            kind = "scan"
        plan = LanePlan(
            kind=kind, table=tname, nlits=n,
            out_names=[o for o, _ in out],
            out_types=[types[s] for _, s in out],
            out_pos=[pos[s] for _, s in out],
            out_decode=[_decoder(types[s]) for _, s in out],
            pk_lit=eq, lo_lit=lo, lo_strict=lo_strict,
            hi_lit=hi, hi_strict=hi_strict, limit_lit=limit_ref)
        plan.out_pairs = list(zip(plan.out_pos, plan.out_decode))
        return plan

    def _classify_insert(self, ins, n, lit_ref):
        if ins.select is not None or ins.upsert or len(ins.rows) != 1:
            return None
        tname = ins.table
        if not self._lane_table_ok(tname):
            return None
        schema = self.store.table(tname).schema
        cols = ins.columns or schema.column_names
        if callable(cols):
            cols = cols()
        cols = list(cols)
        if len(ins.rows[0]) != len(cols):
            return None
        refs = []
        for e in ins.rows[0]:
            if isinstance(e, ast.Literal) and e.value is None:
                refs.append(_Const(None))
                continue
            r = lit_ref(e)
            if r is None:
                return None
            refs.append(r)
        # every non-listed column must be nullable or defaulted
        defaults = getattr(self.store.table(tname), "column_defaults",
                           {})
        for c in schema.columns:
            if c.name not in cols and not c.nullable \
                    and c.name not in defaults:
                return None
        if defaults:
            return None           # default exprs take the full path
        return LanePlan(kind="insert", table=tname, nlits=n,
                        ins_cols=list(cols), ins_lits=refs)

    def _classify_update(self, upd, n, lit_ref):
        tname = upd.table
        if not self._lane_table_ok(tname):
            return None
        schema = self.store.table(tname).schema
        pk = schema.primary_key[0]
        sets, slits = [], []
        for cname, e in upd.assignments:
            if cname == pk:
                return None       # pk rewrite: full path
            if not any(c.name == cname for c in schema.columns):
                return None
            if isinstance(e, ast.Literal) and e.value is None:
                slits.append(_Const(None))
                sets.append(cname)
                continue
            r = lit_ref(e)
            if r is None:
                return None
            sets.append(cname)
            slits.append(r)
        eq = self._pk_eq(upd.where, tname, pk, lit_ref)
        if eq is None:
            return None
        return LanePlan(kind="update", table=tname, nlits=n,
                        pk_lit=eq, set_cols=sets, set_lits=slits)

    def _classify_delete(self, dele, n, lit_ref):
        tname = dele.table
        if not self._lane_table_ok(tname):
            return None
        schema = self.store.table(tname).schema
        pk = schema.primary_key[0]
        eq = self._pk_eq(dele.where, tname, pk, lit_ref)
        if eq is None:
            return None
        return LanePlan(kind="delete", table=tname, nlits=n,
                        pk_lit=eq)

    @staticmethod
    def _pk_eq(where, tname, pk, lit_ref):
        if not (isinstance(where, ast.BinOp) and where.op == "="):
            return None
        lhs, rhs = where.left, where.right
        if isinstance(lhs, ast.Literal) and isinstance(
                rhs, ast.ColumnRef):
            lhs, rhs = rhs, lhs
        if not (isinstance(lhs, ast.ColumnRef) and lhs.name == pk
                and lhs.table in (None, tname)):
            return None
        if isinstance(getattr(rhs, "value", None), str):
            return None
        return lit_ref(rhs)

    # -- mirrors ----------------------------------------------------

    def _lane_mirror(self, tname: str):
        """Current mirror for `tname`, rebuilt if the columnstore
        moved underneath it (non-lane writes bump the generation)."""
        td = self.store.tables.get(tname)
        if td is None:
            raise ShapeIneligible(tname)
        m = self._lane_mirrors.get(tname)
        if m is not None and (m.synced_gen == td.generation
                              or self._lane_pending.get(tname)):
            return m
        with self._lane_lock:
            m = self._lane_mirrors.get(tname)
            if m is not None and (m.synced_gen == td.generation
                                  or self._lane_pending.get(tname)):
                return m
            self.store.seal(tname)
            m = TableMirror(self._lane_lib, td.schema)
            m.rebuild(td)
            self._lane_mirrors[tname] = m
            return m

    # -- read handlers ----------------------------------------------

    def _lane_read(self, plan: LanePlan, lits, session):
        self._stmt_lock.acquire_read()
        try:
            m = self._lane_mirror(plan.table)
            td = plan.td
            if td is None:
                td = plan.td = self.store.table(plan.table)
                plan.codec = td.codec
            read_ts = self.clock.now()
            rtsi = read_ts.to_int()
            tsc = self.kv.store.tscache
            if plan.kind == "point":
                key = int(plan.pk_lit.get(lits))
                kb = plan.codec.key_from_pk((key,))
                tsc.add(Span(kb), read_ts, None)
                got = m.read(key, rtsi)
                rows = []
                if got is not None:
                    vals, vld = got
                    rows.append(tuple(
                        dec(vals[p]) if vld[p] else None
                        for p, dec in plan.out_pairs))
                if plan.limit_lit is not None:
                    rows = rows[:max(int(plan.limit_lit.get(lits)),
                                     0)]
                return Result(names=plan.out_names, rows=rows,
                              types=plan.out_types)
            lo = (int(plan.lo_lit.get(lits))
                  if plan.lo_lit is not None else None)
            hi = (int(plan.hi_lit.get(lits))
                  if plan.hi_lit is not None else None)
            limit = (int(plan.limit_lit.get(lits))
                     if plan.limit_lit is not None else None)
            cap_var = int(session.vars.get("index_lookup_limit", 4096)
                          if session is not None else 4096)
            if limit is not None and (limit < 0 or limit > cap_var):
                return None   # compiled path; also bounds the buffer
                # allocation at cap_var (a 1e8 LIMIT must not reserve
                # gigabytes up front — review round-5 finding #6)
            cap = limit if limit is not None else cap_var + 1
            start, end = plan.codec.span()
            kb = (plan.codec.key_from_pk((lo,)) if lo is not None
                  else start)
            ke = (plan.codec.key_from_pk((hi,)) + b"\xff"
                  if hi is not None else end)
            tsc.add(Span(kb, ke), read_ts, None)
            nrow, keys, vals, vld = m.scan(lo, plan.lo_strict, hi,
                                           plan.hi_strict, rtsi, cap)
            if limit is None and nrow > cap_var:
                return None       # low selectivity: compiled path
            ncols = m.ncols
            pairs = plan.out_pairs
            vlist = vals[:nrow * ncols].tolist()
            mlist = vld[:nrow * ncols].tolist()
            out = []
            base = 0
            for r in range(nrow):
                out.append(tuple(
                    dec(vlist[base + p]) if mlist[base + p] else None
                    for p, dec in pairs))
                base += ncols
            return Result(names=plan.out_names, rows=out,
                          types=plan.out_types)
        finally:
            self._stmt_lock.release_read()

    # -- write handlers ---------------------------------------------

    def _nonlane_busy(self, table: str) -> bool:
        """A full-path statement that can read `table` is in flight
        (statement-scoped when its table set is known, global
        otherwise)."""
        return bool(self._nonlane_active
                    or self._nonlane_tables.get(table))

    def _lane_write(self, plan: LanePlan, lits, session):
        from ..kv.concurrency import TxnAbortedError, TxnRetryError
        from ..kv.txn import DB as KVDB
        from ..kv.txn import Txn
        from .dml import retry_exhausted
        self._stmt_lock.acquire_read()
        try:
            if self._nonlane_busy(plan.table):
                # a full-path statement over this table is in flight:
                # its snapshot was taken after a flush, so new lane
                # writes must queue BEHIND it — take the full path
                # instead (re-checked under _lane_sync at commit time)
                raise ShapeIneligible("nonlane active")
            if any(f.table == plan.table for f in self.cdc_feeds) \
                    or any(th.is_alive() and tb == plan.table
                           for th, tb in self._cdc_threads.values()):
                # a changefeed on THIS table consumes commits from the
                # publish path; a deferred lane publish would starve
                # it. Re-checked HERE (not just at plan build): feeds
                # register asynchronously after CREATE CHANGEFEED
                # returns. Scoped per table, and dead feed threads
                # (failed/finished jobs) do not gate anything.
                raise ShapeIneligible("changefeed active")
            m = self._lane_mirror(plan.table)
            td = self.store.table(plan.table)
            schema = td.schema
            codec = td.codec
            last = None
            for _ in range(KVDB.MAX_ATTEMPTS):
                t = Txn(self.kv.store)
                try:
                    with self._lane_sync:
                        if self._nonlane_busy(plan.table):
                            raise ShapeIneligible("nonlane active")
                        res = self._lane_write_once(plan, lits, t, m,
                                                    td, schema, codec)
                        cts = t.commit()
                        tsi = cts.to_int()
                        op = res[1]
                        if op is not None:
                            with self._lane_lock:
                                self._lane_apply_mirror(m, op, tsi)
                                self._lane_pending.setdefault(
                                    plan.table, []).append((op, tsi))
                    return res[0]
                except (TxnRetryError, TxnAbortedError) as e:
                    t.rollback()
                    last = e
                except ShapeIneligible:
                    t.rollback()
                    raise
                except BaseException:
                    t.rollback()
                    raise
            raise retry_exhausted(last)
        finally:
            self._stmt_lock.release_read()

    @staticmethod
    def _lane_apply_mirror(m: TableMirror, op, tsi: int) -> None:
        kind = op[0]
        if kind == "put":
            row = op[2]
            m.put(row[m.pk], tsi, row)
        else:
            m.delete(op[2], tsi)

    def _lane_write_once(self, plan, lits, t, m, td, schema, codec):
        rtsi = t.meta.read_ts.to_int()
        if plan.kind == "insert":
            row = {}
            for cn, ref in zip(plan.ins_cols, plan.ins_lits):
                col = schema.column(cn)
                v = ref.get(lits)
                if v is None:
                    if not col.nullable:
                        raise EngineError(
                            f"null in non-null column {cn}")
                    row[cn] = None
                else:
                    row[cn] = self._lane_coerce(col, v)
            for col in schema.columns:
                if col.name not in row:
                    if not col.nullable:
                        raise EngineError(
                            f"null in non-null column {col.name}")
                    row[col.name] = None
            key = codec.key(row)
            if t.get(key) is not None or \
                    self._lane_lib.oltp_live(m.h, int(row[m.pk]),
                                             rtsi):
                raise EngineError(
                    f"duplicate key value "
                    f"{codec.pk_values(row)!r} violates primary key "
                    f"of {plan.table!r}")
            t.put(key, codec.encode_value(row))
            return (Result(row_count=1, tag="INSERT"),
                    ("put", key, row))
        pk_val = int(plan.pk_lit.get(lits))
        key = codec.key_from_pk((pk_val,))
        # the KV read both registers the read span and surfaces
        # conflicting intents (push/abort via the txn machinery)
        t.get(key)
        got = m.read(pk_val, rtsi)
        if got is None:
            tag = "UPDATE 0" if plan.kind == "update" else "DELETE 0"
            return (Result(row_count=0, tag=tag.split()[0]), None)
        if plan.kind == "delete":
            t.delete(key)
            return (Result(row_count=1, tag="DELETE"),
                    ("del", key, pk_val))
        vals, vld = got
        row = {}
        for i, cn in enumerate(m.cols):
            row[cn] = vals[i] if vld[i] else None
        for cn, ref in zip(plan.set_cols, plan.set_lits):
            v = ref.get(lits)
            col = schema.column(cn)
            if v is None:
                if not col.nullable:
                    raise EngineError(f"null in non-null column {cn}")
                row[cn] = None
            else:
                row[cn] = self._lane_coerce(col, v)
        t.put(key, codec.encode_value(row))
        return (Result(row_count=1, tag="UPDATE"), ("put", key, row))

    # -- batch windows (exec/oltpbatch.py drives these) -------------

    def _lane_read_batch(self, reqs) -> None:
        """One fused multi-key probe for a window of point reads:
        a single statement-gate acquisition, one read timestamp, and
        one native `multiread` per table instead of len(reqs) point
        reads. Each request's tscache span is still registered
        individually, so writers see exactly the spans the
        per-statement path would have left behind."""
        self._stmt_lock.acquire_read()
        try:
            read_ts = self.clock.now()
            rtsi = read_ts.to_int()
            tsc = self.kv.store.tscache
            groups: dict = {}
            for req in reqs:
                groups.setdefault(req.plan.table, []).append(req)
            for tname, group in groups.items():
                try:
                    m = self._lane_mirror(tname)
                except ShapeIneligible as e:
                    for req in group:
                        req.error = e
                    continue
                keys = []
                for req in group:
                    plan = req.plan
                    if plan.td is None:
                        plan.td = self.store.table(tname)
                        plan.codec = plan.td.codec
                    key = int(plan.pk_lit.get(req.lits))
                    tsc.add(Span(plan.codec.key_from_pk((key,))),
                            read_ts, None)
                    keys.append(key)
                vals, vld, fnd = m.multiread(keys, rtsi)
                ncols = m.ncols
                for i, req in enumerate(group):
                    plan = req.plan
                    rows = []
                    if fnd[i]:
                        base = i * ncols
                        rows.append(tuple(
                            dec(vals[base + p])
                            if vld[base + p] else None
                            for p, dec in plan.out_pairs))
                    if plan.limit_lit is not None:
                        rows = rows[:max(
                            int(plan.limit_lit.get(req.lits)), 0)]
                    req.result = Result(names=plan.out_names,
                                        rows=rows,
                                        types=plan.out_types)
        finally:
            self._stmt_lock.release_read()

    def _lane_write_batch(self, reqs) -> None:
        """Group commit for a window of single-row writes: the window
        splits into rounds with at most one write per (table, pk) —
        a second write to the same key must observe the first's
        committed value, which a shared transaction cannot give it —
        and each round commits as ONE kv transaction (one WAL-append
        analogue) while every waiter still gets its own Result or
        statement error."""
        self._stmt_lock.acquire_read()
        try:
            live = []
            for req in reqs:
                tname = req.plan.table
                if self._nonlane_busy(tname):
                    # a full-path statement over this table is in
                    # flight: its waiters fall back to the full path,
                    # same as the per-statement lane
                    req.error = ShapeIneligible("nonlane active")
                elif any(f.table == tname for f in self.cdc_feeds) \
                        or any(th.is_alive() and tb == tname
                               for th, tb in
                               self._cdc_threads.values()):
                    req.error = ShapeIneligible("changefeed active")
                else:
                    live.append(req)
            while live:
                seen: set = set()
                this_round, defer = [], []
                for req in live:
                    k = (req.plan.table, self._lane_req_pk(req))
                    if k in seen:
                        defer.append(req)
                    else:
                        seen.add(k)
                        this_round.append(req)
                self._lane_write_round(this_round)
                live = defer
        finally:
            self._stmt_lock.release_read()

    def _lane_req_pk(self, req):
        """Primary-key value a write request targets (dedup key for
        round-splitting). Uncoercible values pass through raw — the
        round surfaces the real statement error."""
        plan, lits = req.plan, req.lits
        if plan.kind == "insert":
            pk = self.store.table(plan.table).schema.primary_key[0]
            for cn, ref in zip(plan.ins_cols, plan.ins_lits):
                if cn == pk:
                    v = ref.get(lits)
                    try:
                        return int(v)
                    except (TypeError, ValueError):
                        return v
            return None
        return int(plan.pk_lit.get(lits))

    def _lane_write_round(self, reqs) -> None:
        from ..kv.concurrency import TxnAbortedError, TxnRetryError
        from ..kv.txn import DB as KVDB
        from ..kv.txn import Txn
        from ..kvserver.raft import GROUPCOMMIT
        from .dml import retry_exhausted
        ctx: dict = {}
        for req in reqs:
            tname = req.plan.table
            if tname not in ctx:
                m = self._lane_mirror(tname)
                td = self.store.table(tname)
                ctx[tname] = (m, td, td.schema, td.codec)
        last = None
        for _ in range(KVDB.MAX_ATTEMPTS):
            t = Txn(self.kv.store)
            try:
                with self._lane_sync:
                    if self._nonlane_active or any(
                            self._nonlane_tables.get(tn)
                            for tn in ctx):
                        raise ShapeIneligible("nonlane active")
                    outcomes = []
                    for req in reqs:
                        m, td, schema, codec = ctx[req.plan.table]
                        try:
                            res = self._lane_write_once(
                                req.plan, req.lits, t, m, td,
                                schema, codec)
                        except (EngineError, ShapeIneligible) as e:
                            # per-statement errors all raise BEFORE
                            # t.put, so the shared txn carries no
                            # trace of the failed request
                            outcomes.append((req, None, e))
                        else:
                            outcomes.append((req, res, None))
                    cts = t.commit()   # ONE commit for the round
                    tsi = cts.to_int()
                    nops = 0
                    with self._lane_lock:
                        for req, res, err in outcomes:
                            if res is None or res[1] is None:
                                continue
                            op = res[1]
                            self._lane_apply_mirror(
                                ctx[req.plan.table][0], op, tsi)
                            self._lane_pending.setdefault(
                                req.plan.table, []).append((op, tsi))
                            nops += 1
                if nops:
                    GROUPCOMMIT.bump(nops)
                for req, res, err in outcomes:
                    if err is not None:
                        req.error = err
                    else:
                        req.result = res[0]
                return
            except (TxnRetryError, TxnAbortedError) as e:
                t.rollback()
                last = e
            except ShapeIneligible:
                t.rollback()
                raise
            except BaseException:
                t.rollback()
                raise
        raise retry_exhausted(last)

    @staticmethod
    def _lane_coerce(col, v):
        f = col.type.family
        if f == Family.INT:
            return int(v)
        if f == Family.BOOL:
            return bool(v)
        if f == Family.DECIMAL and isinstance(v, int):
            return v * 10 ** col.type.scale
        raise ShapeIneligible(f"uncoercible {f}")

    # -- deferred publish -------------------------------------------

    def lane_flush(self, tables=None) -> None:
        """Publish queued lane writes to the columnstore. Caller holds
        the write side of the statement gate. ``tables`` limits the
        publish to those tables' queues (statement-scoped flush:
        engine.execute_stmt flushes only what the statement can read,
        so an analytic query never pays another table's upload)."""
        with self._lane_lock:
            if tables is None:
                pending = self._lane_pending
                self._lane_pending = {}
            else:
                pending = {}
                for t in tables:
                    e = self._lane_pending.pop(t, None)
                    if e:
                        pending[t] = e
        for table, entries in pending.items():
            entries.sort(key=lambda e: e[1])
            batches = []
            for op, tsi in entries:
                if batches and batches[-1][1] == tsi:
                    batches[-1][0].append(self._store_op(op))
                else:
                    batches.append(([self._store_op(op)], tsi))
            self.store.apply_committed_batch(table, batches)
            self._evict(table)
            m = self._lane_mirrors.get(table)
            if m is not None:
                m.synced_gen = self.store.table(table).generation

    @staticmethod
    def _store_op(op):
        if op[0] == "put":
            return ("put", op[1], op[2])
        return ("del", op[1])


def _decoder(ty):
    """Per-type storage-int -> client-value decoder."""
    from .stmtutil import _decode_scalar
    f = ty.family
    if f == Family.INT:
        return int
    if f == Family.BOOL:
        return bool
    return lambda v, _t=ty: _decode_scalar(v, True, _t, None)
