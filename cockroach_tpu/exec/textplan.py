"""The statement-text cache: a SELECT whose exact text this engine has
already planned is served from the prepared plan its first execution
built (the reference keeps a server-wide cache of optimized plans keyed
by the SQL string, pkg/sql/querycache/query_cache.go, and checks each
for staleness before use, pkg/sql/plan_opt.go).

A hit skips the parse, the planner and everything the plan-shape cache
lookup derives after it (placement, guards, compaction, literal
lifting, the fingerprint). What is left is the seal check, the entry's
staleness check and a shallow copy of its Prepared bound to the
executing session: Prepared.run() takes a new read timestamp, passes
the lifted literals and subquery arguments, and re-prepares itself
where a scanned table's generation moved, as a pgwire portal does.

An entry is kept only where nothing its preparation read can change
under an unchanged text, as far as the engine observes: not inside a
transaction or a CTE capture, not AS OF SYSTEM TIME, no value folded
at bind time that differs between executions (now(), random(), a
sequence: binder.volatile_folds), not a streamed, spilled or
distributed verdict, and over device batches the resident-table cache
itself holds, so an entry pins no memory that cache would free. It is
dropped on DDL, ANALYZE and a cluster-setting change, on a write to a
table its text names, on the eviction of a device table it reads, and
when its Prepared notes a whole sort or re-plans uncompacted. Session
variables are part of its key. Counters sql.plan.text.{hit,miss} and
sql.plan.text.invalidated (by cause, sql.plan.text.invalidated.<cause>).
"""

from __future__ import annotations

import copy
import threading
from dataclasses import dataclass
from typing import NamedTuple, Optional

from ..sql.binder import volatile_folds
from ..utils import tracing as _trc
from .session import Prepared, Session
from .tenantcache import TenantLRU

# why entries were dropped (sql.plan.text.invalidated.<cause>)
CAUSES = ("ddl", "analyze", "setting", "write", "evicted", "whole_sort",
          "uncompacted")


@dataclass(frozen=True)
class TextPlan:
    """One text's entry. `stmt` is the parse cache's AST of the text,
    handed down a hit's statement path and never planned from;
    `tables` what Engine._stmt_tables found in it (CTE names too),
    sorted; `gens` their generations when it was kept (None for a name
    the store does not hold); `prep` the Prepared, bound to no
    session."""
    stmt: object
    tables: tuple
    gens: tuple
    prep: Prepared


class TextProbe(NamedTuple):
    """What Engine.execute found for its text, published per thread to
    the statement's own _exec_select: the key, the AST it handed down,
    the entry (None on a miss)."""
    key: tuple
    stmt: object
    entry: Optional[TextPlan]


def _unbind(p: Prepared, key: tuple) -> Prepared:
    """The entry's copy of `p`: bound to no session, its subqueries'
    Prepareds copied likewise. `text_key` tells a copy's fallbacks
    which entry to drop."""
    t = copy.copy(p)
    t.session = None
    t._subquery_memo = None
    t.text_key = key
    t.subqueries = tuple((i, _unbind(s, key)) for i, s in p.subqueries)
    return t


def _bind(t: Prepared, session: Session) -> Prepared:
    """A copy of the entry's Prepared of the session's own: run() and
    its fallbacks mutate the handle (_adopt, the subquery memo), so no
    two sessions' threads share one."""
    p = copy.copy(t)
    p.session = session
    p.subqueries = tuple((i, _bind(s, session)) for i, s in t.subqueries)
    return p


class TextPlanMixin:
    """Engine methods for this concern; mixed into exec.engine.Engine
    (all state lives on the Engine instance)."""

    _TEXT_PLAN_MAX = 512

    def _text_plan_init(self) -> None:
        # one cache for every connection, under the same per-tenant
        # budget as the parse cache (sql.exec.plan_cache.tenant_budget)
        self._text_plans = TenantLRU(self._TEXT_PLAN_MAX)
        self._text_lock = threading.Lock()
        self._text_tl = threading.local()
        self._m_text = {
            o: self.metrics.counter(
                f"sql.plan.text.{o}",
                "SELECT texts executed through Engine.execute, by "
                "whether the statement-text cache served their "
                "prepared plan (hit) or they were planned (miss)")
            for o in ("hit", "miss")}
        self._m_text_invalidated = self.metrics.counter(
            "sql.plan.text.invalidated",
            "statement-text cache entries dropped before their next "
            "hit, all causes")
        self._m_text_cause = {
            c: self.metrics.counter(
                f"sql.plan.text.invalidated.{c}",
                "statement-text cache entries dropped, by cause: ddl, "
                "analyze, setting (a cluster setting changed), write "
                "(to a table the text names), evicted (a device table "
                "the entry reads left the resident cache), whole_sort "
                "and uncompacted (the entry's Prepared re-planned)")
            for c in CAUSES}
        self.settings.on_change(
            lambda name, value: self._text_plans_clear("setting"))

    def _text_probe(self, sql: str, session: Session):
        """(key, entry or None) for `sql` in `session`; None where a
        session variable holds a value no key can hold."""
        key = (sql, tuple(sorted(session.vars.values.items())))
        try:
            return key, self._text_plans.get(key)
        except TypeError:
            return None

    def _text_plan_may_serve(self, session: Session) -> bool:
        """Not inside a transaction (its reads see its own writes and
        its snapshot), nor while a composed-CTE capture records."""
        return (session.txn is None and not session.effects
                and self._cte_capture is None
                and not getattr(session, "_cte_depth", 0))

    def _table_gens(self, tables: tuple) -> tuple:
        tds = self.store.tables
        return tuple(getattr(tds.get(t), "generation", None)
                     for t in tables)

    def _text_plan_serve(self, probe: TextProbe,
                         session: Session) -> Optional[Prepared]:
        """The entry's Prepared bound to `session`, or None where it may
        not serve: a transaction or a capture, an entry dropped since
        the probe, a table its text names written since it was kept.
        Its `plan` span marks `build` (the seal check planning does
        first) and `lookup`, tagged text_cache."""
        if not self._text_plan_may_serve(session):
            return None
        ent = probe.entry
        with self.tracer.span("plan"):
            _trc.stage("build")
            self._seal_open_tables()
            _trc.stage("lookup")
            with self._text_lock:
                live = self._text_plans.get(probe.key) is ent
                stale = live and self._table_gens(ent.tables) != ent.gens
                if stale:
                    self._text_plans.pop(probe.key, None)
            if stale:
                self._count_text_drops("write", 1)
            if not live or stale:
                self.tracer.tag(text_cache="miss")
                return None
            prep = _bind(ent.prep, session)
            self.tracer.tag(text_cache="hit", plan_cache="hit")
        self._m_text["hit"].inc()
        # a compiled program was found, as on every hit of the plan
        # cache behind it
        self._m_plan_cache[True].inc()
        return prep

    def _text_plan_keep(self, keep: Optional[tuple], session: Session,
                        sql_text: str, prep: Prepared) -> None:
        """Keep `prep` for the next execution of its text where nothing
        it was prepared from can change under that text (the module's
        docstring). `keep`: (key, binder.volatile_folds() before the
        statement was planned), None for a statement execute() did
        not hand down."""
        if keep is None:
            return
        key, folds = keep
        if not self._text_plan_may_serve(session) \
                or volatile_folds() != folds or prep.as_of is not None \
                or not self._text_plan_holds(prep):
            return
        stmt = self._parse_cache.get(sql_text)
        tables = None if stmt is None else self._stmt_tables(stmt)
        if tables is None:
            return
        tables = tuple(sorted(tables))
        ent = TextPlan(stmt, tables, self._table_gens(tables),
                       _unbind(prep, key))
        prep.text_key = key
        with self._text_lock:
            self._text_plans.max_entries = self._TEXT_PLAN_MAX
            self._text_plans.put(key, ent, self._current_tenant())

    def _text_plan_holds(self, prep: Prepared) -> bool:
        """One resident program over batches the resident-table cache
        holds, its subqueries' too: no paged, spilled or distributed
        verdict, no overlay or host-pruned upload of its own."""
        from .engine import _DistRouter
        with self._device_lock:
            resident = {id(b.sel) for b in self._device_tables.values()}

        def held(p):
            return (p.stream is None and p.spill is None
                    and not isinstance(p.jfn, _DistRouter)
                    and all(id(b.sel) in resident
                            for b in p.scans.values())
                    and all(held(s) for _, s in p.subqueries))
        return held(prep)

    def _text_plans_drop(self, cause: str, key: Optional[tuple] = None,
                         table: Optional[str] = None) -> None:
        """Drop the entry of `key`, or those whose text names `table`."""
        with self._text_lock:
            if key is not None:
                keys = [key] if key in self._text_plans else []
            else:
                keys = [k for k, e in self._text_plans.items()
                        if table in e.tables]
            for k in keys:
                self._text_plans.pop(k, None)
        self._count_text_drops(cause, len(keys))

    def _text_plans_clear(self, cause: str) -> None:
        with self._text_lock:
            n = len(self._text_plans)
            self._text_plans.clear()
        self._count_text_drops(cause, n)

    def _count_text_drops(self, cause: str, n: int) -> None:
        if n:
            self._m_text_invalidated.inc(n)
            self._m_text_cause[cause].inc(n)
