"""The statement-text cache (exec/textplan.py): a SELECT text executed
again is served from the Prepared its first execution built, skipping
the parse and the planner. A second execution hits, in its own session
too; the answers are the planned path's, for a report of CTEs, UNION
ALL and ROLLUP shaped like TPC-DS's channel reports and for an IN
subquery, before and after a write to the subquery's table; no entry
serves across DDL, ANALYZE, a SET of a variable of its key, a write to
a table its text names, a whole-sort note or a Compact overflow; none
is kept inside a transaction with writes, AS OF SYSTEM TIME, over a
plan that folded now(), or for a spilled verdict; sessions on threads
never run one Prepared; an entry pins no device batch past a
write; a hit counts a statement's rows as a miss does and marks the
`plan` span's `build` and `lookup` stages."""

import gc
import os
import sys
import threading
import weakref

import numpy as np
import pytest

from cockroach_tpu.exec.engine import Engine
from cockroach_tpu.exec.session import Prepared
from cockroach_tpu.utils import tracing

SS = [(1, 1, 10, 3), (2, 1, 20, 4), (3, 2, 30, 5), (4, 3, 40, 6),
      (1, 2, 5, 1)]
SR = [(1, 1, 2, 1), (3, 2, 3, 2), (2, 3, 1, 7)]
CS = [(1, 1, 7, 1), (4, 2, 8, 2), (2, 2, 9, 3), (3, 1, 1, 1)]
DD = [(1, 1), (2, 0), (3, 1), (4, 1)]

# two channels, each its sales and returns net of each other through a
# CTE over a UNION ALL joined to a date filter, rolled up by channel
# and id: the shape of TPC-DS Q5 and Q80
REPORT = (
    "WITH ssr AS (SELECT s, sum(amt) AS sales, sum(profit) AS profit "
    "FROM (SELECT d, s, amt, profit FROM ss UNION ALL "
    "SELECT d, s, 0 AS amt, 0 - loss AS profit FROM sr) x, dd "
    "WHERE x.d = dd.k AND dd.flag = 1 GROUP BY s), "
    "csr AS (SELECT s, sum(amt) AS sales, sum(profit) AS profit "
    "FROM cs, dd WHERE cs.d = dd.k AND dd.flag = 1 GROUP BY s) "
    "SELECT ch, id, sum(sales) AS sales, sum(profit) AS profit "
    "FROM (SELECT 'store' AS ch, s AS id, sales, profit FROM ssr "
    "UNION ALL SELECT 'catalog' AS ch, s AS id, sales, profit FROM csr) y "
    "GROUP BY ROLLUP (ch, id) ORDER BY ch, id")
IN_SUBQUERY = ("SELECT s, sum(amt) FROM ss WHERE s IN "
               "(SELECT s FROM sr WHERE loss > 1) GROUP BY s ORDER BY s")
PLAIN = "SELECT s, sum(amt) AS t FROM ss GROUP BY s ORDER BY s"


def _want_report(ss, sr, cs, dd):
    on = {k for k, f in dd if f == 1}
    rows = {}
    for ch, facts in (("store", [(d, s, a, p) for d, s, a, p in ss]
                       + [(d, s, 0, -loss) for d, s, _, loss in sr]),
                      ("catalog", cs)):
        for d, s, a, p in facts:
            if d in on:
                for key in ((ch, s), (ch, None), (None, None)):
                    sa, sp = rows.get(key, (0, 0))
                    rows[key] = (sa + a, sp + p)

    def order(k):
        return tuple((v is None, v) for v in k)
    return [k + rows[k] for k in sorted(rows, key=order)]


def _want_in(ss, sr):
    keep = {s for _, s, _, loss in sr if loss > 1}
    sums = {}
    for _, s, a, _ in ss:
        if s in keep:
            sums[s] = sums.get(s, 0) + a
    return sorted(sums.items())


def _want_plain(ss):
    sums = {}
    for _, s, a, _ in ss:
        sums[s] = sums.get(s, 0) + a
    return sorted(sums.items())


def _values(rows):
    return ", ".join(str(tuple(r)) for r in rows)


@pytest.fixture
def eng():
    """The four tables on an engine, and a session whose statements run
    on one device (the suite's eight virtual devices are a mesh)."""
    e = Engine()
    s = e.session()
    s.vars.set("distsql", "off")
    for ddl in ("CREATE TABLE ss (d INT, s INT, amt INT, profit INT)",
                "CREATE TABLE sr (d INT, s INT, ret INT, loss INT)",
                "CREATE TABLE cs (d INT, s INT, amt INT, profit INT)",
                "CREATE TABLE dd (k INT PRIMARY KEY, flag INT)"):
        e.execute(ddl, s)
    for t, rows in (("ss", SS), ("sr", SR), ("cs", CS), ("dd", DD)):
        e.execute(f"INSERT INTO {t} VALUES {_values(rows)}", s)
        e.execute(f"ANALYZE {t}", s)
    e.one_chip = s
    yield e
    e.close()


def _session(e):
    s = e.session()
    s.vars.set("distsql", "off")
    return s


def _counts(e) -> dict:
    return {k: v for k, v in e.metrics.snapshot().items()
            if not isinstance(v, dict)}


def _run(e, sql, s):
    """Rows and the counters the execution moved."""
    before = _counts(e)
    rows = [tuple(r) for r in e.execute(sql, s).rows]
    after = _counts(e)
    return rows, {k: v - before.get(k, 0) for k, v in after.items()}


def _entry(e, sql):
    ents = [v for k, v in e._text_plans.items() if k[0] == sql]
    assert len(ents) == 1
    return ents[0]


def test_a_second_execution_hits_in_its_own_session_too(eng):
    s = eng.one_chip
    want = _want_report(SS, SR, CS, DD)
    rows, d = _run(eng, REPORT, s)
    assert rows == want
    assert (d["sql.plan.text.miss"], d["sql.plan.text.hit"]) == (1, 0)
    assert d["exec.cte.temps"] == 0
    rows, d = _run(eng, REPORT, s)
    assert rows == want
    assert (d["sql.plan.text.miss"], d["sql.plan.text.hit"]) == (0, 1)
    # the compiled program was found: a hit of the plan cache too
    assert (d["sql.plan.cache.hit"], d["sql.plan.cache.miss"]) == (1, 0)
    rows, d = _run(eng, REPORT, _session(eng))
    assert rows == want
    assert (d["sql.plan.text.miss"], d["sql.plan.text.hit"]) == (0, 1)


@pytest.mark.parametrize("text", ["report", "in_subquery"])
@pytest.mark.parametrize("distsql", ["off", "auto"])
def test_answers_equal_the_planned_path_across_a_write(eng, text,
                                                       distsql):
    """`auto` on the suite's mesh binds the IN list into the plan as a
    constant; `off` joins the subquery's table (SEMI). Either way an
    INSERT into the subquery's table is seen by the next execution. The
    planned path is the same text with the cache emptied first."""
    s = eng.session()
    s.vars.set("distsql", distsql)
    sql = REPORT if text == "report" else IN_SUBQUERY

    def want(sr):
        return (_want_report(SS, sr, CS, DD) if text == "report"
                else _want_in(SS, sr))

    def planned():
        eng._text_plans_clear("setting")
        rows, d = _run(eng, sql, s)
        assert d["sql.plan.text.hit"] == 0
        return rows

    for _ in range(3):
        assert _run(eng, sql, s)[0] == want(SR) == planned()
    new = (3, 1, 0, 9)      # a return of id 1, on a date the filter keeps
    eng.execute(f"INSERT INTO sr VALUES {new}", eng.one_chip)
    rows, d = _run(eng, sql, s)
    assert d["sql.plan.text.hit"] == 0
    assert rows == want(SR + [new]) == planned() != want(SR)
    assert _run(eng, sql, s)[0] == want(SR + [new])


@pytest.mark.parametrize("cause,act", [
    ("ddl", lambda e, s: e.execute("CREATE TABLE other (a INT)", s)),
    ("analyze", lambda e, s: e.execute("ANALYZE ss", s)),
    ("write", lambda e, s: e.execute("INSERT INTO ss VALUES (4, 9, 1, 1)",
                                     s)),
    ("set", lambda e, s: e.execute("SET hash_group_capacity = 4096", s)),
])
def test_no_entry_serves_across(eng, cause, act):
    s = eng.one_chip
    for _ in range(2):
        _run(eng, PLAIN, s)
    act(eng, s)
    ss = SS + [(4, 9, 1, 1)] if cause == "write" else SS
    rows, d = _run(eng, PLAIN, s)
    assert rows == _want_plain(ss)
    assert (d["sql.plan.text.miss"], d["sql.plan.text.hit"]) == (1, 0)
    if cause == "set":
        # a key of its own: the other capacity compiles its own program
        assert d["sql.plan.cache.miss"] == 1
    else:
        assert eng.metrics.snapshot()[
            f"sql.plan.text.invalidated.{cause}"] >= 1
    rows, d = _run(eng, PLAIN, s)
    assert rows == _want_plain(ss)
    assert d["sql.plan.text.hit"] == 1


def test_a_whole_sort_note_drops_the_entry():
    """A Sort over a hash Aggregate sized for 100 groups meets 10,000:
    the engine notes the whole sort for the plan, and the entry that
    held the prefix sort goes, so the next execution prepares the
    whole sort as it would without the cache, and that one is kept."""
    eng = Engine()
    eng.execute("CREATE TABLE g (k1 INT8 NOT NULL, k2 INT8 NOT NULL, "
                "v INT8 NOT NULL)")
    rng = np.random.default_rng(5)
    n = 30_000
    k1 = rng.integers(0, 1000, n)
    k2 = rng.integers(0, 1000, n)
    k1[:10_000], k2[:10_000] = np.divmod(np.arange(10_000), 100)
    k1[-1] = k2[-1] = 999
    v = np.arange(n, dtype=np.int64)
    eng.store.insert_columns(
        "g", {"k1": k1.astype(np.int64), "k2": k2.astype(np.int64),
              "v": v}, eng.clock.now())
    s = _session(eng)
    sql = ("select k1, k2, sum(v) as t from g where k1 < 100 and "
           "k2 < 100 group by k1, k2 order by t desc, k1, k2")
    m = (k1 < 100) & (k2 < 100)
    sums: dict = {}
    for x, y, w in zip(k1[m].tolist(), k2[m].tolist(), v[m].tolist()):
        sums[(x, y)] = sums.get((x, y), 0) + w
    want = sorted(((x, y, t) for (x, y), t in sums.items()),
                  key=lambda r: (-r[2], r[0], r[1]))
    try:
        outcomes = []
        for _ in range(3):
            rows, d = _run(eng, sql, s)
            assert rows == want
            outcomes.append((d["sql.plan.text.hit"],
                             d["sql.plan.text.invalidated.whole_sort"],
                             d.get("exec.sort.prefix_short", 0)))
        assert outcomes == [(0, 1, 1), (0, 0, 0), (1, 0, 0)]
    finally:
        eng.close()


def test_a_compact_overflow_drops_the_entry():
    """A filter estimated at a fifth packs at 0.38 of a block, and one
    block keeps three fifths: the statement is answered by the
    uncompacted replan, and its entry goes each time, so every
    execution prepares and overflows as it would without the cache."""
    block = 32768
    n = 8 * block
    eng = Engine()
    eng.execute("CREATE TABLE f (k INT8 NOT NULL, d INT8 NOT NULL, "
                "v INT8 NOT NULL)")
    eng.execute("CREATE TABLE dm (id INT8 PRIMARY KEY, w INT8 NOT NULL)")
    rng = np.random.default_rng(39)
    w = rng.integers(0, 9, 100)
    eng.store.insert_columns(
        "dm", {"id": np.arange(100, dtype=np.int64),
               "w": w.astype(np.int64)}, eng.clock.now())
    d = rng.integers(0, 100, n)
    d[0], d[1] = 0, 99
    hot = slice(3 * block, 4 * block)
    d[hot] = np.where(rng.random(block) < 0.6,
                      rng.integers(0, 20, block),
                      rng.integers(20, 100, block))
    k = rng.integers(0, 100, n)
    v = rng.integers(0, 1000, n)
    eng.store.insert_columns(
        "f", {"k": k.astype(np.int64), "d": d.astype(np.int64),
              "v": v.astype(np.int64)}, eng.clock.now())
    for t in ("f", "dm"):
        eng.execute(f"ANALYZE {t}")
    s = _session(eng)
    sql = ("select count(*), sum(v), sum(dm.w) from f join dm "
           "on dm.id = f.k where f.d < 20")
    m = d < 20
    want = [(int(m.sum()), int(v[m].sum()), int(w[k[m]].sum()))]
    try:
        for _ in range(2):
            rows, dd = _run(eng, sql, s)
            assert rows == want
            assert dd["exec.compact.overflows"] == 1
            assert dd["sql.plan.text.invalidated.uncompacted"] == 1
            assert dd["sql.plan.text.hit"] == 0
    finally:
        eng.close()


@pytest.mark.parametrize("case", ["overlay", "as_of", "now", "spill"])
def test_no_entry_is_kept(eng, case):
    s = eng.one_chip
    sql, want = PLAIN, _want_plain(SS)
    if case == "overlay":
        eng.execute("BEGIN", s)
        eng.execute("INSERT INTO ss VALUES (4, 9, 1, 1)", s)
        want = _want_plain(SS + [(4, 9, 1, 1)])
    elif case == "as_of":
        ts = eng.clock.now().wall
        sql = ("SELECT s, sum(amt) AS t FROM ss AS OF SYSTEM TIME "
               f"{ts} GROUP BY s ORDER BY s")
    elif case == "now":
        sql = ("SELECT s, sum(amt) AS t FROM ss WHERE now() > "
               "TIMESTAMP '2000-01-01 00:00:00' GROUP BY s ORDER BY s")
    else:
        sql = ("SELECT count(*), sum(amt) FROM ss JOIN dd "
               "ON ss.d = dd.k")
        s.vars.set("spill", "on")
        assert eng.stream_verdict(sql, s).startswith("spill")
        on = {kk for kk, _ in DD}
        want = [(sum(1 for r in SS if r[0] in on),
                 sum(r[2] for r in SS if r[0] in on))]
    try:
        for _ in range(3):
            rows, d = _run(eng, sql, s)
            assert rows == want
            assert (d["sql.plan.text.miss"], d["sql.plan.text.hit"]) \
                == (1, 0)
        assert not [k for k in eng._text_plans if k[0] == sql]
    finally:
        if case == "overlay":
            eng.execute("ROLLBACK", s)
        s.vars.set("spill", "auto")


def test_sessions_on_threads_never_share_a_prepared(eng, monkeypatch):
    """More sessions than cores, each on a thread of its own, with the
    interpreter switching threads every 10 us: every execution answers
    right, each runs a Prepared of its own, and none runs the entry's."""
    ran = {}
    real = Prepared.run

    def spy(self, read_ts=None):
        slot = getattr(threading.current_thread(), "slot", None)
        ran.setdefault(slot, []).append(self)
        return real(self, read_ts)

    monkeypatch.setattr(Prepared, "run", spy)
    _run(eng, PLAIN, eng.one_chip)
    n_threads, n_runs = (os.cpu_count() or 4) + 4, 5
    errors = []

    def work(slot):
        threading.current_thread().slot = slot
        s = _session(eng)
        try:
            for _ in range(n_runs):
                rows, d = _run(eng, PLAIN, s)
                assert rows == _want_plain(SS)
        except Exception as e:   # surfaced below, on the test's thread
            errors.append(e)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not [t for t in threads if t.is_alive()]
    assert not errors
    runs = [p for i in range(n_threads) for p in ran[i]]
    assert [len(ran[i]) for i in range(n_threads)] == [n_runs] * n_threads
    assert len({id(p) for p in runs}) == len(runs)
    template = _entry(eng, PLAIN).prep
    assert all(p is not template and p.session is not None for p in runs)
    assert eng.metrics.snapshot()["sql.plan.text.hit"] == len(runs)


def test_an_entry_pins_no_batch_past_a_write(eng):
    s = eng.one_chip
    for _ in range(2):
        _run(eng, PLAIN, s)
    batch = next(iter(_entry(eng, PLAIN).prep.scans.values()))
    ref = weakref.ref(batch)
    del batch
    eng.execute("INSERT INTO ss VALUES (4, 9, 1, 1)", s)
    gc.collect()
    assert ref() is None
    assert not [k for k in eng._text_plans if k[0] == PLAIN]


def test_a_hit_counts_a_statements_rows_as_a_miss_does(eng):
    s = eng.one_chip
    _run(eng, REPORT, s)
    # a key of its own, the same compiled program: a miss that plans
    s.vars.set("extra_float_digits", 1)
    _, miss = _run(eng, REPORT, s)
    _, hit = _run(eng, REPORT, s)
    assert (miss["sql.plan.text.miss"], hit["sql.plan.text.hit"]) == (1, 1)
    assert miss["sql.plan.cache.hit"] == 1
    for c in ("exec.join.probe_rows", "exec.agg.rollup.rows",
              "exec.dispatch.programs", "exec.transfer.h2d.calls"):
        assert miss[c] == hit[c] > 0, c


def test_a_hit_marks_the_plan_spans_build_and_lookup(eng):
    s = eng.one_chip
    _run(eng, REPORT, s)
    tracing.start_collector()
    try:
        eng.execute(REPORT, s)
    finally:
        roots = tracing.stop_collector()
    plans = [p for r in roots for p in r.find_all("plan")]
    assert len(plans) == 1
    assert plans[0].tags["text_cache"] == "hit"
    assert [st[0] for st in plans[0].stages] == ["build", "lookup"]
    assert not [p for r in roots for p in r.find_all("parse")]
