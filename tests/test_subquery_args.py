"""An uncorrelated scalar subquery's result is an argument of the
compiled program, not a constant in it: the subquery is a prepared
statement of its own, run at every dispatch at the dispatch's read
timestamp. One program whatever the subquery reads (TPC-H Q22's
average, here as the spec prints it and in small), SQL's NULL for no
row, one snapshot for both reads, the `subquery` span beside `plan` and
`dispatch`, and the old form (a constant of the plan, counted
`exec.subquery.inlined`) wherever no filter holds the subquery."""

import pytest

from cockroach_tpu.exec.engine import Engine
from cockroach_tpu.exec.session import Prepared
from cockroach_tpu.models import tpch
from cockroach_tpu.utils import tracing

ABOVE_AVG = ("SELECT count(*) AS n, sum(v) AS s FROM t "
             "WHERE v > (SELECT avg(v) FROM t WHERE v > 0)")


def _counters(eng) -> dict:
    return {k: v for k, v in eng.metrics.snapshot().items()
            if isinstance(v, (int, float))}


def _delta(eng, fn):
    before = _counters(eng)
    out = fn()
    after = _counters(eng)
    return out, {k: v - before.get(k, 0) for k, v in after.items()}


@pytest.fixture
def eng():
    e = Engine()
    e.execute("CREATE TABLE t (k INT NOT NULL, v DECIMAL(15,2) NOT NULL)")
    e.execute("INSERT INTO t VALUES (1,10.00),(2,20.00),(3,30.00),"
              "(4,-5.00)")
    e.one_chip = e.session()
    e.execute("SET distsql = off", e.one_chip)
    yield e
    e.close()


def test_prepared_once_new_rows_same_program(eng):
    prep = eng.prepare(ABOVE_AVG, eng.one_chip)
    assert isinstance(prep, Prepared) and len(prep.subqueries) == 1
    first, d1 = _delta(eng, prep.run)
    assert [tuple(r) for r in first.rows] == [(1, 30.0)]   # avg 20
    assert d1["exec.subquery.args"] == 1
    assert d1["exec.subquery.inlined"] == 0
    # the table changes: the average is 15 now, and 20 is above it
    eng.execute("UPDATE t SET v = 0.00 WHERE k = 1")
    eng.execute("UPDATE t SET v = 10.00 WHERE k = 3")
    jfn = prep.jfn
    again, d2 = _delta(eng, prep.run)
    assert [tuple(r) for r in again.rows] == [(1, 20.0)]
    assert prep.jfn is jfn                  # the refresh found it
    assert d2.get("exec.compile.cache_miss", 0) == 0
    assert d2.get("sql.plan.cache.miss", 0) == 0
    assert d2["exec.subquery.args"] == 1
    assert d2["exec.subquery.inlined"] == 0


def test_each_literal_and_each_load_share_one_program(eng):
    eng.execute(ABOVE_AVG, eng.one_chip)
    _, d = _delta(eng, lambda: eng.execute(
        ABOVE_AVG.replace("v > 0", "v > 5"), eng.one_chip))
    assert d.get("sql.plan.cache.miss", 0) == 0
    assert d.get("exec.compile.cache_miss", 0) == 0


def test_a_snapshot_read_sees_its_own_timestamps_average(eng):
    prep = eng.prepare(ABOVE_AVG, eng.one_chip)
    old = eng.clock.now()
    assert [tuple(r) for r in prep.run().rows] == [(1, 30.0)]
    eng.execute("INSERT INTO t VALUES (5,1000.00)")    # avg 265 now
    assert [tuple(r) for r in prep.run().rows] == [(1, 1000.0)]
    # a reader that began before the write: both its reads, the
    # subquery's and the statement's, are of the old rows
    assert [tuple(r) for r in prep.run(old).rows] == [(1, 30.0)]
    res = eng.execute(f"SELECT count(*) FROM t AS OF SYSTEM TIME "
                      f"{old.wall} WHERE v > (SELECT avg(v) FROM t "
                      f"WHERE v > 0)")
    assert [tuple(r) for r in res.rows] == [(1,)]


def test_what_was_read_is_kept_for_one_timestamp_only(eng):
    prep = eng.prepare(ABOVE_AVG, eng.one_chip)
    ts = eng.clock.now()
    _, d1 = _delta(eng, lambda: prep.run(ts))
    _, d2 = _delta(eng, lambda: prep.run(ts))     # the same snapshot
    _, d3 = _delta(eng, prep.run)                 # a later one
    # the subquery's own program ran for the first and the third
    assert d1["exec.dispatch.programs"] > d2["exec.dispatch.programs"]
    assert d3["exec.dispatch.programs"] == d1["exec.dispatch.programs"]
    hist = eng.metrics.histogram("exec.subquery.seconds")
    assert hist.value()["count"] == 2


def test_no_row_is_null_and_keeps_nothing(eng):
    sql = ("SELECT count(*) FROM t WHERE v > "
           "(SELECT avg(v) FROM t WHERE v > 1000)")
    res, d = _delta(eng, lambda: eng.execute(sql, eng.one_chip))
    assert [tuple(r) for r in res.rows] == [(0,)]
    assert d["exec.subquery.args"] == 1
    # rows arrive: the same program, a value this time
    eng.execute("INSERT INTO t VALUES (6,2000.00),(7,4000.00)")
    res, d = _delta(eng, lambda: eng.execute(sql, eng.one_chip))
    assert [tuple(r) for r in res.rows] == [(1,)]
    assert d.get("exec.compile.cache_miss", 0) == 0


def test_more_than_one_row_is_an_error(eng):
    from cockroach_tpu.sql.binder import BindError
    with pytest.raises((BindError, Exception), match="more than one row"):
        eng.execute("SELECT count(*) FROM t WHERE v > (SELECT v FROM t)",
                    eng.one_chip)


def test_outside_a_filter_the_value_is_a_constant_of_the_plan(eng):
    sql = "SELECT k, v - (SELECT min(v) FROM t) AS d FROM t ORDER BY k"
    res, d = _delta(eng, lambda: eng.execute(sql, eng.one_chip))
    assert [tuple(r) for r in res.rows] == [
        (1, 15.0), (2, 25.0), (3, 35.0), (4, 0.0)]
    assert d["exec.subquery.inlined"] == 1 and d["exec.subquery.args"] == 0
    # and an IN list or an EXISTS is one as it was
    _, d = _delta(eng, lambda: eng.execute(
        "SELECT count(*) FROM t WHERE k IN (SELECT k FROM t WHERE v > 15)",
        eng.one_chip))
    assert d["exec.subquery.inlined"] == 1 and d["exec.subquery.args"] == 0


def test_in_a_transaction_the_subquery_reads_the_transactions_rows(eng):
    s = eng.session()
    eng.execute("SET distsql = off", s)
    eng.execute("BEGIN", s)
    eng.execute("INSERT INTO t VALUES (8,1000.00)", s)
    res = eng.execute(ABOVE_AVG, s)      # avg of 10,20,30,1000 = 265
    assert [tuple(r) for r in res.rows] == [(1, 1000.0)]
    eng.execute("ROLLBACK", s)
    assert [tuple(r) for r in eng.execute(ABOVE_AVG, s).rows] \
        == [(1, 30.0)]


def test_the_subquery_span_stands_beside_plan_and_dispatch(eng):
    eng.execute(ABOVE_AVG, eng.one_chip)
    tracing.start_collector()
    try:
        eng.execute(ABOVE_AVG, eng.one_chip)
    finally:
        roots = tracing.stop_collector()

    def walk(s, parent=None):
        yield s, parent
        for c in s.children:
            yield from walk(c, s)

    spans = [(s, p) for r in roots for s, p in walk(r)]
    (sub, parent), = [(s, p) for s, p in spans if s.name == "subquery"]
    assert sub.tags == {"rows": 1, "cache": "miss"}
    siblings = [c.name for c in parent.children]
    assert siblings.index("plan") < siblings.index("subquery") \
        < siblings.index("dispatch")
    # its own dispatch and pull are beneath it, not beneath the
    # statement's `dispatch`
    assert {c.name for c in sub.children} >= {"dispatch", "materialize"}
    outer, = [c for c in parent.children if c.name == "dispatch"]
    assert all(s.name != "subquery" for s, _ in walk(outer))


def test_q22_as_the_spec_prints_it():
    e = Engine()
    try:
        sess = e.session()
        e.execute("SET distsql = off", sess)
        tpch.load(e, 0.01, tables=("customer", "orders"))
        data = {"customer": tpch.gen_customer(0.01),
                "orders": tpch.gen_orders(0.01)}
        want = tpch.ref_q22(data["customer"], data["orders"])
        res, d1 = _delta(e, lambda: e.execute(tpch.Q22, sess))
        assert [(c, n) for c, n, _ in res.rows] \
            == [(c, n) for c, n, _ in want]
        assert d1["exec.subquery.inlined"] == 0
        assert d1["exec.subquery.args"] == 1
        # every rich customer loses his balance: the average falls,
        # other rows pass, the programs stay
        e.execute("UPDATE customer SET c_acctbal = 1.00 "
                  "WHERE c_acctbal > 9000.00")
        cust = dict(data["customer"])
        cust["c_acctbal"] = cust["c_acctbal"].copy()
        cust["c_acctbal"][cust["c_acctbal"] > 9000.0] = 1.0
        want2 = tpch.ref_q22(cust, data["orders"])
        assert want2 != want
        res, d2 = _delta(e, lambda: e.execute(tpch.Q22, sess))
        assert [(c, n) for c, n, _ in res.rows] \
            == [(c, n) for c, n, _ in want2]
        assert [float(s) for _, _, s in res.rows] == pytest.approx(
            [s for _, _, s in want2], abs=0.005)
        assert d2.get("exec.compile.cache_miss", 0) == 0
        assert d2.get("sql.plan.cache.miss", 0) == 0
        assert d2["exec.subquery.inlined"] == 0
    finally:
        e.close()
