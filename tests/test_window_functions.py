"""Window functions + DISTINCT aggregates.

The colexecwindow / colexec distinct analogue tests (reference:
pkg/sql/logictest/testdata/logic_test/window, distinct_on). The TPU
formulation is one lexsort + cumulative scans per window spec
(ops/window.py); semantics follow PostgreSQL defaults — including
peer-inclusive running frames and last_value's default frame."""

import pytest

from cockroach_tpu.exec.engine import Engine


@pytest.fixture(scope="module")
def eng():
    e = Engine()
    e.execute("CREATE TABLE emp (dept STRING, name STRING, sal INT)")
    e.execute("INSERT INTO emp VALUES "
              "('eng','a',100),('eng','b',200),('eng','c',200),"
              "('ops','d',50),('ops','e',70),('ops','f',NULL)")
    return e


def rows(eng, sql):
    return eng.execute(sql).rows


class TestRanking:
    def test_row_number(self, eng):
        r = dict(rows(eng, "SELECT name, row_number() OVER "
                           "(PARTITION BY dept ORDER BY sal DESC) "
                           "FROM emp"))
        assert r["b"] == 1 and r["a"] == 3
        assert {r["b"], r["c"]} == {1, 2}
        assert r["f"] == 1  # NULLS FIRST on DESC (pg default)

    def test_rank_and_dense_rank(self, eng):
        r = {n: (rk, dr) for n, rk, dr in rows(
            eng, "SELECT name, rank() OVER (PARTITION BY dept "
                 "ORDER BY sal DESC), dense_rank() OVER "
                 "(PARTITION BY dept ORDER BY sal DESC) FROM emp")}
        assert r["b"] == (1, 1) and r["c"] == (1, 1)  # ties share rank
        assert r["a"] == (3, 2)  # rank skips, dense_rank doesn't

    def test_rank_requires_order_by(self, eng):
        from cockroach_tpu.sql.binder import BindError
        with pytest.raises(Exception, match="ORDER BY"):
            rows(eng, "SELECT rank() OVER (PARTITION BY dept) FROM emp")


class TestWindowAggregates:
    def test_partition_total(self, eng):
        r = dict(rows(eng, "SELECT name, sum(sal) OVER "
                           "(PARTITION BY dept) FROM emp"))
        assert r["a"] == 500 and r["d"] == 120
        assert r["f"] == 120  # NULL contributes nothing but sees total

    def test_running_sum_peer_inclusive(self, eng):
        r = dict(rows(eng, "SELECT name, sum(sal) OVER "
                           "(PARTITION BY dept ORDER BY sal) FROM emp"))
        assert r["a"] == 100
        # b and c are ORDER BY peers: both see the peer-group end (pg
        # RANGE UNBOUNDED PRECEDING .. CURRENT ROW includes ties)
        assert r["b"] == 500 and r["c"] == 500

    def test_running_count_avg_minmax(self, eng):
        r = {n: tuple(t) for n, *t in rows(
            eng,
            "SELECT name, "
            "count(sal) OVER (PARTITION BY dept ORDER BY sal), "
            "avg(sal) OVER (PARTITION BY dept ORDER BY sal), "
            "min(sal) OVER (PARTITION BY dept ORDER BY sal), "
            "max(sal) OVER (PARTITION BY dept ORDER BY sal) FROM emp")}
        assert r["e"] == (2, 60.0, 50, 70)
        assert r["f"][0] == 2  # NULL row: count of non-null peers

    def test_count_star_over(self, eng):
        r = dict(rows(eng, "SELECT name, count(*) OVER "
                           "(PARTITION BY dept) FROM emp"))
        assert r["a"] == 3 and r["f"] == 3

    def test_no_partition_whole_table(self, eng):
        r = rows(eng, "SELECT name, sum(sal) OVER () FROM emp")
        assert all(t == 620 for _, t in r)


class TestNavigation:
    def test_lag_lead(self, eng):
        r = {n: (lg, ld) for n, lg, ld in rows(
            eng, "SELECT name, lag(sal) OVER (PARTITION BY dept "
                 "ORDER BY sal), lead(sal) OVER (PARTITION BY dept "
                 "ORDER BY sal) FROM emp")}
        assert r["a"][0] is None          # partition start
        assert r["e"] == (50, None)       # lead hits the NULL row
        assert r["d"] == (None, 70)

    def test_lag_offset(self, eng):
        r = dict(rows(eng, "SELECT name, lag(sal, 2) OVER "
                           "(PARTITION BY dept ORDER BY sal) FROM emp"))
        assert r["a"] is None and r["f"] == 50

    def test_first_last_value(self, eng):
        r = {n: (f, l) for n, f, l in rows(
            eng, "SELECT name, first_value(sal) OVER (PARTITION BY dept "
                 "ORDER BY sal), last_value(sal) OVER (PARTITION BY dept "
                 "ORDER BY sal) FROM emp")}
        assert r["a"] == (100, 100)
        assert r["b"] == (100, 200)  # default frame ends at peer group
        assert r["f"] == (50, None)  # NULL row is its own last peer


class TestWindowMisc:
    def test_window_with_filter(self, eng):
        r = rows(eng, "SELECT name, row_number() OVER (ORDER BY sal) "
                      "FROM emp WHERE sal > 60 ORDER BY 2")
        assert [n for n, _ in r] == ["e", "a", "b", "c"] or \
               [n for n, _ in r] == ["e", "a", "c", "b"]

    def test_window_expr_arithmetic(self, eng):
        r = dict(rows(eng, "SELECT name, rank() OVER (ORDER BY sal) * 10 "
                           "FROM emp WHERE sal IS NOT NULL"))
        assert r["d"] == 10

    def test_window_over_grouped_answers(self, eng):
        # the Window stands above the Aggregate and orders its groups
        # (eng 500, ops 120: sum skips the NULL)
        r = rows(eng, "SELECT dept, sum(sal), rank() OVER "
                      "(ORDER BY sum(sal)) FROM emp GROUP BY dept "
                      "ORDER BY dept")
        assert r == [("eng", 500, 2), ("ops", 120, 1)]

    def test_window_in_cte(self, eng):
        r = rows(eng, "WITH ranked AS (SELECT name, sal, row_number() "
                      "OVER (PARTITION BY dept ORDER BY sal DESC) AS rn "
                      "FROM emp WHERE sal IS NOT NULL) "
                      "SELECT name FROM ranked WHERE rn = 1 ORDER BY name")
        assert [n for (n,) in r] in (["b", "e"], ["c", "e"])


class TestDistinctAggregates:
    def test_grouped_count_sum_distinct(self, eng):
        r = rows(eng, "SELECT dept, count(DISTINCT sal), "
                      "sum(DISTINCT sal) FROM emp GROUP BY dept "
                      "ORDER BY dept")
        assert r == [("eng", 2, 300), ("ops", 2, 120)]

    def test_global_distinct(self, eng):
        assert rows(eng, "SELECT count(DISTINCT sal), avg(DISTINCT sal) "
                         "FROM emp") == [(4, 105.0)]

    def test_distinct_on_string_column(self, eng):
        assert rows(eng, "SELECT count(DISTINCT dept) FROM emp") == [(2,)]

    def test_distinct_and_plain_mix(self, eng):
        r = rows(eng, "SELECT count(DISTINCT sal), count(sal), count(*) "
                      "FROM emp")
        assert r == [(4, 5, 6)]

    def test_distinct_decimal(self, eng):
        e2 = Engine()
        e2.execute("CREATE TABLE p (g INT, m DECIMAL(8,2))")
        e2.execute("INSERT INTO p VALUES (1, 1.50), (1, 1.50), (1, 2.25),"
                   "(2, 1.50)")
        assert e2.execute("SELECT g, sum(DISTINCT m) FROM p GROUP BY g "
                          "ORDER BY g").rows == [(1, 3.75), (2, 1.50)]


def test_ntile():
    from cockroach_tpu.exec.engine import Engine
    e = Engine()
    e.execute("CREATE TABLE wn (g STRING, v INT)")
    e.execute("INSERT INTO wn VALUES ('a',1),('a',2),('a',3),"
              "('a',4),('a',5),('b',10),('b',20)")
    r = e.execute(
        "SELECT v, ntile(2) OVER (ORDER BY v) FROM wn ORDER BY v").rows
    assert [b for _, b in r] == [1, 1, 1, 1, 2, 2, 2]
    r = e.execute("SELECT g, v, ntile(2) OVER "
                  "(PARTITION BY g ORDER BY v) FROM wn "
                  "ORDER BY g, v").rows
    assert [b for _, _, b in r] == [1, 1, 1, 2, 2, 1, 2]


def test_ntile_pg_edge_cases():
    import pytest as _pytest
    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.sql.binder import BindError
    e = Engine()
    e.execute("CREATE TABLE wn2 (v INT)")
    e.execute("INSERT INTO wn2 VALUES (1),(2)")
    # more buckets than rows: sequential 1..size, no gaps (pg)
    r = e.execute(
        "SELECT v, ntile(5) OVER (ORDER BY v) FROM wn2 ORDER BY v").rows
    assert [b for _, b in r] == [1, 2]
    with _pytest.raises(BindError, match="integer"):
        e.execute("SELECT ntile(2.5) OVER (ORDER BY v) FROM wn2")
    with _pytest.raises(BindError, match="integer"):
        e.execute("SELECT ntile('abc') OVER (ORDER BY v) FROM wn2")
    with _pytest.raises(BindError, match="positive"):
        e.execute("SELECT ntile(0) OVER (ORDER BY v) FROM wn2")
