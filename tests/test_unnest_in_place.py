"""What unnesting on one device must not change: EXISTS and NOT EXISTS
as SEMI and ANTI joins keep SQL's answers over NULL keys and duplicate
matches, NOT IN stays what it was (constants of the plan, with SQL's
NULL semantics: it is not unnested), a statement whose derived table
cannot stand in the outer plan says so once and is remembered by its
text, and a user's bind error in a statement with a derived table is
the user's, not a reason to materialize."""

import pytest

from cockroach_tpu.exec.engine import Engine
from cockroach_tpu.sql.binder import BindError
from cockroach_tpu.sql.planner import NotInPlace


@pytest.fixture(scope="module")
def eng():
    e = Engine()
    e.execute("CREATE TABLE a (k INT NOT NULL, g INT NOT NULL)")
    e.execute("CREATE TABLE b (k INT NOT NULL, x INT NOT NULL)")
    e.execute("CREATE TABLE t (y INT NOT NULL)")
    e.execute("CREATE TABLE n (k INT, g INT NOT NULL)")
    e.execute("INSERT INTO n VALUES (1,10),(NULL,20),(9,30)")
    e.execute("INSERT INTO a VALUES (1,10),(2,10),(3,20),(4,20)")
    e.execute("INSERT INTO b VALUES (1,7),(2,8),(2,9)")
    e.execute("INSERT INTO t VALUES (8)")
    # one device's path: nothing is distributed, so subqueries unnest
    # into SEMI / ANTI joins and derived tables are planned in place
    e.one_chip = e.session()
    e.execute("SET distsql = off", e.one_chip)
    return e


def _delta(eng, sql):
    before = dict(eng.metrics.snapshot())
    rows = eng.execute(sql, eng.one_chip).rows
    after = eng.metrics.snapshot()
    return rows, {k: v - before.get(k, 0) for k, v in after.items()
                  if isinstance(v, (int, float))}


CASES = {
    # b has k = 2 twice: a semi-join keeps a's row once
    "exists": (
        "SELECT k FROM a WHERE EXISTS (SELECT * FROM b WHERE b.k = a.k) "
        "ORDER BY k", [(1,), (2,)], {"exec.join.kind.semi": 1}),
    "not_exists": (
        "SELECT k FROM a WHERE NOT EXISTS (SELECT * FROM b "
        "WHERE b.k = a.k AND b.x > 7) ORDER BY k",
        [(1,), (3,), (4,)], {"exec.join.kind.anti": 1}),
    # a NULL key finds nothing: EXISTS drops the row, NOT EXISTS keeps
    "exists_null_key": (
        "SELECT g FROM n WHERE EXISTS (SELECT * FROM b WHERE b.k = n.k) "
        "ORDER BY g", [(10,)], {"exec.join.kind.semi": 1}),
    "not_exists_null_key": (
        "SELECT g FROM n WHERE NOT EXISTS (SELECT * FROM b "
        "WHERE b.k = n.k) ORDER BY g", [(20,), (30,)],
        {"exec.join.kind.anti": 1}),
    # b.x is NULL for a's unmatched rows 3 and 4: NULL NOT IN (8) is
    # NULL, so they go. NOT IN is not unnested: no anti-join
    "not_in_left_join": (
        "SELECT a.k, b.x FROM a LEFT JOIN b ON a.k = b.k "
        "WHERE b.x NOT IN (SELECT y FROM t) ORDER BY a.k, b.x",
        [(1, 7), (2, 9)], {"exec.join.kind.anti": 0}),
    "not_in": (
        "SELECT k FROM a WHERE a.k NOT IN (SELECT k FROM b) ORDER BY k",
        [(3,), (4,)], {"exec.join.kind.anti": 0}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_unnested_tests_keep_sqls_answers(eng, case):
    sql, want, counts = CASES[case]
    rows, delta = _delta(eng, sql)
    assert rows == want
    for counter, n in counts.items():
        assert delta.get(counter, 0) == n, counter


@pytest.fixture
def temps(monkeypatch):
    """The statements that went through _exec_with_temps."""
    seen = []
    real = Engine._exec_with_temps

    def counted(self, sel, session, sql_text):
        seen.append(sql_text)
        return real(self, sel, session, sql_text)

    monkeypatch.setattr(Engine, "_exec_with_temps", counted)
    return seen


def test_a_refused_derived_table_is_remembered_by_its_text(eng, temps,
                                                           monkeypatch):
    # d is joined on a column that is not its GROUP BY key: it cannot
    # be a build side in place, and is materialized
    sql = ("SELECT a.k, d.n FROM a LEFT JOIN (SELECT g, count(*) AS n, "
           "min(k) AS k FROM a GROUP BY g) d ON d.k = a.k ORDER BY a.k")
    want = [(1, 2), (2, None), (3, 2), (4, None)]
    asked = []
    real = Engine._check_derived_build

    def counted(self, join):
        asked.append(join.right.alias)
        return real(self, join)

    monkeypatch.setattr(Engine, "_check_derived_build", counted)
    rows, _ = _delta(eng, sql)
    assert rows == want
    assert temps == [sql]
    assert asked == ["d"] and sql in eng._temps_memo
    rows, _ = _delta(eng, sql)              # decided: not asked again
    assert rows == want
    assert temps == [sql, sql] and asked == ["d"]
    eng.execute("CREATE TABLE later (z INT)")      # DDL forgets it
    assert sql not in eng._temps_memo


def test_a_derived_table_on_its_group_key_is_one_program(eng, temps):
    sql = ("SELECT a.k, d.n FROM a JOIN (SELECT g, count(*) AS n "
           "FROM a GROUP BY g) d ON d.g = a.g ORDER BY a.k")
    rows, delta = _delta(eng, sql)
    assert rows == [(1, 2), (2, 2), (3, 2), (4, 2)]
    assert temps == [] and delta["exec.dispatch.programs"] >= 1
    assert not [t for t in eng.store.tables if t.startswith("__cte")]


def test_a_users_bind_error_is_not_a_reason_to_materialize(eng, temps):
    sql = ("SELECT a.k, nosuch FROM a JOIN (SELECT g, count(*) AS n "
           "FROM a GROUP BY g) d ON d.g = a.g")
    with pytest.raises(BindError, match="nosuch"):
        eng.execute(sql, eng.one_chip)
    assert temps == [] and sql not in eng._temps_memo


def test_not_in_place_is_a_plan_error_of_its_own():
    from cockroach_tpu.sql.planner import PlanError
    assert issubclass(NotInPlace, PlanError)
