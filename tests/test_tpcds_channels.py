"""TPC-DS's cross-channel reports (benchmark cell
`tpcds_sf1_channels.reports`) on the CPU at a small scale factor, and the
three mechanisms they force: Q5, Q77 and Q80 against the benchmark's own
integer references (benchmark/statements/ch_q*.py over the arrays
benchmark/generators/tpcds_channels.py made), the benchmark's texts on
data with no NULL and the specification's texts with 2 % of the fact
tables' keys and measures NULL; composite-key joins past the packed
direct table (inner, left, semi, anti; the bounded and the sorted form;
a slot component repeated up to the statistics' bound, a key absent
from the build, a build out of its component's order, duplicate keys, a
Compact below the probe); UNION ALL planned on the device (branches of
different dictionaries, a branch with no live row, a literal, a join
pushed into each branch, CTEs read once and twice, a literal every
branch repeats lifted as one argument, two literals equal by chance
kept as two); a composite join's form the same for two draws of its
build; and TPC-H Q9's partsupp join on the bounded form. Each case
asserts the strategy counters it expects."""

import os
import re
import sys

import numpy as np
import pytest

from cockroach_tpu.exec.engine import Engine
from cockroach_tpu.exec.stmtutil import push_joins_into_unions
from cockroach_tpu.sql import ast, parser

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import chref  # noqa: E402
import verify  # noqa: E402
from generators import tpcds_channels as gen  # noqa: E402
from refworker import load_module  # noqa: E402

SF = 0.01
SEED = 2147483911
DATES = ("2000-08-23", "1999-02-11")
# a composite key's span past this takes the bounded or sorted form;
# the reports' pairs at SF0.01 span ~5e5, so the tests lower the cap
SMALL_PACK = 1 << 10
# (class, union branches, bounded joins, cross joins) its plan holds
PLANS = {"ch_q5": (9, 1, 0), "ch_q77": (3, 0, 1), "ch_q80": (3, 3, 0)}
# keys and measures a test blanks, as dsdgen leaves some NULL
NULLABLE = {
    "store_sales": ["ss_sold_date_sk", "ss_store_sk", "ss_promo_sk",
                    "ss_ext_sales_price", "ss_net_profit"],
    "store_returns": ["sr_returned_date_sk", "sr_store_sk",
                      "sr_return_amt", "sr_net_loss"],
    "catalog_sales": ["cs_sold_date_sk", "cs_call_center_sk",
                      "cs_catalog_page_sk", "cs_promo_sk",
                      "cs_ext_sales_price", "cs_net_profit"],
    "catalog_returns": ["cr_returned_date_sk", "cr_call_center_sk",
                        "cr_catalog_page_sk", "cr_return_amount",
                        "cr_net_loss"],
    "web_sales": ["ws_sold_date_sk", "ws_web_page_sk", "ws_web_site_sk",
                  "ws_promo_sk", "ws_ext_sales_price", "ws_net_profit"],
    "web_returns": ["wr_returned_date_sk", "wr_web_page_sk",
                    "wr_return_amt", "wr_net_loss"],
}


def _counters(engine) -> dict:
    flat = {}
    for name, v in engine.metrics.snapshot().items():
        if not isinstance(v, dict):
            flat[name] = v
    return flat


def _delta(engine, before: dict) -> dict:
    after = _counters(engine)
    return {k: v - before.get(k, 0) for k, v in after.items()}


def _engine(pack: int) -> Engine:
    """An engine whose statements run on one device (the suite's eight
    virtual devices are a mesh, and a statement the distributed planner
    may take keeps its temps), composite keys past `pack` slots off the
    packed table."""
    eng = Engine()
    eng.MAX_PACKED_JOIN_SLOTS = pack
    sess = eng.session()
    sess.vars.set("distsql", "off")
    eng.run = lambda sql: eng.execute(sql, sess)
    return eng


def _load(null_share: float):
    eng = _engine(SMALL_PACK)
    rng = np.random.default_rng(17)
    tables = {}
    ts = eng.clock.now()
    for t in gen.TABLE_ORDER:
        if t == "customer_demographics":
            continue
        cols, dicts = gen.generate(t, SF, SEED)
        valid = {}
        if null_share:
            for c in NULLABLE.get(t, []):
                valid[c] = rng.random(len(cols[c])) >= null_share
        tables[t] = (cols, dicts, valid)
        eng.execute(gen.DDL[t])
        for c, v in dicts.items():
            eng.store.set_dictionary(t, c, v)
        eng.store.insert_columns(t, cols, ts, valid=valid or None)
        eng.execute(f"ANALYZE {t}")
    return eng, tables


@pytest.fixture(scope="module")
def plain():
    return _load(0.0)


@pytest.fixture(scope="module")
def nulls():
    return _load(0.02)


def _text(name: str, date: str, spec: bool = False) -> str:
    with open(os.path.join(BENCH, "statements", name + ".sql")) as f:
        sql = f.read().format(date=date)
    if spec:
        # the specification's select list: the rolled-up keys as NULL
        sql = re.sub(r"coalesce\((channel|id), (?:'ALL'|0)\) \1", r"\1",
                     sql)
    return sql


@pytest.mark.parametrize("name", sorted(PLANS))
def test_benchmark_texts_equal_the_references(plain, name):
    eng, tables = plain
    mod = load_module("statements", name)
    branches, bounded, cross = PLANS[name]
    for i, date in enumerate(DATES):
        before = _counters(eng)
        rows = eng.run(_text(name, date)).rows
        d = _delta(eng, before)
        got = [[None if v is None else str(v) for v in r] for r in rows]
        assert verify.compare(mod.COLUMNS, got,
                              mod.reference(tables, {"date": date})) \
            is None
        # one program a class: the date is an argument of it
        assert d["sql.plan.cache.miss"] == (1 if i == 0 else 0)
        assert d["exec.cte.temps"] == 0
        if i == 0:
            assert d["exec.setop.union_all.branches"] == branches
            assert d["exec.join.strategy.bounded"] == bounded
            assert d["exec.join.strategy.cross"] == cross
            assert d["exec.join.strategy.hash"] == 0
            assert d["exec.join.strategy.sorted"] == 0


def _canon(rows: list) -> list:
    def key(r):
        return tuple((v is None, "" if v is None else str(v)) for v in r)
    return sorted(rows, key=key)


@pytest.mark.parametrize("name", sorted(PLANS))
def test_specification_texts_with_nulls(nulls, name):
    """The untouched select lists over NULL keys and measures: a NULL
    group key of the data beside the ROLLUP's NULLs, SUM over NULLs,
    an unmatched outer-join row through its COALESCE."""
    eng, tables = nulls
    mod = load_module("statements", name)
    for date in DATES:
        rows = eng.run(_text(name, date, spec=True)).rows
        want = chref.rollup(mod.channel_rows(tables, {"date": date}),
                            False, None)
        assert len(want) < 100          # no tie at the LIMIT's edge
        got = [[r[0], r[1]] + [None if v is None else round(float(v) * 100)
                               for v in r[2:]] for r in rows]
        assert _canon(got) == _canon(want)
        # NULLs last in ORDER BY channel, id
        assert [r[0] is None for r in got] == sorted(
            r[0] is None for r in got)


def _composite_tables(eng, n_build: int, clustered: bool, sparse: bool,
                      dup: bool = False, trim: slice = slice(None)):
    """Build b(k1, k2, v) of up to four rows a k1 (the slot component),
    each with its own k2, and probe p(k1, k2, f, w) of 2^17 rows that
    hit the build, miss k2 within a slot, or miss the slot. `trim`
    keeps a slice of the build's rows in k1 order."""
    rng = np.random.default_rng(3)
    k1 = np.repeat(np.arange(1, n_build // 4 + 1), 4)[:n_build]
    k2 = (np.tile(np.arange(4), n_build // 4 + 1)[:n_build] * 7
          + k1 % 5 + 100)
    k1, k2 = k1[trim], k2[trim]
    n_build = len(k1)
    if dup:
        k2[1::4] = k2[0::4][:len(k2[1::4])]
    if sparse:
        k1 = k1 * 100_003
        k2 = k2 * 10_007
    order = np.arange(n_build) if clustered else rng.permutation(n_build)
    b = {"k1": k1[order], "k2": k2[order],
         "v": rng.integers(1, 100, n_build)}
    n = 1 << 17
    pick = rng.integers(0, n_build, n)
    pk1, pk2 = b["k1"][pick].copy(), b["k2"][pick].copy()
    miss = rng.random(n)
    pk2[miss < 0.2] += 1                      # within the slot, no match
    pk1[(miss >= 0.2) & (miss < 0.3)] = -5    # no such slot
    p = {"k1": pk1, "k2": pk2, "f": rng.integers(0, 100, n),
         "w": rng.integers(1, 10, n)}
    ts = eng.clock.now()
    for name, cols in (("b", b), ("p", p)):
        eng.execute(f"CREATE TABLE {name} ("
                    + ", ".join(f"{c} INT" for c in cols) + ")")
        eng.store.insert_columns(name, cols, ts)
        eng.execute(f"ANALYZE {name}")
    return b, p


def _expected(b, p, kind: str, filt):
    keep = filt(p)
    pairs: dict = {}
    for i, (a, c) in enumerate(zip(b["k1"].tolist(), b["k2"].tolist())):
        pairs.setdefault((a, c), []).append(int(b["v"][i]))
    count, total = 0, 0
    for i in np.flatnonzero(keep).tolist():
        vs = pairs.get((int(p["k1"][i]), int(p["k2"][i])))
        if kind == "inner" and vs:
            count += len(vs)
            total += sum(vs)
        elif kind == "left":
            count += len(vs) if vs else 1
            total += sum(vs) if vs else 0
        elif kind == "semi" and vs or kind == "anti" and not vs:
            count += 1
            total += int(p["w"][i])
    return count, total


QUERIES = {
    "inner": "SELECT count(*), sum(b.v) FROM p JOIN b ON p.k1 = b.k1 "
             "AND p.k2 = b.k2 WHERE p.f < {f}",
    "left": "SELECT count(*), sum(coalesce(b.v, 0)) FROM p LEFT JOIN b "
            "ON p.k1 = b.k1 AND p.k2 = b.k2 WHERE p.f < {f}",
    "semi": "SELECT count(*), sum(p.w) FROM p WHERE p.f < {f} AND EXISTS "
            "(SELECT 1 FROM b WHERE b.k1 = p.k1 AND b.k2 = p.k2)",
    "anti": "SELECT count(*), sum(p.w) FROM p WHERE p.f < {f} AND NOT "
            "EXISTS (SELECT 1 FROM b WHERE b.k1 = p.k1 AND b.k2 = p.k2)",
}


@pytest.mark.parametrize("kind", sorted(QUERIES))
@pytest.mark.parametrize("form", ["bounded", "bounded_unclustered",
                                  "sorted"])
def test_composite_join_past_the_packed_table(kind, form):
    eng = _engine(1 << 8)
    b, p = _composite_tables(eng, 4000, clustered=form == "bounded",
                             sparse=form == "sorted")
    for f in (100, 7):
        before = _counters(eng)
        got = eng.run(QUERIES[kind].format(f=f)).rows[0]
        d = _delta(eng, before)
        want = _expected(b, p, kind, lambda t: t["f"] < f)
        assert (int(got[0]), int(got[1] or 0)) == want
        if d["sql.plan.cache.miss"]:
            assert d[f"exec.join.strategy.{form.split('_')[0]}"] == 1
            assert d["exec.join.strategy.hash"] == 0


@pytest.mark.parametrize("form", ["bounded", "sorted"])
def test_composite_join_form_outlasts_a_new_draw(form):
    """Two draws of a build whose lowest and highest keys differ by a
    few values, as a returns table's tickets do from seed to seed, size
    one and the same table: the program compiles once for both."""
    forms = []
    for trim in (slice(None), slice(4, -4)):
        eng = _engine(1 << 8)
        b, p = _composite_tables(eng, 4000, clustered=True,
                                 sparse=form == "sorted", trim=trim)
        choose = eng._bounded_or_sorted

        def spy(*args, choose=choose):
            forms.append(choose(*args))
            return forms[-1]

        eng._bounded_or_sorted = spy
        got = eng.run(QUERIES["left"].format(f=100)).rows[0]
        assert (int(got[0]), int(got[1] or 0)) == _expected(
            b, p, "left", lambda t: t["f"] < 100)
    assert len(forms) == 2 and forms[0][0] == form
    assert forms[0] == forms[1]


def test_composite_join_expands_duplicate_keys():
    eng = _engine(1 << 8)
    b, p = _composite_tables(eng, 4000, clustered=True, sparse=False,
                             dup=True)
    before = _counters(eng)
    got = eng.run(QUERIES["inner"].format(f=100)).rows[0]
    d = _delta(eng, before)
    assert (int(got[0]), int(got[1])) == _expected(b, p, "inner",
                                                   lambda t: t["f"] < 100)
    assert d["exec.join.strategy.bounded"] == 1
    assert d["exec.join.strategy.hash"] == 0


def test_composite_join_under_a_compact():
    """A selective dimension probe packs the batch before the composite
    probe: the Compact's capacity and the bounded form together."""
    eng = _engine(1 << 8)
    b, p = _composite_tables(eng, 4000, clustered=True, sparse=False)
    eng.execute("CREATE TABLE dim (f INT PRIMARY KEY, g INT)")
    eng.execute("INSERT INTO dim VALUES " + ", ".join(
        f"({i}, {i})" for i in range(100)))
    eng.execute("ANALYZE dim")
    before = _counters(eng)
    got = eng.run(
        "SELECT p.f, count(*), sum(coalesce(b.v, 0)) FROM p JOIN dim ON "
        "p.f = dim.f LEFT JOIN b ON p.k1 = b.k1 AND p.k2 = b.k2 "
        "WHERE dim.g < 3 GROUP BY p.f ORDER BY p.f").rows
    d = _delta(eng, before)
    assert [tuple(int(v) for v in r) for r in got] == [
        (f,) + _expected(b, p, "left", lambda t, f=f: t["f"] == f)
        for f in range(3)]
    assert d["exec.compact.compacts"] >= 1
    assert d["exec.join.strategy.bounded"] == 1
    assert d["exec.join.strategy.hash"] == 0


@pytest.fixture(scope="module")
def union_eng():
    eng = _engine(1 << 27)
    eng.execute("CREATE TABLE t1 (s STRING, d INT, x INT)")
    eng.execute("CREATE TABLE t2 (s STRING, d INT, x INT)")
    eng.execute("CREATE TABLE dd (k INT PRIMARY KEY, flag INT)")
    eng.execute("INSERT INTO t1 VALUES ('a', 1, 10), ('b', 2, 20), "
                "('c', 3, 30), ('a', 4, 40)")
    eng.execute("INSERT INTO t2 VALUES ('z', 1, 5), ('b', 2, 6), "
                "('y', 4, 7)")
    eng.execute("INSERT INTO dd VALUES (1, 1), (2, 0), (3, 1), (4, 1)")
    for t in ("t1", "t2", "dd"):
        eng.execute(f"ANALYZE {t}")
    return eng


def test_union_all_of_different_dictionaries(union_eng):
    before = _counters(union_eng)
    rows = union_eng.run(
        "SELECT ch, s, sum(x) FROM (SELECT 'one' AS ch, s, x FROM t1 "
        "UNION ALL SELECT 'two' AS ch, s, x FROM t2 UNION ALL "
        "SELECT 'three' AS ch, s, x FROM t2 WHERE x > 1000) u "
        "GROUP BY ROLLUP (ch, s) ORDER BY ch, s").rows
    d = _delta(union_eng, before)
    assert rows == [
        ("one", "a", 50), ("one", "b", 20), ("one", "c", 30),
        ("one", None, 100), ("two", "b", 6), ("two", "y", 7),
        ("two", "z", 5), ("two", None, 18), (None, None, 118)]
    assert d["exec.setop.union_all.branches"] == 3
    assert d["exec.cte.temps"] == 0


def test_a_join_above_a_union_is_pushed_into_each_branch(union_eng):
    sql = ("SELECT s, sum(x) FROM (SELECT s, d, x FROM t1 UNION ALL "
           "SELECT s, d, x FROM t2) u, dd WHERE u.d = dd.k AND "
           "dd.flag = 1 GROUP BY s ORDER BY s")
    pushed = push_joins_into_unions(parser.parse(sql),
                                    union_eng._stored_columns)
    assert [j.table.name for j in pushed.joins] == []
    u = pushed.table.subquery
    assert isinstance(u, ast.SetOp)
    for b in (u.left, u.right):
        assert [j.table.name for j in b.joins] == ["dd"]
    before = _counters(union_eng)
    rows = union_eng.run(sql).rows
    d = _delta(union_eng, before)
    assert rows == [("a", 50), ("c", 30), ("y", 7), ("z", 5)]
    assert d["exec.setop.union_all.branches"] == 2
    # the dimension's column is read above: it stays where it is
    kept = push_joins_into_unions(parser.parse(
        "SELECT dd.flag, sum(x) FROM (SELECT d, x FROM t1 UNION ALL "
        "SELECT d, x FROM t2) u, dd WHERE u.d = dd.k GROUP BY dd.flag"),
        union_eng._stored_columns)
    assert [j.table.name for j in kept.joins] == ["dd"]


def test_ctes_read_once_are_planned_in_place(union_eng):
    before = _counters(union_eng)
    rows = union_eng.run(
        "WITH a AS (SELECT s, sum(x) AS tot FROM t1 GROUP BY s), "
        "b AS (SELECT s, sum(x) AS tot FROM t2 GROUP BY s) "
        "SELECT s, sum(tot) FROM (SELECT s, tot FROM a UNION ALL "
        "SELECT s, tot FROM b) u GROUP BY s ORDER BY s").rows
    d = _delta(union_eng, before)
    assert rows == [("a", 50), ("b", 26), ("c", 30), ("y", 7), ("z", 5)]
    assert d["exec.cte.temps"] == 0
    assert d["exec.setop.union_all.branches"] == 2
    # a CTE read twice keeps the temps
    before = _counters(union_eng)
    rows = union_eng.run(
        "WITH a AS (SELECT s, sum(x) AS tot FROM t1 GROUP BY s) "
        "SELECT x.s, x.tot + y.tot FROM a AS x JOIN a AS y ON x.s = y.s "
        "ORDER BY x.s").rows
    assert rows == [("a", 100), ("b", 40), ("c", 60)]
    assert _delta(union_eng, before)["exec.cte.temps"] > 0


def test_tpch_q9_takes_the_bounded_join():
    from cockroach_tpu.models import tpch
    eng = _engine(1 << 10)
    tpch.load(eng, sf=0.01, rows=20_000, tables=tpch.ALL_TABLES)
    before = _counters(eng)
    got = eng.run(tpch.Q9).rows
    d = _delta(eng, before)
    want = tpch.ref_q9(tpch.gen_lineitem(0.01, rows=20_000),
                       tpch.gen_orders(0.01), tpch.gen_supplier(0.01),
                       tpch.gen_part(0.01), tpch.gen_partsupp(0.01))
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert (str(g[0]), g[1]) == (w[0], w[1])
        assert g[2] == pytest.approx(w[2], abs=1e-2)
    assert d["exec.join.strategy.bounded"] + \
        d["exec.join.strategy.sorted"] >= 1
    assert d["exec.join.strategy.hash"] == 0


def test_plan_span_carries_union_branches_and_join_forms(union_eng):
    from cockroach_tpu.utils import tracing

    tracing.start_collector()
    try:
        union_eng.run("SELECT s, count(*) FROM (SELECT s, d FROM t1 UNION "
                      "ALL SELECT s, d FROM t2 UNION ALL SELECT s, d FROM "
                      "t1) u, dd WHERE u.d = dd.k AND dd.flag = 0 "
                      "GROUP BY s ORDER BY s")
    finally:
        roots = tracing.stop_collector()

    def plans(span):
        if span.name == "plan":
            yield span.tags
        for c in span.children:
            yield from plans(c)

    tags = [t for r in roots for t in plans(r)]
    assert [(t["union_branches"], t["join_strategy"]) for t in tags] \
        == [(3, "direct:3")]


def test_a_literal_every_branch_repeats_is_one_argument(union_eng):
    """A report's window in each of its CTEs and branches: twenty
    occurrences of two values are two arguments of one program, so a
    second window finds that program (past sixteen distinct arguments
    a plan keeps its literals)."""
    def sql(lo, hi):
        branch = f"SELECT s FROM t1 WHERE x > {lo} AND x < {hi}"
        return ("SELECT s, count(*) FROM (" + " UNION ALL ".join(
            [branch] * 10) + ") u GROUP BY s ORDER BY s")

    before = _counters(union_eng)
    assert union_eng.run(sql(15, 45)).rows == [("a", 10), ("b", 10),
                                               ("c", 10)]
    assert union_eng.run(sql(5, 25)).rows == [("a", 10), ("b", 10)]
    d = _delta(union_eng, before)
    assert (d["sql.plan.cache.miss"], d["sql.plan.cache.hit"]) == (1, 1)


def test_two_literals_equal_by_chance_are_two_arguments(union_eng):
    """Under sixteen occurrences each literal keeps an argument of its
    own: a parameter set in which two of them happen to be equal finds
    the program of the sets in which they differ."""
    def sql(a, b):
        return (f"SELECT s, count(*) FROM t1 WHERE d < {a} AND x > {b} "
                "GROUP BY s ORDER BY s")

    before = _counters(union_eng)
    assert union_eng.run(sql(4, 15)).rows == [("b", 1), ("c", 1)]
    assert union_eng.run(sql(3, 3)).rows == [("a", 1), ("b", 1)]
    d = _delta(union_eng, before)
    assert (d["sql.plan.cache.miss"], d["sql.plan.cache.hit"]) == (1, 1)
