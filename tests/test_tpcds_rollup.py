"""TPC-DS's subtotal reports (workload/tpcds.py) through the engine:
Q27, Q36, Q67 and Q89 against their numpy oracles on seeded data, with
the kernel allowed (`auto`) and not (`off`); GROUP BY ROLLUP / GROUPING
SETS and grouping() against answers computed here (a NULL in the data
against a rolled-up NULL, the grand total of no rows, AVG through the
combine step, the dense and the sorted layouts, NULL keys and values,
slots an estimate sized too small); the sorted layout's networks;
windows over grouped queries; what exec.agg.grouping_sets and
exec.agg.rollup.network count a plan; and a plain GROUP BY past the
dense bound whose keys pack, on the sorted layout as one set or on the
hash table by its batch's rows (Q89's among them), and what
exec.agg.sorted.* count."""

import math
from fractions import Fraction

import numpy as np
import pytest

from cockroach_tpu.exec.engine import Engine
from cockroach_tpu.workload import tpcds

SF = 0.01
SEED = 11


def _seeded():
    data = tpcds.generate(SF, SEED, null_share=0.02)
    ss = data["store_sales"][0]
    rng = np.random.default_rng(5)
    # plant rows where the narrowest filters look: Q89's six (category,
    # class) pairs in 1999, Q27's demographic in 2002
    it, itd, _ = data["item"]
    cat = np.asarray(itd["i_category"], dtype=object)[it["i_category"]]
    cls = np.asarray(itd["i_class"], dtype=object)[it["i_class"]]
    q89 = np.flatnonzero(np.isin(cat, ["Books", "Men", "Sports"])
                         & np.isin(cls, ["computers", "shirts", "football"]))
    assert len(q89)
    rows = slice(0, 600)
    ss["ss_item_sk"][rows] = it["i_item_sk"][rng.choice(q89, 600)]
    lo = tpcds.date_sk(tpcds.datetime.date(1999, 1, 1))
    ss["ss_sold_date_sk"][rows] = rng.integers(lo, lo + 365, 600)
    cd = data["customer_demographics"][0]
    want = np.flatnonzero((cd["cd_gender"] == 0) & (cd["cd_marital_status"]
                                                    == 1)
                          & (cd["cd_education_status"] == 2))
    rows = slice(600, 900)
    ss["ss_cdemo_sk"][rows] = cd["cd_demo_sk"][rng.choice(want, 300)]
    lo = tpcds.date_sk(tpcds.datetime.date(2002, 1, 1))
    ss["ss_sold_date_sk"][rows] = rng.integers(lo, lo + 365, 300)
    return data


@pytest.fixture(scope="module")
def loaded():
    data = _seeded()
    eng = Engine()
    tpcds.load(eng, tables=data)
    return eng, data


def _same(got, want) -> bool:
    if want is None or got is None:
        return got is None and want is None
    if isinstance(want, Fraction):
        return math.isclose(float(got), float(want), rel_tol=1e-12,
                            abs_tol=1e-12)
    return got == want


def _check(rows, want):
    assert len(rows) == len(want)
    for i, (g, w) in enumerate(zip(rows, want)):
        assert len(g) == len(w), i
        for j, (a, b) in enumerate(zip(g, w)):
            assert _same(a, b), (i, j, g, w)


@pytest.mark.parametrize("kernel", ["auto", "off"])
@pytest.mark.parametrize("name", ["q27", "q36", "q67", "q89"])
def test_query_equals_its_oracle(loaded, name, kernel):
    eng, data = loaded
    eng.execute(f"SET pallas_groupagg = {kernel}")
    try:
        rows = eng.execute(tpcds.query(name)).rows
    finally:
        eng.execute("SET pallas_groupagg = auto")
    want = tpcds.ORACLES[name](data, **tpcds.QUALIFICATION[name])
    assert len(want) >= 20, name
    _check(rows, want)


@pytest.fixture(scope="module")
def small():
    eng = Engine()
    eng.execute("CREATE TABLE t (a STRING, b INT, x INT, y DECIMAL(7,2))")
    eng.execute("INSERT INTO t VALUES ('p', 1, 10, 1.50), ('p', 2, 20, "
                "2.25), ('q', 1, 5, NULL), (NULL, 1, 7, 3.00), "
                "(NULL, NULL, 1, 0.75), ('q', NULL, NULL, 4.00)")
    eng.execute("CREATE TABLE e (a STRING, x INT)")
    eng.execute("ANALYZE t")
    return eng


ROWS = [("p", 1, 10, Fraction(150, 100)), ("p", 2, 20, Fraction(225, 100)),
        ("q", 1, 5, None), (None, 1, 7, Fraction(3)),
        (None, None, 1, Fraction(3, 4)), ("q", None, None, Fraction(4))]


def _expect(sets):
    """(a, b, grouping(a), grouping(b), sum(x), count(*), avg(y)) a
    group of every set, computed here row by row."""
    out = []
    for s in sets:
        groups: dict = {}
        for r in ROWS:
            key = tuple(r[j] if j in s else None for j in (0, 1))
            groups.setdefault(key, []).append(r)
        for key, rs in groups.items():
            xs = [r[2] for r in rs if r[2] is not None]
            ys = [r[3] for r in rs if r[3] is not None]
            out.append(key + (0 if 0 in s else 1, 0 if 1 in s else 1,
                              sum(xs) if xs else None, len(rs),
                              sum(ys) / len(ys) if ys else None))
    return out


def _sort_key(r):
    return tuple((v is None, v if v is not None else 0) for v in r[:4])


@pytest.mark.parametrize("group_by,sets", [
    ("rollup(a, b)", [(0, 1), (0,), ()]),
    ("grouping sets ((a, b), (b), ())", [(0, 1), (1,), ()]),
    ("a, rollup(b)", [(0, 1), (0,)]),
])
def test_grouping_sets_tell_a_null_from_a_rolled_up_key(small, group_by,
                                                        sets):
    got = small.execute(
        "SELECT a, b, grouping(a), grouping(b), sum(x), count(*), avg(y) "
        f"FROM t GROUP BY {group_by}").rows
    want = _expect(sets)
    _check(sorted(got, key=_sort_key), sorted(want, key=_sort_key))
    # a NULL of the data keeps grouping() 0; a rolled-up one reads 1
    assert any(r[0] is None and r[2] == 0 for r in got)
    if sets[-1] == ():
        assert any(r[0] is None and r[2] == 1 for r in got)


def test_grouping_is_a_bit_a_key(small):
    got = small.execute(
        "SELECT grouping(a, b), count(*) FROM t GROUP BY rollup(a, b) "
        "ORDER BY 1, 2").rows
    assert {g for g, _ in got} == {0, 1, 3}
    assert (3, 6) in got


def test_grand_total_of_no_rows(small):
    got = small.execute("SELECT a, grouping(a), count(*), sum(x) FROM e "
                        "GROUP BY rollup(a)").rows
    assert got == [(None, 1, 0, None)]


def test_avg_through_the_combine_step(small):
    rolled = small.execute("SELECT avg(y), avg(x) FROM t GROUP BY "
                           "rollup(a) HAVING grouping(a) = 1").rows
    direct = small.execute("SELECT avg(y), avg(x) FROM t").rows
    assert len(rolled) == 1
    assert all(math.isclose(a, b, rel_tol=1e-12)
               for a, b in zip(rolled[0], direct[0]))


@pytest.mark.parametrize("sql,match", [
    ("SELECT a, count(*) FROM t GROUP BY cube(a, b)", "CUBE"),
    ("SELECT a, count(DISTINCT x) FROM t GROUP BY rollup(a)", "DISTINCT"),
    ("SELECT grouping(x) FROM t GROUP BY rollup(a)", "GROUP BY keys"),
])
def test_unsupported_forms_are_refused(small, sql, match):
    with pytest.raises(Exception, match=match):
        small.execute(sql)


@pytest.fixture(scope="module")
def wide():
    """Keys whose dense domain is past the planner's bound, so the
    grouping sets take the sorted layout."""
    eng = Engine()
    eng.execute("CREATE TABLE w (k1 INT, k2 INT, k3 INT, v INT)")
    rng = np.random.default_rng(3)
    n = 3000
    k1 = rng.integers(0, 40, n)
    k2 = rng.integers(0, 900, n)
    k3 = rng.integers(0, 50, n)
    v = rng.integers(-1000, 1000, n)
    eng.store.insert_columns("w", {"k1": k1, "k2": k2, "k3": k3, "v": v},
                             eng.clock.now())
    eng.execute("ANALYZE w")
    return eng, (k1, k2, k3, v)


def test_sorted_layout_equals_each_set_alone(wide):
    eng, (k1, k2, k3, v) = wide
    q = ("SELECT k1, k2, k3, grouping(k1, k2, k3), sum(v), count(*), "
         "min(v), max(v) FROM w GROUP BY rollup(k1, k2, k3)")
    plan = eng.execute("EXPLAIN " + q).rows
    assert any("sets=" in str(r) for r in plan)
    got = eng.execute(q).rows
    want = []
    keys = [k1, k2, k3]
    for m in range(3, -1, -1):
        groups: dict = {}
        for i in range(len(v)):
            groups.setdefault(tuple(int(keys[j][i]) for j in range(m)),
                              []).append(int(v[i]))
        for key, vs in groups.items():
            want.append(key + (None,) * (3 - m)
                        + ((1 << (3 - m)) - 1, sum(vs), len(vs), min(vs),
                           max(vs)))
    _check(sorted(got, key=_sort_key), sorted(want, key=_sort_key))


def _nulls_table(n, seed):
    """A table of `n` rows whose keys and values are NULL now and then,
    with a float column: keys past the dense bound."""
    eng = Engine()
    eng.execute("CREATE TABLE wn (k1 INT, k2 INT, k3 INT, v INT, f FLOAT8)")
    rng = np.random.default_rng(seed)
    cols = {"k1": rng.integers(0, 40, n), "k2": rng.integers(0, 900, n),
            "k3": rng.integers(0, 50, n), "v": rng.integers(-1000, 1000, n),
            "f": rng.normal(size=n) * 100}
    valid = {c: rng.random(n) > 0.05 for c in cols}
    eng.store.insert_columns("wn", cols, eng.clock.now(), valid=valid)
    eng.execute("ANALYZE wn")
    rows = [tuple(None if not valid[c][i] else
                  (float(cols[c][i]) if c == "f" else int(cols[c][i]))
                  for c in cols) for i in range(n)]
    return eng, rows


NULLS_Q = ("SELECT k1, k2, k3, grouping(k1, k2, k3), sum(v), count(v), "
           "count(*), avg(v), sum(f), avg(f), min(f), max(v) FROM wn "
           "GROUP BY {}")


def _each_set_alone(rows, sets):
    """NULLS_Q's rows, a set at a time, computed here row by row."""
    out = []
    for s in sets:
        groups: dict = {}
        for r in rows:
            groups.setdefault(tuple(r[j] if j in s else None
                                    for j in range(3)), []).append(r)
        for key, rs in groups.items():
            vs = [r[3] for r in rs if r[3] is not None]
            fs = [r[4] for r in rs if r[4] is not None]
            out.append(key + (
                sum(1 << (2 - j) for j in range(3) if j not in s),
                sum(vs) if vs else None, len(vs), len(rs),
                Fraction(sum(vs), len(vs)) if vs else None,
                math.fsum(fs) if fs else None,
                math.fsum(fs) / len(fs) if fs else None,
                min(fs) if fs else None, max(vs) if vs else None))
    return out


def _check_close(got, want):
    """_check, with the float sums (taken in another order) close."""
    got = sorted(got, key=_sort_key)
    want = sorted(want, key=_sort_key)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g[:7] == w[:7] and _same(g[7], w[7]), (g, w)
        for a, b in zip(g[8:], w[8:]):
            assert (a is None) == (b is None) and (
                a is None or math.isclose(a, b, rel_tol=1e-9,
                                          abs_tol=1e-9)), (g, w)


@pytest.mark.parametrize("group_by,sets", [
    ("rollup(k1, k2, k3)", [(0, 1, 2), (0, 1), (0,), ()]),
    ("grouping sets ((k1, k2, k3), (k1), ())", [(0, 1, 2), (0,), ()]),
])
def test_sorted_layout_with_nulls_equals_each_set_alone(group_by, sets):
    eng, rows = _nulls_table(3000, 7)
    before = eng.metrics.snapshot()
    got = eng.execute(NULLS_Q.format(group_by)).rows
    after = eng.metrics.snapshot()
    # the sorted layout: every set through the network; the float sum,
    # the float average's sum and the two extremes segmented
    assert after["exec.agg.rollup.network"] - before[
        "exec.agg.rollup.network"] == len(sets)
    assert after["exec.agg.rollup.segmented"] - before[
        "exec.agg.rollup.segmented"] == 4
    _check_close(got, _each_set_alone(rows, sets))
    # a NULL of the data keeps grouping() 0; a rolled-up one reads 1
    assert any(r[0] is None and r[3] == 0 for r in got)


def test_sorted_layout_past_its_slots_replans_exactly():
    """Slots sized from an estimate that proves low (8,192 for some
    11,000 groups over the sets): the top-k sentinel fires, the plan
    with every set's whole slots answers, and keeps answering."""
    eng, rows = _nulls_table(6000, 9)
    eng._estimate_set_groups = lambda agg: 10.0
    sets = [(0, 1, 2), (0, 1), (0,), ()]
    want = _each_set_alone(rows, sets)
    assert len(want) > 8192
    q = NULLS_Q.format("rollup(k1, k2, k3)")
    _check_close(eng.execute(q).rows, want)
    assert len(eng._whole_sorts) == 1
    _check_close(eng.execute(q).rows, want)


def test_sets_each_plan_counts(loaded):
    # an engine of its own, whose plans are all traced anew
    eng = Engine()
    tpcds.load(eng, tables=loaded[1])
    counters = ("exec.agg.grouping_sets", "exec.agg.rollup.network")
    # Q67's nine sets are runs of one sort, packed by the networks;
    # Q27's and Q36's are dense levels, Q89 has none
    for name, n, net in (("q27", 3, 0), ("q36", 3, 0), ("q67", 9, 9),
                         ("q89", 0, 0)):
        before = eng.metrics.snapshot()
        eng.execute(tpcds.query(name))
        after = eng.metrics.snapshot()
        assert [after[c] - before[c] for c in counters] == [n, net], name


def test_sorted_sets_scatter_nothing_after_the_sort():
    """Q67's shape (eight keys, nine sets, 1.6 M rows into 2^20 slots)
    with exact states only: one sort, no scatter, and no gather but the
    permutation's of the code and of each state's data and validity."""
    import jax
    import jax.numpy as jnp

    from cockroach_tpu.exec import rollup
    from cockroach_tpu.sql.bound import BoundAgg
    from cockroach_tpu.sql.types import INT8

    n, k = 1605632, 8
    dims = [10, 100, 1000, 18000, 5, 4, 12, 12]
    sets = [tuple(range(m)) for m in range(k, -1, -1)]
    aggs = [BoundAgg("sum_int", None, INT8), BoundAgg("count", None, INT8)]

    def f(keys, states, sel):
        return rollup.sorted_sets(sets, [(d, 0) for d in dims],
                                  [f"k{j}" for j in range(k)], keys, states,
                                  aggs, sel, 1 << 20)

    def col(dt):
        return jax.ShapeDtypeStruct((n,), dt)

    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(f)(
            [(col(jnp.int32), col(jnp.bool_)) for _ in range(k)],
            [(col(jnp.int64), col(jnp.bool_)) for _ in aggs], col(jnp.bool_))
    prims = []

    def walk(j):
        for e in j.eqns:
            prims.append(e.primitive.name)
            for p in e.params.values():
                for q in p if isinstance(p, (list, tuple)) else [p]:
                    inner = getattr(q, "jaxpr", q)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jaxpr.jaxpr)
    assert prims.count("sort") == 1
    assert not [p for p in prims if p.startswith("scatter")]
    assert prims.count("gather") == 1 + 2 * len(aggs)


class TestWindowsOverGroups:
    def test_sum_of_sums(self, small):
        got = small.execute(
            "SELECT a, sum(x), sum(sum(x)) OVER () FROM t "
            "WHERE a IS NOT NULL GROUP BY a ORDER BY a").rows
        assert got == [("p", 30, 35), ("q", 5, 35)]

    def test_rank_by_an_aggregate(self, small):
        got = small.execute(
            "SELECT a, rank() OVER (ORDER BY sum(x) DESC) FROM t "
            "GROUP BY a ORDER BY 2").rows
        assert got == [("p", 1), (None, 2), ("q", 3)]

    def test_partition_by_grouping(self, small):
        got = small.execute(
            "SELECT a, b, rank() OVER (PARTITION BY grouping(b) "
            "ORDER BY count(*) DESC, a, b) FROM t WHERE a = 'p' "
            "GROUP BY rollup(a, b) ORDER BY 3, 1, 2").rows
        # the two (a, b) groups rank 1 and 2 among themselves; the
        # subtotal and the grand total (grouping(b) = 1) tie at 2 rows
        assert [r[2] for r in got] == [1, 1, 2, 2]

    def test_window_in_order_by(self, small):
        got = small.execute(
            "SELECT a, sum(x) FROM t GROUP BY a "
            "ORDER BY rank() OVER (ORDER BY sum(x)) DESC").rows
        assert [a for a, _ in got] == ["p", None, "q"]

    def test_rank_filter_equals_the_full_rank(self, small):
        full = small.execute(
            "SELECT a, b, rank() OVER (PARTITION BY a ORDER BY sum(x) "
            "DESC) FROM t GROUP BY rollup(a, b)").rows
        cut = small.execute(
            "SELECT * FROM (SELECT a, b, rank() OVER (PARTITION BY a "
            "ORDER BY sum(x) DESC) rk FROM t GROUP BY rollup(a, b)) r "
            "WHERE rk <= 1").rows
        assert sorted(cut, key=_sort_key) == sorted(
            [r for r in full if r[2] <= 1], key=_sort_key)


@pytest.mark.parametrize("dtype", ["int64", "int32", "bool", "float64"])
@pytest.mark.parametrize("mask", ["random", "all", "none"])
@pytest.mark.parametrize("n", [1, 1000, 4096, (1 << 15) + 3, 9 << 12])
def test_compress_equals_a_scatter(n, mask, dtype):
    """The network compaction (ops/prefix.py compress) against a
    scatter of the kept rows to their ranks, keeping the first m rows
    for m under and over the count kept."""
    import jax
    import jax.numpy as jnp

    from cockroach_tpu.ops import prefix

    rng = np.random.default_rng(n)
    keep = {"random": rng.random(n) < 0.3, "all": np.ones(n, bool),
            "none": np.zeros(n, bool)}[mask]
    x = (rng.random(n) < 0.5 if dtype == "bool"
         else rng.normal(size=n) if dtype == "float64"
         else rng.integers(-(1 << 40), 1 << 40, n)).astype(dtype)
    with jax.enable_x64(True):
        got, = jax.jit(prefix.compress)(jnp.asarray(keep), [jnp.asarray(x)])
        got = np.asarray(got)
    count = int(keep.sum())
    for m in sorted({max(count - 7, 0), count // 2, count, count + 5, n}):
        ref = np.zeros(m, x.dtype)
        dest = np.where(keep, np.cumsum(keep) - 1, m)
        ref[dest[dest < m]] = x[dest < m]
        assert got.dtype == x.dtype
        assert (got[:min(m, count)] == ref[:min(m, count)]).all(), m


@pytest.mark.parametrize("n", [1000, 4096, 1 << 15, 5000, (1 << 15) + 3])
def test_prefix_scans_equal_the_plain_ones(n):
    import jax
    import jax.numpy as jnp

    from cockroach_tpu.ops import prefix

    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.integers(-(1 << 40), 1 << 40, n))
    y = jnp.asarray(rng.integers(-1000, 1000, n).astype(np.int32))
    assert (prefix.cumsum(x) == jnp.cumsum(x)).all()
    assert (prefix.cummax(y) == jax.lax.cummax(y)).all()
    assert (prefix.cummin(y) == jax.lax.cummin(y)).all()


def test_a_year_denser_than_its_share_of_the_dates_overflows_exactly():
    """The date join's share is read off the dimension
    (exec/dimstats.py) for each statement's own constants: of the date
    keys the fact table's key range reaches, those the year keeps,
    rounded up to a quarter octave. Years of one rounded share share
    one program; a year outside the fact rows' range gets its own (the
    estimate's), and answers nothing. A year whose fact rows are
    denser than its share of the keys (here 2004: a fifth of the days,
    three sevenths of the rows) overflows the Compact's blocks: the
    sentinel trips, the uncompacted plan answers exactly, and
    exec.compact.overflows counts it."""
    from cockroach_tpu.sql import parser
    from cockroach_tpu.sql import plan as P

    block = 32768
    n = 8 * block
    eng = Engine()
    eng.execute("CREATE TABLE f (k INT8 NOT NULL, d INT8 NOT NULL, "
                "v INT8 NOT NULL)")
    eng.execute("CREATE TABLE dd (id INT8 PRIMARY KEY, yr INT8 NOT NULL)")
    eng.execute("CREATE TABLE dm (id INT8 PRIMARY KEY, w INT8 NOT NULL)")
    ids = np.arange(200 * 365, dtype=np.int64)       # two centuries
    eng.store.insert_columns("dd", {"id": ids, "yr": 1900 + ids // 365},
                             eng.clock.now())
    rng = np.random.default_rng(43)
    w = rng.integers(0, 9, 100)
    eng.store.insert_columns("dm", {"id": np.arange(100, dtype=np.int64),
                                    "w": w.astype(np.int64)},
                             eng.clock.now())
    # five years of sales, 2000-2004; 2004 three times as dense
    yrs = rng.choice(5, n, p=[1 / 7] * 4 + [3 / 7])
    d = (100 + yrs) * 365 + rng.integers(0, 365, n)
    k = rng.integers(0, 100, n)
    v = rng.integers(0, 1000, n)
    eng.store.insert_columns("f", {"k": k, "d": d, "v": v},
                             eng.clock.now())
    for t in ("f", "dd", "dm"):
        eng.execute(f"ANALYZE {t}")
    s = eng.session()
    s.vars.set("distsql", "off")

    def sql(y):
        return ("select dm.w, sum(v) from f join dd on f.d = dd.id "
                f"join dm on f.k = dm.id where dd.yr = {y} "
                "group by dm.w order by dm.w")

    def fracs(y):
        node, _ = eng._plan(parser.parse(sql(y)), s)
        eng._check_join_builds(node, eng._read_ts(s), {})
        node = eng._insert_compaction(node, {"f": n})
        out = []
        while node is not None:
            if isinstance(node, P.Compact):
                out.append(node.frac)
            node = getattr(node, "child", None) \
                or getattr(node, "left", None)
        return out

    def want(y):
        m = 1900 + d // 365 == y
        return [(int(g), int(v[m & (w[k] == g)].sum()))
                for g in np.unique(w[k[m]])]

    def counters():
        snap = eng.metrics.snapshot()
        return snap["exec.compact.overflows"], snap["sql.plan.cache.miss"]

    # a fifth of the keys, 2^-2.25 once rounded, and 1.5x headroom
    assert fracs(2001) == fracs(2004) == [pytest.approx(0.3810, abs=5e-5)]
    assert eng.execute(sql(2001), session=s).rows == want(2001)
    assert counters() == (0, 1)
    # no sales in 1950: the estimate's capacity, another program
    assert fracs(1950) == [pytest.approx(0.02, abs=5e-5)]
    assert eng.execute(sql(1950), session=s).rows == []
    assert counters() == (0, 2)
    # the same program for 2004, whose blocks keep 3/7 of their rows
    assert eng.execute(sql(2004), session=s).rows == want(2004)
    assert counters()[0] == 1
    assert eng.execute(sql(2002), session=s).rows == want(2002)
    assert counters()[0] == 1


# -- a plain GROUP BY on the sorted layout -------------------------------------

# SORTED_GROUP_MIN_ROWS that sends a test's batch to each path
THRESHOLD = {"sorted": 0, "hash": 1 << 40}

SIX_Q = ("SELECT s1, s2, s3, k1, k2, k3, sum(m), count(m), count(*), "
         "avg(m), min(v), max(v) FROM six "
         "GROUP BY s1, s2, s3, k1, k2, k3")


def _six_keys(n, seed):
    """Six keys (three strings, three small integers) whose dense
    domain is past the planner's bound, drawn from 700 tuples so that a
    group holds several rows; 2 % NULL keys and values. (the columns,
    the rows as SQL sees them: a NULL None, money in hundredths)."""
    rng = np.random.default_rng(seed)
    words = {"s1": [f"c{i}" for i in range(8)],
             "s2": [f"cl{i}" for i in range(40)],
             "s3": [f"b{i}" for i in range(25)]}
    spans = {"s1": 8, "s2": 40, "s3": 25, "k1": 12, "k2": 30, "k3": 5}
    pool = {c: rng.integers(0, s, 700) for c, s in spans.items()}
    pick = rng.integers(0, 700, n)
    cols = {c: pool[c][pick] for c in spans}
    cols["k1"] = cols["k1"] + 1         # 1..12, a month
    cols["m"] = rng.integers(0, 100_000, n)
    cols["v"] = rng.integers(-1000, 1000, n)
    valid = {c: rng.random(n) > 0.02 for c in cols}
    rows = []
    for i in range(n):
        rows.append(tuple(
            None if not valid[c][i] else
            words[c][cols[c][i]] if c in words else int(cols[c][i])
            for c in cols))
    return cols, words, valid, rows


def _six_engine(cols, words, valid):
    eng = Engine()
    eng.execute("CREATE TABLE six (s1 STRING, s2 STRING, s3 STRING, "
                "k1 INT, k2 INT, k3 INT, m DECIMAL(9,2), v INT)")
    for c, values in words.items():
        eng.store.set_dictionary("six", c, values)
    eng.store.insert_columns("six", cols, eng.clock.now(), valid=valid)
    eng.execute("ANALYZE six")
    return eng


def _six_oracle(rows):
    """SIX_Q's groups, computed here row by row."""
    groups: dict = {}
    for r in rows:
        groups.setdefault(r[:6], []).append(r)
    out = []
    for key, rs in groups.items():
        ms = [r[6] for r in rs if r[6] is not None]
        vs = [r[7] for r in rs if r[7] is not None]
        out.append(key + (
            Fraction(sum(ms), 100) if ms else None, len(ms), len(rs),
            Fraction(sum(ms), 100 * len(ms)) if ms else None,
            min(vs) if vs else None, max(vs) if vs else None))
    return out


def _six_key(r):
    return tuple((v is None, v if v is not None else 0) for v in r[:6])


def _deltas(eng, run, names):
    before = eng.metrics.snapshot()
    out = run()
    after = eng.metrics.snapshot()
    return out, {n: after[n] - before[n] for n in names}


SORTED_COUNTERS = ("exec.agg.sorted.group_by", "exec.agg.sorted.declined",
                   "exec.agg.strategy.sorted", "exec.agg.strategy.hash",
                   "exec.agg.grouping_sets", "exec.agg.rollup.network")


@pytest.mark.parametrize("path", ["sorted", "hash"])
def test_plain_group_by_past_the_dense_bound_equals_its_oracle(
        path, monkeypatch):
    """Six keys past the dense bound that pack into one code: one sort
    of the rows over a batch of SORTED_GROUP_MIN_ROWS or more, the
    while-loop table under it; the same groups either way, NULL keys
    grouped together, exact sums and averages."""
    from cockroach_tpu.exec import compile as C
    monkeypatch.setattr(C, "SORTED_GROUP_MIN_ROWS", THRESHOLD[path])
    cols, words, valid, rows = _six_keys(6000, 17)
    eng = _six_engine(cols, words, valid)
    s = eng.session()
    s.vars.set("distsql", "off")    # on a mesh the table merges shards
    got, d = _deltas(eng, lambda: eng.execute(SIX_Q, session=s).rows,
                     SORTED_COUNTERS)
    want = _six_oracle(rows)
    assert any(None in r[:6] for r in want)
    _check(sorted(got, key=_six_key), sorted(want, key=_six_key))
    # one set, so nothing of it is a grouping set or a network's level
    assert d == {"exec.agg.sorted.group_by": path == "sorted",
                 "exec.agg.sorted.declined": path == "hash",
                 "exec.agg.strategy.sorted": path == "sorted",
                 "exec.agg.strategy.hash": path == "hash",
                 "exec.agg.grouping_sets": 0,
                 "exec.agg.rollup.network": 0}


def test_the_rule_from_the_plan_alone():
    """Keys that pack over a batch of at least SORTED_GROUP_MIN_ROWS:
    `sorted`; under it, or with an exact sum the plan does not prove
    inside int64: `hash`; a key with no domain (a float, or an
    aggregate's result as TPC-H Q13's outer key): `hash`, and no
    PlanError; distributed: `hash`."""
    from cockroach_tpu.exec import compile as C
    from cockroach_tpu.sql import parser
    from cockroach_tpu.sql import plan as P

    cols, words, valid, _ = _six_keys(500, 3)
    eng = _six_engine(cols, words, valid)
    eng.execute("CREATE TABLE fl (f FLOAT8, x INT)")
    eng.execute("INSERT INTO fl VALUES (1.5, 1), (2.5, 2), (1.5, 3)")

    def aggregate(sql):
        node, _ = eng._plan(parser.parse(sql), eng.session())
        while not isinstance(node, P.Aggregate):
            node = node.child
        return node

    one = C.ExecParams()
    t = C.SORTED_GROUP_MIN_ROWS
    agg = aggregate(SIX_Q)
    assert agg.max_groups == 0 and agg.grouping_sets is None
    assert [dim for dim, _ in agg.sort_dims] == [8, 40, 25, 12, 30, 5]
    assert C.aggregate_strategy(agg, t, one) == "sorted"
    assert C.aggregate_strategy(agg, t - 1, one) == "hash"
    assert C.aggregate_strategy(
        agg, t, C.ExecParams(axis_name="shards")) == "hash"
    # a sum of a signed column is proven inside int64 by nothing
    agg = aggregate(SIX_Q.replace("max(v)", "max(v), sum(v)"))
    assert agg.sort_dims and not C._exact_sums_cannot_wrap(agg.aggs, t)
    assert C.aggregate_strategy(agg, t, one) == "hash"
    for sql in ("SELECT f, x, count(*) FROM fl GROUP BY f, x",
                "SELECT c, count(*) FROM (SELECT x, count(*) AS c FROM fl "
                "GROUP BY x) d GROUP BY c"):
        agg = aggregate(sql)
        assert agg.max_groups == 0 and agg.sort_dims == []
        assert C.aggregate_strategy(agg, 1 << 40, one) == "hash"
        assert eng.execute(sql).rows


def test_plain_sorted_layout_past_its_slots_replans_exactly(monkeypatch):
    """Slots sized from an estimate that proves low (8,192 for some
    11,000 groups): the top-k sentinel fires, the plan with the whole
    slots answers, and keeps answering."""
    from cockroach_tpu.exec import compile as C
    monkeypatch.setattr(C, "SORTED_GROUP_MIN_ROWS", 0)
    eng, rows = _nulls_table(12000, 9)
    eng._estimate_set_groups = lambda agg: 10.0
    # NULLS_Q less its sums and averages of the signed v, which keep
    # the table (no proof bounds them)
    want = [r[:3] + (r[5], r[6], r[8], r[10], r[11])
            for r in _each_set_alone(rows, [(0, 1, 2)])]
    assert len(want) > 8192
    q = ("SELECT k1, k2, k3, count(v), count(*), sum(f), min(f), max(v) "
         "FROM wn GROUP BY k1, k2, k3")
    s = eng.session()
    s.vars.set("distsql", "off")
    # traced with the estimate's slots, then with the whole ones; from
    # then on the plan that answered is the cached one
    for traces in (2, 0):
        got, d = _deltas(eng, lambda: eng.execute(q, session=s).rows,
                         ("exec.agg.sorted.group_by",))
        got, want = sorted(got, key=_sort_key), sorted(want, key=_sort_key)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g[:5] == w[:5] and g[6:] == w[6:], (g, w)
            assert (g[5] is None) == (w[5] is None) and (
                g[5] is None or math.isclose(g[5], w[5], rel_tol=1e-9,
                                             abs_tol=1e-9)), (g, w)
        assert len(eng._whole_sorts) == 1
        assert d == {"exec.agg.sorted.group_by": traces}


@pytest.mark.parametrize("path", ["sorted", "hash"])
def test_sums_no_proof_bounds_keep_the_table_and_stay_exact(
        path, monkeypatch):
    """Sums the plan cannot prove inside int64 (a BIGINT of epoch
    microseconds, rows x max|value| far past 2^62 though every group's
    sum fits; a signed column) keep the hash table, whose f64 shadow
    tells a wrapped sum from one that fits, over a batch of any size:
    exact answers and no overflow error."""
    from cockroach_tpu.exec import compile as C
    monkeypatch.setattr(C, "SORTED_GROUP_MIN_ROWS", THRESHOLD[path])
    cols, words, valid, rows = _six_keys(6000, 23)
    rng = np.random.default_rng(29)
    micros = 1_700_000_000_000_000 + rng.integers(0, 10 ** 12, 6000)
    eng = _six_engine(cols, words, valid)
    eng.execute("CREATE TABLE ts (s1 STRING, s2 STRING, s3 STRING, "
                "k1 INT, k2 INT, k3 INT, t BIGINT, v INT)")
    for c, values in words.items():
        eng.store.set_dictionary("ts", c, values)
    eng.store.insert_columns(
        "ts", {**{c: cols[c] for c in cols if c not in ("m",)},
               "t": micros},
        eng.clock.now(),
        valid={**{c: valid[c] for c in valid if c != "m"},
               "t": valid["m"]})
    eng.execute("ANALYZE ts")
    s = eng.session()
    s.vars.set("distsql", "off")
    q = ("SELECT s1, s2, s3, k1, k2, k3, sum(t), avg(t), sum(v) FROM ts "
         "GROUP BY s1, s2, s3, k1, k2, k3")
    got, d = _deltas(eng, lambda: eng.execute(q, session=s).rows,
                     SORTED_COUNTERS)
    groups: dict = {}
    for i, r in enumerate(rows):
        groups.setdefault(r[:6], []).append(
            (int(micros[i]) if valid["m"][i] else None, r[7]))
    want = []
    for key, rs in groups.items():
        ts = [t for t, _ in rs if t is not None]
        vs = [v for _, v in rs if v is not None]
        want.append(key + (sum(ts) if ts else None,
                           Fraction(sum(ts), len(ts)) if ts else None,
                           sum(vs) if vs else None))
    assert 6000 * int(micros.max()) > 1 << 62
    assert max(len(rs) for rs in groups.values()) > 1
    got, want = sorted(got, key=_six_key), sorted(want, key=_six_key)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g[:7] == w[:7] and g[8] == w[8], (g, w)
        assert (g[7] is None) == (w[7] is None) and (
            g[7] is None or math.isclose(g[7], w[7], rel_tol=1e-12)), (g, w)
    assert d == {"exec.agg.sorted.group_by": 0,
                 "exec.agg.sorted.declined": 1,
                 "exec.agg.strategy.sorted": 0,
                 "exec.agg.strategy.hash": 1,
                 "exec.agg.grouping_sets": 0,
                 "exec.agg.rollup.network": 0}


@pytest.mark.parametrize("path", ["sorted", "hash"])
def test_q89_on_either_path_equals_its_oracle(loaded, path, monkeypatch):
    """Q89's six keys (category, class, brand, store name, company,
    month) past the dense bound: sorted where its batch reaches the
    threshold (at SF1 313,600 rows, over 2^17), the table under it;
    its Window orders the prefix of what the Aggregate hands on, live
    groups first on either path."""
    from cockroach_tpu.exec import compile as C
    monkeypatch.setattr(C, "SORTED_GROUP_MIN_ROWS", THRESHOLD[path])
    eng = Engine()
    tpcds.load(eng, tables=loaded[1])
    got, d = _deltas(eng, lambda: eng.execute(tpcds.query("q89")).rows,
                     SORTED_COUNTERS)
    want = tpcds.ORACLES["q89"](loaded[1], **tpcds.QUALIFICATION["q89"])
    assert len(want) >= 20
    _check(got, want)
    assert d == {"exec.agg.sorted.group_by": path == "sorted",
                 "exec.agg.sorted.declined": path == "hash",
                 "exec.agg.strategy.sorted": path == "sorted",
                 "exec.agg.strategy.hash": path == "hash",
                 "exec.agg.grouping_sets": 0,
                 "exec.agg.rollup.network": 0}
