"""The value-range proof of an exact SUM / AVG argument (PERF.md, PR 32).

Two layers:

1. `sql/valuerange.expr_int_range` against brute force: over small
   random columns the interval of `+`, `-`, `*`, unary minus, the
   decimal rescale and TPC-H Q1's two nested expressions is exactly
   the range of the expression over the product of its columns'
   values, so it holds every row's value, and anything else proves
   nothing;
2. what `Engine._plan` attaches to a plan's aggregates
   (BoundAgg.arg_nonneg / arg_bits): the bit length for sums AND avgs
   over columns and expressions, nothing for `//`, CASE, a function, a
   FLOAT argument, a range that can be negative or pass int64, or a
   table the transaction has written; and the plan-cache contract: a
   write inside the proven bits finds the compiled program again, a
   write past them compiles a new one and answers exactly.
"""

import itertools

import numpy as np
import pytest

from cockroach_tpu.sql.bound import (BBin, BCase, BCol, BConst, BFunc,
                                     BUnary)
from cockroach_tpu.sql.types import BOOL, FLOAT8, INT4, INT8, SQLType
from cockroach_tpu.sql.valuerange import expr_int_range, nonneg_bits

DEC2 = SQLType.decimal(15, 2)
DEC4 = SQLType.decimal(19, 4)
DEC6 = SQLType.decimal(19, 6)


def _col(name, ty=DEC2):
    return BCol(name, ty)


def _q1_disc_price():
    # l_extendedprice * (1 - l_discount), as sql/binder.py binds it
    return BBin("*", _col("price"),
                BBin("-", BConst(100, DEC2), _col("disc"), DEC2), DEC4)


SHAPES = {
    "add": lambda: BBin("+", _col("a"), _col("b"), DEC2),
    "sub": lambda: BBin("-", _col("a"), _col("b"), DEC2),
    "mul": lambda: BBin("*", _col("a"), _col("b"), DEC4),
    "neg": lambda: BUnary("-", _col("a"), DEC2),
    "rescale": lambda: BBin("*", _col("a"), BConst(10 ** 4, INT8), DEC6),
    "neg_of_product": lambda: BUnary(
        "-", BBin("*", _col("a"), BBin("-", _col("b"), BConst(7, DEC2),
                                       DEC2), DEC4), DEC4),
    "q1_disc_price": _q1_disc_price,
    "q1_charge": lambda: BBin(
        "*", _q1_disc_price(),
        BBin("+", BConst(100, DEC2), _col("tax"), DEC2), DEC6),
}


def _evaluate(e, row: dict) -> int:
    """The expression over one row, in Python integers."""
    if isinstance(e, BConst):
        return int(e.value)
    if isinstance(e, BCol):
        return int(row[e.name])
    if isinstance(e, BUnary):
        return -_evaluate(e.operand, row)
    x, y = _evaluate(e.left, row), _evaluate(e.right, row)
    return {"+": x + y, "-": x - y, "*": x * y}[e.op]


def _columns_of(e) -> list:
    if isinstance(e, BCol):
        return [e.name]
    if isinstance(e, BUnary):
        return _columns_of(e.operand)
    if isinstance(e, BBin):
        return _columns_of(e.left) + _columns_of(e.right)
    return []


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_interval_is_the_range_over_the_columns_product(shape, seed):
    rng = np.random.default_rng([seed, sorted(SHAPES).index(shape)])
    e = SHAPES[shape]()
    names = sorted(set(_columns_of(e)))
    # Q1's own columns are never negative; the others take both signs
    lo = 0 if shape.startswith("q1") else -500
    cols = {n: rng.integers(lo, 5000, 6) for n in names}
    if "disc" in cols:
        cols["disc"] = rng.integers(0, 11, 6)
    got = expr_int_range(
        e, lambda n: (int(cols[n].min()), int(cols[n].max())))
    values = [_evaluate(e, dict(zip(names, combo)))
              for combo in itertools.product(*(cols[n] for n in names))]
    # each column appears once, so interval arithmetic is exact over
    # the product of the columns' values, a superset of any table's
    # rows: no row's value lies outside it
    assert got == (min(values), max(values))
    rows = [_evaluate(e, {n: cols[n][i] for n in names}) for i in range(6)]
    assert got[0] <= min(rows) and max(rows) <= got[1]
    bits = nonneg_bits(got)
    if got[0] < 0:
        assert bits == 0
    else:
        assert bits >= 1 and max(rows) < 1 << bits
        assert bits == max(1, got[1].bit_length())


def test_tpch_domains_give_the_issues_bits():
    dom = {"qty": (100, 5000), "price": (90100, 10495000),
           "disc": (0, 10), "tax": (0, 8)}
    bits = {s: nonneg_bits(expr_int_range(SHAPES[s](), dom.get))
            for s in ("q1_disc_price", "q1_charge")}
    assert bits == {"q1_disc_price": 30, "q1_charge": 37}
    assert [nonneg_bits(dom[c]) for c in ("qty", "price", "disc")] \
        == [13, 24, 4]


@pytest.mark.parametrize("expr", [
    BBin("//", _col("a"), BConst(100, INT8), DEC2),
    BBin("/", _col("a"), _col("b"), DEC2),
    BBin("%", _col("a"), BConst(7, INT8), INT8),
    BCase([(BCol("p", BOOL), _col("a"))], BConst(0, DEC2), DEC2),
    BFunc("abs", [_col("a")], DEC2),
    BUnary("abs", _col("a"), DEC2),
    _col("f", FLOAT8),
    BBin("*", _col("f", FLOAT8), _col("a"), FLOAT8),
    BConst(None, DEC2),
    BConst(True, BOOL),
    _col("unknown"),
], ids=["idiv", "div", "mod", "case", "func", "unary_fn", "float_col",
        "float_product", "null", "bool", "no_range"])
def test_nothing_else_is_proven(expr):
    known = {"a": (0, 100), "b": (1, 9), "f": (0, 1), "p": (0, 1)}
    assert expr_int_range(expr, known.get) is None
    assert nonneg_bits(None) == 0


def test_an_interval_past_the_dtype_is_no_proof():
    big = {"a": (0, 1 << 40), "b": (0, 1 << 40), "i": (0, 1 << 20),
           "j": (0, 1 << 20)}
    # 2^80 does not fit int64: no proof, although both factors do
    assert expr_int_range(SHAPES["mul"](), big.get) is None
    # an intermediate that wraps is no proof even where the final
    # value would fit again
    back = BBin("-", SHAPES["mul"](), SHAPES["mul"](), DEC4)
    assert expr_int_range(back, big.get) is None
    # INT4 arithmetic runs, and is proven, in 32 bits
    i4 = BBin("*", _col("i", INT4), _col("j", INT4), INT4)
    assert expr_int_range(i4, big.get) is None
    small = {"i": (0, 1 << 10), "j": (0, 1 << 10)}
    assert expr_int_range(i4, small.get) == (0, 1 << 20)
    # -(-2^63) is not an int64
    lowest = {"a": (-(1 << 63), 0)}
    assert expr_int_range(_col("a", INT8), lowest.get) == (-(1 << 63), 0)
    assert expr_int_range(BUnary("-", _col("a", INT8), INT8),
                          lowest.get) is None
    # a range that can be negative, or whose top is past int64, gives
    # no bits
    assert nonneg_bits((-1, 5)) == 0
    assert nonneg_bits((0, 1 << 63)) == 0
    assert nonneg_bits((0, (1 << 63) - 1)) == 63
    assert nonneg_bits((0, 0)) == 1


# -- what the plan carries -----------------------------------------------------

N = 6000        # under the 8,192-row bucket, with room for the writes


def _make_table(e, name):
    e.execute(f"CREATE TABLE {name} (id INT8 PRIMARY KEY, "
              "g INT8 NOT NULL, q DECIMAL(15,2), p DECIMAL(15,2), "
              "d DECIMAL(15,2), s INT8, f FLOAT8, big INT8)")
    rng = np.random.default_rng(32)
    e.store.insert_columns(name, {
        "id": np.arange(N),
        # group 0 is the largest by far, so a row more in another
        # group moves no rows-a-group bound
        "g": np.where(np.arange(N) < 2000, 0, rng.integers(1, 8, N)),
        "q": rng.integers(100, 5001, N),
        "p": rng.integers(90000, 10_000_000, N),
        "d": rng.integers(0, 11, N),
        "s": rng.integers(-1000, 1000, N),
        "f": rng.random(N),
        "big": rng.integers(0, 1 << 40, N)}, e.clock.now())


@pytest.fixture(scope="module")
def eng():
    from cockroach_tpu.exec.engine import Engine
    e = Engine()
    _make_table(e, "rp")
    return e


def _session(eng, pallas="auto"):
    s = eng.session()
    s.vars.set("distsql", "off")
    s.vars.set("pallas_groupagg", pallas)
    return s


def _proofs(eng, sql, session=None):
    from cockroach_tpu.exec.stmtutil import _root_aggregate
    node, _ = eng._plan(eng._parse_cached(sql), session or _session(eng))
    return [(a.func, a.arg_nonneg, a.arg_bits)
            for a in _root_aggregate(node).aggs]


def test_sums_and_avgs_carry_the_bits(eng):
    got = _proofs(eng, "SELECT g, sum(q), avg(q), avg(d), sum(p * (1 - d)), "
                       "avg(p * (1 - d) * (1 + d)), sum(-(-q)), count(*) "
                       "FROM rp GROUP BY g")
    assert got == [("sum", True, 13), ("avg", True, 13), ("avg", True, 4),
                   ("sum", True, 30), ("avg", True, 37),
                   ("sum", True, 13), ("count_rows", False, 0)]


@pytest.mark.parametrize("arg", [
    "s",                        # a column that can be negative
    "q - p",                    # an interval that can be negative
    "big * big",                # an interval past int64
    "f",                        # FLOAT
    "CASE WHEN d > 5 THEN q ELSE 0 END",
    "abs(q)",
    "q / 2",
    "mod(s, 7)",
])
def test_no_proof(eng, arg):
    for func in ("sum", "avg"):
        got = _proofs(eng, f"SELECT g, {func}({arg}) FROM rp GROUP BY g")
        assert [(nn, bits) for _, nn, bits in got] == [(False, 0)], got


def test_a_table_the_txn_wrote_proves_nothing(eng):
    sql = "SELECT g, sum(q), avg(p * (1 - d)) FROM rp GROUP BY g"
    s = _session(eng)
    eng.execute("BEGIN", session=s)
    assert [b for _, _, b in _proofs(eng, sql, s)] == [13, 30]
    eng.execute(f"INSERT INTO rp VALUES ({N + 100}, 1, 1.00, 900.00, "
                "0.05, 0, 0.5, 1)", session=s)
    assert [(nn, b) for _, nn, b in _proofs(eng, sql, s)] \
        == [(False, 0), (False, 0)]
    # and its own write is in its answer
    got = dict((r[0], r[1]) for r in eng.execute(sql, session=s).rows)
    eng.execute("ROLLBACK", session=s)
    after = dict((r[0], r[1]) for r in eng.execute(
        sql, session=_session(eng)).rows)
    assert float(got[1]) == pytest.approx(float(after[1]) + 1.0)
    assert [b for _, _, b in _proofs(eng, sql)] == [13, 30]


@pytest.mark.parametrize("pallas", ["auto", "off"])
def test_a_write_past_the_bits_is_a_new_plan_and_an_exact_answer(
        eng, pallas):
    """The plan-cache contract of the proof: the fingerprint holds the
    bit length, so a write inside it finds the compiled program and a
    write past it compiles a new one; either way the sums are exact."""
    from decimal import Decimal
    t = f"rpw_{pallas}"
    _make_table(eng, t)
    sql = f"SELECT g, sum(q), avg(q), sum(p * (1 - d)) FROM {t} " \
          "GROUP BY g ORDER BY g"
    s = _session(eng, pallas)

    def run():
        """The statement's rows, and what it added to the plan cache's
        (hit, miss)."""
        names = ("sql.plan.cache.hit", "sql.plan.cache.miss")
        before = eng.metrics.snapshot()
        rows = eng.execute(sql, session=s).rows
        after = eng.metrics.snapshot()
        return rows, tuple(after[k] - before.get(k, 0) for k in names)

    def check(rows):
        want = {}
        for g, q, p, d in eng.execute(f"SELECT g, q, p, d FROM {t}",
                                      session=s).rows:
            acc = want.setdefault(g, [Decimal(0), Decimal(0)])
            acc[0] += Decimal(str(q))
            acc[1] += Decimal(str(p)) * (1 - Decimal(str(d)))
        assert [r[0] for r in rows] == sorted(want)
        for g, sum_q, _, sum_dp in rows:
            assert Decimal(str(sum_q)) == want[g][0]
            assert Decimal(str(sum_dp)) == want[g][1]

    def insert(rowid, q):
        eng.execute(f"INSERT INTO {t} VALUES ({rowid}, 3, {q}, 1000.00, "
                    "0.05, 0, 0.5, 1)", session=s)

    rows, delta = run()
    assert delta == (0, 1)
    check(rows)
    assert _proofs(eng, sql, s)[0] == ("sum", True, 13)
    insert(N + 1, "49.00")                  # 4,900 < 2^13: inside
    rows, delta = run()
    assert delta == (1, 0)
    check(rows)
    insert(N + 2, "90.00")                  # 9,000 >= 2^13: 14 bits
    assert _proofs(eng, sql, s)[0] == ("sum", True, 14)
    rows, delta = run()
    assert delta == (0, 1)
    check(rows)
    assert max(r[1] for r in eng.execute(
        f"SELECT g, q FROM {t} WHERE id > {N}", session=s).rows) == 90
