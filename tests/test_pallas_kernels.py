"""Which aggregations take the Pallas kernel, and that taking it never
changes an answer (interpret mode on CPU; the Mosaic lowering is
compiled in test_mosaic_compile.py and run by chip_smoke.py).

The decision lives in one place, compile.large_kernel_eligible: the
envelope table below pins it per (aggregate, argument family), and the
engine tests hold `pallas_groupagg = auto` (and the spellings that read
as auto) to the XLA path's rows, `off` being the oracle."""

import numpy as np
import pytest

from cockroach_tpu.exec import compile as C
from cockroach_tpu.ops.pallas import groupagg_large as pgl
from cockroach_tpu.sql import plan as P
from cockroach_tpu.sql import types as T
from cockroach_tpu.sql.bound import BCol, BoundAgg

FAMILIES = {"INT": T.INT8, "DECIMAL": T.SQLType.decimal(12, 2),
            "FLOAT": T.FLOAT8}

# (aggregate, argument family) -> takes the kernel under `auto`.
# Counts and `any` never read the argument's value in the kernel;
# sums, avgs and extremes are exact for integers only.
ENVELOPE = [("count_rows", None, True)] + [
    (func, fam, exact or fam != "FLOAT")
    for func, exact in (("count", True), ("any", True), ("sum", False),
                        ("avg", False), ("min", False), ("max", False))
    for fam in FAMILIES]


def _dense_aggregate(func, fam, **kw):
    """A dense 12-group GROUP BY with one aggregate, as the planner
    would hand it to compile_plan."""
    arg = None if fam is None else BCol("t.x", FAMILIES[fam])
    agg = BoundAgg(func, arg, T.INT8 if arg is None else arg.type, **kw)
    return P.Aggregate(child=None, group_by=[("g", BCol("t.g", T.INT8))],
                       aggs=[agg], max_groups=12, group_dims=[11])


class TestEnvelope:
    @pytest.mark.parametrize("func,fam,takes", ENVELOPE)
    def test_auto_takes_exact_aggregates_only(self, func, fam, takes):
        node = _dense_aggregate(func, fam)
        for interpret in (False, True):
            assert C.large_kernel_eligible(node, 8192, C.ExecParams(
                pallas_groupagg="auto",
                pallas_interpret=interpret)) is takes
        # `off` (ExecParams' own default too) takes nothing
        assert not C.large_kernel_eligible(
            node, 8192, C.ExecParams(pallas_groupagg="off"))
        assert not C.large_kernel_eligible(node, 8192, C.ExecParams())

    def test_what_else_keeps_a_plan_on_xla(self):
        auto = C.ExecParams(pallas_groupagg="auto")
        node = _dense_aggregate("sum", "INT")
        assert C.large_kernel_eligible(node, 8192, auto)
        # toy inputs, rows not a whole number of vregs
        assert not C.large_kernel_eligible(node, C.AUTO_MIN_ROWS - 128,
                                           auto)
        assert not C.large_kernel_eligible(node, 8192 + 64, auto)
        # DISTINCT, hash strategy, no GROUP BY, a domain past the cap
        assert not C.large_kernel_eligible(
            _dense_aggregate("sum", "INT", distinct=True), 8192, auto)
        node.max_groups, node.group_dims = 0, []
        assert not C.large_kernel_eligible(node, 8192, auto)
        node = _dense_aggregate("sum", "INT")
        node.group_by = []
        assert not C.large_kernel_eligible(node, 8192, auto)
        node = _dense_aggregate("sum", "INT")
        node.group_dims = [C.LARGE_G_MAX]
        assert not C.large_kernel_eligible(node, 1 << 20, auto)

    def test_exec_params_carry_two_pallas_fields(self):
        assert sorted(f for f in C.ExecParams.__dataclass_fields__
                      if f.startswith("pallas_")) \
            == ["pallas_groupagg", "pallas_interpret"]


N = 4096    # auto's row floor: the smallest table the kernel takes


@pytest.fixture(scope="module")
def eng():
    from cockroach_tpu.exec.engine import Engine
    e = Engine()
    e.execute("CREATE TABLE px (g STRING NOT NULL, f FLOAT, "
              "d DECIMAL(10,2))")
    rng = np.random.default_rng(3)
    e.store.insert_columns("px", {
        "g": np.array([f"k{int(g)}" for g in rng.integers(0, 3, N)]),
        "f": rng.normal(size=N) * 10,
        "d": rng.integers(0, 10_000, N).astype(np.int64)},
        e.clock.now())
    return e


def _rows(eng, sql, mode):
    s = eng.session()
    s.vars.set("distsql", "off")
    s.vars.set("pallas_groupagg", mode)
    return eng.execute(sql, session=s).rows


class TestEnginePallasGroupBy:
    """Dense strategy needs dict-coded (STRING/BOOL) group keys: the
    Q1 shape."""

    SQL = ("SELECT g, count(*) AS c, sum(f) AS s, avg(f) AS a, "
           "min(f) AS lo, max(f) AS hi FROM px "
           "GROUP BY g ORDER BY g")

    def test_matches_xla_path(self, eng):
        # FLOAT aggregates are outside the envelope: every mode runs
        # the XLA program and gives its rows, bit for bit
        want = _rows(eng, self.SQL, "off")
        before = pgl.BUILDS.value("large")
        assert len(want) == 3
        for mode in ("auto", "on"):
            assert _rows(eng, self.SQL, mode) == want
        assert pgl.BUILDS.value("large") == before

    def test_decimal_rides_large_kernel_exactly(self, eng):
        # DECIMAL sums ride the int64-limb path: the results must be
        # EXACT (bit-identical int64 fixed-point sums)
        sql = "SELECT g, sum(d) AS s FROM px GROUP BY g ORDER BY g"
        want = _rows(eng, sql, "off")
        before = pgl.BUILDS.value("large")
        got = _rows(eng, sql, "auto")
        assert pgl.BUILDS.value("large") > before, \
            "large kernel never routed"
        assert got == want

    @pytest.mark.parametrize("spelling", ["on", "true"])
    def test_old_opt_ins_read_as_auto(self, eng, spelling):
        """`SET pallas_groupagg = on` (and the legacy True) name the
        `auto` program: the same plan-cache key, so the statement is a
        hit on what `auto` compiled, not a third program."""
        sql = "SELECT g, sum(d) AS s, count(d) AS c FROM px GROUP BY g"
        s = eng.session()
        s.vars.set("distsql", "off")
        want = eng.execute(sql, session=s).rows      # auto, the default
        eng.execute(f"SET pallas_groupagg = {spelling}", session=s)
        assert eng._pallas_mode(s.vars.get("pallas_groupagg")) == "auto"
        before = eng.metrics.snapshot()
        assert eng.execute(sql, session=s).rows == want
        after = eng.metrics.snapshot()
        assert after["sql.plan.cache.hit"] == before["sql.plan.cache.hit"] + 1
        assert after["sql.plan.cache.miss"] == before["sql.plan.cache.miss"]


class TestUngroupedPallas:
    """Ungrouped aggregation (num_groups == 1, the Q6 shape) has no
    kernel: `auto` is the XLA program."""

    def test_matches_xla(self, eng):
        q = ("SELECT count(*), avg(f), min(f), max(f), sum(d) FROM px "
             "WHERE d >= 5000")
        before = pgl.BUILDS.value("large")
        assert _rows(eng, q, "auto") == _rows(eng, q, "off")
        assert pgl.BUILDS.value("large") == before

    def test_q6_shape(self):
        from cockroach_tpu.exec.engine import Engine
        from cockroach_tpu.models import tpch
        e = Engine()
        tpch.load(e, sf=0.01, rows=8192, tables=("lineitem",))
        want = tpch.ref_q6(tpch.gen_lineitem(0.01, rows=8192))
        got = _rows(e, tpch.Q6, "auto")
        assert got == _rows(e, tpch.Q6, "off")
        assert abs(got[0][0] - want) < max(1e-4 * abs(want), 1e-4)
