"""TPC-H's nested statements (benchmark cell `tpch_sf1_full.nested`) on
the CPU at a rehearsal scale factor: every class at every parameter set
through the served path (pgwire -> planner -> one compiled program)
against the BENCHMARK's own integer references
(benchmark/statements/nested_q*.py over the arrays
benchmark/generators/tpch_full.py made, which import nothing of the
program); those references against models/tpch.py's oracles on the same
tables (two references written apart agree); the counters each class
must raise (which join kinds its subqueries became, how a subquery's
result reached the program); that a later seed's data finds every
program in the compile cache; and the generator's own contract."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import pgclient  # noqa: E402
import traffic  # noqa: E402
import verify  # noqa: E402
from refworker import load_module  # noqa: E402

SF = 0.01
SEEDS = (2147484001, 7)
# asked only after SEEDS[0] was, by the test that counts its compiles
LATER_SEED = 3600000011
CLASSES = ["q4", "q13", "q22", "q21", "q17"]
with open(os.path.join(BENCH, "traffic", "nested.json")) as _f:
    MIX = json.load(_f)
# the spec's validation values, which models/tpch.py's oracles are
# written for; Q13's pair stands in no comment, as the oracle's does
VALIDATION = {
    "q4": {"date": "1993-07-01"},
    "q13": {"word1": "zyzzyva", "word2": "qoph"},
    "q22": {f"i{i + 1}": v for i, v in
            enumerate((13, 31, 23, 29, 30, 18, 17))},
    "q21": {"nation": "SAUDI ARABIA"},
    "q17": {"brand": "Brand#23", "container": "MED BOX"},
}
# counter -> what the class's first execution must add to it: the join
# kinds its plan holds, the rewrites it took, its subquery's argument
RAISES = {
    "q4": {"exec.join.kind.semi": 1, "exec.join.kind.left": 0,
           "exec.decorrelate.exists": 1},
    "q13": {"exec.join.kind.left": 1, "exec.join.kind.inner": 0},
    "q22": {"exec.join.kind.anti": 1, "exec.subquery.args": 1,
            "exec.decorrelate.exists": 1},
    # the inequality keeps Q21's two tests grouped LEFT JOINs
    # (its inner joins are three or, where the planner adds reducing
    # joins of its own for the data at hand, more)
    "q21": {"exec.join.kind.left": 2,
            "exec.join.kind.semi": 0, "exec.join.kind.anti": 0,
            "exec.decorrelate.exists": 2},
    "q17": {"exec.join.kind.left": 1, "exec.decorrelate.scalar": 1},
}


def _sql(name: str, params: dict) -> str:
    with open(os.path.join(BENCH, "statements",
                           f"nested_{name}.sql")) as f:
        return f.read().format(**params)


def _counters(engine) -> dict:
    flat = {}
    for name, v in engine.metrics.snapshot().items():
        if isinstance(v, dict):
            for k, x in v.items():
                flat[f"{name}.{k}"] = x
        else:
            flat[name] = v
    return flat


class _Served:
    """One server.Node holding one seed's eight tables, and the one
    connection the classes are asked over."""

    def __init__(self, seed: int):
        from cockroach_tpu.server import Node, NodeConfig

        self.gen = load_module("generators", "tpch_full")
        self.node = Node(NodeConfig()).start()
        self.engine = self.node.engine
        self.tables = {}
        ts = self.engine.clock.now()
        for t in self.gen.TABLE_ORDER:
            cols, dicts = self.gen.generate(t, SF, seed)
            self.tables[t] = (cols, dicts)
            self.engine.execute(self.gen.DDL[t])
            for col, values in dicts.items():
                self.engine.store.set_dictionary(t, col, values)
            self.engine.store.insert_columns(t, cols, ts)
            self.engine.execute(f"ANALYZE {t}")
        self.client = pgclient.MiniClient(*self.node.sql_addr,
                                          timeout=600)
        # the suite's eight virtual devices are a mesh; the cell is one
        # chip, where nothing is distributed
        assert self.client.exchange("SET distsql = off")[3] is None
        self.first = {}

    def ask(self, name: str, params: dict | None = None):
        """(rows, counter deltas of the FIRST execution) at `params`,
        the validation values unless given."""
        params = VALIDATION[name] if params is None else params
        key = (name, json.dumps(params, sort_keys=True))
        if key not in self.first:
            before = _counters(self.engine)
            _, _, reply, error = self.client.exchange(_sql(name, params))
            assert error is None, error
            after = _counters(self.engine)
            delta = {k: v - before.get(k, 0) for k, v in after.items()
                     if isinstance(v, (int, float))}
            self.first[key] = (
                pgclient.MiniClient.text_rows(reply), delta)
        return self.first[key]

    def close(self):
        self.client.close()
        self.node.stop()
        self.engine.close()


@pytest.fixture(scope="module")
def served():
    nodes = {}

    def get(seed: int) -> _Served:
        if seed not in nodes:
            nodes[seed] = _Served(seed)
        return nodes[seed]

    yield get
    for s in nodes.values():
        s.close()


@pytest.mark.parametrize("name", CLASSES)
def test_class_raises_its_counters(served, name):
    # the first test of the file: a join's kind is tallied when its
    # program is traced, which only a class's first execution does
    _, delta = served(SEEDS[0]).ask(name)
    for counter, count in RAISES[name].items():
        assert delta.get(counter, 0) == count, (counter, delta.get(counter))
    # no subquery's result is a constant of a program, and no derived
    # table went through the host (a temp is created, uploaded, dropped)
    assert delta.get("exec.subquery.inlined", 0) == 0
    assert not [t for t in served(SEEDS[0]).engine.store.tables
                if t.startswith("__cte")]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CLASSES)
def test_served_reply_equals_the_benchmarks_reference(served, name, seed):
    s = served(seed)
    mod = load_module("statements", "nested_" + name)
    sets = traffic.parameter_sets(MIX, seed)[name]
    assert len(sets) == (4 if name in ("q4", "q13") else 1)
    for params in sets:          # every set the cell would run
        rows, _ = s.ask(name, params)
        want = json.loads(json.dumps(mod.reference(s.tables, params)))
        assert verify.compare(mod.COLUMNS, rows, want) is None, params


def _decoded(tables: dict, name: str) -> dict:
    """A table as models/tpch.py's oracles read one: strings as text,
    DECIMALs as floats, dates as day numbers."""
    cols, dicts = tables[name]
    money = ("price", "acctbal", "discount", "tax", "supplycost")
    out = {}
    for c, v in cols.items():
        if c in dicts:
            out[c] = np.array(dicts[c], dtype=object)[v]
        elif c.endswith(money):
            out[c] = v / 100.0
        else:
            out[c] = v
    return out


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CLASSES)
def test_two_references_agree(served, name, seed):
    from cockroach_tpu.models import tpch as model

    t = served(seed).tables
    mod = load_module("statements", "nested_" + name)
    mine = mod.reference(t, VALIDATION[name])
    table = {n: _decoded(t, n) for n in mod.TABLES
             if n in ("lineitem", "orders", "customer", "part",
                      "supplier")}
    if name == "q4":
        theirs = model.ref_q4(table["lineitem"], table["orders"])
        assert [tuple(r) for r in mine] == theirs
    elif name == "q13":
        theirs = model.ref_q13(table["orders"], table["customer"])
        assert [tuple(r) for r in mine] == theirs
    elif name == "q21":
        theirs = model.ref_q21(table["lineitem"], table["orders"],
                               table["supplier"])
        assert [tuple(r) for r in mine] == theirs
    elif name == "q17":
        (total, by), = mine[0]
        assert total / 100.0 / by == pytest.approx(
            model.ref_q17(table["lineitem"], table["part"]), rel=1e-12)
    else:
        theirs = model.ref_q22(table["customer"], table["orders"])
        assert [(c, n) for c, n, _ in mine] == [(c, n)
                                                for c, n, _ in theirs]
        assert [s / 100.0 for _, _, s in mine] == pytest.approx(
            [s for _, _, s in theirs], abs=0.005)


@pytest.mark.parametrize("name", CLASSES)
def test_second_seed_compiles_nothing(served, name):
    served(SEEDS[0]).ask(name)       # compiles, into the suite's cache
    _, delta = served(LATER_SEED).ask(name)
    assert delta.get("exec.compile.cache_miss", 0) == 0, name
    assert delta.get("exec.compile.cache_hit", 0) >= 1


def test_a_repeat_neither_plans_nor_uploads(served):
    s = served(SEEDS[0])
    for name in CLASSES:
        sql = _sql(name, VALIDATION[name])
        first, _ = s.ask(name)
        s.client.exchange(sql)       # settle: a wider upload may follow
        before = _counters(s.engine)
        _, _, reply, error = s.client.exchange(sql)
        after = _counters(s.engine)
        assert error is None
        assert pgclient.MiniClient.text_rows(reply) == first
        for counter in ("sql.plan.cache.miss", "sql.device.upload.bytes",
                        "exec.compile.cache_miss",
                        "exec.subquery.inlined"):
            assert after.get(counter, 0) == before.get(counter, 0), \
                (name, counter)


# -- the generator ----------------------------------------------------------

def test_generator_row_counts_by_scale_factor():
    from generators import tpch_full as g

    assert [g.n_rows(t, 1.0) for t in g.TABLE_ORDER] == [
        6_001_215, 200_000, 1_500_000, 150_000, 800_000, 10_000, 25, 5]
    assert g.n_rows("partsupp", 0.01) == 4 * g.n_rows("part", 0.01)
    widths = {"lineitem": 16, "part": 9, "orders": 9, "customer": 8,
              "partsupp": 5, "supplier": 7, "nation": 4, "region": 3}
    assert set(g.DDL) == set(widths) == set(g.TABLE_ORDER)
    for t in ("partsupp", "supplier", "nation", "region"):
        cols, dicts = g.generate(t, SF, 11)
        assert list(cols) == g._column_order(t) and len(cols) == widths[t]
        assert {len(v) for v in cols.values()} == {g.n_rows(t, SF)}
        for name, codes in cols.items():
            if name in dicts:
                assert codes.dtype == np.int32
                assert 0 <= codes.min() and codes.max() < len(dicts[name])
                assert len(set(dicts[name])) == len(dicts[name])
            else:
                assert codes.dtype == np.int64


def test_every_lineitem_pair_is_in_partsupp():
    from generators import tpch_full as g

    li, _ = g.generate("lineitem", SF, 13)
    ps, _ = g.generate("partsupp", SF, 13)
    width = int(ps["ps_suppkey"].max()) + 1
    have = set((ps["ps_partkey"] * width + ps["ps_suppkey"]).tolist())
    assert len(have) == len(ps["ps_partkey"])     # the key is a key
    assert set((li["l_partkey"] * width + li["l_suppkey"]).tolist()) <= have


def test_every_foreign_key_resolves():
    from generators import tpch_full as g

    t = {n: g.generate(n, SF, 23)[0] for n in g.TABLE_ORDER}
    for child, col, parent, key in (
            ("lineitem", "l_orderkey", "orders", "o_orderkey"),
            ("lineitem", "l_partkey", "part", "p_partkey"),
            ("lineitem", "l_suppkey", "supplier", "s_suppkey"),
            ("orders", "o_custkey", "customer", "c_custkey"),
            ("partsupp", "ps_partkey", "part", "p_partkey"),
            ("partsupp", "ps_suppkey", "supplier", "s_suppkey"),
            ("customer", "c_nationkey", "nation", "n_nationkey"),
            ("supplier", "s_nationkey", "nation", "n_nationkey"),
            ("nation", "n_regionkey", "region", "r_regionkey")):
        keys = t[parent][key]
        assert len(np.unique(keys)) == len(keys), (parent, key)
        assert np.isin(t[child][col], keys).all(), (child, col)
    # every part has its four suppliers, and the spec's nation table
    assert (np.bincount(t["partsupp"]["ps_partkey"])[1:] == 4).all()
    assert set(t["supplier"]["s_nationkey"].tolist()) <= set(range(25))
    assert t["nation"]["n_regionkey"].tolist() == [
        0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2, 3, 4, 2,
        3, 3, 1]


def test_one_seed_one_database_two_seeds_two():
    from generators import tpch, tpch_full as g

    for t in g.TABLE_ORDER:
        a, da = g.generate(t, SF, 17)
        b, db = g.generate(t, SF, 17)
        assert all(np.array_equal(a[k], b[k]) for k in a) and da == db
    for t in ("partsupp", "supplier"):
        a, _ = g.generate(t, SF, 17)
        c, _ = g.generate(t, SF, 18)
        assert any(not np.array_equal(a[k], c[k]) for k in a)
    for t in tpch.TABLE_ORDER:        # handed on array for array
        a, da = g.generate(t, SF, 17)
        b, db = tpch.generate(t, SF, 17)
        assert list(a) == list(b) and da == db
        assert all(np.array_equal(a[k], b[k]) for k in a)


def test_supplier_comments_and_phones_follow_the_spec():
    from generators import tpch_full as g

    supp, dicts = g.generate("supplier", 1.0, 19)
    text = [dicts["s_comment"][c] for c in supp["s_comment"].tolist()]
    for tail in ("Complaints", "Recommends"):
        marked = [s for s in text if "Customer" in s
                  and tail in s[s.index("Customer"):]]
        assert len(marked) == 5            # 5 x SF
    assert all(25 <= len(s) <= 100 for s in text)
    phones = [dicts["s_phone"][c] for c in supp["s_phone"].tolist()]
    assert all(int(p[:2]) == n + 10 for p, n in
               zip(phones, supp["s_nationkey"].tolist()))
    assert supp["s_acctbal"].min() >= -99999
    assert supp["s_acctbal"].max() <= 999999


def test_generator_refuses_a_program_that_inlines_subqueries(monkeypatch):
    import types

    from generators import tpch_full as g

    fake = types.ModuleType(g.PLANPARAM)
    monkeypatch.setitem(sys.modules, g.PLANPARAM, fake)
    with pytest.raises(SystemExit) as e:
        g.generate("region", SF, 1)
    assert "SubqueryArg" in str(e.value)
    fake.SubqueryArg = object
    g.generate("region", SF, 1)
