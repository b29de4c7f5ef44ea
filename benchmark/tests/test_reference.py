"""The benchmark's copies against the program's originals: the seeded
generator keeps the schema's shape, and the integer references give
what models/tpch.py's float oracles give at the validation parameters."""

import math

import numpy as np
import pytest

from generators import tpch as gen
import refworker

SF = 0.02
VALIDATION = {
    "q1": {"delta": 90},
    "q6": {"year": 1994, "discount": "0.06", "quantity": 24},
    "q14": {"month": "1995-09-01"},
    "q3": {"segment": "BUILDING", "date": "1995-03-15"},
    "q18": {"quantity": 150},  # the spec's 300 is empty at a tiny SF
}


@pytest.fixture(scope="module")
def answers():
    job = {"generator": "tpch", "sf": SF, "seed": 42,
           "tables": list(gen.TABLE_ORDER),
           "classes": [{"name": n, "statement": n, "sets": [p]}
                       for n, p in VALIDATION.items()]}
    return {n: rows[0] for n, rows in refworker.answers(job).items()}


@pytest.fixture(scope="module")
def oracle_tables():
    """The same data in the form models/tpch.py's oracles read: floats
    for DECIMALs, strings for codes."""
    out = {}
    for t in gen.TABLE_ORDER:
        cols, dicts = gen.generate(t, SF, 42)
        conv = {}
        for name, a in cols.items():
            if name in dicts:
                conv[name] = np.asarray(dicts[name], dtype=object)[a]
            elif "DECIMAL" in [ln for ln in gen.DDL[t].splitlines()
                               if ln.split()[:1] == [name]][0]:
                conv[name] = a / 100.0
            else:
                conv[name] = a
        out[t] = conv
    return out


def test_same_seed_same_data_other_seed_other_data():
    a, _ = gen.generate("lineitem", 0.01, 1)
    b, _ = gen.generate("lineitem", 0.01, 1)
    c, _ = gen.generate("lineitem", 0.01, 2)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["l_partkey"], c["l_partkey"])
    assert len(a["l_orderkey"]) == int(gen.LINEITEM_PER_SF * 0.01)
    o1, _ = gen.generate("orders", 0.01, 1)
    o2, _ = gen.generate("orders", 0.01, 2)
    assert not np.array_equal(o1["o_custkey"], o2["o_custkey"])


def _close(a, b):
    return math.isclose(a, b, rel_tol=1e-9, abs_tol=1e-6)


def test_q6_q14(answers, oracle_tables):
    from cockroach_tpu.models import tpch
    li, part = oracle_tables["lineitem"], oracle_tables["part"]
    assert _close(answers["q6"][0][0] / 1e4, tpch.ref_q6(li))
    num, den = answers["q14"][0][0]
    assert _close(num / den, tpch.ref_q14(li, part))


def test_q1(answers, oracle_tables):
    from cockroach_tpu.models import tpch
    want = tpch.ref_q1(oracle_tables["lineitem"])
    got = answers["q1"]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g[:2] == w[:2] and g[9] == w[9]
        for j, scale in ((2, 1e2), (3, 1e2), (4, 1e4), (5, 1e6)):
            assert _close(g[j] / scale, w[j])
        for j in (6, 7, 8):
            assert _close(g[j][0] / g[j][1] / 1e2, w[j])


def test_q3_q18(answers, oracle_tables):
    from cockroach_tpu.models import tpch
    li, orders, cust = (oracle_tables[t] for t in
                        ("lineitem", "orders", "customer"))
    want = tpch.ref_q3(li, orders, cust)
    got = answers["q3"]
    assert len(got) == len(want) == 10
    for g, w in zip(got, want):
        assert g[0] == w[0] and _close(g[1] / 1e4, w[1])
        assert gen.iso(g[2]) == w[2].isoformat() and g[3] == w[3]
    want = tpch.ref_q18(li, orders, cust, threshold=150)
    got = answers["q18"]
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert (g[0], g[1], g[2]) == (w[0], w[1], w[2])
        assert gen.iso(g[3]) == w[3].isoformat()
        assert _close(g[4] / 1e2, w[4]) and _close(g[5] / 1e2, w[5])


# TPC-H spec 1.4.1: the columns of each loaded table, and the declared
# width of each string column
SPEC_SCHEMA = {
    "lineitem": {"l_orderkey": None, "l_partkey": None, "l_suppkey": None,
                 "l_linenumber": None, "l_quantity": None,
                 "l_extendedprice": None, "l_discount": None, "l_tax": None,
                 "l_returnflag": 1, "l_linestatus": 1, "l_shipdate": None,
                 "l_commitdate": None, "l_receiptdate": None,
                 "l_shipinstruct": 25, "l_shipmode": 10, "l_comment": 44},
    "part": {"p_partkey": None, "p_name": 55, "p_mfgr": 25, "p_brand": 10,
             "p_type": 25, "p_size": None, "p_container": 10,
             "p_retailprice": None, "p_comment": 23},
    "orders": {"o_orderkey": None, "o_custkey": None, "o_orderstatus": 1,
               "o_totalprice": None, "o_orderdate": None,
               "o_orderpriority": 15, "o_clerk": 15, "o_shippriority": None,
               "o_comment": 79},
    "customer": {"c_custkey": None, "c_name": 25, "c_address": 40,
                 "c_nationkey": None, "c_phone": 15, "c_acctbal": None,
                 "c_mktsegment": 10, "c_comment": 117},
}
# text string [lo, hi] and v-string [lo, hi] of spec 4.2.3
SPEC_LENGTHS = {"l_comment": (10, 43), "p_comment": (5, 22),
                "o_comment": (19, 78), "c_comment": (29, 116),
                "c_address": (10, 40), "o_clerk": (15, 15)}


@pytest.mark.parametrize("table", gen.TABLE_ORDER)
def test_every_column_of_the_spec_at_its_width(table):
    cols, dicts = gen.generate(table, SF, 42)
    want = SPEC_SCHEMA[table]
    assert list(cols) == list(want)
    assert set(dicts) == {c for c, w in want.items() if w is not None}
    for c, values in dicts.items():
        assert cols[c].dtype == np.int32
        assert 0 <= cols[c].min() and cols[c].max() < len(values)
        assert len(set(values)) == len(values), c
        used = [len(values[i]) for i in np.unique(cols[c])]
        assert max(used) <= want[c], c
        if c in SPEC_LENGTHS:
            lo, hi = SPEC_LENGTHS[c]
            assert lo == min(used) and max(used) == hi, c
            # most rows are distinct, as in dbgen's output
            if lo != hi:
                assert len(values) > 0.8 * len(cols[c]), c
        assert f"({want[c]})" in [ln for ln in gen.DDL[table].splitlines()
                                  if ln.split()[:1] == [c]][0]
    if table == "part":
        names = [dicts["p_name"][i].split(" ") for i in cols["p_name"][:500]]
        assert all(len(w) == 5 == len(set(w)) and set(w) <= set(gen.COLORS)
                   for w in names)
