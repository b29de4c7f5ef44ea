"""The Star Schema Benchmark's full flight (workload/ssb.py) through the
engine: each of the thirteen queries against its numpy oracle on seeded
data, with the kernel allowed (`auto`) and not (`off`); the strategy
each aggregate takes at the paper's cardinalities, from the plan alone;
what exec.join.* counts for a three- and a four-way plan; and the
value-range proof of each sum in the flight."""

import numpy as np
import pytest

from cockroach_tpu.exec import compile as C
from cockroach_tpu.exec.engine import Engine
from cockroach_tpu.sql import parser
from cockroach_tpu.sql import plan as P
from cockroach_tpu.sql.valuerange import nonneg_bits
from cockroach_tpu.workload import ssb

SF = 0.01
ROWS = 20_000
NAMES = list(ssb.QUERIES)


def _plant(lo, dims, rows, rng, customer=None, supplier=None, part=None,
           days=None):
    """Point the fact rows `rows` at dimension rows that a mask keeps:
    at this size the narrowest queries (two cities, one month) would
    otherwise find nothing to sum."""
    n = rows.stop - rows.start
    for key, table, mask in (("lo_custkey", "customer", customer),
                             ("lo_suppkey", "supplier", supplier),
                             ("lo_partkey", "part", part)):
        if mask is not None:
            keys = np.flatnonzero(mask) + 1
            assert len(keys), table
            lo[key][rows] = rng.choice(keys, size=n)
    if days is not None:
        lo["lo_orderdate"][rows] = rng.choice(
            dims["date"]["d_datekey"][days], size=n)


def _seeded():
    dims = ssb.gen_dims(SF, seed=8)
    rng = np.random.default_rng(33)
    # a few customers and suppliers in the two cities Q3.3 / Q3.4 name
    for table, p, n in (("customer", "c", 12), ("supplier", "s", 8)):
        d = dims[table]
        d[p + "_city"][:n] = np.array(["UNITED KI1", "UNITED KI5"] * (n // 2),
                                      dtype=object)
        d[p + "_nation"][:n] = "UNITED KINGDOM"
        d[p + "_region"][:n] = "EUROPE"
    lo = ssb.gen_lineorder(SF, dims, seed=7, rows=ROWS)
    date, cust, supp, part = (dims[t] for t in
                              ("date", "customer", "supplier", "part"))
    ki = ("UNITED KI1", "UNITED KI5")
    _plant(lo, dims, slice(0, 300), rng,
           customer=np.isin(cust["c_city"], ki),
           supplier=np.isin(supp["s_city"], ki),
           days=date["d_year"] <= 1997)
    _plant(lo, dims, slice(300, 400), rng,
           customer=np.isin(cust["c_city"], ki),
           supplier=np.isin(supp["s_city"], ki),
           days=date["d_yearmonth"] == "Dec1997")
    _plant(lo, dims, slice(400, 700), rng,
           customer=cust["c_region"] == "AMERICA",
           supplier=supp["s_nation"] == "UNITED STATES",
           part=part["p_category"] == "MFGR#14",
           days=date["d_year"] >= 1997)
    _plant(lo, dims, slice(700, 900), rng,
           supplier=supp["s_region"] == "EUROPE",
           part=part["p_brand1"] == "MFGR#2221")
    return dims, lo


@pytest.fixture(scope="module")
def loaded():
    dims, lo = _seeded()
    eng = Engine()
    ssb.create_tables(eng)
    ssb.insert(eng, dims, lo)
    for t in ssb.DDL:
        eng.execute(f"ANALYZE {t}")
    return eng, dims, lo


def _rows(result):
    return [tuple(int(x) if not isinstance(x, str) else x for x in row)
            for row in result.rows]


@pytest.mark.parametrize("mode", ["auto", "off"])
@pytest.mark.parametrize("name", NAMES)
def test_query_matches_its_oracle(loaded, name, mode):
    eng, dims, lo = loaded
    s = eng.session()
    s.vars.set("pallas_groupagg", mode)
    got = _rows(eng.execute(ssb.QUERIES[name], session=s))
    want = ssb.ORACLES[name](lo, dims)
    if isinstance(want, int):
        assert got == [(want,)]
    else:
        assert want, "the seeded data leaves this query nothing to sum"
        assert got == want          # rows in the paper's ORDER BY


# -- the strategy of each aggregate, from the plan alone ----------------------

# dense group domain at the paper's cardinalities (d_year 7, nation 25,
# city 250, category 25, brand1 1,000; one NULL slot a key), 0 = over
# the planner's dense bound
STRATEGY = {
    "q1.1": ("scalar", 1), "q1.2": ("scalar", 1), "q1.3": ("scalar", 1),
    "q2.1": ("kernel", 8 * 1001), "q2.2": ("kernel", 8 * 1001),
    "q2.3": ("kernel", 8 * 1001),
    "q3.1": ("kernel", 26 * 26 * 8),
    "q3.2": ("hash", 0), "q3.3": ("hash", 0), "q3.4": ("hash", 0),
    "q4.1": ("kernel", 8 * 26), "q4.2": ("kernel", 8 * 26 * 26),
    "q4.3": ("hash", 0),
}


def _aggregate_of(eng, sql):
    node, _ = eng._plan(parser.parse(sql), eng.session())
    while not isinstance(node, P.Aggregate):
        node = node.child
    return node


def _aggregate_rows(eng, sql):
    """Rows of the batch the plan's Compacts hand its Aggregate when
    lineorder fills the SF1 bucket."""
    s = eng.session()
    s.vars.set("distsql", "off")
    node, _ = eng._plan(parser.parse(sql), s)
    eng._check_join_builds(node, eng._read_ts(s), {})
    node = eng._insert_compaction(node, {"lineorder": SF1_ROWS})
    while not isinstance(node, P.Aggregate):
        node = node.child
    return C.plan_rows(node.child, {"lineorder": SF1_ROWS})


@pytest.mark.parametrize("name", NAMES)
def test_strategy_at_the_papers_cardinalities(loaded, paper, name):
    eng = loaded[0]
    agg = _aggregate_of(eng, ssb.QUERIES[name])
    want, groups = STRATEGY[name]
    # on the chip, over the 2^23-row bucket SF1 pads to
    chip = C.ExecParams(pallas_groupagg="auto", pallas_interpret=False)
    if want == "hash":
        # past the dense bound, keys that pack (cities, a year): the
        # sorted layout over a batch of SORTED_GROUP_MIN_ROWS or more,
        # the table over the one the two Compacts hand on at SF1
        assert agg.max_groups == 0 and agg.sort_dims
        rows = _aggregate_rows(paper, ssb.QUERIES[name])
        assert rows < C.SORTED_GROUP_MIN_ROWS <= 1 << 23
        assert C.aggregate_strategy(agg, rows, chip) == want
        # over the whole bucket: sorted where the plan proves the sums
        # inside int64 (Q3's revenue), the table where it cannot (Q4.3's
        # profit, revenue less supply cost, is signed)
        whole = "hash" if name == "q4.3" else "sorted"
        assert C.aggregate_strategy(agg, 1 << 23, chip) == whole
        return
    assert C.aggregate_strategy(agg, 1 << 23, chip) == want
    if want == "kernel":
        assert C.dense_num_groups(agg) == groups <= C.LARGE_G_MAX
        off = C.ExecParams(pallas_groupagg="off")
        assert C.aggregate_strategy(agg, 1 << 23, off) == "dense"


def test_strategy_is_tallied_where_the_aggregate_is_traced(loaded):
    eng = loaded[0]
    s = eng.session()
    s.vars.set("distsql", "off")    # one program, traced once
    # a statement text of its own, so that the plan is compiled here
    for sql, kind in ((ssb.Q1_1 + " and lo_tax < 9", "scalar"),
                      (ssb.Q4_1.replace("MFGR#2", "MFGR#3"), "kernel"),
                      (ssb.Q3_2.replace("1997", "1996"), "hash")):
        before = C.AGG_STRATEGY.value(kind)
        declined = C.SORTED_GROUP_BYS.value("declined")
        eng.execute(sql, session=s)
        assert C.AGG_STRATEGY.value(kind) == before + 1, kind
        # Q3.2's keys pack, but its batch is under SORTED_GROUP_MIN_ROWS
        assert C.SORTED_GROUP_BYS.value("declined") \
            == declined + (kind == "hash"), kind
    s.vars.set("pallas_groupagg", "off")
    before = C.AGG_STRATEGY.value("dense")
    eng.execute(ssb.Q4_1.replace("MFGR#2", "MFGR#4"), session=s)
    assert C.AGG_STRATEGY.value("dense") == before + 1
    snap = eng.metrics.snapshot()
    for kind in ("kernel", "dense", "hash", "scalar"):
        assert snap[f"exec.agg.strategy.{kind}"] \
            == C.AGG_STRATEGY.value(kind)
    for kind in ("group_by", "declined"):
        assert snap[f"exec.agg.sorted.{kind}"] \
            == C.SORTED_GROUP_BYS.value(kind)


# -- exec.join.* ---------------------------------------------------------------

def _join_counts(eng):
    snap = eng.metrics.snapshot()
    return tuple(snap[f"exec.join.{k}"]
                 for k in ("joins", "probe_rows", "build_rows"))


@pytest.mark.parametrize("name,tables", [("q3.1", 3), ("q4.1", 4)])
def test_join_counts_of_a_three_and_a_four_way_plan(loaded, name, tables):
    eng = loaded[0]
    sql = ssb.QUERIES[name]
    s = eng.session()
    s.vars.set("distsql", "off")    # one program over the whole batch
    prep = eng.prepare(sql, s)
    prep.run()                      # traced, if it was not before
    joins, probe, build = prep.meta.join_stats.totals
    # every dimension is joined once; a join whose payload was deferred
    # past a Compact is probed again above it
    assert tables <= joins <= 2 * tables
    bucket = prep.scans["lineorder"].n
    assert ROWS <= bucket
    # no probe is wider than the fact batch, and the first runs over
    # all of it
    assert bucket <= probe <= joins * bucket
    assert 0 < build < bucket
    before = _join_counts(eng)
    prep.run()
    prep.run()
    after = _join_counts(eng)
    assert tuple(a - b for a, b in zip(after, before)) \
        == (2 * joins, 2 * probe, 2 * build)


def test_plan_span_carries_joins_and_strategy(loaded):
    from cockroach_tpu.utils import tracing

    eng = loaded[0]
    tracing.start_collector()
    try:
        eng.execute(ssb.Q4_2)
        eng.execute(ssb.Q1_2)
    finally:
        roots = tracing.stop_collector()

    def plans(span):
        if span.name == "plan":
            yield span.tags
        for c in span.children:
            yield from plans(c)

    tags = [t for r in roots for t in plans(r)]
    assert [t["agg"] for t in tags] == ["kernel", "scalar"]
    assert tags[0]["joins"] >= 4 and tags[1]["joins"] == 1


# -- the range proof -----------------------------------------------------------

@pytest.mark.parametrize("name", NAMES)
def test_the_range_proof_of_each_sum_of_the_flight(loaded, name):
    eng, dims, lo = loaded
    agg = _aggregate_of(eng, ssb.QUERIES[name])
    (a,) = agg.aggs
    assert a.func in ("sum", "sum_int")
    if name.startswith("q4"):
        # a difference whose interval reaches below zero
        # (lo_revenue's least less lo_supplycost's most, though no
        # row's profit is negative): the proof is of values that are
        # never negative, so flight 4's sums keep their 64-bit limbs
        # and the run-time overflow gate (PERF.md section 7)
        assert lo["lo_revenue"].min() < lo["lo_supplycost"].max()
        assert nonneg_bits((int(lo["lo_revenue"].min()
                                - lo["lo_supplycost"].max()), 1)) == 0
        assert (a.arg_bits, a.arg_nonneg) == (0, False)
        assert not C._sum_cannot_wrap(a, 1 << 23)
        return
    assert a.arg_nonneg and C._proven_bits(a) == a.arg_bits > 0
    # the sum of 2^23 such values cannot wrap: no overflow sentinel
    assert C._sum_cannot_wrap(a, 1 << 23)
    arg = (lo["lo_extendedprice"] * lo["lo_discount"]
           if name.startswith("q1") else lo["lo_revenue"])
    assert a.arg_bits == int(arg.max()).bit_length()


def test_string_between_is_one_lookup_table(loaded):
    eng = loaded[0]
    between = _rows(eng.execute(
        "select count(*) from part where p_brand1 between 'MFGR#2221' "
        "and 'MFGR#2228'"))
    both = _rows(eng.execute(
        "select count(*) from part where p_brand1 >= 'MFGR#2221' "
        "and p_brand1 <= 'MFGR#2228'"))
    outside = _rows(eng.execute(
        "select count(*) from part where p_brand1 not between "
        "'MFGR#2221' and 'MFGR#2228'"))
    brand = loaded[1]["part"]["p_brand1"]
    want = int(((brand >= "MFGR#2221") & (brand <= "MFGR#2228")).sum())
    assert between == both == [(want,)]
    assert outside == [(len(brand) - want,)]


# -- what the flight's cold compile forced ------------------------------------

@pytest.fixture(scope="module")
def pairs():
    """g(k1, k2, v): both keys in [0, 999], so GROUP BY k1, k2 is a
    hash-strategy Aggregate (10^6 slots, over the planner's dense
    bound); every pair under 100 x 100 is present."""
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
    s = eng.session()
    s.vars.set("distsql", "off")

    def want(under, fold=int.__add__):
        sums: dict = {}
        for x, y, w in zip(k1.tolist(), k2.tolist(), v.tolist()):
            if x < under and y < under:
                sums[(x, y)] = fold(sums[(x, y)], w) \
                    if (x, y) in sums else w
        return sorted(((x, y, t) for (x, y), t in sums.items()),
                      key=lambda r: (-r[2], r[0], r[1]))
    return eng, s, want


def _sorted_pairs_sql(under, agg="sum"):
    return (f"select k1, k2, {agg}(v) as t from g where k1 < {under} and "
            f"k2 < {under} group by k1, k2 order by t desc, k1, k2")


def _whole_sort_replans(eng, run):
    """Run `run()` and return how many times it was prepared again with
    the whole sort (no_topk: the answer to a raised __topk_inexact)."""
    calls = []
    real = Engine._prepare_select

    def spy(self, *a, **kw):
        calls.append(kw.get("no_topk", False))
        return real(self, *a, **kw)

    Engine._prepare_select = spy
    try:
        out = run()
    finally:
        Engine._prepare_select = real
    return out, sum(calls)


@pytest.mark.parametrize("under,groups,prefix,replans", [
    # estimated 6 groups, 2,500 live: the prefix holds them
    (50, 2_500, C.HASH_SORT_PREFIX, 0),
    # estimated 10^6: the whole sort, chosen when the plan is made
    (1000, None, 0, 0),
    # estimated 100, 10,000 live: the sentinel, once
    (100, 10_000, C.HASH_SORT_PREFIX, 1),
])
def test_a_sort_over_a_hash_aggregate_is_sized_by_the_estimate(
        pairs, under, groups, prefix, replans):
    """A Sort right above a hash-strategy Aggregate orders the first
    HASH_SORT_PREFIX slots (the table numbers its groups densely from
    0) where the plan estimates at most half as many groups, else all
    of them. An estimate that proves low raises the top-k sentinel
    once: the engine keeps the whole sort for that plan, and later
    executions neither raise nor run two programs."""
    eng, s, want = pairs
    sql = _sorted_pairs_sql(under)
    node, _ = eng._plan(parser.parse(sql), s)
    assert isinstance(node, P.Sort) and node.prefix == prefix
    assert C.aggregate_strategy(node.child, 1 << 15,
                                C.ExecParams()) == "hash"
    rows = want(under)
    assert groups is None or len(rows) == groups
    got, n = _whole_sort_replans(
        eng, lambda: _rows(eng.execute(sql, session=s)))
    assert got == rows and n == replans
    # from the second execution on: one program, no sentinel
    for _ in range(2):
        got, n = _whole_sort_replans(
            eng, lambda: _rows(eng.execute(sql, session=s)))
        assert got == rows and n == 0
    # the constants are parameters of one program: the plan that met
    # 10,000 groups is the plan of `under` 50 too
    assert len(eng._whole_sorts) == (under == 100) \
        == eng.metrics.snapshot().get("exec.sort.prefix_short", 0)


def test_a_prepared_handle_keeps_the_whole_sort(pairs):
    """A handle executed again and again (a pgwire portal) adopts the
    whole-sort program the first time its prefix proves short."""
    eng, s, want = pairs
    sql = _sorted_pairs_sql(100, agg="max")    # a plan of its own
    prep = eng.prepare(sql, s)
    assert prep.prefix_key is not None
    for replans in (1, 0, 0):
        res, n = _whole_sort_replans(eng, prep.run)
        assert _rows(res) == want(100, max) and n == replans
    assert prep.prefix_key is None
    # and a fresh prepare of the plan goes straight there
    again, n = _whole_sort_replans(eng, lambda: eng.prepare(sql, s))
    assert n == 0 and again.prefix_key is None
    assert _rows(again.run()) == want(100, max)


# Q3.2's keys under Q3.1's filter: 50 x 50 cities x 6 years, more
# groups than a prefix sort is trusted with
Q3_2_WIDE = (ssb.Q3_2.replace("c_nation = 'UNITED STATES'",
                              "c_region = 'ASIA'")
             .replace("s_nation = 'UNITED STATES'", "s_region = 'ASIA'"))


@pytest.mark.parametrize("sql,est,prefix", [
    (ssb.Q3_2, 10 * 10 * 6, C.HASH_SORT_PREFIX),
    (ssb.Q3_3, 2 * 2 * 6, C.HASH_SORT_PREFIX),
    (ssb.Q3_4, 2 * 2 * 1, C.HASH_SORT_PREFIX),
    (ssb.Q4_3, 2 * 10 * 40, C.HASH_SORT_PREFIX),
    (Q3_2_WIDE, 50 * 50 * 6, 0),
], ids=["q3.2", "q3.3", "q3.4", "q4.3", "q3.2-wide"])
def test_the_group_estimate_of_the_flights_hash_aggregates(
        loaded, sql, est, prefix):
    """At the paper's cardinalities, from the dictionaries and the
    dimensions' filters alone, whatever the size loaded."""
    assert "ASIA" in Q3_2_WIDE and "UNITED STATES" not in Q3_2_WIDE
    eng = loaded[0]
    node, _ = eng._plan(parser.parse(sql), eng.session())
    assert isinstance(node, P.Sort) and node.child.max_groups <= 0
    assert eng._estimate_groups(node.child) == pytest.approx(est)
    assert node.prefix == prefix


def test_a_program_is_handed_its_own_columns():
    """What another statement made resident neither retraces nor
    recompiles a program: a scan served by a resident copy with more
    columns is handed its own (flight 4 reads a superset of flights 2
    and 3)."""
    eng = Engine()
    eng.execute("CREATE TABLE w (k INT8 NOT NULL, x INT8 NOT NULL, "
                "y INT8 NOT NULL, z INT8 NOT NULL)")
    n = 5000
    eng.store.insert_columns(
        "w", {c: np.arange(n, dtype=np.int64) for c in "kxyz"},
        eng.clock.now())
    s = eng.session()
    s.vars.set("distsql", "off")
    narrow = eng.prepare("select sum(x) from w where k < 100", s)
    before = narrow.scans["w"].names
    assert narrow.run().rows == [(sum(range(100)),)]
    # a statement over a superset replaces the resident copy ...
    wide = eng.prepare("select sum(x + y + z) from w where k < 100", s)
    assert set(wide.scans["w"].names) > set(before)
    wide.run()
    # ... and the first statement's program still sees its own columns
    again = eng.prepare("select sum(x) from w where k < 100", s)
    assert again.scans["w"].names == before
    misses = eng.metrics.snapshot()["exec.compile.cache_miss"]
    assert again.run().rows == [(sum(range(100)),)]
    assert eng.metrics.snapshot()["exec.compile.cache_miss"] == misses


def test_or_of_equalities_is_estimated_arm_by_arm(loaded):
    eng = loaded[0]
    node, _ = eng._plan(parser.parse(ssb.Q3_3), eng.session())

    def scans(n):
        if isinstance(n, P.Scan):
            yield n
        for attr in ("child", "left", "right"):
            c = getattr(n, attr, None)
            if c is not None:
                yield from scans(c)

    est = {sc.table: eng._estimate_scan_selectivity(sc)
           for sc in scans(node) if sc.filter is not None}
    # two cities of 250, and 1992..1997 of the date table's seven years
    assert est["customer"] == pytest.approx(2 / 250)
    assert est["supplier"] == pytest.approx(2 / 250)
    assert 0.8 < est["date"] < 0.9


# -- where a plan packs: Engine._insert_compaction's cost comparison ----------

SF1_ROWS = 1 << 23      # the bucket 6,000,000 lineorder / lineitem rows pad to


@pytest.fixture(scope="module")
def paper():
    """The dimensions at the paper's SF1 cardinalities (a probe is
    priced by its build's rows) over a 20,000-row lineorder; the plans
    are asked for at the SF1 bucket, which is all the rule reads of
    the fact table's size."""
    eng = Engine()
    ssb.load(eng, sf=1, rows=ROWS)
    for t in ssb.DDL:
        eng.execute(f"ANALYZE {t}")
    return eng


def _spine_compacts(node):
    """[(what the Compact sits on: the scan's table, or the build side
    of the join right under it; its capacity)] down a plan's probe
    spine, from the scan up."""
    out = []
    while node is not None:
        if isinstance(node, P.Compact):
            under = node.child
            out.append((under.table if isinstance(under, P.Scan)
                        else under.right.table, node.frac))
        node = getattr(node, "child", None) or getattr(node, "left", None)
    return out[::-1]


def _compacts(eng, sql, fact):
    """_spine_compacts of the plan `sql` runs when its fact table fills
    the SF1 bucket."""
    s = eng.session()
    s.vars.set("distsql", "off")
    node, _ = eng._plan(parser.parse(sql), s)
    eng._check_join_builds(node, eng._read_ts(s), {})
    return _spine_compacts(eng._insert_compaction(node, {fact: SF1_ROWS}))


# what each statement keeps of the fact rows, join by join (estimates:
# one region of five, one nation of 25, a city of 250, a category of
# 25, ...), decides the capacities; which joins pack, the cost
# comparison. Flight 1 packs the scan under its one probe; a star join
# packs after its first thinning join and once more after the next
# (a third Compact would be handed a ragged batch and is not made)
FLIGHT_COMPACTS = {
    "q1.1": [("lineorder", 0.3491)],
    "q1.2": [("lineorder", 0.2182)],
    "q1.3": [("lineorder", 0.2182)],
    "q2.1": [("part", 0.16), ("supplier", 0.1998)],
    "q2.2": [("part", 0.032), ("supplier", 0.1820)],
    "q2.3": [("part", 0.004), ("supplier", 0.1024)],
    "q3.1": [("customer", 0.3738), ("supplier", 0.3030)],
    "q3.2": [("customer", 0.16), ("supplier", 0.04)],
    "q3.3": [("customer", 0.032), ("supplier", 0.0073)],
    "q3.4": [("customer", 0.032), ("supplier", 0.0073)],
    "q4.1": [("customer", 0.3805), ("supplier", 0.3211)],
    "q4.2": [("customer", 0.3805), ("supplier", 0.3211)],
    "q4.3": [("part", 0.16), ("supplier", 0.04)],
}


@pytest.mark.parametrize("name", NAMES)
def test_where_the_flights_compacts_sit(paper, name):
    """Q1.x: under the `date` probe, at the BETWEENs' estimate. Q3.1
    (customer keeps 0.171 with the date join beneath) and Q4.1 / Q4.2
    (0.2) right after `customer`, where the eighth-bar left two or
    three probes at full width. Q3.2's 0.04 -> 0.0016 wraps twice, as
    it did."""
    got = _compacts(paper, ssb.QUERIES[name], "lineorder")
    want = FLIGHT_COMPACTS[name]
    assert [t for t, _ in got] == [t for t, _ in want]
    assert [f for _, f in got] == pytest.approx([f for _, f in want],
                                                abs=5e-5)


@pytest.mark.parametrize("est,frac", [
    (1 / 100, 4 / 100), (1 / 16, 1 / 4),    # 4x up to a sixteenth
    (0.04 * 3 / 11, 0.16 * 3 / 11),
    (0.2, 0.3805), (1 / 4, 3 / 8),          # down to 1.5x at a quarter
    (1 / 3, 1 / 2), (1e-4, 1 / 256)])
def test_the_headroom_falls_as_the_estimate_grows(paper, est, frac):
    assert paper._compact_frac(est) == pytest.approx(frac, abs=5e-5)


def _scans(n):
    if isinstance(n, P.Scan):
        yield n
    for attr in ("child", "left", "right"):
        c = getattr(n, attr, None)
        if c is not None:
            yield from _scans(c)


@pytest.mark.parametrize("where,est", [
    ("lo_discount between 1 and 3", 3 / 11),
    ("lo_discount between 1 and 3 and lo_quantity < 25", 3 / 11 * 24 / 50),
    ("lo_discount between 4 and 6 and lo_quantity between 26 and 35",
     3 / 11 * 10 / 50),
    ("lo_discount not between 1 and 3", None),
])
def test_a_between_is_estimated_by_its_two_bounds(paper, where, est):
    """`lo_discount` spans 0..10 and `lo_quantity` 1..50: Q1.1 read
    0.48 (the quantity alone) and Q1.2 / Q1.3 nothing, so none of them
    packed before its probe."""
    node, _ = paper._plan(parser.parse(
        "select sum(lo_revenue) from lineorder where " + where),
        paper.session())
    scan, = _scans(node)
    got = paper._estimate_scan_selectivity(scan)
    assert got == (pytest.approx(est) if est is not None else None)


@pytest.fixture(scope="module")
def tpch_small():
    from cockroach_tpu.models import tpch
    eng = Engine()
    tpch.load(eng, sf=0.01, rows=ROWS,
              tables=("lineitem", "orders", "customer", "part"))
    return eng


@pytest.mark.parametrize("query,want", [
    # lineitem's shipdate keeps 0.537 (over half: no pack), orders'
    # date 0.486 of that (0.261: packed at 1.5x), a market segment a
    # fifth of that again
    ("Q3", [("orders", 0.3917), ("customer", 0.3503)]),
    # one month of lineitem's seven years under the part probe
    ("Q14", [("lineitem", 0.0475)]),
    # Q1 and Q6 feed an aggregate with no join above: masked
    ("Q1", []), ("Q6", []),
])
def test_tpch_plan_shapes_at_sf1(tpch_small, query, want):
    from cockroach_tpu.models import tpch
    got = _compacts(tpch_small, getattr(tpch, query), "lineitem")
    assert [t for t, _ in got] == [t for t, _ in want]
    assert [f for _, f in got] == pytest.approx([f for _, f in want],
                                                abs=5e-5)


def test_q18_packs_behind_its_in_list(tpch_small):
    """Q18's orders are an IN list (the subquery's result, bound at
    prepare): the orders join keeps len(list) / orders of lineitem and
    the Compact above it four times that share."""
    from cockroach_tpu.models import tpch
    li = tpch.gen_lineitem(0.01, rows=ROWS)
    qty = np.bincount(li["l_orderkey"], weights=li["l_quantity"])
    keys = int((qty > 150).sum())
    assert keys > 10
    # through Engine.prepare, which runs the subquery first: the last
    # plan it packs is the statement's own
    plans = []
    real = Engine._insert_compaction

    def at_sf1(self, node, scan_rows=None):
        plans.append(real(self, node, {**scan_rows, "lineitem": SF1_ROWS}))
        return plans[-1]

    s = tpch_small.session()
    s.vars.set("distsql", "off")
    try:
        Engine._insert_compaction = at_sf1
        tpch_small.prepare(tpch.Q18_TEMPLATE.format(threshold=150), s)
    finally:
        Engine._insert_compaction = real
    got = _spine_compacts(plans[-1])
    orders = tpch_small.store.table("orders").row_count
    assert [t for t, _ in got] == ["orders"]
    assert got[0][1] == pytest.approx(4 * keys / orders)


def test_a_skewed_block_under_the_larger_capacities_replans_and_is_counted():
    """A filter estimated at a fifth packs at 0.38 of a block. One
    block of the fact table keeps three fifths: the sentinel trips, the
    statement is answered by the uncompacted plan, exactly, and
    exec.compact.overflows counts it once an execution (the page
    /_status/vars serves shows it)."""
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
    d[0], d[1] = 0, 99                      # the range the estimate reads
    hot = slice(3 * block, 4 * block)
    d[hot] = np.where(rng.random(block) < 0.6,
                      rng.integers(0, 20, block), rng.integers(20, 100, block))
    k = rng.integers(0, 100, n)
    v = rng.integers(0, 1000, n)
    eng.store.insert_columns(
        "f", {"k": k.astype(np.int64), "d": d.astype(np.int64),
              "v": v.astype(np.int64)}, eng.clock.now())
    for t in ("f", "dm"):
        eng.execute(f"ANALYZE {t}")
    s = eng.session()
    s.vars.set("distsql", "off")
    sql = ("select count(*), sum(v), sum(dm.w) from f join dm "
           "on dm.id = f.k where f.d < 20")
    assert _compacts(eng, sql, "f") == [("f", pytest.approx(0.3805,
                                                            abs=5e-5))]
    m = d < 20
    assert m[hot].mean() > 0.5 > 0.3805 > m.mean()
    want = [(int(m.sum()), int(v[m].sum()), int(w[k[m]].sum()))]

    def overflows():
        return eng.metrics.snapshot()["exec.compact.overflows"]

    assert overflows() == 0
    assert _rows(eng.execute(sql, session=s)) == want
    assert overflows() == 1
    assert _rows(eng.execute(sql, session=s)) == want
    assert overflows() == 2
    assert "exec_compact_overflows 2" in eng.metrics.to_prometheus()
    # the same statement over rows no block of which is skewed packs
    # and is not counted
    eng.execute("CREATE TABLE g (k INT8 NOT NULL, d INT8 NOT NULL, "
                "v INT8 NOT NULL)")
    d2 = rng.integers(0, 100, n)
    eng.store.insert_columns(
        "g", {"k": k.astype(np.int64), "d": d2.astype(np.int64),
              "v": v.astype(np.int64)}, eng.clock.now())
    eng.execute("ANALYZE g")
    m2 = d2 < 20
    got = _rows(eng.execute(sql.replace(" f ", " g ").replace("f.", "g."),
                            session=s))
    assert got == [(int(m2.sum()), int(v[m2].sum()), int(w[k[m2]].sum()))]
    assert overflows() == 2


@pytest.mark.parametrize("pallas", ["auto", "off"])
def test_an_inner_compact_that_overflows_under_an_outer_one_replans(
        pallas):
    """Two Compacts on one spine, foreign keys skewed into one block:
    the inner Compact's block overflows and drops rows, so the outer
    sees too few to overflow itself. The inner flag has to survive the
    outer Compact (compact_batch ORs it into its own) for the engine
    to replan without compaction, and the sums are exact."""
    from cockroach_tpu.exec import engine as E

    block = 32768
    n = 16 * block
    eng = Engine()
    eng.execute("CREATE TABLE f (k1 INT8 NOT NULL, k2 INT8 NOT NULL, "
                "g INT8 NOT NULL, v INT8 NOT NULL)")
    for t in ("d1", "d2"):
        eng.execute(f"CREATE TABLE {t} (id INT8 PRIMARY KEY, "
                    "a INT8 NOT NULL)")
        # a = 0 keeps one id in sixteen: ids 16, 32, ...
        ids = np.arange(1, 1025, dtype=np.int64)
        eng.store.insert_columns(t, {"id": ids, "a": ids % 16},
                                 eng.clock.now())
    rng = np.random.default_rng(41)
    kept = np.arange(16, 1025, 16)
    out = np.setdiff1d(np.arange(1, 1025), kept)
    k1 = rng.choice(out, n)
    k2 = rng.choice(out, n)
    # the first block: 15,000 rows only d1 keeps, 15,000 only d2
    # keeps, 1,000 both keep, shuffled. Whichever join the planner
    # puts first keeps 16,000 of its 32,768 rows where the first
    # Compact holds 8,192, and the second Compact, 2,048 a block of
    # four, is handed a few hundred
    kind = rng.permutation(np.repeat([0, 1, 2, 3],
                                     [15000, 15000, 1000, 1768]))
    k1[:block][kind != 1] = rng.choice(kept, int((kind != 1).sum()))
    k1[:block][kind == 3] = rng.choice(out, int((kind == 3).sum()))
    k2[:block][(kind == 1) | (kind == 2)] = rng.choice(kept, 16000)
    g = rng.integers(0, 100, n)
    v = rng.integers(1, 1000, n)
    eng.store.insert_columns(
        "f", {"k1": k1.astype(np.int64), "k2": k2.astype(np.int64),
              "g": g.astype(np.int64), "v": v.astype(np.int64)},
        eng.clock.now())
    eng.execute("ANALYZE f")
    eng.execute("ANALYZE d1")
    eng.execute("ANALYZE d2")
    s = eng.session()
    s.vars.set("distsql", "off")
    s.vars.set("pallas_groupagg", pallas)
    sql = ("select g, sum(v) as t from f, d1, d2 where k1 = d1.id and "
           "k2 = d2.id and d1.a = 0 and d2.a = 0 group by g order by g")

    node, _ = eng._plan(parser.parse(sql), s)
    eng._check_join_builds(node, eng._read_ts(s), {})
    node = eng._insert_compaction(node)
    fracs = []
    while node is not None:
        if isinstance(node, P.Compact):
            fracs.append(node.frac)
        node = getattr(node, "child", None) or getattr(node, "left", None)
    assert fracs[::-1] == pytest.approx([0.25, 1 / 16])

    replans = []
    real = Engine._prepare_select

    def spy(self, *a, **kw):
        replans.append(kw.get("no_compact", False))
        return real(self, *a, **kw)

    both = np.isin(k1, kept) & np.isin(k2, kept)
    assert 1000 <= both.sum() < 1100
    want = [(int(x), int(v[both & (g == x)].sum()))
            for x in np.unique(g[both])]
    try:
        E.Engine._prepare_select = spy
        got = _rows(eng.execute(sql, session=s))
    finally:
        E.Engine._prepare_select = real
    assert got == want
    assert True in replans, "no CompactOverflow replan"
