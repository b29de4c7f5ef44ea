"""Beyond-HBM streaming scan tests.

The HBM analogue of the reference's byte-limited KV paging
(pkg/sql/row/kv_batch_fetcher.go:191) + disk-spill aggregation
(colexecdisk): when the pruned device upload of the fact table exceeds
``sql.exec.hbm_budget_bytes``, aggregate-rooted plans execute page by
page with device-resident partial state. Forcing a tiny budget makes
every query here stream; results must match the unconstrained path
bit-for-bit (ints) / to fp tolerance (floats).
"""

import math

import numpy as np
import pytest

from cockroach_tpu.exec.engine import Engine
from cockroach_tpu.models import tpch
from cockroach_tpu.ops.batch import MVCC_COLUMNS

ROWS = 50_000


def _mk_engine(budget: int) -> Engine:
    eng = Engine(mesh=None)
    eng.settings.set("sql.exec.hbm_budget_bytes", budget)
    tpch.load(eng, sf=0.01, rows=ROWS)
    return eng


@pytest.fixture(scope="module")
def engines():
    big = _mk_engine(12 << 30)          # resident path (oracle)
    small = _mk_engine(1 << 20)         # 1MB: everything streams
    s = small.session()
    s.vars.set("distsql", "off")   # isolate streaming from mesh dist
    s.vars.set("streaming_page_rows", 1 << 13)  # 8K rows/page => 7 pages
    return big, small, s


def _assert_rows_close(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert len(ra) == len(rb)
        for va, vb in zip(ra, rb):
            if isinstance(va, float) or isinstance(vb, float):
                assert math.isclose(float(va), float(vb),
                                    rel_tol=1e-9, abs_tol=1e-9), (ra, rb)
            else:
                assert va == vb, (ra, rb)


def test_streaming_kicks_in(engines):
    big, small, s = engines
    p = small._prepare_select(
        __import__("cockroach_tpu.sql.parser", fromlist=["parser"])
        .parse(tpch.Q6), s, tpch.Q6)
    assert p.stream is not None
    alias, tname, page_rows = p.stream
    assert tname == "lineitem"
    assert page_rows == 1 << 13


def test_q6_streamed_matches_resident(engines):
    big, small, s = engines
    want = big.execute(tpch.Q6).rows
    got = small.execute(tpch.Q6, s).rows
    _assert_rows_close(got, want)


def test_q1_streamed_matches_resident(engines):
    """Dense GROUP BY with sum/avg/count partials across pages."""
    big, small, s = engines
    want = big.execute(tpch.Q1).rows
    got = small.execute(tpch.Q1, s).rows
    _assert_rows_close(got, want)


def test_q14_streamed_join_probe(engines):
    """The probe side streams; the join build (part) stays resident."""
    big, small, s = engines
    want = big.execute(tpch.Q14).rows
    got = small.execute(tpch.Q14, s).rows
    _assert_rows_close(got, want)


def test_min_max_having_order_limit_streamed(engines):
    big, small, s = engines
    q = ("SELECT l_returnflag, min(l_quantity) AS mn, max(l_quantity) "
         "AS mx, count(*) AS n FROM lineitem GROUP BY l_returnflag "
         "HAVING count(*) > 10 ORDER BY l_returnflag DESC LIMIT 2")
    want = big.execute(q).rows
    got = small.execute(q, s).rows
    _assert_rows_close(got, want)


def test_page_boundary_exact_multiple():
    """Table rows an exact multiple of the page size (no ragged tail)."""
    eng = Engine(mesh=None)
    eng.settings.set("sql.exec.hbm_budget_bytes", 1 << 16)
    eng.execute("CREATE TABLE t (a INT8 NOT NULL, b INT8)")
    n = 1 << 14
    vals = ", ".join(f"({i}, {i % 7})" for i in range(4096))
    for _ in range(n // 4096):
        eng.execute(f"INSERT INTO t VALUES {vals}")
    s = eng.session()
    s.vars.set("distsql", "off")
    s.vars.set("streaming_page_rows", 4096)
    r = eng.execute("SELECT sum(a) AS s, count(*) AS c FROM t", s)
    # 0..4095 inserted n/4096 times
    assert r.rows == [((n // 4096) * (4095 * 4096 // 2), n)]


def test_streamed_respects_mvcc_deletes():
    """Tombstoned rows across page boundaries stay invisible."""
    eng = Engine(mesh=None)
    eng.execute("CREATE TABLE d (a INT8 NOT NULL PRIMARY KEY)")
    vals = ", ".join(f"({i})" for i in range(10_000))
    eng.execute(f"INSERT INTO d VALUES {vals}")
    eng.execute("DELETE FROM d WHERE a % 2 = 0")
    eng.settings.set("sql.exec.hbm_budget_bytes", 1 << 14)
    s = eng.session()
    s.vars.set("distsql", "off")
    s.vars.set("streaming_page_rows", 1 << 10)
    r = eng.execute("SELECT count(*) AS c, sum(a) AS s FROM d", s)
    assert r.rows == [(5000, 5000 * 5000)]


def test_streaming_off_session_var(engines):
    # with streaming disabled, an over-budget table is a clean quota
    # error at prepare time (memory monitor), not a silent upload
    big, small, s2 = engines
    s = small.session()
    s.vars.set("distsql", "off")
    s.vars.set("streaming", "off")
    from cockroach_tpu.sql import parser
    from cockroach_tpu.utils.mon import MemoryQuotaError
    with pytest.raises(MemoryQuotaError, match="budget"):
        small._prepare_select(parser.parse(tpch.Q6), s, tpch.Q6)


def test_column_pruning_uploads_only_needed():
    # fresh engine: superset-reuse would otherwise serve a wider batch
    # cached by an earlier query
    eng = _mk_engine(12 << 30)
    from cockroach_tpu.sql import parser as pr
    p = eng._prepare_select(pr.parse(tpch.Q6), eng.session(), tpch.Q6)
    b = p.scans["lineitem"]
    # Q6 touches 4 lineitem columns; batch adds the 4 MVCC word columns
    assert len(b.names) <= 8, b.names
    assert set(MVCC_COLUMNS) <= set(b.names)
    # untouched wide columns (e.g. comment-ish/string cols) not uploaded
    assert "l_orderkey" not in b.names


def test_streamed_dict_growth_invalidates_plan():
    """A new dictionary code appearing after the plan was cached must
    not decode through the stale compiled program (review regression:
    the streamed table's cache key previously dropped dictlens)."""
    eng = Engine(mesh=None)
    eng.settings.set("sql.exec.hbm_budget_bytes", 1 << 12)
    eng.execute("CREATE TABLE sd (s STRING, a INT8)")
    eng.execute("INSERT INTO sd VALUES ('x', 1), ('y', 2)")
    s = eng.session()
    s.vars.set("distsql", "off")
    s.vars.set("streaming_page_rows", 1 << 10)
    q = "SELECT s, count(*) AS c FROM sd GROUP BY s ORDER BY s"
    assert eng.execute(q, s).rows == [("x", 1), ("y", 1)]
    eng.execute("INSERT INTO sd VALUES ('zzz', 3)")
    assert eng.execute(q, s).rows == [("x", 1), ("y", 1), ("zzz", 1)]


def test_page_rows_zero_clamped():
    eng = Engine(mesh=None)
    eng.settings.set("sql.exec.hbm_budget_bytes", 1 << 10)
    eng.execute("CREATE TABLE pz (a INT8 NOT NULL)")
    eng.execute("INSERT INTO pz VALUES " +
                ", ".join(f"({i})" for i in range(3000)))
    s = eng.session()
    s.vars.set("distsql", "off")
    s.vars.set("streaming_page_rows", 0)  # must not hang
    r = eng.execute("SELECT count(*) AS c FROM pz", s)
    assert r.rows == [(3000,)]


def test_device_cache_superset_reuse():
    eng = Engine(mesh=None)
    eng.execute("CREATE TABLE sup (a INT8, b INT8, c INT8)")
    eng.execute("INSERT INTO sup VALUES (1, 2, 3)")
    s = eng.session()
    s.vars.set("distsql", "off")
    eng.execute("SELECT a, b, c FROM sup", s)       # full-ish upload
    n_before = len(eng._device_tables)
    eng.execute("SELECT a FROM sup", s)             # subset: reuse
    assert len(eng._device_tables) == n_before
