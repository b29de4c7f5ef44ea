"""The pipelined streaming data plane (PR 3 tentpole): bounded page
prefetch, zone-map page skipping, and their end-to-end correctness
against unskipped / unpipelined execution."""

import threading
import time

import numpy as np
import pytest

from cockroach_tpu.exec.engine import Engine
from cockroach_tpu.exec.stream import (PageSource, ZonePred,
                                       extract_zone_preds, prefetch)
from cockroach_tpu.ops.batch import (MVCC_TS_HI, MVCC_TS_LO, NEVER_TS,
                                     ts_words)


# ---------------------------------------------------------------------------
# prefetch unit tests
# ---------------------------------------------------------------------------

def _no_prefetch_threads(timeout=5.0):
    """True once no page-prefetch worker is alive (joined)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not any(t.name == "page-prefetch" and t.is_alive()
                   for t in threading.enumerate()):
            return True
        time.sleep(0.01)
    return False


class TestPrefetch:
    def test_yields_in_order(self):
        assert list(prefetch(iter(range(100)))) == list(range(100))

    def test_empty_source(self):
        assert list(prefetch(iter(()))) == []
        assert _no_prefetch_threads()

    def test_bounded_depth(self):
        produced = []

        def src():
            for i in range(50):
                produced.append(i)
                yield i

        g = prefetch(src(), depth=2)
        first = next(g)  # starts the worker
        assert first == 0
        time.sleep(0.3)  # let the worker run as far ahead as it can
        # depth items queued + one blocked in put + the one consumed
        assert len(produced) <= 2 + 2
        g.close()
        assert _no_prefetch_threads()

    def test_worker_exception_propagates(self):
        class Boom(RuntimeError):
            pass

        def src():
            yield 1
            yield 2
            raise Boom("assembly failed")

        g = prefetch(src())
        assert next(g) == 1
        assert next(g) == 2
        with pytest.raises(Boom, match="assembly failed"):
            next(g)
        assert _no_prefetch_threads()

    def test_early_close_joins_worker(self):
        g = prefetch(iter(range(10_000)), depth=2)
        assert next(g) == 0
        g.close()
        assert _no_prefetch_threads()

    def test_full_consumption_joins_worker(self):
        assert sum(prefetch(iter(range(1000)))) == 499500
        assert _no_prefetch_threads()

    def test_stall_histogram_observes(self):
        class H:
            n = 0

            def observe(self, v):
                H.n += 1

        h = H()
        list(prefetch(iter(range(5)), stall_hist=h))
        assert H.n == 6  # one wait per item + the done marker


def test_a_page_is_not_rewritten_by_the_next():
    """A page's host buffers are its own. They were one set reused for
    every page behind jnp.array, which does not own a copy when it
    returns (the numpy buffer aliased zero-copy on the CPU backend,
    the copy a program dispatched asynchronously): at 2^16 rows a
    page read the next page's rows about one time in five, and a
    streamed aggregate summed them."""
    rows, pages = 1 << 16, 6
    eng = Engine(mesh=None)
    eng.execute("CREATE TABLE big (k INT8 NOT NULL PRIMARY KEY, v INT8)")
    k = np.arange(rows * pages, dtype=np.int64)
    eng.store.insert_columns("big", {"k": k, "v": k % 97},
                             eng.clock.now())
    td = eng.store.table("big")
    for _ in range(4):
        got = list(PageSource(td, frozenset({"k", "v"}), rows).pages())
        assert len(got) == pages
        for i, page in enumerate(got):
            want = k[i * rows:(i + 1) * rows]
            assert (np.asarray(page.col("k")) == want).all(), i
            assert (np.asarray(page.col("v")) == want % 97).all(), i


# ---------------------------------------------------------------------------
# zone-map page skipping
# ---------------------------------------------------------------------------

N_ROWS = 16_384
CHUNK = 2_048


def _clustered_engine():
    """Engine whose fact table is clustered on k (8 chunks of 2048 —
    one bulk INSERT per chunk), with a tiny HBM budget so scans
    stream at page_rows=CHUNK."""
    eng = Engine(mesh=None)
    eng.execute("CREATE TABLE t (k INT8 NOT NULL PRIMARY KEY, "
                "v INT8, s STRING)")
    for c in range(N_ROWS // CHUNK):
        vals = ", ".join(
            f"({i}, {i % 97}, '{'even' if i % 2 == 0 else 'odd'}')"
            for i in range(c * CHUNK, (c + 1) * CHUNK))
        eng.execute(f"INSERT INTO t VALUES {vals}")
    eng.settings.set("sql.exec.hbm_budget_bytes", 1 << 14)
    return eng


@pytest.fixture(scope="module")
def ceng():
    return _clustered_engine()


def _stream_session(eng, pipeline="on"):
    s = eng.session()
    s.vars.set("distsql", "off")
    s.vars.set("streaming_page_rows", CHUNK)
    s.vars.set("streaming_pipeline", pipeline)
    return s


def _counter(eng, name):
    m = eng.metrics.get(name)
    return m.value() if m is not None else 0


class TestZoneSkipping:
    def test_selective_range_skips_and_matches(self, ceng):
        skipped0 = _counter(ceng, "exec.stream.pages_skipped")
        pages0 = _counter(ceng, "exec.stream.pages")
        r = ceng.execute(
            "SELECT count(*) AS c, sum(k) AS s FROM t "
            "WHERE k BETWEEN 3000 AND 3500",
            _stream_session(ceng))
        ks = range(3000, 3501)
        assert r.rows == [(len(ks), sum(ks))]
        # the predicate touches 1 of 8 chunks: at least 6 whole pages
        # never left the host
        assert _counter(ceng, "exec.stream.pages_skipped") - skipped0 >= 6
        assert _counter(ceng, "exec.stream.pages") - pages0 <= 2

    def test_results_identical_to_resident(self, ceng):
        sql = ("SELECT count(*) AS c, sum(v) AS sv, min(k) AS mn, "
               "max(k) AS mx FROM t WHERE k >= 12000")
        streamed = ceng.execute(sql, _stream_session(ceng))
        resident = Engine(mesh=None)
        resident.execute("CREATE TABLE t (k INT8 NOT NULL PRIMARY KEY, "
                         "v INT8, s STRING)")
        vals = ", ".join(
            f"({i}, {i % 97}, '{'even' if i % 2 == 0 else 'odd'}')"
            for i in range(N_ROWS))
        resident.execute(f"INSERT INTO t VALUES {vals}")
        assert streamed.rows == resident.execute(sql).rows

    def test_all_pages_skipped_yields_empty_aggregate(self, ceng):
        r = ceng.execute(
            "SELECT count(*) AS c, sum(k) AS s FROM t WHERE k > 10000000",
            _stream_session(ceng))
        assert r.rows == [(0, None)]

    def test_equality_and_inlist(self, ceng):
        r = ceng.execute(
            "SELECT count(*) AS c FROM t WHERE k = 5000",
            _stream_session(ceng))
        assert r.rows == [(1,)]
        r = ceng.execute(
            "SELECT count(*) AS c FROM t WHERE k IN (100, 101, 9999)",
            _stream_session(ceng))
        assert r.rows == [(3,)]

    def test_string_predicate_zones(self):
        # dictionary-coded predicates: equality compiles to a code
        # comparison, so code-range zones prune chunks that never
        # held the value; an out-of-dictionary value constant-folds
        # to FALSE and prunes everything
        eng = Engine(mesh=None)
        eng.execute("CREATE TABLE u (k INT8 NOT NULL PRIMARY KEY, "
                    "s STRING)")
        for c in range(4):
            vals = ", ".join(f"({i}, 'c{c}')"
                             for i in range(c * CHUNK, (c + 1) * CHUNK))
            eng.execute(f"INSERT INTO u VALUES {vals}")
        eng.settings.set("sql.exec.hbm_budget_bytes", 1 << 14)
        s = _stream_session(eng)
        skipped0 = _counter(eng, "exec.stream.pages_skipped")
        r = eng.execute("SELECT count(*) AS c FROM u WHERE s = 'c2'", s)
        assert r.rows == [(CHUNK,)]
        assert _counter(eng, "exec.stream.pages_skipped") - skipped0 >= 3
        skipped1 = _counter(eng, "exec.stream.pages_skipped")
        r = eng.execute("SELECT count(*) AS c FROM u WHERE s = 'nope'",
                        s)
        assert r.rows == [(0,)]
        assert _counter(eng, "exec.stream.pages_skipped") - skipped1 >= 4

    def test_skipping_respects_mvcc_deletes(self):
        eng = _clustered_engine()
        eng.execute("DELETE FROM t WHERE k BETWEEN 3000 AND 3249")
        r = eng.execute(
            "SELECT count(*) AS c, sum(k) AS s FROM t "
            "WHERE k BETWEEN 3000 AND 3500",
            _stream_session(eng))
        ks = range(3250, 3501)
        assert r.rows == [(len(ks), sum(ks))]

    def test_pipeline_off_matches_on(self, ceng):
        sql = ("SELECT count(*) AS c, sum(v) AS sv FROM t "
               "WHERE k BETWEEN 1000 AND 14000")
        on = ceng.execute(sql, _stream_session(ceng, "on"))
        off = ceng.execute(sql, _stream_session(ceng, "off"))
        assert on.rows == off.rows

    def test_stream_metrics_registered(self, ceng):
        ceng.execute("SELECT sum(v) AS sv FROM t",
                     _stream_session(ceng))
        assert _counter(ceng, "exec.stream.pages") > 0
        assert _counter(ceng, "exec.stream.bytes") > 0
        h = ceng.metrics.get("exec.stream.prefetch_stall_seconds")
        assert h is not None and h.value()["count"] > 0


class TestZonePredExtraction:
    def test_between_and_scan_filter(self, ceng):
        from cockroach_tpu.sql import parser
        from cockroach_tpu.sql.planner import Planner
        node, _ = Planner(ceng.catalog_view()).plan_select(parser.parse(
            "SELECT sum(v) FROM t WHERE k BETWEEN 10 AND 20 AND v >= 3"))
        preds = extract_zone_preds(node, "t")
        assert {p.col for p in preds} == {"k", "v"}
        checks = {p.col: p.check for p in preds}
        # k BETWEEN 10 AND 20: zone [30, 40] cannot satisfy
        assert checks["k"](30, 40, 0, 100) is False
        assert checks["k"](15, 40, 0, 100) is True
        # all-null zones never satisfy a comparison
        assert checks["v"](0, 10, 100, 0) is False

    def test_unknown_bounds_never_skip(self):
        p = ZonePred("x", None)
        del p  # shape only; the contract below is what matters
        node_checks = []
        from cockroach_tpu.exec.stream import _cmp_check
        for op in ("<", "<=", ">", ">=", "=", "!="):
            node_checks.append(_cmp_check(op, 5)(None, None, 0, 10))
        assert all(node_checks)


class TestPageSource:
    def test_prefix_offsets_and_page_content(self, ceng):
        td = ceng.store.table("t")
        src = PageSource(td, frozenset({"k"}), 1000)
        got = []
        for page in src.pages():
            got.append(np.asarray(page.col("k")))
        # 17 pages of 1000 (last one padded)
        assert len(got) == 17
        flat = np.concatenate(got)
        real = np.concatenate(
            [g[:min(1000, N_ROWS - i * 1000)]
             for i, g in enumerate(got)])
        assert (real == np.arange(N_ROWS)).all()
        assert flat.shape[0] == 17_000

    def test_empty_page_is_never_visible(self, ceng):
        td = ceng.store.table("t")
        src = PageSource(td, frozenset({"k"}), 256)
        p = src.empty_page()
        hi, lo = ts_words(NEVER_TS)
        assert (np.asarray(p.col(MVCC_TS_HI)) == hi).all()
        assert (np.asarray(p.col(MVCC_TS_LO)) == lo).all()
        assert p.n == 256
