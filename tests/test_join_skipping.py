"""Write-time statistics and join-induced data skipping (PR 9).

Three layers under test, all sharing the same correctness contract —
a filter may only ever SHRINK the data that moves, never change the
visible rows:

  1. write-time chunk statistics (storage/chunkstats.py): zones,
     blocked bloom filters, and distinct sketches built at chunk seal
     instead of lazily on the scan path;
  2. semi-join filters (exec/joinfilter.py): build-side key summaries
     derived per dispatch and fed into the probe's zone predicates
     (streamed pages), spill-join row pruning, and — as a compact
     wire frame — remote DistSQL shard scans;
  3. MVCC window skipping: AS OF SYSTEM TIME scans skip chunks whose
     whole timestamp window lies outside the read timestamp.

Every skipping test asserts bit-equality against the filter-off run
of the same statement.
"""

import numpy as np
import pytest

from cockroach_tpu.exec.engine import Engine
from cockroach_tpu.storage.chunkstats import BlockedBloom, DistinctSketch

N_ROWS = 16_384
CHUNK = 2_048


def _counter(eng, name):
    m = eng.metrics.get(name)
    return m.value() if m is not None else 0


def _fact_engine(budget=1 << 17):
    """t clustered on k (8 chunks of 2048 — one bulk INSERT per
    chunk) joined against a 100-row dimension whose keys all live in
    t's second chunk. The budget admits the build side but not the
    16K-row probe, so the join's probe scan streams."""
    eng = Engine(mesh=None)
    eng.execute("CREATE TABLE t (k INT8 NOT NULL PRIMARY KEY, "
                "v INT8, s STRING)")
    eng.execute("CREATE TABLE d (k INT8 NOT NULL PRIMARY KEY, "
                "w INT8)")
    for c in range(N_ROWS // CHUNK):
        vals = ", ".join(
            f"({i}, {i % 97}, '{'even' if i % 2 == 0 else 'odd'}')"
            for i in range(c * CHUNK, (c + 1) * CHUNK))
        eng.execute(f"INSERT INTO t VALUES {vals}")
    dvals = ", ".join(f"({i}, {i * 2})" for i in range(3000, 3100))
    eng.execute(f"INSERT INTO d VALUES {dvals}")
    eng.settings.set("sql.exec.hbm_budget_bytes", budget)
    return eng


@pytest.fixture(scope="module")
def jeng():
    return _fact_engine()


def _jsession(eng, join_filter="auto", spill="off"):
    s = eng.session()
    s.vars.set("distsql", "off")
    s.vars.set("streaming_page_rows", CHUNK)
    s.vars.set("spill", spill)
    s.vars.set("join_filter", join_filter)
    return s


JOIN_Q = "SELECT count(*), sum(t.v) FROM t JOIN d ON t.k = d.k"


# ---------------------------------------------------------------------------
# write-time statistics (storage/chunkstats.py)
# ---------------------------------------------------------------------------

class TestWriteTimeStats:
    def test_stats_ready_at_seal(self, jeng):
        """Zone/bloom construction is no longer lazy on the scan
        path: every sealed chunk carries finalized stats."""
        for tname in ("t", "d"):
            td = jeng.store.table(tname)
            assert td.chunks, tname
            for c in td.chunks:
                assert c.stats_ready()
                assert c.key_bloom("k") is not None
                assert c.distinct_sketch("k") is not None

    def test_sealed_zone_matches_recompute(self, jeng):
        td = jeng.store.table("t")
        for c in td.chunks:
            lo, hi, nulls, nvalid = c.zone("k")
            k = c.data["k"][c.valid["k"]]
            assert (lo, hi) == (int(k.min()), int(k.max()))
            assert nulls == int((~c.valid["k"]).sum())
            assert nvalid == len(k)

    def test_bloom_never_false_negative(self):
        rng = np.random.default_rng(11)
        keys = rng.integers(-2**62, 2**62, size=5000, dtype=np.int64)
        bl = BlockedBloom(len(keys))
        bl.add(keys)
        assert bool(np.all(bl.might_contain(keys)))
        # round-trip through the wire form preserves membership
        bl2 = BlockedBloom.from_bytes(bl.tobytes())
        assert bool(np.all(bl2.might_contain(keys)))

    def test_bloom_filters_most_non_members(self):
        rng = np.random.default_rng(12)
        keys = rng.integers(0, 1 << 40, size=4000, dtype=np.int64)
        bl = BlockedBloom(len(keys))
        bl.add(keys)
        probe = rng.integers(1 << 41, 1 << 42, size=4000,
                             dtype=np.int64)
        fp = float(np.mean(bl.might_contain(probe)))
        assert fp < 0.15

    def test_distinct_sketch_estimate(self):
        rng = np.random.default_rng(13)
        true = 20_000
        vals = rng.permutation(true).astype(np.int64)
        sk = DistinctSketch()
        sk.add(vals)
        assert abs(sk.estimate() - true) / true < 0.15

    def test_stats_survive_backfill_and_drop(self):
        eng = Engine(mesh=None)
        eng.execute("CREATE TABLE b (k INT8 NOT NULL PRIMARY KEY, "
                    "v INT8)")
        eng.execute("INSERT INTO b VALUES " + ", ".join(
            f"({i}, {i})" for i in range(100)))
        eng.execute("ALTER TABLE b ADD COLUMN w INT8 DEFAULT 7")
        td = eng.store.table("b")
        for c in td.chunks:
            assert c.stats_ready()
            lo, hi, _, _ = c.zone("w")
            assert (lo, hi) == (7, 7)
        eng.execute("ALTER TABLE b DROP COLUMN w")
        for c in eng.store.table("b").chunks:
            assert c.stats_ready()

    def test_mvcc_window_bounds_visibility(self, jeng):
        """ts_min/del_max bracket every visible version: a read
        inside the window must see rows, a read before ts_min must
        not."""
        td = jeng.store.table("t")
        now = jeng.clock.now().to_int()
        for c in td.chunks:
            ts_min, del_max = c.mvcc_window()
            assert ts_min <= now < del_max


# ---------------------------------------------------------------------------
# streamed probe-side page skipping
# ---------------------------------------------------------------------------

class TestStreamedJoinSkipping:
    def test_selective_join_skips_majority_bit_identical(self, jeng):
        off = jeng.execute(JOIN_Q, _jsession(jeng, "off"))
        sk0 = _counter(jeng, "exec.stream.pages_skipped")
        jf0 = _counter(jeng, "exec.skip.joinfilter.pages")
        fl0 = _counter(jeng, "exec.skip.joinfilter.filters")
        on = jeng.execute(JOIN_Q, _jsession(jeng, "auto"))
        assert on.rows == off.rows
        jf = _counter(jeng, "exec.skip.joinfilter.pages") - jf0
        sk = _counter(jeng, "exec.stream.pages_skipped") - sk0
        n_pages = N_ROWS // CHUNK
        # acceptance: a selective join must skip > 50% of probe pages
        assert jf > n_pages // 2
        assert sk >= jf  # joinfilter skips are a subset of all skips
        assert _counter(jeng, "exec.skip.joinfilter.filters") > fl0
        assert _counter(jeng, "exec.skip.joinfilter.bytes") > 0

    def test_empty_build_skips_every_page(self, jeng):
        # w tops out at 6198: the build side filters to nothing, the
        # derived filter is the empty filter, and every probe page
        # rides the padding-page path
        q = (JOIN_Q + " WHERE d.w > 1000000")
        off = jeng.execute(q, _jsession(jeng, "off"))
        jf0 = _counter(jeng, "exec.skip.joinfilter.pages")
        on = jeng.execute(q, _jsession(jeng, "auto"))
        assert on.rows == off.rows == [(0, None)]
        assert (_counter(jeng, "exec.skip.joinfilter.pages") - jf0
                == N_ROWS // CHUNK)

    def test_filter_off_is_a_real_lever(self, jeng):
        jf0 = _counter(jeng, "exec.skip.joinfilter.pages")
        fl0 = _counter(jeng, "exec.skip.joinfilter.filters")
        jeng.execute(JOIN_Q, _jsession(jeng, "off"))
        assert _counter(jeng, "exec.skip.joinfilter.pages") == jf0
        assert _counter(jeng, "exec.skip.joinfilter.filters") == fl0

    def test_spill_join_prunes_probe_rows(self, jeng):
        off = jeng.execute(JOIN_Q, _jsession(jeng, "off", spill="on"))
        r0 = _counter(jeng, "exec.skip.joinfilter.rows")
        on = jeng.execute(JOIN_Q, _jsession(jeng, "auto", spill="on"))
        assert on.rows == off.rows
        pruned = _counter(jeng, "exec.skip.joinfilter.rows") - r0
        assert pruned > N_ROWS // 2


# ---------------------------------------------------------------------------
# MVCC window skipping (AS OF SYSTEM TIME)
# ---------------------------------------------------------------------------

class TestMVCCSkipping:
    def test_aost_skips_future_chunks(self):
        eng = Engine(mesh=None)
        eng.execute("CREATE TABLE h (k INT8 NOT NULL PRIMARY KEY, "
                    "v INT8)")
        half = N_ROWS // 2
        for c in range(half // CHUNK):
            vals = ", ".join(f"({i}, {i % 53})"
                             for i in range(c * CHUNK, (c + 1) * CHUNK))
            eng.execute(f"INSERT INTO h VALUES {vals}")
        eng.store.seal("h")
        mid = eng.clock.now().to_int()
        for c in range(half // CHUNK, N_ROWS // CHUNK):
            vals = ", ".join(f"({i}, {i % 53})"
                             for i in range(c * CHUNK, (c + 1) * CHUNK))
            eng.execute(f"INSERT INTO h VALUES {vals}")
        eng.settings.set("sql.exec.hbm_budget_bytes", 1 << 14)
        s = _jsession(eng)
        mv0 = _counter(eng, "exec.skip.mvcc.pages")
        r = eng.execute(
            f"SELECT count(*) FROM h AS OF SYSTEM TIME {mid}", s)
        assert r.rows == [(half,)]
        # chunks inserted after `mid` have ts_min > mid: their pages
        # skip on the MVCC window without touching zone predicates
        assert (_counter(eng, "exec.skip.mvcc.pages") - mv0
                >= half // CHUNK)
        r = eng.execute("SELECT count(*) FROM h", _jsession(eng))
        assert r.rows == [(N_ROWS,)]


# ---------------------------------------------------------------------------
# fuzzed on/off bit-equality
# ---------------------------------------------------------------------------

def _fuzz_engine(seed):
    """Random fact/dim pair with NULL keys, INT64 extremes, and a
    dict-coded string column; budget forces the probe to stream."""
    rng = np.random.default_rng(seed)
    eng = Engine(mesh=None)
    eng.execute("CREATE TABLE f (k INT8, v INT8, s STRING)")
    eng.execute("CREATE TABLE g (k INT8, w INT8, name STRING)")
    n = 8192
    ts = eng.clock.now()
    pool = np.concatenate([
        rng.integers(-50, 50, size=n - 4, dtype=np.int64),
        np.array([-(2**62), 2**62, 0, 1], dtype=np.int64)])
    rng.shuffle(pool)
    fvalid = rng.random(n) > 0.1        # ~10% NULL probe keys
    eng.store.insert_columns("f", {
        "k": np.where(fvalid, pool, 0),
        "v": rng.integers(0, 1000, size=n, dtype=np.int64),
        "s": np.array([b"ab", b"cd", b"ef", b"gh"])[
            rng.integers(0, 4, size=n)],
    }, ts, valid={"k": fvalid})
    m = rng.integers(1, 40)
    gvalid = rng.random(m) > 0.2
    eng.store.insert_columns("g", {
        "k": rng.integers(-60, 60, size=m, dtype=np.int64),
        "w": rng.integers(0, 10, size=m, dtype=np.int64),
        "name": np.array([b"ab", b"zz"])[rng.integers(0, 2, size=m)],
    }, ts, valid={"k": gvalid})
    eng.settings.set("sql.exec.hbm_budget_bytes", 1 << 17)
    return eng


FUZZ_QUERIES = (
    "SELECT count(*), sum(f.v) FROM f JOIN g ON f.k = g.k",
    "SELECT count(*), sum(f.v) FROM f JOIN g ON f.k = g.k "
    "WHERE g.w < 5",
    # string join key: no derivable filter (dict code spaces are
    # per-table) — the conservative bail must still be bit-identical
    "SELECT count(*) FROM f JOIN g ON f.s = g.name",
)


def _fuzz_one(seed):
    eng = _fuzz_engine(seed)
    for q in FUZZ_QUERIES:
        for spill in ("off", "on"):
            off = eng.execute(q, _jsession(eng, "off", spill=spill))
            on = eng.execute(q, _jsession(eng, "on", spill=spill))
            assert on.rows == off.rows, (seed, q, spill)


class TestFuzzEquality:
    def test_fuzz_on_off_equal(self):
        _fuzz_one(0)

    def test_empty_build_table(self):
        eng = Engine(mesh=None)
        eng.execute("CREATE TABLE f (k INT8, v INT8)")
        eng.execute("CREATE TABLE g (k INT8)")
        eng.execute("INSERT INTO f VALUES " + ", ".join(
            f"({i}, {i})" for i in range(4096)))
        eng.settings.set("sql.exec.hbm_budget_bytes", 1 << 16)
        q = "SELECT count(*), sum(f.v) FROM f JOIN g ON f.k = g.k"
        off = eng.execute(q, _jsession(eng, "off"))
        on = eng.execute(q, _jsession(eng, "on"))
        assert on.rows == off.rows == [(0, None)]

    @pytest.mark.slow
    @pytest.mark.parametrize("seed", list(range(1, 14)))
    def test_fuzz_on_off_equal_heavy(self, seed):
        _fuzz_one(seed)


# ---------------------------------------------------------------------------
# DistSQL: the join-filter wire frame
# ---------------------------------------------------------------------------

def _fakedist(transport_cls=None, **gw_kw):
    """3 data nodes, t range-sharded (4 clustered chunks each), d
    replicated everywhere; gateway (node 0) holds d but no t rows."""
    from cockroach_tpu.distsql.node import DistSQLNode, Gateway
    from cockroach_tpu.kvserver.transport import LocalTransport
    transport = (transport_cls or LocalTransport)()
    nodes, engines = [], []
    dk = np.arange(100, 160, dtype=np.int64)
    for i in range(4):
        eng = Engine()
        eng.execute("CREATE TABLE t (k INT8 NOT NULL PRIMARY KEY, "
                    "v INT8)")
        eng.execute("CREATE TABLE d (k INT8 NOT NULL PRIMARY KEY, "
                    "w INT8)")
        ts = eng.clock.now()
        if i > 0:
            base = (i - 1) * 20000
            for c in range(4):
                lo = base + c * 500
                k = np.arange(lo, lo + 500, dtype=np.int64)
                eng.store.insert_columns("t", {"k": k, "v": k % 97},
                                         ts)
        eng.store.insert_columns("d", {"k": dk, "w": dk * 2}, ts)
        engines.append(eng)
        nodes.append(DistSQLNode(i, eng, transport))
    gw = Gateway(nodes[0], [1, 2, 3], replicated_tables={"d"},
                 **gw_kw)

    oracle = Engine()
    oracle.execute("CREATE TABLE t (k INT8 NOT NULL PRIMARY KEY, "
                   "v INT8)")
    oracle.execute("CREATE TABLE d (k INT8 NOT NULL PRIMARY KEY, "
                   "w INT8)")
    ts = oracle.clock.now()
    allk = np.concatenate(
        [np.arange((i - 1) * 20000 + c * 500,
                   (i - 1) * 20000 + c * 500 + 500)
         for i in range(1, 4) for c in range(4)]).astype(np.int64)
    oracle.store.insert_columns("t", {"k": allk, "v": allk % 97}, ts)
    oracle.store.insert_columns("d", {"k": dk, "w": dk * 2}, ts)
    return gw, engines, oracle


DIST_Q = "SELECT count(*), sum(v) FROM t JOIN d ON t.k = d.k"


class TestDistSQLJoinFilter:
    def test_remote_chunks_skip_host_side(self):
        gw, engines, oracle = _fakedist()
        got = gw.run(DIST_Q)
        want = oracle.execute(DIST_Q)
        assert got.rows == want.rows
        # the gateway derived the frame from its replicated build copy
        assert _counter(engines[0],
                        "exec.skip.joinfilter.filters") >= 1
        # only node 1 holds the matching chunk (keys 100..159): nodes
        # 2 and 3 skip all 4 of their chunks, node 1 skips 3 of 4
        per_node = [_counter(e, "exec.skip.joinfilter.chunks")
                    for e in engines]
        assert sum(per_node) == 11, per_node

    def test_wire_frame_roundtrip(self):
        from cockroach_tpu.exec.joinfilter import JoinFilter
        rng = np.random.default_rng(5)
        keys = np.unique(rng.integers(0, 1 << 30, size=300,
                                      dtype=np.int64))
        f = JoinFilter("t", "k", lo=int(keys[0]), hi=int(keys[-1]),
                       keys=keys)
        g = JoinFilter.from_wire(f.to_wire())
        assert (g.table, g.col, g.lo, g.hi) == ("t", "k",
                                                f.lo, f.hi)
        assert np.array_equal(g.keys, keys)
        # oversized key sets degrade to a bloom on the wire: still
        # never false-negative
        big = np.arange(100_000, dtype=np.int64)
        h = JoinFilter.from_wire(
            JoinFilter("t", "k", lo=0, hi=99_999,
                       keys=big).to_wire())
        assert h.keys is None and h.bloom is not None
        assert bool(np.all(h.bloom.might_contain(big[:4096])))

    def test_frame_survives_dup_and_delay(self):
        """Per-link transport faults on the setup_flow frames that
        carry the join filter: duplicated/delayed delivery must not
        change rows or break the skip accounting."""
        from cockroach_tpu.kvserver.transport import LocalTransport
        from cockroach_tpu.rpc.context import FaultInjector

        inj = FaultInjector(seed=9)
        inj.set_rule(0, 1, dup=1.0)          # gateway -> node 1 dups
        inj.set_rule(0, 2, delay=1.0, delay_s=0.0)

        class FaultyTransport(LocalTransport):
            def send(self, frm, to, msg):
                if msg[0] == "setup_flow":
                    for _ in inj.plan(frm, to):
                        super().send(frm, to, msg)
                    return
                super().send(frm, to, msg)

        gw, engines, oracle = _fakedist(transport_cls=FaultyTransport)
        got = gw.run(DIST_Q)
        assert got.rows == oracle.execute(DIST_Q).rows
        assert sum(_counter(e, "exec.skip.joinfilter.chunks")
                   for e in engines) >= 11

    def test_dropped_setup_flow_fails_not_corrupts(self):
        """A dropped link loses the flow, and the gateway reports it
        as FlowUnavailable — never as wrong rows."""
        from cockroach_tpu.distsql.node import FlowUnavailable
        from cockroach_tpu.kvserver.transport import LocalTransport
        from cockroach_tpu.rpc.context import FaultInjector

        inj = FaultInjector(seed=10)
        inj.set_rule(0, 3, drop=1.0)

        class DropTransport(LocalTransport):
            def send(self, frm, to, msg):
                if msg[0] == "setup_flow":
                    for _ in inj.plan(frm, to):
                        super().send(frm, to, msg)
                    return
                super().send(frm, to, msg)

        gw, _, _ = _fakedist(transport_cls=DropTransport,
                             flow_timeout=1.5)
        with pytest.raises(FlowUnavailable):
            gw.run(DIST_Q)


# ---------------------------------------------------------------------------
# shuffle link faults (parallel/shuffle.py + distagg dispatch)
# ---------------------------------------------------------------------------

class TestShuffleLinkFaults:
    def test_plan_aggregation(self):
        from cockroach_tpu.parallel import shuffle
        from cockroach_tpu.rpc.context import FaultInjector
        inj = FaultInjector(seed=3)
        shuffle.install_link_faults(inj, 4)
        try:
            assert shuffle.link_fault_plan() == [0.0]
            inj.set_rule("shard:0", "shard:2", drop=1.0)
            assert shuffle.link_fault_plan() == []
            inj.clear_rules()
            inj.set_rule("shard:1", "shard:3", delay=1.0,
                         delay_s=0.02)
            assert shuffle.link_fault_plan() == [0.02]
            inj.clear_rules()
            inj.set_rule("shard:2", "shard:0", dup=1.0)
            assert len(shuffle.link_fault_plan()) == 2
        finally:
            shuffle.install_link_faults(None, 0)
        assert shuffle.link_fault_plan() is None

    def test_dispatch_drop_dup(self):
        from cockroach_tpu.parallel import distagg, shuffle
        from cockroach_tpu.rpc.context import FaultInjector
        inj = FaultInjector(seed=4)
        shuffle.install_link_faults(inj, 2)
        calls = []
        fn = distagg.queued_collective_call(
            lambda x: calls.append(x) or x)
        try:
            inj.set_rule("shard:0", "shard:1", drop=1.0)
            with pytest.raises(distagg.CollectiveFault):
                fn(7)
            assert calls == []
            inj.clear_rules()
            inj.set_rule("shard:1", "shard:0", dup=1.0)
            assert fn(9) == 9
            assert calls == [9, 9]  # duplicate dispatch, last kept
        finally:
            shuffle.install_link_faults(None, 0)
        assert fn(5) == 5


# ---------------------------------------------------------------------------
# prewarm from journaled shape buckets (exec/coldstart.py)
# ---------------------------------------------------------------------------

class TestPrewarmStreamed:
    def test_journal_entries_carry_buckets(self, tmp_path):
        from cockroach_tpu.exec import coldstart
        d = str(tmp_path)
        coldstart.journal_record(d, "SELECT 1", bucket=2048)
        coldstart.journal_record(d, "SELECT 1", bucket=2048)
        coldstart.journal_record(d, "SELECT 2", bucket=0)
        ents = coldstart.journal_entries(d, 10)
        assert ("SELECT 1", 2048, {}) in ents
        assert ("SELECT 2", 0, {}) in ents
        # back-compat: journal_top still returns bare texts
        assert "SELECT 1" in coldstart.journal_top(d, 10)

    def test_prewarm_compiles_streamed_join(self, private_compile_cache):
        """A streamed join lands in the shapes journal with its page
        bucket; a fresh prewarm must re-prepare it and exercise the
        page/combine/final executables without touching results."""
        eng = _fact_engine()
        want = eng.execute(JOIN_Q, _jsession(eng)).rows
        eng._exec_cache.clear()
        warmed = eng.prewarm(8)
        assert warmed >= 1
        got = eng.execute(JOIN_Q, _jsession(eng)).rows
        assert got == want

    @pytest.mark.slow
    def test_prewarm_compiles_spill_join(self, private_compile_cache):
        eng = _fact_engine()
        want = eng.execute(JOIN_Q, _jsession(eng, spill="on")).rows
        eng._exec_cache.clear()
        assert eng.prewarm(8) >= 1
        got = eng.execute(JOIN_Q, _jsession(eng, spill="on")).rows
        assert got == want
