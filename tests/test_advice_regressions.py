"""Regression tests for advisor findings (round 1 ADVICE.md)."""

import pytest

from cockroach_tpu.kvserver.cluster import AmbiguousResultError, Cluster
from cockroach_tpu.kvserver.raft import Entry, Message, MsgType, RaftNode
from cockroach_tpu.kvserver.transport import LocalTransport


def test_remove_live_leaseholder_does_not_wedge_range():
    """ADVICE medium: removing the live leaseholder used to leave the
    survivors' lease record naming a live, unfenced node forever, so no
    replica could ever re-acquire. change_replicas must transfer the
    lease to a survivor first."""
    c = Cluster(n_nodes=4)
    c.create_range(b"a", b"z", replicas=[1, 2, 3])
    c.put(b"k1", b"v1")
    lh = c.leaseholder(1)
    assert lh is not None
    c.change_replicas(1, add=4, remove=lh)
    c.pump(10)
    # the range must still be fully usable: reads, writes, a leaseholder
    assert c.get(b"k1") == b"v1"
    c.put(b"k2", b"v2")
    assert c.get(b"k2") == b"v2"
    new_lh = c.leaseholder(1)
    assert new_lh is not None and new_lh != lh
    assert lh not in c.descriptors[1].replicas


def test_acquire_lease_treats_removed_holder_as_fenced():
    """Defense in depth: even if a lease record names a node that is no
    longer a member of the range, survivors can re-acquire."""
    c = Cluster(n_nodes=4)
    c.create_range(b"a", b"z", replicas=[1, 2, 3])
    lh = c.ensure_lease(1)
    # force a stale lease record naming a non-member (bypassing the
    # transfer in change_replicas, as if the transfer were lost)
    survivors = [n for n in (1, 2, 3) if n != lh]
    for nid in survivors + [lh]:
        rep = c.stores[nid].replicas.get(1)
        if rep is not None:
            rep.desc.replicas = [n for n in rep.desc.replicas if n != lh]
            rep.raft.update_membership(rep.desc.replicas)
    c.descriptors[1].replicas = [n for n in c.descriptors[1].replicas
                                 if n != lh]
    c.stores[lh].remove_replica(1)
    # old holder stays live and unfenced — but is no longer a member
    assert c.liveness.is_live(lh)
    # survivors must elect a leader now that the old one is gone
    assert c.pump_until(lambda: any(
        c.stores[n].replicas[1].raft.is_leader() for n in survivors), 300)
    got = c.ensure_lease(1)
    assert got in survivors


def test_heartbeat_does_not_commit_unverified_suffix():
    """ADVICE low: a heartbeat (empty APPEND) must not advance commit
    past the verified prefix — the follower's own divergent old-term
    suffix is not proven to match the leader's log."""
    import random

    n = RaftNode(2, [1, 2, 3], rng=random.Random(0))
    # follower holds a stale term-1 suffix at indexes 1..3
    n.log.append([Entry(1, 1, b"a"), Entry(1, 2, b"stale"),
                  Entry(1, 3, b"stale")])
    # new term-2 leader heartbeats with prev=(1,term 1) and commit=3;
    # only index 1 is verified by the prev check
    n.step(Message(MsgType.APPEND, frm=1, to=2, term=2,
                   log_index=1, log_term=1, entries=[], commit=3))
    assert n.commit == 1, n.commit


def test_quorum_loss_surfaces_ambiguous_result():
    """ADVICE low: a proposal handed to raft that times out is
    ambiguous (it may still commit), not definitely failed."""
    c = Cluster(n_nodes=3)
    c.create_range(b"a", b"z", replicas=[1, 2, 3])
    c.put(b"k", b"v")                      # establishes a leader/lease
    lh = c.leaseholder(1)
    rep = c.stores[lh].replicas[1]
    for nid in (1, 2, 3):
        if nid != lh:
            c.stop_node(nid)
    with pytest.raises(AmbiguousResultError):
        c.propose_and_wait(rep, {"kind": "batch", "ops": [{
            "op": "put", "key": "k2", "value": "v2",
            "ts": [c.clock.now().wall, 0]}]}, max_iter=10)


def test_transport_rejects_conflicting_registration():
    """ADVICE low: silent handler overwrite would let a Store and a
    DistSQL node clobber each other's delivery."""
    t = LocalTransport()

    def h1(frm, msg):
        pass

    def h2(frm, msg):
        pass

    t.register(1, h1)
    t.register(1, h1)            # same handler: fine (restart paths)
    with pytest.raises(ValueError):
        t.register(1, h2)


# ---------------------------------------------------------------------------
# round 2 ADVICE.md findings
# ---------------------------------------------------------------------------

@pytest.fixture
def eng():
    from cockroach_tpu.exec.engine import Engine
    return Engine()


class TestFKRestrictOverfire:
    def test_update_unrelated_ref_column(self, eng):
        """ADVICE high: updating one referenced column must not probe
        OTHER FKs (e.g. one on the PK) whose referencing rows are
        untouched."""
        eng.execute("CREATE TABLE parent (id INT PRIMARY KEY, "
                    "a INT UNIQUE, b INT UNIQUE)")
        eng.execute("CREATE TABLE child_a (x INT PRIMARY KEY, "
                    "ra INT REFERENCES parent (a))")
        eng.execute("CREATE TABLE child_b (x INT PRIMARY KEY, "
                    "rb INT REFERENCES parent (b))")
        eng.execute("INSERT INTO parent VALUES (1, 10, 100)")
        eng.execute("INSERT INTO child_a VALUES (1, 10)")
        # b is unreferenced: updating it must succeed even though
        # child_a references column a of the same row
        r = eng.execute("UPDATE parent SET b = 200 WHERE id = 1")
        assert r.row_count == 1
        # but updating a (still referenced) must fail
        from cockroach_tpu.exec.engine import EngineError
        with pytest.raises(EngineError, match="foreign key"):
            eng.execute("UPDATE parent SET a = 11 WHERE id = 1")

    def test_upsert_unrelated_ref_column(self, eng):
        """Same over-fire through the UPSERT path."""
        eng.execute("CREATE TABLE parent (id INT PRIMARY KEY, "
                    "a INT UNIQUE, b INT UNIQUE)")
        eng.execute("CREATE TABLE child_a (x INT PRIMARY KEY, "
                    "ra INT REFERENCES parent (a))")
        eng.execute("INSERT INTO parent VALUES (1, 10, 100)")
        eng.execute("INSERT INTO child_a VALUES (1, 10)")
        r = eng.execute("UPSERT INTO parent VALUES (1, 10, 200)")
        assert r.row_count == 1
        rows = eng.execute("SELECT b FROM parent WHERE id = 1").rows
        assert rows == [(200,)]


class TestSelfRefBulkDelete:
    def test_delete_parent_and_child_together(self, eng):
        """ADVICE medium: a bulk delete removing both parent and child
        of a self-referential FK in one statement is legal in pg."""
        eng.execute("CREATE TABLE emp (id INT PRIMARY KEY, "
                    "mgr INT REFERENCES emp (id))")
        eng.execute("INSERT INTO emp VALUES (1, NULL), (2, 1), (3, 2)")
        r = eng.execute("DELETE FROM emp WHERE id >= 1")
        assert r.row_count == 3
        assert eng.execute("SELECT count(*) FROM emp").rows == [(0,)]

    def test_delete_parent_and_child_in_explicit_txn(self, eng):
        """Same statement inside BEGIN: the txn-buffered (pending) rows
        being deleted must be excluded from the probe too."""
        eng.execute("CREATE TABLE emp2 (id INT PRIMARY KEY, "
                    "mgr INT REFERENCES emp2 (id))")
        s = eng.session()
        eng.execute("BEGIN", s)
        eng.execute("INSERT INTO emp2 VALUES (1, NULL), (2, 1)", s)
        r = eng.execute("DELETE FROM emp2 WHERE id >= 1", s)
        assert r.row_count == 2
        eng.execute("COMMIT", s)
        assert eng.execute("SELECT count(*) FROM emp2").rows == [(0,)]

    def test_partial_delete_still_restricted(self, eng):
        from cockroach_tpu.exec.engine import EngineError
        eng.execute("CREATE TABLE emp (id INT PRIMARY KEY, "
                    "mgr INT REFERENCES emp (id))")
        eng.execute("INSERT INTO emp VALUES (1, NULL), (2, 1)")
        # deleting only the referenced manager must still fail
        with pytest.raises(EngineError, match="foreign key"):
            eng.execute("DELETE FROM emp WHERE id = 1")


class TestVolatileFoldGuards:
    def test_nextval_in_select_with_from_rejected(self, eng):
        """ADVICE medium: nextval() folded once per statement, so every
        row of SELECT nextval('s') FROM t got the SAME value; reject
        instead of silently corrupting."""
        eng.execute("CREATE SEQUENCE sq")
        eng.execute("CREATE TABLE t3 (x INT PRIMARY KEY)")
        eng.execute("INSERT INTO t3 VALUES (1), (2), (3)")
        with pytest.raises(Exception, match="FROM clause"):
            eng.execute("SELECT nextval('sq') FROM t3")
        # the sequence must not have advanced
        assert eng.execute("SELECT nextval('sq')").rows == [(1,)]

    def test_random_with_from_rejected(self, eng):
        eng.execute("CREATE TABLE t4 (x INT PRIMARY KEY)")
        eng.execute("INSERT INTO t4 VALUES (1), (2)")
        with pytest.raises(Exception, match="FROM clause"):
            eng.execute("SELECT random() FROM t4")
        # without FROM both stay usable
        assert len(eng.execute("SELECT random()").rows) == 1

    def test_dml_where_volatile_still_works(self, eng):
        """The guard is for executed SELECTs only: UPDATE/DELETE with
        random() in WHERE (no FROM clause) keep the documented
        per-statement fold."""
        eng.execute("CREATE TABLE t6 (id INT PRIMARY KEY, x FLOAT)")
        eng.execute("INSERT INTO t6 VALUES (1, 0.0)")
        assert eng.execute(
            "UPDATE t6 SET x = random() WHERE id = 1").row_count == 1
        assert eng.execute(
            "DELETE FROM t6 WHERE random() < 2.0").row_count == 1

    def test_drop_table_rejected_with_pending_writes(self, eng):
        """DROP TABLE shares the TRUNCATE hazard: a txn committing
        after the drop would crash _publish on the missing table."""
        from cockroach_tpu.exec.engine import EngineError
        eng.execute("CREATE TABLE td1 (x INT PRIMARY KEY)")
        s = eng.session()
        eng.execute("BEGIN", s)
        eng.execute("INSERT INTO td1 VALUES (1)", s)
        with pytest.raises(EngineError, match="DROP TABLE"):
            eng.execute("DROP TABLE td1")
        eng.execute("ROLLBACK", s)
        eng.execute("DROP TABLE td1")

    def test_explain_still_allowed(self, eng):
        eng.execute("CREATE SEQUENCE sq2")
        eng.execute("CREATE TABLE t5 (x INT PRIMARY KEY)")
        eng.execute("EXPLAIN SELECT nextval('sq2') FROM t5")
        # EXPLAIN must not have allocated
        assert eng.execute("SELECT nextval('sq2')").rows == [(1,)]


class TestTruncateVsOpenTxn:
    def test_truncate_rejected_with_pending_writes(self, eng):
        """ADVICE low: a txn begun before TRUNCATE could commit after
        it and resurrect rows; refuse while open txns hold buffered
        effects on the table."""
        from cockroach_tpu.exec.engine import EngineError
        eng.execute("CREATE TABLE tt (x INT PRIMARY KEY)")
        eng.execute("INSERT INTO tt VALUES (1)")
        s = eng.session()
        eng.execute("BEGIN", s)
        eng.execute("INSERT INTO tt VALUES (2)", s)
        with pytest.raises(EngineError, match="TRUNCATE"):
            eng.execute("TRUNCATE tt")
        eng.execute("COMMIT", s)
        # after commit the truncate goes through
        eng.execute("TRUNCATE tt")
        assert eng.execute("SELECT count(*) FROM tt").rows == [(0,)]


# ---------------------------------------------------------------------------
# round 3 ADVICE.md findings
# ---------------------------------------------------------------------------

class TestCopyProtocolSync:
    """ADVICE medium: a parse error mid-COPY must drain the client's
    remaining CopyData/CopyDone frames before erroring, or the serve
    loop reads them as unknown frontend messages and the connection is
    desynced."""

    @pytest.fixture(scope="class")
    def node(self):
        from cockroach_tpu.server import Node, NodeConfig
        with Node(NodeConfig()) as n:
            yield n

    def test_bad_column_count_keeps_connection_usable(self, node):
        from cockroach_tpu.cli import PgClient, PgError
        c = PgClient(*node.sql_addr)
        c.query("CREATE TABLE cps (k INT PRIMARY KEY, v STRING)")
        with pytest.raises(PgError):
            # 3 fields into a 2-column COPY, with MORE data after the
            # bad row — all of it must be drained
            c.copy_in("COPY cps (k, v) FROM STDIN",
                      ["1\ta", "2\tb\textra", "3\tc", "4\td"])
        # the NEXT query must work (previously: 'unknown frontend
        # message' desync)
        _, rows, _ = c.query("SELECT 42")
        assert rows == [("42",)]
        c.close()

    def test_null_text_for_int_column_rejected(self, node):
        """ADVICE low: the literal text 'NULL' is invalid input for an
        int column (pg only accepts \\N), never SQL NULL."""
        from cockroach_tpu.cli import PgClient, PgError
        c = PgClient(*node.sql_addr)
        c.query("CREATE TABLE cpn (k INT PRIMARY KEY, n INT)")
        with pytest.raises(PgError) as ei:
            c.copy_in("COPY cpn (k, n) FROM STDIN", ["1\tNULL"])
        assert ei.value.sqlstate == "22P02"
        # real NULL via \N still works, connection still usable
        assert c.copy_in("COPY cpn (k, n) FROM STDIN",
                         ["1\t\\N"]) == "COPY 1"
        _, rows, _ = c.query("SELECT k, n FROM cpn")
        assert rows == [("1", None)]
        c.close()

    def test_malformed_numeric_rejected(self, node):
        from cockroach_tpu.cli import PgClient, PgError
        c = PgClient(*node.sql_addr)
        c.query("CREATE TABLE cpm (k INT PRIMARY KEY)")
        with pytest.raises(PgError) as ei:
            c.copy_in("COPY cpm (k) FROM STDIN", ["1); DROP TABLE x--"])
        assert ei.value.sqlstate == "22P02"
        _, rows, _ = c.query("SELECT count(*) FROM cpm")
        assert rows == [("0",)]
        c.close()


class TestHiddenSortKeyOrderability:
    """ADVICE medium: a hidden sort key (__ordN) for a datum-typed
    expression must hit the same orderability check as visible keys —
    not silently sort by dictionary insertion code."""

    def test_order_by_hidden_array_expr_rejected(self, eng):
        from cockroach_tpu.sql.planner import PlanError
        eng.execute("CREATE TABLE arr (k INT PRIMARY KEY, a INT[])")
        eng.execute("INSERT INTO arr VALUES (1, ARRAY[9]), "
                    "(2, ARRAY[1,2]), (3, ARRAY[1])")
        with pytest.raises(PlanError, match="ORDER BY"):
            eng.execute("SELECT k FROM arr ORDER BY a || ARRAY[1]")

    def test_order_by_visible_int_still_works(self, eng):
        eng.execute("CREATE TABLE arr2 (k INT PRIMARY KEY, a INT[])")
        eng.execute("INSERT INTO arr2 VALUES (2, ARRAY[1]), "
                    "(1, ARRAY[2])")
        r = eng.execute("SELECT k FROM arr2 ORDER BY k")
        assert [row[0] for row in r.rows] == [1, 2]


class TestDatumCompareBindError:
    """ADVICE low: WHERE a = 'not-an-array' must surface a BindError
    (the engine's SQL error classes), not a raw DatumError."""

    def test_invalid_array_text_is_bind_error(self, eng):
        from cockroach_tpu.sql.binder import BindError
        eng.execute("CREATE TABLE da (k INT PRIMARY KEY, a INT[])")
        eng.execute("INSERT INTO da VALUES (1, ARRAY[1])")
        with pytest.raises(BindError):
            eng.execute("SELECT k FROM da WHERE a = 'not-an-array'")

    def test_valid_array_text_still_compares(self, eng):
        eng.execute("CREATE TABLE da2 (k INT PRIMARY KEY, a INT[])")
        eng.execute("INSERT INTO da2 VALUES (1, ARRAY[1,2]), "
                    "(2, ARRAY[3])")
        r = eng.execute("SELECT k FROM da2 WHERE a = '{1,2}'")
        assert r.rows == [(1,)]


class TestStagingPushGuard:
    """ADVICE low: a pusher's blind poison must not finalize a STAGING
    record as aborted — only recovery (write-set proof) or the
    coordinator may; the poison fails with existing='staging' and the
    pusher runs recovery."""

    def test_plain_abort_cannot_finalize_staging(self):
        from cockroach_tpu.kv.disttxn import (DistTxn, propose_txn_record,
                                              read_txn_record)
        from cockroach_tpu.kvserver.cluster import Cluster
        c = Cluster(n_nodes=3)
        c.create_range(b"a", b"n", replicas=[1, 2, 3])
        c.create_range(b"n", b"z", replicas=[1, 2, 3])
        t = DistTxn(c)
        t.put(b"apple", b"1")
        res = propose_txn_record(
            c, t.anchor, t.id, "staging", c.clock.now(),
            writes=["apple"])
        assert res["ok"]
        # a blind poison (no finalize authority) must FAIL
        res = propose_txn_record(c, t.anchor, t.id, "aborted",
                                 c.clock.now())
        assert not res.get("ok") and res.get("existing") == "staging"
        rec = read_txn_record(c, t._meta())
        assert rec["status"] == "staging"
        # recovery (finalize authority) may
        res = propose_txn_record(c, t.anchor, t.id, "aborted",
                                 c.clock.now(), finalize_staging=True)
        assert res["ok"]

    def test_pusher_commits_implicitly_committed_staging(self):
        """The full path: reader pushes an intent of a txn whose
        staging record + all declared writes are applied — the verdict
        must be COMMITTED (recovery), not a spurious abort."""
        from cockroach_tpu.kv.disttxn import (DistTxn, propose_txn_record,
                                              read_txn_record)
        from cockroach_tpu.kvserver.cluster import Cluster
        c = Cluster(n_nodes=3)
        c.create_range(b"a", b"n", replicas=[1, 2, 3])
        c.create_range(b"n", b"z", replicas=[1, 2, 3])
        t = DistTxn(c)
        t.put(b"apple", b"1")
        t.put(b"pear", b"2")
        res = propose_txn_record(
            c, t.anchor, t.id, "staging", c.clock.now(),
            writes=[k.decode("latin1") for k in t.intents])
        assert res["ok"]
        c.pump(5)
        reader = DistTxn(c)
        assert reader.get(b"apple") == b"1"
        rec = read_txn_record(c, t._meta())
        assert rec is not None and rec["status"] == "committed"


class TestCrossGatewayTxnPush:
    """Round-4 advisor (high + medium): a gateway pushing an UNKNOWN
    foreign txn id must consult the REPLICATED anchor-range record —
    never map a live txn to ABORTED — and the record read must route
    over the fabric (NetCluster's stores map holds only the local
    store; indexing a remote leaseholder id raised KeyError)."""

    def _two_netclusters(self):
        import time

        from cockroach_tpu.kvserver.netcluster import NetCluster
        n1 = NetCluster(1)
        n1.bootstrap()
        n2 = NetCluster(2, join={1: n1.addr})
        n2.join()
        deadline = time.time() + 20
        while time.time() < deadline:
            n1.replicate_queue_scan()
            if sorted(n1.descriptors[1].replicas)[:2] == [1, 2]:
                break
            time.sleep(0.05)
        return n1, n2

    def test_live_foreign_txn_not_aborted(self):
        from cockroach_tpu.kv.concurrency import (TxnRetryError,
                                                  TxnStatus)
        from cockroach_tpu.kv.rangekv import ClusterKVStore
        from cockroach_tpu.kv.txn import Txn
        n1, n2 = self._two_netclusters()
        try:
            store_a = ClusterKVStore(n1)
            store_b = ClusterKVStore(n2)
            ta = Txn(store_a)
            ta.put(b"\x01conflict", b"va")      # live intent, no record
            tb = Txn(store_b)
            # the push must see PENDING (recent foreign intent), not
            # silently abort the live txn
            rec = store_b.txns.push(ta.meta, push_abort=True)
            assert rec.status == TxnStatus.PENDING
            with pytest.raises(TxnRetryError):
                tb.put(b"\x01conflict", b"vb")
            tb.rollback()
            # the live txn commits untouched
            ta.commit()
            tc = Txn(store_b)
            assert tc.get(b"\x01conflict") == b"va"
            tc.commit()
        finally:
            n1.stop()
            n2.stop()

    def test_committed_foreign_record_honored(self):
        """A staging/committed replicated record finalizes the push
        via the recovery protocol instead of guessing."""
        from cockroach_tpu.kv.concurrency import TxnStatus
        from cockroach_tpu.kv.disttxn import propose_txn_record
        from cockroach_tpu.kv.rangekv import ClusterKVStore
        from cockroach_tpu.kv.txn import Txn
        n1, n2 = self._two_netclusters()
        try:
            store_a = ClusterKVStore(n1)
            store_b = ClusterKVStore(n2)
            ta = Txn(store_a)
            ta.put(b"\x01rec", b"va")
            res = propose_txn_record(n1, b"\x01rec", ta.meta.id,
                                     "committed", n1.clock.now())
            assert res["ok"]
            rec = store_b.txns.push(ta.meta, push_abort=True)
            assert rec.status == TxnStatus.COMMITTED
        finally:
            n1.stop()
            n2.stop()
