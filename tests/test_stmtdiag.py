"""Round 13 acceptance: per-operator execution profiles and statement
diagnostics bundles.

- Arm a fingerprint (HTTP POST or SET statement_diagnostics); the
  next matching execution captures a JSON bundle — plan, per-operator
  profile, trace, settings/session vars, sketch stats, metric
  deltas — fetchable at /_status/stmtdiag/<id>.
- EXPLAIN ANALYZE (DEBUG) returns the same bundle inline; over a
  DistSQL gateway its profile carries node-tagged operator rows from
  every participating flow and the per-operator device_seconds sum to
  the statement's device_time_s (within 10%).
- The always-on coarse plane never changes results
  (sql.stmt_profile.enabled on/off is bit-identical) and feeds the
  application_name-keyed rollups at /_status/tenants.

Reference analogues: pkg/sql/stmtdiagnostics (activation registry),
execinfrapb.ComponentStats + execstats/traceanalyzer.go (per-processor
stats stitched into the bundle).
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

from cockroach_tpu.distsql.node import DistSQLNode, Gateway
from cockroach_tpu.exec import profile as prof
from cockroach_tpu.exec.engine import Engine
from cockroach_tpu.kvserver.transport import LocalTransport
from cockroach_tpu.models import tpch
from cockroach_tpu.server.node import Node, NodeConfig, _merge_tenants

ROWS = 360
DIST_ROWS = 600
Q = ("SELECT l_returnflag, count(*), sum(l_quantity) FROM lineitem "
     "GROUP BY l_returnflag ORDER BY l_returnflag")

BUNDLE_KEYS = {"sql", "fingerprint", "plan", "profile", "trace",
               "settings", "session_vars", "sketch_stats",
               "metric_deltas", "latency_s", "compile_s",
               "device_time_s"}


def _http_get(node, path: str):
    host, port = node.http_addr
    with urllib.request.urlopen(
            f"http://{host}:{port}{path}", timeout=10) as r:
        return r.read().decode()


def _http_post(node, path: str, payload: dict):
    host, port = node.http_addr
    req = urllib.request.Request(
        f"http://{host}:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.read().decode()


@pytest.fixture(scope="module")
def node():
    n = Node(NodeConfig(http_port=0, listen_port=0)).start()
    tpch.load(n.engine, sf=0.01, rows=ROWS)
    yield n
    n.stop()


class TestHttpArmCaptureFetch:
    def test_arm_capture_fetch_roundtrip(self, node):
        sql = "SELECT count(*) FROM lineitem WHERE l_quantity > 7"
        out = json.loads(_http_post(node, "/_status/stmtdiag",
                                    {"sql": sql}))
        rid, fp = out["request_id"], out["fingerprint"]
        assert "lineitem" in fp and "_" in fp  # literals stripped
        summary = json.loads(_http_get(node, "/_status/stmtdiag"))
        assert {"request_id": rid, "fingerprint": fp} \
            in summary["armed"]

        node.engine.execute(sql)
        summary = json.loads(_http_get(node, "/_status/stmtdiag"))
        assert not any(a["request_id"] == rid
                       for a in summary["armed"])
        assert any(b["id"] == rid for b in summary["bundles"])

        bundle = json.loads(_http_get(node,
                                      f"/_status/stmtdiag/{rid}"))
        assert BUNDLE_KEYS <= set(bundle)
        assert bundle["fingerprint"] == fp
        assert bundle["sql"] == sql
        assert bundle["profile"]["ops"], "empty operator profile"
        assert any("scan" in o["op"]
                   for o in bundle["profile"]["ops"])
        # the plan ships annotated with the profiled numbers
        assert any("device=" in ln for ln in bundle["plan"])

    def test_capture_is_one_shot(self, node):
        sql = "SELECT count(*) FROM lineitem WHERE l_quantity > 11"
        rid = json.loads(_http_post(
            node, "/_status/stmtdiag", {"sql": sql}))["request_id"]
        node.engine.execute(sql)
        node.engine.execute(sql)  # second run must not re-capture
        summary = json.loads(_http_get(node, "/_status/stmtdiag"))
        assert sum(1 for b in summary["bundles"]
                   if b["id"] == rid) == 1

    def test_arm_by_fingerprint(self, node):
        sql = "SELECT count(*) FROM lineitem WHERE l_linenumber = 3"
        fp = json.loads(_http_post(
            node, "/_status/stmtdiag", {"sql": sql}))["fingerprint"]
        # re-arming the SAME pending fingerprint reuses the request
        again = json.loads(_http_post(
            node, "/_status/stmtdiag", {"fingerprint": fp}))
        assert again["fingerprint"] == fp

    def test_fetch_errors(self, node):
        with pytest.raises(urllib.error.HTTPError) as e:
            _http_get(node, "/_status/stmtdiag/999999")
        assert e.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as e:
            _http_get(node, "/_status/stmtdiag/nope")
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _http_post(node, "/_status/stmtdiag", {"bogus": 1})
        assert e.value.code == 400


class TestSqlArm:
    def test_set_statement_diagnostics(self, node):
        eng = node.engine
        sql = "SELECT sum(l_quantity) FROM lineitem WHERE l_tax > 0.01"
        res = eng.execute(
            f"SET statement_diagnostics = '{sql}'")
        assert res.names == ["request_id", "fingerprint"]
        rid, fp = res.rows[0]
        eng.execute(sql)
        bundle = eng.stmtdiag.get(rid)
        assert bundle is not None and bundle["fingerprint"] == fp
        assert BUNDLE_KEYS <= set(bundle)
        # settings/session snapshots are real dicts, not stubs
        assert "sql.stmt_profile.enabled" in bundle["settings"]
        assert "application_name" in bundle["session_vars"]


class TestExplainAnalyzeDebugLocal:
    def test_inline_bundle_and_device_sum(self, node):
        res = node.engine.execute("EXPLAIN ANALYZE (DEBUG) " + Q)
        assert res.names == ["bundle"] and len(res.rows) == 1
        bundle = json.loads(res.rows[0][0])
        assert BUNDLE_KEYS <= set(bundle)
        dev = bundle["profile"]["device_time_s"]
        op_sum = sum(o["device_seconds"]
                     for o in bundle["profile"]["ops"])
        assert dev > 0
        # per-operator self times sum to the profiled wall (small
        # absolute slack keeps tiny-query noise from flaking the 10%)
        assert abs(op_sum - dev) <= 0.10 * dev + 2e-3, (op_sum, dev)
        # the inline bundle is also registered for later fetch
        assert node.engine.stmtdiag.get(bundle["id"]) is not None

    def test_explain_analyze_renders_profile_columns(self, node):
        res = node.engine.execute("EXPLAIN ANALYZE " + Q)
        text = "\n".join(r[0] for r in res.rows)
        assert "device=" in text
        assert "bytes=" in text


class TestProfileParityAndOverhead:
    def test_results_bit_identical_with_profiling_off(self, node):
        eng = node.engine
        on = eng.execute(Q)
        try:
            eng.settings.set("sql.stmt_profile.enabled", False)
            off = eng.execute(Q)
        finally:
            eng.settings.set("sql.stmt_profile.enabled", True)
        assert on.rows == off.rows  # exact, not approx

    def test_operator_profile_digest(self, node):
        out = node.engine.operator_profile(Q)
        assert out["top_ops"], out
        names = [t["op"] for t in out["top_ops"]]
        assert any("scan" in n or "aggregate" in n for n in names)
        assert out["wall_s"] > 0


class TestTenantRollups:
    def test_tenant_rollup_and_endpoint(self, node):
        eng = node.engine
        sa = eng.session()
        sa.vars.set("application_name", "tenant_a")
        sb = eng.session()
        sb.vars.set("application_name", "tenant_b")
        eng.execute(Q, sa)
        eng.execute(Q, sa)
        eng.execute(Q, sb)
        by_name = {t.app_name: t for t in eng.sqlstats.tenants()}
        assert by_name["tenant_a"].statements >= 2
        assert by_name["tenant_b"].statements >= 1
        assert by_name["tenant_a"].device_seconds >= 0.0
        body = json.loads(_http_get(node, "/_status/tenants"))
        names = {t["app_name"] for t in body["tenants"]}
        assert {"tenant_a", "tenant_b"} <= names

    def test_merge_tenants_sums_and_maxes(self):
        t = {"app_name": "a", "statements": 2, "failures": 0,
             "rows": 10, "device_seconds": 1.0, "bytes_moved": 100,
             "hbm_bytes_held": 500, "stall_seconds": 0.1}
        u = dict(t, statements=3, hbm_bytes_held=900,
                 device_seconds=2.0)
        merged = _merge_tenants(
            1, {"tenants": [t]}, {2: {"tenants": [u]}}, False)
        assert merged["cluster"] is True
        assert merged["partial"] is False
        assert merged["nodes"] == [1, 2]
        m = merged["tenants"][0]
        assert m["statements"] == 5
        assert m["device_seconds"] == pytest.approx(3.0)
        assert m["hbm_bytes_held"] == 900  # max, not sum

    def test_slow_trace_carries_tenant_tags(self, node):
        eng = node.engine
        s = eng.session()
        s.vars.set("application_name", "slowapp")
        eng.settings.set("sql.trace.slow_statement.threshold", 1e-9)
        try:
            eng.execute("SELECT count(*) FROM lineitem", s)
        finally:
            eng.settings.set(
                "sql.trace.slow_statement.threshold", 0.0)
        ent = eng.slow_traces[-1]
        assert ent["application_name"] == "slowapp"
        assert ent["session"].startswith("s")
        assert ent["fingerprint"]


class TestProfileSinkConcurrency:
    def test_concurrent_notes_accumulate_exactly(self):
        """_KernelTally discipline: 8 threads hammering one sink lose
        nothing."""
        sink = prof.ProfileSink()

        def worker():
            for _ in range(1000):
                sink.note("op", batches=1, bytes_uploaded=2)

        ts = [threading.Thread(target=worker) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        ents = {lbl: e for _tag, lbl, e in sink.entries()}
        assert ents["op"].batches == 8000
        assert ents["op"].bytes_uploaded == 16000

    def test_module_note_drops_without_active_sink(self):
        prof.note("nobody-listening", batches=1)  # must not raise

    def test_nested_activation_restores_outer(self):
        outer, inner = prof.ProfileSink(), prof.ProfileSink()
        with prof.active(outer):
            with prof.active(inner, fine=True):
                assert prof.current() is inner
                assert prof.requested()
            assert prof.current() is outer
            assert not prof.requested()
        assert prof.current() is None


class TestCloseLifecycle:
    def test_engine_close_clears_diagnostics(self):
        eng = Engine()
        eng.execute("CREATE TABLE t (a INT)")
        eng.execute("INSERT INTO t VALUES (1), (2)")
        rid = eng.stmtdiag.arm("SELECT count(*) FROM t")["request_id"]
        eng.execute("SELECT count(*) FROM t")
        assert eng.stmtdiag.get(rid) is not None
        eng.close()
        assert eng.stmtdiag.get(rid) is None
        assert eng.stmtdiag.summary() == {"armed": [], "bundles": []}


def _slice(cols, lo, hi):
    return {k: v[lo:hi] for k, v in cols.items()}


@pytest.fixture(scope="module")
def fakedist():
    """3 data nodes with lineitem row-sharded over the local
    transport, one gateway with the schema but no rows — the
    distributed plane the DEBUG bundle must profile node-tagged."""
    li = tpch.gen_lineitem(0.01, rows=DIST_ROWS)
    transport = LocalTransport()
    bounds = [0, DIST_ROWS // 3, 2 * DIST_ROWS // 3, DIST_ROWS]
    nodes = []
    for i in range(4):
        eng = Engine()
        eng.execute(tpch.DDL["lineitem"])
        if i > 0:
            eng.store.insert_columns(
                "lineitem", _slice(li, bounds[i - 1], bounds[i]),
                eng.clock.now())
        nodes.append(DistSQLNode(i, eng, transport))
    gw = Gateway(nodes[0], [1, 2, 3])
    oracle = Engine()
    tpch.load(oracle, sf=0.01, rows=DIST_ROWS)
    return gw, oracle


class TestDistributedDebugBundle:
    def test_plain_run_matches_oracle(self, fakedist):
        gw, oracle = fakedist
        got, want = gw.run(Q), oracle.execute(Q)
        assert len(got.rows) == len(want.rows)
        for rg, rw in zip(got.rows, want.rows):
            for a, b in zip(rg, rw):
                if isinstance(b, float):
                    assert a == pytest.approx(b)
                else:
                    assert a == b

    def test_debug_bundle_node_tagged_and_sums(self, fakedist):
        gw, _ = fakedist
        before = gw.run(Q).rows
        res = gw.run("EXPLAIN ANALYZE (DEBUG) " + Q)
        bundle = json.loads(res.rows[0][0])
        assert bundle["gateway"] == 0
        assert bundle["rows_returned"] == 3
        ops = bundle["profile"]["ops"]
        # node-tagged per-operator rows from >= 2 NON-gateway nodes
        remote = {o.get("node") for o in ops} - {0, None}
        assert len(remote) >= 2, ops
        # ISSUE acceptance: node-tagged per-operator device_seconds
        # sum to the statement's device_time_s within 10%
        dev = bundle["profile"]["device_time_s"]
        op_sum = sum(o["device_seconds"] for o in ops)
        assert dev > 0
        assert abs(op_sum - dev) <= 0.10 * dev, (op_sum, dev)
        # shuffle bytes attributed at the gather site
        assert any(o["bytes_shuffled"] > 0 for o in ops)
        # ... and the profiled run leaves plain execution untouched
        assert gw.run(Q).rows == before

    def test_debug_does_not_leak_fine_bit(self, fakedist):
        gw, _ = fakedist
        gw.run("EXPLAIN ANALYZE (DEBUG) " + Q)
        assert not prof.requested()
        assert prof.current() is None
