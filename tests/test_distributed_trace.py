"""PR 2 acceptance: distributed observability end-to-end.

One 3-node socket-replicated NetCluster (raft over TCP) carries the
lineitem rows; leases are spread so every node leads a third of the
table; a DistSQL gateway riding a started server Node (HTTP status
endpoints) runs EXPLAIN ANALYZE over a distributed GROUP BY. The
acceptance bar (ISSUE.md):

- the rendered trace shows node-tagged spans from >= 2 non-gateway
  nodes (remote flow recordings shipped back over the wire and
  stitched under the gateway's recording);
- /_status/vars exposes nonzero rpc.*, distsender.*, breaker.* and
  shuffle.bytes* families after the query;
- /debug/tracez serves the slow-statement ring and
  /_status/statements the per-fingerprint stats.

Reference: pkg/util/tracing recording propagation on BatchResponse /
SetupFlow, pkg/server/status (vars, statements), tracez snapshots.
"""

import json
import re
import threading
import time
import urllib.request

import pytest

from cockroach_tpu.distsql.node import DistSQLNode, Gateway
from cockroach_tpu.exec.engine import Engine
from cockroach_tpu.kv.distsender import BatchRequest, DistSender
from cockroach_tpu.kv.rowfetch import RangeTable
from cockroach_tpu.kvserver.netcluster import NetCluster, _TimeoutError
from cockroach_tpu.models import tpch
from cockroach_tpu.rpc.context import FaultInjector, SocketTransport
from cockroach_tpu.server.node import (Node, NodeConfig,
                                       register_status_sources)

ROWS = 360
Q = ("SELECT l_returnflag, count(*), sum(l_quantity) FROM lineitem "
     "GROUP BY l_returnflag ORDER BY l_returnflag")


def _http_get(node, path: str) -> str:
    host, port = node.http_addr
    with urllib.request.urlopen(
            f"http://{host}:{port}{path}", timeout=10) as r:
        return r.read().decode()


def _whole_scrape(node, path: str, within: float = 30.0) -> dict:
    """A ?cluster=1 scrape that reached every peer. The fan-out asks
    only peers whose replicated liveness record is unexpired at the
    scraped node's clock and gives each 2 s, so on a loaded host a
    late heartbeat or reply makes a scrape `partial`, as it should:
    wait for the next whole one (a renewal), not for one moment's
    liveness. The last body is returned either way."""
    deadline = time.time() + within
    while True:
        body = json.loads(_http_get(node, path))
        if not body["partial"] or time.time() > deadline:
            return body
        time.sleep(0.2)


@pytest.fixture(scope="module")
def obs():
    oracle = Engine()
    tpch.load(oracle, sf=0.01, rows=ROWS)

    inj = FaultInjector(seed=7)
    n1 = NetCluster(1, injector=inj)
    n1.bootstrap()
    n2 = NetCluster(2, join={1: n1.addr}, injector=inj)
    n2.join()
    n3 = NetCluster(3, join={1: n1.addr}, injector=inj)
    n3.join()
    ncs = {1: n1, 2: n2, 3: n3}
    deadline = time.time() + 15
    while time.time() < deadline:
        n1.replicate_queue_scan()
        if sorted(n1.descriptors[1].replicas) == [1, 2, 3]:
            break
        time.sleep(0.05)
    assert sorted(n1.descriptors[1].replicas) == [1, 2, 3]

    # the status node: its engine is the gateway engine, so flow /
    # shuffle / distsql metrics land on the same /_status/vars page
    # as the SQL metrics
    node = Node(NodeConfig(listen_port=0, http_port=0)).start()
    reg = node.engine.metrics
    n1.attach_metrics(reg)
    node.engine.execute(tpch.DDL["lineitem"])
    # cluster-wide status plane: the gateway node answers for n1;
    # a second HTTP node rides n2 (so ?cluster=1 can be scraped from
    # a NON-gateway node); n3's engine joins the plane directly
    node.enable_cluster_status(n1)
    node2 = Node(NodeConfig(listen_port=0, http_port=0)).start()
    node2.enable_cluster_status(n2)

    # DistSQL plane: its own socket mesh (ids 0..3), one pump thread
    # per data node, each data node scoped to ITS NetCluster view
    txs = [SocketTransport(i) for i in range(4)]
    for a in txs:
        for b in txs:
            if a is not b:
                a.connect(b.node_id, b.addr)
    stop = threading.Event()
    dnodes = [DistSQLNode(0, node.engine, txs[0], cluster=n1)]
    engines = []
    for i in range(1, 4):
        e = Engine()
        e.execute(tpch.DDL["lineitem"])
        engines.append(e)
        dnodes.append(DistSQLNode(i, e, txs[i], cluster=ncs[i]))
    register_status_sources(n3, engines[2])
    for i in range(1, 4):
        def pump(t=txs[i]):
            while not stop.is_set():
                t.deliver_all()
                time.sleep(0.002)
        threading.Thread(target=pump, daemon=True).start()

    # lineitem into the replicated range plane, split in thirds, one
    # lease per node so PartitionSpans lands a flow on each of them
    schema = node.engine.store.table("lineitem").schema
    rt = RangeTable(n1, schema)
    lo, hi = rt.codec.span()
    for frac in (b"\x40", b"\x80"):
        n1.split_range(lo + frac)
    td = oracle.store.table("lineitem")
    rows = []
    for chunk in td.chunks:
        for ri in range(chunk.n):
            rows.append(oracle.store.extract_row(td, chunk, ri))
    rt.insert_rows(rows)
    rid2 = n1.range_for_key(lo + b"\x40").range_id
    rid3 = n1.range_for_key(lo + b"\x80").range_id
    deadline = time.time() + 10
    while time.time() < deadline:
        if rid2 in n2.store.replicas and rid3 in n3.store.replicas:
            break
        time.sleep(0.05)
    assert n2.acquire_lease(rid2, 2)
    assert n3.acquire_lease(rid3, 3)

    # distsender.* traffic: routed writes + reads over the fabric
    ds = DistSender(n1, metrics=reg)
    ds.send(BatchRequest().put(b"\x01obs", b"v"))
    assert ds.send(BatchRequest().get(b"\x01obs")) == [b"v"]

    # breaker.* traffic: partition a peer, let one RPC time out (the
    # per-peer breaker trips), then heal
    inj.partition(1, 3)
    with pytest.raises(_TimeoutError):
        n1.call(3, "read", {"range_id": 1, "op": "get", "key": "x",
                            "ts": n1.clock.now().to_int()},
                timeout=0.5)
    inj.heal()
    assert n1.peer_breaker(3).trip_count >= 1
    n1.peer_breaker(3).reset()  # clean slate for the status fan-out

    # the distributed GROUP BY, plain and under EXPLAIN ANALYZE
    gw = Gateway(dnodes[0], [1, 2, 3], cluster=n1)
    want = oracle.execute(Q)
    got = gw.run(Q)
    ea = "\n".join(r[0] for r in
                   gw.run("EXPLAIN ANALYZE " + Q).rows)

    # slow-statement ring + sqlstats for the debug endpoints
    node.engine.settings.set(
        "sql.trace.slow_statement.threshold", 1e-9)
    node.engine.execute("SELECT count(*) FROM lineitem")

    out = {
        "node": node, "node2": node2, "reg": reg, "ea": ea,
        "got": got.rows, "want": want.rows,
        "gw": gw, "n1": n1, "n2": n2, "inj": inj,
        "vars": _http_get(node, "/_status/vars"),
        "tracez": json.loads(_http_get(node, "/debug/tracez")),
        "stmts": json.loads(_http_get(node, "/_status/statements")),
    }
    yield out
    stop.set()
    for t in txs:
        t.close()
    node.stop()
    node2.stop()
    for n in ncs.values():
        n.stop()


def _parse_vars(text: str):
    """Parse Prometheus text exposition: {name: [(labels, value)]},
    {name: type}. Raises on malformed lines."""
    samples: dict = {}
    types: dict = {}
    sample_re = re.compile(
        r'^([a-z_][a-z0-9_]*)(\{le="[^"]+"\})? (-?[0-9.eE+-]+|'
        r'-?inf|nan)$')
    for ln in text.splitlines():
        if not ln.strip():
            continue
        if ln.startswith("# HELP "):
            assert re.match(r"^# HELP [a-z_][a-z0-9_]* \S", ln), ln
            continue
        if ln.startswith("# TYPE "):
            _, _, name, kind = ln.split(" ", 3)
            assert kind in ("counter", "gauge", "histogram"), ln
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = kind
            continue
        m = sample_re.match(ln)
        assert m, f"malformed sample line: {ln!r}"
        name, labels, val = m.group(1), m.group(2), float(m.group(3))
        samples.setdefault(name, []).append((labels, val))
    return samples, types


class TestDistributedTrace:
    def test_explain_analyze_renders_remote_node_spans(self, obs):
        ea = obs["ea"]
        assert "rows returned: 3" in ea
        # flow recordings shipped back from >= 2 NON-gateway nodes,
        # each tagged with the node that produced it
        remote = {int(m) for m in re.findall(r"node=(\d+)", ea)}
        assert len(remote - {0}) >= 2, ea
        assert "flow" in ea and "gateway=0" in ea

    def test_distributed_groupby_matches_oracle(self, obs):
        assert len(obs["got"]) == len(obs["want"])
        for g, w in zip(obs["got"], obs["want"]):
            for gv, wv in zip(g, w):
                if isinstance(wv, float):
                    assert gv == pytest.approx(wv)
                else:
                    assert gv == wv

    def test_status_vars_families_nonzero(self, obs):
        samples, _ = _parse_vars(obs["vars"])

        def family_total(prefix):
            return sum(v for name, pairs in samples.items()
                       if name.startswith(prefix)
                       for _, v in pairs)

        assert family_total("rpc_") > 0            # fabric frames
        assert family_total("distsender_") > 0     # routed batches
        assert family_total("breaker_") > 0        # the tripped peer
        assert family_total("shuffle_bytes") > 0   # flow streams
        assert family_total("distsql_flows_launched") > 0

    def test_status_vars_exposition_lint(self, obs):
        """Format lint over the real scrape: every sample typed,
        histograms cumulative with a +Inf bucket equal to _count."""
        samples, types = _parse_vars(obs["vars"])
        for name in samples:
            base = re.sub(r"_(bucket|sum|count)$", "", name)
            assert name in types or base in types, \
                f"sample {name} has no # TYPE line"
        for name, kind in types.items():
            if kind != "histogram":
                continue
            buckets = [v for lbl, v in samples.get(name + "_bucket", [])
                       if lbl and "+Inf" not in lbl]
            inf = [v for lbl, v in samples.get(name + "_bucket", [])
                   if lbl and "+Inf" in lbl]
            count = samples[name + "_count"][0][1]
            assert inf and inf[0] == count, name
            assert buckets == sorted(buckets), \
                f"{name} buckets not cumulative"
            assert all(b <= count for b in buckets), name

    def test_tracez_ring_and_statements_endpoints(self, obs):
        traces = obs["tracez"]["traces"]
        assert traces, "slow-statement ring is empty"
        t = traces[-1]
        assert t["duration_s"] > 0 and t["fingerprint"]
        assert t["span"]["n"] and "c" in t["span"]
        fps = [s["fingerprint"] for s in obs["stmts"]["statements"]]
        assert any("lineitem" in fp for fp in fps)
        assert all(s["count"] >= 1 for s in obs["stmts"]["statements"])

    def test_statements_carry_latency_quantiles(self, obs):
        """p50/p95/p99 derive from the log2 latency buckets — same
        observations as the means, no extra recording path."""
        for s in obs["stmts"]["statements"]:
            assert sum(s["latency_buckets"]) == s["count"]
            p50, p95, p99 = (s["p50_latency_s"], s["p95_latency_s"],
                             s["p99_latency_s"])
            assert 0 < p50 <= p95 <= p99
            # each quantile is a bucket upper bound covering max
            assert p99 >= s["max_latency_s"] / 2


class TestClusterFanout:
    def test_cluster_tracez_from_non_gateway_node(self, obs):
        """ISSUE acceptance: /debug/tracez?cluster=1 scraped from a
        node that is NOT the gateway returns the gateway's
        slow-statement entry, node-tagged."""
        body = _whole_scrape(obs["node2"], "/debug/tracez?cluster=1")
        assert body["cluster"] is True
        assert body["partial"] is False
        assert sorted(body["nodes"]) == [1, 2, 3]
        mine = [t for t in body["traces"]
                if t["node"] == 1 and "lineitem" in t["sql"]]
        assert mine, "gateway's slow entry missing from the fan-out"
        assert mine[-1]["span"]["n"]

    def test_cluster_statements_merge_exactly(self, obs):
        """Fingerprints merge by summing raw totals and bucket
        arrays; quantiles/means re-derive from the merged values."""
        local = json.loads(_http_get(obs["node"],
                                     "/_status/statements"))
        merged = _whole_scrape(obs["node"],
                               "/_status/statements?cluster=1")
        assert merged["cluster"] is True and merged["partial"] is False
        by_fp = {s["fingerprint"]: s for s in merged["statements"]}
        for s in local["statements"]:
            m = by_fp[s["fingerprint"]]
            # this fixture's statements ran on the gateway engine
            # only, so the merged row equals the local row
            assert m["count"] >= s["count"]
            assert m["total_latency_s"] >= s["total_latency_s"] - 1e-9
            assert sum(m["latency_buckets"]) == m["count"]
            assert abs(m["mean_latency_s"] * m["count"]
                       - m["total_latency_s"]) < 1e-6


class TestSessionTraceControl:
    def test_set_tracing_cluster_stitches_raft_and_flow(self, obs):
        """ISSUE acceptance: SET tracing = cluster, a replicated
        INSERT and a distributed GROUP BY on ONE session; SHOW TRACE
        FOR SESSION renders node-tagged remote flow spans AND raft
        propose/apply events."""
        from cockroach_tpu.exec.session import Session
        eng = Engine(cluster=obs["n1"])
        s = Session()
        # the fixture bulk-wrote lineitem KV pairs under the FIRST
        # user-table prefix (RangeTable bypasses this catalog); burn
        # that id on an empty spacer so trc_t's keys are its own
        eng.execute("CREATE TABLE trc_spacer (x INT)", session=s)
        eng.execute("CREATE TABLE trc_t (a INT PRIMARY KEY, b INT)",
                    session=s)
        eng.execute("SET tracing = cluster", session=s)
        eng.execute("INSERT INTO trc_t VALUES (1, 10), (2, 20)",
                    session=s)
        obs["gw"].run(Q, session=s)
        eng.execute("SET tracing = off", session=s)
        res = eng.execute("SHOW TRACE FOR SESSION", session=s)
        text = "\n".join(r[0] for r in res.rows)
        # raft events from the replicated write path
        assert "raft-propose" in text, text
        assert "raft-apply" in text, text
        # node-tagged remote flow spans from the distributed read
        remote = {int(m) for m in re.findall(r"flow.*node=(\d+)",
                                             text)}
        assert len(remote - {0}) >= 2, text
        # SET tracing = off stops recording: no new spans after
        n_rows = len(res.rows)
        eng.execute("SELECT count(*) FROM trc_t", session=s)
        res2 = eng.execute("SHOW TRACE FOR SESSION", session=s)
        assert len(res2.rows) == n_rows

    def test_tracing_on_stays_gateway_local(self, obs):
        """SET tracing = on records, but remote nodes stay dark: the
        trace context ships without the record-request bit, so flows
        come back without remote recordings."""
        from cockroach_tpu.exec.session import Session
        s = Session()
        s.vars.set("tracing", "on")
        obs["gw"].run(Q, session=s)
        assert s.trace, "gateway-local recording missing"
        text = "\n".join(ln for rec in s.trace
                         for ln in rec.tree_lines())
        remote = {int(m) for m in re.findall(r"flow.*node=(\d+)",
                                             text)}
        assert not (remote - {0}), \
            f"remote flows recorded under tracing=on: {text}"


class TestClusterFanoutPartial:
    """LAST in the file: partitions the fabric. The fixture's other
    consumers have all scraped by now."""

    def test_partitioned_peer_marks_partial_within_timeout(self, obs):
        inj, n2 = obs["inj"], obs["n2"]
        inj.partition(2, 3)
        try:
            t0 = time.monotonic()
            body = json.loads(_http_get(
                obs["node2"], "/debug/tracez?cluster=1&timeout=0.5"))
            elapsed = time.monotonic() - t0
            assert body["partial"] is True
            assert 3 not in body["nodes"]
            assert 1 in body["nodes"]  # the healthy peer still merged
            # one partitioned peer costs at most ~one per-peer timeout
            assert elapsed < 5.0, elapsed
            # the gateway's entry still arrives despite the partition
            assert any(t["node"] == 1 and "lineitem" in t["sql"]
                       for t in body["traces"])
        finally:
            inj.heal()
            n2.peer_breaker(3).reset()
