"""Node lifecycle: assemble subsystems and serve clients.

The analogue of the reference's server package (pkg/server/server.go:203
``NewServer`` wires rpc/gossip/kv/sql together; ``PreStart``
server.go:1213 boots them in dependency order; ``AcceptClients``
server.go:1915 opens the pgwire listener). Here a Node owns the
columnstore scan plane, the HLC clock, the transactional KV plane
(inside Engine), cluster settings, and the pgwire server; ``start()``
brings them up and returns once the SQL listener is bound.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from .. import __version__
from ..exec.engine import Engine
from ..storage.columnstore import ColumnStore
from ..storage.hlc import Clock
from ..utils.settings import Settings
from .pgwire import PgServer


@dataclass
class NodeConfig:
    listen_host: str = "127.0.0.1"
    listen_port: int = 0          # 0 = ephemeral (tests); CLI default 26257
    http_port: int | None = 0     # status/metrics; None disables
    mesh: object = None           # optional device mesh for DistSQL
    load_tpch_sf: float | None = None  # demo mode: preload TPC-H tables
    # cluster fabric: this node's id + RPC port, and peer addresses to
    # join ({node_id: (host, port)}); None disables the fabric
    node_id: int = 1
    rpc_port: int | None = None
    join: dict | None = None
    gossip_interval: float = 0.2
    # tests: a shared rpc.FaultInjector (seeded nemesis schedule for
    # the socket fabric); None = faults off
    fault_injector: object = None
    # background maintenance loop: orphaned-job adoption + MVCC GC
    # passes (the store queues / job registry adoption loops of the
    # reference); None disables
    maintenance_interval: float | None = None
    # raft-replicated data plane: a kvserver.Cluster shared by the
    # nodes of one logical cluster. With this set, the node's SQL
    # engine serves DML/catalog/jobs from replicated ranges
    # (kv/rangekv.py) instead of a node-local store — several Nodes
    # handed the same Cluster serve the same data (VERDICT r3 #1c)
    cluster: object = None
    # pgwire password gate: {user: cleartext password}; None = insecure
    # mode (the reference's --insecure), every user accepted
    auth: dict | None = None
    # TLS: directory holding node.crt/node.key (cli.py `cert` creates
    # them); None serves plaintext only
    certs_dir: str | None = None


# -- cluster-wide status fan-out ----------------------------------------
# The payload builders are module-level so ANY NetCluster participant
# can serve them over the fabric's "status" RPC — including engines
# embedded in tests or tools that never construct a Node. A Node wires
# its own engine in via enable_cluster_status() below.

def _tracez_payload(engine) -> dict:
    """The /debug/tracez body: the slow-statement ring (engine
    docstring; span in wire format)."""
    return {"traces": list(engine.slow_traces)}


def _statements_payload(engine) -> dict:
    """The /_status/statements body. Carries the raw totals and the
    log2 latency-bucket array alongside the derived means/quantiles,
    so a fan-out merge can recombine fingerprints exactly instead of
    averaging averages."""
    return {"statements": [{
        "fingerprint": s.fingerprint,
        "count": s.count,
        "total_latency_s": s.total_latency_s,
        "mean_latency_s": s.mean_latency_s,
        "max_latency_s": s.max_latency_s,
        # p50/p95/p99 from the log2-bucketed latency distribution
        # (utils/sqlstats.py; same observations as the means)
        "p50_latency_s": s.p50_latency_s,
        "p95_latency_s": s.p95_latency_s,
        "p99_latency_s": s.p99_latency_s,
        "latency_buckets": list(s.latency_buckets),
        # compile-vs-execute split (exec/coldstart.py per-thread XLA
        # compile attribution): high mean_compile_s with low
        # mean_exec_s means the fix is cache/prewarm, not the plan
        "total_compile_s": s.total_compile_s,
        "mean_compile_s": s.mean_compile_s,
        "mean_exec_s": s.mean_exec_s,
        "total_rows": s.total_rows,
        "failures": s.failures,
    } for s in engine.sqlstats.all()]}


def _tenants_payload(engine) -> dict:
    """The /_status/tenants body: application_name-keyed resource
    rollups (device-seconds, bytes moved, HBM high-water) from the
    always-on statement profile plane (exec/profile.py)."""
    return {"tenants": [t.to_wire()
                        for t in engine.sqlstats.tenants()]}


def membership_status() -> dict:
    """The /_status/membership body: this host's view of the elastic
    pod — epoch'd live set, per-member state/incarnation, heartbeat
    suspects, and the shard-lease assignment at the current epoch
    (read through the epoch-guarded LeaseView, never the raw lease
    records). ``{"elastic": false}`` when the pod is static or
    single-process (pkg/server/status.go NodesLiveness analogue)."""
    from cockroach_tpu.parallel import multihost
    mem = multihost.membership()
    if mem is None:
        return {"elastic": False}
    view = mem.view()
    out = {
        "elastic": True,
        "host_id": mem.host_id,
        "incarnation": mem.incarnation,
        "epoch": view.epoch,
        "live": sorted(view.live),
        "members": {str(h): dict(view.members.get(str(h), {}))
                    for h in view.live},
        "suspects": sorted(mem.suspects(view.live)),
        "expelled": bool(mem.expelled()),
    }
    try:
        from cockroach_tpu.distsql.leases import ShardLeases
        lv = ShardLeases(mem).view_at(view.epoch)
        out["leases"] = {
            t: {str(s): o for s, o in sorted(lv.assignment(t).items())}
            for t in sorted(lv.assignments)}
    except Exception:   # noqa: BLE001 — lease table may not exist yet
        out["leases"] = {}
    return out


def register_status_sources(cluster, engine) -> None:
    """Expose this engine's tracez/statements/tenants payloads to
    peers over the NetCluster "status" RPC (the server side of
    ?cluster=1)."""
    cluster.status_handlers["tracez"] = \
        lambda: _tracez_payload(engine)
    cluster.status_handlers["statements"] = \
        lambda: _statements_payload(engine)
    cluster.status_handlers["tenants"] = \
        lambda: _tenants_payload(engine)


def _fanout_status(cluster, what: str,
                   timeout: float) -> tuple[dict, bool]:
    """Collect `what` payloads from every live peer. Liveness-gated
    (a node the cluster already believes dead costs nothing), each
    peer bounded by `timeout`; any skipped/failed peer marks the
    result partial instead of failing the scrape."""
    results: dict[int, dict] = {}
    partial = False
    live = set(cluster.live_peers())
    with cluster._mu:
        known = sorted(cluster._peers)
    for nid in known:
        if nid == cluster.node_id:
            continue
        if nid not in live:
            partial = True
            continue
        try:
            results[nid] = cluster.call(nid, "status",
                                        {"what": what},
                                        timeout=timeout)
        except Exception:
            partial = True
    return results, partial


def _merge_tracez(own_id: int, local: dict, remote: dict,
                  partial: bool) -> dict:
    traces = [dict(t, node=own_id) for t in local["traces"]]
    for nid, payload in sorted(remote.items()):
        traces.extend(dict(t, node=nid)
                      for t in payload.get("traces", []))
    return {"traces": traces, "cluster": True, "partial": partial,
            "nodes": sorted([own_id, *remote])}


def _merge_statements(own_id: int, local: dict, remote: dict,
                      partial: bool) -> dict:
    """Per-fingerprint exact merge: sum the totals and bucket arrays,
    take the max of maxes, then re-derive means and quantiles from
    the combined values."""
    from ..utils.metric import buckets_quantile
    merged: dict[str, dict] = {}

    def fold(payload):
        for s in payload.get("statements", []):
            m = merged.get(s["fingerprint"])
            if m is None:
                merged[s["fingerprint"]] = dict(s)
                continue
            m["count"] += s["count"]
            m["total_latency_s"] += s["total_latency_s"]
            m["total_compile_s"] += s["total_compile_s"]
            m["total_rows"] += s["total_rows"]
            m["failures"] += s["failures"]
            m["max_latency_s"] = max(m["max_latency_s"],
                                     s["max_latency_s"])
            m["latency_buckets"] = [
                a + b for a, b in zip(m["latency_buckets"],
                                      s["latency_buckets"])]

    fold(local)
    for _, payload in sorted(remote.items()):
        fold(payload)
    for m in merged.values():
        n = m["count"] or 1
        m["mean_latency_s"] = m["total_latency_s"] / n
        m["mean_compile_s"] = m["total_compile_s"] / n
        m["mean_exec_s"] = max(0.0, m["mean_latency_s"]
                               - m["mean_compile_s"])
        for q, k in ((0.50, "p50_latency_s"), (0.95, "p95_latency_s"),
                     (0.99, "p99_latency_s")):
            m[k] = buckets_quantile(m["latency_buckets"], q)
    stmts = sorted(merged.values(),
                   key=lambda m: -m["total_latency_s"])
    return {"statements": stmts, "cluster": True, "partial": partial,
            "nodes": sorted([own_id, *remote])}


def _merge_tenants(own_id: int, local: dict, remote: dict,
                   partial: bool) -> dict:
    """Per-tenant exact merge: counters and seconds sum across nodes;
    hbm_bytes_held is a per-node high-water, so the cluster view takes
    the max (the tenant held at most that much on any one node)."""
    merged: dict[str, dict] = {}

    def fold(payload):
        for t in payload.get("tenants", []):
            m = merged.get(t["app_name"])
            if m is None:
                merged[t["app_name"]] = dict(t)
                continue
            for k in ("statements", "failures", "rows",
                      "device_seconds", "bytes_moved",
                      "stall_seconds"):
                m[k] += t[k]
            m["hbm_bytes_held"] = max(m["hbm_bytes_held"],
                                      t["hbm_bytes_held"])

    fold(local)
    for _, payload in sorted(remote.items()):
        fold(payload)
    tenants = sorted(merged.values(),
                     key=lambda m: -m["device_seconds"])
    return {"tenants": tenants, "cluster": True, "partial": partial,
            "nodes": sorted([own_id, *remote])}


class Node:
    def __init__(self, config: NodeConfig | None = None):
        self.config = config or NodeConfig()
        self.clock = Clock()
        self.store = ColumnStore()
        self.settings = Settings()
        self.engine = Engine(store=self.store, clock=self.clock,
                             settings=self.settings,
                             mesh=self.config.mesh,
                             cluster=self.config.cluster)
        if self.config.cluster is not None:
            self.clock = self.engine.clock  # one HLC per cluster
        from ..jobs import IMPORT_JOB, ImportResumer
        # share the engine's registry (schema-change/changefeed/backup/
        # restore/ttl resumers pre-registered) so the maintenance loop
        # can adopt ANY orphaned job type
        self.jobs = self.engine.jobs
        self.jobs.session_id = f"node-{self.config.node_id}"
        self.jobs.register(IMPORT_JOB, lambda: ImportResumer(self.engine))
        self.pg: PgServer | None = None
        self._http = None
        self.rpc = None
        self.gossip = None
        self._gossip_stop = None
        self._started = False
        # internal time-series DB: metrics recorded into the KV plane
        # by the maintenance loop (pkg/ts analogue, server/ts.py)
        from .ts import TimeSeriesDB
        self.tsdb = TimeSeriesDB(self.engine.kv, self.engine.metrics)
        # cluster-wide status fan-out: the NetCluster serving this
        # node's tracez/statements to peers (enable_cluster_status)
        self._status_cluster = None

    @property
    def sql_addr(self) -> tuple[str, int]:
        assert self.pg is not None, "node not started"
        return self.pg.addr

    @property
    def http_addr(self) -> tuple[str, int]:
        assert self._http is not None, "status server not started"
        return self._http.server_address[:2]

    def _start_status_server(self):
        """Status/metrics HTTP endpoint (pkg/server/status: /healthz,
        /_status/vars Prometheus text)."""
        import http.server
        import json
        import threading

        node = self

        class H(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                from urllib.parse import parse_qs, urlparse
                path = urlparse(self.path).path
                qs = parse_qs(urlparse(self.path).query)
                if path in ("/metrics", "/_status/vars"):
                    body = node.engine.metrics.to_prometheus().encode()
                    ctype = "text/plain; version=0.0.4"
                elif self.path.startswith("/ts/query"):
                    q = qs
                    try:
                        pts = node.tsdb.query(
                            q["name"][0],
                            int(q.get("start", ["0"])[0]),
                            int(q.get("end", [str(2**62)])[0]),
                            downsample_s=int(
                                q.get("downsample", ["10"])[0]),
                            agg=q.get("agg", ["avg"])[0],
                            rate=q.get("rate", ["0"])[0] == "1")
                        body = json.dumps(pts).encode()
                    except (KeyError, ValueError) as ex:
                        self.send_response(400)
                        self.end_headers()
                        self.wfile.write(str(ex).encode())
                        return
                    ctype = "application/json"
                elif path == "/ts/metrics":
                    body = json.dumps(
                        node.tsdb.list_metrics()).encode()
                    ctype = "application/json"
                elif path == "/healthz":
                    body = json.dumps({
                        "status": "ok",
                        "version": __version__,
                        "tables": len(node.store.tables),
                        "hbm_used_bytes": node.engine.hbm.used,
                    }).encode()
                    ctype = "application/json"
                elif path == "/_status/nodes":
                    # `cockroach node status` backing (pkg/server/
                    # status.go Nodes): this node + its fabric view
                    mon = getattr(node, "peer_monitor", None)
                    peers = {}
                    if mon is not None:
                        ids = set(mon.misses) | set(mon.rtt_ns)
                        peers = {str(p): {
                            "healthy": mon.healthy(p),
                            "rtt_ns": mon.rtt_ns.get(p),
                            "clock_offset_ns": mon.offset_ns.get(p),
                        } for p in sorted(ids)}
                    body = json.dumps({
                        "node_id": node.config.node_id,
                        "version": __version__,
                        "sql_addr": list(node.sql_addr),
                        "tables": sorted(node.store.tables),
                        "peers": peers,
                    }).encode()
                    ctype = "application/json"
                elif path == "/_status/runtime":
                    # device, compile cache and native plane this
                    # node is on, with what it skipped and why
                    body = json.dumps(
                        node.engine.runtime_status()).encode()
                    ctype = "application/json"
                elif path == "/_status/membership":
                    # elastic-pod membership + shard leases as this
                    # host sees them (epoch'd view, suspects,
                    # epoch-guarded lease assignment)
                    body = json.dumps(membership_status()).encode()
                    ctype = "application/json"
                elif path == "/_status/statements":
                    # per-fingerprint statement stats (pkg/server
                    # /statements.go Statements endpoint); ?cluster=1
                    # fans out to live peers and merges fingerprints
                    payload = _statements_payload(node.engine)
                    c = node._status_cluster
                    if qs.get("cluster", ["0"])[0] == "1" \
                            and c is not None:
                        timeout = float(
                            qs.get("timeout", ["2.0"])[0])
                        remote, part = _fanout_status(
                            c, "statements", timeout)
                        payload = _merge_statements(
                            c.node_id, payload, remote, part)
                    body = json.dumps(payload).encode()
                    ctype = "application/json"
                elif path == "/debug/tracez":
                    # ring buffer of recent slow-statement trace
                    # recordings (threshold via the cluster setting
                    # sql.trace.slow_statement.threshold; the tracez
                    # snapshot page of the reference); ?cluster=1
                    # concatenates every live peer's ring, node-tagged
                    payload = _tracez_payload(node.engine)
                    c = node._status_cluster
                    if qs.get("cluster", ["0"])[0] == "1" \
                            and c is not None:
                        timeout = float(
                            qs.get("timeout", ["2.0"])[0])
                        remote, part = _fanout_status(
                            c, "tracez", timeout)
                        payload = _merge_tracez(
                            c.node_id, payload, remote, part)
                    body = json.dumps(payload).encode()
                    ctype = "application/json"
                elif path == "/_status/tenants":
                    # application_name-keyed resource rollups from the
                    # statement profile plane; ?cluster=1 sums tenants
                    # across every live peer (hbm high-water maxes)
                    payload = _tenants_payload(node.engine)
                    c = node._status_cluster
                    if qs.get("cluster", ["0"])[0] == "1" \
                            and c is not None:
                        timeout = float(
                            qs.get("timeout", ["2.0"])[0])
                        remote, part = _fanout_status(
                            c, "tenants", timeout)
                        payload = _merge_tenants(
                            c.node_id, payload, remote, part)
                    body = json.dumps(payload).encode()
                    ctype = "application/json"
                elif path == "/_status/stmtdiag":
                    # pending diagnostics requests + completed bundle
                    # summaries (POST here arms a fingerprint)
                    body = json.dumps(
                        node.engine.stmtdiag.summary()).encode()
                    ctype = "application/json"
                elif path.startswith("/_status/stmtdiag/"):
                    # one completed bundle by id
                    try:
                        bid = int(path.rsplit("/", 1)[1])
                    except ValueError:
                        self.send_response(400)
                        self.end_headers()
                        return
                    b = node.engine.stmtdiag.get(bid)
                    if b is None:
                        self.send_response(404)
                        self.end_headers()
                        return
                    body = json.dumps(b, default=str).encode()
                    ctype = "application/json"
                elif path == "/_debug/ranges":
                    # `cockroach debug` analogue: range descriptors +
                    # leaseholders when this node serves a cluster
                    c = node.config.cluster
                    if c is None:
                        body = json.dumps({"ranges": []}).encode()
                    else:
                        rngs = []
                        for rid, desc in sorted(
                                c.descriptors.items()):
                            rngs.append({
                                "range_id": rid,
                                "start": desc.start_key.decode(
                                    "latin1"),
                                "end": desc.end_key.decode("latin1"),
                                "replicas": list(desc.replicas),
                                "leaseholder": c.leaseholder(rid),
                            })
                        body = json.dumps({"ranges": rngs}).encode()
                    ctype = "application/json"
                else:
                    self.send_response(404)
                    self.end_headers()
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                from urllib.parse import urlparse
                path = urlparse(self.path).path
                if path != "/_status/stmtdiag":
                    self.send_response(404)
                    self.end_headers()
                    return
                # arm a statement fingerprint: the next matching
                # execution captures a diagnostics bundle. Body:
                # {"sql": "..."} or {"fingerprint": "..."}
                try:
                    n = int(self.headers.get("Content-Length", "0"))
                    req = json.loads(self.rfile.read(n) or b"{}")
                    if "fingerprint" in req:
                        out = node.engine.stmtdiag.arm(
                            str(req["fingerprint"]),
                            is_fingerprint=True)
                    else:
                        out = node.engine.stmtdiag.arm(
                            str(req["sql"]))
                except (KeyError, ValueError) as ex:
                    self.send_response(400)
                    self.end_headers()
                    self.wfile.write(str(ex).encode())
                    return
                body = json.dumps(out).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        class Srv(http.server.ThreadingHTTPServer):
            daemon_threads = True

        self._http = Srv((self.config.listen_host,
                          self.config.http_port), H)
        threading.Thread(target=self._http.serve_forever,
                         name="status-http", daemon=True).start()

    def _start_fabric(self):
        """RPC listener + gossip loop (pkg/rpc, pkg/gossip): cluster
        settings set on any node converge on all of them."""
        import threading

        from ..rpc import Gossip, SocketTransport
        from ..rpc.gossip import wire_settings

        cfg = self.config
        self.rpc = SocketTransport(cfg.node_id, cfg.listen_host,
                                   cfg.rpc_port,
                                   injector=cfg.fault_injector)
        peers = [cfg.node_id]
        for nid, addr in (cfg.join or {}).items():
            self.rpc.connect(nid, tuple(addr))
            peers.append(nid)
        self.gossip = Gossip(cfg.node_id, self.rpc, peers=peers)
        # fabric liveness: heartbeats + per-peer breakers + clock-skew
        # checks ride the same loop (pkg/rpc/heartbeat.go analogue)
        from ..rpc.heartbeat import PeerMonitor
        self.peer_monitor = PeerMonitor(cfg.node_id, self.rpc)
        # extensible fabric dispatch: gossip consumes its own payloads
        # (handle() returns False otherwise); other subsystems add
        # themselves under a message "kind" without clobbering gossip
        self.rpc_handlers: dict[str, object] = {}

        def dispatch(frm, msg):
            if self.peer_monitor.handle(frm, msg):
                return
            if self.gossip.handle(frm, msg):
                return
            kind = msg.get("kind") if isinstance(msg, dict) else None
            h = self.rpc_handlers.get(kind)
            if h is not None:
                h(frm, msg)

        self.rpc.register(cfg.node_id, dispatch)
        wire_settings(self.gossip, self.settings)
        self.gossip.add_info(f"node:{cfg.node_id}:sql_addr",
                             list(self.sql_addr))
        self._gossip_stop = threading.Event()
        rpc, gossip, stop = self.rpc, self.gossip, self._gossip_stop

        monitor = self.peer_monitor

        def loop():
            # locals, not self.*: stop() nulls the attributes while
            # this thread may still be mid-tick
            while not stop.is_set():
                gossip.tick()
                monitor.tick()
                rpc.deliver_all()
                stop.wait(cfg.gossip_interval)

        self._gossip_thread = threading.Thread(target=loop,
                                               name="gossip", daemon=True)
        self._gossip_thread.start()

    def connect_peer(self, node_id: int, rpc_addr) -> None:
        """Late join: learn a peer after startup."""
        assert self.rpc is not None
        self.rpc.connect(node_id, tuple(rpc_addr))
        if node_id not in self.gossip.peers:
            self.gossip.peers.append(node_id)

    def enable_cluster_status(self, cluster=None) -> "Node":
        """Join the cluster-wide status plane: serve this node's
        tracez/statements to peers over `cluster`'s fabric and honor
        ?cluster=1 on the HTTP endpoints by fanning out over it.
        Default: the NodeConfig's cluster (auto-called by start()
        when that is a NetCluster)."""
        c = cluster if cluster is not None else self.config.cluster
        if c is None or not hasattr(c, "status_handlers"):
            return self
        register_status_sources(c, self.engine)
        self._status_cluster = c
        return self

    def start(self) -> "Node":
        if self._started:
            return self
        self.enable_cluster_status()
        if self.config.load_tpch_sf is not None:
            from ..models import tpch
            tpch.load(self.engine, sf=self.config.load_tpch_sf)
        self.pg = PgServer(self.engine, self.config.listen_host,
                           self.config.listen_port,
                           version=__version__,
                           auth=self.config.auth,
                           certs_dir=self.config.certs_dir).start()
        if self.config.http_port is not None:
            self._start_status_server()
        if self.config.rpc_port is not None:
            self._start_fabric()
        if self.config.maintenance_interval is not None:
            self._start_maintenance()
        self._started = True
        from ..utils import log
        log.structured(log.OPS, "node_start",
                       node_id=self.config.node_id,
                       sql_addr="%s:%d" % self.pg.addr)
        return self

    def _start_maintenance(self):
        """Adopt orphaned jobs (registry.go:1508 adoption loop) and run
        MVCC GC passes (mvcc_gc_queue) on a background cadence."""
        import threading

        self._maint_stop = threading.Event()

        def loop():
            while not self._maint_stop.wait(
                    self.config.maintenance_interval):
                try:
                    self.jobs.adopt_and_run_all()
                except Exception:
                    pass  # job failures land in their records
                for name in list(self.engine.store.tables):
                    if name.startswith("__"):
                        continue
                    try:
                        self.engine.run_gc(name)
                    except Exception:
                        pass
                try:
                    # abandoned-intent sweep (intentresolver analogue):
                    # clears intents of crashed coordinators so reads
                    # never pay a push for them
                    self.engine.kv.store.intent_resolver.clean_span()
                except Exception:
                    pass
                if self.engine.cluster is not None:
                    try:
                        # aged-out aborted txn records (gc/gc.go)
                        self.engine.cluster.gc_txn_records()
                    except Exception:
                        pass
                try:
                    # metric samples into the KV-backed time-series DB
                    # + its rollup/prune pass (pkg/ts maintenance).
                    # Fine-slab retention follows the cluster setting
                    # (timeseries.storage.resolution_10s.ttl analogue)
                    self.tsdb.record()
                    self.run_ts_maintenance()
                except Exception:
                    pass

        self._maint_thread = threading.Thread(target=loop, daemon=True)
        self._maint_thread.start()

    def run_ts_maintenance(self) -> None:
        """One tsdb rollup/prune pass with the fine-slab retention
        taken from the ``timeseries.retention.seconds`` cluster
        setting (factored out of the maintenance loop so tests can
        tick it synchronously)."""
        try:
            fine_s = int(self.settings.get(
                "timeseries.retention.seconds"))
        except Exception:
            fine_s = 6 * 3600
        self.tsdb.maintain(retention_fine_s=fine_s)

    def stop(self):
        if getattr(self, "_maint_stop", None) is not None:
            self._maint_stop.set()
            self._maint_thread.join(timeout=5)
            self._maint_stop = None
        if self._gossip_stop is not None:
            self._gossip_stop.set()
            self._gossip_thread.join(timeout=5)
        if self.rpc is not None:
            self.rpc.close()
            self.rpc = None
        if self.pg is not None:
            self.pg.stop()
        if self._http is not None:
            self._http.shutdown()
            self._http.server_close()
            self._http = None
        if self._started:
            from ..utils import log
            log.structured(log.OPS, "node_stop",
                           node_id=self.config.node_id)
        self._started = False

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
