"""One statement, one span tree (ISSUE 24): host spans from the frame
to ReadyForQuery, the process-wide collector, off meaning off, the
boundary counters, and plan-operator scopes in the lowered program.

References: pkg/util/tracing (recordings, the active-spans registry),
sql/conn_executor_exec.go (the statement's span from the wire down).
"""

import json
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

from cockroach_tpu.exec.engine import Engine
from cockroach_tpu.models import tpch
from cockroach_tpu.ops.batch import read_ts_words
from cockroach_tpu.server import pgwire
from cockroach_tpu.server.miniclient import MiniClient
from cockroach_tpu.server.node import Node, NodeConfig
from cockroach_tpu.utils import tracing

ROWS = 2000
Q = ("SELECT l_returnflag, count(*), sum(l_quantity) FROM lineitem "
     "WHERE l_quantity > {k} GROUP BY l_returnflag "
     "ORDER BY l_returnflag")
# the served statement's layers, outermost first (PERF.md section 3)
WIRE_SPANS = ["wire.queue", "parse", "encode", "send"]
ENGINE_SPANS = ["gate", "plan", "dispatch", "materialize"]


@pytest.fixture(scope="module")
def node():
    n = Node(NodeConfig(http_port=0, listen_port=0)).start()
    tpch.load(n.engine, sf=0.01, rows=ROWS)
    yield n
    n.stop()


@pytest.fixture(scope="module")
def threads_server(node):
    srv = pgwire.PgServer(node.engine, "127.0.0.1", 0,
                          version=node.pg.version,
                          frontend="threads").start()
    yield srv
    srv.stop()


@pytest.fixture(autouse=True)
def _collector_off():
    yield
    tracing.stop_collector()


def _client(addr):
    return MiniClient(addr[0], addr[1])


def _walk(s):
    yield s
    for c in s.children:
        yield from _walk(c)


def _self_ns(s):
    return (s.end_ns - s.start_ns) - sum(
        c.end_ns - c.start_ns for c in s.children)


def _served_root(addr, sql, cpu=False):
    """Serve `sql` once warm, then once more with the collector on:
    the one root of that second execution."""
    c = _client(addr)
    try:
        c.query(sql)
        tracing.start_collector(cpu=cpu)
        c.query(sql)
        roots = _stop_after_served(1)
    finally:
        c.close()
    served = [r for r in roots if r.tags.get("served")]
    assert len(served) == 1, [r.name for r in roots]
    return served[0]


def _stop_after_served(n):
    """A root closes after its reply is flushed, so the client can
    hold the reply before the collector holds the root: wait for it."""
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and len(
            [r for r in tracing.collected()
             if r.tags.get("served")]) < n:
        time.sleep(0.002)
    return tracing.stop_collector()


def _check_tree(root, sql, front):
    names = [c.name for c in root.children]
    want = [n for n in WIRE_SPANS
            if not (n == "wire.queue" and front == "threads")]
    assert names == want[:-2] + [sql] + want[-2:], names
    assert root.tags["fingerprint"] and root.tags["frame"] == "Q"
    stmt = root.children[names.index(sql)]
    assert [c.name for c in stmt.children] == ENGINE_SPANS
    assert stmt.find("parse") is None
    assert root.find("parse").tags["cache"] == "hit"
    assert stmt.find("gate").tags == {"shared": True}
    assert stmt.find("plan").tags["plan_cache"] == "hit"
    mat = stmt.find("materialize")
    assert [c.name for c in mat.children] == ["pull", "decode"]
    assert mat.find("pull").tags == {"programs": 1, "transfers": 1,
                                     "bytes": mat.find("pull")
                                     .tags["bytes"]}
    assert mat.find("pull").tags["bytes"] > 0
    spans = list(_walk(root))
    assert {s.trace_id for s in spans} == {root.trace_id}
    assert len({s.span_id for s in spans}) == len(spans)
    for s in spans:
        assert s.start_ns <= s.end_ns
        prev_end = s.start_ns
        for c in s.children:        # inside the parent, in order
            assert prev_end <= c.start_ns and c.end_ns <= s.end_ns, \
                (s.name, c.name)
            prev_end = c.end_ns
    # so the self times add up to the root: the tree has no hole
    assert sum(_self_ns(s) for s in spans) == \
        root.end_ns - root.start_ns
    assert all(_self_ns(s) >= 0 for s in spans)


class TestServedTree:
    def test_reactor_frame_to_ready_for_query(self, node):
        sql = Q.format(k=3)
        _check_tree(_served_root(node.sql_addr, sql), sql, "reactor")

    def test_threads_front_end_has_no_wire_queue(self, threads_server):
        sql = Q.format(k=4)
        _check_tree(_served_root(threads_server.addr, sql), sql,
                    "threads")

    def test_first_execution_uploads_and_compiles_under_plan(self, node):
        node.engine.execute("CREATE TABLE spans_up (a INT, b INT)")
        node.engine.execute(
            "INSERT INTO spans_up VALUES (1, 2), (3, 4), (5, 6)")
        tracing.start_collector()
        node.engine.execute("SELECT sum(b) FROM spans_up WHERE a > 1")
        root = tracing.stop_collector()[-1]
        plan = root.find("plan")
        assert plan.tags["plan_cache"] == "miss"
        assert [c.name for c in plan.children] == ["upload", "compile"]
        assert plan.find("upload").tags["table"] == "spans_up"
        assert plan.find("upload").tags["bytes"] > 0
        assert root.find("parse") is None   # a library root: no wire

    def test_mesh_dispatch_records_its_queue_wait(self, node):
        """A distributed plan's call crosses to the mesh dispatcher's
        thread: the wait in its queue is a `queue` span under
        `dispatch`, stamped there and recorded here."""
        if node.engine.mesh is None:
            pytest.skip("one device: no mesh dispatcher")
        sql = Q.format(k=14)
        before = node.engine.metrics.snapshot().get(
            "exec.allreduce.calls", 0)
        node.engine.execute(sql)
        tracing.start_collector()
        node.engine.execute(sql)
        # the collector and the mesh dispatcher (one a device set) are
        # the process's, not this engine's: another engine of this
        # worker may finish a statement after ours, so the root is
        # found by name, and may have the dispatcher busy, in which
        # case `sql.exec.submesh.size = auto` sends the plan to a
        # sub-mesh and its first upload there is a child of `dispatch`
        # beside the wait
        root = [r for r in tracing.stop_collector() if r.name == sql][-1]
        if node.engine.metrics.snapshot().get(
                "exec.allreduce.calls", 0) == before:
            pytest.skip("the plan ran gateway-local")
        disp = root.find("dispatch")
        names = [c.name for c in disp.children]
        assert names.count("queue") == 1 and \
            set(names) <= {"queue", "upload"}, names
        q = disp.children[names.index("queue")]
        assert disp.start_ns <= q.start_ns <= q.end_ns <= disp.end_ns

    def test_every_thread_is_collected(self, node):
        sql = Q.format(k=5)
        node.engine.execute(sql)
        tracing.start_collector()
        ts = [threading.Thread(target=node.engine.execute, args=(sql,))
              for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        roots = tracing.stop_collector()
        assert [r.name for r in roots] == [sql] * 4
        assert len({r.trace_id for r in roots}) == 4

    def test_collector_is_bounded_and_drops_the_oldest(self, node):
        tracing.start_collector(max_roots=3)
        for k in range(5):
            node.engine.execute(f"SELECT {k} + 1")
        assert tracing.collecting()
        assert [r.name for r in tracing.collected()] == \
            [f"SELECT {k} + 1" for k in (2, 3, 4)]
        assert len(tracing.stop_collector()) == 3
        assert not tracing.collecting() and tracing.collected() == []


class TestOffIsOff:
    def test_untraced_statement_allocates_no_span(self, node,
                                                  monkeypatch):
        made, seen = [], []

        class CountedSpan(tracing.Span):
            def __init__(self, *a, **kw):
                made.append(a[0])
                super().__init__(*a, **kw)

        inner = node.engine._dispatch_stmt

        def probe(stmt, session, sql_text=""):
            seen.append((tracing.current_span(),
                         tracing.recording_requested(),
                         tracing.trace_context()))
            return inner(stmt, session, sql_text)

        class CountedClock:
            """tracing's `time`, with the CPU clock counted."""
            calls = 0
            monotonic_ns = staticmethod(time.monotonic_ns)

            @classmethod
            def thread_time_ns(cls):
                cls.calls += 1
                return time.thread_time_ns()

        monkeypatch.setattr(tracing, "Span", CountedSpan)
        monkeypatch.setattr(tracing, "time", CountedClock)
        monkeypatch.setattr(node.engine, "_dispatch_stmt", probe)
        c = _client(node.sql_addr)
        try:
            c.query(Q.format(k=6))
        finally:
            c.close()
        assert seen == [(None, False, None)]
        assert made == []
        assert CountedClock.calls == 0
        # and a mark with nothing open is a return: no span to mark
        assert tracing.stage("build") is None
        assert tracing.stage_cpu(5) is None
        assert CountedClock.calls == 0
        # the collector's roots read it only when asked
        tracing.start_collector()
        c = _client(node.sql_addr)
        try:
            c.query(Q.format(k=6))
        finally:
            c.close()
        root = [r for r in _stop_after_served(1)
                if r.tags.get("served")][-1]
        assert CountedClock.calls == 0 and made
        assert all(s.cpu_ns is None for s in _walk(root))
        assert root.stages and not any(m[2] for s in _walk(root)
                                       for m in s.stages)
        # the same statement with the clock asked for does read it:
        # the probe above counts what it claims to
        tracing.start_collector(cpu=True)
        c = _client(node.sql_addr)
        try:
            c.query(Q.format(k=6))
        finally:
            c.close()
        _stop_after_served(1)
        assert CountedClock.calls > 0

    def test_untraced_rpc_ships_no_recording_request(self):
        """A statement of an untraced session on a socket cluster:
        no frame carries a trace context and no reply a recording
        (the unread stmt:<type> root used to put "rec": 1 on every
        RPC, and every reply came back with an `sp` payload)."""
        from cockroach_tpu.kvserver.netcluster import NetCluster
        n1 = NetCluster(1)
        n1.bootstrap()
        n2 = NetCluster(2, join={1: n1.addr})
        n2.join()
        frames = []
        for n in (n1, n2):
            send = n._send

            def spy(to, msg, _send=send):
                frames.append(msg)
                return _send(to, msg)
            n._send = spy
        try:
            eng = Engine(cluster=n2)
            eng.execute("CREATE TABLE rpc_t (a INT PRIMARY KEY, b INT)")
            eng.execute("INSERT INTO rpc_t VALUES (1, 10), (2, 20)")
            assert eng.execute("SELECT b FROM rpc_t WHERE a = 2"
                               ).rows == [(20,)]
            reqs = [m for m in frames if m.get("k") == "req"]
            assert reqs, "the statements sent no RPC"
            assert not [m for m in frames if "tc" in m or "sp" in m]
            # and a session that asks gets both
            s = eng.session()
            eng.execute("SET tracing = cluster", s)
            eng.execute("INSERT INTO rpc_t VALUES (3, 30)", s)
            assert any(m.get("tc", {}).get("rec") for m in frames)
            assert any(m.get("sp") for m in frames)
        finally:
            n1.stop()
            n2.stop()


def _spin(seconds):
    end = time.monotonic() + seconds
    while time.monotonic() < end:
        pass


def _stage_self_ns(s):
    """[(stage, self ns)] of a span: a stage owns the span's self time
    from its mark to the next mark or the close; `None` before the
    first mark."""
    cuts = [(None, s.start_ns)] + [(m[0], m[1]) for m in s.stages]
    ends = [at for _, at in cuts[1:]] + [s.end_ns]
    out = []
    for (name, a), b in zip(cuts, ends):
        kids = sum(max(0, min(b, c.end_ns) - max(a, c.start_ns))
                   for c in s.children)
        out.append((name, b - a - kids))
    return out


class TestCpuAndStages:
    """ISSUE 38: a span carries its thread's CPU, a layer's own time
    is cut by marks, the process counts its threads and collector."""

    @pytest.mark.parametrize("work,lo,hi", [
        (_spin, 0.8, 1.2), (time.sleep, 0.0, 0.1)],
        ids=["busy_loop", "sleep"])
    def test_a_span_records_its_threads_cpu(self, work, lo, hi):
        for _ in range(12):     # a shared machine can preempt a spin
            c0 = time.thread_time_ns()
            with tracing.capture("root") as root:
                with tracing.span("child") as sp:
                    work(0.05)
            spent = time.thread_time_ns() - c0
            # the span's reading is this thread's clock, whatever the
            # machine did to the thread meanwhile
            assert 0 <= spent - sp.cpu_ns < 2_000_000, (spent, sp.cpu_ns)
            wall = sp.end_ns - sp.start_ns
            if lo * wall <= sp.cpu_ns <= hi * wall:
                break
        if work is _spin and sp.cpu_ns < lo * wall:
            pytest.skip("the machine gave a spinning thread under 80 % "
                        "of a core in twelve tries of 50 ms")
        assert lo * wall <= sp.cpu_ns <= hi * wall, (sp.cpu_ns, wall)
        assert root.cpu_ns >= sp.cpu_ns
        assert f"cpu={sp.cpu_ns / 1e6:.2f}ms" in root.tree_lines()[1]

    def test_a_span_stamped_elsewhere_has_no_cpu_unless_given(self):
        with tracing.capture("root") as root:
            t = time.monotonic_ns()
            wait = tracing.record("queue", t - 50, t)
            call = tracing.record("call", t - 40, t, cpu_ns=17, k=1)
            tracing.event("mark")
        assert wait.cpu_ns is None and "cpu=" not in root.tree_lines()[1]
        assert call.cpu_ns == 17 and call.tags == {"k": 1}
        wire = tracing.span_to_wire(root)
        assert "u" not in wire["c"][0] and wire["c"][1]["u"] == 17
        assert tracing.span_from_wire(wire).children[0].cpu_ns is None

    def test_stages_partition_a_spans_self_time(self):
        with tracing.capture("root") as root:
            with tracing.span("plan") as plan:
                _spin(0.002)                # before any mark
                tracing.stage("build")
                _spin(0.004)
                tracing.stage("tables")
                with tracing.span("upload"):
                    time.sleep(0.003)
                _spin(0.001)
                tracing.stage("key")
                _spin(0.002)
        parts = _stage_self_ns(plan)
        assert [n for n, _ in parts] == [None, "build", "tables", "key"]
        assert all(ns > 0 for _, ns in parts)
        assert sum(ns for _, ns in parts) == _self_ns(plan)   # exactly
        by = dict(parts)
        # a stage owns what its span owns there: `tables` less `upload`
        (_, t_build, _, _), (_, t_tables, _, _), (_, t_key, _, _) = \
            plan.stages
        upload = plan.children[0]
        assert by["build"] == t_tables - t_build >= 4e6
        assert by["tables"] == (t_key - t_tables) - (
            upload.end_ns - upload.start_ns) > 0
        # CPU likewise: marks read the span's own clock, in order
        cpus = [m[2] for m in plan.stages]
        assert cpus == sorted(cpus) and cpus[-1] <= plan.cpu_ns
        iv = plan.stage_intervals()
        assert [i[0] for i in iv] == ["build", "tables", "key"]
        assert iv[0][2] == iv[1][1] and iv[-1][2] == plan.end_ns
        assert sum(i[3] for i in iv) == plan.cpu_ns - cpus[0]
        assert "stages[build=" in root.tree_lines()[1]
        # and the wire carries them; a reader of b / e / c alone reads
        # the span it read before
        wire = tracing.span_to_wire(root)
        back = tracing.span_from_wire(json.loads(json.dumps(wire)))
        p2 = back.find("plan")
        assert p2.stages == plan.stages and p2.cpu_ns == plan.cpu_ns
        assert _stage_self_ns(p2) == parts
        assert (p2.start_ns, p2.end_ns, len(p2.children)) == \
            (plan.start_ns, plan.end_ns, 1)
        old = {k: v for k, v in wire["c"][0].items()
               if k not in ("u", "g")}
        assert tracing.span_from_wire(old).stages == []

    def test_a_served_statements_layers_are_staged(self, node):
        root = _served_root(node.sql_addr, Q.format(k=15), cpu=True)
        assert [m[0] for m in root.stages] == [
            "frame", "route", "setup", "account"]
        stmt = root.find(Q.format(k=15))
        assert [m[0] for m in stmt.stages] == ["select", "unwind"]
        assert [m[0] for m in stmt.find("plan").stages] == [
            "build", "placement", "tables", "key", "fingerprint", "lookup"]
        assert [m[0] for m in stmt.find("dispatch").stages] == [
            "args", "call"]
        assert [m[0] for m in stmt.find("materialize").stages] == [
            "flags", "assemble"]
        for s in _walk(root):
            # marks lie inside their span, in order, outside its children
            at = [m[1] for m in s.stages]
            assert at == sorted(at)
            assert all(s.start_ns <= t <= s.end_ns for t in at), s.name
            assert not any(c.start_ns < t < c.end_ns
                           for t in at for c in s.children), s.name
            assert sum(ns for _, ns in _stage_self_ns(s)) == _self_ns(s)
            if s.name not in ("wire.queue", "queue"):
                assert s.cpu_ns is not None and \
                    0 <= s.cpu_ns <= (s.end_ns - s.start_ns) * 1.05 + 50_000

    def test_the_mesh_calls_cpu_is_the_dispatcher_threads(self):
        """queued_collective_call runs the call on `mesh-dispatch-*`
        while the statement's thread waits: the stage open there is
        credited with that thread's CPU, and tagged with its stamps."""
        from cockroach_tpu.parallel.distagg import queued_collective_call

        def burn(x):     # 50 ms of this thread's CPU, however long
            t0 = time.thread_time()
            while time.thread_time() - t0 < 0.05:
                pass
            return (x, threading.current_thread().name)

        call = queued_collective_call(burn)
        assert call(1)[1].startswith("mesh-dispatch-")   # untraced
        with tracing.capture("root"):
            with tracing.span("dispatch") as disp:
                tracing.stage("args")
                tracing.stage("call")
                out, name = call(2)
        assert out == 2 and disp.tags["call_thread"] == name
        assert [c.name for c in disp.children] == ["queue"]
        (_, _, _, args_other), (_, _, _, other) = disp.stages
        assert args_other == 0
        ran = disp.tags["call_e"] - disp.tags["call_b"]
        # (two clocks: the CPU's may run a hair ahead of the wall's)
        assert 50e6 <= other <= ran * 1.02 + 100_000
        assert ran <= disp.end_ns - disp.start_ns
        # the statement's own thread slept through it
        assert disp.cpu_ns < 0.2 * other
        assert disp.children[0].end_ns <= disp.tags["call_b"]

    def test_thread_counters_rise_for_a_worker_that_spins(self, node):
        def read():
            snap = node.engine.metrics.snapshot()
            return {g: snap[f"process.threads.cpu.seconds.{g}"]
                    for g in ("reactor", "workers", "mesh_dispatch",
                              "other")}, snap["process.cpu.seconds"], \
                snap["process.wall.seconds"]
        stop = threading.Event()

        def spinner():
            while not stop.is_set():
                pass

        ts = [threading.Thread(target=spinner, name="pgfront-worker_77"),
              threading.Thread(target=stop.wait, name="mesh-dispatch-z")]
        before, cpu0, wall0 = read()
        for t in ts:
            t.start()
        try:
            read()               # both seen while alive
            time.sleep(0.3)
            mid, _, _ = read()
        finally:
            stop.set()
            for t in ts:
                t.join(5)
        assert not any(t.is_alive() for t in ts)
        time.sleep(0.002)
        after, cpu1, wall1 = read()
        assert mid["workers"] - before["workers"] > 0.05
        assert mid["mesh_dispatch"] - before["mesh_dispatch"] < 0.02
        # a thread that has exited keeps what it had
        assert after["workers"] >= mid["workers"]
        assert cpu1 - cpu0 >= mid["workers"] - before["workers"]
        assert 0.3 <= wall1 - wall0 < 30

    def test_a_forced_collection_lands_in_its_generation(self, node):
        import gc

        def read():
            snap = node.engine.metrics.snapshot()
            return [snap[f"process.gc.pause.seconds.gen{g}"]
                    for g in range(3)]
        before = read()
        junk = [[i] for i in range(50_000)]
        gc.collect(2)
        gc.collect(0)
        after = read()
        del junk
        assert after[2]["count"] >= before[2]["count"] + 1
        assert after[2]["sum"] > before[2]["sum"]
        assert after[0]["count"] >= before[0]["count"] + 1
        host, port = node.http_addr
        with urllib.request.urlopen(
                f"http://{host}:{port}/_status/runtime", timeout=10) as r:
            proc = json.loads(r.read().decode())["process"]
        assert proc["gc.pause.seconds.gen2"]["count"] >= after[2]["count"]
        assert proc["cpu.seconds"] > 0 and \
            {"threads.cpu.seconds." + g for g in (
                "reactor", "workers", "mesh_dispatch", "other")} <= set(proc)


class TestReadersSeeTheNewSpans:
    NEW = ("gate", "pull", "decode")

    def test_set_tracing_and_show_trace(self, node):
        c = _client(node.sql_addr)
        try:
            c.query("SET tracing = on")
            c.query(Q.format(k=7))
            c.query("SET tracing = off")
            _, rows = c.query("SHOW TRACE FOR SESSION")[:2]
        finally:
            c.close()
        text = "\n".join(r[0] for r in rows)
        for name in self.NEW:
            assert f"\n    {name}: " in text or \
                f"\n  {name}: " in text, text

    def test_explain_analyze(self, node):
        res = node.engine.execute("EXPLAIN ANALYZE " + Q.format(k=8))
        text = "\n".join(str(r[0]) for r in res.rows)
        # its recording opens under the gate: the layers beneath it
        for name in ("  plan: ", "  materialize: ", "    pull: ",
                     "    decode: "):
            assert f"\n{name}" in text, text

    def test_tracez_ring_while_the_collector_is_on(self, node):
        eng = node.engine
        eng.settings.set("sql.trace.slow_statement.threshold", 1e-9)
        tracing.start_collector()
        c = _client(node.sql_addr)
        try:
            c.query(Q.format(k=9))
        finally:
            c.close()
            eng.settings.set("sql.trace.slow_statement.threshold", 0.0)
        host, port = node.http_addr
        with urllib.request.urlopen(
                f"http://{host}:{port}/debug/tracez", timeout=10) as r:
            ring = json.loads(r.read().decode())["traces"]
        ent = [t for t in ring if t["sql"] == Q.format(k=9)][-1]
        names = {s.name for s in _walk(tracing.span_from_wire(
            ent["span"]))}
        assert set(self.NEW) <= names
        # the same Span sits in the collector's root, not a copy of
        # the work: the ring's subtree is the served root's child
        root = [r for r in _stop_after_served(1)
                if r.tags.get("served")][-1]
        assert root.find(Q.format(k=9)).span_id == ent["span"]["sid"]

    def test_stmtdiag_bundle(self, node):
        sql = Q.format(k=10)
        rid = node.engine.stmtdiag.arm(sql)["request_id"] \
            if hasattr(node.engine.stmtdiag, "arm") else None
        if rid is None:
            pytest.skip("no stmtdiag.arm on this tree")
        node.engine.execute(sql)
        bundle = node.engine.stmtdiag.get(rid)
        names = {s.name for s in _walk(tracing.span_from_wire(
            bundle["trace"]))}
        assert set(self.NEW) <= names


class TestBoundaryCounters:
    def test_one_statement_moves_them_by_what_it_sent(self, node):
        eng = node.engine
        sql = Q.format(k=11)
        prep = eng.prepare(sql)
        eng.execute(sql)

        def read():
            snap = eng.metrics.snapshot()
            return {k: snap[k] for k in (
                "exec.dispatch.programs", "exec.transfer.h2d.calls",
                "exec.transfer.h2d.bytes", "exec.transfer.d2h.calls",
                "exec.transfer.d2h.bytes")}
        before = read()
        tracing.start_collector()
        eng.execute(sql)
        pull = tracing.stop_collector()[-1].find("pull")
        d = {k: v - before[k] for k, v in read().items()}
        # the plan's program, one flag reduction per sentinel column
        # of its output, the pack
        flags = d["exec.dispatch.programs"] - 2
        assert flags >= 0
        assert d["exec.transfer.h2d.calls"] == 3 + len(prep.params)
        assert d["exec.transfer.h2d.bytes"] >= 16
        assert d["exec.transfer.d2h.calls"] == pull.tags["transfers"] == 1
        assert d["exec.transfer.d2h.bytes"] == pull.tags["bytes"]


class TestOperatorScopes:
    @pytest.fixture(scope="class")
    def eng(self):
        e = Engine(mesh=None)
        tpch.load(e, sf=0.01, rows=ROWS,
                  tables=("lineitem", "orders", "customer"))
        return e

    @staticmethod
    def _lowered(prep) -> str:
        # one chip's program (an engine given no mesh builds one over
        # every device, and its jfn then routes; SET distsql = off
        # keeps the single-device executable)
        return prep.jfn.lower(
            prep.scans, read_ts_words(0), np.int32(1), np.int32(0),
            prep.params).as_text(debug_info=True)

    @staticmethod
    def _prepare(eng, sql):
        s = eng.session()
        s.vars.set("distsql", "off")
        return eng.prepare(sql, s)

    def test_q1_names_its_aggregate_and_phases(self, eng):
        text = self._lowered(self._prepare(eng, tpch.Q1))
        assert "/aggregate." in text
        for phase in ("keys", "finalize"):
            assert f"/{phase}/" in text or f"/{phase}\"" in text, phase
        assert "/scan." in text

    def test_q3_names_its_hash_joins(self, eng):
        text = self._lowered(self._prepare(eng, tpch.Q3))
        assert "/hashjoin." in text and "/aggregate." in text
        assert "/build/" in text and "/probe/" in text

    def test_two_parameter_sets_of_one_shape_share_the_text(self, eng):
        a = self._prepare(eng, Q.format(k=12))
        b = self._prepare(eng, Q.format(k=13))
        assert a.params != b.params
        ta, tb = self._lowered(a), self._lowered(b)
        assert ta == tb and "/aggregate." in ta
        # ordinals are positions in the plan, not a running count:
        # preparing the shape again (a cold cache) names them alike
        eng._exec_cache.clear()
        again = self._lowered(self._prepare(eng, Q.format(k=12)))

        def op_names(text):
            return sorted(set(re.findall(r'loc\("([^"]*)"', text)))
        assert op_names(again) == op_names(ta)
        assert any("/sort.0/aggregate.1/scan.2/" in n
                   for n in op_names(ta)), op_names(ta)[:20]

    def test_scopes_are_part_of_a_cached_executables_identity(self, eng):
        """JAX's persistent-cache key leaves op metadata out unless
        told otherwise; a profile would then read the scope names of
        whichever tree compiled the entry first."""
        import jax
        assert eng._compile_cache_dir
        assert jax.config.jax_compilation_cache_include_metadata_in_key

    def test_harness_programs_are_scoped(self):
        import jax.numpy as jnp
        from cockroach_tpu.ops import batch
        x = jnp.arange(8) > 3
        assert "harness" in batch._any.lower(x).as_text(debug_info=True)
        assert "harness" in batch._pack.lower([x, x]).as_text(
            debug_info=True)
