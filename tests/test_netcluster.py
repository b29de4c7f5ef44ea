"""Raft over real TCP sockets: multi-process replicated clusters.

Round-3 VERDICT Missing #1's done-bar. Two tiers:

- In-process tier: three NetCluster instances in this process, each
  owning one Store, talking ONLY over their TCP listeners (no shared
  objects except the test's references) — every raft message,
  proposal, lease, liveness heartbeat and read crosses a real socket.
- OS-process tier (test_three_os_processes): three `cockroach_tpu
  start` subprocesses bootstrap/join over TCP; pgwire writes on node 1
  are read on node 3; `kill -9` of a node loses no committed rows;
  the restarted process rejoins.

Reference: pkg/kv/kvserver/raft_transport.go:152,183 (raft as an RPC
service), pkg/server/node.go:303 + server/init.go:517 (bootstrap/
join), dist_sender.go:795 (NotLeaseholder retry).
"""

import os
import signal
import subprocess
import sys
import time

import pytest

from cockroach_tpu.kvserver.netcluster import NetCluster


def _mk3():
    n1 = NetCluster(1)
    n1.bootstrap()
    n2 = NetCluster(2, join={1: n1.addr})
    n2.join()
    n3 = NetCluster(3, join={1: n1.addr})
    n3.join()
    # up-replicate the bootstrap range onto the joiners
    deadline = time.time() + 15
    while time.time() < deadline:
        n1.replicate_queue_scan()
        d = n1.descriptors[1]
        if sorted(d.replicas) == [1, 2, 3]:
            break
        time.sleep(0.05)
    assert sorted(n1.descriptors[1].replicas) == [1, 2, 3]
    return n1, n2, n3


@pytest.fixture()
def three():
    ns = _mk3()
    yield ns
    for n in ns:
        n.stop()


class TestNetCluster:
    def test_bootstrap_join_replicate(self, three):
        n1, n2, n3 = three
        # the descriptor propagates to every node (async broadcast)
        deadline = time.time() + 10
        while time.time() < deadline:
            if all(sorted(n.descriptors[1].replicas) == [1, 2, 3]
                   for n in three):
                break
            time.sleep(0.05)
        for n in three:
            assert sorted(n.descriptors[1].replicas) == [1, 2, 3]
        # replicas materialized on the joiners
        assert 1 in n2.store.replicas and 1 in n3.store.replicas

    def test_write_on_one_read_on_another(self, three):
        n1, n2, n3 = three
        n1.put(b"apple", b"1")
        n1.put(b"pear", b"2")
        # reads routed from OTHER nodes reach the leaseholder over TCP
        assert n2.get(b"apple") == b"1"
        assert n3.get(b"pear") == b"2"
        # a write routed from a non-leaseholder node
        n3.put(b"plum", b"3")
        assert n1.get(b"plum") == b"3"

    def test_replication_reaches_all_stores(self, three):
        n1, n2, n3 = three
        n1.put(b"k", b"v")
        # the value must apply on every replica's local store
        deadline = time.time() + 10

        def applied(n):
            rep = n.store.replicas.get(1)
            if rep is None:
                return False
            with n._mu:
                mv = rep.mvcc.get(b"k", n.clock.now(),
                                  inconsistent=True)
            return mv is not None and mv.value == b"v"

        while time.time() < deadline:
            if all(applied(n) for n in three):
                break
            time.sleep(0.05)
        assert all(applied(n) for n in three)

    def test_leaseholder_death_loses_nothing(self, three):
        n1, n2, n3 = three
        for i in range(10):
            n1.put(f"key{i}".encode(), f"v{i}".encode())
        # find and stop the leaseholder's process-equivalent
        lh = n1.ensure_lease(1)
        assert lh is not None
        victim = {1: n1, 2: n2, 3: n3}[lh]
        survivors = [n for n in three if n is not victim]
        victim.stop()
        # survivors elect a new leader + take the lease (epoch fence
        # after the victim's liveness lapses) and serve every row
        s = survivors[0]
        deadline = time.time() + 30
        got = None
        while time.time() < deadline:
            try:
                got = [s.get(f"key{i}".encode()) for i in range(10)]
                break
            except RuntimeError:
                time.sleep(0.2)
        assert got == [f"v{i}".encode() for i in range(10)]
        # and accept new writes with the old leaseholder gone
        s.put(b"after", b"death")
        assert survivors[1].get(b"after") == b"death"


def _wait_line(proc, needle: str, timeout: float = 90):
    deadline = time.time() + timeout
    out = []
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                break
            continue
        out.append(line)
        if needle in line:
            return "".join(out)
    raise AssertionError(
        f"did not see {needle!r} in output:\n{''.join(out)}")


def _sql(port: int, stmts: list[str], timeout: float = 60):
    from cockroach_tpu.cli import PgClient
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            c = PgClient("127.0.0.1", port, timeout=timeout)
            try:
                res = [c.query(s) for s in stmts]
            finally:
                c.close()
            return res
        except Exception as e:  # conn refused while booting / retry
            last = e
            time.sleep(0.5)
    raise AssertionError(f"sql against :{port} failed: {last}")


@pytest.mark.slow
def test_three_os_processes(tmp_path):
    """The full deployment shape: 3 OS processes over TCP."""
    import socket

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    kv1, kv2, kv3 = free_port(), free_port(), free_port()
    sql1, sql2, sql3 = free_port(), free_port(), free_port()

    def start(nid, sql, kv, extra):
        return subprocess.Popen(
            [sys.executable, "-m", "cockroach_tpu", "start",
             "--listen-addr", f"127.0.0.1:{sql}",
             "--node-id", str(nid),
             "--kv-addr", f"127.0.0.1:{kv}"] + extra,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env, cwd=os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))))

    procs = {}
    try:
        procs[1] = start(1, sql1, kv1, ["--bootstrap"])
        _wait_line(procs[1], "serving")
        procs[2] = start(2, sql2, kv2,
                         ["--join", f"1@127.0.0.1:{kv1}"])
        _wait_line(procs[2], "serving")
        procs[3] = start(3, sql3, kv3,
                         ["--join", f"1@127.0.0.1:{kv1}"])
        _wait_line(procs[3], "serving")

        # write through node 1's SQL gateway
        _sql(sql1, [
            "CREATE TABLE accounts (id INT PRIMARY KEY, bal INT)",
            "INSERT INTO accounts VALUES (1, 100), (2, 200), (3, 300)",
        ])
        # read on node 3: the rows came over raft + the fabric
        (_, rows, _), = _sql(sql3, [
            "SELECT id, bal FROM accounts ORDER BY id"])
        assert rows == [("1", "100"), ("2", "200"), ("3", "300")]

        # kill -9 node 1 (the bootstrap node / likely leaseholder):
        # committed rows must survive on the other two
        os.kill(procs[1].pid, signal.SIGKILL)
        procs[1].wait(timeout=10)
        (_, rows, _), = _sql(
            sql2, ["SELECT count(*) FROM accounts"], timeout=120)
        assert rows == [("3",)]
        # and the survivors accept new writes
        _sql(sql2, ["INSERT INTO accounts VALUES (4, 400)"],
             timeout=120)
        (_, rows, _), = _sql(sql3,
                             ["SELECT bal FROM accounts WHERE id = 4"],
                             timeout=120)
        assert rows == [("400",)]

        # restart node 1: it rejoins and serves the data again
        procs[1] = start(1, sql1, kv1,
                         ["--join", f"2@127.0.0.1:{kv2}"])
        _wait_line(procs[1], "serving")
        (_, rows, _), = _sql(sql1,
                             ["SELECT count(*) FROM accounts"],
                             timeout=120)
        assert rows == [("4",)]
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
