"""Reactor pgwire front end (server/pgfront.py): parity, soak, quotas.

Four planes:

1. **Wire parity**: the reactor and thread front ends drive the same
   ``_Conn`` handlers, so every reply stream must be BYTE-IDENTICAL
   (modulo BackendKeyData, whose conn id is per-accept) across the
   ``pgwire_frontend`` A/B lever — simple queries, the extended
   protocol, error + skip-until-Sync recovery, SSL-deny, and cancel
   packets.
2. **Idle-session soak**: 1K parked sessions must cost zero threads
   and O(1) memory each — thread count flat between 200 and 1000
   connected sessions, RSS growth bounded per session, and a clean
   scale-down with no leaked handler threads.
3. **Hygiene**: slow-loris startup deadline, idle-session timeout
   (with the in-transaction carve-out), and abrupt RST teardown.
4. **Tenant quotas**: a noisy tenant churning novel statements
   self-evicts at ``sql.exec.plan_cache.tenant_budget`` and cannot
   push another tenant's plan-cache entries out; the admission
   controller's per-tenant slot/HBM ledger parks the over-quota
   tenant while leaving others on the fast path; the prepared-
   statement budget rejects with SQLSTATE 53400.
"""

import os
import socket
import struct
import threading
import time

import pytest

from cockroach_tpu.cli import PgClient, PgError
from cockroach_tpu.server import Node, NodeConfig
from cockroach_tpu.server import pgwire
from cockroach_tpu.utils.admission import (AdmissionController,
                                           AdmissionRejected)


@pytest.fixture(scope="module")
def node():
    with Node(NodeConfig()) as n:
        yield n


@pytest.fixture(scope="module")
def threads_server(node):
    """A second, thread-per-connection front door over the SAME engine
    (the reactor is the node's default) — the parity A/B pair."""
    srv = pgwire.PgServer(node.engine, "127.0.0.1", 0,
                          version=node.pg.version,
                          frontend="threads").start()
    yield srv
    srv.stop()


@pytest.fixture(scope="module", autouse=True)
def _file_descriptors():
    """The soak opens ~2K fds in-process (client + server end)."""
    try:
        import resource
        soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        want = min(hard, 4096) if hard > 0 else 4096
        if soft < want:
            resource.setrlimit(resource.RLIMIT_NOFILE, (want, hard))
    except Exception:
        pass
    yield


# ---------------------------------------------------------------------------
# wire helpers (raw pgwire v3 bytes, no client abstraction in the way)
# ---------------------------------------------------------------------------

def _startup(user="root", database="defaultdb"):
    params = (f"user\x00{user}\x00database\x00{database}\x00\x00"
              .encode())
    body = struct.pack("!I", 196608) + params
    return struct.pack("!I", len(body) + 4) + body


def _frame(typ: bytes, body: bytes = b"") -> bytes:
    return typ + struct.pack("!I", len(body) + 4) + body


def _recv_all(sock, timeout=15.0) -> bytes:
    """Everything the server sends until it closes the connection. A
    server that closes with bytes of ours unread in its buffer (a
    FATAL startup reply: the script's next packet may or may not have
    arrived yet) resets the connection where it would have sent FIN;
    what it wrote before is still delivered first, so a reset ends the
    stream like a close."""
    sock.settimeout(timeout)
    chunks = []
    while True:
        try:
            b = sock.recv(1 << 16)
        except (socket.timeout, TimeoutError):
            raise AssertionError("server did not close the connection")
        except ConnectionResetError:
            b = b""
        if not b:
            return b"".join(chunks)
        chunks.append(b)


def _frames(data: bytes):
    """Split a backend byte stream into (type, body) frames. A leading
    b'N' (SSL denied) is a bare byte, not a typed frame — detect it by
    the nonsense length a frame read would produce."""
    out = []
    if data[:1] == b"N":
        ln = (struct.unpack_from("!I", data, 1)[0]
              if len(data) >= 5 else 0)
        if ln < 4 or ln > len(data) - 1:
            out.append((b"N*", b""))
            data = data[1:]
    off = 0
    while off < len(data):
        typ = data[off:off + 1]
        (ln,) = struct.unpack_from("!I", data, off + 1)
        out.append((typ, data[off + 5:off + 1 + ln]))
        off += 1 + ln
    return out


def _exchange(addr, payload: bytes, prelude: bytes = b"") -> list:
    """Connect, run startup (+ optional prelude packet first), send
    the scripted payload, and return the full reply as parsed frames
    with BackendKeyData dropped (its conn id is per-accept, the one
    legitimately non-identical frame across front ends)."""
    sock = socket.create_connection(addr, timeout=15.0)
    try:
        try:
            if prelude:
                sock.sendall(prelude)
            sock.sendall(_startup())
            sock.sendall(payload)
        except OSError:
            pass  # server may close first (FATAL startup replies)
        data = _recv_all(sock)
    finally:
        sock.close()
    return [(t, b) for t, b in _frames(data) if t != b"K"]


# ---------------------------------------------------------------------------
# 1. reactor == threads on the wire
# ---------------------------------------------------------------------------

class TestFrontendParity:
    @pytest.fixture(scope="class", autouse=True)
    def _data(self, node):
        c = PgClient(*node.sql_addr)
        c.query("DROP TABLE IF EXISTS par; "
                "CREATE TABLE par (k INT PRIMARY KEY, v FLOAT); "
                "INSERT INTO par VALUES (1, 1.5), (2, 2.5), (3, 3.5)")
        c.close()
        yield

    def _ab(self, node, threads_server, payload, prelude=b""):
        a = _exchange(node.sql_addr, payload, prelude)
        b = _exchange(threads_server.addr, payload, prelude)
        assert a == b, "reply streams diverge across frontends"
        return a

    def test_simple_query(self, node, threads_server):
        payload = (_frame(b"Q", b"SELECT k, v FROM par ORDER BY k\x00")
                   + _frame(b"Q", b"SELECT 40 + 2\x00")
                   + _frame(b"X"))
        frames = self._ab(node, threads_server, payload)
        types = [t for t, _ in frames]
        assert types.count(b"T") == 2 and types.count(b"D") == 4

    def test_multi_statement_and_error(self, node, threads_server):
        payload = (_frame(b"Q", b"SELECT 1; SELECT 2\x00")
                   + _frame(b"Q", b"SELECT no_such_col FROM par\x00")
                   + _frame(b"Q", b"SELECT 7\x00")  # conn survives
                   + _frame(b"X"))
        frames = self._ab(node, threads_server, payload)
        types = [t for t, _ in frames]
        assert b"E" in types
        assert types.count(b"Z") == 4  # startup + 3 queries

    def test_extended_protocol_and_skip_until_sync(
            self, node, threads_server):
        parse = (b"\x00" + b"SELECT k, v FROM par WHERE k = 2\x00"
                 + struct.pack("!H", 0))
        bind = (b"\x00\x00" + struct.pack("!H", 0)
                + struct.pack("!H", 0) + struct.pack("!H", 0))
        payload = (
            _frame(b"P", parse) + _frame(b"B", bind)
            + _frame(b"D", b"P\x00")
            + _frame(b"E", b"\x00" + struct.pack("!I", 0))
            + _frame(b"S")
            # a failing Parse flips the error state: the Bind/Execute
            # behind it must be skipped until Sync on BOTH front ends
            + _frame(b"P", b"\x00" + b"SELEC nope\x00"
                     + struct.pack("!H", 0))
            + _frame(b"B", bind)
            + _frame(b"E", b"\x00" + struct.pack("!I", 0))
            + _frame(b"S")
            + _frame(b"X"))
        frames = self._ab(node, threads_server, payload)
        types = [t for t, _ in frames]
        assert types.count(b"D") == 1     # one row from the good portal
        assert b"E" in types              # the bad Parse errored
        assert types.count(b"Z") == 3     # startup + 2 Syncs

    def test_ssl_denied_then_cleartext(self, node, threads_server):
        ssl_req = struct.pack("!II", 8, 80877103)
        payload = _frame(b"Q", b"SELECT 5\x00") + _frame(b"X")
        frames = self._ab(node, threads_server, payload,
                          prelude=ssl_req)
        assert frames[0][0] == b"N*"      # both front ends deny with N

    def test_cancel_request_closes_silently(self, node, threads_server):
        cancel = struct.pack("!IIII", 16, 80877102, 1234, 5678)
        for addr in (node.sql_addr, threads_server.addr):
            sock = socket.create_connection(addr, timeout=10.0)
            try:
                sock.sendall(cancel)
                assert _recv_all(sock) == b""
            finally:
                sock.close()

    def test_unsupported_protocol_fatal(self, node, threads_server):
        bad = struct.pack("!II", 8, (2 << 16))
        a = _exchange(node.sql_addr, b"", prelude=bad)
        # prelude consumed as the startup packet; _startup() after it
        # is never parsed (conn is closed) on either frontend
        b = _exchange(threads_server.addr, b"", prelude=bad)
        assert a == b
        assert a and a[0][0] == b"E" and b"0A000" in a[0][1]


# ---------------------------------------------------------------------------
# 2. the 1K-idle-session soak: flat RSS, constant threads
# ---------------------------------------------------------------------------

def _rss_kb() -> int:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1])
    return 0


def _connect_idle(addr):
    """Connect, finish startup through ReadyForQuery, then go idle."""
    sock = socket.create_connection(addr, timeout=30.0)
    sock.sendall(_startup())
    sock.settimeout(30.0)
    buf = b""
    while True:
        off = 0
        while len(buf) - off >= 5:
            typ = buf[off:off + 1]
            (ln,) = struct.unpack_from("!I", buf, off + 1)
            if len(buf) - off < 1 + ln:
                break
            if typ == b"Z":
                return sock
            off += 1 + ln
        buf = buf[off:]
        b = sock.recv(4096)
        if not b:
            raise ConnectionError("server closed during startup")
        buf += b


def test_idle_session_soak_flat_memory_and_threads(node):
    impl = node.pg._impl
    base_sessions = len(impl._sessions)
    socks = []
    try:
        for _ in range(200):
            socks.append(_connect_idle(node.sql_addr))
        threads_at_200 = threading.active_count()
        rss_at_200 = _rss_kb()
        for _ in range(800):
            socks.append(_connect_idle(node.sql_addr))
        threads_at_1000 = threading.active_count()
        rss_at_1000 = _rss_kb()
        assert len(impl._sessions) >= base_sessions + 1000
        # zero threads per parked session: workers belong to the
        # executor pool, which spawns one only when a startup finds
        # none idle — under a loaded CPU it may still be growing
        # after 200 startups, but never past its fixed cap, however
        # many sessions park
        assert threads_at_1000 - threads_at_200 <= \
            impl._pool._max_workers, (
            f"threads grew {threads_at_200} -> {threads_at_1000} "
            f"over 800 idle sessions")
        # O(1) memory per parked session (a _Session + a _Conn + an
        # engine Session; a thread-per-conn stack would be ~8MB each)
        per_session_kb = max(0, rss_at_1000 - rss_at_200) / 800.0
        assert per_session_kb < 100, (
            f"{per_session_kb:.0f}KB RSS per idle session")
        # all 1000 are parked: nobody owns a worker
        deadline = time.monotonic() + 10
        while impl._count_active() > 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert impl._count_active() == 0
    finally:
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
    # clean scale-down: every teardown runs, nothing leaks
    deadline = time.monotonic() + 30
    while (len(impl._sessions) > base_sessions
           and time.monotonic() < deadline):
        time.sleep(0.1)
    assert len(impl._sessions) <= base_sessions
    # 1000 teardowns ran on the bounded pool: thread count is capped
    # by the pool size, never by the session count
    assert (threading.active_count()
            <= threads_at_1000 + impl._pool._max_workers)


# ---------------------------------------------------------------------------
# 3. hygiene: slow-loris, idle timeout, RST teardown
# ---------------------------------------------------------------------------

def test_startup_deadline_cuts_slow_loris(node):
    node.engine.settings.set("server.startup_deadline_seconds", 0.5)
    try:
        sock = socket.create_connection(node.sql_addr, timeout=10.0)
        try:
            # send nothing: a half-open startup must not pin the front
            # door past the deadline
            sock.settimeout(10.0)
            assert sock.recv(64) == b""
        finally:
            sock.close()
    finally:
        node.engine.settings.set("server.startup_deadline_seconds",
                                 10.0)


def test_idle_session_timeout_retires_parked_sessions(node):
    node.engine.settings.set("server.idle_session_timeout", 0.5)
    try:
        sock = _connect_idle(node.sql_addr)
        try:
            sock.settimeout(10.0)
            assert sock.recv(64) == b""   # retired, socket closed
        finally:
            sock.close()
    finally:
        node.engine.settings.set("server.idle_session_timeout", 0.0)


def test_idle_timeout_spares_open_transactions(node):
    node.engine.settings.set("server.idle_session_timeout", 0.6)
    try:
        c = PgClient(*node.sql_addr)
        c.query("BEGIN")
        time.sleep(1.5)   # several sweep periods past the deadline
        # the txn carve-out: a session holding locks is never retired
        _, rows, _ = c.query("SELECT 11 + 31")
        assert rows == [("42",)]
        c.query("ROLLBACK")
        c.close()
    finally:
        node.engine.settings.set("server.idle_session_timeout", 0.0)


def test_rst_teardown_leaks_nothing(node):
    impl = node.pg._impl
    base_threads = threading.active_count()
    for _ in range(10):
        sock = _connect_idle(node.sql_addr)
        # SO_LINGER(on, 0): close() sends RST, not FIN — the ugly
        # teardown path (client crash, NAT reset)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack("ii", 1, 0))
        sock.close()
    deadline = time.monotonic() + 10
    while (any(not s.closed for s in list(impl._sessions.values()))
           and time.monotonic() < deadline):
        time.sleep(0.05)
    time.sleep(0.2)
    assert threading.active_count() <= base_threads + 2


# ---------------------------------------------------------------------------
# 4. tenant quotas: cache isolation, slot/HBM ledger, prepared budget
# ---------------------------------------------------------------------------

def test_noisy_tenant_cannot_evict_neighbor_plans(node):
    eng = node.engine
    eng.settings.set("sql.exec.plan_cache.tenant_budget", 4)
    try:
        quiet = eng.session()
        quiet.vars.set("application_name", "t_quiet")
        noisy = eng.session()
        noisy.vars.set("application_name", "t_noisy")
        eng.execute("SELECT 1 + 0", session=quiet)
        assert "SELECT 1 + 0" in eng._parse_cache
        # the noisy tenant churns 20 novel statement shapes
        for i in range(20):
            eng.execute(f"SELECT {i} + 1000", session=noisy)
        counts = eng._parse_cache.tenant_entry_counts()
        assert counts.get("t_noisy", 0) <= 4, (
            "noisy tenant exceeded its plan-cache budget")
        # isolation: the quiet tenant's entry survived the churn
        assert "SELECT 1 + 0" in eng._parse_cache
        assert eng._parse_cache.tenant_evictions.get("t_noisy", 0) >= 16
        assert eng._parse_cache.tenant_evictions.get("t_quiet", 0) == 0
    finally:
        eng.settings.set("sql.exec.plan_cache.tenant_budget", 0)


def test_tenant_slot_ledger_parks_only_the_noisy_tenant():
    ac = AdmissionController(slots=4)
    ac.tenant_slots = 1
    ac.acquire(tenant="noisy")
    # noisy's second statement must queue (tenant at its slot cap)...
    with pytest.raises(AdmissionRejected):
        ac.acquire(tenant="noisy", timeout=0.05)
    assert ac.tenant_slot_waits >= 1
    # ...while a well-behaved tenant sails through the fast path
    t0 = time.monotonic()
    ac.acquire(tenant="quiet")
    assert time.monotonic() - t0 < 0.05
    # release unblocks the parked tenant
    done = []
    th = threading.Thread(
        target=lambda: (ac.acquire(tenant="noisy", timeout=5.0),
                        done.append(1)))
    th.start()
    time.sleep(0.05)
    ac.release(tenant="noisy")
    th.join(timeout=5.0)
    assert done == [1]
    ac.release(tenant="noisy")
    ac.release(tenant="quiet")
    assert ac.tenant_usage() == {}


def test_tenant_hbm_ledger_admits_first_statement():
    """A statement bigger than the whole tenant HBM budget must not
    deadlock: with zero in-flight bytes the tenant is always
    HBM-eligible (the budget gates CONCURRENCY, not statement size)."""
    ac = AdmissionController(slots=4)
    ac.tenant_hbm_bytes = 1000
    ac.acquire(tenant="big", hbm=5000)      # over budget, held == 0
    with pytest.raises(AdmissionRejected):
        ac.acquire(tenant="big", hbm=1, timeout=0.05)
    assert ac.tenant_hbm_waits >= 1
    ac.release(tenant="big", hbm=5000)
    ac.acquire(tenant="big", hbm=1)         # ledger drained
    ac.release(tenant="big", hbm=1)


def test_prepared_statement_budget_rejects_with_53400(node):
    node.engine.settings.set("server.prepared_statement_budget", 4)
    try:
        sock = socket.create_connection(node.sql_addr, timeout=15.0)
        try:
            sock.sendall(_startup())
            parses = b""
            for i in range(5):
                parses += _frame(
                    b"P", (f"s{i}".encode() + b"\x00"
                           + b"SELECT 1\x00" + struct.pack("!H", 0)))
            sock.sendall(parses + _frame(b"S") + _frame(b"X"))
            frames = _frames(_recv_all(sock))
        finally:
            sock.close()
        types = [t for t, _ in frames]
        assert types.count(b"1") == 4        # four ParseComplete
        errs = [b for t, b in frames if t == b"E"]
        assert len(errs) == 1 and b"53400" in errs[0]
    finally:
        node.engine.settings.set("server.prepared_statement_budget",
                                 256)
