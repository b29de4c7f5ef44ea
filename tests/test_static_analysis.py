"""graftlint (cockroach_tpu/analysis) — the tier-1 gate and self-tests.

Three layers:

1. **The repo gate**: ``run()`` over the real tree must report ZERO
   unwaived findings across all eight rules, and every waiver must carry
   a reason (an empty-reason waiver is itself a finding, so this gate
   fails on it). Analyzer wall time and per-rule finding counts are
   printed so the tier-1 log shows what the gate cost and covered.
2. **Seeded-bad fixtures**: for each rule, a minimal violating snippet
   written into a throwaway package tree must be caught, its waived
   twin must pass, and a clean twin must report nothing — so a rule
   that silently stops matching (ast drift, refactor of the scan)
   fails here before a real regression slips through.
3. **Core units**: thread-role classification for the three seeded
   roles (pgwire session handler, mesh-dispatcher loop, page-prefetch
   worker), the git-scoped ``--changed-only`` file discovery, and a
   self-scan smoke check (the analyzer parses its own package).

Select just these with ``pytest -m graftlint``.
"""

import subprocess
import textwrap

import pytest

from cockroach_tpu.analysis import (ModuleIndex, RULES, render_human,
                                    render_json, run)
from cockroach_tpu.analysis import runner as runner_mod
from cockroach_tpu.analysis import rules_plan
from cockroach_tpu.analysis.runner import WAIVER_SYNTAX_BIT, changed_files
from cockroach_tpu.analysis.rules_registration import repo_root

pytestmark = pytest.mark.graftlint

REPO = repo_root()

RULE_NAMES = [name for name, _bit, _fn in RULES]


@pytest.fixture(scope="module")
def report():
    """One shared whole-repo analysis for every test in this module."""
    return run(root=REPO)


@pytest.fixture(scope="module")
def index(report):
    return report["index"]


def _tree(tmp_path, files: dict):
    """Materialize a throwaway package tree and return its root."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return tmp_path


def _scan(tmp_path, files: dict, rules):
    return run(root=_tree(tmp_path, files), rules=rules)


def _unwaived(report, rule=None):
    return [f for f in report["findings"]
            if not f.waived and (rule is None or f.rule == rule)]


# ---------------------------------------------------------------------------
# 1. the repo gate
# ---------------------------------------------------------------------------

class TestRepoGate:
    def test_zero_unwaived_findings(self, report):
        summary = render_human(report, show_waived=True)
        # the tier-1 log carries the analyzer cost + coverage counts
        print(f"\n{summary}")
        t = report["timings"]
        print(f"graftlint gate: {report['files']} files in "
              f"{t['total_seconds']:.2f}s; "
              + "; ".join(
                  f"{n}={report['counts'].get(n, {}).get('findings', 0)}"
                  for n in RULE_NAMES))
        assert report["exit_code"] == 0, f"\n{summary}"
        assert not _unwaived(report), f"\n{summary}"

    def test_all_eight_rules_ran(self, report):
        assert len(RULE_NAMES) == 8
        for name in RULE_NAMES:
            assert name in report["timings"], f"{name} did not run"

    def test_every_waiver_has_a_reason(self, index):
        bad = [(rel, line, rule)
               for rel, m in index.modules.items()
               for line, entries in m.waivers.items()
               for rule, reason in entries if not reason.strip()]
        assert not bad, f"waivers without reasons: {bad}"

    def test_waivers_name_real_rules(self, index):
        known = set(RULE_NAMES)
        bad = [(rel, line, rule)
               for rel, m in index.modules.items()
               # the analyzer's own sources quote the waiver syntax in
               # their docstrings ("waive[rule] reason"); everything
               # else must name a registered rule
               if not rel.startswith("cockroach_tpu/analysis/")
               for line, entries in m.waivers.items()
               for rule, _reason in entries if rule not in known]
        assert not bad, f"waivers for unknown rules (typo?): {bad}"

    def test_render_json_round_trips(self, report):
        import json
        data = json.loads(render_json(report))
        assert data["exit_code"] == report["exit_code"]
        assert data["files"] == report["files"]


# ---------------------------------------------------------------------------
# 2. seeded-bad fixtures, one per rule
# ---------------------------------------------------------------------------

BAD_ASARRAY = """
    import jax.numpy as jnp

    def upload(buf):
        return jnp.asarray(buf)
"""

WAIVED_ASARRAY = """
    import jax.numpy as jnp
    import numpy as np

    def upload():
        fresh = np.zeros(8)
        # graftlint: waive[no-aliasing-upload] fresh np.zeros above,
        # never written after this conversion
        return jnp.asarray(fresh)
"""

CLEAN_ASARRAY = """
    import jax.numpy as jnp

    def upload(buf):
        return jnp.array(buf)
"""


class TestNoAliasingUpload:
    RULE = ["no-aliasing-upload"]

    def test_bare_asarray_in_exec_is_caught(self, tmp_path):
        r = _scan(tmp_path, {"cockroach_tpu/exec/bad.py": BAD_ASARRAY},
                  self.RULE)
        hits = _unwaived(r, "no-aliasing-upload")
        assert len(hits) == 1 and r["exit_code"] == 1
        assert "jnp.asarray" in hits[0].message

    def test_waived_site_passes(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/exec/waived.py": WAIVED_ASARRAY},
                  self.RULE)
        assert r["exit_code"] == 0
        assert not _unwaived(r)
        assert r["counts"]["no-aliasing-upload"]["waived"] == 1

    def test_clean_and_out_of_scope_pass(self, tmp_path):
        r = _scan(tmp_path, {
            "cockroach_tpu/exec/clean.py": CLEAN_ASARRAY,
            # control plane: asarray is allowed outside the data plane
            "cockroach_tpu/server/ctl.py": BAD_ASARRAY,
        }, self.RULE)
        assert r["exit_code"] == 0 and not _unwaived(r)

    def test_empty_reason_waiver_fails_the_gate(self, tmp_path):
        src = """
            import jax.numpy as jnp

            def upload(buf):
                # graftlint: waive[no-aliasing-upload]
                return jnp.asarray(buf)
        """
        r = _scan(tmp_path, {"cockroach_tpu/exec/bad.py": src},
                  self.RULE)
        assert r["exit_code"] & WAIVER_SYNTAX_BIT
        assert any(f.rule == "waiver-syntax" for f in r["findings"])


BAD_COLLECTIVE = """
    import jax

    def fanout(fn, xs):
        return jax.pmap(fn)(xs)
"""

BAD_ESCAPED_MESH_FN = """
    from ..parallel.distagg import make_distributed_fn

    def plan(mesh, spec):
        dist = make_distributed_fn(mesh, spec)
        return dist  # escapes the dispatcher
"""

CLEAN_QUEUED_MESH_FN = """
    from ..parallel.distagg import (make_distributed_fn,
                                    queued_collective_call)

    def plan(mesh, spec, batch):
        dist = make_distributed_fn(mesh, spec)
        return queued_collective_call(mesh, dist, batch)
"""

BAD_RENDEZVOUS = """
    import jax

    def join(coord, n, i):
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=n, process_id=i)
"""

BAD_MULTIHOST_UTILS = """
    from jax.experimental import multihost_utils

    def fence(name):
        multihost_utils.sync_global_devices(name)
"""

WAIVED_RENDEZVOUS = """
    import jax

    def leave():
        # graftlint: waive[collective-discipline] test-only teardown of
        # a coordinator this process exclusively owns
        jax.distributed.shutdown()
"""

CLEAN_RENDEZVOUS = """
    from ..parallel import multihost

    def join(coord, n, i):
        return multihost.init_distributed(coord, n, i)

    def leave():
        multihost.shutdown_distributed()
"""


class TestCollectiveDiscipline:
    RULE = ["collective-discipline"]

    def test_pmap_outside_dispatcher_home_is_caught(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/exec/bad.py": BAD_COLLECTIVE},
                  self.RULE)
        hits = _unwaived(r, "collective-discipline")
        assert len(hits) == 1 and r["exit_code"] == 2
        assert "pmap" in hits[0].message

    def test_escaped_make_distributed_fn_is_caught(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/exec/bad.py": BAD_ESCAPED_MESH_FN},
                  self.RULE)
        assert len(_unwaived(r, "collective-discipline")) == 1

    def test_queued_flow_passes(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/exec/ok.py": CLEAN_QUEUED_MESH_FN},
                  self.RULE)
        assert r["exit_code"] == 0 and not _unwaived(r)

    def test_dispatcher_home_is_exempt(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/parallel/distagg.py": BAD_COLLECTIVE},
                  self.RULE)
        assert r["exit_code"] == 0 and not _unwaived(r)

    # round-15 extension: cross-host rendezvous entry points are
    # sanctioned only in parallel/multihost.py

    def test_rendezvous_outside_multihost_home_is_caught(self,
                                                         tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/server/bad.py": BAD_RENDEZVOUS},
                  self.RULE)
        hits = _unwaived(r, "collective-discipline")
        assert len(hits) == 1 and r["exit_code"] == 2
        assert "jax.distributed.initialize" in hits[0].message
        assert "multihost" in hits[0].message

    def test_multihost_utils_outside_home_is_caught(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/exec/bad.py": BAD_MULTIHOST_UTILS},
                  self.RULE)
        hits = _unwaived(r, "collective-discipline")
        assert len(hits) == 1
        assert "multihost_utils.sync_global_devices" in hits[0].message

    def test_waived_rendezvous_passes(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/server/waived.py": WAIVED_RENDEZVOUS},
                  self.RULE)
        assert r["exit_code"] == 0 and not _unwaived(r)
        assert r["counts"]["collective-discipline"]["waived"] == 1

    def test_multihost_home_is_exempt(self, tmp_path):
        r = _scan(tmp_path, {
            "cockroach_tpu/parallel/multihost.py": BAD_RENDEZVOUS,
            # wrapper calls from anywhere else are the sanctioned path
            "cockroach_tpu/server/clean.py": CLEAN_RENDEZVOUS,
        }, self.RULE)
        assert r["exit_code"] == 0 and not _unwaived(r)


BAD_RACY_GLOBAL = """
    SECONDS = [0.0]

    def note(dt):
        SECONDS[0] += dt
"""

CLEAN_LOCKED_GLOBAL = """
    import threading

    SECONDS = [0.0]
    _LOCK = threading.Lock()

    def note(dt):
        with _LOCK:
            SECONDS[0] += dt
"""

CLEAN_TALLY_GLOBAL = """
    from ..ops.pallas.groupagg_large import _KernelTally

    RUNS = _KernelTally()

    def note():
        RUNS.bump("hit")
"""


class TestRacyGlobal:
    RULE = ["racy-global"]

    def test_unlocked_augassign_is_caught(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/exec/bad.py": BAD_RACY_GLOBAL},
                  self.RULE)
        hits = _unwaived(r, "racy-global")
        assert len(hits) == 1 and r["exit_code"] == 4
        assert "SECONDS" in hits[0].message

    def test_locked_augassign_passes(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/exec/ok.py": CLEAN_LOCKED_GLOBAL},
                  self.RULE)
        assert r["exit_code"] == 0 and not _unwaived(r)

    def test_tally_wrapper_is_exempt(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/exec/ok.py": CLEAN_TALLY_GLOBAL},
                  self.RULE)
        assert r["exit_code"] == 0 and not _unwaived(r)


BAD_BLOCKING = """
    import threading
    import jax

    _LOCK = threading.Lock()

    def push(x):
        with _LOCK:
            return jax.device_put(x)
"""

CLEAN_BLOCKING = """
    import threading
    import jax

    _LOCK = threading.Lock()
    _CACHE = {}

    def push(key, x):
        with _LOCK:
            if key in _CACHE:
                return _CACHE[key]
        b = jax.device_put(x)
        with _LOCK:
            _CACHE[key] = b
        return b
"""

CLEAN_CV_WAIT = """
    import threading

    _CV = threading.Condition()

    def park():
        with _CV:
            _CV.wait(timeout=1.0)
"""


class TestBlockingUnderLock:
    RULE = ["blocking-under-lock"]

    def test_device_put_under_lock_is_caught(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/exec/bad.py": BAD_BLOCKING},
                  self.RULE)
        hits = _unwaived(r, "blocking-under-lock")
        assert len(hits) == 1 and r["exit_code"] == 8
        assert "device_put" in hits[0].message

    def test_upload_outside_lock_passes(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/exec/ok.py": CLEAN_BLOCKING},
                  self.RULE)
        assert r["exit_code"] == 0 and not _unwaived(r)

    def test_condition_variable_wait_is_sanctioned(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/exec/ok.py": CLEAN_CV_WAIT},
                  self.RULE)
        assert r["exit_code"] == 0 and not _unwaived(r)


# --- round 18: the batch-window wait/notify + group-commit tally
# idioms, seeded BAD/CLEAN so the analyzer keeps guarding the shapes
# oltpbatch.py and kvserver/raft.py actually use -------------------

BAD_WINDOW_STATS = """
    import threading

    WINDOW_SIZES = []
    _LOCK = threading.Lock()

    def note_window(reqs):
        # stats bump escaped the lock: two leaders draining their
        # windows concurrently lose appends
        WINDOW_SIZES.append(len(reqs))
"""

BAD_GLOBAL_PROPOSALS = """
    PROPOSALS = 0

    def bump():
        global PROPOSALS
        PROPOSALS += 1
"""

CLEAN_GROUPCOMMIT_TALLY = """
    import threading

    class _GroupCommitTally:
        def __init__(self):
            self._mu = threading.Lock()
            self._proposals = 0

        def bump(self, commands):
            with self._mu:
                self._proposals += 1

    GROUPCOMMIT = _GroupCommitTally()

    def commit_round(nops):
        GROUPCOMMIT.bump(nops)
"""

BAD_WINDOW_RUN_UNDER_LOCK = """
    import threading

    _LOCK = threading.Lock()

    def submit(req, done):
        with _LOCK:
            # leader runs the window while every follower's submit
            # blocks on the same lock: the convoy the split
            # collectors exist to avoid
            done.wait(timeout=5.0)
"""

CLEAN_COLLECTOR_WINDOW = """
    import threading

    class Collector:
        def __init__(self, run_fn):
            self.window_cv = threading.Condition()
            self.queue = []
            self.busy = False
            self.run_fn = run_fn

        def submit(self, req):
            batch = None
            with self.window_cv:
                self.queue.append(req)
                while not req.done:
                    if not self.busy:
                        self.busy = True
                        batch, self.queue = self.queue, []
                        break
                    self.window_cv.wait(timeout=1.0)
            if batch is not None:
                try:
                    self.run_fn(batch)
                finally:
                    with self.window_cv:
                        self.busy = False
                        self.window_cv.notify_all()
"""

WAIVED_WINDOW_STATS = """
    import threading

    WINDOW_SIZES = []
    _LOCK = threading.Lock()

    def note_window(reqs):
        # graftlint: waive[racy-global] single-threaded bench
        # bookkeeping, never reached from session threads
        WINDOW_SIZES.append(len(reqs))
"""


class TestBatchWindowIdioms:
    """The round-18 concurrency shapes stay analyzable: unlocked
    window stats and bare global proposal counters are caught, the
    lock-inside-Tally wrapper and the condition-variable collector
    are sanctioned, waivers still work."""

    def test_unlocked_window_stats_caught(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/exec/bad.py": BAD_WINDOW_STATS},
                  ["racy-global"])
        hits = _unwaived(r, "racy-global")
        assert len(hits) == 1
        assert "WINDOW_SIZES" in hits[0].message

    def test_bare_global_proposal_counter_caught(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/kvserver/bad.py":
                   BAD_GLOBAL_PROPOSALS},
                  ["racy-global"])
        hits = _unwaived(r, "racy-global")
        assert len(hits) == 1
        assert "PROPOSALS" in hits[0].message

    def test_groupcommit_tally_wrapper_sanctioned(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/kvserver/ok.py":
                   CLEAN_GROUPCOMMIT_TALLY},
                  ["racy-global"])
        assert r["exit_code"] == 0 and not _unwaived(r)

    def test_window_run_under_plain_lock_caught(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/exec/bad.py":
                   BAD_WINDOW_RUN_UNDER_LOCK},
                  ["blocking-under-lock"])
        hits = _unwaived(r, "blocking-under-lock")
        assert len(hits) == 1
        assert "wait" in hits[0].message

    def test_collector_cv_idiom_sanctioned(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/exec/ok.py": CLEAN_COLLECTOR_WINDOW},
                  ["blocking-under-lock", "racy-global"])
        assert r["exit_code"] == 0 and not _unwaived(r)

    def test_waived_window_stats_pass(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/exec/ok.py": WAIVED_WINDOW_STATS},
                  ["racy-global"])
        assert not _unwaived(r)
        waived = [f for f in r["findings"] if f.waived]
        assert len(waived) == 1


class TestPlanKeyCompleteness:
    def test_real_prepare_closure_is_complete(self, report):
        assert not _unwaived(report, "plan-key-completeness")

    def test_lost_anchor_is_a_loud_finding(self, index, monkeypatch):
        # a rename of _prepare_select must NOT silently disable the
        # rule: the anchor miss is itself a finding
        monkeypatch.setattr(rules_plan, "PREPARE_FUNC",
                            "renamed_out_from_under_the_rule")
        findings = rules_plan.check_plan_key_completeness(index)
        assert len(findings) == 1
        assert "anchor" in findings[0].message

    def test_whitelist_entries_are_all_read(self, index):
        # drift findings double as this check, but assert directly so
        # a stale whitelist shows up with its own message
        findings = rules_plan.check_plan_key_completeness(index)
        drift = [f for f in findings if "whitelist drift" in f.message]
        assert not drift, [f.message for f in drift]


class TestRegistrationDrift:
    def test_real_tree_is_clean(self, report):
        assert not _unwaived(report, "registration-drift")

    def test_bad_metric_name_and_doc_drift_caught(self, tmp_path):
        src = """
            def reg(metrics):
                metrics.counter("Bad.Name", "desc").inc()
        """
        r = _scan(tmp_path, {"cockroach_tpu/exec/m.py": src},
                  ["registration-drift"])
        msgs = [f.message for f in _unwaived(r, "registration-drift")]
        assert any("lowercase" in m for m in msgs)
        assert any("OBSERVABILITY.md" in m for m in msgs)
        assert r["exit_code"] == 32


BAD_LEASE_READ = """
    def plan(leases, table):
        # raw ownership poke: no epoch fence
        return leases._assignments[(table, 3)]
"""

BAD_LEASE_KEY = """
    from cockroach_tpu.parallel import multihost

    def owner_of(table, sid, epoch):
        import json
        raw = multihost.kv_try_get(f"ls/assign/{table}/{epoch}")
        return json.loads(raw)[str(sid)]
"""

WAIVED_LEASE_READ = """
    def cache_depth(leases):
        # graftlint: waive[lease-discipline] introspection only: counts
        # cached epochs, never reads an owner out of the raw table
        return len(leases._assignments)
"""

CLEAN_LEASE_READ = """
    def plan(pod, table, epoch):
        view = pod.leases.view_at(epoch)
        return view.assignment(table)
"""


class TestLeaseDiscipline:
    RULE = ["lease-discipline"]

    def test_real_tree_is_clean(self, report):
        assert not _unwaived(report, "lease-discipline")

    def test_raw_assignment_read_is_caught(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/distsql/bad.py": BAD_LEASE_READ},
                  self.RULE)
        hits = _unwaived(r, "lease-discipline")
        assert len(hits) == 1 and r["exit_code"] == 64
        assert "_assignments" in hits[0].message
        assert "epoch" in hits[0].message

    def test_raw_lease_key_in_server_is_caught(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/server/bad.py": BAD_LEASE_KEY},
                  self.RULE)
        hits = _unwaived(r, "lease-discipline")
        assert len(hits) == 1 and r["exit_code"] == 64
        assert "ls/assign" in hits[0].message

    def test_waived_site_passes(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/distsql/waived.py": WAIVED_LEASE_READ},
                  self.RULE)
        assert r["exit_code"] == 0 and not _unwaived(r)
        assert r["counts"]["lease-discipline"]["waived"] == 1

    def test_clean_and_out_of_scope_pass(self, tmp_path):
        r = _scan(tmp_path, {
            "cockroach_tpu/distsql/clean.py": CLEAN_LEASE_READ,
            # the lease home itself owns the raw substrate
            "cockroach_tpu/distsql/leases.py": BAD_LEASE_READ,
            # engine/ops trees are out of scope (no planner reads there)
            "cockroach_tpu/exec/off.py": BAD_LEASE_KEY,
        }, self.RULE)
        assert r["exit_code"] == 0 and not _unwaived(r)


BAD_REACTOR_LOOP = """
    class PollReactor:
        def _loop(self):
            while True:
                events = self.sel.select(0.25)
                for key, _mask in events:
                    data = key.fileobj.recv(4096)
                    fut = self.pool.submit(self.work, data)
                    fut.result()
"""

BAD_REACTOR_HELPER = """
    class FanReactor:
        def _loop(self):
            while not self.stopping:
                self._tick()

        def _tick(self):
            self.engine.execute("SELECT 1")
"""

WAIVED_REACTOR = """
    class DrainReactor:
        def _loop(self):
            while not self.stopping:
                self.sel.select(0.25)
            # graftlint: waive[reactor-discipline] shutdown path: the
            # stop flag is already set, no session is parked behind us
            self.flusher.join()
"""

CLEAN_REACTOR = """
    class CalmReactor:
        def _loop(self):
            while not self.stopping:
                events = self.sel.select(0.25)
                for key, _mask in events:
                    self._readable(key.data)

        def _readable(self, sess):
            data = sess.sock.recv(65536)
            with sess.lk:
                sess.frames.append(data)
            self.pool.submit(self._drain, sess)

        def _drain(self, sess):
            # worker side: blocking is fine here, and submit() passed
            # this as an argument, so the walk never enters it
            return sess.fut.result()
"""

NONREACTOR_LOOP = """
    class PollServer:
        def _loop(self):
            self.fut.result()
"""


class TestReactorDiscipline:
    RULE = ["reactor-discipline"]

    def test_real_tree_is_clean(self, report):
        assert not _unwaived(report, "reactor-discipline")

    def test_blocking_in_loop_body_caught(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/server/badfront.py": BAD_REACTOR_LOOP},
                  self.RULE)
        hits = _unwaived(r, "reactor-discipline")
        assert r["exit_code"] == 128
        assert any(".result()" in h.message for h in hits)
        assert any(".recv()" in h.message for h in hits)
        assert len(hits) == 2

    def test_transitive_helper_caught(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/server/fan.py": BAD_REACTOR_HELPER},
                  self.RULE)
        hits = _unwaived(r, "reactor-discipline")
        assert len(hits) == 1 and r["exit_code"] == 128
        assert ".execute()" in hits[0].message
        assert "_tick" in hits[0].message  # blames the helper site

    def test_waived_site_passes(self, tmp_path):
        r = _scan(tmp_path,
                  {"cockroach_tpu/server/drain.py": WAIVED_REACTOR},
                  self.RULE)
        assert r["exit_code"] == 0 and not _unwaived(r)
        assert r["counts"]["reactor-discipline"]["waived"] == 1

    def test_clean_and_out_of_scope_pass(self, tmp_path):
        r = _scan(tmp_path, {
            "cockroach_tpu/server/calm.py": CLEAN_REACTOR,
            # classes not named *Reactor* keep the blocking idiom
            "cockroach_tpu/server/plain.py": NONREACTOR_LOOP,
            # and the rule only scopes server/ modules
            "cockroach_tpu/exec/off.py": BAD_REACTOR_LOOP,
        }, self.RULE)
        assert r["exit_code"] == 0 and not _unwaived(r)


# ---------------------------------------------------------------------------
# 3. core units
# ---------------------------------------------------------------------------

class TestThreadRoles:
    def test_pgwire_session_handler(self, index):
        roles = index.roles_of("cockroach_tpu/server/pgwire.py"
                               "::_Conn.serve")
        assert "pgwire-session" in roles

    def test_mesh_dispatcher_loop(self, index):
        roles = index.roles_of("cockroach_tpu/parallel/distagg.py"
                               "::_MeshDispatcher._loop")
        assert "mesh-dispatch" in roles

    def test_prefetch_worker(self, index):
        roles = index.roles_of("cockroach_tpu/exec/stream.py"
                               "::prefetch.<locals>.worker")
        assert "page-prefetch" in roles

    def test_roles_propagate_along_calls(self, tmp_path):
        src = """
            import threading

            def _inner():
                pass

            def _body():
                _inner()

            def start():
                threading.Thread(target=_body, name="bg-loop").start()
        """
        idx = ModuleIndex.build(
            _tree(tmp_path, {"cockroach_tpu/exec/t.py": src}))
        assert "bg-loop" in idx.roles_of(
            "cockroach_tpu/exec/t.py::_body")
        assert "bg-loop" in idx.roles_of(
            "cockroach_tpu/exec/t.py::_inner")


class TestChangedOnly:
    def test_changed_files_parses_porcelain(self, monkeypatch):
        out = (" M cockroach_tpu/exec/engine.py\n"
               "?? cockroach_tpu/analysis/new_rule.py\n"
               " M tests/test_static_analysis.py\n"
               " M README.md\n"
               "R  a.py -> cockroach_tpu/exec/renamed.py\n")

        class _Done:
            stdout = out

        monkeypatch.setattr(
            runner_mod.subprocess, "run",
            lambda *a, **k: _Done())
        assert changed_files(REPO) == [
            "cockroach_tpu/exec/engine.py",
            "cockroach_tpu/analysis/new_rule.py",
            "cockroach_tpu/exec/renamed.py",
        ]

    def test_changed_files_none_when_git_fails(self, monkeypatch):
        def _boom(*a, **k):
            raise subprocess.SubprocessError("no git")

        monkeypatch.setattr(runner_mod.subprocess, "run", _boom)
        assert changed_files(REPO) is None

    def test_only_files_filters_findings(self, tmp_path):
        root = _tree(tmp_path, {
            "cockroach_tpu/exec/bad.py": BAD_ASARRAY,
            "cockroach_tpu/exec/also_bad.py": BAD_ASARRAY,
        })
        r = run(root=root, rules=["no-aliasing-upload"],
                only_files=["cockroach_tpu/exec/bad.py"])
        assert {f.path for f in r["findings"]} == \
            {"cockroach_tpu/exec/bad.py"}


class TestSelfScan:
    def test_analyzer_indexes_itself(self, index):
        for rel in ("cockroach_tpu/analysis/core.py",
                    "cockroach_tpu/analysis/runner.py",
                    "cockroach_tpu/analysis/rules_device.py",
                    "cockroach_tpu/analysis/rules_concurrency.py",
                    "cockroach_tpu/analysis/rules_plan.py",
                    "cockroach_tpu/analysis/rules_registration.py"):
            assert rel in index.modules, f"self-scan lost {rel}"
        assert not index.parse_errors

    def test_module_entrypoint_runs_clean(self):
        # the exact command STATIC_ANALYSIS.md documents, subset to the
        # two cheapest rules so the smoke test stays fast
        proc = subprocess.run(
            ["python", "-m", "cockroach_tpu.analysis",
             "--rules", "no-aliasing-upload,racy-global"],
            cwd=str(REPO), capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "no-aliasing-upload" in proc.stdout
