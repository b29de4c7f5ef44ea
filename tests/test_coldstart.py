"""Cold-start elimination (exec/coldstart.py).

Covers the persistent-compile-cache plumbing (cross-process warm
start lives in the slow lane), the shape-bucket ladder (parity across
ladder configs + the executable budget), the bounded parse/
executable cache eviction, the per-statement compile-vs-execute
split, and the retired `sql.exec.pallas.autotune` setting the
benchmark's configurations still SET."""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from cockroach_tpu.exec import coldstart
from cockroach_tpu.exec.coldstart import ShapeLadder
from cockroach_tpu.exec.engine import Engine
from cockroach_tpu.utils.settings import SettingError, Settings

REPO = pathlib.Path(__file__).resolve().parent.parent


def _next_pow2(n):
    return 1 << (max(n, 1) - 1).bit_length()


# ---------------------------------------------------------------- ladder

class TestShapeLadder:
    def test_default_is_classic_pow2_padding(self):
        lad = ShapeLadder()
        for n in (1, 5, 1000, 1024, 1025, 5000, 1 << 20, (1 << 20) + 1):
            assert lad.bucket(n) == max(_next_pow2(n), 1024)

    def test_steps_per_octave_2(self):
        lad = ShapeLadder(steps_per_octave=2)
        assert lad.bucket(1024) == 1024
        assert lad.bucket(1025) == 1536
        assert lad.bucket(1536) == 1536
        assert lad.bucket(1537) == 2048
        assert lad.bucket(3073) == 4096
        # idempotent + monotone + Pallas-aligned
        prev = 0
        for n in range(1, 9000, 37):
            b = lad.bucket(n)
            assert b >= n and b % 128 == 0
            assert lad.bucket(b) == b
            assert b >= prev
            prev = b

    def test_budget_counts_reachable_rungs(self):
        assert ShapeLadder().budget(3500) == 3          # 1K, 2K, 4K
        assert ShapeLadder(steps_per_octave=2).budget(3500) == 5
        assert ShapeLadder().rungs(3500) == [1024, 2048, 4096]

    def test_validation(self):
        with pytest.raises(ValueError):
            ShapeLadder(min_rows=1000)
        with pytest.raises(ValueError):
            ShapeLadder(steps_per_octave=3)
        with pytest.raises(ValueError):
            ShapeLadder(min_rows=128, steps_per_octave=2)


# ------------------------------------------------------- cache plumbing

class TestCompileCachePlumbing:
    def test_cache_placed_by_env_is_left_alone(self):
        import jax
        placed = os.environ["JAX_COMPILATION_CACHE_DIR"]
        eng = Engine()
        # exactly the directory the launcher named: no subdirectory,
        # and jax's own setting untouched by the engine
        assert eng._compile_cache_dir == placed
        assert jax.config.jax_compilation_cache_dir == placed
        assert coldstart.cache_error() is None

    def test_env_set_after_import_is_refused(self, tmp_path,
                                             monkeypatch):
        # jax read the variable at import; an engine that re-pointed
        # jax at a late value would be setting the directory in code
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "late"))
        with pytest.raises(RuntimeError, match="after jax was imported"):
            Engine()

    def test_unset_env_uses_checkout_dir(self, tmp_path):
        assert coldstart.checkout_cache_dir() == str(REPO / ".jax_cache")
        # the child runs from a checkout of its own, the package linked
        # into tmp_path: in the real one its .jax_cache, present while
        # the child lives, fails the hermetic check (conftest) of
        # whatever the other workers finish meanwhile
        (tmp_path / "cockroach_tpu").symlink_to(
            REPO / "cockroach_tpu", target_is_directory=True)
        default = str(tmp_path / ".jax_cache")
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_COMPILATION_CACHE_DIR", "PYTHONPATH")}
        code = ("import jax\n"
                "from cockroach_tpu.exec.engine import Engine\n"
                "eng = Engine()\n"
                "assert eng._compile_cache_dir == "
                "jax.config.jax_compilation_cache_dir\n"
                "print(eng._compile_cache_dir)\n")
        out = subprocess.run(
            [sys.executable, "-c", code], env=env, cwd=str(tmp_path),
            capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip().splitlines()[-1] == default
        assert os.path.isdir(default)

    def test_setting_keeps_only_off(self):
        from cockroach_tpu.utils.settings import SettingError
        eng = Engine()
        with pytest.raises(SettingError):
            eng.settings.set("sql.exec.compile_cache.dir", "/some/dir")
        eng.settings.set("sql.exec.compile_cache.dir", "off")
        assert coldstart.init_compile_cache(eng.settings) is None

    def test_compile_metrics_move_on_first_compile(
            self, private_compile_cache):
        # needs a genuinely cold cache (the suite-shared dir may
        # already hold this statement's programs)
        eng = Engine()
        eng.execute("CREATE TABLE cm (v INT)")
        eng.execute("INSERT INTO cm VALUES (1), (2), (3)")
        before = eng.metrics.snapshot()
        eng.execute("SELECT count(*), sum(v) FROM cm WHERE v > 1")
        after = eng.metrics.snapshot()
        for k in ("exec.compile.cache_hit", "exec.compile.cache_miss",
                  "exec.compile.seconds", "exec.compile.prewarmed"):
            assert k in after
        # a fresh per-test cache dir: the statement's programs all
        # missed the persistent cache and paid the backend compiler
        assert after["exec.compile.cache_miss"] \
            > before["exec.compile.cache_miss"]
        assert after["exec.compile.seconds"] \
            > before["exec.compile.seconds"]

    def test_statement_compile_split_recorded(
            self, private_compile_cache):
        # a cold cache of its own: a persistent cache that any test of
        # this worker warmed with the same program loads it in 0 s,
        # and the split under test would have nothing to show
        eng = Engine()
        eng.execute("CREATE TABLE sp (v INT)")
        eng.execute("INSERT INTO sp VALUES (1), (5), (9)")
        sql = "SELECT count(*), sum(v) FROM sp WHERE v > 2"
        eng.execute(sql)
        st = eng.sqlstats.get(sql)
        assert st is not None and st.count == 1
        assert st.total_compile_s > 0, \
            "first execution must attribute its XLA compile time"
        first = st.total_compile_s
        eng.execute(sql)  # plan-cache hit: no new backend compile
        st = eng.sqlstats.get(sql)
        assert st.count == 2
        assert st.total_compile_s == pytest.approx(first, abs=0.05)
        assert st.mean_compile_s <= st.mean_latency_s
        assert st.mean_exec_s >= 0

    def test_explain_analyze_shows_compile_split(self):
        eng = Engine()
        eng.execute("CREATE TABLE ea (v INT)")
        eng.execute("INSERT INTO ea VALUES (1), (5), (9)")
        res = eng.execute(
            "EXPLAIN ANALYZE SELECT count(*) FROM ea WHERE v > 2")
        lines = [r[0] for r in res.rows]
        assert any(ln.strip().startswith("compile:") for ln in lines), \
            "plan-build span missing from EXPLAIN ANALYZE"
        assert any("xla compile:" in ln for ln in lines), \
            "XLA compile split missing from EXPLAIN ANALYZE"

    def test_statements_endpoint_reports_split(self):
        eng = Engine()
        eng.execute("CREATE TABLE se (v INT)")
        eng.execute("INSERT INTO se VALUES (1), (2)")
        eng.execute("SELECT sum(v) FROM se")
        s = eng.sqlstats.all()[0]
        # the /_status/statements handler renders exactly these
        for attr in ("total_compile_s", "mean_compile_s",
                     "mean_exec_s"):
            assert isinstance(getattr(s, attr), float)

    def test_journal_and_prewarm(self, private_compile_cache):
        # private cache: the suite-shared journal holds other tests'
        # statements, which would crowd out this one's top-k slot
        eng = Engine()
        eng.execute("CREATE TABLE jw (k INT, v INT)")
        eng.execute("INSERT INTO jw VALUES (1, 10), (2, 20), (3, 30)")
        sql = "SELECT k, sum(v) FROM jw GROUP BY k ORDER BY k"
        want = eng.execute(sql).rows
        jp = coldstart.journal_path(eng._compile_cache_dir)
        assert os.path.exists(jp), "exec-cache miss must journal"
        assert sql in coldstart.journal_top(eng._compile_cache_dir, 5)
        # simulate a restart of the executable cache: prewarm must
        # re-prepare the journaled statement before any user query
        eng._exec_cache.clear()
        warmed = eng.prewarm(top_k=5)
        assert warmed >= 1
        assert len(eng._exec_cache) >= 1
        assert eng.execute(sql).rows == want

    def test_prewarm_disabled_by_default(self):
        eng = Engine()
        assert eng.prewarm() == 0  # setting defaults to 0

    def test_journal_replays_session_vars(self, private_compile_cache):
        # a statement that compiled under non-default plan-key vars
        # journals them; prewarm re-prepares under the SAME vars, so
        # the session that set them gets a plan-cache hit after the
        # simulated restart instead of a recompile at defaults
        eng = Engine()
        eng.execute("CREATE TABLE jv (k INT, v INT)")
        eng.execute("INSERT INTO jv VALUES (1, 10), (2, 20), (3, 30)")
        s = eng.session()
        s.vars.set("hash_group_capacity", 4096)
        s.vars.set("pallas_groupagg", "off")
        sql = "SELECT k, sum(v) FROM jv GROUP BY k"
        want = eng.execute(sql, s).rows
        vars_of = {e[0]: e[2] for e in coldstart.journal_entries(
            eng._compile_cache_dir, 10)}
        assert vars_of[sql] == {"hash_group_capacity": 4096,
                                "pallas_groupagg": "off"}
        eng._exec_cache.clear()
        assert eng.prewarm(top_k=10) >= 1
        hits = eng.metrics.snapshot().get("sql.plan.cache.hit", 0)
        assert eng.execute(sql, s).rows == want
        assert eng.metrics.snapshot().get(
            "sql.plan.cache.hit", 0) > hits


# ------------------------------------------------- bounded cache policy

class TestCacheEviction:
    def test_parse_cache_evicts_oldest_half(self):
        eng = Engine()
        eng._PARSE_CACHE_MAX = 8
        texts = [f"SELECT * FROM t WHERE a = {i}" for i in range(9)]
        for t in texts[:8]:
            eng._parse_cached(t)
        assert len(eng._parse_cache) == 8
        eng._parse_cached(texts[8])  # evicts the oldest 4, keeps 4+1
        assert len(eng._parse_cache) == 5
        assert texts[0] not in eng._parse_cache
        assert texts[7] in eng._parse_cache
        assert texts[8] in eng._parse_cache

    def test_exec_cache_capped(self):
        eng = Engine()
        eng._EXEC_CACHE_MAX = 2
        eng.execute("CREATE TABLE ec (v INT)")
        eng.execute("INSERT INTO ec VALUES (1), (2), (3)")
        for i in range(4):
            eng.execute(f"SELECT count(*) FROM ec WHERE v > {i}")
        assert 0 < len(eng._exec_cache) <= 2


# --------------------------------------------------------- bucket sweep

class TestBucketLadderParity:
    SIZES = (1000, 1030, 2049, 3500)  # straddle the 1K/2K/4K rungs
    SQL = "SELECT g, count(*) AS c, sum(v) AS s FROM bl GROUP BY g ORDER BY g"

    def _mk(self, steps):
        eng = Engine()
        if steps != 1:
            eng.settings.set("sql.exec.shape_bucket.steps_per_octave",
                             steps)
        eng.execute("CREATE TABLE bl (g INT, v INT)")
        return eng

    def _sweep(self, eng):
        s = eng.session()
        s.vars.set("distsql", "off")
        rng = np.random.default_rng(7)
        out, have = [], 0
        for size in self.SIZES:
            add = size - have
            vals = ", ".join(
                f"({int(g)}, {int(v)})"
                for g, v in zip(rng.integers(0, 8, add),
                                rng.integers(0, 10 ** 6, add)))
            eng.execute(f"INSERT INTO bl VALUES {vals}")
            have = size
            out.append(eng.execute(self.SQL, session=s).rows)
        return out

    def test_parity_across_ladders_and_budget(self):
        coarse, fine = self._mk(1), self._mk(2)
        got_c = self._sweep(coarse)
        got_f = self._sweep(fine)
        # different padded shapes (1030 -> 2048 vs 1536), identical
        # results at every size: bucketing is invisible to answers
        assert got_c == got_f
        for eng, steps in ((coarse, 1), (fine, 2)):
            lad = eng.shape_ladder()
            assert lad.steps_per_octave == steps
            # every executable compiled during the sweep sits on a
            # ladder rung, and the distinct shapes stay within the
            # ladder's budget for the swept range
            ns = {n for key in eng._exec_cache
                  for (_t, n, _d) in key[1]}
            assert ns <= set(lad.rungs(max(self.SIZES)))
            assert len(ns) <= lad.budget(max(self.SIZES))

    def test_same_bucket_rerun_hits_plan_cache(self):
        eng = self._mk(1)
        s = eng.session()
        s.vars.set("distsql", "off")
        eng.execute("INSERT INTO bl VALUES (1, 10), (2, 20)")
        eng.execute(self.SQL, session=s)
        before = eng.metrics.snapshot().get("sql.plan.cache.hit", 0)
        eng.execute(self.SQL, session=s)
        assert eng.metrics.snapshot()["sql.plan.cache.hit"] > before


# ------------------------------------------------------ retired setting

RETIRED = "sql.exec.pallas.autotune"


class TestRetiredSetting:
    """The autotuner went (PR 31); `benchmark/configs/*.json` still set
    its cluster setting to `off`. As with pkg/settings' retired names,
    a SET is accepted and does nothing, and the name is not listed."""

    @pytest.mark.parametrize("value", ["auto", "on", "off"])
    def test_set_is_accepted_and_inert(self, value):
        st = Settings()
        seen = []
        st.on_change(lambda name, v: seen.append(name))
        before = st.snapshot()
        st.set(RETIRED, value)
        assert st.snapshot() == before and not seen
        with pytest.raises(SettingError):
            st.get(RETIRED)     # no value is kept, nothing reads one

    def test_an_unknown_name_still_raises(self):
        st = Settings()
        for name in ("sql.exec.pallas.autotune2", "sql.exec.pallas"):
            with pytest.raises(SettingError, match="unknown"):
                st.set(name, "off")

    def test_snapshots_leave_it_out_and_take_it(self):
        st = Settings()
        assert RETIRED not in st.snapshot()
        # an older node's gossiped snapshot still holds it
        st.apply_snapshot({RETIRED: "off", "kv.gc.ttl_seconds": 60,
                           "no.such.setting": 1})
        assert st.get("kv.gc.ttl_seconds") == 60
        assert RETIRED not in st.snapshot()
        assert "no.such.setting" not in st.snapshot()

    def test_the_benchmarks_configurations_still_start(self):
        import glob
        configs = sorted(glob.glob(str(REPO / "benchmark/configs/*.json")))
        assert len(configs) >= 3
        eng = Engine()
        for path in configs:
            with open(path) as f:
                for name, value in json.load(f)["settings"].items():
                    eng.settings.set(name, value)
        eng.execute(f"SET CLUSTER SETTING {RETIRED} = 'off'")


# ------------------------------------------------ cross-process (slow)

_CHILD = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "1")
import jax
jax.config.update("jax_enable_x64", True)
from cockroach_tpu.exec.engine import Engine

eng = Engine()
eng.execute("CREATE TABLE t (k INT, v INT)")
rows = ", ".join("(%d, %d)" % (i % 97, (i * 2654435761) % 100000)
                 for i in range(2000))
eng.execute("INSERT INTO t VALUES " + rows)
res = eng.execute(
    "SELECT k, count(*) AS c, sum(v) AS s, min(v) AS lo, "
    "max(v) AS hi FROM t GROUP BY k ORDER BY k")
snap = eng.metrics.snapshot()
print(json.dumps({
    "rows": [[repr(c) for c in r] for r in res.rows],
    "hit": snap.get("exec.compile.cache_hit", 0),
    "miss": snap.get("exec.compile.cache_miss", 0),
    "dir": eng._compile_cache_dir}))
"""


@pytest.mark.slow
class TestCrossProcessWarmStart:
    def test_second_process_serves_from_cache(self, tmp_path):
        cache = str(tmp_path / "xproc-cache")
        script = tmp_path / "child.py"
        script.write_text(_CHILD)
        env = dict(os.environ)
        env["JAX_COMPILATION_CACHE_DIR"] = cache
        env["PYTHONPATH"] = str(REPO)
        env.pop("XLA_FLAGS", None)  # single device is enough

        def run():
            p = subprocess.run(
                [sys.executable, str(script)], cwd=str(REPO), env=env,
                capture_output=True, text=True, timeout=600)
            assert p.returncode == 0, p.stderr[-4000:]
            return json.loads(p.stdout.splitlines()[-1])

        cold = run()
        warm = run()
        assert cold["dir"] == cache
        assert cold["miss"] > 0, "cold process must compile"
        assert warm["hit"] > 0, \
            "warm process must deserialize from the persistent cache"
        assert warm["rows"] == cold["rows"], \
            "warm results must be bit-identical to cold"
