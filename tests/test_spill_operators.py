"""Out-of-core spill tier (exec/spill.py): partitioned external hash
join + external merge sort parity against the resident paths, the
four-way placement verdict, spill metrics, the resident-path
HLO-unchanged guarantee, and the ICI-path fault hooks.

Parity contract (ISSUE acceptance): a join/order-by whose working set
exceeds ``sql.exec.hbm_budget_bytes`` completes under spill=auto
bit-identical to spill=off at ample budget."""

import numpy as np
import pytest

from cockroach_tpu.exec.engine import Engine
from cockroach_tpu.ops.batch import read_ts_words
from cockroach_tpu.parallel import distagg
from cockroach_tpu.rpc.context import FaultInjector
from cockroach_tpu.utils.metric import MetricRegistry

AMPLE = 12 << 30
TINY = 1 << 16
I64_MIN = -(2 ** 63)
I64_MAX = 2 ** 63 - 1


def _mk_engine(n=6000, m=1500, seed=0):
    """fact (dup int keys incl. NULLs + INT64 extremes in v) joined to
    dim (NULL-able key + payload); keys scattered so the dense-range
    planner paths never pre-empt the join/sort shapes under test."""
    eng = Engine()
    eng.execute("CREATE TABLE fact (k INT8, g INT8 NOT NULL, v INT8, "
                "x INT8)")
    eng.execute("CREATE TABLE dim (k INT8, w INT8)")
    rng = np.random.default_rng(seed)
    k = rng.integers(0, m, n).astype(np.int64) * 7 + 3
    kv = rng.random(n) > 0.05          # some NULL probe keys
    g = rng.integers(0, 8, n).astype(np.int64)
    v = rng.integers(-100, 100, n).astype(np.int64)
    vv = rng.random(n) > 0.1
    # x: sort-only column carrying the INT64 extremes (summing it
    # would legitimately trip the __sum_overflow sentinel)
    x = rng.integers(I64_MIN // 2, I64_MAX // 2, n).astype(np.int64)
    x[: 4] = (I64_MIN, I64_MAX, 0, -1)
    xv = rng.random(n) > 0.1
    eng.store.insert_columns("fact",
                             {"k": k, "g": g, "v": v, "x": x},
                             eng.clock.now(),
                             valid={"k": kv, "v": vv, "x": xv})
    dk = np.arange(m, dtype=np.int64) * 7 + 3
    dkv = rng.random(m) > 0.05         # some NULL build keys
    dw = rng.integers(0, 50, m).astype(np.int64)
    dwv = rng.random(m) > 0.2
    eng.store.insert_columns("dim", {"k": dk, "w": dw},
                             eng.clock.now(),
                             valid={"k": dkv, "w": dwv})
    eng.execute("ANALYZE fact")
    eng.execute("ANALYZE dim")
    sess = eng.session()
    sess.vars.set("distsql", "off")
    sess.vars.set("streaming_page_rows", 2048)
    return eng, sess


@pytest.fixture(scope="module")
def ejs():
    return _mk_engine()


def _ab(eng, sess, sql):
    """Baseline at (spill=off, ample budget) vs (spill=auto, tiny
    budget) — the acceptance A/B — returning both row lists."""
    eng.settings.set("sql.exec.hbm_budget_bytes", AMPLE)
    sess.vars.set("spill", "off")
    base = eng.execute(sql, sess).rows
    eng.settings.set("sql.exec.hbm_budget_bytes", TINY)
    sess.vars.set("spill", "auto")
    try:
        got = eng.execute(sql, sess).rows
    finally:
        eng.settings.set("sql.exec.hbm_budget_bytes", AMPLE)
        sess.vars.set("spill", "off")
    return base, got


JOIN_Q = ("SELECT g, SUM(v) AS sv, SUM(w) AS sw, COUNT(*) AS c "
          "FROM fact JOIN dim ON fact.k = dim.k "
          "GROUP BY g ORDER BY g")


class TestSpillJoinParity:
    def test_q3_class_join_over_budget(self, ejs):
        eng, sess = ejs
        base, got = _ab(eng, sess, JOIN_Q)
        assert len(base) == 8 and got == base

    def test_left_join(self, ejs):
        eng, sess = ejs
        base, got = _ab(eng, sess,
                        "SELECT g, COUNT(*) AS c, COUNT(w) AS cw, "
                        "SUM(w) AS sw FROM fact LEFT JOIN dim "
                        "ON fact.k = dim.k GROUP BY g ORDER BY g")
        assert got == base

    def test_filtered_join(self, ejs):
        eng, sess = ejs
        base, got = _ab(eng, sess,
                        "SELECT COUNT(*) AS c, MIN(v) AS lo, "
                        "MAX(w) AS hi FROM fact JOIN dim "
                        "ON fact.k = dim.k WHERE v > 0 AND w < 40")
        assert got == base

    def test_forced_spill_matches_at_ample_budget(self, ejs):
        eng, sess = ejs
        sess.vars.set("spill", "off")
        base = eng.execute(JOIN_Q, sess).rows
        sess.vars.set("spill", "on")
        try:
            assert eng.stream_verdict(JOIN_Q, sess) == "spill-join"
            assert eng.execute(JOIN_Q, sess).rows == base
        finally:
            sess.vars.set("spill", "off")

    def test_off_arm_dies_on_quota_where_auto_completes(self, ejs):
        """The gap spill-join exists for: build uploads reserve before
        moving, so at a sub-build budget the off arm raises a quota
        error while auto completes (bit-identical, proven above)."""
        from cockroach_tpu.utils.mon import MemoryQuotaError
        eng, sess = ejs
        eng.drop_device_cache()
        eng.settings.set("sql.exec.hbm_budget_bytes", TINY)
        sess.vars.set("spill", "off")
        try:
            with pytest.raises(MemoryQuotaError):
                eng.execute(JOIN_Q, sess)
        finally:
            eng.settings.set("sql.exec.hbm_budget_bytes", AMPLE)


class TestSpillSortParity:
    @pytest.mark.parametrize("sql", [
        "SELECT k, v FROM fact ORDER BY v DESC, k LIMIT 37",
        "SELECT k, v FROM fact ORDER BY v NULLS FIRST, k DESC "
        "LIMIT 50 OFFSET 13",
        "SELECT g, v FROM fact WHERE v > -50 ORDER BY g DESC, v",
        "SELECT v FROM fact ORDER BY v",
        # INT64 extremes under DESC/NULLS FIRST (the lexsort-era
        # negation bug class: INT64_MIN is its own arithmetic
        # negation)
        "SELECT k, x FROM fact ORDER BY x DESC NULLS FIRST, k "
        "LIMIT 64",
        "SELECT x FROM fact ORDER BY x LIMIT 8",
    ])
    def test_order_by_over_budget(self, ejs, sql):
        eng, sess = ejs
        base, got = _ab(eng, sess, sql)
        assert got == base and len(base) > 0

    def test_empty_selection(self, ejs):
        eng, sess = ejs
        base, got = _ab(eng, sess, "SELECT k, v FROM fact "
                                   "WHERE v > 9000 ORDER BY v LIMIT 5")
        assert got == base == []


class TestVerdictMatrix:
    """The four-way placement verdict (resident | stream-scan |
    spill-join | spill-sort), driven by working set vs budget and the
    spill session var."""

    def _verdict(self, eng, sess, sql, budget, spill="auto"):
        eng.settings.set("sql.exec.hbm_budget_bytes", budget)
        sess.vars.set("spill", spill)
        try:
            return eng.stream_verdict(sql, sess)
        finally:
            eng.settings.set("sql.exec.hbm_budget_bytes", AMPLE)
            sess.vars.set("spill", "off")

    def test_resident_when_fits(self, ejs):
        eng, sess = ejs
        assert self._verdict(eng, sess, JOIN_Q, AMPLE) == "resident"

    def test_spill_join_when_build_over_budget(self, ejs):
        eng, sess = ejs
        assert self._verdict(eng, sess, JOIN_Q, TINY) == "spill-join"

    def test_spill_sort_when_table_over_budget(self, ejs):
        eng, sess = ejs
        q = "SELECT k, v FROM fact ORDER BY v LIMIT 9"
        assert self._verdict(eng, sess, q, TINY) == "spill-sort"
        assert self._verdict(eng, sess, q, AMPLE) == "resident"

    def test_stream_scan_when_joinless_agg_over_budget(self, ejs):
        eng, sess = ejs
        q = "SELECT g, SUM(v) AS s FROM fact GROUP BY g ORDER BY g"
        assert self._verdict(eng, sess, q, TINY) == "stream-scan"

    def test_off_disables_spill(self, ejs):
        eng, sess = ejs
        v = self._verdict(eng, sess, JOIN_Q, TINY, spill="off")
        assert v in ("stream-scan", "resident")
        q = "SELECT k, v FROM fact ORDER BY v LIMIT 9"
        assert self._verdict(eng, sess, q, TINY, spill="off") \
            == "resident"

    def test_on_forces_eligible_shapes(self, ejs):
        eng, sess = ejs
        assert self._verdict(eng, sess, JOIN_Q, AMPLE,
                             spill="on") == "spill-join"
        q = "SELECT k, v FROM fact ORDER BY v LIMIT 9"
        assert self._verdict(eng, sess, q, AMPLE,
                             spill="on") == "spill-sort"


class TestSpillMetrics:
    def test_counters_move(self, ejs):
        eng, sess = ejs
        s0 = eng.metrics.snapshot()
        _ab(eng, sess, JOIN_Q)
        s1 = eng.metrics.snapshot()

        def delta(name):
            return s1.get(name, 0) - s0.get(name, 0)
        assert delta("exec.spill.rounds") >= 1
        assert delta("exec.spill.partitions") >= 2
        assert delta("exec.spill.bytes") > 0
        assert delta("exec.spill.upload_overlap_seconds") >= 0


class TestResidentHloUnchanged:
    def test_fitting_working_set_compiles_identically(self, ejs):
        """spill=auto must be invisible to plans that fit: same
        verdict, same compiled program (HLO text) as spill=off."""
        eng, sess = ejs
        eng.settings.set("sql.exec.hbm_budget_bytes", AMPLE)
        sess.vars.set("spill", "off")
        p_off = eng._prepare_select(
            eng._parse_cached(JOIN_Q), sess, JOIN_Q)
        sess.vars.set("spill", "auto")
        p_auto = eng._prepare_select(
            eng._parse_cached(JOIN_Q), sess, JOIN_Q)
        sess.vars.set("spill", "off")
        assert p_off.spill is None and p_auto.spill is None
        tsv = read_ts_words(0)
        hlo_off = p_off.jfn.lower(p_off.scans, tsv, np.int32(1),
                                  np.int32(0)).as_text()
        hlo_auto = p_auto.jfn.lower(p_auto.scans, tsv, np.int32(1),
                                    np.int32(0)).as_text()
        assert hlo_off == hlo_auto


class TestPageRowsPow2:
    def test_session_page_rows_round_up(self, ejs):
        """Satellite: a non-pow2 SET streaming_page_rows rounds UP so
        tail pages share every other page's compiled shape."""
        eng, sess = ejs
        s = eng.session()
        s.vars.set("streaming_page_rows", 3000)
        assert eng._page_rows(s) == 4096
        s.vars.set("streaming_page_rows", 4096)
        assert eng._page_rows(s) == 4096
        s.vars.set("streaming_page_rows", 100)
        assert eng._page_rows(s) == 1024


class TestIciFaultHooks:
    """Satellite: seeded FaultInjector targeting the collective
    dispatch path (parallel/distagg.queued_collective_call)."""

    def _injected(self, drop=0.0, dup=0.0, delay=0.0, delay_s=0.0):
        inj = FaultInjector(seed=7)
        inj.set_rule("ici", "ici", drop=drop, dup=dup, delay=delay,
                     delay_s=delay_s)
        distagg.install_ici_faults(inj)
        return inj

    def teardown_method(self, method):
        distagg.install_ici_faults(None)

    def test_drop_raises_collective_fault(self):
        inj = self._injected(drop=1.0)
        calls = []
        call = distagg.queued_collective_call(
            lambda: calls.append(1), mesh=None)
        with pytest.raises(distagg.CollectiveFault):
            call()
        assert inj.dropped == 1 and not calls

    def test_duplicate_dispatch_is_idempotent(self):
        inj = self._injected(dup=1.0)
        reg = MetricRegistry()
        call = distagg.queued_collective_call(lambda x: x + 1,
                                              metrics=reg, mesh=None)
        assert call(41) == 42
        assert inj.duplicated == 1
        # one logical collective call, even when delivered twice
        assert reg.get("exec.allreduce.calls").value() == 1

    def test_delay_then_heal(self):
        inj = self._injected(delay=1.0, delay_s=0.01)
        call = distagg.queued_collective_call(lambda x: x * 2,
                                              mesh=None)
        assert call(21) == 42
        assert inj.delayed == 1
        distagg.install_ici_faults(None)
        assert call(21) == 42
        assert inj.delayed == 1  # healed: no further evaluation

    def test_uninjected_path_untouched(self):
        call = distagg.queued_collective_call(lambda x: x - 1,
                                              mesh=None)
        assert call(43) == 42


@pytest.mark.slow
class TestSpillFuzz:
    """Heavy corpus: randomized data (dup keys, NULLs, INT64
    extremes) across seeds; spilled results must be bit-identical to
    resident for both operators."""

    @pytest.mark.parametrize("seed", range(6))
    def test_join_corpus(self, seed):
        eng, sess = _mk_engine(n=4000 + 731 * seed,
                               m=700 + 211 * seed, seed=seed)
        base, got = _ab(eng, sess, JOIN_Q)
        assert got == base

    @pytest.mark.parametrize("seed", range(6))
    def test_sort_corpus(self, seed):
        eng, sess = _mk_engine(n=4000 + 731 * seed,
                               m=700 + 211 * seed, seed=seed)
        rng = np.random.default_rng(seed)
        lim = int(rng.integers(1, 200))
        off = int(rng.integers(0, 40))
        sql = (f"SELECT k, g, v FROM fact ORDER BY v DESC "
               f"NULLS LAST, g, k DESC LIMIT {lim} OFFSET {off}")
        base, got = _ab(eng, sess, sql)
        assert got == base
