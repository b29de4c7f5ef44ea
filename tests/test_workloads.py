"""Workload generator tests (reference: pkg/workload).

bank's conserved-total invariant, YCSB mixes, raw kv, and SSB query
correctness against numpy oracles.
"""

import numpy as np
import pytest

from cockroach_tpu.exec.engine import Engine
from cockroach_tpu.workload import SSB, WORKLOADS, Bank, KVLoad, YCSB
from cockroach_tpu.workload import ssb as ssbmod


class TestBank:
    def test_transfers_conserve_total(self):
        eng = Engine()
        b = Bank(eng, accounts=20, seed=1)
        b.setup()
        assert b.check()
        out = b.run(steps=30)
        assert out["transfers"] > 0
        assert b.check(), f"money not conserved: {out}"

    def test_explicit_txn_rollback_mid_transfer(self):
        eng = Engine()
        b = Bank(eng, accounts=5)
        b.setup()
        s = eng.session()
        eng.execute("BEGIN", s)
        eng.execute("UPDATE bank SET balance = 0 WHERE id = 0", s)
        eng.execute("ROLLBACK", s)
        assert b.check()


class TestYCSB:
    @pytest.mark.parametrize("wl", ["A", "B", "C", "D", "E", "F"])
    def test_mix_runs_and_counts(self, wl):
        eng = Engine()
        y = YCSB(eng, workload=wl, records=50, seed=3)
        y.setup()
        out = y.run(steps=20)
        assert sum(out["ops"].values()) == 20
        # the dominant op of each mix actually dominates (loose bound
        # against small-sample noise)
        top = max(y.mix, key=y.mix.get)
        assert out["ops"][top] >= int(20 * y.mix[top] * 0.5)

    def test_rmw_increments(self):
        eng = Engine()
        y = YCSB(eng, workload="F", records=10, seed=5,
                 distribution="uniform")
        y.setup()
        before = eng.execute(
            "SELECT sum(field0) AS s FROM usertable").rows[0][0]
        for _ in range(10):
            y.step()
        after = eng.execute(
            "SELECT sum(field0) AS s FROM usertable").rows[0][0]
        assert after >= before


class TestKVLoad:
    def test_read_write_mix(self):
        eng = Engine()
        k = KVLoad(eng.kv, keyspace=100, read_percent=50, seed=2)
        out = k.run(steps=50)
        assert out["reads"] + out["writes"] == 50
        assert out["writes"] > 5


class TestSSB:
    @pytest.fixture(scope="class")
    def loaded(self):
        eng = Engine()
        data = ssbmod.load(eng, sf=0.01, rows=20_000)
        return eng, data

    def test_q1_1_matches_oracle(self, loaded):
        eng, data = loaded
        got = eng.execute(ssbmod.Q1_1).rows[0][0]
        want = ssbmod.ref_q1_1(data["lineorder"], data["dims"])
        assert got == want

    def test_q2_1_matches_oracle(self, loaded):
        eng, data = loaded
        r = eng.execute(ssbmod.Q2_1)
        # the paper's column order: sum(lo_revenue), d_year, p_brand1
        got = [(int(rev), y, b) for rev, y, b in r.rows]
        want = ssbmod.ref_q2_1(data["lineorder"], data["dims"])
        assert got == want

    def test_q3_1_and_q4_1_run(self, loaded):
        eng, data = loaded
        r3 = eng.execute(ssbmod.Q3_1)
        assert len(r3.rows) > 0
        # revenue sorted descending within each year
        r4 = eng.execute(ssbmod.Q4_1)
        assert len(r4.rows) > 0
        years = [row[0] for row in r4.rows]
        assert years == sorted(years)

    def test_registry_names(self):
        assert set(WORKLOADS) == {"bank", "kv", "ycsb", "ssb",
                                  "tpcc", "movr"}
