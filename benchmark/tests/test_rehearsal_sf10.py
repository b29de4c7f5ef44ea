"""`tpch_sf10_scan.scan1` end to end on the CPU at SF0.01, with what the
other cells' rehearsal asserts, and the property the cell was given one
session for: whatever the seed, the session sends the same statements
in the same order, so no run falls into another queueing orbit than
the next (PERF.md, PR 28)."""

import itertools
import json
import os
import re

import pytest

import traffic
from helpers import BENCH, run_cell

CELL = "tpch_sf10_scan.scan1"


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "scan1.json")) as f:
        return json.load(f)


def test_cell_rehearsal():
    rc, result, out = run_cell(CELL)
    assert rc == 0, out[-3000:]
    assert result["rehearsal"] is True and result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 1
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    counts = result["counts"]
    assert counts["compiles_in_window"] == 0
    assert counts["upload_bytes_in_window"] == 0
    assert counts["plan_hit_share"] == 100.0
    # what this cell adds, as far as the CPU can count it
    assert counts["placement_not_resident"] == 0
    assert counts["ingest_mrows_per_s"] > 0
    assert 1 <= counts["q1_limb_width"] <= 22
    assert counts["q1_matmul_rows"] >= 8
    # no memory statistics on the CPU: the reader leaves it out
    assert "placement_model_x" not in counts


def test_warmed_sets_share_one_program():
    rc, result, out = run_cell(CELL, seconds=1, seed=2147483659)
    assert rc == 0, out[-3000:]
    for cls, misses in result["new_plans"].items():
        assert sum(misses[1:]) == 0, (cls, misses)


def test_one_session_sends_the_same_statements_whatever_the_seed(mix):
    assert mix["loop"] == "closed" and int(mix["sessions"]) == 1
    assert not mix["think_time_ms"]
    names = [c["name"] for c in mix["classes"]]
    assert names == ["q6", "q1"]
    templates = {}
    for cls in mix["classes"]:
        with open(os.path.join(BENCH, "statements",
                               cls["statement"] + ".sql")) as f:
            templates[cls["name"]] = f.read()
    shapes = []
    for seed in (5, 2147485417):
        sets = traffic.parameter_sets(mix, seed)
        n_sets = [len(sets[n]) for n in names]
        assert n_sets == [4, 4]
        seq = list(itertools.islice(
            traffic.session_statements(mix, seed, 0, n_sets), 64))
        # the classes in their fixed order from the seeded start ...
        start = seq[0][0]
        assert [c for c, _ in seq] == [(start + i) % 2 for i in range(64)]
        # ... and each statement its class's text at one of its sets
        assert all(0 <= s < 4 for _, s in seq)
        for name in names:
            for p in sets[name]:
                dom = next(c for c in mix["classes"]
                           if c["name"] == name)["params"]
                assert set(p) == set(dom)
        # the texts with their parameters blanked, from q6 on
        blank = {n: re.sub(r"\{\w+\}", "?", t) for n, t in
                 templates.items()}
        shapes.append([blank[names[c]] for c, _ in seq[start:start + 62]])
    assert shapes[0] == shapes[1]


def test_bulk_generator_is_tpch_and_refuses_a_store_without_bulk_ingest(
        monkeypatch):
    import sys
    import types

    import numpy as np

    from generators import tpch, tpch_bulk

    with open(os.path.join(BENCH, "configs", "tpch_sf10_scan.json")) as f:
        assert json.load(f)["generator"] == "tpch_bulk"
    assert tpch_bulk.DDL is tpch.DDL
    assert tpch_bulk.TABLE_ORDER == tpch.TABLE_ORDER
    # beside a program with the bulk ingest (this one), and beside none
    # (the reference worker): tpch's tables, array for array
    import cockroach_tpu.storage.chunkstats as stats
    assert hasattr(stats, "compute_many")
    for present in (True, False):
        if not present:
            monkeypatch.delitem(sys.modules, tpch_bulk.CHUNKSTATS)
        cols, dicts = tpch_bulk.generate("lineitem", 0.001, 77)
        want_cols, want_dicts = tpch.generate("lineitem", 0.001, 77)
        assert list(cols) == list(want_cols) and dicts == want_dicts
        for k in cols:
            assert np.array_equal(cols[k], want_cols[k])
    # beside a program whose store summarizes a load as one chunk
    monkeypatch.setitem(sys.modules, tpch_bulk.CHUNKSTATS,
                        types.ModuleType(tpch_bulk.CHUNKSTATS))
    with pytest.raises(SystemExit, match="no bulk ingest"):
        tpch_bulk.generate("lineitem", 0.001, 77)
