"""`ssb_sf1.flight` end to end on the CPU at SF0.01, with what the other
cells' rehearsals assert; the thirteen classes' files found by name; the
benchmark's references against the program's own oracles on one seeded
data set; the per-flight readers on a slice that lacks some classes;
and the generator's refusal of a program that cannot bind Q2.2."""

import json
import os
import sys
import types

import numpy as np
import pytest

import traffic
from helpers import BENCH, ROOT, run_cell
from refworker import load_module

CELL = "ssb_sf1.flight"
CLASSES = ["q1_1", "q1_2", "q1_3", "q2_1", "q2_2", "q2_3", "q3_1", "q3_2",
           "q3_3", "q3_4", "q4_1", "q4_2", "q4_3"]


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "flight.json")) as f:
        return json.load(f)


def test_cell_rehearsal():
    rc, result, out = run_cell(CELL, seconds=4, seed=2147483777)
    assert rc == 0, out[-3000:]
    assert result["rehearsal"] is True and result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 1
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= len(CLASSES)
    counts = result["counts"]
    assert counts["compiles_in_window"] == 0
    assert counts["upload_bytes_in_window"] == 0
    assert counts["plan_hit_share"] == 100.0
    # what this cell adds, as far as the CPU can count it. Of the six
    # dense domains under 2^19 those take the kernel (interpreted here)
    # whose batch a Compact has not cut under the kernel's 4,096 rows at
    # this size (all six over the chip's 2^23); the four domains over
    # the planner's dense bound take the hash table. A tally a trace: a
    # statement traced again in set-up counts again
    assert 1 <= counts["ssb_kernel_aggs"]
    assert counts["ssb_hash_aggs"] >= 4
    # at least one probe over the fact batch's 2^16-row bucket a join
    assert counts["ssb_probe_rows_per_stmt"] >= 1 << 16
    # one parameter set a class, one plan a class
    assert result["new_plans"] == {c: [1] for c in CLASSES}


def test_the_cell_is_the_papers_flight(mix):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("ssb_sf1", "flight", 1)
    assert mix["loop"] == "closed" and int(mix["sessions"]) == 1
    assert not mix["think_time_ms"] and int(mix["parameter_sets"]) == 1
    assert [c["name"] for c in mix["classes"]] == CLASSES
    for cls in mix["classes"]:
        assert cls["statement"] == "ssb_" + cls["name"]
        assert all(d["kind"] == "fixed" for d in cls["params"].values())
        for ext in (".sql", ".py"):
            assert os.path.exists(os.path.join(
                BENCH, "statements", cls["statement"] + ext))
        mod = load_module("statements", cls["statement"])
        assert set(mod.COLUMNS) <= {"int", "text"}
        assert "lineorder" in mod.TABLES
    # whatever the seed: the same thirteen texts in the same order
    texts = []
    for seed in (5, 2147485417):
        sets = traffic.parameter_sets(mix, seed)
        assert all(len(sets[c]) == 1 for c in CLASSES)
        row = []
        for cls in mix["classes"]:
            with open(os.path.join(BENCH, "statements",
                                   cls["statement"] + ".sql")) as f:
                row.append(f.read().format(**sets[cls["name"]][0]))
        texts.append(row)
    assert texts[0] == texts[1]
    # the paper's constants reach the text
    assert "'MFGR#2221' and 'MFGR#2228'" in texts[0][4]
    assert "c_city='UNITED KI1' or c_city='UNITED KI5'" in texts[0][8]
    assert "d_yearmonth = 'Dec1997'" in texts[0][9]


def test_config_states_what_the_contract_asks():
    with open(os.path.join(BENCH, "configs", "ssb_sf1.json")) as f:
        cfg = json.load(f)
    assert cfg["generator"] == "ssb" and cfg["scale_factor"] == 1.0
    assert cfg["chips"] == 1 and cfg["settings"] == {}
    assert set(cfg["reduced"]) == {"nodes", "scale"}
    for key in ("source", "deployment", "schema", "guarantees", "assumed"):
        assert cfg[key]
    assert set(cfg["guarantees"]) >= {"answers", "isolation", "writes",
                                      "replies"}


def test_generator_makes_the_papers_schema():
    from generators import ssb

    widths = {"lineorder": 17, "customer": 8, "supplier": 7, "part": 9,
              "date": 17}
    assert set(ssb.DDL) == set(widths) == set(ssb.TABLE_ORDER)
    assert [ssb.n_rows(t, 1.0) for t in ("lineorder", "customer",
                                          "supplier", "part")] \
        == [6_000_000, 30_000, 2_000, 200_000]
    assert ssb.n_rows("part", 4.0) == 600_000
    for table, width in widths.items():
        cols, dicts = ssb.generate(table, 0.002, 2147483999)
        assert list(cols) == ssb._column_order(table)
        assert len(cols) == width
        n = {len(v) for v in cols.values()}
        assert n == {ssb.n_rows(table, 0.002)}
        for name, codes in cols.items():
            if name in dicts:
                assert codes.dtype == np.int32
                assert 0 <= codes.min() and codes.max() < len(dicts[name])
            else:
                assert codes.dtype == np.int64
    # the domains the queries name
    assert len(ssb.CITIES) == 250 and "UNITED KI1" in ssb.CITIES
    assert len(set(ssb.CITIES)) == 250
    assert len(ssb.BRANDS) == 1000 and "MFGR#2221" in ssb.BRANDS
    assert "MFGR#12" in ssb.CATEGORIES and len(ssb.CATEGORIES) == 25
    date, ddicts = ssb.generate("date", 1.0, 1)
    assert "Dec1997" in ddicts["d_yearmonth"]
    assert date["d_datekey"][0] == 19920101
    assert date["d_datekey"][-1] == 19981231
    assert (np.diff(date["d_datekey"]) > 0).all()
    # the same seed, the same table
    a, _ = ssb.generate("lineorder", 0.002, 7)
    b, _ = ssb.generate("lineorder", 0.002, 7)
    c, _ = ssb.generate("lineorder", 0.002, 8)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["lo_partkey"], c["lo_partkey"])
    lo = a
    assert (lo["lo_revenue"] == lo["lo_extendedprice"]
            * (100 - lo["lo_discount"]) // 100).all()
    assert lo["lo_linenumber"].min() == 1 and lo["lo_linenumber"].max() <= 7


def _as_the_program_holds_it(tables):
    """The benchmark's tables in the form workload/ssb.py's oracles
    read: strings as text, the dimensions in one dict."""
    out = {}
    for name, (cols, dicts) in tables.items():
        out[name] = {c: (np.array(dicts[c], dtype=object)[v]
                         if c in dicts else v) for c, v in cols.items()}
    lo = out.pop("lineorder")
    return lo, out


def test_references_equal_the_programs_oracles(mix):
    from cockroach_tpu.workload import ssb as program
    from generators import ssb

    sf, seed = 0.02, 2147484001
    tables = {t: ssb.generate(t, sf, seed) for t in ssb.TABLE_ORDER}
    lo, dims = _as_the_program_holds_it(tables)
    sets = traffic.parameter_sets(mix, seed)
    nonempty = 0
    for cls in mix["classes"]:
        mod = load_module("statements", cls["statement"])
        got = mod.reference(tables, sets[cls["name"]][0])
        want = program.ORACLES[cls["name"].replace("_", ".")](lo, dims)
        if isinstance(want, int):
            want = [[want]]
        assert [list(r) for r in got] == [list(r) for r in want], \
            cls["name"]
        assert all(len(r) == len(mod.COLUMNS) for r in got)
        nonempty += bool(got)
    assert nonempty >= 11   # two cities and one month may find nothing


def test_flight_readers_take_the_classes_the_slice_held():
    import ssb_flights

    ctx = {"trace": {"per_class": {
        "q1_1": {"device_ms": 10.0}, "q1_3": {"device_ms": 20.0},
        "q2_1": {"device_ms": 100.0},
        "q3_1": {"device_ms": None}}},
        "client": {"class_median_ms": {"q1_1": 12.0, "q1_2": 14.0,
                                       "q1_3": 22.0, "q4_1": 7.0}}}
    assert load_module("layer_metrics", "ssb_device_ms_f1").read(ctx) == 15.0
    assert load_module("layer_metrics", "ssb_device_ms_f2").read(ctx) == 100.0
    assert load_module("layer_metrics", "ssb_device_ms_f3").read(ctx) is None
    assert load_module("layer_metrics", "ssb_device_ms_f4").read(ctx) is None
    assert load_module("layer_metrics", "ssb_lat_f1_ms").read(ctx) == 16.0
    assert load_module("layer_metrics", "ssb_lat_f2_ms").read(ctx) is None
    assert load_module("layer_metrics", "ssb_lat_f4_ms").read(ctx) == 7.0
    # no trace at all (the CPU rehearsal)
    assert ssb_flights.mean_device_ms({"trace": None}, "f1") is None
    assert sorted(c for f in ssb_flights.FLIGHTS.values() for c in f) \
        == sorted(CLASSES)


def test_counter_readers_print_nothing_on_a_program_without_them():
    old = {"counters": {"setup": {"exec.pallas.kernel.builds.large": 6},
                        "window": {"exec.dispatch.programs": 40}},
           "client": {"completed": 10}}
    for name in ("ssb_kernel_aggs", "ssb_hash_aggs",
                 "ssb_probe_rows_per_stmt"):
        assert load_module("layer_metrics", name).read(old) is None
    new = {"counters": {"setup": {"exec.agg.strategy.kernel": 6,
                                  "exec.agg.strategy.scalar": 3},
                        "window": {"exec.join.probe_rows": 5 << 23}},
           "client": {"completed": 10}}
    assert load_module("layer_metrics", "ssb_kernel_aggs").read(new) == 6.0
    # tallied side by side: a strategy no aggregate took reads 0
    assert load_module("layer_metrics", "ssb_hash_aggs").read(new) == 0.0
    assert load_module("layer_metrics",
                       "ssb_probe_rows_per_stmt").read(new) == (5 << 23) / 10


def test_generator_refuses_a_program_that_cannot_bind_q2_2(monkeypatch):
    from generators import ssb

    # beside this program, and beside none (the reference worker)
    import cockroach_tpu.sql.binder as binder
    assert hasattr(binder.Binder, "bind_string_between")
    ssb.generate("supplier", 0.001, 3)
    monkeypatch.delitem(sys.modules, ssb.BINDER)
    ssb.generate("supplier", 0.001, 3)
    # beside a program whose binder has no string BETWEEN
    old = types.ModuleType(ssb.BINDER)
    old.Binder = type("Binder", (), {})
    monkeypatch.setitem(sys.modules, ssb.BINDER, old)
    with pytest.raises(SystemExit, match="BETWEEN"):
        ssb.generate("supplier", 0.001, 3)
