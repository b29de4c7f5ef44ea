"""`tpch_sf1_full.nested` end to end on the CPU at SF0.01, with what the
other cells' rehearsals assert; the cell's files found by name and its
traffic as ISSUE 36 set it; the per-kind readers on a slice that lacks
some classes and on a program that lacks the counters; and the
generator's refusal of a program that writes a subquery's result into
the plan."""

import json
import os
import sys
import types

import pytest

import traffic
from helpers import BENCH, ROOT, run_cell
from refworker import load_module

CELL = "tpch_sf1_full.nested"


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "nested.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def classes(mix):
    return [c["name"] for c in mix["classes"]]


def test_cell_rehearsal(classes):
    rc, result, out = run_cell(CELL, seconds=6, seed=2147483777)
    assert rc == 0, out[-3000:]
    assert result["rehearsal"] is True and result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 1
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    counts = result["counts"]
    assert counts["compiles_in_window"] == 0
    assert counts["upload_bytes_in_window"] == 0
    assert counts["plan_hit_share"] == 100.0
    # every new metric a CPU run may print (the counters) prints.
    # What the five plans hold: Q4's EXISTS a semi-join and Q22's NOT
    # EXISTS an anti-join; four left joins, Q13's own and the grouped
    # sub-selects that Q21's two tests and Q17's average unnest into;
    # and no subquery's result a constant of a program
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        counters = {m["name"] for m in json.load(f)["per_layer"]
                    if m.get("workloads") == [CELL]
                    and m["source"] == "program_counter"}
    assert counters == {"nested_semi_anti_joins", "nested_outer_joins",
                        "nested_inlined_subqueries",
                        "nested_probe_rows_per_stmt"}
    assert counters <= set(counts)
    assert counts["nested_semi_anti_joins"] == 2
    assert counts["nested_outer_joins"] == 4
    assert counts["nested_inlined_subqueries"] == 0
    assert counts["nested_probe_rows_per_stmt"] >= 1 << 16
    # one plan a class, whatever the parameter set (Q22 has its scalar
    # subquery's beside): Q4's dates and Q13's words are arguments
    for name, new in result["new_plans"].items():
        assert new[0] in (1, 2) and not any(new[1:]), (name, new)


def test_the_cell_is_the_issues(mix, classes):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("tpch_sf1_full", "nested", 1)
    assert mix["loop"] == "closed" and int(mix["sessions"]) == 1
    assert not mix["think_time_ms"] and int(mix["parameter_sets"]) == 4
    assert classes == ["q4", "q13", "q22", "q21", "q17"]   # ISSUE 36
    fixed = {c["name"]: {k: d["value"] for k, d in c["params"].items()
                         if d["kind"] == "fixed"}
             for c in mix["classes"]}
    assert fixed["q22"] == dict(zip(
        ("i1", "i2", "i3", "i4", "i5", "i6", "i7"),
        (13, 31, 23, 29, 30, 18, 17)))
    assert fixed["q21"] == {"nation": "SAUDI ARABIA"}
    assert fixed["q17"] == {"brand": "Brand#23", "container": "MED BOX"}
    assert not fixed["q4"] and not fixed["q13"]
    tables = []
    for cls in mix["classes"]:
        assert cls["statement"] == "nested_" + cls["name"]
        mod = load_module("statements", cls["statement"])
        assert set(mod.COLUMNS) <= {"int", "text", "dec2", "dec4", "avg2"}
        tables.extend(mod.TABLES)
        with open(os.path.join(BENCH, "statements",
                               cls["statement"] + ".sql")) as f:
            text = f.read()
        sets = traffic.parameter_sets(mix, 2147485417)[cls["name"]]
        assert 1 <= len(sets) <= int(mix["parameter_sets"])
        for p in sets:
            assert "{" not in text.format(**p)
    # all eight tables are loaded, whichever classes are kept
    assert set(tables) == {"lineitem", "part", "orders", "customer",
                           "partsupp", "supplier", "nation", "region"}
    metrics = {m["name"] for m in bench["per_layer"]
               if m.get("workloads") == [CELL]}
    assert metrics == {
        "nested_device_ms_semi_anti", "nested_device_ms_outer",
        "nested_device_ms_unnested", "nested_lat_semi_anti_ms",
        "nested_lat_outer_ms", "nested_lat_unnested_ms",
        "nested_op_share_hashjoin", "nested_semi_anti_joins",
        "nested_outer_joins", "nested_inlined_subqueries",
        "nested_subquery_ms", "nested_probe_rows_per_stmt"}
    for name in metrics:     # each has its reader, found by name
        assert callable(load_module("layer_metrics", name).read)


def test_config_states_what_the_contract_asks():
    with open(os.path.join(BENCH, "configs", "tpch_sf1_full.json")) as f:
        cfg = json.load(f)
    assert cfg["generator"] == "tpch_full" and cfg["scale_factor"] == 1.0
    assert cfg["chips"] == 1 and cfg["settings"] == {}
    assert set(cfg["reduced"]) == {"nodes", "queries"}
    for key in ("source", "deployment", "schema", "guarantees", "assumed"):
        assert cfg[key]
    assert set(cfg["guarantees"]) >= {"answers", "isolation", "writes",
                                      "replies"}
    assert "tpchvec.go:44-52" in cfg["source"]
    assert "1e-12" in cfg["guarantees"]["answers"]


def test_readers_take_the_classes_the_slice_held(monkeypatch):
    import nested_classes
    import span_reduce

    ctx = {"trace": {"per_class": {"q4": {"device_ms": 100.0},
                                   "q22": {"device_ms": 50.0},
                                   "q13": {"device_ms": 7.0}}},
           "client": {"class_median_ms": {"q4": 110.0, "q21": 900.0,
                                          "q22": 70.0, "q13": 9.0,
                                          "q17": 500.0},
                      "completed": 10},
           "counters": {"setup": {"exec.join.kind.semi": 1,
                                  "exec.join.kind.anti": 1,
                                  "exec.join.kind.left": 4,
                                  "exec.join.kind.inner": 9,
                                  "exec.subquery.inlined": 0},
                        "window": {"exec.subquery.inlined": 0,
                                   "exec.subquery.seconds.count": 4,
                                   "exec.subquery.seconds.sum": 0.01,
                                   "exec.join.probe_rows": 1000}}}
    monkeypatch.setattr(span_reduce, "_captured",
                        {"reduced": {"op_share_hashjoin": 61.5}})
    assert nested_classes.mean_device_ms(ctx, "semi_anti") == 75.0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]
                 if m.get("workloads") == [CELL]]
    got = {n: load_module("layer_metrics", n).read(ctx) for n in names}
    assert got == {
        "nested_device_ms_semi_anti": 75.0,   # q21 not in the slice
        "nested_device_ms_outer": 7.0,
        "nested_device_ms_unnested": None,    # nor q17: left out
        "nested_lat_semi_anti_ms": 360.0,
        "nested_lat_outer_ms": 9.0,
        "nested_lat_unnested_ms": 500.0,
        "nested_op_share_hashjoin": 61.5,
        "nested_semi_anti_joins": 2.0,
        "nested_outer_joins": 4.0,
        "nested_inlined_subqueries": 0.0,
        "nested_subquery_ms": pytest.approx(2.5),
        "nested_probe_rows_per_stmt": 100.0}
    # a program without the counters, a run without a trace or a
    # capture: nothing is printed and nothing divides by zero
    monkeypatch.setattr(span_reduce, "_captured", {"reduced": None})
    bare = {"trace": None, "client": {"class_median_ms": {},
                                      "completed": 0},
            "counters": {"setup": {}, "window": {}}}
    for n in names:
        assert load_module("layer_metrics", n).read(bare) is None, n


def test_generator_refuses_a_program_that_inlines_subqueries(monkeypatch):
    from generators import tpch_full

    fake = types.ModuleType(tpch_full.PLANPARAM)
    monkeypatch.setitem(sys.modules, tpch_full.PLANPARAM, fake)
    with pytest.raises(SystemExit) as e:
        tpch_full.generate("nation", 0.01, 3)
    assert "SubqueryArg" in str(e.value) and e.value.code != 0
    fake.SubqueryArg = object
    cols, dicts = tpch_full.generate("nation", 0.01, 3)
    assert len(cols["n_nationkey"]) == 25 and "n_comment" in dicts
