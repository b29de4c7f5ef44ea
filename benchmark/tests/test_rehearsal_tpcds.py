"""`tpcds_sf1.rollup` end to end on the CPU at SF0.01, with what the
other cells' rehearsals assert and the counters this cell adds; the
cell's files found by name and its traffic as ISSUE 40 set it; the
generator's schema; the benchmark's references against the program's
own oracles on one seeded data set; the readers on a slice that lacks
some classes and on a program without the counters; and the
generator's refusal of a program that cannot parse ROLLUP."""

import json
import math
import os
import sys
import types
from fractions import Fraction

import numpy as np
import pytest

import traffic
from helpers import BENCH, ROOT, run_cell
from refworker import load_module

CELL = "tpcds_sf1.rollup"
CLASSES = ["q27", "q36", "q67", "q89"]


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "rollup.json")) as f:
        return json.load(f)


def test_cell_rehearsal():
    rc, result, out = run_cell(CELL, seconds=4, seed=2147483911)
    assert rc == 0, out[-3000:]
    assert result["rehearsal"] is True and result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= len(CLASSES)
    counts = result["counts"]
    assert counts["compiles_in_window"] == 0
    assert counts["upload_bytes_in_window"] == 0
    assert counts["plan_hit_share"] == 100.0
    # ROLLUP of k keys is k + 1 sets: Q27 3, Q36 3, Q67 9 (Q89 none)
    assert counts["ds_grouping_sets"] == 15
    # the coarser sets read the finest set's slots, and the windows
    # sort grouped rows, never the 2^15-row fact batch of this size
    assert 0 < counts["ds_rollup_rows_per_stmt"]
    assert 0 < counts["ds_window_rows_per_stmt"] < 1 << 15
    # no Compact's block overflowed into the uncompacted replan
    assert counts["ds_compact_overflows"] == 0
    # one plan a class: no parameter the traffic varies builds another
    assert result["new_plans"] == {c: [1, 0, 0, 0] for c in
                                   ("q27", "q36", "q67")} | {"q89": [1]}


def test_the_cell_is_the_issues(mix):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("tpcds_sf1", "rollup", 1)
    assert mix["loop"] == "closed" and int(mix["sessions"]) == 1
    assert not mix["think_time_ms"] and int(mix["parameter_sets"]) == 4
    assert [c["name"] for c in mix["classes"]] == CLASSES
    for cls in mix["classes"]:
        assert cls["statement"] == "ds_" + cls["name"]
        for ext in (".sql", ".py"):
            assert os.path.exists(os.path.join(
                BENCH, "statements", cls["statement"] + ext))
    sets = traffic.parameter_sets(mix, 3500000711)
    assert all(1998 <= p["year"] <= 2002 for p in sets["q27"] + sets["q36"])
    assert all(1176 <= p["dms"] <= 1224 for p in sets["q67"])
    assert sets["q89"] == [{"year": 1999}]
    assert sets["q27"][0]["gen"] == "M" and sets["q27"][0]["es"] == \
        "College"
    metrics = {m["name"]: m for m in bench["per_layer"]
               if CELL in m.get("workloads", [])}
    assert set(metrics) >= {"ds_device_ms_q67", "ds_device_ms_star",
                            "ds_lat_q67_ms", "ds_grouping_sets",
                            "ds_rollup_rows_per_stmt",
                            "ds_window_rows_per_stmt",
                            "ds_compact_overflows"}


def test_config_states_what_the_contract_asks():
    with open(os.path.join(BENCH, "configs", "tpcds_sf1.json")) as f:
        cfg = json.load(f)
    assert cfg["generator"] == "tpcds" and cfg["scale_factor"] == 1.0
    assert cfg["chips"] == 1 and cfg["settings"] == {}
    assert set(cfg["reduced"]) == {"nodes", "tables", "queries"}
    for key in ("source", "deployment", "schema", "guarantees", "assumed"):
        assert cfg[key]
    assert set(cfg["guarantees"]) >= {"answers", "isolation", "writes",
                                      "replies"}


def test_generator_makes_the_specs_schema():
    from generators import tpcds

    widths = {"store_sales": 23, "item": 22, "date_dim": 28, "store": 29,
              "customer_demographics": 9}
    assert set(tpcds.DDL) == set(widths) == set(tpcds.TABLE_ORDER)
    assert [tpcds.n_rows(t, 1.0) for t in tpcds.TABLE_ORDER] \
        == [73_049, 12, 18_000, 1_920_800, 2_880_404]
    for table, width in widths.items():
        cols, dicts = tpcds.generate(table, 0.01, 2147483999)
        assert len(cols) == width
        assert len({len(v) for v in cols.values()}) == 1
        for name, codes in cols.items():
            if name in dicts:
                assert codes.dtype == np.int32
                assert 0 <= codes.min() and codes.max() < len(dicts[name])
    date, _ = tpcds.generate("date_dim", 1.0, 1)
    jan2000 = date["d_date_sk"][(date["d_year"] == 2000)
                                & (date["d_moy"] == 1)]
    assert set(date["d_month_seq"][np.isin(date["d_date_sk"], jan2000)]) \
        == {1200}
    a, _ = tpcds.generate("store_sales", 0.01, 7)
    b, _ = tpcds.generate("store_sales", 0.01, 7)
    c, _ = tpcds.generate("store_sales", 0.01, 8)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["ss_item_sk"], c["ss_item_sk"])
    assert (a["ss_net_profit"] == a["ss_net_paid"]
            - a["ss_ext_wholesale_cost"]).all()


def _as_the_program_holds_it(tables):
    return {t: (cols, dicts, {}) for t, (cols, dicts) in tables.items()}


def _same(ref_value, oracle_value, kind):
    if kind.startswith("avg") or kind == "ratio":
        num, den = ref_value
        want = Fraction(num, den * (10 ** int(kind[3:])
                                    if kind.startswith("avg") else 1))
        return math.isclose(float(want), float(oracle_value),
                            rel_tol=1e-12)
    if kind == "dec2":
        return Fraction(ref_value, 100) == oracle_value
    return ref_value == oracle_value


@pytest.mark.parametrize("name,as_set", [("q27", True), ("q36", False),
                                         ("q89", False)])
def test_references_equal_the_programs_oracles(mix, name, as_set):
    """Q67 orders by its coalesced keys (a rolled-up category, 'ALL',
    sorts first; NULL sorts last in the program's text), so its first
    hundred rows differ by design; Q27's two rows of an item swap."""
    from cockroach_tpu.workload import tpcds as program
    from generators import tpcds

    tables = {t: tpcds.generate(t, 0.02, 2147484001)
              for t in tpcds.TABLE_ORDER}
    mod = load_module("statements", "ds_" + name)
    params = traffic.parameter_sets(mix, 2147484001)[name][0]
    got = mod.reference(tables, params)
    want = program.ORACLES[name](_as_the_program_holds_it(tables),
                                 **{k: v for k, v in params.items()
                                    if k != "state"})
    assert len(got) == len(want) > 10
    if as_set:
        got = sorted(got, key=lambda r: (r[0], r[1] == "ALL"))
        want = sorted(want, key=lambda r: (r[0] or "ALL", r[1] is None))
    for g, w in zip(got, want):
        w = ["ALL" if v is None else v for v in w]
        assert all(_same(a, b, k) for a, b, k in zip(g, w, mod.COLUMNS)), \
            (g, w)


def test_readers_take_the_classes_the_slice_held():
    import ds_classes

    ctx = {"trace": {"per_class": {"q27": {"device_ms": 30.0},
                                   "q89": {"device_ms": 50.0}}},
           "client": {"class_median_ms": {"q67": 900.0}},
           "counters": {"setup": {}, "window": {}}}
    assert load_module("layer_metrics", "ds_device_ms_q67").read(ctx) is None
    assert load_module("layer_metrics",
                       "ds_device_ms_star").read(ctx) == 40.0
    assert load_module("layer_metrics", "ds_lat_q67_ms").read(ctx) == 900.0
    assert ds_classes.device_ms({"trace": None}, ("q67",)) is None


def test_counter_readers_print_nothing_on_a_program_without_them():
    old = {"counters": {"setup": {"exec.agg.strategy.hash": 1},
                        "window": {"exec.dispatch.programs": 40}},
           "client": {"completed": 10}}
    for name in ("ds_grouping_sets", "ds_rollup_rows_per_stmt",
                 "ds_window_rows_per_stmt"):
        assert load_module("layer_metrics", name).read(old) is None
    new = {"counters": {"setup": {"exec.agg.grouping_sets": 15},
                        "window": {"exec.agg.rollup.rows": 800,
                                   "exec.window.rows": 400}},
           "client": {"completed": 4}}
    assert load_module("layer_metrics", "ds_grouping_sets").read(new) == 15
    assert load_module("layer_metrics",
                       "ds_rollup_rows_per_stmt").read(new) == 200
    assert load_module("layer_metrics",
                       "ds_window_rows_per_stmt").read(new) == 100


def test_generator_refuses_a_program_that_cannot_parse_rollup(monkeypatch):
    from generators import tpcds

    import cockroach_tpu.sql.parser as parser
    assert hasattr(parser.Parser, "parse_group_by")
    tpcds.generate("store", 0.01, 3)
    monkeypatch.delitem(sys.modules, tpcds.PARSER)
    tpcds.generate("store", 0.01, 3)
    old = types.ModuleType(tpcds.PARSER)
    old.Parser = type("Parser", (), {})
    monkeypatch.setitem(sys.modules, tpcds.PARSER, old)
    with pytest.raises(SystemExit, match="ROLLUP"):
        tpcds.generate("store", 0.01, 3)
