"""`tpcds_sf1_channels.reports` end to end on the CPU at SF0.01, with what
the other cells' rehearsals assert and the counters this cell adds; the
cell's files found by name and its traffic; the
generator's schema and its keys (a sales line's (item, ticket or order)
unique, every return joining its line); the readers on a slice that
lacks some classes and on a program without the counters; and the
generator's refusal of a program that plans no UNION ALL on the
device."""

import json
import os
import sys
import types

import numpy as np
import pytest

import traffic
from helpers import BENCH, ROOT, run_cell
from refworker import load_module

CELL = "tpcds_sf1_channels.reports"
CLASSES = ["q5", "q77", "q80"]
METRICS = {"ch_device_ms_q5", "ch_device_ms_q77", "ch_device_ms_q80",
           "ch_composite_joins", "ch_hash_loop_joins", "ch_union_branches",
           "ch_cte_temps_in_window"}


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "reports.json")) as f:
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
    # Q5 nine branches, Q77 three, Q80 three, each traced once
    assert counts["ch_union_branches"] == 15
    assert counts["ch_cte_temps_in_window"] == 0
    assert counts["ch_hash_loop_joins"] == 0
    # at SF0.01 the (item, ticket) pairs span ~5e5 slots and take the
    # packed table; at SF1 they span 4.3e9 and take the bounded form
    # (tests/test_tpcds_channels.py lowers the cap to see it here)
    assert counts["ch_composite_joins"] == 0
    # one plan a class: the start date is an argument of the program
    assert result["new_plans"] == {c: [1, 0, 0, 0] for c in CLASSES}


def test_the_cell_and_its_traffic(mix):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("tpcds_sf1_channels", "reports", 1)
    assert mix["loop"] == "closed" and int(mix["sessions"]) == 1
    assert not mix["think_time_ms"] and int(mix["parameter_sets"]) == 4
    assert [c["name"] for c in mix["classes"]] == CLASSES
    for cls in mix["classes"]:
        assert cls["statement"] == "ch_" + cls["name"]
        for ext in (".sql", ".py"):
            assert os.path.exists(os.path.join(
                BENCH, "statements", cls["statement"] + ext))
    sets = traffic.parameter_sets(mix, 3500000711)
    for c in CLASSES:
        assert len(sets[c]) == 4
        assert all("1998-01-16" <= p["date"] <= "2002-11-20"
                   for p in sets[c])
    metrics = {m["name"]: m for m in bench["per_layer"]
               if CELL in m.get("workloads", [])}
    assert set(metrics) >= METRICS
    for name in METRICS:
        assert metrics[name]["workloads"] == [CELL]
    for name, days in (("ch_q5", 14), ("ch_q77", 30), ("ch_q80", 30)):
        with open(os.path.join(BENCH, "statements", name + ".sql")) as f:
            assert f"interval '{days} days'" in f.read()


def test_config_states_what_the_contract_asks():
    with open(os.path.join(BENCH, "configs",
                           "tpcds_sf1_channels.json")) as f:
        cfg = json.load(f)
    assert cfg["generator"] == "tpcds_channels"
    assert cfg["scale_factor"] == 1.0
    assert cfg["chips"] == 1 and cfg["settings"] == {}
    assert set(cfg["reduced"]) == {"nodes", "tables", "queries"}
    for key in ("source", "deployment", "schema", "guarantees", "assumed"):
        assert cfg[key]
    assert set(cfg["guarantees"]) >= {"answers", "isolation", "writes",
                                      "replies"}


def test_generator_makes_the_specs_schema():
    from generators import tpcds_channels as gen

    widths = {"store_returns": 20, "catalog_sales": 34,
              "catalog_returns": 27, "web_sales": 34, "web_returns": 24,
              "promotion": 19, "catalog_page": 9, "web_site": 26,
              "web_page": 14}
    rows = {"store_returns": 287_514, "catalog_sales": 1_441_548,
            "catalog_returns": 144_067, "web_sales": 719_384,
            "web_returns": 71_763, "promotion": 300, "catalog_page": 11_718,
            "web_site": 30, "web_page": 60, "store_sales": 2_880_404,
            "item": 18_000, "date_dim": 73_049, "store": 12}
    assert {t: gen.n_rows(t, 1.0) for t in rows} == rows
    assert set(gen.DDL) == set(gen.TABLE_ORDER)
    data = {}
    for table in gen.TABLE_ORDER:
        if table == "customer_demographics":
            continue
        cols, dicts = gen.generate(table, 0.01, 2147483999)
        data[table] = cols
        order = [ln.split()[0] for ln in gen.DDL[table].splitlines()
                 if ln.startswith("    ")]
        assert list(cols) == order
        assert len(cols) == widths.get(table, len(cols))
        assert len({len(v) for v in cols.values()}) == 1
        for name, codes in cols.items():
            if name in dicts:
                assert codes.dtype == np.int32
                assert 0 <= codes.min() and codes.max() < len(dicts[name])
    for sales, (item, ticket) in gen.LINE_KEY.items():
        pairs = set(zip(data[sales][item].tolist(),
                        data[sales][ticket].tolist()))
        if sales != "store_sales":   # tpcds.py's tickets may repeat one
            assert len(pairs) == len(data[sales][item])
        returns = next(r for r, s in gen.RETURNS_OF.items() if s == sales)
        rcols = data[returns]
        ret_item = next(c for c in rcols if c.endswith("_item_sk"))
        ret_ticket = next(c for c in rcols if c.endswith(("_ticket_number",
                                                          "_order_number")))
        ret = list(zip(rcols[ret_item].tolist(),
                       rcols[ret_ticket].tolist()))
        assert len(set(ret)) == len(ret)         # a key of the returns
        assert set(ret) <= pairs                 # each joins its line
    a, _ = gen.generate("catalog_sales", 0.01, 7)
    b, _ = gen.generate("catalog_sales", 0.01, 7)
    c, _ = gen.generate("catalog_sales", 0.01, 8)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["cs_item_sk"], c["cs_item_sk"])


def test_readers_take_the_classes_the_slice_held():
    ctx = {"trace": {"per_class": {"q5": {"device_ms": 30.0},
                                   "q80": {"device_ms": 50.0}}},
           "counters": {"setup": {}, "window": {}}}
    assert load_module("layer_metrics", "ch_device_ms_q5").read(ctx) == 30.0
    assert load_module("layer_metrics", "ch_device_ms_q77").read(ctx) is None
    assert load_module("layer_metrics",
                       "ch_device_ms_q80").read(ctx) == 50.0
    assert load_module("layer_metrics",
                       "ch_device_ms_q5").read({"trace": None}) is None


def test_counter_readers_print_nothing_on_a_program_without_them():
    old = {"counters": {"setup": {"exec.join.kind.left": 4},
                        "window": {"exec.dispatch.programs": 40}}}
    for name in ("ch_composite_joins", "ch_hash_loop_joins",
                 "ch_union_branches", "ch_cte_temps_in_window"):
        assert load_module("layer_metrics", name).read(old) is None
    new = {"counters": {"setup": {"exec.join.strategy.bounded": 3,
                                  "exec.join.strategy.sorted": 1,
                                  "exec.join.strategy.hash": 0,
                                  "exec.setop.union_all.branches": 15},
                        "window": {"exec.cte.temps": 0}}}
    assert load_module("layer_metrics",
                       "ch_composite_joins").read(new) == 4
    assert load_module("layer_metrics",
                       "ch_hash_loop_joins").read(new) == 0
    assert load_module("layer_metrics",
                       "ch_union_branches").read(new) == 15
    assert load_module("layer_metrics",
                       "ch_cte_temps_in_window").read(new) == 0


def test_generator_refuses_a_program_without_union_all(monkeypatch):
    from generators import tpcds_channels as gen

    import cockroach_tpu.sql.plan as plan
    assert hasattr(plan, "UnionAll")
    gen.generate("web_site", 0.01, 3)
    monkeypatch.delitem(sys.modules, gen.PLAN)
    gen.generate("web_site", 0.01, 3)
    old = types.ModuleType(gen.PLAN)
    monkeypatch.setitem(sys.modules, gen.PLAN, old)
    with pytest.raises(SystemExit, match="UNION ALL"):
        gen.generate("web_site", 0.01, 3)
