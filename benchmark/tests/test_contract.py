"""BENCHMARK.json against the contract's limits that a file can break,
and every name in it against the files the harness finds by name."""

import json
import os
import re

import pytest

from helpers import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 \
        and "\n" not in s and "\t" not in s


def test_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert bench["paths"] == ["benchmark"]
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(bench["run_seconds"], int)
    cells = 24  # later PRs may fill every cell at this length
    assert (2 + 14 * cells) * (bench["run_seconds"] + 60) \
        + cells * 180 + 1200 <= 43200


def test_names_units_and_lines(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
        assert m["source"] in SOURCES, m
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _line(m["layer"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert _line(w["why"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    assert {m["name"]: m["unit"] for m in bench["per_layer"]
            if m["name"].endswith("_roofline")} == {"q6_roofline": "%"}


def test_cells_and_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    cells = bench["workloads"]
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert {w["config"] for w in cells} == set(configs)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 2)
    files = [c["file"] for c in configs.values()]
    assert len(set(files)) == len(files)
    for w in cells:
        with open(os.path.join(ROOT, configs[w["config"]]["file"])) as f:
            cfg = json.load(f)
        assert cfg["chips"] == w["chips"]
        assert set(configs[w["config"]]["reduced"]) <= set(cfg["reduced"])
        with open(os.path.join(BENCH, "traffic",
                               w["traffic"] + ".json")) as f:
            mix = json.load(f)
        for cls in mix["classes"]:
            for ext in (".sql", ".py"):
                assert os.path.exists(os.path.join(
                    BENCH, "statements", cls["statement"] + ext))


def test_every_metric_has_a_reader_and_a_cell(bench):
    cells = {w["name"] for w in bench["workloads"]}
    end = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        base = os.path.join(BENCH, "layer_metrics", m["name"])
        assert os.path.exists(base + ".json") != os.path.exists(
            base + ".py"), m["name"]
        where = set(m.get("workloads", cells))
        assert where <= cells
        # a per-layer metric is reported only where the metric it moves is
        moved = end[m["moves"]]
        assert where <= set(moved.get("workloads", cells)), m["name"]
    for cell in cells:
        for group in ("end_to_end", "per_layer"):
            mine = [m for m in bench[group]
                    if cell in m.get("workloads", cells)]
            assert len(mine) >= (2 if group == "end_to_end" else 1)
    layers = {m["layer"] for m in bench["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert all(layer in perf for layer in layers)
