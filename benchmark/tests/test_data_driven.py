"""A later PR adds a configuration, a traffic mix, a statement and a
counter-based per-layer metric as new files, plus entries in
BENCHMARK.json, and edits no file that is there. Done here in a
temporary copy; the new cell then runs."""

import hashlib
import json
import os
import shutil

from helpers import BENCH, ROOT, run_cell

NEW_SQL = """SELECT l_shipmode, count(*) AS n, sum(l_quantity) AS qty
FROM lineitem
WHERE l_shipdate >= date '{year}-01-01'
GROUP BY l_shipmode
ORDER BY l_shipmode
"""
NEW_REF = '''"""Reference of the made-up statement."""
from generators.tpch import days

COLUMNS = ["text", "int", "dec2"]
TABLES = ("lineitem",)


def reference(tables, p):
    li, dicts = tables["lineitem"]
    m = li["l_shipdate"] >= days(f"{int(p['year'])}-01-01")
    rows = []
    for code, mode in enumerate(dicts["l_shipmode"]):
        g = m & (li["l_shipmode"] == code)
        if g.any():
            rows.append((mode, int(g.sum()),
                         int(li["l_quantity"][g].sum())))
    return sorted(rows)
'''


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        if os.sep + "out" in d or "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha1(
                    fh.read()).hexdigest()
    return out


def test_new_cell_from_new_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _digests(os.path.join(root, "benchmark"))
    b = os.path.join(root, "benchmark")

    with open(os.path.join(b, "configs", "tpch_sf1.json")) as f:
        cfg = json.load(f)
    cfg["source"] = "a made-up deployment for the self-test"
    with open(os.path.join(b, "configs", "madeup.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "madeup_mix.json"), "w") as f:
        json.dump({"loop": "closed", "sessions": 2, "think_time_ms": 1,
                   "parameter_sets": 2, "classes": [
                       {"name": "modes", "statement": "madeup_modes",
                        "params": {"year": {"kind": "int", "lo": 1993,
                                            "hi": 1996}}},
                       {"name": "q6", "statement": "q6", "params": {
                           "year": {"kind": "fixed", "value": 1994},
                           "discount": {"kind": "fixed", "value": "0.06"},
                           "quantity": {"kind": "fixed", "value": 24}}}]},
                  f)
    with open(os.path.join(b, "traffic", "madeup_open.json"), "w") as f:
        json.dump({"loop": "open", "sessions": 2, "rate_per_s": 20.0,
                   "arrivals": "poisson", "parameter_sets": 2,
                   "burst": {"every_s": 1.0, "for_s": 0.25, "factor": 4.0},
                   "classes": [
                       {"name": "modes", "statement": "madeup_modes",
                        "params": {"year": {"kind": "int", "lo": 1993,
                                            "hi": 1996}}}]}, f)
    with open(os.path.join(b, "statements", "madeup_modes.sql"), "w") as f:
        f.write(NEW_SQL)
    with open(os.path.join(b, "statements", "madeup_modes.py"), "w") as f:
        f.write(NEW_REF)
    with open(os.path.join(b, "layer_metrics", "madeup_admitted.json"),
              "w") as f:
        json.dump({"kind": "per_statement",
                   "counters": ["admission.admitted"]}, f)

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "madeup", "source": "self-test",
                             "file": "benchmark/configs/madeup.json",
                             "reduced": [], "why": "self-test"})
    bench["workloads"].append({"name": "madeup.cell", "config": "madeup",
                               "traffic": "madeup_mix", "chips": 1,
                               "why": "self-test"})
    bench["workloads"].append({"name": "madeup.open", "config": "madeup",
                               "traffic": "madeup_open", "chips": 1,
                               "why": "self-test of the open loop"})
    bench["per_layer"].append({
        "name": "madeup_admitted", "unit": "stmts/stmt", "better": "lower",
        "source": "program_counter", "layer": "admission",
        "moves": "stmts_per_s",
        "workloads": ["madeup.cell", "madeup.open"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    rc, result, out = run_cell("madeup.cell", root=root)
    assert rc == 0, out[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["counts"]["madeup_admitted"] >= 1.0
    assert result["counts"]["compiles_in_window"] == 0
    rc, result, out = run_cell("madeup.open", root=root, seconds=3)
    assert rc == 0, out[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    # 20/s, four times that for a quarter of each second: ~35/s offered
    assert 60 <= result["attempted"] <= 160
    after = _digests(os.path.join(root, "benchmark"))
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/madeup.json", "layer_metrics/madeup_admitted.json",
        "statements/madeup_modes.py", "statements/madeup_modes.sql",
        "traffic/madeup_mix.json", "traffic/madeup_open.json"]
