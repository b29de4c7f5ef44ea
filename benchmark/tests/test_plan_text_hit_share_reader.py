"""`plan_text_hit_share` reads the statement-text cache's hits over its
lookups in the window, and prints nothing on a program without the
counters."""

import json
import os

import run
from helpers import ROOT


def _metric() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    m, = [m for m in spec["per_layer"]
          if m["name"] == "plan_text_hit_share"]
    return m


def test_reads_the_text_cache_share_or_nothing():
    m = _metric()
    old = {"counters": {"setup": {},
                        "window": {"sql.plan.cache.hit": 250}}}
    assert run.read_layer_metrics([m], old) == {}
    new = {"counters": {"setup": {"sql.plan.text.miss": 12},
                        "window": {"sql.plan.text.hit": 247,
                                   "sql.plan.text.miss": 3,
                                   "sql.plan.cache.hit": 250}}}
    assert run.read_layer_metrics([m], new) == {
        "plan_text_hit_share": {"value": 100.0 * 247 / 250, "unit": "%"}}
