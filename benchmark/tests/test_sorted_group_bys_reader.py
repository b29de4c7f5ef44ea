"""`ds_sorted_group_bys` reads the sorted GROUP BY counter over set-up,
and prints nothing on a program without it."""

from refworker import load_module


def test_reads_the_sorted_group_by_counter_or_nothing():
    reader = load_module("layer_metrics", "ds_sorted_group_bys")
    old = {"counters": {"setup": {"exec.agg.strategy.hash": 1},
                        "window": {}}}
    assert reader.read(old) is None
    new = {"counters": {"setup": {"exec.agg.strategy.sorted": 2,
                                  "exec.agg.sorted.group_by": 1},
                        "window": {}}}
    assert reader.read(new) == 1
