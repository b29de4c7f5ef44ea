"""`ds_rollup_network_sets` reads the network's counter over set-up,
and prints nothing on a program without it."""

from refworker import load_module


def test_reads_the_network_counter_or_nothing():
    reader = load_module("layer_metrics", "ds_rollup_network_sets")
    old = {"counters": {"setup": {"exec.agg.grouping_sets": 15},
                        "window": {}}}
    assert reader.read(old) is None
    new = {"counters": {"setup": {"exec.agg.grouping_sets": 15,
                                  "exec.agg.rollup.network": 9},
                        "window": {}}}
    assert reader.read(new) == 9
