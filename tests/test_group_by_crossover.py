"""group_by_crossover.py, the timing of a plain GROUP BY's two
strategies past the dense bound that exec/compile.py
SORTED_GROUP_MIN_ROWS cites, run at a CPU's size: both strategies find
the same groups and the same sums over batches of 3 and 6 keys."""

import importlib.util
import pathlib

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def crossover():
    spec = importlib.util.spec_from_file_location(
        "group_by_crossover", REPO / "group_by_crossover.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("keys", [3, 6])
def test_both_strategies_find_the_same_groups(crossover, keys):
    rec = crossover.measure(4096, keys, 300, 1)
    assert 250 <= rec["groups"] <= 300
    assert rec["hash_ms"] > 0 and rec["sorted_ms"] > 0
    assert rec["hash_compile_s"] > 0 and rec["sorted_compile_s"] > 0
