"""The general generator: seeded, within its declared domains, and the
session processes free of the program and of JAX."""

import datetime
import json
import os
import random
import subprocess
import sys

import pytest

import peaks
import traffic
from helpers import BENCH

MIX = {
    "loop": "open", "sessions": 3, "rate_per_s": 200.0,
    "arrivals": "poisson", "parameter_sets": 4,
    "burst": {"every_s": 2.0, "for_s": 0.5, "factor": 8.0},
    "classes": [
        {"name": "a", "statement": "q6", "params": {
            "n": {"kind": "int", "lo": 3, "hi": 9},
            "d": {"kind": "date", "lo": "1995-01-01", "hi": "1995-03-01",
                  "step_days": 7},
            "c": {"kind": "choice", "values": ["x", "y", "z"]},
            "z": {"kind": "zipf", "n": 1000, "theta": 0.99}}},
        {"name": "b", "statement": "q1", "params": {
            "f": {"kind": "fixed", "value": "BUILDING"}}}]}


def test_parameter_sets_are_seeded_and_in_domain():
    a = traffic.parameter_sets(MIX, 7)
    assert a == traffic.parameter_sets(MIX, 7)
    assert a != traffic.parameter_sets(MIX, 8)
    assert len(a["a"]) == 4 and a["b"] == [{"f": "BUILDING"}]
    for p in a["a"]:
        assert 3 <= p["n"] <= 9 and p["c"] in "xyz" and 1 <= p["z"] <= 1000
        d = datetime.date.fromisoformat(p["d"])
        assert (d - datetime.date(1995, 1, 1)).days % 7 == 0
        assert d <= datetime.date(1995, 3, 1)


def test_zipf_is_skewed():
    rng = random.Random(1)
    dom = {"kind": "zipf", "n": 1000, "theta": 0.99}
    draws = [traffic.draw(dom, rng) for _ in range(5000)]
    assert min(draws) == 1 and max(draws) <= 1000
    assert draws.count(1) > 20 * draws.count(500)


def test_closed_sessions_cycle_classes_from_a_seeded_offset():
    it = traffic.session_statements({"classes": [1, 2, 3]}, 5, 0, [4, 1, 2])
    seq = [next(it) for _ in range(9)]
    assert [c for c, _ in seq[1:]] == [(seq[0][0] + i) % 3
                                       for i in range(1, 9)]
    assert all(s < (4, 1, 2)[c] for c, s in seq)
    again = traffic.session_statements({"classes": [1, 2, 3]}, 5, 0,
                                       [4, 1, 2])
    assert seq == [next(again) for _ in range(9)]


def test_open_arrivals_are_seeded_and_burst():
    a = traffic.arrivals(MIX, 3, 4.0, [4, 1])
    assert a == traffic.arrivals(MIX, 3, 4.0, [4, 1])
    assert all(0 <= t < 4.0 and 0 <= s < 3 for t, s, _, _ in a)
    in_burst = sum(1 for t, *_ in a if t % 2.0 < 0.5)
    assert in_burst > len(a) / 2  # a quarter of the time, 8x the rate
    uniform = traffic.arrivals(dict(MIX, arrivals="uniform", burst=None),
                               3, 1.0, [4, 1])
    assert len(uniform) == 199


@pytest.mark.parametrize("module", ["loadgen", "refworker", "traffic",
                                    "pgclient"])
def test_client_side_imports_neither_the_program_nor_jax(module):
    code = (f"import sys; sys.path.insert(0, {BENCH!r}); import {module}; "
            "bad = [m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'jaxlib', 'cockroach_tpu')]; print(bad); "
            "sys.exit(1 if bad else 0)")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True)
    assert p.returncode == 0, p.stdout + p.stderr


def test_closed_loop_rate_counts_whole_rounds():
    """A window that ends inside a round gives the rate of its whole
    rounds: what the partial round holds depends on the seeded offset."""
    import run
    mix = {"loop": "closed", "sessions": 1, "classes": [
        {"name": "long"}, {"name": "short"}]}
    s, ms = int(1e9), int(1e6)
    samples, t = [], 50 * ms  # the session starts 50 ms late
    for i in range(7):  # long 900 ms, short 100 ms: 2 a second
        d = (900 if i % 2 == 0 else 100) * ms
        samples.append([i % 2, 0, t, t, t + d, True, 0])
        t += d
    # the eighth statement is still under way when the window ends
    samples.append([1, 0, t, t, t + 100 * ms, True, 0])
    view = run.client_view(mix, samples, 0, int(3.99 * s))
    assert view["attempted"] == 8 and view["completed"] == 7
    assert view["failed"] == 0
    assert view["stmts_per_s"] == pytest.approx(2.0)
    assert view["stmts_per_s_plain"] == pytest.approx(7 / 3.99)
    # two sessions add up; a failed statement falls back to the count
    both = samples + [x[:6] + [1] for x in samples]
    assert run.client_view(dict(mix, sessions=2), both, 0, int(3.99 * s)
                           )["stmts_per_s"] == pytest.approx(4.0)
    samples[2][5] = False
    view = run.client_view(mix, samples, 0, int(3.99 * s))
    assert view["failed"] == 1
    assert view["stmts_per_s"] == pytest.approx(6 / 3.99)


def test_value_metric_reads_into_lists():
    import run
    ctx = {"counters": {"window": {}},
           "setup": {"facts": {"q18": {"first_exec_s": [18.0, 0.7]}}}}
    spec = {"kind": "value", "key": "setup/facts/q18/first_exec_s/0"}
    assert run.read_counter_metric(spec, ctx) == 18.0
    spec["key"] = "setup/facts/q18/first_exec_s/5"
    assert run.read_counter_metric(spec, ctx) is None
    spec["key"] = "setup/facts/q3/first_exec_s/0"
    assert run.read_counter_metric(spec, ctx) is None


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert "source" in p
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
    with open(os.path.join(BENCH, "peaks.json")) as f:
        assert all("source" in v for v in json.load(f).values())


def test_q6_bytes_from_shapes():
    import numpy as np
    n = 1000
    cols = {"l_shipdate": np.zeros(n, np.int32),
            "l_discount": np.full(n, 10, np.int64),
            "l_quantity": np.full(n, 5000, np.int64),
            "l_extendedprice": np.full(n, 2 ** 40, np.int64)}
    # three columns fit 32 bits, the fourth does not
    assert peaks.q6_bytes(cols) == n * (4 + 4 + 4 + 8)
    share = peaks.roofline_share(819e9, 0.0, 2.0, "TPU v5 lite")
    assert share == {"share": 0.5, "bound": "hbm"}
