"""Each cell end to end on the CPU at SF0.01 (the mesh cell on four
virtual devices), and the refusals: no TPU, a wrong device count."""

import pytest

from helpers import run_cell

CELLS = [("tpch_sf1.scan", 1), ("tpch_sf1.join", 1),
         ("tpch_sf1_mesh4.mixed", 4)]


@pytest.mark.parametrize("workload,devices", CELLS)
def test_cell_rehearsal(workload, devices):
    rc, result, out = run_cell(workload, devices=devices)
    assert rc == 0, out[-3000:]
    assert result["rehearsal"] is True and result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == devices
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    counts = result["counts"]
    assert counts["compiles_in_window"] == 0
    assert counts["upload_bytes_in_window"] == 0
    assert counts["plan_hit_share"] == 100.0
    if devices == 4:
        # below 1 a statement fell back gateway-local
        assert counts["collectives_per_stmt"] >= 1.0


@pytest.mark.parametrize("workload,devices", CELLS)
def test_warmed_sets_share_one_program(workload, devices):
    """Only a class's first parameter set may build a plan: a set that
    compiles again would put a compile into every run's set-up (Q3's
    date and Q18's quantity do, which is why they are fixed)."""
    rc, result, out = run_cell(workload, devices=devices, seconds=1,
                               seed=9)
    assert rc == 0, out[-3000:]
    for cls, misses in result["new_plans"].items():
        assert sum(misses[1:]) == 0, (cls, misses)


def test_refuses_without_a_tpu():
    rc, result, out = run_cell("tpch_sf1.scan", rehearse=False)
    assert rc != 0 and result is None
    assert "refusing to measure" in out


def test_refuses_a_wrong_device_count():
    rc, result, out = run_cell("tpch_sf1_mesh4.mixed", devices=1)
    assert rc != 0 and result is None
    rc, result, out = run_cell("tpch_sf1.scan", devices=4)
    assert rc != 0 and result is None
