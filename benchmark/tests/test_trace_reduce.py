"""The reduction from a trace to numbers: arithmetic on made-up ops,
then the recorded TPU trace kept in benchmark/fixtures."""

import json
import os

import pytest

import trace_reduce as tr
from helpers import BENCH

PALLAS = ('%large_group_aggregate.1 = (f32[7,512]{1,0:T(8,128)S(1)}, '
          's32[54,512]{1,0}) custom-call(s32[1,8388608]{1,0} %bitcast.975), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
OPS = [(0, 10, "while.1"), (2, 4, "fusion.1"), (5, 9, "fusion.2"),
       (6, 7, "copy.1"), (20, 30, PALLAS), (40, 45, "fusion.1")]


def test_busy_is_the_union_and_self_time_unnests():
    assert tr.busy_ns(OPS, 0, 50) == 25
    assert tr.busy_ns(OPS, 8, 22) == 2 + 2
    self_time = tr.self_time_by_name(OPS, 0, 50)
    assert self_time == {"while.1": 4, "fusion.1": 2 + 5, "fusion.2": 3,
                         "copy.1": 1, PALLAS: 10}
    assert sum(self_time.values()) == tr.busy_ns(OPS, 0, 50)


def test_short_names():
    assert tr.short_name(
        "%fusion.16 = s32[8388608]{0:T(1024)} fusion(s32[1500001]{0:T(1024)}"
        " %get-tuple-element.298), kind=kCustom, calls=%fused_computation.16"
    ) == "fusion.16 fusion s32[8388608]"
    assert tr.short_name("%copy-start = (s32[12]{0:T(128)S(1)}, u32[]{:S(2)})"
                         " copy-start(s32[12]{0} %x)"
                         ) == "copy-start copy-start s32[12]"
    assert tr.short_name("while.1") == "while.1"


def test_statement_attribution():
    a = tr.attribute_statement({0: OPS, 1: OPS[4:]}, 1, 35)
    assert a["before_first_device_op"] == 1     # op at 2, sent at 1
    assert a["after_last_device_op"] == 5       # last op ends at 30
    assert a["device_ns"] == (16 + 10) / 2      # mean over the chips
    idle = tr.attribute_statement({0: OPS}, 31, 39)
    assert idle["device_ns"] == 0 and idle["before_first_device_op"] == 8


def test_clock_offset_pairs_marks_in_order():
    assert tr.clock_offset([105, 205, 330], [5, 105, 205]) == 100
    with pytest.raises(ValueError):
        tr.clock_offset([1, 2], [1])


def test_reduce_on_made_up_segments():
    trace = {"devices": {0: OPS}, "sync": []}
    segs = {"mix": {"lo": 0, "hi": 50,
                    "statements": [("a", 0, 12), ("b", 15, 48)]},
            "single": {"lo": 0, "hi": 50,
                       "statements": [("a", 0, 12), ("b", 15, 48)]}}
    r = tr.reduce_trace(trace, 0, segs)
    assert r["busy_s"] == 25e-9 and r["window_s"] == 50e-9
    assert r["idle_share"] == 0.5 and r["device_ms_per_stmt"] == 12.5e-6
    assert r["custom_call_share"] == 10 / 25
    assert r["device_ops"][0] == [
        "large_group_aggregate.1 custom-call:tpu_custom_call f32[7,512]",
        10e-9]
    assert r["per_class"]["a"]["device_ms"] == 10e-6
    assert r["per_class"]["b"]["device_ms"] == 15e-6
    gaps = dict(r["idle_gaps"])
    assert gaps["no_statement_in_flight"] == 3e-9
    assert gaps["b:between_device_ops"] == 10e-9
    assert gaps["b:after_last_device_op"] == 3e-9
    with pytest.raises(tr.NoDevicePlane):
        tr.reduce_trace({"devices": {}, "sync": []}, 0, segs)


FIXTURE = os.path.join(BENCH, "fixtures", "scan_slice.xplane.pb")


def test_recorded_tpu_trace():
    """A slice of tpch_sf1.scan recorded on a v5e (PR 22), with the
    segments the run wrote beside it."""
    with open(os.path.join(BENCH, "fixtures", "scan_slice.json")) as f:
        rec = json.load(f)
    trace = tr.read_xplane(FIXTURE)
    assert sorted(trace["devices"]) == [0]
    assert len(trace["sync"]) == len(rec["marks"])
    offset = tr.clock_offset(trace["sync"], rec["marks"])
    r = tr.reduce_trace(trace, offset, rec["segments"])
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert 0.0 < r["idle_share"] < 1.0
    assert set(r["per_class"]) == {"q1", "q6"}
    for c in r["per_class"].values():
        assert 0.0 < c["device_ms"] < 100.0 and c["tail_host_ms"] > 0.0
    assert r["per_class"]["q1"]["device_ms"] > \
        r["per_class"]["q6"]["device_ms"]
    assert 0.0 < r["custom_call_share"] < 1.0  # Q1's Pallas kernel
    assert r["device_ops"] and r["idle_gaps"]
    assert rec["expected"]["busy_s"] == pytest.approx(r["busy_s"])
    assert rec["expected"]["per_class"]["q6"]["device_ms"] == \
        pytest.approx(r["per_class"]["q6"]["device_ms"])
