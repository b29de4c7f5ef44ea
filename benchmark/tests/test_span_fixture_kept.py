"""The slice of tpch_sf1.scan recorded in PR 24 (fixtures/span_slice.*)
still reduces to what that chip run printed, and the keys PR 38 put
beside `b` / `e` on a span's wire form move no reading of
span_reduce.py, which knows neither."""

import json
import os

import pytest

import span_reduce as sr
import trace_reduce as tr
from helpers import BENCH

FIXTURE = os.path.join(BENCH, "fixtures", "span_slice.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "fixtures", "span_slice.json")) as f:
        rec = json.load(f)
    trace = sr.read_xplane(FIXTURE)
    offset = tr.clock_offset(trace["sync"], rec["marks"])
    return rec, trace, sr.reduce_capture(
        trace, offset, rec["segments"], rec["roots"], rec["class_of_sql"])


EXPECTED_KEYS = ["roots", "per_class", "per_class_mix", "wire_queue_ms",
                 "parse_plan_ms", "gate_wait_ms", "dispatch_host_ms",
                 "pull_wait_ms", "decode_ms", "encode_send_ms", "other_ms",
                 "host_stretch_x", "idle_s", "idle_share", "idle_by_span",
                 "idle_no_stmt_share", "device_operators",
                 "op_attributed_share", "op_share_aggregate",
                 "host_events_per_stmt"]


@pytest.mark.parametrize("key", EXPECTED_KEYS)
def test_recorded_slice_reduces_to_its_expected_block(recorded, key):
    """Every reading the chip run printed, from the same roots today."""
    rec, _, r = recorded
    got = json.loads(json.dumps(r[key]))
    if isinstance(got, (dict, list)):
        assert got == rec["expected"][key]
    else:
        assert got == pytest.approx(rec["expected"][key])


def test_new_wire_keys_move_no_old_reading(recorded):
    """The recorded roots with a CPU reading and stage marks put on
    every span, as today's program ships them: every layer, label and
    idle row reads as before."""
    rec, trace, r = recorded

    def dress(s):
        return dict(s, u=(s["e"] - s["b"]) // 2,
                    g=[["a", s["b"], 0, 0],
                       ["b", (s["b"] + s["e"]) // 2, 1, 3]],
                    c=[dress(c) for c in s["c"]])
    offset = tr.clock_offset(trace["sync"], rec["marks"])
    again = sr.reduce_capture(trace, offset, rec["segments"],
                              [dress(x) for x in rec["roots"]],
                              rec["class_of_sql"])
    assert again == r
