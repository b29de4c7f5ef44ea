"""The reduction of the program's spans and operator scopes:
arithmetic on made-up spans and ops, then the slice of tpch_sf1.scan
recorded on a v5e after the spans went in (fixtures/span_slice.*)."""

import json
import os

import pytest

import span_reduce as sr
import trace_reduce as tr
from helpers import BENCH


def span(name, b, e, *children, **tags):
    return {"n": name, "b": b, "e": e, "t": tags, "c": list(children)}


def served(sql, b, e, *children):
    return span("statement", b, e, *children, served=True)


SQL = {"select 6": "q6", "select 1": "q1"}
ROOT = served(
    "select 6", 0, 100,
    span("wire.queue", 0, 4),
    span("parse", 5, 7),
    span("select 6", 10, 80,
         span("admission", 10, 11),
         span("gate", 11, 13),
         span("plan", 15, 30, span("upload", 16, 20), span("compile", 20, 26)),
         span("dispatch", 30, 40, span("queue", 31, 34)),
         span("materialize", 42, 78,
              span("pull", 45, 70), span("decode", 70, 78))),
    span("encode", 82, 90),
    span("send", 90, 98))


def test_layer_self_times_add_up_to_the_root():
    ms = sr.layer_ms(ROOT)
    assert ms["wire_queue_ms"] == 4e-6
    assert ms["parse_plan_ms"] == (2 + 15 - 4 - 6) * 1e-6
    assert ms["gate_wait_ms"] == 3e-6
    assert ms["dispatch_host_ms"] == 7e-6
    assert ms["pull_wait_ms"] == 25e-6
    assert ms["decode_ms"] == (36 - 25) * 1e-6   # materialize less pull
    assert ms["encode_send_ms"] == 16e-6
    assert ms["outside_pull_ms"] == 75e-6
    layers = sum(ms[k] for k in sr.LAYERS)
    assert layers + ms["other_ms"] == pytest.approx(100e-6)
    # what no layer holds: upload, compile and queue are other layers'
    assert ms["other_ms"] == pytest.approx((100 - 73) * 1e-6)
    assert sr.classify(ROOT, SQL) == "q6"
    assert sr.classify(served("x", 0, 1, span("select  1;", 0, 1)),
                       SQL) == "q1"
    assert sr.classify(served("x", 0, 1), SQL) is None


def test_leaf_segments_tile_the_root():
    pieces: list = []
    sr.leaf_segments(ROOT, sr.WIRE_SELF, pieces)
    assert pieces[0] == (0, 4, "wire.queue")
    assert sum(b - a for a, b, _ in pieces) == 100
    assert all(a1 >= b0 for (_, b0, _), (a1, _, _)
               in zip(pieces, pieces[1:]))
    by = {}
    for a, b, label in pieces:
        by[label] = by.get(label, 0) + b - a
    assert by["pull"] == 25 and by["plan"] == 5 and by["upload"] == 4
    assert by[sr.WIRE_SELF] == 1 + 3 + 2 + 2  # the root's own time
    assert by["select 6"] == 2 + 2 + 2


def test_idle_goes_to_the_open_spans_and_adds_up():
    a = [(0, 10, "q6:plan"), (10, 30, "q6:pull")]
    b = [(5, 25, "q1:pull")]
    idle = [(2, 8), (20, 40)]
    got = sr.idle_by_span(idle, a + b)
    assert got == {"q6:plan": 3 + 1.5, "q1:pull": 1.5 + 2.5,
                   "q6:pull": 2.5 + 5, sr.NO_STATEMENT: 10}
    assert sum(got.values()) == sum(e - s for s, e in idle)
    assert sr.idle_by_span([(0, 5)], []) == {sr.NO_STATEMENT: 5}
    assert sr.complement([(2, 4), (6, 9)], 0, 10) == \
        [(0, 2), (4, 6), (9, 10)]


def test_scope_of_an_op_path():
    assert sr.scope_of("jit(fn)/sort.0/aggregate.1/operands/"
                       "concatenate:") == "aggregate.1/operands"
    assert sr.scope_of("jit(fn)/aggregate.0/kernel/shard_merge/psum:"
                       ) == "aggregate.0/shard_merge"
    assert sr.scope_of("jit(fn)/sort.0/aggregate.1/kernel/jit(large_group_"
                       "aggregate)/operands/stack:") == "aggregate.1/operands"
    assert sr.scope_of("jit(fn)/sort.0/aggregate.1/kernel/jit(large_group_"
                       "aggregate)/pallas_call:") == "aggregate.1/kernel"
    assert sr.scope_of("jit(fn)/limit.0/sort.1/hashjoin.2/probe/"
                       "jit(_take)/gather:") == "hashjoin.2/probe"
    assert sr.scope_of("jit(fn)/aggregate.0/filter.1/scan.2/and:"
                       ) == "scan.2"
    assert sr.scope_of("jit(_pack)/harness/concatenate:") == sr.HARNESS
    assert sr.scope_of("jit(fn)/gather:") == sr.UNSCOPED
    assert sr.scope_of(None) == sr.UNSCOPED


OPS = [(0, 10, 1), (2, 4, 2), (5, 9, 3), (20, 30, 4), (40, 45, 2)]
META = {1: {"tf_op": "jit(fn)/sort.0/while:"},
        2: {"tf_op": "jit(fn)/sort.0/aggregate.1/keys/add:"},
        3: {"tf_op": "jit(fn)/sort.0/aggregate.1/filter.2/scan.3/lt:"},
        4: {"tf_op": "jit(_pack)/harness/concatenate:"}}


def test_device_time_folds_by_innermost_operator():
    trace = {"devices": {0: OPS}, "ops": {0: META}}
    stmts = [("q6", 0, 12), ("q1", 15, 48)]
    got = sr.device_operators(trace, stmts, 0, 50)
    assert got == {"q6/sort.0": 4, "q6/aggregate.1/keys": 2,
                   "q6/scan.3": 4, "q1/harness": 10,
                   "q1/aggregate.1/keys": 5}
    assert sum(got.values()) == tr.busy_ns(
        [(a, b, "") for a, b, _ in OPS], 0, 50)


def test_reduce_on_made_up_capture():
    trace = {"devices": {0: OPS}, "ops": {0: META}, "sync": [],
             "host": [(1, "PjitFunction(fn)"), (16, "PjitFunction(fn)"),
                      (17, "DevicePut")]}
    seg = {"lo": 0, "hi": 50,
           "statements": [("q6", 0, 12), ("q1", 15, 48)]}
    roots = [served("select 6", 0, 12, span("select 6", 1, 11,
                                            span("pull", 2, 10))),
             served("select 1", 15, 48, span("select 1", 16, 47,
                                             span("pull", 18, 46)))]
    r = sr.reduce_capture(trace, 0, {"mix": seg, "single": seg}, roots,
                          SQL)
    assert r["per_class"]["q6"]["pull_wait_ms"] == 8e-6
    assert r["pull_wait_ms"] == (8e-6 + 28e-6) / 2
    assert r["host_stretch_x"] is None      # one slice: nothing to compare
    assert r["idle_s"] == 25e-9 == pytest.approx(r["idle_by_span_total_s"])
    idle = dict(r["idle_by_span"])
    assert idle[sr.NO_STATEMENT] == 5e-9    # 12-15 and 48-50
    assert idle["q1:pull"] == (2 + 10 + 1) * 1e-9
    assert set(idle) == {sr.NO_STATEMENT, "q1:pull", "q1:engine", "q1:wire",
                         "q6:engine", "q6:wire"}
    assert r["idle_no_stmt_share"] == 20.0
    assert r["op_attributed_share"] == 100.0
    assert r["op_share_aggregate"] == pytest.approx(100 * 7 / 25)
    assert r["op_share_scan_filter"] == pytest.approx(100 * 4 / 25)
    assert r["op_share_hashjoin"] == 0.0
    assert dict(r["host_events_per_stmt"]) == {"PjitFunction(fn)": 1.0,
                                              "DevicePut": 0.5}
    assert r["per_class"]["q1"]["client_ms"] == 33e-6
    assert r["per_class"]["q1"]["device_ms"] == 15e-6
    # a program that names no scope: the shares are left out
    bare = dict(trace, ops={0: {}})
    r = sr.reduce_capture(bare, 0, {"mix": seg, "single": seg}, roots, SQL)
    assert r["op_attributed_share"] is None
    assert r["op_share_aggregate"] is None
    # no device plane (a CPU rehearsal): spans only
    r = sr.reduce_capture(dict(trace, devices={}), 0,
                          {"mix": seg, "single": seg}, roots, SQL)
    assert r["pull_wait_ms"] and "idle_by_span" not in r


def test_counters_per_statement_and_a_program_without_them():
    ctx = {"counters": {"window": {"exec.dispatch.programs": 30}},
           "client": {"completed": 10}}
    assert sr.per_statement(ctx, ["exec.dispatch.programs"]) == 3.0
    assert sr.per_statement(ctx, ["exec.transfer.d2h.calls"]) is None


def test_the_reader_agrees_with_profile_data():
    """The old fixture (PR 22) through both readers: the same events on
    the same clock; its program names no operator."""
    path = os.path.join(BENCH, "fixtures", "scan_slice.xplane.pb")
    mine, theirs = sr.read_xplane(path), tr.read_xplane(path)
    assert mine["sync"] == theirs["sync"]
    assert [(a, b) for a, b, _ in mine["devices"][0]] == \
        [(a, b) for a, b, _ in theirs["devices"][0]]
    names = {m["name"] for m in mine["ops"][0].values()}
    assert {n for _, _, n in theirs["devices"][0]} <= names
    assert any(m.get("tf_op", "").startswith("jit(fn)/")
               for m in mine["ops"][0].values())
    assert {sr.scope_of(m.get("tf_op")) for m in mine["ops"][0].values()
            } == {sr.UNSCOPED}
    assert any(n.startswith("PjitFunction(") for _, n in mine["host"])


FIXTURE = os.path.join(BENCH, "fixtures", "span_slice.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "fixtures", "span_slice.json")) as f:
        rec = json.load(f)
    trace = sr.read_xplane(FIXTURE)
    offset = tr.clock_offset(trace["sync"], rec["marks"])
    return rec, trace, sr.reduce_capture(
        trace, offset, rec["segments"], rec["roots"], rec["class_of_sql"])


def test_recorded_spans(recorded):
    """tpch_sf1.scan, 0.4 s of four sessions and 0.4 s of one, recorded
    on a v5e with the collector on (PR 24)."""
    rec, _, r = recorded
    assert set(r["per_class"]) == {"q1", "q6"}
    for cls, c in r["per_class"].items():
        assert c["statements"] >= 3
        layers = sum(c[k] for k in sr.LAYERS)
        # medians of parts against the median of the whole
        assert layers + c["other_ms"] == pytest.approx(
            c["statement_ms"], rel=0.15)
        assert 0.0 < c["other_ms"] < c["statement_ms"] / 2
        # the served root sits inside the client's send..receive
        assert c["statement_ms"] < c["client_ms"]
        assert c["pull_wait_ms"] > c["device_ms"] * 0.5
    assert r["per_class"]["q1"]["pull_wait_ms"] > \
        r["per_class"]["q6"]["pull_wait_ms"]
    assert r["host_stretch_x"] > 0.5
    for root in rec["roots"]:
        assert root["t"]["served"] is True and root["t"]["fingerprint"]
        assert [c["n"] for c in root["c"]][:2] == ["wire.queue", "parse"]
    assert rec["expected"]["pull_wait_ms"] == pytest.approx(
        r["pull_wait_ms"])


def test_recorded_idle_rows_add_up(recorded):
    _, _, r = recorded
    assert 0.0 < r["idle_share"] < 1.0
    assert r["idle_by_span_total_s"] == pytest.approx(r["idle_s"],
                                                      rel=1e-9)
    rows = dict(r["idle_by_span"])
    assert all(":" in k or k == sr.NO_STATEMENT for k in rows)
    assert any(k.endswith(":pull") for k in rows)
    assert 0.0 <= r["idle_no_stmt_share"] <= 100.0


def test_recorded_operator_scopes(recorded):
    _, trace, r = recorded
    scopes = {sr.scope_of(m.get("tf_op"))
              for m in trace["ops"][0].values()}
    # (no op is a scan's own: XLA fuses the scan's masks into the
    # aggregate's fusions, which carry the aggregate's path)
    assert {"aggregate.1/operands", "aggregate.1/kernel",
            "aggregate.0/kernel", "sort.0", sr.HARNESS} <= scopes
    rows = dict(r["device_operators"])
    assert any(k.startswith("q1/aggregate.") for k in rows)
    assert sum(rows.values()) <= r["busy_single_s"] * (1 + 1e-9)
    assert 90.0 < r["op_share_aggregate"] <= r["op_attributed_share"] < 100.0
    assert r["op_share_hashjoin"] == 0.0
    assert r["op_attributed_share"] == pytest.approx(
        recorded[0]["expected"]["op_attributed_share"])
    # the large-G Pallas kernel is Q1's aggregate's `kernel` phase
    assert [sr.scope_of(m["tf_op"]) for m in trace["ops"][0].values()
            if tr.PALLAS_CALL in m["name"]] == ["aggregate.1/kernel"]
