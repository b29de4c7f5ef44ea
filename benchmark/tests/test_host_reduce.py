"""The reduction of span CPU and stage marks (host_reduce.py):
arithmetic on made-up roots, the readers of what the harness holds,
then a slice of tpch_sf1.scan recorded on a v5e with four sessions
(fixtures/host_roots.json)."""

import json
import os

import pytest

import host_reduce as hr
import span_reduce as sr
from helpers import BENCH


def span(name, b, e, cpu, *children, stages=(), **tags):
    s = {"n": name, "b": b, "e": e, "t": tags, "c": list(children)}
    if cpu is not None:
        s["u"] = cpu
    if stages:
        s["g"] = [list(m) for m in stages]
    return s


SQL = {"select 6": "q6", "select 1": "q1"}


def root(sql, shift=0, slow=0):
    """A served root of 100 + slow ns: 4 in the frame queue, the
    statement 70 + slow, its `plan` 15 with two marks and an `upload`
    beneath the second, its `dispatch` 10 + slow whose `call` another
    thread ran for 5."""
    e = 100 + slow
    r = span(
        "statement", 0, e, 50,
        span("wire.queue", 0, 4, None),
        span("parse", 5, 7, 2),
        span(sql, 10, 80 + slow, 40,
             span("gate", 11, 13, 0),
             span("plan", 15, 30, 14,
                  span("upload", 22, 26, 3),
                  stages=[("build", 16, 1, 0), ("tables", 20, 5, 0)]),
             span("dispatch", 30, 40 + slow, 2,
                  span("queue", 32, 34, None),
                  stages=[("args", 30, 0, 0), ("call", 31, 1, 5)],
                  call_thread="mesh-dispatch-0", call_b=34,
                  call_e=39 + slow),
             span("materialize", 42 + slow, 78 + slow, 12,
                  span("pull", 45 + slow, 70 + slow, 4),
                  span("decode", 70 + slow, 78 + slow, 7),
                  stages=[("flags", 42 + slow, 0, 0)]),
             stages=[("select", 13, 2, 0)]),
        span("send", 90 + slow, 98 + slow, 3),
        stages=[("setup", 8, 3, 0), ("account", 81 + slow, 44, 0)],
        served=True)

    def move(s):
        s["b"] += shift
        s["e"] += shift
        for m in s.get("g", []):
            m[1] += shift
        for k in ("call_b", "call_e"):
            if k in s["t"]:
                s["t"][k] += shift
        for c in s["c"]:
            move(c)
    move(r)
    return r


def test_one_roots_fold_adds_up():
    rows = hr.fold_root(root("select 6"))
    assert set(k for k in rows if "/" not in k) == {
        "wire", "wire.queue", "parse", "engine", "gate", "plan", "upload",
        "dispatch", "queue", "materialize", "pull", "decode", "send"}
    spans = {k: v for k, v in rows.items() if "/" not in k}
    # the self walls tile the root; the self CPUs add up to its CPU
    assert sum(v["self_wall"] for v in spans.values()) == 100
    assert sum(v["self_cpu"] for v in spans.values()) == 50
    assert rows["plan"] == {"wall": 15, "cpu": 14, "self_wall": 11,
                            "self_cpu": 11, "other_cpu": 0,
                            "other_wall": 0}
    # a stage runs to the next mark or the close and owns the self time
    assert rows["plan/(head)"]["self_wall"] == 1
    assert rows["plan/build"] == {"wall": 4, "cpu": 4, "self_wall": 4,
                                  "self_cpu": 4, "other_cpu": 0,
                                  "other_wall": 0}
    assert rows["plan/tables"] == {"wall": 10, "cpu": 9, "self_wall": 6,
                                   "self_cpu": 6, "other_cpu": 0,
                                   "other_wall": 0}
    for label in ("plan", "dispatch", "materialize", "engine", "wire"):
        for f in ("self_wall", "self_cpu", "other_cpu", "other_wall"):
            assert sum(v[f] for k, v in rows.items()
                       if k.startswith(label + "/")) == rows[label][f], \
                (label, f)
    # the call another thread ran: its CPU is the stage's, not a wait
    call = rows["dispatch/call"]
    assert (call["self_wall"], call["self_cpu"], call["other_cpu"]) == \
        (7, 1, 5)
    # it ran there for 5 of the stage's 7: the rest is the hand-off
    assert call["other_wall"] == 5 and rows["dispatch/args"][
        "other_wall"] == 0
    assert hr.off_cpu(call) == 1 and hr.off_cpu(rows["dispatch"]) == 1
    # a wait stamped elsewhere has no CPU: all of it is off the processor
    assert hr.off_cpu(rows["wire.queue"]) == 4
    assert hr.off_cpu(rows["pull"]) == 21
    # the root's own marks: `account` runs to the close, less `send`
    assert rows["wire/account"]["self_wall"] == 19 - 8
    assert rows["wire/setup"]["self_wall"] == 2 + 1   # around the span
    t = hr.root_totals(rows)
    assert t["statement"] == 100 and t["cpu"] == 55
    assert t["waits"] == 4 + 2 + 2 + 21
    assert t["cpu"] + t["waits"] + t["host_offcpu"] == 100


def test_a_root_without_marks_or_cpu_still_folds():
    """What the parent's collector records: b / e / c alone."""
    old = root("select 6")

    def strip(s):
        s.pop("u", None)
        s.pop("g", None)
        for c in s["c"]:
            strip(c)
    strip(old)
    rows = hr.fold_root(old)
    assert not [k for k in rows if "/" in k]
    assert sum(v["self_wall"] for v in rows.values()) == 100
    assert sr.layer_ms(old) == sr.layer_ms(root("select 6"))


def test_reduce_is_a_class_then_the_mean_over_classes():
    roots = [root("select 6", 0), root("select 6", 200, slow=6),
             root("select 6", 400, slow=30), root("select 1", 600),
             root("other", 800)]          # of no class: left out
    roots[-1]["c"][2]["n"] = "other"
    r = hr.reduce_roots(roots, SQL, 0, 10_000)
    assert r["roots"] == 4 and r["statements"] == {"q6": 3, "q1": 1}
    ms = 1e-6
    assert r["spans"]["dispatch"]["wall"] == pytest.approx(
        ((10 + 6) + 10) / 2 * ms)
    assert r["stages"]["dispatch/call"]["other_cpu"] == pytest.approx(
        5 * ms)
    # a wall is a class's median, anything of a CPU reading its mean
    assert r["spans"]["dispatch"]["self_wall"] == pytest.approx(
        ((8 + 6) + 8) / 2 * ms)
    assert r["spans"]["dispatch"]["self_cpu"] == pytest.approx(2 * ms)
    t = r["totals"]
    assert t["statement_median_ms"] == pytest.approx((106 + 100) / 2 * ms)
    assert t["statement_ms"] == pytest.approx(
        ((100 + 106 + 130) / 3 + 100) / 2 * ms)
    assert t["cpu_ms"] == pytest.approx(55 * ms)
    assert t["waits_ms"] == pytest.approx(29 * ms)
    # the slow calls are time off the processor in `dispatch`
    assert t["host_offcpu_ms"] == pytest.approx(
        ((16 + 22 + 46) / 3 + 16) / 2 * ms)
    assert r["host_offcpu_ms"] == t["host_offcpu_ms"]
    assert r["accounted_share"] == pytest.approx(100.0)
    assert r["cpu_tick_ms"] == 1e-6     # readings of 2, 3, 4 ... ns
    assert list(r["offcpu_by_span"])[0] == "pull"
    assert r["offcpu_by_span"]["dispatch"] == pytest.approx(
        ((1 + 7 + 31) / 3 + 1) / 2 * ms)
    assert r["slowest"]["e"] - r["slowest"]["b"] == 130
    # the slice's bounds: a root belongs where it closed
    assert hr.reduce_roots(roots, SQL, 0, 320)["roots"] == 2
    assert hr.reduce_roots(roots, SQL, 9_000, 9_001) == {
        "roots": 0, "statements": {}}
    # what the metric files read
    assert hr.stage(r, "build", "cpu") == pytest.approx(4 * ms)
    assert hr.stage(r, "call", "cpu") == pytest.approx((1 + 5) * ms)
    assert hr.stage(r, "flags", "self_wall") == pytest.approx(3 * ms)
    assert hr.stage(r, "no_such_stage", "cpu") is None


THREADS = {"reactor": 0.5, "workers": 6.0, "mesh_dispatch": 0.25,
           "other": 0.25}


def _ctx(window, completed=2000):
    return {"counters": {"window": window},
            "client": {"completed": completed}}


def test_window_cpu_and_a_program_without_the_counters(capsys):
    window = {"process.threads.cpu.seconds." + g: v
              for g, v in THREADS.items()}
    window.update({"process.cpu.seconds": 9.0,
                   "process.wall.seconds": 40.0,
                   "process.gc.pause.seconds.gen2.count": 2,
                   "process.gc.pause.seconds.gen2.sum": 0.5})
    cpu = hr.window_cpu(_ctx(window))
    assert cpu["threads"] == 7.0 and cpu["runtime"] == 2.0
    assert cpu["gc_gen2"] == [2, 0.5] and cpu["gc_gen0"] == [0, 0.0]
    assert "# host_process " in capsys.readouterr().out
    assert hr.window_cpu(_ctx(window)) == cpu
    assert capsys.readouterr().out == ""        # printed once a run
    from refworker import load_module
    ctx = _ctx(window)
    assert load_module("layer_metrics", "host_cpu_ms_per_stmt").read(
        ctx) == 3.5
    assert load_module("layer_metrics", "runtime_cpu_ms_per_stmt").read(
        ctx) == 1.0
    assert load_module("layer_metrics", "gc_gen2_pause_ms").read(
        ctx) == 500.0
    # the parent: no `process.*` counter, every reader leaves its metric out
    old = _ctx({"sql.select.count": 2000})
    assert hr.window_cpu(old) is None
    for name in ("host_cpu_ms_per_stmt", "runtime_cpu_ms_per_stmt",
                 "gc_gen2_pause_ms"):
        assert load_module("layer_metrics", name).read(old) is None
    import run
    with open(os.path.join(BENCH, "layer_metrics",
                           "interpreter_busy_share.json")) as f:
        spec = json.load(f)
    assert run.read_counter_metric(spec, ctx) == 100.0 * 7.0 / 40.0
    assert run.read_counter_metric(spec, old) is None


def test_idle_share_folds_the_printed_rows_by_label(monkeypatch):
    reduced = {"idle_s": 2.0, "idle_by_span": [
        ["q1:pull", 0.8], ["q6:plan", 0.3], ["q1:plan", 0.2],
        ["q6:engine", 0.1], [sr.NO_STATEMENT, 0.4]]}
    monkeypatch.setattr(sr, "_captured", {"reduced": reduced})
    assert hr.idle_share({}, "plan") == pytest.approx(25.0)
    assert hr.idle_share({}, "engine") == pytest.approx(5.0)
    assert hr.idle_share({}, "dispatch") == 0.0
    monkeypatch.setattr(sr, "_captured", {"reduced": None})
    assert hr.idle_share({}, "plan") is None


def test_no_capture_outside_a_traced_run(monkeypatch):
    monkeypatch.setattr(hr, "_captured", {})
    assert hr.capture({"trace": None}) is None
    assert hr.metric({"trace": None}, "host_offcpu_ms") is None
    assert hr.stage_ms({"trace": None}, "build", "cpu") is None


# -- the recorded slice ------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "fixtures", "host_roots.json")) as f:
        rec = json.load(f)
    return rec, hr.reduce_roots(rec["roots"], rec["class_of_sql"],
                                rec["lo"], rec["hi"])


def test_recorded_roots_reduce_to_what_the_chip_run_printed(recorded):
    """tpch_sf1.scan, 0.2 s of four sessions on a v5e, collector on,
    no profiler (PR 38; tests/record_host_fixture.py)."""
    rec, r = recorded
    got = json.loads(json.dumps(r))
    assert got == rec["expected"]
    assert set(r["statements"]) == {"q1", "q6"} and r["roots"] >= 8


def test_recorded_roots_account_for_their_statements(recorded):
    rec, r = recorded
    for raw in rec["roots"]:
        rows = hr.fold_root(raw)
        t = hr.root_totals(rows)
        assert t["cpu"] + t["waits"] + t["host_offcpu"] == t["statement"]
        assert all(v["self_wall"] >= 0 for v in rows.values())
        # the host's CPU clock ticks: one span's reading is 0 or a
        # multiple of 10 ms, whatever its wall time (host_reduce takes
        # means of these, never a median)
        assert all(v["cpu"] % 10_000_000 == 0 for v in rows.values())
    assert r["accounted_share"] == pytest.approx(100.0)
    for s in ("plan/build", "plan/key", "plan/fingerprint", "plan/lookup",
              "dispatch/call", "materialize/flags", "wire/setup",
              "wire/account", "engine/unwind"):
        assert r["stages"][s]["self_wall"] > 0, s
    # four sessions behind one interpreter: the threads wait
    assert r["totals"]["host_offcpu_ms"] > r["totals"]["cpu_ms"] * 0.2
    # the host's CPU clock ticks: a reading is a multiple of 10 ms
    assert r["cpu_tick_ms"] == 10.0
    assert r["stages"]["dispatch/call"]["other_cpu"] == 0   # one chip
    # what is left unnamed in the staged spans is small
    for label in ("plan", "dispatch", "materialize", "engine"):
        assert r["stages"][f"{label}/(head)"]["self_wall"] < 0.5
