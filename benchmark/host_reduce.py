"""The statement's host time, all of it: the program's spans with the
CPU their threads spent and the stage marks inside them, and the
process's own counters of CPU and collector pauses, reduced to numbers.

Since PR 38 a span carries `u`, the CPU its thread spent while it was
open (`time.thread_time_ns()`), and `g`, the stage marks that cut its
own time (`[name, monotonic_ns, CPU since the span opened, CPU another
thread spent for the stage]`; cockroach_tpu/utils/tracing.py). A span's
wall time less its CPU is the time its thread was off the processor:
inside the five named waits (WAITS) that is the wait, anywhere else the
interpreter lock or the OS.

- `capture(ctx)` runs, once a traced run, one more slice of the cell's
  own mix (all its sessions, MIX_S) with the collector on, asked to
  read the CPU clock (`start_collector(cpu=True)`: span_reduce.py's
  slices do not ask, so what they read costs what it did), and **no
  profiler** (theirs run under one, which is host work of its own),
  from the plan the harness left in benchmark/out/<cell>/. A program
  whose spans carry no CPU (the parent of the PR that added it) gives
  None, and every metric here is then left out.
- `fold_root` is arithmetic on one served root: per span label (as
  span_reduce.idle_by_span labels them) and per `label/stage`, wall,
  CPU, self wall and self CPU; a stage runs from its mark to the next
  mark or the span's close and owns the span's self time in between,
  `(head)` what lies before the first mark. The self walls of one root
  add up to its wall time, and each is self CPU + CPU credited from
  another thread + time off the processor.
- `reduce_roots` takes, a class, the median of every wall time and the
  **mean** of everything a CPU reading is part of, then the mean over
  classes. The mean, because a CPU clock may tick: on the host the
  benchmark runs on `thread_time_ns()` moves in steps of 10 ms
  (`cpu_tick_ms` on the printed line says what the slice saw), so one
  span's reading is 0 or 10 ms and only the mean over many statements
  is its CPU; and because a wait for the interpreter lock is a few long
  waits, not many short ones. It prints what the result line cannot
  carry on a line of its own, `# host_reduced {...}`: every span and
  stage, the time off the processor by span, and the slowest root of
  the slice whole.
- `window_cpu` / `idle_share` read what the harness already holds: the
  `process.*` counters over the window, and span_reduce's
  `idle_by_span`.
"""

from __future__ import annotations

import json
import math
import os
import statistics
import time

import span_reduce

MIX_S = 4.0      # as run.py's and span_reduce.py's mix slices
KEEP = False     # tests/record_host_fixture.py: leave the roots behind
WAITS = ("pull", "queue", "gate", "admission", "wire.queue")
HEAD = "(head)"
THREADS = ["process.threads.cpu.seconds." + g
           for g in ("reactor", "workers", "mesh_dispatch", "other")]
FIELDS = ("wall", "cpu", "self_wall", "self_cpu", "other_cpu",
          "other_wall")
CPU_FIELDS = ("cpu", "self_cpu", "other_cpu")   # means; the rest medians


# -- one root ----------------------------------------------------------------

def _label(s: dict, depth: int) -> str:
    if depth == 0:
        return span_reduce.WIRE_SELF
    return s["n"] if s["n"] in span_reduce.SPAN_NAMES \
        else span_reduce.ENGINE_SELF


def _add(rows: dict, key: str, **ns) -> None:
    row = rows.setdefault(key, dict.fromkeys(FIELDS, 0))
    for k, v in ns.items():
        row[k] += v


def _overlap(a: int, b: int, c: dict) -> int:
    return max(0, min(b, c["e"]) - max(a, c["b"]))


def fold_root(root: dict) -> dict:
    """{label or "label/stage": {wall, cpu, self_wall, self_cpu,
    other_cpu, other_wall}} of one root in wire form, in ns. Spans of
    one label add up; so do stages of one name in one label.
    `other_wall` is how long another thread ran the stage's work (the
    mesh dispatcher's call: the span's `call_b` / `call_e` tags): the
    stage's self wall less it is the hand-off between the threads."""
    rows: dict = {}

    def walk(s: dict, depth: int) -> None:
        label = _label(s, depth)
        wall, cpu = s["e"] - s["b"], s.get("u") or 0
        kids = s["c"]
        _add(rows, label, wall=wall, cpu=cpu,
             self_wall=wall - sum(c["e"] - c["b"] for c in kids),
             self_cpu=cpu - sum(c.get("u") or 0 for c in kids))
        marks = s.get("g") or []
        ran_b, ran_e = s["t"].get("call_b", 0), s["t"].get("call_e", 0)
        if ran_e > ran_b:
            _add(rows, label, other_wall=ran_e - ran_b)
            open_ = [m[0] for m in marks if m[1] <= ran_b]
            if marks:
                _add(rows, f"{label}/{open_[-1] if open_ else HEAD}",
                     other_wall=ran_e - ran_b)
        if marks:
            # [name, start, end, the thread's CPU at start and at end]
            cuts = [[HEAD, s["b"], marks[0][1], 0, marks[0][2], 0]]
            for m, nxt in zip(marks, marks[1:] + [[None, s["e"], cpu, 0]]):
                cuts.append([m[0], m[1], nxt[1], m[2], nxt[2], m[3]])
            for name, a, b, c0, c1, other in cuts:
                inside = [c for c in kids if a <= c["b"] < b]
                _add(rows, f"{label}/{name}", wall=b - a, cpu=c1 - c0,
                     self_wall=b - a - sum(_overlap(a, b, c) for c in kids),
                     self_cpu=c1 - c0 - sum(c.get("u") or 0
                                            for c in inside),
                     other_cpu=other)
                _add(rows, label, other_cpu=other)
        for c in kids:
            walk(c, depth + 1)

    walk(root, 0)
    return rows


def off_cpu(row: dict) -> int:
    """Of a span's or stage's own time, what no thread's CPU covers."""
    return row["self_wall"] - row["self_cpu"] - row["other_cpu"]


# -- the slice ---------------------------------------------------------------

def _ms(ns: float) -> float:
    return ns / 1e6


def root_totals(rows: dict) -> dict:
    """One root's fold summed to what accounts for its wall time, ns:
    `cpu` (every span's own CPU and what other threads spent for it),
    `waits` (the time off the processor inside the five named waits),
    `host_offcpu` (off the processor anywhere else), `statement`."""
    spans = {k: v for k, v in rows.items() if "/" not in k}
    return {
        "statement": spans[span_reduce.WIRE_SELF]["wall"],
        "cpu": sum(v["self_cpu"] + v["other_cpu"] for v in spans.values()),
        "waits": sum(off_cpu(v) for k, v in spans.items() if k in WAITS),
        "host_offcpu": sum(off_cpu(v) for k, v in spans.items()
                           if k not in WAITS)}


def _over_classes(by_class: dict, pick, how) -> float:
    return statistics.fmean(how(pick(x) for x in xs)
                            for xs in by_class.values())


def cpu_tick_ns(roots: list) -> int:
    """The step the CPU clock moved in, as far as the roots show: the
    greatest common divisor of every span's reading (1 on a clock that
    counts nanoseconds, 10,000,000 on one that ticks at 100 Hz)."""
    tick = 0

    def walk(s: dict) -> None:
        nonlocal tick
        tick = math.gcd(tick, s.get("u") or 0)
        for c in s["c"]:
            walk(c)
    for r in roots:
        walk(r)
    return tick


def reduce_roots(roots: list, class_of_sql: dict, lo: int, hi: int) -> dict:
    """The fold of every served root that closed in [lo, hi), ms.
    `spans` and `stages`: a class's median of the walls and mean of the
    CPU fields (CPU_FIELDS), then the mean over classes. `totals`: a
    root's sums (root_totals), a class's mean, mean over classes: they
    add up to the statement exactly (`accounted_share`); the median
    statement is beside them. `offcpu_by_span` is means too."""
    by_class: dict = {}
    slowest = None
    kept = []
    for r in roots:
        cls = span_reduce.classify(r, class_of_sql)
        if cls is None or not lo <= r["e"] < hi:
            continue
        kept.append(r)
        by_class.setdefault(cls, []).append(fold_root(r))
        if slowest is None or r["e"] - r["b"] > \
                slowest["e"] - slowest["b"]:
            slowest = r
    out: dict = {"roots": len(kept),
                 "statements": {c: len(v) for c, v in by_class.items()}}
    if not by_class:
        return out
    out["cpu_tick_ms"] = _ms(cpu_tick_ns(kept))
    zero = dict.fromkeys(FIELDS, 0)
    keys = sorted({k for folds in by_class.values() for f in folds
                   for k in f})
    table = {k: {f: _ms(_over_classes(
        by_class, lambda x, k=k, f=f: x.get(k, zero)[f],
        statistics.fmean if f in CPU_FIELDS else statistics.median))
        for f in FIELDS} for k in keys}
    out["spans"] = {k: v for k, v in table.items() if "/" not in k}
    out["stages"] = {k: v for k, v in table.items() if "/" in k}
    totals = {c: [root_totals(f) for f in folds]
              for c, folds in by_class.items()}
    out["totals"] = {f"{k}_ms": _ms(_over_classes(
        totals, lambda x, k=k: x[k], statistics.fmean))
        for k in ("statement", "cpu", "waits", "host_offcpu")}
    t = out["totals"]
    t["statement_median_ms"] = _ms(_over_classes(
        totals, lambda x: x["statement"], statistics.median))
    out["accounted_share"] = 100.0 * (
        t["cpu_ms"] + t["waits_ms"] + t["host_offcpu_ms"]) \
        / t["statement_ms"]
    out["host_offcpu_ms"] = t["host_offcpu_ms"]
    offcpu = {k: _ms(_over_classes(
        by_class, lambda x, k=k: off_cpu(x.get(k, zero)),
        statistics.fmean)) for k in out["spans"]}
    out["offcpu_by_span"] = dict(sorted(offcpu.items(),
                                        key=lambda kv: -kv[1]))
    out["slowest"] = slowest
    return out


def stage(reduced: dict, name: str, field: str):
    """A stage's `field` summed over the spans that carry a mark of
    that name; None where none does."""
    hits = [v for k, v in reduced.get("stages", {}).items()
            if k.split("/", 1)[1] == name]
    if not hits:
        return None
    if field == "cpu":      # whichever thread spent it
        return sum(v["self_cpu"] + v["other_cpu"] for v in hits)
    return sum(v[field] for v in hits)


# -- the capture -------------------------------------------------------------

_captured: dict = {}


def capture(ctx: dict):
    """The reduced slice of this run, made on first use; None where
    the program's spans carry no CPU, the harness left no plan, or the
    run is not a traced one."""
    if "reduced" not in _captured:
        _captured["reduced"] = None
        if ctx.get("trace") is not None:
            try:
                _captured["reduced"] = _capture(ctx)
            except Exception as e:  # noqa: BLE001 — a metric is left
                print(f"# host_reduce: no capture: {e!r}",  # out, the
                      flush=True)                           # run holds
    return _captured["reduced"]


def _capture(ctx: dict):
    from cockroach_tpu.utils import tracing
    if not hasattr(tracing, "stage"):
        return None
    plan_path = span_reduce._newest("trace_single_plan.json")
    if plan_path is None:
        return None
    with open(plan_path) as f:
        base = json.load(f)
    mix = ctx["mix"]
    if base["mix"]["classes"] != mix["classes"]:
        return None

    import run as harness

    names = [c["name"] for c in mix["classes"]]
    class_of_sql = {span_reduce._sql_key(st["sql"]): names[ci]
                    for ci, sets in enumerate(base["statements"])
                    for st in sets}
    tracing.start_collector(cpu=True)
    try:
        start_ns = time.monotonic_ns() + int(harness.START_DELAY_S * 1e9)
        plan = dict(base, mix=mix, start_ns=start_ns,
                    end_ns=start_ns + int(MIX_S * 1e9))
        harness.run_sessions(os.path.dirname(plan_path), "host_mix",
                             plan, int(mix["sessions"]))
        time.sleep(0.05)    # a root closes after its reply is flushed
    finally:
        roots = [tracing.span_to_wire(r)
                 for r in tracing.stop_collector()
                 if r.tags.get("served")]
    reduced = reduce_roots(roots, class_of_sql, start_ns, plan["end_ns"])
    if KEEP:
        with open(os.path.join(os.path.dirname(plan_path),
                               "host_roots.json"), "w") as f:
            json.dump({"roots": roots, "class_of_sql": class_of_sql,
                       "lo": start_ns, "hi": plan["end_ns"],
                       "expected": reduced}, f)
    reduced["slice_s"] = MIX_S
    reduced["sessions"] = int(mix["sessions"])
    print("# host_reduced " + json.dumps(reduced), flush=True)
    return reduced


# -- what layer_metrics/*.py call --------------------------------------------

def metric(ctx: dict, name: str):
    reduced = capture(ctx)
    return None if reduced is None else reduced.get(name)


def stage_ms(ctx: dict, name: str, field: str):
    """A stage's reading in the slice. Its CPU only where the clock
    can tell: where the slice saw it move in steps of a millisecond or
    more, a stage of a tenth of that reads 0 or a whole step, and a
    session's period beats against the step, so not even the mean over
    a slice holds (PERF.md, PR 38); the stage's own wall time stands in
    there, which is its CPU when one session has the interpreter and
    holds the waits for it when several do."""
    reduced = capture(ctx)
    if reduced is None:
        return None
    if field == "cpu" and reduced.get("cpu_tick_ms", 0) >= 1.0:
        field = "self_wall"
    return stage(reduced, name, field)


def window_cpu(ctx: dict) -> dict | None:
    """The process's CPU over the window by who spent it, seconds:
    `threads` (the Python threads: reactor, workers, mesh dispatchers,
    the rest), `runtime` (the process less those: the runtime's native
    threads) and the collector's pauses; None on a program without
    the counters. Printed once, on `# host_process {...}`."""
    window = ctx["counters"]["window"]
    if any(c not in window for c in THREADS + ["process.cpu.seconds"]):
        return None
    out = {c.rsplit(".", 1)[1]: window[c] for c in THREADS}
    out["threads"] = sum(window[c] for c in THREADS)
    out["runtime"] = window["process.cpu.seconds"] - out["threads"]
    out["wall"] = window.get("process.wall.seconds")
    for gen in (0, 1, 2):
        h = f"process.gc.pause.seconds.gen{gen}"
        out[f"gc_gen{gen}"] = [window.get(h + ".count", 0),
                               window.get(h + ".sum", 0.0)]
    out["statements"] = ctx["client"]["completed"]
    if "process" not in _captured:
        _captured["process"] = True
        print("# host_process " + json.dumps(out), flush=True)
    return out


def idle_share(ctx: dict, label: str):
    """The share of the mix slice's device idle time in which a span
    of `label` was the innermost open one, over the classes: of the
    rows span_reduce prints (its sixteen largest), so a lower bound."""
    rows = span_reduce.metric(ctx, "idle_by_span")
    idle_s = span_reduce.metric(ctx, "idle_s")
    if rows is None or not idle_s:
        return None
    return 100.0 * sum(v for k, v in rows
                       if k.rsplit(":", 1)[-1] == label) / idle_s
