"""The program's own spans and operator scopes, reduced to numbers.

The program records one span tree a served statement (frame picked up
to ReadyForQuery flushed; cockroach_tpu/utils/tracing.py, PERF.md
section 3) while its process-wide collector is on, and names every HLO
op by its plan operator (`jit(fn)/aggregate.0/filter.1/scan.2/...`, the
`tf_op` stat of the op's metadata in the profile). This module
turns both into the per-layer metrics of layer_metrics/*.py:

- `capture(ctx)` runs, once a traced run, a profiler slice of its own
  after the harness's (run.py hands a metric only `ctx`, and deletes
  its trace before any metric is read): the cell's traffic for MIX_S
  and one session for SINGLE_S, with the collector on, from the plans
  the harness left in benchmark/out/<cell>/. A program without the
  collector (the parent of the PR that added it) gives None, and every
  metric here is then left out.
- `read_xplane` is a reader of the .xplane.pb wire format that reaches
  what `jax.profiler.ProfileData` does not: an event's metadata id,
  and the metadata's stats (`tf_op`). Times are nanoseconds on the
  trace's clock, as in trace_reduce.py; spans are CLOCK_MONOTONIC and
  are shifted by the offset of the `bench_sync` marks.
- `reduce_capture` is arithmetic: a layer's self time is its span less
  what its children cover; device idle time in the mix slice is put
  down to the innermost span open in the server at that instant,
  split equally among statements, `no_statement_open` when none is, so
  the rows add up to the slice's idle time; device self time is folded
  by the op's innermost operator scope.

The two breakdowns the result line cannot carry (run.py builds it) are
printed on a line of their own, `# span_reduced {...}`.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import shutil
import statistics
import time

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
MIX_S = 4.0      # as run.py's own slices
SINGLE_S = 6.0
KEEP = False     # tests/record_span_fixture.py: leave the trace behind
TOP = 16

OPERATOR = re.compile(r"^(scan|filter|project|hashjoin|compact|aggregate|"
                      r"window|sort|limit)\.\d+$")
PHASES = {"build", "probe", "expand", "keys", "operands", "kernel",
          "finalize", "shard_merge"}
HARNESS = "harness"
UNSCOPED = "unscoped"
NO_STATEMENT = "no_statement_open"
# a served tree's two spans without a fixed name, as idle_by_span
# labels them: the root's own time is the wire front end's, the time of
# the span the engine names by the statement's text is the engine's
WIRE_SELF, ENGINE_SELF = "wire", "engine"
SPAN_NAMES = {"wire.queue", "parse", "admission", "gate", "plan", "upload",
              "compile", "dispatch", "queue", "materialize", "pull",
              "decode", "encode", "send"}
LAYERS = ("wire_queue_ms", "parse_plan_ms", "gate_wait_ms",
          "dispatch_host_ms", "pull_wait_ms", "decode_ms",
          "encode_send_ms")


# -- the .xplane.pb wire format ----------------------------------------------
# XSpace{1: planes}; XPlane{2: name, 3: lines, 4: event_metadata map,
# 5: stat_metadata map}; XLine{2: name, 3: timestamp_ns, 4: events};
# XEvent{1: metadata_id, 2: offset_ps, 3: duration_ps};
# XEventMetadata{1: id, 2: name, 5: stats}; XStat{1: metadata_id,
# 3: uint64, 4: int64, 5: str, 7: ref to a stat_metadata name};
# XStatMetadata{1: id, 2: name}.

def _varint(buf, i: int) -> tuple:
    r = s = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << s
        if b < 0x80:
            return r, i
        s += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif kind in (1, 5):
            ln = 8 if kind == 1 else 4
            v = buf[i:i + ln]
            i += ln
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield tag >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), None)


def _plane(buf) -> dict:
    name, lines, emeta, smeta = "", [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            emeta[len(emeta)] = v
        elif f == 5:
            smeta[len(smeta)] = v
    return {"name": name, "lines": lines, "emeta": emeta, "smeta": smeta}


def _stat_names(plane: dict) -> dict:
    out = {}
    for entry in plane["smeta"].values():
        d = dict(_fields(_map_value(entry)))
        out[d.get(1, 0)] = _text(d.get(2, b""))
    return out


def _event_metadata(plane: dict, want_stats: tuple = ()) -> dict:
    """{metadata id: {"name", <stat name>: value for want_stats}}."""
    stat_names = _stat_names(plane) if want_stats else {}
    out = {}
    for entry in plane["emeta"].values():
        meta = {"name": ""}
        mid = 0
        for f, v in _fields(_map_value(entry)):
            if f == 1:
                mid = v
            elif f == 2:
                meta["name"] = _text(v)
            elif f == 5 and want_stats:
                d = dict(_fields(v))
                sname = stat_names.get(d.get(1))
                if sname in want_stats:
                    if 7 in d:      # a string kept once, by reference
                        meta[sname] = stat_names.get(d[7], "")
                    else:
                        val = d.get(5, d.get(3, d.get(4)))
                        meta[sname] = (val if isinstance(val, int)
                                       or val is None else _text(val))
        out[mid] = meta
    return out


def _line_events(line) -> list:
    """[(start_ns, end_ns, metadata id)] of one line, by start."""
    t0, events = 0, []
    for f, v in _fields(line):
        if f == 3:
            t0 = v
        elif f == 4:
            events.append(v)
    out = []
    for ev in events:
        mid = off = dur = 0
        for f, v in _fields(ev):
            if f == 1:
                mid = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
        start = t0 + off // 1000
        out.append((start, start + dur // 1000, mid))
    out.sort()
    return out


def read_xplane(path: str) -> dict:
    """{"devices": {chip: [(start, end, metadata id)]}, "ops": {chip:
    {metadata id: {"name", "tf_op"}}}, "sync": [bench_sync starts],
    "host": [(start, event name)]}. The trace's clock, nanoseconds."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    devices, ops, sync, host = {}, {}, [], []
    for f, v in _fields(buf):
        if f != 1:
            continue
        plane = _plane(v)
        m = trace_reduce.DEVICE_PLANE.match(plane["name"])
        if m:
            for line in plane["lines"]:
                # a line's name is written before its events: only
                # the ops line is worth walking to its end
                name = next((_text(vv) for ff, vv in _fields(line)
                             if ff == 2), "")
                if name == trace_reduce.OPS_LINE:
                    devices[int(m.group(1))] = _line_events(line)
            ops[int(m.group(1))] = _event_metadata(plane, ("tf_op",))
        elif plane["name"] == trace_reduce.HOST_PLANE:
            names = {k: d["name"] for k, d in
                     _event_metadata(plane).items()}
            for line in plane["lines"]:
                for start, _, mid in _line_events(line):
                    name = names.get(mid, "")
                    if name == trace_reduce.SYNC_NAME:
                        sync.append(start)
                    else:
                        host.append((start, name))
    host.sort()
    return {"devices": devices, "ops": ops, "sync": sorted(sync),
            "host": host}


# -- spans -------------------------------------------------------------------
# A span here is the program's wire form (tracing.span_to_wire): "n"
# name, "b" and "e" stamps in CLOCK_MONOTONIC ns, "t" tags, "c" children.

def _dur(s: dict) -> int:
    return s["e"] - s["b"]


def _find_all(s: dict, name: str) -> list:
    out = [s] if s["n"] == name else []
    for c in s["c"]:
        out.extend(_find_all(c, name))
    return out


def _total(s: dict, name: str) -> int:
    return sum(_dur(x) for x in _find_all(s, name))


def _sql_key(sql: str) -> str:
    """A statement's text as far as both sides agree on it: the wire
    front end splits a frame at `;` and trims."""
    return " ".join(sql.split()).rstrip("; ")


def classify(root: dict, class_of_sql: dict):
    """The class of the statement a root served: the engine names its
    span by the statement's text (`class_of_sql` is keyed by
    _sql_key)."""
    for s in [root] + root["c"]:
        cls = class_of_sql.get(_sql_key(s["n"]))
        if cls is not None:
            return cls
    return None


def layer_ms(root: dict) -> dict:
    """One statement's time by layer metric, in ms; `other_ms` is what
    no layer metric holds (the root's and the statement span's own
    time: bookkeeping between the layers)."""
    plan = sum(_dur(p) - sum(_dur(c) for c in p["c"])
               for p in _find_all(root, "plan"))
    pull = _total(root, "pull")
    mats = _find_all(root, "materialize")
    out = {
        "wire_queue_ms": _total(root, "wire.queue"),
        "parse_plan_ms": _total(root, "parse") + plan,
        "gate_wait_ms": _total(root, "admission") + _total(root, "gate"),
        "dispatch_host_ms": _total(root, "dispatch")
        - _total(root, "queue"),
        "pull_wait_ms": pull,
        "decode_ms": (sum(_dur(m) for m in mats) - pull) if mats
        else _total(root, "decode"),
        "encode_send_ms": _total(root, "encode") + _total(root, "send"),
    }
    out["other_ms"] = _dur(root) - sum(out.values())
    out["outside_pull_ms"] = _dur(root) - pull
    out["statement_ms"] = _dur(root)
    return {k: v / 1e6 for k, v in out.items()}


def leaf_segments(s: dict, label: str, out: list) -> None:
    """Cut a span tree into disjoint (start, end, label) pieces, each
    labelled by the innermost span open there."""
    at = s["b"]
    for c in sorted(s["c"], key=lambda c: c["b"]):
        if c["b"] > at:
            out.append((at, c["b"], label))
        leaf_segments(c, c["n"], out)
        at = max(at, c["e"])
    if s["e"] > at:
        out.append((at, s["e"], label))


def idle_by_span(idle: list, segments: list) -> dict:
    """Idle intervals [(start, end)] put down to the (start, end,
    label) pieces open at each instant, split equally among them;
    NO_STATEMENT where none is open. The values add up to the idle
    time."""
    events = []
    for a, b in idle:
        events.append((a, 0, None))
        events.append((b, 1, None))
    for a, b, label in segments:
        if b > a:
            events.append((a, 2, label))
            events.append((b, 3, label))
    events.sort(key=lambda e: e[0])
    out: dict = {}
    open_: dict = {}
    n_open, idle_depth, at = 0, 0, None
    for t, kind, label in events:
        if idle_depth and at is not None and t > at:
            if n_open:
                share = (t - at) / n_open
                for lab, n in open_.items():
                    if n:
                        out[lab] = out.get(lab, 0.0) + share * n
            else:
                out[NO_STATEMENT] = out.get(NO_STATEMENT, 0.0) + (t - at)
        at = t
        if kind == 0:
            idle_depth += 1
        elif kind == 1:
            idle_depth -= 1
        elif kind == 2:
            open_[label] = open_.get(label, 0) + 1
            n_open += 1
        else:
            open_[label] -= 1
            n_open -= 1
    return out


def complement(busy: list, lo: int, hi: int) -> list:
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


# -- operator scopes ---------------------------------------------------------

def scope_of(tf_op) -> str:
    """`jit(fn)/sort.0/aggregate.1/operands/concatenate` ->
    `aggregate.1/operands`: the innermost operator scope and the
    innermost phase named beneath it (`.../kernel/jit(large_group_
    aggregate)/operands/stack` is `operands`); HARNESS for the result
    path's own programs; UNSCOPED where no scope is on the op's path
    (an op the compiler made, or a program that names none)."""
    parts = str(tf_op or "").split("/")
    last = max((i for i, p in enumerate(parts) if OPERATOR.match(p)),
               default=None)
    if last is None:
        return HARNESS if HARNESS in parts else UNSCOPED
    phase = [p for p in parts[last + 1:] if p in PHASES][-1:]
    return "/".join([parts[last]] + phase)


def device_operators(trace: dict, statements: list, lo: int,
                     hi: int) -> dict:
    """{"<class>/<scope>": ns}, mean over chips: self time of every op
    in [lo, hi) folded by scope, the class that of the statement
    (class, send, recv on the trace's clock) the op started in."""
    starts = [s[1] for s in statements]
    out: dict = {}
    for chip, ops in trace["devices"].items():
        meta = trace["ops"].get(chip, {})
        # trace_reduce's un-nesting, an event at a time: each event is
        # "named" by its start and metadata id
        events = [(a, b, (a, mid)) for a, b, mid in ops]
        for (start, mid), ns in trace_reduce.self_time_by_name(
                events, lo, hi).items():
            i = bisect.bisect_right(starts, start) - 1
            cls = (statements[i][0] if i >= 0
                   and start < statements[i][2] else "-")
            key = f"{cls}/{scope_of(meta.get(mid, {}).get('tf_op'))}"
            out[key] = out.get(key, 0.0) + ns / len(trace["devices"])
    return out


# -- the reduction -----------------------------------------------------------

def _mean_of_class_medians(per_class: dict, key: str):
    vals = [c[key] for c in per_class.values() if key in c]
    return statistics.fmean(vals) if vals else None


def reduce_capture(trace: dict, offset: int, segments: dict,
                   roots: list, class_of_sql: dict) -> dict:
    """Everything the span and scope metrics read. `segments` as in
    trace_reduce.reduce_trace; `roots` the collector's served roots in
    wire form; `trace` from read_xplane (its devices may be empty: a
    CPU rehearsal has spans and no device plane)."""
    out: dict = {"roots": len(roots)}
    by_slice: dict = {}
    for tag, seg in segments.items():
        rows: dict = {}
        for r in roots:
            cls = classify(r, class_of_sql)
            if cls is not None and seg["lo"] <= r["e"] < seg["hi"]:
                rows.setdefault(cls, []).append(layer_ms(r))
        by_slice[tag] = {
            cls: {k: statistics.median(x[k] for x in v) for k in v[0]}
            | {"statements": len(v)} for cls, v in rows.items()}
    single, mix = by_slice["single"], by_slice["mix"]
    out["per_class"] = single
    out["per_class_mix"] = mix
    for k in LAYERS + ("other_ms",):
        out[k] = _mean_of_class_medians(single, k)
    stretch = [mix[c]["outside_pull_ms"] / single[c]["outside_pull_ms"]
               for c in single if c in mix
               and single[c]["outside_pull_ms"] > 0]
    one_slice = segments["mix"]["lo"] == segments["single"]["lo"]
    out["host_stretch_x"] = (statistics.fmean(stretch)
                             if stretch and not one_slice else None)

    devices = trace["devices"]
    if not devices:
        return out
    # device idle time of the mix slice, by the span open in the server
    seg = segments["mix"]
    lo, hi = seg["lo"] + offset, seg["hi"] + offset
    pieces: list = []
    for r in roots:
        if r["e"] + offset <= lo or r["b"] + offset >= hi:
            continue
        cls = classify(r, class_of_sql) or "-"
        mine: list = []
        leaf_segments(r, WIRE_SELF, mine)
        for a, b, label in mine:
            if label != WIRE_SELF and label not in SPAN_NAMES:
                label = ENGINE_SELF
            pieces.append((max(a + offset, lo), min(b + offset, hi),
                           f"{cls}:{label}"))
    idle: dict = {}
    idle_ns = 0.0
    for ops in devices.values():
        gaps = complement(trace_reduce.merged(
            [(a, b, None) for a, b, _ in ops], lo, hi), lo, hi)
        idle_ns += sum(b - a for a, b in gaps) / len(devices)
        for label, ns in idle_by_span(gaps, pieces).items():
            idle[label] = idle.get(label, 0.0) + ns / len(devices)
    out["idle_s"] = idle_ns / 1e9
    out["idle_share"] = idle_ns / (hi - lo)
    out["idle_by_span"] = [[k, v / 1e9] for k, v in sorted(
        idle.items(), key=lambda kv: -kv[1])[:TOP]]
    out["idle_by_span_total_s"] = sum(idle.values()) / 1e9
    out["idle_no_stmt_share"] = (100.0 * idle.get(NO_STATEMENT, 0.0)
                                 / idle_ns if idle_ns else None)

    # device self time of the one-session slice, by operator scope
    seg = segments["single"]
    lo, hi = seg["lo"] + offset, seg["hi"] + offset
    stmts = sorted(((c, s + offset, r + offset)
                    for c, s, r in seg["statements"]),
                   key=lambda s: s[1])
    if stmts:   # not the ops of the statement the slice's end cut off
        hi = min(hi, max(r for _, _, r in stmts))
    folded = device_operators(trace, stmts, lo, hi)
    busy = sum(folded.values())
    out["device_operators"] = [[k, v / 1e9] for k, v in sorted(
        folded.items(), key=lambda kv: -kv[1])[:TOP]]
    out["busy_single_s"] = busy / 1e9

    def share(pred):
        return (100.0 * sum(v for k, v in folded.items()
                            if pred(k.split("/", 1)[1])) / busy
                if busy else None)

    named = any(k.split("/", 1)[1] != UNSCOPED for k in folded)
    out["op_attributed_share"] = share(
        lambda s: s != UNSCOPED) if named else None
    for metric, kinds in (("op_share_aggregate", ("aggregate",)),
                          ("op_share_hashjoin", ("hashjoin",)),
                          ("op_share_scan_filter", ("scan", "filter"))):
        out[metric] = share(lambda s, k=kinds: s.split(".")[0] in k) \
            if named else None

    # what the host did a statement, by the profiler's own event names
    # (PjitFunction(<program>), DevicePut, ...), beside the counters
    n = len(stmts)
    counts: dict = {}
    for start, name in trace["host"][
            bisect.bisect_left(trace["host"], (lo,)):
            bisect.bisect_left(trace["host"], (hi,))]:
        counts[name] = counts.get(name, 0) + 1
    out["host_events_per_stmt"] = [[k, v / n] for k, v in sorted(
        counts.items(), key=lambda kv: -kv[1])[:TOP]] if n else []

    # socket to socket: the spans against the clients' own stamps
    plain = {chip: [(a, b, "") for a, b, _ in ops]
             for chip, ops in devices.items()}
    for cls, c in single.items():
        mine = [(s, r) for k, s, r in stmts if k == cls]
        if mine:
            c["client_ms"] = statistics.median(
                (r - s) / 1e6 for s, r in mine)
            c["device_ms"] = statistics.median(
                trace_reduce.attribute_statement(plain, s, r)
                ["device_ns"] / 1e6 for s, r in mine)
    return out


# -- the capture -------------------------------------------------------------

_captured: dict = {}


def _newest(pattern: str):
    paths = glob.glob(os.path.join(HERE, "out", "*", pattern))
    return max(paths, key=os.path.getmtime) if paths else None


def capture(ctx: dict):
    """The reduced capture of this run, made on first use; None where
    the program has no collector, the harness left no plan, or the run
    is not a traced one."""
    if "reduced" not in _captured:
        _captured["reduced"] = None
        if ctx.get("trace") is not None or KEEP:
            try:
                _captured["reduced"] = _capture(ctx)
            except Exception as e:  # noqa: BLE001 — a metric is left
                print(f"# span_reduce: no capture: {e!r}",  # out, the
                      flush=True)                           # run holds
    return _captured["reduced"]


def _capture(ctx: dict):
    from cockroach_tpu.utils import tracing
    if not hasattr(tracing, "start_collector"):
        return None
    plan_path = _newest("trace_single_plan.json")
    if plan_path is None:
        return None
    with open(plan_path) as f:
        base = json.load(f)
    mix = ctx["mix"]
    if base["mix"]["classes"] != mix["classes"]:
        return None
    import jax

    import run as harness

    out_dir = os.path.dirname(plan_path)
    trace_dir = os.path.join(out_dir, "span_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    names = [c["name"] for c in mix["classes"]]
    class_of_sql = {_sql_key(st["sql"]): names[ci]
                    for ci, sets in enumerate(base["statements"])
                    for st in sets}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    marks: list = []

    def sync() -> None:
        for _ in range(harness.SYNC_MARKS):
            marks.append(time.monotonic_ns())
            with jax.profiler.TraceAnnotation(trace_reduce.SYNC_NAME):
                pass

    plans = [("single", base["mix"], SINGLE_S)]
    if mix["loop"] != "closed" or int(mix["sessions"]) != 1:
        plans.insert(0, ("mix", mix, MIX_S))
    segments: dict = {}
    tracing.start_collector()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        sync()
        for tag, m, secs in plans:
            start_ns = time.monotonic_ns() + int(
                harness.START_DELAY_S * 1e9)
            plan = dict(base, mix=m, start_ns=start_ns,
                        end_ns=start_ns + int(secs * 1e9))
            samples = harness.run_sessions(out_dir, "spans_" + tag, plan,
                                           int(m["sessions"]))
            segments[tag] = {
                "lo": start_ns, "hi": plan["end_ns"],
                "statements": sorted(
                    ((names[s[0]], s[3], s[4]) for s in samples if s[5]
                     and s[4] <= plan["end_ns"]), key=lambda x: x[1])}
        sync()
        time.sleep(0.05)    # a root closes after its reply is flushed
    finally:
        jax.profiler.stop_trace()
        roots = [tracing.span_to_wire(r)
                 for r in tracing.stop_collector()
                 if r.tags.get("served")]
    segments.setdefault("mix", segments["single"])
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    try:
        trace = read_xplane(found[-1])
        offset = trace_reduce.clock_offset(trace["sync"], marks)
        reduced = reduce_capture(trace, offset, segments, roots,
                                 class_of_sql)
        if KEEP:
            with open(os.path.join(out_dir, "span_segments.json"),
                      "w") as f:
                json.dump({"marks": marks, "segments": segments,
                           "roots": roots, "class_of_sql": class_of_sql,
                           "expected": reduced}, f)
    finally:
        if not KEEP:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print("# span_reduced " + json.dumps(reduced), flush=True)
    return reduced


# -- what layer_metrics/*.py call --------------------------------------------

def metric(ctx: dict, name: str):
    """A value of the reduced capture, None where there is none."""
    reduced = capture(ctx)
    return None if reduced is None else reduced.get(name)


def per_statement(ctx: dict, counters: list):
    """Counter deltas over the window a statement completed in it;
    None where the program has no such counter."""
    window = ctx["counters"]["window"]
    n = ctx["client"]["completed"]
    if not n or any(c not in window for c in counters):
        return None
    return sum(window[c] for c in counters) / n
