#!/usr/bin/env python3
"""One run of one cell of the benchmark: data in, one line out.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json: a configuration
(`configs` -> its file) under a traffic mix (benchmark/traffic/<mix>.json).
This process holds the chip: it starts `server.Node` the way a user
does (default reactor pgwire front end, ephemeral port), makes the
cell's tables from --seed, ingests and ANALYZEs them, executes every
statement class at each of its K parameter sets once (that compiles or
loads every program the window will use) and compares each first reply
with the integer reference, all of which is set-up. Then the sessions,
client processes that import neither the program nor JAX, offer the
mix's load for --seconds; every reply inside the window must equal its
verified first reply byte for byte. With --trace 1 the window is
followed by a short profiler slice and the line carries the cell's
per-layer metrics instead of its end-to-end ones.

It refuses to measure without a TPU or with another number of chips
than the cell asks for. `--rehearse-cpu-sf <sf>` is the rehearsal for a
sandbox without a chip: it runs the same path on the CPU at a tiny
scale factor, prints `"rehearsal": true`, platform `cpu` and no metric,
and refuses to run on anything but the CPU.

Everything that belongs to one configuration, mix, statement or
per-layer metric is a file found by name; adding one edits nothing
here (benchmark/tests/test_data_driven.py does it).
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # process start, as near as Python gets

import argparse  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import pgclient  # noqa: E402
import trace_reduce  # noqa: E402
from refworker import load_module  # noqa: E402
import traffic  # noqa: E402
import verify  # noqa: E402

# a cold Q3/Q18 compile is minutes; the set-up client must outwait it
SETUP_TIMEOUT_S = 1150.0
STATEMENT_TIMEOUT_S = 120.0
START_DELAY_S = 1.0     # sessions connect before the window opens
TRACE_MIX_S = 4.0       # profiler slice under the cell's own traffic
TRACE_SINGLE_S = 6.0    # profiler slice under one session
SYNC_MARKS = 5


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


# -- the cell, from data -----------------------------------------------------

def load_cell(workload: str) -> dict:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in "
                         f"BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    mix = load_json(os.path.join(HERE, "traffic",
                                 cell["traffic"] + ".json"))

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return {"cell": cell, "config": config, "mix": mix,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def render_statements(mix: dict, seed: int) -> tuple:
    """([class][set] -> sql text, {class name: parameter sets})."""
    sets = traffic.parameter_sets(mix, seed)
    texts = []
    for cls in mix["classes"]:
        with open(os.path.join(HERE, "statements",
                               cls["statement"] + ".sql")) as f:
            template = f.read()
        texts.append([template.format(**p) for p in sets[cls["name"]]])
    return texts, sets


def statement_tables(mix: dict) -> list:
    names: list = []
    for cls in mix["classes"]:
        mod = load_module("statements", cls["statement"])
        names.extend(t for t in mod.TABLES if t not in names)
    return names


# -- device ------------------------------------------------------------------

def require_device(chips: int, rehearsal: bool) -> dict:
    """Refuse to go on without the accelerator the cell asks for."""
    import jax

    backend = jax.default_backend()
    devs = jax.devices()
    if rehearsal:
        if backend != "cpu":
            raise SystemExit("run.py: --rehearse-cpu-sf runs on the CPU "
                             f"only; jax.default_backend() is {backend!r}")
    elif backend != "tpu":
        raise SystemExit(
            f"run.py: refusing to measure: jax.default_backend() is "
            f"{backend!r}, not 'tpu' (devices: {devs})")
    if len(devs) != chips:
        raise SystemExit(f"run.py: the cell asks for {chips} chip(s), "
                         f"JAX reports {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peaks():
    """The fullest chip: {"peak_bytes", "live_peak_bytes",
    "reserved_peak_bytes"}, or None where JAX keeps no memory statistics
    (the CPU). The peak is live buffers at their high-water mark plus
    what the runtime reserved for the loaded programs' temporaries,
    which the TPU client counts apart (Q1's 3.63 GiB are in
    `peak_bytes_reserved` and not in `peak_bytes_in_use`, PERF.md
    findings)."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    if any(s is None for s in stats):
        return None
    for i, s in enumerate(stats):
        log(f"memory_stats[{i}] " + json.dumps(s))
    full = max(stats, key=lambda s: s["peak_bytes_in_use"]
               + s.get("peak_bytes_reserved", 0))
    live, reserved = full["peak_bytes_in_use"], full.get(
        "peak_bytes_reserved", 0)
    return {"peak_bytes": live + reserved, "live_peak_bytes": live,
            "reserved_peak_bytes": reserved}


# -- counters ----------------------------------------------------------------

def counters(engine) -> dict:
    """The engine's metric registry, flat: a histogram becomes
    <name>.count and <name>.sum."""
    flat = {}
    for name, v in engine.metrics.snapshot().items():
        if isinstance(v, dict):
            for k, x in v.items():
                flat[f"{name}.{k}"] = x
        else:
            flat[name] = v
    return flat


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if isinstance(v, (int, float))}


# -- set-up ------------------------------------------------------------------

def ingest(engine, gen, tables: list, sf: float, seed: int) -> tuple:
    """Generate, ingest and ANALYZE the cell's tables. Returns (the
    tables as generated, seconds by step)."""
    split = {"generate_s": 0.0, "ingest_s": 0.0, "analyze_s": 0.0}
    data = {}
    ts = engine.clock.now()
    for t in tables:
        t0 = time.monotonic()
        cols, dicts = gen.generate(t, sf, seed)
        data[t] = (cols, dicts)
        t1 = time.monotonic()
        engine.execute(gen.DDL[t])
        for col, values in dicts.items():
            engine.store.set_dictionary(t, col, values)
        engine.store.insert_columns(t, cols, ts)
        t2 = time.monotonic()
        engine.execute(f"ANALYZE {t}")
        t3 = time.monotonic()
        split["generate_s"] += t1 - t0
        split["ingest_s"] += t2 - t1
        split["analyze_s"] += t3 - t2
    return data, split


def first_executions(client, engine, mix: dict, texts: list) -> tuple:
    """Every (class, set) once, in order. Returns ([class][set] ->
    reply bytes, per-class facts for the set-up split)."""
    replies, facts = [], {}
    for cls, sqls in zip(mix["classes"], texts):
        row, secs, misses = [], [], []
        for sql in sqls:
            before = counters(engine)
            send_ns, recv_ns, reply, error = client.exchange(sql)
            if error is not None:
                raise RuntimeError(f"{cls['name']}: the server answered "
                                   f"{error!r} to\n{sql}")
            d = delta(counters(engine), before)
            row.append(reply)
            secs.append((recv_ns - send_ns) / 1e9)
            misses.append(int(d.get("sql.plan.cache.miss", 0)))
        replies.append(row)
        facts[cls["name"]] = {"first_exec_s": secs,
                              "plan_cache_miss": misses}
        log(f"first executions {cls['name']}: "
            f"{[round(s, 3) for s in secs]} s, new plans {misses}")
    return replies, facts


def settle(client, engine, mix: dict, texts: list, replies: list) -> int:
    """Every class again until a whole pass compiles and plans nothing
    new. A first execution is not yet the steady program: an upload of
    more columns of a table replaces the resident copy, and the
    statements that read the old copy trace again on their next
    execution (PERF.md findings). Returns the passes it took."""
    watch = ("exec.compile.cache_miss", "exec.compile.cache_hit",
             "sql.plan.cache.miss")
    for n in range(1, 5):
        before = counters(engine)
        for cls, sqls, row in zip(mix["classes"], texts, replies):
            _, _, reply, error = client.exchange(sqls[0])
            if error is not None or (reply != row[0]
                                     and not cls.get("writes")):
                raise RuntimeError(f"{cls['name']}: the second reply "
                                   f"differs from the first ({error})")
        d = delta(counters(engine), before)
        if not any(d.get(k, 0) for k in watch):
            return n
    raise RuntimeError("the statements still compile after four passes")


def check_replies(mix: dict, replies: list, refs: dict) -> list:
    """Every first reply against the reference; the differences."""
    wrong = []
    for cls, row in zip(mix["classes"], replies):
        if cls.get("writes"):
            continue
        mod = load_module("statements", cls["statement"])
        for si, reply in enumerate(row):
            diff = verify.compare(mod.COLUMNS,
                                  pgclient.MiniClient.text_rows(reply),
                                  refs[cls["name"]][si])
            if diff:
                wrong.append(f"{cls['name']}[{si}]: {diff}")
    return wrong


# -- the sessions ------------------------------------------------------------

def run_sessions(out_dir: str, tag: str, plan: dict, sessions: int) -> list:
    """Start one client process per session, wait for all, return their
    samples [[class, set, due_ns, send_ns, recv_ns, ok, session], ...]
    merged."""
    plan_path = os.path.join(out_dir, f"{tag}_plan.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    procs = []
    limit = (plan["end_ns"] - time.monotonic_ns()) / 1e9 \
        + STATEMENT_TIMEOUT_S + 30
    try:
        for s in range(sessions):
            out_path = os.path.join(out_dir, f"{tag}_session{s}.json")
            procs.append((subprocess.Popen(
                [sys.executable, os.path.join(HERE, "loadgen.py"),
                 plan_path, str(s), out_path]), out_path))
        samples = []
        for proc, out_path in procs:
            rc = proc.wait(timeout=limit)
            if rc != 0:
                raise RuntimeError(f"a session process exited with {rc}")
            got = load_json(out_path)
            if got["modules"]:
                raise RuntimeError("a session process imported "
                                   f"{got['modules']}")
            samples.extend(got["samples"])
        return samples
    finally:
        for proc, _ in procs:
            if proc.poll() is None:
                proc.kill()
            proc.wait()


def make_plan(node, mix: dict, seed: int, texts: list, replies: list,
              start_ns: int, seconds: float) -> dict:
    host, port = node.sql_addr
    return {
        "host": host, "port": port, "mix": mix, "seed": seed,
        "timeout_s": STATEMENT_TIMEOUT_S,
        "start_ns": start_ns, "end_ns": start_ns + int(seconds * 1e9),
        "statements": [
            [{"sql": sql, "digest": hashlib.sha1(reply).hexdigest()}
             for sql, reply in zip(sqls, row)]
            for sqls, row in zip(texts, replies)]}


def client_view(mix: dict, samples: list, start_ns: int, end_ns: int) -> dict:
    """What the clients saw in the window, from their own stamps."""
    names = [c["name"] for c in mix["classes"]]
    attempted = [s for s in samples if s[3] < end_ns]
    good = [s for s in attempted if s[5] and s[4] <= end_ns]
    lat = {n: [] for n in names}
    for ci, _, due, _, recv, _, _ in good:
        lat[names[ci]].append((recv - due) / 1e6)
    medians = {n: statistics.median(v) for n, v in lat.items() if v}
    all_lat = sorted(x for v in lat.values() for x in v)

    def pct(q: float):
        """Nearest-rank percentile over every statement of the window."""
        if not all_lat:
            return None
        return all_lat[max(math.ceil(q * len(all_lat)) - 1, 0)]

    # a closed loop's rate over whole rounds: a session cycles the
    # classes from a seeded offset, so what the window's last, partial
    # round holds changes with the seed, and with it a plain count (in
    # tpch_sf1.join one statement in 58, and a round is a Q3 of 1.25 s
    # beside a Q14 of 0.05 s). Per session: statements in whole rounds
    # over the time from its first send (a session process that starts
    # late is the host's doing, 0.16 s in one run on the chip) to the
    # last one's reply; the sessions' rates add up.
    window_s = (end_ns - start_ns) / 1e9
    rate = len(good) / window_s
    if mix["loop"] == "closed" and all(s[5] for s in attempted):
        by_session: dict = {}
        for s in sorted(good, key=lambda s: s[4]):
            by_session.setdefault(s[6], []).append(s)
        whole = {k: len(v) // len(names) * len(names)
                 for k, v in by_session.items()}
        if all(whole.values()):
            rate = sum(n / ((by_session[k][n - 1][4]
                             - by_session[k][0][3]) / 1e9)
                       for k, n in whole.items())

    # the clients' own overhead: reply read -> next send, per session
    gaps = []
    if mix["loop"] == "closed" and not mix.get("think_time_ms"):
        last_recv: dict = {}
        for s in sorted(attempted, key=lambda s: s[3]):
            if s[6] in last_recv:
                gaps.append((s[3] - last_recv[s[6]]) / 1e6)
            last_recv[s[6]] = s[4]
    return {
        "attempted": len(attempted),
        "failed": len(attempted) - len([s for s in attempted if s[5]]),
        "completed": len(good),
        "stmts_per_s": rate,
        "stmts_per_s_plain": len(good) / window_s,
        "class_median_ms": medians,
        "class_count": {n: len(v) for n, v in lat.items()},
        "lat_geomean_ms": (math.exp(sum(math.log(m) for m in
                                        medians.values()) / len(medians))
                           if len(medians) == len(names) else None),
        "lat_max_ms": all_lat[-1] if all_lat else None,
        "lat_p95_ms": pct(0.95),
        "lat_p99_ms": pct(0.99),
        "gap_mean_ms": statistics.fmean(gaps) if gaps else None,
        # open loop: how late the generator sent what was due
        "late_mean_ms": (statistics.fmean((s[3] - s[2]) / 1e6
                                          for s in attempted)
                         if attempted else None),
    }


# -- the traced slice --------------------------------------------------------

def traced_slice(node, out_dir: str, mix: dict, seed: int, texts: list,
                 replies: list, mix_s: float = TRACE_MIX_S,
                 single_s: float = TRACE_SINGLE_S,
                 keep: bool = False) -> tuple:
    """Run the profiler over a short slice of the cell's own traffic
    (mix_s) and, for a mix of several sessions, a second slice under
    one session (single_s); reduce the trace. `keep` leaves the trace
    and its segments in out_dir (tests/record_fixture.py). Returns
    (reduced, samples ok?)."""
    import glob

    import jax

    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    marks: list = []

    def sync() -> None:
        for _ in range(SYNC_MARKS):
            marks.append(time.monotonic_ns())
            with jax.profiler.TraceAnnotation(trace_reduce.SYNC_NAME):
                pass

    single_mix = dict(mix, loop="closed", sessions=1, think_time_ms=0)
    segments, all_ok = {}, True
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        sync()
        plans = [("single", single_mix, single_s)]
        if mix["loop"] != "closed" or int(mix["sessions"]) != 1:
            plans.insert(0, ("mix", mix, mix_s))
        for tag, m, secs in plans:
            start_ns = time.monotonic_ns() + int(START_DELAY_S * 1e9)
            plan = make_plan(node, m, seed, texts, replies, start_ns, secs)
            samples = run_sessions(out_dir, "trace_" + tag, plan,
                                   int(m["sessions"]))
            all_ok = all_ok and all(s[5] for s in samples)
            names = [c["name"] for c in mix["classes"]]
            segments[tag] = {
                "lo": start_ns, "hi": plan["end_ns"],
                "statements": sorted(
                    ((names[s[0]], s[3], s[4]) for s in samples if s[5]
                     and s[4] <= plan["end_ns"]), key=lambda x: x[1])}
        sync()
    finally:
        jax.profiler.stop_trace()
    segments.setdefault("mix", segments["single"])
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    log(f"trace: {path} {os.path.getsize(path)} bytes")
    try:
        trace = trace_reduce.read_xplane(path)
        offset = trace_reduce.clock_offset(trace["sync"], marks)
        reduced = trace_reduce.reduce_trace(trace, offset, segments)
        if keep:
            with open(os.path.join(out_dir, "trace_segments.json"),
                      "w") as f:
                json.dump({"marks": marks, "segments": segments,
                           "expected": reduced}, f)
    finally:
        if not keep:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return reduced, all_ok


# -- per-layer metrics, from data --------------------------------------------

def _lookup(ctx: dict, path: str):
    """ctx["a"]["b"][0] for the path "a/b/0", None where a step is
    missing."""
    cur = ctx
    for part in path.split("/"):
        if isinstance(cur, list) and part.isdigit() and int(part) < len(cur):
            cur = cur[int(part)]
        elif isinstance(cur, dict) and part in cur:
            cur = cur[part]
        else:
            return None
    return cur


def read_counter_metric(spec: dict, ctx: dict):
    """A per-layer metric that is arithmetic on named counters or a
    value the harness already holds (layer_metrics/<name>.json)."""
    kind = spec["kind"]
    scale = float(spec.get("scale", 1.0))
    d = ctx["counters"][spec.get("phase", "window")]

    def total(names):
        return sum(d.get(n, 0) for n in names)

    if kind == "delta":
        return scale * total(spec["counters"])
    if kind == "ratio_of_deltas":
        den = total(spec["denominator"])
        return scale * total(spec["numerator"]) / den if den else None
    if kind == "mean_of_histogram_delta":
        n = d.get(spec["histogram"] + ".count", 0)
        return (scale * d.get(spec["histogram"] + ".sum", 0.0) / n
                if n else 0.0)
    if kind == "per_statement":
        n = ctx["client"]["completed"]
        return scale * total(spec["counters"]) / n if n else None
    if kind == "value":
        v = _lookup(ctx, spec["key"])
        return None if v is None else scale * v
    raise ValueError(f"unknown layer metric kind {kind!r}")


def read_layer_metrics(per_layer: list, ctx: dict) -> dict:
    out = {}
    for m in per_layer:
        base = os.path.join(HERE, "layer_metrics", m["name"])
        if os.path.exists(base + ".json"):
            value = read_counter_metric(load_json(base + ".json"), ctx)
        else:
            value = load_module("layer_metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# -- main --------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu-sf", type=float, default=None)
    args = ap.parse_args(argv)
    rehearsal = args.rehearse_cpu_sf is not None

    spec = load_cell(args.workload)
    cell, config, mix = spec["cell"], spec["config"], spec["mix"]
    sf = args.rehearse_cpu_sf if rehearsal else config["scale_factor"]
    out_dir = os.path.join(HERE, "out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    texts, sets = render_statements(mix, args.seed)
    tables = statement_tables(mix)

    # the reference answers, in a process of their own beside set-up
    ref_job = os.path.join(out_dir, "ref_job.json")
    ref_out = os.path.join(out_dir, "ref_out.json")
    with open(ref_job, "w") as f:
        json.dump({"generator": config["generator"], "sf": sf,
                   "seed": args.seed, "tables": tables,
                   "classes": [{"name": c["name"],
                                "statement": c["statement"],
                                "sets": sets[c["name"]]}
                               for c in mix["classes"]
                               if not c.get("writes")]}, f)
    ref_proc = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "refworker.py"), ref_job,
         ref_out], env=dict(os.environ, JAX_PLATFORMS="cpu"))
    node = None
    try:
        # importing the package keeps the cpu backend beside the
        # accelerator before JAX picks its platforms (chip_smoke.py)
        import cockroach_tpu  # noqa: F401
        device = require_device(int(config["chips"]), rehearsal)
        if int(cell["chips"]) != int(config["chips"]):
            raise SystemExit("run.py: the cell and its configuration "
                             "disagree on the chips")
        log(f"device: {json.dumps(device)}")
        from cockroach_tpu.server import Node, NodeConfig

        gen = load_module("generators", config["generator"])
        node = Node(NodeConfig()).start()
        engine = node.engine
        for k, v in config.get("settings", {}).items():
            engine.settings.set(k, v)
        split = {"import_and_node_s": time.monotonic() - T_START}
        c_start = counters(engine)
        data, load_split = ingest(engine, gen, tables, sf, args.seed)
        split.update(load_split)
        host, port = node.sql_addr
        client = pgclient.MiniClient(host, port, timeout=SETUP_TIMEOUT_S)
        t0 = time.monotonic()
        replies, facts = first_executions(client, engine, mix, texts)
        split["first_executions_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        split["settle_passes"] = settle(client, engine, mix, texts, replies)
        client.close()
        split["settle_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        if ref_proc.wait(timeout=SETUP_TIMEOUT_S) != 0:
            raise RuntimeError("the reference worker failed")
        split["reference_wait_s"] = time.monotonic() - t0
        wrong = check_replies(mix, replies, load_json(ref_out)["answers"])
        for w in wrong:
            log(f"WRONG ANSWER {w}")

        c_setup = counters(engine)
        start_ns = time.monotonic_ns() + int(START_DELAY_S * 1e9)
        setup_s = start_ns / 1e9 - T_START
        plan = make_plan(node, mix, args.seed, texts, replies, start_ns,
                         args.seconds)
        samples = run_sessions(out_dir, "window", plan,
                               int(mix["sessions"]))
        c_window = counters(engine)
        view = client_view(mix, samples, start_ns, plan["end_ns"])
        memory = memory_peaks()
        peak = None if memory is None else memory["peak_bytes"]
        split["upload_bytes_setup"] = delta(c_setup, c_start).get(
            "sql.device.upload.bytes", 0)
        log("setup_split " + json.dumps(
            {k: round(v, 3) for k, v in split.items()}
            | {"setup_s": round(setup_s, 3)}))
        log("client_view " + json.dumps(view))

        correct = not wrong and view["failed"] == 0
        reduced = None
        if args.trace:
            try:
                reduced, slice_ok = traced_slice(
                    node, out_dir, mix, args.seed, texts, replies)
            except trace_reduce.NoDevicePlane:
                if not rehearsal:  # a CPU trace has no device plane
                    raise
                reduced, slice_ok = None, True
            correct = correct and slice_ok
            log("trace_reduced " + json.dumps(reduced))
        ctx = {
            "counters": {"setup": delta(c_setup, c_start),
                         "window": delta(c_window, c_setup)},
            "client": view, "setup": {"facts": facts, "split": split,
                                      "setup_s": setup_s},
            "trace": reduced, "device": device, "config": config,
            "mix": mix, "data": data, "memory": memory,
        }
        values = {
            "stmts_per_s": view["stmts_per_s"],
            "lat_geomean_ms": view["lat_geomean_ms"],
            "hbm_peak_gib": None if peak is None else peak / 2 ** 30,
            "setup_s": setup_s,
        }
        result = {"correct": bool(correct), "attempted": view["attempted"],
                  "failed": view["failed"], "metrics": {},
                  "device": dict(device, memory_peak_bytes=peak)}
        if rehearsal:
            # counts only: nothing a CPU run times is a device number
            result["rehearsal"] = True
            result["new_plans"] = {n: f["plan_cache_miss"]
                                   for n, f in facts.items()}
            result["counts"] = {
                name: m["value"] for name, m in read_layer_metrics(
                    [m for m in spec["per_layer"]
                     if m["source"] == "program_counter"], ctx).items()}
        elif args.trace:
            result["metrics"] = read_layer_metrics(spec["per_layer"], ctx)
            result["device"].update(busy_s=reduced["busy_s"],
                                    window_s=reduced["window_s"])
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
        else:
            for m in spec["end_to_end"]:
                if values.get(m["name"]) is None:
                    raise RuntimeError(f"no value for {m['name']}")
                result["metrics"][m["name"]] = {
                    "value": values[m["name"]], "unit": m["unit"]}
    finally:
        if ref_proc.poll() is None:
            ref_proc.kill()
        ref_proc.wait()
        if node is not None:
            node.stop()
            node.engine.close()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
