"""Benchmark: TPC-H Q6/Q1/Q14 throughput on the attached TPU chip.

Prints ONE JSON line:
  {"metric": "tpch_q6_rows_per_sec", "value": N, "unit": "rows/s",
   "vs_baseline": N, ...}
plus per-query fields (q1_rows_per_sec, q14_rows_per_sec) and the
measured host-CPU number (cpu_q6_rows_per_sec / vs_cpu) when the CPU
baseline pass ran.

Baselines — two, with provenance:
- ASSUMED colexec baseline (vs_baseline): the reference publishes no
  absolute numbers (BASELINE.md); public roachperf-class runs put a
  Q6-shaped scan+filter+sum around 20-40M rows/s/core, i.e. ~1.25e8
  rows/s on the 3x4-vCPU roachtest config the reference gates on
  (pkg/cmd/roachtest/tests/tpchvec.go). Kept constant across rounds so
  vs_baseline stays comparable.
- MEASURED host-CPU baseline (vs_cpu): this same engine's Q6 plan
  compiled with XLA-CPU on this host (all cores), measured in a
  subprocess each bench run. This is a *generous* stand-in for colexec
  (XLA vectorizes + multithreads); beating it by 5-10x on one chip is
  the honest accomplishment.

Methodology: steady-state engine throughput. The query is prepared
once (Engine.prepare — the pgwire portal path), then PIPELINE
executions are dispatched back-to-back and synchronized once at the
end, the same way the reference's engine streams 600M rows through a
scan without a client round trip per batch. Single-shot blocking
latency is reported on stderr alongside.

Environment knobs: BENCH_ROWS (default 2^25 on TPU so the default run
finishes in minutes; 2^22 on CPU —
BENCH_ROWS=$((1<<27)) reproduces the headline run in BENCHMARKS.md),
BENCH_QUERY (q6|q1|q14|all; default all), BENCH_PIPELINE (default 16),
BENCH_REPEATS (default 5), BENCH_CPU=0 to skip the CPU-baseline
subprocess, BENCH_CPU_ROWS (default 2^22), BENCH_STREAM=0 /
BENCH_DISPATCHQ=0 to skip the PR 3 data-plane benches (streamed-scan
pipeline A/B and concurrent distributed dispatch), BENCH_PALLAS=0 to
skip the round-6 grouped-aggregation kernel A/B (auto vs off over
q1/q3/q18; BENCH_PALLAS_ROWS, default 2^18), BENCH_SPILL=0 to skip
the round-8 out-of-core A/B (spill=auto vs off at a forced-small HBM
budget; BENCH_SPILL_ROWS default 2^19, BENCH_SPILL_BUDGET default
2^21 bytes).
"""

import json
import os
import statistics
import subprocess
import sys
import time

BASELINE_ROWS_PER_SEC = 1.25e8  # assumed colexec-equivalent Q6 throughput


def metric_deltas(before: dict, after: dict) -> dict:
    """Registry-snapshot delta across one benchmarked query: counter/
    gauge movement plus histogram count growth. Gives each BENCH
    record the engine's own accounting of what the run did (device
    uploads, collective dispatches, plan-cache traffic) next to the
    throughput number it produced."""
    out = {}
    for k, av in after.items():
        bv = before.get(k, 0)
        if isinstance(av, dict):  # histogram: compare observation counts
            d = av.get("count", 0) - (bv.get("count", 0)
                                      if isinstance(bv, dict) else 0)
            if d:
                out[k + ".count"] = d
        elif isinstance(av, (int, float)) and not isinstance(av, bool):
            d = av - (bv if isinstance(bv, (int, float)) else 0)
            if d:
                out[k] = round(d, 6) if isinstance(d, float) else d
    # device-pressure columns (utils/devstats.py), always present so
    # rounds can attribute a regression to device time / HBM pressure
    # without a profiler: the query's device-execute seconds (delta)
    # and the process HBM high-water mark (absolute, a ratchet — the
    # delta would usually be 0)
    du = after.get("exec.device.util.seconds")
    if isinstance(du, (int, float)):
        out["device_time_s"] = round(
            du - (before.get("exec.device.util.seconds", 0) or 0), 6)
    wm = after.get("exec.device.hbm.watermark")
    if isinstance(wm, (int, float)):
        out["hbm_watermark_bytes"] = int(wm)
    return out


def bench_query(eng, sql, rows, pipeline, repeats, lat_probes=3):
    import jax

    t0 = time.time()
    eng.execute(sql)  # warmup: compile + device upload
    warm_s = time.time() - t0

    prep = eng.prepare(sql)
    lat = []
    for _ in range(lat_probes):
        t0 = time.time()
        prep.run()
        lat.append(time.time() - t0)

    # CTE-heavy shapes (q9/q18) re-execute through the engine per run
    # and cannot dispatch asynchronously; their per-exec cost is
    # seconds, so synchronous back-to-back runs measure the same
    # steady state without the pipelining trick
    try:
        jax.block_until_ready(prep.dispatch())  # sync: don't let the
        # probe's device work bleed into the first timed repeat
        async_ok = True
    except Exception:
        async_ok = False

    rates = []
    for _ in range(repeats):
        t0 = time.time()
        if async_ok:
            outs = [prep.dispatch() for _ in range(pipeline)]
            jax.block_until_ready(outs)
        else:
            for _ in range(pipeline):
                prep.run()
        dt = time.time() - t0
        rates.append(rows * pipeline / dt)
    return statistics.median(rates), statistics.median(lat), warm_s, rates


# per-query (pipeline, repeats, latency_probes) overrides: the
# compile-heavy suite shapes run seconds per execution — a deep
# pipeline (or even the default 3 single-shot latency probes) would
# blow the child timeout measuring nothing new. Round 4: q9 rides the
# composed device-resident CTE pipeline (exec/ctecompose.py, 142K ->
# ~5M rows/s) and q18/q3 the compaction + FD/limb agg work, so all
# three now take real pipelines.
# q9 rides the composed CTE pipeline at ~150ms/exec now: a
# pipeline of 8 amortizes the final sync like the other shapes
QUERY_OVERRIDES = {"q3": (8, 3, 2), "q9": (8, 3, 2), "q18": (8, 3, 2)}


_Q_COLS = {
    "q6": ("l_shipdate", "l_quantity", "l_discount",
           "l_extendedprice"),
    "q1": ("l_shipdate", "l_quantity", "l_extendedprice",
           "l_discount", "l_tax", "l_returnflag", "l_linestatus"),
}


def _scan_bytes_per_row(eng, table: str, which: str) -> int:
    narrow = eng.narrow32_cols(table)
    schema = eng.store.table(table).schema
    total = 0
    for cn in _Q_COLS[which]:
        col = schema.column(cn)
        if col.type.uses_dictionary:
            total += 4          # dict codes are int32
        elif cn in narrow:
            total += 4
        else:
            import numpy as _np
            total += _np.dtype(col.type.np_dtype).itemsize
    return total


def run(rows_by_query, pipeline, repeats, tag=""):
    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.models import tpch

    results = {}
    rows_used = {}
    deltas = {}
    # group queries sharing a row count onto one engine/dataset
    by_rows: dict[int, list] = {}
    for which, rows in rows_by_query.items():
        by_rows.setdefault(rows, []).append(which)
    for rows, queries in by_rows.items():
        eng = Engine()
        t0 = time.time()
        suite = {"q2", "q3", "q4", "q5", "q7", "q8", "q9", "q10",
                 "q11", "q12", "q13", "q15", "q16", "q18", "q20",
                 "q21", "q22"}
        if suite & set(queries):
            tables = tpch.ALL_TABLES
        elif {"q14", "q17", "q19"} & set(queries):
            tables = ("lineitem", "part")
        else:
            tables = ("lineitem",)
        tpch.load(eng, sf=rows / tpch.LINEITEM_PER_SF, rows=rows,
                  tables=tables, encoded=True)
        gen_s = time.time() - t0
        for which in queries:
            # one resident pruned column set per query: drop the
            # previous query's upload so peak HBM is one working set
            eng.drop_device_cache()
            o_pipe, o_reps, o_lat = QUERY_OVERRIDES.get(
                which, (pipeline, repeats, 3))
            q_pipe = min(pipeline, o_pipe)
            q_reps = min(repeats, o_reps)
            snap0 = eng.metrics.snapshot()
            rps, lat, warm_s, rates = bench_query(
                eng, tpch.QUERIES[which], rows, q_pipe, q_reps,
                lat_probes=o_lat)
            deltas[which] = metric_deltas(snap0, eng.metrics.snapshot())
            # operator-profile digest (round 13): the instrumented
            # eager rerun attributes the query's device seconds and
            # bytes moved to individual plan operators — top-3 by
            # device time lands in the BENCH record next to the rate
            # it explains. Never lets a profiling failure kill the
            # measured number.
            try:
                deltas[which]["profile"] = eng.operator_profile(
                    tpch.QUERIES[which])
            except Exception as e:  # pragma: no cover
                deltas[which]["profile"] = {"error": type(e).__name__}
            results[which] = rps
            rows_used[which] = rows
            gbps = ""
            if which in ("q6", "q1"):
                # effective scan bandwidth: HBM bytes/row the fused
                # pipeline actually reads at the UPLOADED widths
                # (stats-narrowed int64 columns ride as int32)
                bpr = _scan_bytes_per_row(eng, "lineitem", which)
                results[which + "_gbps"] = rps * bpr / 1e9
                gbps = (f" effective_GBps={rps * bpr / 1e9:.1f} "
                        f"(bytes/row={bpr})")
            print(f"# {tag}{which}: rows={rows} pipeline={q_pipe} "
                  f"rows_per_sec={rps:.3e} median_latency_s={lat:.4f} "
                  f"warmup_s={warm_s:.1f} "
                  f"rates_Mrps={['%.0f' % (r / 1e6) for r in rates]}"
                  f"{gbps}",
                  file=sys.stderr)
            interesting = {k: v for k, v in deltas[which].items()
                           if k.startswith(("exec.", "sql.device",
                                            "sql.plan"))}
            if interesting:
                print(f"# {tag}{which} metric deltas: "
                      f"{json.dumps(interesting, sort_keys=True)}",
                      file=sys.stderr)
            prof = deltas[which].get("profile")
            if prof and "top_ops" in prof:
                print(f"# {tag}{which} profile: "
                      f"{json.dumps(prof, sort_keys=True)}",
                      file=sys.stderr)
        print(f"# {tag}datagen_s={gen_s:.1f} rows={rows}", file=sys.stderr)
        del eng
    return results, rows_used, deltas


def run_ssb(rows, pipeline, repeats):
    """SSB full flight (BASELINE.md config 4): star-schema joins.
    Reports per-query pipelined throughput plus the flight rate
    (total lineorder rows scanned / total time)."""
    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.workload import ssb

    eng = Engine()
    t0 = time.time()
    ssb.load(eng, sf=rows / ssb.LINEORDER_PER_SF, rows=rows)
    print(f"# ssb datagen_s={time.time() - t0:.1f} rows={rows}",
          file=sys.stderr)
    per = {}
    total_t = 0.0
    for name, sql in ssb.QUERIES.items():
        eng.drop_device_cache()
        rps, lat, warm_s, rates = bench_query(eng, sql, rows,
                                              pipeline, repeats)
        per[name.replace(".", "_")] = rps
        total_t += rows / rps
        print(f"# ssb {name}: rows_per_sec={rps:.3e} "
              f"median_latency_s={lat:.4f} warmup_s={warm_s:.1f}",
              file=sys.stderr)
    flight = rows * len(ssb.QUERIES) / total_t
    return flight, per


def run_ycsb_e(records, steps):
    """YCSB-E (BASELINE.md config 5): 95% short MVCC range scans with
    predicate pushdown + 5% inserts, served by the host-side ordered
    index-range fastpath (no per-literal XLA compiles)."""
    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.workload.ycsb import YCSB

    eng = Engine()
    w = YCSB(eng, workload="E", records=records, seed=1)
    t0 = time.time()
    w.setup()
    print(f"# ycsb-e setup_s={time.time() - t0:.1f} "
          f"records={records}", file=sys.stderr)
    w.run(steps=min(100, steps))  # warm plan/locator caches
    out = w.run(steps=steps)
    # 16 concurrent drivers: read-only scans share the statement gate
    # (utils/rwlock.py), inserts take it exclusively — the
    # concurrency shape of `workload run ycsb --concurrency 16`
    outc = w.run_concurrent(steps=steps * 4, workers=16)
    print(f"# ycsb-e: ops_per_sec={out['ops_per_sec']:.0f} "
          f"ops={out['ops']} "
          f"concurrent16_ops_per_sec={outc['ops_per_sec']:.0f}",
          file=sys.stderr)
    return out["ops_per_sec"], outc["ops_per_sec"]


def run_stream(rows, repeats):
    """Streamed-scan A/B (PR 3 tentpole): Q6 over a lineitem bigger
    than the HBM budget, paged through the data plane with the
    background prefetch pipeline on vs off (`SET streaming_pipeline`).
    The on/off ratio is the overlap win: worker-thread page assembly
    + upload hidden behind device compute. NOTE: on the XLA-CPU
    backend "device" compute shares the host cores with the prefetch
    worker, so there is no free capacity to overlap into and the
    ratio can dip below 1; the win is real when compute runs on the
    accelerator."""
    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.models import tpch

    eng = Engine(mesh=None)
    t0 = time.time()
    tpch.load(eng, sf=rows / tpch.LINEITEM_PER_SF, rows=rows,
              tables=("lineitem",), encoded=True)
    print(f"# stream datagen_s={time.time() - t0:.1f} rows={rows}",
          file=sys.stderr)
    # budget far below the table at any bench size: the scan MUST
    # stream
    eng.settings.set("sql.exec.hbm_budget_bytes", 1 << 20)
    page_rows = min(1 << 18, rows // 8)
    rates = {}
    for pipeline in ("on", "off"):
        s = eng.session()
        s.vars.set("distsql", "off")
        s.vars.set("streaming_page_rows", page_rows)
        s.vars.set("streaming_pipeline", pipeline)
        eng.execute(tpch.QUERIES["q6"], s)  # warmup: compile page fns
        snap0 = eng.metrics.snapshot()
        per = []
        for _ in range(repeats):
            t0 = time.time()
            eng.execute(tpch.QUERIES["q6"], s)
            per.append(rows / (time.time() - t0))
        rates[pipeline] = statistics.median(per)
        d = metric_deltas(snap0, eng.metrics.snapshot())
        print(f"# stream pipeline={pipeline} "
              f"rows_per_sec={rates[pipeline]:.3e} "
              f"pages={d.get('exec.stream.pages', 0)} "
              f"stalls={d.get('exec.stream.prefetch_stall_seconds.count', 0)}",
              file=sys.stderr)
    return rates["on"], rates["off"]


def run_pallas_ab(rows, repeats):
    """Pallas grouped-aggregation A/B (round 6 tentpole): the GROUP BY
    ladder queries (q1 dense small-G, q3/q18 hash-strategy large-G)
    with `SET pallas_groupagg` auto vs off. The auto arm rides the
    one-pass large-G kernel (one-hot MXU matmuls into VMEM tiles, no
    scatters); the off arm is the XLA segment path with its
    per-aggregate scatter tail. Both arms always record, so a CPU run
    (where the kernel executes in interpret mode and the ratio is
    meaningless) still proves the plumbing and gives the off-arm
    baseline; the ratio is the tentpole win on the real chip."""
    import jax

    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.models import tpch
    from cockroach_tpu.ops.pallas import groupagg as _pg

    if jax.default_backend() != "tpu" and rows > (1 << 15):
        # off-TPU the kernel executes in interpret mode and auto's
        # cost model refuses large grids (compile.AUTO_INTERPRET_STEPS)
        # — clamp so the auto arm still routes and the A/B stays an
        # A/B rather than off-vs-off
        print(f"# pallas: non-TPU backend, clamping rows {rows} -> "
              f"{1 << 15} so auto still routes interpreted kernels",
              file=sys.stderr)
        rows = 1 << 15
    eng = Engine()
    t0 = time.time()
    tpch.load(eng, sf=rows / tpch.LINEITEM_PER_SF, rows=rows,
              tables=("lineitem", "orders", "customer"), encoded=True)
    print(f"# pallas datagen_s={time.time() - t0:.1f} rows={rows}",
          file=sys.stderr)
    out = {}
    for which in ("q1", "q3", "q18"):
        eng.drop_device_cache()
        for arm in ("auto", "off"):
            s = eng.session()
            s.vars.set("pallas_groupagg", arm)
            b0, f0 = _pg.BUILDS.value("large"), _pg.FALLBACKS.value()
            eng.execute(tpch.QUERIES[which], s)  # warmup: compile
            per = []
            for _ in range(repeats):
                t0 = time.time()
                eng.execute(tpch.QUERIES[which], s)
                per.append(rows / (time.time() - t0))
            rps = statistics.median(per)
            out[f"pallas_{which}_{arm}_rows_per_sec"] = round(rps)
            print(f"# pallas {which} arm={arm} rows_per_sec={rps:.3e} "
                  f"large_builds={_pg.BUILDS.value('large') - b0} "
                  f"fallbacks={_pg.FALLBACKS.value() - f0}",
                  file=sys.stderr)
        auto = out[f"pallas_{which}_auto_rows_per_sec"]
        off = out[f"pallas_{which}_off_rows_per_sec"]
        out[f"pallas_{which}_speedup"] = \
            round(auto / off, 3) if off else 0
    out["pallas_rows"] = rows  # post-clamp: the measured size
    return out


def run_sort_ab(rows, repeats):
    """Normalized-sort-key A/B (round 7 tentpole): an ORDER BY-heavy
    query (3 keys incl. DESC, LIMIT past TOPK_MAX so the full sort
    runs but only the head materializes) and a window query (partition
    + 2-key order) with `SET sort_normalized` auto vs off. The auto
    arm packs the whole key list into uint64 lanes and runs one stable
    2-operand sort per lane; the off arm restores the variadic lexsort
    (2K+1 operands, ~20s XLA compile per operand past 64K rows on the
    real chip). Warmup (compile) seconds are recorded per arm — the
    compile-wall delta is the headline off-CPU; on CPU the runtime
    ratio mostly proves the plumbing."""
    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.models import tpch
    from cockroach_tpu.ops import sortkey as _sk

    eng = Engine()
    t0 = time.time()
    tpch.load(eng, sf=rows / tpch.LINEITEM_PER_SF, rows=rows,
              tables=("lineitem",), encoded=True)
    print(f"# sort datagen_s={time.time() - t0:.1f} rows={rows}",
          file=sys.stderr)
    qs = {
        "order3": ("SELECT l_orderkey, l_quantity FROM lineitem "
                   "ORDER BY l_returnflag DESC, l_linestatus, "
                   "l_quantity DESC LIMIT 2048"),
        "window": ("SELECT l_orderkey, row_number() OVER "
                   "(PARTITION BY l_returnflag ORDER BY "
                   "l_quantity DESC, l_orderkey) AS rn "
                   "FROM lineitem ORDER BY rn LIMIT 2048"),
    }
    out = {}
    for which, sql in qs.items():
        for arm in ("auto", "off"):
            s = eng.session()
            s.vars.set("sort_normalized", arm)
            n0, f0 = _sk.NORMALIZED.value(), _sk.FALLBACKS.value()
            t0 = time.time()
            eng.execute(sql, s)  # warmup: compile
            warm = time.time() - t0
            per = []
            for _ in range(repeats):
                t0 = time.time()
                eng.execute(sql, s)
                per.append(rows / (time.time() - t0))
            rps = statistics.median(per)
            out[f"sort_{which}_{arm}_rows_per_sec"] = round(rps)
            out[f"sort_{which}_{arm}_compile_s"] = round(warm, 2)
            print(f"# sort {which} arm={arm} rows_per_sec={rps:.3e} "
                  f"compile_s={warm:.2f} "
                  f"normalized={_sk.NORMALIZED.value() - n0} "
                  f"fallbacks={_sk.FALLBACKS.value() - f0}",
                  file=sys.stderr)
        auto = out[f"sort_{which}_auto_rows_per_sec"]
        off = out[f"sort_{which}_off_rows_per_sec"]
        out[f"sort_{which}_speedup"] = \
            round(auto / off, 3) if off else 0
    out["sort_rows"] = rows
    return out


def run_spill_ab(rows, repeats):
    """Out-of-core spill-tier A/B (round 8 tentpole): a q3-class join
    (lineitem probe x orders build, small dense group key) and a
    q9-class ORDER BY ... LIMIT, each run three ways:

      resident  spill=off at an ample budget — the correctness
                baseline every other arm must match row-for-row
      off       spill=off at BENCH_SPILL_BUDGET — the pre-round-8
                engine: the build/sort upload blows the quota monitor
                and the query DIES (recorded as an error, value 0)
      auto      spill=auto at the same small budget — the partitioned
                external hash join / external merge sort complete the
                query; metric deltas record exec.spill.bytes moved
                and the prefetch-overlap seconds

    The headline is not a speed ratio: the off arm at the small
    budget cannot finish at all, so the auto arm's completion +
    bit-parity against the resident baseline IS the win. NOTE: on the
    XLA-CPU backend partition/page assembly shares host cores with
    "device" compute, so overlap seconds understate the real chip."""
    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.models import tpch

    eng = Engine(mesh=None)
    t0 = time.time()
    tpch.load(eng, sf=rows / tpch.LINEITEM_PER_SF, rows=rows,
              tables=("lineitem", "orders"), encoded=True)
    print(f"# spill datagen_s={time.time() - t0:.1f} rows={rows}",
          file=sys.stderr)
    budget = int(os.environ.get("BENCH_SPILL_BUDGET", 1 << 21))
    ample = 12 << 30
    qs = {
        "join": ("SELECT o_orderpriority, count(*) AS n, "
                 "sum(l_quantity) AS q FROM lineitem JOIN orders "
                 "ON l_orderkey = o_orderkey "
                 "GROUP BY o_orderpriority ORDER BY o_orderpriority"),
        "sort": ("SELECT l_orderkey, l_extendedprice FROM lineitem "
                 "ORDER BY l_extendedprice DESC, l_orderkey "
                 "LIMIT 1000"),
    }
    out = {"spill_budget_bytes": budget}
    for which, sql in qs.items():
        base = None
        for arm, arm_budget, spill in (("resident", ample, "off"),
                                       ("off", budget, "off"),
                                       ("auto", budget, "auto")):
            eng.drop_device_cache()
            eng.settings.set("sql.exec.hbm_budget_bytes", arm_budget)
            s = eng.session()
            s.vars.set("distsql", "off")
            s.vars.set("streaming_page_rows", 8192)
            s.vars.set("spill", spill)
            verdict = eng.stream_verdict(qs[which], s)
            snap0 = eng.metrics.snapshot()
            try:
                res = eng.execute(sql, s)  # warmup: compile + upload
                per = []
                for _ in range(repeats):
                    t0 = time.time()
                    res = eng.execute(sql, s)
                    per.append(rows / (time.time() - t0))
                rps = statistics.median(per)
            except Exception as e:
                # the expected off-arm outcome at the small budget:
                # the whole-build/whole-table upload trips the quota
                # monitor before any execution
                out[f"spill_{which}_{arm}_rows_per_sec"] = 0
                out[f"spill_{which}_{arm}_error"] = type(e).__name__
                print(f"# spill {which} arm={arm} verdict={verdict} "
                      f"error={type(e).__name__}: {str(e)[:100]}",
                      file=sys.stderr)
                continue
            d = metric_deltas(snap0, eng.metrics.snapshot())
            out[f"spill_{which}_{arm}_rows_per_sec"] = round(rps)
            if arm == "resident":
                base = res.rows
            else:
                out[f"spill_{which}_{arm}_parity"] = res.rows == base
            if arm == "auto":
                out[f"spill_{which}_partitions"] = \
                    d.get("exec.spill.partitions", 0)
                out[f"spill_{which}_bytes"] = \
                    d.get("exec.spill.bytes", 0)
                out[f"spill_{which}_overlap_s"] = round(
                    d.get("exec.spill.upload_overlap_seconds", 0), 4)
            print(f"# spill {which} arm={arm} verdict={verdict} "
                  f"rows_per_sec={rps:.3e} "
                  f"spill_bytes={d.get('exec.spill.bytes', 0)} "
                  f"partitions={d.get('exec.spill.partitions', 0)} "
                  f"overlap_s="
                  f"{d.get('exec.spill.upload_overlap_seconds', 0):.4f}",
                  file=sys.stderr)
    return out


def run_movement_ab(rows, repeats):
    """Data-movement A/B (round 13 tentpole): a distributed join
    ladder where each data node's lineitem shard is sized at 0.5x /
    1x / 2x / 4x of the node's HBM slice (the replicated orders build
    side always stays resident — build sides cannot page). Pre-round-
    13 every rung past 0.5x DIED with MemoryQuotaError on the data
    nodes; now the node-side distributed spill pages the shard
    through the movement scheduler. Two arms per rung:

      overlap  FlowSpec.overlap=True (default): producers double-
               buffer the send side and page uploads ride the
               prefetch worker — ship time hides behind compute
      serial   overlap=False: the historical compute-then-ship frame
               exchange

    Headline: completion + bit-parity against the all-resident
    single-engine oracle on every rung, and the 2x/1x overlap-arm
    throughput ratio (the linear-degradation gate: paging a working
    set 2x over budget should cost bandwidth, not fall off a cliff).
    NOTE: on XLA-CPU 'device' compute shares host cores with page
    assembly and frame serialization, so overlap seconds understate
    a real chip."""
    from cockroach_tpu.distsql.node import DistSQLNode, Gateway
    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.kvserver.transport import LocalTransport
    from cockroach_tpu.models import tpch

    sf = rows / tpch.LINEITEM_PER_SF
    t0 = time.time()
    li = tpch.gen_lineitem(sf, rows=rows)
    orders = tpch.gen_orders(sf)
    print(f"# movement datagen_s={time.time() - t0:.1f} rows={rows}",
          file=sys.stderr)
    nshards = 3
    transport = LocalTransport()
    bounds = [i * rows // nshards for i in range(nshards + 1)]
    nodes, engines = [], []
    for i in range(nshards + 1):            # 0 = gateway, ample
        eng = Engine()
        eng.execute(tpch.DDL["lineitem"])
        eng.execute(tpch.DDL["orders"])
        ts = eng.clock.now()
        if i > 0:
            eng.store.insert_columns(
                "lineitem",
                {k: v[bounds[i - 1]:bounds[i]] for k, v in li.items()},
                ts)
        eng.store.insert_columns("orders", orders, ts)
        engines.append(eng)
        nodes.append(DistSQLNode(i, eng, transport))
    gw = Gateway(nodes[0], list(range(1, nshards + 1)),
                 replicated_tables={"orders"})
    sql = ("SELECT o_orderpriority, count(*) AS n, "
           "sum(l_quantity) AS q FROM lineitem JOIN orders "
           "ON l_orderkey = o_orderkey "
           "GROUP BY o_orderpriority ORDER BY o_orderpriority")
    oracle = Engine()
    tpch.load(oracle, sf=sf, rows=rows, tables=("lineitem", "orders"),
              encoded=True)
    base = oracle.execute(sql).rows

    e1 = engines[1]
    shard_b = e1._table_device_bytes(e1.store.table("lineitem"), None)
    orders_b = e1._table_device_bytes(e1.store.table("orders"), None)
    out = {"movement_shard_bytes": int(shard_b),
           "movement_build_bytes": int(orders_b)}
    spill_keys = ("exec.movement.dist_spill_fallbacks",
                  "exec.stream.pages",
                  "exec.movement.overlap_seconds",
                  "exec.spill.upload_overlap_seconds")
    for label, factor in (("0p5x", 0.5), ("1x", 1.0), ("2x", 2.0),
                          ("4x", 4.0)):
        budget = int(orders_b + shard_b / factor)
        for eng in engines[1:]:
            eng.drop_device_cache()
            eng.settings.set("sql.exec.hbm_budget_bytes", str(budget))
        out[f"movement_{label}_node_budget_bytes"] = budget
        for arm in ("overlap", "serial"):
            gw.overlap = arm == "overlap"
            snap0 = [e.metrics.snapshot() for e in engines[1:]]
            try:
                res = gw.run(sql)          # warmup: compile + upload
                per = []
                for _ in range(repeats):
                    t0 = time.time()
                    res = gw.run(sql)
                    per.append(rows / (time.time() - t0))
                rps = statistics.median(per)
            except Exception as e:
                out[f"movement_{label}_{arm}_rows_per_sec"] = 0
                out[f"movement_{label}_{arm}_error"] = type(e).__name__
                print(f"# movement {label} arm={arm} "
                      f"error={type(e).__name__}: {str(e)[:100]}",
                      file=sys.stderr)
                continue
            d = {}
            for s0, eng in zip(snap0, engines[1:]):
                for k, v in metric_deltas(
                        s0, eng.metrics.snapshot()).items():
                    if k in spill_keys:
                        d[k] = d.get(k, 0) + v
            out[f"movement_{label}_{arm}_rows_per_sec"] = round(rps)
            out[f"movement_{label}_{arm}_parity"] = res.rows == base
            if arm == "overlap":
                out[f"movement_{label}_overlap_s"] = round(
                    d.get("exec.movement.overlap_seconds", 0), 4)
                out[f"movement_{label}_spill_overlap_s"] = round(
                    d.get("exec.spill.upload_overlap_seconds", 0), 4)
                out[f"movement_{label}_pages"] = \
                    d.get("exec.stream.pages", 0)
            print(f"# movement {label} arm={arm} "
                  f"rows_per_sec={rps:.3e} parity={res.rows == base} "
                  f"pages={d.get('exec.stream.pages', 0)} "
                  f"fallbacks="
                  f"{d.get('exec.movement.dist_spill_fallbacks', 0)} "
                  f"overlap_s="
                  f"{d.get('exec.movement.overlap_seconds', 0):.4f}",
                  file=sys.stderr)
        gw.overlap = True
    # the linear-degradation gate: a 2x-over-budget working set pages
    # half its scans per rerun — throughput should degrade toward the
    # movement bound, not collapse (cliff = the scheduler failed to
    # overlap or thrashed pages)
    r1 = out.get("movement_1x_overlap_rows_per_sec", 0)
    r2 = out.get("movement_2x_overlap_rows_per_sec", 0)
    if r1:
        out["movement_ratio_2x_1x"] = round(r2 / r1, 3)
        if r2 / r1 < 0.35:
            print(f"# REGRESSION movement_ratio_2x_1x="
                  f"{r2 / r1:.3f} < 0.35: beyond-HBM rung fell off "
                  "a cliff instead of degrading linearly",
                  file=sys.stderr)
            out.setdefault("regressions", []).append(
                "movement_ratio_2x_1x")
    return out


def run_joinskip_ab(rows, repeats):
    """Join-induced data skipping A/B (round 10 tentpole): semi-join
    filters derived from the hash-join build side at dispatch time,
    fed into the probe scan's zone predicates.

    Two ladders, each off (join_filter=off) vs auto, both checked
    row-for-row against a resident ample-budget baseline:

      q3-class  streamed lineitem probe x orders build restricted to
                a 5% o_orderkey prefix. l_orderkey is clustered, so
                the derived [lo, hi] + key summary skips the pages
                whose whole key range misses the build — the metric
                deltas record exec.skip.joinfilter.pages/bytes.
      q9-class  spill-join lineitem probe x part build restricted to
                a small p_partkey prefix. l_partkey is NOT clustered
                (no page can skip) — the win is host-side row pruning
                before partition gather/upload, recorded as
                exec.skip.joinfilter.rows.

    The skipped pages/rows never assemble or upload, so the auto arm
    does strictly less host->device work for identical rows."""
    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.models import tpch

    eng = Engine(mesh=None)
    t0 = time.time()
    sf = rows / tpch.LINEITEM_PER_SF
    # chunked ingest (the shape real writes produce): per-chunk
    # write-time zones over l_orderkey are what make q3-class probe
    # pages skippable; one monolithic chunk would span every key
    tpch.load(eng, sf=sf, rows=rows,
              tables=("lineitem", "orders", "part"), encoded=True,
              chunk_rows=1 << 14)
    print(f"# joinskip datagen_s={time.time() - t0:.1f} rows={rows}",
          file=sys.stderr)
    budget = int(os.environ.get("BENCH_JOINSKIP_BUDGET", 1 << 25))
    ample = 12 << 30
    okey_cap = int(tpch.ORDERS_PER_SF * max(sf, 0.01) * 0.05)
    qs = {
        "q3": ("SELECT o_orderpriority, count(*) AS n, "
               "sum(l_quantity) AS q, sum(l_extendedprice) AS v, "
               "sum(l_discount) AS dc FROM lineitem JOIN orders "
               "ON l_orderkey = o_orderkey "
               f"WHERE o_orderkey <= {okey_cap} "
               "GROUP BY o_orderpriority ORDER BY o_orderpriority",
               "off"),
        "q9": ("SELECT count(*) AS n, sum(l_extendedprice) AS v, "
               "sum(l_quantity) AS q, sum(l_discount) AS dc "
               "FROM lineitem JOIN part ON l_partkey = p_partkey "
               "WHERE p_partkey <= 100",
               "on"),
    }
    out = {"joinskip_budget_bytes": budget,
           "joinskip_okey_cap": okey_cap}
    for which, (sql, spill) in qs.items():
        base = None
        for arm, jf in (("resident", "off"), ("off", "off"),
                        ("auto", "auto")):
            eng.drop_device_cache()
            eng.settings.set(
                "sql.exec.hbm_budget_bytes",
                ample if arm == "resident" else budget)
            s = eng.session()
            s.vars.set("distsql", "off")
            s.vars.set("streaming_page_rows", 8192)
            s.vars.set("spill", "off" if arm == "resident" else spill)
            s.vars.set("join_filter", jf)
            snap0 = eng.metrics.snapshot()
            res = eng.execute(sql, s)  # warmup: compile + upload
            per = []
            for _ in range(repeats):
                t0 = time.time()
                res = eng.execute(sql, s)
                per.append(rows / (time.time() - t0))
            rps = statistics.median(per)
            d = metric_deltas(snap0, eng.metrics.snapshot())
            out[f"joinskip_{which}_{arm}_rows_per_sec"] = round(rps)
            if arm == "resident":
                base = res.rows
            else:
                out[f"joinskip_{which}_{arm}_parity"] = \
                    res.rows == base
                out[f"joinskip_{which}_{arm}_pages_skipped"] = \
                    d.get("exec.stream.pages_skipped", 0)
                out[f"joinskip_{which}_{arm}_bytes_skipped"] = \
                    d.get("exec.stream.bytes_skipped", 0)
            if arm == "auto":
                out[f"joinskip_{which}_jf_pages"] = \
                    d.get("exec.skip.joinfilter.pages", 0)
                out[f"joinskip_{which}_jf_bytes"] = \
                    d.get("exec.skip.joinfilter.bytes", 0)
                out[f"joinskip_{which}_jf_rows"] = \
                    d.get("exec.skip.joinfilter.rows", 0)
            print(f"# joinskip {which} arm={arm} "
                  f"rows_per_sec={rps:.3e} "
                  f"jf_pages={d.get('exec.skip.joinfilter.pages', 0)} "
                  f"jf_rows={d.get('exec.skip.joinfilter.rows', 0)} "
                  f"pages_skipped="
                  f"{d.get('exec.stream.pages_skipped', 0)}",
                  file=sys.stderr)
    return out


def run_joinorder_ab(rows, repeats):
    """Sketch-fed join ordering A/B (round 12 tentpole): the memo's
    cost-based join-order search running on seal-time sketch
    statistics alone — no ANALYZE is ever issued, so the syntax arm
    cannot borrow cardinalities either.

    q9-class ladder: lineitem joins supplier, part and an EXPANDING
    partsupp (partkey only — 4 rows per part, so the join copies
    every probe lane 4x) before the one join that actually cuts
    rows — orders, restricted to ~2% of customers. orders is also
    the LARGEST dim, so the stats-blind orderer (build tables
    ascending by row count) agrees with syntax order and schedules
    it last. Two arms over identical data:

      syntax  optimizer_sketch_stats=off — without distinct counts
              the memo search disengages; every dim join probes at
              full fact width, the partsupp expansion quadruples
              that width, and the dense GROUP BY scatters over it.
              The expansion also caps the compaction walk, so no
              Compact ever lands: full price on every stage.
      sketch  default — HLL distincts give the memo real join output
              cardinalities (out = probe * build / max(nd)), so it
              pulls the filtered orders join to the bottom and the
              expanding partsupp join to the top; the compaction
              gate wraps the ~2% orders output and the remaining
              probes, the 4x expansion and the aggregation all run
              at a fraction of the batch width.

    All aggregates are exact-int (count/min/max + int sums), so the
    two plans must return bit-identical rows."""
    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.models import tpch

    eng = Engine(mesh=None)
    t0 = time.time()
    sf = rows / tpch.LINEITEM_PER_SF
    ts = eng.clock.now()
    gens = {
        "lineitem": lambda: tpch.gen_lineitem(sf, rows=rows,
                                              encoded=True),
        "orders": lambda: tpch.gen_orders(sf),
        "supplier": lambda: tpch.gen_supplier(sf),
        "part": lambda: tpch.gen_part(sf),
        "partsupp": lambda: tpch.gen_partsupp(sf),
    }
    for t, gen in gens.items():
        eng.execute(tpch.DDL[t])
        if t == "lineitem":
            for cn, vals in tpch.LINEITEM_DICTS.items():
                eng.store.set_dictionary(t, cn, vals)
        cols = gen()
        n = len(next(iter(cols.values())))
        for lo in range(0, n, 1 << 14):
            eng.store.insert_columns(
                t, {k: v[lo:lo + (1 << 14)] for k, v in cols.items()},
                ts)
        eng.store.seal(t)
    print(f"# joinorder datagen_s={time.time() - t0:.1f} rows={rows}",
          file=sys.stderr)
    # filter on o_custkey, NOT o_orderkey: custkeys are uniform over
    # the orders while lineitem is clustered by orderkey, so the
    # surviving fact rows spread evenly across compact blocks (a
    # clustered prefix would overflow the per-block capacity and
    # replan uncompacted — a different bench)
    ncust = tpch._n_cust(sf)
    cap = max(ncust // 50, 10)   # ~2% of orders survive
    sql = ("SELECT l_partkey AS pk, count(*) AS n, "
           "sum(l_linenumber) AS sl, sum(ps_availqty) AS sa, "
           "min(l_orderkey) AS mn, max(l_orderkey) AS mx "
           "FROM lineitem "
           "JOIN supplier ON l_suppkey = s_suppkey "
           "JOIN part ON l_partkey = p_partkey "
           "JOIN partsupp ON l_partkey = ps_partkey "
           "JOIN orders ON l_orderkey = o_orderkey "
           f"WHERE o_custkey <= {cap} "
           "GROUP BY l_partkey ORDER BY pk LIMIT 64")
    out = {"joinorder_ckey_cap": cap, "joinorder_ncust": ncust}
    base = None
    for arm in ("syntax", "sketch"):
        eng.drop_device_cache()
        s = eng.session()
        s.vars.set("distsql", "off")
        if arm == "syntax":
            s.vars.set("optimizer_sketch_stats", "off")
        snap0 = eng.metrics.snapshot()
        res = eng.execute(sql, s)  # warmup: compile + upload
        per = []
        for _ in range(repeats):
            t0 = time.time()
            res = eng.execute(sql, s)
            per.append(rows / (time.time() - t0))
        rps = statistics.median(per)
        d = metric_deltas(snap0, eng.metrics.snapshot())
        out[f"joinorder_{arm}_rows_per_sec"] = round(rps)
        out[f"joinorder_{arm}_plans"] = d.get(
            f"sql.optimizer.{'default' if arm == 'syntax' else 'sketch'}"
            "_plans", 0)
        if base is None:
            base = res.rows
        else:
            out["joinorder_parity"] = res.rows == base
        print(f"# joinorder arm={arm} rows_per_sec={rps:.3e}",
              file=sys.stderr)
    syn = out.get("joinorder_syntax_rows_per_sec", 0)
    if syn:
        out["joinorder_speedup"] = round(
            out["joinorder_sketch_rows_per_sec"] / syn, 3)
    return out


def run_dispatchq(rows, workers=2, iters=6):
    """Concurrent distributed dispatch (PR 3 tentpole): N sessions
    issue distributed GROUP BYs at once through the per-mesh FIFO
    dispatcher (the old process-wide collective lock serialized whole
    executions; the queue only serializes dispatch, so query i+1's
    dispatch overlaps query i's device work)."""
    import threading as _th

    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.models import tpch
    from cockroach_tpu.parallel.mesh import make_mesh

    eng = Engine(mesh=make_mesh())
    t0 = time.time()
    tpch.load(eng, sf=rows / tpch.LINEITEM_PER_SF, rows=rows,
              tables=("lineitem",), encoded=True)
    print(f"# dispatchq datagen_s={time.time() - t0:.1f} rows={rows}",
          file=sys.stderr)
    sql = ("SELECT l_returnflag, count(*) AS n, sum(l_quantity) AS q "
           "FROM lineitem GROUP BY l_returnflag ORDER BY l_returnflag")
    eng.execute(sql)  # warmup: compile + upload

    t0 = time.time()
    for _ in range(workers * iters):
        eng.execute(sql)
    serial_qps = workers * iters / (time.time() - t0)

    errors = []

    def worker():
        try:
            s = eng.session()
            for _ in range(iters):
                eng.execute(sql, s)
        except BaseException as e:
            errors.append(e)

    threads = [_th.Thread(target=worker) for _ in range(workers)]
    t0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    conc_qps = workers * iters / (time.time() - t0)
    if errors:
        raise errors[0]
    print(f"# dispatchq serial_qps={serial_qps:.2f} "
          f"concurrent{workers}_qps={conc_qps:.2f}", file=sys.stderr)
    return serial_qps, conc_qps


def run_concurrency(rows, sessions=(1, 8, 32, 100)):
    """Multi-tenant front door (round 11 tentpole): N concurrent
    sessions drive a YCSB-E + TPC-H-shaped q3/q6 mix through the
    admission front door, sub-mesh dispatch on (auto) vs off. The
    analytic statements vary their literals per op, so steady state
    also rides the statement-shape plan cache (one trace per shape,
    not per literal). A distributed-only rung at 8 sessions isolates
    the sub-mesh concurrency win; at the 100-session rung the shed
    thresholds arm and half the sessions run low-priority — their
    rejections must be clean (counted, never stalled) while admitted
    work's p99 stays bounded."""
    import threading as _th

    import numpy as _np

    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.models import tpch
    from cockroach_tpu.parallel.mesh import make_mesh
    from cockroach_tpu.utils.admission import AdmissionRejected
    from cockroach_tpu.workload.ycsb import YCSB

    eng = Engine(mesh=make_mesh())
    ndev = eng.mesh.devices.size
    t0 = time.time()
    tpch.load(eng, sf=rows / tpch.LINEITEM_PER_SF, rows=rows,
              tables=("lineitem", "orders"), encoded=True)
    YCSB(eng, workload="E", records=4000, seed=1).setup()
    print(f"# concurrency datagen_s={time.time() - t0:.1f} "
          f"rows={rows} devices={ndev}", file=sys.stderr)

    def q6_text(rng):
        return ("SELECT sum(l_extendedprice * l_discount) "
                "FROM lineitem WHERE l_quantity < "
                f"{int(rng.integers(20, 40))}")

    def q3_text(rng):
        return ("SELECT o_orderkey, sum(l_extendedprice) AS rev "
                "FROM lineitem JOIN orders ON l_orderkey = o_orderkey "
                f"WHERE l_quantity > {int(rng.integers(5, 30))} "
                "GROUP BY o_orderkey ORDER BY rev DESC, o_orderkey "
                "LIMIT 10")

    # warm every executable OUTSIDE the timed rungs: the full-mesh
    # programs, then each sub-mesh's own trace at every size auto can
    # pick (round-robin acquisition covers all domains of a size)
    rng0 = _np.random.default_rng(0)
    warm = [q6_text(rng0), q3_text(rng0)]
    eng.settings.set("sql.exec.submesh.size", "off")
    parity = [eng.execute(q).rows for q in warm]
    size = ndev // 2
    while size >= 1:
        eng.settings.set("sql.exec.submesh.size", str(size))
        for _ in range(ndev // size):
            got = [eng.execute(q).rows for q in warm]
            assert got == parity, f"sub-mesh size {size} drifted"
        size //= 2
    print("# concurrency warmup done, parity held across sizes",
          file=sys.stderr)

    results = {"conc_parity": True}
    rung = 0
    for arm in ("off", "auto"):
        eng.settings.set("sql.exec.submesh.size", arm)
        for n in sessions:
            rung += 1
            iters = max(2, 64 // n)
            shed_armed = n >= 100
            if shed_armed:
                eng.settings.set("sql.admission.shed.queue_depth", 48)
            lat = {"ycsb": [], "q6": [], "q3": []}
            rejects = [0]
            errors: list = []
            lock = _th.Lock()

            def worker(idx, iters=iters, shed_armed=shed_armed,
                       lat=lat, rejects=rejects, errors=errors,
                       rung=rung):
                try:
                    s = eng.session()
                    if shed_armed and idx % 2 == 1:
                        s.vars.set("admission_priority", "low")
                    rng = _np.random.default_rng(7000 + idx)
                    d = YCSB(eng, workload="E", records=4000,
                             seed=2000 + idx)
                    # disjoint insert keyspace per (rung, worker):
                    # every rung builds fresh drivers, so the offset
                    # must never repeat across rungs either
                    d.next_key = 4000 + \
                        (rung * 128 + idx + 1) * 1_000_000
                    for _ in range(iters):
                        r = rng.random()
                        t1 = time.monotonic()
                        try:
                            if r < 0.5:
                                d.step()
                                kind = "ycsb"
                            elif r < 0.8:
                                eng.execute(q6_text(rng), s)
                                kind = "q6"
                            else:
                                eng.execute(q3_text(rng), s)
                                kind = "q3"
                        except AdmissionRejected:
                            with lock:
                                rejects[0] += 1
                            continue
                        with lock:
                            lat[kind].append(time.monotonic() - t1)
                except BaseException as e:  # pragma: no cover
                    errors.append(e)

            threads = [_th.Thread(target=worker, args=(i,))
                       for i in range(n)]
            t0 = time.time()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.time() - t0
            if errors:
                raise errors[0]
            done = sum(len(v) for v in lat.values())
            ops = done / wall if wall else 0.0
            ana = sorted(lat["q6"] + lat["q3"])
            p50 = p99 = 0.0
            if ana:
                p50 = ana[len(ana) // 2] * 1000
                p99 = ana[min(len(ana) - 1,
                              int(len(ana) * 0.99))] * 1000
            key = f"conc_{arm}_{n}"
            results[f"{key}_ops_per_sec"] = round(ops, 1)
            results[f"{key}_p50_ms"] = round(p50, 1)
            results[f"{key}_p99_ms"] = round(p99, 1)
            if shed_armed:
                results[f"{key}_rejected"] = rejects[0]
                eng.settings.set("sql.admission.shed.queue_depth", 0)
            print(f"# concurrency arm={arm} n={n} "
                  f"ops_per_sec={ops:.1f} p50_ms={p50:.1f} "
                  f"p99_ms={p99:.1f} rejected={rejects[0]}",
                  file=sys.stderr)

    # distributed-only rung: 8 sessions of small distributed q6
    # variants — the shape the sub-mesh pool exists for
    dist = {}
    for arm in ("off", "auto"):
        eng.settings.set("sql.exec.submesh.size", arm)
        n, iters = 8, 6
        errors = []

        def dworker(idx, errors=errors):
            try:
                s = eng.session()
                rng = _np.random.default_rng(9000 + idx)
                for _ in range(6):
                    eng.execute(q6_text(rng), s)
            except BaseException as e:  # pragma: no cover
                errors.append(e)

        threads = [_th.Thread(target=dworker, args=(i,))
                   for i in range(n)]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.time() - t0
        if errors:
            raise errors[0]
        dist[arm] = n * iters / wall if wall else 0.0
        print(f"# concurrency dist8 arm={arm} "
              f"qps={dist[arm]:.2f}", file=sys.stderr)
    eng.settings.set("sql.exec.submesh.size", "auto")
    results["conc_dist8_off_qps"] = round(dist["off"], 2)
    results["conc_dist8_auto_qps"] = round(dist["auto"], 2)
    results["conc_dist8_speedup"] = \
        round(dist["auto"] / dist["off"], 3) if dist["off"] else 0.0
    return results


def run_oltp_batch(records: int = 20000, steps: int = 6000,
                   sessions=(32, 1000)):
    """Fused OLTP lane A/B (round 18 tentpole): YCSB-A (50% point
    read / 50% point update, zipfian) at 32 and 1000 concurrent
    sessions, oltp_batch=off (per-statement lane, one mirror read /
    one txn commit per statement) vs auto (cross-session batch
    fusion + group commit: one multi-key mirror probe and one commit
    per window). An analytic tenant runs a q6-style aggregate on a
    duty cycle throughout, so the OLTP rates are measured with the
    device path live — the interleaving the fused lane exists to
    survive — without a busy loop saturating the interpreter.
    Metric deltas around the auto arm verify the group-commit
    shape: one proposal per fused write window, commands/proposal =
    average window size. Retries are client-side txn restarts: the
    off arm burns them on zipfian write-write races, the single
    write collector serializes them away in auto."""
    import threading as _th

    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.models import tpch
    from cockroach_tpu.server import pgfront
    from cockroach_tpu.workload.ycsb import YCSB

    eng = Engine()
    # r19 satellite: sub-default GIL switch quantum. The r14 bars
    # carried a caveat — an analytic statement holding the GIL for the
    # full 5ms default quantum stretches batch-window close latency.
    # sql.exec.switch_interval is the serving-path lever (armed by
    # PgServer.start); the bench arms it identically so the oltp bars
    # now price the lane with the quantum the front door serves under.
    switch = float(os.environ.get("BENCH_SWITCH_INTERVAL", "0.001"))
    eng.settings.set("sql.exec.switch_interval", switch)
    pgfront.apply_switch_interval(eng.settings)
    t0 = time.time()
    wl = YCSB(eng, workload="A", records=records, seed=1)
    wl.setup()
    arows = 1 << 14
    tpch.load(eng, sf=arows / tpch.LINEITEM_PER_SF, rows=arows,
              tables=("lineitem",), encoded=True)
    print(f"# oltpbatch datagen_s={time.time() - t0:.1f} "
          f"records={records}", file=sys.stderr)
    # warm both lanes + the analytic plan outside the timed arms
    wl.run_concurrent(steps=256, workers=8,
                      session_vars={"oltp_batch": "off"})
    wl.run_concurrent(steps=256, workers=8,
                      session_vars={"oltp_batch": "auto"})
    q6 = ("SELECT sum(l_extendedprice * l_discount) FROM lineitem "
          "WHERE l_quantity < 24")
    eng.execute(q6)

    results = {"oltp_records": records, "oltp_steps": steps,
               "oltp_switch_interval": switch}
    for n in sessions:
        per_arm = {}
        for arm in ("off", "auto"):
            stop = _th.Event()
            ana_ops = [0]

            def analytic(stop=stop, ana_ops=ana_ops):
                # duty-cycled, not a busy loop: a spinning analytic
                # thread just measures GIL contention, not the lane
                s = eng.session()
                while not stop.is_set():
                    eng.execute(q6, s)
                    ana_ops[0] += 1
                    stop.wait(0.15)

            snap0 = eng.metrics.snapshot()
            ath = _th.Thread(target=analytic)
            ath.start()
            try:
                r = wl.run_concurrent(
                    steps=steps, workers=n,
                    session_vars={"oltp_batch": arm},
                    record_latency=True)
            finally:
                stop.set()
                ath.join()
            snap1 = eng.metrics.snapshot()
            per_arm[arm] = r
            key = f"oltp_{arm}_{n}"
            results[f"{key}_ops_per_sec"] = round(r["ops_per_sec"], 1)
            results[f"{key}_p50_ms"] = round(r.get("p50_ms", 0.0), 3)
            results[f"{key}_p99_ms"] = round(r.get("p99_ms", 0.0), 3)
            results[f"{key}_retries"] = r["retries"]
            if arm == "auto":
                windows = (snap1.get("exec.oltp.batch.windows", 0)
                           - snap0.get("exec.oltp.batch.windows", 0))
                fused = (snap1.get("exec.oltp.batch.fused", 0)
                         - snap0.get("exec.oltp.batch.fused", 0))
                props = (
                    snap1.get("kv.raft.groupcommit.proposals", 0)
                    - snap0.get("kv.raft.groupcommit.proposals", 0))
                cmds = (
                    snap1.get("kv.raft.groupcommit.commands", 0)
                    - snap0.get("kv.raft.groupcommit.commands", 0))
                results[f"oltp_auto_{n}_windows"] = windows
                results[f"oltp_auto_{n}_fused_stmts"] = fused
                results[f"oltp_auto_{n}_gc_proposals"] = props
                results[f"oltp_auto_{n}_gc_commands"] = cmds
                results[f"oltp_auto_{n}_cmds_per_proposal"] = \
                    round(cmds / props, 2) if props else 0.0
            print(f"# oltpbatch arm={arm} n={n} "
                  f"ops_per_sec={r['ops_per_sec']:.1f} "
                  f"p99_ms={r.get('p99_ms', 0.0):.3f} "
                  f"analytic_ops={ana_ops[0]}", file=sys.stderr)
        off = per_arm["off"]["ops_per_sec"]
        results[f"oltp_batch_speedup_{n}"] = \
            round(per_arm["auto"]["ops_per_sec"] / off, 3) if off \
            else 0.0
    return results


def run_frontdoor(sessions=(1000, 10000)):
    """Round-19 tentpole A/B: the selector reactor front door
    (pgwire_frontend=reactor) vs thread-per-connection (threads) at
    1K/10K CONNECTED sessions, almost all parked. Per rung: wall time
    to connect+authenticate N sessions, RSS per parked session,
    process thread count with everything idle, and point-read /
    small-analytic latency from live tenants measured WHILE the idle
    fleet is parked (the front door's job is that parked sessions
    cost nothing — the live tenants shouldn't feel them). The
    threads arm stops at 1K: a thread per idle session at 10K is the
    pathology the reactor exists to remove, not a bar worth burning
    ~80GB of stacks to print. A quota rung on the reactor arms
    sql.admission.tenant.slots and sends a noisy analytic tenant
    against quiet tenants — quiet p99 must hold while the noisy
    tenant's excess statements queue (admission.tenant.slot_waits)."""
    import socket as _socket
    import struct as _struct
    import threading as _th

    from cockroach_tpu.cli import PgClient
    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.server.pgwire import PgServer

    # fd headroom: both ends of every connection live in this process
    want = max(sessions) * 2 + 1024
    try:
        import resource
        soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        if soft < want:
            resource.setrlimit(
                resource.RLIMIT_NOFILE,
                (min(hard, want) if hard > 0 else want, hard))
        soft, _ = resource.getrlimit(resource.RLIMIT_NOFILE)
    except Exception:
        soft = 1024
    cap = max(64, (soft - 1024) // 2)
    sessions = tuple(min(n, cap) for n in sessions)

    eng = Engine()
    s0 = eng.session()
    eng.execute("CREATE TABLE fd (k INT PRIMARY KEY, v FLOAT)", s0)
    eng.execute("INSERT INTO fd VALUES "
                + ", ".join(f"({i}, {i}.5)" for i in range(512)), s0)
    ana_sql = "SELECT sum(k + v) FROM fd WHERE k < 400"
    eng.execute(ana_sql, s0)                    # warm the plan
    eng.execute("SELECT v FROM fd WHERE k = 3", s0)

    def rss_kb():
        with open("/proc/self/status") as f:
            for ln in f:
                if ln.startswith("VmRSS:"):
                    return int(ln.split()[1])
        return 0

    sp = (b"user\x00root\x00database\x00defaultdb\x00\x00")
    startup = _struct.pack("!I", len(sp) + 8) \
        + _struct.pack("!I", 196608) + sp

    def connect_idle(addr):
        sock = _socket.create_connection(addr, timeout=120.0)
        sock.sendall(startup)
        sock.settimeout(120.0)
        buf = b""
        while True:
            off = 0
            while len(buf) - off >= 5:
                (ln,) = _struct.unpack_from("!I", buf, off + 1)
                if len(buf) - off < 1 + ln:
                    break
                if buf[off:off + 1] == b"Z":
                    return sock
                off += 1 + ln
            buf = buf[off:]
            b = sock.recv(4096)
            if not b:
                raise ConnectionError("closed during startup")
            buf += b

    def p_ms(lat, q):
        if not lat:
            return 0.0
        lat = sorted(lat)
        return lat[min(len(lat) - 1, int(len(lat) * q))] * 1000

    results = {}
    for arm in ("reactor", "threads"):
        srv = PgServer(eng, "127.0.0.1", 0, frontend=arm).start()
        addr = srv.addr
        try:
            for n in sessions:
                if arm == "threads" and n > 1000:
                    print(f"# frontdoor arm=threads n={n} skipped "
                          "(thread-per-idle-session at 10K is the "
                          "pathology under test, not a bar)",
                          file=sys.stderr)
                    continue
                idle: list = []
                errors: list = []
                ilock = _th.Lock()
                rss0, th0 = rss_kb(), _th.active_count()
                t0 = time.time()

                def connector(k, per=(n + 15) // 16):
                    got = []
                    try:
                        for _ in range(min(per, n - k * per)):
                            got.append(connect_idle(addr))
                    except BaseException as e:
                        errors.append(e)
                    with ilock:
                        idle.extend(got)

                cth = [_th.Thread(target=connector, args=(k,))
                       for k in range(16)]
                for t in cth:
                    t.start()
                for t in cth:
                    t.join()
                connect_s = time.time() - t0
                if errors:
                    raise errors[0]
                time.sleep(1.0)          # let startup workers park
                rss1, th1 = rss_kb(), _th.active_count()
                # live tenants against the parked fleet: 4 point-read
                # sessions + 1 analytic session
                lat_pt: list = []
                lat_ana: list = []
                llock = _th.Lock()

                def oltp(idx):
                    try:
                        c = PgClient(*addr)
                        got = []
                        for i in range(64):
                            t1 = time.monotonic()
                            c.query("SELECT v FROM fd WHERE k = "
                                    f"{(idx * 64 + i) % 512}")
                            got.append(time.monotonic() - t1)
                        c.close()
                        with llock:
                            lat_pt.extend(got)
                    except BaseException as e:
                        errors.append(e)

                def analytic():
                    try:
                        c = PgClient(*addr)
                        got = []
                        for _ in range(8):
                            t1 = time.monotonic()
                            c.query(ana_sql)
                            got.append(time.monotonic() - t1)
                        c.close()
                        with llock:
                            lat_ana.extend(got)
                    except BaseException as e:
                        errors.append(e)

                live = [_th.Thread(target=oltp, args=(i,))
                        for i in range(4)]
                live.append(_th.Thread(target=analytic))
                for t in live:
                    t.start()
                for t in live:
                    t.join()
                if errors:
                    raise errors[0]
                key = f"fd_{arm}_{n}"
                results[f"{key}_connect_s"] = round(connect_s, 2)
                results[f"{key}_rss_kb_per_idle"] = \
                    round(max(0, rss1 - rss0) / n, 1)
                results[f"{key}_threads"] = th1 - th0
                results[f"{key}_oltp_p50_ms"] = \
                    round(p_ms(lat_pt, 0.50), 2)
                results[f"{key}_oltp_p99_ms"] = \
                    round(p_ms(lat_pt, 0.99), 2)
                results[f"{key}_ana_p99_ms"] = \
                    round(p_ms(lat_ana, 0.99), 2)
                print(f"# frontdoor arm={arm} n={n} "
                      f"connect_s={connect_s:.2f} "
                      f"rss_kb_per_idle={results[f'{key}_rss_kb_per_idle']} "
                      f"threads=+{th1 - th0} "
                      f"oltp_p99_ms={results[f'{key}_oltp_p99_ms']} "
                      f"ana_p99_ms={results[f'{key}_ana_p99_ms']}",
                      file=sys.stderr)
                for s in idle:
                    try:
                        s.close()
                    except OSError:
                        pass
                # drain teardowns before the next rung measures RSS
                deadline = time.time() + 60
                while (getattr(srv._impl, "_sessions", None)
                       and len(srv._impl._sessions) > 0
                       and time.time() < deadline):
                    time.sleep(0.1)
        finally:
            srv.stop()

    # quota rung (reactor): noisy analytic tenant vs quiet tenants at
    # the 1K-mixed shape — tenant slot quota parks the noisy excess
    srv = PgServer(eng, "127.0.0.1", 0, frontend="reactor").start()
    addr = srv.addr
    try:
        def quiet_run(lat_out):
            errors2: list = []

            def quiet(idx):
                try:
                    c = PgClient(*addr)
                    c.query("SET application_name = 'fd_quiet'")
                    got = []
                    for _ in range(16):
                        t1 = time.monotonic()
                        c.query(ana_sql)
                        got.append(time.monotonic() - t1)
                    c.close()
                    lat_out.extend(got)
                except BaseException as e:
                    errors2.append(e)

            ths = [_th.Thread(target=quiet, args=(i,))
                   for i in range(2)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            if errors2:
                raise errors2[0]

        base_lat: list = []
        quiet_run(base_lat)
        eng.settings.set("sql.admission.tenant.slots", 2)
        waits0 = eng.admission.tenant_slot_waits
        stop = _th.Event()

        def noisy():
            try:
                c = PgClient(*addr)
                c.query("SET application_name = 'fd_noisy'")
                while not stop.is_set():
                    c.query(ana_sql)
                c.close()
            except BaseException:
                pass

        storm = [_th.Thread(target=noisy) for _ in range(8)]
        for t in storm:
            t.start()
        time.sleep(0.5)
        noisy_lat: list = []
        quiet_run(noisy_lat)
        stop.set()
        for t in storm:
            t.join(timeout=30)
        waits = eng.admission.tenant_slot_waits - waits0
        eng.settings.set("sql.admission.tenant.slots", 0)
        results["fd_quota_quiet_p99_ms"] = round(p_ms(base_lat, 0.99), 2)
        results["fd_quota_quiet_p99_noisy_ms"] = \
            round(p_ms(noisy_lat, 0.99), 2)
        results["fd_quota_slot_waits"] = waits
        print(f"# frontdoor quota quiet_p99_ms="
              f"{results['fd_quota_quiet_p99_ms']} "
              f"noisy-storm quiet_p99_ms="
              f"{results['fd_quota_quiet_p99_noisy_ms']} "
              f"slot_waits={waits}", file=sys.stderr)
    finally:
        srv.stop()
    return results


def run_coldstart(query: str, rows: int):
    """Leaf: time-to-first-result for one headline query in THIS
    fresh process (round 9 tentpole). Data generation is excluded;
    the TTFR clock covers parse -> plan -> XLA compile (or, on a warm
    persistent cache, deserialize) -> execute -> decode. The parent
    runs this twice against one shared cache dir: the first child is
    the cold arm, the second must serve its executables from disk."""
    import hashlib
    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.models import tpch

    eng = Engine()
    tables = (tpch.ALL_TABLES if query in
              ("q2", "q3", "q4", "q5", "q7", "q8", "q9", "q10", "q18")
              else ("lineitem",))
    t0 = time.time()
    tpch.load(eng, sf=rows / tpch.LINEITEM_PER_SF, rows=rows,
              tables=tables, encoded=True)
    gen_s = time.time() - t0
    # warm arm only: a restarted node replays the previous run's
    # shapes journal at STARTUP (persistent cache makes each replayed
    # compile a deserialization), so the first real query finds its
    # executable resident. The prewarm bill is startup time, not TTFR
    # — reported separately as prewarm_s.
    prewarm_s = 0.0
    prewarmed = 0
    if os.environ.get("BENCH_PREWARM", "0") == "1":
        t0 = time.time()
        prewarmed = eng.prewarm(top_k=8)
        prewarm_s = time.time() - t0
    s = eng.session()
    t0 = time.time()
    res = eng.execute(tpch.QUERIES[query], s)
    ttfr = time.time() - t0
    snap = eng.metrics.snapshot()
    digest = hashlib.sha256(repr(res.rows).encode()).hexdigest()[:16]
    print(f"# coldstart {query}: rows={rows} ttfr_s={ttfr:.3f} "
          f"datagen_s={gen_s:.1f} prewarmed={prewarmed} "
          f"prewarm_s={prewarm_s:.2f} "
          f"cache_hit={snap.get('exec.compile.cache_hit', 0)} "
          f"cache_miss={snap.get('exec.compile.cache_miss', 0)} "
          f"compile_s={snap.get('exec.compile.seconds', 0):.2f}",
          file=sys.stderr)
    return {
        "metric": f"coldstart_{query}_ttfr_s",
        "value": round(ttfr, 4), "unit": "s", "rows": rows,
        "digest": digest, "result_rows": len(res.rows),
        "prewarmed": prewarmed, "prewarm_s": round(prewarm_s, 3),
        "cache_hit": snap.get("exec.compile.cache_hit", 0),
        "cache_miss": snap.get("exec.compile.cache_miss", 0),
        "compile_s": round(snap.get("exec.compile.seconds", 0.0), 3),
    }


def run_multihost(rows: int, repeat: int = 3) -> dict:
    """Round-15 multi-host pod ladder: 1/2/4 REAL host processes on
    localhost (server/hostd.py, jax.distributed rendezvous + socket
    fabric), each owning its contiguous shard of lineitem, running the
    combine-exact partial-agg rungs through the hierarchical merge
    tree (fanout 2), plus a flat fan-in (fanout 0) A/B arm at 4 hosts.

    Caveat recorded with the numbers: on one machine every "host"
    shares the same CPU cores and XLA-CPU cannot run cross-process
    device computations, so rows/s here prices the control/data-plane
    orchestration, NOT pod compute scaling — the transferable signal
    is the BYTES story (gateway ingest shrinking under the tree while
    interior hosts absorb merge bytes)."""
    import socket as _socket
    here = os.path.dirname(os.path.abspath(__file__))

    def _pod(n, fanout):
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env.setdefault("JAX_ENABLE_X64", "1")
        env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")

        def cmd(pid):
            return [sys.executable, "-m", "cockroach_tpu.server.hostd",
                    "--process-id", str(pid),
                    "--num-processes", str(n),
                    "--coordinator", f"127.0.0.1:{port}",
                    "--fanout", str(fanout), "--rows", str(rows),
                    "--queries", "q6,groupby",
                    "--repeat", str(repeat)]

        workers = [subprocess.Popen(cmd(pid), env=env, cwd=here,
                                    stdout=subprocess.DEVNULL,
                                    stderr=subprocess.DEVNULL)
                   for pid in range(1, n)]
        try:
            proc = subprocess.run(cmd(0), env=env, cwd=here,
                                  capture_output=True, text=True,
                                  timeout=900)
        finally:
            for w in workers:
                try:
                    w.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    w.kill()
        if proc.returncode != 0:
            print(f"# multihost h{n} fanout={fanout} failed "
                  f"rc={proc.returncode}", file=sys.stderr)
            sys.stderr.write(proc.stderr[-2000:])
            return None
        line = next((ln for ln in proc.stdout.splitlines()
                     if ln.startswith("{")), None)
        return json.loads(line) if line else None

    out = {"multihost_rows": rows}
    base = {}
    for n in (1, 2, 4):
        pod = _pod(n, fanout=2)
        if pod is None:
            continue
        gwm = pod.get("metrics", {}).get("0", {})
        merged = sum((m or {}).get("exec.multihost.merge.bytes", 0)
                     for m in pod.get("metrics", {}).values())
        out[f"multihost_h{n}_gateway_recv_bytes"] = \
            gwm.get("shuffle.bytes.received", 0)
        out[f"multihost_h{n}_merge_bytes"] = merged
        for q, t in pod.get("timings", {}).items():
            out[f"multihost_{q}_h{n}_rows_per_sec"] = \
                round(t["rows_per_s"])
            if n == 1:
                base[q] = t["rows_per_s"]
            elif base.get(q):
                out[f"multihost_{q}_h{n}_vs_h1"] = \
                    round(t["rows_per_s"] / base[q], 3)
            print(f"# multihost h{n} fanout=2 {q} "
                  f"rows_per_sec={t['rows_per_s']:.3e} "
                  f"gw_recv={gwm.get('shuffle.bytes.received', 0)} "
                  f"merged={merged}", file=sys.stderr)
    flat = _pod(4, fanout=0)
    if flat is not None:
        gwm = flat.get("metrics", {}).get("0", {})
        out["multihost_h4_flat_gateway_recv_bytes"] = \
            gwm.get("shuffle.bytes.received", 0)
        for q, t in flat.get("timings", {}).items():
            out[f"multihost_{q}_h4_flat_rows_per_sec"] = \
                round(t["rows_per_s"])
        tree_b = out.get("multihost_h4_gateway_recv_bytes", 0)
        flat_b = out["multihost_h4_flat_gateway_recv_bytes"]
        if flat_b:
            # < 1.0 = the tree shed gateway ingress onto interior hosts
            out["multihost_h4_gateway_bytes_tree_vs_flat"] = \
                round(tree_b / flat_b, 3)
        print(f"# multihost h4 fanout=0 gw_recv={flat_b} "
              f"(tree gw_recv={tree_b})", file=sys.stderr)
    return out


def run_elastic(rows: int, repeat: int = 8) -> dict:
    """Round-16 elastic pod lanes (server/hostd.py --elastic): real
    host processes over the socket KV coordinator + shard leases.

    Lane A (failover): a 4-host pod runs a sustained groupby/join
    statement loop; one worker is SIGKILLed mid-loop. The gateway must
    convict it, move its shard leases to survivors, replan, and finish
    with ZERO failed statements — every run bit-identical (the
    ``consistent`` flag compares all runs of a query pairwise).

    Lane B (scale-out): a 2-host pod runs the same loop while two more
    hosts late-join the RUNNING pod; leases rebalance online (old
    owners keep serving until the epoch flip) and the final assignment
    must span all four hosts, again with every run identical.

    Same caveat as the round-15 multihost lanes: all "hosts" share one
    machine's cores, so rows/s prices the orchestration planes, not
    pod compute scaling — the transferable signals are the zero failed
    statements, the failover/lease-move counts, and the rebalance
    bytes that moved through the movement scheduler's lease."""
    import tempfile as _tempfile
    here = os.path.dirname(os.path.abspath(__file__))

    def _env():
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env.setdefault("JAX_ENABLE_X64", "1")
        env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
        return env

    def _pod(initial, late=0, kill_after=None, join_after=None):
        tmp = _tempfile.mkdtemp(prefix="bench-elastic-")
        addr_file = os.path.join(tmp, "kv_addr")
        base = [sys.executable, "-m", "cockroach_tpu.server.hostd",
                "--elastic", "--rows", str(rows), "--nshards", "8",
                "--queries", "groupby,join", "--repeat", str(repeat),
                "--statement-gap", "0.15", "--fanout", "2",
                "--flow-timeout", "60",
                "--heartbeat-interval", "0.1",
                "--liveness-window", "1.0"]
        env = _env()
        founder = subprocess.Popen(
            base + ["--process-id", "0", "--kv-addr-file", addr_file,
                    "--initial-hosts", str(initial)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            env=env, cwd=here, text=True)
        workers, joiners = [], []
        try:
            deadline = time.time() + 120
            while not (os.path.exists(addr_file)
                       and open(addr_file).read().strip()):
                if founder.poll() is not None or time.time() > deadline:
                    err = founder.communicate()[1]
                    print(f"# elastic founder never published the KV "
                          f"addr:\n{err[-2000:]}", file=sys.stderr)
                    return None
                time.sleep(0.05)
            addr = open(addr_file).read().strip()
            for pid in range(1, initial):
                workers.append(subprocess.Popen(
                    base + ["--process-id", str(pid),
                            "--kv-addr", addr],
                    stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL, env=env, cwd=here))
            if join_after is not None:
                time.sleep(join_after)
                for pid in range(initial, initial + late):
                    joiners.append(subprocess.Popen(
                        base + ["--process-id", str(pid),
                                "--kv-addr", addr, "--late-join"],
                        stdout=subprocess.DEVNULL,
                        stderr=subprocess.DEVNULL, env=env, cwd=here))
            if kill_after is not None:
                time.sleep(kill_after)
                workers[-1].kill()      # the failover lane's victim
            out, err = founder.communicate(timeout=600)
        finally:
            grace = time.monotonic() + 60.0
            for w in workers + joiners:
                try:
                    w.wait(timeout=max(0.1,
                                       grace - time.monotonic()))
                except subprocess.TimeoutExpired:
                    w.kill()
            if founder.poll() is None:
                founder.kill()
        if founder.returncode != 0:
            print(f"# elastic pod rc={founder.returncode}\n"
                  f"{err[-2000:]}", file=sys.stderr)
            return None
        line = next((ln for ln in out.splitlines()
                     if ln.startswith("{")), None)
        return json.loads(line) if line else None

    def _metric_sum(pod, key):
        return sum((m or {}).get(key, 0)
                   for m in pod.get("metrics", {}).values())

    out = {"elastic_rows": rows, "elastic_statements_per_query": repeat}

    kill = _pod(initial=4, kill_after=4.0)
    if kill is not None:
        res = kill.get("results", {})
        failed = sum(1 for r in res.values() if "error" in r)
        out["elastic_kill_failed_statements"] = failed
        out["elastic_kill_consistent"] = int(all(
            r.get("consistent") for r in res.values()
            if "error" not in r) and not failed)
        gwm = kill.get("metrics", {}).get("0", {})
        out["elastic_kill_failovers"] = \
            gwm.get("distsql.degrade.failover", 0)
        out["elastic_kill_lease_failovers"] = \
            _metric_sum(kill, "exec.lease.failovers")
        out["elastic_kill_live_hosts"] = \
            len(kill.get("membership", {}).get("live", []))
        for q, t in kill.get("timings", {}).items():
            out[f"elastic_kill_{q}_rows_per_sec"] = \
                round(t["rows_per_s"])
        print(f"# elastic kill-mid-bench: failed={failed} "
              f"consistent={out['elastic_kill_consistent']} "
              f"failovers={out['elastic_kill_failovers']} "
              f"live={kill.get('membership', {}).get('live')}",
              file=sys.stderr)

    scale = _pod(initial=2, late=2, join_after=3.0)
    if scale is not None:
        res = scale.get("results", {})
        failed = sum(1 for r in res.values() if "error" in r)
        out["elastic_scaleout_consistent"] = int(all(
            r.get("consistent") for r in res.values()
            if "error" not in r) and not failed)
        mb = scale.get("membership", {})
        out["elastic_scaleout_live_hosts"] = len(mb.get("live", []))
        owners = set(mb.get("leases", {}).get("lineitem", {}).values())
        out["elastic_scaleout_lease_owners"] = len(owners)
        out["elastic_scaleout_lease_moves"] = \
            _metric_sum(scale, "exec.lease.moves")
        out["elastic_scaleout_rebalance_bytes"] = \
            _metric_sum(scale, "exec.movement.rebalance.bytes")
        for q, t in scale.get("timings", {}).items():
            out[f"elastic_scaleout_{q}_rows_per_sec"] = \
                round(t["rows_per_s"])
        print(f"# elastic scale-out 2->4: "
              f"consistent={out['elastic_scaleout_consistent']} "
              f"live={mb.get('live')} owners={sorted(owners)} "
              f"moves={out['elastic_scaleout_lease_moves']} "
              f"rebal_bytes={out['elastic_scaleout_rebalance_bytes']}",
              file=sys.stderr)
    return out


def _emit_leaf(rec: dict) -> None:
    """One leaf's JSON line, stamped with the device JAX ran it on, so
    no number travels without the platform it was measured on."""
    import jax
    devs = jax.devices()
    print(json.dumps({**rec, "platform": devs[0].platform,
                      "device_kind": devs[0].device_kind,
                      "device_count": len(devs)}))


def run_child(rows: int, query: str, timeout: int, attempts: int = 2,
              mode: str = "tpu_child", extra_env: dict | None = None):
    """One query/measurement in its own subprocess: a fresh backend
    per query, so a hung compile costs ONE attempt, not the whole
    bench. mode="cpu" runs the same plan under
    XLA-CPU (sequenced BEFORE the TPU section — both are host-CPU
    hungry, so overlapping them would bias the ratio)."""
    env = dict(os.environ, BENCH_MODE=mode, BENCH_ROWS=str(rows),
               BENCH_QUERY=query, BENCH_CPU="0")
    if mode == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        env["BENCH_REPEATS"] = "3"
    if mode == "coldstart_child":
        # TTFR is a host/compile story: measured on XLA-CPU
        env["JAX_PLATFORMS"] = "cpu"
    if mode == "concurrency_child":
        # the multi-tenant front-door bench measures the CPU-host
        # mesh (ISSUE round 11); sub-mesh routing needs >1 device
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    if mode == "movement_child":
        # the fakedist cluster is N in-process Engines over a local
        # transport; page assembly + frame exchange are host paths, so
        # measure on XLA-CPU (each Engine runs single-device — the
        # distribution axis is across Engines, not mesh devices)
        env["JAX_PLATFORMS"] = "cpu"
    if mode == "elastic_child":
        # elastic pod lanes spawn real hostd --elastic processes;
        # like the multihost lanes they measure the control/data
        # planes on XLA-CPU hosts
        env["JAX_PLATFORMS"] = "cpu"
    if mode == "tpcc_child":
        # TPC-C is a HOST path (txn machinery, index fastpaths);
        # statements that do fall to a compiled scan compile for the
        # host CPU, not a device dispatch each. YCSB stays on the
        # default platform: the OLTP lane never dispatches to the
        # device.
        env["JAX_PLATFORMS"] = "cpu"
    if mode == "oltpbatch_child":
        # the fused OLTP lane is a host path (mirror probes, group
        # commit); its analytic tenant compiles one small aggregate —
        # both run on XLA-CPU
        env["JAX_PLATFORMS"] = "cpu"
    if mode == "frontdoor_child":
        # the 1K/10K-session front-door rungs price socket plumbing,
        # frame parsing, and thread scheduling — pure host paths; the
        # one analytic plan belongs on XLA-CPU
        env["JAX_PLATFORMS"] = "cpu"
    if extra_env:
        env.update(extra_env)
    for attempt in range(attempts):
        try:
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__)], env=env,
                capture_output=True, text=True, timeout=timeout)
        except subprocess.TimeoutExpired:
            print(f"# {query}: attempt {attempt + 1} timed out after "
                  f"{timeout}s", file=sys.stderr)
            continue
        sys.stderr.write(out.stderr)
        if out.returncode != 0:
            print(f"# {query}: child failed rc={out.returncode}",
                  file=sys.stderr)
            continue
        for line in out.stdout.splitlines():
            if line.startswith("{"):
                return json.loads(line)
    print(f"# {query}: all {attempts} attempts failed, skipping",
          file=sys.stderr)
    return None


def main():
    mode = os.environ.get("BENCH_MODE", "tpu")
    # Default sized to finish in a few minutes on one chip (upload
    # dominates warmup). BENCH_ROWS=$((1<<27)) reproduces
    # the headline beyond-2^27 run recorded in BENCHMARKS.md.
    default_rows = 1 << 22 if mode == "cpu" else 1 << 25
    rows = int(os.environ.get("BENCH_ROWS", default_rows))
    qenv = os.environ.get("BENCH_QUERY", "all")
    # default ladder: scan/agg/join shapes plus the deep-join suite
    # queries the round-2 verdict asked for (q3/q9/q18). q9's
    # composite-key partsupp join and q18's IN-subquery now ride the
    # packed direct-address path (~8s and ~3s per exec at 2^20, down
    # from ~140s), so they run by default; BENCH_SUITE=0 drops them
    # if a ladder run needs to stay short.
    queries = (["q6", "q1", "q14", "q3"] if qenv == "all"
               else [q.strip() for q in qenv.split(",")])
    if qenv == "all" and os.environ.get("BENCH_SUITE", "1") == "1":
        queries += ["q9", "q18"]
    pipeline = int(os.environ.get("BENCH_PIPELINE", 16))
    repeats = int(os.environ.get("BENCH_REPEATS", 5))

    # q1/q14 run at resident-friendly row counts; q6 takes the full
    # size. The multi-table suite queries (q3/q9/q18: 3-6-way joins,
    # derived tables, IN-subqueries) run smaller — their cost is joins
    # and host orchestration, not scan rate.
    # suite queries are compile-heavy (hash-strategy GROUP BY while
    # loops: q3 ~5min XLA compile at 2^20) — keep their row counts
    # small so each child stays inside its timeout; their metric is
    # join/plan breadth, not scan rate
    caps = ({"q1": 1 << 25, "q14": 1 << 23, "q3": 1 << 20,
             "q9": 1 << 20, "q18": 1 << 20}
            if mode.startswith("tpu") else {})
    rows_by_query = {q: min(rows, caps.get(q, rows)) for q in queries}

    if mode == "coldstart_child":
        _emit_leaf(run_coldstart(queries[0], rows))
        return
    if mode == "ssb_child":
        flight, per = run_ssb(rows, pipeline,
                              max(3, repeats - 2))
        _emit_leaf({
            "metric": "ssb_flight_rows_per_sec",
            "value": round(flight), "unit": "rows/s", "rows": rows,
            **{f"ssb_{w}_rows_per_sec": round(r)
               for w, r in per.items()},
        })
        return
    if mode == "ycsb_child":
        ops, ops16 = run_ycsb_e(
            int(os.environ.get("BENCH_YCSB_RECORDS", 20000)),
            int(os.environ.get("BENCH_YCSB_STEPS", 2000)))
        _emit_leaf({
            "metric": "ycsb_e_ops_per_sec", "value": round(ops),
            "unit": "ops/s",
            "ycsb_e_c16_ops_per_sec": round(ops16)})
        return
    if mode == "tpcc_child":
        from cockroach_tpu.exec.engine import Engine
        from cockroach_tpu.workload.tpcc import TPCC
        wh = int(os.environ.get("BENCH_TPCC_WAREHOUSES", 10))
        steps = int(os.environ.get("BENCH_TPCC_STEPS", 600))
        eng = Engine()
        w = TPCC(eng, warehouses=wh)
        t0 = time.time()
        w.setup()
        print(f"# tpcc setup_s={time.time() - t0:.1f} "
              f"warehouses={wh}", file=sys.stderr)
        w.run(steps=min(100, steps))  # warm plan caches
        out = w.run(steps=steps)
        print(f"# tpcc: tpm_c={out['tpm_c']:.0f} "
              f"new_orders={out['new_orders']} "
              f"retries={out.get('retries', 0)}", file=sys.stderr)
        _emit_leaf({
            "metric": "tpcc_tpmc", "value": round(out["tpm_c"]),
            "unit": "tpmC", "warehouses": wh})
        return
    if mode == "stream_child":
        on, off = run_stream(rows, max(3, repeats - 2))
        _emit_leaf({
            "metric": "stream_scan_rows_per_sec", "value": round(on),
            "unit": "rows/s", "rows": rows,
            "stream_scan_off_rows_per_sec": round(off),
            "stream_pipeline_speedup": round(on / off, 3) if off else 0,
        })
        return
    if mode == "pallas_child":
        per = run_pallas_ab(rows, max(3, repeats - 2))
        _emit_leaf({
            "metric": "pallas_q1_auto_rows_per_sec",
            "value": per.get("pallas_q1_auto_rows_per_sec", 0),
            "unit": "rows/s", "rows": per.get("pallas_rows", rows),
            **per,
        })
        return
    if mode == "sort_child":
        per = run_sort_ab(rows, max(3, repeats - 2))
        _emit_leaf({
            "metric": "sort_order3_auto_rows_per_sec",
            "value": per.get("sort_order3_auto_rows_per_sec", 0),
            "unit": "rows/s", "rows": per.get("sort_rows", rows),
            **per,
        })
        return
    if mode == "spill_child":
        per = run_spill_ab(rows, max(3, repeats - 2))
        _emit_leaf({
            "metric": "spill_join_auto_rows_per_sec",
            "value": per.get("spill_join_auto_rows_per_sec", 0),
            "unit": "rows/s", "rows": rows,
            **per,
        })
        return
    if mode == "joinskip_child":
        per = run_joinskip_ab(rows, max(3, repeats - 2))
        _emit_leaf({
            "metric": "joinskip_q3_auto_rows_per_sec",
            "value": per.get("joinskip_q3_auto_rows_per_sec", 0),
            "unit": "rows/s", "rows": rows,
            **per,
        })
        return
    if mode == "joinorder_child":
        per = run_joinorder_ab(rows, max(3, repeats - 2))
        _emit_leaf({
            "metric": "joinorder_sketch_rows_per_sec",
            "value": per.get("joinorder_sketch_rows_per_sec", 0),
            "unit": "rows/s", "rows": rows,
            **per,
        })
        return
    if mode == "multihost_child":
        per = run_multihost(rows,
                            int(os.environ.get("BENCH_REPEATS", 3)))
        _emit_leaf({
            "metric": "multihost_groupby_h2_vs_h1",
            "value": per.get("multihost_groupby_h2_vs_h1", 0),
            "unit": "x", "rows": rows,
            **per,
        })
        return
    if mode == "elastic_child":
        per = run_elastic(rows,
                          int(os.environ.get("BENCH_REPEATS", 8)))
        _emit_leaf({
            "metric": "elastic_kill_failed_statements",
            "value": per.get("elastic_kill_failed_statements", -1),
            "unit": "statements", "rows": rows,
            **per,
        })
        return
    if mode == "movement_child":
        per = run_movement_ab(rows, max(3, repeats - 2))
        _emit_leaf({
            "metric": "movement_ratio_2x_1x",
            "value": per.get("movement_ratio_2x_1x", 0),
            "unit": "x", "rows": rows,
            **per,
        })
        return
    if mode == "concurrency_child":
        per = run_concurrency(
            rows, sessions=tuple(int(x) for x in os.environ.get(
                "BENCH_CONCURRENCY_SESSIONS", "1,8,32,100").split(",")))
        _emit_leaf({
            "metric": "conc_dist8_speedup",
            "value": per.get("conc_dist8_speedup", 0),
            "unit": "x", "rows": rows,
            **per,
        })
        return
    if mode == "oltpbatch_child":
        per = run_oltp_batch(
            int(os.environ.get("BENCH_OLTP_RECORDS", 20000)),
            int(os.environ.get("BENCH_OLTP_STEPS", 6000)),
            sessions=tuple(int(x) for x in os.environ.get(
                "BENCH_OLTP_SESSIONS", "32,1000").split(",")))
        _emit_leaf({
            "metric": "oltp_batch_speedup_32",
            "value": per.get("oltp_batch_speedup_32", 0),
            "unit": "x",
            **per,
        })
        return
    if mode == "frontdoor_child":
        per = run_frontdoor(
            sessions=tuple(int(x) for x in os.environ.get(
                "BENCH_FRONTDOOR_SESSIONS", "1000,10000").split(",")))
        _emit_leaf({
            "metric": "fd_reactor_1000_rss_kb_per_idle",
            "value": per.get("fd_reactor_1000_rss_kb_per_idle", 0),
            "unit": "KB/session",
            **per,
        })
        return
    if mode == "dispatchq_child":
        serial, conc = run_dispatchq(rows)
        _emit_leaf({
            "metric": "dispatch_concurrent2_qps",
            "value": round(conc, 2), "unit": "queries/s", "rows": rows,
            "dispatch_serial_qps": round(serial, 2),
            "dispatch_concurrency_speedup":
                round(conc / serial, 3) if serial else 0,
        })
        return
    if mode in ("cpu", "tpu_child"):
        # leaf mode: measure in-process and emit one JSON line
        tag = "cpu " if mode == "cpu" else ""
        results, rows_used, deltas = run(rows_by_query, pipeline,
                                         repeats, tag=tag)
        primary = queries[0]
        _emit_leaf({
            "metric": f"tpch_{primary}_rows_per_sec",
            "value": round(results[primary]),
            "unit": "rows/s",
            "rows": rows_used[primary],
            **{f"{w}_rows_per_sec": round(r)
               for w, r in results.items()
               if not w.endswith("_gbps")},
            **{f"{w[:-5]}_effective_gbps": round(r, 1)
               for w, r in results.items() if w.endswith("_gbps")},
            "metric_deltas": deltas,
        })
        return

    # BENCH_TPCH=0 skips the TPU ladder so a section added below (e.g.
    # the CPU-only coldstart TTFR arms) can be measured alone on a box
    # without the chip — the r06 "measure one child, carry the rest"
    # workflow, without faking a dead ladder as all-children-failed
    bench_tpch = os.environ.get("BENCH_TPCH", "1") != "0"
    cpu = None
    cpu_query = None
    if bench_tpch and os.environ.get("BENCH_CPU", "1") != "0":
        # measured BEFORE the TPU section so the parent's host work
        # cannot depress the CPU number (which would overstate vs_cpu)
        cpu_query = ([q for q in queries if q == "q6"] or queries[:1])[0]
        cpu = run_child(int(os.environ.get("BENCH_CPU_ROWS", 1 << 22)),
                        cpu_query, timeout=600, attempts=1, mode="cpu")

    # healthy children finish well inside this; a wedged compile eats
    # one timeout then retries in a fresh process
    child_timeout = int(os.environ.get(
        "BENCH_CHILD_TIMEOUT", max(900, rows >> 17)))
    results = {}
    rows_used = {}
    gbps_keys = {}
    all_deltas = {}
    for q in (queries if bench_tpch else []):
        # q6 first: the primary metric lands early
        r = run_child(rows_by_query[q], q, child_timeout)
        if r is not None:
            results[q] = r["value"]
            rows_used[q] = r["rows"]
            all_deltas.update(r.get("metric_deltas") or {})
            # round-4 weak #5: the child computed effective_GBps but
            # the parent dropped it, so the roofline metric never
            # reached the persisted BENCH record — forward it
            gbps_keys.update({k: v for k, v in r.items()
                              if k.endswith("_effective_gbps")})
    if bench_tpch and not results:
        print(json.dumps({"metric": "tpch_q6_rows_per_sec", "value": 0,
                          "unit": "rows/s", "vs_baseline": 0,
                          "error": "all bench children failed"}))
        sys.exit(1)
    if results:
        primary = "q6" if "q6" in results else next(iter(results))
        out = {
            "metric": f"tpch_{primary}_rows_per_sec",
            "value": round(results[primary]),
            "unit": "rows/s",
            "vs_baseline": round(results[primary]
                                 / BASELINE_ROWS_PER_SEC, 3),
            "rows": rows_used[primary],
            "baseline_provenance": ("assumed 1.25e8 rows/s colexec Q6 "
                                    "on 3x4vCPU (no published numbers; "
                                    "see bench.py docstring)"),
        }
    else:
        out = {"metric": "bench_partial", "value": 0, "unit": "none"}
    for which, rps in results.items():
        out[f"{which}_rows_per_sec"] = round(rps)
        out[f"{which}_rows"] = rows_used[which]
    out.update(gbps_keys)
    if all_deltas:
        # per-query registry movement (uploads, collective dispatches,
        # plan-cache traffic) recorded next to the rates they explain
        out["metric_deltas"] = all_deltas

    if cpu is not None:
        out[f"cpu_{cpu_query}_rows_per_sec"] = cpu["value"]
        out["cpu_rows"] = cpu.get("rows")
        if cpu["value"] and cpu_query == primary:
            out["vs_cpu"] = round(results[primary] / cpu["value"], 3)

    # the rest of the BASELINE.md bench ladder: SSB star-schema joins
    # (config 4) + YCSB-E range scans (config 5)
    if os.environ.get("BENCH_SSB", "1") != "0":
        r = run_child(int(os.environ.get("BENCH_SSB_ROWS", 1 << 21)),
                      "flight", child_timeout, mode="ssb_child")
        if r is not None:
            out["ssb_flight_rows_per_sec"] = r["value"]
            out["ssb_rows"] = r["rows"]
            out.update({k: v for k, v in r.items()
                        if k.startswith("ssb_q")})
    if os.environ.get("BENCH_YCSB", "1") != "0":
        r = run_child(0, "ycsb_e", 900, mode="ycsb_child")
        if r is not None:
            out["ycsb_e_ops_per_sec"] = r["value"]
            if "ycsb_e_c16_ops_per_sec" in r:
                out["ycsb_e_c16_ops_per_sec"] = \
                    r["ycsb_e_c16_ops_per_sec"]
    # PR 3 data-plane benches: streamed-scan pipeline A/B + concurrent
    # distributed dispatch through the per-mesh queue
    if os.environ.get("BENCH_STREAM", "1") != "0":
        r = run_child(int(os.environ.get("BENCH_STREAM_ROWS", 1 << 22)),
                      "stream", child_timeout, mode="stream_child")
        if r is not None:
            out["stream_scan_rows_per_sec"] = r["value"]
            out["stream_scan_off_rows_per_sec"] = \
                r["stream_scan_off_rows_per_sec"]
            out["stream_pipeline_speedup"] = r["stream_pipeline_speedup"]
            out["stream_rows"] = r["rows"]
    # round 6 tentpole A/B: one-pass Pallas grouped aggregation
    # (auto) vs the XLA segment/scatter path (off), both arms recorded
    if os.environ.get("BENCH_PALLAS", "1") != "0":
        r = run_child(int(os.environ.get("BENCH_PALLAS_ROWS", 1 << 18)),
                      "pallas", child_timeout, mode="pallas_child")
        if r is not None:
            out.update({k: v for k, v in r.items()
                        if k.startswith("pallas_")})
            out.setdefault("pallas_rows", r["rows"])
    # round 7 tentpole A/B: normalized sort keys (auto, one 2-operand
    # sort per uint64 lane) vs the variadic lexsort (off)
    if os.environ.get("BENCH_SORT", "1") != "0":
        r = run_child(int(os.environ.get("BENCH_SORT_ROWS", 1 << 18)),
                      "sort", child_timeout, mode="sort_child")
        if r is not None:
            out.update({k: v for k, v in r.items()
                        if k.startswith("sort_")})
    # round 8 tentpole A/B: out-of-core spill tier (spill=auto) vs
    # the quota-bound engine (spill=off) at a forced-small HBM budget
    if os.environ.get("BENCH_SPILL", "1") != "0":
        r = run_child(int(os.environ.get("BENCH_SPILL_ROWS", 1 << 19)),
                      "spill", child_timeout, mode="spill_child")
        if r is not None:
            out.update({k: v for k, v in r.items()
                        if k.startswith("spill_")})
            out.setdefault("spill_rows", r["rows"])
    # round 10 tentpole A/B: join-induced data skipping
    # (join_filter=auto) vs the unfiltered probe scan (off) on q3/q9
    # -class ladders at a forced-small HBM budget
    if os.environ.get("BENCH_JOINSKIP", "1") != "0":
        r = run_child(int(os.environ.get("BENCH_JOINSKIP_ROWS",
                                         1 << 20)),
                      "joinskip", child_timeout, mode="joinskip_child")
        if r is not None:
            out.update({k: v for k, v in r.items()
                        if k.startswith("joinskip_")})
            out.setdefault("joinskip_rows", r["rows"])
    # round 12 tentpole A/B: sketch-fed cost-based join ordering vs
    # the syntax-ordered plan (optimizer_sketch_stats=off, no ANALYZE)
    # on a q9-class ladder whose selective join hides last in syntax
    if os.environ.get("BENCH_JOINORDER", "1") != "0":
        r = run_child(int(os.environ.get("BENCH_JOINORDER_ROWS",
                                         1 << 20)),
                      "joinorder", child_timeout,
                      mode="joinorder_child")
        if r is not None:
            out.update({k: v for k, v in r.items()
                        if k.startswith("joinorder_")})
            out.setdefault("joinorder_rows", r["rows"])
    # round 13 tentpole A/B: data-movement-first distributed executor
    # — beyond-HBM join ladder (working set 0.5x..4x of each node's
    # budget), overlapped vs serial exchange, on a fakedist cluster
    if os.environ.get("BENCH_MOVEMENT", "1") != "0":
        r = run_child(int(os.environ.get("BENCH_MOVEMENT_ROWS",
                                         1 << 17)),
                      "movement", child_timeout,
                      mode="movement_child")
        if r is not None:
            out.update({k: v for k, v in r.items()
                        if k.startswith("movement_")})
            out.setdefault("movement_rows", r["rows"])
    # round 15 tentpole: multi-host pod scale-out — 1/2/4 real host
    # processes (jax.distributed rendezvous, host-owned shards) with
    # the hierarchical partial-agg merge tree vs flat gateway fan-in
    if os.environ.get("BENCH_MULTIHOST", "1") != "0":
        r = run_child(int(os.environ.get("BENCH_MULTIHOST_ROWS",
                                         1 << 17)),
                      "multihost", max(child_timeout, 1200),
                      mode="multihost_child")
        if r is not None:
            out.update({k: v for k, v in r.items()
                        if k.startswith("multihost_")})
    # round 16 robustness lanes: elastic pod — kill-one-host
    # mid-bench (zero failed statements) + 2->4 online scale-out
    if os.environ.get("BENCH_ELASTIC", "1") != "0":
        r = run_child(int(os.environ.get("BENCH_ELASTIC_ROWS",
                                         1 << 15)),
                      "elastic", max(child_timeout, 1200),
                      mode="elastic_child")
        if r is not None:
            out.update({k: v for k, v in r.items()
                        if k.startswith("elastic_")})
    if os.environ.get("BENCH_DISPATCHQ", "1") != "0":
        r = run_child(int(os.environ.get("BENCH_DISPATCHQ_ROWS",
                                         1 << 20)),
                      "dispatchq", child_timeout, mode="dispatchq_child")
        if r is not None:
            out["dispatch_concurrent2_qps"] = r["value"]
            out["dispatch_serial_qps"] = r["dispatch_serial_qps"]
            out["dispatch_concurrency_speedup"] = \
                r["dispatch_concurrency_speedup"]
    if os.environ.get("BENCH_CONCURRENCY", "1") != "0":
        r = run_child(int(os.environ.get("BENCH_CONCURRENCY_ROWS",
                                         1 << 17)),
                      "concurrency", child_timeout,
                      mode="concurrency_child")
        if r is not None:
            out.update({k: v for k, v in r.items()
                        if k.startswith("conc_")})
            out.setdefault("concurrency_rows", r["rows"])
    # round 18 tentpole A/B: cross-session batch fusion + group
    # commit (oltp_batch=auto) vs the per-statement lane (off) on a
    # YCSB-B mix at 32/1000 sessions with an analytic tenant running
    if os.environ.get("BENCH_OLTPBATCH", "1") != "0":
        r = run_child(0, "oltpbatch", max(child_timeout, 1200),
                      mode="oltpbatch_child")
        if r is not None:
            out.update({k: v for k, v in r.items()
                        if k.startswith("oltp_")})
    # round 19 tentpole: selector-reactor front door vs thread-per-
    # conn at 1K/10K parked sessions, plus the tenant-quota rung
    if os.environ.get("BENCH_FRONTDOOR", "1") != "0":
        r = run_child(0, "frontdoor", max(child_timeout, 1200),
                      mode="frontdoor_child")
        if r is not None:
            out.update({k: v for k, v in r.items()
                        if k.startswith("fd_")})
    if os.environ.get("BENCH_TPCC", "1") != "0":
        r = run_child(0, "tpcc", 900, mode="tpcc_child")
        if r is not None:
            out["tpcc_tpmc"] = r["value"]
            out["tpcc_warehouses"] = r.get("warehouses")
    # round 9 tentpole: cold-start elimination. Each headline query
    # runs twice in fresh subprocesses sharing ONE empty persistent
    # compile-cache dir — run 1 pays the compiler (cold TTFR), run 2
    # must deserialize its executables from disk (warm TTFR), serve
    # bit-identical rows, and show cache hits. The dir is per QUERY so
    # one query's compiled subprograms can't quietly warm the next
    # query's "cold" arm.
    if os.environ.get("BENCH_COLDSTART", "1") != "0":
        import tempfile
        cs_rows = int(os.environ.get("BENCH_COLDSTART_ROWS", 1 << 16))
        for q in ("q1", "q3", "q6", "q18"):
            with tempfile.TemporaryDirectory(
                    prefix=f"bench-coldstart-{q}-") as cdir:
                cenv = {"JAX_COMPILATION_CACHE_DIR": cdir}
                cold = run_child(cs_rows, q, 900, attempts=1,
                                 mode="coldstart_child",
                                 extra_env=cenv)
                warm = run_child(cs_rows, q, 900, attempts=1,
                                 mode="coldstart_child",
                                 extra_env={**cenv,
                                            "BENCH_PREWARM": "1"})
            if cold is None or warm is None:
                continue
            out[f"coldstart_{q}_ttfr_cold_s"] = cold["value"]
            out[f"coldstart_{q}_ttfr_warm_s"] = warm["value"]
            if warm["value"]:
                out[f"coldstart_{q}_warm_speedup"] = \
                    round(cold["value"] / warm["value"], 2)
            out[f"coldstart_{q}_warm_prewarm_s"] = warm["prewarm_s"]
            out[f"coldstart_{q}_warm_cache_hits"] = warm["cache_hit"]
            out[f"coldstart_{q}_parity"] = \
                cold["digest"] == warm["digest"]
            out.setdefault("coldstart_rows", cs_rows)
    regression_report(out)
    print(json.dumps(out))


# metrics where a value change is configuration, not performance
_NON_PERF_KEYS = {"vs_baseline", "vs_cpu", "n", "rc", "rows",
                  "cpu_rows", "ssb_rows", "tpcc_warehouses",
                  "spill_budget_bytes", "coldstart_rows",
                  "joinskip_budget_bytes", "joinskip_okey_cap",
                  "movement_shard_bytes", "movement_build_bytes",
                  "multihost_rows", "elastic_rows",
                  "elastic_statements_per_query",
                  "elastic_kill_failed_statements",
                  "elastic_kill_consistent", "elastic_kill_failovers",
                  "elastic_kill_lease_failovers",
                  "elastic_kill_live_hosts",
                  "elastic_scaleout_consistent",
                  "elastic_scaleout_live_hosts",
                  "elastic_scaleout_lease_owners",
                  "elastic_scaleout_lease_moves",
                  "elastic_scaleout_rebalance_bytes",
                  # window/proposal counts are shape verification —
                  # they track load timing, not performance
                  "oltp_records", "oltp_steps",
                  "oltp_auto_32_windows", "oltp_auto_32_fused_stmts",
                  "oltp_auto_32_gc_proposals",
                  "oltp_auto_32_gc_commands",
                  "oltp_auto_32_cmds_per_proposal",
                  "oltp_auto_1000_windows",
                  "oltp_auto_1000_fused_stmts",
                  "oltp_auto_1000_gc_proposals",
                  "oltp_auto_1000_gc_commands",
                  "oltp_auto_1000_cmds_per_proposal",
                  "oltp_off_32_retries", "oltp_auto_32_retries",
                  "oltp_off_1000_retries", "oltp_auto_1000_retries",
                  # front-door shape numbers: thread/RSS/quota counts
                  # verify the reactor's resource model, not speed
                  "oltp_switch_interval",
                  "fd_reactor_1000_threads", "fd_reactor_10000_threads",
                  "fd_threads_1000_threads",
                  "fd_reactor_1000_rss_kb_per_idle",
                  "fd_reactor_10000_rss_kb_per_idle",
                  "fd_threads_1000_rss_kb_per_idle",
                  "fd_quota_slot_waits"}


def regression_report(out: dict) -> None:
    """Compare this run against the newest BENCH_r{N}.json and print a
    per-metric delta report; any >10% drop gets a loud REGRESSION line
    and lands in out["regressions"]. Round-4 lesson: Q14 silently lost
    25% for a whole round because nothing compared BENCH_rN against
    BENCH_rN-1 (the reference regression-tests exact perf counts,
    pkg/bench/rttanalysis)."""
    import glob as _glob
    here = os.path.dirname(os.path.abspath(__file__))
    prevs = sorted(_glob.glob(os.path.join(here, "BENCH_r*.json")))
    if not prevs:
        return
    try:
        with open(prevs[-1]) as f:
            prev = json.load(f).get("parsed") or {}
    except (OSError, ValueError):
        return
    name = os.path.basename(prevs[-1])
    regs = []
    for k in sorted(set(prev) & set(out)):
        pv, cv = prev[k], out[k]
        if k in _NON_PERF_KEYS or k.endswith("_rows") or \
                k.endswith("_cache_hits") or \
                k.endswith("_node_budget_bytes") or \
                k.endswith("_overlap_s") or k.endswith("_pages") or \
                k.endswith("_recv_bytes") or \
                k.endswith("_merge_bytes") or \
                k.endswith("_bytes_tree_vs_flat") or \
                isinstance(pv, bool) or isinstance(cv, bool) or \
                not isinstance(pv, (int, float)) or \
                not isinstance(cv, (int, float)) or not pv:
            continue
        delta = (cv - pv) / pv
        # TTFR/prewarm metrics are seconds: LOWER is better, so the
        # warm-start gate fires on a >10% increase, not a >10% drop
        worse = (delta > 0.10
                 if ("_ttfr_" in k or k.endswith("_prewarm_s"))
                 else delta < -0.10)
        if worse:
            regs.append(k)
            print(f"# REGRESSION {k}: {pv:.6g} -> {cv:.6g} "
                  f"({delta:+.1%}) vs {name}", file=sys.stderr)
        else:
            print(f"# delta {k}: {pv:.6g} -> {cv:.6g} ({delta:+.1%})",
                  file=sys.stderr)
    if regs:
        print(f"# REGRESSION SUMMARY: {len(regs)} metric(s) dropped "
              f">10% vs {name}: {', '.join(regs)}", file=sys.stderr)
        out["regressions"] = regs


if __name__ == "__main__":
    main()
