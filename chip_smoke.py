#!/usr/bin/env python3
"""chip_smoke.py: does the served SQL path still start on the chip?

Drives the system's main path once, through the entry points a user
calls, at the scale the reference's own TPC-H roachtests run (SF1:
6,001,215 lineitem rows, pkg/workload/tpch/tpch.go:39;
pkg/cmd/roachtest/tests/tpchvec.go:44-52):

    server.Node -> reactor pgwire front end -> parse/plan ->
    exec/scanplane upload -> compiled device program (XLA + ops/pallas)
    -> materialize -> wire

One process holds the chip: this script starts the node itself and
talks to it over real TCP sockets with cli.PgClient. It refuses to run
unless JAX's default backend is the TPU, fails (non-zero exit, no
result line) at the first thing that is wrong, and on success prints as
its last line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Statement times it prints are set-up information (first run compiles,
second is steady), not results. Data is regenerated from the workload's
fixed seeds; every answer is compared with the numpy oracles in
models/tpch.py.

Usage: python chip_smoke.py [--chips N]
  --chips N  require at least N devices (the four-chip mesh phase runs
             whenever JAX reports >= 4 devices)

tests/test_chip_smoke.py calls the same functions on the CPU with a
smaller scale factor as an argument.
"""

from __future__ import annotations

import argparse
import datetime
import json
import math
import numbers
import sys
import time

SF = 1.0  # the reference's tpchvec/tpchbench scale
TABLES = ("lineitem", "part", "orders", "customer")
MESH_CHIPS = 4
# a cold Q3/Q18 compile is minutes; the client must outwait it
CLIENT_TIMEOUT_S = 1150.0

# a GROUP BY the planner cannot bound statically (DATE key), so it takes
# the hash strategy: on a mesh, shard-local hash groups exchange to their
# hash-owner shard over all_to_all (exec/compile.py
# _compile_hash_dist_aggregate)
HASH_GROUPBY = """
SELECT l_shipdate, count(*) AS n, sum(l_quantity) AS qty
FROM lineitem
GROUP BY l_shipdate
ORDER BY l_shipdate
"""


class SmokeFailure(Exception):
    """A smoke check did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


# -- device ------------------------------------------------------------------

def require_tpu(min_chips: int) -> dict:
    """Refuse to go on without the accelerator; describe what answered."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"chip_smoke: refusing to run: jax.default_backend() is "
            f"{backend!r}, not 'tpu' (devices: {jax.devices()})")
    devs = jax.devices()
    if len(devs) < min_chips:
        raise SystemExit(
            f"chip_smoke: --chips {min_chips} but JAX reports "
            f"{len(devs)} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def report_runtime(engine) -> None:
    """Print what the engine says it is running on and fail on what
    must not be defaulted: a budget above the device's memory, a compile
    cache that did not arm, a native plane that did not load."""
    from importlib import metadata

    from cockroach_tpu import native

    native.get_lib()
    native.get_oltp()
    st = engine.runtime_status()
    log(f"platform={st['platform']} device_kind={st['device_kind']} "
        f"device_count={st['device_count']} mesh={st['mesh_devices']}")
    log(f"jax={st['jax']} jaxlib={st['jaxlib']} "
        f"libtpu={metadata.version('libtpu')}")
    log(f"compile_cache_dir={st['compile_cache_dir']}")
    log(f"hbm_budget_bytes={st['hbm_budget_bytes']} "
        f"device_bytes_limit={st['device_bytes_limit']}")
    check(st["compile_cache_dir"] is not None,
          f"compile cache did not arm: {st['compile_cache_error']}")
    limits = [b for b in st["device_bytes_limit"] if b is not None]
    if st["platform"] == "tpu":
        check(len(limits) == st["device_count"],
              "a TPU device reports no memory_stats()['bytes_limit']")
    for b in limits:
        check(st["hbm_budget_bytes"] <= b,
              f"sql.exec.hbm_budget_bytes={st['hbm_budget_bytes']} "
              f"exceeds the device's bytes_limit={b}")
    for comp in ("keyenc", "oltp"):
        c = st["native"].get(comp, {})
        log(f"native.{comp}: plane={c.get('plane')} "
            f"built_this_run={c.get('built')} so={c.get('so')}")
        # the library's name is the hash of its source, so one that
        # loaded was either built by this process or matches the source
        check(c.get("plane") == "native",
              f"native component {comp} is on the Python plane: "
              f"{c.get('error')}")


# -- node, data, oracle ------------------------------------------------------

def start_node(mesh=None):
    """A served node on an ephemeral port, default (reactor) front end."""
    from cockroach_tpu.server import Node, NodeConfig

    node = Node(NodeConfig(mesh=mesh)).start()
    check(node.settings.get("server.pgwire_frontend") == "reactor",
          "default pgwire front end is not the reactor")
    return node


def load_tpch(node, sf: float, rows: int | None = None) -> float:
    """Generate, ingest and ANALYZE the four tables the way `demo` does
    (models/tpch.load, encoded fast path). Returns wall seconds."""
    from cockroach_tpu.models import tpch

    t0 = time.monotonic()
    tpch.load(node.engine, sf, tables=TABLES, rows=rows, encoded=True)
    return time.monotonic() - t0


def oracle_data(sf: float, rows: int | None = None) -> dict:
    """The same data again, as numpy arrays for the ref_q* oracles."""
    import numpy as np

    from cockroach_tpu.models import tpch

    li = tpch.gen_lineitem(sf, rows=rows, encoded=True)
    for col, values in tpch.LINEITEM_DICTS.items():
        li[col] = np.asarray(values)[li[col]]
    return {"li": li, "part": tpch.gen_part(sf),
            "orders": tpch.gen_orders(sf),
            "cust": tpch.gen_customer(sf)}


def connect(node):
    from cockroach_tpu.cli import PgClient

    host, port = node.sql_addr
    return PgClient(host, port, timeout=CLIENT_TIMEOUT_S)


# -- wire rows vs oracle rows ------------------------------------------------

def _same(got: str, want, rel: float, abs_: float) -> bool:
    """One wire (text) value against one oracle value."""
    if isinstance(want, datetime.date):
        return got == want.isoformat()
    if isinstance(want, numbers.Integral):  # numpy ints included
        return int(got) == int(want)
    if isinstance(want, numbers.Real):
        return math.isclose(float(got), float(want), rel_tol=rel,
                            abs_tol=abs_)
    return got == str(want)


def compare_rows(name: str, got: list, want: list, rel: float = 0.0,
                 abs_: float = 0.0) -> None:
    check(len(got) == len(want),
          f"{name}: {len(got)} rows, oracle has {len(want)}")
    for i, (g, w) in enumerate(zip(got, want)):
        check(len(g) == len(w), f"{name} row {i}: width {len(g)} vs "
                                f"oracle {len(w)}")
        for j, (gv, wv) in enumerate(zip(g, w)):
            check(gv is not None and _same(gv, wv, rel, abs_),
                  f"{name} row {i} col {j}: got {gv!r}, oracle {wv!r}")


def ref_hash_groupby(li: dict) -> list:
    """Numpy oracle for HASH_GROUPBY."""
    import numpy as np

    from cockroach_tpu.models import tpch

    days, inv = np.unique(li["l_shipdate"], return_inverse=True)
    n = np.bincount(inv)
    qty = np.bincount(inv, weights=li["l_quantity"])
    return [(tpch.EPOCH + datetime.timedelta(days=int(d)), int(c),
             float(q)) for d, c, q in zip(days, n, qty)]


def analytic_statements(data: dict) -> list:
    """(name, sql, oracle rows, rel tol, abs tol): the statement list
    and the tolerances tests/test_tpch.py holds the engine to."""
    from cockroach_tpu.models import tpch

    li, part, orders, cust = (data["li"], data["part"], data["orders"],
                              data["cust"])
    return [
        ("q6", tpch.Q6, [(tpch.ref_q6(li),)], 1e-9, 0.0),
        ("q1", tpch.Q1, tpch.ref_q1(li), 1e-6, 0.0),
        ("q14", tpch.Q14, [(tpch.ref_q14(li, part),)], 1e-9, 0.0),
        ("q3", tpch.Q3, tpch.ref_q3(li, orders, cust), 0.0, 1e-4),
        ("q18", tpch.Q18, tpch.ref_q18(li, orders, cust), 0.0, 1e-6),
        ("groupby", HASH_GROUPBY, ref_hash_groupby(li), 0.0, 1e-6),
    ]


def run_twice(client, name: str, sql: str) -> list:
    """First run compiles, second is steady; both wall times are set-up
    information. The two runs must return the same rows."""
    t0 = time.monotonic()
    _, first, _ = client.query(sql)
    t1 = time.monotonic()
    _, second, _ = client.query(sql)
    t2 = time.monotonic()
    log(f"{name}: rows={len(second)} first_s={t1 - t0:.3f} "
        f"second_s={t2 - t1:.3f}")
    check(first == second, f"{name}: second run's rows differ from "
                           f"the first's")
    return second


# -- upload accounting -------------------------------------------------------

class UploadLedger:
    """Checks sql.device.table_uploads / sql.device.upload.bytes against
    the arrays that actually appeared on the device."""

    def __init__(self, engine):
        self.engine = engine
        self.keys = set(engine._device_tables)
        self.snap = engine.metrics.snapshot()
        self.placements: set = set()  # of the last settle's uploads

    def settle(self, what: str) -> int:
        """Bytes uploaded since the last call, as the metric counts
        them; fails unless that matches the new resident batches."""
        eng = self.engine
        snap = eng.metrics.snapshot()
        n = (snap.get("sql.device.table_uploads", 0)
             - self.snap.get("sql.device.table_uploads", 0))
        nbytes = (snap.get("sql.device.upload.bytes", 0)
                  - self.snap.get("sql.device.upload.bytes", 0))
        new = [k for k in eng._device_tables if k not in self.keys]
        want = 0
        for k in new:
            b = eng._device_tables[k]
            held = (sum(a.nbytes for a in b.data)
                    + sum(v.nbytes for v in b.valid) + b.sel.nbytes)
            copies = (len(b.sel.sharding.device_set)
                      if k[2] == "replicated" else 1)
            # the metric leaves out sel and the four MVCC word columns'
            # valid masks (one array on the device, a byte a row each
            # as counted here), which are made on the device
            want += (held - 5 * b.n) * copies
        log(f"{what}: table_uploads+={n} upload_bytes+={nbytes} "
            f"new_resident={[k[:3] for k in new]}")
        # a superset upload evicts the subset it replaces within one
        # statement, so uploads may outnumber the batches still held
        check(n >= len(new), f"{what}: {len(new)} new resident batches "
                             f"but table_uploads moved by {n}")
        if n == len(new):
            check(nbytes == want,
                  f"{what}: sql.device.upload.bytes moved by {nbytes}, "
                  f"the new resident batches hold {want}")
        else:
            check(nbytes >= want,
                  f"{what}: sql.device.upload.bytes moved by {nbytes}, "
                  f"less than the {want} now resident")
        self.keys = set(eng._device_tables)
        self.snap = snap
        self.placements = {k[2] for k in new}
        return nbytes


# -- phases ------------------------------------------------------------------

def run_oltp(client, engine) -> None:
    """CREATE / multi-row INSERT / UPDATE..WHERE / DELETE..WHERE (the
    host-evaluated predicates, exec/dml.py _host_eval), a point SELECT
    twice (the second must be a lane hit), then an aggregate that must
    read back exactly what was acknowledged."""
    rows = {k: [k * 10, k * 100] for k in range(1, 9)}
    client.query("CREATE TABLE smoke_kv (k INT PRIMARY KEY, a INT, "
                 "b INT)")
    _, _, tags = client.query(
        "INSERT INTO smoke_kv VALUES "
        + ", ".join(f"({k}, {a}, {b})" for k, (a, b) in rows.items()))
    check(tags == ["INSERT 0 8"], f"INSERT acknowledged {tags}")
    _, _, tags = client.query(
        "UPDATE smoke_kv SET a = a + 1 WHERE b >= 500")
    hit = [k for k, (_, b) in rows.items() if b >= 500]
    for k in hit:
        rows[k][0] += 1
    check(tags == [f"UPDATE {len(hit)}"], f"UPDATE acknowledged {tags}")
    _, _, tags = client.query("DELETE FROM smoke_kv WHERE a < 30")
    gone = [k for k, (a, _) in rows.items() if a < 30]
    for k in gone:
        del rows[k]
    check(tags == [f"DELETE {len(gone)}"], f"DELETE acknowledged {tags}")
    point = "SELECT a, b FROM smoke_kv WHERE k = 5"
    _, first, _ = client.query(point)
    hits = engine.lane_hits
    _, second, _ = client.query(point)
    want = [tuple(str(v) for v in rows[5])]
    check(first == want and second == want,
          f"point read returned {first} then {second}, want {want}")
    check(engine.lane_hits > hits,
          "second point SELECT was not an OLTP-lane hit")
    _, agg, _ = client.query(
        "SELECT count(*), sum(a), sum(b) FROM smoke_kv")
    want = [(str(len(rows)), str(sum(a for a, _ in rows.values())),
             str(sum(b for _, b in rows.values())))]
    check(agg == want, f"aggregate read back {agg}, acknowledged "
                       f"writes add up to {want}")
    log(f"oltp: {len(rows)} rows survive, lane_hits={engine.lane_hits}")


# Q1's avg_qty, avg_price, avg_disc. Under `off` an AVG over DECIMAL
# is an f64 sum of quotients, which rounds in summation order; the
# kernel arm divides the exact integer sum. The two differ in the last
# digits on every backend, so these columns cannot be the precision
# check; every other column is exact in both arms.
Q1_AVG_COLS = (6, 7, 8)


def check_groupagg_parity(client, default_rows: dict) -> None:
    """Q1 and Q3 on the XLA path (`pallas_groupagg = off`) against the
    default `auto` routing: every exact column (group keys, DECIMAL
    sums, counts) bit-identical. The large-G Pallas kernel's limb sums
    are exact only if the MXU contraction runs at full f32; interpret
    mode always is, so only the chip can fail this."""
    from cockroach_tpu.models import tpch

    client.query("SET pallas_groupagg = off")
    try:
        for name, sql, inexact in (("q1", tpch.Q1, Q1_AVG_COLS),
                                   ("q3", tpch.Q3, ())):
            off = run_twice(client, f"{name}[pallas_groupagg=off]", sql)
            auto = default_rows[name]
            check(len(off) == len(auto),
                  f"{name}: {len(auto)} rows under auto, {len(off)} "
                  f"under off")
            for i, (a, o) in enumerate(zip(auto, off)):
                for j, (av, ov) in enumerate(zip(a, o)):
                    same = (math.isclose(float(av), float(ov),
                                         rel_tol=1e-12)
                            if j in inexact else av == ov)
                    check(same, f"{name} row {i} col {j}: "
                                f"pallas_groupagg=auto gave {av!r}, "
                                f"off gave {ov!r}")
    finally:
        client.query("SET pallas_groupagg = auto")


def int_minmax_is_exact(seed: int, n: int, groups: int,
                        interpret: bool) -> bool:
    """Integer MIN/MAX as the engine routes it (exec/compile.py
    _pallas_large_partials): the kernel reduces the arithmetic high
    limb `value >> MM_HI_SHIFT` (|limb| <= 2^23: exact in f32, order-
    preserving), an XLA fold over the rows holding the winning limb
    returns the full-width value. Compared bit for bit with ops/agg's
    folds on seeded int64s across both signs and past 2^24, where a
    plain f32 MIN/MAX is already wrong."""
    import jax.numpy as jnp
    import numpy as np

    from cockroach_tpu.exec.compile import MM_HI_SHIFT
    from cockroach_tpu.ops import agg as aggops
    from cockroach_tpu.ops.pallas import groupagg_large as pgl

    rng = np.random.default_rng(1000 + seed)
    gid = jnp.asarray(rng.integers(0, groups, n), jnp.int32)
    sel = jnp.asarray(rng.random(n) < 0.85)
    vals = rng.integers(-(1 << 62), 1 << 62, n, dtype=np.int64)
    small = rng.random(n) < 0.3   # mix in sub-2^24 magnitudes
    vals[small] = rng.integers(-100, 100, int(small.sum()))
    d = jnp.asarray(vals)
    hi = jnp.right_shift(d, jnp.int64(MM_HI_SHIFT))
    hif = hi.astype(jnp.float32)
    acc_f, _ = pgl.large_group_aggregate(
        gid, sel, (), (), (),
        (jnp.where(sel, hif, jnp.float32(np.inf)),
         jnp.where(sel, hif, jnp.float32(-np.inf))),
        num_groups=groups, layout=(("live",),),
        mm_ops=(pgl.MIN, pgl.MAX), interpret=interpret)
    live = np.asarray(aggops.group_count(gid, sel, groups)) > 0
    # no f32 sum rows in this layout, so the MIN/MAX rows lead acc_f
    for row, fold in ((0, aggops.group_min), (1, aggops.group_max)):
        ghi = acc_f[row, :].astype(jnp.int64)
        refine = jnp.logical_and(sel, hi == ghi[gid])
        got = np.asarray(fold(d, gid, refine, groups))
        want = np.asarray(fold(d, gid, sel, groups))
        if not np.array_equal(got[live], want[live]):
            return False
    return True


def check_kernels(engine, st_before: dict) -> None:
    """The device was not hidden: the large-G kernel was built, by
    Mosaic, and its integer MIN/MAX slots are exact there."""
    snap = engine.metrics.snapshot()
    built = (snap.get("exec.pallas.kernel.builds.large", 0)
             - st_before.get("exec.pallas.kernel.builds.large", 0))
    st = engine.runtime_status()
    log(f"pallas: builds.large+={built} "
        f"interpret={st['pallas_interpret']} "
        f"fallbacks={snap.get('exec.pallas.kernel.fallbacks', 0)}")
    check(built >= 1, "no large-G Pallas kernel was built")
    if st["platform"] == "tpu":
        check(not st["pallas_interpret"],
              "Pallas kernels ran interpreted on the TPU backend")
    n, groups = (512, 64) if st["pallas_interpret"] else (4096, 256)
    for seed in range(3):
        check(int_minmax_is_exact(seed, n, groups,
                                  st["pallas_interpret"]),
              f"int MIN/MAX through the kernel differs from the XLA "
              f"fold (seed {seed})")
    log("pallas: int MIN/MAX exact against the XLA folds, 3 seeds")


def check_no_staging(mesh, moved: int) -> None:
    """After the first sharded upload of `moved` bytes, no chip may have
    peaked anything like a whole table above the others."""
    stats = [d.memory_stats() for d in mesh.devices.flat]
    if mesh.devices.flat[0].platform != "tpu" and None in stats:
        log("peak_bytes_in_use: not reported by this backend")
        return
    peaks = [st["peak_bytes_in_use"] for st in stats]
    log(f"peak_bytes_in_use after first sharded upload: {peaks}")
    check(max(peaks) - min(peaks) < moved // 2,
          f"one chip peaked {max(peaks) - min(peaks)} bytes above "
          f"another after a {moved}-byte sharded upload: the table was "
          f"staged through it")


ONE_CHIP = ("q6", "q1", "q14", "q3", "q18")
ON_MESH = ("q6", "q1", "q3", "groupby")


def run_on_mesh(client, eng, stmts: list) -> dict:
    """Q6, Q1, Q3 and a hash-shuffled GROUP BY on the node's mesh (the
    session default). Runs before anything else touches the chips, so
    that peak_bytes_in_use still tells whether a table was staged whole
    on one of them. Returns name -> wire rows."""
    chips = eng.mesh.devices.size
    tag = f"mesh[{chips}]"
    ledger = UploadLedger(eng)
    calls0 = eng.metrics.snapshot().get("exec.allreduce.calls", 0)
    rows = {}
    for i, (name, sql, want, rel, abs_) in enumerate(stmts):
        rows[name] = run_twice(client, f"{tag} {name}", sql)
        compare_rows(name, rows[name], want, rel, abs_)
        moved = ledger.settle(f"{tag} {name}")
        if i == 0:
            check_no_staging(eng.mesh, moved)
    for key, b in eng._device_tables.items():
        if key[0] != "lineitem":
            continue
        check(key[2] == "sharded",
              f"lineitem resident as {key[2]}, not sharded")
        for cn, a in zip(b.names, b.data):
            check(len(a.sharding.device_set) == chips,
                  f"lineitem.{cn} lives on {len(a.sharding.device_set)} "
                  f"device(s), not {chips}")
    calls = eng.metrics.snapshot().get("exec.allreduce.calls", 0) - calls0
    log(f"{tag}: collective dispatches={calls}")
    # two executions of each statement; fewer means one fell back
    # gateway-local (exec/session.py CollectiveFault path)
    check(calls >= 2 * len(stmts),
          f"only {calls} collective dispatches for {2 * len(stmts)} "
          f"mesh statement executions")
    return rows


def run_on_one_chip(client, eng, stmts: list) -> dict:
    """Everything the one-chip contract asks for, on a connection whose
    statements run on one device. Returns name -> wire rows."""
    ledger = UploadLedger(eng)
    out = {}
    for name, sql, want, rel, abs_ in stmts:
        out[name] = run_twice(client, name, sql)
        compare_rows(name, out[name], want, rel, abs_)
        ledger.settle(name)
        check(ledger.placements <= {"single"},
              f"{name}: a one-chip statement uploaded "
              f"{sorted(ledger.placements)} batches")
    run_oltp(client, eng)
    check_groupagg_parity(client, out)
    return out


def run_phases(sf: float, data: dict, mesh_chips: int = 0,
               rows: int | None = None) -> None:
    """One served node, every phase: with `mesh_chips`, first the mesh
    statements on a mesh of that many devices and then the one-chip
    phases with `SET distsql = off`, the two compared row for row."""
    from cockroach_tpu.parallel.mesh import make_mesh

    node = start_node(make_mesh(n=mesh_chips) if mesh_chips else None)
    try:
        eng = node.engine
        report_runtime(eng)
        log(f"load sf={sf}: {load_tpch(node, sf, rows):.1f}s "
            f"lineitem_rows={eng.store.table('lineitem').row_count}")
        before = eng.metrics.snapshot()
        stmts = analytic_statements(data)
        client = connect(node)
        mesh_rows = {}
        if mesh_chips:
            check(len(eng.runtime_status()["mesh_devices"]) == mesh_chips,
                  "the node's engine did not take the mesh")
            mesh_rows = run_on_mesh(
                client, eng, [s for s in stmts if s[0] in ON_MESH])
        if eng.mesh is not None:
            # an Engine given no mesh still builds one over every
            # device it sees; this session's statements stay local
            client.query("SET distsql = off")
        names = ONE_CHIP + (("groupby",) if mesh_chips else ())
        one = run_on_one_chip(client, eng,
                              [s for s in stmts if s[0] in names])
        client.close()
        for name, got in mesh_rows.items():
            check(got == one[name],
                  f"{name}: {mesh_chips}-chip rows differ from one "
                  f"chip's:\n  mesh: {got[:3]}\n  one:  {one[name][:3]}")
        if mesh_rows:
            log(f"mesh[{mesh_chips}] rows equal one chip's: "
                f"{sorted(mesh_rows)}")
        check_kernels(eng, before)
        snap = eng.metrics.snapshot()
        log(f"compile: cache_hit={snap.get('exec.compile.cache_hit')} "
            f"cache_miss={snap.get('exec.compile.cache_miss')} "
            f"seconds={snap.get('exec.compile.seconds'):.1f}")
    finally:
        node.stop()
        node.engine.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    t_start = time.monotonic()
    # importing the package keeps the cpu backend beside the accelerator
    # (host-side DML predicates need it) before JAX picks its platforms
    import cockroach_tpu  # noqa: F401
    device = require_tpu(args.chips)
    log(f"device: {json.dumps(device)}")
    t0 = time.monotonic()
    data = oracle_data(SF)
    log(f"oracle data sf={SF}: {time.monotonic() - t0:.1f}s "
        f"lineitem_rows={len(data['li']['l_orderkey'])}")
    run_phases(SF, data,
               MESH_CHIPS if device["count"] >= MESH_CHIPS else 0)
    log(f"total wall {time.monotonic() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
