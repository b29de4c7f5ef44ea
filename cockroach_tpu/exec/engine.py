"""The query engine: sessions, statement dispatch, result materialization.

The analogue of the reference's connExecutor (pkg/sql/conn_executor.go:
1835: run/execCmd -> dispatchToExecutionEngine) minus the wire protocol
(server/ speaks that). Each statement: parse -> bind/plan -> compiled
XLA program (cached) -> device run -> host decode.

Executable caching: keyed by (sql, table generations) — the reference
caches optimized memos per query fingerprint similarly (plan cache).
Table data is uploaded to device HBM once per (table, generation) and
reused across queries (the HBM analogue of the block cache); row
counts are padded to a closed shape-bucket ladder
(exec/coldstart.ShapeLadder, classic pow2 by default) so XLA
recompiles only on bucket growth, not every ingest. XLA executables
additionally persist across processes through the on-disk compile
cache wired by exec/coldstart.init_compile_cache, so a restarted node
serves its first query warm.
"""

from __future__ import annotations

import dataclasses
import datetime
import math
import threading
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kv.concurrency import (Span, TxnAbortedError, TxnRetryError)
from ..kv.txn import DB as KVDB
from ..kv.txn import KVStore, Txn
from ..ops.batch import SCAN_WIDE_ARGS, ColumnBatch, read_ts_words
from ..parallel import mesh as meshmod
from ..parallel.distagg import analyze as dist_analyze
from ..parallel.distagg import make_distributed_fn, queued_collective_call
from ..parallel.mesh import SHARD_AXIS
from ..sql import ast, parser
from ..sql import plan as P
from ..sql.binder import (Binder, BindError, ColumnBinding, Scope,
                          volatile_folds)
from ..sql.bound import BConst
from ..sql.planner import CatalogView, NotInPlace, PlanError, Planner
from ..sql.rowenc import ROWID
from ..sql.types import ColumnSchema, Family, TableSchema
from ..storage import keys as K
from ..storage.columnstore import MAX_TS_INT, Chunk, ColumnStore
from ..storage.hlc import Clock, Timestamp
from ..utils import tracing as _trc
from ..utils.metric import (MetricRegistry, process_status,
                            register_process_metrics)
from ..utils.mon import BytesMonitor, MemoryQuotaError
from ..utils.settings import SessionVars, Settings
from . import coldstart
from . import movement
from . import rollup as _rollup
from .compile import (AGG_STRATEGY, COMPACTS, JOIN_KINDS, JOIN_STRATEGY,
                      RANGE_PROOFS, SORTED_GROUP_BYS, UNION_BRANCHES,
                      ExecParams, JoinStats, RunContext,
                      _compact_block_rows, aggregate_strategy, can_stream,
                      compile_plan, compile_streaming, plan_rows)
from .dimstats import DimStats
from .planparam import (SubqueryValue, inline_subquery_args,
                        param_signature, parameterize, plan_fingerprint,
                        shape_text)
from .expr import ExprContext, compile_expr
from .stream import extract_zone_preds
from .session import (subquery_const,
                      CompactOverflow, EngineError, HashCapacityExceeded,
                      Prepared, Result, Session)
from .stmtutil import (_StreamFns, _RerunPrepared, _has_prefix_sort,
                      _host_sort, _count_aggs,
                      _collect_scan_columns, _collect_scans,
                      _contains_func, _decode_column, inline_ctes,
                      push_joins_into_unions,
                      _decode_scalar, _decode_storage_value,
                      _next_pow2, _propagate_as_of,
                      _render_create, _rewrite_table_names,
                      _slice_chunks, _stmt_table_refs,
                      split_conjuncts_ast)

EPOCH_DATE = datetime.date(1970, 1, 1)
EPOCH_DT = datetime.datetime(1970, 1, 1)

# -- where a Compact pays (Engine._insert_compaction) ------------------------
# Measured on one TPU v5e, in nanoseconds a row of the batch; PERF.md
# section 6 holds the runs. They are facts of the chip and of the
# kernels, not settings.
# One Compact over 2^23 rows is 1.3 ms plus 0.5 ms a carried 32-bit
# word, whatever its capacity (PR 37: 2.69 ms at three int32 columns,
# 3.29 at four, 4.76 at seven; ops/pallas/compact.py's route and pack)
COMPACT_NS = 1.3e6 / (1 << 23)
COMPACT_WORD_NS = 0.5e6 / (1 << 23)
# a probed key (PR 37, SSB's first, full-width probes over 2^23 keys):
# 60 ms into `date`'s 2,557 rows, 80-124 ms into customer's 30,000 and
# the larger builds
PROBE_SMALL_NS = 7.0
PROBE_SMALL_ROWS = 1 << 13
PROBE_NS = 12.0
# an aggregate that scatters is priced as one more such pass
SCATTER_NS = PROBE_NS
# wrap where the saving is at least this many times the cost: the
# prices above are means over plans that differ by a factor of two
# (where XLA keeps the table a probe gathers from: PERF.md section 7),
# and a Compact also costs a Mosaic compile a carried column, once a
# plan. PR 39 chose it on the chip: PERF.md section 6
COMPACT_PAYS = 2.0
# no Compact whose capacity is over half its input: it saves under half
# of what lies above and its headroom is thinnest there
COMPACT_MAX_FRAC = 1 / 2


def _find_scan_column(node, bname: str):
    """(table, stored column) of the Scan beneath `node` that makes the
    batch column `bname`, or None."""
    if isinstance(node, P.Scan):
        stored = node.columns.get(bname)
        return None if stored is None else (node.table, stored)
    for attr in ("child", "left", "right"):
        c = getattr(node, attr, None)
        if c is not None:
            hit = _find_scan_column(c, bname)
            if hit is not None:
                return hit
    return None


from .constraints import ConstraintMixin  # noqa: E402
from .ddl import DDLMixin  # noqa: E402
from .dml import DMLMixin  # noqa: E402
from .fastpath import FastpathMixin  # noqa: E402
from .maintenance import MaintenanceMixin  # noqa: E402
from .oltplane import OltpLaneMixin  # noqa: E402
from .scanplane import ScanPlaneMixin  # noqa: E402
from .textplan import TextPlanMixin, TextProbe  # noqa: E402


class _DistRouter:
    """Per-dispatch routing of one prepared distributed plan onto the
    full mesh or a pool sub-mesh (parallel/mesh.py MeshPool).

    Stored in ``_exec_cache`` in place of the jitted callable — it
    matches the jfn calling convention ``(scans, ts, nparts, pid,
    lits)`` — and lazily builds one compiled program + dispatcher
    wrapper per target mesh (the mesh is baked into shard_map, so each
    sub-mesh traces its own executable; ``psum`` over fewer shards is
    still exact, keeping results bit-identical across targets).

    Routing policy (sql.exec.submesh.size): ``off`` = always the full
    mesh (the pre-pool behavior); a power of two = always that
    sub-mesh size when the working set fits, escalating to larger
    sizes / the full mesh when it doesn't; ``auto`` = full mesh while
    the front door is idle, smallest fitting sub-mesh once dispatches
    are queueing — small queries then run side-by-side on disjoint
    rendezvous domains instead of serializing behind one dispatch
    thread."""

    # share of a device's HBM-budget slice a routed plan may occupy
    FOOTPRINT_FRAC = 0.5

    def __init__(self, engine, node, meta, scan_aliases, decision,
                 exec_params, upload_spec, sharded_bytes, repl_bytes):
        self.engine = engine
        self.node = node
        self.meta = meta
        self.scan_aliases = scan_aliases
        self.decision = decision
        self.exec_params = exec_params
        # [(alias, tname, placement, cols, narrow)] — how each scan
        # resolves a device batch against an arbitrary target mesh
        self.upload_spec = upload_spec
        self.sharded_bytes = sharded_bytes
        self.repl_bytes = repl_bytes
        self._lock = threading.Lock()
        self._runfs: dict = {}   # n_shards -> compiled plan fn
        self._calls: dict = {}   # "full" | (size, idx) -> queued call

    def _runf_for(self, n_shards: int):
        f = self._runfs.get(n_shards)
        if f is None:
            import dataclasses as _dc
            p = _dc.replace(self.exec_params, n_shards=n_shards)
            f = compile_plan(self.node, p, self.meta)
            self._runfs[n_shards] = f
        return f

    def _call_for(self, key, mesh, n_shards: int):
        with self._lock:
            c = self._calls.get(key)
            if c is None:
                c = queued_collective_call(
                    jax.jit(make_distributed_fn(
                        self._runf_for(n_shards), mesh,
                        self.scan_aliases, self.decision)),
                    metrics=self.engine.metrics, mesh=mesh,
                    movement=self.engine.movement,
                    # per-dispatch exchange working-buffer estimate:
                    # exchanged rows are bounded by one shard's
                    # post-filter slice plus the replicated builds
                    lease_bytes=(self.sharded_bytes
                                 // max(n_shards, 1)
                                 + self.repl_bytes))
                self._calls[key] = c
            return c

    def _target_size(self):
        """Sub-mesh size for this dispatch, or None for the full mesh."""
        eng = self.engine
        try:
            mode = str(eng.settings.get("sql.exec.submesh.size"))
        except Exception:
            return None
        if mode == "off":
            return None
        pool = eng._submesh_pool()
        if pool is None:
            return None
        full = eng.mesh.devices.size
        sizes = sorted(pool.sizes())  # ascending; full mesh excluded
        if mode == "auto":
            from ..parallel.distagg import _dispatcher_for
            busy = (_dispatcher_for(eng.mesh).depth() > 0
                    or pool.occupancy() > 0)
            if not busy:
                return None
        else:
            want = int(mode)
            if want >= full:
                return None
            sizes = [s for s in sizes if s >= want]
        per_dev_budget = eng.hbm.limit / max(full, 1)
        for s in sizes:
            if (self.sharded_bytes / s + self.repl_bytes
                    <= self.FOOTPRINT_FRAC * per_dev_budget):
                return s
        return None  # working set needs the full mesh

    def __call__(self, scans, tsv, nparts, pid, lits=()):
        size = self._target_size()
        if size is None:
            call = self._call_for("full", self.engine.mesh,
                                  self.engine.mesh.devices.size)
            return call(scans, tsv, nparts, pid, lits)
        eng = self.engine
        pool = eng._submesh_pool()
        submesh, token = pool.acquire(size)
        try:
            call = self._call_for(token, submesh, size)
            sub = {alias: eng._device_table(tname, placement, cols,
                                            narrow=narrow, mesh=submesh)
                   for alias, tname, placement, cols, narrow
                   in self.upload_spec}
            return call(sub, tsv, nparts, pid, lits)
        finally:
            pool.release(token)


class Engine(OltpLaneMixin, FastpathMixin, ScanPlaneMixin, DDLMixin,
             ConstraintMixin, MaintenanceMixin, DMLMixin, TextPlanMixin):
    def __init__(self, store: ColumnStore | None = None,
                 clock: Clock | None = None,
                 settings: Settings | None = None,
                 mesh=None, cluster=None):
        self.store = store or ColumnStore()
        # the transactional row plane: DML writes intents here via
        # kv.Txn (latches, tscache, pushes — kv/txn.py) and publishes
        # committed effects into the columnstore scan plane. With a
        # Cluster attached, that plane IS the raft-replicated range
        # plane (kv/rangekv.py): intents, catalog, sequences and jobs
        # all replicate and survive node failure; without one, a
        # single-store embedded KV serves the same interface (the
        # single-node deployment, like `cockroach start-single-node`).
        self.cluster = cluster
        if cluster is not None:
            from ..kv.rangekv import ClusterKVStore
            self.clock = cluster.clock
            self.kv = KVDB(ClusterKVStore(cluster))
        else:
            self.clock = clock or Clock()
            self.kv = KVDB(KVStore(clock=self.clock))
        self.settings = settings or Settings()
        # catalog: versioned descriptors in KV + leases (pkg/sql/catalog);
        # the columnstore's TableData.schema is the runtime cache of the
        # PUBLIC schema, kept in sync by the DDL/schema-change paths
        from ..catalog import Catalog, LeaseManager
        self.catalog = Catalog(self.kv)
        self.leases = LeaseManager(self.catalog, holder=f"sql-{id(self)}",
                                   now_ns=lambda: self.clock.now().wall)
        # changefeed event taps (cdc/changefeed.py TableFeed)
        self.cdc_feeds: list = []
        self._cdc_threads: dict[int, tuple] = {}  # id -> (thread, table)
        # observability: span tracing (util/tracing) + per-statement
        # fingerprint stats (pkg/sql/sqlstats)
        from ..utils.sqlstats import StatsRegistry
        from ..utils.tracing import Tracer
        self.tracer = Tracer()
        self.sqlstats = StatsRegistry()
        # admission control in front of execution (pkg/util/admission):
        # bounded priority queue so overload rejects cleanly instead of
        # stacking unbounded latency behind the statement lock
        from ..utils.admission import AdmissionController
        # sized to real parallelism now that read-only SELECTs share
        # the statement gate (round-4: the RW lock replaced the global
        # RLock; 4 slots gated a one-at-a-time engine)
        self.admission = AdmissionController(slots=16, max_queue=128)
        if mesh is None and len(jax.devices()) > 1:
            mesh = meshmod.make_mesh()
        self.mesh = mesh
        # sub-mesh dispatch pool (parallel/mesh.py MeshPool): built
        # lazily on the first routed distributed dispatch; None until
        # then and forever on meshes too small to split
        self._mesh_pool = None
        self._mesh_pool_lock = threading.Lock()
        self._device_tables: dict[tuple, ColumnBatch] = {}
        # coarse (name, placement, devids, narrow) -> Event for uploads
        # in flight: non-owners wait on the event OUTSIDE _device_lock
        # so the host->device transfer never runs under the cache lock
        self._device_inflight: dict[tuple, threading.Event] = {}
        # tenant-partitioned compiled-plan / parse caches (exec/
        # tenantcache.py): dict-compatible on the read path; the put
        # path tags entries with the executing statement's tenant so
        # sql.exec.plan_cache.tenant_budget bounds each tenant to
        # evicting its own shapes
        from .tenantcache import TenantLRU
        self._exec_cache: TenantLRU = TenantLRU(self._EXEC_CACHE_MAX)
        # plan-cache keys of prefix sorts (_size_hash_sorts) that met
        # more groups than their prefix holds: prepared with the whole
        # sort from then on
        self._whole_sorts: set = set()
        # what a small table's filter keeps, from its host columns
        # (exec/dimstats.py): join shares and key tuples for estimates
        self.dimstats = DimStats(self.store)
        self._parse_cache: TenantLRU = TenantLRU(
            self._PARSE_CACHE_MAX,
            on_evict=lambda k: (self._plain_memo.discard(k),
                                self._temps_memo.discard(k),
                                self._inplace_memo.pop(k, None)))
        # the executing statement's tenant, published per-thread
        # between admission acquire/release so cache puts deep in the
        # dispatch stack can attribute entries without plumbing
        self._tenant_tl = threading.local()
        # SELECT texts proven view-free/subquery-free: the "_plain"
        # memo keyed by TEXT instead of mutating the shared cached AST
        # (round-4 advisor, low: an in-place annotation on a shared
        # node is a latent cross-thread race under the read gate)
        self._plain_memo: set[str] = set()
        # SELECT texts whose derived tables cannot be planned in place
        # (NotInPlace, found on their first execution): they take
        # _exec_with_temps without asking the planner again. Kept in
        # step with the parse cache, as _plain_memo is
        self._temps_memo: set[str] = set()
        # SELECT texts with CTEs planned in place -> the statement as
        # the planner takes it (CTEs inlined, joins pushed into unions,
        # decorrelated): the same on every execution, kept in step with
        # the parse cache, as _plain_memo is
        self._inplace_memo: dict = {}
        # per-table secondary-index descriptors, cached off the catalog
        # (invalidated by index DDL; a fresh engine lazily reloads)
        self._index_defs: dict[str, list] = {}
        # per-table (checks, fks) cache + reverse fk map, same policy
        self._constraint_defs: dict[str, tuple] = {}
        self._fk_children: dict | None = None
        # live sessions (weakly held): non-transactional DDL like
        # TRUNCATE must observe open txns' buffered effects (the
        # reference serializes this via descriptor leases/intents)
        import weakref
        self._open_sessions = weakref.WeakSet()
        # cluster mode: generation token each local materialization was
        # built from (see dml.py _sync_scan_plane)
        self._scan_gens: dict[str, bytes | None] = {}
        # statement execution is serialized per engine: pgwire serves
        # each connection on its own thread, and the plan/device caches
        # plus columnstore publish are not safe under concurrent
        # mutation (the reference runs a connExecutor per conn against
        # thread-safe subsystems; finer-grained locking is later work)
        from ..utils.rwlock import RWLock
        # the statement gate: read-only SELECTs share it, everything
        # that mutates engine-shared state (DML/DDL/txn/CTE temps/
        # sequences/scan-plane sync) is exclusive. `with _stmt_lock:`
        # is the write side (utils/rwlock.py).
        self._stmt_lock = RWLock()
        # serializes device-cache upload/eviction (concurrent shared-
        # lock SELECTs race the resident-table map otherwise)
        self._device_lock = threading.RLock()
        self.metrics = MetricRegistry()
        register_process_metrics(self.metrics)
        # statement diagnostics (utils/stmtdiag.py): armed fingerprints
        # capture a JSON bundle on their next execution; bundles serve
        # at /_status/stmtdiag/<id> and inline via EXPLAIN ANALYZE
        # (DEBUG)
        from ..utils.stmtdiag import StmtDiagRegistry
        self.stmtdiag = StmtDiagRegistry(metrics=self.metrics)
        # what every statement counts, held: a look-up by formatted
        # name is a lock and a dict probe a statement each
        self._m_profile_statements = self.metrics.counter(
            "exec.profile.statements",
            "statements executed with an active profile sink")
        self._m_profile_operators = self.metrics.counter(
            "exec.profile.operators",
            "operator entries recorded into profile sinks")
        self._m_exec_latency = self.metrics.histogram(
            "sql.exec.latency", "statement execution latency (s)")
        self._m_stmt_count: dict = {}   # statement type -> its counter
        self._m_plan_cache = {
            hit: self.metrics.counter(
                "sql.plan.cache.hit" if hit else "sql.plan.cache.miss",
                "compiled-plan cache lookups, by outcome")
            for hit in (True, False)}
        # the statement-text cache in front of the planner
        # (exec/textplan.py)
        self._text_plan_init()
        # cold-start elimination (exec/coldstart.py): persistent XLA
        # compile cache so a restarted process deserializes instead of
        # recompiling; None when disabled or the backend/dir refuses
        self._compile_cache_dir = coldstart.init_compile_cache(
            self.settings)
        coldstart.register_metrics(self.metrics)
        # device-memory accounting: resident table uploads reserve
        # against the HBM budget BEFORE device_put, so an over-budget
        # upload fails with a quota error naming the knob instead of
        # an XLA OOM (pkg/util/mon/bytes_usage.go:173 analogue)
        self.hbm = BytesMonitor(
            "hbm", lambda: int(self.settings.get(
                "sql.exec.hbm_budget_bytes")),
            on_change=lambda used: self.metrics.gauge(
                "sql.mem.device.current",
                "bytes of HBM reserved by resident tables").set(used))
        # data-movement-first executor (exec/movement.py): every
        # data-moving path — resident uploads, stream/spill pages,
        # shuffle buffers — admits its bytes through one scheduler so
        # concurrent sessions stop racing the single HBM budget
        self.movement = movement.TransferScheduler(self.hbm,
                                                   self.metrics)
        from ..parallel import shuffle as _shuf
        self.metrics.func_counter(
            "exec.movement.exchange.traced.bytes",
            lambda: _shuf.EXCHANGE_TRACED.value(),
            "all_to_all exchange buffer bytes, tallied at trace time")
        # what a statement sends across the host/device boundary,
        # counted at the call sites (exec/session.py, ops/batch.py)
        from ..ops import batch as _ob
        for tally, help_ in (
                (_ob.PROGRAMS, "programs dispatched to the device: a "
                 "plan's executable, a sentinel-flag reduction, a "
                 "result pack, an eager gather"),
                (_ob.H2D_CALLS, "host values handed to the device "
                 "with a dispatch (scalars, gather indices)"),
                (_ob.H2D_BYTES, "bytes of those host values"),
                (_ob.D2H_CALLS, "device-to-host result transfers"),
                (_ob.D2H_BYTES, "bytes those transfers moved"),
                (_ob.JOINS, "hash joins in the plans of the statements "
                 "dispatched as one program, a dispatch"),
                (_ob.JOIN_BUILD_ROWS, "rows of those joins' build sides "
                 "(the padded batch each build is traced over)"),
                (_ob.JOIN_PROBE_ROWS, "rows those joins' probes are "
                 "traced over, after any Compact beneath: 2^23 a join "
                 "while a probe runs over the full-width fact batch"),
                (_ob.SITE_ROWS["exec.agg.rollup.rows"], "rows the "
                 "grouping sets above the finest of those statements' "
                 "Aggregates are traced over, a dispatch: the finest "
                 "set's group slots, never the child's rows "
                 "(exec/rollup.py)"),
                (_ob.SITE_ROWS["exec.window.rows"], "rows the Windows "
                 "of those statements sort, a dispatch (a prefix of a "
                 "hash Aggregate's slots where Engine._size_hash_sorts "
                 "gave one)"),
                (SCAN_WIDE_ARGS, "row-length arrays of a 64-bit element "
                 "type among the scan batches of the statements "
                 "prepared: each is a split pass over every row of "
                 "every execution on a TPU (a data column past 32 "
                 "bits; never the MVCC pair, which travels as words)")):
            self.metrics.func_counter(tally.name, tally.value, help_)
        # the placement verdict of each prepare (resident | stream |
        # spill | distributed), and the largest working set the
        # resident-or-stream model has weighed (scanplane.
        # _stream_decision; the statement's own is on its `plan` span)
        self._m_placement = {
            v: self.metrics.counter(
                f"sql.exec.placement.{v}",
                "prepares by placement verdict: resident, stream "
                "(paged through HBM), spill (out-of-core join or "
                "sort), distributed (over the mesh)")
            for v in ("resident", "stream", "spill", "distributed")}
        self._placement_model_max = 0
        self.metrics.func_gauge(
            "sql.exec.placement.model_bytes.max",
            lambda: self._placement_model_max,
            "largest working set (pruned upload + what the "
            "aggregation path allocates) the placement model has "
            "weighed against sql.exec.hbm_budget_bytes since start")
        self.metrics.func_counter(
            "storage.ingest.rows", lambda: self.store.ingest_rows,
            "rows taken by bulk columnar ingest (insert_columns)")
        self.metrics.func_counter(
            "storage.ingest.seconds", lambda: self.store.ingest_seconds,
            "seconds bulk columnar ingest held: encoding checks, "
            "chunking and the chunks' seal-time statistics")
        # TPU-plane visibility: Pallas kernel tallies are trace-time
        # module counters (ops/pallas/groupagg_large.py); read live at
        # scrape. All of them count at TRACE time — executions run
        # inside jitted programs and are not host-countable.
        from ..ops.pallas.groupagg_large import (
            BUILDS, FALLBACKS, GROUP_TILE_LANES, LIMB_BITS, MATMUL_ROWS,
            MXU_PASSES, OPERAND_BYTES, OPERAND_WORDS, PROVED_SUMS, ROWS)
        self.metrics.func_counter(
            "exec.pallas.kernel.builds",
            lambda: BUILDS.value(),
            "Pallas group-aggregate kernel traces/builds")
        self.metrics.func_counter(
            "exec.pallas.kernel.builds.large",
            lambda: BUILDS.value("large"),
            "large-G (one-hot matmul) group-aggregate kernel builds")
        self.metrics.func_counter(
            "exec.pallas.kernel.fallbacks",
            lambda: FALLBACKS.value(),
            "aggregations compiled on the XLA segment path while "
            "pallas_groupagg was enabled (outside a kernel envelope)")
        self.metrics.func_counter(
            "exec.pallas.kernel.operand_bytes",
            lambda: OPERAND_BYTES.value("large"),
            "bytes of the HBM arrays handed to the large-G kernel, a "
            "build: the aggregates' arguments as 32-bit words, the "
            "packed masks and the group ids, not the limb rows")
        self.metrics.func_counter(
            "exec.pallas.kernel.limb_bits",
            lambda: LIMB_BITS.value("large"),
            "limb width of the large-G kernel's exact int64 sums, "
            "summed over builds: over builds.large, the width the "
            "group-rows bound gave (8 at 2^23 rows, 6 or 5 at 2^26)")
        self.metrics.func_counter(
            "exec.pallas.kernel.matmul_rows",
            lambda: MATMUL_ROWS.value("large"),
            "rows of the large-G kernel's matmul operands (limb and "
            "count rows, three rows a shadow; built in VMEM), summed "
            "over builds")
        self.metrics.func_counter(
            "exec.pallas.kernel.group_tile",
            lambda: GROUP_TILE_LANES.value("large"),
            "lanes of the group tile the large-G kernel took, summed "
            "over builds: the group count rounded up to 128, at most "
            "GROUP_TILE's 512 (128 for TPC-H Q1's 12 groups)")
        self.metrics.func_counter(
            "exec.pallas.kernel.mxu_passes",
            lambda: MXU_PASSES.value("large"),
            "bf16 MXU passes of the large-G kernel's contraction of "
            "its exact rows, summed over builds (1 a build: limbs of "
            "at most 8 bits, counts and the one-hot are exact in bf16)")
        self.metrics.func_counter(
            "exec.pallas.kernel.operand_words",
            lambda: OPERAND_WORDS.value("large"),
            "[1, n] 32-bit arrays handed to the large-G kernel, summed "
            "over builds: the group ids, the packed mask words, one "
            "word a source the plan proved under 2^31 and two a "
            "source it did not, one a MIN/MAX slot (TPC-H Q1: 8, with "
            "no proof 12)")
        self.metrics.func_counter(
            "exec.pallas.kernel.proved_sums",
            lambda: PROVED_SUMS.value("large"),
            "exact sums and avgs of the large-G kernel's builds whose "
            "argument carried a value-range proof (BoundAgg.arg_bits), "
            "summed over builds (TPC-H Q1: all 7)")
        self.metrics.func_counter(
            "exec.agg.range_proof.proved",
            lambda: RANGE_PROOFS.value("proved"),
            "exact SUM / AVG aggregates over INT / DECIMAL compiled "
            "with a value-range proof of their argument (non-negative, "
            "so many bits: sql/valuerange.py over the store's column "
            "ranges), every aggregation strategy")
        self.metrics.func_counter(
            "exec.agg.range_proof.unproved",
            lambda: RANGE_PROOFS.value("unproved"),
            "exact SUM / AVG aggregates over INT / DECIMAL compiled "
            "with no such proof: 64-bit words, every limb, the "
            "run-time overflow gate")
        for kind, how in (
                ("kernel", "a dense group domain on the large-G Pallas "
                 "kernel"),
                ("dense", "a dense group domain on XLA's segment sums "
                 "(outside the kernel's envelope, or pallas_groupagg "
                 "off)"),
                ("hash", "a group domain the planner could not bound: "
                 "the while-loop hash table, segment sums over its "
                 "slots"),
                ("scalar", "no GROUP BY: masked reductions"),
                ("sorted", "past the dense bound, keys that pack "
                 "into one code: one sort of the rows by it "
                 "(exec/rollup.py), grouping sets always, a plain "
                 "GROUP BY over a batch of SORTED_GROUP_MIN_ROWS or "
                 "more whose exact sums are proven inside int64")):
            self.metrics.func_counter(
                "exec.agg.strategy." + kind,
                lambda kind=kind: AGG_STRATEGY.value(kind),
                "Aggregates compiled, by the strategy their trace "
                f"took (compile.aggregate_strategy): {how}")
        self.metrics.func_counter(
            "exec.agg.grouping_sets",
            lambda: _rollup.SETS.value("sets"),
            "grouping sets of the grouping-set Aggregates traced (GROUP "
            "BY ROLLUP / GROUPING SETS; ROLLUP of k keys is k + 1): one "
            "tally a set a trace")
        for kind, what in (
                ("group_by", "plain GROUP BYs past the dense bound that "
                 "took the sorted layout as one set"),
                ("declined", "plain GROUP BYs past the dense bound whose "
                 "keys pack that kept the hash table: a batch under "
                 "SORTED_GROUP_MIN_ROWS, or an exact sum not proven "
                 "inside int64")):
            self.metrics.func_counter(
                "exec.agg.sorted." + kind,
                lambda kind=kind: SORTED_GROUP_BYS.value(kind),
                f"{what} (compile.aggregate_strategy): one tally a "
                "trace")
        for kind, what in (
                ("network", "sets of the sorted grouping-set Aggregates "
                 "traced whose groups a displacement network packed: "
                 "one tally a set a trace"),
                ("segmented", "states of those Aggregates that kept a "
                 "segmented reduction (min, max, any, a float sum): one "
                 "tally a state a trace")):
            self.metrics.func_counter(
                "exec.agg.rollup." + kind,
                lambda kind=kind: _rollup.SETS.value(kind),
                f"{what} (exec/rollup.py sorted_sets)")
        for kind, how in (
                ("direct", "a direct-address table on one integer key"),
                ("packed", "a direct-address table on a composite key "
                 "whose components' spans multiply to at most "
                 "MAX_PACKED_JOIN_SLOTS"),
                ("bounded", "a composite key past that: a direct table "
                 "on its densest component, each slot's candidates "
                 "bounded by the store's statistics, a fixed number of "
                 "compares (ops/join.py bounded_table)"),
                ("sorted", "a composite key with no component dense "
                 "enough: a sorted build, a branch-free binary search"),
                ("hash", "the open-addressing table's while loops "
                 "(ops/hashtable.py)"),
                ("cross", "no key: a cartesian product over a build "
                 "side of few rows")):
            self.metrics.func_counter(
                "exec.join.strategy." + kind,
                lambda kind=kind: JOIN_STRATEGY.value(kind),
                "hash joins traced, by the strategy their trace took "
                f"(Engine._maybe_direct_join chooses it): {how}")
        self.metrics.func_counter(
            "exec.setop.union_all.branches",
            lambda: UNION_BRANCHES.value("branches"),
            "branches of the UNION ALLs traced into device programs "
            "(plan.UnionAll; a set operation the planner cannot place "
            "runs its branches as statements of their own)")
        self._m_cte_temps = self.metrics.counter(
            "exec.cte.temps",
            "temp tables materialized for a CTE, a derived table or a "
            "set operation's CTE, counted at every execution: what the "
            "planner could not place in the statement's program")
        for kind in ("inner", "left", "semi", "anti"):
            self.metrics.func_counter(
                "exec.join.kind." + kind,
                lambda kind=kind: JOIN_KINDS.value(kind),
                "hash joins traced, by join type (one tally a traced "
                "join, beside exec.join.joins a dispatch): semi and "
                "anti are what EXISTS / NOT EXISTS with equality "
                "correlations unnest into on one device")
        for kind, what in (
                ("compacts", "Compact nodes traced"),
                ("rows_in", "rows of the batches they were handed"),
                ("rows_out", "rows of the batches they handed on (a "
                 "Compact over a batch too small or too ragged to "
                 "shrink hands it on as it is)"),
                ("columns", "columns those batches held (each goes "
                 "through the network by itself, and XLA drops the "
                 "ones the statement never reads)")):
            self.metrics.func_counter(
                "exec.compact." + kind,
                lambda kind=kind: COMPACTS.value(kind),
                f"{what}: one tally a traced Compact, static shapes "
                "(compile.compact_batch, the displacement network of "
                "ops/pallas/compact.py)")
        self._m_compact_overflows = self.metrics.counter(
            "exec.compact.overflows",
            "statements answered by the uncompacted replan: a block of "
            "one of the plan's Compacts kept more rows than its capacity "
            "(the estimate undershot, or the rows are skewed between "
            "blocks), the __compact_overflow sentinel came back set and "
            "the statement ran again without Compacts. One a statement "
            "and an execution: such a statement pays for both programs "
            "every time it runs")
        self._m_subquery = {
            k: self.metrics.counter(
                "exec.subquery." + k,
                "results of uncorrelated scalar subqueries, counted a "
                "dispatched program, by how they reached it: args "
                "(read at the dispatch's timestamp by the subquery's "
                "own prepared statement and passed beside the lifted "
                "literals, planparam.SubqueryArg) or inlined "
                "(constants of the plan, read when it was prepared: "
                "another program for other rows)")
            for k in ("args", "inlined")}
        self._m_subquery_seconds = self.metrics.histogram(
            "exec.subquery.seconds",
            "seconds a `subquery` span took: one subquery's prepared "
            "statement run for one dispatch of the statement that "
            "takes its result as an argument")
        self._m_decorrelate = {
            k: self.metrics.counter(
                "exec.decorrelate." + k,
                "subqueries unnested at prepare (sql/decorrelate.py): "
                "exists counts EXISTS / NOT EXISTS, scalar a "
                "correlated scalar subquery")
            for k in ("exists", "scalar")}
        self.metrics.func_counter(
            "exec.pallas.rows",
            lambda: ROWS.value(),
            "rows offered to Pallas group-aggregate kernels at trace "
            "time (per-build input height, not per-execution)")
        # normalized-sort tallies (ops/sortkey.py) — trace-time, like
        # the Pallas counters above
        from ..ops import sortkey as _sk
        self.metrics.func_counter(
            "exec.sort.normalized",
            lambda: _sk.NORMALIZED.value(),
            "sorts traced through the normalized-key plane (packed "
            "uint64 lanes, one stable single-key argsort per lane) "
            "across ORDER BY / top-k / window / join-chain / "
            "DISTINCT sites")
        self.metrics.func_counter(
            "exec.sort.lexsort_fallback",
            lambda: _sk.FALLBACKS.value(),
            "sorts that wanted key normalization but compiled on the "
            "variadic lexsort (some key dtype not encodable)")
        self.metrics.func_counter(
            "exec.sort.lanes",
            lambda: _sk.LANES.value(),
            "uint64 lanes sorted by normalized-key sorts at trace "
            "time (lanes per sort ~ packed key-list width / 64)")
        # device-utilization plane (utils/devstats.py): actual HBM in
        # use + watermark, per-statement device-execute seconds, and
        # dispatcher queue pressure as exec.device.* — the maintenance
        # loop snapshots these into server/ts.py for /ts/query history
        from ..utils.devstats import DeviceStats
        self.devstats = DeviceStats(hbm=self.hbm).register(self.metrics)
        # /debug/tracez ring buffer: recordings of statements slower
        # than sql.trace.slow_statement.threshold (0 disables)
        from collections import deque as _deque
        self.slow_traces: _deque = _deque(maxlen=32)
        # admission-control plane: counters read live off the
        # controller; the wait histogram observes every queued grant
        self.metrics.func_counter(
            "admission.admitted", lambda: self.admission.admitted,
            "statements granted an execution slot")
        self.metrics.func_counter(
            "admission.rejected", lambda: self.admission.rejected,
            "statements rejected (queue full, wait timeout, or shed)")
        self.metrics.func_counter(
            "admission.queued", lambda: self.admission.queued,
            "statements that waited in the admission queue")
        self.admission.wait_observer = self.metrics.histogram(
            "admission.wait_seconds",
            "admission queue wait per queued grant (s)").observe
        # transfer-stall back-pressure: when the p99 of
        # exec.movement.wait_seconds crosses the shed threshold, the
        # interconnect is saturated and low-priority statements shed
        # before queueing (ROADMAP follow-up: the histogram was
        # recorded but nothing shed on it)
        self.admission.movement_wait_p99 = (
            lambda: self.movement.m_wait.quantile(0.99))
        # device-backlog back-pressure: the live dispatcher queue depth
        # (exec.device.queue.depth) feeds the exec-queue shed rung —
        # when the mesh itself is backlogged, admitting more work only
        # grows execution-stall p99
        self.admission.exec_queue_depth = (
            lambda: self.devstats.queue_depth())
        # per-tenant quota plane: hard slot/HBM budgets at dispatch
        # (sql.admission.tenant.*) and plan-cache partitioning
        # (sql.exec.plan_cache.tenant_budget)
        self.metrics.func_counter(
            "admission.tenant.slot_waits",
            lambda: self.admission.tenant_slot_waits,
            "statements queued because their tenant was at its "
            "concurrent-slot cap while global slots were free")
        self.metrics.func_counter(
            "admission.tenant.hbm_waits",
            lambda: self.admission.tenant_hbm_waits,
            "statements queued because their tenant's in-flight HBM "
            "ledger could not fit the statement's estimate")
        self.metrics.func_gauge(
            "admission.tenant.active",
            lambda: len(self.admission.tenant_usage()),
            "tenants currently holding at least one execution slot")
        self.metrics.func_counter(
            "admission.tenant.plan_evictions",
            lambda: (sum(self._exec_cache.tenant_evictions.values())
                     + sum(self._parse_cache.tenant_evictions.values())
                     + sum(self._text_plans.tenant_evictions.values())),
            "plan/parse/statement-text cache entries a tenant evicted "
            "from its OWN partition on hitting "
            "sql.exec.plan_cache.tenant_budget")
        self._admission_settings()
        self.settings.on_change(
            lambda n, v: self._admission_settings()
            if n.startswith(("sql.admission.",
                             "sql.exec.plan_cache.",
                             "sql.exec.hbm_budget_bytes")) else None)
        # sub-mesh dispatch plane (exec.submesh.dispatches counts in
        # _submesh_pool's router; count/occupancy read the pool live)
        self.metrics.func_gauge(
            "exec.submesh.count",
            lambda: (0 if self._mesh_pool is None else
                     sum(self._mesh_pool.count(s)
                         for s in self._mesh_pool.sizes())),
            "sub-meshes in the dispatch pool (0 = pool not built)")
        self.metrics.func_counter(
            "exec.submesh.dispatches",
            lambda: (0 if self._mesh_pool is None else
                     self._mesh_pool.dispatches),
            "distributed dispatches routed to a sub-mesh")
        self.metrics.func_gauge(
            "exec.submesh.occupancy",
            lambda: (0 if self._mesh_pool is None else
                     self._mesh_pool.occupancy()),
            "in-flight distributed dispatches across all sub-meshes")
        # multi-host pod membership, read live off the rendezvous
        # (parallel/multihost.py): 1 until init_distributed ran
        from ..parallel import multihost as _mh
        self.metrics.func_gauge(
            "exec.multihost.hosts", _mh.num_hosts,
            "host processes in this engine's rendezvous domain "
            "(1 = single-host)")
        self._lane_init()
        # OLTP batch-window plane (exec/oltpbatch.py): window counts,
        # statements that actually rode a multi-statement window, the
        # rolling median window size, and per-request wait-in-window
        # time. Group-commit counters read the process-wide raft tally
        # (single-node lane commits bump it too — the fused kv commit
        # is the WAL-append analogue there).
        _lb = self._lane_batcher
        self.metrics.func_counter(
            "exec.oltp.batch.windows", lambda: _lb.windows,
            "OLTP batch windows executed (a solo statement is a "
            "window of one)")
        self.metrics.func_counter(
            "exec.oltp.batch.fused", lambda: _lb.fused,
            "statements that shared a multi-statement batch window")
        self.metrics.func_gauge(
            "exec.oltp.batch.size_p50", _lb.size_p50,
            "median batch-window size over the last 512 windows")
        _lb.wait_observer = self.metrics.histogram(
            "exec.oltp.batch.flush_wait_seconds",
            "per-request wall time inside the batch window, queue to "
            "outcome (s)").observe
        from ..kvserver.raft import GROUPCOMMIT as _gc
        self.metrics.func_counter(
            "kv.raft.groupcommit.proposals", _gc.proposals,
            "group-commit proposals (one fused log append / kv commit "
            "per batch-window write round)")
        self.metrics.func_counter(
            "kv.raft.groupcommit.commands", _gc.commands,
            "individual commands that rode group-commit proposals")

    def _admission_settings(self) -> None:
        """Refresh the controller's shed thresholds and tenant quotas
        from cluster settings (sql.admission.*,
        sql.exec.plan_cache.tenant_budget; 0 disables each)."""
        try:
            self.admission.shed_queue_depth = int(self.settings.get(
                "sql.admission.shed.queue_depth"))
            self.admission.shed_wait_seconds = float(self.settings.get(
                "sql.admission.shed.wait_seconds"))
            self.admission.shed_exec_queue_depth = int(self.settings.get(
                "sql.admission.shed.exec_queue_depth"))
            self.admission.tenant_slots = int(self.settings.get(
                "sql.admission.tenant.slots"))
            frac = float(self.settings.get(
                "sql.admission.tenant.hbm_fraction"))
            self.admission.tenant_hbm_bytes = int(
                frac * int(self.settings.get("sql.exec.hbm_budget_bytes"))
            ) if frac > 0 else 0
            budget = int(self.settings.get(
                "sql.exec.plan_cache.tenant_budget"))
            self._exec_cache.tenant_budget = budget
            self._parse_cache.tenant_budget = budget
            self._text_plans.tenant_budget = budget
        except Exception:
            pass

    def _submesh_pool(self):
        """Lazy MeshPool over this engine's mesh; None when the mesh
        can't split (absent or single-device)."""
        if self.mesh is None or self.mesh.devices.size < 2:
            return None
        pool = self._mesh_pool
        if pool is None:
            with self._mesh_pool_lock:
                pool = self._mesh_pool
                if pool is None:
                    pool = self._mesh_pool = meshmod.MeshPool(self.mesh)
        return pool

    def close(self) -> None:
        """Retire engine-held device state: dispatcher threads (full
        mesh and every pool sub-mesh) and the device table cache.
        Dispatcher objects stay registered — a later dispatch through a
        cached closure respawns its thread (parallel/distagg.py)."""
        from ..parallel.distagg import shutdown_dispatchers
        # profiling lifecycle: drop armed diagnostics requests and
        # retained bundles — a closed engine must leak no profiling
        # state (sinks hold no threads; per-statement sinks die with
        # their statement's thread-local)
        self.stmtdiag.clear()
        self.drop_device_cache()
        if self.mesh is not None:
            shutdown_dispatchers(self.mesh)
        pool = self._mesh_pool
        if pool is not None:
            for s in pool.sizes():
                for m in pool.submeshes(s):
                    shutdown_dispatchers(m)
        # tear down the cross-host rendezvous too: a closed engine
        # must not leave a live distributed client behind, or the
        # NEXT engine in this process (back-to-back tests, hostd
        # restarts) inherits a stale coordinator and hangs its
        # jax.distributed.initialize
        from ..parallel import multihost as _mh
        _mh.shutdown_distributed()

    # -- public API ----------------------------------------------------------
    def session(self) -> Session:
        s = Session()
        self._open_sessions.add(s)
        return s

    # parse cache: OLTP workloads re-issue hot statement texts
    # (zipfian keys repeat literals); parsing was ~30% of a YCSB-E op.
    # Execution paths mutate ASTs (view expansion, decorrelation,
    # planner rewrites), so hits hand out a DEEP COPY — still ~3x
    # cheaper than a re-parse. The reference's sql.Statement cache
    # keys on the text the same way (plan_cache.go).
    _PARSE_CACHE_MAX = 4096

    def _parse_cached(self, sql: str):
        import copy
        with _trc.span("parse") as sp:
            hit = self._parse_cache.get(sql)
            if sp is not None:
                sp.tags["cache"] = "hit" if hit is not None else "miss"
            if hit is not None:
                # plain SELECTs (no CTEs/derived tables) execute
                # without mutating the AST — view expansion copies
                # before editing, subquery-free decorrelation is
                # identity, the planner builds a separate plan tree —
                # so hits share the cached object (deepcopy cost
                # exceeded the parse it saved). Shapes whose executors
                # DO rewrite in place (CTE bodies, DML coercions) hand
                # out a deep copy.
                if isinstance(hit, ast.Select) and not hit.ctes \
                        and not self._has_derived(hit):
                    return hit
                return copy.deepcopy(hit)
            stmt = parser.parse(sql)
            # insertion delegates eviction to the TenantLRU: a tenant
            # past its sql.exec.plan_cache.tenant_budget evicts its
            # own oldest entries; at the global cap the oldest half
            # goes (a full clear made every hot statement reparse at
            # once — a stampede exactly when the cache was earning its
            # keep). The on_evict hook keeps _plain_memo in sync.
            self._parse_cache.max_entries = self._PARSE_CACHE_MAX
            self._parse_cache.put(sql, stmt, self._current_tenant())
            return copy.deepcopy(stmt) if not (
                isinstance(stmt, ast.Select) and not stmt.ctes
                and not self._has_derived(stmt)) else stmt

    # executable cache: same bounded-growth policy as the parse cache
    # (long-lived multi-tenant sessions must not grow it without
    # bound — each entry pins a compiled XLA program)
    _EXEC_CACHE_MAX = 512

    def _exec_cache_put(self, key, val) -> None:
        self._exec_cache.max_entries = self._EXEC_CACHE_MAX
        self._exec_cache.put(key, val, self._current_tenant())

    def _current_tenant(self) -> str:
        """Tenant of the statement executing on this thread ('' when
        none): published across acquire/release in
        _execute_stmt_inner so cache puts anywhere in the dispatch
        stack (scanplane mixin, spill keys, parse inserts) attribute
        entries without plumbing a tenant argument through."""
        return getattr(self._tenant_tl, "value", "") or ""

    def _stmt_hbm_estimate(self, stmt: ast.Statement) -> int:
        """Coarse working-set estimate for the tenant HBM ledger:
        8 bytes per (row, column) over the statement's enumerable base
        tables. Deliberately cheap and over-inclusive (projection and
        filters ignored) — the ledger gates *concurrency* per tenant,
        it is not an allocator; the BytesMonitor still owns real
        reservations at upload time. Computed only when
        sql.admission.tenant.hbm_fraction arms the quota."""
        tables = self._stmt_tables(stmt)
        if not tables:
            return 0
        total = 0
        for t in tables:
            td = self.store.tables.get(t)
            if td is not None:
                try:
                    total += td.row_count * len(td.schema.columns) * 8
                except Exception:
                    pass
        return total

    def shape_ladder(self) -> coldstart.ShapeLadder:
        """The shape-bucket ladder every padded row count comes from:
        resident uploads, streamed pages and spill partitions all
        bucket through it, so a row sweep compiles at most
        ladder.budget(max_n) executables per program shape."""
        return coldstart.ladder_from_settings(self.settings)

    def _row_bucket(self, n: int) -> int:
        return self.shape_ladder().bucket(n)

    @staticmethod
    def _pallas_interpret() -> bool:
        """Pallas kernels lower through Mosaic on the TPU backend and
        run interpreted everywhere else."""
        return jax.default_backend() != "tpu"

    def runtime_status(self) -> dict:
        """Which device, cache and native plane this engine is really
        on, with the reasons for anything it skipped: the
        /_status/runtime body, and what chip_smoke.py checks."""
        import jaxlib

        from .. import native
        devs = jax.devices()
        mesh_ids = ([int(d.id) for d in self.mesh.devices.flat]
                    if self.mesh is not None else None)
        return {
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "mesh_devices": mesh_ids,
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "device_bytes_limit": [
                (d.memory_stats() or {}).get("bytes_limit")
                for d in devs],
            "hbm_budget_bytes": int(self.settings.get(
                "sql.exec.hbm_budget_bytes")),
            "compile_cache_dir": self._compile_cache_dir,
            "compile_cache_error": coldstart.cache_error(),
            "pallas_interpret": self._pallas_interpret(),
            "native": native.status(),
            # the process's own cost (utils/metric.py): CPU seconds of
            # the process and of its Python threads by role, the
            # collector's pauses by generation
            "process": process_status(),
        }

    # session vars a journal entry may replay into a prewarm session:
    # exactly the plan-key-changing vars _prepare_select journals —
    # anything else in a (possibly hand-edited) journal is ignored
    _PREWARM_VARS = ("hash_group_capacity", "pallas_groupagg",
                     "sort_normalized")

    def prewarm(self, top_k: int | None = None) -> int:
        """Re-prepare the top-K statement texts from the shapes
        journal of a previous run (exec/coldstart.py), so their
        executables load from the persistent compile cache before the
        first real query. Call after the catalog/data are loaded —
        texts whose tables no longer exist are skipped. Returns the
        number of statements warmed."""
        if top_k is None:
            try:
                top_k = int(self.settings.get(
                    "sql.exec.compile_cache.prewarm"))
            except Exception:
                top_k = 0
        if not top_k or not self._compile_cache_dir:
            return 0
        warmed = 0
        for sql, bucket, jvars in coldstart.journal_entries(
                self._compile_cache_dir, top_k):
            try:
                jvars = {k: v for k, v in (jvars or {}).items()
                         if k in self._PREWARM_VARS}
                session = None
                if bucket or jvars:
                    # a journaled page bucket means the statement ran
                    # on a paged plane (streamed or spill); re-derive
                    # that shape rather than the resident/distributed
                    # plan a fresh default session might pick.
                    # Journaled vars are the plan-key-changing session
                    # vars the statement compiled under — re-prepare
                    # under them or the warm misses its executable
                    session = self.session()
                    for name, val in jvars.items():
                        session.vars.set(name, val)
                if bucket:
                    session.vars.set("distsql", "off")
                    session.vars.set("streaming_page_rows", bucket)
                prep = self.prepare(sql, session)
                # jax.jit compiles at first CALL, not at prepare:
                # dispatch once so the executable is loaded now, not
                # under the first user query. Paged/spill dispatches
                # run whole data pipelines, so those warm their
                # page/partition executables from never-visible
                # padding batches at the journaled shape bucket
                # instead (Prepared.warm)
                if isinstance(prep, _RerunPrepared):
                    pass
                elif prep.stream is not None or prep.spill is not None:
                    prep.warm(bucket)
                else:
                    jax.block_until_ready(prep.dispatch())
                warmed += 1
                coldstart.note_prewarmed()
            except Exception:
                continue
        return warmed

    def execute(self, sql: str, session: Session | None = None) -> Result:
        # OLTP fast lane (exec/oltplane.py): literal-normalized shape
        # cache + native row plane; returns None for anything it
        # doesn't serve bit-for-bit
        res = self.lane_execute(sql, session)
        if res is not None:
            return res
        session = session or self.session()
        # a SELECT text planned before: its entry holds the parsed
        # statement too (exec/textplan.py)
        probe = self._text_probe(sql, session)
        entry = None if probe is None else probe[1]
        if entry is not None:
            stmt = entry.stmt
        else:
            # publish the tenant for the parse-cache put: admission
            # (which publishes it for exec-cache puts) only runs later,
            # inside _execute_stmt_inner — too late for the parse insert
            app = str(session.vars.get("application_name") or "")
            prev_tenant = getattr(self._tenant_tl, "value", "")
            self._tenant_tl.value = app or f"s{id(session)}"
            try:
                stmt = self._parse_cached(sql)
            except Exception:
                # a syntax error inside an explicit txn block aborts it,
                # same as any other statement failure (pg semantics)
                if session.txn is not None:
                    session.txn_aborted = True
                raise
            finally:
                self._tenant_tl.value = prev_tenant
        prev_probe = getattr(self._text_tl, "probe", None)
        self._text_tl.probe = (None if probe is None
                               else TextProbe(probe[0], stmt, entry))
        try:
            return self.execute_stmt(stmt, session, sql_text=sql)
        finally:
            self._text_tl.probe = prev_probe

    def execute_stmt(self, stmt: ast.Statement, session: Session,
                     sql_text: str = "") -> Result:
        if session.txn_aborted and not isinstance(
                stmt, (ast.CommitTxn, ast.RollbackTxn)):
            raise EngineError(
                "current transaction is aborted, commands ignored "
                "until end of transaction block")
        # full-path statements see the columnstore: publish any lane
        # writes still queued in the mirror first, and suspend lane
        # writes while this statement runs (its snapshot must not have
        # unflushed lane commits beneath it — exec/oltplane.py).
        # Suspension and flush are SCOPED to the statement's base
        # tables when they can be enumerated: a multi-tenant analytic
        # statement over other tables neither stalls the OLTP lane nor
        # forces its deferred publish (round-18 group-commit lane).
        _trc.stage("route")
        probe = getattr(self._text_tl, "probe", None)
        tables = (probe.entry.tables if probe is not None
                  and probe.entry is not None and probe.stmt is stmt
                  else self._stmt_tables(stmt))
        with self._lane_sync:
            # atomic with lane commits: after this block, any lane
            # write to a suspended table either already sits in
            # _lane_pending (flushed below) or will observe the
            # suspension and take the full path (exec/oltplane.py)
            if tables is None:
                self._nonlane_active += 1
                pending = bool(self._lane_pending)
            else:
                nt = self._nonlane_tables
                for t in tables:
                    nt[t] = nt.get(t, 0) + 1
                pending = any(t in self._lane_pending for t in tables)
        try:
            if pending:
                with self._stmt_lock:
                    self.lane_flush(tables)
            return self._execute_stmt_inner(stmt, session, sql_text)
        finally:
            with self._lane_sync:
                if tables is None:
                    self._nonlane_active -= 1
                else:
                    nt = self._nonlane_tables
                    for t in tables:
                        n = nt.get(t, 0) - 1
                        if n > 0:
                            nt[t] = n
                        else:
                            nt.pop(t, None)

    def _stmt_tables(self, stmt: ast.Statement):
        """Base tables `stmt` can read or write, or None when they
        cannot be enumerated (DDL, EXPLAIN, txn control, views, ...).
        Conservative by construction: only statement shapes listed
        here return a set; a view reference returns None because the
        expansion's base tables are not visible in the AST. Callers
        treat None as 'touches everything' (the pre-round-18 global
        lane suspension)."""
        if not isinstance(stmt, (ast.Select, ast.SetOp, ast.Insert,
                                 ast.Update, ast.Delete)):
            return None
        names: set = set()
        try:
            tbl = getattr(stmt, "table", None)
            if isinstance(tbl, str):
                names.add(tbl)
            self._collect_tables(stmt, names)
        except RecursionError:  # pragma: no cover - absurd nesting
            return None
        if names & self._view_map().keys():
            return None
        return names

    @classmethod
    def _collect_tables(cls, node, out: set) -> None:
        """Recursive TableRef harvest over parsed statement trees.
        Every AST node is a dataclass, so a generic field walk reaches
        subqueries/CTEs/derived tables wherever they nest; table names
        carried as plain `str` fields (Insert/Update/Delete.table) are
        added by _stmt_tables before the walk."""
        if node is None or isinstance(node, (str, int, float, bool,
                                             bytes)):
            return
        if isinstance(node, (list, tuple)):
            for x in node:
                cls._collect_tables(x, out)
            return
        if isinstance(node, ast.TableRef):
            if node.subquery is not None:
                cls._collect_tables(node.subquery, out)
            else:
                out.add(node.name)
            return
        if dataclasses.is_dataclass(node):
            for f in dataclasses.fields(node):
                cls._collect_tables(getattr(node, f.name), out)

    def _execute_stmt_inner(self, stmt: ast.Statement, session: Session,
                            sql_text: str = "") -> Result:
        if type(stmt).__name__.startswith(
                ("Create", "Drop", "Alter", "Truncate", "Rename")):
            # schema changes invalidate cached parses (a text's view/
            # table resolution or _plain memo may no longer hold) and
            # every lane plan (eligibility may have flipped: a new
            # index/FK/changefeed must push writes back onto the full
            # path, exec/oltplane.py)
            self._parse_cache.clear()
            self._text_plans_clear("ddl")
            self._plain_memo.clear()
            self._temps_memo.clear()
            self._inplace_memo.clear()
            self._lane_shapes.clear()
            self._lane_mirrors.clear()
        if self.cluster is not None:
            # the scan plane is a cache of committed range data: check
            # every referenced table's replicated generation token and
            # re-materialize what other gateways changed. Under the
            # statement lock — the refresh mutates the columnstore,
            # which concurrent pgwire threads may be scanning.
            with self._stmt_lock:
                self._sync_scan_plane(stmt)
        import time as _time
        # the observability plane's own time, marked on whatever span
        # is open around the statement's (the served root): `setup`
        # up to the statement's span, `account` from its close
        _trc.stage("setup")
        t0 = _time.monotonic()
        prio = session.vars.get("admission_priority", "normal")
        # tenant identity for the fair queue: application_name when the
        # client set one (the multi-tenant front door's natural key),
        # else the session object — each anonymous connection is its
        # own tenant rather than one shared bucket
        app_name = str(session.vars.get("application_name") or "")
        tenant = app_name or f"s{id(session)}"
        # per-tenant HBM ledger (sql.admission.tenant.hbm_fraction):
        # estimate the working set only when the quota is armed — the
        # estimate walks the statement's base tables
        hbm_est = (self._stmt_hbm_estimate(stmt)
                   if self.admission.tenant_hbm_bytes else 0)
        self.admission.acquire(priority=prio, tenant=tenant,
                               hbm=hbm_est)
        # publish the tenant for cache-put attribution (restored in
        # the finally below; nested statements keep their outer value)
        prev_tenant = getattr(self._tenant_tl, "value", "")
        self._tenant_tl.value = tenant
        # SET tracing = on|cluster (pgwire trace control): "on"
        # records gateway-local; "cluster" additionally sets the
        # recording-request bit so every RPC / DistSQL flow the
        # statement touches records remotely and ships spans back
        tmode = str(session.vars.get("tracing", "off")).lower()
        tracing = tmode in ("on", "cluster") \
            and not isinstance(stmt, ast.ShowTrace)
        try:
            slow_thresh = float(self.settings.get(
                "sql.trace.slow_statement.threshold"))
        except Exception:
            slow_thresh = 0.0
        from ..utils.sqlstats import fingerprint as _fp
        from . import profile as _prof
        # statement diagnostics (utils/stmtdiag.py): an armed
        # fingerprint captures a bundle on THIS execution, which needs
        # a trace recording and a before-snapshot of the metric plane.
        # The fingerprint is computed here once (three regex passes
        # over the text) and handed to every reader below
        fp = _fp(sql_text) if sql_text else type(stmt).__name__
        diag_req = (self.stmtdiag.should_capture(fp)
                    if sql_text else None)
        diag_m0 = None
        if diag_req is not None:
            try:
                diag_m0 = self.metrics.snapshot()
            except Exception:
                diag_m0 = {}
        # per-statement coarse operator profile: the data-movement
        # call sites (uploads, stream page loops, spill sweeps,
        # shuffle) attribute bytes/stalls to this sink via the
        # thread-local exec/profile.py plane. Host-side accounting
        # only — the jitted program is identical with or without it.
        psink = None
        try:
            if bool(self.settings.get("sql.stmt_profile.enabled")):
                psink = _prof.ProfileSink()
        except Exception:
            psink = _prof.ProfileSink()
        # slow-statement sampling records even untraced statements —
        # but never nested ones (an active span that is not the
        # served statement's own root means some outer statement
        # already owns the recording on this thread)
        outer = _trc.current_span()
        served = outer is not None and outer.tags.get("served") is True
        capture = tracing or diag_req is not None or (
            slow_thresh > 0 and (outer is None or served)
            and not isinstance(stmt, ast.ShowTrace))
        shared = self._stmt_read_only(stmt, session, sql_text)
        # per-statement compile-vs-execute split: XLA backend
        # compilation runs synchronously on this thread, so the
        # thread-local compile-seconds delta across dispatch is THIS
        # statement's compile bill (exec/coldstart.py; ~0 on plan-
        # cache hits and on warm restarts via the persistent cache)
        c0 = coldstart.thread_compile_seconds()
        compile_s = 0.0

        def _run():
            nonlocal compile_s
            with _prof.active(psink):
                r = self._dispatch_locked(stmt, session, sql_text,
                                          shared)
            compile_s = coldstart.thread_compile_seconds() - c0
            if compile_s > 0:
                # tagged while the statement span is still open, so
                # EXPLAIN ANALYZE / tracez distinguish "slow because
                # compiling" from "slow because executing"
                self.tracer.tag(compile_s=round(compile_s, 6))
            return r
        try:
            # session tracing "on" keeps the recording gateway-local
            # (remote nodes stay dark); "cluster" and the implicit
            # captures (slow sampling) request remote recordings too;
            # a recording nobody asked for here (the collector's, an
            # outer statement's) asks for none
            rec_req = (tmode == "cluster" if tracing else True) \
                if capture else None
            name = sql_text or type(stmt).__name__
            if outer is not None and (served or not capture):
                # under the served statement's root (pgwire opened it
                # at the frame) or nested in an outer statement: one
                # tree, this statement a child of it
                scope = _trc.span(name, record_request=rec_req)
            elif capture or _trc.collecting():
                scope = _trc.capture(name, record_request=bool(rec_req),
                                     collect=True)
            else:
                # nothing records: no Span exists, current_span()
                # stays None and no RPC carries a recording request
                scope = _trc.NO_SPAN
            with scope as rec:
                if rec is not None:
                    # on the tree's root: the wire's when it opened one
                    (outer if served else rec).tags.setdefault(
                        "fingerprint", fp)
                res = _run()
            _trc.stage("account")
            if not capture:
                rec = None      # read by no sink below
            elif tracing:
                session.trace.append(rec)
            m_count = self._m_stmt_count.get(type(stmt))
            if m_count is None:
                m_count = self._m_stmt_count[type(stmt)] = \
                    self.metrics.counter(
                        f"sql.{type(stmt).__name__.lower()}.count",
                        "statements executed, by type")
            m_count.inc()
            dt = _time.monotonic() - t0
            self._m_exec_latency.observe(dt)
            if sql_text:
                self.sqlstats.record_fp(fp, dt,
                                        max(len(res.rows), res.row_count),
                                        compile_s=compile_s)
            # device-execute seconds: the statement's wall time net of
            # its XLA compile bill (utils/devstats.py)
            device_s = max(0.0, dt - compile_s)
            self.devstats.note_execute(device_s)
            # per-tenant resource rollup (/_status/tenants): the
            # application_name-keyed device-seconds / bytes-moved /
            # HBM-held attribution feeding the admission/WFQ story
            if psink is not None:
                self.sqlstats.record_tenant(
                    app_name or "(unset)", device_s=device_s,
                    bytes_moved=psink.total_bytes_moved(),
                    rows=max(len(res.rows), res.row_count),
                    hbm_bytes=self.devstats.hbm_bytes(),
                    stall_s=psink.total_stall_seconds())
                self._m_profile_statements.inc()
                n_ops = len(psink.entries())
                if n_ops:
                    self._m_profile_operators.inc(n_ops)
            if rec is not None and slow_thresh > 0 \
                    and dt >= slow_thresh:
                # tenant-attributable slow traces: application_name +
                # session id ride every ring entry (/debug/tracez)
                self.slow_traces.append({
                    "sql": sql_text or type(stmt).__name__,
                    "fingerprint": fp,
                    "application_name": app_name,
                    "session": f"s{id(session):x}",
                    "duration_s": dt,
                    "span": _trc.span_to_wire(rec)})
            if diag_req is not None:
                # armed capture: assemble and store the bundle; any
                # failure re-arms the fingerprint (diagnostics must
                # never fail the statement)
                try:
                    bundle = self._diag_bundle(
                        stmt, session, sql_text, rec, psink, dt,
                        compile_s, diag_m0)
                    self.stmtdiag.fulfill(diag_req, bundle)
                except Exception:
                    self.stmtdiag.rearm(fp, diag_req)
            return res
        except Exception:
            # any error inside an explicit txn block aborts it until
            # ROLLBACK (postgres semantics; the connExecutor state
            # machine's stateAborted) — not just DML failures
            self.metrics.counter("sql.failure.count",
                                 "statements that errored").inc()
            if diag_req is not None:
                # the armed execution failed before capture: keep the
                # request pending for the next matching execution
                self.stmtdiag.rearm(fp, diag_req)
            if sql_text:
                self.sqlstats.record_fp(
                    fp, _time.monotonic() - t0, 0, failed=True,
                    compile_s=coldstart.thread_compile_seconds() - c0)
            if psink is not None:
                self.sqlstats.record_tenant(
                    app_name or "(unset)",
                    device_s=max(0.0, _time.monotonic() - t0),
                    bytes_moved=psink.total_bytes_moved(),
                    failed=True)
            if session.txn is not None and not isinstance(
                    stmt, ast.BeginTxn):
                session.txn_aborted = True
            raise
        finally:
            self._tenant_tl.value = prev_tenant
            self.admission.release(tenant=tenant, hbm=hbm_est)

    def _dispatch_locked(self, stmt, session, sql_text: str,
                         shared: bool) -> Result:
        lock = self._stmt_lock
        # the wait for the statement gate, up to the lock held
        with _trc.span("gate", shared=shared):
            if shared:
                lock.acquire_read()
            else:
                lock.acquire_write()
        # the statement span's own time by stage: `select` from the
        # gate on (the memos and fast-path matches before `plan`, the
        # glue between the layers' spans), `unwind` (Prepared.run)
        # from the rows back
        _trc.stage("select")
        try:
            return self._dispatch_stmt(stmt, session, sql_text)
        finally:
            if shared:
                lock.release_read()
            else:
                lock.release_write()

    def _stmt_read_only(self, stmt, session: Session,
                        sql_text: str) -> bool:
        """May this statement run under the SHARED side of the
        statement gate? Read-only plain SELECTs qualify; anything
        that can mutate engine-shared state — DML/DDL, txn sessions
        (latch/tscache traffic), CTE/derived temps (columnstore
        tables), view expansion (may introduce derived temps),
        sequences, nested subqueries (decorrelation can materialize
        temps) — stays exclusive. Mutations that remain on the read
        path (plan/exec caches, device uploads, store stat caches)
        are individually locked."""
        if not isinstance(stmt, ast.Select):
            return False
        if session.txn is not None or session.effects:
            return False
        if stmt.ctes or self._has_derived(stmt):
            return False
        low = (sql_text or "").lower()
        if "nextval" in low or "setval" in low or "currval" in low:
            return False
        if low.count("select") != 1:
            return False      # subqueries can decorrelate into temps
        views = self._view_map()
        if views:
            refs = ([stmt.table] if stmt.table is not None else []) \
                + [j.table for j in stmt.joins]
            if any(r.subquery is None and r.name in views
                   for r in refs):
                return False
        return True

    def _dispatch_stmt(self, stmt: ast.Statement, session: Session,
                       sql_text: str = "") -> Result:
        if isinstance(stmt, (ast.Select, ast.SetOp)):
            return self._exec_select(stmt, session, sql_text)
        if isinstance(stmt, ast.CreateTable):
            return self._exec_create(stmt)
        if isinstance(stmt, ast.DropTable):
            return self._exec_drop(stmt)
        if isinstance(stmt, ast.AlterTable):
            return self._exec_alter(stmt, session)
        if isinstance(stmt, ast.ConfigureZone):
            import json as _json
            if stmt.table not in self.store.tables:
                raise EngineError(
                    f"table {stmt.table!r} does not exist")
            allowed = {"gc.ttl_seconds", "range_max_bytes"}
            bad = set(stmt.options) - allowed
            if bad:
                raise EngineError(
                    f"unknown zone option(s) {sorted(bad)}; "
                    f"supported: {sorted(allowed)}")
            cur = self.zone_config(stmt.table)
            cur.update(stmt.options)
            self.kv.txn(lambda t: t.put(
                b"/zone/" + stmt.table.encode(),
                _json.dumps(cur, sort_keys=True).encode()))
            return Result(tag="CONFIGURE ZONE")
        if isinstance(stmt, ast.ShowZone):
            z = self.zone_config(stmt.table)
            if not z:
                z = {"gc.ttl_seconds":
                     self.settings.get("kv.gc.ttl_seconds"),
                     "range_max_bytes":
                     self.settings.get("kv.range.max_bytes")}
            return Result(names=["option", "value"],
                          rows=sorted((k, str(v))
                                      for k, v in z.items()),
                          tag="SHOW ZONE CONFIGURATION")
        if isinstance(stmt, (ast.Insert, ast.Update, ast.Delete,
                             ast.Truncate, ast.AlterTable)):
            tbl = getattr(stmt, "table", None)
            if tbl in self._view_map():
                raise EngineError(
                    f"{tbl!r} is a view; views are not modifiable")
        if isinstance(stmt, ast.CreateView):
            return self._exec_create_view(stmt, session)
        if isinstance(stmt, ast.DropView):
            return self._exec_drop_view(stmt)
        if isinstance(stmt, ast.CreateSequence):
            return self._exec_create_sequence(stmt)
        if isinstance(stmt, ast.DropSequence):
            return self._exec_drop_sequence(stmt)
        if isinstance(stmt, ast.ShowSequences):
            import json as _json
            rows = []
            for k, v in self.kv.scan(self.SEQ_PREFIX,
                                     K.prefix_end(self.SEQ_PREFIX)):
                d = _json.loads(v.decode())
                rows.append((k[len(self.SEQ_PREFIX):].decode(),
                             d["start"], d["increment"],
                             d.get("value")))
            return Result(
                names=["sequence_name", "start", "increment",
                       "last_value"],
                rows=sorted(rows), tag="SHOW SEQUENCES")
        if isinstance(stmt, ast.Truncate):
            return self._exec_truncate(stmt)
        if isinstance(stmt, ast.CreateIndex):
            return self._exec_create_index(stmt, session)
        if isinstance(stmt, ast.DropIndex):
            return self._exec_drop_index(stmt, session)
        if isinstance(stmt, ast.ShowColumns):
            d = self.catalog.get_by_name(stmt.table)
            if d is None:
                raise EngineError(
                    f"table {stmt.table!r} does not exist")
            idx_cols = {cn for i in d.indexes for cn in i.columns} \
                | set(d.primary_key)
            return Result(
                names=["column_name", "data_type", "is_nullable",
                       "indexed"],
                rows=[(c.name, str(c.type), c.nullable,
                       c.name in idx_cols)
                      for c in d.columns if c.state == "public"],
                tag="SHOW COLUMNS")
        if isinstance(stmt, ast.ShowIndexes):
            d = self.catalog.get_by_name(stmt.table)
            if d is None:
                raise EngineError(
                    f"table {stmt.table!r} does not exist")
            rows = [(stmt.table, "primary",
                     ", ".join(d.primary_key) or ROWID, True, "public")]
            rows += [(stmt.table, i.name, ", ".join(i.columns),
                      i.unique, i.state) for i in d.indexes]
            return Result(
                names=["table_name", "index_name", "columns",
                       "unique", "state"],
                rows=rows, tag="SHOW INDEXES")
        if isinstance(stmt, ast.Insert):
            return self._exec_insert(stmt, session)
        if isinstance(stmt, ast.Update):
            return self._exec_update(stmt, session)
        if isinstance(stmt, ast.Delete):
            return self._exec_delete(stmt, session)
        if isinstance(stmt, ast.SetVar):
            if stmt.cluster:
                self.settings.set(stmt.name, stmt.value)
            elif stmt.name == "statement_diagnostics":
                # SQL arming surface for the diagnostics registry:
                # SET statement_diagnostics = '<stmt text>' arms that
                # statement's fingerprint so its NEXT execution
                # captures a bundle (the HTTP twin is POST
                # /_status/stmtdiag; fetch at /_status/stmtdiag/<id>)
                req = self.stmtdiag.arm(str(stmt.value))
                return Result(
                    names=["request_id", "fingerprint"],
                    rows=[(req["request_id"], req["fingerprint"])],
                    tag="SET")
            else:
                session.vars.set(stmt.name, stmt.value)
            return Result(tag="SET")
        if isinstance(stmt, ast.Backup):
            from ..jobs.backup import BACKUP_JOB
            for t in stmt.tables:
                if t not in self.store.tables:
                    raise EngineError(f"table {t!r} does not exist")
            jid = self.jobs.create(BACKUP_JOB, {
                "tables": stmt.tables, "dest": stmt.dest})
            rec = self.jobs.run_job(jid)
            if rec.status != "succeeded":
                raise EngineError(f"BACKUP failed: {rec.error}")
            return Result(names=["job_id"], rows=[(jid,)], tag="BACKUP")
        if isinstance(stmt, ast.Restore):
            from ..jobs.backup import RESTORE_JOB
            jid = self.jobs.create(RESTORE_JOB, {
                "tables": stmt.tables, "src": stmt.src})
            rec = self.jobs.run_job(jid)
            if rec.status != "succeeded":
                raise EngineError(f"RESTORE failed: {rec.error}")
            return Result(names=["job_id"], rows=[(jid,)],
                          tag="RESTORE")
        if isinstance(stmt, ast.CreateChangefeed):
            jid = self.create_changefeed(stmt.table, stmt.sink)
            return Result(names=["job_id"], rows=[(jid,)],
                          tag="CREATE CHANGEFEED")
        if isinstance(stmt, ast.ShowJobs):
            recs = sorted(self.jobs.jobs(), key=lambda r: r.id)
            return Result(
                names=["job_id", "job_type", "status",
                       "fraction_completed"],
                rows=[(r.id, r.type, r.status,
                       round(r.fraction_completed, 3)) for r in recs],
                tag="SHOW JOBS")
        if isinstance(stmt, ast.CancelJob):
            # async cancel (the statement lock is held here and the
            # changefeed thread may be waiting on it — joining would
            # self-deadlock); the job observes the request at its next
            # check_cancel and exits
            self.jobs.cancel(stmt.job_id)
            self._cdc_threads.pop(stmt.job_id, None)
            return Result(tag="CANCEL JOB")
        if isinstance(stmt, ast.ShowTables):
            descs = sorted(self.catalog.list_tables(),
                           key=lambda d: d.name)
            return Result(
                names=["table_name", "version"],
                rows=[(d.name, d.version) for d in descs
                      if not d.name.startswith("__")],
                tag="SHOW TABLES")
        if isinstance(stmt, ast.ShowVar):
            v = session.vars.get(stmt.name, None)
            if v is None:
                v = self.settings.get(stmt.name)
            return Result(names=[stmt.name], rows=[(v,)], tag="SHOW")
        if isinstance(stmt, ast.Explain):
            from ..sql.stats import estimate
            if stmt.analyze:
                return self._explain_analyze(stmt.stmt, session,
                                             sql_text,
                                             debug=stmt.debug)
            target = stmt.stmt
            from ..sql.rules import RuleTrace
            rtrace = RuleTrace()
            if isinstance(target, ast.Select):
                expanded = self._expand_views(target)
                if expanded is not target:
                    rtrace.fire("expand_views")
                target = expanded
            if isinstance(target, ast.Select) and (
                    target.ctes or self._has_derived(target)):
                # composite shapes (CTEs / derived / views): explain
                # each sub-plan; the main stage re-plans over the
                # materialized temps at execution time
                return Result(
                    names=["plan"],
                    rows=[(ln,) for ln in
                          self._explain_composite(target, session)],
                    tag="EXPLAIN")
            node, emeta = self._plan(target, session,
                                     for_explain=True, trace=rtrace)
            costs = estimate(node, self.catalog_view().stats)
            tree = P.plan_tree_repr(node, costs=costs)
            rows = []
            tr = emeta.rule_trace
            if tr is not None and tr.firings:
                rows.append(
                    ("rules: " + "; ".join(tr.summary()),))
            for alias, ap in sorted(emeta.access_paths.items()):
                label, est, cost = ap
                if not label.startswith("full"):
                    rows.append((f"access: {alias} via {label} "
                                 f"rows≈{est:.0f} "
                                 f"cost≈{cost:.0f}",))
            if emeta.memo is not None:
                m_ = emeta.memo
                rows.append((
                    f"memo: {m_.groups} groups, {m_.considered} "
                    f"plans costed; best order "
                    f"{[m_.root] + m_.order} cost≈{m_.cost:.0f}",))
            if isinstance(target, ast.Select):
                m = self._index_fastpath_match(target, session)
                if m is not None:
                    label, cols, vals, _residual = m
                    # mirror the runtime selectivity guard when a warm
                    # locator exists; never BUILD one here — EXPLAIN
                    # must stay metadata-only (no O(table) work)
                    tname = target.table.name
                    td = self.store.table(tname)
                    lim = int(session.vars.get(
                        "index_lookup_limit", 4096))
                    cached = td.sec_index_cache.get(cols)
                    declined = (
                        cached is not None
                        and cached[0] == td.generation
                        and len(cached[1].get(vals, [])) > lim)
                    if not declined:
                        rows.append((
                            f"index scan {tname}@{label} "
                            f"({', '.join(cols)}) = {vals!r}",))
            rows += [(line,) for line in tree.rstrip().split("\n")]
            return Result(names=["plan"], rows=rows, tag="EXPLAIN")
        if isinstance(stmt, ast.ShowCreateTable):
            d = self.catalog.get_by_name(stmt.table)
            if d is None:
                raise EngineError(
                    f"table {stmt.table!r} does not exist")
            if d.view_sql:
                cols = (f" ({', '.join(d.view_columns)})"
                        if d.view_columns else "")
                ddl = f"CREATE VIEW {d.name}{cols} AS {d.view_sql}"
            else:
                ddl = _render_create(d)
            return Result(names=["table_name", "create_statement"],
                          rows=[(d.name, ddl)],
                          tag="SHOW CREATE TABLE")
        if isinstance(stmt, ast.ShowAll):
            return Result(
                names=["variable", "value"],
                rows=sorted((k, str(v))
                            for k, v in session.vars.values.items()),
                tag="SHOW ALL")
        if isinstance(stmt, ast.ShowTrace):
            rows = []
            for rec in session.trace:
                for line in rec.tree_lines():
                    rows.append((line,))
            return Result(names=["span"], rows=rows,
                          tag="SHOW TRACE")
        if isinstance(stmt, ast.ShowStatements):
            return Result(
                names=["fingerprint", "count", "mean_latency_ms",
                       "max_latency_ms", "rows", "failures"],
                rows=[(s.fingerprint, s.count,
                       round(s.mean_latency_s * 1e3, 3),
                       round(s.max_latency_s * 1e3, 3),
                       s.total_rows, s.failures)
                      for s in self.sqlstats.all()],
                tag="SHOW STATEMENTS")
        if isinstance(stmt, ast.Analyze):
            self.store.analyze(stmt.table)
            # stats feed the memo's join order and Compact capacities
            self._text_plans_clear("analyze")
            self.metrics.counter("sql.stats.analyze",
                                 "ANALYZE statements run").inc()
            return Result(tag="ANALYZE")
        if isinstance(stmt, ast.BeginTxn):
            if session.txn is not None:
                raise EngineError("transaction already open")
            session.txn = Txn(self.kv.store)
            session.effects = []
            session.txn_aborted = False
            return Result(tag="BEGIN")
        if isinstance(stmt, ast.CommitTxn):
            t = session.txn
            if t is None:
                return Result(tag="COMMIT")
            effects = session.effects
            aborted = session.txn_aborted
            session.txn, session.effects = None, []
            session.txn_aborted = False
            if aborted:
                # COMMIT of an aborted txn is a rollback (pg semantics)
                t.rollback()
                return Result(tag="ROLLBACK")
            toks = {}
            try:
                if self.cluster is not None and effects:
                    toks = self._bump_table_gens(
                        t, sorted({tb for tb, _ in effects}))
                commit_ts = t.commit()
            except (TxnRetryError, TxnAbortedError) as e:
                t.rollback()
                # the pg "restart transaction" error class (40001):
                # client must retry the whole txn
                raise EngineError(f"restart transaction: {e}") from e
            self._publish(effects, commit_ts)
            self._scan_gens.update(toks)
            return Result(tag="COMMIT")
        if isinstance(stmt, ast.RollbackTxn):
            if session.txn is not None:
                session.txn.rollback()
            session.txn, session.effects = None, []
            session.txn_aborted = False
            return Result(tag="ROLLBACK")
        raise EngineError(f"unsupported statement {type(stmt).__name__}")

    def _explain_composite(self, sel: ast.Select,
                           session: Session) -> list[str]:
        """EXPLAIN for CTE / derived-table / view shapes: one plan
        block per sub-select (the reference similarly renders each
        WithExpr's bound plan); the main stage is re-planned over the
        materialized temps at execution."""
        from ..sql.stats import estimate
        lines: list[str] = []

        def emit(label: str, sub):
            if isinstance(sub, ast.Select):
                sub = self._expand_views(sub)
            lines.append(f"{label}:")
            if isinstance(sub, ast.Select) and (
                    sub.ctes or self._has_derived(sub)):
                lines.extend("  " + ln for ln in
                             self._explain_composite(sub, session))
            elif isinstance(sub, ast.Select) and sub.table is not None:
                node, _ = self._plan(sub, session, for_explain=True)
                costs = estimate(node, self.catalog_view().stats)
                lines.extend(
                    "  " + ln for ln in P.plan_tree_repr(
                        node, costs=costs).rstrip().split("\n"))
            else:
                lines.append(
                    "  (table-free or set-op; planned at execution)")

        for name, _cols, s in sel.ctes:
            emit(f"cte {name}", s)
        refs = ([sel.table] if sel.table is not None else []) \
            + [j.table for j in sel.joins]
        for r in refs:
            if r.subquery is not None:
                emit(f"derived {r.alias or r.name}", r.subquery)
        lines.append(
            "main: re-planned over the materialized temps at "
            "execution")
        return lines

    def _explain_analyze(self, sel, session: Session,
                         sql_text: str, debug: bool = False) -> Result:
        """EXPLAIN ANALYZE: run the statement under a trace recording
        and render the plan with measured phase timings + row counts
        (the reference's instrumented statement diagnostics,
        sql/instrumentation.go). ``debug`` (EXPLAIN ANALYZE (DEBUG))
        instead captures a full statement diagnostics bundle, stores
        it in the registry (fetchable at /_status/stmtdiag/<id>), and
        returns the JSON inline."""
        if not isinstance(sel, ast.Select):
            raise EngineError("can only EXPLAIN ANALYZE SELECT")
        import time as _time
        from . import profile as _prof
        if debug:
            import json as _json
            try:
                m0 = self.metrics.snapshot()
            except Exception:
                m0 = {}
            dc0 = coldstart.thread_compile_seconds()
            psink = _prof.ProfileSink()
            with _prof.active(psink, fine=True):
                with self.tracer.capture(
                        "explain-analyze-debug",
                        record_request=True) as rec:
                    t0 = _time.monotonic()
                    self._exec_select(sel, session, sql_text)
                    dt = _time.monotonic() - t0
            compile_s = coldstart.thread_compile_seconds() - dc0
            bundle = self._diag_bundle(sel, session, sql_text, rec,
                                       psink, dt, compile_s, m0)
            bundle["id"] = self.stmtdiag.fulfill(None, bundle)
            return Result(
                names=["bundle"],
                rows=[(_json.dumps(bundle, default=str),)],
                tag="EXPLAIN ANALYZE (DEBUG)")
        c0 = coldstart.thread_compile_seconds()
        with self.tracer.capture("explain-analyze") as rec:
            t0 = _time.monotonic()
            res = self._exec_select(sel, session, sql_text)
            total_ms = (_time.monotonic() - t0) * 1e3
        xla_ms = (coldstart.thread_compile_seconds() - c0) * 1e3
        node, _ = self._plan(sel, session)
        from ..sql.stats import estimate
        cv = self.catalog_view()
        costs = estimate(node, cv.stats)
        sources = self._scan_estimate_sources(node, cv)
        try:
            actuals, prof, _pw = self._measure_operator_profile(node)
        except Exception:
            actuals = prof = None   # diagnostics must never fail the
            #                         statement
        lines = ["planning/execution:"]

        def layers(s, depth):
            # the statement's layer spans, nested as recorded
            for c in s.children:
                if c.name in ("plan", "compile", "upload", "dispatch",
                              "queue", "materialize", "pull", "decode"):
                    tag_s = "".join(f" {k}={v}"
                                    for k, v in c.tags.items())
                    lines.append(f"{'  ' * depth}{c.name}: "
                                 f"{c.duration_ms:.2f}ms{tag_s}")
                    layers(c, depth + 1)
        layers(rec, 1)
        if xla_ms > 0:
            # "slow because compiling" vs "slow because executing":
            # XLA backend-compile time inside this statement (~0 on
            # plan-cache hits and warm persistent-cache restarts)
            lines.append(f"  xla compile: {xla_ms:.2f}ms")
        lines.append(f"  total: {total_ms:.2f}ms, "
                     f"rows returned: {len(res.rows)}")
        lines.append("plan:")
        lines.extend("  " + ln for ln in P.plan_tree_repr(
            node, costs=costs, actuals=actuals,
            sources=sources, profile=prof).rstrip().split("\n"))

        # stitched remote recordings (trace propagation): subtrees
        # tagged with the serving node id render per-node, the
        # reference's distributed statement diagnostics
        def remote_roots(s):
            out = []
            for c in s.children:
                if c.tags.get("node") is not None and (
                        c.name in ("flow", "flow-stage")
                        or c.name.startswith("rpc:")):
                    out.append(c)
                else:
                    out.extend(remote_roots(c))
            return out
        rr = remote_roots(rec)
        if rr:
            lines.append("distributed:")
            for s in rr:
                lines.extend("  " + ln for ln in s.tree_lines())
        return Result(names=["info"], rows=[(ln,) for ln in lines],
                      tag="EXPLAIN ANALYZE")

    def _scan_estimate_sources(self, node, cv) -> dict:
        """id(scan) -> where the optimizer's cardinalities for that
        table came from ("analyze" | "sketch" | "default"), rendered
        next to the estimates by EXPLAIN ANALYZE."""
        out: dict = {}

        def rec(n):
            if isinstance(n, P.Scan):
                st = cv.stats.get(n.table)
                out[id(n)] = getattr(st, "source", "default")
            for attr in ("child", "left", "right"):
                c = getattr(n, attr, None)
                if c is not None:
                    rec(c)
        rec(node)
        return out

    def _measure_actual_rows(self, node) -> dict:
        """Back-compat shim: actual row counts only (the est-vs-actual
        columns). Prefer _measure_operator_profile."""
        return self._measure_operator_profile(node)[0]

    def _measure_operator_profile(self, node):
        """Instrumented re-execution for EXPLAIN ANALYZE / diagnostics
        bundles: compile the plan with a row hook AND a ProfileSink
        and run it eagerly (unjitted) over wide resident uploads. Each
        operator closure records post-sel rows, self device-seconds
        (block_until_ready at operator exit; self = inclusive minus
        children), and scan upload bytes. Returns
        ``(actuals, sink, wall_s)`` where actuals is the
        id(node) -> rows dict of the est-vs-actual columns and wall_s
        is the profiled execution's independently-measured wall — the
        denominator the per-operator device_seconds must sum close to.
        Diagnostics only: gateway-local and resident regardless of
        the statement's real placement verdict, and any failure falls
        back to estimate-only rendering at the call site."""
        import time as _time
        from . import profile as _prof
        actual: dict = {}

        def hook(n, batch):
            try:
                actual[id(n)] = int(np.asarray(batch.sel).sum())
            except Exception:
                pass
        sink = _prof.ProfileSink()
        scans = {alias: self._device_table(tname, narrow=False)
                 for alias, tname in _collect_scans(node).items()}
        runf = compile_plan(node,
                            ExecParams(row_hook=hook, profile=sink))
        t0 = _time.monotonic()
        with _prof.active(sink, fine=True):
            runf(RunContext(
                scans, read_ts_words(self.clock.now().to_int())))
        return actual, sink, _time.monotonic() - t0

    def _diag_bundle(self, stmt, session: Session, sql_text: str,
                     rec, psink, dt: float, compile_s: float,
                     m0) -> dict:
        """Assemble one statement diagnostics bundle (the reference's
        stmtdiagnostics zip, here a JSON dict): bound plan with
        per-operator profile annotations, the operator profile itself,
        the trace recording, cluster settings + session vars, sketch
        stats for every referenced table, and the statement's metric
        deltas. Every section is best-effort — diagnostics must never
        fail the statement that carried them."""
        from ..utils.sqlstats import fingerprint
        from . import profile as _prof
        bundle: dict = {
            "sql": sql_text,
            "fingerprint": (fingerprint(sql_text) if sql_text
                            else type(stmt).__name__),
            "statement": type(stmt).__name__,
            "latency_s": dt,
            "compile_s": compile_s,
            "device_time_s": max(0.0, dt - compile_s),
        }
        target = stmt.stmt if isinstance(stmt, ast.Explain) else stmt
        merged = _prof.ProfileSink()
        if psink is not None:
            merged.merge(psink)
        prof_wall = None
        node = None
        try:
            if isinstance(target, ast.Select) and not target.ctes \
                    and not self._has_derived(target):
                node, _ = self._plan(target, session)
                from ..sql.stats import estimate
                cv = self.catalog_view()
                costs = estimate(node, cv.stats)
                actuals, fine, prof_wall = \
                    self._measure_operator_profile(node)
                merged.merge(fine)
                bundle["plan"] = P.plan_tree_repr(
                    node, costs=costs, actuals=actuals,
                    sources=self._scan_estimate_sources(node, cv),
                    profile=fine).rstrip().split("\n")
        except Exception:
            pass
        bundle.setdefault("plan", [])
        bundle["profile"] = {
            # the profiled execution's wall: remote-stitched entries
            # carry their own walls in "remote_device_time_s" slots
            # merged by the caller (distsql Gateway); locally it is
            # the instrumented rerun's measured wall
            "device_time_s": (prof_wall if prof_wall is not None
                              else max(0.0, dt - compile_s)),
            "ops": merged.to_wire(),
        }
        try:
            bundle["trace"] = (_trc.span_to_wire(rec)
                               if rec is not None else None)
        except Exception:
            bundle["trace"] = None
        try:
            bundle["settings"] = {k: str(v) for k, v in
                                  self.settings.snapshot().items()}
        except Exception:
            bundle["settings"] = {}
        try:
            bundle["session_vars"] = {
                k: str(v) for k, v in session.vars.values.items()}
        except Exception:
            bundle["session_vars"] = {}
        try:
            stats: dict = {}
            if node is not None:
                cv = self.catalog_view()
                for tname in sorted(
                        set(_collect_scans(node).values())):
                    st = cv.stats.get(tname)
                    if st is None:
                        continue
                    d = {}
                    for a in ("rows", "row_count", "source",
                              "analyzed_rows", "distinct"):
                        v = getattr(st, a, None)
                        if isinstance(v, (int, float, str)):
                            d[a] = v
                    stats[tname] = d
            bundle["sketch_stats"] = stats
        except Exception:
            bundle["sketch_stats"] = {}
        try:
            m1 = self.metrics.snapshot()
            m0 = m0 or {}
            bundle["metric_deltas"] = {
                k: v - m0.get(k, 0) for k, v in m1.items()
                if isinstance(v, (int, float))
                and isinstance(m0.get(k, 0), (int, float))
                and v != m0.get(k, 0)}
        except Exception:
            bundle["metric_deltas"] = {}
        return bundle

    def operator_profile(self, sql: str,
                         session: Session | None = None) -> dict:
        """Profile one SELECT's operators via the instrumented eager
        rerun and return the digest (a benchmark can record this per
        headline query: top operators by device_seconds + total bytes
        moved). Never touches the statement's real execution path."""
        sess = session or self.session()
        stmt = parser.parse(sql)
        if isinstance(stmt, ast.Explain):
            stmt = stmt.stmt
        node, _ = self._plan(stmt, sess)
        _actuals, sink, wall = self._measure_operator_profile(node)
        out = sink.summary()
        out["wall_s"] = round(wall, 6)
        return out

    # -- catalog -------------------------------------------------------------
    def catalog_view(self, int_ranges: bool = True,
                     read_ts: Timestamp | None = None,
                     stats: bool = True,
                     sketch: bool = True) -> CatalogView:
        """``stats=False`` hides every data-dependent signal (row
        counts, distinct/uniqueness probes, int ranges) so the plan
        SHAPE is a pure function of schema + statement — required by
        distsql/shuffle.py, where every node must re-derive an
        identical stage graph from the SQL despite holding a
        different shard."""
        from ..sql.stats import TableStats
        # planners see the PUBLIC schema: columns mid-add (WRITE_ONLY
        # descriptor state, schemachange.py) are physically present but
        # hidden until published
        schemas = {}
        for n, td in self.store.tables.items():
            if any(c.hidden for c in td.schema.columns):
                s = TableSchema(
                    name=td.schema.name,
                    columns=[c for c in td.schema.columns
                             if not c.hidden],
                    primary_key=list(td.schema.primary_key),
                    table_id=td.schema.table_id)
                schemas[n] = s
            else:
                schemas[n] = td.schema
        dicts = {n: dict(td.dictionaries)
                 for n, td in self.store.tables.items()}
        indexes = {}
        for n in self.store.tables:
            try:
                defs = self._table_indexes(n)
            except Exception:
                defs = []
            pub = [(i.name, tuple(i.columns), i.unique)
                   for i in defs if i.state == "public"]
            if pub:
                indexes[n] = pub
        if not stats:
            return CatalogView(schemas, dicts, {}, indexes=indexes)
        stale_frac = self.settings.get("sql.stats.stale_row_fraction")
        stats_map = {}
        for n, td in self.store.tables.items():
            st = None
            if td.stats is not None:
                # ANALYZE output wins while the table hasn't drifted
                # far from the row count it was computed at; past the
                # threshold it is STALE — exact-but-wrong numbers stop
                # beating live sketch estimates
                base = max(td.stats.analyzed_rows, 0)
                drifted = abs(td.row_count - base) > \
                    stale_frac * max(base, 1)
                if not (sketch and drifted):
                    st = TableStats(
                        row_count=td.row_count,
                        distinct=dict(td.stats.distinct),
                        null_frac=dict(td.stats.null_frac),
                        analyzed=td.stats_generation == td.generation,
                        source="analyze",
                        analyzed_rows=td.stats.analyzed_rows)
            if st is None and sketch and td.chunks:
                try:
                    st = self.store.sketch_stats(n)
                    st.row_count = td.row_count
                except Exception:
                    st = None
            if st is None:
                st = TableStats(row_count=td.row_count)
            stats_map[n] = st
        unique_fn = None
        if read_ts is not None:
            rti = read_ts.to_int()

            def unique_fn(t, cols, _rti=rti):
                return self.store.keys_unique_for_read(t, cols, _rti)
        return CatalogView(schemas, dicts, stats_map,
                           key_distinct_fn=self.store.key_distinct,
                           int_range_fn=(self.store.key_int_range
                                         if int_ranges else None),
                           keys_unique_fn=unique_fn,
                           indexes=indexes)

    def _read_ts(self, session: Session) -> Timestamp:
        return session.txn_read_ts or self.clock.now()

    def _as_of_ts(self, sel, session: Session):
        """Resolve AS OF SYSTEM TIME to a Timestamp, or None when the
        statement has no AS OF clause. Accepted forms (a subset of
        the reference's, sql/as_of.go): a negative interval string
        ('-10s', '-2m', '-1h'), a timestamp string, or a decimal HLC
        wall-nanos value."""
        aso = getattr(sel, "as_of", None)
        if aso is None:
            return None
        if session.txn is not None:
            raise EngineError(
                "AS OF SYSTEM TIME is not allowed inside a "
                "transaction")
        if not isinstance(aso, ast.Literal):
            raise EngineError(
                "AS OF SYSTEM TIME requires a constant")
        v = aso.value
        if isinstance(v, str):
            import re as _re
            m = _re.fullmatch(r"-(\d+(?:\.\d+)?)([smh])", v.strip())
            if m:
                mult = {"s": 1e9, "m": 60e9, "h": 3600e9}[m.group(2)]
                wall = self.clock.now().wall - int(
                    float(m.group(1)) * mult)
            else:
                from ..sql.binder import parse_timestamp
                try:
                    wall = parse_timestamp(v) * 1000  # micros -> ns
                except Exception:
                    raise EngineError(
                        f"cannot parse AS OF SYSTEM TIME {v!r}")
        elif isinstance(v, (int, float)):
            wall = int(v)
        else:
            raise EngineError(
                f"cannot parse AS OF SYSTEM TIME {v!r}")
        if wall <= 0 or wall > self.clock.now().wall:
            raise EngineError(
                "AS OF SYSTEM TIME must be in the past")
        return Timestamp(int(wall), 0)

    # -- SELECT --------------------------------------------------------------
    def _plan(self, stmt, session, for_explain: bool = False,
              no_memo: bool = False, trace=None,
              subquery_slots: list | None = None):
        if not isinstance(stmt, ast.Select):
            raise EngineError("can only EXPLAIN SELECT")
        # AS OF pins the whole statement: now() and plan-time
        # subquery evaluation read at the historical timestamp too
        # (the reference pins the txn's read ts, sql/as_of.go)
        read_ts = self._as_of_ts(stmt, session) or \
            self._read_ts(session)
        # EXPLAIN must not execute volatile functions: sequences bind
        # to a placeholder instead of allocating (pg EXPLAIN semantics)
        seq_ops = ((lambda fn, name, arg: 0) if for_explain
                   else self._sequence_ops(session))
        cv = self.catalog_view(
            # int-range dense GROUP BY is withheld inside explicit
            # txns: overlay rows could fall outside the committed range
            # and corrupt the mixed-radix group code
            int_ranges=(session.txn is None),
            read_ts=(read_ts if session.txn is None else None),
            sketch=(str(session.vars.get("optimizer_sketch_stats",
                                         "on")).lower()
                    not in ("off", "false")))
        planner = Planner(
            cv,
            subquery_eval=lambda sel, lim: self._eval_subquery(
                _propagate_as_of(sel, stmt), session, lim),
            now_micros=read_ts.wall // 1000,
            sequence_ops=seq_ops,
            use_memo=(not no_memo
                      and session.vars.get("optimizer", "on")
                      != "off"),
            volatile_fold_ok=for_explain,
            rules=(session.vars.get("optimizer_rules", "on")
                   != "off"),
            trace=trace,
            # a caller that dispatches what it plans keeps the
            # subqueries' prepared statements in this list
            subquery_arg=(None if subquery_slots is None else
                          lambda sel: self._prepare_subquery(
                              _propagate_as_of(sel, stmt), session,
                              subquery_slots)))
        result = planner.plan_select(stmt)
        self._prove_agg_arg_ranges(result[0], session)
        self._size_hash_sorts(result[0])
        self._size_grouping_sets(result[0])
        if not for_explain:
            self._count_plan_source(result[0], cv)
        return result

    def _count_plan_source(self, node, cv) -> None:
        """sql.optimizer.{sketch,analyze,default}_plans: classify each
        planned statement by the best estimate source its scans drew
        on (sketch beats analyze beats default, mirroring how much of
        the new costing actually engaged)."""
        try:
            from ..sql import plan as P
            srcs = set()

            def rec(n):
                if isinstance(n, P.Scan):
                    st = cv.stats.get(n.table)
                    if st is not None:
                        srcs.add(getattr(st, "source", "default"))
                for attr in ("child", "left", "right"):
                    c = getattr(n, attr, None)
                    if c is not None:
                        rec(c)
            rec(node)
            kind = ("sketch" if "sketch" in srcs
                    else "analyze" if "analyze" in srcs
                    else "default")
            self.metrics.counter(
                f"sql.optimizer.{kind}_plans",
                "planned statements by estimate source").inc()
        except Exception:
            pass

    # -- sequences ------------------------------------------------------------
    SEQ_PREFIX = b"/seq/"

    def _sequence_ops(self, session: Session):
        return lambda fn, name, arg: self._sequence_op(
            session, fn, name, arg)

    def _seq_desc(self, name: str) -> dict:
        import json as _json
        raw = self.kv.txn(
            lambda t: t.get(self.SEQ_PREFIX + name.encode()))
        if raw is None:
            raise EngineError(f"sequence {name!r} does not exist")
        return _json.loads(raw.decode())

    def _sequence_op(self, session: Session, fn: str, name: str,
                     arg) -> int:
        """nextval/currval/setval. nextval allocates in its OWN KV
        txn — sequence values are never rolled back (pg semantics;
        the reference likewise increments outside the user txn,
        pkg/sql/sequence.go)."""
        import json as _json
        key = self.SEQ_PREFIX + name.encode()
        if fn == "currval":
            if name not in session.seq_currval:
                raise EngineError(
                    f"currval of sequence {name!r} is not yet "
                    f"defined in this session")
            return session.seq_currval[name]
        if fn == "nextval":
            def bump(t):
                raw = t.get(key)
                if raw is None:
                    raise EngineError(
                        f"sequence {name!r} does not exist")
                d = _json.loads(raw.decode())
                if d.get("value") is None:
                    d["value"] = d["start"]
                else:
                    d["value"] += d["increment"]
                t.put(key, _json.dumps(d).encode())
                return d["value"]
            v = self.kv.txn(bump)
        else:  # setval
            desc = self._seq_desc(name)
            desc["value"] = int(arg)
            self.kv.txn(lambda t: t.put(
                key, _json.dumps(desc).encode()))
            v = int(arg)
        session.seq_currval[name] = v
        return v

    # -- subqueries / CTEs ---------------------------------------------------
    def _prepare_subquery(self, sel, session: Session, slots: list):
        """Prepare an uncorrelated scalar subquery as a statement of
        its own and append it to `slots`: (slot, result type) for the
        binder's BSubqueryArg, or None for a shape that only
        _eval_subquery serves (set operations, CTEs and derived
        tables, a table-free SELECT, paged and spilled plans, a
        transaction's overlay). The statement that holds the argument
        runs it at each dispatch, at the dispatch's read timestamp
        (Prepared.subquery_params)."""
        if not isinstance(sel, ast.Select) or session.txn is not None \
                or session.effects or self._cte_capture is not None:
            return None
        sel = self._decorrelate(
            self._expand_views(sel),
            inline=self._plans_in_place(sel, session))
        if sel.ctes or self._has_derived(sel) or sel.table is None:
            return None
        prep = self._prepare_select(sel, session, f"(subquery {sel!r})")
        if prep.stream is not None or prep.spill is not None:
            return None
        if len(prep.meta.types) != 1:
            raise BindError("scalar subquery must return one column")
        slots.append(prep)
        return len(slots) - 1, prep.meta.types[0]

    def _eval_subquery(self, sel: ast.Select, session: Session,
                       limit_one: bool = False):
        """Execute an expression subquery while the main statement is
        bound (the reference's planTop.subqueryPlans, sql/subquery.go)
        and hand (rows, types) back to the binder, which writes them
        into the plan as constants: EXISTS, IN (SELECT ...), and the
        scalar subqueries _prepare_subquery does not take."""
        import copy
        if limit_one and sel.limit is None:
            sel = copy.copy(sel)
            sel.limit = 1  # EXISTS needs one row, not the result set
        res = self._exec_select(sel, session, f"(subquery {sel!r})")
        return res.rows, res.types

    def _decorrelate(self, sel: ast.Select,
                     inline: bool = False) -> ast.Select:
        """Unnest correlated (NOT) EXISTS and correlated scalar
        subqueries into grouped LEFT JOINs (sql/decorrelate.py; the
        opt/norm/decorrelate.go analogue). `inline`: the statement
        runs as one program on one device (_plans_in_place), so an
        EXISTS / NOT EXISTS whose correlations are all equalities
        becomes a SEMI / ANTI join of the subquery's table, a count
        beneath an outer join is pushed below it (eager_count), and
        derived tables' bodies are unnested too."""
        from ..sql.decorrelate import (decorrelate_exists,
                                       decorrelate_scalar, eager_count)

        from ..sql.types import Family

        def columns_of(name):
            if name not in self.store.tables:
                return None
            return set(self.store.table(name).schema.column_names)

        def is_string_col(table, col):
            try:
                sch = self.store.table(table).schema
                return sch.column(col).type.uses_dictionary
            except KeyError:
                return True   # unknown: refuse the min/max trick
        # a SEMI / ANTI join reads the subquery's table as a build
        # side of the statement's own program; on a mesh the statement
        # keeps the grouped LEFT JOIN the distributed planner knows
        join_ok = ((lambda table, alias: table in self.store.tables)
                   if inline else None)
        applied: list = []
        out = decorrelate_exists(sel, columns_of, is_string_col,
                                 join_ok=join_ok, applied=applied)
        out = decorrelate_scalar(out, columns_of, applied=applied)
        for kind in applied:
            self._m_decorrelate[kind].inc()
        if inline:
            out = eager_count(out, columns_of)
        if inline and self._has_derived(out):
            # a derived table's body is a statement of its own: its
            # subqueries unnest in it (q22's custsale)
            import copy
            if out is sel:
                out = copy.copy(sel)
            out.joins = [copy.copy(j) for j in out.joins]
            for holder in [out] + out.joins:    # each has a .table
                ref = holder.table
                if ref is not None and ref.subquery is not None:
                    holder.table = ast.TableRef(
                        ref.name, ref.alias,
                        self._decorrelate_body(ref.subquery))
        return out

    def _decorrelate_body(self, body):
        """A derived table's body decorrelated in place: a SELECT, or
        each SELECT branch of a set operation."""
        import copy
        if isinstance(body, ast.SetOp):
            body = copy.copy(body)
            body.left = self._decorrelate_body(body.left)
            body.right = self._decorrelate_body(body.right)
            return body
        return self._decorrelate(body, inline=True)

    def _plans_in_place(self, sel, session: Session) -> bool:
        """Does this statement run as one program on one device, so
        that its subqueries' tables can join it (SEMI / ANTI) and its
        derived tables be planned in place? Not on a mesh that may
        distribute it (the distributed planner keeps grouped LEFT
        JOINs over temps), not inside a transaction's overlay, not
        while a composed-CTE capture records temps. A statement's CTEs
        are planned in place where each is read once in a FROM
        (stmtutil.inline_ctes); others keep the temps."""
        if not isinstance(sel, ast.Select):
            return False
        if session.txn is not None or session.effects \
                or self._cte_capture is not None \
                or getattr(session, "_cte_depth", 0):
            return False
        return (self.mesh is None or self.mesh.size <= 1
                or session.vars.get("distsql", "auto") == "off")

    def _stored_columns(self, name: str):
        """A stored table's column names, or None."""
        if name not in self.store.tables:
            return None
        return set(self.store.table(name).schema.column_names)

    @staticmethod
    def _has_derived(sel: ast.Select) -> bool:
        refs = ([sel.table] if sel.table is not None else []) + \
            [j.table for j in sel.joins]
        return any(r.subquery is not None for r in refs)

    def _exec_with_temps(self, sel: ast.Select, session: Session,
                         sql_text: str) -> Result:
        """WITH ctes / FROM (SELECT...): materialize each into a temp
        columnstore table, rewrite references, run the main query, drop
        the temps. The reference plans CTEs as once-materialized
        buffers (sql/opt: WithExpr / spool); here the natural TPU form
        is a temp scan-plane table the main program reads like any
        other."""
        import copy
        # DEEP copy: the rewrites below assign into nested JoinClause/
        # TableRef objects; a shallow copy would corrupt the caller's
        # AST, which prepared statements re-execute (decorrelate's
        # deepcopy used to mask this, but it now skips subquery-free
        # statements)
        sel = copy.deepcopy(sel)
        temps: list[str] = []
        mapping: dict[str, str] = {}
        # STABLE temp names: re-executions of the same statement (a
        # pgwire portal / Prepared re-run) must produce the same temp
        # table names, or every plan/executable-cache key downstream
        # misses and the main query pays a full XLA recompile per
        # execution (~1.5s/exec measured on q9). Session identity
        # separates concurrent sessions; nesting depth separates a
        # CTE whose body re-enters this path.
        depth = getattr(session, "_cte_depth", 0)
        session._cte_depth = depth + 1
        if depth > 0 and self._cte_capture is not None:
            # nested CTE bodies re-enter here; the composition only
            # models one level — keep such statements on the slow path
            self._cte_capture["disabled"] = True
        prefix = f"__cte_{id(session):x}_d{depth}"
        seq = [0]

        def _tname(name: str) -> str:
            seq[0] += 1
            return f"{prefix}_{seq[0]}_{name}"

        try:
            for name, cols, sub in sel.ctes:
                sub = _propagate_as_of(
                    _rewrite_table_names(sub, mapping), sel)
                tname = _tname(name)
                self._materialize_temp_select(tname, sub, session,
                                              cols, f"(cte {sub!r})")
                self._m_cte_temps.inc()
                mapping[name] = tname
                temps.append(tname)
            sel.ctes = []
            refs = ([("table", sel.table)] if sel.table is not None
                    else []) + [("join", j) for j in sel.joins]
            for kind, obj in refs:
                ref = obj if kind == "table" else obj.table
                if ref.subquery is None:
                    continue
                sub = _propagate_as_of(
                    _rewrite_table_names(ref.subquery, mapping), sel)
                tname = _tname(ref.alias)
                self._materialize_temp_select(
                    tname, sub, session, None, f"(derived {sub!r})")
                self._m_cte_temps.inc()
                temps.append(tname)
                newref = ast.TableRef(tname, ref.alias)
                if kind == "table":
                    sel.table = newref
                else:
                    obj.table = newref
            sel = _rewrite_table_names(sel, mapping)
            if self._cte_capture is not None and depth == 0:
                # the next _prepare_select is the main program
                self._cte_capture["want_main"] = True
            return self._exec_select(sel, session, sql_text)
        finally:
            session._cte_depth = depth
            for t in temps:
                if t in self.store.tables:
                    self.store.drop_table(t)
                    for k in [k for k in self._device_tables
                              if k[0] == t]:
                        self._evict_device(k)

    _temp_counter = [0]

    def _temp_seq(self) -> int:
        self._temp_counter[0] += 1
        return self._temp_counter[0]

    # -- composed CTE capture (exec/ctecompose.py) -----------------------
    # While a _RerunPrepared drives a slow-path execution, the engine
    # records the sub/main Prepared programs + temp shapes here so the
    # NEXT run can compose them device-resident. None = not capturing.
    _cte_capture = None

    def _begin_cte_capture(self, stmt, session) -> bool:
        if not isinstance(stmt, ast.Select) or session.txn is not None \
                or session.effects:
            return False
        if self.mesh is not None and getattr(self.mesh, "size", 1) > 1:
            return False
        self._cte_capture = {"temps": [], "preps": [],
                             "disabled": False, "want_main": False}
        return True

    def _end_cte_capture(self):
        cap = self._cte_capture
        self._cte_capture = None
        return cap

    def _materialize_temp_select(self, tname: str, sub: ast.Select,
                                 session: Session, rename,
                                 sql_text: str) -> None:
        """Materialize a CTE/derived-table SELECT into a temp table.

        Fast path: run the compiled program and ingest the DEVICE
        output columns directly — they are already in storage-physical
        form (scaled-int decimals, day/micro ints, dictionary codes),
        so nothing round-trips through per-value Python decode/encode
        (q9's 134K-row derived table cost ~18s that way; the columnar
        ingest is ~0.1s). Falls back to the decoded-row path for
        shapes the direct prepare cannot serve (spill recursion,
        top-k tie fallback, nested CTEs/fastpath-only statements)."""
        from .session import TopKInexact
        try:
            if not isinstance(sub, ast.Select) or sub.ctes:
                # set-op bodies and nested CTEs take the row path
                raise EngineError("shape takes the row path")
            # same preprocessing _exec_select performs: view bodies and
            # correlated subqueries must be rewritten BEFORE prepare,
            # or the binder rejects what the row path would serve
            sub = self._decorrelate(self._expand_views(sub))
            if sub.ctes or self._has_derived(sub):
                # decorrelation can introduce derived tables
                raise EngineError("shape takes the row path")
            prep = self._prepare_select(sub, session, sql_text)
            runner = getattr(prep, "jfn", None)
            if runner is None or prep.stream is not None:
                raise EngineError("shape takes the row path")
            from ..ops.batch import pull_arrays
            out = prep.dispatch()

            def _flags(b):
                """(sel, sentinel flags) in ONE packed transfer, not
                one device sync per array."""
                from .session import SENTINEL_COLUMNS
                sent = [s for s in SENTINEL_COLUMNS if b.has(s)]
                pulled = pull_arrays(
                    [b.sel] + [jnp.any(b.col(s)) for s in sent])
                return pulled[0], dict(zip(sent, pulled[1:]))

            sel, flags = _flags(out)
            if flags.get("__compact_overflow"):
                # retry the COLUMNAR fast path uncompacted rather
                # than dropping to the ~100x-slower decoded-row
                # ingest (which would also re-compact and overflow
                # again before its own fallback)
                self._m_compact_overflows.inc()
                prep = self._prepare_select(sub, session, sql_text,
                                            no_compact=True)
                out = prep.dispatch()
                sel, flags = _flags(out)
            for sentinel, exc in (
                    ("__ht_overflow", HashCapacityExceeded),
                    ("__topk_inexact", TopKInexact),
                    ("__compact_overflow", CompactOverflow)):
                if flags.get(sentinel):
                    raise exc(sentinel)
            if flags.get("__sum_overflow"):
                # a user-facing error, not a row-path retry: the row
                # path would raise the same thing
                raise EngineError(
                    "decimal SUM overflowed int64 accumulation; "
                    "CAST the argument to FLOAT to trade exactness "
                    "for range")
            meta = prep.meta
            names = list(meta.names)
            if rename is not None:
                if len(rename) != len(names):
                    raise EngineError(
                        "CTE column list length does not match query")
                names = list(rename)
            if len(set(names)) != len(names):
                raise EngineError(f"duplicate column names in {tname}")
            schema = TableSchema(
                name=tname,
                columns=[ColumnSchema(n, t, True)
                         for n, t in zip(names, meta.types)],
                primary_key=[],
                table_id=self.store.alloc_table_id())
            self.store.create_table(schema)
            # one packed transfer for the live rows of every column
            # (data + valid): per-column pulls were ~17 transfers per
            # q9 execution, and a join-expanded output is mostly
            # padding (134K live of a multi-million-row batch)
            from ..ops.batch import pull_batch_columns
            pulled, _ = pull_batch_columns(out, list(meta.names),
                                           sel_np=sel)
            cols: dict[str, np.ndarray] = {}
            valid: dict[str, np.ndarray] = {}
            for cname, oname, ty in zip(names, meta.names,
                                        meta.types):
                arr, v = pulled[oname]
                if ty.uses_dictionary:
                    d = meta.dictionaries.get(oname)
                    if d is None:
                        raise EngineError(
                            "undictionaried string takes the row path")
                    self.store.set_dictionary(tname, cname,
                                              list(d.values))
                    arr = np.clip(arr.astype(np.int32), 0,
                                  max(len(d) - 1, 0))
                cols[cname] = arr
                valid[cname] = v
            if len(sel) and sel.any():
                self.store.insert_columns(tname, cols, Timestamp(1, 0),
                                          valid=valid)
            cap = self._cte_capture
            if cap is not None and not cap["disabled"]:
                nrows = (next(iter(cols.values())).shape[0]
                         if cols else 0)
                cap["temps"].append({"tname": tname, "prep": prep,
                                     "meta": meta, "names": names,
                                     "rows": nrows})
            return
        except (EngineError, PlanError) as e:
            if tname in self.store.tables:
                self.store.drop_table(tname)
            if not (isinstance(e, (HashCapacityExceeded, TopKInexact,
                                   CompactOverflow, PlanError))
                    or str(e).endswith("row path")):
                raise
            # fall through: spill recursion / top-k tie fallback /
            # row-path-only shapes; PlanError lets the row path replan
            # with its wider strategy set (fastpath, set ops)
        if self._cte_capture is not None:
            self._cte_capture["disabled"] = True  # row-path temp
        res = self._exec_select(sub, session, sql_text)
        self._materialize_temp(tname, res, rename)

    def _materialize_temp(self, tname: str, res: Result,
                          rename: list | None) -> None:
        """Create a columnstore table from a decoded Result."""
        names = list(res.names)
        if rename is not None:
            if len(rename) != len(names):
                raise EngineError(
                    "CTE column list length does not match query")
            names = list(rename)
        if len(set(names)) != len(names):
            raise EngineError(f"duplicate column names in {tname}")
        types = res.types
        if not types:
            raise EngineError("subquery produced no column types")
        schema = TableSchema(
            name=tname,
            columns=[ColumnSchema(n, t, True)
                     for n, t in zip(names, types)],
            primary_key=[],
            table_id=self.store.alloc_table_id())
        self.store.create_table(schema)
        if not res.rows:
            return
        n = len(res.rows)
        cols: dict[str, np.ndarray] = {}
        valid: dict[str, np.ndarray] = {}
        for i, (cname, ty) in enumerate(zip(names, types)):
            vals = [r[i] for r in res.rows]
            v = np.array([x is not None for x in vals], dtype=bool)
            f = ty.family
            if f == Family.STRING:
                arr = np.array([x if x is not None else "" for x in vals],
                               dtype=object)
            elif f in (Family.ARRAY, Family.JSON):
                # decoded rows hold python lists/dicts: re-canonicalize
                from ..sql import datum as dtm
                arr = np.array(
                    [(dtm.canon_array(x, ty.elem) if f == Family.ARRAY
                      else dtm.canon_json(x)) if x is not None else ""
                     for x in vals], dtype=object)
            elif f == Family.DATE:
                arr = np.array(
                    [(x - EPOCH_DATE).days if isinstance(x, datetime.date)
                     else (x or 0) for x in vals], dtype=np.int64)
            elif f == Family.TIMESTAMP:
                arr = np.array(
                    [int((x - EPOCH_DT).total_seconds() * 1e6)
                     if isinstance(x, datetime.datetime) else (x or 0)
                     for x in vals], dtype=np.int64)
            else:
                # DECIMAL floats are rescaled by insert_columns
                arr = np.array([x if x is not None else 0 for x in vals],
                               dtype=ty.np_dtype
                               if f != Family.DECIMAL else np.float64)
            cols[cname] = arr
            valid[cname] = v
        # temps ingest at wall=1 so they are visible at ANY read
        # timestamp — including a txn's pinned one from before the
        # materialization happened
        self.store.insert_columns(tname, cols, Timestamp(1, 0),
                                  valid=valid)

    def _prepare_select(self, sel: ast.Select, session: Session,
                        sql_text: str,
                        no_memo: bool = False,
                        no_topk: bool = False,
                        no_compact: bool = False,
                        no_dist: bool = False) -> "Prepared":
        return self._prepare_select_inner(
            sel, session, sql_text, no_memo=no_memo, no_topk=no_topk,
            no_compact=no_compact, no_dist=no_dist)

    def _upload_prepare_scans(self, node, session, scan_aliases,
                              scan_cols, overlay, decision, stream,
                              spill, narrow_by_alias, read_ts,
                              scans, gens, shapes, upload_spec):
        """Resolve every scan alias to a device batch (the
        _prepare_select upload loop, extracted so the distributed
        verdict can catch MemoryQuotaError and fall to the spill
        tier). Mutates scans/gens/shapes/upload_spec; returns the
        router's (sharded_bytes, repl_bytes) footprint estimate."""
        sharded_bytes = 0
        repl_bytes = 0
        for alias, tname in scan_aliases.items():
            self._register_table_read(session.txn, tname, read_ts)
            cols = scan_cols.get(alias)
            # default WIDE: an alias missing from the walk must never
            # be served an int32 upload its compiled scan won't upcast
            do_narrow = narrow_by_alias.get(alias, False)
            if stream is not None and alias == stream[0]:
                # the streamed fact table never uploads whole; its
                # shape contribution is the (static) page size — but
                # dictionary sizes still fingerprint the compiled plan
                # (group codes are baked into the XLA program)
                gens.append((tname, self.store.table(tname).generation))
                dictlens = tuple(
                    sorted((cn, len(d)) for cn, d in
                           self.store.table(tname).dictionaries.items()))
                shapes.append((tname, stream[2], dictlens))
                continue
            if spill is not None and alias in (spill.alias,
                                               spill.build_alias):
                # spilled probe/build never upload whole either; their
                # execution-time shapes (page size / the shared build
                # partition pad) don't fingerprint the plan — the
                # SpillPlan in the cache key covers the placement, and
                # jit retraces per gathered shape anyway
                gens.append((tname, self.store.table(tname).generation))
                dictlens = tuple(
                    sorted((cn, len(d)) for cn, d in
                           self.store.table(tname).dictionaries.items()))
                shapes.append((tname, 0, dictlens))
                continue
            if tname in overlay:
                b = self._overlay_batch(tname, session.effects, read_ts)
                gens.append((tname, -1))
            elif decision is not None:
                sharded = alias in decision.sharded
                placement = "sharded" if sharded else "replicated"
                b = self._device_table(tname, placement, cols,
                                       narrow=do_narrow)
                gens.append((tname, self.store.table(tname).generation))
                upload_spec.append((alias, tname, placement, cols,
                                    do_narrow))
                nb = sum(int(x.nbytes) for x in jax.tree.leaves(b))
                # the router's footprint check sizes sub-meshes from
                # the ESTIMATED post-filter working set: a selective
                # scan's uploaded bytes mostly die at the filter, so
                # they shouldn't force the full mesh (the check is
                # advisory — hbm.reserve still accounts exact bytes)
                frac = self._scan_survival_frac(node, alias, tname)
                if sharded:
                    sharded_bytes += int(nb * frac)
                else:
                    repl_bytes += int(nb * frac)
            else:
                b = self._maybe_pruned_upload(node, alias, tname,
                                              cols, do_narrow)
                if b is None:
                    b = self._device_table(tname, cols=cols,
                                           narrow=do_narrow)
                gens.append((tname, self.store.table(tname).generation))
            scans[alias] = b
            dictlens = tuple(
                sorted((cn, len(d)) for cn, d in
                       self.store.table(tname).dictionaries.items()))
            shapes.append((tname, b.n, dictlens))
        return sharded_bytes, repl_bytes

    def _prepare_select_inner(self, sel, session: Session,
                              sql_text: str,
                              no_memo: bool = False,
                              no_topk: bool = False,
                              no_compact: bool = False,
                              no_dist: bool = False) -> "Prepared":
        # one span over the whole prepare: planning, the device
        # tables (an `upload` beneath it when one is not resident),
        # the plan-shape cache lookup and, on a miss, `compile`
        with self.tracer.span("plan"):
            return self._prepare_select_planned(
                sel, session, sql_text, no_memo, no_topk, no_compact,
                no_dist)

    def _seal_open_tables(self) -> None:
        """Seal every table with rows still buffered, so a plan reads
        what its tables hold."""
        for td in self.store.tables.values():
            if td.open_ts:
                self.store.seal(td.schema.name)

    def _prepare_select_planned(self, sel, session: Session,
                                sql_text: str, no_memo: bool,
                                no_topk: bool, no_compact: bool,
                                no_dist: bool) -> "Prepared":
        # the `plan` span's own time by stage (marks, not child spans:
        # utils/tracing.stage): `build` the planner, `placement` the
        # verdict, `tables` the device tables and the guards that read
        # them, `key` session variables, compaction and literal
        # lifting, `fingerprint` the plan's structural hash and the
        # key, `lookup` the cache and what is made of its answer
        _trc.stage("build")
        self._seal_open_tables()
        # the statement's uncorrelated scalar subqueries, each a
        # prepared statement of its own (_prepare_subquery)
        subs: list = []
        node, meta = self._plan(sel, session, no_memo=no_memo,
                                subquery_slots=subs)

        _trc.stage("placement")
        scan_aliases = _collect_scans(node)
        scan_cols = _collect_scan_columns(node)
        # read-your-own-writes: tables this txn has written get an
        # overlay snapshot (committed + buffered effects), not the
        # shared device cache; overlay scans stay single-device
        overlay = set()
        if session.txn is not None and session.effects:
            touched = {tb for tb, _ in session.effects}
            overlay = touched & set(scan_aliases.values())
        decision = (None if (overlay or no_dist)
                    else self._dist_decision(node, session))
        # four-way placement verdict: distributed > spill > stream-scan
        # > resident. Spill outranks stream-scan because it covers the
        # shapes streaming can't rescue: over-budget join builds (the
        # stream path uploads builds whole and dies at hbm.reserve) and
        # Sort/Limit plans with no aggregate to page into partials.
        spill = (None if (overlay or decision is not None)
                 else self._spill_decision(node, scan_aliases, scan_cols,
                                           session, meta))
        stream = (None if (overlay or decision is not None
                           or spill is not None)
                  else self._stream_decision(node, scan_aliases, scan_cols,
                                             session))
        planned = node  # as the placement verdicts saw it
        verdict = ("distributed" if decision is not None
                   else "spill" if spill is not None
                   else "stream" if stream is not None else "resident")
        self.tracer.tag(placement=verdict)
        self._m_placement[verdict].inc()
        _trc.stage("tables")
        read_ts = self._read_ts(session)
        # the join-build uniqueness guard is snapshot-aware: it must
        # judge the rows visible at THIS query's read timestamp — and
        # know about txn-buffered build rows the store can't see
        as_of = self._as_of_ts(sel, session)
        if as_of is not None:
            read_ts = as_of
        overlay_puts = {
            t: sum(1 for tb, op in session.effects
                   if tb == t and op[0] == "put")
            for t in overlay}
        try:
            self._check_join_builds(node, read_ts, overlay_puts)
            self._bound_agg_group_rows(node, read_ts, overlay_puts)
            wide = set()
            if stream is not None:
                wide.add(stream[0])
            if spill is not None:
                wide.add(spill.alias)
                if spill.build_alias:
                    wide.add(spill.build_alias)
            narrow_by_alias = self._set_scan_narrowing(
                node, overlay, frozenset(wide))
        except EngineError:
            if meta.memo is not None and not no_memo:
                # the memo's stats-estimated build order violated the
                # engine's EXACT multiplicity cap (avg vs max skew):
                # replan with the greedy orderer, which consults the
                # store's exact probes (the reference's optimizer
                # likewise falls back when exploration yields no
                # executable plan)
                return self._prepare_select(sel, session, sql_text,
                                            no_memo=True,
                                            no_dist=no_dist)
            raise

        scans = {}
        gens = []
        shapes = []
        # distributed plans record how each scan resolves against an
        # arbitrary target mesh (sub-mesh dispatch re-uploads lazily)
        # plus the working-set footprint the router sizes against
        upload_spec = []
        sharded_bytes = 0
        repl_bytes = 0
        try:
            sharded_bytes, repl_bytes = self._upload_prepare_scans(
                node, session, scan_aliases, scan_cols, overlay,
                decision, stream, spill, narrow_by_alias, read_ts,
                scans, gens, shapes, upload_spec)
        except MemoryQuotaError:
            if decision is None:
                raise
            # distributed spill: a shard working set that outgrows its
            # HBM slice re-prepares WITHOUT the distributed verdict —
            # the spill/stream tiers then page the same (mergeable by
            # construction) partials through the partition machinery
            # instead of dying on the upload reservation
            self.movement.m_spill_fallbacks.inc()
            return self._prepare_select(
                sel, session, sql_text, no_memo=no_memo,
                no_topk=no_topk, no_compact=no_compact, no_dist=True)
        SCAN_WIDE_ARGS.inc(sum(d.dtype.itemsize == 8
                               for b in scans.values() for d in b.data))

        _trc.stage("key")
        cap = int(session.vars.get("hash_group_capacity", 1 << 17))
        pallas = session.vars.get("pallas_groupagg", "auto")
        pallas = self._pallas_mode(pallas)
        # same normalization discipline for the sort-key plane
        sortn = session.vars.get("sort_normalized", "auto")
        if isinstance(sortn, bool):
            sortn = "on" if sortn else "off"
        sortn = str(sortn).lower()
        if sortn not in ("auto", "on", "off"):
            sortn = "off"
        # keyed by shape (padded row-count bucket) + dictionary sizes,
        # NOT data generation: the compiled XLA program depends only on
        # shapes and on literal dictionary codes (append-only, so any
        # growth shows up in dictlens) — the plan-cache fingerprint idea
        # of the reference (sql/plan_opt.go), adapted to XLA's
        # shape-specialized compilation model
        if not no_compact and stream is None and decision is None \
                and spill is None and not overlay:
            # selection compaction: low-selectivity scans feeding
            # aggregation pack their survivors before join probes /
            # agg partials run (see compile.compact_batch). Gated off
            # under streaming (the sentinel cannot ride page state)
            # and distributed plans (a per-shard pack + psum merges
            # would need sentinel plumbing through collectives)
            node = self._insert_compaction(
                node, {a: b.n for a, b in scans.items()})
        # statement-shape plan cache: lift filter literals out of the
        # plan into runtime arguments so literal-varying statements of
        # one shape share a compiled program (the reference strips
        # placeholders before fingerprinting, sql/plan_opt.go; the OLTP
        # lane's literal-stripped point lookups generalized to the
        # analytic path). Gated off under streaming/spill (their page
        # programs re-derive plans elsewhere), overlay, CTE capture
        # (composition re-binds constants), and plan_shape_cache=off.
        pvals: tuple = ()
        psc = str(session.vars.get("plan_shape_cache", "auto")).lower()
        if psc != "off" and stream is None and spill is None \
                and not overlay and self._cte_capture is None:
            pnode, vals = parameterize(
                node, tables=decision is None
                and self._reads_large_dictionary(scan_aliases, scan_cols))
            if vals is not None:
                node, pvals = pnode, vals
        inlined = meta.subqueries
        if subs:
            # a subquery's result that no filter holds, or of a plan
            # that is not parameterized: read now, at this prepare's
            # timestamp, and written into the plan (the form every
            # subquery had before: another program for other rows)
            def const_of(arg):
                return subquery_const(subs[arg.slot].run(read_ts))

            node, n = inline_subquery_args(node, const_of)
            inlined += n
        _trc.stage("fingerprint")
        if pvals:
            # literals left the plan, so they must leave the key text
            # too; the structural fingerprint below is what rejects a
            # literal that changed the plan's SHAPE (e.g. LIMIT, or a
            # constant that re-ordered the memo's join plan)
            keytext = shape_text(sql_text)
            plan_fp = plan_fingerprint(node)
        else:
            # plan fingerprint: what a subquery returned while the
            # statement was bound (an IN list, an EXISTS, a scalar no
            # filter holds) is a constant of the plan, so two
            # preparations of the SAME sql_text can compile DIFFERENT
            # programs when underlying data moved — sql_text alone
            # would hand back a stale compiled constant. (A scalar
            # subquery in a filter is an argument, above, and leaves
            # the key as the literals do.)
            keytext = sql_text
            plan_fp = hash(repr(node))
        psig = param_signature(pvals)
        key = (keytext, tuple(sorted(shapes)), decision is not None,
               stream, spill, cap, pallas, sortn, plan_fp, no_topk,
               no_compact, psig)
        # a prefix Sort whose estimate proved low (Prepared.run noted
        # its key): straight to the whole sort, not through the
        # sentinel and a second program on every execution
        prefix_key = None
        if not no_topk and decision is None \
                and _has_prefix_sort(node):
            if key in self._whole_sorts:
                no_topk = True
                key = (keytext, tuple(sorted(shapes)),
                       decision is not None, stream, spill, cap, pallas,
                       sortn, plan_fp, no_topk, no_compact, psig)
            else:
                prefix_key = key
        _trc.stage("lookup")
        cached = self._exec_cache.get(key)
        self.tracer.tag(plan_cache="hit" if cached else "miss")
        if _trc.current_span() is not None:
            self.tracer.tag(**self._plan_shape_tags(node, scans, pallas))
        self._m_plan_cache[cached is not None].inc()
        if cached is None:
            if verdict == "resident" and not overlay:
                self.note_placement_model(planned, scan_aliases,
                                          scan_cols, session)
            # feed the startup pre-warm: texts that missed here are
            # what a restarted process should compile first, at the
            # shape bucket their paged executables specialize on
            # plan-key-changing vars (non-default only): prewarm must
            # re-prepare under these or it compiles a different
            # executable than the one this statement is about to miss
            jvars = {}
            if cap != 1 << 17:
                jvars["hash_group_capacity"] = cap
            if pallas != "auto":
                jvars["pallas_groupagg"] = pallas
            if sortn != "auto":
                jvars["sort_normalized"] = sortn
            coldstart.journal_record(
                self._compile_cache_dir, sql_text,
                bucket=(stream[2] if stream is not None
                        else spill.page_rows if spill is not None
                        else 0),
                vars=jvars)
            with self.tracer.span("compile"):
                params = ExecParams(
                    hash_group_capacity=cap,
                    axis_name=(SHARD_AXIS if decision is not None
                               else None),
                    n_shards=(self.mesh.devices.size
                              if decision is not None else 1),
                    pallas_groupagg=pallas,
                    pallas_interpret=self._pallas_interpret(),
                    topk_sort=not no_topk,
                    sort_normalized=sortn,
                    join_stats=JoinStats())
                # beside the executable in the cache: a hit finds what
                # the miss's trace noted
                meta.join_stats = params.join_stats
                if spill is not None and spill.kind == "join":
                    # the spill-join probes with the UNCHANGED
                    # streaming page program: each probe row lands in
                    # exactly one (partition, page) and matches only
                    # inside its partition, so the per-page partial
                    # combine algebra is exact over the partition
                    # sweep (and the partials stay mergeable across
                    # DistSQL for the same reason)
                    splan = compile_streaming(node, params, meta)

                    def spage_fn(scans_in, ts_in, _f=splan.page_fn):
                        return _f(RunContext(scans_in, ts_in))
                    jfn = _StreamFns(jax.jit(spage_fn),
                                     jax.jit(splan.combine),
                                     jax.jit(splan.final_fn))
                elif spill is not None:
                    from .spill import compile_spill_sort
                    runf = compile_spill_sort(node, params, meta)

                    def sort_fn(scans_in, ts_in, _f=runf):
                        return _f(RunContext(scans_in, ts_in))
                    jfn = jax.jit(sort_fn)
                elif stream is not None:
                    splan = compile_streaming(node, params, meta)

                    def page_fn(scans_in, ts_in, _f=splan.page_fn):
                        return _f(RunContext(scans_in, ts_in))
                    jfn = _StreamFns(jax.jit(page_fn),
                                     jax.jit(splan.combine),
                                     jax.jit(splan.final_fn))
                elif decision is not None:
                    # the router matches the queued-call convention but
                    # picks full mesh vs pool sub-mesh per dispatch;
                    # each target mesh lazily traces its own executable
                    jfn = _DistRouter(self, node, meta, scan_aliases,
                                      decision, params, upload_spec,
                                      sharded_bytes, repl_bytes)
                else:
                    runf = compile_plan(node, params, meta)

                    def fn(scans_in, ts_in, nparts, pid, lits=()):
                        return runf(
                            RunContext(scans_in, ts_in, nparts, pid,
                                       params=lits))
                    jfn = jax.jit(fn)
            self._exec_cache_put(key, (jfn, meta))
        else:
            jfn, meta = cached
        gens = tuple(sorted(gens))
        # zone-map checks for the streamed scan's pushed-down
        # predicates: compiled from THIS prepare's plan (constants are
        # inlined), so they track the statement's current bindings
        if stream is not None:
            stream_zone = extract_zone_preds(node, stream[0])
        elif spill is not None and spill.kind == "sort":
            stream_zone = extract_zone_preds(node, spill.alias)
        else:
            # spill-join probes with no zone pruning: every probe row
            # belongs to exactly one partition regardless of predicate
            # outcome, and the partitioner indexes rows globally
            stream_zone = ()
        paged = spill.alias if spill is not None else (
            stream[0] if stream is not None else None)
        # join-induced skipping (exec/joinfilter.py): specs detected
        # over THIS prepare's plan; key summaries derive per dispatch
        from .joinfilter import find_specs
        if stream is not None:
            jf_specs = find_specs(node, stream[0], self.store)
        elif spill is not None and spill.kind == "join":
            jf_specs = find_specs(node, spill.alias, self.store)
        else:
            jf_specs = ()
        prepared = Prepared(self, session, sel, sql_text, jfn, scans,
                            meta, gens, stream=stream,
                            stream_cols=(scan_cols.get(paged)
                                         if paged is not None else None),
                            stream_zone=stream_zone,
                            as_of=as_of, spill=spill,
                            spill_cols=(scan_cols.get(spill.build_alias)
                                        if spill is not None
                                        and spill.build_alias else None),
                            joinfilter=jf_specs,
                            params=pvals, prefix_key=prefix_key,
                            subqueries=tuple(
                                (i, subs[v.slot]) for i, v in
                                enumerate(pvals)
                                if isinstance(v, SubqueryValue)),
                            inlined_subqueries=inlined)
        # alias -> table map (composed CTE execution patches temp
        # aliases' scan batches per run, exec/ctecompose.py)
        prepared.scan_tables = dict(scan_aliases)
        cap = self._cte_capture
        if cap is not None and cap.get("want_main") \
                and not cap["disabled"] and prepared.spill is None:
            cap["preps"].append(prepared)
        return prepared

    def prepare(self, sql: str, session: Session | None = None) -> "Prepared":
        """Prepare a SELECT for repeated execution (the pgwire
        prepared-statement/portal path, pkg/sql/pgwire/conn.go Describe/
        Bind/Execute). ``Prepared.dispatch()`` launches the compiled
        program without blocking on the result, so a stream of
        executions pipelines on-device instead of paying a full
        host<->device round trip per query."""
        session = session or self.session()
        stmt = parser.parse(sql)
        if isinstance(stmt, ast.Select):
            stmt = self._expand_views(stmt)
        if isinstance(stmt, ast.SetOp) or (
                isinstance(stmt, ast.Select)
                and (stmt.ctes or self._has_derived(stmt))):
            # CTE/set-op/derived statements materialize temps per
            # execution: prepare degrades to a re-execute handle (the
            # reference's portals likewise re-plan non-cacheable
            # statements)
            return _RerunPrepared(self, session, stmt, sql)
        if not isinstance(stmt, ast.Select) or stmt.table is None:
            raise EngineError("can only prepare table-reading SELECTs")
        return self._prepare_select(stmt, session, sql_text=sql)

    def _exec_select(self, sel, session: Session,
                     sql_text: str, in_place: bool = True) -> Result:
        probe = getattr(self._text_tl, "probe", None)
        keep = None
        if probe is not None and sel is probe.stmt:
            # the statement execute() handed down: served from the
            # statement-text cache, or kept there once prepared. Taken
            # once: the statements it runs beneath are not its own
            self._text_tl.probe = None
            if probe.entry is not None:
                prep = self._text_plan_serve(probe, session)
                if prep is not None:
                    return prep.run()
                # the entry's AST is shared: plan from a copy of its own
                sel = self._parse_cached(sql_text)
            self._m_text["miss"].inc()
            keep = (probe.key, volatile_folds())
        if isinstance(sel, ast.SetOp):
            return self._exec_setop(sel, session, sql_text)
        inline = in_place and self._plans_in_place(sel, session)
        orig = sel
        rewritten = None
        if inline and sel.ctes:
            # each CTE read once is a derived table planned in place
            rewritten = self._inplace_memo.get(sql_text)
            inlined = (rewritten if rewritten is not None
                       or sql_text in self._temps_memo
                       else inline_ctes(sel))
            if inlined is None:
                inline = False
            else:
                sel = inlined
        if rewritten is None and inline and self._has_derived(sel):
            sel = push_joins_into_unions(sel, self._stored_columns)
        if rewritten is None and sql_text not in self._plain_memo:
            sel2 = self._decorrelate(self._expand_views(sel),
                                     inline=inline)
            if sel2 is sel and sql_text and \
                    sql_text.lower().count("select") == 1:
                # memoize BY TEXT so hot OLTP statements skip both
                # walks on re-execution without annotating the shared
                # cached AST (round-4 advisor). Only SUBQUERY-FREE
                # texts qualify: decorrelation rewrites nested
                # subqueries IN PLACE while returning the same object,
                # so `is sel` alone cannot prove it was a no-op — a
                # memo hit on a fresh parse copy would then skip a
                # rewrite the planner needs (the q2 regression this
                # guard fixes). DDL invalidates with the parse cache.
                self._plain_memo.add(sql_text)
            sel = sel2
        if inline and orig.ctes and sql_text:
            self._inplace_memo[sql_text] = sel
        if inline and not sel.ctes and self._has_derived(sel):
            # derived tables planned in place (plan.Derived): one
            # program, nothing materialized on the host, nothing in it
            # measured from a temp's rows. A shape the planner cannot
            # place (a derived build side joined on other columns than
            # its GROUP BY key, a body of CTEs or set operations) says
            # so once, by NotInPlace; its text is remembered, and
            # takes the temps below from then on, as before
            if sql_text not in self._temps_memo:
                try:
                    prep = self._prepare_select(sel, session, sql_text)
                except PlanError as e:
                    # a CTE's body the planner cannot place (a table-
                    # free SELECT, a set-returning function) is what
                    # the temps served before CTEs were planned here
                    if not (isinstance(e, NotInPlace) or orig.ctes):
                        raise
                    if sql_text:
                        self._temps_memo.add(sql_text)
                else:
                    self._text_plan_keep(keep, session, sql_text, prep)
                    return prep.run()
            if orig.ctes:
                # the statement as written, its CTEs through the temps
                return self._exec_select(orig, session, sql_text,
                                         in_place=False)
        if sel.ctes or self._has_derived(sel):
            return self._exec_with_temps(sel, session, sql_text)
        if sel.table is None:
            return self._exec_table_free(sel, session)
        match = self._index_fastpath_match(sel, session)
        if match is not None:
            res = self._exec_index_fastpath(sel, session, match)
            if res is not None:
                self.metrics.counter(
                    "sql.select.index_fastpath",
                    "SELECTs served by the index point-read path").inc()
                return res
        rmatch = self._range_fastpath_match(sel, session)
        if rmatch is not None:
            res = self._exec_range_fastpath(sel, session, rmatch)
            if res is not None:
                self.metrics.counter(
                    "sql.select.range_fastpath",
                    "SELECTs served by the ordered index-range "
                    "path").inc()
                return res
        prep = self._prepare_select(sel, session, sql_text)
        self._text_plan_keep(keep, session, sql_text, prep)
        return prep.run()

    def _exec_setop(self, so: ast.SetOp, session: Session,
                    sql_text: str) -> Result:
        """UNION / INTERSECT / EXCEPT [ALL]: both branches execute as
        ordinary statements (each fully device-compiled); the combine
        is a host multiset merge over decoded rows — matching the
        reference's setOpNode, which likewise merges above the
        vectorized inputs (sql/union.go)."""
        import copy
        if so.ctes:
            # WITH over a set op: materialize temps then recurse with
            # names rewritten in both branches
            temps: list[str] = []
            mapping: dict[str, str] = {}
            so = copy.copy(so)
            try:
                for name, cols, sub in so.ctes:
                    sub = _rewrite_table_names(sub, mapping)
                    res = self._exec_select(sub, session,
                                            f"(cte {sub!r})")
                    tname = f"__cte{self._temp_seq()}_{name}"
                    self._materialize_temp(tname, res, cols)
                    self._m_cte_temps.inc()
                    mapping[name] = tname
                    temps.append(tname)
                so.ctes = []
                so = _rewrite_table_names(so, mapping)
                return self._exec_setop(so, session, sql_text)
            finally:
                for t in temps:
                    if t in self.store.tables:
                        self.store.drop_table(t)
                        for k in [k for k in self._device_tables
                                  if k[0] == t]:
                            self._evict_device(k)
        left = self._exec_select(so.left, session,
                                 f"(setop-l {so.left!r})")
        right = self._exec_select(so.right, session,
                                  f"(setop-r {so.right!r})")
        if len(left.names) != len(right.names):
            raise EngineError(
                f"each {so.op.upper()} branch must have the same "
                f"number of columns ({len(left.names)} vs "
                f"{len(right.names)})")
        numeric = (Family.INT, Family.FLOAT, Family.DECIMAL)
        out_types = list(left.types)
        coerce_cols = {}  # column index -> unified SQLType
        for i, (lt, rt) in enumerate(zip(left.types, right.types)):
            if lt.family == rt.family or \
                    "unknown" in (lt.family.value, rt.family.value):
                continue
            if lt.family in numeric and rt.family in numeric:
                # unify like expression arithmetic would
                # (common_numeric_type): the merged rows and the
                # declared column type must agree, or a temp-table
                # materialization / pgwire OID would mis-encode
                from ..sql.types import common_numeric_type
                ut = common_numeric_type(lt, rt)
                out_types[i] = ut
                coerce_cols[i] = ut
                continue
            raise EngineError(
                f"{so.op.upper()} branch column types do not "
                f"match: {lt} vs {rt}")
        lrows, rrows = list(left.rows), list(right.rows)
        if coerce_cols:
            import decimal as _dec

            def _unify(rows):
                out = []
                for r in rows:
                    r = list(r)
                    for i, ut in coerce_cols.items():
                        v = r[i]
                        if v is None:
                            continue
                        if ut.family == Family.FLOAT:
                            r[i] = float(v)
                        elif ut.family == Family.DECIMAL:
                            r[i] = _dec.Decimal(str(v))
                    out.append(tuple(r))
                return out
            lrows, rrows = _unify(lrows), _unify(rrows)
        left.types = out_types
        if so.op == "union":
            rows = lrows + rrows
            if not so.all:
                rows = list(dict.fromkeys(rows))
        elif so.op == "intersect":
            from collections import Counter
            rc = Counter(rrows)
            if so.all:
                rows = []
                for r in lrows:
                    if rc[r] > 0:
                        rc[r] -= 1
                        rows.append(r)
            else:
                rset = set(rrows)
                rows = list(dict.fromkeys(
                    r for r in lrows if r in rset))
        else:  # except
            from collections import Counter
            rc = Counter(rrows)
            if so.all:
                rows = []
                for r in lrows:
                    if rc[r] > 0:
                        rc[r] -= 1
                    else:
                        rows.append(r)
            else:
                rset = set(rrows)
                rows = list(dict.fromkeys(
                    r for r in lrows if r not in rset))
        if so.order_by:
            rows = self._sort_decoded(rows, left.names, so.order_by)
        if so.offset:
            rows = rows[so.offset:]
        if so.limit is not None:
            rows = rows[:so.limit]
        return Result(names=list(left.names), rows=rows,
                      types=list(left.types))

    @staticmethod
    def _sort_decoded(rows: list, names: list, order_by) -> list:
        """Host sort of decoded rows by output columns/positions; pg
        NULL ordering (last for asc, first for desc)."""
        out = list(rows)
        for ob in reversed(order_by):
            if isinstance(ob.expr, ast.Literal) \
                    and isinstance(ob.expr.value, int):
                i = ob.expr.value - 1
            elif isinstance(ob.expr, ast.ColumnRef) \
                    and ob.expr.name in names:
                i = names.index(ob.expr.name)
            else:
                raise EngineError(
                    "set-op ORDER BY must reference output columns")

            null_first = (ob.nulls_first if ob.nulls_first is not None
                          else ob.desc)

            def key(r, i=i, nf=null_first, desc=ob.desc):
                v = r[i]
                # pre-reverse null flag so the PRESENTED order puts
                # NULLs where nulls_first says (see _host_sort)
                flag = (v is None) if desc == nf else (v is not None)
                return (flag, 0 if v is None else v)
            out.sort(key=key, reverse=ob.desc)
        return out

    def _bound_agg_group_rows(self, node, read_ts: Timestamp,
                              overlay: dict) -> None:
        """Attach a static rows-per-group upper bound to Aggregate
        nodes whose group keys trace to stored columns of a probe-
        spine scan through expand==1 joins (filters/compaction only
        shrink groups; one-row-per-probe joins never grow them). The
        bound sizes the i32 limb width of exact int64 group sums
        (ops/agg.py _group_sum_i64_limbs): with a tight bound a
        200K-group decimal SUM is 3 fast i32 scatters instead of one
        software-emulated 64-bit scatter (~5x, the q3/q18 wall named
        in BENCHMARKS.md). 0 = unknown (the kernel falls back to a
        width safe for the whole batch)."""
        from ..sql.bound import BCol

        def spine(n, names):
            while True:
                if isinstance(n, (P.Filter, P.Compact)):
                    n = n.child
                    continue
                if isinstance(n, P.Project):
                    nxt = []
                    items = dict(n.items)
                    for nm in names:
                        e = items.get(nm)
                        if not isinstance(e, BCol):
                            return None
                        nxt.append(e.name)
                    names = nxt
                    n = n.child
                    continue
                if isinstance(n, P.HashJoin):
                    if n.join_type not in ("inner", "left") \
                            or n.expand != 1:
                        return None
                    n = n.left
                    continue
                if isinstance(n, P.Scan):
                    stored = []
                    for nm in names:
                        s = n.columns.get(nm)
                        if s is None:
                            return None
                        stored.append(s)
                    return n.table, tuple(stored)
                return None

        def walk(n):
            if isinstance(n, P.Aggregate):
                if n.group_by and n.aggs:
                    names = []
                    ok = True
                    for _, e in n.group_by:
                        if not isinstance(e, BCol):
                            ok = False
                            break
                        names.append(e.name)
                    hit = spine(n.child, names) if ok else None
                    if hit is not None:
                        table, stored = hit
                        k = self.store.key_max_multiplicity(
                            table, stored, read_ts.to_int(),
                            include_null_group=True)
                        # txn-buffered rows are invisible to the
                        # store's measurement; each can add one row
                        # to some group
                        k += overlay.get(table, 0)
                        if k > 0:
                            n.max_group_rows = k
                walk(n.child)
                return
            for attr in ("child", "left", "right"):
                c = getattr(n, attr, None)
                if c is not None:
                    walk(c)

        walk(node)

    def _set_scan_narrowing(self, node, overlay,
                            wide_aliases: frozenset) -> dict:
        """Mark each Scan's int64 columns whose proven value range
        fits int32 (scanplane.narrow32_cols): the upload moves half
        the HBM bytes and the compiled scan upcasts, so downstream
        programs are unchanged. Skipped for txn-overlay scans (their
        fresh uploads don't consult the generation-cached ranges), the
        streamed/spilled scans (pages and gathered partitions upload
        wide — ``wide_aliases``), and any scan feeding
        a JOIN: in probe pipelines XLA materializes the upcast as a
        full-width int64 copy instead of fusing it into the gathers —
        measured 147M -> 111M rows/s on Q14 at 2^23, the round-4
        silent regression. Scan->aggregate shapes (Q6/Q1) keep the
        ~2x upload win; probe spines read wide."""

        joins = []

        def find_joins(n):
            if isinstance(n, P.HashJoin):
                joins.append(n)
            for attr in ("child", "left", "right"):
                c = getattr(n, attr, None)
                if c is not None:
                    find_joins(c)

        find_joins(node)
        under_join: set[int] = set()

        def mark(n):
            if isinstance(n, P.Scan):
                under_join.add(id(n))
                return
            for attr in ("child", "left", "right"):
                c = getattr(n, attr, None)
                if c is not None:
                    mark(c)

        for j in joins:
            mark(j.left)
            mark(j.right)

        narrow_by_alias: dict[str, bool] = {}

        def walk(n):
            if isinstance(n, P.Scan):
                if n.table not in overlay \
                        and n.alias not in wide_aliases \
                        and id(n) not in under_join:
                    n.narrowed = self.narrow32_cols(
                        n.table, frozenset(n.columns.values()))
                narrow_by_alias[n.alias] = bool(n.narrowed)
                return
            for attr in ("child", "left", "right"):
                c = getattr(n, attr, None)
                if c is not None:
                    walk(c)

        walk(node)
        # alias -> whether the upload may narrow: consumed by the
        # prepare loop so the device upload dtype matches the scan
        return narrow_by_alias

    def _prove_agg_arg_ranges(self, node, session: Session) -> None:
        """Attach a value-range proof to every exact SUM / AVG (and
        MIN / MAX, whose scatter then runs in 32 bits) whose
        INT / DECIMAL argument is arithmetic over stored columns and
        constants (BoundAgg.arg_nonneg / arg_bits): interval
        arithmetic (sql/valuerange.py) from the store's all-versions
        column ranges, which already feed narrow32_cols and the direct
        join tables. A proven non-negative argument travels to the
        large-G kernel as the words and limbs its bits need (a 13-bit
        quantity: one word, two 8-bit limbs, not two words and eight),
        rides bits/w i32 scatters on the XLA path
        (ops/agg.py _group_sum_i64_limbs), and where rows x 2^bits
        stays under 2^62 its sum provably cannot wrap, so no overflow
        sentinel is compiled. A table this txn has written proves
        nothing (its buffered rows are not in the store's ranges), nor
        does any other expression: those aggregates compile as they
        always did. A write that widens a range past the proven bits
        moves the table's generation, and the statement is planned
        and proven again."""
        from ..sql.valuerange import expr_int_range, nonneg_bits

        written = ({tb for tb, _ in session.effects}
                   if session.txn is not None else set())

        def prove(agg):
            stored = {}     # batch column -> (table, stored column)
            ranges = {}     # batch column -> its [lo, hi], asked once

            def scans(n):
                if isinstance(n, P.Scan):
                    for bname, sname in n.columns.items():
                        stored[bname] = (n.table, sname)
                    return
                for attr in ("child", "left", "right"):
                    c = getattr(n, attr, None)
                    if c is not None:
                        scans(c)

            def col_range(name):
                if name not in ranges:
                    ranges[name] = stored_range(stored.get(name))
                return ranges[name]

            def stored_range(hit):
                if hit is None or hit[0] in written:
                    return None
                try:
                    rng = self.store.key_int_range(*hit)
                except (KeyError, TypeError):   # no integer zone map
                    return None
                return None if rng is None else (rng[0], rng[1])

            scans(agg.child)
            for a in agg.aggs:
                if a.func in ("sum", "sum_int", "avg", "min", "max") \
                        and a.arg is not None \
                        and a.arg.type.family in (Family.INT,
                                                  Family.DECIMAL):
                    a.arg_bits = nonneg_bits(
                        expr_int_range(a.arg, col_range))
                    a.arg_nonneg = a.arg_bits > 0

        def walk(n):
            if isinstance(n, P.Aggregate):
                prove(n)
            for attr in ("child", "left", "right"):
                c = getattr(n, attr, None)
                if c is not None:
                    walk(c)

        walk(node)

    def _size_hash_sorts(self, node) -> None:
        """Give a Sort or a Window right above a plain GROUP BY past
        the dense bound the prefix it orders (P.Sort.prefix,
        P.Window.prefix, compile.HASH_SORT_PREFIX) where the Aggregate's
        estimated group count is at most half of it; otherwise, and
        where nothing is known of a key, it orders all the slots (the
        hash table's hash_group_capacity, the sorted layout's
        set_slots) as a Sort always did. Either strategy hands its live
        groups on first. An estimate that proves low raises the top-k
        sentinel once (_whole_sorts)."""
        from .compile import HASH_SORT_PREFIX

        def walk(n):
            if isinstance(n, (P.Sort, P.Window)) \
                    and isinstance(n.child, P.Aggregate) \
                    and n.child.grouping_sets is None \
                    and n.child.group_by and n.child.max_groups <= 0:
                est = self._estimate_groups(n.child)
                if est is not None and 2 * est <= HASH_SORT_PREFIX:
                    n.prefix = HASH_SORT_PREFIX
                elif est is None and self._groups_by_aggregates(n.child):
                    n.prefix = HASH_SORT_PREFIX
            for attr in ("child", "left", "right"):
                c = getattr(n, attr, None)
                if c is not None:
                    walk(c)

        walk(node)

    @staticmethod
    def _groups_by_aggregates(agg) -> bool:
        """Is every key of this Aggregate an aggregate's result of a
        derived table planned beneath it (TPC-H Q13 groups customers
        by their count of orders: an aggregate of an aggregate)? Such
        a key has no stored column to estimate from, and takes few
        values as a rule: a histogram's bars are far fewer than what
        it counts. The Sort above then orders the prefix; if more
        groups are live the sentinel falls back to the whole sort
        once, as for an estimate that proved low."""
        from ..sql.bound import BCol
        n = agg.child
        while isinstance(n, (P.Filter, P.Compact, P.Project)):
            n = n.child
        if not isinstance(n, P.Derived) \
                or not isinstance(n.child, P.Aggregate):
            return False
        grouped = {name for name, e in n.child.items
                   if isinstance(e, BCol)
                   and e.name in dict(n.child.group_by)}
        return all(isinstance(e, BCol) and e.name in n.columns
                   and n.columns[e.name] not in grouped
                   for _, e in agg.group_by)

    def _join_share(self, jn) -> tuple:
        """(share of probe rows the inner join `jn` keeps, restricted)
        from its build table's host columns (exec/dimstats.py): of the
        build keys the probe key's stored range reaches, those the
        build's filter keeps; restricted where that range leaves out a
        tenth of the build's keys or more. (None, False) where the
        build is no small filtered table with one key."""
        if jn.join_type != "inner" or not isinstance(jn.right, P.Scan) \
                or len(jn.right_keys) != 1:
            return None, False
        probe = _scan_of(jn.left, jn.left_keys[0])
        window = None
        if probe is not None:
            try:
                r = self.store.key_int_range(
                    probe.table, probe.columns[jn.left_keys[0]])
            except (KeyError, TypeError):
                r = None
            if r is not None:
                window = (r[0], r[1])
        return self.dimstats.join_share(jn.right, jn.right_keys[0], window)

    def _estimate_rows(self, n) -> float | None:
        """Estimated live rows of the batch a spine of scans, filters
        and joins hands on: a scan's rows times its filter's share, an
        inner join's probe rows times _join_share (or its build's
        filter share). None above anything else."""
        if isinstance(n, P.Scan):
            sel = self._estimate_scan_selectivity(n)
            return self.store.table(n.table).row_count * (
                sel if sel is not None else 1.0)
        if isinstance(n, (P.Filter, P.Compact, P.Project)):
            return self._estimate_rows(n.child)
        if isinstance(n, P.HashJoin) and n.join_type in ("inner",
                                                         "left"):
            rows = self._estimate_rows(n.left)
            if rows is None or n.join_type == "left" \
                    or not isinstance(n.right, P.Scan):
                return rows
            share, _ = self._join_share(n)
            if share is None:
                share = self._estimate_scan_selectivity(n.right)
            return rows * (share if share is not None else 1.0)
        return None

    def _size_grouping_sets(self, node) -> None:
        """Give an Aggregate of the sorted layout (grouping sets, or a
        plain GROUP BY as its one set) the slots its sets' groups are
        packed into (P.Aggregate.set_slots): a power of two past 5/4
        of the groups estimated over all its sets, at least 2^13. A
        set's groups are estimated from D, the
        product over the tables its keys come from of the distinct
        tuples of those keys among the rows the table's filter keeps
        (exec/dimstats.py), and n, the rows into the Aggregate: the
        D (1 - e^(-n/D)) distinct keys of n draws spread evenly over D
        (TPC-DS Q67 at SF1: 466 K of 1.3 M for 576 K rows), at most
        the groups of a finer set. Nothing is set where a key has
        no small table beneath (every set's whole slots then); an
        estimate that proves low raises the top-k sentinel once and
        the plan keeps the whole slots from then on (_whole_sorts)."""

        def walk(n):
            if isinstance(n, P.Aggregate) and n.sort_dims:
                est = self._estimate_set_groups(n)
                if est is not None:
                    n.set_slots = max(1 << 13, 1 << max(
                        0, math.ceil(math.log2(est * 1.25))))
            for attr in ("child", "left", "right"):
                c = getattr(n, attr, None)
                if c is not None:
                    walk(c)

        walk(node)

    def _estimate_set_groups(self, agg) -> float | None:
        from ..sql.bound import BCol
        scans = []
        for _, e in agg.group_by:
            sc = _scan_of(agg.child, e.name) if isinstance(e, BCol) \
                else None
            if sc is None:
                return None
            scans.append(sc)
        rows = self._estimate_rows(agg.child)
        sets = (agg.grouping_sets if agg.grouping_sets is not None
                else [tuple(range(len(agg.group_by)))])
        total, finer = 0.0, None
        for s in sorted(sets, key=len, reverse=True):
            by_scan: dict = {}
            for j in s:
                by_scan.setdefault(id(scans[j]), (scans[j], []))[1].append(
                    scans[j].columns[agg.group_by[j][1].name])
            groups = 1.0
            for sc, cols in by_scan.values():
                t = self.dimstats.tuples(sc, tuple(cols))
                if t is None:
                    t = self.store.table(sc.table).row_count
                groups *= max(t, 1)
            if rows is not None:
                # the distinct keys `rows` rows drawn evenly over
                # `groups` combinations hold
                groups *= -math.expm1(-rows / groups)
            if finer is not None:
                groups = min(groups, finer)
            finer = groups
            total += groups
        return total

    def _estimate_groups(self, agg) -> float | None:
        """Estimated number of groups of an Aggregate whose keys are
        stored columns: the product over the keys of the distinct
        values of each (a dictionary's length; an integer column's
        range or row count, whichever is less) times the share of its
        table that the scan's pushed filter keeps (_estimate_scan_
        selectivity: SSB Q3.2's `c_nation = 'UNITED STATES'` leaves 250
        / 25 cities), at least one a key. None where a key is not a
        stored column of a scan beneath, or its column has no
        range."""
        from ..sql.bound import BCol
        scans = {}

        def collect(n):
            if isinstance(n, P.Scan):
                for bname in n.columns:
                    scans[bname] = n
                return
            for attr in ("child", "left", "right"):
                c = getattr(n, attr, None)
                if c is not None:
                    collect(c)

        collect(agg.child)
        est = 1.0
        for _, e in agg.group_by:
            sc = scans.get(e.name) if isinstance(e, BCol) else None
            if sc is None:
                return None
            stored = sc.columns[e.name]
            try:
                if e.type.uses_dictionary:
                    d = self.store.table(sc.table).dictionaries.get(stored)
                    ndv = len(d.values) if d is not None else None
                else:
                    r = self.store.key_int_range(sc.table, stored)
                    ndv = None if r is None else min(r[1] - r[0] + 1, r[2])
            except (KeyError, TypeError):
                return None
            if not ndv:
                return None
            sel = self._estimate_scan_selectivity(sc)
            est *= max(1.0, ndv * (sel if sel is not None else 1.0))
        return est

    def _check_join_builds(self, node, read_ts: Timestamp,
                           overlay: set = frozenset()) -> None:
        """The device hash join gathers ONE build row per probe key
        (ops/join.py: exact for unique build keys). Verify build-side
        key uniqueness on the host over the rows VISIBLE at the query's
        read timestamp before running — a duplicate-keyed build must be
        a clean error, never a silently-dropped match. The reference's
        hash join handles duplicates by row expansion (colexecjoin/
        hashjoiner.go:870); that emission strategy is future work."""

        def walk(n):
            if isinstance(n, P.HashJoin):
                if isinstance(n.right, P.Derived):
                    self._check_derived_build(n)
                elif n.join_type in ("inner", "left"):
                    self._check_one_build(n, read_ts, overlay)
                elif isinstance(n.right, P.Scan):
                    # SEMI / ANTI: a probe row asks whether ANY build
                    # row has its key, so duplicate keys need neither
                    # a check nor an expansion; a dense key domain
                    # still takes the direct-address table
                    stored = [n.right.columns.get(k)
                              for k in n.right_keys]
                    if all(c is not None for c in stored):
                        self._maybe_direct_join(n, n.right, stored,
                                                read_ts, overlay)
                walk(n.left)
                walk(n.right)
                return
            for attr in ("child", "left", "right"):   # a UnionAll's too
                c = getattr(n, attr, None)
                if c is not None:
                    walk(c)

        walk(node)

    def _check_derived_build(self, join) -> None:
        """A join whose build side is a derived table planned in place
        (plan.Derived). Nothing of it is stored, so uniqueness and the
        key domain come from its plan: keys that are all the GROUP BY
        columns of its Aggregate are unique, and a single key that is
        a group column read off a stored integer column spans no more
        than that column (Engine.store.key_int_range of the BASE
        table: the same whatever rows the sub-select keeps, so the
        program does not follow the data). Anything else refuses the
        in-place plan (NotInPlace), and the statement takes temps."""
        from ..sql.bound import BCol
        d = join.right
        join.expand = 1
        join.direct = None
        if join.join_type == "cross":
            return      # the planner proved the build's rows few
        n = d.child
        while isinstance(n, (P.Project, P.Sort, P.Limit, P.Filter)):
            if isinstance(n, P.Project) and not all(
                    isinstance(e, BCol) for _, e in n.items):
                break
            n = n.child
        out_of = {}
        if isinstance(n, P.Aggregate) and n.group_by:
            groups = dict(n.group_by)           # "g0:name" -> expr
            for name, e in n.items:
                if isinstance(e, BCol) and e.name in groups:
                    out_of[name] = groups[e.name]
        keys = [out_of.get(d.columns.get(k)) for k in join.right_keys]
        unique = (isinstance(n, P.Aggregate) and None not in keys
                  and len(keys) == len(n.group_by))
        if not unique and join.join_type in ("inner", "left"):
            raise NotInPlace(
                f"derived table {d.alias!r} is joined on columns that "
                "are not its GROUP BY key: it cannot be a build side "
                "in place")
        if len(keys) == 1 and isinstance(keys[0], BCol):
            src = _find_scan_column(n, keys[0].name)
            if src is not None:
                r = self.store.key_int_range(*src)
                if r is not None:
                    join.direct = self._direct_slots(*r)

    def _check_one_build(self, join, read_ts: Timestamp,
                         overlay: set) -> None:
        from ..sql.stats import _underlying_col
        b = join.right
        if not isinstance(b, P.Scan):
            return
        stored = []
        all_plain = True  # every key is a stored column, not computed
        computed = dict(b.computed)
        for rk in join.right_keys:
            sname = b.columns.get(rk)
            if sname is None:
                all_plain = False
                # computed key: a dictionary-code remap of a column is
                # injective, so check the underlying column instead
                inner = _underlying_col(computed.get(rk))
                if inner is not None:
                    sname = b.columns.get(inner.name)
            if sname is None:
                return  # cannot map back to storage; accept
            stored.append(sname)
        # direct addressing needs the RUNTIME key values' range, so
        # only plain stored keys qualify (a remapped key's codes live
        # in the other dictionary's space)
        if all_plain:
            self._maybe_direct_join(join, b, stored, read_ts, overlay)
        # txn-buffered writes to the build table are invisible to the
        # store's committed-rows measurements: each buffered put can
        # add one more row per key, so it widens the bound — and
        # forfeits the uniqueness fast path
        buffered_puts = self._overlay_put_count(b.table, overlay)
        if buffered_puts == 0 and self.store.keys_unique_for_read(
                b.table, tuple(stored), read_ts.to_int()):
            join.expand = 1
            return
        # duplicate-keyed build: measure the max multiplicity among
        # visible rows and bake it in as the STATIC expansion factor
        # (ops/join.py expansion path). NB: measured at TABLE
        # granularity — a pushed build filter can only reduce the true
        # multiplicity, so K is a safe upper bound.
        k = self.store.key_max_multiplicity(b.table, tuple(stored),
                                            read_ts.to_int()) \
            + buffered_puts
        if k > self.MAX_JOIN_EXPANSION:
            raise EngineError(
                f"hash join build side {b.table!r} has up to {k} "
                f"duplicate rows per key {stored} (limit "
                f"{self.MAX_JOIN_EXPANSION}); make the lower-"
                "multiplicity table the build side")
        join.expand = max(k, 1)

    @staticmethod
    def _overlay_put_count(table: str, overlay) -> int:
        """Buffered put-ops on `table` in the current txn (0 when the
        caller passed a plain membership set)."""
        if isinstance(overlay, dict):
            return overlay.get(table, 0)
        return 0

    MAX_DIRECT_JOIN_SLOTS = 1 << 22
    # packed composite keys size the table by the SPAN PRODUCT
    MAX_PACKED_JOIN_SLOTS = 1 << 27

    def _direct_slots(self, lo: int, hi: int, n_all: int):
        """(base, slots) of the direct-address table of a single
        integer key spanning [lo, hi] over n_all rows, or None where
        the span is too sparse or too wide. Density is a MEMORY
        question, not a perf one: the build is a single scatter over
        the table regardless of sparsity, and a sparse table still
        beats the ~100x-slower while-loop hash probe. SSB's date
        dimension (YYYYMMDD ints: ~2.5K keys over a ~60K span) is the
        canonical sparse-but-small case round 2's 4x-density guard
        wrongly sent to the hash path."""
        span = hi - lo + 1
        if span <= max(256 * n_all, 4096) \
                and span + 1 <= self.MAX_DIRECT_JOIN_SLOTS:
            return (lo, span + 1)
        return None

    def _maybe_direct_join(self, join, b, stored, read_ts,
                           overlay: set) -> None:
        """Direct-address the join when the single build key is
        int-family with a dense live-value range (dimension pks, dict
        codes): one scatter + one gather instead of hash-table
        while_loops, which TPUs execute ~100x slower. Skipped for
        txn-overlay builds — uncommitted rows could fall outside the
        measured range and steal slots from committed matches."""
        join.direct = None
        if b.table in overlay:
            return
        ranges = []
        n_all = 0
        for s in stored:
            col = self.store.table(b.table).schema.column(s)
            if col.type.family == Family.FLOAT:
                return
            r = self.store.key_int_range(b.table, s)
            if r is None:
                return
            lo, hi, n_all = r
            ranges.append((lo, hi - lo + 1))
        if len(ranges) == 1:
            lo, span = ranges[0]
            join.direct = self._direct_slots(lo, lo + span - 1, n_all)
            return
        # composite keys (q9's partsupp (ps_partkey, ps_suppkey)):
        # mixed-radix-pack the components; the span PRODUCT sizes the
        # table, so the cap is higher (an int32 slot table at 2^27 is
        # 0.5GB of HBM) and the sparsity allowance wider. The payload-
        # folding path allocates ~one size-length table per carried
        # payload column on top of the slot table: budget TOTAL
        # slot-table cells, not just the key table (2^29 cells ~= 2-4GB
        # transient HBM worst case; duplicate-keyed builds take the
        # expand path, which builds only the slot table)
        total = 1
        for _, span in ranges:
            total *= span
        los = tuple(lo for lo, _ in ranges)
        spans = tuple(span for _, span in ranges)
        if total <= self.MAX_PACKED_JOIN_SLOTS \
                and total * (2 + len(join.payload)) <= 1 << 29 \
                and total <= max(2048 * n_all, 4096):
            join.direct = ("packed", los, spans)
            return
        join.direct = self._bounded_or_sorted(b.table, stored, los, spans,
                                              n_all, read_ts)

    # candidates a slot of the bounded form at most, and its table's
    # cells (slots x candidates) at most
    MAX_BOUNDED_CANDIDATES = 32
    MAX_BOUNDED_CELLS = 1 << 26

    @staticmethod
    def _coarse_range(lo: int, hi: int) -> tuple:
        """[lo, hi] widened outward to multiples of g, an eighth of the
        power of two above hi - lo (at least 1): at most half the span
        again, and the same for every range whose ends lie in the same
        cells of the grid."""
        g = 1 << max((hi - lo).bit_length() - 3, 0)
        return (lo // g) * g, (hi // g + 1) * g - 1

    def _bounded_or_sorted(self, table: str, stored, los, spans,
                           n_all: int, read_ts):
        """The form of a composite-key join past the packed table (TPC-DS
        Q80's sale to its return on (item, ticket): 4.3e9 slots at SF1;
        TPC-H Q9's partsupp: 2e9), chosen from the store's statistics,
        never the while-loop hash table:

        - `bounded`: a direct table on the component whose values have
          the fewest live rows each (a ticket's lines, a part's
          suppliers), where its span takes a direct table and that
          count, k, is small: two scatters to build where the table
          is stored in that component's order, k rounds of one where
          not; one gather of k candidates and k compares of each other
          component to probe (ops/join.py bounded_table);
        - `sorted`: where no component is dense enough, the packed key
          in 62 bits: a sorted build, a binary search of log2(n) steps;
        - None (the hash table) only where the key does not pack.

        Each component's range is widened to a coarse grid
        (`_coarse_range`) before either form is sized: a returns
        table's lowest and highest ticket move with the lines drawn,
        and a program that followed them would compile again for every
        draw of the same table."""
        coarse = [self._coarse_range(lo, lo + span - 1)
                  for lo, span in zip(los, spans)]
        los = tuple(lo for lo, _ in coarse)
        spans = tuple(hi - lo + 1 for lo, hi in coarse)
        best = None
        for i, (s, lo, span) in enumerate(zip(stored, los, spans)):
            slots = self._direct_slots(lo, lo + span - 1, n_all)
            if slots is None:
                continue
            k = self.store.key_max_multiplicity(table, (s,),
                                                read_ts.to_int())
            # a power of four: the largest count moves with the data (a
            # ticket's 7 returns, its 8 or its 9 over draws of one
            # table), a program that followed it would compile again per
            # draw, and a power of two would still split 8 from 9
            k = 4 ** math.ceil(math.log(k, 4) - 1e-9) if k > 0 else 0
            if 0 < k <= self.MAX_BOUNDED_CANDIDATES \
                    and slots[1] * k <= self.MAX_BOUNDED_CELLS \
                    and (best is None or (k, slots[1]) < best[:2]):
                best = (k, slots[1], i, slots[0])
        if best is not None:
            k, size, i, base = best
            return ("bounded", i, base, size, k, los, spans)
        if sum(int(span).bit_length() for span in spans) <= 62:
            return ("sorted", los, spans)
        return None

    def _dist_decision(self, node, session: Session):
        """Choose distributed (SPMD over the mesh) vs single-device —
        the analogue of the DistSQL distribution decision
        (sql/distsql_physical_planner.go shouldDistributePlan)."""
        if session.vars.get("distsql", "auto") == "off":
            return None
        if self.mesh is None or self.mesh.size <= 1:
            return None
        if self.mesh.size & (self.mesh.size - 1):
            return None  # table padding is pow2; shards must divide it
        if not self.settings.get("sql.distsql.mesh_partitioning.enabled"):
            return None
        d = dist_analyze(node)
        return d if d.ok else None

    def _maybe_generate_series(self, sel: ast.Select, binder: Binder):
        """SELECT generate_series(a, b [, step]) — the one supported
        set-returning function (pg SRF in the select list), table-free
        context only; args must fold to constants."""
        if len(sel.items) != 1 or sel.items[0].star:
            return None
        e = sel.items[0].expr
        if isinstance(e, ast.FuncCall) and e.name == "unnest":
            return self._exec_unnest(sel, e, binder)
        if not (isinstance(e, ast.FuncCall)
                and e.name == "generate_series"):
            return None
        if sel.where is not None or sel.distinct or sel.group_by \
                or sel.having:
            raise EngineError(
                "generate_series supports only ORDER BY/LIMIT/OFFSET "
                "(materialize it in a CTE for WHERE/GROUP BY)")
        if len(e.args) not in (2, 3):
            raise EngineError("generate_series(start, stop [, step])")
        vals = []
        for a in e.args:
            b = binder.bind(a)
            if not isinstance(b, BConst) or b.value is None:
                raise EngineError(
                    "generate_series arguments must be constants")
            vals.append(int(b.value))
        start, stop = vals[0], vals[1]
        step = vals[2] if len(vals) == 3 else 1
        if step == 0:
            raise EngineError("generate_series step cannot be 0")
        series = range(start, stop + (1 if step > 0 else -1), step)
        name = sel.items[0].alias or "generate_series"
        rows = [(int(v),) for v in series]
        if sel.order_by:
            rows = self._sort_decoded(rows, [name], sel.order_by)
        if sel.offset:
            rows = rows[sel.offset:]
        if sel.limit is not None:
            rows = rows[:sel.limit]
        from ..sql.types import INT8
        return Result(names=[name], rows=rows, types=[INT8])

    # -- selection compaction (compile.compact_batch) ------------------------
    def _estimate_scan_selectivity(self, scan) -> float | None:
        """Estimated selectivity of a scan's pushed-down filter from
        stored column ranges (the int_range direct-join machinery
        reused as a mini histogram: uniform within [min, max]) and
        dictionary sizes, conjuncts taken as independent; None where
        nothing of the filter is understood. Int-family range,
        equality and BETWEEN conjuncts contribute; a conjunct that is
        not understood can only shrink the true share further. Not a
        bound: skew inside a range, correlated conjuncts and the
        near-unique guess for an IN list can all undershoot, and a
        Compact's capacity (_compact_frac) is the estimate plus a
        headroom that is 1.5x where the estimate is large. What makes
        that safe is the Compact's overflow sentinel and the
        uncompacted replan, never the estimate."""
        return self._estimate_pred_selectivity(scan, scan.filter)

    def _estimate_pred_selectivity(self, scan, pred) -> float | None:
        """_estimate_scan_selectivity of one predicate over the scan's
        columns: a conjunction, whose OR conjuncts are estimated arm by
        arm."""
        from ..sql.bound import (BBetween, BBin, BCol, BConst,
                                 BDictLookup, BInList)
        if pred is None:
            return None
        cons: dict[str, list] = {}
        dict_fracs: list[float] = []

        def _dict_len(col: BCol) -> int | None:
            stored = scan.columns.get(col.name)
            if stored is None:
                return None
            try:
                d = self.store.table(scan.table).dictionaries.get(stored)
            except KeyError:
                return None
            return len(d.values) if d is not None else None

        def walk(e):
            if isinstance(e, BBin) and e.op == "and":
                walk(e.left)
                walk(e.right)
                return
            if isinstance(e, BBin) and e.op == "or":
                # a disjunction keeps at most the sum of what its arms
                # keep (SSB's `c_city = 'UNITED KI1' or c_city =
                # 'UNITED KI5'`: 2 / 250); an arm nothing is known of
                # leaves the whole conjunct unknown
                arms = [self._estimate_pred_selectivity(scan, x)
                        for x in (e.left, e.right)]
                if None not in arms:
                    dict_fracs.append(min(1.0, sum(arms)))
                return
            if isinstance(e, BDictLookup) and isinstance(e.expr, BCol):
                # precomputed dictionary predicate (LIKE / ordered
                # string compare): the bool table's mean IS the
                # fraction of distinct values matching
                tbl = np.asarray(e.table)
                if tbl.size:
                    dict_fracs.append(float(tbl.mean()))
                return
            if isinstance(e, BInList) and isinstance(e.expr, BCol) \
                    and e.expr.type.uses_dictionary and not e.negated:
                n = _dict_len(e.expr)
                if n:
                    dict_fracs.append(min(1.0, len(e.values) / n))
                return
            if isinstance(e, BInList) and isinstance(e.expr, BCol) \
                    and not e.negated \
                    and e.expr.type.family in (Family.INT,
                                               Family.DATE):
                # int IN-list (the inlined result of a decorrelated
                # subquery, q18's o_orderkey IN (...)): estimate
                # len(values)/rowcount assuming near-unique values.
                # NOT a hard upper bound for duplicate-keyed columns
                # — Compact's overflow sentinel replans if it
                # undershoots, so an aggressive estimate is safe
                stored = scan.columns.get(e.expr.name)
                if stored is not None:
                    try:
                        r = self.store.key_int_range(scan.table,
                                                     stored)
                    except KeyError:
                        r = None
                    if r is not None and r[2] > 0:
                        dict_fracs.append(
                            min(1.0, len(e.values) / r[2]))
                return
            if isinstance(e, BBetween) and not e.negated:
                # `lo_discount between 1 and 3`: its two bounds
                if isinstance(e.expr, BCol) \
                        and not e.expr.type.uses_dictionary and all(
                        isinstance(b, BConst) and isinstance(b.value, int)
                        and not isinstance(b.value, bool)
                        for b in (e.lo, e.hi)):
                    cons.setdefault(e.expr.name, []).extend(
                        [(">=", e.lo.value), ("<=", e.hi.value)])
                return
            if isinstance(e, BBin) and e.op in ("<", "<=", ">", ">=",
                                                "="):
                l, r, op = e.left, e.right, e.op
                if isinstance(l, BConst) and isinstance(r, BCol):
                    l, r = r, l
                    op = {"<": ">", "<=": ">=", ">": "<",
                          ">=": "<="}.get(op, op)
                if not (isinstance(l, BCol) and isinstance(r, BConst)
                        and isinstance(r.value, int)
                        and not isinstance(r.value, bool)):
                    return
                if op == "=" and l.type.uses_dictionary:
                    # dict-code equality: 1/ndv with the dictionary
                    # length as the distinct count
                    n = _dict_len(l)
                    if n:
                        dict_fracs.append(1.0 / n)
                    return
                cons.setdefault(l.name, []).append((op, r.value))
        walk(pred)
        if not cons and not dict_fracs:
            return None
        est = 1.0
        for f in dict_fracs:
            est *= f
        got = bool(dict_fracs)
        for bname, cs in cons.items():
            stored = scan.columns.get(bname)
            if stored is None:
                continue
            try:
                r = self.store.key_int_range(scan.table, stored)
            except KeyError:
                continue
            if r is None:
                continue
            lo_c, hi_c, _n = r
            lo, hi = lo_c, hi_c
            for op, v in cs:
                if op == ">=":
                    lo = max(lo, v)
                elif op == ">":
                    lo = max(lo, v + 1)
                elif op == "<=":
                    hi = min(hi, v)
                elif op == "<":
                    hi = min(hi, v - 1)
                else:           # =
                    lo, hi = max(lo, v), min(hi, v)
            width = hi_c - lo_c + 1
            if width <= 0:
                continue
            est *= max(0, hi - lo + 1) / width
            got = True
        return est if got else None

    def _reads_large_dictionary(self, scan_aliases: dict,
                                scan_cols: dict) -> bool:
        """Does a scan of the plan read a string column whose
        dictionary is past planparam's table length? Only then can the
        plan hold a table worth lifting (a table is indexed by a
        column's codes), and only then is the plan walked for one: a
        statement is prepared on every execution, and `.scan`'s are
        five milliseconds of host path each."""
        from .planparam import _TABLE_MIN
        for alias, tname in scan_aliases.items():
            dicts = self.store.table(tname).dictionaries
            for col in scan_cols.get(alias, ()):
                d = dicts.get(col)
                if d is not None and len(d.values) > _TABLE_MIN:
                    return True
        return False

    def _plan_shape_tags(self, node, scans: dict, pallas: str) -> dict:
        """The `plan` span's `joins` (hash joins in the plan), `compacts`
        (its Compact nodes), `agg` (compile.aggregate_strategy of its
        outermost Aggregate, `none` without one), `union_branches` (the
        branches of its UNION ALLs) and `join_strategy` (its joins by
        ops/join.py join_strategy, `direct:9,bounded:1`), from the plan
        and its scans' shapes alone."""
        from ..ops.join import join_strategy
        from ..sql import plan as P

        def nodes(n):
            yield n
            for attr in ("child", "left", "right"):
                c = getattr(n, attr, None)
                if c is not None:
                    yield from nodes(c)

        joins, compacts, agg = 0, 0, None
        kinds = {"semi": 0, "anti": 0, "left": 0}
        sets = windows = branches = 0
        forms: dict = {}
        for n in nodes(node):
            joins += isinstance(n, P.HashJoin)
            if isinstance(n, P.UnionAll):
                branches += 1 if isinstance(n.left, P.UnionAll) else 2
            if isinstance(n, P.HashJoin):
                form = join_strategy(n.direct, n.join_type)
                forms[form] = forms.get(form, 0) + 1
            compacts += isinstance(n, P.Compact)
            if isinstance(n, P.Aggregate) and n.grouping_sets is not None:
                sets += len(n.grouping_sets)
            if isinstance(n, P.Window):
                windows += len(n.windows)
            if isinstance(n, P.HashJoin) and n.join_type in kinds:
                kinds[n.join_type] += 1
            if agg is None and isinstance(n, P.Aggregate):
                agg = n
        strategy = "none"
        if agg is not None:
            rows = plan_rows(agg.child,
                             {a: b.n for a, b in scans.items()})
            strategy = aggregate_strategy(agg, rows or 0, ExecParams(
                pallas_groupagg=pallas,
                pallas_interpret=self._pallas_interpret()))
        return {"joins": joins, "compacts": compacts, "agg": strategy,
                "grouping_sets": sets, "windows": windows,
                "union_branches": branches,
                "join_strategy": ",".join(
                    f"{k}:{v}" for k, v in sorted(forms.items())),
                **kinds}

    def _compact_frac(self, est: float) -> float:
        """Capacity of a Compact, as a share of its input batch, whose
        rows an estimated `est` survive: the estimate times a headroom
        that falls as the estimate grows. 4x up to a sixteenth (skew
        between blocks at a share of 1/100 is what it was set for),
        then linear in log2(est) down to 1.5x at a quarter and above:
        a 32,768-row block that keeps a fifth of uniform rows deviates
        by 1 % of its mean, so there the headroom only has to cover the
        estimate itself, and a capacity past a half buys nothing (the
        caller wraps nothing above COMPACT_MAX_FRAC). Worse skew, or
        an estimate that undershoots, trips the block's sentinel and
        the statement is answered by the uncompacted plan."""
        t = min(1.0, max(0.0, (math.log2(max(est, 1e-9)) + 4) / 2))
        return max(est * (4 - 2.5 * t), 1 / 256)

    def _insert_compaction(self, node, scan_rows: dict | None = None):
        """Wrap a probe spine under aggregation in Compact nodes
        (compile.compact_batch) wherever packing the survivors costs
        clearly less than it saves: everything above a Compact (join
        probe gathers, CASE math, grouped scatter-adds) runs over its
        capacity and not over the batch it was handed.

        The candidates are a Scan with a join above it and a
        non-expanding HashJoin whose build side's filter thins the
        batch, with a join or a scattering aggregate above. Selectivity
        accumulates up the spine: a scan's pushed filter (Q14's date
        range, SSB Q1.x's discount and quantity) and every inner join
        against a filtered build side (SSB's dimension predicates,
        folded into the packed join table) shrink the selected set. At
        a candidate whose batch holds `rows` rows of which an estimated
        share survives, the capacity is _compact_frac of that share
        and the two sides are, in nanoseconds a row of the batch
        (constants beside COMPACT_PAYS, measured on the v5e):

          cost    COMPACT_NS + COMPACT_WORD_NS a carried 32-bit word:
                  the batch's columns that anything above reads, a
                  64-bit one two words unless the store proves it
                  within int32 (`narrow`, which the Compact is told);
          saving  (1 - capacity) of the work above: PROBE_NS a probe
                  (PROBE_SMALL_NS into a build of PROBE_SMALL_ROWS rows
                  or fewer) and SCATTER_NS once if the aggregate
                  scatters (hash, or dense beyond the unrolled small-G
                  path); a Project-rooted spine counts as one scatter.

        It is wrapped where saving >= COMPACT_PAYS * cost, the capacity
        is at most COMPACT_MAX_FRAC and the packed batch is smaller
        (compile._compact_block_rows: a batch under two blocks, or
        ragged, is handed on as it is). A spine is wrapped again by the
        same comparison wherever a join thins it further (SSB Q3.2
        keeps 0.04 after the customer join and 0.0016 after the
        supplier's). A scan feeding aggregation with NO join above
        stays masked: the fused filter+agg pipeline is already optimal
        (measured: Q6 1.9B -> 33M rows/s when compacted). Expanding
        joins (duplicate build keys) bound the wrap point: their
        output length breaks the bookkeeping above, but the spine below
        them still compacts, so the K-way copy runs over the packed
        rows. Project and Window stop the walk (fresh columns would
        drop the sentinel / order matters). A Compact keeps a block's
        rows in their order but interleaves filler between blocks, and
        what sits above one must not read an order out of it.
        `scan_rows` is {scan alias: padded rows of its batch}; without
        it the store's row count stands in. No estimate is trusted for
        correctness: see _estimate_scan_selectivity."""
        from ..sql import plan as P
        from ..sql.bound import BCol
        from ..sql.bound import walk as walk_expr

        def table_rows(scan) -> int:
            return self.store.table(scan.table).row_count

        def build_sel(jn) -> float:
            if jn.join_type != "inner":
                return 1.0
            if isinstance(jn.right, P.Scan):
                # what the filter keeps, read off the table, where the
                # estimate cannot see it: a probe key that reaches a
                # part of the build's keys only (a date dimension of
                # two centuries), or conjuncts that are not independent
                # (a class under its category) and keep twice the
                # estimate or more. Measured at SF1 (PR 40), the share
                # over the estimate reads 0.82-1.31 on every join of
                # the SSB and TPC-H cells (the quarter-octave rounding
                # and no more), and 4.9-15,000 where the estimate is
                # blind (TPC-DS's item pairs and date_dim, SSB Q1.2's
                # d_yearmonthnum); taking the share everywhere would
                # move the first kind's capacities by that rounding
                share, restricted = self._join_share(jn)
                e = self._estimate_scan_selectivity(jn.right)
                if share is not None and (
                        restricted or e is not None and share > 2 * e):
                    return share
                return e if e is not None else 1.0
            return 1.0

        def probe_ns(jn) -> float:
            small = isinstance(jn.right, P.Scan) \
                and table_rows(jn.right) <= PROBE_SMALL_ROWS
            return PROBE_SMALL_NS if small else PROBE_NS

        def reads_of(exprs) -> dict:
            """{column: the 32-bit words its type travels as} over the
            columns the expressions read."""
            out = {}
            for e in exprs:
                for x in walk_expr(e):
                    if isinstance(x, BCol):
                        wide = not x.type.uses_dictionary \
                            and x.type.np_dtype.itemsize > 4
                        out[x.name] = 2 if wide else 1
            return out

        seen: dict[int, tuple] = {}

        def columns(n) -> tuple[set, set]:
            """(the batch's columns, those of them that are stored
            columns proven within int32: narrow32_cols) for the spine
            under `n`. A scan's columns keep their names through
            filters, joins (as probe columns or payload) and Compacts;
            anything else renames or computes, and proves nothing."""
            if isinstance(n, (P.Filter, P.Compact)):
                return columns(n.child)
            got = seen.get(id(n))
            if got is not None:
                return got
            got = set(), set()
            if isinstance(n, P.Scan):
                fits = self.narrow32_cols(
                    n.table, frozenset(n.columns.values()))
                got = set(n.columns), {bn for bn, sn in n.columns.items()
                                       if sn in fits}
            elif isinstance(n, P.HashJoin):
                have, slim = columns(n.left)
                got = have | set(n.payload), slim | columns(n.right)[1]
            seen[id(n)] = got
            return got

        def wrap(n, live, rows, above_ns, reads):
            """(n, or a Compact over it where that pays; the rows of
            the batch handed on) for a batch of `rows` rows of which an
            estimated `live` survive, under `above_ns` of work a row
            and the columns `reads` names."""
            block = P.Compact.block
            frac = self._compact_frac(live / rows)
            kb = _compact_block_rows(rows, frac, block)
            if frac > COMPACT_MAX_FRAC or kb == block:
                return n, rows
            have, slim = columns(n)
            words = sum(1 if c in slim else w
                        for c, w in reads.items() if c in have)
            cost = COMPACT_NS + COMPACT_WORD_NS * words
            saving = (1 - kb / block) * above_ns
            if saving < COMPACT_PAYS * cost:
                return n, rows
            return (P.Compact(n, frac=frac, narrow=frozenset(slim)),
                    rows // block * kb)

        # spine(n, ...) -> (node, live, rows): `rows` those of the
        # batch `n` hands on after the Compacts beneath (0: nothing
        # above may wrap), `live` how many of them are estimated to be
        # selected. `joined`: a join lies above; `above_ns` what the
        # probes and the aggregate above cost a row of that batch;
        # `reads` {column: words} what they read
        def spine(n, joined, above_ns, reads):
            if isinstance(n, P.Filter):
                c, live, rows = spine(n.child, joined, above_ns,
                                      {**reads, **reads_of([n.pred])})
                n.child = c
                return n, live, rows
            if isinstance(n, P.Scan):
                rows = (scan_rows or {}).get(n.alias) \
                    or _next_pow2(max(table_rows(n), 1))
                if not joined:
                    return n, rows, rows
                est = self._estimate_scan_selectivity(n)
                live = rows if est is None else est * rows
                c, rows = wrap(n, live, rows, above_ns, reads)
                return c, live, rows
            if isinstance(n, P.HashJoin):
                below = dict(reads)
                for k in n.left_keys:
                    below.setdefault(k, 2)
                if n.expand != 1:
                    # output length is expand*input, which breaks the
                    # bookkeeping for wraps at or above this node —
                    # but the probe spine BELOW still benefits: a
                    # selective join under the expansion compacts,
                    # and the K-way copy then multiplies the packed
                    # rows instead of the full batch. Rows 0 so
                    # nothing above tries to compact the expanded
                    # output.
                    n.left = spine(n.left, True,
                                   probe_ns(n) + n.expand * above_ns,
                                   below)[0]
                    return n, 0, 0
                c, live, rows = spine(n.left, True,
                                      above_ns + probe_ns(n), below)
                n.left = c
                thins = build_sel(n)
                if rows and thins < 1.0:
                    live *= thins
                    c, rows = wrap(n, live, rows, above_ns, reads)
                    return c, live, rows
                return n, live, rows
            return n, 0, 0

        def walk(n):
            if isinstance(n, P.Aggregate):
                dense = n.max_groups > 0
                scatters = bool(n.group_by) and \
                    (not dense or n.max_groups > 64)
                reads = reads_of([e for _, e in n.group_by]
                                 + [a.arg for a in n.aggs
                                    if a.arg is not None])
                n.child = spine(n.child, False,
                                SCATTER_NS if scatters else 0.0, reads)[0]
                return n
            if isinstance(n, P.Derived) \
                    or isinstance(n, P.Project) and isinstance(
                        n.child, (P.Window, P.Derived)) \
                    or isinstance(n, P.Window) and isinstance(
                        n.child, (P.Aggregate, P.Derived)):
                # a Window or a derived table orders or renames what
                # an Aggregate beneath made: that Aggregate's spine
                # packs as any other (TPC-DS Q36, Q67, Q89)
                if isinstance(n, P.Derived):
                    walked.add(id(n))
                n.child = walk(n.child)
                return n
            if isinstance(n, P.Project):
                # a projection-rooted spine (CTE/derived bodies, q9's
                # `profit`): the projection math + payload pull-up +
                # temp materialization above the compact are the work
                # being shrunk; compile bubbles the overflow sentinel
                # through Project
                n.child = spine(n.child, False, SCATTER_NS,
                                reads_of([e for _, e in n.items]))[0]
                return n
            if isinstance(n, (P.Sort, P.Limit)):
                n.child = walk(n.child)
                return n
            return n

        walked: set = set()

        def nested(n):
            """The plans a derived table or a UNION ALL's branch holds
            (a CTE's aggregate beneath a spine's end, TPC-DS Q80's
            channels) pack as a statement's would: each one's spine is
            walked once."""
            for attr in ("child", "left", "right"):
                c = getattr(n, attr, None)
                if c is None:
                    continue
                if isinstance(n, P.UnionAll) or isinstance(n, P.Derived) \
                        and id(n) not in walked:
                    walked.add(id(n))
                    c = walk(c)
                    setattr(n, attr, c)
                nested(c)

        root = walk(node)
        nested(root)
        return self._defer_payloads_past_compact(root)

    def _defer_payloads_past_compact(self, root):
        """Payload pull-up: for every direct inner join BELOW a
        Compact, defer payload columns no node between the join and
        the Compact consumes to a re-probe join ABOVE the Compact:

            join(match [+ used/packed payloads]) -> Compact
              -> join(deferred payloads)

        Each deferred payload gather then touches ~est*n compacted
        rows instead of the full probe width (q3: o_orderdate /
        o_shippriority, q18: three orders payloads — ~7.5ms each at
        2^20 rows, ~free compacted). The build side compiles twice;
        its tables are size-length ops over the small build domain,
        so the duplication is noise. Packed (dict-code/bool)
        payloads stay below: they already cost one fused gather and
        upstream Filters consume their bits."""
        from ..sql.bound import referenced_columns

        # the re-probe joins made here: one whose whole payload a
        # Compact further up defers again has nothing left to carry
        # (its rows matched at the join below) and is dropped
        reprobes: set[int] = set()

        def pull_up(compact):
            used: set[str] = set()
            deferred: list = []

            def descend(n):
                if isinstance(n, P.Filter):
                    used.update(referenced_columns(n.pred))
                    n.child = descend(n.child)
                    return n
                if isinstance(n, P.HashJoin):
                    used.update(n.left_keys)
                    used.update(n.right_keys)
                    if n.join_type == "inner" and n.expand == 1 \
                            and n.direct is not None:
                        packed = set(n.pack_payload or ())
                        defer = [p for p in n.payload
                                 if p not in packed and p not in used]
                        if defer:
                            n.payload = [p for p in n.payload
                                         if p not in defer]
                            deferred.append(P.HashJoin(
                                left=None, right=n.right,
                                left_keys=list(n.left_keys),
                                right_keys=list(n.right_keys),
                                payload=defer, join_type="inner",
                                expand=1, direct=n.direct,
                                pack_payload=[]))
                            reprobes.add(id(deferred[-1]))
                            if id(n) in reprobes and not n.payload:
                                return descend(n.left)
                    used.update(n.payload)
                    n.left = descend(n.left)
                    return n
                return n

            compact.child = descend(compact.child)
            top = compact
            for dj in deferred:
                dj.left = top
                top = dj
            return top

        def walk(n):
            for attr in ("child", "left", "right"):
                c = getattr(n, attr, None)
                if c is not None:
                    setattr(n, attr, walk(c))
            if isinstance(n, P.Compact):
                return pull_up(n)
            return n

        return walk(root)

    def _exec_unnest(self, sel: ast.Select, e: ast.FuncCall,
                     binder: Binder):
        """SELECT unnest(ARRAY[...]) — constant-array SRF, table-free
        context (pg's unnest over a column needs a lateral row
        explosion; materialize via a CTE + join instead)."""
        from ..sql import datum as dtm
        from ..sql.types import Family
        if sel.where is not None or sel.distinct or sel.group_by \
                or sel.having:
            raise EngineError(
                "unnest supports only ORDER BY/LIMIT/OFFSET here "
                "(materialize it in a CTE for WHERE/GROUP BY)")
        if len(e.args) != 1:
            raise EngineError("unnest(array)")
        b = binder.bind(e.args[0])
        if not isinstance(b, BConst):
            raise EngineError(
                "unnest over columns is not supported (constant "
                "arrays only)")
        name = sel.items[0].alias or "unnest"
        if b.value is None:
            return Result(names=[name], rows=[], types=[b.type.elem
                          if b.type.family == Family.ARRAY else b.type])
        if b.type.family != Family.ARRAY:
            raise EngineError("unnest needs an array argument")
        vals = dtm.parse_array(b.value, b.type.elem)
        rows = [(v,) for v in vals]
        if sel.order_by:
            rows = self._sort_decoded(rows, [name], sel.order_by)
        if sel.offset:
            rows = rows[sel.offset:]
        if sel.limit is not None:
            rows = rows[:sel.limit]
        return Result(names=[name], rows=rows, types=[b.type.elem])

    def _exec_table_free(self, sel: ast.Select,
                         session: Session | None = None) -> Result:
        """SELECT <exprs> with no FROM."""
        session = session or self.session()
        read_ts = self._read_ts(session)
        binder = Binder(
            Scope(),
            subquery_eval=lambda s, lim: self._eval_subquery(
                s, session, lim),
            now_micros=read_ts.wall // 1000,
            sequence_ops=self._sequence_ops(session))
        srf = self._maybe_generate_series(sel, binder)
        if srf is not None:
            return srf
        names, exprs = [], []
        for it in sel.items:
            if it.star:
                raise EngineError("SELECT * requires FROM")
            b = binder.bind(it.expr)
            names.append(it.alias or "column")
            exprs.append(b)
        ctx = ExprContext({}, 1)
        row = []
        types = []
        for b in exprs:
            if isinstance(b, BConst):
                # constants (incl. folded string builtins) skip the
                # device: strings have no resident dictionary here
                v = b.value
                if b.type.family == Family.DECIMAL and v is not None:
                    v = v / 10 ** b.type.scale
                elif b.type.family == Family.DATE and v is not None:
                    v = EPOCH_DATE + datetime.timedelta(days=int(v))
                elif b.type.family == Family.TIMESTAMP and v is not None:
                    v = EPOCH_DT + datetime.timedelta(microseconds=int(v))
                elif b.type.family in (Family.ARRAY, Family.JSON) \
                        and v is not None:
                    from ..sql import datum as dtm
                    v = dtm.decode_text(v, b.type)
                row.append(v)
                types.append(b.type)
                continue
            d, v = compile_expr(b)(ctx)
            row.append(_decode_scalar(np.asarray(d)[0], bool(np.asarray(v)[0]),
                                      b.type, None))
            types.append(b.type)
        return Result(names=names, rows=[tuple(row)], types=types)


def _scan_of(node, batch_name: str):
    """The Scan beneath `node` whose batch column `batch_name` is, or
    None (a computed or derived column)."""
    if isinstance(node, P.Scan):
        return node if batch_name in node.columns else None
    if isinstance(node, P.Derived):
        return None
    for attr in ("child", "left", "right"):
        c = getattr(node, attr, None)
        if c is not None:
            got = _scan_of(c, batch_name)
            if got is not None:
                return got
    return None
