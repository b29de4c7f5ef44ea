"""Settings: the three config planes of the reference (SURVEY.md §5).

1. Cluster settings (pkg/settings: typed, dynamic, `SET CLUSTER
   SETTING`) -> ``Settings`` registry with typed registration and
   update callbacks (gossip propagation arrives with the cluster
   fabric).
2. Session vars (pkg/sql/sessiondatapb, vars.go; the north-star gate
   `SET vectorize=...` lives there) -> ``SessionVars``.
3. Node config (CLI flags / base.Config) -> ``NodeConfig``.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Callable, Optional


class SettingError(Exception):
    pass


@dataclass
class _Setting:
    name: str
    default: object
    kind: type
    description: str = ""
    validate: Optional[Callable[[object], None]] = None


class Settings:
    """Typed cluster-setting registry (cf. settings.RegisterBoolSetting,
    pkg/settings/bool.go:107)."""

    def __init__(self):
        self._defs: dict[str, _Setting] = {}
        self._retired: set[str] = set()
        self._values: dict[str, object] = {}
        self._lock = threading.Lock()
        self._watchers: list[Callable[[str, object], None]] = []
        _register_builtins(self)

    def register(self, name: str, default, kind: type, description: str = "",
                 validate=None):
        self._defs[name] = _Setting(name, default, kind, description, validate)

    def retire(self, name: str) -> None:
        """A setting this version no longer has (pkg/settings'
        retiredSettings): a SET of it, from an older configuration or
        script, is accepted and does nothing; it holds no value, is
        not listed, and cannot be read."""
        self._retired.add(name)

    def set(self, name: str, value) -> None:
        if name in self._retired:
            return
        d = self._defs.get(name)
        if d is None:
            raise SettingError(f"unknown cluster setting {name!r}")
        if d.kind is bool and isinstance(value, str):
            value = value.lower() in ("true", "on", "1", "yes")
        try:
            value = d.kind(value)
        except (TypeError, ValueError) as e:
            raise SettingError(f"bad value for {name}: {value!r}") from e
        if d.validate is not None:
            d.validate(value)
        with self._lock:
            self._values[name] = value
            watchers = list(self._watchers)
        for w in watchers:
            w(name, value)

    def get(self, name: str):
        d = self._defs.get(name)
        if d is None:
            raise SettingError(f"unknown cluster setting {name!r}")
        with self._lock:
            return self._values.get(name, d.default)

    def on_change(self, fn: Callable[[str, object], None]):
        self._watchers.append(fn)

    def snapshot(self) -> dict:
        with self._lock:
            out = {n: d.default for n, d in self._defs.items()}
            out.update(self._values)
            return out

    def apply_snapshot(self, snap: dict) -> None:
        """Adopt a gossiped snapshot from another node (names this
        version does not have, a retired one of an older node's
        included, are passed over)."""
        for k, v in snap.items():
            if k in self._defs:
                with self._lock:
                    self._values[k] = v


def _pow2(v):
    if v & (v - 1) != 0:
        raise SettingError("must be a power of two")


def _cache_dir_switch(v):
    if v.lower() not in ("", "off"):
        raise SettingError(
            "must be '' or 'off'; place the cache directory with "
            "JAX_COMPILATION_CACHE_DIR")


def _submesh_size(v):
    if v in ("auto", "off"):
        return
    try:
        n = int(v)
    except ValueError:
        raise SettingError("must be auto, off, or a power of two")
    if n < 1 or n & (n - 1) != 0:
        raise SettingError("must be auto, off, or a power of two")


def _register_builtins(s: Settings):
    s.register("version", "25.3-tpu.1", str, "cluster version gate")
    s.register("sql.tpu.direct_columnar_scans.enabled", True, bool,
               "serve scans straight from the columnar MVCC store "
               "(cf. V23_1_KVDirectColumnarScans)")
    s.register("sql.distsql.mesh_partitioning.enabled", True, bool,
               "partition scan spans over the device mesh")
    s.register("kv.range.max_bytes", 512 << 20, int,
               "range split threshold (cf. 512MB default)")
    s.register("kv.gc.ttl_seconds", 14400, int, "MVCC GC TTL")
    s.register("sql.exec.hash_group_capacity", 1 << 17, int,
               "device hash-table slots for GROUP BY", _pow2)
    s.register("sql.exec.hbm_budget_bytes", 12 << 30, int,
               "device-memory budget for resident table uploads; "
               "aggregate scans over bigger tables stream in pages "
               "(the HBM analogue of --max-sql-memory / workmem)")
    s.register("sql.stats.stale_row_fraction", 0.2, float,
               "row-count drift (fraction of the ANALYZE-time count) "
               "past which ANALYZE statistics are considered stale "
               "and the planner falls back to seal-time sketch "
               "estimates")
    s.register("exec.agg.adaptive_raw_fraction", 0.5, float,
               "DistSQL adaptive aggregation: when a shard's "
               "estimated group count exceeds this fraction of its "
               "row count, ship raw rows instead of per-shard "
               "partial aggregates (Partial Partial Aggregates)")
    s.register("sql.trace.slow_statement.threshold", 0.0, float,
               "statements slower than this many seconds keep their "
               "trace recording in the /debug/tracez ring buffer "
               "(0 disables; sql.trace.txn.enable_threshold analogue)")
    # cold-start elimination (exec/coldstart.py): persistent XLA
    # compile cache + shape bucket ladder
    s.register("sql.exec.compile_cache.dir", "", str,
               "'off' disables the persistent XLA compile cache; '' "
               "(default) uses $JAX_COMPILATION_CACHE_DIR, else "
               "<checkout>/.jax_cache. The directory itself is placed "
               "only through that environment variable",
               _cache_dir_switch)
    s.register("sql.exec.compile_cache.prewarm", 0, int,
               "top-K statement texts from the previous run's shapes "
               "journal that Engine.prewarm() re-prepares at startup "
               "(0 disables)")
    s.register("sql.exec.shape_bucket.min_rows", 1024, int,
               "smallest row bucket executables are compiled for",
               _pow2)
    s.register("sql.exec.shape_bucket.steps_per_octave", 1, int,
               "row buckets per doubling of the shape ladder "
               "(1 = classic pow2 padding; 2/4/8 insert intermediate "
               "buckets: less padding waste, more executables)",
               _pow2)
    # the large-G kernel's tile is its module's constants since the
    # autotuner went; benchmark/configs/*.json still SET this to off
    s.retire("sql.exec.pallas.autotune")
    # multi-tenant front door: sub-mesh dispatch + admission shedding
    s.register("sql.exec.submesh.size", "auto", str,
               "devices per dispatch sub-mesh for eligible distributed "
               "plans: a power of two divides the mesh into disjoint "
               "rendezvous domains that execute concurrently; auto = "
               "pick the smallest size whose per-device working set "
               "fits the HBM budget share; off = always the full mesh",
               _submesh_size)
    s.register("sql.admission.shed.queue_depth", 0, int,
               "admission queue depth at which low-priority statements "
               "are rejected up front instead of queued (0 disables)")
    s.register("sql.admission.shed.wait_seconds", 0.0, float,
               "recent admission grant-wait (EWMA, seconds) above which "
               "low-priority statements are shed (0 disables)")
    s.register("sql.admission.shed.exec_queue_depth", 0, int,
               "live device-dispatcher queue depth (exec.device.queue."
               "depth) above which low-priority statements are shed: "
               "when the mesh itself is backlogged, queueing more work "
               "only grows execution-stall p99 (0 disables)")
    s.register("sql.admission.tenant.slots", 0, int,
               "per-tenant cap on concurrently held admission slots; a "
               "tenant at its cap queues behind other tenants even when "
               "global slots are free (0 disables; the quota analogue "
               "of tenant-weighted WorkQueue ordering)")
    s.register("sql.admission.tenant.hbm_fraction", 0.0, float,
               "fraction of sql.exec.hbm_budget_bytes one tenant's "
               "in-flight statements may pin at once; statements whose "
               "estimated working set would push the tenant over wait "
               "for an eligible slot instead of dispatching (0 disables)")
    s.register("sql.exec.plan_cache.tenant_budget", 0, int,
               "per-tenant entry budget in the compiled-plan and parse "
               "caches: a tenant past its budget evicts its OWN oldest "
               "entries, never another tenant's compiled shapes "
               "(0 = shared LRU, no partitioning)")
    s.register("server.prepared_statement_budget", 256, int,
               "named prepared statements one pgwire session may hold; "
               "Parse past the budget fails with 53400 instead of "
               "growing server memory unboundedly (0 disables)")
    # pgwire front door (server/pgfront.py reactor)
    s.register("server.pgwire_frontend", "reactor", str,
               "pgwire connection front end: reactor = one selector "
               "event loop owns all sockets, idle sessions hold no "
               "thread, a bounded worker pool sized by active "
               "statements runs the protocol; threads = legacy "
               "thread-per-connection socketserver (bit-for-bit A/B "
               "lever)")
    s.register("server.idle_session_timeout", 0.0, float,
               "seconds a pgwire session may sit idle outside a "
               "transaction before the server closes it (0 disables; "
               "idle_session_timeout analogue)")
    s.register("server.startup_deadline_seconds", 10.0, float,
               "deadline for a new connection to complete its startup "
               "packet and authentication; a slow-loris connect is "
               "closed at the deadline instead of pinning the front "
               "door (0 disables)")
    s.register("sql.exec.switch_interval", 0.0, float,
               "sys.setswitchinterval applied while executor workers "
               "run (0 = leave the interpreter default of 5ms). "
               "Process-global: a smaller quantum lets OLTP batch "
               "windows close while an analytic statement holds the "
               "GIL (measured ~2x oltpbatch flip at 0.0005)")
    # observability: operator profiles + statement diagnostics
    s.register("sql.stmt_profile.enabled", True, bool,
               "per-statement coarse operator profile (exec/profile"
               ".py): data-movement call sites attribute bytes/stalls "
               "to the executing statement's sink, feeding per-tenant "
               "rollups at /_status/tenants. Off = the kill switch "
               "(profiling is host-side accounting only; results are "
               "identical either way)")
    s.register("timeseries.retention.seconds", 6 * 3600, int,
               "fine-resolution (10s) timeseries slabs older than "
               "this are rolled up to coarse resolution and pruned by "
               "the maintenance loop (timeseries.storage.resolution_"
               "10s.ttl analogue); coarse slabs keep their own 30-day "
               "retention")


def _meta_page_rows() -> int:
    from .metamorphic import metamorphic_pow2
    return metamorphic_pow2("sql.streaming_page_rows", 1 << 21, 12, 21)


@dataclass
class SessionVars:
    """Session variables with reference-compatible names where sensible."""
    values: dict = field(default_factory=lambda: {
        "vectorize": "on",           # on | off  (off = host row engine)
        "distsql": "auto",           # auto | on | off | always
        "streaming": "auto",         # auto | off (beyond-HBM paging)
        "streaming_page_rows": _meta_page_rows(),
        # on | off: background page-prefetch pipeline for streamed
        # scans (off = assemble each page synchronously; A/B lever)
        "streaming_pipeline": "on",
        "direct_columnar_scans_enabled": True,
        "hash_group_capacity": 1 << 17,
        # one-pass large-G Pallas GROUP BY kernel. auto (default):
        # per-plan eligibility (compile.large_kernel_eligible), exact
        # results only; off: the XLA path, the oracle of auto == off
        "pallas_groupagg": "auto",   # auto | off
        # normalized sort keys (ops/sortkey.py): pack the whole
        # ORDER BY / window / distinct key list into uint64 lanes and
        # sort with one stable argsort per lane instead of the
        # variadic lexsort (XLA compiles ~20s per sort operand beyond
        # 64K rows). auto (default): whenever every key is encodable,
        # lexsort fallback otherwise (tallied); off: escape hatch /
        # bench A/B lever
        "sort_normalized": "auto",   # auto | on | off
        # out-of-core spill tier (exec/spill.py): partitioned external
        # hash join and external merge sort when the working set
        # exceeds sql.exec.hbm_budget_bytes. auto (default): spill
        # only when the resident/stream-scan paths would blow the
        # budget; on: force spill whenever the plan shape is eligible;
        # off: escape hatch / bench A/B lever
        "spill": "auto",             # auto | on | off
        # join-induced data skipping (exec/joinfilter.py): summarize
        # the build side of an inner/semi hash join (min/max + exact
        # keys or bloom) and skip probe-side pages/chunks/rows that
        # cannot match. auto (default): derive when the build is
        # small enough to summarize cheaply; on: always derive; off:
        # escape hatch / bench A/B lever. Results are bit-identical
        # in every mode — the filter is never false-negative.
        "join_filter": "auto",       # auto | on | off
        # SET tracing = off | on | cluster (exec/engine.py): on
        # records each statement gateway-locally for SHOW TRACE FOR
        # SESSION; cluster additionally requests remote recordings
        # from every RPC / DistSQL flow the statement touches
        "tracing": "off",            # off | on | cluster
        # statement-shape plan cache (exec/planparam.py): strip
        # eligible filter literals into runtime args so statements
        # differing only in literals share one compiled _exec_cache
        # entry. auto (default): parameterize resident + distributed
        # selects, conservative bail-out when a literal shapes the
        # plan; off: text keying (escape hatch / bench A/B lever)
        "plan_shape_cache": "auto",  # auto | off
        # memo-based join ordering / rule pipeline / sketch-fed
        # costing (off = syntax order, no rewrites, ANALYZE-only
        # stats). Registered with the same defaults the read sites
        # fall back to — graftlint registration-drift found these
        # read-but-unregistered (invisible to SHOW and the journal)
        "optimizer": "on",           # on | off
        "optimizer_rules": "on",     # on | off
        "optimizer_sketch_stats": "on",   # on | off
        # secondary-index locator plane (exec/fastpath.py,
        # exec/oltplane.py): index scans and the per-key row limit
        # past which a warm locator declines in favor of the scan
        "index_scan": "on",          # on | off
        "index_lookup_limit": 4096,
        # cross-session batch fusion on the OLTP lane
        # (exec/oltpbatch.py): auto fuses concurrent point statements
        # into batch windows (one multi-key probe / one group commit);
        # off restores the per-statement lane path (bench A/B lever)
        "oltp_batch": "auto",        # auto | off
        # admission tier for this session's statements (the reference's
        # admission.WorkPriority): high | normal | low
        "admission_priority": "normal",
        "application_name": "",
        "database": "defaultdb",
        "extra_float_digits": 0,
        "statement_timeout": 0,
    })

    def set(self, name: str, value) -> None:
        self.values[name] = value

    def get(self, name: str, default=None):
        return self.values.get(name, default)


@dataclass
class NodeConfig:
    """Per-node boot config (cf. base.Config + CLI flags)."""
    node_id: int = 1
    addr: str = "127.0.0.1:26257"
    http_addr: str = "127.0.0.1:8080"
    store_dir: str = ""
    join: list[str] = field(default_factory=list)
    max_offset_ns: int = 500_000_000
