"""cockroach_tpu: a TPU-native distributed SQL database framework.

A from-scratch rebuild of the capabilities of CockroachDB (reference:
/root/reference, a Go distributed SQL database) designed TPU-first:

- The *device side* (JAX/XLA/Pallas) owns columnar query execution: the
  analogue of the reference's vectorized engine (``pkg/sql/colexec``,
  453K lines of generated per-type Go kernels) is a small set of
  dtype-generic, mask-based JAX kernels compiled by XLA onto the MXU/VPU.
- The *host side* (Python, C++ where hot) owns what a database host must
  own: pgwire-ish wire protocol, SQL parsing/planning, the catalog, the
  MVCC KV store, replication, and job control.
- The *distribution* layer maps the reference's DistSQL flows
  (``pkg/sql/distsql_physical_planner.go``) onto ``jax.sharding.Mesh``:
  range partitions become per-chip shards, and DistSQL's final-stage
  partial-aggregate shuffle becomes an ICI allreduce
  (``jax.lax.psum`` inside ``shard_map``).

Layer map (mirrors SURVEY.md §1):

    sql/        parser, AST, semantic analysis, logical planner,
                memoized cost-based join ordering (memo.py), stats
    exec/       logical plan -> compiled JAX program (the "colexec"):
                streaming beyond-HBM scans, hash-partitioned spill,
                host-side index point/range fastpaths, constraints
    ops/        device columnar core: ColumnBatch, kernels, agg, join
                (+ ops/pallas: hand-written TPU kernels)
    storage/    host columnar MVCC store + memtable/LSM + HLC, index
                locators (hash + sorted, generation-cached)
    catalog/    versioned descriptors in KV, leases, views, indexes,
                checks/fks
    kv/         transactional KV client (txn coordinator, latches,
                DistSender + range cache, intent resolver)
    kvserver/   ranges: raft, leases, liveness, splits/merges, queues,
                circuit breakers, loss-of-quorum recovery
    parallel/   mesh partitioning, shard_map flows, collectives
    distsql/    cross-node flow runtime (specs, registry, outbox/inbox)
    server/     node lifecycle + pgwire v3 + KV-backed time-series DB
    jobs/       durable job registry, checkpoint/resume, IMPORT,
                schema changes, index backfill, BACKUP/RESTORE, TTL
    cdc/        changefeeds over rangefeeds
    workload/   TPC-C, YCSB A-F, SSB, bank, kv, MovR generators
    models/     flagship query "models" (TPC-H workloads) for bench
    utils/      settings, metrics, tracing, admission, circuit, mon
    native/     C++ hot-path components (batch key encoder)
    cli.py      cockroach-tpu start / sql / demo
"""

__version__ = "0.4.0"

# The engine's physical types require 64-bit lanes (HLC timestamps and
# scaled-decimal int64 accumulation); JAX disables x64 by default.
import jax as _jax  # noqa: E402

_jax.config.update("jax_enable_x64", True)

# Host-side DML predicates and the OLTP lane evaluate on the cpu
# backend (exec/dml.py _host_eval), so it has to initialise beside the
# accelerator: a platform list that names only the accelerator
# (JAX_PLATFORMS=tpu) gains cpu behind it. The first entry stays the
# default backend, and still fails at start-up if it cannot come up.
_platforms = _jax.config.jax_platforms
if _platforms and "cpu" not in _platforms.split(","):
    _jax.config.update("jax_platforms", _platforms + ",cpu")
