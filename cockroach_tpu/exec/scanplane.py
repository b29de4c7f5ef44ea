"""Scan-plane runtime: hash-partitioned spill, beyond-HBM streaming, the
device table cache, and result materialization (the block-cache +
disk-spiller analogues, colexecdisk/disk_spiller.go:75).

Split out of exec/engine.py (round-2 VERDICT Weak #4); see that
module's docstring for the overall execution model."""


import datetime
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.batch import (MVCC_COLUMNS, NEVER_TS, ColumnBatch,
                         alloc_mvcc_words, fill_mvcc_words,
                         put_mvcc_words, read_ts_words)
from ..parallel import mesh as meshmod
from ..parallel.distagg import analyze as dist_analyze
from ..parallel.distagg import make_distributed_fn, queued_collective_call
from ..parallel.mesh import SHARD_AXIS
from ..sql import plan as P
from ..storage.hlc import Timestamp
from ..utils import tracing as _trc
from ..utils.mon import MemoryQuotaError
from .compile import (ExecParams, RunContext, can_spill_sort,
                      can_stream, compile_plan)

EPOCH_DATE = datetime.date(1970, 1, 1)
EPOCH_DT = datetime.datetime(1970, 1, 1)

from .session import (SENTINEL_COLUMNS, CompactOverflow, EngineError,
                      HashCapacityExceeded, Prepared, TopKInexact,
                      Result, Session)
from .stmtutil import (_collect_scans, _count_aggs, _decode_column,
                       _has_join, _host_sort, _root_aggregate)
from .stream import PageSource
from .stream import prefetch as stream_prefetch
from . import profile as _prof
import time as _time


# exception factory per sentinel; names come from the one registry
# (session.SENTINEL_COLUMNS) so a new sentinel missing its mapping
# here fails loudly at import time
_SENTINEL_EXCS = {
    "__ht_overflow": lambda: HashCapacityExceeded(
        "GROUP BY cardinality exceeded hash_group_capacity; "
        "SET hash_group_capacity to a larger power of two"),
    "__sum_overflow": lambda: EngineError(
        "decimal SUM overflowed int64 accumulation; "
        "CAST the argument to FLOAT to trade exactness for range"),
    "__topk_inexact": lambda: TopKInexact(
        "top-k cut crossed a primary-key tie group; "
        "replanning with the full sort"),
    "__compact_overflow": lambda: CompactOverflow(
        "selection compaction overflowed a block's capacity; "
        "replanning uncompacted"),
}
_SENTINEL_PAIRS = tuple((n, _SENTINEL_EXCS[n]) for n in SENTINEL_COLUMNS)


def _own_columns(b: ColumnBatch, cols: frozenset | None) -> ColumnBatch:
    """The batch a scan of `cols` is handed when a resident copy with
    more columns serves it (_device_lookup_locked): its own columns and
    the MVCC words, in the table's order, a view. A program's arguments
    then do not depend on what other statements made resident, so a
    wider upload neither retraces nor recompiles it (SSB's flight 4
    reads a superset of flights 2 and 3: seven programs were compiled
    twice in every cold set-up)."""
    if cols is None or len(b.names) <= len(cols) + len(MVCC_COLUMNS):
        return b
    return b.project([n for n in b.names
                      if n in cols or n in MVCC_COLUMNS])


class ScanPlaneMixin:
    """Engine methods for this concern; mixed into exec.engine.Engine
    (all state lives on the Engine instance)."""

    # -- hash-partitioned spill ---------------------------------------------
    MAX_SPILL_PARTITIONS = 256
    # duplicate-key join expansion cap: output rows = probe.n * K
    MAX_JOIN_EXPANSION = 32

    def _run_partitioned(self, prep: "Prepared",
                         read_ts: Optional[Timestamp]) -> Result:
        """Partition-and-recurse fallback for hash GROUP BY overflow.

        The compiled program already takes (nparts, pid) scalars and
        keeps only rows whose salted key-hash lands in partition pid
        (ops/hashtable.py partition_mask), so spilling is: rerun the
        SAME program once per partition, concatenate the per-partition
        group rows on the host, then apply any Sort/Limit there
        (device sort/limit would have been per-partition). Doubling
        the partition count until every partition fits mirrors the
        reference's recursive hash_based_partitioner; re-reads hit the
        resident HBM table instead of disk.
        """
        node, meta = self._plan(prep.stmt, prep.session)
        limit_node = sort_node = None
        if isinstance(node, P.Limit):
            limit_node, node = node, node.child
        if isinstance(node, P.Sort):
            sort_node, node = node, node.child
        if not isinstance(node, P.Aggregate) or node.max_groups > 0:
            raise HashCapacityExceeded(
                "GROUP BY overflow in a non-spillable plan shape; "
                "SET hash_group_capacity to a larger power of two")

        # compile the STRIPPED plan (no device Sort/Limit — a per-
        # partition limit would truncate wrongly); reuse prep's device
        # scans, which already match the distribution decision
        cap = int(prep.session.vars.get("hash_group_capacity", 1 << 17))
        decision = self._dist_decision(node, prep.session)
        shapes = tuple(sorted((a, b.n) for a, b in prep.scans.items()))
        dictlens = tuple(
            sorted((t, tuple(sorted((cn, len(d)) for cn, d in
                                    self.store.table(t).dictionaries
                                    .items())))
                   for t, _ in prep.gens))
        key = ("spill", prep.sql_text, shapes, dictlens, cap,
               decision is not None, hash(repr(node)))
        cached = self._exec_cache.get(key)
        if cached is None:
            params = ExecParams(
                hash_group_capacity=cap,
                axis_name=SHARD_AXIS if decision is not None else None,
                n_shards=(self.mesh.devices.size
                          if decision is not None else 1))
            runf = compile_plan(node, params, meta)
            if decision is not None:
                jfn = queued_collective_call(jax.jit(
                    make_distributed_fn(
                        runf, self.mesh, _collect_scans(node),
                        decision)),
                    metrics=self.metrics, mesh=self.mesh)
            else:
                def fn(scans_in, ts_in, np_, pid_):
                    return runf(RunContext(scans_in, ts_in, np_, pid_))
                jfn = jax.jit(fn)
            self._exec_cache_put(key, (jfn, meta))
        else:
            jfn, meta = cached

        ts = read_ts or self._read_ts(prep.session)
        tsv = read_ts_words(ts.to_int())

        def run_pid(fn, scans, np_enc: int, pid_enc: int) -> list:
            out = fn(scans, tsv, np.int32(np_enc), np.int32(pid_enc))
            return self._materialize(out, meta).rows

        def pid_rows(fn, scans, nparts: int, pid: int) -> list:
            try:
                return run_pid(fn, scans, nparts, pid)
            except HashCapacityExceeded:
                if nparts < self.MAX_SPILL_PARTITIONS:
                    raise  # outer loop doubles the level-1 fan-out
                # grace-style recursion (the reference's
                # hash_based_partitioner): at the level-1 ceiling this
                # partition's keys collide under the first salt, so
                # doubling can never separate them — subdivide JUST
                # this partition under the rotated salt (encoded into
                # the same (nparts, pid) scalars, ops/hashtable.py)
                l2 = 2
                while l2 <= self.MAX_SPILL_PARTITIONS:
                    try:
                        rows: list = []
                        for pid2 in range(l2):
                            rows.extend(run_pid(
                                fn, scans, nparts * l2,
                                pid2 * nparts + pid))
                        self.metrics.counter(
                            "exec.spill.grace_subsweeps",
                            "spill partitions subdivided under a "
                            "rotated hash past the level-1 ceiling"
                        ).inc()
                        return rows
                    except HashCapacityExceeded:
                        l2 *= 2
                raise HashCapacityExceeded(
                    f"GROUP BY did not fit hash_group_capacity even "
                    f"at {self.MAX_SPILL_PARTITIONS} spill partitions "
                    f"x {self.MAX_SPILL_PARTITIONS} rotated-salt "
                    f"sub-partitions")

        # transient working-set estimate for the unified transfer
        # budget: one partition's slice of the resident inputs
        scan_bytes = sum(int(x.nbytes)
                         for b in prep.scans.values()
                         for x in jax.tree.leaves(b))
        nparts = 2
        while True:
            try:
                with self.movement.soft_lease(
                        "spill", scan_bytes // max(nparts, 1)):
                    all_rows = self._sweep_spill_partitions(
                        jfn, decision, prep, nparts, pid_rows, key,
                        node, meta, cap)
                break
            except HashCapacityExceeded:
                if nparts >= self.MAX_SPILL_PARTITIONS:
                    raise  # grace depth exhausted inside pid_rows
                nparts *= 2

        _prof.note("spill:agg", batches=nparts, rows=len(all_rows))
        rows = all_rows
        if sort_node is not None:
            rows = _host_sort(rows, meta, sort_node.keys)
        if limit_node is not None:
            off = limit_node.offset or 0
            end = (off + limit_node.limit
                   if limit_node.limit is not None else None)
            rows = rows[off:end]
        return Result(names=list(meta.names), rows=rows)

    def _sweep_spill_partitions(self, jfn, decision, prep, nparts: int,
                                pid_rows, key, node, meta, cap) -> list:
        """Run every spill partition and concatenate rows in pid
        order. With a distributed decision and a splittable mesh, the
        sweep fans out over DISJOINT pool sub-meshes (round-10
        MeshPool) so independent partitions overlap instead of
        serializing through one device set; any failure to stand up
        the sub-mesh plane (budget, pool shape) falls back to the
        serial full-mesh sweep."""
        subs = None
        if decision is not None and nparts >= 2:
            subs = self._submesh_spill_calls(key, node, meta, cap,
                                             decision)
        if subs is None:
            out: list = []
            for pid in range(nparts):
                out.extend(pid_rows(jfn, prep.scans, nparts, pid))
            return out
        calls, scanses = subs
        nsub = len(calls)
        import concurrent.futures as cf
        results: list = [None] * nparts

        def worker(pid: int) -> list:
            # fixed pid->sub-mesh assignment: two pids on one sub-mesh
            # serialize through its FIFO dispatcher; different
            # sub-meshes run concurrently (disjoint rendezvous
            # domains, same-mode gate windows)
            idx = pid % nsub
            return pid_rows(calls[idx], scanses[idx], nparts, pid)

        with cf.ThreadPoolExecutor(max_workers=nsub) as ex:
            futs = {pid: ex.submit(worker, pid)
                    for pid in range(nparts)}
            err = None
            for pid, f in futs.items():
                try:
                    results[pid] = f.result()
                except HashCapacityExceeded as e:
                    err = err or e
            if err is not None:
                raise err
        self.metrics.counter(
            "exec.spill.submesh_sweeps",
            "spill partition sweeps fanned out over pool sub-meshes"
        ).inc()
        return [r for part in results for r in part]

    def _submesh_spill_calls(self, key, node, meta, cap, decision):
        """Per-sub-mesh compiled calls + re-resolved device scans for
        the concurrent spill sweep, cached under the spill exec-cache
        key. None when the pool can't yield >=2 disjoint sub-meshes
        or the budget can't hold the per-sub-mesh table copies."""
        pool = self._submesh_pool()
        if pool is None:
            return None
        sizes = [s for s in sorted(pool.sizes(), reverse=True)
                 if s >= 2 and pool.count(s) >= 2]
        if not sizes:
            return None
        size = sizes[0]
        ck = key + ("submesh", size)
        cached = self._exec_cache.get(ck)
        if cached is not None:
            return cached
        aliases = _collect_scans(node)
        params = ExecParams(hash_group_capacity=cap,
                            axis_name=SHARD_AXIS, n_shards=size)
        runf = compile_plan(node, params, meta)
        calls = []
        scanses = []
        try:
            for sub in pool.submeshes(size):
                calls.append(queued_collective_call(
                    jax.jit(make_distributed_fn(runf, sub, aliases,
                                                decision)),
                    metrics=self.metrics, mesh=sub))
                scanses.append({
                    alias: self._device_table(
                        tname,
                        ("sharded" if alias in decision.sharded
                         else "replicated"),
                        cols=None, narrow=False, mesh=sub)
                    for alias, tname in aliases.items()})
        except MemoryQuotaError:
            return None
        out = (calls, scanses)
        self._exec_cache_put(ck, out)
        return out

    # -- beyond-HBM streaming ------------------------------------------------
    def _stream_decision(self, node, scan_aliases: dict, scan_cols: dict,
                         session: Session):
        """Page the fact table through HBM when the statement's working
        set would not fit the device budget (``sql.exec.hbm_budget_
        bytes``). The working set is a model of the program that will
        run (placement_model): the pruned upload the resident path
        would make, plus what the plan's aggregation path allocates
        beside it. Eligibility mirrors the mesh distribution analysis
        (the plan must reduce to mergeable aggregate partials); only
        the probe-spine scan streams.
        Returns (alias, table, page_rows) or None."""
        scan = self._streamable_scan(node, scan_aliases, scan_cols,
                                     session)
        if scan is None:
            return None
        alias, tname, eff_bytes, padded = scan
        budget = int(self.settings.get("sql.exec.hbm_budget_bytes"))
        # the scatter path's term bounds the kernel path's words from
        # above (two 64-bit temporaries an aggregate against at most
        # two 32-bit words an aggregate and two they share), so a
        # statement that fits under it fits on either path, and a
        # plan-cache hit far from the budget does not plan the
        # kernel's operands to learn that. (The accumulator tiles, a
        # few hundred MB at the widest group domain, are not in the
        # bound.)
        if eff_bytes + 16 * _count_aggs(node) * padded <= budget:
            return None
        if self.placement_model(node, session, eff_bytes,
                                padded) <= budget:
            return None
        # Build-side tables still upload whole: streaming the probe is
        # strictly better than not, and an over-budget build fails
        # upstream with a clean quota error rather than silently here.
        return (alias, tname, self._page_rows(session))

    def _streamable_scan(self, node, scan_aliases: dict, scan_cols: dict,
                         session: Session):
        """(alias, table, upload bytes, padded rows) of the one scan a
        resident-or-stream verdict is about, or None where the plan is
        not one paging can run (or streaming is off, or the table
        empty)."""
        if session.vars.get("streaming", "auto") == "off":
            return None
        if int(self.settings.get("sql.exec.hbm_budget_bytes")) <= 0:
            return None
        if not can_stream(node):
            # dist_analyze accepts more shapes (e.g. hash GROUP BY)
            # than paging can compile; never pick those
            return None
        d = dist_analyze(node)
        if not d.ok or len(d.sharded) != 1:
            return None
        alias = next(iter(d.sharded))
        tname = scan_aliases[alias]
        if self.store.table(tname).row_count == 0:
            return None
        # the resident upload this decision weighs would narrow its
        # int32-provable columns UNLESS the scan feeds a join
        # (_set_scan_narrowing keeps probe spines wide) — charging
        # int64 width for narrowed columns inflates the estimate ~2x
        # and streams tables that actually fit
        cols = scan_cols.get(alias)
        narrow = (frozenset() if _has_join(node)
                  else self.narrow32_cols(tname, cols))
        # the working set a resident execution would REALLY upload:
        # zone-surviving chunks when the whole table is over budget
        # (selective scans stop escalating to paging unnecessarily)
        eff_bytes, eff_rows = self._effective_table_bytes(
            node, alias, tname, cols, narrow=narrow)
        return alias, tname, eff_bytes, self._row_bucket(eff_rows)

    def placement_model(self, node, session: Session, eff_bytes: int,
                        padded: int) -> int:
        """The working set the resident-or-stream verdict weighs: the
        upload plus _agg_temp_bytes. Goes onto the open `plan` span
        (tag `model_bytes`) and raises the gauge
        ``sql.exec.placement.model_bytes.max``. Evaluated where the
        verdict needs it and where a plan is compiled
        (note_placement_model), not on every plan-cache hit."""
        model_bytes = eff_bytes + self._agg_temp_bytes(node, session,
                                                       padded)
        _trc.tag(model_bytes=model_bytes)
        self._placement_model_max = max(self._placement_model_max,
                                        model_bytes)
        return model_bytes

    def note_placement_model(self, node, scan_aliases: dict,
                             scan_cols: dict, session: Session) -> None:
        """placement_model for a plan about to be compiled resident:
        the statement's span and the gauge carry the model even where
        the bound above settled the verdict."""
        scan = self._streamable_scan(node, scan_aliases, scan_cols,
                                     session)
        if scan is not None:
            self.placement_model(node, session, *scan[2:])

    def _agg_temp_bytes(self, node, session: Session, padded: int) -> int:
        """Device bytes the plan's aggregation allocates beside its
        `padded`-row input, by the path it will compile onto.

        Large-G kernel (a dense GROUP BY for which compile.large_
        kernel_eligible says what the compile will be told): the
        32-bit words the kernel is handed and its accumulator tiles
        (compile.large_kernel_bytes). Measured on
        the v5e at TPC-H SF10, 2^26 rows, Q1 (my chip run, PR 28,
        PERF.md): 3.0 GiB of operand words modelled, 3.375 GiB of
        temporaries reserved by the loaded programs beside a 3.19 GiB
        upload, where the scatter term below reads 8.0 GiB.

        Anything else (hash GROUP BY, ungrouped or tiny aggregates,
        kernels off): XLA's segment reductions, which materialize
        about two n-length 64-bit temporaries an aggregate at once
        (16 bytes a row an aggregate; the figure comes from Q1 on the
        scatter path at 2^27 rows, ~12 GB of HLO temporaries, a
        reading older than the v5e runs and not repeated there), so a
        table that "fits" can still run out at compile time without
        this term."""
        from .compile import large_kernel_bytes, large_kernel_eligible
        agg = _root_aggregate(node)
        if agg is not None:
            # graftlint: waive[plan-key-completeness] the verdict this
            # feeds (`stream`) is a key element, and so is the var
            pallas = session.vars.get("pallas_groupagg", "auto")
            params = ExecParams(
                pallas_groupagg=self._pallas_mode(pallas),
                pallas_interpret=self._pallas_interpret())
            if large_kernel_eligible(agg, padded, params):
                return large_kernel_bytes(agg, padded)
        return 16 * _count_aggs(node) * padded

    @staticmethod
    def _pallas_mode(pallas) -> str:
        """A value of session var pallas_groupagg as auto | off: the
        spellings that once opted in further (`on`, True) read as
        auto, the kernel enabled inside its exact envelope; False and
        anything unrecognized mean off."""
        on = str(pallas).lower() in ("auto", "on", "true")
        return "auto" if on else "off"

    def _page_rows(self, session: Session) -> int:
        """Session page size rounded UP to a shape-ladder bucket: page
        shapes feed the same bucket-padded programs as resident
        uploads and spill partitions (exec/coldstart.ShapeLadder), so
        an off-ladder SET streaming_page_rows would give the tail page
        a shape no other page shares and recompile per page."""
        return self._row_bucket(
            int(session.vars.get("streaming_page_rows", 1 << 21)))

    # -- out-of-core spill tier (exec/spill.py) -----------------------------
    def _spill_decision(self, node, scan_aliases: dict, scan_cols: dict,
                        session: Session, meta):
        """Third verdict of the four-way plan placement (resident |
        stream-scan | spill-join | spill-sort): hand the plan to the
        out-of-core tier when the working set cannot fit the device
        budget any other way. ``SET spill = auto|on|off`` gates it:
        auto spills only when the resident/stream paths would blow
        ``sql.exec.hbm_budget_bytes``, on forces every eligible shape
        (tests/bench), off disables (the A/B lever). Returns a
        spill.SpillPlan or None."""
        mode = session.vars.get("spill", "auto")
        if mode == "off":
            return None
        budget = int(self.settings.get("sql.exec.hbm_budget_bytes"))
        if budget <= 0:
            return None
        page_rows = self._page_rows(session)
        sp = self._spill_join_decision(node, scan_aliases, scan_cols,
                                       mode, budget, page_rows)
        if sp is not None:
            return sp
        return self._spill_sort_decision(node, scan_aliases, scan_cols,
                                         meta, mode, budget, page_rows)

    def _spill_join_decision(self, node, scan_aliases: dict,
                             scan_cols: dict, mode: str, budget: int,
                             page_rows: int):
        """Partitioned-external-hash-join eligibility + trigger.

        Shape: a streamable aggregate over a join spine (the same
        can_stream + single-sharded-alias contract the stream-scan
        path uses — the probe pages through the device either way),
        where some build side is a plain Scan joined on raw stored
        int-family keys on BOTH sides. STRING keys are out: their
        stored values are per-table dictionary codes, so one side
        compares through a code remap and raw-code partitioning would
        split equal keys. Int-family keys are safe regardless of
        width: the device compares values (int32 uploads upcast), and
        equal values cast to equal int64 bits, so both sides of an
        equal pair hash to the same partition. Inner/left only — a
        build row unmatched in ITS partition is genuinely unmatched.

        Trigger (auto): the stream-scan path uploads every build
        whole, so its runtime floor is sum(build uploads) + two
        in-flight probe pages + per-page aggregation temps (the
        streamed compile aggregates page-at-a-time, so temps scale
        with the page, not the table); spill when that floor exceeds
        the budget (the resident path needs strictly more). The
        LARGEST eligible build spills; the partition count doubles
        until one resident partition fits what the budget leaves."""
        from .spill import SpillPlan
        if not _has_join(node) or not can_stream(node):
            return None
        d = dist_analyze(node)
        if not d.ok or len(d.sharded) != 1:
            return None
        alias = next(iter(d.sharded))
        tname = scan_aliases[alias]
        ptd = self.store.table(tname)
        if ptd.row_count == 0:
            return None
        probe_scan = None
        cands = []  # (build_bytes, join, build_scan, pkeys, bkeys)
        stack = [node]
        while stack:
            n = stack.pop()
            if isinstance(n, P.Scan) and n.alias == alias:
                probe_scan = n
            if (isinstance(n, P.HashJoin)
                    and n.join_type in ("inner", "left")
                    and isinstance(n.right, P.Scan)
                    and alias in _collect_scans(n.left)):
                cands.append(n)
            for attr in ("child", "left", "right"):
                c = getattr(n, attr, None)
                if c is not None:
                    stack.append(c)
        if probe_scan is None:
            return None
        joins = []
        for j in cands:
            b = j.right
            pkeys = tuple(probe_scan.columns.get(k) for k in j.left_keys)
            bkeys = tuple(b.columns.get(k) for k in j.right_keys)
            if None in pkeys or None in bkeys:
                continue  # a computed/remapped key: raw partitioning
                # would not match the device's comparison space
            if not all(self._raw_partitionable(t, ks) for t, ks in
                       ((tname, pkeys), (b.table, bkeys))):
                continue
            btd = self.store.table(b.table)
            if btd.row_count == 0:
                continue
            bb = self._table_device_bytes(btd,
                                          scan_cols.get(b.alias))
            joins.append((bb, j, b, pkeys, bkeys))
        if not joins:
            return None
        n_aggs = _count_aggs(node)
        page_padded = self._row_bucket(page_rows)
        temp_bytes = 2 * 16 * n_aggs * page_padded
        page_bytes = 2 * self._page_device_bytes(
            ptd, scan_cols.get(alias), page_rows)  # depth-2 prefetch
        # builds charge what they will actually upload (the scans loop
        # prunes zone-failing chunks from over-budget builds), so a
        # selective build no longer forces the spill tier
        build_total = sum(
            self._effective_table_bytes(node, a, t, scan_cols.get(a))[0]
            for a, t in scan_aliases.items() if a != alias)
        if (mode == "auto"
                and build_total + temp_bytes + page_bytes <= budget):
            return None
        des_bytes, j, b, pkeys, bkeys = max(joins, key=lambda x: x[0])
        # des_bytes is the FULL build (partitions gather every build
        # row); build_total is effective, so clamp the residual
        avail = max(budget - max(build_total - des_bytes, 0)
                    - temp_bytes - page_bytes, 1)
        nparts = 2
        while (nparts < self.MAX_SPILL_PARTITIONS
               and des_bytes // nparts > avail):
            nparts *= 2
        return SpillPlan(kind="join", alias=alias, table=tname,
                         page_rows=page_rows, build_alias=b.alias,
                         build_table=b.table, probe_keys=pkeys,
                         build_keys=bkeys, nparts=nparts)

    def _raw_partitionable(self, tname: str, stored_keys) -> bool:
        """May the spill partitioner hash these stored columns raw?
        Int-family only (incl. bool); STRING dictionary codes and
        FLOAT (-0.0 == 0.0 with different bits) partition wrong."""
        from ..sql.types import Family
        td = self.store.table(tname)
        by_name = {c.name: c for c in td.schema.columns}
        for k in stored_keys:
            col = by_name.get(k)
            if col is None or col.type.family == Family.STRING:
                return False
            if np.dtype(col.type.np_dtype).kind not in "iub":
                return False
        return True

    def _spill_sort_decision(self, node, scan_aliases: dict,
                             scan_cols: dict, meta, mode: str,
                             budget: int, page_rows: int):
        """External-merge-sort eligibility + trigger: Limit?/Sort over
        a join-free single-scan spine (can_spill_sort) whose every
        key is normalized-encodable — the uint64 lanes double as the
        device run keys AND the host merge keys, so the merged order
        is byte-for-byte the device's. Auto triggers when the pruned
        resident upload + sort temporaries (perm + lane per row)
        would blow the budget."""
        from .spill import SpillPlan
        if not can_spill_sort(node) or len(scan_aliases) != 1:
            return None
        from ..sql.types import Family
        alias, tname = next(iter(scan_aliases.items()))
        td = self.store.table(tname)
        if td.row_count == 0:
            return None
        limit_node = None
        n = node
        if isinstance(n, P.Limit):
            limit_node, n = n, n.child
        sort_node = n
        names = list(meta.names)
        for key in sort_node.keys:
            kn = key[0]
            if kn not in names:
                return None  # hidden key: type unknowable here
            fam = meta.types[names.index(kn)].family
            if fam == Family.STRING:
                if meta.dictionaries.get(kn) is None:
                    return None  # no rank table -> unencodable
            elif fam not in (Family.INT, Family.DECIMAL, Family.DATE,
                             Family.TIMESTAMP, Family.BOOL,
                             Family.FLOAT):
                return None
        cols = scan_cols.get(alias)
        if mode == "auto":
            eff_bytes, eff_rows = self._effective_table_bytes(
                node, alias, tname, cols,
                narrow=self.narrow32_cols(tname, cols))
            if eff_bytes + 24 * self._row_bucket(eff_rows) <= budget:
                return None
        return SpillPlan(
            kind="sort", alias=alias, table=tname, page_rows=page_rows,
            sort_keys=tuple(
                (k[0], bool(k[1]), (k[2] if len(k) > 2 else None))
                for k in sort_node.keys),
            limit=(limit_node.limit
                   if limit_node is not None
                   and limit_node.limit is not None else -1),
            offset=((limit_node.offset or 0)
                    if limit_node is not None else 0))

    def _page_device_bytes(self, td, cols, page_rows: int) -> int:
        """Device bytes of one streamed page of this table's pruned
        column set (PageSource.page_bytes, computed pre-source)."""
        total = 16 * page_rows
        for col in td.schema.columns:
            if cols is not None and col.name not in cols:
                continue
            w = np.dtype(col.type.np_dtype).itemsize
            total += (w + 1) * page_rows
        return total

    def stream_verdict(self, sql: str, session: Session | None = None
                       ) -> str:
        """Which placement tier would this SELECT execute on?
        "distributed" | "spill-join" | "spill-sort" | "stream-scan" |
        "resident" — the planner's four-way verdict plus the mesh
        plane, exposed for eligibility tests and EXPLAIN-style
        introspection (no execution, no uploads)."""
        session = session or self.session()
        stmt = self._parse_cached(sql)
        node, meta = self._plan(stmt, session)
        from .stmtutil import _collect_scan_columns
        scan_aliases = _collect_scans(node)
        scan_cols = _collect_scan_columns(node)
        if self._dist_decision(node, session) is not None:
            return "distributed"
        sp = self._spill_decision(node, scan_aliases, scan_cols,
                                  session, meta)
        if sp is not None:
            return f"spill-{sp.kind}"
        if self._stream_decision(node, scan_aliases, scan_cols,
                                 session) is not None:
            return "stream-scan"
        return "resident"

    def _table_device_bytes(self, td, cols,
                            narrow: frozenset = frozenset()) -> int:
        """Device bytes a pruned upload of this table would take.
        Columns in ``narrow`` upload as int32 (narrow32_cols), so they
        charge 4+1 bytes per row, not the stored 8+1."""
        n = td.row_count
        padded = self._row_bucket(n)
        total = 16 * padded  # the MVCC pair: four 32-bit word columns
        for col in td.schema.columns:
            if cols is not None and col.name not in cols:
                continue
            w = (4 if col.name in narrow
                 else np.dtype(col.type.np_dtype).itemsize)
            total += (w + 1) * padded
        return total

    def _chunks_device_bytes(self, td, chunks, cols,
                             narrow: frozenset = frozenset()) -> int:
        """_table_device_bytes over a chunk subset (+ any open rows)."""
        n = sum(c.n for c in chunks) + len(td.open_ts)
        padded = self._row_bucket(n)
        total = 16 * padded
        for col in td.schema.columns:
            if cols is not None and col.name not in cols:
                continue
            w = (4 if col.name in narrow
                 else np.dtype(col.type.np_dtype).itemsize)
            total += (w + 1) * padded
        return total

    def _zone_surviving_chunks(self, node, alias, tname):
        """(surviving chunks, compiled preds) for the plan's pushed-
        down predicates over `alias`, judged against seal-time zones
        and blooms — the same per-chunk verdict the streamed page
        source renders, evaluated once at decision/upload time. Empty
        preds means nothing was zone-judgeable (keep == all chunks)."""
        from .stream import extract_zone_preds
        td = self.store.table(tname)
        preds = extract_zone_preds(node, alias)
        if not preds:
            return list(td.chunks), ()
        keep = []
        for c in td.chunks:
            ok = True
            for p in preds:
                if p.col is None:
                    if not p.check(None, None, 0, 0):
                        ok = False
                        break
                    continue
                lo, hi, nulls, nvalid = c.zone(p.col)
                if not p.check(lo, hi, nulls, nvalid):
                    ok = False
                    break
                if p.member is not None \
                        and not p.member.chunk_ok(c, p.col):
                    ok = False
                    break
            if ok:
                keep.append(c)
        return keep, preds

    def _effective_table_bytes(self, node, alias, tname, cols,
                               narrow: frozenset = frozenset()
                               ) -> tuple[int, int]:
        """(device bytes, rows) the upload of this scan will ACTUALLY
        take: the whole table when it fits the budget (the cached
        resident path), else the zone-surviving chunk subset — exactly
        what _maybe_pruned_upload ships. Sizing the stream/spill
        verdicts from this instead of the declared table keeps
        selective scans from escalating to paging/spill when their
        post-filter working set fits."""
        td = self.store.table(tname)
        full = self._table_device_bytes(td, cols, narrow=narrow)
        budget = int(self.settings.get("sql.exec.hbm_budget_bytes"))
        if budget <= 0 or full <= budget:
            return full, td.row_count
        keep, preds = self._zone_surviving_chunks(node, alias, tname)
        if not preds or len(keep) == len(td.chunks):
            return full, td.row_count
        rows = sum(c.n for c in keep) + len(td.open_ts)
        return (self._chunks_device_bytes(td, keep, cols,
                                          narrow=narrow), rows)

    def _maybe_pruned_upload(self, node, alias, tname, cols,
                             do_narrow: bool):
        """UNCACHED upload of only the zone-surviving chunks, used
        when the whole table would blow the HBM budget but the scan's
        pushed-down predicates prune chunks host-side — the resident
        analogue of streamed page skipping, with the same correctness
        contract (a dropped chunk's rows fail the predicate for every
        row version, so the device filter would drop them anyway).
        None -> caller keeps the cached whole-table path."""
        budget = int(self.settings.get("sql.exec.hbm_budget_bytes"))
        if budget <= 0:
            return None
        td = self.store.table(tname)
        narrow = (self.narrow32_cols(tname, cols) if do_narrow
                  else frozenset())
        if self._table_device_bytes(td, cols, narrow=narrow) <= budget:
            return None
        if td.open_ts:
            self.store.seal(tname)
        keep, preds = self._zone_surviving_chunks(node, alias, tname)
        if not preds or len(keep) == len(td.chunks):
            return None
        row_w = 16 + sum(
            np.dtype(c.type.np_dtype).itemsize + 1
            for c in td.schema.columns
            if cols is None or c.name in cols)
        dropped_rows = sum(c.n for c in td.chunks) \
            - sum(c.n for c in keep)
        self.metrics.counter(
            "exec.skip.predicate.chunks",
            "over-budget resident scan chunks pruned host-side by "
            "pushed-down zone predicates").inc(
                len(td.chunks) - len(keep))
        self.metrics.counter(
            "exec.skip.predicate.bytes",
            "host->device bytes avoided by predicate chunk pruning"
        ).inc(row_w * dropped_rows)
        return self._batch_from_chunks(td, keep, cols, narrow=narrow)

    def _scan_survival_frac(self, node, alias, tname) -> float:
        """Estimated post-filter fraction of a scan's rows: sketch-
        stats selectivity of its pushed-down predicates (scan filter
        plus Filter nodes separated only by Filter/Compact, the
        extract_zone_preds discipline). 1.0 when nothing is judgeable;
        floored at 1/64 so footprint heuristics never size to zero."""
        from ..sql import stats as S
        from .stream import _find_chain
        td = self.store.table(tname)
        if td.row_count == 0:
            return 1.0
        try:
            st = self.store.sketch_stats(tname)
        except Exception:
            return 1.0
        chain = _find_chain(node, alias)
        if chain is None:
            return 1.0
        sel = 1.0
        scan = chain[0]
        if scan.filter is not None:
            sel *= S._pred_selectivity(scan.filter, st)
        for anc in chain[1:]:
            if isinstance(anc, P.Compact):
                continue
            if isinstance(anc, P.Filter):
                if anc.pred is not None:
                    sel *= S._pred_selectivity(anc.pred, st)
                continue
            break
        return float(min(1.0, max(sel, 1.0 / 64.0)))

    def _page_source(self, tname: str, cols, page_rows: int,
                     zone_preds=(), read_ts=None) -> PageSource:
        """One-time per-execution setup for streamed paging: seal open
        rows ONCE here (not per page), snapshot the chunk list, and
        hand the prefix-offset assembler its zone predicates plus the
        read timestamp (chunk MVCC-window skipping)."""
        td = self.store.table(tname)
        if td.open_ts:
            self.store.seal(tname)
        return PageSource(td, cols, page_rows, zone_preds=zone_preds,
                          metrics=self.metrics, read_ts=read_ts)

    def _stream_pages(self, tname: str, cols, page_rows: int,
                      zone_preds=(), pipeline: bool = True,
                      read_ts=None):
        """Iterator of fixed-shape device pages of a table's chunks,
        padded to page_rows with never-visible rows so one XLA program
        serves every page. With ``pipeline``, a bounded background
        worker assembles+uploads page i+1 while the caller's device
        work on page i runs; zone-pruned pages never leave the host."""
        src = self._page_source(tname, cols, page_rows, zone_preds,
                                read_ts=read_ts)
        if not pipeline:
            it = src.pages()
        else:
            it = stream_prefetch(
                src.pages(),
                stall_hist=self.metrics.histogram(
                    "exec.stream.prefetch_stall_seconds",
                    "consumer wait per streamed page (0 when the "
                    "prefetch pipeline is ahead of the device)"))
        metered = self._metered_pages(it, tname, src.page_bytes,
                                      stalls=pipeline)
        # the stream's transient working window (the page computing +
        # the one the prefetch worker holds) charges the unified
        # movement budget for its lifetime — best-effort, so a tight
        # budget degrades to observable overcommit, never a failure
        window = (2 if pipeline else 1) * src.page_bytes

        def leased():
            with self.movement.soft_lease("page", window):
                yield from metered
        return leased()

    @staticmethod
    def _metered_pages(it, tname: str, page_bytes: int,
                       stalls: bool = False):
        """Statement-profile metering wrapper around a page iterator:
        runs on the CONSUMER thread (where the statement's thread-local
        sink lives — the prefetch worker would miss it). With a
        pipeline upstream the wait for ``next`` is consumer stall; the
        synchronous path's wait is assembly+upload work, not stall."""
        inner = iter(it)
        label = f"stream:{tname}"
        try:
            while True:
                t0 = _time.monotonic()
                try:
                    b = next(inner)
                except StopIteration:
                    return
                sink = _prof.current()
                if sink is not None:
                    sink.note(label, batches=1, rows=int(b.n),
                              bytes_uploaded=page_bytes,
                              stall_seconds=((_time.monotonic() - t0)
                                             if stalls else 0.0))
                yield b
        finally:
            close = getattr(inner, "close", None)
            if close is not None:
                close()

    def _filtered_scan_batch(self, tname: str, filters, read_ts):
        """Remote-side application of gateway-shipped join-filter
        frames (distsql/node.py): drop whole chunks whose key set
        cannot match before anything serializes or uploads. Returns
        None when nothing prunes (the caller keeps its cached
        device-table path); otherwise an UNCACHED wide upload of the
        surviving chunks — correctness is untouched because a dropped
        chunk's rows would have been dropped by the inner/semi join
        (or by MVCC) on device anyway."""
        td = self.store.table(tname)
        if td.open_ts:
            self.store.seal(tname)
        row_w = 16 + sum(
            np.dtype(c.type.np_dtype).itemsize + 1
            for c in td.schema.columns)
        keep, dropped, dropped_bytes = [], 0, 0
        for c in td.chunks:
            ok = True
            if read_ts is not None:
                ts_min, del_max = c.mvcc_window()
                ok = ts_min <= read_ts < del_max
            if ok:
                ok = all(f.chunk_ok(c, f.col) for f in filters)
            if ok:
                keep.append(c)
            else:
                dropped += 1
                dropped_bytes += row_w * c.n
        if dropped == 0:
            return None
        self.metrics.counter(
            "exec.skip.joinfilter.chunks",
            "remote scan chunks pruned host-side by a gateway-shipped "
            "join-filter frame (DistSQL)").inc(dropped)
        self.metrics.counter(
            "exec.skip.joinfilter.bytes",
            "host->device bytes avoided by join-induced skipping"
        ).inc(dropped_bytes)
        return self._batch_from_chunks(td, keep)

    # -- device table cache --------------------------------------------------
    def _evict_device(self, key) -> None:
        with self._device_lock:
            self._device_tables.pop(key, None)
            self.movement.release_resident(key)
        # a statement-text cache entry must not keep the batch alive
        self._text_plans_drop("evicted", table=key[0])

    def drop_device_cache(self) -> None:
        """Evict every resident table upload AND release its memory
        reservation (a raw _device_tables.clear() would leak the
        monitor's accounting)."""
        for k in list(self._device_tables):
            self._evict_device(k)

    def _device_table(self, name: str, placement: str = "single",
                      cols: frozenset | None = None,
                      narrow: bool = True, mesh=None) -> ColumnBatch:
        """Resident device copy of ``name`` — cached, or uploaded now.

        The cache lock guards only dict state. The expensive part
        (host assembly + jax.device_put, tens of ms for a large
        table) runs OUTSIDE ``_device_lock`` behind a per-(table,
        placement) in-flight event, so concurrent statements needing
        OTHER tables — or a cached hit on this one — never convoy
        behind a PCIe transfer, and two statements needing the SAME
        cold table produce one upload, not two."""
        # the target mesh is part of the upload's identity: sub-mesh
        # dispatch (parallel/mesh.py MeshPool) shards/replicates the
        # same table over different device subsets, and a batch placed
        # on sub-mesh A must never serve a program compiled for B
        if placement == "single":
            mesh, devids = None, ()
        else:
            mesh = mesh if mesh is not None else self.mesh
            devids = tuple(int(d.id) for d in mesh.devices.flat)
        flight = (name, placement, devids, narrow)
        while True:
            with self._device_lock:
                td = self.store.table(name)
                hit = self._device_lookup_locked(
                    name, td.generation, placement, devids, narrow,
                    cols)
                if hit is not None:
                    return _own_columns(hit, cols)
                ev = self._device_inflight.get(flight)
                if ev is None:
                    ev = threading.Event()
                    self._device_inflight[flight] = ev
                    break  # this thread owns the upload
            # another thread is uploading this table: wait without the
            # lock, then retry the lookup (the timeout only bounds the
            # re-check; a failed owner clears the event in its finally
            # and the retrier becomes the new owner)
            ev.wait(timeout=5.0)
        try:
            with _trc.span("upload", table=name):
                return self._device_upload(name, td, placement, cols,
                                           narrow, mesh, devids)
        finally:
            with self._device_lock:
                self._device_inflight.pop(flight, None)
            ev.set()

    def _device_lookup_locked(self, name: str, generation,
                              placement: str, devids: tuple,
                              narrow: bool,
                              cols: frozenset | None):
        """Cache probe; caller holds ``_device_lock``. A cached upload
        with a SUPERSET of the needed columns serves this scan
        directly (scans read columns by name); this keeps one resident
        copy per table instead of one per column set. The narrow flag
        is part of the identity: a wide consumer (DistSQL workers
        compile without the upcast) must never be served an
        int32-narrowed upload."""
        for k, v in self._device_tables.items():
            if (k[0] == name and k[1] == generation
                    and k[2] == placement and k[4] == narrow
                    and k[5] == devids
                    and (k[3] is None
                         or (cols is not None and cols <= k[3]))):
                return v
        return None

    def _device_upload(self, name: str, td, placement: str,
                       cols: frozenset | None, narrow: bool, mesh,
                       devids: tuple) -> ColumnBatch:
        """Assemble and upload one resident table copy. Runs with NO
        lock held (graftlint blocking-under-lock: the original
        held ``_device_lock`` across seal + host assembly +
        jax.device_put, serializing every concurrent scan behind one
        upload); only the final cache insert re-takes the lock."""
        # evict stale generations of this table
        with self._device_lock:
            stale = [k for k in self._device_tables if k[0] == name
                     and k[1] != td.generation]
        for k in stale:
            self._evict_device(k)
        if td.open_ts:
            self.store.seal(name)
        key = (name, td.generation, placement, cols, narrow, devids)
        # account BEFORE upload; replication costs a copy per device.
        # The reservation uses the same narrow set the upload will,
        # so narrowed tables no longer reserve ~2x their real bytes
        narrow_set = (self.narrow32_cols(name, cols) if narrow
                      else frozenset())
        nbytes = self._table_device_bytes(td, cols, narrow=narrow_set)
        if placement == "replicated" and mesh is not None:
            nbytes *= mesh.size
        if placement != "single" and mesh is not None:
            from ..parallel import multihost
            if multihost.num_hosts() > 1:
                # resident uploads are strictly host-local on a pod:
                # device_put of host arrays cannot address another
                # process's devices, and silently trying yields an XLA
                # crash deep in the upload. The cross-host dimension
                # of a scan is the distsql merge tree's job (each host
                # owns its shard), never a cross-DCN placement here.
                local = set(jax.local_devices())
                if any(d not in local for d in mesh.devices.flat):
                    raise EngineError(
                        f"table {name!r}: resident upload targets a "
                        "mesh with non-addressable (remote-host) "
                        "devices; use the host-local mesh "
                        "(parallel.mesh.pod_mesh degrades to it)")
        self.movement.reserve_resident(key, nbytes)
        try:
            # host arrays go to device_put WITH the target sharding,
            # so each chip receives only its shard; staging the whole
            # table on the default device first cannot load a table
            # that needs the mesh's combined HBM
            sharding = None
            if placement == "sharded":
                sharding = meshmod.row_sharding(mesh)
            elif placement == "replicated":
                sharding = meshmod.replicated(mesh)
            b = self._batch_from_chunks(td, td.chunks, cols,
                                        narrow=narrow_set,
                                        sharding=sharding)
        except BaseException:
            self.movement.release_resident(key)
            raise
        # drop now-redundant strict-subset uploads of the same table
        with self._device_lock:
            subsets = [k for k in self._device_tables
                       if k[0] == name and k[1] == td.generation
                       and k[2] == placement and k[5] == devids
                       and k[3] is not None
                       and (cols is None or k[3] < cols)]
        for k in subsets:
            self._evict_device(k)
        with self._device_lock:
            self._device_tables[key] = b
        self.metrics.counter("sql.device.table_uploads",
                             "resident table uploads to HBM").inc()
        self.metrics.counter(
            "sql.device.upload.bytes",
            "host->device bytes moved by table uploads").inc(nbytes)
        _prof.note(f"upload:{name}", batches=1, rows=td.row_count,
                   bytes_uploaded=nbytes)
        _trc.tag(bytes=nbytes)   # on _device_table's `upload` span
        return b

    def narrow32_cols(self, name: str,
                      cols: frozenset | None = None) -> frozenset:
        """Stored int64 columns of `name` whose ALL-VERSIONS value
        range fits int32 (generation-cached store probe): these upload
        to HBM as int32 and the compiled scan upcasts them back —
        identical program semantics, half the HBM bytes, and none of
        the software-emulated int64 limb ops on the first touch
        (int64 is emulated on TPU; Q6's scan measured ~2x from this).
        NULL lanes may wrap when narrowed — they are masked by
        validity everywhere downstream, same as any garbage lane."""
        from ..sql.types import Family
        td = self.store.table(name)
        out = set()
        for col in td.schema.columns:
            cn = col.name
            if cols is not None and cn not in cols:
                continue
            if col.type.family not in (Family.INT, Family.DECIMAL,
                                       Family.DATE, Family.TIMESTAMP):
                continue
            if np.dtype(col.type.np_dtype) != np.dtype(np.int64):
                continue
            try:
                r = self.store.key_int_range(name, cn)
            except (KeyError, TypeError):
                continue
            if r is None:
                continue
            lo, hi, _n = r
            if -(2 ** 31) < lo and hi < 2 ** 31 - 1:
                out.add(cn)
        return frozenset(out)

    def _batch_from_chunks(self, td, chunks: list,
                           prune: frozenset | None = None,
                           narrow: frozenset = frozenset(),
                           sharding=None) -> ColumnBatch:
        """Concatenate chunks, pad to a power-of-two row bucket, and
        upload as a device-resident ColumnBatch with the MVCC word
        columns.
        With ``prune`` set, only those stored columns upload (the scan
        projection; HBM is the scarce resource the reference's
        needed-columns fetch logic protects, cfetcher.go:668).
        Columns in ``narrow`` upload as int32 (see narrow32_cols).
        ``sharding`` places every column straight from the host
        (None = the default device)."""
        cols: dict[str, np.ndarray] = {}
        valid: dict[str, np.ndarray] = {}
        n = sum(c.n for c in chunks)
        padded = self._row_bucket(n)

        def gather(parts, dtype) -> np.ndarray:
            """The chunks' arrays in one padded array of `dtype`, each
            written once into its place: a 2^26-row column is held
            once on the host, not as a concatenation, a cast and a
            padded copy of it."""
            out = np.empty(padded, dtype=dtype)
            at = 0
            for part in parts:
                out[at:at + len(part)] = part
                at += len(part)
            out[at:] = 0
            return out

        for col in td.schema.columns:
            cn = col.name
            if prune is not None and cn not in prune:
                continue
            parts = [c.data[cn] for c in chunks]
            cols[cn] = gather(
                parts, np.int32 if cn in narrow
                else parts[0].dtype if parts else col.type.np_dtype)
            if any(c.zone(cn)[2] for c in chunks):
                # all-valid masks regenerate on device (ones) for free
                # instead of paying PCIe for a constant
                valid[cn] = gather([c.valid[cn] for c in chunks], bool)
        # the MVCC pair as 32-bit words (ops/batch.py), each chunk's
        # written once into its place; padding rows are never visible
        words = alloc_mvcc_words(padded)
        at = 0
        for c in chunks:
            put_mvcc_words(words, at, c.mvcc_ts, c.mvcc_del)
            at += c.n
        fill_mvcc_words(words, at, padded, NEVER_TS, 0)
        cols.update(words)
        # cols/valid hold fresh arrays built
        # above, with no later writes. All-valid masks and sel are
        # created in place under the same sharding, so nothing of the
        # batch lands whole on one device first.

        def ones():
            return jnp.ones((padded,), jnp.bool_, device=sharding)

        # no scan reads a word column's validity: the four share one
        # mask, so the pair costs no more of HBM in words than in int64
        words_valid = ones()
        return ColumnBatch.from_dict(
            {k: jax.device_put(v, sharding) for k, v in cols.items()},
            {k: (jax.device_put(valid[k], sharding) if k in valid
                 else words_valid if k in words else ones())
             for k in cols},
            sel=ones())

    def _overlay_batch(self, name: str, effects: list,
                       read_ts: Timestamp) -> ColumnBatch:
        """Uncached device snapshot of committed chunks + this txn's
        buffered effects (read-your-own-writes)."""
        td = self.store.table(name)
        chunks = self._overlay_chunks(name, effects, read_ts)
        return self._batch_from_chunks(td, chunks)

    # -- result materialization ---------------------------------------------

    _SENTINELS = _SENTINEL_PAIRS

    def _materialize(self, out: ColumnBatch, meta: P.OutputMeta) -> Result:
        """Decode a device result batch into host rows.

        Transfer discipline: sentinel flags reduce to scalars on
        device and ride the same packed pull as the data — one
        transfer for small batches; for wide (join-expanded) batches,
        one pull for (sel + flags), then one pull of the live rows
        gathered on device."""
        from ..ops.batch import _SMALL_PULL, flag_any, pull_arrays, \
            pull_batch_columns
        # the span's own time by stage: `flags` up to the pull (the
        # sentinel programs, the column lists), then what
        # pull_batch_columns marks behind it (`gather`, `assemble`)
        _trc.stage("flags")
        sent = [(n, exc) for n, exc in self._SENTINELS if out.has(n)]
        flags_dev = [flag_any(out.col(n)) for n, _ in sent]
        names = list(meta.names)
        if out.n <= _SMALL_PULL:
            pulled, flags = pull_batch_columns(out, names,
                                               extra=flags_dev)
            self._raise_sentinels(sent, flags)
        else:
            # sentinel flags ride the sel pull so an overflow raises
            # BEFORE the (possibly garbage-width) live gather runs
            first = pull_arrays([out.sel] + flags_dev)
            self._raise_sentinels(sent, first[1:])
            pulled, _ = pull_batch_columns(out, names,
                                           sel_np=first[0])
        # everything after the last pull: masks, dictionaries, rows
        with _trc.span("decode"):
            host = {c: np.ma.masked_array(d, mask=~v)
                    for c, (d, v) in pulled.items()}
            res = Result(names=names, types=list(meta.types))
            cols = []
            for name, ty in zip(names, meta.types):
                arr = host[name]
                d = meta.dictionaries.get(name)
                cols.append(_decode_column(arr, ty, d))
            res.rows = list(zip(*cols)) if cols else []
            return res

    @staticmethod
    def _raise_sentinels(sent, flags) -> None:
        for (name, exc), f in zip(sent, flags):
            if bool(f):
                raise exc()

