"""Out-of-core spill tier: partitioned external hash join and
external merge sort over the streamed plane.

The streamed data plane (exec/stream.py) pages beyond-HBM *scans*
through the device, but two shapes still demanded full residency:

  joins   build sides upload whole, so a join whose build exceeds
          ``sql.exec.hbm_budget_bytes`` dies with a MemoryQuotaError
          at ``hbm.reserve`` before a single row moves;
  sorts   Limit?/Sort plans have no aggregate to page into partial
          states, so ``can_stream`` rejects them outright.

This module supplies both missing tiers (Theseus' memory-tier plane,
PAPERS.md — "optimized for efficient data movement"; Tailwind frames
the upload/compute overlap):

  spill-join   radix-partition BOTH sides host-side by a hash of the
               join key (ops/join.radix_partition_ids over the sealed
               chunk snapshots), then per partition upload ONE
               resident build batch and stream the matching probe
               partition's pages against it. Equal keys share a
               partition, so per-(partition, page) aggregate partials
               combine with the UNCHANGED streaming combine algebra —
               which is also why spilled partials stay mergeable
               across the DistSQL plane. Partition upload overlaps
               device probe via the same depth-2 prefetch() worker
               the scan plane uses.
  spill-sort   run the Sort's child over each streamed page, sort the
               page on device by its normalized uint64 key lanes
               (ops/sortkey.py — the radix-run keys), cut each run to
               LIMIT+OFFSET live rows, pull runs host-side, and merge
               them with one stable host lexsort over the lanes
               (sortkey.merge_lanes_host). Stable runs concatenated
               in row order + a stable merge reproduce byte-for-byte
               the permutation of one device sort over all rows.

The planner verdict (resident | stream-scan | spill-join |
spill-sort) is computed by scanplane._spill_decision and carried on
``Prepared.spill`` as a SpillPlan; ``SET spill = auto|on|off`` gates
it (auto spills only when the resident/stream paths would blow the
budget, on forces eligible shapes, off is the bench A/B arm).

exec.spill.* metrics account the tier: partitions/runs processed,
host->device bytes moved by spill uploads, executions, and the
upload/compute overlap evidence (worker busy seconds not covered by
consumer stalls).
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from ..ops import sortkey
from ..ops.batch import ColumnBatch, pull_arrays, read_ts_words
from ..ops.join import radix_partition_ids
from ..sql import plan as P
from .compile import (ExecError, RunContext, _normalized_lanes,
                      _sort_rank_tables, compile_plan)
from .stmtutil import _decode_column
from .stream import prefetch as stream_prefetch
from . import profile as _prof

# scanplane._stream_pages registers this histogram with the same help
# text; both paths feed it so "is the pipeline ahead of the device?"
# reads off one family regardless of tier
_STALL_HELP = ("consumer wait per streamed page (0 when the "
               "prefetch pipeline is ahead of the device)")


@dataclass(frozen=True)
class SpillPlan:
    """The planner's spill verdict, carried on Prepared.spill and
    hashed into the compiled-plan cache key (frozen => hashable)."""
    kind: str                # "join" | "sort"
    alias: str               # the paged scan's alias (probe / sorted)
    table: str
    page_rows: int
    # spill-join only
    build_alias: str = ""
    build_table: str = ""
    probe_keys: tuple = ()   # stored key column names, probe table
    build_keys: tuple = ()   # stored key column names, build table
    nparts: int = 0
    # spill-sort only
    sort_keys: tuple = ()    # ((name, desc, null_first|None), ...)
    limit: int = -1          # -1 = no LIMIT
    offset: int = 0


class _StallSum:
    """Accumulates consumer-stall seconds for the overlap metric while
    forwarding each observation to the shared stall histogram."""

    def __init__(self, hist=None):
        self.total = 0.0
        self.hist = hist

    def observe(self, v: float) -> None:
        self.total += v
        if self.hist is not None:
            self.hist.observe(v)


def _spill_metrics(metrics):
    return (
        metrics.counter(
            "exec.spill.partitions",
            "spill-tier units processed: join partitions swept + "
            "sort runs merged"),
        metrics.counter(
            "exec.spill.bytes",
            "host->device bytes moved by spill partition/run uploads"),
        metrics.counter(
            "exec.spill.rounds",
            "spill-tier executions (join partition sweeps + external "
            "merge sorts)"),
        metrics.counter(
            "exec.spill.upload_overlap_seconds",
            "seconds of partition/page assembly+upload hidden under "
            "device compute (worker busy time not surfacing as "
            "consumer stalls) — the prefetch-overlap evidence"),
    )


def _batch_bytes(src, n_rows: int) -> int:
    """Host->device bytes of one n_rows batch of src's columns (same
    accounting shape as PageSource.page_bytes)."""
    return n_rows * (16 + sum(d.itemsize + 1
                              for d in src.dtypes.values()))


def _host_key_cols(src, names):
    """Stored key columns + validity over the sealed chunk snapshot —
    the partitioner's host-side input. Deleted/invisible row versions
    partition too; they are masked by MVCC on device like any row."""
    cols, valids = [], []
    for cn in names:
        if src.chunks:
            d = np.concatenate([c.data[cn] for c in src.chunks])
            v = np.concatenate([c.valid[cn] for c in src.chunks])
        else:
            d = np.zeros(0, dtype=src.dtypes[cn])
            v = np.zeros(0, dtype=bool)
        cols.append(d)
        valids.append(v)
    return cols, valids


def host_page_iter(n_rows: int, cols: dict, page_rows: int):
    """Fixed-size host pages over a column dict — the spill tier's
    page discipline exposed for host→host movers (shard-lease
    rebalance streams ride this so a shard handoff's working set is
    bounded per page exactly like a spill partition upload). Yields
    ``(page_len, {col: slice})``; always yields at least one (possibly
    empty) page so empty shards still produce a schema-carrying
    frame."""
    page_rows = max(1, int(page_rows))
    if n_rows <= 0:
        yield 0, {c: v[:0] for c, v in cols.items()}
        return
    for lo in range(0, n_rows, page_rows):
        hi = min(n_rows, lo + page_rows)
        yield hi - lo, {c: v[lo:hi] for c, v in cols.items()}


def _partition_indices(pids: np.ndarray, nparts: int) -> list:
    """Global row indices per partition, ascending within each (stable
    argsort keeps row order), so chunk-run gather assembly applies."""
    order = np.argsort(pids, kind="stable")
    bounds = np.searchsorted(pids[order], np.arange(nparts + 1))
    return [order[bounds[p]:bounds[p + 1]] for p in range(nparts)]


# ---------------------------------------------------------------------------
# partitioned external hash join
# ---------------------------------------------------------------------------

def run_spill_join(engine, prep, read_ts: int) -> ColumnBatch:
    """Execute a spill-join Prepared at the int timestamp `read_ts`:
    sweep the partitions, combining per-(partition, page) aggregate
    partials, and return the device result batch (Prepared.run
    materializes it like any other).

    Correctness rests on two invariants: (a) equal join keys hash to
    the same partition on both sides, so every device match the
    resident hash_join would find happens in exactly one partition;
    (b) each probe row lands in exactly one (partition, page), so the
    streaming combine algebra — already exact over pages — stays
    exact over the partition sweep. Duplicate-key expansion and
    direct-address tables work unchanged per partition: a key's whole
    duplicate chain shares its partition."""
    sp: SpillPlan = prep.spill
    fns = prep.jfn
    tsv = read_ts_words(read_ts)
    m_parts, m_bytes, m_rounds, m_overlap = _spill_metrics(
        engine.metrics)
    m_rounds.inc()

    psrc = engine._page_source(sp.table, prep.stream_cols,
                               sp.page_rows)
    bsrc = engine._page_source(sp.build_table, prep.spill_cols, 1024)

    ppids = radix_partition_ids(
        *_host_key_cols(psrc, sp.probe_keys), sp.nparts)
    bpids = radix_partition_ids(
        *_host_key_cols(bsrc, sp.build_keys), sp.nparts)
    pidx = _partition_indices(ppids, sp.nparts)
    bidx = _partition_indices(bpids, sp.nparts)

    # join-induced skipping at row grain: the partitioner already
    # materialized the probe's stored key columns, so a derived
    # semi-join filter prunes non-matching rows from the partition
    # index arrays before any gather/upload (inner/semi only — those
    # rows would be dropped by the join on device anyway)
    filters = prep._join_filters(read_ts)
    if filters:
        keep = None
        for f in filters:
            cols, valids = _host_key_cols(psrc, (f.col,))
            k = f.rows_ok(cols[0], valids[0])
            keep = k if keep is None else (keep & k)
        if keep is not None and not keep.all():
            n_dropped = int(len(keep) - keep.sum())
            engine.metrics.counter(
                "exec.skip.joinfilter.rows",
                "spill-join probe rows pruned host-side by a "
                "semi-join filter (never gathered or uploaded)"
            ).inc(n_dropped)
            pidx = [ix[keep[ix]] for ix in pidx]
    # ONE shared shape-ladder bucket for every build partition: jit
    # retraces per input shape, so a shared pad means one XLA program
    # serves the whole sweep (and steady-state re-runs reuse it); the
    # bucket comes from the same ladder as resident uploads and
    # streamed pages (exec/coldstart.ShapeLadder), so spill programs
    # share executables with them across processes too
    bpad = engine._row_bucket(max(len(ix) for ix in bidx))
    bbytes = _batch_bytes(bsrc, bpad)
    # journal the build-partition bucket so Engine.prewarm can compile
    # the partition-sweep executable at the right shape next process
    # (exec/coldstart.journal_entries)
    from . import coldstart
    coldstart.journal_record(engine._compile_cache_dir, prep.sql_text,
                             bucket=bpad)

    busy = [0.0]
    # statement-profile accounting: plain accumulators updated on the
    # feed side (possibly the prefetch worker), noted once into the
    # statement's sink on the consumer thread after the sweep
    moved = [0]
    units = [0]

    def feed():
        """(kind, batch) stream: each partition's build batch, then
        its probe pages. Runs on the prefetch worker so assembly and
        upload of item i+1 overlap the device's probe of item i —
        across partition boundaries too."""
        for p in range(sp.nparts):
            if len(pidx[p]) == 0:
                continue  # no probe rows: nothing can match or emit
            t0 = time.monotonic()
            bb = bsrc.gather_batch(bidx[p], bpad)
            busy[0] += time.monotonic() - t0
            m_parts.inc()
            m_bytes.inc(bbytes)
            units[0] += 1
            moved[0] += bbytes
            yield ("build", bb)
            it = psrc.gather_pages(pidx[p])
            while True:
                t0 = time.monotonic()
                try:
                    page = next(it)
                except StopIteration:
                    break
                busy[0] += time.monotonic() - t0
                m_bytes.inc(psrc.page_bytes)
                moved[0] += psrc.page_bytes
                yield ("page", page)

    pipeline = prep.session.vars.get("streaming_pipeline",
                                     "on") != "off"
    stall = _StallSum(engine.metrics.histogram(
        "exec.stream.prefetch_stall_seconds", _STALL_HELP))
    items = (stream_prefetch(feed(), stall_hist=stall)
             if pipeline else feed())
    state = None
    scans = dict(prep.scans)
    try:
        for kind, b in items:
            if kind == "build":
                scans[sp.build_alias] = b
                continue
            scans[sp.alias] = b
            s = fns.page(scans, tsv)
            state = s if state is None else fns.combine(state, s)
    finally:
        close = getattr(items, "close", None)
        if close is not None:
            close()
    if state is None:
        # empty probe: one never-visible padding round yields the
        # aggregate's empty state (COUNT 0, NULL sums)
        scans[sp.build_alias] = bsrc.gather_batch(
            np.zeros(0, dtype=np.int64), bpad)
        scans[sp.alias] = psrc.empty_page()
        state = fns.page(scans, tsv)
    m_overlap.inc(max(0.0, busy[0] - stall.total))
    _prof.note(f"spill:join:{sp.table}", batches=units[0],
               bytes_spilled=moved[0], stall_seconds=stall.total)
    return fns.final(state)


# ---------------------------------------------------------------------------
# external merge sort
# ---------------------------------------------------------------------------

def compile_spill_sort(node: P.PlanNode, params, meta):
    """Compile the per-run device program of the external merge sort.

    Per page: run the Sort's child subtree, pack the key list into
    normalized uint64 lanes (always — the lanes ARE the host merge
    keys, so there is no lexsort arm here; the decision layer verified
    encodability), stable-sort the run on device, cut it to
    LIMIT+OFFSET live rows when a Limit rides above (a row past that
    rank within its own run can never make the global cut), and
    return (run batch, packed lanes) for the host merge."""
    limit_node = None
    n = node
    if isinstance(n, P.Limit):
        limit_node, n = n, n.child
    if not isinstance(n, P.Sort):
        raise ExecError("spill sort requires a Sort-rooted plan")
    sort_node = n
    keys = list(sort_node.keys)
    rank_tables = _sort_rank_tables(keys, meta)
    childf = compile_plan(sort_node.child, params)
    cut = (limit_node.limit + (limit_node.offset or 0)
           if limit_node is not None and limit_node.limit is not None
           else None)

    def run_fn(rc: RunContext):
        b = childf(rc)
        lanes = _normalized_lanes(b, keys, rank_tables, "spill")
        if lanes is None:
            raise ExecError(
                "spill sort keys must be normalized-encodable "
                "(the spill decision should not have picked this plan)")
        perm = sortkey.sort_perm(lanes, kind="spill")
        data = tuple(d[perm] for d in b.data)
        valid = tuple(v[perm] for v in b.valid)
        sel = b.sel[perm]
        lanes = [lane[perm] for lane in lanes]
        if cut is not None and cut < b.n:
            data = tuple(d[:cut] for d in data)
            valid = tuple(v[:cut] for v in valid)
            sel = sel[:cut]
            lanes = [lane[:cut] for lane in lanes]
        out = ColumnBatch(data, valid, sel, b.names)
        # dead rows keep their all-ones masked lanes: they merge last
        # and the host drops them by sel
        return out, jnp.stack(lanes)

    return run_fn


def run_spill_sort(engine, prep, read_ts: int):
    """Execute a spill-sort Prepared host-side at the int timestamp
    `read_ts` and return a decoded Result (there is no single device
    output batch to hand back: the merge happens on the host)."""
    from .session import Result
    sp: SpillPlan = prep.spill
    meta = prep.meta
    tsv = read_ts_words(read_ts)
    names = list(meta.names)
    m_parts, m_bytes, m_rounds, m_overlap = _spill_metrics(
        engine.metrics)
    m_rounds.inc()

    src = engine._page_source(sp.table, prep.stream_cols,
                              sp.page_rows,
                              zone_preds=prep.stream_zone,
                              read_ts=read_ts)
    busy = [0.0]

    def feed():
        it = src.pages()
        while True:
            t0 = time.monotonic()
            try:
                page = next(it)
            except StopIteration:
                return
            busy[0] += time.monotonic() - t0
            yield page

    pipeline = prep.session.vars.get("streaming_pipeline",
                                     "on") != "off"
    stall = _StallSum(engine.metrics.histogram(
        "exec.stream.prefetch_stall_seconds", _STALL_HELP))
    pages = (stream_prefetch(feed(), stall_hist=stall)
             if pipeline else feed())
    scans = dict(prep.scans)
    runs = []  # (per-col data, per-col valid, lanes), live rows only
    try:
        for page in pages:
            scans[sp.alias] = page
            out, lanes = prep.jfn(scans, tsv)
            m_parts.inc()
            m_bytes.inc(_batch_bytes(src, sp.page_rows))
            _prof.note(f"spill:sort:{sp.table}", batches=1,
                       bytes_spilled=_batch_bytes(src, sp.page_rows))
            pulled = pull_arrays(
                [out.sel, lanes]
                + [out.col(c) for c in names]
                + [out.col_valid(c) for c in names])
            sel, lv = pulled[0], pulled[1]
            datas = pulled[2:2 + len(names)]
            valids = pulled[2 + len(names):]
            live = np.flatnonzero(sel)  # ascending: run order kept
            runs.append(([d[live] for d in datas],
                         [v[live] for v in valids],
                         lv[:, live]))
    finally:
        close = getattr(pages, "close", None)
        if close is not None:
            close()
    m_overlap.inc(max(0.0, busy[0] - stall.total))
    _prof.note(f"spill:sort:{sp.table}", stall_seconds=stall.total)

    res = Result(names=names, types=list(meta.types))
    if not runs:
        return res
    order = sortkey.merge_lanes_host([r[2] for r in runs])
    lo = sp.offset
    hi = (lo + sp.limit) if sp.limit >= 0 else None
    order = order[lo:hi]
    cols = []
    for i, (name, ty) in enumerate(zip(names, meta.types)):
        d = np.concatenate([r[0][i] for r in runs])[order]
        v = np.concatenate([r[1][i] for r in runs])[order]
        arr = np.ma.masked_array(d, mask=~v)
        cols.append(_decode_column(arr, ty,
                                   meta.dictionaries.get(name)))
    res.rows = list(zip(*cols)) if cols else []
    return res
