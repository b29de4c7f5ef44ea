"""Host columnar MVCC store — the table-data plane feeding the TPU.

Design rationale (SURVEY.md §7 step 3 + "Host↔HBM feed rate"): the
reference stores SQL rows as KV pairs and pays a per-row decode
(cFetcher, pkg/sql/colfetcher/cfetcher.go) on every scan; its own
direct-columnar-scan work (pkg/storage/col_mvcc.go:37-64) moves that
decode server-side to skip a network hop. We go one step further and
keep the *primary* analytic representation columnar: each table is a
list of immutable column chunks (numpy arrays + validity), with MVCC
visibility as two int64 timestamp columns per chunk:

    _mvcc_ts   — commit timestamp of the row version (Timestamp.to_int)
    _mvcc_del  — deletion timestamp (MAX if live)

A scan AS OF timestamp T selects ``_mvcc_ts <= T < _mvcc_del`` — a pure
mask kernel that runs on device beside the WHERE clause, so MVCC
visibility filtering costs one compare+and per row (SURVEY.md §7
"MVCC visibility filtering on device": resolved in favor of on-device).
The int64 form is the host store's alone: a device batch holds each
timestamp as two 32-bit word columns (``_mvcc_ts_hi`` / ``_mvcc_ts_lo``,
``_mvcc_del_hi`` / ``_mvcc_del_lo``), split on the host at upload, and
the scan compares words (ops/batch.py ``mvcc_live``: the TPU has no
64-bit integers and would split an int64 column on every execution).

Updates/deletes write tombstones (set _mvcc_del) and appended new
versions; chunks are sealed at `chunk_rows` and never mutated except
for the deletion column, mirroring LSM immutability. String columns
are dictionary-encoded at ingest (codes on device, dictionary on
host). Point reads and the write path go through the row-oriented KV
layer (storage/memtable.py, kv/); this module is the scan plane.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..sql.types import ColumnSchema, Family, TableSchema
from . import chunkstats
from .hlc import MAX_TIMESTAMP, Timestamp

MAX_TS_INT = MAX_TIMESTAMP.to_int()


class Dictionary:
    """Growable string dictionary: value <-> int32 code."""

    def __init__(self):
        self.values: list[str] = []
        # value -> code; None while a list taken whole by seed() waits
        # for the first lookup that needs the other direction
        self._codes: Optional[dict[str, int]] = {}

    @property
    def codes(self) -> dict[str, int]:
        if self._codes is None:
            vals = self.values
            codes = dict(zip(vals, range(len(vals))))
            if len(codes) != len(vals):
                raise ValueError(
                    "a dictionary was seeded with a repeated value: "
                    "set_dictionary takes distinct values")
            self._codes = codes
        return self._codes

    def derived(self, key, build):
        """What `build(values)` makes of this dictionary's values (a
        LIKE mask, a substring's code map), kept for as long as no
        value is added: a dictionary only grows, so its length says
        whether what was made still covers it. A statement that is
        prepared on every execution would otherwise walk a 1.5 M-entry
        dictionary (TPC-H Q13's o_comment) every time."""
        cache = self.__dict__.setdefault("_derived", {})
        hit = cache.get(key)
        if hit is not None and hit[0] == len(self.values):
            return hit[1]
        out = build(self.values)
        if len(cache) >= 64:
            cache.pop(next(iter(cache)))
        cache[key] = (len(self.values), out)
        return out

    def encode(self, v: str) -> int:
        c = self.codes.get(v)
        if c is None:
            c = len(self.values)
            self.values.append(v)
            self.codes[v] = c
        return c

    def seed(self, values) -> None:
        """Take a loader's own dictionary of a column: its values, which
        it promises are distinct, become codes 0..n-1 in one step, with
        no call a value. The other direction (value -> code, which only
        a string literal or a string insert asks for) is built in one
        pass when first needed, and that pass refuses a repeated value:
        tens of millions of comment strings are neither hashed nor held
        a second time by a load that never looks one up. A dictionary
        that already has values goes value by value."""
        if self.values:
            for v in values:
                self.encode(v)
        else:
            self.values, self._codes = list(values), None

    def encode_array(self, vals) -> np.ndarray:
        arr = np.asarray(vals)
        if arr.shape[0] > 4096:
            # bulk path: unique once, then one gather (600M-row ingest
            # must not loop per value)
            uniq, inv = np.unique(arr.astype(str), return_inverse=True)
            lut = np.fromiter((self.encode(u) for u in uniq),
                              dtype=np.int32, count=len(uniq))
            return lut[inv].astype(np.int32)
        return np.fromiter((self.encode(v) for v in arr),
                           dtype=np.int32, count=len(arr))

    def decode_array(self, codes: np.ndarray) -> np.ndarray:
        arr = np.asarray(self.values, dtype=object)
        return arr[codes]

    def __len__(self):
        return len(self.values)


@dataclass
class Chunk:
    """Immutable columnar slab (the storage analogue of an SSTable)."""
    data: dict[str, np.ndarray]
    valid: dict[str, np.ndarray]
    mvcc_ts: np.ndarray   # int64 creation timestamps
    mvcc_del: np.ndarray  # int64 deletion timestamps (MAX_TS_INT = live)
    n: int
    # hidden per-row id: stable identity for rows of tables with no
    # declared primary key (the reference synthesizes a rowid column
    # the same way, pkg/sql/catalog/tabledesc)
    rowid: Optional[np.ndarray] = None
    # per-column zone maps (sstable block-property collectors / the
    # reference's crdb_internal_mvcc-free span stats): column data is
    # immutable once the chunk is sealed, so a computed summary stays
    # valid for the chunk's lifetime. mvcc_del IS mutable
    # (tombstones), but zones summarize data columns only — a deleted
    # row's value still bounds the zone, which keeps skipping
    # conservative under any read timestamp. Populated at SEAL time
    # by finalize_stats (storage/chunkstats.py) on every creation
    # path; the in-method computation below survives only as a
    # fallback for directly-constructed chunks (tests).
    _zones: dict = field(default_factory=dict, repr=False, compare=False)
    # seal-time ChunkStats (blooms, distinct sketches, MVCC window);
    # None only for chunks that never went through a store path
    _stats: Optional[object] = field(default=None, repr=False,
                                     compare=False)

    def live_mask(self, ts: int) -> np.ndarray:
        return (self.mvcc_ts <= ts) & (ts < self.mvcc_del)

    def finalize_stats(self) -> None:
        """Build the write-time summaries (zones + blooms + distinct
        sketches + MVCC window) for this chunk. Called by every store
        path that creates or rebuilds a chunk, so the scan plane never
        has to compute a zone on demand."""
        self.set_stats(chunkstats.compute(self.data, self.valid,
                                          self.mvcc_ts, self.mvcc_del))

    def set_stats(self, st) -> None:
        self._stats = st
        self._zones.update(st.zones)

    def stats_ready(self) -> bool:
        return self._stats is not None

    def key_bloom(self, col: str):
        """Seal-time blocked bloom over `col`'s valid values (int
        family / dict codes only), or None."""
        st = self._stats
        return st.blooms.get(col) if st is not None else None

    def distinct_sketch(self, col: str):
        st = self._stats
        return st.distinct.get(col) if st is not None else None

    def mvcc_window(self) -> tuple[int, int]:
        """(ts_min, del_max): nothing in this chunk is visible at
        read_ts when ts_min > read_ts or del_max <= read_ts. ts_min
        is exact forever (mvcc_ts is sealed-immutable); del_max is the
        seal-time max and stays a valid UPPER bound because tombstones
        only ever lower mvcc_del — so no invalidation is needed when
        later deletes land on this chunk."""
        st = self._stats
        if st is not None:
            return st.ts_min, st.del_max
        if self.n == 0:
            return 0, 0
        return int(self.mvcc_ts.min()), int(self.mvcc_del.max())

    def zone(self, col: str):
        """(lo, hi, null_count, valid_count) over this chunk's valid
        lanes of `col`; (None, None, ...) when bounds are unknown
        (object dtype, NaNs, or an all-null chunk). Bounds cover ALL
        row versions, so predicate checks against them are
        visibility-independent and only ever under-skip."""
        z = self._zones.get(col)
        if z is None:
            d = self.data[col]
            v = self.valid[col]
            nvalid = int(v.sum())
            if nvalid == 0 or d.dtype.kind not in "biuf":
                z = (None, None, self.n - nvalid, nvalid)
            else:
                vals = d if nvalid == self.n else d[v]
                lo, hi = vals.min(), vals.max()
                if d.dtype.kind == "f" and (np.isnan(lo) or np.isnan(hi)):
                    z = (None, None, self.n - nvalid, nvalid)
                elif d.dtype.kind == "f":
                    z = (float(lo), float(hi), self.n - nvalid, nvalid)
                else:
                    z = (int(lo), int(hi), self.n - nvalid, nvalid)
            self._zones[col] = z
        return z


@dataclass
class TableData:
    schema: TableSchema
    dictionaries: dict[str, Dictionary] = field(default_factory=dict)
    chunks: list[Chunk] = field(default_factory=list)
    open_rows: dict[str, list] = field(default_factory=dict)  # building chunk
    open_ts: list = field(default_factory=list)
    chunk_rows: int = 1 << 20
    # generation bumps on every mutation; device caches key on it
    generation: int = 0
    open_rowids: list = field(default_factory=list)
    next_rowid: int = 1
    # pk-key bytes -> (chunk_index, row_index) of the LIVE version.
    # Built lazily on first transactional DML; None = not built.
    pk_index: Optional[dict] = None
    # ANALYZE output (sql/stats.py TableStats) + the generation it was
    # computed at; stale stats still inform the planner (estimates),
    # exact row_count always comes from row_count
    stats: Optional[object] = None
    stats_generation: int = -1
    # cached multi-column distinct counts for join-uniqueness checks:
    # (cols tuple) -> (generation, distinct, live_rows)
    key_distinct_cache: dict = field(default_factory=dict)
    # sorted-index locators: (cols tuple) -> (generation, sorted list
    # of (vals tuple, chunk, row)) over ALL versions — the range-scan
    # analogue of sec_index_cache (binary search for bounds)
    sorted_index_cache: dict = field(default_factory=dict)
    # secondary-index locators: (cols tuple) -> (generation, mapping)
    # where mapping is value-tuple -> [(chunk, row), ...] over ALL row
    # versions (lookups filter by MVCC visibility), rebuilt lazily
    # when the generation moves (storage analogue of an index that is
    # maintained by the write path in the reference; here the scan
    # plane is the source of truth and the index is derived)
    sec_index_cache: dict = field(default_factory=dict)

    @property
    def row_count(self) -> int:
        return sum(c.n for c in self.chunks) + len(self.open_ts)

    @property
    def codec(self):
        from ..sql.rowenc import RowCodec
        if not hasattr(self, "_codec") or self._codec is None:
            self._codec = RowCodec(self.schema)
        return self._codec


class ColumnStore:
    """All tables of one store (one node's data plane)."""

    def __init__(self, chunk_rows: int | None = None):
        from ..utils.metamorphic import metamorphic_pow2
        if chunk_rows is None:
            # metamorphic: chunk size is perf-only; results must not
            # change at 64 rows or 1M rows
            chunk_rows = metamorphic_pow2(
                "columnstore.chunk_rows", 1 << 20, 6, 20)
        self._lock = threading.RLock()
        self.tables: dict[str, TableData] = {}
        self.chunk_rows = chunk_rows
        # monotonic: a dropped table's id is never reused, so its
        # orphaned KV rows can never alias a new table's keyspace
        # (the reference keeps descriptor ids monotonic the same way)
        self._next_table_id = 100
        # bulk ingest so far (insert_columns): rows taken and the
        # seconds it held, read by the engine's metric registry
        self.ingest_rows = 0
        self.ingest_seconds = 0.0

    def alloc_table_id(self) -> int:
        with self._lock:
            tid = self._next_table_id
            self._next_table_id += 1
            return tid

    # -- DDL ---------------------------------------------------------------
    def create_table(self, schema: TableSchema) -> TableData:
        with self._lock:
            if schema.name in self.tables:
                raise ValueError(f"table {schema.name!r} exists")
            self._next_table_id = max(self._next_table_id,
                                      schema.table_id + 1)
            td = TableData(schema=schema, chunk_rows=self.chunk_rows)
            for col in schema.columns:
                if col.type.uses_dictionary:
                    td.dictionaries[col.name] = Dictionary()
                td.open_rows[col.name] = []
            self.tables[schema.name] = td
            return td

    def drop_table(self, name: str) -> None:
        with self._lock:
            del self.tables[name]

    def table(self, name: str) -> TableData:
        td = self.tables.get(name)
        if td is None:
            raise KeyError(f"table {name!r} does not exist")
        return td

    # -- ingest ------------------------------------------------------------
    def set_dictionary(self, name: str, col: str, values) -> None:
        """Pre-seed a string column's dictionary so bulk ingest can pass
        already-encoded int32 codes (the big-data path: encoding 600M
        object strings through np.unique would dominate ingest)."""
        self.table(name).dictionaries[col].seed(values)

    def insert_columns(self, name: str, cols: dict[str, np.ndarray],
                       ts: Timestamp,
                       valid: Optional[dict[str, np.ndarray]] = None) -> int:
        """Bulk columnar ingest (IMPORT path, the analogue of AddSSTable
        ingestion in pkg/sql/importer): the rows are sealed in chunks
        of the table's `chunk_rows`, so that a zone map or a bloom
        covers a megarow and not the whole load, and the chunks'
        statistics are built side by side (chunkstats.compute_many).
        A chunk's arrays are views of the caller's where the dtype
        already fits: a 60M-row load is held once.

        String columns accept either string arrays (dictionary-encoded
        here) or int32 code arrays into a dictionary pre-seeded via
        set_dictionary."""
        t0 = time.monotonic()
        td = self.table(name)
        valid = valid or {}
        n = len(next(iter(cols.values())))
        data: dict[str, np.ndarray] = {}
        vmap: dict[str, np.ndarray] = {}
        with self._lock:
            defaults = getattr(td, "column_defaults", {})
            for col in td.schema.columns:
                cn = col.name
                if cn not in cols:
                    dv = defaults.get(cn)
                    if dv is not None:
                        cols = dict(cols)
                        cols[cn] = np.full(
                            n, dv, dtype=object
                            if col.type.uses_dictionary
                            else None)
                    elif not col.nullable:
                        raise ValueError(f"missing non-null column {cn}")
                    else:
                        data[cn] = np.zeros(n, dtype=col.type.np_dtype)
                        vmap[cn] = np.zeros(n, dtype=bool)
                        continue
                raw = cols[cn]
                if col.type.uses_dictionary and raw.dtype.kind in ("U", "O", "S"):
                    arr = td.dictionaries[cn].encode_array(raw)
                elif (col.type.uses_dictionary
                      and raw.dtype.kind in ("i", "u")):
                    arr = np.asarray(raw, dtype=np.int32)
                    if arr.size and (int(arr.max()) >= len(td.dictionaries[cn])
                                     or int(arr.min()) < 0):
                        raise ValueError(
                            f"encoded codes for {cn} out of dictionary "
                            f"range (seed it with set_dictionary first)")
                elif col.type.family == Family.DECIMAL and raw.dtype.kind == "f":
                    arr = np.round(raw * (10 ** col.type.scale)).astype(np.int64)
                else:
                    arr = np.asarray(raw, dtype=col.type.np_dtype)
                data[cn] = arr
                vmap[cn] = (np.asarray(valid[cn], dtype=bool) if cn in valid
                            else np.ones(n, dtype=bool))
            rid0 = td.next_rowid
            td.next_rowid += n
            mvcc_ts = np.full(n, ts.to_int(), dtype=np.int64)
            mvcc_del = np.full(n, MAX_TS_INT, dtype=np.int64)
            rowid = np.arange(rid0, rid0 + n, dtype=np.int64)
            chunks = []
            for lo in range(0, max(n, 1), td.chunk_rows):
                rows = slice(lo, lo + td.chunk_rows)
                chunks.append(Chunk(
                    data={k: v[rows] for k, v in data.items()},
                    valid={k: v[rows] for k, v in vmap.items()},
                    mvcc_ts=mvcc_ts[rows], mvcc_del=mvcc_del[rows],
                    n=len(mvcc_ts[rows]), rowid=rowid[rows]))
            for chunk, st in zip(chunks, chunkstats.compute_many(
                    [(c.data, c.valid, c.mvcc_ts, c.mvcc_del)
                     for c in chunks])):
                chunk.set_stats(st)
            td.chunks.extend(chunks)
            td.pk_index = None  # rebuilt lazily if DML touches this table
            td.generation += 1
            self.ingest_rows += n
            self.ingest_seconds += time.monotonic() - t0
        return n

    def insert_rows(self, name: str, rows: list[dict], ts: Timestamp) -> int:
        """Row-at-a-time insert (INSERT VALUES path): buffers into the
        open chunk, sealing at chunk_rows."""
        td = self.table(name)
        from ..sql.rowenc import ROWID
        with self._lock:
            tsi = ts.to_int()
            defaults = getattr(td, "column_defaults", {})
            for row in rows:
                for col in td.schema.columns:
                    td.open_rows[col.name].append(
                        row.get(col.name, defaults.get(col.name)))
                td.open_ts.append(tsi)
                rid = row.get(ROWID)
                if rid is None:
                    rid = td.next_rowid
                    td.next_rowid += 1
                td.open_rowids.append(int(rid))
            td.pk_index = None
            td.generation += 1
            if len(td.open_ts) >= td.chunk_rows:
                self._seal_locked(td)
        return len(rows)

    def _seal_locked(self, td: TableData) -> None:
        if not td.open_ts:
            return
        n = len(td.open_ts)
        data, vmap = {}, {}
        for col in td.schema.columns:
            vals = td.open_rows[col.name]
            v = np.array([x is not None for x in vals], dtype=bool)
            if col.type.uses_dictionary:
                d = td.dictionaries[col.name]
                arr = np.fromiter(
                    (d.encode(x) if x is not None else 0 for x in vals),
                    dtype=np.int32, count=n)
            elif col.type.family == Family.DECIMAL:
                # ints are already-scaled physical values (binder output);
                # floats are logical and get scaled here (bulk loaders)
                scale = 10 ** col.type.scale
                arr = np.fromiter(
                    (0 if x is None else
                     x if isinstance(x, (int, np.integer)) else
                     int(round(float(x) * scale))
                     for x in vals),
                    dtype=np.int64, count=n)
            else:
                arr = np.array([x if x is not None else 0 for x in vals],
                               dtype=col.type.np_dtype)
            data[col.name] = arr
            vmap[col.name] = v
            td.open_rows[col.name] = []
        if len(td.open_rowids) != n:
            # rows buffered before the rowid plumbing existed, or by a
            # caller that bypassed insert_rows: allocate fresh ids
            td.open_rowids = list(range(td.next_rowid, td.next_rowid + n))
            td.next_rowid += n
        chunk = Chunk(
            data=data, valid=vmap,
            mvcc_ts=np.asarray(td.open_ts, dtype=np.int64),
            mvcc_del=np.full(n, MAX_TS_INT, dtype=np.int64), n=n,
            rowid=np.asarray(td.open_rowids, dtype=np.int64))
        chunk.finalize_stats()
        td.chunks.append(chunk)
        td.open_ts = []
        td.open_rowids = []

    def insert_versions(self, name: str,
                        versions: list[tuple[dict, int, int]]) -> int:
        """Bulk ingest with explicit MVCC bounds: each element is
        (row, mvcc_ts_int, mvcc_del_int). Used when materializing the
        scan plane from committed range data (exec/dml.py
        refresh_table_from_ranges) — the columnstore must reproduce
        the range plane's version history, not re-stamp it, or open
        snapshots and AS OF SYSTEM TIME reads go silently wrong."""
        td = self.table(name)
        from ..sql.rowenc import ROWID
        if not versions:
            with self._lock:
                td.generation += 1
            return 0
        with self._lock:
            self._seal_locked(td)   # don't interleave with open rows
            n = len(versions)
            data, vmap = {}, {}
            for col in td.schema.columns:
                vals = [r.get(col.name) for r, _t, _d in versions]
                v = np.array([x is not None for x in vals], dtype=bool)
                if col.type.uses_dictionary:
                    d = td.dictionaries[col.name]
                    arr = np.fromiter(
                        (d.encode(x) if x is not None else 0
                         for x in vals), dtype=np.int32, count=n)
                elif col.type.family == Family.DECIMAL:
                    scale = 10 ** col.type.scale
                    arr = np.fromiter(
                        (0 if x is None else
                         x if isinstance(x, (int, np.integer)) else
                         int(round(float(x) * scale))
                         for x in vals), dtype=np.int64, count=n)
                else:
                    arr = np.array(
                        [x if x is not None else 0 for x in vals],
                        dtype=col.type.np_dtype)
                data[col.name] = arr
                vmap[col.name] = v
            rowids = []
            for r, _t, _d in versions:
                rid = r.get(ROWID)
                if rid is None:
                    rid = td.next_rowid
                    td.next_rowid += 1
                rowids.append(int(rid))
            # synthetic-pk rowids came from the decoded keys: future
            # inserts must allocate past them or keys collide
            td.next_rowid = max(td.next_rowid, max(rowids) + 1)
            chunk = Chunk(
                data=data, valid=vmap,
                mvcc_ts=np.asarray([t for _r, t, _d in versions],
                                   dtype=np.int64),
                mvcc_del=np.asarray([d for _r, _t, d in versions],
                                    dtype=np.int64), n=n,
                rowid=np.asarray(rowids, dtype=np.int64))
            chunk.finalize_stats()
            td.chunks.append(chunk)
            td.pk_index = None
            td.generation += 1
        return n

    def seal(self, name: str) -> None:
        td = self.table(name)
        with self._lock:
            if not td.open_ts:
                return  # nothing buffered: data unchanged, caches stay
            self._seal_locked(td)
            td.generation += 1

    # -- mutation (tombstones + new versions) -------------------------------
    def delete_where(self, name: str, pred, ts: Timestamp) -> int:
        """Tombstone rows matching pred(chunk)->bool mask, visible as of
        ts (MVCC: set deletion timestamp; old readers still see them)."""
        td = self.table(name)
        tsi = ts.to_int()
        deleted = 0
        with self._lock:
            self._seal_locked(td)
            for chunk in td.chunks:
                mask = chunk.live_mask(tsi) & pred(chunk)
                chunk.mvcc_del[mask] = tsi
                deleted += int(mask.sum())
            td.pk_index = None
            td.generation += 1
        return deleted

    def update_where(self, name: str, pred, assign, ts: Timestamp) -> int:
        """MVCC update = tombstone old version + append new version.
        assign(chunk, mask) -> (data_cols, valid_cols) for the new
        versions of the masked rows."""
        td = self.table(name)
        tsi = ts.to_int()
        updated = 0
        with self._lock:
            self._seal_locked(td)
            new_rows = []
            for chunk in td.chunks:
                mask = chunk.live_mask(tsi) & pred(chunk)
                cnt = int(mask.sum())
                if cnt == 0:
                    continue
                chunk.mvcc_del[mask] = tsi
                new_rows.append(assign(chunk, mask))
                updated += cnt
            for data, vmap in new_rows:
                n = len(next(iter(data.values())))
                rid0 = td.next_rowid
                td.next_rowid += n
                chunk = Chunk(
                    data={k: np.asarray(v) for k, v in data.items()},
                    valid={k: np.asarray(v, dtype=bool)
                           for k, v in vmap.items()},
                    mvcc_ts=np.full(n, tsi, dtype=np.int64),
                    mvcc_del=np.full(n, MAX_TS_INT, dtype=np.int64), n=n,
                    rowid=np.arange(rid0, rid0 + n, dtype=np.int64))
                chunk.finalize_stats()
                td.chunks.append(chunk)
            td.pk_index = None
            td.generation += 1
        return updated

    # -- transactional publish (the scan plane as a materialization of
    # the committed KV row plane; engine DML writes intents through
    # kv.Txn and publishes here at the commit timestamp) ---------------------
    # -- schema changes (ALTER TABLE; pkg/sql/backfill analogue) -----------
    def add_column(self, name: str, col, default=None,
                   hidden: bool = True) -> None:
        """Add a column to the live schema (hidden until published).
        Existing sealed chunks are backfilled separately, chunk by
        chunk (backfill_column_chunk) by the schema-change job; the
        open chunk and all future writes carry it immediately."""
        td = self.table(name)
        with self._lock:
            if any(c.name == col.name for c in td.schema.columns):
                raise ValueError(f"column {col.name!r} already exists")
            col.hidden = hidden
            td.schema.columns.append(col)
            if col.type.uses_dictionary:
                td.dictionaries.setdefault(col.name, Dictionary())
            td.column_defaults = getattr(td, "column_defaults", {})
            if default is not None:
                td.column_defaults[col.name] = default
            td.open_rows[col.name] = [default] * len(td.open_ts)
            td._codec = None
            td.pk_index = None
            td.generation += 1

    def backfill_column_chunk(self, name: str, colname: str,
                              chunk_index: int) -> bool:
        """Fill one sealed chunk with the column's default (idempotent;
        returns False when the chunk already has it). The unit of
        schema-change checkpointing, like the reference's per-span
        backfill progress (pkg/sql/backfill)."""
        td = self.table(name)
        with self._lock:
            if chunk_index >= len(td.chunks):
                return False
            chunk = td.chunks[chunk_index]
            if colname in chunk.data:
                return False
            col = td.schema.column(colname)
            default = getattr(td, "column_defaults", {}).get(colname)
            n = chunk.n
            if default is None:
                chunk.data[colname] = np.zeros(n, dtype=(
                    np.int32 if col.type.uses_dictionary
                    else col.type.np_dtype))
                chunk.valid[colname] = np.zeros(n, dtype=bool)
            elif col.type.uses_dictionary:
                code = td.dictionaries[colname].encode(default)
                chunk.data[colname] = np.full(n, code, dtype=np.int32)
                chunk.valid[colname] = np.ones(n, dtype=bool)
            else:
                v = default
                if col.type.family == Family.DECIMAL \
                        and not isinstance(v, (int, np.integer)):
                    v = int(round(float(v) * 10 ** col.type.scale))
                chunk.data[colname] = np.full(n, v,
                                              dtype=col.type.np_dtype)
                chunk.valid[colname] = np.ones(n, dtype=bool)
            if chunk._stats is not None:
                chunkstats.extend(chunk._stats, colname,
                                  chunk.data[colname],
                                  chunk.valid[colname])
                chunk._zones[colname] = chunk._stats.zones[colname]
            else:
                chunk.finalize_stats()
            td.generation += 1
            return True

    def unfilled_chunks(self, name: str, colname: str) -> list[int]:
        td = self.table(name)
        with self._lock:
            return [i for i, c in enumerate(td.chunks)
                    if colname not in c.data]

    def publish_column(self, name: str, colname: str) -> None:
        """Make an added column visible to readers (descriptor went
        PUBLIC)."""
        td = self.table(name)
        with self._lock:
            td.schema.column(colname).hidden = False
            td.generation += 1

    def hide_column(self, name: str, colname: str) -> None:
        td = self.table(name)
        with self._lock:
            td.schema.column(colname).hidden = True
            td.generation += 1

    def drop_column(self, name: str, colname: str) -> None:
        td = self.table(name)
        with self._lock:
            idx = td.schema.column_index(colname)
            if td.schema.columns[idx].name in td.schema.primary_key:
                raise ValueError(
                    f"cannot drop primary key column {colname!r}")
            del td.schema.columns[idx]
            td.dictionaries.pop(colname, None)
            td.open_rows.pop(colname, None)
            getattr(td, "column_defaults", {}).pop(colname, None)
            for c in td.chunks:
                c.data.pop(colname, None)
                c.valid.pop(colname, None)
                c._zones.pop(colname, None)
                if c._stats is not None:
                    c._stats.zones.pop(colname, None)
                    c._stats.blooms.pop(colname, None)
                    c._stats.distinct.pop(colname, None)
            td._codec = None
            td.pk_index = None
            td.generation += 1

    def alloc_rowids(self, name: str, n: int) -> list[int]:
        td = self.table(name)
        with self._lock:
            r0 = td.next_rowid
            td.next_rowid += n
            return list(range(r0, r0 + n))

    def extract_row(self, td: TableData, chunk: Chunk, ri: int) -> dict:
        """One row in storage-logical form (strings decoded, numerics
        physical) — the inverse of the seal path's encode."""
        from ..sql.rowenc import ROWID
        row: dict = {}
        for col in td.schema.columns:
            cn = col.name
            if not chunk.valid[cn][ri]:
                row[cn] = None
            elif col.type.uses_dictionary:
                row[cn] = td.dictionaries[cn].values[int(chunk.data[cn][ri])]
            else:
                row[cn] = chunk.data[cn][ri].item()
        if chunk.rowid is not None:
            row[ROWID] = int(chunk.rowid[ri])
        return row

    def row_key(self, td: TableData, chunk: Chunk, ri: int) -> bytes:
        """The KV key bytes for one stored row version (pk columns
        decoded and run through the table's order-preserving codec)."""
        codec = td.codec
        if codec.synthetic_pk:
            return codec.key_from_pk((int(chunk.rowid[ri]),))
        pk = []
        for cn in codec.pk_cols:
            col = td.schema.column(cn)
            v = chunk.data[cn][ri]
            if col.type.uses_dictionary:
                pk.append(td.dictionaries[cn].values[int(v)])
            else:
                pk.append(v.item())
        return codec.key_from_pk(tuple(pk))

    def ensure_pk_index(self, name: str) -> dict:
        """Build (lazily) the pk-key -> (chunk, row) locator for LIVE
        rows. The DML path needs it to tombstone superseded versions;
        bulk-ingested tables only pay for it if they are ever DML'd."""
        td = self.table(name)
        with self._lock:
            self._seal_locked(td)
            if td.pk_index is not None:
                return td.pk_index
            idx: dict[bytes, tuple[int, int]] = {}
            for ci, chunk in enumerate(td.chunks):
                live = chunk.mvcc_del == MAX_TS_INT
                ris = np.nonzero(live)[0]
                batch = self._batch_row_keys(td, chunk, ris)
                if batch is not None:
                    for ri, key in zip(ris, batch):
                        idx[key] = (ci, int(ri))
                else:
                    for ri in ris:
                        idx[self.row_key(td, chunk, int(ri))] = \
                            (ci, int(ri))
            td.pk_index = idx
            return idx

    def _batch_row_keys(self, td: TableData, chunk: Chunk,
                        ris: np.ndarray):
        """Bulk pk-key encode via the native codec (native/keyenc.cpp);
        None = shape not covered (multi-column or float pk) or no
        toolchain — caller falls back to the Python row_key loop."""
        from .. import native
        from . import keys as K
        codec = td.codec
        if len(ris) == 0:
            return []
        prefix = K.table_prefix(codec.table_id)
        if codec.synthetic_pk:
            return native.batch_encode_int_keys(prefix,
                                                chunk.rowid[ris])
        if len(codec.pk_cols) != 1:
            return None
        cn = codec.pk_cols[0]
        col = td.schema.column(cn)
        fam = col.type.family
        if col.type.uses_dictionary:
            vals = td.dictionaries[cn].decode_array(
                chunk.data[cn][ris])
            return native.batch_encode_str_keys(prefix, list(vals))
        if fam in (Family.INT, Family.DATE, Family.TIMESTAMP,
                   Family.DECIMAL, Family.BOOL, Family.INTERVAL):
            return native.batch_encode_int_keys(
                prefix, chunk.data[cn][ris].astype(np.int64))
        return None

    def apply_committed(self, name: str, ops: list, ts: Timestamp) -> None:
        """Publish one committed txn's effects on this table.

        ops: ordered list of ("put", key_bytes, row_dict) and
        ("del", key_bytes). A put supersedes (tombstones) the prior
        live version of the same key; rows carry storage-logical
        values (see extract_row). Mirrors how the reference's scan
        plane only ever sees resolved, committed versions (intents are
        filtered by pebbleMVCCScanner before SQL decodes them)."""
        self.apply_committed_batch(name, [(ops, ts.to_int())])

    def apply_committed_batch(self, name: str, batches: list) -> None:
        """Publish MANY committed txns' effects in ONE sealed chunk.

        batches: [(ops, tsi)] in ascending commit-timestamp order (the
        OLTP lane's deferred-publish queue, exec/oltplane.py). A row
        superseded by a LATER batch still publishes — with its
        [ts, del_ts) visibility window — so historical reads over the
        flushed chunk see exactly what the mirror served. Batching is
        also what keeps single-row OLTP statements from growing one
        chunk per statement (the memtable batching of an LSM ingest)."""
        td = self.table(name)
        from ..sql.rowenc import ROWID
        with self._lock:
            idx = self.ensure_pk_index(name)
            # key -> position of its newest pending row in new_rows
            new_rows: list = []  # [key, row|None, tsi, del_tsi]
            new_keys: dict[bytes, int] = {}
            for ops, tsi in batches:
                for op in ops:
                    kind, key = op[0], op[1]
                    pos = idx.pop(key, None)
                    if pos is not None:
                        ci, ri = pos
                        td.chunks[ci].mvcc_del[ri] = tsi
                    npos = new_keys.pop(key, None)
                    if npos is not None:
                        if new_rows[npos][2] == tsi:
                            # superseded within one txn: never visible
                            new_rows[npos][1] = None
                        else:
                            # superseded by a later txn: close its
                            # visibility window
                            new_rows[npos][3] = tsi
                    if kind == "put":
                        row = dict(op[2])
                        if td.codec.synthetic_pk and ROWID not in row:
                            row[ROWID] = td.next_rowid
                            td.next_rowid += 1
                        new_keys[key] = len(new_rows)
                        new_rows.append([key, row, tsi, MAX_TS_INT])
            emit = [e for e in new_rows if e[1] is not None]
            live = emit  # warm indexes cover all published versions
            base_ci = len(td.chunks)
            if emit:
                rows = [r for _, r, _, _ in emit]
                defaults = getattr(td, "column_defaults", {})
                for _key, row, tsi, _dts in emit:
                    for col in td.schema.columns:
                        td.open_rows[col.name].append(
                            row.get(col.name, defaults.get(col.name)))
                    td.open_ts.append(tsi)
                    td.open_rowids.append(int(row.get(ROWID, 0)) or
                                          self._next_rowid_locked(td))
                self._seal_locked(td)
                chunk = td.chunks[base_ci]
                for i, (k, _row, _tsi, dts) in enumerate(emit):
                    if dts != MAX_TS_INT:
                        chunk.mvcc_del[i] = dts
                    else:
                        idx[k] = (base_ci, i)
            # keep warm secondary-index locators valid across the
            # publish instead of forcing an O(table) rebuild per DML
            # statement (the scan-plane analogue of the reference's
            # write path maintaining index KV entries in place)
            if td.sec_index_cache or td.sorted_index_cache:
                import bisect
                defaults = getattr(td, "column_defaults", {})
                for cols, (gen, mapping) in list(
                        td.sec_index_cache.items()):
                    if gen != td.generation:
                        del td.sec_index_cache[cols]
                        continue
                    if live:
                        for i, (_k, row, _tsi, _dts) in enumerate(live):
                            vals = tuple(row.get(cn, defaults.get(cn))
                                         for cn in cols)
                            if any(v is None for v in vals):
                                continue
                            mapping.setdefault(vals, []).append(
                                (base_ci, i))
                    td.sec_index_cache[cols] = (td.generation + 1,
                                                mapping)
                for cols, (gen, entries) in list(
                        td.sorted_index_cache.items()):
                    if gen != td.generation:
                        del td.sorted_index_cache[cols]
                        continue
                    if live:
                        # copy-on-write: in-place insort would SHIFT
                        # positions under a reader iterating the old
                        # list (range fastpath holds it outside the
                        # lock); a published list is never mutated
                        entries = list(entries)
                        for i, (_k, row, _tsi, _dts) in enumerate(live):
                            vals = tuple(row.get(cn, defaults.get(cn))
                                         for cn in cols)
                            if any(v is None for v in vals):
                                continue
                            bisect.insort(entries,
                                          (vals, base_ci, i),
                                          key=lambda e: e[0])
                    td.sorted_index_cache[cols] = (td.generation + 1,
                                                   entries)
            td.generation += 1

    def _next_rowid_locked(self, td: TableData) -> int:
        r = td.next_rowid
        td.next_rowid += 1
        return r

    def ensure_secondary_index(self, name: str, cols: tuple) -> dict:
        """Build (lazily, generation-cached) the value-tuple ->
        [(chunk, row), ...] locator over ALL row versions of `cols`.
        Rows with a NULL in any indexed column are excluded (SQL
        uniqueness and equality both ignore NULLs). Lookups must
        filter positions by MVCC visibility at their read timestamp —
        superseded versions are indexed on purpose so historical
        reads (txn-pinned / follower-read timestamps) stay correct."""
        td = self.table(name)
        with self._lock:
            self._seal_locked(td)
            cached = td.sec_index_cache.get(cols)
            if cached is not None and cached[0] == td.generation:
                return cached[1]
            idx: dict[tuple, list] = {}
            for ci, chunk in enumerate(td.chunks):
                valid = np.ones(chunk.n, dtype=bool)
                arrs = []
                for cn in cols:
                    valid &= chunk.valid[cn]
                    col = td.schema.column(cn)
                    if col.type.uses_dictionary:
                        arrs.append(td.dictionaries[cn].decode_array(
                            chunk.data[cn]))
                    else:
                        arrs.append(chunk.data[cn])
                for ri in np.nonzero(valid)[0]:
                    key = tuple(a[ri].item() if hasattr(a[ri], "item")
                                else a[ri] for a in arrs)
                    idx.setdefault(key, []).append((ci, int(ri)))
            stale = [k for k, v in td.sec_index_cache.items()
                     if v[0] != td.generation]
            for k in stale:
                del td.sec_index_cache[k]
            td.sec_index_cache[cols] = (td.generation, idx)
            return idx

    def ensure_sorted_index(self, name: str, cols: tuple) -> list:
        """Sorted [(vals, chunk, row)] over ALL row versions of `cols`
        (generation-cached): binary search gives range bounds, ordered
        iteration gives index order — the host-side analogue of an
        ordered KV index scan (pebbleMVCCScanner over an index span).
        NULL rows are excluded like ensure_secondary_index."""
        td = self.table(name)
        with self._lock:
            self._seal_locked(td)
            cached = td.sorted_index_cache.get(cols)
            if cached is not None and cached[0] == td.generation:
                return cached[1]
            entries: list = []
            for ci, chunk in enumerate(td.chunks):
                valid = np.ones(chunk.n, dtype=bool)
                arrs = []
                for cn in cols:
                    valid &= chunk.valid[cn]
                    col = td.schema.column(cn)
                    if col.type.uses_dictionary:
                        arrs.append(td.dictionaries[cn].decode_array(
                            chunk.data[cn]))
                    else:
                        arrs.append(chunk.data[cn])
                for ri in np.nonzero(valid)[0]:
                    vals = tuple(a[ri].item() if hasattr(a[ri], "item")
                                 else a[ri] for a in arrs)
                    entries.append((vals, ci, int(ri)))
            entries.sort(key=lambda e: e[0])
            stale = [k for k, v in td.sorted_index_cache.items()
                     if v[0] != td.generation]
            for k in stale:
                del td.sorted_index_cache[k]
            td.sorted_index_cache[cols] = (td.generation, entries)
            return entries

    # -- statistics ----------------------------------------------------------
    def analyze(self, name: str):
        """ANALYZE: exact per-column stats over live rows (sql/stats)."""
        from ..sql.stats import analyze_columns
        td = self.table(name)
        with self._lock:
            self._seal_locked(td)
            td.stats = analyze_columns(td)
            td.stats_generation = td.generation
            return td.stats

    def sketch_stats(self, name: str):
        """Planner stats derived from seal-time chunk summaries
        (sql/stats.sketch_table_stats) — cached per table generation
        like _ts_hi_locked, because the merge walks every chunk's
        sketch registers. Never seals: open rows simply don't
        contribute (the execution path seals before planning, so in
        practice the summaries cover everything)."""
        from ..sql.stats import sketch_table_stats
        td = self.table(name)
        with self._lock:
            ck = ("__sketch_stats__",)
            hit = td.key_distinct_cache.get(ck)
            if hit is not None and hit[0] == td.generation:
                return hit[1]
            st = sketch_table_stats(td)
            td.key_distinct_cache[ck] = (td.generation, st)
            return st

    def _distinct_under(self, td: TableData, cols: tuple,
                        row_mask_fn) -> tuple[int, int]:
        """(distinct combined-key count, non-NULL-key row count) over
        rows selected by row_mask_fn(chunk) -> bool mask."""
        parts = []
        nonnull_rows = 0
        for chunk in td.chunks:
            sel = row_mask_fn(chunk)
            arrs = [chunk.data[c][sel] for c in cols]
            vals = [chunk.valid[c][sel] for c in cols]
            # NULL keys never join; exclude them from uniqueness
            ok = np.ones(int(sel.sum()), dtype=bool)
            for v in vals:
                ok &= v
            nonnull_rows += int(ok.sum())
            parts.append(np.stack([a[ok] for a in arrs], axis=1)
                         if arrs else np.zeros((0, 0)))
        if parts and sum(p.shape[0] for p in parts):
            allk = np.concatenate(parts, axis=0)
            distinct = int(len(np.unique(allk, axis=0)))
        else:
            distinct = 0
        return distinct, nonnull_rows

    def key_distinct(self, name: str, cols: tuple) -> tuple[int, int]:
        """(distinct combined-key count, non-NULL-key live row count)
        over CURRENTLY-live rows — the planner's build-side swap
        heuristic. Cached per table generation. For the correctness
        guard use keys_unique_for_read (snapshot-aware)."""
        td = self.table(name)
        with self._lock:
            self._seal_locked(td)
            hit = td.key_distinct_cache.get(cols)
            if hit is not None and hit[0] == td.generation:
                return hit[1], hit[2]
            distinct, nonnull = self._distinct_under(
                td, cols, lambda c: c.mvcc_del == MAX_TS_INT)
            td.key_distinct_cache[cols] = (td.generation, distinct,
                                           nonnull)
            return distinct, nonnull

    def _ts_hi_locked(self, td: TableData) -> int:
        """Max MVCC event timestamp (insert or delete) in the table,
        cached per generation. A read at or above it sees exactly the
        currently-live rows, so snapshot-dependent measurements become
        generation-cacheable — the steady state of every prepared
        statement re-executed against unmodified tables."""
        ck = ("__ts_hi__",)
        hit = td.key_distinct_cache.get(ck)
        if hit is not None and hit[0] == td.generation:
            return hit[1]
        hi = 0
        for chunk in td.chunks:
            if chunk.n:
                hi = max(hi, int(chunk.mvcc_ts.max()))
                dels = chunk.mvcc_del[chunk.mvcc_del != MAX_TS_INT]
                if len(dels):
                    hi = max(hi, int(dels.max()))
        td.key_distinct_cache[ck] = (td.generation, hi)
        return hi

    def keys_unique_for_read(self, name: str, cols: tuple,
                             read_ts_int: int) -> bool:
        """Snapshot-aware uniqueness: are the keys unique among the
        rows VISIBLE at read_ts (the rows a scan at that timestamp
        joins)? Tiers: (1) unique across ALL versions (cacheable per
        generation — every snapshot is a subset, so any snapshot is
        unique too) accepts immediately; (2) read_ts at/above the
        table's last MVCC event sees exactly the currently-live rows,
        so that answer caches per generation too; (3) historical
        read_ts inside the table's write history pays the exact
        snapshot computation."""
        td = self.table(name)
        with self._lock:
            self._seal_locked(td)
            allkey = ("__allversions__",) + cols
            hit = td.key_distinct_cache.get(allkey)
            if hit is None or hit[0] != td.generation:
                d, n = self._distinct_under(
                    td, cols, lambda c: np.ones(c.n, dtype=bool))
                td.key_distinct_cache[allkey] = (td.generation, d, n)
            else:
                _, d, n = hit
            if d == n:
                return True
            if read_ts_int >= self._ts_hi_locked(td):
                nowkey = ("__livenow_unique__",) + cols
                hit = td.key_distinct_cache.get(nowkey)
                if hit is None or hit[0] != td.generation:
                    d, n = self._distinct_under(
                        td, cols, lambda c: c.live_mask(read_ts_int))
                    td.key_distinct_cache[nowkey] = (td.generation,
                                                     d, n)
                else:
                    _, d, n = hit
                return d == n
            d, n = self._distinct_under(
                td, cols, lambda c: c.live_mask(read_ts_int))
            return d == n

    def key_max_multiplicity(self, name: str, cols: tuple,
                             read_ts_int: int,
                             include_null_group: bool = False) -> int:
        """Max duplicate count of (cols) among rows visible at read_ts.
        Two consumers with different NULL semantics: the hash join's
        expansion factor excludes NULL-keyed rows (they never join,
        the default); GROUP BY accumulator sizing sets
        include_null_group because NULL keys DO form a group. Cached
        per generation when read_ts sees the table's final state
        (same reasoning as keys_unique_for_read tier 2)."""
        td = self.table(name)
        with self._lock:
            self._seal_locked(td)
            cacheable = read_ts_int >= self._ts_hi_locked(td)
            mk = ("__maxmult__", include_null_group) + cols
            if cacheable:
                hit = td.key_distinct_cache.get(mk)
                if hit is not None and hit[0] == td.generation:
                    return hit[1]
            k = self._key_max_multiplicity_locked(
                td, cols, read_ts_int, include_null_group)
            if cacheable:
                td.key_distinct_cache[mk] = (td.generation, k)
            return k

    @staticmethod
    def _key_max_multiplicity_locked(td: TableData, cols: tuple,
                                     read_ts_int: int,
                                     include_null_group: bool = False
                                     ) -> int:
        dense = ColumnStore._dense_key_multiplicity(
            td, cols, read_ts_int, include_null_group)
        if dense is not None:
            return dense
        parts: list[list[np.ndarray]] = [[] for _ in cols]
        null_rows = 0
        for chunk in td.chunks:
            live = chunk.live_mask(read_ts_int)
            m = live.copy()
            for c in cols:
                m = m & chunk.valid[c]
            if include_null_group:
                null_rows += int((live & ~m).sum())
            for i, c in enumerate(cols):
                parts[i].append(chunk.data[c][m])
        if not parts or not parts[0]:
            return null_rows
        cat = [np.concatenate(p) for p in parts]
        n = len(cat[0])
        if n == 0:
            return null_rows
        order = np.lexsort(tuple(reversed(cat)))
        change = np.zeros(n, dtype=bool)
        change[0] = True
        for c in cat:
            s = c[order]
            change[1:] |= s[1:] != s[:-1]
        starts = np.flatnonzero(change)
        runs = np.diff(np.append(starts, n))
        return max(int(runs.max()), null_rows)

    # widest key domain counted directly (one int64 counter a key)
    DENSE_KEY_DOMAIN = 1 << 22

    @staticmethod
    def _dense_key_multiplicity(td: TableData, cols: tuple,
                                read_ts_int: int,
                                include_null_group: bool):
        """_key_max_multiplicity_locked without the sort, for integer
        keys (dictionary codes among them) whose zone maps bound a
        small domain: a chunk at a time, the keys become one mixed-
        radix index and np.bincount counts them. A GROUP BY over two
        flags of a 60M-row table is a second, not a 60M-row lexsort.
        None where a key is not an integer or the domain is wide."""
        los, dims = [], []
        for c in cols:
            lo = hi = None
            for chunk in td.chunks:
                if chunk.data[c].dtype.kind not in "iu":
                    return None
                zlo, zhi, _, nvalid = chunk.zone(c)
                if nvalid:
                    lo = zlo if lo is None else min(lo, zlo)
                    hi = zhi if hi is None else max(hi, zhi)
            if lo is None:
                lo = hi = 0   # no valid value anywhere
            los.append(lo)
            dims.append(hi - lo + 1)
        domain = 1
        for d in dims:
            domain *= d
        if domain > ColumnStore.DENSE_KEY_DOMAIN:
            return None
        counts = np.zeros(domain, dtype=np.int64)
        null_rows = 0
        for chunk in td.chunks:
            live = chunk.live_mask(read_ts_int)
            m = live
            for c in cols:
                m = m & chunk.valid[c]
            if include_null_group:
                null_rows += int(live.sum()) - int(m.sum())
            idx = np.zeros(chunk.n, dtype=np.int64)
            for c, lo, d in zip(cols, los, dims):
                idx = idx * d + (chunk.data[c].astype(np.int64) - lo)
            counts += np.bincount(idx[m], minlength=domain)
        return max(int(counts.max()), null_rows)

    def key_int_range(self, name: str, col: str):
        """(min, max, count) of an int-family key column over ALL
        versions (NULLs excluded), or None when empty. Sizes the
        direct-address join table (ops/join.py): the all-versions
        range is a superset of every snapshot's, so a table sized by
        it is correct at any read ts — and the result caches per
        generation (like key_distinct_cache)."""
        td = self.table(name)
        with self._lock:
            self._seal_locked(td)
            ck = ("__int_range__", col)
            hit = td.key_distinct_cache.get(ck)
            if hit is not None and hit[0] == td.generation:
                return hit[1]
            lo = hi = None
            n = 0
            for chunk in td.chunks:
                # the seal-time zone map is this chunk's answer
                cmin, cmax, _, nvalid = chunk.zone(col)
                if not nvalid:
                    continue
                if cmin is None:
                    raise TypeError(f"{name}.{col} has no integer range")
                cmin, cmax = int(cmin), int(cmax)
                lo = cmin if lo is None else min(lo, cmin)
                hi = cmax if hi is None else max(hi, cmax)
                n += nvalid
            out = None if lo is None else (lo, hi, n)
            td.key_distinct_cache[ck] = (td.generation, out)
            return out

    # -- GC ------------------------------------------------------------------
    def gc(self, name: str, threshold: Timestamp) -> int:
        """Drop row versions deleted before `threshold` (the analogue of
        the MVCC GC queue, kvserver/mvcc_gc_queue.go)."""
        td = self.table(name)
        ti = threshold.to_int()
        removed = 0
        with self._lock:
            new_chunks = []
            for chunk in td.chunks:
                keep = chunk.mvcc_del > ti
                drop = int((~keep).sum())
                if drop == 0:
                    new_chunks.append(chunk)
                    continue
                removed += drop
                if keep.any():
                    # compaction: the rebuilt chunk recomputes its
                    # write-time summaries (zones, blooms, sketches,
                    # MVCC window) — the invalidation story is
                    # "rebuild recomputes", never "patch in place"
                    nc = Chunk(
                        data={k: v[keep] for k, v in chunk.data.items()},
                        valid={k: v[keep] for k, v in chunk.valid.items()},
                        mvcc_ts=chunk.mvcc_ts[keep],
                        mvcc_del=chunk.mvcc_del[keep],
                        n=int(keep.sum()),
                        rowid=(chunk.rowid[keep]
                               if chunk.rowid is not None else None))
                    nc.finalize_stats()
                    new_chunks.append(nc)
            td.chunks = new_chunks
            td.pk_index = None
            td.generation += 1
        return removed


