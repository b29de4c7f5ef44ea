"""Device-resident columnar batch: the analogue of ``coldata.Batch``.

The reference's batch (pkg/col/coldata/batch.go:30) is a set of typed
column vectors plus an optional *selection vector* of live row indices
(batch.go:53-55): filters produce selection vectors instead of
compacting. On TPU, gathered index vectors create dynamic shapes, so we
use the mask formulation (SURVEY.md §7 "Dynamic shapes"): every batch
carries a boolean ``sel`` mask of live rows, and every column carries a
boolean validity mask (NULL handling, coldata/nulls.go). All arrays have
the same static leading dimension ``n`` — XLA sees only static shapes.

A ColumnBatch is a pytree, so it passes through jit/shard_map/scan
untouched. Column order is the tuple ``names`` (static / hashable).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import tracing
from ..utils.metric import Counter

# What one statement sends across the host/device boundary, counted
# where the call is made. ops/ has no registry of its own: Engine
# exposes these as exec.dispatch.programs and
# exec.transfer.{h2d,d2h}.{calls,bytes} (as exec.pallas.* reads
# ops/pallas's tallies), so they are process-wide, always on.
PROGRAMS = Counter("exec.dispatch.programs")
H2D_CALLS = Counter("exec.transfer.h2d.calls")
H2D_BYTES = Counter("exec.transfer.h2d.bytes")
D2H_CALLS = Counter("exec.transfer.d2h.calls")
D2H_BYTES = Counter("exec.transfer.d2h.bytes")
# what a dispatched statement's joins run over (exec/compile.py
# JoinStats: trace-time shapes, counted once a dispatch)
JOINS = Counter("exec.join.joins")
JOIN_BUILD_ROWS = Counter("exec.join.build_rows")
JOIN_PROBE_ROWS = Counter("exec.join.probe_rows")
# what a dispatched statement's grouping-set Aggregates and Windows run
# over (JoinStats.site_totals, the same trace-time shapes), by name
SITE_ROWS = {"exec.agg.rollup.rows": Counter("exec.agg.rollup.rows"),
             "exec.window.rows": Counter("exec.window.rows")}
# row-length arguments of a 64-bit element type among the scan batches
# of a prepared statement (Engine._prepare_select): each is an
# X64SplitHigh/Low pass over every row of every execution on a TPU
SCAN_WIDE_ARGS = Counter("exec.scan.wide_args")


@jax.tree_util.register_pytree_node_class
@dataclass
class ColumnBatch:
    """A fixed-length slab of columns + selection mask.

    data:  tuple of arrays, each shape (n,) (or (n, k) for arena bytes)
    valid: tuple of bool arrays shape (n,), True = non-NULL
    sel:   bool array shape (n,), True = row is live
    names: tuple of column names (aux data, static under jit)
    """

    data: tuple
    valid: tuple
    sel: jnp.ndarray
    names: tuple

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        return (self.data, self.valid, self.sel), self.names

    @classmethod
    def tree_unflatten(cls, names, children):
        data, valid, sel = children
        return cls(data=data, valid=valid, sel=sel, names=names)

    # -- constructors ------------------------------------------------------
    @staticmethod
    def from_dict(cols: Mapping[str, jnp.ndarray],
                  valid: Mapping[str, jnp.ndarray] | None = None,
                  sel: jnp.ndarray | None = None) -> "ColumnBatch":
        names = tuple(cols.keys())
        data = tuple(jnp.asarray(cols[n]) for n in names)
        if not data:
            raise ValueError("ColumnBatch needs at least one column")
        n = data[0].shape[0]
        if valid is None:
            valid = {}
        vmasks = tuple(
            jnp.asarray(valid[c], dtype=jnp.bool_) if c in valid
            else jnp.ones((n,), dtype=jnp.bool_)
            for c in names)
        if sel is None:
            sel = jnp.ones((n,), dtype=jnp.bool_)
        return ColumnBatch(data=data, valid=vmasks, sel=sel, names=names)

    # -- accessors ---------------------------------------------------------
    @property
    def n(self) -> int:
        return self.data[0].shape[0]

    def index(self, name: str) -> int:
        try:
            return self.names.index(name)
        except ValueError:
            raise KeyError(f"column {name!r} not in batch {self.names}") from None

    def col(self, name: str) -> jnp.ndarray:
        return self.data[self.index(name)]

    def col_valid(self, name: str) -> jnp.ndarray:
        return self.valid[self.index(name)]

    def has(self, name: str) -> bool:
        return name in self.names

    # -- functional updates ------------------------------------------------
    def with_sel(self, sel: jnp.ndarray) -> "ColumnBatch":
        return ColumnBatch(self.data, self.valid, sel, self.names)

    def and_sel(self, mask: jnp.ndarray) -> "ColumnBatch":
        """Apply a filter: narrow the selection (the reference's filter ops
        produce selection vectors the same way, colexecsel)."""
        return self.with_sel(jnp.logical_and(self.sel, mask))

    def with_column(self, name: str, data: jnp.ndarray,
                    valid: jnp.ndarray | None = None) -> "ColumnBatch":
        """Add or replace a column (projection output)."""
        if valid is None:
            valid = jnp.ones((self.n,), dtype=jnp.bool_)
        if name in self.names:
            i = self.index(name)
            datas = list(self.data)
            valids = list(self.valid)
            datas[i] = data
            valids[i] = valid
            return ColumnBatch(tuple(datas), tuple(valids), self.sel, self.names)
        return ColumnBatch(self.data + (data,), self.valid + (valid,),
                           self.sel, self.names + (name,))

    def project(self, names: Iterable[str]) -> "ColumnBatch":
        names = tuple(names)
        idx = [self.index(n) for n in names]
        return ColumnBatch(tuple(self.data[i] for i in idx),
                           tuple(self.valid[i] for i in idx),
                           self.sel, names)

    def rename(self, mapping: Mapping[str, str]) -> "ColumnBatch":
        names = tuple(mapping.get(n, n) for n in self.names)
        return ColumnBatch(self.data, self.valid, self.sel, names)

    # -- host conversion ---------------------------------------------------
    def to_host(self) -> dict[str, np.ndarray]:
        """Compact live rows to host numpy (gateway/result edge only).

        Every device->host transfer is its own synchronisation, and
        jax.device_get does NOT coalesce (21 arrays = 21 transfers
        for a 100-row result). So: bitcast-pack every column into ONE
        uint8 buffer on device and pull it with a single transfer;
        for wide batches pull the sel mask first and gather only the
        live rows so the packed pull moves live bytes, not padded
        bytes."""
        pulled, _ = pull_batch_columns(
            self, list(self.names), with_valid=True)
        out = {}
        for name in self.names:
            dn, vn = pulled[name]
            out[name] = np.ma.masked_array(dn, mask=~vn)
        return out

    def __repr__(self) -> str:
        return f"ColumnBatch(n={self.n}, cols={list(self.names)})"


# -- the MVCC pair on the device ---------------------------------------------
#
# The store keeps a row version's two timestamps as int64
# (storage/columnstore.py). The TPU has no 64-bit integers: an s64[n]
# argument is split by two custom calls that fuse with nothing, 24 B a
# row moved to prepare a compare of 16 (33 % of the device in
# tpch_sf10_scan.scan1, PERF.md PR 34). So a device batch holds each
# timestamp as two 32-bit word columns, written so on the host: the
# high word signed, the low word unsigned, 1-D and contiguous (a minor
# dimension of 2 pads to 128 lanes). Every producer of a scan batch
# writes them through the host half below, the one consumer
# (exec/compile.py _compile_scan) compares them through mvcc_live, and
# the statement's read timestamp travels as read_ts_words.

MVCC_TS_HI, MVCC_TS_LO = "_mvcc_ts_hi", "_mvcc_ts_lo"
MVCC_DEL_HI, MVCC_DEL_LO = "_mvcc_del_hi", "_mvcc_del_lo"
MVCC_COLUMNS = (MVCC_TS_HI, MVCC_TS_LO, MVCC_DEL_HI, MVCC_DEL_LO)
# a padding row is created at NEVER_TS, past every read timestamp; a
# row made on the way (a DistSQL pseudo-table's, a composed CTE's) is
# never deleted: MAX_TS. Stored rows carry the store's own values.
NEVER_TS = 2 ** 62
MAX_TS = 2 ** 63 - 1


def ts_words(ts: int) -> tuple[np.int32, np.uint32]:
    """(high word, low word) of one int64 timestamp."""
    ts = int(ts)
    if not -2 ** 63 <= ts < 2 ** 63:
        raise OverflowError(f"timestamp {ts} is not an int64")
    return np.int32(ts >> 32), np.uint32(ts & 0xFFFFFFFF)


def read_ts_words(ts: int) -> np.ndarray:
    """A statement's read timestamp as a program takes it: uint32[2],
    the high word's bits then the low word. One host value, so one
    transfer a dispatch, and no 64-bit scalar for the TPU to split."""
    hi, lo = ts_words(ts)
    return np.array([hi.view(np.uint32), lo], dtype=np.uint32)


def alloc_mvcc_words(n: int) -> dict[str, np.ndarray]:
    """Unwritten host buffers of the four word columns for n rows."""
    return {MVCC_TS_HI: np.empty(n, np.int32),
            MVCC_TS_LO: np.empty(n, np.uint32),
            MVCC_DEL_HI: np.empty(n, np.int32),
            MVCC_DEL_LO: np.empty(n, np.uint32)}


def put_mvcc_words(bufs: Mapping[str, np.ndarray], at: int,
                   ts: np.ndarray, dl: np.ndarray) -> None:
    """Write the words of the int64 timestamps `ts` / `dl` (a chunk's
    mvcc_ts / mvcc_del, or a slice of them) into rows [at, at + len)
    of the word buffers: each word straight into its place, no int64
    copy of the column in between."""
    for hi, lo, src in ((MVCC_TS_HI, MVCC_TS_LO, ts),
                        (MVCC_DEL_HI, MVCC_DEL_LO, dl)):
        src = np.asarray(src, dtype=np.int64)
        end = at + len(src)
        np.right_shift(src, 32, out=bufs[hi][at:end], casting="unsafe")
        np.bitwise_and(src, 0xFFFFFFFF, out=bufs[lo][at:end],
                       casting="unsafe")


def fill_mvcc_words(bufs: Mapping[str, np.ndarray], start: int,
                    stop: int, ts: int, dl: int) -> None:
    """Rows [start, stop) of the word buffers all created at `ts` and
    deleted at `dl`: (NEVER_TS, 0) is padding no statement sees,
    (0, MAX_TS) a row every statement sees."""
    for hi, lo, v in ((MVCC_TS_HI, MVCC_TS_LO, ts),
                      (MVCC_DEL_HI, MVCC_DEL_LO, dl)):
        bufs[hi][start:stop], bufs[lo][start:stop] = ts_words(v)


def const_mvcc_words(n: int, ts: int, dl: int) -> dict[str, np.ndarray]:
    """The four word columns of n rows with one (ts, dl)."""
    bufs = alloc_mvcc_words(n)
    fill_mvcc_words(bufs, 0, n, ts, dl)
    return bufs


def mvcc_live(raw: ColumnBatch, read_ts) -> jnp.ndarray:
    """Snapshot visibility of each row of a scan batch at `read_ts`
    (read_ts_words): mvcc_ts <= read_ts < mvcc_del, the same bit as
    the int64 compare for every int64 triple. High words compare
    signed, low words unsigned."""
    r_hi = jax.lax.bitcast_convert_type(read_ts[0], jnp.int32)
    r_lo = read_ts[1]
    t_hi, t_lo = raw.col(MVCC_TS_HI), raw.col(MVCC_TS_LO)
    d_hi, d_lo = raw.col(MVCC_DEL_HI), raw.col(MVCC_DEL_LO)
    created = (t_hi < r_hi) | ((t_hi == r_hi) & (t_lo <= r_lo))
    not_deleted = (r_hi < d_hi) | ((r_hi == d_hi) & (r_lo < d_lo))
    return created & not_deleted


# -- single-transfer device->host pulls -------------------------------------
#
# A result is pulled with one transfer, not one per array. Everything
# below funnels into pull_arrays(): one jitted bitcast-pack to a uint8
# buffer, one transfer, host-side views.

def _to_bytes(a: jnp.ndarray) -> jnp.ndarray:
    if a.dtype == jnp.bool_:
        a = a.astype(jnp.uint8)
    if a.dtype != jnp.uint8:
        a = jax.lax.bitcast_convert_type(a, jnp.uint8)
    return a.reshape(-1)


@jax.jit
def _pack(arrs):
    # `harness`: device work of the result path, not of a plan operator
    with jax.named_scope("harness"):
        return jnp.concatenate([_to_bytes(a) for a in arrs])


@jax.jit
def _any(a):
    with jax.named_scope("harness"):
        return jnp.any(a)


def flag_any(a) -> jnp.ndarray:
    """A sentinel column reduced to one device scalar (its own small
    program, dispatched now, pulled with the result)."""
    PROGRAMS.inc()
    return _any(a)


def _np_dtype(dt) -> np.dtype:
    return np.dtype(bool) if dt == jnp.bool_ else np.dtype(dt)


def pull_arrays(arrs: list) -> list[np.ndarray]:
    """Fetch device arrays to host with (nearly) ONE transfer: every
    packable array bitcasts to a shared uint8 buffer pulled once.
    float64 is the exception — this TPU backend's X64 rewrite rejects
    f64 bitcast-convert (verified: every variant 500s in compile), so
    f64 arrays transfer individually with async prefetch overlapping
    the packed pull. Accepts numpy arrays transparently (passed
    through) so callers can mix host- and device-resident columns."""
    with tracing.span("pull") as sp:
        out, programs, transfers, nbytes = _pull(arrs)
        if sp is not None:
            sp.tags.update(programs=programs, transfers=transfers,
                           bytes=nbytes)
    if programs:
        PROGRAMS.inc(programs)
    if transfers:
        D2H_CALLS.inc(transfers)
        D2H_BYTES.inc(nbytes)
    return out


def _pull(arrs: list) -> tuple:
    """(host arrays, pack programs dispatched, device-to-host
    transfers, bytes they moved)."""
    metas = []
    packs = []
    singles = []
    for a in arrs:
        if isinstance(a, np.ndarray) or np.isscalar(a):
            metas.append(("host", a))
        elif a.dtype == jnp.float64:
            metas.append(("single", len(singles)))
            singles.append(a)
        else:
            metas.append(("pack", (a.shape, a.dtype)))
            packs.append(a)
    for s in singles:
        try:
            s.copy_to_host_async()
        except Exception:
            pass
    pieces = []
    programs = 0
    nbytes = 0
    if packs:
        if len(packs) == 1 and packs[0].dtype != jnp.bool_:
            # a single non-bool array needs no pack program
            pieces = [np.asarray(packs[0])]
            nbytes += pieces[0].nbytes
        else:
            programs = 1
            flat = np.asarray(_pack(packs))
            nbytes += flat.nbytes
            off = 0
            for kind, m in metas:
                if kind != "pack":
                    continue
                shape, dt = m
                npdt = _np_dtype(dt)
                count = int(np.prod(shape)) if shape else 1
                nb = count * (1 if npdt == np.dtype(bool)
                              else npdt.itemsize)
                chunk = flat[off:off + nb]
                off += nb
                if npdt == np.dtype(bool):
                    pieces.append(chunk.astype(bool).reshape(shape))
                else:
                    pieces.append(chunk.view(npdt).reshape(shape))
    singles_np = [np.asarray(s) for s in singles]
    nbytes += sum(s.nbytes for s in singles_np)
    out = []
    it = iter(pieces)
    for kind, m in metas:
        if kind == "host":
            out.append(m)
        elif kind == "single":
            out.append(singles_np[m])
        else:
            out.append(next(it))
    return out, programs, bool(packs) + len(singles), nbytes


# below this row count a full-width packed pull is cheaper than the
# extra round trip of a sel-first compaction (2^17 rows * ~10 cols *
# 9B ~ 12MB ~ 0.24s at 50MB/s vs +1 RTT ~ 0.08s... the crossover is
# column-count dependent; 2^17 keeps single-RTT for the common result
# shapes while compacting the join-width monsters)
_SMALL_PULL = 1 << 17


# shared helper (one impl for the three former copies here /
# ops/join.py / exec/stmtutil.py); the alias keeps importers of
# batch._pow2 (exec/ctecompose.py) working
from ..utils.num import next_pow2 as _pow2  # noqa: E402


def pull_batch_columns(batch: ColumnBatch, names: list,
                       with_valid: bool = True,
                       sel_np: np.ndarray | None = None,
                       extra: list = ()):
    """Pull the LIVE rows of the named columns in at most two
    transfers. Returns ({name: (data, valid) or data}, extra_pulled)
    where column arrays hold live rows only and extra_pulled are the
    `extra` device scalars/arrays (sentinel flags), fetched in the
    FIRST transfer.

    Wide batches pull sel first (n bytes), then gather the live rows
    on device — with the gather index padded to a power of two so the
    gather+pack program's compile caches across executions whose live
    count drifts — so the packed transfer moves only real data. The
    single shared implementation of the sel-first discipline; keep
    result materialization and CTE ingest on it."""
    n = batch.n
    extra = list(extra)
    datas = [batch.col(c) for c in names]
    valids = [batch.col_valid(c) for c in names] if with_valid else []

    def assemble(pulled, live_mask=None, trim=None):
        out = {}
        for i, c in enumerate(names):
            d = pulled[i]
            v = pulled[len(names) + i] if with_valid else None
            if live_mask is not None:
                d = d[live_mask]
                v = v[live_mask] if v is not None else None
            if trim is not None:
                d = d[:trim]
                v = v[:trim] if v is not None else None
            out[c] = (d, v) if with_valid else d
        return out

    if n <= _SMALL_PULL and sel_np is None:
        pulled = pull_arrays(datas + valids + [batch.sel] + extra)
        tracing.stage("assemble")
        k = len(datas) + len(valids)
        return assemble(pulled, live_mask=pulled[k]), pulled[k + 1:]
    if sel_np is None:
        first = pull_arrays([batch.sel] + extra)
        sel_np, extra_np = first[0], first[1:]
    else:
        extra_np = pull_arrays(extra) if extra else []
    # between the two pulls: the live rows' indices and one eager
    # gather a column
    tracing.stage("gather")
    live = np.flatnonzero(sel_np)
    if len(live) * 2 < n:
        if not len(live):
            empty = {}
            for c, d in zip(names, datas):
                z = np.zeros((0,) + tuple(d.shape[1:]),
                             _np_dtype(d.dtype))
                empty[c] = (z, np.zeros(0, bool)) if with_valid else z
            return empty, extra_np
        padded = max(_pow2(len(live)), 1024)
        idx_np = np.full(padded, live[-1], dtype=np.int32)
        idx_np[:len(live)] = live
        idx = jax.device_put(idx_np)
        H2D_CALLS.inc()
        H2D_BYTES.inc(idx_np.nbytes)
        PROGRAMS.inc(len(datas) + len(valids))  # one eager gather each
        pulled = pull_arrays([jnp.take(a, idx, axis=0)
                              for a in datas + valids])
        tracing.stage("assemble")
        return assemble(pulled, trim=len(live)), extra_np
    pulled = pull_arrays(datas + valids)
    tracing.stage("assemble")
    return assemble(pulled, live_mask=np.asarray(sel_np)), extra_np


def concat(batches: list[ColumnBatch]) -> ColumnBatch:
    """Concatenate batches with identical schemas along rows."""
    first = batches[0]
    data = tuple(jnp.concatenate([b.data[i] for b in batches])
                 for i in range(len(first.names)))
    valid = tuple(jnp.concatenate([b.valid[i] for b in batches])
                  for i in range(len(first.names)))
    sel = jnp.concatenate([b.sel for b in batches])
    return ColumnBatch(data, valid, sel, first.names)


def pad_to(batch: ColumnBatch, n: int) -> ColumnBatch:
    """Pad a batch to a static length with dead rows (sel=False).

    The distribution layer pads every shard to the same static length so
    one SPMD program covers all shards (ranges are never exactly equal;
    the reference handles ragged spans with per-node dynamic batching,
    we handle them with masked padding)."""
    cur = batch.n
    if cur == n:
        return batch
    if cur > n:
        raise ValueError(f"batch of {cur} rows cannot pad to {n}")
    pad = n - cur

    def padarr(a):
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, widths)

    data = tuple(padarr(d) for d in batch.data)
    valid = tuple(padarr(v) for v in batch.valid)
    sel = padarr(batch.sel)
    return ColumnBatch(data, valid, sel, batch.names)
