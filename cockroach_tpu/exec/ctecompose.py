"""Composed device-resident execution of CTE / derived-table statements.

The row-path architecture (engine._exec_with_temps) materializes each
CTE body through the host: run the sub-program, pull its live rows to
the host, insert into a temp columnstore table, re-upload for the main
program's scan, and re-plan per execution. That is the right SLOW path
(it feeds stats, join checks, and arbitrary consumers), but a
steady-state prepared statement re-executing against unchanged base
tables pays a pull, an upload and a re-plan for nothing.

This module captures the pieces of one successful slow-path execution
— the sub Prepared programs, the main Prepared program, and the temp
batch shapes the main was compiled against — and composes them into a
single device-resident pipeline:

    sub jfn -> glue (jitted: compact live rows into the temp batch
    shape, synthesize MVCC columns) -> main jfn -> one materialize

No host transfer happens between stages; the only sync is the final
result pull. The reference's analogue is a WithExpr spool feeding its
readers in-memory (sql/opt WithExpr; here the buffer never leaves HBM).

Validity: the composition is only used when every non-temp table's
generation is unchanged and the session holds no transaction — then
the sub's visible rows (and so the temp's row count and dictionary
contents) are identical to the captured run. Any drift, glue overflow,
or sub-program sentinel falls back to the slow path (the glue folds
sub sentinels and the live-count check into a __compact_overflow flag
the engine already knows how to honor).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.batch import (MAX_TS, MVCC_COLUMNS, MVCC_DEL_HI, MVCC_DEL_LO,
                         MVCC_TS_HI, MVCC_TS_LO, NEVER_TS, _pow2,
                         read_ts_words, ts_words)
from .session import SENTINEL_COLUMNS as _SENTINELS


def make_glue(template, cname_to_oname: dict, dict_clip: dict,
              w2: int):
    """Jitted sub-output -> temp-scan-batch adapter.

    template: the captured device batch the main program was compiled
    against (names/dtypes/order are the jit pytree contract).
    cname_to_oname: temp stored column name -> sub output column name.
    dict_clip: temp column -> dictionary length (codes clipped like the
    slow path's ingest).
    w2: the temp batch's padded width (pow2, matches the capture run).
    Returns glue(b) -> (ColumnBatch, overflow_flag_scalar)."""
    names = list(template.names)
    dtypes = {nm: template.col(nm).dtype for nm in names}

    @jax.jit
    def glue(b):
        from ..ops.batch import ColumnBatch
        n = b.n
        sel = b.sel
        live_cnt = jnp.sum(sel.astype(jnp.int32))
        (idx,) = jnp.nonzero(sel, size=w2, fill_value=n)
        row_ok = idx < n
        idx_c = jnp.minimum(idx, n - 1).astype(jnp.int32)
        # a gathered row was created at 1 and never deleted, a slot
        # past the live count is never visible: the MVCC words
        # (ops/batch.py) of (1, MAX_TS) and (NEVER_TS, MAX_TS)
        (live_hi, live_lo), (dead_hi, dead_lo) = \
            ts_words(1), ts_words(NEVER_TS)
        del_hi, del_lo = ts_words(MAX_TS)
        mvcc = {MVCC_TS_HI: jnp.where(row_ok, live_hi, dead_hi),
                MVCC_TS_LO: jnp.where(row_ok, live_lo, dead_lo),
                MVCC_DEL_HI: jnp.full((w2,), del_hi),
                MVCC_DEL_LO: jnp.full((w2,), del_lo)}
        cols, valid = {}, {}
        for nm in names:
            if nm in mvcc:
                cols[nm] = mvcc[nm]
                continue
            oname = cname_to_oname[nm]
            d = jnp.take(b.col(oname), idx_c, axis=0)
            v = jnp.logical_and(jnp.take(b.col_valid(oname), idx_c),
                                row_ok)
            clip = dict_clip.get(nm)
            if clip is not None:
                d = jnp.clip(d.astype(jnp.int32), 0, max(clip - 1, 0))
            d = d.astype(dtypes[nm])
            cols[nm] = d
            valid[nm] = v
        overflow = live_cnt > w2
        for s in _SENTINELS:
            if b.has(s):
                overflow = jnp.logical_or(overflow, jnp.any(b.col(s)))
        return ColumnBatch.from_dict(cols, valid), overflow

    return glue


@dataclass
class _Stage:
    prep: object          # the sub's Prepared
    # one jitted adapter PER consuming alias: prune_scan_columns can
    # give two scans of the same CTE different column subsets, so
    # each alias gets a glue shaped to ITS captured template
    glues: list           # [(alias, glue_fn), ...]


@dataclass
class ComposedCTE:
    engine: object
    session: object
    base_gens: tuple      # ((table, generation), ...) — temps excluded
    stages: list
    main: object          # the main Prepared

    def valid(self) -> bool:
        if self.session.txn is not None or self.session.effects:
            return False
        store = self.engine.store
        for t, g in self.base_gens:
            td = store.tables.get(t)
            if td is None or td.generation != g:
                return False
        return True

    def dispatch(self, read_ts=None):
        """Launch the whole pipeline asynchronously; returns the final
        device batch (sentinel-annotated). Nothing blocks — a caller
        can pipeline several dispatches before syncing."""
        eng = self.engine
        ts = read_ts or eng._read_ts(self.session)
        tsv = read_ts_words(ts.to_int())
        one, zero = np.int32(1), np.int32(0)
        scans = dict(self.main.scans)
        flags = []
        for st in self.stages:
            sub_out = st.prep.jfn(st.prep.scans, tsv, one, zero)
            for a, glue in st.glues:
                batch, ovf = glue(sub_out)
                flags.append(ovf)
                scans[a] = batch
        out = self.main.jfn(scans, tsv, one, zero)
        flag = flags[0]
        for f in flags[1:]:
            flag = jnp.logical_or(flag, f)
        if out.has("__compact_overflow"):
            flag = jnp.logical_or(flag,
                                  jnp.any(out.col("__compact_overflow")))
        return out.with_column("__compact_overflow",
                               jnp.broadcast_to(flag, (out.n,)))

    def run(self, read_ts=None):
        out = self.dispatch(read_ts)
        return self.engine._materialize(out, self.main.meta)


def build_composition(engine, session, capture) -> ComposedCTE | None:
    """Assemble a ComposedCTE from one successful slow-path capture,
    or None when the shape can't compose (row-path temps, streaming,
    AS OF, temp-on-temp dependencies, fastpath mains)."""
    if (not capture or capture.get("disabled") or not capture["temps"]
            or not capture["preps"]):
        return None
    main = capture["preps"][-1]
    if main.stream is not None or main.as_of is not None:
        return None
    # parameterized programs (statement-shape plan cache) take their
    # literals as a 5th runtime arg; the composed dispatch is a fixed
    # 4-arg pipeline, so keep the slow path for those
    if any(getattr(p, "params", ()) for p in capture["preps"]):
        return None
    scan_tables = getattr(main, "scan_tables", None)
    if not scan_tables:
        return None
    temp_names = {t["tname"] for t in capture["temps"]}
    for t in capture["temps"]:
        p = t["prep"]
        if p.stream is not None or p.as_of is not None:
            return None
        if any(tb in temp_names for tb, _ in p.gens):
            return None  # temp scanning another temp: keep slow path
    base = {}
    for p in [main] + [t["prep"] for t in capture["temps"]]:
        for tb, g in p.gens:
            if tb in temp_names:
                continue
            if base.get(tb, g) != g:
                return None
            base[tb] = g
    stages = []
    temp_aliases = []
    for t in capture["temps"]:
        aliases = [a for a, tn in scan_tables.items()
                   if tn == t["tname"]]
        if not aliases:
            continue  # CTE declared but never scanned by the main
        meta = t["meta"]
        cname_to_oname = dict(zip(t["names"], meta.names))
        dict_clip = {}
        for cname, oname in cname_to_oname.items():
            d = meta.dictionaries.get(oname)
            if d is not None:
                dict_clip[cname] = len(d)
        w2 = max(_pow2(max(t["rows"], 1)), 1024)
        glues = []
        for a in aliases:
            template = main.scans.get(a)
            if template is None:
                return None
            if any(nm not in cname_to_oname
                   for nm in template.names
                   if nm not in MVCC_COLUMNS):
                return None
            if w2 != template.n:
                return None  # shape drift vs main's compiled input
            glues.append((a, make_glue(template, cname_to_oname,
                                       dict_clip, w2)))
        stages.append(_Stage(prep=t["prep"], glues=glues))
        temp_aliases.extend(aliases)
    if not stages:
        return None
    # release the dropped temps' captured upload batches: the temp
    # tables were dropped (and their HBM reservation released) by
    # _exec_with_temps' cleanup, so holding the device arrays here
    # would keep untracked HBM resident — every composed dispatch
    # replaces these entries anyway
    for a in temp_aliases:
        main.scans[a] = None
    return ComposedCTE(engine=engine, session=session,
                       base_gens=tuple(sorted(base.items())),
                       stages=stages, main=main)
