"""What the references of `tpcds_sf1_channels.reports`' statements
(statements/ch_q*.py) share: a column with its validity, a dimension row
found by key, the report's date window, a sales line's return found by
its (item, ticket or order) pair, sums a group with SQL's NULL rules,
and the ROLLUP (channel, id) over the channels' rows with every
grouping set computed on its own. Plain numpy and Python integers on
the arrays generators/tpcds_channels.py made; imports nothing of the
program.

A table is (columns, dictionaries) as the generator makes it, or
(columns, dictionaries, validity) where a test blanks values: a column
missing from the validity is all valid. A value is an integer (money in
hundredths), a text, or None for SQL NULL."""

from __future__ import annotations

import datetime

import numpy as np

EPOCH = datetime.date(1970, 1, 1)


def col(tables, table: str, name: str) -> tuple:
    """(data, validity) of a column."""
    t = tables[table]
    data = t[0][name]
    valid = t[2].get(name) if len(t) > 2 else None
    if valid is None:
        valid = np.ones(len(data), dtype=bool)
    return data, valid


def find(tables, table: str, key: str, fk: tuple) -> tuple:
    """(row of the dimension, found) for each foreign key value (a
    (data, validity) pair): the dimension's key column, ascending, is
    searched; a NULL or absent key finds nothing."""
    keys, kvalid = col(tables, table, key)
    data, valid = fk
    r = np.clip(np.searchsorted(keys, data), 0, len(keys) - 1)
    return r, valid & (keys[r] == data) & kvalid[r]


def window(tables, start: str, days: int) -> np.ndarray:
    """Over date_dim's rows: d_date BETWEEN start AND start + days."""
    lo = (datetime.date.fromisoformat(start) - EPOCH).days
    d, v = col(tables, "date_dim", "d_date")
    return v & (d >= lo) & (d <= lo + days)


def in_window(tables, fk: tuple, start: str, days: int) -> np.ndarray:
    """Fact rows whose date key joins a day of the window."""
    r, ok = find(tables, "date_dim", "d_date_sk", fk)
    return ok & window(tables, start, days)[r]


def returns_of(tables, sales: str, returns: str, sale_key: tuple,
               return_key: tuple) -> tuple:
    """(row of the return, found) for each sales line: the return whose
    (item, ticket or order) equals the line's, both valid (the pair is
    a key of the returns table)."""
    ri, rv = col(tables, returns, return_key[0])
    rt, rtv = col(tables, returns, return_key[1])
    si, sv = col(tables, sales, sale_key[0])
    st, stv = col(tables, sales, sale_key[1])
    big = np.int64(1) << 32
    rk = np.where(rv & rtv, ri * big + rt, -1)
    order = np.argsort(rk, kind="stable")
    sk = si * big + st
    pos = np.clip(np.searchsorted(rk[order], sk), 0, len(rk) - 1)
    row = order[pos]
    return row, sv & stv & (rk[row] == sk) & (rk[row] >= 0)


def sums(groups: np.ndarray, live: np.ndarray, measures: list) -> dict:
    """{group: [sum of each measure]} over the live rows: `groups` is an
    integer code a row (-1 for a NULL key, a group of its own); a
    measure is (data, validity), its sum skips NULLs and is None where
    a group has no valid value (SQL's SUM)."""
    g = np.asarray(groups)[live].astype(np.int64)
    if not len(g):
        return {}
    order = np.argsort(g, kind="stable")
    g = g[order]
    starts = np.flatnonzero(np.r_[True, g[1:] != g[:-1]])
    out = {int(k): [] for k in g[starts]}
    for d, v in measures:
        d = np.asarray(d)[live][order].astype(np.int64)
        v = np.asarray(v)[live][order]
        total = np.add.reduceat(np.where(v, d, 0), starts)
        count = np.add.reduceat(v.astype(np.int64), starts)
        for k, t, c in zip(g[starts].tolist(), total.tolist(),
                           count.tolist()):
            out[k].append(int(t) if c else None)
    return out


def codes(tables, table: str, name: str, rows: np.ndarray,
          found: np.ndarray) -> np.ndarray:
    """A dimension column's codes (or integers) at `rows`, -1 where the
    row was not found or the value is NULL."""
    data, valid = col(tables, table, name)
    return np.where(found & valid[rows], data[rows], -1).astype(np.int64)


def label(tables, table: str, name: str, code: int, prefix=None):
    """A group key's value: a string column's text (after `prefix`, as
    `'store' || s_store_id` makes it), an integer as it is, None for
    -1 (NULL)."""
    if code == -1:
        return None
    values = tables[table][1].get(name)
    if values is None:
        return int(code)
    return (prefix or "") + values[code]


def add(a, b):
    """a + b under SQL's NULL rules."""
    return None if a is None or b is None else a + b


def sub(a, b):
    return None if a is None or b is None else a - b


def coalesce0(a):
    return 0 if a is None else a


def rollup(rows: list, coalesced: bool, rolled_id) -> list:
    """GROUP BY ROLLUP (channel, id) over the channels' rows [(channel,
    id, [sales, returns, profit])], each grouping set on its own:
    (channel, id), (channel), (). The statement's ORDER BY channel, id
    and LIMIT 100: in the benchmark's text (`coalesced`) a rolled-up
    channel is 'ALL' and a rolled-up id is `rolled_id` ('ALL' or 0),
    ordered as values; in the specification's, both are None, last."""
    out = []
    for depth in (2, 1, 0):
        acc: dict = {}
        for ch, ident, vals in rows:
            k = (ch, ident)[:depth]
            cur = acc.setdefault(k, [None, None, None])
            for m, v in enumerate(vals):
                if v is not None:
                    cur[m] = v + (cur[m] or 0)
        if depth == 0 and not rows:
            acc[()] = [None, None, None]
        for k, vals in acc.items():
            k = list(k) + [None] * (2 - depth)
            if coalesced:
                k = ["ALL" if k[0] is None else k[0],
                     rolled_id if k[1] is None else k[1]]
            out.append(k + vals)

    def key(r):
        return tuple((v is None, v if v is not None else 0)
                     for v in r[:2])
    out.sort(key=key)
    return out[:100]
