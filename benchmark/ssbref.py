"""What the thirteen SSB references (statements/ssb_q*.py) share: the
star join by position, predicates on a dimension, and the grouped
integer sum. Plain numpy on the arrays generators/ssb.py made; imports
nothing of the program."""

from __future__ import annotations

import numpy as np

from refutil import group_sum

KEY = {"customer": "lo_custkey", "supplier": "lo_suppkey",
       "part": "lo_partkey", "date": "lo_orderdate"}


def rows_of(tables, table: str) -> np.ndarray:
    """For every lineorder row, the row of `table` it joins: customer,
    supplier and part keys are 1..n in row order, a datekey is found in
    the sorted d_datekey."""
    keys = tables["lineorder"][0][KEY[table]]
    if table == "date":
        return np.searchsorted(tables["date"][0]["d_datekey"], keys)
    return keys - 1


def equal(tables, table: str, col: str, *values) -> np.ndarray:
    """Over the rows of the dimension: col is one of `values`."""
    cols, dicts = tables[table]
    if col in dicts:
        values = [dicts[col].index(v) for v in values if v in dicts[col]]
    return np.isin(cols[col], values)


def between(tables, table: str, col: str, lo, hi) -> np.ndarray:
    """Over the rows of the dimension: lo <= col <= hi, a string column
    by the order of its text."""
    cols, dicts = tables[table]
    if col in dicts:
        ok = np.array([lo <= v <= hi for v in dicts[col]])
        return ok[cols[col]]
    return (cols[col] >= lo) & (cols[col] <= hi)


def star(tables, **dim_masks) -> np.ndarray:
    """Over the rows of lineorder: the row joins, in every dimension
    named, a row its mask keeps."""
    m = np.ones(len(tables["lineorder"][0]["lo_orderkey"]), dtype=bool)
    for table, mask in dim_masks.items():
        m &= mask[rows_of(tables, table)]
    return m


def grouped_sum(tables, mask: np.ndarray, keys: list,
                values: np.ndarray) -> list:
    """[[key..., sum], ...] over the masked lineorder rows, one row a
    distinct key tuple. A key is (table, column); a string key comes
    back as its text."""
    codes, width = [], []
    for table, col in keys:
        c = tables[table][0][col][rows_of(tables, table)[mask]]
        codes.append(c.astype(np.int64))
        width.append(int(c.max()) + 1 if len(c) else 1)
    flat = np.zeros(int(mask.sum()), dtype=np.int64)
    for c, w in zip(codes, width):
        flat = flat * w + c
    found, sums = group_sum(flat, values[mask])
    out = [[int(s)] for s in sums.tolist()]
    for (table, col), w in zip(reversed(keys), reversed(width)):
        part, found = found % w, found // w
        names = tables[table][1].get(col)
        for row, k in zip(out, part.tolist()):
            row.insert(0, names[k] if names is not None else int(k))
    return out
