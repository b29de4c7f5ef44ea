"""What the references of `tpcds_sf1.rollup`'s statements
(statements/ds_q*.py) share: the star join by key, a dimension's
predicate, and every grouping set of a ROLLUP computed on its own from
the fact rows, with integer sums. Plain numpy on the arrays
generators/tpcds.py made; imports nothing of the program."""

from __future__ import annotations

import numpy as np

from refutil import group_sum

FK = {"date_dim": ("ss_sold_date_sk", "d_date_sk"),
      "item": ("ss_item_sk", "i_item_sk"),
      "store": ("ss_store_sk", "s_store_sk"),
      "customer_demographics": ("ss_cdemo_sk", "cd_demo_sk")}


def equal(tables, table: str, col: str, *values) -> np.ndarray:
    """Over the rows of the dimension: col is one of `values` (a
    string column by its text)."""
    cols, dicts = tables[table]
    if col in dicts:
        values = [dicts[col].index(v) for v in values if v in dicts[col]]
    return np.isin(cols[col], values)


def star(tables, **masks) -> tuple:
    """(mask over store_sales, {dimension: its row for each fact row}):
    the fact rows whose key in every dimension named joins a row that
    dimension's mask keeps (keys are found in the sorted key column)."""
    ss, _ = tables["store_sales"]
    m = np.ones(len(ss["ss_item_sk"]), dtype=bool)
    rows = {}
    for table, mask in masks.items():
        fk, pk = FK[table]
        keys = tables[table][0][pk]
        r = np.clip(np.searchsorted(keys, ss[fk]), 0, len(keys) - 1)
        m &= (keys[r] == ss[fk]) & mask[r]
        rows[table] = r
    return m, rows


def key(tables, table: str, col: str, rows: np.ndarray) -> tuple:
    """(int64 codes of a key for each fact row, the dictionary's texts
    or None for an integer column)."""
    cols, dicts = tables[table]
    return cols[col][rows].astype(np.int64), dicts.get(col)


def rollup(keys: list, mask: np.ndarray, values: list, depth=None) -> list:
    """Every grouping set of ROLLUP(keys), the longest first, each on
    its own from the masked fact rows: [(set length, key values with
    None where rolled up, [sum of each of `values`], row count)], a
    key value a text or an int. `depth`: the set lengths to make."""
    out = []
    codes = [k[mask] for k, _ in keys]
    vals = [v[mask].astype(np.int64) for v in values]
    n = int(mask.sum())
    lens = range(len(keys), -1, -1) if depth is None else depth
    for m in lens:
        flat = np.zeros(n, dtype=np.int64)
        widths = []
        for c in codes[:m]:
            lo, w = (int(c.min()), int(c.max() - c.min()) + 1) if n else (0, 1)
            flat = flat * w + (c - lo)
            widths.append((lo, w))
        found, counts = group_sum(flat, np.ones(n, dtype=np.int64))
        sums = [group_sum(flat, v)[1] for v in vals]
        if m == 0 and not n:
            out.append((0, [None] * len(keys), [None] * len(vals), 0))
            continue
        parts = []
        rest = found
        for lo, w in reversed(widths):
            parts.append(rest % w + lo)
            rest = rest // w
        parts.reverse()
        for g in range(len(found)):
            ks = []
            for j, (_, names) in enumerate(keys):
                if j >= m:
                    ks.append(None)
                    continue
                v = int(parts[j][g])
                ks.append(names[v] if names is not None else v)
            out.append((m, ks, [int(s[g]) for s in sums], int(counts[g])))
    return out


def rank_desc(values: list) -> list:
    """rank() over `values` ordered descending: 1 + how many are
    greater."""
    order = sorted(values, reverse=True)
    first: dict = {}
    for i, v in enumerate(order):
        first.setdefault(v, i + 1)
    return [first[v] for v in values]
