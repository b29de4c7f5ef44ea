"""What the references of `tpch_sf1_full.nested`'s statements
(statements/nested_q*.py) share: a date some months later, a dense
lookup by key, LIKE over a dictionary, a grouped count. Plain numpy on
the arrays generators/tpch_full.py made; imports nothing of the
program."""

from __future__ import annotations

import datetime

import numpy as np

from refutil import group_sum


def months_later(iso: str, months: int) -> str:
    """The ISO date `months` calendar months after a first of a month."""
    d = datetime.date.fromisoformat(iso)
    m = d.month - 1 + months
    return d.replace(year=d.year + m // 12, month=m % 12 + 1).isoformat()


def by_key(keys: np.ndarray, values: np.ndarray, fill=0) -> np.ndarray:
    """Dense array a with a[key] = value (keys are small positive
    integers, each once)."""
    out = np.full(int(keys.max()) + 1, fill, dtype=np.asarray(values).dtype)
    out[keys] = values
    return out


def like(text: str, *parts: str) -> bool:
    """text LIKE '%part%part%...%': the parts in order, not overlapping."""
    at = 0
    for part in parts:
        at = text.find(part, at)
        if at < 0:
            return False
        at += len(part)
    return True


def dict_mask(cols, dicts, col: str, pred) -> np.ndarray:
    """Over the rows of a table: pred(text of the string column)."""
    ok = np.fromiter((pred(v) for v in dicts[col]), dtype=bool,
                     count=len(dicts[col]))
    return ok[cols[col]]


def group_count(keys: np.ndarray):
    """(distinct keys ascending, rows per key)."""
    return group_sum(keys, np.ones(len(keys), dtype=np.int64))
