"""Small exact helpers shared by the statement references."""

from __future__ import annotations

import numpy as np


def group_sum(keys: np.ndarray, values: np.ndarray):
    """(distinct keys ascending, int64 sum of values per key)."""
    if len(keys) == 0:
        return keys.astype(np.int64), values.astype(np.int64)
    if np.any(keys[1:] < keys[:-1]):
        order = np.argsort(keys, kind="stable")
        keys, values = keys[order], values[order]
    starts = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
    return keys[starts], np.add.reduceat(values.astype(np.int64), starts)


def top_rows(sort_keys: tuple, limit: int) -> list:
    """Row indices of the first `limit` rows ordered by sort_keys, the
    first key most significant, each ascending."""
    return np.lexsort(tuple(reversed(sort_keys)))[:limit].tolist()
