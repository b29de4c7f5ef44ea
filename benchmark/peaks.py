"""The table of peaks and the bytes and operations a kernel must move,
computed from shapes. A device kind that is not in peaks.json is an
error, never a default."""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# the columns TPC-H Q6 must read: its three predicates and its product
Q6_COLUMNS = ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json "
            f"(known: {sorted(table)}); add it with its source")
    return table[device_kind]


def stored_width(column: np.ndarray) -> int:
    """Bytes a value of this column takes on the device: the scan plane
    uploads an integer column whose values fit 32 bits as int32
    (exec/scanplane.py narrow32_cols), anything else at its own width."""
    if column.dtype.kind in "iu" and column.dtype.itemsize > 4 and (
            len(column) == 0 or (-2 ** 31 <= int(column.min())
                                 and int(column.max()) < 2 ** 31)):
        return 4
    return column.dtype.itemsize


def q6_bytes(columns: dict) -> int:
    """Bytes Q6 must read: every row of its four columns at the width
    the device holds them in."""
    return sum(len(columns[c]) * stored_width(columns[c])
               for c in Q6_COLUMNS)


def roofline_share(bytes_moved: float, flops: float, seconds: float,
                   device_kind: str) -> dict:
    """Least time the chip could take over the time it took, and which
    of the two peaks bounds it."""
    p = peaks_for(device_kind)
    t_bytes = bytes_moved / p["hbm_bytes_per_s"]
    t_flops = flops / p["bf16_flops_per_s"]
    return {"share": max(t_bytes, t_flops) / seconds,
            "bound": "hbm" if t_bytes >= t_flops else "flops"}
