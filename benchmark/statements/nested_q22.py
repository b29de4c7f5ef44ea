"""Integer reference of nested_q22.sql (TPC-H Q22, global sales
opportunity: spec 2.4.22). The average is compared as integers:
c_acctbal > sum / count  <=>  c_acctbal * count > sum."""

import numpy as np

from refutil import group_sum
from tpchref import group_count

COLUMNS = ["text", "int", "dec2"]
TABLES = ("customer", "orders")


def reference(tables, p):
    cust, cdicts = tables["customer"]
    orders, _ = tables["orders"]
    codes = [str(p[f"i{i}"]) for i in range(1, 8)]
    prefix = np.array([int(s[:2]) for s in cdicts["c_phone"]])[
        cust["c_phone"]]
    listed = np.isin(prefix, [int(c) for c in codes])
    bal = cust["c_acctbal"]
    rich = listed & (bal > 0)
    total, count = int(bal[rich].sum()), int(rich.sum())
    ordered = np.bincount(orders["o_custkey"],
                          minlength=int(cust["c_custkey"].max()) + 1) > 0
    m = listed & (bal * count > total) & ~ordered[cust["c_custkey"]]
    found, sums = group_sum(prefix[m], bal[m])
    _, counts = group_count(prefix[m])
    return [(str(c), int(n), int(s)) for c, n, s in
            zip(found.tolist(), counts.tolist(), sums.tolist())]
