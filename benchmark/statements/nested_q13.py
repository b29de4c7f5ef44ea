"""Integer reference of nested_q13.sql (TPC-H Q13, customer
distribution: spec 2.4.13)."""

import numpy as np

from tpchref import dict_mask, group_count, like

COLUMNS = ["int", "int"]
TABLES = ("customer", "orders")


def reference(tables, p):
    cust, _ = tables["customer"]
    orders, odicts = tables["orders"]
    keep = ~dict_mask(orders, odicts, "o_comment",
                      lambda s: like(s, p["word1"], p["word2"]))
    per_customer = np.bincount(orders["o_custkey"][keep],
                               minlength=int(cust["c_custkey"].max()) + 1)
    c_count, custdist = group_count(per_customer[cust["c_custkey"]])
    return sorted(zip(c_count.tolist(), custdist.tolist()),
                  key=lambda r: (-r[1], -r[0]))
