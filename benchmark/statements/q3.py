"""Integer reference of q3.sql (TPC-H Q3, shipping priority)."""

import numpy as np

from generators.tpch import days
from refutil import group_sum, top_rows

COLUMNS = ["int", "dec4", "date", "int"]
TABLES = ("lineitem", "orders", "customer")


def reference(tables, p):
    li, _ = tables["lineitem"]
    orders, _ = tables["orders"]
    cust, cdicts = tables["customer"]
    cut = days(p["date"])
    in_segment = np.zeros(int(cust["c_custkey"].max()) + 1, dtype=bool)
    in_segment[cust["c_custkey"]] = (
        cust["c_mktsegment"] == cdicts["c_mktsegment"].index(p["segment"]))
    om = (orders["o_orderdate"] < cut) & in_segment[orders["o_custkey"]]
    n_keys = int(orders["o_orderkey"].max()) + 1
    order_ok = np.zeros(n_keys, dtype=bool)
    order_ok[orders["o_orderkey"][om]] = True
    order_row = np.zeros(n_keys, dtype=np.int64)
    order_row[orders["o_orderkey"]] = np.arange(len(orders["o_orderkey"]))
    lm = (li["l_shipdate"] > cut) & order_ok[li["l_orderkey"]]
    keys, revenue = group_sum(
        li["l_orderkey"][lm],
        li["l_extendedprice"][lm] * (100 - li["l_discount"][lm]))
    rows = order_row[keys]
    odate = orders["o_orderdate"][rows]
    prio = orders["o_shippriority"][rows]
    top = top_rows((-revenue, odate, keys), 10)
    return [(int(keys[i]), int(revenue[i]), int(odate[i]), int(prio[i]))
            for i in top]
