"""Integer reference of nested_q4.sql (TPC-H Q4, order priority checking:
spec 2.4.4). Names all eight tables, so that the deployment's whole
schema is loaded whichever classes the cell keeps: run.py loads the
tables the statements name."""

import numpy as np

from generators.tpch import days
from generators.tpch_full import TABLE_ORDER
from tpchref import group_count, months_later

COLUMNS = ["text", "int"]
TABLES = TABLE_ORDER


def reference(tables, p):
    li, _ = tables["lineitem"]
    orders, odicts = tables["orders"]
    late = np.zeros(int(orders["o_orderkey"].max()) + 1, dtype=bool)
    late[li["l_orderkey"][li["l_commitdate"] < li["l_receiptdate"]]] = True
    m = ((orders["o_orderdate"] >= days(p["date"]))
         & (orders["o_orderdate"] < days(months_later(p["date"], 3)))
         & late[orders["o_orderkey"]])
    codes, counts = group_count(orders["o_orderpriority"][m])
    names = odicts["o_orderpriority"]
    return sorted((names[c], int(n)) for c, n in zip(codes.tolist(),
                                                     counts.tolist()))
