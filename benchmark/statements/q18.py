"""Integer reference of q18.sql (TPC-H Q18, large volume customers)."""

import numpy as np

from refutil import group_sum, top_rows

COLUMNS = ["text", "int", "int", "date", "dec2", "dec2"]
TABLES = ("lineitem", "orders", "customer")


def reference(tables, p):
    li, _ = tables["lineitem"]
    orders, _ = tables["orders"]
    cust, cdicts = tables["customer"]
    keys, qty = group_sum(li["l_orderkey"], li["l_quantity"])
    big = qty > int(p["quantity"]) * 100
    keys, qty = keys[big], qty[big]
    order_row = np.zeros(int(orders["o_orderkey"].max()) + 1,
                         dtype=np.int64)
    order_row[orders["o_orderkey"]] = np.arange(len(orders["o_orderkey"]))
    rows = order_row[keys]
    price = orders["o_totalprice"][rows]
    odate = orders["o_orderdate"][rows]
    custkey = orders["o_custkey"][rows]
    name_code = np.zeros(int(cust["c_custkey"].max()) + 1, dtype=np.int64)
    name_code[cust["c_custkey"]] = cust["c_name"]
    names = cdicts["c_name"]
    top = top_rows((-price, odate, keys), 100)
    return [(names[name_code[custkey[i]]], int(custkey[i]), int(keys[i]),
             int(odate[i]), int(price[i]), int(qty[i])) for i in top]
