"""Integer reference of nested_q21.sql (TPC-H Q21, suppliers who kept
orders waiting: spec 2.4.21). A late line l1 of supplier s qualifies
when its order has another supplier (EXISTS) and no other supplier of
the order was late (NOT EXISTS): the order's distinct suppliers are two
or more and its distinct late suppliers are s alone."""

import numpy as np

from tpchref import by_key, group_count

COLUMNS = ["text", "int"]
TABLES = ("supplier", "lineitem", "orders", "nation")


def reference(tables, p):
    li, _ = tables["lineitem"]
    orders, odicts = tables["orders"]
    supp, sdicts = tables["supplier"]
    nation, ndicts = tables["nation"]
    okey, skey = li["l_orderkey"], li["l_suppkey"]
    late = li["l_receiptdate"] > li["l_commitdate"]
    width = int(skey.max()) + 1
    n_orders = int(orders["o_orderkey"].max()) + 1
    suppliers = np.bincount(np.unique(okey * width + skey) // width,
                            minlength=n_orders)
    late_suppliers = np.bincount(
        np.unique(okey[late] * width + skey[late]) // width,
        minlength=n_orders)
    status_f = by_key(orders["o_orderkey"], orders["o_orderstatus"]
                      == odicts["o_orderstatus"].index("F"), False)
    nkey = int(nation["n_nationkey"][
        nation["n_name"] == ndicts["n_name"].index(p["nation"])][0])
    in_nation = by_key(supp["s_suppkey"], supp["s_nationkey"] == nkey,
                       False)
    m = (late & status_f[okey] & in_nation[skey] & (suppliers[okey] >= 2)
         & (late_suppliers[okey] == 1))
    found, counts = group_count(skey[m])
    name = by_key(supp["s_suppkey"], supp["s_name"])
    rows = [(sdicts["s_name"][name[s]], int(n))
            for s, n in zip(found.tolist(), counts.tolist())]
    return sorted(rows, key=lambda r: (-r[1], r[0]))[:100]
