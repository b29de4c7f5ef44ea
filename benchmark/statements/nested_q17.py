"""Integer reference of nested_q17.sql (TPC-H Q17, small-quantity-order
revenue: spec 2.4.17). The part's average is compared as integers:
l_quantity < 0.2 * sum / count  <=>  5 * l_quantity * count < sum."""

import numpy as np

from tpchref import by_key

COLUMNS = ["avg2"]
TABLES = ("lineitem", "part")


def reference(tables, p):
    li, _ = tables["lineitem"]
    part, pdicts = tables["part"]
    n_parts = int(part["p_partkey"].max()) + 1
    pkey, qty = li["l_partkey"], li["l_quantity"]
    total = np.bincount(pkey, weights=qty, minlength=n_parts).astype(
        np.int64)
    count = np.bincount(pkey, minlength=n_parts)
    ok = by_key(part["p_partkey"],
                (part["p_brand"] == pdicts["p_brand"].index(p["brand"]))
                & (part["p_container"]
                   == pdicts["p_container"].index(p["container"])), False)
    m = ok[pkey] & (5 * qty * count[pkey] < total[pkey])
    return [((int(li["l_extendedprice"][m].sum()), 7),)]
