"""Integer reference of q14.sql (TPC-H Q14, promotion effect)."""

import datetime

import numpy as np

from generators.tpch import days

COLUMNS = ["ratio"]
TABLES = ("lineitem", "part")


def reference(tables, p):
    li, _ = tables["lineitem"]
    part, pdicts = tables["part"]
    first = datetime.date.fromisoformat(p["month"])
    nxt = (first.replace(day=28) + datetime.timedelta(days=4)).replace(day=1)
    m = ((li["l_shipdate"] >= days(first.isoformat()))
         & (li["l_shipdate"] < days(nxt.isoformat())))
    promo_type = np.array([t.startswith("PROMO") for t in pdicts["p_type"]])
    is_promo = np.zeros(int(part["p_partkey"].max()) + 1, dtype=bool)
    is_promo[part["p_partkey"]] = promo_type[part["p_type"]]
    rev = li["l_extendedprice"][m] * (100 - li["l_discount"][m])
    promo = int(rev[is_promo[li["l_partkey"][m]]].sum())
    return [((100 * promo, int(rev.sum())),)]
