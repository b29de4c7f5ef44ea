"""Integer reference of ds_q36.sql (TPC-DS Q36: gross margin by item
category and class for one year, ROLLUP(i_category, i_class), ranked
within each parent of the hierarchy). A margin is compared as the exact
quotient of two integer sums (`ratio`), and ranked by it exactly: the
engine's float64 quotient orders the same but on an exact tie. A
rolled-up key is written 'ALL' (COALESCE), as verify.py holds no
NULL."""

from fractions import Fraction

import numpy as np

import dsref

COLUMNS = ["ratio", "text", "text", "int", "int"]
TABLES = ("store_sales", "date_dim", "item", "store")


def reference(tables, p):
    ss, _ = tables["store_sales"]
    m, rows = dsref.star(
        tables,
        date_dim=tables["date_dim"][0]["d_year"] == int(p["year"]),
        item=np.ones(len(tables["item"][0]["i_item_sk"]), dtype=bool),
        store=dsref.equal(tables, "store", "s_state", p["state"]))
    keys = [dsref.key(tables, "item", "i_category", rows["item"]),
            dsref.key(tables, "item", "i_class", rows["item"])]
    groups = []
    for depth, ks, (profit, sales), _ in dsref.rollup(
            keys, m, [ss["ss_net_profit"], ss["ss_ext_sales_price"]]):
        groups.append([(profit, sales), ks[0], ks[1], 2 - depth])
    # rank() over (partition by lochierarchy, the category where the
    # class is kept, order by the margin)
    parts: dict = {}
    for g in groups:
        parts.setdefault((g[3], g[1] if g[3] == 0 else None), []).append(g)
    for rs in parts.values():
        margins = [Fraction(*g[0]) for g in rs]
        for g, mg in zip(rs, margins):
            g.append(1 + sum(x < mg for x in margins))
    groups.sort(key=lambda g: (-g[3], g[1] if g[3] == 0 else "", g[4]))
    return [[g[0], g[1] or "ALL", g[2] or "ALL", g[3], g[4]]
            for g in groups[:100]]
