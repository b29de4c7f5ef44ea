"""Integer reference of ds_q67.sql (TPC-DS Q67: store sales summed over
ROLLUP of eight keys, item category down to the store, for twelve
months, ranked within each category, the best hundred of each). Every
grouping set on its own from the fact rows; a rolled-up key is written
'ALL' or 0 (COALESCE), as verify.py holds no NULL."""

import numpy as np

import dsref

COLUMNS = ["text", "text", "text", "text", "int", "int", "int", "text",
           "dec2", "int"]
TABLES = ("store_sales", "date_dim", "store", "item")


def reference(tables, p):
    ss, _ = tables["store_sales"]
    dd = tables["date_dim"][0]
    dms = int(p["dms"])
    m, rows = dsref.star(
        tables,
        date_dim=(dd["d_month_seq"] >= dms) & (dd["d_month_seq"]
                                               <= dms + 11),
        item=np.ones(len(tables["item"][0]["i_item_sk"]), dtype=bool),
        store=np.ones(len(tables["store"][0]["s_store_sk"]), dtype=bool))
    keys = [dsref.key(tables, "item", c, rows["item"])
            for c in ("i_category", "i_class", "i_brand",
                      "i_product_name")]
    keys += [dsref.key(tables, "date_dim", c, rows["date_dim"])
             for c in ("d_year", "d_qoy", "d_moy")]
    keys.append(dsref.key(tables, "store", "s_store_id", rows["store"]))
    amount = ss["ss_sales_price"] * ss["ss_quantity"]
    groups = [ks + [sums[0]] for _, ks, sums, _ in
              dsref.rollup(keys, m, [amount])]
    parts: dict = {}
    for g in groups:
        parts.setdefault(g[0], []).append(g)
    for rs in parts.values():
        for g, rk in zip(rs, dsref.rank_desc([g[8] for g in rs])):
            g.append(rk)
    out = [[v if v is not None else ("ALL" if j in (0, 1, 2, 3, 7) else 0)
            for j, v in enumerate(g)] for g in groups if g[9] <= 100]
    out.sort()
    return out[:100]
