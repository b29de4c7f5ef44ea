"""Integer reference of ds_q27.sql (TPC-DS Q27: four averages of store
sales by item and state, ROLLUP(i_item_id, s_state), for one
demographic and one year). The text keeps every constant of the
specification's query; a rolled-up key is written 'ALL' (COALESCE)
because the comparison (verify.py) holds no NULL."""

import numpy as np

import dsref

COLUMNS = ["text", "text", "int", "avg0", "avg2", "avg2", "avg2"]
TABLES = ("store_sales", "customer_demographics", "date_dim", "store",
          "item")


def reference(tables, p):
    ss, _ = tables["store_sales"]
    m, rows = dsref.star(
        tables,
        date_dim=tables["date_dim"][0]["d_year"] == int(p["year"]),
        item=np.ones(len(tables["item"][0]["i_item_sk"]), dtype=bool),
        store=dsref.equal(tables, "store", "s_state", p["state"]),
        customer_demographics=(
            dsref.equal(tables, "customer_demographics", "cd_gender",
                        p["gen"])
            & dsref.equal(tables, "customer_demographics",
                          "cd_marital_status", p["ms"])
            & dsref.equal(tables, "customer_demographics",
                          "cd_education_status", p["es"])))
    keys = [dsref.key(tables, "item", "i_item_id", rows["item"]),
            dsref.key(tables, "store", "s_state", rows["store"])]
    vals = [ss[c] for c in ("ss_quantity", "ss_list_price",
                            "ss_coupon_amt", "ss_sales_price")]
    out = [[ks[0] or "ALL", ks[1] or "ALL", 0 if depth == 2 else 1]
           + [(s, n) for s in sums]
           for depth, ks, sums, n in dsref.rollup(keys, m, vals)]
    out.sort(key=lambda r: (r[0], r[1]))
    return out[:100]
