"""Integer reference of ds_q89.sql (TPC-DS Q89: monthly store sales of
six (category, class) pairs for one year against their brand's and
store's monthly average, a window aggregate over an aggregate; the
months more than a tenth off it). The filter is decided in integers:
|S - T/c| / (T/c) > 1/10  <=>  10 |S c - T| > T, T > 0, for a month's
sum S and its partition's c sums of total T. The average is compared as
the exact quotient T / (c * 100) (`ratio`). The text orders by the
specification's keys and then by the rest of the group's keys, so that
its first hundred rows are one list."""

from fractions import Fraction

import numpy as np

import dsref

COLUMNS = ["text", "text", "text", "text", "text", "int", "dec2", "ratio"]
TABLES = ("item", "store_sales", "date_dim", "store")

PAIRS = ((("Books", "Electronics", "Sports"),
          ("computers", "stereo", "football")),
         (("Men", "Jewelry", "Women"), ("shirts", "birdal", "dresses")))


def reference(tables, p):
    ss, _ = tables["store_sales"]
    item = None
    for cats, classes in PAIRS:
        arm = (dsref.equal(tables, "item", "i_category", *cats)
               & dsref.equal(tables, "item", "i_class", *classes))
        item = arm if item is None else item | arm
    m, rows = dsref.star(
        tables,
        date_dim=tables["date_dim"][0]["d_year"] == int(p["year"]),
        item=item,
        store=np.ones(len(tables["store"][0]["s_store_sk"]), dtype=bool))
    keys = [dsref.key(tables, "item", c, rows["item"])
            for c in ("i_category", "i_class", "i_brand")]
    keys += [dsref.key(tables, "store", c, rows["store"])
             for c in ("s_store_name", "s_company_name")]
    keys.append(dsref.key(tables, "date_dim", "d_moy", rows["date_dim"]))
    groups = [(ks, sums[0]) for _, ks, sums, _ in
              dsref.rollup(keys, m, [ss["ss_sales_price"]], depth=[6])]
    parts: dict = {}
    for ks, s in groups:
        t, c = parts.get((ks[0], ks[2], ks[3], ks[4]), (0, 0))
        parts[(ks[0], ks[2], ks[3], ks[4])] = (t + s, c + 1)
    out = []
    for ks, s in groups:
        t, c = parts[(ks[0], ks[2], ks[3], ks[4])]
        if t > 0 and 10 * abs(s * c - t) > t:
            out.append((Fraction(s * c - t, c), ks, s, t, c))
    out.sort(key=lambda r: (r[0], r[1][3], r[1][0], r[1][2], r[1][5],
                            r[1][1]))
    return [list(ks) + [s, (t, c * 100)] for _, ks, s, t, c in out[:100]]
