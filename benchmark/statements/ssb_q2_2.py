"""Integer reference of ssb_q2_2.sql (SSB Q2.2: a range of brands)."""

import ssbref

COLUMNS = ["int", "int", "text"]
TABLES = ("lineorder", "date", "part", "supplier")


def reference(tables, p):
    lo, _ = tables["lineorder"]
    m = ssbref.star(
        tables, part=ssbref.between(tables, "part", "p_brand1",
                                     p["brand_lo"], p["brand_hi"]),
        supplier=ssbref.equal(tables, "supplier", "s_region", p["region"]))
    rows = ssbref.grouped_sum(tables, m, [("date", "d_year"),
                                          ("part", "p_brand1")],
                              lo["lo_revenue"])
    return [[r, y, b] for y, b, r in sorted(rows, key=lambda r: r[:2])]
