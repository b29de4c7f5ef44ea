"""Integer reference of ssb_q2_1.sql (SSB Q2.1: a category's brands by
year, one region's suppliers)."""

import ssbref

COLUMNS = ["int", "int", "text"]
TABLES = ("lineorder", "date", "part", "supplier")


def reference(tables, p):
    lo, _ = tables["lineorder"]
    m = ssbref.star(
        tables, part=ssbref.equal(tables, "part", "p_category",
                                   p["category"]),
        supplier=ssbref.equal(tables, "supplier", "s_region", p["region"]))
    rows = ssbref.grouped_sum(tables, m, [("date", "d_year"),
                                          ("part", "p_brand1")],
                              lo["lo_revenue"])
    return [[r, y, b] for y, b, r in sorted(rows, key=lambda r: r[:2])]
