"""Integer reference of ssb_q2_3.sql (SSB Q2.3: one brand)."""

import ssbref

COLUMNS = ["int", "int", "text"]
TABLES = ("lineorder", "date", "part", "supplier")


def reference(tables, p):
    lo, _ = tables["lineorder"]
    m = ssbref.star(
        tables, part=ssbref.equal(tables, "part", "p_brand1", p["brand"]),
        supplier=ssbref.equal(tables, "supplier", "s_region", p["region"]))
    rows = ssbref.grouped_sum(tables, m, [("date", "d_year"),
                                          ("part", "p_brand1")],
                              lo["lo_revenue"])
    return [[r, y, b] for y, b, r in sorted(rows, key=lambda r: r[:2])]
