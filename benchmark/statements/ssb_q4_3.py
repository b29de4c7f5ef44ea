"""Integer reference of ssb_q4_3.sql (SSB Q4.3: by supplier city and brand,
one nation, one category)."""

import ssbref

COLUMNS = ["int", "text", "text", "int"]
TABLES = ("date", "customer", "supplier", "part", "lineorder")


def reference(tables, p):
    lo, _ = tables["lineorder"]
    m = ssbref.star(
        tables,
        customer=ssbref.equal(tables, "customer", "c_region", p["region"]),
        supplier=ssbref.equal(tables, "supplier", "s_nation", p["nation"]),
        part=ssbref.equal(tables, "part", "p_category", p["category"]),
        date=ssbref.equal(tables, "date", "d_year", p["year1"], p["year2"]))
    return sorted(ssbref.grouped_sum(
        tables, m, [("date", "d_year"), ("supplier", "s_city"),
                    ("part", "p_brand1")],
        lo["lo_revenue"] - lo["lo_supplycost"]))
