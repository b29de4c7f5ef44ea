"""Integer reference of ssb_q4_2.sql (SSB Q4.2: by supplier nation and
category, two years)."""

import ssbref

COLUMNS = ["int", "text", "text", "int"]
TABLES = ("date", "customer", "supplier", "part", "lineorder")


def reference(tables, p):
    lo, _ = tables["lineorder"]
    m = ssbref.star(
        tables,
        customer=ssbref.equal(tables, "customer", "c_region", p["region"]),
        supplier=ssbref.equal(tables, "supplier", "s_region", p["region"]),
        part=ssbref.equal(tables, "part", "p_mfgr", p["mfgr1"], p["mfgr2"]),
        date=ssbref.equal(tables, "date", "d_year", p["year1"], p["year2"]))
    return sorted(ssbref.grouped_sum(
        tables, m, [("date", "d_year"), ("supplier", "s_nation"),
                    ("part", "p_category")],
        lo["lo_revenue"] - lo["lo_supplycost"]))
