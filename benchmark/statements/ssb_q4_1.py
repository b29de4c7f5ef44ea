"""Integer reference of ssb_q4_1.sql (SSB Q4.1: profit by year and customer
nation, one region, two manufacturers)."""

import ssbref

COLUMNS = ["int", "text", "int"]
TABLES = ("date", "customer", "supplier", "part", "lineorder")


def reference(tables, p):
    lo, _ = tables["lineorder"]
    m = ssbref.star(
        tables,
        customer=ssbref.equal(tables, "customer", "c_region", p["region"]),
        supplier=ssbref.equal(tables, "supplier", "s_region", p["region"]),
        part=ssbref.equal(tables, "part", "p_mfgr", p["mfgr1"], p["mfgr2"]))
    return sorted(ssbref.grouped_sum(
        tables, m, [("date", "d_year"), ("customer", "c_nation")],
        lo["lo_revenue"] - lo["lo_supplycost"]))
