"""Integer reference of ssb_q3_1.sql (SSB Q3.1: revenue by customer and
supplier nation and year, one region)."""

import ssbref

COLUMNS = ["text", "text", "int", "int"]
TABLES = ("customer", "lineorder", "supplier", "date")


def reference(tables, p):
    lo, _ = tables["lineorder"]
    m = ssbref.star(
        tables,
        customer=ssbref.equal(tables, "customer", "c_region", p["region"]),
        supplier=ssbref.equal(tables, "supplier", "s_region", p["region"]),
        date=ssbref.between(tables, "date", "d_year", p["year_lo"],
                            p["year_hi"]))
    rows = ssbref.grouped_sum(
        tables, m, [("customer", "c_nation"), ("supplier", "s_nation"),
                    ("date", "d_year")], lo["lo_revenue"])
    return sorted(rows, key=lambda r: (r[2], -r[3]))
