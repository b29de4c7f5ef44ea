"""Integer reference of ssb_q3_2.sql (SSB Q3.2: by city, one nation)."""

import ssbref

COLUMNS = ["text", "text", "int", "int"]
TABLES = ("customer", "lineorder", "supplier", "date")


def reference(tables, p):
    lo, _ = tables["lineorder"]
    m = ssbref.star(
        tables,
        customer=ssbref.equal(tables, "customer", "c_nation", p["nation"]),
        supplier=ssbref.equal(tables, "supplier", "s_nation", p["nation"]),
        date=ssbref.between(tables, "date", "d_year", p["year_lo"],
                            p["year_hi"]))
    rows = ssbref.grouped_sum(
        tables, m, [("customer", "c_city"), ("supplier", "s_city"),
                    ("date", "d_year")], lo["lo_revenue"])
    return sorted(rows, key=lambda r: (r[2], -r[3]))
