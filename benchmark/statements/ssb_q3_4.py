"""Integer reference of ssb_q3_4.sql (SSB Q3.4: two cities, one month)."""

import ssbref

COLUMNS = ["text", "text", "int", "int"]
TABLES = ("customer", "lineorder", "supplier", "date")


def reference(tables, p):
    lo, _ = tables["lineorder"]
    m = ssbref.star(
        tables,
        customer=ssbref.equal(tables, "customer", "c_city", p["city1"],
                              p["city2"]),
        supplier=ssbref.equal(tables, "supplier", "s_city", p["city1"],
                              p["city2"]),
        date=ssbref.equal(tables, "date", "d_yearmonth", p["yearmonth"]))
    rows = ssbref.grouped_sum(
        tables, m, [("customer", "c_city"), ("supplier", "s_city"),
                    ("date", "d_year")], lo["lo_revenue"])
    return sorted(rows, key=lambda r: (r[2], -r[3]))
