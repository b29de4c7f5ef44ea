"""Integer reference of q1.sql (TPC-H Q1, pricing summary report)."""

from generators.tpch import days

COLUMNS = ["text", "text", "dec2", "dec2", "dec4", "dec6", "avg2", "avg2",
           "avg2", "int"]
TABLES = ("lineitem",)


def reference(tables, p):
    li, dicts = tables["lineitem"]
    flags, status = dicts["l_returnflag"], dicts["l_linestatus"]
    mask = li["l_shipdate"] <= days("1998-12-01") - int(p["delta"])
    group = li["l_returnflag"] * len(status) + li["l_linestatus"]
    rows = []
    for g in range(len(flags) * len(status)):
        m = mask & (group == g)
        n = int(m.sum())
        if not n:
            continue
        qty = li["l_quantity"][m]
        price = li["l_extendedprice"][m]
        disc = li["l_discount"][m]
        disc_price = price * (100 - disc)
        charge = disc_price * (100 + li["l_tax"][m])
        sums = [int(a.sum()) for a in (qty, price, disc_price, charge)]
        rows.append((flags[g // len(status)], status[g % len(status)],
                     *sums, (sums[0], n), (sums[1], n),
                     (int(disc.sum()), n), n))
    return sorted(rows, key=lambda r: r[:2])
