select sum(lo_extendedprice*lo_discount) as revenue
from lineorder, date
where lo_orderdate = d_datekey
and d_year = {year}
and lo_discount between {discount_lo} and {discount_hi}
and lo_quantity < {quantity}
