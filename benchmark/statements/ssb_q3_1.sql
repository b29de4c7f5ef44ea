select c_nation, s_nation, d_year, sum(lo_revenue) as revenue
from customer, lineorder, supplier, date
where lo_custkey = c_custkey
and lo_suppkey = s_suppkey
and lo_orderdate = d_datekey
and c_region = '{region}' and s_region = '{region}'
and d_year >= {year_lo} and d_year <= {year_hi}
group by c_nation, s_nation, d_year
order by d_year asc, revenue desc
