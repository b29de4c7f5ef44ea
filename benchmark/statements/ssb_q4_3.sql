select d_year, s_city, p_brand1, sum(lo_revenue - lo_supplycost) as profit
from date, customer, supplier, part, lineorder
where lo_custkey = c_custkey
and lo_suppkey = s_suppkey
and lo_partkey = p_partkey
and lo_orderdate = d_datekey
and c_region = '{region}'
and s_nation = '{nation}'
and (d_year = {year1} or d_year = {year2})
and p_category = '{category}'
group by d_year, s_city, p_brand1
order by d_year, s_city, p_brand1
