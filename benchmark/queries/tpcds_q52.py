"""TPC-DS q52 (specification v3, query template query52.tpl), with its
qualification parameters MONTH = 11, YEAR = 2000 (manager 1 is fixed
in the template):

    select dt.d_year, item.i_brand_id brand_id, item.i_brand brand,
           sum(ss_ext_sales_price) ext_price
    from date_dim dt, store_sales, item
    where dt.d_date_sk = store_sales.ss_sold_date_sk
      and store_sales.ss_item_sk = item.i_item_sk
      and item.i_manager_id = 1 and dt.d_moy = 11 and dt.d_year = 2000
    group by dt.d_year, item.i_brand, item.i_brand_id
    order by dt.d_year, ext_price desc, brand_id
    limit 100
"""

from benchmark.queries._tpcds_star import star_query

ANSWER, build, reference, input_bytes, device_bytes = star_query(
    where={"i_manager_id": 1, "d_moy": 11, "d_year": 2000},
    group=["d_year", "i_brand", "i_brand_id"],
    select=[("d_year", "d_year"), ("i_brand_id", "brand_id"),
            ("i_brand", "brand")],
    total="ext_price",
    order=[("d_year", "asc"), ("ext_price", "desc"), ("brand_id", "asc")])
