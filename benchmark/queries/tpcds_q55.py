"""TPC-DS q55 (specification v3, query template query55.tpl), with its
qualification parameters MANAGER = 28, MONTH = 11, YEAR = 1999:

    select i_brand_id brand_id, i_brand brand,
           sum(ss_ext_sales_price) ext_price
    from date_dim, store_sales, item
    where d_date_sk = ss_sold_date_sk and ss_item_sk = i_item_sk
      and i_manager_id = 28 and d_moy = 11 and d_year = 1999
    group by i_brand, i_brand_id
    order by ext_price desc, i_brand_id
    limit 100
"""

from benchmark.queries._tpcds_star import star_query

ANSWER, build, reference, input_bytes, device_bytes = star_query(
    where={"i_manager_id": 28, "d_moy": 11, "d_year": 1999},
    group=["i_brand", "i_brand_id"],
    select=[("i_brand_id", "brand_id"), ("i_brand", "brand")],
    total="ext_price",
    order=[("ext_price", "desc"), ("brand_id", "asc")])
