"""TPC-DS q3 (specification v3, query template query3.tpl), with its
qualification parameters MANUFACT = 128, MONTH = 11:

    select dt.d_year, item.i_brand_id brand_id, item.i_brand brand,
           sum(ss_ext_sales_price) sum_agg
    from date_dim dt, store_sales, item
    where dt.d_date_sk = store_sales.ss_sold_date_sk
      and store_sales.ss_item_sk = item.i_item_sk
      and item.i_manufact_id = 128 and dt.d_moy = 11
    group by dt.d_year, item.i_brand, item.i_brand_id
    order by dt.d_year, sum_agg desc, brand_id
    limit 100
"""

from benchmark.queries._tpcds_star import star_query

ANSWER, build, reference, input_bytes, device_bytes = star_query(
    where={"i_manufact_id": 128, "d_moy": 11},
    group=["d_year", "i_brand", "i_brand_id"],
    select=[("d_year", "d_year"), ("i_brand_id", "brand_id"),
            ("i_brand", "brand")],
    total="sum_agg",
    order=[("d_year", "asc"), ("sum_agg", "desc"), ("brand_id", "asc")])
