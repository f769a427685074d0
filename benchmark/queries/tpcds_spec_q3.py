"""TPC-DS q3 (specification v3, query template query3.tpl), with its
qualification parameters MANUFACT = 128, MONTH = 11, built as the
template's text reads: the tables of the FROM clause under the aliases
it gives them, joined on the WHERE clause's join conditions, then ONE
`where` over the joined frame (the optimizer, not the query, moves
each conjunct below the join whose side it reads):

    select dt.d_year, item.i_brand_id brand_id, item.i_brand brand,
           sum(ss_ext_sales_price) sum_agg
    from date_dim dt, store_sales, item
    where dt.d_date_sk = store_sales.ss_sold_date_sk
      and store_sales.ss_item_sk = item.i_item_sk
      and item.i_manufact_id = 128 and dt.d_moy = 11
    group by dt.d_year, item.i_brand, item.i_brand_id
    order by dt.d_year, sum_agg desc, brand_id
    limit 100

The answer's description, the plain reference and the byte counts are
those of `tpcds_q3` (same parameters, `_tpcds_star.star_query`); only
`build` is this file's own.
"""

from benchmark.queries._tpcds_star import star_query

ANSWER, _, reference, input_bytes, device_bytes = star_query(
    where={"i_manufact_id": 128, "d_moy": 11},
    group=["d_year", "i_brand", "i_brand_id"],
    select=[("d_year", "d_year"), ("i_brand_id", "brand_id"),
            ("i_brand", "brand")],
    total="sum_agg",
    order=[("d_year", "asc"), ("sum_agg", "desc"), ("brand_id", "asc")])


def build(spark, tables):
    from spark_rapids_tpu.api import functions as F

    dt = tables["date_dim"].alias("dt")
    store_sales = tables["store_sales"].alias("store_sales")
    item = tables["item"].alias("item")
    return (store_sales
            .join(dt, F.col("dt.d_date_sk")
                  == F.col("store_sales.ss_sold_date_sk"))
            .join(item, F.col("store_sales.ss_item_sk")
                  == F.col("item.i_item_sk"))
            .where((F.col("item.i_manufact_id") == 128)
                   & (F.col("dt.d_moy") == 11))
            .groupBy(F.col("dt.d_year"), F.col("item.i_brand"),
                     F.col("item.i_brand_id"))
            .agg(F.sum("ss_ext_sales_price").alias("sum_agg"))
            .select(F.col("d_year"), F.col("i_brand_id").alias("brand_id"),
                    F.col("i_brand").alias("brand"), F.col("sum_agg"))
            .orderBy(F.col("d_year"), F.col("sum_agg").desc(),
                     F.col("brand_id"))
            .limit(100))
