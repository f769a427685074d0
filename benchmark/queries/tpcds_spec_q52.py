"""TPC-DS q52 (specification v3, query template query52.tpl), with its
qualification parameters MONTH = 11, YEAR = 2000 (manager 1 is fixed
in the template), built as the template's text reads (see
`tpcds_spec_q3`):

    select dt.d_year, item.i_brand_id brand_id, item.i_brand brand,
           sum(ss_ext_sales_price) ext_price
    from date_dim dt, store_sales, item
    where dt.d_date_sk = store_sales.ss_sold_date_sk
      and store_sales.ss_item_sk = item.i_item_sk
      and item.i_manager_id = 1 and dt.d_moy = 11 and dt.d_year = 2000
    group by dt.d_year, item.i_brand, item.i_brand_id
    order by dt.d_year, ext_price desc, brand_id
    limit 100

The answer's description, the plain reference and the byte counts are
those of `tpcds_q52`; only `build` is this file's own.
"""

from benchmark.queries._tpcds_star import star_query

ANSWER, _, reference, input_bytes, device_bytes = star_query(
    where={"i_manager_id": 1, "d_moy": 11, "d_year": 2000},
    group=["d_year", "i_brand", "i_brand_id"],
    select=[("d_year", "d_year"), ("i_brand_id", "brand_id"),
            ("i_brand", "brand")],
    total="ext_price",
    order=[("d_year", "asc"), ("ext_price", "desc"), ("brand_id", "asc")])


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
            .where((F.col("item.i_manager_id") == 1)
                   & (F.col("dt.d_moy") == 11)
                   & (F.col("dt.d_year") == 2000))
            .groupBy(F.col("dt.d_year"), F.col("item.i_brand"),
                     F.col("item.i_brand_id"))
            .agg(F.sum("ss_ext_sales_price").alias("ext_price"))
            .select(F.col("d_year"), F.col("i_brand_id").alias("brand_id"),
                    F.col("i_brand").alias("brand"), F.col("ext_price"))
            .orderBy(F.col("d_year"), F.col("ext_price").desc(),
                     F.col("brand_id"))
            .limit(100))
