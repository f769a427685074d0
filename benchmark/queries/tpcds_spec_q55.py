"""TPC-DS q55 (specification v3, query template query55.tpl), with its
qualification parameters MANAGER = 28, MONTH = 11, YEAR = 1999, built
as the template's text reads (see `tpcds_spec_q3`; this template
gives no table an alias and qualifies no column):

    select i_brand_id brand_id, i_brand brand,
           sum(ss_ext_sales_price) ext_price
    from date_dim, store_sales, item
    where d_date_sk = ss_sold_date_sk and ss_item_sk = i_item_sk
      and i_manager_id = 28 and d_moy = 11 and d_year = 1999
    group by i_brand, i_brand_id
    order by ext_price desc, i_brand_id
    limit 100

The answer's description, the plain reference and the byte counts are
those of `tpcds_q55`; only `build` is this file's own.
"""

from benchmark.queries._tpcds_star import star_query

ANSWER, _, reference, input_bytes, device_bytes = star_query(
    where={"i_manager_id": 28, "d_moy": 11, "d_year": 1999},
    group=["i_brand", "i_brand_id"],
    select=[("i_brand_id", "brand_id"), ("i_brand", "brand")],
    total="ext_price",
    order=[("ext_price", "desc"), ("brand_id", "asc")])


def build(spark, tables):
    from spark_rapids_tpu.api import functions as F

    date_dim, store_sales, item = (
        tables[t] for t in ("date_dim", "store_sales", "item"))
    return (store_sales
            .join(date_dim, F.col("d_date_sk") == F.col("ss_sold_date_sk"))
            .join(item, F.col("ss_item_sk") == F.col("i_item_sk"))
            .where((F.col("i_manager_id") == 28) & (F.col("d_moy") == 11)
                   & (F.col("d_year") == 1999))
            .groupBy("i_brand", "i_brand_id")
            .agg(F.sum("ss_ext_sales_price").alias("ext_price"))
            .select(F.col("i_brand_id").alias("brand_id"),
                    F.col("i_brand").alias("brand"), F.col("ext_price"))
            .orderBy(F.col("ext_price").desc(), F.col("brand_id"))
            .limit(100))
