"""TPC-DS q42 (specification v3, query template query42.tpl), with its
qualification parameters MONTH = 11, YEAR = 2000 (manager 1 is fixed
in the template), built as the template's text reads (see
`tpcds_spec_q3`):

    select dt.d_year, item.i_category_id, item.i_category,
           sum(ss_ext_sales_price) total_sales
    from date_dim dt, store_sales, item
    where dt.d_date_sk = store_sales.ss_sold_date_sk
      and store_sales.ss_item_sk = item.i_item_sk
      and item.i_manager_id = 1 and dt.d_moy = 11 and dt.d_year = 2000
    group by dt.d_year, item.i_category_id, item.i_category
    order by sum(ss_ext_sales_price) desc, dt.d_year,
             item.i_category_id, item.i_category
    limit 100

The sum is named `total_sales` here; the template leaves it unnamed.
The answer's description, the plain reference and the byte counts are
those of `tpcds_q42`; only `build` is this file's own.
"""

from benchmark.queries._tpcds_star import star_query

ANSWER, _, reference, input_bytes, device_bytes = star_query(
    where={"i_manager_id": 1, "d_moy": 11, "d_year": 2000},
    group=["d_year", "i_category_id", "i_category"],
    select=[("d_year", "d_year"), ("i_category_id", "i_category_id"),
            ("i_category", "i_category")],
    total="total_sales",
    order=[("total_sales", "desc"), ("d_year", "asc"),
           ("i_category_id", "asc"), ("i_category", "asc")])


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
            .groupBy(F.col("dt.d_year"), F.col("item.i_category_id"),
                     F.col("item.i_category"))
            .agg(F.sum("ss_ext_sales_price").alias("total_sales"))
            .select(F.col("d_year"), F.col("i_category_id"),
                    F.col("i_category"), F.col("total_sales"))
            .orderBy(F.col("total_sales").desc(), F.col("d_year"),
                     F.col("i_category_id"), F.col("i_category"))
            .limit(100))
