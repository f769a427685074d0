"""TPC-DS q42 (specification v3, query template query42.tpl), with its
qualification parameters MONTH = 11, YEAR = 2000 (manager 1 is fixed
in the template):

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
"""

from benchmark.queries._tpcds_star import star_query

ANSWER, build, reference, input_bytes, device_bytes = star_query(
    where={"i_manager_id": 1, "d_moy": 11, "d_year": 2000},
    group=["d_year", "i_category_id", "i_category"],
    select=[("d_year", "d_year"), ("i_category_id", "i_category_id"),
            ("i_category", "i_category")],
    total="total_sales",
    order=[("total_sales", "desc"), ("d_year", "asc"),
           ("i_category_id", "asc"), ("i_category", "asc")])
