"""TPC-DS q5 (specification v3, query template query5.tpl), with its
qualification parameter SALES_DATE = 2000-08-23, built as the
template's text reads:

    with ssr as
     (select s_store_id, sum(sales_price) as sales, sum(profit) as profit,
             sum(return_amt) as returns, sum(net_loss) as profit_loss
      from (select ss_store_sk as store_sk, ss_sold_date_sk as date_sk,
                   ss_ext_sales_price as sales_price,
                   ss_net_profit as profit,
                   cast(0 as decimal(7,2)) as return_amt,
                   cast(0 as decimal(7,2)) as net_loss
            from store_sales
            union all
            select sr_store_sk as store_sk,
                   sr_returned_date_sk as date_sk,
                   cast(0 as decimal(7,2)) as sales_price,
                   cast(0 as decimal(7,2)) as profit,
                   sr_return_amt as return_amt, sr_net_loss as net_loss
            from store_returns) salesreturns, date_dim, store
      where date_sk = d_date_sk
        and d_date between cast('2000-08-23' as date)
                       and (cast('2000-08-23' as date) + 14 days)
        and store_sk = s_store_sk
      group by s_store_id),
     csr as (... catalog_sales union all catalog_returns, catalog_page,
              group by cp_catalog_page_id),
     wsr as
     (select web_site_id, ...
      from (select ws_web_site_sk as wsr_web_site_sk, ... from web_sales
            union all
            select ws_web_site_sk as wsr_web_site_sk,
                   wr_returned_date_sk as date_sk, ...
            from web_returns left outer join web_sales on
                 (wr_item_sk = ws_item_sk
                  and wr_order_number = ws_order_number)) salesreturns,
           date_dim, web_site
      where date_sk = d_date_sk and d_date between ...
        and wsr_web_site_sk = web_site_sk
      group by web_site_id)
    select channel, id, sum(sales) as sales, sum(returns) as returns,
           sum(profit) as profit
    from (select 'store channel' as channel, 'store' || s_store_id as id,
                 sales, returns, (profit - profit_loss) as profit
          from ssr
          union all
          select 'catalog channel' as channel,
                 'catalog_page' || cp_catalog_page_id as id, ...
          from csr
          union all
          select 'web channel' as channel, 'web_site' || web_site_id as id,
                 ...
          from wsr) x
    group by rollup (channel, id)
    order by channel, id
    limit 100

Each CTE is its UNION ALL under the alias `salesreturns`, joined to
`date_dim` and its channel's dimension on the WHERE clause's join
conditions, then ONE `where` with the date window. decimal(7,2) is
DOUBLE in this configuration, so `cast(0 as decimal(7,2))` is 0.0.
ORDER BY is ascending with nulls first, Spark's default, which puts
the ROLLUP's grand total and subtotals before their rows.
"""

import datetime

import numpy as np
import pyarrow as pa

from benchmark.reference import Precision, column, group_sums

SALES_DATE = datetime.date(2000, 8, 23)
SALES_DATE_END = SALES_DATE + datetime.timedelta(days=14)
LIMIT = 100

ANSWER = {"keys": ["channel", "id"], "exact": [],
          "approx": ["sales", "returns", "profit"],
          "order": [("channel", "asc"), ("id", "asc")], "limit": LIMIT}

#: channel -> (sales, returns, the returns' date key, outlet dimension,
#: its key, its id, the id's prefix)
CHANNELS = {
    "store channel": ("store_sales", "store_returns", "sr_returned_date_sk",
                      "store", "s_store_sk", "s_store_id", "store"),
    "catalog channel": ("catalog_sales", "catalog_returns",
                        "cr_returned_date_sk", "catalog_page",
                        "cp_catalog_page_sk", "cp_catalog_page_id",
                        "catalog_page"),
    "web channel": ("web_sales", "web_returns", "wr_returned_date_sk",
                    "web_site", "web_site_sk", "web_site_id", "web_site"),
}


def build(spark, tables):
    return ordered_rollup(spark, tables).limit(LIMIT)


def ordered_rollup(spark, tables):
    """The query up to its ORDER BY: the whole ROLLUP, not cut."""
    from spark_rapids_tpu.api import functions as F

    def c(name, alias):
        return F.col(name).alias(alias)

    def zero(alias):
        return F.lit(0.0).alias(alias)

    date_dim = tables["date_dim"]
    window = ((F.col("d_date") >= F.lit(SALES_DATE))
              & (F.col("d_date") <= F.lit(SALES_DATE_END)))
    sums = [F.sum("sales_price").alias("sales"),
            F.sum("profit").alias("profit"),
            F.sum("return_amt").alias("returns"),
            F.sum("net_loss").alias("profit_loss")]

    store_sales, store_returns = tables["store_sales"], tables["store_returns"]
    salesreturns = store_sales.select(
        c("ss_store_sk", "store_sk"), c("ss_sold_date_sk", "date_sk"),
        c("ss_ext_sales_price", "sales_price"), c("ss_net_profit", "profit"),
        zero("return_amt"), zero("net_loss"),
    ).unionAll(store_returns.select(
        c("sr_store_sk", "store_sk"), c("sr_returned_date_sk", "date_sk"),
        zero("sales_price"), zero("profit"),
        c("sr_return_amt", "return_amt"), c("sr_net_loss", "net_loss"),
    )).alias("salesreturns")
    ssr = (salesreturns
           .join(date_dim, F.col("date_sk") == F.col("d_date_sk"))
           .join(tables["store"], F.col("store_sk") == F.col("s_store_sk"))
           .where(window)
           .groupBy(F.col("s_store_id"))
           .agg(*sums))

    catalog_sales = tables["catalog_sales"]
    catalog_returns = tables["catalog_returns"]
    salesreturns = catalog_sales.select(
        c("cs_catalog_page_sk", "page_sk"), c("cs_sold_date_sk", "date_sk"),
        c("cs_ext_sales_price", "sales_price"), c("cs_net_profit", "profit"),
        zero("return_amt"), zero("net_loss"),
    ).unionAll(catalog_returns.select(
        c("cr_catalog_page_sk", "page_sk"),
        c("cr_returned_date_sk", "date_sk"),
        zero("sales_price"), zero("profit"),
        c("cr_return_amount", "return_amt"), c("cr_net_loss", "net_loss"),
    )).alias("salesreturns")
    csr = (salesreturns
           .join(date_dim, F.col("date_sk") == F.col("d_date_sk"))
           .join(tables["catalog_page"],
                 F.col("page_sk") == F.col("cp_catalog_page_sk"))
           .where(window)
           .groupBy(F.col("cp_catalog_page_id"))
           .agg(*sums))

    web_sales, web_returns = tables["web_sales"], tables["web_returns"]
    salesreturns = web_sales.select(
        c("ws_web_site_sk", "wsr_web_site_sk"),
        c("ws_sold_date_sk", "date_sk"),
        c("ws_ext_sales_price", "sales_price"), c("ws_net_profit", "profit"),
        zero("return_amt"), zero("net_loss"),
    ).unionAll(web_returns.join(
        web_sales,
        (F.col("wr_item_sk") == F.col("ws_item_sk"))
        & (F.col("wr_order_number") == F.col("ws_order_number")),
        "left",
    ).select(
        c("ws_web_site_sk", "wsr_web_site_sk"),
        c("wr_returned_date_sk", "date_sk"),
        zero("sales_price"), zero("profit"),
        c("wr_return_amt", "return_amt"), c("wr_net_loss", "net_loss"),
    )).alias("salesreturns")
    wsr = (salesreturns
           .join(date_dim, F.col("date_sk") == F.col("d_date_sk"))
           .join(tables["web_site"],
                 F.col("wsr_web_site_sk") == F.col("web_site_sk"))
           .where(window)
           .groupBy(F.col("web_site_id"))
           .agg(*sums))

    def channel(cte, name, prefix, id_col):
        return cte.select(
            F.lit(name).alias("channel"),
            F.concat(F.lit(prefix), F.col(id_col)).alias("id"),
            F.col("sales"), F.col("returns"),
            (F.col("profit") - F.col("profit_loss")).alias("profit"))

    x = (channel(ssr, "store channel", "store", "s_store_id")
         .unionAll(channel(csr, "catalog channel", "catalog_page",
                           "cp_catalog_page_id"))
         .unionAll(channel(wsr, "web channel", "web_site", "web_site_id")))
    return (x.rollup(F.col("channel"), F.col("id"))
            .agg(F.sum("sales").alias("sales"),
                 F.sum("returns").alias("returns"),
                 F.sum("profit").alias("profit"))
            .orderBy(F.col("channel"), F.col("id")))


# --- the plain reference: numpy and pyarrow, nothing of the engine ---

def _rows_of(keys: np.ndarray, wanted: np.ndarray):
    """For each of `wanted`, the row of `keys` (unique) that holds it,
    and whether one does."""
    by_key = np.argsort(keys, kind="stable")
    pos = np.searchsorted(keys[by_key], wanted)
    pos[pos == len(keys)] = 0
    row = by_key[pos]
    return row, keys[row] == wanted


def _keys(table, name: str):
    """A nullable integer column -> (values with nulls as 0, valid)."""
    col = table.column(name).combine_chunks()
    return (col.fill_null(0).to_numpy().astype(np.int64),
            ~col.is_null().to_numpy(zero_copy_only=False))


def _in_window(tables, date_sk: np.ndarray, valid: np.ndarray):
    dates = tables["date_dim"]
    day = column(dates, "d_date").astype("datetime64[D]")
    inside = ((day >= np.datetime64(SALES_DATE))
              & (day <= np.datetime64(SALES_DATE_END)))
    row, hit = _rows_of(column(dates, "d_date_sk").astype(np.int64), date_sk)
    return valid & hit & inside[row]


def _web_returns_sites(tables):
    """web_returns LEFT OUTER JOIN web_sales on (item, order number):
    each return's sale's web site key, and whether it has one (no
    matching sale, or a sale without a site: null)."""
    ws, wr = tables["web_sales"], tables["web_returns"]
    orders = int(max(column(ws, "ws_order_number").max(),
                     column(wr, "wr_order_number").max())) + 1

    def pair(t, item, order):
        return (column(t, item).astype(np.int64) * orders
                + column(t, order).astype(np.int64))

    row, hit = _rows_of(pair(ws, "ws_item_sk", "ws_order_number"),
                        pair(wr, "wr_item_sk", "wr_order_number"))
    site, has_site = _keys(ws, "ws_web_site_sk")
    return site[row], hit & has_site[row]


def _channel_rows(tables, name: str, p: Precision):
    """-> (ids, sales, returns, profit - profit_loss) of one channel's
    CTE: the union's rows in the window with an outlet, summed by the
    outlet's id."""
    sales_t, returns_t, ret_date, dim_t, dim_key, dim_id, _ = CHANNELS[name]
    sales, returns = tables[sales_t], tables[returns_t]
    dim = tables[dim_t]
    prefix = sales_t[0] + "s_"
    outlet_col = {"store_sales": "ss_store_sk",
                  "catalog_sales": "cs_catalog_page_sk",
                  "web_sales": "ws_web_site_sk"}[sales_t]
    s_outlet, s_has = _keys(sales, outlet_col)
    s_date, s_dated = _keys(sales, prefix + "sold_date_sk")
    if sales_t == "web_sales":
        r_outlet, r_has = _web_returns_sites(tables)
    else:
        r_outlet, r_has = _keys(returns, outlet_col.replace("s_", "r_", 1))
    r_date, r_dated = _keys(returns, ret_date)
    dim_keys = column(dim, dim_key).astype(np.int64)
    ids = dim.column(dim_id).combine_chunks().dictionary_decode() \
        .to_numpy(zero_copy_only=False)

    def kept(outlet, has, date, dated):
        row, hit = _rows_of(dim_keys, outlet)
        keep = np.flatnonzero(has & hit & _in_window(tables, date, dated))
        return keep, ids[row[keep]]

    s_keep, s_ids = kept(s_outlet, s_has, s_date, s_dated)
    r_keep, r_ids = kept(r_outlet, r_has, r_date, r_dated)
    names, gid = np.unique(np.concatenate([s_ids, r_ids]),
                           return_inverse=True)
    s_gid, r_gid = gid[:len(s_keep)], gid[len(s_keep):]
    n = len(names)
    amount, loss = {
        "store_returns": ("sr_return_amt", "sr_net_loss"),
        "catalog_returns": ("cr_return_amount", "cr_net_loss"),
        "web_returns": ("wr_return_amt", "wr_net_loss")}[returns_t]
    # the union's zeros add nothing: each sum is over the branch that
    # carries the column
    sales_sum = group_sums(column(sales, prefix + "ext_sales_price")[s_keep],
                           s_gid, n, p)
    profit = group_sums(column(sales, prefix + "net_profit")[s_keep],
                        s_gid, n, p)
    returns_sum = group_sums(column(returns, amount)[r_keep], r_gid, n, p)
    profit_loss = group_sums(column(returns, loss)[r_keep], r_gid, n, p)
    return names, sales_sum, returns_sum, p.sub(profit, profit_loss)


def reference(tables, precision: str = "float64") -> pa.Table:
    """The whole ROLLUP in the query's order, NOT cut to the limit:
    each channel's rows by id, its subtotal, and the grand total, the
    sums computed in `precision` (a CONTROL below float64); keys, dates
    and joins stay exact."""
    p = Precision(precision)
    channel, ids, parts = [], [], {"sales": [], "returns": [], "profit": []}
    totals = {k: [] for k in parts}
    for name, spec in CHANNELS.items():
        names, *sums = _channel_rows(tables, name, p)
        channel += [name] * (len(names) + 1)
        ids += [spec[6] + s for s in names] + [None]
        for k, v in zip(parts, sums):
            sub = group_sums(v, np.zeros(len(v), np.int64), 1, p)
            parts[k] += [p.cast(v), sub]
            totals[k].append(sub)
    channel.append(None)
    ids.append(None)
    out = {"channel": pa.array(channel, pa.string()),
           "id": pa.array(ids, pa.string())}
    for k, v in parts.items():
        grand = group_sums(np.concatenate(totals[k]),
                           np.zeros(len(totals[k]), np.int64), 1, p)
        out[k] = pa.array(np.concatenate(v + [grand]).astype(np.float64))
    return pa.table(out).sort_by([("channel", "ascending", "at_start"),
                                  ("id", "ascending", "at_start")])


def _table_bytes(config: dict, id_bytes: dict) -> int:
    s = config["scale"]
    return (s["store_sales_rows"] * (4 + 4 + 8 + 8)
            + s["store_returns_rows"] * (4 + 4 + 8 + 8)
            + s["catalog_sales_rows"] * (4 + 4 + 8 + 8)
            + s["catalog_returns_rows"] * (4 + 4 + 8 + 8)
            + s["web_sales_rows"] * (4 + 4 + 4 + 4 + 8 + 8)
            + s["web_returns_rows"] * (4 + 4 + 4 + 8 + 8)
            + s["date_dim_rows"] * (4 + 4)
            + sum(s[t + "_rows"] * (4 + b) for t, b in id_bytes.items()))


def input_bytes(config: dict) -> int:
    """Logical Arrow bytes of the columns q5 reads: int32 keys, dates
    and order numbers, DOUBLE amounts, the ids as int32 dictionary
    codes."""
    return _table_bytes(config, {"store": 4, "catalog_page": 4,
                                 "web_site": 4})


def device_bytes(config: dict) -> int:
    """The least a device must read for q5 whatever implements it: each
    of those columns once, an id as the bytes its dimension's rows
    need (102 stores and 42 web sites one, 12,000 pages two)."""
    return _table_bytes(config, {"store": 1, "catalog_page": 2,
                                 "web_site": 1})
