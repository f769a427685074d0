"""TPC-DS's three sales channels with their returns, and `date_dim`,
`store`, `catalog_page` and `web_site`: the columns q5 reads, rows
from a seed.

The dates, items and prices of every sale and every return are the
star's draws (`tpcds_store_sales_star.make_sales_part`, imported): a
date key uniform in 2450816..2452642 (1998-01-02 .. 2003-01-02), 4%
null, and an extended price of quantity x sales price to the cent. The
`store_sales` files draw the streams the star's own `store_sales`
files draw, so a seed gives both cells the same dates and prices. On
top of them, from the specification v3's domains:

- `*_store_sk`, `*_catalog_page_sk`, `ws_web_site_sk`: uniform over
  the dimension's keys, 4% null;
- `*_net_profit`: the price less a wholesale cost of 25..200% of it,
  to the cent; it may be negative, as dsdgen's is;
- `web_sales` lines come in orders of 8..16 lines, the order numbers
  dense from 1; an order's lines hold consecutive items from a start
  drawn uniformly, so that (ws_item_sk, ws_order_number), the table's
  key, is unique;
- a return is a share of 1..100% of a sale's price, its net loss 5..50%
  of that, and its date 1..`RETURN_DAYS` days after the sale's (null
  where that is null); each `web_returns` row is a line of `web_sales`
  drawn without replacement, and carries its item and order number;
- `date_dim`: `d_date_sk` 2415022 (1900-01-02) upwards, one a day, and
  `d_date`, its day; `store`, `catalog_page`, `web_site`: keys 1..rows
  and dsdgen's 16-character business ids (`business_id`).

Files are PLAIN and uncompressed, one row group each, the ids
dictionary-encoded. Every file has a generator of its own keyed by
(seed, stream), so the same seed gives the same bytes whatever the
thread count. Surrogate keys and order numbers are INT32, as the
specification's `identifier`; decimal(7,2) amounts are DOUBLE.
"""

import datetime
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa

from benchmark.datagen import file_rows
from benchmark.datagen.tpch_customer_orders_lineitem import _write
from benchmark.datagen.tpcds_store_sales_star import (
    FIRST_DATE_SK,
    NULL_DATE_SHARE,
    _FIRST_DATE,
    make_date_dim,
    make_sales_part,
)

FACTS = ("store_sales", "store_returns", "catalog_sales",
         "catalog_returns", "web_sales", "web_returns")
DIMENSIONS = ("date_dim", "store", "catalog_page", "web_site")
TABLES = FACTS + DIMENSIONS
#: a return's date lies 1..RETURN_DAYS days after its sale's
RETURN_DAYS = 90
ORDER_LINES = (8, 16)
#: the first of each table's generator streams; file i draws stream + i
STREAMS = {"store_sales": 0, "store_returns": 100, "catalog_sales": 200,
           "catalog_returns": 300, "web_sales": 400, "web_returns": 500}

_i32, _f64 = pa.int32(), pa.float64()
_id = pa.dictionary(pa.int32(), pa.string())


def _schema(*fields):
    return pa.schema([pa.field(n, t, nullable=null) for n, t, null in fields])


SCHEMAS = {
    "store_sales": _schema(
        ("ss_sold_date_sk", _i32, True), ("ss_store_sk", _i32, True),
        ("ss_ext_sales_price", _f64, False), ("ss_net_profit", _f64, False)),
    "store_returns": _schema(
        ("sr_returned_date_sk", _i32, True), ("sr_store_sk", _i32, True),
        ("sr_return_amt", _f64, False), ("sr_net_loss", _f64, False)),
    "catalog_sales": _schema(
        ("cs_sold_date_sk", _i32, True), ("cs_catalog_page_sk", _i32, True),
        ("cs_ext_sales_price", _f64, False), ("cs_net_profit", _f64, False)),
    "catalog_returns": _schema(
        ("cr_returned_date_sk", _i32, True),
        ("cr_catalog_page_sk", _i32, True),
        ("cr_return_amount", _f64, False), ("cr_net_loss", _f64, False)),
    "web_sales": _schema(
        ("ws_sold_date_sk", _i32, True), ("ws_item_sk", _i32, False),
        ("ws_web_site_sk", _i32, True), ("ws_order_number", _i32, False),
        ("ws_ext_sales_price", _f64, False), ("ws_net_profit", _f64, False)),
    "web_returns": _schema(
        ("wr_returned_date_sk", _i32, True), ("wr_item_sk", _i32, False),
        ("wr_order_number", _i32, False), ("wr_return_amt", _f64, False),
        ("wr_net_loss", _f64, False)),
    "date_dim": _schema(("d_date_sk", _i32, False),
                        ("d_date", pa.date32(), False)),
    "store": _schema(("s_store_sk", _i32, False), ("s_store_id", _id, False)),
    "catalog_page": _schema(("cp_catalog_page_sk", _i32, False),
                            ("cp_catalog_page_id", _id, False)),
    "web_site": _schema(("web_site_sk", _i32, False),
                        ("web_site_id", _id, False)),
}
#: the dimension each fact's outlet key points into, and its id column
OUTLETS = {"store": "s_store_id", "catalog_page": "cp_catalog_page_id",
           "web_site": "web_site_id"}
_BKEY = "ABCDEFGHIJKLMNOP"


def business_id(key: int) -> str:
    """dsdgen's `mk_bkey`: 16 letters A..P, the key's 64 bits four at a
    time, the high word first and each word's low nibble first."""
    hi, lo = key >> 32, key & 0xFFFFFFFF
    return "".join(_BKEY[(w >> (4 * i)) & 15]
                   for w in (hi, lo) for i in range(8))


def _keys(rng, n: int, hi: int) -> pa.Array:
    """Uniform 1..hi, 4% null: a fact's foreign key into a dimension."""
    keys = rng.integers(1, hi + 1, n, dtype=np.int32)
    return pa.array(keys, mask=rng.random(n, dtype=np.float32)
                    < NULL_DATE_SHARE)


def _cents(x: np.ndarray) -> np.ndarray:
    return np.round(x * 100).astype(np.int64)


def _share(rng, cents: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """lo..hi percent of each amount, to the cent, as dollars."""
    return cents * rng.integers(lo, hi + 1, len(cents)) // 100 / 100.0


def _sales(seed: int, stream: int, n: int, items: int):
    """The star's draws for n sales: (date key, its nulls, item key,
    price in dollars), and a generator for what this file adds."""
    sold = make_sales_part(seed, stream, n, items)
    date = sold.column("ss_sold_date_sk").combine_chunks()
    return (date, sold.column("ss_item_sk").to_numpy(),
            sold.column("ss_ext_sales_price").to_numpy(),
            np.random.default_rng([seed, stream, 1]))


def _profit(rng, price: np.ndarray) -> pa.Array:
    cents = _cents(price)
    return pa.array((cents - cents * rng.integers(25, 201, len(cents))
                     // 100) / 100.0)


def _returned(rng, sold_date: pa.Array) -> pa.Array:
    """Each sale's return date, 1..RETURN_DAYS days after it."""
    lag = rng.integers(1, RETURN_DAYS + 1, len(sold_date), dtype=np.int32)
    return pa.array(sold_date.fill_null(0).to_numpy() + lag,
                    mask=sold_date.is_null().to_numpy(zero_copy_only=False))


def make_channel_sales(table: str, seed: int, index: int, n: int,
                       items: int, outlets: int) -> pa.Table:
    """File `index` of `store_sales` or `catalog_sales`."""
    date, _, price, rng = _sales(seed, STREAMS[table] + index, n, items)
    return pa.Table.from_arrays(
        [date, _keys(rng, n, outlets), pa.array(price), _profit(rng, price)],
        schema=SCHEMAS[table])


def make_channel_returns(table: str, seed: int, index: int, n: int,
                         items: int, outlets: int) -> pa.Table:
    """File `index` of `store_returns` or `catalog_returns`: returns of
    sales the star's draws make."""
    date, _, price, rng = _sales(seed, STREAMS[table] + index, n, items)
    amount = _share(rng, _cents(price), 1, 100)
    return pa.Table.from_arrays(
        [_returned(rng, date), _keys(rng, n, outlets), pa.array(amount),
         pa.array(_share(rng, _cents(amount), 5, 50))],
        schema=SCHEMAS[table])


def order_lines(rng, orders: int, lines: int) -> np.ndarray:
    """Lines of each order: uniform 8..16, then single lines taken from
    or given to orders drawn at random until they sum to `lines`."""
    lo, hi = ORDER_LINES
    if not lo * orders <= lines <= hi * orders:
        raise ValueError(f"{lines} lines cannot be dealt to {orders} "
                         f"orders of {lo}..{hi}")
    counts = rng.integers(lo, hi + 1, orders, dtype=np.int32)
    while (diff := lines - int(counts.sum())) != 0:
        step = 1 if diff > 0 else -1
        room = np.flatnonzero(counts < hi if step > 0 else counts > lo)
        counts[rng.choice(room, min(abs(diff), room.size),
                          replace=False)] += step
    return counts


def make_web_sales(seed: int, index: int, first_order: int, orders: int,
                   n: int, items: int, sites: int) -> pa.Table:
    """File `index` of `web_sales`: the orders first_order+1 ..
    first_order+orders, their lines in order."""
    date, _, price, rng = _sales(seed, STREAMS["web_sales"] + index, n,
                                 items)
    counts = order_lines(rng, orders, n)
    order = np.repeat(np.arange(first_order + 1, first_order + orders + 1,
                                dtype=np.int32), counts)
    starts = np.cumsum(counts) - counts
    nth = np.arange(n) - np.repeat(starts, counts)
    first_item = np.repeat(rng.integers(0, items, orders), counts)
    item = ((first_item + nth) % items + 1).astype(np.int32)
    return pa.Table.from_arrays(
        [date, pa.array(item), _keys(rng, n, sites), pa.array(order),
         pa.array(price), _profit(rng, price)],
        schema=SCHEMAS["web_sales"])


def make_web_returns(seed: int, sales: pa.Table, n: int) -> pa.Table:
    """`web_returns`: n lines of `web_sales` drawn without replacement,
    in the sales' order."""
    rng = np.random.default_rng([seed, STREAMS["web_returns"]])
    lines = np.sort(rng.choice(sales.num_rows, n, replace=False))
    sale = sales.take(pa.array(lines))
    amount = _share(rng, _cents(sale.column("ws_ext_sales_price")
                                .to_numpy()), 1, 100)
    return pa.Table.from_arrays(
        [_returned(rng, sale.column("ws_sold_date_sk").combine_chunks()),
         sale.column("ws_item_sk"), sale.column("ws_order_number"),
         pa.array(amount), pa.array(_share(rng, _cents(amount), 5, 50))],
        schema=SCHEMAS["web_returns"])


def make_date_days(rows: int) -> pa.Table:
    """The star's `date_dim` keys, each with its day."""
    sk = make_date_dim(rows).column("d_date_sk").combine_chunks()
    day0 = (_FIRST_DATE - datetime.date(1970, 1, 1)).days
    days = sk.to_numpy() - FIRST_DATE_SK + day0
    return pa.Table.from_arrays([sk, pa.array(days, pa.int32()).cast(
        pa.date32())], schema=SCHEMAS["date_dim"])


def make_outlets(table: str, rows: int) -> pa.Table:
    ids = [business_id(k) for k in range(1, rows + 1)]
    return pa.Table.from_arrays([
        pa.array(np.arange(1, rows + 1, dtype=np.int32)),
        pa.DictionaryArray.from_arrays(
            pa.array(np.arange(rows, dtype=np.int32)), ids),
    ], schema=SCHEMAS[table])


def table_rows(config: dict, rows: int = None) -> dict:
    """-> {fact table: rows, "web_orders": orders}. `rows` cuts
    `store_sales`, and the other facts and the web orders in the
    published proportion."""
    scale = config["scale"]
    full = {t: scale[t + "_rows"] for t in FACTS}
    full["web_orders"] = scale["web_orders"]
    if not rows:
        return full
    big = full["store_sales"]
    out = {t: max(-(-rows * n // big), 1) for t, n in full.items()}
    lo, hi = ORDER_LINES
    files = scale["files"]["web_sales"]
    out["web_orders"] = min(max(out["web_orders"],
                                -(-out["web_sales"] // hi)),
                            out["web_sales"] // lo)
    out["web_orders"] = max(out["web_orders"], files)
    out["web_sales"] = max(out["web_sales"], lo * out["web_orders"])
    return out


def generate(config: dict, seed: int, out_dir: str, rows: int = None) -> dict:
    """Write the ten tables under `out_dir`; -> {table name:
    directory}. `rows` cuts the six facts for rehearsals and tests
    only; the dimensions are whole at every size."""
    scale = config["scale"]
    files, items = scale["files"], scale["item_rows"]
    n = table_rows(config, rows)
    dirs = {name: os.path.join(out_dir, name) for name in TABLES}
    for path in dirs.values():
        os.makedirs(path, exist_ok=True)

    def path(table, i):
        return os.path.join(dirs[table], f"part-{i:02d}.parquet")

    jobs = []
    for table, outlet in (("store", "store"), ("catalog", "catalog_page")):
        for kind, make in (("sales", make_channel_sales),
                           ("returns", make_channel_returns)):
            name = f"{table}_{kind}"
            for i, k in enumerate(file_rows(n[name], files[name])):
                jobs.append((name, i, make, (name, seed, i, k, items,
                                             scale[outlet + "_rows"])))
    web_files = files["web_sales"]
    first = np.cumsum([0] + file_rows(n["web_orders"], web_files))
    for i, (o, k) in enumerate(zip(file_rows(n["web_orders"], web_files),
                                   file_rows(n["web_sales"], web_files))):
        jobs.append(("web_sales", i, make_web_sales,
                     (seed, i, int(first[i]), o, k, items,
                      scale["web_site_rows"])))

    def write(job):
        table, i, make, args = job
        out = make(*args)
        _write(out, path(table, i))
        return out if table == "web_sales" else None

    with ThreadPoolExecutor(max_workers=8) as pool:
        web = [t for t in pool.map(write, jobs) if t is not None]
    web_returns = make_web_returns(seed, pa.concat_tables(web),
                                   n["web_returns"])
    start = 0
    for i, k in enumerate(file_rows(n["web_returns"], files["web_returns"])):
        _write(web_returns.slice(start, k), path("web_returns", i))
        start += k
    _write(make_date_days(scale["date_dim_rows"]), path("date_dim", 0))
    for table, id_col in OUTLETS.items():
        _write(make_outlets(table, scale[table + "_rows"]), path(table, 0),
               [id_col])
    return dirs
