"""TPC-H `customer`, `orders` and `lineitem`, the columns Q3 reads, rows
from a seed: customers, their orders and each order's 1..7 lines.

Column domains follow the TPC-H specification v3, section 4.2.3:

- `c_custkey`       1..customers, dense, ascending
- `c_mktsegment`    one of the five segments, uniform
- `o_orderkey`      the sparse keys of 4.2.3 (`tpch_lineitem_orders.
                    order_keys`: the first 8 of every 32 integers)
- `o_custkey`       uniform over the customer keys not divisible by 3:
                    a third of the customers have no order
- `o_orderdate`     uniform in [1992-01-01, 1998-12-31 - 151 days]
- `o_shippriority`  0
- lines an order    `tpch_lineitem_orders.line_counts`: uniform 1..7,
                    single lines moved until the table has exactly the
                    published row count
- `l_orderkey`      its order's key; the table is clustered by it, and
                    file i of `lineitem` holds the lines of the orders
                    in file i of `orders`
- `l_shipdate`      o_orderdate + 1..121 days
- `l_extendedprice` l_quantity (1..50) x p_retailprice of a part key
                    drawn from 1..SF x 200,000, as `tpch_lineitem`
- `l_discount`      0.00..0.10 in steps of 0.01

The two decimal(15,2) columns are written as DOUBLE, exact to the cent.
Files are PLAIN, uncompressed, one row group each, `c_mktsegment`
dictionary-encoded. Each pair of files (orders i, lineitem i) and each
file of `customer` has a generator of its own keyed by (seed, table,
i), so the same seed gives the same bytes whatever the thread count.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from benchmark.datagen import file_rows
from benchmark.datagen.tpch_lineitem import END_DATE, START_DATE
from benchmark.datagen.tpch_lineitem_orders import line_counts, order_keys

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
TABLES = ("customer", "orders", "lineitem")

CUSTOMER_SCHEMA = pa.schema([
    pa.field("c_custkey", pa.int64(), nullable=False),
    pa.field("c_mktsegment", pa.dictionary(pa.int32(), pa.string()),
             nullable=False),
])
ORDERS_SCHEMA = pa.schema([
    pa.field("o_orderkey", pa.int64(), nullable=False),
    pa.field("o_custkey", pa.int64(), nullable=False),
    pa.field("o_orderdate", pa.date32(), nullable=False),
    pa.field("o_shippriority", pa.int32(), nullable=False),
])
LINEITEM_SCHEMA = pa.schema([
    pa.field("l_orderkey", pa.int64(), nullable=False),
    pa.field("l_extendedprice", pa.float64(), nullable=False),
    pa.field("l_discount", pa.float64(), nullable=False),
    pa.field("l_shipdate", pa.date32(), nullable=False),
])


def make_customers(seed: int, index: int, first: int, n: int) -> pa.Table:
    """File `index` of `customer`: the keys first+1..first+n."""
    rng = np.random.default_rng([seed, 0, index])
    segment = rng.integers(0, len(SEGMENTS), n, dtype=np.int32)
    return pa.Table.from_arrays([
        pa.array(np.arange(first + 1, first + n + 1, dtype=np.int64)),
        pa.DictionaryArray.from_arrays(segment, SEGMENTS),
    ], schema=CUSTOMER_SCHEMA)


def customer_keys_with_orders(rng, n: int, customers: int) -> np.ndarray:
    """`n` keys uniform over 1..customers, none divisible by 3: the
    j-th such key is j + (j - 1) // 2 (1, 2, 4, 5, 7, 8, ...)."""
    having = customers - customers // 3
    j = rng.integers(1, having + 1, n, dtype=np.int64)
    return j + (j - 1) // 2


def make_parts(seed: int, index: int, first_order: int, orders: int,
               lines: int, customers: int, scale_factor: int) -> tuple:
    """-> (orders table, lineitem table) of file `index`."""
    rng = np.random.default_rng([seed, 1, index])
    i32 = np.int32
    okey = order_keys(first_order, orders)
    custkey = customer_keys_with_orders(rng, orders, customers)
    odate = rng.integers(START_DATE, END_DATE - 151 + 1, orders, dtype=i32)
    counts = line_counts(rng, orders, lines)
    lkey = np.repeat(okey, counts)
    ship = np.repeat(odate, counts) + rng.integers(1, 122, lines, dtype=i32)
    partkey = rng.integers(1, 200_000 * scale_factor + 1, lines, dtype=i32)
    qty = rng.integers(1, 51, lines, dtype=i32)
    retail_cents = 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1000)
    price = (qty.astype(np.int64) * retail_cents) / 100.0
    discount = rng.integers(0, 11, lines, dtype=i32) / 100.0
    return (
        pa.Table.from_arrays([
            pa.array(okey),
            pa.array(custkey),
            pa.array(odate, type=pa.date32()),
            pa.array(np.zeros(orders, dtype=i32)),
        ], schema=ORDERS_SCHEMA),
        pa.Table.from_arrays([
            pa.array(lkey),
            pa.array(price),
            pa.array(discount),
            pa.array(ship, type=pa.date32()),
        ], schema=LINEITEM_SCHEMA))


def table_rows(config: dict, rows: int = None) -> tuple:
    """-> (customer rows, orders rows, lineitem rows). `rows` cuts
    `lineitem`, and the other two in the published proportion."""
    scale = config["scale"]
    if not rows:
        return (scale["customer_rows"], scale["orders_rows"],
                scale["lineitem_rows"])

    def cut(table_rows: int) -> int:
        return max(-(-rows * table_rows // scale["lineitem_rows"]),
                   scale["files"])

    return cut(scale["customer_rows"]), cut(scale["orders_rows"]), rows


def _write(table: pa.Table, path: str, encoded=()) -> None:
    pq.write_table(table, path, compression="NONE",
                   use_dictionary=list(encoded),
                   row_group_size=max(table.num_rows, 1),
                   data_page_size=64 << 20)


def generate(config: dict, seed: int, out_dir: str, rows: int = None) -> dict:
    """Write the three tables under `out_dir`; -> {table name:
    directory}. `rows` cuts them for rehearsals and tests only."""
    scale = config["scale"]
    files = scale["files"]
    customers, orders, lines = table_rows(config, rows)
    dirs = {name: os.path.join(out_dir, name) for name in TABLES}
    for path in dirs.values():
        os.makedirs(path, exist_ok=True)

    def starts(per_file):
        return np.concatenate([[0], np.cumsum(per_file)[:-1]])

    per_file_customers = file_rows(customers, files)
    per_file_orders = file_rows(orders, files)

    def write(args):
        i, first_customer, n_customers, first_order, n_orders, n_lines = args
        name = f"part-{i:02d}.parquet"
        _write(make_customers(seed, i, int(first_customer), n_customers),
               os.path.join(dirs["customer"], name), ["c_mktsegment"])
        o, li = make_parts(seed, i, int(first_order), n_orders, n_lines,
                           customers, scale["scale_factor"])
        _write(o, os.path.join(dirs["orders"], name))
        _write(li, os.path.join(dirs["lineitem"], name))

    with ThreadPoolExecutor(max_workers=files) as pool:
        list(pool.map(write, zip(
            range(files), starts(per_file_customers), per_file_customers,
            starts(per_file_orders), per_file_orders,
            file_rows(lines, files))))
    return dirs
