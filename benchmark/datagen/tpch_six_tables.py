"""TPC-H `customer`, `orders`, `lineitem`, `supplier`, `nation` and
`region`, the columns Q5 reads, rows from a seed.

Orders, their 1..7 lines, dates and prices are Q3's generator's
(`tpch_customer_orders_lineitem.make_parts`, imported): the same seed
gives Q3's `orders` and Q3's `lineitem` prices here. Column domains
follow the TPC-H specification v3, section 4.2.3:

- `c_custkey`       1..customers, dense, ascending
- `c_nationkey`     uniform 0..24
- `o_*`, `l_orderkey`, `l_extendedprice`, `l_discount`: Q3's generator
- `l_suppkey`       the i-th supplier (i uniform 0..3) of the line's part
                    key, the one Q3's generator draws for its price:
                    (partkey + i x (S/4 + (partkey - 1) / S)) mod S + 1,
                    S the suppliers
- `s_suppkey`       1..S, dense, ascending
- `s_nationkey`     uniform 0..24
- `nation`, `region` the specification's 25 and 5 fixed rows

Files are PLAIN, uncompressed, one row group each, `n_name` and
`r_name` dictionary-encoded. Each file of `customer`, each pair of
files (orders i, lineitem i) and `supplier` has a generator of its own
keyed by (seed, table, i), so the same seed gives the same bytes
whatever the thread count.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa

from benchmark.datagen import file_rows
from benchmark.datagen import tpch_customer_orders_lineitem as q3
from benchmark.datagen.tpch_lineitem import END_DATE, START_DATE
from benchmark.datagen.tpch_lineitem_orders import line_counts

TABLES = ("customer", "orders", "lineitem", "supplier", "nation", "region")

#: (n_name, n_regionkey) of n_nationkey 0..24 (4.2.3)
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
#: r_name of r_regionkey 0..4 (4.2.3)
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]

KEY = pa.int64()
CODED = pa.dictionary(pa.int32(), pa.string())
CUSTOMER_SCHEMA = pa.schema([
    pa.field("c_custkey", KEY, nullable=False),
    pa.field("c_nationkey", KEY, nullable=False)])
ORDERS_SCHEMA = pa.schema([
    pa.field("o_orderkey", KEY, nullable=False),
    pa.field("o_custkey", KEY, nullable=False),
    pa.field("o_orderdate", pa.date32(), nullable=False)])
LINEITEM_SCHEMA = pa.schema([
    pa.field("l_orderkey", KEY, nullable=False),
    pa.field("l_suppkey", KEY, nullable=False),
    pa.field("l_extendedprice", pa.float64(), nullable=False),
    pa.field("l_discount", pa.float64(), nullable=False)])
SUPPLIER_SCHEMA = pa.schema([
    pa.field("s_suppkey", KEY, nullable=False),
    pa.field("s_nationkey", KEY, nullable=False)])
NATION_SCHEMA = pa.schema([
    pa.field("n_nationkey", KEY, nullable=False),
    pa.field("n_name", CODED, nullable=False),
    pa.field("n_regionkey", KEY, nullable=False)])
REGION_SCHEMA = pa.schema([
    pa.field("r_regionkey", KEY, nullable=False),
    pa.field("r_name", CODED, nullable=False)])


def supplier_of(partkey: np.ndarray, i: np.ndarray, suppliers: int
                ) -> np.ndarray:
    """The i-th of the four suppliers of each part key (4.2.3)."""
    p = partkey.astype(np.int64)
    s = suppliers
    return (p + i * (s // 4 + (p - 1) // s)) % s + 1


def part_keys(seed: int, index: int, orders: int, lines: int,
              customers: int, scale_factor: int) -> np.ndarray:
    """The part key of each line of file `index`, as Q3's `make_parts`
    draws it for the line's price: its generator's draws, in its order."""
    rng = np.random.default_rng([seed, 1, index])
    q3.customer_keys_with_orders(rng, orders, customers)
    rng.integers(START_DATE, END_DATE - 151 + 1, orders, dtype=np.int32)
    line_counts(rng, orders, lines)
    rng.integers(1, 122, lines, dtype=np.int32)
    return rng.integers(1, 200_000 * scale_factor + 1, lines, dtype=np.int32)


def make_customers(seed: int, index: int, first: int, n: int) -> pa.Table:
    """File `index` of `customer`: the keys first+1..first+n."""
    rng = np.random.default_rng([seed, 2, index])
    return pa.Table.from_arrays([
        pa.array(np.arange(first + 1, first + n + 1, dtype=np.int64)),
        pa.array(rng.integers(0, len(NATIONS), n, dtype=np.int64)),
    ], schema=CUSTOMER_SCHEMA)


def make_parts(seed: int, index: int, first_order: int, orders: int,
               lines: int, customers: int, suppliers: int,
               scale_factor: int) -> tuple:
    """-> (orders table, lineitem table) of file `index`."""
    o, li = q3.make_parts(seed, index, first_order, orders, lines,
                          customers, scale_factor)
    partkey = part_keys(seed, index, orders, lines, customers, scale_factor)
    nth = np.random.default_rng([seed, 3, index]).integers(
        0, 4, lines, dtype=np.int64)
    return (
        pa.Table.from_arrays([o.column(n) for n in ORDERS_SCHEMA.names],
                             schema=ORDERS_SCHEMA),
        pa.Table.from_arrays([
            li.column("l_orderkey"),
            pa.array(supplier_of(partkey, nth, suppliers)),
            li.column("l_extendedprice"),
            li.column("l_discount"),
        ], schema=LINEITEM_SCHEMA))


def make_supplier(seed: int, n: int) -> pa.Table:
    rng = np.random.default_rng([seed, 4])
    return pa.Table.from_arrays([
        pa.array(np.arange(1, n + 1, dtype=np.int64)),
        pa.array(rng.integers(0, len(NATIONS), n, dtype=np.int64)),
    ], schema=SUPPLIER_SCHEMA)


def make_nation() -> pa.Table:
    names = [name for name, _ in NATIONS]
    return pa.Table.from_arrays([
        pa.array(np.arange(len(NATIONS), dtype=np.int64)),
        pa.DictionaryArray.from_arrays(
            pa.array(np.arange(len(NATIONS), dtype=np.int32)), names),
        pa.array(np.array([r for _, r in NATIONS], dtype=np.int64)),
    ], schema=NATION_SCHEMA)


def make_region() -> pa.Table:
    return pa.Table.from_arrays([
        pa.array(np.arange(len(REGIONS), dtype=np.int64)),
        pa.DictionaryArray.from_arrays(
            pa.array(np.arange(len(REGIONS), dtype=np.int32)), REGIONS),
    ], schema=REGION_SCHEMA)


def table_rows(config: dict, rows: int = None) -> tuple:
    """-> (customer, orders, lineitem, supplier rows). `rows` cuts
    `lineitem`, and the other three in the published proportion."""
    scale = config["scale"]
    customers, orders, lines = q3.table_rows(config, rows)
    if not rows:
        return customers, orders, lines, scale["supplier_rows"]
    suppliers = max(-(-rows * scale["supplier_rows"]
                      // scale["lineitem_rows"]), 4)
    return customers, orders, lines, suppliers


def generate(config: dict, seed: int, out_dir: str, rows: int = None) -> dict:
    """Write the six tables under `out_dir`; -> {table name:
    directory}. `rows` cuts the four large ones for rehearsals and
    tests only; nation and region are whole at every size."""
    scale = config["scale"]
    files = scale["files"]
    customers, orders, lines, suppliers = table_rows(config, rows)
    dirs = {name: os.path.join(out_dir, name) for name in TABLES}
    for path in dirs.values():
        os.makedirs(path, exist_ok=True)

    def starts(per_file):
        return np.concatenate([[0], np.cumsum(per_file)[:-1]])

    per_file_customers = file_rows(customers, files)
    per_file_orders = file_rows(orders, files)
    one = "part-00.parquet"

    def write(args):
        i, first_customer, n_customers, first_order, n_orders, n_lines = args
        name = f"part-{i:02d}.parquet"
        q3._write(make_customers(seed, i, int(first_customer), n_customers),
                  os.path.join(dirs["customer"], name))
        o, li = make_parts(seed, i, int(first_order), n_orders, n_lines,
                           customers, suppliers, scale["scale_factor"])
        q3._write(o, os.path.join(dirs["orders"], name))
        q3._write(li, os.path.join(dirs["lineitem"], name))

    with ThreadPoolExecutor(max_workers=files) as pool:
        list(pool.map(write, zip(
            range(files), starts(per_file_customers), per_file_customers,
            starts(per_file_orders), per_file_orders,
            file_rows(lines, files))))
    q3._write(make_supplier(seed, suppliers),
              os.path.join(dirs["supplier"], one))
    q3._write(make_nation(), os.path.join(dirs["nation"], one), ["n_name"])
    q3._write(make_region(), os.path.join(dirs["region"], one), ["r_name"])
    return dirs
