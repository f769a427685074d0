"""TPC-H `orders` and `lineitem`, the columns Q12 reads, rows from a
seed: orders and their 1..7 lines are modelled, which
`tpch_lineitem` does not.

Column domains follow the TPC-H specification v3, section 4.2.3:

- `o_orderkey`      the sparse keys of 4.2.3: the first 8 of every 32
                    consecutive integers, from 1; unique, ascending
- `o_orderpriority` one of the five priorities, uniform
- `o_orderdate`     uniform in [1992-01-01, 1998-12-31 - 151 days];
                    drawn, used for the line dates, not written
- lines an order    uniform in 1..7, then the least number of them
                    moved by one line so that the table has exactly the
                    published row count (59,986,052 at SF10: dbgen's
                    own draw gives it that sum, this one is made to)
- `l_orderkey`      its order's key; the table is clustered by it, as
                    dbgen writes it, and file i of `lineitem` holds the
                    lines of the orders in file i of `orders`
- `l_shipdate`      o_orderdate + 1..121 days
- `l_commitdate`    o_orderdate + 30..90 days
- `l_receiptdate`   l_shipdate + 1..30 days
- `l_shipmode`      one of the seven modes, uniform

Files are PLAIN, uncompressed, one row group each, the two string
columns dictionary-encoded. Each pair of files (orders i, lineitem i)
has a generator of its own keyed by (seed, i), so the same seed gives
the same bytes whatever the thread count.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from benchmark.datagen import file_rows
from benchmark.datagen.tpch_lineitem import END_DATE, START_DATE

PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIP_MODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
MAX_LINES = 7

_STRING = pa.dictionary(pa.int32(), pa.string())
ORDERS_SCHEMA = pa.schema([
    pa.field("o_orderkey", pa.int64(), nullable=False),
    pa.field("o_orderpriority", _STRING, nullable=False),
])
LINEITEM_SCHEMA = pa.schema([
    pa.field("l_orderkey", pa.int64(), nullable=False),
    pa.field("l_shipmode", _STRING, nullable=False),
    pa.field("l_shipdate", pa.date32(), nullable=False),
    pa.field("l_commitdate", pa.date32(), nullable=False),
    pa.field("l_receiptdate", pa.date32(), nullable=False),
])


def order_keys(first: int, n: int) -> np.ndarray:
    """Keys of the orders numbered first..first+n-1 (from 0): 8 used of
    every 32."""
    i = np.arange(first, first + n, dtype=np.int64)
    return (i >> 3) * 32 + (i & 7) + 1


def line_counts(rng, orders: int, lines: int) -> np.ndarray:
    """Lines of each order: uniform 1..7, then single lines taken from
    or given to orders drawn at random until they sum to `lines`."""
    if not orders <= lines <= MAX_LINES * orders:
        raise ValueError(f"{lines} lines cannot be dealt to {orders} "
                         f"orders of 1..{MAX_LINES}")
    counts = rng.integers(1, MAX_LINES + 1, orders, dtype=np.int32)
    while (diff := lines - int(counts.sum())) != 0:
        step = 1 if diff > 0 else -1
        room = np.flatnonzero(counts < MAX_LINES if step > 0 else counts > 1)
        counts[rng.choice(room, min(abs(diff), room.size),
                          replace=False)] += step
    return counts


def make_parts(seed: int, index: int, first_order: int, orders: int,
               lines: int) -> tuple:
    """-> (orders table, lineitem table) of file `index`."""
    rng = np.random.default_rng([seed, index])
    i32 = np.int32
    okey = order_keys(first_order, orders)
    priority = rng.integers(0, len(PRIORITIES), orders, dtype=i32)
    odate = rng.integers(START_DATE, END_DATE - 151 + 1, orders, dtype=i32)
    counts = line_counts(rng, orders, lines)
    lkey = np.repeat(okey, counts)
    ldate = np.repeat(odate, counts)
    ship = ldate + rng.integers(1, 122, lines, dtype=i32)
    commit = ldate + rng.integers(30, 91, lines, dtype=i32)
    receipt = ship + rng.integers(1, 31, lines, dtype=i32)
    mode = rng.integers(0, len(SHIP_MODES), lines, dtype=i32)
    return (
        pa.Table.from_arrays([
            pa.array(okey),
            pa.DictionaryArray.from_arrays(priority, PRIORITIES),
        ], schema=ORDERS_SCHEMA),
        pa.Table.from_arrays([
            pa.array(lkey),
            pa.DictionaryArray.from_arrays(mode, SHIP_MODES),
            pa.array(ship, type=pa.date32()),
            pa.array(commit, type=pa.date32()),
            pa.array(receipt, type=pa.date32()),
        ], schema=LINEITEM_SCHEMA))


def table_rows(config: dict, rows: int = None) -> tuple:
    """-> (orders rows, lineitem rows). `rows` cuts `lineitem`, and
    `orders` in the published proportion."""
    scale = config["scale"]
    if not rows:
        return scale["orders_rows"], scale["lineitem_rows"]
    orders = -(-rows * scale["orders_rows"] // scale["lineitem_rows"])
    return max(orders, scale["files"]), rows


def generate(config: dict, seed: int, out_dir: str, rows: int = None) -> dict:
    """Write `orders` and `lineitem` under `out_dir`; -> {table name:
    directory}. `rows` cuts the tables for rehearsals and tests only."""
    files = config["scale"]["files"]
    orders, lines = table_rows(config, rows)
    dirs = {name: os.path.join(out_dir, name)
            for name in ("orders", "lineitem")}
    for path in dirs.values():
        os.makedirs(path, exist_ok=True)
    per_file_orders = file_rows(orders, files)
    firsts = np.concatenate([[0], np.cumsum(per_file_orders)[:-1]])

    def write(args):
        i, first, n_orders, n_lines = args
        tables = make_parts(seed, i, int(first), n_orders, n_lines)
        for name, table, encoded in zip(
                ("orders", "lineitem"), tables,
                ("o_orderpriority", "l_shipmode")):
            pq.write_table(
                table, os.path.join(dirs[name], f"part-{i:02d}.parquet"),
                compression="NONE", use_dictionary=[encoded],
                row_group_size=max(table.num_rows, 1),
                data_page_size=64 << 20)

    with ThreadPoolExecutor(max_workers=files) as pool:
        list(pool.map(write, zip(range(files), firsts, per_file_orders,
                                 file_rows(lines, files))))
    return dirs
