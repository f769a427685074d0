"""TPC-H `lineitem`, the seven columns Q1 and Q6 read, rows from a seed.

Column domains follow the TPC-H specification v3, section 4.2.3:

- `l_quantity`      random integer 1..50
- `l_extendedprice` l_quantity x p_retailprice of a part key drawn from
                    1..SF x 200,000, with p_retailprice =
                    (90000 + ((partkey / 10) mod 20001)
                     + 100 x (partkey mod 1000)) / 100
- `l_discount`      0.00..0.10, `l_tax` 0.00..0.08, steps of 0.01
- `l_shipdate`      o_orderdate + 1..121 days, o_orderdate uniform in
                    [1992-01-01, 1998-12-31 - 151 days]
- `l_returnflag`    "R" or "A" when l_receiptdate (= l_shipdate +
                    1..30 days) <= 1995-06-17, else "N"
- `l_linestatus`    "O" when l_shipdate > 1995-06-17, else "F"

Rows are independent draws: orders are not modelled (listed under
`assumed` in the configuration). The four decimal(15,2) columns are
written as DOUBLE, exact to the cent. Files are PLAIN, uncompressed,
one row group each, the two flag columns dictionary-encoded: what
`io/parquet_plain.py` serves device-direct for the numeric columns.

Each file has a generator of its own keyed by (seed, file index), so
the same seed gives the same bytes whatever the thread count.
"""

import datetime
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from benchmark.datagen import file_rows

_EPOCH = datetime.date(1970, 1, 1)


def days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - _EPOCH).days


START_DATE = days(1992, 1, 1)
END_DATE = days(1998, 12, 31)
CURRENT_DATE = days(1995, 6, 17)
RETURN_FLAGS = ["A", "N", "R"]
LINE_STATUS = ["F", "O"]

SCHEMA = pa.schema([
    pa.field("l_quantity", pa.float64(), nullable=False),
    pa.field("l_extendedprice", pa.float64(), nullable=False),
    pa.field("l_discount", pa.float64(), nullable=False),
    pa.field("l_tax", pa.float64(), nullable=False),
    pa.field("l_shipdate", pa.date32(), nullable=False),
    pa.field("l_returnflag", pa.dictionary(pa.int32(), pa.string()),
             nullable=False),
    pa.field("l_linestatus", pa.dictionary(pa.int32(), pa.string()),
             nullable=False),
])


def make_part(seed: int, index: int, n: int, scale_factor: int) -> pa.Table:
    rng = np.random.default_rng([seed, index])
    i32 = np.int32
    partkey = rng.integers(1, 200_000 * scale_factor + 1, n, dtype=i32)
    qty = rng.integers(1, 51, n, dtype=i32)
    retail_cents = 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1000)
    price = (qty.astype(np.int64) * retail_cents) / 100.0
    discount = rng.integers(0, 11, n, dtype=i32) / 100.0
    tax = rng.integers(0, 9, n, dtype=i32) / 100.0
    order = rng.integers(START_DATE, END_DATE - 151 + 1, n, dtype=i32)
    ship = order + rng.integers(1, 122, n, dtype=i32)
    receipt = ship + rng.integers(1, 31, n, dtype=i32)
    returned = rng.integers(0, 2, n, dtype=i32) * 2  # "A" = 0, "R" = 2
    flag = np.where(receipt <= CURRENT_DATE, returned, 1).astype(i32)
    status = (ship > CURRENT_DATE).astype(i32)
    return pa.Table.from_arrays([
        pa.array(qty.astype(np.float64)),
        pa.array(price),
        pa.array(discount),
        pa.array(tax),
        pa.array(ship, type=pa.date32()),
        pa.DictionaryArray.from_arrays(flag, RETURN_FLAGS),
        pa.DictionaryArray.from_arrays(status, LINE_STATUS),
    ], schema=SCHEMA)


def generate(config: dict, seed: int, out_dir: str, rows: int = None) -> dict:
    """Write `lineitem` under `out_dir`; -> {table name: directory}.
    `rows` cuts the table for rehearsals and tests only."""
    scale = config["scale"]
    rows = rows or scale["lineitem_rows"]
    files = scale["files"]
    path = os.path.join(out_dir, "lineitem")
    os.makedirs(path, exist_ok=True)

    def write(i_n):
        i, n = i_n
        pq.write_table(
            make_part(seed, i, n, scale["scale_factor"]),
            os.path.join(path, f"part-{i:02d}.parquet"),
            compression="NONE",
            use_dictionary=["l_returnflag", "l_linestatus"],
            row_group_size=n, data_page_size=64 << 20)

    with ThreadPoolExecutor(max_workers=files) as pool:
        list(pool.map(write, enumerate(file_rows(rows, files))))
    return {"lineitem": path}
