"""TPC-DS `store_sales` with `date_dim` and `item`: the columns that
q3, q42, q52 and q55 read, rows from a seed.

From the TPC-DS specification v3 (table layouts of section 2,
dsdgen's domains):

- `date_dim`    one row per day, `d_date_sk` 2415022 (1900-01-02) ..
                2488070 (2100-01-01), 73,049 rows at every scale
                factor; `d_year`, `d_moy` of that day. Not random.
- `item`        `i_item_sk` 1..rows; `i_category_id` 1..10 with the
                ten category names, a class 1..16 within it, a brand
                1..10 within the class; `i_brand_id` = category x
                1,000,000 + class x 1,000 + brand, and `i_brand` a
                syllable name fixed by that id; `i_manufact_id`
                1..1000, `i_manager_id` 1..100.
- `store_sales` `ss_sold_date_sk` in 2450816..2452642 (1998-01-02 ..
                2003-01-02), 4% null; `ss_item_sk` 1..item rows;
                `ss_ext_sales_price` = quantity 1..100 x sales price,
                sales price = wholesale 1.00..100.00 marked up 0..200%
                and discounted 0..100%, to the cent.

`assumed` in the configuration: foreign keys uniform where dsdgen is
seasonal, brand names made here, the decimal(7,2) price written as
DOUBLE. Dimension keys are unique by construction. Surrogate keys and
ids are INT32 as the specification's `integer`.
"""

import datetime
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from benchmark.datagen import file_rows

FIRST_DATE_SK = 2415022          # 1900-01-02
_FIRST_DATE = datetime.date(1900, 1, 2)
SALES_FIRST_SK, SALES_LAST_SK = 2450816, 2452642
NULL_DATE_SHARE = 0.04
CATEGORIES = ["Books", "Children", "Electronics", "Home", "Jewelry",
              "Men", "Music", "Shoes", "Sports", "Women"]
_SYLLABLES = ["amalg", "importo", "edu pack", "exporti", "scholar",
              "brand", "corp", "maxi", "univ", "nameless", "able", "ought",
              "pri", "ese", "anti", "cally"]
CLASSES, BRANDS_PER_CLASS = 16, 10

_i32 = pa.int32()
_dict = pa.dictionary(pa.int32(), pa.string())
DATE_DIM_SCHEMA = pa.schema([
    pa.field("d_date_sk", _i32, nullable=False),
    pa.field("d_year", _i32, nullable=False),
    pa.field("d_moy", _i32, nullable=False)])
ITEM_SCHEMA = pa.schema([
    pa.field("i_item_sk", _i32, nullable=False),
    pa.field("i_brand_id", _i32, nullable=False),
    pa.field("i_brand", _dict, nullable=False),
    pa.field("i_category_id", _i32, nullable=False),
    pa.field("i_category", _dict, nullable=False),
    pa.field("i_manufact_id", _i32, nullable=False),
    pa.field("i_manager_id", _i32, nullable=False)])
STORE_SALES_SCHEMA = pa.schema([
    pa.field("ss_sold_date_sk", _i32, nullable=True),
    pa.field("ss_item_sk", _i32, nullable=False),
    pa.field("ss_ext_sales_price", pa.float64(), nullable=False)])


def brand_name(category: int, klass: int, brand: int) -> str:
    """The brand's name, fixed by its id: a syllable for the class, one
    for the category, and the brand's number."""
    return f"{_SYLLABLES[klass - 1]}{_SYLLABLES[category - 1]} #{brand}"


def make_date_dim(rows: int) -> pa.Table:
    sk = np.arange(FIRST_DATE_SK, FIRST_DATE_SK + rows, dtype=np.int32)
    day0 = np.datetime64(_FIRST_DATE, "D")
    dates = day0 + np.arange(rows)
    year = dates.astype("datetime64[Y]").astype(np.int32) + 1970
    moy = dates.astype("datetime64[M]").astype(np.int32) % 12 + 1
    return pa.Table.from_arrays(
        [pa.array(sk), pa.array(year), pa.array(moy.astype(np.int32))],
        schema=DATE_DIM_SCHEMA)


def make_item(seed: int, rows: int) -> pa.Table:
    rng = np.random.default_rng([seed, 1_000_001])
    i32 = np.int32
    cat = rng.integers(1, len(CATEGORIES) + 1, rows, dtype=i32)
    klass = rng.integers(1, CLASSES + 1, rows, dtype=i32)
    brand = rng.integers(1, BRANDS_PER_CLASS + 1, rows, dtype=i32)
    # every brand a code of its own, in (category, class, brand) order
    code = ((cat - 1) * CLASSES + (klass - 1)) * BRANDS_PER_CLASS + brand - 1
    names = [brand_name(c, k, b)
             for c in range(1, len(CATEGORIES) + 1)
             for k in range(1, CLASSES + 1)
             for b in range(1, BRANDS_PER_CLASS + 1)]
    return pa.Table.from_arrays([
        pa.array(np.arange(1, rows + 1, dtype=i32)),
        pa.array(cat * 1_000_000 + klass * 1_000 + brand),
        pa.DictionaryArray.from_arrays(code.astype(i32), names),
        pa.array(cat),
        pa.DictionaryArray.from_arrays(cat - 1, CATEGORIES),
        pa.array(rng.integers(1, 1001, rows, dtype=i32)),
        pa.array(rng.integers(1, 101, rows, dtype=i32)),
    ], schema=ITEM_SCHEMA)


def make_sales_part(seed: int, index: int, n: int, item_rows: int
                    ) -> pa.Table:
    rng = np.random.default_rng([seed, index])
    i32 = np.int32
    date_sk = rng.integers(SALES_FIRST_SK, SALES_LAST_SK + 1, n, dtype=i32)
    date_null = rng.random(n, dtype=np.float32) < NULL_DATE_SHARE
    item_sk = rng.integers(1, item_rows + 1, n, dtype=i32)
    qty = rng.integers(1, 101, n, dtype=i32).astype(np.int64)
    wholesale = rng.integers(100, 10_001, n, dtype=i32).astype(np.int64)
    list_cents = wholesale * (100 + rng.integers(0, 201, n, dtype=i32)) // 100
    sales_cents = list_cents * (100 - rng.integers(0, 101, n, dtype=i32)) // 100
    return pa.Table.from_arrays([
        pa.array(date_sk, mask=date_null),
        pa.array(item_sk),
        pa.array(sales_cents * qty / 100.0),
    ], schema=STORE_SALES_SCHEMA)


def generate(config: dict, seed: int, out_dir: str, rows: int = None) -> dict:
    """Write the three tables under `out_dir`; -> {table: directory}.
    `rows` cuts the fact table for rehearsals and tests only."""
    scale = config["scale"]
    rows = rows or scale["store_sales_rows"]
    files = scale["files"]
    dirs = {t: os.path.join(out_dir, t)
            for t in ("store_sales", "date_dim", "item")}
    for d in dirs.values():
        os.makedirs(d, exist_ok=True)
    plain = dict(compression="NONE", data_page_size=64 << 20)
    pq.write_table(make_date_dim(scale["date_dim_rows"]),
                   os.path.join(dirs["date_dim"], "part-00.parquet"),
                   use_dictionary=False, **plain)
    pq.write_table(make_item(seed, scale["item_rows"]),
                   os.path.join(dirs["item"], "part-00.parquet"),
                   use_dictionary=["i_brand", "i_category"], **plain)

    def write(i_n):
        i, n = i_n
        pq.write_table(
            make_sales_part(seed, i, n, scale["item_rows"]),
            os.path.join(dirs["store_sales"], f"part-{i:02d}.parquet"),
            use_dictionary=False, row_group_size=n, **plain)

    with ThreadPoolExecutor(max_workers=files) as pool:
        list(pool.map(write, enumerate(file_rows(rows, files))))
    return dirs
