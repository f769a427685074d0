"""Each generator gives its specification's row widths and domains,
and the same bytes for the same seed."""

import hashlib
import json
import os

import numpy as np
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from benchmark import run
from benchmark.datagen import file_rows
from benchmark.datagen import tpch_lineitem as li

ROWS = 40_000
SEED = 2_147_483_659  # beyond 31 bits, as the driver's seeds are


def config(name):
    return run.load_json(run.HERE, "configs", name + ".json")


def digest(dirs):
    h = hashlib.sha256()
    for table in sorted(dirs):
        for f in sorted(os.listdir(dirs[table])):
            with open(os.path.join(dirs[table], f), "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()


def generate(name, seed, tmp_path, sub):
    conf = config(name)
    gen = run.load_module("datagen", conf["generator"])
    return conf, gen.generate(conf, seed, str(tmp_path / sub), rows=ROWS)


@pytest.mark.parametrize("name", ["tpch_sf10_lineitem",
                                  "tpcds_sf10_store_sales_star"])
def test_same_seed_same_bytes(name, tmp_path):
    _, a = generate(name, SEED, tmp_path, "a")
    _, b = generate(name, SEED, tmp_path, "b")
    _, c = generate(name, SEED + 1, tmp_path, "c")
    assert digest(a) == digest(b) != digest(c)


def test_lineitem_widths_and_domains(tmp_path):
    conf, dirs = generate("tpch_sf10_lineitem", SEED, tmp_path, "t")
    files = sorted(os.listdir(dirs["lineitem"]))
    assert len(files) == conf["scale"]["files"] == 8
    t = pq.read_table(dirs["lineitem"])
    assert t.num_rows == ROWS
    assert t.schema.names == list(conf["schema"]["lineitem"])
    assert [str(f.type) for f in t.schema][:5] == [
        "double", "double", "double", "double", "date32[day]"]
    qty = t.column("l_quantity").to_numpy()
    assert qty.min() == 1 and qty.max() == 50 and (qty % 1 == 0).all()
    for col, top in (("l_discount", 10), ("l_tax", 8)):
        cents = t.column(col).to_numpy() * 100
        assert set(np.rint(cents).astype(int)) == set(range(top + 1))
        assert np.abs(cents - np.rint(cents)).max() < 1e-9
    price = t.column("l_extendedprice").to_numpy()
    retail = price / qty  # p_retailprice: 900.00 .. 2098.99
    assert 900.0 <= retail.min() and retail.max() <= 2098.995
    ship = t.column("l_shipdate").cast("int32").to_numpy()
    assert li.days(1992, 1, 2) <= ship.min()
    assert ship.max() <= li.days(1998, 12, 1)
    flag = t.column("l_returnflag").to_pylist()
    status = t.column("l_linestatus").to_pylist()
    assert set(flag) == {"A", "N", "R"} and set(status) == {"F", "O"}
    open_ = np.array(status) == "O"
    assert (open_ == (ship > li.CURRENT_DATE)).all()
    # a line not yet shipped cannot have been returned
    assert set(np.array(flag)[open_]) == {"N"}
    md = pq.ParquetFile(os.path.join(dirs["lineitem"], files[0])).metadata
    assert md.num_row_groups == 1
    for i in range(md.num_columns):
        col = md.row_group(0).column(i)
        assert col.compression == "UNCOMPRESSED"
        plain = "RLE_DICTIONARY" not in col.encodings
        assert plain == (col.path_in_schema not in ("l_returnflag",
                                                    "l_linestatus"))


def test_full_scale_sizes_pass_the_fused_engines_gates():
    """The arithmetic of the configuration's `gates`, from its shapes."""
    conf = config("tpch_sf10_lineitem")
    rows = conf["scale"]["lineitem_rows"]
    assert sum(file_rows(rows, 8)) == rows == 59_986_052
    pool = int(0.85 * 16_909_336_064)
    parquet_bytes = rows * (4 * 8 + 4) + rows // 4  # + bit-packed codes
    assert parquet_bytes * 6 < pool
    resident = rows * (4 * 8 + 4 + 2 * 4 + 7)  # values, codes, validity
    assert resident * 4 < pool
    # an uncached scan of SF10's files would go to the streaming engine
    # (stream/planner.py: files x 6 > half of free HBM); the cut passes
    assert parquet_bytes * 6 > pool // 2
    half = config("tpch_sf10_lineitem_half")
    assert half["reduced"] == ["scale"]
    cut = half["scale"]["lineitem_rows"]
    assert cut == 29_999_795 < rows
    assert (cut * (4 * 8 + 4) + cut // 4) * 6 < 0.95 * (pool // 2)
    same = ("schema", "session_conf", "generator", "assumed", "guarantees")
    assert all(half[k] == conf[k] for k in same)


def test_star_widths_and_domains(tmp_path):
    conf, dirs = generate("tpcds_sf10_store_sales_star", SEED, tmp_path, "t")
    scale = conf["scale"]
    dates = pq.read_table(dirs["date_dim"])
    assert dates.num_rows == scale["date_dim_rows"] == 73_049
    sk = dates.column("d_date_sk").to_numpy()
    assert sk[0] == 2_415_022 and sk[-1] == 2_488_070
    assert len(np.unique(sk)) == len(sk)
    first = {c: dates.column(c)[0].as_py() for c in ("d_year", "d_moy")}
    assert first == {"d_year": 1900, "d_moy": 1}
    # 2000-11-01 is Julian day 2451850
    row = int(np.flatnonzero(sk == 2_451_850)[0])
    assert (dates.column("d_year")[row].as_py(),
            dates.column("d_moy")[row].as_py()) == (2000, 11)
    item = pq.read_table(dirs["item"])
    assert item.num_rows == scale["item_rows"] == 102_000
    assert item.column("i_item_sk").to_numpy().tolist() == list(
        range(1, 102_001))
    assert pc.min_max(item.column("i_manufact_id")).as_py() == {
        "min": 1, "max": 1000}
    assert pc.min_max(item.column("i_manager_id")).as_py() == {
        "min": 1, "max": 100}
    brands = set(zip(item.column("i_brand_id").to_pylist(),
                     item.column("i_brand").to_pylist()))
    assert len(brands) == len({b for b, _ in brands}) == len(
        {n for _, n in brands})  # one name per id, one id per name
    assert len(set(item.column("i_category").to_pylist())) == 10
    sales = pq.read_table(dirs["store_sales"])
    assert sales.num_rows == ROWS
    assert [str(f.type) for f in sales.schema] == ["int32", "int32", "double"]
    nulls = sales.column("ss_sold_date_sk").null_count / ROWS
    assert 0.03 < nulls < 0.05
    assert sales.column("ss_item_sk").null_count == 0
    sold = pc.min_max(sales.column("ss_sold_date_sk")).as_py()
    assert 2_450_816 <= sold["min"] and sold["max"] <= 2_452_642
    price = sales.column("ss_ext_sales_price").to_numpy()
    assert 0.0 <= price.min() and price.max() <= 100 * 300.0
    assert np.abs(price * 100 - np.rint(price * 100)).max() < 1e-6
    assert json.dumps(conf["reduced"]) == "[]"
