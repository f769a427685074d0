"""What TPC-DS q3, q42, q52 and q55 share: `store_sales` joined to
`date_dim` and `item`, filtered on attributes of the two dimensions,
summed by year and brand or category, ordered, cut to 100 rows. Each
query's own file states its text and its parameters.
"""

import numpy as np
import pyarrow as pa

from benchmark.reference import Precision, column, group_sums

TABLES = ["store_sales", "date_dim", "item"]
LIMIT = 100


def build_star(tables, where: dict, group: list, select: list, total: str,
               order: list):
    """`where`: column -> value, ANDed. `group`: grouping columns.
    `select`: (source column, output name) in output order, `total` the
    name of sum(ss_ext_sales_price). `order`: (output name, asc|desc)."""
    from spark_rapids_tpu.api import functions as F

    sales, dates, item = (tables[t] for t in TABLES)
    cond = None
    for name, value in where.items():
        term = F.col(name) == value
        cond = term if cond is None else cond & term
    dated = sales.join(dates, sales["ss_sold_date_sk"] == dates["d_date_sk"])
    df = (dated.join(item, dated["ss_item_sk"] == item["i_item_sk"])
          .filter(cond)
          .groupBy(*group)
          .agg(F.sum("ss_ext_sales_price").alias(total))
          .select(*[F.col(src).alias(out) for src, out in select],
                  F.col(total)))
    return df.orderBy(*[F.col(c).asc() if d == "asc" else F.col(c).desc()
                        for c, d in order]).limit(LIMIT)


def _lookup(dim_keys: np.ndarray, keys: np.ndarray):
    """Row of the dimension for each key, and whether it has one."""
    by_key = np.argsort(dim_keys, kind="stable")
    pos = np.searchsorted(dim_keys[by_key], keys)
    pos[pos == len(dim_keys)] = 0
    row = by_key[pos]
    return row, dim_keys[row] == keys


_JOIN = "_star_join"  # the memo's key in the caller's `tables` dict


def _joined(tables: dict) -> dict:
    """Per fact row, its row in each dimension and whether it has one.
    The four queries share it, so it is kept in the caller's `tables`
    under a key of its own and worked out once."""
    if _JOIN not in tables:
        sales, dates, item = (tables[t] for t in TABLES)
        date_sk = sales.column("ss_sold_date_sk").combine_chunks()
        has_date = ~date_sk.is_null().to_numpy(zero_copy_only=False)
        date_row, date_hit = _lookup(
            column(dates, "d_date_sk"), date_sk.fill_null(0).to_numpy())
        item_row, item_hit = _lookup(
            column(item, "i_item_sk"), column(sales, "ss_item_sk"))
        tables[_JOIN] = {"matched": has_date & date_hit & item_hit,
                         "d_": (dates, date_row), "i_": (item, item_row)}
    return tables[_JOIN]


def reference_star(tables, where: dict, group: list, select: list,
                   total: str, order: list,
                   precision: str = "float64") -> pa.Table:
    """The whole grouped answer in the query's order, NOT cut to the
    limit: the comparison needs the rows beyond it to judge ties."""
    joined = _joined(tables)
    keep = joined["matched"].copy()
    for prefix in ("d_", "i_"):
        dim, row = joined[prefix]
        passes = np.ones(dim.num_rows, dtype=bool)
        for name, value in where.items():
            if name.startswith(prefix):
                passes &= column(dim, name) == value
        keep &= passes[row]
    kept = np.flatnonzero(keep)

    def attr(name):
        dim, row = joined[name[:2]]
        return column(dim, name)[row[kept]]

    keys = np.stack([attr(g).astype(np.int64) for g in group], axis=1)
    uniq, gid = np.unique(keys, axis=0, return_inverse=True)
    sums = group_sums(column(tables["store_sales"], "ss_ext_sales_price")[kept],
                      gid.reshape(-1), len(uniq), Precision(precision))
    out = {}
    for src, name in select:
        codes = uniq[:, group.index(src)]
        col = joined[src[:2]][0].column(src).combine_chunks()
        if hasattr(col, "dictionary"):
            out[name] = col.dictionary.take(pa.array(codes))
        else:
            out[name] = pa.array(codes, type=col.type)
    out[total] = pa.array(sums)
    return pa.table(out).sort_by(
        [(c, "ascending" if d == "asc" else "descending") for c, d in order])


def star_query(where: dict, group: list, select: list, total: str,
               order: list) -> tuple:
    """What a query's file exports, from its parameters: (ANSWER,
    build, reference, input_bytes, device_bytes)."""
    answer = {"keys": [out for _, out in select], "exact": [],
              "approx": [total], "order": order, "limit": LIMIT}

    def build(spark, tables):
        return build_star(tables, where, group, select, total, order)

    def reference(tables, precision="float64"):
        return reference_star(tables, where, group, select, total, order,
                              precision)

    return answer, build, reference, star_input_bytes, star_input_bytes


def star_input_bytes(config: dict) -> int:
    """Logical Arrow bytes a star query reads: two int32 keys and a
    double per fact row, and the dimension columns it touches (keys,
    two filter attributes, the group ids; names as int32 codes)."""
    s = config["scale"]
    return (s["store_sales_rows"] * (4 + 4 + 8) + s["date_dim_rows"] * 3 * 4
            + s["item_rows"] * 4 * 4)
