"""The packed multi-key sort (ops/common.py `key_fields`,
`pack_fields`, `sort_permutation_fields`) against `numpy.lexsort`, and
`group_by` through it against a plain numpy grouping: mixed int / f64 /
dictionary / string keys, both directions, nulls first and last, NaN
and -0.0, ties kept stable; a key set that packs into one 32-bit
operand and ones that go as passes, never more than one key operand."""

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.exec.fused import upload_narrowed
from spark_rapids_tpu.ops import common, segmented

N = 3000
SEED = 20261004


def _table(seed=SEED, n=N):
    rng = np.random.default_rng(seed)
    f = rng.choice([0.0, -0.0, 1.5, -2.25, np.nan, np.inf, -np.inf, 7e300,
                    -7e300, 1e-310], n)
    names = ["pear", "apple", "fig", "apple pie", "", "zest", "Fig"]
    return pa.table({
        "small": pa.array(rng.integers(-3, 4, n), pa.int32(),
                          mask=rng.random(n) < 0.1),
        "wide": pa.array(rng.integers(-2 ** 62, 2 ** 62, n), pa.int64(),
                         mask=rng.random(n) < 0.1),
        "f": pa.array(f, pa.float64(), mask=rng.random(n) < 0.1),
        "code": pa.DictionaryArray.from_arrays(
            pa.array(rng.integers(0, len(names), n).astype(np.int32),
                     mask=rng.random(n) < 0.1), names),
        "s": pa.array([names[i] for i in rng.integers(0, len(names), n)]),
        "flag": pa.array(rng.random(n) < 0.5),
        "few": pa.array(rng.integers(0, 5, n), pa.int32()),
    })


def _numpy_key(arr: pa.ChunkedArray, ascending: bool, nulls_first: bool):
    """lexsort keys (least significant first) that order one column as
    SQL does: null rank above the value's rank among the distinct
    values (Double.compare order: -0.0 < 0.0, NaN last)."""
    arr = arr.combine_chunks()
    if pa.types.is_dictionary(arr.type):
        arr = arr.dictionary_decode()
    valid = ~np.asarray(arr.is_null())
    vals = arr.to_pylist()

    def order_key(v):
        if isinstance(v, float):
            if v != v:
                return (2, 0.0, 0)
            return (1, v, 0 if (v == 0 and np.signbit(v)) else 1)
        if isinstance(v, str):
            return v.encode()
        return v

    distinct = sorted({order_key(v) for v in vals if v is not None})
    rank_of = {k: r for r, k in enumerate(distinct)}
    rank = np.array([rank_of[order_key(v)] if v is not None else 0
                     for v in vals], np.int64)
    if not ascending:
        rank = -rank
    rank = np.where(valid, rank, 0)
    null_rank = np.where(valid, 1, 0) if nulls_first else \
        np.where(valid, 0, 1)
    return [rank, null_rank]


def _batch(table, narrow=True):
    return upload_narrowed(table, narrow=narrow)


ORDERS = [
    pytest.param([("few", True, True), ("flag", False, False)], "packed",
                 id="8-bits-one-operand"),
    pytest.param([("small", True, True), ("code", False, False),
                  ("few", True, False), ("flag", True, True)], "packed",
                 id="packed-under-32-bits"),
    pytest.param([("small", False, True), ("f", False, False),
                  ("code", True, True)], "passes", id="int-f64desc-dict"),
    pytest.param([("few", True, True), ("f", False, False),
                  ("wide", True, True)], "passes", id="over-96-bits"),
    pytest.param([("s", True, True), ("small", False, False)], "passes",
                 id="string-words"),
    pytest.param([("f", True, True)], "passes", id="f64-exact-on-cpu"),
    pytest.param([("wide", False, True), ("s", False, False)], "passes",
                 id="int64-desc-string-desc"),
]


@pytest.mark.parametrize("orders,how", ORDERS)
def test_sort_equals_lexsort(orders, how):
    table = _table()
    batch = _batch(table)
    live = batch.live_mask()
    names = table.column_names

    def fields():
        out = []
        for name, asc, nf in orders:
            out.extend(common.key_fields(batch.columns[names.index(name)],
                                         asc, nf, live))
        return out

    with common.noting_sorts() as notes:
        perm, _ = common.sort_permutation_fields(fields(), live,
                                                 batch.capacity)
    assert [n["how"] for n in notes] == [how]
    assert notes[0]["operands"] == 1
    assert (notes[0]["passes"] == 1) == (how == "packed")
    assert notes[0]["keyBits"] <= 32 * notes[0]["passes"]
    keys = []
    for name, asc, nf in reversed(orders):
        keys.extend(_numpy_key(table.column(name), asc, nf))
    want = np.lexsort(keys)  # stable: ties keep the rows' own order
    got = np.asarray(perm)[:table.num_rows]
    assert got.tolist() == want.tolist()
    # dead rows (the capacity's padding) come last, in their own order
    assert sorted(np.asarray(perm)[table.num_rows:].tolist()) == list(
        range(table.num_rows, batch.capacity))


def test_key_bits_follow_the_stamped_ranges():
    """40 bits packed into two words, 8 into one: a stamped range, a
    dictionary's size and a boolean's one bit, not the dtype's width."""
    batch = _batch(_table())
    live = batch.live_mask()
    names = _table().column_names
    cols = {n: batch.columns[names.index(n)] for n in names}
    bits = {n: [b for _, b in common.key_fields(cols[n], True, True, live)]
            for n in names}
    assert bits["few"] == [1, 3]          # vrange (0, 7)
    assert bits["small"] == [1, 3]        # vrange (-4, 3)
    assert bits["flag"] == [1, 1]
    assert bits["code"] == [1, 3]         # 7 values
    assert bits["wide"] == [1, 32, 32]
    assert bits["f"] == [1, 32, 32]       # exact f64 bits on the CPU
    words, total = common.pack_fields(
        [f for n in ("few", "small", "flag", "code")
         for f in common.key_fields(cols[n], True, True, live)], live)
    assert (len(words), total) == (1, 1 + 4 + 4 + 2 + 4)
    words, total = common.pack_fields(
        [f for n in ("few", "wide") for f in
         common.key_fields(cols[n], True, True, live)], live)
    assert (len(words), total) == (3, 1 + 4 + 65)


def test_f64_key_is_its_f32_rounding_and_the_rest_where_64_bits_do_not_bitcast(
        monkeypatch):
    """On a TPU a 64-bit bitcast does not compile: the key of a double
    is the f32's total order, then that of what the rounding left — two
    words whose order is the double's own (a TPU holds the double as
    that pair of f32), so sums 1e-9 apart still order (docs/
    compatibility.md)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    table = _table()
    close = np.array([1e5 * (1 + i * 1e-10) for i in range(40)])
    rng = np.random.default_rng(2)
    rng.shuffle(close)
    vals = np.asarray(table.column("f").combine_chunks().fill_null(0.0)
                      ).copy()
    vals[:40] = close
    # what a pair of f32 cannot hold is not a double a TPU ever sees
    finite = np.isfinite(vals)
    vals[finite & (np.abs(vals) > 1e38)] = np.sign(
        vals[finite & (np.abs(vals) > 1e38)]) * 1e30
    vals[(vals != 0) & (np.abs(vals) < 1e-37)] = 1e-30
    null = np.asarray(table.column("f").combine_chunks().is_null()).copy()
    null[:40] = False
    table = table.set_column(table.column_names.index("f"), "f",
                             pa.array(vals, mask=null))
    batch = _batch(table)
    live = batch.live_mask()
    f = batch.columns[table.column_names.index("f")]
    assert [b for _, b in common.key_fields(f, False, False, live)] == [
        1, 32, 32]
    perm, _ = common.sort_permutation_fields(
        common.key_fields(f, False, False, live), live, batch.capacity)
    want = np.lexsort(_numpy_key(table.column("f"), False, False))
    assert np.asarray(perm)[:table.num_rows].tolist() == want.tolist()


def test_legacy_multi_key_sort_goes_as_one_key_passes():
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.integers(0, 4, 512))
    b = jnp.asarray(rng.integers(-5, 5, 512))
    with common.noting_sorts() as notes:
        perm = common.sort_permutation([a, b], 512)
    assert notes == [{"how": "passes", "by": "sort", "operands": 1,
                      "keyBits": 128, "passes": 2, "slots": 512}]
    assert np.asarray(perm).tolist() == np.lexsort(
        [np.asarray(b), np.asarray(a)]).tolist()
    text = jax.jit(lambda x, y: common.sort_permutation([x, y], 512)
                   ).lower(a, b).as_text()
    assert "num_keys" not in text or "num_keys = 1" in text


def _plain_groups(table, keys, live):
    rows = [tuple(table.column(k)[i].as_py() for k in keys)
            for i in range(table.num_rows)]

    def norm(v):
        # Spark's NormalizeFloatingNumbers: -0.0 groups with 0.0 (XLA's
        # CPU backend compares a subnormal equal to 0.0 as well, which
        # `normalize_floating` has always inherited)
        if isinstance(v, float):
            return "nan" if v != v else (0.0 if abs(v) < 1e-300 else v)
        return v

    return [tuple(norm(v) for v in r) if keep else None
            for r, keep in zip(rows, live)]


@pytest.mark.parametrize("keys", [["few"], ["small", "code", "flag"],
                                  ["f", "few"], ["s", "wide"],
                                  ["code", "f", "wide"]],
                         ids=lambda k: "-".join(k))
@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
def test_group_by_equals_plain_grouping(keys, masked):
    table = _table(seed=SEED + 1, n=1500)
    batch = _batch(table)
    names = table.column_names
    rng = np.random.default_rng(5)
    keep = (rng.random(table.num_rows) < 0.6) if masked else \
        np.ones(table.num_rows, bool)
    live = np.zeros(batch.capacity, bool)
    live[:table.num_rows] = keep
    with common.noting_sorts() as notes:
        g = segmented.group_by(batch, [names.index(k) for k in keys],
                               jnp.asarray(live))
    assert len(notes) == 1 and notes[0]["by"] == "group"
    assert notes[0]["operands"] == 1
    want = _plain_groups(table, keys, keep)
    distinct = {w for w in want if w is not None}
    assert int(g.num_groups) == len(distinct)
    # rows of one group share a gid, rows of two groups do not; the
    # sorted batch's rows are the original ones moved by one
    # permutation, so the row's position in the table is recovered
    # through any column that identifies it: use the permutation itself
    perm, _ = common.sort_permutation_fields(
        [f for k in keys for f in common.key_fields(
            common.normalize_floating(batch.columns[names.index(k)]),
            True, True, jnp.asarray(live), codes_ok=True)],
        jnp.asarray(live), batch.capacity)
    perm = np.asarray(perm)
    gid = np.asarray(g.gid)
    live_s = np.asarray(g.live)
    seen = {}
    for pos in range(batch.capacity):
        if not live_s[pos]:
            continue
        key = want[perm[pos]]
        assert key is not None
        assert seen.setdefault(key, gid[pos]) == gid[pos]
    assert len(set(seen.values())) == len(seen) == len(distinct)
    first = np.asarray(g.first_pos)[:len(distinct)]
    assert sorted(want[perm[p]] is not None for p in first) == \
        [True] * len(distinct)


def test_dictionary_orders_by_its_values_not_its_codes():
    table = _table()
    batch = _batch(table)
    live = batch.live_mask()
    code = batch.columns[table.column_names.index("code")]
    assert code.encoding is not None
    perm, _ = common.sort_permutation_fields(
        common.key_fields(code, True, True, live), live, batch.capacity)
    got = [table.column("code")[int(i)].as_py()
           for i in np.asarray(perm)[:table.num_rows]]
    vals = [g for g in got if g is not None]
    assert got[:len(got) - len(vals)] == [None] * (len(got) - len(vals))
    assert vals == sorted(vals, key=str.encode)


def test_batch_sort_and_group_programs_hold_no_multi_key_sort():
    """The lowered text of a 3-key ORDER BY and a 3-key GROUP BY: every
    `sort` has one key operand and the permutation."""
    from spark_rapids_tpu.ops import sortops
    from spark_rapids_tpu.expr import BoundReference
    from spark_rapids_tpu.plan.logical import SortOrder

    table = _table()
    batch = _batch(table)
    names = table.column_names
    orders = [SortOrder(BoundReference(names.index(n), f.dataType, True),
                        asc)
              for n, asc in (("small", True), ("f", False), ("wide", True))
              for f in [batch.schema.fields[names.index(n)]]]
    text = jax.jit(lambda b: sortops.sort_batch(b, orders)
                   ).lower(batch).as_text()
    text += jax.jit(lambda b: segmented.group_by(
        b, [names.index(n) for n in ("small", "f", "wide")]).gid
    ).lower(batch).as_text()
    sorts = [ln for ln in text.splitlines() if "stablehlo.sort" in ln]
    assert sorts
    for ln in sorts:
        # (key, permutation) -> two operands, two results
        assert ln.count("%") <= 5, ln
    want = np.lexsort(
        _numpy_key(table.column("wide"), True, True)
        + _numpy_key(table.column("f"), False, False)
        + _numpy_key(table.column("small"), True, True))
    got = sortops.sort_batch(batch, orders)
    assert isinstance(got, ColumnBatch)
    col = np.asarray(got.columns[names.index("few")].data)[:table.num_rows]
    assert col.tolist() == np.asarray(
        table.column("few"))[want].tolist()
