"""MXU (one-hot matmul) segmented reductions vs the scatter path.

The binned group-by lowers its reductions to two-level one-hot matmuls
on TPU backends (ops/segmented.py `_mm_pass`); these tests force that
path on the CPU test backend and check it against the scatter
implementation and the pyarrow oracle: counts and every integer sum
(one vector under a tight vrange, 8-bit limbs otherwise) must be
bit-exact, float sums within f32-chunk accumulation tolerance.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import ColumnBatch, make_column
from spark_rapids_tpu.ops import segmented
from spark_rapids_tpu.sqltypes import StructField, StructType
from spark_rapids_tpu.sqltypes.datatypes import (
    byte,
    double,
    integer,
    long,
    short,
)


def _mk_batch(n, cap, nstores, seed=0, with_nulls=True):
    rng = np.random.default_rng(seed)
    store = rng.integers(0, nstores, n)
    qty = rng.integers(-50, 100, n)
    amt = rng.random(n) * 1e4 - 100.0
    sv = rng.random(n) > 0.1 if with_nulls else np.ones(n, bool)
    av = rng.random(n) > 0.1 if with_nulls else np.ones(n, bool)
    schema = StructType([
        StructField("store", long, True),
        StructField("qty", long, True),
        StructField("amt", double, True),
    ])
    cols = [
        make_column(long, store, sv, cap),
        make_column(long, qty, av, cap),
        make_column(double, amt, av, cap),
    ]
    cols[0].vrange = (0, nstores - 1)
    cols[1].vrange = (-50, 99)
    batch = ColumnBatch(schema, cols, n)
    return batch, store, qty, amt, sv, av


def _agg(mode="partial"):
    from spark_rapids_tpu.exec.operators import TpuHashAggregateExec
    from spark_rapids_tpu.expr import (
        Alias, Average, BoundReference, Count, Sum,
    )

    g = [Alias(BoundReference(0, long, True), "store")]
    aggs = [
        Alias(Sum(BoundReference(1, long, True)), "sq"),
        Alias(Sum(BoundReference(2, double, True)), "sa"),
        Alias(Count(BoundReference(2, double, True)), "ca"),
        Alias(Average(BoundReference(2, double, True)), "avg"),
    ]
    return TpuHashAggregateExec(mode, g, aggs, None, None)


def _collect(agg, part):
    from spark_rapids_tpu.exec.operators import TpuHashAggregateExec

    final = TpuHashAggregateExec("final", agg.grouping, agg.aggs,
                                 None, None)
    out = final._merge_final(part)
    n = int(jnp.asarray(out.num_rows))
    res = {}
    for i in range(n):
        key = (int(out.columns[0].data[i])
               if bool(out.columns[0].validity[i]) else None)
        res[key] = tuple(
            (float(c.data[i]) if bool(c.validity[i]) else None)
            for c in out.columns[1:])
    return res


@pytest.mark.parametrize("nstores", [7, 213, 2050])
def test_mm_matches_scatter(nstores):
    batch, store, qty, amt, sv, av = _mk_batch(5000, 8192, nstores)
    agg = _agg()
    base = _collect(agg, agg._partial(batch))
    before = segmented.mm_traced_sweeps
    with segmented.force_matmul_path():
        mm = _collect(agg, agg._partial(batch))
    # the matmul path must actually have engaged (not scatter-vs-scatter)
    assert segmented.mm_traced_sweeps > before
    assert set(base) == set(mm)
    for k in base:
        for i, (b, m) in enumerate(zip(base[k], mm[k])):
            if b is None or m is None:
                assert b == m, (k, i)
            elif i == 0:  # bounded int sum: exact
                assert b == m, (k, i, b, m)
            else:
                assert m == pytest.approx(b, rel=2e-5, abs=1e-3), (k, i)


def test_mm_exact_vs_numpy_oracle():
    n = 20000
    batch, store, qty, amt, sv, av = _mk_batch(n, 32768, 97, seed=3)
    agg = _agg()
    with segmented.force_matmul_path():
        got = _collect(agg, agg._partial(batch))
    for s in np.unique(store[sv]):
        m = (store == s) & sv
        want_sq = int(qty[m & av].sum()) if (m & av).any() else None
        want_ca = int((m & av).sum())
        row = got[int(s)]
        assert row[0] == want_sq
        assert row[2] == want_ca
        if want_ca:
            assert row[1] == pytest.approx(float(amt[m & av].sum()),
                                           rel=2e-5, abs=1e-3)


def test_mm_null_key_bin_and_empty_bins():
    n, cap = 1000, 1024
    rng = np.random.default_rng(5)
    store = rng.integers(0, 4, n)
    kv = rng.random(n) > 0.5  # half the keys null
    vals = rng.integers(0, 10, n)
    schema = StructType([StructField("k", long, True),
                         StructField("v", long, True)])
    cols = [make_column(long, store, kv, cap),
            make_column(long, vals, None, cap)]
    cols[0].vrange = (0, 40)  # loose bound: most bins empty
    cols[1].vrange = (0, 9)
    batch = ColumnBatch(schema, cols, n)
    from spark_rapids_tpu.expr import Alias, BoundReference, Count, Sum

    from spark_rapids_tpu.exec.operators import TpuHashAggregateExec

    g = [Alias(BoundReference(0, long, True), "k")]
    aggs = [Alias(Sum(BoundReference(1, long, True)), "sv"),
            Alias(Count(None), "c")]
    agg = TpuHashAggregateExec("partial", g, aggs, None, None)
    with segmented.force_matmul_path():
        got = _collect(agg, agg._partial(batch))
    assert None in got  # the null-key group exists
    assert got[None][0] == int(vals[~kv].sum())
    assert got[None][1] == int((~kv).sum())
    for s in range(4):
        m = (store == s) & kv
        assert got[int(s)][0] == int(vals[m].sum())
        assert got[int(s)][1] == int(m.sum())
    assert len(got) == 5  # empty bins compacted away


def test_mm_pass_kernel_direct():
    rng = np.random.default_rng(9)
    for b in (3, 64, 1000, 4096):
        n = 4096
        gid = jnp.asarray(rng.integers(0, b, n).astype(np.int32))
        w = jnp.asarray(rng.random(n).astype(np.float32))
        got = np.asarray(segmented._mm_pass(w, gid, b, 512, jnp.float64))
        want = np.zeros(b)
        np.add.at(want, np.asarray(gid), np.asarray(w, dtype=np.float64))
        assert np.allclose(got, want, rtol=1e-5, atol=1e-5)


def test_mm_nonfinite_confined_to_own_group():
    # an Inf/NaN row must not poison other groups' sums (the masked
    # outer product would turn inf*0 into NaN without the chunk guard)
    n, cap, b = 512, 1024, 8
    rng = np.random.default_rng(13)
    gid_np = rng.integers(0, b, n).astype(np.int32)
    vals_np = rng.random(n)
    vals_np[7] = np.inf
    gid_np[7] = 3
    vals_np[11] = np.nan
    gid_np[11] = 5
    gid = jnp.asarray(gid_np)
    vals = jnp.asarray(vals_np)
    valid = jnp.ones(n, bool)
    with segmented.force_matmul_path(), segmented.binned_bins(b), \
            segmented.unsorted_gids():
        got = np.asarray(segmented.seg_sum(vals, valid, gid, b))
    for s in range(b):
        m = gid_np == s
        want = vals_np[m].sum()
        if s == 3:
            assert np.isinf(got[s]) and got[s] > 0
        elif s == 5:
            assert np.isnan(got[s])
        else:
            assert np.isfinite(got[s])
            assert got[s] == pytest.approx(want, rel=2e-5)


def test_mm_unbounded_int64_rides_limbs():
    # no vrange + wide values: no single f32 vector is exact, so
    # seg_sum splits the value into 8-bit limbs and still never
    # scatters — exact on values > 2^24
    n, cap, b = 256, 1024, 16
    rng = np.random.default_rng(11)
    gid = jnp.asarray(rng.integers(0, b, n).astype(np.int32))
    vals = jnp.asarray(rng.integers(-2**40, 2**40, n))
    valid = jnp.ones(n, bool)
    before = segmented.mm_traced_sweeps
    with segmented.force_matmul_path(), segmented.binned_bins(b), \
            segmented.unsorted_gids():
        got = np.asarray(segmented.seg_sum(vals, valid, gid, b))
    assert segmented.mm_traced_sweeps > before
    want = np.zeros(b, dtype=np.int64)
    np.add.at(want, np.asarray(gid), np.asarray(vals))
    assert np.array_equal(got, want)


# --- an integer sum with no tight static bound: 8-bit limbs ---

_INT_TYPES = {"int8": byte, "int16": short, "int32": integer,
              "int64": long}
#: (source width, values): only a 64-bit column can wrap the int64 sum
_LIMB_CASES = [(w, p) for w in _INT_TYPES
               for p in ("non_negative", "mixed_sign", "min_and_max")
               ] + [("int64", "wraps_int64")]


def _int_values(width, pattern, n, rng):
    info = np.iinfo(width)
    if pattern == "non_negative":
        return rng.integers(0, info.max, n, dtype=width, endpoint=True)
    if pattern == "mixed_sign":
        return rng.integers(info.min, info.max, n, dtype=width,
                            endpoint=True)
    if pattern == "min_and_max":  # nothing but the type's two ends
        return np.where(rng.random(n) < 0.5, info.min, info.max
                        ).astype(width)
    # a few dozen values near the top in every group: their sum wraps
    return rng.integers(info.max - 1000, info.max, n, dtype=width)


def _scatter_count(lowered_text: str) -> int:
    return lowered_text.count('"stablehlo.scatter"(')


def _int_sum_partial(width, vals, valid, key, nkeys, cap, vrange=None):
    from spark_rapids_tpu.exec.operators import TpuHashAggregateExec
    from spark_rapids_tpu.expr import Alias, BoundReference, Sum

    t = _INT_TYPES[width]
    cols = [make_column(long, key, None, cap),
            make_column(t, vals, valid, cap)]
    assert cols[1].data.dtype == np.dtype(width)
    cols[0].vrange = (0, nkeys - 1)
    cols[1].vrange = vrange
    batch = ColumnBatch(StructType([StructField("k", long, True),
                                    StructField("v", t, True)]),
                        cols, len(key))
    agg = TpuHashAggregateExec(
        "partial", [Alias(BoundReference(0, long, True), "k")],
        [Alias(Sum(BoundReference(1, t, True)), "s")], None, None)
    return agg, batch


@pytest.mark.parametrize("nulls", ["no_nulls", "half_null"])
@pytest.mark.parametrize("width,pattern", _LIMB_CASES)
def test_limb_sums_equal_the_wrapping_int64_oracle(width, pattern, nulls):
    """The binned partial + final merge of an integer Sum whose column
    carries no vrange equals numpy's wrapping int64 sum bit for bit,
    rode the MXU, and its lowered text scatters nothing but the bins'
    own compaction (segmented.dense_bin_perm, bin space)."""
    import jax

    n, cap, nkeys = 5000, 8192, 5
    rng = np.random.default_rng([32, len(width), len(pattern)])
    key = rng.integers(0, nkeys, n)
    vals = _int_values(width, pattern, n, rng)
    valid = rng.random(n) < 0.5 if nulls == "half_null" else None
    agg, batch = _int_sum_partial(width, vals, valid, key, nkeys, cap)
    before = segmented.mm_traced_sweeps
    with segmented.force_matmul_path(), \
            segmented.noting_sum_lowerings() as noted:
        got = _collect_ints(agg, agg._partial(batch))
        lowered = jax.jit(agg._partial).lower(batch).as_text()
        bins_only = jax.jit(
            lambda occ: segmented.dense_bin_perm(occ, 1024)).lower(
            jnp.zeros(1024, bool)).as_text()
    assert segmented.mm_traced_sweeps > before
    assert noted == {"limbs": 2}  # the eager partial, then the lowering
    assert _scatter_count(lowered) == _scatter_count(bins_only) == 1
    live = np.ones(n, bool) if valid is None else valid
    want = {}
    with np.errstate(over="ignore"):
        for k in range(nkeys):
            m = (key == k) & live
            want[k] = int(vals[m].astype(np.int64).sum(dtype=np.int64))
    if pattern == "wraps_int64":  # the oracle itself wrapped
        exact = {k: sum(int(v) for v in vals[(key == k) & live])
                 for k in range(nkeys)}
        assert any(exact[k] != want[k] for k in want)
    assert got == want


def _collect_ints(agg, part):
    """{key: the first aggregate as a Python int} of the final merge:
    `_collect` reads through float and cannot tell sums above 2^53."""
    from spark_rapids_tpu.exec.operators import TpuHashAggregateExec

    out = TpuHashAggregateExec("final", agg.grouping, agg.aggs, None,
                               None)._merge_final(part)
    n = int(jnp.asarray(out.num_rows))
    keys, sums = (np.asarray(c.data)[:n] for c in out.columns[:2])
    assert np.asarray(out.columns[1].validity)[:n].all()
    return {int(k): int(v) for k, v in zip(keys, sums)}


def test_a_tight_vrange_keeps_its_single_vector():
    """A column stamped (0, 9) sums with ONE weight vector, as before:
    the record reads `bounded`, and the sweep holds three vectors (the
    sum, its null-tracking count, the bins' occupancy), where limbs
    would make it ten."""
    import jax

    n, cap, nkeys = 5000, 8192, 5
    rng = np.random.default_rng(33)
    key = rng.integers(0, nkeys, n)
    vals = rng.integers(0, 10, n).astype(np.int64)
    valid = rng.random(n) < 0.5
    stacked = {}
    for vrange in ((0, 9), None):
        agg, batch = _int_sum_partial("int64", vals, valid, key, nkeys,
                                      cap, vrange=vrange)
        with segmented.force_matmul_path(), \
                segmented.noting_sum_lowerings() as noted:
            got = _collect_ints(agg, agg._partial(batch))
            jaxpr = str(jax.make_jaxpr(agg._partial)(batch))
        assert got == {k: int(vals[(key == k) & valid].sum())
                       for k in range(nkeys)}
        (kind,) = noted
        # the stacked dot's result: [GH, vectors * GL]
        (dot,) = [ln for ln in jaxpr.splitlines() if "dot_general" in ln]
        stacked[kind] = dot
    gh, gl = segmented._mm_factors(nkeys + 1)  # the keys and the null bin
    assert set(stacked) == {"bounded", "limbs"}
    assert f"f32[{gh},{3 * gl}] = dot_general" in stacked["bounded"]
    assert f"f32[{gh},{10 * gl}] = dot_general" in stacked["limbs"]


def test_q12s_conditional_counts_ride_limbs_through_the_fused_engine(
        tmp_path):
    """TPC-H Q12's aggregate — two `sum(case when … then 1 else 0
    end)` grouped by a dictionary-coded string — through the fused
    engine: the eager engine's answer, and `last_execution["agg"]`
    says both sums rode limbs in every chain program and none
    scattered."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.session import TpuSparkSession

    n = 20_000
    rng = np.random.default_rng(12)
    modes = ["AIR", "MAIL", "RAIL", "SHIP", None]
    table = pa.table({
        "mode": pa.array([modes[i] for i in rng.integers(0, 5, n)]
                         ).dictionary_encode(),
        "prio": pa.array(rng.integers(0, 5, n),
                         mask=rng.random(n) < 0.1)})
    for i in range(2):  # two files: two chain programs' dispatches
        pq.write_table(table.slice(i * n // 2, n // 2),
                       str(tmp_path / f"part-{i}.parquet"),
                       use_dictionary=["mode"])

    def answer(conf):
        spark = TpuSparkSession({
            "spark.rapids.sql.format.parquet.reader.type": "PERFILE",
            **conf})
        try:
            high = F.col("prio") < 2
            out = (spark.read.parquet(str(tmp_path)).groupBy("mode")
                   .agg(F.sum(F.when(high, 1).otherwise(0)).alias("high"),
                        F.sum(F.when(~high, 1).otherwise(0)).alias("low"))
                   .collect_arrow())
            return (sorted(zip(*(out.column(c).to_pylist()
                                 for c in ("mode", "high", "low"))),
                           key=str), spark.last_execution)
        finally:
            spark.stop()

    eager, rec = answer({"spark.rapids.sql.fusedExec.enabled": False})
    assert rec["engine"] != "fused" and rec["agg"] is None
    before = segmented.mm_traced_sweeps
    with segmented.force_matmul_path():
        fused, rec = answer({})
        again, rec2 = answer({})  # every program a cache hit
    assert segmented.mm_traced_sweeps > before
    assert rec["engine"] == "fused" and not rec["fallbacks"]
    assert fused == eager == again and len(fused) == 5
    assert rec["agg"] == rec2["agg"] == {"limbs": 4}  # 2 sums x 2 parts
    # off the MXU (this CPU backend, unforced) the same plan scatters
    _, rec = answer({})
    assert rec["agg"] == {"scatter": 4}
