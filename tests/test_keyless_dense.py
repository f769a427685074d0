"""A keyless aggregate is a dense reduction (ops/segmented.py
`one_segment`), not a scatter of every row into slot 0.

Each case runs keyless `_partial` (two batches, each under a `live`
mask) -> `_merge_buffers` -> `_merge_final` twice — with the dense
lowering the operator picks for itself and with the scatter lowering
it had before — and holds both against a numpy / Python oracle:
integers, counts and decimals bit-equal, doubles within 1e-12
relative. The trace-time counter must move on the dense side only, so
a gate that silently regresses cannot pass scatter-against-scatter."""

import decimal
import math
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.arrow_bridge import (
    arrow_to_device,
    device_to_arrow,
)
from spark_rapids_tpu.columnar.batch import ColumnBatch, concat_batches
from spark_rapids_tpu.exec.operators import TpuHashAggregateExec
from spark_rapids_tpu.expr import Alias, BoundReference
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.ops import segmented
from spark_rapids_tpu.sqltypes import DecimalType
from spark_rapids_tpu.sqltypes.datatypes import (
    boolean,
    byte,
    double,
    integer,
    long,
    short,
)

N, CAP = 3000, 4096
D = decimal.Decimal


def _rng(case):
    return np.random.default_rng(sum(map(ord, case)))


def _ref(i, dtype):
    return BoundReference(i, dtype, True)


# Each case: (arrow types of the input columns, values(rng, n) -> one
# numpy array / list per column, aggregates -> [(function, oracle)]).
# An oracle takes the rows that are live and non-null in every column
# the function reads, as Python lists, and returns the expected value.

def _f64(rng, n):
    return [rng.random(n) * 2e4 - 1e4]


def _f64_special(rng, n):
    """NaN in the first column, both infinities in the second, +Inf
    alone in the third: 30 of each, so some survive every mask."""
    cols = [rng.random(n) * 200 - 100 for _ in range(3)]
    for x, specials in zip(cols, ([np.nan], [np.inf, -np.inf], [np.inf])):
        if n:
            x[rng.integers(0, n, 30)] = specials * (30 // len(specials))
    return cols


def _i64(rng, n):
    return [rng.integers(-2 ** 40, 2 ** 40, n)]


def _narrow(rng, n):
    return [rng.integers(-128, 128, n).astype(np.int8),
            rng.integers(-2 ** 15, 2 ** 15, n).astype(np.int16),
            rng.integers(-2 ** 31, 2 ** 31, n).astype(np.int32)]


def _xy(rng, n):
    x = rng.random(n) * 10
    return [x, 3 * x + rng.random(n)]


def _dec(rng, n):
    return [[D(int(v)).scaleb(-2)
             for v in rng.integers(-10 ** 11, 10 ** 11, n)],
            [D(int(v) * 10 ** 9).scaleb(-4)
             for v in rng.integers(-10 ** 17, 10 ** 17, n)]]


def _flags(rng, n):
    return [rng.random(n) > 0.2, rng.integers(0, 1000, n)]


def _moment(v, k):
    a = np.asarray(v, np.float64)
    return float(((a - a.mean()) ** k).sum())


def _avg_dec(v, scale):
    return (sum(v) / len(v)).quantize(D(1).scaleb(-scale),
                                      rounding=decimal.ROUND_HALF_UP)


DEC12, DEC30 = DecimalType(12, 2), DecimalType(30, 4)

CASES = {
    "f64_sum_count_avg_min_max": (
        [pa.float64()], _f64, lambda: [
            (A.Sum(_ref(0, double)), lambda v: math.fsum(v)),
            (A.Count(_ref(0, double)), None),
            (A.Average(_ref(0, double)),
             lambda v: math.fsum(v) / len(v)),
            (A.Min(_ref(0, double)), min),
            (A.Max(_ref(0, double)), max)]),
    "f64_nan_inf": (
        [pa.float64()] * 3, _f64_special, lambda: [
            (A.Sum(_ref(0, double)), lambda v: math.nan),
            (A.Min(_ref(0, double)), lambda v: math.nan),
            (A.Max(_ref(0, double)), lambda v: math.nan),
            (A.Sum(_ref(1, double)), lambda v: math.nan),  # inf - inf
            (A.Min(_ref(1, double)), lambda v: -math.inf),
            (A.Max(_ref(1, double)), lambda v: math.inf),
            (A.Sum(_ref(2, double)), lambda v: math.inf),
            (A.Min(_ref(2, double)), min),
            (A.Average(_ref(2, double)), lambda v: math.inf)]),
    "i64_sum_count_avg_min_max": (
        [pa.int64()], _i64, lambda: [
            (A.Sum(_ref(0, long)), sum),
            (A.Count(None), None),
            (A.Count(_ref(0, long)), None),
            (A.Average(_ref(0, long)), lambda v: sum(v) / len(v)),
            (A.Min(_ref(0, long)), min),
            (A.Max(_ref(0, long)), max)]),
    "narrow_ints": (
        [pa.int8(), pa.int16(), pa.int32()], _narrow, lambda: [
            (A.Sum(_ref(0, byte)), sum),
            (A.Sum(_ref(1, short)), sum),
            (A.Sum(_ref(2, integer)), sum),
            (A.Average(_ref(1, short)), lambda v: sum(v) / len(v)),
            (A.Min(_ref(0, byte)), min),
            (A.Max(_ref(2, integer)), max)]),
    "variance_family": (
        [pa.float64()], lambda r, n: [r.random(n) * 100], lambda: [
            (A.VariancePop(_ref(0, double)),
             lambda v: _moment(v, 2) / len(v)),
            (A.VarianceSamp(_ref(0, double)),
             lambda v: _moment(v, 2) / (len(v) - 1)
             if len(v) > 1 else None),
            (A.StddevPop(_ref(0, double)),
             lambda v: math.sqrt(_moment(v, 2) / len(v))),
            (A.StddevSamp(_ref(0, double)),
             lambda v: math.sqrt(_moment(v, 2) / (len(v) - 1))
             if len(v) > 1 else None)]),
    "covariance_family": (
        [pa.float64(), pa.float64()], _xy, lambda: [
            (A.CovarPop(_ref(0, double), _ref(1, double)),
             lambda x, y: float(np.cov(x, y, bias=True)[0, 1])),
            (A.CovarSamp(_ref(0, double), _ref(1, double)),
             lambda x, y: float(np.cov(x, y)[0, 1])
             if len(x) > 1 else None),
            (A.Corr(_ref(0, double), _ref(1, double)),
             lambda x, y: float(np.corrcoef(x, y)[0, 1]))]),
    "decimal128": (
        [pa.decimal128(12, 2), pa.decimal128(30, 4)], _dec, lambda: [
            (A.Sum(_ref(0, DEC12)), sum),       # wide buffer, narrow in
            (A.Sum(_ref(1, DEC30)), sum),
            (A.Average(_ref(0, DEC12)), lambda v: _avg_dec(v, 6)),
            (A.Min(_ref(1, DEC30)), min),       # the two-limb extremum
            (A.Max(_ref(1, DEC30)), max)]),
    "bool_and_first_last": (
        [pa.bool_(), pa.int64()], _flags, lambda: [
            (A.BoolAnd(_ref(0, boolean)), all),
            (A.BoolOr(_ref(0, boolean)), any),
            (A.First(_ref(1, long)), lambda v: v[0]),
            (A.Last(_ref(1, long)), lambda v: v[-1])]),
}

SHAPES = ["nulls_and_live", "all_null", "empty"]


def _inputs(case, shape):
    """Two batches' worth of (table, live mask, per-column validity)."""
    types, values, _ = CASES[case]
    rng = _rng(case)
    out = []
    for n in ((0, 0) if shape == "empty" else (N, N // 3)):
        cols = values(rng, n)
        if shape == "all_null":
            valid = [np.zeros(n, bool) for _ in cols]
        else:
            valid = [rng.random(n) > 0.15 for _ in cols]
        live = rng.random(n) > 0.3
        arrays = [pa.array(list(c) if isinstance(c, list) else c,
                           type=t, mask=~v)
                  for c, t, v in zip(cols, types, valid)]
        table = pa.table({f"c{i}": a for i, a in enumerate(arrays)})
        out.append((table, live, cols, valid))
    return out


def _run(case, inputs, dense):
    fns = [fn for fn, _ in CASES[case][2]()]
    aggs = [Alias(fn, f"a{i}") for i, fn in enumerate(fns)]
    part = TpuHashAggregateExec("partial", [], aggs, None, None)
    final = TpuHashAggregateExec("final", [], aggs, None, None)
    if not dense:  # the lowering this aggregate had: scatter into slot 0
        part._reductions = final._reductions = nullcontext
    partials = []
    for table, live, _, _ in inputs:
        mask = np.zeros(CAP, bool)
        mask[:len(live)] = live
        partials.append(part._partial(
            arrow_to_device(table, capacity=CAP), live=jnp.asarray(mask)))
    for p in partials:
        assert int(p.num_rows) == 1  # one group, even over no rows
    merged = final._merge_buffers(concat_batches(partials))
    out = final._merge_final(merged)
    assert int(out.num_rows) == 1
    return device_to_arrow(out).to_pylist()[0]


def _expected(case, inputs):
    want = {}
    for i, (fn, oracle) in enumerate(CASES[case][2]()):
        refs = [c.ordinal for c in fn.children]
        rows = []
        for _, live, cols, valid in inputs:
            keep = live.copy()
            for r in refs:
                keep &= valid[r]
            rows.append([[cols[r][j] for j in np.flatnonzero(keep)]
                         for r in refs])
        args = [sum((b[k] for b in rows), []) for k in range(len(refs))]
        args = [[x.item() if hasattr(x, "item") else x for x in a]
                for a in args]
        if isinstance(fn, A.Count):
            n = (len(args[0]) if refs
                 else sum(int(live.sum()) for _, live, _, _ in inputs))
            want[f"a{i}"] = n
        elif not args[0]:
            want[f"a{i}"] = None
        else:
            want[f"a{i}"] = oracle(*args)
    return want


def _same(got, want, rel):
    if want is None or got is None:
        return got is None and want is None
    if isinstance(want, float):
        if math.isnan(want) or math.isinf(want):
            return (math.isnan(got) if math.isnan(want) else got == want)
        return got == pytest.approx(want, rel=rel, abs=1e-300)
    return got == want  # ints, counts, bools, decimals: bit-equal


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_keyless_dense_equals_scatter_and_oracle(case, shape):
    inputs = _inputs(case, shape)
    before = segmented.dense_traced_reductions
    dense = _run(case, inputs, dense=True)
    engaged = segmented.dense_traced_reductions
    assert engaged > before, "the one-segment path did not engage"
    scatter = _run(case, inputs, dense=False)
    assert segmented.dense_traced_reductions == engaged
    want = _expected(case, inputs)
    assert set(dense) == set(scatter) == set(want)
    moments = case in ("variance_family", "covariance_family")
    for name in want:
        assert _same(dense[name], scatter[name], 1e-12), (
            name, dense[name], scatter[name])
        # the power-sum formulas cancel: their oracle is centred
        assert _same(dense[name], want[name], 1e-9 if moments else 1e-12), (
            name, dense[name], want[name])


# --- what the lowering holds ---

def _batch(n=N, nstores=7):
    from test_mm_segments import _mk_batch

    return _mk_batch(n, CAP, nstores)[0]


def _keyless():
    aggs = [Alias(A.Sum(_ref(2, double)), "s"),
            Alias(A.Sum(_ref(1, long)), "q"),
            Alias(A.Count(None), "n"),
            Alias(A.Min(_ref(2, double)), "lo"),
            Alias(A.Max(_ref(1, long)), "hi")]
    return TpuHashAggregateExec("partial", [], aggs, None, None)


def _lowered(agg, fn, batch):
    return jax.jit(getattr(agg, fn)).lower(batch).as_text()


@pytest.mark.parametrize("phase", ["_partial", "_merge_buffers",
                                   "_merge_final"])
def test_keyless_phases_lower_without_scatter(phase):
    agg = _keyless()
    batch = _batch() if phase == "_partial" else agg._partial(_batch())
    if phase == "_merge_final":
        agg = TpuHashAggregateExec("final", [], agg.aggs, None, None)
    assert "scatter" not in _lowered(agg, phase, batch)
    agg._reductions = nullcontext  # the parent's lowering, for contrast
    assert "scatter" in _lowered(agg, phase, batch)


def _sha(text):
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()[:16]


def test_keyed_aggregates_lower_as_before():
    """A grouped aggregate never enters the one-segment mode: its
    lowered text is the same with the mode's hook taken away and the
    same as at the commit before the mode existed (the digests below
    were taken there; a PR that means to change the keyed lowering
    takes them again), the binned one still counts a matmul sweep, the
    sorted one still scatters, and neither counts a dense reduction."""
    from test_mm_segments import _agg

    batch = _batch()
    dense0 = segmented.dense_traced_reductions
    sweeps0 = segmented.mm_traced_sweeps
    with segmented.force_matmul_path():
        binned = _lowered(_agg(), "_partial", batch)
        assert segmented.mm_traced_sweeps > sweeps0
        unhooked = _agg()
        unhooked._reductions = nullcontext
        assert _lowered(unhooked, "_partial", batch) == binned
    assert "dot_general" in binned
    assert _sha(binned) == "73f6bcb91447a974"
    plain = ColumnBatch(batch.schema, [c.replace(vrange=None)
                                       for c in batch.columns],
                        batch.num_rows)
    sorted_text = _lowered(_agg(), "_partial", plain)
    assert "scatter" in sorted_text and "dot_general" not in sorted_text
    assert _sha(sorted_text) == "e3e3b9ab85876a11"  # PR 33: packed sort
    assert segmented.dense_traced_reductions == dense0


def test_primitives_outside_the_mode_keep_their_lowering():
    """Window operators, partitioning and collectives call the same
    primitives outside `one_segment()`: a scatter by gid, as before."""
    gid = jnp.asarray(np.arange(64) % 4, jnp.int32)
    x = jnp.arange(64, dtype=jnp.float64)
    valid = jnp.ones(64, bool)
    dense0 = segmented.dense_traced_reductions
    got = segmented.seg_sum(x, valid, jnp.sort(gid), 64)
    assert float(got[1]) == float(np.arange(16, 32).sum())
    with segmented.one_segment():
        one = segmented.seg_sum(x, valid, gid, 64)
        assert segmented.mm_bins_active() is None
    assert float(one[0]) == float(np.arange(64).sum())
    assert not np.asarray(one[1:]).any()
    assert segmented.dense_traced_reductions == dense0 + 1


def test_explain_names_the_lowering():
    assert _keyless()._node_string() == \
        "TpuHashAggregateExec [reduce=dense]"
    from test_mm_segments import _agg

    assert _agg()._node_string() == "TpuHashAggregateExec"
