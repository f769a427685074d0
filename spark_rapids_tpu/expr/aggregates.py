"""Declarative aggregate functions with partial/merge/final phases.

Mirrors the reference's aggregate architecture
(`org/apache/spark/sql/rapids/aggregate/aggregateFunctions.scala` +
`GpuAggregateExec.scala:175-400`): each function declares
- update: raw input values -> per-group partial buffers (segmented
  reductions over the sorted/grouped batch),
- merge: partial buffers from many batches/partitions -> combined
  buffers (used after shuffle),
- evaluate: buffers -> final value.

Buffers are plain DeviceColumns, so partial-aggregate results travel
through shuffle like any other batch.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar.batch import DeviceColumn
from spark_rapids_tpu.expr.core import Expression
from spark_rapids_tpu.ops import segmented
from spark_rapids_tpu.sqltypes import (
    DataType,
    DecimalType,
    DoubleType,
    FloatType,
)
from spark_rapids_tpu.sqltypes.datatypes import boolean, double, long


class AggregateFunction(Expression):
    """Base; children are the input expressions (if any).

    `jittable=False` marks functions whose update/merge need dynamic
    output shapes (collect_list and friends); the aggregate exec runs
    those phases eagerly instead of under jax.jit.
    """

    name: str = "agg"
    jittable: bool = True
    #: False for functions whose update/merge require CONTIGUOUS sorted
    #: segments (the collect family's rank computation); the aggregate
    #: exec then keeps the sorted grouping even when keys are binnable.
    binned_safe: bool = True

    @property
    def input(self):
        return self.children[0] if self.children else None

    def buffer_types(self) -> List[DataType]:
        raise NotImplementedError

    def update(self, values: DeviceColumn, live, gid, cap
               ) -> List[DeviceColumn]:
        """Segmented partial aggregation over grouped input rows."""
        raise NotImplementedError

    def merge(self, buffers: List[DeviceColumn], live, gid, cap
              ) -> List[DeviceColumn]:
        """Combine partial buffers grouped by key."""
        raise NotImplementedError

    def evaluate(self, buffers: List[DeviceColumn]) -> DeviceColumn:
        raise NotImplementedError


def _sum_result_type(t: DataType) -> DataType:
    if isinstance(t, (FloatType, DoubleType)):
        return double
    if isinstance(t, DecimalType):
        # Spark: sum(decimal(p,s)) -> decimal(p+10, s); beyond 18 digits
        # the buffer/result is DECIMAL128 (limb pairs, ops/decimal128.py)
        p = min(DecimalType.MAX_PRECISION, t.precision + 10)
        return DecimalType(p, t.scale)
    return long


class Sum(AggregateFunction):
    name = "sum"

    def __init__(self, child: Expression):
        super().__init__([child])

    @property
    def dtype(self):
        return _sum_result_type(self.children[0].dtype)

    def buffer_types(self):
        return [self.dtype, long]  # (sum, count_nonnull)

    def update(self, values, live, gid, cap):
        from spark_rapids_tpu.ops import decimal128 as d128

        out_t = self.dtype
        valid = values.validity & live
        if d128.is_wide(out_t):
            cnt = segmented.seg_count(valid, gid, cap)
            hi, lo = d128.widen_column(values)
            sh, sl = d128.seg_sum128(hi, lo, valid, gid, cap)
            return [DeviceColumn(out_t, d128.join(sh, sl), cnt > 0),
                    DeviceColumn(long, cnt, jnp.ones(cnt.shape, bool))]
        s, cnt = segmented.seg_sum_count(
            values.data, valid, gid, cap, vbound=values.vrange,
            out_dtype=out_t.np_dtype)
        return [DeviceColumn(out_t, s, cnt > 0),
                DeviceColumn(long, cnt, jnp.ones(cnt.shape, bool))]

    def merge(self, buffers, live, gid, cap):
        from spark_rapids_tpu.ops import decimal128 as d128

        cnt = segmented.seg_sum(buffers[1].data, live, gid, cap)
        ones = jnp.ones(cnt.shape, bool)
        buf = buffers[0]
        if buf.data.ndim == 2:
            hi, lo = d128.split(buf.data)
            sh, sl = d128.seg_sum128(hi, lo, buf.validity & live, gid,
                                     cap)
            return [DeviceColumn(buf.dtype, d128.join(sh, sl), cnt > 0),
                    DeviceColumn(long, cnt, ones)]
        s = segmented.seg_sum(buf.data, buf.validity & live, gid, cap)
        return [DeviceColumn(buf.dtype, s, cnt > 0),
                DeviceColumn(long, cnt, ones)]

    def evaluate(self, buffers):
        return buffers[0]


class Count(AggregateFunction):
    """count(expr) skips nulls; count(*) counts rows (child=None)."""

    name = "count"

    def __init__(self, child: Expression = None):
        super().__init__([child] if child is not None else [])

    @property
    def dtype(self):
        return long

    @property
    def nullable(self):
        return False

    def buffer_types(self):
        return [long]

    def update(self, values, live, gid, cap):
        if values is None:
            valid = live
        else:
            valid = values.validity & live
        cnt = segmented.seg_count(valid, gid, cap)
        return [DeviceColumn(long, cnt, jnp.ones(cnt.shape, bool))]

    def merge(self, buffers, live, gid, cap):
        cnt = segmented.seg_sum(buffers[0].data, live, gid, cap)
        return [DeviceColumn(long, cnt, jnp.ones(cnt.shape, bool))]

    def evaluate(self, buffers):
        return buffers[0]


class _MinMax(AggregateFunction):
    def __init__(self, child: Expression):
        super().__init__([child])

    @property
    def dtype(self):
        return self.children[0].dtype

    def buffer_types(self):
        return [self.dtype]

    def _seg(self, data, valid, gid, cap):
        raise NotImplementedError

    def _seg_any(self, data, valid, gid, cap):
        if data.ndim != 2:
            return self._seg(data, valid, gid, cap)
        # DECIMAL128: two-pass segmented extremum over (hi, lo') limbs
        from spark_rapids_tpu.ops import decimal128 as d128

        hi, lo = d128.split(data)
        lo_o = lo ^ jnp.int64(d128._SIGN64)  # unsigned-orderable
        h = self._seg(hi, valid, gid, cap)
        tie = valid & (hi == jnp.take(h, gid))
        l_o = self._seg(lo_o, tie, gid, cap)
        return d128.join(h, l_o ^ jnp.int64(d128._SIGN64))

    def update(self, values, live, gid, cap):
        valid = values.validity & live
        r = self._seg_any(values.data, valid, gid, cap)
        cnt = segmented.seg_count(valid, gid, cap)
        return [DeviceColumn(self.dtype, r, cnt > 0)]

    def merge(self, buffers, live, gid, cap):
        valid = buffers[0].validity & live
        r = self._seg_any(buffers[0].data, valid, gid, cap)
        cnt = segmented.seg_count(valid, gid, cap)
        return [DeviceColumn(buffers[0].dtype, r, cnt > 0)]

    def evaluate(self, buffers):
        return buffers[0]


class Min(_MinMax):
    name = "min"

    def _seg(self, data, valid, gid, cap):
        return segmented.seg_min(data, valid, gid, cap)


class Max(_MinMax):
    name = "max"

    def _seg(self, data, valid, gid, cap):
        return segmented.seg_max(data, valid, gid, cap)


class Average(AggregateFunction):
    name = "avg"

    def __init__(self, child: Expression):
        super().__init__([child])

    @property
    def dtype(self):
        # Spark: avg(decimal) -> decimal(p+4, s+4); others -> double.
        t = self.children[0].dtype
        if isinstance(t, DecimalType):
            m = DecimalType.MAX_PRECISION
            return DecimalType(min(m, t.precision + 4),
                               min(m, t.scale + 4))
        return double

    def buffer_types(self):
        return [_sum_result_type(self.children[0].dtype), long]

    def update(self, values, live, gid, cap):
        return Sum(self.children[0]).update(values, live, gid, cap)

    def merge(self, buffers, live, gid, cap):
        return Sum(self.children[0]).merge(buffers, live, gid, cap)

    def evaluate(self, buffers):
        from spark_rapids_tpu.ops import decimal128 as d128

        s, cnt = buffers
        out_t = self.dtype
        safe = jnp.maximum(cnt.data, 1)
        if isinstance(out_t, DecimalType) and s.data.ndim == 2:
            in_t = self.children[0].dtype
            up = out_t.scale - in_t.scale
            hi, lo = d128.rescale(*d128.split(s.data), up)
            qh, ql = d128.div128_round_half_up(hi, lo, safe)
            valid = (cnt.data > 0) & d128.fits_precision(
                qh, ql, out_t.precision)
            if d128.is_wide(out_t):
                return DeviceColumn(out_t, d128.join(qh, ql), valid)
            return DeviceColumn(out_t, ql,
                                valid & d128.fits_i64(qh, ql))
        if isinstance(out_t, DecimalType):
            in_t = self.children[0].dtype
            up = out_t.scale - in_t.scale
            num = s.data.astype(jnp.int64) * (10 ** up)
            q = jnp.abs(num) // safe
            rem = jnp.abs(num) - q * safe
            q = q + (2 * rem >= safe).astype(jnp.int64)
            data = jnp.sign(num) * q
        else:
            data = s.data.astype(jnp.float64) / safe.astype(jnp.float64)
        return DeviceColumn(out_t, data, cnt.data > 0)


class First(AggregateFunction):
    name = "first"

    def __init__(self, child: Expression, ignore_nulls: bool = True):
        super().__init__([child])
        self.ignore_nulls = ignore_nulls

    @property
    def dtype(self):
        return self.children[0].dtype

    def key(self):
        return ("first", self.ignore_nulls, self.children[0].key())

    def buffer_types(self):
        return [self.dtype]

    _take_last = False  # Last flips to a segment_max over positions

    def _first(self, values: DeviceColumn, valid, gid, cap):
        n = values.data.shape[0]
        pos = jnp.arange(n, dtype=jnp.int32)
        ones = jnp.ones((n,), bool)
        if self._take_last:
            fp = segmented.seg_max(jnp.where(valid, pos, -1), ones,
                                   gid, cap)
            found = fp >= 0
        else:
            fp = segmented.seg_min(jnp.where(valid, pos, n), ones,
                                   gid, cap)
            found = fp < n
        safe = jnp.clip(fp, 0, n - 1)
        data = jnp.take(values.data, safe, axis=0)
        lengths = None if values.lengths is None else jnp.take(
            values.lengths, safe)
        return DeviceColumn(values.dtype, data,
                            found & jnp.take(values.validity, safe), lengths)

    def update(self, values, live, gid, cap):
        valid = live & (values.validity if self.ignore_nulls
                        else jnp.ones_like(live))
        return [self._first(values, valid, gid, cap)]

    def merge(self, buffers, live, gid, cap):
        valid = live & (buffers[0].validity if self.ignore_nulls
                        else jnp.ones_like(live))
        return [self._first(buffers[0], valid, gid, cap)]

    def evaluate(self, buffers):
        return buffers[0]


class Last(First):
    """last(col): final (by sorted position) value per group — First
    with segment_max over positions."""

    name = "last"
    _take_last = True

    def key(self):
        return ("last", self.ignore_nulls, self.children[0].key())


class AnyValue(First):
    """any_value(col): any value from the group (reference registers it
    as a First-family aggregate)."""

    name = "any_value"

    def key(self):
        return ("any_value", self.ignore_nulls, self.children[0].key())


class GroupingID(Expression):
    """Marker for F.grouping_id(); rewritten by rollup/cube/grouping-
    sets agg() into a reference to the synthesized grouping-id column.
    Invalid outside those contexts (as in Spark)."""

    @property
    def dtype(self):
        return long

    @property
    def nullable(self):
        return False


class GroupingBit(Expression):
    """Marker for F.grouping(col): 1 when the column is aggregated
    (masked) in the grouping set, else 0."""

    def __init__(self, child: Expression):
        super().__init__([child])

    @property
    def dtype(self):
        return long

    @property
    def nullable(self):
        return False


# --------------------------------------------------------- moment family
#
# Variance/stddev/skewness/kurtosis over raw power sums (n, Σx, Σx²,…)
# — the declarative-buffer design of the reference's M2-based aggregates
# (aggregateFunctions.scala GpuStddevPop/GpuVarianceSamp etc.) with
# power sums instead of streaming M2 so partial/merge are plain
# segmented additions (one XLA segment_sum per buffer).


class _Moments(AggregateFunction):
    """Buffers: [n (long), Σx, Σx², … Σx^k (double)]."""

    n_powers = 2

    def __init__(self, child: Expression):
        super().__init__([child])

    @property
    def dtype(self):
        return double

    def buffer_types(self):
        return [long] + [double] * self.n_powers

    def update(self, values, live, gid, cap):
        valid = values.validity & live
        x = values.data.astype(jnp.float64)
        powers = [x]
        for _ in range(self.n_powers - 1):
            powers.append(powers[-1] * x)
        cnt, sums = segmented.seg_multi_sum(powers, valid, gid, cap)
        ones = jnp.ones(cnt.shape, bool)
        return ([DeviceColumn(long, cnt, ones)]
                + [DeviceColumn(double, s, cnt > 0) for s in sums])

    def merge(self, buffers, live, gid, cap):
        cnt = segmented.seg_sum(buffers[0].data, live, gid, cap)
        ones = jnp.ones(cnt.shape, bool)
        out = [DeviceColumn(long, cnt, ones)]
        for b in buffers[1:]:
            s = segmented.seg_sum(b.data, b.validity & live, gid, cap)
            out.append(DeviceColumn(double, s, cnt > 0))
        return out

    @staticmethod
    def _m2(n, s1, s2):
        """Central second moment Σ(x-μ)² = Σx² - (Σx)²/n."""
        safe = jnp.maximum(n, 1.0)
        return s2 - s1 * s1 / safe

    def evaluate(self, buffers):
        raise NotImplementedError


class VariancePop(_Moments):
    name = "var_pop"

    def evaluate(self, buffers):
        n = buffers[0].data.astype(jnp.float64)
        m2 = self._m2(n, buffers[1].data, buffers[2].data)
        data = jnp.maximum(m2, 0.0) / jnp.maximum(n, 1.0)
        return DeviceColumn(double, data, n >= 1)


class VarianceSamp(_Moments):
    """var_samp: NULL for n<2 (Spark 3.x default,
    spark.sql.legacy.statisticalAggregate=false)."""

    name = "var_samp"

    def evaluate(self, buffers):
        n = buffers[0].data.astype(jnp.float64)
        m2 = self._m2(n, buffers[1].data, buffers[2].data)
        data = jnp.maximum(m2, 0.0) / jnp.maximum(n - 1.0, 1.0)
        return DeviceColumn(double, data, n >= 2)


class StddevPop(VariancePop):
    name = "stddev_pop"

    def evaluate(self, buffers):
        v = super().evaluate(buffers)
        return DeviceColumn(double, jnp.sqrt(v.data), v.validity)


class StddevSamp(VarianceSamp):
    name = "stddev_samp"

    def evaluate(self, buffers):
        v = super().evaluate(buffers)
        return DeviceColumn(double, jnp.sqrt(v.data), v.validity)


class Skewness(_Moments):
    """skewness = sqrt(n)·m3 / m2^1.5 (NULL when n=0 or m2=0)."""

    name = "skewness"
    n_powers = 3

    def evaluate(self, buffers):
        n = buffers[0].data.astype(jnp.float64)
        s1, s2, s3 = (b.data for b in buffers[1:])
        safe = jnp.maximum(n, 1.0)
        mu = s1 / safe
        m2 = jnp.maximum(s2 - s1 * mu, 0.0)
        m3 = s3 - 3.0 * mu * s2 + 2.0 * mu * mu * s1
        den = jnp.maximum(m2, 1e-300) ** 1.5
        data = jnp.sqrt(safe) * m3 / den
        return DeviceColumn(double, data, (n >= 1) & (m2 > 0))


class Kurtosis(_Moments):
    """kurtosis (excess) = n·m4/m2² - 3 (NULL when n=0 or m2=0)."""

    name = "kurtosis"
    n_powers = 4

    def evaluate(self, buffers):
        n = buffers[0].data.astype(jnp.float64)
        s1, s2, s3, s4 = (b.data for b in buffers[1:])
        safe = jnp.maximum(n, 1.0)
        mu = s1 / safe
        m2 = jnp.maximum(s2 - s1 * mu, 0.0)
        m4 = (s4 - 4.0 * mu * s3 + 6.0 * mu * mu * s2
              - 3.0 * mu ** 3 * s1)
        den = jnp.maximum(m2 * m2, 1e-300)
        data = safe * m4 / den - 3.0
        return DeviceColumn(double, data, (n >= 1) & (m2 > 0))


# ------------------------------------------------------ bivariate family


class _Bivariate(AggregateFunction):
    """Two-input aggregates (corr / covar_*). A row participates only
    when BOTH inputs are non-null (Spark semantics). Buffers:
    [n, Σx, Σy, Σxy] (+ Σx², Σy² for corr)."""

    extra_squares = False

    def __init__(self, x: Expression, y: Expression):
        super().__init__([x, y])

    @property
    def dtype(self):
        return double

    def buffer_types(self):
        return [long] + [double] * (5 if self.extra_squares else 3)

    def update(self, values, live, gid, cap):
        xc, yc = values
        valid = xc.validity & yc.validity & live
        x = xc.data.astype(jnp.float64)
        y = yc.data.astype(jnp.float64)
        vecs = [x, y, x * y]
        if self.extra_squares:
            vecs += [x * x, y * y]
        cnt, sums = segmented.seg_multi_sum(vecs, valid, gid, cap)
        ones = jnp.ones(cnt.shape, bool)
        return ([DeviceColumn(long, cnt, ones)]
                + [DeviceColumn(double, s, cnt > 0) for s in sums])

    def merge(self, buffers, live, gid, cap):
        cnt = segmented.seg_sum(buffers[0].data, live, gid, cap)
        ones = jnp.ones(cnt.shape, bool)
        out = [DeviceColumn(long, cnt, ones)]
        for b in buffers[1:]:
            out.append(DeviceColumn(
                double, segmented.seg_sum(b.data, b.validity & live, gid,
                                          cap), cnt > 0))
        return out


class CovarPop(_Bivariate):
    name = "covar_pop"

    def evaluate(self, buffers):
        n = buffers[0].data.astype(jnp.float64)
        sx, sy, sxy = (b.data for b in buffers[1:4])
        safe = jnp.maximum(n, 1.0)
        data = (sxy - sx * sy / safe) / safe
        return DeviceColumn(double, data, n >= 1)


class CovarSamp(_Bivariate):
    name = "covar_samp"

    def evaluate(self, buffers):
        n = buffers[0].data.astype(jnp.float64)
        sx, sy, sxy = (b.data for b in buffers[1:4])
        safe = jnp.maximum(n, 1.0)
        data = (sxy - sx * sy / safe) / jnp.maximum(n - 1.0, 1.0)
        return DeviceColumn(double, data, n >= 2)


class Corr(_Bivariate):
    """Pearson correlation; NULL when n=0 or either variance is 0."""

    name = "corr"
    extra_squares = True

    def evaluate(self, buffers):
        n = buffers[0].data.astype(jnp.float64)
        sx, sy, sxy, sxx, syy = (b.data for b in buffers[1:6])
        safe = jnp.maximum(n, 1.0)
        cov = sxy - sx * sy / safe
        vx = jnp.maximum(sxx - sx * sx / safe, 0.0)
        vy = jnp.maximum(syy - sy * sy / safe, 0.0)
        den = jnp.sqrt(vx) * jnp.sqrt(vy)
        data = cov / jnp.maximum(den, 1e-300)
        return DeviceColumn(double, jnp.clip(data, -1.0, 1.0),
                            (n >= 1) & (den > 0))


# ----------------------------------------------------------- bool family


class _BoolReduce(AggregateFunction):
    _use_max = False  # bool_or reduces with max, bool_and with min

    def __init__(self, child: Expression):
        super().__init__([child])

    @property
    def dtype(self):
        return boolean

    def buffer_types(self):
        return [boolean]

    def _seg(self, data, valid, gid, cap):
        x = data.astype(jnp.int32)
        if self._use_max:
            r = segmented.seg_max(x, valid, gid, cap)
        else:
            r = segmented.seg_min(x, valid, gid, cap)
        return r > 0

    def update(self, values, live, gid, cap):
        valid = values.validity & live
        r = self._seg(values.data, valid, gid, cap)
        cnt = segmented.seg_count(valid, gid, cap)
        return [DeviceColumn(boolean, r, cnt > 0)]

    def merge(self, buffers, live, gid, cap):
        valid = buffers[0].validity & live
        r = self._seg(buffers[0].data, valid, gid, cap)
        cnt = segmented.seg_count(valid, gid, cap)
        return [DeviceColumn(boolean, r, cnt > 0)]

    def evaluate(self, buffers):
        return buffers[0]


class BoolAnd(_BoolReduce):
    name = "bool_and"


class BoolOr(_BoolReduce):
    name = "bool_or"
    _use_max = True


# ------------------------------------------------- collect / exact sets
#
# collect_list/collect_set produce ArrayType results; their buffers are
# array columns ([cap, max_elems] padded matrices). max_elems is data-
# dependent (the largest group), so update/merge run EAGERLY
# (jittable=False) — jax eager mode allows the dynamic output width
# while keeping the compute on device. Reference: cuDF collect_list /
# collect_set GroupByAggregations (GpuAggregateExec + cuDF ragged
# lists); here the ragged result is the padded-matrix array layout of
# columnar/batch.py.


def _eq_nan_aware(a, b):
    """Element equality where NaN == NaN (Spark set semantics: collect_set
    and count(DISTINCT) treat NaN as equal to itself)."""
    eq = a == b
    if jnp.issubdtype(a.dtype, jnp.floating):
        eq = eq | (jnp.isnan(a) & jnp.isnan(b))
    return eq


def _seg_exclusive_ranks(valid, gid, cap):
    """Rank of each valid row within its (contiguous, sorted) segment."""
    csum = jnp.cumsum(valid.astype(jnp.int32)) - valid.astype(jnp.int32)
    n = valid.shape[0]
    # contiguous gid: first position of segment g by binary search
    fp = jnp.searchsorted(gid, jnp.arange(cap, dtype=gid.dtype),
                          side="left")
    base = jnp.take(csum, jnp.clip(fp, 0, n - 1))
    return csum - jnp.take(base, gid)


class CollectList(AggregateFunction):
    name = "collect_list"
    jittable = False
    binned_safe = False  # _seg_exclusive_ranks needs sorted gids

    #: Traced-mode (mesh SPMD) sizing: when set, the element matrix is
    #: this static width instead of the eager largest-group host sync;
    #: groups wider than the width set `_overflow` (a traced bool the
    #: mesh executor folds into its expansion-retry flag, the same
    #: static-capacity + recompile-bigger discipline as the
    #: collectives). None = eager data-dependent sizing.
    _static_width = None
    _overflow = None

    def begin_static(self, width: int) -> None:
        self._static_width = int(width)
        self._overflow = jnp.zeros((), bool)

    def end_static(self):
        ovf = self._overflow
        self._static_width = None
        self._overflow = None
        return ovf

    def key(self):
        return (self.name, self._static_width,
                self.children[0].key())

    def __init__(self, child: Expression):
        super().__init__([child])

    @property
    def dtype(self):
        from spark_rapids_tpu.sqltypes import ArrayType

        return ArrayType(self.children[0].dtype, containsNull=False)

    @property
    def nullable(self):
        return False  # empty array, never null (Spark collect_list)

    def buffer_types(self):
        return [self.dtype]

    def _scatter(self, elem_dt, vals, valid, gid, cap):
        """Rows -> [cap, me] padded array column (me = largest group,
        or the static traced-mode width)."""
        cnt = segmented.seg_count(valid, gid, cap)
        if self._static_width is not None:
            me = self._static_width
            self._overflow = self._overflow | jnp.any(cnt > me)
            cnt = jnp.minimum(cnt, me)  # ranks >= me scatter out of
            #                             bounds and drop (mode="drop")
        else:
            me = max(int(jnp.max(cnt)), 1)
        rank = _seg_exclusive_ranks(valid, gid, cap)
        # invalid rows scatter out of range and are dropped
        col = jnp.where(valid, rank, me)
        out = jnp.zeros((cap, me), vals.dtype)
        out = out.at[gid, col].set(vals, mode="drop")
        ev = (jnp.arange(me, dtype=jnp.int32)[None, :] < cnt[:, None])
        from spark_rapids_tpu.sqltypes import ArrayType

        # collect_* is never NULL (empty array for all-null groups);
        # rows past num_groups are sliced away by the batch row count.
        return DeviceColumn(ArrayType(elem_dt, False), out,
                            jnp.ones(cap, bool), cnt.astype(jnp.int32), ev)

    def update(self, values, live, gid, cap):
        valid = values.validity & live
        return [self._scatter(values.dtype, values.data, valid, gid, cap)]

    def _merge_elements(self, buf, live, gid, cap, dedup: bool):
        """Flatten each group's row-lists into per-element rows, then
        re-scatter per group (optionally deduplicating)."""
        me_in = buf.data.shape[1] if buf.data.ndim == 2 else 1
        n = buf.data.shape[0]
        vals = buf.data.reshape(n * me_in)
        egid = jnp.repeat(gid, me_in)
        within = jnp.arange(me_in, dtype=jnp.int32)[None, :]
        evalid = ((within < buf.lengths[:, None])
                  & live[:, None]).reshape(n * me_in)
        if buf.elem_validity is not None:
            evalid = evalid & buf.elem_validity.reshape(n * me_in)
        if dedup:
            # sort invalid (padding) elements to each segment's end so
            # equal valid values are adjacent for the dup test
            order = jnp.lexsort((vals, ~evalid, egid))
            vals = jnp.take(vals, order)
            egid = jnp.take(egid, order)
            evalid = jnp.take(evalid, order)
            prev_same = jnp.concatenate([
                jnp.array([False]),
                (egid[1:] == egid[:-1])
                & _eq_nan_aware(vals[1:], vals[:-1]) & evalid[:-1]])
            evalid = evalid & ~prev_same
        elem_dt = buf.dtype.elementType
        return self._scatter(elem_dt, vals, evalid, egid, cap)

    def merge(self, buffers, live, gid, cap):
        return [self._merge_elements(buffers[0], live, gid, cap,
                                     dedup=False)]

    def evaluate(self, buffers):
        return buffers[0]


class CollectSet(CollectList):
    """collect_set: distinct values per group. update deduplicates
    within the batch segment; merge deduplicates across partials."""

    name = "collect_set"

    def update(self, values, live, gid, cap):
        valid = values.validity & live
        vals = values.data
        order = jnp.lexsort((vals, ~valid, gid))
        svals = jnp.take(vals, order)
        sgid = jnp.take(gid, order)
        svalid = jnp.take(valid, order)
        prev_same = jnp.concatenate([
            jnp.array([False]),
            (sgid[1:] == sgid[:-1])
            & _eq_nan_aware(svals[1:], svals[:-1]) & svalid[:-1]])
        keep = svalid & ~prev_same
        return [self._scatter(values.dtype, svals, keep, sgid, cap)]

    def merge(self, buffers, live, gid, cap):
        return [self._merge_elements(buffers[0], live, gid, cap,
                                     dedup=True)]


class CountDistinct(AggregateFunction):
    """count(DISTINCT col) — CollectSet buffers, cardinality at
    evaluate (the planner's Expand-based distinct rewrite in Spark,
    collapsed into one set-buffer aggregate here)."""

    name = "count_distinct"
    jittable = False
    binned_safe = False  # delegates to the collect-set buffer

    def __init__(self, child: Expression):
        super().__init__([child])

    # traced-mode static sizing delegates to the underlying set buffer
    _static_width = None
    _overflow = None
    begin_static = CollectList.begin_static
    end_static = CollectList.end_static

    def key(self):
        return (self.name, self._static_width,
                self.children[0].key())

    @property
    def _set(self):
        # derived lazily: children are rebound during plan analysis;
        # the throwaway delegate carries this instance's traced-mode
        # state in and out
        s = CollectSet(self.children[0])
        s._static_width = self._static_width
        s._overflow = self._overflow
        return s

    def _delegated(self, s: "CollectSet", out):
        if s._static_width is not None:
            self._overflow = s._overflow
        return out

    @property
    def dtype(self):
        return long

    @property
    def nullable(self):
        return False

    def buffer_types(self):
        return self._set.buffer_types()

    def update(self, values, live, gid, cap):
        s = self._set
        return self._delegated(s, s.update(values, live, gid, cap))

    def merge(self, buffers, live, gid, cap):
        s = self._set
        return self._delegated(s, s.merge(buffers, live, gid, cap))

    def evaluate(self, buffers):
        buf = buffers[0]
        cnt = buf.lengths.astype(jnp.int64)
        return DeviceColumn(long, cnt, jnp.ones(cnt.shape, bool))


class SumDistinct(CountDistinct):
    name = "sum_distinct"

    @property
    def dtype(self):
        return _sum_result_type(self.children[0].dtype)

    @property
    def nullable(self):
        return True

    def evaluate(self, buffers):
        buf = buffers[0]
        me = buf.data.shape[1]
        mask = (jnp.arange(me, dtype=jnp.int32)[None, :]
                < buf.lengths[:, None])
        out_t = self.dtype
        data = jnp.where(mask, buf.data.astype(out_t.np_dtype), 0).sum(
            axis=1)
        return DeviceColumn(out_t, data, buf.lengths > 0)


class Percentile(AggregateFunction):
    """Exact percentile with linear interpolation (Spark `percentile`).
    Buffers collect the group's raw values (the reference's exact
    GpuPercentile accumulates a value->count histogram via JNI
    Histogram; the padded-array buffer plays that role here), so this
    is for group sizes that fit a device row — the same practical
    envelope as the reference's exact path."""

    name = "percentile"
    jittable = False
    binned_safe = False  # collect-list buffers (sorted-gid ranks)

    def __init__(self, child: Expression, percentage: float,
                 accuracy: int = 10000):
        super().__init__([child])
        self.percentage = float(percentage)
        self.accuracy = int(accuracy)

    @property
    def _list(self):
        # derived lazily: children are rebound during plan analysis
        return CollectList(self.children[0])

    @property
    def dtype(self):
        return double

    def key(self):
        return (self.name, self.percentage, self.children[0].key())

    def buffer_types(self):
        return self._list.buffer_types()

    def update(self, values, live, gid, cap):
        return self._list.update(values, live, gid, cap)

    def merge(self, buffers, live, gid, cap):
        return self._list.merge(buffers, live, gid, cap)

    def evaluate(self, buffers):
        buf = buffers[0]
        me = buf.data.shape[1]
        cnt = buf.lengths
        mask = (jnp.arange(me, dtype=jnp.int32)[None, :] < cnt[:, None])
        vals = jnp.where(mask, buf.data.astype(jnp.float64), jnp.inf)
        svals = jnp.sort(vals, axis=1)
        rk = self.percentage * jnp.maximum(cnt - 1, 0).astype(jnp.float64)
        lo = jnp.floor(rk).astype(jnp.int32)
        hi = jnp.ceil(rk).astype(jnp.int32)
        frac = rk - lo
        safe_lo = jnp.clip(lo, 0, me - 1)
        safe_hi = jnp.clip(hi, 0, me - 1)
        vlo = jnp.take_along_axis(svals, safe_lo[:, None], axis=1)[:, 0]
        vhi = jnp.take_along_axis(svals, safe_hi[:, None], axis=1)[:, 0]
        data = vlo + (vhi - vlo) * frac
        return DeviceColumn(double, data, cnt > 0)


class ApproxPercentile(Percentile):
    """approx_percentile as a BOUNDED, MERGEABLE quantile sketch — the
    t-digest role (reference GpuApproximatePercentile.scala + JNI
    t-digest), re-designed for XLA's static shapes.

    binned_safe again (unlike the exact path): update/merge sort by
    gid themselves, so unsorted binned gids are fine.

    The sketch is K equally-spaced quantile points + a count per group
    (K derives from `accuracy`, capped so the buffer stays K+1 device
    columns regardless of group size — unlike the exact path's
    padded-array buffer, memory is O(K) per group):
    - update: sort rows by (group, value), gather each group's
      rank-floor(q_j * (n-1)) values — one device sort + K gathers;
    - merge: treat every partial's points as weight-(n/K) samples,
      sort the flattened points by (group, value), and re-extract the
      K combined quantiles by segmented weighted-rank selection;
    - evaluate: interpolate `percentage` over the K points.

    Rank error is O(1/K) per merge level (vs the reference t-digest's
    O(1/accuracy)); both satisfy "approximate" with bounded buffers,
    which is what matters at scale — and jittable=True means this
    lowers into the mesh SPMD program and the fused single-chip
    engine, which the exact collect-based path cannot.
    """

    name = "approx_percentile"
    jittable = True
    binned_safe = True

    def key(self):
        # K shapes the buffer schema and the jitted partial/merge
        # programs — cache entries must not collide across accuracies
        return (self.name, self.percentage, self.K,
                self.children[0].key())

    @property
    def K(self) -> int:
        return int(min(max(self.accuracy, 16), 128))

    def buffer_types(self):
        return [double] * self.K + [long]

    def _extract(self, svals, sw_gid, live_s, pos, cap, weights=None):
        """Shared rank-selection over (group, value)-sorted points.
        Returns K [cap] arrays indexed by group id + count/weight.

        `cap` is the number of segments (groups); the POSITION domain is
        len(pos), which differs in merge (cap*K flattened points) — the
        sentinel and clip bounds must use it, not cap."""
        npos = int(pos.shape[0])
        if weights is None:
            weights = jnp.where(live_s, 1.0, 0.0)
        total = jax.ops.segment_sum(weights, sw_gid, num_segments=cap)
        first = jax.ops.segment_min(
            jnp.where(weights > 0, pos, jnp.int32(npos)), sw_gid,
            num_segments=cap)
        # exclusive running weight within the group
        cw = jnp.cumsum(weights)
        base = jnp.take(cw - weights, jnp.clip(first, 0, npos - 1))
        cw_in = (cw - weights) - jnp.take(base, sw_gid)
        outs = []
        K = self.K
        for j in range(K):
            q = j / max(K - 1, 1)
            tgt = q * jnp.take(total, sw_gid)
            hit = (weights > 0) & (cw_in + weights >= tgt - 1e-12)
            p = jax.ops.segment_min(
                jnp.where(hit, pos, jnp.int32(npos)), sw_gid,
                num_segments=cap)
            outs.append(jnp.take(svals, jnp.clip(p, 0, npos - 1)))
        return outs, total

    def update(self, values, live, gid, cap):
        valid = live & values.validity
        v = values.data.astype(jnp.float64)
        from spark_rapids_tpu.ops.common import sort_permutation

        # row domain (gid length) and segment domain (cap) differ under
        # the binned grouping, which keeps groups at bin-count capacity
        nrow = int(gid.shape[0])
        rank = jnp.where(valid, 0, 1).astype(jnp.int32)
        key_v = jnp.where(valid, v, jnp.inf)
        perm = sort_permutation(
            [gid.astype(jnp.int64), rank.astype(jnp.int64), key_v], nrow)
        svals = jnp.take(key_v, perm)
        sgid = jnp.take(gid, perm)
        slive = jnp.take(valid, perm)
        pos = jnp.arange(nrow, dtype=jnp.int32)
        outs, total = self._extract(svals, sgid, slive, pos, cap)
        n = total.astype(jnp.int64)
        ok = n > 0
        cols = [DeviceColumn(double, o, ok) for o in outs]
        cols.append(DeviceColumn(long, n, jnp.ones((cap,), bool)))
        return cols

    def merge(self, buffers, live, gid, cap):
        from spark_rapids_tpu.ops.common import sort_permutation

        K = self.K
        n_row = buffers[K].data.astype(jnp.float64)
        row_ok = live & (n_row > 0) & buffers[0].validity
        flat = cap * K
        vals = jnp.stack([b.data for b in buffers[:K]],
                         axis=1).reshape(flat)
        gid_f = jnp.repeat(gid, K)
        w_f = jnp.repeat(jnp.where(row_ok, n_row / K, 0.0), K)
        ok_f = w_f > 0
        rank = jnp.where(ok_f, 0, 1).astype(jnp.int64)
        key_v = jnp.where(ok_f, vals, jnp.inf)
        perm = sort_permutation(
            [gid_f.astype(jnp.int64), rank, key_v], flat)
        svals = jnp.take(key_v, perm)
        sgid = jnp.take(gid_f, perm)
        sw = jnp.take(w_f, perm)
        pos = jnp.arange(flat, dtype=jnp.int32)
        # segment ids live in [0, cap); the flattened domain only needs
        # cap segments
        outs, total = self._extract(svals, sgid, sw > 0, pos, cap,
                                    weights=sw)
        n = jnp.round(total).astype(jnp.int64)
        ok = n > 0
        cols = [DeviceColumn(double, o, ok) for o in outs]
        cols.append(DeviceColumn(long, n, jnp.ones((cap,), bool)))
        return cols

    def evaluate(self, buffers):
        K = self.K
        n = buffers[K].data
        rk = self.percentage * (K - 1)
        lo = int(np.floor(rk))
        hi = int(np.ceil(rk))
        frac = rk - lo
        vlo = buffers[lo].data
        vhi = buffers[hi].data
        data = vlo + (vhi - vlo) * frac
        return DeviceColumn(double, data, n > 0)
