"""Expression tree core — the GpuExpression analog.

The reference's expressions implement `columnarEval(batch) -> GpuColumnVector`
(`sql-plugin/.../GpuExpressions.scala:155`), each node launching cuDF
kernels. Here `Expression.eval(ctx)` emits jax/jnp ops instead; an entire
projection/filter/aggregation expression tree is traced into ONE XLA
program by the enclosing jitted operator, so per-node fusion is the
compiler's job (the TPU answer to cuDF's AST fused-eval path,
`GpuExpressions.scala:171` convertToAst).

Null semantics follow Spark: every node declares nullability and
propagates validity masks explicitly.

`key()` returns a hashable structural description used to cache compiled
operator programs across batches.
"""

from __future__ import annotations

import math
from typing import Any, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar.batch import ColumnBatch, DeviceColumn
from spark_rapids_tpu.sqltypes import (
    BooleanType,
    DataType,
    DateType,
    DecimalType,
    DoubleType,
    FloatType,
    IntegerType,
    LongType,
    StringType,
    TimestampType,
)


class EvalContext:
    """Carries the input batch plus derived values during tree evaluation."""

    def __init__(self, batch: ColumnBatch):
        self.batch = batch
        self.live = batch.live_mask()

    @property
    def capacity(self) -> int:
        return self.batch.capacity


class Expression:
    """Base expression node."""

    def __init__(self, children: Sequence["Expression"] = ()):
        self.children = list(children)

    @property
    def dtype(self) -> DataType:
        raise NotImplementedError

    @property
    def nullable(self) -> bool:
        return any(c.nullable for c in self.children)

    def eval(self, ctx: EvalContext) -> DeviceColumn:
        raise NotImplementedError

    def key(self) -> Tuple:
        return (type(self).__name__,
                tuple(c.key() for c in self.children))

    def references(self) -> List[int]:
        out: List[int] = []
        for c in self.children:
            out.extend(c.references())
        return out

    def transform(self, fn) -> "Expression":
        """Bottom-up rewrite; fn(node) returns node or a replacement."""
        new_children = [c.transform(fn) for c in self.children]
        node = self.with_children(new_children)
        return fn(node)

    def with_children(self, children: List["Expression"]) -> "Expression":
        import copy

        node = copy.copy(self)
        node.children = list(children)
        return node

    def __repr__(self):
        cs = ", ".join(repr(c) for c in self.children)
        return f"{type(self).__name__}({cs})"


class BoundReference(Expression):
    """Reference to input column by ordinal (already resolved/bound)."""

    def __init__(self, ordinal: int, dtype: DataType, nullable: bool = True):
        super().__init__()
        self.ordinal = ordinal
        self._dtype = dtype
        self._nullable = nullable

    @property
    def dtype(self):
        return self._dtype

    @property
    def nullable(self):
        return self._nullable

    def eval(self, ctx: EvalContext) -> DeviceColumn:
        col = ctx.batch.columns[self.ordinal]
        if getattr(col, "encoding", None) is not None:
            # dictionary-encoded columns DECODE here by default, so
            # every downstream expression sees the standard string
            # layout without auditing each one. The consumers that can
            # run on codes (grouping, bare-column projections, the
            # equality/IN/null predicate probes, CodesOf join keys)
            # bypass eval() and read the batch column directly
            # (columnar/encoding.py raw_column / eval_preserving).
            from spark_rapids_tpu.columnar import encoding as _enc

            return _enc.decode_column(col)
        return col

    def key(self):
        return ("ref", self.ordinal, repr(self._dtype))

    def references(self):
        return [self.ordinal]

    def __repr__(self):
        return f"col#{self.ordinal}"


class Literal(Expression):
    def __init__(self, value: Any, dtype: Optional[DataType] = None):
        super().__init__()
        if dtype is None:
            dtype = _infer_literal_type(value)
            value = _temporal_value(value)
        self.value = value
        self._dtype = dtype

    @property
    def dtype(self):
        return self._dtype

    @property
    def nullable(self):
        return self.value is None

    def eval(self, ctx: EvalContext) -> DeviceColumn:
        cap = ctx.capacity
        dt = self._dtype
        if isinstance(dt, StringType):
            raw = (self.value or "").encode("utf-8")
            mb = max(8, 1 << max(0, (len(raw) - 1)).bit_length())
            mat = np.zeros((1, mb), np.uint8)
            mat[0, :len(raw)] = list(raw)
            data = jnp.broadcast_to(jnp.asarray(mat), (cap, mb))
            lengths = jnp.full((cap,), np.int32(len(raw)))
            valid = jnp.full((cap,), self.value is not None)
            return DeviceColumn(dt, data, valid, lengths)
        from spark_rapids_tpu.ops import decimal128 as _d128

        wide = _d128.is_wide(dt)
        if self.value is None:
            data = jnp.zeros((cap, 2) if wide else (cap,), dt.np_dtype)
            return DeviceColumn(dt, data, jnp.zeros((cap,), bool))
        v = self.value
        if isinstance(dt, DecimalType):
            import decimal

            v = int(decimal.Decimal(str(v)).scaleb(dt.scale)
                    .to_integral_value())
            if wide:
                hi = (v >> 64)
                lo = _d128._i64_bits(v)
                data = jnp.broadcast_to(
                    jnp.asarray([hi, lo], jnp.int64), (cap, 2))
                return DeviceColumn(dt, data, jnp.ones((cap,), bool))
        data = jnp.full((cap,), v, dtype=dt.np_dtype)
        return DeviceColumn(dt, data, jnp.ones((cap,), bool))

    def key(self):
        return ("lit", repr(self.value), repr(self._dtype))

    def __repr__(self):
        return f"lit({self.value!r})"


def _infer_literal_type(v: Any) -> DataType:
    from spark_rapids_tpu.sqltypes.datatypes import (
        boolean, double, integer, long, string,
    )

    if v is None:
        return LongType()
    if isinstance(v, bool):
        return boolean
    if isinstance(v, int):
        return integer if -(2**31) <= v < 2**31 else long
    if isinstance(v, float):
        return double
    if isinstance(v, str):
        return string
    import datetime
    import decimal

    if isinstance(v, datetime.datetime):
        return TimestampType()
    if isinstance(v, datetime.date):
        return DateType()

    if isinstance(v, decimal.Decimal):
        sign, digits, exp = v.as_tuple()
        scale = max(0, -exp)
        return DecimalType(max(len(digits), scale), scale)
    if isinstance(v, (list, tuple)):
        from spark_rapids_tpu.sqltypes import ArrayType

        elem = next((x for x in v if x is not None), None)
        if elem is None:
            return ArrayType(LongType())
        et = _infer_literal_type(elem)
        if isinstance(elem, int) and not isinstance(elem, bool):
            et = LongType()  # match the common array<bigint> columns
        return ArrayType(et)
    raise TypeError(f"cannot infer literal type for {v!r}")


def _temporal_value(v: Any) -> Any:
    """A date or datetime as its physical encoding, which is what a
    typed `Literal(days, DateType())` holds: days since the epoch, or
    microseconds since the epoch in UTC (a naive datetime is UTC)."""
    import datetime

    if isinstance(v, datetime.datetime):
        if v.tzinfo is None:
            v = v.replace(tzinfo=datetime.timezone.utc)
        delta = v - datetime.datetime(1970, 1, 1,
                                      tzinfo=datetime.timezone.utc)
        return (delta.days * 86_400 + delta.seconds) * 1_000_000 \
            + delta.microseconds
    if isinstance(v, datetime.date):
        return (v - datetime.date(1970, 1, 1)).days
    return v


class Alias(Expression):
    """Named wrapper — transparent at eval time."""

    def __init__(self, child: Expression, name: str):
        super().__init__([child])
        self.name = name

    @property
    def dtype(self):
        return self.children[0].dtype

    @property
    def nullable(self):
        return self.children[0].nullable

    def eval(self, ctx):
        return self.children[0].eval(ctx)

    def key(self):
        return ("alias", self.children[0].key())

    def __repr__(self):
        return f"{self.children[0]!r} AS {self.name}"


def binary_validity(left: DeviceColumn, right: DeviceColumn) -> jnp.ndarray:
    return left.validity & right.validity
