"""Shared kernel utilities: orderable sort keys and row-wise equality.

Replaces cuDF's internal comparator machinery (`Table.sort`,
`Table.*JoinGatherMaps` key handling). The TPU strategy: every column is
lowered to one or more **int64 arrays whose signed order equals the SQL
order** ("orderable keys"), so `jax.lax.sort` with multiple key operands
implements multi-column ORDER BY / GROUP BY / join-key ordering directly:

- integrals/date/timestamp/decimal64: sign-extended int64.
- float/double: IEEE-754 total-order bit trick with NaN canonicalized, so
  NaN sorts greater than +inf and -0.0 < 0.0, matching Spark's
  Double.compare ordering.
- strings: zero-padded bytes packed big-endian 4-per-int64 word (always
  non-negative, so signed int64 order == unsigned byte order without any
  64-bit bitcast, which this TPU's 64-bit-emulation pass cannot compile).
- a leading "null rank" key encodes NULLS FIRST/LAST and forces logically
  dead rows (index >= num_rows) after all live rows.

Descending order is bitwise NOT of the key (total order reversal without
overflow).

TPU 64-bit caveat: XLA:TPU v5e emulates s64 exactly and f64 as a pair
of f32 (sums read ~1e-13 relative on the chip: docs/compatibility.md)
but cannot bitcast 64-bit types. DoubleType sort keys therefore go
through the f32 total-order bits on TPU (order is approximate for
doubles closer than 2^-24 relative) and through exact f64 bits on the
CPU backend.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu.columnar.batch import DeviceColumn
from spark_rapids_tpu.sqltypes import (
    BooleanType,
    DoubleType,
    FloatType,
    StringType,
)


def supports_64bit_bitcast() -> bool:
    """True when the default backend compiles 64-bit bitcast_convert (CPU);
    False on TPU v5e where the x64-rewrite pass lacks it."""
    return jax.default_backend() == "cpu"


def _float_orderable(data: jnp.ndarray) -> jnp.ndarray:
    """float -> int64 whose signed order is Java's Double.compare order."""
    if data.dtype == jnp.float64 and supports_64bit_bitcast():
        b = lax.bitcast_convert_type(data, jnp.int64)
        b = jnp.where(jnp.isnan(data), jnp.int64(0x7FF8000000000000), b)
        # flip negative range: b<0 -> MIN - b maps descending negatives to
        # ascending; equivalent to the classic bit trick in signed space.
        return jnp.where(b < 0, jnp.int64(-0x8000000000000000) - b - 1, b)
    f = data.astype(jnp.float32)
    b = lax.bitcast_convert_type(f, jnp.int32)
    b = jnp.where(jnp.isnan(f), jnp.int32(0x7FC00000), b)
    b = jnp.where(b < 0, jnp.int32(-0x80000000) - b - 1, b)
    return b.astype(jnp.int64)


def _string_orderable(col: DeviceColumn) -> List[jnp.ndarray]:
    """Packed big-endian 4-byte int64 words; relies on the zero-padding
    invariant (bytes at positions >= length are 0). The length vector is
    the final tie-break key so strings with trailing/embedded NUL bytes
    ("a" vs "a\\x00") stay distinct — and it orders them correctly, since
    equal-prefix shorter strings sort first."""
    mb = col.max_bytes
    nwords = (mb + 3) // 4
    pad = nwords * 4 - mb
    data = col.data
    if pad:
        data = jnp.pad(data, ((0, 0), (0, pad)))
    words = data.reshape(data.shape[0], nwords, 4).astype(jnp.int64)
    shifts = jnp.array([24, 16, 8, 0], dtype=jnp.int64)
    packed = (words << shifts[None, None, :]).sum(axis=-1)
    return [packed[:, i] for i in range(nwords)] + [
        col.lengths.astype(jnp.int64)]


def normalize_floating(col: DeviceColumn) -> DeviceColumn:
    """Spark's NormalizeFloatingNumbers: -0.0 -> 0.0 for group/join keys
    (NaNs are already canonicalized by the total-order key transform)."""
    if isinstance(col.dtype, (FloatType, DoubleType)):
        data = jnp.where(col.data == 0.0, jnp.zeros_like(col.data), col.data)
        return DeviceColumn(col.dtype, data, col.validity, col.lengths)
    return col


def orderable_keys(col: DeviceColumn, ascending: bool, nulls_first: bool,
                   live: jnp.ndarray,
                   codes_ok: bool = False) -> List[jnp.ndarray]:
    """Lower one column (+ sort direction) to signed-orderable int64 keys.

    Returns [null_rank_key, value_key...]; dead rows always rank last
    regardless of direction.

    Dictionary-ENCODED columns: with `codes_ok` (equality-only
    contexts — grouping, where only tuple EQUALITY matters and interned
    dictionaries guarantee code equality == value equality) the key is
    the raw code vector; otherwise the column decodes in-device first
    so the order is the true lexicographic string order.
    """
    if getattr(col, "encoding", None) is not None:
        if codes_ok:
            valid = col.validity
            if nulls_first:
                rank = jnp.where(valid, 1, 0)
            else:
                rank = jnp.where(valid, 0, 1)
            rank = jnp.where(live, rank, 2).astype(jnp.int64)
            vals = [jnp.where(valid & live,
                              col.data.astype(jnp.int64), 0)]
            if not ascending:
                vals = [~v for v in vals]
            return [rank] + vals
        from spark_rapids_tpu.columnar import encoding as _enc

        col = _enc.decode_column(col)
    valid = col.validity
    if nulls_first:
        rank = jnp.where(valid, 1, 0)
    else:
        rank = jnp.where(valid, 0, 1)
    rank = jnp.where(live, rank, 2).astype(jnp.int64)

    dt = col.dtype
    if isinstance(dt, StringType):
        vals = _string_orderable(col)
    elif isinstance(dt, (FloatType, DoubleType)):
        vals = [_float_orderable(col.data)]
    elif isinstance(dt, BooleanType):
        vals = [col.data.astype(jnp.int64)]
    elif col.data.ndim == 2:  # DECIMAL128 limb matrix
        from spark_rapids_tpu.ops import decimal128 as _d128

        vals = _d128.orderable_limbs(col.data)
    else:
        vals = [col.data.astype(jnp.int64)]
    # Null/dead rows: zero the value keys so ordering within them is stable.
    vals = [jnp.where(valid & live, v, 0) for v in vals]
    if not ascending:
        vals = [~v for v in vals]
    return [rank] + vals


def equality_keys(col: DeviceColumn, live: jnp.ndarray,
                  codes_ok: bool = False) -> List[jnp.ndarray]:
    """Keys whose tuple equality == SQL group/join-key equality (null ==
    null for grouping; NaN == NaN, +0.0 == -0.0? No: Spark group keys use
    binary equality where NaN==NaN and -0.0==0.0 normalized — the float
    total-order key satisfies NaN==NaN; -0.0/0.0 map to distinct keys, so
    normalize zeros first in the caller for float group keys).
    `codes_ok` lets SINGLE-BATCH equality contexts (grouping) key
    encoded columns by their dictionary codes; cross-batch contexts
    (join sides prepared in separate programs) must leave it False."""
    return orderable_keys(col, True, True, live, codes_ok=codes_ok)


def rows_equal_adjacent(keys: List[jnp.ndarray]) -> jnp.ndarray:
    """For sorted gathered keys: eq[i] = keys[i] == keys[i-1] (eq[0]=False)."""
    eq = None
    for k in keys:
        e = jnp.concatenate([jnp.array([False]), k[1:] == k[:-1]])
        eq = e if eq is None else (eq & e)
    return eq


def sorted_with_permutation(key_arrays: List[jnp.ndarray], capacity: int
                            ) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """Stable multi-key sort; -> (the keys, sorted; the gather
    permutation). The sorted keys are the sort's own outputs: a caller
    of `sort_permutation` that wants them gathers them a second time."""
    iota = jnp.arange(capacity, dtype=jnp.int32)
    out = lax.sort(tuple(key_arrays) + (iota,), num_keys=len(key_arrays),
                   is_stable=True)
    return list(out[:-1]), out[-1]


def sort_permutation(key_arrays: List[jnp.ndarray],
                     capacity: int) -> jnp.ndarray:
    """Stable multi-key sort; returns the gather permutation (cuDF
    `Table.sortOrder` analog)."""
    return sorted_with_permutation(key_arrays, capacity)[1]
