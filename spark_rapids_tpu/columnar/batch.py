"""Device columnar batch — the `GpuColumnVector`/`ColumnarBatch` analog.

The reference wraps cuDF device columns as Spark `ColumnarBatch` columns
(`sql-plugin/src/main/java/com/nvidia/spark/rapids/GpuColumnVector.java:555`).
Here the device format is designed for XLA on TPU instead of for cuDF:

- Every batch has a **static row capacity** (power-of-two bucket) plus a
  traced `num_rows` scalar. XLA compiles one program per (schema, capacity)
  bucket; refills of the same bucket hit the jit cache. This is the answer
  to "dynamic shapes on XLA" (SURVEY.md section 7 hard part #1): operators
  whose output size is data-dependent (filter, join, aggregate) write into
  full-capacity buffers and carry the logical row count as data.
- Columns are validity-masked flat arrays; strings are a padded byte matrix
  plus a length vector (see sqltypes.datatypes.StringType).
- `ColumnBatch`/`DeviceColumn` are registered JAX pytrees so jitted kernels
  take and return them natively, and `jax.device_put`/`device_get` move
  whole batches for the spill tiers.

Rows at index >= num_rows are garbage; every kernel masks with
``row_mask(capacity, num_rows)``.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.sqltypes import (
    DataType,
    StringType,
    StructField,
    StructType,
)

MIN_CAPACITY = 1024

# device-epoch stamp source (runtime/device_monitor.py). Lazy module
# ref: importing device_monitor at module level would cycle through
# the runtime package __init__ back into this module.
_dm = None


def _current_epoch() -> int:
    global _dm
    if _dm is None:
        from spark_rapids_tpu.runtime import device_monitor

        _dm = device_monitor
    return _dm._EPOCH


def next_capacity(rows: int, minimum: int = MIN_CAPACITY) -> int:
    """Smallest power-of-two capacity bucket holding `rows`."""
    cap = max(int(minimum), 1)
    rows = max(int(rows), 1)
    while cap < rows:
        cap <<= 1
    return cap


def row_mask(capacity: int, num_rows) -> jnp.ndarray:
    """Boolean [capacity] mask of logically-live rows."""
    return jnp.arange(capacity, dtype=jnp.int32) < jnp.asarray(
        num_rows, dtype=jnp.int32)


class DeviceColumn:
    """One device column: data (+ lengths for strings/arrays) + validity.

    data:     [cap] of dtype.np_dtype; [cap, max_bytes] uint8 for strings;
              [cap, max_elems] of element np_dtype for arrays;
              [cap, max_elems, max_bytes] uint8 for array<string>
    lengths:  [cap] int32 (strings: byte count; arrays: element count)
    validity: [cap] bool, True = valid (non-null row)
    elem_validity: [cap, max_elems] bool (arrays only): per-element nulls
    elem_lengths:  [cap, max_elems] int32 (array<string> only): per-
              element byte counts
    encoding: DeviceDictionary (columnar/encoding.py) for DICTIONARY-
              ENCODED string columns: `data` is then a [cap] vector of
              integer codes into the shared device dictionary and
              `lengths` is None; decode is deferred to the last
              operator that needs materialized values.
    """

    __slots__ = ("dtype", "data", "validity", "lengths",
                 "elem_validity", "map_values", "vrange", "children",
                 "elem_lengths", "encoding", "epoch")

    def __init__(self, dtype: DataType, data, validity, lengths=None,
                 elem_validity=None, map_values=None, vrange=None,
                 children=None, elem_lengths=None, encoding=None,
                 epoch=None):
        self.dtype = dtype
        self.data = data          # maps: the KEY matrix
        self.validity = validity
        self.lengths = lengths
        self.elem_validity = elem_validity  # maps: VALUE validity
        self.map_values = map_values        # maps only: value matrix
        self.elem_lengths = elem_lengths    # array<string> only
        # STATIC (lo, hi) bound on the column's integer values, stamped
        # at upload time (quantized so refills retrace rarely). Enables
        # the sort-free direct-binned group-by; ops that change values
        # drop it (None).
        self.vrange = vrange
        # STRUCT columns: per-field child DeviceColumns (struct-of-
        # arrays; the cuDF nested-column role). `data` is a [cap] int8
        # placeholder carrying the capacity; row-level ops recurse.
        self.children = children
        # DICTIONARY-ENCODED strings: the shared DeviceDictionary
        # (columnar/encoding.py); data is then [cap] integer codes
        self.encoding = encoding
        # DEVICE EPOCH stamp (runtime/device_monitor.py): which
        # generation of the PJRT backend this column's device buffers
        # belong to. Checked at dispatch/unspill use sites — a column
        # stamped before a device-loss recovery raises DeviceLostError
        # instead of touching recycled device memory. Deliberately NOT
        # part of the pytree aux: treedefs (and thus traced programs)
        # are epoch-independent; unflattened columns re-stamp at the
        # current epoch because their leaves were just produced by the
        # live backend.
        self.epoch = _current_epoch() if epoch is None else epoch

    @property
    def is_string(self) -> bool:
        return isinstance(self.dtype, StringType)

    @property
    def is_encoded(self) -> bool:
        return self.encoding is not None

    @property
    def is_array(self) -> bool:
        from spark_rapids_tpu.sqltypes import ArrayType

        return isinstance(self.dtype, ArrayType)

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    @property
    def max_bytes(self) -> Optional[int]:
        return int(self.data.shape[1]) \
            if self.is_string and self.data.ndim == 2 else None

    @property
    def max_elems(self) -> Optional[int]:
        return int(self.data.shape[1]) if self.is_array else None

    @property
    def is_struct(self) -> bool:
        return self.children is not None

    def truncate(self, cap: int) -> "DeviceColumn":
        """Row-prefix view [:cap] of every per-row leaf (trace-safe;
        static slice); the shared dictionary of an encoded column is
        NOT row-shaped and rides unchanged. Callers guarantee live
        rows fit in cap."""
        return DeviceColumn(
            self.dtype, self.data[:cap], self.validity[:cap],
            None if self.lengths is None else self.lengths[:cap],
            None if self.elem_validity is None
            else self.elem_validity[:cap],
            None if self.map_values is None else self.map_values[:cap],
            self.vrange,
            None if self.children is None
            else [c.truncate(cap) for c in self.children],
            None if self.elem_lengths is None
            else self.elem_lengths[:cap],
            encoding=self.encoding, epoch=self.epoch)

    def padded(self, cap: int) -> "DeviceColumn":
        """`truncate`'s converse: every per-row leaf grown to `cap` rows
        with zeros (trace-safe; a pad, not a gather), so the rows added
        are null and their values zero; the dictionary rides
        unchanged."""
        def pad(x):
            if x is None or x.shape[0] == cap:
                return x
            return jnp.pad(x, ((0, cap - x.shape[0]),)
                           + ((0, 0),) * (x.ndim - 1))

        return DeviceColumn(
            self.dtype, pad(self.data), pad(self.validity),
            pad(self.lengths), pad(self.elem_validity),
            pad(self.map_values), self.vrange,
            None if self.children is None
            else [c.padded(cap) for c in self.children],
            pad(self.elem_lengths), encoding=self.encoding,
            epoch=self.epoch)

    def device_size_bytes(self) -> int:
        n = self.data.size * self.data.dtype.itemsize
        n += self.validity.size  # bool = 1 byte
        if self.lengths is not None:
            n += self.lengths.size * 4
        if self.elem_validity is not None:
            n += self.elem_validity.size
        if self.map_values is not None:
            n += self.map_values.size * self.map_values.dtype.itemsize
        if self.elem_lengths is not None:
            n += self.elem_lengths.size * 4
        if self.children is not None:
            n += sum(c.device_size_bytes() for c in self.children)
        # the dictionary of an encoded column is deliberately EXCLUDED:
        # it is shared across every referencing batch and owned/charged
        # by the encoding cache's own SpillCatalog reservation
        # (columnar/encoding.py device_dictionary)
        return n

    def with_validity(self, validity) -> "DeviceColumn":
        return self.replace(validity=validity)

    def replace(self, **kw) -> "DeviceColumn":
        """Copy with selected leaves replaced. The ONLY sanctioned way
        to rebuild a column from an existing one — hand-rolled
        DeviceColumn(c.dtype, c.data, ...) constructions silently drop
        leaves added later (struct children taught this the hard way)."""
        return DeviceColumn(
            kw.get("dtype", self.dtype),
            kw.get("data", self.data),
            kw.get("validity", self.validity),
            kw.get("lengths", self.lengths),
            kw.get("elem_validity", self.elem_validity),
            kw.get("map_values", self.map_values),
            kw.get("vrange", self.vrange),
            kw.get("children", self.children),
            kw.get("elem_lengths", self.elem_lengths),
            encoding=kw.get("encoding", self.encoding),
            epoch=kw.get("epoch", self.epoch),
        )

    def gather(self, indices, take=None) -> "DeviceColumn":
        """Row gather; indices must be in [0, capacity). Gathered values
        are a subset, so the static vrange bound survives — and for an
        encoded column only the [cap] CODES move (the dictionary is
        shared, which is exactly why join payload gathers over encoded
        strings are cheap).

        `take(array, indices)`: how the caller reads a 1-D int32 array
        at the indices (a lookup join reads a small one a row of 128
        entries a slot, ops/joinops.py `read_entries`). Given one, a
        plain integer column (dictionary codes too) whose stamped range
        spans fewer than 2^31 values is read through it as ONE 32-bit
        array, its value less the range's low end and -1 where it is
        null, where the gather below reads the data (two 32-bit halves
        for a 64-bit column) and the validity, each a 4-byte read a
        slot. Making that array is a pass over the whole column, which
        a read of about the column's own width repays and a gather of
        a few rows from a large column does not."""
        if take is not None and self.reads_as_one_array():
            return self.decoded(take(self.coded(), indices))
        return DeviceColumn(
            self.dtype,
            jnp.take(self.data, indices, axis=0),
            jnp.take(self.validity, indices, axis=0),
            None if self.lengths is None else jnp.take(self.lengths, indices,
                                                       axis=0),
            None if self.elem_validity is None else jnp.take(
                self.elem_validity, indices, axis=0),
            None if self.map_values is None else jnp.take(
                self.map_values, indices, axis=0),
            vrange=self.vrange,
            children=None if self.children is None
            else [c.gather(indices) for c in self.children],
            elem_lengths=None if self.elem_lengths is None
            else jnp.take(self.elem_lengths, indices, axis=0),
            encoding=self.encoding,
            epoch=self.epoch,
        )

    def reads_as_one_array(self) -> bool:
        """A flat integer column with a stamped range of fewer than
        2^31 values: its value less the range's low end, or -1, fits
        one int32 (`coded`)."""
        vr = self.vrange
        return (vr is not None and self.data.ndim == 1
                and jnp.issubdtype(self.data.dtype, jnp.integer)
                and vr[1] - vr[0] < 2 ** 31 - 1 and self.lengths is None
                and self.elem_validity is None and self.map_values is None
                and self.children is None and self.elem_lengths is None)

    def _wide(self):
        return jnp.int64 if self.data.dtype.itemsize > 4 else jnp.int32

    def coded(self) -> jnp.ndarray:
        """The column as ONE int32 array (`reads_as_one_array`): each
        value less the stamped range's low end, -1 where it is null."""
        return jnp.where(self.validity,
                         self.data.astype(self._wide()) - self.vrange[0],
                         -1).astype(jnp.int32)

    def decoded(self, got: jnp.ndarray) -> "DeviceColumn":
        """The column whose `coded` array read `got` at some rows."""
        valid = got >= 0
        data = jnp.where(valid, got.astype(self._wide()) + self.vrange[0], 0)
        return self.replace(data=data.astype(self.data.dtype),
                            validity=valid)

    def _tree_flatten(self):
        leaves = [self.data, self.validity]
        if self.lengths is not None:
            leaves.append(self.lengths)
        if self.elem_validity is not None:
            leaves.append(self.elem_validity)
        if self.map_values is not None:
            leaves.append(self.map_values)
        if self.elem_lengths is not None:
            leaves.append(self.elem_lengths)
        if self.encoding is not None:
            # DeviceDictionary is a registered pytree node; its aux
            # carries the dict_id, so a different dictionary means a
            # different treedef (and a retrace) by construction
            leaves.append(self.encoding)
        if self.children is not None:
            # child DeviceColumns are registered pytree nodes; jax
            # recurses into them
            leaves.extend(self.children)
        return tuple(leaves), (self.dtype, self.lengths is not None,
                               self.elem_validity is not None,
                               self.map_values is not None, self.vrange,
                               len(self.children)
                               if self.children is not None else -1,
                               self.elem_lengths is not None,
                               self.encoding is not None)

    @classmethod
    def _tree_unflatten(cls, aux, children):
        (dtype, has_len, has_ev, has_mv, vrange, n_struct, has_el,
         has_enc) = aux
        it = iter(children)
        data = next(it)
        validity = next(it)
        lengths = next(it) if has_len else None
        ev = next(it) if has_ev else None
        mv = next(it) if has_mv else None
        el = next(it) if has_el else None
        enc = next(it) if has_enc else None
        kids = ([next(it) for _ in range(n_struct)]
                if n_struct >= 0 else None)
        return cls(dtype, data, validity, lengths, ev, mv, vrange, kids,
                   el, encoding=enc)


jax.tree_util.register_pytree_node(
    DeviceColumn,
    lambda c: c._tree_flatten(),
    DeviceColumn._tree_unflatten,
)


class ColumnBatch:
    """A batch of device columns with shared capacity and row count.

    `num_rows` may be a Python int or a traced/device int32 scalar; inside
    jitted kernels it is always traced. `row_count()` forces a host value
    (device sync) and caches it.
    """

    __slots__ = ("schema", "columns", "num_rows", "_host_rows")

    def __init__(self, schema: StructType, columns: List[DeviceColumn],
                 num_rows):
        assert len(schema.fields) == len(columns)
        self.schema = schema
        self.columns = columns
        self.num_rows = num_rows
        self._host_rows = num_rows if isinstance(num_rows, int) else None

    @property
    def capacity(self) -> int:
        if not self.columns:
            return MIN_CAPACITY
        return self.columns[0].capacity

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def row_count(self) -> int:
        if self._host_rows is None:
            from spark_rapids_tpu.obs import telemetry

            self._host_rows = int(telemetry.ledgered_get(
                self.num_rows, "batch.rowCount"))
        return self._host_rows

    def live_mask(self) -> jnp.ndarray:
        return row_mask(self.capacity, self.num_rows)

    def device_size_bytes(self) -> int:
        return sum(c.device_size_bytes() for c in self.columns)

    def column(self, name: str) -> DeviceColumn:
        return self.columns[self.schema.field_index(name)]

    def select(self, indices: Sequence[int]) -> "ColumnBatch":
        return ColumnBatch(
            StructType([self.schema.fields[i] for i in indices]),
            [self.columns[i] for i in indices],
            self.num_rows,
        )

    def gather(self, indices, new_num_rows) -> "ColumnBatch":
        return ColumnBatch(
            self.schema, [c.gather(indices) for c in self.columns],
            new_num_rows)

    def _tree_flatten(self):
        nr = self.num_rows
        if isinstance(nr, (int, np.integer)):
            nr = jnp.asarray(nr, jnp.int32)
        return (tuple(self.columns), nr), self.schema

    @classmethod
    def _tree_unflatten(cls, schema, children):
        columns, num_rows = children
        return cls(schema, list(columns), num_rows)

    def __repr__(self):
        return (f"ColumnBatch(rows={self._host_rows or '?'}, "
                f"cap={self.capacity}, cols={self.schema.names})")


jax.tree_util.register_pytree_node(
    ColumnBatch,
    lambda b: b._tree_flatten(),
    ColumnBatch._tree_unflatten,
)


def make_column(dtype: DataType, values: np.ndarray,
                validity: Optional[np.ndarray], capacity: int,
                lengths: Optional[np.ndarray] = None,
                elem_validity: Optional[np.ndarray] = None) -> DeviceColumn:
    """Build a column from host numpy data, padding to capacity. The
    returned column holds NUMPY leaves — the caller uploads the whole
    batch with ONE jax.device_put instead of one transfer per array.

    For strings, `values` is a [n, max_bytes] uint8 matrix and `lengths`
    the per-row byte counts. For arrays, `values` is [n, max_elems] of
    the element dtype, `lengths` the element counts, and `elem_validity`
    the per-element null mask.
    """
    from spark_rapids_tpu.sqltypes import ArrayType

    # maps pass (key_matrix, value_matrix)
    n = len(values[0]) if isinstance(values, tuple) else len(values)
    if validity is None:
        validity = np.ones(n, dtype=np.bool_)
    vpad = np.zeros(capacity, dtype=np.bool_)
    vpad[:n] = validity
    if isinstance(dtype, StringType):
        assert values.ndim == 2 and values.dtype == np.uint8
        data = np.zeros((capacity, values.shape[1]), dtype=np.uint8)
        data[:n, :] = values
        lpad = np.zeros(capacity, dtype=np.int32)
        if lengths is not None:
            lpad[:n] = lengths
        return DeviceColumn(dtype, data, vpad, lpad)
    if isinstance(dtype, ArrayType) and isinstance(dtype.elementType,
                                                   StringType):
        # array<string>: (values cube [n, E, B] uint8, per-element byte
        # lengths [n, E]) arrive as a tuple
        cube, elens = values
        assert cube.ndim == 3 and cube.dtype == np.uint8
        data = np.zeros((capacity,) + cube.shape[1:], dtype=np.uint8)
        data[:n] = cube
        lpad = np.zeros(capacity, dtype=np.int32)
        if lengths is not None:
            lpad[:n] = lengths
        ev = np.zeros((capacity, cube.shape[1]), dtype=np.bool_)
        if elem_validity is not None:
            ev[:n] = elem_validity
        el = np.zeros((capacity, cube.shape[1]), dtype=np.int32)
        el[:n] = elens
        return DeviceColumn(dtype, data, vpad, lpad, ev,
                            elem_lengths=el)
    if isinstance(dtype, ArrayType):
        assert values.ndim == 2
        data = np.zeros((capacity, values.shape[1]),
                        dtype=dtype.elementType.np_dtype)
        data[:n, :] = values
        lpad = np.zeros(capacity, dtype=np.int32)
        if lengths is not None:
            lpad[:n] = lengths
        ev = np.zeros((capacity, values.shape[1]), dtype=np.bool_)
        if elem_validity is not None:
            ev[:n, :] = elem_validity
        return DeviceColumn(dtype, data, vpad, lpad, ev)
    from spark_rapids_tpu.sqltypes import MapType

    if isinstance(dtype, MapType):
        # values is (key_matrix, value_matrix); elem_validity covers
        # VALUES (map keys are never null)
        kmat, vmat = values
        me = kmat.shape[1]
        kd = np.zeros((capacity, me), dtype=dtype.keyType.np_dtype)
        kd[:n, :] = kmat
        vd = np.zeros((capacity, me), dtype=dtype.valueType.np_dtype)
        vd[:n, :] = vmat
        lpad = np.zeros(capacity, dtype=np.int32)
        if lengths is not None:
            lpad[:n] = lengths
        ev = np.zeros((capacity, me), dtype=np.bool_)
        if elem_validity is not None:
            ev[:n, :] = elem_validity
        return DeviceColumn(dtype, kd, vpad, lpad, ev, vd)
    if values.ndim == 2:  # DECIMAL128 limb matrix [n, 2]
        data = np.zeros((capacity, 2), dtype=np.int64)
        data[:n, :] = values
        return DeviceColumn(dtype, data, vpad)
    data = np.zeros(capacity, dtype=dtype.np_dtype)
    data[:n] = values
    return DeviceColumn(dtype, data, vpad)


def row_select(pred, x, y):
    """Row-wise where: broadcast a [cap] predicate across every
    trailing axis of x/y (strings, arrays, array<string> cubes)."""
    return jnp.where(pred.reshape((-1,) + (1,) * (x.ndim - 1)), x, y)


def pad_trailing(x, trailing):
    """Zero-pad x's trailing axes up to `trailing` (no-op when equal) —
    the one alignment primitive for variable-width leaves (string
    bytes, array elems, array<string> elems x bytes)."""
    if x is None or tuple(x.shape[1:]) == tuple(trailing):
        return x
    return jnp.pad(x, ((0, 0),) + tuple(
        (0, t - s) for s, t in zip(x.shape[1:], trailing)))


def align_trailing(leaves):
    """Pad every leaf's trailing axes to the per-axis max across
    leaves (all leaves must share ndim)."""
    nd = leaves[0].ndim
    if nd == 1:
        return list(leaves)
    target = tuple(max(int(x.shape[ax]) for x in leaves)
                   for ax in range(1, nd))
    return [pad_trailing(x, target) for x in leaves]


def _empty_column(dataType: DataType, capacity: int,
                  string_bytes: int) -> DeviceColumn:
    from spark_rapids_tpu.sqltypes import ArrayType

    if isinstance(dataType, StringType):
        return DeviceColumn(
            dataType,
            jnp.zeros((capacity, string_bytes), jnp.uint8),
            jnp.zeros(capacity, jnp.bool_),
            jnp.zeros(capacity, jnp.int32))
    if isinstance(dataType, ArrayType):
        et = dataType.elementType
        if isinstance(et, StringType):  # array<string> cube layout
            return DeviceColumn(
                dataType,
                jnp.zeros((capacity, 1, string_bytes), jnp.uint8),
                jnp.zeros(capacity, jnp.bool_),
                jnp.zeros(capacity, jnp.int32),
                jnp.zeros((capacity, 1), jnp.bool_),
                elem_lengths=jnp.zeros((capacity, 1), jnp.int32))
        return DeviceColumn(
            dataType,
            jnp.zeros((capacity, 1), et.np_dtype),
            jnp.zeros(capacity, jnp.bool_),
            jnp.zeros(capacity, jnp.int32),
            jnp.zeros((capacity, 1), jnp.bool_))
    if isinstance(dataType, StructType):
        return DeviceColumn(
            dataType, jnp.zeros(capacity, jnp.int8),
            jnp.zeros(capacity, jnp.bool_),
            children=[_empty_column(f.dataType, capacity, string_bytes)
                      for f in dataType.fields])
    from spark_rapids_tpu.ops import decimal128 as _d128

    shape = ((capacity, 2) if _d128.is_wide(dataType)
             else (capacity,))
    return DeviceColumn(
        dataType,
        jnp.zeros(shape, dataType.np_dtype),
        jnp.zeros(capacity, jnp.bool_))


def empty_like_schema(schema: StructType, capacity: int,
                      string_bytes: int = 8) -> ColumnBatch:
    cols = [_empty_column(f.dataType, capacity, string_bytes)
            for f in schema.fields]
    return ColumnBatch(schema, cols, 0)


def _concat_columns(pieces: List[Tuple[DeviceColumn, int]], cap: int,
                    total: int, dtype: DataType) -> DeviceColumn:
    """Concatenate per-batch column prefixes into one [cap] column
    (recursing into struct children). Encoded pieces stay encoded only
    when every piece shares ONE dictionary; any identity mismatch
    decodes first (code spaces are not comparable across
    dictionaries)."""
    if any(c.encoding is not None for c, _ in pieces):
        from spark_rapids_tpu.columnar import encoding as _enc

        aligned = _enc.align_encodings([c for c, _ in pieces])
        pieces = list(zip(aligned, (n for _, n in pieces)))
    first = pieces[0][0]
    if first.children is not None:
        kids = [
            _concat_columns([(c.children[i], n) for c, n in pieces],
                            cap, total, first.children[i].dtype)
            for i in range(len(first.children))
        ]
        pad = cap - total
        val = jnp.pad(jnp.concatenate(
            [c.validity[:n] for c, n in pieces]), (0, pad))
        data = jnp.zeros((cap,), jnp.int8)
        return DeviceColumn(dtype, data, val, children=kids)
    def align_cat(parts):
        """Concatenate row prefixes, padding every TRAILING axis to
        its max across pieces (string bytes, array elems, and both
        axes of an array<string> cube)."""
        parts = align_trailing(parts)
        out = jnp.concatenate(parts, axis=0)
        if pad:
            out = jnp.pad(out,
                          ((0, pad),) + ((0, 0),) * (out.ndim - 1))
        return out

    pad = cap - total
    data = align_cat([c.data[:n] for c, n in pieces])
    val = align_cat([c.validity[:n] for c, n in pieces])
    lens = ev = mv = el = None
    if first.lengths is not None:
        lens = align_cat([c.lengths[:n] for c, n in pieces])
    if first.elem_validity is not None:
        ev = align_cat([c.elem_validity[:n] for c, n in pieces])
    if first.map_values is not None:
        mv = align_cat([c.map_values[:n] for c, n in pieces])
    if first.elem_lengths is not None:
        el = align_cat([c.elem_lengths[:n] for c, n in pieces])
    # encoded columns keep their [0, K) code bound through concat (the
    # binned group-by depends on it); plain columns keep the historical
    # drop-vrange-at-concat behavior
    vr = first.vrange if (
        first.encoding is not None
        and all(c.vrange == first.vrange for c, _ in pieces)) else None
    return DeviceColumn(dtype, data, val, lens, ev, mv, vrange=vr,
                        elem_lengths=el, encoding=first.encoding)


def concat_batches(batches: List[ColumnBatch]) -> ColumnBatch:
    """Concatenate batches (cuDF `Table.concatenate` analog) — the engine of
    coalescing (reference GpuCoalesceBatches.scala:250)."""
    assert batches
    if len(batches) == 1:
        return batches[0]
    schema = batches[0].schema
    total = sum(b.row_count() for b in batches)
    cap = next_capacity(total)
    cols: List[DeviceColumn] = []
    for ci, field in enumerate(schema.fields):
        pieces = [(b.columns[ci], b.row_count()) for b in batches]
        cols.append(_concat_columns(pieces, cap, total, field.dataType))
    return ColumnBatch(schema, cols, total)
