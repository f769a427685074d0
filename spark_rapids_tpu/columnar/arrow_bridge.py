"""Host (Arrow) <-> device (ColumnBatch) transitions.

The reference's row/columnar transitions are `GpuRowToColumnarExec` and
`GpuColumnarToRowExec` plus the cuDF host<->device copies
(`GpuRowToColumnarExec.scala:861`, `GpuColumnarToRowExec.scala:335`). Here
the host-side columnar currency is pyarrow (which also backs the CPU oracle
backend and the file readers), so the transitions are Arrow<->ColumnBatch:

- arrow_to_device: pads each column into its capacity bucket, builds the
  string byte-matrix layout vectorized in numpy (no per-row Python), and
  `jax.device_put`s the result.
- device_to_arrow: slices to the logical row count and rebuilds Arrow
  arrays, reconstructing string offsets from the padded matrix.
"""

from __future__ import annotations

import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from spark_rapids_tpu.columnar.batch import (
    ColumnBatch,
    DeviceColumn,
    make_column,
    next_capacity,
)
from spark_rapids_tpu.sqltypes import (
    ArrayType,
    DataType,
    DecimalType,
    MapType,
    StringType,
    StructField,
    StructType,
)
from spark_rapids_tpu.sqltypes.datatypes import from_arrow_type, to_arrow_type


def _round_up_pow2(n: int, minimum: int = 8) -> int:
    c = minimum
    while c < n:
        c <<= 1
    return c


def schema_from_arrow(schema: pa.Schema) -> StructType:
    return StructType([
        StructField(f.name, from_arrow_type(f.type), f.nullable)
        for f in schema
    ])


def _check_string_ceiling(max_len: int) -> None:
    """Enforce spark.rapids.tpu.string.maxBytes: the padded-matrix
    width adapts per column, but a pathological value (a megabyte blob)
    would multiply the whole column's footprint — fail loudly with the
    conf escape hatch instead."""
    from spark_rapids_tpu.config import rapids_conf as rc

    ceiling = rc.STRING_MAX_BYTES.default
    try:
        from spark_rapids_tpu.api.session import TpuSparkSession

        s = TpuSparkSession.active()
        if s is not None:
            ceiling = s.rapids_conf.get(rc.STRING_MAX_BYTES)
    except Exception:
        pass
    if max_len > ceiling:
        from spark_rapids_tpu.runtime.errors import StringWidthExceeded

        raise StringWidthExceeded(
            f"string of {max_len} bytes exceeds the device padded-width "
            f"ceiling {ceiling} (spark.rapids.tpu.string.maxBytes); "
            "query falls back to the CPU engine")


def _string_to_matrix(arr: pa.Array, pad_to: Optional[int] = None):
    """Arrow utf8 array -> ([n, max_bytes] uint8, lengths int32) vectorized."""
    arr = arr.cast(pa.large_string()) if pa.types.is_string(arr.type) else arr
    if pa.types.is_large_string(arr.type):
        offsets = np.frombuffer(arr.buffers()[1], dtype=np.int64,
                                count=len(arr) + arr.offset + 1)
    else:
        offsets = np.frombuffer(arr.buffers()[1], dtype=np.int32,
                                count=len(arr) + arr.offset + 1)
    offsets = offsets[arr.offset:arr.offset + len(arr) + 1].astype(np.int64)
    data_buf = arr.buffers()[2]
    flat = (np.frombuffer(data_buf, dtype=np.uint8)
            if data_buf is not None and len(data_buf) else
            np.zeros(1, dtype=np.uint8))
    lengths = (offsets[1:] - offsets[:-1]).astype(np.int32)
    max_len = int(lengths.max()) if len(lengths) else 0
    _check_string_ceiling(max_len)
    mb = _round_up_pow2(max(max_len, 1), minimum=pad_to or 8)
    n = len(arr)
    idx = offsets[:-1, None] + np.arange(mb, dtype=np.int64)[None, :]
    mask = np.arange(mb, dtype=np.int32)[None, :] < lengths[:, None]
    out = np.where(mask, flat[np.clip(idx, 0, len(flat) - 1)], 0).astype(
        np.uint8)
    return out, lengths


def _matrix_to_string(data: np.ndarray, lengths: np.ndarray,
                      validity: np.ndarray) -> pa.Array:
    """([n, mb] uint8, lengths, validity) -> Arrow utf8 array."""
    n = len(lengths)
    if n == 0:
        return pa.array([], type=pa.string())
    mb = data.shape[1]
    lengths = np.minimum(lengths.astype(np.int64), mb)
    mask = np.arange(mb)[None, :] < lengths[:, None]
    flat = data[mask]
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(lengths, out=offsets[1:])
    arr = pa.StringArray.from_buffers(
        n, pa.py_buffer(offsets.tobytes()), pa.py_buffer(flat.tobytes()))
    if not validity.all():
        arr = pa.compute.if_else(pa.array(validity), arr,
                                 pa.nulls(n, pa.string()))
    return arr


def _list_to_matrix(arr: pa.Array, elem_dtype: DataType):
    """Arrow list<primitive> -> ([n, max_elems] element matrix,
    lengths int32, elem_validity [n, max_elems]) vectorized."""
    arr = arr.cast(pa.large_list(arr.type.value_type)) \
        if pa.types.is_list(arr.type) else arr
    offsets = np.asarray(arr.offsets).astype(np.int64)
    values = arr.values  # flat child array
    lengths = (offsets[1:] - offsets[:-1]).astype(np.int32)
    n = len(arr)
    max_len = int(lengths.max()) if len(lengths) else 0
    me = _round_up_pow2(max(max_len, 1), minimum=4)
    flat_vals, flat_valid = _primitive_np(values, elem_dtype)
    if len(flat_vals) == 0:
        flat_vals = np.zeros(1, dtype=elem_dtype.np_dtype)
        flat_valid = np.zeros(1, dtype=np.bool_)
    idx = offsets[:-1, None] + np.arange(me, dtype=np.int64)[None, :]
    in_row = np.arange(me, dtype=np.int32)[None, :] < lengths[:, None]
    safe = np.clip(idx, 0, len(flat_vals) - 1)
    mat = np.where(in_row, flat_vals[safe], 0).astype(elem_dtype.np_dtype)
    ev = np.where(in_row, flat_valid[safe], False)
    return mat, lengths, ev


def _strlist_to_cube(arr: pa.Array):
    """Arrow list<string> -> ([n, max_elems, max_bytes] uint8 cube,
    row lengths int32, elem_validity [n, E], elem byte lengths [n, E])
    — the string padded-matrix layout one level up."""
    arr = arr.cast(pa.large_list(pa.large_string())) \
        if not pa.types.is_large_list(arr.type) else arr
    offsets = np.asarray(arr.offsets).astype(np.int64)
    lengths = (offsets[1:] - offsets[:-1]).astype(np.int32)
    n = len(arr)
    max_e = int(lengths.max()) if len(lengths) else 0
    me = _round_up_pow2(max(max_e, 1), minimum=2)
    smat, slens = _string_to_matrix(arr.values)  # flat child strings
    svalid = np.asarray(arr.values.is_valid()) if len(arr.values) \
        else np.zeros(0, bool)
    if len(smat) == 0:
        smat = np.zeros((1, 1), np.uint8)
        slens = np.zeros(1, np.int32)
        svalid = np.zeros(1, bool)
    idx = offsets[:-1, None] + np.arange(me, dtype=np.int64)[None, :]
    in_row = np.arange(me, dtype=np.int32)[None, :] < lengths[:, None]
    safe = np.clip(idx, 0, len(smat) - 1)
    cube = np.where(in_row[:, :, None], smat[safe], 0)
    ev = np.where(in_row, svalid[safe], False)
    el = np.where(in_row, slens[safe], 0).astype(np.int32)
    return cube, lengths, ev, el


def _cube_to_strlist(data: np.ndarray, lengths: np.ndarray,
                     validity: np.ndarray, ev: np.ndarray,
                     el: np.ndarray) -> pa.Array:
    """Device array<string> cube -> Arrow list<string>, vectorized:
    flatten the in-row elements to one string matrix, reuse the
    offsets-reconstruction of _matrix_to_string, and wrap with list
    offsets — no per-element Python."""
    n = len(lengths)
    if n == 0:
        return pa.array([], type=pa.list_(pa.string()))
    E = data.shape[1]
    lengths = np.minimum(lengths, E)  # clamp like _matrix_to_list
    in_row = (np.arange(E, dtype=np.int32)[None, :] < lengths[:, None]
              ) & validity[:, None]  # null rows contribute no elements
    ri, ei = np.nonzero(in_row)           # kept elements, row-major
    flat = data[ri, ei]                   # [m, B] uint8
    flens = el[ri, ei].astype(np.int32)
    fvalid = ev[ri, ei]
    values = _matrix_to_string(flat, flens, fvalid)
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(np.where(validity, lengths, 0), out=offsets[1:])
    return pa.ListArray.from_arrays(
        pa.array(offsets), values,
        mask=None if validity.all() else pa.array(~validity))


def _matrix_to_list(data: np.ndarray, lengths: np.ndarray,
                    validity: np.ndarray, ev: np.ndarray,
                    elem_dtype: DataType) -> pa.Array:
    """Device array layout -> Arrow list<primitive>."""
    n = len(lengths)
    at = to_arrow_type(elem_dtype)
    if n == 0:
        return pa.array([], type=pa.list_(at))
    me = data.shape[1]
    lengths = np.minimum(lengths.astype(np.int64), me)
    in_row = np.arange(me)[None, :] < lengths[:, None]
    flat = data[in_row]
    flat_valid = ev[in_row]
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(lengths, out=offsets[1:])
    if isinstance(elem_dtype, DecimalType):
        import decimal as _dec

        s = elem_dtype.scale
        with _dec.localcontext() as _ctx:
            _ctx.prec = 50  # scaleb rounds at context precision
            child = pa.array(
                [_dec.Decimal(int(v)).scaleb(-s) if ok else None
                 for v, ok in zip(flat, flat_valid)], type=at)
    else:
        child = pa.array(flat, type=at,
                         mask=None if flat_valid.all() else ~flat_valid)
    mask = None if validity.all() else pa.array(~validity)
    return pa.ListArray.from_arrays(pa.array(offsets, type=pa.int32()),
                                    child, mask=mask)


def _map_to_matrices(arr: pa.Array, dt):
    """Arrow map<k, v> -> (key matrix, value matrix, lengths,
    value validity) in the device padded-matrix layout."""
    offsets = np.asarray(arr.offsets).astype(np.int64)
    offsets = offsets[:len(arr) + 1]
    lengths = (offsets[1:] - offsets[:-1]).astype(np.int32)
    n = len(arr)
    max_len = int(lengths.max()) if len(lengths) else 0
    me = _round_up_pow2(max(max_len, 1), minimum=4)
    kvals, _ = _primitive_np(arr.keys, dt.keyType)
    vvals, vvalid = _primitive_np(arr.items, dt.valueType)
    if len(kvals) == 0:
        kvals = np.zeros(1, dtype=dt.keyType.np_dtype)
        vvals = np.zeros(1, dtype=dt.valueType.np_dtype)
        vvalid = np.zeros(1, dtype=np.bool_)
    idx = offsets[:-1, None] + np.arange(me, dtype=np.int64)[None, :]
    in_row = np.arange(me, dtype=np.int32)[None, :] < lengths[:, None]
    safe = np.clip(idx, 0, len(kvals) - 1)
    kmat = np.where(in_row, kvals[safe], 0).astype(dt.keyType.np_dtype)
    vmat = np.where(in_row, vvals[safe], 0).astype(
        dt.valueType.np_dtype)
    ev = np.where(in_row, vvalid[safe], False)
    return kmat, vmat, lengths, ev


def _matrices_to_map(kmat: np.ndarray, vmat: np.ndarray,
                     lengths: np.ndarray, validity: np.ndarray,
                     vvalid: np.ndarray, dt) -> pa.Array:
    """Device map layout -> Arrow map array."""
    at = to_arrow_type(dt)
    n = len(lengths)
    if n == 0:
        return pa.array([], type=at)
    me = kmat.shape[1]
    lengths = np.minimum(lengths.astype(np.int64), me)
    in_row = np.arange(me)[None, :] < lengths[:, None]
    flat_k = kmat[in_row]
    flat_v = vmat[in_row]
    flat_vv = vvalid[in_row]
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(lengths, out=offsets[1:])

    def child_array(flat, t, target_type, mask):
        if isinstance(t, DecimalType):
            import decimal as _dec

            with _dec.localcontext() as _ctx:
                _ctx.prec = 50
                return pa.array(
                    [_dec.Decimal(int(v)).scaleb(-t.scale)
                     if ok else None
                     for v, ok in zip(flat, (np.ones(len(flat), bool)
                                             if mask is None else mask))],
                    type=target_type)
        return pa.array(flat, type=target_type,
                        mask=None if mask is None or mask.all()
                        else ~mask)

    keys = child_array(flat_k, dt.keyType, at.key_type, None)
    items = child_array(flat_v, dt.valueType, at.item_type, flat_vv)
    mask = None if validity.all() else pa.array(~validity)
    if mask is not None:
        # MapArray.from_arrays has no mask param in older pyarrow;
        # compose via null substitution
        m = pa.MapArray.from_arrays(pa.array(offsets, type=pa.int32()),
                                    keys, items)
        return pa.compute.if_else(pa.array(validity), m,
                                  pa.nulls(n, at))
    return pa.MapArray.from_arrays(pa.array(offsets, type=pa.int32()),
                                   keys, items)


def _primitive_np(arr: pa.Array, dtype: DataType):
    """Arrow primitive array -> (np values with nulls zero-filled, validity)."""
    validity = np.asarray(arr.is_valid())
    at = arr.type
    if pa.types.is_decimal(at):
        # 16-byte little-endian two's complement words from the
        # decimal128 buffer directly (vectorized). precision<=18: the
        # low word IS the value (DECIMAL64); wider: [n, 2] (hi, lo)
        # limb matrix (the device DECIMAL128 layout, ops/decimal128.py).
        arr128 = arr.cast(pa.decimal128(38, at.scale))
        buf = arr128.buffers()[1]
        words = np.frombuffer(buf, dtype=np.int64,
                              count=(arr128.offset + len(arr128)) * 2)
        words = words[arr128.offset * 2:(arr128.offset + len(arr128)) * 2]
        lo = words[0::2].copy()
        if isinstance(dtype, DecimalType) and \
                dtype.precision > DecimalType.MAX_LONG_DIGITS:
            hi = words[1::2].copy()
            lo[~validity] = 0
            hi[~validity] = 0
            return np.stack([hi, lo], axis=1), validity
        lo[~validity] = 0
        return lo, validity
    if pa.types.is_timestamp(at):
        arr = arr.cast(pa.timestamp("us", tz=getattr(at, "tz", None) or "UTC"))
        vals = np.asarray(arr.cast(pa.int64()).fill_null(0))
        return vals.astype(np.int64), validity
    if pa.types.is_date32(at):
        vals = np.asarray(arr.cast(pa.int32()).fill_null(0))
        return vals.astype(np.int32), validity
    if pa.types.is_boolean(at):
        vals = np.asarray(arr.fill_null(False))
        return vals.astype(np.bool_), validity
    fill = arr.type
    zero = 0
    vals = np.asarray(arr.fill_null(zero))
    return vals.astype(dtype.np_dtype), validity


def column_from_arrow(arr, field, cap: int,
                      string_pad_min: int = 8) -> DeviceColumn:
    """One pyarrow array -> one capacity-padded host-numpy DeviceColumn
    (shared by arrow_to_device and the fused executor's narrowed
    upload). THE encoding-aware entry point for dictionary columns:
    low-cardinality strings upload as codes + a deduplicated device
    dictionary (columnar/encoding.py); everything else decodes through
    the ONE shared `encoding.dictionary_decode` so the two upload paths
    can never disagree on null handling again."""
    if pa.types.is_dictionary(arr.type):
        from spark_rapids_tpu.columnar import encoding as _enc

        enc_col = _enc.encoded_column_from_arrow(arr, field, cap)
        if enc_col is not None:
            return enc_col
        arr = _enc.dictionary_decode(arr)
    if isinstance(field.dataType, StringType):
        mat, lengths = _string_to_matrix(arr, pad_to=string_pad_min)
        validity = np.asarray(arr.is_valid())
        return make_column(field.dataType, mat, validity, cap,
                           lengths=lengths)
    if isinstance(field.dataType, ArrayType):
        if isinstance(field.dataType.elementType, StringType):
            cube, lengths, ev, el = _strlist_to_cube(arr)
            validity = np.asarray(arr.is_valid())
            return make_column(field.dataType, (cube, el), validity,
                               cap, lengths=lengths, elem_validity=ev)
        mat, lengths, ev = _list_to_matrix(
            arr, field.dataType.elementType)
        validity = np.asarray(arr.is_valid())
        return make_column(field.dataType, mat, validity, cap,
                           lengths=lengths, elem_validity=ev)
    if isinstance(field.dataType, MapType):
        kmat, vmat, lengths, vvalid = _map_to_matrices(
            arr, field.dataType)
        validity = np.asarray(arr.is_valid())
        return make_column(field.dataType, (kmat, vmat),
                           validity, cap, lengths=lengths,
                           elem_validity=vvalid)
    if isinstance(field.dataType, StructType):
        # struct-of-arrays: one child DeviceColumn per field, parent
        # validity for row nullity
        n = len(arr)
        validity = np.asarray(arr.is_valid()) if n else np.zeros(0, bool)
        vpad = np.zeros(cap, dtype=np.bool_)
        vpad[:n] = validity
        kids = [
            column_from_arrow(
                arr.field(i) if n else pa.array(
                    [], type=to_arrow_type(f.dataType)),
                f, cap, string_pad_min)
            for i, f in enumerate(field.dataType.fields)]
        return DeviceColumn(field.dataType, np.zeros(cap, np.int8),
                            vpad, children=kids)
    vals, validity = _primitive_np(arr, field.dataType)
    return make_column(field.dataType, vals, validity, cap)


def arrow_to_device(table, capacity: Optional[int] = None,
                    string_pad_min: int = 8) -> ColumnBatch:
    """pyarrow Table/RecordBatch -> device ColumnBatch."""
    if isinstance(table, pa.RecordBatch):
        table = pa.Table.from_batches([table])
    table = table.combine_chunks()
    n = table.num_rows
    cap = capacity or next_capacity(n)
    schema = schema_from_arrow(table.schema)
    cols: List[DeviceColumn] = []
    for i, field in enumerate(schema.fields):
        col = table.column(i)
        arr = (col.chunk(0) if col.num_chunks else
               pa.array([], type=table.schema.field(i).type))
        cols.append(column_from_arrow(arr, field, cap, string_pad_min))
    # ONE transfer for the whole batch instead of one per array
    # (make_column returns numpy-backed columns; the per-transfer
    # set-up cost on a chip is not measured). The staging
    # bytes ride the pinned transfer budget (runtime/host_alloc.py,
    # PinnedMemoryPool role). device_put dispatches asynchronously, so
    # the scope bounds concurrent DISPATCHES, not completion — syncing
    # here would serialize the upload pipeline.
    from spark_rapids_tpu.obs import telemetry
    from spark_rapids_tpu.runtime import host_alloc

    nbytes = sum(c.device_size_bytes() for c in cols)
    with host_alloc.get().reserved(nbytes, pinned=True):
        t0 = time.monotonic_ns()
        out = jax.device_put(ColumnBatch(schema, cols, n))
        # ns covers the DISPATCH only (device_put is async by design
        # here) — bytes are exact, per-site GB/s is an upper bound
        telemetry.record("h2d", "upload.arrow", nbytes,
                         ns=time.monotonic_ns() - t0)
    out._host_rows = n  # pytree flatten devicified num_rows; keep the
    # known count so the first row_count() is not a device roundtrip
    return out


def _attached_dict_bytes(batch: ColumnBatch) -> int:
    """Bytes of the DISTINCT dictionaries riding a batch's encoded
    columns — they cross the link with the batch pytree, so D2H
    accounting must include them (once per distinct dictionary)."""
    seen = {}
    for c in batch.columns:
        dd = getattr(c, "encoding", None)
        if dd is not None:
            seen[dd.dict_id] = dd.size_bytes()
    return sum(seen.values())


def device_to_arrow(batch: ColumnBatch,
                    encoded: bool = False) -> pa.Table:
    """Device ColumnBatch -> pyarrow Table (device->host boundary).

    Slices to the smallest capacity bucket ON DEVICE before the D2H
    copy: operators hand back full-capacity buffers (an aggregate over
    a 4M-row batch returns a 4M-capacity result holding 2K groups), and
    fetching dead capacity is D2H time spent on nothing.

    Encoded columns fetch as CODES + their (small) dictionary and
    decode host-side — the link never carries decoded strings. With
    `encoded=True` (the shuffle write path) the arrow output keeps them
    as DictionaryArrays, so shuffle blocks carry codes + a per-block
    dictionary reference instead of decoded values."""
    n = batch.row_count()
    small = next_capacity(n)
    if small < batch.capacity:
        batch = ColumnBatch(
            batch.schema,
            [c.truncate(small) for c in batch.columns],
            n)
    from spark_rapids_tpu.obs import telemetry
    from spark_rapids_tpu.runtime import host_alloc

    nbytes = batch.device_size_bytes() + _attached_dict_bytes(batch)
    with host_alloc.get().reserved(nbytes, pinned=True):
        t0 = time.monotonic_ns()
        host = jax.device_get(batch)
        telemetry.record("d2h", "collect", nbytes,
                         ns=time.monotonic_ns() - t0)
    return _host_batch_to_arrow(batch.schema, host.columns, n,
                                encoded=encoded)


def device_to_arrow_fused(batch: ColumnBatch, extra):
    """Single-sync D2H variant: fetches (batch, extra) in ONE
    device_get — no row_count pre-sync, no on-device slice; the row
    count rides along and slicing happens host-side. The bet is that
    the dead-capacity bytes of a small result cost less than the two
    extra round trips the standard path pays; PERF.md has the round
    trip and D2H rate measured on a v5e, and whether the bet still
    holds there is not measured. Callers should keep the standard
    `device_to_arrow` for large-capacity results.

    Returns (table, host_extra)."""
    from spark_rapids_tpu.obs import telemetry
    from spark_rapids_tpu.runtime import host_alloc

    nbytes = batch.device_size_bytes() + _attached_dict_bytes(batch)
    with host_alloc.get().reserved(nbytes, pinned=True):
        t0 = time.monotonic_ns()
        host, host_extra = jax.device_get((batch, extra))
        telemetry.record("d2h", "collect.fused", nbytes,
                         ns=time.monotonic_ns() - t0)
    n = int(np.asarray(host.num_rows))
    return _host_batch_to_arrow(host.schema, host.columns, n), host_extra


def _host_batch_to_arrow(schema, host_columns, n: int,
                         encoded: bool = False) -> pa.Table:
    arrays = []
    names = []
    for field, col in zip(schema.fields, host_columns):
        names.append(field.name)
        arrays.append(_host_column_to_array(field, col, n,
                                            encoded=encoded))
    return pa.Table.from_arrays(arrays, names=names)


def _host_column_to_array(field, col, n: int,
                          encoded: bool = False) -> pa.Array:
    validity = np.asarray(col.validity[:n])
    if getattr(col, "encoding", None) is not None:
        # encoded column: the fetched leaves are [n] codes plus the
        # shared dictionary — decode host-side (a numpy gather), or
        # keep the DictionaryArray for the shuffle wire
        dd = col.encoding
        ddata = np.asarray(dd.data)
        dlens = np.asarray(dd.lengths)
        k = max(ddata.shape[0], 1)
        codes = np.clip(np.asarray(col.data[:n]).astype(np.int64),
                        0, k - 1)
        if encoded:
            from spark_rapids_tpu.columnar import encoding as _enc

            values = _enc.dictionary_values(dd.dict_id)
            if values is None:
                values = _matrix_to_string(ddata, dlens,
                                           np.ones(len(dlens), bool))
            idx = pa.array(codes.astype(np.int32),
                           mask=None if validity.all() else ~validity)
            return pa.DictionaryArray.from_arrays(idx, values)
        return _matrix_to_string(
            ddata[codes], np.where(validity, dlens[codes], 0),
            validity)
    if isinstance(field.dataType, StructType):
        if not field.dataType.fields:  # struct() with no fields
            return pa.array(
                [{} if ok else None for ok in validity],
                type=pa.struct([]))
        kids = [_host_column_to_array(f, kid, n)
                for f, kid in zip(field.dataType.fields, col.children)]
        return pa.StructArray.from_arrays(
            kids,
            fields=[pa.field(f.name, to_arrow_type(f.dataType),
                             f.nullable)
                    for f in field.dataType.fields],
            mask=None if validity.all() else pa.array(~validity))
    if isinstance(field.dataType, StringType):
        return _matrix_to_string(
            np.asarray(col.data[:n]), np.asarray(col.lengths[:n]),
            validity)
    if isinstance(field.dataType, MapType):
        return _matrices_to_map(
            np.asarray(col.data[:n]),
            np.asarray(col.map_values[:n]),
            np.asarray(col.lengths[:n]), validity,
            np.asarray(col.elem_validity[:n]), field.dataType)
    if isinstance(field.dataType, ArrayType):
        if isinstance(field.dataType.elementType, StringType):
            return _cube_to_strlist(
                np.asarray(col.data[:n]), np.asarray(col.lengths[:n]),
                validity, np.asarray(col.elem_validity[:n]),
                np.asarray(col.elem_lengths[:n]))
        return _matrix_to_list(
            np.asarray(col.data[:n]), np.asarray(col.lengths[:n]),
            validity, np.asarray(col.elem_validity[:n]),
            field.dataType.elementType)
    vals = np.asarray(col.data[:n])
    at = to_arrow_type(field.dataType)
    if isinstance(field.dataType, DecimalType):
        import decimal as _dec
        s = field.dataType.scale
        # scaleb rounds at context precision (default 28 digits —
        # it would corrupt 29+ digit DECIMAL128 values)
        with _dec.localcontext() as _ctx:
            _ctx.prec = 50
            if vals.ndim == 2:  # DECIMAL128 limb matrix (hi, lo)
                py = []
                for (h, lo_), ok in zip(vals, validity):
                    if not ok:
                        py.append(None)
                        continue
                    v = (int(h) << 64) | (int(lo_) & ((1 << 64) - 1))
                    v &= (1 << 128) - 1
                    if v >= 1 << 127:
                        v -= 1 << 128
                    py.append(_dec.Decimal(v).scaleb(-s))
            else:
                py = [
                    _dec.Decimal(int(v)).scaleb(-s) if ok else None
                    for v, ok in zip(vals, validity)
                ]
        return pa.array(py, type=at)
    mask = None if validity.all() else ~validity
    if pa.types.is_timestamp(at):
        arr = pa.array(vals.astype(np.int64), type=pa.int64(), mask=mask)
        return arr.cast(at)
    if pa.types.is_date32(at):
        arr = pa.array(vals.astype(np.int32), type=pa.int32(), mask=mask)
        return arr.cast(at)
    return pa.array(vals, type=at, mask=mask)


def arrow_to_pandas(table: pa.Table):
    return table.to_pandas(types_mapper=None)
