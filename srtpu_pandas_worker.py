"""Worker-process entry for the pandas-UDF Arrow exchange
(spark_rapids_tpu/udf/pandas_udf.py).

Deliberately a TOP-LEVEL module with only pyarrow/cloudpickle imports:
worker processes unpickle functions by module reference, and importing
the spark_rapids_tpu package would initialize the JAX backend inside
every worker (slow, and on a TPU machine fatal: a chip belongs to the
one process that holds it). The reference keeps its Python workers
equally minimal (python/rapids/worker.py) for the same reason.
"""

from __future__ import annotations

import pyarrow as pa


def ipc_bytes(table: pa.Table) -> bytes:
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, table.schema) as w:
        w.write_table(table)
    return sink.getvalue().to_pybytes()


def ipc_table(data: bytes) -> pa.Table:
    with pa.ipc.open_stream(pa.py_buffer(data)) as r:
        return r.read_all()


def worker_apply(fn_bytes: bytes, payload: bytes,
                 schema_blob: bytes) -> bytes:
    """Arrow in, pandas apply, Arrow out."""
    import cloudpickle

    fn = cloudpickle.loads(fn_bytes)
    table = ipc_table(payload)
    series = [table.column(i).to_pandas()
              for i in range(table.num_columns)]
    result = fn(*series)
    out_type = pa.ipc.read_schema(
        pa.py_buffer(schema_blob)).field(0).type
    arr = pa.Array.from_pandas(result, type=out_type)
    return ipc_bytes(pa.table({"r": arr}))


def _df_of(table: pa.Table):
    return table.to_pandas()


def _table_of(df, schema_blob: bytes) -> pa.Table:
    schema = pa.ipc.read_schema(pa.py_buffer(schema_blob))
    cols = []
    for f in schema:
        if f.name not in df.columns:
            raise ValueError(
                f"pandas function result is missing column {f.name!r}; "
                f"got {list(df.columns)}")
        cols.append(pa.Array.from_pandas(df[f.name], type=f.type))
    return pa.Table.from_arrays(cols, schema=schema)


def worker_apply_df(fn_bytes: bytes, payload: bytes,
                    schema_blob: bytes) -> bytes:
    """pandas.DataFrame -> pandas.DataFrame function (applyInPandas /
    mapInPandas worker side)."""
    import cloudpickle

    fn = cloudpickle.loads(fn_bytes)
    out = fn(_df_of(ipc_table(payload)))
    return ipc_bytes(_table_of(out, schema_blob))


def worker_apply_cogroup(fn_bytes: bytes, payload_l: bytes,
                         payload_r: bytes, schema_blob: bytes) -> bytes:
    """(left_df, right_df) -> pandas.DataFrame (cogrouped
    applyInPandas worker side)."""
    import cloudpickle

    fn = cloudpickle.loads(fn_bytes)
    out = fn(_df_of(ipc_table(payload_l)), _df_of(ipc_table(payload_r)))
    return ipc_bytes(_table_of(out, schema_blob))


# ---------------------------------------------------------------- daemon
#
# Stdin/stdout framed-pickle server (the reference's python worker
# daemon pattern, python/rapids/daemon.py): the driver launches
# `python srtpu_pandas_worker.py serve` subprocesses directly, so no
# multiprocessing start method ever re-imports the USER's __main__
# (fork/spawn/forkserver all break unguarded user scripts).

import struct as _struct
import sys as _sys


def _read_frame(stream):
    head = stream.read(8)
    if len(head) < 8:
        return None
    (ln,) = _struct.unpack("<q", head)
    return stream.read(ln)


def _write_frame(stream, data: bytes):
    stream.write(_struct.pack("<q", len(data)))
    stream.write(data)
    stream.flush()


def serve():
    import io
    import os
    import pickle
    import traceback

    fns = {
        "worker_apply": worker_apply,
        "worker_apply_df": worker_apply_df,
        "worker_apply_cogroup": worker_apply_cogroup,
    }
    stdin = _sys.stdin.buffer
    # the framing channel owns a PRIVATE dup of fd 1; fd 1 is then
    # redirected to stderr so print() inside user UDFs cannot corrupt
    # the length-prefixed protocol
    stdout = io.FileIO(os.dup(1), "wb")
    os.dup2(2, 1)
    _sys.stdout = _sys.stderr
    while True:
        frame = _read_frame(stdin)
        if frame is None:
            return
        try:
            name, args = pickle.loads(frame)
            result = fns[name](*args)
            _write_frame(stdout, pickle.dumps(("ok", result)))
        except BaseException:
            _write_frame(stdout,
                         pickle.dumps(("err", traceback.format_exc())))


if __name__ == "__main__":
    if len(_sys.argv) > 1 and _sys.argv[1] == "serve":
        serve()
