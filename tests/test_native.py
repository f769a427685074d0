"""Native C++ runtime tests: wire format, spark-exact host hashing parity
with the device kernels, row<->column conversion, host buffer pool, and
the file-backed MULTITHREADED shuffle end to end.
"""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import native


def _require_native():
    if not native.available():
        pytest.skip("native toolchain unavailable")


def test_pack_unpack_roundtrip():
    _require_native()
    bufs = [np.arange(100, dtype=np.int64).view(np.uint8),
            np.array([], dtype=np.uint8),
            np.random.default_rng(0).integers(
                0, 255, 1000).astype(np.uint8)]
    packed = native.pack_buffers(bufs)
    out = native.unpack_buffers(packed)
    assert len(out) == 3
    for orig, got in zip(bufs, out):
        assert np.array_equal(orig.view(np.uint8).reshape(-1), got)


def test_pack_python_fallback_compatible():
    """The pure-Python pack and the native pack produce identical bytes
    (format stability across fallback)."""
    _require_native()
    bufs = [np.arange(17, dtype=np.int32).view(np.uint8),
            np.frombuffer(b"hello world", dtype=np.uint8)]
    sizes = np.array([b.nbytes for b in bufs], dtype=np.int64)
    a = native.pack_buffers(bufs)
    b = native._py_pack([b.reshape(-1) for b in bufs], sizes)
    assert np.array_equal(a, b)
    for orig, got in zip(bufs, native._py_unpack(a)):
        assert np.array_equal(orig.reshape(-1), got)


def _device_hash(table, fn_name, seed=42):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from spark_rapids_tpu.columnar import arrow_to_device
    from spark_rapids_tpu.ops import hashing

    batch = arrow_to_device(table)
    n = batch.row_count()
    if fn_name == "murmur3":
        h = hashing.murmur3_columns(batch.columns, seed)
        return np.asarray(h)[:n]
    h = hashing.xxhash64_columns(batch.columns, seed)
    return np.asarray(h).view(np.int64)[:n]


def _host_columns(table):
    cols = []
    for col in table.columns:
        arr = col.combine_chunks()
        valid = (np.ones(len(arr), dtype=np.uint8)
                 if arr.null_count == 0 else
                 np.asarray(arr.is_valid()).astype(np.uint8))
        if pa.types.is_string(arr.type):
            pys = arr.to_pylist()
            bs = [(s or "").encode() for s in pys]
            mb = max(1, max((len(b) for b in bs), default=1))
            mat = np.zeros((len(bs), mb), dtype=np.uint8)
            lens = np.zeros(len(bs), dtype=np.int32)
            for i, b in enumerate(bs):
                mat[i, :len(b)] = np.frombuffer(b, dtype=np.uint8)
                lens[i] = len(b)
            cols.append((mat, lens, valid))
        else:
            vals = np.asarray(
                arr.fill_null(0) if arr.null_count else arr)
            cols.append((vals, valid))
    return cols


@pytest.fixture
def hash_table():
    rng = np.random.default_rng(11)
    n = 500
    ints = rng.integers(-10**9, 10**9, n)
    mask = rng.random(n) < 0.1
    return pa.table({
        "a": pa.array(ints, type=pa.int64(),
                      mask=mask),
        "b": pa.array(rng.integers(-1000, 1000, n), type=pa.int32()),
        "c": pa.array(rng.random(n) * 1000 - 500, type=pa.float64()),
        "f": pa.array((rng.random(n) * 10 - 5).astype(np.float32),
                      type=pa.float32()),
        "s": pa.array([f"key-{i % 37}-{'x' * (i % 11)}"
                       for i in range(n)]),
    })


def test_native_murmur3_matches_device(hash_table):
    _require_native()
    host = native.murmur3_host(_host_columns(hash_table))
    dev = _device_hash(hash_table, "murmur3")
    assert np.array_equal(host, dev)


def test_native_xxhash64_matches_device(hash_table):
    _require_native()
    host = native.xxhash64_host(_host_columns(hash_table))
    dev = _device_hash(hash_table, "xxhash64")
    assert np.array_equal(host, dev)


def test_rows_to_columns_roundtrip():
    _require_native()
    rng = np.random.default_rng(5)
    n = 257
    cols = [
        (rng.integers(-100, 100, n).astype(np.int64),
         (rng.random(n) < 0.9)),
        (rng.random(n).astype(np.float64), None),
        (rng.integers(0, 2, n).astype(np.int8),
         (rng.random(n) < 0.8)),
    ]
    rows, stride = native.columns_to_rows(cols)
    assert rows.shape == (n, stride)
    out = native.rows_to_columns(
        rows, [np.int64, np.float64, np.int8])
    for (vals, valid), (ovals, ovalid) in zip(cols, out):
        want_valid = np.ones(n, bool) if valid is None else valid
        assert np.array_equal(ovalid, want_valid)
        assert np.array_equal(vals[want_valid], ovals[want_valid])


def test_host_buffer_pool():
    _require_native()
    pool = native.HostBufferPool(1 << 20)
    a = pool.alloc(1000)
    b = pool.alloc(2000)
    assert a is not None and b is not None
    assert pool.in_use == 3000
    pool.free(a)
    assert pool.in_use == 2000
    # freelist reuse: same-size alloc reuses the freed block
    c = pool.alloc(1000)
    assert c is not None
    assert pool.in_use == 3000
    # budget exhaustion returns None
    d = pool.alloc(2 << 20)
    assert d is None
    assert pool.peak == 3000
    pool.close()


def test_serde_roundtrip_types():
    from spark_rapids_tpu.shuffle import serde

    rng = np.random.default_rng(9)
    n = 123
    t = pa.table({
        "i": pa.array(rng.integers(-100, 100, n), type=pa.int64(),
                      mask=rng.random(n) < 0.2),
        "f": pa.array(rng.random(n), type=pa.float64()),
        "s": pa.array([None if i % 7 == 0 else f"s{i}"
                       for i in range(n)]),
        "d": pa.array(rng.integers(0, 10000, n),
                      type=pa.int32()).cast(pa.date32()),
        "b": pa.array(rng.random(n) < 0.5),
    })
    out = serde.deserialize_table(serde.serialize_table(t))
    assert out.equals(t)


def test_serde_sliced_table():
    from spark_rapids_tpu.shuffle import serde

    t = pa.table({"x": list(range(100)),
                  "s": [f"v{i}" for i in range(100)]})
    sl = t.slice(13, 40)
    out = serde.deserialize_table(serde.serialize_table(sl))
    assert out.equals(pa.table({"x": list(range(13, 53)),
                                "s": [f"v{i}" for i in range(13, 53)]}))


def test_multithreaded_shuffle_query():
    """End-to-end query through the file-backed MULTITHREADED shuffle."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.testing.asserts import (
        assert_tpu_and_cpu_are_equal_collect,
    )

    def q(s):
        df = s.createDataFrame({
            "k": [i % 13 for i in range(300)],
            "v": [float(i) for i in range(300)],
            "s": [f"name{i % 5}" for i in range(300)],
        })
        return df.groupBy("k").agg(F.sum("v").alias("sv"),
                                   F.count("*").alias("n"))

    assert_tpu_and_cpu_are_equal_collect(
        q, conf={"spark.sql.shuffle.partitions": 4,
                 "spark.rapids.shuffle.mode": "MULTITHREADED"})


def test_string_minmax_agg_falls_back():
    """String min/max aggregation is tagged to CPU (v1) but stays
    correct, including through the MULTITHREADED shuffle."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.testing.asserts import (
        assert_tpu_and_cpu_are_equal_collect,
    )

    def q(s):
        df = s.createDataFrame({
            "k": [i % 7 for i in range(100)],
            "s": [f"name{(i * 13) % 23}" for i in range(100)],
        })
        return df.groupBy("k").agg(F.max("s").alias("ms"),
                                   F.min("s").alias("mn"))

    assert_tpu_and_cpu_are_equal_collect(
        q, conf={"spark.sql.shuffle.partitions": 3,
                 "spark.rapids.shuffle.mode": "MULTITHREADED"})


def test_build_is_keyed_by_source_and_host():
    """The git-ignored build directory may travel with the tree; only a
    library built from THIS source on THIS host's CPU is loaded."""
    import os

    _require_native()
    key = native._build_key()
    assert len(key) == 16 and key == native._build_key()
    so = os.path.join(native._OUT_DIR, key, "libsparktpu.so")
    assert os.path.exists(so)
    # a stray library at the old, unkeyed path is not what gets loaded
    assert native._build() == so
    assert native.runtime_in_use() == "native"


def test_build_key_follows_the_source(tmp_path, monkeypatch):
    src = tmp_path / "rt.cpp"
    src.write_text("int a;")
    monkeypatch.setattr(native, "_SRC", str(src))
    first = native._build_key()
    src.write_text("int b;")
    assert native._build_key() != first
