"""Dictionary-encoded device columns — compressed execution.

The round-5 review measured a hot query at roofline_frac ~ 0.006;
one lever is to move fewer bytes by executing over compressed,
device-resident data ("GPU Acceleration of
SQL Analytics on Compressed Data", PAPERS.md). This module makes
dictionary encoding a first-class device representation:

- A `DeviceColumn` whose `encoding` slot holds a `DeviceDictionary` is
  ENCODED: `data` is a [cap] vector of narrow integer codes and the
  dictionary itself (a padded string byte-matrix + lengths) lives in a
  separate, deduplicated device allocation. The link carries codes
  (2-4 B/row) instead of padded value bytes; a 2000-entry string
  dimension crosses once as a dictionary, not 36M decoded rows.
- Dictionaries are interned by CONTENT: the same parquet dictionary
  appearing in many row groups / shuffle blocks maps to one `dict_id`
  (a content digest, stable across processes) and one device upload,
  charged to the SpillCatalog's reservation ledger.
- Decode is DEFERRED to the last operator that needs materialized
  values: `decode_column` is an HBM-local gather (trace-safe), and the
  D2H collect path decodes host-side from the fetched codes+dictionary
  so the link never carries decoded strings at all.
- Operators lower onto codes where value semantics allow it:
  equality/IN/null predicates probe the host dictionary and compare
  codes (`encoded_equality`); group-by keys group on codes (interned
  dictionaries have unique values, so code equality == value
  equality) and ride the sort-free binned-aggregation path via the
  stamped [0, K) vrange; equi-join keys rewrite to `CodesOf` when both
  sides are encoded — dictionary identity is checked and a mismatched
  side RE-ENCODES through a host remap table instead of decoding.

Null handling is normalized at intern time (the one dictionary-null
discipline both upload paths share): a null VALUE inside the arrow
dictionary folds into row validity, and duplicate values collapse to
one canonical code — so code comparisons are always value-exact.
"""

from __future__ import annotations

import hashlib
import os
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from spark_rapids_tpu.expr.core import Expression
from spark_rapids_tpu.sqltypes import StringType
from spark_rapids_tpu.sqltypes.datatypes import integer as _int_type

#: codes narrower than this dictionary size ship as int16
_INT16_MAX_K = 1 << 15
#: host-side dictionaries retained for predicate probes / remaps
_HOST_KEEP = 512


class DeviceDictionary:
    """Device-resident dictionary shared by every column encoded with
    it: `data` [K, max_bytes] uint8 padded value matrix, `lengths` [K]
    int32. `dict_id` (the content digest) rides in the pytree aux, so
    jax retraces — and the fused engine re-keys — per distinct
    dictionary, which is what makes trace-time host probes of the
    dictionary safe to bake into compiled programs."""

    __slots__ = ("data", "lengths", "dict_id")

    def __init__(self, data, lengths, dict_id: str):
        self.data = data
        self.lengths = lengths
        self.dict_id = dict_id

    @property
    def num_values(self) -> int:
        return int(self.data.shape[0])

    def size_bytes(self) -> int:
        return (self.data.size * self.data.dtype.itemsize
                + self.lengths.size * 4)

    def _tree_flatten(self):
        return (self.data, self.lengths), self.dict_id

    @classmethod
    def _tree_unflatten(cls, dict_id, children):
        data, lengths = children
        return cls(data, lengths, dict_id)


jax.tree_util.register_pytree_node(
    DeviceDictionary,
    lambda d: d._tree_flatten(),
    DeviceDictionary._tree_unflatten,
)


class _HostDict:
    """Host-side view of one interned dictionary: the padded matrix the
    device copy was built from, the value->code index for predicate
    probes, and the canonical pyarrow values for re-emitting
    DictionaryArrays at the shuffle boundary."""

    __slots__ = ("matrix", "lengths", "values", "index", "nbytes")

    def __init__(self, matrix: np.ndarray, lengths: np.ndarray,
                 values: pa.Array):
        self.matrix = matrix
        self.lengths = lengths
        self.values = values
        self.index: Dict[str, int] = {
            v: i for i, v in enumerate(values.to_pylist())}
        self.nbytes = matrix.nbytes + lengths.nbytes


_lock = threading.Lock()
_host_dicts: "OrderedDict[str, _HostDict]" = OrderedDict()
_device_dicts: "OrderedDict[str, Tuple[DeviceDictionary, int]]" = \
    OrderedDict()
_device_pid: Optional[int] = None


def enabled() -> bool:
    """spark.rapids.tpu.encoded.enabled of the active session (default
    on; sessionless callers — tests driving the bridge directly — get
    the default)."""
    from spark_rapids_tpu.config import rapids_conf as rc

    try:
        from spark_rapids_tpu.api.session import TpuSparkSession

        s = TpuSparkSession.active()
        if s is not None:
            return bool(s.rapids_conf.get(rc.ENCODED_ENABLED))
    except Exception:
        pass
    return bool(rc.ENCODED_ENABLED.default)


def _conf_int(entry) -> int:
    try:
        from spark_rapids_tpu.api.session import TpuSparkSession

        s = TpuSparkSession.active()
        if s is not None:
            return int(s.rapids_conf.get(entry))
    except Exception:
        pass
    return int(entry.default)


def max_dictionary_rows() -> int:
    from spark_rapids_tpu.config import rapids_conf as rc

    return _conf_int(rc.ENCODED_MAX_DICT_ROWS)


def dictionary_decode(arr: pa.Array) -> pa.Array:
    """THE host-side dictionary decode both upload paths share
    (arrow_bridge.column_from_arrow and fused.upload_narrowed used to
    carry their own copies): index-nulls AND null values inside the
    dictionary both land as result nulls, one discipline for both."""
    if pa.types.is_dictionary(arr.type):
        arr = arr.dictionary_decode()
    return arr


# ------------------------------------------------------------- interning

def _digest(values: pa.Array) -> str:
    h = hashlib.sha1()
    for v in values.to_pylist():
        if v is None:
            h.update(b"\x01N")
        else:
            b = v.encode("utf-8")
            h.update(len(b).to_bytes(4, "little"))
            h.update(b)
    return h.hexdigest()[:20]


def intern_dictionary(values: pa.Array
                      ) -> Tuple[str, Optional[np.ndarray]]:
    """Intern one arrow dictionary VALUES array; returns (dict_id,
    remap) where remap maps raw code -> canonical code (-1 for codes
    whose value is null), or None when the dictionary was already
    canonical (unique, no nulls). Canonicalization is what makes code
    equality == value equality everywhere downstream."""
    pv = values.to_pylist()
    seen: Dict[str, int] = {}
    canon: List[str] = []
    remap = np.empty(max(len(pv), 1), dtype=np.int32)
    dirty = False
    for i, v in enumerate(pv):
        if v is None:
            remap[i] = -1
            dirty = True
            continue
        j = seen.get(v)
        if j is None:
            j = seen[v] = len(canon)
            canon.append(v)
        else:
            dirty = True
        remap[i] = j
    cvals = pa.array(canon, type=pa.large_string())
    dict_id = _digest(cvals)
    with _lock:
        hd = _host_dicts.get(dict_id)
    if hd is None:
        from spark_rapids_tpu.columnar.arrow_bridge import \
            _string_to_matrix

        if len(cvals):
            matrix, lengths = _string_to_matrix(cvals)
        else:
            # empty dictionary: one zero row keeps decode gathers and
            # program shapes well-formed (no code ever references it)
            matrix = np.zeros((1, 8), np.uint8)
            lengths = np.zeros(1, np.int32)
        hd = _HostDict(matrix, lengths, cvals)
        with _lock:
            _host_dicts[dict_id] = hd
            _host_dicts.move_to_end(dict_id)
            while len(_host_dicts) > _HOST_KEEP:
                _host_dicts.popitem(last=False)
    return dict_id, (remap[:len(pv)] if dirty else None)


def _host_dict(dict_id: str) -> Optional[_HostDict]:
    with _lock:
        hd = _host_dicts.get(dict_id)
        if hd is not None:
            _host_dicts.move_to_end(dict_id)
        return hd


def device_dictionary(dict_id: str) -> Optional[DeviceDictionary]:
    """Device copy of an interned dictionary, uploaded ONCE per
    distinct content and charged to the SpillCatalog's reservation
    ledger; returns None (caller falls back to decoded upload) when
    the dictionary is unknown or the reservation fails."""
    global _device_pid
    from spark_rapids_tpu.config import rapids_conf as rc
    from spark_rapids_tpu.obs import telemetry
    from spark_rapids_tpu.runtime.errors import (
        TpuRetryOOM,
        TpuSplitAndRetryOOM,
    )
    from spark_rapids_tpu.runtime.memory import get_catalog

    pid = os.getpid()
    with _lock:
        if _device_pid != pid:
            # forked worker: inherited device arrays/reservations
            # belong to the parent — start a fresh cache (same rule as
            # the obs bus post-fork reinstall)
            _device_dicts.clear()
            _device_pid = pid
        cached = _device_dicts.get(dict_id)
        if cached is not None:
            _device_dicts.move_to_end(dict_id)
            return cached[0]
    hd = _host_dict(dict_id)
    if hd is None:
        return None
    nbytes = hd.nbytes
    catalog = get_catalog()
    try:
        catalog.reserve(nbytes, tag="encoded.dict", query_id=0)
    except (TpuRetryOOM, TpuSplitAndRetryOOM):
        return None
    dd = DeviceDictionary(
        telemetry.ledgered_put(jnp.asarray(hd.matrix),
                               "encoded.dictUpload"),
        jnp.asarray(hd.lengths), dict_id)
    budget = _conf_int(rc.ENCODED_DICT_CACHE_BYTES)
    with _lock:
        _device_dicts[dict_id] = (dd, nbytes)
        _device_dicts.move_to_end(dict_id)
        total = sum(b for _, b in _device_dicts.values())
        while total > budget and len(_device_dicts) > 1:
            _, (_, old_bytes) = _device_dicts.popitem(last=False)
            catalog.release(old_bytes, query_id=0)
            total -= old_bytes
    return dd


def dictionary_values(dict_id: str) -> Optional[pa.Array]:
    hd = _host_dict(dict_id)
    return None if hd is None else hd.values


def probe_code(dict_id: str, value: Optional[str]) -> Optional[int]:
    """Host-side dictionary probe: the canonical code of `value`, or
    None when the value is absent (or null, or the dictionary is no
    longer retained)."""
    if value is None:
        return None
    hd = _host_dict(dict_id)
    if hd is None:
        return None
    return hd.index.get(value)


def remap_table(src_id: str, dst_id: str) -> Optional[np.ndarray]:
    """[K_src] int32 mapping src code -> dst code (-1 when the value is
    absent from dst) — the re-encode fallback for joins over
    identity-mismatched dictionaries."""
    if src_id == dst_id:
        return None
    src = _host_dict(src_id)
    dst = _host_dict(dst_id)
    if src is None or dst is None:
        return None
    out = np.full(max(len(src.index), 1), -1, dtype=np.int32)
    for v, c in src.index.items():
        out[c] = dst.index.get(v, -1)
    return out


# --------------------------------------------------- column construction

def encoded_column_from_arrow(arr: pa.Array, field, cap: int):
    """pa.DictionaryArray -> encoded DeviceColumn (numpy code leaves,
    device dictionary handle), or None when encoding does not apply
    (non-string values, disabled, oversized dictionary, failed device
    reservation) — the caller then decodes through
    `dictionary_decode` and uploads plain."""
    if not isinstance(field.dataType, StringType):
        return None
    if not enabled():
        return None
    values = arr.dictionary
    if len(values) > max_dictionary_rows():
        return None
    dict_id, remap = intern_dictionary(values)
    dd = device_dictionary(dict_id)
    if dd is None:
        return None
    n = len(arr)
    validity = np.asarray(arr.is_valid()) if n else np.zeros(0, bool)
    idx = arr.indices
    codes = (np.asarray(idx.fill_null(0)).astype(np.int64) if n
             else np.zeros(0, np.int64))
    if remap is not None and n:
        codes = remap[np.clip(codes, 0, len(remap) - 1)].astype(np.int64)
        validity = validity & (codes >= 0)
        codes = np.where(codes >= 0, codes, 0)
    k = dd.num_values
    code_dt = np.int16 if k < _INT16_MAX_K else np.int32
    data = np.zeros(cap, dtype=code_dt)
    data[:n] = codes.astype(code_dt)
    vpad = np.zeros(cap, dtype=np.bool_)
    vpad[:n] = validity
    from spark_rapids_tpu.columnar.batch import DeviceColumn

    col = DeviceColumn(field.dataType, data, vpad,
                       vrange=(0, max(k - 1, 0)), encoding=dd)
    # savings ledger: what the padded-matrix upload WOULD have moved
    # vs what the codes move (the dictionary itself is ledgered once
    # at its own upload)
    hd = _host_dict(dict_id)
    if hd is not None:
        from spark_rapids_tpu.obs import telemetry

        plain = cap * (hd.matrix.shape[1] + 4 + 1)
        actual = data.nbytes + vpad.nbytes
        telemetry.record_encoded("scan.encode", actual, plain)
    return col


# --------------------------------------------------------------- decode

def decode_column(col):
    """Encoded column -> standard padded-matrix string column via an
    HBM-local dictionary gather. Trace-safe; identity for plain
    columns. This is the ONE in-device decode point — operators that
    cannot run on codes route through it."""
    dd = getattr(col, "encoding", None)
    if dd is None:
        return col
    k = dd.data.shape[0]
    codes = jnp.clip(col.data.astype(jnp.int32), 0, max(k - 1, 0))
    data = jnp.take(dd.data, codes, axis=0)
    lengths = jnp.take(dd.lengths, codes)
    # keep the zero-padding / zero-dead-rows invariants of the plain
    # string layout
    data = jnp.where(col.validity[:, None], data, 0)
    lengths = jnp.where(col.validity, lengths, 0)
    return col.replace(data=data, lengths=lengths, vrange=None,
                       encoding=None)


def align_encodings(cols):
    """Pre-concat normalization: keep the encoded representation only
    when EVERY piece is encoded with the SAME dictionary; any identity
    mismatch decodes all pieces (code spaces are not comparable across
    dictionaries)."""
    encs = [getattr(c, "encoding", None) for c in cols]
    if all(e is None for e in encs):
        return list(cols)
    if all(e is not None for e in encs) and \
            len({e.dict_id for e in encs}) == 1:
        return list(cols)
    return [decode_column(c) for c in cols]


def encoding_key(obj) -> tuple:
    """Per-column dictionary identities of a ColumnBatch (or a
    BuildTable / BuildIndex wrapping one) — the fused engine folds this
    into its program keys so the program cache never serves a program
    whose baked host probes belong to a different dictionary."""
    cols = getattr(obj, "columns", None)
    if cols is None:
        b = getattr(obj, "batch", None)
        cols = getattr(b, "columns", None)
    if cols is None:
        return ()
    return tuple(
        e.dict_id if (e := getattr(c, "encoding", None)) is not None
        else None
        for c in cols)


# ------------------------------------------- expression-level lowerings

def raw_column(expr, ctx):
    """The UNDECODED batch column behind a (possibly Alias-wrapped)
    BoundReference, or None when the expression is anything else."""
    from spark_rapids_tpu.expr.core import Alias, BoundReference

    if isinstance(expr, Alias):
        expr = expr.children[0]
    if isinstance(expr, BoundReference):
        return ctx.batch.columns[expr.ordinal]
    return None


def eval_preserving(expr, ctx):
    """Evaluate an expression, passing encoded columns through
    UNdecoded when the expression is a bare (aliased) column reference
    — the projection/grouping fast path that keeps codes flowing to
    the operators that can use them."""
    col = raw_column(expr, ctx)
    if col is not None and getattr(col, "encoding", None) is not None:
        return col
    return expr.eval(ctx)


def encoded_equality(left, right, ctx):
    """EqualTo fast path: `<encoded column> = <string literal>` (either
    side) compares CODES against one host-probed code — no decode, no
    byte-matrix comparison. Returns the boolean result column, or None
    when the shape doesn't apply."""
    from spark_rapids_tpu.expr.core import Literal
    from spark_rapids_tpu.sqltypes.datatypes import boolean

    ref, lit = left, right
    if isinstance(ref, Literal):
        ref, lit = right, left
    if not isinstance(lit, Literal) or not isinstance(lit.dtype,
                                                      StringType):
        return None
    col = raw_column(ref, ctx)
    if col is None:
        return None
    dd = getattr(col, "encoding", None)
    if dd is None:
        return None
    from spark_rapids_tpu.columnar.batch import DeviceColumn

    cap = col.capacity
    if lit.value is None:
        # `x = NULL` is null for every row
        return DeviceColumn(boolean, jnp.zeros((cap,), bool),
                            jnp.zeros((cap,), bool))
    code = probe_code(dd.dict_id, lit.value)
    if code is None:
        eq = jnp.zeros((cap,), bool)
    else:
        eq = col.data.astype(jnp.int32) == jnp.int32(code)
    return DeviceColumn(boolean, eq, col.validity)


class CodesOf(Expression):
    """Join-key lowering over an encoded column: evaluates to the
    column's integer CODES re-encoded into `dict_id`'s code space.
    Identity match is a free cast; a mismatched dictionary gathers
    through a host remap table (absent values -> -1, which matches no
    canonical code). Only valid over a BoundReference whose column is
    encoded — the caller (`_encoded_key_rewrite`) checks that before
    rewriting."""

    def __init__(self, child, dict_id: str):
        super().__init__([child])
        self.dict_id = dict_id

    @property
    def dtype(self):
        return _int_type

    @property
    def nullable(self):
        return self.children[0].nullable

    def key(self):
        return ("codesof", self.children[0].key(), self.dict_id)

    def eval(self, ctx):
        from spark_rapids_tpu.columnar.batch import DeviceColumn

        col = raw_column(self.children[0], ctx)
        dd = None if col is None else getattr(col, "encoding", None)
        if dd is None:
            raise TypeError(
                "CodesOf over a non-encoded column — the encoded join "
                "rewrite must only fire when both key columns carry "
                "dictionaries")
        codes = col.data.astype(jnp.int32)
        if dd.dict_id != self.dict_id:
            table = remap_table(dd.dict_id, self.dict_id)
            if table is None:
                raise TypeError(
                    f"no remap from dictionary {dd.dict_id} to "
                    f"{self.dict_id} (host dictionary evicted)")
            codes = jnp.take(jnp.asarray(table),
                             jnp.clip(codes, 0, table.shape[0] - 1))
        return DeviceColumn(_int_type, codes, col.validity)


def invalidate_device_cache() -> int:
    """Device-loss recovery hook (runtime/device_monitor.py): every
    cached DeviceDictionary was uploaded to the backend recovery just
    tore down — drop the device cache and release its catalog
    reservations. HOST dictionaries survive: the next
    `device_dictionary(dict_id)` call re-uploads the same content into
    the fresh backend (encoded columns re-intern lazily, like the warm
    executables). Returns how many device entries were dropped."""
    from spark_rapids_tpu.runtime.memory import _catalog

    with _lock:
        dev = list(_device_dicts.values())
        _device_dicts.clear()
    if _catalog is not None:
        for _, nbytes in dev:
            _catalog.release(nbytes, query_id=0)
    return len(dev)


def clear_for_tests() -> None:
    """Drop every interned dictionary (host + device) and release the
    device cache's catalog reservations — test isolation only."""
    from spark_rapids_tpu.runtime.memory import get_catalog

    with _lock:
        dev = list(_device_dicts.values())
        _device_dicts.clear()
        _host_dicts.clear()
    catalog = get_catalog()
    for _, nbytes in dev:
        catalog.release(nbytes, query_id=0)
