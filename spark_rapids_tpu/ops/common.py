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

import contextvars
from contextlib import contextmanager
from typing import List, Optional, Tuple

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
    """Stable sort on ONE key array; -> (the key, sorted; the gather
    permutation). The sorted key is the sort's own output: a caller
    of `sort_permutation` that wants it gathers it a second time.
    Several keys never go to one `lax.sort`: `sort_permutation`."""
    keys, (perm,) = sorted_carrying(
        key_arrays, [jnp.arange(capacity, dtype=jnp.int32)], capacity)
    return keys, perm


def sorted_carrying(key_arrays: List[jnp.ndarray],
                    carried: List[jnp.ndarray], capacity: int
                    ) -> Tuple[List[jnp.ndarray], List[jnp.ndarray]]:
    """Stable sort on ONE key array that takes `carried` (arrays of its
    length) into its order as the sort's own payload operands: -> (the
    key, sorted; each carried array, sorted). A payload is not a key:
    the sort is noted as `sorted_with_permutation`'s, which carries
    the row ids."""
    (key,) = key_arrays
    note_sort("packed", 1, 8 * key.dtype.itemsize, capacity)
    out = lax.sort((key, *carried), num_keys=1, is_stable=True)
    return [out[0]], list(out[1:])


def sort_permutation(key_arrays: List[jnp.ndarray],
                     capacity: int) -> jnp.ndarray:
    """Stable multi-key sort of already-orderable arrays; returns the
    gather permutation (cuDF `Table.sortOrder` analog). Several keys
    go as stable one-key passes from the least significant key. A
    caller that has COLUMNS packs them first (`key_fields`,
    `sort_permutation_fields`): far fewer bits, far fewer passes."""
    if len(key_arrays) == 1:
        return sorted_with_permutation(key_arrays, capacity)[1]
    note_sort("passes", 1, sum(8 * k.dtype.itemsize for k in key_arrays),
              capacity, passes=len(key_arrays))
    if len({k.dtype for k in key_arrays}) == 1:
        return _sort_passes(list(key_arrays), capacity)
    # keys of several dtypes (a float among integers) cannot share one
    # loop's operand: a pass each, every one in its own dtype's order
    perm = jnp.arange(capacity, dtype=jnp.int32)
    for key in reversed(key_arrays):
        perm = lax.sort((jnp.take(key, perm), perm), num_keys=1,
                        is_stable=True)[1]
    return perm


def _sort_passes(words: List[jnp.ndarray], capacity: int) -> jnp.ndarray:
    """The permutation that sorts rows by `words` (most significant
    first, all of one dtype), as stable sorts on ONE key operand each,
    from the least significant word. The passes are the trips of one
    loop, so a program holds one sort whatever the width of its keys:
    the TPU's compiler takes 15 s and more over every sort of 32Ki
    slots or more, and minutes over one with several 64-bit key
    operands (PERF.md, PR 33)."""
    iota = jnp.arange(capacity, dtype=jnp.int32)
    if len(words) == 1:
        return lax.sort((words[0], iota), num_keys=1, is_stable=True)[1]
    stacked = jnp.stack(words[::-1])

    def one_pass(i, perm):
        key = jnp.take(lax.dynamic_index_in_dim(stacked, i, keepdims=False),
                       perm)
        return lax.sort((key, perm), num_keys=1, is_stable=True)[1]

    return lax.fori_loop(0, len(words), one_pass, iota)


# ---- packed sort keys: columns -> bit fields -> 32-bit words ----
#
# A sort or a group-by over COLUMNS knows how few bits each key needs:
# a stamped value range (the narrowed upload's `vrange`), a
# dictionary's size, a column's own width. Each column lowers to bit
# FIELDS, most significant first — (uint32 array, bits), the value
# below 2**bits and its unsigned order the SQL order — which are laid
# end to end behind one leading "dead row" bit and cut into 32-bit
# words. One word: one sort on one 32-bit operand ("packed"). More:
# one stable pass a word ("passes", `_sort_passes`).

Field = Tuple[jnp.ndarray, int]

_SORT_NOTES = contextvars.ContextVar("srtpu_sort_notes", default=None)


@contextmanager
def noting_sorts():
    """Collect how the sorts traced inside were lowered: a list of
    {"how": "packed" | "passes", "operands", "keyBits", "passes",
    "slots"} (runtime/jit_cache.py keeps it with the program,
    exec/fused.py reports it as `sort`)."""
    notes: list = []
    tok = _SORT_NOTES.set(notes)
    try:
        yield notes
    finally:
        _SORT_NOTES.reset(tok)


def note_sort(how: str, operands: int, key_bits: int, slots: int,
              passes: int = 1, by: str = "sort") -> None:
    notes = _SORT_NOTES.get()
    if notes is not None:
        notes.append({"how": how, "by": by, "operands": operands,
                      "keyBits": key_bits, "passes": passes,
                      "slots": slots})


_EXPAND_NOTES = contextvars.ContextVar("srtpu_expand_notes", default=None)


@contextmanager
def noting_expands():
    """Collect the Expands traced inside, as `noting_sorts` collects
    sorts: a list of {"sets", "inputSlots", "outputSlots"}
    (runtime/jit_cache.py keeps it with the program, exec/fused.py
    reports it as `expand`)."""
    notes: list = []
    tok = _EXPAND_NOTES.set(notes)
    try:
        yield notes
    finally:
        _EXPAND_NOTES.reset(tok)


def note_expand(sets: int, input_slots: int, output_slots: int) -> None:
    notes = _EXPAND_NOTES.get()
    if notes is not None:
        notes.append({"sets": sets, "inputSlots": input_slots,
                      "outputSlots": output_slots})


def _u32(x: jnp.ndarray) -> jnp.ndarray:
    return x.astype(jnp.uint32)


def _split_i64(key: jnp.ndarray) -> List[Field]:
    """A signed-orderable int64 key as two 32-bit fields."""
    hi = (key >> 32) + jnp.int64(1 << 31)
    return [(_u32(hi), 32), (_u32(key & jnp.int64(0xFFFFFFFF)), 32)]


def _dictionary_ranks(dd) -> Optional[jnp.ndarray]:
    """code -> rank of its value in byte order (equal values, equal
    ranks), from the host's copy of the dictionary while the program
    is traced (the dictionary's identity is in every program key), or
    None where the host no longer holds it."""
    import numpy as np

    from spark_rapids_tpu.columnar import encoding as _enc

    values = _enc.dictionary_values(dd.dict_id)
    if values is None or len(values) != dd.num_values:
        return None
    raw = [v.encode() if v is not None else b""
           for v in values.to_pylist()]
    order = {v: r for r, v in enumerate(sorted(set(raw)))}
    return jnp.asarray(np.array([order[v] for v in raw], np.uint32))


def _value_fields(col: DeviceColumn, codes_ok: bool) -> List[Field]:
    """One column's value as fields; null and dead rows are zeroed by
    the caller."""
    dd = getattr(col, "encoding", None)
    if dd is not None:
        bits = max(dd.num_values - 1, 1).bit_length()
        codes = jnp.clip(col.data.astype(jnp.int32), 0,
                         max(dd.num_values - 1, 0))
        if codes_ok:
            return [(_u32(codes), bits)]
        ranks = _dictionary_ranks(dd)
        if ranks is not None:
            return [(jnp.take(ranks, codes), bits)]
        from spark_rapids_tpu.columnar import encoding as _enc

        col = _enc.decode_column(col)
    dt, data = col.dtype, col.data
    if isinstance(dt, StringType):
        *words, lengths = _string_orderable(col)
        return ([(_u32(w), 32) for w in words]
                + [(_u32(lengths), max(col.max_bytes, 1).bit_length())])
    if isinstance(dt, (FloatType, DoubleType)):
        key = _float_orderable(data)
        if data.dtype != jnp.float64:
            return [(_u32(key + jnp.int64(1 << 31)), 32)]
        if supports_64bit_bitcast():
            return _split_i64(key)
        # a TPU cannot bitcast 64 bits, and holds a double as a pair of
        # f32 anyway: the double's order is that of its f32 rounding,
        # then of what the rounding left (exact to the pair's ~48 bits;
        # NaN - NaN and inf - inf are the one canonical NaN)
        rest = data - data.astype(jnp.float32).astype(jnp.float64)
        return [(_u32(key + jnp.int64(1 << 31)), 32),
                (_u32(_float_orderable(rest.astype(jnp.float32))
                      + jnp.int64(1 << 31)), 32)]
    if isinstance(dt, BooleanType):
        return [(_u32(data), 1)]
    if data.ndim == 2:  # DECIMAL128 limb matrix
        from spark_rapids_tpu.ops import decimal128 as _d128

        return [f for limb in _d128.orderable_limbs(data)
                for f in _split_i64(limb)]
    wide = data.astype(jnp.int64)
    if col.vrange is not None and jnp.issubdtype(data.dtype, jnp.integer):
        lo, hi = col.vrange
        if hi - lo < 1 << 32:
            return [(_u32(wide - lo), max(hi - lo, 1).bit_length())]
    width = 8 * data.dtype.itemsize
    if width <= 32:
        return [(_u32(wide + (1 << (width - 1))), width)]
    return _split_i64(wide)


def key_fields(col: DeviceColumn, ascending: bool, nulls_first: bool,
               live: jnp.ndarray, codes_ok: bool = False) -> List[Field]:
    """One column (+ sort direction) as fields: a null bit, then its
    value. `codes_ok` as in `orderable_keys`; without it a dictionary
    column orders by its values' ranks."""
    valid = col.validity
    rank = jnp.where(valid, 1, 0) if nulls_first else jnp.where(valid, 0, 1)
    keep = valid & live
    out = [(_u32(jnp.where(live, rank, 0)), 1)]
    for value, bits in _value_fields(col, codes_ok):
        if not ascending:
            value = jnp.uint32((1 << bits) - 1) - value
        out.append((jnp.where(keep, value, jnp.uint32(0)), bits))
    return out


def pack_fields(fields: List[Field], live: jnp.ndarray
                ) -> Tuple[List[jnp.ndarray], int]:
    """Fields (most significant first) behind a leading bit that sends
    dead rows last -> (32-bit words, most significant first; the bits
    used). A field that straddles two words is split."""
    fields = [(_u32(~live), 1)] + list(fields)
    total = sum(bits for _, bits in fields)
    words, word, used = [], None, 0  # built from the least significant end
    for value, bits in reversed(fields):
        while bits:
            take = min(bits, 32 - used)
            part = value if take == 32 else \
                value & jnp.uint32((1 << take) - 1)
            word = part if word is None else word | (part << used)
            used += take
            bits -= take
            if bits:
                value = value >> take
            if used == 32:
                words.append(word)
                word, used = None, 0
    if word is not None:
        words.append(word)
    return words[::-1], total


def sort_permutation_fields(fields: List[Field], live: jnp.ndarray,
                            capacity: int, by: str = "sort"
                            ) -> Tuple[jnp.ndarray, List[jnp.ndarray]]:
    """Stable sort of the rows by `fields`, dead rows last; -> (the
    gather permutation, the packed words in the rows' own order: equal
    words, equal keys)."""
    words, bits = pack_fields(fields, live)
    note_sort("packed" if len(words) == 1 else "passes", 1, bits, capacity,
              passes=len(words), by=by)
    return _sort_passes(words, capacity), words
