"""Sort-based grouping + segmented reductions — the cuDF
`Table.groupBy(...).aggregate(...)` replacement.

cuDF uses a device hash-map groupby; HLO has no hash tables, but
`lax.sort` + `jax.ops.segment_*` map perfectly onto TPU: sort rows by the
orderable group keys, find segment boundaries, then segmented reductions
with num_segments = capacity (static). Group outputs land compacted at
segment-id positions, so the result batch needs no extra compaction pass.

A KEYLESS aggregate has one segment, and a scatter-add of every row
into slot 0 is the worst thing to ask of XLA:TPU (the serialized update
loop below: 77 ns a row, 4.6 s of every TPC-H Q6 over 30M rows). Inside
`one_segment()` — entered by TpuHashAggregateExec when its plan has no
grouping key — the six primitives (seg_count, seg_sum, seg_sum_count,
seg_multi_sum, seg_min, seg_max) are masked whole-array reductions in
the buffer's OWN type (f64 sums accumulate in f64, integer sums and
counts in i64), with the scalar placed at position 0 of the same [cap]
result. The f32-chunk / f64-carry stance further down belongs to the
binned MXU path only.

Reference: GpuAggregateExec.scala:175-400 (AggHelper pre-process ->
groupby -> merge).
"""

from __future__ import annotations

import contextvars
from contextlib import contextmanager
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import ColumnBatch, DeviceColumn
from spark_rapids_tpu.ops.common import (
    key_fields,
    normalize_floating,
    rows_equal_adjacent,
    sort_permutation_fields,
)


class GroupedBatch(NamedTuple):
    """Sorted-by-key view of a batch with segment structure."""

    sorted_batch: ColumnBatch      # rows permuted so groups are contiguous
    gid: jnp.ndarray               # [cap] int32 segment id per sorted row
    live: jnp.ndarray              # [cap] bool live mask in sorted order
    num_groups: jnp.ndarray        # scalar int32
    first_pos: jnp.ndarray         # [cap] int32: sorted position of each
    #                                group's first row (by gid)


# Trace-time flag: the binned (sort-free) grouping path produces gids
# in original row order, so segment ops must not claim sortedness.
# ContextVar (not a module global) because program construction runs
# concurrently from reader/compile thread pools.
_SORTED_GIDS = contextvars.ContextVar("srtpu_sorted_gids", default=True)


@contextmanager
def unsorted_gids():
    tok = _SORTED_GIDS.set(False)
    try:
        yield
    finally:
        _SORTED_GIDS.reset(tok)


# Trace-time flag: every gid is 0 (a keyless aggregate), so a segmented
# reduction is a dense one. Static, chosen by the plan's shape; callers
# outside the context (grouped aggregates, window operators,
# partitioning, collectives) lower exactly as before.
_ONE_SEGMENT = contextvars.ContextVar("srtpu_one_segment", default=False)

#: trace-time counter of one-segment (dense) reductions, the twin of
#: mm_traced_sweeps: tests assert that the keyless path engaged
dense_traced_reductions = 0


@contextmanager
def one_segment():
    """Declare that every row belongs to segment 0 (no grouping key):
    the seg_* primitives ignore `gid` and reduce densely."""
    tok = _ONE_SEGMENT.set(True)
    try:
        yield
    finally:
        _ONE_SEGMENT.reset(tok)


def _dense(reduce, values: jnp.ndarray, valid: jnp.ndarray, ident,
           cap: int) -> jnp.ndarray:
    """reduce(where(valid, values, ident)) over the row axis at position
    0 of a [cap, ...] result; the other positions hold what the scatter
    left in segments no row reached (`ident`). Built with a select, not
    `.at[0].set`, which is itself a scatter."""
    global dense_traced_reductions
    dense_traced_reductions += 1
    ident = jnp.asarray(ident, values.dtype)
    row = valid.reshape(valid.shape + (1,) * (values.ndim - 1))
    r = reduce(jnp.where(row, values, ident), axis=0)
    first = jnp.arange(cap, dtype=jnp.int32) == 0
    return jnp.where(first.reshape((cap,) + (1,) * r.ndim), r, ident)


# ---- MXU segmented reductions (the binned path's hot kernels) ----
#
# XLA:TPU lowers scatter-add (jax.ops.segment_sum) to a serialized
# update loop — 62.6 ns a row on v5e for a 64-bit scatter-add of
# 122,880 unsorted rows into 8 bins (TPC-H Q12's partial aggregate,
# PERF.md section 6, PR 32), i.e. seconds per 32M-row batch — while
# one-hot matmuls ride the MXU at >100x that rate. When
# the bin count B is statically small (the binned group-by), a
# segmented sum is an outer-product accumulation:
#
#   out[h, l] = sum_r value_r * [gid_r // GL == h] * [gid_r % GL == l]
#             = onehot_hi.T @ (values[:, None] * onehot_lo)
#
# with (GH, GL) factoring B, computed chunk-by-chunk under lax.scan so
# the one-hot tiles never materialize at full length. The MXU has no
# f64/i64 path (emulated f64 dots measured 16x slower), so every dot
# runs in f32 with exactness arranged around it:
#   - counts: chunk counts <= chunk size < 2^24 are exact in f32; the
#     cross-chunk carry accumulates in i64 -> exact.
#   - bounded int sums: when |value| <= V (static vrange metadata from
#     upload narrowing), a chunk of C rows sums to < V*C; choosing C
#     with V*C <= 2^24 keeps every chunk partial exact in f32, and the
#     i64 carry is exact: ONE weight vector.
#   - every other int sum (no vrange: a CASE, a product, a cast; or a
#     bound too loose for a chunk of 2048 rows): the value is split by
#     its OWN width W into W/8 limbs of 8 bits, the low ones unsigned,
#     the top one signed (an arithmetic shift), each a weight vector of
#     the same sweep with an i64 carry. A chunk partial is at most
#     255*C < 2^24 (C capped at _LIMB_CHUNK), so every dot is exact,
#     and sum_k S_k << 8k in wrapping i64 is the scatter-add's answer
#     bit for bit, overflow wrap included: an integer sum under
#     binned_bins never scatters.
#   - float sums: f32 chunk partials with an f64 carry — ~1e-5
#     relative at worst, the stance docs/compatibility.md states for
#     THIS path only (v5e's f64 is emulated to ~1e-13 elsewhere; a
#     keyless aggregate's dense reduce, above, keeps that).
# min/max have no outer-product form and keep the scatter path (their
# cost only matters if a plan min/maxes a huge un-sorted batch).

_MM_BINS = contextvars.ContextVar("srtpu_mm_bins", default=None)
_MM_FORCE = contextvars.ContextVar("srtpu_mm_force", default=False)

#: trace-time counter of matmul-path sweeps — tests assert the path
#: actually engaged (a silently regressed gate would otherwise let
#: scatter-vs-scatter comparisons pass vacuously)
mm_traced_sweeps = 0

#: trace-time record of how each partial aggregate's Sum/Average was
#: lowered: {"bounded" | "limbs" | "float" | "scatter": count}, kept by
#: whoever traces a program (runtime/jit_cache.py keeps it with the
#: program, exec/fused.py reports it as `agg`)
_SUM_LOWERINGS = contextvars.ContextVar("srtpu_sum_lowerings", default=None)


@contextmanager
def noting_sum_lowerings():
    """Collect the sum lowerings of what is traced inside."""
    notes: dict = {}
    tok = _SUM_LOWERINGS.set(notes)
    try:
        yield notes
    finally:
        _SUM_LOWERINGS.reset(tok)


def note_sum_lowering(kind: str) -> None:
    notes = _SUM_LOWERINGS.get()
    if notes is not None:
        notes[kind] = notes.get(kind, 0) + 1


MM_MAX_BINS = 1 << 14
_MM_CHUNK = 1 << 15
#: most rows of a chunk whose 8-bit limb partials stay exact in f32
_LIMB_CHUNK = ((1 << 24) - 1) // 255
_MM_LIMITS = contextvars.ContextVar("srtpu_mm_limits", default=None)


def mm_chunk() -> int:
    lim = _MM_LIMITS.get()
    return lim[1] if lim else _MM_CHUNK


@contextmanager
def binned_bins(b: int, max_bins: Optional[int] = None,
                chunk: Optional[int] = None):
    """Declare that gids lie in [0, b) with b static (binned group-by);
    enables the matmul reductions on TPU backends. max_bins/chunk
    override the defaults (conf spark.rapids.sql.agg.matmulSegments.*;
    callers must key any program cache on them)."""
    tok = _MM_BINS.set(int(b))
    tok2 = _MM_LIMITS.set((max_bins or MM_MAX_BINS, chunk or _MM_CHUNK))
    try:
        yield
    finally:
        _MM_LIMITS.reset(tok2)
        _MM_BINS.reset(tok)


@contextmanager
def force_matmul_path():
    """Tests: take the matmul path regardless of backend."""
    tok = _MM_FORCE.set(True)
    try:
        yield
    finally:
        _MM_FORCE.reset(tok)


def _mm_bins() -> Optional[int]:
    if _ONE_SEGMENT.get():
        return None  # one segment: dense reduce, never the f32 chunks
    b = _MM_BINS.get()
    lim = _MM_LIMITS.get()
    if b is None or b > (lim[0] if lim else MM_MAX_BINS):
        return None
    if not (_MM_FORCE.get() or jax.default_backend() == "tpu"):
        return None
    return b


def mm_bins_active() -> Optional[int]:
    """Bin count when the matmul reductions will engage (inside a
    binned_bins context on a TPU/forced backend), else None."""
    return _mm_bins()


def _mm_factors(b: int) -> Tuple[int, int]:
    """(GH, GL) with GH*GL >= b. VPU work per row is ~2*GL + GH
    (two one-hot builds + the masked product), so GL ~ sqrt(b/2)."""
    gl = 1
    while gl * gl * 2 < b:
        gl <<= 1
    return -(-b // gl), gl


def _mm_pass(weights: jnp.ndarray, gid: jnp.ndarray, b: int, chunk: int,
             acc_dtype, guard_nonfinite: bool = False) -> jnp.ndarray:
    """sum_r weights_r * onehot(gid_r) -> [b] acc_dtype. weights must be
    f32 and pre-masked (0 for dead rows).

    Dots run at Precision.HIGHEST: the TPU default lowers f32 matmuls to
    one-pass bf16 (8-bit mantissa), which would silently break the
    exact-count/exact-bounded-int contract and degrade float sums far
    below f32-chunk precision.

    guard_nonfinite (float sums): Inf inputs would poison whole chunks
    (inf * one-hot-0 = NaN inside both the mask product and the dot), so
    each chunk checks all-finite and falls back to a scatter-add for
    that chunk alone — IEEE special values then confine to their own
    group exactly like the scatter path, at scatter cost only for
    chunks that actually contain them."""
    return _mm_pass_multi([weights], gid, b, chunk, [acc_dtype],
                          guard_nonfinite)[0]


def _mm_pass_multi(weights_list, gid: jnp.ndarray, b: int, chunk: int,
                   acc_dtypes, guard_nonfinite: bool = False):
    """k segmented sums in ONE row sweep: the one-hot tiles are built
    once per chunk and all k weight vectors ride a single stacked dot
    ([GH, C] @ [C, k*GL]) — the one-hot build dominates VPU cost, so
    fusing k sums costs barely more than one."""
    global mm_traced_sweeps
    mm_traced_sweeps += 1
    n = gid.shape[0]
    k = len(weights_list)
    gh, gl = _mm_factors(b)
    c = min(chunk, n)
    pad = (-n) % c
    if pad:
        weights_list = [
            jnp.concatenate([w, jnp.zeros(pad, w.dtype)])
            for w in weights_list]
        gid = jnp.concatenate([gid, jnp.zeros(pad, gid.dtype)])
    lo = gid % gl
    hi = gid // gl
    il = jnp.arange(gl, dtype=jnp.int32)
    ih = jnp.arange(gh, dtype=jnp.int32)

    def body(carry, xs):
        hb, lb = xs[0], xs[1]
        wbs = xs[2:]

        def mm(_):
            ohl = (lb[:, None] == il[None, :]).astype(jnp.float32)
            ohh = (hb[:, None] == ih[None, :]).astype(jnp.float32)
            stacked = jnp.concatenate(
                [wb[:, None] * ohl for wb in wbs], axis=1)
            m = jnp.matmul(ohh.T, stacked,
                           precision=jax.lax.Precision.HIGHEST)
            return tuple(m[:, j * gl:(j + 1) * gl] for j in range(k))

        def scatter(_):
            return tuple(
                jax.ops.segment_sum(wb, hb * gl + lb,
                                    num_segments=gh * gl).reshape(gh, gl)
                for wb in wbs)

        if guard_nonfinite:
            ms = jax.lax.cond(
                jnp.all(jnp.stack([jnp.isfinite(wb).all() for wb in wbs])),
                mm, scatter, 0)
        else:
            ms = mm(0)
        return tuple(cy + m.astype(dt) for cy, m, dt
                     in zip(carry, ms, acc_dtypes)), None

    init = tuple(jnp.zeros((gh, gl), dt) for dt in acc_dtypes)
    xs = (hi.reshape(-1, c), lo.reshape(-1, c)) + tuple(
        w.reshape(-1, c) for w in weights_list)
    out, _ = jax.lax.scan(body, init, xs)
    return [o.reshape(-1)[:b] for o in out]


def _pad_bins(vals: jnp.ndarray, cap: int) -> jnp.ndarray:
    if vals.shape[0] >= cap:
        return vals[:cap]
    return jnp.concatenate(
        [vals, jnp.zeros(cap - vals.shape[0], vals.dtype)])


def _mm_seg_count(valid: jnp.ndarray, gid: jnp.ndarray,
                  b: int) -> jnp.ndarray:
    # chunk counts <= chunk size < 2^24: exact in f32; i64 carry exact
    return _mm_pass(valid.astype(jnp.float32), gid, b, mm_chunk(),
                    jnp.int64)


class _SumPlan(NamedTuple):
    """How one segmented sum rides `_mm_pass_multi`."""

    kind: str        # "float" | "bounded" | "limbs": the `agg` record's
    weights: list    # pre-masked f32 vectors, one dot column-block each
    chunk: int       # most rows a chunk may hold
    acc: object      # the carry's dtype, of every vector
    guard: bool      # guard_nonfinite

    def combine(self, outs) -> jnp.ndarray:
        """The vectors' bin sums -> the sum, in `acc`."""
        total = outs[0]
        for k, s in enumerate(outs[1:], 1):  # limbs: ring arithmetic
            total = total + (s << (8 * k))
        return total


def _mm_sum_plan(values: jnp.ndarray, valid: jnp.ndarray,
                 vbound) -> Optional[_SumPlan]:
    """The matmul plan of a segmented sum of `values`, which must come
    in the column's OWN dtype (before any cast to the sum type: an
    integer's limbs are counted from its width). None for what is
    neither float nor integer."""
    dt = values.dtype
    masked = jnp.where(valid, values, 0)
    if jnp.issubdtype(dt, jnp.floating):
        return _SumPlan("float", [masked.astype(jnp.float32)], mm_chunk(),
                        jnp.float64, True)
    if not jnp.issubdtype(dt, jnp.integer):
        return None
    if vbound is not None:
        v = max(abs(int(vbound[0])), abs(int(vbound[1])), 1)
        chunk = 1
        while chunk * 2 * v <= (1 << 24) and chunk < mm_chunk():
            chunk <<= 1
        if chunk >= 2048:  # else: too loose for exact f32 chunks
            return _SumPlan("bounded", [masked.astype(jnp.float32)],
                            chunk, jnp.int64, False)
    top = dt.itemsize - 1
    limbs = [(masked >> (8 * k)) & 0xFF for k in range(top)]
    limbs.append(masked >> (8 * top))  # arithmetic: carries the sign
    return _SumPlan("limbs", [b.astype(jnp.float32) for b in limbs],
                    min(mm_chunk(), _LIMB_CHUNK), jnp.int64, False)


def _mm_multi_sum(plans: Sequence[_SumPlan], gid: jnp.ndarray, b: int,
                  counted: Optional[jnp.ndarray] = None):
    """All of `plans` (and the count of `counted`, where given) in ONE
    row sweep -> ([sum in its plan's acc], count or None)."""
    ws = [w for p in plans for w in p.weights]
    accs = [p.acc for p in plans for _ in p.weights]
    if counted is not None:
        ws.append(counted.astype(jnp.float32))
        accs.append(jnp.int64)
    outs = _mm_pass_multi(ws, gid, b, min(p.chunk for p in plans), accs,
                          guard_nonfinite=any(p.guard for p in plans))
    sums, at = [], 0
    for p in plans:
        sums.append(p.combine(outs[at:at + len(p.weights)]))
        at += len(p.weights)
    return sums, (outs[-1] if counted is not None else None)


def dense_bin_perm(occupied: jnp.ndarray, cap: int) -> jnp.ndarray:
    """Gather permutation mapping dense group position j -> the j-th
    occupied bin (rows past num_groups are garbage)."""
    dense = jnp.cumsum(occupied.astype(jnp.int32)) - 1
    return jnp.zeros((cap,), jnp.int32).at[
        jnp.where(occupied, dense, cap)].set(
        jnp.arange(cap, dtype=jnp.int32), mode="drop")


def group_by(batch: ColumnBatch, key_idxs: Sequence[int],
             live: Optional[jnp.ndarray] = None) -> GroupedBatch:
    cap = batch.capacity
    if live is None:
        live = batch.live_mask()
    if not key_idxs:
        # global aggregation: every live row in segment 0; one group
        # always exists (Spark's global agg emits one row on empty input)
        gid = jnp.zeros((cap,), jnp.int32)
        first_pos = jnp.zeros((cap,), jnp.int32)
        return GroupedBatch(batch, gid, live, jnp.int32(1), first_pos)
    fields = []
    for i in key_idxs:
        # codes_ok: grouping is a single-batch EQUALITY context, so
        # dictionary-encoded keys group on their codes (interned
        # dictionaries make code equality == value equality) instead
        # of decoding to byte matrices
        fields.extend(key_fields(normalize_floating(batch.columns[i]),
                                 True, True, live, codes_ok=True))
    # every key of the group in as few 32-bit words as its stamped
    # ranges allow, ONE sort operand a pass (ops/common.py)
    perm, words = sort_permutation_fields(fields, live, cap, by="group")
    sorted_keys = [jnp.take(w, perm) for w in words]
    live_s = jnp.take(live, perm)
    eq = rows_equal_adjacent(sorted_keys)
    boundary = live_s & ~eq
    gid = (jnp.cumsum(boundary.astype(jnp.int32)) - 1).astype(jnp.int32)
    gid = jnp.clip(gid, 0, cap - 1)
    num_groups = jnp.sum(boundary).astype(jnp.int32)
    pos = jnp.arange(cap, dtype=jnp.int32)
    big = jnp.int32(cap)
    first_pos = jax.ops.segment_min(jnp.where(live_s, pos, big), gid,
                                    num_segments=cap)
    sorted_batch = batch.gather(perm, batch.num_rows)
    return GroupedBatch(sorted_batch, gid, live_s, num_groups, first_pos)


# --- segmented reduction primitives (masked; num_segments = capacity) ---
#
# PRECONDITION: gid must be SORTED ascending (group_by sorts rows
# before every reduction) UNLESS the caller is inside `unsorted_gids()`
# (the binned grouping path). The indices_are_sorted flag is an XLA
# correctness contract, not a hint — claiming sortedness over unsorted
# gids produces silently wrong results on TPU.

def seg_count(valid: jnp.ndarray, gid: jnp.ndarray, cap: int) -> jnp.ndarray:
    if _ONE_SEGMENT.get():
        return _dense(jnp.sum, valid.astype(jnp.int64), valid, 0, cap)
    b = _mm_bins()
    if b is not None and b <= cap:
        return _pad_bins(_mm_seg_count(valid, gid, b), cap)
    return jax.ops.segment_sum(valid.astype(jnp.int64), gid,
                               num_segments=cap,
                               indices_are_sorted=_SORTED_GIDS.get())


def _seg_sum(values, valid, gid, cap: int, vbound, out_dtype,
             with_count: bool):
    """-> (sum, count or None, how the sum was lowered: a `_SumPlan`
    kind, "scatter", or None for one segment's dense reduce)."""
    out_dtype = out_dtype or values.dtype
    b = _mm_bins()
    if b is not None and b <= cap and values.ndim == 1:
        plan = _mm_sum_plan(values, valid, vbound)
        if plan is not None:
            (s,), c = _mm_multi_sum([plan], gid, b,
                                    valid if with_count else None)
            return (_pad_bins(s.astype(out_dtype), cap),
                    _pad_bins(c, cap) if with_count else None, plan.kind)
    values = values.astype(out_dtype)
    if _ONE_SEGMENT.get():
        s, kind = _dense(jnp.sum, values, valid, 0, cap), None
    else:
        zero = jnp.zeros((), dtype=values.dtype)
        s, kind = jax.ops.segment_sum(
            jnp.where(valid, values, zero), gid, num_segments=cap,
            indices_are_sorted=_SORTED_GIDS.get()), "scatter"
    return s, (seg_count(valid, gid, cap) if with_count else None), kind


def seg_sum(values: jnp.ndarray, valid: jnp.ndarray, gid: jnp.ndarray,
            cap: int, vbound=None, out_dtype=None) -> jnp.ndarray:
    """Segmented sum in `out_dtype` (default: the values' own). Hand an
    integer column over in its OWN dtype, with its `vrange` as `vbound`
    and the sum type as `out_dtype`: the matmul plan reads all three."""
    return _seg_sum(values, valid, gid, cap, vbound, out_dtype, False)[0]


def seg_sum_count(values: jnp.ndarray, valid: jnp.ndarray,
                  gid: jnp.ndarray, cap: int, vbound=None, out_dtype=None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(segmented sum, segmented count) of the same masked rows, as an
    aggregate's Sum/Average needs them (the count tracks nulls): on the
    matmul path both ride ONE row sweep (`_mm_pass_multi`), and how the
    sum was lowered is noted (`noting_sum_lowerings`)."""
    s, c, kind = _seg_sum(values, valid, gid, cap, vbound, out_dtype, True)
    if kind:
        note_sum_lowering(kind)
    return s, c


def seg_multi_sum(values_list, valid: jnp.ndarray, gid: jnp.ndarray,
                  cap: int, with_count: bool = True):
    """(count, [sums]) over the SAME masked rows, fused into one row
    sweep on the matmul path (the variance/covariance families need
    2-5 power/cross sums plus a count — each as its own sweep would
    rebuild the dominant one-hot tiles k times)."""
    b = _mm_bins()
    if (b is not None and b <= cap
            and all(v.ndim == 1 for v in values_list)):
        plans = [_mm_sum_plan(v, valid, None) for v in values_list]
        if all(p is not None for p in plans):
            sums, cnt = _mm_multi_sum(plans, gid, b,
                                      valid if with_count else None)
            sums = [_pad_bins(o.astype(v.dtype), cap)
                    for o, v in zip(sums, values_list)]
            return (_pad_bins(cnt, cap) if with_count else None), sums
    cnt = seg_count(valid, gid, cap) if with_count else None
    return cnt, [seg_sum(v, valid, gid, cap) for v in values_list]


def seg_min(values: jnp.ndarray, valid: jnp.ndarray, gid: jnp.ndarray,
            cap: int) -> jnp.ndarray:
    if jnp.issubdtype(values.dtype, jnp.floating):
        ident = jnp.array(jnp.inf, dtype=values.dtype)
    else:
        ident = jnp.array(jnp.iinfo(values.dtype).max, dtype=values.dtype)
    if _ONE_SEGMENT.get():
        return _dense(jnp.min, values, valid, ident, cap)
    return jax.ops.segment_min(jnp.where(valid, values, ident), gid,
                               num_segments=cap,
                               indices_are_sorted=_SORTED_GIDS.get())


def seg_max(values: jnp.ndarray, valid: jnp.ndarray, gid: jnp.ndarray,
            cap: int) -> jnp.ndarray:
    if jnp.issubdtype(values.dtype, jnp.floating):
        ident = jnp.array(-jnp.inf, dtype=values.dtype)
    else:
        ident = jnp.array(jnp.iinfo(values.dtype).min, dtype=values.dtype)
    if _ONE_SEGMENT.get():
        return _dense(jnp.max, values, valid, ident, cap)
    return jax.ops.segment_max(jnp.where(valid, values, ident), gid,
                               num_segments=cap,
                               indices_are_sorted=_SORTED_GIDS.get())


def seg_first(values: jnp.ndarray, first_pos_valid: jnp.ndarray
              ) -> jnp.ndarray:
    """First (by sorted position) value per segment; the caller supplies
    per-group positions (e.g. seg_min over valid positions for
    FIRST(ignore nulls), or GroupedBatch.first_pos for group keys)."""
    safe = jnp.clip(first_pos_valid, 0, values.shape[0] - 1)
    return jnp.take(values, safe)
