"""Window kernel engine — the cuDF RollingAggregation/WindowOptions
replacement (reference: window/GpuWindowExecMeta.scala,
GpuWindowExpression.scala:2133, BasicWindowCalc.scala).

cuDF evaluates window frames with per-partition rolling kernels; XLA has
no rolling hash machinery, but the whole window family maps onto three
fully-vectorized primitives over a (partition, order)-sorted domain:

1. segment structure: one stable multi-key sort puts partition groups
   contiguous; per-row segment/peer bounds come from segmented min/max.
2. prefix sums answer every sum/count/avg frame in O(1) per row.
3. a sparse table (doubling) answers min/max over arbitrary [start, end]
   frames in O(1) per row after O(n log n) build — the TPU answer to
   cuDF's bounded-window scan kernels.

Frames are inclusive position ranges [start, end] in the sorted domain;
ROWS frames clip offsets to segment bounds, RANGE frames locate value
bounds with a vectorized binary search (the GpuBatchedBoundedWindowExec
role). Results are scattered back to input order via the inverse
permutation, since window operators preserve their input rows.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu.columnar.batch import ColumnBatch, DeviceColumn
from spark_rapids_tpu.ops import segmented
from spark_rapids_tpu.ops.common import (
    key_fields,
    normalize_floating,
    orderable_keys,
    rows_equal_adjacent,
    sort_permutation_fields,
)


class SortedWindow(NamedTuple):
    """Sorted-domain view: positions/segments for one window spec."""

    perm: jnp.ndarray        # [cap] sorted j <- original perm[j]
    inv: jnp.ndarray         # [cap] original i -> sorted position
    live: jnp.ndarray        # [cap] live mask in sorted order
    pos: jnp.ndarray         # [cap] iota
    seg_start: jnp.ndarray   # [cap] per-row first position of its partition
    seg_end: jnp.ndarray     # [cap] per-row last position (inclusive)
    seg_len: jnp.ndarray     # [cap]
    peer_start: jnp.ndarray  # [cap] first position of the ORDER BY peer run
    peer_end: jnp.ndarray    # [cap] last position of the peer run


def _ones(x):
    return jnp.ones(x.shape[:1], bool)


def sort_for_window(batch: ColumnBatch,
                    part_cols: Sequence[DeviceColumn],
                    order_cols: Sequence[Tuple[DeviceColumn, bool, bool]],
                    ) -> SortedWindow:
    cap = batch.capacity
    live = batch.live_mask()
    pos = jnp.arange(cap, dtype=jnp.int32)

    part_keys: List[jnp.ndarray] = []
    for c in part_cols:
        part_keys.extend(orderable_keys(normalize_floating(c), True, True,
                                        live))
    order_keys: List[jnp.ndarray] = []
    for c, asc, nulls_first in order_cols:
        order_keys.extend(orderable_keys(c, asc, nulls_first, live))

    if part_keys or order_keys:
        # the sort takes the keys packed (ops/common.py); the int64
        # keys above only say which neighbours are equal
        fields = []
        for c in part_cols:
            fields.extend(key_fields(normalize_floating(c), True, True,
                                     live))
        for c, asc, nulls_first in order_cols:
            fields.extend(key_fields(c, asc, nulls_first, live))
        perm, _ = sort_permutation_fields(fields, live, cap, by="window")
    else:
        perm = pos  # dead rows already trail in the original layout
    live_s = jnp.take(live, perm)

    if part_keys:
        pk_s = [jnp.take(k, perm) for k in part_keys]
        boundary = live_s & ~rows_equal_adjacent(pk_s)
        gid = (jnp.cumsum(boundary.astype(jnp.int32)) - 1).astype(jnp.int32)
        gid = jnp.clip(gid, 0, cap - 1)
    else:
        gid = jnp.zeros((cap,), jnp.int32)

    big = jnp.int32(cap)
    live_pos = jnp.where(live_s, pos, big)
    seg_start = jnp.take(
        segmented.seg_min(live_pos, _ones(live_pos), gid, cap), gid)
    seg_end = jnp.take(
        segmented.seg_max(jnp.where(live_s, pos, -1), _ones(pos), gid,
                          cap), gid)
    seg_len = seg_end - seg_start + 1

    if order_keys:
        ok_s = [jnp.take(k, perm) for k in part_keys + order_keys]
        pboundary = live_s & ~rows_equal_adjacent(ok_s)
        pid = (jnp.cumsum(pboundary.astype(jnp.int32)) - 1).astype(jnp.int32)
        pid = jnp.clip(pid, 0, cap - 1)
        peer_start = jnp.take(
            segmented.seg_min(live_pos, _ones(live_pos), pid, cap), pid)
        peer_end = jnp.take(
            segmented.seg_max(jnp.where(live_s, pos, -1), _ones(pos),
                              pid, cap), pid)
    else:
        # no ORDER BY: every row in the partition is a peer
        peer_start, peer_end = seg_start, seg_end

    inv = jnp.zeros((cap,), jnp.int32).at[perm].set(pos)
    return SortedWindow(perm, inv, live_s, pos, seg_start, seg_end, seg_len,
                        peer_start, peer_end)


# ------------------------------------------------------------ frame bounds

def rows_frame_bounds(sw: SortedWindow, lower: Optional[int],
                      upper: Optional[int]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ROWS BETWEEN lower AND upper (None = unbounded; offsets relative,
    negative = preceding). Returns inclusive [start, end] clipped to the
    segment."""
    start = sw.seg_start if lower is None else jnp.maximum(
        sw.pos + jnp.int32(lower), sw.seg_start)
    end = sw.seg_end if upper is None else jnp.minimum(
        sw.pos + jnp.int32(upper), sw.seg_end)
    return start, end


def default_frame_bounds(sw: SortedWindow, has_order: bool
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Spark's implicit frame: RANGE UNBOUNDED PRECEDING..CURRENT ROW when
    ordered (current row's full peer run included), whole partition
    otherwise."""
    if has_order:
        return sw.seg_start, sw.peer_end
    return sw.seg_start, sw.seg_end


def _lower_bound(gid_s: jnp.ndarray, val_s: jnp.ndarray,
                 tgt_val: jnp.ndarray, cap: int,
                 strict: bool) -> jnp.ndarray:
    """Vectorized binary search over the (gid, value)-sorted arrays:
    first position p with (gid[p], val[p]) >= (gid[i], tgt_val[i])
    (> when strict). gid comparison uses each row's own segment id."""
    tgt_gid = gid_s
    lo = jnp.zeros((cap,), jnp.int32)
    hi = jnp.full((cap,), cap, jnp.int32)
    steps = max(1, cap.bit_length())
    for _ in range(steps):
        mid = (lo + hi) // 2
        safe = jnp.clip(mid, 0, cap - 1)
        mg = jnp.take(gid_s, safe)
        mv = jnp.take(val_s, safe)
        if strict:
            less = (mg < tgt_gid) | ((mg == tgt_gid) & (mv <= tgt_val))
        else:
            less = (mg < tgt_gid) | ((mg == tgt_gid) & (mv < tgt_val))
        less = less & (mid < hi)
        lo = jnp.where(less, mid + 1, lo)
        hi = jnp.where(less, hi, mid)
    return lo


def range_frame_bounds(sw: SortedWindow, order_col_sorted: DeviceColumn,
                       gid_s: jnp.ndarray, lower, upper,
                       nulls_first: bool = True
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """RANGE BETWEEN value offsets over a single ascending numeric key.

    lower/upper: None = unbounded, 0 = current row (peer bounds), other
    numbers = value offsets (negative preceding). Rows whose order value
    is NULL frame over exactly their null peer run (Spark semantics).
    """
    cap = order_col_sorted.capacity
    data = order_col_sorted.data
    float_offsets = isinstance(lower, float) or isinstance(upper, float)
    if jnp.issubdtype(data.dtype, jnp.integer) and not float_offsets:
        acc = data.astype(jnp.int64)
        neg_inf = jnp.int64(jnp.iinfo(jnp.int64).min // 2)
        pos_inf = jnp.int64(jnp.iinfo(jnp.int64).max // 2)
    else:
        acc = data.astype(jnp.float64)
        neg_inf = jnp.float64(-jnp.inf)
        pos_inf = jnp.float64(jnp.inf)
    usable = order_col_sorted.validity & sw.live
    # keep (gid, val) monotone: live nulls take the sentinel matching
    # where they sorted (-inf when nulls-first, +inf when nulls-last);
    # dead rows trail the final segment -> +inf
    null_sentinel = neg_inf if nulls_first else pos_inf
    val_s = jnp.where(usable, acc,
                      jnp.where(sw.live, null_sentinel, pos_inf))
    is_null = ~order_col_sorted.validity

    if lower is None:
        start = sw.seg_start
    elif lower == 0:
        start = sw.peer_start
    else:
        tgt = val_s + jnp.asarray(lower, val_s.dtype)
        start = _lower_bound(gid_s, val_s, tgt, cap, strict=False)
        start = jnp.maximum(start.astype(jnp.int32), sw.seg_start)
        start = jnp.where(is_null, sw.peer_start, start)
    if upper is None:
        end = sw.seg_end
    elif upper == 0:
        end = sw.peer_end
    else:
        tgt = val_s + jnp.asarray(upper, val_s.dtype)
        end = _lower_bound(gid_s, val_s, tgt, cap, strict=True) - 1
        end = jnp.minimum(end.astype(jnp.int32), sw.seg_end)
        end = jnp.where(is_null, sw.peer_end, end)
    return start, end


def segment_ids_sorted(sw: SortedWindow) -> jnp.ndarray:
    """Per-sorted-row partition id (for range search): derived from
    seg_start, which is constant within a segment and strictly increasing
    across segments."""
    return sw.seg_start


# --------------------------------------------------- frame aggregations

def _prefix(vals: jnp.ndarray) -> jnp.ndarray:
    """Exclusive-then-inclusive prefix: p[i] = sum(vals[:i]); length
    cap+1 so frame sums are p[end+1] - p[start]."""
    z = jnp.zeros((1,), vals.dtype)
    return jnp.concatenate([z, jnp.cumsum(vals)])


def frame_count(valid: jnp.ndarray, sw: SortedWindow, start, end
                ) -> jnp.ndarray:
    """COUNT over frames: number of valid live rows in [start, end]."""
    cap = valid.shape[0]
    contrib = (valid & sw.live).astype(jnp.int64)
    p = _prefix(contrib)
    s = jnp.take(p, jnp.clip(end + 1, 0, cap)) - \
        jnp.take(p, jnp.clip(start, 0, cap))
    return jnp.where(end >= start, s, 0)


def frame_sum(vals: jnp.ndarray, valid: jnp.ndarray, sw: SortedWindow,
              start, end, acc_dtype) -> jnp.ndarray:
    cap = vals.shape[0]
    contrib = jnp.where(valid & sw.live, vals.astype(acc_dtype),
                        jnp.zeros((), acc_dtype))
    p = _prefix(contrib)
    s = jnp.take(p, jnp.clip(end + 1, 0, cap)) - \
        jnp.take(p, jnp.clip(start, 0, cap))
    return jnp.where(end >= start, s, jnp.zeros((), acc_dtype))


def _sparse_table(vals: jnp.ndarray, ident, maximum: bool) -> jnp.ndarray:
    """[L, cap] doubling table; table[l, i] = reduce over [i, i + 2^l)."""
    cap = vals.shape[0]
    rows = [vals]
    step = 1
    while step < cap:
        prev = rows[-1]
        shifted = jnp.concatenate(
            [prev[step:], jnp.full((step,), ident, prev.dtype)])
        rows.append(jnp.maximum(prev, shifted) if maximum
                    else jnp.minimum(prev, shifted))
        step <<= 1
    return jnp.stack(rows)


def frame_minmax(vals: jnp.ndarray, valid: jnp.ndarray, sw: SortedWindow,
                 start, end, maximum: bool) -> jnp.ndarray:
    cap = vals.shape[0]
    if jnp.issubdtype(vals.dtype, jnp.floating):
        # Spark float ordering: NaN is the largest value. jnp.minimum/
        # maximum would propagate NaN, so strip NaNs from the table and
        # re-inject where the Spark answer is NaN (max with any NaN in
        # frame; min of an all-NaN frame).
        nan_mask = jnp.isnan(vals)
        nan_cnt = frame_count(valid & nan_mask, sw, start, end)
        clean_valid = valid & ~nan_mask
        clean_cnt = frame_count(clean_valid, sw, start, end)
        ident = jnp.array(-jnp.inf if maximum else jnp.inf, vals.dtype)
        masked = jnp.where(clean_valid & sw.live, vals, ident)
        table = _sparse_table(masked, ident, maximum)
        length = jnp.maximum(end - start + 1, 1)
        k = (31 - lax.clz(length.astype(jnp.int32))).astype(jnp.int32)
        flat = table.reshape(-1)
        left = jnp.take(flat, k * cap + jnp.clip(start, 0, cap - 1))
        ridx = jnp.clip(end - (jnp.int32(1) << k) + 1, 0, cap - 1)
        right = jnp.take(flat, k * cap + ridx)
        out = (jnp.maximum(left, right) if maximum
               else jnp.minimum(left, right))
        nan = jnp.array(jnp.nan, vals.dtype)
        if maximum:
            out = jnp.where(nan_cnt > 0, nan, out)
        else:
            out = jnp.where(clean_cnt == 0, nan, out)
        return jnp.where(end >= start, out, ident)
    if vals.dtype == jnp.bool_:
        vals = vals.astype(jnp.int32)
        ident = jnp.array(0 if maximum else 1, jnp.int32)
    else:
        info = jnp.iinfo(vals.dtype)
        ident = jnp.array(info.min if maximum else info.max, vals.dtype)
    masked = jnp.where(valid & sw.live, vals, ident)
    table = _sparse_table(masked, ident, maximum)
    length = jnp.maximum(end - start + 1, 1)
    k = (31 - lax.clz(length.astype(jnp.int32))).astype(jnp.int32)
    flat = table.reshape(-1)
    left = jnp.take(flat, k * cap + jnp.clip(start, 0, cap - 1))
    ridx = jnp.clip(end - (jnp.int32(1) << k) + 1, 0, cap - 1)
    right = jnp.take(flat, k * cap + ridx)
    out = jnp.maximum(left, right) if maximum else jnp.minimum(left, right)
    return jnp.where(end >= start, out, ident)


def frame_first_last(vals: jnp.ndarray, valid: jnp.ndarray,
                     sw: SortedWindow, start, end, last: bool,
                     ignore_nulls: bool
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """first_value/last_value over frames; returns (values, validity)."""
    cap = vals.shape[0]
    if ignore_nulls:
        pos = sw.pos
        ok = valid & sw.live
        p = _prefix(ok.astype(jnp.int32))

        # first valid >= start: binary search over prefix counts
        def pick(target_count):
            lo = jnp.zeros((cap,), jnp.int32)
            hi = jnp.full((cap,), cap, jnp.int32)
            for _ in range(max(1, cap.bit_length())):
                mid = (lo + hi) // 2
                c = jnp.take(p, jnp.clip(mid + 1, 0, cap))
                less = (c < target_count) & (mid < hi)
                lo = jnp.where(less, mid + 1, lo)
                hi = jnp.where(less, hi, mid)
            return lo

        before_start = jnp.take(p, jnp.clip(start, 0, cap))
        upto_end = jnp.take(p, jnp.clip(end + 1, 0, cap))
        has = upto_end > before_start
        idx = pick(upto_end if last else before_start + 1)
        idx = jnp.clip(idx, 0, cap - 1)
        v = jnp.take(vals, idx, axis=0)
        return v, has & (end >= start)
    idx = jnp.clip(jnp.where(end >= start, end if last else start, 0),
                   0, cap - 1)
    v = jnp.take(vals, idx, axis=0)
    ok = jnp.take(valid, idx) & (end >= start)
    return v, ok


def frame_collect(vals: jnp.ndarray, valid: jnp.ndarray,
                  sw: SortedWindow, start, end, frame,
                  distinct: bool):
    """collect_list/collect_set over BOUNDED ROWS frames — the device
    RollingAggregation COLLECT_LIST/COLLECT_SET role. The output width
    is the frame's static span (lower+upper+1), so the padded array
    column has a compile-time shape; unbounded frames take the CPU
    path via planner tagging.

    Returns (data [cap, W], row_validity, lengths, elem_validity) with
    elements left-packed in frame order (nulls skipped, like Spark);
    collect_set additionally drops duplicates keeping first occurrence.
    """
    assert frame is not None and frame.frame_type == "rows"
    width = int(frame.upper) - int(frame.lower) + 1
    cap = vals.shape[0]
    offs = jnp.arange(width, dtype=jnp.int32)[None, :]
    idx = start[:, None] + offs                      # [cap, W]
    inside = idx <= end[:, None]
    safe = jnp.clip(idx, 0, cap - 1)
    elem = jnp.take(vals, safe, axis=0)              # [cap, W]
    ok = inside & jnp.take(valid, safe) & jnp.take(sw.live, safe)
    if distinct:
        # keep the first occurrence of each value within the row
        dup = jnp.zeros_like(ok)
        for j in range(1, width):
            prev_eq = (elem[:, :j] == elem[:, j:j + 1]) & ok[:, :j]
            dup = dup.at[:, j].set(jnp.any(prev_eq, axis=1))
        ok = ok & ~dup
    # left-pack kept elements preserving frame order: stable argsort on
    # the drop flag
    order = jnp.argsort(jnp.where(ok, 0, 1).astype(jnp.int8), axis=1,
                        stable=True)
    packed = jnp.take_along_axis(elem, order, axis=1)
    kept = jnp.take_along_axis(ok, order, axis=1)
    lengths = jnp.sum(ok, axis=1).astype(jnp.int32)
    row_valid = jnp.ones((cap,), bool)  # empty array, never null
    return packed, row_valid, lengths, kept


# --------------------------------------------------------- ranking family

def row_number(sw: SortedWindow) -> jnp.ndarray:
    return (sw.pos - sw.seg_start + 1).astype(jnp.int32)


def rank(sw: SortedWindow) -> jnp.ndarray:
    return (sw.peer_start - sw.seg_start + 1).astype(jnp.int32)


def dense_rank(sw: SortedWindow) -> jnp.ndarray:
    cap = sw.pos.shape[0]
    new_peer = (sw.pos == sw.peer_start) & sw.live
    peer_ord = jnp.cumsum(new_peer.astype(jnp.int32))
    first_of_seg = jnp.take(peer_ord, jnp.clip(sw.seg_start, 0, cap - 1))
    return (peer_ord - first_of_seg + 1).astype(jnp.int32)


def percent_rank(sw: SortedWindow) -> jnp.ndarray:
    r = rank(sw).astype(jnp.float64)
    d = jnp.maximum(sw.seg_len - 1, 1).astype(jnp.float64)
    return jnp.where(sw.seg_len > 1, (r - 1.0) / d, 0.0)


def cume_dist(sw: SortedWindow) -> jnp.ndarray:
    n = (sw.peer_end - sw.seg_start + 1).astype(jnp.float64)
    return n / sw.seg_len.astype(jnp.float64)


def ntile(sw: SortedWindow, n: int) -> jnp.ndarray:
    idx = sw.pos - sw.seg_start
    q = sw.seg_len // n
    r = sw.seg_len % n
    threshold = r * (q + 1)
    small = idx // jnp.maximum(q + 1, 1)
    bigq = jnp.maximum(q, 1)
    large = r + (idx - threshold) // bigq
    return jnp.where(idx < threshold, small, large).astype(jnp.int32) + 1


def lead_lag(vals: jnp.ndarray, valid: jnp.ndarray, sw: SortedWindow,
             offset: int
             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """lead(+offset)/lag(-offset) -> (values, validity, inside_partition);
    out-of-partition rows take the caller's default."""
    cap = vals.shape[0]
    tgt = sw.pos + jnp.int32(offset)
    inside = (tgt >= sw.seg_start) & (tgt <= sw.seg_end)
    safe = jnp.clip(tgt, 0, cap - 1)
    v = jnp.take(vals, safe, axis=0)
    ok = jnp.take(valid, safe) & inside
    return v, ok, inside
