"""Sort kernels: per-batch sort + sorted-run merge (out-of-core sort).

cuDF gives `Table.sort` and `Table.merge` for the reference's out-of-core
sort (GpuSortExec.scala:151-633: sort each input batch, keep a spillable
queue of sorted runs, merge). The TPU formulation:

- sort_batch: one fixed-shape program — orderable int64 keys
  (ops/common.py) through `lax.sort`.
- merge_sorted: merge two sorted runs WITHOUT re-sorting: each row's
  output position = own index + count of earlier rows in the other run,
  computed by vectorized lexicographic binary search (the same kernel
  shape as the join probe), then a scatter. Stable: run-A rows win ties.
"""

from __future__ import annotations

from typing import List, Tuple

import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import (
    ColumnBatch,
    DeviceColumn,
    next_capacity,
)
from spark_rapids_tpu.expr import EvalContext
from spark_rapids_tpu.ops.common import orderable_keys
from spark_rapids_tpu.ops.joinops import _binary_search


def order_keys(batch: ColumnBatch, orders) -> List[jnp.ndarray]:
    """Orderable key arrays for a batch under the given SortOrders
    (dead rows rank last)."""
    live = batch.live_mask()
    ctx = EvalContext(batch)
    keys: List[jnp.ndarray] = []
    for o in orders:
        col = o.expr.eval(ctx)
        keys.extend(orderable_keys(col, o.ascending, o.nulls_first, live))
    return keys


def sort_batch(batch: ColumnBatch, orders) -> ColumnBatch:
    """The batch in the order of `orders`: the keys packed into as few
    32-bit words as their stamped ranges, dictionaries and widths
    allow, one sort operand a pass (ops/common.py)."""
    from spark_rapids_tpu.ops.common import (
        key_fields,
        sort_permutation_fields,
    )

    live = batch.live_mask()
    ctx = EvalContext(batch)
    fields = []
    for o in orders:
        fields.extend(key_fields(o.expr.eval(ctx), o.ascending,
                                 o.nulls_first, live))
    perm, _ = sort_permutation_fields(fields, live, batch.capacity)
    return batch.gather(perm, batch.num_rows)


def _align_col(ca: DeviceColumn, cb: DeviceColumn
               ) -> Tuple[DeviceColumn, DeviceColumn]:
    """Pad a column pair's 2-D leaves to common widths (recursing into
    struct children) so key structures and scatters line up."""
    if ca.children is not None:
        pairs = [_align_col(ka, kb)
                 for ka, kb in zip(ca.children, cb.children)]
        return (ca.replace(children=[p[0] for p in pairs]),
                cb.replace(children=[p[1] for p in pairs]))
    if ca.data.ndim < 2:
        return ca, cb

    from spark_rapids_tpu.columnar.batch import pad_trailing

    def pad_to(c: DeviceColumn, trailing) -> DeviceColumn:
        if tuple(c.data.shape[1:]) == tuple(trailing):
            return c
        ew = trailing[:1]  # elems axis for the 2-D sidecars
        return c.replace(
            data=pad_trailing(c.data, trailing),
            elem_validity=pad_trailing(c.elem_validity, ew),
            elem_lengths=pad_trailing(c.elem_lengths, ew),
            map_values=pad_trailing(c.map_values, ew))

    trailing = tuple(max(int(x), int(y)) for x, y in
                     zip(ca.data.shape[1:], cb.data.shape[1:]))
    return pad_to(ca, trailing), pad_to(cb, trailing)


def align_string_widths(a: ColumnBatch, b: ColumnBatch
                        ) -> Tuple[ColumnBatch, ColumnBatch]:
    """Pad string columns of both batches to a common byte width so key
    structures (packed word counts) and scatters line up."""
    pairs = [_align_col(ca, cb)
             for ca, cb in zip(a.columns, b.columns)]
    return (ColumnBatch(a.schema, [p[0] for p in pairs], a.num_rows),
            ColumnBatch(b.schema, [p[1] for p in pairs], b.num_rows))


def merge_sorted(a: ColumnBatch, b: ColumnBatch, orders,
                 out_cap: int = None) -> ColumnBatch:
    """Merge two batches already sorted by `orders` into one sorted batch
    (cuDF `Table.merge` analog). `out_cap` only needs to hold the LIVE
    rows (pass next_capacity(rows_a + rows_b) to avoid capacity bloat
    across merge-tree levels); dead-row scatters are dropped."""
    a, b = align_string_widths(a, b)
    ka = order_keys(a, orders)
    kb = order_keys(b, orders)
    na = jnp.asarray(a.num_rows, jnp.int32)
    nb = jnp.asarray(b.num_rows, jnp.int32)
    ca, cb = a.capacity, b.capacity
    if out_cap is None:
        out_cap = next_capacity(ca + cb)
    # count of live b-rows strictly before each a-row (ties -> a first)
    pos_b = _binary_search(kb, ka, nb, cb, upper=False)
    # count of live a-rows at-or-before each b-row
    pos_a = _binary_search(ka, kb, na, ca, upper=True)
    live_a = jnp.arange(ca, dtype=jnp.int32) < na
    live_b = jnp.arange(cb, dtype=jnp.int32) < nb
    dest_a = jnp.arange(ca, dtype=jnp.int32) + pos_b
    dest_b = jnp.arange(cb, dtype=jnp.int32) + pos_a
    # dead rows scatter out of range -> dropped
    dest_a = jnp.where(live_a, dest_a, out_cap)
    dest_b = jnp.where(live_b, dest_b, out_cap)

    def scat(xa, xb):
        # trailing dims already aligned by align_string_widths
        shape = (out_cap,) + tuple(xa.shape[1:])
        out = jnp.zeros(shape, xa.dtype)
        out = out.at[dest_b].set(xb, mode="drop")
        return out.at[dest_a].set(xa, mode="drop")

    def merge_col(fa: DeviceColumn, fb: DeviceColumn) -> DeviceColumn:
        # constructs FRESH columns (replace() is for rebuilds of one
        # source column); vrange is dropped ON PURPOSE — fa's bound
        # does not bound fb's values
        val = scat(fa.validity, fb.validity)
        if fa.children is not None:  # structs: recurse per field
            kids = [merge_col(ka_, kb_)
                    for ka_, kb_ in zip(fa.children, fb.children)]
            return DeviceColumn(fa.dtype,
                                jnp.zeros((out_cap,), jnp.int8), val,
                                children=kids)
        data = scat(fa.data, fb.data)
        lens = (None if fa.lengths is None
                else scat(fa.lengths, fb.lengths))
        ev = (None if fa.elem_validity is None
              else scat(fa.elem_validity, fb.elem_validity))
        mv = (None if fa.map_values is None
              else scat(fa.map_values, fb.map_values))
        el = (None if fa.elem_lengths is None
              else scat(fa.elem_lengths, fb.elem_lengths))
        return DeviceColumn(fa.dtype, data, val, lens, ev, mv,
                            elem_lengths=el)

    cols = [merge_col(fa, fb) for fa, fb in zip(a.columns, b.columns)]
    return ColumnBatch(a.schema, cols, na + nb)
