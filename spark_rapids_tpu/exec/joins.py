"""Join operator family — the GpuHashJoin/GpuBroadcastHashJoin/
GpuBroadcastNestedLoopJoin analogs.

Reference surface being reproduced (SURVEY.md section 2.5 "Joins"):
- GpuShuffledHashJoinExec (GpuShuffledHashJoinExec.scala:107): partitioned
  equi-join via gather maps (GpuHashJoin.scala:403,490-564).
- Conditional ("mixed") joins: cuDF mixed*JoinGatherMaps fuse an AST
  condition with the hash probe. The TPU formulation materializes the
  key-equal candidate pairs as gather maps, evaluates the bound condition
  expression over the gathered pair batch in the same XLA program, and
  derives every join type from the surviving-pair mask.
- GpuBroadcastHashJoinExecBase.scala:204: build side materialized once
  and shared across probe partitions (no exchange on either side).
- GpuBroadcastNestedLoopJoinExecBase.scala:815 + GpuCartesianProductExec:
  cross/condition-only joins via full pair expansion.
- ExistenceJoin.scala: left rows + a boolean `exists` column.

The CPU oracle generalizes pyarrow joins with an index-pair algorithm so
conditional/cross/existence joins diff-test against the device path.
"""

from __future__ import annotations

import threading
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from spark_rapids_tpu.columnar.batch import (
    ColumnBatch,
    DeviceColumn,
    concat_batches,
    empty_like_schema,
    next_capacity,
)
from spark_rapids_tpu.exec import cpu_eval
from spark_rapids_tpu.exec.base import PhysicalPlan
from spark_rapids_tpu.expr import BoundReference, EvalContext
from spark_rapids_tpu.expr.core import Expression
from spark_rapids_tpu.ops import filterops, joinops
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.sqltypes import StructField, StructType
from spark_rapids_tpu.sqltypes.datatypes import boolean, to_arrow_type

def remap_refs(expr: Expression, fn) -> Expression:
    """Rewrite every BoundReference ordinal through fn(ordinal)."""

    def rewrite(node):
        if isinstance(node, BoundReference):
            return BoundReference(fn(node.ordinal), node.dtype,
                                  node.nullable)
        return node

    return expr.transform(rewrite)


def swap_condition(cond: Expression, n_left: int,
                   n_right: int) -> Expression:
    """Remap a condition bound to [left|right] ordinals onto the swapped
    [right|left] layout."""
    return remap_refs(
        cond, lambda o: o + n_right if o < n_left else o - n_left)


class _DeviceJoinBase(PhysicalPlan):
    """Shared device join machinery over candidate-pair gather maps."""

    def __init__(self, left, right, join_type: str,
                 left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 condition: Optional[Expression], schema, conf):
        super().__init__([left, right], schema, conf)
        self.join_type = join_type
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.condition = condition

    # --- helpers ---

    def _prepare_keys(self, batch: ColumnBatch, keys):
        """Return (batch_with_keys, key_ordinals). Plain column refs use
        the batch directly; computed keys (e.g. implicit casts) are
        evaluated and appended as temp columns."""
        if all(isinstance(k, BoundReference) for k in keys):
            return batch, [k.ordinal for k in keys]
        ctx = EvalContext(batch)
        kcols = [k.eval(ctx) for k in keys]
        fields = list(batch.schema.fields) + [
            StructField(f"__jk{i}", c.dtype, True)
            for i, c in enumerate(kcols)]
        work = ColumnBatch(StructType(fields),
                           list(batch.columns) + kcols, batch.num_rows)
        n0 = len(batch.columns)
        return work, list(range(n0, n0 + len(keys)))

    def _pair_schema(self) -> StructType:
        lsch = self.children[0].schema
        rsch = self.children[1].schema
        return StructType(list(lsch.fields) + list(rsch.fields))

    def _left_nulls_batch(self, lsch, right_batch: ColumnBatch
                          ) -> ColumnBatch:
        """All-null left columns + the given right rows."""
        nulls = empty_like_schema(lsch, right_batch.capacity)
        cols = nulls.columns + right_batch.columns
        schema = StructType(list(lsch.fields) +
                            list(right_batch.schema.fields))
        return ColumnBatch(schema, cols, right_batch.num_rows)

    def _right_nulls_batch(self, left_batch: ColumnBatch, rsch
                           ) -> ColumnBatch:
        nulls = empty_like_schema(rsch, left_batch.capacity)
        schema = StructType(list(left_batch.schema.fields) +
                            list(rsch.fields))
        return ColumnBatch(schema, left_batch.columns + nulls.columns,
                           left_batch.num_rows)

    def _exists_batch(self, left: ColumnBatch, matched) -> ColumnBatch:
        col = DeviceColumn(boolean, matched,
                           jnp.ones((left.capacity,), bool))
        return ColumnBatch(self.schema, list(left.columns) + [col],
                           left.num_rows)

    # --- the pair engine ---

    def _gather_pairs(self, left: ColumnBatch, build: ColumnBatch,
                      pi, bi, num_rows) -> ColumnBatch:
        pair_cols = ([c.gather(pi) for c in left.columns] +
                     [c.gather(jnp.clip(bi, 0, build.capacity - 1))
                      for c in build.columns])
        return ColumnBatch(self._pair_schema(), pair_cols, num_rows)

    def _finish_from_pairs(self, left: ColumnBatch, build: ColumnBatch,
                           pi, bi, ok, total_cap: int,
                           pair_batch: Optional[ColumnBatch] = None,
                           jt_override: Optional[str] = None
                           ) -> ColumnBatch:
        """Derive any join type from candidate pairs (pi, bi) and the
        surviving-pair mask ok (condition AND key-equality AND live).
        `pair_batch` reuses an already-gathered pair table (from
        condition evaluation) to avoid a second full gather.
        `jt_override` lets chunked drivers run a full-outer join as
        per-chunk left-outer while they accumulate build-match state
        themselves (GpuBroadcastNestedLoopJoinExecBase splitting)."""
        jt = jt_override or self.join_type
        lsch = self.children[0].schema
        rsch = self.children[1].schema
        matched_l = (jnp.zeros((left.capacity,), jnp.int32)
                     .at[pi].max(jnp.where(ok, 1, 0)) > 0)
        if jt == "left_semi":
            return filterops.compact(left, matched_l)
        if jt == "left_anti":
            return filterops.compact(left, ~matched_l)
        if jt == "existence":
            return self._exists_batch(left, matched_l)

        n_pairs = jnp.sum(jnp.where(ok, 1, 0)).astype(jnp.int32)
        if pair_batch is None:
            pair_batch = self._gather_pairs(left, build, pi, bi, n_pairs)
        else:
            pair_batch = ColumnBatch(pair_batch.schema, pair_batch.columns,
                                     n_pairs)
        # compact survivors to the front (ok is not necessarily prefix)
        perm, _ = filterops.compact_perm(ok, total_cap)
        pair_batch = pair_batch.gather(perm, n_pairs)
        if jt in ("inner", "cross"):
            return pair_batch
        # outer padding
        parts = [pair_batch]
        if jt in ("left", "full"):
            left_un = filterops.compact(left, ~matched_l)
            if left_un.row_count() > 0:
                parts.append(self._right_nulls_batch(left_un, rsch))
        if jt == "full":
            matched_b = (jnp.zeros((build.capacity,), jnp.int32)
                         .at[jnp.clip(bi, 0, build.capacity - 1)]
                         .max(jnp.where(ok, 1, 0)) > 0)
            right_un = filterops.compact(build, ~matched_b)
            if right_un.row_count() > 0:
                parts.append(self._left_nulls_batch(lsch, right_un))
        out = concat_batches(parts) if len(parts) > 1 else parts[0]
        return ColumnBatch(self.schema, out.columns, out.num_rows)

    def _conditional_equi_join(self, left: ColumnBatch,
                               bt: joinops.BuildTable,
                               lo, counts) -> ColumnBatch:
        from spark_rapids_tpu.obs import telemetry

        total = int(telemetry.ledgered_get(jnp.sum(counts),
                                           "join.counts"))
        cap = next_capacity(max(total, 1))
        pi, bi, _ = joinops.expand_gather_maps(lo, counts, cap)
        pair_live = jnp.arange(cap, dtype=jnp.int32) < total
        ok = pair_live
        pair_batch = None
        if self.condition is not None:
            pair_batch = self._gather_pairs(left, bt.batch, pi, bi, total)
            pred = self.condition.eval(EvalContext(pair_batch))
            ok = ok & pred.data & pred.validity
        return self._finish_from_pairs(left, bt.batch, pi, bi, ok, cap,
                                       pair_batch=pair_batch)

    # --- unconditioned fast paths (no pair materialization) ---

    def _fast_equi_join(self, left: ColumnBatch, bt: joinops.BuildTable,
                        lo, counts) -> Optional[ColumnBatch]:
        jt = self.join_type
        lsch = self.children[0].schema
        rsch = self.children[1].schema
        right = bt.batch
        if jt == "left_semi":
            return filterops.compact(left, counts > 0)
        if jt == "left_anti":
            return filterops.compact(left, counts == 0)
        if jt == "existence":
            return self._exists_batch(left, counts > 0)
        eff_counts = counts
        if jt in ("left", "full"):
            live = left.live_mask()
            eff_counts = jnp.where(live & (counts == 0), 1, counts)
        from spark_rapids_tpu.obs import telemetry

        total = int(telemetry.ledgered_get(jnp.sum(eff_counts),
                                           "join.counts"))
        extra = 0
        matched_build = None
        if jt == "full":
            matched_build = self._matched_build_mask(bt, lo, counts)
            extra = int(telemetry.ledgered_get(
                jnp.sum(~matched_build & bt.batch.live_mask()),
                "join.counts"))
        cap_out = next_capacity(total + extra)
        pi, bi, _ = joinops.expand_gather_maps(lo, eff_counts, cap_out)
        lcols = [c.gather(pi) for c in left.columns]
        rcols = [c.gather(jnp.clip(bi, 0, right.capacity - 1))
                 for c in bt.batch.columns]
        if jt in ("left", "full"):
            unmatched = (counts == 0)
            row_unmatched = jnp.take(unmatched, pi)
            rcols = [c.replace(validity=c.validity & ~row_unmatched)
                     for c in rcols]
        out_cols = lcols + rcols
        out_schema = StructType(list(lsch.fields) + list(rsch.fields))
        out = ColumnBatch(out_schema, out_cols, total)
        if jt == "full" and extra > 0:
            unmatched_right = filterops.compact(bt.batch, ~matched_build)
            pad = self._left_nulls_batch(lsch, unmatched_right)
            out = concat_batches([out, pad])
        return out

    def _matched_build_mask(self, bt, lo, counts):
        cap = bt.batch.capacity
        delta = jnp.zeros((cap + 1,), jnp.int32)
        hi = lo + counts
        delta = delta.at[jnp.clip(lo, 0, cap)].add(
            jnp.where(counts > 0, 1, 0))
        delta = delta.at[jnp.clip(hi, 0, cap)].add(
            jnp.where(counts > 0, -1, 0))
        return jnp.cumsum(delta[:-1]) > 0

    # --- empty-side handling shared by hash joins ---

    def _encoded_key_rewrite(self, left: ColumnBatch,
                             right: ColumnBatch):
        """Encoded-execution join-key lowering: when BOTH sides of an
        equi-key are dictionary-encoded columns, compare CODES instead
        of decoded strings. Dictionary identity is checked host-side;
        a mismatched build dictionary RE-ENCODES into the probe's code
        space through a host remap table (encoding.CodesOf) — only
        when neither applies do the keys fall back to the in-device
        decode inside the key transform. Returns (left_keys,
        right_keys), possibly rewritten."""
        from spark_rapids_tpu.columnar import encoding as enc

        lkeys = list(self.left_keys)
        rkeys = list(self.right_keys)
        for i, (lk, rk) in enumerate(zip(lkeys, rkeys)):
            if not (isinstance(lk, BoundReference)
                    and isinstance(rk, BoundReference)):
                continue
            le = getattr(left.columns[lk.ordinal], "encoding", None)
            re_ = getattr(right.columns[rk.ordinal], "encoding", None)
            if le is None or re_ is None:
                continue
            if re_.dict_id != le.dict_id and \
                    enc.remap_table(re_.dict_id, le.dict_id) is None:
                continue  # host dictionary evicted: decode fallback
            lkeys[i] = enc.CodesOf(lk, le.dict_id)
            rkeys[i] = enc.CodesOf(rk, le.dict_id)
        return lkeys, rkeys

    def _join_batches(self, left_batches, right_batches,
                      prepared_bt: Optional[joinops.BuildTable] = None
                      ) -> Optional[ColumnBatch]:
        jt = self.join_type
        if not left_batches and jt in ("inner", "left", "left_semi",
                                       "left_anti", "existence"):
            return None
        if not right_batches and jt in ("inner", "left_semi"):
            return None
        lsch = self.children[0].schema
        rsch = self.children[1].schema
        left = (concat_batches(left_batches) if left_batches else None)
        right = (concat_batches(right_batches) if right_batches else None)
        if left is None:
            if jt in ("right", "full"):
                return self._left_nulls_batch(lsch, right)
            return None
        if right is None:
            if jt == "left_anti":
                return left
            if jt == "existence":
                return self._exists_batch(
                    left, jnp.zeros((left.capacity,), bool))
            if jt in ("left", "full"):
                return self._right_nulls_batch(left, rsch)
            return None
        lkeys, rkeys = self.left_keys, self.right_keys
        if prepared_bt is None:
            # a shared prepared build table was sorted on the ORIGINAL
            # key transform; the codes rewrite only applies when this
            # call builds its own table from both sides in hand
            lkeys, rkeys = self._encoded_key_rewrite(left, right)
        bt = prepared_bt if prepared_bt is not None \
            else self._build_table(right, keys=rkeys)
        left = self._bloom_prefilter(left, right, jt)
        work_l, lk = self._prepare_keys(left, lkeys)
        lo, counts = joinops.probe_ranges(bt, work_l, lk)
        if self.condition is None:
            return self._fast_equi_join(left, bt, lo, counts)
        return self._conditional_equi_join(left, bt, lo, counts)

    def _bloom_prefilter(self, left: ColumnBatch, right: ColumnBatch,
                         jt: str) -> ColumnBatch:
        """Build-side bloom filter applied to the probe side BEFORE the
        hash probe (the runtime-filter role of spark-rapids-jni
        BloomFilter + GpuBloomFilterMightContain): provably-absent keys
        drop and the probe batch re-buckets to a smaller capacity, so
        every downstream gather/expand shrinks. Only for joins where a
        non-matching probe row produces nothing (inner/left_semi)."""
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.ops import bloom

        if jt not in ("inner", "left_semi"):
            return left
        if self.conf is not None and not self.conf.get(
                rc.JOIN_BLOOM_FILTER):
            return left
        build_rows = right.row_count()
        # pay the filter only when the probe side is meaningfully larger
        if build_rows == 0 or left.capacity < 4 * build_rows:
            return left
        # build once per build batch: broadcast joins probe the SAME
        # right batch from every partition (benign race: concurrent
        # probes compute identical bits)
        cached = getattr(self, "_bloom_cache", None)
        if cached is not None and cached[0] is right:
            bits = cached[1]
        else:
            work_r, rk = self._prepare_keys(right, self.right_keys)
            rkeys = [work_r.columns[i] for i in rk]
            bits = bloom.build(rkeys, right.live_mask(),
                               bloom.size_for(build_rows))
            self._bloom_cache = (right, bits)
        work_l, lk = self._prepare_keys(left, self.left_keys)
        lkeys = [work_l.columns[i] for i in lk]
        keep = bloom.might_contain(bits, lkeys)
        rows = left.row_count()
        n = int(jnp.sum(keep & left.live_mask()))
        if n == rows:
            return left  # nothing provably absent: skip the compaction
        self.metrics[M.BLOOM_FILTERED_ROWS].add(rows - n)
        reduced = filterops.compact(left, keep)
        cap2 = next_capacity(n)
        if cap2 >= left.capacity:
            return reduced
        return ColumnBatch(reduced.schema,
                           [c.truncate(cap2) for c in reduced.columns],
                           n)

    def _build_table(self, right: ColumnBatch,
                     keys=None) -> joinops.BuildTable:
        rsch = self.children[1].schema
        work_r, rk = self._prepare_keys(right,
                                        keys if keys is not None
                                        else self.right_keys)
        bt = joinops.build_side(work_r, rk)
        if len(bt.batch.columns) != len(right.columns):
            # strip temp key columns from the (sorted) build batch
            bt = joinops.BuildTable(
                ColumnBatch(rsch,
                            bt.batch.columns[:len(right.columns)],
                            bt.batch.num_rows),
                bt.keys, bt.valid_bound)
        return bt

    def _build_index(self, right: ColumnBatch, live,
                     reads_columns: bool) -> joinops.BuildIndex:
        """The build side indexed where it lies, for a lookup join that
        reads its columns at the rows a probe matched, or (semi, anti,
        existence: `reads_columns` false) reads none."""
        work_r, rk = self._prepare_keys(right, self.right_keys)
        idx = joinops.build_index(work_r, rk, live)
        if not reads_columns:
            return idx._replace(batch=None, perm=None)
        return idx._replace(batch=right)

    def _build_positions(self, right: ColumnBatch, live,
                         reads_columns: bool) -> joinops.BuildPositions:
        """The build side as a row-or-absent table over its key's
        stamped range (`build_key_range` is not None), read by
        position where `_build_index`'s is searched."""
        work_r, rk = self._prepare_keys(right, self.right_keys)
        pos = joinops.build_positions(work_r, rk, live)
        return pos._replace(batch=right if reads_columns else None)

    def build_key_range(self, right: ColumnBatch):
        """The stamped (lo, hi) of the build side's one plain integer
        key column, or None."""
        if not all(isinstance(k, BoundReference) for k in self.right_keys):
            return None
        return joinops.key_range(right, [k.ordinal for k in self.right_keys])

    def build_is_filtered(self) -> bool:
        """Whether the build side sits under a filter: the join then
        filters its probe side, as a WHERE on a dimension's attributes
        does once it is pushed below the join. An inner or semi join on
        the way down is a filter itself where ITS build side is one
        (TPC-H Q3: `orders` under the segment's customers), and its
        probe side is looked at as well."""
        return _is_filtered(self.children[1])


def _is_filtered(node) -> bool:
    from spark_rapids_tpu.exec import operators as ops

    while True:
        if isinstance(node, ops.TpuFilterExec):
            return True
        if (isinstance(node, _DeviceJoinBase)
                and node.join_type in ("inner", "left_semi")):
            if node.build_is_filtered():
                return True
        elif not isinstance(node, (ops.TpuProjectExec,
                                   ops.TpuCoalesceBatchesExec,
                                   ops.TpuShuffleExchangeExec)):
            return False
        node = node.children[0]


class TpuShuffledHashJoinExec(_DeviceJoinBase):
    """Partitioned equi-join; children must be co-partitioned by key
    (the planner inserts exchanges). Right side is the build side.
    Oversized build sides fall back to key-hash sub-partitioning
    (GpuSubPartitionHashJoin.scala): both sides are split into K
    co-partitioned pieces joined independently, bounding the working
    set."""

    def __init__(self, left, right, join_type, left_keys, right_keys,
                 schema, conf, condition: Optional[Expression] = None):
        super().__init__(left, right, join_type, left_keys, right_keys,
                         condition, schema, conf)

    def _build_size_target(self) -> int:
        from spark_rapids_tpu.config import rapids_conf as rc

        return (self.conf.get(rc.BATCH_SIZE_BYTES) if self.conf
                else 1 << 30)

    def _hash_split(self, batch: ColumnBatch, keys, nparts: int
                    ) -> List[Optional[ColumnBatch]]:
        """Split one batch into nparts key-hash co-partitions (seeded
        differently from the shuffle so the split is non-degenerate
        post-exchange)."""
        from spark_rapids_tpu.ops import partition as P

        work, kidx = self._prepare_keys(batch, keys)
        parts = P.split_to_slices(work, kidx, nparts,
                                  seed=P.SUB_PARTITION_SEED)
        if len(work.columns) != len(batch.columns):
            n0 = len(batch.columns)
            parts = [p.select(list(range(n0))) if p is not None else None
                     for p in parts]
        return parts

    def execute_partition(self, pid, ctx):
        with self.metrics[M.JOIN_TIME].ns():
            right_batches = list(
                self.children[1].execute_partition(pid, ctx))
            left_batches = list(
                self.children[0].execute_partition(pid, ctx))
            build_bytes = sum(b.device_size_bytes()
                              for b in right_batches)
            target = self._build_size_target()
            if build_bytes > target and left_batches and right_batches:
                nparts = max(2, -(-build_bytes // target))
                right = concat_batches(right_batches)
                left = concat_batches(left_batches)
                rparts = self._hash_split(right, self.right_keys, nparts)
                lparts = self._hash_split(left, self.left_keys, nparts)
                for lp, rp in zip(lparts, rparts):
                    out = self._join_batches(
                        [lp] if lp is not None else [],
                        [rp] if rp is not None else [])
                    if out is not None:
                        yield out
                return
            out = self._join_batches(left_batches, right_batches)
            if out is not None:
                yield out


_node_lock_guard = threading.Lock()


def _node_bcast_lock(node) -> threading.Lock:
    """Per-node build lock, created lazily (node objects are plan
    nodes; the lock's lifetime is the plan's)."""
    with _node_lock_guard:
        lk = getattr(node, "_srtpu_bcast_lock", None)
        if lk is None:
            lk = threading.Lock()
            node._srtpu_bcast_lock = lk
        return lk


class _BroadcastBuildMixin:
    """Materializes the build (right) side exactly once, shared by every
    probe partition. Subclasses call _init_broadcast() in __init__."""

    def _init_broadcast(self):
        self._bcast_lock = threading.Lock()

    @property
    def num_partitions(self):
        return self.children[0].num_partitions

    def _broadcast_build(self, ctx) -> List[ColumnBatch]:
        """Materialize the build side ONCE per build NODE: the cache
        lives on the child, so joins sharing a deduped build subtree
        (plan/broadcast_reuse.py, the ReusedExchange role) share the
        device-resident batches too."""
        rchild = self.children[1]
        with _node_bcast_lock(rchild):
            cache = getattr(rchild, "_srtpu_bcast_batches", None)
            if cache is None:
                batches: List[ColumnBatch] = []
                for rp in range(rchild.num_partitions):
                    batches.extend(rchild.execute_partition(rp, ctx))
                cache = [concat_batches(batches)] if batches else []
                rchild._srtpu_bcast_batches = cache
            return cache


class TpuBroadcastHashJoinExec(_BroadcastBuildMixin, _DeviceJoinBase):
    """Equi-join with the (small) right side materialized ONCE and shared
    by every probe partition — no exchange on either side
    (GpuBroadcastHashJoinExecBase.scala:204). Not valid for full outer
    (build-side match tracking would span partitions); the planner only
    selects it for inner/left/semi/anti/existence."""

    def __init__(self, left, right, join_type, left_keys, right_keys,
                 schema, conf, condition: Optional[Expression] = None):
        assert join_type != "full", "broadcast build cannot do full outer"
        super().__init__(left, right, join_type, left_keys, right_keys,
                         condition, schema, conf)
        self._init_broadcast()

    def _broadcast_build_table(self, ctx):
        """(build_batches, prepared BuildTable) — the sorted build table
        is computed once per (shared build node, join keys): joins that
        share a deduped build subtree AND sort it by the same keys share
        the prepared table and its device residency too."""
        batches = self._broadcast_build(ctx)
        rchild = self.children[1]
        keys = tuple(k.key() for k in self.right_keys)
        with _node_bcast_lock(rchild):
            bts = getattr(rchild, "_srtpu_bcast_bt", None)
            if bts is None:
                bts = {}
                rchild._srtpu_bcast_bt = bts
            bt = bts.get(keys)
            if batches and bt is None:
                bt = self._build_table(batches[0])
                bts[keys] = bt
            return batches, bt

    def execute_partition(self, pid, ctx):
        with self.metrics[M.JOIN_TIME].ns():
            build, bt = self._broadcast_build_table(ctx)
            left_batches = list(
                self.children[0].execute_partition(pid, ctx))
            out = self._join_batches(left_batches, build, prepared_bt=bt)
            if out is not None:
                yield out


class TpuBroadcastNestedLoopJoinExec(_BroadcastBuildMixin, _DeviceJoinBase):
    """Cross / condition-only joins: expand the full candidate pair set
    (probe x broadcast build) as gather maps, evaluate the condition over
    the gathered pairs, and derive the join type from the survivor mask
    (GpuBroadcastNestedLoopJoinExecBase.scala:815,
    GpuCartesianProductExec.scala). full/right variants are planned onto
    a single partition so build-match tracking is local."""

    def __init__(self, left, right, join_type, schema, conf,
                 condition: Optional[Expression] = None):
        super().__init__(left, right, join_type, [], [], condition,
                         schema, conf)
        self._init_broadcast()

    def _nlj_chunk(self, left: ColumnBatch, right: ColumnBatch
                   ) -> Optional[ColumnBatch]:
        """Join one probe chunk against the whole build side. For full
        outer, runs as left-outer and accumulates the build-match mask
        into self._nlj_matched_build; the driver pads unmatched build
        rows once after all chunks."""
        jt = self.join_type
        n_l = left.row_count()
        n_r = right.row_count()
        cap = next_capacity(max(n_l * n_r, 1))
        counts = jnp.where(left.live_mask(),
                           jnp.int32(n_r), jnp.int32(0))
        lo = jnp.zeros((left.capacity,), jnp.int32)
        pi, bi, _ = joinops.expand_gather_maps(lo, counts, cap)
        total = n_l * n_r
        ok = jnp.arange(cap, dtype=jnp.int64) < total
        pair_batch = None
        if self.condition is not None:
            pair_batch = self._gather_pairs(left, right, pi, bi, total)
            pred = self.condition.eval(EvalContext(pair_batch))
            ok = ok & pred.data & pred.validity
        jt_override = None
        if jt == "full":
            matched_b = (jnp.zeros((right.capacity,), jnp.int32)
                         .at[jnp.clip(bi, 0, right.capacity - 1)]
                         .max(jnp.where(ok, 1, 0)) > 0)
            self._nlj_matched_build = self._nlj_matched_build | matched_b
            jt_override = "left"
        return self._finish_from_pairs(left, right, pi, bi, ok, cap,
                                       pair_batch=pair_batch,
                                       jt_override=jt_override)

    def execute_partition(self, pid, ctx):
        from spark_rapids_tpu.runtime.memory import get_catalog
        from spark_rapids_tpu.runtime.retry import retry_on_oom, with_retry

        with self.metrics[M.JOIN_TIME].ns():
            build = self._broadcast_build(ctx)
            left_batches = list(
                self.children[0].execute_partition(pid, ctx))
            jt = self.join_type
            lsch = self.children[0].schema
            rsch = self.children[1].schema
            if not left_batches:
                if jt == "full" and build:
                    yield self._left_nulls_batch(lsch, build[0])
                return
            left = concat_batches(left_batches)
            if not build:
                if jt == "left_anti":
                    yield left
                elif jt == "existence":
                    yield self._exists_batch(
                        left, jnp.zeros((left.capacity,), bool))
                elif jt in ("left", "full"):
                    yield self._right_nulls_batch(left, rsch)
                return
            right = build[0]
            # Ledger honesty: the real allocation of a nested-loop join
            # is the expanded pair set (n_l * n_r rows), invisible to the
            # output-only reservation the other operators use. Reserve it
            # up front and split the probe side in half on
            # TpuSplitAndRetryOOM (GpuBroadcastNestedLoopJoinExecBase
            # split machinery).
            catalog = get_catalog()
            row_bytes = (
                left.device_size_bytes() // max(1, left.capacity) +
                right.device_size_bytes() // max(1, right.capacity))
            self._nlj_matched_build = jnp.zeros((right.capacity,), bool)
            sb = retry_on_oom(lambda: catalog.add_batch(left))

            def step(s):
                chunk = s.get_batch()
                pair_cap = next_capacity(
                    max(chunk.row_count() * right.row_count(), 1))
                with catalog.reserved(pair_cap * row_bytes, "nlj_pairs"):
                    return self._nlj_chunk(chunk, right)

            for out in with_retry(sb, step):
                if out is not None:
                    yield out
            if jt == "full":
                unmatched = filterops.compact(
                    right,
                    ~self._nlj_matched_build & right.live_mask())
                if unmatched.row_count() > 0:
                    yield self._left_nulls_batch(lsch, unmatched)


class CpuJoinExec(PhysicalPlan):
    """CPU fallback/oracle. Plain equi-joins use pyarrow Table.join;
    conditional/cross/existence joins use an index-pair algorithm:
    candidate (lidx, ridx) pairs -> condition mask -> per-type assembly."""

    is_tpu = False

    _ARROW_TYPE = {"inner": "inner", "left": "left outer",
                   "right": "right outer", "full": "full outer",
                   "left_semi": "left semi", "left_anti": "left anti"}

    def __init__(self, left, right, join_type, left_keys, right_keys,
                 schema, conf, condition: Optional[Expression] = None):
        super().__init__([left, right], schema, conf)
        self.join_type = join_type
        self.left_keys = left_keys
        self.right_keys = right_keys
        self.condition = condition

    def execute_partition(self, pid, ctx):
        lt = list(self.children[0].execute_partition(pid, ctx))
        rt = list(self.children[1].execute_partition(pid, ctx))
        if not lt and not rt:
            return
        lsch = self.children[0].schema
        rsch = self.children[1].schema

        def mk(tables, sch):
            if tables:
                return pa.concat_tables(tables, promote_options="none")
            arrow_schema = pa.schema([
                pa.field(f.name, to_arrow_type(f.dataType))
                for f in sch.fields])
            return arrow_schema.empty_table()

        left = mk(lt, lsch)
        right = mk(rt, rsch)
        nested_payload = any(
            pa.types.is_nested(f.type)
            for f in list(left.schema) + list(right.schema))
        if (self.condition is None and self.left_keys and
                self.join_type in self._ARROW_TYPE and
                not nested_payload and
                all(isinstance(k, BoundReference)
                    for k in list(self.left_keys) + list(self.right_keys))):
            yield self._arrow_join(left, right, lsch, rsch)
            return
        yield self._pair_join(left, right)

    # --- plain equi path (arrow native) ---

    def _arrow_join(self, left, right, lsch, rsch):
        lnames = [lsch.names[k.ordinal] for k in self.left_keys]
        rnames = [rsch.names[k.ordinal] for k in self.right_keys]
        joined = left.join(
            right, keys=lnames, right_keys=rnames,
            join_type=self._ARROW_TYPE[self.join_type],
            coalesce_keys=False)
        want = self.schema.names
        have = joined.column_names
        cols = []
        for i, nm in enumerate(want):
            idx = have.index(nm)
            cols.append(joined.column(idx))
            have[idx] = None  # consume duplicates in order
        if len(set(want)) == len(want):
            return pa.table(dict(zip(want, cols)))
        return pa.Table.from_arrays(
            [c.combine_chunks() for c in cols], names=want)

    # --- general pair path ---

    def _candidate_pairs(self, left: pa.Table, right: pa.Table):
        n_l, n_r = left.num_rows, right.num_rows
        if self.left_keys:
            lcols = {f"k{i}": cpu_eval.eval_expr(k, left)
                     for i, k in enumerate(self.left_keys)}
            lcols["__lidx"] = pa.array(np.arange(n_l, dtype=np.int64))
            rcols = {f"k{i}": cpu_eval.eval_expr(k, right)
                     for i, k in enumerate(self.right_keys)}
            rcols["__ridx"] = pa.array(np.arange(n_r, dtype=np.int64))
            knames = [f"k{i}" for i in range(len(self.left_keys))]
            pairs = pa.table(lcols).join(pa.table(rcols), keys=knames,
                                         join_type="inner")
            lidx = np.asarray(pairs.column("__lidx"))
            ridx = np.asarray(pairs.column("__ridx"))
            return lidx, ridx
        lidx = np.repeat(np.arange(n_l, dtype=np.int64), n_r)
        ridx = np.tile(np.arange(n_r, dtype=np.int64), n_l)
        return lidx, ridx

    def _pair_join(self, left: pa.Table, right: pa.Table) -> pa.Table:
        import pyarrow.compute as pc

        jt = self.join_type
        n_l, n_r = left.num_rows, right.num_rows
        lidx, ridx = self._candidate_pairs(left, right)
        if self.condition is not None and len(lidx):
            lpart = left.take(pa.array(lidx))
            rpart = right.take(pa.array(ridx))
            pair_table = pa.Table.from_arrays(
                [c.combine_chunks() for c in lpart.columns] +
                [c.combine_chunks() for c in rpart.columns],
                names=list(left.column_names) + list(right.column_names))
            mask = cpu_eval.eval_expr(self.condition, pair_table)
            ok = np.asarray(pc.fill_null(mask, False))
            lidx, ridx = lidx[ok], ridx[ok]
        matched_l = np.zeros(n_l, dtype=bool)
        matched_l[lidx] = True
        if jt == "left_semi":
            return left.take(pa.array(np.flatnonzero(matched_l)))
        if jt == "left_anti":
            return left.take(pa.array(np.flatnonzero(~matched_l)))
        if jt == "existence":
            arrays = [c.combine_chunks() for c in left.columns]
            arrays.append(pa.array(matched_l))
            return pa.Table.from_arrays(
                arrays, names=list(left.column_names) +
                [self.schema.names[-1]])

        def pair_rows(li, ri):
            lpart = left.take(pa.array(li))
            rpart = right.take(pa.array(ri))
            return ([c.combine_chunks() for c in lpart.columns],
                    [c.combine_chunks() for c in rpart.columns])

        lcols, rcols = pair_rows(lidx, ridx)
        chunks_l = [lcols]
        chunks_r = [rcols]
        if jt in ("left", "full"):
            un = np.flatnonzero(~matched_l)
            if len(un):
                lpart = left.take(pa.array(un))
                chunks_l.append([c.combine_chunks() for c in lpart.columns])
                chunks_r.append([
                    pa.nulls(len(un), type=to_arrow_type(f.dataType))
                    for f in self.children[1].schema.fields])
        if jt in ("right", "full"):
            matched_r = np.zeros(n_r, dtype=bool)
            matched_r[ridx] = True
            un = np.flatnonzero(~matched_r)
            if len(un):
                rpart = right.take(pa.array(un))
                chunks_l.append([
                    pa.nulls(len(un), type=to_arrow_type(f.dataType))
                    for f in self.children[0].schema.fields])
                chunks_r.append([c.combine_chunks() for c in rpart.columns])
        arrays = []
        n_lc = left.num_columns
        for ci in range(n_lc):
            arrays.append(pa.concat_arrays(
                [chunk[ci].cast(to_arrow_type(
                    self.children[0].schema.fields[ci].dataType))
                 for chunk in chunks_l]))
        for ci in range(right.num_columns):
            arrays.append(pa.concat_arrays(
                [chunk[ci].cast(to_arrow_type(
                    self.children[1].schema.fields[ci].dataType))
                 for chunk in chunks_r]))
        return pa.Table.from_arrays(arrays, names=self.schema.names)
