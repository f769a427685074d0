"""Device-resident relation cache — Spark's CacheManager +
InMemoryRelation pair with HBM as the storage tier.

The reference accelerates Spark's `df.cache()` by GPU-encoding cached
data as parquet blobs (`ParquetCachedBatchSerializer.scala`) that are
re-DECODED on every reuse; here every reuse would then also pay the
host->device upload again (PERF.md has the link's measured rate). The
TPU-native design keeps the cached relation AS DEVICE BATCHES: HBM is
16 GB/chip and the spill catalog already tiers DEVICE->HOST->DISK, so
cached relations are SpillableBatches — hot queries read them at HBM
bandwidth, and memory pressure demotes them instead of failing.

Usage mirrors Spark:

    base = spark.read.parquet(path).cache(storage="device")
    base.filter(...).groupBy(...).agg(...)   # serves from HBM

Matching is by CANONICAL plan structure (plan/logical.py plan_key),
Spark CacheManager's canonicalized-plan discipline: a freshly built
`spark.read.parquet(same_path)` hits a cache registered by an earlier,
independent DataFrame over the same path. Entries are explicitly
managed (`unpersist`), like Spark's — no file-mtime invalidation.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

import pyarrow as pa


class DeviceCacheEntry:
    """Lazily materialized device-resident copy of one logical subtree.

    `parts` are catalog SpillableBatches: pinned handles that the spill
    framework may demote to host/disk under pressure and transparently
    restore on access.
    """

    def __init__(self, logical, conf):
        self.logical = logical
        self.conf = conf
        self._spills: Optional[List] = None
        self._released = False
        self._lock = threading.Lock()

    @property
    def schema(self):
        return self.logical.schema

    def _child_physical(self):
        from spark_rapids_tpu.plan.optimizer import optimize
        from spark_rapids_tpu.plan.overrides import plan_query

        phys, _ = plan_query(optimize(self.logical), self.conf)
        return phys

    def materialize(self) -> None:
        with self._lock:
            if self._released:
                # a released entry must not silently re-run its plan
                # (source files may be gone; fresh spillables would
                # leak — nothing owns a released entry anymore)
                raise RuntimeError(
                    "cached relation was unpersisted; re-cache the "
                    "DataFrame to use it again")
            if self._spills is not None:
                return
            from spark_rapids_tpu.runtime.memory import get_catalog

            phys = self._child_physical()
            parts = None
            try:
                from spark_rapids_tpu.exec.fused import (
                    FusedCompileError,
                    FusedSingleChipExecutor,
                )

                parts = FusedSingleChipExecutor(
                    self.conf).execute_parts(phys)
            except (FusedCompileError, NotImplementedError):
                pass
            if parts is None:
                # arbitrary plan: run it on the standard engine, upload
                # the result once
                from spark_rapids_tpu.exec.fused import upload_narrowed

                table = phys.collect()
                parts = [upload_narrowed(table)] if table.num_rows \
                    else []
            catalog = get_catalog()
            self._spills = [catalog.add_batch(b) for b in parts]

    def num_parts(self) -> int:
        """Partition count WITHOUT touching batch data (a get_batch
        sweep would re-promote every spilled part to HBM just to take a
        length)."""
        self.materialize()
        with self._lock:
            return len(self._spills) if self._spills is not None else 0

    def _drop_lost(self) -> None:
        """A device-loss recovery invalidated this entry's device-tier
        spillables (runtime/device_monitor.py): close the stale
        handles and let the next access re-run the cached plan — the
        relation cache's lineage is its logical plan, so 'restore' is
        a rematerialization in the new epoch."""
        with self._lock:
            if self._spills is not None:
                for sb in self._spills:
                    try:
                        sb.close()
                    except Exception:
                        pass
                self._spills = None

    def device_part(self, i: int):
        """One materialized part (unspilling only that part). A stale
        entry from before a device-loss recovery rematerializes once."""
        from spark_rapids_tpu.runtime.errors import DeviceLostError

        for attempt in (0, 1):
            self.materialize()
            # hold the lock through get_batch: a concurrent release()
            # may not close handles mid-access (unspill happens under
            # the lock; it never re-enters this entry)
            try:
                with self._lock:
                    if self._spills is None or i >= len(self._spills):
                        raise IndexError(
                            f"cached relation part {i} released")
                    return self._spills[i].get_batch()
            except DeviceLostError:
                if attempt:
                    raise
                self._drop_lost()

    def device_parts(self) -> List:
        """Materialized device ColumnBatches (unspilling as needed);
        a stale entry from before a device-loss recovery
        rematerializes once."""
        from spark_rapids_tpu.runtime.errors import DeviceLostError

        for attempt in (0, 1):
            self.materialize()
            try:
                with self._lock:
                    spills = list(self._spills) \
                        if self._spills is not None else []
                    return [sb.get_batch() for sb in spills]
            except DeviceLostError:
                if attempt:
                    raise
                self._drop_lost()

    def collect(self) -> pa.Table:
        from spark_rapids_tpu.columnar.arrow_bridge import device_to_arrow

        parts = self.device_parts()
        if not parts:
            from spark_rapids_tpu.columnar.batch import empty_like_schema

            return device_to_arrow(empty_like_schema(self.schema, 1024))
        tables = [device_to_arrow(p) for p in parts]
        return pa.concat_tables(tables)

    def release(self) -> None:
        with self._lock:
            self._released = True
            if self._spills is not None:
                for sb in self._spills:
                    try:
                        sb.close()
                    except Exception:
                        pass
                self._spills = None


class CacheManager:
    """Session-level registry: canonical plan key -> DeviceCacheEntry.

    Keys are structural (plan/logical.py plan_key) — Spark's
    canonicalized-plan matching — so an independently re-built
    DataFrame over the same source and transforms hits the cache, not
    just DataFrames derived from the cached object."""

    def __init__(self):
        self._entries: Dict[tuple, DeviceCacheEntry] = {}
        self._lock = threading.Lock()

    @staticmethod
    def _key(logical) -> tuple:
        from spark_rapids_tpu.plan.logical import plan_key

        return plan_key(logical)

    def register(self, logical, conf) -> DeviceCacheEntry:
        key = self._key(logical)
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                entry = DeviceCacheEntry(logical, conf)
                self._entries[key] = entry
            return entry

    def lookup(self, logical) -> Optional[DeviceCacheEntry]:
        with self._lock:
            if not self._entries:  # keys are O(plan); skip when empty
                return None
        key = self._key(logical)
        with self._lock:
            return self._entries.get(key)

    def unregister(self, logical) -> None:
        key = self._key(logical)
        with self._lock:
            entry = self._entries.pop(key, None)
        if entry is not None:
            entry.release()

    def clear(self) -> None:
        with self._lock:
            entries = list(self._entries.values())
            self._entries.clear()
        for e in entries:
            e.release()

    def substitute(self, logical):
        """Rewrite a logical tree, replacing registered subtrees with
        CachedRelation leaves (Spark CacheManager.useCachedData role).
        Structural: any subtree canonically equal to a registered plan
        serves from the cache, shared object or not. Keys compose
        bottom-up in ONE pass (plan_own_key), not per-subtree."""
        import copy

        from spark_rapids_tpu.plan import logical as L
        from spark_rapids_tpu.plan.logical import plan_own_key

        with self._lock:
            if not self._entries:
                return logical

        def walk(node):
            """-> (key, possibly-rewritten node)"""
            results = [walk(c) for c in node.children]
            key = (type(node).__name__, plan_own_key(node),
                   tuple(k for k, _ in results))
            with self._lock:
                entry = self._entries.get(key)
            if entry is not None:
                return key, L.CachedRelation(entry)
            new_children = [c for _, c in results]
            if all(n is o for n, o in zip(new_children, node.children)):
                return key, node
            node = copy.copy(node)
            node.children = new_children
            return key, node

        return walk(logical)[1]
