"""Planner-driven SPMD execution — compile a physical plan into ONE
shard_map'd XLA program over a device mesh.

The single-chip engine executes planner output as thread-pool tasks with
an in-process shuffle manager. In mesh mode (`spark.rapids.tpu.mesh=N`)
the SAME planner output compiles into a single SPMD program over an
N-device `jax.sharding.Mesh`:

- every `TpuShuffleExchangeExec` becomes an `all_to_all` collective
  riding ICI (the reference's UCX P2P transport role,
  `shuffle/RapidsShuffleTransport.scala:303`, `RapidsShuffleClient.scala:95`,
  `shuffle-plugin/.../ucx/UCX.scala` — replaced by compiled collectives,
  SURVEY.md section 5.8),
- broadcast-join builds become `all_gather` (GpuBroadcastExchangeExec),
- global sort becomes a sample-based range exchange + per-shard sort
  (GpuRangePartitioner.scala + GpuSortExec, distributed),
- unary operators (project/filter/aggregate phases/limit) trace their
  per-shard phase functions inline, fused by XLA.

Data-dependent sizes use the engine's standard static-capacity +
overflow-flag discipline: each collective slot / join expansion has a
static capacity; any overflow raises TpuSplitAndRetryOOM on the host and
the whole program recompiles with a doubled expansion factor.

Plans containing operators without a mesh lowering raise
MeshCompileError; the session falls back to the thread-pool engine.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
from jax import lax
from jax.sharding import PartitionSpec as P

from spark_rapids_tpu.columnar.arrow_bridge import (
    arrow_to_device,
    device_to_arrow,
)
from spark_rapids_tpu.columnar.batch import (
    ColumnBatch,
    DeviceColumn,
    next_capacity,
)
from spark_rapids_tpu.exec import joins as J
from spark_rapids_tpu.exec import operators as ops
from spark_rapids_tpu.exec.base import PhysicalPlan
from spark_rapids_tpu.expr import EvalContext
from spark_rapids_tpu.ops import filterops, joinops
from spark_rapids_tpu.ops.hashing import murmur3_columns, pmod
from spark_rapids_tpu.ops.joinops import _binary_search
from spark_rapids_tpu.ops.sortops import order_keys, sort_batch
from spark_rapids_tpu.parallel import mesh_exec
from spark_rapids_tpu.parallel.collective import (
    all_gather_batch,
    all_to_all_batch,
    gather_to_one,
    slot_capacity,
)
from spark_rapids_tpu.runtime.errors import TpuSplitAndRetryOOM
from spark_rapids_tpu.sqltypes import StringType, StructType

AXIS = mesh_exec.AXIS
HOST_AXIS = mesh_exec.HOST_AXIS


class MeshCompileError(NotImplementedError):
    """Plan contains an operator with no mesh lowering (caller falls back
    to the single-chip thread-pool engine)."""


#: Stats of the most recent sharded scan ingestion in THIS process —
#: lets multi-process tests assert each process decoded only its own
#: shard of the file list (never the whole table).
last_ingest_stats: Dict[str, int] = {}

#: Per-compiled-program trace-time profiles, keyed by the cached_jit
#: key: the ICI collective byte tape (replayed into the transfer
#: ledger on every execution — collectives cannot self-report from
#: inside jit) and the output columns' dictionary ids (encodings are
#: stripped from the traced output — a replicated dictionary has no
#: row axis for the P(AXIS) out-spec — and re-attached after the run).
_ici_profiles: Dict[tuple, list] = {}
_out_enc_profiles: Dict[tuple, list] = {}


# --------------------------------------------------- trace-safe helpers

def concat_traced(batches: List[ColumnBatch]) -> ColumnBatch:
    """Trace-safe concat: static capacity = sum of capacities, live rows
    compacted to the front (the jit-compatible sibling of
    columnar.batch.concat_batches, which syncs row counts to the host)."""
    if len(batches) == 1:
        return batches[0]
    interim, live = _concat_columns(batches)
    perm, total = filterops.compact_perm(live, interim.capacity)
    return interim.gather(perm, total)


def _merged_vrange(parts):
    """The envelope of the parts' stamped value ranges where each plain
    column has one: the rows are the parts' own, so the bound holds.
    (`concat_traced` drops it, as it always has: its callers' programs
    are keyed on treedefs that never held one.)"""
    if any(p.vrange is None or p.encoding is not None for p in parts):
        return None
    return (min(p.vrange[0] for p in parts),
            max(p.vrange[1] for p in parts))


def concat_in_place(batches: List[ColumnBatch]
                    ) -> Tuple[ColumnBatch, jnp.ndarray]:
    """The batches end to end, each part's dead rows left where they
    are; -> (batch, live mask). For a consumer that takes a mask and
    moves the rows itself (a join's build side: its sort sends dead
    rows last), which `concat_traced`'s compaction would only cost a
    scatter and a gather of every column. Plain columns keep the
    envelope of their parts' value ranges."""
    if len(batches) == 1:
        return batches[0], batches[0].live_mask()
    return _concat_columns(batches, keep_vrange=True)


def _concat_columns(batches: List[ColumnBatch], keep_vrange: bool = False
                    ) -> Tuple[ColumnBatch, jnp.ndarray]:
    schema = batches[0].schema
    caps = [b.capacity for b in batches]
    total_cap = sum(caps)
    live = jnp.concatenate([b.live_mask() for b in batches])

    def catnd(leaves):
        # align every TRAILING axis (string bytes / array elements /
        # array<string> elems x bytes) before concatenating rows
        from spark_rapids_tpu.columnar.batch import align_trailing

        return jnp.concatenate(align_trailing(leaves), axis=0)

    def cat_col(parts, dtype):
        if any(getattr(p, "encoding", None) is not None for p in parts):
            # encoded pieces stay encoded only when they share ONE
            # dictionary; identity mismatch decodes in-trace first
            from spark_rapids_tpu.columnar import encoding as _enc

            parts = _enc.align_encodings(parts)
        if parts[0].children is not None:  # structs: recurse per field
            kids = [cat_col([p.children[i] for p in parts],
                            parts[0].children[i].dtype)
                    for i in range(len(parts[0].children))]
            return DeviceColumn(
                dtype, jnp.concatenate([p.data for p in parts]),
                jnp.concatenate([p.validity for p in parts]),
                children=kids)
        data = catnd([p.data for p in parts])
        val = jnp.concatenate([p.validity for p in parts])
        lens = None
        if parts[0].lengths is not None:
            lens = jnp.concatenate([p.lengths for p in parts])
        ev = None
        if parts[0].elem_validity is not None:
            ev = catnd([p.elem_validity for p in parts])
        mv = None
        if parts[0].map_values is not None:
            mv = catnd([p.map_values for p in parts])
        el = None
        if parts[0].elem_lengths is not None:
            el = catnd([p.elem_lengths for p in parts])
        # encoded columns keep their [0, K) code bound (binned group-by
        # needs it); plain columns keep the historical drop-at-concat
        vr = parts[0].vrange if (
            parts[0].encoding is not None
            and all(p.vrange == parts[0].vrange for p in parts)) \
            else (_merged_vrange(parts) if keep_vrange else None)
        return DeviceColumn(dtype, data, val, lens, ev, mv, vrange=vr,
                            elem_lengths=el,
                            encoding=parts[0].encoding)

    cols: List[DeviceColumn] = []
    for ci, field in enumerate(schema.fields):
        cols.append(cat_col([b.columns[ci] for b in batches],
                            field.dataType))
    return ColumnBatch(schema, cols, total_cap), live


def shard_equi_join(node: J._DeviceJoinBase, left: ColumnBatch,
                    right: ColumnBatch, out_cap: int
                    ) -> Tuple[ColumnBatch, jnp.ndarray]:
    """Trace-safe per-shard equi-join with a static output capacity.
    Returns (batch, overflow_flag); overflow means the true pair count
    exceeded out_cap and the caller must recompile bigger.

    Same gather-map algorithm as the eager join family (exec/joins.py),
    minus the host syncs that pick capacity buckets dynamically."""
    jt = node.join_type
    lsch = node.children[0].schema
    rsch = node.children[1].schema
    no_ovf = jnp.zeros((), bool)
    # encoded execution: both sides are in this ONE trace, so string
    # equi-keys over dictionary columns compare CODES (identity
    # checked, re-encode via host remap on mismatch — exec/joins.py)
    lkeys, rkeys = node._encoded_key_rewrite(left, right)
    bt = node._build_table(right, keys=rkeys)
    work_l, lk = node._prepare_keys(left, lkeys)
    lo, counts = joinops.probe_ranges(bt, work_l, lk)

    if node.condition is None:
        if jt == "left_semi":
            return filterops.compact(left, counts > 0), no_ovf
        if jt == "left_anti":
            return filterops.compact(left, counts == 0), no_ovf
        if jt == "existence":
            return node._exists_batch(left, counts > 0), no_ovf
        eff = counts
        if jt in ("left", "full"):
            eff = jnp.where(left.live_mask() & (counts == 0), 1, counts)
        pi, bi, total = joinops.expand_gather_maps(lo, eff, out_cap)
        overflow = total > out_cap
        lcols = [c.gather(pi) for c in left.columns]
        safe_bi = jnp.clip(bi, 0, bt.batch.capacity - 1)
        rcols = [c.gather(safe_bi) for c in bt.batch.columns]
        if jt in ("left", "full"):
            row_un = jnp.take(counts == 0, pi)
            rcols = [c.replace(validity=c.validity & ~row_un)
                     for c in rcols]
        out_schema = StructType(list(lsch.fields) + list(rsch.fields))
        out = ColumnBatch(out_schema, lcols + rcols,
                          jnp.minimum(total, out_cap))
        if jt == "full":
            matched_b = node._matched_build_mask(bt, lo, counts)
            un_b = filterops.compact(bt.batch, ~matched_b)
            out = concat_traced([out, node._left_nulls_batch(lsch, un_b)])
        return out, overflow

    # conditional equi-join: materialize candidate pairs, evaluate the
    # bound condition over the gathered pair batch, derive the type
    pi, bi, total = joinops.expand_gather_maps(lo, counts, out_cap)
    overflow = total > out_cap
    pair_live = jnp.arange(out_cap, dtype=jnp.int64) < total
    pair_batch = node._gather_pairs(left, bt.batch, pi, bi,
                                    jnp.minimum(total, out_cap))
    pred = node.condition.eval(EvalContext(pair_batch))
    ok = pair_live & pred.data & pred.validity

    matched_l = (jnp.zeros((left.capacity,), jnp.int32)
                 .at[pi].max(jnp.where(ok, 1, 0)) > 0)
    if jt == "left_semi":
        return filterops.compact(left, matched_l), overflow
    if jt == "left_anti":
        return filterops.compact(left, ~matched_l), overflow
    if jt == "existence":
        return node._exists_batch(left, matched_l), overflow
    n_pairs = jnp.sum(jnp.where(ok, 1, 0)).astype(jnp.int32)
    perm, _ = filterops.compact_perm(ok, out_cap)
    survivors = pair_batch.gather(perm, n_pairs)
    if jt in ("inner", "cross"):
        return survivors, overflow
    parts = [survivors]
    if jt in ("left", "full"):
        left_un = filterops.compact(left, ~matched_l)
        parts.append(node._right_nulls_batch(left_un, rsch))
    if jt == "full":
        matched_b = (jnp.zeros((bt.batch.capacity,), jnp.int32)
                     .at[jnp.clip(bi, 0, bt.batch.capacity - 1)]
                     .max(jnp.where(ok, 1, 0)) > 0)
        right_un = filterops.compact(bt.batch, ~matched_b)
        parts.append(node._left_nulls_batch(lsch, right_un))
    out = concat_traced(parts)
    return ColumnBatch(node.schema, out.columns, out.num_rows), overflow


def range_exchange_sort(batch: ColumnBatch, orders, n: int, axis: str,
                        slot: int, samples_per_shard: int = 64
                        ) -> Tuple[ColumnBatch, jnp.ndarray]:
    """Distributed global sort: sample-based range bounds (all_gather of
    per-shard key samples), all_to_all range exchange, per-shard sort.
    Shard s holds the s-th global key range, so concatenating shards in
    order IS the global order (GpuRangePartitioner.scala +
    GpuSortExec.scala, fused into the SPMD program)."""
    keys = order_keys(batch, orders)
    cap = batch.capacity
    s_n = min(samples_per_shard, cap)
    pos = (jnp.arange(s_n, dtype=jnp.int32) * cap) // s_n
    gathered = [lax.all_gather(jnp.take(k, pos), axis).reshape(-1)
                for k in keys]
    from spark_rapids_tpu.ops.common import sort_permutation

    total_s = n * s_n
    perm = sort_permutation(gathered, total_s)
    skeys = [jnp.take(g, perm) for g in gathered]
    # dead/garbage sample rows carry leading null-rank 2 and sort last
    live_ct = jnp.sum(skeys[0] < 2).astype(jnp.int32)
    j = jnp.clip((jnp.arange(n - 1, dtype=jnp.int32) + 1) * live_ct // n,
                 0, total_s - 1)
    bounds = [jnp.take(k, j) for k in skeys]
    dest = _binary_search(bounds, keys, jnp.int32(n - 1), max(n - 1, 1),
                          upper=True)
    exchanged, overflow = all_to_all_batch(batch, dest, n, slot, axis,
                                           site="ici.sort")
    return sort_batch(exchanged, orders), overflow


# --------------------------------------------------------- the executor

_SOURCE_TYPES = (ops.LocalRelationExec, ops.RangeExec, ops.TpuFileScanExec,
                 ops.ArrowToDeviceExec, ops.TpuCachedRelationExec)

_SUPPORTED = (ops.TpuProjectExec, ops.TpuFilterExec,
              ops.TpuHashAggregateExec, ops.TpuShuffleExchangeExec,
              ops.TpuSortExec, ops.TpuLocalLimitExec, ops.UnionExec,
              ops.TpuWindowExec, ops.TpuGenerateExec,
              ops.TpuCoalesceBatchesExec,
              J.TpuShuffledHashJoinExec, J.TpuBroadcastHashJoinExec)


def shard_generate(node: ops.TpuGenerateExec, batch: ColumnBatch,
                   out_cap: int):
    """Trace-safe per-shard explode with a static output capacity
    (overflow -> recompile bigger); shares the operator's explode
    program."""
    return node._explode_to_cap(batch, out_cap)


def _plan_key(node: PhysicalPlan) -> tuple:
    """Structural key of a physical plan for caching the compiled SPMD
    program (the jit_cache discipline applied to whole-plan programs).
    Two plans with equal keys trace to identical programs."""
    from spark_rapids_tpu.runtime.jit_cache import (
        aliases_key,
        orders_key,
        schema_key,
    )

    t = type(node).__name__
    if isinstance(node, ops.TpuProjectExec):
        own = aliases_key(node.exprs)
    elif isinstance(node, ops.TpuFilterExec):
        own = node.condition.key()
    elif isinstance(node, ops.TpuHashAggregateExec):
        own = (node.mode, aliases_key(node.grouping),
               aliases_key(node.aggs)) + node.lowering_key()
    elif isinstance(node, ops.TpuSortExec):
        own = orders_key(node.orders)
    elif isinstance(node, ops.TpuRangeShuffleExchangeExec):
        own = (orders_key(node.orders), node.num_partitions)
    elif isinstance(node, ops.TpuShuffleExchangeExec):
        own = (tuple(k.key() for k in node.key_exprs)
               if node.key_exprs else None, node.num_partitions)
    elif isinstance(node, ops.TpuLocalLimitExec):
        own = (node.n,)
    elif isinstance(node, ops.TpuWindowExec):
        own = (aliases_key(node.window_exprs), node.presorted,
               node.halo)
    elif isinstance(node, ops.TpuGenerateExec):
        own = (node.gen_alias.name, node.gen_alias.key(),
               aliases_key(node.pass_through), node.position)
    elif isinstance(node, ops.TpuExpandExec):
        # rollup/cube/grouping-sets share one output schema but differ
        # in their projection lists — the program key must carry them
        own = tuple(aliases_key(p) for p in node.projections)
    elif isinstance(node, ops.TpuSampleExec):
        own = (node.fraction, node.seed)
    elif isinstance(node, (J.TpuShuffledHashJoinExec,
                           J.TpuBroadcastHashJoinExec)):
        own = (node.join_type,
               tuple(k.key() for k in node.left_keys),
               tuple(k.key() for k in node.right_keys),
               node.condition.key() if node.condition is not None
               else None,
               schema_key(node.schema))
    else:
        own = schema_key(node.schema)
    return (t, own, tuple(_plan_key(c) for c in node.children))


def stamp_exchange_strategies(phys: PhysicalPlan, conf=None) -> None:
    """Stamp each shuffle exchange with its transport strategy — "ici"
    (compiled to an on-device all_to_all, zero host-direction bytes)
    when ICI shuffle is enabled and the exchange's producer subtree is
    mesh-lowerable (the consumer side is by construction: the mesh
    executor compiles the whole plan as one SPMD program), else
    "host". A "host" exchange has no mesh lowering, so the plan falls
    back to the single-chip engine. Needs no mesh — explain() stamps
    a fresh plan with it so the planner's choice is visible."""
    from spark_rapids_tpu.config import rapids_conf as rc

    ici_on = conf is None or conf.get(rc.MULTICHIP_ICI_SHUFFLE)
    sim = (conf.get(rc.MULTIHOST_SIMULATED_HOSTS) if conf is not None
           else rc.MULTIHOST_SIMULATED_HOSTS.default)
    multihost = jax.process_count() > 1 or (sim or 0) > 1
    probe = MeshQueryExecutor.__new__(MeshQueryExecutor)

    def mesh_resident(node: PhysicalPlan) -> bool:
        try:
            probe._collect_sources(node, [])
        except MeshCompileError:
            return False
        return True

    def walk(node: PhysicalPlan) -> None:
        for c in node.children:
            walk(c)
        if isinstance(node, ops.TpuShuffleExchangeExec):
            node.ici_strategy = ("ici" if ici_on and mesh_resident(node)
                                 else "host")
            if multihost and node.ici_strategy == "ici":
                # DCN placement (informational, for explain()): a
                # partial->final aggregate hand-off reduces per host
                # BEFORE crossing DCN (_hierarchical_agg_exchange);
                # any other keyed/round-robin exchange rides the
                # generic ICI-then-DCN two-stage split
                c = node.children[0]
                node.dcn_strategy = (
                    "reduce-then-dcn"
                    if (node.key_exprs
                        and isinstance(c, ops.TpuHashAggregateExec)
                        and c.mode == "partial" and c.grouping)
                    else "two-stage")

    walk(phys)


def plan_bears_exchange(phys: PhysicalPlan) -> bool:
    """True when executing this plan on a mesh would move rows between
    shards through a hash/range exchange — explicit exchange nodes AND
    the operators whose mesh lowering materializes one internally
    (shuffled join co-partitioning, aggregate partial->final hand-off,
    global sort's range exchange, window partitioning)."""

    def walk(n: PhysicalPlan) -> bool:
        if isinstance(n, (ops.TpuShuffleExchangeExec,
                          ops.TpuHashAggregateExec,
                          ops.TpuSortExec,
                          ops.TpuWindowExec,
                          J.TpuShuffledHashJoinExec)):
            return True
        return any(walk(c) for c in n.children)

    return walk(phys)


class MeshQueryExecutor:
    """Compile + run one physical plan as a single SPMD program."""

    def __init__(self, mesh, conf=None, expansion: int = 0):
        self.mesh = mesh
        self.conf = conf
        # topology: a 1D mesh is (chips,) = the classic single-host
        # engine; a 2D mesh is (hosts, chips) host failure domains —
        # collectives over AXIS stay on ICI, collectives over
        # HOST_AXIS cross DCN, and the lowerings below place traffic
        # accordingly. self.n is always the TOTAL row-shard count.
        shape = dict(mesh.shape)
        self.hosts = int(shape.get(HOST_AXIS, 1))
        self.chips = int(shape[AXIS])
        self.n = self.hosts * self.chips
        self._row_spec = mesh_exec.row_spec(mesh)
        if expansion <= 0:
            from spark_rapids_tpu.config import rapids_conf as rc

            expansion = (conf.get(rc.MULTICHIP_EXPANSION)
                         if conf is not None
                         else rc.MULTICHIP_EXPANSION.default)
        self._expansion = max(1, int(expansion))
        #: ids of the devices that held the last run's result shards
        #: (session.last_execution["meshDevices"]): on real chips the
        #: proof that the program spread out and did not land on one
        self.result_devices: List[int] = []

    #: (n_devices, chip_epoch) -> Mesh. Keyed by the chip epoch so a
    #: fence/unfence never hands back a mesh laid out over a dead chip;
    #: cached_jit programs key on the mesh object identity transitively
    #: through shard_map, so stale programs die with their mesh.
    _mesh_cache: Dict[tuple, object] = {}

    @classmethod
    def for_devices(cls, n_devices: int, conf=None) -> "MeshQueryExecutor":
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.parallel import multihost
        from spark_rapids_tpu.runtime import device_monitor as dm

        fenced = dm.fenced_chips()
        healthy = [d for d in jax.devices() if d.id not in fenced]
        if not healthy:
            raise MeshCompileError(
                "every local device is chip-fenced; no mesh possible")
        sim = (conf.get(rc.MULTIHOST_SIMULATED_HOSTS) if conf is not None
               else rc.MULTIHOST_SIMULATED_HOSTS.default)
        if sim and sim > 1:
            # a fenced simulated host shrinks the host axis (its chips
            # are already out of `healthy`); real topologies shrink by
            # losing their process's device group instead
            sim = max(1, int(sim) - len(dm.fenced_hosts()))
        groups = multihost.host_groups(healthy, sim)
        if len(groups) <= 1:
            n = min(max(1, n_devices), len(healthy))
            key = (n, dm.chip_epoch())
            mesh = cls._mesh_cache.get(key)
            if mesh is None:
                mesh = mesh_exec.make_mesh(n, devices=healthy)
                cls._mesh_cache[key] = mesh
            return cls(mesh, conf)
        hosts = len(groups)
        chips = min(min(len(g) for g in groups),
                    max(1, n_devices // hosts))
        key = ("2d", hosts, chips, dm.chip_epoch())
        mesh = cls._mesh_cache.get(key)
        if mesh is None:
            mesh = mesh_exec.make_host_mesh([g[:chips] for g in groups])
            cls._mesh_cache[key] = mesh
        return cls(mesh, conf)

    # --- plan walking ---

    def _collect_sources(self, node: PhysicalPlan,
                         out: List[PhysicalPlan]) -> None:
        if isinstance(node, _SOURCE_TYPES) or not node.is_tpu:
            out.append(node)
            return
        if not isinstance(node, _SUPPORTED):
            raise MeshCompileError(
                f"{type(node).__name__} has no mesh lowering")
        if isinstance(node, ops.UnionExec) and not node.is_tpu:
            raise MeshCompileError("host-side union")
        for c in node.children:
            self._collect_sources(c, out)

    def _materialize(self, source: PhysicalPlan) -> ColumnBatch:
        """Run a source subtree on the host engine and build one padded
        device batch whose capacity divides the mesh size. Only used for
        sources that are inherently single-host (local relations, CPU
        fallback subtrees); file scans ingest per shard
        (_ingest_scan_sharded)."""
        table = source.collect()
        cap = next_capacity(max(table.num_rows, 1))
        if cap % self.n:
            cap = -(-cap // self.n) * self.n
        return arrow_to_device(table, capacity=cap)

    def _ingest_scan_sharded(self, scan: ops.TpuFileScanExec
                             ) -> ColumnBatch:
        """Partitioned mesh ingestion: split the scan's file-task list
        across shards; each shard decodes ONLY its own files into its
        own device buffer (reader pool in parallel), assembled into one
        globally-sharded array per leaf — no whole-table host batch
        ever exists (the MultiFileCloudPartitionReader role,
        GpuParquetScan.scala:2051, mapped onto mesh ingestion)."""
        from concurrent.futures import ThreadPoolExecutor

        from jax.sharding import NamedSharding

        from spark_rapids_tpu.columnar.arrow_bridge import column_from_arrow
        from spark_rapids_tpu.columnar.batch import concat_batches  # noqa: F401
        from spark_rapids_tpu.sqltypes.datatypes import to_arrow_type

        n = self.n
        files = [f for t in scan._tasks for f in t]
        shard_files = [files[s::n] for s in range(n)]
        devs = list(self.mesh.devices.reshape(-1))
        # multi-host: this process decodes ONLY the shards that land on
        # its own devices — no process ever holds the whole table (the
        # per-executor task split of the reference's scan RDD)
        my_proc = jax.process_index()
        local_ids = [s for s in range(n)
                     if devs[s].process_index == my_proc]
        last_ingest_stats.update(
            files=sum(len(shard_files[s]) for s in local_ids),
            total_files=len(files), local_shards=len(local_ids),
            process=my_proc)

        def decode(fs) -> pa.Table:
            if not fs:
                arrow_schema = pa.schema([
                    pa.field(f.name, to_arrow_type(f.dataType), f.nullable)
                    for f in scan.schema.fields])
                return pa.table(
                    {f.name: pa.array([], f.type) for f in arrow_schema},
                    schema=arrow_schema)
            tabs = []
            for t in scan._host_tables(fs):
                tabs.append(t)
            return pa.concat_tables(tabs, promote_options="none")

        with ThreadPoolExecutor(max_workers=min(8, len(local_ids))) as pool:
            local_tables = list(pool.map(
                decode, [shard_files[s] for s in local_ids]))
        shard_cap = next_capacity(
            max(max(t.num_rows for t in local_tables), 1))
        shard_cap = self._sync_max(shard_cap)
        shard_cols = []
        for t in local_tables:
            t = t.combine_chunks()
            cols = []
            for i, field in enumerate(scan.schema.fields):
                col = t.column(i)
                arr = (col.chunk(0) if col.num_chunks else
                       pa.array([], type=t.schema.field(i).type))
                cols.append(column_from_arrow(arr, field, shard_cap))
            shard_cols.append(cols)
        # per-shard dictionary reconciliation: each shard decoded its
        # own files, so encoded columns arrive with per-shard
        # dictionaries; rewrite every shard's codes onto ONE union
        # dictionary so codes are value-comparable across shards and
        # exchanges ship codes over ICI (encodings are stripped here
        # and the shared dictionary re-attached replicated after the
        # global-array assembly)
        col_dicts = self._reconcile_dictionaries(scan, shard_cols)
        # align variable-width leaves to the global max widths — EVERY
        # trailing axis of every leaf (string bytes, array elems, the
        # array<string> cube's elems x bytes, struct children's
        # matrices) must reach the same extent or the global-array
        # assembly rejects the shards. Leaf-wise over the column
        # pytree so struct children align too.
        def pad_axis(a, ax, m):
            if a.shape[ax] >= m:
                return a
            pad_width = [(0, 0)] * a.ndim
            pad_width[ax] = (0, m - a.shape[ax])
            return np.pad(a, pad_width)

        for ci in range(len(scan.schema.fields)):
            flats = [jax.tree_util.tree_flatten(sc[ci])
                     for sc in shard_cols]
            leaves = [list(f[0]) for f in flats]
            for li in range(len(leaves[0])):
                nd = getattr(leaves[0][li], "ndim", 1)
                for ax in range(1, nd):
                    m = self._sync_max(max(int(l[li].shape[ax])
                                           for l in leaves))
                    for l in leaves:
                        l[li] = pad_axis(l[li], ax, m)
            for sc, (_, treedef), l in zip(shard_cols, flats, leaves):
                sc[ci] = jax.tree_util.tree_unflatten(treedef, l)
        sharding = NamedSharding(self.mesh, self._row_spec)
        local_devs = [devs[s] for s in local_ids]

        def assemble(leaves_per_shard, global_shape):
            from spark_rapids_tpu.obs import telemetry

            singles = [telemetry.ledgered_put(leaf, "mesh.assemble",
                                              device=d)
                       for leaf, d in zip(leaves_per_shard, local_devs)]
            return jax.make_array_from_single_device_arrays(
                global_shape, sharding, singles)

        def asm_leaf(*per_shard):
            gshape = (n * shard_cap,) + tuple(per_shard[0].shape[1:])
            return assemble(list(per_shard), gshape)

        out_cols = []
        for ci in range(len(scan.schema.fields)):
            per = [sc[ci] for sc in shard_cols]
            col = jax.tree_util.tree_map(asm_leaf, *per)
            dd = col_dicts.get(ci)
            if dd is not None:
                col = col.replace(
                    encoding=mesh_exec.replicate_dictionary(
                        self.mesh, dd),
                    vrange=(0, max(dd.num_values - 1, 0)))
            out_cols.append(col)
        counts = assemble(
            [np.asarray([t.num_rows], dtype=np.int32)
             for t in local_tables],
            (n,))
        return ColumnBatch(scan.schema, out_cols, counts)

    def _reconcile_dictionaries(self, scan, shard_cols):
        """Rewrite per-shard encoded columns onto one shared dictionary.

        Returns {column_index: host DeviceDictionary} for columns that
        stay encoded; their shard columns are left holding remapped
        codes with encoding STRIPPED (the caller re-attaches the shared
        dictionary replicated over the mesh after assembly). Columns
        whose shards cannot reconcile — a live plain shard mixed with
        encoded ones, an evicted host dictionary — decode host-side to
        the plain padded layout instead (PR 8's fallback discipline).

        Multi-process meshes reconcile HIERARCHICALLY: each process
        unions its own shards' dictionaries locally (free), then ONE
        cross-host value exchange (_union_dictionary_id) builds the
        global union; intern_dictionary is content-addressed, so every
        process arrives at the same dict_id without shipping objects.
        Every cross-process decision below (live_plain, the decode
        fallback) is sync'd — processes disagreeing on whether a
        column stays encoded would deadlock the global assembly."""
        from spark_rapids_tpu.columnar import encoding as enc_mod
        from spark_rapids_tpu.columnar.encoding import DeviceDictionary
        from spark_rapids_tpu.config import rapids_conf as rc

        multi = jax.process_count() > 1
        reconcile = (self.conf is None or self.conf.get(
            rc.MULTICHIP_RECONCILE_DICTS))
        col_dicts: Dict[int, DeviceDictionary] = {}
        for ci in range(len(scan.schema.fields)):
            cols = [sc[ci] for sc in shard_cols]
            encs = [getattr(c, "encoding", None) for c in cols]
            enc_any = any(e is not None for e in encs)
            if multi:
                enc_any = bool(self._sync_max(int(enc_any)))
            if not enc_any:
                continue
            live_plain = any(
                e is None and int(np.asarray(c.validity).sum()) > 0
                for c, e in zip(cols, encs))
            if multi:
                live_plain = bool(self._sync_max(int(live_plain)))
            hd = None
            union_id = None
            if reconcile and not live_plain:
                union_id = self._union_dictionary_id(encs)
                hd = (enc_mod._host_dict(union_id)
                      if union_id is not None else None)
            if multi and bool(self._sync_max(1 if hd is None else 0)):
                # any process missing the union dictionary forces the
                # decode fallback EVERYWHERE — a column half-encoded
                # across processes cannot assemble
                hd, union_id = None, None
            if hd is None:
                # decode fallback: plain padded layout on every shard
                for s, c in enumerate(cols):
                    if encs[s] is not None:
                        shard_cols[s][ci] = self._decode_host(c)
                continue
            k = max(hd.matrix.shape[0], 1)
            code_dt = np.int16 if k < (1 << 15) else np.int32
            for s, (c, e) in enumerate(zip(cols, encs)):
                if e is None:  # empty plain shard: all-dead codes
                    shard_cols[s][ci] = c.replace(
                        data=np.zeros(len(np.asarray(c.validity)),
                                      dtype=code_dt),
                        validity=np.zeros_like(np.asarray(c.validity)),
                        lengths=None, vrange=(0, k - 1), encoding=None)
                    continue
                codes = np.asarray(c.data).astype(np.int64)
                remap = enc_mod.remap_table(e.dict_id, union_id)
                if remap is not None:
                    codes = remap[np.clip(codes, 0, len(remap) - 1)]
                    codes = np.where(codes >= 0, codes, 0)
                shard_cols[s][ci] = c.replace(
                    data=codes.astype(code_dt), vrange=(0, k - 1),
                    encoding=None)
            col_dicts[ci] = DeviceDictionary(hd.matrix, hd.lengths,
                                             union_id)
        return col_dicts

    def _union_dictionary_id(self, encs):
        """dict_id of the union dictionary covering every shard's
        encoding, or None when any contributing dictionary is gone.

        Single-process: concatenate the distinct dictionaries' values
        in shard order and intern (the PR 8 behavior, unchanged).
        Multi-process: union the LOCAL dictionaries first (the
        per-host rung — free), then allgather each process's value
        list as one padded JSON blob over DCN and intern the
        process-order concatenation; intern_dictionary is
        content-addressed so every process computes the same id from
        the same bytes."""
        from spark_rapids_tpu.columnar import encoding as enc_mod

        if jax.process_count() == 1:
            ids = []
            for e in encs:
                if e is not None and e.dict_id not in ids:
                    ids.append(e.dict_id)
            if len(ids) == 1:
                return ids[0]
            values: List[str] = []
            for did in ids:
                v = enc_mod.dictionary_values(did)
                if v is None:
                    return None
                values.extend(x for x in v.to_pylist()
                              if x is not None)
            if not values:
                return None
            uid, _ = enc_mod.intern_dictionary(
                pa.array(values, type=pa.large_string()))
            return uid
        import json

        local: List[str] = []
        seen = set()
        missing = 0
        for e in encs:
            if e is None:
                continue
            v = enc_mod.dictionary_values(e.dict_id)
            if v is None:
                missing = 1
                break
            for x in v.to_pylist():
                if x is not None and x not in seen:
                    seen.add(x)
                    local.append(x)
        # agree on the bail-out BEFORE the collective below: one
        # process returning early while the rest enter the allgather
        # would deadlock the pod
        if self._sync_max(missing):
            return None
        try:
            from jax.experimental import multihost_utils

            from spark_rapids_tpu.obs import telemetry

            blob = np.frombuffer(json.dumps(local).encode(), np.uint8)
            m = max(self._sync_max(len(blob)), 1)
            padded = np.zeros((m,), np.uint8)
            padded[:len(blob)] = blob
            blobs = np.asarray(
                multihost_utils.process_allgather(padded))
            lens = np.asarray(multihost_utils.process_allgather(
                np.asarray([len(blob)], np.int64))).reshape(-1)
            telemetry.record_dcn("dcn.dict_union", int(blobs.size))
            values = []
            vseen = set()
            for p in range(blobs.shape[0]):
                for x in json.loads(
                        bytes(blobs[p, :int(lens[p])]).decode()):
                    if x not in vseen:
                        vseen.add(x)
                        values.append(x)
            if not values:
                return None
            uid, _ = enc_mod.intern_dictionary(
                pa.array(values, type=pa.large_string()))
            return uid
        except Exception:
            return None

    @staticmethod
    def _decode_host(col):
        """Host-side decode of a numpy-leaf encoded column to the
        plain padded string layout (the pre-upload twin of
        encoding.decode_column)."""
        enc = col.encoding
        dmat = np.asarray(enc.data)
        dlen = np.asarray(enc.lengths)
        k = max(dmat.shape[0], 1)
        codes = np.clip(np.asarray(col.data).astype(np.int64), 0, k - 1)
        val = np.asarray(col.validity)
        data = np.where(val[:, None], dmat[codes], 0).astype(np.uint8)
        lengths = np.where(val, dlen[codes], 0).astype(np.int32)
        return col.replace(data=data, lengths=lengths, vrange=None,
                           encoding=None)

    @staticmethod
    def _sync_max(v: int) -> int:
        """Agree on a global max (shard capacity / padded width) across
        processes: shapes must be identical on every host or the global
        arrays don't assemble. One tiny DCN allgather; no-op
        single-process."""
        if jax.process_count() == 1:
            return int(v)
        from jax.experimental import multihost_utils

        return int(np.max(multihost_utils.process_allgather(
            np.asarray([v], np.int64))))

    # --- execution ---

    def execute(self, phys: PhysicalPlan) -> pa.Table:
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.runtime.faults import InjectedFault

        if self.conf is not None and self.conf.get(rc.ANSI_ENABLED):
            # ANSI checks live in the eager engine's per-batch check
            # programs; the SPMD program has no raise points
            raise MeshCompileError("ANSI mode uses the eager engine")
        if (self.conf is not None
                and not self.conf.get(rc.MULTICHIP_ICI_SHUFFLE)
                and self.n > 1 and plan_bears_exchange(phys)):
            # every exchange is pinned to the host transport — there is
            # no mesh lowering for a host-staged exchange, so the whole
            # plan keeps the single-chip engine's serialized shuffle
            raise MeshCompileError(
                "ICI shuffle disabled: exchanges keep the host path")
        self.plan_exchange_strategies(phys)
        if self.hosts > 1:
            self._multihost_unsupported(phys)
        sources: List[PhysicalPlan] = []
        self._collect_sources(phys, sources)
        sharded = []
        for s in sources:
            if isinstance(s, ops.TpuFileScanExec) and s.is_tpu:
                sharded.append(self._ingest_scan_sharded(s))
            else:
                sharded.append(mesh_exec.shard_batch(
                    self.mesh, self._materialize(s)))
        expansion = self._expansion
        retries = (self.conf.get(rc.MULTICHIP_ICI_RETRIES)
                   if self.conf is not None
                   else rc.MULTICHIP_ICI_RETRIES.default)
        dcn_retries = (self.conf.get(rc.MULTIHOST_DCN_RETRIES)
                       if self.conf is not None
                       else rc.MULTIHOST_DCN_RETRIES.default)
        while True:
            try:
                return self._run(phys, sources, sharded, expansion)
            except TpuSplitAndRetryOOM:
                if expansion >= 256:
                    if self._has_static_collect(phys):
                        # a group wider than the largest static collect
                        # width (16*256) is better served by the eager
                        # engine's data-dependent buffers — fall back
                        # rather than fail the query
                        raise MeshCompileError(
                            "collect group exceeds the largest static "
                            "mesh width; eager engine handles it")
                    raise
                expansion *= 2
            except InjectedFault as e:
                if e.site == "ici.collective" and retries > 0:
                    # transient fabric fault: the SPMD program is pure
                    # over the (still-resident) sharded inputs, so a
                    # straight re-dispatch is the retry
                    retries -= 1
                    obs_events.emit("ici.retry", detail=e.detail,
                                    left=retries)
                    continue
                if e.site == "dcn.collective" and dcn_retries > 0:
                    # transient cross-host fault: same purity argument,
                    # separately budgeted — DCN flakes (a dropped link,
                    # a slow switch) are far more common than ICI ones
                    dcn_retries -= 1
                    obs_events.emit("dcn.retry", detail=e.detail,
                                    left=dcn_retries)
                    continue
                if e.site == "chip.fatal":
                    return self._recover_chip_loss(phys, e)
                if e.site == "host.fatal":
                    return self._recover_host_loss(phys, e)
                raise

    @staticmethod
    def _multihost_unsupported(phys: PhysicalPlan) -> None:
        """Operators with no 2D-mesh lowering: global sort and window
        would need cross-host range/partition exchanges this PR does
        not place, and a full join's per-host matched-build tracking
        would double-count unmatched build rows (the build side is
        host-replicated). MeshCompileError -> thread-pool fallback."""

        def walk(n: PhysicalPlan) -> None:
            if isinstance(n, ops.TpuSortExec):
                raise MeshCompileError(
                    "global sort has no multi-host mesh lowering")
            if isinstance(n, ops.TpuWindowExec):
                raise MeshCompileError(
                    "window has no multi-host mesh lowering")
            if isinstance(n, (J.TpuShuffledHashJoinExec,
                              J.TpuBroadcastHashJoinExec)) \
                    and n.join_type == "full":
                raise MeshCompileError(
                    "full join has no multi-host mesh lowering (the "
                    "host-replicated build side would double-count "
                    "unmatched build rows)")
            for c in n.children:
                walk(c)

        walk(phys)

    def plan_exchange_strategies(self, phys: PhysicalPlan) -> None:
        stamp_exchange_strategies(phys, self.conf)

    def _recover_chip_loss(self, phys: PhysicalPlan,
                           exc) -> pa.Table:
        """One chip died mid-collective: fence ONLY that chip (the
        process-wide monitor stays unfenced — other queries on the
        surviving chips keep serving), rebuild the mesh over the
        survivors, and recover the lost shards from lineage: sources
        re-ingest deterministically over the new topology, so
        re-executing the SPMD program over n-1 chips reconstructs
        every lost shard's rows (the PR 3 deterministic-attempt
        discipline applied to shards instead of tasks)."""
        import time

        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.runtime import device_monitor as dm
        from spark_rapids_tpu.runtime.errors import DeviceLostError

        recover = (self.conf is None
                   or self.conf.get(rc.MULTICHIP_CHIP_RECOVERY))
        # chaos-driven loss carries no PJRT device handle; the victim
        # is the mesh's last device (deterministic, so the recovery
        # mesh and its compiled programs are test-stable)
        victim = list(self.mesh.devices.reshape(-1))[-1]
        chip_ep = dm.fence_chip(victim.id, cause=str(exc))
        if not recover or self.n <= 1:
            raise DeviceLostError(
                f"chip {victim.id} lost during mesh execution "
                f"(chip epoch {chip_ep}): {exc}")
        t0 = time.monotonic()
        survivor = MeshQueryExecutor.for_devices(self.n - 1, self.conf)
        out = survivor.execute(phys)
        dm.note_chip_recovery()
        obs_events.emit(
            "chip.recovery", device=victim.id, chipEpoch=chip_ep,
            shards=self.n, survivors=survivor.n,
            ms=round((time.monotonic() - t0) * 1000.0, 3))
        return out

    def _host_ids(self) -> List[str]:
        """Stable failure-domain label per host row of the 2D mesh:
        the owning process for real multi-host topologies, the row's
        first device id for simulated hosts (unique and stable across
        refencing — device ids never reassign)."""
        if self.hosts <= 1:
            return ["host0"]
        rows = [list(r) for r in self.mesh.devices]
        if jax.process_count() > 1:
            return [f"proc{r[0].process_index}" for r in rows]
        return [f"sim{r[0].id}" for r in rows]

    def _recover_host_loss(self, phys: PhysicalPlan,
                           exc) -> pa.Table:
        """A whole host died mid-collective: the chip ladder rung
        scaled up one level. Fence EVERY chip of that host in one
        epoch step (per-chip fencing would hand the half-dead host
        shard assignments for n-1 more timeouts), rebuild the mesh
        over the surviving hosts, and recover the lost shards from
        lineage exactly as the chip path does — sources re-ingest
        deterministically over the new topology."""
        import time

        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.runtime import device_monitor as dm
        from spark_rapids_tpu.runtime.errors import DeviceLostError

        recover = (self.conf is None
                   or self.conf.get(rc.MULTIHOST_HOST_RECOVERY))
        # chaos-driven loss carries no host handle; the victim is the
        # mesh's last host row (deterministic — same discipline as the
        # chip path's last-device victim)
        victims = list(self.mesh.devices[-1]) if self.hosts > 1 \
            else list(self.mesh.devices.reshape(-1))
        host_id = self._host_ids()[-1]
        ids = [d.id for d in victims]
        chip_ep = dm.fence_host(host_id, ids, cause=str(exc))
        if not recover or self.hosts <= 1:
            raise DeviceLostError(
                f"host {host_id} (devices {ids}) lost during mesh "
                f"execution (chip epoch {chip_ep}): {exc}")
        t0 = time.monotonic()
        survivor = MeshQueryExecutor.for_devices(self.n, self.conf)
        out = survivor.execute(phys)
        dm.note_host_recovery()
        obs_events.emit(
            "host.recovery", host=host_id, devices=ids,
            chipEpoch=chip_ep, hosts=self.hosts,
            survivorHosts=survivor.hosts, shards=self.n,
            survivors=survivor.n,
            ms=round((time.monotonic() - t0) * 1000.0, 3))
        return out

    @staticmethod
    def _has_static_collect(phys: PhysicalPlan) -> bool:
        from spark_rapids_tpu.expr.aggregates import (
            CollectList,
            CountDistinct,
        )

        def walk(n) -> bool:
            if isinstance(n, ops.TpuHashAggregateExec) and any(
                    isinstance(a.children[0], (CollectList, CountDistinct))
                    for a in n.aggs):
                return True
            return any(walk(c) for c in n.children)

        return walk(phys)

    def _run(self, phys: PhysicalPlan, sources: List[PhysicalPlan],
             sharded: List[ColumnBatch], expansion: int) -> pa.Table:
        n = self.n
        src_index: Dict[int, int] = {id(s): i for i, s in
                                     enumerate(sources)}
        out_enc: List[tuple] = []

        def step(*shards):
            overflow = jnp.zeros((), bool)

            def track(pair):
                nonlocal overflow
                out, ovf = pair
                overflow = overflow | ovf
                return out

            def emit(node: PhysicalPlan) -> ColumnBatch:
                if id(node) in src_index:
                    return shards[src_index[id(node)]]
                if isinstance(node, ops.TpuCoalesceBatchesExec):
                    # identity: each shard already holds one batch
                    return emit(node.children[0])
                if isinstance(node, ops.TpuProjectExec):
                    return node._run(emit(node.children[0]))
                if isinstance(node, ops.TpuFilterExec):
                    return node._run(emit(node.children[0]))
                if isinstance(node, ops.TpuLocalLimitExec):
                    return self._shard_prefix_limit(
                        emit(node.children[0]), node.n)
                if isinstance(node, ops.UnionExec):
                    return concat_traced(
                        [emit(c) for c in node.children])
                if isinstance(node, ops.TpuHashAggregateExec):
                    return self._emit_agg(node, emit, track, expansion)
                if isinstance(node, ops.TpuGenerateExec):
                    cb = emit(node.children[0])
                    out_cap = next_capacity(expansion * cb.capacity)
                    return track(shard_generate(node, cb, out_cap))
                if isinstance(node, ops.TpuWindowExec):
                    # rows of one window partition must share a shard:
                    # hash-exchange by partition keys (or gather-to-one
                    # for unpartitioned specs), then the per-shard
                    # window program runs whole (it is trace-safe)
                    child = node.children[0]
                    if (isinstance(child, ops.TpuSortExec) and
                            node.presorted):
                        # the single-chip batched-window pipeline sorts
                        # + chunks; the shard program windows in one
                        # pass (its _run sorts internally), so bypass
                        child = child.children[0]
                    spec = node.spec0
                    if spec.partitions:
                        # own the partition-key exchange; bypass a
                        # planner-inserted one carrying the same keys
                        # (as the join lowering does)
                        child = self._skip_keyed_exchange(
                            child, spec.partitions)
                        cb = self._key_exchange(
                            emit(child), spec.partitions, track,
                            expansion)
                    else:
                        if (isinstance(child, ops.TpuShuffleExchangeExec)
                                and child.key_exprs is None
                                and child.num_partitions == 1):
                            child = child.children[0]
                        cb = gather_to_one(emit(child), AXIS, n)
                    return node._run(cb)
                if isinstance(node, ops.TpuShuffleExchangeExec):
                    return self._emit_exchange(
                        node, emit(node.children[0]), track, expansion)
                if isinstance(node, ops.TpuSortExec):
                    child = node.children[0]
                    if (isinstance(child, ops.TpuRangeShuffleExchangeExec)
                            or (isinstance(child,
                                           ops.TpuShuffleExchangeExec)
                                and child.key_exprs is None
                                and child.num_partitions == 1)):
                        # the mesh sort does its own range exchange
                        child = child.children[0]
                    cb = emit(child)
                    slot = slot_capacity(cb.capacity, n, expansion)
                    return track(range_exchange_sort(
                        cb, node.orders, n, AXIS, slot))
                if isinstance(node, J.TpuShuffledHashJoinExec):
                    # the join owns co-partitioning: each side rides one
                    # all_to_all keyed by its join keys. Planner-inserted
                    # exchanges carrying exactly those keys are bypassed
                    # (they would be a redundant second shuffle).
                    lc = self._skip_keyed_exchange(node.children[0],
                                                   node.left_keys)
                    rc = self._skip_keyed_exchange(node.children[1],
                                                   node.right_keys)
                    lb = self._key_exchange(emit(lc), node.left_keys,
                                            track, expansion)
                    rb = self._key_exchange(emit(rc), node.right_keys,
                                            track, expansion)
                    if self.hosts > 1:
                        # both sides are chip-partitioned by the same
                        # hash % chips; gathering the BUILD side over
                        # the host axis gives chip (h, c) every global
                        # build row with hash % chips == c exactly
                        # once — probe rows never cross DCN, and each
                        # probe row meets each build row on exactly
                        # one shard (correct for every non-full type)
                        rb = all_gather_batch(rb, HOST_AXIS,
                                              self.hosts,
                                              site="dcn.broadcast")
                    out_cap = next_capacity(
                        expansion * max(lb.capacity, rb.capacity))
                    return track(shard_equi_join(node, lb, rb, out_cap))
                if isinstance(node, J.TpuBroadcastHashJoinExec):
                    lb = emit(node.children[0])
                    rb0 = emit(node.children[1])
                    if self.hosts > 1:
                        # DCN first (hosts x cap), then ICI fans the
                        # union out chip-wise — the reverse order
                        # would push chips x cap across DCN
                        rb0 = all_gather_batch(rb0, HOST_AXIS,
                                               self.hosts,
                                               site="dcn.broadcast")
                    rb = all_gather_batch(rb0, AXIS, self.chips,
                                          site="ici.broadcast")
                    out_cap = next_capacity(
                        expansion * max(lb.capacity, rb.capacity))
                    return track(shard_equi_join(node, lb, rb, out_cap))
                raise MeshCompileError(type(node).__name__)

            out = emit(phys)
            cols = []
            for ci, c in enumerate(out.columns):
                dd = getattr(c, "encoding", None)
                if dd is not None:
                    # the dictionary is replicated; only codes ride the
                    # P(AXIS) out-spec — record which dictionary to
                    # re-attach host-side (trace-time side channel)
                    out_enc.append((ci, dd.dict_id))
                    c = c.replace(encoding=None)
                cols.append(c)
            out = ColumnBatch(
                out.schema, cols,
                jnp.asarray(out.num_rows, jnp.int32).reshape(1))
            return out, overflow.reshape(1)

        from spark_rapids_tpu.runtime.jit_cache import cached_jit
        from spark_rapids_tpu.shims import get_shim

        # leaf-wise so struct children / string matrices / validity all
        # participate in the program identity; dictionary ids too —
        # trace-time host probes (join remap tables) bake per dictionary
        shape_key = tuple(
            tuple((tuple(leaf.shape), str(leaf.dtype))
                  for leaf in jax.tree_util.tree_leaves(tuple(sb.columns)))
            + ((sb.capacity,),)
            for sb in sharded)
        enc_key = tuple(
            tuple((ci, c.encoding.dict_id)
                  for ci, c in enumerate(sb.columns)
                  if getattr(c, "encoding", None) is not None)
            for sb in sharded)
        # topology in the key: hosts and the flat device-id layout —
        # a 1x8 and a 2x4 mesh share n=8 but trace DIFFERENT programs
        # (the 2D one carries host-axis collectives), and a rebuilt
        # same-n mesh over different survivors must not reuse programs
        # compiled against the dead layout
        key = ("mesh_plan", _plan_key(phys), n, self.hosts,
               tuple(int(d.id) for d in self.mesh.devices.reshape(-1)),
               expansion, shape_key, enc_key)
        jitted = cached_jit(
            key,
            lambda: get_shim().shard_map(
                step, self.mesh,
                tuple(mesh_exec.batch_arg_specs(sb, self._row_spec)
                      for sb in sharded),
                (self._row_spec, self._row_spec)))
        from spark_rapids_tpu.obs import telemetry
        from spark_rapids_tpu.parallel import collective
        from spark_rapids_tpu.runtime import faults

        # chaos sites: a transient fabric fault (bounded retry in
        # execute) and a single-chip loss (per-chip fence + lineage
        # recovery in execute) — both fire host-side at the dispatch
        # point, the same place a real collective failure surfaces
        faults.maybe_inject("ici.collective", detail="mesh all_to_all")
        faults.maybe_inject("chip.fatal",
                            detail=f"mesh chip {n - 1} of {n}")
        if self.hosts > 1:
            # the multi-host rungs of the ladder: a transient DCN
            # flake (bounded retry) and a whole-host loss (fence_host
            # + survivor remesh + lineage recovery in execute)
            faults.maybe_inject("dcn.collective",
                                detail="mesh cross-host collective")
            faults.maybe_inject(
                "host.fatal",
                detail=f"mesh host {self.hosts - 1} of {self.hosts}")
        collective.begin_ici_tape()
        try:
            out, ovf = jitted(*sharded)
            jax.block_until_ready(jax.tree_util.tree_leaves(out))
        finally:
            tape = collective.end_ici_tape()
        self.result_devices = sorted(
            int(s.device.id)
            for s in out.columns[0].data.addressable_shards)
        if tape:
            # first call traced the program: persist the static
            # per-shard collective bytes for replay on cache hits
            _ici_profiles[key] = tape
        if out_enc:
            _out_enc_profiles[key] = list(out_enc)
        for site, wire, host_eq in _ici_profiles.get(key, ()):
            if site.startswith("dcn"):
                # host-axis collectives cross DCN; every one of the n
                # shards participates (the host axis subgroups span
                # all chips), so wire*n is total bytes here too
                telemetry.record_dcn(site, wire * n)
            else:
                telemetry.record_ici(site, wire * n, host_eq * n)
        if bool(mesh_exec.fetch_host(ovf).any()):
            raise TpuSplitAndRetryOOM(
                "mesh collective slot / join expansion overflowed; "
                "recompiling with a larger expansion factor")
        enc_out = _out_enc_profiles.get(key, ())
        if enc_out:
            in_dicts = {}
            for sb in sharded:
                for c in sb.columns:
                    dd = getattr(c, "encoding", None)
                    if dd is not None:
                        in_dicts.setdefault(dd.dict_id, dd)
            cols = list(out.columns)
            for ci, did in enc_out:
                dd = in_dicts.get(did)
                if dd is not None:
                    cols[ci] = cols[ci].replace(encoding=dd)
            out = ColumnBatch(out.schema, cols, out.num_rows)
        host = mesh_exec.gather_result(out, self.n)
        return device_to_arrow(host)

    # --- node lowerings needing state ---

    @staticmethod
    def _skip_keyed_exchange(child: PhysicalPlan, keys) -> PhysicalPlan:
        if (isinstance(child, ops.TpuShuffleExchangeExec)
                and child.key_exprs is not None
                and len(child.key_exprs) == len(keys)
                and all(a is b for a, b in zip(child.key_exprs, keys))):
            return child.children[0]
        return child

    def _global_index(self):
        """This shard's GLOBAL index in host-major flat order —
        host_row * chips + chip_col; plain chip index on a 1D mesh.
        Matches the layout mesh_exec.gather_result reads back."""
        me = lax.axis_index(AXIS)
        if self.hosts > 1:
            me = me + lax.axis_index(HOST_AXIS) * self.chips
        return me

    def _gather_counts(self, nr):
        """All shards' scalar `nr` as a [n] vector in host-major flat
        order (index i belongs to the shard whose _global_index is i).
        Nested per-axis all_gathers rather than a tuple axis name —
        explicit about the two fabric tiers and version-safe."""
        counts = lax.all_gather(nr, AXIS)
        if self.hosts > 1:
            counts = lax.all_gather(counts, HOST_AXIS).reshape(-1)
        return counts

    def _key_exchange(self, batch: ColumnBatch, keys, track,
                      expansion: int) -> ColumnBatch:
        """Intra-host co-partitioning by key hash: row -> chip
        hash % chips, over the ICI tier only. On a 1D mesh chips == n,
        byte-identical to the classic lowering. On a 2D mesh each host
        partitions its own rows the same way, so chip column c of
        EVERY host holds exactly the keys with hash % chips == c —
        the invariant the shuffled-join DCN build broadcast relies on."""
        ctx = EvalContext(batch)
        kcols = [k.eval(ctx) for k in keys]
        dest = pmod(murmur3_columns(kcols), self.chips)
        slot = slot_capacity(batch.capacity, self.chips, expansion)
        return track(all_to_all_batch(batch, dest, self.chips, slot,
                                      AXIS, site="ici.exchange"))

    def _shard_prefix_limit(self, batch: ColumnBatch,
                            k: int) -> ColumnBatch:
        """Global prefix limit across shard order: shard s keeps
        max(0, min(rows_s, k - rows_before_s)). Correct for range-sorted
        shards (ordered limit) and for gathered single-shard data; always
        yields <= k rows total."""
        nr = jnp.asarray(batch.num_rows, jnp.int32).reshape(())
        counts = self._gather_counts(nr)
        me = self._global_index()
        start = jnp.sum(jnp.where(
            jnp.arange(self.n, dtype=jnp.int32) < me, counts, 0))
        keep = jnp.clip(jnp.int32(k) - start, 0, nr)
        return ColumnBatch(batch.schema, batch.columns, keep)

    def _emit_agg(self, node: ops.TpuHashAggregateExec, emit, track,
                  expansion: int) -> ColumnBatch:
        from spark_rapids_tpu.expr.aggregates import (
            CollectList,
            CountDistinct,
        )

        n = self.n
        fns = [a.children[0] for a in node.aggs]
        static_fns = [f for f in fns if not f.jittable
                      and isinstance(f, (CollectList, CountDistinct))]
        if any(not f.jittable for f in fns
               if not isinstance(f, (CollectList, CountDistinct))):
            # exact percentile keeps its unbounded row-sized buffers —
            # approx_percentile is the bounded mesh path
            raise MeshCompileError("non-jittable aggregate (exact "
                                   "percentile family)")
        # collect/distinct family: static element width under the same
        # overflow-recompile discipline as the collective slots
        # (reference: cuDF ragged collect lists; here the padded matrix
        # width doubles with the expansion factor until the widest
        # group fits). The bracket wraps ONLY this node's phase calls —
        # partial and final plan nodes share fn instances, and
        # emit(child) may reach the sibling phase's _emit_agg.
        def run_phase(phase_fn, batch):
            for f in static_fns:
                f.begin_static(16 * expansion)
            try:
                out = phase_fn(batch)
            except Exception:
                for f in static_fns:
                    f.end_static()
                raise
            for f in static_fns:
                out = track((out, f.end_static()))
            return out

        if node.mode == "partial":
            return run_phase(node._partial, emit(node.children[0]))
        if node.mode == "final":
            child = node.children[0]
            while isinstance(child, ops.TpuCoalesceBatchesExec):
                child = child.children[0]
            nk = len(node.grouping)
            if (self.hosts > 1 and nk
                    and isinstance(child, ops.TpuShuffleExchangeExec)
                    and child.key_exprs
                    and len(child.key_exprs) == nk
                    and isinstance(child.children[0],
                                   ops.TpuHashAggregateExec)
                    and child.children[0].mode == "partial"
                    and len(child.children[0].grouping) == nk):
                # own the partial->final hand-off exchange so only
                # per-host REDUCED buffers cross DCN (hierarchical
                # aggregation) instead of every partial buffer riding
                # the generic two-stage exchange
                part = emit(child.children[0])
                ex = self._hierarchical_agg_exchange(
                    node, part, track, expansion, run_phase)
                return self._first_shard_only(
                    run_phase(node._merge_final, ex), node)
            return self._first_shard_only(
                run_phase(node._merge_final, emit(node.children[0])),
                node)
        # complete: the planner saw one partition; distribute it as
        # partial -> key-hash all_to_all -> final (the same shape the
        # planner emits for multi-partition children)
        child = emit(node.children[0])
        part = run_phase(node._partial, child)
        nk = len(node.grouping)
        if nk:
            ex = self._hierarchical_agg_exchange(
                node, part, track, expansion, run_phase)
        else:
            ex = gather_to_one(part, AXIS, self.chips)
            if self.hosts > 1:
                # after the ICI gather only each host's chip 0 holds
                # rows; one host-axis gather lands them all on (0,0)
                ex = gather_to_one(ex, HOST_AXIS, self.hosts,
                                   site="dcn.gather")
        return self._first_shard_only(run_phase(node._merge_final, ex),
                                      node)

    def _hierarchical_agg_exchange(self, node, part: ColumnBatch,
                                   track, expansion: int,
                                   run_phase) -> ColumnBatch:
        """DCN-aware grouped-aggregate hand-off. Global destination
        shard g = hash(keys) % n decomposes as g = (g // chips) * chips
        + (g % chips): stage 1 moves rows to chip g % chips over ICI
        (within each host), a per-host _merge_buffers collapses
        duplicate keys, and stage 2 moves the REDUCED buffers to host
        g // chips over DCN — every key group still lands wholly on
        global shard g, but the expensive tier carries merged rows
        only. On a 1D mesh chips == n, so stage 1 alone is
        byte-identical to the classic single-exchange lowering."""
        nk = len(node.grouping)
        key_cols = [part.columns[i] for i in range(nk)]
        g = pmod(murmur3_columns(key_cols), self.n)
        slot = slot_capacity(part.capacity, self.chips, expansion)
        ex1 = track(all_to_all_batch(part, g % self.chips, self.chips,
                                     slot, AXIS, site="ici.exchange"))
        if self.hosts <= 1:
            return ex1
        merged = run_phase(node._merge_buffers, ex1)
        g2 = pmod(murmur3_columns(
            [merged.columns[i] for i in range(nk)]), self.n)
        # The DCN slot BETS on the reduction: each destination host
        # receives exactly one global shard's worth of MERGED groups,
        # so the per-dest expectation is a 1/n share of the original
        # shard — not the 1/hosts share a raw-row exchange would need
        # (which is statically wire-equal to the ICI stage and would
        # put as many bytes on the slow fabric as the fast one). A
        # low-reduction aggregate (near-distinct keys) overflows the
        # slot and recompiles with doubled expansion, like every slot.
        slot2 = slot_capacity(part.capacity, self.n, expansion)
        return track(all_to_all_batch(merged, g2 // self.chips,
                                      self.hosts, slot2, HOST_AXIS,
                                      site="dcn.exchange"))

    def _first_shard_only(self, out: ColumnBatch,
                          node: ops.TpuHashAggregateExec) -> ColumnBatch:
        """A global (ungrouped) aggregate emits exactly one row — on
        global shard 0, where gather_to_one put the buffers; the
        per-shard merge would otherwise emit its 'one row on empty
        input' everywhere."""
        if node.grouping:
            return out
        me = self._global_index()
        nr = jnp.where(me == 0,
                       jnp.asarray(out.num_rows, jnp.int32).reshape(()),
                       jnp.int32(0))
        return ColumnBatch(out.schema, out.columns, nr)

    def _emit_exchange(self, node: ops.TpuShuffleExchangeExec,
                       child: ColumnBatch, track,
                       expansion: int) -> ColumnBatch:
        if getattr(node, "ici_strategy", "ici") == "host":
            # the planner pinned this exchange to the host shuffle
            # path (iciShuffle disabled): no mesh lowering for it —
            # the whole plan falls back to the single-chip engine
            raise MeshCompileError(
                "exchange pinned to the host shuffle path")
        if node.key_exprs:
            # stage 1: intra-host by hash % chips over ICI (on a 1D
            # mesh chips == n — the whole exchange, byte-identical to
            # the classic lowering)
            ctx = EvalContext(child)
            kcols = [e.eval(ctx) for e in node.key_exprs]
            g = pmod(murmur3_columns(kcols), self.n)
            slot = slot_capacity(child.capacity, self.chips, expansion)
            b1 = track(all_to_all_batch(child, g % self.chips,
                                        self.chips, slot, AXIS,
                                        site="ici.exchange"))
            if self.hosts <= 1:
                return b1
            # stage 2: cross-host by hash // chips over DCN. The
            # exchange preserves the schema, so the keys re-evaluate
            # on the exchanged rows; g = (g//chips)*chips + (g%chips)
            # lands every key group wholly on global shard g.
            ctx1 = EvalContext(b1)
            k1 = [e.eval(ctx1) for e in node.key_exprs]
            g2 = pmod(murmur3_columns(k1), self.n)
            # sized off the ORIGINAL shard capacity (not b1's inflated
            # chips*slot one) so the DCN tier's static wire bytes stay
            # below the ICI tier's; skew overflows recompile bigger
            slot2 = slot_capacity(child.capacity, self.hosts, expansion)
            return track(all_to_all_batch(b1, g2 // self.chips,
                                          self.hosts, slot2, HOST_AXIS,
                                          site="dcn.exchange"))
        if node.num_partitions == 1:
            out = gather_to_one(child, AXIS, self.chips)
            if self.hosts > 1:
                out = gather_to_one(out, HOST_AXIS, self.hosts,
                                    site="dcn.gather")
            return out
        # round-robin repartition: balance rows across shards —
        # intra-host spread over ICI, then (2D) a host-axis spread of
        # the received rows over DCN
        dest = jnp.arange(child.capacity, dtype=jnp.int32) % self.chips
        slot = slot_capacity(child.capacity, self.chips, expansion)
        out = track(all_to_all_batch(child, dest, self.chips, slot,
                                     AXIS, site="ici.exchange"))
        if self.hosts <= 1:
            return out
        # spread by LIVE-row rank (not slot position): stage 1's output
        # is sparse (n_dest*slot with per-source tails), so a position
        # modulus could pile live rows on one host; the rank modulus
        # balances them exactly, which is what lets slot2 size off the
        # original shard capacity and keep DCN wire bytes below ICI's
        live2 = out.live_mask().astype(jnp.int32)
        dest2 = (jnp.cumsum(live2) - 1) % self.hosts
        slot2 = slot_capacity(child.capacity, self.hosts, expansion)
        return track(all_to_all_batch(out, dest2, self.hosts, slot2,
                                      HOST_AXIS, site="dcn.exchange"))
