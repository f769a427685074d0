"""Whole-stage fusion for the single-chip engine.

The eager engine executes planner output one operator dispatch at a
time (the reference's hot loop: `GpuExec.internalDoExecuteColumnar`
chaining one cuDF kernel per expression node, SURVEY.md section 3.3).
Every dispatch pays a fixed host cost and every host sync a round trip
(PERF.md has what a v5e attached to its host measured), so a
multi-operator pipeline is dispatch-bound long before it is
bandwidth-bound. This module compiles a whole query into a handful of
XLA programs instead:

- one fused PER-PARTITION program per scan task — the scan-side
  operator chain (filter/project/partial-aggregate) plus a static
  "shrink" that slices aggregate output down to a small capacity
  bucket so concatenation stays cheap;
- one fused REDUCE program per blocking operator (final aggregate,
  sort, window, join, limit) that concatenates the per-partition
  results ON DEVICE and applies the operator in the same program, so
  a single-chip exchange costs zero host traffic (the one-device
  analog of the mesh compiler's all_to_all lowering,
  parallel/plan_compiler.py).

Data-dependent sizes use the engine's standard static-capacity +
overflow-flag discipline: join expansions and aggregate shrink caps
are static; overflow raises TpuSplitAndRetryOOM on the host and the
query re-runs with doubled factors (leaf batches stay device-resident
across retries, so only the programs recompile).

Host->device transfer is the other cost a resident engine pays once
per byte, so scan uploads are NARROWED: integer columns whose observed min/max fit a smaller
width ship at that width and widen back to their logical dtype inside
the fused program (the role nvcomp-compressed shuffle payloads play
for the reference's PCIe transfers, TableCompressionCodec.scala).

Plans containing operators without a fused lowering raise
FusedCompileError; the session falls back to the per-operator
out-of-core engine, which remains the path for HBM-exceeding inputs.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import hashlib
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from spark_rapids_tpu.columnar.arrow_bridge import (
    _primitive_np,
    device_to_arrow,
    schema_from_arrow,
)
from spark_rapids_tpu.columnar.batch import (
    ColumnBatch,
    DeviceColumn,
    empty_like_schema,
    next_capacity,
)
from spark_rapids_tpu.exec import agg_pushdown
from spark_rapids_tpu.exec import joins as J
from spark_rapids_tpu.exec import operators as ops
from spark_rapids_tpu.exec.base import PhysicalPlan
from spark_rapids_tpu.ops import filterops, joinops
from spark_rapids_tpu.runtime import faults
from spark_rapids_tpu.runtime.errors import TpuSplitAndRetryOOM
from spark_rapids_tpu.sqltypes import StringType, StructType

# capacity granularity for scan uploads: fine-grained (vs power-of-two
# buckets) because padding bytes cross the host->device link and sit
# in HBM (what the granularity is worth on a chip: not measured)
_UPLOAD_ALIGN = 1 << 16
# rows from which a PLAIN file's batch is padded to its bucket: below,
# `bucket_capacity`'s steps are the 64Ki floor, up to 2^16 slots of
# padding for a table of a few thousand rows
_PLAIN_BUCKET_ROWS = 1 << 20


class FusedCompileError(NotImplementedError):
    """Plan has no fused single-chip lowering (caller falls back to the
    per-operator out-of-core engine)."""


class LookupUniquenessLost(Exception):
    """The lookup-join lowering's unique-build-key bet failed (a probe
    row saw >1 matches). Internal to the fused retry loop: `joins`
    holds (plan key, "unique") of the inner and left lookup joins of
    the programs that saw it, the re-run keeps the same capacity
    factors but lowers those via the expanded blocking path, and the
    executor's `wide_joins` remembers them."""

    def __init__(self, joins):
        super().__init__("duplicate build keys; re-lowering joins "
                         "expanded")
        self.joins = joins


class GroupOverflow(Exception):
    """A final aggregate found more groups than the capacity it shrinks
    its output to. Internal to the fused retry loop: `aggs` holds the
    plan keys of the aggregates that overflowed, the re-run gives those
    four times the capacity (only their programs and the ones after
    them recompile), and the executor's `wide_joins` remembers the
    capacity that held."""

    def __init__(self, aggs):
        super().__init__("more groups than a final aggregate's "
                         "capacity; re-running it larger")
        self.aggs = aggs


class PushdownOverflow(Exception):
    """The agg-pushdown bet failed to fit: the probe side has more
    distinct join keys than the group capacity, so the pre-aggregate
    would not shrink. Internal to the fused retry loop: the re-run
    keeps the same factors but skips the pushdown rewrite (the
    original plan's own capacities are unaffected)."""


class SurvivorOverflow(Exception):
    """A lookup join's survivor bet failed: more rows passed the
    pending filter than the static capacity the join brought them to
    (`survivor_capacity`). Internal to the fused retry loop: `joins`
    holds the plan keys of the joins that lost, the re-run lowers those
    at full width, and the executor's `wide_joins` remembers them."""

    def __init__(self, joins):
        super().__init__("filter survivors exceed the lookup join's "
                         "capacity; re-running it at full width")
        self.joins = joins


def _check_host_flags(host: np.ndarray, ovf_aggs: tuple,
                      uniq_joins: tuple = (), n_push: int = 0,
                      survivor_joins: tuple = ()) -> None:
    """host = [capacity | uniqueness | pushdown | survivors | ansi
    3-vectors]. `ovf_aggs` names, for each capacity flag, the final
    aggregate whose shrink it is (else None); `uniq_joins`, for each
    uniqueness flag, the joins of its program that made the bet. A
    lost survivor bet wins: its run dropped rows, so every other flag
    is re-checked by the re-run on the full data. Then capacity
    overflow (a final aggregate's own where no other program
    overflowed), then the lookup-uniqueness and pushdown re-lowering
    retries, then ANSI raises per error class."""
    from spark_rapids_tpu.expr.ansicheck import raise_host

    n_ovf, n_uniq = len(ovf_aggs), len(uniq_joins)
    flagged = n_ovf + n_uniq + n_push
    lost = host[flagged:flagged + len(survivor_joins)]
    if bool(np.any(lost)):
        raise SurvivorOverflow(
            {k for k, f in zip(survivor_joins, lost) if f})
    over = [agg for agg, f in zip(ovf_aggs, host[:n_ovf]) if f]
    if over:
        if None not in over:
            raise GroupOverflow(set(over))
        raise TpuSplitAndRetryOOM(
            "fused program capacity overflow; recompiling larger")
    dup = host[n_ovf:n_ovf + n_uniq]
    if bool(np.any(dup)):
        raise LookupUniquenessLost(
            {(k, "unique") for ks, f in zip(uniq_joins, dup) if f
             for k in ks})
    if bool(np.any(host[n_ovf + n_uniq:n_ovf + n_uniq + n_push])):
        raise PushdownOverflow(
            "probe join-key cardinality exceeds group capacity; "
            "re-running without agg pushdown")
    rest = host[flagged + len(survivor_joins):]
    if rest.size:
        a = rest.reshape(-1, 3).any(axis=0)
        raise_host(bool(a[0]), bool(a[1]), bool(a[2]))


# ----------------------------------------------------- narrowed upload

_NARROW_STEPS = {
    np.dtype(np.int64): (np.int32, np.int16),
    np.dtype(np.int32): (np.int16,),
}


def _quantize_range(lo: int, hi: int):
    """Power-of-two envelope of an observed [lo, hi] so refills of the
    same column land on the same static vrange (one trace, not one per
    file)."""
    hi_q = (1 << int(max(hi, 0)).bit_length()) - 1
    lo_q = 0 if lo >= 0 else -(1 << int(-lo).bit_length())
    return lo_q, hi_q


def _narrow(vals: np.ndarray):
    """-> (vals possibly narrowed, quantized (lo, hi) or None)."""
    if vals.size == 0 or not np.issubdtype(vals.dtype, np.integer):
        return vals, None
    lo, hi = int(vals.min()), int(vals.max())
    vrange = _quantize_range(lo, hi)
    for cand in reversed(_NARROW_STEPS.get(vals.dtype, ())):
        info = np.iinfo(cand)
        if info.min <= lo and hi <= info.max:
            return vals.astype(cand), vrange
    return vals, vrange


def bucket_capacity(n: int) -> int:
    """Padded-shape bucket for scan uploads: capacities land on one of
    16 steps per power-of-two octave (1/16-octave granularity), so
    files of merely SIMILAR size share one compiled program per stage
    instead of one per distinct row count — each distinct capacity
    multiplies every downstream fused program. Padding stays <= 12.5%
    (a full power-of-two bucket would cost up to 100%, in upload bytes
    and in HBM). Below 2^20 rows the _UPLOAD_ALIGN floor dominates
    and the bucketing is the old alignment exactly."""
    n = max(int(n), 1)
    step = max(1 << max(int(n - 1).bit_length() - 4, 0), _UPLOAD_ALIGN)
    return -(-n // step) * step


def upload_narrowed(table: pa.Table, capacity: Optional[int] = None,
                    narrow: bool = True,
                    bucket: bool = True) -> ColumnBatch:
    """pyarrow Table -> device ColumnBatch with integer columns shipped
    at their observed width (widened back in-trace by `widen_traced`).
    One device_put for the whole batch, like arrow_to_device."""
    from spark_rapids_tpu.obs import events as obs_events
    from spark_rapids_tpu.obs import telemetry

    with obs_events.span("scan.decode") as sp:
        host, nbytes = _narrowed_host_batch(table, capacity, narrow,
                                            bucket)
        sp.set(rows=table.num_rows, bytes=nbytes)
    return telemetry.put_watched(host, "scan.upload", nbytes)


def _narrowed_host_batch(table: pa.Table, capacity: Optional[int],
                         narrow: bool, bucket: bool):
    """-> (the ColumnBatch of numpy leaves to upload, its bytes)."""
    table = table.combine_chunks()
    n = table.num_rows
    cap = capacity or (
        bucket_capacity(n) if bucket else
        max(_UPLOAD_ALIGN,
            -(-max(n, 1) // _UPLOAD_ALIGN) * _UPLOAD_ALIGN))
    schema = schema_from_arrow(table.schema)
    cols: List[DeviceColumn] = []
    for i, field in enumerate(schema.fields):
        col = table.column(i)
        arr = (col.chunk(0) if col.num_chunks else
               pa.array([], type=table.schema.field(i).type))
        if pa.types.is_dictionary(arr.type) and not isinstance(
                field.dataType, StringType):
            # non-string dictionaries decode through the ONE shared
            # entry point (string dictionaries fall through to
            # column_from_arrow, which uploads them ENCODED)
            from spark_rapids_tpu.columnar import encoding as _enc

            arr = _enc.dictionary_decode(arr)
        dt = field.dataType
        np_dt = getattr(dt, "np_dtype", None)
        if (narrow and np_dt is not None
                and np.issubdtype(np.dtype(np_dt), np.integer)
                and not isinstance(dt, StringType)):
            vals, validity = _primitive_np(arr, dt)
            if getattr(vals, "ndim", 1) == 1:
                vals, vrange = _narrow(np.ascontiguousarray(vals))
                if validity is None:
                    validity = np.ones(n, dtype=np.bool_)
                data = np.zeros(cap, dtype=vals.dtype)
                data[:n] = vals
                vpad = np.zeros(cap, dtype=np.bool_)
                vpad[:n] = validity
                cols.append(DeviceColumn(dt, data, vpad, vrange=vrange))
                continue
        from spark_rapids_tpu.columnar.arrow_bridge import (
            column_from_arrow,
        )

        cols.append(column_from_arrow(arr, field, cap))
    return (ColumnBatch(schema, cols, n),
            sum(c.device_size_bytes() for c in cols))


def same_ranges(parts: List[ColumnBatch]) -> List[ColumnBatch]:
    """The parts of ONE source with every column's stamped value range
    made the envelope of its parts' ranges. A range is static metadata
    of a traced program (it sizes packed sort keys and tables of
    positions), so parts that differ in nothing else would each trace
    and compile a program of their own: a table clustered by its key
    (TPC-H `lineitem` by `l_orderkey`) stamps another power-of-two
    envelope on every other file, and the 8 parts of one shape compiled
    the chain above them four times, 20 s each (PERF.md, PR 35). No
    data moves."""
    if len(parts) < 2:
        return parts
    out = [list(p.columns) for p in parts]
    for i in range(len(out[0])):
        ranges = {p.columns[i].vrange for p in parts}
        if len(ranges) > 1 and None not in ranges:
            env = (min(lo for lo, _ in ranges), max(hi for _, hi in ranges))
            for cols in out:
                cols[i] = cols[i].replace(vrange=env)
    return [ColumnBatch(p.schema, cols, p.num_rows)
            for p, cols in zip(parts, out)]


def widen_traced(batch: ColumnBatch) -> ColumnBatch:
    """In-trace inverse of the narrowed upload: restore each column's
    logical dtype (free relative to HBM bandwidth; fused with the first
    consumer by XLA)."""
    cols = []
    for c, f in zip(batch.columns, batch.schema.fields):
        np_dt = getattr(f.dataType, "np_dtype", None)
        if (np_dt is not None and c.data.ndim == 1
                and c.data.dtype != np.dtype(np_dt)
                and np.issubdtype(c.data.dtype, np.integer)):
            c = DeviceColumn(c.dtype, c.data.astype(np_dt), c.validity,
                             c.lengths, c.elem_validity, c.map_values,
                             vrange=c.vrange)
        cols.append(c)
    return ColumnBatch(batch.schema, cols, batch.num_rows)


def shrink_traced(batch: ColumnBatch, cap2: int):
    """Slice a front-compacted batch to a smaller static capacity.
    Aggregate outputs land compacted at segment-id positions
    (ops/segmented.py), so the slice is exact unless the true row count
    exceeds cap2 — reported via the overflow flag."""
    if cap2 >= batch.capacity:
        return batch, jnp.zeros((), bool)
    nr = jnp.asarray(batch.num_rows, jnp.int32)
    ovf = nr > cap2
    cols = [c.truncate(cap2) for c in batch.columns]
    return ColumnBatch(batch.schema, cols, jnp.minimum(nr, cap2)), ovf


#: A lookup join under a pending filter searches the filter's
#: survivors, brought to the front of this share of the batch: a bet
#: like `group_cap`, whose loss re-runs that join at full width. 1/64
#: holds a filter that keeps 1% with room; 1/8 would leave the search
#: at an eighth of the full width's cost, most of a query.
_SURVIVOR_SHARE = 64
_SURVIVOR_ALIGN = 1024


def survivor_capacity(n: int) -> Optional[int]:
    """The static capacity a batch of capacity `n` brings its filter's
    survivors to before a lookup join, or None where the batch is too
    small for the bet to pay."""
    cap = -(-max(n // _SURVIVOR_SHARE, 1) // _SURVIVOR_ALIGN) \
        * _SURVIVOR_ALIGN
    return cap if cap * 4 <= n else None


#: A filter that keeps this many times the survivors' share or more by
#: its own columns' stamped ranges is a bet not worth placing: its loss
#: costs a run and a compile of every program above it.
_HOPELESS = 4


def filter_share(condition, batch: ColumnBatch) -> Optional[float]:
    """The share of `batch`'s rows that `condition` keeps, by what the
    host knows without a sync: every conjunct an integer or date
    column against a literal, each taken for uniform over the range
    its upload stamped (`_narrow`: a power-of-two envelope) and
    independent of the others. None where a conjunct is anything else:
    two columns compared, a string, a function — which may keep any
    share (TPC-H Q12's keeps 0.5 % where its date range alone keeps
    14 %)."""
    from spark_rapids_tpu.plan.optimizer import (
        _filter_tuple,
        _split_conjuncts,
    )

    share = 1.0
    for conj in _split_conjuncts(condition):
        found = _filter_tuple(conj, batch.schema)
        if found is None:
            return None
        name, op, value = found
        col = batch.columns[batch.schema.names.index(name)]
        if (col.vrange is None or isinstance(value, bool)
                or not isinstance(value, (int, np.integer))):
            return None
        lo, hi = col.vrange
        below = min(max((value - lo) / (hi - lo + 1), 0.0), 1.0)
        share *= {"<": below, "<=": below, ">": 1.0 - below,
                  ">=": 1.0 - below, "=": 1.0 / (hi - lo + 1)}[op]
    return share


#: what the planner calls a join decides nothing on one chip, where
#: every partition is co-resident and an exchange passes through: a
#: shuffled hash join takes the lookup lowering as a broadcast one does
_HASH_JOINS = (J.TpuBroadcastHashJoinExec, J.TpuShuffledHashJoinExec)

#: lookup joins that read no build column: row-preserving whatever the
#: build keys hold, and nothing of the build side's payload is moved
_NO_BUILD_COLUMN = ("left_semi", "left_anti", "existence")


def build_gather(join_type: str) -> str:
    """What a lookup join moves of its build side, for the join's
    record and the program keys: "matched", the columns read at the
    rows a probe matched (`perm[lo]`), or "none"."""
    return "none" if join_type in _NO_BUILD_COLUMN else "matched"


@functools.lru_cache(maxsize=4096)
def program_name(key_tag: str, nodes_key) -> str:
    """`fused_<kind>_<8 hex digits>`: what a fused program is called in
    the device trace (XLA module `jit_<name>`) and on its
    `fused.dispatch` span. A digest of the structural key, so the same
    plan gives the same name in every process; shapes are not in it."""
    digest = hashlib.sha256(repr(nodes_key).encode()).hexdigest()[:8]
    return f"fused_{key_tag}_{digest}"


# --------------------------------------------------------- the executor

_SOURCE_TYPES = (ops.LocalRelationExec, ops.RangeExec, ops.TpuFileScanExec,
                 ops.ArrowToDeviceExec, ops.TpuCachedRelationExec)


def _agg_jittable(node: ops.TpuHashAggregateExec) -> bool:
    return all(a.children[0].jittable for a in node.aggs)


class FusedSingleChipExecutor:
    """Compile + run one physical plan as a few fused XLA programs on
    the default (single) device."""

    def __init__(self, conf=None, expansion: Optional[int] = None,
                 group_cap: Optional[int] = None,
                 wide_joins: Optional[set] = None):
        from spark_rapids_tpu.config import rapids_conf as rc

        self.conf = conf
        #: the bets this plan's programs lost, never placed again by
        #: whoever owns the set, which is the session
        #: (api/dataframe.py): the plan key of a lookup join that lost
        #: its survivor bet (SurvivorOverflow; `(key, "matches")` the
        #: bet on its own matches), `(key, "unique")` of one whose build
        #: keys were not unique (LookupUniquenessLost), `("groups",
        #: key, capacity)` of a final aggregate that found more groups
        #: than it shrank to (GroupOverflow)
        self._wide_joins = wide_joins if wide_joins is not None else set()

        def c(entry):
            return conf.get(entry) if conf is not None else entry.default

        self._expansion = expansion or c(rc.FUSED_EXPANSION)
        self._group_cap = group_cap or c(rc.FUSED_GROUP_CAP)
        self._max_expansion = c(rc.FUSED_MAX_EXPANSION)
        self._fetch_fused_bytes = c(rc.FUSED_SINGLE_SYNC_FETCH_BYTES)
        self._ansi = c(rc.ANSI_ENABLED)
        self._agg_pushdown = c(rc.FUSED_AGG_PUSHDOWN)
        self._lookup_conf = c(rc.FUSED_LOOKUP_JOIN)
        self._shape_buckets = c(rc.FUSED_SHAPE_BUCKETS)
        #: compile accounting of the most recent execute()/
        #: execute_repeated(): variantCount / programsCompiled /
        #: cacheHits (api/dataframe.py folds it into
        #: session.last_execution["compile"])
        self.last_compile_metrics = None
        #: what the joins of the most recent execute() did, or None
        #: where the plan has none: session.last_execution["join"]
        self.last_join_metrics = None
        self._run_joins: List[dict] = []
        #: how the settled run's partial aggregates lowered their sums,
        #: summed over its programs ({"limbs": 16, ...}), or None where
        #: it summed nothing: session.last_execution["agg"]
        self.last_agg_metrics = None
        self._run_agg = collections.Counter()
        #: how the settled run's programs lowered their sorts and
        #: group-bys, one record a dispatch and sort ({"program",
        #: "how": "packed" | "passes", "by", "operands", "keyBits",
        #: "passes", "slots"}) under "lowerings", with the most key
        #: operands any of them handed to `lax.sort`, or None where it
        #: sorted nothing: session.last_execution["sort"]
        self.last_sort_metrics = None
        self._run_sorts: List[dict] = []
        #: the settled run's final aggregates, one {"capacity",
        #: "found"} each in plan order: the slots its output was shrunk
        #: to and the groups it found, or None where it had none:
        #: session.last_execution["groups"]
        self.last_group_metrics = None
        self._run_groups: List[dict] = []

    # --- source preparation (once; survives expansion retries) ---

    def _collect_sources(self, node: PhysicalPlan,
                         out: List[PhysicalPlan]) -> None:
        if isinstance(node, _SOURCE_TYPES) or not node.is_tpu:
            out.append(node)
            return
        for c in node.children:
            self._collect_sources(c, out)

    def _hbm_budget(self) -> int:
        from spark_rapids_tpu.runtime.memory import get_catalog

        return get_catalog().pool.limit

    def _plain_file_batch(self, scan: ops.TpuFileScanExec, path: str,
                          parent=None) -> Optional[ColumnBatch]:
        """Device-direct scan of one PLAIN parquet file
        (io/parquet_plain.py): page payloads become zero-copy typed
        views, integers narrow for the link. A file of a million rows
        or more takes the capacity of its rows' bucket, as the general
        reader's batches do (`bucket_capacity`: TPC-H `lineitem`'s
        eight files at SF10 hold 7,498,257 or 7,498,256 rows, and every
        program above them would compile twice); a smaller one, one
        that fills its bucket, or any with bucketing off keeps capacity
        == rows and no pad copy touches the columns. None -> general
        reader."""
        from spark_rapids_tpu.io.parquet_plain import read_plain_columns
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.obs import telemetry

        if scan.fmt != "parquet" or scan.pushed_filters:
            return None
        names = [f.name for f in scan.schema.fields]
        with obs_events.span("scan.decode", parent=parent,
                             path=path) as sp:
            cols_np = read_plain_columns(path, names)
            if cols_np is None:
                return None
            n = len(cols_np[names[0]])
            cap = bucket_capacity(n) \
                if self._shape_buckets and n >= _PLAIN_BUCKET_ROWS else n
            valid = np.zeros(cap, dtype=np.bool_)
            valid[:n] = True
            cols: List[DeviceColumn] = []
            for f in scan.schema.fields:
                vals, vrange = _narrow(cols_np[f.name])
                if cap != n:
                    padded = np.zeros(cap, dtype=vals.dtype)
                    padded[:n] = vals
                    vals = padded
                cols.append(DeviceColumn(f.dataType, vals, valid,
                                         vrange=vrange))
            nbytes = sum(c.device_size_bytes() for c in cols)
            sp.set(rows=n, bytes=nbytes)
        return telemetry.put_watched(
            ColumnBatch(scan.schema, list(cols), n), "scan.plain",
            nbytes, parent)

    def _scan_parts(self, scan: ops.TpuFileScanExec) -> List[ColumnBatch]:
        tasks = [t for t in scan._tasks if t]
        if not tasks:
            return [empty_like_schema(scan.schema, 1024)]
        # pre-decode gate: decompressed+padded working set must fit HBM
        # comfortably, else the out-of-core engine is the right path
        fsz = sum(os.path.getsize(f) for t in tasks for f in t
                  if os.path.exists(f))
        if fsz * 6 > self._hbm_budget():
            raise FusedCompileError("scan working set exceeds HBM budget")

        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.obs import telemetry

        # the reader threads have no query scope and no open span:
        # theirs hang under this thread's (`fused.prepare`), by name
        parent = obs_events.current_span()

        def one(task):
            out, rest = [], []
            for path in task:
                b = (self._plain_file_batch(scan, path, parent)
                     if scan.fmt == "parquet" else None)
                if b is not None:
                    out.append(b)
                else:
                    rest.append(path)
            if rest or scan.fmt != "parquet":
                files = rest if scan.fmt == "parquet" else task
                tables = iter(scan._host_tables(files))
                path = files[0] if len(files) == 1 else \
                    f"{files[0]} (+{len(files) - 1})"
                while True:
                    # the reader is lazy: the parquet read happens in
                    # next(), so that is where the decode span opens
                    with obs_events.span("scan.decode", parent=parent,
                                         path=path) as sp:
                        t = next(tables, None)
                        if t is None:
                            sp.discard()
                            break
                        host, nbytes = _narrowed_host_batch(
                            t, None, True, self._shape_buckets)
                        sp.set(rows=t.num_rows, bytes=nbytes)
                    out.append(telemetry.put_watched(
                        host, "scan.upload", nbytes, parent))
            return out

        if len(tasks) == 1:
            groups = [one(tasks[0])]
        else:
            with ThreadPoolExecutor(
                    max_workers=min(8, len(tasks))) as pool:
                groups = list(pool.map(one, tasks))
        return [b for g in groups for b in g]

    def _prepare(self, phys: PhysicalPlan,
                 root_may_be_source: bool = False
                 ) -> Dict[int, List[ColumnBatch]]:
        sources: List[PhysicalPlan] = []
        self._collect_sources(phys, sources)
        if any(s is phys for s in sources):
            # a device source root is meaningful when materializing
            # parts (the relation cache); a HOST root never is
            if not (root_may_be_source and phys.is_tpu):
                raise FusedCompileError("plan root is a host operator")
        parts: Dict[int, List[ColumnBatch]] = {}
        total = 0
        for s in sources:
            if isinstance(s, ops.TpuCachedRelationExec):
                # device-resident cache entry: no decode, no upload
                ps = s.entry.device_parts()
            elif isinstance(s, ops.TpuFileScanExec) and s.is_tpu:
                ps = self._scan_parts(s)
            else:
                table = s.collect()
                if table.nbytes * 4 > self._hbm_budget():
                    raise FusedCompileError("source exceeds HBM budget")
                ps = [upload_narrowed(table, bucket=self._shape_buckets)]
            total += sum(b.device_size_bytes() for b in ps)
            parts[id(s)] = same_ranges(ps)
        if total * 4 > self._hbm_budget():
            raise FusedCompileError("working set exceeds HBM budget")
        self._src_parts = parts
        self._sources = sources
        return parts

    # --- per-run state ---

    def execute_parts(self, phys: PhysicalPlan) -> List[ColumnBatch]:
        """Run the plan but keep its output as DEVICE batches (no final
        host collect) — the relation cache's materializer
        (exec/relation_cache.py). Source-level integer narrowing and
        vrange metadata survive into the cached parts, so consumers of
        the cache keep the binned-aggregation fast path."""
        return self.execute(phys, as_parts=True)

    def _scaffold(self, phys: PhysicalPlan, root_may_be_source: bool,
                  body):
        """Shared run harness: validate, materialize caches, take the
        semaphore, prepare sources, run `body`, release/clean up. Both
        execute() and execute_repeated() run through here so the
        benchmark path cannot drift from the production path."""
        from spark_rapids_tpu.exec.base import new_task_context
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.runtime import semaphore as sem

        # `fused.prepare`: everything the first launch waits for
        with obs_events.span("fused.prepare") as sp:
            # validate the plan BEFORE decoding/uploading anything
            self._validate(phys)
            # materialize cold cache entries BEFORE taking permits:
            # entry materialization runs a nested execute() with a
            # FRESH task id, and a nested acquire under held permits
            # deadlocks the semaphore (its re-entrancy is per-task-id)
            self._premater_cached(phys)
            ctx = new_task_context(self.conf)
            sem.get().acquire_if_necessary(ctx.task_id)
            self._rewrite_memo = {}  # keyed on node ids: valid per run
            self._compile_metrics = {"keys": set(),
                                     "programsRequested": 0,
                                     "cacheHits": 0}
            try:
                parts = self._prepare(
                    phys, root_may_be_source=root_may_be_source)
            except BaseException:
                self._release(ctx)
                raise
            if sp.ref is not None:
                sp.set(sources=len(parts),
                       parts=sum(len(ps) for ps in parts.values()),
                       bytes=sum(b.device_size_bytes()
                                 for ps in parts.values() for b in ps))
        try:
            return body()
        finally:
            self._release(ctx)

    def _release(self, ctx) -> None:
        """The way out of `_scaffold`: permits, per-run state, and the
        run's compile accounting."""
        from spark_rapids_tpu.runtime import semaphore as sem

        sem.get().release_if_necessary(ctx.task_id)
        self._src_parts = None
        self._sources = None
        self._rewrite_memo = {}
        m = self._compile_metrics
        self.last_compile_metrics = {
            "variantCount": len(m["keys"]),
            "programsCompiled": m["programsRequested"],
            "cacheHits": m["cacheHits"],
        }

    def _run_with_retry(self, phys: PhysicalPlan, as_parts: bool):
        """One settled run under the retry loop; returns
        (result, (expansion, group_cap, use_lookup)) at the settings
        that succeeded. Capacity overflow doubles the factors; a final
        aggregate's own overflow raises its capacity alone; a lost
        lookup-uniqueness bet only flips the joins that made it to the
        expanded blocking lowering (same factors — nothing else
        recompiles bigger); a lost survivor bet lowers that join at
        full width; each for as long as `wide_joins` lives."""
        expansion, group_cap = self._expansion, self._group_cap
        use_lookup = use_pushdown = True
        reruns: List[str] = []
        while True:
            try:
                out = (self._run(phys, expansion, group_cap,
                                 as_parts=as_parts,
                                 use_lookup=use_lookup,
                                 use_pushdown=use_pushdown),
                       (expansion, group_cap, use_lookup,
                        use_pushdown))
                self._record_joins(reruns)
                self.last_agg_metrics = dict(self._run_agg) or None
                self.last_group_metrics = self._run_groups or None
                self.last_sort_metrics = {
                    "maxKeyOperands": max(
                        s["operands"] for s in self._run_sorts),
                    "lowerings": self._run_sorts,
                } if self._run_sorts else None
                return out
            except SurvivorOverflow as e:
                self._wide_joins.update(e.joins)
                reruns.append("survivorOverflow")
            except LookupUniquenessLost as e:
                if e.joins <= self._wide_joins:
                    use_lookup = False  # nothing new to learn: all of them
                self._wide_joins.update(e.joins)
                reruns.append("uniquenessLost")
            except GroupOverflow as e:
                self._wide_joins.update(
                    ("groups", k, 4 * self._final_cap(k, group_cap))
                    for k in e.aggs)
                reruns.append("groupOverflow")
            except PushdownOverflow:
                use_pushdown = False
                reruns.append("pushdownOverflow")
            except TpuSplitAndRetryOOM:
                if expansion >= self._max_expansion:
                    raise
                expansion *= 2
                group_cap *= 4
                reruns.append("capacityOverflow")

    def _known_cap(self, key) -> int:
        """The largest capacity the session remembers for the final
        aggregate with plan key `key`, or 0: it has not run yet."""
        return max([0] + [k[2] for k in self._wide_joins
                          if k[0] == "groups" and k[1] == key])

    def _final_cap(self, key, group_cap: int) -> int:
        """What that aggregate shrinks its output to: `group_cap`, or
        what the first look at its rows, or an overflow of its own,
        has brought it to."""
        return max(group_cap, self._known_cap(key))

    def _record_joins(self, reruns: List[str]) -> None:
        """The settled run's joins -> `last_join_metrics`, one `join`
        event each, and the open `fused.execute` span's `join` field."""
        from spark_rapids_tpu.obs import events as obs_events

        if not self._run_joins:
            self.last_join_metrics = None
            return
        self.last_join_metrics = {"runs": len(reruns) + 1,
                                  "rerunReasons": list(reruns),
                                  "joins": self._run_joins}
        for j in self._run_joins:
            obs_events.emit("join", runs=len(reruns) + 1, **j)

    def execute(self, phys: PhysicalPlan, as_parts: bool = False):
        from spark_rapids_tpu.config import rapids_conf as rc

        if (self.conf is not None
                and self.conf.get(rc.OOM_INJECTION_MODE) != "none"):
            # forced-OOM fault injection targets the eager engine's
            # allocation points (runtime/retry.py, the RmmSpark-forced
            # OOM analog) — fused programs have none to inject into, so
            # the inputs ROUTE THROUGH the eager path automatically (a
            # metric-counted degradation, not an error) and the
            # injection reaches real allocation sites
            if as_parts:
                # parts materialization (relation cache) keeps the
                # structural fallback its caller already handles
                raise FusedCompileError(
                    "OOM injection routes fused inputs through the "
                    "eager engine")
            return self._oom_injection_eager_fallback(phys)
        from spark_rapids_tpu.obs import events as obs_events

        # the fused engine runs whole stages as single XLA programs, so
        # there are no operator spans: its tree is prepare, one
        # dispatch per program, fetch
        with obs_events.span("fused.execute",
                             root=type(phys).__name__) as sp:
            out = self._scaffold(
                phys, as_parts,
                lambda: self._run_with_retry(phys, as_parts)[0])
            if self.last_join_metrics is not None:
                sp.set(join=self.last_join_metrics)
            if self.last_agg_metrics is not None:
                sp.set(agg=self.last_agg_metrics)
            if self.last_group_metrics is not None:
                sp.set(groups=self.last_group_metrics)
            if self.last_sort_metrics is not None:
                sp.set(sort=self.last_sort_metrics)
            return out

    def _oom_injection_eager_fallback(self, phys: PhysicalPlan):
        """Run the plan on the per-operator eager engine (whose
        reservation points honor oomInjection.mode), counting the
        demotion in the degrade ledger and the active session's
        metrics + last_execution['degradations']."""
        from spark_rapids_tpu.api.session import TpuSparkSession
        from spark_rapids_tpu.runtime import degrade

        reason = ("OOM injection targets the eager engine's "
                  "allocation points")
        degrade.record_demotion("fusedOomInjectionFallback")
        s = TpuSparkSession.active()
        if s is not None:
            s.query_metrics.metric(
                "degrade.fusedOomInjectionFallback").add(1)
            rec = s.last_execution
            if isinstance(rec, dict):
                rec.setdefault("degradations", []).append(
                    {"from": "fused", "to": "eager", "reason": reason})
        return phys.collect()

    def execute_repeated(self, phys: PhysicalPlan,
                         iters: int = 8) -> float:
        """Benchmark aid: dispatch the full compiled program pipeline
        `iters` times back-to-back with ONE host sync at the end and
        return the amortized per-iteration seconds. A single timed run
        includes the final host sync's round trip; the pipelined loop
        amortizes it away, leaving device compute + host dispatch, the
        reference's `compute time` notion (nsight device spans) for
        this engine."""
        import time as _time

        def body():
            # warm: compile + settle capacities through the standard
            # retry loop (fetches its own flags)
            _, (expansion, group_cap, use_lookup, use_pushdown) = \
                self._run_with_retry(phys, as_parts=True)
            t0 = _time.perf_counter()
            for _ in range(iters):
                parts, arr, ns = self._run(
                    phys, expansion, group_cap, as_parts=True,
                    defer_flags=True, use_lookup=use_lookup,
                    use_pushdown=use_pushdown)
            from spark_rapids_tpu.obs import telemetry as _tel

            # one sync drains the pipeline
            host = _tel.ledgered_get(arr, "fused.flags")
            dt = _time.perf_counter() - t0
            _check_host_flags(host, *ns)
            return dt / iters

        return self._scaffold(phys, True, body)

    def _premater_cached(self, node: PhysicalPlan) -> None:
        if isinstance(node, ops.TpuCachedRelationExec):
            node.entry.materialize()
            return
        for c in node.children:
            self._premater_cached(c)

    # --- validation walk (no device work) ---

    def _validate(self, node: PhysicalPlan) -> None:
        if isinstance(node, _SOURCE_TYPES) or not node.is_tpu:
            return
        ok = isinstance(node, (
            ops.TpuProjectExec, ops.TpuFilterExec, ops.TpuExpandExec,
            ops.TpuGenerateExec, ops.TpuLocalLimitExec, ops.UnionExec,
            ops.TpuSortExec, ops.TpuWindowExec,
            ops.TpuCoalesceBatchesExec,
            ops.TpuShuffleExchangeExec,
            J.TpuShuffledHashJoinExec, J.TpuBroadcastHashJoinExec))
        if isinstance(node, ops.TpuHashAggregateExec):
            ok = _agg_jittable(node)
        if not ok:
            raise FusedCompileError(
                f"{type(node).__name__} has no fused lowering")
        for c in node.children:
            self._validate(c)

    # --- plan walking / program construction ---

    def _is_per_partition(self, node: PhysicalPlan) -> bool:
        # coalesce is identity here: fused stages already run on
        # whole-partition batches
        if isinstance(node, (ops.TpuProjectExec, ops.TpuFilterExec,
                             ops.TpuExpandExec, ops.TpuGenerateExec,
                             ops.TpuCoalesceBatchesExec)):
            return True
        return (isinstance(node, ops.TpuHashAggregateExec)
                and node.mode == "partial")

    def _is_lookup_join(self, node: PhysicalPlan,
                        use_lookup: bool) -> bool:
        """Equi-joins that lower as a ROW-PRESERVING lookup inside the
        per-partition chain, whatever the planner called them
        (`_HASH_JOINS`): each probe row gathers its
        single build match (or its absence becomes a pending-mask /
        null-validity fact), so the join needs NO expansion buffer and
        fuses with the downstream aggregate — the star-schema shape.
        semi/anti/existence are row-preserving unconditionally;
        inner/left additionally assume UNIQUE build keys, checked by a
        dedicated uniqueness flag — a duplicate-key build re-runs
        (same capacity factors) with that join lowered via the
        expanded blocking path (`emit_blocking`), which `_run`'s
        `is_lookup` then keeps it on (`wide_joins`)."""
        if not isinstance(node, _HASH_JOINS) \
                or node.condition is not None:
            return False
        if not self._lookup_conf:
            return False
        if node.join_type in _NO_BUILD_COLUMN:
            return True
        return node.join_type in ("inner", "left") and use_lookup

    def _run(self, phys: PhysicalPlan, expansion: int,
             group_cap: int, as_parts: bool = False,
             defer_flags: bool = False, use_lookup: bool = True,
             use_pushdown: bool = True):
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.obs import telemetry
        from spark_rapids_tpu.parallel.plan_compiler import (
            _plan_key,
            concat_in_place,
            concat_traced,
            shard_equi_join,
        )
        from spark_rapids_tpu.runtime.jit_cache import cached_jit

        flags: List[tuple] = []       # (final agg's key | None, scalar)
        uniq_flags: List[tuple] = []  # (its joins' keys, scalar)
        push_flags: List[jnp.ndarray] = []  # pushdown shrink, scalar
        surv_flags: List[tuple] = []        # (join key, scalar): lost bet
        ansi_flags: List[jnp.ndarray] = []  # (3,) [arith, div0, cast]
        # what each join of this run did, by its plan key, summed over
        # the parts; `buildRows` holds device scalars until the fetch
        joins: Dict[tuple, dict] = {}
        # the final aggregates: their capacity and, a device scalar
        # until the fetch, the groups they found
        groups: List[dict] = []
        plan_keys: Dict[int, tuple] = {}
        self._run_joins = []
        self._run_groups = []
        self._run_agg = collections.Counter()
        self._run_sorts = []
        ansi_on = self._ansi
        # ANSI checks see pre-join row visibility; the pushdown's
        # pre-aggregate would evaluate agg inputs on probe rows the
        # join later drops, raising spurious ANSI errors — so ANSI
        # keeps the literal plan order
        push_on = use_pushdown and self._agg_pushdown and not ansi_on
        src_parts = self._src_parts

        def shapes_key(batches):
            from spark_rapids_tpu.columnar import encoding as _enc

            # dictionary identities ride the key: trace-time host
            # probes (predicate code rewrites, remap tables) bake
            # dictionary CONTENT into a program, so a cached program
            # must never serve a different dictionary
            return tuple(
                (tuple((tuple(leaf.shape), str(leaf.dtype))
                       for leaf in jax.tree_util.tree_leaves(b)),
                 _enc.encoding_key(b))
                for b in batches)

        def run_program(key_tag, nodes_key, fn, inputs, join_fields=None,
                        **uses):
            # program dispatch = the fused engine's cooperative yield
            # point (the per-attempt check of the stage scheduler,
            # scaled to this engine's unit of work): a cancelled query
            # stops before the next compile/dispatch instead of running
            # the pipeline to completion
            from spark_rapids_tpu.runtime import cancellation

            name = program_name(key_tag, nodes_key)
            with obs_events.span("fused.dispatch", program=name) as sp:
                if join_fields:
                    sp.set(joins=join_fields)
                cancellation.check_current()
                return dispatch(name, sp, key_tag, nodes_key, fn, inputs,
                                **uses)

        def dispatch(name, sp, key_tag, nodes_key, fn, inputs,
                     uses_expansion=False, uses_group_cap=False,
                     uses_ansi=False, survivor_joins=(), uniq_joins=(),
                     window_joins=(), final_agg=None):
            # chaos site device.dispatch: an injected fault here is the
            # fused engine "dying mid-dispatch"; the dispatch ladder
            # (api/dataframe.py) demotes the query to the eager engine
            faults.maybe_inject("device.dispatch", detail=str(key_tag))
            # device-loss gates (runtime/device_monitor.py): inputs
            # stamped before the current device epoch must raise here,
            # not dereference recycled device memory inside XLA
            from spark_rapids_tpu.runtime import device_monitor as _dm

            for inp in inputs:
                _dm.check_batch(inp)
            # VARIANT DEDUP: the key carries ONLY the parameters the
            # traced program consumes. The old key stamped every
            # program with (expansion, group_cap, ansi_on, use_lookup,
            # push_on), so an expansion retry, a lookup/pushdown
            # re-lowering, or the ANSI channel recompiled the WHOLE
            # pipeline; canonically a sort program is identical at any
            # expansion factor, and the lowering choices are already
            # structural (they change nodes_key). Round 5 measured the
            # multiplied variants at 482 s of cold start.
            key = ("fused", key_tag, nodes_key,
                   expansion if uses_expansion else None,
                   # True: the run's; a number: a final aggregate's own
                   (group_cap if uses_group_cap is True
                    else uses_group_cap or None),
                   bool(uses_ansi), shapes_key(inputs))
            from spark_rapids_tpu.runtime import compile_cache as cc
            from spark_rapids_tpu.runtime import jit_cache as jc

            m = self._compile_metrics
            hit = True  # a key this run has seen was built by then
            if key not in m["keys"]:
                m["keys"].add(key)
                hit = jc.probe(key)
                if hit:
                    m["cacheHits"] += 1
                    cc.stats.on_hit()
                else:
                    m["programsRequested"] += 1
                    # a compile is seconds, a fetch of the flags so far
                    # is not: a run that has already lost a bet stops
                    # HERE, before it compiles programs for shapes the
                    # re-run will not have (a join's survivors, an
                    # aggregate's capacity)
                    if flags and not defer_flags:
                        _check_host_flags(*flags_so_far())
            # the XLA module is `jit_<name>`: what the device trace
            # calls this program
            fn.__name__ = fn.__qualname__ = name
            jitted = cached_jit(key, lambda: fn)
            # fatal-classification + chaos site device.fatal: a dead
            # PJRT client surfacing here fences the engine for warm
            # recovery instead of leaking an XlaRuntimeError (or being
            # mistaken for a ladder-demotable dispatch fault)
            # a built program's call is the PJRT enqueue (it blocks
            # once about five programs are outstanding); a build's first
            # call stays under its `compile` span
            with _dm.guard("fused.dispatch", detail=str(key_tag),
                           inject=True), (
                    obs_events.span("fused.enqueue") if hit
                    else contextlib.nullcontext()):
                out, fl, *rest = jitted(*inputs)
            # how the program's partial aggregate lowered its sums was
            # decided when it was traced, and is kept with it
            agg = jc.sum_lowerings(key)
            if agg:
                sp.set(agg=agg)
                self._run_agg.update(agg)
            # and so was how it lowered its sorts and group-bys
            sorts = jc.sort_lowerings(key)
            if sorts:
                sp.set(sort=sorts)
                self._run_sorts.extend(dict(s, program=name)
                                       for s in sorts)
            # fl: scalar=[cap] | [cap, uniq, push] (chain programs), then
            # one lost-bet flag for each of `survivor_joins`, then what
            # nothing reads (joinops.rows_at); rest: the ANSI vector
            # where the program has one, then, a scalar for each of
            # `window_joins`, the blocks of its search that took a window
            fl = jnp.asarray(fl).reshape(-1)
            flags.append((final_agg, fl[0]))
            if fl.shape[0] > 1:
                uniq_flags.append((tuple(uniq_joins), fl[1]))
                push_flags.append(fl[2])
            surv_flags.extend(
                (k, fl[3 + i]) for i, k in enumerate(survivor_joins))
            if uses_ansi:
                ansi_flags.append(rest.pop(0))
            for k, took in zip(window_joins, rest):
                joins[k]["windowedBlocks"].append(took)
            return out

        def ansi_vec(exprs, b, live):
            """Accumulated ANSI mask reduction for one node's exprs, or
            None when nothing in them can raise (expr/ansicheck.py);
            rows hidden by the pending filter mask never raise — same
            visibility the eager engine gets from compacting first."""
            from spark_rapids_tpu.expr import ansicheck

            if not ansi_on or not any(
                    ansicheck.has_ansi_checks(e) for e in exprs):
                return None
            return ansicheck.flags_vec(list(exprs), b, live)

        def chain_traced(nodes, batch, builds=(), ansi_live=False,
                         join_plan=(), masked=None):
            """Apply a bottom-up list of per-partition operators inside
            one trace; returns (batch, overflow). `builds` holds the
            already-materialized build batch for each lookup join in
            `nodes`, in chain (bottom-up) order. `ansi_live` is hoisted
            by the caller (chain_has_ansi): a chain none of whose
            expressions can raise traces to the SAME program with ANSI
            on or off, and keying on the hoisted fact instead of the
            session flag lets the two share the compiled executable.

            `masked`: the chain feeds a join's build side, whose index
            takes a mask of live rows and moves no row itself: the
            output is then (the batch UNCOMPACTED, its live mask), and
            of its plain columns only the ordinals in `masked` are
            computed (the others are zeros nobody reads: a gather of a
            column the probe side never asks for is most of such a
            chain's time).

            Filters are carried as a PENDING MASK rather than a physical
            compaction: an aggregation consumes the mask directly (its
            segment reductions already mask per row), so the canonical
            scan -> filter -> project -> partial-agg stage runs with no
            row movement at all — pure elementwise + scatter work."""
            from spark_rapids_tpu.expr import EvalContext

            ovf = jnp.zeros((), bool)
            uniq = jnp.zeros((), bool)
            push = jnp.zeros((), bool)
            ansi = jnp.zeros((3,), bool)
            b = widen_traced(batch)
            mask = None  # pending filter predicate over b's rows
            builds = list(builds)
            join_plan = list(join_plan)
            lost = []  # one flag per "lookupSurvivors" join
            unread = []  # joinops.rows_at: an output's place, no flag
            windows = []  # one count per join whose search is blocked

            def materialized(b, mask):
                return b if mask is None else filterops.compact(b, mask)

            def visible(b, mask):
                return b.live_mask() if mask is None \
                    else mask & b.live_mask()

            def lookup_join(nd, b, mask, bt, uniq, bet_to=None,
                            blocked=False):
                """Row-preserving join-as-gather (see _is_lookup_join):
                probe rows keep their positions; match/no-match lands
                in the pending mask (inner/semi/anti), the exists
                column, or right-column validity (left). `bt` is the
                build side as the buildprep program indexed it, ONCE
                per join and not once per probe partition: the batch
                lies as it lay and is read at `perm[lo]`, or at the row
                its table of positions names (joinops.BuildPositions).
                `bet_to`: the join's build side sits under a filter,
                so its MATCHES are brought to the front of a batch of
                that capacity before any build column is read
                (chain_joins); -> also whether they did not fit.
                `blocked`: the probes are searched a block at a time
                (`searchBlocks`), and how many blocks took a window of
                the index goes to `windows`."""
                work_l, lk = nd._prepare_keys(b, nd.left_keys)
                # `at`: the matching build row itself, or its place in
                # the sorted index
                by_position = isinstance(bt, joinops.BuildPositions)
                if by_position:
                    at, matched, dup = joinops.probe_positions(
                        bt, work_l, lk)
                else:
                    at, matched, *took = joinops.probe_matched(
                        bt, work_l, lk, with_windowed=blocked)
                    windows.extend(took)
                jt = nd.join_type
                over = None

                def and_mask(m):
                    return m if mask is None else mask & m

                def second(b, at, matched):
                    """A second match of a row of `b` matched at `at`:
                    the table of positions marked it; a search reads
                    the key after the match, at the width the caller
                    has brought the matches to."""
                    if by_position:
                        return dup
                    work, keys = nd._prepare_keys(b, nd.left_keys)
                    return joinops.second_match(bt, work, keys, at,
                                                matched)

                if jt in ("left_semi", "inner"):
                    # a visible probe row with >1 matches trips the
                    # uniqueness flag (inner: the re-run lowers this
                    # join via the expanded blocking path, same
                    # capacity factors; a semi join does not care)
                    if jt == "inner" and (bet_to is None or by_position):
                        uniq = uniq | jnp.any(
                            second(b, at, matched) & visible(b, mask))
                    mask = and_mask(matched)
                    if bet_to is not None:
                        ids, total = joinops.front_row_ids(
                            visible(b, mask), bet_to)
                        b = b.gather(ids, jnp.minimum(total, bet_to))
                        matched = jnp.arange(bet_to, dtype=jnp.int32) \
                            < total
                        at = jnp.take(at, ids)
                        mask, over = None, total > bet_to
                        if jt == "inner" and not by_position:
                            # every visible match is among these rows
                            # unless the bet is lost, and then the run
                            # is repeated
                            uniq = uniq | jnp.any(second(b, at, matched))
                    if jt == "left_semi":
                        return b, mask, uniq, over
                elif jt == "left_anti":
                    return b, and_mask(~matched), uniq, over
                elif jt == "existence":
                    return nd._exists_batch(b, matched), mask, uniq, over
                else:  # left: a miss keeps its row, its build columns null
                    uniq = uniq | jnp.any(
                        second(b, at, matched) & visible(b, mask))
                # inner / left: unique-build single-match gather
                rows = at
                if not by_position:
                    rows, plain_read = joinops.rows_at(
                        bt, jnp.clip(at, 0, bt.capacity - 1))
                    unread.append(plain_read)
                rcols = [c.gather(rows) for c in bt.batch.columns]
                rcols = [c.replace(validity=c.validity & matched)
                         for c in rcols]
                # nd.schema carries the planner's nullability (left
                # joins promote build-side fields to nullable)
                b = ColumnBatch(nd.schema, list(b.columns) + rcols,
                                b.num_rows)
                return b, mask, uniq, over

            def survivors(b, mask, cap):
                """The rows the pending mask lets through, at the front
                of a batch of capacity `cap`; -> (batch, lost bet)."""
                ids, total = joinops.front_row_ids(visible(b, mask), cap)
                return (b.gather(ids, jnp.minimum(total, cap)),
                        total > cap)

            for nd in nodes:
                if isinstance(nd, _HASH_JOINS):
                    jp = join_plan.pop(0)
                    # the host's walk (chain_joins) and this trace are
                    # one decision: a capacity it did not foresee is a
                    # defect, not a run-time condition
                    assert jp["probeSlots"] in (None, b.capacity), \
                        (jp, b.capacity)
                    if "probeFilter" in jp["bet"]:
                        b, over = survivors(b, mask, jp["searchedSlots"])
                        mask = None
                        lost.append(over)
                    b, mask, uniq, over = lookup_join(
                        nd, b, mask, builds.pop(0), uniq,
                        jp["outputCapacity"]
                        if "buildFilter" in jp["bet"] else None,
                        blocked=jp["searchBlocks"] > 0)
                    if over is not None:
                        lost.append(over)
                elif isinstance(nd, ops.TpuFilterExec):
                    av = ansi_vec([nd.condition], b, visible(b, mask))
                    if av is not None:
                        ansi = ansi | av
                    pred = nd.condition.eval(EvalContext(b))
                    m = pred.data & pred.validity
                    mask = m if mask is None else mask & m
                elif isinstance(nd, ops.TpuProjectExec):
                    av = ansi_vec(nd.exprs, b, visible(b, mask))
                    if av is not None:
                        ansi = ansi | av
                    b = nd._run(b)  # row-preserving; mask stays aligned
                elif isinstance(nd, ops.TpuExpandExec):
                    b, mask = materialized(b, mask), None
                    b = concat_traced(
                        [nd._run(b, i)
                         for i in range(len(nd.projections))])
                elif isinstance(nd, ops.TpuCoalesceBatchesExec):
                    pass  # identity: the stage input is one batch
                elif isinstance(nd, ops.TpuGenerateExec):
                    b, mask = materialized(b, mask), None
                    out_cap = next_capacity(expansion * b.capacity)
                    b, o = nd._explode_to_cap(b, out_cap)
                    ovf = ovf | o
                elif isinstance(nd, agg_pushdown.MergeTail):
                    # agg-pushdown terminator (exec/agg_pushdown.py):
                    # the batch holds [keys..., buffers...] of the
                    # pre-aggregated, joined groups — merge them per
                    # part (the blocking final/complete merge across
                    # parts happens in emit_blocking). Capacity is
                    # already <= group_cap: stage A shrank and the
                    # lookup join is row-preserving, so no shrink (the
                    # pushdown bet is checked at the pre-aggregate)
                    b, mask = materialized(b, mask), None
                    b = nd.agg._merge_buffers(b)
                else:  # partial aggregate: consumes the mask as `live`
                    live = visible(b, mask)
                    av = ansi_vec(list(nd.grouping) + list(nd.aggs),
                                  b, live)
                    if av is not None:
                        ansi = ansi | av
                    b, mask = nd._partial(b, live=live), None
                    b, o = shrink_traced(b, group_cap)
                    if getattr(nd, "_pushdown_synth", False):
                        # the synthesized pre-aggregate's shrink not
                        # fitting means the pushdown bet lost — the
                        # original plan's capacities are fine
                        push = push | o
                    else:
                        ovf = ovf | o
            if masked is None:
                out = materialized(b, mask)
            else:
                def dead(c):
                    return DeviceColumn(c.dtype, jnp.zeros_like(c.data),
                                        jnp.zeros_like(c.validity),
                                        vrange=c.vrange)

                cols = [c if i in masked or c.data.ndim != 1
                        or c.encoding is not None or c.children is not None
                        else dead(c) for i, c in enumerate(b.columns)]
                out = (ColumnBatch(b.schema, cols, b.capacity),
                       visible(b, mask))
            fl = jnp.stack([ovf, uniq, push] + lost + unread)
            return (out, fl) + ((ansi,) if ansi_live else ()) \
                + tuple(windows)

        def emit_parts(node: PhysicalPlan, read=None) -> List[ColumnBatch]:
            """`read`: the ordinals of `node`'s output its consumer
            reads (None: all), for a chain that builds a join's side
            (`live_after`)."""
            if id(node) in src_parts:
                return src_parts[id(node)]
            if (isinstance(node, ops.TpuCoalesceBatchesExec)
                    and id(node.children[0]) in src_parts):
                # coalesce directly over a source is identity here; skip
                # the program so source narrowing survives (matters for
                # cache materialization)
                return src_parts[id(node.children[0])]
            if isinstance(node, ops.TpuShuffleExchangeExec):
                # single chip: every partition is already co-resident
                return emit_parts(node.children[0], read)
            if isinstance(node, ops.UnionExec):
                return [b for c in node.children for b in emit_parts(c)]
            if chainable(node):
                nodes, cur = collect_chain(node)
                base = emit_parts(cur)
                if use_lookup and push_on \
                        and not bets_on_matches(nodes, base):
                    rep = rewrite_memo(nodes)
                    if rep is not None:
                        nodes, read = rep, None
                return run_chain(nodes, base, read=read)
            return [emit_blocking(node)]

        def is_lookup(n) -> bool:
            """`_is_lookup_join`, less the inner and left joins whose
            build keys this session has seen twice."""
            return (self._is_lookup_join(n, use_lookup)
                    and (n.join_type in _NO_BUILD_COLUMN
                         or (plan_key_memo(n), "unique")
                         not in self._wide_joins))

        def chainable(n):
            return self._is_per_partition(n) or is_lookup(n)

        def through_exchanges(n):
            # single chip: every partition is already co-resident
            while isinstance(n, ops.TpuShuffleExchangeExec):
                n = n.children[0]
            return n

        def collect_chain(node):
            """Walk the chainable span below `node` (inclusive), through
            the exchanges the planner put under a shuffled join;
            -> (exec-order nodes, the non-chainable base)."""
            chain = [node]
            cur = through_exchanges(node.children[0])
            while chainable(cur) and id(cur) not in src_parts:
                chain.append(cur)
                cur = through_exchanges(cur.children[0])
            return yield_lost_bets(list(reversed(chain))), cur

        def yield_lost_bets(nodes):
            """The chain with every inner lookup join that LOST its bet
            on its matches (`chain_joins`) behind the join right after
            it, where that one still places the bet and reads probe
            columns only: the join that was seen to keep more than 1/64
            of its rows yields to one that may keep fewer, and then
            probes, and bets, over that join's survivors. The columns
            come out in the plan's order through a projection of bare
            references, which moves nothing. Inner joins on columns of
            the same probe side commute."""
            if not self._wide_joins:
                return nodes
            # a bet lost in this run's earlier attempt counts at once
            memo = ("yield", len(self._wide_joins)) \
                + tuple(id(n) for n in nodes)
            if memo in self._rewrite_memo:
                return self._rewrite_memo[memo]
            out, i = [], 0
            while i < len(nodes):
                a = nodes[i]
                b = nodes[i + 1] if i + 1 < len(nodes) else None
                if b is not None and yields_to(a, b):
                    out.extend(swapped_joins(a, b))
                    i += 2
                else:
                    out.append(a)
                    i += 1
            self._rewrite_memo[memo] = out
            return out

        def yields_to(a, b) -> bool:
            def plain_inner(n):
                return is_lookup(n) and n.join_type == "inner"

            if not (plain_inner(a) and plain_inner(b)):
                return False
            n_probe = len(a.children[0].schema.fields)
            return ((plan_key_memo(a), "matches") in self._wide_joins
                    and (plan_key_memo(b), "matches")
                    not in self._wide_joins
                    and b.build_is_filtered()
                    and all(r < n_probe for k in b.left_keys
                            for r in k.references()))

        def swapped_joins(a, b):
            """[b over a's probe side, a over that, the columns back in
            a-then-b order]."""
            from spark_rapids_tpu.expr import Alias, BoundReference
            from spark_rapids_tpu.sqltypes import StructType

            probe = list(a.children[0].schema.fields)
            a_cols = list(a.schema.fields[len(probe):])
            b_cols = list(b.schema.fields[len(a.schema.fields):])
            first = type(b)(
                a.children[0], b.children[1], b.join_type, b.left_keys,
                b.right_keys, StructType(probe + b_cols), b.conf)
            second = type(a)(
                first, a.children[1], a.join_type, a.left_keys,
                a.right_keys, StructType(probe + b_cols + a_cols), a.conf)
            for new, old in ((first, b), (second, a)):
                new.build_side, new.chosen_by = planned_sides(old)
                new.origin = old
            n_p, n_a, n_b = len(probe), len(a_cols), len(b_cols)
            order = (list(range(n_p))
                     + list(range(n_p + n_b, n_p + n_b + n_a))
                     + list(range(n_p, n_p + n_b)))
            back = ops.TpuProjectExec(
                [Alias(BoundReference(o, f.dataType, f.nullable), f.name)
                 for o, f in zip(order, b.schema.fields)],
                second, b.schema, b.conf)
            return [first, second, back]

        def rewrite_memo(nodes):
            """Per-run memo of agg_pushdown.rewrite_chain: the rewrite
            deep-copies expressions and constructs fresh exec nodes, so
            re-deriving it on every dispatch (retries, execute_repeated
            iterations) is pure host-side waste on identical input."""
            key = tuple(id(n) for n in nodes)
            if key not in self._rewrite_memo:
                self._rewrite_memo[key] = \
                    agg_pushdown.rewrite_chain(nodes)
            return self._rewrite_memo[key]

        def chain_keys(nodes):
            return [n.chain_key()
                    if isinstance(n, agg_pushdown.MergeTail)
                    else plan_key_memo(n) for n in nodes]

        def plan_key_memo(n):
            # `_plan_key` builds a whole-subtree key: once a node a run
            if id(n) not in plan_keys:
                plan_keys[id(n)] = _plan_key(n)
            return plan_keys[id(n)]

        def bets_on_matches(nodes, base) -> bool:
            """Whether the chain's LAST join, the one the aggregate
            pushdown would aggregate below, bets on its own matches
            (`chain_joins`): it is then a selective filter, and an
            aggregate pushed below it would group the rows it drops.
            Once that bet is lost the pushdown applies again."""
            if not any(isinstance(n, _HASH_JOINS) for n in nodes):
                return False
            keys = chain_keys(nodes)
            return any("buildFilter" in chain_joins(
                nodes, keys, b)[-1]["bet"] for b in base)

        def chain_has_ansi(nodes) -> bool:
            """Hoisted ANSI relevance for one chain: True only when the
            session flag is on AND some chained expression can actually
            raise — the dedup axis run_program keys on."""
            from spark_rapids_tpu.expr import ansicheck

            if not ansi_on:
                return False
            for nd in nodes:
                if isinstance(nd, ops.TpuFilterExec):
                    exprs = [nd.condition]
                elif isinstance(nd, ops.TpuProjectExec):
                    exprs = nd.exprs
                elif isinstance(nd, ops.TpuHashAggregateExec):
                    exprs = list(nd.grouping) + list(nd.aggs)
                else:
                    continue
                if any(ansicheck.has_ansi_checks(e) for e in exprs):
                    return True
            return False

        def chain_joins(nodes, keys, base):
            """The host's walk of a chain that `chain_traced` follows:
            for each lookup join, bottom-up, what it is lowered to and
            the capacities around it, from the chain's input `base`. A
            join under a pending FILTER (a mask that a match
            alone left is no bet) searches the filter's survivors at
            `survivor_capacity`, unless the batch is too small, the
            join lost that bet before (`wide_joins`) or the filters,
            read off `base`'s own columns, keep `_HOPELESS` times that
            share by `filter_share` (`filterShare` in the record); the
            batch goes on at that capacity. An inner or semi join whose BUILD side
            sits under a filter is itself a filter: it places the same
            bet on its own matches, brought to the front before any
            build column is read (`bet`: which of the two a
            "lookupSurvivors" join placed). After an aggregate the
            capacity is the aggregate's own (None here)."""
            cap, filtered, out = base.capacity, False, []
            # what the pending filters keep, while they still read
            # `base`'s own columns (None: not known)
            share, at_base = 1.0, True
            for nd, key in zip(nodes, keys):
                if isinstance(nd, _HASH_JOINS):
                    bet, probe_slots = [], cap
                    hopeless = (share is not None and share
                                * _SURVIVOR_SHARE >= _HOPELESS)
                    to = (survivor_capacity(cap)
                          if filtered and cap is not None
                          and not hopeless
                          and key not in self._wide_joins else None)
                    if to:
                        bet.append("probeFilter")
                        cap, filtered = to, False
                    searched = cap
                    to = (survivor_capacity(cap)
                          if cap is not None
                          and nd.join_type in ("inner", "left_semi")
                          and (key, "matches") not in self._wide_joins
                          and nd.build_is_filtered() else None)
                    if to:
                        bet.append("buildFilter")
                        cap, filtered = to, False
                    out.append({
                        "lowering": "lookupSurvivors" if bet else "lookup",
                        "bet": "+".join(bet),
                        **join_labels(nd),
                        "joinType": nd.join_type,
                        "buildGather": build_gather(nd.join_type),
                        "probeSlots": probe_slots,
                        "searchedSlots": searched,
                        "outputCapacity": cap})
                    if filtered and share is not None:
                        out[-1]["filterShare"] = round(share, 4)
                    share, at_base = None, False
                    if bet:
                        # reads a slot brought to the front pays for
                        # its row id, in the widest mask searched
                        out[-1]["rowIdReads"] = joinops.search_reads(
                            probe_slots if "probeFilter" in bet
                            else searched)
                elif isinstance(nd, ops.TpuFilterExec):
                    filtered = True
                    one = filter_share(nd.condition, base) \
                        if at_base else None
                    share = None if None in (share, one) else share * one
                elif isinstance(nd, ops.TpuCoalesceBatchesExec):
                    pass
                elif isinstance(nd, ops.TpuProjectExec):
                    at_base = False
                elif isinstance(nd, ops.TpuExpandExec):
                    filtered, share, at_base = False, None, False
                    cap = cap and cap * len(nd.projections)
                elif isinstance(nd, ops.TpuGenerateExec):
                    filtered, share, at_base = False, None, False
                    cap = cap and next_capacity(expansion * cap)
                else:
                    cap, filtered = None, False  # an aggregate's own
                    share, at_base = None, False
            return out

        def planned_sides(nd) -> tuple:
            # a join made here, not by the planner, says "written"
            return (getattr(nd, "build_side", "right"),
                    getattr(nd, "chosen_by", "written"))

        def join_labels(nd) -> dict:
            """What the planner called the join and which child, as
            written, it builds (plan/overrides.py `_convert_join`);
            `buildJoins`, where the build side holds joins itself."""
            side, by = planned_sides(nd)
            out = {"planned": ("broadcast" if isinstance(
                       nd, J.TpuBroadcastHashJoinExec) else "shuffled"),
                   "buildSide": side, "chosenBy": by}

            def count(n) -> int:
                return isinstance(n, _HASH_JOINS) + sum(
                    count(c) for c in n.children)

            inside = count(nd.children[1])
            if inside:
                out["buildJoins"] = inside
            return out

        def note_join(key, rec, rows):
            """Add one program's share of a join to the run's record;
            `rows`: the device scalars that sum to its build rows."""
            if key not in joins:
                joins[key] = dict(rec, buildRows=rows, windowedBlocks=[])
                return
            was = joins[key]
            if rec["lowering"] not in was["lowering"].split("+"):
                was["lowering"] += "+" + rec["lowering"]
            for k in ("probeSlots", "searchedSlots", "outputCapacity",
                      "searchBlocks"):
                was[k] = (None if None in (was[k], rec[k])
                          else was[k] + rec[k])

        def agg_reads(agg):
            return {r for e in list(agg.grouping) + list(agg.aggs)
                    for r in e.references()}

        def live_after(nodes, i, read=None):
            """Ordinals of `nodes[i]`'s output that the rest of the
            chain reads, or None: all of them (the chain's output is
            materialized and its consumer did not say what it reads
            of it, `read`; or a node is not understood)."""
            last = nodes[-1]
            if i == len(nodes) - 1:
                return read
            if isinstance(last, ops.TpuHashAggregateExec):
                need, rest = agg_reads(last), nodes[i + 1:-1]
            elif read is not None:
                need, rest = set(read), nodes[i + 1:]
            else:
                return None
            for nd in reversed(rest):
                if isinstance(nd, ops.TpuProjectExec):
                    need = {r for o in need
                            for r in nd.exprs[o].references()}
                elif isinstance(nd, ops.TpuFilterExec):
                    need = need | set(nd.condition.references())
                elif isinstance(nd, _HASH_JOINS):
                    n_probe = len(nd.children[0].schema.fields)
                    need = {o for o in need if o < n_probe} | {
                        r for k in nd.left_keys for r in k.references()}
                elif not isinstance(nd, ops.TpuCoalesceBatchesExec):
                    return None
            return need

        def run_chain(nodes, base, masked=None, read=None):
            keys = chain_keys(nodes)
            nodes_key = tuple(
                k if isinstance(n, agg_pushdown.MergeTail) else k[:2]
                for n, k in zip(nodes, keys))
            # lookup-join build sides are indexed ONCE, outside the
            # per-partition programs, and ride in as extra inputs
            join_keys = [k for n, k in zip(nodes, keys)
                         if isinstance(n, _HASH_JOINS)]
            join_nodes = [n for n in nodes if isinstance(n, _HASH_JOINS)]
            # the inner and left ones bet on unique build keys
            # (a join the engine made of the plan's own, to push an
            # aggregate below it or to let it yield, bets for that one)
            uniq_keys = [plan_key_memo(getattr(n, "origin", n))
                         for n in join_nodes
                         if n.join_type not in _NO_BUILD_COLUMN]
            # the host's walk of every part's chain comes first: how a
            # build side is indexed follows from the slots it is
            # probed from
            plans = [chain_joins(nodes, keys, b)
                     if join_nodes else [] for b in base]
            def build_columns_read(n):
                need = live_after(nodes, nodes.index(n), read)
                n_probe = len(n.children[0].schema.fields)
                return None if need is None else {
                    o - n_probe for o in need if o >= n_probe}

            built = [
                build_table(n, sum(plan[i]["searchedSlots"] or 0
                                   for plan in plans),
                            sum(b.capacity for b in base),
                            build_columns_read(n))
                for i, n in enumerate(join_nodes)]
            builds = [bt for bt, _ in built]
            build_slots = [slots for _, slots in built]
            ansi_live = chain_has_ansi(nodes)
            uses = dict(
                uses_expansion=any(isinstance(n, ops.TpuGenerateExec)
                                   for n in nodes),
                uses_group_cap=any(
                    isinstance(n, ops.TpuHashAggregateExec)
                    for n in nodes),
                uses_ansi=ansi_live)

            def one(b, plan):
                bets = []
                for key, bt, slots, jp in zip(join_keys, builds,
                                              build_slots, plan):
                    jp["buildSlots"] = slots
                    by_position = isinstance(bt, joinops.BuildPositions)
                    jp["probe"] = "position" if by_position else "search"
                    jp["probeSteps"] = 1 if by_position \
                        else joinops.search_reads(slots)
                    # the one read of a probe by position: a row of the
                    # table where it has few enough, else an entry
                    jp["tableRows"] = joinops.table_rows(
                        bt.table.shape[0]) if by_position else 0
                    # a width that is an aggregate's own is not known
                    # here, and its search is not counted
                    jp["searchBlocks"] = 0 if by_position \
                        else joinops.search_blocks(jp["searchedSlots"] or 0)
                    note_join(key, jp, [bt.num_rows])
                    if "probeFilter" in jp["bet"]:
                        bets.append(key)
                    if "buildFilter" in jp["bet"]:
                        bets.append((key, "matches"))

                def stage_fn(b, *bs, _nodes=nodes, _al=ansi_live,
                             _plan=plan):
                    return chain_traced(_nodes, b, bs, ansi_live=_al,
                                        join_plan=_plan, masked=masked)

                # the lowering is structural: which joins search their
                # survivors, what each reads of a build side left as it
                # lay and how it finds the row is part of the program's
                # key (a chain with no join keeps the key, and the
                # name, it had; so does one with none of the later
                # lowerings: a mark is added only where one engaged)
                marked = nodes_key
                if bets:
                    marked += (("survivors",
                                tuple(jp["lowering"] for jp in plan)),)
                if any("buildFilter" in jp["bet"] for jp in plan):
                    marked += (("bets", tuple(jp["bet"] for jp in plan)),)
                if any(jp["probe"] == "position" for jp in plan):
                    marked += (("probe",
                                tuple(jp["probe"] for jp in plan)),)
                if plan:
                    marked += (("buildGather",
                                tuple(jp["buildGather"] for jp in plan)),)
                if masked is not None:
                    marked += (("masked", tuple(sorted(masked))),)
                return run_program("chain", marked, stage_fn,
                                   [b] + builds, join_fields=plan,
                                   survivor_joins=tuple(bets),
                                   uniq_joins=tuple(uniq_keys),
                                   window_joins=tuple(
                                       k for k, jp in zip(join_keys, plan)
                                       if jp["searchBlocks"]), **uses)

            return [one(b, plan) for b, plan in zip(base, plans)]

        def build_table(jn: PhysicalPlan, probed_slots: int,
                        chain_slots: int, columns_read=None):
            """-> (the build side of one lookup join, indexed where it
            lies, its slots) — ONE buildprep program per join per run,
            shared by every per-partition chain program as an extra
            pytree input. Its one plain integer key is read BY POSITION
            (joinops.BuildPositions: a table with an entry for every
            value of the key's stamped range) where writing that table
            costs no more than the search it saves — its entries are
            no more than `probed_slots`, the slots the chain programs
            probe it from, times the steps of a search of the sorted
            index — and it has no more entries than the chain's input
            has slots (`chain_slots`: 4 bytes a fact row at most). A
            sparse key under a selective probe-side filter (TPC-H
            Q12's `o_orderkey`: 67M values for 983,040 survivors x 24
            steps) keeps the sorted index and its search
            (joinops.BuildIndex). A build side that is a chain itself
            (TPC-H Q3: `orders` under the segment's customers) hands
            its parts over uncompacted, each with its mask, and
            computes only the columns in `columns_read` (None: all),
            its keys and what its filters read (`chain_traced`,
            `masked`)."""
            # filters right above the build side's source are taken
            # into this program as its mask of live rows: the index
            # sends the rows they drop last, or leaves them out of its
            # table, and a program that only compacted them is saved
            src, filters = jn.children[1], []
            while (isinstance(src, (ops.TpuFilterExec,
                                    ops.TpuCoalesceBatchesExec,
                                    ops.TpuShuffleExchangeExec))
                   and id(src) not in src_parts):
                if isinstance(src, ops.TpuFilterExec):
                    filters.insert(0, src)
                src = src.children[0]
            if chain_has_ansi(filters):
                src, filters = jn.children[1], []
            masks = []
            derived = id(src) not in src_parts and chainable(src)
            if derived:
                nodes, cur = collect_chain(src)
                derived = not isinstance(
                    nodes[-1], (ops.TpuHashAggregateExec,
                                agg_pushdown.MergeTail))
            if derived:
                n_cols = len(src.schema.fields)
                keep = set(range(n_cols)) if columns_read is None \
                    else set(columns_read)
                keep |= {r for e in list(jn.right_keys)
                         + [f.condition for f in filters]
                         for r in e.references()}
                pairs = run_chain(nodes, emit_parts(cur), masked=keep)
                parts, masks = ([p for p, _ in pairs],
                                [m for _, m in pairs])
            else:
                parts = emit_parts(src)
            gather = build_gather(jn.join_type)
            ranges = [jn.build_key_range(p) for p in parts]
            slots = sum(p.capacity for p in parts)
            # log2 of the slots, though the search now reads a row of
            # 128 keys a level (joinops.search_reads): what WRITING a
            # table costs did not change, and both cells' choices hold
            steps = max(1, slots.bit_length())
            by_position = bool(ranges) and None not in ranges
            if by_position:
                entries = (max(hi for _, hi in ranges)
                           - min(lo for lo, _ in ranges))
                # a table of more than one chunk is written a chunk at
                # a time, every build slot offered to every chunk
                # (joinops.build_positions): that must not cost more
                # than one read a probed slot either (TPC-H Q3's
                # 15.7M-slot derived `orders` into 58 chunks: not taken)
                chunks = -(-(entries + 1) // joinops.TABLE_CHUNK)
                by_position = (
                    entries < min(probed_slots * steps, chain_slots)
                    and (chunks == 1 or chunks * slots <= probed_slots))

            def bp_fn(*ps):
                from spark_rapids_tpu.expr import EvalContext

                # the parts end to end, uncompacted: the build side's
                # sort sends every dead row last anyway, and its table
                # of positions takes no dead row
                cb, live = concat_in_place(
                    concat_inputs(list(ps[:len(parts)])))
                if masks:
                    live = live & jnp.concatenate(ps[len(parts):])
                for f in filters:
                    pred = f.condition.eval(EvalContext(cb))
                    live = live & pred.data & pred.validity
                index = (jn._build_positions if by_position
                         else jn._build_index)
                return (index(cb, live, gather == "matched"),
                        jnp.zeros((), bool))

            # in the key, as in the chain's: no cache may hand a chain
            # that reads `perm[lo]` the sorted batch this program made
            # before it made an index, nor a table of positions
            marked = plan_key_memo(jn)[:2]
            if filters:
                marked += (("buildFilter", tuple(
                    plan_key_memo(f)[:2] for f in filters)),)
            if by_position:
                marked += (("probe", "position"),)
            marked += (("buildGather", gather),)
            if masks:
                marked += (("masked",),)
            return run_program("buildprep", marked, bp_fn,
                               parts + masks), slots

        def concat_inputs(parts):
            return [widen_traced(p) for p in parts]

        def emit_blocking(node: PhysicalPlan) -> ColumnBatch:
            if isinstance(node, ops.TpuHashAggregateExec):
                mode = node.mode
                if mode == "complete" and use_lookup and push_on:
                    # single-partition plans carry the aggregate as ONE
                    # complete node; the pushdown still applies — the
                    # per-part chain pre-aggregates + joins + merges
                    # buffers, and the blocking step only merge-finals
                    nodes, cur = collect_chain(node)
                    base = emit_parts(cur)
                    rep = (rewrite_memo(nodes)
                           if len(nodes) > 1
                           and not bets_on_matches(nodes, base) else None)
                    if rep is not None:
                        parts = run_chain(rep, base)

                        def mf_fn(*ps):
                            cb = concat_traced(concat_inputs(list(ps)))
                            return shrink_traced(node._merge_final(cb),
                                                 group_cap)

                        return run_program("aggmf",
                                           _plan_key(node)[:2],
                                           mf_fn, parts,
                                           uses_group_cap=True)
                parts = emit_parts(
                    node.children[0],
                    agg_reads(node) if mode == "complete" else None)
                # the shrink of THIS aggregate's output is a bet of its
                # own: where it alone overflowed, it alone grows
                # (GroupOverflow), and the session remembers by how much
                agg_key = _plan_key(node)[:2]
                if not self._known_cap(agg_key):
                    # the first time a session runs this aggregate it
                    # looks before it bets: the groups are no more than
                    # the rows that reach it (one fetch, once; TPC-H
                    # Q3's 114K groups would lose the bet, and the
                    # lost run's programs are compiled for nothing)
                    rows = sum(int(r) for r in telemetry.ledgered_get(
                        [p.num_rows for p in parts], "fused.flags"))
                    fit = group_cap
                    while fit < min(rows, sum(p.capacity for p in parts)):
                        fit *= 4
                    self._wide_joins.add(("groups", agg_key, fit))
                cap = self._final_cap(agg_key, group_cap)

                def agg_fn(*ps):
                    cb = concat_traced(concat_inputs(list(ps)))
                    av = None
                    if mode in ("complete",):
                        # complete mode evaluates the grouping/agg INPUT
                        # exprs here (partial mode checked them in-chain)
                        av = ansi_vec(
                            list(node.grouping) + list(node.aggs),
                            cb, cb.live_mask())
                        cb = node._partial(cb)
                    out = node._merge_final(cb)
                    out, ovf = shrink_traced(out, cap)
                    if av is not None:
                        return out, ovf, av
                    return out, ovf

                from spark_rapids_tpu.expr import ansicheck

                agg_ansi = (ansi_on and mode == "complete" and any(
                    ansicheck.has_ansi_checks(e)
                    for e in list(node.grouping) + list(node.aggs)))
                out = run_program("agg", agg_key, agg_fn, parts,
                                  uses_group_cap=cap == group_cap or cap,
                                  uses_ansi=agg_ansi, final_agg=agg_key)
                groups.append({"capacity": out.capacity,
                               "found": out.num_rows})
                return out
            if isinstance(node, ops.TpuSortExec):
                child = node.children[0]
                if isinstance(child, ops.TpuShuffleExchangeExec):
                    child = child.children[0]
                parts = emit_parts(child)

                def sort_fn(*ps):
                    cb = concat_traced(concat_inputs(list(ps)))
                    return node._run(cb), jnp.zeros((), bool)

                return run_program("sort", _plan_key(node)[:2], sort_fn,
                                   parts)
            if isinstance(node, ops.TpuWindowExec):
                child = node.children[0]
                if (isinstance(child, ops.TpuSortExec)
                        and node.presorted):
                    # the window program sorts internally
                    child = child.children[0]
                if isinstance(child, ops.TpuShuffleExchangeExec):
                    child = child.children[0]
                parts = emit_parts(child)

                def win_fn(*ps):
                    cb = concat_traced(concat_inputs(list(ps)))
                    return node._run(cb), jnp.zeros((), bool)

                return run_program("window", _plan_key(node)[:2], win_fn,
                                   parts)
            if isinstance(node, ops.TpuLocalLimitExec):
                parts = emit_parts(node.children[0])
                k = node.n

                def limit_fn(*ps):
                    cb = concat_traced(concat_inputs(list(ps)))
                    return filterops.slice_head(cb, k), jnp.zeros((), bool)

                return run_program("limit", (_plan_key(node)[:2],), limit_fn,
                                   parts)
            if isinstance(node, (J.TpuShuffledHashJoinExec,
                                 J.TpuBroadcastHashJoinExec)):
                lparts = emit_parts(node.children[0])
                rparts = emit_parts(node.children[1])
                nl = len(lparts)

                probe_slots = sum(p.capacity for p in lparts)
                build_slots = sum(p.capacity for p in rparts)
                out_cap = next_capacity(
                    expansion * max(probe_slots, build_slots))
                rec = {"lowering": "expand", **join_labels(node),
                       # the buffer an expanding join fills is sized by
                       # the session's factor, before a count is known
                       "capacityFrom": "factor",
                       "joinType": node.join_type,
                       "probeSlots": probe_slots,
                       "searchedSlots": probe_slots,
                       # only a lookup join's search is counted
                       "searchBlocks": 0,
                       "outputCapacity": out_cap,
                       "buildSlots": build_slots,
                       # shard_equi_join sorts the whole build side
                       "buildGather": "sorted"}
                key = _plan_key(node)
                note_join(key, rec, [p.num_rows for p in rparts])

                def join_fn(*ps):
                    lb = concat_traced(concat_inputs(list(ps[:nl])))
                    rb = concat_traced(concat_inputs(list(ps[nl:])))
                    return shard_equi_join(node, lb, rb, out_cap)

                return run_program("join", key[:2], join_fn,
                                   lparts + rparts, join_fields=[rec],
                                   uses_expansion=True)
            raise FusedCompileError(type(node).__name__)

        def all_flags_arr():
            tagged = flags or [(None, jnp.zeros((), bool))]
            ovf = [f.reshape((1,)) for _, f in tagged]
            uq = [f.reshape((1,)) for _, f in uniq_flags]
            pf = [f.reshape((1,)) for f in push_flags]
            sf = [f.reshape((1,)) for _, f in surv_flags]
            return (jnp.concatenate(ovf + uq + pf + sf + ansi_flags),
                    flag_tags(tagged))

        def flag_tags(tagged):
            # what `_check_host_flags` takes beside the flags
            return (tuple(a for a, _ in tagged),
                    tuple(ks for ks, _ in uniq_flags), len(push_flags),
                    tuple(k for k, _ in surv_flags))

        def flags_so_far():
            """-> (host flags, *ns) as `all_flags_arr` lays them out,
            assembled on the HOST from the scalars as they lie: asked
            before each compile of a cold run, with another count of
            flags each time, a device-side concatenate would be a
            program of its own to compile for every one."""
            host = telemetry.ledgered_get(
                ([f for _, f in flags], [f for _, f in uniq_flags],
                 push_flags, [f for _, f in surv_flags], ansi_flags),
                "fused.flags")
            return (np.concatenate(
                        [np.asarray(x, bool).reshape(-1)
                         for part in host for x in part]),
                    *flag_tags(flags))

        def assembled_flags(result, copied=()):
            """all_flags_arr(), a dozen tiny device operations enqueued
            from the host, then the `fetch.wait` until they and
            `result` are ready on the device: where the host learns
            that the query's device work is done. The copies to the
            host of the flags and of `copied` are started before the
            wait, as the device_get that follows would start them, so
            the wait adds no round trip. The build sides' row counts,
            the blocks of their searches that took a window and the
            final aggregates' groups ride the same fetch."""
            arr, ns = all_flags_arr()
            extra = (arr, [j["buildRows"] for j in joins.values()],
                     [g["found"] for g in groups],
                     [j["windowedBlocks"] for j in joins.values()])
            for leaf in jax.tree_util.tree_leaves((copied, extra)):
                if isinstance(leaf, jax.Array):
                    leaf.copy_to_host_async()
            with obs_events.span("fetch.wait"):
                jax.block_until_ready((result, extra))
            return extra, ns

        def settle(host, ns):
            """The fetched (flags, build rows, groups found, windowed
            blocks): raise what the flags say, else complete the run's
            records."""
            host_flags, host_rows, host_found, host_took = host
            _check_host_flags(np.asarray(host_flags), *ns)
            for rec, rows, took in zip(joins.values(), host_rows,
                                       host_took):
                rec["buildRows"] = sum(int(r) for r in rows)
                rec["windowedBlocks"] = sum(int(t) for t in took)
            for rec, found in zip(groups, host_found):
                rec["found"] = int(found)
            self._run_joins = list(joins.values())
            self._run_groups = groups

        parts = emit_parts(phys)
        if as_parts:
            if defer_flags:
                # benchmark path: caller syncs flags itself
                arr, ns = all_flags_arr()
                return parts, arr, ns
            # one host sync for overflow + ANSI; parts stay on device
            with obs_events.span("fetch", rows=0) as sp:
                extra, ns = assembled_flags(parts)
                settle(telemetry.ledgered_get(extra, "fused.flags"), ns)
                sp.set(bytes=extra[0].nbytes)
            return parts
        if len(parts) > 1:
            def collect_fn(*ps):
                return (concat_traced(concat_inputs(list(ps))),
                        jnp.zeros((), bool))

            result = run_program("collect", ("collect",), collect_fn,
                                 parts)
        else:
            def one_fn(b):
                return widen_traced(b), jnp.zeros((), bool)

            result = run_program("collect1", ("collect1",), one_fn, parts)
        # `fetch` is the device time the dispatches left outstanding
        # (its `fetch.wait`) plus the host's own copy, conversion and
        # settle
        with obs_events.span("fetch") as sp:
            nbytes = result.device_size_bytes()
            small = nbytes <= self._fetch_fused_bytes
            extra, ns = assembled_flags(result, result if small else ())
            if small:
                # small result: ONE round trip for rows+flags+data (the
                # standard path pays three — row_count, flags, fetch)
                from spark_rapids_tpu.columnar.arrow_bridge import (
                    device_to_arrow_fused,
                )

                table, host = device_to_arrow_fused(result, extra)
                settle(host, ns)
            else:
                # one host sync for all flags before fetching results
                settle(telemetry.ledgered_get(extra, "fused.flags"), ns)
                table = device_to_arrow(result)  # cut to its rows first
                nbytes = table.nbytes
            sp.set(bytes=nbytes, rows=table.num_rows)
        return table
