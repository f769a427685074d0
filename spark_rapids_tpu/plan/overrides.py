"""TpuOverrides — the planner/override engine (GpuOverrides analog).

Reference behavior being reproduced (`GpuOverrides.scala:4619-4775`,
`RapidsMeta.scala`, `GpuTransitionOverrides.scala`):
- wrap every logical node in a meta, tag device support with reasons
  (per-operator granularity; one unsupported expression sends just that
  operator to CPU),
- convert the plan to physical operators (Tpu* or Cpu* fallback),
- insert the physical necessities: partial/final aggregation around
  exchanges, co-partitioning exchanges for joins, single-partition
  exchange for global sort/limit, and host<->device transitions at every
  backend boundary (GpuRowToColumnarExec/GpuColumnarToRowExec roles),
- explain-only mode: report the would-be placement without executing
  (`spark.rapids.sql.mode=explainOnly`, `explainPotentialGpuPlan`
  GpuOverrides.scala:4500).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from spark_rapids_tpu.config import rapids_conf as rc
from spark_rapids_tpu.exec import operators as ops
from spark_rapids_tpu.exec.base import PhysicalPlan
from spark_rapids_tpu.expr import Alias, BoundReference
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.typesig import (
    expr_unsupported_reasons,
    key_type_supported,
)


class PlanMeta:
    """Tagging record for one logical node (RapidsMeta analog)."""

    def __init__(self, node: L.LogicalPlan):
        self.node = node
        self.reasons: List[str] = []
        self.children: List[PlanMeta] = []

    @property
    def can_run_on_device(self) -> bool:
        return not self.reasons

    def cannot_run(self, reason: str):
        self.reasons.append(reason)

    def explain(self, indent: int = 0, only_not_on_device=True) -> str:
        tag = ("*" if self.can_run_on_device else
               "!NOT_ON_TPU " + "; ".join(self.reasons))
        lines = []
        if not only_not_on_device or not self.can_run_on_device:
            lines.append("  " * indent +
                         f"{type(self.node).__name__} {tag}")
        for c in self.children:
            sub = c.explain(indent + 1, only_not_on_device)
            if sub:
                lines.append(sub)
        return "\n".join([ln for ln in lines if ln])


class TpuOverrides:
    def __init__(self, conf: rc.RapidsConf):
        self.conf = conf
        self.metas: List[PlanMeta] = []

    # ----- tagging -----

    def tag(self, node: L.LogicalPlan) -> PlanMeta:
        meta = PlanMeta(node)
        if not self.conf.get(rc.SQL_ENABLED):
            meta.cannot_run("spark.rapids.sql.enabled is false")
        op_name = type(node).__name__
        if not self.conf.exec_enabled(op_name):
            # per-exec switch (spark.rapids.sql.exec.<Name>=false —
            # the GpuOverrides exec-registry disable surface)
            meta.cannot_run(
                f"{op_name} disabled via spark.rapids.sql.exec."
                f"{op_name}=false")
        if self.conf.get(rc.CPU_ORACLE_ENABLED):
            meta.cannot_run("cpu-oracle session")
        elif isinstance(node, L.Project):
            for e in node.exprs:
                for r in expr_unsupported_reasons(e, self.conf):
                    meta.cannot_run(r)
        elif isinstance(node, L.Filter):
            for r in expr_unsupported_reasons(node.condition, self.conf):
                meta.cannot_run(r)
        elif isinstance(node, L.Aggregate):
            from spark_rapids_tpu.expr.aggregates import Max, Min
            from spark_rapids_tpu.sqltypes import StringType

            for e in node.grouping + node.aggregates:
                for r in expr_unsupported_reasons(e, self.conf):
                    meta.cannot_run(r)
            for g in node.grouping:
                r = key_type_supported(g.dtype)
                if r:
                    meta.cannot_run(r)
            from spark_rapids_tpu.expr.aggregates import (
                CollectList, CountDistinct, Percentile, _Bivariate,
                _Moments,
            )
            from spark_rapids_tpu.sqltypes import (
                ArrayType as _AT,
                NumericType as _NT,
            )

            for a in node.aggregates:
                fn = a.children[0]
                if (isinstance(fn, (Min, Max)) and fn.input is not None
                        and isinstance(fn.input.dtype, StringType)):
                    meta.cannot_run(
                        "string min/max aggregation runs on CPU in v1")
                from spark_rapids_tpu.plan.typesig import _wide_dec

                if (isinstance(fn, (CollectList, CountDistinct))
                        and fn.input is not None
                        and (isinstance(fn.input.dtype, (StringType, _AT))
                             or _wide_dec(fn.input.dtype))):
                    meta.cannot_run(
                        "collect/distinct over string/array/decimal128 "
                        "input runs on CPU in v1")
                if isinstance(fn, (_Moments, _Bivariate, Percentile)):
                    for e in fn.children:
                        if not isinstance(e.dtype, _NT):
                            meta.cannot_run(
                                f"{fn.name} requires numeric input")
        elif isinstance(node, L.Join):
            for e in node.left_keys + node.right_keys:
                for r in expr_unsupported_reasons(e, self.conf):
                    meta.cannot_run(r)
                r = key_type_supported(e.dtype)
                if r:
                    meta.cannot_run(r)
            if node.condition is not None:
                for r in expr_unsupported_reasons(node.condition, self.conf):
                    meta.cannot_run(r)
        elif isinstance(node, L.Sort):
            for o in node.orders:
                for r in expr_unsupported_reasons(o.expr, self.conf):
                    meta.cannot_run(r)
                r = key_type_supported(o.expr.dtype)
                if r:
                    meta.cannot_run(r)
        elif isinstance(node, L.Generate):
            for e in node.pass_through:
                for r in expr_unsupported_reasons(e, self.conf):
                    meta.cannot_run(r)
            gen_input = node.gen_alias.children[0].children[0]
            for r in expr_unsupported_reasons(gen_input, self.conf):
                meta.cannot_run(r)
        elif isinstance(node, L.Expand):
            for p in node.projections:
                for e in p:
                    for r in expr_unsupported_reasons(e, self.conf):
                        meta.cannot_run(r)
        elif isinstance(node, L.Sample):
            if node.with_replacement:
                meta.cannot_run("with-replacement sampling has no "
                                "fixed-shape device lowering (CPU)")
        elif isinstance(node, (L.MapInPandas, L.GroupedMapInPandas,
                               L.CoGroupedMapInPandas)):
            meta.cannot_run(
                "pandas exchange runs via the Arrow worker pool "
                "(GpuArrowEvalPythonExec family is host-side in the "
                "reference too)")
        elif isinstance(node, L.Window):
            self._tag_window(node, meta)
        elif isinstance(node, L.FileScan):
            from spark_rapids_tpu.plan.typesig import type_supported

            fmt_entry = rc._FMT_READ_ENTRIES.get(node.fmt)
            if fmt_entry is not None and not self.conf.get(fmt_entry):
                meta.cannot_run(
                    f"{node.fmt} reads disabled via {fmt_entry.key}")
            for f in node.schema.fields:
                r = type_supported(f.dataType)
                if r:
                    meta.cannot_run(f"column {f.name!r}: {r}")
        elif isinstance(node, L.LocalRelation):
            meta.cannot_run("in-memory relation stays host-side until "
                            "first device operator")
        # CachedRelation: always device-capable (the entry IS device
        # batches), no tagging required
        meta.children = [self.tag(c) for c in node.children]
        self.metas.append(meta)
        return meta

    def _tag_window(self, node: "L.Window", meta: PlanMeta):
        from spark_rapids_tpu.expr import windows as we
        from spark_rapids_tpu.expr.aggregates import (
            Average, CollectList, Count, First, Last, Max, Min,
            StddevPop, StddevSamp, Sum, VariancePop, VarianceSamp,
        )
        from spark_rapids_tpu.sqltypes import (
            ArrayType,
            MapType,
            NumericType,
            StringType,
        )

        supported_aggs = (Sum, Count, Min, Max, Average, First, Last,
                          VariancePop, VarianceSamp, StddevPop,
                          StddevSamp, CollectList)
        from spark_rapids_tpu.sqltypes import StructType as _St

        for f in node.children[0].schema.fields:
            if isinstance(f.dataType, _St):
                # the window exec rebuilds pass-through columns
                # leaf-wise via the sort permutation scatter; no
                # children-aware path yet
                meta.cannot_run(
                    f"struct payload column {f.name!r}: device window "
                    "has no struct lowering")
        for a in node.window_exprs:
            wexpr = a.children[0]
            for e in wexpr.spec.partitions:
                for r in expr_unsupported_reasons(e, self.conf):
                    meta.cannot_run(r)
            for o in wexpr.spec.orders:
                for r in expr_unsupported_reasons(o.expr, self.conf):
                    meta.cannot_run(r)
            fn = wexpr.function
            if isinstance(fn, we.WindowFunction):
                if fn.needs_order and not wexpr.spec.orders:
                    meta.cannot_run(
                        f"{type(fn).__name__} requires ORDER BY")
                if isinstance(fn, we.Lead):
                    for r in expr_unsupported_reasons(fn.input, self.conf):
                        meta.cannot_run(r)
                    if fn.default is not None:
                        for r in expr_unsupported_reasons(fn.default, self.conf):
                            meta.cannot_run(r)
            elif isinstance(fn, supported_aggs):
                from spark_rapids_tpu.plan.typesig import _wide_dec as _wd

                if fn.input is not None and _wd(fn.input.dtype):
                    meta.cannot_run(
                        "decimal(>18) window aggregation runs on CPU "
                        "in v1")
                if fn.input is not None:
                    for r in expr_unsupported_reasons(fn.input, self.conf):
                        meta.cannot_run(r)
                    if (isinstance(fn.input.dtype,
                                   (ArrayType, MapType))
                            and not isinstance(fn, CollectList)):
                        # frame kernels take flat/2-D inputs; array
                        # payloads (incl. the array<string> cube) have
                        # no first/last/min-max frame lowering
                        meta.cannot_run(
                            f"window {type(fn).__name__} over "
                            f"{fn.input.dtype.simpleString} runs on CPU")
                if (isinstance(fn, (Min, Max)) and
                        isinstance(fn.input.dtype, StringType)):
                    meta.cannot_run(
                        "string min/max over window frames runs on CPU")
                if isinstance(fn, CollectList):  # CollectSet subclasses
                    frame = wexpr.spec.frame
                    bounded = (frame is not None
                               and frame.frame_type == "rows"
                               and frame.lower is not None
                               and frame.upper is not None)
                    if not bounded:
                        meta.cannot_run(
                            "window collect over unbounded frames runs "
                            "on CPU (device output width is the static "
                            "frame span)")
                    elif int(frame.upper) - int(frame.lower) + 1 > 1024:
                        # the device kernel materializes a [rows, span]
                        # element matrix — wide frames belong on CPU
                        meta.cannot_run(
                            "window collect frame span > 1024 runs on "
                            "CPU")
                    elif isinstance(fn.input.dtype,
                                    (StringType, ArrayType, MapType)):
                        # frame_collect gathers a [cap, W] element
                        # matrix — only flat scalar elements fit
                        meta.cannot_run(
                            "window collect of string/array/map "
                            "elements runs on CPU")
            else:
                meta.cannot_run(f"window function {type(fn).__name__} "
                                "has no device implementation")
            frame = wexpr.spec.frame
            if (frame is not None and frame.frame_type == "range" and
                    (frame.lower not in (None, 0) or
                     frame.upper not in (None, 0))):
                orders = wexpr.spec.orders
                if (len(orders) != 1 or not orders[0].ascending or
                        not isinstance(orders[0].expr.dtype, NumericType)):
                    meta.cannot_run(
                        "RANGE frame offsets need one ascending numeric "
                        "ORDER BY key on device")

    # ----- conversion -----

    def apply(self, plan: L.LogicalPlan) -> Tuple[PhysicalPlan, PlanMeta]:
        meta = self.tag(plan)
        from spark_rapids_tpu.plan import cbo

        if self.conf.get(cbo.OPTIMIZER_ENABLED):
            cbo.apply_cbo(meta, self.conf)
        phys = self._convert(meta)
        explain_mode = self.conf.get(rc.EXPLAIN)
        if explain_mode != "NONE":
            txt = meta.explain(only_not_on_device=explain_mode ==
                               "NOT_ON_GPU")
            if txt:
                print(txt)
        return phys, meta

    def _to_device(self, child: PhysicalPlan) -> PhysicalPlan:
        if child.is_tpu:
            return child
        return ops.ArrowToDeviceExec(child, self.conf)

    def _gather_host(self, child: PhysicalPlan) -> PhysicalPlan:
        """Host child funneled to ONE partition (global grouping)."""
        host = self._to_host(child)
        if host.num_partitions > 1:
            return ops.CpuShuffleExchangeExec(host, None, 1, self.conf)
        return host

    def _to_host(self, child: PhysicalPlan) -> PhysicalPlan:
        if not child.is_tpu:
            return child
        return ops.DeviceToArrowExec(child, self.conf)

    def _convert(self, meta: PlanMeta) -> PhysicalPlan:
        node = meta.node
        conf = self.conf
        on_device = meta.can_run_on_device

        if isinstance(node, L.LocalRelation):
            return ops.LocalRelationExec(node.table, node.schema, conf)
        if isinstance(node, L.CachedRelation):
            return ops.TpuCachedRelationExec(node.entry, node.schema,
                                             conf)
        if isinstance(node, L.Range):
            return ops.RangeExec(node.start, node.end, node.step,
                                 node.num_partitions, node.schema, conf)
        if isinstance(node, L.FileScan):
            cols = node.schema.names
            filters = getattr(node, "pushed_filters", None)
            if on_device:
                scan = ops.TpuFileScanExec(node.fmt, node.paths,
                                           node.schema, conf,
                                           pushed_columns=cols,
                                           pushed_filters=filters,
                                           options=node.options)
                if conf.get(rc.COALESCE_AFTER_SCAN):
                    # chunked scans feed many small batches; coalesce
                    # toward batchSizeRows before per-batch consumers
                    # (GpuCoalesceBatches after-scan insertion)
                    return ops.TpuCoalesceBatchesExec(scan, conf)
                return scan
            return ops.CpuFileScanExec(node.fmt, node.paths, node.schema,
                                       conf, pushed_columns=cols,
                                       pushed_filters=filters,
                                       options=node.options)

        if isinstance(node, L.Limit):
            smeta = meta.children[0]
            if (isinstance(smeta.node, L.Sort) and smeta.node.global_sort
                    and on_device and smeta.can_run_on_device):
                # TakeOrderedAndProject fusion (GpuOverrides.scala:4084):
                # per-partition sort+limit, gather, final sort+limit —
                # never materializes more than n rows per partition
                inner = self._to_device(self._convert(smeta.children[0]))
                return self._take_ordered(node.n, smeta.node.orders,
                                          inner)

        children = [self._convert(c) for c in meta.children]

        if isinstance(node, L.Project):
            if on_device:
                return ops.TpuProjectExec(node.exprs,
                                          self._to_device(children[0]),
                                          node.schema, conf)
            return ops.CpuProjectExec(node.exprs, self._to_host(children[0]),
                                      node.schema, conf)
        if isinstance(node, L.Filter):
            if on_device:
                return ops.TpuFilterExec(node.condition,
                                         self._to_device(children[0]), conf)
            return ops.CpuFilterExec(node.condition,
                                     self._to_host(children[0]), conf)
        if isinstance(node, L.Expand):
            if on_device:
                return ops.TpuExpandExec(node.projections,
                                         self._to_device(children[0]),
                                         node.schema, conf)
            return ops.CpuExpandExec(node.projections,
                                     self._to_host(children[0]),
                                     node.schema, conf)
        if isinstance(node, L.Sample):
            if on_device:
                return ops.TpuSampleExec(node.fraction, node.seed,
                                         self._to_device(children[0]), conf)
            return ops.CpuSampleExec(node.fraction, node.seed,
                                     node.with_replacement,
                                     self._to_host(children[0]), conf)
        if isinstance(node, L.MapInPandas):
            # map is per-row: partition layout is irrelevant
            return ops.CpuMapInPandasExec(
                node.fn, node.schema, self._to_host(children[0]), conf)
        if isinstance(node, L.GroupedMapInPandas):
            # grouping must be GLOBAL: gather multi-partition children
            # (the aggregate path inserts the same exchange)
            return ops.CpuGroupedMapInPandasExec(
                node.key_names, node.fn, node.schema,
                self._gather_host(children[0]), conf)
        if isinstance(node, L.CoGroupedMapInPandas):
            return ops.CpuCoGroupedMapInPandasExec(
                node.key_names, node.fn, node.schema,
                self._gather_host(children[0]),
                self._gather_host(children[1]), conf)
        if isinstance(node, L.Aggregate):
            return self._convert_aggregate(node, children[0], on_device)
        if isinstance(node, L.Join):
            return self._convert_join(node, children, on_device)
        if isinstance(node, L.Sort):
            return self._convert_sort(node, children[0], on_device)
        if isinstance(node, L.Generate):
            if on_device:
                return ops.TpuGenerateExec(
                    node.pass_through, node.gen_alias, node.position,
                    self._to_device(children[0]), conf)
            return ops.CpuGenerateExec(
                node.pass_through, node.gen_alias, node.position,
                self._to_host(children[0]), conf)
        if isinstance(node, L.Window):
            return self._convert_window(node, children[0], on_device)
        if isinstance(node, L.Limit):
            return self._convert_limit(node, children[0], on_device)
        if isinstance(node, L.Union):
            tpu = all(c.is_tpu for c in children)
            kids = ([self._to_device(c) for c in children] if tpu
                    else [self._to_host(c) for c in children])
            return ops.UnionExec(kids, node.schema, conf, tpu)
        if isinstance(node, L.Repartition):
            child = children[0]
            keys = node.keys
            if on_device and (child.is_tpu or keys is not None):
                # no coalesce wrap: the exchange's reduce side already
                # re-slices fetched blocks at batchSizeRows (the
                # GpuShuffleCoalesceExec discipline), and downstream
                # isinstance-based exchange bypasses must keep matching
                return ops.TpuShuffleExchangeExec(
                    self._to_device(child), keys, node.num_partitions,
                    conf)
            return ops.CpuShuffleExchangeExec(self._to_host(child), keys,
                                              node.num_partitions, conf)
        raise NotImplementedError(f"logical node {type(node).__name__}")

    def _convert_aggregate(self, node: L.Aggregate, child: PhysicalPlan,
                           on_device: bool) -> PhysicalPlan:
        conf = self.conf
        shuffle_parts = conf.get(rc.SHUFFLE_PARTITIONS)
        if not on_device:
            return ops.CpuHashAggregateExec(
                node.grouping, node.aggregates,
                ops.CpuShuffleExchangeExec(
                    self._to_host(child), None, 1, conf)
                if child.num_partitions > 1 else self._to_host(child),
                node.schema, conf)
        child = self._to_device(child)
        if child.num_partitions == 1:
            return ops.TpuHashAggregateExec(
                "complete", node.grouping, node.aggregates, child, conf)
        partial = ops.TpuHashAggregateExec(
            "partial", node.grouping, node.aggregates, child, conf)
        if node.grouping:
            key_refs = [BoundReference(i, g.dtype)
                        for i, g in enumerate(node.grouping)]
            exchange = ops.TpuShuffleExchangeExec(
                partial, key_refs, shuffle_parts, conf)
        else:
            exchange = ops.TpuShuffleExchangeExec(partial, None, 1, conf)
        return ops.TpuHashAggregateExec(
            "final", node.grouping, node.aggregates, exchange, conf)

    def _convert_join(self, node: L.Join, children: List[PhysicalPlan],
                      on_device: bool) -> PhysicalPlan:
        from spark_rapids_tpu.exec.joins import swap_condition

        conf = self.conf
        left, right = children
        if not on_device:
            return ops.CpuJoinExec(
                self._single(self._to_host(left)),
                self._single(self._to_host(right)),
                node.join_type, node.left_keys, node.right_keys,
                node.schema, conf, condition=node.condition)
        shuffle_parts = conf.get(rc.SHUFFLE_PARTITIONS)
        left = self._to_device(left)
        right = self._to_device(right)
        join_type = node.join_type
        left_keys, right_keys = node.left_keys, node.right_keys
        condition = node.condition
        n_l = len(node.children[0].schema.fields)
        n_r = len(node.children[1].schema.fields)
        build_logical = node.children[1]
        # the build side is the RIGHT child of the exec node. A right
        # outer join is a swapped left outer; an inner equi-join builds
        # the side with fewer rows by what the planner knows of them
        # (Spark's JoinSelection picks the smaller side; the reference
        # plugin's GpuShuffledSymmetricHashJoinExec at run time), not
        # the side written last. Either way the columns come out in
        # the written order through a projection of bare references.
        swapped = join_type == "right"
        chosen_by = "written"
        if join_type == "inner" and left_keys:
            rows = [L.estimate_rows(c) for c in node.children]
            # the order of writing stands against anything short of
            # twice the rows. A count that is only a bound (a filter
            # below) may hide a far smaller side: where the written
            # build side alone has one, it stands unless the other
            # side's EXACT count is at most half that bound (TPC-H Q5:
            # 1.5M customers against 15M orders under a date filter,
            # whose non-unique `o_custkey` would expand the join)
            if None not in rows:
                swapped = 2 * rows[0] <= rows[1]
                if (swapped or L.rows_are_a_bound(node.children[0])
                        or not L.rows_are_a_bound(node.children[1])):
                    chosen_by = "rows"
        if swapped:
            left, right = right, left
            left_keys, right_keys = right_keys, left_keys
            if join_type == "right":
                join_type = "left"
            build_logical = node.children[0]
            if condition is not None:
                condition = swap_condition(condition, n_l, n_r)
        exec_schema = (self._swapped_schema(left, right, join_type)
                       if swapped else node.schema)
        if not left_keys or join_type == "cross":
            joined = self._nested_loop_join(
                left, right, join_type, condition, exec_schema)
        else:
            joined = self._hash_join(
                left, right, join_type, left_keys, right_keys, condition,
                exec_schema, build_logical, shuffle_parts)
        # for the join's record (exec/fused.py): which child as written
        # is built, and what chose it
        joined.build_side = "left" if swapped else "right"
        joined.chosen_by = chosen_by
        if not swapped:
            return joined
        # swapped layout is [orig-right fields | orig-left fields];
        # reorder back to node.schema = [left | right]
        swapped_schema = joined.schema
        order = list(range(n_r, n_r + n_l)) + list(range(n_r))
        reorder = [Alias(BoundReference(o, swapped_schema.fields[o].dataType,
                                        f.nullable), f.name)
                   for o, f in zip(order, node.schema.fields)]
        return ops.TpuProjectExec(reorder, joined, node.schema, conf)

    def _swapped_schema(self, left, right, join_type):
        """[left | right] of the swapped children: a right outer join
        run as a left outer one has every field of its new left side
        nullable, as it always had here; an inner join changes none."""
        from spark_rapids_tpu.sqltypes import StructField, StructType

        outer = join_type == "left"
        return StructType(
            [StructField(f.name, f.dataType, True if outer else f.nullable)
             for f in left.schema.fields] +
            [StructField(f.name, f.dataType, f.nullable)
             for f in right.schema.fields])

    def _hash_join(self, left, right, join_type, left_keys, right_keys,
                   condition, exec_schema, build_logical, shuffle_parts):
        conf = self.conf
        threshold = conf.get(rc.BROADCAST_THRESHOLD)
        est = L.estimate_size_bytes(build_logical)
        broadcastable = (threshold >= 0 and est is not None and
                         est <= threshold and
                         join_type in ("inner", "left", "left_semi",
                                       "left_anti", "existence"))
        if broadcastable:
            return ops.TpuBroadcastHashJoinExec(
                left, right, join_type, left_keys, right_keys,
                exec_schema, conf, condition=condition)
        both_single = (left.num_partitions == 1 and
                       right.num_partitions == 1)
        if not both_single:
            left = ops.TpuShuffleExchangeExec(
                left, left_keys, shuffle_parts, conf)
            right = ops.TpuShuffleExchangeExec(
                right, right_keys, shuffle_parts, conf)
        return ops.TpuShuffledHashJoinExec(
            left, right, join_type, left_keys, right_keys,
            exec_schema, conf, condition=condition)

    def _nested_loop_join(self, left, right, join_type, condition,
                          exec_schema):
        conf = self.conf
        if join_type == "full":
            # build-match tracking must be partition-local
            left = self._single(left)
        return ops.TpuBroadcastNestedLoopJoinExec(
            left, right, join_type, exec_schema, conf,
            condition=condition)

    def _single(self, plan: PhysicalPlan) -> PhysicalPlan:
        if plan.num_partitions == 1:
            return plan
        if plan.is_tpu:
            return ops.TpuShuffleExchangeExec(plan, None, 1, self.conf)
        return ops.CpuShuffleExchangeExec(plan, None, 1, self.conf)

    def _take_ordered(self, n: int, orders, child: PhysicalPlan
                      ) -> PhysicalPlan:
        conf = self.conf
        local = ops.TpuLocalLimitExec(
            n, ops.TpuSortExec(orders, child, conf), conf)
        if local.num_partitions > 1:
            local = ops.TpuLocalLimitExec(
                n, ops.TpuSortExec(
                    orders,
                    ops.TpuShuffleExchangeExec(local, None, 1, conf),
                    conf), conf)
        return local

    def _convert_sort(self, node: L.Sort, child: PhysicalPlan,
                      on_device: bool) -> PhysicalPlan:
        conf = self.conf
        if not on_device:
            return ops.CpuSortExec(node.orders,
                                   self._single(self._to_host(child)), conf)
        child = self._to_device(child)
        if node.global_sort and child.num_partitions > 1:
            # distributed global sort: sample-based range exchange, then
            # per-partition out-of-core sort; partition order == global
            # order (GpuRangePartitioner.scala + GpuSortExec.scala)
            child = ops.TpuRangeShuffleExchangeExec(
                child, node.orders, conf.get(rc.SHUFFLE_PARTITIONS), conf)
        return ops.TpuSortExec(node.orders, child, conf)

    def _convert_window(self, node: "L.Window", child: PhysicalPlan,
                        on_device: bool) -> PhysicalPlan:
        conf = self.conf
        if not on_device:
            return ops.CpuWindowExec(
                node.window_exprs, self._single(self._to_host(child)),
                node.schema, conf)
        child = self._to_device(child)
        spec = node.window_exprs[0].children[0].spec
        if child.num_partitions > 1:
            if spec.partitions:
                child = ops.TpuShuffleExchangeExec(
                    child, spec.partitions,
                    conf.get(rc.SHUFFLE_PARTITIONS), conf)
            else:
                child = ops.TpuShuffleExchangeExec(child, None, 1, conf)
        halo = ops.window_halo(node.window_exprs)
        chunk_rows = conf.get(rc.BATCH_SIZE_ROWS)
        if halo is not None and halo > chunk_rows // 2:
            # the batched path peeks at most one following chunk for the
            # suffix halo; frames wider than half a chunk must take the
            # whole-partition path for correctness
            halo = None
        from spark_rapids_tpu.plan.logical import SortOrder

        def chunked_sort_child():
            # out-of-core sort on the partition+order keys emitting
            # bounded chunks (shared by the halo and running paths)
            orders = ([SortOrder(p, True) for p in spec.partitions] +
                      list(spec.orders))
            return ops.TpuSortExec(orders, child, conf,
                                   chunk_rows=chunk_rows)

        if halo is not None and (spec.partitions or spec.orders):
            # bounded-frame batched window, evaluated with halo
            # context (GpuBatchedBoundedWindowExec role)
            return ops.TpuWindowExec(node.window_exprs,
                                     chunked_sort_child(), conf,
                                     presorted=True, halo=halo)
        mode = (ops.window_streaming_mode(node.window_exprs)
                if conf.get(rc.WINDOW_STREAMING) else None)
        if mode == "running" and spec.orders:
            # running frames / ranking: sorted chunks + carried scan
            # state (GpuRunningWindowExec role) — O(chunk) residency
            return ops.TpuWindowExec(node.window_exprs,
                                     chunked_sort_child(), conf,
                                     presorted=True, mode="running")
        if mode == "u2u":
            # whole-partition aggregates: two-pass partial+lookup
            # (GpuUnboundedToUnboundedAggWindowExec role), no sort
            return ops.TpuWindowExec(node.window_exprs, child, conf,
                                     mode="u2u")
        return ops.TpuWindowExec(node.window_exprs, child, conf)

    def _convert_limit(self, node: L.Limit, child: PhysicalPlan,
                       on_device: bool) -> PhysicalPlan:
        conf = self.conf
        if not on_device:
            local = ops.CpuLocalLimitExec(node.n, self._to_host(child), conf)
            if local.num_partitions > 1:
                local = ops.CpuLocalLimitExec(
                    node.n, ops.CpuShuffleExchangeExec(local, None, 1, conf),
                    conf)
            return local
        child = self._to_device(child)
        local = ops.TpuLocalLimitExec(node.n, child, conf)
        if local.num_partitions > 1:
            local = ops.TpuLocalLimitExec(
                node.n, ops.TpuShuffleExchangeExec(local, None, 1, conf),
                conf)
        return local


def plan_query(logical: L.LogicalPlan, conf: rc.RapidsConf
               ) -> Tuple[PhysicalPlan, PlanMeta]:
    phys, meta = TpuOverrides(conf).apply(logical)
    from spark_rapids_tpu.plan.broadcast_reuse import (
        dedup_broadcast_builds,
    )

    dedup_broadcast_builds(phys)
    return phys, meta
