"""DataFrame — the user-facing lazy query surface (pyspark-compatible
subset), building logical plans that TpuOverrides plans onto the device.
"""

from __future__ import annotations

from typing import List, Optional, Union

import pyarrow as pa

from spark_rapids_tpu.api.column import Column
from spark_rapids_tpu.api.functions import UnresolvedColumn
from spark_rapids_tpu.expr import Alias, BoundReference
from spark_rapids_tpu.expr.aggregates import AggregateFunction
from spark_rapids_tpu.expr.core import Expression
from spark_rapids_tpu.plan import logical as L


def _resolve(expr, schema, session=None, qualifiers=None) -> Expression:
    """Replace UnresolvedColumn markers with BoundReferences; attempt
    UDF bytecode compilation once argument types are concrete.
    `qualifiers`: the alias each field came in under (DataFrame.alias),
    or None."""
    if isinstance(expr, UnresolvedColumn):
        i = _field_index(schema, expr.name, qualifiers)
        f = schema.fields[i]
        return BoundReference(i, f.dataType, f.nullable)
    if isinstance(expr, Expression):
        new_children = [_resolve(c, schema, session, qualifiers)
                        for c in expr.children]
        node = expr.with_children(new_children)
        if getattr(node, "_wants_compile", False):
            from spark_rapids_tpu.config import rapids_conf as _rc
            from spark_rapids_tpu.expr import Cast
            from spark_rapids_tpu.udf import UdfCompileError, compile_udf

            # the OWNING session's conf (fall back to the process
            # active one only when no session is threaded through)
            s = session
            if s is None:
                from spark_rapids_tpu.api.session import TpuSparkSession

                s = TpuSparkSession.active()
            if (s is not None and not
                    s.rapids_conf.get(_rc.UDF_COMPILER_ENABLED)):
                node.compile_error = (
                    "udf compiler disabled via "
                    "spark.rapids.sql.udfCompiler.enabled=false")
                node._wants_compile = False
                return node
            try:
                compiled = compile_udf(node.fn, new_children)
                if compiled.dtype != node.dtype:
                    compiled = Cast(compiled, node.dtype)
                return compiled
            except UdfCompileError as e:
                node.compile_error = str(e)
                node._wants_compile = False
        return node
    raise TypeError(f"cannot resolve {expr!r}")


def _stamp_session(expr: Expression, session) -> Expression:
    """Post-resolution session pass: stamp the session timezone on
    tz-aware nodes (GpuTimeZoneDB role — the zone becomes part of every
    jit key) and pin current_date/current_timestamp to one literal per
    query (Spark's QueryExecution does the same)."""
    from spark_rapids_tpu.config import rapids_conf as rc
    from spark_rapids_tpu.expr.cast import Cast
    from spark_rapids_tpu.expr.datetimes import (
        CurrentDate,
        CurrentTimestamp,
        TzAware,
    )

    tz = session.rapids_conf.get(rc.SESSION_TZ) if session else "UTC"

    def fn(node):
        if isinstance(node, (TzAware, Cast, CurrentDate,
                             CurrentTimestamp)):
            node.tz = tz  # node is a fresh copy from transform()
        return node

    return expr.transform(fn)


def _count_nodes(node) -> int:
    return 1 + sum(_count_nodes(c) for c in node.children)


def _count_swapped(node) -> int:
    """Inner joins that build the child written on the LEFT, the one
    with fewer rows (plan/overrides.py `_convert_join`); a right outer
    join run as a left outer one is not among them."""
    return ((getattr(node, "build_side", None) == "left"
             and node.join_type == "inner")
            + sum(_count_swapped(c) for c in node.children))


def _pin_query_time(plan):
    """Replace current_date/current_timestamp markers with ONE literal
    per query (Spark pins both at query start), applied at physical
    planning time."""
    import time

    import numpy as np

    from spark_rapids_tpu.expr.core import Literal
    from spark_rapids_tpu.expr.datetimes import (
        CurrentDate,
        CurrentTimestamp,
    )
    from spark_rapids_tpu.ops import tzdb
    from spark_rapids_tpu.sqltypes.datatypes import (
        date as date_t,
        timestamp as timestamp_t,
    )

    now_us = int(time.time() * 1_000_000)

    def efn(node):
        if isinstance(node, CurrentTimestamp):
            return Literal(now_us, timestamp_t)
        if isinstance(node, CurrentDate):
            local = int(tzdb.utc_to_local_np(
                np.array([now_us], np.int64),
                getattr(node, "tz", "UTC"))[0])
            return Literal(local // 86_400_000_000, date_t)
        return node

    return L.transform_expressions(plan, lambda e: e.transform(efn))


def _field_index(schema, name: str, qualifiers=None) -> int:
    """The field `name` means. `qualifiers` holds, per field, the alias
    it came in under (DataFrame.alias) or None: `alias.field` then
    names the field of that input, and a bare name that two aliased
    inputs both hold is ambiguous, as in Spark. Without aliases the
    first field of that name is meant, as before."""
    lowered = [n.lower() for n in schema.names]
    if qualifiers is not None and any(qualifiers):
        alias, dot, field = name.partition(".")
        if dot and name not in schema.names:
            hits = [i for i, (q, n) in enumerate(zip(qualifiers, lowered))
                    if q == alias and n == field.lower()]
            if len(hits) == 1:
                return hits[0]
            if hits:
                raise ValueError(f"reference {name!r} is ambiguous")
        hits = [i for i, n in enumerate(lowered) if n == name.lower()]
        if len({qualifiers[i] for i in hits}) > 1:
            raise ValueError(
                f"reference {name!r} is ambiguous: it could be "
                + ", ".join(f"{qualifiers[i] or '<no alias>'}.{name}"
                            for i in hits))
    if name in schema.names:
        return schema.names.index(name)
    if name.lower() in lowered:
        return lowered.index(name.lower())
    raise KeyError(f"column {name!r} not in {schema.names}")


def _named(expr: Expression, fallback: str) -> Alias:
    if isinstance(expr, Alias):
        return expr
    return Alias(expr, fallback)


class DataFrame:
    def __init__(self, plan: L.LogicalPlan, session, qualifiers=None):
        self._plan = plan
        self.session = session
        # per field, the alias it came in under (`alias`), kept through
        # joins, filters, sorts and limits; None: no field has one
        self._qualifiers = qualifiers

    # --- schema ---

    @property
    def schema(self):
        return self._plan.schema

    @property
    def columns(self) -> List[str]:
        return self._plan.schema.names

    def __getitem__(self, name: str) -> Column:
        i = _field_index(self.schema, name, self._qualifiers)
        f = self.schema.fields[i]
        ref = BoundReference(i, f.dataType, f.nullable)
        # provenance for join-condition resolution (df1.a == df2.b)
        ref._origin_plan = self._plan
        return Column(ref, f.name if self._qualifiers else name)

    def alias(self, name: str) -> "DataFrame":
        """This frame under a name of its own: `F.col("name.field")`
        then means its field, also after joins (`from date_dim dt`)."""
        return DataFrame(self._plan, self.session,
                         [name] * len(self.schema.fields))

    def _quals(self) -> list:
        return self._qualifiers or [None] * len(self.schema.fields)

    def _out_name(self, c):
        """The output name of column `c`: `alias.field` gives `field`."""
        name = c if isinstance(c, str) else c.name
        if (self._qualifiers and isinstance(name, str) and "." in name
                and name not in self.columns):
            try:
                return self.columns[_field_index(self.schema, name,
                                                 self._qualifiers)]
            except KeyError:
                pass
        return name

    # --- transformations ---

    def _col_expr(self, c) -> Expression:
        if isinstance(c, str):
            return _stamp_session(self[c].expr, self.session)
        if isinstance(c, Column):
            return _stamp_session(
                _resolve(c.expr, self.schema, self.session,
                         self._qualifiers),
                self.session)
        raise TypeError(repr(c))

    def select(self, *cols) -> "DataFrame":
        exprs = []
        for i, c in enumerate(cols):
            if isinstance(c, str) and c == "*":
                for j, f in enumerate(self.schema.fields):
                    exprs.append(Alias(BoundReference(j, f.dataType,
                                                      f.nullable), f.name))
                continue
            name = self._out_name(c)
            e = self._col_expr(c)
            exprs.append(_named(e, name if isinstance(name, str)
                                else f"col{i}"))
        return self._finish_project(exprs)

    def _finish_project(self, exprs: List[Alias]) -> "DataFrame":
        """Emit Project, extracting window expressions into Window nodes
        and generators into Generate nodes first (Spark's
        ExtractWindowExpressions / ExtractGenerator rules)."""
        from spark_rapids_tpu.expr.generators import (
            Explode,
            PosExplode,
            contains_generator,
        )
        from spark_rapids_tpu.expr.windows import (
            WindowExpression,
            contains_window,
        )

        if any(contains_generator(e) for e in exprs):
            if any(contains_window(e) for e in exprs):
                raise ValueError(
                    "explode combined with window expressions in one "
                    "select is not supported; materialize the window "
                    "column with a prior select first")
            gens = [e for e in exprs
                    if isinstance(e.children[0], Explode)]
            others = [e for e in exprs
                      if not isinstance(e.children[0], Explode)]
            if len(gens) != 1 or any(contains_generator(e)
                                     for e in others):
                raise ValueError(
                    "exactly one top-level explode/posexplode per "
                    "select (Spark's one-generator rule)")
            gen = gens[0]
            plan = L.Generate(others, gen, self._plan,
                              position=isinstance(gen.children[0],
                                                  PosExplode))
            return DataFrame(plan, self.session)

        if not any(contains_window(e) for e in exprs):
            return DataFrame(L.Project(exprs, self._plan), self.session)
        plan = self._plan
        n_base = len(plan.schema.fields)
        groups = {}  # sort_key -> [Alias(WindowExpression)]
        for e in exprs:
            base = e.children[0]
            if isinstance(base, WindowExpression):
                groups.setdefault(base.spec.sort_key(), []).append(e)
            elif contains_window(e):
                raise NotImplementedError(
                    "window expressions must be top-level in v1 "
                    "(wrap arithmetic around them in a second select)")
        appended = {}
        ordinal = n_base
        for key, aliases in groups.items():
            plan = L.Window(aliases, plan)
            for a in aliases:
                appended[id(a)] = ordinal
                ordinal += 1
        out = []
        for e in exprs:
            if id(e) in appended:
                out.append(Alias(
                    BoundReference(appended[id(e)], e.dtype, True), e.name))
            else:
                out.append(e)
        return DataFrame(L.Project(out, plan), self.session)

    def withColumn(self, name: str, c: Column) -> "DataFrame":
        exprs = []
        replaced = False
        for j, f in enumerate(self.schema.fields):
            if f.name == name:
                exprs.append(Alias(self._col_expr(c), name))
                replaced = True
            else:
                exprs.append(Alias(BoundReference(j, f.dataType, f.nullable),
                                   f.name))
        if not replaced:
            exprs.append(Alias(self._col_expr(c), name))
        return self._finish_project(exprs)

    def withColumnRenamed(self, old: str, new: str) -> "DataFrame":
        exprs = []
        for j, f in enumerate(self.schema.fields):
            exprs.append(Alias(BoundReference(j, f.dataType, f.nullable),
                               new if f.name == old else f.name))
        return DataFrame(L.Project(exprs, self._plan), self.session)

    def drop(self, *names) -> "DataFrame":
        keep = [f.name for f in self.schema.fields if f.name not in names]
        return self.select(*keep)

    def filter(self, condition) -> "DataFrame":
        from spark_rapids_tpu.expr.windows import contains_window

        if isinstance(condition, str):
            raise NotImplementedError("SQL string filters: use Column")
        cond = self._col_expr(condition)
        if contains_window(cond):
            raise ValueError(
                "window functions are not allowed in filter conditions; "
                "materialize with select/withColumn first (Spark analysis "
                "rule)")
        return DataFrame(L.Filter(cond, self._plan), self.session,
                         self._qualifiers)

    where = filter

    def groupBy(self, *cols) -> "GroupedData":
        return GroupedData(self, list(cols))

    def rollup(self, *cols) -> "GroupedData":
        """GROUP BY ROLLUP — hierarchical subtotal grouping sets
        (lowered through Expand, like Spark's rollup plan)."""
        return GroupedData(self, list(cols), mode="rollup")

    def cube(self, *cols) -> "GroupedData":
        """GROUP BY CUBE — all 2^n grouping-set combinations."""
        return GroupedData(self, list(cols), mode="cube")

    def groupingSets(self, sets, *cols) -> "GroupedData":
        """Explicit grouping sets: `sets` is a list of lists of column
        names drawn from `cols`."""
        return GroupedData(self, list(cols), mode="grouping_sets",
                           sets=sets)

    def agg(self, *cols) -> "DataFrame":
        return GroupedData(self, []).agg(*cols)

    def mapInPandas(self, fn, schema) -> "DataFrame":
        """Iterator-of-pandas-frames transform through the Arrow worker
        pool (GpuMapInPandasExec role). `schema` is a DDL string
        ('a long, b double') or StructType."""
        from spark_rapids_tpu.sqltypes.datatypes import parse_ddl_schema

        return DataFrame(
            L.MapInPandas(fn, parse_ddl_schema(schema), self._plan),
            self.session)

    def sample(self, withReplacement=None, fraction=None,
               seed=None) -> "DataFrame":
        """Bernoulli row sample (pyspark-compatible overloads:
        sample(fraction), sample(fraction, seed),
        sample(withReplacement, fraction, seed))."""
        if isinstance(withReplacement, float):
            # sample(fraction[, seed]) form
            withReplacement, fraction, seed = False, withReplacement, \
                fraction
        if fraction is None:
            raise ValueError("sample() requires a fraction")
        if not 0.0 <= float(fraction) <= 1.0 and not withReplacement:
            raise ValueError(f"fraction must be in [0, 1]: {fraction}")
        if seed is None:
            import random

            seed = random.randint(0, 2 ** 31 - 1)
        return DataFrame(
            L.Sample(float(fraction), int(seed), bool(withReplacement),
                     self._plan),
            self.session)

    def crossJoin(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(
            L.Join(self._plan, other._plan, "cross", [], []), self.session,
            self._joined_qualifiers(other, "cross"))

    def _resolve_combined(self, other: "DataFrame", e) -> Expression:
        """Resolve an expression against [left fields | right fields]:
        UnresolvedColumn binds left-first; BoundReferences originating
        from `other` (df2["x"]) shift into the right half."""
        n_l = len(self.schema.fields)

        def go(node):
            if isinstance(node, UnresolvedColumn):
                try:
                    i = _field_index(self.schema, node.name,
                                     self._qualifiers)
                    f = self.schema.fields[i]
                    return BoundReference(i, f.dataType, f.nullable)
                except KeyError:
                    i = _field_index(other.schema, node.name,
                                     other._qualifiers)
                    f = other.schema.fields[i]
                    return BoundReference(n_l + i, f.dataType, f.nullable)
            if isinstance(node, BoundReference):
                org = getattr(node, "_origin_plan", None)
                if org is other._plan:
                    return BoundReference(node.ordinal + n_l, node.dtype,
                                          node.nullable)
                if org is None or org is self._plan:
                    return node
                raise ValueError(
                    "join condition references a column from a DataFrame "
                    "that is neither side of this join; re-derive it from "
                    "the joined inputs (e.g. use the filtered/projected "
                    "DataFrame's own columns)")
            if isinstance(node, Expression):
                return node.with_children([go(c) for c in node.children])
            raise TypeError(f"cannot resolve {node!r}")

        return _stamp_session(go(e), self.session)

    @staticmethod
    def _promote_keys(lk, rk):
        """Implicit numeric promotion of mismatched key types
        (Spark's ImplicitTypeCasts)."""
        from spark_rapids_tpu.expr import Cast
        from spark_rapids_tpu.sqltypes import NumericType
        from spark_rapids_tpu.sqltypes.datatypes import numeric_promotion

        out_l, out_r = [], []
        for a, b in zip(lk, rk):
            if a.dtype != b.dtype:
                if isinstance(a.dtype, NumericType) and isinstance(
                        b.dtype, NumericType):
                    common = numeric_promotion(a.dtype, b.dtype)
                    a = a if a.dtype == common else Cast(a, common)
                    b = b if b.dtype == common else Cast(b, common)
                else:
                    raise TypeError(
                        f"join key type mismatch: {a.dtype} vs {b.dtype}")
            out_l.append(a)
            out_r.append(b)
        return out_l, out_r

    @staticmethod
    def _split_conjuncts(e: Expression) -> List[Expression]:
        from spark_rapids_tpu.expr import And

        if isinstance(e, And):
            return (DataFrame._split_conjuncts(e.children[0]) +
                    DataFrame._split_conjuncts(e.children[1]))
        return [e]

    def _extract_equi_keys(self, cond: Expression):
        """Spark's ExtractEquiJoinKeys: pull EqualTo conjuncts whose
        sides reference only one input each; remainder stays a
        condition."""
        from spark_rapids_tpu.expr import And, EqualTo

        n_l = len(self.schema.fields)
        lk, rk, rest = [], [], []
        for c in self._split_conjuncts(cond):
            if isinstance(c, EqualTo):
                a, b = c.children
                ra, rb = a.references(), b.references()
                if ra and rb:
                    if max(ra) < n_l <= min(rb):
                        lk.append(a)
                        rk.append(b)
                        continue
                    if max(rb) < n_l <= min(ra):
                        lk.append(b)
                        rk.append(a)
                        continue
            rest.append(c)
        from spark_rapids_tpu.exec.joins import remap_refs

        rk = [remap_refs(k, lambda o: o - n_l) for k in rk]
        remainder = None
        for c in rest:
            remainder = c if remainder is None else And(remainder, c)
        return lk, rk, remainder

    def join(self, other: "DataFrame", on=None, how: str = "inner"
             ) -> "DataFrame":
        how = {"outer": "full", "full_outer": "full", "leftouter": "left",
               "rightouter": "right", "leftsemi": "left_semi",
               "semi": "left_semi", "leftanti": "left_anti",
               "anti": "left_anti"}.get(how, how)
        if on is None:
            if how not in ("inner", "cross"):
                raise ValueError(
                    f"join type {how!r} requires join keys or a condition")
            return self.crossJoin(other)
        if how == "cross":
            # keys given: Spark treats cross-with-keys as an equi join
            how = "inner"
        if isinstance(on, str):
            on = [on]
        if isinstance(on, Column) or isinstance(on, Expression):
            cond = self._resolve_combined(
                other, on.expr if isinstance(on, Column) else on)
            lk, rk, remainder = self._extract_equi_keys(cond)
            lk, rk = self._promote_keys(lk, rk)
            jt = "cross" if not lk and remainder is None else how
            plan = L.Join(self._plan, other._plan, jt, lk, rk,
                          condition=remainder)
            return DataFrame(plan, self.session,
                             self._joined_qualifiers(other, jt))
        if isinstance(on, (list, tuple)) and on and isinstance(on[0], str):
            lk = [self[c].expr for c in on]
            rk = [other[c].expr for c in on]
        else:
            raise TypeError(
                "join `on` must be column name(s) or a Column expression")
        # name-keyed joins rewrite mismatched key columns to the common
        # type in place (the joined output carries the promoted type,
        # matching Spark's ImplicitTypeCasts on USING joins)
        plk, prk = self._promote_keys(lk, rk)
        df_l, df_r = self, other
        if any(p is not o for p, o in zip(plk, lk)):
            for i, (p, o) in enumerate(zip(plk, lk)):
                if p is not o:
                    df_l = df_l.withColumn(on[i], Column(p))
            lk = [df_l[c].expr for c in on]
        if any(p is not o for p, o in zip(prk, rk)):
            for i, (p, o) in enumerate(zip(prk, rk)):
                if p is not o:
                    df_r = df_r.withColumn(on[i], Column(p))
            rk = [df_r[c].expr for c in on]
        plan = L.Join(df_l._plan, df_r._plan, how, lk, rk)
        return DataFrame(plan, self.session,
                         self._joined_qualifiers(other, how))

    def _joined_qualifiers(self, other: "DataFrame", how: str):
        if self._qualifiers is None and other._qualifiers is None:
            return None
        if how in ("left_semi", "left_anti"):
            return self._qualifiers
        if how == "existence":
            return self._quals() + [None]
        return self._quals() + other._quals()

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(L.Union([self._plan, other._plan]), self.session)

    unionAll = union

    def orderBy(self, *cols, ascending=None) -> "DataFrame":
        from spark_rapids_tpu.api.column import SortColumn
        from spark_rapids_tpu.expr.windows import contains_window

        orders = []
        asc_list = (ascending if isinstance(ascending, (list, tuple))
                    else [ascending] * len(cols))
        for c, asc in zip(cols, asc_list):
            if isinstance(c, SortColumn):
                orders.append(L.SortOrder(
                    _stamp_session(
                        _resolve(c.expr, self.schema, self.session,
                                 self._qualifiers),
                        self.session),
                    c.ascending, c.nulls_first))
                continue
            a = True if asc is None else bool(asc)
            orders.append(L.SortOrder(self._col_expr(c), a))
        for o in orders:
            if contains_window(o.expr):
                raise ValueError(
                    "window functions are not allowed in orderBy; "
                    "materialize with select/withColumn first")
        return DataFrame(L.Sort(orders, self._plan, global_sort=True),
                         self.session, self._qualifiers)

    sort = orderBy

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(L.Limit(n, self._plan), self.session,
                         self._qualifiers)

    def distinct(self) -> "DataFrame":
        return self.groupBy(*self.columns).agg()

    def repartition(self, n: int, *cols) -> "DataFrame":
        keys = [self._col_expr(c) for c in cols] or None
        return DataFrame(L.Repartition(self._plan, n, keys), self.session)

    # --- actions ---

    def _physical(self, cpu_oracle: bool = False):
        """What the optimizer's rules did on the way (plan/optimizer.py
        `optimize`) is left in `self._optimizer_notes`."""
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.plan.optimizer import optimize
        from spark_rapids_tpu.plan.overrides import plan_query

        if cpu_oracle:
            # data-shape fallback: all-CPU plan from the ORIGINAL
            # logical tree — substituting device-cached relations here
            # would re-materialize them on device and re-raise the very
            # condition (e.g. StringWidthExceeded) being fallen back
            # from
            plan = _pin_query_time(self._plan)
            conf = rc.RapidsConf({
                **self.session._settings,
                "spark.rapids.tpu.test.cpuOracle": True})
            return plan_query(optimize(plan), conf)
        # serve registered device-cached subtrees from their entries
        # (Spark CacheManager.useCachedData role) BEFORE time pinning:
        # pinning may rebuild nodes, which would break identity matching
        plan = self.session.cache_manager.substitute(self._plan)
        plan = _pin_query_time(plan)
        self._optimizer_notes = {"pushedThroughJoin": 0}
        plan = optimize(plan, self._optimizer_notes)
        # the overrides pass: tagging, conversion, row estimates and
        # the build sides
        with obs_events.span("plan.convert"):
            return plan_query(plan, self.session.rapids_conf)

    # --- caching ---
    #
    # Two tiers, mirroring the reference's split:
    # - host (default): the ParquetCachedBatchSerializer analog — this
    #   DataFrame's RESULT as a compressed parquet blob, returned on
    #   re-collect.
    # - device: the CacheManager/InMemoryRelation analog
    #   (exec/relation_cache.py) — the RELATION as HBM-resident
    #   spillable batches; any DERIVED query serves its scan from HBM
    #   (no decode, no host->device link traffic): the TPU-native tier.

    def cache(self, storage: str = "host") -> "DataFrame":
        if storage == "device":
            self.session.cache_manager.register(
                self._plan, self.session.rapids_conf)
        elif storage == "host":
            self._cached = True
        else:
            raise ValueError(
                f"unknown cache storage {storage!r}: use 'host' "
                "(result blob) or 'device' (HBM-resident relation)")
        return self

    def persist(self, storage="host", *_a, **_k) -> "DataFrame":
        # PySpark callers pass a StorageLevel positionally; anything
        # non-string maps to the host tier.
        if not isinstance(storage, str):
            storage = "host"
        return self.cache(storage)

    def unpersist(self) -> "DataFrame":
        self._cached = False
        self._cache_blob = None
        self.session.cache_manager.unregister(self._plan)
        return self

    def _cache_store(self, table: pa.Table):
        import io as _io

        import pyarrow.parquet as pq

        buf = _io.BytesIO()
        pq.write_table(table, buf, compression="snappy")
        self._cache_blob = buf.getvalue()

    def _cache_load(self) -> Optional[pa.Table]:
        blob = getattr(self, "_cache_blob", None)
        if blob is None:
            return None
        import io as _io

        import pyarrow.parquet as pq

        return pq.read_table(_io.BytesIO(blob))

    def collect_arrow(self) -> pa.Table:
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.runtime import admission, device_monitor
        from spark_rapids_tpu.runtime.errors import (
            DeadlockDetectedError,
            DeviceLostError,
        )

        try:
            return self._collect_arrow_admitted()
        except DeviceLostError:
            # this query was unwound by device-loss fencing
            # (runtime/device_monitor.py): its permits/buffers/slot are
            # released, warm recovery rebuilds the backend and bumps
            # the device epoch, and ONE resubmission through admission
            # re-runs the query against the fresh backend (the
            # retryVictim pattern). Outermost collect only; the wait
            # for the fence to lift is bounded by
            # device.recovery.timeoutMs.
            mon = device_monitor.get()
            if admission.current_handle() is not None or \
                    not mon.resubmit or \
                    not self.session.rapids_conf.get(
                        rc.DEVICE_RECOVERY_RESUBMIT):
                raise
            if not mon.await_ready():
                raise  # recovery itself is wedged — surface the loss
            mon.note_resubmit()
            return self._collect_arrow_admitted()
        except DeadlockDetectedError:
            # this query was unwound as a deadlock victim
            # (runtime/sanitizer.py): every permit/buffer/slot it held
            # is released, so a single resubmission through admission
            # serializes behind the cycle's survivors and completes.
            # Only the OUTERMOST collect retries (a nested collect's
            # error belongs to the outer query's token), and only once
            # — a second cycle means something is systemically wedged
            # and the caller should see it.
            if admission.current_handle() is not None or \
                    not self.session.rapids_conf.get(
                        rc.SANITIZER_VICTIM_RETRY):
                raise
            return self._collect_arrow_admitted()

    def _collect_arrow_admitted(self) -> pa.Table:
        # Engine-selection record (GpuOverrides NOT_ON_GPU diagnostics
        # discipline applied to whole-query engine dispatch): which
        # engine ran, and why each faster engine was skipped. Surfaced
        # via explain() and session.query_metrics — a fused/mesh compile
        # error must never silently land a query on the dispatch-bound
        # eager path.
        import contextlib
        import time as _time

        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.runtime import admission

        rec = {"engine": None, "fallbacks": [], "compile": None,
               "degradations": [], "scheduler": None, "plan": None,
               "join": None, "agg": None, "groups": None, "sort": None}
        self._last_exec = rec
        self.session.last_execution = rec
        # admission front door (runtime/admission.py): the OUTERMOST
        # collect takes a query slot (possibly queueing, possibly shed
        # with QueryRejectedError before any work), owns the query's
        # CancelToken for the whole execution, and releases the slot on
        # exit; nested collects ride the enclosing query's handle
        scope = admission.AdmissionScope(
            self.session, description=type(self._plan).__name__)
        submitted_ns = _time.time_ns()
        with scope as handle:
            admitted_ns = _time.time_ns()
            # the query scope brackets the event stream (query.start /
            # query.end frame the event log + span tree); nested
            # collects fold into the outer query's stream
            qid = obs_events.begin_query(handle.query_id)
            rec["queryId"] = qid
            rec["admission"] = {"queueWaitMs": handle.queue_wait_ms,
                                "priority": handle.priority}
            # the `query` span is the tree's root, from the submission
            # (so the queue wait lies inside it) to the last of the
            # entry's own work; a nested collect's spans hang under
            # whatever span of the enclosing query is open
            root = None if scope.nested else \
                obs_events.span("query", start_ns=submitted_ns)
            try:
                with root or contextlib.nullcontext():
                    if root is not None and handle.queue_wait_ms:
                        obs_events.record_span(
                            "admission", submitted_ns, admitted_ns,
                            metric="queueWaitMs",
                            queueWaitMs=handle.queue_wait_ms)
                    return self._collect_arrow_summarized(rec, qid, root)
            finally:
                obs_events.finish_query(
                    qid, engine=rec["engine"],
                    status="ok" if rec["engine"] is not None
                    else "error",
                    fallbacks=len(rec["fallbacks"]),
                    degradations=len(rec["degradations"]))

    def _collect_arrow_summarized(self, rec, qid: int, root) -> pa.Table:
        """The traced collect, then the query's data-movement report:
        the transfer ledger's per-query view + roofline fractions over
        the measured wall time. The OUTERMOST scope (the one that has
        the `query` span, `root`) owns the summary event (nested
        collects would snapshot the same qid mid-flight); every rec
        still carries the view so callers see bytes for their slice
        too."""
        import time as _time

        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.obs import telemetry as _tel

        t0 = _time.perf_counter()
        out_rows = None
        try:
            out = self._collect_arrow_traced(rec)
            out_rows = out.num_rows
            return out
        finally:
            tel = _tel.query_summary(
                qid, wall_s=_time.perf_counter() - t0,
                output_rows=out_rows)
            rec["telemetry"] = tel or None
            if tel and root is not None:
                _tel.ledger.finalize_query(qid, tel)
                obs_events.emit(
                    "telemetry.summary",
                    bytesMoved=tel.get("bytesMoved"),
                    bytesMovedTotal=tel.get("bytesMovedTotal"),
                    hbmPeakBytes=tel.get("hbmPeakBytes"),
                    rooflineFrac=tel.get("rooflineFrac"),
                    linkFrac=tel.get("linkFrac"),
                    bytesPerOutputRow=tel.get("bytesPerOutputRow"),
                    wallMs=tel.get("wallMs"))
            if root is not None:
                root.set(engine=rec["engine"],
                         status="ok" if rec["engine"] is not None
                         else "error")

    def _collect_arrow_traced(self, rec) -> pa.Table:
        from spark_rapids_tpu.obs import events as obs_events

        def ran(engine: str, out: pa.Table, store: bool = True
                ) -> pa.Table:
            rec["engine"] = engine
            self.session.query_metrics.metric("engine." + engine).add(1)
            if store and getattr(self, "_cached", False):
                self._cache_store(out)
            return out

        def fell_back(engine: str, reason: str) -> None:
            rec["fallbacks"].append((engine, reason))
            self.session.query_metrics.metric(
                "engineFallback." + engine).add(1)

        cached = self._cache_load()
        if cached is not None:
            return ran("hostCache", cached, store=False)

        with obs_events.span("plan") as sp:
            self._optimizer_notes = None  # a prebuilt plan leaves none
            phys, meta = self._physical()
            rec["plan"] = dict(self._optimizer_notes or {},
                               nodes=_count_nodes(phys),
                               buildSidesSwapped=_count_swapped(phys))
            sp.set(**rec["plan"])
        # structured twin of the NOT_ON_TPU explain: one placement
        # event per plan node, with the verbatim fallback reason —
        # what obs.report.qualification() reads
        obs_events.emit_plan_placement(meta)
        if self.session.rapids_conf.is_explain_only:
            return pa.table({})
        from spark_rapids_tpu.runtime import compile_cache as _cc
        from spark_rapids_tpu.runtime.errors import StringWidthExceeded

        from spark_rapids_tpu.runtime import scheduler as _sched

        # Compile observability (the tentpole's watch-forever channel):
        # the process compile ledger is snapshotted around the query and
        # the delta — programs compiled, structural cache hits, compile
        # seconds, jax's disk hits and misses — lands in
        # last_execution["compile"] and the session metrics, with the
        # fused engine's distinct program-variant count folded in when
        # it ran. The stage
        # scheduler's ledger (tasks launched/retried/speculated,
        # recomputed partitions, evicted workers) rides the same
        # snapshot-delta channel into last_execution["scheduler"].
        before = _cc.stats.snapshot()
        sched_before = _sched.stats.snapshot()
        try:
            return self._dispatch_engines(phys, ran, fell_back, rec)
        except StringWidthExceeded as e:
            # DATA-shape fallback: a string column's longest value
            # exceeds the device padded-width ceiling — re-plan on the
            # CPU engine, recorded like any other fallback (the
            # "anything unsupported falls back with a reason" planner
            # invariant extended to data-dependent shapes)
            fell_back("device", str(e))
            phys_cpu, _ = self._physical(cpu_oracle=True)
            return ran("cpu", phys_cpu.collect())
        finally:
            comp = _cc.stats.delta(before, _cc.stats.snapshot())
            comp["variantCount"] = rec.pop("_fused_variants", None)
            rec["compile"] = comp
            qm = self.session.query_metrics
            qm.metric("compile.programsCompiled").add(
                comp["programsCompiled"])
            qm.metric("compile.cacheHits").add(comp["cacheHits"])
            qm.metric("compile.timeMs").add(
                int(comp["compileSeconds"] * 1000))
            sch = _sched.stats.delta(sched_before,
                                     _sched.stats.snapshot())
            rec["scheduler"] = sch
            for key in ("tasksLaunched", "tasksRetried",
                        "tasksSpeculated", "speculativeWins",
                        "recomputedPartitions", "evictedWorkers"):
                if sch.get(key):
                    qm.metric("scheduler." + key).add(sch[key])

    def _dispatch_engines(self, phys, ran, fell_back, rec) -> pa.Table:
        """Engine dispatch with the DEGRADATION LADDER (PR 2):
        mesh/fused compile errors fall back as before (a missing
        lowering is structural), but execution FAILURES — terminal
        OOMs, injected device.dispatch faults — demote down the ladder
        fused -> eager -> CPU, each demotion recorded in
        rec["degradations"] and the degrade.* metrics. A per-program-key
        circuit breaker (runtime/degrade.py) stops re-trying the fused
        engine on a plan that keeps dying there."""
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.runtime import cancellation, degrade, faults
        from spark_rapids_tpu.runtime.errors import TpuOOMError

        conf = self.session.rapids_conf
        ladder_on = conf.get(rc.DEGRADE_ENABLED)
        qm = self.session.query_metrics
        # ladder rungs are yield points: a cancelled/expired query must
        # not start the next (slower) engine
        cancellation.check_current()

        def demoted(frm: str, to: str, reason: str) -> None:
            rec["degradations"].append(
                {"from": frm, "to": to, "reason": reason})
            degrade.record_demotion(f"{frm}To{to.capitalize()}",
                                    frm=frm, to=to, reason=reason)
            qm.metric(f"degrade.{frm}To{to.capitalize()}").add(1)

        from spark_rapids_tpu.runtime import device_monitor as _dm

        mon = _dm.get()
        if mon.enabled and mon.fenced and ladder_on:
            # engine FENCED for device-loss recovery: the device rungs
            # are down, but the service is not — serve this query on
            # the CPU rung (the PR 2 degrade discipline), recorded
            # like any other demotion
            demoted("fused", "cpu",
                    f"device fenced for recovery (epoch {mon.epoch}): "
                    f"serving on the CPU rung")
            phys_cpu, _ = self._physical(cpu_oracle=True)
            return ran("cpu", phys_cpu.collect())

        mesh_n = conf.get(rc.MESH_SIZE)
        if not mesh_n and conf.get(rc.SHUFFLE_MODE) == "ICI":
            # ICI shuffle == the SPMD mesh engine over every local chip
            import jax

            mesh_n = len(jax.devices())
        if mesh_n:
            from spark_rapids_tpu.parallel.plan_compiler import (
                MeshCompileError,
                MeshQueryExecutor,
            )

            try:
                mesh_ex = MeshQueryExecutor.for_devices(mesh_n, conf)
                out = mesh_ex.execute(phys)
                rec["meshDevices"] = mesh_ex.result_devices
                return ran("mesh", out)
            except MeshCompileError as e:
                # operator without a mesh lowering: thread-pool path
                fell_back("mesh", str(e))
        skip_fused = False
        if conf.get(rc.STREAM_ENABLED):
            from spark_rapids_tpu.runtime.errors import DeviceLostError
            from spark_rapids_tpu.stream import (
                StreamCompileError,
                StreamExecutor,
                stream_selected,
            )

            if stream_selected(phys, conf):
                # a scan's working set exceeds the window quota of free
                # HBM: the resident engines would OOM or thrash, so the
                # out-of-core rung runs FIRST for this plan
                try:
                    return ran("stream", StreamExecutor(conf)
                               .execute(phys))
                except StreamCompileError as e:
                    # selected scan has no streamable prefix worth
                    # running: structural, not a failure
                    fell_back("stream", str(e))
                except DeviceLostError:
                    # mid-stream device loss: retired partitions are
                    # lineage-cached; the outermost collect's one-shot
                    # resubmit resumes the stream past them
                    raise
                except (TpuOOMError, faults.InjectedFault) as e:
                    if not ladder_on:
                        raise
                    demoted("stream", "eager",
                            f"{type(e).__name__}: {e}")
                    # this plan was SELECTED because its working set
                    # exceeds HBM — the fused rung would refuse it at
                    # the same gate, so demote straight to eager
                    skip_fused = True
        if conf.get(rc.FUSED_EXEC) and not skip_fused:
            from spark_rapids_tpu.exec.fused import (
                FusedCompileError,
                FusedSingleChipExecutor,
            )

            fkey = degrade.plan_fingerprint(phys)
            breaker = degrade.breaker()
            if conf.get(rc.OOM_INJECTION_MODE) != "none":
                # the forced-OOM harness targets eager allocation
                # points; fused inputs route through the eager path
                # (satellite of the fused.py:453 crash replacement)
                degrade.record_demotion("fusedOomInjectionFallback")
                qm.metric("degrade.fusedOomInjectionFallback").add(1)
                demoted("fused", "eager",
                        "OOM injection targets the eager engine's "
                        "allocation points")
            elif ladder_on and not breaker.allow(fkey):
                degrade.record_demotion("breakerShortCircuit")
                qm.metric("degrade.breakerShortCircuit").add(1)
                demoted("fused", "eager",
                        f"circuit breaker open after "
                        f"{breaker.threshold} consecutive fused "
                        f"failures for this program key")
            else:
                ex = FusedSingleChipExecutor(
                    conf, wide_joins=self.session.fused_wide_joins)
                try:
                    out = ex.execute(phys)
                    if ex.last_compile_metrics is not None:
                        rec["_fused_variants"] = \
                            ex.last_compile_metrics["variantCount"]
                    rec["join"] = ex.last_join_metrics
                    rec["agg"] = ex.last_agg_metrics
                    rec["groups"] = ex.last_group_metrics
                    rec["sort"] = ex.last_sort_metrics
                    breaker.record_success(fkey)
                    return ran("fused", out)
                except FusedCompileError as e:
                    # no fused lowering / too big: per-operator engine
                    # (structural, not a failure — no breaker state)
                    fell_back("fused", str(e))
                except (TpuOOMError, faults.InjectedFault) as e:
                    if not ladder_on:
                        raise
                    n = breaker.record_failure(fkey)
                    demoted("fused", "eager",
                            f"{type(e).__name__}: {e} "
                            f"(failure {n}/{breaker.threshold} for "
                            f"this program key)")
        try:
            cancellation.check_current()
            if conf.get(rc.ADAPTIVE_ENABLED):
                from spark_rapids_tpu.exec.operators import (
                    TpuShuffleExchangeExec,
                )
                from spark_rapids_tpu.plan.aqe import (
                    AdaptiveQueryExecutor,
                )

                def has_exchange(n):
                    return isinstance(n, TpuShuffleExchangeExec) or any(
                        has_exchange(c) for c in n.children)

                if has_exchange(phys):
                    faults.maybe_inject("device.dispatch", detail="aqe")
                    with _dm.guard("eager.dispatch", detail="aqe",
                                   inject=True):
                        return ran("aqe", AdaptiveQueryExecutor(
                            conf).execute(phys))
            faults.maybe_inject("device.dispatch", detail="eager")
            # fatal-classification + chaos site device.fatal around the
            # per-operator engine: a dead backend fences for warm
            # recovery (DeviceLostError rides past the ladder — slow
            # beats dead does not apply to a resubmittable loss)
            with _dm.guard("eager.dispatch", detail="eager",
                           inject=True):
                return ran("eager", phys.collect())
        except (TpuOOMError, faults.InjectedFault) as e:
            if not ladder_on:
                raise
            cancellation.check_current()
            # last rung: the CPU engine (exec/cpu_eval.py lowering via
            # the cpu-oracle plan) — slow beats dead
            demoted("eager", "cpu", f"{type(e).__name__}: {e}")
            phys_cpu, _ = self._physical(cpu_oracle=True)
            return ran("cpu", phys_cpu.collect())

    def collect(self) -> List[tuple]:
        t = self.collect_arrow()
        names = t.column_names
        cols = [t.column(i).to_pylist() for i in range(t.num_columns)]
        return [Row(zip(names, vals)) for vals in zip(*cols)] if cols \
            else []

    def toPandas(self):
        return self.collect_arrow().to_pandas()

    def count(self) -> int:
        from spark_rapids_tpu.api import functions as F

        agg_df = self.agg(F.count("*").alias("count"))
        return agg_df.collect_arrow().column("count").to_pylist()[0]

    def show(self, n: int = 20, truncate: bool = True):
        print(self.limit(n).toPandas().to_string(index=False))

    def explain(self, extended: bool = False):
        phys, meta = self._physical()
        rec0 = getattr(self, "_last_exec", None)
        if rec0 is not None and rec0.get("engine") == "mesh":
            # re-derive the mesh planner's exchange-transport choice on
            # this fresh plan so pretty() shows [strategy=ici]
            from spark_rapids_tpu.parallel.plan_compiler import (
                stamp_exchange_strategies,
            )

            stamp_exchange_strategies(phys, self.session.rapids_conf)
        if rec0 is not None and rec0.get("engine") == "stream":
            # re-derive the streaming selection on this fresh plan so
            # pretty() shows TpuFileScanExec [strategy=stream]
            from spark_rapids_tpu.stream import stamp_stream_strategy

            stamp_stream_strategy(phys, self.session.rapids_conf)
        print("== Physical Plan ==")
        print(phys.pretty())
        if extended:
            print("== Device Placement ==")
            print(meta.explain(only_not_on_device=False))
        rec = getattr(self, "_last_exec", None)
        if rec is not None and rec["engine"] is not None:
            print("== Engine ==")
            print(rec["engine"])
            for eng, reason in rec["fallbacks"]:
                print(f"  fell back from {eng}: {reason}")
            for d in rec.get("degradations", []):
                print(f"  degraded {d['from']} -> {d['to']}: "
                      f"{d['reason']}")
            sch = rec.get("scheduler") or {}
            if sch.get("tasksLaunched"):
                detail = ", ".join(
                    f"{sch[k]} {label}" for k, label in (
                        ("tasksRetried", "retried"),
                        ("tasksSpeculated", "speculated"),
                        ("recomputedPartitions", "recomputed"),
                        ("evictedWorkers", "workers evicted"))
                    if sch.get(k))
                print(f"  scheduler: {sch['tasksLaunched']} task "
                      f"attempts" + (f" ({detail})" if detail else ""))

    def write_parquet(self, path: str):
        self.session.write_parquet(self, path)

    @property
    def write(self) -> "DataFrameWriter":
        return DataFrameWriter(self)


class DataFrameWriter:
    """df.write.format(...).mode(...).partitionBy(...).save(path) — the
    columnar write path (ColumnarOutputWriter / GpuFileFormatDataWriter
    roles, io/writers.py) plus the Delta Lake commit protocol
    (lakehouse/delta.py)."""

    def __init__(self, df: DataFrame):
        self._df = df
        self._format = "parquet"
        self._mode = "error"
        self._partition_by: List[str] = []
        self._options: dict = {}

    def format(self, fmt: str) -> "DataFrameWriter":
        self._format = fmt
        return self

    def mode(self, m: str) -> "DataFrameWriter":
        self._mode = {"errorifexists": "error"}.get(m, m)
        return self

    def option(self, k, v) -> "DataFrameWriter":
        self._options[k] = v
        return self

    def partitionBy(self, *cols) -> "DataFrameWriter":
        self._partition_by = list(cols)
        return self

    def save(self, path: str):
        """Transactional save: the whole write — the reading collect
        included — runs inside ONE query scope, so write.* events and
        the telemetry `write` block attribute to the same queryId the
        read side reported under."""
        from spark_rapids_tpu.obs import events as obs_events

        qid = obs_events.begin_query()
        status = "error"
        try:
            if self._format == "delta":
                from spark_rapids_tpu.lakehouse.delta import write_delta

                # delta.* writer options become table properties
                props = {k: str(v) for k, v in self._options.items()
                         if k.startswith("delta.")}
                write_delta(self._df, path, mode=self._mode,
                            partition_by=self._partition_by,
                            properties=props or None)
                status = "ok"
                return
            out = self._save_committed(path, qid)
            status = "ok"
            return out
        finally:
            obs_events.finish_query(qid, engine=None, status=status,
                                    fallbacks=0, degradations=0)

    def _save_committed(self, path: str, qid: int):
        """File-format save through the two-phase commit protocol
        (io/commit.py): N write tasks stage under the scheduler's
        retry/speculation discipline (first task commit wins), the job
        commit publishes atomically (_SUCCESS last; overwrite = the
        deferred dir swap), and any failure aborts leak-free with
        pre-existing data untouched."""
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.io import commit as iocommit
        from spark_rapids_tpu.io.writers import WriteStats, write_task
        from spark_rapids_tpu.runtime.scheduler import (
            StageScheduler,
            Task,
        )

        session = self._df.session
        conf = getattr(session, "rapids_conf", None)
        committer = iocommit.JobCommitter(
            path, mode=self._mode, fmt=self._format, conf=conf,
            partition_by=self._partition_by or None,
            options=self._options)
        if not committer.setup_job():
            return None  # mode=ignore with existing output
        stats = WriteStats()
        try:
            table = self._df.collect_arrow()
            n = (conf.get(rc.WRITE_TASKS) if conf is not None
                 else rc.WRITE_TASKS.default)
            n = max(1, min(int(n), table.num_rows or 1))
            step = -(-max(table.num_rows, 1) // n)  # ceil division

            def make_run(i: int, piece):
                def run(attempt):
                    adir = committer.attempt_dir(i, attempt)
                    recs: list = []

                    def stage(rel, write_fn, rows):
                        recs.append(iocommit.stage_file(
                            adir, rel, rows, write_fn))

                    write_task(self._format, piece, adir, i,
                               self._partition_by or None, None,
                               options=self._options, stage=stage,
                               file_tag=committer.job_id)
                    return adir, recs

                return run

            tasks = [
                Task(i, run=make_run(i, table.slice(i * step, step)),
                     commit=lambda res, att, i=i:
                         committer.commit_task(i, res, stats),
                     abort=lambda att, i=i:
                         committer.abort_task(i, att),
                     lineage=f"write {self._format} task {i}")
                for i in range(n)]
            StageScheduler(conf, name=f"write-{self._format}",
                           max_parallel=n).run(tasks)
            committer.commit_job()
        except BaseException:
            committer.abort_job(reason="write failed")
            raise
        from spark_rapids_tpu.obs import telemetry as _tel

        _tel.merge_final(qid, {"write": {
            "bytes": stats.num_bytes, "files": stats.num_files,
            "rows": stats.num_rows, "jobs": 1,
            "commitMs": int(committer.commit_ms)}})
        return stats

    def parquet(self, path: str):
        return self.format("parquet").save(path)

    def orc(self, path: str):
        return self.format("orc").save(path)

    def csv(self, path: str):
        return self.format("csv").save(path)

    def json(self, path: str):
        return self.format("json").save(path)

    def avro(self, path: str):
        return self.format("avro").save(path)

    def delta(self, path: str):
        return self.format("delta").save(path)


class Row(dict):
    def __getattr__(self, k):
        try:
            return self[k]
        except KeyError as e:
            raise AttributeError(k) from e

    def __repr__(self):
        return "Row(" + ", ".join(f"{k}={v!r}" for k, v in
                                  self.items()) + ")"


class GroupedData:
    def __init__(self, df: DataFrame, cols, mode: str = "groupby",
                 sets=None):
        from spark_rapids_tpu.expr.windows import contains_window

        self.df = df
        self.mode = mode
        self._user_sets = sets
        self.grouping = [
            _named(df._col_expr(c), df._out_name(c)) for c in cols]
        from spark_rapids_tpu.sqltypes import MapType

        for g in self.grouping:
            if contains_window(g):
                raise ValueError(
                    "window functions are not allowed as grouping keys; "
                    "materialize with select/withColumn first")
            if isinstance(g.dtype, MapType):
                raise ValueError(
                    "expression cannot be used as a grouping expression "
                    "because its data type is a map (Spark "
                    "EXPRESSION_TYPE_IS_NOT_ORDERABLE)")

    def agg(self, *cols) -> DataFrame:
        from spark_rapids_tpu.expr.aggregates import GroupingBit, GroupingID
        from spark_rapids_tpu.expr.windows import contains_window

        entries = []  # (base_expr, name); base is agg fn or marker
        for i, c in enumerate(cols):
            e = self.df._col_expr(c)
            if contains_window(e):
                raise ValueError(
                    "window functions are not allowed in groupBy.agg(); "
                    "use select/withColumn")
            base = e.children[0] if isinstance(e, Alias) else e
            if isinstance(base, (GroupingID, GroupingBit)):
                if self.mode == "groupby":
                    raise ValueError(
                        "grouping()/grouping_id() are only valid with "
                        "rollup/cube/groupingSets")
                if isinstance(e, Alias):
                    name = e.name
                elif isinstance(base, GroupingID):
                    name = "spark_grouping_id()"
                else:
                    name = f"grouping({base.children[0]!r})"
                entries.append((base, name))
                continue
            name = (e.name if isinstance(e, Alias)
                    else f"{base.name}({_input_name(base)})")
            assert isinstance(base, AggregateFunction), \
                f"agg() requires aggregate expressions, got {base!r}"
            entries.append((base, name))
        if self.mode != "groupby":
            return self._expand_agg(entries)
        aggs = [Alias(b, n) for b, n in entries]
        plan = L.Aggregate(self.grouping, aggs, self.df._plan)
        return DataFrame(plan, self.df.session)

    def _grouping_sets(self):
        """Index sets (into self.grouping) included per grouping set."""
        n = len(self.grouping)
        if self.mode == "rollup":
            return [frozenset(range(k)) for k in range(n, -1, -1)]
        if self.mode == "cube":
            from itertools import combinations

            out = []
            for k in range(n, -1, -1):
                out.extend(frozenset(s)
                           for s in combinations(range(n), k))
            return out
        # grouping_sets: user lists of column names
        by_name = {g.name: i for i, g in enumerate(self.grouping)}
        out = []
        for s in self._user_sets:
            try:
                out.append(frozenset(by_name[c] for c in s))
            except KeyError as e:
                raise ValueError(
                    f"grouping set column {e} not in groupingSets "
                    f"columns {sorted(by_name)}")
        return out

    def _expand_agg(self, entries) -> DataFrame:
        """rollup/cube/groupingSets: Expand (one projection per
        grouping set, null-masked keys + grouping-id) -> Aggregate over
        (keys + gid) -> Project dropping the internal gid key. The
        Spark lowering (ExpandExec), device-planned like everything
        else (reference GpuExpandExec.scala)."""
        from spark_rapids_tpu.expr.aggregates import (
            GroupingBit,
            GroupingID,
            Max,
        )
        from spark_rapids_tpu.expr.core import BoundReference, Literal
        from spark_rapids_tpu.expr.mathexpr import BitwiseAnd, ShiftRight
        from spark_rapids_tpu.sqltypes.datatypes import long as long_t

        child = self.df._plan
        cs = child.schema
        ncols = len(cs.fields)
        n = len(self.grouping)
        gid_ord = ncols + n
        sets = self._grouping_sets()
        # duplicate grouping sets must produce duplicate result rows
        # (Spark adds a grouping-set position to disambiguate)
        need_pos = len(set(sets)) < len(sets)
        projections = []
        for pos_i, s in enumerate(sets):
            gid_val = sum(1 << (n - 1 - i) for i in range(n)
                          if i not in s)
            proj = [Alias(BoundReference(j, f.dataType, f.nullable),
                          f.name)
                    for j, f in enumerate(cs.fields)]
            proj += [
                Alias(g.children[0] if i in s
                      else Literal(None, g.dtype), f"__g{i}")
                for i, g in enumerate(self.grouping)]
            proj.append(Alias(Literal(gid_val, long_t),
                              "spark_grouping_id"))
            if need_pos:
                proj.append(Alias(Literal(pos_i, long_t),
                                  "__grouping_pos"))
            projections.append(proj)
        expand = L.Expand(projections, child)
        new_grouping = [
            Alias(BoundReference(ncols + i, g.dtype, True), g.name)
            for i, g in enumerate(self.grouping)]
        new_grouping.append(
            Alias(BoundReference(gid_ord, long_t, False),
                  "spark_grouping_id"))
        if need_pos:
            new_grouping.append(
                Alias(BoundReference(gid_ord + 1, long_t, False),
                      "__grouping_pos"))
        gid_ref = BoundReference(gid_ord, long_t, False)
        agg_aliases = []
        for base, name in entries:
            if isinstance(base, GroupingID):
                agg_aliases.append(Alias(Max(gid_ref), name))
            elif isinstance(base, GroupingBit):
                i = self._grouping_index(base.children[0])
                bit = BitwiseAnd(
                    ShiftRight(gid_ref, Literal(n - 1 - i, long_t)),
                    Literal(1, long_t))
                agg_aliases.append(Alias(Max(bit), name))
            else:
                agg_aliases.append(Alias(base, name))
        agg_plan = L.Aggregate(new_grouping, agg_aliases, expand)
        nkeys = len(new_grouping)
        out = [Alias(BoundReference(i, g.dtype, True), g.name)
               for i, g in enumerate(self.grouping)]
        out += [
            Alias(BoundReference(nkeys + j, a.dtype,
                                 a.children[0].nullable), a.name)
            for j, a in enumerate(agg_aliases)]
        return DataFrame(L.Project(out, agg_plan), self.df.session)

    def _grouping_index(self, expr) -> int:
        key = expr.key()
        for i, g in enumerate(self.grouping):
            if g.children[0].key() == key:
                return i
        raise ValueError(
            f"grouping() argument {expr!r} is not a grouping column")

    def count(self) -> DataFrame:
        from spark_rapids_tpu.api import functions as F

        return self.agg(F.count("*").alias("count"))

    def applyInPandas(self, fn, schema) -> DataFrame:
        """Grouped-map pandas exchange: fn(pandas.DataFrame) ->
        pandas.DataFrame per key group
        (GpuFlatMapGroupsInPandasExec role)."""
        from spark_rapids_tpu.sqltypes.datatypes import parse_ddl_schema

        key_names = [g.name for g in self.grouping]
        if self.mode != "groupby":
            raise ValueError("applyInPandas requires plain groupBy()")
        return DataFrame(
            L.GroupedMapInPandas(key_names, fn,
                                 parse_ddl_schema(schema),
                                 self.df._plan),
            self.df.session)

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        """Pair two grouped frames for cogrouped applyInPandas
        (GpuFlatMapCoGroupsInPandasExec role)."""
        return CoGroupedData(self, other)

    def _simple(self, fn, *cols) -> DataFrame:
        from spark_rapids_tpu.api import functions as F

        return self.agg(*[getattr(F, fn)(c).alias(f"{fn}({c})")
                          for c in cols])

    def sum(self, *cols):
        return self._simple("sum", *cols)

    def avg(self, *cols):
        return self._simple("avg", *cols)

    def min(self, *cols):
        return self._simple("min", *cols)

    def max(self, *cols):
        return self._simple("max", *cols)


def _input_name(fn: AggregateFunction) -> str:
    if not fn.children:
        return "*"
    c = fn.children[0]
    if isinstance(c, BoundReference):
        return f"#{c.ordinal}"
    return repr(c)


class CoGroupedData:
    def __init__(self, left: GroupedData, right: GroupedData):
        if [g.name for g in left.grouping] != \
                [g.name for g in right.grouping]:
            raise ValueError(
                "cogroup requires identical grouping column names")
        self.left = left
        self.right = right

    def applyInPandas(self, fn, schema) -> DataFrame:
        from spark_rapids_tpu.sqltypes.datatypes import parse_ddl_schema

        key_names = [g.name for g in self.left.grouping]
        return DataFrame(
            L.CoGroupedMapInPandas(key_names, fn,
                                   parse_ddl_schema(schema),
                                   self.left.df._plan,
                                   self.right.df._plan),
            self.left.df.session)
