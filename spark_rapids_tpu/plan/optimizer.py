"""Logical optimizations: predicates pushed through joins, scan
column pruning + parquet predicate pushdown (the reference gets these
from Spark's optimizer — PushPredicateThroughJoin, ColumnPruning — and
its own row-group filtering, GpuParquetScan.scala:556; standalone we
run a small rewrite pass before physical planning).
"""

from __future__ import annotations

import copy
from typing import List, Optional, Tuple

from spark_rapids_tpu.expr import (
    BoundReference,
    EqualTo,
    GreaterThan,
    GreaterThanOrEqual,
    LessThan,
    LessThanOrEqual,
    Literal,
)
from spark_rapids_tpu.expr.core import Expression
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.sqltypes import StructType

_CMP_OPS = {EqualTo: "=", LessThan: "<", LessThanOrEqual: "<=",
            GreaterThan: ">", GreaterThanOrEqual: ">="}
_FLIP = {"=": "=", "<": ">", "<=": ">=", ">": "<", ">=": "<="}


def optimize(plan: L.LogicalPlan, notes: Optional[dict] = None
             ) -> L.LogicalPlan:
    """`notes`, where given, counts what the rules did:
    `pushedThroughJoin`, the conjuncts moved below a join."""
    from spark_rapids_tpu.plan.struct_keys import expand_struct_keys

    new_children = [optimize(c, notes) for c in plan.children]
    plan = _with_children(plan, new_children)
    plan = expand_struct_keys(plan)
    plan = _push_through_join(plan, notes)
    plan = _push_filters(plan)
    plan = _prune_scan_columns(plan)
    return plan


def _with_children(plan: L.LogicalPlan, children) -> L.LogicalPlan:
    if all(a is b for a, b in zip(plan.children, children)) and \
            len(plan.children) == len(children):
        return plan
    node = copy.copy(plan)
    node.children = list(children)
    return node


# ------------------------------------------------- predicate pushdown

def _split_conjuncts(e: Expression) -> List[Expression]:
    from spark_rapids_tpu.expr import And

    if isinstance(e, And):
        return (_split_conjuncts(e.children[0]) +
                _split_conjuncts(e.children[1]))
    return [e]


def _filter_tuple(e: Expression, schema: StructType
                  ) -> Optional[Tuple[str, str, object]]:
    """BoundReference <cmp> Literal -> a pyarrow filter tuple. SQL
    comparisons are null-rejecting, matching pyarrow filter semantics,
    so pushdown never changes results."""
    op = _CMP_OPS.get(type(e))
    if op is None:
        return None
    a, b = e.children
    if isinstance(a, BoundReference) and isinstance(b, Literal):
        if b.value is None:
            return None
        return (schema.names[a.ordinal], op, b.value)
    if isinstance(b, BoundReference) and isinstance(a, Literal):
        if a.value is None:
            return None
        return (schema.names[b.ordinal], _FLIP[op], a.value)
    return None


def _conjunction(conjuncts: List[Expression]) -> Optional[Expression]:
    from spark_rapids_tpu.expr import And

    out = None
    for c in conjuncts:
        out = c if out is None else And(out, c)
    return out


def _movable(e: Expression) -> bool:
    """A conjunct may move below a join unless it calls user code,
    whose result for a row may depend on which rows it has seen."""
    if type(e).__name__.endswith("UDF"):
        return False
    return all(_movable(c) for c in e.children)


#: join type -> which inputs a conjunct over that input alone may move
#: to: both for an inner join, the preserved side of an outer one (a
#: filter on the null-extended side is not the filter before the
#: join), the left of a semi, anti or existence join (their output IS
#: the left's rows). A full join preserves both and filters neither.
_PUSH_SIDES = {"inner": (True, True), "cross": (True, True),
               "left": (True, False), "right": (False, True),
               "left_semi": (True, False), "left_anti": (True, False),
               "existence": (True, False), "full": (False, False)}


def _push_through_join(plan: L.LogicalPlan, notes: Optional[dict]
                       ) -> L.LogicalPlan:
    """Filter over Join: each conjunct that reads one side only moves
    below the join, down to that side's relation (Catalyst's
    PushPredicateThroughJoin); the rest stays. The dimensions of a
    star query are then filtered BEFORE they are build sides."""
    if not (isinstance(plan, L.Filter)
            and isinstance(plan.children[0], L.Join)):
        return plan
    from spark_rapids_tpu.exec.joins import remap_refs

    join: L.Join = plan.children[0]
    to_left, to_right = _PUSH_SIDES[join.join_type]
    n_l = len(join.children[0].schema.fields)
    n_r = len(join.children[1].schema.fields)
    left, right, stay = [], [], []
    for conj in _split_conjuncts(plan.condition):
        refs = conj.references()
        if not refs or not _movable(conj):
            stay.append(conj)
        elif to_left and max(refs) < n_l:
            left.append(conj)
        elif to_right and n_l <= min(refs) and max(refs) < n_l + n_r:
            right.append(remap_refs(conj, lambda o: o - n_l))
        else:
            stay.append(conj)
    if not left and not right:
        return plan
    if notes is not None:
        notes["pushedThroughJoin"] = (notes.get("pushedThroughJoin", 0)
                                      + len(left) + len(right))
    sides = []
    for child, moved in zip(join.children, (left, right)):
        if moved:
            # further down where the side is itself a join, and into
            # the scan where it is a parquet file
            child = _push_filters(_push_through_join(
                L.Filter(_conjunction(moved), child), notes))
        sides.append(child)
    out = _with_children(join, sides)
    return L.Filter(_conjunction(stay), out) if stay else out


def _push_filters(plan: L.LogicalPlan) -> L.LogicalPlan:
    if not (isinstance(plan, L.Filter) and
            isinstance(plan.children[0], L.FileScan) and
            plan.children[0].fmt == "parquet"):
        return plan
    scan: L.FileScan = plan.children[0]
    tuples = []
    for conj in _split_conjuncts(plan.condition):
        t = _filter_tuple(conj, scan.schema)
        if t is not None:
            tuples.append(t)
    if not tuples:
        return plan
    new_scan = copy.copy(scan)
    new_scan.pushed_filters = (getattr(scan, "pushed_filters", None) or
                               []) + tuples
    # the Filter stays (pushdown is row-group pruning, not exact)
    return _with_children(plan, [new_scan])


# --------------------------------------------------- column pruning

def _remap(e: Expression, mapping) -> Expression:
    def fn(node):
        if isinstance(node, BoundReference):
            return BoundReference(mapping[node.ordinal], node.dtype,
                                  node.nullable)
        return node

    return e.transform(fn)


def _prune(scan: L.FileScan, needed: List[int]):
    """-> (new_scan, old_ordinal -> new_ordinal) or None if no gain."""
    if scan.fmt == "hivetext":
        # positional headerless format: the parser needs the full file
        # schema (every line carries every field anyway)
        return None
    if len(needed) >= len(scan.schema.fields) or not needed:
        return None
    fields = [scan.schema.fields[i] for i in sorted(needed)]
    new_scan = copy.copy(scan)
    new_scan._schema = StructType(fields)
    mapping = {old: new for new, old in enumerate(sorted(needed))}
    return new_scan, mapping


def _prune_scan_columns(plan: L.LogicalPlan) -> L.LogicalPlan:
    # Project/Aggregate over (optional Filter over) FileScan
    if isinstance(plan, L.Project):
        top_exprs = plan.exprs
    elif isinstance(plan, L.Aggregate):
        top_exprs = plan.grouping + plan.aggregates
    else:
        return plan
    child = plan.children[0]
    filt: Optional[L.Filter] = None
    if isinstance(child, L.Filter) and isinstance(child.children[0],
                                                  L.FileScan):
        filt = child
        scan = child.children[0]
    elif isinstance(child, L.FileScan):
        scan = child
    else:
        return plan
    needed = set()
    for e in top_exprs:
        needed.update(e.references())
    if filt is not None:
        needed.update(filt.condition.references())
    pruned = _prune(scan, sorted(needed))
    if pruned is None:
        return plan
    new_scan, mapping = pruned
    bottom: L.LogicalPlan = new_scan
    if filt is not None:
        bottom = L.Filter(_remap(filt.condition, mapping), new_scan)
    if isinstance(plan, L.Project):
        return L.Project([_remap(e, mapping) for e in plan.exprs],
                         bottom)
    return L.Aggregate([_remap(g, mapping) for g in plan.grouping],
                       [_remap(a, mapping) for a in plan.aggregates],
                       bottom)
