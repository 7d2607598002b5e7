"""Logical optimizations: implicit joins to equi-joins with their
one-sided conjuncts pushed under them, then column pruning, then the
marking of equal aggregates for in-query reuse.

Reference analog: Spark's ``PushPredicateThroughJoin`` and
``ColumnPruning`` rules, which the reference plugin inherits from
Catalyst before ``GpuOverrides`` ever sees the plan — a ``FROM a, b
WHERE a.k = b.k AND a.x = 1`` arrives as an equi-join over a filtered
``a``, and scans read only referenced columns.  This engine owns its
whole stack, so both rules live here.

``rewrite_implicit_joins`` works on the bound plan and changes no
node's output schema, so no ancestor needs a remap.  ``prune_columns``
is a top-down required-ordinal analysis over the bound logical plan,
then a bottom-up rebuild that narrows ``FileScan``/``InMemoryScan``
leaves and remaps every ancestor's ``BoundReference`` ordinals through
the changed schemas.

``mark_equal_aggregates`` runs last, on the pruned plan, and changes
no schema either: it stamps copies (docs/work_sharing.md).

Pruning a scan matters twice on TPU: the device parquet decode skips
whole column chunks (the q6 bench decodes 4 of 6 columns), and
in-memory uploads skip the HBM transfer entirely.
"""

from __future__ import annotations

import copy
from typing import Dict, List, Optional, Sequence, Set, Tuple

from spark_rapids_tpu import dtypes as dt
from spark_rapids_tpu.expr import ir
from spark_rapids_tpu.plan import logical as lp
from spark_rapids_tpu.plan.logical import Schema

# mapping: old output ordinal -> new output ordinal; None = unchanged
_Mapping = Optional[Dict[int, int]]


def _refs(exprs) -> Set[int]:
    out: Set[int] = set()
    for e in exprs:
        if e is None:
            continue
        for b in ir.collect(e, lambda n: isinstance(n, ir.BoundReference)):
            out.add(b.ordinal)
    return out


def _remap_expr(e: ir.Expression, mapping: Dict[int, int]
                ) -> ir.Expression:
    if isinstance(e, ir.BoundReference):
        if e.ordinal not in mapping:
            raise KeyError(f"pruned column referenced: {e.sql()}")
        return ir.BoundReference(mapping[e.ordinal], e.dtype, e.nullable,
                                 e.ref_name)
    if not e.children:
        return e
    new_children = tuple(_remap_expr(c, mapping) for c in e.children)
    if all(n is o for n, o in zip(new_children, e.children)):
        return e
    e2 = copy.copy(e)
    e2.children = new_children
    return e2


def _remap_all(exprs, mapping):
    return [None if e is None else _remap_expr(e, mapping)
            for e in exprs]


def _shallow(node, **attrs):
    n2 = copy.copy(node)
    for k, v in attrs.items():
        setattr(n2, k, v)
    return n2


def _all(node) -> Set[int]:
    return set(range(len(node.schema.names)))


# ---------------------------------------------------------------------------
# implicit joins
# ---------------------------------------------------------------------------

# re-evaluating one of these under a join would see other rows, other
# partitions or another draw than above it
_PUSH_BARRIERS = (ir.MonotonicallyIncreasingID, ir.Rand, ir.PythonUDF,
                  ir.InputFileName, ir.SparkPartitionID,
                  ir.AggregateExpression, ir.WindowExpression)

# sides a conjunct over one side alone may go under: the null-supplying
# side of an outer join keeps its rows until the ON has been applied
_PUSH_LEFT = ("cross", "inner", "left", "semi", "anti")
_PUSH_RIGHT = ("cross", "inner", "right")


def _conjuncts(e: ir.Expression) -> List[ir.Expression]:
    """``a AND b AND c`` as ``[a, b, c]``, in the text's order."""
    if isinstance(e, ir.And):
        return _conjuncts(e.children[0]) + _conjuncts(e.children[1])
    return [e]


def _conjoin(parts: Sequence[ir.Expression]) -> ir.Expression:
    out = parts[0]
    for p in parts[1:]:
        out = ir.And(out, p)
        out.resolve()
    return out


def _key_pair(c: ir.Expression, n_l: int, lschema: Schema,
              rschema: Schema) -> Optional[Tuple[str, str]]:
    """``(left name, right name)`` where the conjunct is an equality of
    one bare column of each side that the join can take as a key."""
    if not isinstance(c, ir.EqualTo):
        return None
    a, b = c.children
    if not (isinstance(a, ir.BoundReference)
            and isinstance(b, ir.BoundReference)):
        return None
    if (a.ordinal < n_l) == (b.ordinal < n_l):
        return None
    if a.ordinal >= n_l:
        a, b = b, a
    lf = lschema.fields[a.ordinal]
    rf = rschema.fields[b.ordinal - n_l]
    # a join key is found by name on its side
    if lschema.names.count(lf.name) != 1 or \
            rschema.names.count(rf.name) != 1:
        return None
    if not _key_dtypes_ok(lf.dtype, rf.dtype):
        return None
    return lf.name, rf.name


def _key_dtypes_ok(ld, rd) -> bool:
    """Whether a join can take an equality of these two as a key:
    float keys are left to the filter (the join normalizes NaN and
    -0.0, ``=`` need not)."""
    for d in (ld, rd):
        if d.is_nested or d.is_floating:
            return False
    return ld == rd or (ld.is_numeric and rd.is_numeric)


def _product_inputs(node: lp.LogicalPlan, out: List[lp.LogicalPlan]
                    ) -> List[lp.LogicalPlan]:
    """The inputs of a tree of bare products (``FROM a, b, c``), in the
    text's order; a join with keys, a condition or a hint is an input,
    not part of the tree."""
    if isinstance(node, lp.Join) and node.how == "cross" and \
            not node.left_keys and node.condition is None and \
            node.hint is None:
        for c in node.children:
            _product_inputs(c, out)
    else:
        out.append(node)
    return out


def _connected_order(n: int, edges: Set[Tuple[int, int]],
                     residuals: List[Set[int]]) -> List[int]:
    """An order of ``n`` relations in which each, where the equalities
    allow it, meets one already placed.  No statistics: the relation
    with the most equality partners comes first (the hub of a star is
    its fact side, and the first input is the one the joins stream);
    then, of the relations an equality ties to those placed, one that
    completes a conjunct over several relations (that join filters, so
    it goes before the ones that only widen), else the first in the
    text.  Relations no equality reaches follow in the text's order:
    their joins stay products."""
    partners = [len({b if a == i else a for a, b in edges if i in (a, b)})
                for i in range(n)]
    order = [max(range(n), key=lambda i: (partners[i], -i))]
    while len(order) < n:
        placed = set(order)
        rest = [i for i in range(n) if i not in placed]
        tied = [i for i in rest
                if any((min(i, j), max(i, j)) in edges for j in placed)]
        filtering = [i for i in tied
                     if any(i in r and r <= placed | {i}
                            for r in residuals)]
        order.append((filtering or tied or rest)[0])
    return order


def _order_products(f: lp.Filter, counts: List[int]
                    ) -> Optional[lp.LogicalPlan]:
    """``Filter`` over a tree of three or more bare products whose
    text order leaves a join without a key though the conjuncts'
    equalities tie its inputs together: the same inputs in a connected
    order, under a projection that puts the columns back where the
    text had them, so no ancestor sees a change.  ``None`` where the
    text's order already has a key at every join (those plans, and
    their programs, stay as they are) or nothing ties the inputs."""
    rels = _product_inputs(f.children[0], [])
    if len(rels) < 3:
        return None
    widths = [len(r.schema.names) for r in rels]
    starts = [sum(widths[:i]) for i in range(len(rels))]
    fields = [fl for r in rels for fl in r.schema.fields]
    rel_of = [i for i, w in enumerate(widths) for _ in range(w)]
    edges: Set[Tuple[int, int]] = set()
    residuals: List[Set[int]] = []
    for c in _conjuncts(f.condition):
        touched = {rel_of[o] for o in _refs([c])}
        if len(touched) < 2:
            continue
        if isinstance(c, ir.EqualTo) and len(touched) == 2 and all(
                isinstance(x, ir.BoundReference) for x in c.children) \
                and _key_dtypes_ok(*(fields[x.ordinal].dtype
                                     for x in c.children)):
            edges.add((min(touched), max(touched)))
        else:
            residuals.append(touched)
    if all(any((j, k) in edges for j in range(k))
           for k in range(1, len(rels))):
        return None
    order = _connected_order(len(rels), edges, residuals)
    if order == list(range(len(rels))):
        return None
    tree = rels[order[0]]
    for i in order[1:]:
        tree = lp.Join(tree, rels[i], [], [], "cross")
    new_starts, at = {}, 0
    for i in order:
        new_starts[i] = at
        at += widths[i]
    moved = {o: new_starts[rel_of[o]] + o - starts[rel_of[o]]
             for o in range(len(fields))}
    counts[2] += sum(1 for k in range(1, len(order))
                     if order[k] != k or set(order[:k]) != set(range(k)))
    back = [ir.BoundReference(moved[o], fl.dtype, fl.nullable, fl.name)
            for o, fl in enumerate(fields)]
    return lp.Project(lp.Filter(tree, _remap_expr(f.condition, moved)),
                      [ir.Alias(b, fl.name) for b, fl in zip(back, fields)])


def _push_through_join(f: lp.Filter, counts: List[int]
                       ) -> Optional[lp.LogicalPlan]:
    """``Filter(Join)`` with every conjunct the join allows moved into
    or under the join; ``None`` where none can move."""
    join = f.children[0]
    left, right = join.children
    n_l = len(left.schema.names)
    semi = join.how in ("semi", "anti")
    to_left, to_right, keys, keep = [], [], [], []
    for c in _conjuncts(f.condition):
        refs = _refs([c])
        if not refs or ir.collect(
                c, lambda n: isinstance(n, _PUSH_BARRIERS)):
            keep.append(c)
        elif semi or all(o < n_l for o in refs):
            (to_left if join.how in _PUSH_LEFT else keep).append(c)
        elif all(o >= n_l for o in refs):
            (to_right if join.how in _PUSH_RIGHT else keep).append(c)
        else:
            pair = _key_pair(c, n_l, left.schema, right.schema) \
                if join.how in ("cross", "inner") else None
            (keys if pair else keep).append(pair or c)
    if not (to_left or to_right or keys):
        return None
    if to_left:
        left = lp.Filter(left, _conjoin(to_left))
    if to_right:
        shift = {o: o - n_l for o in range(n_l, len(join.schema.names))}
        right = lp.Filter(right, _conjoin(_remap_all(to_right, shift)))
    new = _shallow(join, children=(left, right))
    if keys:
        if join.how == "cross" or not join.left_keys:
            counts[0] += 1
        new.how = "inner"
        new.left_keys = join.left_keys + [lk for lk, _ in keys]
        new.right_keys = join.right_keys + [rk for _, rk in keys]
        new.key_dtypes = list(join.key_dtypes)
        for lk, rk in keys:
            ld = left.schema.field(lk).dtype
            rd = right.schema.field(rk).dtype
            new.key_dtypes.append(ld if ld == rd else
                                  dt.promote(ld, rd))
    counts[1] += len(to_left) + len(to_right)
    return lp.Filter(new, _conjoin(keep)) if keep else new


def _rewrite_joins(node: lp.LogicalPlan, counts: List[int]
                   ) -> lp.LogicalPlan:
    if isinstance(node, lp.Filter) and \
            isinstance(node.children[0], lp.Join):
        ordered = _order_products(node, counts)
        if ordered is not None:
            node = ordered
    while isinstance(node, lp.Filter) and \
            isinstance(node.children[0], lp.Join):
        pushed = _push_through_join(node, counts)
        if pushed is None:
            break
        node = pushed
    children = tuple(_rewrite_joins(c, counts) for c in node.children)
    if all(n is o for n, o in zip(children, node.children)):
        return node
    return _shallow(node, children=children)


def rewrite_implicit_joins(plan: lp.LogicalPlan) -> lp.LogicalPlan:
    """The ``WHERE`` of an implicit join becomes the joins' keys.

    A ``Filter`` over a ``Join`` is split into its conjuncts.  An
    equality between a bare column of one side and a bare column of
    the other becomes a key of that join (``cross``, or an ``inner``
    with or without keys, ends as ``inner``); a conjunct over one side
    alone goes under the join on that side, where the same rule meets
    it again if that side is a join, and a scan's own filter push if it
    is a scan; what is left stays above.  An outer join passes a
    conjunct only to its preserved side, a semi or anti join to its
    left.  Joins keep the order the text gives them wherever that
    order has a key at every join; a ``FROM a, b, c`` of three or more
    relations whose text order is no join order (``a`` and ``b`` share
    no equality, both meet ``c``) is put into a connected one first
    (``_order_products``, docs/joins.md).  Counted under
    ``plan.rewrite.implicitJoins`` (joins that went from a product to
    keys), ``plan.rewrite.pushedConjuncts`` (conjuncts moved under
    a join, once a join passed) and ``plan.rewrite.reorderedJoins``
    (joins whose inputs are not the ones the text's order gave
    them)."""
    counts = [0, 0, 0]
    new = _rewrite_joins(plan, counts)
    if any(counts):
        from spark_rapids_tpu.obs import registry as obsreg
        obsreg.get_registry().inc_many(
            ("plan.rewrite.implicitJoins", counts[0]),
            ("plan.rewrite.pushedConjuncts", counts[1]),
            ("plan.rewrite.reorderedJoins", counts[2]))
    return new


# ---------------------------------------------------------------------------
# in-query reuse
# ---------------------------------------------------------------------------

def mark_equal_aggregates(plan: lp.LogicalPlan) -> lp.LogicalPlan:
    """Aggregates of one query that give the same result are computed
    once (docs/work_sharing.md, in-query reuse).

    Equal is ``plan/digest``'s node hash: output names and aliases do
    not count, literals, files and join kinds do.  Runs on the pruned
    plan, where q65's derived table, written once under ``sb`` and once
    as ``sc``, hashes alike; a ``WITH`` name referenced twice arrives
    as one node with two parents and is its own duplicate.  Each
    occurrence of such an aggregate comes back as a copy stamped
    ``_reuse`` with the hash (a private attribute, as ``_incremental``
    is: the digest never sees it); the planner carries the stamp to the
    exec and ``TpuOverrides.apply`` ties equal stamps to one
    computation.  Only ``Aggregate`` roots: the result is whole when it
    yields and small beside its input (two equal scans are
    ``io/scan_share``'s).  Only the outermost: under a later occurrence
    nothing is looked for, since nothing there will run.  Never a
    subtree ``plan_fingerprint`` would not let the result cache serve
    (``rand()`` written twice is two draws), nor an aggregate under
    incremental maintenance.  A plan with no two equal aggregates is
    returned as it came."""
    def aggregates(n: lp.LogicalPlan) -> int:
        return isinstance(n, lp.Aggregate) + \
            sum(aggregates(c) for c in n.children)

    if aggregates(plan) < 2:
        return plan                 # nothing to compare: no hash walk
    from spark_rapids_tpu.plan import digest
    hashes = digest.node_hashes(plan)
    cacheable: dict = {}

    def key_of(n: lp.LogicalPlan) -> Optional[str]:
        if isinstance(n, lp.Aggregate) and \
                getattr(n, "_incremental", None) is None and \
                digest.subtree_cacheable(n, cacheable):
            return hashes[id(n)]
        return None

    # occurrences by hash, in the order the rewrite below meets them; a
    # cached relation's child is planned apart (exec/cache.py)
    met: Dict[str, int] = {}

    def count(n: lp.LogicalPlan) -> None:
        key = key_of(n)
        if key is not None:
            met[key] = met.get(key, 0) + 1
            if met[key] > 1:
                return
        if not isinstance(n, lp.CachedRelation):
            for c in n.children:
                count(c)

    count(plan)
    if all(k < 2 for k in met.values()):
        return plan
    first: Set[str] = set()

    def rewrite(n: lp.LogicalPlan) -> lp.LogicalPlan:
        key = key_of(n)
        if key is not None and met[key] < 2:
            key = None
        if key in first:
            return _shallow(n, _reuse=key)
        if isinstance(n, lp.CachedRelation):
            return n
        children = tuple(rewrite(c) for c in n.children)
        if key is None:
            if all(c is o for c, o in zip(children, n.children)):
                return n
            return _shallow(n, children=children)
        first.add(key)
        return _shallow(n, children=children, _reuse=key)

    return rewrite(plan)


# ---------------------------------------------------------------------------
# column pruning
# ---------------------------------------------------------------------------

def prune_columns(plan: lp.LogicalPlan) -> lp.LogicalPlan:
    """Return an equivalent plan whose scans read only needed columns."""
    try:
        new, mapping = _rewrite(plan, None)
    except KeyError:
        return plan          # a reference the analysis missed: bail out
    # the root's output schema must be unchanged (needed=None = all)
    return plan if mapping is not None else new


def _rewrite(node: lp.LogicalPlan, needed: Optional[Set[int]]
             ) -> Tuple[lp.LogicalPlan, _Mapping]:
    if needed is not None and needed >= _all(node):
        needed = None

    # ---- leaves -----------------------------------------------------------
    if isinstance(node, lp.FileScan):
        if needed is None or node.options.get("columns"):
            return node, None
        keep = sorted(needed)
        if not keep:                       # COUNT(*)-style: keep one
            keep = [0]
        names = [node.schema.names[o] for o in keep]
        # the logical schema must narrow too: ancestors that derive
        # their schema from child.schema (Join, Window) otherwise
        # compute ordinal offsets from the unpruned column list
        new = lp.FileScan(node.fmt, node.paths,
                          Schema([node.schema.field(c) for c in names]),
                          dict(node.options, columns=names))
        return new, {o: i for i, o in enumerate(keep)}
    if isinstance(node, lp.InMemoryScan):
        if needed is None:
            return node, None
        keep = sorted(needed)
        if not keep:
            keep = [0]
        names = [node.schema.names[o] for o in keep]
        new = lp.InMemoryScan(node.table.select(names),
                              node.num_partitions)
        return new, {o: i for i, o in enumerate(keep)}
    if not node.children:
        return node, None

    # ---- single-child nodes ----------------------------------------------
    if isinstance(node, lp.Project):
        # a projection computes only what its parent reads (the
        # projection that puts a reordered join's columns back where
        # the text had them names every column of every input)
        keep = None if needed is None else (sorted(needed) or [0])
        exprs = node.exprs if keep is None \
            else [node.exprs[o] for o in keep]
        child, m = _rewrite(node.children[0], _refs(exprs))
        if m is not None:
            exprs = _remap_all(exprs, m)
        if keep is None:
            if m is None and child is node.children[0]:
                return node, None
            return _shallow(node, children=(child,), exprs=exprs), None
        return _shallow(
            node, children=(child,), exprs=exprs,
            _schema=Schema([node.schema.fields[o] for o in keep])), \
            {o: i for i, o in enumerate(keep)}
    if isinstance(node, lp.Aggregate):
        child, m = _rewrite(node.children[0],
                            _refs(node.groupings) |
                            _refs(node.aggregates))
        if m is None:
            if child is node.children[0]:
                return node, None
            return _shallow(node, children=(child,)), None
        return _shallow(
            node, children=(child,),
            groupings=_remap_all(node.groupings, m),
            aggregates=_remap_all(node.aggregates, m)), None
    if isinstance(node, lp.Filter):
        child_need = None if needed is None else \
            set(needed) | _refs([node.condition])
        child, m = _rewrite(node.children[0], child_need)
        if m is None:
            if child is node.children[0]:
                return node, None
            return _shallow(node, children=(child,)), None
        return _shallow(node, children=(child,),
                        condition=_remap_expr(node.condition, m)), m
    if isinstance(node, lp.Sort):
        child_need = None if needed is None else \
            set(needed) | _refs([o.expr for o in node.orders])
        child, m = _rewrite(node.children[0], child_need)
        if m is None:
            if child is node.children[0]:
                return node, None
            return _shallow(node, children=(child,)), None
        orders = [lp.SortOrder(_remap_expr(o.expr, m), o.ascending,
                               o.nulls_first) for o in node.orders]
        return _shallow(node, children=(child,), orders=orders), m
    if isinstance(node, (lp.Limit, lp.CoalescePartitions)):
        child, m = _rewrite(node.children[0], needed)
        if m is None:
            if child is node.children[0]:
                return node, None
            return _shallow(node, children=(child,)), None
        return _shallow(node, children=(child,)), m
    if isinstance(node, lp.Repartition):
        child_need = None if needed is None else (
            set(needed) | _refs(node.exprs)
            | _refs([o.expr for o in node.orders]))
        child, m = _rewrite(node.children[0], child_need)
        if m is None:
            if child is node.children[0]:
                return node, None
            return _shallow(node, children=(child,)), None
        orders = [lp.SortOrder(_remap_expr(o.expr, m), o.ascending,
                               o.nulls_first) for o in node.orders]
        return _shallow(node, children=(child,),
                        exprs=_remap_all(node.exprs, m),
                        orders=orders), m
    if isinstance(node, lp.Window):
        n_child = len(node.children[0].schema.names)
        if needed is None:
            child_need = None
        else:
            child_need = {o for o in needed if o < n_child} | \
                _refs(node.window_exprs)
        child, m = _rewrite(node.children[0], child_need)
        if m is None:
            if child is node.children[0]:
                return node, None
            return _shallow(node, children=(child,)), None
        wexprs = _remap_all(node.window_exprs, m)
        new_fields = list(child.schema.fields) + \
            [lp.Field(n, e.dtype, e.nullable)
             for n, e in zip(node.out_names, wexprs)]
        out_map = {o: m[o] for o in sorted(m)}
        n_new_child = len(child.schema.names)
        for i, _ in enumerate(node.out_names):
            out_map[n_child + i] = n_new_child + i
        return _shallow(node, children=(child,), window_exprs=wexprs,
                        _schema=Schema(new_fields)), out_map

    # ---- multi-child nodes ------------------------------------------------
    if isinstance(node, lp.Union):
        if needed is None:
            outs = [_rewrite(c, None) for c in node.children]
            # needed=None passes through, so no branch can narrow its
            # OUTPUT (mapping None) — but a branch may still have pruned
            # scans deeper down (e.g. below its own Project)
            assert all(m is None for _, m in outs)
            if all(c is o for (c, _), o in zip(outs, node.children)):
                return node, None
            return _shallow(node,
                            children=tuple(c for c, _ in outs)), None
        # positional schemas: same ordinals for every branch; narrow the
        # union output only when every branch narrows identically —
        # otherwise keep each branch's internal pruning but present the
        # full output (re-rewrite with needed=None)
        outs = [_rewrite(c, set(needed)) for c in node.children]
        maps = [m for _, m in outs]
        if all(m is None for m in maps):
            if all(c is o for (c, _), o in zip(outs, node.children)):
                return node, None
            return _shallow(node,
                            children=tuple(c for c, _ in outs)), None
        if any(m is None for m in maps) or len({tuple(sorted(m.items()))
                                                for m in maps}) != 1:
            outs = [_rewrite(c, None) for c in node.children]
            if all(c is o for (c, _), o in zip(outs, node.children)):
                return node, None
            return _shallow(node,
                            children=tuple(c for c, _ in outs)), None
        return _shallow(node, children=tuple(c for c, _ in outs)), \
            maps[0]
    if isinstance(node, lp.Join):
        lnames = node.children[0].schema.names
        rnames = node.children[1].schema.names
        n_l = len(lnames)
        semi = node.how in ("semi", "anti")
        if needed is None:
            l_need: Optional[Set[int]] = None
            r_need: Optional[Set[int]] = None
        else:
            l_need = {o for o in needed if o < n_l}
            r_need = set() if semi else \
                {o - n_l for o in needed if o >= n_l}
        cond_refs = _refs([node.condition])
        if l_need is not None:
            l_need |= {lnames.index(k) for k in node.left_keys}
            l_need |= {o for o in cond_refs if o < n_l}
        if r_need is not None:
            r_need |= {rnames.index(k) for k in node.right_keys}
            r_need |= {o - n_l for o in cond_refs if o >= n_l}
        lc, lm = _rewrite(node.children[0], l_need)
        rc, rm = _rewrite(node.children[1], r_need)
        if lm is None and rm is None:
            if lc is node.children[0] and rc is node.children[1]:
                return node, None
            return _shallow(node, children=(lc, rc)), None
        lm = lm if lm is not None else {i: i for i in range(n_l)}
        n_l_new = len(lc.schema.names)
        rm = rm if rm is not None else {i: i for i in range(len(rnames))}
        # rebuild through the constructor: it rederives the output
        # schema, key dtypes, and binds the (unbound-equivalent)
        # condition — remap the old condition to the new joined space
        joined_map = dict(lm)
        for o, n in rm.items():
            joined_map[n_l + o] = n_l_new + n
        cond = None if node.condition is None else \
            _remap_expr(node.condition, joined_map)
        new = copy.copy(node)
        new.children = (lc, rc)
        new.condition = cond
        lf, rf = lc.schema.fields, rc.schema.fields
        if semi:
            new._schema = Schema(list(lf))
        else:
            nullable_l = node.how in ("right", "full")
            nullable_r = node.how in ("left", "full")
            new._schema = Schema(
                [lp.Field(f.name, f.dtype, f.nullable or nullable_l)
                 for f in lf] +
                [lp.Field(f.name, f.dtype, f.nullable or nullable_r)
                 for f in rf])
        if semi:
            return new, (None if lm == {i: i for i in range(n_l)}
                         else lm)
        return new, (None if joined_map ==
                     {i: i for i in range(len(node.schema.names))}
                     else joined_map)

    # unhandled node kinds (Generate, Expand, pandas nodes, caches, …):
    # require everything below, never narrow through
    new_children = []
    changed = False
    for c in node.children:
        nc, m = _rewrite(c, None)
        changed = changed or nc is not c
        assert m is None
        new_children.append(nc)
    if not changed:
        return node, None
    return _shallow(node, children=tuple(new_children)), None
