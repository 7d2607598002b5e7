"""Physical planner: logical plan -> CPU physical plan.

Plays the role Spark's query planner plays above the reference plugin: it
produces the "stock" CPU physical plan that TpuOverrides then rewrites
(reference call stack: SURVEY.md §3.1).

Planning pipeline (session ``_plan_physical``): ``prune_columns``
and ``mark_equal_aggregates`` (plan/optimizer.py) -> ``plan_cpu``
(here) -> ``TpuOverrides.apply``
(plan/overrides.py), which converts to Tpu execs and then runs the
whole-stage fusion pass (plan/fusion.py) — Project/Filter chains
collapse into single-dispatch ``TpuFusedStageExec`` nodes and
aggregate prologues inline into the update kernel.  Fusion must see
the CONVERTED plan (it fuses Tpu execs, not the CPU nodes built
here), which is why it lives behind the overrides rather than in this
module.
"""

from __future__ import annotations

from spark_rapids_tpu.config import RapidsTpuConf
from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.exec import cpu as cpux
from spark_rapids_tpu.exec.base import PhysicalPlan
from spark_rapids_tpu.plan import logical as lp


def plan_cpu(node: lp.LogicalPlan, conf: RapidsTpuConf) -> PhysicalPlan:
    if isinstance(node, lp.InMemoryScan):
        return cpux.CpuScanExec(node.table, node.num_partitions,
                                conf.get(cfg.MAX_READER_BATCH_SIZE_ROWS))
    if isinstance(node, lp.FileScan):
        from spark_rapids_tpu.io.readers import CpuFileScanExec
        return CpuFileScanExec(node, conf)
    if isinstance(node, lp.CachedRelation):
        from spark_rapids_tpu.exec.cache import CpuInMemoryTableScanExec
        return CpuInMemoryTableScanExec(node, conf)
    if isinstance(node, lp.Project):
        child = plan_cpu(node.children[0], conf)
        return _plan_project(node, child, conf)
    if isinstance(node, lp.Filter):
        child = plan_cpu(node.children[0], conf)
        return _plan_filter(node, child, conf)
    if isinstance(node, lp.Sort):
        child = plan_cpu(node.children[0], conf)
        return _plan_sort(node, child, conf)
    if isinstance(node, lp.Aggregate):
        child = plan_cpu(node.children[0], conf)
        from spark_rapids_tpu.expr import ir
        aggs = []
        for a in node.aggregates:
            inner = a.children[0] if isinstance(a, ir.Alias) else a
            if not isinstance(inner, ir.AggregateExpression):
                raise NotImplementedError(
                    "aggregate expressions must be plain aggregate "
                    "functions (optionally aliased) for now")
            aggs.append(inner)
        # pandas UDFs in grouping keys / aggregate args evaluate in an
        # ArrowEvalPython stage below the aggregate
        flat = list(node.groupings) + \
            [a.children[0] for a in aggs if a.children]
        new_flat, child = _extract_pandas_udfs(flat, child)
        groupings = new_flat[:len(node.groupings)]
        k = len(node.groupings)
        for a in aggs:
            if a.children:
                a.children = (new_flat[k],)
                k += 1
        # distributed plan shape: hash-exchange on the grouping keys, then
        # a per-partition (complete) aggregate — Spark's partial/final
        # split restructured so the exchange is a planner-visible node the
        # ICI data plane can ride (reference: aggregate.scala partial/
        # final stage pair around GpuShuffleExchangeExec)
        two_stage = bool(groupings) and (
            conf.get(cfg.AGG_EXCHANGE)
            or str(conf.get(cfg.SHUFFLE_TRANSPORT)) in ("ici", "ici_ring",
                                                        "process"))
        if two_stage and all(g.dtype is not None and not g.dtype.is_nested
                             for g in groupings):
            from spark_rapids_tpu.shuffle import exchange as ex
            child = ex.CpuShuffleExchangeExec(
                child, ex.HashPartitioning(conf.shuffle_partitions,
                                           list(groupings)))
            agg_exec = cpux.CpuHashAggregateExec(child, groupings, aggs,
                                                 node.schema,
                                                 per_partition=True)
        else:
            agg_exec = cpux.CpuHashAggregateExec(child, groupings, aggs,
                                                 node.schema)
            # incremental-maintenance stamp (exec/incremental.py): ride
            # the logical node's partial-capture/retained-state hooks
            # through to the physical aggregate; a private attr so the
            # plan digest and expression enumeration never see it
            inc = getattr(node, "_incremental", None)
            if inc is not None:
                agg_exec._incremental = inc
        # in-query reuse stamp (optimizer.mark_equal_aggregates): equal
        # stamps are tied to one computation in TpuOverrides.apply
        reuse = getattr(node, "_reuse", None)
        if reuse is not None:
            agg_exec._reuse = reuse
        return agg_exec
    if isinstance(node, lp.Limit):
        child = plan_cpu(node.children[0], conf)
        return cpux.CpuLimitExec(child, node.n)
    if isinstance(node, lp.Union):
        return cpux.CpuUnionExec([plan_cpu(c, conf) for c in node.children])
    if isinstance(node, lp.Join):
        return _plan_join(node, conf)
    if isinstance(node, lp.Repartition):
        from spark_rapids_tpu.shuffle import exchange as ex
        child = plan_cpu(node.children[0], conf)
        n = node.num_partitions
        if node.kind == "hash":
            part = ex.HashPartitioning(n, node.exprs)
        elif node.kind == "range":
            part = ex.RangePartitioning(n, node.orders)
        elif node.kind == "single":
            part = ex.SinglePartitioning(n)
        else:
            part = ex.RoundRobinPartitioning(n)
        return ex.CpuShuffleExchangeExec(child, part)
    if isinstance(node, lp.CoalescePartitions):
        from spark_rapids_tpu.shuffle.exchange import \
            CpuCoalescePartitionsExec
        child = plan_cpu(node.children[0], conf)
        return CpuCoalescePartitionsExec(child, node.num_partitions)
    if isinstance(node, lp.Range):
        return cpux.CpuRangeExec(node.start, node.end, node.step,
                                 node.num_partitions)
    if isinstance(node, lp.Expand):
        child = plan_cpu(node.children[0], conf)
        return cpux.CpuExpandExec(child, node.projections, node.schema)
    if isinstance(node, lp.Generate):
        from spark_rapids_tpu.exec.generate import CpuGenerateExec
        child = plan_cpu(node.children[0], conf)
        return CpuGenerateExec(child, node.generator, node.schema)
    if isinstance(node, lp.Window):
        from spark_rapids_tpu.exec.cpu_window import CpuWindowExec
        child = plan_cpu(node.children[0], conf)
        # distributed plan shape: when every window spec shares the same
        # non-empty PARTITION BY, hash-exchange on those keys and run
        # the window per partition (Spark's ClusteredDistribution
        # requirement under GpuWindowExec, restructured so the exchange
        # is a planner-visible node the ICI plane can ride)
        dist = conf.get(cfg.WINDOW_EXCHANGE) or \
            str(conf.get(cfg.SHUFFLE_TRANSPORT)) in ("ici", "ici_ring")
        if dist and node.window_exprs:
            psigs = {tuple(e.sql() for e in we.partition_exprs)
                     for we in node.window_exprs}
            pk = list(node.window_exprs[0].partition_exprs)
            if len(psigs) == 1 and pk and \
                    all(e.dtype is not None and not e.dtype.is_nested
                        for e in pk):
                from spark_rapids_tpu.shuffle import exchange as ex
                child = ex.CpuShuffleExchangeExec(
                    child, ex.HashPartitioning(conf.shuffle_partitions,
                                               pk))
                return CpuWindowExec(child, node.window_exprs,
                                     node.out_names, node.schema,
                                     partitionwise=True)
        return CpuWindowExec(child, node.window_exprs, node.out_names,
                             node.schema)
    if isinstance(node, lp.MapInPandas):
        from spark_rapids_tpu.pyworker.execs import CpuMapInPandasExec
        child = plan_cpu(node.children[0], conf)
        return CpuMapInPandasExec(child, node.fn, node.schema)
    if isinstance(node, lp.FlatMapGroupsInPandas):
        from spark_rapids_tpu.pyworker.execs import \
            CpuFlatMapGroupsInPandasExec
        child = plan_cpu(node.children[0], conf)
        return CpuFlatMapGroupsInPandasExec(child, node.keys, node.fn,
                                            node.schema)
    if isinstance(node, lp.CoGroupedMapInPandas):
        from spark_rapids_tpu.pyworker.execs import \
            CpuFlatMapCoGroupsInPandasExec
        return CpuFlatMapCoGroupsInPandasExec(
            plan_cpu(node.children[0], conf),
            plan_cpu(node.children[1], conf),
            node.left_keys, node.right_keys, node.fn, node.schema)
    if isinstance(node, lp.AggregateInPandas):
        from spark_rapids_tpu.pyworker.execs import CpuAggregateInPandasExec
        child = plan_cpu(node.children[0], conf)
        return CpuAggregateInPandasExec(child, node.keys, node.fn,
                                        node.args, node.out_field)
    if isinstance(node, lp.WindowInPandas):
        from spark_rapids_tpu.pyworker.execs import CpuWindowInPandasExec
        child = plan_cpu(node.children[0], conf)
        return CpuWindowInPandasExec(child, node.part_keys, node.fn,
                                     node.args, node.out_field)
    raise NotImplementedError(f"planner: {type(node).__name__}")


def _is_pandas_udf(x) -> bool:
    from spark_rapids_tpu.expr import ir
    return isinstance(x, ir.PythonUDF) and getattr(x, "vectorized", False)


def _extract_pandas_udfs(exprs, child: PhysicalPlan):
    """ExtractPythonUDFs-rule analog: peel vectorized PythonUDFs out of
    ``exprs`` into ArrowEvalPython execs below, innermost-first in waves
    (so chained pandas UDFs each get their own eval stage, like Spark's
    batched extraction above GpuArrowEvalPythonExec).

    Returns (rewritten_exprs, new_child).
    """
    from spark_rapids_tpu.expr import ir
    from spark_rapids_tpu.pyworker.execs import CpuArrowEvalPythonExec

    counter = [0]
    while True:
        # innermost wave = vectorized UDFs with no vectorized descendant
        wave: list = []

        def visit(x):
            has_nested = False
            for c in x.children:
                has_nested |= visit(c)
            me = _is_pandas_udf(x)
            if me and not has_nested and not any(y is x for y in wave):
                wave.append(x)
            return me or has_nested

        found_any = False
        for e in exprs:
            found_any |= visit(e)
        if not found_any:
            return exprs, child
        base_n = len(child.schema)
        names = []
        for _u in wave:
            names.append(f"_pandas_udf_{counter[0]}")
            counter[0] += 1
        child = CpuArrowEvalPythonExec(child, list(zip(names, wave)))

        def replace(x):
            for i, u in enumerate(wave):
                if x is u:
                    return ir.BoundReference(base_n + i, u.return_type,
                                             True, name_=names[i])
            return None

        exprs = [ir.transform(e, replace) for e in exprs]


def _plan_project(node: lp.Project, child: PhysicalPlan,
                  conf: RapidsTpuConf) -> PhysicalPlan:
    """Extract vectorized (pandas) PythonUDFs out of projections into
    ArrowEvalPython execs below the project."""
    exprs, child = _extract_pandas_udfs(node.exprs, child)
    return cpux.CpuProjectExec(child, exprs, node.schema)


def _plan_filter(node: lp.Filter, child: PhysicalPlan,
                 conf: RapidsTpuConf) -> PhysicalPlan:
    """Filter conditions may contain pandas UDFs too: extract them below
    the filter, then drop the eval columns with a project so the output
    schema is unchanged."""
    from spark_rapids_tpu.expr import ir
    (cond,), eval_child = _extract_pandas_udfs([node.condition], child)
    if eval_child is child:
        return cpux.CpuFilterExec(child, node.condition)
    filt = cpux.CpuFilterExec(eval_child, cond)
    keep = [ir.BoundReference(i, f.dtype, f.nullable, name_=f.name)
            for i, f in enumerate(child.schema.fields)]
    return cpux.CpuProjectExec(filt, keep, child.schema)


def _plan_sort(node: lp.Sort, child: PhysicalPlan,
               conf: RapidsTpuConf) -> PhysicalPlan:
    """Sort keys may contain pandas UDFs: evaluate them below the sort,
    then project the eval columns away."""
    from spark_rapids_tpu.expr import ir
    exprs = [o.expr for o in node.orders]
    new_exprs, eval_child = _extract_pandas_udfs(exprs, child)
    if eval_child is child:
        # distributed plan shape: a RANGE exchange on the sort keys,
        # then per-partition sorts — partition p holds range-bucket p,
        # so partition-ordered concatenation IS the total order and the
        # exchange can ride the ICI plane (reference:
        # GpuRangePartitioning + per-shard GpuSortExec)
        dist = bool(node.orders) and (
            conf.get(cfg.SORT_EXCHANGE)
            or str(conf.get(cfg.SHUFFLE_TRANSPORT)) in ("ici",
                                                        "ici_ring"))
        if dist and all(
                o.expr.dtype is not None and not o.expr.dtype.is_nested
                for o in node.orders):
            from spark_rapids_tpu.shuffle import exchange as ex
            exch = ex.CpuShuffleExchangeExec(
                child, ex.RangePartitioning(conf.shuffle_partitions,
                                            node.orders))
            return cpux.CpuSortExec(exch, node.orders,
                                    partitionwise=True)
        return cpux.CpuSortExec(child, node.orders)
    orders = [lp.SortOrder(e, o.ascending, o.nulls_first)
              for e, o in zip(new_exprs, node.orders)]
    srt = cpux.CpuSortExec(eval_child, orders)
    keep = [ir.BoundReference(i, f.dtype, f.nullable, name_=f.name)
            for i, f in enumerate(child.schema.fields)]
    return cpux.CpuProjectExec(srt, keep, child.schema)


def _plan_join(node, conf: RapidsTpuConf):
    """Join strategy selection (the role Spark's JoinSelection strategy +
    EnsureRequirements play above the reference plugin).

    broadcast-hash when a side is hinted or estimated under
    spark.rapids.tpu.sql.autoBroadcastJoinThreshold (Spark build-side
    validity rules), else shuffled-hash with a hash exchange inserted on
    both sides; cross joins become broadcast-nested-loop (small side) or
    a partitionwise cartesian product.
    """
    from spark_rapids_tpu.shuffle import exchange as ex
    from spark_rapids_tpu.expr import ir

    left = plan_cpu(node.children[0], conf)
    right = plan_cpu(node.children[1], conf)
    threshold = conf.get(cfg.AUTO_BROADCAST_THRESHOLD)
    lsize = lp.size_estimate(node.children[0])
    rsize = lp.size_estimate(node.children[1])
    args = (node.left_keys, node.right_keys, node.how, node.condition,
            node.schema, node.key_dtypes)

    if node.how == "cross" or not node.left_keys:
        small = min(lsize, rsize)
        if node.hint == "broadcast_left" or (
                node.hint is None and small <= threshold and lsize <= rsize):
            return cpux.CpuBroadcastNestedLoopJoinExec(
                left, right, *args, build_side="left")
        if node.hint == "broadcast_right" or (
                node.hint is None and small <= threshold):
            return cpux.CpuBroadcastNestedLoopJoinExec(
                left, right, *args, build_side="right")
        return cpux.CpuCartesianProductExec(left, right, *args)

    # Spark build-side validity: inner/cross either; left/semi/anti build
    # right only; right outer build left only; full outer no broadcast
    can_build_right = node.how in ("inner", "left", "semi", "anti")
    can_build_left = node.how in ("inner", "right")
    build = None
    if node.hint == "broadcast_right" and can_build_right:
        build = "right"
    elif node.hint == "broadcast_left" and can_build_left:
        build = "left"
    elif can_build_right and rsize <= threshold and \
            (not can_build_left or rsize <= lsize):
        build = "right"
    elif can_build_left and lsize <= threshold:
        build = "left"
    if build is not None:
        return cpux.CpuBroadcastHashJoinExec(left, right, *args,
                                             build_side=build)

    n = conf.shuffle_partitions

    def bound_keys(side_plan, names):
        s = side_plan.schema
        out = []
        for k, kd in zip(names, node.key_dtypes):
            e = ir.bind(ir.UnresolvedAttribute(k), s.names, s.dtypes,
                        s.nullables)
            if e.dtype != kd:
                # both sides must hash the promoted key type identically
                e = ir.Cast(e, kd)
                e.resolve()
            out.append(e)
        return out

    lex = ex.CpuShuffleExchangeExec(
        left, ex.HashPartitioning(n, bound_keys(node.children[0],
                                                node.left_keys)))
    rex = ex.CpuShuffleExchangeExec(
        right, ex.HashPartitioning(n, bound_keys(node.children[1],
                                                 node.right_keys)))
    return cpux.CpuShuffledHashJoinExec(lex, rex, *args)
