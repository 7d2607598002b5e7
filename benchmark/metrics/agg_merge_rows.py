"""``agg_merge_rows`` (layer: operators: aggregate): the rows the
aggregates' merges took in inside the window (``agg.merge.rowsIn``: the
groups the buffered partials held together when they were merged), over
the queries completed.  Partial aggregation that removes little leaves
this near the rows that reached the aggregates.  Nothing where the
program has no such counter."""


def read(run):
    from spark_rapids_tpu.obs import registry
    total = registry.get_registry().snapshot()["counters"]
    n = len(run["completed"])
    if not n or "agg.merge.rowsIn" not in total:
        return None
    return run["counters"].get("agg.merge.rowsIn", 0) / n
