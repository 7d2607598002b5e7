"""``agg_sorted_updates`` (layer: operators: aggregate): grouped
aggregate updates at the capacity ladder's scale that reduced in sorted
row space (``agg.update.sorted``) inside the window, over the queries
completed.  0 is the expected reading where every batch holds few
groups: those updates take the dense form (``agg.update.dense``) and
move no value vector into key order.  Nothing where the program counts
neither form (it has no such counter)."""


def read(run):
    from spark_rapids_tpu.obs import registry
    total = registry.get_registry().snapshot()["counters"]
    n = len(run["completed"])
    if not n or not any(name.startswith("agg.update.") for name in total):
        return None
    return run["counters"].get("agg.update.sorted", 0) / n
