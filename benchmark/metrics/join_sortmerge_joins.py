"""``join_sortmerge_joins`` (layer: operators: join): joins of build
and stream batch that took the sort-merge path inside the window
(``join.path.sortMerge``), over the queries completed.  0 is the
expected reading for integer keys whose range fits the direct table.
Nothing where the program counts neither path (it has no such
counter)."""


def read(run):
    from spark_rapids_tpu.obs import registry
    total = registry.get_registry().snapshot()["counters"]
    n = len(run["completed"])
    if not n or not any(name.startswith("join.path.") for name in total):
        return None
    return run["counters"].get("join.path.sortMerge", 0) / n
