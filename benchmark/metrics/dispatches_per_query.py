"""``dispatches_per_query`` (layer: operators): ``kernel.dispatches``
inside the window over the queries completed; an exact count with one
client."""


def read(run):
    n = len(run["completed"])
    return run["counters"].get("kernel.dispatches", 0) / n if n else None
