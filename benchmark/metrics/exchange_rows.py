"""``exchange_rows`` (layer: exchange): the rows the ICI exchanges took
in inside the window (``exchange.ici.rowsIn``: every row that reached an
``all_to_all``, whichever chip it lay on), over the queries completed.
Partial aggregation under an exchange lowers it.  Nothing where the
program has no such counter."""


def read(run):
    from spark_rapids_tpu.obs import registry
    total = registry.get_registry().snapshot()["counters"]
    n = len(run["completed"])
    if not n or "exchange.ici.rowsIn" not in total:
        return None
    return run["counters"].get("exchange.ici.rowsIn", 0) / n
