"""``exchange_fill_pct`` (layer: exchange): the rows the ICI exchanges
took in inside the window over the slots their senders' buckets had,
100 x ``exchange.ici.rowsIn`` / ``exchange.ici.sendSlots`` (an exchange
of ``n`` chips sends ``n x n`` buckets).  The step's gathers, its
``all_to_all`` and the receivers' compaction all run over the slots, so
an empty share of a bucket is paid by each of them.  Nothing where the
program has neither counter."""


def read(run):
    from spark_rapids_tpu.obs import registry
    total = registry.get_registry().snapshot()["counters"]
    slots = run["counters"].get("exchange.ici.sendSlots", 0)
    if "exchange.ici.sendSlots" not in total or not slots:
        return None
    return 100.0 * run["counters"].get("exchange.ici.rowsIn", 0) / slots
