"""``scan_fill_pct`` (layer: operators: scan decode): the live rows of
the batches the fused parquet scans decoded inside the window over the
slots of the capacity tiers those batches were born at, 100 x
``scan.batch.rows`` / ``scan.batch.slots``.  Every program above a scan
runs over the slots, so an empty half of a tier is paid by each of
them.  Nothing where the program has neither counter."""


def read(run):
    from spark_rapids_tpu.obs import registry
    total = registry.get_registry().snapshot()["counters"]
    slots = run["counters"].get("scan.batch.slots", 0)
    if "scan.batch.slots" not in total or not slots:
        return None
    return 100.0 * run["counters"].get("scan.batch.rows", 0) / slots
