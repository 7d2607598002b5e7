"""``device_reads_per_query`` (layer: device): the host's blocking
reads of device values inside the window (``device.reads``: the calls
of ``columnar/batch.read_host`` that copied; each drains its chip's
queue until the value is back), over the queries completed.
``device.reads.<site>`` splits the count by where the read is.
Nothing where the program has no such counter."""


def read(run):
    from spark_rapids_tpu.obs import registry
    total = registry.get_registry().snapshot()["counters"]
    n = len(run["completed"])
    if not n or "device.reads" not in total:
        return None
    return run["counters"].get("device.reads", 0) / n
