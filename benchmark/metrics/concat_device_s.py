"""``concat_device_s`` (layer: operators: concat): seconds of the
traced interval in ``jit_concat`` (``concat_batches``), over the
queries the interval touches.  Nothing without a device trace or where
none ran."""

import families


def read(run):
    return families.device_seconds(run, "concat_device_s")
