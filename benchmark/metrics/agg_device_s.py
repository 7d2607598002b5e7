"""``agg_device_s`` (layer: operators: aggregate): seconds of the
traced interval in ``jit_agg_update``, ``jit_agg_merge`` and
``jit_agg_final``, over the queries the interval touches.  Nothing
without a device trace or where none ran."""

import families


def read(run):
    return families.device_seconds(run, "agg_device_s")
