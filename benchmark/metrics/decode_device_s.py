"""``decode_device_s`` (layer: operators: scan decode): seconds of the
traced interval in the scans' decode programs (``jit_pq_fused*``,
``jit_decode_*``), over the queries the interval touches.  Nothing
without a device trace or where none ran."""

import families


def read(run):
    return families.device_seconds(run, "decode_device_s")
