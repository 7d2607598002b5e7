"""``sort_device_s`` (layer: operators: sort): seconds of the traced
interval in ``jit_sort_keys``, ``jit_sort_apply`` and
``jit_shared_digit_sort``, over the queries the interval touches.
Nothing without a device trace or where none ran."""

import families


def read(run):
    return families.device_seconds(run, "sort_device_s")
