"""``peak_hbm_gb`` (layer: memory): ``peak_bytes_in_use`` of the
fullest device after the window, in GB (1e9 bytes).  Nothing where the
backend reports none."""


def read(run):
    peak = run["memory_peak_bytes"]
    return peak / 1e9 if peak else None
