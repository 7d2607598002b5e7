"""``device_idle_pct`` (layer: device): 1 less the union of the
device operations' intervals over the traced interval, which covers
whole queries inside the window; mean over the chips.  Nothing without
a device trace."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
