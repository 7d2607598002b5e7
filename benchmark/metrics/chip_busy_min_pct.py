"""``chip_busy_min_pct`` (layer: device): the least busy chip's busy
seconds over the traced interval (``busy_s_per_chip`` and ``window_s``
of ``tracered.reduce``), in percent: on a host of several chips the
mean (``device_idle_pct``) hides a chip that waits for the others.
Before it answers, the reader prints one observation line: every
chip's busy seconds, every chip's peak bytes where the program can tell
them (``mem.device.memory_peaks``), and what each ICI exchange of the
first traced query counted (the ``exchange.ici`` spans' arguments:
rows in, the bucket's tier, each receiver's rows and capacity).
Nothing without a device trace."""

import json


def chip_peaks():
    """Each device's peak bytes in use, or ``None`` at a program that
    has no such helper."""
    from spark_rapids_tpu.mem import device
    peaks = getattr(device, "memory_peaks", None)
    return None if peaks is None else peaks()


def exchanges(run) -> list:
    """The arguments of the first completed query's ``exchange.ici``
    spans, in the order the exchanges ran; nothing at a program that
    records no such span."""
    for r in run.get("completed") or ():
        profile = r.get("profile")
        if profile is not None:
            spans = sorted((s for s in profile.spans
                            if s["name"] == "exchange.ici"
                            and s.get("args")), key=lambda s: s["ts_ns"])
            return [s["args"] for s in spans]
    return []


def read(run):
    trace = run["trace"]
    if not trace or not trace["window_s"] or \
            not trace.get("busy_s_per_chip"):
        return None
    print(json.dumps({"phase": "chips",
                      "busy_s_per_chip": trace["busy_s_per_chip"],
                      "window_s": trace["window_s"],
                      "peak_bytes_per_chip": chip_peaks(),
                      "exchanges": exchanges(run)}, default=str),
          flush=True)
    return 100.0 * min(trace["busy_s_per_chip"]) / trace["window_s"]
