"""``scan_hostprep_ms`` (layer: scan host prep): the scans'
``scan.hostPrepTime`` + ``scan.uploadTime`` of each query's executed
plan (host time, nanoseconds in the operators' metrics), mean over the
window's queries.  Nothing where no scan recorded either."""

import placement


def read(run):
    ns = [placement.node_extras(r["profile"], "scan.hostPrepTime")
          + placement.node_extras(r["profile"], "scan.uploadTime")
          for r in run["completed"] if r["profile"] is not None]
    return sum(ns) / len(ns) / 1e6 if ns and sum(ns) > 0 else None
