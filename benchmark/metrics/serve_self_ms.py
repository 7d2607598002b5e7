"""``serve_self_ms`` (layer: serve): self time of the ``serve.request``
span, the root of a query's span tree (request receipt to END frame):
its duration less the union of its children (``sched.queueWait``,
``query.plan|execute|collect``, ``serve.stream``), so what the front
end itself spent: parse, digest, submission, thread hand-offs.  Mean
over the window's queries.  Nothing where the program records no such
root."""

import families
import tracered


def read(run):
    selfs = []
    for r in run["completed"]:
        root, children = families.span_tree(r["profile"])
        if root is None:
            continue
        t0, t1 = root["ts_ns"], root["ts_ns"] + root["dur_ns"]
        inside = tracered.union(
            (max(c["ts_ns"], t0), min(c["ts_ns"] + c["dur_ns"], t1))
            for c in children if c["ts_ns"] < t1
            and c["ts_ns"] + c["dur_ns"] > t0)
        selfs.append(root["dur_ns"] - sum(e - s for s, e in inside))
    return sum(selfs) / len(selfs) / 1e6 if selfs else None
