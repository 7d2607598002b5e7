"""``plan_ms`` (layer: plan): the ``plan`` phase of each query's
profile, host time, mean over the window's queries."""


def read(run):
    ns = [r["profile"].phases["plan"] for r in run["completed"]
          if r["profile"] is not None and "plan" in r["profile"].phases]
    return sum(ns) / len(ns) / 1e6 if ns else None
