"""``download_ms`` (layer: serve: download): the ``collect.download``
span of each query: after the device is done, the host copies of the
packed result and the Arrow build.  Mean over the window's queries.
Nothing where the program records no such span."""


def read(run):
    ns = []
    for r in run["completed"]:
        spans = r["profile"].spans if r["profile"] is not None else []
        mine = [s["dur_ns"] for s in spans
                if s["name"] == "collect.download"]
        if mine:
            ns.append(sum(mine))
    return sum(ns) / len(ns) / 1e6 if ns else None
