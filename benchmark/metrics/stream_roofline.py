"""``stream_roofline`` (layer: kernels; all device programs of the
statement together): the least time the chips could take to move the
statement's bytes once (``bytes_model.statement_bytes``) at the peak
HBM bandwidth of ``peaks.json``, over the device's busy time a query
from the trace.  Bandwidth-bound by construction: the statements scan,
join and aggregate and have no matrix product.  Where the trace covers
part of a query, that share of the query's bytes is held against the
busy time of the part.  Nothing without a device trace."""

import bytes_model


def read(run):
    trace = run["trace"]
    if not trace or not trace["queries"] or not trace["busy_s"]:
        return None
    by_index = {r["index"]: r for r in run["completed"]}
    by_name = {s.name: s for s in run["cell"].statements}
    least = 0.0
    for index, share in trace["covered"]:
        r = by_index.get(index)
        if r is None:
            continue
        nbytes = bytes_model.statement_bytes(
            run["root"], by_name[r["stmt"]].spec["reads"],
            r["table"].nbytes)["least_bytes"]
        least += share * nbytes / (trace["chips"]
                                   * run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / trace["busy_s"] if least else None
