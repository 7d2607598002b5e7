"""``join_roofline`` (layer: kernels: join): the least time the chip
could take to move the joins' bytes once (``join_bytes.py``, from the
shapes that the statement's reference computes on the same files) at
the peak HBM bandwidth of ``peaks.json``, over ``join_device_s``, the
join programs' seconds a query.  Where the trace covers part of a
query, that share of the joins' bytes is held against it.  Nothing
without a device trace, where no join program is among those handed
over, or for a statement whose reference gives no join shapes."""

import cells
import join_bytes


def read(run):
    trace = run["trace"]
    seconds = cells.reader("join_device_s")(run)
    if not seconds or not trace["queries"]:
        return None
    by_index = {r["index"]: r for r in run["completed"]}
    by_name = {s.name: s for s in run["cell"].statements}
    least, shapes = 0.0, {}
    for index, share in trace["covered"]:
        r = by_index.get(index)
        stmt = by_name[r["stmt"]] if r else None
        if stmt is None or not hasattr(stmt.reference, "join_shapes"):
            continue
        if stmt.name not in shapes:
            shapes[stmt.name] = join_bytes.statement_join_bytes(
                stmt.reference.join_shapes(run["root"]))["least_bytes"]
        least += share * shapes[stmt.name] / (
            trace["chips"] * run["peaks"]["hbm_bytes_per_s"])
    # join_device_s is seconds a covered query
    busy = seconds * len(trace["covered"])
    return 100.0 * least / busy if least else None
