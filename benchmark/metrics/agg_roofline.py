"""``agg_roofline`` (layer: kernels: aggregate): the least time the
chip could take to move the aggregates' bytes once (``agg_bytes.py``,
from the shapes that the statement's reference computes on the same
files) at the peak HBM bandwidth of ``peaks.json``, over the seconds of
the programs named ``jit_agg_*`` (update, shrink, merge, final) a
query.  Where the trace covers part of a query, that share of the
aggregates' bytes is held against it, as ``join_roofline`` does.
Nothing without a device trace, where no aggregate program is among
those handed over, or for a statement whose reference gives no
aggregate shapes."""

import agg_bytes


def is_agg_program(program: str) -> bool:
    return program.split(":", 1)[0].startswith("jit_agg_")


def read(run):
    trace = run["trace"]
    if not trace or not trace.get("covered") or not trace["queries"]:
        return None
    busy = sum(t for name, t in trace["device_programs"]
               if is_agg_program(name))
    if not busy:
        return None
    by_index = {r["index"]: r for r in run["completed"]}
    by_name = {s.name: s for s in run["cell"].statements}
    least, shapes = 0.0, {}
    for index, share in trace["covered"]:
        r = by_index.get(index)
        stmt = by_name[r["stmt"]] if r else None
        if stmt is None or not hasattr(stmt.reference, "agg_shapes"):
            continue
        if stmt.name not in shapes:
            shapes[stmt.name] = agg_bytes.statement_agg_bytes(
                stmt.reference.agg_shapes(run["root"]))["least_bytes"]
        least += share * shapes[stmt.name] / (
            trace["chips"] * run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / busy if least else None
