"""``exchange_roofline`` (layer: kernels: exchange): the least time the
chips could take to carry the statement's exchanged rows
(``exchange_bytes.py``: each row read once and written once at the peak
HBM bandwidth, the share that changes chips once over ICI at
``peaks.json``'s ``ici_bits_per_s`` a chip; rows and widths from the
reference's ``exchange_shapes`` on the same files) over
``exchange_device_s``, the exchange programs' seconds a query.  Where
the trace covers part of a query, that share of the exchanges is held
against it.  Nothing without a device trace, where no exchange program
is among those handed over, or for a statement whose reference gives no
exchange shapes."""

import cells
import exchange_bytes


def read(run):
    trace = run["trace"]
    seconds = cells.reader("exchange_device_s")(run)
    if not seconds or not trace["queries"] or not run["peaks"]:
        return None
    by_index = {r["index"]: r for r in run["completed"]}
    by_name = {s.name: s for s in run["cell"].statements}
    least, shapes = 0.0, {}
    for index, share in trace["covered"]:
        r = by_index.get(index)
        stmt = by_name[r["stmt"]] if r else None
        if stmt is None or not hasattr(stmt.reference, "exchange_shapes"):
            continue
        if stmt.name not in shapes:
            shapes[stmt.name] = exchange_bytes.statement_exchange_seconds(
                stmt.reference.exchange_shapes(run["root"]),
                trace["chips"], run["peaks"])["least_s"]
        least += share * shapes[stmt.name]
    # exchange_device_s is seconds a covered query
    busy = seconds * len(trace["covered"])
    return 100.0 * least / busy if least else None
