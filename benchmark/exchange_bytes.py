"""The least time a statement's exchanges take, from shapes alone: the
same whatever implements the exchange.

An exchange's shape is what its reference's ``exchange_shapes(root)``
gives, one entry an exchange in the text's order: ``rows`` (rows that
reach it) and ``row_bytes`` (bytes of a row's columns).  Every row is
read once where it lies and written once where it lands, at the peak
HBM bandwidth of every chip together; with the rows spread evenly,
``(chips - 1) / chips`` of them change chips, and each chip sends its
share over ICI at ``ici_bits_per_s``.
"""


def exchange_bytes(shape: dict) -> float:
    return float(shape["rows"]) * float(shape["row_bytes"])


def statement_exchange_seconds(shapes, chips: int, peaks: dict) -> dict:
    each = [exchange_bytes(s) for s in shapes]
    total = sum(each)
    hbm_s = 2.0 * total / (chips * peaks["hbm_bytes_per_s"])
    crossing = total * (chips - 1) / chips
    ici_s = crossing / (chips * peaks["ici_bits_per_s"] / 8.0)
    return {"exchanges": len(each), "bytes_by_exchange": each,
            "bytes": total, "hbm_s": hbm_s, "ici_s": ici_s,
            "least_s": hbm_s + ici_s}
