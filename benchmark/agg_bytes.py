"""The least bytes a statement's aggregates have to move, from shapes
alone: the same whatever implements the aggregate.

An aggregate's shape is what its reference's ``agg_shapes(root)``
gives, one entry an aggregate in the text's order: ``rows_in`` (rows
that reach it), ``key_bytes`` and ``value_bytes`` (bytes of its
grouping columns and of its aggregated columns in a row in),
``groups_out`` and ``out_value_bytes`` (the groups it leaves and the
bytes of their aggregates in a row out).  Every row in is read once,
every group out is written once with its keys.
"""


def agg_bytes(shape: dict) -> int:
    read = shape["rows_in"] * (shape["key_bytes"] + shape["value_bytes"])
    written = shape["groups_out"] * (shape["key_bytes"]
                                     + shape["out_value_bytes"])
    return int(read + written)


def statement_agg_bytes(shapes) -> dict:
    each = [agg_bytes(s) for s in shapes]
    return {"aggregates": len(each), "bytes_by_aggregate": each,
            "least_bytes": sum(each)}
