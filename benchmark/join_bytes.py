"""The least bytes a statement's joins have to move, from shapes
alone: the same whatever implements the join.

A join's shape is what its reference's ``join_shapes(root)`` gives, one
entry a join in the text's order: ``build_rows`` and ``stream_rows``
(rows that reach the join on each side), ``key_bytes`` (bytes of one
side's key columns in a row), ``table_entries`` (the values the build
side's keys span: the smallest table that addresses them directly),
``out_rows`` (matched rows) and ``out_row_bytes`` (bytes of the
columns the join's output carries, in a row; a string its mean bytes
plus a 4-byte offset).  Each side's key columns are read once, the
table is written once and read once at 4 bytes an entry, the output is
written once.
"""

TABLE_ENTRY_BYTES = 4


def join_bytes(shape: dict) -> int:
    keys = (shape["build_rows"] + shape["stream_rows"]) * shape["key_bytes"]
    table = 2 * TABLE_ENTRY_BYTES * shape["table_entries"]
    return int(keys + table + shape["out_rows"] * shape["out_row_bytes"])


def statement_join_bytes(shapes) -> dict:
    each = [join_bytes(s) for s in shapes]
    return {"joins": len(each), "bytes_by_join": each,
            "least_bytes": sum(each)}
