"""The least bytes a statement has to move, from shapes alone: the
same whatever implements the operators.

For each column the statement reads (``SPEC["reads"]`` of its
reference): the encoded pages as they are uploaded (the column chunks'
uncompressed size in the files' footers), and the decoded column
written once and read once (rows x width; a string column its bytes
plus a 4-byte offset a row).  Then the answer's bytes once.
"""

import os

import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as papq


def _decoded_bytes(folder: str, column: str, field_type, rows: int) -> int:
    if pa.types.is_string(field_type) or pa.types.is_binary(field_type):
        col = papq.read_table(folder, columns=[column]).column(column)
        return int(pc.sum(pc.binary_length(col)).as_py() or 0) + 4 * rows
    return rows * field_type.bit_width // 8


def statement_bytes(root: str, reads: dict, answer_bytes: int) -> dict:
    encoded = decoded = 0
    for table, columns in reads.items():
        folder = os.path.join(root, table)
        rows, schema = 0, None
        for name in sorted(os.listdir(folder)):
            pf = papq.ParquetFile(os.path.join(folder, name))
            meta = pf.metadata
            schema = schema or pf.schema_arrow
            rows += meta.num_rows
            index = {meta.schema.column(i).path: i
                     for i in range(meta.num_columns)}
            for rg in range(meta.num_row_groups):
                for c in columns:
                    encoded += meta.row_group(rg).column(
                        index[c]).total_uncompressed_size
        for c in columns:
            decoded += _decoded_bytes(folder, c, schema.field(c).type, rows)
    return {"encoded_bytes": encoded, "decoded_bytes": decoded,
            "answer_bytes": int(answer_bytes),
            "least_bytes": encoded + 2 * decoded + int(answer_bytes)}
