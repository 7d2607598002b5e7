"""What a plan's parquet footers say about its size: the bytes a
pruned scan reads, and a bound on an aggregate's groups.

``logical.size_estimate`` feeds the broadcast decision with these (the
role of Spark's file statistics; an aggregate's output Spark learns at
run time, from the map output its adaptive execution reads, where this
engine plans once).  A scan that reads 5 of 22 columns is sized by
those columns' chunks, not by the files; a ``GROUP BY`` whose keys are
integer columns of scans leaves at most one group a combination of
values in the keys' ``[min, max]`` ranges (and the null), so the
per-store average over a 28.8M-row fact table is a hundred rows and
not half the table.  Anything the footers do not say (another format,
a computed key, a column without statistics) gives ``None`` and the
caller keeps its rule of thumb.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

from spark_rapids_tpu.expr import ir
from spark_rapids_tpu.plan import logical as lp

# path -> (mtime_ns, size, {column: [uncompressed bytes, min, max]});
# min and max are None where a chunk that holds values has no integer
# statistics
_FOOTERS: Dict[str, Tuple[int, int, dict]] = {}
_FOOTERS_MAX = 4096


def _footer(path: str) -> Optional[dict]:
    try:
        st = os.stat(path)
    except OSError:
        return None
    hit = _FOOTERS.get(path)
    if hit is not None and hit[:2] == (st.st_mtime_ns, st.st_size):
        return hit[2]
    import pyarrow.parquet as papq
    try:
        meta = papq.read_metadata(path)
    except Exception:                  # not a parquet file after all
        return None
    cols: dict = {}
    unranged = set()
    for rg in range(meta.num_row_groups):
        group = meta.row_group(rg)
        for i in range(meta.num_columns):
            chunk = group.column(i)
            col = cols.setdefault(chunk.path_in_schema, [0, None, None])
            col[0] += chunk.total_uncompressed_size
            s = chunk.statistics
            if s is not None and s.has_min_max and \
                    isinstance(s.min, int) and isinstance(s.max, int):
                col[1] = s.min if col[1] is None else min(col[1], s.min)
                col[2] = s.max if col[2] is None else max(col[2], s.max)
            elif s is None or s.null_count != chunk.num_values:
                unranged.add(chunk.path_in_schema)
    for name in unranged:
        cols[name][1:] = [None, None]
    if len(_FOOTERS) >= _FOOTERS_MAX:
        _FOOTERS.clear()
    _FOOTERS[path] = (st.st_mtime_ns, st.st_size, cols)
    return cols


def scan_bytes(scan) -> Optional[int]:
    """Uncompressed bytes of the column chunks a pruned parquet scan
    reads; ``None`` for a scan of every column or of another format."""
    names = scan.options.get("columns")
    if scan.fmt != "parquet" or not names:
        return None
    total = 0
    for path in scan.paths:
        cols = _footer(path)
        if cols is None or any(n not in cols for n in names):
            return None
        total += sum(cols[n][0] for n in names)
    return total


def _scan_range(scan, name: str) -> Optional[Tuple[int, int]]:
    if scan.fmt != "parquet":
        return None
    lo = hi = None
    for path in scan.paths:
        cols = _footer(path)
        if cols is None or name not in cols:
            return None
        _, flo, fhi = cols[name]
        if flo is None:
            return None
        lo = flo if lo is None else min(lo, flo)
        hi = fhi if hi is None else max(hi, fhi)
    return None if lo is None else (lo, hi)


def column_range(node, ordinal: int) -> Optional[Tuple[int, int]]:
    """``(min, max)`` of an integer output column that is, unchanged,
    a column of a parquet scan underneath; ``None`` otherwise."""
    if isinstance(node, lp.FileScan):
        f = node.schema.fields[ordinal]
        return _scan_range(node, f.name) if f.dtype.is_integral else None
    if isinstance(node, (lp.Filter, lp.Sort, lp.Limit)):
        return column_range(node.children[0], ordinal)
    if isinstance(node, (lp.Project, lp.Aggregate)):
        exprs = node.exprs if isinstance(node, lp.Project) \
            else node.groupings
        if ordinal >= len(exprs):
            return None
        e = exprs[ordinal]
        while isinstance(e, ir.Alias):
            e = e.children[0]
        return column_range(node.children[0], e.ordinal) \
            if isinstance(e, ir.BoundReference) else None
    if isinstance(node, lp.Join):
        left, right = node.children
        n_l = len(left.schema.names)
        return column_range(left, ordinal) if ordinal < n_l \
            else column_range(right, ordinal - n_l)
    return None


def aggregate_bytes(node) -> Optional[int]:
    """A bound on a grouped aggregate's output: one row a combination
    of its keys' ranges (one more value a key for the null), at the
    output's row width.  ``None`` where a key has no range."""
    groups = 1
    for i in range(len(node.groupings)):
        r = column_range(node, i)
        if r is None:
            return None
        groups *= r[1] - r[0] + 2
    return groups * sum(f.dtype.byte_width for f in node.schema.fields)
