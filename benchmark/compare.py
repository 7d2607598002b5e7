"""The comparison that decides ``correct``: an answer the timed window
returned to the client against the plain reference's on the same files.

Numbers compared, each with a limit of its own (``limits.json``):

- ``rows_diff``      rows returned less rows due (after the limit);
- ``key_mismatch``   returned rows whose key or exact columns are not
                     the reference's, or that stand where the ORDER BY
                     does not allow them;
- ``float_rel_err``  the widest relative gap of a float aggregate.

An ordered answer is compared in order.  Two rows may change places
only where the float they are ordered by is equal within the float
limit in the reference: there the order hangs on the last bit of a sum
and both orders are right.
"""

import numpy as np
import pyarrow as pa

# a float that cannot be compared at all (a column missing, a NaN on one
# side): finite, so that the result line stays plain JSON
UNCOMPARABLE = 1e300


def _rows(table: pa.Table, cols) -> list:
    return list(zip(*[table.column(c).to_pylist() for c in cols])) \
        if cols else [()] * table.num_rows


def _tie_classes(values: np.ndarray, rtol: float) -> np.ndarray:
    """Class number of each reference row: neighbours whose ordering
    float agrees within ``rtol`` share a class."""
    if values.size == 0:
        return np.zeros(0, dtype=np.int64)
    gap = np.abs(np.diff(values))
    scale = np.maximum(np.abs(values[1:]), np.abs(values[:-1]))
    return np.concatenate([[0], np.cumsum(gap > rtol * scale)])


def compare(got: pa.Table, want: pa.Table, spec: dict, rtol: float) -> dict:
    """``want`` is the reference's whole answer in its order, before
    any limit.  Returns the three numbers."""
    keys, exact, approx = spec["keys"], spec["exact"], spec["approx"]
    due = want.num_rows if spec.get("limit") is None \
        else min(want.num_rows, int(spec["limit"]))
    missing = [c for c in keys + exact + approx
               if c not in got.column_names]
    if missing:
        return {"rows_diff": abs(got.num_rows - due),
                "key_mismatch": max(got.num_rows, due, 1),
                "float_rel_err": UNCOMPARABLE}
    if not spec.get("ordered"):
        got = got.sort_by([(k, "ascending") for k in keys])
        want = want.sort_by([(k, "ascending") for k in keys])
    want_pos = {k: i for i, k in enumerate(_rows(want, keys))}
    order_float = spec.get("order_float")
    classes = _tie_classes(
        want.column(order_float).to_numpy(zero_copy_only=False), rtol) \
        if order_float else np.arange(want.num_rows)
    want_exact = _rows(want, exact)
    want_approx = {c: want.column(c).to_numpy(zero_copy_only=False)
                   .astype(np.float64) for c in approx}
    got_approx = {c: got.column(c).to_numpy(zero_copy_only=False)
                  .astype(np.float64) for c in approx}
    bad, worst, seen = 0, 0.0, set()
    for i, (k, e) in enumerate(zip(_rows(got, keys), _rows(got, exact))):
        p = want_pos.get(k)
        # a row stands right where its class in the reference is the
        # class of the reference's row at that place
        if p is None or p in seen or e != want_exact[p] \
                or i >= want.num_rows or classes[p] != classes[i]:
            bad += 1
            continue
        seen.add(p)
        for c in approx:
            a, b = got_approx[c][i], want_approx[c][p]
            if np.isnan(a) != np.isnan(b):
                worst = UNCOMPARABLE
            elif not np.isnan(b):
                worst = max(worst, abs(a - b) / max(abs(b), 1e-300))
    return {"rows_diff": abs(got.num_rows - due), "key_mismatch": bad,
            "float_rel_err": worst}
