"""Device-side CSV decode: byte-tensor delimiter scan in HBM.

Reference analog: ``GpuBatchScanExec`` decodes CSV on device via
``Table.readCSV`` (reference: GpuBatchScanExec.scala:465, libcudf's CUDA
CSV parser).  The TPU formulation keeps the O(bytes) work in vector
ops:

  * the raw file bytes upload ONCE as a uint8 tensor,
  * ONE kernel finds every delimiter/newline with an elementwise
    compare, ranks them with a cumsum, and scatters their positions
    into a [rows, cols] boundary matrix (no sort, no per-byte host
    work),
  * per column, a static-width byte window gathers the field and a
    fixed-step fold (v = v*10 + digit) parses ints/floats exactly —
    per-row Python never runs.

The host does an O(bytes) vectorized numpy prescan only to SIZE the
static shapes (row count, per-column width buckets) and to detect
dialects the kernel doesn't do (quoted fields, ragged rows, exotic
numerics) — those fall back to the Arrow CSV reader per file, the same
per-operator fallback philosophy as the parquet path.

Coverage: int32/int64/float32/float64 (fixed-point, optional sign,
optional fraction; NaN/Inf/exponent fall back), bool (true/false),
strings, empty-string nulls, trailing ``\\r`` (CRLF), header skip.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from spark_rapids_tpu import dtypes as dt
from spark_rapids_tpu.columnar.batch import (DeviceBatch, DeviceColumn,
                                             _bucket_strlen, bucket_rows)
from spark_rapids_tpu.exec.kernel_cache import jit_named
from spark_rapids_tpu.plan.logical import Schema


class UnsupportedCsv(Exception):
    pass


def prescan(raw: bytes, n_cols: int, sep: bytes = b",",
            header: bool = True):
    """Vectorized host prescan: row count, per-column width buckets,
    dialect checks.  O(bytes) numpy, no per-field work."""
    a = np.frombuffer(raw, dtype=np.uint8)
    if header:
        # strip the header BEFORE the quote check: writers commonly
        # quote column names while leaving data unquoted
        first_nl = int(np.argmax(a == 0x0A)) if 0x0A in a[:1 << 20] \
            else -1
        if first_nl < 0:
            raise UnsupportedCsv("no header newline")
        a = a[first_nl + 1:]
    if np.any(a == ord('"')):
        raise UnsupportedCsv("quoted fields")
    if a.shape[0] and a[-1] != 0x0A:
        a = np.concatenate([a, np.array([0x0A], np.uint8)])
    is_nl = a == 0x0A
    n_rows = int(is_nl.sum())
    if n_rows == 0:
        return a, 0, [1] * n_cols
    is_delim = (a == sep[0]) | is_nl
    pos = np.flatnonzero(is_delim)
    if pos.shape[0] != n_rows * n_cols:
        raise UnsupportedCsv("ragged rows")
    bounds = pos.reshape(n_rows, n_cols)
    starts = np.empty_like(bounds)
    starts[:, 1:] = bounds[:, :-1] + 1
    starts[0, 0] = 0
    starts[1:, 0] = bounds[:-1, -1] + 1
    widths = (bounds - starts).max(axis=0)
    return a, n_rows, [max(int(w), 1) for w in widths]


@partial(jit_named, family="decode_csv",
         static_argnames=("n_cols", "cap", "widths", "dtypes_key", "sep",
                          "parse_cols"))
def _decode_kernel(raw: jnp.ndarray, n_rows, n_cols: int, cap: int,
                   widths: Tuple[int, ...], dtypes_key: Tuple[str, ...],
                   sep: int, parse_cols: Tuple[int, ...]):
    """ONE program: delimiter scan -> boundary matrix -> per-column
    parse.  Shapes are static buckets only; the exact row count is a
    traced operand so the compile cache hits across files."""
    nb = raw.shape[0]
    is_nl = raw == jnp.uint8(0x0A)
    is_delim = (raw == jnp.uint8(sep)) | is_nl
    # rank every delimiter and scatter its byte position
    did = jnp.cumsum(is_delim.astype(jnp.int32)) - 1
    tgt = jnp.where(is_delim, did, cap * n_cols)
    bounds = jnp.full((cap * n_cols + 1,), nb,
                      dtype=jnp.int32).at[tgt].set(
        jnp.arange(nb, dtype=jnp.int32), mode="drop")[:-1]
    bounds = bounds.reshape(cap, n_cols)
    starts = jnp.concatenate(
        [jnp.concatenate([jnp.zeros((1, 1), jnp.int32),
                          bounds[:-1, -1:] + 1]),
         bounds[:, :-1] + 1], axis=1)
    lens = bounds - starts
    # strip trailing \r (CRLF) from the LAST field of each row
    last_byte = jnp.take(
        raw, jnp.clip(bounds[:, -1] - 1, 0, nb - 1))
    lens = lens.at[:, -1].add(
        jnp.where((last_byte == 0x0D) & (lens[:, -1] > 0), -1, 0))

    row_pad = jnp.arange(cap) < n_rows
    out = []
    # column pruning: the delimiter scan covers every column, but the
    # gather+parse runs only for requested ones
    for c in parse_cols:
        F = widths[c]
        st = jnp.where(row_pad, starts[:, c], 0)
        ln = jnp.where(row_pad, lens[:, c], 0)
        idx = st[:, None] + jnp.arange(F, dtype=jnp.int32)[None, :]
        in_field = jnp.arange(F)[None, :] < ln[:, None]
        mat = jnp.where(
            in_field & row_pad[:, None],
            jnp.take(raw, jnp.clip(idx, 0, nb - 1)), 0)
        out.append(_parse_column(mat, ln, row_pad, dtypes_key[c], F))
    return tuple(out)


def _parse_column(mat: jnp.ndarray, ln: jnp.ndarray,
                  row_pad: jnp.ndarray, dkey: str, F: int):
    """(data, validity[, lengths, ok]) for one column; `ok` is a scalar
    False when a field used syntax the kernel doesn't parse."""
    empty = ln == 0
    if dkey == "string":
        valid = row_pad & ~empty
        return (jnp.where(valid[:, None], mat, 0), valid,
                jnp.where(valid, ln, 0).astype(jnp.int32),
                jnp.bool_(True))
    if dkey == "bool":
        def word(wd: bytes):
            m = ln == len(wd)
            for j, byte in enumerate(wd):
                if j < F:
                    m = m & ((mat[:, j] | 0x20) == (byte | 0x20))
                else:
                    m = jnp.zeros_like(m)
            return m
        is_t = word(b"true")
        is_f = word(b"false")
        valid = row_pad & ~empty & (is_t | is_f)
        ok = jnp.all(~row_pad | empty | is_t | is_f)
        return is_t & valid, valid, None, ok

    # numeric: [-]digits[.digits]
    neg = mat[:, 0] == ord("-")
    digit = mat - ord("0")
    is_digit = (digit >= 0) & (digit <= 9)
    is_dot = mat == ord(".")
    pos_in = jnp.arange(F)[None, :]
    in_field = pos_in < ln[:, None]
    legal = ~in_field | is_digit | is_dot | \
        ((pos_in == 0) & neg[:, None])
    ok = jnp.all(legal | ~row_pad[:, None])
    one_dot = jnp.sum((is_dot & in_field).astype(jnp.int32),
                      axis=1) <= 1
    ok = ok & jnp.all(one_dot | ~row_pad)

    dot_pos = jnp.min(jnp.where(is_dot & in_field, pos_in,
                                jnp.int32(F)), axis=1)
    int_v = jnp.zeros(mat.shape[0], dtype=jnp.int64)
    frac_v = jnp.zeros(mat.shape[0], dtype=jnp.int64)
    frac_n = jnp.zeros(mat.shape[0], dtype=jnp.int32)
    n_dig = jnp.zeros(mat.shape[0], dtype=jnp.int32)
    for i in range(F):
        d = digit[:, i].astype(jnp.int64)
        take_int = is_digit[:, i] & (i < ln) & (i < dot_pos)
        take_frac = is_digit[:, i] & (i < ln) & (i > dot_pos)
        int_v = jnp.where(take_int, int_v * 10 + d, int_v)
        frac_v = jnp.where(take_frac, frac_v * 10 + d, frac_v)
        frac_n = frac_n + take_frac.astype(jnp.int32)
        n_dig = n_dig + (take_int | take_frac).astype(jnp.int32)
    # a bare '-' / '.' is NOT a number, and >18 digits would silently
    # wrap the int64 fold — both host-fallback instead
    ok = ok & jnp.all((n_dig >= 1) | empty | ~row_pad)
    ok = ok & jnp.all((n_dig <= 18) | ~row_pad)
    valid = row_pad & ~empty
    if dkey in ("int32", "int64"):
        # a '.' in an integer column falls back
        ok = ok & jnp.all(dot_pos >= jnp.where(row_pad, ln, 0))
        v = jnp.where(neg, -int_v, int_v)
        v = jnp.where(valid, v, 0)
        if dkey == "int32":
            # the 18-digit guard only protects the int64 fold; values
            # outside int32 range would silently wrap on the device cast
            # — route them to the host fallback like other unsupported
            # numerics
            in_range = (v >= jnp.int64(-2**31)) & (v <= jnp.int64(2**31 - 1))
            ok = ok & jnp.all(in_range | ~row_pad)
            v = v.astype(jnp.int32)
        return v, valid, None, ok
    v = int_v.astype(jnp.float64) + \
        frac_v.astype(jnp.float64) / (10.0 ** frac_n.astype(jnp.float64))
    v = jnp.where(neg, -v, v)
    v = jnp.where(valid, v, 0.0)
    if dkey == "float32":
        v = v.astype(jnp.float32)
    return v, valid, None, ok


_DKEY = {dt.TypeId.INT32: "int32", dt.TypeId.INT64: "int64",
         dt.TypeId.FLOAT32: "float32", dt.TypeId.FLOAT64: "float64",
         dt.TypeId.BOOL: "bool", dt.TypeId.STRING: "string"}


def decode_csv(path: str, schema: Schema,
               columns: Optional[List[str]] = None, sep: str = ",",
               header: bool = True) -> Tuple[DeviceBatch, List[str]]:
    """Decode one CSV file to a DeviceBatch (raises UnsupportedCsv for
    dialects the kernel doesn't cover — caller falls back to Arrow).

    Returns (batch, fallback_columns): columns whose runtime content
    used unsupported numeric syntax are re-decoded on host."""
    wanted = columns or [f.name for f in schema.fields]
    all_names = [f.name for f in schema.fields]
    for f in schema.fields:
        if f.dtype.id not in _DKEY:
            raise UnsupportedCsv(f"dtype {f.dtype.name}")
    with open(path, "rb") as fh:
        raw = fh.read()
    a, n_rows, widths = prescan(raw, len(all_names),
                                sep.encode(), header)
    cap = bucket_rows(max(n_rows, 1))
    bcap = bucket_rows(max(a.shape[0], 64), 64)
    dev_raw = jnp.asarray(np.concatenate(
        [a, np.zeros(bcap - a.shape[0], np.uint8)]))
    widths_b = tuple(_bucket_strlen(w) for w in widths)
    dkeys = tuple(_DKEY[f.dtype.id] for f in schema.fields)
    parse_cols = tuple(i for i, nme in enumerate(all_names)
                       if nme in wanted)
    outs = _decode_kernel(dev_raw, jnp.int32(n_rows),
                          n_cols=len(all_names), cap=cap,
                          widths=widths_b, dtypes_key=dkeys,
                          sep=ord(sep), parse_cols=parse_cols)
    out_by_idx = dict(zip(parse_cols, outs))

    # one tiny read for the per-column ok flags
    oks = [bool(x) for x in np.asarray(
        jnp.stack([o[3] for o in outs]))]
    fallbacks = [all_names[i] for i, okf in zip(parse_cols, oks)
                 if not okf]
    host_cols = {}
    if fallbacks:
        from spark_rapids_tpu.io.readers import _normalize, _read_csv
        fb_schema = Schema([schema.field(n) for n in fallbacks])
        t = _normalize(_read_csv(path, {"header": header, "sep": sep}),
                       fb_schema, permissive=True)
        from spark_rapids_tpu.columnar.batch import from_arrow
        sub = from_arrow(t, capacity=cap)
        host_cols = dict(zip(sub.names, sub.columns))

    cols, names = [], []
    for i, (name, f) in enumerate(zip(all_names, schema.fields)):
        if name not in wanted:
            continue
        o = out_by_idx[i]
        if name in host_cols:
            cols.append(host_cols[name])
        elif f.dtype.is_string:
            cols.append(DeviceColumn(f.dtype, o[0], o[1],
                                     o[2]))
        else:
            cols.append(DeviceColumn(f.dtype, o[0], o[1], None))
        names.append(name)
    return DeviceBatch(names, cols, n_rows), fallbacks
