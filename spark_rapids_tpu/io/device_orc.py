"""Device-side ORC decode: stripe streams upload packed, expand in HBM.

TPU-native analog of the reference's device ORC scan
(reference: GpuOrcScan.scala:206+ — CPU walks stripe footers, libcudf
decodes on GPU).  Mirrors io/device_parquet.py's architecture:

  host (O(runs), not O(values)):
    * hand-parsed protobuf postscript/footer/stripe-footer (ORC metadata
      is plain proto wire format; no generated code needed)
    * RLEv2 run walking — SHORT_REPEAT -> RLE runs, DIRECT -> big-endian
      bit-pack runs; DELTA materializes via vectorized numpy (base +
      cumsum); PATCHED_BASE falls the column back to host Arrow
    * boolean/PRESENT byte-RLE expanded with numpy (n/8 bytes)

  device (O(values), jitted per bucket):
    * big-endian bit-pack expansion (the MSB-first twin of parquet's
      run expansion), zigzag decode, PRESENT scatter via the shared
      ``_def_expand`` two-pass pattern, string dictionary gathers

Coverage: int8/16/32/64, date32, float32/64, boolean, strings
(DICTIONARY_V2 gathers in HBM; DIRECT_V2 builds the byte matrix on
host), flat schemas, NONE/ZLIB/ZSTD/SNAPPY(if available)/LZ4-frame
stream compression.  Anything else falls back to host Arrow *per
column*, same philosophy as the parquet decoder.
"""

from __future__ import annotations

import struct
import zlib
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.orc as paorc

import jax.numpy as jnp

from spark_rapids_tpu import dtypes as dt
from spark_rapids_tpu.columnar.batch import (DeviceBatch, DeviceColumn,
                                             _bucket_strlen, bucket_rows,
                                             from_arrow)
from spark_rapids_tpu.exec.kernel_cache import jit_named
from spark_rapids_tpu.io.device_parquet import (RunTable, _def_expand,
                                                _dict_gather, _pad_np,
                                                _string_dict_matrix,
                                                _to_cap, _upload_runs)
from spark_rapids_tpu.plan.logical import Schema

_MAX_W = 24  # device window supports shift(<=7) + w bits in 4 bytes

# stream kinds
PRESENT, DATA, LENGTH, DICTIONARY_DATA, SECONDARY = 0, 1, 2, 3, 5
# column encodings
ENC_DIRECT, ENC_DICTIONARY, ENC_DIRECT_V2, ENC_DICTIONARY_V2 = 0, 1, 2, 3


class UnsupportedOrc(Exception):
    pass


# ---------------------------------------------------------------------------
# protobuf-lite: ORC metadata is plain proto2 wire format
# ---------------------------------------------------------------------------

def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    out = 0
    shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7


def _fields(buf: bytes):
    """Iterate (field_number, wire_type, value) over one message."""
    pos = 0
    n = len(buf)
    while pos < n:
        key, pos = _varint(buf, pos)
        fnum, wt = key >> 3, key & 7
        if wt == 0:
            v, pos = _varint(buf, pos)
        elif wt == 2:
            ln, pos = _varint(buf, pos)
            v = buf[pos:pos + ln]
            pos += ln
        elif wt == 5:
            v = buf[pos:pos + 4]
            pos += 4
        elif wt == 1:
            v = buf[pos:pos + 8]
            pos += 8
        else:
            raise UnsupportedOrc(f"proto wire type {wt}")
        yield fnum, wt, v


@dataclass
class StripeInfo:
    offset: int = 0
    index_len: int = 0
    data_len: int = 0
    footer_len: int = 0
    num_rows: int = 0


@dataclass
class OrcMeta:
    compression: int = 0           # 0 none, 1 zlib, 2 snappy, 4 lz4, 5 zstd
    block_size: int = 262144
    stripes: List[StripeInfo] = field(default_factory=list)
    kinds: List[int] = field(default_factory=list)       # per type id
    field_names: List[str] = field(default_factory=list)  # of the root


def read_meta(raw: bytes) -> OrcMeta:
    ps_len = raw[-1]
    ps = raw[-1 - ps_len:-1]
    m = OrcMeta()
    footer_len = 0
    for fnum, _, v in _fields(ps):
        if fnum == 1:
            footer_len = v
        elif fnum == 2:
            m.compression = v
        elif fnum == 3:
            m.block_size = v
    footer_raw = _decompress(m, raw[-1 - ps_len - footer_len:-1 - ps_len])
    for fnum, _, v in _fields(footer_raw):
        if fnum == 3:  # StripeInformation
            si = StripeInfo()
            for f2, _, v2 in _fields(v):
                if f2 == 1:
                    si.offset = v2
                elif f2 == 2:
                    si.index_len = v2
                elif f2 == 3:
                    si.data_len = v2
                elif f2 == 4:
                    si.footer_len = v2
                elif f2 == 5:
                    si.num_rows = v2
            m.stripes.append(si)
        elif fnum == 4:  # Type
            kind = 0
            names: List[str] = []
            for f2, _, v2 in _fields(v):
                if f2 == 1:
                    kind = v2
                elif f2 == 3:
                    names.append(v2.decode("utf-8"))
            m.kinds.append(kind)
            if not m.field_names and names:
                m.field_names = names
    return m


def _decompress(m: OrcMeta, buf: bytes) -> bytes:
    """ORC stream decompression: 3-byte chunk headers (len << 1 | raw)."""
    if m.compression == 0:
        return buf
    out = bytearray()
    pos = 0
    while pos + 3 <= len(buf):
        h = buf[pos] | (buf[pos + 1] << 8) | (buf[pos + 2] << 16)
        pos += 3
        ln = h >> 1
        chunk = buf[pos:pos + ln]
        pos += ln
        if h & 1:  # original (uncompressed) chunk
            out += chunk
        elif m.compression == 1:
            out += zlib.decompress(chunk, wbits=-15)
        elif m.compression == 5:
            import zstandard
            out += zstandard.ZstdDecompressor().decompress(
                chunk, max_output_size=m.block_size)
        elif m.compression == 4:
            import lz4.frame
            out += lz4.frame.decompress(chunk)
        elif m.compression == 2:
            try:
                import snappy
                out += snappy.decompress(chunk)
            except ImportError:
                raise UnsupportedOrc("snappy codec not available")
        else:
            raise UnsupportedOrc(f"orc compression {m.compression}")
    return bytes(out)


@dataclass
class StreamInfo:
    kind: int
    column: int
    length: int
    offset: int = 0  # absolute file offset


def read_stripe_footer(raw: bytes, m: OrcMeta, si: StripeInfo
                       ) -> Tuple[List[StreamInfo], List[Tuple[int, int]]]:
    foot = _decompress(m, raw[si.offset + si.index_len + si.data_len:
                              si.offset + si.index_len + si.data_len
                              + si.footer_len])
    streams: List[StreamInfo] = []
    encodings: List[Tuple[int, int]] = []  # (kind, dict_size) per column
    for fnum, _, v in _fields(foot):
        if fnum == 1:
            s = StreamInfo(0, 0, 0)
            for f2, _, v2 in _fields(v):
                if f2 == 1:
                    s.kind = v2
                elif f2 == 2:
                    s.column = v2
                elif f2 == 3:
                    s.length = v2
            streams.append(s)
        elif fnum == 2:
            kind = 0
            dsz = 0
            for f2, _, v2 in _fields(v):
                if f2 == 1:
                    kind = v2
                elif f2 == 2:
                    dsz = v2
            encodings.append((kind, dsz))
    # streams are laid out back to back from the stripe start (index
    # streams first, then data streams) in footer order
    off = si.offset
    for s in streams:
        s.offset = off
        off += s.length
    return streams, encodings


# ---------------------------------------------------------------------------
# RLEv2 host walking
# ---------------------------------------------------------------------------

_FBS = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18,
        19, 20, 21, 22, 23, 24, 26, 28, 30, 32, 40, 48, 56, 64]


def _zigzag_np(u: np.ndarray) -> np.ndarray:
    return (u >> 1) ^ -(u & 1)


def _svarint(buf: bytes, pos: int) -> Tuple[int, int]:
    u, pos = _varint(buf, pos)
    return (u >> 1) ^ -(u & 1), pos


def walk_rlev2(buf: bytes, n_values: int, signed: bool,
               runs: RunTable, packed: bytearray
               ) -> Optional[np.ndarray]:
    """Walk an RLEv2 stream into device-expandable runs.

    SHORT_REPEAT and DIRECT (w <= 24) append to the shared run table
    (bit-pack regions are BIG-endian — the device expander's BE twin
    reads them in place).  DELTA sub-streams are materialized into a
    numpy overlay (vectorized cumsum) returned alongside; a non-None
    return means "use the overlay for the whole stream" (mixed
    run/overlay streams keep runs for non-delta spans with the overlay
    filled only where delta runs landed — simplest correct form:
    materialize EVERYTHING into the overlay once any delta run exists).
    PATCHED_BASE raises (column falls back).
    """
    pos = 0
    seen = 0
    # lazy: materialize host values ONLY if a delta run shows up (the
    # device expands short-repeat/direct runs; re-deriving them on host
    # for nothing would be O(values) host work)
    descs: List[Tuple] = []
    vals: List[np.ndarray] = []
    any_delta = False

    def _materialize_pending():
        for d in descs:
            if d[0] == "rle":
                vals.append(np.full(d[1], d[2], dtype=np.int64))
            else:
                _, cnt_, w_, region_ = d
                bits_ = np.unpackbits(
                    np.frombuffer(region_, dtype=np.uint8))
                u_ = _bits_be_to_uint(bits_, cnt_, w_)
                vals.append(_zigzag_np(u_.astype(np.int64)) if signed
                            else u_.astype(np.int64))
        descs.clear()

    while seen < n_values and pos < len(buf):
        h = buf[pos]
        enc = h >> 6
        if enc == 0:  # SHORT_REPEAT
            w = ((h >> 3) & 7) + 1
            cnt = (h & 7) + 3
            val = int.from_bytes(buf[pos + 1:pos + 1 + w], "big")
            pos += 1 + w
            if signed:
                val = (val >> 1) ^ -(val & 1)
            runs.counts.append(cnt)
            runs.is_rle.append(True)
            runs.values.append(val)
            runs.bit_bases.append(0)
            runs.widths.append(0)
            descs.append(("rle", cnt, val))
            seen += cnt
        elif enc == 1:  # DIRECT
            w = _FBS[(h >> 1) & 0x1F]
            cnt = (((h & 1) << 8) | buf[pos + 1]) + 1
            pos += 2
            nbytes = (cnt * w + 7) // 8
            region = buf[pos:pos + nbytes]
            pos += nbytes
            if w > _MAX_W:
                raise UnsupportedOrc(f"direct width {w}")
            runs.counts.append(cnt)
            runs.is_rle.append(False)
            runs.values.append(1 if signed else 0)  # zigzag flag
            runs.bit_bases.append(len(packed) * 8)
            runs.widths.append(w)
            packed += region
            descs.append(("bits", cnt, w, region))
            seen += cnt
        elif enc == 3:  # DELTA
            any_delta = True
            _materialize_pending()
            w_code = (h >> 1) & 0x1F
            w = 0 if w_code == 0 else _FBS[w_code]
            cnt = (((h & 1) << 8) | buf[pos + 1]) + 1
            pos += 2
            if signed:
                base, pos = _svarint(buf, pos)
            else:
                base, pos = _varint(buf, pos)
            delta0, pos = _svarint(buf, pos)
            out = np.empty(cnt, dtype=np.int64)
            out[0] = base
            if cnt > 1:
                out[1] = base + delta0
            if cnt > 2:
                if w == 0:
                    deltas = np.full(cnt - 2, delta0, dtype=np.int64)
                else:
                    nbytes = ((cnt - 2) * w + 7) // 8
                    region = buf[pos:pos + nbytes]
                    pos += nbytes
                    bits = np.unpackbits(
                        np.frombuffer(region, dtype=np.uint8))
                    mags = _bits_be_to_uint(bits, cnt - 2, w).astype(
                        np.int64)
                    deltas = np.where(delta0 < 0, -mags, mags)
                out[2:] = out[1] + np.cumsum(deltas)
            vals.append(out)
            seen += cnt
        else:
            raise UnsupportedOrc("PATCHED_BASE run")
    if any_delta:
        _materialize_pending()
        return np.concatenate(vals)[:n_values] if vals else \
            np.zeros(0, np.int64)
    return None


def _bits_be_to_uint(bits: np.ndarray, cnt: int, w: int) -> np.ndarray:
    """MSB-first bit array -> cnt w-bit unsigned values (host numpy)."""
    need = cnt * w
    b = bits[:need].reshape(cnt, w).astype(np.uint64)
    weights = (np.uint64(1) << np.arange(w - 1, -1, -1,
                                         dtype=np.uint64))
    return (b * weights).sum(axis=1, dtype=np.uint64)


def decode_bool_rle(buf: bytes, n_bits: int) -> np.ndarray:
    """ORC byte-RLE over a bit stream -> bool[n_bits] (host, n/8 bytes)."""
    arr = decode_byte_rle(buf, (n_bits + 7) // 8)
    return np.unpackbits(arr, bitorder="big")[:n_bits].astype(bool)


def decode_byte_rle(buf: bytes, n: int) -> np.ndarray:
    """ORC byte-RLE -> uint8[n] (PRESENT/bool bits, tinyint DATA)."""
    out = bytearray()
    pos = 0
    while pos < len(buf) and len(out) < n:
        h = buf[pos]
        pos += 1
        if h < 128:
            out += bytes([buf[pos]]) * (h + 3)
            pos += 1
        else:
            lit = 256 - h
            out += buf[pos:pos + lit]
            pos += lit
    return np.frombuffer(bytes(out[:n]), dtype=np.uint8)


# ---------------------------------------------------------------------------
# Device expansion (big-endian twin of device_parquet._expand_runs)
# ---------------------------------------------------------------------------

@partial(jit_named, family="decode_runs_be", static_argnames=("cap",))
def _expand_runs_be(runs_mat: jnp.ndarray, packed: jnp.ndarray,
                    cap: int) -> jnp.ndarray:
    """Expand SHORT_REPEAT/DIRECT runs; DIRECT regions are MSB-first.

    runs_mat columns: (end, is_rle, value_or_zigzag_flag, bit_base,
    width).  For bit-pack runs the value column carries the zigzag flag
    (1 = signed zigzag decode after unpack).  Values are int64.
    """
    run_ends = runs_mat[:, 0]
    run_is_rle = runs_mat[:, 1] != 0
    run_value = runs_mat[:, 2]
    run_bit_base = runs_mat[:, 3]
    run_w = runs_mat[:, 4]
    i = jnp.arange(cap, dtype=jnp.int64)
    rid = jnp.searchsorted(run_ends, i, side="right")
    rid = jnp.clip(rid, 0, run_ends.shape[0] - 1)
    prev_end = jnp.where(rid > 0, jnp.take(run_ends, rid - 1), 0)
    local = i - prev_end
    w = jnp.take(run_w, rid)
    bitpos = jnp.take(run_bit_base, rid) + local * w
    byte0 = bitpos >> 3
    sh = (bitpos & 7).astype(jnp.uint32)
    nb = packed.shape[0]
    g = lambda k: jnp.take(packed, jnp.clip(byte0 + k, 0, nb - 1)
                           ).astype(jnp.uint32)
    # big-endian 32-bit window starting at byte0
    window = (g(0) << 24) | (g(1) << 16) | (g(2) << 8) | g(3)
    wu = w.astype(jnp.uint32)
    shift = jnp.uint32(32) - sh - wu
    mask = ((jnp.uint32(1) << wu) - 1)
    unpacked = ((window >> shift) & mask).astype(jnp.int64)
    zig = jnp.take(run_value, rid) != 0
    dezig = (unpacked >> 1) ^ -(unpacked & 1)
    vals = jnp.where(zig, dezig, unpacked)
    return jnp.where(jnp.take(run_is_rle, rid),
                     jnp.take(run_value, rid), vals)


# ---------------------------------------------------------------------------
# Column decode
# ---------------------------------------------------------------------------

# ORC type kinds
K_BOOL, K_BYTE, K_SHORT, K_INT, K_LONG, K_FLOAT, K_DOUBLE, K_STRING, \
    K_BINARY, K_TIMESTAMP, K_LIST, K_MAP, K_STRUCT, K_UNION, K_DECIMAL, \
    K_DATE = range(16)

_INT_KINDS = {K_SHORT, K_INT, K_LONG, K_DATE}


def _expand_ints(runs: RunTable, packed: bytes,
                 overlay: Optional[np.ndarray], nn: int,
                 vcap: int) -> jnp.ndarray:
    """Non-null value vector (int64) from runs or a host overlay."""
    if overlay is not None:
        return jnp.asarray(_pad_np(overlay[:nn], vcap))
    dev = _upload_runs(runs, bytes(packed))
    return _expand_runs_be(dev["runs_mat"], dev["packed"], cap=vcap)


def decode_column(kind: int, enc: Tuple[int, int],
                  streams: Dict[int, bytes], out_dtype: dt.DType,
                  n_rows: int, cap: int) -> DeviceColumn:
    """Decode one flat column of a stripe into a DeviceColumn."""
    enc_kind, dict_size = enc
    present = streams.get(PRESENT)
    if present is not None:
        validity_np = decode_bool_rle(present, n_rows)
        nn = int(validity_np.sum())
    else:
        validity_np = np.ones(n_rows, dtype=bool)
        nn = n_rows
    vcap = bucket_rows(max(n_rows, 1))
    validity = jnp.asarray(_pad_np(validity_np, vcap))
    levels = validity.astype(jnp.uint32)

    def def_scatter(vals):
        if present is None:
            data = vals
            return data, jnp.arange(vcap) < n_rows
        return _def_expand(levels, vals, n_rows, cap=vcap)

    if kind in _INT_KINDS:
        if enc_kind != ENC_DIRECT_V2:
            raise UnsupportedOrc(f"int encoding {enc_kind}")
        runs = RunTable.empty()
        packed = bytearray()
        overlay = walk_rlev2(streams[DATA], nn, True, runs, packed)
        vals = _expand_ints(runs, packed, overlay, nn, vcap)
        data, valid = def_scatter(vals)
        return _to_cap(DeviceColumn(
            out_dtype, data.astype(out_dtype.to_np()), valid), cap)

    if kind == K_BYTE:
        vals = jnp.asarray(_pad_np(
            decode_byte_rle(streams[DATA], nn).astype(np.int64), vcap))
        data, valid = def_scatter(vals)
        return _to_cap(DeviceColumn(
            out_dtype, data.astype(out_dtype.to_np()), valid), cap)

    if kind in (K_FLOAT, K_DOUBLE):
        npdt = np.dtype("<f4") if kind == K_FLOAT else np.dtype("<f8")
        vals_np = np.frombuffer(streams[DATA], dtype=npdt, count=nn)
        vals = jnp.asarray(_pad_np(vals_np.copy(), vcap))
        data, valid = def_scatter(vals)
        return _to_cap(DeviceColumn(
            out_dtype, data.astype(out_dtype.to_np()), valid), cap)

    if kind == K_BOOL:
        bits = decode_bool_rle(streams[DATA], nn)
        vals = jnp.asarray(_pad_np(bits, vcap))
        data, valid = def_scatter(vals)
        return _to_cap(DeviceColumn(out_dtype, data, valid), cap)

    if kind == K_STRING:
        if enc_kind == ENC_DICTIONARY_V2:
            # dict lengths + blob on host (dictionaries are small),
            # per-row indices expand + gather on device
            lruns = RunTable.empty()
            lpacked = bytearray()
            lover = walk_rlev2(streams[LENGTH], dict_size, False, lruns,
                               lpacked)
            if lover is None:
                dev = _upload_runs(lruns, bytes(lpacked))
                lens64 = np.asarray(_expand_runs_be(
                    dev["runs_mat"], dev["packed"],
                    cap=bucket_rows(max(dict_size, 1))))[:dict_size]
            else:
                lens64 = lover[:dict_size]
            blob = streams.get(DICTIONARY_DATA, b"")
            offs = np.concatenate([[0], np.cumsum(lens64)])
            entries = [blob[offs[i]:offs[i + 1]]
                       for i in range(dict_size)]
            dmat, dlens = _string_dict_matrix(entries)
            iruns = RunTable.empty()
            ipacked = bytearray()
            iover = walk_rlev2(streams[DATA], nn, False, iruns, ipacked)
            idx = _expand_ints(iruns, ipacked, iover, nn, vcap)
            data_idx, valid = def_scatter(idx)
            mat = _dict_gather(data_idx, jnp.asarray(dmat), valid,
                               cap=vcap)
            lens = _dict_gather(data_idx, jnp.asarray(dlens), valid,
                                cap=vcap)
            return _to_cap(DeviceColumn(out_dtype, mat, valid,
                                        lens.astype(jnp.int32)), cap)
        if enc_kind == ENC_DIRECT_V2:
            lruns = RunTable.empty()
            lpacked = bytearray()
            lover = walk_rlev2(streams[LENGTH], nn, False, lruns,
                               lpacked)
            if lover is None:
                dev = _upload_runs(lruns, bytes(lpacked))
                lens64 = np.asarray(_expand_runs_be(
                    dev["runs_mat"], dev["packed"],
                    cap=bucket_rows(max(nn, 1))))[:nn]
            else:
                lens64 = lover[:nn]
            blob = np.frombuffer(streams.get(DATA, b""), dtype=np.uint8)
            max_len = _bucket_strlen(int(lens64.max()) if nn else 0)
            offs = np.concatenate([[0], np.cumsum(lens64)]).astype(
                np.int64)
            mat_np = np.zeros((max(nn, 1), max_len), dtype=np.uint8)
            colidx = np.arange(max_len)[None, :]
            src = offs[:nn, None] + colidx
            ok = colidx < lens64[:nn, None]
            mat_np[:nn][ok] = blob[src[ok]]
            mat = jnp.asarray(_pad_np(mat_np, vcap))
            lens = jnp.asarray(_pad_np(lens64[:nn].astype(np.int32),
                                       vcap))
            data, valid = def_scatter(mat)
            lens_s, _ = def_scatter(lens)
            return _to_cap(DeviceColumn(out_dtype, data, valid,
                                        lens_s.astype(jnp.int32)), cap)
        raise UnsupportedOrc(f"string encoding {enc_kind}")

    raise UnsupportedOrc(f"orc kind {kind}")


# ---------------------------------------------------------------------------
# Stripe-level API (decode_row_group twin)
# ---------------------------------------------------------------------------

def decode_stripe(path: str, stripe: int, schema: Schema,
                  columns: Optional[List[str]] = None,
                  raw: Optional[bytes] = None,
                  meta: Optional[OrcMeta] = None
                  ) -> Tuple[DeviceBatch, List[str]]:
    """Decode one ORC stripe to a DeviceBatch.

    Returns (batch, fallback_columns); fallback columns host-decode via
    Arrow so one exotic column doesn't knock the stripe off device.
    Pass ``meta`` (from ``read_meta``) to skip the O(stripes) redundant
    footer re-parse when decoding many stripes of one file."""
    if raw is None:
        with open(path, "rb") as f:
            raw = f.read()
    if meta is None:
        meta = read_meta(raw)
    wanted = columns or [f.name for f in schema.fields]
    # flat-schema guard: nested types shift ORC column ids (each subtree
    # claims a contiguous id range) — decoding by field position would
    # silently read the WRONG column's streams; whole stripe falls back
    if any(k in (K_LIST, K_MAP, K_STRUCT, K_UNION)
           for k in meta.kinds[1:]):
        import io as _io
        t = paorc.ORCFile(_io.BytesIO(raw)).read_stripe(
            stripe, columns=wanted)
        t = pa.Table.from_batches([t]) if not isinstance(t, pa.Table) \
            else t
        cast = pa.Table.from_arrays(
            [_cast_one(t.select([c]), schema.field(c)).column(0)
             for c in wanted], names=wanted)
        return from_arrow(cast), list(wanted)
    si = meta.stripes[stripe]
    streams, encodings = read_stripe_footer(raw, meta, si)
    n_rows = si.num_rows
    cap = bucket_rows(max(n_rows, 1))
    names = meta.field_names

    cols: List[DeviceColumn] = []
    out_names: List[str] = []
    fallbacks: List[str] = []
    orc_file = None
    for name in wanted:
        f = schema.field(name)
        if name not in names:
            npd = f.dtype.to_np() if not f.dtype.is_string else np.uint8
            if f.dtype.is_string:
                col = DeviceColumn(f.dtype,
                                   jnp.zeros((cap, 1), dtype=jnp.uint8),
                                   jnp.zeros((cap,), dtype=bool),
                                   jnp.zeros((cap,), dtype=jnp.int32))
            else:
                col = DeviceColumn(f.dtype,
                                   jnp.zeros((cap,), dtype=npd),
                                   jnp.zeros((cap,), dtype=bool))
            cols.append(col)
            out_names.append(name)
            continue
        # ORC column ids: 0 is the root struct; field i is column i+1
        cid = names.index(name) + 1
        try:
            kind = meta.kinds[cid]
            sdata: Dict[int, bytes] = {}
            for s in streams:
                if s.column == cid and s.kind in (PRESENT, DATA, LENGTH,
                                                  DICTIONARY_DATA):
                    sdata[s.kind] = _decompress(
                        meta, raw[s.offset:s.offset + s.length])
            col = decode_column(kind, encodings[cid], sdata, f.dtype,
                                n_rows, cap)
        except Exception:
            fallbacks.append(name)
            if orc_file is None:
                import io as _io
                orc_file = paorc.ORCFile(_io.BytesIO(raw))
            t = orc_file.read_stripe(stripe, columns=[name])
            t = pa.Table.from_batches([t]) if not isinstance(
                t, pa.Table) else t
            sub = from_arrow(_cast_one(t, f), capacity=cap)
            col = sub.columns[0]
        cols.append(col)
        out_names.append(name)
    return DeviceBatch(out_names, cols, n_rows), fallbacks


def _cast_one(t: pa.Table, f) -> pa.Table:
    col = t.column(0).cast(f.dtype.to_arrow())
    return pa.Table.from_arrays(
        [col], schema=pa.schema([pa.field(f.name, f.dtype.to_arrow(),
                                          f.nullable)]))


def num_stripes(path: str) -> int:
    return paorc.ORCFile(path).nstripes
