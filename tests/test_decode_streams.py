"""The parquet decode's stream primitives against oracles that are not
the engine: the fused decode's dense bit-unpack
(``parquet_fused._unpack_width``) and the per-column decoder's run
expansion (``device_parquet._expand_stream``) against numpy bit
arithmetic, and both decoders, file by file, against pyarrow (0-bit
all-same dictionaries, 1-bit, runs crossing page boundaries, null
validity, string dictionaries).

File-level widths are whatever pyarrow writes for the given cardinality
(bit width = ceil(log2(dict size))), so widths past the per-column
expansion's 4-byte window (``device_parquet._MAX_W`` = 24) are reached
at the stream level: there the expansion must refuse
(``UnsupportedChunk``) and the column must come back host-decoded and
correct."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import to_arrow
from spark_rapids_tpu.io import device_parquet as devpq
from spark_rapids_tpu.io import parquet_fused as pqf
from spark_rapids_tpu.io.device_parquet import RunTable, UnsupportedChunk
from spark_rapids_tpu.plan.logical import Schema

from tests.parity import assert_tables_equal


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _bitpack(values: np.ndarray, w: int) -> bytes:
    """Parquet LSB-first bit-pack (reference packer for synthetic
    streams; values padded to a multiple of 8)."""
    n = -(-len(values) // 8) * 8
    bits = np.zeros(n * max(w, 1), dtype=np.uint8)
    for i, v in enumerate(values):
        for b in range(w):
            bits[i * w + b] = (int(v) >> b) & 1
    return np.packbits(bits, bitorder="little").tobytes() if w else b""


def _mk_runs(segs, w: int):
    """RunTable from [('rle', count, value) | ('bp', values...)], its
    packed bytes and the values it encodes."""
    runs = RunTable.empty()
    packed = bytearray()
    expect = []
    for seg in segs:
        if seg[0] == "rle":
            _, c, v = seg
            runs.counts.append(c)
            runs.is_rle.append(True)
            runs.values.append(v)
            runs.bit_bases.append(0)
            runs.widths.append(w)
            expect.extend([v] * c)
        else:
            vals = np.asarray(seg[1])
            pad = (-len(vals)) % 8
            vals8 = np.concatenate([vals, np.zeros(pad, vals.dtype)])
            runs.counts.append(len(vals8))
            runs.is_rle.append(False)
            runs.values.append(0)
            runs.bit_bases.append(len(packed) * 8)
            runs.widths.append(w)
            packed += _bitpack(vals8, w)
            expect.extend(int(v) for v in vals8)
    return runs, bytes(packed), np.asarray(expect, dtype=np.uint64)


def _append(r0: RunTable, p0: bytes, r1: RunTable, p1: bytes):
    """``r1`` after ``r0`` as one stream over one packed buffer."""
    r0.counts += r1.counts
    r0.is_rle += r1.is_rle
    r0.values += r1.values
    r0.bit_bases += [b + len(p0) * 8 for b in r1.bit_bases]
    r0.widths += r1.widths
    return r0, p0 + p1


def _expand(runs, packed, cap) -> np.ndarray:
    return np.asarray(devpq._expand_stream(runs, packed, cap))


# ---------------------------------------------------------------------------
# the fused decode's phase 0: dense bit-unpack
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("w", [1, 2, 3, 5, 7, 8, 12, 15, 17, 20, 24,
                               25, 31, 32])
def test_unpack_width_all_widths(w):
    rng = np.random.default_rng(w)
    ncap = 2048
    raw = rng.integers(0, 256, ncap * w // 8).astype(np.uint8)
    got = np.asarray(pqf._unpack_width(jnp.asarray(raw), w, ncap))
    bits = np.unpackbits(raw, bitorder="little")[:ncap * w]
    ref = (bits.reshape(ncap, w).astype(np.uint64) <<
           np.arange(w, dtype=np.uint64)).sum(axis=1)
    assert np.array_equal(got.astype(np.uint64), ref)


# ---------------------------------------------------------------------------
# the per-column decoder's run expansion
# ---------------------------------------------------------------------------

def test_expand_stream_mixed_runs():
    rng = np.random.default_rng(5)
    vals = rng.integers(0, 1 << 11, 720)
    runs, packed, expect = _mk_runs(
        [("rle", 500, 7), ("bp", vals[:400]), ("rle", 123, 2000),
         ("bp", vals[400:]), ("rle", 9, 0)], w=11)
    total = runs.total
    got = _expand(runs, packed, 2048)
    assert np.array_equal(got[:total].astype(np.uint64), expect[:total])


def test_expand_stream_zero_bit_width():
    # 0-bit streams: a single-entry dictionary encodes every value in
    # zero bits (all-RLE or zero-width bit-pack groups)
    runs, packed, _ = _mk_runs(
        [("rle", 700, 0), ("bp", np.zeros(96, np.int64)),
         ("rle", 200, 0)], w=0)
    assert not _expand(runs, packed, 1024)[:runs.total].any()


def test_expand_stream_zero_then_wider_width():
    # a width-0 bit-packed run (1-entry dictionary page) FOLLOWED by a
    # wider page: the 0-bit run holds zero packed bytes, so reading it
    # through its bit base would alias the next run's values; it must
    # decode as constant 0
    rng = np.random.default_rng(8)
    vals = rng.integers(1, 8, 64)
    r0, p0, _ = _mk_runs([("bp", np.zeros(8, np.int64))], w=0)
    r1, p1, e1 = _mk_runs([("bp", vals)], w=3)
    runs, packed = _append(r0, p0, r1, p1)
    total = runs.total
    got = _expand(runs, packed, 128)
    assert not got[:8].any()
    assert np.array_equal(got[8:total].astype(np.uint64), e1[:total - 8])


def test_expand_stream_runs_across_many_packed_bytes():
    # two 16-bit bit-packed regions with an RLE run between them, 12,000
    # values: far past one window of the run matrix
    rng = np.random.default_rng(7)
    vals = rng.integers(0, 1 << 16, 12_000, dtype=np.uint64)
    runs, packed, expect = _mk_runs(
        [("bp", vals[:6000]), ("rle", 500, 40_000),
         ("bp", vals[6000:])], w=16)
    total = runs.total
    got = _expand(runs, packed, 1 << 14)
    assert np.array_equal(got[:total].astype(np.uint64), expect[:total])


def test_expand_stream_zero_bit_page_after_long_page():
    # the 0-bit page after 9,000 packed values, a wider page behind it
    rng = np.random.default_rng(9)
    head = rng.integers(1, 200, 9000, dtype=np.uint64)
    tail = rng.integers(1, 200, 64, dtype=np.uint64)
    r0, p0, e0 = _mk_runs([("bp", head)], w=8)
    rz, pz, _ = _mk_runs([("bp", np.zeros(8, np.int64))], w=0)
    r1, p1, e1 = _mk_runs([("bp", tail)], w=8)
    runs, packed = _append(*_append(r0, p0, rz, pz), r1, p1)
    total, n0 = runs.total, len(e0)
    got = _expand(runs, packed, 1 << 14)
    assert np.array_equal(got[:n0].astype(np.uint64), e0)
    assert not got[n0:n0 + 8].any()
    assert np.array_equal(got[n0 + 8:total].astype(np.uint64),
                          e1[:total - n0 - 8])


@pytest.mark.parametrize("w", [25, 31, 32])
def test_expand_stream_past_window_is_host_decoded(w, tmp_path,
                                                   monkeypatch):
    # past the 4-byte window (_MAX_W = 24) the expansion refuses ...
    rng = np.random.default_rng(w)
    vals = rng.integers(0, 1 << w, 512, dtype=np.uint64)
    runs, packed, _ = _mk_runs(
        [("bp", vals[:256]), ("rle", 100, (1 << w) - 5),
         ("bp", vals[256:])], w=w)
    with pytest.raises(UnsupportedChunk):
        devpq._expand_stream(runs, packed, 1024)
    # ... and the refusal costs the column its device decode, not its
    # values.  No file pyarrow writes here has an index that wide (it
    # would take a dictionary of 2^24 entries), so the window is drawn
    # in under this file's 10-bit index instead
    n = 3000
    t = pa.table({
        "a": pa.array(rng.integers(0, 1000, n), type=pa.int64()),
        "b": pa.array(rng.integers(0, 3, n).astype(np.int32))})
    path = str(tmp_path / "wide.parquet")
    papq.write_table(t, path, use_dictionary=["a", "b"])
    monkeypatch.setattr(devpq, "_MAX_W", 9)
    batch, fallbacks = devpq.decode_row_group(
        path, 0, Schema.from_arrow(t.schema))
    assert fallbacks == ["a"]
    assert_tables_equal(t, to_arrow(batch))


# ---------------------------------------------------------------------------
# both decoders, file by file, against pyarrow
# ---------------------------------------------------------------------------

def _decode_fused(path, schema):
    pf = papq.ParquetFile(path)
    return pqf.decode_row_groups_fused([(pf, path, 0)], schema)


def _decode_per_column(path, schema):
    return devpq.decode_row_group(path, 0, schema)


def _all_same_dictionary(rng):
    # single-entry dictionary: the narrowest possible index stream
    # (0 or 1 bit, whatever pyarrow writes), plus nulls
    n = 4000
    nulls = np.zeros(n, bool)
    nulls[100:200] = True
    t = pa.table({"a": pa.array(np.where(nulls, None, 42),
                                type=pa.int64())})
    return t, dict(use_dictionary=["a"])


def _one_bit_dictionary(rng):
    vals = rng.integers(0, 2, 5000) * 1000 + 5     # two distinct values
    return (pa.table({"a": pa.array(vals, type=pa.int64())}),
            dict(use_dictionary=["a"]))


def _runs_crossing_page_boundaries(rng):
    # tiny data pages force many pages per chunk: the hybrid stream's
    # runs (and their group-of-8 bit-pack padding) cross page
    # boundaries, with nulls interleaved
    n = 20000
    vals = rng.integers(0, 300, n)
    nulls = rng.random(n) < 0.15
    t = pa.table({
        "a": pa.array(np.where(nulls, None, vals), type=pa.int64()),
        "b": pa.array(rng.integers(0, 4, n).astype(np.int32))})
    return t, dict(use_dictionary=["a", "b"], data_page_size=2048)


def _null_validity_interaction(rng):
    # null-heavy and null-free columns side by side: def-level streams
    # (w=1) and index streams decode together
    n = 3000
    vals = rng.integers(0, 50, n)
    nulls = rng.random(n) < 0.6
    t = pa.table({
        "mostly_null": pa.array(np.where(nulls, None, vals),
                                type=pa.int64()),
        "no_null": pa.array(vals, type=pa.int64()),
        "f": pa.array(np.where(~nulls, None, rng.uniform(0, 1, n)))})
    return t, dict(use_dictionary=["mostly_null", "no_null"])


def _nulls_many_pages_wide_dictionary(rng):
    # null-heavy 10-bit and 6-bit dictionary columns over tiny pages
    n = 20000
    vals = rng.integers(0, 900, n)
    nulls = rng.random(n) < 0.2
    t = pa.table({
        "a": pa.array(np.where(nulls, None, vals), type=pa.int64()),
        "b": pa.array(rng.integers(0, 37, n).astype(np.int32))})
    return t, dict(use_dictionary=["a", "b"], data_page_size=2048)


def _string_dictionary(rng):
    n = 6000
    strs = np.array([f"name_{i:05d}" for i in range(300)])
    nulls = rng.random(n) < 0.1
    picks = strs[rng.integers(0, 300, n)]
    t = pa.table({
        "s": pa.array(np.where(nulls, None, picks), type=pa.string()),
        "k": pa.array(rng.integers(1, 30, n).astype(np.int64))})
    return t, dict(use_dictionary=["s", "k"], data_page_size=4096)


@pytest.mark.parametrize("decode", [_decode_fused, _decode_per_column],
                         ids=["fused", "per_column"])
@pytest.mark.parametrize("make", [
    _all_same_dictionary, _one_bit_dictionary,
    _runs_crossing_page_boundaries, _null_validity_interaction,
    _nulls_many_pages_wide_dictionary, _string_dictionary],
    ids=lambda f: f.__name__.lstrip("_"))
def test_decode_file_against_pyarrow(make, decode, tmp_path):
    table, write_kw = make(np.random.default_rng(6))
    path = str(tmp_path / "edge.parquet")
    papq.write_table(table, path, **write_kw)
    batch, fallbacks = decode(path, Schema.from_arrow(table.schema))
    assert fallbacks == []          # decoded on the device, every column
    got = to_arrow(batch)
    assert_tables_equal(table.cast(got.schema), got)
