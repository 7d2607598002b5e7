"""Pallas kernel backend (spark_rapids_tpu/kernels/): parity vs the
XLA paths and vs pyarrow, per-kernel fallback accounting, decode edge
widths (0-bit all-same dictionaries, 1-bit, exact 32-bit, runs
crossing page boundaries, null-validity interaction).

The XLA composed-array-op formulations are the correctness oracle
(the ``sql.fusion.enabled`` pattern); on CPU every Pallas kernel runs
under ``interpret=True``, so these tests execute the REAL kernel
bodies, not a skip.  File-level widths are whatever pyarrow writes for
the given cardinality (bit width = ceil(log2(dict size)), so a 32-bit
file-level width would need a >2^31-entry dictionary); the exact-32
and >24 widths are therefore exercised at the stream level with a
numpy reference, where the Pallas dense unpack EXTENDS device coverage
past the XLA window-gather cap (``device_parquet._MAX_W`` = 24)."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

import jax.numpy as jnp

from spark_rapids_tpu import dtypes as dt
from spark_rapids_tpu.columnar.batch import to_arrow
from spark_rapids_tpu.exec import scans
from spark_rapids_tpu.exec.tpu_aggregate import _group_ctx
from spark_rapids_tpu.expr.eval_tpu import ColVal
from spark_rapids_tpu.io import device_parquet as devpq
from spark_rapids_tpu.io.device_parquet import RunTable, UnsupportedChunk
from spark_rapids_tpu.kernels import backend as kb
from spark_rapids_tpu.kernels import decode as kdec
from spark_rapids_tpu.kernels import filter_decode as kfd
from spark_rapids_tpu.kernels import segreduce as kseg
from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.plan.logical import Schema

from tests.parity import assert_tables_equal


@pytest.fixture(autouse=True)
def _reset_backend_default():
    """Tests here flip the process default backend (via sessions and
    overrides); restore the process default ('pallas' since the PR 14
    flip) so later test MODULES that call decode helpers without
    creating a session aren't silently rerouted."""
    yield
    kb.set_default_backend(kb.PALLAS)


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
    """RunTable from [('rle', count, value) | ('bp', values...)]."""
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


def _expand_both(runs, packed, cap):
    with kb.backend_override("xla"):
        x = np.asarray(kdec.expand_stream(runs, packed, cap))
    with kb.backend_override("pallas"):
        p = np.asarray(kdec.expand_stream(runs, packed, cap))
    return x, p


# ---------------------------------------------------------------------------
# kernel 1: dense phase-decomposed RLE/bit-unpack
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("w", [1, 2, 3, 5, 7, 8, 12, 15, 17, 20, 24,
                               25, 31, 32])
def test_unpack_bits_parity_all_widths(w):
    rng = np.random.default_rng(w)
    ncap = 2048
    raw = rng.integers(0, 256, ncap * w // 8).astype(np.uint8)
    x = np.asarray(kdec._unpack_xla(jnp.asarray(raw), w, ncap))
    p = np.asarray(kdec._unpack_pallas(jnp.asarray(raw), w, ncap))
    assert np.array_equal(x, p)
    # golden vs numpy bit arithmetic
    bits = np.unpackbits(raw, bitorder="little")[:ncap * w]
    ref = (bits.reshape(ncap, w).astype(np.uint64) <<
           np.arange(w, dtype=np.uint64)).sum(axis=1)
    assert np.array_equal(x.astype(np.uint64), ref)


def test_expand_stream_parity_mixed_runs():
    rng = np.random.default_rng(5)
    vals = rng.integers(0, 1 << 11, 720)
    runs, packed, expect = _mk_runs(
        [("rle", 500, 7), ("bp", vals[:400]), ("rle", 123, 2000),
         ("bp", vals[400:]), ("rle", 9, 0)], w=11)
    total = runs.total
    x, p = _expand_both(runs, packed, 2048)
    assert np.array_equal(x[:total], p[:total])
    assert np.array_equal(x[:total].astype(np.uint64), expect[:total])


def test_expand_stream_zero_bit_width():
    # 0-bit streams: a single-entry dictionary encodes every value in
    # zero bits (all-RLE or zero-width bit-pack groups)
    runs, packed, expect = _mk_runs(
        [("rle", 700, 0), ("bp", np.zeros(96, np.int64)),
         ("rle", 200, 0)], w=0)
    total = runs.total
    x, p = _expand_both(runs, packed, 1024)
    assert np.array_equal(x[:total], p[:total])
    assert not x[:total].any()


def test_expand_stream_zero_then_wider_width():
    # regression (review repro): a width-0 bit-packed run (1-entry
    # dictionary page) FOLLOWED by a wider page — the 0-bit run holds
    # zero packed bytes, so mapping it through bit_base//w would alias
    # the next run's values; it must decode as constant 0 on both
    # backends, still on the pallas path (no fallback needed)
    rng = np.random.default_rng(8)
    vals = rng.integers(1, 8, 64)
    r0, p0, _ = _mk_runs([("bp", np.zeros(8, np.int64))], w=0)
    r1, p1, e1 = _mk_runs([("bp", vals)], w=3)
    r0.counts += r1.counts
    r0.is_rle += r1.is_rle
    r0.values += r1.values
    r0.bit_bases += [b + len(p0) * 8 for b in r1.bit_bases]
    r0.widths += r1.widths
    packed = p0 + p1
    total = r0.total
    view = obsreg.get_registry().view()
    x, p = _expand_both(r0, packed, 128)
    assert np.array_equal(x[:total], p[:total])
    assert not p[:8].any()
    assert np.array_equal(p[8:total].astype(np.uint64), e1[:total - 8])
    d = view.delta()["counters"]
    assert d.get("kernel.backend.pallas.hits.decode.expand", 0) >= 1, d


def test_expand_stream_exact_32_bit_extends_coverage():
    # w=32: past the XLA window-gather cap (_MAX_W=24) — the XLA path
    # must keep its historical behavior (UnsupportedChunk -> the
    # caller's per-column host fallback) while pallas stays on device;
    # the numpy reference pins correctness
    rng = np.random.default_rng(32)
    vals = rng.integers(0, 1 << 32, 512, dtype=np.uint64)
    runs, packed, expect = _mk_runs(
        [("bp", vals[:256]), ("rle", 100, (1 << 32) - 5),
         ("bp", vals[256:])], w=32)
    total = runs.total
    with kb.backend_override("pallas"):
        p = np.asarray(kdec.expand_stream(runs, packed, 1024))
    assert np.array_equal(p[:total].astype(np.uint64), expect[:total])
    with kb.backend_override("xla"):
        with pytest.raises(UnsupportedChunk):
            kdec.expand_stream(runs, packed, 1024)


@pytest.mark.parametrize("w", [25, 31])
def test_expand_stream_wide_widths_pallas_only(w):
    rng = np.random.default_rng(w)
    vals = rng.integers(0, 1 << w, 384, dtype=np.uint64)
    runs, packed, expect = _mk_runs([("bp", vals)], w=w)
    total = runs.total
    with kb.backend_override("pallas"):
        p = np.asarray(kdec.expand_stream(runs, packed, 512))
    assert np.array_equal(p[:total].astype(np.uint64), expect[:total])


def test_expand_stream_mixed_width_fallback_reason():
    # two BIT-PACKED widths in one stream: outside the single-width
    # dense unpack — must fall back PER KERNEL with a tagged reason
    # and still be bit-identical to the XLA result (RLE-run widths are
    # irrelevant: only bit-packed regions carry a width)
    r1, p1, _ = _mk_runs([("bp", np.arange(64) % 8)], w=3)
    runs, packed, _ = _mk_runs([("bp", np.arange(32) % 16)], w=5)
    runs.counts = r1.counts + runs.counts
    runs.is_rle = r1.is_rle + runs.is_rle
    runs.values = r1.values + runs.values
    runs.bit_bases = r1.bit_bases + \
        [b + len(p1) * 8 for b in runs.bit_bases]
    runs.widths = r1.widths + runs.widths
    packed = p1 + packed
    total = runs.total
    view = obsreg.get_registry().view()
    x, p = _expand_both(runs, packed, 128)
    assert np.array_equal(x[:total], p[:total])
    d = view.delta()["counters"]
    assert d.get(
        "kernel.backend.pallas.fallbacks.decode.expand.mixed_widths",
        0) >= 1, d
    assert d.get("kernel.backend.pallas.fallbacks", 0) >= 1


# ---------------------------------------------------------------------------
# kernel 3: single-pass segmented reduction
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cap,np_t,op,ident", [
    (1024, np.float64, "add", 0.0),
    (1 << 17, np.float64, "add", 0.0),      # blocked carry path
    (1024, np.int64, "min", np.iinfo(np.int64).max),
    (1 << 17, np.int64, "max", np.iinfo(np.int64).min),
    (1024, np.int32, "add", 0),
    (1 << 17, np.uint64, "min", np.iinfo(np.uint64).max),
])
def test_seg_scan_sorted_parity(cap, np_t, op, ident):
    rng = np.random.default_rng(cap % 97)
    flags = np.zeros(cap, bool)
    flags[rng.integers(0, cap, 40)] = True
    flags[0] = True
    if np.dtype(np_t).kind == "f":
        vals = rng.uniform(-1e6, 1e6, cap).astype(np_t)
    else:
        vals = rng.integers(0, 1000, cap).astype(np_t)
    ref = np.asarray(scans.seg_scan(
        kseg._OPS[op], jnp.asarray(flags), jnp.asarray(vals), ident))
    got = np.asarray(kseg.seg_scan_sorted(
        jnp.asarray(flags), jnp.asarray(vals), op, ident))
    assert np.array_equal(ref, got)     # bit-identical incl. floats


def test_gather_seg_scan_fuses_take_sorted():
    rng = np.random.default_rng(3)
    cap = 1 << 16
    order = rng.permutation(cap).astype(np.int32)
    flags = np.zeros(cap, bool)
    flags[0] = True
    flags[rng.integers(0, cap, 25)] = True
    vals = rng.uniform(-10, 10, cap)
    ref = np.asarray(scans.seg_scan(
        jnp.add, jnp.asarray(flags),
        jnp.take(jnp.asarray(vals), jnp.asarray(order)), 0.0))
    got = np.asarray(kseg.gather_seg_scan(
        jnp.asarray(vals), jnp.asarray(order), jnp.asarray(flags),
        "add", 0.0))
    assert np.array_equal(ref, got)


def test_sorted_ctx_backend_parity_all_reductions():
    rng = np.random.default_rng(17)
    cap, n = 4096, 3700
    keys = np.zeros(cap, np.int64)
    keys[:n] = rng.integers(0, 23, n)
    fvals = np.where(np.arange(cap) < n,
                     rng.uniform(-1e5, 1e5, cap), 0.0)
    ivals = np.where(np.arange(cap) < n,
                     rng.integers(-500, 500, cap), 0).astype(np.int64)
    kv = ColVal(dt.INT64, jnp.asarray(keys), jnp.ones(cap, bool), None)
    f = jnp.asarray(fvals)
    iv = jnp.asarray(ivals)
    mask = jnp.arange(cap) < n
    sub = mask & (iv % 3 == 0)

    def run(backend):
        ctx = _group_ctx([kv], cap, n, backend=backend)
        ng = int(ctx.n_groups)
        # compare the REAL groups only: slots past n_groups hold
        # formulation-dependent garbage on both backends, masked by
        # group_exists before anything leaves the aggregate
        # (_append_buffers)
        return [np.asarray(a)[:ng] for a in (
            ctx.seg_sum(f, mask, out_np=np.float64),
            ctx.seg_sum(iv, mask, out_np=np.int64),
            ctx.seg_sum(iv, mask, out_np=np.int64, narrow_bits=10),
            ctx.seg_count(mask),
            ctx.seg_count(sub),
            ctx.seg_min_of(f, mask, np.inf),
            ctx.seg_max_of(iv, mask, np.iinfo(np.int64).min),
        )]

    for a, b in zip(run("xla"), run("pallas")):
        assert np.array_equal(a, b)


def test_segreduce_string_and_firstlast_parity():
    # string MIN (word-wise u64 tie-break through seg_scan_reduce) and
    # first/last (index-min/max picks with traced identities) ride the
    # pallas seg kernels too — full parity against the xla session
    import pandas as pd
    df = pd.DataFrame({
        "k": [i % 5 for i in range(400)],
        "s": [f"v{i % 17:03d}" for i in range(400)],
        "x": [float(i % 50) for i in range(400)]})

    def run(backend):
        from spark_rapids_tpu import TpuSparkSession, functions as F
        s = TpuSparkSession({
            "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
            "spark.rapids.tpu.kernel.backend": backend})
        view = obsreg.get_registry().view()
        out = (s.create_dataframe(df).group_by("k")
               .agg(F.min("s").alias("ms"), F.sum("x").alias("sx"),
                    F.first("s").alias("fs"),
                    F.count("*").alias("c"))
               .sort("k")).collect()
        return out, view.delta()["counters"]

    xla_t, _ = run("xla")
    pal_t, d = run("pallas")
    assert xla_t.equals(pal_t)
    assert d.get("kernel.backend.pallas.hits.agg.segreduce", 0) > 0


def test_segreduce_supported_gates():
    # the fallback matrix's per-kernel reasons (docs/kernels.md)
    ok, _ = kseg.supported(1024, np.float64, "add")
    assert ok
    assert kseg.supported(1024, np.float64, None)[1] == "op"
    assert kseg.supported(1024, np.uint8, "add", ndim=2)[1] == "ndim"
    # any cap at or under one block is a single scan; off-grid caps
    # only matter past the block size
    assert kseg.supported(1000, np.float64, "add")[0]
    assert kseg.supported(kseg._BLOCK + 8, np.float64,
                          "add")[1] == "shape"
    assert kseg.supported(1024, np.complex128, "add")[1] == "dtype"
    assert kseg.op_name(jnp.add) == "add"
    assert kseg.op_name(jnp.minimum) == "min"
    assert kseg.op_name(max) is None


# ---------------------------------------------------------------------------
# kernel 2: fused dictionary-decode + filter
# ---------------------------------------------------------------------------

def test_dict_filter_decode_unit_parity():
    rng = np.random.default_rng(9)
    cap = 4096
    dbuf = jnp.asarray(rng.integers(-1000, 1000, 512).astype(np.int64))
    codes = jnp.asarray(rng.integers(0, 512, cap).astype(np.int32))
    keep_np = rng.random(cap) < 0.25
    keep_np[1024:2048] = False          # a fully-dropped block
    keep = jnp.asarray(keep_np)
    x = np.asarray(kfd.decode_xla(dbuf, codes, keep))
    p = np.asarray(kfd.decode_pallas(dbuf, codes, keep))
    assert np.array_equal(x, p)
    # filtered-out rows never materialize decoded values
    assert not x[~keep_np].any()
    assert np.array_equal(
        x[keep_np], np.asarray(dbuf)[np.asarray(codes)[keep_np]])


def test_scan_filter_pushdown_defers_dict_gather(tmp_path):
    rng = np.random.default_rng(21)
    n = 6000
    t = pa.table({
        "k": pa.array(rng.integers(1, 30, n).astype(np.int64)),
        "q": pa.array(rng.integers(1, 90, n).astype(np.int32)),
        "p": np.round(rng.uniform(0.0, 100.0, n), 2)})
    papq.write_table(t, str(tmp_path / "t.parquet"),
                     use_dictionary=["k", "q"], data_page_size=8192)

    def run(backend):
        from spark_rapids_tpu import TpuSparkSession, col, functions as F
        s = TpuSparkSession({
            "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
            "spark.rapids.tpu.kernel.backend": backend})
        view = obsreg.get_registry().view()
        out = (s.read.parquet(str(tmp_path))
               .filter(col("p") > 75.0)
               .group_by("k")
               .agg(F.sum("q").alias("sq"), F.count("*").alias("c"))
               .sort("k")).collect()
        return out, view.delta()["counters"]

    xla_t, _ = run("xla")
    pal_t, d = run("pallas")
    assert xla_t.equals(pal_t)
    # the pushed filter armed the deferred dictionary decode
    assert d.get("kernel.backend.pallas.hits.scan.filterDecode", 0) \
        >= 1, d
    # pyarrow oracle
    import pyarrow.compute as pc
    flt = t.filter(pc.greater(t.column("p"), 75.0))
    ref = flt.group_by("k").aggregate(
        [("q", "sum"), ("k", "count")]).sort_by("k")
    assert np.array_equal(np.asarray(pal_t.column("k")),
                          np.asarray(ref.column("k")))
    assert np.array_equal(np.asarray(pal_t.column("sq")),
                          np.asarray(ref.column("q_sum")))


def test_pushdown_skipped_when_condition_reads_dict_column(tmp_path):
    # a condition over the dictionary column itself cannot defer that
    # column (its values feed the mask) — the fallback reason is
    # tagged, and results still match the xla path
    rng = np.random.default_rng(4)
    n = 3000
    t = pa.table({"k": pa.array(rng.integers(1, 20, n).astype(
        np.int64))})
    papq.write_table(t, str(tmp_path / "t.parquet"),
                     use_dictionary=["k"])

    def run(backend):
        from spark_rapids_tpu import TpuSparkSession, col, functions as F
        s = TpuSparkSession({
            "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
            "spark.rapids.tpu.kernel.backend": backend})
        view = obsreg.get_registry().view()
        out = (s.read.parquet(str(tmp_path))
               .filter(col("k") > 10)
               .group_by("k").agg(F.count("*").alias("c"))
               .sort("k")).collect()
        return out, view.delta()["counters"]

    xla_t, _ = run("xla")
    pal_t, d = run("pallas")
    assert xla_t.equals(pal_t)
    assert d.get("kernel.backend.pallas.fallbacks.scan.filterDecode."
                 "condition_column", 0) >= 1 or \
        d.get("kernel.backend.pallas.fallbacks.scan.filterDecode."
              "no_dict_columns", 0) >= 1, d


# ---------------------------------------------------------------------------
# file-level decode edge widths (parity pallas vs xla vs pyarrow)
# ---------------------------------------------------------------------------

def _decode_file_both(tmp_path, table: pa.Table, **write_kw):
    path = str(tmp_path / "edge.parquet")
    papq.write_table(table, path, **write_kw)
    schema = Schema.from_arrow(table.schema)
    out = {}
    for backend in ("xla", "pallas"):
        batch, _fb = devpq.decode_row_group(path, 0, schema,
                                            backend=backend)
        out[backend] = to_arrow(batch)
    assert out["xla"].equals(out["pallas"])     # backend parity
    assert_tables_equal(out["pallas"],
                        table.cast(out["pallas"].schema))  # pyarrow
    return out["pallas"]


def test_decode_all_same_dictionary(tmp_path):
    # single-entry dictionary: the narrowest possible index stream
    # (0 or 1 bit, whatever pyarrow writes), plus nulls
    n = 4000
    vals = np.full(n, 42, np.int64)
    nulls = np.zeros(n, bool)
    nulls[100:200] = True
    t = pa.table({"a": pa.array(np.where(nulls, None, vals),
                                type=pa.int64())})
    _decode_file_both(tmp_path, t, use_dictionary=["a"])


def test_decode_one_bit_dictionary(tmp_path):
    n = 5000
    rng = np.random.default_rng(1)
    vals = rng.integers(0, 2, n) * 1000 + 5     # two distinct values
    t = pa.table({"a": pa.array(vals, type=pa.int64())})
    _decode_file_both(tmp_path, t, use_dictionary=["a"])


def test_decode_runs_crossing_page_boundaries(tmp_path):
    # tiny data pages force many pages per chunk: the hybrid stream's
    # runs (and their group-of-8 bit-pack padding) cross page
    # boundaries, with nulls interleaved
    n = 20000
    rng = np.random.default_rng(2)
    vals = rng.integers(0, 300, n)
    nulls = rng.random(n) < 0.15
    t = pa.table({
        "a": pa.array(np.where(nulls, None, vals), type=pa.int64()),
        "b": pa.array(rng.integers(0, 4, n).astype(np.int32)),
    })
    _decode_file_both(tmp_path, t, use_dictionary=["a", "b"],
                      data_page_size=2048)


def test_decode_null_validity_interaction(tmp_path):
    # null-heavy and null-free columns side by side: def-level streams
    # (w=1) and index streams take the pallas path together
    n = 3000
    rng = np.random.default_rng(6)
    vals = rng.integers(0, 50, n)
    nulls = rng.random(n) < 0.6
    t = pa.table({
        "mostly_null": pa.array(np.where(nulls, None, vals),
                                type=pa.int64()),
        "no_null": pa.array(vals, type=pa.int64()),
        "f": pa.array(np.where(~nulls, None,
                               rng.uniform(0, 1, n))),
    })
    _decode_file_both(tmp_path, t, use_dictionary=["mostly_null",
                                                   "no_null"])


# ---------------------------------------------------------------------------
# backend plumbing
# ---------------------------------------------------------------------------

def test_backend_knob_configures_process_default():
    from spark_rapids_tpu import TpuSparkSession
    TpuSparkSession({"spark.rapids.tpu.kernel.backend": "pallas"})
    assert kb.default_backend() == "pallas"
    # a session WITHOUT the knob re-asserts the default — XLA, the
    # path every program of which compiles for the chip
    # (tests/test_chip_compile.py; the scan_cache.configure idiom: no
    # leakage into later sessions)
    TpuSparkSession({})
    assert kb.default_backend() == "xla"
    with pytest.raises(ValueError):
        TpuSparkSession({"spark.rapids.tpu.kernel.backend": "vulkan"})
    with pytest.raises(ValueError):
        TpuSparkSession({"spark.rapids.tpu.kernel.pallas.tileBytes": 1})


def test_plan_stamp_wins_over_process_default(tmp_path):
    # two live sessions with different kernel.backend: each plan
    # carries its own stamp, so the later session's default cannot
    # flip the earlier session's kernels (the donation-stamp lesson)
    from spark_rapids_tpu import TpuSparkSession, functions as F
    import pandas as pd
    df = pd.DataFrame({"k": [1, 2, 1, 2, 3], "x": [1.0] * 5})
    s_pallas = TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        "spark.rapids.tpu.kernel.backend": "pallas"})
    q = (s_pallas.create_dataframe(df).group_by("k")
         .agg(F.sum("x").alias("sx")).sort("k"))
    TpuSparkSession({})           # resets the process default to xla
    view = obsreg.get_registry().view()
    out = q.collect()
    d = view.delta()["counters"]
    assert d.get("kernel.dispatches.agg_update.pallas", 0) >= 1, d
    assert out.num_rows == 3


def test_per_family_dispatch_backend_tagging():
    from spark_rapids_tpu import TpuSparkSession, functions as F
    import pandas as pd
    df = pd.DataFrame({"k": [i % 3 for i in range(64)],
                       "x": [float(i) for i in range(64)]})
    s = TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        "spark.rapids.tpu.kernel.backend": "pallas"})
    view = obsreg.get_registry().view()
    s.create_dataframe(df).group_by("k").agg(
        F.sum("x").alias("sx")).collect()
    d = view.delta()["counters"]
    assert d.get("kernel.dispatches.agg_update", 0) >= 1
    assert d.get("kernel.dispatches.agg_update.pallas", 0) >= 1
    # the untagged total and the tagged variant agree
    assert d["kernel.dispatches.agg_update.pallas"] <= \
        d["kernel.dispatches.agg_update"]


def test_profile_surfaces_kernel_section():
    from spark_rapids_tpu import TpuSparkSession, functions as F
    import pandas as pd
    s = TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        "spark.rapids.tpu.kernel.backend": "pallas"})
    df = pd.DataFrame({"k": [1, 2, 1], "x": [1.0, 2.0, 3.0]})
    s.create_dataframe(df).group_by("k").agg(
        F.sum("x").alias("sx")).collect()
    prof = s.last_query_profile()
    assert "kernel" in prof.metrics       # always-present section
    ker = prof.metrics["kernel"]
    assert any(k.startswith("kernel.dispatches.agg_update")
               for k in ker), ker
    assert any(k.endswith(".pallas") for k in ker), ker
