"""Fused multi-row-group parquet decode (io/parquet_fused.py) against
pyarrow golden (reference analog: the COALESCING reader's one
Table.readParquet per assembled buffer, GpuParquetScan.scala:824,1022)."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq

from spark_rapids_tpu.columnar.batch import to_arrow
from spark_rapids_tpu.io.parquet_fused import decode_row_groups_fused
from spark_rapids_tpu.plan.logical import Schema

from tests.parity import assert_tables_equal


def _write(tmp_path, name, table, **kw):
    p = str(tmp_path / name)
    papq.write_table(table, p, **kw)
    return p, papq.ParquetFile(p)


def _sources(*files):
    out = []
    for p, pf in files:
        for rg in range(pf.metadata.num_row_groups):
            out.append((pf, p, rg))
    return out


def test_fused_two_files_parity(tmp_path):
    rng = np.random.default_rng(0)
    t1 = pa.table({
        "k": pa.array(rng.integers(0, 40, 3000), pa.int64()),
        "v": pa.array(rng.normal(size=3000),
                      mask=rng.random(3000) < 0.2),
    })
    t2 = pa.table({
        "k": pa.array(rng.integers(0, 40, 1700), pa.int64()),
        "v": pa.array(rng.normal(size=1700),
                      mask=rng.random(1700) < 0.2),
    })
    f1 = _write(tmp_path, "a.parquet", t1, row_group_size=1024)
    f2 = _write(tmp_path, "b.parquet", t2, row_group_size=1024)
    schema = Schema.from_arrow(t1.schema)
    batch, fallbacks = decode_row_groups_fused(_sources(f1, f2), schema)
    assert fallbacks == []
    got = to_arrow(batch)
    expect = pa.concat_tables([t1, t2])
    assert_tables_equal(got, expect.cast(got.schema))


def test_fused_only_list_fallback_column(tmp_path):
    # schema is a single list column the device list path cannot decode
    # (PLAIN boolean list): the fallback merge must run even though no
    # non-list column ever executed the per-column planning loop
    t = pa.table({"l": pa.array([[True, False], None, [False]] * 100,
                                pa.list_(pa.bool_()))})
    f1 = _write(tmp_path, "l.parquet", t, use_dictionary=False)
    schema = Schema.from_arrow(t.schema)
    batch, fallbacks = decode_row_groups_fused(_sources(f1), schema)
    assert fallbacks == ["l"]
    got = to_arrow(batch)
    assert got.column("l").to_pylist() == t.column("l").to_pylist()


def test_fused_fallback_column_missing_from_one_file(tmp_path):
    # file A: "s" is PLAIN byte_array (device-unsupported -> fallback)
    # file B: has no "s" at all AND no other fallback column, so the
    # fallback merge hits the "present is empty" leg (the round-3
    # NameError: `md` was undefined there)
    t1 = pa.table({
        "x": pa.array(range(600), pa.int64()),
        "s": pa.array([f"v{i}" for i in range(600)]),
    })
    t2 = pa.table({"x": pa.array(range(600, 1000), pa.int64())})
    f1 = _write(tmp_path, "a.parquet", t1, use_dictionary=False)
    f2 = _write(tmp_path, "b.parquet", t2, use_dictionary=False)
    schema = Schema.from_arrow(t1.schema)
    batch, fallbacks = decode_row_groups_fused(_sources(f1, f2), schema)
    assert fallbacks == ["s"]
    got = to_arrow(batch)
    assert got.column("x").to_pylist() == list(range(1000))
    assert got.column("s").to_pylist() == \
        [f"v{i}" for i in range(600)] + [None] * 400


def test_assemble_keeps_a_streams_layout_with_its_cached_plan(tmp_path):
    """The per-run part of the fused plan is made once a cached chunk
    plan: a second scan of the same file finds every stream's layout
    on its run table, uploads the same arrays and decodes the same
    rows.  One stream here holds several bit widths (a dictionary that
    grows page after page), RLE stretches between bit-packed ones, and
    nulls."""
    from spark_rapids_tpu.io import parquet_fused as pqf
    from spark_rapids_tpu.io import scan_cache
    rng = np.random.default_rng(3)
    n = 40_000
    k = np.where((np.arange(n) // 500) % 2 == 0,
                 np.repeat(rng.integers(0, 5, n // 100), 100),
                 rng.integers(0, 1 + np.arange(n) // 40, n))
    t = pa.table({"k": pa.array(k.astype(np.int32),
                                mask=rng.random(n) < 0.05)})
    path, _ = _write(tmp_path, "w.parquet", t, data_page_size=4096,
                     row_group_size=n)
    schema = Schema.from_arrow(t.schema)
    scan_cache.configure(True, 256 << 20)
    scan_cache.clear()
    # the stamped handle a session's scan holds: its plans are cached
    src, dtypes = [(scan_cache.open_source(path), path, 0)], \
        [schema.field("k").dtype]
    plans, fallbacks, _ = pqf._collect_plans(src, schema, ["k"], 1)
    assert fallbacks == []
    first = pqf.assemble(plans, dtypes, ["k"], [n])
    lay = plans[0][0].val_runs.layout
    assert len({w for w, _, _ in lay.slices}) > 1
    assert (lay.c != 0).any() and (lay.c == 0).any()
    again, _, _ = pqf._collect_plans(src, schema, ["k"], 1)
    second = pqf.assemble(again, dtypes, ["k"], [n])
    assert again[0][0].val_runs.layout is lay
    assert first.key == second.key
    for name, arr in first.arrays.items():
        assert np.array_equal(arr, second.arrays[name]), name
    batch, fallbacks = decode_row_groups_fused(src, schema)
    assert fallbacks == []
    got = to_arrow(batch)
    assert_tables_equal(got, t.cast(got.schema))
