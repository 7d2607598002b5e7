"""Scan-plan cache (io/scan_cache.py) + pipelined host prep.

Covers the ISSUE-2 acceptance contract: warm scans perform ZERO
page-header walks (walk-counter probe + planCacheHits metric),
mtime/size invalidation forces a fresh walk with correct results, LRU
byte-budget eviction, thread safety under concurrent partition
iterators, and byte-identical results cached-vs-uncached and
prefetch-on-vs-off over fixtures with dict-encoded strings, nullable
columns and multi-row-group files.
"""

import concurrent.futures as cf
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

from spark_rapids_tpu.columnar.batch import to_arrow
from spark_rapids_tpu.exec.base import Metrics
from spark_rapids_tpu.io import parquet_meta as pm
from spark_rapids_tpu.io import scan_cache as sc
from spark_rapids_tpu.io.device_parquet import decode_row_group
from spark_rapids_tpu.io.parquet_fused import decode_row_groups_fused
from spark_rapids_tpu.plan.logical import Schema

from tests.parity import assert_tables_equal


@pytest.fixture(autouse=True)
def _fresh_cache():
    sc.configure(True, 256 << 20)
    sc.clear()
    yield
    sc.configure(True, 256 << 20)
    sc.clear()


def _table(n=3000, seed=0):
    """Dict-encoded strings + nullable float/int + int keys."""
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": pa.array(rng.integers(0, 50, n), pa.int64()),
        "v": pa.array(rng.normal(size=n), mask=rng.random(n) < 0.2),
        "s": pa.array([f"name_{i % 17}" for i in range(n)]),
        "q": pa.array(rng.integers(0, 100, n).astype(np.int32),
                      mask=rng.random(n) < 0.1),
    })


def _write(tmp_path, name, table, **kw):
    p = str(tmp_path / name)
    papq.write_table(table, p, **kw)
    return p


def _sources(*paths):
    # footer handles the way the engine opens them: the plan-cache key
    # is pinned to the stamp the footer was parsed under (handle_key)
    out = []
    for p in paths:
        f = sc.get_footer(p)
        for rg in range(f.metadata.num_row_groups):
            out.append((f, p, rg))
    return out


def test_warm_fused_scan_zero_walks_and_hit_accounting(tmp_path):
    t = _table()
    p = _write(tmp_path, "a.parquet", t, row_group_size=1024)
    schema = Schema.from_arrow(t.schema)
    srcs = _sources(p)
    assert len(srcs) >= 3  # multi-row-group fixture

    m1 = Metrics()
    b1, fb1 = decode_row_groups_fused(srcs, schema, metrics=m1)
    assert fb1 == []
    misses = m1.extra.get("scan.planCacheMisses", 0)
    assert misses == len(srcs) * len(t.column_names)
    assert m1.extra.get("scan.planCacheHits", 0) == 0
    walks = pm.walk_count()

    m2 = Metrics()
    b2, fb2 = decode_row_groups_fused(srcs, schema, metrics=m2)
    assert fb2 == []
    # acceptance: second pass performs ZERO page-header walks and is
    # served entirely from the plan cache
    assert pm.walk_count() == walks
    assert m2.extra.get("scan.planCacheHits", 0) == misses
    assert m2.extra.get("scan.planCacheMisses", 0) == 0
    assert_tables_equal(to_arrow(b2), to_arrow(b1))


def test_cached_vs_uncached_parity(tmp_path):
    t1 = _table(seed=1)
    t2 = _table(n=1700, seed=2)
    p1 = _write(tmp_path, "a.parquet", t1, row_group_size=1024)
    p2 = _write(tmp_path, "b.parquet", t2, row_group_size=1024)
    schema = Schema.from_arrow(t1.schema)
    srcs = _sources(p1, p2)

    sc.configure(False, 256 << 20)  # uncached oracle
    cold, _ = decode_row_groups_fused(srcs, schema)
    sc.configure(True, 256 << 20)
    decode_row_groups_fused(srcs, schema)          # populate
    warm, _ = decode_row_groups_fused(srcs, schema)  # served from cache
    assert_tables_equal(to_arrow(warm), to_arrow(cold))
    expect = pa.concat_tables([t1, t2])
    got = to_arrow(warm)
    assert_tables_equal(got, expect.cast(got.schema))


def test_invalidation_on_overwrite(tmp_path):
    t_old = _table(seed=3)
    p = _write(tmp_path, "a.parquet", t_old, row_group_size=1024)
    schema = Schema.from_arrow(t_old.schema)
    b_old, _ = decode_row_groups_fused(_sources(p), schema)
    assert to_arrow(b_old).num_rows == t_old.num_rows

    t_new = _table(n=2100, seed=4)
    papq.write_table(t_new, p, row_group_size=1024)
    # force a visibly different stamp even on coarse-mtime filesystems
    st = os.stat(p)
    os.utime(p, ns=(st.st_atime_ns, st.st_mtime_ns + 1_000_000))

    walks = pm.walk_count()
    m = Metrics()
    b_new, _ = decode_row_groups_fused(_sources(p), schema, metrics=m)
    assert pm.walk_count() > walks          # fresh walk, not stale plans
    assert m.extra.get("scan.planCacheHits", 0) == 0
    got = to_arrow(b_new)
    assert_tables_equal(got, t_new.cast(got.schema))
    assert sc.stats()["invalidations"] >= 1


@pytest.mark.perf
def test_lru_byte_budget_eviction(tmp_path):
    t = _table()
    paths = [_write(tmp_path, f"f{i}.parquet", _table(seed=10 + i),
                    row_group_size=1024) for i in range(3)]
    schema = Schema.from_arrow(t.schema)

    # size one file's entry, then budget for ~1.5 entries
    decode_row_groups_fused(_sources(paths[0]), schema)
    one_entry = sc.stats()["bytes"]
    assert one_entry > 0
    sc.clear()
    sc.configure(True, int(one_entry * 1.5))

    decode_row_groups_fused(_sources(paths[0]), schema)
    decode_row_groups_fused(_sources(paths[1]), schema)  # evicts f0
    assert sc.stats()["evictions"] >= 1
    assert sc.stats()["bytes"] <= int(one_entry * 1.5)

    walks = pm.walk_count()
    m = Metrics()
    b, _ = decode_row_groups_fused(_sources(paths[0]), schema,
                                   metrics=m)
    assert pm.walk_count() > walks          # f0 was evicted: re-walked
    got = to_arrow(b)
    assert_tables_equal(got, _table(seed=10).cast(got.schema))


def test_thread_safety_concurrent_iterators(tmp_path):
    tables = [_table(n=1500, seed=20 + i) for i in range(4)]
    paths = [_write(tmp_path, f"f{i}.parquet", t, row_group_size=512)
             for i, t in enumerate(tables)]
    schema = Schema.from_arrow(tables[0].schema)

    def one(i):
        # every worker hammers every file, half warm, half cold
        out = []
        for j, p in enumerate(paths):
            b, fb = decode_row_groups_fused(_sources(p), schema,
                                            host_threads=2)
            assert fb == []
            out.append(to_arrow(b))
        return out

    with cf.ThreadPoolExecutor(max_workers=4) as pool:
        results = list(pool.map(one, range(4)))
    for got_list in results:
        for got, expect in zip(got_list, tables):
            assert_tables_equal(got, expect.cast(got.schema))


def test_blob_plan_cache_roundtrip():
    import io as _io
    t = _table(n=800, seed=5)
    buf = _io.BytesIO()
    papq.write_table(t, buf, row_group_size=400)
    blob = buf.getvalue()
    schema = Schema.from_arrow(t.schema)
    skey = sc.blob_key(blob)

    pf = sc.blob_footer(blob)
    outs = []
    for rg in range(pf.metadata.num_row_groups):
        b, _ = decode_row_group(blob, rg, schema, parquet_file=pf,
                                source_key=skey)
        outs.append(to_arrow(b))
    walks = pm.walk_count()
    outs2 = []
    for rg in range(pf.metadata.num_row_groups):
        b, _ = decode_row_group(blob, rg, schema, parquet_file=pf,
                                source_key=skey)
        outs2.append(to_arrow(b))
    assert pm.walk_count() == walks   # blob plans cached by content key
    got = pa.concat_tables(outs2)
    assert_tables_equal(got, t.cast(got.schema))
    assert_tables_equal(got, pa.concat_tables(outs))


def test_prefetch_on_vs_off_collect_parity(tmp_path):
    from spark_rapids_tpu import TpuSparkSession
    tables = [_table(n=1200, seed=30 + i) for i in range(4)]
    for i, t in enumerate(tables):
        _write(tmp_path, f"part-{i:02d}.parquet", t,
               row_group_size=512)
    root = str(tmp_path)
    base = {
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        # small reader batches force several fused groups so the
        # prefetch window actually pipelines
        "spark.rapids.tpu.sql.reader.batchSizeRows": 1024,
    }

    s_off = TpuSparkSession(dict(
        base, **{"spark.rapids.tpu.sql.scan.prefetch.depth": 0,
                 "spark.rapids.tpu.sql.scan.hostPrep.threads": 1}))
    t_off = s_off.read.parquet(root).collect()

    captured = []
    s_on = TpuSparkSession(dict(
        base, **{"spark.rapids.tpu.sql.scan.prefetch.depth": 3,
                 "spark.rapids.tpu.sql.scan.hostPrep.threads": 4}))
    s_on.add_plan_listener(lambda r: captured.append(r.plan))
    t_on = s_on.read.parquet(root).collect()

    assert_tables_equal(t_on, t_off)

    # per-scan metrics stamped into Metrics.extra
    scans = []
    captured[-1].foreach(
        lambda p: scans.append(p)
        if type(p).__name__ == "TpuParquetScanExec" else None)
    assert scans
    extra = scans[0].metrics.extra
    assert "scan.hostPrepTime" in extra
    assert "scan.uploadTime" in extra
    assert extra.get("scan.planCacheMisses", 0) + \
        extra.get("scan.planCacheHits", 0) > 0


def test_stale_footer_never_poisons_new_stamp(tmp_path):
    """A file rewritten mid-scan must not cache plans derived through
    the STALE footer under the new (mtime, size) key: handle_key pins
    the stamp captured at footer-parse time."""
    t_old = _table(seed=7)
    p = _write(tmp_path, "a.parquet", t_old, row_group_size=1024)
    f_old = sc.get_footer(p)
    old_key = f_old.cache_key
    assert old_key is not None

    t_new = _table(n=2400, seed=8)
    papq.write_table(t_new, p, row_group_size=1024)
    st = os.stat(p)
    os.utime(p, ns=(st.st_atime_ns, st.st_mtime_ns + 1_000_000))

    # plans walked through the stale handle key under the OLD stamp
    assert sc.handle_key(f_old, p) == old_key
    assert sc.handle_key(f_old, p) != sc.file_key(p)

    # a fresh scan (new footer) must see a cold cache for the new
    # stamp and decode the NEW contents correctly
    m = Metrics()
    b, _ = decode_row_groups_fused(_sources(p),
                                   Schema.from_arrow(t_new.schema),
                                   metrics=m)
    assert m.extra.get("scan.planCacheHits", 0) == 0
    got = to_arrow(b)
    assert_tables_equal(got, t_new.cast(got.schema))


def test_unsupported_chunk_negative_cache(tmp_path):
    """Warm scans of a device-unsupported column (PLAIN byte_array)
    replay the cached UnsupportedChunk verdict instead of re-walking,
    and still produce correct host-fallback results."""
    t = pa.table({
        "x": pa.array(range(500), pa.int64()),
        "s": pa.array([f"v{i}" for i in range(500)]),
    })
    p = _write(tmp_path, "a.parquet", t, use_dictionary=False)
    schema = Schema.from_arrow(t.schema)
    b1, fb1 = decode_row_groups_fused(_sources(p), schema)
    assert fb1 == ["s"]
    walks = pm.walk_count()
    b2, fb2 = decode_row_groups_fused(_sources(p), schema)
    assert fb2 == ["s"]
    assert pm.walk_count() == walks    # verdict served from cache
    got = to_arrow(b2)
    assert_tables_equal(got, t.cast(got.schema))


def test_footer_dedup_schema_inference_then_scan(tmp_path):
    """infer_schema and the scan share ONE footer parse per file."""
    t = _table(n=600, seed=6)
    p = _write(tmp_path, "a.parquet", t)
    h0 = sc.stats()["hits"]
    from spark_rapids_tpu.io.readers import infer_schema
    infer_schema("parquet", [p])           # parses + caches the footer
    f = sc.get_footer(p)                   # scan-side lookup: a hit
    assert sc.stats()["hits"] > h0
    assert f.schema_arrow.names == t.schema.names


def test_assembled_set_uploaded_once_and_dropped_with_its_stamp(tmp_path):
    from spark_rapids_tpu.io import parquet_fused as pqf
    from spark_rapids_tpu.obs import registry as obsreg
    t = _table(seed=30)
    p = _write(tmp_path, "a.parquet", t, row_group_size=1024)
    schema = Schema.from_arrow(t.schema)
    calls = []
    orig = pqf.assemble
    pqf.assemble = lambda *a, **k: calls.append(1) or orig(*a, **k)
    try:
        view = obsreg.get_registry().view()
        b1, _ = decode_row_groups_fused(_sources(p), schema)
        b2, _ = decode_row_groups_fused(_sources(p), schema)
        moved = dict(view.delta()["counters"])
        assert len(calls) == 1
        assert moved["scan.assembledCacheMisses"] == 1
        assert moved["scan.assembledCacheHits"] == 1
        st = sc.stats()
        assert st["assembled"] == 1
        # what was uploaded once is what is held, and nothing more was
        assert moved["scan.bytesUploaded"] == st["assembled_bytes"] > 0
        assert st["bytes"] + st["assembled_bytes"] <= 256 << 20
        assert_tables_equal(to_arrow(b2), to_arrow(b1))
        # another column set is another batch
        decode_row_groups_fused(_sources(p), schema, columns=["k", "s"])
        assert len(calls) == 2 and sc.stats()["assembled"] == 2

        # the file rewritten: both sets of the old stamp go with it
        t_new = _table(n=2100, seed=31)
        papq.write_table(t_new, p, row_group_size=1024)
        st_ = os.stat(p)
        os.utime(p, ns=(st_.st_atime_ns, st_.st_mtime_ns + 1_000_000))
        b3, _ = decode_row_groups_fused(_sources(p), schema)
        assert len(calls) == 3 and sc.stats()["assembled"] == 1
        got = to_arrow(b3)
        assert_tables_equal(got, t_new.cast(got.schema))
    finally:
        pqf.assemble = orig


def test_assembled_sets_go_under_memory_pressure(tmp_path):
    from spark_rapids_tpu.mem import spill
    t = _table(seed=32)
    p = _write(tmp_path, "a.parquet", t, row_group_size=1024)
    schema = Schema.from_arrow(t.schema)
    b1, _ = decode_row_groups_fused(_sources(p), schema)
    held = sc.stats()["assembled_bytes"]
    assert held > 0
    # the admission-pressure hook and the OOM retry both reach them
    assert spill._aux_pressure_spill(1 << 62) >= held
    assert sc.stats()["assembled"] == 0
    decode_row_groups_fused(_sources(p), schema)
    assert sc.stats()["assembled"] == 1
    assert spill.hbm_oom_recover(RuntimeError("RESOURCE_EXHAUSTED: x"))
    assert sc.stats()["assembled"] == 0
    b2, _ = decode_row_groups_fused(_sources(p), schema)
    assert_tables_equal(to_arrow(b2), to_arrow(b1))


def test_assembled_sets_take_only_what_the_plans_leave(tmp_path):
    paths = [_write(tmp_path, f"f{i}.parquet", _table(seed=40 + i),
                    row_group_size=1024) for i in range(2)]
    schema = Schema.from_arrow(_table().schema)
    decode_row_groups_fused(_sources(paths[0]), schema)
    st = sc.stats()
    plans, packed = st["bytes"], st["assembled_bytes"]
    assert plans > 0 and packed > 0
    evicted = st["evictions"]

    # room for both files' plans and one assembled set: the older set
    # goes, no plan does
    sc.clear()
    sc.configure(True, 2 * plans + packed + packed // 2)
    for p in paths:
        decode_row_groups_fused(_sources(p), schema)
    st = sc.stats()
    assert st["entries"] == 2 and st["evictions"] == evicted
    assert st["assembled"] == 1
    walks = pm.walk_count()
    decode_row_groups_fused(_sources(paths[0]), schema)
    assert pm.walk_count() == walks

    # no room beside the plans: nothing assembled is kept
    sc.clear()
    sc.configure(True, plans + packed // 2)
    decode_row_groups_fused(_sources(paths[0]), schema)
    st = sc.stats()
    assert st["assembled"] == 0 and st["entries"] == 1
    assert st["evictions"] == evicted


def test_assembled_set_not_cached_without_a_stamp(tmp_path):
    t = _table(seed=50)
    p = _write(tmp_path, "a.parquet", t, row_group_size=1024)
    schema = Schema.from_arrow(t.schema)
    pf = papq.ParquetFile(p)      # a plain handle pins no stamp
    try:
        srcs = [(pf, p, rg) for rg in range(pf.metadata.num_row_groups)]
        b, _ = decode_row_groups_fused(srcs, schema)
        assert sc.stats()["assembled"] == 0
        got = to_arrow(b)
        assert_tables_equal(got, t.cast(got.schema))
    finally:
        pf.close()
