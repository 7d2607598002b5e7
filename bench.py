"""Benchmark: TPC-DS q6-class pipeline over parquet (BASELINE.json #1).

Measures "TPC-DS q6 @ SF1 parquet (scan+filter+hash-agg), single local
executor": parquet scan -> decode -> filter -> group-by aggregate,
through the engine's real kernels, on both engines.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

value / vs_baseline — the HEADLINE: device-pipeline throughput.  The
engine's actual fused decode kernel (io/parquet_fused.py), expression
evaluator filter and sort-based aggregate kernels run K times inside ONE
jitted lax.fori_loop over the parquet page bytes resident in HBM, ending
in a scalar checksum read; per-query time is the difference between a
K=ITERS and a K=1 run divided by (ITERS-1).  vs_baseline divides the
engine's own CPU (pyarrow) execution of the same end-to-end query by
that per-query device time — the "stock Spark CPU vs accelerator"
framing of the reference (docs/FAQ.md: 3-7x typical).

WHY the loop harness: one dispatch, K real iterations with a
loop-carried data dependence (so XLA cannot hoist or elide the work),
one scalar read whose fixed cost cancels in the K-difference.  What a
dispatch and a read-back cost on the attached chip is not measured;
the served end-to-end time is what the benchmark PR (ROADMAP A0)
defines.

The row/value parity of TPU vs CPU results is asserted (rows_match) —
an incorrect pipeline fails the bench instead of reporting a number.
"""

import json
import os
import re
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq

ITERS_LOOP = 8       # fori_loop trips: one program must stay under
                     # the TPU runtime's per-execution watchdog


def _gen_store_sales(n: int, seed: int = 42) -> pa.Table:
    """q6-class fact slice: sold date fk, item fk, price, qty."""
    rng = np.random.default_rng(seed)
    return pa.table({
        "ss_sold_date_sk": pa.array(
            rng.integers(1, 1827, n).astype(np.int64)),
        "ss_item_sk": pa.array(
            rng.integers(1, 18001, n).astype(np.int64)),
        "ss_quantity": pa.array(rng.integers(1, 101, n).astype(np.int32)),
        "ss_list_price": np.round(rng.uniform(1.0, 200.0, n), 2),
        "ss_sales_price": np.round(rng.uniform(0.2, 200.0, n), 2),
        "ss_ext_sales_price": np.round(rng.uniform(1.0, 20000.0, n), 2),
    })


def _write_dataset(root: str, n: int, files: int) -> int:
    per = n // files
    total = 0
    for i in range(files):
        path = os.path.join(root, f"part-{i:04d}.parquet")
        # dictionary-encode only the low-cardinality columns; pyarrow
        # would otherwise start dict pages for the price columns and
        # fall back to PLAIN mid-chunk once the dictionary overflows
        papq.write_table(
            _gen_store_sales(per, seed=100 + i), path,
            use_dictionary=["ss_sold_date_sk", "ss_item_sk",
                            "ss_quantity"])
        total += os.path.getsize(path)
    return total


def _query(session, path):
    from spark_rapids_tpu import col, functions as F
    return (session.read.parquet(path)
            .filter(col("ss_sales_price") > 150.0)
            .group_by("ss_item_sk")
            .agg(F.count("*").alias("cnt"),
                 F.sum("ss_quantity").alias("qty"),
                 F.avg("ss_ext_sales_price").alias("aesp")))


def _probe_query(session, path):
    """q6-class pipeline WITH its expression prologue un-collapsed: two
    computed columns and a filter between scan and aggregate, i.e. the
    project/filter chain shape whole-stage fusion exists for (the
    headline ``_query`` is the minimal filter+agg form the loop harness
    times)."""
    from spark_rapids_tpu import col, functions as F
    return (session.read.parquet(path)
            .with_column("net", col("ss_ext_sales_price") -
                         col("ss_list_price"))
            .filter(col("ss_sales_price") > 150.0)
            .with_column("net_qty", col("net") * col("ss_quantity"))
            .group_by("ss_item_sk")
            .agg(F.count("*").alias("cnt"),
                 F.sum("net_qty").alias("nq")))


def _dispatch_count_probe(n: int = 160_000, files: int = 2) -> dict:
    """Per-query jit dispatch count + distinct-kernel count from the
    obs registry, fusion on vs off, over a small q6-class dataset.

    Asserts (1) fused and unfused results match row-for-row (the
    fallback path is a correctness oracle, not just a knob) and (2)
    fusion cuts the per-query dispatch count by >= 30% — the fused
    numbers land in the bench JSON so the dispatch reduction is a
    measured number, not a claim."""
    from spark_rapids_tpu import TpuSparkSession
    from spark_rapids_tpu.obs import registry as obsreg

    def run(root, fusion_enabled: bool):
        s = TpuSparkSession({
            "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
            "spark.rapids.tpu.sql.fusion.enabled": fusion_enabled})
        cold = obsreg.get_registry().view()
        _probe_query(s, root).collect()  # warm: compiles off the count
        cold_misses = cold.delta()["counters"].get(
            "kernel.cache.misses", 0)
        view = obsreg.get_registry().view()
        out = _probe_query(s, root).collect()
        d = view.delta()["counters"]
        return out, {
            "dispatches": int(d.get("kernel.dispatches", 0)),
            # INCREMENTAL: new compiles during this run only.  The
            # kernel cache is process-wide and the fused run goes
            # first, so the unfused number excludes every kernel the
            # two paths share (scan decode, agg update/merge/final) —
            # it is NOT a standalone compile-breadth figure; compare
            # compile bills in fresh processes (the /compiles ledger)
            "kernels_compiled_incremental": int(cold_misses),
            "dispatches_saved":
                int(d.get("fusion.dispatchesSaved", 0)),
            "fused_stages": int(d.get("fusion.stages", 0)),
            "agg_prologues_inlined":
                int(d.get("fusion.aggProloguesInlined", 0)),
        }

    with tempfile.TemporaryDirectory(prefix="q6_dispatch_") as root:
        _write_dataset(root, n, files)
        fused_t, fused = run(root, True)
        plain_t, plain = run(root, False)

    fs = fused_t.sort_by("ss_item_sk")
    ps = plain_t.sort_by("ss_item_sk")
    rows_match = (fs.num_rows == ps.num_rows and
                  fs.column("cnt").equals(ps.column("cnt")) and
                  np.allclose(fs.column("nq").to_numpy(
                      zero_copy_only=False),
                      ps.column("nq").to_numpy(zero_copy_only=False),
                      rtol=1e-9, equal_nan=True))
    assert rows_match, ("fusion on/off results diverge — whole-stage "
                        "fusion is broken")
    drop = 1.0 - fused["dispatches"] / max(plain["dispatches"], 1)
    assert drop >= 0.30, (
        f"fusion cut q6-class dispatches only {drop:.0%} "
        f"({plain['dispatches']} -> {fused['dispatches']}); "
        f"the >=30% contract failed")
    return {"fused": fused, "unfused": plain,
            "dispatch_drop_pct": round(100 * drop, 1),
            "rows_match": True}


def _kernel_backend_probe(rows: int = 1 << 17) -> dict:
    """Per-backend (xla vs pallas, ``kernel.backend``) timings of the
    two gather-wall kernels this round targets, with parity asserted
    before any number is reported (the bench's standing honesty rule):

      * decode — one hybrid RLE/bit-pack stream expansion
        (kernels/decode.expand_stream vs the window-gather XLA path)
      * agg — one masked grouped seg_sum + seg_count through
        ``_SortedCtx`` (kernels/segreduce single-pass vs the composed
        gather+scan chain)

    Also reports gathers-per-element: the XLA decode's count is
    MEASURED by walking its traced jaxpr for [cap]-sized gather ops;
    the Pallas count is by construction of the dense unpack (exactly
    one dense-value gather inside the expand kernel).  On CPU smoke
    runs the Pallas kernels execute under interpret=True, so the ms
    numbers are only meaningful relative to hardware runs — the parity
    and gather accounting are the point there."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.exec.tpu_aggregate import _group_ctx
    from spark_rapids_tpu.expr.eval_tpu import ColVal
    from spark_rapids_tpu import dtypes as dt
    from spark_rapids_tpu.io.device_parquet import (RunTable,
                                                    expand_runs_matrix,
                                                    _upload_runs)
    from spark_rapids_tpu.kernels import backend as kb
    from spark_rapids_tpu.kernels import decode as kdec

    rng = np.random.default_rng(11)
    w = 15
    runs = RunTable.empty()
    packed = bytearray()
    total = 0
    while total < rows - 4096:
        if rng.random() < 0.5:
            c = int(rng.integers(100, 2000))
            runs.counts.append(c)
            runs.is_rle.append(True)
            runs.values.append(int(rng.integers(0, 1 << w)))
            runs.bit_bases.append(0)
            runs.widths.append(w)
        else:
            groups = int(rng.integers(8, 64))
            c = groups * 8
            runs.counts.append(c)
            runs.is_rle.append(False)
            runs.values.append(0)
            runs.bit_bases.append(len(packed) * 8)
            runs.widths.append(w)
            packed += rng.integers(0, 256, groups * w).astype(
                np.uint8).tobytes()
        total += c
    cap = rows

    def timed_ms(fn, reps: int = 3) -> float:
        np.asarray(fn())          # compile/warm
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(fn())
            dt_ = time.perf_counter() - t0
            best = dt_ if best is None else min(best, dt_)
        return best * 1e3

    from spark_rapids_tpu.obs import registry as obsreg

    out: dict = {}
    decode_res = {}
    decode_tiles: dict = {}
    for bk_name in ("xla", "pallas"):
        with kb.backend_override(bk_name):
            # tile accounting around exactly ONE invocation: decode's
            # record_tiles fires per host call, so including the warm
            # + timing reps would report call-count multiplicity, not
            # streamed volume (segreduce's fires once per jit trace
            # and needs no such scoping)
            one_view = obsreg.get_registry().view()
            decode_res[bk_name] = np.asarray(
                kdec.expand_stream(runs, bytes(packed), cap))[:total]
            if bk_name == "pallas":
                decode_tiles = one_view.delta()["counters"]
            ms = timed_ms(lambda: kdec.expand_stream(
                runs, bytes(packed), cap))
        out[f"decode_{bk_name}_ms"] = round(ms, 3)
    assert np.array_equal(decode_res["xla"], decode_res["pallas"]), \
        "kernel.backend decode parity failed — no number is reported"

    # measured gather count of the XLA expansion (per-element = output
    # at least [cap]-sized), vs the Pallas kernel's single dense gather
    dev = _upload_runs(runs, bytes(packed))

    def _xla_expand(runs_mat, pk):
        return expand_runs_matrix(runs_mat, pk, cap)
    jaxpr = jax.make_jaxpr(_xla_expand)(dev["runs_mat"], dev["packed"])
    gathers = 0

    def walk(jx):
        nonlocal gathers
        for eq in jx.eqns:
            if eq.primitive.name == "gather" and \
                    eq.outvars[0].aval.shape and \
                    eq.outvars[0].aval.shape[0] >= cap:
                gathers += 1
            for v in eq.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr)
    walk(jaxpr.jaxpr)
    out["gathers_per_element"] = {
        "xla_measured": gathers,
        "pallas_by_construction":
            kdec.GATHERS_PER_ELEMENT["pallas"],
    }

    # -- aggregate seg-reduce leg ------------------------------------
    n = cap - 777
    keys = np.zeros(cap, np.int64)
    keys[:n] = rng.integers(0, 64, n)
    vals = np.zeros(cap, np.float64)
    vals[:n] = rng.uniform(-1e4, 1e4, n)
    kv = ColVal(dt.INT64, jnp.asarray(keys),
                jnp.ones(cap, bool), None)
    v = jnp.asarray(vals)
    mask = jnp.arange(cap) < n
    agg_res = {}
    agg_tiles: dict = {}
    for bk_name in ("xla", "pallas"):
        def one(bk=bk_name):
            ctx = _group_ctx([kv], cap, n, backend=bk)
            return ctx.seg_sum(v, mask, out_np=np.float64) + \
                ctx.seg_count(mask)
        agg_fn = jax.jit(one)
        one_view = obsreg.get_registry().view()
        agg_res[bk_name] = np.asarray(agg_fn())[:64]   # traces here
        if bk_name == "pallas":
            agg_tiles = one_view.delta()["counters"]
        out[f"agg_{bk_name}_ms"] = round(timed_ms(agg_fn), 3)
    assert np.array_equal(agg_res["xla"], agg_res["pallas"]), \
        "kernel.backend aggregate parity failed"
    # HBM->VMEM streaming-tiler accounting (the counters that replaced
    # the retired whole-buffer residency fallbacks): decode from ONE
    # scoped invocation (per-call counting), segreduce from its
    # per-compile counting inside agg_view's window — both are the
    # per-probe streamed volume, not timing-rep multiplicity
    out["tiles"] = {
        "decode": int(decode_tiles.get(
            "kernel.pallas.tiles.decode.expand", 0)),
        "decode_bytes": int(decode_tiles.get(
            "kernel.pallas.tileBytes.decode.expand", 0)),
        "segreduce": int(agg_tiles.get(
            "kernel.pallas.tiles.agg.segreduce", 0)),
        "segreduce_bytes": int(agg_tiles.get(
            "kernel.pallas.tileBytes.agg.segreduce", 0)),
        "plan_hits": int(agg_tiles.get("kernel.tilePlan.hits", 0)) +
            int(decode_tiles.get("kernel.tilePlan.hits", 0)),
        "plan_misses": int(agg_tiles.get("kernel.tilePlan.misses", 0)) +
            int(decode_tiles.get("kernel.tilePlan.misses", 0)),
        "tile_bytes_conf": kb.tile_bytes(),
    }
    out["rows"] = rows
    out["rows_match"] = True
    return out


def _concurrent_probe(root: str, n_queries: int) -> dict:
    """N mixed q6-class queries through the concurrent scheduler
    (sched/service.py): a serial pass first (the parity oracle and the
    compile warm-up), then every query submitted at once via
    ``collect_async`` under ``sched.maxConcurrent=3``.  Reports
    queries/sec and p50/p95 queue wait (from each future's admission
    wait) into the bench JSON; serial-vs-concurrent results must match
    row for row."""
    from spark_rapids_tpu import TpuSparkSession
    max_concurrent = 3
    s = TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        "spark.rapids.tpu.sched.maxConcurrent": max_concurrent})
    # mixed shapes: the minimal filter+agg form and the computed-column
    # prologue form alternate, so admitted queries differ in plan shape
    queries = [(_query if i % 2 == 0 else _probe_query)(s, root)
               for i in range(n_queries)]

    t0 = time.perf_counter()
    serial = [q.collect() for q in queries]
    serial_wall = time.perf_counter() - t0

    # window the SLO bucket histograms around the concurrent pass so
    # the probe's p50/p95/p99 are ITS latencies, not the serial
    # warm-up's (the RegistryView delta carve)
    from spark_rapids_tpu.obs import registry as obsreg
    view = obsreg.get_registry().view()
    t0 = time.perf_counter()
    futs = [q.collect_async() for q in queries]
    tables = [f.result(timeout=900) for f in futs]
    wall = time.perf_counter() - t0
    lat = _window_quantiles(view.delta(), "slo.latencyMs")

    for i, (a, b) in enumerate(zip(serial, tables)):
        assert a.sort_by("ss_item_sk").equals(b.sort_by("ss_item_sk")), \
            f"concurrent query {i} diverges from its serial run"
    waits_ms = sorted(f.queue_wait_ns / 1e6 for f in futs)

    def pct(p: float) -> float:
        return waits_ms[min(len(waits_ms) - 1,
                            int(p * (len(waits_ms) - 1) + 0.5))]

    return {
        "n_queries": n_queries,
        "max_concurrent": max_concurrent,
        "wall_s": round(wall, 3),
        "serial_wall_s": round(serial_wall, 3),
        "queries_per_sec": round(n_queries / wall, 3),
        "queue_wait_p50_ms": round(pct(0.50), 2),
        "queue_wait_p95_ms": round(pct(0.95), 2),
        "latency": lat,
        "rows_match": True,
    }


def _window_quantiles(delta: dict, name: str) -> dict:
    """p50/p95/p99 (+ sample count) of one SLO bucket histogram over a
    RegistryView window; {} when the window saw no observations."""
    from spark_rapids_tpu.obs import registry as obsreg
    h = (delta.get("bucket_histograms") or {}).get(name)
    if not h:
        return {}
    out = {"count": int(h["count"])}
    for q, key in ((0.50, "p50_ms"), (0.95, "p95_ms"),
                   (0.99, "p99_ms")):
        v = obsreg.bucket_quantile(h["bounds"], h["counts"], q)
        out[key] = round(v, 3) if v is not None else None
    return out


def _slo_quantiles() -> dict:
    """Whole-run p50/p95/p99 per SLO bucket histogram (latency, queue
    wait, first chunk) for the trend record — quantiles, not just
    means."""
    try:
        from spark_rapids_tpu.obs import registry as obsreg
        snap = obsreg.get_registry().snapshot()
        out = {}
        for name, h in sorted(
                snap.get("bucket_histograms", {}).items()):
            if ".tpl." in name:
                continue      # per-template series stay on /slo
            row = {"count": int(h["count"])}
            for q, key in ((0.50, "p50_ms"), (0.95, "p95_ms"),
                           (0.99, "p99_ms")):
                v = obsreg.bucket_quantile(h["bounds"], h["counts"], q)
                row[key] = round(v, 3) if v is not None else None
            out[name] = row
        return out
    except Exception:
        return {}


def _shuffle_pipeline_probe(n_queries: int = 4) -> dict:
    """Pipelined process-transport exchange probe: the same
    shuffle-heavy query batch runs sequential
    (``shuffle.pipeline.depth=0``, the barrier exchange) and pipelined
    with lz4 wire compression, through the concurrent scheduler both
    times.  Asserts bit-identical results and reports queries/sec for
    both modes, the pipeline overlap ratio (``overlapNs / (overlapNs +
    stallNs)`` — of the time the look-ahead was either hiding work or
    starving, the fraction hidden), and the compressed-vs-raw wire
    bytes — the shuffle block of the trend record."""
    from spark_rapids_tpu import TpuSparkSession, functions as F
    from spark_rapids_tpu.obs import registry as obsreg
    from spark_rapids_tpu.shuffle import procpool

    rng = np.random.default_rng(29)
    rows = 30_000
    t = pa.table({
        "k": pa.array(rng.integers(0, 23, rows).astype(np.int64)),
        "v": pa.array(rng.integers(0, 5000, rows).astype(np.int64)),
        "w": pa.array(np.round(rng.uniform(0.0, 100.0, rows), 3)),
    })

    def run(depth: int, codec: str):
        s = TpuSparkSession({
            "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
            "spark.rapids.tpu.shuffle.transport": "process",
            "spark.rapids.tpu.shuffle.transport.processExecutors": 2,
            "spark.rapids.tpu.sql.shuffle.partitions": 4,
            "spark.rapids.tpu.shuffle.pipeline.depth": depth,
            "spark.rapids.tpu.shuffle.compression.codec": codec,
        })

        def q():
            return (s.create_dataframe(t, num_partitions=3)
                    .group_by("k")
                    .agg(F.count("*").alias("c"),
                         F.sum("v").alias("sv"),
                         F.avg("w").alias("aw"))
                    .sort("k"))

        q().collect()                    # warm-up: compiles + fleet spawn
        view = obsreg.get_registry().view()
        t0 = time.perf_counter()
        futs = [q().collect_async() for _ in range(n_queries)]
        tables = [f.result(timeout=900) for f in futs]
        wall = time.perf_counter() - t0
        return tables, wall, view.delta()["counters"]

    seq_tables, seq_wall, _ = run(0, "none")
    pipe_tables, pipe_wall, d = run(2, "lz4")
    for i, (a, b) in enumerate(zip(seq_tables, pipe_tables)):
        # int columns must match exactly; the float avg is compared
        # with tolerance — the sequential iterator yields remote
        # batches in ARRIVAL order (nondeterministic across peers), so
        # its own float-agg order varies run to run (the accepted
        # variableFloatAgg contract; the pipelined path is actually
        # the more deterministic of the two, assembling sorted)
        for col_name in ("k", "c", "sv"):
            assert a.column(col_name).equals(b.column(col_name)), \
                f"pipelined shuffle query {i} diverges on {col_name!r}"
        assert np.allclose(a.column("aw").to_numpy(),
                           b.column("aw").to_numpy(), rtol=1e-9), \
            f"pipelined shuffle query {i} float avg diverges"
    procpool.reset_executor_pool()
    overlap = d.get("shuffle.pipeline.overlapNs", 0)
    stall = d.get("shuffle.pipeline.stallNs", 0)
    raw = d.get("shuffle.wire.rawBytes", 0)
    wire = d.get("shuffle.wire.wireBytes", 0)
    return {
        "n_queries": n_queries,
        "sequential_qps": round(n_queries / seq_wall, 3),
        "pipelined_qps": round(n_queries / pipe_wall, 3),
        "overlap_ms": round(overlap / 1e6, 2),
        "stall_ms": round(stall / 1e6, 2),
        "overlap_ratio": (round(overlap / (overlap + stall), 4)
                          if overlap + stall else None),
        "wire_raw_bytes": int(raw),
        "wire_bytes": int(wire),
        "wire_compression_ratio": (round(raw / wire, 3)
                                   if wire else None),
        "rows_match": True,
    }


def _time_engine_cpu(path: str, iters: int = 3):
    """Engine CPU (pyarrow) leg: min wall over iters + the result."""
    from spark_rapids_tpu import TpuSparkSession
    s = TpuSparkSession({
        "spark.rapids.tpu.sql.enabled": False,
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
    out = _query(s, path).collect()  # warm
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = _query(s, path).collect()
        times.append(time.perf_counter() - t0)
    return min(times), out


def _build_device_pipeline(root: str):
    """Assemble the engine's REAL q6 pipeline as one jittable function
    over HBM-resident parquet page structures.

    Returns (loop_fn(K) -> checksum scalar, host prep timings,
    upload_arrays).  loop_fn composes: fused parquet decode
    (io/parquet_fused kernel) -> filter (expr/eval_tpu) -> hash
    aggregate (exec/tpu_aggregate update/merge/final) — the same
    kernels the planner drives.

    Host prep runs TWICE through the engine's scan-plan cache
    (io/scan_cache.py): the cold pass pays footer parses + page walks,
    the warm pass (the "second collect() over the same files") must
    serve every plan from cache with ZERO page-header walks — asserted
    via the parquet_meta walk counter."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.io import parquet_fused as pqf
    from spark_rapids_tpu.io import parquet_meta as pqm
    from spark_rapids_tpu.io import scan_cache as sc
    from spark_rapids_tpu.exec.tpu_aggregate import (
        finalize_aggregate, make_spec, update_aggregate)
    from spark_rapids_tpu.columnar.batch import DeviceBatch
    from spark_rapids_tpu.expr import ir
    from spark_rapids_tpu.plan.logical import Schema

    paths = sorted(os.path.join(root, p) for p in os.listdir(root))
    # the planner's column pruning (plan/optimizer.py) narrows the scan
    # to the query's referenced columns; the loop harness decodes the
    # same pruned set
    wanted = ["ss_item_sk", "ss_quantity", "ss_sales_price",
              "ss_ext_sales_price"]

    def host_prep():
        """The engine's own prepare path (pqf.prepare_fused), timed by
        its scan.hostPrepTime metric — walks + assembly, not uploads."""
        from spark_rapids_tpu.exec.base import Metrics
        m = Metrics()
        footers = {p: sc.get_footer(p) for p in paths}
        full = Schema.from_arrow(footers[paths[0]].schema_arrow)
        schema = Schema([full.field(c) for c in wanted])
        sources = [(footers[p], p, rg) for p in paths
                   for rg in range(footers[p].metadata.num_row_groups)]
        prep = pqf.prepare_fused(sources, schema, columns=wanted,
                                 host_threads=4, metrics=m)
        assert not prep.fallbacks, \
            f"bench columns fell back: {prep.fallbacks}"
        # timed_extra accumulates NANOSECONDS; convert at report time
        return prep.fp, m.extra_s("scan.hostPrepTime")

    sc.clear()  # cold: fresh process semantics even under repeat runs
    fp, host_prep_s = host_prep()
    walks_after_cold = pqm.walk_count()
    _, host_prep_warm_s = host_prep()
    assert pqm.walk_count() == walks_after_cold, \
        "warm host prep re-walked page headers despite the plan cache"
    decode = pqf._make_kernel(fp)
    n_rows = fp.n_rows
    total_rows = sum(n_rows)
    full = Schema.from_arrow(
        sc.get_footer(paths[0]).schema_arrow)
    schema = Schema([full.field(c) for c in wanted])

    def b(e):
        return ir.bind(e, schema.names, schema.dtypes, schema.nullables)

    cond = b(ir.GreaterThan(ir.UnresolvedAttribute("ss_sales_price"),
                            ir.Literal(150.0)))
    groupings = [b(ir.UnresolvedAttribute("ss_item_sk"))]
    aggregates = []
    for a in [ir.Count(None),
              ir.Sum(b(ir.UnresolvedAttribute("ss_quantity"))),
              ir.Average(b(ir.UnresolvedAttribute("ss_ext_sales_price")))]:
        a.resolve()
        aggregates.append(a)
    specs = [make_spec(a) for a in aggregates]

    def one_query(arrays):
        cols, _ = decode(arrays)
        batch = DeviceBatch(wanted, list(cols), total_rows)
        # fused filter (the planner's agg.fusedFilter post-pass shape):
        # the filter is a MASK inside the aggregate's update kernel —
        # compaction would cost one full-capacity gather per column
        # while the sort-based grouping is capacity-proportional anyway
        partial = update_aggregate(batch, groupings, aggregates,
                                   specs, condition=cond)
        out = finalize_aggregate(partial, 1, specs,
                                 ["k", "cnt", "qty", "aesp"])
        chk = (jnp.sum(out.columns[1].data,
                       where=out.columns[1].validity) +
               jnp.sum(out.columns[2].data,
                       where=out.columns[2].validity))
        return chk.astype(jnp.int32), out

    def loop_fn(arrays, k: int):
        def body(_, carry):
            chk, meta0 = carry
            # loop-carried data dependence: the select cannot be folded
            # (chk == sentinel is unknowable at compile time), so every
            # trip re-runs the real decode+filter+agg — no hoisting
            arrs = dict(arrays)
            arrs["meta"] = jnp.where(chk == jnp.int32(-123456789),
                                     meta0 + 1, meta0)
            chk2, _ = one_query(arrs)
            return chk ^ chk2, meta0
        chk, _ = jax.lax.fori_loop(
            0, k, body, (jnp.int32(0), arrays["meta"]))
        return chk

    return loop_fn, one_query, (host_prep_s, host_prep_warm_s), fp


def _device_pipeline_metric(root: str):
    """Per-query device pipeline seconds + TPU q6 result for parity."""
    import jax
    import jax.numpy as jnp

    loop_fn, one_query, host_prep, fp = _build_device_pipeline(root)
    arrays = {k: jnp.asarray(v) for k, v in fp.arrays.items()}

    f1 = jax.jit(lambda a: loop_fn(a, 1))
    fN = jax.jit(lambda a: loop_fn(a, ITERS_LOOP))

    # parity check batch (also compiles/loads one_query's program)
    _, out_batch = jax.jit(one_query)(arrays)
    from spark_rapids_tpu.columnar.batch import to_arrow
    tpu_table = to_arrow(out_batch)  # first read: pays the replay once

    def timed_read(f):
        t0 = time.perf_counter()
        v = int(np.asarray(f(arrays)))
        return time.perf_counter() - t0, v

    timed_read(f1)            # load both executables (sync mode now)
    timed_read(fN)
    t1, v1 = timed_read(f1)
    tN, vN = timed_read(fN)
    t1b, _ = timed_read(f1)
    tNb, _ = timed_read(fN)
    per_query = (min(tN, tNb) - min(t1, t1b)) / (ITERS_LOOP - 1)
    return max(per_query, 1e-9), host_prep, tpu_table


def _write_profile(root: str, out_path: str):
    """One profiled engine collect of the bench query with tracing on:
    the QueryProfile JSON (+ its Chrome trace alongside) lands next to
    the BENCH results so the perf trajectory is self-explaining."""
    from spark_rapids_tpu import TpuSparkSession
    s = TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        "spark.rapids.tpu.obs.trace.enabled": True})
    out = _query(s, root).collect()
    prof = s.last_query_profile()
    assert prof is not None and prof.result_rows == out.num_rows, \
        "query profile rows disagree with the collected result"
    with open(out_path, "w") as f:
        f.write(prof.to_json())
    prof.dump_chrome_trace(out_path + ".trace.json")
    from spark_rapids_tpu.obs import trace as obs_trace
    obs_trace.configure(False)  # don't trace the rest of the bench
    return out_path


def _serve_probe(root: str, n_clients: int) -> dict:
    """N remote clients through the serving front-end (serve/): each
    client prepares the q6-class statement once and executes it
    repeatedly with a per-client binding — the dashboard access
    pattern.  Repeats within a client hit the result-set cache, so the
    probe reports both the remote queries/sec and the hit ratio, plus
    a parity check of every remote result against the in-process
    oracle."""
    from spark_rapids_tpu import TpuSparkSession
    from spark_rapids_tpu.obs import registry as obsreg
    from spark_rapids_tpu.serve.client import ServeClient

    s = TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        "spark.rapids.tpu.serve.enabled": True})
    s.register_view("ss", s.read.parquet(root))
    sql = ("select ss_item_sk, count(*) as cnt, sum(ss_quantity) as "
           "qty from ss where ss_sales_price > :lo group by "
           "ss_item_sk order by ss_item_sk")
    cuts = [150.0 + 2.0 * i for i in range(n_clients)]
    oracles = {lo: s.sql(sql.replace(":lo", repr(lo))).collect()
               for lo in cuts}
    repeats = 3
    view = obsreg.get_registry().view()
    results: dict = {}
    errors: list = []

    def run(idx: int) -> None:
        try:
            lo = cuts[idx]
            with ServeClient("127.0.0.1", s.serve_server.port) as c:
                h = c.prepare(sql, params={"lo": "double"})
                results[idx] = [h.execute({"lo": lo})
                                for _ in range(repeats)]
        except Exception as e:
            errors.append(f"client {idx}: {type(e).__name__}: {e}")

    t0 = time.perf_counter()
    threads = [threading.Thread(target=run, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    wall = time.perf_counter() - t0
    total = n_clients * repeats
    # a failed or hung client must fail the probe, not silently skip
    # its parity check
    assert not errors, errors
    hung = [t.name for t in threads if t.is_alive()]
    assert not hung, f"serve clients still running: {hung}"
    for i in range(n_clients):
        got = results.get(i, [])
        assert len(got) == repeats, f"client {i}: {len(got)} results"
        for r in got:
            assert r.equals(oracles[cuts[i]]), \
                f"serve client {i} diverges from the in-process oracle"
    d = view.delta()["counters"]
    s.serve_server.shutdown()
    return {
        "n_clients": n_clients,
        "queries": total,
        "wall_s": round(wall, 3),
        "queries_per_sec": round(total / wall, 3),
        "result_cache_hits": int(d.get("serve.resultCacheHits", 0)),
        "result_cache_misses": int(d.get("serve.resultCacheMisses", 0)),
        "streamed_batches": int(d.get("serve.streamedBatches", 0)),
        "rows_match": True,
    }


def _fleet_metrics_hist(obs_port: int, name: str):
    """(bounds, counts) of one bucket histogram scraped from a
    replica's /metrics exposition (cumulative le buckets
    de-cumulated), or None when the replica never observed it."""
    import urllib.request
    prom = "spark_rapids_tpu_" + name.replace(".", "_")
    with urllib.request.urlopen(
            f"http://127.0.0.1:{obs_port}/metrics", timeout=10) as r:
        text = r.read().decode()
    rows = re.findall(
        rf'^{re.escape(prom)}_bucket{{le="([^"]+)"}} (\d+)$',
        text, re.MULTILINE)
    bounds, counts, prev = [], [], 0
    for le, cum in rows:
        if le == "+Inf":
            continue
        bounds.append(float(le))
        counts.append(int(cum) - prev)
        prev = int(cum)
    return (bounds, counts) if any(counts) else None


def _fleet_probe(root: str, n_replicas: int) -> dict:
    """--fleet=N: the horizontally scaled serve tier (fleet/).  A
    cache-miss-heavy prepared-statement workload — result cache OFF on
    every replica, so each execute runs the engine; one device slot
    per replica (sched.maxConcurrent=1), the fleet's actual topology —
    is pushed through the router against ONE replica and against N.
    Reports the qps scaling and the fleet-merged serve-latency p95
    from the replicas' SLO histograms.  N>=3 must clear >= 2x the
    single-replica qps (the PR-20 acceptance floor: linear-ish scaling
    minus router + placement overhead)."""
    from spark_rapids_tpu.fleet.replica import FleetManager
    from spark_rapids_tpu.fleet.router import FleetRouter
    from spark_rapids_tpu.obs import registry as obsreg
    from spark_rapids_tpu.serve.client import ServeClient

    sql = ("select ss_item_sk, count(*) as cnt, sum(ss_quantity) as "
           "qty from ss where ss_sales_price > :lo group by "
           "ss_item_sk order by ss_item_sk")
    n_clients = max(3, n_replicas)
    repeats = 6
    base_conf = {
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        "spark.rapids.tpu.serve.resultCache.enabled": False,
        "spark.rapids.tpu.serve.incremental.enabled": False,
        "spark.rapids.tpu.sched.maxConcurrent": 1,
    }
    store_root = tempfile.mkdtemp(prefix="fleet_bench_")

    def run_tier(n_reps: int) -> dict:
        mgr = FleetManager(
            os.path.join(store_root, f"store{n_reps}"),
            base_conf=base_conf,
            views={"ss": {"parquet": root}})
        router = None
        try:
            reps = [mgr.spawn(name=f"r{i}") for i in range(n_reps)]
            router = FleetRouter([r.endpoint() for r in reps],
                                 health_poll_ms=60_000).start()
            errors: list = []
            handles: dict = {}
            clients: dict = {}
            # connect + prepare + ONE warm execute per client (pays
            # the per-replica kernel compiles outside the timed
            # window; each client keeps its fixed binding so the warm
            # programs are exactly the timed ones)
            for i in range(n_clients):
                c = ServeClient("127.0.0.1", router.port)
                clients[i] = c
                handles[i] = c.prepare(sql, params={"lo": "double"})
                handles[i].execute({"lo": 150.0 + 2.0 * i})

            def run(idx: int) -> None:
                try:
                    for _ in range(repeats):
                        handles[idx].execute({"lo": 150.0 + 2.0 * idx})
                except Exception as e:
                    errors.append(
                        f"client {idx}: {type(e).__name__}: {e}")

            # pre-scrape so the merged histogram covers only the
            # timed window (warm-round compiles would dominate p95)
            before = {r.name: _fleet_metrics_hist(r.obs_port,
                                                  "slo.latencyMs")
                      for r in reps}
            t0 = time.perf_counter()
            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(n_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
            wall = time.perf_counter() - t0
            assert not errors, errors
            hung = [t.name for t in threads if t.is_alive()]
            assert not hung, f"fleet clients still running: {hung}"
            for c in clients.values():
                c.close()
            # fleet-merged serve-latency histogram across replicas
            bounds, counts = None, None
            for r in reps:
                h = _fleet_metrics_hist(r.obs_port, "slo.latencyMs")
                if h is None:
                    continue
                cts = list(h[1])
                pre = before.get(r.name)
                if pre is not None:
                    cts = [a - b for a, b in zip(cts, pre[1])]
                if bounds is None:
                    bounds, counts = h[0], cts
                else:
                    counts = [a + b for a, b in zip(counts, cts)]
            p95 = (obsreg.bucket_quantile(bounds, counts, 0.95)
                   if bounds else None)
            total = n_clients * repeats
            return {"replicas": n_reps, "queries": total,
                    "wall_s": round(wall, 3),
                    "qps": round(total / wall, 3),
                    "latency_p95_ms":
                        round(p95, 3) if p95 is not None else None}
        finally:
            if router is not None:
                router.shutdown()
            mgr.stop_all()

    single = run_tier(1)
    fleet = run_tier(n_replicas)
    speedup = round(fleet["qps"] / single["qps"], 3)
    # the scaling floor holds when every replica can own an execution
    # slot ("device" = a CPU core in this emulation; a TPU per replica
    # on real hardware).  On a box with fewer cores than replicas the
    # fleet time-slices one core and no horizontal speedup is
    # physically possible — report the numbers, skip the floor.
    cores = os.cpu_count() or 1
    gated = n_replicas >= 3 and cores >= n_replicas
    if gated:
        assert speedup >= 2.0, (
            f"{n_replicas} replicas only {speedup}x the single-replica "
            f"qps ({fleet['qps']} vs {single['qps']})")
    return {
        "n_replicas": n_replicas,
        "n_clients": n_clients,
        "cores": cores,
        "single": single,
        "fleet": fleet,
        "speedup": speedup,
        "speedup_floor": ("asserted >= 2.0" if gated else
                          f"skipped: {cores} core(s) < {n_replicas} "
                          f"replicas, no per-replica device"),
    }


def _sharing_probe(root: str, n_clients: int = 8) -> dict:
    """Multi-query work sharing (ISSUE 16): the SAME q6-class query
    submitted by N concurrent clients, with sharing off (every client
    pays a full execution) vs on (single-flight collapses the batch to
    one execution, sched.dedup.hits = N-1).  Results bit-identical to
    a serial run both ways; the shared batch must clear >= 3x
    queries/sec — the redundant-traffic contract."""
    from spark_rapids_tpu import TpuSparkSession
    from spark_rapids_tpu.obs import registry as obsreg

    def batch(extra: dict):
        conf = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True}
        conf.update(extra)
        s = TpuSparkSession(conf)
        serial = _query(s, root).collect()   # warm + parity oracle
        view = obsreg.get_registry().view()
        t0 = time.perf_counter()
        futs = [_query(s, root).collect_async()
                for _ in range(n_clients)]
        tables = [f.result(timeout=900) for f in futs]
        wall = time.perf_counter() - t0
        for i, t in enumerate(tables):
            assert t.equals(serial), \
                f"shared client {i} diverges from the serial run"
        return wall, view.delta()["counters"]

    wall_off, _ = batch({
        "spark.rapids.tpu.sched.dedup.enabled": False,
        "spark.rapids.tpu.sql.scan.shared.enabled": False,
        "spark.rapids.tpu.serve.batch.enabled": False})
    wall_on, d = batch({})                   # sharing is the default
    assert int(d.get("sched.dedup.flights", 0)) == 1, d
    assert int(d.get("sched.dedup.hits", 0)) == n_clients - 1, d
    speedup = wall_off / max(wall_on, 1e-9)
    assert speedup >= 3.0, (
        f"work sharing only {speedup:.2f}x faster at {n_clients} "
        f"concurrent identical queries ({wall_off:.3f}s off vs "
        f"{wall_on:.3f}s on)")
    return {
        "n_clients": n_clients,
        "wall_off_s": round(wall_off, 3),
        "wall_on_s": round(wall_on, 3),
        "qps_off": round(n_clients / wall_off, 3),
        "qps_on": round(n_clients / wall_on, 3),
        "speedup": round(speedup, 2),
        "dedup_hits": int(d.get("sched.dedup.hits", 0)),
        "rows_match": True,
    }


def _join_probe(n: int = 24_000) -> dict:
    """Out-of-core + skew-resilient joins (exec/join_partition.py,
    exec/adaptive.py): a seeded skewed fact table (~60% of probe rows
    on one key) shuffled-hash-joined against a dim table, skew
    splitting off vs on, plus the same join unconstrained vs under a
    build budget ~4x smaller than the build side.

    The reduce-stage metric is the CRITICAL PATH — the largest single
    reduce unit's probe bytes (with parallel reducers, the stage wall
    is its largest bucket; splitting the hot bucket shrinks exactly
    that).  The acceptance contract is >= 1.5x critical-path
    improvement with splitting on, bit-identical results all four
    ways, and the grace counters proving the out-of-core join really
    spilled and re-streamed."""
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu import TpuSparkSession, col
    from spark_rapids_tpu.exec.adaptive import TpuSkewJoinReaderExec
    from spark_rapids_tpu.obs import registry as obsreg

    rng = np.random.default_rng(19)
    keys = np.where(rng.random(n) < 0.6, 7,
                    rng.integers(0, 500, n)).astype(np.int64)
    fact = pa.table({"k": keys, "v": rng.integers(0, 1000, n)})
    dim = pa.table({"k2": np.arange(500, dtype=np.int64),
                    "w": rng.integers(0, 1000, 500)})
    base_conf = {
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1,
        "spark.rapids.tpu.sql.shuffle.partitions": 16,
    }

    def df_of(s):
        f = s.create_dataframe(fact, num_partitions=4)
        d = s.create_dataframe(dim, num_partitions=4)
        return (f.join(d, col("k") == col("k2"))
                 .select(col("k").alias("a"), col("v").alias("b"),
                         col("w").alias("c")))

    def run(extra: dict):
        s = TpuSparkSession(dict(base_conf, **extra))
        df_of(s).collect()                 # warm kernels off the clock
        view = obsreg.get_registry().view()
        t0 = time.perf_counter()
        out = df_of(s).collect()
        wall = time.perf_counter() - t0
        return s, out.sort_by([("a", "ascending"), ("b", "ascending"),
                               ("c", "ascending")]), wall, \
            view.delta()["counters"]

    # -- skew: off vs on, critical path from the planted reader state --
    _s0, base, wall_off, _ = run({})
    skew_conf = {"spark.rapids.tpu.sql.join.skew.enabled": True,
                 "spark.rapids.tpu.sql.join.skew.minBucketBytes": 1024}
    s_on, split, wall_on, d = run(skew_conf)
    assert split.equals(base), "skew-split result diverges"
    assert int(d.get("shuffle.skew.detected", 0)) >= 1, d

    # re-plan once more to read the reader's plan: specs + per-bucket
    # probe totals give the exact reduce units both ways
    df = df_of(s_on)
    phys = s_on._plan_physical(df.plan).plan
    readers = []
    phys.foreach(lambda nd: readers.append(nd)
                 if isinstance(nd, TpuSkewJoinReaderExec) else None)
    assert readers, "skew conf planted no TpuSkewJoinReaderExec"
    rd = readers[0]
    for it in phys.execute():            # populate the runtime state
        for _ in it:
            pass
    st = rd.state
    totals = st.outs[st.probe].totals
    critical_off = max(totals)
    per_unit = {p: float(tb) for p, tb in enumerate(totals)}
    for sp in st.specs:
        if sp[0] == "split":
            per_unit[sp[1]] = totals[sp[1]] / float(sp[3])
    critical_on = max(per_unit.values())
    balance = critical_off / max(critical_on, 1.0)
    assert balance >= 1.5, (
        f"hot-bucket split only {balance:.2f}x reduce-stage "
        f"critical-path improvement ({critical_off} -> "
        f"{int(critical_on)} bytes)")

    # -- out-of-core: unconstrained oracle vs ~4x-over-budget grace ----
    _s2, oracle, wall_free, _ = run({
        "spark.rapids.tpu.sql.join.buildSideBudgetBytes": -1})
    budget = max(1024, int(dim.nbytes) // 16)  # per-partition build /4
    _s3, grace, wall_oo, dg = run({
        "spark.rapids.tpu.sql.join.buildSideBudgetBytes": budget})
    assert grace.equals(oracle), "grace join result diverges"
    assert int(dg.get("join.grace.activations", 0)) >= 1, dg
    assert int(dg.get("join.grace.restreams", 0)) >= 1, dg
    assert int(dg.get("join.grace.spilledBuildBytes", 0)) > 0, dg
    oo_overhead = (wall_oo - wall_free) / max(wall_free, 1e-9)
    return {
        "rows": n,
        "skew_off_qps": round(1.0 / max(wall_off, 1e-9), 3),
        "skew_on_qps": round(1.0 / max(wall_on, 1e-9), 3),
        "reduce_critical_path_improvement": round(balance, 2),
        "hot_buckets": int(d.get("shuffle.skew.detected", 0)),
        "splits": int(d.get("shuffle.skew.splits", 0)),
        "oocore_overhead_pct": round(100 * oo_overhead, 1),
        "oocore_budget_bytes": budget,
        "grace_partitions": int(dg.get("join.grace.partitions", 0)),
        "grace_spilled_bytes":
            int(dg.get("join.grace.spilledBuildBytes", 0)),
        "rows_match": True,
    }


def _incremental_probe(n: int = 160_000, files: int = 8,
                       append_pct: float = 0.02) -> dict:
    """Incremental result maintenance (exec/incremental.py): time a
    FULL aggregate refresh vs the DELTA refresh after a ~2% append to
    the same watched dataset, parity-asserted against each other.  The
    delta path must be >= 3x faster (ISSUE 15 acceptance): its scan,
    decode, upload and update work scale with the appended bytes, not
    the dataset."""
    import shutil

    from spark_rapids_tpu import TpuSparkSession
    from spark_rapids_tpu.exec import incremental as inc
    from spark_rapids_tpu.obs import registry as obsreg
    from spark_rapids_tpu.serve import result_cache

    root = tempfile.mkdtemp(prefix="bench_inc_")
    try:
        _write_dataset(root, n, files)
        s = TpuSparkSession({
            "spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
        from spark_rapids_tpu import functions as F
        df = (s.read.parquet(root).group_by("ss_item_sk")
              .agg(F.count("*").alias("cnt"),
                   F.sum("ss_quantity").alias("qty")))
        names = tuple(df.plan.schema.names)
        result_cache.configure(True, 256 << 20)
        maint = inc.IncrementalMaintainer(s)
        key = "bench-incremental"
        # capture run: warms compiles + the scan-plan cache, retains
        # the merged partial state
        stamps = inc.current_stamps(df.plan)
        sub, ctx = maint.prepare(df.plan, key, names, stamps)
        assert ctx is not None and ctx.mode == "capture"
        maint.finish(ctx, s._execute(sub))
        # two ~2% appends: the FIRST delta refresh warms the delta-
        # shaped programs (a steady stream of similar-size appends is
        # the workload this path exists for — its first-ever delta pays
        # one-time compiles exactly like the first-ever full run did),
        # the SECOND is the timed steady-state refresh
        def append(i: int, seed: int):
            extra = _gen_store_sales(max(int(n * append_pct), 1000),
                                     seed=seed)
            papq.write_table(extra, os.path.join(
                root, f"part-{files + i:05d}.parquet"),
                row_group_size=1 << 20)

        def delta_refresh():
            stamps_now = inc.current_stamps(df.plan)
            sub_d, ctx_d = maint.prepare(df.plan, key, names,
                                         stamps_now)
            assert ctx_d is not None and ctx_d.mode == "delta", \
                "append did not classify as a delta"
            return maint.finish(ctx_d, s._execute(sub_d))

        append(0, seed=97)
        delta_refresh()                    # warm the delta shapes
        append(1, seed=131)
        reg_view = obsreg.get_registry().view()
        t0 = time.perf_counter()
        delta_table = delta_refresh()
        delta_ms = (time.perf_counter() - t0) * 1e3
        d = reg_view.delta()["counters"]
        t0 = time.perf_counter()
        full_table = s._execute(inc.repin_plan(df.plan))
        full_ms = (time.perf_counter() - t0) * 1e3
        assert delta_table.sort_by("ss_item_sk").equals(
            full_table.sort_by("ss_item_sk")), \
            "incremental refresh diverges from full recompute"
        speedup = full_ms / max(delta_ms, 1e-6)
        assert speedup >= 3.0, (
            f"delta refresh only {speedup:.2f}x faster than full "
            f"recompute ({delta_ms:.0f} vs {full_ms:.0f} ms)")
        result_cache.clear()
        return {
            "rows": n, "files": files,
            "append_pct": append_pct,
            "full_refresh_ms": round(full_ms, 1),
            "delta_refresh_ms": round(delta_ms, 1),
            "speedup": round(speedup, 2),
            "delta_batches": int(d.get("incremental.deltaBatches", 0)),
            "rows_match": True,
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main() -> None:
    import spark_rapids_tpu  # noqa: F401 (x64, compile cache)

    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    n = int(args[0]) if args else 2_880_000  # SF1 store_sales slice
    files = 8
    smoke = "--smoke" in sys.argv
    profile_out = None
    concurrent_n = None    # None = flag absent; 0 = explicitly off
    serve_n = 0            # --serve=N remote clients; 0 = off
    fleet_n = 0            # --fleet=N serve replicas; 0 = off
    trend_out = "BENCH_trend.json"   # --trend-out= overrides
    for a in sys.argv[1:]:
        if a.startswith("--profile-out="):
            profile_out = a.split("=", 1)[1]
        elif a.startswith("--concurrent="):
            concurrent_n = int(a.split("=", 1)[1])
        elif a.startswith("--serve="):
            serve_n = int(a.split("=", 1)[1])
        elif a.startswith("--fleet="):
            fleet_n = int(a.split("=", 1)[1])
        elif a.startswith("--trend-out="):
            trend_out = a.split("=", 1)[1]
    if smoke:
        n = 160_000
        if concurrent_n is None:
            # the trend file tracks queue-wait percentiles; a smoke run
            # (the CI path) exercises a small concurrent batch so the
            # scheduler columns are populated, not null — an explicit
            # --concurrent=0 still suppresses the probe
            concurrent_n = 4
    concurrent_n = concurrent_n or 0
    with tempfile.TemporaryDirectory(prefix="tpcds_q6_") as root:
        nbytes = _write_dataset(root, n, files)
        if profile_out:
            _write_profile(root, profile_out)
        cpu_time, cpu_table = _time_engine_cpu(root)
        per_query, (host_prep_s, host_prep_warm_s), tpu_table = \
            _device_pipeline_metric(root)

        cpu_sorted = cpu_table.sort_by("ss_item_sk")
        tpu_sorted = tpu_table.rename_columns(
            list(cpu_table.column_names)).sort_by("ss_item_sk")
        rows_match = (cpu_sorted.num_rows == tpu_sorted.num_rows and
                      cpu_sorted.column("cnt").equals(
                          tpu_sorted.column("cnt")) and
                      cpu_sorted.column("qty").equals(
                          tpu_sorted.column("qty")) and
                      np.allclose(
                          cpu_sorted.column("aesp").to_numpy(
                              zero_copy_only=False),
                          tpu_sorted.column("aesp").to_numpy(
                              zero_copy_only=False),
                          rtol=1e-9, equal_nan=True))

        concurrent = None
        shuffle_probe = None
        if concurrent_n:
            concurrent = _concurrent_probe(root, concurrent_n)
            # the pipelined-exchange block rides the same flag: a
            # --concurrent run (and the CI smoke) always records the
            # shuffle overlap/compression trend columns
            shuffle_probe = _shuffle_pipeline_probe(concurrent_n)

        serve = None
        if serve_n:
            serve = _serve_probe(root, serve_n)

        # horizontally scaled serve tier: cache-miss-heavy prepared
        # statements, 1 replica vs N through the router (>= 2x qps at
        # N>=3 asserted inside)
        fleet = None
        if fleet_n:
            fleet = _fleet_probe(root, fleet_n)

        # multi-query work sharing: 8 concurrent identical clients,
        # sharing off vs on (>= 3x asserted inside, bit-identical)
        sharing = _sharing_probe(root, 8)

        # out-of-core + skew-resilient joins: seeded skewed fact join,
        # splitting off vs on (>= 1.5x reduce-stage critical path
        # asserted inside) and unconstrained vs 4x-over-budget grace
        join_probe = _join_probe(12_000 if smoke else 24_000)

    if not rows_match:
        print(json.dumps({"error": "TPU/CPU result mismatch — no "
                          "performance number is reported for an "
                          "incorrect pipeline",
                          "rows_match": False}))
        sys.exit(1)

    # fusion-on vs fusion-off dispatch counts on their own small
    # dataset (asserts parity + the >=30% dispatch-reduction contract);
    # AFTER the rows_match gate so a probe assertion can never mask the
    # structured mismatch report downstream tooling parses
    dispatch_probe = _dispatch_count_probe()

    # per-backend kernel timings (kernel.backend xla vs pallas);
    # parity-asserted inside; a kernel the compiler refuses raises
    kernels = _kernel_backend_probe(1 << 15 if smoke else 1 << 17)

    # incremental maintenance: full vs delta refresh after a ~2%
    # append (>= 3x asserted inside; parity-asserted against the full
    # recompute)
    incremental = _incremental_probe(
        80_000 if smoke else 160_000, files=8)

    gbps = nbytes / per_query / 1e9
    result = {
        "metric": "TPC-DS q6-class device pipeline over parquet "
                  f"({n} rows, {files} files, {nbytes >> 20} MiB): "
                  "page decode+filter+hash-agg per query "
                  "(fori-loop harness, see PERF.md)",
        "value": round(gbps, 3),
        "unit": "GB/s",
        "vs_baseline": round(cpu_time / per_query, 3),
        "tpu_pipeline_ms": round(per_query * 1e3, 2),
        "cpu_wall_s": round(cpu_time, 4),
        "host_prep_s": round(host_prep_s, 3),
        "host_prep_warm_s": round(host_prep_warm_s, 3),
        "rows_match": bool(rows_match),
        "dispatch_probe": dispatch_probe,
        "kernels": kernels,
        "incremental": incremental,
        "concurrent": concurrent,
        "shuffle": shuffle_probe,
        "serve": serve,
        "fleet": fleet,
        "sharing": sharing,
        "join": join_probe,
        "profile_out": profile_out,
    }
    print(json.dumps(result))
    _write_trend_file(result, n=n, files=files, smoke=smoke,
                      out_name=trend_out)


def _git_commit() -> str:
    """Short commit hash stamped into trend records (None when the
    bench runs outside a git checkout)."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or None
    except Exception:
        return None


def _compile_totals() -> dict:
    """Compile-observatory totals for the trend record (obs/compile.py
    + the cache-tier counters), so the compile bill rides the same
    rolling series the throughput numbers do."""
    try:
        from spark_rapids_tpu.obs import compile as obscompile
        from spark_rapids_tpu.obs import registry as obsreg
        c = obsreg.get_registry().snapshot()["counters"]
        t = obscompile.totals()
        return {
            "programs_compiled": int(c.get("kernel.cache.compiles", 0)),
            "persistent_reloads":
                int(c.get("kernel.cache.persistentHits", 0)),
            "compile_wall_ms": t.get("compile_wall_ms"),
            "families": t.get("families"),
        }
    except Exception:
        return {}


def _write_trend_file(result: dict, n: int, files: int,
                      smoke: bool,
                      out_name: str = "BENCH_trend.json") -> str:
    """Machine-readable trend series at the repo root (name set by
    ``--trend-out=``, default BENCH_trend.json): ONE rolling file,
    schema spark-rapids-tpu-bench-trend/3 — each bench run APPENDS a
    record (suite timings, dispatch counts, per-backend kernel
    timings, queue-wait percentiles, compile-observatory totals)
    stamped with the current commit (and a PR label when SRT_BENCH_PR
    is set), so the perf trajectory across PRs is machine-readable
    from a single rolling series — `BENCH_trend.json` is the one
    canonical trend file (earlier per-PR snapshot files were folded
    into it and deleted)."""
    probe = result.get("dispatch_probe") or {}
    conc = result.get("concurrent") or {}
    kern = result.get("kernels") or {}
    shuf = result.get("shuffle") or {}
    record = {
        "pr": os.environ.get("SRT_BENCH_PR"),
        "commit": _git_commit(),
        "generated_unix": time.time(),
        "config": {"rows": n, "files": files, "smoke": smoke},
        "suite_timings": {
            "tpu_pipeline_ms": result.get("tpu_pipeline_ms"),
            "cpu_wall_s": result.get("cpu_wall_s"),
            "host_prep_s": result.get("host_prep_s"),
            "host_prep_warm_s": result.get("host_prep_warm_s"),
            "throughput_gbps": result.get("value"),
            "vs_baseline": result.get("vs_baseline"),
        },
        "dispatch_counts": {
            "fused": (probe.get("fused") or {}).get("dispatches"),
            "unfused": (probe.get("unfused") or {}).get("dispatches"),
            "dispatch_drop_pct": probe.get("dispatch_drop_pct"),
            "dispatches_saved":
                (probe.get("fused") or {}).get("dispatches_saved"),
        },
        "queue_wait": {
            "n_queries": conc.get("n_queries"),
            "max_concurrent": conc.get("max_concurrent"),
            "queries_per_sec": conc.get("queries_per_sec"),
            "p50_ms": conc.get("queue_wait_p50_ms"),
            "p95_ms": conc.get("queue_wait_p95_ms"),
        },
        # per-probe e2e latency quantiles (concurrent window) plus the
        # run-wide SLO histograms — the trend carries quantiles, not
        # just means (ISSUE 18)
        "latency": conc.get("latency") or {},
        "slo": _slo_quantiles(),
        # per-backend kernel.backend timings (decode / aggregate) +
        # gathers-per-element accounting (the PR-9 headline) and the
        # PR-14 HBM->VMEM streaming-tiler volume (tile counts/bytes +
        # tile-plan memo hits) across the probe window
        "kernels": {
            "decode_xla_ms": kern.get("decode_xla_ms"),
            "decode_pallas_ms": kern.get("decode_pallas_ms"),
            "agg_xla_ms": kern.get("agg_xla_ms"),
            "agg_pallas_ms": kern.get("agg_pallas_ms"),
            "gathers_per_element": kern.get("gathers_per_element"),
            "tiles": kern.get("tiles"),
            "rows": kern.get("rows"),
            "rows_match": kern.get("rows_match"),
            "error": kern.get("error"),
        },
        # the pipelined process-transport exchange (ISSUE 13): qps
        # sequential vs pipelined+lz4, how much of the look-ahead's
        # background wall the consumer never waited out, and the
        # compressed wire leg's shrink
        "shuffle": {
            "n_queries": shuf.get("n_queries"),
            "sequential_qps": shuf.get("sequential_qps"),
            "pipelined_qps": shuf.get("pipelined_qps"),
            "overlap_ms": shuf.get("overlap_ms"),
            "overlap_ratio": shuf.get("overlap_ratio"),
            "wire_raw_bytes": shuf.get("wire_raw_bytes"),
            "wire_bytes": shuf.get("wire_bytes"),
            "wire_compression_ratio":
                shuf.get("wire_compression_ratio"),
        },
        # incremental result maintenance (ISSUE 15): full vs delta
        # refresh wall after a ~2% append, and the measured speedup
        "incremental": {
            "full_refresh_ms":
                (result.get("incremental") or {}).get("full_refresh_ms"),
            "delta_refresh_ms":
                (result.get("incremental") or {}).get(
                    "delta_refresh_ms"),
            "speedup": (result.get("incremental") or {}).get("speedup"),
            "append_pct":
                (result.get("incremental") or {}).get("append_pct"),
        },
        # horizontally scaled serve fleet (ISSUE 20): cache-miss-heavy
        # prepared statements through the router, 1 replica vs N —
        # qps scaling plus the fleet-merged serve-latency p95
        "fleet": {
            "n_replicas": (result.get("fleet") or {}).get("n_replicas"),
            "single_qps": ((result.get("fleet") or {}).get("single")
                           or {}).get("qps"),
            "fleet_qps": ((result.get("fleet") or {}).get("fleet")
                          or {}).get("qps"),
            "speedup": (result.get("fleet") or {}).get("speedup"),
            "single_p95_ms": ((result.get("fleet") or {}).get("single")
                              or {}).get("latency_p95_ms"),
            "fleet_p95_ms": ((result.get("fleet") or {}).get("fleet")
                             or {}).get("latency_p95_ms"),
        },
        # multi-query work sharing (ISSUE 16): N concurrent identical
        # clients, sharing off vs on, and the single-flight collapse
        "sharing": {
            "n_clients": (result.get("sharing") or {}).get("n_clients"),
            "qps_off": (result.get("sharing") or {}).get("qps_off"),
            "qps_on": (result.get("sharing") or {}).get("qps_on"),
            "speedup": (result.get("sharing") or {}).get("speedup"),
            "dedup_hits":
                (result.get("sharing") or {}).get("dedup_hits"),
        },
        # out-of-core + skew-resilient joins (ISSUE 19): skewed-vs-
        # uniform reduce balance with hot-bucket splitting, and the
        # grace join's overhead at ~4x over the build budget
        "join": {
            "skew_off_qps":
                (result.get("join") or {}).get("skew_off_qps"),
            "skew_on_qps":
                (result.get("join") or {}).get("skew_on_qps"),
            "reduce_critical_path_improvement":
                (result.get("join") or {}).get(
                    "reduce_critical_path_improvement"),
            "hot_buckets": (result.get("join") or {}).get("hot_buckets"),
            "splits": (result.get("join") or {}).get("splits"),
            "oocore_overhead_pct":
                (result.get("join") or {}).get("oocore_overhead_pct"),
            "grace_partitions":
                (result.get("join") or {}).get("grace_partitions"),
            "grace_spilled_bytes":
                (result.get("join") or {}).get("grace_spilled_bytes"),
        },
        "compile": _compile_totals(),
        "rows_match": result.get("rows_match"),
    }
    return append_trend_record(record, out_name)


def append_trend_record(record: dict,
                        out_name: str = "BENCH_trend.json") -> str:
    """Append one record to the rolling trend series — the ONE writer
    of the 'spark-rapids-tpu-bench-trend/3' file (bench runs append
    their run records here, so schema/locking/corrupt-handling changes
    happen in one place)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        out_name)
    series = {"schema": "spark-rapids-tpu-bench-trend/3", "runs": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                loaded = json.load(f)
            if isinstance(loaded, dict) and \
                    isinstance(loaded.get("runs"), list):
                series["runs"] = loaded["runs"]
            elif isinstance(loaded, dict) and "suite_timings" in loaded:
                # a stray trend/1 or trend/2 single-record file under
                # this name: fold it in as the series' first run rather
                # than destroying the measurement
                series["runs"] = [loaded]
        except Exception:
            # unreadable (e.g. a previous run was killed mid-write):
            # preserve the evidence instead of clobbering history
            try:
                os.replace(path, path + ".corrupt")
            except OSError:
                pass
    series["runs"].append(record)
    # temp-file + rename: a run killed mid-dump must never truncate
    # the rolling series it exists to preserve
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(series, f, indent=2)
        f.write("\n")
    os.replace(tmp, path)
    return path


if __name__ == "__main__":
    main()
