"""Live operational telemetry: HTTP endpoint, flight recorder,
cross-process trace stitching, slow-query log.

PR 3's obs layer is per-query and post-hoc; these tests cover the
always-on layer above it — the Prometheus/queries/profiles endpoint
(obs/server.py), the flight recorder's failure bundles
(obs/recorder.py, driven through the PR 1 fault-injection harness),
and the executor->driver span round trip that puts process-shuffle map
stages on their own lanes in the query's Chrome trace.
"""

import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import TpuSparkSession, col, functions as F
from spark_rapids_tpu.obs import recorder as obsrec
from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.obs import trace as obstrace
from spark_rapids_tpu.obs.server import parse_prometheus, render_prometheus


@pytest.fixture(autouse=True)
def _clean_obs_state():
    yield
    obsrec.disable()
    obstrace.configure(False)


def _get(port, path, timeout=10):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
        return r.status, r.read().decode()


def _data(n=2000, seed=5):
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": pa.array(rng.integers(0, 9, n).astype(np.int64)),
        "v": pa.array(rng.integers(0, 500, n).astype(np.int64)),
    })


def _agg(s, t, parts=3):
    return (s.create_dataframe(t, num_partitions=parts)
            .group_by("k")
            .agg(F.count("*").alias("c"), F.sum("v").alias("sv")))


# ---------------------------------------------------------------------------
# Prometheus rendering + HTTP endpoint
# ---------------------------------------------------------------------------

def test_prometheus_rendering_parses_and_sanitizes():
    reg = obsreg.MetricsRegistry()
    reg.inc("scan.planCacheHits", 7)
    reg.set_gauge("sched.admittedBytes", 123456789)
    reg.observe("sched.queueWait", 2.5e6)
    reg.observe("sched.queueWait", 1.5e6)
    text = render_prometheus(reg.snapshot())
    samples = parse_prometheus(text)
    assert samples["spark_rapids_tpu_scan_planCacheHits"] == 7
    assert samples["spark_rapids_tpu_sched_admittedBytes"] == 123456789
    assert samples["spark_rapids_tpu_sched_queueWait_count"] == 2
    assert samples["spark_rapids_tpu_sched_queueWait_sum"] == 4e6
    # the '.' never leaks into a metric name
    assert "." not in text.split(" ")[0]
    assert "# TYPE spark_rapids_tpu_scan_planCacheHits counter" in text


def test_http_endpoint_routes_and_profile_ring():
    s = TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        "spark.rapids.tpu.obs.http.enabled": True,
    })
    try:
        port = s.obs_server.port
        assert port > 0
        code, body = _get(port, "/healthz")
        assert code == 200 and json.loads(body)["ok"]

        t = _data()
        fut = s.submit(_agg(s, t))
        out = fut.result(timeout=120)
        assert out.num_rows

        code, body = _get(port, "/metrics")
        assert code == 200
        samples = parse_prometheus(body)
        assert samples["spark_rapids_tpu_sched_submitted"] >= 1
        assert samples["spark_rapids_tpu_sched_running"] == 0

        code, body = _get(port, "/queries")
        rows = json.loads(body)["queries"]
        mine = [r for r in rows if r["query_id"] == fut.query_id]
        assert mine and mine[0]["state"] == "success"
        assert "estimate_bytes" in mine[0]
        assert "queue_wait_ms" in mine[0]
        assert "priority" in mine[0]

        code, body = _get(port, f"/profiles/{fut.query_id}")
        prof = json.loads(body)
        assert prof["query_id"] == fut.query_id
        assert prof["status"] == "success"
        assert "wall_breakdown" in prof

        for bad in ("/profiles/999999", "/profiles/zzz", "/nope"):
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(port, bad)
            assert e.value.code == 404
    finally:
        s.obs_server.shutdown()


def test_http_endpoint_off_by_default():
    s = TpuSparkSession({})
    assert s.obs_server is None
    assert s.flight_recorder is None
    # and the recorder hot hook is a no-op bool check
    assert not obsrec.is_enabled()
    obsrec.record_event("anything", x=1)  # must not raise


def test_queries_table_tracks_states():
    s = TpuSparkSession({
        "spark.rapids.tpu.sched.maxConcurrent": 1,
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    })
    t = _data()
    futs = [s.submit(_agg(s, t)) for _ in range(3)]
    # while the 1-slot engine drains, the table must never lose a
    # query; the concurrency bound is asserted on the controller's
    # locked stats (a finishing row can still read "running" for a
    # moment after its slot released — benign, but a row-count assert
    # on it would be flaky)
    deadline = time.time() + 120
    while not all(f.done() for f in futs):
        rows = {r["query_id"]: r for r in s.scheduler.query_table()}
        assert all(f.query_id in rows for f in futs)
        assert s.scheduler.controller.stats()["running"] <= 1
        assert time.time() < deadline, "queries never drained"
        time.sleep(0.01)
    for f in futs:
        f.result(timeout=120)
    rows = {r["query_id"]: r for r in s.scheduler.query_table()}
    for f in futs:
        assert rows[f.query_id]["state"] == "success"
        assert rows[f.query_id]["wall_s"] >= 0


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

def test_recorder_ring_bounded_and_disabled_noop():
    rec = obsrec.configure("/tmp/unused", max_events=32)
    for i in range(200):
        obsrec.record_event("test.evt", i=i)
    evts = rec.events()
    assert len(evts) == 32
    assert evts[-1]["i"] == 199      # oldest dropped, newest kept
    assert evts[0]["i"] == 168
    obsrec.disable()
    obsrec.record_event("test.evt", i=-1)
    assert obsrec.get_recorder() is None


def test_flight_recorder_bundle_on_injected_fetch_fault(tmp_path):
    """The ISSUE acceptance case: kill a shuffle fetch mid-query with
    the PR 1 fault harness (every DATA frame dropped, retries and the
    CPU fallback disabled), and assert a complete, parseable bundle
    lands in obs.recorder.dir."""
    from spark_rapids_tpu.shuffle import faults, procpool
    from spark_rapids_tpu.shuffle.iterator import (
        RapidsShuffleFetchFailedException, RapidsShuffleTimeoutException)

    faults.set_fault_plan(faults.FaultPlan.parse(
        "seed=8;tcp.client.data:drop@1:x100000"))
    rec_dir = str(tmp_path / "recorder")
    try:
        s = TpuSparkSession({
            "spark.rapids.tpu.shuffle.transport": "process",
            "spark.rapids.tpu.shuffle.transport.processExecutors": 2,
            "spark.rapids.tpu.sql.shuffle.partitions": 3,
            "spark.rapids.tpu.shuffle.readTimeoutMs": 300,
            "spark.rapids.tpu.shuffle.fetch.maxRetries": 0,
            "spark.rapids.tpu.shuffle.fetch.cpuFallbackEnabled": False,
            "spark.rapids.tpu.obs.recorder.dir": rec_dir,
        })
        assert s.flight_recorder is not None
        with pytest.raises((RapidsShuffleFetchFailedException,
                            RapidsShuffleTimeoutException)):
            _agg(s, _data(seed=23)).collect()
    finally:
        faults.set_fault_plan(None)
        faults.reset_fault_stats()
        procpool.reset_executor_pool()

    bundles = sorted(os.listdir(rec_dir))
    assert bundles, "no flight-recorder bundle written"
    bundle = os.path.join(rec_dir, bundles[-1])
    assert "-failure-" in bundles[-1]

    prof = json.load(open(os.path.join(bundle, "profile.json")))
    assert prof["status"] == "failure"
    assert prof["error"]
    assert "RapidsShuffle" in prof["error"]

    trace = json.load(open(os.path.join(bundle, "trace.json")))
    assert "traceEvents" in trace

    events = [json.loads(line) for line in
              open(os.path.join(bundle, "events.jsonl"))]
    kinds = {e["kind"] for e in events}
    assert "sched.submitted" in kinds
    assert "sched.admitted" in kinds
    assert all("ts_unix" in e and "t_ns" in e for e in events)

    config = json.load(open(os.path.join(bundle, "config.json")))
    assert config["spark.rapids.tpu.shuffle.fetch.maxRetries"] == 0
    assert config["spark.rapids.tpu.obs.recorder.dir"] == rec_dir

    registry = json.load(open(os.path.join(bundle, "registry.json")))
    assert "counters" in registry and "gauges" in registry


def test_recorder_bundle_reason_classification(tmp_path):
    """Timeout/cancellation failures name their reason in the bundle
    directory (classification is by exception type NAME, keeping obs a
    leaf package)."""
    from spark_rapids_tpu.sched.cancel import (QueryCancelledError,
                                               QueryTimeoutError)
    s = TpuSparkSession({
        "spark.rapids.tpu.obs.recorder.dir": str(tmp_path),
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    })
    _agg(s, _data()).collect()
    prof = s.last_query_profile()
    rec = s.flight_recorder
    assert "-timeout-" in os.path.basename(
        rec.dump_bundle(prof, reason=obsrec._classify(
            QueryTimeoutError("deadline"))))
    assert "-cancelled-" in os.path.basename(
        rec.dump_bundle(prof, reason=obsrec._classify(
            QueryCancelledError("user"))))
    assert "-failure-" in os.path.basename(
        rec.dump_bundle(prof, reason=obsrec._classify(
            ValueError("boom"))))
    assert obsrec._classify(None) == "oom-retry"


# ---------------------------------------------------------------------------
# Cross-process trace stitching
# ---------------------------------------------------------------------------

def test_record_foreign_shifts_and_labels_lanes():
    obstrace.configure(True, buffer_spans=4096)
    obstrace.clear()
    foreign = [
        (0, 111, "map.work", "exec", 1000, 500, 1, {"x": 1}),
        (1, 111, "map.inner", "exec", 1100, 100, 2, None),
        (2, 222, "map.other", "exec", 1200, 50, 1, None),
    ]
    n = obstrace.record_foreign(foreign, offset_ns=10_000,
                                label="executor-0 pid=42")
    assert n == 3
    spans = obstrace.snapshot()
    by_name = {s[2]: s for s in spans}
    # timestamps shifted into the local clock domain
    assert by_name["map.work"][4] == 11_000
    assert by_name["map.inner"][4] == 11_100
    # the two foreign threads map to two distinct local lanes, labeled
    lanes = {by_name["map.work"][1], by_name["map.other"][1]}
    assert len(lanes) == 2
    labels = {obstrace.lane_label(t) for t in lanes}
    assert labels == {"executor-0 pid=42", "executor-0 pid=42/t1"}
    # span args carry the lane label for profile-level assertions
    assert by_name["map.other"][7]["lane"].startswith("executor-0")
    # chrome export names the lanes via thread_name metadata
    trace = obstrace.chrome_trace(spans)
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} == labels
    b = sum(1 for e in trace["traceEvents"] if e["ph"] == "B")
    e = sum(1 for e in trace["traceEvents"] if e["ph"] == "E")
    assert b == e == 3


def test_record_foreign_noop_when_disabled():
    obstrace.configure(False)
    assert obstrace.record_foreign(
        [(0, 1, "x", "exec", 0, 1, 1, None)], 0, "lane") == 0


def test_process_shuffle_trace_stitching_roundtrip():
    """A process-transport query's Chrome trace shows executor-side
    map-stage spans on their own labeled lanes, clock-aligned into the
    driver's window."""
    from spark_rapids_tpu.shuffle import procpool
    try:
        s = TpuSparkSession({
            "spark.rapids.tpu.shuffle.transport": "process",
            "spark.rapids.tpu.shuffle.transport.processExecutors": 2,
            "spark.rapids.tpu.sql.shuffle.partitions": 3,
            "spark.rapids.tpu.obs.trace.enabled": True,
            "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        })
        out = _agg(s, _data(seed=31)).collect()
        assert out.num_rows
        prof = s.last_query_profile()
        assert prof is not None

        stitched = [sp for sp in prof.spans
                    if (sp.get("args") or {}).get(
                        "lane", "").startswith("executor-")]
        assert stitched, ("no executor-side spans stitched into the "
                          "query window")
        # clock alignment: stitched spans land inside the driver-side
        # query window (generous slack for the probe's error bound)
        driver_ts = [sp["ts_ns"] for sp in prof.spans
                     if "lane" not in (sp.get("args") or {})]
        lo, hi = min(driver_ts), max(driver_ts)
        for sp in stitched:
            assert lo - 1e9 <= sp["ts_ns"] <= hi + 1e9, sp

        # the Chrome trace renders them as named lanes
        trace = obstrace.chrome_trace(prof._raw_spans)
        meta = [e for e in trace["traceEvents"] if e["ph"] == "M"
                and e["args"]["name"].startswith("executor-")]
        assert meta, "no executor lane metadata in the chrome trace"
        lane_tids = {e["tid"] for e in meta}
        lane_events = [e for e in trace["traceEvents"]
                       if e["ph"] in "BE" and e["tid"] in lane_tids]
        assert lane_events, "executor lanes are empty"
        b = sum(1 for e in trace["traceEvents"] if e["ph"] == "B")
        e = sum(1 for e in trace["traceEvents"] if e["ph"] == "E")
        assert b == e and b > 0
    finally:
        procpool.reset_executor_pool()


# ---------------------------------------------------------------------------
# Slow-query log
# ---------------------------------------------------------------------------

def test_slow_query_log_jsonl_schema(tmp_path):
    log = str(tmp_path / "slow.jsonl")
    s = TpuSparkSession({
        "spark.rapids.tpu.obs.slowQueryMs": 1,    # everything is slow
        "spark.rapids.tpu.obs.slowQueryPath": log,
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    })
    _agg(s, _data()).collect()
    _agg(s, _data()).collect()
    lines = [json.loads(line) for line in open(log)]
    assert len(lines) == 2
    for rec in lines:
        for key in ("ts_unix", "query_id", "status", "wall_s",
                    "queue_wait_s", "result_rows", "phases",
                    "wall_breakdown", "threshold_ms"):
            assert key in rec, f"slow-query record missing {key}"
        assert rec["status"] == "success"
        assert rec["wall_s"] >= 0.001


def test_slow_query_log_threshold_filters(tmp_path):
    log = str(tmp_path / "slow.jsonl")
    s = TpuSparkSession({
        "spark.rapids.tpu.obs.slowQueryMs": 10 ** 9,  # nothing is slow
        "spark.rapids.tpu.obs.slowQueryPath": log,
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    })
    _agg(s, _data()).collect()
    assert not os.path.exists(log)


# ---------------------------------------------------------------------------
# Satellites: prefetch stall labels, donation-disarm visibility
# ---------------------------------------------------------------------------

def test_prefetch_stall_span_names_source():
    from spark_rapids_tpu.exec.scans import ScanPrefetcher
    obstrace.configure(True, buffer_spans=4096)
    obstrace.clear()

    def slow():
        time.sleep(0.05)
        return "x"

    pf = ScanPrefetcher([slow, slow], depth=1,
                        labels=["part-0.parquet#rg0",
                                "part-0.parquet#rg1"])
    try:
        assert pf.get(0) == "x"      # consumer outruns the window
        assert pf.get(1) == "x"
    finally:
        pf.close()
    stalls = [s for s in obstrace.snapshot()
              if s[2] == "scan.prefetchStall"]
    assert stalls, "no stall span despite an outrun prefetcher"
    for s in stalls:
        assert s[7]["src"].startswith("part-0.parquet#rg")
        assert "batch" in s[7]
    prefetches = [s for s in obstrace.snapshot()
                  if s[2] == "scan.prefetch"]
    assert all("src" in s[7] for s in prefetches)


def test_donation_is_cache_state_independent():
    """donate_ok does not depend on the persistent compile cache, and
    a donating kernel is observed by the compile observatory like any
    other program (it records a compile, fresh or persistent)."""
    import jax.numpy as jnp
    from spark_rapids_tpu.exec import fused_stage, kernel_cache as kc
    from spark_rapids_tpu.exec.base import PhysicalPlan

    class HostToDeviceExec(PhysicalPlan):   # allowlisted producer name
        pass

    # producer safe, plan-stamped on -> donation ARMS
    assert fused_stage.donate_ok(HostToDeviceExec(), True) is True
    # and a knob-off plan never donates
    assert fused_stage.donate_ok(HostToDeviceExec(), False) is False

    TpuSparkSession({})          # observatory on (its default)
    reg = obsreg.get_registry()
    base = (reg.counter("kernel.cache.compiles") +
            reg.counter("kernel.cache.persistentHits"))
    donating = kc.get_kernel(
        ("test_obs_donating", 1), lambda: (lambda x: x + 7),
        oom_retry=False, donate_argnums=(0,))
    assert int(donating(jnp.arange(8))[3]) == 10
    assert (reg.counter("kernel.cache.compiles") +
            reg.counter("kernel.cache.persistentHits")) == base + 1
