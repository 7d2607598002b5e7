#!/usr/bin/env bash
# CI gate (reference analog: jenkins/spark-premerge-build.sh:24-30 —
# build + full test suite + a smoke benchmark, red on any failure).
set -euo pipefail
cd "$(dirname "$0")"

echo "== lint (syntax + import sanity) =="
python -m compileall -q spark_rapids_tpu tests __graft_entry__.py
if python -c "import pyflakes" 2>/dev/null; then
    python -m pyflakes spark_rapids_tpu tests __graft_entry__.py \
        || exit 1
fi

echo "== generated docs up to date =="
python - <<'EOF'
import io, subprocess, sys
cur = open("docs/configs.md").read()
new = subprocess.run([sys.executable, "-m", "spark_rapids_tpu.config"],
                     capture_output=True, text=True).stdout
if cur != new:
    sys.exit("docs/configs.md is stale: run "
             "python -m spark_rapids_tpu.config > docs/configs.md")
EOF

echo "== full test suite (one process) =="
python -m pytest tests/ -q

echo "== graft entry + multichip dryrun =="
python - <<'EOF'
import jax
import __graft_entry__ as g
fn, args = g.entry()
jax.jit(fn)(*args)
g.dryrun_multichip(8)
EOF

echo "== fusion fallback parity (sql.fusion.enabled=false vs fused) =="
python - <<'EOF'
# the unfused per-node path is the fused path's correctness oracle;
# running one real query both ways in CI keeps the fallback from
# silently rotting (and asserts fusion actually engages + saves
# dispatches, via the obs registry)
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from spark_rapids_tpu import TpuSparkSession, col, functions as F
from spark_rapids_tpu.obs import registry as obsreg

def query(s):
    df = s.create_dataframe(
        {"k": [i % 7 for i in range(2000)],
         "x": [float(i % 100) for i in range(2000)],
         "s": [f"v{i % 13}" for i in range(2000)]},
        num_partitions=3)
    return (df.with_column("y", col("x") * 2.0 + 1.0)
              .filter(col("y") > 20.0)
              .with_column("z", col("y") - col("k"))
              .group_by("k")
              .agg(F.count("*").alias("n"), F.sum("z").alias("sz"))
              .sort("k"))

runs = {}
for fused in (True, False):
    s = TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        "spark.rapids.tpu.sql.fusion.enabled": fused})
    view = obsreg.get_registry().view()
    runs[fused] = (query(s).collect(),
                   view.delta()["counters"].get("kernel.dispatches", 0))
fused_t, fused_d = runs[True]
plain_t, plain_d = runs[False]
assert fused_t.equals(plain_t), (
    "fusion on/off results diverge:\n"
    f"fused={fused_t.to_pydict()}\nunfused={plain_t.to_pydict()}")
assert fused_d < plain_d, (
    f"fusion saved no dispatches ({fused_d} vs {plain_d})")
print(f"fusion parity OK; dispatches {plain_d} -> {fused_d}")
EOF

echo "== concurrency smoke (8 async queries, sched.maxConcurrent=3, live /metrics + /queries scrape) =="
timeout 300 python - <<'EOF'
# N=8 mixed TPC-like queries through the concurrent query scheduler
# (sched/service.py): serial first (the oracle), then all submitted at
# once via collect_async under sched.maxConcurrent=3.  Asserts
# bit-identical results, zero deadlocks (the outer `timeout 300` is the
# hard wall-clock bound, each future waits at most 120s), and that at
# least one profile attributes real queue wait.  The telemetry endpoint
# (obs/server.py) serves throughout: /metrics and /queries are scraped
# DURING the concurrent batch and validated after it — Prometheus
# exposition must parse and the query table must account for every
# submission.
import json, os, time, urllib.request
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from spark_rapids_tpu import TpuSparkSession, col, functions as F
# the strict exposition linter runs on EVERY scrape: TYPE coverage,
# cumulative _bucket series ending at le="+Inf", +Inf == _count
from spark_rapids_tpu.obs.server import lint_exposition

s = TpuSparkSession({
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    "spark.rapids.tpu.sched.maxConcurrent": 3,
    "spark.rapids.tpu.obs.http.enabled": True})

_base_url = f"http://127.0.0.1:{s.obs_server.port}"
def scrape(path):
    with urllib.request.urlopen(_base_url + path, timeout=10) as r:
        return r.read().decode()

lint_exposition(scrape("/metrics"))  # serves before any query

def base(n):
    return s.create_dataframe(
        {"k": [i % 7 for i in range(n)],
         "x": [float(i % 100) for i in range(n)],
         "s": [f"v{i % 13}" for i in range(n)]},
        num_partitions=3)

def q_filter_agg(n):
    return (base(n).with_column("y", col("x") * 2.0 + 1.0)
            .filter(col("y") > 20.0).group_by("k")
            .agg(F.count("*").alias("c"), F.sum("y").alias("sy"))
            .sort("k"))

def q_shuffle_agg(n):
    return (base(n).repartition(4, "k").group_by("k")
            .agg(F.avg("x").alias("ax")).sort("k"))

def q_project_sort(n):
    return (base(n).with_column("z", col("x") - col("k"))
            .filter(col("z") > 5.0).sort("z", "k").limit(50))

def q_distinct(n):
    return base(n).select("s").distinct().sort("s")

queries = [q(1500 + 100 * i) for i, q in enumerate(
    [q_filter_agg, q_shuffle_agg, q_project_sort, q_distinct] * 2)]
serial = [q.collect() for q in queries]

futs = [q.collect_async() for q in queries]
# live scrape DURING the concurrent batch: the running count must
# respect sched.maxConcurrent and the table must see the submissions.
# The bound is asserted on the sched.running GAUGE (published under
# the controller lock, refreshed at scrape time) — per-row future
# states have a benign finish window where a completing query still
# reads "running" after its admission slot was already released, so a
# row-count assert would be flaky.
seen_running = 0
while not all(f.done() for f in futs):
    live = lint_exposition(scrape("/metrics"))
    running = live.get("spark_rapids_tpu_sched_running", 0)
    assert running <= 3, f"maxConcurrent=3 violated: {running}"
    seen_running = max(seen_running, int(running))
    rows = json.loads(scrape("/queries"))["queries"]
    assert all(r["state"] in ("queued", "running", "success")
               for r in rows), rows
    # the compile observatory serves mid-batch too (obs/compile.py)
    comp_live = json.loads(scrape("/compiles"))
    assert comp_live["enabled"] and "churn" in comp_live, comp_live
    time.sleep(0.05)
tables = [f.result(timeout=120) for f in futs]
for i, (a, b) in enumerate(zip(serial, tables)):
    assert a.equals(b), (
        f"query {i}: concurrent result differs from serial\n"
        f"serial={a.to_pydict()}\nconcurrent={b.to_pydict()}")

waits = [(f.profile.metrics["sched"]["sched.queueWaitNs"]
          if f.profile is not None else 0) for f in futs]
assert any(w > 0 for w in waits), (
    "no query recorded queue wait despite 8 submissions at "
    f"maxConcurrent=3: {waits}")

# post-run endpoint validation: the exposition's submitted counter and
# the query table must both account for every submission this session
# made (8 serial collects + 8 async = 16, no queued/running leftovers)
metrics = lint_exposition(scrape("/metrics"))
submitted = metrics.get("spark_rapids_tpu_sched_submitted", 0)
assert submitted == 16, f"sched_submitted={submitted}, expected 16"
assert metrics.get("spark_rapids_tpu_sched_running") == 0
rows = json.loads(scrape("/queries"))["queries"]
done = [r for r in rows if r["state"] == "success"]
assert len(done) == 16, [r["state"] for r in rows]
assert not [r for r in rows if r["state"] in ("queued", "running")]
# the profile ring serves over HTTP too
qid = done[-1]["query_id"]
prof = json.loads(scrape(f"/profiles/{qid}"))
assert prof["query_id"] == qid and prof["status"] == "success"

# compile-observatory contract (obs/compile.py): every compiled
# program in the ledger must carry the triggering query's id AND its
# canonical plan digest — a compile that escapes attribution would
# make the compile bill un-billable
comp = json.loads(scrape("/compiles?n=4096"))
evs = comp["events"]
assert evs, "no compile events despite 16 cold-ish queries"
unattributed = [e for e in evs
                if not e.get("query_id") or not e.get("plan_digest")]
assert not unattributed, f"unattributed compiles: {unattributed[:3]}"
assert comp["totals"]["events"] >= len(evs) > 0
assert comp["churn"], "empty churn report despite compile events"

# repeated-query probe: a NEW plan shape compiles programs on its
# first run and must report ZERO fresh compiles on its second (the
# in-memory kernel-cache tier, kernel.cache.memHits)
from spark_rapids_tpu.obs import registry as obsreg
probe = (base(2500).with_column("w", col("x") * col("x") + 3.0)
         .group_by("k").agg(F.min("w").alias("mn"),
                            F.avg("w").alias("aw")).sort("k"))
v1 = obsreg.get_registry().view()
first = probe.collect()
d1 = v1.delta()["counters"]
assert d1.get("kernel.cache.compiles", 0) > 0, (
    f"probe's first run compiled nothing — the repeat check would be "
    f"vacuous: {d1}")
v2 = obsreg.get_registry().view()
second = probe.collect()
d2 = v2.delta()["counters"]
assert first.equals(second)
assert d2.get("kernel.cache.compiles", 0) == 0, (
    f"repeated query re-compiled fresh programs: {d2}")
assert d2.get("kernel.cache.persistentHits", 0) == 0, d2
assert d2.get("kernel.cache.memHits", 0) > 0, d2
row = max(json.loads(scrape("/queries"))["queries"],
          key=lambda r: r["query_id"])   # the probe's second run
assert row["kernels_compiled"] is None and row["compile_ms"] is None, row

s.obs_server.shutdown()
print(f"concurrency smoke OK: 8/8 bit-identical, "
      f"max queue wait {max(waits) / 1e6:.1f}ms, "
      f"peak running seen {seen_running}, endpoint validated, "
      f"{len(evs)} compiles attributed, repeat probe 0 fresh compiles")
EOF

echo "== serving smoke (3 remote clients, prepared + ad-hoc + result-cache hit, live /metrics scrape) =="
timeout 300 python - <<'EOF'
# the multi-tenant serving front-end (serve/): an ephemeral-port server
# over one engine session, driven by 3 concurrent remote clients —
# one ad-hoc, one prepared with two bindings, one repeating a query to
# assert a result-set-cache hit with ZERO incremental device
# dispatches and zero scheduler submissions.  /metrics is scraped
# DURING the run; every remote result is checked bit-identical to the
# in-process collect() oracle.
import json, os, tempfile, threading, urllib.request
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import pyarrow as pa, pyarrow.parquet as papq
from spark_rapids_tpu import TpuSparkSession
from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.obs.server import lint_exposition
from spark_rapids_tpu.serve.client import ServeClient

root = tempfile.mkdtemp(prefix="serve_smoke_")
papq.write_table(pa.table({
    "k": [i % 9 for i in range(6000)],
    "x": [float((i * 7) % 250) for i in range(6000)]}),
    os.path.join(root, "t.parquet"))
s = TpuSparkSession({
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    "spark.rapids.tpu.serve.enabled": True,
    "spark.rapids.tpu.obs.http.enabled": True})
s.register_view("t", s.read.parquet(root))

ADHOC = ("select k, count(*) as c, sum(x) as sx from t "
         "where x > 40.0 group by k order by k")
PREP = ("select k, sum(x) as sx from t where x > :lo "
        "group by k order by k")
HOT = "select k, max(x) as mx from t group by k order by k"
oracle_adhoc = s.sql(ADHOC).collect()
oracle_prep = {lo: s.sql(PREP.replace(":lo", repr(lo))).collect()
               for lo in (30.0, 120.0)}
oracle_hot = s.sql(HOT).collect()

port = s.serve_server.port
results, errors = {}, []

def adhoc_client():
    with ServeClient("127.0.0.1", port) as c:
        results["adhoc"] = [c.sql(ADHOC) for _ in range(2)]

def prepared_client():
    with ServeClient("127.0.0.1", port) as c:
        h = c.prepare(PREP, params={"lo": "double"})
        results["prep"] = {lo: h.execute({"lo": lo})
                           for lo in (30.0, 120.0)}

def hot_client():
    with ServeClient("127.0.0.1", port) as c:
        first = c.sql(HOT)                 # populates the result cache
        view = obsreg.get_registry().view()
        second = c.sql(HOT)                # must be served from it
        d = view.delta()["counters"]
        assert d.get("kernel.dispatches", 0) == 0, (
            f"result-cache hit dispatched kernels: {d}")
        assert d.get("serve.resultCacheHits", 0) == 1, d
        assert d.get("sched.submitted", 0) == 0, d
        results["hot"] = [first, second]

def run(fn):
    def wrapped():
        try:
            fn()
        except Exception as e:
            errors.append(f"{fn.__name__}: {type(e).__name__}: {e}")
    t = threading.Thread(target=wrapped)
    t.start()
    return t

threads = [run(adhoc_client), run(prepared_client)]
# live scrape while the first two clients are in flight: the
# exposition must pass the strict linter (lint_exposition raises on a
# malformed line or family) and already carry the serving gauges
with urllib.request.urlopen(
        f"http://127.0.0.1:{s.obs_server.port}/metrics", timeout=10) as r:
    live = lint_exposition(r.read().decode())
assert "spark_rapids_tpu_serve_activeSessions" in live, sorted(live)[:20]
for t in threads:
    t.join(timeout=240)
threads = [run(hot_client)]
for t in threads:
    t.join(timeout=240)
assert not errors, errors

for got in results["adhoc"]:
    assert got.equals(oracle_adhoc), "ad-hoc result diverges"
for lo, got in results["prep"].items():
    assert got.equals(oracle_prep[lo]), f"prepared({lo}) diverges"
for got in results["hot"]:
    assert got.equals(oracle_hot), "hot-query result diverges"

# post-run exposition: serving counters made it to /metrics
with urllib.request.urlopen(
        f"http://127.0.0.1:{s.obs_server.port}/metrics", timeout=10) as r:
    m = lint_exposition(r.read().decode())
assert m.get("spark_rapids_tpu_serve_sessions", 0) >= 3, m
assert m.get("spark_rapids_tpu_serve_statementsPrepared", 0) >= 1
assert m.get("spark_rapids_tpu_serve_resultCacheHits", 0) >= 1
assert m.get("spark_rapids_tpu_serve_streamedBatches", 0) >= 5
# the live /queries table attributed the remote sessions
with urllib.request.urlopen(
        f"http://127.0.0.1:{s.obs_server.port}/queries", timeout=10) as r:
    rows = json.loads(r.read().decode())["queries"]
served = [r for r in rows if r.get("session_id")]
assert served and all(r["plan_digest"] for r in served), rows
s.serve_server.shutdown()
s.obs_server.shutdown()
print(f"serving smoke OK: 3 clients bit-identical, "
      f"cache hit with 0 incremental dispatches, "
      f"{int(m.get('spark_rapids_tpu_serve_streamedBatches', 0))} "
      f"chunks streamed")
EOF

echo "== serve-chaos gate (3 clients under a seeded fault plan + drain/restart, bit-identical resumes, leak gauges zero) =="
timeout 300 python - <<'EOF'
# the hardened serving plane under its own fault harness
# (serve/faults.py): a seeded plan drops streamed chunks, kills
# connections mid-stream and fails session lookups while 3 reconnecting
# clients run repeated queries — every result must be BIT-IDENTICAL to
# the in-process oracle (the chunk sequence numbers make resumes
# duplicate-free by construction).  Then one graceful drain/restart
# cycle mid-stream: the successor server answers the resume on the same
# port, the stream completes bit-identical, and the drained server's
# leak audit (connections / streamer threads / admission slots /
# sessions) reads all-zero.
import os, threading, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from spark_rapids_tpu import TpuSparkSession
from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.serve.client import ServeClient

s = TpuSparkSession({
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    "spark.rapids.tpu.serve.enabled": True,
    "spark.rapids.tpu.serve.stream.chunkRows": 120,
    "spark.rapids.tpu.serve.test.faultPlan":
        "seed=5;stream.chunk:drop@3;stream.chunk:close@9:x2;"
        "session.lookup:fail@6"})
df = s.create_dataframe(
    {"k": [i % 7 for i in range(1200)],
     "x": [float(i % 50) for i in range(1200)],
     "v": [f"s{i % 11}" for i in range(1200)]},
    num_partitions=3)
s.register_view("t", df)

QUERIES = [
    "select k, x, v from t order by k, x, v",
    "select k, count(*) as c, sum(x) as sx from t "
    "where x > 5.0 group by k order by k",
    "select v, count(*) as c from t group by v order by v"]
oracles = [s.sql(q).collect() for q in QUERIES]
port = s.serve_server.port
results, errors = {}, []

def chaos_client(i):
    try:
        with ServeClient("127.0.0.1", port, reconnect=True,
                         max_reconnects=8, backoff_s=0.05) as c:
            results[i] = [c.sql(QUERIES[i]) for _ in range(3)]
    except Exception as e:
        errors.append(f"client {i}: {type(e).__name__}: {e}")

threads = [threading.Thread(target=chaos_client, args=(i,))
           for i in range(3)]
for t in threads:
    t.start()
for t in threads:
    t.join(timeout=240)
assert not errors, errors
for i, oracle in enumerate(oracles):
    for got in results[i]:
        assert got.num_rows == oracle.num_rows, (
            f"client {i}: duplicate/missing chunks "
            f"({got.num_rows} vs {oracle.num_rows} rows)")
        assert got.equals(oracle), f"client {i} diverges under faults"
c0 = obsreg.get_registry().snapshot()["counters"]
injected = int(c0.get("serve.faults.injected", 0))
assert injected >= 1, f"fault plan never fired: {c0}"

# drain/restart cycle mid-stream (the plan re-arms fresh on the
# successor — the resumed leg runs under chaos too)
cli = ServeClient("127.0.0.1", port, reconnect=True,
                  max_reconnects=8, backoff_s=0.05)
stream = cli.sql_stream(QUERIES[0], credit=2)
it = iter(stream)
pieces = [next(it)]
old = s.serve_server

def swap():
    time.sleep(0.05)
    s.restart_serve_server(drain_deadline_ms=200)

t = threading.Thread(target=swap)
t.start()
for tbl in it:
    pieces.append(tbl)
t.join(60)
import pyarrow as pa
got = pa.concat_tables(pieces)
assert got.num_rows == oracles[0].num_rows, "resume duplicated chunks"
assert got.equals(oracles[0]), "resumed stream not bit-identical"
assert s.serve_server.port == port, "successor changed ports"
leaks = old.leak_stats()
assert leaks["connections"] == 0, leaks
assert leaks["streamer_threads"] == 0, leaks
assert leaks["inflight"] == 0, leaks
assert leaks["sessions"] == 0, leaks
cli.close()
# the successor's teardown is async after the client close: poll the
# leak gauges back to zero
deadline = time.time() + 30
while time.time() < deadline:
    live = s.serve_server.leak_stats()
    if live["connections"] == 0 and live["streamer_threads"] == 0 \
            and live["inflight"] == 0:
        break
    time.sleep(0.05)
live = s.serve_server.leak_stats()
assert live["connections"] == 0, live
assert live["streamer_threads"] == 0, live
assert live["inflight"] == 0, live
c = obsreg.get_registry().snapshot()["counters"]
assert int(c.get("serve.drains", 0)) == 1, c
resumed = int(c.get("serve.resumedStreams", 0))
s.serve_server.shutdown()
print(f"serve-chaos gate OK: 3 clients x3 queries bit-identical under "
      f"{int(c.get('serve.faults.injected', 0))} injected faults, "
      f"drain/restart resume bit-identical ({resumed} server-side "
      f"resumes), leak gauges zero")
EOF

echo "== incremental-maintenance gate (append probe: delta bit-identical, zero old-file walks, refresher observed) =="
timeout 300 python - <<'EOF'
# ISSUE 15 acceptance: after an append to a cached aggregate query's
# watched sources, the refresh recomputes ONLY the delta row groups —
# the page-walk counter (scan metadata cache disabled, so every
# scanned chunk walks) must show exactly the delta file's chunks and
# zero reads of unchanged files — with results bit-identical to the
# full recompute, and the background refresher must be OBSERVED
# keeping the entry warm off the serving path.
import json, os, tempfile, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import pyarrow as pa, pyarrow.parquet as papq
from spark_rapids_tpu import TpuSparkSession, functions as F
from spark_rapids_tpu.io import parquet_meta as pqm
from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.serve.client import ServeClient

root = tempfile.mkdtemp(prefix="inc_gate_")
def write(i, n0, n):
    papq.write_table(pa.table({
        "k": pa.array([j % 9 for j in range(n0, n0 + n)],
                      type=pa.int64()),
        "x": pa.array([(j * 7) % 250 for j in range(n0, n0 + n)],
                      type=pa.int64())}),
        os.path.join(root, f"part-{i:03d}.parquet"))
for i in range(4):
    write(i, i * 3000, 3000)

s = TpuSparkSession({
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    "spark.rapids.tpu.serve.enabled": True,
    # every scanned chunk page-walks, so the counter is the proof
    "spark.rapids.tpu.sql.scan.metadataCache.enabled": False,
    "spark.rapids.tpu.serve.incremental.refreshMs": 100})
s.register_view("t", s.read.parquet(root))
Q = ("select k, count(*) as c, sum(x) as sx, min(x) as mn, "
     "max(x) as mx from t group by k")
def oracle():
    return (s.read.parquet(root).group_by("k")
            .agg(F.count("*").alias("c"), F.sum("x").alias("sx"),
                 F.min("x").alias("mn"), F.max("x").alias("mx"))
            .collect().sort_by("k"))

reg = obsreg.get_registry()
with ServeClient("127.0.0.1", s.serve_server.port) as c:
    first = c.sql(Q)
    assert first.sort_by("k").equals(oracle()), "capture run diverges"

    # ~2% append -> the next lookup must delta-refresh, reading ONLY
    # the appended file's row groups
    write(4, 12000, 250)
    w0 = pqm.walk_count()
    v = reg.view()
    got = c.sql(Q)
    walked = pqm.walk_count() - w0
    d = v.delta()["counters"]
    assert d.get("serve.incremental.hits") == 1, d
    assert d.get("serve.incremental.deltaFiles") == 1, d
    assert d.get("serve.incremental.deltaBatches", 0) >= 1, d
    # the delta file has 2 leaf columns x 1 row group = 2 chunk walks;
    # any old-file row-group read would add to the counter
    assert walked == 2, f"delta refresh walked {walked} chunks (want 2)"
    assert got.sort_by("k").equals(oracle()), (
        "incremental result diverges from full recompute")

    # background refresher: append while idle, observe a refresh run,
    # then the client lookup must hit warm with ZERO dispatches
    write(5, 12250, 250)
    deadline = time.time() + 60
    while time.time() < deadline:
        if reg.snapshot()["counters"].get(
                "serve.incremental.refreshRuns", 0) >= 1:
            break
        time.sleep(0.05)
    runs = reg.snapshot()["counters"].get(
        "serve.incremental.refreshRuns", 0)
    assert runs >= 1, "no refresher run observed within 60s"
    v2 = reg.view()
    warm = c.sql(Q)
    d2 = v2.delta()["counters"]
    assert d2.get("serve.resultCacheHits") == 1, d2
    assert d2.get("kernel.dispatches", 0) == 0, (
        f"post-refresh lookup dispatched kernels: {d2}")
    assert warm.sort_by("k").equals(oracle()), "refreshed entry diverges"
s.serve_server.shutdown()
print(f"incremental gate OK: delta walked 2/2 delta chunks "
      f"(0 old-file reads), bit-identical, {runs} refresher run(s), "
      f"warm hit with 0 dispatches")
EOF

echo "== work-sharing gate (8 concurrent identical -> single-flight: one execution, bit-identical, zero follower dispatches) =="
timeout 300 python - <<'EOF'
# ISSUE 16 contract: N concurrent identical deterministic submissions
# collapse to ONE execution.  A plan listener parks the leader at plan
# time so all 7 followers provably join the open flight (no timing
# luck); the followers' dispatch bill must be ZERO — the 8-way batch
# pays exactly one serial run's kernel.dispatches.
import os, threading, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from spark_rapids_tpu import TpuSparkSession, col, functions as F
from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.sched import cancel as sched_cancel

s = TpuSparkSession({
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": True})

def query():
    df = s.create_dataframe(
        {"k": [i % 9 for i in range(3000)],
         "x": [float(i % 83) for i in range(3000)]},
        num_partitions=3)
    return (df.filter(col("x") > 7.0).group_by("k")
            .agg(F.sum("x").alias("sx"), F.count("*").alias("c"))
            .sort("k"))

serial = query().collect()                 # warm compiles
view = obsreg.get_registry().view()
serial2 = query().collect()
one_exec = view.delta()["counters"].get("kernel.dispatches", 0)
assert serial2.equals(serial)

class Parker:
    def __init__(self):
        self.release = threading.Event()
        self.parked = threading.Semaphore(0)
    def __call__(self, result):
        self.parked.release()
        tok = sched_cancel.current()
        deadline = time.time() + 60
        while not self.release.is_set() and time.time() < deadline:
            if tok is not None and tok.is_cancelled:
                return
            time.sleep(0.005)

parker = Parker()
s.add_plan_listener(parker)
reg = obsreg.get_registry()
view = reg.view()
try:
    leader = query().collect_async()
    assert parker.parked.acquire(timeout=30), "leader never planned"
    followers = [query().collect_async() for _ in range(7)]
    deadline = time.time() + 20
    while reg.counter("sched.dedup.hits") < 7 and \
            time.time() < deadline:
        time.sleep(0.01)
finally:
    parker.release.set()
tables = [leader.result(timeout=300)] + \
    [f.result(timeout=300) for f in followers]
for i, t in enumerate(tables):
    assert t.equals(serial), f"shared result {i} diverges"
d = view.delta()["counters"]
assert d.get("sched.dedup.flights", 0) == 1, d
assert d.get("sched.dedup.hits", 0) == 7, d
got = d.get("kernel.dispatches", 0)
assert got == one_exec, (
    f"8-way batch dispatched {got} kernels, one serial run costs "
    f"{one_exec} — followers executed instead of subscribing")
for f in followers:
    assert f.profile.metrics["sharing"][
        "sched.dedup.leaderQueryId"] == leader.query_id
print(f"work-sharing gate OK: 8 concurrent identical -> 1 execution "
      f"({got} dispatches == serial bill), 7 dedup hits, "
      f"bit-identical")
EOF

echo "== tenant ledger exactness gate (single-flight + batched statements -> per-tenant sum == global counter delta) =="
timeout 300 python - <<'EOF'
# ISSUE 18 contract: the ResourceLedger's accounting identity.  Over a
# mixed window — an 8-way single-flight in-process batch (leader + 7
# followers billed equal shares of ONE execution) plus one 3-way
# batched prepared-statement execution (members billed by row share) —
# the sum of per-tenant kernel.dispatches across /tenants rows must
# equal the global kernel.dispatches counter delta EXACTLY: nothing
# dropped, nothing double-billed.
import json, os, threading, time, urllib.request
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from spark_rapids_tpu import TpuSparkSession, col, functions as F
from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.sched import cancel as sched_cancel
from spark_rapids_tpu.serve.client import ServeClient

s = TpuSparkSession({
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    "spark.rapids.tpu.obs.http.enabled": True,
    "spark.rapids.tpu.serve.enabled": True,
    # maxStatements=3 flushes deterministically on the third binding;
    # the cache must not satisfy the bindings before the batcher does
    "spark.rapids.tpu.serve.batch.windowMs": 2000,
    "spark.rapids.tpu.serve.batch.maxStatements": 3,
    "spark.rapids.tpu.serve.resultCache.enabled": False})

def scrape(path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{s.obs_server.port}{path}",
            timeout=10) as r:
        return r.read().decode()

def tenant_sum(snap, metric):
    return sum(r["usage"].get(metric, 0.0) for r in snap["tenants"])

df = s.create_dataframe(
    {"k": [i % 7 for i in range(2400)],
     "x": [float(i % 50) for i in range(2400)]},
    num_partitions=3)
s.register_view("t", df)

def query():
    return (df.filter(col("x") > 7.0).group_by("k")
            .agg(F.sum("x").alias("sx"), F.count("*").alias("c"))
            .sort("k"))

query().collect()                          # warm compiles
time.sleep(0.2)                            # let the warm-up bill fold

reg = obsreg.get_registry()
base_snap = json.loads(scrape("/tenants"))
base_global = reg.counter("kernel.dispatches")

# leg 1: 8-way single-flight — leader parked at plan time so all 7
# followers provably join the open flight (the work-sharing idiom)
class Parker:
    def __init__(self):
        self.release = threading.Event()
        self.parked = threading.Semaphore(0)
    def __call__(self, result):
        self.parked.release()
        tok = sched_cancel.current()
        deadline = time.time() + 60
        while not self.release.is_set() and time.time() < deadline:
            if tok is not None and tok.is_cancelled:
                return
            time.sleep(0.005)

parker = Parker()
s.add_plan_listener(parker)
try:
    leader = query().collect_async()
    assert parker.parked.acquire(timeout=30), "leader never planned"
    followers = [query().collect_async() for _ in range(7)]
    deadline = time.time() + 20
    while reg.counter("sched.dedup.hits") < 7 and \
            time.time() < deadline:
        time.sleep(0.01)
finally:
    parker.release.set()
for f in [leader] + followers:
    assert f.result(timeout=300).num_rows
s.remove_plan_listener(parker)

# leg 2: one 3-way batched prepared-statement execution
TEMPLATE = "select k, x from t where x > :lo"
clients = [ServeClient("127.0.0.1", s.serve_server.port)
           for _ in range(3)]
handles = [cl.prepare(TEMPLATE, {"lo": "double"}) for cl in clients]
los = [5.0, 10.0, 20.0]
out = [None] * 3
def run(i):
    out[i] = handles[i].execute({"lo": los[i]})
threads = [threading.Thread(target=run, args=(i,)) for i in range(3)]
for t in threads:
    t.start()
for t in threads:
    t.join()
assert all(o is not None and o.num_rows for o in out)
for cl in clients:
    cl.close()
time.sleep(0.2)                            # let the last bills fold

snap = json.loads(scrape("/tenants"))
global_delta = reg.counter("kernel.dispatches") - base_global
ledger_delta = tenant_sum(snap, "kernel.dispatches") - \
    tenant_sum(base_snap, "kernel.dispatches")
assert global_delta > 0
assert abs(ledger_delta - global_delta) < 1e-6, (
    f"ledger identity broken: per-tenant sum moved {ledger_delta}, "
    f"global kernel.dispatches moved {global_delta}")
# the batched bindings appear as per-session template rows, and the
# batch paid one vectorized execution between them
tpl_rows = [r for r in snap["tenants"] if r["workload"] == TEMPLATE]
assert len(tpl_rows) == 3, [
    (r["session_id"], r["workload"]) for r in snap["tenants"]]
assert reg.counter("serve.batch.vectorizedExecutions") == 1
assert reg.counter("sched.dedup.hits") >= 7
s.serve_server.shutdown()
print(f"ledger exactness gate OK: per-tenant sum delta "
      f"{ledger_delta:.3f} == global delta {global_delta:.3f} "
      f"(8-way flight + 3-way batch), 3 template rows")
EOF

echo "== drift sentinel probe (serve-fault slow action -> exactly one slo bundle; control run silent) =="
timeout 300 python - <<'EOF'
# ISSUE 18 contract: the drift sentinel fires ONCE per sustained
# episode, with flight-recorder attribution — and a healthy control
# run fires never.  Latency degradation is injected with the serving
# fault plan's SLOW action (a server-side per-chunk sleep), so the
# regression the watcher sees is real wire latency, deterministic by
# plan.  Ticks are driven synchronously — the same unit the sentinel
# thread loops — so the windows are exact, not timing luck.
import json, os, tempfile
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from spark_rapids_tpu import TpuSparkSession
from spark_rapids_tpu.obs import recorder as obsrec
from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.obs.sentinel import DriftSentinel
from spark_rapids_tpu.serve.client import ServeClient

bundles = tempfile.mkdtemp(prefix="sentinel_probe_")
obsrec.configure(bundles)
reg = obsreg.get_registry()
SQL = ("select k, sum(x) as sx from t where x > 5.0 "
       "group by k order by k")

def make_session(fault_plan=""):
    s = TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        "spark.rapids.tpu.serve.enabled": True,
        "spark.rapids.tpu.serve.resultCache.enabled": False,
        "spark.rapids.tpu.serve.test.faultPlan": fault_plan})
    df = s.create_dataframe(
        {"k": [i % 7 for i in range(900)],
         "x": [float(i % 50) for i in range(900)]},
        num_partitions=2)
    s.register_view("t", df)
    return s

def traffic(s, n=4):
    with ServeClient("127.0.0.1", s.serve_server.port) as c:
        for _ in range(n):
            assert c.sql(SQL).num_rows

healthy = make_session()
traffic(healthy)                           # warm compiles pre-arming

# control: healthy traffic only — the watcher must stay silent
control = DriftSentinel(rules="latency:factor=3,sustain=2,min=3")
control.tick()                             # arming tick
for _ in range(4):
    traffic(healthy)
    assert control.tick() == []
assert reg.counter("obs.sentinel.breaches") == 0

# probe: same config, healthy baseline then SLOW-degraded windows
probe = DriftSentinel(rules="latency:factor=3,sustain=2,min=3")
probe.tick()
for _ in range(3):
    traffic(healthy)
    assert probe.tick() == []
healthy.serve_server.shutdown()

# every streamed chunk now sleeps 250ms server-side
slow = make_session("seed=7;stream.chunk:slow:d250:x100000")
opened = []
for _ in range(3):                         # sustained degradation
    traffic(slow, n=3)
    opened += probe.tick()
assert opened == ["latency"], opened       # exactly ONE episode
assert reg.counter("obs.sentinel.breaches.latency") == 1
assert reg.counter("obs.sentinel.breaches") == 1
slo_bundles = [b for b in os.listdir(bundles) if "-slo-" in b]
assert len(slo_bundles) == 1, slo_bundles
with open(os.path.join(bundles, slo_bundles[0],
                       "sentinel.json")) as f:
    payload = json.load(f)
assert payload["rules"] == ["latency"]
assert payload["top_talkers"], "breach bundle lost its attribution"
slow.serve_server.shutdown()
print("sentinel probe OK: 1 slo bundle, breaches.latency=1, "
      "control run silent")
EOF

echo "== shape-erased ABI collapse gate (>=4x fewer programs, bit-identical) =="
timeout 560 python - <<'EOF'
# the serving-shaped probe: ONE query family over 2 schemas x 2 value
# ranges x 2 batch sizes (the variance multi-tenant serving traffic
# actually shows) runs in two fresh subprocesses — kernel.abi.enabled
# off (the pre-ABI oracle) and on — and the erased ABI must compile
# >= 4x fewer distinct programs for bit-identical results
# (ISSUE 12 / ROADMAP item 2 acceptance).
import json, os, subprocess, sys, tempfile

PROBE = r'''
import json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
abi = sys.argv[1] == "on"
from spark_rapids_tpu import TpuSparkSession, col, functions as F
from spark_rapids_tpu.obs import registry as obsreg
s = TpuSparkSession({
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    "spark.rapids.tpu.kernel.abi.enabled": abi})
def q(df, k, x):
    return (df.with_column("y", col(x) * 2.0 + 1.0)
              .filter(col("y") > 20.0)
              .with_column("z", col("y") - col(k))
              .group_by(k).agg(F.count("*").alias("n"),
                               F.sum("z").alias("sz"))
              .sort(k))
view = obsreg.get_registry().view()
results = []
for names in (("k", "x"), ("a", "b")):       # schema drift
    for scale in (1, 900):                   # value-range drift
        for n in (2200, 4200):               # batch-size drift
            df = s.create_dataframe(
                {names[0]: [(i % 7) * scale for i in range(n)],
                 names[1]: [float(i % 100) for i in range(n)]},
                num_partitions=2)
            results.append(list(q(df, *names).collect()
                                .to_pydict().values()))
d = view.delta()["counters"]
print(json.dumps({"programs": int(d.get("kernel.cache.compiles", 0)),
                  "results": results}))
'''
def run(mode):
    with tempfile.NamedTemporaryFile("w", suffix=".py",
                                     delete=False) as f:
        f.write(PROBE)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.getcwd()     # probe runs from a temp file
    out = subprocess.run([sys.executable, f.name, mode],
                         capture_output=True, text=True, env=env,
                         cwd=os.getcwd())
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])

off, on = run("off"), run("on")
assert on["results"] == off["results"], (
    "erased-ABI results diverge from the pre-ABI oracle")
ratio = off["programs"] / max(on["programs"], 1)
assert ratio >= 4.0, (
    f"ABI collapse below the 4x gate: {off['programs']} -> "
    f"{on['programs']} programs ({ratio:.2f}x)")
print(f"ABI collapse OK: {off['programs']} -> {on['programs']} "
      f"distinct programs ({ratio:.2f}x), 8/8 bit-identical")
EOF

echo "== corpus-replay warm-start gate (restart-sim: zero fresh compiles on /compiles) =="
timeout 560 python - <<'EOF'
# ROADMAP item 2's replica-restart contract: process A runs a probe
# suite with a persistent XLA cache dir + the precompile corpus;
# process B (fresh, same cache dir) replays the corpus through the AOT
# precompile service BEFORE serving, then re-runs the probe and must
# report ZERO fresh compiles on /compiles — persistent reloads only,
# every one of them paid off the serving path by the replay thread.
import json, os, subprocess, sys, tempfile

work = tempfile.mkdtemp(prefix="warm_gate_")
env = dict(os.environ)
env.update({"JAX_PLATFORMS": "cpu",
            "PYTHONPATH": os.getcwd(),   # probes run from temp files
            "JAX_COMPILATION_CACHE_DIR": os.path.join(work, "xla")})
corpus = os.path.join(work, "corpus.jsonl")

COMMON = r'''
import json, os, sys, urllib.request
corpus = sys.argv[1]
from spark_rapids_tpu import TpuSparkSession, col, functions as F
def probe(s):
    out = []
    for n in (1800, 3000):
        df = s.create_dataframe(
            {"k": [i % 6 for i in range(n)],
             "x": [float(i % 120) for i in range(n)]},
            num_partitions=2)
        out.append(list((df.with_column("y", col("x") * 1.5 + 2.0)
                         .filter(col("y") > 30.0)
                         .group_by("k").agg(F.count("*").alias("c"),
                                            F.sum("y").alias("sy"))
                         .sort("k")).collect().to_pydict().values()))
    return out
'''

A = COMMON + r'''
s = TpuSparkSession({
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    "spark.rapids.tpu.sql.fusion.donateInputs": False,
    "spark.rapids.tpu.obs.compile.corpusPath": corpus})
res = probe(s)
recs = [json.loads(l) for l in open(corpus)]
progs = [p for r in recs for p in r.get("programs", [])]
assert progs, "probe wrote no corpus programs"
assert any(p.get("replay") for p in progs), "no replay payloads"
print(json.dumps({"results": res, "programs": len(progs)}))
'''

B = COMMON + r'''
s = TpuSparkSession({
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    "spark.rapids.tpu.sql.fusion.donateInputs": False,
    "spark.rapids.tpu.obs.http.enabled": True,
    "spark.rapids.tpu.sched.precompile.enabled": True,
    "spark.rapids.tpu.sched.precompile.corpusPath": corpus,
    "spark.rapids.tpu.sched.precompile.idleWaitMs": 0})
svc = s.precompile_service
assert svc is not None and svc.wait(timeout=300), "replay did not finish"
stats = svc.stats()
assert stats["warmed"] > 0 and stats["failed"] == 0, stats
res = probe(s)                     # the restarted replica's first queries
with urllib.request.urlopen(
        f"http://127.0.0.1:{s.obs_server.port}/compiles?n=0",
        timeout=10) as r:
    comp = json.loads(r.read().decode())
fresh = {q: rec for q, rec in comp["per_query"].items()
         if rec["kernels_compiled"]}
assert not fresh, f"probe queries compiled FRESH after replay: {fresh}"
reloads = sum(rec["persistent_reloads"]
              for rec in comp["per_query"].values())
assert reloads > 0, comp["per_query"]
s.obs_server.shutdown()
print(json.dumps({"results": res, "warmed": stats["warmed"],
                  "reloads": reloads}))
'''

def run(code):
    with tempfile.NamedTemporaryFile("w", suffix=".py",
                                     delete=False) as f:
        f.write(code)
    out = subprocess.run([sys.executable, f.name, corpus],
                         capture_output=True, text=True, env=env,
                         cwd=os.getcwd())
    assert out.returncode == 0, (out.stderr[-2000:] or out.stdout[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])

a, b = run(A), run(B)
assert a["results"] == b["results"], "restart-sim results diverge"
print(f"warm-start gate OK: {a['programs']} corpus programs, "
      f"{b['warmed']} warmed by replay, {b['reloads']} persistent "
      f"reloads, 0 fresh compiles on the probe re-run")
EOF

echo "== pipelined-shuffle gate (depth=2 vs 0 bit-identical, overlap>0, codec parity) =="
timeout 560 python - <<'EOF'
# the sequential barrier exchange (shuffle.pipeline.depth=0) is the
# pipelined data plane's correctness oracle (the sql.fusion.enabled
# pattern): one process-transport shuffle query runs sequential,
# pipelined, and pipelined+lz4 — all three must be BIT-IDENTICAL, the
# pipelined run must show real overlap (shuffle.pipeline.overlapNs>0:
# background prefetch wall the consumer did not wait out), the
# compressed run must actually shrink the wire leg, and a fault-free
# run must not retry or stall (regression: the make_client dial race
# clobbered the server's DATA routing and surfaced exactly here).
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import pyarrow as pa
from spark_rapids_tpu import TpuSparkSession, functions as F
from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.shuffle import faults

rng = np.random.default_rng(17)
n = 6000
t = pa.table({
    "k": pa.array(rng.integers(0, 13, n).astype(np.int64)),
    "v": pa.array(rng.integers(0, 1000, n).astype(np.int64))})
BASE = {
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    "spark.rapids.tpu.shuffle.transport": "process",
    "spark.rapids.tpu.shuffle.transport.processExecutors": 2,
    "spark.rapids.tpu.sql.shuffle.partitions": 3,
}

def run(depth, codec):
    faults.reset_fault_stats()
    s = TpuSparkSession(dict(BASE, **{
        "spark.rapids.tpu.shuffle.pipeline.depth": depth,
        "spark.rapids.tpu.shuffle.compression.codec": codec}))
    view = obsreg.get_registry().view()
    out = (s.create_dataframe(t, num_partitions=3)
           .group_by("k")
           .agg(F.count("*").alias("c"), F.sum("v").alias("sv"))
           .sort("k")).collect()
    d = view.delta()["counters"]
    stats = faults.get_fault_stats()
    assert stats.get("retries") == 0 and stats.get("timeouts") == 0, (
        f"fault-free run retried/stalled (depth={depth}, "
        f"codec={codec}): {stats}")
    return out, d

seq, _ = run(0, "none")
piped, d = run(2, "none")
assert piped.equals(seq), "pipelined result diverges from sequential"
overlap = d.get("shuffle.pipeline.overlapNs", 0)
assert overlap > 0, f"no overlap observed on the pipelined run: {d}"
lz4, dz = run(2, "lz4")
assert lz4.equals(seq), "compressed result diverges"
wire, raw = dz.get("shuffle.wire.wireBytes", 0), \
    dz.get("shuffle.wire.rawBytes", 0)
assert 0 < wire < raw, f"wire leg did not shrink: {wire} vs {raw}"
from spark_rapids_tpu.shuffle import procpool
procpool.reset_executor_pool()
print(f"pipelined-shuffle gate OK: 3/3 bit-identical, "
      f"overlap {overlap / 1e6:.1f}ms, wire {raw} -> {wire} bytes "
      f"({raw / wire:.2f}x)")
EOF

echo "== pipelined fault smoke (drop / kill / fallback / cancel with the pipeline pinned on) =="
timeout 560 python -m pytest tests/test_shuffle_pipeline.py -q \
    -k "drop or kill or fallback or cancel"

echo "== out-of-core join gate (4x over budget: bit-identical, spill counters > 0, zero leaked catalog entries) =="
timeout 560 python - <<'EOF'
# the unconstrained gather (buildSideBudgetBytes=-1) is the grace
# join's correctness oracle (the sql.fusion.enabled pattern): one
# seeded zipf join runs unconstrained, then under a budget ~4x smaller
# than its build side — bit-identical after sort-normalization, the
# grace counters proving the partitions really spilled and
# re-streamed, and the spill catalog owning ZERO grace-priority
# entries after the query drains (the leak contract).
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import pyarrow as pa
from spark_rapids_tpu import TpuSparkSession, col
from spark_rapids_tpu.mem import spill as spillmod
from spark_rapids_tpu.obs import registry as obsreg

rng = np.random.default_rng(11)
n = 8000
z = np.minimum(rng.zipf(1.3, n), 400).astype(np.int64)
fact = pa.table({"k": z, "v": rng.integers(0, 1000, n)})
rk = np.minimum(rng.zipf(1.3, n // 2), 400).astype(np.int64)
dim = pa.table({"k2": rk, "w": rng.integers(0, 1000, n // 2)})
BASE = {
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1,
    "spark.rapids.tpu.sql.shuffle.partitions": 4,
}

def run(budget):
    s = TpuSparkSession(dict(BASE, **{
        "spark.rapids.tpu.sql.join.buildSideBudgetBytes": budget}))
    f = s.create_dataframe(fact, num_partitions=4)
    d = s.create_dataframe(dim, num_partitions=4)
    out = (f.join(d, col("k") == col("k2"))
           .select(col("k").alias("a"), col("v").alias("b"),
                   col("w").alias("c")).collect())
    return out.sort_by([("a", "ascending"), ("b", "ascending"),
                        ("c", "ascending")])

oracle = run(-1)
assert not any(k.startswith("join.grace.") for k in
               obsreg.get_registry().snapshot()["counters"]), \
    "oracle run must not activate grace"
budget = max(1024, int(dim.nbytes) // 16)
grace = run(budget)
d = obsreg.get_registry().snapshot()["counters"]
assert d.get("join.grace.activations", 0) >= 1, d
assert d.get("join.grace.restreams", 0) >= 1, d
assert d.get("join.grace.spilledBuildBytes", 0) > 0, d
assert grace.equals(oracle), \
    "grace join diverges from the unconstrained oracle"
cat = spillmod.get_catalog()
with cat._lock:
    leaked = [b for b in cat._buffers.values()
              if b.priority == spillmod.GRACE_JOIN_PARTITION_PRIORITY]
assert not leaked, f"{len(leaked)} grace catalog entries leaked"
print(f"out-of-core gate OK: {grace.num_rows} rows bit-identical at "
      f"budget {budget}B, {int(d['join.grace.restreams'])} re-streams, "
      f"{int(d['join.grace.spilledBuildBytes'])}B spilled, 0 leaks")
EOF

echo "== skew-split gate (seeded hot key: bucket split before the fetch, reduce critical path shrinks >= 1.5x, bit-identical) =="
timeout 560 python - <<'EOF'
# a seeded 60%-hot-key probe against a uniform dim: with
# join.skew.enabled the map-output tracker must split the hot bucket
# BEFORE the reduce fetch (shuffle.skew.detected/splits counters), the
# reduce-stage critical path — the largest single reduce unit's probe
# bytes — must shrink >= 1.5x, and the result must be bit-identical to
# the unsplit run.
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import pyarrow as pa
from spark_rapids_tpu import TpuSparkSession, col
from spark_rapids_tpu.exec.adaptive import TpuSkewJoinReaderExec
from spark_rapids_tpu.obs import registry as obsreg

rng = np.random.default_rng(13)
n = 16000
keys = np.where(rng.random(n) < 0.6, 7,
                rng.integers(0, 500, n)).astype(np.int64)
fact = pa.table({"k": keys, "v": rng.integers(0, 1000, n)})
dim = pa.table({"k2": np.arange(500, dtype=np.int64),
                "w": rng.integers(0, 1000, 500)})
BASE = {
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

def norm(t):
    return t.sort_by([("a", "ascending"), ("b", "ascending"),
                      ("c", "ascending")])

base = norm(df_of(TpuSparkSession(BASE)).collect())
assert not any(k.startswith("shuffle.skew.") for k in
               obsreg.get_registry().snapshot()["counters"]), \
    "skew-off run must not touch the skew plane"
s = TpuSparkSession(dict(BASE, **{
    "spark.rapids.tpu.sql.join.skew.enabled": True,
    "spark.rapids.tpu.sql.join.skew.minBucketBytes": 1024}))
df = df_of(s)
phys = s._plan_physical(df.plan).plan
readers = []
phys.foreach(lambda nd: readers.append(nd)
             if isinstance(nd, TpuSkewJoinReaderExec) else None)
assert readers, "skew conf planted no TpuSkewJoinReaderExec"
batches = []
for it in phys.execute():
    for b in it:
        batches.append(b)
d = obsreg.get_registry().snapshot()["counters"]
assert d.get("shuffle.skew.detected", 0) >= 1, d
assert d.get("shuffle.skew.splits", 0) >= 2, d
st = readers[0].state
totals = st.outs[st.probe].totals
critical_off = max(totals)
per_unit = {p: float(tb) for p, tb in enumerate(totals)}
for sp in st.specs:
    if sp[0] == "split":
        per_unit[sp[1]] = totals[sp[1]] / float(sp[3])
critical_on = max(per_unit.values())
balance = critical_off / max(critical_on, 1.0)
assert balance >= 1.5, (
    f"reduce critical path only improved {balance:.2f}x "
    f"({critical_off} -> {int(critical_on)} bytes)")
split = norm(df_of(s).collect())
assert split.equals(base), "skew-split result diverges"
print(f"skew-split gate OK: {int(d['shuffle.skew.detected'])} hot "
      f"bucket(s) -> {int(d['shuffle.skew.splits'])} sub-readers, "
      f"critical path {balance:.2f}x better, "
      f"{split.num_rows} rows bit-identical")
EOF

echo "== fleet chaos gate (router + 3 replicas, kill one mid-stream + drain another, bit-identical, warm replacement) =="
timeout 420 python - <<'EOF'
# the horizontally scaled serve tier (fleet/) under chaos: a router
# fronting 3 subprocess replicas on a shared file store, 3 reconnecting
# clients running repeated queries through it.  Mid-run one replica is
# SIGKILLed (no goodbye — the router must fail the affected sessions
# over: re-hello, prepared-statement replay, resume/re-execute with
# duplicate chunks dropped at the router) and another is gracefully
# drained (its leak audit must read zero and the router must stop
# placing on it).  Every client result must be BIT-IDENTICAL to the
# in-process oracle — equal row counts prove no chunk was duplicated
# or lost across either failure.  Finally a replacement replica joins,
# warms from the fleet's shared precompile corpus before its ready
# handshake, and serves with ZERO fresh kernel compiles.
import json, os, tempfile, threading, time, urllib.request
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import pyarrow as pa, pyarrow.parquet as papq
from spark_rapids_tpu import TpuSparkSession
from spark_rapids_tpu.fleet.replica import FleetManager
from spark_rapids_tpu.fleet.router import FleetRouter
from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.serve.client import ServeClient

td = tempfile.mkdtemp(prefix="fleet_gate_")
data = os.path.join(td, "t.parquet")
papq.write_table(pa.table(
    {"k": pa.array([i % 7 for i in range(1800)], type=pa.int64()),
     "x": [float(i % 50) for i in range(1800)],
     "v": [f"s{i % 11}" for i in range(1800)]}), data)

QUERIES = [
    "select k, x, v from t order by k, x, v",
    "select k, count(*) as c, sum(x) as sx from t "
    "where x > 5.0 group by k order by k",
    "select v, count(*) as c from t group by v order by v"]

# in-process oracle (serve plane off: just the engine)
s = TpuSparkSession(
    {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
s.register_view("t", s.read.parquet(data))
oracles = [s.sql(q).collect() for q in QUERIES]

env = dict(os.environ)
mgr = FleetManager(
    os.path.join(td, "store"),
    base_conf={
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        "spark.rapids.tpu.sql.fusion.donateInputs": False,
        "spark.rapids.tpu.sched.precompile.enabled": True,
        "spark.rapids.tpu.sched.precompile.idleWaitMs": 0,
        "spark.rapids.tpu.serve.stream.chunkRows": 120},
    views={"t": {"parquet": data}}, env=env)
reps = [mgr.spawn(name=f"r{i}") for i in range(3)]
router = FleetRouter([r.endpoint() for r in reps],
                     health_poll_ms=200).start()

results, errors = {}, []
ROUNDS = 4

def chaos_client(i):
    try:
        with ServeClient("127.0.0.1", router.port, reconnect=True,
                         max_reconnects=8, backoff_s=0.05) as c:
            out = []
            for _ in range(ROUNDS):
                out.append(c.sql(QUERIES[i]))
            results[i] = out
    except Exception as e:
        errors.append(f"client {i}: {type(e).__name__}: {e}")

threads = [threading.Thread(target=chaos_client, args=(i,))
           for i in range(3)]
for t in threads:
    t.start()

# chaos: SIGKILL one replica while clients stream, then drain another
time.sleep(1.0)
reps[1].kill()
time.sleep(1.5)
drain_ack = reps[2].drain()

for t in threads:
    t.join(timeout=300)
assert not errors, errors
hung = [t.name for t in threads if t.is_alive()]
assert not hung, f"clients still running: {hung}"
for i, oracle in enumerate(oracles):
    assert len(results[i]) == ROUNDS, f"client {i} lost rounds"
    for got in results[i]:
        assert got.num_rows == oracle.num_rows, (
            f"client {i}: duplicate/missing chunks "
            f"({got.num_rows} vs {oracle.num_rows} rows)")
        assert got.equals(oracle), \
            f"client {i} diverges under fleet chaos"

# the drained replica's leak audit is all-zero
assert drain_ack["drained"], drain_ack
for k in ("connections", "streamer_threads", "inflight", "sessions"):
    assert drain_ack["leaks"][k] == 0, drain_ack["leaks"]

# the surviving replica's gauges settle to zero
def healthz(port):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5) as r:
        return json.loads(r.read().decode())
deadline = time.time() + 30
while time.time() < deadline and healthz(reps[0].obs_port)["inflight"]:
    time.sleep(0.1)
hz = healthz(reps[0].obs_port)
assert hz["state"] == "serving" and hz["inflight"] == 0, hz

# replacement replica: joins off the shared corpus, serves the fleet's
# queries with zero fresh compiles
rnew = mgr.spawn(name="r3")
assert rnew.ready_info["precompile"].get("warmed", 0) > 0, \
    rnew.ready_info
router.add_replica(rnew.endpoint())
with ServeClient("127.0.0.1", rnew.serve_port) as c:
    for i, q in enumerate(QUERIES):
        got = c.sql(q)
        assert got.equals(oracles[i]), f"replacement diverges on q{i}"
with urllib.request.urlopen(
        f"http://127.0.0.1:{rnew.obs_port}/compiles?n=0",
        timeout=10) as r:
    comp = json.loads(r.read().decode())
fresh = {q: rec for q, rec in comp.get("per_query", {}).items()
         if rec.get("kernels_compiled")}
assert not fresh, f"replacement compiled fresh kernels: {fresh}"

c0 = obsreg.get_registry().snapshot()["counters"]
failovers = int(c0.get("fleet.router.failovers", 0))
assert failovers >= 1, f"kill/drain never exercised failover: {c0}"

router.shutdown()
mgr.stop_all()
print(f"fleet chaos gate OK: 3 clients x{ROUNDS} rounds bit-identical "
      f"through SIGKILL + drain ({failovers} failovers, "
      f"{int(c0.get('fleet.router.droppedDuplicateChunks', 0))} "
      f"duplicate chunks dropped at the router), drained leak audit "
      f"zero, replacement warmed "
      f"{rnew.ready_info['precompile']['warmed']} programs, "
      f"zero fresh compiles")
EOF

echo "CI GREEN"
