"""One span tree a query (obs/trace.py, obs/profile.py, serve/server.py)
and the names device programs carry (exec/kernel_cache.jit_named).

A served query's spans share its query id and resolve by ``parent``
links to one ``serve.request`` root; a session without the serve layer
roots its tree at ``query``; queries that run side by side keep their
spans apart, whichever thread recorded them; with tracing off nothing
is pushed or allocated.
"""

import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu import TpuSparkSession
from spark_rapids_tpu.columnar.batch import read_host
from spark_rapids_tpu.exec.base import Metrics, timed, timed_extra
from spark_rapids_tpu.exec.placement import drain_by_chip
from spark_rapids_tpu.mem import device as devmgr
from spark_rapids_tpu.obs import trace
from spark_rapids_tpu.sched import cancel
from spark_rapids_tpu.serve.client import ServeClient

SQL = "select k, sum(v) as sv, count(*) as n from {view} group by k order by k"


@pytest.fixture(autouse=True)
def _tracer_off_after():
    yield
    trace.configure(False)
    trace.clear()


def _parquet(tmp_path, name, n=4000, files=2):
    root = str(tmp_path / name)
    os.makedirs(root)
    for i in range(files):
        papq.write_table(pa.table({
            "k": pa.array(np.arange(n) % 7, pa.int64()),
            "v": pa.array(np.arange(n, dtype=np.float64) + i),
        }), os.path.join(root, f"p{i}.parquet"), row_group_size=1024)
    return root


@pytest.fixture()
def served(tmp_path):
    spark = TpuSparkSession({
        "spark.rapids.tpu.serve.enabled": True,
        "spark.rapids.tpu.obs.trace.enabled": True,
        "spark.rapids.tpu.serve.resultCache.enabled": False,
        # each query decodes its own scan, in several groups, so that
        # host prep runs on the scan's prefetch threads
        "spark.rapids.tpu.sql.scan.shared.enabled": False,
        "spark.rapids.tpu.sql.reader.batchSizeRows": 1024,
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
    for view in ("a", "b"):
        spark.register_view(
            view, spark.read.parquet(_parquet(tmp_path, view)))
    trace.clear()
    yield spark
    spark.serve_server.shutdown()


def _run(spark, client, view):
    stream = client.sql_stream(SQL.format(view=view))
    table = stream.read_all()
    qid = stream.summary["query_id"]
    # the server records ``serve.request`` after the END frame went
    # out: the client can be back before the streamer thread got there
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        spans = spark.query_profile(qid).spans
        if any(s["name"] == "serve.request" for s in spans):
            return qid, table, spans
        time.sleep(0.01)
    raise AssertionError(f"no serve.request span for query {qid}")


def _assert_one_tree(spans, qid, root_name):
    by_id = {s["id"]: s for s in spans}
    assert len(by_id) == len(spans), "span ids repeat"
    roots = [s for s in spans if s["parent"] == 0]
    assert [r["name"] for r in roots] == [root_name]
    for s in spans:
        assert s["query"] == qid, s
        hops, at = 0, s
        while at["parent"]:
            assert at["parent"] in by_id, f"{s['name']}: parent not in tree"
            at, hops = by_id[at["parent"]], hops + 1
            assert hops <= len(spans), "parent links loop"
        assert at is roots[0]


def test_served_query_is_one_tree_under_serve_request(served):
    client = ServeClient("127.0.0.1", served.serve_server.port)
    try:
        qid, table, spans = _run(served, client, "a")
    finally:
        client.close()
    assert table.num_rows == 7
    _assert_one_tree(spans, qid, "serve.request")
    by_id = {s["id"]: s for s in spans}
    names = {s["name"] for s in spans}
    assert {"query.plan", "query.execute", "query.collect", "serve.stream",
            "collect.deviceWait", "collect.download"} <= names
    root = next(s for s in spans if s["parent"] == 0)

    def parent_name(name):
        return by_id[next(s for s in spans
                          if s["name"] == name)["parent"]]["name"]
    for child in ("query.plan", "query.execute", "query.collect",
                  "serve.stream"):
        assert parent_name(child) == "serve.request"
    for child in ("collect.deviceWait", "collect.download"):
        assert parent_name(child) == "query.collect"
    # the profile was assembled in finish(), before the result was
    # streamed: spans that ended later are in it all the same
    collect = next(s for s in spans if s["name"] == "query.collect")
    stream = next(s for s in spans if s["name"] == "serve.stream")
    assert stream["ts_ns"] >= collect["ts_ns"] + collect["dur_ns"]
    assert root["ts_ns"] <= min(s["ts_ns"] for s in spans)
    assert root["ts_ns"] + root["dur_ns"] >= \
        stream["ts_ns"] + stream["dur_ns"]
    # operator spans hang under the phase that drove them
    assert parent_name("scan.hostPrepTime") in (
        "query.execute", "query.collect", "serve.request")


def test_session_without_serve_roots_the_tree_at_query(tmp_path):
    spark = TpuSparkSession({
        "spark.rapids.tpu.obs.trace.enabled": True,
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
    trace.clear()
    spark.register_view("a", spark.read.parquet(_parquet(tmp_path, "a")))
    spark.sql(SQL.format(view="a")).collect()
    prof = spark.last_query_profile()
    _assert_one_tree(prof.spans, prof.query_id, "query")
    assert {"query.plan", "query.execute", "query.collect"} <= \
        {s["name"] for s in prof.spans}


def test_two_clients_get_disjoint_span_sets(served):
    out, errors = {}, []

    def client_loop(view):
        client = ServeClient("127.0.0.1", served.serve_server.port)
        try:
            out[view] = [_run(served, client, view) for _ in range(3)]
        except BaseException as e:       # surfaces in the main thread
            errors.append(e)
        finally:
            client.close()
    threads = [threading.Thread(target=client_loop, args=(v,))
               for v in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    runs = out["a"] + out["b"]
    assert len({qid for qid, _, _ in runs}) == 6
    seen = {}
    for qid, _, spans in runs:
        _assert_one_tree(spans, qid, "serve.request")
        for s in spans:
            assert seen.setdefault(s["id"], qid) == qid, \
                f"span {s['name']} is in two profiles"
    # the scans' host prep runs on prefetch threads: those spans carry
    # the query they worked for, taken from the thread's CancelToken
    for qid, _, spans in runs:
        workers = {s["tid"] for s in spans if s["name"].startswith("query.")}
        assert len(workers) == 1
        assert any(s["name"].startswith("scan.") for s in spans)
    off_worker = [s for qid, _, spans in runs for s in spans
                  if s["name"].startswith("scan.")
                  and s["tid"] not in {t["tid"] for t in spans
                                       if t["name"].startswith("query.")}]
    assert off_worker, "no scan span was recorded off the worker thread"


def test_disabled_tracer_pushes_and_allocates_nothing():
    trace.configure(False)
    trace.clear()
    mark = trace.mark()
    assert trace.span("a") is trace.span("b")
    m = Metrics()

    def body():
        with timed(m, "op"):
            with timed_extra(m, "op.extra"):
                with trace.span("inner"):
                    trace.record("y", 0, 1)
        # a read and a barrier of several chips record nothing either
        with devmgr.task_chip(1):
            assert int(read_host(jnp.int32(3) + 1, "test.offWait")) == 4
        drain_by_chip([iter((p,)) for p in range(4)], lambda p, b: None,
                      2, stage="test")
    done = threading.Event()
    stacks = []

    def on_thread():
        body()
        stacks.append(getattr(trace._tls, "stack", None))
        done.set()
    devmgr.initialize(2, chips=2)
    try:
        threading.Thread(target=on_thread).start()
        assert done.wait(10)
    finally:
        devmgr.initialize(2)
    assert stacks == [None]              # no stack was ever made
    assert trace.spans_since(mark) == [] and not trace._roots
    assert m.total_time_ns > 0 and m.extra["op.extra"] > 0


def test_spans_nest_by_id_and_inherit_the_threads_query():
    trace.configure(True, 4096)
    trace.clear()
    m = Metrics()
    with cancel.install(cancel.CancelToken(41)):
        with timed(m, "outer"):
            with timed_extra(m, "mid"):
                trace.record("leaf", 5, 1)
    trace.record("elsewhere", 5, 1)
    by_name = {s[2]: s for s in trace.snapshot()}
    outer, mid, leaf = (by_name[n] for n in ("outer", "mid", "leaf"))
    assert leaf[trace.PARENT] == mid[trace.SID]
    assert mid[trace.PARENT] == outer[trace.SID]
    # no open span above ``outer``: it hangs under query 41's root
    assert outer[trace.PARENT] == trace.root_id(41)
    assert {s[trace.QUERY] for s in (outer, mid, leaf)} == {41}
    assert by_name["elsewhere"][trace.QUERY] is None
    assert by_name["elsewhere"][trace.PARENT] == 0
    assert [s[2] for s in trace.query_spans(41)] == ["leaf", "mid", "outer"]
    # a second root of one query hangs under the first
    trace.record_root("serve.request", 0, 10, 41)
    trace.record_root("serve.request", 0, 10, 41)
    first, second = [s for s in trace.query_spans(41)
                     if s[2] == "serve.request"]
    assert first[trace.SID] == trace.root_id(41) and first[trace.PARENT] == 0
    assert second[trace.PARENT] == first[trace.SID]


def test_foreign_spans_join_the_callers_tree():
    trace.configure(True, 4096)
    trace.clear()
    foreign = [
        (1, 111, "map.inner", "exec", 1100, 100, 2, None, 8, 7, None),
        (2, 111, "map.work", "exec", 1000, 500, 1, None, 7, 0, None),
        (3, 222, "old.reply", "exec", 1200, 50, 1, None),     # 8 fields
    ]
    with cancel.install(cancel.CancelToken(9)):
        with trace.span("exchange.mapStages"):
            assert trace.record_foreign(foreign, 0, "executor-0") == 3
    by_name = {s[2]: s for s in trace.query_spans(9)}
    stage = by_name["exchange.mapStages"]
    assert by_name["map.work"][trace.PARENT] == stage[trace.SID]
    assert by_name["old.reply"][trace.PARENT] == stage[trace.SID]
    assert by_name["map.inner"][trace.PARENT] == \
        by_name["map.work"][trace.SID]
    assert len({s[trace.SID] for s in by_name.values()}) == 4


def test_a_span_carries_the_chip_its_thread_works_for():
    """On a mesh of several chips a span recorded under ``task_chip(c)``
    carries mesh device ``c``'s jax id and one recorded outside carries
    None; on one chip every span carries None.  The profile's dicts and
    the Chrome export carry it, and foreign spans keep theirs."""
    trace.configure(True, 4096)
    trace.clear()
    devmgr.initialize(2, chips=4, device_ids=[4, 5, 6, 7])
    try:
        with devmgr.task_chip(2):
            trace.record("record.chip2", 0, 1)
            with trace.span("span.chip2"):
                pass
        trace.record("outside", 0, 1)
        with cancel.install(cancel.CancelToken(5)):
            trace.record_foreign([
                (1, 9, "foreign.chip5", "exec", 0, 1, 0, None, 3, 0, None,
                 5),
                (2, 9, "foreign.old", "exec", 0, 1, 0, None, 4, 0, None),
            ], 0, "executor-0")
    finally:
        devmgr.initialize(2)
    with devmgr.task_chip(2):
        trace.record("one.chip", 0, 1)
    chip = {s[2]: s[trace.CHIP] for s in trace.snapshot()}
    assert chip == {"record.chip2": 6, "span.chip2": 6, "outside": None,
                    "foreign.chip5": 5, "foreign.old": None,
                    "one.chip": None}
    dicts = {d["name"]: d for d in trace.span_dicts(trace.snapshot())}
    assert dicts["span.chip2"]["chip"] == 6
    assert dicts["outside"]["chip"] is None
    begins = {e["name"]: e for e in trace.chrome_trace()["traceEvents"]
              if e["ph"] == "B"}
    assert begins["span.chip2"]["args"] == {"chip": 6}
    assert "args" not in begins["outside"]


def test_a_chip_that_waits_for_its_peers_says_so():
    """``drain_by_chip`` is a barrier: with one chip's tasks held back
    by a known delay, every other chip records one ``chip.peerWait`` of
    about that delay, stamped with its chip, and the slow chip none."""
    delay = 0.4
    trace.configure(True, 4096)
    trace.clear()
    devmgr.initialize(2, chips=4, device_ids=[10, 11, 12, 13])

    def part(p):
        if p % 4 == 1:
            time.sleep(delay)
        yield p
    try:
        with cancel.install(cancel.CancelToken(77)):
            drain_by_chip([part(p) for p in range(8)], lambda p, b: None,
                          stage="exchange")
    finally:
        devmgr.initialize(2)
    waits = [s for s in trace.query_spans(77) if s[2] == "chip.peerWait"]
    assert sorted(s[trace.CHIP] for s in waits) == [10, 12, 13]
    for s in waits:
        assert s[3] == "query" and s[7] == {"stage": "exchange"}
        assert 0.5 * delay < s[5] / 1e9 < delay + 1.0


def test_ici_exchange_step_carries_its_scope_on_four_devices():
    from jax.sharding import Mesh
    from spark_rapids_tpu.columnar.batch import from_arrow
    from spark_rapids_tpu.shuffle import ici
    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs four (virtual) devices")
    mesh = Mesh(np.array(devices[:4]), ("shuffle",))
    batch = from_arrow(pa.table({
        "k": pa.array(np.arange(64) % 4, pa.int32()),
        "v": pa.array(np.arange(64, dtype=np.float64))}))
    aug = ici.with_capacity(batch, 64)
    leaves, counts = ici.shard_batch(aug, mesh, "shuffle")
    step = ici.make_exchange_step(mesh, "shuffle", aug.names, aug.dtypes,
                                  ("test_trace_tree", 16))
    lowered = step.lower(leaves, counts)
    text = lowered.as_text(debug_info=True)
    assert "module @jit_ici_exchange " in text
    assert "all_to_all" in text and "ici.exchange" in text
    # the compiled program's collectives keep the scope in their metadata
    hlo = lowered.compile().as_text()
    tagged = [ln for ln in hlo.splitlines()
              if "all-to-all" in ln and "ici.exchange" in ln]
    assert tagged, "no all-to-all instruction carries ici.exchange"
