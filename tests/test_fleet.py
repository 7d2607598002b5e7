"""Fleet tier (fleet/): the shared store plane, the routing front
door, replica lifecycle, hello auth + TLS, /healthz drain states, and
cross-replica cache invalidation through the shared store."""

import json
import os
import socket
import subprocess
import threading
import time
import urllib.request

import pyarrow as pa
import pyarrow.parquet as papq
import pytest

from spark_rapids_tpu import TpuSparkSession, functions as F
from spark_rapids_tpu.fleet.router import (FleetRouter, ReplicaEndpoint,
                                           RouterError)
from spark_rapids_tpu.fleet.store import (FileStore, StoreServer,
                                          TcpStore, store_from_url)
from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.serve import result_cache
from spark_rapids_tpu.serve.client import ServeClient, ServeError


@pytest.fixture(autouse=True)
def _fresh_fleet_state():
    obsreg.reset_registry()
    result_cache.clear()
    result_cache.configure_store(None)
    yield
    obsreg.reset_registry()
    result_cache.clear()
    result_cache.configure_store(None)


def _counters():
    return obsreg.get_registry().snapshot()["counters"]


def _session(extra=None):
    conf = {
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        "spark.rapids.tpu.serve.enabled": True,
    }
    conf.update(extra or {})
    return TpuSparkSession(conf)


def _obs_session(extra=None):
    conf = {"spark.rapids.tpu.obs.http.enabled": True,
            "spark.rapids.tpu.obs.http.port": 0}
    conf.update(extra or {})
    return _session(conf)


def _healthz(port):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5) as r:
        return json.loads(r.read().decode())


def _register_t(s, n=600):
    df = s.create_dataframe(
        {"k": [i % 7 for i in range(n)],
         "x": [float(i % 50) for i in range(n)]},
        num_partitions=2)
    s.register_view("t", df)


# ---------------------------------------------------------------------------
# store plane
# ---------------------------------------------------------------------------

def test_file_store_roundtrip(tmp_path):
    st = FileStore(str(tmp_path / "store"))
    assert st.get("result", "missing") is None
    st.put("result", "k1", b"abc")
    assert st.get("result", "k1") == b"abc"
    st.put("result", "k1", b"xyz")            # overwrite is atomic
    assert st.get("result", "k1") == b"xyz"
    st.put("stmt", "k1", b"other-namespace")
    assert st.get("stmt", "k1") == b"other-namespace"
    assert sorted(st.keys("result")) == ["k1"]
    st.delete("result", "k1")
    assert st.get("result", "k1") is None
    # hostile key characters never escape the namespace dir
    st.put("result", "../../escape", b"v")
    assert st.get("result", "../../escape") == b"v"
    for root, _dirs, files in os.walk(str(tmp_path)):
        for f in files:
            assert ".." not in f
    # shared directories exist and are stable
    assert os.path.isdir(st.compile_cache_dir())
    assert os.path.isdir(st.corpus_dir())
    assert st.compile_cache_dir() == st.compile_cache_dir()


def test_tcp_store_roundtrip_and_reconnect():
    srv = StoreServer("127.0.0.1", 0)
    try:
        cli = TcpStore("127.0.0.1", srv.port)
        cli.put("result", "a", b"1")
        cli.put("latest", "a", b"2")
        assert cli.get("result", "a") == b"1"
        assert cli.get("latest", "a") == b"2"
        assert cli.keys("result") == ["a"]
        cli.delete("result", "a")
        assert cli.get("result", "a") is None
        assert srv.entry_count() == 1            # the "latest" row
        # transparent reconnect after the socket dies under the client
        cli._sock.close()
        assert cli.get("latest", "a") == b"2"
        cli.close()
    finally:
        srv.shutdown()


def test_store_from_url(tmp_path):
    st = store_from_url(f"file://{tmp_path}/s1")
    assert isinstance(st, FileStore)
    st2 = store_from_url(str(tmp_path / "s2"))   # bare path
    assert isinstance(st2, FileStore)
    srv = StoreServer("127.0.0.1", 0)
    try:
        st3 = store_from_url(srv.url)
        assert isinstance(st3, TcpStore)
        st3.close()
    finally:
        srv.shutdown()
    with pytest.raises(ValueError):
        store_from_url("redis://nope")


# ---------------------------------------------------------------------------
# shared result cache (two-level lookup through the store)
# ---------------------------------------------------------------------------

_T = pa.table({"a": [1, 2, 3], "b": ["x", "y", "z"]})
_STAMPS = ((("file", "/f", 1, 10),),)


def test_result_cache_store_publish_and_adopt(tmp_path):
    result_cache.configure_store(FileStore(str(tmp_path)))
    result_cache.insert("d1", ("a", "b"), _STAMPS, _T)
    # wipe the LOCAL cache: simulates a replica that never executed it
    result_cache.clear()
    got = result_cache.lookup("d1", ("a", "b"), _STAMPS)
    assert got is not None and got.equals(_T)    # bit-identical
    c = _counters()
    assert c.get("serve.resultCacheSharedHits") == 1, c
    assert c.get("serve.resultCacheHits") == 1, c
    # the adopted entry now serves locally without another store read
    g0 = c.get("fleet.store.gets", 0)
    again = result_cache.lookup("d1", ("a", "b"), _STAMPS)
    assert again is not None and again.equals(_T)
    assert _counters().get("fleet.store.gets", 0) == g0


def test_result_cache_latest_pointer_shared(tmp_path):
    result_cache.configure_store(FileStore(str(tmp_path)))
    result_cache.insert("d2", ("a", "b"), _STAMPS, _T)
    result_cache.clear()
    hit = result_cache.lookup_latest("d2", ("a", "b"))
    assert hit is not None
    stamps, got = hit
    assert stamps == _STAMPS and got.equals(_T)
    assert _counters().get("serve.resultCacheSharedHits") == 1


def test_result_cache_stale_stamps_not_served(tmp_path):
    result_cache.configure_store(FileStore(str(tmp_path)))
    result_cache.insert("d3", ("a", "b"), _STAMPS, _T)
    result_cache.clear()
    new_stamps = ((("file", "/f", 2, 20),),)
    assert result_cache.lookup("d3", ("a", "b"), new_stamps) is None
    assert _counters().get("serve.resultCacheSharedHits", 0) == 0


def test_store_detached_is_inert():
    """fleet.enabled=false one-knob revert: no store, no counters, the
    local path byte-for-byte unchanged."""
    assert not result_cache.store_attached()
    result_cache.insert("d4", ("a", "b"), _STAMPS, _T)
    result_cache.clear()
    assert result_cache.lookup("d4", ("a", "b"), _STAMPS) is None
    c = _counters()
    assert c.get("fleet.store.puts", 0) == 0
    assert c.get("fleet.store.gets", 0) == 0


# ---------------------------------------------------------------------------
# hello auth + TLS (serve.auth.tokens / serve.tls.*)
# ---------------------------------------------------------------------------

def test_auth_token_required():
    s = _session({"spark.rapids.tpu.serve.auth.tokens": "tok1, tok2"})
    _register_t(s, 60)
    port = s.serve_server.port
    with pytest.raises(ServeError) as ei:
        with ServeClient("127.0.0.1", port) as c:
            c.sql("select k from t")
    assert ei.value.code == "AuthFailed"
    with pytest.raises(ServeError) as ei:
        with ServeClient("127.0.0.1", port, auth_token="wrong") as c:
            c.sql("select k from t")
    assert ei.value.code == "AuthFailed"
    with ServeClient("127.0.0.1", port, auth_token="tok2") as c:
        assert c.sql("select count(*) as n from t").to_pydict() == \
            {"n": [60]}
    c = _counters()
    assert c.get("serve.authFailures") == 2, c
    s.serve_server.shutdown()


def _mint_cert(tmp_path):
    cert = str(tmp_path / "cert.pem")
    key = str(tmp_path / "key.pem")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048",
         "-keyout", key, "-out", cert, "-days", "2", "-nodes",
         "-subj", "/CN=127.0.0.1"],
        check=True, capture_output=True)
    return cert, key


def test_tls_serving(tmp_path):
    cert, key = _mint_cert(tmp_path)
    s = _session({"spark.rapids.tpu.serve.tls.certFile": cert,
                  "spark.rapids.tpu.serve.tls.keyFile": key})
    _register_t(s, 60)
    port = s.serve_server.port
    with ServeClient("127.0.0.1", port, tls_ca_file=cert) as c:
        assert c.sql("select count(*) as n from t").to_pydict() == \
            {"n": [60]}
    # a plaintext client against the TLS listener fails the handshake
    with pytest.raises((ServeError, OSError)):
        with ServeClient("127.0.0.1", port, connect_timeout=5) as c:
            c.sql("select k from t", timeout=5)
    deadline = time.time() + 5
    while time.time() < deadline and not _counters().get(
            "serve.tlsHandshakeFailures"):
        time.sleep(0.05)
    assert _counters().get("serve.tlsHandshakeFailures", 0) >= 1
    s.serve_server.shutdown()


def test_tls_requires_both_files(tmp_path):
    cert, _key = _mint_cert(tmp_path)
    with pytest.raises(ValueError):
        _session({"spark.rapids.tpu.serve.tls.certFile": cert})


# ---------------------------------------------------------------------------
# /healthz drain state (satellite: router honors it)
# ---------------------------------------------------------------------------

def test_healthz_reports_drain_state():
    s = _obs_session()
    _register_t(s, 60)
    hz = _healthz(s.obs_server.port)
    assert hz["state"] == "serving" and hz["inflight"] == 0
    s.serve_server.drain()
    hz = _healthz(s.obs_server.port)
    assert hz["state"] == "drained"
    s.serve_server.shutdown()
    s.obs_server.shutdown()


def test_healthz_without_serve_server():
    s = TpuSparkSession({"spark.rapids.tpu.obs.http.enabled": True,
                         "spark.rapids.tpu.obs.http.port": 0})
    hz = _healthz(s.obs_server.port)
    assert hz["ok"] and hz["state"] == "serving"
    s.obs_server.shutdown()


# ---------------------------------------------------------------------------
# router: placement, affinity, auth, quotas, failover
# ---------------------------------------------------------------------------

def _two_replicas(extra=None):
    s1 = _obs_session(extra)
    s2 = _obs_session(extra)
    for s in (s1, s2):
        _register_t(s)
    eps = [ReplicaEndpoint("127.0.0.1", s.serve_server.port,
                           s.obs_server.port, name=n)
           for s, n in ((s1, "A"), (s2, "B"))]
    router = FleetRouter(eps, health_poll_ms=60_000)
    router.start()
    return s1, s2, router


def _teardown(router, *sessions):
    router.shutdown()
    for s in sessions:
        if s.serve_server is not None:
            s.serve_server.shutdown()
        if s.obs_server is not None:
            s.obs_server.shutdown()


def test_router_places_new_sessions_across_replicas():
    s1, s2, router = _two_replicas()
    try:
        with ServeClient("127.0.0.1", router.port) as c1, \
                ServeClient("127.0.0.1", router.port) as c2:
            r1 = c1.sql("select count(*) as n from t")
            r2 = c2.sql("select count(*) as n from t")
            assert r1.equals(r2)
            st = router.stats()
            names = {hit[0] for hit in router._affinity.values()}
            # two fresh sessions spread over both replicas
            assert names == {"A", "B"}, st
        assert _counters().get("fleet.router.placements") == 2
    finally:
        _teardown(router, s1, s2)


def test_router_affinity_by_resume_token():
    s1, s2, router = _two_replicas()
    try:
        with ServeClient("127.0.0.1", router.port) as c:
            c.sql("select count(*) as n from t")
            tok = next(iter(router._affinity))
            home = router._affinity[tok][0]
        # a reconnecting client presenting the token goes home
        rep, utoken = router.pick(resume_token=tok)
        assert rep.name == home and utoken == tok
    finally:
        _teardown(router, s1, s2)


def test_router_auth_failure_counted():
    s1, s2, router = _two_replicas()
    router._auth_tokens = frozenset({"fleet-tok"})
    try:
        with pytest.raises(ServeError) as ei:
            with ServeClient("127.0.0.1", router.port) as c:
                c.sql("select 1 as x")
        assert ei.value.code == "AuthFailed"
        assert _counters().get("fleet.router.authFailures") == 1
        with ServeClient("127.0.0.1", router.port,
                         auth_token="fleet-tok") as c:
            c.sql("select count(*) as n from t")
    finally:
        _teardown(router, s1, s2)


def test_router_tenant_quota():
    s1, s2, router = _two_replicas(
        {"spark.rapids.tpu.serve.stream.chunkRows": 20})
    router._tenant_max = 1
    try:
        with ServeClient("127.0.0.1", router.port,
                         default_credit=1) as c:
            # an unconsumed stream holds the tenant's one slot
            stream = c.sql_stream("select k, x from t order by k, x")
            it = iter(stream)
            next(it)
            with pytest.raises(ServeError) as ei:
                c.sql("select count(*) as n from t")
            assert ei.value.code == "TenantQuotaExceeded"
            assert _counters().get("fleet.router.quotaRefusals") == 1
            for _ in it:       # drain the stream -> slot releases
                pass
            deadline = time.time() + 5
            while time.time() < deadline:
                try:
                    c.sql("select count(*) as n from t")
                    break
                except ServeError:
                    time.sleep(0.05)
            else:
                pytest.fail("quota slot never released")
    finally:
        _teardown(router, s1, s2)


def test_router_failover_replays_statements():
    s1, s2, router = _two_replicas()
    try:
        with ServeClient("127.0.0.1", router.port) as c:
            ps = c.prepare("select k, count(*) as c from t "
                           "where k = :k group by k",
                           params={"k": "bigint"})
            before = ps.execute({"k": 3})
            home = router._affinity[next(iter(router._affinity))][0]
            dead = s1 if home == "A" else s2
            dead.serve_server.shutdown()
            after = ps.execute({"k": 3})     # replayed on the survivor
            assert after.equals(before)
            fresh = c.sql("select count(*) as n from t")
            assert fresh.to_pydict() == {"n": [600]}
        c = _counters()
        assert c.get("fleet.router.failovers") == 1, c
    finally:
        _teardown(router, s1, s2)


def test_router_mid_stream_failover_no_duplicates():
    s1, s2, router = _two_replicas(
        {"spark.rapids.tpu.serve.stream.chunkRows": 25})
    try:
        oracle = None
        with ServeClient("127.0.0.1",
                         s2.serve_server.port) as direct:
            oracle = direct.sql("select k, x from t order by k, x")
        with ServeClient("127.0.0.1", router.port,
                         default_credit=2) as c:
            stream = c.sql_stream("select k, x from t order by k, x")
            it = iter(stream)
            pieces = [next(it), next(it)]
            home = router._affinity[next(iter(router._affinity))][0]
            dead = s1 if home == "A" else s2
            dead.serve_server.shutdown()
            for tbl in it:
                pieces.append(tbl)
        got = pa.concat_tables(pieces)
        # bit-identical == no duplicate AND no missing chunks
        assert got.equals(oracle), (got.num_rows, oracle.num_rows)
        c = _counters()
        assert c.get("fleet.router.failovers") == 1, c
    finally:
        _teardown(router, s1, s2)


def test_router_drain_state_honored():
    s1, s2, router = _two_replicas()
    try:
        s1.serve_server.drain()
        router.poll_once()
        reps = {r["name"]: r for r in router.replicas()}
        assert reps["A"]["state"] == "drained"
        for _ in range(4):     # every new placement avoids A
            rep, _tok = router.pick()
            assert rep.name == "B"
    finally:
        _teardown(router, s1, s2)


def test_router_no_replica_available():
    router = FleetRouter([], health_poll_ms=60_000).start()
    try:
        with pytest.raises(RouterError):
            router.pick()
        with pytest.raises(ServeError) as ei:
            with ServeClient("127.0.0.1", router.port,
                             connect_timeout=5) as c:
                c.sql("select 1 as x", timeout=10)
        assert ei.value.code in ("NoReplicaAvailable",
                                 "ConnectionClosed")
    finally:
        router.shutdown()


# ---------------------------------------------------------------------------
# fleet-enabled serve plane: shared statements, nonced ids, revert knob
# ---------------------------------------------------------------------------

def _fleet_session(tmp_path, extra=None):
    conf = {"spark.rapids.tpu.fleet.enabled": True,
            "spark.rapids.tpu.fleet.store.url":
                f"file://{tmp_path}/store"}
    conf.update(extra or {})
    return _session(conf)


def test_statement_ids_nonced_only_with_store(tmp_path):
    s = _session()
    _register_t(s, 30)
    with ServeClient("127.0.0.1", s.serve_server.port) as c:
        ps = c.prepare("select k from t where k = :k",
                       params={"k": "bigint"})
        # storeless: the legacy id format, byte-for-byte
        assert ps.statement_id == "stmt-00001"
    s.serve_server.shutdown()

    sf = _fleet_session(tmp_path)
    _register_t(sf, 30)
    with ServeClient("127.0.0.1", sf.serve_server.port) as c:
        ps = c.prepare("select k from t where k = :k",
                       params={"k": "bigint"})
        assert ps.statement_id != "stmt-00001"     # nonce-prefixed
        assert ps.statement_id.startswith("stmt-")
    sf.serve_server.shutdown()


def test_statement_adopted_from_store(tmp_path):
    """A statement prepared on replica 1 executes on replica 2 by id:
    replica 2 adopts the template from the shared store."""
    s1 = _fleet_session(tmp_path)
    _register_t(s1, 60)
    with ServeClient("127.0.0.1", s1.serve_server.port) as c:
        ps = c.prepare("select count(*) as n from t where k = :k",
                       params={"k": "bigint"})
        sid = ps.statement_id
        want = ps.execute({"k": 1})
    s1.serve_server.shutdown()

    s2 = _fleet_session(tmp_path)
    _register_t(s2, 60)
    with ServeClient("127.0.0.1", s2.serve_server.port) as c:
        got = c.execute(sid, {"k": 1})
        assert got.equals(want)
    assert _counters().get("serve.statementsAdopted") == 1
    s2.serve_server.shutdown()


def test_fleet_session_serves_shared_cache_zero_dispatch(tmp_path):
    """The tentpole acceptance shape in one process: replica 2 serves
    a query it never executed from the shared store."""
    q = ("select k, count(*) as c, sum(x) as sx from t "
         "group by k order by k")
    s1 = _fleet_session(tmp_path)
    _register_t(s1, 600)
    with ServeClient("127.0.0.1", s1.serve_server.port) as c:
        first = c.sql(q)
    s1.serve_server.shutdown()

    result_cache.clear()       # replica 2 = fresh local cache
    obsreg.reset_registry()
    s2 = _fleet_session(tmp_path)
    _register_t(s2, 600)
    reg = obsreg.get_registry()
    v = reg.view()
    with ServeClient("127.0.0.1", s2.serve_server.port) as c:
        got = c.sql(q)
    d = v.delta()["counters"]
    assert got.equals(first)                       # bit-identical
    assert d.get("serve.resultCacheSharedHits") == 1, d
    assert d.get("sched.submitted", 0) == 0, d     # zero dispatches
    s2.serve_server.shutdown()


# ---------------------------------------------------------------------------
# two-replica shared-store invalidation (in-process A + subprocess B)
# ---------------------------------------------------------------------------

_CHILD_B = r'''
import json, sys
from spark_rapids_tpu import TpuSparkSession
from spark_rapids_tpu.obs import registry as obsreg
root, store = sys.argv[1], sys.argv[2]
s = TpuSparkSession({
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    "spark.rapids.tpu.serve.enabled": True,
    "spark.rapids.tpu.fleet.enabled": True,
    "spark.rapids.tpu.fleet.store.url": store})
s.register_view("t", s.read.parquet(root))
from spark_rapids_tpu.serve.client import ServeClient
with ServeClient("127.0.0.1", s.serve_server.port) as c:
    got = c.sql("select k, count(*) as c, sum(x) as sx from t "
                "group by k order by k")
snap = obsreg.get_registry().snapshot()["counters"]
print(json.dumps({"rows": got.num_rows,
                  "result": got.to_pydict(),
                  "incremental_hits":
                      snap.get("serve.incremental.hits", 0),
                  "delta_files":
                      snap.get("serve.incremental.deltaFiles", 0),
                  "shared_hits":
                      snap.get("serve.resultCacheSharedHits", 0)}))
s.serve_server.shutdown()
'''


def _write_part(root, i, n0, n):
    papq.write_table(pa.table({
        "k": pa.array([j % 5 for j in range(n0, n0 + n)],
                      type=pa.int64()),
        "x": pa.array([float((j * 3) % 100)
                       for j in range(n0, n0 + n)])}),
        os.path.join(root, f"part-{i:03d}.parquet"))


def test_two_replica_shared_store_invalidation(tmp_path):
    """Satellite gate: A serves a cached aggregate; the source gains a
    file under B; B's run delta-refreshes from the shared partials and
    publishes under the new stamps; A's next lookup must NOT serve the
    stale entry — and serves the refreshed one without recompute."""
    import sys
    root = str(tmp_path / "data")
    os.makedirs(root)
    _write_part(root, 0, 0, 2000)
    _write_part(root, 1, 2000, 2000)
    store_url = f"file://{tmp_path}/store"
    q = ("select k, count(*) as c, sum(x) as sx from t "
         "group by k order by k")

    a = _fleet_session(str(tmp_path))
    a.register_view("t", a.read.parquet(root))
    with ServeClient("127.0.0.1", a.serve_server.port) as c:
        first = c.sql(q)
        assert c.sql(q).equals(first)              # plain cached serve

        # the append lands "under replica B"
        _write_part(root, 2, 4000, 300)
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        out = subprocess.run(
            [sys.executable, "-c", _CHILD_B, root, store_url],
            capture_output=True, text=True, env=env, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        b = json.loads(out.stdout.strip().splitlines()[-1])
        # B never ran the capture query, yet its refresh rode the
        # shared partials: a delta over the ONE appended file
        assert b["incremental_hits"] == 1, b
        assert b["delta_files"] == 1, b

        # A must not serve the stale entry — and must not recompute
        reg = obsreg.get_registry()
        v = reg.view()
        got = c.sql(q)
        d = v.delta()["counters"]
        oracle = (a.read.parquet(root).group_by("k")
                  .agg(F.count("*").alias("c"), F.sum("x").alias("sx"))
                  .collect().sort_by("k"))
        assert got.sort_by("k").equals(oracle)     # fresh, not stale
        assert b["result"] == got.to_pydict()      # bit-identical A==B
        assert d.get("serve.resultCacheSharedHits", 0) >= 1, d
        assert d.get("sched.submitted", 0) == 0, d
    a.serve_server.shutdown()


# ---------------------------------------------------------------------------
# replica lifecycle (subprocess spawn / drain / stop)
# ---------------------------------------------------------------------------

def test_replica_spawn_serve_drain_stop(tmp_path):
    from spark_rapids_tpu.fleet.replica import FleetManager
    p = str(tmp_path / "f.parquet")
    papq.write_table(pa.table({"a": list(range(40))}), p)
    mgr = FleetManager(str(tmp_path / "store"),
                       views={"t": {"parquet": p}})
    try:
        rep = mgr.spawn(name="r1")
        assert rep.ready_info["pid"] == rep.proc.pid
        with ServeClient("127.0.0.1", rep.serve_port) as c:
            assert c.sql("select count(*) as n from t").to_pydict() \
                == {"n": [40]}
        assert _healthz(rep.obs_port)["state"] == "serving"
        ack = rep.drain()
        assert ack["drained"] and ack["leaks"]["connections"] == 0
        assert _healthz(rep.obs_port)["state"] == "drained"
        assert rep.stop() == 0
        assert not rep.alive()
    finally:
        mgr.stop_all()


@pytest.mark.slow
def test_warm_join_zero_fresh_compiles(tmp_path):
    """A replacement replica joining the fleet warms from the shared
    precompile corpus before its ready handshake; its first queries
    pay zero fresh compiles."""
    import urllib.request as _url
    from spark_rapids_tpu.fleet.replica import FleetManager
    p = str(tmp_path / "f.parquet")
    papq.write_table(pa.table(
        {"k": [i % 6 for i in range(1800)],
         "x": [float(i % 120) for i in range(1800)]}), p)
    env = dict(os.environ)
    # the shared store's compile cache applies only where jax's own
    # variable does not place the cache (the environment wins)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    mgr = FleetManager(
        str(tmp_path / "store"),
        base_conf={
            "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
            "spark.rapids.tpu.sql.fusion.donateInputs": False,
            "spark.rapids.tpu.sched.precompile.enabled": True,
            "spark.rapids.tpu.sched.precompile.idleWaitMs": 0},
        views={"t": {"parquet": p}}, env=env)
    try:
        a = mgr.spawn(name="A")
        with ServeClient("127.0.0.1", a.serve_port) as c:
            c.sql("select k, count(*) as c, sum(x) as sx from t "
                  "where x > 30.0 group by k order by k")
        joiner = mgr.spawn(name="J")
        assert joiner.ready_info["precompile"]["warmed"] > 0
        with ServeClient("127.0.0.1", joiner.serve_port) as c:
            # the query the fleet has served before: every program must
            # come out of the warmed cache (a NOVEL query would rightly
            # compile fresh — that is not what the join gate covers)
            c.sql("select k, count(*) as c, sum(x) as sx from t "
                  "where x > 30.0 group by k order by k")
        with _url.urlopen(f"http://127.0.0.1:{joiner.obs_port}"
                          f"/compiles?n=0", timeout=10) as r:
            comp = json.loads(r.read().decode())
        fresh = {q: rec for q, rec in comp["per_query"].items()
                 if rec["kernels_compiled"]}
        assert not fresh, fresh
    finally:
        mgr.stop_all()
