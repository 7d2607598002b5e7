"""Compile observatory (obs/compile.py): per-compile attribution,
cache-tier classification, churn analytics, precompile corpus, storms.

The observatory is default-on and process-global; each test resets the
ledger (configuration included) so assertions are about THIS test's
events.  Synthetic ledger tests drive :func:`record_compile` directly
(with a CancelToken installed to fake query context where attribution
matters); end-to-end tests clear the process kernel cache first so
real queries actually compile.
"""

import json
import threading
import urllib.request

import jax.numpy as jnp
import pytest

from spark_rapids_tpu import TpuSparkSession, col, functions as F
from spark_rapids_tpu.exec import kernel_cache as kc
from spark_rapids_tpu.obs import compile as obscompile
from spark_rapids_tpu.obs import recorder as obsrec
from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.sched import cancel as sched_cancel


@pytest.fixture(autouse=True)
def _fresh_ledger():
    obscompile.reset()
    obscompile.configure(True)
    yield
    obscompile.reset()
    obscompile.configure(True)


_LEAVES = ((((4096,), "int64")), (((4096,), "float64")))


def _fake_compile(key, family="fam", leaves=_LEAVES,
                  dur_ns=1_000_000, tier=obscompile.TIER_FRESH):
    obscompile.record_compile(key=key, family=family,
                              leaves=leaves, t0_ns=0, dur_ns=dur_ns,
                              tier=tier)


def _df(session, n=2000):
    return session.create_dataframe(
        {"k": [i % 7 for i in range(n)],
         "x": [float(i % 100) for i in range(n)]})


def _session(extra=None):
    conf = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True}
    conf.update(extra or {})
    return TpuSparkSession(conf)


# ---------------------------------------------------------------------------
# ledger mechanics
# ---------------------------------------------------------------------------

def test_ledger_ring_bound():
    obscompile.configure(True, ring_events=16)
    for i in range(40):
        _fake_compile(("fam", i))
    assert len(obscompile.events()) == 16          # None = whole ring
    assert obscompile.events(max_events=0) == []   # explicit 0 = none
    assert len(obscompile.events(max_events=4)) == 4
    # process-lifetime aggregates are NOT ring-bounded
    t = obscompile.totals()
    assert t["events"] == 40 and t["fresh"] == 40
    rows = obscompile.churn_report()
    assert rows[0]["family"] == "fam"
    assert rows[0]["distinct_signatures"] == 40


def test_disabled_path_noop():
    obscompile.configure(False)
    _fake_compile(("fam", 1))
    assert obscompile.events() == []
    # the real kernel path records nothing and bumps no tier counters
    view = obsreg.get_registry().view()
    fn = kc.get_kernel(("tobs_disabled", 1), lambda: (lambda x: x + 1))
    fn(jnp.arange(64))
    d = view.delta()["counters"]
    assert obscompile.events() == []
    assert not any(k.startswith("kernel.compile.") or
                   k in ("kernel.cache.compiles",
                         "kernel.cache.persistentHits") for k in d), d
    assert obscompile.totals()["events"] == 0


def test_reenable_does_not_fake_fresh_compiles():
    # built while disabled: never observed, even after a re-enable
    obscompile.configure(False)
    fn = kc.get_kernel(("tobs_toggle", 1), lambda: (lambda x: x - 1))
    fn(jnp.arange(32))
    obscompile.configure(True)
    fn(jnp.arange(32))          # warm dispatch of an unobserved kernel
    assert obscompile.totals()["events"] == 0
    # built while enabled: a shape compiled DURING a disabled window is
    # still seen-tracked, so re-enabling cannot misreport its next
    # (warm, microsecond) dispatch as a fresh compile
    fn2 = kc.get_kernel(("tobs_toggle", 2), lambda: (lambda x: x - 2))
    fn2(jnp.arange(32))                       # recorded
    obscompile.configure(False)
    fn2(jnp.arange(64))                       # compiled, not recorded
    obscompile.configure(True)
    fn2(jnp.arange(64))                       # warm: no bogus event
    assert obscompile.totals()["events"] == 1


def test_observed_compile_via_get_kernel():
    view = obsreg.get_registry().view()
    fn = kc.get_kernel(("tobs_real", 7), lambda: (lambda x: x * 2))
    fn(jnp.arange(128))         # first (key, shape): one event
    fn(jnp.arange(128))         # repeat shape: no new event
    fn(jnp.arange(256))         # new shape bucket: second event
    d = view.delta()["counters"]
    assert d.get("kernel.compile.events", 0) == 2
    assert d.get("kernel.cache.compiles", 0) + \
        d.get("kernel.cache.persistentHits", 0) == 2
    evs = [e for e in obscompile.events()
           if e["family"] == "tobs_real"]
    assert len(evs) == 2
    assert evs[0]["signature"] != evs[1]["signature"]
    assert all(e["wall_ms"] >= 0 for e in evs)


def _scaled(factor, x):
    return x * factor


@pytest.mark.parametrize("family,want", [
    ("agg_update", "jit_agg_update"),
    ("Probe-Emit.D", "jit_probe_emit_d"),        # cut to [a-z0-9_]
])
def test_programs_are_named_by_kernel_family(monkeypatch, family, want):
    """The module a kernel lowers to is ``jit_<family>`` whatever the
    traceable is (a partial and a lambda have no name of their own),
    and a replay payload re-jitted as sched/precompile.py does gives
    the same name: an AOT-warmed program under another module name
    would never be hit."""
    import functools
    built = []
    real = kc.jit_named
    monkeypatch.setattr(
        kc, "jit_named",
        lambda inner, fam, **kw: built.append(real(inner, fam, **kw))
        or built[-1])
    x = jnp.arange(32)
    inner = functools.partial(_scaled, 3)
    fn = kc.get_kernel((family, "tnamed", want), lambda: inner)
    assert list(fn(x)[:2]) == [0, 3]
    assert kc.program_name(family) == want
    (jitted,) = built
    assert f"module @{want} " in jitted.lower(x).as_text()
    spec = kc.load_replay_payload(
        kc._replay_payload(inner, {}, (x,), {}, family=family))
    replayed = kc.jit_replayed(spec).lower(*spec["args"])
    assert f"module @{want} " in replayed.as_text()
    # static arguments resolve against the traceable's own signature
    sliced = real(lambda b, cap: b[:cap], "dl_compact",
                  static_argnames=("cap",))
    assert sliced(x, 4).shape == sliced(x, cap=4).shape == (4,)
    assert "module @jit_dl_compact " in sliced.lower(x, cap=4).as_text()


def test_build_split_counters_and_event_parts():
    """A first call adds to ``kernel.build.traceNs`` and to exactly one
    of ``.compileNs`` / ``.loadNs`` (a compile, or a read-back from the
    persistent cache); a second call of the same shape adds to none.
    The parts ride the CompileEvent."""
    import time as _time
    names = [f"kernel.build.{p}Ns" for p in obscompile.BUILD_PARTS]

    def slow_to_trace(x):
        _time.sleep(0.02)             # tracing is host time
        return jnp.cumsum(x * 5 + 1)
    fn = kc.get_kernel(("tbuild", _time.time_ns()), lambda: slow_to_trace)
    x = jnp.arange(512)       # an eager program of its own: built here
    view = obsreg.get_registry().view()
    fn(x)
    first = view.delta()["counters"]
    assert first.get("kernel.build.traceNs", 0) >= 20e6
    assert first.get("kernel.build.lowerNs", 0) > 0
    assert (first.get("kernel.build.compileNs", 0) > 0) != \
        (first.get("kernel.build.loadNs", 0) > 0), first
    (evt,) = [e for e in obscompile.events() if e["family"] == "tbuild"]
    assert set(evt["build"]) == {"traceNs", "lowerNs", "compileNs",
                                 "loadNs"}
    assert evt["build"]["traceNs"] >= 20e6
    # the tier and the split agree on what the first call did
    loaded = evt["build"]["loadNs"] > 0
    assert evt["tier"] == (obscompile.TIER_PERSISTENT if loaded
                           else obscompile.TIER_FRESH)
    # the first call's wall holds the parts jax timed
    assert evt["wall_ms"] * 1e6 >= 0.9 * sum(evt["build"].values())
    view = obsreg.get_registry().view()
    fn(x)
    second = view.delta()["counters"]
    assert not any(second.get(n) for n in names), second


# ---------------------------------------------------------------------------
# query attribution
# ---------------------------------------------------------------------------

def test_concurrent_attribution_no_cross():
    kc.clear()
    s = _session()
    q1 = (_df(s).with_column("y", col("x") * 3.0 - 1.0)
          .filter(col("y") > 30.0).group_by("k")
          .agg(F.count("*").alias("c"), F.sum("y").alias("sy")))
    q2 = _df(s).select("x", "k").sort("x", "k").limit(40)
    f1, f2 = q1.collect_async(), q2.collect_async()
    f1.result(timeout=300), f2.result(timeout=300)
    qids = {f1.query_id, f2.query_id}
    digests = {f.query_id: f.profile.plan_digest for f in (f1, f2)}
    evs = [e for e in obscompile.events()
           if e["query_id"] in qids]
    assert evs, "two cold queries compiled nothing"
    # no cross-attribution: every event's digest is exactly the digest
    # of the query id it claims triggered it
    for e in evs:
        assert e["plan_digest"] == digests[e["query_id"]], e
    assert {e["query_id"] for e in evs} == qids
    # the per-query table accounts for every attributed event
    for qid in qids:
        st = obscompile.query_stats(qid)
        n = sum(1 for e in evs if e["query_id"] == qid)
        assert st["kernels_compiled"] + st["persistent_reloads"] == n


def test_cache_tier_classification():
    kc.clear()
    s = _session()
    q = (_df(s).filter(col("x") > 40.0).group_by("k")
         .agg(F.sum("x").alias("sx"), F.count("*").alias("c")))

    view = obsreg.get_registry().view()
    q.collect()
    d1 = view.delta()["counters"]
    assert d1.get("kernel.compile.events", 0) > 0

    # second run of the same query: zero fresh compiles, zero events —
    # everything is an in-memory kernel-cache hit
    view = obsreg.get_registry().view()
    q.collect()
    d2 = view.delta()["counters"]
    assert d2.get("kernel.cache.compiles", 0) == 0
    assert d2.get("kernel.compile.events", 0) == 0
    assert d2.get("kernel.cache.memHits", 0) > 0

    # drop every executable (this cache + jax's): the rebuild reloads
    # from the persistent XLA cache (enabled by tests/conftest.py) and
    # must classify as persistentHits, not fresh compiles
    kc.clear_compile_state()
    view = obsreg.get_registry().view()
    q.collect()
    d3 = view.delta()["counters"]
    assert d3.get("kernel.cache.persistentHits", 0) > 0, d3
    assert d3.get("kernel.cache.compiles", 0) == 0, d3
    tiers = {e["tier"] for e in obscompile.events()
             if e["query_id"] is not None}
    assert obscompile.TIER_PERSISTENT in tiers


# ---------------------------------------------------------------------------
# corpus
# ---------------------------------------------------------------------------

def test_corpus_jsonl_roundtrip(tmp_path):
    corpus = str(tmp_path / "corpus.jsonl")
    kc.clear()
    s = _session({"spark.rapids.tpu.obs.compile.corpusPath": corpus})
    qa = (_df(s).filter(col("x") > 11.0).group_by("k")
          .agg(F.sum("x").alias("sx")))
    qb = (_df(s).filter(col("x") > 93.0).group_by("k")
          .agg(F.sum("x").alias("sx")))
    qa.collect()
    qa.collect()          # repeat: same digest, no new corpus record
    qb.collect()          # distinct literal -> distinct digest + kernels
    with open(corpus) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert len(lines) == 2, lines
    digests = [r["plan_digest"] for r in lines]
    assert len(set(digests)) == 2
    for rec in lines:
        assert rec["query_id"] >= 1
        assert rec["programs"], rec
        for prog in rec["programs"]:
            assert prog["family"] and prog["signature"] and prog["key"]
            assert "backend" not in prog
    # round-trip: the first record's digest is the profile's digest
    prof = s.query_profile(lines[0]["query_id"])
    assert prof is not None and prof.plan_digest == digests[0]


# ---------------------------------------------------------------------------
# churn analytics
# ---------------------------------------------------------------------------

def test_churn_report_top_offender_ordering():
    # famC: 8 distinct capacity-keyed programs that width-bucket to 1;
    # famA: 5; famB: 2 — the report must rank C, A, B and estimate the
    # bucketed collapse
    for fam, n in (("famC", 8), ("famA", 5), ("famB", 2)):
        for i in range(n):
            cap = 1000 + i          # buckets to 1024 for every i
            _fake_compile(("k", fam, cap), family=fam,
                          leaves=((((cap,), "int64")),))
    rows = obscompile.churn_report()
    fams = [r["family"] for r in rows]
    assert fams == ["famC", "famA", "famB"]
    top = rows[0]
    assert top["distinct_signatures"] == 8
    assert top["est_programs_width_bucketed"] == 1
    assert top["est_collapse_savings"] == 7


def test_churn_bucketing_distinguishes_dtype_class():
    _fake_compile(("k", 900), family="fx",
                  leaves=((((900,), "int64")),))
    _fake_compile(("k", 901), family="fx",
                  leaves=((((901,), "float64")),))
    r = obscompile.churn_report()[0]
    # same pow2 bucket, different dtype CLASS: no collapse across types
    assert r["distinct_signatures"] == 2
    assert r["est_programs_width_bucketed"] == 2


# ---------------------------------------------------------------------------
# storms
# ---------------------------------------------------------------------------

def test_storm_fires_once_per_query(tmp_path):
    obscompile.configure(True, storm_threshold=3)
    obsrec.configure(str(tmp_path))
    try:
        obscompile.register_query(901, "digest-901")
        with sched_cancel.install(sched_cancel.CancelToken(901)):
            for i in range(6):      # crosses 3 once, stays crossed
                _fake_compile(("s", i))
        obscompile.register_query(902, "digest-902")
        with sched_cancel.install(sched_cancel.CancelToken(902)):
            for i in range(5):
                _fake_compile(("s2", i))
        storms = [e for e in obsrec.get_recorder().events()
                  if e["kind"] == "compile.storm"]
        assert [e["query"] for e in storms] == [901, 902]
        assert all(e["threshold"] == 3 for e in storms)
        assert storms[0]["plan_digest"] == "digest-901"
        assert obscompile.query_stats(901)["storm"] is True
        assert obsreg.get_registry().counter(
            "kernel.compile.storms") >= 2
    finally:
        obsrec.disable()


# ---------------------------------------------------------------------------
# surfaces: profile section, query table, slow-query log, endpoint
# ---------------------------------------------------------------------------

def test_profile_compile_section_and_span():
    kc.clear()
    s = _session({"spark.rapids.tpu.obs.trace.enabled": True})
    (_df(s).with_column("z", col("x") + 0.5).group_by("k")
     .agg(F.max("z").alias("mz"))).collect()
    prof = s.last_query_profile()
    assert "compile" in prof.metrics      # always-present section
    comp = prof.metrics["compile"]
    programs = comp.get("kernel.cache.compiles", 0) + \
        comp.get("kernel.cache.persistentHits", 0)
    assert programs > 0, comp
    assert comp.get("kernel.compile.events", 0) == programs
    assert comp.get("kernel.compile.wallNs", 0) > 0
    assert "kernel.compile.wallMs" in comp      # the histogram
    # wall_breakdown attribution + the real kernel.compile trace spans
    assert prof.wall_breakdown["compile_s"] > 0
    spans = [sp for sp in prof.spans if sp["name"] == "kernel.compile"]
    assert len(spans) == programs
    assert all(sp["args"]["tier"] in ("fresh", "persistent")
               for sp in spans)
    from spark_rapids_tpu.obs import trace as obs_trace
    obs_trace.configure(False)


def test_query_table_compile_fields():
    kc.clear()
    s = _session()
    q = (_df(s).filter(col("x") < 77.0).group_by("k")
         .agg(F.avg("x").alias("ax")))
    f1 = q.collect_async()
    f1.result(timeout=300)
    f2 = q.collect_async()
    f2.result(timeout=300)
    rows = {r["query_id"]: r for r in s.scheduler.query_table()}
    cold = rows[f1.query_id]
    warm = rows[f2.query_id]
    assert cold["kernels_compiled"] >= 1
    assert cold["compile_ms"] > 0
    # null when zero, per the slow-query/queries field contract
    assert warm["kernels_compiled"] is None
    assert warm["compile_ms"] is None


def test_slow_query_log_compile_fields(tmp_path):
    log = str(tmp_path / "slow.jsonl")
    kc.clear()
    s = _session({"spark.rapids.tpu.obs.slowQueryMs": 1,
                  "spark.rapids.tpu.obs.slowQueryPath": log})
    (_df(s).with_column("v", col("x") * 9.0).group_by("k")
     .agg(F.sum("v").alias("sv"))).collect()
    with open(log) as f:
        rec = json.loads(f.readline())
    assert "kernels_compiled" in rec and "compile_ms" in rec
    assert rec["kernels_compiled"] >= 1
    assert rec["compile_ms"] > 0


def test_compiles_endpoint(tmp_path):
    kc.clear()
    s = _session({"spark.rapids.tpu.obs.http.enabled": True})
    (_df(s).filter(col("x") > 64.0).group_by("k")
     .agg(F.count("*").alias("c"))).collect()
    base = f"http://127.0.0.1:{s.obs_server.port}"
    with urllib.request.urlopen(base + "/compiles?n=5",
                                timeout=10) as r:
        payload = json.loads(r.read().decode())
    assert payload["enabled"] is True
    assert payload["totals"]["events"] > 0
    assert len(payload["events"]) <= 5
    assert payload["churn"] and payload["per_query"]
    assert "selection" not in payload
    for e in payload["events"]:
        assert e["query_id"] and e["plan_digest"], e
    # the route is advertised
    with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
        assert "/compiles" in json.loads(r.read().decode())["routes"]
    s.obs_server.shutdown()


def test_threaded_ledger_consistency():
    # concurrent recorders must neither drop aggregate counts nor
    # corrupt the ring (deque append is atomic; aggregates are locked)
    def spin(tid):
        for i in range(50):
            _fake_compile(("t", tid, i), family=f"thr{tid}")
    threads = [threading.Thread(target=spin, args=(t,))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert obscompile.totals()["events"] == 200
    rows = {r["family"]: r for r in obscompile.churn_report()}
    assert all(rows[f"thr{t}"]["distinct_signatures"] == 50
               for t in range(4))
