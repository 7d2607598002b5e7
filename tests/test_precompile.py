"""AOT precompile service tests (sched/precompile.py + the corpus
replay payloads from exec/kernel_cache._replay_payload).

The restart-simulation contract (the CI corpus-replay gate runs the
two-process version): after dropping every in-memory compiled handle
and replaying the corpus, re-running the recorded plan reports ZERO
fresh compiles — persistent-cache reloads only.
"""

from __future__ import annotations

import json

import pytest

import jax

from spark_rapids_tpu import TpuSparkSession, col, functions as F
from spark_rapids_tpu.exec import kernel_cache as kc
from spark_rapids_tpu.obs import compile as obscompile
from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.sched.precompile import PrecompileService


def _corpus_session(tmp_path, **extra):
    corpus = str(tmp_path / "corpus.jsonl")
    conf = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
            "spark.rapids.tpu.obs.compile.corpusPath": corpus}
    conf.update(extra)
    return TpuSparkSession(conf), corpus


def _query(s, n=1500, mark=1.5):
    """``mark`` gives each test a DISTINCT plan (digest + expression
    signatures): the corpus dedups digests and the kernel cache holds
    programs for the whole process, so a repeated plan would write no
    corpus record and compile nothing."""
    df = s.create_dataframe(
        {"k": [i % 5 for i in range(n)],
         "x": [float(i % 90) for i in range(n)]},
        num_partitions=2)
    return (df.with_column("y", col("x") + mark).filter(col("y") > 10)
              .group_by("k").agg(F.sum("y").alias("sy")).sort("k"))


def test_corpus_programs_carry_replay_payloads(tmp_path):
    s, corpus = _corpus_session(tmp_path)
    _query(s).collect()
    recs = [json.loads(line) for line in open(corpus)]
    assert recs and recs[0]["plan_digest"]
    progs = [p for r in recs for p in r["programs"]]
    assert progs
    replayable = [p for p in progs if p.get("replay")]
    assert replayable, "no program carried a replay payload"
    # a payload round-trips to (traceable, jit kwargs, abstract args)
    spec = kc.load_replay_payload(replayable[0]["replay"])
    assert callable(spec["fn"])
    leaves = jax.tree_util.tree_leaves((spec["args"], spec["kwargs"]))
    assert any(isinstance(x, jax.ShapeDtypeStruct) for x in leaves)


def test_replay_disabled_by_corpus_replay_knob(tmp_path):
    s, corpus = _corpus_session(
        tmp_path,
        **{"spark.rapids.tpu.obs.compile.corpusReplay": False})
    _query(s, mark=2.25).collect()
    progs = [p for r in (json.loads(line) for line in open(corpus))
             for p in r["programs"]]
    assert progs and not any(p.get("replay") for p in progs)


def test_restart_sim_replay_then_zero_fresh_compiles(tmp_path):
    if not jax.config.jax_compilation_cache_dir:
        pytest.skip("persistent compile cache not active")
    s, corpus = _corpus_session(tmp_path)
    q = _query(s, mark=3.75)
    expect = q.collect()

    # restart simulation: drop every in-memory compiled handle; the
    # persistent cache dir (conftest) survives like a replica restart
    kc.clear_compile_state()
    obscompile.reset()

    svc = PrecompileService(s, corpus, idle_wait_ms=0)
    stats = svc.replay()
    assert stats["warmed"] > 0, stats
    assert stats["failed"] == 0, stats

    view = obsreg.get_registry().view()
    second = q.collect()
    d = view.delta()["counters"]
    assert second.equals(expect)
    assert d.get("kernel.cache.compiles", 0) == 0, dict(d)
    assert d.get("kernel.cache.persistentHits", 0) > 0, dict(d)


def test_replay_counts_skipped_and_dedup(tmp_path):
    corpus = tmp_path / "c.jsonl"
    prog = {"family": "f", "key": "k1", "signature": "s1"}
    recs = [
        {"plan_digest": "d1", "programs": [prog, dict(prog)]},   # dedup
        {"plan_digest": "d2", "programs": [
            {"family": "f", "key": "k2", "signature": "s2"}]},   # no payload
        {"plan_digest": "d3", "programs": [
            {"family": "f", "key": "k3", "signature": "s3",
             "replay": "!!!not-base64!!!"}]},                    # failed
    ]
    corpus.write_text("\n".join(json.dumps(r) for r in recs) + "\n"
                      + "{torn line\n")
    s = TpuSparkSession(
        {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
    svc = PrecompileService(s, str(corpus), idle_wait_ms=0)
    stats = svc.replay()
    assert stats["plans"] == 3
    assert stats["programs"] == 3           # dedup'd duplicate excluded
    assert stats["dedup"] == 1
    assert stats["skipped"] == 2            # k1 + k2: no payload
    assert stats["failed"] == 1             # k3: broken payload
    assert stats["warmed"] == 0


def test_replay_ignores_a_backend_field(tmp_path):
    # a corpus written before the engine had one kernel backend names
    # one on every program; such a file replays as any other
    s, corpus = _corpus_session(tmp_path)
    _query(s, mark=4.5).collect()
    recs = [json.loads(line) for line in open(corpus)]
    for r in recs:
        for p in r["programs"]:
            p["backend"] = "xla"
    with open(corpus, "w") as f:
        f.write("".join(json.dumps(r) + "\n" for r in recs))
    kc.clear_compile_state()
    stats = PrecompileService(s, corpus, idle_wait_ms=0).replay()
    assert stats["warmed"] > 0 and stats["failed"] == 0, stats


def test_background_start_and_wait(tmp_path):
    s, corpus = _corpus_session(tmp_path)
    _query(s, mark=5.125).collect()
    # a second session starting the service against the written corpus
    # (the session-init path): background replay, wait() joins it
    s2 = TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        "spark.rapids.tpu.sched.precompile.enabled": True,
        "spark.rapids.tpu.sched.precompile.corpusPath": corpus,
        "spark.rapids.tpu.sched.precompile.idleWaitMs": 0})
    svc = s2.precompile_service
    assert svc is not None
    assert svc.wait(timeout=120), "background replay did not finish"
    stats = svc.stats()
    assert stats["programs"] > 0
    assert stats["warmed"] + stats["skipped"] + stats["failed"] == \
        stats["programs"]


def test_donating_programs_record_replay_payload(tmp_path):
    """Donating kernels are cached and replayed like any other
    program: the corpus carries their payload (donate_argnums rides
    the recorded jit kwargs).  A fused chain over a donate-safe
    producer exercises one."""
    s, corpus = _corpus_session(tmp_path)
    df = s.create_dataframe(
        {"k": [i % 5 for i in range(800)],
         "x": [float(i) for i in range(800)]}, num_partitions=1)
    # standalone fused stage (not inlined into an aggregate): sort
    # consumes it, so the chain fuses and donates
    view = obsreg.get_registry().view()
    (df.with_column("y", col("x") * 2.0).filter(col("y") > 10.0)
       .select("y").sort("y").limit(5)).collect()
    d = view.delta()["counters"]
    if d.get("fusion.donatedDispatches", 0) == 0:
        pytest.skip("no donating dispatch in this plan shape")
    recs = [json.loads(line) for line in open(corpus)]
    fused = [p for r in recs for p in r["programs"]
             if p["family"] == "fused_stage"]
    assert fused and all(p.get("replay") for p in fused)
    from spark_rapids_tpu.exec import kernel_cache as kc
    assert any(kc.load_replay_payload(p["replay"])["jit"].get(
        "donate_argnums") for p in fused)
