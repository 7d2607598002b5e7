"""Whole-stage fusion tests (plan/fusion.py + exec/fused_stage.py).

Parity contract: every query must produce identical results with
``sql.fusion.enabled`` on and off (the unfused per-node path is the
fused path's correctness oracle), and the fused path must demonstrably
save jit dispatches (obs registry ``kernel.dispatches``).
"""

from __future__ import annotations

import pyarrow as pa
import pytest

from spark_rapids_tpu import TpuSparkSession, col, functions as F
from spark_rapids_tpu.exec.fused_stage import TpuFusedStageExec
from spark_rapids_tpu.obs import registry as obsreg


def _session(fusion: bool = True, **extra) -> TpuSparkSession:
    conf = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
            "spark.rapids.tpu.sql.fusion.enabled": fusion}
    conf.update(extra)
    return TpuSparkSession(conf)


def _data(session, num_partitions=2):
    return session.create_dataframe(
        {"a": [1, None, 3, 4, None, 6, 7, 8],
         "b": [10.0, 20.0, None, 40.0, 50.0, 60.0, None, 80.0],
         "s": ["ab", "cd", None, "ef", "gh", None, "ij", "kl"],
         "k": [0, 1, 0, 1, 0, 1, 0, 1]},
        num_partitions=num_partitions)


def _plan_names(session, df):
    res = session._plan_physical(df.plan)
    names = []
    res.plan.foreach(lambda n: names.append(type(n).__name__))
    return names, res.plan


def _collect_both(build, sort_key, **extra):
    """Run ``build(df)`` under fused and unfused sessions; return the
    sorted tables plus the fused session/plan for shape assertions."""
    sf = _session(True, **extra)
    su = _session(False, **extra)
    tf = build(_data(sf)).collect().sort_by(sort_key)
    tu = build(_data(su)).collect().sort_by(sort_key)
    return tf, tu, sf


# ---------------------------------------------------------------------------
# parity sweep
# ---------------------------------------------------------------------------

def test_project_filter_chain_parity_and_shape():
    def build(df):
        return (df.with_column("d", col("a") + col("b"))
                  .filter(col("d") > 15.0)
                  .with_column("e", col("d") * 2)
                  .select("e", "k"))
    tf, tu, sf = _collect_both(build, "e")
    assert tf.equals(tu)
    names, _ = _plan_names(sf, build(_data(sf)))
    assert "TpuFusedStageExec" in names
    assert "TpuProjectExec" not in names and "TpuFilterExec" not in names


def test_string_chain_with_nulls_parity():
    def build(df):
        return (df.with_column("u", F.upper(col("s")))
                  .filter(col("u") != "AB")
                  .with_column("c2", F.concat(col("u"), col("s"))))
    tf, tu, _ = _collect_both(build, "c2")
    assert tf.equals(tu)


def test_narrow_string_output_projects_before_compaction_parity():
    # composed output (1 string col) is narrower than the stage input,
    # so the kernel takes the project-first ordering (compaction
    # scatters only the output columns) — pin parity for the
    # variable-length-column case on that branch
    def build(df):
        return (df.with_column("u", F.upper(col("s")))
                  .filter(col("a") > 2)
                  .select(F.concat(col("u"), col("s")).alias("c2")))
    tf, tu, sf = _collect_both(build, "c2")
    assert tf.equals(tu)
    names, _ = _plan_names(sf, build(_data(sf)))
    assert "TpuFusedStageExec" in names


def test_chain_around_limit_parity():
    # limit is not fusable; chains fuse independently on either side
    def build(df):
        return (df.with_column("d", col("a") * 2)
                  .filter(col("d") >= 2)
                  .limit(4)
                  .with_column("e", col("d") + col("k"))
                  .select("d", "e"))
    sf, su = _session(True), _session(False)
    tf = build(_data(sf, num_partitions=1)).collect()
    tu = build(_data(su, num_partitions=1)).collect()
    assert tf.equals(tu)


def test_agg_prologue_inlined_parity():
    def build(df):
        return (df.with_column("d", col("a") + col("b"))
                  .filter(col("d") > 15.0)
                  .group_by("k")
                  .agg(F.count("*").alias("n"),
                       F.sum("d").alias("sd")))
    tf, tu, sf = _collect_both(build, "k")
    assert tf.equals(tu)
    names, plan = _plan_names(sf, build(_data(sf)))
    # the whole prologue inlined into the aggregate: no standalone
    # project/filter/stage dispatches remain below it
    assert "TpuProjectExec" not in names
    assert "TpuFilterExec" not in names
    assert "TpuFusedStageExec" not in names
    aggs = []
    plan.foreach(lambda n: aggs.append(n)
                 if type(n).__name__ == "TpuHashAggregateExec" else None)
    assert aggs and aggs[0].fused_prologue_execs >= 2
    assert aggs[0].fused_condition is not None


def test_repeated_collect_of_same_dataframe_is_stable():
    # R2 substitutes into the aggregate's expressions; those must be
    # CLONES — the logical plan shares the aggregate nodes, so in-place
    # mutation would poison the next planning of the SAME DataFrame
    # (regression: second collect once returned sums with the grouping
    # key folded in)
    s = _session(True)
    df = _data(s)
    q = (df.select((col("a") + col("b")).alias("d"), col("k"))
           .group_by("k").agg(F.sum("d").alias("sd")))
    first = q.collect().sort_by("k")
    for _ in range(2):
        assert q.collect().sort_by("k").equals(first)
    su = _session(False)
    qu = (_data(su).select((col("a") + col("b")).alias("d"), col("k"))
          .group_by("k").agg(F.sum("d").alias("sd")))
    assert qu.collect().sort_by("k").equals(first)


def test_chain_below_sort_parity():
    def build(df):
        return (df.with_column("d", col("a") + col("k"))
                  .filter(col("d") >= 2)
                  .sort("d", "k"))
    tf, tu, sf = _collect_both(build, "d")
    assert tf.equals(tu)
    names, _ = _plan_names(sf, build(_data(sf)))
    assert "TpuFusedStageExec" in names


def test_pure_select_is_zero_dispatch_passthrough():
    s = _session(True)
    df = _data(s).select("a", "k")
    names, plan = _plan_names(s, df)
    assert "TpuFusedStageExec" in names
    stages = []
    plan.foreach(lambda n: stages.append(n)
                 if isinstance(n, TpuFusedStageExec) else None)
    assert stages[0].is_passthrough
    view = obsreg.get_registry().view()
    out = df.collect()
    d = view.delta()["counters"]
    # zero CHAIN dispatches (the terminal collect's pack kernel is the
    # download path, not the chain)
    for fam in ("project", "filter", "fused_stage"):
        assert d.get(f"kernel.dispatches.{fam}", 0) == 0
    assert out.column_names == ["a", "k"]
    su = _session(False)
    assert out.equals(_data(su).select("a", "k").collect())


# ---------------------------------------------------------------------------
# partition-dependent expressions
# ---------------------------------------------------------------------------

def test_spark_partition_id_inside_fused_kernel():
    def build(df):
        return (df.with_column("p", F.spark_partition_id())
                  .filter(col("a").is_not_null())
                  .with_column("pk", col("p") * 10 + col("k")))
    sf, su = _session(True), _session(False)
    dff, dfu = build(_data(sf, 4)), build(_data(su, 4))
    names, _ = _plan_names(sf, dff)
    assert "TpuFusedStageExec" in names  # SparkPartitionID fuses
    tf = dff.collect().sort_by([("a", "ascending")])
    tu = dfu.collect().sort_by([("a", "ascending")])
    assert tf.equals(tu)
    # the fused kernel saw the real task context, not a default
    assert len(set(tf.column("p").to_pylist())) > 1


def test_spark_partition_id_blocks_agg_inline_but_stays_correct():
    def build(df):
        return (df.with_column("p", F.spark_partition_id())
                  .group_by("p").agg(F.count("*").alias("n")))
    sf, su = _session(True), _session(False)
    tf = build(_data(sf, 3)).collect().sort_by("p")
    tu = build(_data(su, 3)).collect().sort_by("p")
    assert tf.equals(tu)
    names, plan = _plan_names(sf, build(_data(sf, 3)))
    aggs = []
    plan.foreach(lambda n: aggs.append(n)
                 if type(n).__name__ == "TpuHashAggregateExec" else None)
    # the update kernel has no task context — the pid projection must
    # NOT inline into the aggregate
    assert aggs[0].fused_prologue_execs == 0


def test_partition_id_filter_under_agg_stays_outside_and_correct():
    # regression: the lone-filter-under-aggregate post-pass
    # (overrides._fuse_filters_into_aggregates) used to fuse ANY filter
    # unconditionally — a partition-dependent condition then evaluated
    # against the default task context inside the update kernel and
    # every partition saw pid=0 (empty/wrong aggregate, both fusion on
    # AND off, so the parity sweep never caught it)
    def build(s):
        df = s.create_dataframe(
            {"k": [i % 3 for i in range(300)],
             "x": [float(i) for i in range(300)]}, num_partitions=4)
        return (df.filter(F.spark_partition_id() > 0)
                  .group_by("k").agg(F.count("*").alias("n")).sort("k"))
    tf = build(_session(True)).collect()
    tu = build(_session(False)).collect()
    assert tf.equals(tu)
    # 3 of 4 partitions survive the pid filter: 75 rows each
    assert sum(tf.column("n").to_pylist()) == 225


def test_standalone_partition_id_filter_sees_task_context():
    # regression: TpuFilterExec's kernel took no pid/offset, so a
    # partition-dependent condition evaluated against the context
    # default (0, 0) on every partition
    def build(s):
        df = s.create_dataframe(
            {"a": list(range(120))}, num_partitions=3)
        return df.filter(F.spark_partition_id() == 1)
    tf = build(_session(True)).collect()
    tu = build(_session(False)).collect()
    assert tf.num_rows == tu.num_rows == 40


# ---------------------------------------------------------------------------
# fusion barriers
# ---------------------------------------------------------------------------

def test_monotonic_id_is_a_fusion_barrier():
    def build(df):
        return (df.with_column("m", F.monotonically_increasing_id())
                  .filter(col("k") == 0)
                  .select("a", "m"))
    sf, su = _session(True), _session(False)
    names, _ = _plan_names(sf, build(_data(sf)))
    # the mid project must survive (position-dependent across the
    # compaction a fused stage would reorder)
    assert "TpuProjectExec" in names
    tf = build(_data(sf)).collect().sort_by("m")
    tu = build(_data(su)).collect().sort_by("m")
    assert tf.equals(tu)


def test_rand_is_a_fusion_barrier():
    s = _session(True)
    df = (_data(s).with_column("r", F.rand(7))
                  .filter(col("k") == 1)
                  .select("r", "a"))
    names, _ = _plan_names(s, df)
    assert "TpuProjectExec" in names


def test_python_udf_is_a_fusion_barrier():
    s = _session(True,
                 **{"spark.rapids.tpu.sql.udfCompiler.enabled": False})
    fn = F.udf(lambda x: (x or 0) + 1, returnType="long")
    df = (_data(s).with_column("u", fn(col("a")))
                  .filter(col("k") == 0))
    names, _ = _plan_names(s, df)
    assert "TpuFusedStageExec" not in names
    su = _session(False,
                  **{"spark.rapids.tpu.sql.udfCompiler.enabled": False})
    dfu = (_data(su).with_column("u", fn(col("a")))
                    .filter(col("k") == 0))
    assert df.collect().sort_by("a").equals(dfu.collect().sort_by("a"))


def test_multi_consumer_subtree_does_not_fuse():
    from spark_rapids_tpu.config import RapidsTpuConf
    from spark_rapids_tpu.exec import cpu as cpux, tpu_basic as tpub
    from spark_rapids_tpu.expr import ir
    from spark_rapids_tpu.plan.fusion import fuse_stages
    from spark_rapids_tpu.plan.logical import Field, Schema
    from spark_rapids_tpu import dtypes as dt

    table = pa.table({"a": [1, 2, 3, 4], "b": [5, 6, 7, 8]})
    scan = cpux.CpuScanExec(table, 1, 1 << 20)
    h2d = tpub.HostToDeviceExec(scan)

    def bind(name, schema):
        return ir.bind(ir.UnresolvedAttribute(name), schema.names,
                       schema.dtypes, schema.nullables)

    in_schema = h2d.schema
    ssum = ir.Add(bind("a", in_schema), bind("b", in_schema))
    ssum.resolve()
    shared_schema = Schema([Field("s", ssum.dtype, True),
                            Field("a", dt.INT64, True)])
    shared = tpub.TpuProjectExec(
        h2d, [ir.Alias(ssum, "s"), bind("a", in_schema)], shared_schema)

    def branch(threshold):
        c = ir.GreaterThan(bind("s", shared_schema),
                           ir.Literal(threshold))
        c.resolve()
        filt = tpub.TpuFilterExec(shared, c)
        dbl = ir.Multiply(bind("s", shared_schema), ir.Literal(2))
        dbl.resolve()
        return tpub.TpuProjectExec(
            filt, [ir.Alias(dbl, "d")],
            Schema([Field("d", dbl.dtype, True)]))

    union = tpub.TpuUnionExec([branch(6), branch(8)])
    fused = fuse_stages(union, RapidsTpuConf({}))
    projects = []
    fused.foreach(lambda n: projects.append(n)
                  if isinstance(n, tpub.TpuProjectExec) else None)
    # each branch's own [project, filter] pair fuses, but the chain
    # must STOP at the shared (multi-consumer) project — it survives
    # as ONE node referenced from both branches
    assert len({id(p) for p in projects}) == 1
    assert projects[0] is shared
    stages = []
    fused.foreach(lambda n: stages.append(n)
                  if isinstance(n, TpuFusedStageExec) else None)
    assert len(stages) == 2
    assert all(st.children[0] is shared for st in stages)


def test_chain_below_shared_subtree_still_fuses():
    """Refcounts are parent-EDGE counts, not root-to-node path counts:
    a single-consumer Project/Filter chain sitting BELOW a
    multi-consumer node must still fuse (a path-counting walk would
    see every descendant of the shared node as multi-consumer and
    silently skip fusion there)."""
    from spark_rapids_tpu.config import RapidsTpuConf
    from spark_rapids_tpu.exec import cpu as cpux, tpu_basic as tpub
    from spark_rapids_tpu.expr import ir
    from spark_rapids_tpu.plan.fusion import fuse_stages
    from spark_rapids_tpu.plan.logical import Field, Schema

    table = pa.table({"a": [1, 2, 3, 4], "b": [5, 6, 7, 8]})
    scan = cpux.CpuScanExec(table, 1, 1 << 20)
    h2d = tpub.HostToDeviceExec(scan)

    def bind(name, schema):
        return ir.bind(ir.UnresolvedAttribute(name), schema.names,
                       schema.dtypes, schema.nullables)

    # single-consumer chain below the shared node: project -> filter
    ssum = ir.Add(bind("a", h2d.schema), bind("b", h2d.schema))
    ssum.resolve()
    p1_schema = Schema([Field("s", ssum.dtype, True)])
    p1 = tpub.TpuProjectExec(h2d, [ir.Alias(ssum, "s")], p1_schema)
    c1 = ir.GreaterThan(bind("s", p1_schema), ir.Literal(6))
    c1.resolve()
    f1 = tpub.TpuFilterExec(p1, c1)

    # multi-consumer shared node above the chain (barrier expr keeps
    # the shared project itself out of any chain)
    mid = ir.MonotonicallyIncreasingID()
    mid.resolve()
    shared_schema = Schema([Field("s", ssum.dtype, True),
                            Field("i", mid.dtype, False)])
    shared = tpub.TpuProjectExec(
        f1, [bind("s", p1_schema), ir.Alias(mid, "i")], shared_schema)

    def branch(threshold):
        c = ir.GreaterThan(bind("s", shared_schema),
                           ir.Literal(threshold))
        c.resolve()
        return tpub.TpuFilterExec(shared, c)

    union = tpub.TpuUnionExec([branch(7), branch(9)])
    fused = fuse_stages(union, RapidsTpuConf({}))
    stages = []
    fused.foreach(lambda n: stages.append(n)
                  if isinstance(n, TpuFusedStageExec) else None)
    # foreach walks per-path, so the one stage under the SHARED node is
    # reported once per parent — dedupe by identity
    below = {id(st): st for st in stages if st.children[0] is h2d}
    assert len(below) == 1
    (stage,) = below.values()
    assert stage.fused == ("TpuFilterExec", "TpuProjectExec")


def test_max_exprs_guard_blocks_fusion():
    s = _session(True,
                 **{"spark.rapids.tpu.sql.fusion.maxExprs": 3})
    df = (_data(s).with_column("d", col("a") + col("b"))
                  .filter(col("d") > 15.0))
    names, _ = _plan_names(s, df)
    assert "TpuFusedStageExec" not in names
    su = _session(False)
    dfu = (_data(su).with_column("d", col("a") + col("b"))
                    .filter(col("d") > 15.0))
    assert df.collect().sort_by("a").equals(
        dfu.collect().sort_by("a"))


# ---------------------------------------------------------------------------
# dispatch accounting + kernel-cache hygiene
# ---------------------------------------------------------------------------

def test_dispatch_count_drops_with_fusion():
    def build(df):
        return (df.with_column("d", col("a") + col("b"))
                  .filter(col("d") > 15.0)
                  .with_column("e", col("d") - col("k"))
                  .select("e", "k"))
    counts = {}
    for fused in (True, False):
        s = _session(fused)
        build(_data(s)).collect()  # warm compiles
        view = obsreg.get_registry().view()
        build(_data(s)).collect()
        d = view.delta()["counters"]
        counts[fused] = d.get("kernel.dispatches", 0)
        if fused:
            assert d.get("fusion.dispatchesSaved", 0) > 0
    assert counts[True] < counts[False]
    assert 1 - counts[True] / counts[False] >= 0.30


def test_aliased_projections_share_one_kernel():
    s = _session(False)  # raw TpuProjectExec path
    df = _data(s)
    df.select((col("a") + col("b")).alias("x")).collect()
    view = obsreg.get_registry().view()
    out = df.select((col("a") + col("b")).alias("y")).collect()
    d = view.delta()["counters"]
    # same expression under a different alias: no new PROJECT kernel
    # compiles (the terminal download's pack kernel keys on output
    # names and may re-compile), and the output carries the new name
    assert d.get("kernel.cache.misses.project", 0) == 0
    assert d.get("kernel.cache.hits.project", 0) >= 1
    assert out.column_names == ["y"]


def test_donation_armed_while_persistent_cache_active():
    # the test suite runs WITH the persistent compile cache (conftest);
    # donation stays armed under it and donating programs are cached
    # like any other
    import jax
    if not jax.config.jax_compilation_cache_dir:
        pytest.skip("persistent compile cache not active")
    s = _session(True)
    view = obsreg.get_registry().view()
    out = (_data(s).with_column("d", col("a") + col("b"))
           .filter(col("d") > 15.0).select("d")).collect()
    d = view.delta()["counters"]
    assert d.get("fusion.donatedDispatches", 0) > 0
    assert out.num_rows > 0


def _reset_persistent_cache(cache_dir):
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    cc.reset_cache()


def test_donating_programs_persist_and_reload(tmp_path):
    # a donating kernel built through the kernel cache is written to
    # the persistent XLA cache like any other program, and the copy
    # reloaded from disk computes the same answers
    import os
    import jax
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.exec import kernel_cache as kc
    _session(True)   # ensures the persistent-cache flags are configured
    prev = jax.config.jax_compilation_cache_dir
    cache = str(tmp_path / "xla")
    os.makedirs(cache, exist_ok=True)
    _reset_persistent_cache(cache)
    try:
        def build():
            return lambda x, y: (x * 3 + 1, y + x.astype(y.dtype))
        want = (np.arange(32) * 3 + 1).tolist()
        y = jnp.ones(32, dtype=jnp.float32)
        k1 = kc.get_kernel(("test_donate_persist", 1), build,
                           oom_retry=False, donate_argnums=(0,))
        assert np.asarray(k1(jnp.arange(32), y)[0]).tolist() == want
        assert os.listdir(cache), "donating program was not persisted"
        kc.clear_compile_state()    # the re-jit reloads from disk
        k2 = kc.get_kernel(("test_donate_persist", 1), build,
                           oom_retry=False, donate_argnums=(0,))
        assert np.asarray(k2(jnp.arange(32), y)[0]).tolist() == want
    finally:
        _reset_persistent_cache(prev)


def test_donation_persistent_cache_repro():
    # pinned contract: compile a donating identity-shaped kernel, write
    # it to a persistent cache, drop jax's in-memory caches so the
    # re-jit RELOADS the executable from disk, and assert the reloaded
    # executable applies the donation aliasing table correctly.  An
    # older jax returned af's bits inside the ai+0 output after such a
    # reload; the engine persists donating programs, so this must hold
    # (chip_smoke.py runs the same check on the chip).
    import tempfile
    import jax
    import jax.numpy as jnp
    prev = jax.config.jax_compilation_cache_dir
    cache = tempfile.mkdtemp(prefix="donate_repro_")
    _reset_persistent_cache(cache)
    try:
        def k(ai, af, p):
            return ai + 0, af * 1.0, p + ai.astype(p.dtype)
        ai = jnp.arange(16, dtype=jnp.int32)
        af = jnp.linspace(0.0, 1.0, 16, dtype=jnp.float32)
        p = jnp.ones(16, dtype=jnp.float32)
        expect = [x.tolist()
                  for x in jax.jit(k, donate_argnums=(0,))(ai, af, p)]
        jax.clear_caches()      # force the re-jit to reload from disk
        got = [x.tolist() for x in jax.jit(k, donate_argnums=(0,))(
            jnp.arange(16, dtype=jnp.int32), af, p)]
        assert got == expect, (
            "persistent-cache reload mis-applied donate_argnums "
            f"aliasing: {got[0][:4]} vs {expect[0][:4]}")
    finally:
        _reset_persistent_cache(prev)


def test_donation_knob_parity_and_counter():
    def build(df):
        return (df.with_column("d", col("a") + col("b"))
                  .filter(col("d") > 15.0)
                  .with_column("e", col("d") * 3)
                  .select("e"))
    import jax
    cache_dir = jax.config.jax_compilation_cache_dir
    try:
        # donation arms regardless of persistent-cache state now (the
        # no-persist guard replaced the auto-disarm); the dir is still
        # nulled here so this test exercises the plain donation path
        # independent of the guard.  The donate flag itself is
        # PLAN-stamped per session (not process-global), so the two
        # sessions cannot interfere
        s_on = _session(True)
        s_off = _session(
            True, **{"spark.rapids.tpu.sql.fusion.donateInputs": False})
        jax.config.update("jax_compilation_cache_dir", None)
        view = obsreg.get_registry().view()
        t_on = build(_data(s_on)).collect().sort_by("e")
        donated = view.delta()["counters"].get(
            "fusion.donatedDispatches", 0)
        view = obsreg.get_registry().view()
        t_off = build(_data(s_off)).collect().sort_by("e")
        donated_off = view.delta()["counters"].get(
            "fusion.donatedDispatches", 0)
        assert t_on.equals(t_off)
        # CPU jax supports donation (probed on 0.4.37); the counter
        # must reflect the dispatches that actually donated
        assert donated > 0
        # the knob-off session's plans must NOT donate, even though a
        # default-conf session exists in the same process — the stamp
        # is per-plan, there is no last-writer-wins global
        assert donated_off == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_dir)


def test_donated_batches_keep_row_count_metrics_alive():
    # regression: kernels donated the WHOLE input batch pytree, so XLA
    # invalidated its num_rows scalar — the very array the producing
    # stage had lazily buffered in Metrics._rows_pending.  Resolution
    # at profile time then raised "Array has been deleted" (or the
    # profile silently lost per-node row counts).  The count now rides
    # as a separate non-donated kernel argument (rows_detached).
    import json

    import jax
    cache_dir = jax.config.jax_compilation_cache_dir
    try:
        s = _session(True)
        jax.config.update("jax_compilation_cache_dir", None)  # arm
        df = s.create_dataframe(
            {"a": [1, 2, 3, 4] * 50, "b": [10.0, 20.0, 30.0, 40.0] * 50},
            num_partitions=2)
        view = obsreg.get_registry().view()
        # rand() is a fusion barrier with NO context host-sync: the
        # standalone project above the stage donates the stage's output
        # without ever reading num_rows host-side first
        t = (df.with_column("d", col("a") + col("b"))
               .filter(col("d") > 15.0)
               .with_column("r", F.rand(42))).collect()
        assert t.num_rows == 150
        donated = view.delta()["counters"].get(
            "fusion.donatedDispatches", 0)
        assert donated > 0  # donation really engaged
        prof = json.loads(s.last_query_profile().to_json())

        def walk(n, out):
            out.append(n)
            for c in n.get("children", []):
                walk(c, out)
        nodes = []
        walk(prof["plan"], nodes)
        fused_rows = [n["rows"] for n in nodes
                      if "FusedStage" in n["name"]]
        # the stage's lazily-buffered device-scalar count must resolve
        assert fused_rows == [150]
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_dir)


def test_duplicated_column_passthrough_does_not_crash_donating_consumer():
    # regression (confirmed XlaRuntimeError "Attempt to donate the same
    # buffer twice"): a passthrough stage duplicating a column forwards
    # ONE device array as two batch leaves; the barrier-bearing project
    # above it donates the stage's output batch.  donate_ok must refuse
    # when the passthrough's ordinals contain duplicates.
    import jax
    cache_dir = jax.config.jax_compilation_cache_dir
    try:
        s = _session(True)
        jax.config.update("jax_compilation_cache_dir", None)  # arm
        q = (_data(s).select(col("a"), col("a").alias("a2"))
                     .with_column("m", F.monotonically_increasing_id()))
        t = q.collect()
        assert t.column("a").equals(t.column("a2"))
        su = _session(False)
        jax.config.update("jax_compilation_cache_dir", None)
        tu = (_data(su).select(col("a"), col("a").alias("a2"))
                       .with_column("m", F.monotonically_increasing_id())
              ).collect()
        assert t.sort_by("m").equals(tu.sort_by("m"))
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_dir)


def test_lone_filter_under_agg_saves_nothing_vs_legacy_baseline():
    # the legacy lone-filter-under-aggregate post-pass (agg.fusedFilter)
    # absorbs scan->filter->agg's filter even with fusion OFF, so the
    # R2 inlining of that same filter is not a dispatch fusion saves —
    # dispatchesSaved must stay 0 and the ground-truth dispatch counts
    # must match between fusion on and off
    def run(fused):
        s = _session(fused)
        # every column used: no pruning select exists to become a
        # (legitimately counted) passthrough stage
        df = s.create_dataframe(
            {"b": [10.0, 20.0, None, 40.0] * 2, "k": [0, 1] * 4},
            num_partitions=2)
        q = (df.filter(col("b") > 15.0)
               .group_by("k").agg(F.count("*").alias("n")))
        q.collect()  # warm compiles
        view = obsreg.get_registry().view()
        q.collect()
        d = view.delta()["counters"]
        return (d.get("kernel.dispatches", 0),
                d.get("fusion.dispatchesSaved", 0))
    fused_counts, fused_saved = run(True)
    plain_counts, _ = run(False)
    assert fused_counts == plain_counts
    assert fused_saved == 0


def test_donate_ok_sees_through_passthrough_stages():
    # a passthrough stage forwards its child's buffers by reference;
    # the donation decision must apply to the TRANSITIVE producer
    import spark_rapids_tpu.dtypes as dt
    from spark_rapids_tpu.exec import fused_stage as fs
    from spark_rapids_tpu.exec.base import PhysicalPlan
    from spark_rapids_tpu.expr import ir
    from spark_rapids_tpu.plan.logical import Field, Schema

    ref = ir.BoundReference(0, dt.INT64, True, name_="x")
    ref2 = ir.BoundReference(0, dt.INT64, True, name_="x2")
    sch = Schema([Field("x", dt.INT64, True)])
    sch2 = Schema([Field("x", dt.INT64, True),
                   Field("x2", dt.INT64, True)])

    class UnsafeProducer(PhysicalPlan):  # cache/shuffle-like
        pass

    class HostToDeviceExec(PhysicalPlan):  # allowlisted name
        pass

    over_unsafe = TpuFusedStageExec(
        UnsafeProducer(), [ref], sch, None, ["TpuProjectExec"])
    over_safe = TpuFusedStageExec(
        HostToDeviceExec(), [ref], sch, None, ["TpuProjectExec"])
    assert over_unsafe.is_passthrough and over_safe.is_passthrough
    assert not fs.donate_ok(over_unsafe, True)
    assert fs.donate_ok(over_safe, True)
    # the consumer's plan-stamped flag gates everything
    assert not fs.donate_ok(over_safe, False)
    # a passthrough duplicating a column yields the SAME device
    # array as two batch leaves — donating that batch is an XLA
    # "donate the same buffer twice" error, so it bars donation
    dup = TpuFusedStageExec(
        HostToDeviceExec(), [ref, ref2], sch2, None,
        ["TpuProjectExec"])
    assert dup.is_passthrough
    assert not fs.donate_ok(dup, True)


def test_fusion_metrics_in_query_profile():
    s = _session(True)
    q = (_data(s).with_column("d", col("a") + col("b"))
                 .filter(col("d") > 15.0)
                 .with_column("e", col("d") * 2)
                 .select("e", "k"))
    q.collect()
    prof = s.last_query_profile()
    assert prof is not None
    assert "fusion" in prof.metrics
    assert prof.metrics["fusion"].get("fusion.stages", 0) >= 1
    assert prof.metrics["fusion"].get("fusion.dispatchesSaved", 0) > 0
    assert "fused_stage_s" in prof.wall_breakdown
    assert prof.wall_breakdown["fused_stage_s"] > 0


def test_fused_stage_explain_names_the_collapsed_execs():
    s = _session(True)
    q = (_data(s).with_column("d", col("a") + col("b"))
                 .filter(col("d") > 15.0)
                 .select("d"))
    _, plan = _plan_names(s, q)
    stages = []
    plan.foreach(lambda n: stages.append(n)
                 if isinstance(n, TpuFusedStageExec) else None)
    assert stages
    ss = stages[0].simple_string()
    assert "TpuProjectExec" in ss and "TpuFilterExec" in ss


# ---------------------------------------------------------------------------
# refcount-aware donation bar for shared scans (io/scan_share.try_steal)
# ---------------------------------------------------------------------------

def _scan_conf(**extra):
    conf = {
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        "spark.rapids.tpu.sched.dedup.enabled": False,
        "spark.rapids.tpu.sql.scan.metadataCache.enabled": False,
        "spark.rapids.tpu.memory.spill.enabled": False,
    }
    conf.update(extra)
    return conf


def _scan_query(tmp_path, session_conf):
    import pyarrow.parquet as papq
    p = str(tmp_path / "donation.parquet")
    import os
    if not os.path.exists(p):
        # write ONCE per test: a rewrite bumps mtime_ns and the
        # content-addressed share key would never match again
        papq.write_table(pa.table(
            {"a": list(range(4000)),
             "b": [float(i % 97) for i in range(4000)]}), p)
    s = TpuSparkSession(session_conf)
    df = s.read.parquet(p)
    return lambda: df.filter(col("a") > 10).select("a", "b").collect()


def test_solo_shared_scan_recovers_donation(tmp_path):
    """A scan batch nobody else holds must DONATE even with sharing
    enabled: try_steal withdraws it from the retention window and the
    donating kernel twin dispatches (the static bar used to forfeit
    this donation for every shared-capable scan)."""
    from spark_rapids_tpu.io import scan_share
    q = _scan_query(tmp_path, _scan_conf())
    base = q()                       # warm kernels; retains the batch
    sh = scan_share.peek_share()
    assert sh is not None
    sh.clear()
    view = obsreg.get_registry().view()
    assert q().equals(base)
    d = view.delta()["counters"]
    assert d.get("fusion.donationsRecovered", 0) > 0, d
    assert d.get("scan.shared.donationSteals", 0) > 0, d
    assert d.get("fusion.donatedDispatches", 0) > 0, d
    assert d.get("fusion.donationsBarred", 0) == 0, d
    # the steal re-opened the key: nothing retained, nothing leaked
    assert sh.stats()["window_entries"] == 0


def test_shared_scan_with_live_subscriber_stays_barred(tmp_path):
    """While another query's pipeline holds the multicast batch
    (joined > 0), the per-batch gate must refuse donation — the
    consumer dispatches through the non-donating kernel twin."""
    from spark_rapids_tpu.io import scan_share
    # populate the retention window WITHOUT stealing: donation off
    q_off = _scan_query(tmp_path, _scan_conf(**{
        "spark.rapids.tpu.sql.fusion.donateInputs": False}))
    base = q_off()
    sh = scan_share.peek_share()
    assert sh is not None and sh.stats()["window_entries"] >= 1
    # a second query "holds" the batch: a live join claim on the entry
    key = next(iter(sh._window.keys()))
    role, held = sh.claim(key)
    assert role == "join"
    try:
        q_on = _scan_query(tmp_path, _scan_conf())
        view = obsreg.get_registry().view()
        assert q_on().equals(base)
        d = view.delta()["counters"]
        assert d.get("fusion.donationsBarred", 0) > 0, d
        assert d.get("fusion.donationsRecovered", 0) == 0, d
        assert d.get("fusion.donatedDispatches", 0) == 0, d
        assert d.get("scan.shared.donationSteals", 0) == 0, d
    finally:
        sh.release(held)


def test_try_steal_refuses_multicast_history():
    """joined>0 bars the steal FOREVER: a subscriber's pipeline may
    hold the batch object long after its claim released, so a batch
    that was EVER multicast can never be donated."""
    from spark_rapids_tpu.io.scan_share import ScanShare
    sh = ScanShare(1 << 20)
    role, e = sh.claim(("k",))
    assert role == "lead"

    class _B:
        def nbytes(self):
            return 1024
    sh.publish(e, _B())
    role2, e2 = sh.claim(("k",))
    assert role2 == "join" and e2 is e
    sh.release(e)
    sh.release(e2)
    # both claims released, but the join HAPPENED: steal must refuse
    assert e.joined == 1 and e.refs == 0
    assert sh.try_steal(e) is False
    # never-joined entry steals fine once its claim drops
    role3, e3 = sh.claim(("k2",))
    sh.publish(e3, _B())
    sh.release(e3)
    assert sh.try_steal(e3) is True
    # stolen == gone: the key re-opens for a fresh lead
    role4, _e4 = sh.claim(("k2",))
    assert role4 == "lead"
