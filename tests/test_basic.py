"""End-to-end smoke tests for the core slice: scan -> project/filter ->
aggregate/sort/limit (SURVEY.md §7 phases 2-3 milestone tests)."""

import pyarrow as pa
import pytest
from spark_rapids_tpu import TpuSparkSession, col, lit, functions as F
from tests.parity import (assert_tpu_and_cpu_are_equal_collect,
                          assert_tables_equal)
from tests.data_gen import (gen_df, int_gen, long_gen, double_gen,
                            int_key_gen, boolean_gen)


def test_select_arithmetic(session):
    df = session.create_dataframe({"a": [1, 2, 3], "b": [10, 20, 30]})
    out = df.select((col("a") + col("b")).alias("s"),
                    (col("a") * lit(2)).alias("d")).collect()
    assert out.column("s").to_pylist() == [11, 22, 33]
    assert out.column("d").to_pylist() == [2, 4, 6]


def test_select_runs_on_tpu(session):
    from tests.parity import collect_plans
    captured = collect_plans(session)
    df = session.create_dataframe({"a": [1, 2, 3]})
    df.select((col("a") + 1).alias("b")).collect()
    names = []
    captured[-1].plan.foreach(lambda n: names.append(type(n).__name__))
    assert "TpuProjectExec" in names, names


def test_filter(session):
    df = session.create_dataframe({"a": [1, 2, 3, 4, 5]})
    out = df.filter(col("a") > 2).collect()
    assert out.column("a").to_pylist() == [3, 4, 5]


def test_filter_with_nulls(session):
    df = session.create_dataframe({"a": [1, None, 3, None, 5]})
    out = df.filter(col("a") > 2).collect()
    assert out.column("a").to_pylist() == [3, 5]


def test_parity_project_filter():
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: gen_df(s, [int_gen, long_gen, double_gen],
                         ["a", "b", "c"], n=200)
        .filter(col("a").is_not_null() & (col("a") % 3 == 0))
        .select("a", (col("b") + col("a")).alias("ab"),
                (col("c") / 2).alias("c2")))


def test_groupby_sum_count():
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: gen_df(s, [int_key_gen, long_gen], ["k", "v"], n=300)
        .group_by("k").agg(F.sum("v").alias("s"),
                           F.count("v").alias("c"),
                           F.count("*").alias("n")),
        ignore_order=True)


def test_groupby_min_max_avg():
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: gen_df(s, [int_key_gen, int_gen, double_gen],
                         ["k", "v", "w"], n=300)
        .group_by("k").agg(F.min("v").alias("mn"),
                           F.max("v").alias("mx"),
                           F.avg("w").alias("a")),
        ignore_order=True)


def test_groupby_string_min_max():
    from tests.data_gen import StringGen
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: gen_df(s, [int_key_gen, StringGen(max_len=10)],
                         ["k", "s"], n=300)
        .group_by("k").agg(F.min("s").alias("mn"),
                           F.max("s").alias("mx"),
                           F.first("s").alias("f"),
                           F.last("s").alias("l")),
        ignore_order=True)


def test_global_string_min_max():
    from tests.data_gen import StringGen
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: gen_df(s, [StringGen(max_len=12)], ["s"], n=150)
        .agg(F.min("s").alias("mn"), F.max("s").alias("mx")))


def test_global_string_min_max_empty():
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe({"s": pa.array([], type=pa.string())})
        .agg(F.min("s").alias("mn"), F.max("s").alias("mx")))


def test_global_agg():
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: gen_df(s, [long_gen], ["v"], n=100)
        .agg(F.sum("v").alias("s"), F.count("*").alias("n"),
             F.min("v").alias("mn"), F.max("v").alias("mx")))


def test_global_agg_empty():
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe({"v": pa.array([], type=pa.int64())})
        .agg(F.sum("v").alias("s"), F.count("*").alias("n")))


def test_sort():
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: gen_df(s, [int_gen, long_gen], ["a", "b"], n=150)
        .sort(col("a").asc(), col("b").desc()))


def test_sort_with_nulls():
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: gen_df(s, [int_gen], ["a"], n=80)
        .sort(col("a").asc()))


def test_limit(session):
    df = session.range(100)
    assert df.limit(7).collect().num_rows == 7


def test_range_parity():
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.range(0, 1000, 3).select(
            (col("id") * 2).alias("x")))


def test_union():
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: gen_df(s, [int_gen], ["a"], n=40, seed=1).union(
            gen_df(s, [int_gen], ["a"], n=40, seed=2)),
        ignore_order=True)


def test_count_action(session):
    df = session.create_dataframe({"a": [1, 2, None, 4]})
    assert df.count() == 4
    assert df.filter(col("a").is_not_null()).count() == 3


def test_distinct():
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: gen_df(s, [int_key_gen], ["k"], n=100).distinct(),
        ignore_order=True)


def test_with_column(session):
    df = session.create_dataframe({"a": [1, 2]})
    out = df.with_column("b", col("a") + 10).collect()
    assert out.column("b").to_pylist() == [11, 12]


def test_conditional_parity():
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: gen_df(s, [int_gen, boolean_gen], ["a", "p"], n=120)
        .select(F.when(col("p"), col("a"))
                .when(col("a") > 0, col("a") * 2)
                .otherwise(lit(-1)).alias("w")))


def test_explain_fallback(session):
    # StringReplace has no TPU implementation yet -> fallback with reason
    df = session.create_dataframe({"s": ["ab", "cd"]})
    q = df.select(F.replace(col("s"), "a", "x").alias("r"))
    text = q.explain_string("tpu")
    assert "cannot run on TPU" in text


def test_empty_input():
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: s.create_dataframe(
            {"a": pa.array([], type=pa.int32())})
        .filter(col("a") > 0).select((col("a") + 1).alias("b")))


def test_filter_fuses_into_aggregate():
    """A Filter directly under a hash aggregate fuses into the update
    kernel as a mask (overrides post-pass) — and still matches CPU."""
    import numpy as np
    from tests.parity import (assert_tables_equal, collect_plans,
                              with_cpu_session)
    from spark_rapids_tpu import TpuSparkSession, col, functions as F
    rng = np.random.default_rng(21)
    t = pa.table({
        "k": pa.array(rng.integers(0, 9, 400), type=pa.int32()),
        "v": pa.array(rng.integers(-50, 50, 400), type=pa.int64()),
    })

    def q(s):
        df = s.create_dataframe(t, num_partitions=2)
        return df.filter(col("v") > 0).group_by("k").agg(
            F.count("*").alias("c"), F.sum("v").alias("sv"))

    cpu = with_cpu_session(lambda s: q(s).collect())
    s = TpuSparkSession(
        {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
    captured = collect_plans(s)
    got = q(s).collect()
    assert_tables_equal(cpu, got, ignore_order=True)
    from spark_rapids_tpu.exec.tpu_aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.tpu_basic import TpuFilterExec
    aggs, filters = [], []
    captured[-1].plan.foreach(
        lambda x: aggs.append(x) if isinstance(x, TpuHashAggregateExec)
        else filters.append(x) if isinstance(x, TpuFilterExec) else None)
    assert aggs and any(a.fused_condition is not None for a in aggs)
    assert not filters, "filter should have fused away"
    assert "fusedFilter" in captured[-1].plan.tree_string()

    # kill switch restores the unfused shape
    s2 = TpuSparkSession({
        "spark.rapids.tpu.sql.agg.fusedFilter.enabled": False,
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
    captured2 = collect_plans(s2)
    got2 = q(s2).collect()
    assert_tables_equal(cpu, got2, ignore_order=True)
    filters2 = []
    captured2[-1].plan.foreach(
        lambda x: filters2.append(x) if isinstance(x, TpuFilterExec)
        else None)
    assert filters2


@pytest.mark.parametrize("thresh,over,rung",
                         [(7, 0, 128), (1, 128, 256), (-10, 256, 512)],
                         ids=["quarter", "half", "full"])
def test_fused_filter_ladder_every_branch(monkeypatch, thresh, over, rung):
    """Cover EVERY lax.switch ladder branch of the fused-filter
    permutation compact at suite scale by lowering the engagement
    threshold (normally only the 4M-row bench reaches it)."""
    import numpy as np
    from spark_rapids_tpu.exec import kernel_cache as kc
    from spark_rapids_tpu.exec import tpu_aggregate as agg
    from tests.parity import assert_tables_equal, with_cpu_session
    from spark_rapids_tpu import TpuSparkSession, col, functions as F

    monkeypatch.setattr(agg, "_LADDER_MIN_RUNG", 8)
    kc.clear()      # no program traced under the default threshold
    rng = np.random.default_rng(33)
    n = 512  # cap 512, rungs 128 and 256
    t = pa.table({
        "k": pa.array(rng.integers(0, 7, n), type=pa.int64()),
        "v": pa.array(rng.integers(-9, 9, n), type=pa.int64()),
    })
    # selective -> cap/4; 7 values of 18 -> cap/2; all -> cap
    assert over < int((t["v"].to_numpy() > thresh).sum()) <= rung

    def q(s):
        df = s.create_dataframe(t)
        return df.filter(col("v") > thresh).group_by("k").agg(
            F.count("*").alias("c"), F.sum("v").alias("sv"),
            F.max("v").alias("mx"))

    cpu = with_cpu_session(lambda s: q(s).collect())
    got = TpuSparkSession(
        {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
    assert_tables_equal(cpu, q(got).collect(), ignore_order=True)
    kc.clear()


def test_rollup_subtotals():
    """rollup: per-prefix grouping sets through the Expand lowering
    (GpuExpandExec analog), vs a pandas ground truth on both engines."""
    import numpy as np
    from spark_rapids_tpu import TpuSparkSession, functions as F
    rng = np.random.default_rng(3)
    t = pa.table({"a": pa.array(rng.integers(0, 3, 200)),
                  "b": pa.array(rng.integers(0, 2, 200)),
                  "v": pa.array(rng.integers(0, 50, 200))})
    pd_ = t.to_pandas()
    for conf in ({"spark.rapids.tpu.sql.variableFloatAgg.enabled": True},
                 {"spark.rapids.tpu.sql.enabled": False}):
        s = TpuSparkSession(conf)
        out = (s.create_dataframe(t).rollup("a", "b")
               .agg(F.sum("v").alias("sv"), F.count("*").alias("n"))
               .collect().to_pandas())
        assert len(out) == len(pd_.groupby(["a", "b"])) + \
            len(pd_.groupby("a")) + 1
        grand = out[out["a"].isna() & out["b"].isna()]
        assert int(grand["sv"].iloc[0]) == int(pd_["v"].sum())
        assert int(grand["n"].iloc[0]) == len(pd_)
        lvl1 = out[out["a"].notna() & out["b"].isna()]
        assert sorted(lvl1["sv"]) == \
            sorted(pd_.groupby("a")["v"].sum().tolist())
        detail = out[out["a"].notna() & out["b"].notna()]
        assert sorted(detail["sv"]) == \
            sorted(pd_.groupby(["a", "b"])["v"].sum().tolist())


def test_cube_all_combinations():
    import numpy as np
    from spark_rapids_tpu import TpuSparkSession, functions as F
    rng = np.random.default_rng(4)
    t = pa.table({"a": pa.array(rng.integers(0, 3, 150)),
                  "b": pa.array(rng.integers(0, 2, 150)),
                  "v": pa.array(rng.integers(0, 9, 150))})
    pd_ = t.to_pandas()
    s = TpuSparkSession(
        {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
    out = (s.create_dataframe(t).cube("a", "b")
           .agg(F.sum("v").alias("sv")).collect().to_pandas())
    # cube adds the b-only subtotal level rollup lacks
    b_only = out[out["a"].isna() & out["b"].notna()]
    assert sorted(b_only["sv"]) == \
        sorted(pd_.groupby("b")["v"].sum().tolist())
    assert len(out) == len(pd_.groupby(["a", "b"])) + \
        len(pd_.groupby("a")) + len(pd_.groupby("b")) + 1
    # the expand lowering really runs on device
    from tests.parity import collect_plans
    s2 = TpuSparkSession(
        {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
    captured = collect_plans(s2)
    (s2.create_dataframe(t).cube("a", "b")
     .agg(F.sum("v").alias("sv")).collect())
    names = []
    captured[-1].plan.foreach(lambda n: names.append(type(n).__name__))
    assert "TpuExpandExec" in names, names


def test_rollup_natural_null_keys_stay_separate():
    """A natural null key value at the detail level must not merge with
    the subtotal row (the grouping id keeps them distinct)."""
    from spark_rapids_tpu import TpuSparkSession, functions as F
    t = pa.table({"a": pa.array([1, 1, None, None], type=pa.int64()),
                  "v": pa.array([10, 20, 5, 7], type=pa.int64())})
    s = TpuSparkSession(
        {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
    out = (s.create_dataframe(t).rollup("a")
           .agg(F.sum("v").alias("sv")).collect().to_pandas())
    # rows: a=1 (30), a=null detail (12), grand total (42)
    assert sorted(out["sv"].tolist()) == [12, 30, 42]
