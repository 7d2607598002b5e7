"""Join suite (reference analog: integration_tests join tests; execs:
GpuShuffledHashJoinExec/GpuBroadcastHashJoinExec — currently CPU fallback
until the TPU join exec lands)."""

import pytest

from spark_rapids_tpu import col
from tests.parity import assert_tpu_and_cpu_are_equal_collect
from tests.data_gen import gen_df, int_key_gen, long_gen, string_key_gen


def _two_dfs(s, seed=0):
    left = gen_df(s, [int_key_gen, long_gen], ["k", "lv"], n=60, seed=seed)
    right = gen_df(s, [int_key_gen, long_gen], ["k2", "rv"], n=40,
                   seed=seed + 10)
    return left, right.with_column("k2", col("k2"))


@pytest.mark.parametrize("how", ["inner", "left", "right", "full",
                                 "semi", "anti"])
def test_join_parity(how):
    def q(s):
        l = gen_df(s, [int_key_gen, long_gen], ["k", "lv"], n=60, seed=1)
        r = (gen_df(s, [int_key_gen, long_gen], ["j", "rv"], n=40, seed=2)
             .select(col("j").alias("k"), "rv"))
        # rename right key to match for the name-based join API
        out = l.join(r, on="k", how=how)
        return out
    assert_tpu_and_cpu_are_equal_collect(q, ignore_order=True)


def test_cross_join():
    def q(s):
        l = s.create_dataframe({"a": [1, 2, 3]})
        r = s.create_dataframe({"b": [10, 20]})
        return l.join(r, how="cross")
    assert_tpu_and_cpu_are_equal_collect(q, ignore_order=True)


def test_inner_join_result(session):
    l = session.create_dataframe({"k": [1, 2, 3], "v": [10, 20, 30]})
    r = session.create_dataframe({"k": [2, 3, 4], "w": [200, 300, 400]})
    out = l.join(r, on="k").sort("k").collect()
    assert out.column_names == ["k", "v", "k", "w"]
    assert out.column(1).to_pylist() == [20, 30]
    assert out.column(3).to_pylist() == [200, 300]


def test_join_runs_on_tpu(session):
    from tests.parity import collect_plans
    captured = collect_plans(session)
    l = session.create_dataframe({"k": [1, 2], "v": [10, 20]})
    r = session.create_dataframe({"k": [2, 3], "w": [1, 2]})
    l.join(r, on="k").collect()
    names = []
    captured[-1].plan.foreach(lambda n: names.append(type(n).__name__))
    # tiny right side -> broadcast hash join strategy
    assert "TpuBroadcastHashJoinExec" in names, names
    l.join(r, how="cross").collect()
    names = []
    captured[-1].plan.foreach(lambda n: names.append(type(n).__name__))
    assert "TpuBroadcastNestedLoopJoinExec" in names, names


def test_join_with_condition():
    def q(s):
        l = s.create_dataframe({"k": [1, 1, 2], "v": [5, 30, 20]})
        r = s.create_dataframe({"k": [1, 2], "w": [10, 15]})
        return l.join(r, on="k").filter(col("v") > col("w"))
    assert_tpu_and_cpu_are_equal_collect(q, ignore_order=True)


def test_join_float_keys_nan():
    def q(s):
        nan = float("nan")
        l = s.create_dataframe({"k": [1.0, nan, -0.0, 2.0],
                                "v": [1, 2, 3, 4]})
        r = s.create_dataframe({"k": [nan, 0.0, 2.0], "w": [10, 20, 30]})
        return l.join(r, on="k")
    # Spark joins NaN==NaN and -0.0==0.0 after normalization
    assert_tpu_and_cpu_are_equal_collect(q, ignore_order=True)


def test_join_mixed_numeric_key_dtypes():
    """Spark promotes int/double key pairs to double before comparing;
    1.5 must not truncate-match 1."""
    def q(s):
        l = s.create_dataframe({"k": [1.5, 2.0], "v": [1, 2]})
        r = s.create_dataframe({"k": [1, 2], "w": [10, 20]})
        return l.join(r, on="k")
    assert_tpu_and_cpu_are_equal_collect(q, ignore_order=True)


def test_join_incompatible_key_dtypes_error(session):
    import pytest as _pt
    l = session.create_dataframe({"k": ["a"], "v": [1]})
    r = session.create_dataframe({"k": [1], "w": [2]})
    with _pt.raises(TypeError):
        l.join(r, on="k")


def test_join_null_keys_dont_match(session):
    l = session.create_dataframe({"k": [1, None], "v": [10, 20]})
    r = session.create_dataframe({"k": [1, None], "w": [100, 200]})
    out = l.join(r, on="k").collect()
    assert out.num_rows == 1  # SQL: null keys never equal


def test_string_key_join():
    def q(s):
        l = gen_df(s, [string_key_gen, long_gen], ["k", "lv"], n=50, seed=3)
        r = (gen_df(s, [string_key_gen, long_gen], ["j", "rv"], n=50, seed=4)
             .select(col("j").alias("k"), "rv"))
        return l.join(r, on="k", how="inner")
    assert_tpu_and_cpu_are_equal_collect(q, ignore_order=True)


# -- path choice: the keys' range, not their magnitude --------------------

def _range_join(session, first_key: int, span: int, dim_rows: int,
                how: str, seed: int):
    """A dimension of ``dim_rows`` distinct keys inside ``[first_key,
    first_key + span)`` joined to 4,000 fact rows (half of their keys
    the dimension's, the others anywhere in its range or a tenth of
    the span below it, a tenth of all null), against pandas."""
    import numpy as np
    import pandas as pd
    import pyarrow as pa
    rng = np.random.default_rng(seed)
    dk = np.sort(rng.choice(span, dim_rows, replace=False)) + first_key
    dk[[0, -1]] = first_key, first_key + span - 1
    dim = pa.table({"dk": dk.astype(np.int32),
                    "dv": np.arange(dim_rows, dtype=np.int32)})
    fk = np.where(rng.random(4000) < 0.5, rng.choice(dk, 4000),
                  rng.integers(first_key - span // 10, first_key + span,
                               4000))
    fact = pa.table({
        "fk": pa.array(fk.astype(np.int32), mask=rng.random(4000) < 0.1),
        "fv": np.arange(4000, dtype=np.int32)})
    got = (session.create_dataframe(fact)
           .join(session.create_dataframe(dim), on=col("fk") == col("dk"),
                 how=how).collect().to_pandas())
    f, d = fact.to_pandas(), dim.to_pandas()
    if how == "semi":
        want = f[f.fk.isin(d.dk)]
    elif how == "anti":
        want = f[~f.fk.isin(d.dk)]
    else:
        want = f.merge(d, left_on="fk", right_on="dk", how=how)
        if how == "left":       # pandas matches null keys to nothing too
            assert want.dv.isna().sum() > 0
    by = ["fv"]
    pd.testing.assert_frame_equal(
        got.sort_values(by).reset_index(drop=True),
        want.sort_values(by).reset_index(drop=True), check_dtype=False)
    return len(want)


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
@pytest.mark.parametrize("case,first_key,span,dim_rows,path", [
    ("julian", 2_415_022, 73_049, 73_049, "direct"),
    ("from-1", 1, 73_049, 73_049, "direct"),
    ("sparse", 2_415_022, 73_049, 600, "direct"),
    ("past-4M", 1, 5_000_000, 600, "sortMerge"),
])
def test_join_path_is_chosen_by_key_range(session, how, case, first_key,
                                          span, dim_rows, path):
    from spark_rapids_tpu.obs import registry
    if case == "from-1":
        # the same span from another base, first: what the second
        # base could mint, were the base part of the program
        _range_join(session, 2_415_022, span, dim_rows, how, seed=7)
    view = registry.get_registry().view()
    assert _range_join(session, first_key, span, dim_rows, how, seed=7)
    moved = view.delta()["counters"]
    other = "sortMerge" if path == "direct" else "direct"
    assert moved.get(f"join.path.{path}", 0) == 1
    assert moved.get(f"join.path.{other}", 0) == 0
    assert moved.get("device.reads.join.rangeWait", 0) == 1
    if case == "from-1":
        assert moved.get("kernel.cache.compiles", 0) == 0
        assert moved.get("kernel.cache.persistentHits", 0) == 0
    if path == "direct":
        assert registry.get_registry().gauge("join.table.entries") \
            >= span


def test_join_path_composite_keys_by_range(session):
    """Two keys whose ranges multiply to fewer entries than the table
    holds take the direct path; their value hints alone (two 32-bit
    buckets) never would."""
    import numpy as np
    import pandas as pd
    import pyarrow as pa
    from spark_rapids_tpu.obs import registry
    rng = np.random.default_rng(11)
    # years far from zero, so that no 16-bit hint bounds the pair
    dim = pa.table({"y": (np.repeat(np.arange(1998, 2003), 12) + 100_000)
                    .astype(np.int32),
                    "m": np.tile(np.arange(1, 13), 5).astype(np.int32),
                    "dv": np.arange(60, dtype=np.int32)})
    fact = pa.table({
        "fy": pa.array((rng.integers(1996, 2005, 3000) + 100_000)
                       .astype(np.int32), mask=rng.random(3000) < 0.05),
        "fm": rng.integers(0, 14, 3000).astype(np.int32),
        "fv": np.arange(3000, dtype=np.int32)})
    view = registry.get_registry().view()
    got = (session.create_dataframe(fact)
           .join(session.create_dataframe(dim),
                 on=(col("fy") == col("y")) & (col("fm") == col("m")))
           .collect().to_pandas())
    moved = view.delta()["counters"]
    assert moved.get("join.path.direct", 0) == 1
    assert moved.get("join.path.sortMerge", 0) == 0
    want = fact.to_pandas().dropna(subset=["fy"]).merge(
        dim.to_pandas(), left_on=["fy", "fm"], right_on=["y", "m"])
    assert len(want) > 0
    pd.testing.assert_frame_equal(
        got.sort_values("fv").reset_index(drop=True),
        want.sort_values("fv").reset_index(drop=True), check_dtype=False)
