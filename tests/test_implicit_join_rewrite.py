"""The WHERE of an implicit join becomes the joins' keys
(``plan/optimizer.rewrite_implicit_joins``): plan shapes, and answers
against the CPU engine."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

from spark_rapids_tpu.plan import logical as lp
from spark_rapids_tpu.plan.optimizer import rewrite_implicit_joins
from tests.parity import (assert_tables_equal, collect_plans,
                          with_cpu_session, with_tpu_session)


def _views(s, tmp_path=None):
    """Three small tables; as parquet files where ``tmp_path`` is
    given, so that a pushed conjunct can reach a file scan."""
    rng = np.random.default_rng(3)
    tables = {
        "a": pa.table({"ak": np.arange(40, dtype=np.int32),
                       "ax": rng.integers(0, 5, 40).astype(np.int32),
                       "av": rng.uniform(0, 1, 40)}),
        "b": pa.table({"bk": rng.integers(0, 40, 300).astype(np.int32),
                       "bc": rng.integers(0, 25, 300).astype(np.int32),
                       "bv": rng.uniform(0, 1, 300)}),
        "c": pa.table({"ck": np.arange(25, dtype=np.int32),
                       "cy": rng.integers(0, 5, 25).astype(np.int32)})}
    for name, t in tables.items():
        if tmp_path is None:
            s.register_view(name, s.create_dataframe(t))
        else:
            (tmp_path / name).mkdir()
            papq.write_table(t, tmp_path / name / "part-0.parquet")
            s.register_view(name, s.read.parquet(str(tmp_path / name)))
    return s


def _nodes(plan, kind=None):
    out = []

    def walk(n):
        if kind is None or isinstance(n, kind):
            out.append(n)
        for ch in n.children:
            walk(ch)
    walk(plan)
    return out


def _physical_names(session, sql):
    captured = collect_plans(session)
    session.sql(sql).collect()
    names = []
    captured[-1].plan.foreach(lambda n: names.append(type(n).__name__))
    return names, captured[-1].plan


COMMA = ("select ax, sum(bv) as s from a, b, c "
         "where a.ak = b.bk and b.bc = c.ck and a.ax = 2 and c.cy < 3 "
         "group by ax")


def test_comma_join_plans_as_equi_joins(session):
    _views(session)
    plan = rewrite_implicit_joins(session.sql(COMMA).plan)
    joins = _nodes(plan, lp.Join)
    assert [j.how for j in joins] == ["inner", "inner"]
    assert [(j.left_keys, j.right_keys) for j in joins] == \
        [(["bc"], ["ck"]), (["ak"], ["bk"])]
    # nothing of the WHERE is left above the joins
    assert all(isinstance(f.children[0], lp.InMemoryScan)
               for f in _nodes(plan, lp.Filter))
    names, _ = _physical_names(session, COMMA)
    assert not [n for n in names if "NestedLoop" in n or "Cartesian" in n]
    assert sum("HashJoin" in n for n in names) == 2


def test_one_sided_conjuncts_reach_the_scans(session, tmp_path):
    from spark_rapids_tpu import TpuSparkSession
    s = _views(TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True}), tmp_path)
    _, physical = _physical_names(s, COMMA)
    filtered = {}

    def over_scan(n):
        for ch in n.children:
            if type(ch).__name__ == "TpuParquetScanExec":
                filtered[tuple(ch.schema.names)] = \
                    getattr(n, "condition", None) is not None
    physical.foreach(over_scan)
    # a and c each have their conjunct directly over their scan
    assert filtered[("ak", "ax")]
    assert filtered[("ck", "cy")]
    assert not filtered[("bk", "bc", "bv")]


def test_comma_join_without_an_equality_stays_cross(session):
    _views(session)
    sql = "select ak, ck from a, c where a.ax < c.cy and a.ak < 3"
    plan = rewrite_implicit_joins(session.sql(sql).plan)
    (join,) = _nodes(plan, lp.Join)
    assert join.how == "cross" and not join.left_keys
    # the one-sided conjunct went under it, the other stayed above
    assert isinstance(join.children[0], lp.Filter)
    assert isinstance(_nodes(plan, lp.Filter)[0].children[0], lp.Join)
    names, _ = _physical_names(session, sql)
    assert any("NestedLoop" in n or "Cartesian" in n for n in names)


def test_equality_across_the_first_and_third_table(session):
    _views(session)
    sql = ("select ak, bc, cy from a, b, c "
           "where a.ax = c.cy and a.ak = b.bk and c.ck < 4")
    plan = rewrite_implicit_joins(session.sql(sql).plan)
    top, low = _nodes(plan, lp.Join)
    assert (top.how, top.left_keys, top.right_keys) == \
        ("inner", ["ax"], ["cy"])
    assert (low.how, low.left_keys, low.right_keys) == \
        ("inner", ["ak"], ["bk"])


@pytest.mark.parametrize("how,pushed_side", [("left", 0), ("right", 1),
                                             ("full", None)])
def test_outer_joins_keep_their_on(session, how, pushed_side):
    _views(session)
    sql = (f"select ak, ax, ck, cy from a {how} join c on a.ak = c.ck "
           f"where (a.ax = 2 or a.ax is null) "
           f"and (c.cy < 3 or c.cy is null)")
    plan = rewrite_implicit_joins(session.sql(sql).plan)
    (join,) = _nodes(plan, lp.Join)
    assert join.how == how and join.left_keys == ["ak"]
    # only the preserved side takes its conjunct
    for side in (0, 1):
        assert isinstance(join.children[side], lp.Filter) == \
            (side == pushed_side)


@pytest.mark.parametrize("sql", [
    COMMA,
    "select ak, ck from a, c where a.ax < c.cy and a.ak < 3",
    "select ak, bc, cy from a, b, c "
    "where a.ax = c.cy and a.ak = b.bk and c.ck < 4",
    "select ak, ax, ck, cy from a left join c on a.ak = c.ck "
    "where (a.ax = 2 or a.ax is null) and (c.cy < 3 or c.cy is null)",
    "select ak, ax, ck, cy from a right join c on a.ak = c.ck "
    "where (a.ax = 2 or a.ax is null) and (c.cy < 3 or c.cy is null)",
    "select ak, ax, ck, cy from a full join c on a.ak = c.ck "
    "where (a.ax = 2 or a.ax is null) and (c.cy < 3 or c.cy is null)",
    "select ak from a left semi join c on a.ak = c.ck where a.ax = 2",
    "select ak, bv from a join b on a.ak = b.bk, c "
    "where b.bc = c.ck and c.cy = 1 and a.av < b.bv",
], ids=["comma3", "no-equality", "first-third", "left", "right", "full",
        "semi", "mixed"])
def test_rewritten_answers_equal_the_plan_as_written(sql, monkeypatch):
    """The oracle is the CPU engine on the plan the parser gave it:
    products and the whole WHERE above them."""
    from spark_rapids_tpu.plan import optimizer
    with monkeypatch.context() as m:
        m.setattr(optimizer, "rewrite_implicit_joins", lambda plan: plan)
        want = with_cpu_session(lambda s: _views(s).sql(sql).collect())
    got = with_tpu_session(lambda s: _views(s).sql(sql).collect())
    assert want.num_rows > 0
    assert_tables_equal(want, got, ignore_order=True)


def test_rewrite_counters(session):
    from spark_rapids_tpu.obs import registry
    _views(session)
    view = registry.get_registry().view()
    session.sql(COMMA).collect()
    moved = view.delta()["counters"]
    assert moved["plan.rewrite.implicitJoins"] == 2
    # counted a join passed: a.ax = 2 goes under two
    assert moved["plan.rewrite.pushedConjuncts"] == 4
