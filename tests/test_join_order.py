"""A FROM list whose order is no join order (``FROM a, b, c`` where
``a`` and ``b`` share no equality and both meet ``c``) is planned in a
connected order (plan/optimizer._order_products): same answer as the
text that lists the relations in a join order, no product operator in
the executed plan.  A text whose order already has a key at every join
keeps it.  Derived tables that share a column name join through
qualified names (sql/parser.rename_apart)."""

import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

from spark_rapids_tpu import TpuSparkSession
from spark_rapids_tpu.obs import registry
from spark_rapids_tpu.plan import logical as lp, optimizer, stats
from spark_rapids_tpu.sql.parser import SqlParseError
from tests.parity import collect_plans

_CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True}


def _tables(seed=3):
    rng = np.random.default_rng(seed)
    n = 4000
    return {
        "a": pa.table({"ak": pa.array(np.arange(40), pa.int32()),
                       "aname": [f"a{i:02d}" for i in range(40)]}),
        "b": pa.table({"bk": pa.array(np.arange(25), pa.int32()),
                       "bw": rng.normal(size=25)}),
        "c": pa.table({
            "ca": pa.array([None if i % 11 == 0 else int(v) for i, v in
                            enumerate(rng.integers(0, 50, n))], pa.int32()),
            "cb": pa.array(rng.integers(0, 30, n), pa.int32()),
            "cv": rng.normal(size=n)}),
        "d": pa.table({"dk": pa.array(np.arange(30), pa.int32()),
                       "dx": pa.array(np.arange(30) * 2, pa.int32())}),
    }


@pytest.fixture
def session():
    s = TpuSparkSession(_CONF)
    for name, t in _tables().items():
        s.register_view(name, s.create_dataframe(t))
    return s


def _run(s, text):
    captured = collect_plans(s)
    view = registry.get_registry().view()
    got = s.sql(text).collect()
    names = []
    captured[-1].plan.foreach(lambda n: names.append(type(n).__name__))
    return got, names, view.delta()["counters"]


def _products(names):
    return [n for n in names if "NestedLoop" in n or "Cartesian" in n]


_SELECT = "select aname, bw, dx, cv from {frm} where {where} " \
          "order by aname, bw, dx, cv"
_WHERE = "ak = ca and bk = cb and dk = cb"
# every permutation that puts the hub last, first or in the middle
_ORDERS = ["a, b, d, c", "b, a, c, d", "d, b, a, c", "a, b, c, d",
           "c, a, b, d", "a, c, b, d"]
_NO_JOIN_ORDER = {"a, b, d, c", "b, a, c, d", "d, b, a, c", "a, b, c, d"}


def _want():
    t = {k: v.to_pandas() for k, v in _tables().items()}
    j = t["c"].dropna(subset=["ca"]).merge(t["a"], left_on="ca",
                                           right_on="ak")
    j = j.merge(t["b"], left_on="cb", right_on="bk")
    j = j.merge(t["d"], left_on="cb", right_on="dk")
    return j.sort_values(["aname", "bw", "dx", "cv"])[
        ["aname", "bw", "dx", "cv"]].reset_index(drop=True)


@pytest.mark.parametrize("frm", _ORDERS)
def test_any_from_order_gives_the_join_orders_answer(session, frm):
    got, names, moved = _run(session, _SELECT.format(frm=frm, where=_WHERE))
    pd.testing.assert_frame_equal(got.to_pandas(), _want(),
                                  check_dtype=False)
    assert not _products(names), names
    assert sum("HashJoin" in n for n in names) == 3
    assert moved.get("join.path.product", 0) == 0
    reordered = moved.get("plan.rewrite.reorderedJoins", 0)
    assert (reordered > 0) == (frm in _NO_JOIN_ORDER), (frm, reordered)


def _optimized(s, text):
    return optimizer.rewrite_implicit_joins(s.sql(text).plan)


def _join_inputs(plan):
    """The leaves of the plan's join tree, left to right."""
    out = []

    def walk(n):
        if isinstance(n, lp.Join):
            for ch in n.children:
                walk(ch)
        elif isinstance(n, (lp.Filter, lp.Project)):
            walk(n.children[0])
        else:
            out.append(n.schema.names[0])
    node = plan
    while not isinstance(node, lp.Join):
        node = node.children[0]
    walk(node)
    return out


def test_a_text_order_with_a_key_at_every_join_is_kept(session):
    view = registry.get_registry().view()
    plan = _optimized(session, _SELECT.format(frm="a, c, b, d",
                                              where=_WHERE))
    assert _join_inputs(plan) == ["ak", "ca", "bk", "dk"]
    assert view.delta()["counters"].get(
        "plan.rewrite.reorderedJoins", 0) == 0


def test_the_hub_streams_and_a_filtering_join_goes_first(session):
    """``c`` has the most equality partners, so it comes first; ``d``
    completes ``cv <= dx`` and so joins before ``a`` and ``b``, with the
    conjunct as the filter right over that join."""
    plan = _optimized(session, _SELECT.format(
        frm="a, b, d, c", where=_WHERE + " and cv <= 0.1 * dx"))
    assert _join_inputs(plan) == ["ca", "dk", "ak", "bk"]
    found = []

    def walk(n):
        if isinstance(n, lp.Filter) and isinstance(n.children[0], lp.Join):
            found.append(_join_inputs(n))
        for ch in n.children:
            walk(ch)
    walk(plan)
    assert found == [["ca", "dk"]]


def test_the_residual_is_evaluated_as_written(session):
    got, names, _ = _run(session, _SELECT.format(
        frm="a, b, d, c", where=_WHERE + " and cv <= 0.1 * dx"))
    want = _want()
    want = want[want.cv <= 0.1 * want.dx].reset_index(drop=True)
    pd.testing.assert_frame_equal(got.to_pandas(), want, check_dtype=False)
    assert not _products(names)


def test_a_relation_no_equality_reaches_stays_a_product(session):
    got, names, moved = _run(
        session, "select count(*) n, sum(cv) s from a, b, c "
                 "where ak = ca and bw > 100")
    assert got.column("n").to_pylist() == [0]
    got, names, moved = _run(
        session, "select count(*) n from a, d, c where ak = ca and dk < 2")
    c = _tables()["c"].to_pandas()
    assert got.column("n").to_pylist() == [
        2 * int(c.ca.dropna().isin(range(40)).sum())]
    assert _products(names) and moved.get("join.path.product", 0) >= 1


@pytest.mark.parametrize("n,edges,residuals,want", [
    (4, {(0, 3), (1, 3), (2, 3)}, [], [3, 0, 1, 2]),
    (4, {(0, 3), (1, 3), (2, 3)}, [{2, 3}], [3, 2, 0, 1]),
    (3, {(0, 1)}, [], [0, 1, 2]),
    (4, {(0, 2), (1, 3), (2, 3)}, [], [2, 0, 3, 1]),
    (5, {(0, 4), (1, 4), (2, 3)}, [{1, 4}], [4, 1, 0, 2, 3]),
], ids=["star", "star-filtering-first", "unreached-last", "chain",
        "two-components"])
def test_connected_order(n, edges, residuals, want):
    assert optimizer._connected_order(n, edges, residuals) == want


# -- qualified names through derived tables that share a column name ---

_PAIR = ("(select cb, sum(cv) as sv from c group by cb) x, "
         "(select cb, max(cv) as mv, count(*) as n from c group by cb) y")


def test_derived_tables_that_share_a_column_join_by_qualified_names(
        session):
    got = session.sql(
        f"select x.cb, y.cb as ycb, x.sv, y.mv, n from {_PAIR} "
        "where x.cb = y.cb order by x.cb").collect()
    c = _tables()["c"].to_pandas()
    want = c.groupby("cb").agg(sv=("cv", "sum"), mv=("cv", "max"),
                               n=("cv", "size")).reset_index()
    assert got.column_names == ["cb", "ycb", "sv", "mv", "n"]
    assert got.column("cb").to_pylist() == want.cb.tolist()
    assert got.column("ycb").to_pylist() == want.cb.tolist()
    assert got.column("n").to_pylist() == want.n.tolist()
    np.testing.assert_allclose(got.column("sv").to_numpy(), want.sv,
                               rtol=1e-12)
    np.testing.assert_allclose(got.column("mv").to_numpy(), want.mv)


@pytest.mark.parametrize("text,message", [
    (f"select cb from {_PAIR} where x.cb = y.cb", "ambiguous"),
    (f"select x.sv from {_PAIR} where cb = 1", "ambiguous"),
    (f"select z.cb from {_PAIR} where x.cb = y.cb", "unknown table alias"),
    ("select 1 from c, c where cv > 0", "ambiguous|duplicate"),
], ids=["select", "where", "alias", "self-join-without-aliases"])
def test_a_bare_shared_name_is_an_error(session, text, message):
    with pytest.raises(SqlParseError, match=message):
        session.sql(text)


@pytest.mark.parametrize("form", ["comma", "join-on", "star"])
def test_self_join_through_aliases(session, form):
    text = {
        "comma": "select l.dk, r.dx from d l, d r where l.dx = r.dk "
                 "order by l.dk",
        "join-on": "select l.dk, r.dx from d l join d r on l.dx = r.dk "
                   "order by l.dk",
        "star": "select l.dk, r.* from d l, d r where l.dx = r.dk "
                "order by l.dk"}[form]
    got = session.sql(text).collect()
    d = _tables()["d"].to_pandas()
    want = d.merge(d, left_on="dx", right_on="dk", suffixes=("", "_r")) \
        .sort_values("dk")
    assert got.column_names == (["dk", "dk", "dx"] if form == "star"
                                else ["dk", "dx"])
    assert got.column(0).to_pylist() == want.dk.tolist()
    assert got.column(got.num_columns - 1).to_pylist() == \
        want.dx_r.tolist()


# -- the sizes the broadcast decision sees -----------------------------

def _nodes(plan, cls) -> list:
    out = [plan] if isinstance(plan, cls) else []
    for ch in plan.children:
        out += _nodes(ch, cls)
    return out


@pytest.fixture
def fact_files(tmp_path):
    rng = np.random.default_rng(9)
    n = 60_000
    fact = pa.table({
        "store": pa.array([None if i % 25 == 0 else int(v) for i, v in
                           enumerate(rng.integers(1, 52, n))], pa.int32()),
        "item": pa.array(rng.integers(1, 3001, n), pa.int32()),
        "price": rng.uniform(0, 100, n),
        "pad": pa.array([f"filler text number {i}" for i in range(n)])})
    for k in range(2):
        (tmp_path / "fact").mkdir(exist_ok=True)
        papq.write_table(fact.slice(k * n // 2, n // 2),
                         str(tmp_path / "fact" / f"part-{k}.parquet"))
    return str(tmp_path / "fact"), fact


def test_an_aggregates_size_is_bounded_by_its_keys_ranges(fact_files):
    root, fact = fact_files
    s = TpuSparkSession(_CONF)
    s.register_view("fact", s.read.parquet(root))
    plan = optimizer.prune_columns(s.sql(
        "select store, avg(revenue) ave from (select store, item, "
        "sum(price) revenue from fact group by store, item) sa "
        "group by store").plan)
    outer, inner = _nodes(plan, lp.Aggregate)
    scans = _nodes(plan, lp.FileScan)
    # the scan reads three of four columns: their chunks, not the files
    assert lp.size_estimate(scans[0]) == stats.scan_bytes(scans[0])
    assert lp.size_estimate(scans[0]) < sum(
        os.path.getsize(p) for p in scans[0].paths)
    assert stats.column_range(inner, 0) == (1, 51)
    assert stats.column_range(inner, 1) == (1, 3000)
    assert stats.column_range(inner, 2) is None
    # 51 stores and the null, 12 bytes a row
    assert lp.size_estimate(outer) == 52 * 12
    assert lp.size_estimate(inner) == min(
        lp.size_estimate(scans[0]) // 2, 52 * 3001 * 16)


def test_a_key_that_is_computed_has_no_range(fact_files):
    root, _ = fact_files
    s = TpuSparkSession(_CONF)
    s.register_view("fact", s.read.parquet(root))
    plan = optimizer.prune_columns(s.sql(
        "select store + 1 as k, sum(price) p from fact group by store + 1"
    ).plan)
    aggs = _nodes(plan, lp.Aggregate)
    assert stats.aggregate_bytes(aggs[0]) is None
    assert lp.size_estimate(aggs[0]) == \
        lp.size_estimate(aggs[0].children[0]) // 2


# -- two scans of one table in one query ---------------------------------

@pytest.mark.parametrize("files", [1, 4], ids=["one-batch", "four-batches"])
def test_two_scans_of_one_table_in_one_query_finish(tmp_path, files):
    """q65 reads ``store_sales`` under ``sb`` (a join's build side) and
    under ``sc`` (its stream side).  The stream side's look-ahead
    claims the first batches' shared-scan flights before the build
    side reads them, and decodes them only after the build is done: the
    build side decodes in the leader's place instead of waiting for it
    (io/scan_share.ScanShare.begin)."""
    import threading
    rng = np.random.default_rng(4)
    n = 3000
    (tmp_path / "t").mkdir()
    frames = []
    for k in range(files):
        t = pa.table({"k": pa.array(rng.integers(0, 40, n), pa.int32()),
                      "v": rng.normal(size=n)})
        frames.append(t.to_pandas())
        papq.write_table(t, str(tmp_path / "t" / f"part-{k}.parquet"))
    s = TpuSparkSession({**_CONF,
                         "spark.rapids.tpu.sql.reader.batchSizeRows": n})
    s.register_view("t", s.read.parquet(str(tmp_path / "t")))
    out = {}

    def run():
        out["got"] = s.sql(
            "select x.k, sv, mv from "
            "(select k, sum(v) as sv from t group by k) x, "
            "(select k, max(v) as mv from t group by k) y "
            "where x.k = y.k order by x.k").collect()
    th = threading.Thread(target=run, daemon=True)
    th.start()
    th.join(120)
    assert not th.is_alive(), "the query did not finish: scans deadlocked"
    df = pd.concat(frames)
    want = df.groupby("k").agg(sv=("v", "sum"), mv=("v", "max")) \
        .reset_index()
    got = out["got"].to_pandas()
    np.testing.assert_array_equal(got.k, want.k)
    np.testing.assert_allclose(got.sv, want.sv, rtol=1e-12)
    np.testing.assert_allclose(got.mv, want.mv)
