"""TPC-DS q65 in the source's own text (comma joins in an order that is
no join order, derived tables ``sa``, ``sb``, ``sc`` that share
``ss_store_sk``) and in the ``JOIN ... ON`` form, against the
benchmark's plain reference (pandas; it imports nothing of the
program), on seeded data from the benchmark's scaled generator at
small size with the spec's key shapes: null store keys, Julian dates,
a store whose every item is under the threshold and one with none.
Then what q65 leans on in the aggregate: updates of more than
``_DENSE_MAX_GROUPS`` groups and a merge of partials whose groups
outnumber any one partial's."""

import importlib.util
import os
import sys

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from tests.parity import collect_plans

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(CHECKOUT, "benchmark")
CONFIG = "tpcds-sf10-store"
_BLOCK = """(select ss_store_sk, ss_item_sk, sum(ss_sales_price) as revenue
  from store_sales join date_dim on ss_sold_date_sk = d_date_sk
  where d_month_seq between 1176 and 1176+11
  group by ss_store_sk, ss_item_sk)"""
Q65_ON = f"""
select s_store_name, i_item_desc, sc.revenue, i_current_price,
       i_wholesale_cost, i_brand
from {_BLOCK} sc
join (select ss_store_sk, avg(revenue) as ave from {_BLOCK} sa
      group by ss_store_sk) sb
  on sb.ss_store_sk = sc.ss_store_sk and sc.revenue <= 0.1 * sb.ave
join store on s_store_sk = sc.ss_store_sk
join item on i_item_sk = sc.ss_item_sk
order by s_store_name, i_item_desc
limit 100
"""
ALL_UNDER, NONE_UNDER = 3, 5          # two of the selling (odd) stores
TABLES = {"store_sales": {"rows": 90_000, "files": 3,
                          "foreign_keys": {
                              "customer": 5000, "customer_address": 2500,
                              "customer_demographics": 19208,
                              "household_demographics": 72,
                              "promotion": 5}},
          "item": {"rows": 400, "files": 2},
          "store": {"rows": 12, "files": 1},
          "date_dim": {"rows": 73_049, "files": 1}}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    """The benchmark's generator, statement, reference and comparison,
    found as its harness finds them."""
    sys.path.insert(0, BENCH)
    try:
        import compare
        import datagen
        from datagen import tpcds_scaled
        with open(os.path.join(BENCH, "sql", CONFIG, "q65.sql")) as f:
            text = " ".join(f.read().split())
        reference = _load(os.path.join(BENCH, "reference", CONFIG,
                                       "q65.py"), "reference_q65")
        yield {"datagen": datagen, "scaled": tpcds_scaled,
               "compare": compare, "sql": text, "reference": reference}
    finally:
        sys.path.remove(BENCH)
        for name in ("compare", "datagen", "datagen.tpcds",
                     "datagen.tpcds_scaled"):
            sys.modules.pop(name, None)


def _doctored(sales: pa.Table) -> pa.Table:
    """One store sells everything for nothing (every revenue is 0, so
    is the average, and ``0 <= 0.1 * 0`` holds for each of its items),
    one sells every line at one price (no revenue is under a tenth of
    the average of multiples of it)."""
    store = sales.column("ss_store_sk")
    price = sales.column("ss_sales_price")
    price = pc.if_else(pc.fill_null(pc.equal(store, ALL_UNDER), False),
                       0.0, price)
    price = pc.if_else(pc.fill_null(pc.equal(store, NONE_UNDER), False),
                       7.0, price)
    at = sales.schema.get_field_index("ss_sales_price")
    return sales.set_column(at, "ss_sales_price", price)


@pytest.fixture(scope="module")
def served(bench, tmp_path_factory):
    """The data on disk and a session over it, once for the module."""
    from spark_rapids_tpu import TpuSparkSession
    root = str(tmp_path_factory.mktemp("q65"))
    made = bench["scaled"].make(TABLES, 2**31 + 65)
    sales, dict_cols = made["store_sales"]
    made["store_sales"] = (_doctored(sales), dict_cols)
    bench["datagen"].write(root, made, TABLES)
    s = TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
    for t in TABLES:
        s.register_view(t, s.read.parquet(os.path.join(root, t)))
    return s, root


@pytest.mark.parametrize("form", ["source-text", "join-on"])
def test_q65_equals_the_plain_reference(bench, served, form):
    from spark_rapids_tpu.obs import registry
    s, root = served
    captured = collect_plans(s)
    view = registry.get_registry().view()
    got = s.sql(bench["sql"] if form == "source-text" else Q65_ON).collect()
    moved = view.delta()["counters"]
    ref = bench["reference"]
    want = ref.compute(root, {})
    assert want.num_rows > 100           # the LIMIT cuts
    nums = bench["compare"].compare(got, want, ref.SPEC, 1e-9)
    assert nums == {"rows_diff": 0, "key_mismatch": 0,
                    "float_rel_err": pytest.approx(0, abs=1e-12)}, nums
    # the store that sells for nothing sits on its threshold (0 <= 0)
    # and the reference says so
    assert ref.threshold_margin(root) == 0.0 == \
        float(want.schema.metadata[b"threshold_margin"])
    names = []
    captured[-1].plan.foreach(lambda n: names.append(type(n).__name__))
    assert not [n for n in names if "NestedLoop" in n or "Cartesian" in n]
    # the block the text writes twice is planned once (its date join,
    # its aggregate) and read from twice: tests/test_subplan_reuse.py
    assert sum("HashJoin" in n for n in names) == 4
    assert sum("HashAggregate" in n for n in names) == 2
    assert names.count("TpuReusedSubplanExec") == 2
    assert moved.get("plan.reuse.subplans") == 1
    assert moved.get("exec.reuse.served") == 1
    assert not [n for n in names if "Exchange" in n]
    assert moved.get("join.path.product", 0) == 0
    assert moved.get("join.path.sortMerge", 0) == 0
    # the text's FROM order is no join order; the ON form's is
    assert (moved.get("plan.rewrite.reorderedJoins", 0) > 0) == \
        (form == "source-text")


def test_q65_whole_answer_and_the_two_stores(bench, served):
    """Without the LIMIT: every row of the reference, the store that
    sells for nothing with all its items, the one-price store with
    none."""
    import pyarrow.dataset as pads
    s, root = served
    got = s.sql(bench["sql"].replace("limit 100", "")).collect()
    ref = bench["reference"]
    want = ref.compute(root, {})
    spec = {**ref.SPEC, "limit": None}
    assert bench["compare"].compare(got, want, spec, 1e-9) == {
        "rows_diff": 0, "key_mismatch": 0,
        "float_rel_err": pytest.approx(0, abs=1e-12)}
    stores = pads.dataset(os.path.join(root, "store")).to_table(
        columns=["s_store_sk", "s_store_name"]).to_pandas()
    name = dict(zip(stores.s_store_sk, stores.s_store_name))
    by_store = got.to_pandas().groupby("s_store_name").size()
    block = ref._block(ref._block_rows(root), "float64")
    assert by_store[name[ALL_UNDER]] == \
        int((block.ss_store_sk == ALL_UNDER).sum()) > 50
    assert name[NONE_UNDER] not in by_store.index
    assert int((block.ss_store_sk == NONE_UNDER).sum()) > 50
    # the null store key is a group of its own inside the block and
    # in no row of the answer
    assert block.ss_store_sk.isna().sum() > 50
    assert set(by_store.index) <= set(name.values())


def test_q65_data_has_the_specs_key_shapes(bench, served):
    import pyarrow.dataset as pads
    _, root = served
    ss = pads.dataset(os.path.join(root, "store_sales")).to_table()
    assert ss.num_columns == 23 and ss.num_rows == 90_000
    store = ss.column("ss_store_sk")
    assert 0.02 < store.null_count / ss.num_rows < 0.06
    # the selling stores are the odd keys of 1..store
    assert set(pc.unique(store.drop_null()).to_pylist()) == {1, 3, 5, 7,
                                                            9, 11}
    assert ss.column("ss_item_sk").null_count == 0
    assert pc.min(ss.column("ss_sold_date_sk")).as_py() >= 2450816
    assert pc.max(ss.column("ss_item_sk")).as_py() <= 400
    for col, rows in (("ss_customer_sk", 5000), ("ss_addr_sk", 2500),
                      ("ss_cdemo_sk", 19208), ("ss_hdemo_sk", 72),
                      ("ss_promo_sk", 5)):
        assert 1 <= pc.min(ss.column(col)).as_py()
        assert pc.max(ss.column(col)).as_py() <= rows
    st = pads.dataset(os.path.join(root, "store")).to_table()
    assert st.num_columns == 29 and st.num_rows == 12
    assert len(set(st.column("s_store_name").to_pylist())) == 12
    item = pads.dataset(os.path.join(root, "item")).to_table()
    assert item.num_columns == 22
    assert len(set(item.column("i_item_desc").to_pylist())) == 400
    dd = pads.dataset(os.path.join(root, "date_dim")).to_table(
        columns=["d_date_sk", "d_month_seq", "d_year", "d_moy"])
    jan98 = dd.filter(pc.equal(dd.column("d_month_seq"), 1176))
    assert set(jan98.column("d_year").to_pylist()) == {1998}
    assert set(jan98.column("d_moy").to_pylist()) == {1}
    assert pc.min(dd.column("d_date_sk")).as_py() == 2415022


def test_q65_agg_shapes_count_what_the_aggregates_move(bench, served):
    _, root = served
    ref = bench["reference"]
    sys.path.insert(0, BENCH)
    try:
        import agg_bytes
    finally:
        sys.path.remove(BENCH)
        sys.modules.pop("agg_bytes", None)
    sa, sb, sc = ref.agg_shapes(root)
    assert sa == sc and sa["rows_in"] > sa["groups_out"] > 1000
    assert sb["rows_in"] == sa["groups_out"] and sb["groups_out"] == 7
    total = agg_bytes.statement_agg_bytes([sa, sb, sc])
    assert total["least_bytes"] == sum(total["bytes_by_aggregate"]) == \
        2 * (sa["rows_in"] * 16 + sa["groups_out"] * 16) \
        + sb["rows_in"] * 12 + 7 * 12


# -- the aggregate at many groups ---------------------------------------

def _many_groups(n, stores, items, seed):
    rng = np.random.default_rng(seed)
    store = rng.integers(1, stores + 1, n)
    return pa.table({
        "s": pa.array([None if i % 23 == 0 else int(v)
                       for i, v in enumerate(store)], pa.int32()),
        "i": pa.array(rng.integers(1, items + 1, n), pa.int32()),
        "p": np.round(rng.uniform(0, 100, n), 2)})


@pytest.mark.parametrize("parts", [1, 4], ids=["one-update", "merge-of-4"])
def test_an_aggregate_of_many_groups_equals_pandas(parts):
    """6,000 rows a batch over 40 x 300 cells: each update holds some
    4,700 groups, over ``_DENSE_MAX_GROUPS``, and the four partials'
    groups together (some 10,000 distinct) outnumber any one's."""
    from spark_rapids_tpu import TpuSparkSession, functions as F
    from spark_rapids_tpu.exec import tpu_aggregate as agg
    from spark_rapids_tpu.obs import registry
    t = _many_groups(6000 * parts, 40, 300, seed=65)
    s = TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
    view = registry.get_registry().view()
    got = s.create_dataframe(t, num_partitions=parts).group_by("s", "i") \
        .agg(F.sum("p").alias("revenue"), F.count("*").alias("n")) \
        .collect().to_pandas()
    moved = view.delta()["counters"]
    want = t.to_pandas().groupby(["s", "i"], dropna=False).agg(
        revenue=("p", "sum"), n=("p", "size")).reset_index()
    key = ["s", "i"]
    got = got.sort_values(key, na_position="first").reset_index(drop=True)
    want = want.sort_values(key, na_position="first").reset_index(drop=True)
    assert len(want) > agg._DENSE_MAX_GROUPS
    assert got.s.isna().sum() == want.s.isna().sum() > 0
    np.testing.assert_array_equal(got.i, want.i)
    np.testing.assert_array_equal(got.s.fillna(-1), want.s.fillna(-1))
    np.testing.assert_array_equal(got.n, want.n)
    np.testing.assert_allclose(got.revenue, want.revenue, rtol=1e-12)
    partial_groups = moved.get("agg.partials.groups", 0)
    if parts == 1:
        assert partial_groups == len(want)
        assert "agg.merge.rowsIn" not in moved
    else:
        per_batch = [len(t.slice(k * 6000, 6000).to_pandas()
                         .groupby(key, dropna=False).size())
                     for k in range(parts)]
        assert max(per_batch) < len(want) < sum(per_batch)
        assert moved["agg.merge.rowsIn"] == partial_groups == \
            sum(per_batch)
        assert moved["agg.merge.groupsOut"] == len(want)


def test_an_average_of_an_aggregate_equals_pandas():
    """``sb`` over ``sa``: an aggregate whose input is an aggregate's
    output, the null key a group of its own in both."""
    from spark_rapids_tpu import TpuSparkSession
    t = _many_groups(20_000, 9, 2000, seed=66)
    s = TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
    s.register_view("t", s.create_dataframe(t, num_partitions=3))
    got = s.sql("select s, avg(revenue) as ave, count(*) as n from "
                "(select s, i, sum(p) as revenue from t group by s, i) sa "
                "group by s order by s").collect().to_pandas()
    sa = t.to_pandas().groupby(["s", "i"], dropna=False).agg(
        revenue=("p", "sum")).reset_index()
    want = sa.groupby("s", dropna=False).agg(
        ave=("revenue", "mean"), n=("revenue", "size")).reset_index() \
        .sort_values("s", na_position="first").reset_index(drop=True)
    assert len(want) == 10 and np.isnan(want.s[0])
    np.testing.assert_array_equal(got.n, want.n)
    np.testing.assert_allclose(got.ave, want.ave, rtol=1e-12)
