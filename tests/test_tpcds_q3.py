"""TPC-DS q3 in the source's own text (comma joins, aliases, LIMIT 100)
and in the ``JOIN ... ON`` form, against the benchmark's plain
reference (pandas; it imports nothing of the program), on seeded data
at small size with the spec's key shapes: Julian ``d_date_sk`` from
2415022, null foreign keys, item keys past 32,768 in one case."""

import importlib.util
import json
import os
import sys

import pytest

from tests.parity import collect_plans

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(CHECKOUT, "benchmark")
Q3_ON = """
select dt.d_year, item.i_brand_id brand_id, item.i_brand brand,
       sum(ss_ext_sales_price) sum_agg
from store_sales
join date_dim dt on dt.d_date_sk = store_sales.ss_sold_date_sk
join item on store_sales.ss_item_sk = item.i_item_sk
where item.i_manufact_id = 128 and dt.d_moy = 11
group by dt.d_year, item.i_brand, item.i_brand_id
order by dt.d_year, sum_agg desc, brand_id
limit 100
"""


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
        with open(os.path.join(BENCH, "sql", "tpcds-sf1-store",
                               "q3.sql")) as f:
            text = " ".join(f.read().split())
        reference = _load(os.path.join(BENCH, "reference",
                                       "tpcds-sf1-store", "q3.py"),
                          "reference_q3")
        yield {"datagen": datagen, "compare": compare, "sql": text,
               "reference": reference}
    finally:
        sys.path.remove(BENCH)
        for name in ("compare", "datagen", "datagen.tpcds"):
            sys.modules.pop(name, None)


def _session(bench, root, items: int, seed: int):
    from spark_rapids_tpu import TpuSparkSession
    tables = {"store_sales": {"rows": 300_000, "files": 2},
              "item": {"rows": items, "files": 1},
              "date_dim": {"rows": 73_049, "files": 1}}
    bench["datagen"].generate("tpcds", root, tables, seed)
    s = TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
    for t in tables:
        s.register_view(t, s.read.parquet(os.path.join(root, t)))
    return s


def _check(bench, got, root):
    ref = bench["reference"]
    want = ref.compute(root, {})
    assert want.num_rows >= 5
    nums = bench["compare"].compare(got, want, ref.SPEC, 1e-9)
    assert nums == {"rows_diff": 0, "key_mismatch": 0,
                    "float_rel_err": pytest.approx(0, abs=1e-12)}, nums


@pytest.mark.parametrize("form", ["source-text", "join-on"])
@pytest.mark.parametrize("items", [3_000, 40_000],
                         ids=["items-3000", "items-past-32768"])
def test_q3_equals_the_plain_reference(bench, tmp_path, form, items):
    from spark_rapids_tpu.obs import registry
    # a thousandth of the items are manufacturer 128's: two or three
    # of 3,000 with this seed, forty of 40,000
    s = _session(bench, str(tmp_path), items, seed=2**31 + items)
    captured = collect_plans(s)
    view = registry.get_registry().view()
    got = s.sql(bench["sql"] if form == "source-text" else Q3_ON).collect()
    moved = view.delta()["counters"]
    _check(bench, got, str(tmp_path))
    names = []
    captured[-1].plan.foreach(lambda n: names.append(type(n).__name__))
    assert not [n for n in names if "NestedLoop" in n or "Cartesian" in n]
    assert sum("HashJoin" in n for n in names) == 2
    # both joins, every batch pair, through the direct-address table
    assert moved.get("join.path.direct", 0) >= 2
    assert moved.get("join.path.sortMerge", 0) == 0
    # date_dim is keyed as the spec keys it
    assert registry.get_registry().gauge("join.table.entries") >= 73_049


def test_q3_data_has_the_specs_key_shapes(bench, tmp_path):
    import pyarrow.compute as pc
    import pyarrow.dataset as pads
    _session(bench, str(tmp_path), 40_000, seed=5)
    root = str(tmp_path)
    dd = pads.dataset(os.path.join(root, "date_dim")).to_table()
    sk = dd.column("d_date_sk")
    assert (pc.min(sk).as_py(), pc.max(sk).as_py()) == (2415022, 2488070)
    assert dd.num_columns == 28 and dd.column("d_date")[0].as_py() \
        .isoformat() == "1900-01-02"
    ss = pads.dataset(os.path.join(root, "store_sales")).to_table()
    assert ss.num_columns == 23
    assert 0 < ss.column("ss_sold_date_sk").null_count < ss.num_rows // 10
    # with ss_ticket_number the table's primary key: never null
    assert ss.column("ss_item_sk").null_count == 0
    assert pc.max(ss.column("ss_item_sk")).as_py() > 32_768
    assert pc.min(ss.column("ss_sold_date_sk")).as_py() >= 2450816
    item = pads.dataset(os.path.join(root, "item")).to_table()
    assert item.num_columns == 22


def test_q3_null_keys_join_nothing(bench, tmp_path):
    """The rows whose foreign keys are null are in no group: the sums
    equal the reference's, which drops them before it joins."""
    import pyarrow.dataset as pads
    s = _session(bench, str(tmp_path), 3_000, seed=11)
    root = str(tmp_path)
    ss = pads.dataset(os.path.join(root, "store_sales")).to_table(
        columns=["ss_sold_date_sk", "ss_item_sk"])
    assert ss.column(0).null_count
    got = s.sql("select count(*) c from date_dim dt, store_sales, item "
                "where dt.d_date_sk = ss_sold_date_sk "
                "and ss_item_sk = i_item_sk").collect()
    both = ss.to_pandas().dropna()
    assert got.column("c").to_pylist() == [len(both)]


def test_configuration_states_the_source(bench):
    with open(os.path.join(BENCH, "configs", "tpcds-sf1-store.json")) as f:
        conf = json.load(f)
    assert conf["tables"]["store_sales"]["rows"] == 2_880_404
    assert conf["tables"]["item"]["rows"] == 18_000
    assert conf["tables"]["date_dim"]["rows"] == 73_049
    assert "i_manufact_id = 128" in bench["sql"] and \
        "d_moy=11" in bench["sql"] and "LIMIT 100" in bench["sql"]
    assert "from date_dim dt, store_sales, item" in bench["sql"].lower()
