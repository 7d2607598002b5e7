"""``tpcds-sf10.q65``'s three readers (``metrics/agg_roofline.py``,
``agg_merge_rows.py``, ``join_products.py``) and the bytes the first
is held against (``agg_bytes.py``), each on a synthetic ``run``; then
the scaled generator: row counts and key ranges from the configuration
file, distinct names, null shares."""

import json
import os
import types

import pyarrow.compute as pc
import pytest

import agg_bytes
import cells
from conftest import BENCH

SHAPES = [{"rows_in": 5_500_000, "key_bytes": 8, "value_bytes": 8,
           "groups_out": 3_400_000, "out_value_bytes": 8},
          {"rows_in": 3_400_000, "key_bytes": 4, "value_bytes": 8,
           "groups_out": 52, "out_value_bytes": 8}]


def test_agg_bytes_from_shapes():
    block, sb = (agg_bytes.agg_bytes(s) for s in SHAPES)
    assert block == 5_500_000 * 16 + 3_400_000 * 16
    assert sb == 3_400_000 * 12 + 52 * 12
    assert agg_bytes.statement_agg_bytes(SHAPES) == {
        "aggregates": 2, "bytes_by_aggregate": [block, sb],
        "least_bytes": block + sb}


def a_run(programs, shapes=SHAPES, share=1.0):
    reference = types.SimpleNamespace(agg_shapes=lambda root: shapes) \
        if shapes is not None else types.SimpleNamespace()
    stmt = types.SimpleNamespace(name="q65", reference=reference)
    trace = None if programs is None else {
        "covered": [(0, share)], "queries": share, "chips": 1,
        "device_programs": programs}
    return {"trace": trace, "root": "/nowhere",
            "completed": [{"index": 0, "stmt": "q65"}],
            "cell": types.SimpleNamespace(statements=[stmt]),
            "counters": {}, "peaks": {"hbm_bytes_per_s": 819e9}}


def test_roofline_reads_the_aggregate_programs():
    run = a_run([["jit_agg_merge:*", 6.0], ["jit_pq_fused6:*", 4.0],
                 ["jit_agg_update:*", 3.5], ["jit_agg_shrink:*", 0.25],
                 ["jit_agg_final:*", 0.25], ["jit_aggregate:*", 9.0]])
    least = agg_bytes.statement_agg_bytes(SHAPES)["least_bytes"] / 819e9
    got = cells.reader("agg_roofline")(run)
    assert got == pytest.approx(100 * least / 10.0)
    assert 0 < got < 100
    # a third of a query traced: a third of its bytes against what ran
    part = a_run([["jit_agg_merge:*", 2.0]], share=1 / 3)
    assert cells.reader("agg_roofline")(part) == \
        pytest.approx(100 * least / 3 / 2.0)


@pytest.mark.parametrize("run", [
    a_run(None), a_run([["jit_pq_fused6:*", 0.3]]),
    a_run([["jit_agg_update:*", 0.3]], shapes=None),
], ids=["no-trace", "no-aggregate-program", "no-shapes"])
def test_roofline_has_nothing_to_read(run):
    assert cells.reader("agg_roofline")(run) is None


@pytest.mark.parametrize("metric,counter,also", [
    ("agg_merge_rows", "agg.merge.rowsIn", "agg.merge.rowsIn"),
    ("join_products", "join.path.product", "plan.rewrite.reorderedJoins"),
])
def test_counter_readers(metric, counter, also):
    from spark_rapids_tpu.obs import registry
    read = cells.reader(metric)
    run = a_run(None)
    reg = registry.get_registry()
    if counter not in reg.snapshot()["counters"] and \
            also not in reg.snapshot()["counters"]:
        assert read(run) is None       # a program with no such counter
    reg.inc(also, 0)
    assert read(run) == 0
    run["counters"] = {counter: 11}
    run["completed"] = run["completed"] * 2
    assert read(run) == 5.5


# -- the scaled generator -----------------------------------------------

@pytest.fixture(scope="module")
def made():
    import datagen
    from datagen import tpcds_scaled
    with open(os.path.join(BENCH, "configs",
                           "tpcds-sf10-store.json")) as f:
        config = json.load(f)
    tables = {t: dict(s) for t, s in config["tables"].items()}
    tables["store_sales"]["rows"] = 240_007      # the last ticket short
    out = tpcds_scaled.make(tables, 2**31 + 7)
    again = tpcds_scaled.make({"store_sales": tables["store_sales"],
                               "store": tables["store"],
                               "item": tables["item"]}, 2**31 + 7)
    assert datagen.rng_for(1, 2) is not None
    return config, {t: v[0] for t, v in out.items()}, \
        {t: v[0] for t, v in again.items()}


def test_rows_and_columns_are_the_configurations(made):
    config, tables, _ = made
    assert {t: tables[t].num_rows for t in ("item", "store", "date_dim")} \
        == {"item": 102_000, "store": 102, "date_dim": 73_049}
    assert tables["store_sales"].num_rows == 240_007
    assert [tables[t].num_columns for t in
            ("store_sales", "item", "store", "date_dim")] == [23, 22, 29, 28]
    assert config["tables"]["store_sales"]["rows"] == 28_800_991


def test_key_ranges_come_from_the_file(made):
    config, tables, _ = made
    ss = tables["store_sales"]
    ranges = dict(config["tables"]["store_sales"]["foreign_keys"])
    for col, name in (("ss_customer_sk", "customer"),
                      ("ss_addr_sk", "customer_address"),
                      ("ss_cdemo_sk", "customer_demographics"),
                      ("ss_hdemo_sk", "household_demographics"),
                      ("ss_promo_sk", "promotion"), ("ss_item_sk", "item")):
        lo, hi = pc.min(ss.column(col)).as_py(), pc.max(ss.column(col)).as_py()
        assert 1 <= lo and ranges[name] * 0.9 < hi <= ranges[name], col
    stores = set(pc.unique(ss.column("ss_store_sk").drop_null()).to_pylist())
    assert stores == set(range(1, 102, 2))       # the 51 odd keys
    assert pc.min(ss.column("ss_sold_date_sk")).as_py() >= 2450816
    assert pc.max(ss.column("ss_sold_date_sk")).as_py() <= 2452642


def test_null_shares_and_tickets(made):
    _, tables, _ = made
    ss = tables["store_sales"]
    assert ss.column("ss_item_sk").null_count == 0
    assert ss.column("ss_ticket_number").null_count == 0
    for col in ("ss_store_sk", "ss_sold_date_sk", "ss_customer_sk"):
        assert 0.03 < ss.column(col).null_count / ss.num_rows < 0.05, col
    # twelve lines a ticket, one store and one date a ticket
    df = ss.select(["ss_ticket_number", "ss_store_sk",
                    "ss_sold_date_sk"]).to_pandas()
    by = df.groupby("ss_ticket_number")
    assert by.size().max() == 12 and by.size().iloc[-1] == 240_007 % 12
    assert by.ss_store_sk.nunique().max() == 1
    assert by.ss_sold_date_sk.nunique().max() == 1


def test_names_are_distinct_and_the_seed_decides(made):
    _, tables, again = made
    assert len(set(tables["store"].column("s_store_name").to_pylist())) == 102
    assert len(set(tables["item"].column("i_item_desc").to_pylist())) \
        == 102_000
    assert max(len(d) for d in
               tables["item"].column("i_item_desc").to_pylist()) <= 200
    for t in ("store_sales", "store", "item"):
        assert tables[t].equals(again[t]), t
