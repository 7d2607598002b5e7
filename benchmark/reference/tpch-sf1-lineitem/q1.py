"""Plain reference of TPC-H Q1 (``sql/tpch-sf1-lineitem/q1.sql``,
DELTA = 90 days): pandas over the same files."""

import datetime
import os

import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.dataset as pads

SPEC = {"keys": ["l_returnflag", "l_linestatus"], "exact": ["count_order"],
        "approx": ["sum_qty", "sum_base_price", "sum_disc_price",
                   "sum_charge", "avg_qty", "avg_price", "avg_disc"],
        "ordered": True, "order_float": None, "limit": None,
        "reads": {"lineitem": ["l_returnflag", "l_linestatus", "l_quantity",
                               "l_extendedprice", "l_discount", "l_tax",
                               "l_shipdate"]},
        "need_operators": ["ParquetScan", "HashAggregate", "Sort"]}


def compute(root: str, bindings: dict, float_dtype: str = "float64"):
    li = pads.dataset(os.path.join(root, "lineitem")).to_table(
        columns=["l_returnflag", "l_linestatus", "l_quantity",
                 "l_extendedprice", "l_discount", "l_tax"],
        filter=pc.field("l_shipdate") <= datetime.date(1998, 9, 2)
    ).to_pandas()
    for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax"):
        li[c] = li[c].astype(float_dtype)
    one = li.l_discount.dtype.type(1.0)
    li["disc_price"] = li.l_extendedprice * (one - li.l_discount)
    li["charge"] = li.disc_price * (one + li.l_tax)
    g = li.groupby(["l_returnflag", "l_linestatus"], as_index=False).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size"))
    g = g.sort_values(["l_returnflag", "l_linestatus"])
    return pa.Table.from_pandas(g, preserve_index=False)
