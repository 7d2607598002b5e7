"""Plain reference of TPC-DS q3 (``sql/tpcds-sf1-store/q3.sql``:
``query3.tpl`` with MANUFACT = 128 and MONTH = 11): pandas over the
same files.  The whole answer in the ORDER BY's order, before the
LIMIT."""

import os

import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.dataset as pads

SPEC = {"keys": ["d_year", "brand_id", "brand"], "exact": [],
        "approx": ["sum_agg"], "ordered": True, "order_float": "sum_agg",
        "limit": 100,
        "reads": {"store_sales": ["ss_sold_date_sk", "ss_item_sk",
                                  "ss_ext_sales_price"],
                  "date_dim": ["d_date_sk", "d_year", "d_moy"],
                  "item": ["i_item_sk", "i_brand_id", "i_brand",
                           "i_manufact_id"]},
        "need_operators": ["ParquetScan", "HashJoin", "HashAggregate",
                           "Sort", "Limit"]}


def _read(root: str, table: str, columns, row_filter=None):
    return pads.dataset(os.path.join(root, table)).to_table(
        columns=columns, filter=row_filter).to_pandas()


def _sides(root: str):
    dt = _read(root, "date_dim", ["d_date_sk", "d_year"],
               pc.field("d_moy") == 11)
    item = _read(root, "item", ["i_item_sk", "i_brand_id", "i_brand"],
                 pc.field("i_manufact_id") == 128)
    ss = _read(root, "store_sales", SPEC["reads"]["store_sales"])
    return dt, ss, item


def _joined(dt, ss, item):
    """The text's two joins in its order; a null key joins nothing."""
    first = ss.dropna(subset=["ss_sold_date_sk"]).merge(
        dt, left_on="ss_sold_date_sk", right_on="d_date_sk")
    second = first.dropna(subset=["ss_item_sk"]).merge(
        item, left_on="ss_item_sk", right_on="i_item_sk")
    return first, second


def _span(keys) -> int:
    return int(keys.max() - keys.min() + 1) if len(keys) else 1


def join_shapes(root: str) -> list:
    """The two joins' shapes for ``join_bytes.py``: keys are int32;
    the first join's output carries ``d_year``, ``ss_item_sk`` and
    ``ss_ext_sales_price``, the second's ``d_year``, the price,
    ``i_brand_id`` and ``i_brand``."""
    dt, ss, item = _sides(root)
    first, second = _joined(dt, ss, item)
    brand = float(second.i_brand.str.len().mean()) if len(second) else 0.0
    return [
        {"build_rows": len(dt), "stream_rows": len(ss), "key_bytes": 4,
         "table_entries": _span(dt.d_date_sk), "out_rows": len(first),
         "out_row_bytes": 4 + 4 + 8},
        {"build_rows": len(item), "stream_rows": len(first), "key_bytes": 4,
         "table_entries": _span(item.i_item_sk), "out_rows": len(second),
         "out_row_bytes": 4 + 8 + 4 + 4 + brand}]


def compute(root: str, bindings: dict, float_dtype: str = "float64"):
    j = _joined(*_sides(root))[1]
    j["ss_ext_sales_price"] = j.ss_ext_sales_price.astype(float_dtype)
    g = j.groupby(["d_year", "i_brand", "i_brand_id"], as_index=False).agg(
        sum_agg=("ss_ext_sales_price", "sum"))
    g = g.rename(columns={"i_brand_id": "brand_id", "i_brand": "brand"})
    g = g.sort_values(["d_year", "sum_agg", "brand_id"],
                      ascending=[True, False, True])
    return pa.Table.from_pandas(
        g[["d_year", "brand_id", "brand", "sum_agg"]], preserve_index=False)
