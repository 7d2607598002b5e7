"""Plain reference of TPC-DS q65 (``sql/tpcds-sf10-store-ici4/q65.sql``,
byte for byte ``sql/tpcds-sf10-store/q65.sql``: ``query65.tpl`` with
DMS = 1176): pandas over the same files.  The whole answer in the
ORDER BY's order, before the LIMIT.  ``compute``, ``agg_shapes`` and
``threshold_margin`` are the one-chip configuration's; the four-chip
deployment adds ``ShuffleExchange`` to the operators a plan must hold
and ``exchange_shapes``, the rows its three exchanges have to carry.

The text's inner block (``sa`` and ``sc`` are the same block): the
sales of the twelve months from ``d_month_seq`` 1176 summed by
``(ss_store_sk, ss_item_sk)``.  A null date key joins nothing; a null
``ss_store_sk`` is a group of its own inside the block (SQL groups
nulls together), takes no part in ``sb``'s join (null equals nothing)
and is dropped by the join to ``store``."""

import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.dataset as pads

# the item's two prices are float64 columns that only pass through: the
# chip keeps a float64 as a pair of float32, so even a copy comes back
# a few units of the 15th digit off (PERF.md, PR 33) and they are held
# to the float limit, not to equality
SPEC = {"keys": ["s_store_name", "i_item_desc"], "exact": ["i_brand"],
        "approx": ["revenue", "i_current_price", "i_wholesale_cost"],
        "ordered": True, "limit": 100,
        "reads": {"store_sales": ["ss_sold_date_sk", "ss_item_sk",
                                  "ss_store_sk", "ss_sales_price"],
                  "date_dim": ["d_date_sk", "d_month_seq"],
                  "store": ["s_store_sk", "s_store_name"],
                  "item": ["i_item_sk", "i_item_desc", "i_current_price",
                           "i_wholesale_cost", "i_brand"]},
        "need_operators": ["ParquetScan", "HashJoin", "HashAggregate",
                           "Sort", "Limit", "ShuffleExchange"]}
DMS = 1176
SHARE = 0.1                     # sc.revenue <= 0.1 * sb.ave


def _read(root: str, table: str, columns, row_filter=None):
    return pads.dataset(os.path.join(root, table)).to_table(
        columns=columns, filter=row_filter).to_pandas()


def _block_rows(root: str):
    """The rows that reach the block's aggregate: store_sales joined to
    the twelve months of date_dim."""
    dt = _read(root, "date_dim", ["d_date_sk"],
               (pc.field("d_month_seq") >= DMS)
               & (pc.field("d_month_seq") <= DMS + 11))
    ss = _read(root, "store_sales", SPEC["reads"]["store_sales"])
    ss = ss.dropna(subset=["ss_sold_date_sk"])
    return ss[ss.ss_sold_date_sk.isin(dt.d_date_sk)]


def _block(rows, float_dtype: str):
    rows = rows.assign(ss_sales_price=rows.ss_sales_price.astype(float_dtype))
    return rows.groupby(["ss_store_sk", "ss_item_sk"], as_index=False,
                        dropna=False).agg(revenue=("ss_sales_price", "sum"))


def _under_threshold(root: str, float_dtype: str):
    """``sc`` joined to ``sb`` on the store key (a null key joins
    nothing), with each group's distance from its store's threshold,
    before the residual is applied."""
    sc = _block(_block_rows(root), float_dtype)
    sb = sc.groupby("ss_store_sk", as_index=False, dropna=False).agg(
        ave=("revenue", "mean"))
    j = sc.dropna(subset=["ss_store_sk"]).merge(
        sb.dropna(subset=["ss_store_sk"]), on="ss_store_sk")
    return j.assign(threshold=SHARE * j.ave)


def _margin(j) -> float:
    if not len(j):
        return float("inf")
    gap = np.abs(j.revenue - j.threshold) / np.maximum(
        np.abs(j.threshold), 1e-300)
    return float(gap.min())


def threshold_margin(root: str, float_dtype: str = "float64") -> float:
    """How near the nearest group lies to ``0.1 * ave``, relative to
    it: a row can change sides on rounding only where this is of the
    float error's size.  ``compute`` leaves the same number in its
    answer's schema metadata."""
    return _margin(_under_threshold(root, float_dtype))


def agg_shapes(root: str) -> list:
    """Each aggregate of the text, in its order (``sa``, ``sb`` over
    it, ``sc``), for ``agg_bytes.py``: the rows that reach it, the bytes
    of its keys and of its values in a row in and in a row out, and the
    groups it leaves.  The block's keys are two int32 and its value a
    float64 sum; ``sb`` has one int32 key and reads a float64 to leave
    a float64 average."""
    rows = _block_rows(root)
    block = _block(rows, "float64")
    shape = {"rows_in": len(rows), "key_bytes": 8, "value_bytes": 8,
             "groups_out": len(block), "out_value_bytes": 8}
    stores = int(block.ss_store_sk.nunique(dropna=False))
    return [shape,
            {"rows_in": len(block), "key_bytes": 4, "value_bytes": 8,
             "groups_out": stores, "out_value_bytes": 8},
            dict(shape)]


def _joined(root: str, j):
    """The groups under their store's threshold with the store's and
    the item's columns: the rows that reach the ORDER BY."""
    j = j[j.revenue <= j.threshold]
    j = j.merge(_read(root, "store", SPEC["reads"]["store"]),
                left_on="ss_store_sk", right_on="s_store_sk")
    return j.merge(_read(root, "item", SPEC["reads"]["item"]),
                   left_on="ss_item_sk", right_on="i_item_sk")


OUT_COLUMNS = ["s_store_name", "i_item_desc", "revenue", "i_current_price",
               "i_wholesale_cost", "i_brand"]


def exchange_shapes(root: str) -> list:
    """Each exchange a distributed plan of the text needs, in the
    text's order, for ``exchange_bytes.py``: the rows that reach it and
    the bytes of a row.  The block's GROUP BY (the rows joined to the
    twelve months, two int32 keys and a float64; the block is computed
    once), ``sb``'s GROUP BY over the block's groups (one int32 key,
    one float64) and the ORDER BY (the select list's six columns, the
    strings at their mean length in the files)."""
    rows = _block_rows(root)
    block = _block(rows, "float64")
    out = _joined(root, _under_threshold(root, "float64"))
    width = 3 * 8.0 + sum(float(out[c].str.len().mean() or 0.0)
                          for c in ("s_store_name", "i_item_desc",
                                    "i_brand")) if len(out) else 0.0
    return [{"name": "block", "rows": len(rows), "row_bytes": 16},
            {"name": "sb", "rows": len(block), "row_bytes": 12},
            {"name": "order_by", "rows": len(out), "row_bytes": width}]


def compute(root: str, bindings: dict, float_dtype: str = "float64"):
    j = _under_threshold(root, float_dtype)
    margin = _margin(j)
    j = _joined(root, j).sort_values(["s_store_name", "i_item_desc"])
    # the ORDER BY has to be total, or two right answers differ
    assert not j.duplicated(["s_store_name", "i_item_desc"]).any(), \
        "q65's sort key (s_store_name, i_item_desc) is not unique"
    out = pa.Table.from_pandas(
        j[OUT_COLUMNS], preserve_index=False)
    return out.replace_schema_metadata(
        {"threshold_margin": repr(margin)})
