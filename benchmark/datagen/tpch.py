"""TPC-H, dbgen-lite: ``lineitem`` built alongside ``orders`` so keys
and dates agree, and ``customer``.  The rules are those of the repo's
generator (``spark_rapids_tpu/bench/tpch.py``: 1 to 7 lines an order,
2% bulk orders, flags from the dates against 1995-06-17), vectorised:
text columns are dictionary codes, never one Python string per row.
Not audited dbgen output.

``lineitem``'s row count is exact: the lines per order are drawn, then
single lines are added or taken until the total is the configuration's.
"""

import numpy as np
import pyarrow as pa

from datagen import dict_strings, rng_for

DAY_START = 8035        # 1992-01-01, days since 1970-01-01
DAY_CURRENT = 9298      # 1995-06-17
DAY_END = 10591         # 1998-12-31
WORDS = ["packages", "deposits", "accounts", "foxes", "ideas",
         "theodolites", "dependencies", "instructions", "excuses",
         "platelets", "requests", "asymptotes", "courts", "dolphins",
         "multipliers", "sauternes", "warthogs", "frets", "dinos",
         "attainments"]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
PARTS, SUPPLIERS = 200_000, 10_000     # SF1 ranges of the foreign keys

LINEITEM_DICT = ["l_suppkey", "l_linenumber", "l_quantity", "l_discount",
                 "l_tax", "l_returnflag", "l_linestatus", "l_shipdate",
                 "l_commitdate", "l_receiptdate", "l_shipinstruct",
                 "l_shipmode", "l_comment"]
ORDERS_DICT = ["o_orderstatus", "o_orderdate", "o_orderpriority", "o_clerk",
               "o_shippriority", "o_comment"]


def _dates(days: np.ndarray) -> pa.Array:
    return pa.array(days.astype(np.int32), type=pa.date32())


def _text(rng, n: int) -> pa.Array:
    """Four words from ``WORDS``: 160,000 distinct texts, as codes."""
    values = [f"{a} {b} {c} {d}" for a in WORDS for b in WORDS
              for c in WORDS for d in WORDS]
    return dict_strings(rng.integers(0, len(values), n), values)


def _lines_per_order(rng, orders: int, lines: int):
    is_bulk = rng.random(orders) < 0.02
    n = np.where(is_bulk, 7, rng.integers(1, 8, orders))
    diff = lines - int(n.sum())
    room = np.flatnonzero(~is_bulk & ((n < 7) if diff > 0 else (n > 1)))
    if abs(diff) > room.size:
        raise ValueError(f"{lines} lineitem rows cannot come from "
                         f"{orders} orders of 1 to 7 lines")
    n[rng.choice(room, abs(diff), replace=False)] += 1 if diff > 0 else -1
    return n, is_bulk


def customer(rows: int, rng) -> pa.Table:
    key = np.arange(1, rows + 1, dtype=np.int64)
    nation = rng.integers(0, 25, rows).astype(np.int32)
    a, b, c = (rng.integers(lo, hi, rows) for lo, hi in
               ((100, 999), (100, 999), (1000, 9999)))
    return pa.table({
        "c_custkey": pa.array(key),
        "c_name": [f"Customer#{i:09d}" for i in key],
        "c_address": _text(rng, rows),
        "c_nationkey": pa.array(nation),
        "c_phone": [f"{10 + k}-{x}-{y}-{z}"
                    for k, x, y, z in zip(nation, a, b, c)],
        "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, rows), 2),
        "c_mktsegment": dict_strings(rng.integers(0, 5, rows), SEGMENTS),
        "c_comment": _text(rng, rows),
    })


def orders_and_lineitem(orders: int, lines: int, customers: int, rng,
                        want: set) -> dict:
    nlines, is_bulk = _lines_per_order(rng, orders, lines)
    o_key = np.arange(1, orders + 1, dtype=np.int64)
    o_day = rng.integers(DAY_START, DAY_END - 151, orders)
    ends = np.cumsum(nlines)
    starts = ends - nlines

    l_order = np.repeat(o_key, nlines)
    l_oday = np.repeat(o_day, nlines)
    l_part = rng.integers(1, PARTS + 1, lines).astype(np.int64)
    l_supp = 1 + (l_part - 1 + rng.integers(0, 4, lines)
                  * (SUPPLIERS // 4 + 1)) % SUPPLIERS
    l_qty = np.where(np.repeat(is_bulk, nlines),
                     rng.integers(45, 51, lines), rng.integers(1, 51, lines))
    retail = 900.0 + (l_part % 1000) / 10.0 + 100.0 * (l_part % 10)
    l_price = np.round(l_qty * retail / 10.0, 2)
    l_disc = np.round(rng.integers(0, 11, lines) / 100.0, 2)
    l_tax = np.round(rng.integers(0, 9, lines) / 100.0, 2)
    l_ship = l_oday + rng.integers(1, 122, lines)
    l_commit = l_oday + rng.integers(30, 91, lines)
    l_receipt = l_ship + rng.integers(1, 31, lines)
    # A or R once received by the current date, else N; O while unshipped
    l_flag = np.where(l_receipt <= DAY_CURRENT,
                      rng.integers(0, 2, lines) * 2, 1)
    l_open = l_ship > DAY_CURRENT

    out = {}
    if "orders" in want:
        n_open = np.add.reduceat(l_open.astype(np.int64), starts)
        total = np.add.reduceat(
            np.round(l_price * (1.0 + l_tax) * (1.0 - l_disc), 2), starts)
        out["orders"] = (pa.table({
            "o_orderkey": pa.array(o_key),
            # the spec gives orders to two thirds of the customers
            "o_custkey": pa.array(rng.integers(
                1, max(2, customers * 2 // 3) + 1, orders).astype(np.int64)),
            "o_orderstatus": dict_strings(
                np.where(n_open == 0, 0, np.where(n_open == nlines, 1, 2)),
                ["F", "O", "P"]),
            "o_totalprice": np.round(total, 2),
            "o_orderdate": _dates(o_day),
            "o_orderpriority": dict_strings(rng.integers(0, 5, orders),
                                            PRIORITIES),
            "o_clerk": dict_strings(rng.integers(0, 1000, orders),
                                    [f"Clerk#{i:09d}"
                                     for i in range(1, 1001)]),
            "o_shippriority": pa.array(np.zeros(orders, dtype=np.int32)),
            "o_comment": _text(rng, orders),
        }), ORDERS_DICT)
    if "lineitem" in want:
        out["lineitem"] = (pa.table({
            "l_orderkey": pa.array(l_order),
            "l_partkey": pa.array(l_part),
            "l_suppkey": pa.array(l_supp),
            "l_linenumber": pa.array((np.arange(lines)
                                      - np.repeat(starts, nlines) + 1)
                                     .astype(np.int32)),
            "l_quantity": pa.array(l_qty.astype(np.float64)),
            "l_extendedprice": l_price,
            "l_discount": l_disc,
            "l_tax": l_tax,
            "l_returnflag": dict_strings(l_flag, ["A", "N", "R"]),
            "l_linestatus": dict_strings(l_open, ["F", "O"]),
            "l_shipdate": _dates(l_ship),
            "l_commitdate": _dates(l_commit),
            "l_receiptdate": _dates(l_receipt),
            "l_shipinstruct": dict_strings(rng.integers(0, 4, lines),
                                           INSTRUCTS),
            "l_shipmode": dict_strings(rng.integers(0, 7, lines), SHIPMODES),
            "l_comment": _text(rng, lines),
        }), LINEITEM_DICT)
    return out


def make(tables: dict, seed: int) -> dict:
    out = {}
    customers = int(tables.get("customer", {}).get("rows", 150_000))
    if "customer" in tables:
        out["customer"] = (customer(customers, rng_for(seed, 1)), True)
    want = {"orders", "lineitem"} & set(tables)
    if want:
        # a cell that reads lineitem alone still draws it from the
        # configuration's orders, so every cell sees the same lineitem
        orders = int(tables.get("orders", {}).get(
            "rows", round(int(tables["lineitem"]["rows"]) / 4.0008)))
        lines = int(tables.get("lineitem", {}).get("rows", orders * 4))
        out.update(orders_and_lineitem(orders, lines, customers,
                                       rng_for(seed, 2), want))
    return out
