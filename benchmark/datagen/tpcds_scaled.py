"""TPC-DS, dsdgen-lite at a scale factor the configuration file gives:
``store_sales``, ``item``, ``store`` and ``date_dim``.  ``datagen.tpcds``
fixes SF1's foreign-key ranges as constants; here every range is a row
count of the configuration: ``item`` and ``store`` from their own
entries, the tables that are not written from ``store_sales``'
``foreign_keys`` entry.  ``date_dim`` and the word lists are
``datagen.tpcds``'s.

What differs from ``datagen.tpcds`` beside the ranges:

- ``store`` is written (29 columns, keyed 1..rows); ``ss_store_sk`` is
  uniform over the odd keys, one store a ticket: ``store`` keeps the
  revisions of its business keys and dsdgen sells from one revision of
  each, about half of the rows (that it is the odd half is this
  generator's choice);
- ``s_store_name`` is distinct a store (dsdgen spells a store's number
  in syllables too, over a short cycle) and ``i_item_desc`` distinct an
  item (dsdgen's are random sentences): a statement that orders by them
  has one right order;
- ``store_sales`` is made in slices of whole tickets, each from a
  stream of its own, on a thread pool: numpy's generators and rounding
  release the lock, and at SF10 a single stream is most of a run's
  data time.

Surrogate keys int32, money float64, text as dictionary codes: never a
Python string a fact row.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa

from datagen import dict_strings, rng_for
from datagen import tpcds

SLICE_TICKETS = 150_000             # 1.8M rows a slice
MAKE_THREADS = 8
STREETS = ["Main", "Oak", "Park", "Elm", "Lake", "Hill", "Pine", "Maple",
           "Cedar", "River", "Ridge", "Church", "Spring", "Mill", "Forest"]
STREET_TYPES = ["Street", "Ave", "Blvd", "Road", "Lane", "Court", "Way",
                "Drive", "Pkwy", "Circle"]
CITIES = ["Midway", "Fairview", "Oak Grove", "Five Points", "Riverside",
          "Pleasant Hill", "Centerville", "Mount Pleasant", "Georgetown",
          "Salem", "Union", "Greenville", "Franklin", "Clinton", "Marion"]
COUNTIES = ["Williamson County", "Walker County", "Ziebach County",
            "Daviess County", "Barrow County", "Franklin Parish",
            "Luce County", "Richland County", "Bronx County",
            "Fairfield County"]
STATES = ["TN", "AL", "SD", "IN", "GA", "LA", "MI", "OH", "NY", "CT"]
HOURS = ["8AM-4PM", "8AM-8AM", "8AM-12AM"]
GEOGRAPHY = ["Unknown"]
MANAGERS = ["William Ward", "Scott Smith", "Edwin Adams", "David Thomas",
            "Michael Lee", "Brett Yates", "Kevin Hall", "Robert Lopez",
            "Thomas Pollack", "Larry Young"]


def store(rows: int, rng) -> pa.Table:
    key = np.arange(1, rows + 1, dtype=np.int64)
    start = rng.integers(tpcds.EPOCH_FIRST + 35000,
                         tpcds.EPOCH_FIRST + 37000, rows)
    tax = np.round(rng.integers(0, 12, rows) / 100.0, 2)
    state = rng.integers(0, len(STATES), rows)
    descs = [f"{tpcds.COLORS[i % 24]} market of {CITIES[i % 15]} "
             f"number {i}" for i in range(100)]
    return pa.table({
        "s_store_sk": tpcds._i32(key),
        "s_store_id": tpcds._ids(rows),
        "s_rec_start_date": pa.array(start.astype(np.int32),
                                     type=pa.date32()),
        "s_rec_end_date": pa.array((start + 1095).astype(np.int32),
                                   type=pa.date32(),
                                   mask=rng.random(rows) < 0.5),
        "s_closed_date_sk": pa.array(
            rng.integers(tpcds.SALES_FIRST, tpcds.SALES_LAST, rows)
            .astype(np.int32), mask=rng.random(rows) < 0.7),
        # distinct a store: the store's number in dsdgen's syllables
        "s_store_name": pa.array(tpcds._word(key)),
        "s_number_employees": tpcds._i32(rng.integers(200, 301, rows)),
        "s_floor_space": tpcds._i32(rng.integers(5_000_000, 10_000_001,
                                                 rows)),
        "s_hours": dict_strings(rng.integers(0, len(HOURS), rows), HOURS),
        "s_manager": dict_strings(rng.integers(0, len(MANAGERS), rows),
                                  MANAGERS),
        "s_market_id": tpcds._i32(rng.integers(1, 11, rows)),
        "s_geography_class": dict_strings(np.zeros(rows, np.int8),
                                          GEOGRAPHY),
        "s_market_desc": dict_strings(rng.integers(0, 100, rows), descs),
        "s_market_manager": dict_strings(
            rng.integers(0, len(MANAGERS), rows), MANAGERS),
        "s_division_id": tpcds._i32(np.ones(rows)),
        "s_division_name": dict_strings(np.zeros(rows, np.int8),
                                        ["Unknown"]),
        "s_company_id": tpcds._i32(np.ones(rows)),
        "s_company_name": dict_strings(np.zeros(rows, np.int8),
                                       ["Unknown"]),
        "s_street_number": pa.array(
            [str(v) for v in rng.integers(1, 1000, rows)]),
        "s_street_name": dict_strings(rng.integers(0, len(STREETS), rows),
                                      STREETS),
        "s_street_type": dict_strings(
            rng.integers(0, len(STREET_TYPES), rows), STREET_TYPES),
        "s_suite_number": pa.array(
            [f"Suite {v}" for v in rng.integers(0, 500, rows)]),
        "s_city": dict_strings(rng.integers(0, len(CITIES), rows), CITIES),
        "s_county": dict_strings(state, COUNTIES),
        "s_state": dict_strings(state, STATES),
        "s_zip": pa.array([f"{v:05d}" for v in
                           rng.integers(10000, 99999, rows)]),
        "s_country": dict_strings(np.zeros(rows, np.int8),
                                  ["United States"]),
        "s_gmt_offset": np.where(state < 5, -6.0, -5.0),
        "s_tax_precentage": tax,
    })


def item(rows: int, rng) -> pa.Table:
    """``datagen.tpcds``'s item with ``i_item_desc`` distinct an item:
    the item's number in a permutation drawn from the seed, so the
    description's order is not the key's."""
    table = tpcds.item(rows, rng)
    number = rng.permutation(rows)
    desc = pa.array(
        [f"{tpcds.COLORS[n % 24]} {tpcds.CLASSES[n % 16]} for "
         f"{tpcds.CATEGORIES[n % 10]} number {n}" for n in number.tolist()])
    at = table.schema.get_field_index("i_item_desc")
    return table.set_column(at, "i_item_desc", desc)


def _sales_slice(first_ticket: int, tickets: int, rows: int, ranges: dict,
                 rng) -> pa.Table:
    """``rows`` lines of ``tickets`` whole tickets (the last ticket of
    the table may be short).  A ticket is one customer's visit to one
    store: its lines share date, time, customer, demographics, address
    and store."""
    line = np.arange(rows, dtype=np.int64) // tpcds.TICKET_LINES

    def per_ticket(lo: int, hi: int) -> np.ndarray:
        return rng.integers(lo, hi + 1, tickets)[line]

    def nullable(values: np.ndarray) -> pa.Array:
        return pa.array(values.astype(np.int32),
                        mask=rng.random(rows) < tpcds.NULL_SHARE)

    quantity = rng.integers(1, 101, rows)
    wholesale = np.round(rng.uniform(1.0, 100.0, rows), 2)
    list_price = np.round(wholesale * rng.uniform(1.0, 2.0, rows), 2)
    sales_price = np.round(list_price * rng.uniform(0.0, 1.0, rows), 2)
    ext_sales = np.round(quantity * sales_price, 2)
    ext_wholesale = np.round(quantity * wholesale, 2)
    ext_list = np.round(quantity * list_price, 2)
    coupon = np.where(rng.random(rows) < 0.2,
                      np.round(ext_sales * rng.uniform(0.0, 1.0, rows), 2),
                      0.0)
    net_paid = np.round(ext_sales - coupon, 2)
    tax = np.round(net_paid * rng.integers(0, 10, rows) / 100.0, 2)
    return pa.table({
        "ss_sold_date_sk": nullable(
            per_ticket(tpcds.SALES_FIRST, tpcds.SALES_LAST)),
        "ss_sold_time_sk": nullable(per_ticket(28800, 75599)),
        "ss_item_sk": tpcds._i32(rng.integers(1, ranges["item"] + 1, rows)),
        "ss_customer_sk": nullable(per_ticket(1, ranges["customer"])),
        "ss_cdemo_sk": nullable(
            per_ticket(1, ranges["customer_demographics"])),
        "ss_hdemo_sk": nullable(
            per_ticket(1, ranges["household_demographics"])),
        "ss_addr_sk": nullable(per_ticket(1, ranges["customer_address"])),
        # the selling stores: keys 1, 3, 5, ... of 1..store
        "ss_store_sk": nullable(
            2 * per_ticket(1, (ranges["store"] + 1) // 2) - 1),
        "ss_promo_sk": nullable(
            rng.integers(1, ranges["promotion"] + 1, rows)),
        "ss_ticket_number": pa.array(line + first_ticket),
        "ss_quantity": nullable(quantity),
        "ss_wholesale_cost": wholesale,
        "ss_list_price": list_price,
        "ss_sales_price": sales_price,
        "ss_ext_discount_amt": np.round(ext_list - ext_sales, 2),
        "ss_ext_sales_price": ext_sales,
        "ss_ext_wholesale_cost": ext_wholesale,
        "ss_ext_list_price": ext_list,
        "ss_ext_tax": tax,
        "ss_coupon_amt": coupon,
        "ss_net_paid": net_paid,
        "ss_net_paid_inc_tax": np.round(net_paid + tax, 2),
        "ss_net_profit": np.round(net_paid - ext_wholesale, 2),
    })


def store_sales(rows: int, ranges: dict, seed: int) -> pa.Table:
    per = SLICE_TICKETS * tpcds.TICKET_LINES
    jobs = []
    for k, lo in enumerate(range(0, rows, per)):
        n = min(per, rows - lo)
        jobs.append((1 + lo // tpcds.TICKET_LINES,
                     -(-n // tpcds.TICKET_LINES), n, ranges,
                     rng_for(seed, 1000 + k)))
    with ThreadPoolExecutor(max_workers=MAKE_THREADS) as pool:
        slices = list(pool.map(lambda job: _sales_slice(*job), jobs))
    return pa.concat_tables(slices)


def make(tables: dict, seed: int) -> dict:
    out = {}
    if "date_dim" in tables:
        out["date_dim"] = (tpcds.date_dim(int(tables["date_dim"]["rows"])),
                           True)
    if "item" in tables:
        out["item"] = (item(int(tables["item"]["rows"]), rng_for(seed, 1)),
                       True)
    if "store" in tables:
        out["store"] = (store(int(tables["store"]["rows"]),
                              rng_for(seed, 3)), True)
    if "store_sales" in tables:
        spec = tables["store_sales"]
        ranges = dict(spec["foreign_keys"])
        for name in ("item", "store"):
            # a written dimension's keys are its rows
            if name in tables:
                ranges[name] = int(tables[name]["rows"])
        out["store_sales"] = (store_sales(int(spec["rows"]), ranges, seed),
                              tpcds.STORE_SALES_DICT)
    return out
