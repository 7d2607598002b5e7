"""TPC-DS, dsdgen-lite: ``store_sales``, ``item`` and ``date_dim`` with
the spec's columns, key shapes and row counts (taken from the
configuration file, so another scale factor is a data file).  Not
audited dsdgen output; what a statement can see of the keys is the
spec's:

- ``date_dim`` holds one row a day from 1900-01-02, ``d_date_sk`` the
  Julian day number (2415022 for the first row; 73,049 rows end at
  2488070, 2100-01-01), ``d_year``/``d_moy``/``d_dom`` from the calendar;
- ``item`` is keyed 1..rows; ``i_manufact_id`` is uniform over 1..1000,
  ``i_brand_id`` is category x 1,000,000 + class x 1,000 + brand number
  and ``i_brand`` is spelled from the same three, so equal brand ids
  carry equal brand names as in dsdgen;
- ``store_sales`` draws ``ss_sold_date_sk`` uniformly from the spec's
  sales window (2450816..2452642: 1998-01-02 to 2003-01-02) and
  ``ss_item_sk`` uniformly from the item keys; every foreign key but
  ``ss_item_sk`` is null in about 4% of rows (dsdgen's nullable fact
  keys; ``ss_item_sk`` and ``ss_ticket_number`` are the table's
  primary key and never null).

Surrogate keys are int32 and money float64, as spark-rapids'
``TpcdsLikeSpark`` schema has them.  Text columns are dictionary
codes, never one Python string per row, except the two id columns of
the dimensions.
"""

import numpy as np
import pyarrow as pa

from datagen import dict_strings, rng_for

JULIAN_FIRST = 2415022              # d_date_sk of 1900-01-02
EPOCH_FIRST = -25566                # 1900-01-02 in days since 1970-01-01
SALES_FIRST, SALES_LAST = 2450816, 2452642
NULL_SHARE = 0.04
# SF1 ranges of the foreign keys whose tables are not written
CUSTOMERS, CDEMOS, HDEMOS, ADDRESSES = 100_000, 1_920_800, 7_200, 50_000
STORES, PROMOS, TICKET_LINES = 12, 300, 12

SYLLABLES = ["amalg", "importo", "edu pack", "exporti", "scholar", "brand",
             "corp", "maxi", "nameless", "univ"]
DIGITS = ["bar", "ought", "able", "pri", "ese", "anti", "cally", "ation",
          "eing", "n st"]
CATEGORIES = ["Women", "Men", "Children", "Shoes", "Music", "Jewelry",
              "Home", "Sports", "Books", "Electronics"]
CLASSES = ["dresses", "pants", "shirts", "accessories", "athletic", "kids",
           "classical", "country", "pop", "rock", "bedding", "lighting",
           "fitness", "golf", "fiction", "cameras"]
SIZES = ["petite", "small", "medium", "large", "extra large", "economy",
         "N/A"]
COLORS = ["almond", "azure", "beige", "bisque", "blush", "burlywood",
          "chiffon", "coral", "cornsilk", "cream", "dodger", "firebrick",
          "frosted", "gainsboro", "honeydew", "indian", "khaki", "lace",
          "lavender", "linen", "metallic", "mint", "misty", "navajo"]
UNITS = ["Unknown", "Each", "Dozen", "Case", "Pallet", "Gross", "Carton",
         "Box", "Bunch", "Bundle", "Cup", "Dram", "Gram", "Lb", "Oz",
         "Ounce", "Pound", "Ton", "Tsp", "Tbl", "N/A"]
DAY_NAMES = ["Monday", "Tuesday", "Wednesday", "Thursday", "Friday",
             "Saturday", "Sunday"]

STORE_SALES_DICT = ["ss_sold_date_sk", "ss_sold_time_sk", "ss_item_sk",
                    "ss_customer_sk", "ss_hdemo_sk", "ss_addr_sk",
                    "ss_store_sk", "ss_promo_sk", "ss_quantity"]


def _i32(values) -> pa.Array:
    return pa.array(np.asarray(values).astype(np.int32))


def _nullable_i32(rng, values: np.ndarray) -> pa.Array:
    return pa.array(values.astype(np.int32),
                    mask=rng.random(values.shape[0]) < NULL_SHARE)


def _ids(n: int) -> list:
    """The 16-character business keys: ``AAAAAAAA`` then eight letters
    spelling the row number in base 16 from ``A``."""
    digits = np.stack([(np.arange(n) >> (4 * k)) & 15
                       for k in range(7, -1, -1)], axis=1) + ord("A")
    tails = np.ascontiguousarray(digits.astype(np.uint8)).view("S8").ravel()
    return ["AAAAAAAA" + t.decode() for t in tails]


def _word(number: np.ndarray) -> list:
    """dsdgen's number words: one syllable a decimal digit."""
    return ["".join(DIGITS[int(d)] for d in str(int(v))) for v in number]


def date_dim(rows: int) -> pa.Table:
    import pandas as pd
    days = EPOCH_FIRST + np.arange(rows, dtype=np.int64)
    idx = pd.DatetimeIndex(days.astype("datetime64[D]"))
    year, moy, dom = (idx.year.to_numpy(), idx.month.to_numpy(),
                      idx.day.to_numpy())
    dow = idx.dayofweek.to_numpy()                    # Monday = 0
    qoy = (moy - 1) // 3 + 1
    sk = JULIAN_FIRST + np.arange(rows, dtype=np.int64)
    month_seq = (year - 1900) * 12 + moy - 1
    week_seq = (np.arange(rows) + 1) // 7 + 1
    quarter_seq = (year - 1900) * 4 + qoy
    first_dom = sk - (dom - 1)
    last_dom = first_dom + idx.days_in_month.to_numpy() - 1
    yes_no = ["N", "Y"]
    return pa.table({
        "d_date_sk": _i32(sk),
        "d_date_id": _ids(rows),
        "d_date": pa.array(days.astype(np.int32), type=pa.date32()),
        "d_month_seq": _i32(month_seq),
        "d_week_seq": _i32(week_seq),
        "d_quarter_seq": _i32(quarter_seq),
        "d_year": _i32(year),
        "d_dow": _i32((dow + 1) % 7),                 # Sunday = 0
        "d_moy": _i32(moy),
        "d_dom": _i32(dom),
        "d_qoy": _i32(qoy),
        "d_fy_year": _i32(year),
        "d_fy_quarter_seq": _i32(quarter_seq),
        "d_fy_week_seq": _i32(week_seq),
        "d_day_name": dict_strings(dow, DAY_NAMES),
        "d_quarter_name": dict_strings(
            quarter_seq - quarter_seq.min(),
            [f"{1900 + q // 4}Q{q % 4 + 1}"
             for q in range(int(quarter_seq.max() - quarter_seq.min()) + 1)]),
        "d_holiday": dict_strings((moy == 12) & (dom == 25), yes_no),
        "d_weekend": dict_strings(dow >= 5, yes_no),
        "d_following_holiday": dict_strings((moy == 12) & (dom == 26),
                                            yes_no),
        "d_first_dom": _i32(first_dom),
        "d_last_dom": _i32(last_dom),
        "d_same_day_ly": _i32(sk - 365),
        "d_same_day_lq": _i32(sk - 91),
        "d_current_day": dict_strings(np.zeros(rows, np.int8), yes_no),
        "d_current_week": dict_strings(np.zeros(rows, np.int8), yes_no),
        "d_current_month": dict_strings(np.zeros(rows, np.int8), yes_no),
        "d_current_quarter": dict_strings(np.zeros(rows, np.int8), yes_no),
        "d_current_year": dict_strings(np.zeros(rows, np.int8), yes_no),
    })


def item(rows: int, rng) -> pa.Table:
    key = np.arange(1, rows + 1, dtype=np.int64)
    category = rng.integers(1, len(CATEGORIES) + 1, rows)
    klass = rng.integers(1, len(CLASSES) + 1, rows)
    brand_no = rng.integers(1, 11, rows)
    manufact = rng.integers(1, 1001, rows)
    price = np.round(rng.uniform(0.09, 99.99, rows), 2)
    brands = [f"{a}{b} #{n}" for a in SYLLABLES for b in SYLLABLES
              for n in range(1, 11)]
    brand_code = (((klass - 1) % 10) * 10 + (category - 1)) * 10 \
        + brand_no - 1
    words = _word(np.arange(1001))
    start = rng.integers(EPOCH_FIRST + 35000, EPOCH_FIRST + 37000, rows)
    return pa.table({
        "i_item_sk": _i32(key),
        "i_item_id": _ids(rows),
        "i_rec_start_date": pa.array(start.astype(np.int32),
                                     type=pa.date32()),
        "i_rec_end_date": pa.array(
            (start + 1095).astype(np.int32), type=pa.date32(),
            mask=rng.random(rows) < 0.5),
        "i_item_desc": dict_strings(
            rng.integers(0, 1000, rows),
            [f"{COLORS[i % 24]} {CLASSES[i % 16]} for {CATEGORIES[i % 10]} "
             f"number {i}" for i in range(1000)]),
        "i_current_price": price,
        "i_wholesale_cost": np.round(price * rng.uniform(0.3, 0.9, rows), 2),
        "i_brand_id": _i32(category * 1_000_000 + klass * 1_000 + brand_no),
        "i_brand": dict_strings(brand_code, brands),
        "i_class_id": _i32(klass),
        "i_class": dict_strings(klass - 1, CLASSES),
        "i_category_id": _i32(category),
        "i_category": dict_strings(category - 1, CATEGORIES),
        "i_manufact_id": _i32(manufact),
        "i_manufact": dict_strings(manufact, words),
        "i_size": dict_strings(rng.integers(0, len(SIZES), rows), SIZES),
        "i_formulation": dict_strings(
            rng.integers(0, 1000, rows),
            [f"{i:05d}{COLORS[i % 24]}{i * 7919 % 100000:05d}"
             for i in range(1000)]),
        "i_color": dict_strings(rng.integers(0, len(COLORS), rows), COLORS),
        "i_units": dict_strings(rng.integers(0, len(UNITS), rows), UNITS),
        "i_container": dict_strings(np.zeros(rows, np.int8), ["Unknown"]),
        "i_manager_id": _i32(rng.integers(1, 101, rows)),
        "i_product_name": dict_strings(key % 1001, words),
    })


def store_sales(rows: int, items: int, rng) -> pa.Table:
    # a ticket is one customer's visit to one store: its lines share
    # date, time, customer, demographics, address and store
    ticket = np.arange(rows, dtype=np.int64) // TICKET_LINES + 1
    tickets = int(ticket[-1]) if rows else 0

    def per_ticket(lo: int, hi: int) -> np.ndarray:
        return rng.integers(lo, hi + 1, tickets)[ticket - 1]

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
        "ss_sold_date_sk": _nullable_i32(
            rng, per_ticket(SALES_FIRST, SALES_LAST)),
        "ss_sold_time_sk": _nullable_i32(rng, per_ticket(28800, 75599)),
        "ss_item_sk": _i32(rng.integers(1, items + 1, rows)),
        "ss_customer_sk": _nullable_i32(rng, per_ticket(1, CUSTOMERS)),
        "ss_cdemo_sk": _nullable_i32(rng, per_ticket(1, CDEMOS)),
        "ss_hdemo_sk": _nullable_i32(rng, per_ticket(1, HDEMOS)),
        "ss_addr_sk": _nullable_i32(rng, per_ticket(1, ADDRESSES)),
        "ss_store_sk": _nullable_i32(rng, per_ticket(1, STORES)),
        "ss_promo_sk": _nullable_i32(rng, rng.integers(1, PROMOS + 1, rows)),
        "ss_ticket_number": pa.array(ticket),
        "ss_quantity": _nullable_i32(rng, quantity),
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


def make(tables: dict, seed: int) -> dict:
    out = {}
    if "date_dim" in tables:
        out["date_dim"] = (date_dim(int(tables["date_dim"]["rows"])), True)
    items = int(tables.get("item", {}).get("rows", 18_000))
    if "item" in tables:
        out["item"] = (item(items, rng_for(seed, 1)), True)
    if "store_sales" in tables:
        out["store_sales"] = (store_sales(
            int(tables["store_sales"]["rows"]), items, rng_for(seed, 2)),
            STORE_SALES_DICT)
    return out
