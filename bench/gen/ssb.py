"""Star Schema Benchmark tables (O'Neil, O'Neil, Chen and Revilak, 2009).

Every table has the spec's columns, in the spec's order: lineorder 17,
customer 8, supplier 7, part 9, date 17.  The engine holds int32 columns
only, so a text column is a dictionary code (its value index) and a price
is in cents.

Row counts at scale factor SF: lineorder about 6,000,000 x SF (orders of
1 to 7 lines, cut at the count), customer 30,000 x SF, supplier 2,000 x SF,
part 200,000 x SF (linear here; the spec's ``floor(1 + log2 SF)`` factor is
below 1 under SF 1), date 2,556 days from 1992-01-01.  Keys are dense from
1; foreign keys are uniform over the referenced key range; an order's
lines share its customer, order date and priority, and ``lo_orderdate`` is
uniform over the order dates TPC-H allows (1992-01-01 to 1998-08-02), as
``d_datekey`` values (YYYYMMDD).  Prices follow TPC-H's formulas.
"""

from __future__ import annotations

import datetime

import numpy as np

FIRST_DAY = datetime.date(1992, 1, 1)
DATE_ROWS = 2556
LAST_ORDER_DAY = datetime.date(1998, 8, 2)
I32 = np.int32

# dictionary sizes of the text columns (spec / TPC-H value lists)
PRIORITIES, SHIPMODES, SEGMENTS = 5, 7, 5
COLORS, TYPES, CONTAINERS = 92, 150, 40
NATIONS, CITIES_PER_NATION = 25, 10


def _days(days: np.ndarray):
    base = np.datetime64(FIRST_DAY.isoformat(), "D")
    d = base + days.astype("timedelta64[D]")
    y = d.astype("datetime64[Y]")
    m = d.astype("datetime64[M]")
    year = y.astype(np.int64) + 1970
    month = (m - y.astype("datetime64[M]")).astype(np.int64) + 1
    day = (d - m.astype("datetime64[D]")).astype(np.int64) + 1
    return d, y, m, year, month, day


def datekeys(days: np.ndarray) -> np.ndarray:
    """YYYYMMDD keys of day offsets from 1992-01-01."""
    _, _, _, year, month, day = _days(days)
    return (year * 10000 + month * 100 + day).astype(I32)


def table_rows(sf: float) -> dict[str, int]:
    return {"lineorder": int(6_000_000 * sf), "customer": int(30_000 * sf),
            "supplier": int(2_000 * sf), "part": int(200_000 * sf),
            "date": DATE_ROWS}


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """TPC-H's P_RETAILPRICE of a part key, in cents."""
    pk = partkey.astype(np.int64)
    return 90_000 + (pk // 10) % 20_001 + 100 * (pk % 1_000)


def lineorder(sizes: dict[str, int],
              rng: np.random.Generator) -> dict[str, np.ndarray]:
    n = sizes["lineorder"]
    lines = rng.integers(1, 8, n)       # n orders of 1 line or more cover n
    ends = np.cumsum(lines)
    orders = int(np.searchsorted(ends, n)) + 1
    order = np.repeat(np.arange(orders), lines[:orders])[:n]
    first = np.concatenate([[0], ends[:orders - 1]])
    linenumber = np.arange(n) - first[order] + 1
    order_days = (LAST_ORDER_DAY - FIRST_DAY).days + 1
    o_day = rng.integers(0, order_days, orders)
    o_cust = rng.integers(1, sizes["customer"] + 1, orders)
    o_prio = rng.integers(0, PRIORITIES, orders)
    partkey = rng.integers(1, sizes["part"] + 1, n)
    quantity = rng.integers(1, 51, n)
    discount = rng.integers(0, 11, n)
    tax = rng.integers(0, 9, n)
    ext = quantity * retail_cents(partkey)
    charged = ext * (100 - discount) * (100 + tax) // 10_000
    total = np.bincount(order, weights=charged, minlength=orders)
    return {
        "lo_orderkey": (order + 1).astype(I32),
        "lo_linenumber": linenumber.astype(I32),
        "lo_custkey": o_cust[order].astype(I32),
        "lo_partkey": partkey.astype(I32),
        "lo_suppkey": rng.integers(1, sizes["supplier"] + 1, n).astype(I32),
        "lo_orderdate": datekeys(o_day[order]),
        "lo_orderpriority": o_prio[order].astype(I32),
        "lo_shippriority": np.zeros(n, I32),
        "lo_quantity": quantity.astype(I32),
        "lo_extendedprice": ext.astype(I32),
        "lo_ordtotalprice": total[order].astype(I32),
        "lo_discount": discount.astype(I32),
        "lo_revenue": (ext * (100 - discount) // 100).astype(I32),
        "lo_supplycost": (6 * retail_cents(partkey) // 10).astype(I32),
        "lo_tax": tax.astype(I32),
        "lo_commitdate": datekeys(o_day[order] + rng.integers(30, 91, n)),
        "lo_shipmode": rng.integers(0, SHIPMODES, n).astype(I32),
    }


def _party(prefix: str, key_name: str, n: int,
           rng: np.random.Generator) -> dict[str, np.ndarray]:
    """Customer or supplier: key, name, address, city, nation, region,
    phone (country code nation + 10, then a 7-digit number)."""
    key = np.arange(1, n + 1, dtype=I32)
    nation = rng.integers(0, NATIONS, n)
    return {
        key_name: key,
        f"{prefix}_name": key.copy(),
        f"{prefix}_address": rng.integers(0, 1 << 30, n).astype(I32),
        f"{prefix}_city": (nation * CITIES_PER_NATION
                           + rng.integers(0, CITIES_PER_NATION, n)).astype(I32),
        f"{prefix}_nation": nation.astype(I32),
        f"{prefix}_region": (nation // 5).astype(I32),
        f"{prefix}_phone": ((nation + 10) * 10_000_000
                            + rng.integers(0, 10_000_000, n)).astype(I32),
    }


def part(n: int, rng: np.random.Generator) -> dict[str, np.ndarray]:
    key = np.arange(1, n + 1, dtype=I32)
    mfgr = rng.integers(1, 6, n)
    category = mfgr * 10 + rng.integers(1, 6, n)       # MFGR#11 .. MFGR#55
    return {
        "p_partkey": key,
        "p_name": rng.integers(0, 1 << 30, n).astype(I32),
        "p_mfgr": mfgr.astype(I32),
        "p_category": category.astype(I32),
        "p_brand1": (category * 100 + rng.integers(1, 41, n)).astype(I32),
        "p_color": rng.integers(0, COLORS, n).astype(I32),
        "p_type": rng.integers(0, TYPES, n).astype(I32),
        "p_size": rng.integers(1, 51, n).astype(I32),
        "p_container": rng.integers(0, CONTAINERS, n).astype(I32),
    }


def date() -> dict[str, np.ndarray]:
    days = np.arange(DATE_ROWS)
    d, y, m, year, month, day = _days(days)
    dow = (d.astype(np.int64) + 4) % 7          # 1970-01-01 a Thursday; 0 = Sunday
    day_of_year = (d - y.astype("datetime64[D]")).astype(np.int64) + 1
    next_day = _days(days + 1)[5]
    season = np.select([np.isin(month, (12, 1)), np.isin(month, (2, 3, 4)),
                        np.isin(month, (5, 6, 7, 8)), np.isin(month, (9, 10))],
                       [0, 1, 2, 3], 4)                 # Christmas .. Fall
    key = datekeys(days)
    return {
        "d_datekey": key,
        "d_date": key.copy(),
        "d_dayofweek": dow.astype(I32),
        "d_month": month.astype(I32),
        "d_year": year.astype(I32),
        "d_yearmonthnum": (year * 100 + month).astype(I32),
        "d_yearmonth": (year * 100 + month).astype(I32),
        "d_daynuminweek": (dow + 1).astype(I32),
        "d_daynuminmonth": day.astype(I32),
        "d_daynuminyear": day_of_year.astype(I32),
        "d_monthnuminyear": month.astype(I32),
        "d_weeknuminyear": ((day_of_year - 1) // 7 + 1).astype(I32),
        "d_sellingseason": season.astype(I32),
        "d_lastdayinweekfl": (dow == 6).astype(I32),
        "d_lastdayinmonthfl": (next_day == 1).astype(I32),
        "d_holidayfl": (((month == 12) & (day == 25))
                        | ((month == 1) & (day == 1))).astype(I32),
        "d_weekdayfl": ((dow >= 1) & (dow <= 5)).astype(I32),
    }


def make(cfg: dict, rng: np.random.Generator) -> dict[str, dict[str, np.ndarray]]:
    """The five tables at ``cfg["sf"]``."""
    sizes = table_rows(cfg["sf"])
    customer = _party("c", "c_custkey", sizes["customer"], rng)
    customer["c_mktsegment"] = rng.integers(
        0, SEGMENTS, sizes["customer"]).astype(I32)
    return {
        "lineorder": lineorder(sizes, rng),
        "customer": customer,
        "supplier": _party("s", "s_suppkey", sizes["supplier"], rng),
        "part": part(sizes["part"], rng),
        "date": date(),
    }
