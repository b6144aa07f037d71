"""Seeded tables for TPC-DS query 51 at scale factor 1, as numpy arrays.

``store_sales`` and ``web_sales`` keep ``tpcds_star_2m/generator.py``'s
shapes where they meet: fact keys uniform, money as the unscaled int64 of
``decimal(7,2)`` drawn over that type's whole domain (1.00 .. 99,999.99), so
that an item's running sum of a few days passes 2**24 unscaled and needs the
exact decimals the configuration guarantees (``reference.control_answer``).

New here: ``date_dim`` is the specification's calendar. ``d_date_sk``
2415022 is 1900-01-02 and there is one row a day for 73,049 days; ``d_date``
is that day, as days since 1970-01-01; ``d_month_seq`` counts the months
since January 1900 (January 2000 is 1200). Both facts' sold dates are uniform
over the five years of sales, 1998-01-02 .. 2003-01-02 (``d_date_sk``
2450816 .. 2452642); their items over the 18,000 keys of ``item``, which the
statement does not read (``rows["item"]`` where a test asks for fewer).
Imports nothing of the engine.
"""

import numpy as np

FIRST_DATE_SK = 2415022                  # 1900-01-02
FIRST_DATE = np.datetime64("1900-01-02")
SOLD_FROM_SK, SOLD_TO_SK = 2450816, 2452642   # 1998-01-02 .. 2003-01-02
ITEMS = 18_000


def calendar(n_date: int) -> dict:
    days = FIRST_DATE + np.arange(n_date)
    months = days.astype("datetime64[M]").astype(np.int64)   # since 1970-01
    return {
        "d_date_sk": FIRST_DATE_SK + np.arange(n_date),
        "d_date": days.astype("datetime64[D]").astype(np.int64).astype(np.int32),
        "d_month_seq": (months + 70 * 12).astype(np.int32),
    }


def _sales(rng, prefix: str, n: int, items: int) -> dict:
    return {
        f"{prefix}_sold_date_sk": rng.integers(SOLD_FROM_SK, SOLD_TO_SK + 1, n),
        f"{prefix}_item_sk": rng.integers(1, items + 1, n),
        f"{prefix}_sales_price": rng.integers(100, 10_000_000, n),
    }


def generate(seed: int, rows: dict) -> dict:
    """``rows`` maps table name to row count; returns table -> column -> array."""
    rng = np.random.default_rng(seed)
    items = rows.get("item", ITEMS)
    return {"date_dim": calendar(rows["date_dim"]),
            "store_sales": _sales(rng, "ss", rows["store_sales"], items),
            "web_sales": _sales(rng, "ws", rows["web_sales"], items)}
