"""Seeded star-schema slice for TPC-DS query 3, as numpy arrays.

Copied from ``bench.write_tpcds`` (PR 21) with the seed as a parameter and
the same order of draws: ``store_sales`` with uniform keys, money as the
unscaled int64 of ``decimal(7,2)``, drawn over that type's whole domain
(1.00 .. 99,999.99) so that a group's sum of two or three prices passes
2**24 unscaled and needs the exact ``decimal(17,2)`` the configuration
guarantees (``reference.control_answer``). Imports nothing of the engine.
"""

import numpy as np


def generate(seed: int, rows: dict) -> dict:
    """``rows`` maps table name to row count; returns table -> column -> array."""
    rng = np.random.default_rng(seed)
    n_item, n_date, n = rows["item"], rows["date_dim"], rows["store_sales"]
    item = {
        "i_item_sk": np.arange(1, n_item + 1),
        "i_brand_id": rng.integers(1, 1000, n_item).astype(np.int32),
        "i_brand": np.array([f"brand#{i % 997:03d}" for i in range(n_item)]),
        "i_manufact_id": rng.integers(1, 1001, n_item).astype(np.int32),
    }
    day = np.arange(n_date)
    date_dim = {
        "d_date_sk": np.arange(1, n_date + 1),
        "d_year": (1998 + (day // 365) % 7).astype(np.int32),
        "d_moy": (1 + (day // 30) % 12).astype(np.int32),
    }
    store_sales = {
        "ss_sold_date_sk": rng.integers(1, n_date + 1, n),
        "ss_item_sk": rng.integers(1, n_item + 1, n),
        "ss_ext_sales_price": rng.integers(100, 10_000_000, n),
    }
    return {"item": item, "date_dim": date_dim, "store_sales": store_sales}
