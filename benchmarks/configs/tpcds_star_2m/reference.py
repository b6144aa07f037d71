"""Plain reference for the TPC-DS query 3 star join: numpy and Python integers.

The two joins are lookups by surrogate key (``d_date_sk`` and ``i_item_sk``
run from 1 without gaps, as the generator makes them and as ``answer``
checks); the aggregate and the TopN are a dictionary and a sort. A decimal in
the answer is the pair ``(unscaled, scale)``. Imports nothing of the engine.
"""

import numpy as np

LIMIT = 100


def _dense_keys(keys: np.ndarray) -> None:
    if not np.array_equal(keys, np.arange(1, len(keys) + 1)):
        raise ValueError("the reference looks dimensions up by position: "
                         "their keys must run 1..n in order")


def answer(tables: dict, binding: dict) -> list:
    item, date, fact = tables["item"], tables["date_dim"], tables["store_sales"]
    _dense_keys(item["i_item_sk"])
    _dense_keys(date["d_date_sk"])
    item_ok = item["i_manufact_id"] == binding["manufact_id"]
    date_ok = date["d_moy"] == binding["moy"]
    item_at = fact["ss_item_sk"] - 1
    date_at = fact["ss_sold_date_sk"] - 1
    hit = np.flatnonzero(item_ok[item_at] & date_ok[date_at])
    sums = {}
    for year, brand_id, brand, price in zip(
            date["d_year"][date_at[hit]].tolist(),
            item["i_brand_id"][item_at[hit]].tolist(),
            item["i_brand"][item_at[hit]].tolist(),
            fact["ss_ext_sales_price"][hit].tolist()):
        key = (year, brand_id, brand)
        sums[key] = sums.get(key, 0) + price
    # brand breaks a tie the statement leaves open; none occurs in practice
    ordered = sorted(sums.items(),
                     key=lambda kv: (kv[0][0], -kv[1], kv[0][1], kv[0][2]))
    return [(year, brand_id, brand, (total, 2))
            for (year, brand_id, brand), total in ordered[:LIMIT]]
