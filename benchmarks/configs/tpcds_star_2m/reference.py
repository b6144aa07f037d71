"""Plain reference for the TPC-DS query 3 star join: numpy and Python integers.

The two joins are lookups by surrogate key (``d_date_sk`` and ``i_item_sk``
run from 1 without gaps, as the generator makes them and as ``answer``
checks); the aggregate and the TopN are a dictionary and a sort. A decimal in
the answer is the pair ``(unscaled, scale)``. Imports nothing of the engine.

``control_answer`` is the same statement in the nearest precision below the
exact ``decimal(17,2)`` that can go wrong here: each group's sum carried in
float32 over the unscaled prices, what an accumulator narrower than the
emulated 64-bit integers of the chip would tempt a later PR to take (float64
holds every sum of this size exactly). Prices are drawn over the whole domain
of ``decimal(7,2)``, so two or three of them pass 2**24 and some sum of every
seed comes out wrong. ``order_control_answer`` breaks another stated
guarantee, the order of the rows under the ``LIMIT``: it sorts by year and
brand and leaves ``sum_agg DESC`` out. The comparison has to refuse both
(``benchmarks/tests/test_control.py``); no run of the benchmark calls them.
"""

import numpy as np

LIMIT = 100


def _dense_keys(keys: np.ndarray) -> None:
    if not np.array_equal(keys, np.arange(1, len(keys) + 1)):
        raise ValueError("the reference looks dimensions up by position: "
                         "their keys must run 1..n in order")


def _exact_add(total, price: int):
    return total + price


def _float32_add(total, price: int):
    return np.float32(total) + np.float32(price)


def control_answer(tables: dict, binding: dict) -> list:
    return answer(tables, binding, add=_float32_add)


def order_control_answer(tables: dict, binding: dict) -> list:
    return answer(tables, binding, by_sum=False)


def answer(tables: dict, binding: dict, by_sum: bool = True,
           add=_exact_add) -> list:
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
        sums[key] = add(sums.get(key, 0), price)
    sums = {key: int(total) for key, total in sums.items()}
    # brand breaks a tie the statement leaves open; none occurs in practice
    ordered = sorted(sums.items(),
                     key=lambda kv: (kv[0][0], -kv[1] if by_sum else 0,
                                     kv[0][1], kv[0][2]))
    return [(year, brand_id, brand, (total, 2))
            for (year, brand_id, brand), total in ordered[:LIMIT]]
