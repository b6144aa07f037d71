"""Plain reference for TPC-DS query 67: numpy and Python integers only.

Written from the statement's meaning, not from a plan:

- the joins: each ``store_sales`` row of the twelve months from
  ``d_month_seq`` = DMS whose item and store exist, with that day's
  ``d_year``, ``d_qoy``, ``d_moy``, the item's four strings (each may be
  null) and the store's ``s_store_id`` (a dimension's surrogate key is
  taken as unique, which the generator makes it; a business key is not);
- ``coalesce(ss_sales_price * ss_quantity, 0)``: the unscaled price times
  the quantity, at scale 2 (the generator writes no null price);
- ``ROLLUP`` of the eight keys: nine grouping levels, the level that keeps
  the first ``k`` keys summing over rows equal in those keys (strings as
  strings, not as an item's key; a null equal to a null), the other keys
  null; one lexicographic sort of the rows' key codes, and a level's
  groups are the runs of its first ``k`` codes. A genuine null key and a
  rolled-up one are told apart by their level, not by their value;
- ``rank() over (partition by i_category order by sumsales desc)``:
  within a category (the null category's partition holds the grand total
  and every group of items with a null category) one more than the
  number of rows with a larger sum, so ties share a rank and leave a gap;
- ``rk <= 100``, then the ten output columns ascending with nulls first
  (Spark's default), cut by ``LIMIT 100``.

A decimal in the answer is the pair ``(unscaled, scale)``. Imports nothing
of the engine.

Four controls, each the same statement with one stated guarantee broken;
the comparison has to refuse every one and no run of the benchmark calls
them (``benchmarks/tests/test_control.py`` finds them by their names, and
``test_q67_cell.py`` holds them at the cell's size):
``control_answer`` carries the sums in float32, the nearest precision
below the exact decimals that goes wrong here (a level's sum passes 2**24
unscaled); ``finest_only_control_answer`` groups by the eight keys without
``ROLLUP``; ``unpartitioned_rank_control_answer`` ranks every row in one
partition; ``nulls_last_control_answer`` orders with nulls last, which
moves the grand total and every subtotal.
"""

import numpy as np

LIMIT = 100
RANK_LIMIT = 100
SCALE = 2
KEYS = ("i_category", "i_class", "i_brand", "i_product_name", "d_year",
        "d_qoy", "d_moy", "s_store_id")


def _codes(values) -> tuple:
    """Dense codes in ascending order of ``values``, null first (code 0),
    and the values by code."""
    values = np.asarray(values)
    null = np.array([v is None for v in values.tolist()], dtype=bool)
    uniq, inverse = np.unique(values[~null], return_inverse=True)
    codes = np.zeros(len(values), np.int64)
    codes[~null] = inverse + 1
    return codes, [None] + list(uniq.tolist())


def joined(tables: dict, binding: dict) -> tuple:
    """The joined rows of the year: a matrix of the eight keys' codes
    (a row a fact row), each key's values by code, and the rows' amounts
    (unscaled, scale 2)."""
    date, store, item = tables["date_dim"], tables["store"], tables["item"]
    fact = tables["store_sales"]
    dms = binding["dms"]
    seq = np.asarray(date["d_month_seq"])
    in_year = (seq >= dms) & (seq <= dms + 11)

    def lookup(dim_sk, fact_sk, keep=None):
        dim_sk = np.asarray(dim_sk)
        idx = np.flatnonzero(keep) if keep is not None else \
            np.arange(len(dim_sk))
        order = idx[np.argsort(dim_sk[idx], kind="stable")]
        at = np.searchsorted(dim_sk[order], fact_sk)
        at = np.minimum(at, max(len(order) - 1, 0))
        hit = (dim_sk[order][at] == fact_sk) if len(order) else \
            np.zeros(len(fact_sk), bool)
        return order[at], hit

    d_at, d_hit = lookup(date["d_date_sk"], fact["ss_sold_date_sk"], in_year)
    i_at, i_hit = lookup(item["i_item_sk"], fact["ss_item_sk"])
    s_at, s_hit = lookup(store["s_store_sk"], fact["ss_store_sk"])
    rows = np.flatnonzero(d_hit & i_hit & s_hit)
    d_at, i_at, s_at = d_at[rows], i_at[rows], s_at[rows]
    columns = [np.asarray(item[k])[i_at] for k in KEYS[:4]]
    columns += [np.asarray(date[k])[d_at] for k in KEYS[4:7]]
    columns.append(np.asarray(store["s_store_id"])[s_at])
    codes, values = zip(*(_codes(c) for c in columns))
    amount = (np.asarray(fact["ss_sales_price"])[rows].astype(np.int64)
              * np.asarray(fact["ss_quantity"])[rows].astype(np.int64))
    return np.stack(codes, axis=1), list(values), amount


def levels(tables: dict, binding: dict, f32: bool = False,
           rollup: bool = True) -> list:
    """Every row of ``dw1``: ``[8 keys (None where rolled up), sumsales]``
    for each level, the finest first."""
    return [row for _, row in _levels(tables, binding, f32, rollup)]


def _levels(tables: dict, binding: dict, f32: bool, rollup: bool) -> list:
    """``levels``' rows, each beside the number of keys its level keeps."""
    codes, values, amount = joined(tables, binding)
    order = np.lexsort(codes.T[::-1])
    codes, amount = codes[order], amount[order]
    n = len(amount)
    out = []
    for k in (range(8, -1, -1) if rollup else (8,)):
        if n == 0:
            break
        starts = np.zeros(n, bool)
        starts[0] = True
        if k:
            starts[1:] = (codes[1:, :k] != codes[:-1, :k]).any(axis=1)
        first = np.flatnonzero(starts)
        if f32:
            sums = [int(v) for v in np.add.reduceat(
                amount.astype(np.float32), first, dtype=np.float32)]
        else:
            sums = np.add.reduceat(amount, first).tolist()
        for at, total in zip(first.tolist(), sums):
            key = [values[j][codes[at, j]] if j < k else None
                   for j in range(8)]
            out.append((k, key + [total]))
    return out


def level_groups(tables: dict, binding: dict) -> list:
    """Groups of each level by the keys it keeps: the grand total first,
    the finest last (nine numbers)."""
    counts = [0] * 9
    for k, _ in _levels(tables, binding, False, True):
        counts[k] += 1
    return counts


def ranked(rows: list, partitioned: bool = True) -> list:
    """``dw2``: each row with its rank by ``sumsales`` descending within
    its category: one more than the rows of its partition with a larger
    sum."""
    by_part: dict = {}
    for i, r in enumerate(rows):
        by_part.setdefault(r[0] if partitioned else "", []).append(i)
    rank = [0] * len(rows)
    for members in by_part.values():
        sums = np.array([rows[i][8] for i in members], dtype=np.int64)
        ordered = np.sort(sums)
        greater = len(sums) - np.searchsorted(ordered, sums, side="right")
        for i, g in zip(members, greater.tolist()):
            rank[i] = g + 1
    return [r + [k] for r, k in zip(rows, rank)]


def _order_key(row: list, nulls_first: bool):
    return tuple((0, None) if v is None and nulls_first
                 else (2, None) if v is None else (1, v) for v in row)


def answer(tables: dict, binding: dict, f32: bool = False,
           rollup: bool = True, partitioned: bool = True,
           nulls_first: bool = True) -> list:
    rows = ranked(levels(tables, binding, f32, rollup), partitioned)
    kept = [r for r in rows if r[9] <= RANK_LIMIT]
    kept.sort(key=lambda r: _order_key(r, nulls_first))
    return [tuple(r[:8]) + ((r[8], SCALE), r[9]) for r in kept[:LIMIT]]


def control_answer(tables: dict, binding: dict) -> list:
    return answer(tables, binding, f32=True)


def finest_only_control_answer(tables: dict, binding: dict) -> list:
    return answer(tables, binding, rollup=False)


def unpartitioned_rank_control_answer(tables: dict, binding: dict) -> list:
    return answer(tables, binding, partitioned=False)


def nulls_last_control_answer(tables: dict, binding: dict) -> list:
    return answer(tables, binding, nulls_first=False)
