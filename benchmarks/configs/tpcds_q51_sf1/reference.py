"""Plain reference for TPC-DS query 51: numpy and Python integers only.

Written from the statement's meaning, not from a plan:

- ``web_v1`` / ``store_v1``: the channel's sales of the twelve months from
  ``d_month_seq`` = DMS, summed per ``(item, day)`` in a dictionary; then,
  per item, a loop over its days in date order carries the running sum.
- the full outer join: one row per ``(item, day)`` that either channel has,
  the missing side ``None``.
- the second window: per item, a loop over the days in date order carries
  the greatest running sum seen so far of each channel; a ``None`` is
  skipped, and before the first value the maximum is ``None``.
- the filter keeps a row where both maxima exist and the web's is greater;
  the rows come in ``item_sk, d_date`` order (total: the pair is the join's
  key) cut by ``LIMIT 100``.

The join with ``date_dim`` is a dictionary that assumes nothing of the keys
(a ``d_date_sk`` met twice joins twice). A decimal in the answer is the pair
``(unscaled, scale)``; ``d_date`` is the day as the table holds it. Imports
nothing of the engine.

Three controls, each the same statement with one stated guarantee broken;
the comparison has to refuse every one and no run of the benchmark calls
them. ``control_answer`` carries the sums in float32 over the unscaled
prices, the nearest precision below the exact decimals that goes wrong here
(a running sum of a few days passes 2**24; float64 would hold every sum of
this size). ``outer_control_answer`` reads the full outer join as an inner join. Both
still give 100 rows at the cell's size, and
``benchmarks/tests/test_control.py`` finds them by their names.
``whole_frame_answer`` reads every window over the whole partition, not up
to the current row: an item's rows then pass the filter all or none, and
only where its web sales of the year exceed its store sales, some 80 rows in
all. ``test_control.py`` asks a control for as many rows as the answer has,
so its name keeps it out of that test's list and
``benchmarks/tests/test_q51_cell.py`` holds it to ``not correct`` instead
(ISSUE 36 calls it ``frame_control_answer``).
"""

import numpy as np

LIMIT = 100
SCALE = 2


def _exact_add(total, price: int):
    return total + price


def _float32_add(total, price: int):
    return np.float32(total) + np.float32(price)


def _channel(fact: dict, prefix: str, days_of_sk: dict, add, running: bool
             ) -> dict:
    """``(item, day) -> cume_sales`` of one channel's CTE."""
    sold, item, price = (fact[f"{prefix}_sold_date_sk"],
                         fact[f"{prefix}_item_sk"],
                         fact[f"{prefix}_sales_price"])
    hit = np.flatnonzero(np.isin(sold, list(days_of_sk)))
    sums: dict = {}
    for sk, it, pr in zip(sold[hit].tolist(), item[hit].tolist(),
                          price[hit].tolist()):
        for day in days_of_sk[sk]:
            key = (it, day)
            sums[key] = add(sums.get(key, 0), pr)
    by_item: dict = {}
    for (it, day), total in sums.items():
        by_item.setdefault(it, []).append((day, total))
    cume: dict = {}
    for it, days in by_item.items():
        days.sort()
        total = 0
        for day, s in days:
            total = add(total, s)
            cume[(it, day)] = total
        if not running:
            for day, _ in days:
                cume[(it, day)] = total
    return {key: int(v) for key, v in cume.items()}


def _greater(best, v):
    return v if best is None or (v is not None and v > best) else best


def control_answer(tables: dict, binding: dict) -> list:
    return answer(tables, binding, add=_float32_add)


def whole_frame_answer(tables: dict, binding: dict) -> list:
    return answer(tables, binding, running=False)


def outer_control_answer(tables: dict, binding: dict) -> list:
    return answer(tables, binding, outer=False)


def _row(row: list) -> tuple:
    return tuple(row[:2]) + tuple(
        None if v is None else (v, SCALE) for v in row[2:])


def relation(tables: dict, binding: dict, add=_exact_add,
             running: bool = True, outer: bool = True):
    """Every row of ``y``, the second window's output before the filter, an
    item's rows at a time, in ``item_sk, d_date`` order."""
    date = tables["date_dim"]
    dms = binding["dms"]
    in_year = (date["d_month_seq"] >= dms) & (date["d_month_seq"] <= dms + 11)
    days_of_sk: dict = {}
    for sk, day in zip(date["d_date_sk"][in_year].tolist(),
                       date["d_date"][in_year].tolist()):
        days_of_sk.setdefault(sk, []).append(day)
    web = _channel(tables["web_sales"], "ws", days_of_sk, add, running)
    store = _channel(tables["store_sales"], "ss", days_of_sk, add, running)
    keys = set(web) | set(store) if outer else set(web) & set(store)
    by_item: dict = {}
    for it, day in keys:
        by_item.setdefault(it, []).append(day)
    for it in sorted(by_item):
        rows, web_best, store_best = [], None, None
        for day in sorted(by_item[it]):
            w, s = web.get((it, day)), store.get((it, day))
            web_best, store_best = _greater(web_best, w), _greater(store_best, s)
            rows.append([it, day, w, s, web_best, store_best])
        if not running:
            for row in rows:
                row[4], row[5] = web_best, store_best
        yield [_row(row) for row in rows]


def whole_relation(tables: dict, binding: dict) -> list:
    """``y`` without the statement's WHERE, ORDER BY and LIMIT: some 683
    thousand rows at the cell's size, which
    ``docs/profiles/pr36/chip_full_relation.py`` holds the engine's windows
    and full outer join to on the chip (``correct`` sees the first 100 rows
    of the filter, a dozen items of 18,000)."""
    return [row for rows in relation(tables, binding) for row in rows]


def answer(tables: dict, binding: dict, add=_exact_add, running: bool = True,
           outer: bool = True) -> list:
    out = []
    for rows in relation(tables, binding, add, running, outer):
        out.extend(r for r in rows if r[4] is not None and r[5] is not None
                   and r[4][0] > r[5][0])
        if len(out) >= LIMIT:
            break
    return out[:LIMIT]
