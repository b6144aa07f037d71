"""Plain reference for TPC-H Q21: numpy and Python integers only.

Written from the statement's meaning. A line ``l1`` waits where it was
received after its commit date and, among the lines of its order,

- EXISTS: some line has another supplier. That holds for every line of the
  order or for none: where the order's lines have two suppliers or more;
- NOT EXISTS: no line of another supplier was late too. ``l1`` is itself a
  late line of the order, so this holds exactly where all the order's late
  lines have one supplier (``l1``'s own).

Both are decided per order from the minimum and maximum ``l_suppkey`` over
its lines and over its late lines, whatever the lines' place in the table.
The joins are dictionaries that assume nothing of the keys: an order key
met twice among the ``F`` orders, or a supplier key met twice, joins twice,
as SQL says. Rows come in ``numwait DESC, s_name`` order, which is total
(``s_name`` is the group), cut by ``LIMIT 100``. Imports nothing of the engine.

Two controls, each the same statement with one stated guarantee broken; the
comparison has to refuse both and no run of the benchmark calls them. Q21 has
no arithmetic whose precision could be lowered (its one number is a count),
so the controls break the guarantee this configuration adds, the residual
condition of each subquery. ``control_answer`` leaves ``l2.l_suppkey <>
l1.l_suppkey`` out of the EXISTS, which then holds for every line (the line
is its own partner): still 100 rows, with other counts
(``benchmarks/tests/test_control.py`` finds it by its name).
``not_exists_any_other_answer`` takes ``l3.l_receiptdate > l3.l_commitdate``
away from the NOT EXISTS: an order then has to have another supplier and no
other supplier at once, and no row is left. ``test_control.py`` asks a
control for as many rows as the answer has, so its name keeps it out of that
test's list and ``benchmarks/tests/test_q21_cell.py`` holds it to ``not
correct`` instead.
"""

import numpy as np

LIMIT = 100


def _per_order_extremes(group: np.ndarray, groups: int, suppkey: np.ndarray):
    """``(min, max)`` of ``suppkey`` per order; an order with none of the
    lines given reads ``(max int, min int)``, which are never equal."""
    info = np.iinfo(np.int64)
    low = np.full(groups, info.max, dtype=np.int64)
    high = np.full(groups, info.min, dtype=np.int64)
    np.minimum.at(low, group, suppkey)
    np.maximum.at(high, group, suppkey)
    return low, high


def waiting_lines(lineitem: dict, exists_another_supplier: bool = True,
                  others_late_only: bool = True) -> np.ndarray:
    """Row numbers of the ``l1`` lines the three ``lineitem`` predicates keep."""
    keys, group = np.unique(lineitem["l_orderkey"], return_inverse=True)
    suppkey = lineitem["l_suppkey"].astype(np.int64)
    late = lineitem["l_receiptdate"] > lineitem["l_commitdate"]
    low, high = _per_order_extremes(group, len(keys), suppkey)
    late_low, late_high = _per_order_extremes(group[late], len(keys),
                                              suppkey[late])
    keep = late.copy()
    if exists_another_supplier:
        keep &= (low != high)[group]
    if others_late_only:
        keep &= (late_low == late_high)[group]
    else:
        keep &= (low == high)[group]
    return np.flatnonzero(keep)


def control_answer(tables: dict, binding: dict) -> list:
    return answer(tables, binding, exists_another_supplier=False)


def not_exists_any_other_answer(tables: dict, binding: dict) -> list:
    return answer(tables, binding, others_late_only=False)


def answer(tables: dict, binding: dict, exists_another_supplier: bool = True,
           others_late_only: bool = True) -> list:
    lineitem, orders, supplier, nation = (
        tables["lineitem"], tables["orders"], tables["supplier"],
        tables["nation"])
    nations = set(nation["n_nationkey"][
        nation["n_name"] == binding["nation"]].tolist())
    names: dict = {}
    for key, name, nkey in zip(supplier["s_suppkey"].tolist(),
                               supplier["s_name"].tolist(),
                               supplier["s_nationkey"].tolist()):
        # a nation key met twice in ``nation`` would join twice as well
        names.setdefault(key, []).extend(
            [str(name)] * sum(1 for n in nations if n == nkey))
    rows = waiting_lines(lineitem, exists_another_supplier, others_late_only)
    rows = rows[np.isin(lineitem["l_suppkey"][rows],
                        [k for k, v in names.items() if v])]
    finished: dict = {}
    wanted = set(lineitem["l_orderkey"][rows].tolist())
    for key in orders["o_orderkey"][orders["o_orderstatus"] == "F"].tolist():
        if key in wanted:
            finished[key] = finished.get(key, 0) + 1
    numwait: dict = {}
    for okey, skey in zip(lineitem["l_orderkey"][rows].tolist(),
                          lineitem["l_suppkey"][rows].tolist()):
        for name in names[skey]:
            numwait[name] = numwait.get(name, 0) + finished.get(okey, 0)
    ordered = sorted(((n, c) for n, c in numwait.items() if c),
                     key=lambda nc: (-nc[1], nc[0]))
    return ordered[:LIMIT]
