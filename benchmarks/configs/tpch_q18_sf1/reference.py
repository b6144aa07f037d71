"""Plain reference for TPC-H Q18: numpy and Python integers only.

The subquery is one integer sum per ``l_orderkey``; the orders it keeps are
few, so the joins and the outer aggregate are a dictionary loop over them
that assumes nothing of the keys (an order key or a customer key met twice
joins twice, as SQL says). A decimal in the answer is the pair ``(unscaled,
scale)``: ``o_totalprice`` is ``decimal(15,2)``, ``sum(l_quantity)``
``decimal(25,2)``. ``o_orderdate`` is the integer the table holds (days since
1970-01-01). Rows come in ``o_totalprice DESC, o_orderdate`` order, cut by
``LIMIT 100``; rows that tie on both are further ordered by their other
columns, which the statement leaves open (none has occurred: prices are drawn
over 55 million values). Imports nothing of the engine.

Two controls, each the same statement with one stated guarantee broken; the
comparison has to refuse both and no run of the benchmark calls them.
``control_answer`` carries ``o_totalprice`` through float32, the nearest
precision below the exact ``decimal(15,2)``: unscaled prices pass 2**24, so
rows differ and may change places (``benchmarks/tests/test_control.py``
finds it by its name). ``having_or_equal_answer`` reads ``>=`` for the
subquery's ``>``: an order whose lines sum to exactly the threshold gets in.
It adds rows, and ``test_control.py`` asks a control for as many rows as the
answer has, so its name keeps it out of that test's list and
``benchmarks/tests/test_q18_cell.py`` holds it to ``not correct`` instead.
"""

import numpy as np

LIMIT = 100


def order_quantities(lineitem: dict):
    """``(keys, sums)``: the distinct ``l_orderkey`` values and the exact
    integer sum of ``l_quantity`` (unscaled) of each."""
    keys, at = np.unique(lineitem["l_orderkey"], return_inverse=True)
    sums = np.zeros(len(keys), dtype=np.int64)
    np.add.at(sums, at, lineitem["l_quantity"].astype(np.int64))
    return keys, sums


def _exact(price: int) -> int:
    return price


def _float32(price: int) -> int:
    return int(np.float32(price))


def control_answer(tables: dict, binding: dict) -> list:
    return answer(tables, binding, price=_float32)


def having_or_equal_answer(tables: dict, binding: dict) -> list:
    return answer(tables, binding, or_equal=True)


def answer(tables: dict, binding: dict, price=_exact,
           or_equal: bool = False) -> list:
    customer, orders, lineitem = (tables["customer"], tables["orders"],
                                  tables["lineitem"])
    threshold = int(binding["quantity"]) * 100          # scale 2
    keys, sums = order_quantities(lineitem)
    large = keys[sums >= threshold if or_equal else sums > threshold]
    order_rows = np.flatnonzero(np.isin(orders["o_orderkey"], large))
    line_rows = np.flatnonzero(np.isin(lineitem["l_orderkey"], large))
    lines = {}
    for key, qty in zip(lineitem["l_orderkey"][line_rows].tolist(),
                        lineitem["l_quantity"][line_rows].tolist()):
        lines.setdefault(key, []).append(qty)
    wanted = set(orders["o_custkey"][order_rows].tolist())
    names = {}
    for key, name in zip(customer["c_custkey"].tolist(),
                         customer["c_name"].tolist()):
        if key in wanted:
            names.setdefault(key, []).append(str(name))
    groups = {}
    for i in order_rows.tolist():
        okey, ckey = int(orders["o_orderkey"][i]), int(orders["o_custkey"][i])
        for name in names.get(ckey, []):
            group = (name, ckey, okey, int(orders["o_orderdate"][i]),
                     price(int(orders["o_totalprice"][i])))
            for qty in lines.get(okey, []):
                groups[group] = groups.get(group, 0) + qty
    ordered = sorted(groups.items(),
                     key=lambda kv: (-kv[0][4], kv[0][3], kv[0][:3]))
    return [(name, ckey, okey, date, (total, 2), (qty, 2))
            for (name, ckey, okey, date, total), qty in ordered[:LIMIT]]
