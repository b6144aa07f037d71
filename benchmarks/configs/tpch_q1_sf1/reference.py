"""Plain reference for TPC-H Q1: numpy masks and Python integers only.

Works on the arrays the generator made (money as unscaled integers of scale
2). A decimal in the answer is the pair ``(unscaled, scale)``; the result
types are those Spark defines for ``sum`` and ``avg`` over decimals, which the
benchmark's tests hold against the CPU engine. Imports nothing of the engine.

``control_answer`` is the same statement with one stated guarantee broken,
exact decimal arithmetic: sums carried in float64, the step the conf's
``variableFloatAgg`` would tempt a later PR to take. The comparison has to
refuse it (``benchmarks/tests/test_control.py``); no run of the benchmark
calls it.
"""

import numpy as np

CUTOFF = int((np.datetime64("1998-09-02") - np.datetime64("1970-01-01")).astype(int))
_CHUNK = 1 << 20  # sums are carried in Python integers, chunk by chunk


def _exact_sum(values: np.ndarray) -> int:
    """Sum of an int64 array as a Python integer. A chunk of 2**20 products
    below 2**41 cannot wrap int64; the total is unbounded."""
    return sum(int(values[i:i + _CHUNK].sum())
               for i in range(0, len(values), _CHUNK))


def _avg_half_up(total: int, count: int, shift: int) -> int:
    """``total * 10**shift / count`` rounded half up (totals here are >= 0)."""
    num = total * 10 ** shift
    return (2 * num + count) // (2 * count)


def _float64_sum(values: np.ndarray) -> int:
    return int(values.astype(np.float64).sum())


def control_answer(tables: dict, binding: dict) -> list:
    return answer(tables, binding, total=_float64_sum)


def answer(tables: dict, binding: dict, total=_exact_sum) -> list:
    t = tables["lineitem"]
    keep = t["l_shipdate"] <= CUTOFF
    qty = t["l_quantity"].astype(np.int64)
    price = t["l_extendedprice"].astype(np.int64)
    disc = t["l_discount"].astype(np.int64)
    tax = t["l_tax"].astype(np.int64)
    disc_price = price * (100 - disc)        # scale 4
    charge = disc_price * (100 + tax)        # scale 6
    out = []
    for flag in sorted(set(t["l_returnflag"].tolist())):
        in_flag = keep & (t["l_returnflag"] == flag)
        for status in sorted(set(t["l_linestatus"].tolist())):
            m = in_flag & (t["l_linestatus"] == status)
            count = int(m.sum())
            if count == 0:
                continue
            s_qty, s_price = total(qty[m]), total(price[m])
            out.append((
                str(flag), str(status),
                (s_qty, 2), (s_price, 2),
                (total(disc_price[m]), 4),
                (total(charge[m]), 6),
                (_avg_half_up(s_qty, count, 4), 6),
                (_avg_half_up(s_price, count, 4), 6),
                (_avg_half_up(total(disc[m]), count, 4), 6),
                count))
    return out
