"""Seeded SF1-shaped ``lineitem``: the 7 columns Q1 reads, as numpy arrays.

Copied from ``bench.make_lineitem`` (PR 21) with the seed as a parameter:
uniform draws over dbgen's 4.2.2.13 domains, money columns as the unscaled
int64 of ``decimal(15,2)``, dates as days since 1970-01-01. Imports nothing
of the engine, so the plain reference reads the very arrays that are written.
"""

import numpy as np

_EPOCH = np.datetime64("1970-01-01")
SHIPDATE_LO = int((np.datetime64("1992-01-02") - _EPOCH).astype(int))
SHIPDATE_HI = int((np.datetime64("1998-12-01") - _EPOCH).astype(int))


def generate(seed: int, rows: dict) -> dict:
    """``rows`` maps table name to row count; returns table -> column -> array."""
    n = rows["lineitem"]
    rng = np.random.default_rng(seed)
    quantity = rng.integers(1, 51, n) * 100           # 1.00 .. 50.00
    extendedprice = rng.integers(90100, 10494951, n)  # 901.00 .. 104949.50
    discount = rng.integers(0, 11, n)                 # 0.00 .. 0.10
    tax = rng.integers(0, 9, n)                       # 0.00 .. 0.08
    returnflag = np.array(["A", "N", "R"])[rng.integers(0, 3, n)]
    linestatus = np.array(["O", "F"])[rng.integers(0, 2, n)]
    shipdate = rng.integers(SHIPDATE_LO, SHIPDATE_HI + 1, n).astype(np.int32)
    return {"lineitem": {
        "l_quantity": quantity,
        "l_extendedprice": extendedprice,
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": returnflag,
        "l_linestatus": linestatus,
        "l_shipdate": shipdate,
    }}
