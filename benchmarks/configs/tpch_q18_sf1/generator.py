"""Seeded SF1-shaped ``customer``, ``orders`` and ``lineitem``: the 8 columns
TPC-H Q18 reads, as numpy arrays, in dbgen's shapes (clause 4.2.3):

- ``o_orderkey`` sparse, the first 8 of every 32 keys (dbgen's ``mk_sparse``);
- ``o_custkey`` uniform over the customers whose key is no multiple of 3;
- 1 to 7 lines an order, ``lineitem`` in ``l_orderkey`` order, so that an
  order's lines lie together in one file as dbgen writes them;
- ``l_quantity`` 1 to 50, ``c_name`` ``Customer#%09d``, ``o_orderdate``
  uniform over STARTDATE .. ENDDATE - 151 days.

Assumed (``config.json`` says why): the line counts are drawn uniformly and
then corrected, one line at a time on orders drawn from the seed, so that
``lineitem`` has exactly the rows asked for (6,001,215 at scale factor 1) for
every seed; ``o_totalprice`` is uniform over the range dbgen's formula
reaches and not computed from columns Q18 does not read. Money is the
unscaled int64 of ``decimal(15,2)``, dates are days since 1970-01-01.
Imports nothing of the engine.
"""

import numpy as np

_EPOCH = np.datetime64("1970-01-01")
ORDERDATE_LO = int((np.datetime64("1992-01-01") - _EPOCH).astype(int))
ORDERDATE_HI = int((np.datetime64("1998-08-02") - _EPOCH).astype(int))
TOTALPRICE_LO, TOTALPRICE_HI = 85_771, 55_528_516   # 857.71 .. 555,285.16
MAX_LINES = 7


def _line_counts(rng, orders: int, lines: int) -> np.ndarray:
    """1..7 lines an order, uniform, then corrected to sum to ``lines``."""
    if not orders <= lines <= MAX_LINES * orders:
        raise ValueError(f"{lines} lines cannot be spread over {orders} "
                         f"orders of 1 to {MAX_LINES} lines")
    counts = rng.integers(1, MAX_LINES + 1, orders)
    while True:
        short = lines - int(counts.sum())
        if short == 0:
            return counts
        step = 1 if short > 0 else -1
        room = np.flatnonzero(counts < MAX_LINES if step > 0 else counts > 1)
        counts[rng.choice(room, min(abs(short), len(room)),
                          replace=False)] += step


def generate(seed: int, rows: dict) -> dict:
    """``rows`` maps table name to row count; returns table -> column -> array."""
    rng = np.random.default_rng(seed)
    n_cust, n_ord, n_line = rows["customer"], rows["orders"], rows["lineitem"]
    custkey = np.arange(1, n_cust + 1)
    customer = {
        "c_custkey": custkey,
        "c_name": np.array([f"Customer#{k:09d}" for k in custkey.tolist()]),
    }
    seq = np.arange(1, n_ord + 1)
    not_third = custkey[custkey % 3 != 0]
    orders = {
        "o_orderkey": ((seq >> 3) << 5) | (seq & 7),
        "o_custkey": not_third[rng.integers(0, len(not_third), n_ord)],
        "o_totalprice": rng.integers(TOTALPRICE_LO, TOTALPRICE_HI + 1, n_ord),
        "o_orderdate": rng.integers(ORDERDATE_LO, ORDERDATE_HI + 1,
                                    n_ord).astype(np.int32),
    }
    counts = _line_counts(rng, n_ord, n_line)
    lineitem = {
        "l_orderkey": np.repeat(orders["o_orderkey"], counts),
        "l_quantity": rng.integers(1, 51, n_line) * 100,   # 1.00 .. 50.00
    }
    return {"customer": customer, "orders": orders, "lineitem": lineitem}
