"""Seeded SF1-shaped ``lineitem``, ``orders``, ``supplier`` and ``nation``: the
11 columns TPC-H Q21 reads, as numpy arrays, in dbgen's shapes (clause 4.2.3):

- ``o_orderkey`` sparse, the first 8 of every 32 keys (dbgen's ``mk_sparse``);
- 1 to 7 lines an order, ``lineitem`` in ``l_orderkey`` order, so that an
  order's lines lie together in one file as dbgen writes them;
- ``o_orderdate`` uniform over STARTDATE .. ENDDATE - 151 days;
  ``l_shipdate = o_orderdate + [1, 121]``, ``l_commitdate = o_orderdate +
  [30, 90]``, ``l_receiptdate = l_shipdate + [1, 30]``, so that about 63% of
  the lines are received after their commit date;
- ``l_linestatus`` ``F`` for a line shipped by CURRENTDATE (1995-06-17), else
  ``O``; ``o_orderstatus`` ``F`` where all of the order's lines are ``F``,
  ``O`` where all are ``O``, else ``P`` (about 49% ``F``);
- ``s_name`` ``Supplier#%09d``, ``s_nationkey`` uniform over the 25 nations,
  which carry their specified names under their specified keys.

Assumed (``config.json`` says why): the line counts are drawn uniformly and
then corrected, one line at a time on orders drawn from the seed, so that
``lineitem`` has exactly the rows asked for (6,001,215 at scale factor 1) for
every seed; ``l_suppkey`` is uniform over the suppliers in place of dbgen's
part-supplier formula. Only the columns the statement reads are returned
(``l_shipdate``, ``l_linestatus`` and ``o_orderdate`` are drawn to derive
them). Dates are days since 1970-01-01. Imports nothing of the engine.
"""

import numpy as np

_EPOCH = np.datetime64("1970-01-01")


def _days(date: str) -> int:
    return int((np.datetime64(date) - _EPOCH).astype(int))


ORDERDATE_LO, ORDERDATE_HI = _days("1992-01-01"), _days("1998-08-02")
CURRENTDATE = _days("1995-06-17")
MAX_LINES = 7
NATIONS = (
    "ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA", "FRANCE",
    "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ", "JAPAN", "JORDAN", "KENYA",
    "MOROCCO", "MOZAMBIQUE", "PERU", "CHINA", "ROMANIA", "SAUDI ARABIA",
    "VIETNAM", "RUSSIA", "UNITED KINGDOM", "UNITED STATES")


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
    n_supp, n_ord, n_line = rows["supplier"], rows["orders"], rows["lineitem"]
    if rows["nation"] != len(NATIONS):
        raise ValueError(f"nation has {len(NATIONS)} rows at every scale")
    nation = {"n_nationkey": np.arange(len(NATIONS)),
              "n_name": np.array(NATIONS)}
    suppkey = np.arange(1, n_supp + 1)
    supplier = {
        "s_suppkey": suppkey,
        "s_name": np.array([f"Supplier#{k:09d}" for k in suppkey.tolist()]),
        "s_nationkey": rng.integers(0, len(NATIONS), n_supp),
    }
    seq = np.arange(1, n_ord + 1)
    orderkey = ((seq >> 3) << 5) | (seq & 7)
    orderdate = rng.integers(ORDERDATE_LO, ORDERDATE_HI + 1, n_ord)
    counts = _line_counts(rng, n_ord, n_line)
    of_order = np.repeat(np.arange(n_ord), counts)
    shipdate = orderdate[of_order] + rng.integers(1, 122, n_line)
    lineitem = {
        "l_orderkey": orderkey[of_order],
        "l_suppkey": rng.integers(1, n_supp + 1, n_line),
        "l_commitdate": (orderdate[of_order]
                         + rng.integers(30, 91, n_line)).astype(np.int32),
        "l_receiptdate": (shipdate
                          + rng.integers(1, 31, n_line)).astype(np.int32),
    }
    # an order's lines lie together: its count of F lines is one reduceat
    shipped = np.add.reduceat((shipdate <= CURRENTDATE).astype(np.int64),
                              np.cumsum(counts) - counts)
    orders = {
        "o_orderkey": orderkey,
        "o_orderstatus": np.where(shipped == counts, "F",
                                  np.where(shipped == 0, "O", "P")),
    }
    return {"lineitem": lineitem, "orders": orders, "supplier": supplier,
            "nation": nation}
