"""Plain reference for TPC-H Q13: numpy and Python integers only.

Written from the statement's meaning. An order takes part in the outer join
where its comment does not match ``'%WORD1%WORD2%'``: LIKE's ``%`` matches
any run of characters, so a comment matches where WORD1 occurs and WORD2
occurs after it. The earliest WORD1 leaves WORD2 the most room, so a comment
is excluded exactly where ``i = s.find(word1)`` is ``>= 0`` and
``s.find(word2, i + len(word1))`` is ``>= 0``. A customer's ``c_count`` is the
number of its orders that take part, 0 where none does (``count(o_orderkey)``
counts no null-extended row); ``custdist`` counts the customers of each
``c_count``. The join assumes nothing of the keys: a customer key met twice
is one group whose count is doubled, as SQL says. Rows come in ``custdist
DESC, c_count DESC`` order, which is total (``c_count`` is the group).
Imports nothing of the engine.

Controls, each the same statement with one stated guarantee broken; the
comparison has to refuse every one and no run of the benchmark calls them.
Q13 has no arithmetic whose precision could be lowered (its numbers are
counts). ``control_answer`` lets the outer ``count(*)`` count the joined rows
under each ``c_count`` in place of the customers, an aggregate over an
aggregate flattened into one: the same ``c_count`` keys, so as many rows as
the answer (``benchmarks/tests/test_control.py`` finds it by its name and
asks that). The others change the number of rows, so their names keep them
out of that test's list and ``benchmarks/tests/test_q13_cell.py`` holds them
to ``not correct``: ``inner_join_answer`` (the customers with no order
leave), ``filter_after_join_answer`` (the NOT LIKE as a WHERE over the outer
join's rows, where it drops the null-extended rows: by SQL's three-valued
logic the inner join's answer), ``count_star_answer`` (``count(*)`` for
``count(o_orderkey)``: a customer with none counts 1) and
``either_word_answer`` (a comment holding either word is excluded).
"""

import numpy as np


def matches(comment: str, word1: str, word2: str) -> bool:
    """``comment LIKE '%word1%word2%'``."""
    i = comment.find(word1)
    return i >= 0 and comment.find(word2, i + len(word1)) >= 0


def _kept(orders: dict, binding: dict, either_word: bool = False
          ) -> np.ndarray:
    w1, w2 = binding["word1"], binding["word2"]
    if either_word:
        hit = [w1 in s or w2 in s for s in orders["o_comment"].tolist()]
    else:
        hit = [matches(s, w1, w2) for s in orders["o_comment"].tolist()]
    return ~np.array(hit, dtype=bool)


def customer_counts(tables: dict, binding: dict, either_word: bool = False,
                    count_star: bool = False):
    """``(c_count, joined)`` per distinct ``c_custkey``: the orders that take
    part in the join (``count(*)`` counts 1 for a customer with none when
    ``count_star``), and the rows the outer join gives the group."""
    keys, cust_rows = np.unique(tables["customer"]["c_custkey"],
                                return_counts=True)
    orders = tables["orders"]
    custkey = orders["o_custkey"][_kept(orders, binding, either_word)]
    at = np.searchsorted(keys, custkey)
    found = at < len(keys)
    found[found] = keys[at[found]] == custkey[found]
    per_key = np.bincount(at[found], minlength=len(keys)).astype(np.int64)
    c_count = per_key * cust_rows
    joined = np.where(per_key > 0, c_count, cust_rows)
    if count_star:
        c_count = joined
    return c_count, joined


def _ordered(c_count: np.ndarray, weights=None) -> list:
    values, at = np.unique(c_count, return_inverse=True)
    dist = np.bincount(at, weights=weights, minlength=len(values))
    rows = [(int(v), int(d)) for v, d in zip(values.tolist(), dist.tolist())]
    return sorted(rows, key=lambda r: (-r[1], -r[0]))


def answer(tables: dict, binding: dict) -> list:
    return _ordered(customer_counts(tables, binding)[0])


def control_answer(tables: dict, binding: dict) -> list:
    c_count, joined = customer_counts(tables, binding)
    return _ordered(c_count, weights=joined)


def inner_join_answer(tables: dict, binding: dict) -> list:
    c_count = customer_counts(tables, binding)[0]
    return _ordered(c_count[c_count > 0])


# the outer join on the key alone, then the NOT LIKE over its rows: a
# null-extended row's comment is null, so NOT LIKE is null and drops it, and
# a customer whose rows all go leaves no group: the inner join's rows
filter_after_join_answer = inner_join_answer


def count_star_answer(tables: dict, binding: dict) -> list:
    return _ordered(customer_counts(tables, binding, count_star=True)[0])


def either_word_answer(tables: dict, binding: dict) -> list:
    return _ordered(customer_counts(tables, binding, either_word=True)[0])
