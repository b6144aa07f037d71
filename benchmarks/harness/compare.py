"""The comparison that decides ``correct``: rows of the system under test
against the plain reference, exactly, so every number compared has the
limit 0."""

import decimal
import sys
from typing import Dict, List, Sequence


def normal_value(v):
    """A decimal becomes ``(unscaled, scale)``; strings and integers stay."""
    if isinstance(v, decimal.Decimal):
        sign, digits, exponent = v.as_tuple()
        if not isinstance(exponent, int):
            raise ValueError(f"not a finite decimal: {v!r}")
        unscaled = int("".join(map(str, digits)) or "0")
        if exponent > 0:
            unscaled, exponent = unscaled * 10 ** exponent, 0
        return (-unscaled if sign else unscaled, -exponent)
    if isinstance(v, bool) or v is None or isinstance(v, (int, str)):
        return v
    if hasattr(v, "item"):  # a numpy scalar out of a payload
        return normal_value(v.item())
    raise TypeError(f"the reference has no form for {type(v).__name__}: {v!r}")


def normal_rows(rows: Sequence[Sequence]) -> List[tuple]:
    return [tuple(normal_value(v) for v in row) for row in rows]


def first_difference(want: List[tuple], got: List[tuple]) -> str:
    if len(want) != len(got):
        return f"{len(want)} reference rows against {len(got)}"
    for i, (w, g) in enumerate(zip(want, got)):
        if w != g:
            return f"row {i}: reference {w!r} against {g!r}"
    return "equal"


def compared_numbers(records: Sequence, fallbacks: Sequence[str]) -> Dict:
    """Every number ``correct`` rests on, beside its limit: the answers of the
    window held to the reference, those that differ from it, those that
    raised or never came, and the statements' fallback reports."""
    return {
        "answers_compared": {"value": len(records), "at_least": 1},
        "answers_wrong": {"value": sum(r.differs for r in records), "limit": 0},
        "answers_missing": {
            "value": sum(not r.ok and not r.differs for r in records),
            "limit": 0},
        "fallback_reports": {"value": len(fallbacks), "limit": 0}}


def is_correct(compared: Dict) -> bool:
    return all(n["value"] <= n["limit"] if "limit" in n
               else n["value"] >= n["at_least"] for n in compared.values())


def print_compared(compared: Dict, correct: bool) -> None:
    """The run's last lines on standard error."""
    for name, n in compared.items():
        limit = (f"limit {n['limit']}" if "limit" in n
                 else f"at least {n['at_least']}")
        print(f"[correct] {name} {n['value']} ({limit})", file=sys.stderr)
    print(f"[correct] {correct}", file=sys.stderr, flush=True)
