"""The comparison that decides ``correct``: rows of the system under test
against the plain reference, exactly."""

import decimal
from typing import List, Sequence


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
