"""Helpers shared by the plain references in `reference/` and by the
comparison: dates as days since 1970, exact decimals from unscaled ints."""

from __future__ import annotations

import decimal

import numpy as np


def day(iso: str) -> int:
    return int(np.datetime64(iso, "D").astype(np.int64))


def date_str(days) -> str:
    return str(np.datetime64(int(days), "D"))


def dec(unscaled, scale: int) -> decimal.Decimal:
    return decimal.Decimal(int(unscaled)).scaleb(-scale)


def cents(text: str) -> int:
    """'0.05' -> 5: a scale-2 decimal literal as its unscaled value."""
    return int(decimal.Decimal(text).scaleb(2))


def dec_from_float(x, scale: int) -> decimal.Decimal:
    """What a floating-point engine would print for a decimal(…, scale)."""
    q = decimal.Decimal(1).scaleb(-scale)
    return decimal.Decimal(repr(float(x))).quantize(q, decimal.ROUND_HALF_UP)
