"""What the four readers of the join's phases share: a sum over the phases a
reader selects that is `None`, never 0, for a statement that recorded none
of them - a program from before the join had phases, or a statement with no
join in it."""

from __future__ import annotations

from typing import Callable, Optional

from benchmark import phase_summaries as ps


def total(field: str, prefix: str = "", names=None) -> Callable[[dict], Optional[float]]:
    """A `per_statement` for `phase_summaries.mean`: the sum of `field` over
    the phases, of every thread role, whose name starts with `prefix` and is
    one of `names` (where given); `None` where no phase is selected."""
    def per_statement(summary: dict) -> Optional[float]:
        found = [agg[field] for _, name, agg in ps.phases(summary, prefix=prefix)
                 if names is None or name in names]
        return sum(found) if found else None
    return per_statement
