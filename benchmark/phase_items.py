"""What the readers of `sf1_q72`'s two phases share: a sum of `items` over a
statement's occurrences of one phase (`join_build_table`: a sorted build's
live rows; `join_outer`: the lanes of a batch a LEFT or FULL join handed on
whole), every thread role, that is `None`, never 0, for a statement that
recorded the phase not once - a statement without such a join, or a
program from before the phase."""

from __future__ import annotations

from typing import Callable, Optional

from benchmark import phase_summaries as ps


def items(name: str) -> Callable[[dict], Optional[float]]:
    """A `per_statement` for `phase_summaries.mean`: the sum of `items`
    (which a summary leaves out where every occurrence counted nothing)
    over the phases named `name`."""
    def per_statement(summary: dict) -> Optional[float]:
        found = [agg for _, n, agg in ps.phases(summary) if n == name]
        return float(sum(agg.get("items", 0) for agg in found)) if found else None
    return per_statement
