"""What the readers of the grace aggregation's counts share beside
`join_phases.total`: a count over a statement's phases that is a number, 0
included, wherever the statement replayed a spilled aggregation at all
(`agg_replay` recorded), and `None`, never 0, where it did not - a program
from before the aggregation had phases, or a statement whose aggregates all
fit one group table."""

from __future__ import annotations

from typing import Callable, Optional

from benchmark import phase_summaries as ps


def count(field: str, *names: str) -> Callable[[dict], Optional[float]]:
    """A `per_statement` for `phase_summaries.mean`: the sum of `field`
    (`n`, or `items`, which a summary leaves out where every occurrence
    counted nothing) over the phases of these names, every thread role."""
    def per_statement(summary: dict) -> Optional[float]:
        found = {name for _, name, _ in ps.phases(summary)}
        if "agg_replay" not in found:
            return None
        return float(sum(agg.get(field, 0) for _, name, agg
                         in ps.phases(summary) if name in names))
    return per_statement
