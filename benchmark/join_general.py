"""What the readers of the join's general path share (a build that fans out:
`probe_counts`, `probe_expand`, one read of `total` a batch): a count over a
statement's phases that is a number, 0 included, wherever the statement had
a probe batch on that path at all (`host_sync:join_total` recorded), and
`None`, never 0, where it had none - a statement whose builds are all
`unique`, a statement with no join, a program from before the join had
phases. An engine without `join_expand` (the parent of PR 35) runs the path
and records none of the three phases below: its readers give `None` too."""

from __future__ import annotations

from typing import Callable, Optional

from benchmark import join_phases, phase_summaries as ps

batches = join_phases.total("n", names=("host_sync:join_total",))


def count(*names: str) -> Callable[[dict], Optional[float]]:
    """A `per_statement` for `phase_summaries.mean`: the sum of `items`
    (which a summary leaves out where every occurrence counted nothing)
    over the phases of these names, every thread role."""
    def per_statement(summary: dict) -> Optional[float]:
        found = [(name, agg) for _, name, agg in ps.phases(summary)]
        if not batches(summary) or all(n != "join_expand" for n, _ in found):
            return None
        return float(sum(agg.get("items", 0) for name, agg in found
                         if name in names))
    return per_statement
