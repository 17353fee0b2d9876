"""scheduler + operators: lanes of the batches a statement's GROUPING SETS
aggregates absorb (`items` of `grouping_set`: one occurrence a set's
aggregate - its final step behind the exchange - no time of its own, the
capacities of the batches it drained; `exec/runtime.py`,
`_grouping_set_lanes`), all threads, mean per statement. Nine sets a Q67
statement. `None` for a statement without grouping sets."""

from benchmark import phase_items, phase_summaries as ps


def read(run):
    return ps.mean(run, phase_items.items("grouping_set"))
