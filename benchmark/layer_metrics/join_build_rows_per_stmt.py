"""lifecycle / planner: rows a statement's sorted join builds held live
(`items` of `join_build_table`: one occurrence a build, no time of its own,
the row count the host reads with the build's other statistics;
`exec/runtime.py`, `_observe_build_table`), all threads, mean per
statement. Which side builds is the planner's choice: a plan that built
inventory would read 2.35e7 at SF1. `None` for a statement with no sorted
build."""

from benchmark import phase_items, phase_summaries as ps


def read(run):
    return ps.mean(run, phase_items.items("join_build_table"))
