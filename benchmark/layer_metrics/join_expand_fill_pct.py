"""scheduler + operators: how much of what a statement's general path
gathered is rows: 100 x `items` of `join_expand` (the batches' totals) over
`items` of `join_expand_lanes` (chunks handed on x `out_cap`), all threads,
per statement, then the mean. **0.0, a number**, where the batches expanded
to nothing (every batch still gathers one chunk); `None` for a statement
with no batch on that path."""

from benchmark import join_general, phase_summaries as ps

_rows = join_general.count("join_expand")
_lanes = join_general.count("join_expand_lanes")


def _per_statement(summary):
    lanes = _lanes(summary)
    return 100.0 * _rows(summary) / lanes if lanes else None


def read(run):
    return ps.mean(run, _per_statement)
