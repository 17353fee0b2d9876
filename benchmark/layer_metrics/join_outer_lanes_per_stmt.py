"""scheduler + operators: lanes of the batches a statement's LEFT and FULL
joins handed on whole (`items` of `join_outer`: one occurrence a batch, no
time of its own - a single-match probe's dense emit, the general path's
NULL-extended rows; `exec/runtime.py`, `_outer_phase`), all threads, mean
per statement. `None` for a statement without such a join."""

from benchmark import phase_items, phase_summaries as ps


def read(run):
    return ps.mean(run, phase_items.items("join_outer"))
