"""scheduler + operators: rows a statement's general-path probe batches
expanded to (`items` of `join_expand`: one occurrence a batch, no time of
its own, `items` the `total` the host has just read; `exec/runtime.py`,
`_expand_phases`), all threads, mean per statement. Repeats exactly for one
seed. `None` for a statement with no batch on that path."""

from benchmark import join_general, phase_summaries as ps


def read(run):
    return ps.mean(run, join_general.count("join_expand"))
