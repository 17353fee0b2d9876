"""scheduler + operators: lanes of the batches a statement's windows sort
(`items` of `window_lanes`: one occurrence a Window node, no time of its
own, the capacity of the batch `_execute_window` concatenates from its
input - the sum of the input batches' capacities, however few rows they
hold; `exec/runtime.py`), all threads, mean per statement. `None` for a
statement without a window."""

from benchmark import phase_items, phase_summaries as ps


def read(run):
    return ps.mean(run, phase_items.items("window_lanes"))
