"""scheduler + operators: probe rows of a statement whose candidates passed
the counting pass's scan (`max_fanout_scan`, 8), so the count fell back to
the hash-match superset (`items` of `join_fanout_overflow`: one occurrence a
batch that had such rows, no time of its own; beside the process counter
`join_fanout_overflow_rows`), all threads, mean per statement. **0.0, a
number**, where a statement had general batches and none overflowed; `None`
for a statement with no batch on that path."""

from benchmark import join_general, phase_summaries as ps


def read(run):
    return ps.mean(run, join_general.count("join_fanout_overflow"))
