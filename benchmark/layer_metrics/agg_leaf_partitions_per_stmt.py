"""scheduler + operators: leaf partitions a statement's aggregates replay
(`n` of `agg_replay`: one occurrence a leaf begun; a leaf that had to split
counts once and so does each of its children), all threads, mean per
statement. Repeats exactly for one text and one seed."""

from benchmark import agg_phases, phase_summaries as ps


def read(run):
    return ps.mean(run, agg_phases.count("n", "agg_replay"))
