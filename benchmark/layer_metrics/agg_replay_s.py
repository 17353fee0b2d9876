"""scheduler + operators: seconds a statement's aggregates spend reading
their spilled partitions back and merging them on the device (`busy_s` of
`agg_replay`: `exec/runtime.py`, `finalize_leaf` - one occurrence a leaf
partition begun, round its page reads, its merge programs and the reads of
their group counts; and of `agg_repartition`: the split of a leaf whose
groups outnumbered the ceiling, by the next hash bits), all threads, mean
per statement."""

from benchmark import join_phases, phase_summaries as ps


def read(run):
    return ps.mean(run, join_phases.total(
        "busy_s", names=("agg_replay", "agg_repartition")))
