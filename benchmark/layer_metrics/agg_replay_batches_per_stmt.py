"""scheduler + operators: batches a statement's aggregates merge while they
replay their leaf partitions, one program call each (`items` of `agg_replay`:
a leaf's pages come back packed into whole batches of one capacity,
`spiller.py`, `pack_pages`, so a leaf of r rows is ceil(r / capacity) of
them), all threads, mean per statement. Repeats exactly for one text and one
seed."""

from benchmark import agg_phases, phase_summaries as ps


def read(run):
    return ps.mean(run, agg_phases.count("items", "agg_replay"))
