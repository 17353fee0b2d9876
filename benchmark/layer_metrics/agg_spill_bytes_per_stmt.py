"""scheduler + operators: bytes a statement's aggregates write to spill
files (`items` of `agg_spill_write`: one occurrence a page appended by an
aggregate's spiller, `items` its length on disk, header included;
`spiller.py`, `SpillFile.append`; a join's files record nothing and do not
count), all threads, mean per statement. Repeats exactly for one text and
one seed."""

from benchmark import agg_phases, phase_summaries as ps


def read(run):
    return ps.mean(run, agg_phases.count("items", "agg_spill_write"))
