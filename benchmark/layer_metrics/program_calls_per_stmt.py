"""scheduler + operators: calls of compiled programs a statement makes
(`n` of `program_call:*`), all threads, mean per statement. Repeats exactly
for one text at one scale."""

from benchmark import phase_summaries as ps


def read(run):
    return ps.mean(run, ps.total("n", prefix="program_call:"))
