"""scheduler + operators: what the tracer itself makes per statement -
spans in the stitched trace plus those dropped at the cap, mean per
statement."""

from benchmark import phase_summaries as ps


def read(run):
    return ps.mean(run, lambda summary: summary["spans"] + summary["dropped"])
