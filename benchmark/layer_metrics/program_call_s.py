"""scheduler + operators: seconds a statement spends inside calls of
compiled programs (`program_call:<node kind>`: `exec/programs.wrap`), all
threads, mean per statement. The call returns when the work is enqueued."""

from benchmark import phase_summaries as ps


def read(run):
    return ps.mean(run, ps.total("busy_s", prefix="program_call:"))
