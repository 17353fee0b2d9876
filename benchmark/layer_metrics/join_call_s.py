"""scheduler + operators: seconds a statement spends inside calls of the
joins' compiled programs (`program_call:HashJoin`, `program_call:MultiwayJoin`:
build, probe, counts, expand), all threads, mean per statement. The call
returns when the work is enqueued."""

from benchmark import join_phases, phase_summaries as ps


def read(run):
    return ps.mean(run, join_phases.total(
        "busy_s", names=("program_call:HashJoin", "program_call:MultiwayJoin")))
