"""scheduler + operators: seconds a statement spends building its joins'
tables (`join_build`: `exec/runtime.py`, `_join_with_spill` - from the first
pull of the build stream to the sorted `BuildTable`, the wait on the
upstream fragment's pages included), all threads, mean per statement."""

from benchmark import join_phases, phase_summaries as ps


def read(run):
    return ps.mean(run, join_phases.total("busy_s", names=("join_build",)))
