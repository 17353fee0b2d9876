"""scheduler + operators: seconds from the scheduler's entry until the
last task-create POST has returned (`schedule`, on the coordinator's query
thread), mean per statement."""

from benchmark import phase_summaries as ps


def read(run):
    return ps.mean(run, ps.total("busy_s", names=("schedule",)))
