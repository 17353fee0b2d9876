"""scheduler + operators: seconds the `task` threads - the ones that launch
the programs - were starved by their producers (`window_wait` + `scan_wait`
on the `task` role), mean per statement."""

from benchmark import phase_summaries as ps


def read(run):
    return ps.mean(run, ps.total("busy_s", role="task",
                                 names=("window_wait", "scan_wait")))
