"""scheduler + operators: seconds the coordinator spends fetching and
stitching the tasks' span dumps before the statement's stream ends
(`trace_collect`): what the tracer costs on the critical path."""

from benchmark import phase_summaries as ps


def read(run):
    return ps.mean(run, ps.total("busy_s", names=("trace_collect",)))
