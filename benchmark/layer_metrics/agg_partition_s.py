"""scheduler + operators: seconds a statement's aggregates spend routing
their input to spill files (`busy_s` of `agg_partition`: `exec/runtime.py`,
`_execute_aggregate`'s `grace_ingest` - one occurrence an aggregate that went
grace, from the first pull of its input to the last page written, so the
wait on the fragment upstream is inside it and `self_s` is the host's own
share: the chain program, the read of the keys, the hash, the page appends),
all threads, mean per statement."""

from benchmark import join_phases, phase_summaries as ps


def read(run):
    return ps.mean(run, join_phases.total("busy_s", names=("agg_partition",)))
