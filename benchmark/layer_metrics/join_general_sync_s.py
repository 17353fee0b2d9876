"""scheduler + operators: seconds a statement's threads wait on the general
path's two reads a batch (`busy_s` of `host_sync:join_total` and
`host_sync:join_overflow`): where the device's time in the counts and expand
programs shows on the host. They count in `join_sync_s` and `host_sync_s`
too. `None` for a statement with no batch on that path."""

from benchmark import join_phases, phase_summaries as ps


def read(run):
    return ps.mean(run, join_phases.total("busy_s", names=(
        "host_sync:join_total", "host_sync:join_overflow")))
