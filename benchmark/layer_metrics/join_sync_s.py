"""scheduler + operators: seconds a statement's threads spend in the join
path's device-to-host reads (`host_sync:join_build_rows`, `join_total`,
`join_overflow`, `join_selectivity`: `exec/runtime.py`, `_JoinProber`;
`join_output_rows`: `_merging_output` behind a join), mean per statement.
They count in `host_sync_s` too."""

from benchmark import join_phases, phase_summaries as ps


def read(run):
    return ps.mean(run, join_phases.total("busy_s", prefix="host_sync:join_"))
