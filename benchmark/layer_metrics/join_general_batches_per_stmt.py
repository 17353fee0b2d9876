"""scheduler + operators: probe batches of a statement that took the join's
general path (`n` of `host_sync:join_total`: that path reads `total` exactly
once a batch and the single-match path never; `exec/runtime.py`,
`_JoinProber.probe_finish`, `_MultiwayProber.probe_batch`), all threads, mean
per statement. Repeats exactly. `None` for a statement with no such batch."""

from benchmark import join_general, phase_summaries as ps


def read(run):
    return ps.mean(run, join_general.batches)
