"""lifecycle / planner: share of a statement's probe batches that took the
single-match path, the one a build whose keys the planner proved `unique`
(`plan/builder.py`, `_derives_unique`) opens: 100 x (`n` of `join_probe` less
`n` of `host_sync:join_total`) over `n` of `join_probe`, all threads, mean per
statement. The general path reads `total` exactly once a batch and the
single-match path never (`tests/test_join_phases.py`). A statement that
probed and took the general path every time reads 0, the worst case of a
`better: higher` metric; only one that recorded no `join_probe` (no join in
it, or a program from before the join had phases) has nothing to read."""

from benchmark import join_phases, phase_summaries as ps

_probes = join_phases.total("n", names=("join_probe",))
_general = join_phases.total("n", names=("host_sync:join_total",))


def _per_statement(summary):
    probes = _probes(summary)
    if not probes:
        return None
    return 100.0 * (probes - (_general(summary) or 0)) / probes


def read(run):
    return ps.mean(run, _per_statement)
