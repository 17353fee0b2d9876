"""scheduler + operators: seconds a statement spends reading splits
(`scan_read`: `read_split` in `exec/runtime.py`, on the `scan-prefetch`
thread), mean per statement, from the engine's own phase summaries."""

from benchmark import phase_summaries as ps


def read(run):
    return ps.mean(run, ps.total("busy_s", names=("scan_read",)))
