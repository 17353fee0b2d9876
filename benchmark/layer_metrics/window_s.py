"""scheduler + operators: seconds a statement's windows take (`busy_s` of
`window_collect` - `exec/runtime.py`, `_execute_window`: from the first pull
of the window's input to the concatenated batch, the wait on the fragment
upstream inside it - plus `busy_s` of `program_call:Window`, the sort and
the window functions), all threads, mean per statement. `None` for a
statement without `window_collect`: no window, or a program from before the
phase."""

from benchmark import join_phases, phase_summaries as ps

_PHASES = ("window_collect", "program_call:Window")


def _per_statement(summary):
    if join_phases.total("n", names=("window_collect",))(summary) is None:
        return None
    return join_phases.total("busy_s", names=_PHASES)(summary)


def read(run):
    return ps.mean(run, _per_statement)
