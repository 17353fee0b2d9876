"""scheduler + operators: seconds a statement spends stacking batches into
windows (`window_stack`: `exec/fragment_jit._flush`, on the
`fragment-window-producer` thread), mean per statement."""

from benchmark import phase_summaries as ps


def read(run):
    return ps.mean(run, ps.total("busy_s", names=("window_stack",)))
