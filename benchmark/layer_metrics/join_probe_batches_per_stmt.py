"""scheduler + operators: probe batches a statement's joins take (`n` of
`join_probe`: one occurrence a batch, however many chunks it yields), all
threads, mean per statement. Repeats exactly for one text and one seed."""

from benchmark import join_phases, phase_summaries as ps


def read(run):
    return ps.mean(run, join_phases.total("n", names=("join_probe",)))
