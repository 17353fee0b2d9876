"""scheduler + operators: seconds a statement's exchange consumers spend
putting a page's planes on the device (`busy_s` of `page_upload`:
`serde.deserialize_batch`), all threads, mean per statement. `None` for a
statement that recorded no `page_upload`: a program from before the page
path had phases."""

from benchmark import join_phases, phase_summaries as ps


def read(run):
    return ps.mean(run, join_phases.total("busy_s", names=("page_upload",)))
