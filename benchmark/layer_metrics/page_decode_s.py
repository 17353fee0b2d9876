"""scheduler + operators: seconds a statement's exchange consumers - the tasks
and the coordinator's root stream - spend turning a page back into host
planes: header, zstd, padding, dictionaries (`busy_s` of `page_decode`:
`serde.deserialize_batch`), all threads, mean per statement. `None` for a
statement that recorded no `page_decode`: a program from before the page
path had phases."""

from benchmark import join_phases, phase_summaries as ps


def read(run):
    return ps.mean(run, join_phases.total("busy_s", names=("page_decode",)))
