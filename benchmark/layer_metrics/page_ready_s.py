"""scheduler + operators: seconds a statement's sinks wait for the device to
finish a batch before they copy it (`busy_s` of `page_ready`:
`serde.serialize_batch` as `server/worker.py`'s sinks call it, inside
`host_sync:sink_serialize`), all threads, mean per statement. `None` for a
statement that recorded no `page_ready`: a program from before the page path
had phases."""

from benchmark import join_phases, phase_summaries as ps


def read(run):
    return ps.mean(run, join_phases.total("busy_s", names=("page_ready",)))
