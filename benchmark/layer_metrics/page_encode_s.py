"""scheduler + operators: seconds a statement's sinks spend making pages of the
host copies: the live mask, the buffers, the header, zstd (`busy_s` of
`page_encode`: `serde.serialize_batch`, inside `host_sync:sink_serialize`),
all threads, mean per statement. `None` for a statement that recorded no
`page_encode`: a program from before the page path had phases."""

from benchmark import join_phases, phase_summaries as ps


def read(run):
    return ps.mean(run, join_phases.total("busy_s", names=("page_encode",)))
