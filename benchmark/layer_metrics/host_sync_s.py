"""scheduler + operators: seconds a statement's task threads spend in the
deliberate device-to-host reads on its path (`host_sync:<site>`: the
aggregate's group-count confirms, the sinks' row count and page
serialization), mean per statement."""

from benchmark import phase_summaries as ps


def read(run):
    return ps.mean(run, ps.total("busy_s", prefix="host_sync:"))
